"""Blob-id construction of the repository format, from hashlib alone.

    id(blob) = SHA-256("VMRK1" || le64(len) || leaf_0 || ... || leaf_k)
    leaf_i   = SHA-256(blob[4096*i : 4096*(i+1)])

Written from the format's description (the program's own copy is
``volsync_tpu/repo/blobid.py``; this file imports nothing of it, so a
change there that alters ids shows as a failed comparison here).
"""

from __future__ import annotations

import hashlib

LEAF = 4096
DOMAIN = b"VMRK1"


def blob_id(data) -> str:
    view = memoryview(data)
    root = hashlib.sha256(DOMAIN + len(view).to_bytes(8, "little"))
    for off in range(0, max(len(view), 1), LEAF):
        root.update(hashlib.sha256(view[off: off + LEAF]).digest())
    return root.hexdigest()


def file_sha256(path, piece: int = 8 << 20) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        while True:
            buf = f.read(piece)
            if not buf:
                return h.hexdigest()
            h.update(buf)
