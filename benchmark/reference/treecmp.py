"""Two directory trees compared, with ``os`` and ``hashlib`` alone (the
latter through ``reference/blobid.py``'s ``file_sha256``): the
plain reference a restored volume is held to.

``compare(source, restored)`` walks both and returns the relative paths
that are ``missing`` from the restored tree, ``extra`` in it, and of
those in both the ones that differ in kind or size (``size``), in
content (``content``: the SHA-256 of a regular file, the target of a
symlink) or in mode or mtime (``meta``; a symlink has no mode of its
own worth holding, directories are compared too, the two roots are
not: a restore is handed its root), with the number of entries
``compared`` and the restored files' digests (``digests``).
"""

from __future__ import annotations

import os
import stat

from benchmark.reference.blobid import file_sha256


def entries(root) -> dict[str, os.stat_result]:
    """{relative path: lstat} of everything under ``root``."""
    out = {}
    root = os.fspath(root)
    for dirpath, dirnames, filenames in os.walk(root):
        for name in dirnames + filenames:
            full = os.path.join(dirpath, name)
            out[os.path.relpath(full, root)] = os.lstat(full)
    return out


def compare(source, restored) -> dict:
    want, got = entries(source), entries(restored)
    out = {"missing": sorted(set(want) - set(got)),
           "extra": sorted(set(got) - set(want)),
           "size": [], "content": [], "meta": [], "compared": 0,
           "digests": {}}
    for rel in sorted(set(want) & set(got)):
        a, b = want[rel], got[rel]
        out["compared"] += 1
        if stat.S_IFMT(a.st_mode) != stat.S_IFMT(b.st_mode):
            out["size"].append(rel)
            continue
        if stat.S_ISLNK(a.st_mode):
            if os.readlink(os.path.join(source, rel)) != \
                    os.readlink(os.path.join(restored, rel)):
                out["content"].append(rel)
            continue
        if stat.S_ISREG(a.st_mode):
            if a.st_size != b.st_size:
                out["size"].append(rel)
                continue
            digest = file_sha256(os.path.join(restored, rel))
            out["digests"][rel] = digest
            if digest != file_sha256(os.path.join(source, rel)):
                out["content"].append(rel)
        if a.st_mode != b.st_mode or a.st_mtime_ns != b.st_mtime_ns:
            out["meta"].append(rel)
    return out
