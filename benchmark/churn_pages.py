"""The churn of a volume whose large files are written in place, as a
database or a disk image writes them, from a cell's ``params``:

    {"page_bytes": 16384, "page_share": 0.01,
     "insert": {"path": "mid/m02.bin", "bytes": 1000}}

``page_share`` of the ``page_bytes`` pages of every named file are
rewritten in place with new random bytes at seeded page-aligned offsets
(at least one a file; not aligned to rsync's block lengths, which
follow a file's size), and ``bytes`` random bytes are inserted at the
midpoint of ``path``, so that its second half is found again only at
offsets no block boundary falls on. ``churn.py`` (small files rewritten
whole, an append) is the other kind and is not edited.
"""

from __future__ import annotations

from pathlib import Path

import numpy as np


def apply(root: Path, files: dict[str, int], paths: list[str],
          params: dict, seed: int) -> dict[str, int]:
    """Changes the volume under ``root`` in place. Returns the volume's
    files afterwards ({relative path: bytes})."""
    rng = np.random.default_rng([seed, 0xD8])
    page = int(params["page_bytes"])
    after = dict(files)
    for rel in sorted(paths):
        pages = files[rel] // page
        n = max(1, round(pages * float(params["page_share"])))
        picked = sorted(rng.permutation(pages)[:n].tolist())
        with open(root / rel, "r+b") as f:
            for i in picked:
                f.seek(i * page)
                f.write(rng.bytes(page))
    grow = params.get("insert")
    if grow:
        path = root / grow["path"]
        body = path.read_bytes()
        mid = len(body) // 2
        path.write_bytes(body[:mid] + rng.bytes(int(grow["bytes"]))
                         + body[mid:])
        after[grow["path"]] += int(grow["bytes"])
    return after
