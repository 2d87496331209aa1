"""The churn between two backups of one volume, from a shape's
``history``:

    {"rewrite_small_share": 0.05,
     "append": {"path": "mid/m00.bin", "bytes": N}}

That share of the volume's small files (the caller says which they
are; at least one, drawn from the seed) is rewritten with new random
bytes at its size, and ``bytes`` random bytes are appended to ``path``.
A rewrite keeps the file's size, so only its mtime tells a backup that
it changed; the append grows one file past what its first backup
stored.
"""

from __future__ import annotations

from pathlib import Path

import numpy as np

from benchmark.reference.blobid import file_sha256


def apply(root: Path, files: dict[str, int], small: list[str],
          history: dict, seed: int,
          ) -> tuple[dict[str, int], dict[str, str]]:
    """Changes the volume under ``root`` in place. Returns the volume's
    files afterwards ({relative path: bytes}) and, for every file it
    touched, the SHA-256 of the state it had before."""
    rng = np.random.default_rng([seed, 0xC7])
    small = sorted(small)
    n = max(1, round(len(small) * float(history["rewrite_small_share"]))) \
        if small else 0
    picked = [small[i] for i in
              sorted(rng.permutation(len(small))[:n].tolist())]
    after, before = dict(files), {}
    for rel in picked:
        path = root / rel
        before[rel] = file_sha256(path)
        path.write_bytes(rng.bytes(files[rel]))
    grow = history.get("append")
    if grow:
        path = root / grow["path"]
        before[grow["path"]] = file_sha256(path)
        with open(path, "ab") as f:
            f.write(rng.bytes(int(grow["bytes"])))
        after[grow["path"]] += int(grow["bytes"])
    return after, before
