"""Seeded volumes from a configuration's ``shape``.

The SET of file sizes is fixed by the shape (its small-file sizes come
from the shape's own ``size_seed``), so every ``--seed`` does the same
amount of work on the same sizes; the seed decides the bytes and which
path gets which size. A shape is data:

    {"files": [{"path": "big.bin", "bytes": N, "repeat_half": true}, ...],
     "small": {"count": n, "lo": bytes, "hi": bytes, "dirs": d,
               "size_seed": k}}
"""

from __future__ import annotations

from pathlib import Path

import numpy as np


def small_sizes(small: dict) -> list[int]:
    """log-uniform sizes in [lo, hi], the same list for every run seed."""
    rng = np.random.default_rng(int(small["size_seed"]))
    lo, hi = np.log(small["lo"]), np.log(small["hi"])
    return np.exp(rng.uniform(lo, hi, int(small["count"]))) \
        .astype(np.int64).tolist()


def plan(shape: dict, seed: int) -> list[tuple[str, int, bool]]:
    """[(relative path, bytes, repeat_half)] — sizes from the shape,
    their assignment to the small-file paths permuted by the seed."""
    out = [(f["path"], int(f["bytes"]), bool(f.get("repeat_half")))
           for f in shape.get("files", [])]
    small = shape.get("small")
    if small:
        sizes = small_sizes(small)
        order = np.random.default_rng([seed, 0x5A]).permutation(len(sizes))
        dirs = int(small.get("dirs", 1))
        for i, j in enumerate(order.tolist()):
            out.append((f"small/d{i % dirs:02d}/f{i:05d}", sizes[j], False))
    return out


def write(root: Path, shape: dict, seed: int) -> dict[str, int]:
    """Materialize the volume under ``root``; returns {rel path: bytes}.
    Random bytes (incompressible); a ``repeat_half`` file's second half
    repeats its first, so dedup has exactly half of it to find."""
    rng = np.random.default_rng([seed, 0xB0])
    root.mkdir(parents=True)
    made: dict[str, int] = {}
    seen_dirs: set = set()
    for rel, n, repeat in plan(shape, seed):
        p = root / rel
        if p.parent not in seen_dirs:
            p.parent.mkdir(parents=True, exist_ok=True)
            seen_dirs.add(p.parent)
        with open(p, "wb") as f:
            if repeat:
                half = n // 2
                uniq = rng.bytes(half)
                f.write(uniq)
                f.write(uniq[: n - half])
                if n - half > half:
                    f.write(rng.bytes(n - 2 * half))
            else:
                f.write(rng.bytes(n))
        made[rel] = n
    return made
