"""What a run of the scan cell is made from, by the seed alone: the
history a repository holds before the first real backup, and the states
of the one file that is backed up into it night after night. The driver
writes them; a check child makes them again and keeps nothing of the
driver's.

The history is ``count`` blobs of ``nbytes`` random bytes each, blob
``j`` being bytes ``[j * nbytes, (j + 1) * nbytes)`` of one stream of
the seed, stored through the program's own writer
(``Repository.add_blobs`` in batches, ``flush`` every
``PENDING_INDEX_LIMIT`` blobs, so that an index object holds what a long
first backup's or a prune's consolidation's holds) under the format's
ids, and one snapshot of another path that names them all: a repository
a prune would leave alone. Nothing here knows the pack or the index
format; the tree is the JSON ``backup_check.snapshot_files`` reads.

The file is a configuration's one ``repeat_half`` file: two equal
halves, each ``keep`` bytes that stay as state 0 wrote them and then
``fresh`` bytes that operation ``i`` finds new (``R_i``: the same bytes
in both halves, so the second half still repeats the first).
"""

from __future__ import annotations

import json
import os
import time
from pathlib import Path

import numpy as np

from benchmark.reference import blobid

#: blobs a call of ``add_blobs`` is given while the history is written
BATCH = 4096
HISTORY_HOST, HISTORY_PATH = "bench-history", "/history"


def history_blobs(seed: int, count: int, nbytes: int
                  ) -> tuple[memoryview, list[str]]:
    """(the history's bytes laid end to end, its ids in order)."""
    raw = memoryview(np.random.default_rng([seed, 0x41]).integers(
        0, 256, count * nbytes, dtype=np.uint8))
    return raw, [blobid.blob_id(raw[j * nbytes: (j + 1) * nbytes])
                 for j in range(count)]


def history_sample(seed: int, count: int, sample: int) -> list[int]:
    """Which history blobs are read back, drawn from the seed."""
    rng = np.random.default_rng([seed, 0x53])
    return sorted(rng.permutation(count)[:sample].tolist())


def write_history(repo, seed: int, count: int, nbytes: int) -> dict:
    """The history into an initialised repository; returns what it
    wrote and how long each part took."""
    t0 = time.monotonic()
    raw, ids = history_blobs(seed, count, nbytes)
    t1 = time.monotonic()
    per_index = repo.PENDING_INDEX_LIMIT
    entries = []
    for a in range(0, count, per_index):
        b = min(a + per_index, count)
        for lo in range(a, b, BATCH):
            repo.add_blobs("data", [
                (ids[j], raw[j * nbytes: (j + 1) * nbytes])
                for j in range(lo, min(lo + BATCH, b))])
        repo.flush()
        entries.append({
            "name": f"blobs-{a // per_index:04d}.bin", "type": "file",
            "mode": 0o600, "mtime_ns": 0, "uid": 0, "gid": 0,
            "size": (b - a) * nbytes, "content": ids[a:b]})
    tree = json.dumps({"entries": entries}, sort_keys=True).encode()
    tree_id = blobid.blob_id(tree)
    repo.add_blob("tree", tree_id, tree)
    repo.flush()
    snap = repo.save_snapshot({
        "hostname": HISTORY_HOST, "paths": [HISTORY_PATH], "tags": [],
        "tree": tree_id, "parent": None})
    return {"blobs": count, "index_objects": len(entries),
            "tree": tree_id, "snapshot": snap,
            "ids_s": round(t1 - t0, 3),
            "write_s": round(time.monotonic() - t1, 3)}


def layout(shape: dict, fresh: int) -> tuple[str, int, int]:
    """(the file's path, a half's bytes, the bytes of it that stay)."""
    (spec,) = shape["files"]
    size = int(spec["bytes"])
    if not spec.get("repeat_half") or size % 2 or not 0 < fresh < size // 2:
        raise ValueError("the scan cell's volume is one repeat_half file "
                         "with room for its fresh bytes in each half")
    return spec["path"], size // 2, size // 2 - fresh


def _random(seed: int, stream: int, i: int, n: int) -> np.ndarray:
    return np.random.default_rng([seed, stream, i]).integers(
        0, 256, n, dtype=np.uint8)


def fresh_bytes(seed: int, i: int, n: int) -> np.ndarray:
    """``R_i``: what operation ``i`` finds new in each half."""
    return _random(seed, 0xD5, i, n)


def file_states(shape: dict, fresh: int, seed: int, numbers):
    """The whole file as each of the operations ``numbers`` meets it,
    one after another in ONE buffer: a state is good until the next is
    asked for."""
    _, half, keep = layout(shape, fresh)
    out = np.empty(2 * half, np.uint8)
    out[:keep] = _random(seed, 0xB0, 0, keep)
    out[half: half + keep] = out[:keep]
    for i in numbers:
        out[keep:half] = fresh_bytes(seed, i, fresh)
        out[half + keep:] = out[keep:half]
        yield out


def write_volume(root: Path, shape: dict, fresh: int, seed: int
                 ) -> dict[str, int]:
    """State 0 under ``root``; returns {relative path: bytes}."""
    rel, half, keep = layout(shape, fresh)
    root.mkdir(parents=True)
    (root / rel).parent.mkdir(parents=True, exist_ok=True)
    kept, new = _random(seed, 0xB0, 0, keep), fresh_bytes(seed, 0, fresh)
    with open(root / rel, "wb") as f:
        for _half in range(2):
            f.write(kept)
            f.write(new)
    return {rel: 2 * half}


def churn(root: Path, shape: dict, fresh: int, seed: int, i: int) -> None:
    """The step before operation ``i``: ``R_i`` over ``R_(i-1)`` in both
    halves, in place. The file keeps its size; its mtime moves."""
    rel, half, keep = layout(shape, fresh)
    new = memoryview(fresh_bytes(seed, i, fresh))
    fd = os.open(root / rel, os.O_WRONLY)
    try:
        for off in (keep, half + keep):
            done = 0
            while done < fresh:
                done += os.pwrite(fd, new[done:], off + done)
    finally:
        os.close(fd)
