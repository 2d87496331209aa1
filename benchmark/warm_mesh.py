"""Loading the fused mesh program at the shapes a cell's volume can
present, before the window.

The mesh hasher keys a program by (shard length, candidate capacity,
chunk capacity, eof). Which shard length a segment lands in follows the
file's size and, at a bucket's edge, the seed's bytes (the tail a
segment carries into the next), so set-up works out every one the
volume's sizes can present and runs the hasher once at each, as
``warm.py`` does for the one-chip engine's batched program.

Nothing of that is a constant here or in a cell's file: the bytes a
segment takes are the engine's own fill for this hasher
(``engine/chunker._segment_source``: the stream's default segment
scaled by the hasher's shards), the shard length of a segment is the
hasher's ``shard_bucket``, the capacities its ``fused_caps``, the
host-hashed floor the chunker's ``min_size``; and the hasher is the
one the mover's entry will hash with (``entry.mesh_hasher``), because
a mesh program lives with the hasher that built it.
``compiles_in_window`` says when the plan missed one.
"""

from __future__ import annotations

import inspect

import numpy as np

from benchmark.warm import buckets_between


def segment_ranges(nbytes: int, fill: int, max_tail: int):
    """(shortest, longest, eof) of every segment of one ``nbytes``
    stream: each takes up to ``fill`` new bytes, every one but the first
    also the 0 to ``max_tail`` - 1 bytes the one before left uncut, and
    the one whose read met the end of the stream is the last. (The same
    walk as ``warm.file_buckets``, which does not say which is last.)"""
    left, first = nbytes, True
    while True:
        new = min(left, fill)
        yield new, (new if first else new + max_tail - 1), new < fill
        left -= new
        first = False
        if new < fill:
            return


def mesh_plan(sizes, chunker_params, hasher) -> list[tuple]:
    """[(shard_len, cand_cap, chunk_cap, eof)] a backup of files of
    ``sizes`` can run on ``hasher``: the keys of its fused programs."""
    from volsync_tpu.engine import chunker

    segment = inspect.signature(chunker.stream_chunk_batches) \
        .parameters["segment_size"].default
    fill = chunker._segment_source(lambda n: b"", chunker_params, segment,
                                   hasher).target
    plan = set()
    for n in sizes:
        if n <= chunker_params.min_size:
            continue  # hashed on the host
        for lo, hi, eof in segment_ranges(int(n), fill,
                                          chunker_params.max_size):
            lo = max(lo, chunker_params.min_size + 1)
            for total in (buckets_between(lo, hi, hasher.buffer_bucket)
                          if hi >= lo else ()):
                shard_len = total // hasher.n_shards
                plan.add((shard_len, *hasher.fused_caps(shard_len), eof))
    return sorted(plan)


def mesh_programs(hasher, plan, seed: int) -> int:
    """Runs the hasher at every planned program, on a segment that
    fills the shards to the byte; returns how many."""
    bufs: dict[int, np.ndarray] = {}
    for shard_len, _cand_cap, _chunk_cap, eof in plan:
        size = shard_len * hasher.n_shards
        if size not in bufs:
            bufs[size] = np.frombuffer(
                np.random.default_rng([seed, size]).bytes(size), np.uint8)
        hasher.process(bufs[size], eof=eof)
    return len(plan)
