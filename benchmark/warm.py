"""Loading the batched segment program at the shapes a cell's traffic
can meet, before the window.

Which lanes coalesce in the program's micro-batcher is a matter of
thread timing, and which staging bucket a segment lands in follows the
seed's bytes (the tail a segment carries into the next), so a warm-up
operation alone leaves some (lanes, bucket) program to compile — or to
be read back from the cache, seconds either way — inside some run's
window. Set-up therefore works out every (lanes, bucket) the cell's
sizes can present and runs ``ops/segment.py``'s ``BatchedSegmentHasher``
— the host driver of the one program both the backup engine's shared
batcher and the service's batcher dispatch — once at each.

Nothing of that is a constant here or in a cell's file: the bucket of a
length is the engine's own ``_buffer_bucket``, the bytes a segment takes
are the engine's own ``_SegmentFill`` at ``stream_chunk_batches``'
default segment size, the host-hashed floor is the chunker's
``min_size``, and the lanes are the engine's worker count or the
service's batch limit. A change to any of them in the program moves the
plan with it. ``compiles_in_window`` says when the plan missed one.
"""

from __future__ import annotations

import inspect

import numpy as np


def _pow2ceil(n: int) -> int:
    v = 1
    while v < n:
        v *= 2
    return v


def lane_counts(concurrent: int) -> set[int]:
    """The padded lane counts 1..``concurrent`` same-bucket segments
    can be dispatched at (the program pads lanes to a power of two)."""
    return {_pow2ceil(k) for k in range(1, max(1, concurrent) + 1)}


def buckets_between(lo: int, hi: int, bucket) -> set[int]:
    """Every staging bucket of a length in [lo, hi]."""
    out = set()
    n = max(lo, 1)
    while n <= hi:
        b = bucket(n)
        out.add(b)
        n = b + 1
    return out


def file_buckets(nbytes: int, fill: int, max_tail: int, bucket) -> set[int]:
    """Every staging bucket the segments of one ``nbytes`` stream can
    land in: each segment takes up to ``fill`` new bytes, and every one
    but the first also the tail the one before left uncut, 0 to
    ``max_tail`` - 1 bytes of it. A stream that ends exactly on a fill
    sends one last segment of the tail alone."""
    out: set[int] = set()
    left, first = nbytes, True
    while True:
        new = min(left, fill)
        hi = new if first else new + max_tail - 1
        if hi > 0:
            out |= buckets_between(new, hi, bucket)
        left -= new
        first = False
        if new < fill:
            return out


def backup_plan(sizes, chunker_params) -> list[tuple[int, int]]:
    """[(lanes, bucket)] a backup of files of ``sizes`` can dispatch:
    files over the chunker's ``min_size`` stream through
    ``stream_chunk_batches``; ``backup_workers()`` files at a time, one
    segment of each in flight."""
    from volsync_tpu import envflags
    from volsync_tpu.engine import chunker

    segment = inspect.signature(chunker.stream_chunk_batches) \
        .parameters["segment_size"].default
    fill = chunker._SegmentFill(lambda n: b"", segment,
                                chunker_params.max_size).target
    files_at: dict[int, int] = {}
    for n in sizes:
        if n > chunker_params.min_size:
            for b in file_buckets(int(n), fill, chunker_params.max_size,
                                  chunker._buffer_bucket):
                files_at[b] = files_at.get(b, 0) + 1
    workers = envflags.backup_workers()
    return sorted((lanes, b) for b, files in files_at.items()
                  for lanes in lane_counts(min(workers, files)))


def stream_plan(sizes, server_kwargs: dict, clients: int,
                ) -> list[tuple[int, int]]:
    """[(lanes, bucket)] the service can dispatch for ChunkStreams of
    ``sizes`` from ``clients`` concurrent clients: one segment a stream
    (a stream over the service's cut would be several: not planned
    here), up to ``max_workers`` coalesced."""
    from volsync_tpu.engine.chunker import _buffer_bucket
    from volsync_tpu.ops.gearcdc import DEFAULT_PARAMS
    from volsync_tpu.service.server import MoverJaxServer

    args = {k: v.default for k, v in
            inspect.signature(MoverJaxServer.__init__).parameters.items()}
    args.update(server_kwargs)
    params = args["params"] or DEFAULT_PARAMS
    cut = args["segment_size"] + params.max_size
    if max(sizes) > cut:
        raise ValueError(f"a stream of {max(sizes)} bytes is more than one "
                         f"segment ({cut}): stream_plan plans one a stream")
    lanes = lane_counts(min(int(args["max_workers"]), clients))
    return sorted((n, b) for b in {_buffer_bucket(int(s)) for s in sizes}
                  for n in lanes)


def segment_programs(chunker_params, plan, seed: int) -> int:
    """Runs the program at every (lanes, bucket); returns how many."""
    from volsync_tpu.ops.segment import BatchedSegmentHasher

    hasher = BatchedSegmentHasher(chunker_params)
    bufs: dict[int, bytes] = {}
    for lanes, size in plan:
        if size not in bufs:
            bufs[size] = np.random.default_rng([seed, size]).bytes(size)
        buf = bufs[size]
        hasher.hash_segments([(buf, len(buf), True)] * lanes)
    return len(plan)
