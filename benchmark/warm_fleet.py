"""The (lanes, bucket) programs a fleet of movers can make one mover-jax
service meet, worked out from the movers' file sizes and the service's
own numbers, so that set-up can load them all before the window
(``warm.segment_programs``).

A mover hashes one file at a time and the service keeps one segment of
a stream in flight, so a mover presents one segment at a time. Which
segments a file's one stream is cut into follows its bytes
(``service/server.py`` ``stream_segment_spans``: full segments of the
service's cut, then what is left), which staging bucket a length lands
in is the engine's ``_buffer_bucket``, and how many same-bucket segments
of different movers one dispatch may take is the service's batch limit
under its stage limit (``ops/segment.py`` ``coalesced_lanes``). Files of
at most the chunker's ``min_size`` are hashed in the mover and never
streamed. Nothing of that is a constant here: a change to any of them in
the program moves the plan with it, and ``compiles_in_window`` says when
the plan missed a program.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark import warm

#: programs loaded side by side: a program's compile, or its read from
#: the cache, runs beside the next one's trace (which holds the
#: interpreter), and the compiler's threads have the host's other cores
LOADERS = 4


def stream_buckets(nbytes: int, server) -> set[int]:
    """Every staging bucket the segments of one ``nbytes`` ChunkHash
    stream can land in at ``server`` (a ``MoverJaxServer``)."""
    from volsync_tpu.ops.segment import _buffer_bucket
    from volsync_tpu.service.server import stream_segment_spans

    cut = server.segment_size + server.params.max_size
    full, last = stream_segment_spans(nbytes, cut, server.params.max_size)
    out = {_buffer_bucket(cut)} if full else set()
    for lo, hi in last:
        out |= warm.buckets_between(max(lo, 1), hi, _buffer_bucket)
    return out


def fleet_plan(sizes_by_mover, server) -> list[tuple[int, int]]:
    """[(lanes, bucket)] the service can dispatch for movers whose
    volumes hold files of ``sizes_by_mover`` (one list a mover)."""
    from volsync_tpu.ops.segment import coalesced_lanes

    movers_at: dict[int, int] = {}
    for sizes in sizes_by_mover:
        mine: set[int] = set()
        for n in sizes:
            if n > server.params.min_size:
                mine |= stream_buckets(int(n), server)
        for b in mine:
            movers_at[b] = movers_at.get(b, 0) + 1
    return sorted(
        (lanes, b) for b, movers in movers_at.items()
        for lanes in warm.lane_counts(min(
            movers, server.max_batch,
            coalesced_lanes(b, server.stage_limit))))


def load_programs(chunker_params, plan, seed: int) -> int:
    """Runs the batched segment program at every (lanes, bucket) of
    ``plan``, as ``warm.segment_programs`` does, ``LOADERS`` at a time
    and the largest first: the cell has more programs than the others
    and a checkout's first run compiles each (about 15 s of the
    compiler's time beside 8 s of tracing and lowering in Python, my
    chip run, PR 48), which one after another does not fit a run."""
    from volsync_tpu.ops.segment import BatchedSegmentHasher

    hasher = BatchedSegmentHasher(chunker_params)
    bufs = {size: np.random.default_rng([seed, size]).bytes(size)
            for size in {b for _, b in plan}}

    def load(pair) -> None:
        lanes, size = pair
        hasher.hash_segments([(bufs[size], size, True)] * lanes)

    with ThreadPoolExecutor(LOADERS) as pool:
        list(pool.map(load, sorted(plan, key=lambda p: -p[0] * p[1])))
    return len(plan)
