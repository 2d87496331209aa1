"""Device time of the tail-leaf stage of the segment programs, alone.

The stage that hashes the ONE partial 4 KiB leaf a lane, timed on the
live chip: the generic hasher it used to be (``sha256_chunks_device``:
a byte gather + a fixed 65-step scan, timed apart) against
``ops/segment._tail_leaf_digests`` (its row gather + packing apart, and
with other floors under its lanes than ``_TAIL_MIN_LANES``), at 1 lane
on a 1 MiB and a 48 MiB resident buffer, and at 16 and 128 lanes; then
the whole batched program at 1 x 1 MiB and 1 x 48 MiB with either stage.

Each stage runs K times inside ONE program (a fori_loop whose carry
picks the next page, so nothing hoists), so the host's launch cost is
paid once: what is printed is device time an iteration.

Run on the TPU; not part of the test suite.

Usage: python scripts/profile_tail.py [--reps K]
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from volsync_tpu.compile_cache import configure as _configure_cache  # noqa: E402

_configure_cache()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from volsync_tpu.ops import segment as seg  # noqa: E402
from volsync_tpu.ops import sha256 as sha  # noqa: E402
from volsync_tpu.ops.gearcdc import DEFAULT_PARAMS as p  # noqa: E402

LEAF = seg.LEAF_SIZE
PADDED = (LEAF + 9 + 63) // 64 * 64  # the generic hasher's 4,160 bytes


def old_stage(data, page, ln):
    return sha.sha256_chunks_device(data, page * LEAF, ln, max_len=LEAF)


def old_gather(data, page, ln):
    """The generic hasher's byte gather alone, reduced to [N, 8]."""
    idx = jnp.clip(page[:, None] * LEAF
                   + jnp.arange(PADDED, dtype=jnp.int32)[None, :],
                   0, data.shape[0] - 1)
    raw = data[idx].astype(jnp.uint32)  # [N, 4160]
    return raw.reshape(raw.shape[0], 8, -1).sum(axis=2)


def old_scan(data, page, ln):
    """Its 65-step scan alone, over blocks that cost no gather."""
    n = page.shape[0]
    blocks = (jnp.arange(n * 65 * 16, dtype=jnp.uint32).reshape(n, 65, 16)
              + page[:, None, None].astype(jnp.uint32))
    return sha.sha256_blocks(blocks, (ln + 9 + 63) // 64)


new_stage = seg._tail_leaf_digests


def new_rows(data, page, ln):
    """The helper's row gather + word packing alone, reduced to [N, 8]."""
    F = data.shape[0] // LEAF
    rows = data.reshape(F, LEAF)[jnp.clip(page, 0, F - 1)]
    w = sha.pack_words_rows(rows.reshape(-1, LEAF // 8))
    return w.reshape(page.shape[0], 8, -1).sum(axis=2)


def new_at(min_lanes):
    """The helper with another floor under its lanes (read at trace)."""
    def stage(data, page, ln):
        prev = seg._TAIL_MIN_LANES
        seg._TAIL_MIN_LANES = min_lanes
        try:
            return new_stage(data, page, ln)
        finally:
            seg._TAIL_MIN_LANES = prev
    return stage


def repeated(stage, reps):
    """``stage`` run ``reps`` times in one program; the carry moves the
    page by 0 or 1 each time, a real dependency the compiler cannot
    lift out of the loop."""
    @jax.jit
    def run(data, page, ln):
        F = data.shape[0] // LEAF

        def body(_, acc):
            pg = (page + (acc[0, 0] & 1).astype(jnp.int32)) % F
            return acc ^ stage(data, pg, ln)

        return jax.lax.fori_loop(
            0, reps, body, jnp.zeros((page.shape[0], 8), jnp.uint32))
    return run


def time_ms(fn, *args, calls=5):
    jax.block_until_ready(fn(*args))  # compile + warm
    best = float("inf")
    for _ in range(calls):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def whole_program(tail_fn):
    """The batched program's body with ``tail_fn`` as its tail stage."""
    def impl(*a, **kw):
        prev = seg._tail_leaf_digests
        seg._tail_leaf_digests = tail_fn
        try:
            return seg._chunk_hash_segments_impl(*a, **kw)
        finally:
            seg._tail_leaf_digests = prev
    return functools.partial(
        jax.jit, static_argnames=seg._SEGMENTS_STATIC)(impl)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=50)
    ap.add_argument("--out", default="chiprun_out/profile_tail.json")
    ap.add_argument("--rehearsal", action="store_true",
                    help="small buffers only: control flow on the CPU")
    args = ap.parse_args()
    dev = jax.devices()[0]
    print(f"device {dev.platform} {dev.device_kind}", flush=True)
    rng = np.random.RandomState(11)
    rows = []

    def note(**kw):
        rows.append(kw)
        print(json.dumps(kw), flush=True)

    stages = {"old": old_stage, "old.gather": old_gather,
              "old.scan": old_scan, "new": new_stage, "new.rows": new_rows}
    for floor in (1, 2, 4, 8, 32, 128):
        stages[f"new@{floor}"] = new_at(floor)
    cases = ((1, 1, (4095, 2000, 1, 0)), (48, 1, (4095, 2000, 0)),
             (16, 16, (4095,)), (32, 128, (4095,)))
    if args.rehearsal:
        cases = ((1, 1, (4095, 0)), (1, 4, (2000,)))
    for mib, lanes, tails in cases:
        data = jnp.asarray(rng.randint(  # lint: ignore[VL502] a measured case's own buffer
            0, 256, size=(mib << 20,), dtype=np.uint8))
        F = (mib << 20) // LEAF
        page = jnp.asarray(  # lint: ignore[VL502] a measured case's own lanes
            np.linspace(0, F - 2, lanes).astype(np.int32))
        for tail in tails:
            ln = jnp.full((lanes,), tail, jnp.int32)  # lint: ignore[VL502] a measured case's own lengths
            for name, stage in stages.items():
                if tail != 4095 and ("." in name or "@" in name):
                    continue  # parts and floors: at the longest tail
                ms = time_ms(repeated(stage, args.reps), data, page, ln)
                note(buffer_mib=mib, lanes=lanes, tail_len=tail,
                     stage=name, ms_per_run=ms / args.reps)

    # The whole batched program, one lane: eof with a tail, and not eof.
    for mib in ((1,) if args.rehearsal else (1, 48)):
        P = mib << 20
        cand_cap, chunk_cap = seg.segment_caps(P, p)
        kw = dict(min_size=p.min_size, avg_size=p.avg_size,
                  max_size=p.max_size, seed=p.seed, mask_s=p.mask_s,
                  mask_l=p.mask_l, align=p.align, cand_cap=cand_cap,
                  chunk_cap=chunk_cap)
        data = jnp.asarray(rng.randint(  # lint: ignore[VL502] a measured case's own buffer
            0, 256, size=(P,), dtype=np.uint8))
        vl = jnp.asarray([P - 1234], jnp.int32)  # lint: ignore[VL502] a measured case's own length
        for which, tail_fn in (("old", old_stage), ("new", new_stage)):
            prog = whole_program(tail_fn)
            for eof in (True, False):
                e = jnp.asarray([eof])

                def burst(n=20):
                    out = None
                    for _ in range(n):
                        out = prog(data, vl, e, **kw)
                    return out

                ms = time_ms(burst) / 20
                note(program="chunk_hash_segments", lanes=1, bucket_mib=mib,
                     eof=eof, tail_stage=which, ms_per_dispatch=ms)

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
