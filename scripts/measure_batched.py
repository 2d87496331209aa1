"""Salted throughput sweep on the live chip.

Every iteration composes a distinct uint8 salt into the program on
device, so no two timed dispatches share arguments (the same basis as
bench.py). Measures:
  1. true device-only throughput of the fused single-segment program
     (pipelined dispatches, one final block);
  2. the batched multi-lane program at several (S lanes x P bytes)
     shapes, fetch included (the shipped protocol);
  3. batched with T concurrent pipelines (overlapping round trips).
Usage: python scripts/measure_batched.py [quick|full]
"""
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from volsync_tpu.compile_cache import configure as _configure_cache  # noqa: E402

_configure_cache()

import functools
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from volsync_tpu.ops import segment as seg
from volsync_tpu.ops.gearcdc import DEFAULT_PARAMS

p = DEFAULT_PARAMS
MODE = sys.argv[1] if len(sys.argv) > 1 else "quick"


def make_base(n):
    rng = np.random.RandomState(7)
    host = rng.randint(0, 256, size=(n,), dtype=np.uint8)
    d = jnp.asarray(host)
    jax.block_until_ready(d)
    return d


@functools.partial(jax.jit, static_argnames=("eof", "cand_cap", "chunk_cap"))
def salted_single(d, s, vl, *, eof, cand_cap, chunk_cap):
    return seg.chunk_hash_segment(
        d ^ s, vl, min_size=p.min_size, avg_size=p.avg_size,
        max_size=p.max_size, seed=p.seed, mask_s=p.mask_s, mask_l=p.mask_l,
        align=p.align, eof=eof, cand_cap=cand_cap, chunk_cap=chunk_cap)


@functools.partial(jax.jit, static_argnames=("cand_cap", "chunk_cap"))
def salted_batch(d, salts, vl, eof, *, cand_cap, chunk_cap):
    rows = jnp.tile(d, salts.shape[0]) ^ jnp.repeat(salts, d.shape[0])
    return seg.chunk_hash_segments(
        rows, vl, eof, min_size=p.min_size, avg_size=p.avg_size,
        max_size=p.max_size, seed=p.seed, mask_s=p.mask_s, mask_l=p.mask_l,
        align=p.align, cand_cap=cand_cap, chunk_cap=chunk_cap)


def device_only(seg_mib, iters=8):
    """Pipelined dispatches, block at the end: true device throughput."""
    n = seg_mib << 20
    d = make_base(n)
    cc, kc = seg.segment_caps(n, p)
    out = salted_single(d, jnp.uint8(0), n, eof=True, cand_cap=cc,
                        chunk_cap=kc)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    # Per-iteration salted dispatch is the unbatched baseline arm this
    # script exists to measure against the batched kernels.
    outs = [salted_single(d, jnp.uint8(i + 1), n, eof=True, cand_cap=cc,  # lint: ignore[VL502] baseline arm
                          chunk_cap=kc) for i in range(iters)]
    jax.block_until_ready(outs)
    dt = time.perf_counter() - t0
    print(f"single {seg_mib:4d}MiB device-only   "
          f"{dt / iters * 1e3:8.1f} ms/disp  "
          f"{iters * n / dt / (1 << 30):7.2f} GiB/s", flush=True)


def batched(seg_mib, lanes, iters=4, threads=1):
    n = seg_mib << 20
    d = make_base(n)
    cc, kc = seg.segment_caps(n, p)
    vl = jnp.full((lanes,), n, jnp.int32)
    eof = jnp.ones((lanes,), bool)
    salt_ctr = [0]

    def one(i):
        s0 = salt_ctr[0]; salt_ctr[0] += lanes
        salts = jnp.asarray(
            (np.arange(s0, s0 + lanes) % 251 + 1).astype(np.uint8))
        out = np.asarray(salted_batch(d, salts, vl, eof, cand_cap=cc,
                                      chunk_cap=kc))
        assert int(out[0, 0]) > 0
        return out

    one(0)  # warm
    t0 = time.perf_counter()
    if threads == 1:
        for i in range(iters):
            one(i)
    else:
        with ThreadPoolExecutor(threads) as ex:
            list(ex.map(one, range(iters)))
    dt = time.perf_counter() - t0
    total = lanes * iters * n
    print(f"batch {seg_mib:4d}MiBx{lanes:2d} T={threads} "
          f"{dt / iters * 1e3:8.1f} ms/disp  "
          f"{total / dt / (1 << 30):7.2f} GiB/s", flush=True)


print(f"backend={jax.default_backend()}", flush=True)
if MODE == "quick":
    device_only(64)
    batched(64, 8)
    batched(64, 8, threads=2, iters=6)
else:
    device_only(64)
    device_only(256)
    batched(64, 8)
    batched(128, 8, iters=3)
    batched(256, 8, iters=3)
    batched(64, 8, threads=2, iters=6)
    batched(128, 8, threads=2, iters=6)
    batched(256, 8, threads=2, iters=6)
    batched(256, 8, threads=3, iters=9)
