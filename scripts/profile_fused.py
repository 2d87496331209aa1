"""Stage-by-stage timing of the fused segment pipeline on the live chip.

One script, three granularities of the same measurement — pick with
``--variant``:

  base  coarse device stages (gear scan, page digests, pack/transpose)
        with block_until_ready between dispatches, plus the end-to-end
        shipped protocol (fused program + result fetch) and the
        dispatch round-trip floor.
  v2    fenced, salted stage split (scalar-fetch fence,
        per-iteration salts): full pipeline vs
        page digests vs gear+walk.
  v3    finest-grain gear-side isolation: gear only, +compaction,
        +successor tables, +FastCDC walk, full fused.

Run on the TPU; not part of the test suite.

Usage: python scripts/profile_fused.py [--variant base|v2|v3] [SEG_MIB]
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from volsync_tpu.compile_cache import configure as _configure_cache  # noqa: E402

_configure_cache()

import jax
import jax.numpy as jnp
import numpy as np

from volsync_tpu.envflags import root_unroll
from volsync_tpu.ops import segment as seg
from volsync_tpu.ops import sha256 as sha
from volsync_tpu.ops.gearcdc import DEFAULT_PARAMS, gear_at_aligned

p = DEFAULT_PARAMS


def run_base(seg_mib: int, iters: int) -> None:
    N = seg_mib << 20
    rng = np.random.RandomState(7)
    data = jnp.asarray(rng.randint(0, 256, size=(N,), dtype=np.uint8))
    jax.block_until_ready(data)
    cand_cap, chunk_cap = seg.segment_caps(N, p)
    F = N // seg.LEAF_SIZE
    npp = seg._n_pages_pad(F)

    def timeit(name, fn, *args, scale_bytes=N):
        out = fn(*args)
        jax.block_until_ready(out)  # warm/compile
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / iters
        print(f"{name:34s} {dt*1e3:8.2f} ms  "
              f"{scale_bytes/dt/(1<<30):7.2f} GiB/s", flush=True)
        return dt

    print(f"== segment {seg_mib} MiB, backend={jax.default_backend()}, "
          f"pallas={sha.use_pallas_leaves()}, npp={npp}", flush=True)

    # 1. gear scan only
    gear_j = jax.jit(lambda d: gear_at_aligned(d, p.seed, p.align))
    timeit("gear_at_aligned", gear_j, data)

    # 2. page digests (pack + transpose + sha kernel)
    pd = jax.jit(lambda d: seg._page_digests_flat(d, npp))
    timeit("page_digests_flat (full)", pd, data)

    # 2a. word pack only
    def pack_only(d):
        r = d.reshape(F, seg.LEAF_SIZE)
        b0 = r[:, 0::4].astype(jnp.uint32)
        b1 = r[:, 1::4].astype(jnp.uint32)
        b2 = r[:, 2::4].astype(jnp.uint32)
        b3 = r[:, 3::4].astype(jnp.uint32)
        return ((b0 << np.uint32(24)) | (b1 << np.uint32(16))
                | (b2 << np.uint32(8)) | b3)
    pack_j = jax.jit(pack_only)
    timeit("  word pack", pack_j, data)

    # 2b. pack + transpose (the Pallas kernel lowers on TPU only)
    if jax.default_backend() != "cpu":
        def pack_t(d):
            x2 = pack_only(d)
            if npp != F:
                x2 = jnp.pad(x2, ((0, npp - F), (0, 0)))
            return seg._pallas_transpose(x2)
        packt_j = jax.jit(pack_t)
        timeit("  pack + pallas transpose", packt_j, data)
    else:
        print("  pack + pallas transpose           skipped (cpu backend)",
              flush=True)

    # 3. full fused program (device only, no fetch)
    def fused(d):
        return seg.chunk_hash_segment(
            d, N, min_size=p.min_size, avg_size=p.avg_size,
            max_size=p.max_size, seed=p.seed, mask_s=p.mask_s,
            mask_l=p.mask_l, align=p.align, eof=True,
            cand_cap=cand_cap, chunk_cap=chunk_cap)
    timeit("chunk_hash_segment (no fetch)", fused, data)

    # 4. end-to-end with fetch (the shipped protocol)
    def fused_fetch(d):
        return np.asarray(fused(d))
    fused_fetch(data)
    t0 = time.perf_counter()
    for _ in range(iters):
        fused_fetch(data)
    dt = (time.perf_counter() - t0) / iters
    print(f"{'chunk_hash_segment + fetch':34s} {dt*1e3:8.2f} ms  "
          f"{N/dt/(1<<30):7.2f} GiB/s", flush=True)

    # 5. dispatch round-trip floor (tiny program + tiny fetch)
    tiny = jax.jit(lambda v: (v * 2 + 1).sum())
    x = jnp.arange(64, dtype=jnp.float32)
    jax.block_until_ready(tiny(x))
    t0 = time.perf_counter()
    for _ in range(20):
        float(tiny(x))
    rt = (time.perf_counter() - t0) / 20
    print(f"{'dispatch+fetch round trip':34s} {rt*1e3:8.2f} ms", flush=True)


def _fence_timeit(name, fn, base, N, iters):
    """Salted scalar-fetch fence: the scalar
    result forces execution; per-iteration salts keep every timed
    call's arguments distinct."""
    float(fn(base, jnp.uint8(0)))
    t0 = time.perf_counter()
    out = None
    for i in range(iters):
        out = fn(base, jnp.uint8(i + 1))  # lint: ignore[VL502] per-dispatch timing is the measurement
    float(out)
    dt = (time.perf_counter() - t0) / iters
    print(f"{name:28s} {dt * 1e3:8.2f} ms  "
          f"{N / dt / (1 << 30):7.2f} GiB/s", flush=True)


def run_v2(seg_mib: int, iters: int) -> None:
    N = seg_mib << 20
    rng = np.random.RandomState(7)
    base = jnp.asarray(rng.randint(0, 256, size=(N,), dtype=np.uint8))
    jax.block_until_ready(base)
    cand_cap, chunk_cap = seg.segment_caps(N, p)
    F = N // 4096
    npp = seg._n_pages_pad(F)

    @jax.jit
    def full(d, s):
        out = seg.chunk_hash_segment(
            d ^ s, N, min_size=p.min_size, avg_size=p.avg_size,
            max_size=p.max_size, seed=p.seed, mask_s=p.mask_s,
            mask_l=p.mask_l, align=p.align, eof=True,
            cand_cap=cand_cap, chunk_cap=chunk_cap)
        return out.astype(jnp.uint32)[::97].sum()

    @jax.jit
    def pages_only(d, s):
        return seg._page_digests_flat(d ^ s, npp)[::4097].sum()

    @jax.jit
    def gear_walk_only(d, s):
        d = d ^ s
        h = gear_at_aligned(d, p.seed, p.align)
        R = N // p.align
        pos_all = jnp.arange(R, dtype=jnp.int32) * p.align + (p.align - 1)
        ok = pos_all < N
        is_s = ((h & np.uint32(p.mask_s)) == 0) & ok
        is_l = ((h & np.uint32(p.mask_l)) == 0) & ok
        pos_s = seg._compact_candidates(is_s, cand_cap, R, p.align)
        pos_l = seg._compact_candidates(is_l, cand_cap, R, p.align)
        ns = jnp.sum(is_s).astype(jnp.int32)
        nl = jnp.sum(is_l).astype(jnp.int32)
        starts, lens, count, consumed = seg._select_boundaries_device(
            pos_s, jnp.minimum(ns, cand_cap), pos_l,
            jnp.minimum(nl, cand_cap), jnp.int32(N), min_size=p.min_size,
            avg_size=p.avg_size, max_size=p.max_size, chunk_cap=chunk_cap,
            eof=True)
        return starts.sum() + lens.sum() + count + consumed

    print(f"== {seg_mib} MiB fused split, backend={jax.default_backend()}",
          flush=True)
    _fence_timeit("full fused program", full, base, N, iters)
    _fence_timeit("page digests only", pages_only, base, N, iters)
    _fence_timeit("gear + walk only", gear_walk_only, base, N, iters)


def run_v3(seg_mib: int, iters: int) -> None:
    N = seg_mib << 20
    rng = np.random.RandomState(7)
    base = jnp.asarray(rng.randint(0, 256, size=(N,), dtype=np.uint8))
    jax.block_until_ready(base)
    cand_cap, chunk_cap = seg.segment_caps(N, p)
    R = N // p.align

    def candidates(d):
        h = gear_at_aligned(d, p.seed, p.align)
        pos_all = jnp.arange(R, dtype=jnp.int32) * p.align + (p.align - 1)
        ok = pos_all < N
        is_s = ((h & np.uint32(p.mask_s)) == 0) & ok
        is_l = ((h & np.uint32(p.mask_l)) == 0) & ok
        return is_s, is_l

    @jax.jit
    def gear_only(d, s):
        h = gear_at_aligned(d ^ s, p.seed, p.align)
        return h.astype(jnp.uint32).sum()

    @jax.jit
    def gear_compact(d, s):
        is_s, is_l = candidates(d ^ s)
        pos_s = seg._compact_candidates(is_s, cand_cap, R, p.align)
        pos_l = seg._compact_candidates(is_l, cand_cap, R, p.align)
        return pos_s.sum() + pos_l.sum()

    def tables(pos_s, ns, pos_l, nl):
        i32 = jnp.int32
        L = jnp.int32(N)
        pos_r = jnp.arange(R, dtype=i32) * p.align
        lo = pos_r + (p.min_size - 1)
        mid = pos_r + (p.avg_size - 1)
        hi = pos_r + (p.max_size - 1)
        i = jnp.searchsorted(pos_s, lo, side="left").astype(i32)
        cs = pos_s[jnp.clip(i, 0, cand_cap - 1)]
        lim_s = jnp.minimum(jnp.minimum(mid - 1, L - 1), hi)
        found_s = (i < ns) & (cs <= lim_s)
        j = jnp.searchsorted(pos_l, jnp.maximum(lo, mid),
                             side="left").astype(i32)
        cl = pos_l[jnp.clip(j, 0, cand_cap - 1)]
        found_l = (j < nl) & (cl <= jnp.minimum(hi, L - 1))
        hi_ok = hi <= L - 1
        cut = jnp.where(found_s, cs,
                        jnp.where(found_l, cl,
                                  jnp.where(hi_ok, hi, L - 1)))
        emit = found_s | found_l | hi_ok
        return cut, emit

    @jax.jit
    def gear_compact_tables(d, s):
        is_s, is_l = candidates(d ^ s)
        pos_s = seg._compact_candidates(is_s, cand_cap, R, p.align)
        pos_l = seg._compact_candidates(is_l, cand_cap, R, p.align)
        ns = jnp.sum(is_s).astype(jnp.int32)
        nl = jnp.sum(is_l).astype(jnp.int32)
        cut, emit = tables(pos_s, ns, pos_l, nl)
        return cut.sum() + emit.sum()

    @jax.jit
    def gear_walk(d, s):
        is_s, is_l = candidates(d ^ s)
        pos_s = seg._compact_candidates(is_s, cand_cap, R, p.align)
        pos_l = seg._compact_candidates(is_l, cand_cap, R, p.align)
        ns = jnp.sum(is_s).astype(jnp.int32)
        nl = jnp.sum(is_l).astype(jnp.int32)
        starts, lens, count, consumed = seg._select_boundaries_device(
            pos_s, jnp.minimum(ns, cand_cap), pos_l,
            jnp.minimum(nl, cand_cap), jnp.int32(N), min_size=p.min_size,
            avg_size=p.avg_size, max_size=p.max_size, chunk_cap=chunk_cap,
            eof=True, align=p.align, n_rows=R)
        return starts.sum() + lens.sum() + count + consumed

    @jax.jit
    def full(d, s):
        out = seg.chunk_hash_segment(
            d ^ s, N, min_size=p.min_size, avg_size=p.avg_size,
            max_size=p.max_size, seed=p.seed, mask_s=p.mask_s,
            mask_l=p.mask_l, align=p.align, eof=True,
            cand_cap=cand_cap, chunk_cap=chunk_cap)
        return out.astype(jnp.uint32)[::97].sum()

    print(f"== {seg_mib} MiB fine split, backend={jax.default_backend()}, "
          f"root_unroll={root_unroll()}", flush=True)
    _fence_timeit("gear only", gear_only, base, N, iters)
    _fence_timeit("gear + compaction", gear_compact, base, N, iters)
    _fence_timeit("gear + compact + tables", gear_compact_tables,
                  base, N, iters)
    _fence_timeit("gear + compact + walk", gear_walk, base, N, iters)
    _fence_timeit("full fused", full, base, N, iters)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--variant", choices=("base", "v2", "v3"),
                    default="base")
    ap.add_argument("seg_mib", nargs="?", type=int, default=64)
    ap.add_argument("--iters", type=int, default=None,
                    help="timed iterations (default: 5 base, 12 v2/v3)")
    args = ap.parse_args()
    iters = args.iters if args.iters is not None else (
        5 if args.variant == "base" else 12)
    {"base": run_base, "v2": run_v2, "v3": run_v3}[args.variant](
        args.seg_mib, iters)
    return 0


if __name__ == "__main__":
    sys.exit(main())
