"""Isolate the root-loop stage and measure real compute at sizes where
the ~7 ms per-dispatch overhead is amortized (>= 256 MiB)."""
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
from volsync_tpu.ops.gearcdc import DEFAULT_PARAMS

p = DEFAULT_PARAMS
SEG_MIB = int(sys.argv[1]) if len(sys.argv) > 1 else 256
N = SEG_MIB << 20
F = N // 4096
ITERS = int(sys.argv[2]) if len(sys.argv) > 2 else 8

rng = np.random.RandomState(7)
host = rng.randint(0, 256, size=(N,), dtype=np.uint8)
base = jnp.asarray(host)
jax.block_until_ready(base)
cand_cap, chunk_cap = seg.segment_caps(N, p)
npp = seg._n_pages_pad(F)


def timeit(name, fn, *args):
    float(fn(*args, jnp.uint8(0)))
    t0 = time.perf_counter()
    out = None
    for i in range(ITERS):
        out = fn(*args, jnp.uint8(i + 1))  # lint: ignore[VL502] per-dispatch timing is the measurement
    float(out)
    dt = (time.perf_counter() - t0) / ITERS
    print(f"{name:30s} {dt * 1e3:8.2f} ms  {N / dt / (1 << 30):7.2f} GiB/s",
          flush=True)
    return dt


@jax.jit
def full(d, s):
    out = seg.chunk_hash_segment(
        d ^ s, N, min_size=p.min_size, avg_size=p.avg_size,
        max_size=p.max_size, seed=p.seed, mask_s=p.mask_s, mask_l=p.mask_l,
        align=p.align, eof=True, cand_cap=cand_cap, chunk_cap=chunk_cap)
    return out.astype(jnp.uint32)[::97].sum()


@jax.jit
def pages(d, s):
    return seg._page_digests_flat(d ^ s, npp)[::4097].sum()


# Root loop with a REAL chunk table (decoded from a warm run) but fed
# salted digests so no two timed calls share arguments. nb/max_nb structure is
# identical to the in-program loop.
warm = seg.chunk_hash_segment(
    base, N, min_size=p.min_size, avg_size=p.avg_size, max_size=p.max_size,
    seed=p.seed, mask_s=p.mask_s, mask_l=p.mask_l, align=p.align, eof=True,
    cand_cap=cand_cap, chunk_cap=chunk_cap)
chunks, _, _, _ = seg.decode_segment(np.asarray(warm), chunk_cap)
count = len(chunks)
starts_np = np.zeros((chunk_cap,), np.int32)
lens_np = np.zeros((chunk_cap,), np.int32)
for c, (s0, l, _) in enumerate(chunks):
    starts_np[c] = s0
    lens_np[c] = l
live_np = np.arange(chunk_cap) < count
nleaves_np = np.where(live_np, (lens_np + 4095) // 4096, 0)
page0_np = starts_np // 4096
sizes = sorted(lens_np[live_np] // (1 << 20))
print(f"chunks={count} max_chunk={max(sizes)}MiB "
      f"max_nb={(32 * max(nleaves_np) + 22 + 63) // 64}", flush=True)

page0 = jnp.asarray(page0_np)
nleaves = jnp.asarray(nleaves_np)
lens_d = jnp.asarray(lens_np)
live = jnp.asarray(live_np)
flat0 = jnp.arange(8 * npp, dtype=jnp.uint32)  # synthetic digest table


@jax.jit
def root_only(fl, s):
    st = seg._root_digests_loop(
        fl ^ s.astype(jnp.uint32), npp, page0, nleaves, lens_d, live)
    return st.astype(jnp.uint32).sum()


print(f"== {SEG_MIB} MiB, backend={jax.default_backend()}, "
      f"U={root_unroll()}", flush=True)
timeit("full fused", full, base)
timeit("pages only", pages, base)
timeit("root only (word-major)", root_only, flat0)
