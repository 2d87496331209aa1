"""Empirical tuning of the Pallas SHA-256 leaf kernel on the live chip.

Variants: sublane tile size (register pressure: a [S,128] u32 value
spans S/8 vregs; the unrolled SHA round loop keeps ~24 values live, so
S=32 implies ~96+ live vregs -> spills), and the XLA scan path for
reference. All timed with per-iteration salts (distinct arguments
per call) and a scalar checksum fetch (forces completion without a
bulk result transfer).
"""
import functools
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from volsync_tpu.compile_cache import configure as _configure_cache  # noqa: E402

_configure_cache()

import jax
import jax.numpy as jnp
import numpy as np

from volsync_tpu.ops import segment as seg
from volsync_tpu.ops import sha256 as sha

SEG_MIB = int(sys.argv[1]) if len(sys.argv) > 1 else 64
N = SEG_MIB << 20
F = N // 4096
ITERS = 20

rng = np.random.RandomState(7)
host = rng.randint(0, 256, size=(N,), dtype=np.uint8)
base = jnp.asarray(host)
jax.block_until_ready(base)


def make_kernel(lane_sub: int):
    """The leaf kernel with a parameterized sublane tile."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    lane_tile = lane_sub * 128

    def kernel(x_ref, o_ref, st_ref):
        S = st_ref.shape[1]
        t = pl.program_id(1)

        @pl.when(t == 0)
        def _():
            for j in range(8):
                st_ref[j] = jnp.full((S, 128), np.uint32(sha._H0[j]),
                                     jnp.uint32)

        state = tuple(st_ref[j] for j in range(8))
        w = x_ref[0]
        state = sha._round64_p(state, [w[j] for j in range(16)])
        for j in range(8):
            st_ref[j] = state[j]

        @pl.when(t == 63)
        def _():
            zero = jnp.zeros((S, 128), jnp.uint32)
            pad = [zero + np.uint32(0x80000000)] + [zero] * 13 + [
                zero, zero + np.uint32(4096 * 8)]
            fin = sha._round64_p(state, pad)
            for j in range(8):
                o_ref[j] = fin[j]

    def run(x, npp):
        return pl.pallas_call(
            kernel,
            grid=(npp // lane_tile, 64),
            in_specs=[pl.BlockSpec((1, 16, lane_sub, 128),
                                   lambda i, t: (t, 0, i, 0),
                                   memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec((8, lane_sub, 128),
                                   lambda i, t: (0, i, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((8, npp // 128, 128),
                                           jnp.uint32),
            scratch_shapes=[pltpu.VMEM((8, lane_sub, 128), jnp.uint32)],
        )(x)

    return run, lane_tile


def page_digest_variant(lane_sub: int):
    run, lane_tile = make_kernel(lane_sub)
    npp = max(lane_tile, (F + lane_tile - 1) // lane_tile * lane_tile)

    @jax.jit
    def fn(d, s):
        d = d ^ s
        r = d.reshape(F, 4096)
        x2 = sha.pack_words_rows(r)  # [F, 1024]
        if npp != F:
            x2 = jnp.pad(x2, ((0, npp - F), (0, 0)))
        xt = seg._pallas_transpose(x2)
        x = xt.reshape(64, 16, npp // 128, 128)
        out = run(x, npp)
        return out.reshape(-1)[::4097].sum()  # tiny checksum fetch

    return fn


@jax.jit
def xla_scan_variant(d, s):
    d = d ^ s
    wb = sha.pack_words(d)
    rows0 = jnp.arange(F, dtype=jnp.int32) * 64
    dig = sha._sha256_rows(wb, rows0, 4096)
    return dig.reshape(-1)[::61].sum()


def timeit(name, fn):
    # A scalar FETCH of the last pipelined output is the completion
    # barrier: executions run in dispatch order, so it fences the
    # whole batch (jax.block_until_ready on it would do the same).
    float(fn(base, jnp.uint8(0)))  # warm/compile
    t0 = time.perf_counter()
    out = None
    for i in range(ITERS):
        out = fn(base, jnp.uint8(i + 1))  # lint: ignore[VL502] per-dispatch timing is the measurement
    float(out)
    dt = (time.perf_counter() - t0) / ITERS
    print(f"{name:28s} {dt * 1e3:8.2f} ms  {N / dt / (1 << 30):7.2f} GiB/s",
          flush=True)


print(f"== {SEG_MIB} MiB, backend={jax.default_backend()}", flush=True)
for ls in (int(x) for x in (sys.argv[2] if len(sys.argv) > 2
                            else "32,16,8").split(",")):
    timeit(f"pallas lane_sub={ls}", page_digest_variant(ls))
if os.environ.get("TUNE_XLA"):
    timeit("xla scan", xla_scan_variant)
