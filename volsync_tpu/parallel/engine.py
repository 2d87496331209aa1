"""Sharded chunk+hash pipeline step — the framework's flagship compute.

One step consumes a [W, L] batch of byte streams (W independent
relationship "waves" × L bytes of volume data) laid out over the
(wave, seq) mesh and produces, fully on device:

- the gear-hash CDC boundary-candidate mask for every byte position
  (the restic-chunker replacement — SURVEY.md §2.2 #25),
- SHA-256 digests of every fixed-size block (the dedup/content-address
  hash — restic blob ids / syncthing block hashes),
- global dedup statistics via collectives: a bloom sketch of digests
  unioned with ``psum`` over the whole mesh, plus candidate/byte counts.

Cross-shard correctness: a gear hash at position i depends on the 31
preceding bytes, so each seq shard sends its 31-byte tail to its right
neighbor with ``ppermute`` (the sequence-parallel halo exchange — the
same pattern ring attention uses for block boundaries). The reference has
no intra-volume parallelism at all (SURVEY.md §5 "long-context" note);
this step is where the TPU build beats it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from volsync_tpu.ops.gearcdc import DEFAULT_PARAMS, GearParams, _mix_u32
from volsync_tpu.ops.sha256 import sha256_blocks
from volsync_tpu.parallel.mesh import SEQ_AXIS, WAVE_AXIS

_HALO = 31  # gear window is 32 bytes -> 31 bytes of left context


def _gear_doubling(g: jax.Array) -> jax.Array:
    """The 5 shift-scale-add passes turning per-byte table values into the
    32-byte-window gear hash (see ops/gearcdc.py)."""
    h = g
    pad_cfg = [(0, 0)] * (h.ndim - 1)
    for m in (1, 2, 4, 8, 16):
        shifted = jnp.pad(h[..., :-m], pad_cfg + [(m, 0)])
        h = h + (shifted << np.uint32(m))
    return h


def _gear_lastaxis(data: jax.Array, seed: int) -> jax.Array:
    """Gear hash over the last axis ([..., L] uint8 -> [..., L] uint32),
    log-depth doubling form with an arithmetic (gather-free) byte table
    (see ops/gearcdc.py)."""
    g = _mix_u32(data.astype(jnp.uint32) + np.uint32(seed & 0xFFFFFFFF))
    return _gear_doubling(g)


def sha256_fixed_blocks(blocks_u8: jax.Array) -> jax.Array:
    """SHA-256 of equal-length messages ([B, L] uint8, L % 64 == 0 -> [B, 8]).

    Fixed length means the FIPS 180-4 padding is one constant extra block,
    applied as a final compression — no gathers, so this is the cheapest
    bulk-hash path (the fixed-block dedup table and the syncthing-style
    block index; variable-length CDC chunks go through
    sha256_chunks_device).

    Memory layout: every bulk intermediate keeps a large minor dimension.
    A [B, nblocks, 16]-words layout would be 8x-padded by the TPU's
    (8, 128) tiling (and [.., 4] byte groups 32x), so words are extracted
    with strided slices from a [B, L/4] array and fed to the scan as a
    16-tuple of [nblocks, B] arrays instead.
    """
    from volsync_tpu.ops.sha256 import _H0, _compress

    B, L = blocks_u8.shape
    assert L % 64 == 0, "fixed-block path requires 64-byte-aligned blocks"
    x = blocks_u8.astype(jnp.uint32)  # [B, L]
    w = (
        (x[:, 0::4] << np.uint32(24)) | (x[:, 1::4] << np.uint32(16))
        | (x[:, 2::4] << np.uint32(8)) | x[:, 3::4]
    )  # [B, L/4] big-endian message words
    xs = tuple(jnp.transpose(w[:, t::16]) for t in range(16))  # 16 x [nb, B]

    state0 = jnp.broadcast_to(jnp.asarray(_H0), (B, 8))
    state0 = state0 ^ (w[:, :8] & jnp.uint32(0))  # varying-axis alignment

    def step(state, wt):
        return _compress(state, jnp.stack(wt, axis=-1)), None

    state, _ = jax.lax.scan(step, state0, xs)

    pad = np.zeros((16,), dtype=np.uint32)
    pad[0] = 0x80000000
    bitlen = L * 8
    pad[14] = (bitlen >> 32) & 0xFFFFFFFF
    pad[15] = bitlen & 0xFFFFFFFF
    pad_block = (state[:, :1] & jnp.uint32(0)) ^ jnp.asarray(pad)[None, :]
    return _compress(state, pad_block)


def make_chunk_hash_step(mesh, *, block_len: int = 64 * 1024,
                         params: GearParams = DEFAULT_PARAMS,
                         bloom_log2: int = 20):
    """Build the jitted sharded step for ``mesh``.

    Returns ``step(data)`` where data is [W, L] uint8 with W divisible by
    the wave axis and L by (seq axis * block_len). Output dict:

    - ``digests``   [W, L // block_len, 8] uint32 — per-block SHA-256,
      sharded (wave, seq);
    - ``cand_mask`` [W, L] bool — CDC boundary candidates (strict mask),
      sharded (wave, seq);
    - ``bloom``     [2^bloom_log2] uint32 — global digest-occupancy counts
      (replicated; membership = >0);
    - ``stats``     dict of replicated scalars: total_bytes,
      total_candidates, distinct_block_estimate, duplicate_block_estimate.
    """
    seed = params.seed
    mask_s = np.uint32(params.dense_mask_s)  # per-position evaluation
    bloom_size = 1 << bloom_log2

    def local_step(data):  # data: [Wl, Sl] — this shard's slice
        n_seq = jax.lax.axis_size(SEQ_AXIS)
        seq_i = jax.lax.axis_index(SEQ_AXIS)

        # Sequence-parallel halo: my left context is the previous shard's
        # 31-byte tail. ppermute shifts tails one step to the right along
        # the seq ring; shard 0 (true buffer start) zeroes its halo.
        tail = data[:, -_HALO:]
        halo = jax.lax.ppermute(
            tail, SEQ_AXIS, [(i, (i + 1) % n_seq) for i in range(n_seq)]
        )
        ext = jnp.concatenate([halo, data], axis=1)  # [Wl, HALO + Sl]
        g = _mix_u32(ext.astype(jnp.uint32) + np.uint32(seed & 0xFFFFFFFF))
        # Shard 0 starts the true buffer: its halo positions must
        # contribute *nothing* to the hash (the unsharded recurrence
        # starts from h=0), so zero the table values — zeroing the halo
        # bytes would still contribute _mix_u32(seed) per position.
        g = jnp.where(
            (seq_i == 0)
            & (jnp.arange(ext.shape[1], dtype=jnp.int32) < _HALO)[None, :],
            jnp.uint32(0), g,
        )
        h = _gear_doubling(g)[:, _HALO:]  # [Wl, Sl]
        cand = (h & mask_s) == 0

        Wl, Sl = data.shape
        nb = Sl // block_len
        digests = sha256_fixed_blocks(
            data.reshape(Wl * nb, block_len)
        ).reshape(Wl, nb, 8)

        # Dedup sketch: one bit per digest (keyed by word 0 — uniform for
        # SHA-256), psum-unioned across the whole mesh.
        slot = digests[..., 0].reshape(-1) & np.uint32(bloom_size - 1)
        local_bloom = jnp.zeros((bloom_size,), jnp.uint32).at[slot].max(
            jnp.uint32(1)
        )
        bloom = jax.lax.psum(local_bloom, (WAVE_AXIS, SEQ_AXIS))

        total_cand = jax.lax.psum(
            jnp.sum(cand, dtype=jnp.uint32), (WAVE_AXIS, SEQ_AXIS)
        )
        distinct = jnp.sum(bloom > 0, dtype=jnp.uint32)
        return digests, cand, bloom, total_cand, distinct

    sharded = shard_map(
        local_step,
        mesh=mesh,
        in_specs=P(WAVE_AXIS, SEQ_AXIS),
        out_specs=(
            P(WAVE_AXIS, SEQ_AXIS, None),
            P(WAVE_AXIS, SEQ_AXIS),
            P(),
            P(),
            P(),
        ),
    )

    jitted = jax.jit(sharded)

    def step(data):
        # Byte/block totals are static shape facts — computed host-side in
        # Python ints (a device uint32 psum would wrap at 4 GiB batches).
        W, L = data.shape
        total_blocks = W * (L // block_len)
        digests, cand, bloom, total_cand, distinct = jitted(data)
        return {
            "digests": digests, "cand_mask": cand, "bloom": bloom,
            "stats": {
                "total_bytes": W * L,
                "total_candidates": total_cand,
                "distinct_block_estimate": distinct,
                "duplicate_block_estimate": total_blocks - distinct,
            },
        }

    return step


@functools.partial(jax.jit, static_argnames=("block_len", "mask_s", "seed"))
def _single_chip_step(data, *, block_len: int, mask_s: int, seed: int):
    h = _gear_lastaxis(data, seed)
    cand = (h & np.uint32(mask_s)) == 0
    nb = data.shape[0] // block_len
    digests = sha256_fixed_blocks(data[: nb * block_len].reshape(nb, block_len))
    return digests, jnp.sum(cand, dtype=jnp.uint32)


def chunk_hash_block(data, *, block_len: int = 64 * 1024,
                     params: GearParams = DEFAULT_PARAMS):
    """Single-chip pipeline on one flat buffer: ([L] uint8) ->
    (block digests [L//block_len, 8], CDC candidate count). The jittable
    core behind it (``_single_chip_step``) is what ``__graft_entry__.entry``
    exposes for the driver's compile check."""
    return _single_chip_step(
        jnp.asarray(data), block_len=block_len, mask_s=params.dense_mask_s,
        seed=params.seed,
    )
