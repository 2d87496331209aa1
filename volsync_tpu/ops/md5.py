"""Batched MD5 as vectorized uint32 JAX ops.

The reference's rsync mover uses MD5 as the strong per-block checksum in its
delta-transfer algorithm (reference: mover-rsync/source.sh:54 invokes
``rsync -aAhHSxz``; rsync's wire protocol pairs a rolling Adler-32-style
weak checksum with an MD5 strong checksum). Our delta engine
(volsync_tpu.engine.deltasync) verifies weak-checksum match candidates with
this batched MD5, vectorized across candidate offsets.

Same architecture as volsync_tpu.ops.sha256: ``lax.scan`` over 64-byte
message blocks, batch dimension across messages, uint32 wraparound lanes.
MD5 is little-endian (words and the trailing 64-bit length), unlike SHA-256.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

# T[i] = floor(2^32 * |sin(i+1)|) (RFC 1321 §3.4). Computed in double
# precision, which reproduces the canonical table; golden tests vs hashlib
# enforce bit-exactness.
_T = np.array(
    [int(math.floor(abs(math.sin(i + 1)) * 2**32)) & 0xFFFFFFFF for i in range(64)],
    dtype=np.uint32,
)

_S = np.array(
    [7, 12, 17, 22] * 4 + [5, 9, 14, 20] * 4 + [4, 11, 16, 23] * 4 + [6, 10, 15, 21] * 4,
    dtype=np.int32,
)

# Message word index per operation.
_G = np.array(
    [i for i in range(16)]
    + [(5 * i + 1) % 16 for i in range(16)]
    + [(3 * i + 5) % 16 for i in range(16)]
    + [(7 * i) % 16 for i in range(16)],
    dtype=np.int32,
)

_A0 = np.array([0x67452301, 0xEFCDAB89, 0x98BADCFE, 0x10325476], dtype=np.uint32)


def _rotl(x: jax.Array, n) -> jax.Array:
    n = n if isinstance(n, jax.Array) else np.uint32(n)
    return (x << n) | (x >> (np.uint32(32) - n))


def _compress_unrolled(state: jax.Array, block: jax.Array) -> jax.Array:
    """Straight-line MD5 rounds (TPU path; see sha256._compress)."""
    m = [block[..., t] for t in range(16)]
    a, b, c, d = (state[..., i] for i in range(4))
    for i in range(64):
        if i < 16:
            f = (b & c) | (~b & d)
        elif i < 32:
            f = (d & b) | (~d & c)
        elif i < 48:
            f = b ^ c ^ d
        else:
            f = c ^ (b | ~d)
        tmp = a + f + _T[i] + m[int(_G[i])]
        a, d, c, b = d, c, b, b + _rotl(tmp, int(_S[i]))
    out = jnp.stack([a, b, c, d], axis=-1)
    return state + out


def _compress_scan(state: jax.Array, block: jax.Array) -> jax.Array:
    """Rolled MD5 rounds (CPU path — fast compile): scan over the
    (T, S, G) tables; per-phase boolean function is a 4-way select on
    ``i // 16``."""
    m = jnp.moveaxis(block, -1, 0)  # [16, ...]
    quad = tuple(state[..., i] for i in range(4))
    xs = (
        jnp.arange(64, dtype=jnp.int32),
        jnp.asarray(_T),
        jnp.asarray(_S).astype(jnp.uint32),
        jnp.asarray(_G),
    )

    def round_step(carry, x):
        a, b, c, d = carry
        i, t_i, s_i, g_i = x
        phase = i >> 2 >> 2  # i // 16
        f = jnp.where(
            phase == 0, (b & c) | (~b & d),
            jnp.where(
                phase == 1, (d & b) | (~d & c),
                jnp.where(phase == 2, b ^ c ^ d, c ^ (b | ~d)),
            ),
        )
        tmp = a + f + t_i + m[g_i]
        return (d, b + _rotl(tmp, s_i), b, c), None

    (a, b, c, d), _ = jax.lax.scan(round_step, quad, xs)
    return state + jnp.stack([a, b, c, d], axis=-1)


def _compress(state: jax.Array, block: jax.Array) -> jax.Array:
    """state: [..., 4] uint32; block: [..., 16] uint32 little-endian words.
    Backend-selected at trace time (jit caches are per-backend)."""
    if jax.default_backend() == "cpu":
        return _compress_scan(state, block)
    return _compress_unrolled(state, block)


@jax.jit
def md5_blocks(blocks: jax.Array, nblocks: jax.Array) -> jax.Array:
    """blocks: [B, N, 16] uint32 LE words (padded); nblocks: [B] int32.

    Returns [B, 4] uint32 state words (little-endian serialization gives the
    standard digest).
    """
    B, N, _ = blocks.shape
    state0 = jnp.broadcast_to(jnp.asarray(_A0), (B, 4))
    # Align shard_map varying-axis metadata with the input (see sha256.py).
    state0 = state0 ^ (blocks[:, 0, :4] & jnp.uint32(0))
    xs_blocks = jnp.transpose(blocks, (1, 0, 2))
    active = (jnp.arange(N, dtype=jnp.int32)[:, None]
              < nblocks[None, :].astype(jnp.int32))

    def step(state, xs):
        block, act = xs
        new = _compress(state, block)
        return jnp.where(act[:, None], new, state), None

    state, _ = jax.lax.scan(step, state0, (xs_blocks, active))
    return state


def md5_pack_host(chunks: list[bytes]):
    """Pad messages into [B, N, 16] uint32 little-endian blocks + nblocks."""
    B = len(chunks)
    nb = np.array([(len(c) + 9 + 63) // 64 for c in chunks], dtype=np.int32)
    N = int(nb.max()) if B else 1
    buf = np.zeros((B, N * 64), dtype=np.uint8)
    for i, c in enumerate(chunks):
        L = len(c)
        buf[i, :L] = np.frombuffer(c, dtype=np.uint8)
        buf[i, L] = 0x80
        buf[i, nb[i] * 64 - 8 : nb[i] * 64] = np.frombuffer(
            np.array([L * 8], dtype="<u8").tobytes(), dtype=np.uint8  # lint: ignore[VL106] 8 B length field
        )
    words = buf.reshape(B, N, 16, 4).astype(np.uint32)
    blocks = (
        words[..., 0] | (words[..., 1] << 8)
        | (words[..., 2] << 16) | (words[..., 3] << 24)
    )
    return blocks, nb


def md5_many(chunks: list[bytes]) -> list[bytes]:
    """Hash byte strings; returns standard 16-byte MD5 digests."""
    if not chunks:
        return []
    blocks, nblocks = md5_pack_host(chunks)
    out = np.asarray(md5_blocks(jnp.asarray(blocks), jnp.asarray(nblocks)))  # lint: ignore[VL501] host-digest convenience API: one batched fetch
    le = out.astype("<u4")
    return [le[i].tobytes() for i in range(le.shape[0])]  # lint: ignore[VL106] 16 B digests


@functools.partial(jax.jit, static_argnames=("block_len",))
def md5_fixed_blocks_device(data: jax.Array, starts: jax.Array,
                            *, block_len: int) -> jax.Array:
    """MD5 of fixed-length windows of a device buffer (delta strong check).

    data: [L] uint8; starts: [B] int32 window starts; every window has
    length ``block_len`` (callers pad the tail window host-side or exclude
    it). Returns [B, 4] uint32 states.
    """
    B = starts.shape[0]
    L = data.shape[0]
    padded = (block_len + 9 + 63) // 64 * 64
    N = padded // 64
    j = jnp.arange(padded, dtype=jnp.int32)
    idx = jnp.clip(starts.astype(jnp.int32)[:, None] + j[None, :], 0, L - 1)
    raw = data[idx]
    msg = jnp.where(j[None, :] < block_len, raw,
                    jnp.where(j[None, :] == block_len, jnp.uint8(0x80), jnp.uint8(0)))
    # Little-endian 64-bit bit length in the final 8 bytes; block_len is
    # static so the length bytes are a host-computed constant row.
    len_bytes = np.zeros((padded,), dtype=np.uint8)
    len_bytes[-8:] = np.frombuffer(np.array([block_len * 8], dtype="<u8").tobytes(),  # lint: ignore[VL106] 8 B length field
                                   dtype=np.uint8)
    is_len = np.zeros((padded,), dtype=bool)
    is_len[-8:] = True
    msg = jnp.where(jnp.asarray(is_len)[None, :], jnp.asarray(len_bytes)[None, :], msg)
    words = msg.reshape(B, N, 16, 4).astype(jnp.uint32)
    blocks = (
        words[..., 0] | (words[..., 1] << np.uint32(8))
        | (words[..., 2] << np.uint32(16)) | (words[..., 3] << np.uint32(24))
    )
    nb = jnp.full((B,), N, dtype=jnp.int32)
    return md5_blocks(blocks, nb)


@functools.partial(jax.jit, static_argnames=("block_len",))
def md5_contiguous_blocks_device(data: jax.Array, *,
                                 block_len: int) -> jax.Array:
    """MD5 of every contiguous ``block_len`` window of ``data``
    ([L] uint8, L % block_len == 0) -> [L/block_len, 4] uint32 states.

    The delta signature's bulk path (engine/deltasync.build_signature:
    the destination's blocks tile its file, so its strong checksums
    never need the windowed gather of md5_fixed_blocks_device, which is
    reserved for sparse match verification). TPU-fast by construction
    (docs/performance.md op classes): little-endian words pack via 2-D
    minor-dim strides, a Pallas tile-transpose puts blocks on the lane
    axis, and the per-64-byte-block scan takes row slices of the
    transposed table — no data-sized XLA gather or transpose anywhere.
    block_len must be a multiple of 1024 (the Pallas transpose tiles
    256 word columns; pick_block_len yields pow2 >= 4 KiB) — the
    build_signature wrapper falls back to the windowed kernel for other
    sizes.
    """
    assert block_len % 1024 == 0, "fast path needs 256-word columns"
    L = data.shape[0]
    return _md5_rows(data.reshape(L // block_len, block_len))


def _md5_rows(r: jax.Array) -> jax.Array:
    """MD5 of every row of ``r`` ([B, block_len] uint8, block_len a
    multiple of 1024) -> [B, 4] uint32 states: the body of
    ``md5_contiguous_blocks_device``, shared with the windowed strong
    check (``md5_windows_device``), traced inside the caller's jit."""
    from volsync_tpu.ops.sha256 import pack_words_rows, use_pallas_leaves

    B, block_len = r.shape
    w = pack_words_rows(r, little_endian=True)  # [B, W] LE words
    if not use_pallas_leaves():
        # Shares sha256's predicate: off the TPU the XLA transpose
        # stands in for the Mosaic kernel.
        xt = jnp.transpose(w, (1, 0))
        Bp = B
    else:
        from volsync_tpu.ops.segment import _pallas_transpose

        Bp = (B + 255) // 256 * 256
        if Bp != B:
            w = jnp.pad(w, ((0, Bp - B), (0, 0)))
        xt = _pallas_transpose(w)  # [W, Bp]

    state0 = jnp.broadcast_to(jnp.asarray(_A0), (Bp, 4))

    def step(state, t):
        m = jnp.stack(
            [jax.lax.dynamic_index_in_dim(xt, t * 16 + j, 0, False)
             for j in range(16)], axis=-1)  # [Bp, 16]
        return _compress(state, m), None

    state, _ = jax.lax.scan(step, state0,
                            jnp.arange(block_len // 64, dtype=jnp.int32))
    # FIPS pad for a fixed full-length message: one constant extra block
    # (0x80 terminator then the 64-bit LE bit length).
    pad = np.zeros((16,), dtype=np.uint32)
    pad[0] = 0x80
    bitlen = block_len * 8
    pad[14] = bitlen & 0xFFFFFFFF
    pad[15] = (bitlen >> 32) & 0xFFFFFFFF
    pad_block = jnp.broadcast_to(jnp.asarray(pad), (Bp, 16))
    return _compress(state, pad_block)[:B]


@functools.partial(jax.jit, static_argnames=("block_len",))
def md5_windows_device(data: jax.Array, starts: jax.Array, *,
                       block_len: int) -> jax.Array:
    """MD5 of the ``block_len`` bytes at each of ``starts`` in ``data``
    ([L] uint8; starts [K] int32, each at most L - block_len) ->
    [K, 4] uint32 states: the delta scan's strong check at a fixed
    candidate capacity. Each window is one contiguous slice (a gather
    of K rows of block_len bytes, not of K * block_len single bytes)
    hashed by the signature's transposed-lane body. block_len must be a
    multiple of 1024."""
    assert block_len % 1024 == 0, "fast path needs 256-word columns"
    rows = jax.vmap(
        lambda s: jax.lax.dynamic_slice(data, (s,), (block_len,)))(starts)
    return _md5_rows(rows)
