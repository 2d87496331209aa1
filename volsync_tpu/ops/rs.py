"""Batched GF(2^8) Reed-Solomon erasure coding as JAX kernels.

Replaces the 2x full-pack mirrors (``VOLSYNC_PACK_COPIES=2``) with
systematic k+m striping: a sealed pack body is split into k equal data
shards and extended with m parity shards so ANY k of the k+m shards
reconstruct the body — m arbitrary losses survive at (k+m)/k storage
instead of failing on the second copy (ROADMAP item 4; arxiv
2508.05797's vector-lane chunking, arxiv 2602.22237's
lightweight-metadata DR layout).

Design notes
------------
- Field: GF(2^8) mod the primitive polynomial 0x11D, generator 2 — the
  classic RS-256 field. Multiplication is the log/exp-table form
  ``exp[log[a] + log[b]]`` with a doubled exp table so the index sum
  never needs a mod-255; zeros are masked (log[0] is undefined).
- Generator matrix: systematic ``[I_k ; C]`` where C is the m x k
  Cauchy matrix ``C[i][j] = 1/(x_i ^ y_j)`` with ``x_i = k + i`` and
  ``y_j = j``. Every k x k submatrix of ``[I_k ; C]`` is invertible, so
  the code is MDS: any k surviving rows decode.
- Dispatch shape mirrors the fused SHA-256 (ops/sha256.py): shards are
  packed host-side into a ``[k, P, _PAGE]`` uint8 page grid (pages as
  the vector lanes, ``pad_pages_to`` bounds jit recompiles the way
  ``pad_blocks_to`` does for sha256_pack_host), and the kernel is one
  log-gather per input shard plus one exp-gather per (row, shard)
  coefficient term — all table lookups, no field loops on device.
- Zero padding is harmless: RS is linear, zero bytes encode to zero
  parity, and the caller trims to the true shard length.
- Decoding inverts the tiny k x k surviving submatrix on the host
  (Gauss-Jordan over GF(2^8) on a matrix of at most 32x32 bytes) and
  applies the SAME device matmul kernel with the inverse rows — encode
  and decode share one jitted primitive per coefficient matrix.
- Bit-exactness is enforced by golden tests against the pure-NumPy
  oracle (``rs_encode_np`` / ``rs_reconstruct_np``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from volsync_tpu.obs import record_copy

_GF_POLY = 0x11D  # x^8 + x^4 + x^3 + x^2 + 1, primitive over GF(2)
_PAGE = 4096      # page-grid minor dim (matches the pack seal alignment)
_MAX_SHARDS = 256  # field size bounds k + m

# exp/log tables for generator 2. The exp table is doubled (510 live
# entries) so exp[log[a] + log[b]] never needs an explicit mod 255.
_GF_EXP = np.zeros(512, dtype=np.uint8)
_GF_LOG = np.zeros(256, dtype=np.int32)
_x = 1
for _i in range(255):
    _GF_EXP[_i] = _x
    _GF_LOG[_x] = _i
    _x <<= 1
    if _x & 0x100:
        _x ^= _GF_POLY
_GF_EXP[255:510] = _GF_EXP[:255]
del _x, _i


def gf_mul_np(a, b) -> np.ndarray:
    """Elementwise GF(2^8) multiply (NumPy oracle path)."""
    a = np.asarray(a, dtype=np.uint8)
    b = np.asarray(b, dtype=np.uint8)
    prod = _GF_EXP[_GF_LOG[a] + _GF_LOG[b]]
    return np.where((a == 0) | (b == 0), 0, prod).astype(np.uint8)


def gf_inv_np(a: int) -> int:
    """GF(2^8) multiplicative inverse of a nonzero scalar."""
    if a == 0:
        raise ZeroDivisionError("gf_inv_np(0)")
    return int(_GF_EXP[255 - _GF_LOG[a]])


def rs_generator_matrix(k: int, m: int) -> np.ndarray:
    """[m, k] uint8 Cauchy parity rows (x_i = k+i, y_j = j)."""
    if k < 1 or m < 1 or k + m > _MAX_SHARDS:
        raise ValueError(f"invalid RS scheme {k}+{m}")
    rows = np.zeros((m, k), dtype=np.uint8)
    for i in range(m):
        for j in range(k):
            rows[i, j] = gf_inv_np((k + i) ^ j)
    return rows


def rs_full_matrix(k: int, m: int) -> np.ndarray:
    """[k+m, k] systematic matrix: identity data rows over Cauchy parity."""
    return np.concatenate(
        [np.eye(k, dtype=np.uint8), rs_generator_matrix(k, m)], axis=0)


def gf_mat_inv_np(a: np.ndarray) -> np.ndarray:
    """Invert a [k, k] GF(2^8) matrix by Gauss-Jordan (host side; k is
    tiny). Raises ValueError if singular — cannot happen for submatrices
    of the Cauchy construction, but decode guards anyway."""
    k = a.shape[0]
    aug = np.concatenate(
        [a.astype(np.uint8), np.eye(k, dtype=np.uint8)], axis=1)
    for col in range(k):
        piv = col
        while piv < k and aug[piv, col] == 0:
            piv += 1
        if piv == k:
            raise ValueError("singular GF(2^8) matrix")
        if piv != col:
            aug[[col, piv]] = aug[[piv, col]]
        aug[col] = gf_mul_np(gf_inv_np(int(aug[col, col])), aug[col])
        for row in range(k):
            if row != col and aug[row, col]:
                aug[row] ^= gf_mul_np(int(aug[row, col]), aug[col])
    return aug[:, k:].copy()


# -- NumPy golden oracle -----------------------------------------------------


def rs_encode_np(data: np.ndarray, m: int) -> np.ndarray:
    """[k, L] uint8 data shards -> [m, L] parity shards (pure NumPy)."""
    k = data.shape[0]
    gm = rs_generator_matrix(k, m)
    out = np.zeros((m, data.shape[1]), dtype=np.uint8)
    for i in range(m):
        acc = np.zeros(data.shape[1], dtype=np.uint8)
        for j in range(k):
            acc ^= gf_mul_np(gm[i, j], data[j])
        out[i] = acc
    return out


def rs_decode_plan(k: int, m: int, have: list[int]) -> tuple[list[int],
                                                             np.ndarray]:
    """Pick k surviving shard indices and build the [k, k] inverse that
    maps their rows back to the data shards. ``have`` is the sorted set
    of healthy shard indices (0..k-1 data, k..k+m-1 parity); data shards
    are preferred so a fully-systematic survival decodes by identity."""
    if len(have) < k:
        raise ValueError(f"need {k} shards, have {len(have)}")
    use = sorted(have)[:k]
    sub = rs_full_matrix(k, m)[use]
    return use, gf_mat_inv_np(sub)


def rs_reconstruct_np(shards: dict[int, np.ndarray], k: int,
                      m: int) -> np.ndarray:
    """Recover the [k, L] data shards from any k healthy shards
    (pure-NumPy oracle; ``shards`` maps shard index -> [L] uint8)."""
    use, inv = rs_decode_plan(k, m, sorted(shards))
    L = shards[use[0]].shape[0]
    out = np.zeros((k, L), dtype=np.uint8)
    for j in range(k):
        acc = np.zeros(L, dtype=np.uint8)
        for i in range(k):
            acc ^= gf_mul_np(inv[j, i], shards[use[i]])
        out[j] = acc
    return out


# -- device kernels ----------------------------------------------------------


@functools.lru_cache(maxsize=128)
def _gf_matmul_fn(rows_key: tuple, r: int, k: int):
    """Jitted GF(2^8) matrix-times-shards kernel, cached per coefficient
    matrix (encode rows and decode inverses both land here). The matrix
    is static: zero coefficients drop their term at trace time, and each
    surviving term is one exp-table gather on pre-shared log lanes."""
    rows = np.array(rows_key, dtype=np.uint8).reshape(r, k)
    logc = _GF_LOG[rows]  # [r, k] static int32 coefficient logs
    exp_t = jnp.asarray(_GF_EXP)
    log_t = jnp.asarray(_GF_LOG)

    @jax.jit
    def matmul(data: jax.Array) -> jax.Array:
        # data: [k, P, _PAGE] uint8 page grid -> [r, P, _PAGE] uint8.
        dlog = jnp.take(log_t, data.astype(jnp.int32))  # shared log lanes
        zero = data == jnp.uint8(0)
        outs = []
        for i in range(r):
            acc = None
            for j in range(k):
                if rows[i, j] == 0:
                    continue
                term = jnp.take(exp_t, dlog[j] + np.int32(logc[i, j]))
                term = jnp.where(zero[j], jnp.uint8(0), term)
                acc = term if acc is None else acc ^ term
            if acc is None:
                acc = jnp.zeros(data.shape[1:], dtype=jnp.uint8)
            outs.append(acc)
        return jnp.stack(outs)

    return matmul


def gf_matmul_device(rows: np.ndarray, data: jax.Array) -> jax.Array:
    """Apply a static [r, k] GF(2^8) matrix to a [k, P, _PAGE] page grid."""
    r, k = rows.shape
    key = tuple(np.asarray(rows, dtype=np.uint8).reshape(-1).tolist())
    return _gf_matmul_fn(key, r, k)(data)


def rs_pack_host(shards: list, *, pad_pages_to: int | None = None):
    """Pack k equal-length shard buffers into the [k, P, _PAGE] page
    grid. Zero-pads the tail page (linear-code safe) and optionally
    rounds P up to a multiple of ``pad_pages_to`` to bound recompiles,
    mirroring sha256_pack_host's pad_blocks_to."""
    k = len(shards)
    if k == 0:
        raise ValueError("rs_pack_host: no shards")
    L = len(shards[0])
    pages = max((L + _PAGE - 1) // _PAGE, 1)
    if pad_pages_to is not None:
        pages = ((pages + pad_pages_to - 1) // pad_pages_to) * pad_pages_to
    buf = np.zeros((k, pages * _PAGE), dtype=np.uint8)
    for i, s in enumerate(shards):
        if len(s) != L:
            raise ValueError("rs_pack_host: unequal shard lengths")
        buf[i, :L] = np.frombuffer(s, dtype=np.uint8)
    return buf.reshape(k, pages, _PAGE), L


def rs_encode_device(data_grid: jax.Array, m: int) -> jax.Array:
    """[k, P, _PAGE] data page grid -> [m, P, _PAGE] parity page grid."""
    k = int(data_grid.shape[0])
    return gf_matmul_device(rs_generator_matrix(k, m), data_grid)


def rs_reconstruct_device(shards: dict, k: int, m: int,
                          shard_len: int) -> list[bytes]:
    """Recover all k data shards from any k healthy shards on device.

    ``shards`` maps shard index -> buffer; returns the k data shards as
    ``shard_len``-byte strings. Survived data shards pass through the
    identity rows of the inverse, so the all-systematic case is pure
    gathers with no field math surviving dead-code elimination."""
    use, inv = rs_decode_plan(k, m, sorted(shards))
    grid, L = rs_pack_host([shards[i] for i in use])
    if L != shard_len:
        raise ValueError("rs_reconstruct_device: shard length mismatch")
    out = np.asarray(gf_matmul_device(inv, grid))
    flat = out.reshape(k, -1)[:, :shard_len]
    record_copy("ec.decode", k * shard_len)
    return [flat[i].tobytes() for i in range(k)]
