"""rsync-style rolling weak checksums, parallelized via prefix sums.

The reference's rsync mover delegates the delta scan to the rsync binary
(reference: mover-rsync/source.sh:54, ``rsync -aAhHSxz --delete``), whose
hot loop slides an Adler-32-style weak checksum over every byte offset of
the source file to find blocks already present on the destination. The
sequential "roll" (add the entering byte, drop the leaving byte) looks
inherently serial — but both components are window sums, so they collapse
into differences of prefix sums, and prefix sums are log-depth parallel
scans on TPU.

Checksum of window x[k .. k+W-1] (rsync weak32):

    a(k) = sum x_j                  (mod 2^16)
    b(k) = sum (k + W - j) x_j      (mod 2^16)   -- position-weighted
    s(k) = a(k) | b(k) << 16

With S = exclusive-cumsum(x) and T = exclusive-cumsum(j * x_j), all in
uint32 *wraparound* arithmetic (consistent mod 2^32, and 2^16 | 2^32 so the
final mod-2^16 residues are exact):

    a(k) = S[k+W] - S[k]
    b(k) = (k + W) * (S[k+W] - S[k]) - (T[k+W] - T[k])
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_M16 = np.uint32(0xFFFF)


def _excl_cumsum_u32(x: jax.Array) -> jax.Array:
    c = jnp.cumsum(x, dtype=jnp.uint32)
    return jnp.pad(c, (1, 0))  # [L+1], exclusive


@functools.partial(jax.jit, static_argnames=("window",))
def rolling_weak_checksums(data: jax.Array, *, window: int) -> jax.Array:
    """Weak checksum at every offset: [L] uint8 -> [max(L - window + 1, 0)] uint32.

    Buffers shorter than the window have no full window; returns empty
    (callers checksum short tails at their true length via
    block_weak_checksums / weak_checksum_host).
    """
    L = data.shape[0]
    if L < window:  # static shape: resolved at trace time
        return jnp.zeros((0,), dtype=jnp.uint32)
    x = data.astype(jnp.uint32)
    j = jnp.arange(L, dtype=jnp.uint32)
    S = _excl_cumsum_u32(x)
    T = _excl_cumsum_u32(j * x)
    k = jnp.arange(L - window + 1, dtype=jnp.uint32)
    dS = S[window:] - S[: L - window + 1]
    dT = T[window:] - T[: L - window + 1]
    a = dS & _M16
    b = ((k + np.uint32(window)) * dS - dT) & _M16
    return a | (b << np.uint32(16))


@functools.partial(jax.jit, static_argnames=("block_len",))
def block_weak_checksums(data: jax.Array, *, block_len: int) -> jax.Array:
    """Weak checksum of each non-overlapping block ([L] uint8 -> [nb] uint32).

    The final partial block (if any) is checksummed at its true (shorter)
    length, matching the signature the delta engine builds for file tails.
    """
    L = data.shape[0]
    nb = (L + block_len - 1) // block_len
    x = data.astype(jnp.uint32)
    j = jnp.arange(L, dtype=jnp.uint32)
    S = _excl_cumsum_u32(x)
    T = _excl_cumsum_u32(j * x)
    starts = jnp.arange(nb, dtype=jnp.uint32) * np.uint32(block_len)
    ends = jnp.minimum(starts + np.uint32(block_len), np.uint32(L))
    dS = S[ends] - S[starts]
    dT = T[ends] - T[starts]
    a = dS & _M16
    b = (ends * dS - dT) & _M16
    return a | (b << np.uint32(16))


def weak_checksum_host(block) -> int:
    """The same checksum of one block on the host (numpy): short file
    tails in the engine, and the tests' reference."""
    x = np.frombuffer(block, np.uint8).astype(np.uint64)
    n = len(x)
    a = int(x.sum()) & 0xFFFF
    b = int((x * np.arange(n, 0, -1, dtype=np.uint64)).sum()) & 0xFFFF
    return a | (b << 16)
