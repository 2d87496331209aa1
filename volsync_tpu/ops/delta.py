"""Device-side primitives for the rsync-style delta scan.

The reference's delta transfer happens inside the rsync binary (reference:
mover-rsync/source.sh:54): the destination sends per-block (weak, strong)
checksums; the source slides the weak checksum over every offset, and on a
weak match verifies with the strong checksum, emitting copy ops for matched
blocks and literal bytes for the rest.

TPU mapping: the full rolling-weak scan is one parallel pass
(volsync_tpu.ops.rolling); membership against the destination's weak set is
a vectorized binary search (jnp.searchsorted) over the sorted signature;
candidate offsets are compacted on device; strong verification batches MD5
over the candidate windows (volsync_tpu.ops.md5.md5_windows_device).
The mover's path runs ``delta_sig_flat`` / ``delta_match_rows`` /
``delta_md5_flat`` on one staged buffer of a fixed size (a batch of
small files packed into it, or one window of a long file), so a tree of
any sizes meets a small fixed set of programs; ``match_offsets`` and
``verify_candidates`` stay as the exact-shape oracle the tests hold
that path to (engine/deltasync.compute_delta).
The final greedy left-to-right op selection (sequential, but only over the
sparse verified matches) runs on host in the engine layer
(volsync_tpu.engine.deltasync).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from volsync_tpu.ops.md5 import (
    md5_contiguous_blocks_device,
    md5_fixed_blocks_device,
    md5_windows_device,
)
from volsync_tpu.ops.rolling import block_weak_checksums, rolling_weak_checksums


def build_signature(data: jax.Array, *, block_len: int):
    """Destination side: per-block (weak uint32, strong md5 [nb,4] uint32).

    The tail block's strong checksum is computed over its true length by the
    host wrapper in the engine; here all full blocks are batched on device.
    """
    weak = block_weak_checksums(data, block_len=block_len)
    L = int(data.shape[0])
    n_full = L // block_len
    if block_len % 1024 == 0:
        # The destination's blocks tile the file contiguously: the
        # strong checksums take the gather-free transposed-lane path
        # (pick_block_len sizes are always eligible; the windowed
        # gather kernel stays for sparse match verification and for
        # caller-chosen odd block sizes).
        strong = md5_contiguous_blocks_device(
            jax.lax.slice_in_dim(data, 0, n_full * block_len),
            block_len=block_len)
    else:
        starts = jnp.arange(n_full, dtype=jnp.int32) * block_len
        strong = md5_fixed_blocks_device(data, starts,
                                         block_len=block_len)
    return weak, strong


@functools.partial(jax.jit, static_argnames=("window", "max_candidates"))
def match_offsets(data: jax.Array, sorted_weak: jax.Array, *,
                  window: int, max_candidates: int):
    """Source side: offsets whose rolling weak checksum hits the signature.

    data:        [L] uint8 source buffer.
    sorted_weak: [nb] uint32, destination block weak checksums, sorted.
    Returns (cand_idx [max_candidates] int32 ascending with L as fill,
    true_count) — host re-runs with a larger bound on truncation.
    """
    L = data.shape[0]
    if sorted_weak.shape[0] == 0 or L < window:  # static: no possible match
        return (jnp.full((max_candidates,), L, dtype=jnp.int32),
                jnp.zeros((), dtype=jnp.int32))
    weak = rolling_weak_checksums(data, window=window)  # [L-window+1]
    pos = jnp.searchsorted(sorted_weak, weak)
    pos = jnp.clip(pos, 0, sorted_weak.shape[0] - 1)
    hit = sorted_weak[pos] == weak
    cand = jnp.nonzero(hit, size=max_candidates, fill_value=L)[0]
    return cand.astype(jnp.int32), jnp.sum(hit)


def verify_candidates(data: jax.Array, cand: np.ndarray, *,
                      block_len: int) -> np.ndarray:
    """Batch MD5 over candidate windows -> [n, 4] uint32 states (host array)."""
    if len(cand) == 0:
        return np.zeros((0, 4), dtype=np.uint32)
    starts = jnp.asarray(np.asarray(cand, dtype=np.int32))
    return np.asarray(md5_fixed_blocks_device(data, starts, block_len=block_len))  # lint: ignore[VL501] host-result contract: one batched strong-check fetch


_M16 = np.uint32(0xFFFF)
#: row length of the 2-D view the flat programs work in: every block
#: length the engine picks is a multiple of it, so a shift by one block
#: is a shift by whole rows (1-D strides and odd shifts lower badly on
#: the TPU, docs/performance.md op classes)
_COLS = 1024


@functools.partial(jax.jit, static_argnames=("block_len",))
def delta_sig_flat(data: jax.Array, *, block_len: int):
    """Destination side, one staged buffer: the weak and the strong
    checksum of every ``block_len`` block of ``data`` ([N] uint8,
    N % block_len == 0, block_len % 1024 == 0) -> ([N / block_len]
    uint32, [N / block_len, 4] uint32). Files are laid into the buffer
    at block-aligned offsets by the engine, which keeps the blocks that
    are whole blocks of a file and checksums short tails on the host."""
    nb = data.shape[0] // block_len
    x = data.reshape(nb, block_len).astype(jnp.uint32)
    # b = sum (block_len - i) * x_i: uint32 wraparound keeps the
    # mod-2^16 residue exact, as in ops/rolling.py
    w = (np.uint32(block_len)
         - jnp.arange(block_len, dtype=jnp.uint32))[None, :]
    a = jnp.sum(x, axis=1, dtype=jnp.uint32) & _M16
    b = jnp.sum(x * w, axis=1, dtype=jnp.uint32) & _M16
    weak = a | (b << np.uint32(16))
    strong = md5_contiguous_blocks_device(data, block_len=block_len)
    return weak, strong


def _flat_prefix(v: jax.Array):
    """Exclusive prefix sums of ``v`` ([R, _COLS] uint32, row-major
    flat order) and the total, by rows: a short scan along each row
    plus a scan of the row totals."""
    inc = jnp.cumsum(v, axis=1, dtype=jnp.uint32)
    rows = inc[:, -1]
    base = jnp.cumsum(rows, dtype=jnp.uint32) - rows
    return inc - v + base[:, None], base[-1] + rows[-1]


@functools.partial(jax.jit, static_argnames=("window", "max_candidates"))
def delta_match_rows(data: jax.Array, sorted_weak: jax.Array,
                     n_sig: jax.Array, rows: jax.Array,
                     row_until: jax.Array, lo: jax.Array, *,
                     window: int, max_candidates: int):
    """Source side, one staged buffer: among the offsets of the listed
    rows of 1024, those whose rolling weak checksum over ``window``
    bytes is in the signatures' weak set. The engine lists the rows
    that the block-aligned probe (``delta_sig_flat`` on the same
    buffer) left open: a search at every offset costs a table lookup an
    offset, which is what the chip is slow at (a gather an element),
    while the prefix sums over the whole buffer are cheap.

    data:        [N] uint8: files laid at slot starts (or one window of
                 a long file), zeros between them.
    sorted_weak: [nb_cap] uint32: the weak checksums of the full blocks
                 of every signature of the buffer, merged and sorted,
                 0xFFFFFFFF past the first ``n_sig``. A hit on another
                 file's block is a false candidate like any other: the
                 engine keeps a candidate only if its own file's
                 signature holds (weak, strong).
    rows:        [G] int32 ascending: the rows to search (row r holds
                 the offsets r * 1024 ...); unused places repeat a row
                 with ``row_until`` 0.
    row_until:   [G] int32: for each listed row, the flat offset one
                 past the last window start that lies wholly inside the
                 file that owns the row (0: nothing). Masks padding and
                 windows that would run past a file's end.
    lo:          int32 scalar: candidates below it are left out (the
                 engine's next round after an overflow).

    Returns (cand [max_candidates] int32 flat offsets ascending, N as
    fill; their weak checksums [max_candidates] uint32; the true count
    from ``lo`` on).
    """
    N = data.shape[0]
    R = N // _COLS
    x = data.reshape(R, _COLS).astype(jnp.uint32)
    k = (jnp.arange(R, dtype=jnp.uint32)[:, None] * np.uint32(_COLS)
         + jnp.arange(_COLS, dtype=jnp.uint32)[None, :])
    S, s_all = _flat_prefix(x)
    T, t_all = _flat_prefix(k * x)
    shift = window // _COLS  # E[k + window]: whole rows up

    def ahead(E, total):
        fill = jnp.broadcast_to(total, (shift, _COLS))
        return jnp.concatenate([E[shift:], fill], axis=0)

    dS = ahead(S, s_all) - S
    dT = ahead(T, t_all) - T
    a = dS & _M16
    b = ((k + np.uint32(window)) * dS - dT) & _M16
    weak = (a | (b << np.uint32(16)))[rows]              # [G, _COLS]
    at = (rows[:, None] * _COLS
          + jnp.arange(_COLS, dtype=jnp.int32)[None, :])
    pos = jnp.searchsorted(sorted_weak, weak.reshape(-1),
                           method="sort").reshape(weak.shape)
    found = sorted_weak[jnp.minimum(pos, sorted_weak.shape[0] - 1)]
    hit = ((found == weak) & (pos < n_sig) & (at < row_until[:, None])
           & (at >= lo)).reshape(-1)
    G = rows.shape[0]
    idx = jnp.nonzero(hit, size=max_candidates, fill_value=G * _COLS)[0]
    safe = jnp.minimum(idx, G * _COLS - 1)
    cand = jnp.where(idx < G * _COLS, at.reshape(-1)[safe], N)
    return cand.astype(jnp.int32), weak.reshape(-1)[safe], jnp.sum(hit)


@functools.partial(jax.jit, static_argnames=("block_len",))
def delta_md5_flat(data: jax.Array, starts: jax.Array, *,
                   block_len: int) -> jax.Array:
    """The strong check of one staged buffer's candidates: MD5 of the
    ``block_len`` bytes at each of ``starts`` ([K] int32, padded with 0
    by the engine to its fixed capacity) -> [K, 4] uint32 states."""
    return md5_windows_device(data, starts, block_len=block_len)
