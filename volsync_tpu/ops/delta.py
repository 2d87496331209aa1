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
any sizes meets a small fixed set of programs. There the scan and the
membership are taken for the listed rows alone, a group of rows at a
time in a loop on the device (one sort of a group's checksums merged
with the table; no gather or scatter an offset). ``match_offsets`` and
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


def _by_rows(v: jax.Array) -> jax.Array:
    """A flat array as rows of ``_COLS`` (of its whole length where it
    is shorter): the 2-D view the scans below work in."""
    return v.reshape(-1, min(_COLS, v.shape[0]))


def _flat_prefix(v: jax.Array):
    """Exclusive prefix sums of ``v`` ([R, C] uint32, row-major flat
    order) and the total, by rows: a short scan along each row plus a
    scan of the row totals."""
    inc = jnp.cumsum(v, axis=1, dtype=jnp.uint32)
    rows = inc[:, -1]
    base = jnp.cumsum(rows, dtype=jnp.uint32) - rows
    return inc - v + base[:, None], base[-1] + rows[-1]


def _flat_cummax(v: jax.Array) -> jax.Array:
    """The running maximum of ``v`` ([R, C] uint32, row-major flat
    order), by rows as ``_flat_prefix``."""
    inc = jax.lax.cummax(v, axis=1)
    rows = jax.lax.cummax(inc[:, -1], axis=0)
    base = jnp.concatenate([jnp.zeros((1,), v.dtype), rows[:-1]])
    return jnp.maximum(inc, base[:, None])


_NOT_A_QUERY = np.int32(np.iinfo(np.int32).max)


@functools.partial(jax.jit, static_argnames=(
    "window", "group_rows", "max_candidates", "capacity"))
def delta_match_rows(data: jax.Array, sorted_weak: jax.Array,
                     n_sig: jax.Array, rows: jax.Array,
                     row_until: jax.Array, n_groups: jax.Array,
                     first_group: jax.Array, lo: jax.Array, *,
                     window: int, group_rows: int, max_candidates: int,
                     capacity: int):
    """Source side, one staged buffer: among the offsets of the listed
    rows of 1024, those whose rolling weak checksum over ``window``
    bytes is in the signatures' weak set. The engine lists the rows
    that the block-aligned probe (``delta_sig_flat`` on the same
    buffer) left open, all of a buffer's in one call; the program takes
    them ``group_rows`` at a time in a loop on the device, so its cost
    follows the rows it is given (two sorts of a group's offsets).

    The whole buffer is read once a call, for the byte totals of its
    rows. A row's checksums come from those and from the bytes of the
    row itself and of the row one block ahead (the prefix sums ``S``
    and ``T`` of ops/rolling.py at the row's offsets: the row's base
    plus a scan inside the row). Membership is one sort of a group's
    checksums merged with the table: a checksum is in the table where
    the table value carried forward through the sorted order equals
    it. The hits alone go back to the offsets' order (a second sort
    brings the first ``max_candidates`` of a group to the front).

    data:        [N] uint8: files laid at slot starts (or one window of
                 a long file), zeros between them.
    sorted_weak: [nb_cap] uint32: the weak checksums of the full blocks
                 of every signature of the buffer, merged and sorted,
                 0xFFFFFFFF past the first ``n_sig``. A hit on another
                 file's block is a false candidate like any other: the
                 engine keeps a candidate only if its own file's
                 signature holds (weak, strong).
    rows:        [groups * group_rows] int32 ascending: the rows to
                 search (row r holds the offsets r * 1024 ...); unused
                 places hold any row with ``row_until`` 0.
    row_until:   as ``rows``: for each listed row, the flat offset one
                 past the last window start that lies wholly inside the
                 file that owns the row (0: nothing). Masks padding and
                 windows that would run past a file's end.
    n_groups:    int32 scalar: the groups of ``group_rows`` rows that
                 hold a listed row.
    first_group: int32 scalar: the group to start at (0, or where the
                 call before stopped).
    lo:          int32 scalar: candidates below it are left out (the
                 engine's next call after one that stopped early).

    Returns (cand [capacity] int32 flat offsets ascending, N as fill;
    their weak checksums [capacity] uint32; [taken, next_group,
    groups_run] int32). The loop stops early, with ``next_group`` under
    ``n_groups``, at a group that holds more than ``max_candidates``
    candidates (the first ``max_candidates`` of it are taken: the
    engine calls again from that group with ``lo`` past the last one
    taken) or when fewer than ``max_candidates`` places are left.
    """
    N = data.shape[0]
    R = N // _COLS
    K = min(max_candidates, group_rows * _COLS)
    if capacity < K:
        raise ValueError(f"capacity {capacity} under one group's {K}")
    shift = window // _COLS  # k + window: whole rows up
    x = data.reshape(R, _COLS)
    col = jnp.arange(_COLS, dtype=jnp.uint32)[None, :]
    # S and T (the sums of x[j] and of j * x[j] over j < k) at the
    # start of every row, and past the last: one read of the buffer
    xw = x.astype(jnp.uint32)
    tot = jnp.sum(xw, axis=1, dtype=jnp.uint32)
    wtot = jnp.sum(xw * col, axis=1, dtype=jnp.uint32)
    first = jnp.arange(R, dtype=jnp.uint32) * np.uint32(_COLS)
    s_row, s_all = _flat_prefix(_by_rows(tot))
    t_row, t_all = _flat_prefix(_by_rows(first * tot + wtot))
    s_row = jnp.concatenate([s_row.reshape(-1), s_all[None]])
    t_row = jnp.concatenate([t_row.reshape(-1), t_all[None]])

    def prefixes(r):
        """S and T at every offset of the rows ``r`` ([G] int32; a row
        past the buffer reads the totals)."""
        inside = (r < R)[:, None]
        v = jnp.where(inside, x[jnp.minimum(r, R - 1)], 0).astype(jnp.uint32)
        base = jnp.minimum(r, R)
        k0 = base.astype(jnp.uint32)[:, None] * np.uint32(_COLS)
        vc = v * col
        p = jnp.cumsum(v, axis=1, dtype=jnp.uint32) - v
        wp = jnp.cumsum(vc, axis=1, dtype=jnp.uint32) - vc
        return s_row[base][:, None] + p, t_row[base][:, None] + k0 * p + wp

    # the table's place in a group's sort: its values first among
    # equals (-1 sorts under every offset), its padding neither table
    # nor query
    pad = -sorted_weak.shape[0] % _COLS
    table = jnp.pad(sorted_weak, (0, pad),
                    constant_values=np.uint32(0xFFFFFFFF))
    table_tag = jnp.where(
        jnp.arange(table.shape[0], dtype=jnp.int32) < n_sig,
        np.int32(-1), _NOT_A_QUERY)
    least = sorted_weak[0]
    local = jnp.arange(group_rows * _COLS, dtype=jnp.int32)

    def group(carry):
        g, taken, cand, weak_out, _full = carry
        r = jax.lax.dynamic_slice(rows, (g * group_rows,), (group_rows,))
        until = jax.lax.dynamic_slice(row_until, (g * group_rows,),
                                      (group_rows,))
        S0, T0 = prefixes(r)
        S1, T1 = prefixes(r + shift)
        k = r.astype(jnp.uint32)[:, None] * np.uint32(_COLS) + col
        dS = S1 - S0
        a = dS & _M16
        b = ((k + np.uint32(window)) * dS - (T1 - T0)) & _M16
        weak = (a | (b << np.uint32(16))).reshape(-1)
        at = k.astype(jnp.int32)
        asked = ((at < until[:, None]) & (at >= lo)).reshape(-1)
        value, tag = jax.lax.sort(
            (jnp.concatenate([table, weak]),
             jnp.concatenate([table_tag,
                              jnp.where(asked, local, _NOT_A_QUERY)])),
            num_keys=2, is_stable=False)  # no two (value, tag) are told apart
        is_table = tag < 0
        carried = _flat_cummax(_by_rows(
            jnp.where(is_table, value, 0))).reshape(-1)
        hit = (~is_table & (tag != _NOT_A_QUERY) & (value >= least)
               & (carried == value))
        found = jnp.sum(hit, dtype=jnp.int32)
        idx = jax.lax.sort(jnp.where(hit, tag, _NOT_A_QUERY),
                           is_stable=False)[:K]
        safe = jnp.minimum(idx, group_rows * _COLS - 1)
        offs = jnp.where(idx != _NOT_A_QUERY, at.reshape(-1)[safe], N)
        cand = jax.lax.dynamic_update_slice(cand, offs, (taken,))
        weak_out = jax.lax.dynamic_update_slice(weak_out, weak[safe],
                                                (taken,))
        full = found > K
        return (jnp.where(full, g, g + 1), taken + jnp.minimum(found, K),
                cand, weak_out, full)

    def more(carry):
        g, taken, _cand, _weak, full = carry
        return (g < n_groups) & ~full & (taken + K <= capacity)

    g, taken, cand, weak_out, full = jax.lax.while_loop(more, group, (
        first_group, jnp.int32(0), jnp.full((capacity,), N, jnp.int32),
        jnp.zeros((capacity,), jnp.uint32), jnp.bool_(False)))
    # a slice written past ``taken`` held the next group's fill
    cand = jnp.where(jnp.arange(capacity) < taken, cand, N)
    ran = g - first_group + full.astype(jnp.int32)
    return cand, weak_out, jnp.stack([taken, g, ran])


@functools.partial(jax.jit, static_argnames=("block_len",))
def delta_md5_flat(data: jax.Array, starts: jax.Array, *,
                   block_len: int) -> jax.Array:
    """The strong check of one staged buffer's candidates: MD5 of the
    ``block_len`` bytes at each of ``starts`` ([K] int32, padded with 0
    by the engine to its fixed capacity) -> [K, 4] uint32 states."""
    return md5_windows_device(data, starts, block_len=block_len)
