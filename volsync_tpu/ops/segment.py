"""Fused single-dispatch segment pipeline: chunk + hash + Merkle roots.

The per-segment protocol of the original engine (engine/chunker.py) was
two device dispatches with two result fetches: (1) compacted CDC
candidates -> host FastCDC walk, (2) leaf digests -> host root assembly.
Every result fetch costs a fixed round trip (not measured on the
current machine), and the digest fetch moves 32 bytes
per 4 KiB leaf — ~8 MiB per GiB of input. This module collapses the
whole segment into ONE device program with ONE small result fetch
(~20 KiB: the chunk table + one 32-byte blob id per chunk).

The enabling format choice is ``GearParams.align == 4096``: cut
positions land on the 4 KiB Merkle-leaf grid, so every full leaf of
every chunk IS a page of the segment — leaf hashing becomes *contiguous*
page hashing with no gather at all, and at most ONE leaf per segment
(the final eof tail) is partial. That matters because on TPU the only
fast bulk primitives are elementwise/reduction ops and Pallas kernels:
XLA-level gathers and transposes of data-sized arrays were the slow
op class when this was written (not measured on the current machine),
so the pipeline is built exclusively from:

- elementwise candidate masks + small ``nonzero`` compactions;
- a ``lax.while_loop`` FastCDC walk over compacted candidates,
  bit-identical to ``gearcdc._select_boundaries_py`` (golden-tested);
- a Pallas tile-transpose (VMEM shuffles, ~HBM speed) feeding the
  Pallas SHA-256 lane kernel, digests kept in kernel layout;
- a tail stage for the ONE partial leaf a lane (``_tail_leaf_digests``:
  its page as one row, a while_loop to the longest live tail; the
  arbitrary-offset hasher ``sha256_chunks_device`` is only its oracle);
- a root stage that hashes "VMRK1" || le64(len) || leaf-digests
  (repo/blobid.py) with a while_loop over message blocks — a 17-word
  gather per block per chunk lane, nothing data-sized.

Replaces the hot loop of the reference's vendored restic engine
(reference: mover-restic/entry.sh:63, Dockerfile:7-10) on its real
streaming path; engine/chunker.DeviceChunkHasher dispatches this program
when the page-aligned format is active.

Capacity model: all shapes are static under jit. ``segment_caps`` sizes
the candidate/chunk tables from the segment length with generous
headroom; the packed result carries the TRUE counts and the host
retries with doubled capacities iff real data overflowed (adversarial
inputs only). eof is a static arg (two compiled variants per shape).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from volsync_tpu.obs import count as obs_count
from volsync_tpu.obs import record_copy, span
from volsync_tpu.ops.gearcdc import GearParams, gear_at_aligned
from volsync_tpu.ops.sha256 import (
    _H0,
    _LANE_SUB,
    _LANE_TILE,
    _compress,
    _sha256_leaf_kernel,
    _sha256_rows,
    pack_words,
    pack_words_rows,
    use_pallas_leaves,
)

LEAF_SIZE = 4096  # == repo.blobid.LEAF_SIZE (static repo format constant)

#: Largest flat [S*P] byte view one batched dispatch may address: the
#: view is gathered with int32 indices (x64 off; TPUs index in int32).
#: chunk_hash_segments refuses bigger batches; BatchedSegmentHasher
#: splits them. Module constant so tests can exercise the split with
#: small shapes.
_MAX_FLAT_BYTES = (1 << 31) - 1
_DOMAIN_WORD0 = int.from_bytes(b"VMRK", "big")  # "VMRK1" header, word 0
_DOMAIN_BYTE4 = b"VMRK1"[4]


from volsync_tpu.ops.gearcdc import _pow2ceil_int as _pow2ceil


def _buffer_bucket(length: int) -> int:
    """Pad target for input buffers. Shapes are static under jit, so an
    unbounded variety of buffer lengths (every file tail is unique) would
    mean a fresh multi-second XLA compile each — pad into a small fixed
    set instead: pow2 up to 8 MiB, then multiples of 8 MiB."""
    if length <= 8 * 1024 * 1024:
        return _pow2ceil(length, 64 * 1024)
    m = 8 * 1024 * 1024
    return (length + m - 1) // m * m


def segment_caps(padded_len: int, params: GearParams) -> tuple[int, int]:
    """(cand_cap, chunk_cap) for a padded segment length.

    Expected lax-candidate density is 2^-(eff_bits-norm) per aligned
    position — the default gives ~8-16x headroom. chunk_cap covers the
    min_size packing bound exactly (+ slack for the eof tail)."""
    chunk_cap = _pow2ceil(padded_len // params.min_size + 2, 16)
    cand_cap = max(4096, _pow2ceil(4 * padded_len // params.avg_size, 4096))
    return cand_cap, chunk_cap


def count_dispatch(lanes: int, lanes_padded: int, bytes_valid: int,
                   bytes_padded: int) -> None:
    """One run of a segment program, counted the same way on both ways
    to the device (the batched program and the single-lane one):
    ``ops.lanes`` / ``ops.dispatches`` is the occupancy of a run,
    ``ops.bytes_valid`` / ``ops.bytes_padded`` the useful share of the
    bytes handed to the device."""
    obs_count("ops.dispatches")
    obs_count("ops.lanes", lanes)
    obs_count("ops.lanes_padded", lanes_padded)
    obs_count("ops.bytes_valid", bytes_valid)
    obs_count("ops.bytes_padded", bytes_padded)


def _compact_candidates(mask: jax.Array, cand_cap: int, R: int,
                        align: int) -> jax.Array:
    """[R] bool candidate mask -> [cand_cap] sorted aligned cut
    positions, sentinel-padded (sentinel > any valid position). The one
    compaction used by BOTH the single-segment and batched programs —
    the sentinel/fill protocol must never drift between them."""
    sentinel = jnp.int32(2**31 - 2)
    ridx = jnp.nonzero(mask, size=cand_cap, fill_value=R)[0]
    return jnp.where(ridx < R,
                     ridx.astype(jnp.int32) * align + (align - 1),
                     sentinel)


def _word_index(n_pages_pad: int):
    """THE home of the digest-table index formula (word-major: digest
    word j of page p at j*n_pages_pad + p, the row-major flattening of
    the SHA kernel's [8, B/128, 128] output) — every producer, tail
    override, root gather, and host decode must route through this one
    mapping or the layouts silently desynchronize."""
    return lambda j, p: j * n_pages_pad + p


def _apply_tail_overrides(flat: jax.Array, n_pages_pad: int,
                          tail_pages: jax.Array, tail_digs: jax.Array,
                          has_tail: jax.Array) -> jax.Array:
    """Overwrite the page-digest table with per-lane partial tail-leaf
    digests (lanes with has_tail False write out of bounds -> dropped).
    tail_pages/has_tail: [N]; tail_digs: [N, 8]. Shared by the single,
    batched, and span programs so the layout indexing (_word_index)
    has ONE home."""
    wi = _word_index(n_pages_pad)
    j8 = jnp.arange(8, dtype=jnp.int32)[None, :]
    ovr = jnp.where(has_tail[:, None], wi(j8, tail_pages[:, None]),
                    8 * n_pages_pad)  # OOB -> dropped
    return flat.at[ovr.reshape(-1)].set(tail_digs.reshape(-1), mode="drop")


_TAIL_BLOCKS = LEAF_SIZE // 64 + 1  # blocks of the longest tail leaf, 4,095 B

#: Fewest lanes the tail stage runs its compressions over. Measured on a
#: v5e (scripts/profile_tail.py): ONE lane costs 62 us a compression
#: (4.1 ms for a 65-block tail: the whole ~4 ms a dispatch the stage
#: used to cost), 16 lanes 2.3 us for all sixteen; lanes are free, a
#: one-lane compression is not, so a lone tail rides with inert lanes.
_TAIL_MIN_LANES = 16


def _tail_leaf_digests(data: jax.Array, tail_page: jax.Array,
                       tail_len: jax.Array) -> jax.Array:
    """SHA-256 of each lane's partial tail leaf: the first ``tail_len``
    bytes of page ``tail_page``. THE tail stage of every segment program
    (single, batched, span, mesh), so it has ONE home.

    data: [P] uint8, P % LEAF_SIZE == 0; tail_page: [N] int32 page
    index into ``data``; tail_len: [N] int32 in [0, LEAF_SIZE), 0 =
    the lane has no tail: it takes no compression and its row is the
    initial state (callers drop it through ``has_tail``). Returns
    [N, 8] uint32, bit-exact vs hashlib.

    A tail leaf always starts on a page boundary and is shorter than a
    page: invariants of every caller, so none of the generality of
    ``sha256_chunks_device`` (arbitrary byte offsets: a byte gather and
    a scan of always 65 compressions over exactly the caller's lanes)
    is paid here. Each page is taken as one contiguous row and packed
    to words on a 2-D minor dim (see ``pack_words``: through [8N, 512],
    so that one lane is not a 1-D stride), the FIPS padding is laid on
    in words, and a while_loop over a block-major message runs to the
    LARGEST live lane's block count, zero iterations for a segment with
    no tail (every non-eof one), at no fewer than ``_TAIL_MIN_LANES``.
    """
    N = tail_page.shape[0]
    F = data.shape[0] // LEAF_SIZE
    u32 = jnp.uint32
    rows = data.reshape(F, LEAF_SIZE)[jnp.clip(tail_page, 0, F - 1)]
    words = pack_words_rows(rows.reshape(N * 8, LEAF_SIZE // 8))
    lanes = max(N, _TAIL_MIN_LANES)
    words = jnp.pad(words.reshape(N, LEAF_SIZE // 4),
                    ((0, lanes - N), (0, 16)))  # [lanes, 65 * 16]
    tail_len = jnp.pad(tail_len.astype(jnp.int32), (0, lanes - N))

    # Word q holds bytes 4q..4q+3. Word tail_len // 4 keeps its first
    # tail_len % 4 bytes and takes the 0x80 terminator after them;
    # later words are zero but the last of block nb-1, the bit length
    # (< 2^15: the high length word stays zero).
    q = jnp.arange(_TAIL_BLOCKS * 16, dtype=jnp.int32)[None, :]
    qterm = (tail_len >> 2)[:, None]
    r8 = ((tail_len & 3).astype(u32) << u32(3))[:, None]
    partial = (words & ~(u32(0xFFFFFFFF) >> r8)) | (u32(0x80000000) >> r8)
    nb = jnp.where(tail_len > 0, (tail_len + 9 + 63) // 64, 0)  # [lanes]
    msg = jnp.where(q < qterm, words,
                    jnp.where(q == qterm, partial, u32(0)))
    msg = jnp.where(q == (nb * 16 - 1)[:, None],
                    (tail_len.astype(u32) << u32(3))[:, None], msg)
    # Block-major, so an iteration indexes the major dim (half the time
    # of a 16-word dynamic_slice of the minor dim at 16 lanes).
    blocks = msg.reshape(lanes, _TAIL_BLOCKS, 16).transpose(1, 0, 2)
    max_nb = jnp.max(nb)

    def cond(c):
        return c[0] < max_nb

    def body(c):
        n, state = c
        new = _compress(state, blocks[n])
        return n + 1, jnp.where((n < nb)[:, None], new, state)

    state0 = jnp.broadcast_to(jnp.asarray(_H0), (lanes, 8))
    _, state = jax.lax.while_loop(cond, body, (jnp.int32(0), state0))
    return state[:N]


def _select_boundaries_device(pos_s, ns, pos_l, nl, valid_len, *,
                              min_size: int, avg_size: int, max_size: int,
                              chunk_cap: int, eof: bool,
                              align: int = 0, n_rows: int = 0):
    """FastCDC walk == gearcdc._select_boundaries_py, successor-table
    form.

    pos_s/pos_l: sorted compacted candidate cut positions (padded with a
    sentinel greater than any valid position); ns/nl their true counts.
    Returns (starts[chunk_cap], lens[chunk_cap], count, consumed).

    With the page-aligned format every reachable chunk start is a
    multiple of ``align`` (cuts are ≡ align-1 mod align; the max_size
    fallback advances by a page multiple), so the cut decision is a pure
    function of the start ROW. The cut/emit tables for ALL ``n_rows``
    possible starts are precomputed with two BATCHED searchsorted calls
    (one vector op each), and the sequential walk degrades to a
    per-step table gather. Measured on v5e (64 MiB): ~6 ms of
    per-iteration searchsorted pairs -> <1 ms. ``align``/``n_rows`` == 0
    keeps the generic per-iteration form (callers without row
    structure).
    """
    i32 = jnp.int32
    L = valid_len.astype(i32)
    cap_s = pos_s.shape[0]
    cap_l = pos_l.shape[0]

    def cut_emit(pos):
        """(cut, emit) of a chunk starting at ``pos`` — scalar in the
        per-iteration form, [n_rows] in the table precompute. ONE home
        for the FastCDC decision so the two forms cannot drift."""
        lo = pos + (min_size - 1)
        mid = pos + (avg_size - 1)
        hi = pos + (max_size - 1)
        i = jnp.searchsorted(pos_s, lo, side="left").astype(i32)
        cs = pos_s[jnp.clip(i, 0, cap_s - 1)]
        lim_s = jnp.minimum(jnp.minimum(mid - 1, L - 1), hi)
        found_s = (i < ns) & (cs <= lim_s)
        j = jnp.searchsorted(pos_l, jnp.maximum(lo, mid),
                             side="left").astype(i32)
        cl = pos_l[jnp.clip(j, 0, cap_l - 1)]
        found_l = (j < nl) & (cl <= jnp.minimum(hi, L - 1))
        hi_ok = hi <= L - 1
        cut = jnp.where(found_s, cs,
                        jnp.where(found_l, cl,
                                  jnp.where(hi_ok, hi, L - 1)))
        # eof may be a static Python bool (single-segment path, part of
        # the jit cache key) OR a traced per-lane scalar (batched path).
        emit = found_s | found_l | hi_ok | jnp.asarray(eof, jnp.bool_)
        return cut, emit

    use_table = (align > 0 and (align & (align - 1)) == 0 and n_rows > 0
                 and min_size % align == 0 and max_size % align == 0
                 and avg_size % align == 0)
    if use_table:
        # Successor tables over every possible start row: two BATCHED
        # searchsorted calls replace a searchsorted pair per iteration.
        cut_tab, emit_tab = cut_emit(jnp.arange(n_rows, dtype=i32) * align)
        shift = int(align).bit_length() - 1

    def cond(c):
        pos, cnt, done, _, _ = c
        return (~done) & (pos < L) & (cnt < chunk_cap)

    def body(c):
        pos, cnt, done, starts, lens = c
        if use_table:
            r = jnp.clip(pos >> shift, 0, n_rows - 1)
            cut = cut_tab[r]
            emit = emit_tab[r]
        else:
            cut, emit = cut_emit(pos)
        # Predicated append: drop the write when not emitting.
        wr = jnp.where(emit, cnt, chunk_cap)
        starts = starts.at[wr].set(pos, mode="drop")
        lens = lens.at[wr].set(cut - pos + 1, mode="drop")
        return (jnp.where(emit, cut + 1, pos), cnt + emit.astype(i32),
                ~emit, starts, lens)

    init = (jnp.int32(0), jnp.int32(0), jnp.bool_(False),
            jnp.zeros((chunk_cap,), i32), jnp.zeros((chunk_cap,), i32))
    pos, cnt, _, starts, lens = jax.lax.while_loop(cond, body, init)
    return starts, lens, cnt, pos


# ---------------------------------------------------------------------------
# Page-digest stage: contiguous leaf hashing, no gathers
# ---------------------------------------------------------------------------

def _n_pages_pad(F: int) -> int:
    """Page count padded for the Pallas lane grid (identity on CPU).
    The single source of truth — chunk_hash_segment, page_digests, and
    span_roots_device must agree or their word-major indexing into
    _page_digests_flat desynchronizes."""
    if not use_pallas_leaves():
        return F
    return max(_LANE_TILE, (F + _LANE_TILE - 1) // _LANE_TILE * _LANE_TILE)


def _transpose_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...].T


def _pallas_transpose(x: jax.Array) -> jax.Array:
    """[R, C] u32 -> [C, R] via VMEM tile shuffles, in place of XLA's
    own data-sized transpose lowering (neither is measured on the
    current machine). R % 256 == 0, C % 256 == 0."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, C = x.shape
    return pl.pallas_call(
        _transpose_kernel,
        grid=(R // 256, C // 256),
        in_specs=[pl.BlockSpec((256, 256), lambda i, j: (i, j),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((256, 256), lambda i, j: (j, i),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((C, R), jnp.uint32),
        name="transpose_tiles",
    )(x)


def _page_digests_flat(data: jax.Array, n_pages_pad: int) -> jax.Array:
    """SHA-256 of every 4 KiB page of ``data``, flat WORD-MAJOR layout
    (result[j * n_pages_pad + p] = word j of page p's digest).

    data: [P] uint8, P % LEAF_SIZE == 0; hashes are computed for
    ``n_pages_pad`` >= P/LEAF_SIZE pages (the pad region hashes zeros
    and is never referenced by the root stage).

    TPU: pack_words (elementwise) -> Pallas tile-transpose -> the
    Pallas SHA lane kernel; the digest output stays in the kernel's
    [8, B/128, 128] layout, whose row-major flattening IS word-major.
    CPU (tests/dry-runs): the XLA scan path + a small transpose.
    """
    P = data.shape[0]
    F = P // LEAF_SIZE

    if not use_pallas_leaves():
        wb = pack_words(data)  # [P/64, 16]
        rows0 = jnp.arange(n_pages_pad, dtype=jnp.int32) * (LEAF_SIZE // 64)
        rows0 = jnp.minimum(rows0, P // 64 - LEAF_SIZE // 64)
        dig = _sha256_rows(wb, rows0, LEAF_SIZE)  # [n_pages_pad, 8]
        return dig.T.reshape(-1)

    # Words packed straight into [F, 1024]: any [*, 16]-minor layout
    # tile-pads 8x on TPU, and 1-D stride-4 slices lower ~100x slower
    # than the same stride on a 2-D minor dim (measured) — so: page
    # rows first, then minor-dim byte strides.
    r = data.reshape(F, LEAF_SIZE)
    b0 = r[:, 0::4].astype(jnp.uint32)
    b1 = r[:, 1::4].astype(jnp.uint32)
    b2 = r[:, 2::4].astype(jnp.uint32)
    b3 = r[:, 3::4].astype(jnp.uint32)
    x2 = ((b0 << np.uint32(24)) | (b1 << np.uint32(16))
          | (b2 << np.uint32(8)) | b3)  # [F, 1024]
    if n_pages_pad != F:
        x2 = jnp.pad(x2, ((0, n_pages_pad - F), (0, 0)))
    xt = _pallas_transpose(x2)  # [1024, n_pages_pad]
    x = xt.reshape(64, 16, n_pages_pad // 128, 128)

    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    out = pl.pallas_call(
        _sha256_leaf_kernel,
        grid=(n_pages_pad // _LANE_TILE, 64),
        in_specs=[pl.BlockSpec((1, 16, _LANE_SUB, 128),
                               lambda i, t: (t, 0, i, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((8, _LANE_SUB, 128), lambda i, t: (0, i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((8, n_pages_pad // 128, 128),
                                       jnp.uint32),
        scratch_shapes=[pltpu.VMEM((8, _LANE_SUB, 128), jnp.uint32)],
        name="sha256_pages",
    )(x)
    return out.reshape(-1)  # [8 * n_pages_pad], word-major


# ---------------------------------------------------------------------------
# Root stage: while_loop over message blocks, small per-block gathers
# ---------------------------------------------------------------------------

def _root_digests_loop(flat, n_pages_pad: int, page0, nleaves, lens, live,
                       word_index=None):
    """Blob ids (repo/blobid.py: SHA-256 of "VMRK1" || le64(len) ||
    leaf digests) from word-major page digests.

    flat: flattened u32 page digests; word j of page p lives at
    j*n_pages_pad + p (word-major kernel layout, tail-leaf override
    already applied). ``word_index(j, p)`` overrides the mapping —
    the mesh-sharded path passes the all-gathered per-shard layout's
    index function. page0: [C_cap] first page of each chunk;
    nleaves/lens/live: the chunk table.

    The digest stream of chunk c is D(t) = flat[word_index(t%8,
    page0[c] + t//8)]. The 13-byte header shifts it to byte offset
    13 = 4*3+1, so message word q >= 4 is the byte-splice
    (D(q-4) << 24) | (D(q-3) >> 8); words 0..3 are header constants and
    the FIPS terminator/bit-length overlay at computed word indices.
    A while_loop runs only to the LARGEST live chunk's block count —
    per iteration one [C_cap, 17]-word gather + one compression, so
    low-entropy segments (few, max_size chunks) don't pay a
    max-possible-length scan.
    """
    C_cap = page0.shape[0]
    nl8 = 8 * nleaves  # digest stream length in words
    nb = (32 * nleaves + 13 + 9 + 63) // 64  # true block counts [C_cap]
    max_nb = jnp.max(jnp.where(live, nb, 0))
    qterm = 3 + nl8  # word holding the 0x80 terminator (byte 1)
    qlen = nb * 16 - 1  # word holding the bit length
    bitlen = (13 + 32 * nleaves.astype(jnp.uint32)) * jnp.uint32(8)

    lens_u = lens.astype(jnp.uint32)
    w1 = ((jnp.uint32(_DOMAIN_BYTE4) << jnp.uint32(24))
          | ((lens_u & jnp.uint32(0xFF)) << jnp.uint32(16))
          | (((lens_u >> jnp.uint32(8)) & jnp.uint32(0xFF)) << jnp.uint32(8))
          | ((lens_u >> jnp.uint32(16)) & jnp.uint32(0xFF)))
    w2 = ((lens_u >> jnp.uint32(24)) & jnp.uint32(0xFF)) << jnp.uint32(24)

    Fp = n_pages_pad
    if word_index is None:
        word_index = _word_index(Fp)
    # U message blocks per while iteration: ONE [C_cap, 16U+1] gather
    # covers all U sub-blocks (each needs D words m*16-4+j, j<=16 — the
    # sub-slices overlap by one word), so the loop pays the gather and
    # loop-carry overhead once per U compressions. The compressions
    # themselves chain (SHA is sequential per lane) — U trades overhead,
    # not parallelism.
    # Tuning knob for profiling runs only: read at TRACE time and not
    # part of any jit cache key, so it must be set before the first
    # compile of a shape in a fresh process. envflags clamps U >= 1
    # (U = 0 would make the loop body a no-op that never advances n —
    # device hang).
    from volsync_tpu import envflags
    U = envflags.root_unroll()
    jj = jnp.arange(16 * U + 1, dtype=jnp.int32)[None, :]
    q16 = jnp.arange(16, dtype=jnp.int32)[None, :]

    def cond(c):
        return c[0] < max_nb

    def body(c):
        n, state = c
        t = n * 16 - 4 + jj  # [1, 16U+1] broadcast over lanes
        tc = jnp.clip(t, 0, Fp * 8 - 1)
        idx = word_index(tc % 8, page0[:, None] + tc // 8)
        d = flat[jnp.clip(idx, 0, flat.shape[0] - 1)]  # [C_cap, 16U+1]
        d = jnp.where((t >= 0) & (t < nl8[:, None]), d, jnp.uint32(0))
        for u in range(U):
            m = n + u
            du = d[:, 16 * u: 16 * u + 17]  # this sub-block's 17 words
            blk = (du[:, :16] << jnp.uint32(24)) \
                | (du[:, 1:] >> jnp.uint32(8))
            q = m * 16 + q16  # [1,16]
            blk = jnp.where(q == 0, jnp.uint32(_DOMAIN_WORD0), blk)
            blk = jnp.where(q == 1, w1[:, None], blk)
            blk = jnp.where(q == 2, w2[:, None], blk)
            blk = jnp.where(q == 3, du[:, 4:5] >> jnp.uint32(8), blk)
            blk = jnp.where(q == qterm[:, None],
                            blk | jnp.uint32(0x00800000), blk)
            blk = jnp.where(q == qlen[:, None], bitlen[:, None], blk)
            new = _compress(state, blk)
            keep = (m < nb)[:, None]
            state = jnp.where(keep, new, state)
        return n + U, state

    state0 = jnp.broadcast_to(jnp.asarray(_H0), (C_cap, 8))
    _, state = jax.lax.while_loop(cond, body, (jnp.int32(0), state0))
    return state


@functools.partial(
    jax.jit,
    static_argnames=("min_size", "avg_size", "max_size", "seed", "mask_s",
                     "mask_l", "align", "eof", "cand_cap", "chunk_cap"))
def chunk_hash_segment(data: jax.Array, valid_len, *, min_size: int,
                       avg_size: int, max_size: int, seed: int, mask_s: int,
                       mask_l: int, align: int, eof: bool, cand_cap: int,
                       chunk_cap: int) -> jax.Array:
    """The whole segment in one device program, one small result.

    data: [P] uint8, P % LEAF_SIZE == 0 (zero-padded; candidates beyond
    ``valid_len`` are masked); requires align == LEAF_SIZE (the
    page-aligned cut format). Returns ONE uint32 array
    ``[4 + chunk_cap*10]``: header (count, consumed, true lax-candidate
    count, page count) then starts[chunk_cap], lens[chunk_cap],
    roots[chunk_cap*8]. Decode with ``decode_segment``.
    """
    assert align == LEAF_SIZE, "fused path requires page-aligned cuts"
    P = data.shape[0]
    R = P // align
    F = P // LEAF_SIZE
    n_pages_pad = _n_pages_pad(F)
    valid_len = jnp.asarray(valid_len, jnp.int32)

    # Each stage under a jax.named_scope (metadata only: the program
    # computes the same) so a device trace names the stage an op
    # belongs to; the same six names as the batched program.
    # --- candidates (aligned gear evaluation, as cdc_candidates_aligned)
    with jax.named_scope("gear_candidates"):
        h = gear_at_aligned(data, seed, align)
        pos_all = (jnp.arange(R, dtype=jnp.int32) * align + (align - 1))
        ok = pos_all < valid_len
        is_s = ((h & np.uint32(mask_s)) == 0) & ok
        is_l = ((h & np.uint32(mask_l)) == 0) & ok
    with jax.named_scope("compact"):
        pos_s = _compact_candidates(is_s, cand_cap, R, align)
        pos_l = _compact_candidates(is_l, cand_cap, R, align)
        ns = jnp.sum(is_s).astype(jnp.int32)
        nl = jnp.sum(is_l).astype(jnp.int32)

    # --- FastCDC boundary walk (on device)
    with jax.named_scope("boundary_walk"):
        starts, lens, count, consumed = _select_boundaries_device(
            pos_s, jnp.minimum(ns, cand_cap), pos_l,
            jnp.minimum(nl, cand_cap), valid_len, min_size=min_size,
            avg_size=avg_size, max_size=max_size, chunk_cap=chunk_cap,
            eof=eof, align=align, n_rows=R)

    # --- page digests (all full leaves are pages; no gather)
    with jax.named_scope("page_sha"):
        flat = _page_digests_flat(data, n_pages_pad)

    # --- the ONE possibly-partial leaf: the final chunk's tail page.
    # Interior cuts land on the page grid (align == LEAF_SIZE and
    # min/avg/max are page multiples), so only the last chunk (eof, or
    # a chunk_cap-overflow remainder) can end off-grid.
    live = jnp.arange(chunk_cap, dtype=jnp.int32) < count
    end = jnp.where(count > 0,
                    starts[jnp.maximum(count - 1, 0)]
                    + lens[jnp.maximum(count - 1, 0)], 0)
    has_tail = (count > 0) & (end % LEAF_SIZE != 0)
    tail_page = jnp.maximum(end - 1, 0) // LEAF_SIZE
    tail_len = end - tail_page * LEAF_SIZE
    with jax.named_scope("tail_sha"):
        tail_dig = _tail_leaf_digests(
            data, tail_page[None], jnp.where(has_tail, tail_len, 0)[None])
        flat = _apply_tail_overrides(flat, n_pages_pad, tail_page[None],
                                     tail_dig, has_tail[None])

    # --- roots
    with jax.named_scope("merkle_roots"):
        nleaves = jnp.where(live, (lens + (LEAF_SIZE - 1)) // LEAF_SIZE, 0)
        page0 = starts // LEAF_SIZE
        roots = _root_digests_loop(flat, n_pages_pad, page0, nleaves, lens,
                                   live)

    header = jnp.stack([count.astype(jnp.uint32),
                        consumed.astype(jnp.uint32),
                        nl.astype(jnp.uint32),
                        jnp.sum(nleaves).astype(jnp.uint32)])
    return jnp.concatenate([
        header, starts.astype(jnp.uint32), lens.astype(jnp.uint32),
        roots.reshape(-1)])


def _chunk_hash_segments_impl(data: jax.Array, valid_len: jax.Array,
                              eof: jax.Array, *, min_size: int,
                              avg_size: int, max_size: int, seed: int,
                              mask_s: int, mask_l: int,
                              align: int, cand_cap: int,
                              chunk_cap: int) -> jax.Array:
    """MANY independent segments in ONE device program — the cross-PVC
    batched form of ``chunk_hash_segment`` (BASELINE configs[5]: many
    concurrent relationships share one chip; batching their segments
    into one dispatch replaces S dispatch/fetch round-trips with one).

    data: [S*P] uint8 — the S zero-padded segments laid end to end
    (P % 4096 == 0; S is valid_len's length). FLAT on purpose: the host
    stages its [S, P] rows contiguously, so the flat view is free
    there, while an [S, P] -> [S*P] reshape on the device is a relayout
    of tiled memory that made this program's compile for a v5e take
    minutes (2 x 40 MiB: 216 s against 21 s flat — ROADMAP Speed 4).
    valid_len: [S] int32; eof: [S] bool — both TRACED, so one compiled
    program serves every batch composition. Padding lanes use
    valid_len == 0. Returns [S, 4 + chunk_cap*10] packed rows, each
    decodable with ``decode_segment``.

    Stage economics vs S separate dispatches: page hashing runs as ONE
    Pallas lane batch over all S*P/4096 pages (better MXU/VPU occupancy
    for small segments), the FastCDC walk vmaps (one masked while_loop
    to the slowest lane), and root assembly runs as a single
    S*chunk_cap-lane loop. One fetch returns every stream's chunk
    table.
    """
    assert align == LEAF_SIZE, "fused path requires page-aligned cuts"
    valid_len = jnp.asarray(valid_len, jnp.int32)
    S = valid_len.shape[0]
    P = data.shape[0] // S
    assert data.ndim == 1 and S * P == data.shape[0], \
        "batched segments are staged flat: [S*P] uint8"
    if S * P > _MAX_FLAT_BYTES:
        # The flat [S*P] buffer is gathered with int32 indices (x64 is
        # off; TPUs index in int32) — a >=2 GiB batch silently can't.
        # BatchedSegmentHasher splits batches to stay under the bound;
        # the bench ladder respects it too.
        raise ValueError(
            f"batched dispatch of {S}x{P} bytes exceeds the int32 "
            f"index space (2 GiB); split the batch")
    R = P // align
    F = P // LEAF_SIZE
    npp = _n_pages_pad(S * F)
    eof = jnp.asarray(eof, jnp.bool_)

    # Each stage under a jax.named_scope (metadata only: the program
    # computes the same) so a device trace names the stage an op
    # belongs to, whatever id the compiler gives the op.
    # --- candidates: gear is page-local, so the flat evaluation equals
    # the per-segment one; masks reshape back to [S, R].
    with jax.named_scope("gear_candidates"):
        h = gear_at_aligned(data, seed, align).reshape(S, R)
        pos_all = jnp.arange(R, dtype=jnp.int32) * align + (align - 1)
        ok = pos_all[None, :] < valid_len[:, None]
        is_s = ((h & np.uint32(mask_s)) == 0) & ok
        is_l = ((h & np.uint32(mask_l)) == 0) & ok

    def compact(row):
        return _compact_candidates(row, cand_cap, R, align)

    with jax.named_scope("compact"):
        pos_s = jax.vmap(compact)(is_s)
        pos_l = jax.vmap(compact)(is_l)
        ns = jnp.sum(is_s, axis=1).astype(jnp.int32)
        nl = jnp.sum(is_l, axis=1).astype(jnp.int32)

    # --- FastCDC walk per lane (vmapped masked while_loop)
    def walk(ps, n_s, plx, n_l, vl, e):
        return _select_boundaries_device(
            ps, jnp.minimum(n_s, cand_cap), plx, jnp.minimum(n_l, cand_cap),
            vl, min_size=min_size, avg_size=avg_size, max_size=max_size,
            chunk_cap=chunk_cap, eof=e, align=align, n_rows=R)

    with jax.named_scope("boundary_walk"):
        starts, lens, count, consumed = jax.vmap(walk)(
            pos_s, ns, pos_l, nl, valid_len, eof)

    # --- page digests: ONE kernel batch over every page of every lane
    with jax.named_scope("page_sha"):
        digests = _page_digests_flat(data, npp)

    # --- per-lane tail override (each lane has at most one partial leaf)
    live = (jnp.arange(chunk_cap, dtype=jnp.int32)[None, :]
            < count[:, None])
    last = jnp.maximum(count - 1, 0)
    end = jnp.where(count > 0,
                    jnp.take_along_axis(starts, last[:, None], axis=1)[:, 0]
                    + jnp.take_along_axis(lens, last[:, None], axis=1)[:, 0],
                    0)
    has_tail = (count > 0) & (end % LEAF_SIZE != 0)
    tail_page_local = jnp.maximum(end - 1, 0) // LEAF_SIZE
    tail_page = jnp.arange(S, dtype=jnp.int32) * F + tail_page_local
    tail_len = end - tail_page_local * LEAF_SIZE
    with jax.named_scope("tail_sha"):
        tail_dig = _tail_leaf_digests(
            data, tail_page, jnp.where(has_tail, tail_len, 0))  # [S, 8]
        digests = _apply_tail_overrides(digests, npp, tail_page,
                                        tail_dig, has_tail)

    # --- roots: one flat S*chunk_cap-lane loop over the shared digest
    # table (page0 offset per lane's segment)
    with jax.named_scope("merkle_roots"):
        nleaves = jnp.where(live, (lens + (LEAF_SIZE - 1)) // LEAF_SIZE, 0)
        page0 = (starts // LEAF_SIZE
                 + (jnp.arange(S, dtype=jnp.int32) * F)[:, None])
        roots = _root_digests_loop(
            digests, npp, page0.reshape(-1), nleaves.reshape(-1),
            lens.reshape(-1), live.reshape(-1))  # [S*chunk_cap, 8]

    header = jnp.stack([count.astype(jnp.uint32),
                        consumed.astype(jnp.uint32),
                        jnp.broadcast_to(nl, count.shape).astype(jnp.uint32),
                        jnp.sum(nleaves, axis=1).astype(jnp.uint32)],
                       axis=1)  # [S, 4]
    return jnp.concatenate([
        header, starts.astype(jnp.uint32), lens.astype(jnp.uint32),
        roots.reshape(S, chunk_cap * 8)], axis=1)


_SEGMENTS_STATIC = ("min_size", "avg_size", "max_size", "seed", "mask_s",
                    "mask_l", "align", "cand_cap", "chunk_cap")

chunk_hash_segments = functools.partial(
    jax.jit, static_argnames=_SEGMENTS_STATIC)(_chunk_hash_segments_impl)


@functools.partial(jax.jit, static_argnames=("n_pages_pad",))
def _page_digests_jit(data, n_pages_pad: int):
    return _page_digests_flat(data, n_pages_pad)


def page_digests(dev) -> np.ndarray:
    """SHA-256 of every full 4 KiB page of a resident buffer ->
    [P/4096, 8] big-endian-word ndarray (one dispatch, one fetch of
    32 bytes per page). The streaming whole-file hasher's primitive."""
    P = int(dev.shape[0])
    F = P // LEAF_SIZE
    npps = _n_pages_pad(F)
    # The protocol's one sync point: a single bounded 32 B/page digest
    # download for the whole buffer (metadata, never payload bytes).
    flat = np.asarray(_page_digests_jit(dev, npps))  # lint: ignore[VL501] bounded batched digest staging
    wi = _word_index(npps)
    j, p = np.meshgrid(np.arange(8), np.arange(F), indexing="xy")
    return flat[wi(j, p)]  # [F, 8]: j/p broadcast to (F, 8)


@jax.jit
def span_roots_device(data: jax.Array, starts: jax.Array,
                      lens: jax.Array) -> jax.Array:
    """Blob ids for page-aligned spans of a resident buffer, ONE fetch.

    data: [P] uint8, P % LEAF_SIZE == 0; starts/lens: [N] int32 with
    every start % LEAF_SIZE == 0 (padding lanes: lens < 0). Used by the
    rclone-style checksum mover (reference: mover-rclone/active.sh:19
    ``rclone sync --checksum``): many whole files pack into one buffer
    at page-aligned offsets, so all full Merkle leaves are pages of the
    buffer (hashed contiguously, no gather) and only each span's final
    partial leaf — at most one per span — goes through the tail stage
    (``_tail_leaf_digests``: N row gathers, one loop). Returns
    [N, 8] uint32 roots (garbage on padding lanes).

    Unlike chunk_hash_segment there is no boundary walk: the spans ARE
    the blobs. CONTRACT: spans must be page-DISJOINT (no two spans may
    touch the same 4 KiB page) — the tail override mutates the shared
    page-digest table, so a page shared between spans would corrupt the
    other span's root. That also rules out zero-length spans (they'd
    override a page they don't own): callers mark them as padding lanes
    (lens < 0) and emit blob_id(b"") host-side, as
    engine/chunker.hash_spans does; its _spans_page_disjoint is the
    matching gate.
    """
    P = data.shape[0]
    F = P // LEAF_SIZE
    n_pages_pad = _n_pages_pad(F)
    starts = starts.astype(jnp.int32)
    lens = lens.astype(jnp.int32)
    # lens <= 0 lanes are inert: no tail override (they own no page —
    # writing one would corrupt its real owner) and a garbage root.
    live = lens > 0
    lens_c = jnp.maximum(lens, 0)

    flat = _page_digests_flat(data, n_pages_pad)

    # Per-span tail leaf: the partial last page (len % LEAF != 0).
    end = starts + lens_c
    has_tail = live & (lens_c % LEAF_SIZE != 0)
    tail_page = jnp.maximum(end - 1, 0) // LEAF_SIZE
    tail_len = end - tail_page * LEAF_SIZE
    with jax.named_scope("tail_sha"):
        tail_dig = _tail_leaf_digests(
            data, tail_page, jnp.where(has_tail, tail_len, 0))  # [N, 8]
        flat = _apply_tail_overrides(flat, n_pages_pad, tail_page,
                                     tail_dig, has_tail)

    nleaves = jnp.where(live,
                        jnp.maximum((lens_c + LEAF_SIZE - 1) // LEAF_SIZE, 1),
                        0)
    page0 = starts // LEAF_SIZE
    return _root_digests_loop(flat, n_pages_pad, page0, nleaves, lens_c,
                              live)


def decode_segment(packed: np.ndarray, chunk_cap: int
                   ) -> tuple[list[tuple[int, int, str]], int, int, int]:
    """packed u32 array -> ([(start, len, root-hex)], consumed,
    true_candidates, total_leaves)."""
    packed = np.asarray(packed, dtype=np.uint32)
    count = int(packed[0])
    consumed = int(packed[1])
    n_cand = int(packed[2])
    n_leaves = int(packed[3])
    starts = packed[4: 4 + chunk_cap].astype(np.int64)
    lens = packed[4 + chunk_cap: 4 + 2 * chunk_cap].astype(np.int64)
    roots = packed[4 + 2 * chunk_cap:].reshape(chunk_cap, 8).astype(">u4")
    out = [(int(starts[c]), int(lens[c]), roots[c].tobytes().hex())  # lint: ignore[VL106] 32 B digests
           for c in range(count)]
    return out, consumed, n_cand, n_leaves


class FusedSegmentHasher:
    """Host driver for ``chunk_hash_segment``: capacity bucketing +
    overflow retry. Stateless apart from the params; safe to share
    across threads (jit cache is global)."""

    def __init__(self, params: GearParams):
        assert params.align == LEAF_SIZE, \
            "fused path requires the page-aligned cut format (align=4096)"
        self.params = params

    def dispatch(self, dev, length: int, *, eof: bool,
                 cand_cap: int | None = None, chunk_cap: int | None = None):
        p = self.params
        P = int(dev.shape[0])
        cc, kc = segment_caps(P, p)
        cand_cap = cand_cap or cc
        chunk_cap = chunk_cap or kc
        count_dispatch(1, 1, length, P)
        return chunk_hash_segment(
            dev, length, min_size=p.min_size, avg_size=p.avg_size,
            max_size=p.max_size, seed=p.seed, mask_s=p.mask_s,
            mask_l=p.mask_l, align=p.align, eof=eof,
            cand_cap=cand_cap, chunk_cap=chunk_cap), \
            (cand_cap, chunk_cap)

    def finish(self, dev, length: int, inflight, *, eof: bool):
        """Fetch + decode; re-dispatch with doubled capacities iff the
        true counts overflowed the compiled tables (adversarial data)."""
        handle, (cand_cap, chunk_cap) = inflight
        while True:
            chunks, consumed, grown = decode_with_overflow_check(
                np.asarray(handle), length, cand_cap, chunk_cap)
            if grown is None:
                return chunks, consumed
            cand_cap, chunk_cap = grown
            handle, (cand_cap, chunk_cap) = self.dispatch(
                dev, length, eof=eof, cand_cap=cand_cap,
                chunk_cap=chunk_cap)


def coalesced_lanes(bucket: int, stage_limit: int) -> int:
    """The most same-bucket segments one dispatch takes under
    ``stage_limit``: the largest power of two of them whose staged rows
    (lanes x bucket) stay within the limit, and one where even two do
    not. A plan of the (lanes, bucket) programs a producer can meet
    asks this (``benchmark/warm_fleet.py``)."""
    lanes = 1
    while lanes * 2 * bucket <= stage_limit:
        lanes *= 2
    return lanes


class BatchedSegmentHasher:
    """Host driver for ``chunk_hash_segments``: many independent
    streams' segments in one dispatch + one fetch (the cross-PVC batch
    of BASELINE configs[5]).

    ``hash_segments(items)`` takes ``[(bytes-like, valid_len, eof)]``,
    groups the lanes by bucketed length, copies each group into rows
    zero-padded to its bucket, and returns ``[(chunks, consumed)]`` per
    lane. A lane that is alone in its dispatch and already as long as
    its bucket (a stream's pooled segment: engine/chunker.py
    stream_chunk_batches pads it in place) is the row: it goes to the
    device as it is, uncopied. Lanes whose true counts overflow
    the compiled capacities retry INDIVIDUALLY through the
    single-segment path (adversarial data only — the batch result for
    the other lanes is already in hand).

    ``stage_limit`` bounds what one dispatch stages: same-bucket lanes
    go to the device :func:`coalesced_lanes` at a time, so a large
    bucket meets the program at few lane counts (one, from half the
    limit up) and a small one at all of them. None: no bound."""

    def __init__(self, params: GearParams,
                 stage_limit: Optional[int] = None):
        assert params.align == LEAF_SIZE, \
            "batched path requires the page-aligned cut format"
        self.params = params
        self.stage_limit = stage_limit
        self._single = FusedSegmentHasher(params)

    def hash_segments(self, items) -> list:
        if not items:
            return []
        # Lanes GROUP BY buffer bucket: padding every lane to the
        # largest one would multiply host/HBM bytes by the batch size
        # when one 32 MiB flush coalesces with tiny eof tails — grouped,
        # per-lane padded waste is bounded by the bucket rounding (<2x).
        groups: dict[int, list[int]] = {}
        for i, (buf, _, _) in enumerate(items):
            groups.setdefault(_buffer_bucket(max(len(buf), 1)),
                              []).append(i)
        out: list = [None] * len(items)
        for P, idxs in groups.items():
            per = (len(idxs) if self.stage_limit is None
                   else coalesced_lanes(P, self.stage_limit))
            for k in range(0, len(idxs), per):
                part = idxs[k: k + per]
                for i, res in zip(part, self._hash_bucket(
                        P, [items[i] for i in part])):
                    out[i] = res
        return out

    def _hash_bucket(self, P: int, items) -> list:
        """One dispatch for same-bucket lanes (lane count padded to a
        pow2 so the jit cache sees a bounded set of (S, P) shapes;
        padding lanes carry valid_len == 0). Batches whose PADDED shape
        would cross the int32 index-space bound (2 GiB — see
        chunk_hash_segments) split into compliant sub-batches."""
        import jax.numpy as jnp

        max_lanes = max(1, _MAX_FLAT_BYTES // P)
        if _pow2ceil(len(items), 1) > max_lanes:
            half = max(1, len(items) // 2)
            return (self._hash_bucket(P, items[:half])
                    + self._hash_bucket(P, items[half:]))

        p = self.params
        cand_cap, chunk_cap = segment_caps(P, p)
        S = _pow2ceil(len(items), 1)
        # The dispatch thread's four costs, one span each, with no
        # synchronisation the path did not have: the program is
        # launched asynchronously and ops.fetch is where it is waited
        # for.
        at = {"lanes": len(items), "bucket": P}
        # One lane that is already the bucket (S == 1, every one of its
        # P bytes the caller's, pad included) is the [1, P] the program
        # takes: the same shapes, so the same executable, and the same
        # bytes a copy of it would hold.
        direct = len(items) == 1 and len(items[0][0]) == P
        with span("ops.stage", part="direct" if direct else "fill", **at):
            lanes = [np.frombuffer(buf, dtype=np.uint8, count=len(buf))
                     for buf, _, _ in items]
            lens = np.zeros((S,), dtype=np.int32)
            eofs = np.zeros((S,), dtype=bool)
            for i, (_, n, eof) in enumerate(items):
                lens[i] = n
                eofs[i] = eof
            if direct:
                rows = lanes[0].reshape(1, P)
            else:
                rows = np.zeros((S, P), dtype=np.uint8)
                for i, arr in enumerate(lanes):
                    rows[i, : arr.shape[0]] = arr
            # the lanes' bytes on their way to the device, copied into
            # rows first or not (as mesh.stage counts them): what the
            # segment programs' share of the HBM roofline is taken over
            record_copy("device.stage", sum(arr.shape[0] for arr in lanes))
        count_dispatch(len(items), S, int(lens.sum()), S * P)
        if direct:
            obs_count("ops.lanes_direct")
        with span("ops.launch", **at):
            handle = chunk_hash_segments(
                jnp.asarray(rows.reshape(-1)), jnp.asarray(lens),
                jnp.asarray(eofs),
                min_size=p.min_size, avg_size=p.avg_size,
                max_size=p.max_size, seed=p.seed, mask_s=p.mask_s,
                mask_l=p.mask_l, align=p.align,
                cand_cap=cand_cap, chunk_cap=chunk_cap)
        with span("ops.fetch", **at):
            packed = np.asarray(handle)
        out = []
        with span("ops.decode", **at):
            for i, (buf, n, eof) in enumerate(items):
                chunks, consumed, grown = decode_with_overflow_check(
                    packed[i], int(lens[i]), cand_cap, chunk_cap)
                if grown is not None:
                    # adversarial lane: retry alone, doubled capacities
                    with span("ops.overflow_retry", bucket=P):
                        dev = jnp.asarray(rows[i])  # lint: ignore[VL502] rare overflow retry: one adversarial lane re-dispatched alone
                        inflight = self._single.dispatch(
                            dev, int(lens[i]), eof=bool(eofs[i]),
                            cand_cap=grown[0], chunk_cap=grown[1])
                        chunks, consumed = self._single.finish(
                            dev, int(lens[i]), inflight, eof=bool(eofs[i]))
                out.append((chunks, consumed))
        if direct:
            return out  # the caller's buffer: nothing to give back
        # Copied rows go back to the allocator under the stage's name
        # too: at the sizes where a fresh S x P array is a mapping of
        # its own, staging costs faulting those pages in while they are
        # filled and unmapping them again, a dispatch later.
        with span("ops.stage", part="release", **at):
            del rows
        return out


def decode_with_overflow_check(packed: np.ndarray, length: int,
                               cand_cap: int, chunk_cap: int):
    """Decode one packed result and apply the capacity-retry protocol.

    Returns (chunks, consumed, grown): ``grown`` is None when the
    result is trustworthy, else the (cand_cap, chunk_cap) to re-dispatch
    with. The in-band header makes truncation always detectable: slot 2
    carries the true (single-chip) / worst-shard (mesh) candidate count,
    and a full chunk table with bytes still unconsumed means the walk
    was cut short. Shared by FusedSegmentHasher and the mesh path so the
    protocol cannot drift between the single- and multi-chip engines.
    """
    chunks, consumed, n_cand, _ = decode_segment(packed, chunk_cap)
    grown_cand, grown_chunk = cand_cap, chunk_cap
    retry = False
    if n_cand > cand_cap:
        grown_cand = _pow2ceil(n_cand, cand_cap * 2)
        retry = True
    if len(chunks) >= chunk_cap and consumed < length:
        grown_chunk = chunk_cap * 2
        retry = True
    return chunks, consumed, (grown_cand, grown_chunk) if retry else None
