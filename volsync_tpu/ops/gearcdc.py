"""Content-defined chunking with a gear rolling hash, TPU-parallel.

Replaces the Rabin-fingerprint content-defined chunking inside the
reference's vendored restic engine (reference: mover-restic/Dockerfile:7-10;
restic cuts blobs with a 64-byte Rabin window, min 512KiB / avg 1MiB / max
8MiB). This is a clean-room design with equivalent *semantics* (content-
defined cut points, min/avg/max bounds, deterministic for identical content)
built around a gear hash, which is the TPU-friendly choice:

    h_i = (h_{i-1} << 1) + G[b_i]  (mod 2^32)
        = sum_{k=0}^{31} 2^k * G[b_{i-k}]          -- exactly 32-byte window

Because the shift drops bits after 32 steps, the hash at position ``i`` is a
pure function of the trailing 32 bytes — no sequential carry survives, so
the whole buffer can be hashed *in parallel*. We compute it in log2(32)=5
doubling passes of shift-scale-add over uint32 lanes:

    h^(2m)_i = h^(m)_i + 2^m * h^(m)_{i-m}

(a parallel prefix specialized to the mod-2^32 linear recurrence). Boundary
candidates are positions where the top bits of ``h`` vanish under a mask
(high bits carry the most mixing for gear). FastCDC-style normalization
uses a harder mask before the average size and an easier one after, which
tightens the chunk-size distribution. Final boundary *selection* (min/max
enforcement, which is sequential but touches only the sparse candidate
list) runs on host over compacted candidate indices.

Chunk determinism: boundaries depend only on content in the trailing 32
bytes plus the previous boundary, so identical content yields identical
chunks regardless of how the buffer was segmented for streaming (the engine
carries a 31-byte halo between segments).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

_WINDOW = 32  # bytes of context in a 32-bit gear hash


def _mix_u32(x):
    """Murmur3-style finalizer: full-avalanche u32 mixing with 6 vector
    ops — the gear table as a *function*. A 256-entry gather would
    serialize on the TPU VPU (gathers are scalar-ish; measured ~100x
    slower than arithmetic), so the device evaluates this directly on the
    byte lanes and the host materializes the identical 256-entry table for
    the scalar/streaming paths. numpy and jax.numpy both wrap mod 2^32."""
    x = (x ^ (x >> np.uint32(16))) * np.uint32(0x7FEB352D)
    x = (x ^ (x >> np.uint32(15))) * np.uint32(0x846CA68B)
    return x ^ (x >> np.uint32(16))


def _make_gear_table(seed: int) -> np.ndarray:
    b = np.arange(256, dtype=np.uint32)
    with np.errstate(over="ignore"):
        return _mix_u32(b + np.uint32(seed & 0xFFFFFFFF))


def _pow2ceil_int(n: int, lo: int) -> int:
    """Pow2 bucketing for retry capacities — arbitrary sizes would mint a
    fresh XLA compile per distinct value."""
    v = lo
    while v < n:
        v *= 2
    return v


def _top_mask(bits: int) -> int:
    """Mask selecting the top ``bits`` bits of a uint32."""
    bits = max(1, min(bits, 31))
    return (((1 << bits) - 1) << (32 - bits)) & 0xFFFFFFFF


@dataclasses.dataclass(frozen=True)
class GearParams:
    """CDC parameters. Defaults mirror restic's chunker envelope.

    ``align`` constrains cut positions so every chunk start is a multiple
    of ``align`` (the mask is evaluated only at eligible positions, with
    its bit count reduced by log2(align) to keep the same average chunk
    size). align=64 is the TPU-native default: the gear window at an
    eligible position sits entirely inside one 64-byte row (no halo), the
    candidate compaction shrinks 64x, and — the big one — every Merkle
    leaf becomes 64-byte-row-aligned so leaf hashing runs the strided
    (gather-free) SHA-256 layout. The trade: chunk boundaries are content
    -defined only modulo the 64-byte phase, so an insertion of k bytes
    (k % 64 != 0) inside one large file re-chunks that file's tail
    (cross-snapshot dedup of unshifted/whole-file/appended data — the
    dominant backup pattern — is unaffected). ``align=1`` restores the
    reference engine's fully shift-invariant behavior and the gather
    hashing path.
    """

    min_size: int = 512 * 1024
    avg_size: int = 1024 * 1024
    max_size: int = 8 * 1024 * 1024
    seed: int = 0x5EED_CDC1
    norm_level: int = 2  # FastCDC normalization: mask_s=bits+n, mask_l=bits-n
    align: int = 64

    def __post_init__(self):
        assert self.min_size >= _WINDOW
        assert self.min_size <= self.avg_size <= self.max_size
        assert self.avg_size & (self.avg_size - 1) == 0, "avg_size must be 2^k"
        assert self.align >= 1 and self.align & (self.align - 1) == 0
        if self.align > 1:
            # The aligned kernel reads the gear window from one row.
            assert self.align >= _WINDOW, "align must be >= the gear window"
            assert self.min_size % self.align == 0
            assert self.max_size % self.align == 0
            assert self.eff_bits - self.norm_level >= 1, \
                "avg_size too small for this align/norm combination"

    @property
    def bits(self) -> int:
        return int(self.avg_size).bit_length() - 1

    @property
    def eff_bits(self) -> int:
        """Mask bits after discounting the 1/align eligible positions:
        candidate density stays 2^-bits overall."""
        return self.bits - (int(self.align).bit_length() - 1)

    @property
    def mask_s(self) -> int:
        """Strict mask for ALIGNED evaluation (applied at 1/align
        positions — the align discount keeps overall candidate density
        at 2^-(bits+norm))."""
        return _top_mask(self.eff_bits + self.norm_level)

    @property
    def mask_l(self) -> int:
        return _top_mask(self.eff_bits - self.norm_level)

    @functools.cached_property
    def table(self) -> np.ndarray:
        return _make_gear_table(self.seed)


#: Repo-format default: page-aligned cuts (align == the 4 KiB Merkle
#: leaf). Every full leaf of every chunk is then a PAGE of the stream,
#: so the fused engine (ops/segment.py) hashes leaves contiguously — no
#: data-sized gather/transpose outside Pallas, which on TPU is the
#: difference between ~1% and ~100% of HBM bandwidth. The trade (cuts
#: are content-defined modulo the 4 KiB phase) only affects dedup of
#: data that moved by a non-page-multiple offset within a file;
#: whole-file, unshifted, and appended dedup — the dominant backup
#: pattern — is unaffected. align=64 keeps the finer-grained split-phase
#: engine; align=1 the fully shift-invariant legacy behavior.
DEFAULT_PARAMS = GearParams(align=4096)


def gear_hash_positions(data: jax.Array, seed: int) -> jax.Array:
    """Gear hash at every byte position of ``data`` ([L] uint8 -> [L] uint32).

    Positions < 31 hash a shorter prefix window (consistent with the
    recurrence started from h=0); boundary selection never uses them because
    min_size >= 32. The per-byte table value is computed arithmetically
    (``_mix_u32``) — no gather.
    """
    g = _mix_u32(data.astype(jnp.uint32) + np.uint32(seed & 0xFFFFFFFF))
    h = g
    for m in (1, 2, 4, 8, 16):
        shifted = jnp.pad(h[:-m], (m, 0))
        h = h + (shifted << np.uint32(m))
    return h


def gear_at_aligned(data: jax.Array, seed: int, align: int) -> jax.Array:
    """Gear hash evaluated only at positions p = r*align + align-1
    ([L] uint8, L % align == 0 -> [L/align] uint32).

    For align >= 32 the 32-byte window ending at p lies inside row r
    (columns align-32..align-1), so this is a pure reshape + weighted
    row-sum: h_p = sum_m G[s_m] << (31-m) over the window bytes s_0..s_31
    — ~32x less arithmetic than hashing every position, no halo, no
    shift-doubling passes.
    """
    L = data.shape[0]
    rows = data.reshape(L // align, align)[:, align - _WINDOW:]
    g = _mix_u32(rows.astype(jnp.uint32) + np.uint32(seed & 0xFFFFFFFF))
    shifts = np.arange(_WINDOW - 1, -1, -1, dtype=np.uint32)  # 31..0
    return jnp.sum(g << shifts[None, :], axis=1, dtype=jnp.uint32)


@functools.partial(jax.jit, static_argnames=("seed", "max_candidates",
                                             "mask_s", "mask_l", "align"))
def cdc_candidates_aligned(data: jax.Array, *, seed: int,
                           mask_s: int, mask_l: int, align: int,
                           max_candidates: int, valid_len=None):
    """Aligned-cut candidate compaction: one nonzero over L/align lanes.

    Because the strict mask's zero-bits are a superset of the lax mask's
    (top_mask(eff+n) ⊃ top_mask(eff-n)), is_s ⊆ is_l — so only the lax
    candidates are compacted, each carrying its strict flag; the host
    splits them. Returns (positions [cap] int32 cut positions, strict
    flags [cap] bool, true count).
    """
    h = gear_at_aligned(data, seed, align)
    R = h.shape[0]
    is_s = (h & np.uint32(mask_s)) == 0
    is_l = (h & np.uint32(mask_l)) == 0
    if valid_len is not None:
        pos_ok = (jnp.arange(R, dtype=jnp.int32) * align + (align - 1)) \
            < valid_len
        is_s = is_s & pos_ok
        is_l = is_l & pos_ok
    ridx = jnp.nonzero(is_l, size=max_candidates, fill_value=R)[0]
    flags = jnp.where(ridx < R, is_s[jnp.clip(ridx, 0, R - 1)], False)
    pos = ridx.astype(jnp.int32) * align + (align - 1)
    return pos, flags, jnp.sum(is_l)


@functools.partial(jax.jit, static_argnames=("seed", "mask_s", "mask_l",
                                             "align", "max_candidates"))
def cdc_candidates_aligned_packed(data: jax.Array, *, seed: int,
                                  mask_s: int, mask_l: int, align: int,
                                  max_candidates: int, valid_len=None):
    """cdc_candidates_aligned with all three outputs packed into ONE
    int32 array [2*cap + 1] = (positions, strict flags, count) — a single
    result fetch per segment (result round-trips dominate on
    remote-attached devices)."""
    pos, flags, count = cdc_candidates_aligned(
        data, seed=seed, mask_s=mask_s, mask_l=mask_l, align=align,
        max_candidates=max_candidates, valid_len=valid_len)
    return jnp.concatenate([pos.astype(jnp.int32), flags.astype(jnp.int32),
                            count[None].astype(jnp.int32)])


@functools.partial(jax.jit, static_argnames=("seed", "max_candidates",
                                             "mask_s", "mask_l"))
def cdc_candidates(data: jax.Array, *, seed: int,
                   mask_s: int, mask_l: int, max_candidates: int,
                   valid_len=None):
    """Compute compacted candidate cut positions on device.

    Returns (idx_s, count_s, idx_l, count_l): positions where
    ``h & mask == 0`` for the strict / lax masks, as the first
    ``max_candidates`` indices in order plus the *true* total counts (host
    re-runs with a larger bound if truncated, keeping chunking
    deterministic).

    ``valid_len`` (traced scalar) restricts candidates and counts to
    positions < valid_len, so zero-padding a bucketed buffer can neither
    add candidates nor inflate the counts the overflow retry keys on.
    """
    h = gear_hash_positions(data, seed)
    is_s = (h & np.uint32(mask_s)) == 0
    is_l = (h & np.uint32(mask_l)) == 0
    L = data.shape[0]
    if valid_len is not None:
        pos_ok = jnp.arange(L, dtype=jnp.int32) < valid_len
        is_s = is_s & pos_ok
        is_l = is_l & pos_ok
    idx_s = jnp.nonzero(is_s, size=max_candidates, fill_value=L)[0]
    idx_l = jnp.nonzero(is_l, size=max_candidates, fill_value=L)[0]
    return idx_s, jnp.sum(is_s), idx_l, jnp.sum(is_l)


def select_boundaries(idx_s: np.ndarray, idx_l: np.ndarray, length: int,
                      params: GearParams, *, eof: bool = True,
                      base: int = 0) -> list[tuple[int, int]]:
    """FastCDC walk over sparse candidates -> [(start, length), ...].

    ``idx_*`` are sorted candidate cut positions *relative to this buffer*
    (cut after position i => chunk ends at i+1). ``base`` is added only to
    the emitted chunk start offsets, so streaming callers get absolute
    (start, length) pairs while passing buffer-relative candidates.

    If ``eof`` is False the tail (which might extend into the next segment)
    is not emitted; the caller resumes from the returned position.

    Dispatches to the native C walk (native/volio.cpp) when the library
    is available; ``_select_boundaries_py`` is the reference
    implementation, and the golden tests pin their equality.
    """
    try:
        from volsync_tpu.io.native import select_boundaries_native

        out = select_boundaries_native(idx_s, idx_l, length, params,
                                       eof, base)
        if out is not None:
            return out
    except Exception:  # lint: ignore[VL003] — native is an accelerator,
        pass           # not a dep: ANY native failure falls through to
        #              # the pure-Python reference on this per-segment
        #              # hot path (logging here would spam every call)
    return _select_boundaries_py(idx_s, idx_l, length, params, eof=eof,
                                 base=base)


def _select_boundaries_py(idx_s: np.ndarray, idx_l: np.ndarray, length: int,
                          params: GearParams, *, eof: bool = True,
                          base: int = 0) -> list[tuple[int, int]]:
    """Pure-Python reference walk (see select_boundaries)."""
    chunks: list[tuple[int, int]] = []
    pos = 0
    while pos < length:
        lo = pos + params.min_size - 1  # earliest cut position (chunk len >= min)
        mid = pos + params.avg_size - 1
        hi = pos + params.max_size - 1  # latest cut position (chunk len <= max)
        cut = None
        i = np.searchsorted(idx_s, lo, side="left")
        if i < len(idx_s) and idx_s[i] <= min(mid - 1, length - 1, hi):
            cut = int(idx_s[i])
        if cut is None:
            j = np.searchsorted(idx_l, max(lo, mid), side="left")
            if j < len(idx_l) and idx_l[j] <= min(hi, length - 1):
                cut = int(idx_l[j])
        if cut is None:
            if hi <= length - 1:
                cut = hi
            elif eof:
                cut = length - 1  # final short chunk
            else:
                break  # tail continues into the next segment
        chunks.append((base + pos, cut - pos + 1))
        pos = cut + 1
    return chunks


def chunk_buffer(data, params: GearParams = DEFAULT_PARAMS,
                 *, eof: bool = True) -> list[tuple[int, int]]:
    """Chunk a byte buffer (numpy uint8 / bytes / jax array) on device.

    Returns [(start, length)] covering the buffer (the last chunk may be
    shorter than min_size iff ``eof``).
    """
    if isinstance(data, (bytes, bytearray, memoryview)):
        data = np.frombuffer(data, dtype=np.uint8)
    length = int(data.shape[0])
    if length == 0:
        return []
    if length <= params.min_size:
        return [(0, length)] if eof else []
    if params.align > 1:
        padded = (length + params.align - 1) // params.align * params.align
        buf = np.pad(np.asarray(data), (0, padded - length)) \
            if padded != length else np.asarray(data)
        dev = jnp.asarray(buf, dtype=jnp.uint8)
        cap = 4096
        while True:
            pos, flags, count = cdc_candidates_aligned(
                dev, seed=params.seed, mask_s=params.mask_s,
                mask_l=params.mask_l, align=params.align,
                max_candidates=cap, valid_len=length)
            c = int(count)
            if c <= cap:
                break
            cap = _pow2ceil_int(c, cap * 2)
        pos = np.asarray(pos)[:c]
        flags = np.asarray(flags)[:c]
        return select_boundaries(pos[flags], pos, length, params, eof=eof)
    dev = jnp.asarray(data, dtype=jnp.uint8)
    # Expected candidate density is 2^-(bits-norm) for the lax mask; leave
    # generous headroom, and retry exactly if real data is denser.
    guess = max(1024, 8 * length // max(1, params.avg_size >> (params.norm_level + 1)))
    while True:
        idx_s, count_s, idx_l, count_l = cdc_candidates(
            dev, seed=params.seed, mask_s=params.mask_s, mask_l=params.mask_l,
            max_candidates=min(guess, length),
        )
        cs, cl = int(count_s), int(count_l)
        if max(cs, cl) <= guess or guess >= length:
            break
        guess = min(length, max(cs, cl) + 1024)
    idx_s = np.asarray(idx_s)[:cs]
    idx_l = np.asarray(idx_l)[:cl]
    return select_boundaries(idx_s, idx_l, length, params, eof=eof)
