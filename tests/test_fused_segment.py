"""Golden tests for the fused single-dispatch segment pipeline
(ops/segment.py): boundaries must equal the host FastCDC reference walk
and blob ids must equal the hashlib Merkle reference, for eof and
mid-stream segments, across sizes that exercise min/avg/max cuts,
capacity retries, and the streaming protocol. The split-phase (align=64)
engine keeps its own coverage — both engines must agree with the host
reference, not with each other (their cut grids differ)."""

import numpy as np
import pytest

from volsync_tpu.engine.chunker import DeviceChunkHasher, stream_chunks
from volsync_tpu.ops.gearcdc import GearParams, chunk_buffer
from volsync_tpu.ops.segment import (
    FusedSegmentHasher,
    decode_segment,
    segment_caps,
)
from volsync_tpu.repo import blobid

# Page-aligned fused format (align == LEAF_SIZE). avg 32 KiB keeps
# eff_bits - norm >= 1 at this alignment.
PARAMS = GearParams(min_size=4096, avg_size=32768, max_size=65536,
                    align=4096)
# Split-phase aligned engine (64 <= align < 4096).
PARAMS64 = GearParams(min_size=256, avg_size=1024, max_size=4096)


def host_reference(data: bytes, params, *, eof=True):
    """chunk_buffer (golden-tested vs scalar reference) + hashlib ids."""
    chunks = chunk_buffer(data, params, eof=eof)
    return [(s, l, blobid.blob_id(data[s: s + l])) for s, l in chunks]


def run_engine(data: bytes, params, *, eof=True):
    h = DeviceChunkHasher(params)
    return h.process(data, eof=eof)


@pytest.mark.parametrize("n", [5000, 65536, 300_000, 300_000 + 4096,
                               1_050_000])
@pytest.mark.slow
def test_fused_matches_host_reference_random(rng, n):
    data = rng.randint(0, 256, size=(n,), dtype=np.uint8).tobytes()
    assert run_engine(data, PARAMS) == host_reference(data, PARAMS)


@pytest.mark.parametrize("n", [300, 65536, 257 * 1024])
def test_split_phase_matches_host_reference(rng, n):
    data = rng.randint(0, 256, size=(n,), dtype=np.uint8).tobytes()
    assert run_engine(data, PARAMS64) == host_reference(data, PARAMS64)


@pytest.mark.slow
def test_fused_matches_on_redundant_data(rng):
    block = rng.randint(0, 256, size=(131072,), dtype=np.uint8).tobytes()
    data = block * 4 + rng.randint(0, 256, size=(50_000,),
                                   dtype=np.uint8).tobytes()
    got = run_engine(data, PARAMS)
    assert got == host_reference(data, PARAMS)
    # identical content yields identical ids (dedup works)
    ids = [d for _, _, d in got]
    assert len(set(ids)) < len(ids)


def test_fused_zero_entropy_forces_max_cuts():
    # Constant data: gear hash is constant, typically no mask hit -> the
    # max_size rule must fire; all interior chunks are max_size.
    data = bytes(400_000)
    got = run_engine(data, PARAMS)
    assert got == host_reference(data, PARAMS)
    assert all(l <= PARAMS.max_size for _, l, _ in got)


@pytest.mark.slow
def test_fused_non_eof_withholds_tail(rng):
    data = rng.randint(0, 256, size=(500_000,), dtype=np.uint8).tobytes()
    ref = host_reference(data, PARAMS, eof=False)
    got = run_engine(data, PARAMS, eof=False)
    assert got == ref
    end = sum(l for _, l, _ in got)
    assert 0 < end < len(data)  # tail withheld
    assert end % 4096 == 0      # interior cuts stay on the page grid


@pytest.mark.slow
def test_fused_streaming_bit_identical_to_oneshot(rng):
    data = rng.randint(0, 256, size=(2_000_000,), dtype=np.uint8).tobytes()
    pos = [0]

    def reader(n):
        n = min(n, 73_210)  # ragged reads
        piece = data[pos[0]: pos[0] + n]
        pos[0] += len(piece)
        return piece

    out = [(c, d) for c, d in stream_chunks(reader, PARAMS,
                                            segment_size=512 * 1024)]
    assert b"".join(c for c, _ in out) == data
    assert [(len(c), d) for c, d in out] == \
        [(l, d) for _, l, d in host_reference(data, PARAMS)]


@pytest.mark.slow
def test_fused_capacity_retry(rng):
    # Dispatch with deliberately tiny capacities: the true counts in the
    # packed result must trigger host-side retry and still converge to
    # the reference.
    data = rng.randint(0, 256, size=(524288,), dtype=np.uint8)
    fsh = FusedSegmentHasher(PARAMS)
    import jax.numpy as jnp

    dev = jnp.asarray(data)
    inflight = fsh.dispatch(dev, 524288, eof=True, cand_cap=4096,
                            chunk_cap=16)
    # 512 KiB / min 4 KiB -> up to 128 chunks >> 16: must retry.
    chunks, consumed = fsh.finish(dev, 524288, inflight, eof=True)
    assert consumed == 524288
    ref = host_reference(data.tobytes(), PARAMS)
    assert [(s, l, d) for s, l, d in chunks] == ref


# Every SHA-256 padding edge of a partial leaf: terminator and length
# in one block or two, a word boundary or not, the longest tail.
TAIL_EDGES = [1, 3, 4, 55, 56, 63, 64, 119, 120, 2000, 4093, 4095]


@pytest.mark.parametrize("tail_len", [0] + TAIL_EDGES)
def test_tail_leaf_digests_match_generic_hasher_and_hashlib(rng, tail_len):
    """ops/segment._tail_leaf_digests (page-aligned start, under a page)
    against its oracle sha256_chunks_device (arbitrary offsets) and
    hashlib: the same page at three lanes, one of them the buffer's last
    page, one lane with no tail (length 0: no compression, masked by the
    callers)."""
    import hashlib

    import jax.numpy as jnp

    from volsync_tpu.ops.segment import LEAF_SIZE, _tail_leaf_digests
    from volsync_tpu.ops.sha256 import _H0, sha256_chunks_device

    F = 6
    data = np.frombuffer(rng.bytes(F * LEAF_SIZE), np.uint8)
    pages = np.array([2, F - 1, 0, 4], np.int32)
    lens = np.array([tail_len, tail_len, 0, max(tail_len - 1, 0)], np.int32)
    got = np.asarray(_tail_leaf_digests(
        jnp.asarray(data), jnp.asarray(pages), jnp.asarray(lens)))
    want = np.asarray(sha256_chunks_device(
        jnp.asarray(data), jnp.asarray(pages * LEAF_SIZE),
        jnp.asarray(lens), max_len=LEAF_SIZE))
    for lane, (pg, n) in enumerate(zip(pages, lens)):
        if n == 0:  # no tail: the initial state, nothing hashed
            assert (got[lane] == _H0).all(), lane
            continue
        assert (got[lane] == want[lane]).all(), lane
        assert got[lane].astype(">u4").tobytes() == hashlib.sha256(
            data[pg * LEAF_SIZE: pg * LEAF_SIZE + n].tobytes()).digest()


@pytest.mark.parametrize(
    "n", [5 * 4096 + t for t in [0] + TAIL_EDGES] + [65536 - 7],
    ids=[f"tail{t}" for t in [0] + TAIL_EDGES] + ["last-page-of-bucket"])
def test_fused_single_lane_tail_edges(rng, n):
    """chunk_hash_segment end to end with the final chunk ending at
    every padding edge into a page (0: on the grid, no tail at all),
    and with the tail leaf on the very last page of the padded bucket
    (the row gather's upper edge)."""
    data = rng.randint(0, 256, size=(n,), dtype=np.uint8).tobytes()
    assert run_engine(data, PARAMS) == host_reference(data, PARAMS)


def test_span_roots_eight_spans_each_with_its_own_tail(rng):
    """span_roots_device directly: 8 page-disjoint spans, every one with
    a partial last leaf of another length (N lanes, N tails in one
    stage), plus padding lanes; ids against hashlib's Merkle id."""
    import jax.numpy as jnp

    from volsync_tpu.ops.segment import span_roots_device

    sizes = [1, 55, 4096 + 56, 63, 2 * 4096 + 64, 119, 4096 + 120, 4095]
    pieces, starts = [], []
    off = 0
    for n in sizes:
        starts.append(off)
        pieces.append(rng.bytes(n) + bytes(-n % 4096))
        off += len(pieces[-1])
    buf = b"".join(pieces)
    pad = 3  # inert lanes (lens < 0) among the live ones
    roots = np.asarray(span_roots_device(
        jnp.asarray(np.frombuffer(buf, np.uint8)),
        jnp.asarray(starts + [0] * pad, jnp.int32),
        jnp.asarray(sizes + [-1] * pad, jnp.int32)))
    for lane, (s, n) in enumerate(zip(starts, sizes)):
        assert roots[lane].astype(">u4").tobytes().hex() \
            == blobid.blob_id(buf[s: s + n]), (lane, n)


def test_decode_segment_shape():
    cc, kc = segment_caps(65536, PARAMS)
    packed = np.zeros((4 + kc * 10,), np.uint32)
    packed[0] = 1
    packed[1] = 123
    packed[4] = 0          # start
    packed[4 + kc] = 123   # len
    chunks, consumed, n_cand, n_leaves = decode_segment(packed, kc)
    assert chunks[0][:2] == (0, 123) and consumed == 123


def test_small_and_empty_buffers():
    h = DeviceChunkHasher(PARAMS)
    assert h.process(b"") == []
    tiny = b"x" * 100  # <= min_size: host fast path
    [(s, l, d)] = h.process(tiny)
    assert (s, l) == (0, 100) and d == blobid.blob_id(tiny)


def test_hash_spans_page_aligned_fast_path(rng):
    """Aligned spans take span_roots_device (one dispatch/fetch) and
    must match blob_id exactly — including empty files, exact-page
    sizes, and sub-page tails."""
    from volsync_tpu.engine.chunker import hash_spans

    sizes = [0, 1, 4095, 4096, 4097, 12288, 50_000]
    pieces, spans = [], []
    off = 0
    for n in sizes:
        data = rng.randint(0, 256, size=(n,), dtype=np.uint8).tobytes()
        spans.append((off, n))
        pieces.append(data)
        pad = -n % 4096
        pieces.append(bytes(pad))
        off += n + pad
    buf = b"".join(pieces)
    got = hash_spans(buf, spans)
    for (s, l), d in zip(spans, got):
        assert d == blobid.blob_id(buf[s: s + l]), f"span {s},{l}"


def test_hash_spans_unaligned_fallback(rng):
    from volsync_tpu.engine.chunker import hash_spans

    buf = rng.randint(0, 256, size=(40_000,), dtype=np.uint8).tobytes()
    spans = [(0, 10_000), (10_000, 30_000)]  # second start unaligned
    got = hash_spans(buf, spans)
    for (s, l), d in zip(spans, got):
        assert d == blobid.blob_id(buf[s: s + l])


def test_hash_file_streaming_page_path(tmp_path, rng):
    from volsync_tpu.engine.chunker import hash_file_streaming

    for n in (0, 5, 4096, 200_000, 1_048_576 + 123):
        p = tmp_path / f"f{n}"
        data = rng.randint(0, 256, size=(n,), dtype=np.uint8).tobytes()
        p.write_bytes(data)
        assert hash_file_streaming(p, segment_size=256 * 1024) \
            == blobid.blob_id(data), n


def test_hash_spans_overlapping_aligned_fallback(rng):
    """Overlapping page-aligned spans (reachable via the gRPC HashSpans
    endpoint) must NOT take the shared-table fast path — its in-place
    tail override would corrupt the page both spans read."""
    from volsync_tpu.engine.chunker import hash_spans

    buf = rng.randint(0, 256, size=(8192,), dtype=np.uint8).tobytes()
    spans = [(0, 100), (0, 8192), (4096, 100)]
    got = hash_spans(buf, spans)
    for (s, l), d in zip(spans, got):
        assert d == blobid.blob_id(buf[s: s + l])


def test_page_digest_table_is_word_major(rng):
    """The digest table has ONE layout (word j of page p at
    j*n_pages_pad + p): the flat program output indexed through
    ``_word_index`` must equal hashlib per page, padded pages
    included in the stride."""
    import hashlib

    import jax.numpy as jnp

    from volsync_tpu.ops import segment as seg

    n_pages, npp = 5, 8
    data = np.frombuffer(rng.bytes(n_pages * 4096), np.uint8)
    flat = np.asarray(seg._page_digests_flat(jnp.asarray(data), npp))
    assert flat.shape == (8 * npp,)
    wi = seg._word_index(npp)
    for pg in range(n_pages):
        words = np.array([flat[wi(j, pg)] for j in range(8)], ">u4")
        assert words.tobytes() == hashlib.sha256(
            data[pg * 4096:(pg + 1) * 4096]).digest(), pg


@pytest.mark.slow
def test_walk_table_randomized_vs_scalar_reference(rng):
    """Property test for the successor-table walk: random candidate
    sets and lengths (including L < min_size, L a page multiple, L-1
    cuts, empty candidate sets, chunk_cap truncation) must match the
    scalar reference walk exactly."""
    import jax.numpy as jnp

    from volsync_tpu.ops import segment as seg
    from volsync_tpu.ops.gearcdc import GearParams, _select_boundaries_py

    p = GearParams(min_size=4096, avg_size=32768, max_size=65536,
                   seed=1, align=4096)
    align = p.align
    sent = 2**31 - 2
    for trial in range(40):
        n_rows = int(rng.randint(1, 64))
        P = n_rows * align
        # random candidate rows; strict subset of lax (as in the real
        # mask relationship)
        density = rng.choice([0.0, 0.05, 0.3, 0.8])
        lax_rows = np.nonzero(rng.rand(n_rows) < density)[0]
        strict_rows = lax_rows[rng.rand(lax_rows.shape[0]) < 0.4]
        pos_l_np = lax_rows * align + (align - 1)
        pos_s_np = strict_rows * align + (align - 1)
        if trial % 3 == 0:
            L = P  # exact page multiple
        elif trial % 3 == 1:
            L = int(rng.randint(1, P + 1))  # arbitrary
        else:
            L = max(1, P - int(rng.randint(0, align)))  # near the end
        eof = bool(rng.randint(0, 2))
        chunk_cap = int(rng.choice([2, 4, 256]))  # incl. truncation
        cap = 128
        idx_s = pos_s_np[pos_s_np < L]
        idx_l = pos_l_np[pos_l_np < L]

        def padded(a):
            out = np.full((cap,), sent, np.int32)
            out[: a.shape[0]] = a
            return jnp.asarray(out)

        starts, lens, count, consumed = seg._select_boundaries_device(
            padded(idx_s), jnp.int32(idx_s.shape[0]),
            padded(idx_l), jnp.int32(idx_l.shape[0]),
            jnp.int32(L), min_size=p.min_size, avg_size=p.avg_size,
            max_size=p.max_size, chunk_cap=chunk_cap, eof=eof,
            align=align, n_rows=n_rows)
        count = int(count)
        got = [(int(starts[c]), int(lens[c])) for c in range(count)]
        ref = _select_boundaries_py(idx_s, idx_l, L, p, eof=eof)
        assert got == ref[:chunk_cap], \
            (trial, n_rows, L, eof, chunk_cap, got, ref)
        ref_pos = (ref[-1][0] + ref[-1][1]) if ref else 0
        if count < chunk_cap:
            # full walk: consumed == the reference's final position
            # (== L for eof, since the final chunk ends at L-1)
            assert int(consumed) == ref_pos
        else:
            # truncated walk: consumed must be exactly the end of the
            # last emitted chunk — the capacity-retry protocol
            # (decode_with_overflow_check) keys on it
            assert int(consumed) == got[-1][0] + got[-1][1]
