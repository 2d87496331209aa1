"""Batched (cross-PVC) fused segments: one dispatch, many streams.

``chunk_hash_segments`` must be bit-identical, lane for lane, to the
shipped single-segment program ``chunk_hash_segment`` — same chunk
boundaries, same Merkle blob ids — for mixed eof flags, mixed lengths,
padding lanes, and content with duplicate regions (BASELINE configs[5]:
many concurrent relationships share one chip; batching their segments
into one dispatch is the TPU-native form of that concurrency).
"""

import hashlib

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import blobid as ref_blobid
from benchmark.reference import gearcdc as ref_gearcdc
from volsync_tpu.obs import (copies_by_site, counter_totals, reset_copies,
                             reset_spans, span_totals)
from volsync_tpu.ops.gearcdc import GearParams
from volsync_tpu.ops.segment import (
    chunk_hash_segment,
    chunk_hash_segments,
    decode_segment,
    segment_caps,
)
from volsync_tpu.repo import blobid

P = GearParams(min_size=4096, avg_size=32768, max_size=65536,
               seed=0x5EED_CDC1, align=4096)
SEG = 256 * 1024  # per-lane padded segment length


def _kw(cand_cap, chunk_cap, **extra):
    return dict(min_size=P.min_size, avg_size=P.avg_size,
                max_size=P.max_size, seed=P.seed, mask_s=P.mask_s,
                mask_l=P.mask_l, align=P.align, cand_cap=cand_cap,
                chunk_cap=chunk_cap, **extra)


@pytest.mark.slow
def test_batched_matches_single_lane_for_lane(rng):
    cand_cap, chunk_cap = segment_caps(SEG, P)
    lens = [SEG, SEG - 5000, 3 * 4096 + 17, SEG // 2, 0, SEG - 1]
    eofs = [True, False, True, False, True, False]
    rows = np.zeros((len(lens), SEG), dtype=np.uint8)
    for i, n in enumerate(lens):
        rows[i, :n] = np.frombuffer(rng.bytes(n), np.uint8)
    rows[3, : SEG // 4] = rows[0, : SEG // 4]  # shared content dedups

    batched = np.asarray(chunk_hash_segments(
        jnp.asarray(rows.reshape(-1)), jnp.asarray(lens, jnp.int32),
        jnp.asarray(eofs), **_kw(cand_cap, chunk_cap)))

    for i, (n, eof) in enumerate(zip(lens, eofs)):
        single = np.asarray(chunk_hash_segment(
            jnp.asarray(rows[i]), np.int32(n),
            **_kw(cand_cap, chunk_cap, eof=eof)))
        b_chunks, b_consumed, _, b_leaves = decode_segment(
            batched[i], chunk_cap)
        s_chunks, s_consumed, _, s_leaves = decode_segment(
            single, chunk_cap)
        assert b_chunks == s_chunks, f"lane {i}"
        assert b_consumed == s_consumed, f"lane {i}"
        assert b_leaves == s_leaves, f"lane {i}"
        # and the ids really are the repo Merkle ids of the bytes
        view = rows[i].tobytes()
        for s, l, d in b_chunks[:3]:
            assert d == blobid.blob_id(view[s: s + l])


@pytest.mark.slow
def test_batched_empty_and_all_zero_lanes():
    cand_cap, chunk_cap = segment_caps(SEG, P)
    rows = np.zeros((3, SEG), dtype=np.uint8)  # pathological: all zeros
    lens = [0, SEG, P.min_size - 1]
    eofs = [True, True, True]
    out = np.asarray(chunk_hash_segments(
        jnp.asarray(rows.reshape(-1)), jnp.asarray(lens, jnp.int32),
        jnp.asarray(eofs), **_kw(cand_cap, chunk_cap)))
    # lane 0: padding lane, nothing emitted
    chunks0, consumed0, _, _ = decode_segment(out[0], chunk_cap)
    assert chunks0 == [] and consumed0 == 0
    # lane 1: pathological constant data must match the single-segment
    # program exactly (degenerate gear values either cut everywhere or
    # nowhere — both covered by equality with the shipped path)
    chunks1, consumed1, _, _ = decode_segment(out[1], chunk_cap)
    single = np.asarray(chunk_hash_segment(
        jnp.asarray(rows[1]), np.int32(SEG),
        **_kw(cand_cap, chunk_cap, eof=True)))
    s_chunks, s_consumed, _, _ = decode_segment(single, chunk_cap)
    assert (chunks1, consumed1) == (s_chunks, s_consumed)
    assert consumed1 == SEG
    assert sum(l for _, l, _ in chunks1) == SEG
    assert chunks1[0][2] == blobid.blob_id(
        bytes(chunks1[0][1]))  # ids are real Merkle ids of zero bytes
    # lane 2: shorter than min_size with eof -> one whole-buffer chunk
    chunks2, _, _, _ = decode_segment(out[2], chunk_cap)
    assert sum(l for _, l, _ in chunks2) == P.min_size - 1


SMALL = 64 * 1024  # a lane short enough to compile three lane counts


@pytest.mark.parametrize("lens,eofs", [
    ([SMALL - 100], [True]),             # the tail on the bucket's last page
    ([SMALL - 100], [False]),            # not eof: no tail, zero iterations
    ([3 * 4096], [True]),                # eof on the page grid: no tail
    ([5 * 4096 + 55, 0], [True, True]),  # one padding lane (valid_len 0)
    ([5 * 4096 + 56, 2 * 4096 + 4095], [True, True]),
    ([4096 + 63, SMALL - 1], [False, True]),
    ([SMALL - 4095, 64, 0, 7 * 4096 + 119], [True, True, False, True]),
    ([SMALL, 4096 + 120, 9 * 4096 + 1, 12345], [False, True, True, False]),
], ids=["1-last-page", "1-not-eof", "1-on-grid", "2-with-empty",
        "2-eof", "2-mixed", "4-with-empty", "4-mixed"])
def test_batched_tail_leaves_per_lane(rng, lens, eofs):
    """1, 2 and 4 lanes, eof mixed, each eof lane with its own partial
    tail leaf (every SHA padding edge somewhere), a lane of valid_len 0,
    and batches where no lane has a tail: cuts and ids against the host
    walk + hashlib, lane for lane."""
    from volsync_tpu.ops.gearcdc import chunk_buffer

    cand_cap, chunk_cap = segment_caps(SMALL, P)
    rows = np.zeros((len(lens), SMALL), dtype=np.uint8)
    for i, n in enumerate(lens):
        rows[i, :n] = np.frombuffer(rng.bytes(n), np.uint8)
    out = np.asarray(chunk_hash_segments(
        jnp.asarray(rows.reshape(-1)), jnp.asarray(lens, jnp.int32),
        jnp.asarray(eofs), **_kw(cand_cap, chunk_cap)))
    for i, (n, eof) in enumerate(zip(lens, eofs)):
        chunks, consumed, _, _ = decode_segment(out[i], chunk_cap)
        view = rows[i, :n].tobytes()
        want = [(s, l, blobid.blob_id(view[s: s + l]))
                for s, l in chunk_buffer(view, P, eof=eof)] if n else []
        assert chunks == want, f"lane {i}"
        assert consumed == sum(l for _, l, _ in want), f"lane {i}"


def test_batched_duplicate_content_same_ids(rng):
    """Identical lanes produce identical chunk tables/ids — the dedup
    substrate for cross-PVC batches."""
    cand_cap, chunk_cap = segment_caps(SEG, P)
    row = np.frombuffer(rng.bytes(SEG), np.uint8)
    rows = np.stack([row, row, row])
    out = np.asarray(chunk_hash_segments(
        jnp.asarray(rows.reshape(-1)), jnp.asarray([SEG] * 3, jnp.int32),
        jnp.asarray([True] * 3), **_kw(cand_cap, chunk_cap)))
    a = decode_segment(out[0], chunk_cap)
    assert decode_segment(out[1], chunk_cap) == a
    assert decode_segment(out[2], chunk_cap) == a



@pytest.mark.slow
def test_batched_hasher_driver(rng):
    """BatchedSegmentHasher: ragged inputs through one dispatch; lanes
    agree with the single-segment driver chunk for chunk."""
    from volsync_tpu.engine.chunker import DeviceChunkHasher
    from volsync_tpu.ops.segment import BatchedSegmentHasher

    b = BatchedSegmentHasher(P)
    single = DeviceChunkHasher(P)
    items = [
        (rng.bytes(200_000), 200_000, True),
        (rng.bytes(90_000), 90_000, False),
        (b"", 0, True),
        (rng.bytes(5_000), 5_000, True),
    ]
    got = b.hash_segments(items)
    assert len(got) == len(items)
    for (buf, n, eof), (chunks, consumed) in zip(items, got):
        if n == 0:
            assert chunks == [] and consumed == 0
            continue
        want = single.process(np.frombuffer(buf, np.uint8), eof=eof)
        assert chunks == want
        for s, l, d in chunks[:2]:
            assert d == blobid.blob_id(buf[s: s + l])


@pytest.mark.slow
def test_treebackup_with_shared_batcher(tmp_path, monkeypatch):
    """VOLSYNC_BATCH_SEGMENTS=1: two backups at once on two threads
    (as the fleet's replicas run them) coalesce segments through the
    shared microbatcher, and each snapshot is bit-identical to the
    unbatched run."""
    from concurrent.futures import ThreadPoolExecutor

    from volsync_tpu.engine import TreeBackup, restore_snapshot
    from volsync_tpu.objstore import MemObjectStore
    from volsync_tpu.ops import batcher as batcher_mod
    from volsync_tpu.repo.repository import Repository

    rng = np.random.RandomState(9)
    src = tmp_path / "src"
    src.mkdir()
    for i in range(6):
        (src / f"f{i}.bin").write_bytes(rng.bytes(150_000 + i * 7000))

    chunker_cfg = {"min_size": P.min_size, "avg_size": P.avg_size,
                   "max_size": P.max_size, "seed": P.seed, "align": 4096}

    # unbatched reference run
    repo_a = Repository.init(MemObjectStore(), chunker=chunker_cfg)
    snap_a, stats_a = TreeBackup(repo_a).run(src)

    # batched run through a fresh shared batcher
    monkeypatch.setenv("VOLSYNC_BATCH_SEGMENTS", "1")
    monkeypatch.setenv("VOLSYNC_BATCH_WINDOW_MS", "25")
    monkeypatch.setattr(batcher_mod, "_SHARED", {})
    batch_sizes = []
    orig_init = batcher_mod.SegmentMicroBatcher.__init__

    def spy_init(self, params, **kw):
        orig_init(self, params, **kw)
        real = self._hasher.hash_segments

        def spy(items):
            batch_sizes.append(len(items))
            return real(items)

        self._hasher.hash_segments = spy

    monkeypatch.setattr(batcher_mod.SegmentMicroBatcher, "__init__",
                        spy_init)
    repo_b = Repository.init(MemObjectStore(), chunker=chunker_cfg)
    repo_c = Repository.init(MemObjectStore(), chunker=chunker_cfg)
    try:
        with ThreadPoolExecutor(2) as pool:
            (snap_b, stats_b), (snap_c, stats_c) = pool.map(
                lambda repo: TreeBackup(repo).run(src), [repo_b, repo_c])
    finally:
        # don't leak the worker thread into the rest of the session
        for b in batcher_mod._SHARED.values():
            b.stop()

    # identical content: same blob universe, restore matches
    assert repo_a.blob_ids() == repo_b.blob_ids() == repo_c.blob_ids()
    assert stats_a.blobs_new == stats_b.blobs_new == stats_c.blobs_new
    dst = tmp_path / "dst"
    dst.mkdir()
    restore_snapshot(repo_b, dst)
    for i in range(6):
        assert (dst / f"f{i}.bin").read_bytes() == \
            (src / f"f{i}.bin").read_bytes()
    # concurrency actually coalesced
    assert batch_sizes and any(s > 1 for s in batch_sizes), batch_sizes


@pytest.mark.slow
def test_microbatcher_pipelined_concurrent_submits(rng):
    """Many concurrent producers through a pipeline_depth=2 batcher:
    every caller gets ITS lane's result (no cross-batch mixups while
    two dispatches are in flight), identical to the single driver."""
    from concurrent.futures import ThreadPoolExecutor

    from volsync_tpu.engine.chunker import DeviceChunkHasher
    from volsync_tpu.ops.batcher import SegmentMicroBatcher

    single = DeviceChunkHasher(P)
    items = [rng.bytes(30_000 + 7 * i) for i in range(12)]
    want = [single.process(np.frombuffer(b, np.uint8), eof=True)
            for b in items]

    mb = SegmentMicroBatcher(P, max_batch=3, window_ms=5.0,
                             pipeline_depth=2)
    try:
        with ThreadPoolExecutor(6) as ex:
            got = list(ex.map(
                lambda b: mb.submit(b, len(b), True), items))
    finally:
        mb.stop()
    for b, (chunks, consumed), w in zip(items, got, want):
        assert chunks == w
        assert consumed == len(b)


def test_batching_default_follows_backend(monkeypatch):
    """Unset VOLSYNC_BATCH_SEGMENTS -> batching defaults ON only for
    real TPU backends; explicit 0/1 always wins."""
    import jax

    from volsync_tpu.ops import batcher as bm

    monkeypatch.delenv("VOLSYNC_BATCH_SEGMENTS", raising=False)
    assert bm._batching_enabled() is (jax.default_backend() == "tpu")
    monkeypatch.setenv("VOLSYNC_BATCH_SEGMENTS", "1")
    assert bm._batching_enabled() is True
    monkeypatch.setenv("VOLSYNC_BATCH_SEGMENTS", "0")
    assert bm._batching_enabled() is False
    monkeypatch.setenv("VOLSYNC_BATCH_SEGMENTS", "false")
    assert bm._batching_enabled() is False


@pytest.mark.slow
def test_treebackup_batched_plus_device_verified_restore(tmp_path,
                                                         monkeypatch):
    """Feature interaction guard: the shared micro-batcher (batched
    dispatches) composing with device-batched restore verification —
    snapshot bit-identity and a verified restore in one flow."""
    from volsync_tpu.engine import TreeBackup, restore_snapshot
    from volsync_tpu.objstore import MemObjectStore
    from volsync_tpu.ops import batcher as batcher_mod
    from volsync_tpu.repo.repository import Repository

    rng = np.random.RandomState(77)
    src = tmp_path / "src"
    src.mkdir()
    for i in range(4):
        (src / f"f{i}.bin").write_bytes(rng.bytes(120_000 + i * 9000))
    # zero-heavy file: exercises the SPARSE writer inside the
    # device-verified restore path (holes + verification together)
    (src / "holes.bin").write_bytes(
        rng.bytes(8192) + bytes(300_000) + rng.bytes(4096))

    chunker_cfg = {"min_size": P.min_size, "avg_size": P.avg_size,
                   "max_size": P.max_size, "seed": P.seed, "align": 4096}
    monkeypatch.setenv("VOLSYNC_BATCH_SEGMENTS", "1")
    monkeypatch.setenv("VOLSYNC_DEVICE_VERIFY", "1")
    monkeypatch.setattr(batcher_mod, "_SHARED", {})
    repo = Repository.init(MemObjectStore(), chunker=chunker_cfg)
    try:
        snap, _ = TreeBackup(repo).run(src)
        dst = tmp_path / "dst"
        restore_snapshot(repo, dst)
    finally:
        for b in batcher_mod._SHARED.values():
            b.stop()
    for i in range(4):
        assert (dst / f"f{i}.bin").read_bytes() \
            == (src / f"f{i}.bin").read_bytes()
    assert (dst / "holes.bin").read_bytes() \
        == (src / "holes.bin").read_bytes()


def test_batched_rejects_over_int32_index_space():
    """A >=2 GiB batch cannot be gathered with int32 indices (x64 off;
    TPUs index in int32) — the library refuses loudly instead of
    overflowing inside the tail-digest gather. Shape-only: lowering
    with abstract avals, no 2 GiB allocation."""
    import functools

    import jax
    import jax.numpy as jnp
    import pytest

    from volsync_tpu.ops.gearcdc import DEFAULT_PARAMS as p
    from volsync_tpu.ops.segment import chunk_hash_segments, segment_caps

    n = 64 * (1 << 20)
    cand_cap, chunk_cap = segment_caps(n, p)

    @functools.partial(jax.jit, static_argnames=("cand_cap", "chunk_cap"))
    def f(rows, vl, eof, *, cand_cap, chunk_cap):
        return chunk_hash_segments(
            rows, vl, eof, min_size=p.min_size, avg_size=p.avg_size,
            max_size=p.max_size, seed=p.seed, mask_s=p.mask_s,
            mask_l=p.mask_l, align=p.align, cand_cap=cand_cap,
            chunk_cap=chunk_cap)

    with pytest.raises(ValueError, match="int32 index space"):
        f.lower(jax.ShapeDtypeStruct((32 * n,), jnp.uint8),
                jax.ShapeDtypeStruct((32,), jnp.int32),
                jax.ShapeDtypeStruct((32,), jnp.bool_),
                cand_cap=cand_cap, chunk_cap=chunk_cap)
    # 16 lanes x 64 MiB = 1 GiB stays inside and lowers fine.
    f.lower(jax.ShapeDtypeStruct((16 * n,), jnp.uint8),
            jax.ShapeDtypeStruct((16,), jnp.int32),
            jax.ShapeDtypeStruct((16,), jnp.bool_),
            cand_cap=cand_cap, chunk_cap=chunk_cap)


def test_hash_bucket_splits_at_index_space_bound(monkeypatch, rng):
    """An oversized same-bucket batch splits into compliant
    sub-dispatches instead of failing every lane (pinned with a
    shrunken _MAX_FLAT_BYTES so no gigabyte allocations)."""
    from volsync_tpu.ops import segment as seg
    from volsync_tpu.ops.gearcdc import DEFAULT_PARAMS as p
    from volsync_tpu.ops.segment import BatchedSegmentHasher

    h = BatchedSegmentHasher(p)
    bufs = [rng.bytes(192 * 1024) for _ in range(5)]
    items = [(b, len(b), True) for b in bufs]
    want = h.hash_segments(items)  # one dispatch, unbounded

    calls = []
    real = seg.chunk_hash_segments

    def spy(rows, *a, **kw):
        calls.append(int(rows.shape[0]))  # flat [S*P] staging
        return real(rows, *a, **kw)

    monkeypatch.setattr(seg, "chunk_hash_segments", spy)
    # bucket for 192 KiB is 256 KiB: allow at most 2 lanes per dispatch
    monkeypatch.setattr(seg, "_MAX_FLAT_BYTES", 2 * 256 * 1024)
    got = BatchedSegmentHasher(p).hash_segments(items)
    assert got == want  # identical chunks/consumed per lane
    assert len(calls) >= 3  # genuinely split
    assert all(n <= 2 * 256 * 1024 for n in calls)


# -- one lane that is already the bucket goes to the device uncopied ------

CHUNKER = {"min_size": P.min_size, "avg_size": P.avg_size,
           "max_size": P.max_size, "seed": P.seed,
           "norm_level": P.norm_level, "align": P.align}
BUCKET = SEG  # a lane this long is its bucket


def _reference(data: bytes) -> list[tuple[int, int, str]]:
    return [(s, n, ref_blobid.blob_id(data[s: s + n]))
            for s, n in ref_gearcdc.cuts(data, CHUNKER)]


def _padded(valid: int, seed: int) -> bytes:
    """``valid`` random bytes zero-padded to the bucket."""
    return np.random.default_rng([seed, valid]).bytes(valid) \
        + bytes(BUCKET - valid)


def _at_odd_offset(padded: bytes) -> np.ndarray:
    """The view a stream sends: bucket bytes somewhere inside a larger
    bytearray (``head - tail`` is on no grid)."""
    whole = bytearray(13 + BUCKET + 7)
    whole[13: 13 + BUCKET] = padded
    return np.frombuffer(whole, np.uint8)[13: 13 + BUCKET]


_LANE_KINDS = {
    "ndarray": lambda padded: np.frombuffer(padded, np.uint8).copy(),
    "odd-offset-view": _at_odd_offset,
    "bytes": bytes,
    "memoryview": lambda padded: memoryview(bytearray(padded)),
}


@pytest.fixture
def counted():
    """Spans, counters and the copy ledger from zero."""
    reset_spans()
    reset_copies()


@pytest.mark.parametrize("valid,eof", [
    (BUCKET - 5000, True),   # an eof segment with a partial tail leaf
    (BUCKET - 5000, False),  # a stream's middle segment, pad after it
    (BUCKET, True),          # the lane fills the bucket to the byte
], ids=["eof-tail", "not-eof", "full"])
@pytest.mark.parametrize("kind", list(_LANE_KINDS))
def test_a_bucket_shaped_lane_goes_direct_and_reads_the_same(
        counted, kind, valid, eof):
    """Alone in its dispatch and as long as its bucket, a lane is handed
    to the device as it is: the cuts and ids are those of the same bytes
    sent one byte short of the bucket (the copy path pads that byte back
    as a zero) and the plain reference's, no rows were filled and given
    back to stage it, and the buffer reads afterwards as it did
    before."""
    from volsync_tpu.ops.segment import BatchedSegmentHasher

    padded = _padded(valid, 11)
    lane = _LANE_KINDS[kind](padded)
    h = BatchedSegmentHasher(P)
    (chunks, consumed), = h.hash_segments([(lane, valid, eof)])
    assert counter_totals() == {
        "ops.dispatches": 1, "ops.lanes": 1, "ops.lanes_padded": 1,
        "ops.lanes_direct": 1, "ops.bytes_valid": valid,
        "ops.bytes_padded": BUCKET}
    # the bytes handed to the device are counted as the parent counted
    # them (segment_hbm_roofline divides by them); no rows were made
    assert copies_by_site() == {"device.stage": BUCKET}
    assert span_totals()["ops.stage"][0] == 1  # nothing to release
    assert bytes(lane) == padded

    ref = _reference(padded[:valid])
    if eof:
        assert chunks == ref and consumed == valid
    else:
        assert chunks and chunks == ref[: len(chunks)]
        assert consumed == sum(n for _, n, _ in chunks) < valid
    if valid < BUCKET:
        reset_spans()
        reset_copies()
        copied, = h.hash_segments([(padded[: BUCKET - 1], valid, eof)])
        assert copied == (chunks, consumed)
        assert "ops.lanes_direct" not in counter_totals()
        assert copies_by_site()["device.stage"] == BUCKET - 1


@pytest.mark.parametrize("lanes", [
    lambda: [_padded(BUCKET - 5000, 21), _padded(BUCKET - 4096, 22)],
    lambda: [_padded(BUCKET - 5000, 23)[: BUCKET - 1]],
], ids=["two-lanes", "short-lane"])
def test_every_other_batch_is_copied_as_before(counted, lanes):
    """Two lanes, a lane shorter than its bucket: rows are filled as
    they always were, and the direct lane's counter stays at nothing."""
    from volsync_tpu.ops.segment import BatchedSegmentHasher

    valid = BUCKET - 5000
    bufs = lanes()
    got = BatchedSegmentHasher(P).hash_segments(
        [(buf, valid, True) for buf in bufs])
    for buf, (chunks, consumed) in zip(bufs, got):
        assert chunks == _reference(bytes(buf)[:valid])
        assert consumed == valid
    counts = counter_totals()
    assert "ops.lanes_direct" not in counts
    assert counts["ops.lanes"] == len(bufs) and counts["ops.dispatches"] == 1
    assert copies_by_site()["device.stage"] == sum(map(len, bufs))
    assert span_totals()["ops.stage"][0] == 2  # fill, release


def test_a_strided_lane_is_refused_as_before(counted):
    """A buffer that is not contiguous never was a lane (np.frombuffer
    refuses it): being bucket-long and alone does not make it one."""
    from volsync_tpu.ops.segment import BatchedSegmentHasher

    wide = np.zeros((BUCKET, 2), np.uint8)
    with pytest.raises(ValueError, match="contiguous"):
        BatchedSegmentHasher(P).hash_segments(
            [(wide[:, 0], BUCKET - 5000, True)])
    assert counter_totals() == {} and copies_by_site() == {}


@pytest.mark.parametrize("caps,grown", [
    ((4096, 4), "chunk table"),    # 4 chunks of a ~8-chunk segment
    ((2, 512), "candidate table"),  # 2 candidates of dozens
])
def test_overflow_retry_resends_a_direct_lane(counted, monkeypatch, caps,
                                              grown):
    """The lane that overflowed the compiled tables is sent again alone
    from the rows of its batch: on the direct path those are the
    caller's buffer."""
    from volsync_tpu.ops import segment as seg

    monkeypatch.setattr(seg, "segment_caps", lambda padded, params: caps)
    valid = BUCKET - 5000
    padded = _padded(valid, 31)
    lane = _at_odd_offset(padded)
    (chunks, consumed), = seg.BatchedSegmentHasher(P).hash_segments(
        [(lane, valid, True)])
    assert chunks == _reference(padded[:valid]), grown
    assert consumed == valid
    assert span_totals()["ops.overflow_retry"][0] == 1
    counts = counter_totals()
    assert counts["ops.lanes_direct"] == 1
    assert counts["ops.dispatches"] >= 2  # the batch, then the lane alone
    assert bytes(lane) == padded


def test_a_lone_stream_sends_every_segment_direct(counted, batch_segments,
                                                  monkeypatch):
    """``stream_chunk_batches`` over the shared batcher (the backup
    cells' way to the device): every segment is a pooled view padded in
    place to its bucket, so every lane is direct; the chunks are the
    reference's, and once the consumer lets go of them every pooled
    buffer is free again: neither the batcher nor the runtime keeps a
    view of one."""
    import io

    import jax

    from volsync_tpu.engine import bufpool
    from volsync_tpu.engine.chunker import stream_chunk_batches

    batch_segments(True)
    pool = bufpool.BufferPool()
    monkeypatch.setattr(bufpool, "GLOBAL", pool)
    data = np.random.default_rng(41).bytes(5 * BUCKET + 12_345)
    got, at = [], 0
    for batch in stream_chunk_batches(io.BytesIO(data).read, P,
                                      segment_size=BUCKET, readahead=0):
        for view, digest in batch:
            got.append((at, len(view), digest))
            assert bytes(view) == data[at: at + len(view)]
            at += len(view)
        del batch, view
    assert got == _reference(data)
    counts = counter_totals()
    assert counts["ops.lanes"] >= 5
    assert counts["ops.lanes_direct"] == counts["ops.lanes"] \
        == counts["ops.dispatches"]
    assert copies_by_site()["device.stage"] == counts["ops.bytes_padded"]
    # (the runtime may keep its reference to the last host buffer it
    # read until it is next called: one more call lets it go)
    jax.device_put(np.zeros(8, np.uint8)).block_until_ready()
    pool.release(pool.acquire(4096))  # an acquire re-probes what is parked
    assert pool._parked == []
    assert pool._free_bytes > 4096  # and the segments' buffers came back


# -- a batch that cannot grow is not made to wait (PR 50) ------------------

LONG_MS = 5000.0  # a window no test could sit out and still pass


@pytest.fixture
def fake_batcher(counted):
    """-> make(**kw): a SegmentMicroBatcher whose device is a fake
    (each lane answers with its own bytes and its batch's size), the
    batches it saw in ``.seen``; stopped when the test ends."""
    from volsync_tpu.ops.batcher import SegmentMicroBatcher

    made = []

    def make(**kw):
        mb = SegmentMicroBatcher(P, **kw)
        mb.seen = []

        def hash_segments(items):
            mb.seen.append(len(items))
            return [((bytes(data), len(items)), length)
                    for data, length, _eof in items]

        mb._hasher.hash_segments = hash_segments
        made.append(mb)
        return mb

    yield make
    for mb in made:
        mb.stop()


def _timed(fn, *args):
    import time

    t0 = time.monotonic()
    got = fn(*args)
    return got, time.monotonic() - t0


def test_a_lone_registered_producer_is_not_made_to_wait(fake_batcher):
    """One registered producer: its batch is complete as it arrives, so
    a window of five seconds costs it nothing; the batch is still one
    ``ops.queue_wait`` and one ``ops.batch_dispatch``."""
    mb = fake_batcher(window_ms=LONG_MS)
    with mb.producer():
        got, took = _timed(mb.submit, b"mine", 4, True)
    assert got == ((b"mine", 1), 4)
    assert took < 1.0
    counts = counter_totals()
    assert counts["ops.batches"] == counts["ops.batches_complete"] == 1
    spans = span_totals()
    assert spans["ops.queue_wait"][0] == 1
    assert spans["ops.batch_dispatch"][0] == 1


def test_two_registered_producers_go_as_the_second_arrives(fake_batcher):
    """Two registered producers on two threads: the first waits for the
    second, not for the window, and they share ONE batch of two lanes,
    each with its own lane's result."""
    import threading
    import time

    mb = fake_batcher(window_ms=LONG_MS)
    both = threading.Barrier(2)
    got = {}

    def stream(name, delay):
        with mb.producer():
            both.wait()  # neither submits before both are registered
            time.sleep(delay)
            got[name] = mb.submit(name, len(name), True)

    threads = [threading.Thread(target=stream, args=(n, d), name=f"s-{d}")
               for n, d in ((b"first", 0.0), (b"second", 0.05))]
    _, took = _timed(lambda: [t.start() for t in threads]
                     and [t.join() for t in threads])
    assert took < 2.0
    assert mb.seen == [2]
    assert got == {b"first": ((b"first", 2), 5),
                   b"second": ((b"second", 2), 6)}
    counts = counter_totals()
    assert counts["ops.batches"] == counts["ops.batches_complete"] == 1


def test_nobody_registered_waits_the_window_as_before(fake_batcher):
    """The service's shape: nobody registered, two ``submit_async`` 50
    ms apart inside a window of 500: one batch of two, after the window,
    and no batch counted complete."""
    import time

    mb = fake_batcher(window_ms=500.0)
    t0 = time.monotonic()
    a = mb.submit_async(b"a", 1, True)
    time.sleep(0.05)
    b = mb.submit_async(b"b", 1, True)
    assert mb.wait(a) == ((b"a", 2), 1) and mb.wait(b) == ((b"b", 2), 1)
    assert time.monotonic() - t0 >= 0.45
    assert mb.seen == [2]
    counts = counter_totals()
    assert counts["ops.batches"] == 1
    assert counts.get("ops.batches_complete", 0) == 0


def test_a_producer_that_leaves_lets_the_waiter_go(fake_batcher):
    """Two registered, one submits: it waits for the other. When the
    other leaves without a segment, the waiter goes at once and not at
    the window's end."""
    import threading

    mb = fake_batcher(window_ms=LONG_MS)
    other = mb.producer()
    other.__enter__()
    done = []
    with mb.producer():
        t = threading.Thread(
            target=lambda: done.append(mb.submit(b"w", 1, True)),
            name="waiter")
        t.start()
        t.join(0.2)
        assert t.is_alive()  # held for the producer that is absent
        other.__exit__(None, None, None)
        t.join(2.0)
    assert done == [((b"w", 1), 1)]
    assert counter_totals()["ops.batches_complete"] == 1


def test_a_leaked_registration_costs_the_window_never_a_hang(fake_batcher):
    """A registration nobody took back (an abandoned generator): the
    live producer pays the window, as before registrations existed, and
    still gets its result."""
    mb = fake_batcher(window_ms=100.0)
    leaked = mb.producer()  # (held: the collector would end it)
    leaked.__enter__()  # never left
    with mb.producer():
        got, took = _timed(mb.submit, b"live", 4, True)
    assert got == ((b"live", 1), 4)
    assert 0.08 <= took < 2.0
    counts = counter_totals()
    assert counts["ops.batches"] == 1
    assert counts.get("ops.batches_complete", 0) == 0


def test_stop_resolves_a_registered_producers_queued_item(fake_batcher):
    """``stop()`` with a registered producer's item in hand (held for a
    second producer that never comes): the future still resolves, at
    the window's end."""
    import threading

    mb = fake_batcher(window_ms=300.0)
    done = []
    with mb.producer(), mb.producer():
        t = threading.Thread(
            target=lambda: done.append(mb.submit(b"q", 1, True)),
            name="queued")
        t.start()
        t.join(0.1)
        assert t.is_alive()
        stopper = threading.Thread(target=mb.stop, name="stopper")
        stopper.start()
        t.join(7.0)
    stopper.join(10.0)
    assert done == [((b"q", 1), 1)]


def test_producers_that_come_and_go_keep_their_own_lanes(fake_batcher):
    """More producers than cores register, send and leave, over and
    over, under a short switch interval: every caller gets its own
    lane's result, every submitted lane was dispatched once, every
    batch was counted, and nobody is left registered."""
    import sys
    import threading

    mb = fake_batcher(window_ms=20.0, max_batch=4)
    streams, files, segments = 24, 20, 2
    wrong = []

    def stream(i):
        for f in range(files):
            with mb.producer():
                for k in range(segments):
                    mine = b"%d/%d/%d" % (i, f, k)
                    (data, lanes), length = mb.submit(mine, len(mine), True)
                    if data != mine or length != len(mine) \
                            or not 1 <= lanes <= 4:
                        wrong.append((mine, data, lanes))

    threads = [threading.Thread(target=stream, args=(i,), name=f"stream-{i}")
               for i in range(streams)]
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(60.0)
    finally:
        sys.setswitchinterval(was)
    assert not any(t.is_alive() for t in threads)
    assert wrong == []
    assert mb._producers == 0
    assert sum(mb.seen) == streams * files * segments
    assert counter_totals()["ops.batches"] == len(mb.seen)


@pytest.mark.parametrize("ending", ["exhausted", "closed-early", "raises"])
def test_a_stream_is_a_producer_for_as_long_as_it_lives(
        counted, batch_segments, monkeypatch, ending):
    """``stream_chunk_batches`` over the shared batcher with a window
    nobody could sit out: the stream registers itself, so every batch is
    complete as it arrives and none waits; the chunks are the
    reference's; and however the stream ends (exhausted, closed early,
    its reader raising) the batcher counts no producer afterwards."""
    import io
    import time

    from volsync_tpu.engine import bufpool
    from volsync_tpu.engine.chunker import stream_chunk_batches
    from volsync_tpu.ops.batcher import shared_batcher

    batch_segments(True)
    monkeypatch.setenv("VOLSYNC_BATCH_WINDOW_MS", str(LONG_MS))
    monkeypatch.setattr(bufpool, "GLOBAL", bufpool.BufferPool())
    data = np.random.default_rng(50).bytes(3 * BUCKET + 12_345)
    source = io.BytesIO(data)

    def read(n):
        if ending == "raises" and source.tell() >= 3 * BUCKET:
            raise OSError("the volume went away")
        return source.read(n)

    mb = shared_batcher(P)
    assert mb._window == LONG_MS / 1000.0
    stream = stream_chunk_batches(read, P, segment_size=BUCKET, readahead=0)
    got, at = [], 0
    t0 = time.monotonic()
    try:
        for batch in stream:
            assert mb._producers == 1  # for as long as it lives
            for view, digest in batch:
                got.append((at, len(view), digest))
                at += len(view)
            if ending == "closed-early":
                stream.close()
                break
    except OSError:
        assert ending == "raises"
    else:
        assert ending != "raises"
    assert time.monotonic() - t0 < LONG_MS / 1000.0  # no window sat out
    assert mb._producers == 0
    want = _reference(data)
    assert got == (want if ending == "exhausted" else want[:len(got)])
    assert got
    counts = counter_totals()
    assert counts["ops.batches"] == counts["ops.batches_complete"] >= 1
    assert counts["ops.batches"] == span_totals()["ops.batch_dispatch"][0] \
        == span_totals()["ops.queue_wait"][0]
