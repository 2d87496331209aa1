"""The fused paths against the plain reference.

``tests/test_sharded_chunker.py`` holds the mesh engine to the one-chip
engine; here both are held to the benchmark's plain reference of the
repository format (``benchmark/reference/gearcdc.py``: numpy alone,
``benchmark/reference/blobid.py``: hashlib alone), which shares no code
with either: the mesh on 4 of the suite's 8 virtual CPU devices — the
four-chip host of ``dedup-1t.backup-mesh4`` with the chunker scaled
down — and the one-chip engine both ways, the single-lane program the
suite pins and the shared batcher the one-chip cells run.
"""

import io

import numpy as np
import pytest

from benchmark.reference import blobid as ref_blobid
from benchmark.reference import gearcdc as ref_gearcdc
from volsync_tpu.engine.chunker import (DeviceChunkHasher, _buffer_bucket,
                                        _segment_source, stream_chunks)
from volsync_tpu.obs import (copies_by_site, counter_totals, reset_copies,
                             reset_spans, span_totals)
from volsync_tpu.ops.gearcdc import GearParams
from volsync_tpu.parallel.sharded_chunker import (MeshChunkHasher,
                                                  make_stream_mesh)

FUSED = GearParams(min_size=4096, avg_size=32768, max_size=65536, align=4096)
CHUNKER = {"min_size": FUSED.min_size, "avg_size": FUSED.avg_size,
           "max_size": FUSED.max_size, "seed": FUSED.seed,
           "norm_level": FUSED.norm_level, "align": FUSED.align}
SHARDS = 4
PAGE = 4096


@pytest.fixture(scope="module")
def hasher():
    import jax

    return MeshChunkHasher(FUSED, make_stream_mesh(jax.devices()[:SHARDS]))


@pytest.fixture(params=["mesh", "one-chip", "one-chip-batcher"])
def engine(request, batch_segments):
    """Every fused way to the cuts and ids of a segment."""
    if request.param == "mesh":
        return request.getfixturevalue("hasher")
    batch_segments(request.param == "one-chip-batcher")
    return DeviceChunkHasher(FUSED)


def random_bytes(n: int, seed: int) -> bytes:
    return np.random.default_rng([seed, n]).bytes(n)


def reference(data: bytes) -> list[tuple[int, int, str]]:
    return [(s, n, ref_blobid.blob_id(data[s: s + n]))
            for s, n in ref_gearcdc.cuts(data, CHUNKER)]


@pytest.mark.parametrize("length", [
    1_000_003,                    # no multiple of anything
    SHARDS * PAGE * 61 + PAGE,    # whole pages, not a multiple of shards
    SHARDS * 262144,              # fills the shards' bucket to the byte
])
def test_one_segment_cuts_and_ids_are_the_references(engine, length):
    data = random_bytes(length, 1)
    bucket = getattr(engine, "buffer_bucket", _buffer_bucket)
    assert length % (SHARDS * PAGE) or length == bucket(length)
    assert engine.process(data, eof=True) == reference(data)


@pytest.mark.parametrize("first", [600_000, 3 * 262144 + 17])
def test_two_segments_eof_false_then_true(engine, first):
    """The tail a non-eof segment withholds is re-fed with what follows,
    as a stream does it; the two passes together are the reference's
    cuts of the whole."""
    data = random_bytes(1_100_000, 2)
    head = engine.process(data[:first], eof=False)
    consumed = sum(n for _, n, _ in head)
    assert 0 < consumed < first and consumed % PAGE == 0
    rest = engine.process(data[consumed:], eof=True)
    got = head + [(consumed + s, n, d) for s, n, d in rest]
    assert got == reference(data)


def test_repeat_half_resynchronises(engine):
    """A stream whose second half repeats its first (the deployment's
    shape): past the first cut after the seam the second half's chunks
    are the first half's, id for id, whichever shards they fell in."""
    half = 131 * PAGE  # the seam is on the page grid, not on a shard's
    uniq = random_bytes(half, 3)
    data = uniq + uniq
    got = engine.process(data, eof=True)
    assert got == reference(data)
    first = [(s, n, d) for s, n, d in got if s + n <= half]
    second = {(s - half, n, d) for s, n, d in got if s >= half}
    shared = [c for c in first if c in second]
    assert len(shared) >= len(first) - 3, "the halves did not resynchronise"
    assert len({d for _, _, d in got}) < len(got)


@pytest.mark.parametrize("caps,grown", [
    ((1024, 4), "chunk table"),     # 4 chunks of a ~30-chunk segment
    ((2, 512), "candidate table"),  # 2 candidates a shard
])
def test_forced_capacity_overflow_retries_to_the_reference(
        hasher, monkeypatch, caps, grown):
    data = random_bytes(1_000_003, 4)
    monkeypatch.setattr(hasher, "fused_caps", lambda shard_len: caps)
    reset_spans()
    assert hasher.process(data, eof=True) == reference(data), grown
    spans = span_totals()
    assert spans["mesh.overflow_retry"][0] >= 1
    assert spans["mesh.launch"][0] == 1 + spans["mesh.overflow_retry"][0]
    assert counter_totals()["mesh.dispatches"] == 1  # staged once


@pytest.mark.parametrize("nbytes", [3 * 1024 * 1024 + 999, 2_000_000])
def test_stream_segment_follows_the_shards(hasher, nbytes):
    """Through ``stream_chunks`` a mesh hasher is handed segments sized
    by its shards: every full one fills the four shards' one pow2
    bucket (each shard what one chip would be dispatched), straight
    from the pooled buffer with no pad copy, and the cuts and ids are
    still the reference's of the whole stream."""
    segment = 256 * 1024
    data = random_bytes(nbytes, 5)
    fill = _segment_source(lambda n: b"", FUSED, segment, hasher)
    assert fill.target == SHARDS * segment - FUSED.max_size
    assert (hasher.buffer_bucket(fill.target + FUSED.max_size - 1)
            == SHARDS * segment)
    reset_spans()
    reset_copies()
    got = list(stream_chunks(io.BytesIO(data).read, FUSED,
                             segment_size=segment, hasher=hasher))
    want = reference(data)
    assert [(len(c), d) for c, d in got] == [(n, d) for _, n, d in want]
    assert b"".join(bytes(c) for c, _ in got) == data
    counts = counter_totals()
    full, last = divmod(nbytes, fill.target)
    assert counts["mesh.dispatches"] == full + (1 if last else 0)
    assert counts["mesh.shards"] == SHARDS * counts["mesh.dispatches"]
    assert counts["mesh.bytes_valid"] >= nbytes  # + the carried tails
    assert "mesh.pad" not in copies_by_site()
    # the full segments are at least (bucket - max_size) of a bucket
    staged = counts["mesh.bytes_valid"] + counts["mesh.bytes_padded"]
    assert staged == copies_by_site()["mesh.stage"]
    if full >= 3:  # mostly full segments: few zeros go to the chips
        assert counts["mesh.bytes_valid"] / staged > 0.85


@pytest.mark.parametrize("kind", ["mesh", "one-chip", "fake"])
def test_stream_hands_a_hasher_its_view(hasher, kind, monkeypatch):
    """The two arms of ``stream_chunk_batches``: a hasher with ``begin``
    gets a view of exactly ``buffer_bucket(length)`` bytes whose tail
    past ``valid_len`` is zero, whatever the pooled buffer held before;
    an object with ``process`` alone (a test fake) gets the exact view."""
    import hashlib

    segment = 256 * 1024
    seen = []

    class Fake:
        def process(self, buffer, *, eof):
            seen.append((int(buffer.shape[0]), None, False))
            step = FUSED.max_size
            end = len(buffer) if eof else len(buffer) // step * step
            return [(s, min(step, end - s),
                     hashlib.sha256(buffer[s: s + step][: end - s]).hexdigest())
                    for s in range(0, end, step)]

    if kind == "fake":
        h, bucket = Fake(), None
    else:
        h = hasher if kind == "mesh" else DeviceChunkHasher(FUSED)
        bucket = getattr(h, "buffer_bucket", _buffer_bucket)
        real = h.begin

        def begin(buffer, *, eof, valid_len):
            seen.append((int(buffer.shape[0]), valid_len,
                         bool(buffer[valid_len:].any())))
            return real(buffer, eof=eof, valid_len=valid_len)

        monkeypatch.setattr(h, "begin", begin)
    # a first stream of 0xFF leaves the pool's buffers dirty
    for data in (b"\xff" * 2_300_000, random_bytes(1_000_003, 7)):
        seen.clear()
        got = b"".join(bytes(c) for c, _ in stream_chunks(
            io.BytesIO(data).read, FUSED, segment_size=segment, hasher=h))
        assert got == data  # and no view is left to keep a buffer parked
        assert seen
        for have, valid, dirty in seen:
            if bucket is None:
                assert valid is None  # process(): nothing but the data
            else:
                assert have == bucket(valid) and not dirty
    if bucket is None:
        assert any(have != _buffer_bucket(have) for have, _, _ in seen)


def test_one_segment_source_is_unchanged_for_the_one_chip_engine():
    from volsync_tpu.engine.chunker import _SegmentFill

    segment = 32 * 1024 * 1024
    p = GearParams(align=4096)
    got = _segment_source(lambda n: b"", p, segment, DeviceChunkHasher(p))
    want = _SegmentFill(lambda n: b"", segment, p.max_size)
    assert (got.target, got.capacity, got.head) == \
        (want.target, want.capacity, want.head)
    assert want.capacity == p.max_size + _buffer_bucket(
        segment + 2 * p.max_size)


def test_the_deployments_segment_is_one_chips_a_shard(hasher):
    """At the published chunker and the stream's default segment, four
    shards are filled at 32 MiB each: 120 MiB of new bytes a segment."""
    import inspect

    from volsync_tpu.engine.chunker import stream_chunk_batches

    p = GearParams(align=4096)
    mesh = MeshChunkHasher(p, hasher.mesh)
    segment = inspect.signature(stream_chunk_batches) \
        .parameters["segment_size"].default
    fill = _segment_source(lambda n: b"", p, segment, mesh)
    assert fill.target == SHARDS * segment - p.max_size
    assert mesh.shard_bucket(fill.target + p.max_size - 1) == segment
    assert mesh.shard_bucket(fill.target) == segment


def test_spans_and_counters_once_a_dispatch(hasher):
    data = random_bytes(900_001, 6)
    reset_spans()
    reset_copies()
    for eof in (False, True, True):
        hasher.process(data, eof=eof)
    spans, counts, copies = span_totals(), counter_totals(), copies_by_site()
    for name in ("mesh.stage", "mesh.launch", "mesh.fetch", "mesh.decode"):
        assert spans[name][0] == 3, name
    assert "mesh.overflow_retry" not in spans
    assert counts["mesh.dispatches"] == 3
    assert counts["mesh.shards"] == 3 * SHARDS
    assert counts["mesh.bytes_valid"] == 3 * len(data)
    staged = 3 * hasher.buffer_bucket(len(data))
    assert counts["mesh.bytes_valid"] + counts["mesh.bytes_padded"] == staged
    assert copies["mesh.stage"] == staged
    assert copies["mesh.pad"] == 3 * len(data)  # process() pads; a stream does not
