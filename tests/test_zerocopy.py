"""Zero-copy data-plane contracts (docs/performance.md, "Zero-copy
data movement"): input-type parity — chunker and restore entry points
accept bytes / bytearray / memoryview with byte-identical results —
plus the plumbing that makes the plane zero-copy: ``seal_parts`` ≡
``seal``, the buffer pool's park/probe release safety, and the
PackCache's read-only memoryview range serving."""

import hashlib
import io
import os

import numpy as np
import pytest

from volsync_tpu.engine import bufpool
from volsync_tpu.engine.chunker import (
    hash_spans,
    stream_chunks,
    verify_blob_batch,
)
from volsync_tpu.engine.restore import _write_sparse
from volsync_tpu.ops.gearcdc import GearParams
from volsync_tpu.repo import blobid
from volsync_tpu.repo.crypto import PlainBox, SecretBox

PARAMS = GearParams(min_size=32 * 1024, avg_size=64 * 1024,
                    max_size=128 * 1024, seed=7, align=4096)

VARIANTS = (
    ("bytes", bytes),
    ("bytearray", bytearray),
    ("memoryview", lambda b: memoryview(b).toreadonly()),
)


def _data(n: int, seed: int = 11) -> bytes:
    return np.random.RandomState(seed).bytes(n)


# -- chunker input-type parity ----------------------------------------------

def _chunks_via_reader(data, convert, **kw):
    pos = [0]

    def read(n):
        piece = data[pos[0]: pos[0] + n]
        pos[0] += len(piece)
        return convert(piece)

    return [(bytes(c), d) for c, d in
            stream_chunks(read, PARAMS, **kw)]


def test_stream_chunks_reader_type_parity():
    """A reader may hand back bytes, bytearray or memoryview pieces —
    chunk boundaries and digests are identical, and the reassembled
    stream is byte-identical to the input."""
    data = _data(1536 * 1024 + 777)  # multi-segment + odd tail
    golden = _chunks_via_reader(data, bytes, segment_size=512 * 1024)
    assert b"".join(c for c, _ in golden) == data
    for name, convert in VARIANTS[1:]:
        got = _chunks_via_reader(data, convert, segment_size=512 * 1024)
        assert got == golden, f"reader piece type {name} diverged"


def test_stream_chunks_readinto_source_parity():
    """A readinto()-capable source (io.BytesIO — the zero-ingest-copy
    path) chunks identically to a plain ``read(n)`` callable."""
    data = _data(900 * 1024 + 13, seed=3)
    golden = _chunks_via_reader(data, bytes, segment_size=256 * 1024)
    got = [(bytes(c), d) for c, d in
           stream_chunks(io.BytesIO(data).read, PARAMS,
                         segment_size=256 * 1024)]
    assert got == golden


def test_hash_spans_buffer_type_parity():
    data = _data(64 * 1024, seed=5)
    spans = [(0, 4096), (4096, 10_000), (16384, 0), (20480, 44_056)]
    golden = hash_spans(data, spans)
    assert golden[0] == blobid.blob_id(data[:4096])
    assert golden[2] == blobid.blob_id(b"")
    for name, convert in VARIANTS[1:]:
        assert hash_spans(convert(data), spans) == golden, name


def test_verify_blob_batch_buffer_type_parity():
    blobs = [_data(n, seed=n) for n in (4096, 9_999, 1, 70_000)]
    ids = [blobid.blob_id(b) for b in blobs]
    for name, convert in VARIANTS:
        pairs = [(i, convert(b)) for i, b in zip(ids, blobs)]
        assert verify_blob_batch(pairs) == [], name
    # a corrupted payload is flagged regardless of its buffer type
    bad = bytearray(blobs[1])
    bad[17] ^= 0xFF
    assert verify_blob_batch(
        [(ids[0], memoryview(blobs[0])), (ids[1], bad)]) == [ids[1]]


# -- restore write parity ---------------------------------------------------

def _sparse_write(tmp_path, name, data):
    p = tmp_path / name
    with open(p, "wb") as f:
        _write_sparse(f, data)
        f.truncate(len(data))
    st = os.stat(p)
    return p.read_bytes(), st.st_size, st.st_blocks


@pytest.mark.parametrize("case,data", [
    ("dense", _data(10_000)),
    ("hole-middle", _data(4096) + b"\x00" * 8192 + _data(4096, seed=2)),
    ("hole-lead-tail", b"\x00" * 8192 + _data(512) + b"\x00" * 12288),
    ("all-zero-small", b"\x00" * 1000),
    ("all-zero-pages", b"\x00" * 65536),
    ("zero-partial-tail", _data(8192) + b"\x00" * 100),
    ("empty", b""),
])
def test_write_sparse_input_type_parity(tmp_path, case, data):
    """The positional sparse writer produces byte-identical files AND
    the same hole allocation for bytes, bytearray and memoryview input
    (restore hands it decoded memoryview slices)."""
    golden = _sparse_write(tmp_path, f"{case}-bytes", data)
    assert golden[0] == data and golden[1] == len(data)
    for name, convert in VARIANTS[1:]:
        got = _sparse_write(tmp_path, f"{case}-{name}", convert(data))
        assert got == golden, f"{case}: {name} diverged"


def _historical_write_sparse(f, data) -> None:
    """The writer up to PR 32, the oracle of the page scan: it found
    the longest zero run from an int64 index of every non-zero byte
    (eight bytes of index a data byte), and only then looked at pages."""
    view = memoryview(data).cast("B")
    n = len(view)
    if n == 0:
        f.write(view)
        return
    arr = np.frombuffer(view, np.uint8)
    nz = np.flatnonzero(arr)
    if nz.size == 0:
        if n < 4096:  # no zero page exists -> the dense short-circuit
            f.write(view)
        else:
            f.seek(n, os.SEEK_CUR)
        return
    gaps = np.diff(nz) - 1
    longest = max(int(nz[0]), int(n - 1 - nz[-1]),
                  int(gaps.max()) if gaps.size else 0)
    if longest < 4096:
        f.write(view)
        return
    full = n // 4096
    zero_pages = np.logical_not(
        arr[:full * 4096].reshape(full, 4096).any(axis=1))
    bounds = np.flatnonzero(np.diff(zero_pages)) + 1
    starts = np.concatenate(([0], bounds))
    ends = np.concatenate((bounds, [full]))
    for s, e in zip(starts, ends):
        if zero_pages[s]:
            f.seek((e - s) * 4096, os.SEEK_CUR)
        else:
            f.write(view[s * 4096:e * 4096])
    if full * 4096 < n:
        f.write(view[full * 4096:])


class _RecordingFile:
    """A file object that keeps what was asked of it: the calls, and
    the byte extents written, adjacent ones merged (two writers that
    cut one run of data differently cover the same extents)."""

    def __init__(self):
        self.pos = 0
        self.calls = []
        self.written = []

    def write(self, b):
        n = len(b)
        self.calls.append(("write", n))
        if n:
            if self.written and self.written[-1][1] == self.pos:
                self.written[-1][1] += n
            else:
                self.written.append([self.pos, self.pos + n])
        self.pos += n
        return n

    def seek(self, off, whence=os.SEEK_SET):
        assert whence == os.SEEK_CUR
        self.calls.append(("seek", off))
        self.pos += off
        return self.pos


def _solid(n: int, seed: int = 3) -> bytes:
    """``n`` bytes none of which is zero: a zero run laid into them is
    exactly as long as it was made."""
    return np.random.RandomState(seed).randint(
        1, 256, n, dtype=np.uint8).tobytes()


def _with_zero_run(offset: int, run: int, total: int = 5 * 4096 + 13):
    buf = bytearray(_solid(total))
    buf[offset:offset + run] = bytes(run)
    return bytes(buf)


def _at_odd_address(data: bytes) -> memoryview:
    """A view of ``data`` whose first byte sits at an odd address, as
    a pack-slice view may: no wider word than a byte can be read there
    without a copy."""
    backing = bytearray(len(data) + 1)
    start = 1 - np.frombuffer(backing, np.uint8).ctypes.data % 2
    backing[start:start + len(data)] = data
    return memoryview(backing)[start:start + len(data)]


_EDGE_CASES = {
    **{f"run{run}@{off}": _with_zero_run(off, run)
       for run in (4095, 4096, 8190, 8191) for off in (0, 1, 4095)},
    "one-zero-page": _with_zero_run(2 * 4096, 4096),
    "zero-page-first": _with_zero_run(0, 4096, total=4 * 4096),
    "zero-page-last": _with_zero_run(3 * 4096, 4096, total=4 * 4096),
    "zero-page-then-solid-tail":
        _with_zero_run(4 * 4096, 4096, total=5 * 4096 + 100),
    "zero-page-then-zero-tail":
        _with_zero_run(4 * 4096, 4096 + 100, total=5 * 4096 + 100),
    "zero-pages-then-solid-tail": bytes(2 * 4096) + _solid(5),
    "solid-then-zero-tail": _solid(8192) + bytes(100),
    "short-solid-then-zeros": _solid(100) + bytes(3000),
    "all-zero-1": bytes(1),
    "all-zero-4095": bytes(4095),
    "all-zero-4096": bytes(4096),
    "all-zero-4097": bytes(4097),
    "all-zero-1MiB": bytes(1 << 20),
    "dense-10001": _solid(10_001),
    "dense-3-pages-and-5": _solid(3 * 4096 + 5),
    "random-64KiB-and-3": _data(65_539, seed=9),
    "alternating-pages": b"".join(
        bytes(4096) if i % 2 else _solid(4096, seed=i) for i in range(9)),
    "empty": b"",
}


@pytest.mark.parametrize("case", list(_EDGE_CASES))
def test_write_sparse_keeps_the_historical_outcome(tmp_path, case):
    """On the edges of the hole rule the page scan lays down what the
    historical byte index did: the same bytes, size and allocation on
    a real file, and the same extents written and skipped, from
    ``bytes``, ``bytearray`` and a view at an odd address alike."""
    data = _EDGE_CASES[case]
    p = tmp_path / "oracle"
    with open(p, "wb") as f:
        _historical_write_sparse(f, data)
        f.truncate(len(data))
    st = os.stat(p)
    golden = (p.read_bytes(), st.st_size, st.st_blocks)
    assert golden[0] == data
    want = _RecordingFile()
    _historical_write_sparse(want, data)
    for name, convert in (("bytes", bytes), ("bytearray", bytearray),
                          ("odd-address", _at_odd_address)):
        assert _sparse_write(tmp_path, name, convert(data)) == golden, name
        got = _RecordingFile()
        _write_sparse(got, convert(data))
        assert (got.written, got.pos) == (want.written, want.pos), name


def test_write_sparse_reads_a_blob_once_by_the_page():
    """The mechanism: over a blob without a zero page the scan leaves
    one byte a page behind, never an index that grows with the data
    (the historical writer peaked above sixteen times the blob), and
    the blob goes down as ONE write of all of it, no seek."""
    import tracemalloc

    n = 8 << 20
    blob = _data(n, seed=21)
    f = _RecordingFile()
    tracemalloc.start()
    try:
        _write_sparse(f, blob)
        _, peak = tracemalloc.get_traced_memory()
        tracemalloc.reset_peak()
        _historical_write_sparse(_RecordingFile(), blob)
        _, peak_before = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert f.calls == [("write", n)]
    assert peak < n // 8 < 16 * n < peak_before


# -- vectored seal ----------------------------------------------------------

def _boxes():
    return [SecretBox(b"\x01" * 32, b"\x02" * 32), PlainBox()]


def test_seal_parts_equals_seal(monkeypatch):
    """``join(seal_parts(parts))`` is byte-identical to
    ``seal(join(parts))`` — the invariant the vectored pack path rests
    on (nonce pinned so the two seals draw the same randomness)."""
    from volsync_tpu.repo import crypto

    monkeypatch.setattr(crypto.os, "urandom", lambda n: b"\x07" * n)
    parts = [b"alpha", bytearray(b"bb"), memoryview(b"\x00" * 9000),
             b"", b"tail"]
    joined = b"".join(parts)
    for box in _boxes():
        sealed_parts = box.seal_parts(list(parts))
        assert isinstance(sealed_parts, list)
        assert b"".join(sealed_parts) == box.seal(joined)


def test_seal_parts_roundtrip_without_pinned_nonce():
    parts = [_data(5000, seed=9), bytearray(b"x" * 3), memoryview(b"yz")]
    joined = b"".join(parts)
    for box in _boxes():
        assert box.open(b"".join(box.seal_parts(list(parts)))) == joined


# -- buffer pool ------------------------------------------------------------

def test_bufpool_parks_exported_buffers():
    """A released buffer with a live memoryview is parked, never handed
    out again until the view dies — release safety by construction."""
    pool = bufpool.BufferPool()
    a = pool.acquire(5000)
    assert len(a) == 8192  # rounded to the page grid
    view = memoryview(a)
    pool.release(a)
    b = pool.acquire(8192)
    assert b is not a  # a is parked behind its live export
    view.release()
    pool.release(b)
    c = pool.acquire(8192)
    d = pool.acquire(8192)
    # both buffers recycle once the export is gone — no reallocation
    assert {id(c), id(d)} == {id(a), id(b)}


def test_bufpool_free_budget_drops_excess():
    pool = bufpool.BufferPool(max_free_bytes=8192)
    a, b = pool.acquire(8192), pool.acquire(8192)
    pool.release(a)
    pool.release(b)  # over budget: dropped to the allocator
    got = {id(pool.acquire(8192)), id(pool.acquire(8192))}
    assert id(a) in got and id(b) not in got


# -- pack cache -------------------------------------------------------------

def test_packcache_serves_readonly_views():
    from volsync_tpu.objstore.store import MemObjectStore
    from volsync_tpu.repo.packcache import PackCache

    body = _data(32 * 1024, seed=21)
    pack_id = hashlib.sha256(body).hexdigest()
    store = MemObjectStore()
    store.put(f"data/{pack_id[:2]}/{pack_id}", body)
    cache = PackCache(store)
    views = cache.get_ranges(pack_id, [(0, 4096), (10_000, 5), (0, 0)])
    assert [bytes(v) for v in views] == [body[:4096], body[10_000:10_005],
                                         b""]
    assert all(isinstance(v, memoryview) and v.readonly for v in views)
    assert cache.stats()["misses"] == 1
    cache.get_ranges(pack_id, [(1, 1)])
    assert cache.stats()["hits"] >= 1  # served from cache, no new GET
