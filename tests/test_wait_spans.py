"""The spans at the queues (PERF.md section 3): a thread blocked on the
gRPC handler pool (``svc.accept_wait``), on the read-ahead queue
(``engine.read_wait``), on the seal queue (``repo.seal_wait``) or on the
upload window (``repo.upload_slot_wait``) is timed where it waits, and
``backup.file``'s host-path stages have names (``backup.read``,
``backup.blob_id``, ``repo.add``). Each is recorded when its thread
waited, and not when it did not; none changes what it observes.
"""

import io
import threading
import time

import grpc
import numpy as np
import pytest

from volsync_tpu import obs
from volsync_tpu.engine import (DeviceChunkHasher, TreeBackup,
                                params_from_config)
from volsync_tpu.engine.chunker import stream_chunk_batches
from volsync_tpu.objstore.store import LatencyStore, MemObjectStore
from volsync_tpu.ops.gearcdc import GearParams
from volsync_tpu.repo import blobid
from volsync_tpu.repo.repository import Repository
from volsync_tpu.service import MoverJaxClient, MoverJaxServer
from volsync_tpu.service import server as server_mod

P4K = GearParams(min_size=4096, avg_size=32768, max_size=65536, align=4096)
CHUNKER = {"min_size": 1024, "avg_size": 4096, "max_size": 16384, "seed": 7}
CHUNKER_4K = {"min_size": 4096, "avg_size": 32768, "max_size": 65536,
              "seed": 7, "align": 4096}


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.reset_spans()
    obs.reset_trace()
    yield
    obs.reset_spans()
    obs.reset_trace()


def events(name):
    return [e for e in obs.trace_events()
            if e["ph"] == "X" and e["name"] == name]


def end(event):
    return event["ts"] + event["dur"]


# -- (a) svc.accept_wait ---------------------------------------------------

def test_a_stream_waiting_for_a_handler_thread_is_timed(monkeypatch):
    """One handler thread, two streams: the second's ``svc.accept_wait``
    begins as it arrives (while the first is in its handler) and ends
    as its own handler starts, which is after the first's has left: it
    is the first's handler time less the lag of the second's arrival.
    It carries the client's trace id, as ``svc.stream`` does; a refused
    call records none."""
    arrivals = []
    arrived = threading.Condition()
    real = server_mod._finish_on_entry

    def finish_on_entry(handler, wait):
        with arrived:
            arrivals.append(wait)
            arrived.notify_all()
        return real(handler, wait)

    monkeypatch.setattr(server_mod, "_finish_on_entry", finish_on_entry)
    release = threading.Event()
    payload = np.random.RandomState(3).bytes(96 * 1024)

    def held_reader():
        pieces = [payload]

        def read(n):
            if pieces:
                return pieces.pop()
            release.wait(60)  # the handler sits in svc.ingest meanwhile
            return b""
        return read

    def stream(tenant, reader, out):
        with MoverJaxClient("127.0.0.1", srv.port, srv.token,
                            tenant=tenant) as c:
            out[tenant] = list(c.chunk_stream(reader))

    out = {}
    with MoverJaxServer(params=P4K, segment_size=128 * 1024, handlers=1,
                        batch_window_ms=0) as srv:
        first = threading.Thread(
            target=stream, args=("first", held_reader(), out))
        first.start()
        with arrived:
            assert arrived.wait_for(lambda: len(arrivals) == 1, 60)
        deadline = time.monotonic() + 60
        while "svc.accept_wait" not in obs.span_totals():
            assert time.monotonic() < deadline
            time.sleep(0.005)  # the first is in its handler now
        second = threading.Thread(
            target=stream, args=("second", io.BytesIO(payload).read, out))
        second.start()
        with arrived:
            assert arrived.wait_for(lambda: len(arrivals) == 2, 60)
        time.sleep(0.2)  # the second waits for the one thread
        assert obs.span_totals()["svc.accept_wait"][0] == 1
        release.set()
        first.join(60)
        second.join(60)
        assert obs.span_totals()["svc.accept_wait"][0] == 2

        with MoverJaxClient("127.0.0.1", srv.port, "wrong") as c:
            with pytest.raises(grpc.RpcError) as refused:
                c.chunk_bytes(payload)
        assert refused.value.code() == grpc.StatusCode.UNAUTHENTICATED
        assert obs.span_totals()["svc.accept_wait"][0] == 2

        with MoverJaxClient("127.0.0.1", srv.port, srv.token) as c:
            c.info()  # a probe: not a stream's wait
            assert obs.span_totals()["svc.accept_wait"][0] == 2
            assert len(c.hash_spans(payload, [(0, 4096)])) == 1
        assert obs.span_totals()["svc.accept_wait"][0] == 3

    assert out["first"] == out["second"] and out["first"]
    by_tenant = {}
    for name in ("svc.accept_wait", "svc.stream", "client.chunk_stream"):
        for e in events(name):
            by_tenant.setdefault(e["args"].get("tenant"), {})[name] = e
    a, b = by_tenant["first"], by_tenant["second"]
    wait = b["svc.accept_wait"]
    handler = a["svc.stream"]
    assert wait["dur"] >= 0.2e6
    assert handler["ts"] < wait["ts"] < end(handler)
    lag = wait["ts"] - handler["ts"]  # the second arrived this much later
    assert wait["dur"] >= handler["dur"] - lag
    assert end(wait) <= b["svc.stream"]["ts"]
    assert a["svc.accept_wait"]["dur"] < wait["dur"]
    for mine in (a, b):
        client = mine["client.chunk_stream"]["args"]
        for name in ("svc.accept_wait", "svc.stream"):
            assert mine[name]["args"]["trace_id"] == client["trace_id"]
            assert mine[name]["args"]["parent_span_id"] == client["span_id"]
    # a wait is a handle: all of it is self time, and nobody's child
    assert obs.span_self_totals()["svc.accept_wait"][1] \
        == pytest.approx(obs.span_totals()["svc.accept_wait"][1])


# -- (b) repo.seal_wait, repo.upload_slot_wait -------------------------------

def _slow_seal(repo, seconds):
    real = repo._encode_blob

    def encode(data):
        time.sleep(seconds)
        return real(data)

    repo._encode_blob = encode


def _blobs(n, size=3000, seed=5):
    rng = np.random.RandomState(seed)
    return [(blobid.blob_id(d), d) for d in (rng.bytes(size)
                                             for _ in range(n))]


@pytest.mark.parametrize("batched", [False, True], ids=["add_blob",
                                                        "add_blobs"])
def test_a_full_seal_queue_is_a_wait_and_one_with_room_is_not(
        monkeypatch, batched):
    def add(repo, blobs):
        if batched:
            repo.add_blobs("data", blobs)
        else:
            for bid, data in blobs:
                repo.add_blob("data", bid, data)

    roomy = Repository.init(MemObjectStore())
    _slow_seal(roomy, 0.01)
    add(roomy, _blobs(6))  # six of sixteen places
    roomy.flush()
    totals = obs.span_totals()
    assert "repo.seal_wait" not in totals
    assert "repo.upload_slot_wait" not in totals
    assert totals["repo.add"][0] == (1 if batched else 6)
    assert totals["repo.seal"][0] == 6

    obs.reset_spans()
    monkeypatch.setenv("VOLSYNC_TPU_SEAL_QUEUE", "1")
    tight = Repository.init(MemObjectStore())
    _slow_seal(tight, 0.03)
    add(tight, _blobs(6))
    tight.flush()
    n, secs = obs.span_totals()["repo.seal_wait"]
    assert n == 6  # every new blob found the one place taken: its own
    assert secs >= 6 * 0.03 * 0.9
    # the wait closes inside repo.add, whose self time is the rest
    adds = obs.span_totals()["repo.add"][1]
    own = obs.span_self_totals()["repo.add"][1]
    assert own <= adds - secs + 1e-6
    assert sorted(tight.store.list("data/")) \
        == sorted(roomy.store.list("data/"))


def test_a_full_upload_window_is_a_wait_inside_the_seal_wait(monkeypatch):
    monkeypatch.setenv("VOLSYNC_TPU_SEAL_QUEUE", "1")
    monkeypatch.setenv("VOLSYNC_TPU_UPLOAD_WINDOW", "1")
    store = LatencyStore(MemObjectStore(), put_latency=0.05)
    repo = Repository.init(store)
    repo.PACK_TARGET = 4096  # a pack a blob
    with obs.trace_context(sampled=True):
        for bid, data in _blobs(5, size=5000):
            repo.add_blob("data", bid, data)
        repo.flush()
    assert store.max_concurrent_puts == 1
    slots = events("repo.upload_slot_wait")
    seals = {e["args"]["span_id"]: e for e in events("repo.seal_wait")}
    nested = [e for e in slots if e["args"]["parent_span_id"] in seals]
    assert len(nested) >= 3
    assert sum(e["dur"] for e in nested) >= 3 * 0.05e6 * 0.8
    for e in nested:
        outer = seals[e["args"]["parent_span_id"]]
        assert outer["ts"] <= e["ts"] and end(e) <= end(outer) + 1
        assert e["tid"] == outer["tid"]
    # counted once: the seal wait's self time leaves its child out
    # (the flush's last pack may wait too: that one is repo.flush's)
    total = obs.span_totals()["repo.seal_wait"][1]
    own = obs.span_self_totals()["repo.seal_wait"][1]
    assert own == pytest.approx(total - sum(e["dur"] for e in nested) / 1e6,
                                abs=1e-4)
    flushes = {e["args"]["span_id"] for e in events("repo.flush")}
    assert all(e["args"]["parent_span_id"] in flushes
               for e in slots if e not in nested)

    obs.reset_spans()
    monkeypatch.delenv("VOLSYNC_TPU_UPLOAD_WINDOW")
    wide = Repository.init(LatencyStore(MemObjectStore(), put_latency=0.01))
    wide.PACK_TARGET = 4096
    for bid, data in _blobs(3, size=5000):
        wide.add_blob("data", bid, data)
    wide.flush()
    assert "repo.upload_slot_wait" not in obs.span_totals()


# -- (c) engine.read_wait ----------------------------------------------------

class _Hasher:
    """A stand-in for the device: one chunk a segment."""

    def process(self, arr, eof):
        return [(0, len(arr), "00" * 32)] if len(arr) else []


@pytest.mark.parametrize("readahead, waits", [(2, True), (0, False)])
def test_the_consumer_of_a_slow_reader_waits_at_the_readahead(
        readahead, waits):
    data = np.random.RandomState(9).bytes(6 * 65536)  # four segments
    src = io.BytesIO(data)

    def slow_read(n):
        time.sleep(0.02)
        return src.read(min(n, 65536))

    got = b"".join(
        bytes(view) for batch in stream_chunk_batches(
            slow_read, P4K, segment_size=65536, hasher=_Hasher(),
            readahead=readahead)
        for view, _ in batch)
    assert got == data
    totals = obs.span_totals()
    assert totals["engine.read"][0] >= 6
    if not waits:  # inline reads are engine.read on this thread already
        assert "engine.read_wait" not in totals
        return
    n, secs = totals["engine.read_wait"]
    assert n >= 3  # one a segment
    assert secs >= 6 * 0.02 * 0.8
    assert secs <= totals["engine.read"][1] + 0.05


# -- (d), (e) backup.file and what is inside it --------------------------------

@pytest.fixture
def tree(tmp_path, rng):
    root = tmp_path / "src"
    files = {f"small/s{i:02d}": rng.bytes(200 + 311 * i) for i in range(12)}
    files.update({
        "small/at_min": rng.bytes(4096),
        "small/empty": b"",
        "big/b0": rng.bytes(300_000),
        "big/b1": rng.bytes(70_000),
    })
    for rel, data in files.items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_bytes(data)
    return root, files


def _backup(root):
    repo = Repository.init(MemObjectStore(), chunker=CHUNKER_4K)
    snap_id, _ = TreeBackup(repo).run(root)
    return repo, dict(repo.list_snapshots())[snap_id]


def test_backup_file_is_its_children_and_its_self_time(tree, monkeypatch):
    root, files = tree
    on_host = [rel for rel, d in files.items() if 0 < len(d) <= 4096]
    on_device = [rel for rel, d in files.items() if len(d) > 4096]
    hashed = {}  # the totals as the last backup.file has closed
    real = TreeBackup._assemble_tree

    def assemble(self, *args):
        hashed.update(obs.span_totals())
        return real(self, *args)

    monkeypatch.setattr(TreeBackup, "_assemble_tree", assemble)
    with obs.trace_context(sampled=True):
        _backup(root)
    totals = obs.span_totals()
    assert totals["backup.read"][0] == len(on_host) == 13
    assert totals["backup.blob_id"][0] == len(on_host)
    assert totals["backup.file"][0] == len(on_host) + len(on_device)
    assert totals["engine.read_wait"][0] >= len(on_device)
    assert totals["backup.open"][0] == len(on_device)
    assert hashed["repo.add"][0] >= len(on_host) + len(on_device)
    file_events = events("backup.file")
    paths = [e["args"]["path"] for e in file_events]
    assert paths.count("host") == len(on_host)
    assert paths.count("device") == len(on_device)
    for f in file_events:
        kids = {e["name"] for e in obs.trace_events()
                if e["ph"] == "X" and e["tid"] == f["tid"]
                and e["args"]["parent_span_id"] == f["args"]["span_id"]}
        if f["args"]["path"] == "host":
            # once a file and well under a millisecond: totals, and no
            # events to push the rest out of the ring; a full seal
            # queue under load is a wait, which is an event
            assert kids <= {"repo.seal_wait"}
        else:
            assert {"backup.open", "engine.read_wait", "engine.device",
                    "repo.add"} <= kids
            assert not kids & {"backup.read", "backup.blob_id"}
    # the seal a host-path file queued is on the ring, under the file
    by_id = {f["args"]["span_id"]: f for f in file_events}
    sealed_under = [by_id[e["args"]["parent_span_id"]]["args"]["path"]
                    for e in events("repo.seal")
                    if e["args"]["parent_span_id"] in by_id]
    assert sealed_under.count("host") == len(on_host)
    # what closes inside a backup.file, on its thread
    inside = sum(hashed[name][1] for name in (
        "backup.read", "backup.blob_id", "backup.open", "engine.read_wait",
        "engine.device", "repo.add"))
    duration = totals["backup.file"][1]
    own = obs.span_self_totals()["backup.file"][1]
    assert duration == hashed["backup.file"][1]
    assert duration == pytest.approx(own + inside,
                                     abs=1e-6 * totals["backup.file"][0])
    assert 0 < own < duration


def test_a_sampled_context_changes_nothing_a_backup_stores(tree):
    root, _ = tree
    plain, first = _backup(root)
    assert obs.trace_events() == []
    with obs.trace_context(sampled=True):
        traced, second = _backup(root)
    assert events("backup.file") and events("repo.add")
    assert first["tree"] == second["tree"]
    keys = sorted(plain.store.list("data/"))
    assert keys == sorted(traced.store.list("data/")) and keys
    for key in keys:
        assert plain.store.get(key) == traced.store.get(key)


# -- (f) a file that fits one fill: the wait is the read (PR 49) ---------------

class _SmallFill(DeviceChunkHasher):
    """One fill is 64 KiB + max_size = 128 KiB, so a tier-1 file can be
    longer than one."""

    def stream_segment_size(self, segment_size):
        return 65536


@pytest.mark.parametrize("size, fits", [(100_000, True), (300_000, False)],
                         ids=["one-fill", "longer"])
def test_the_wait_for_a_segment_is_the_read_where_nothing_reads_ahead(
        tmp_path, rng, size, fits):
    """``engine.read_wait`` is the hash thread's wait for a segment's
    bytes on both paths. A file that fits one fill is read by that
    thread: one wait a file with ``engine.read`` closing inside it, off
    the ring. A longer file keeps the read-ahead thread, whose
    ``engine.read`` is beside the wait and on the ring."""
    root = tmp_path / "src"
    root.mkdir()
    (root / "f").write_bytes(rng.bytes(size))
    repo = Repository.init(MemObjectStore(), chunker=CHUNKER_4K)
    with obs.trace_context(sampled=True):
        TreeBackup(repo, hasher=_SmallFill(params_from_config(CHUNKER_4K))).run(root)
    totals, own = obs.span_totals(), obs.span_self_totals()
    (file_event,) = events("backup.file")
    waits, reads = events("engine.read_wait"), events("engine.read")
    assert totals["backup.open"][0] == len(events("backup.open")) == 1
    assert {w["tid"] for w in waits} == {file_event["tid"]}
    assert len(waits) == totals["engine.read_wait"][0]
    if fits:
        assert len(waits) == 1
        assert reads == [] and totals["engine.read"][0] >= 2
        assert totals["engine.read"][1] <= totals["engine.read_wait"][1]
        assert own["engine.read_wait"][1] == pytest.approx(
            totals["engine.read_wait"][1] - totals["engine.read"][1])
        assert obs.counter_totals()["backup.reads_direct"] == 1
    else:
        assert len(waits) >= 3  # one a segment
        assert len(reads) == totals["engine.read"][0] >= 3
        assert file_event["tid"] not in {r["tid"] for r in reads}
        assert own["engine.read_wait"][1] == pytest.approx(
            totals["engine.read_wait"][1])
        assert not obs.counter_totals().get("backup.reads_direct")
