"""Fleet replica plane units (ISSUE 11): heartbeat stamps and their
TTL arithmetic, headroom routing and cache-only sibling hints,
decorrelated-jitter retry-after hints, deadline-class scheduling
(EDF within a tenant, typed DeadlineExceeded sheds before device
work), the continuous GC service's outcome loop, and repair's cleanup
of crashed replicas' stale stamps. Deterministic: fake clocks and
driven beats, no wall-clock waits, no gRPC."""

import json
import random
import threading
from concurrent.futures import Future
from datetime import datetime, timedelta, timezone

import pytest

from volsync_tpu.objstore.store import FsObjectStore, MemObjectStore
from volsync_tpu.repo.repository import Repository
from volsync_tpu.service.admission import (
    AdmissionController,
    AdmissionRejected,
)
from volsync_tpu.service.fleet import (
    FLEET_PREFIX,
    FleetRouter,
    ReplicaHeartbeat,
    ReplicaStamp,
)
from volsync_tpu.service.gc import ContinuousGC
from volsync_tpu.service.scheduler import (
    DEFAULT_DEADLINE_CLASSES,
    DeadlineExceeded,
    SegmentScheduler,
    parse_deadline_classes,
)
from volsync_tpu.service.tenants import TenantConfig, TenantRegistry


def _stamp(rid="r00", address="h:1", headroom=4, backlog=0,
           age_seconds=0.0, **kw):
    when = (datetime.now(timezone.utc)
            - timedelta(seconds=age_seconds)).isoformat()
    return ReplicaStamp(replica_id=rid, address=address,
                        headroom=headroom, backlog=backlog,
                        writer_id=kw.get("writer_id", "w"),
                        generation=kw.get("generation", 1),
                        seq=kw.get("seq", 1), time=when)


# -- replica stamps ----------------------------------------------------------

def test_stamp_round_trip_and_torn_payloads():
    stamp = _stamp(headroom=7, backlog=3)
    back = ReplicaStamp.from_json(stamp.to_json())
    assert back == stamp
    for torn in (b"", b"{", b"[]", b'{"replica_id": "x"}'):
        with pytest.raises(ValueError):
            ReplicaStamp.from_json(torn)


def test_stamp_ttl_expiry():
    assert not _stamp(age_seconds=1.0).expired(10.0)
    assert _stamp(age_seconds=11.0).expired(10.0)


# -- heartbeat ---------------------------------------------------------------

def test_heartbeat_beats_and_retires():
    mem = MemObjectStore()
    hb = ReplicaHeartbeat(mem, "r07", "h:9", headroom_fn=lambda: 5,
                          backlog_fn=lambda: 2, beat_seconds=999)
    s1 = hb.beat()
    s2 = hb.beat()
    assert (s1.seq, s2.seq) == (1, 2)  # beat extends, seq orders
    stored = ReplicaStamp.from_json(mem.get(f"{FLEET_PREFIX}r07"))
    assert (stored.address, stored.headroom, stored.backlog) == ("h:9", 5, 2)
    hb.stop(retire=True)
    assert not mem.exists(f"{FLEET_PREFIX}r07")


def test_heartbeat_kill_path_leaves_stamp_to_expire():
    mem = MemObjectStore()
    hb = ReplicaHeartbeat(mem, "r07", "h:9", headroom_fn=lambda: 5,
                          beat_seconds=999)
    hb.beat()
    hb.stop(retire=False)  # died like a killed pod
    assert mem.exists(f"{FLEET_PREFIX}r07")  # stamp ages toward TTL


def test_heartbeat_survives_store_failure():
    class _DeadStore(MemObjectStore):
        def put(self, key, data):
            raise OSError("store down")

    hb = ReplicaHeartbeat(_DeadStore(), "r07", "h:9",
                          headroom_fn=lambda: 5, beat_seconds=999)
    with pytest.raises(OSError):
        hb.beat()  # explicit beat surfaces the error...
    hb.start()  # ...the background path swallows and counts it
    hb.stop(retire=False)
    assert hb.missed >= 1


# -- router ------------------------------------------------------------------

def test_router_routes_by_headroom_then_backlog():
    mem = MemObjectStore()
    for rid, headroom, backlog in (("r00", 2, 9), ("r01", 6, 5),
                                   ("r02", 6, 1), ("r03", 0, 0)):
        st = _stamp(rid=rid, address=f"h:{rid}", headroom=headroom,
                    backlog=backlog)
        mem.put(f"{FLEET_PREFIX}{rid}", st.to_json())
    router = FleetRouter(mem, ttl_seconds=30.0)
    best = router.pick()
    assert best.replica_id == "r02"  # most headroom, least backlog
    assert router.pick(exclude=("r02",)).replica_id == "r01"
    # headroom 0 is never picked even when everyone else is excluded
    assert router.pick(exclude=("r00", "r01", "r02")) is None


def test_router_skips_expired_and_torn_stamps():
    mem = MemObjectStore()
    mem.put(f"{FLEET_PREFIX}dead",
            _stamp(rid="dead", age_seconds=60.0).to_json())
    mem.put(f"{FLEET_PREFIX}torn", b"{not json")
    mem.put(f"{FLEET_PREFIX}live", _stamp(rid="live").to_json())
    router = FleetRouter(mem, ttl_seconds=10.0)
    assert [s.replica_id for s in router.refresh()] == ["live"]
    assert router.pick().replica_id == "live"


def test_router_sibling_hint_is_cache_only_and_excludes_self():
    mem = MemObjectStore()
    mem.put(f"{FLEET_PREFIX}r00", _stamp(rid="r00", address="a:0",
                                         headroom=9).to_json())
    mem.put(f"{FLEET_PREFIX}r01", _stamp(rid="r01", address="a:1",
                                         headroom=3).to_json())
    router = FleetRouter(mem, ttl_seconds=30.0)
    assert router.sibling_hint("r00") is None  # cold cache: no I/O
    router.refresh()
    assert router.sibling_hint("r00") == "a:1"  # self excluded
    assert router.sibling_hint("r99") == "a:0"  # best overall

    class _Tripwire(MemObjectStore):
        def list(self, prefix=""):
            raise AssertionError("sibling_hint must not touch the store")

        def get(self, key):
            raise AssertionError("sibling_hint must not touch the store")

    router.store = _Tripwire()
    assert router.sibling_hint("r00") == "a:1"  # still served from cache


# -- admission: jittered hints + sibling + headroom ---------------------------

def _controller(**kw):
    kw.setdefault("max_streams", 3)
    kw.setdefault("tenant_streams", 2)
    kw.setdefault("max_queued", 10)
    kw.setdefault("retry_after", 0.1)
    return AdmissionController(TenantRegistry(), **kw)


def test_retry_after_hints_are_jittered_and_bounded():
    ctrl = _controller(jitter_rng=random.Random(7))
    for _ in range(2):
        ctrl.admit_stream("a")
    hints = []
    for _ in range(50):
        with pytest.raises(AdmissionRejected) as ei:
            ctrl.admit_stream("a")
        hints.append(ei.value.retry_after)
    base = ctrl.retry_after
    assert all(base <= h <= base * 10 for h in hints)
    # decorrelated: N clients shed together draw DIFFERENT hints
    assert len({round(h, 6) for h in hints}) > 10
    # seeded rng makes the sequence reproducible
    ctrl2 = _controller(jitter_rng=random.Random(7))
    for _ in range(2):
        ctrl2.admit_stream("a")
    replay = []
    for _ in range(50):
        with pytest.raises(AdmissionRejected) as ei2:
            ctrl2.admit_stream("a")
        replay.append(ei2.value.retry_after)
    assert replay == hints


def test_breaker_sheds_keep_exact_cooldown_hint():
    class _OpenBreaker:
        def open_remaining(self):
            return 1.25

    ctrl = _controller(breaker=_OpenBreaker())
    with pytest.raises(AdmissionRejected) as ei:
        ctrl.admit_stream("a")
    assert ei.value.reason == "breaker_open"
    assert ei.value.retry_after == pytest.approx(1.25)  # not jittered


def test_shed_carries_sibling_hint():
    ctrl = _controller(sibling_fn=lambda: "peer:7777")
    for _ in range(2):
        ctrl.admit_stream("a")
    with pytest.raises(AdmissionRejected) as ei:
        ctrl.admit_stream("a")
    assert ei.value.sibling == "peer:7777"
    assert "peer:7777" in str(ei.value)


def test_headroom_tracks_admits_and_drain():
    ctrl = _controller(max_streams=3)
    assert ctrl.headroom() == 3
    t = ctrl.admit_stream("a")
    assert ctrl.headroom() == 2
    ctrl.release(t)
    assert ctrl.headroom() == 3
    ctrl.begin_drain()
    assert ctrl.headroom() == 0  # draining replicas advertise nothing


# -- deadline-class scheduling ------------------------------------------------

class _FakeBatcher:
    _depth = 1
    _max_batch = 16

    def __init__(self):
        self.calls = []

    def submit_async(self, data, length, eof):
        f = Future()
        self.calls.append((data, length, eof, f))
        return f


class _Clock:
    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now


def _drain_rounds(sched, limit=50):
    for _ in range(limit):
        if not sched.service_round():
            return


def test_parse_deadline_classes():
    assert parse_deadline_classes("") == DEFAULT_DEADLINE_CLASSES
    got = parse_deadline_classes("fast=0.25, slow=none ,bulk=inf")
    assert got == {"fast": 0.25, "slow": None, "bulk": None}
    with pytest.raises(ValueError, match="bad deadline class"):
        parse_deadline_classes("fast")
    with pytest.raises(ValueError, match="must be > 0"):
        parse_deadline_classes("fast=-1")


def test_edf_within_tenant_deadline_first_then_fifo():
    """Within one tenant the most urgent segment dispatches first;
    deadline-free segments keep FIFO order among themselves, last."""
    fb = _FakeBatcher()
    clock = _Clock()
    sched = SegmentScheduler(fb, TenantRegistry(), quantum=1000,
                             tenant_queued=64, dispatch_window=1000,
                             clock=clock, start=False)
    sched.submit("t", b"free1", 10, False)               # no deadline
    sched.submit("t", b"lax", 10, False, deadline=9.0)
    sched.submit("t", b"urgent", 10, False, deadline=2.0)
    sched.submit("t", b"free2", 10, False)               # no deadline
    _drain_rounds(sched)
    assert [d for d, _, _, _ in fb.calls] \
        == [b"urgent", b"lax", b"free1", b"free2"]
    sched.stop()


def test_expired_deadline_sheds_typed_before_batcher():
    """A segment whose deadline passed while queued fails with
    DeadlineExceeded and never reaches the batcher (no device work
    for an answer nobody is waiting for)."""
    from volsync_tpu.metrics import GLOBAL as METRICS

    fb = _FakeBatcher()
    clock = _Clock()
    sched = SegmentScheduler(fb, TenantRegistry(), quantum=1000,
                             tenant_queued=64, dispatch_window=1000,
                             clock=clock, start=False)
    before = METRICS.svc_deadline_exceeded.labels(
        tenant="t")._value.get()
    doomed = sched.submit("t", b"late", 10, False, deadline=0.5)
    ok = sched.submit("t", b"fine", 10, False)
    clock.now = 1.0  # the deadline passes while queued
    _drain_rounds(sched)
    with pytest.raises(DeadlineExceeded) as ei:
        doomed.result(timeout=1)
    assert ei.value.tenant == "t"
    assert [d for d, _, _, _ in fb.calls] == [b"fine"]  # late never sent
    assert METRICS.svc_deadline_exceeded.labels(
        tenant="t")._value.get() == before + 1
    fb.calls[0][3].set_result(([], 10))
    assert ok.result(timeout=1) == ([], 10)
    sched.stop()


def test_deadline_class_isolation_under_background_saturation():
    """The acceptance shape, deterministically: an interactive tenant
    with tight deadlines keeps bounded queue wait while a background
    tenant saturates its queue — WDRR isolates across tenants, and
    every interactive segment dispatches (no deadline sheds) while
    background segments wait arbitrarily long without shedding
    (deadline None never expires)."""
    reg = TenantRegistry([TenantConfig(name="fg", weight=4),
                          TenantConfig(name="bg", weight=1)])
    fb = _FakeBatcher()
    clock = _Clock()
    sched = SegmentScheduler(fb, reg, quantum=100, tenant_queued=256,
                             dispatch_window=10_000, clock=clock,
                             start=False)
    for i in range(200):  # saturated background class, no deadline
        sched.submit("bg", b"bg%03d" % i, 100, False)
    for i in range(8):    # interactive, tight deadline
        sched.submit("fg", b"fg%d" % i, 100, False, deadline=5.0)
    # each round advances time; deadlines would expire if interactive
    # work queued behind the background backlog
    for _ in range(60):
        if not sched.service_round():
            break
        clock.now += 0.1
    sent = [d for d, _, _, _ in fb.calls]
    fg_positions = [i for i, d in enumerate(sent) if d.startswith(b"fg")]
    assert len(fg_positions) == 8, "an interactive segment was shed"
    # 4:1 weights: all 8 interactive segments land within the first
    # ~2 rounds' worth of dispatches despite the 200-deep backlog
    assert max(fg_positions) < 20
    sched.stop()


# -- continuous GC service ----------------------------------------------------

def _garbage_repo(tmp_path):
    """A repo with a deleted snapshot's worth of garbage to collect."""
    import numpy as np

    from volsync_tpu.engine import TreeBackup

    fs = FsObjectStore(str(tmp_path / "store"))
    Repository.init(fs, chunker={"min_size": 4096, "avg_size": 32768,
                                 "max_size": 65536, "seed": 7,
                                 "align": 4096})
    src = tmp_path / "src"
    src.mkdir()
    rng = np.random.RandomState(3)
    for i in range(3):
        (src / f"f{i}.bin").write_bytes(rng.bytes(120_000 + i))
    repo = Repository.open(fs)
    repo.PACK_TARGET = 64 * 1024
    doomed, _ = TreeBackup(repo).run(src)
    (src / "f0.bin").write_bytes(rng.bytes(120_000))
    kept, _ = TreeBackup(repo).run(src)
    repo.delete_snapshot(doomed)
    return fs, kept


def test_gc_cycle_outcomes(tmp_path):
    fs, _kept = _garbage_repo(tmp_path)
    gc = ContinuousGC(fs, interval_seconds=999, grace_seconds=0.01)
    assert gc.run_once() == "ok"
    assert gc.last_report is not None

    # contended: a peer holds a conflicting prune-mode lock
    peer = Repository.open(fs)
    with peer.lock(mode="prune"):
        assert gc.run_once() == "contended"
    assert gc.run_once() == "ok"  # lock released: next cycle proceeds

    # fenced: a takeover marked this GC writer dead mid-flight — the
    # cycle reports it and the NEXT cycle reopens a fresh generation
    victim = gc._open()
    old_writer = victim.writer_id
    fs.put(f"fenced/{old_writer}", json.dumps(
        {"writer": "peer", "time":
         datetime.now(timezone.utc).isoformat()}).encode())
    assert gc.run_once() == "fenced"
    assert gc.run_once() == "ok"
    assert gc._open().writer_id != old_writer  # reopened, new identity
    assert gc.outcomes == {"ok": 3, "contended": 1, "fenced": 1}
    assert Repository.open(fs).check(read_data=True) == []


def test_gc_rejects_stop_the_world_grace():
    with pytest.raises(ValueError, match="grace_seconds > 0"):
        ContinuousGC(MemObjectStore(), grace_seconds=0)


def test_gc_background_loop_runs_and_stops(tmp_path):
    fs, _kept = _garbage_repo(tmp_path)
    gc = ContinuousGC(fs, interval_seconds=0.01, grace_seconds=0.01)
    done = threading.Event()
    orig = gc.run_once

    def counting():
        out = orig()
        if gc.cycles >= 2:
            done.set()
        return out

    gc.run_once = counting
    with gc:
        assert done.wait(10.0), "GC loop never completed two cycles"
    assert gc.cycles >= 2


# -- repair reaps crashed replicas' stamps ------------------------------------

def test_repair_clears_stale_fleet_stamps(tmp_path, monkeypatch):
    monkeypatch.setenv("VOLSYNC_LOCK_STALE_S", "5")
    fs, _kept = _garbage_repo(tmp_path)
    fs.put(f"{FLEET_PREFIX}dead",
           _stamp(rid="dead", age_seconds=60.0).to_json())
    fs.put(f"{FLEET_PREFIX}torn", b"{not json")
    fs.put(f"{FLEET_PREFIX}live", _stamp(rid="live").to_json())
    report = Repository.open(fs).repair(grace_seconds=0.01)
    assert f"{FLEET_PREFIX}dead" in report["stale_markers"]
    assert f"{FLEET_PREFIX}torn" in report["stale_markers"]
    assert not fs.exists(f"{FLEET_PREFIX}dead")
    assert not fs.exists(f"{FLEET_PREFIX}torn")
    assert fs.exists(f"{FLEET_PREFIX}live")  # live replicas untouched
