"""Fleet replica failure drills (`make chaos-fleet`): 3 fenced mover
replicas on ONE repository plus a CONTINUOUS GC service, under seeded
fault schedules — including kill-a-replica-mid-stream and a store
partition. The PR 7 x PR 10 composition contract, end to end:

- every admitted backup job completes byte-identically on SOME replica
  (sheds follow sibling hints, deaths re-route through the router),
- the dead replica's stale lock is taken over and its writer fenced;
  its late publish raises StaleWriterError,
- the continuous GC keeps its cadence through contention and weather
  and never sweeps a live pack or leaves a dangling index entry,
- `check(read_data=True)` through the UNFAULTED store ends clean.

Same determinism idiom as tests/test_chaos.py: workers=1 backups keep
the pack keyspace fixed per seed, `at=N` specs fire unconditionally,
and the final contract is inspected through the plain FsObjectStore.
"""

import json
import threading
import time
from datetime import datetime, timedelta, timezone

import numpy as np
import pytest

from volsync_tpu.engine import TreeBackup, restore_snapshot
from volsync_tpu.objstore.faultstore import (
    FaultSchedule,
    FaultSpec,
    FaultStore,
)
from volsync_tpu.objstore.store import FsObjectStore
from volsync_tpu.repo.repository import Repository, StaleWriterError
from volsync_tpu.resilience import CircuitBreaker, ResilientStore, RetryPolicy
from volsync_tpu.service.fleet import ReplicaGroup
from volsync_tpu.service.gc import ContinuousGC

CHUNKER = {"min_size": 4096, "avg_size": 32768, "max_size": 65536,
           "seed": 7, "align": 4096}

N_REPLICAS = 3
N_JOBS = 5


def _chaos_stack(root, seed, specs):
    """open_store() layering with the test-tuned chaos policy (see
    tests/test_chaos.py): attempts high enough that p^attempts is
    negligible, no wall-clock backoff, a breaker that never trips."""
    fs = FsObjectStore(str(root))
    faults = FaultStore(fs, FaultSchedule(seed=seed, specs=list(specs)))
    policy = RetryPolicy(site="chaos", max_attempts=10, base_delay=0.001,
                         max_delay=0.01, sleep_fn=lambda s: None)
    top = ResilientStore(faults, policy=policy,
                         breaker=CircuitBreaker("chaos", threshold=10**9,
                                                reset_seconds=0.01))
    return fs, faults, top


def _age_locks(fs, *, seconds: float) -> int:
    """Backdate every lock's refresh stamp — the fingerprint of holders
    that died a while ago (tests/test_chaos.py idiom)."""
    stamped = 0
    when = (datetime.now(timezone.utc)
            - timedelta(seconds=seconds)).isoformat()
    for key in list(fs.list("locks/")):
        info = json.loads(fs.get(key))
        info["time"] = when
        fs.put(key, json.dumps(info).encode())
        stamped += 1
    return stamped


def _job_tree(tmp_path, j):
    rng = np.random.RandomState(60 + j)
    src = tmp_path / f"job{j}"
    src.mkdir()
    for i in range(2):
        (src / f"f{i}.bin").write_bytes(rng.bytes(90_000 + 13 * i + 7 * j))
    return src


def _seed_garbage(fs, tmp_path):
    """One kept snapshot plus a deleted one's unique chunks, so the
    continuous GC has victims to mark and partially-live packs to
    rewrite WHILE the fleet serves jobs."""
    pre = tmp_path / "pre"
    pre.mkdir()
    rng = np.random.RandomState(77)
    for i in range(4):
        (pre / f"g{i}.bin").write_bytes(rng.bytes(150_000 + 11 * i))
    repo = Repository.open(fs)
    repo.PACK_TARGET = 64 * 1024
    doomed, _ = TreeBackup(repo).run(pre)
    for i in range(2):
        (pre / f"g{i}.bin").write_bytes(rng.bytes(150_000 + 11 * i))
    kept, _ = TreeBackup(repo).run(pre)
    repo.delete_snapshot(doomed)
    return pre, kept


#: Fleet drill matrix — ≥6 seeded schedules. Per entry:
#:
#: - ``replica_specs`` — weather on EVERY replica's store stack;
#: - ``extra`` — {replica_index: [specs]} appended to one replica's
#:   stack: the kill schedule crashes r00's store mid-data-put (it dies
#:   mid-stream like a killed pod, jobs fail over), the partition
#:   schedule makes r00 unreachable for a window (its jobs re-route
#:   while it is dark, it rejoins after the heal);
#: - ``gc_specs`` — faults on the CONTINUOUS GC's own store stack; the
#:   crash entry kills the GC writer mid-mark and the service must keep
#:   its cadence (outcome "error"), with a clean retried prune after;
#: - ``kill`` — also kill r00 at the fleet level mid-run (heartbeat
#:   dies unretired, gRPC hard-stops, locks linger) and assert the full
#:   fence path: takeover, fenced marker, late publish refused.
FLEET_SCHEDULES = [
    ("fleet-transient", 2101, dict(
        replica_specs=[FaultSpec(kind="transient", p=0.15),
                       FaultSpec(kind="transient", at=3)])),
    ("fleet-throttle-latency", 2202, dict(
        replica_specs=[FaultSpec(kind="throttle", p=0.10),
                       FaultSpec(kind="latency", p=0.20, latency=0.001),
                       FaultSpec(kind="throttle", at=4)])),
    ("fleet-partition", 2303, dict(
        extra={0: [FaultSpec(kind="partition", at=3, op="put",
                             latency=0.3)]})),
    ("fleet-kill-mid-stream", 2404, dict(
        kill=True,
        extra={0: [FaultSpec(kind="crash", at=2, op="put",
                             key_prefix="data/")]})),
    ("fleet-gc-weather", 2505, dict(
        replica_specs=[FaultSpec(kind="transient", p=0.10),
                       FaultSpec(kind="transient", at=3)],
        gc_specs=[FaultSpec(kind="transient", p=0.20)])),
    ("fleet-gc-crash", 2606, dict(
        gc_specs=[FaultSpec(kind="crash", at=1, op="put",
                            key_prefix="pending-delete/")])),
    ("fleet-mixed", 2707, dict(
        replica_specs=[FaultSpec(kind="transient", p=0.10),
                       FaultSpec(kind="throttle", p=0.05),
                       FaultSpec(kind="latency", p=0.10, latency=0.001),
                       FaultSpec(kind="truncated_read", p=0.10,
                                 op="get_range"),
                       FaultSpec(kind="transient", at=3)],
        gc_specs=[FaultSpec(kind="transient", p=0.10)])),
]


@pytest.mark.parametrize("name,seed,cfg", FLEET_SCHEDULES,
                         ids=[s[0] for s in FLEET_SCHEDULES])
def test_chaos_fleet(tmp_path, monkeypatch, name, seed, cfg):
    from volsync_tpu.metrics import GLOBAL as METRICS

    monkeypatch.setenv("VOLSYNC_LOCK_STALE_S", "5")
    replica_specs = cfg.get("replica_specs", [])
    gc_specs = cfg.get("gc_specs", [])
    extra = cfg.get("extra", {})
    kill = cfg.get("kill", False)

    root = tmp_path / "store"
    fs = FsObjectStore(str(root))
    Repository.init(fs, chunker=CHUNKER)
    pre, kept = _seed_garbage(fs, tmp_path)
    trees = [_job_tree(tmp_path, j) for j in range(N_JOBS)]

    # one chaos stack per replica: distinct seeds, shared backing store
    stacks = [_chaos_stack(root, seed + t,
                           list(replica_specs) + list(extra.get(t, [])))
              for t in range(N_REPLICAS)]
    _g_fs, g_faults, g_top = _chaos_stack(root, seed + 99, gc_specs)

    if kill:
        # a stalled r00 process from "before the kill": holds a shared
        # lock over the UNFAULTED store so its late publish can be
        # observed after the fleet fences it
        zombie = Repository.open(fs)
        zombie._write_lock("shared")
        zombie_writer = zombie.writer_id
        fenced_before = METRICS.repo_fenced_publishes_total._value.get()
    failovers_before = METRICS.fleet_failovers_total._value.get()

    group = ReplicaGroup([st[2] for st in stacks], router_store=fs,
                         ttl_seconds=30.0, beat_seconds=999.0,
                         batch_window_ms=0, max_streams=4)
    for r in group.replicas:
        r.repo.PACK_TARGET = 64 * 1024
        r.repo.default_lock_wait = 10.0
    gc = ContinuousGC(g_top, interval_seconds=0.05, grace_seconds=0.2,
                      lock_wait=2.0)

    snaps: list = []
    killed_mid_run = False
    with group, gc:
        for j, tree in enumerate(trees):
            group.beat_all()
            snap, rid = group.submit_backup(tree, hostname=f"job{j}")
            snaps.append(snap)
            assert snap and rid in {r.replica_id for r in group.replicas}
            if kill and not killed_mid_run and stacks[0][1].crashed:
                # r00's store just died mid-stream (the job failed over
                # and completed elsewhere); now kill it at the fleet
                # level too — like the pod going away
                group.kill("r00")
                killed_mid_run = True
        group.beat_all()
        deadline = time.monotonic() + 10.0
        while gc.cycles < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
    assert len(snaps) == N_JOBS

    # -- the schedule actually exercised something ------------------------
    if replica_specs:
        assert all(st[1].injected for st in stacks), \
            "a replica schedule never fired — drill tested nothing"
    if extra:
        for t in extra:
            assert stacks[t][1].injected, \
                f"replica {t}'s extra schedule never fired"
    assert gc.cycles >= 2
    if gc_specs and any(s.kind == "crash" for s in gc_specs):
        # the GC writer crashed mid-mark; the service kept its cadence
        # and reported the cycles instead of wedging
        assert g_faults.crashed
        assert gc.outcomes.get("error", 0) >= 1
    if "partition" in {s.kind for s in extra.get(0, [])} or kill:
        # jobs re-routed off the dark/dead replica
        assert (METRICS.fleet_failovers_total._value.get()
                > failovers_before)

    # -- kill drill: takeover + fencing + late publish refused ------------
    if kill:
        assert killed_mid_run, "the kill schedule never killed r00"
        assert group.replica("r00")._killed
        # the dead replica's stamp was never retired: it lingers, aging
        assert fs.exists("fleet/r00")
        # its lock (and the zombie's) linger too; age them past the
        # horizon, then a retried prune must take over and fence
        assert _age_locks(fs, seconds=60) >= 1
        retry = Repository.open(fs)
        retry.default_lock_wait = 10.0
        retry.prune(grace_seconds=0.2)
        assert fs.exists(f"fenced/{zombie_writer}"), \
            "takeover never fenced the dead replica's writer"
        # the zombie wakes up and tries to publish: refused, typed
        with pytest.raises(StaleWriterError):
            TreeBackup(zombie).run(trees[0],
                                              hostname="zombie-late")
        assert (METRICS.repo_fenced_publishes_total._value.get()
                > fenced_before)

    # -- end state: collect, then the full contract through the ----------
    # -- UNFAULTED store --------------------------------------------------
    time.sleep(0.3)  # grace expiry for anything the GC marked late
    # anything still holding a lock crashed (live replicas released on
    # stop): age the leftovers so the final prune can take over
    _age_locks(fs, seconds=60)
    final = Repository.open(fs)
    final.default_lock_wait = 10.0
    # mark-then-sweep pair: when the GC's store died before it ever
    # marked, the first pass parks the victims and the second collects
    # them once the grace expires (no-ops when the GC already finished)
    final.prune(grace_seconds=0.2)
    time.sleep(0.3)
    final.prune(grace_seconds=0.2)
    assert list(fs.list("pending-delete/")) == [], \
        "continuous GC left pending-delete debris"

    check = Repository.open(fs)
    assert check.check(read_data=True) == []
    ids = [s[0] for s in check.list_snapshots()]
    assert set(snaps) <= set(ids), "an admitted job's snapshot vanished"
    for j, snap in enumerate(snaps):
        dst = tmp_path / f"dst{j}"
        prev = len(ids) - 1 - ids.index(snap)
        restore_snapshot(Repository.open(fs), dst, previous=prev)
        for f in sorted(p.name for p in trees[j].iterdir()):
            assert (dst / f).read_bytes() == (trees[j] / f).read_bytes(), f
    dstk = tmp_path / "dstk"
    prev = len(ids) - 1 - ids.index(kept)
    restore_snapshot(Repository.open(fs), dstk, previous=prev)
    for f in sorted(p.name for p in pre.iterdir()):
        assert (dstk / f).read_bytes() == (pre / f).read_bytes(), f
    with check._lock:
        packs = [p for p in check._index.live_packs() if p]
    for p in packs:
        assert fs.exists(f"data/{p[:2]}/{p}"), \
            f"index references missing pack {p} — a live pack was swept"
