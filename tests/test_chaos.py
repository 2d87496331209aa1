"""Chaos soak: full backup -> restore cycles through the resilience
layer over seeded fault schedules (objstore/faultstore.py).

The stack under test is exactly what open_store() builds for a network
backend: ``ResilientStore(FaultStore(FsObjectStore))`` — faults are
injected UNDER the retry layer, where real transport faults occur. For
every schedule the soak asserts the end-to-end contract:

- the backup completes (retries absorb every retryable fault),
- the restore is byte-identical to the source tree,
- the repository checks clean and no index entry references a missing
  pack (inspected through the UNFAULTED store),
- the same seed replays the same fault sequence (determinism).

Crash schedules are the exception: ``InjectedCrash`` is classified
fatal, the backup dies like a killed mover pod, and a fresh open must
see a consistent repository whose retry fully restores.
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
from volsync_tpu.repo.repository import Repository
from volsync_tpu.resilience import CircuitBreaker, ResilientStore, RetryPolicy

CHUNKER = {"min_size": 4096, "avg_size": 32768, "max_size": 65536,
           "seed": 7, "align": 4096}


def _src_tree(tmp_path):
    rng = np.random.RandomState(5)
    src = tmp_path / "src"
    src.mkdir()
    for i in range(3):
        (src / f"f{i}.bin").write_bytes(rng.bytes(120_000 + 13 * i))
    (src / "empty").write_bytes(b"")
    return src


def _chaos_stack(root, seed, specs):
    """(plain fs, fault wrapper, resilient top) — the open_store layering
    with a test-tuned policy: enough attempts that p^attempts is
    negligible, no wall-clock backoff sleeps, a breaker that never
    trips (breaker behavior has its own unit tests)."""
    fs = FsObjectStore(str(root))
    faults = FaultStore(fs, FaultSchedule(seed=seed, specs=list(specs)))
    policy = RetryPolicy(site="chaos", max_attempts=10, base_delay=0.001,
                         max_delay=0.01, sleep_fn=lambda s: None)
    top = ResilientStore(faults, policy=policy,
                         breaker=CircuitBreaker("chaos", threshold=10**9,
                                                reset_seconds=0.01))
    return fs, faults, top


def _assert_consistent_and_restorable(fs, src, dst):
    """Through the UNFAULTED store: repo checks clean, every index-
    referenced pack exists, and a restore is byte-identical."""
    repo = Repository.open(fs)
    assert repo.check(read_data=True) == []
    with repo._lock:
        packs = [p for p in repo._index.live_packs() if p]
    for p in packs:
        assert fs.exists(f"data/{p[:2]}/{p}"), \
            f"index references missing pack {p}"
    restore_snapshot(Repository.open(fs), dst)
    for f in sorted(p.name for p in src.iterdir()):
        assert (dst / f).read_bytes() == (src / f).read_bytes(), f


#: The soak matrix — ≥8 distinct seeded schedules covering every fault
#: kind plus a mixed-weather profile. Pack keys hash ENCRYPTED bytes
#: (fresh salt per init), so probability rolls draw fresh per run:
#: broad specs use p high enough that never-firing is negligible
#: (p=0.2 over ~30 arrivals), while narrowly filtered write/read specs
#: use ``at=N`` — the Nth matching arrival fires unconditionally.
#: Retry exhaustion stays negligible: p^max_attempts = 0.2^10.
SCHEDULES = [
    ("transient-a", 101, [FaultSpec(kind="transient", p=0.20)]),
    ("transient-b", 202, [FaultSpec(kind="transient", p=0.20)]),
    ("transient-landed", 303,
     [FaultSpec(kind="transient", at=1, op="put", landed=True),
      FaultSpec(kind="transient", at=4, op="put", landed=True)]),
    ("throttle", 404, [FaultSpec(kind="throttle", p=0.20)]),
    ("latency", 505, [FaultSpec(kind="latency", p=0.30, latency=0.001)]),
    ("partial-put", 606,
     [FaultSpec(kind="partial_put", at=1, op="put", key_prefix="data/"),
      FaultSpec(kind="partial_put", at=3, op="put", key_prefix="data/")]),
    ("truncated-read", 707,
     [FaultSpec(kind="truncated_read", at=1, op="get"),
      FaultSpec(kind="truncated_read", at=2, op="get_range"),
      FaultSpec(kind="truncated_read", p=0.20, op="get_range")]),
    ("mixed", 808,
     [FaultSpec(kind="transient", p=0.15),
      FaultSpec(kind="throttle", p=0.10),
      FaultSpec(kind="latency", p=0.15, latency=0.001),
      FaultSpec(kind="truncated_read", p=0.10, op="get_range")]),
]


@pytest.mark.parametrize("name,seed,specs", SCHEDULES,
                         ids=[s[0] for s in SCHEDULES])
def test_chaos_backup_restore(tmp_path, name, seed, specs):
    src = _src_tree(tmp_path)
    fs, faults, top = _chaos_stack(tmp_path / "store", seed, specs)
    Repository.init(fs, chunker=CHUNKER)

    repo = Repository.open(top)
    repo.PACK_TARGET = 64 * 1024  # several packs from a small tree
    # workers=1: serial chunking makes the pack keyspace identical
    # run-to-run, so each schedule's firing pattern is a fixed property
    # of its seed — a soak run is a replay, not a lottery.
    snap, _stats = TreeBackup(repo).run(src)
    assert snap

    # restore THROUGH the chaos stack too — reads retry the same way
    dst = tmp_path / "dst"
    restore_snapshot(Repository.open(top), dst)
    for f in sorted(p.name for p in src.iterdir()):
        assert (dst / f).read_bytes() == (src / f).read_bytes(), f

    assert faults.injected, "schedule never fired — soak tested nothing"
    _assert_consistent_and_restorable(fs, src, tmp_path / "dst2")


class _RecordingFaultStore(FaultStore):
    """FaultStore that also records the full (op, key) arrival trace."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.trace = []

    def _decide(self, op, key):
        self.trace.append((op, key))
        return super()._decide(op, key)


def _drive(store, op, key):
    """Replay one recorded arrival; outcomes don't matter, only that
    the schedule sees the identical (op, key) stream."""
    try:
        if op in ("put", "put_if_absent"):
            getattr(store, op)(key, b"x")
        elif op == "get_range":
            store.get_range(key, 0, 1)
        elif op == "list":
            list(store.list(key))
        else:
            getattr(store, op)(key)
    except Exception:  # noqa: BLE001 — injected/NoSuchKey, by design
        pass


def test_chaos_same_seed_same_fault_sequence(tmp_path):
    """Determinism: same seed + same op/key arrival stream => the
    identical fault sequence. A real backup+restore's arrival trace is
    recorded, then replayed through a second FaultStore built from the
    same seed over a different (empty, in-memory) backing store — every
    injection must reproduce exactly, including arrival indices.
    (Whole-workload key streams can't repeat across runs: pack ids hash
    encrypted bytes under a per-init random salt.)"""
    from volsync_tpu.objstore.store import MemObjectStore

    src = _src_tree(tmp_path)
    fs = FsObjectStore(str(tmp_path / "store"))
    specs = [FaultSpec(kind="transient", p=0.20),
             FaultSpec(kind="throttle", p=0.05, op="put")]
    faults = _RecordingFaultStore(fs, FaultSchedule(seed=909, specs=specs))
    policy = RetryPolicy(site="chaos", max_attempts=10, base_delay=0.001,
                         max_delay=0.01, sleep_fn=lambda s: None)
    top = ResilientStore(faults, policy=policy,
                         breaker=CircuitBreaker("chaos-det", threshold=10**9,
                                                reset_seconds=0.01))
    Repository.init(fs, chunker=CHUNKER)
    repo = Repository.open(top)
    repo.PACK_TARGET = 64 * 1024
    TreeBackup(repo).run(src)
    restore_snapshot(Repository.open(top), tmp_path / "dst")
    assert faults.injected, "schedule never fired — replay proves nothing"

    replay = FaultStore(MemObjectStore(),
                        FaultSchedule(seed=909, specs=specs))
    for op, key in faults.trace:
        _drive(replay, op, key)
    assert replay.injected == faults.injected


def test_chaos_concurrent_backups_share_one_repository(tmp_path):
    """Two movers, one repository: concurrent TreeBackup runs over the
    same chaos stack and the same Repository object (shared repo lock,
    sharded-index concurrent writers). Both snapshots must land, each
    restores byte-identically to its own source tree, and no index
    entry may reference a missing pack. Run under static_check.sh this
    executes with the lock-order detector armed."""
    rng = np.random.RandomState(9)
    trees = []
    for t in range(2):
        src = tmp_path / f"src{t}"
        src.mkdir()
        for i in range(3):
            (src / f"f{i}.bin").write_bytes(
                rng.bytes(100_000 + 17 * i + t))
        trees.append(src)
    # p-only schedules can legitimately roll ZERO hits on a run this
    # short (pack keys are salted per init, so rolls differ per run);
    # the at=3 spec fires deterministically so the "schedule never
    # fired" assert below cannot flake.
    fs, faults, top = _chaos_stack(tmp_path / "store", 111,
                                   [FaultSpec(kind="transient", p=0.10),
                                    FaultSpec(kind="transient", at=3)])
    Repository.init(fs, chunker=CHUNKER)
    repo = Repository.open(top)
    repo.PACK_TARGET = 64 * 1024
    results: list = [None, None]
    errors: list = []

    def worker(t):
        try:
            snap, _ = TreeBackup(repo).run(
                trees[t], hostname=f"host{t}")
            results[t] = snap
        except Exception as e:  # surfaced via the errors assert below
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(t,),
                                name=f"chaos-backup-{t}")
               for t in range(2)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert not errors, errors
    assert results[0] and results[1] and results[0] != results[1]
    assert faults.injected, "schedule never fired — soak tested nothing"

    # through the UNFAULTED store: clean check, both snapshots present,
    # each restores byte-identically (selected by list position)
    check = Repository.open(fs)
    assert check.check(read_data=True) == []
    ids = [s[0] for s in check.list_snapshots()]
    assert set(results) <= set(ids)
    for t in range(2):
        dst = tmp_path / f"dst{t}"
        prev = len(ids) - 1 - ids.index(results[t])
        restore_snapshot(Repository.open(fs), dst, previous=prev)
        for f in sorted(p.name for p in trees[t].iterdir()):
            assert (dst / f).read_bytes() == (trees[t] / f).read_bytes(), f
    with check._lock:
        packs = [p for p in check._index.live_packs() if p]
    for p in packs:
        assert fs.exists(f"data/{p[:2]}/{p}"), \
            f"dangling index entry -> {p}"


def test_chaos_crash_midupload_then_recover(tmp_path):
    """Crash at the Nth data-pack upload: the backup dies (fatal, not
    retried), and the restarted 'pod' — a fresh open over the healthy
    store — sees a consistent repository and fully restores."""
    src = _src_tree(tmp_path)
    fs, faults, top = _chaos_stack(
        tmp_path / "store", 42,
        [FaultSpec(kind="crash", at=2, op="put", key_prefix="data/")])
    Repository.init(fs, chunker=CHUNKER)

    repo = Repository.open(top)
    repo.PACK_TARGET = 64 * 1024
    # the pipelined uploader may wrap the crash in UploadError — match
    # on the injected-crash message rather than the concrete type
    with pytest.raises(Exception, match="injected crash|store is dead"):
        TreeBackup(repo).run(src)
    assert faults.crashed

    fresh = Repository.open(fs)
    assert fresh.list_snapshots() == []
    assert fresh.check(read_data=True) == []
    snap, _ = TreeBackup(fresh).run(src)
    assert snap
    _assert_consistent_and_restorable(fs, src, tmp_path / "dst")


# -- multi-writer soak: fenced writers + concurrent two-phase prune --------


def _age_locks(fs, *, seconds: float) -> int:
    """Rewrite every lock object's refresh stamp ``seconds`` into the
    past — the store-side fingerprint of holders that crashed a while
    ago (same trick as tests/test_crash_recovery.py)."""
    stamped = 0
    when = (datetime.now(timezone.utc)
            - timedelta(seconds=seconds)).isoformat()
    for key in list(fs.list("locks/")):
        info = json.loads(fs.get(key))
        info["time"] = when
        fs.put(key, json.dumps(info).encode())
        stamped += 1
    return stamped


def _writer_tree(tmp_path, t):
    rng = np.random.RandomState(40 + t)
    src = tmp_path / f"w{t}"
    src.mkdir()
    for i in range(3):
        (src / f"f{i}.bin").write_bytes(rng.bytes(90_000 + 13 * i + 7 * t))
    return src


def _seed_garbage(fs, tmp_path):
    """One kept snapshot plus dead blobs (a deleted snapshot's unique
    chunks), so the concurrent pruner has partially-live packs to
    rewrite and victims to mark."""
    pre = tmp_path / "pre"
    pre.mkdir()
    rng = np.random.RandomState(77)
    for i in range(4):
        (pre / f"g{i}.bin").write_bytes(rng.bytes(150_000 + 11 * i))
    repo = Repository.open(fs)
    repo.PACK_TARGET = 64 * 1024
    doomed, _ = TreeBackup(repo).run(pre)
    for i in range(2):  # rewrite HALF the files: packs go partially live
        (pre / f"g{i}.bin").write_bytes(rng.bytes(150_000 + 11 * i))
    kept, _ = TreeBackup(repo).run(pre)
    repo.delete_snapshot(doomed)
    return pre, kept


#: Multi-writer soak matrix — every schedule runs 4 concurrent backup
#: writers (each its OWN Repository over its own chaos stack: distinct
#: writer ids, real multi-writer fencing) plus 1 concurrent two-phase
#: pruner over the same backing store. Three spec slots:
#:
#: - ``writer_specs`` — weather on the writers' stores (retries absorb;
#:   the ``at=N`` entries fire deterministically so the "schedule never
#:   fired" assert cannot flake);
#: - ``pruner_specs`` — faults on the CONCURRENT pruner; a ``crash``
#:   kills it mid-protocol like a killed pod, its lingering lock is
#:   aged past the staleness horizon, and a retried prune must take
#:   over (fencing the dead writer) and complete;
#: - ``sweep_specs`` — faults on the LATER sweeping prune (the one that
#:   collects the expired pending-delete manifest).
#:
#: The crash schedules put ``at=1`` on each write boundary the
#: two-phase protocol added on top of the PR 9 matrix (tests/
#: test_crash_recovery.py covers the grace=0 boundaries): the
#: pending-delete manifest put, the consolidated-shard put, the
#: superseded-delta delete, the pack sweep delete, and the manifest
#: sweep delete. ``mw-double-takeover`` pre-ages a zombie peer's lock so
#: all five participants observe it at once — the atomic takeover
#: marker must let exactly ONE win.
MW_SCHEDULES = [
    ("mw-transient", 1101, dict(
        writer_specs=[FaultSpec(kind="transient", p=0.15),
                      FaultSpec(kind="throttle", p=0.05),
                      FaultSpec(kind="transient", at=3)])),
    ("mw-index-partial-put", 1202, dict(
        writer_specs=[FaultSpec(kind="partial_put", at=1, op="put",
                                key_prefix="index/"),
                      FaultSpec(kind="latency", p=0.2, latency=0.001)])),
    ("mw-crash-mark-manifest", 1303, dict(
        pruner_specs=[FaultSpec(kind="crash", at=1, op="put",
                                key_prefix="pending-delete/")])),
    ("mw-crash-consolidate", 1404, dict(
        pruner_specs=[FaultSpec(kind="crash", at=1, op="put",
                                key_prefix="index/")])),
    ("mw-crash-delta-delete", 1505, dict(
        pruner_specs=[FaultSpec(kind="crash", at=1, op="delete",
                                key_prefix="index/")])),
    ("mw-crash-sweep-pack", 1606, dict(
        sweep_specs=[FaultSpec(kind="crash", at=1, op="delete",
                               key_prefix="data/")])),
    ("mw-crash-sweep-manifest", 1707, dict(
        sweep_specs=[FaultSpec(kind="crash", at=1, op="delete",
                               key_prefix="pending-delete/")])),
    ("mw-double-takeover", 1808, dict(
        stale_lock=True,
        writer_specs=[FaultSpec(kind="transient", p=0.10),
                      FaultSpec(kind="transient", at=3)])),
]


@pytest.mark.parametrize("name,seed,cfg", MW_SCHEDULES,
                         ids=[s[0] for s in MW_SCHEDULES])
def test_chaos_multiwriter_prune(tmp_path, monkeypatch, name, seed, cfg):
    """4 concurrent fenced writers + 1 concurrent two-phase pruner under
    a seeded fault/crash schedule. Whatever the schedule does, the end
    state must be: clean ``check(read_data=True)``, every landed
    snapshot restores byte-identically, no index entry references a
    missing pack (no live pack was swept), and a final prune leaves no
    pending-delete debris."""
    from volsync_tpu.metrics import GLOBAL as METRICS

    monkeypatch.setenv("VOLSYNC_LOCK_STALE_S", "5")
    writer_specs = cfg.get("writer_specs", [])
    pruner_specs = cfg.get("pruner_specs", [])
    sweep_specs = cfg.get("sweep_specs", [])
    root = tmp_path / "store"
    fs = FsObjectStore(str(root))
    Repository.init(fs, chunker=CHUNKER)
    pre, kept = _seed_garbage(fs, tmp_path)

    zombie_writer = None
    if cfg.get("stale_lock"):
        zombie = Repository.open(fs)
        zombie._write_lock("shared")
        zombie_writer = zombie.writer_id
        assert _age_locks(fs, seconds=60) >= 1
        takeovers_before = METRICS.repo_takeovers_total._value.get()

    trees = [_writer_tree(tmp_path, t) for t in range(4)]
    stacks = [_chaos_stack(root, seed + t, writer_specs)
              for t in range(4)]
    _p_fs, p_faults, p_top = _chaos_stack(root, seed + 99, pruner_specs)
    barrier = threading.Barrier(5)
    snaps: list = [None] * 4
    errors: list = []
    prune_error: list = []

    def writer(t):
        try:
            repo = Repository.open(stacks[t][2])
            repo.PACK_TARGET = 64 * 1024
            # losers of a takeover race back out and re-poll; give them
            # room instead of the 0-second default
            repo.default_lock_wait = 10.0
            barrier.wait(timeout=60)
            snap, _ = TreeBackup(repo).run(
                trees[t], hostname=f"writer{t}")
            snaps[t] = snap
        except Exception as e:  # surfaced via the errors assert below
            errors.append((t, e))

    def pruner():
        try:
            repo = Repository.open(p_top)
            repo.default_lock_wait = 10.0
            barrier.wait(timeout=60)
            repo.prune(grace_seconds=0.2)
        except Exception as e:  # crash schedules EXPECT this
            prune_error.append(e)

    threads = [threading.Thread(target=writer, args=(t,),
                                name=f"mw-writer-{t}") for t in range(4)]
    threads.append(threading.Thread(target=pruner, name="mw-pruner"))
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert not errors, errors

    if any(s.kind == "crash" for s in pruner_specs):
        # the pruner died mid-protocol; its lock lingers (refresher's
        # delete hit the dead store). Age it, then a retried prune must
        # take over — fencing the dead pruner's writer id — and finish.
        assert prune_error and p_faults.crashed
        assert _age_locks(fs, seconds=60) >= 1
        retry = Repository.open(fs)
        retry.default_lock_wait = 10.0
        retry.prune(grace_seconds=0.2)
        fenced = list(fs.list("fenced/"))
        assert fenced, "takeover of the crashed pruner never fenced it"
    else:
        assert not prune_error, prune_error
    if writer_specs:
        assert all(st[1].injected for st in stacks), \
            "a writer schedule never fired — soak tested nothing"

    if zombie_writer is not None:
        # exactly one participant won the takeover of the pre-aged lock
        assert (METRICS.repo_takeovers_total._value.get()
                == takeovers_before + 1)
        assert fs.exists(f"fenced/{zombie_writer}")
        assert list(fs.list("takeover/")) == []  # marker cleaned up

    # grace expired + every writer lock released -> the sweep gate is
    # open; collect the marked victims (through a faulted stack when
    # the schedule targets the sweep phase)
    time.sleep(0.3)
    if sweep_specs:
        _s_fs, s_faults, s_top = _chaos_stack(root, seed + 7, sweep_specs)
        sweeper = Repository.open(s_top)
        sweeper.default_lock_wait = 10.0
        with pytest.raises(Exception, match="injected crash|store is dead"):
            sweeper.prune(grace_seconds=0.2)
        assert s_faults.crashed
        assert _age_locks(fs, seconds=60) >= 1
    final = Repository.open(fs)
    final.default_lock_wait = 10.0
    final.prune(grace_seconds=0.2)
    assert list(fs.list("pending-delete/")) == [], \
        "retried prune left pending-delete debris"

    # end-to-end contract, through the UNFAULTED store
    check = Repository.open(fs)
    assert check.check(read_data=True) == []
    ids = [s[0] for s in check.list_snapshots()]
    assert all(snaps) and set(snaps) <= set(ids)
    for t in range(4):
        dst = tmp_path / f"dst{t}"
        prev = len(ids) - 1 - ids.index(snaps[t])
        restore_snapshot(Repository.open(fs), dst, previous=prev)
        for f in sorted(p.name for p in trees[t].iterdir()):
            assert (dst / f).read_bytes() == (trees[t] / f).read_bytes(), f
    dstk = tmp_path / "dstk"
    prev = len(ids) - 1 - ids.index(kept)
    restore_snapshot(Repository.open(fs), dstk, previous=prev)
    for f in sorted(p.name for p in pre.iterdir()):
        assert (dstk / f).read_bytes() == (pre / f).read_bytes(), f
    with check._lock:
        packs = [p for p in check._index.live_packs() if p]
    for p in packs:
        assert fs.exists(f"data/{p[:2]}/{p}"), \
            f"index references missing pack {p}"
