"""Mid-backup crash recovery at the repository level.

The reference's movers survive pod kills by Job backoff + restart
(reference: controllers/mover/rsync/mover.go:436-443 delete/recreate at
backoffLimit; mover-restic/entry.sh re-runs ``restic backup`` which
skips already-present blobs). The TPU engine's analogue: a backup
killed between "pack uploaded" and "index/snapshot written" must leave
the repository consistent (orphan packs are invisible to the index),
the retried backup must produce a fully restorable snapshot, and prune
must sweep the orphans — the write-ordering contract of
repo/repository.py (pack -> index -> snapshot).
"""

import numpy as np
import pytest

from volsync_tpu.analysis import lockcheck
from volsync_tpu.engine import TreeBackup, restore_snapshot
from volsync_tpu.objstore.faultstore import (
    FaultSchedule,
    FaultSpec,
    FaultStore,
)
from volsync_tpu.objstore.store import FsObjectStore
from volsync_tpu.repo.repository import Repository


@pytest.fixture(autouse=True)
def _lockcheck_armed(monkeypatch):
    """Crash-recovery paths (retried backups, prune sweeps) run with
    the lock-order/race detector on — see tests/test_lockcheck.py."""
    monkeypatch.setenv("VOLSYNC_TPU_LOCKCHECK", "1")
    lockcheck.reset()
    yield
    assert lockcheck.violations() == []


class DyingStore:
    """FsObjectStore wrapper simulating a mover pod killed around a
    data-pack upload: packs up to ``die_after_packs`` are dropped
    before the write (killed mid-flight); the next one LANDS and then
    the process "dies" (killed after the upload, before the index
    commit) — leaving a real orphan object behind."""

    def __init__(self, inner, die_after_packs: int):
        self._inner = inner
        self._packs = 0
        self._die_after = die_after_packs
        self.dead = False

    def put(self, key: str, data: bytes) -> None:
        if key.startswith("data/"):
            self._packs += 1
            if self._packs > self._die_after:
                self.dead = True
                self._inner.put(key, data)  # the upload itself landed
                raise IOError("simulated mover crash mid-upload")
            return  # killed mid-flight: the bytes never reached the store
        self._inner.put(key, data)

    def __getattr__(self, name):
        return getattr(self._inner, name)


CHUNKER = {"min_size": 4096, "avg_size": 32768, "max_size": 65536,
           "seed": 7, "align": 4096}


@pytest.fixture
def src_tree(tmp_path):
    rng = np.random.RandomState(3)
    src = tmp_path / "src"
    src.mkdir()
    for i in range(5):
        (src / f"f{i}.bin").write_bytes(rng.bytes(300_000 + 17 * i))
    (src / "empty").write_bytes(b"")
    return src


@pytest.mark.slow
def test_backup_crash_then_retry_restores(tmp_path, src_tree):
    root = tmp_path / "store"
    fs = FsObjectStore(str(root))
    Repository.init(fs, chunker=CHUNKER)

    # First attempt dies after one pack reaches the store.
    dying = DyingStore(fs, die_after_packs=0)
    repo_a = Repository.open(dying)
    with pytest.raises(Exception, match="simulated mover crash"):
        TreeBackup(repo_a).run(src_tree)
    assert dying.dead

    # A FRESH open (the restarted mover pod) sees a consistent repo:
    # no snapshots, structural check clean (orphan packs are invisible
    # to the index by write ordering).
    repo_b = Repository.open(fs)
    assert repo_b.list_snapshots() == []
    assert repo_b.check(read_data=True) == []

    # The retried backup completes and restores bit-exactly.
    snap, _stats = TreeBackup(repo_b).run(src_tree)
    dst = tmp_path / "dst"
    repo_c = Repository.open(fs)
    restore_snapshot(repo_c, dst)
    for f in sorted(p.name for p in src_tree.iterdir()):
        assert (dst / f).read_bytes() == (src_tree / f).read_bytes(), f
    assert repo_c.check(read_data=True) == []


def test_pipelined_crash_before_flush_no_dangling_index(tmp_path, src_tree):
    """A pipelined backup abandoned before flush() (pod killed) may have
    uploaded packs, but no index delta or snapshot referencing them can
    exist — orphan packs stay invisible, exactly like the serial path."""
    root = tmp_path / "store"
    fs = FsObjectStore(str(root))
    Repository.init(fs, chunker=CHUNKER)

    repo = Repository.open(fs)
    repo.pipelined = True  # the scenario under test, whatever the env says
    repo.PACK_TARGET = 64 * 1024
    rng = np.random.RandomState(9)
    from volsync_tpu.repo import blobid
    for _ in range(20):
        data = rng.bytes(30_000)
        repo.add_blob("data", blobid.blob_id(data), data)
    # simulate the crash: join in-flight uploads (the pod's sockets may
    # well have completed) but never call flush() — no index persist
    with repo._lock:
        futs = [pk.fut for pk in repo._pl_inflight]
    for f in futs:
        f.result()

    assert list(fs.list("index/")) == []
    assert list(fs.list("snapshots/")) == []
    # the restarted pod opens a consistent, empty-looking repo
    fresh = Repository.open(fs)
    assert fresh.list_snapshots() == []
    assert fresh.check(read_data=True) == []
    # and a clean retry fully restores
    snap, _ = TreeBackup(fresh).run(src_tree)
    dst = tmp_path / "dst"
    restore_snapshot(Repository.open(fs), dst)
    for f in sorted(p.name for p in src_tree.iterdir()):
        assert (dst / f).read_bytes() == (src_tree / f).read_bytes(), f


def test_pipelined_upload_failure_surfaces_on_flush(tmp_path, src_tree):
    """The async upload stage must not swallow store failures: a dying
    store surfaces as an exception at or before flush(), and the index
    never points at the packs that were dropped mid-flight."""
    root = tmp_path / "store"
    fs = FsObjectStore(str(root))
    Repository.init(fs, chunker=CHUNKER)

    dying = DyingStore(fs, die_after_packs=1)
    repo = Repository.open(dying)
    repo.pipelined = True
    repo.PACK_TARGET = 64 * 1024
    rng = np.random.RandomState(10)
    from volsync_tpu.repo import blobid
    with pytest.raises(Exception, match="simulated mover crash"):
        for _ in range(30):
            data = rng.bytes(30_000)
            repo.add_blob("data", blobid.blob_id(data), data)
        repo.flush()
    assert dying.dead
    assert list(fs.list("index/")) == []
    assert Repository.open(fs).check(read_data=True) == []


def test_prune_sweeps_crash_orphans(tmp_path, src_tree):
    root = tmp_path / "store"
    fs = FsObjectStore(str(root))
    Repository.init(fs, chunker=CHUNKER)

    dying = DyingStore(fs, die_after_packs=0)
    with pytest.raises(Exception, match="simulated mover crash"):
        TreeBackup(Repository.open(dying)).run(src_tree)

    orphan_packs = set(fs.list("data/"))
    assert orphan_packs, "the crash left at least one orphan pack"

    repo = Repository.open(fs)
    snap, _ = TreeBackup(repo).run(src_tree)
    before = set(fs.list("data/"))

    repo2 = Repository.open(fs)
    repo2.prune(grace_seconds=0)  # stop-the-world: sweep in this call
    after = set(fs.list("data/"))

    repo3 = Repository.open(fs)
    assert repo3.check(read_data=True) == []
    dst = tmp_path / "dst2"
    restore_snapshot(repo3, dst)
    for f in sorted(p.name for p in src_tree.iterdir()):
        assert (dst / f).read_bytes() == (src_tree / f).read_bytes(), f
    # prune never grows the store...
    assert after <= before
    # ...and it ACTUALLY swept the crash orphans: any orphan key still
    # present must be one the retry legitimately re-referenced in the
    # index (content-addressed reuse); unreferenced orphans are gone.
    with repo3._lock:
        referenced = {f"data/{p[:2]}/{p}"
                      for p in repo3._index.live_packs() if p}
    leftover_orphans = (orphan_packs & after) - referenced
    assert not leftover_orphans, leftover_orphans


@pytest.mark.parametrize("prefix,at", [
    ("data/", 2),    # killed at the 2nd pack upload (1st landed)
    ("index/", 1),   # killed at the index persist (all packs landed)
    ("locks/", 1),   # killed stamping the repository lock (no writes)
], ids=["pack-upload", "index-persist", "lock-stamp"])
def test_injected_crash_at_op_n_recovers(tmp_path, src_tree, prefix, at):
    """Seeded crash-at-op-N (objstore/faultstore.py) across the three
    write stages of a backup. InjectedCrash is classified fatal and
    STICKY — in-flight upload-pool threads cannot quietly finish work
    the dead process started — and a fresh open over the healthy store
    must see a consistent repository whose retried backup restores
    bit-exactly. Runs with the lock-order detector armed (autouse)."""
    root = tmp_path / "store"
    fs = FsObjectStore(str(root))
    Repository.init(fs, chunker=CHUNKER)

    faults = FaultStore(fs, FaultSchedule(seed=1, specs=[
        FaultSpec(kind="crash", at=at, op="put", key_prefix=prefix)]))
    repo = Repository.open(faults)
    repo.PACK_TARGET = 64 * 1024  # several packs from the tree
    # the pipelined uploader may wrap the crash in UploadError
    with pytest.raises(Exception, match="injected crash|store is dead"):
        TreeBackup(repo).run(src_tree)
    assert faults.crashed

    # the restarted mover pod: fresh open over the healthy store
    fresh = Repository.open(fs)
    assert fresh.list_snapshots() == []
    assert fresh.check(read_data=True) == []
    # no index entry may reference a missing pack
    with fresh._lock:
        packs = [p for p in fresh._index.live_packs() if p]
    for p in packs:
        assert fs.exists(f"data/{p[:2]}/{p}"), p

    snap, _ = TreeBackup(fresh).run(src_tree)
    assert snap
    dst = tmp_path / "dst"
    restore_snapshot(Repository.open(fs), dst)
    for f in sorted(p.name for p in src_tree.iterdir()):
        assert (dst / f).read_bytes() == (src_tree / f).read_bytes(), f


def _backdate_locks(fs, *, seconds: float) -> int:
    """Rewrite every lock object's timestamp ``seconds`` into the past —
    the store-side fingerprint of a holder that crashed a while ago."""
    import json
    from datetime import datetime, timedelta, timezone

    stamped = 0
    when = (datetime.now(timezone.utc)
            - timedelta(seconds=seconds)).isoformat()
    for key in list(fs.list("locks/")):
        info = json.loads(fs.get(key))
        info["time"] = when
        fs.put(key, json.dumps(info).encode())
        stamped += 1
    return stamped


@pytest.mark.parametrize("op,prefix", [
    ("put", "index/"),     # step 2: consolidated-index shard write
    ("delete", "index/"),  # step 3: superseded delta delete
    ("delete", "data/"),   # step 4: pack sweep
], ids=["consolidated-index", "delta-delete", "pack-sweep"])
def test_prune_crash_between_steps_keeps_snapshots_restorable(
        tmp_path, src_tree, monkeypatch, op, prefix):
    """Crash injected between each pair of prune's ordered steps
    (rewrite+flush -> consolidated index -> delta delete -> pack
    sweep): after every crash point, a fresh open must pass a full
    read_data check, restore the surviving snapshot byte-identically,
    and complete a retried prune — data is never deleted before its
    replacement is durable.

    The crashed holder leaves its EXCLUSIVE lock in the store (the
    refresher's delete hits the dead store); recovery shortens
    VOLSYNC_LOCK_STALE_S so a minute-old lock is treated as crashed
    instead of stalling the restore behind the 30-minute default —
    the operator knob repo/repository.py reads per instance."""
    monkeypatch.setenv("VOLSYNC_LOCK_STALE_S", "5")
    root = tmp_path / "store"
    fs = FsObjectStore(str(root))
    Repository.init(fs, chunker=CHUNKER)

    repo = Repository.open(fs)
    repo.PACK_TARGET = 64 * 1024
    snap1, _ = TreeBackup(repo).run(src_tree)
    # rewrite one file wholesale: its old chunks become dead the moment
    # snap1 is forgotten, making several packs partially live
    rng = np.random.RandomState(11)
    (src_tree / "f2.bin").write_bytes(rng.bytes(280_000))
    snap2, _ = TreeBackup(repo).run(src_tree)
    assert snap1 and snap2 and snap1 != snap2
    expect = {p.name: p.read_bytes() for p in src_tree.iterdir()}
    repo.delete_snapshot(snap1)

    faults = FaultStore(fs, FaultSchedule(seed=1, specs=[
        FaultSpec(kind="crash", at=1, op=op, key_prefix=prefix)]))
    pruning = Repository.open(faults)
    pruning.PACK_TARGET = 64 * 1024
    with pytest.raises(Exception, match="injected crash|store is dead"):
        pruning.prune(grace_seconds=0)
    assert faults.crashed
    # every crash point sits past at least one op of its kind: the
    # injection actually fired inside prune, not before it
    assert any(kind == "crash" and iop == op and key.startswith(prefix)
               for (_, iop, key, kind) in faults.injected)

    # the dead holder's exclusive lock is still there; age it past the
    # shortened staleness horizon
    assert _backdate_locks(fs, seconds=60) >= 1

    fresh = Repository.open(fs)
    assert fresh.LOCK_STALE_SECONDS == 5.0  # VOLSYNC_LOCK_STALE_S
    assert fresh.check(read_data=True) == []
    dst = tmp_path / "dst"
    restore_snapshot(fresh, dst)
    for name, data in expect.items():
        assert (dst / name).read_bytes() == data, name

    # the retried prune completes over the half-pruned store...
    retry = Repository.open(fs)
    retry.PACK_TARGET = 64 * 1024
    retry.prune(grace_seconds=0)
    # ...and the snapshot STILL restores byte-identically
    final = Repository.open(fs)
    assert final.check(read_data=True) == []
    dst2 = tmp_path / "dst2"
    restore_snapshot(final, dst2)
    for name, data in expect.items():
        assert (dst2 / name).read_bytes() == data, name


@pytest.mark.parametrize("phase,op,prefix", [
    ("mark", "put", "pending-delete/"),
    ("sweep", "delete", "pending-delete/"),
], ids=["mark-manifest", "sweep-manifest"])
def test_two_phase_prune_crash_at_manifest_boundaries(
        tmp_path, src_tree, monkeypatch, phase, op, prefix):
    """The two write boundaries the two-phase protocol ADDS on top of
    the classic prune ordering: the pending-delete manifest put (mark)
    and the manifest delete that retires a completed sweep. A crash at
    either must leave the store fully checkable and restorable, and a
    retried prune must converge to an empty pending-delete/ namespace —
    a manifest is never the only record standing between live data and
    deletion, in either direction."""
    import time

    monkeypatch.setenv("VOLSYNC_LOCK_STALE_S", "5")
    root = tmp_path / "store"
    fs = FsObjectStore(str(root))
    Repository.init(fs, chunker=CHUNKER)

    repo = Repository.open(fs)
    repo.PACK_TARGET = 64 * 1024
    snap1, _ = TreeBackup(repo).run(src_tree)
    rng = np.random.RandomState(11)
    (src_tree / "f2.bin").write_bytes(rng.bytes(280_000))
    snap2, _ = TreeBackup(repo).run(src_tree)
    assert snap1 and snap2 and snap1 != snap2
    expect = {p.name: p.read_bytes() for p in src_tree.iterdir()}
    repo.delete_snapshot(snap1)

    if phase == "sweep":
        # mark cleanly first; the fault fires in the later sweep pass
        marker = Repository.open(fs)
        marker.PACK_TARGET = 64 * 1024
        stats = marker.prune(grace_seconds=0.2)
        assert stats["packs_pending"] > 0
        assert list(fs.list("pending-delete/"))
        time.sleep(0.3)  # let the grace deadline pass

    faults = FaultStore(fs, FaultSchedule(seed=1, specs=[
        FaultSpec(kind="crash", at=1, op=op, key_prefix=prefix)]))
    pruning = Repository.open(faults)
    pruning.PACK_TARGET = 64 * 1024
    with pytest.raises(Exception, match="injected crash|store is dead"):
        pruning.prune(grace_seconds=0.2)
    assert faults.crashed
    assert any(kind == "crash" and iop == op and key.startswith(prefix)
               for (_, iop, key, kind) in faults.injected)

    # the dead pruner's lock survives it; age it past the horizon
    assert _backdate_locks(fs, seconds=60) >= 1

    # crash-at-mark leaves no manifest (the put never landed);
    # crash-at-retire leaves one pointing at already-swept packs —
    # both must read as a healthy repository
    fresh = Repository.open(fs)
    assert fresh.check(read_data=True) == []
    dst = tmp_path / "dst"
    restore_snapshot(fresh, dst)
    for name, data in expect.items():
        assert (dst / name).read_bytes() == data, name

    # the retried prune re-marks (or retires the leftover manifest),
    # and once the grace deadline passes a final pass sweeps everything
    retry = Repository.open(fs)
    retry.PACK_TARGET = 64 * 1024
    retry.prune(grace_seconds=0.2)
    time.sleep(0.3)
    Repository.open(fs).prune(grace_seconds=0.2)
    assert list(fs.list("pending-delete/")) == []

    final = Repository.open(fs)
    assert final.check(read_data=True) == []
    dst2 = tmp_path / "dst2"
    restore_snapshot(final, dst2)
    for name, data in expect.items():
        assert (dst2 / name).read_bytes() == data, name


def test_stale_lock_horizon_and_age_gauge(tmp_path, src_tree, monkeypatch):
    """The two halves of the lock-staleness knob: a conflicting lock
    YOUNGER than VOLSYNC_LOCK_STALE_S blocks acquisition and publishes
    its age on the volsync_repo_lock_age_seconds gauge; once past the
    horizon it is swept as a crashed holder and acquisition proceeds."""
    from volsync_tpu.metrics import GLOBAL as M
    from volsync_tpu.repo.repository import RepoLockedError

    monkeypatch.setenv("VOLSYNC_LOCK_STALE_S", "30")
    root = tmp_path / "store"
    fs = FsObjectStore(str(root))
    Repository.init(fs, chunker=CHUNKER)
    repo = Repository.open(fs)
    assert repo.LOCK_STALE_SECONDS == 30.0

    # a fresh foreign exclusive lock: young -> conflict + gauge
    blocker = Repository.open(fs)
    lock_cm = blocker.lock(exclusive=True)
    lock_cm.__enter__()
    try:
        M.repo_lock_age.set(-1.0)
        with pytest.raises(RepoLockedError):
            with repo.lock(exclusive=False, wait_seconds=0.0):
                pass
        age = M.repo_lock_age._value.get()
        assert 0.0 <= age <= 30.0
    finally:
        lock_cm.__exit__(None, None, None)

    # a crashed holder's lock, aged past the horizon -> swept
    orphan = blocker._write_lock(True)
    assert _backdate_locks(fs, seconds=60) >= 1
    with repo.lock(exclusive=False, wait_seconds=0.0):
        pass  # acquired: the stale exclusive lock was removed
    assert not fs.exists(orphan)
