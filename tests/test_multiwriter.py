"""Multi-writer protocol unit tests (docs/robustness.md): writer
generations + fencing, the atomic stale-lock takeover, read-snapshot
index reloads racing concurrent delta publishes, backup/prune
interleaving, and the ``repair`` recovery verb.

tests/test_chaos.py drives the same protocol end-to-end under seeded
fault schedules; this file pins each mechanism in isolation so a
regression names the broken piece instead of a soak failure.
"""

import glob
import json
import os
import threading
from datetime import datetime, timedelta, timezone

import numpy as np
import pytest

from volsync_tpu.engine import TreeBackup, restore_snapshot
from volsync_tpu.metrics import GLOBAL as METRICS
from volsync_tpu.objstore import FsObjectStore, MemObjectStore
from volsync_tpu.repo import blobid
from volsync_tpu.repo.repository import (
    RepoLockedError,
    Repository,
    StaleWriterError,
    _IndexReloadRace,
)
from volsync_tpu.analysis import lockcheck

CHUNKER = {"min_size": 4096, "avg_size": 32768, "max_size": 65536,
           "seed": 7, "align": 4096}


@pytest.fixture(autouse=True)
def _lockcheck_armed(monkeypatch):
    """Multi-writer paths run with the lock-order/race detector on —
    see tests/test_lockcheck.py."""
    monkeypatch.setenv("VOLSYNC_TPU_LOCKCHECK", "1")
    lockcheck.reset()
    yield
    assert lockcheck.violations() == []


def _write_tree(tmp_path, name, seed, files=3, size=60_000):
    rng = np.random.RandomState(seed)
    src = tmp_path / name
    src.mkdir()
    for i in range(files):
        (src / f"f{i}.bin").write_bytes(rng.bytes(size + 11 * i))
    return src


def _backdate(fs, prefix, *, seconds, field="time"):
    """Rewrite ``field`` of every JSON object under ``prefix`` into the
    past — the store-side fingerprint of a holder/claimant that crashed
    a while ago."""
    when = (datetime.now(timezone.utc)
            - timedelta(seconds=seconds)).isoformat()
    n = 0
    for key in list(fs.list(prefix)):
        info = json.loads(fs.get(key))
        info[field] = when
        fs.put(key, json.dumps(info).encode())
        n += 1
    return n


# -- writer identity / generations -----------------------------------------


def test_open_mints_writer_identity(tmp_path):
    fs = FsObjectStore(str(tmp_path / "store"))
    Repository.init(fs, chunker=CHUNKER)
    a = Repository.open(fs)
    b = Repository.open(fs)
    assert a.writer_id != b.writer_id
    assert b.generation > a.generation > 0
    # stamps are durable: a third open observes the newest generation
    assert Repository.open(fs).generation > b.generation


# -- stale-lock takeover: atomicity + double-takeover regression -----------


def test_takeover_single_winner_under_concurrency(tmp_path):
    """The double-takeover race: N observers of one stale lock race
    ``_take_over_stale_lock``; the atomic put_if_absent marker must let
    exactly ONE win, the losers must NOT delete the lock themselves,
    and the victim writer ends up fenced exactly once."""
    fs = FsObjectStore(str(tmp_path / "store"))
    Repository.init(fs, chunker=CHUNKER)
    zombie = Repository.open(fs)
    zombie._write_lock("shared")
    assert _backdate(fs, "locks/", seconds=3600) == 1
    (key,) = list(fs.list("locks/"))
    info = json.loads(fs.get(key))

    before = METRICS.repo_takeovers_total._value.get()
    repos = [Repository.open(fs) for _ in range(4)]
    wins: list = [None] * 4
    barrier = threading.Barrier(4)

    def claim(i):
        barrier.wait(timeout=30)
        wins[i] = repos[i]._take_over_stale_lock(key, info)

    threads = [threading.Thread(target=claim, args=(i,))
               for i in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert sum(wins) == 1, wins
    assert not fs.exists(key)
    assert fs.exists(f"fenced/{zombie.writer_id}")
    assert list(fs.list("takeover/")) == []  # winner cleaned its marker
    assert METRICS.repo_takeovers_total._value.get() == before + 1
    # the fenced zombie's late publishes are refused from here on
    with pytest.raises(StaleWriterError):
        zombie.save_snapshot({"tree": "00" * 32, "hostname": "z",
                              "paths": [], "tags": []})


def test_takeover_defers_to_foreign_claim_until_it_expires(tmp_path):
    """A pre-placed live takeover marker (a peer mid-removal) blocks
    the takeover WITHOUT deleting the lock; once the claim outlives the
    staleness horizon it is expired, and the next poll wins."""
    fs = FsObjectStore(str(tmp_path / "store"))
    Repository.init(fs, chunker=CHUNKER)
    zombie = Repository.open(fs)
    zombie._write_lock("shared")
    _backdate(fs, "locks/", seconds=3600)
    (key,) = list(fs.list("locks/"))
    info = json.loads(fs.get(key))
    lock_id = key.split("/", 1)[1]
    now = datetime.now(timezone.utc).isoformat()
    fs.put(f"takeover/{lock_id}",
           json.dumps({"writer": "deadbeefdeadbeef",
                       "time": now}).encode())

    contender = Repository.open(fs)
    assert contender._take_over_stale_lock(key, info) is False
    assert fs.exists(key), "loser must never delete the lock itself"
    # the claimant crashes: its marker ages past the horizon
    _backdate(fs, "takeover/", seconds=3600)
    assert contender._take_over_stale_lock(key, info) is False
    assert not fs.exists(f"takeover/{lock_id}"), "expired claim removed"
    assert contender._take_over_stale_lock(key, info) is True
    assert not fs.exists(key)


# -- fencing: the zombie's late publish is refused and observable ----------


def test_fenced_writer_late_publish_refused_and_observable(
        tmp_path, monkeypatch):
    """The full split-brain sequence: writer A stalls (its lock goes
    stale), writer B takes over A's lock (fence-first), and A's later
    index/snapshot publishes raise StaleWriterError — counted on
    volsync_repo_fenced_publishes_total and flight-recorded (trigger
    auto-dump), with nothing half-published left in the store."""
    monkeypatch.setenv("VOLSYNC_LOCK_STALE_S", "5")
    monkeypatch.setenv("VOLSYNC_TRACE_DUMP", str(tmp_path / "dumps"))
    monkeypatch.setenv("VOLSYNC_TRACE_TRIGGER_INTERVAL_S", "0")
    fs = FsObjectStore(str(tmp_path / "store"))
    Repository.init(fs, chunker=CHUNKER)

    a = Repository.open(fs)
    before = METRICS.repo_fenced_publishes_total._value.get()
    with a.lock(mode="shared"):
        # A stalls mid-backup: its lock stops refreshing and ages out
        _backdate(fs, "locks/", seconds=60)
        b = Repository.open(fs)
        with b.lock(mode="exclusive"):
            pass  # acquisition took over A's stale lock and fenced A
        assert fs.exists(f"fenced/{a.writer_id}")

        # the zombie wakes up and tries to publish: refused
        data = os.urandom(30_000)
        a.add_blob("data", blobid.blob_id(data), data)
        index_before = sorted(fs.list("index/"))
        with pytest.raises(StaleWriterError):
            a.flush()
        assert sorted(fs.list("index/")) == index_before, \
            "a fenced writer's delta must never become visible"
        with pytest.raises(StaleWriterError):
            a.save_snapshot({"tree": "00" * 32, "hostname": "a",
                             "paths": [], "tags": []})
        assert list(fs.list("snapshots/")) == []

    assert METRICS.repo_fenced_publishes_total._value.get() >= before + 2
    assert glob.glob(str(tmp_path / "dumps" / "trace-repo_takeover-*")), \
        "takeover must trigger a flight-recorder dump"
    assert glob.glob(
        str(tmp_path / "dumps" / "trace-repo_fenced_publish-*")), \
        "the refused publish must trigger a flight-recorder dump"


# -- load_index read-snapshot semantics ------------------------------------


class _TornDelta:
    """Store wrapper serving a truncated body for one index delta's
    first ``n_torn`` reads — the observable state while a concurrent
    writer's PUT is still landing/retrying (FaultStore's partial_put
    leaves exactly this; the writer's retry overwrites it)."""

    def __init__(self, inner, key, n_torn):
        self.inner = inner
        self.key = key
        self.n_torn = n_torn
        self.reads = 0

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def get(self, key):
        data = self.inner.get(key)
        if key == self.key:
            self.reads += 1
            if self.reads <= self.n_torn:
                return data[:max(1, len(data) // 2)]
        return data


def test_load_index_never_surfaces_half_visible_delta(tmp_path):
    """Reload racing a concurrent delta PUT: the reader sees either
    none of the delta or all of it, never half. The torn first read is
    re-fetched within the same pass (the retrying writer has landed the
    full body by then) and every entry becomes visible atomically."""
    mem = MemObjectStore()
    Repository.init(mem, chunker=CHUNKER)
    writer = Repository.open(mem)
    data = os.urandom(40_000)
    bid = blobid.blob_id(data)
    writer.add_blob("data", bid, data)
    writer.flush()
    (delta,) = [k for k in mem.list("index/")]

    store = _TornDelta(mem, delta, 1)
    reader = Repository.open(store)  # open() reloads through the tear
    assert store.reads >= 2, "torn body must be re-fetched, not trusted"
    assert reader.has_blob(bid)
    assert reader.read_blob(bid) == data


def test_load_index_keeps_previous_snapshot_on_persistent_tear(tmp_path):
    """A delta that STAYS undecodable (a genuinely corrupted object,
    not a racing PUT) fails the reload after bounded retries — and the
    reader keeps its previous index snapshot instead of serving a
    half-loaded one."""
    mem = MemObjectStore()
    Repository.init(mem, chunker=CHUNKER)
    writer = Repository.open(mem)
    d0 = os.urandom(30_000)
    writer.add_blob("data", blobid.blob_id(d0), d0)
    writer.flush()
    reader = Repository.open(mem)
    assert reader.has_blob(blobid.blob_id(d0))

    d1 = os.urandom(30_000)
    writer.add_blob("data", blobid.blob_id(d1), d1)
    writer.flush()
    new_delta = [k for k in mem.list("index/")][-1]
    reader.store = _TornDelta(mem, new_delta, 10**9)
    with pytest.raises(_IndexReloadRace):
        reader.load_index()
    # previous read snapshot intact: d0 still served
    assert reader.has_blob(blobid.blob_id(d0))
    assert reader.read_blob(blobid.blob_id(d0)) == d0


# -- prune/backup interleaving ---------------------------------------------


def test_backup_started_mid_prune_completes(tmp_path):
    """Two-phase prune no longer excludes writers: while a prune-mode
    lock is held (mark phase in progress), a shared-mode backup starts
    AND finishes without waiting for the sweep; a second pruner and an
    exclusive acquirer are still refused."""
    fs = FsObjectStore(str(tmp_path / "store"))
    Repository.init(fs, chunker=CHUNKER)
    pruner = Repository.open(fs)
    with pruner.lock(mode="prune"):
        writer = Repository.open(fs)
        writer.PACK_TARGET = 64 * 1024
        snap, _ = TreeBackup(writer).run(
            _write_tree(tmp_path, "src", seed=3))
        assert snap
        rival = Repository.open(fs)
        with pytest.raises(RepoLockedError):
            with rival.lock(mode="prune"):
                pass
        with pytest.raises(RepoLockedError):
            with rival.lock(exclusive=True):
                pass
    assert Repository.open(fs).check(read_data=True) == []


class _PublishAfterListing:
    """A store that, once armed, runs ``publish`` right after the next
    listing of ``index/`` made through it: the listing a pruner's
    ``load_index`` reads from."""

    def __init__(self, inner):
        self._inner, self._publish = inner, None

    def arm(self, publish):
        self._publish = publish

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def list(self, prefix=""):
        keys = list(self._inner.list(prefix))
        if prefix == "index/" and self._publish is not None:
            publish, self._publish = self._publish, None
            publish()
        return iter(keys)


def test_a_delta_published_after_the_load_survives_the_consolidation(
        tmp_path):
    """A writer publishes a pack and its index delta between the
    listing a prune's ``load_index`` reads and the consolidation (its
    snapshot comes later: a backup still under way). The prune never
    read that delta, so it does not supersede it: a second listing at
    that point named it, it was deleted unread, and the snapshot saved
    next pointed at blobs no index held."""
    fs = MemObjectStore()
    Repository.init(fs, chunker=CHUNKER)
    TreeBackup(Repository.open(fs)).run(_write_tree(tmp_path, "a", seed=1))
    writer = Repository.open(fs)
    data = np.random.RandomState(5).bytes(50_000)
    published = []

    def publish():
        before = set(fs.list("index/"))
        bid = blobid.blob_id(data)
        assert writer.add_blob("data", bid, data)
        writer.flush()
        published.append((bid, set(fs.list("index/")) - before))

    store = _PublishAfterListing(fs)
    pruner = Repository.open(store)
    store.arm(publish)
    pruner.prune(grace_seconds=0.2)
    (bid, delta), = published
    assert len(delta) == 1 and not delta & pruner._loaded_deltas
    assert delta <= set(fs.list("index/"))
    # the index still holds the entry and the blob reads back (its pack,
    # which no index object the prune read names, is parked as an
    # orphan: the sweep's gate, a live writer's lock, is another test's)
    fresh = Repository.open(fs)
    assert bid in fresh.blob_ids() and fresh.read_blob(bid) == data
    assert fresh.check(read_data=True) == []


def test_backup_lands_while_victims_await_sweep(tmp_path):
    """After the mark phase (manifest written, grace running), backups
    proceed normally, never dedup into marked packs, and the deferred
    sweep later removes the victims without touching live data."""
    fs = FsObjectStore(str(tmp_path / "store"))
    Repository.init(fs, chunker=CHUNKER)
    seed = Repository.open(fs)
    seed.PACK_TARGET = 64 * 1024
    src = _write_tree(tmp_path, "src", seed=5)
    doomed, _ = TreeBackup(seed).run(src)
    rng = np.random.RandomState(9)
    (src / "f0.bin").write_bytes(rng.bytes(60_000))
    kept, _ = TreeBackup(seed).run(src)
    seed.delete_snapshot(doomed)

    marker = Repository.open(fs)
    report = marker.prune(grace_seconds=3600)
    assert report["packs_pending"] > 0
    assert list(fs.list("pending-delete/"))

    # a backup STARTED mid-grace completes; marked packs are excluded
    # from its dedup so nothing extends a victim's life
    writer = Repository.open(fs)
    writer.PACK_TARGET = 64 * 1024
    snap2, _ = TreeBackup(writer).run(
        _write_tree(tmp_path, "other", seed=6))
    assert snap2
    check = Repository.open(fs)
    assert check.check(read_data=True) == []
    # dead entries stay in marked packs until the sweep (by design),
    # but every REACHABLE blob must already be homed elsewhere — the
    # mark phase rewrote live blobs, and the new backup's dedup treats
    # marked packs as absent instead of extending their life
    reach, broken = check._walk_trees_tolerant()
    assert not broken
    homes = {check._index.lookup(b)[0] for b in reach}
    assert not (homes & check._pending_packs), \
        "a reachable blob may not be homed in a marked pack"

    # deadline passes (backdate the manifest), no live locks: sweep
    _backdate(fs, "pending-delete/", seconds=7200, field="deadline")
    _backdate(fs, "pending-delete/", seconds=7200, field="marked_at")
    swept = Repository.open(fs).prune(grace_seconds=3600)
    assert swept["packs_swept"] > 0
    assert Repository.open(fs).check(read_data=True) == []


class _PruneOnFirstPackGet:
    """Store shim that fires a callback at the FIRST whole-pack GET —
    i.e. after the pipelined restore has planned against the old index
    but before any pack body arrives."""

    def __init__(self, inner, fire):
        self.inner = inner
        self._fire = fire
        self._fired = False
        self.pack_keys: list[str] = []

    def get(self, key):
        if key.startswith("data/"):
            self.pack_keys.append(key)
            if not self._fired:
                self._fired = True
                self._fire()
        return self.inner.get(key)

    def __getattr__(self, name):
        return getattr(self.inner, name)


def test_pipelined_restore_tolerates_concurrent_prune(tmp_path):
    """A pipelined restore whose fetch window overlaps a two-phase
    prune: the plan was made against the pre-prune index, the mark
    phase rewrites live blobs and parks the old packs — and the
    in-flight fetches still read the parked packs (pending-delete
    means *deferred*, not deleted) for a byte-identical restore."""
    fs = FsObjectStore(str(tmp_path / "store"))
    Repository.init(fs, chunker=CHUNKER)
    seed = Repository.open(fs)
    seed.PACK_TARGET = 64 * 1024
    # small files so several share a pack: the doomed file's blobs sit
    # NEXT TO live blobs, forcing the mark phase to rewrite + park the
    # mixed pack (a pure-garbage pack would park without any overlap)
    src = _write_tree(tmp_path, "src", seed=21, files=6, size=15_000)
    doomed, _ = TreeBackup(seed).run(src)
    (src / "f0.bin").unlink()  # first-packed file: shares its pack
    #                            with still-live neighbours
    kept, _ = TreeBackup(seed).run(src)
    seed.delete_snapshot(doomed)  # f0's blobs are now garbage

    report = {}

    def fire():
        # runs inside a restore fetch-pool thread, while the restore
        # holds its shared lock — prune-mode coexists with shared
        report.update(Repository.open(fs).prune(grace_seconds=3600))

    shim = _PruneOnFirstPackGet(fs, fire)
    stats = restore_snapshot(Repository.open(shim), tmp_path / "dst")
    assert stats and stats["files"] == 5

    # the prune really overlapped: it marked packs, and the restore
    # went on to read at least one pack that is now parked
    assert report.get("packs_pending", 0) > 0
    pending = set()
    for key in fs.list("pending-delete/"):
        pending.update(json.loads(fs.get(key))["packs"])
    fetched = {k.rsplit("/", 1)[1] for k in shim.pack_keys}
    assert fetched & pending, \
        "restore never touched a parked pack — the race didn't happen"

    for f in sorted(p.name for p in src.iterdir()):
        assert (tmp_path / "dst" / f).read_bytes() == \
            (src / f).read_bytes(), f
    assert Repository.open(fs).check(read_data=True) == []


# -- repair ----------------------------------------------------------------


def _damaged_repo(tmp_path):
    """A repository with one snapshot, one orphan pack, a stale fenced
    marker, and a pile of superseded generation stamps."""
    fs = FsObjectStore(str(tmp_path / "store"))
    Repository.init(fs, chunker=CHUNKER)
    repo = Repository.open(fs)
    repo.PACK_TARGET = 64 * 1024
    src = _write_tree(tmp_path, "src", seed=11)
    snap, _ = TreeBackup(repo).run(src)
    orphan = "ab" + os.urandom(31).hex()
    fs.put(f"data/{orphan[:2]}/{orphan}", os.urandom(512))
    old = (datetime.now(timezone.utc)
           - timedelta(seconds=7200)).isoformat()
    fs.put("fenced/deadwriter",
           json.dumps({"by": "x", "lock": "y", "time": old}).encode())
    for _ in range(3):
        Repository.open(fs)  # mint extra generation stamps
    return fs, src, snap, orphan


def test_repair_dry_run_reports_without_mutating(tmp_path):
    fs, _src, _snap, orphan = _damaged_repo(tmp_path)
    keys_before = sorted(fs.list(""))
    report = Repository.open(fs).repair(apply=False)
    assert report["applied"] is False
    assert report["orphan_packs"] == [orphan]
    assert "fenced/deadwriter" in report["stale_markers"]
    assert report["gc"] is None
    # a dry run minted its own lock/gen but deleted the lock on exit;
    # everything that existed before must still exist untouched
    after = sorted(fs.list(""))
    assert set(keys_before) - set(after) == set()
    assert fs.exists(f"data/{orphan[:2]}/{orphan}")
    assert fs.exists("fenced/deadwriter")


def test_repair_resolves_orphans_markers_and_generations(tmp_path):
    fs, src, snap, orphan = _damaged_repo(tmp_path)
    report = Repository.open(fs).repair(grace_seconds=0)
    assert report["applied"] is True
    assert report["orphan_packs"] == [orphan]
    assert report["gc"] is not None
    assert not fs.exists(f"data/{orphan[:2]}/{orphan}")
    assert not fs.exists("fenced/deadwriter")
    assert len(list(fs.list("gen/"))) == 1  # superseded stamps trimmed
    fresh = Repository.open(fs)
    assert fresh.check(read_data=True) == []
    dst = tmp_path / "dst"
    restore_snapshot(fresh, dst)
    for f in sorted(p.name for p in src.iterdir()):
        assert (dst / f).read_bytes() == (src / f).read_bytes(), f


def test_repair_drops_unreachable_dangling_entries(tmp_path):
    """An index entry whose pack is gone AND whose blob no snapshot
    references is debris: repair drops it and the repo checks clean."""
    fs = FsObjectStore(str(tmp_path / "store"))
    Repository.init(fs, chunker=CHUNKER)
    repo = Repository.open(fs)
    data = os.urandom(20_000)
    bid = blobid.blob_id(data)
    repo.add_blob("data", bid, data)
    repo.flush()
    pack = repo._index.lookup(bid)[0]
    fs.delete(f"data/{pack[:2]}/{pack}")

    report = Repository.open(fs).repair(grace_seconds=0)
    assert report["dangling_packs"] == [pack]
    assert report["dangling_entries_dropped"] >= 1
    assert report["unrecoverable_blobs"] == []
    fresh = Repository.open(fs)
    assert fresh.check(read_data=True) == []
    assert not fresh.has_blob(bid)


def test_repair_reports_reachable_loss_and_refuses_gc(tmp_path):
    """A missing pack that a snapshot still references is REAL loss:
    repair reports the blobs as unrecoverable, keeps their index
    entries (never deletes a referenced blob's last record), and skips
    the GC pass."""
    fs = FsObjectStore(str(tmp_path / "store"))
    Repository.init(fs, chunker=CHUNKER)
    repo = Repository.open(fs)
    repo.PACK_TARGET = 64 * 1024
    TreeBackup(repo).run(_write_tree(tmp_path, "src", seed=13))
    pack = sorted(p for p in repo._index.live_packs() if p)[0]
    fs.delete(f"data/{pack[:2]}/{pack}")

    report = Repository.open(fs).repair()
    assert report["dangling_packs"] == [pack]
    assert report["unrecoverable_blobs"]
    assert report["dangling_entries_dropped"] == 0
    assert report["gc"] is None


def test_repair_cli_exit_codes_and_json(tmp_path, capsys):
    from volsync_tpu.cli.repair import main as repair_main

    fs, _src, _snap, orphan = _damaged_repo(tmp_path)
    url = f"file://{tmp_path / 'store'}"
    assert repair_main([url, "--dry-run", "--json"]) == 0
    assert repair_main([url, "--grace-seconds", "0"]) == 0
    assert not fs.exists(f"data/{orphan[:2]}/{orphan}")

    # reachable loss -> exit 1
    pack = sorted(p for p in Repository.open(fs)._index.live_packs()
                  if p)[0]
    fs.delete(f"data/{pack[:2]}/{pack}")
    assert repair_main([url]) == 1

    # operational error -> exit 2
    assert repair_main([f"file://{tmp_path / 'nowhere'}"]) == 2


def test_repair_concurrent_with_live_fenced_writers(tmp_path):
    """``volsync repair`` while live fenced writers are mid-backup
    (fleet operations runbook, docs/service.md): the scan must treat
    the live writers' half-published state as in-flight, not debris —
    it never drops an index entry a landed snapshot needs and never
    sweeps a pack owned by a live writer generation. Pre-seeded debris
    (an orphan pack, a stale fenced marker, a stale fleet stamp) is
    still collected in the same pass."""
    fs, pre_src, pre_snap, orphan = _damaged_repo(tmp_path)
    old = (datetime.now(timezone.utc)
           - timedelta(seconds=7200)).isoformat()
    fs.put("fleet/deadreplica", json.dumps(
        {"replica_id": "deadreplica", "address": "h:1", "headroom": 0,
         "backlog": 0, "writer_id": "w", "generation": 1, "seq": 9,
         "time": old}).encode())

    trees = [_write_tree(tmp_path, f"live{t}", seed=21 + t)
             for t in range(2)]
    barrier = threading.Barrier(3)
    snaps: list = [None, None]
    errors: list = []
    report: list = []

    def writer(t):
        try:
            repo = Repository.open(fs)
            repo.PACK_TARGET = 64 * 1024
            repo.default_lock_wait = 10.0
            barrier.wait(timeout=60)
            snap, _ = TreeBackup(repo).run(
                trees[t], hostname=f"live{t}")
            snaps[t] = snap
        except Exception as e:  # surfaced via the errors assert below
            errors.append((t, e))

    def repairer():
        try:
            repo = Repository.open(fs)
            repo.default_lock_wait = 10.0
            barrier.wait(timeout=60)
            report.append(repo.repair(grace_seconds=0.2))
        except Exception as e:
            errors.append(("repair", e))

    threads = [threading.Thread(target=writer, args=(t,),
                                name=f"live-writer-{t}")
               for t in range(2)]
    threads.append(threading.Thread(target=repairer, name="repairer"))
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert not errors, errors
    assert all(snaps)

    # repair collected the pre-seeded debris...
    rep = report[0]
    assert rep["applied"] is True
    assert orphan in rep["orphan_packs"]
    assert "fenced/deadwriter" in rep["stale_markers"]
    assert "fleet/deadreplica" in rep["stale_markers"]
    assert not fs.exists("fleet/deadreplica")
    # ...without ever declaring a live writer's blobs unrecoverable or
    # dropping entries out from under it
    assert rep["unrecoverable_blobs"] == []
    assert rep["broken_trees"] == []

    # live writers were never fenced (only the stale marker's owner)
    assert list(fs.list("fenced/")) == []

    # end state: every snapshot (pre-existing + both landed mid-repair)
    # restores byte-identically, no index entry references a missing
    # pack — no live-generation pack was swept
    check = Repository.open(fs)
    assert check.check(read_data=True) == []
    ids = [s[0] for s in check.list_snapshots()]
    assert set(snaps) | {pre_snap} <= set(ids)
    for src, snap in [(pre_src, pre_snap), (trees[0], snaps[0]),
                      (trees[1], snaps[1])]:
        dst = tmp_path / f"dst-{snap[:8]}"
        prev = len(ids) - 1 - ids.index(snap)
        restore_snapshot(Repository.open(fs), dst, previous=prev)
        for f in sorted(p.name for p in src.iterdir()):
            assert (dst / f).read_bytes() == (src / f).read_bytes(), f
    with check._lock:
        packs = [p for p in check._index.live_packs() if p]
    for p in packs:
        assert fs.exists(f"data/{p[:2]}/{p}"), \
            f"repair swept live pack {p}"
