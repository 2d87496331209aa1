"""Silent-corruption defense (repo/scrub.py + the bitflip fault kind):
`make scrub-smoke` runs the deterministic half, `make chaos-scrub` adds
the seeded bit-rot storms.

The contract under test, end to end:

- ScrubService walks every indexed pack under a shared lock, verifies
  blob batches on-device, quarantines mismatches, heals from the
  mirror copy (``VOLSYNC_PACK_COPIES=2``) verify-then-replace, and
  escalates unhealable packs (quarantine manifest stays, ``volsync
  scrub`` exits 2).
- ``check(read_data=True)`` defaults to the batched device verify and
  flags exactly the blob set the serial golden path flags.
- Under seeded bitflip schedules with LIVE concurrent backup, restore,
  and ContinuousGC traffic, no single-copy corruption ever reaches a
  restored file: every drill ends quarantine-empty, check-clean, and
  byte-identical.
"""

import json
import threading

import numpy as np
import pytest

from volsync_tpu.engine import RestoreGroup, TreeBackup
from volsync_tpu.engine.restore import restore_snapshot
from volsync_tpu.objstore.faultstore import (
    FaultSchedule,
    FaultSpec,
    FaultStore,
)
from volsync_tpu.objstore.store import FsObjectStore, MemObjectStore
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


def _rot_primary(store, pack_id, segs):
    """Durable bit-rot: flip one payload byte of the pack's first blob
    segment in the PRIMARY copy at rest."""
    off, length = sorted(segs)[0]
    key = f"data/{pack_id[:2]}/{pack_id}"
    body = bytearray(store.get(key))
    body[off + min(5, length - 1)] ^= 0xFF
    store.put(key, bytes(body))
    return key


def _assert_identical(src, dst):
    for p in src.rglob("*"):
        rel = p.relative_to(src)
        if p.is_file():
            assert (dst / rel).read_bytes() == p.read_bytes(), rel


# -- ScrubService unit --------------------------------------------------------

def test_scrub_clean_repo_is_clean(tmp_path, monkeypatch):
    monkeypatch.setenv("VOLSYNC_PACK_COPIES", "2")
    mem = MemObjectStore()
    _backup(mem, _src_tree(tmp_path))
    svc = ScrubService(mem)
    assert svc.run_once() == "clean"
    assert svc.corruptions == 0 and svc.healed == 0
    assert svc.packs_scrubbed == len(list(mem.list("data/")))
    assert svc.last_report["bytes"] > 0
    assert list(mem.list("quarantine/")) == []


def test_scrub_heals_corrupt_primary_from_mirror(tmp_path, monkeypatch):
    monkeypatch.setenv("VOLSYNC_PACK_COPIES", "2")
    mem = MemObjectStore()
    src = _src_tree(tmp_path)
    _backup(mem, src)
    segs = _pack_segments(mem)
    victim = sorted(segs)[0]
    _rot_primary(mem, victim, segs[victim])

    svc = ScrubService(mem)
    assert svc.run_once() == "healed"
    assert svc.corruptions == 1 and svc.healed == 1
    # quarantine manifest removed only AFTER the healed primary
    # re-verified through a fresh fetch
    assert list(mem.list("quarantine/")) == []
    assert Repository.open(mem).check(read_data=True) == []
    assert svc.run_once() == "clean"
    # the healed store restores byte-identical
    dst = tmp_path / "dst"
    restore_snapshot(Repository.open(mem), dst)
    _assert_identical(src, dst)


def test_scrub_unhealable_without_mirror_keeps_quarantine(tmp_path):
    # default VOLSYNC_PACK_COPIES=1: no mirrors anywhere
    mem = MemObjectStore()
    _backup(mem, _src_tree(tmp_path))
    assert list(mem.list("mirror/")) == []
    segs = _pack_segments(mem)
    victim = sorted(segs)[0]
    _rot_primary(mem, victim, segs[victim])

    svc = ScrubService(mem)
    assert svc.run_once() == "unhealable"
    assert svc.unhealable == 1
    manifest = json.loads(mem.get(f"quarantine/{victim}"))
    assert manifest["pack"] == victim
    assert len(manifest["blobs"]) >= 1  # the evidence names the blobs
    # the rot is still there next cycle: escalation is not one-shot
    assert svc.run_once() == "unhealable"


def test_scrub_heal_count_matches_injected_corruptions(tmp_path,
                                                       monkeypatch):
    """Exact accounting: K durably rotten packs => K quarantines, K
    heals, one cycle, then a clean repository."""
    monkeypatch.setenv("VOLSYNC_PACK_COPIES", "2")
    mem = MemObjectStore()
    src = _src_tree(tmp_path, files=7)
    _backup(mem, src)
    segs = _pack_segments(mem)
    victims = sorted(segs)[:3]
    assert len(victims) == 3
    for v in victims:
        _rot_primary(mem, v, segs[v])

    svc = ScrubService(mem)
    assert svc.run_once() == "healed"
    assert svc.corruptions == 3 and svc.healed == 3
    assert svc.unhealable == 0
    assert list(mem.list("quarantine/")) == []
    assert Repository.open(mem).check(read_data=True) == []
    dst = tmp_path / "dst"
    restore_snapshot(Repository.open(mem), dst)
    _assert_identical(src, dst)


def test_scrub_backfills_mirrors_enabled_late(tmp_path, monkeypatch):
    """A repository born single-copy turns on VOLSYNC_PACK_COPIES=2:
    the next scrub cycle re-mirrors every verified-clean primary."""
    mem = MemObjectStore()
    _backup(mem, _src_tree(tmp_path))
    assert list(mem.list("mirror/")) == []
    monkeypatch.setenv("VOLSYNC_PACK_COPIES", "2")
    svc = ScrubService(mem)
    assert svc.run_once() == "healed"  # mirrors written count as heals
    packs = sorted(k.rsplit("/", 1)[1] for k in mem.list("data/"))
    assert sorted(mem.list("mirror/")) == [f"mirror/{p}" for p in packs]
    assert svc.run_once() == "clean"  # backfill is idempotent


def test_scrub_packs_per_cycle_round_robin(tmp_path, monkeypatch):
    monkeypatch.setenv("VOLSYNC_PACK_COPIES", "2")
    mem = MemObjectStore()
    _backup(mem, _src_tree(tmp_path))
    npacks = len(list(mem.list("data/")))
    assert npacks > 1
    svc = ScrubService(mem, packs_per_cycle=1)
    for _ in range(npacks):
        assert svc.run_once() == "clean"
        assert svc.last_report["packs"] == 1
    # the cursor visited every pack exactly once across the cycles
    assert svc.packs_scrubbed == npacks


# -- check(read_data) golden: device batch == serial oracle ------------------

def test_check_device_verify_equals_serial_golden(tmp_path):
    mem = MemObjectStore()
    _backup(mem, _src_tree(tmp_path))
    segs = _pack_segments(mem)
    victim = sorted(segs)[0]
    _rot_primary(mem, victim, segs[victim])

    def flagged(problems):
        # both paths format "blob <id>: <why>"; compare the blob SETS,
        # not the message tails (serial reports the decode exception,
        # the device batch reports the hash mismatch)
        return sorted(p.split()[1].rstrip(":") for p in problems
                      if p.startswith("blob "))

    serial = Repository.open(mem).check(read_data=True,
                                        device_verify=False)
    device = Repository.open(mem).check(read_data=True,
                                        device_verify=True)
    assert flagged(serial) == flagged(device) != []
    # the batched device path is the DEFAULT (VOLSYNC_DEVICE_VERIFY on)
    default = Repository.open(mem).check(read_data=True)
    assert flagged(default) == flagged(device)


# -- volsync scrub CLI --------------------------------------------------------

def _cli(argv, lines):
    from volsync_tpu.cli.main import run

    return run(list(argv), {}, out=lines.append)


def test_scrub_cli_exit_codes(tmp_path, monkeypatch):
    monkeypatch.setenv("VOLSYNC_PACK_COPIES", "2")
    root = tmp_path / "store"
    fs = FsObjectStore(str(root))
    _backup(fs, _src_tree(tmp_path))

    lines: list = []
    assert _cli(["scrub", str(root)], lines) == 0  # clean
    assert any("scrub clean" in ln for ln in lines)

    segs = _pack_segments(fs)
    victim = sorted(segs)[0]
    _rot_primary(fs, victim, segs[victim])
    lines.clear()
    assert _cli(["scrub", str(root), "--json"], lines) == 1  # healed
    report = json.loads("\n".join(lines))
    assert report["outcome"] == "healed" and report["healed"] == 1
    assert _cli(["scrub", str(root)], []) == 0  # the heal persisted

    # rot both copies: unhealable, quarantine manifest left behind
    _rot_primary(fs, victim, segs[victim])
    mbody = bytearray(fs.get(f"mirror/{victim}"))
    mbody[0] ^= 0xFF
    fs.put(f"mirror/{victim}", bytes(mbody))
    assert _cli(["scrub", str(root)], []) == 2
    assert fs.exists(f"quarantine/{victim}")


def test_scrub_cli_bad_store_is_operational_error(tmp_path):
    lines: list = []
    assert _cli(["scrub", str(tmp_path / "nowhere")], lines) == 2
    assert any("error:" in ln for ln in lines)


# -- chaos: seeded bit-rot storms under live traffic -------------------------

def _chaos_stack(root, seed, specs):
    """ResilientStore(FaultStore(FsObjectStore)) — the open_store()
    layering, with the schedule's bitflips hitting pack GETs on the
    wire (post-store, pre-retry: exactly where bit-rot lives)."""
    faults = FaultStore(FsObjectStore(str(root)),
                        FaultSchedule(seed=seed, specs=list(specs)))
    policy = RetryPolicy(site="scrub-chaos", max_attempts=12,
                         base_delay=0.005, max_delay=0.02)
    top = ResilientStore(faults, policy=policy,
                         breaker=CircuitBreaker("scrub-chaos",
                                                threshold=10**9,
                                                reset_seconds=0.01))
    return faults, top


def _converge(svc, tries=10):
    """Finite at=N schedules guarantee convergence: scrub until a full
    cycle reports every pack clean."""
    for _ in range(tries):
        if svc.run_once() == "clean":
            return
    pytest.fail("scrub never converged to a clean cycle")


#: Bit-rot weather. Every schedule uses finite ``at=N`` flips on pack
#: GETs (prefix=data/ — mirrors stay healthy, the single-copy-corruption
#: invariant the drill proves), optionally under loud retryable noise.
SCHEDULES = [
    ("single-flip", 4101,
     [FaultSpec(kind="bitflip", at=1, op="get", key_prefix="data/")]),
    ("multi-flip", 4202,
     [FaultSpec(kind="bitflip", at=1, op="get", key_prefix="data/",
                nbytes=4),
      FaultSpec(kind="bitflip", at=3, op="get", key_prefix="data/")]),
    ("flip-under-weather", 4303,
     [FaultSpec(kind="bitflip", at=2, op="get", key_prefix="data/"),
      FaultSpec(kind="transient", p=0.10)]),
]


@pytest.mark.parametrize("name,seed,specs", SCHEDULES,
                         ids=[s[0] for s in SCHEDULES])
def test_scrub_chaos_bitflip_storm(tmp_path, monkeypatch, name, seed,
                                   specs):
    """Wire bitflips during a restore storm with the scrub service
    live: corrupted payloads are healed (read-repair or scrub — whoever
    gets there first), every restore is byte-identical, and the drill
    ends quarantine-empty and check-clean."""
    monkeypatch.setenv("VOLSYNC_PACK_COPIES", "2")
    src = _src_tree(tmp_path)
    root = tmp_path / "store"
    _backup(FsObjectStore(str(root)), src)
    faults, top = _chaos_stack(root, seed, specs)

    svc = ScrubService(top, interval_seconds=0.02)
    with svc:
        group = RestoreGroup()
        dests = [tmp_path / f"dst{i}" for i in range(3)]
        for d in dests:
            group.add(Repository.open(top), d)
        results = group.run()
    assert all(r is not None and r["files"] == 6 for r in results)
    for d in dests:
        _assert_identical(src, d)
    # the schedule really fired: corrupted payloads reached callers...
    assert any(kind == "bitflip" for (_, _, _, kind) in faults.injected)
    _converge(svc)
    # ...and none of it survived anywhere that matters
    fs = FsObjectStore(str(root))
    assert list(fs.list("quarantine/")) == []
    assert Repository.open(fs).check(read_data=True) == []


def test_scrub_chaos_durable_rot_under_live_traffic(tmp_path,
                                                    monkeypatch):
    """Durable at-rest rot with EVERYTHING running at once — a second
    backup writing new packs, a restore storm reading, ContinuousGC
    pruning, the scrub healing. End state: all primaries byte-perfect,
    quarantine empty, check clean, restores byte-identical."""
    monkeypatch.setenv("VOLSYNC_PACK_COPIES", "2")
    src = _src_tree(tmp_path)
    root = tmp_path / "store"
    fs = FsObjectStore(str(root))
    _backup(fs, src)
    segs = _pack_segments(fs)
    victims = sorted(segs)[:2]
    for v in victims:
        _rot_primary(fs, v, segs[v])

    # live traffic: a second snapshot's backup runs while the storm +
    # scrub + GC are all active
    src2 = _src_tree(tmp_path / "more", seed=23, files=3)

    def backup_more():
        repo = Repository.open(FsObjectStore(str(root)))
        repo.PACK_TARGET = 64 * 1024
        TreeBackup(repo).run(src2)

    svc = ScrubService(fs, interval_seconds=0.02)
    gc = ContinuousGC(FsObjectStore(str(root)), interval_seconds=0.05)
    writer = threading.Thread(target=backup_more, name="chaos-backup")
    with svc, gc:
        writer.start()
        group = RestoreGroup()
        dests = [tmp_path / f"dst{i}" for i in range(2)]
        for d in dests:
            group.add(Repository.open(FsObjectStore(str(root))), d)
        results = group.run()
        writer.join()
    assert all(r is not None and r["files"] == 6 for r in results)
    for d in dests:
        _assert_identical(src, d)
    _converge(svc)
    # both rotten packs were healed by SOMEONE (scrub or read-repair);
    # scrub's own books never exceed the injected corruption count
    assert svc.corruptions <= 2
    import hashlib
    for v in victims:
        body = fs.get(f"data/{v[:2]}/{v}")
        assert hashlib.sha256(body).hexdigest() == v, \
            f"pack {v} still rotten after the drill"
    assert list(fs.list("quarantine/")) == []
    assert Repository.open(fs).check(read_data=True) == []
