"""Restore-storm chaos drill: N concurrent pipelined restores through
the resilience layer over seeded fault schedules (`make chaos-restore`).

The stack is the open_store() layering with an op-counting shim under
the faults:

    ResilientStore(FaultStore(LatencyStore(FsObjectStore)))

so the inner LatencyStore counts only operations that actually REACHED
the store (post-injection) — the number the single-flight PackCache
bounds. For every schedule the drill asserts the end-to-end contract:

- every restore in the storm completes (retries absorb the weather),
- every destination is byte-identical to the source tree,
- each pack crossed the wire ~once for the WHOLE storm: whole-pack
  GETs that landed <= unique packs + faulted re-reads, and always
  strictly below the naive N×packs,
- a crash mid-storm (dead store) leaves NO partial file behind —
  the pipelined restore's failure cleanup unlinks every claimed,
  unfinished target.
"""

import numpy as np
import pytest

from volsync_tpu.engine import RestoreGroup, TreeBackup
from volsync_tpu.objstore.faultstore import (
    FaultSchedule,
    FaultSpec,
    FaultStore,
)
from volsync_tpu.objstore.store import FsObjectStore, LatencyStore
from volsync_tpu.repo.repository import Repository
from volsync_tpu.resilience import CircuitBreaker, ResilientStore, RetryPolicy

CHUNKER = {"min_size": 4096, "avg_size": 32768, "max_size": 65536,
           "seed": 7, "align": 4096}
STORM = 4  # concurrent restores per drill


def _src_tree(tmp_path):
    rng = np.random.RandomState(5)
    src = tmp_path / "src"
    src.mkdir()
    for i in range(5):
        (src / f"f{i}.bin").write_bytes(rng.bytes(110_000 + 13 * i))
    sub = src / "sub"
    sub.mkdir()
    (sub / "nested.bin").write_bytes(rng.bytes(40_000))
    return src


def _storm_stack(root, seed, specs):
    """(counting shim, fault wrapper, resilient top). Retry policy:
    enough attempts that p^attempts is negligible; tiny REAL backoff
    sleeps so partition windows (tens of ms) heal between attempts;
    a breaker that never trips (it has its own unit tests)."""
    counted = LatencyStore(FsObjectStore(str(root)))
    faults = FaultStore(counted, FaultSchedule(seed=seed, specs=list(specs)))
    policy = RetryPolicy(site="restore-storm", max_attempts=12,
                         base_delay=0.005, max_delay=0.02)
    top = ResilientStore(faults, policy=policy,
                         breaker=CircuitBreaker("restore-storm",
                                                threshold=10**9,
                                                reset_seconds=0.01))
    return counted, faults, top


def _seed_repo(fs_root, src):
    fs = FsObjectStore(str(fs_root))
    repo = Repository.init(fs, chunker=CHUNKER)
    repo.PACK_TARGET = 64 * 1024  # several packs from a small tree
    snap, _ = TreeBackup(repo).run(src)
    assert snap
    return len([k for k in fs.list("data/")])


def _assert_identical(src, dst):
    for p in src.rglob("*"):
        rel = p.relative_to(src)
        if p.is_file():
            assert (dst / rel).read_bytes() == p.read_bytes(), rel


#: Storm weather — the read-path fault kinds the ISSUE names. Broad
#: probabilistic specs use p high enough that never-firing is
#: negligible over the drill's arrivals; the narrow partition spec
#: uses ``at=N`` with a window far shorter than the retry budget.
SCHEDULES = [
    ("transient", 2101, [FaultSpec(kind="transient", p=0.20)]),
    ("truncated-read", 2202,
     [FaultSpec(kind="truncated_read", at=1, op="get", key_prefix="data/"),
      FaultSpec(kind="truncated_read", p=0.15, op="get|get_range")]),
    ("partition", 2303,
     [FaultSpec(kind="partition", at=2, op="get", key_prefix="data/",
                latency=0.03)]),
    ("mixed", 2404,
     [FaultSpec(kind="transient", p=0.12),
      FaultSpec(kind="truncated_read", p=0.10, op="get|get_range"),
      FaultSpec(kind="partition", at=3, op="get", key_prefix="data/",
                latency=0.03)]),
]


@pytest.mark.parametrize("name,seed,specs", SCHEDULES,
                         ids=[s[0] for s in SCHEDULES])
def test_restore_storm_chaos(tmp_path, name, seed, specs):
    src = _src_tree(tmp_path)
    npacks = _seed_repo(tmp_path / "store", src)
    assert npacks > 1
    counted, faults, top = _storm_stack(tmp_path / "store", seed, specs)

    group = RestoreGroup()
    dests = [tmp_path / f"dst{i}" for i in range(STORM)]
    for d in dests:
        group.add(Repository.open(top), d)
    results = group.run()

    assert all(r is not None and r["files"] == 6 for r in results)
    for d in dests:
        _assert_identical(src, d)

    # single-flight under weather: only truncated_read executes the
    # inner op before failing, so each such injection on a whole-pack
    # GET may add one landed re-read; everything else never reaches
    # the counter. Naive would be STORM × npacks.
    truncated_pack_gets = sum(
        1 for (_, op, key, kind) in faults.injected
        if kind == "truncated_read" and op == "get"
        and key.startswith("data/"))
    assert counted.pack_fetches <= npacks + truncated_pack_gets, \
        "packs crossed the wire more often than single-flight allows"
    assert counted.pack_fetches < STORM * npacks

    # the shared cache really was shared: ~one miss per pack (faulted
    # leader fetches retry INSIDE the resilient store, so they still
    # count once), the rest of the storm's pack demand served as hits
    stats = group.stats()[0]
    assert stats["misses"] == npacks
    assert stats["hits"] >= (STORM - 1) * npacks


def test_restore_storm_crash_leaves_no_partial_files(tmp_path):
    """Dead store mid-fetch: the drill's hardest contract — a failed
    pipelined restore unlinks every claimed-but-unfinished target, so
    an operator never sees a half-written file."""
    src = _src_tree(tmp_path)
    npacks = _seed_repo(tmp_path / "store", src)
    assert npacks >= 2
    _, faults, top = _storm_stack(
        tmp_path / "store", 2505,
        [FaultSpec(kind="crash", at=2, op="get", key_prefix="data/")])

    group = RestoreGroup()
    dests = [tmp_path / f"dst{i}" for i in range(2)]
    for d in dests:
        group.add(Repository.open(top), d)
    with pytest.raises(Exception, match="injected crash|store is dead"):
        group.run()
    assert faults.crashed

    # fetch stage died before ANY verify batch flushed: directories may
    # exist, but no regular file — partial or complete — was left
    for d in dests:
        leftovers = [p for p in d.rglob("*") if p.is_file()]
        assert leftovers == [], \
            f"failed restore left files behind: {leftovers}"
