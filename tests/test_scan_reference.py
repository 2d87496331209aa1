"""The restic mover backing one rewritten file up into a repository that
already holds a history, against the plain reference
``benchmark/reference/dedupscan.py`` (``hashlib``, numpy, a ``set``, the
reference chunker and ids; nothing of the program), as the benchmark's
cell ``dedup-1t-indexed.scan`` does at its size: a history of 4,096
blobs written through ``Repository.add_blobs``, then three syncs of the
configuration's 12 MiB rehearsal volume through
``movers/restic/entry.restic_entrypoint``. Holds guarantees (a)-(d) of
``benchmark/configs/dedup-1t-indexed.json``; then the index alone at
65,536 ids against a Python ``set``, ``load_index`` over several index
objects, and the spans and counters the cell's layer metrics read. CPU,
seeded."""

import json
import shutil
from pathlib import Path

import numpy as np
import pytest

from benchmark import mover, scanstate
from benchmark.drivers import scan_check
from benchmark.drivers.backup_check import snapshot_files
from benchmark.drivers.backup_scan import COUNTED, LOADS
from benchmark.reference import dedupscan
from volsync_tpu.obs import (counter_totals, reset_spans, reset_trace,
                             span_self_totals, span_totals, trace_context,
                             trace_events)
from volsync_tpu.repo import shardedindex
from volsync_tpu.repo.compactindex import as_key_rows
from volsync_tpu.repo.repository import Repository
from volsync_tpu.repo.shardedindex import INDEX_COUNTERS, ShardedBlobIndex

ROOT = Path(__file__).resolve().parent.parent
CONFIG = json.loads(
    (ROOT / "benchmark/configs/dedup-1t-indexed.json").read_text())
CELL = json.loads((ROOT / "benchmark/workloads/dedup-1t-indexed.scan.json")
                  .read_text())
CHUNKER = CONFIG["chunker"]
SHAPE = CONFIG["rehearsal"]["shape"]
PARAMS = {**CELL["params"], **CELL["rehearsal"]["params"]}
HISTORY, BLOB, FRESH = (PARAMS["index_blobs"], PARAMS["history_blob_bytes"],
                        PARAMS["fresh_bytes"])
SEED = 2147483659
SYNCS = 3


def _env(repo: Path) -> dict:
    return {"RESTIC_REPOSITORY": str(repo), "RESTIC_PASSWORD": mover.PASSWORD,
            "HOSTNAME": "ref"}


def _job(repo: Path, **job) -> dict:
    return {"env": _env(repo), "seed": SEED, "shape": SHAPE, "fresh": FRESH,
            "chunker": CHUNKER, "index_blobs": HISTORY,
            "history_blob_bytes": BLOB, "operations": SYNCS, **job}


def _store(path: Path):
    from volsync_tpu.objstore import open_store

    return open_store(str(path))


@pytest.fixture(scope="module")
def schedule(tmp_path_factory):
    """The history, then the first backup and two syncs, each after its
    step of churn; what a fresh open held before each, what the program
    counted, and what the reference says of the same run."""
    work = tmp_path_factory.mktemp("scan")
    vol, repo = work / "vol", work / "repo"
    wrote = scanstate.write_history(
        Repository.init(_store(repo), password=mover.PASSWORD), SEED,
        HISTORY, BLOB)
    files = scanstate.write_volume(vol, SHAPE, FRESH, SEED)
    syncs = []
    for i in range(SYNCS):
        if i:
            scanstate.churn(vol, SHAPE, FRESH, SEED, i)
        held = mover.open_repo(_env(repo)).blob_ids()
        counts = counter_totals()
        assert mover.run_mover("backup", _env(repo), vol) == 0
        now = counter_totals()
        fresh = mover.open_repo(_env(repo))
        snaps = fresh.list_snapshots()
        syncs.append({
            "held": held, "snaps": snaps,
            "entries": snapshot_files(fresh, snaps[-1][1]["tree"]),
            "counts": {k: now.get(k, 0) - counts.get(k, 0)
                       for k in COUNTED}})
    _, ids = scanstate.history_blobs(SEED, HISTORY, BLOB)
    ref, held = dedupscan.scan(
        ids, scanstate.file_states(SHAPE, FRESH, SEED, range(SYNCS)), CHUNKER)
    return {"repo": repo, "vol": vol, "files": files, "wrote": wrote,
            "syncs": syncs, "ref": ref, "held": held, "history": ids}


@pytest.mark.parametrize("k", range(SYNCS))
def test_an_operation_adds_what_the_reference_says(schedule, k):
    s, ref = schedule["syncs"][k], schedule["ref"][k]
    (rel, size), = schedule["files"].items()
    c = s["counts"]
    assert (c["backup.files_changed"], c["backup.bytes_changed"]) \
        == (1, size)
    assert (c["repo.blobs_new"], c["repo.bytes_new"], c["repo.blobs_dedup"]) \
        == (ref["blobs_new"], ref["bytes_new"], ref["blobs_dedup"])
    assert sum(ref["lengths"]) == size
    # the snapshot names the reference's ids at the reference's cuts
    content = s["entries"][rel]["content"]
    assert content == ref["ids"] and s["entries"][rel]["size"] == size
    # which hits were on entries a fresh open had loaded before the
    # operation, and which on entries the operation itself had just made
    loaded = sum(bid in s["held"] for bid in content)
    first = {bid for bid in content if bid not in s["held"]}
    assert loaded == ref["hits_earlier"]
    assert len(content) - loaded - len(first) == ref["hits_inside"]
    assert ref["hits_earlier"] + ref["hits_inside"] == ref["blobs_dedup"]
    # the history and what earlier operations added were there to find
    assert ref["held_before"] == len(set(schedule["history"]) | {
        bid for earlier in schedule["ref"][:k] for bid in earlier["new"]})
    assert k == 0 or ref["hits_earlier"] > ref["blobs_new"] > 0
    # both loads saw all of it; every chunk and the tree were asked for
    assert c["repo.index_loads"] == LOADS
    assert c["repo.index_entries"] >= LOADS * ref["held_before"]
    assert c["index.queries"] >= len(content)
    assert ref["blobs_dedup"] <= c["index.hits"] <= c["index.queries"]
    # (d) each snapshot names the one before: the history's comes first
    snaps = s["snaps"]
    assert len(snaps) == k + 2
    assert snaps[0][1]["hostname"] == scanstate.HISTORY_HOST
    assert [man.get("parent") for _, man in snaps[1:]] \
        == [sid for sid, _ in snaps[:-1]]


@pytest.mark.parametrize("job", [
    {"mode": "index", "sample": 64}, {"mode": "snapshot", "operation": 0},
    {"mode": "snapshot", "operation": SYNCS - 1}],
    ids=["index", "first", "last"])
def test_the_check_child_finds_the_guarantees_held(schedule, job):
    """Guarantees (a)-(c) as ``scan_check.py`` holds a run to them: every
    new blob, a sample of the history and whole snapshots read back, the
    final id set is the reference's and ``check()`` is empty."""
    out = scan_check.check(_job(schedule["repo"], **job))
    assert out["failed"] == 0 and out["errors"] == [], out
    assert out["attempted"] >= 1
    n = out["counts"]
    if job["mode"] == "snapshot":
        assert n.pop("files_read_back") == 1
        assert out["notes"]["blobs"] == len(
            schedule["ref"][job["operation"]]["ids"])
    else:
        new = sum(op["blobs_new"] for op in schedule["ref"])
        assert out["notes"] == {
            "index_ids": len(schedule["held"]) + 1 + SYNCS,
            "history_sampled": 64, "new_blobs_read_back": new}
        assert [r["blobs_new"] for r in out["reference"]] \
            == [op["blobs_new"] for op in schedule["ref"]]
    assert not any(n.values()), n


def test_the_check_child_meets_a_flipped_bit_in_a_pack(schedule, tmp_path):
    mine = tmp_path / "repo"
    shutil.copytree(schedule["repo"], mine)
    store = _store(mine)
    key = max((k for k in store.list("") if k.startswith("data/")),
              key=store.size)
    body = bytearray(store.get(key))
    body[len(body) // 2] ^= 0x10
    store.put(key, bytes(body))
    out = scan_check.check(_job(mine, mode="index", sample=8))
    assert out["failed"] >= 1 and out["counts"]["read_errors"] >= 1


@pytest.mark.parametrize("prefilter", [True, False],
                         ids=["prefilter", "no-prefilter"])
def test_the_index_against_a_set_across_its_growth(prefilter):
    """65,536 seeded ids inserted one at a time from the smallest tables
    (every shard's table and filter grow several times on the way): the
    index answers as a Python ``set`` does, at every size looked at, by
    the batched path and the scalar one; the prefilter never says
    "absent" of an id that is there; the counters add up."""
    rng = np.random.default_rng(SEED)
    raw = rng.integers(0, 256, (2 * 65536, 32), dtype=np.uint8)
    ids = [row.tobytes().hex() for row in raw]
    present, absent = ids[:65536], ids[65536:]
    index = ShardedBlobIndex(capacity=16, prefilter=prefilter)
    plain = set()
    tables = {sh._table.shape[0] for sh in index._shards}
    for n, bid in enumerate(present, 1):
        assert index.insert(bid, f"pack{n >> 12}", "data", n, 64, 64)
        plain.add(bid)
        if n in (1, 100, 4096, 30000, 65536):
            assert len(index) == len(plain) == n
            assert index.contains_many(present[:n]).all()
            assert not index.contains_many(absent[:n]).any()
            tables.add(min(sh._table.shape[0] for sh in index._shards))
    assert len(tables) >= 3  # the tables were rebuilt on the way
    assert set(index) == plain
    assert index.lookup(present[4095]) == ("pack1", "data", 4096, 64, 64)
    assert all(bid in index for bid in present[::257])
    assert not any(bid in index for bid in absent[::257])
    if prefilter:
        rows = as_key_rows(present)
        for s, f in enumerate(index._filters):
            mine = rows[index._shard_ids(rows) == s]
            assert len(mine) and f.maybe_contains_rows(mine).all()
    # a batch over the small-batch limit takes the vectorized path: of
    # its keys the filter skips some, the probe finds or fails the rest
    reset_spans()
    batch = present[:4096] + absent[:4096]
    assert len(batch) > shardedindex._SMALL_BATCH_PER_SHARD * 16
    mask = index.contains_many(batch)
    assert mask[:4096].all() and not mask[4096:].any()
    q, hits, skips, fps = (counter_totals().get(k, 0)
                           for k in INDEX_COUNTERS)
    assert (q, hits) == (8192, 4096)
    if prefilter:
        assert skips + hits + fps == q and skips > 3900 and 0 < fps < 200
    else:  # no filter: nothing skipped, and no answer of its to be wrong
        assert (skips, fps) == (0, 0)
    # a small batch and the scalar question: queries and hits, once a
    # call with the batch's size, and neither of the other two
    index.contains_many(batch[4090:4102])
    assert present[0] in index and absent[0] not in index
    after = [counter_totals().get(k, 0) for k in INDEX_COUNTERS]
    assert after == [q + 12 + 2, hits + 6 + 1, skips, fps]


def test_load_index_over_three_objects_gives_what_one_would(tmp_path,
                                                            monkeypatch):
    """3,000 history blobs with ``PENDING_INDEX_LIMIT`` at 1,000 leave
    three index objects (and the tree's); at the default they leave one.
    A fresh open loads the same index from either."""
    loaded = {}
    for name, limit in (("three", 1000), ("one", None)):
        if limit:
            monkeypatch.setattr(Repository, "PENDING_INDEX_LIMIT", limit)
        else:
            monkeypatch.undo()
        scanstate.write_history(
            Repository.init(_store(tmp_path / name),
                            password=mover.PASSWORD), SEED, 3000, BLOB)
        reset_spans()
        repo = mover.open_repo(_env(tmp_path / name))
        counts = counter_totals()
        assert repo.check() == []
        loaded[name] = (
            {bid: (length, raw) for bid, (_, btype, _, length, raw)
             in repo._index.items() if btype == "data"},
            counts["repo.index_objects"], counts["repo.index_entries"])
    assert loaded["three"][1:] == (4, 3001)
    assert loaded["one"][1:] == (2, 3001)
    assert loaded["three"][0] == loaded["one"][0]
    _, ids = scanstate.history_blobs(SEED, 3000, BLOB)
    assert set(ids) == set(loaded["one"][0])


def _index_object(repo, name: str, packs: dict) -> None:
    """One index object, as ``_write_index_delta`` seals it."""
    body = repo.box.seal(repo._zc.compress(
        json.dumps({"packs": packs}).encode()))
    repo.store.put(f"index/{name}", body)


@pytest.mark.parametrize("first", ["parked-first", "parked-last"])
def test_an_id_two_objects_list_ends_in_the_pack_that_stays(first):
    """An id listed by two index objects, one of them under a pack that
    ``pending-delete/`` names (a crashed pruner's old delta): whichever
    object is read first, the load ends with the LAST listing in a pack
    that stays, else the FIRST in a parked pack, and the id's row where
    its first listing put it."""
    # a store that lists by name: the order the objects are read in
    repo = Repository.init(_store("mem:"), password=mover.PASSWORD)
    ids = [f"{i:064x}" for i in range(1, 7)]

    def entry(bid, offset):
        return {"id": bid, "type": "data", "offset": offset, "length": 9,
                "raw_length": 7}
    parked = {"a" * 64: [entry(ids[0], 10), entry(ids[1], 11),
                         entry(ids[4], 14)],
              "b" * 64: [entry(ids[4], 24), entry(ids[5], 25)]}
    stays = {"c" * 64: [entry(ids[0], 30), entry(ids[2], 32)],
             "d" * 64: [entry(ids[0], 40), entry(ids[3], 43)]}
    names = ("0" * 64, "1" * 64)
    for name, packs in zip(names if first == "parked-first"
                           else names[::-1], (parked, stays)):
        _index_object(repo, name, packs)
    repo.store.put("pending-delete/x", json.dumps(
        {"packs": ["a" * 64, "b" * 64]}).encode())
    reset_spans()
    repo.load_index()
    want = {ids[0]: ("d" * 64, 40),  # the last listing in a pack that stays
            ids[1]: ("a" * 64, 11), ids[2]: ("c" * 64, 32),
            ids[3]: ("d" * 64, 43),
            ids[4]: ("a" * 64, 14),  # parked twice: the first listing
            ids[5]: ("b" * 64, 25)}
    got = {bid: (pack, offset)
           for bid, (pack, _, offset, _, _) in repo._index.items()}
    assert got == want
    # by shard, then by first listing: every id here is of shard 0
    order = [0, 1, 4, 5, 2, 3] if first == "parked-first" \
        else [0, 2, 3, 1, 4, 5]
    assert list(repo._index) == [ids[i] for i in order]
    counts = counter_totals()
    assert counts["repo.index_bulk_entries"] == 6 \
        == counts["repo.index_entries"]
    assert not repo.has_blobs([ids[1], ids[4]]).any()  # parked: not held
    assert repo.has_blobs([ids[0], ids[2], ids[3]]).all()


def test_the_loads_spans_nest_and_split_it(schedule):
    """``repo.index_fetch``, ``repo.index_decode`` and
    ``repo.index_insert`` close inside ``repo.load_index`` (its self
    time is what they leave), one decode and one insert an index
    object and one insert more for the load's placement, which puts
    every entry in by the column (``repo.index_bulk_entries``); they
    keep totals and leave no event on the ring."""
    reset_trace()
    reset_spans()
    with trace_context(sampled=True):
        repo = mover.open_repo(_env(schedule["repo"]))
    counts = counter_totals()
    objects = counts["repo.index_objects"]
    assert objects == 2 + SYNCS and len(repo.blob_ids()) \
        == counts["repo.index_entries"] == counts["repo.index_bulk_entries"]
    parts = ("repo.index_fetch", "repo.index_decode", "repo.index_insert")
    totals, own = span_totals(), span_self_totals()
    assert totals["repo.index_decode"][0] == objects \
        == totals["repo.index_insert"][0] - 1
    assert totals["repo.index_fetch"][0] == objects + 1  # the listings
    whole, inside = totals["repo.load_index"][1], sum(
        totals[name][1] for name in parts)
    assert own["repo.load_index"][1] == pytest.approx(whole - inside,
                                                      abs=1e-6)
    assert 0.8 * whole <= inside <= whole
    on_ring = {e["name"] for e in trace_events() if e.get("ph") == "X"}
    assert "repo.load_index" in on_ring and not on_ring & set(parts)
