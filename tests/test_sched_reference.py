"""The restic mover on a schedule against the plain reference
``benchmark/reference/increment.py`` (``hashlib``, numpy, the reference
chunker and ids; nothing of the program): a volume backed up, then
synced five times through ``movers/restic/entry.restic_entrypoint`` with
``FORGET_LAST``, each sync after one step of the rehearsal's churn, as
the benchmark's cell ``restic-sched-10g.incremental`` does at its size.
Holds guarantees (a)-(e) of ``benchmark/configs/restic-sched-10g.json``.
CPU, a few MiB, seeded."""

import io
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from benchmark import churn, mover, volumes
from benchmark.drivers.backup_check import snapshot_files
from benchmark.drivers.backup_sched import COUNTED
from benchmark.reference import increment
from volsync_tpu.obs import (counter_totals, reset_trace, span_totals,
                             trace_context, trace_events)

ROOT = Path(__file__).resolve().parent.parent
CELL = "restic-sched-10g.incremental"
CONFIG = json.loads(
    (ROOT / "benchmark/configs/restic-sched-10g.json").read_text())
CHUNKER = CONFIG["chunker"]
SHAPE = CONFIG["rehearsal"]["shape"]
MIDS = sorted(f["path"] for f in SHAPE["files"]
              if f["path"].startswith("mid/"))
SEED = 2147483659
SYNCS, RETAIN = 5, 3
SPANS = ("backup.read", "backup.open", "repo.dedup_query", "repo.forget",
         "repo.load_index", "repo.list_snapshots", "backup.parent")


def _env(repo: Path, **extra) -> dict:
    return {"RESTIC_REPOSITORY": str(repo), "RESTIC_PASSWORD": "ref",
            "HOSTNAME": "ref", **extra}


def _open(repo: Path):
    from volsync_tpu.objstore import open_store
    from volsync_tpu.repo.repository import Repository

    return Repository.open(open_store(str(repo)), password="ref")


def _state(root: Path, files) -> dict:
    out = {}
    for rel in files:
        st = os.lstat(root / rel)
        out[rel] = (st.st_size, st.st_mtime_ns)
    return out


def _sync(repo: Path, vol: Path, **env) -> dict:
    """One entry call; what it counted, the spans it entered, and the
    newest snapshot as a fresh open lists it."""
    counts, spans = counter_totals(), span_totals()
    rc = mover.run_mover("backup", _env(repo, **env), vol)
    now, now_spans = counter_totals(), span_totals()
    fresh = _open(repo)
    snaps = fresh.list_snapshots()
    return {"rc": rc, "snaps": [sid for sid, _ in snaps],
            "id": snaps[-1][0], "manifest": snaps[-1][1],
            "entries": snapshot_files(fresh, snaps[-1][1]["tree"]),
            "counts": {k: now.get(k, 0) - counts.get(k, 0) for k in COUNTED},
            "spans": {k: now_spans.get(k, (0, 0))[0]
                      - spans.get(k, (0, 0))[0] for k in SPANS}}


@pytest.fixture(scope="module")
def schedule(tmp_path_factory):
    """The first backup and five syncs with ``FORGET_LAST=3``; a copy of
    the volume in every state, the churn's record of every step, and
    what the reference says the repository held before each sync."""
    work = tmp_path_factory.mktemp("schedule")
    vol, repo = work / "vol", work / "repo"
    files = volumes.write(vol, SHAPE, SEED)
    small = sorted(rel for rel in files if rel.startswith("small/"))
    syncs, held = [], set()
    for i in range(SYNCS + 1):
        before = {}
        if i:
            time.sleep(0.02)  # two tiny syncs can land in one instant
            files, before = churn.apply(
                vol, files, small,
                {"rewrite_small_share": 0.05,
                 "append": {"path": MIDS[i % len(MIDS)],
                            "bytes": 256 << 10}}, SEED + i)
        got = _sync(repo, vol, FORGET_LAST=str(RETAIN))
        assert got["rc"] == 0
        copy = work / f"state{i}"
        shutil.copytree(vol, copy, symlinks=True)
        got.update(changed=sorted(before), before=before, copy=copy,
                   state=_state(vol, files))
        # what the repository holds from now on, by the reference alone
        was = syncs[-1]["state"] if syncs else {}
        ref = increment.increment(vol, was, got["state"], held, CHUNKER)
        got["ref"] = ref
        held |= set(ref["new"])
        syncs.append(got)
    return repo, vol, syncs


@pytest.mark.parametrize("k", range(1, SYNCS + 1))
def test_a_sync_adds_what_the_reference_says(schedule, k):
    _, _, syncs = schedule
    s, ref = syncs[k], syncs[k]["ref"]
    # the churn's own record: the small files it rewrote and one mid/
    assert ref["read"] == s["changed"] and len(ref["read"]) == 3
    assert s["counts"]["backup.files_changed"] == len(s["changed"])
    assert s["counts"]["backup.files_unchanged"] == len(ref["unchanged"]) \
        == len(s["state"]) - len(s["changed"])
    assert s["counts"]["backup.files"] == len(s["state"])
    assert s["counts"]["backup.bytes_changed"] == ref["bytes_read"] \
        == sum(s["state"][rel][0] for rel in s["changed"])
    assert s["counts"]["backup.bytes_unchanged"] == sum(
        s["state"][rel][0] for rel in ref["unchanged"])
    # the tree holds the state; a read file has the reference's ids at
    # the reference's cuts, an unchanged one its parent's content
    assert {rel: (e["size"], e["mtime_ns"])
            for rel, e in s["entries"].items()} == s["state"]
    fresh = _open(schedule[0])
    for rel, blobs in ref["files"].items():
        assert s["entries"][rel]["content"] == [bid for bid, _ in blobs]
        if k > SYNCS - RETAIN:  # still retained: its blobs are listed
            assert [len(fresh.read_blob(bid)) for bid, _ in blobs] \
                == [n for _, n in blobs]
    for rel in ref["unchanged"]:
        assert s["entries"][rel]["content"] \
            == syncs[k - 1]["entries"][rel]["content"]
    # the blobs the sync added are the read files' less what was held
    assert s["counts"]["repo.blobs_new"] == len(ref["new"])
    assert s["counts"]["repo.bytes_new"] == sum(ref["new"].values())
    assert s["counts"]["repo.blobs_dedup"] == sum(
        map(len, ref["files"].values())) - len(ref["new"])
    # the append: the mid/ file's last chunks are new, the ones before
    # them (where it has a cut) are found in the index
    grown = MIDS[k % len(MIDS)]
    added = sum(ref["new"].get(bid, 0) for bid, _ in ref["files"][grown])
    assert 256 << 10 <= added <= s["state"][grown][0]
    assert (added < s["state"][grown][0]) \
        == (s["counts"]["repo.blobs_dedup"] > 0)
    assert s["manifest"]["parent"] == syncs[k - 1]["id"]
    assert s["manifest"]["stats"]["files_unchanged"] == len(ref["unchanged"])


def test_an_unchanged_file_is_not_opened(schedule):
    """Rule (c): a sync opens the files the churn touched and no other
    (one ``backup.read`` a host-path file, one ``backup.open`` a
    device-path file), and asks the index once a file it takes from its
    parent."""
    _, _, syncs = schedule
    for s in syncs[1:]:
        assert s["spans"]["backup.read"] + s["spans"]["backup.open"] \
            == len(s["changed"])
        assert s["spans"]["backup.open"] == 1  # the mid/ file that grew
        assert s["spans"]["repo.dedup_query"] \
            >= s["counts"]["backup.files_unchanged"]
    first = syncs[0]
    assert first["spans"]["backup.read"] + first["spans"]["backup.open"] \
        == len(first["state"]) == first["counts"]["backup.files_changed"]


def test_what_a_sync_reads_from_the_store(schedule):
    """Two index loads (the open, then under the lock), two snapshot
    listings (the parent, then forget), one forget; every sync leaves
    index objects for the next to read."""
    _, _, syncs = schedule
    for k, s in enumerate(syncs):
        # (the first backup finds no repository to open)
        assert s["spans"]["repo.load_index"] == (2 if k else 1) \
            == s["counts"]["repo.index_loads"]
        assert s["spans"]["repo.list_snapshots"] == 2
        assert s["spans"]["backup.parent"] == s["spans"]["repo.forget"] == 1
        assert s["counts"]["repo.snapshots_listed"] \
            == min(k, RETAIN) + min(k + 1, RETAIN + 1)
        assert s["counts"]["repo.forget_removed"] == int(k >= RETAIN)
    loaded = [s["counts"]["repo.index_objects"] for s in syncs]
    assert loaded[0] == 0 and loaded == sorted(set(loaded))
    # an append leaves the chunks before the file's last cut where they were
    assert sum(s["counts"]["repo.blobs_dedup"] for s in syncs[1:]) > 0


@pytest.fixture
def tiny(tmp_path):
    """A volume of small files alone (the host path: no device), backed
    up once."""
    vol, repo = tmp_path / "vol", tmp_path / "repo"
    files = volumes.write(vol, {"small": {**SHAPE["small"], "count": 8}},
                          SEED)
    first = _sync(repo, vol)
    assert first["rc"] == 0
    return repo, vol, sorted(files), first


def test_a_rewrite_at_its_size_and_old_mtime_is_taken_from_the_parent(tiny):
    """Rule (c) is restic's: size and mtime decide, not the bytes."""
    repo, vol, rels, first = tiny
    was = os.lstat(vol / rels[0])
    (vol / rels[0]).write_bytes(
        np.random.default_rng(1).bytes(was.st_size))
    os.utime(vol / rels[0], ns=(was.st_atime_ns, was.st_mtime_ns))
    (vol / rels[1]).write_bytes(
        np.random.default_rng(2).bytes(os.lstat(vol / rels[1]).st_size))
    got = _sync(repo, vol)
    assert got["rc"] == 0
    assert got["counts"]["backup.files_changed"] == 1
    assert got["counts"]["backup.files_unchanged"] == len(rels) - 1
    assert got["spans"]["backup.read"] == 1
    assert got["entries"][rels[0]]["content"] \
        == first["entries"][rels[0]]["content"]
    assert got["entries"][rels[1]]["content"] \
        != first["entries"][rels[1]]["content"]


def test_a_file_whose_blobs_a_prune_parked_is_read_again(tiny):
    """Rule (c), its second half: size and mtime are the parent's, but
    the index no longer offers the parent's blobs (a prune marked their
    pack while the snapshot that names them was on its way): the file is
    read and stored again, and the next prune loses nothing."""
    repo, vol, rels, first = tiny
    rel = rels[0]
    body, was = (vol / rel).read_bytes(), os.lstat(vol / rel)
    os.unlink(vol / rel)
    assert _sync(repo, vol, FORGET_LAST="1")["rc"] == 0
    held = _open(repo)
    held.prune()  # the default grace: the file's blobs are parked
    assert not held.has_blobs(first["entries"][rel]["content"]).any()
    # the snapshot that still names them lands after the mark
    manifest = {k: v for k, v in first["manifest"].items() if k != "time"}
    held.save_snapshot(manifest)
    (vol / rel).write_bytes(body)
    os.utime(vol / rel, ns=(was.st_atime_ns, was.st_mtime_ns))
    got = _sync(repo, vol, FORGET_LAST="1")
    assert got["rc"] == 0
    assert got["counts"]["backup.files_changed"] == 1 \
        == got["spans"]["backup.read"]
    assert got["counts"]["backup.files_unchanged"] == len(rels) - 1
    assert got["counts"]["repo.blobs_new"] == 1
    assert got["entries"][rel]["content"] == first["entries"][rel]["content"]
    fresh = _open(repo)
    fresh.prune(grace_seconds=0)
    fresh = _open(repo)
    assert fresh.check() == []
    assert fresh.read_blob(got["entries"][rel]["content"][0]) == body


@pytest.mark.parametrize("keep", [1, 2, 4])
def test_forget_last_keeps_the_newest_and_their_chain(tiny, keep):
    repo, vol, rels, first = tiny
    made = [first["id"]]
    for i in range(1, 5):
        (vol / rels[i]).write_bytes(np.random.default_rng(i).bytes(2000 + i))
        got = _sync(repo, vol, FORGET_LAST=str(keep))
        assert got["rc"] == 0
        made.append(got["id"])
        assert got["snaps"] == made[-keep:]
        assert got["counts"]["repo.forget_removed"] == int(i >= keep)
    snaps = _open(repo).list_snapshots()
    assert [sid for sid, _ in snaps] == made[-keep:]
    # every snapshot names the one taken before it, retained or not
    assert [m["parent"] for _, m in snaps] == made[-keep - 1:-1]


@pytest.mark.parametrize("grace", ["0", None], ids=["grace0", "default"])
def test_a_prune_leaves_every_retained_snapshot_whole(schedule, tmp_path,
                                                      monkeypatch, grace):
    """Guarantee (e), through the entry (``DIRECTION=prune``)."""
    repo, vol, syncs = schedule
    mine = tmp_path / "repo"
    shutil.copytree(repo, mine)
    if grace is None:
        monkeypatch.delenv("VOLSYNC_PRUNE_GRACE_S", raising=False)
    else:
        monkeypatch.setenv("VOLSYNC_PRUNE_GRACE_S", grace)
    stored = sum(p.stat().st_size for p in mine.rglob("*") if p.is_file())
    assert mover.run_mover("prune", _env(mine), vol) == 0
    fresh = _open(mine)
    assert fresh.check() == []
    snaps = fresh.list_snapshots()
    assert [sid for sid, _ in snaps] == [s["id"] for s in syncs[-RETAIN:]]
    for (_sid, man), s in zip(snaps, syncs[-RETAIN:]):
        entries = snapshot_files(fresh, man["tree"])
        assert sorted(entries) == sorted(s["state"])
        for rel, e in entries.items():
            data = b"".join(fresh.read_blob(bid) for bid in e["content"])
            assert data == (s["copy"] / rel).read_bytes(), rel
    after = sum(p.stat().st_size for p in mine.rglob("*") if p.is_file())
    # at grace 0 what only forgotten snapshots held is gone at once
    assert (after < stored) == (grace == "0")


def test_the_new_spans_close_inside_prepare_and_the_walk_stays_off_the_ring(
        schedule, tmp_path):
    repo, vol, syncs = schedule
    mine = tmp_path / "repo"
    shutil.copytree(repo, mine)
    reset_trace()
    with trace_context(sampled=True):
        assert mover.run_mover("backup", _env(mine, FORGET_LAST="3"),
                               vol) == 0
    events = [e for e in trace_events() if e.get("ph") == "X"]
    by_id = {e["args"]["span_id"]: e for e in events}

    def inside(name):
        out = []
        for e in events:
            if e["name"] == name:
                up = by_id.get(e["args"]["parent_span_id"])
                out.append(up["name"] if up else None)
        return out

    assert inside("repo.load_index") == ["repo.open", "backup.prepare"] \
        or inside("repo.load_index") == [None, "backup.prepare"]
    assert inside("backup.parent") == ["backup.prepare"]
    assert sorted(inside("repo.list_snapshots")) \
        == ["backup.parent", "repo.forget"]
    assert len(inside("repo.forget")) == 1
    # nothing changed since the last sync: every file is settled in the
    # walk by one index query, and none of them is an event
    files = len(syncs[-1]["state"])
    assert span_totals()["repo.dedup_query"][0] >= files
    assert "backup.walk" not in inside("repo.dedup_query")
    assert len(events) < files


def _run(script, tmp_path, *argv):
    return subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / script), "--workload",
         CELL, "--size", "rehearsal", *argv],
        capture_output=True, text=True, cwd=str(ROOT), timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             # the cells run the shared batcher, the suite pins it off
             "VOLSYNC_BATCH_SEGMENTS": "1",
             "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax_cache")})


def test_the_cells_rehearsal_ends_correct(tmp_path):
    done = _run("run.py", tmp_path, "--seed", "2147483660", "--seconds",
                "3", "--trace", "0")
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(ln) for ln in done.stdout.splitlines()
             if ln.startswith("{")]
    last, info = lines[-1], lines[-2]
    assert last["correct"] is True and last["failed"] == 0
    assert info["operations"] >= 1 and info["in_window"]["compiles"] == 0
    checks = {c["check"]: c for c in lines if "check" in c}
    for name in ("ops_failed", "files_unchanged_off", "files_changed_off",
                 "bytes_changed_off", "forget_removed_off",
                 "snapshots_listed_off", "parent_chain_breaks",
                 "snapshots_not_newest", "check_problems", "tree_state_off",
                 "state_bytes_off", "read_set_off", "blob_id_mismatches",
                 "chunk_boundary_mismatches", "unchanged_content_off",
                 "new_blobs_missing", "new_blobs_extra", "new_bytes_off",
                 "content_mismatch", "size_mismatch", "files_missing",
                 "files_extra", "snapshots_after_prune_off",
                 "check_problems_after_prune", "old_state_mismatch",
                 "read_errors"):
        assert checks[name] == {"check": name, "value": 0, "limit": 0}
    assert checks["files_read_back"]["value"] >= 1
    staged = checks["device_staged_bytes"]
    assert staged["value"] >= staged["at_least"] > 0
    window = next(ln for ln in lines if "sched_window" in ln)["sched_window"]
    assert window["syncs"] == info["operations"]
    for c in window["counts"]:
        assert c["repo.forget_removed"] == 1 and c["repo.index_loads"] == 2


def test_the_cells_control_does_not(tmp_path):
    done = _run("control.py", tmp_path, "--seeds", "2147483661",
                "--seconds", "2")
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-2000:]
    last = json.loads(done.stdout.splitlines()[-1])
    assert last["control"] == "flip_pack_bit" and last["correct"] is False


@pytest.mark.parametrize("hint", ["exact", "short", "long", None])
def test_a_stream_that_ends_on_a_fill_ends_with_it(hint):
    """A file of a whole number of segment fills, with the size the walk
    saw, closes with its last full segment: no segment, and no program
    shape, for its tail alone. The chunks are the reference's with any
    hint or none."""
    from benchmark.reference import gearcdc
    from volsync_tpu.engine import chunker

    conf = {**CHUNKER, "min_size": 16384, "avg_size": 32768,
            "max_size": 131072}
    params = chunker.params_from_config(conf)
    segment = 256 << 10
    data = np.random.default_rng(SEED).bytes(2 * (segment + params.max_size))
    size = {"exact": len(data), "short": len(data) - 5,
            "long": len(data) + 7, None: None}[hint]
    seen = []

    class Counting(chunker.DeviceChunkHasher):
        def begin(self, buffer, **kw):
            seen.append(kw["eof"])
            return super().begin(buffer, **kw)

    got = [(len(c), d) for batch in chunker.stream_chunk_batches(
        io.BytesIO(data).read, params, segment_size=segment,
        hasher=Counting(params), readahead=0, size_hint=size)
        for c, d in batch]
    assert got == [(n, d) for d, n in increment.file_blobs(data, conf)]
    assert [n for n, _ in got] == [n for _, n in gearcdc.cuts(data, conf)]
    assert seen == ([False, True] if hint == "exact"
                    else [False, False, True])


def test_the_reference_needs_nothing_of_the_program():
    src = (ROOT / "benchmark/reference/increment.py").read_text()
    assert "volsync_tpu" not in src.split('"""', 2)[2]
