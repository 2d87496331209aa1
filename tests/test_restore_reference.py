"""The restic mover's restore direction against plain references
(``benchmark/reference/treecmp.py``: ``os`` and ``hashlib``;
``snapselect.py``: the selection rule on a list of times;
``blobid.py``: hashlib ids): a repository that holds two snapshots is
restored through ``movers/restic/entry.restic_entrypoint``, as the
benchmark's cell ``restic-dest-10g.restore`` does at its size. CPU,
small sizes, seeded."""

import json
import os
import shutil
import time
from datetime import datetime, timedelta, timezone
from pathlib import Path

import numpy as np
import pytest

from benchmark import churn, mover, volumes
from benchmark.reference import blobid as ref_blobid
from benchmark.reference import snapselect, treecmp
from volsync_tpu.obs import (copies_by_site, counter_totals, reset_copies,
                             reset_spans, span_totals)

SHAPE = {
    "files": [{"path": "big.bin", "bytes": 3 << 20, "repeat_half": True},
              {"path": "mid/m00.bin", "bytes": 1536 << 10}],
    "small": {"count": 12, "lo": 1024, "hi": 65536, "dirs": 3,
              "size_seed": 2},
    "history": {"rewrite_small_share": 0.2,
                "append": {"path": "mid/m00.bin", "bytes": 256 << 10}},
}
SEED = 2147483659


def _env(repo: Path, **extra) -> dict:
    return {"RESTIC_REPOSITORY": str(repo), "RESTIC_PASSWORD": "ref",
            "HOSTNAME": "ref", **extra}


def _open(repo: Path):
    from volsync_tpu.objstore import open_store
    from volsync_tpu.repo.repository import Repository

    return Repository.open(open_store(str(repo)), password="ref")


@pytest.fixture(scope="module")
def history(tmp_path_factory):
    """A volume backed up, changed (``benchmark/churn.py``) and backed
    up again into one repository through the mover's entry: (the
    repository, the volume in its second state, a copy of its first
    state, the files the churn touched with their first digests)."""
    work = tmp_path_factory.mktemp("history")
    vol, repo, first = work / "vol", work / "repo", work / "first"
    files = volumes.write(vol, SHAPE, SEED)
    # a file with zero pages inside and a hole at its end (sparse
    # restores seek over them), and an empty one
    body = np.random.default_rng(SEED).bytes(700_000)
    (vol / "holes.bin").write_bytes(
        body[:8192] + bytes(3 * 4096) + body[8192:] + bytes(64 << 10))
    (vol / "empty").write_bytes(b"")
    os.chmod(vol / "holes.bin", 0o640)
    assert mover.run_mover("backup", _env(repo), vol) == 0
    shutil.copytree(vol, first, symlinks=True)
    time.sleep(0.05)  # two small backups can land in one instant
    small = [rel for rel in files if rel.startswith("small/")]
    files, before = churn.apply(vol, files, small, SHAPE["history"], SEED)
    assert mover.run_mover("backup", _env(repo), vol) == 0
    return repo, vol, first, before


def _clean(diff: dict) -> bool:
    return not (diff["missing"] or diff["extra"] or diff["size"]
                or diff["content"] or diff["meta"]) and diff["compared"] > 0


@pytest.mark.parametrize("sparse", ["1", "0"], ids=["sparse", "dense"])
def test_the_newest_snapshot_is_restored_as_the_reference_sees_it(
        history, tmp_path, monkeypatch, sparse):
    repo, vol, first, before = history
    monkeypatch.setenv("VOLSYNC_SPARSE", sparse)
    dest = tmp_path / "dest"
    dest.mkdir()
    assert mover.run_mover("restore", _env(repo), dest) == 0
    diff = treecmp.compare(vol, dest)
    assert _clean(diff), {k: v for k, v in diff.items() if k != "digests"}
    # the churn's files carry their second state, not their first
    assert before and all(diff["digests"][rel] != was
                          for rel, was in before.items())
    blocks = (dest / "holes.bin").stat().st_blocks
    dense = (vol / "holes.bin").stat().st_size // 512
    assert (blocks < dense) == (sparse == "1")


def _times(repo: Path) -> list[datetime]:
    return [datetime.fromisoformat(m["time"])
            for _, m in _open(repo).list_snapshots()]


@pytest.mark.parametrize("selector", ["newest", "previous", "as_of",
                                      "as_of_previous", "before_all"])
def test_the_selectors_pick_what_the_reference_rule_picks(
        history, tmp_path, selector):
    repo, vol, first, _ = history
    t = _times(repo)
    assert len(t) == 2 and t[0] < t[1]
    between = t[0] + (t[1] - t[0]) / 2
    extra, as_of, previous = {
        "newest": ({}, None, 0),
        "previous": ({"SELECT_PREVIOUS": "1"}, None, 1),
        "as_of": ({"RESTORE_AS_OF": between.isoformat()}, between, 0),
        "as_of_previous": ({"RESTORE_AS_OF": between.isoformat(),
                            "SELECT_PREVIOUS": "1"}, between, 1),
        "before_all": ({"RESTORE_AS_OF":
                        (t[0] - timedelta(seconds=1)).isoformat()},
                       t[0] - timedelta(seconds=1), 0),
    }[selector]
    want = snapselect.select(t, as_of, previous)
    dest = tmp_path / "dest"
    dest.mkdir()
    rc = mover.run_mover("restore", _env(repo, **extra), dest)
    if want is None:
        assert rc == 3 and not any(dest.iterdir())
        return
    assert rc == 0
    state = [first, vol][want]
    other = [first, vol][1 - want]
    assert _clean(treecmp.compare(state, dest))
    assert treecmp.compare(other, dest)["content"]


@pytest.mark.parametrize("times,as_of,previous,want", [
    ([3, 1, 2], None, 0, 0), ([3, 1, 2], None, 2, 1),
    ([3, 1, 2], None, 3, None), ([3, 1, 2], 2, 0, 2),
    ([3, 1, 2], 2, 1, 1), ([3, 1, 2], 0, 0, None), ([], None, 0, None),
])
def test_the_reference_rule_and_the_repositorys_agree(times, as_of,
                                                      previous, want):
    """``Repository.select_snapshot`` on manifests that carry only a
    time, against the plain rule."""
    from volsync_tpu.repo.repository import Repository

    def at(k):
        return datetime(2026, 7, k + 1, tzinfo=timezone.utc)

    stamps = [at(k) for k in times]
    got = snapselect.select(stamps, None if as_of is None else at(as_of),
                            previous)
    assert got == want
    repo = Repository.__new__(Repository)
    repo.list_snapshots = lambda: sorted(
        ((str(i), {"time": s.isoformat()}) for i, s in enumerate(stamps)),
        key=lambda kv: kv[1]["time"])
    picked = repo.select_snapshot(
        restore_as_of=None if as_of is None else at(as_of),
        previous=previous)
    assert (None if picked is None else int(picked[0])) == want


@pytest.mark.parametrize("n,empty", [(127, False), (128, False),
                                     (129, False), (40, True)],
                         ids=["127", "128", "129", "an-empty-blob"])
def test_verify_blob_batch_ids_are_the_references(n, empty):
    """On both sides of the program's lane capacity step, with blobs
    that end on a page, inside one, and (last case) hold nothing; a
    wrong id is the one that comes back."""
    from volsync_tpu.engine.chunker import verify_blob_batch

    rng = np.random.default_rng([SEED, n])
    sizes = rng.integers(1, 3 * 4096, n).tolist()
    sizes[1], sizes[2] = 4096, 2 * 4096
    if empty:
        sizes[5] = 0
    blobs = [rng.bytes(int(k)) for k in sizes]
    pairs = [(ref_blobid.blob_id(b), b) for b in blobs]
    reset_spans()
    assert verify_blob_batch(pairs) == []
    counts = counter_totals()
    assert counts["verify.bytes_valid"] == sum(sizes)
    assert {"verify.stage", "verify.launch", "verify.fetch"} \
        <= set(span_totals())
    wrong = "0" * 64
    pairs[n // 2] = (wrong, blobs[n // 2])
    assert verify_blob_batch(pairs) == [wrong]


def test_a_flipped_pack_bit_fails_the_restore_and_leaves_no_partial_file(
        history, tmp_path, capsys):
    """One copy of every pack, so read-repair has nothing to heal
    from: the entry raises ``IntegrityError`` (a Job's non-zero exit)
    and every file the destination still holds is whole."""
    repo, vol, _, _ = history
    broken = tmp_path / "repo"
    shutil.copytree(repo, broken)
    pack = max((p for p in (broken / "data").rglob("*") if p.is_file()),
               key=lambda p: p.stat().st_size)
    body = bytearray(pack.read_bytes())
    body[len(body) // 2] ^= 0x10
    pack.write_bytes(bytes(body))
    dest = tmp_path / "dest"
    dest.mkdir()
    rc = mover.run_mover("restore", _env(broken), dest)
    assert rc != 0
    assert "IntegrityError" in capsys.readouterr().out
    diff = treecmp.compare(vol, dest)
    assert diff["missing"]  # the files of the bad pack are not there
    assert not (diff["extra"] or diff["size"] or diff["content"])


def test_one_restores_spans_and_counters(history, tmp_path):
    """Every span and counter the restore direction records, and what
    the counters hold: each pack the plan names fetched once
    (``restore.bytes_fetched`` is the packs' stored bytes), every
    written byte counted, every unique byte staged for the device."""
    repo, vol, _, _ = history
    dest = tmp_path / "dest"
    dest.mkdir()
    reset_spans()
    reset_copies()
    assert mover.run_mover("restore", _env(repo), dest) == 0
    spans, counts = span_totals(), counter_totals()
    assert {"repo.open", "restore.select", "restore.tree", "restore.plan",
            "restore.fetch", "restore.fetch_wait", "restore.decode",
            "restore.verify", "restore.write", "restore.finalize",
            "verify.stage", "verify.launch", "verify.fetch"} <= set(spans)
    assert spans["repo.open"][0] == spans["restore.select"][0] == 1

    opened = _open(repo)
    opened.load_index()
    newest = opened.list_snapshots()[-1][1]
    blobs, stack = [], [newest["tree"]]
    while stack:
        for e in json.loads(opened.read_blob(stack.pop()))["entries"]:
            if e["type"] == "dir":
                stack.append(e["subtree"])
            elif e["type"] == "file":
                blobs += e["content"]
    entries = {b: opened._entry(b) for b in set(blobs)}
    packs = {e.pack for e in entries.values()}
    stored = sum((repo / "data" / p[:2] / p).stat().st_size for p in packs)
    assert counts["restore.packs_fetched"] == len(packs)
    assert counts["restore.bytes_fetched"] == stored
    assert counts["restore.blobs"] == len(entries)
    restored = sum(p.stat().st_size for p in dest.rglob("*") if p.is_file())
    assert counts["restore.bytes_restored"] == restored \
        == sum(entries[b].raw_length for b in blobs)
    unique = sum(e.raw_length for e in entries.values())
    assert counts["verify.bytes_valid"] == unique \
        == copies_by_site()["verify.stage"]
    assert spans["restore.decode"][0] == spans["restore.fetch_wait"][0] \
        == len(packs)


@pytest.mark.parametrize("kind", ["missing", "extra", "size", "content",
                                  "meta"])
def test_the_tree_reference_sees_each_kind_of_difference(tmp_path, kind):
    a, b = tmp_path / "a", tmp_path / "b"
    volumes.write(a, {"small": {"count": 4, "lo": 100, "hi": 5000,
                                "dirs": 2, "size_seed": 1}}, 7)
    shutil.copytree(a, b)
    assert _clean(treecmp.compare(a, b))
    victim = b / "small" / "d00" / "f00000"
    was = victim.stat()
    if kind == "missing":
        victim.unlink()
    elif kind == "extra":
        (b / "small" / "d01" / "stray").write_bytes(b"x")
    elif kind == "size":
        victim.write_bytes(victim.read_bytes() + b"x")
    elif kind == "content":
        body = bytearray(victim.read_bytes())
        body[0] ^= 1
        victim.write_bytes(bytes(body))
        os.utime(victim, ns=(was.st_atime_ns, was.st_mtime_ns))
    else:
        os.chmod(victim, 0o600 if was.st_mode & 0o077 else 0o644)
    for d in ("small/d00", "small/d01"):  # a directory's mtime moved too
        os.utime(b / d, ns=(0, (a / d).stat().st_mtime_ns))
    diff = treecmp.compare(a, b)
    hit = {k for k in ("missing", "extra", "size", "content", "meta")
           if diff[k]}
    assert hit == {kind}, diff
