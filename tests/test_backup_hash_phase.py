"""The hash phase of TreeBackup (engine/backup.py): one file at a time.

Files are read, hashed and stored in walk order on the thread that
called run(): there is no pool of file workers and no flag that brings
one back (PERF.md section 6, PR 27). What a backup produces is held
against ids computed here, from the files' bytes.
"""

import json
import os
import threading
from concurrent.futures import ThreadPoolExecutor

import pytest

from volsync_tpu import envflags, obs
from volsync_tpu.engine import TreeBackup
from volsync_tpu.engine import backup as backup_mod
from volsync_tpu.objstore import MemObjectStore
from volsync_tpu.repo import blobid
from volsync_tpu.repo.repository import BLOB_DATA, Repository

CHUNKER = {"min_size": 1024, "avg_size": 4096, "max_size": 16384, "seed": 7}
MIN = CHUNKER["min_size"]


@pytest.fixture
def tree(tmp_path, rng):
    """Sizes on both sides of min_size, empty files, equal files, files
    of several chunks, hard links, nested directories."""
    root = tmp_path / "src"
    files = {
        "a/below": rng.bytes(MIN - 1),
        "a/at": rng.bytes(MIN),
        "a/above": rng.bytes(MIN + 1),
        "a/empty": b"",
        "b/twin1": b"t" * 700,
        "b/twin2": b"t" * 700,
        "b/empty2": b"",
        "b/large": rng.bytes(5 * MIN + 123),
        "c/deep/er/tiny": b"x",
        "c/streamed": rng.bytes(40_000),
    }
    files.update({f"d/s{i:02d}": rng.bytes(100 + 37 * i) for i in range(11)})
    for rel, data in files.items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_bytes(data)
    os.link(root / "a/below", root / "a/below.link")
    os.link(root / "c/streamed", root / "d/streamed.link")
    return root, files


#: the page-aligned format, scaled down: the one whose segments may go
#: through the shared batcher (conftest.py ``batched``)
CHUNKER_4K = {"min_size": 4096, "avg_size": 32768, "max_size": 65536,
              "seed": 7, "align": 4096}


def run_backup(root, protocol="cdc", repo=None, chunker=CHUNKER):
    repo = repo or Repository.init(MemObjectStore(), chunker=chunker)
    snap_id, stats = TreeBackup(repo, protocol=protocol).run(root)
    return repo, dict(repo.list_snapshots())[snap_id], stats


def file_entries(repo, tree_id, prefix=""):
    """{relative path: entry} of every file in the snapshot's tree, in
    the tree's order (the walk's)."""
    out = {}
    for e in json.loads(repo.read_blob(tree_id))["entries"]:
        if e["type"] == "dir":
            out.update(file_entries(repo, e["subtree"],
                                    f"{prefix}{e['name']}/"))
        elif e["type"] == "file":
            out[prefix + e["name"]] = e
    return out


def hashed_in_walk_order(files):
    """Paths the hash phase visits: every non-empty file, once, in the
    walk's order (names sorted, depth first); a hard link is an entry
    of the tree and no second read."""
    return sorted(rel for rel, data in files.items() if data)


@pytest.mark.parametrize("protocol", ["cdc", "full"])
def test_entries_describe_the_files(tree, protocol):
    root, files = tree
    repo, manifest, stats = run_backup(root, protocol)
    entries = file_entries(repo, manifest["tree"])
    for rel, data in files.items():
        e = entries[rel]
        assert e["size"] == len(data)
        if data and (len(data) <= MIN or protocol == "full"):
            assert e["content"] == [blobid.blob_id(data)]
        chunks = [repo.read_blob(b) for b in e["content"]]
        assert b"".join(chunks) == data
        assert e["content"] == [blobid.blob_id(c) for c in chunks]
    assert entries["a/below.link"]["hardlink_to"] == "a/below"
    assert entries["d/streamed.link"]["hardlink_to"] == "c/streamed"
    assert entries["b/twin1"]["content"] == entries["b/twin2"]["content"]
    assert stats.bytes_scanned == sum(len(d) for d in files.values())
    assert stats.blobs_dedup >= 1  # twin2 against twin1
    assert repo.check() == []


@pytest.mark.parametrize("protocol", ["cdc", "full"])
def test_two_backups_of_one_tree_store_the_same_packs(
        tree, protocol, batch_segments, batched):
    """Nothing in the phase depends on timing, nor on the way to the
    device (the first backup hashes single-lane, the second as the case
    says): the same tree, the same blobs, and the same packs (blobs in
    the same order at the same offsets), so the store lists the same
    data keys."""
    root, _ = tree
    batch_segments(False)
    repo1, first, stats1 = run_backup(root, protocol, chunker=CHUNKER_4K)
    batch_segments(batched)
    obs.reset_spans()
    repo2, second, stats2 = run_backup(root, protocol, chunker=CHUNKER_4K)
    if protocol == "cdc":  # "full" stores files whole, hashed on the host
        assert ("ops.batch_dispatch" in obs.span_totals()) == batched
        assert ("engine.fused_dispatch" in obs.span_totals()) != batched
    assert first["tree"] == second["tree"]
    assert repo1.blob_ids() == repo2.blob_ids()
    assert sorted(repo1.store.list("data/")) \
        == sorted(repo2.store.list("data/"))
    drop = ("bytes_stored",)  # counted as the flush drains the seal pool
    assert {k: v for k, v in stats1.as_dict().items() if k not in drop} \
        == {k: v for k, v in stats2.as_dict().items() if k not in drop}


@pytest.mark.parametrize("protocol", ["cdc", "full"])
def test_files_are_hashed_in_walk_order_on_the_calling_thread(
        tree, protocol, monkeypatch):
    """Also with VOLSYNC_BACKUP_WORKERS in the environment: PR 27 took
    the flag away with the pool."""
    root, files = tree
    monkeypatch.setenv("VOLSYNC_BACKUP_WORKERS", "4")
    seen = []
    real = TreeBackup._hash_file

    def hash_file(self, path, rel, st, stats):
        seen.append((rel, threading.get_ident()))
        return real(self, path, rel, st, stats)

    monkeypatch.setattr(TreeBackup, "_hash_file", hash_file)
    run_backup(root, protocol)
    assert [rel for rel, _ in seen] == hashed_in_walk_order(files)
    assert {thread for _, thread in seen} == {threading.get_ident()}


def test_blobs_are_stored_in_walk_order(tree, monkeypatch):
    """A file's blobs are in the repository before the next file is
    read: the order of the stores is the order of the tree."""
    root, _ = tree
    stored = []
    real_many, real_one = Repository.add_blobs, Repository.add_blob

    def add_blobs(self, btype, blobs, stats=None):
        blobs = list(blobs)
        if btype == BLOB_DATA:
            stored.extend(digest for digest, _ in blobs)
        return real_many(self, btype, blobs, stats)

    def add_blob(self, btype, blob_id, data, stats=None):
        if btype == BLOB_DATA:
            stored.append(blob_id)
        return real_one(self, btype, blob_id, data, stats)

    monkeypatch.setattr(Repository, "add_blobs", add_blobs)
    monkeypatch.setattr(Repository, "add_blob", add_blob)
    repo, manifest, _ = run_backup(root)
    entries = file_entries(repo, manifest["tree"])
    want = [b for rel, e in entries.items()
            if "hardlink_to" not in e for b in e["content"]]
    assert stored == want


def test_auto_decides_each_file_when_it_is_reached(tree, monkeypatch):
    """protocol="auto" asks the planner about a file after every earlier
    file is stored, so that a decision sees what the backup has observed
    so far (link timings, index hits) and not only what came before it."""
    root, files = tree
    events = []
    real_wants, real_hash = TreeBackup._wants_full, TreeBackup._hash_file

    def wants_full(self, size):
        events.append(("decide", size))
        return real_wants(self, size)

    def hash_file(self, path, rel, st, stats):
        out = real_hash(self, path, rel, st, stats)
        events.append(("stored", rel))
        return out

    monkeypatch.setattr(TreeBackup, "_wants_full", wants_full)
    monkeypatch.setattr(TreeBackup, "_hash_file", hash_file)
    run_backup(root, "auto")
    order = hashed_in_walk_order(files)
    asked = [len(files[rel]) for rel in order if len(files[rel]) > MIN]
    assert [size for kind, size in events if kind == "decide"] == asked
    for i, (kind, what) in enumerate(events):
        if kind == "decide":
            done = [rel for k, rel in events[:i] if k == "stored"]
            assert done == order[:len(done)]
            assert len(files[order[len(done)]]) == what


def _after_the_walk(monkeypatch, action):
    real = TreeBackup._walk_dir

    def walk_then(self, *args, **kwargs):
        skeleton = real(self, *args, **kwargs)
        action()
        return skeleton

    monkeypatch.setattr(TreeBackup, "_walk_dir", walk_then)


@pytest.mark.parametrize("victim", ["d/s03", "c/streamed"])
def test_a_file_deleted_between_walk_and_read_fails_the_backup(
        tree, victim, monkeypatch):
    """The read raises and no snapshot is saved, on either path."""
    root, _ = tree
    os.unlink(root / "d/streamed.link")
    _after_the_walk(monkeypatch, (root / victim).unlink)
    repo = Repository.init(MemObjectStore(), chunker=CHUNKER)
    with pytest.raises(FileNotFoundError):
        TreeBackup(repo).run(root)
    assert repo.list_snapshots() == []


def test_a_file_rewritten_between_walk_and_read(tree, monkeypatch):
    """The entry describes the bytes that were read: their length,
    their id and an mtime taken after the read."""
    root, _ = tree
    victim, new = root / "d/s05", b"rewritten after the walk" * 9
    _after_the_walk(monkeypatch, lambda: victim.write_bytes(new))
    repo, manifest, _ = run_backup(root)
    e = file_entries(repo, manifest["tree"])["d/s05"]
    assert e["size"] == len(new)
    assert e["content"] == [blobid.blob_id(new)]
    assert e["mtime_ns"] == victim.lstat().st_mtime_ns
    assert repo.read_blob(e["content"][0]) == new


def test_one_backup_file_span_a_file(tree):
    """``backup.file`` is one span a hashed file, on the thread that
    runs ``backup.hash``, which says how many files it holds."""
    root, files = tree
    obs.reset_spans()
    run_backup(root)
    totals = obs.span_totals()
    assert totals["backup.file"][0] == len(hashed_in_walk_order(files))
    assert totals["backup.hash"][0] == 1
    assert totals["backup.file"][1] <= totals["backup.hash"][1]


def test_there_is_no_worker_count_to_set():
    repo = Repository.init(MemObjectStore(), chunker=CHUNKER)
    with pytest.raises(TypeError):
        TreeBackup(repo, workers=4)
    assert not hasattr(TreeBackup(repo), "workers")
    # what benchmark/warm.py plans the batched segment programs from
    assert envflags.backup_workers() == 1


def test_two_backups_at_once_into_one_repository(tree):
    """Concurrency is between backups now (the fleet's replicas, two
    movers of one process), not inside one: the repository dedups the
    equal blobs of both under its lock, and both snapshots hold the
    tree that one backup alone gives."""
    root, _ = tree
    _, alone, _ = run_backup(root)
    repo = Repository.init(MemObjectStore(), chunker=CHUNKER)
    with ThreadPoolExecutor(2) as pool:
        got = list(pool.map(lambda _: run_backup(root, repo=repo),
                            range(2)))
    assert [manifest["tree"] for _, manifest, _ in got] \
        == [alone["tree"]] * 2
    # every blob, data or tree, was new to exactly one of the two
    assert sum(s.blobs_new for _, _, s in got) == len(repo.blob_ids())
    assert repo.check() == []
