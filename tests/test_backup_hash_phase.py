"""The hash phase of TreeBackup (engine/backup.py): one file at a time.

Files are read, hashed and stored in walk order on the thread that
called run(): there is no pool of file workers and no flag that brings
one back (PERF.md section 6, PR 27). What a backup produces is held
against ids computed here, from the files' bytes.
"""

import builtins
import collections
import errno
import io
import json
import os
import threading
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from benchmark.reference import blobid as ref_blobid
from benchmark.reference import gearcdc as ref_gearcdc
from volsync_tpu import envflags, obs
from volsync_tpu import io as volsync_io
from volsync_tpu.engine import DeviceChunkHasher, TreeBackup, params_from_config
from volsync_tpu.engine import backup as backup_mod
from volsync_tpu.engine import chunker as chunker_mod
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


# -- a file that fits one segment costs one descriptor (PR 49) ---------------
#
# What a file costs on the chip's host is its system calls (PERF.md
# section 6, PR 38), and a timing taken here says nothing about them:
# these count the calls.

FILL_SEGMENT = 65536
#: one fill of a stream over ``SmallFill`` under CHUNKER_4K: 128 KiB
FILL = FILL_SEGMENT + CHUNKER_4K["max_size"]
HOST = {"h/tiny": 300, "h/at_min": CHUNKER_4K["min_size"]}
SHORT = {"s/short": 100_000, "s/at_fill": FILL}
LONG = {"l/over": FILL + 1, "l/long": 300_000}
SIZES = {**HOST, **SHORT, **LONG}
PARAMS_4K = params_from_config(CHUNKER_4K)
REF_CHUNKER = {**CHUNKER_4K, "norm_level": PARAMS_4K.norm_level}


class SmallFill(DeviceChunkHasher):
    """The one-chip engine saying how large a segment it fills, as the
    mesh hasher does: one fill is 128 KiB, so the tier-1 tree holds
    files on both sides of it."""

    def stream_segment_size(self, segment_size):
        return FILL_SEGMENT


@pytest.fixture
def sized(tmp_path):
    root = tmp_path / "src"
    files = {rel: np.random.default_rng([11, n]).bytes(n)
             for rel, n in SIZES.items()}
    for rel, data in files.items():
        (root / rel).parent.mkdir(parents=True, exist_ok=True)
        (root / rel).write_bytes(data)
    return root, files


def reference_content(data):
    if len(data) <= CHUNKER_4K["min_size"]:
        return [ref_blobid.blob_id(data)]
    return [ref_blobid.blob_id(data[off: off + n])
            for off, n in ref_gearcdc.cuts(data, REF_CHUNKER)]


def backup_4k(root, hasher=None, repo=None):
    repo = repo or Repository.init(MemObjectStore(), chunker=CHUNKER_4K)
    hasher = hasher or SmallFill(PARAMS_4K)
    snap_id, _ = TreeBackup(repo, hasher=hasher).run(root)
    manifest = dict(repo.list_snapshots())[snap_id]
    return repo, manifest, file_entries(repo, manifest["tree"])


class Calls:
    """What the hash phase asks the kernel and the runtime about each
    file under ``root``, by relative path: ``open`` / ``fstat`` /
    ``close`` of a descriptor ``os.open`` gave, ``path_stat`` (any stat
    by name, ``Path.lstat`` included), ``io_open`` (a buffered or
    ``pathlib`` open), ``readahead`` (the native reader built), and
    ``thread`` (a ``vtpk-readahead`` thread started while the file was
    hashed)."""

    def __init__(self, monkeypatch, root):
        self.by = collections.defaultdict(collections.Counter)
        self.fds = {}
        self.current = None
        prefix = str(root) + "/"

        def rel(path):
            try:
                path = os.fsdecode(os.fspath(path))
            except TypeError:  # a descriptor
                return None
            return path[len(prefix):] if path.startswith(prefix) else None

        def on_path(module, name, tally):
            real = getattr(module, name)

            def wrapped(path, *args, **kwargs):
                if rel(path) is not None:
                    self.by[rel(path)][tally] += 1
                return real(path, *args, **kwargs)

            monkeypatch.setattr(module, name, wrapped)

        real_open, real_fstat, real_close = os.open, os.fstat, os.close

        def os_open(path, *args, **kwargs):
            fd = real_open(path, *args, **kwargs)
            if rel(path) is not None:
                self.fds[fd] = rel(path)
                self.by[rel(path)]["open"] += 1
            return fd

        def os_fstat(fd):
            if fd in self.fds:
                self.by[self.fds[fd]]["fstat"] += 1
            return real_fstat(fd)

        def os_close(fd):
            if fd in self.fds:
                self.by[self.fds.pop(fd)]["close"] += 1
            return real_close(fd)

        monkeypatch.setattr(os, "open", os_open)
        monkeypatch.setattr(os, "fstat", os_fstat)
        monkeypatch.setattr(os, "close", os_close)
        on_path(os, "stat", "path_stat")
        on_path(os, "lstat", "path_stat")
        on_path(io, "open", "io_open")
        on_path(builtins, "open", "io_open")
        on_path(volsync_io, "ReadaheadReader", "readahead")

        real_hash = TreeBackup._hash_file

        def hash_file(backup, path, rel_, st, stats):
            self.current = rel_
            return real_hash(backup, path, rel_, st, stats)

        monkeypatch.setattr(TreeBackup, "_hash_file", hash_file)
        real_start = threading.Thread.start

        def start(thread):
            if thread.name == "vtpk-readahead":
                self.by[self.current]["thread"] += 1
            return real_start(thread)

        monkeypatch.setattr(threading.Thread, "start", start)


def watch_hash_phase(monkeypatch, root, then=None) -> dict:
    """-> a box that holds ``calls`` once the next backup's walk is
    over: the walk's own calls are not counted. ``then(calls)`` runs
    after the walk too."""
    box = {}

    def start():
        box["calls"] = Calls(monkeypatch, root)
        if then is not None:
            then(box["calls"])

    _after_the_walk(monkeypatch, start)
    return box


def watched_backup(monkeypatch, root, then=None, hasher=None):
    box = watch_hash_phase(monkeypatch, root, then)
    out = backup_4k(root, hasher)
    monkeypatch.undo()  # what the test itself asks is not counted
    return box["calls"], out


@pytest.mark.parametrize("rel", sorted({**HOST, **SHORT}))
def test_a_file_that_fits_one_segment_costs_one_descriptor(
        sized, rel, monkeypatch):
    """One open, one fstat, one close; nothing asked by name, no
    buffered file object, no read-ahead reader and no thread."""
    root, files = sized
    calls, (_, _, entries) = watched_backup(monkeypatch, root)
    assert dict(+calls.by[rel]) == {"open": 1, "fstat": 1, "close": 1}
    assert calls.fds == {}
    assert entries[rel]["content"] == reference_content(files[rel])
    assert entries[rel]["size"] == len(files[rel])
    assert entries[rel]["mtime_ns"] == (root / rel).lstat().st_mtime_ns


@pytest.mark.parametrize("rel", sorted(LONG))
def test_a_file_over_one_fill_keeps_the_readahead(sized, rel, monkeypatch):
    """From one byte over the fill: the native reader, the read-ahead
    thread behind its segments, and the closing stamp by name."""
    root, files = sized
    calls, (_, _, entries) = watched_backup(monkeypatch, root)
    seen = calls.by[rel]
    assert seen["readahead"] == 1 and seen["path_stat"] == 1
    assert seen["thread"] == 1
    assert not seen["open"] and not seen["fstat"] and not seen["io_open"]
    assert entries[rel]["content"] == reference_content(files[rel])
    assert len(entries[rel]["content"]) > 1
    assert entries[rel]["mtime_ns"] == (root / rel).lstat().st_mtime_ns


def test_the_fill_is_the_hashers(sized):
    """The rule reads the fill of the hasher in use: the one-chip
    engine's 32 MiB segment + max_size, what a hasher says through
    ``stream_segment_size`` (the mesh's is the larger)."""
    import jax

    from volsync_tpu.parallel.sharded_chunker import (MeshChunkHasher,
                                                      make_stream_mesh)

    def one_fill(hasher=None):
        repo = Repository.init(MemObjectStore(), chunker=CHUNKER_4K)
        return TreeBackup(repo, hasher=hasher)._one_fill

    one_chip = 32 * 1024 * 1024 + CHUNKER_4K["max_size"]
    assert one_fill() == one_chip
    assert one_fill(SmallFill(PARAMS_4K)) == FILL
    mesh = MeshChunkHasher(PARAMS_4K, make_stream_mesh(jax.devices()[:4]))
    assert one_fill(mesh) == chunker_mod._segment_source(
        None, PARAMS_4K, 32 * 1024 * 1024, mesh).target > one_chip


@pytest.fixture(scope="module")
def service():
    from volsync_tpu.service.server import MoverJaxServer

    with MoverJaxServer(params=PARAMS_4K, segment_size=FILL_SEGMENT) as srv:
        yield srv


def _engine(name, request):
    """-> (hasher, the files that take the read-ahead reader under it)."""
    if name == "one-chip":
        return DeviceChunkHasher(PARAMS_4K), {}
    if name == "small-fill":
        return SmallFill(PARAMS_4K), LONG
    if name == "mesh":
        import jax

        from volsync_tpu.parallel.sharded_chunker import (
            MeshChunkHasher, make_stream_mesh)
        return MeshChunkHasher(
            PARAMS_4K, make_stream_mesh(jax.devices()[:4])), {}
    from volsync_tpu.service.hasher import open_hasher

    srv = request.getfixturevalue("service")
    remote = open_hasher(f"127.0.0.1:{srv.port}", srv.token, "t", PARAMS_4K)
    request.addfinalizer(remote.close)
    if name == "service-small-fill":
        remote.stream_segment_size = lambda segment_size: FILL_SEGMENT
        return remote, LONG
    return remote, {}


@pytest.mark.parametrize("engine", ["one-chip", "small-fill", "mesh",
                                    "service", "service-small-fill"])
def test_the_snapshot_is_the_references_whichever_reader(
        sized, engine, request, monkeypatch):
    """Tree id, every blob id and the packs are one snapshot: the
    reference's cuts and hashlib's ids, whether a file was read through
    one descriptor or the read-ahead reader, in process, over the mesh
    hasher's fill or through ``service/hasher.py`` ``hash_file``."""
    root, files = sized
    hasher, long_files = _engine(engine, request)
    calls, (repo, manifest, entries) = watched_backup(
        monkeypatch, root, hasher=hasher)
    for rel, data in files.items():
        assert entries[rel]["content"] == reference_content(data), rel
        assert entries[rel]["size"] == len(data)
        assert b"".join(repo.read_blob(b)
                        for b in entries[rel]["content"]) == data
        assert bool(calls.by[rel]["readahead"]) == (rel in long_files), rel
        assert calls.by[rel]["open"] == (rel not in long_files), rel
    plain, expected, _ = backup_4k(root, DeviceChunkHasher(PARAMS_4K))
    assert manifest["tree"] == expected["tree"]
    assert repo.blob_ids() == plain.blob_ids()
    assert sorted(repo.store.list("data/")) \
        == sorted(plain.store.list("data/"))
    assert repo.check() == []


GROWN = {"h/tiny": 9_000, "s/short": 3 * FILL + 777}


@pytest.mark.parametrize("rel", sorted(GROWN))
def test_a_file_that_grows_after_the_walk_is_read_to_its_end(
        sized, rel, monkeypatch):
    """A host-path file past min_size is stored whole, as it was read;
    a short device-path file past one fill is read on serially and cut
    where the reference cuts it. Neither gets a reader or a thread it
    did not have, and both are described by the bytes read."""
    root, _ = sized
    new = np.random.default_rng([12, GROWN[rel]]).bytes(GROWN[rel])
    calls, (repo, _, entries) = watched_backup(
        monkeypatch, root,
        then=lambda calls: (root / rel).write_bytes(new))
    e = entries[rel]
    assert e["size"] == len(new)
    assert e["mtime_ns"] == (root / rel).lstat().st_mtime_ns
    if rel in HOST:
        assert e["content"] == [ref_blobid.blob_id(new)]
    else:
        assert e["content"] == reference_content(new)
        assert len(e["content"]) > 3
    assert b"".join(repo.read_blob(b) for b in e["content"]) == new
    seen = calls.by[rel]
    assert (seen["open"], seen["close"]) == (1, 1)
    assert not seen["readahead"] and not seen["thread"]
    assert not seen["path_stat"]


@pytest.mark.parametrize("rel", ["h/tiny", "s/short"])
def test_a_file_renamed_over_after_the_open_is_stamped_as_read(
        sized, rel, monkeypatch):
    """The entry's time is the inode's whose bytes were read, not that
    of whatever holds its name once the read is over."""
    root, files = sized
    victim, other = root / rel, root.parent / "other"
    os.utime(victim, ns=(1_600_000_000_000_000_000,) * 2)
    other.write_bytes(b"another file" * 1000)
    os.utime(other, ns=(1_700_000_000_000_000_000,) * 2)

    def rename_once_open(calls):
        opened = os.open  # Calls' wrapper

        def os_open(path, *args, **kwargs):
            fd = opened(path, *args, **kwargs)
            if os.fspath(path) == str(victim) and other.exists():
                os.replace(other, victim)
            return fd

        monkeypatch.setattr(os, "open", os_open)

    _, (repo, _, entries) = watched_backup(monkeypatch, root,
                                           then=rename_once_open)
    assert not other.exists()
    assert victim.lstat().st_mtime_ns == 1_700_000_000_000_000_000
    e = entries[rel]
    assert e["mtime_ns"] == 1_600_000_000_000_000_000
    assert e["size"] == len(files[rel])
    assert b"".join(repo.read_blob(b) for b in e["content"]) == files[rel]


def _open_descriptors():
    return len(os.listdir("/proc/self/fd"))


@pytest.mark.parametrize("victim, call", [(None, None),
                                          ("h/at_min", "read"),
                                          ("s/short", "readv")])
def test_no_descriptor_is_left_open(sized, victim, call, monkeypatch):
    """After a backup of the tree, and after a read that raises."""
    root, _ = sized
    backup_4k(root)  # the pools and the programs are up
    before = _open_descriptors()
    if victim is None:
        backup_4k(root)
        assert _open_descriptors() == before
        return

    def fail_the_read(calls):
        real = getattr(os, call)

        def failing(fd, *args):
            if calls.fds.get(fd) == victim:
                raise OSError(errno.EIO, "the disk gave up")
            return real(fd, *args)

        monkeypatch.setattr(os, call, failing)

    repo = Repository.init(MemObjectStore(), chunker=CHUNKER_4K)
    box = watch_hash_phase(monkeypatch, root, fail_the_read)
    with pytest.raises(OSError, match="the disk gave up"):
        TreeBackup(repo, hasher=SmallFill(PARAMS_4K)).run(root)
    assert repo.list_snapshots() == []
    seen = box["calls"].by[victim]
    assert (seen["open"], seen["close"]) == (1, 1)
    assert box["calls"].fds == {}
    monkeypatch.undo()
    assert _open_descriptors() == before


@pytest.mark.parametrize("hasher, direct", [
    (None, len(SIZES)), ("small-fill", len(HOST) + len(SHORT))])
def test_reads_direct_counts_the_files_that_took_one_descriptor(
        sized, hasher, direct):
    root, _ = sized
    obs.reset_spans()
    backup_4k(root, DeviceChunkHasher(PARAMS_4K) if hasher is None
              else SmallFill(PARAMS_4K))
    counted = obs.counter_totals()
    assert counted["backup.files_changed"] == len(SIZES)
    assert counted["backup.reads_direct"] == direct
