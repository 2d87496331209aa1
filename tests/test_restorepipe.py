"""Restore data plane (engine/restorepipe.py + repo/packcache.py).

The pipelined restore overlaps pack-granular fetches, device-batched
verification, and positional writes behind the same TreeRestore API the
serial path uses, so the contract is strong:

  * golden byte-identity — the destination tree a pipelined restore
    materializes (content, modes, mtimes, symlinks, hardlinks, sparse
    allocation) is identical to the serial per-blob oracle's;
  * idempotence — delete_extra and the skip-unchanged heuristic behave
    exactly as the serial path (same stats);
  * integrity — a corrupted pack segment is rejected by the
    device-side verify BEFORE any byte of that batch reaches disk, and
    a failed restore leaves no partial file behind;
  * single-flight — N restores of one snapshot through a shared
    PackCache cost each pack ONE store GET for the whole group.
"""

import os
from pathlib import Path

import numpy as np
import pytest

from volsync_tpu import envflags
from volsync_tpu.analysis import lockcheck
from volsync_tpu.engine import RestoreGroup, TreeBackup, TreeRestore
from volsync_tpu.engine.restore import restore_snapshot
from volsync_tpu.objstore.store import LatencyStore, MemObjectStore
from volsync_tpu.repo import crypto
from volsync_tpu.repo.packcache import PackCache
from volsync_tpu.repo.repository import Repository

CHUNKER = {"min_size": 4096, "avg_size": 32768, "max_size": 65536,
           "seed": 7, "align": 4096}


@pytest.fixture(autouse=True)
def _lockcheck_armed(monkeypatch):
    """The whole restore-pipeline suite runs with the lock-order/race
    detector on (same contract as the backup pipeline suite)."""
    monkeypatch.setenv("VOLSYNC_TPU_LOCKCHECK", "1")
    lockcheck.reset()
    yield
    assert lockcheck.violations() == []


def _corpus(tmp_path) -> Path:
    """The pipeline-test corpus: deep tree, sparse file, empty file,
    duplicate content (dedup), symlink, hardlink."""
    rng = np.random.RandomState(5)
    src = tmp_path / "src"
    src.mkdir()
    (src / "a.bin").write_bytes(rng.bytes(150_000))
    (src / "dup.bin").write_bytes((src / "a.bin").read_bytes())
    (src / "empty").write_bytes(b"")
    sparse = bytearray(300_000)
    sparse[:512] = rng.bytes(512)
    sparse[200_000:200_100] = rng.bytes(100)
    (src / "sparse.bin").write_bytes(bytes(sparse))
    os.symlink("a.bin", src / "link")
    os.link(src / "a.bin", src / "hard.bin")
    deep = src
    for i in range(24):  # deep tree: the walkers' any-depth guarantee
        deep = deep / f"d{i}"
        deep.mkdir()
        (deep / "leaf.bin").write_bytes(rng.bytes(3_000 + 17 * i))
    return src


def _backup(store, src, pack_target=64 * 1024):
    repo = Repository.init(store, chunker=CHUNKER)
    repo.PACK_TARGET = pack_target
    snap, _ = TreeBackup(repo).run(src)
    assert snap
    return snap


def _entries(root: Path):
    return sorted(p.relative_to(root)
                  for p in root.rglob("*"))


def _assert_trees_identical(a: Path, b: Path, *, blocks: bool = False):
    """Full-fidelity comparison: layout, content, symlink targets,
    modes, mtimes, hardlink grouping. ``blocks=True`` additionally
    requires identical sparse allocation — valid only when BOTH sides
    were written by a restore (a dense source never matches a holed
    destination)."""
    assert _entries(a) == _entries(b)
    inode_group_a: dict = {}
    inode_group_b: dict = {}
    for rel in _entries(a):
        pa, pb = a / rel, b / rel
        sa, sb = pa.lstat(), pb.lstat()
        assert (sa.st_mode == sb.st_mode
                and sa.st_mtime_ns == sb.st_mtime_ns), rel
        if pa.is_symlink():
            assert os.readlink(pa) == os.readlink(pb), rel
        elif pa.is_file():
            assert pa.read_bytes() == pb.read_bytes(), rel
            if blocks:
                # sparse parity: both restore paths hole the same
                # aligned zero pages, so allocation matches too
                assert sa.st_blocks == sb.st_blocks, rel
            inode_group_a.setdefault(sa.st_ino, set()).add(rel)
            inode_group_b.setdefault(sb.st_ino, set()).add(rel)
    assert (sorted(map(sorted, inode_group_a.values()))
            == sorted(map(sorted, inode_group_b.values()))), \
        "hardlink grouping differs"


# -- golden byte-identity ----------------------------------------------------

def test_golden_pipelined_equals_serial(tmp_path):
    src = _corpus(tmp_path)
    store = MemObjectStore()
    _backup(store, src)
    d_serial, d_pipe = tmp_path / "serial", tmp_path / "pipe"
    r1 = Repository.open(store)
    r2 = Repository.open(store)
    with r1.lock(exclusive=False):
        r1.load_index()
        snap_id, manifest = r1.select_snapshot()
        st_serial = TreeRestore(r1, pipeline=False)._run_locked(
            snap_id, manifest, d_serial)
    with r2.lock(exclusive=False):
        r2.load_index()
        snap_id, manifest = r2.select_snapshot()
        st_pipe = TreeRestore(r2, pipeline=True)._run_locked(
            snap_id, manifest, d_pipe)
    assert st_serial == st_pipe
    _assert_trees_identical(d_serial, d_pipe, blocks=True)
    _assert_trees_identical(src, d_pipe)


def _restore(store, dest, *, pipeline):
    repo = Repository.open(store)
    with repo.lock(exclusive=False):
        repo.load_index()
        snap_id, manifest = repo.select_snapshot()
        TreeRestore(repo, pipeline=pipeline)._run_locked(
            snap_id, manifest, dest)
    return repo, manifest


@pytest.mark.parametrize("sparse", ["1", "0"], ids=["sparse", "dense"])
def test_a_repeated_blob_is_scanned_once_and_lands_everywhere(
        tmp_path, monkeypatch, sparse):
    """A file whose two halves are the same blobs, holes among them:
    each blob's runs are derived once and applied at every place it
    lands, so the tree has the serial path's allocation, every
    placement is counted, and the dense ones are exactly the placements
    of blobs without an aligned zero page (all of them with sparse
    writes off)."""
    import json

    from volsync_tpu.obs import counter_totals, reset_spans

    monkeypatch.setenv("VOLSYNC_SPARSE", sparse)
    rng = np.random.RandomState(17)
    half = b"".join([
        rng.bytes(64 * 1024), bytes(128 * 1024), rng.bytes(40 * 1024),
        bytes(8 * 1024), rng.bytes(20 * 1024), bytes(5000),
        rng.bytes(300_000 - 5000)])
    src = tmp_path / "src"
    src.mkdir()
    (src / "twice.bin").write_bytes(half + half)
    (src / "tail.bin").write_bytes(rng.bytes(10_000) + bytes(2_000))
    store = MemObjectStore()
    _backup(store, src)
    d_serial, d_pipe = tmp_path / "serial", tmp_path / "pipe"
    _restore(store, d_serial, pipeline=False)
    reset_spans()
    repo, manifest = _restore(store, d_pipe, pipeline=True)
    _assert_trees_identical(d_serial, d_pipe, blocks=True)
    _assert_trees_identical(src, d_pipe)

    def has_zero_page(blob: bytes) -> bool:
        return any(blob[i:i + 4096] == bytes(4096)
                   for i in range(0, len(blob) - 4095, 4096))

    content = [blob_id
               for e in json.loads(
                   repo.read_blob(manifest["tree"]))["entries"]
               for blob_id in e.get("content", [])]
    holed = {b for b in set(content) if has_zero_page(repo.read_blob(b))}
    assert holed and len(set(content)) < len(content)  # the shape holds
    if sparse == "1":
        # and the holes are holes: the two 128 KiB ones at the least
        assert d_pipe.joinpath("twice.bin").stat().st_blocks * 512 \
            <= 2 * len(half) - 2 * 128 * 1024
    counts = counter_totals()
    assert counts["restore.blobs"] == len(set(content))
    assert counts["restore.writes"] == len(content)
    assert counts["restore.writes_dense"] == (
        sum(b not in holed for b in content) if sparse == "1"
        else len(content))
    assert counts["restore.bytes_restored"] == 2 * len(half) + 12_000


def test_skip_unchanged_and_delete_extra(tmp_path):
    src = _corpus(tmp_path)
    store = MemObjectStore()
    _backup(store, src)
    dst = tmp_path / "dst"
    first = restore_snapshot(Repository.open(store), dst)
    assert first["files"] > 0 and first["skipped"] == 0
    # drop extras into the tree; a second pipelined restore must skip
    # every unchanged file and delete the extras
    (dst / "extra.bin").write_bytes(b"x" * 100)
    (dst / "d0" / "extra2").write_bytes(b"y")
    second = restore_snapshot(Repository.open(store), dst)
    assert second["files"] == 0
    assert second["skipped"] == first["files"]
    assert second["deleted"] == 2
    _assert_trees_identical(src, dst)


# -- one descriptor a file (PR 46) --------------------------------------------

def _flat_corpus(root: Path) -> Path:
    """A small tree for the destination-state cases: plain files, a
    file ending in zero pages, an empty one, a holed one, a
    duplicate, a subdirectory."""
    rng = np.random.RandomState(23)
    src = root / "src"
    src.mkdir()
    (src / "a.bin").write_bytes(rng.bytes(150_000))
    (src / "dup.bin").write_bytes((src / "a.bin").read_bytes())
    (src / "empty").write_bytes(b"")
    (src / "zero_tail.bin").write_bytes(rng.bytes(20_000) + bytes(90_000))
    holed = bytearray(200_000)
    holed[100_000:100_300] = rng.bytes(300)
    (src / "holed.bin").write_bytes(bytes(holed))
    (src / "sub").mkdir()
    (src / "sub" / "leaf.bin").write_bytes(rng.bytes(40_000))
    os.chmod(src / "sub" / "leaf.bin", 0o440)
    return src


@pytest.fixture(scope="module")
def flat(tmp_path_factory):
    src = _flat_corpus(tmp_path_factory.mktemp("flat"))
    store = MemObjectStore()
    _backup(store, src)
    return src, store


def _restore_stats(store, dest, *, pipeline, delete_extra=True) -> dict:
    repo = Repository.open(store)
    with repo.lock(exclusive=False):
        repo.load_index()
        snap_id, manifest = repo.select_snapshot()
        return TreeRestore(repo, pipeline=pipeline)._run_locked(
            snap_id, manifest, dest, delete_extra=delete_extra)


def _there_identical(store, dest, outside):
    _restore_stats(store, dest, pipeline=False)


def _there_differing(store, dest, outside):
    dest.mkdir()
    (dest / "a.bin").write_bytes(b"stale" * 50_000)  # longer than a.bin
    (dest / "empty").write_bytes(b"no longer")
    os.utime(dest / "a.bin", ns=(1, 1))


def _there_hardlinked(store, dest, outside):
    dest.mkdir()
    outside.write_bytes(b"precious")
    os.link(outside, dest / "a.bin")


def _there_symlink(store, dest, outside):
    dest.mkdir()
    outside.write_bytes(b"precious")
    os.symlink(outside, dest / "a.bin")


def _there_directory(store, dest, outside):
    (dest / "a.bin" / "inner").mkdir(parents=True)
    (dest / "a.bin" / "inner" / "x").write_bytes(b"x")


def _there_fifo(store, dest, outside):
    dest.mkdir()
    os.mkfifo(dest / "a.bin")


def _there_populated(store, dest, outside):
    _restore_stats(store, dest, pipeline=False)
    for extra, body in (("kept.bin", b"k" * 5_000), ("sub/kept2", b"k")):
        (dest / extra).write_bytes(body)
        os.utime(dest / extra, ns=(7, 7))  # the same on both sides
    (dest / "zero_tail.bin").write_bytes(b"other")
    os.unlink(dest / "holed.bin")


@pytest.mark.parametrize("there, delete_extra", [
    (None, True),
    (_there_identical, True),
    (_there_differing, True),
    (_there_hardlinked, True),
    (_there_symlink, True),
    (_there_directory, True),
    (_there_fifo, True),
    (_there_populated, False),
    (_there_populated, True),
], ids=["fresh", "identical", "differing", "hardlinked", "symlink",
        "directory", "fifo", "populated_keep_extra",
        "populated_delete_extra"])
def test_pipeline_matches_serial_over(flat, tmp_path, there, delete_extra):
    """Whatever stands at the destination, the pipeline leaves the tree
    the serial oracle leaves (content, modes, mtimes, allocation) and
    the same stats; a file outside the destination that a target was
    linked to is not written through."""
    src, store = flat
    got = {}
    for name, pipeline in (("serial", False), ("pipe", True)):
        dest = tmp_path / name / "dst"
        dest.parent.mkdir()
        outside = tmp_path / name / "outside.bin"
        if there is not None:
            there(store, dest, outside)
        got[name] = _restore_stats(store, dest, pipeline=pipeline,
                                   delete_extra=delete_extra)
        if outside.exists():
            assert outside.read_bytes() == b"precious"
    assert got["serial"] == got["pipe"]
    d_serial, d_pipe = tmp_path / "serial" / "dst", tmp_path / "pipe" / "dst"
    _assert_trees_identical(d_serial, d_pipe, blocks=True)
    if there is _there_identical:
        assert got["pipe"]["files"] == 0 and got["pipe"]["skipped"] == 6
    if delete_extra:
        _assert_trees_identical(src, d_pipe)
    else:
        assert (d_pipe / "kept.bin").read_bytes() == b"k" * 5_000
        assert (d_pipe / "sub" / "kept2").read_bytes() == b"k"
    # a trailing hole is a hole and an empty file is empty, either way
    tail = (d_pipe / "zero_tail.bin").stat()
    assert tail.st_size == 110_000
    assert tail.st_blocks * 512 <= 20_000 + 2 * 4096
    assert (d_pipe / "empty").stat().st_size == 0


class _FdWatch:
    """``os.open`` / ``os.close`` / builtin ``open`` wrapped: every open
    of a path under ``root`` is recorded, and how many descriptors from
    ``os.open`` were held on such paths at once."""

    def __init__(self, monkeypatch, root: Path):
        import builtins

        self.root = str(root)
        self.opened: list[str] = []
        self.held: dict[int, str] = {}
        self.most_held = 0
        real_open, real_close, real_builtin = os.open, os.close, open

        def os_open(path, *args, **kwargs):
            fd = real_open(path, *args, **kwargs)
            if os.fspath(path).startswith(self.root):
                self.opened.append(os.fspath(path))
                self.held[fd] = os.fspath(path)
                self.most_held = max(self.most_held, len(self.held))
            return fd

        def os_close(fd):
            self.held.pop(fd, None)
            return real_close(fd)

        def builtin_open(file, *args, **kwargs):
            if (isinstance(file, (str, os.PathLike))
                    and os.fspath(file).startswith(self.root)):
                self.opened.append(os.fspath(file))
            return real_builtin(file, *args, **kwargs)

        monkeypatch.setattr(os, "open", os_open)
        monkeypatch.setattr(os, "close", os_close)
        monkeypatch.setattr(builtins, "open", builtin_open)


def test_fresh_destination_opens_each_target_once(flat, tmp_path,
                                                  monkeypatch):
    from volsync_tpu.obs import counter_totals, reset_spans

    src, store = flat
    dest = tmp_path / "dst"
    reset_spans()
    with monkeypatch.context() as patched:
        watch = _FdWatch(patched, dest)
        stats = _restore_stats(store, dest, pipeline=True)
    files = sorted(str(p) for p in dest.rglob("*") if p.is_file())
    assert sorted(watch.opened) == files and len(files) == stats["files"]
    assert watch.held == {}
    counts = counter_totals()
    assert counts["restore.opens"] == counts["restore.files_finished"] \
        == stats["files"]
    _assert_trees_identical(src, dest)


def test_open_descriptors_are_bounded(tmp_path, monkeypatch):
    """Files that share their tails stay unfinished side by side (the
    shared blobs land in all of them at once, each one's own head
    arrives later): with the bound at 2 no more than 2 targets are
    ever open, the evicted ones reopen, and the tree is the source's."""
    from volsync_tpu.engine import restorepipe
    from volsync_tpu.obs import counter_totals, reset_spans

    rng = np.random.RandomState(29)
    src = tmp_path / "src"
    src.mkdir()
    shared = rng.bytes(400_000)
    for i in range(8):
        # a head of whole pages: the chunker cuts on the page grid
        (src / f"f{i}.bin").write_bytes(rng.bytes(17 * 4096) + shared)
    store = MemObjectStore()
    _backup(store, src)
    dest = tmp_path / "dst"
    monkeypatch.setattr(restorepipe, "_MAX_OPEN", 2)
    reset_spans()
    with monkeypatch.context() as patched:
        watch = _FdWatch(patched, dest)
        stats = _restore_stats(store, dest, pipeline=True)
    assert stats["files"] == 8
    assert watch.most_held == 2 and watch.held == {}
    counts = counter_totals()
    assert counts["restore.files_finished"] == 8
    assert counts["restore.opens"] == len(watch.opened) > 8  # reopened
    d_serial = tmp_path / "serial"
    _restore_stats(store, d_serial, pipeline=False)
    _assert_trees_identical(d_serial, dest, blocks=True)
    _assert_trees_identical(src, dest)


def test_a_path_that_appears_after_the_walk_is_claimed(flat, tmp_path,
                                                       monkeypatch):
    """A name the walk found absent exists by the time its first blob
    lands, hardlinked to a file outside the destination: the exclusive
    create refuses it, it is cleared like any present name, restored
    with its final content, and the outside file is not written
    through."""
    from volsync_tpu.engine import restorepipe

    src, store = flat
    dest = tmp_path / "dst"
    outside = tmp_path / "outside.bin"
    outside.write_bytes(b"precious")
    planned = restorepipe._plan

    def plan_then_intrude(*args):
        out = planned(*args)
        os.link(outside, dest / "a.bin")
        os.link(outside, dest / "empty")
        return out

    monkeypatch.setattr(restorepipe, "_plan", plan_then_intrude)
    stats = _restore_stats(store, dest, pipeline=True)
    assert stats["files"] == 6 and stats["skipped"] == 0
    assert outside.read_bytes() == b"precious"
    assert outside.stat().st_nlink == 1
    _assert_trees_identical(src, dest)


def test_pipeline_env_flag(monkeypatch):
    repo = Repository.init(MemObjectStore())
    monkeypatch.setenv("VOLSYNC_RESTORE_PIPELINE", "0")
    assert TreeRestore(repo).pipelined is False
    assert envflags.restore_pipeline_enabled() is False
    monkeypatch.setenv("VOLSYNC_RESTORE_PIPELINE", "1")
    assert TreeRestore(repo).pipelined is True
    assert TreeRestore(repo, pipeline=False).pipelined is False


# -- integrity ---------------------------------------------------------------

def _fds_into(root: Path) -> list[str]:
    """What ``/proc/self/fd`` holds under ``root`` (a deleted file's
    link still starts with its path)."""
    held = []
    for fd in os.listdir("/proc/self/fd"):
        try:
            where = os.readlink(f"/proc/self/fd/{fd}")
        except OSError:
            continue  # the listing's own descriptor, closed by now
        if where.startswith(str(root)):
            held.append(where)
    return held


def test_corrupt_pack_rejected_before_any_write(tmp_path):
    """Seeded corrupt pack: device-side verify rejects the batch and
    the failed restore leaves NOTHING behind — not even the claimed
    empty target."""
    rng = np.random.RandomState(9)
    src = tmp_path / "src"
    src.mkdir()
    (src / "only.bin").write_bytes(rng.bytes(180_000))
    store = MemObjectStore()
    _backup(store, src)

    repo = Repository.open(store)
    import json
    _, manifest = repo.list_snapshots()[0]
    tree = json.loads(repo.read_blob(manifest["tree"]))
    blob0 = tree["entries"][0]["content"][0]
    entry = repo._entry(blob0)
    key = f"data/{entry.pack[:2]}/{entry.pack}"
    body = bytearray(store.get(key))
    body[entry.offset + 5] ^= 0xFF  # flip one byte inside the segment
    store.put(key, bytes(body))

    dst = tmp_path / "dst"
    with pytest.raises(crypto.IntegrityError):
        restore_snapshot(Repository.open(store), dst)
    assert list(dst.rglob("*")) == [], \
        "failed restore left partial state behind"


def test_failed_restore_keeps_complete_files_only(tmp_path):
    """Multi-file restore with one corrupted pack: files whose content
    verified fully may remain (and must be intact); the file fed by
    the bad pack is cleaned up, never left partial."""
    rng = np.random.RandomState(11)
    src = tmp_path / "src"
    src.mkdir()
    for i in range(6):
        (src / f"f{i}.bin").write_bytes(rng.bytes(90_000 + i * 13))
    store = MemObjectStore()
    _backup(store, src)
    # corrupt the LAST data pack so earlier batches verify and write
    repo = Repository.open(store)
    import json
    _, manifest = repo.list_snapshots()[0]
    tree = json.loads(repo.read_blob(manifest["tree"]))
    last_blob = tree["entries"][-1]["content"][-1]
    entry = repo._entry(last_blob)
    key = f"data/{entry.pack[:2]}/{entry.pack}"
    body = bytearray(store.get(key))
    body[entry.offset + entry.length // 2] ^= 0xFF  # inside the payload
    store.put(key, bytes(body))

    dst = tmp_path / "dst"
    with pytest.raises(crypto.IntegrityError):
        restore_snapshot(Repository.open(store), dst)
    for p in dst.rglob("*"):
        if p.is_file():
            assert p.read_bytes() == (src / p.name).read_bytes(), \
                f"partial file survived a failed restore: {p.name}"
    assert _fds_into(dst) == [], "failed restore left descriptors open"


# -- shared cache / single-flight --------------------------------------------

def test_restore_group_single_flight(tmp_path):
    src = _corpus(tmp_path)
    mem = MemObjectStore()
    _backup(mem, src)
    npacks = len(list(mem.list("data/")))
    assert npacks > 1
    counted = LatencyStore(mem)  # zero latency: pure op counter
    group = RestoreGroup()
    dests = [tmp_path / f"dst{i}" for i in range(3)]
    for d in dests:
        group.add(Repository.open(counted), d)
    results = group.run()
    assert all(r is not None and r["files"] > 0 for r in results)
    for d in dests:
        _assert_trees_identical(src, d)
    # every pack fetched ONCE for the whole group (whole-object GETs);
    # per-restore tree-blob reads go through get_range and don't count
    stats = group.stats()[0]
    assert stats["misses"] == npacks
    assert stats["hits"] >= 2 * npacks  # followers + LRU hits
    assert counted.pack_fetches == npacks, \
        "single-flight did not dedup concurrent pack fetches"


def test_pack_cache_lru_eviction_and_budget(tmp_path):
    src = _corpus(tmp_path)
    mem = MemObjectStore()
    _backup(mem, src)
    packs = sorted(k.rsplit("/", 1)[1] for k in mem.list("data/"))
    sizes = {p: mem.size(f"data/{p[:2]}/{p}") for p in packs}
    budget = max(sizes.values()) + min(sizes.values())  # ~2 packs fit
    cache = PackCache(mem, budget_bytes=budget)
    for p in packs:
        cache.get_pack(p)
    st = cache.stats()
    assert st["misses"] == len(packs)
    assert st["evictions"] > 0
    assert st["bytes_cached"] <= budget
    # the newest pack survived the eviction sweep: re-read is a hit
    newest = next(reversed(cache._lru))
    cache.get_pack(newest)
    after = cache.stats()
    assert after["hits"] == st["hits"] + 1
    assert after["bytes_cached"] <= budget


def test_pack_cache_oversized_body_not_cached():
    mem = MemObjectStore()
    pack_id = "ab" * 32
    mem.put(f"data/{pack_id[:2]}/{pack_id}", b"z" * 4096)
    cache = PackCache(mem, budget_bytes=100)
    assert cache.get_pack(pack_id) == b"z" * 4096
    st = cache.stats()
    assert st["packs_cached"] == 0 and st["evictions"] == 0
    # second read must re-fetch (miss), not corrupt the budget
    assert cache.get_pack(pack_id) == b"z" * 4096
    assert cache.stats()["misses"] == 2


# -- read-repair (mirror heal during restore) --------------------------------

class _MirrorCountingStore:
    """Pass-through shim counting ``mirror/`` GETs — the read-repair
    contract is ONE mirror fetch per corrupt pack, however many blobs
    or verify batches that pack spans."""

    def __init__(self, inner):
        self.inner = inner
        self.mirror_gets = 0

    def get(self, key):
        if key.startswith("mirror/"):
            self.mirror_gets += 1
        return self.inner.get(key)

    def __getattr__(self, name):
        return getattr(self.inner, name)


def _corrupt_first_file_blob(store):
    """Flip one payload byte of the pack holding the first file blob;
    returns (pack_id, pack_key)."""
    import json

    repo = Repository.open(store)
    _, manifest = repo.list_snapshots()[0]
    tree = json.loads(repo.read_blob(manifest["tree"]))
    blob0 = next(e for e in tree["entries"]
                 if e["type"] == "file" and e["content"])["content"][0]
    entry = repo._entry(blob0)
    key = f"data/{entry.pack[:2]}/{entry.pack}"
    body = bytearray(store.get(key))
    body[entry.offset + 5] ^= 0xFF
    store.put(key, bytes(body))
    return entry.pack, key


def test_read_repair_heals_corrupt_primary_from_mirror(tmp_path,
                                                       monkeypatch):
    """Corrupt primary + healthy mirror: the restore is byte-identical,
    costs exactly ONE mirror re-fetch, and leaves the primary HEALED in
    the store (verify-then-replace, the repo/scrub.py protocol)."""
    import hashlib

    monkeypatch.setenv("VOLSYNC_PACK_COPIES", "2")
    src = _corpus(tmp_path)
    mem = MemObjectStore()
    _backup(mem, src)
    assert list(mem.list("mirror/")), "copies=2 backup wrote no mirrors"
    pack_id, key = _corrupt_first_file_blob(mem)

    counted = _MirrorCountingStore(mem)
    dst = tmp_path / "dst"
    st = restore_snapshot(Repository.open(counted), dst)
    assert st["files"] > 0
    _assert_trees_identical(src, dst)
    assert counted.mirror_gets == 1, \
        "read-repair must fetch the mirror exactly once per corrupt pack"
    # the primary was healed in place: whole-blob hash re-derives the id
    assert hashlib.sha256(mem.get(key)).hexdigest() == pack_id


def test_read_repair_both_copies_corrupt_raises_no_partial(tmp_path,
                                                           monkeypatch):
    """No healthy copy anywhere: the classic integrity contract holds —
    IntegrityError before any byte of the batch lands, zero partial
    files behind."""
    monkeypatch.setenv("VOLSYNC_PACK_COPIES", "2")
    rng = np.random.RandomState(13)
    src = tmp_path / "src"
    src.mkdir()
    (src / "only.bin").write_bytes(rng.bytes(180_000))
    mem = MemObjectStore()
    _backup(mem, src)
    pack_id, _ = _corrupt_first_file_blob(mem)
    mbody = bytearray(mem.get(f"mirror/{pack_id}"))
    mbody[0] ^= 0xFF  # mirror rot: sha no longer re-derives the id
    mem.put(f"mirror/{pack_id}", bytes(mbody))

    dst = tmp_path / "dst"
    with pytest.raises(crypto.IntegrityError):
        restore_snapshot(Repository.open(mem), dst)
    assert [p for p in dst.rglob("*") if p.is_file()] == [], \
        "failed restore left partial files behind"
    assert _fds_into(dst) == [], "failed restore left descriptors open"


def test_read_repair_disabled_by_flag(tmp_path, monkeypatch):
    """VOLSYNC_SCRUB_READ_REPAIR=0: a healthy mirror exists but the
    restore must not touch it — corruption raises exactly as before the
    feature existed."""
    monkeypatch.setenv("VOLSYNC_PACK_COPIES", "2")
    rng = np.random.RandomState(17)
    src = tmp_path / "src"
    src.mkdir()
    (src / "only.bin").write_bytes(rng.bytes(150_000))
    mem = MemObjectStore()
    _backup(mem, src)
    _corrupt_first_file_blob(mem)

    monkeypatch.setenv("VOLSYNC_SCRUB_READ_REPAIR", "0")
    counted = _MirrorCountingStore(mem)
    dst = tmp_path / "dst"
    with pytest.raises(crypto.IntegrityError):
        restore_snapshot(Repository.open(counted), dst)
    assert counted.mirror_gets == 0
    assert [p for p in dst.rglob("*") if p.is_file()] == []
