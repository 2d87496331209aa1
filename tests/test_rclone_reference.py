"""The rclone mover, both directions, against plain references
(``benchmark/reference/mirror.py``: what a correct mirror's bucket
holds, ``os`` + ``json`` + ``hashlib``; ``treecmp.py``: two trees;
``blobid.py``: hashlib ids): a small volume in the two states of the
benchmark's cell ``rclone-smallfiles.sync`` is synced source -> bucket
-> destination through ``movers/rclone/entry.rclone_entrypoint``, as
the cell does at its size, and the configuration's guarantees (a)-(d)
are held one by one. CPU, small sizes, seeded."""

import ast
import base64
import os
import time
from pathlib import Path

import numpy as np
import pytest

from benchmark import volumes
from benchmark.drivers import rclone_sync
from benchmark.reference import blobid as ref_blobid
from benchmark.reference import mirror, treecmp
from volsync_tpu.obs import (copies_by_site, counter_totals, reset_copies,
                             reset_spans, span_totals)

SHAPE = {"files": [], "small": {"count": 24, "lo": 1024, "hi": 65536,
                                "dirs": 3, "size_seed": 2}}
PARAMS = {"rewrite_share": 0.05, "remove_share": 0.01}
SEED = 2147483659
ENTRY_SPANS = ("rclone.scan", "rclone.hash", "rclone.lease", "rclone.list",
               "rclone.transfer_wait", "rclone.index_read",
               "rclone.index_write", "rclone.sweep", "rclone.delete_local",
               "rclone.place", "rclone.apply_meta")


def _conf(bucket: Path) -> bytes:
    return rclone_sync.rclone_conf(f"file://{bucket}")


def _bucket(bucket: Path):
    """(object names, index entries) as the bucket holds them now, read
    with ``os`` and ``json`` alone."""
    root = bucket / rclone_sync.PREFIX
    names = sorted(os.listdir(root / "objects"))
    return names, mirror.parse_index(lambda key: (root / key).read_bytes())


def _bucket_diff(bucket: Path, tree: Path) -> dict:
    return mirror.compare_bucket(*_bucket(bucket), tree)


def _clean(diff: dict) -> bool:
    return not any(v for k, v in diff.items()
                   if k not in ("compared", "digests"))


def _sync(direction: str, bucket: Path, data: Path) -> int:
    return rclone_sync.run_entry(direction, _conf(bucket), data)


def _make_states(work: Path):
    a, b = work / "a", work / "b"
    files = volumes.write(a, SHAPE, SEED)
    # what a size law does not draw: an empty file, a file of exactly
    # one page, a symlink, an empty directory, a mode of its own
    (a / "empty").write_bytes(b"")
    (a / "page").write_bytes(np.random.default_rng(SEED).bytes(4096))
    os.chmod(a / "page", 0o640)
    os.symlink("small/d00", a / "link")
    (a / "hollow").mkdir()
    return rclone_sync.derive_states(a, b, files, PARAMS, SEED)


@pytest.fixture(scope="module")
def mirrored(tmp_path_factory):
    """First sync of state A, the churned sync (B), the sync back (A),
    each direction through the entry; after each: the return codes, the
    bucket against the reference, the destination against the source
    state, and the counters the two calls left."""
    work = tmp_path_factory.mktemp("mirror")
    states = _make_states(work)
    bucket, dest = work / "bucket", work / "d"
    stages = {}
    for stage, state in (("first", states[0]), ("churned", states[1]),
                         ("back", states[0])):
        reset_spans()
        rcs = (_sync("source", bucket, state["root"]),
               _sync("destination", bucket, dest))
        stages[stage] = {
            "rcs": rcs, "bucket": _bucket_diff(bucket, state["root"]),
            "dest": treecmp.compare(state["root"], dest),
            "counters": counter_totals(), "spans": span_totals(),
            "state": state}
    return stages, states, bucket, dest


def test_the_reference_imports_nothing_of_the_program():
    tree = ast.parse(Path(mirror.__file__).read_text())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names |= {alias.name for alias in node.names}
        elif isinstance(node, ast.ImportFrom):
            names.add(node.module)
    assert names == {"__future__", "base64", "json", "os", "stat",
                     "benchmark.reference.blobid"}


@pytest.mark.parametrize("stage", ["first", "churned", "back"])
def test_the_bucket_is_the_reference_mirror(mirrored, stage):
    """(a): the index lists exactly the tree with its metadata, every
    file's object is there under its hashlib checksum, nothing else."""
    got = mirrored[0][stage]
    assert got["rcs"] == (0, 0)
    assert _clean(got["bucket"]), got["bucket"]
    names, entries = _bucket(mirrored[2])
    if stage == "back":  # the bucket holds the last state
        assert set(names) == mirror.expected_objects(got["state"]["root"])
        assert {"empty", "page", "link", "hollow"} <= set(entries)


@pytest.mark.parametrize("stage", ["first", "churned", "back"])
def test_the_destination_is_the_source_tree(mirrored, stage):
    """(b): byte for byte, with mode and mtime, and nothing else."""
    diff = mirrored[0][stage]["dest"]
    assert _clean(diff), {k: v for k, v in diff.items() if k != "digests"}
    assert diff["compared"] >= 24


@pytest.mark.parametrize("stage", ["churned", "back"])
def test_only_what_differs_moves_and_everything_is_hashed(mirrored, stage):
    """(c): both sides hash every file on every sync; the rewritten
    file and the added one move, the removed one is deleted, on both
    sides; nothing else does."""
    got = mirrored[0][stage]
    c, state = got["counters"], got["state"]
    held = len(state["files"]) + 2  # "empty" and "page"
    moved = len(state["other"]) + 1  # the rewritten, and the one added
    assert c["rclone.files_uploaded"] == c["rclone.files_fetched"] == moved
    assert c["rclone.objects_deleted"] == moved
    assert c["rclone.local_deleted"] == 1
    assert c["rclone.files_skipped"] == 2 * (held - moved)
    # the source's pass, the destination's local pass (the file added
    # is not there yet), and the pass over what was fetched
    assert c["rclone.files_hashed"] == held + (held - 1) + moved
    assert c["rclone.bytes_synced"] == 2 * (state["bytes"] + 4096)
    assert c["rclone.bytes_uploaded"] == c["rclone.bytes_fetched"] > 0
    assert "rclone.fetch_mismatch" not in c
    assert got["spans"]["verify.launch"][0] == c["rclone.hash_batches"] == 3


def test_same_size_and_mtime_other_bytes_is_transferred(tmp_path):
    """(c): no size-and-mtime shortcut, on either side."""
    vol, bucket, dest = tmp_path / "v", tmp_path / "bucket", tmp_path / "d"
    vol.mkdir()
    rng = np.random.default_rng(SEED)
    (vol / "f").write_bytes(rng.bytes(10_000))
    (vol / "g").write_bytes(rng.bytes(5_000))
    assert _sync("source", bucket, vol) == 0
    assert _sync("destination", bucket, dest) == 0
    was = os.stat(vol / "f")
    (vol / "f").write_bytes(rng.bytes(10_000))
    os.utime(vol / "f", ns=(was.st_atime_ns, was.st_mtime_ns))
    reset_spans()
    assert _sync("source", bucket, vol) == 0
    assert counter_totals()["rclone.files_uploaded"] == 1
    assert _clean(_bucket_diff(bucket, vol))
    assert _sync("destination", bucket, dest) == 0
    assert counter_totals()["rclone.files_fetched"] == 1
    assert _clean(treecmp.compare(vol, dest))
    # and the other way round: the destination's copy rots in place
    was = os.stat(dest / "g")
    with open(dest / "g", "r+b") as f:
        f.write(b"\xff")
    os.utime(dest / "g", ns=(was.st_atime_ns, was.st_mtime_ns))
    assert _sync("destination", bucket, dest) == 0
    assert _clean(treecmp.compare(vol, dest))


def _tmp_names(root: Path) -> list[str]:
    return [name for _, _, names in os.walk(root) for name in names
            if name.startswith(".volsync.")]


@pytest.mark.parametrize("fault", ["flip_bit", "other_bytes_same_size"])
def test_a_fetched_file_is_hashed_before_it_takes_its_name(tmp_path, fault):
    """(d): one stored object the destination must fetch does not hold
    the bytes its name says -> non-zero, the old file still in place,
    no temporary, ``rclone.fetch_mismatch`` 1; the files that did
    match are in place, and the sync after a repair converges."""
    vol, bucket, dest = tmp_path / "v", tmp_path / "bucket", tmp_path / "d"
    vol.mkdir()
    rng = np.random.default_rng(SEED)
    for name in ("f", "g"):
        (vol / name).write_bytes(rng.bytes(20_000))
    assert _sync("source", bucket, vol) == 0
    assert _sync("destination", bucket, dest) == 0
    old = (dest / "f").read_bytes()
    for name in ("f", "g"):
        (vol / name).write_bytes(rng.bytes(20_000))
    assert _sync("source", bucket, vol) == 0
    obj = bucket / rclone_sync.PREFIX / "objects" / ref_blobid.blob_id(
        (vol / "f").read_bytes())
    good = obj.read_bytes()
    bad = bytearray(good)
    if fault == "flip_bit":
        bad[len(bad) // 2] ^= 0x10
    else:
        bad[:] = rng.bytes(len(bad))
    obj.write_bytes(bytes(bad))
    reset_spans()
    assert _sync("destination", bucket, dest) != 0
    assert counter_totals()["rclone.fetch_mismatch"] == 1
    assert (dest / "f").read_bytes() == old
    assert (dest / "g").read_bytes() == (vol / "g").read_bytes()
    assert _tmp_names(dest) == []
    obj.write_bytes(good)
    reset_spans()
    assert _sync("destination", bucket, dest) == 0
    assert "rclone.fetch_mismatch" not in counter_totals()
    assert _clean(treecmp.compare(vol, dest))


def test_a_missing_object_fails_the_sync_and_leaves_no_temporary(tmp_path):
    vol, bucket, dest = tmp_path / "v", tmp_path / "bucket", tmp_path / "d"
    vol.mkdir()
    rng = np.random.default_rng(SEED)
    for name in ("f", "g", "h"):
        (vol / name).write_bytes(rng.bytes(9_000))
    assert _sync("source", bucket, vol) == 0
    (bucket / rclone_sync.PREFIX / "objects" / ref_blobid.blob_id(
        (vol / "g").read_bytes())).unlink()
    assert _sync("destination", bucket, dest) != 0
    assert _tmp_names(dest) == []
    assert not (dest / "g").exists()


ITEMS = [0, 1, 4095, 4096, 4097, 65536, 70_001]


def _record_calls(monkeypatch, names, calls):
    """Let ``os.<name>`` append (name, first argument) to ``calls``
    before it does its work."""
    for name in names:
        def recorded(path, *args, _name=name, _fn=getattr(os, name), **kw):
            calls.append((_name, path))
            return _fn(path, *args, **kw)
        monkeypatch.setattr(os, name, recorded)


@pytest.mark.parametrize("caller", ["verify_blob_batch", "hash_files",
                                    "hash_files_sizes_in_hand"])
def test_the_stagers_two_callers_give_the_reference_ids(tmp_path, caller,
                                                        monkeypatch):
    """One stager (``engine/chunker.stage_page_aligned``), two callers:
    the same items come out with the ids of ``reference/blobid.py``,
    and both record the site and the counters the roofline and the
    useful share are read from. A hash pass that is handed the sizes
    its caller's scan took asks the kernel for none."""
    from volsync_tpu.engine.chunker import _buffer_bucket, verify_blob_batch
    from volsync_tpu.movers.rclone.sync import hash_files

    rng = np.random.default_rng(SEED)
    blobs = [rng.bytes(n) for n in ITEMS]
    want = [ref_blobid.blob_id(b) for b in blobs]
    reset_spans()
    reset_copies()
    if caller == "verify_blob_batch":
        assert verify_blob_batch(list(zip(want, blobs))) == []
        wrong = [(want[1], blobs[2]), (want[3], blobs[3])]
        assert verify_blob_batch(wrong) == [want[1]]
        valid = sum(ITEMS) + ITEMS[2] + ITEMS[3]
        padded = _buffer_bucket(sum(n + -n % 4096 for n in ITEMS)) \
            + _buffer_bucket(2 * 4096)
    else:
        for i, b in enumerate(blobs):
            (tmp_path / f"f{i}").write_bytes(b)
        rels = [f"f{i}" for i in range(len(blobs))]
        in_hand = caller == "hash_files_sizes_in_hand"
        stats = []
        _record_calls(monkeypatch, ("stat", "lstat"), stats)
        assert hash_files(tmp_path, rels, list(ITEMS) if in_hand else None) \
            == dict(zip(rels, want))
        monkeypatch.undo()
        asked = [path for _, path in stats
                 if str(path).startswith(str(tmp_path))]
        assert len(asked) == (0 if in_hand else len(ITEMS))
        valid = sum(ITEMS)
        padded = _buffer_bucket(sum(n + -n % 4096 for n in ITEMS))
        assert span_totals()["rclone.read"][0] == 1
        assert counter_totals()["rclone.files_hashed"] == len(ITEMS)
        assert counter_totals()["rclone.bytes_hashed"] == valid
    c = counter_totals()
    assert copies_by_site() == {"verify.stage": valid}
    assert c["verify.bytes_valid"] == valid
    assert c["verify.bytes_valid"] + c["verify.bytes_padded"] == padded
    assert span_totals()["verify.stage"][0] == span_totals()[
        "verify.launch"][0]


def test_a_file_that_shrinks_under_the_hash_pass_fails_it(tmp_path,
                                                          monkeypatch):
    """A slot is sized from the file's length; a file that no longer
    fills it was changed under the pass, and its digest would be of
    bytes the volume never held."""
    from volsync_tpu.movers.rclone import sync

    (tmp_path / "f").write_bytes(b"x" * 9000)
    stage = sync.stage_page_aligned

    def shrink_first(lengths, fill, **kw):
        (tmp_path / "f").write_bytes(b"x" * 100)
        return stage(lengths, fill, **kw)

    monkeypatch.setattr(sync, "stage_page_aligned", shrink_first)
    with pytest.raises(sync.SyncError, match="changed while"):
        sync.hash_files(tmp_path, ["f"])


#: what every call of the slow store waits: the entry's ~30 store calls
#: in a row then outweigh what a loaded test host spends outside a span
DELAY = 0.012


class _SlowStore:
    """A store whose every call takes ``delay`` seconds longer."""

    def __init__(self, inner, delay):
        self._inner, self._delay = inner, delay

    def __getattr__(self, name):
        fn = getattr(self._inner, name)

        def slow(*args, **kwargs):
            time.sleep(self._delay)
            return fn(*args, **kwargs)
        return slow


@pytest.mark.parametrize("direction", ["source", "destination"])
def test_the_entry_threads_spans_add_up_to_the_call(
        tmp_path, monkeypatch, direction):
    """Tentpole 3: on a store with an injected delay, every span of the
    entry's thread is entered and together they are the call's wall;
    the pool's threads record one put or get an object beside it."""
    from volsync_tpu.movers.rclone import entry

    work = tmp_path
    states = _make_states(work)
    bucket, dest = work / "bucket", work / "d"
    assert _sync("source", bucket, states[0]["root"]) == 0
    assert _sync("destination", bucket, dest) == 0
    if direction == "destination":
        assert _sync("source", bucket, states[1]["root"]) == 0
    opened = entry.open_store
    monkeypatch.setattr(entry, "open_store", lambda url, env=None:
                        _SlowStore(opened(url, env=env), DELAY))
    data = states[1]["root"] if direction == "source" else dest
    reset_spans()
    t0 = time.perf_counter()
    assert _sync(direction, bucket, data) == 0
    wall = time.perf_counter() - t0
    spans, c = span_totals(), counter_totals()
    mine = {"source": {"rclone.scan", "rclone.hash", "rclone.lease",
                       "rclone.list", "rclone.transfer_wait",
                       "rclone.index_write", "rclone.sweep"},
            "destination": {"rclone.scan", "rclone.hash",
                            "rclone.transfer_wait", "rclone.index_read",
                            "rclone.delete_local", "rclone.place",
                            "rclone.apply_meta"}}
    assert {s for s in ENTRY_SPANS if s in spans} == mine[direction]
    covered = sum(spans[s][1] for s in mine[direction])
    assert 0.9 * wall <= covered <= wall, (covered, wall, spans)
    inside = sum(spans[s][1] for s in ("verify.stage", "verify.launch",
                                       "verify.fetch"))
    assert inside <= spans["rclone.hash"][1]
    assert spans["rclone.read"][1] <= spans["verify.stage"][1]
    moved = len(states[1]["other"]) + 1
    per_object = "rclone.put" if direction == "source" else "rclone.get"
    assert spans[per_object][0] == moved
    assert spans[per_object][1] >= moved * DELAY
    if direction == "source":  # acquire and release
        assert spans["rclone.lease"][0] == 2
    assert c["rclone.bytes_synced"] == states[1]["bytes"] + 4096


FAULTS = {
    "an_object_gone": ("objects_missing", lambda names, entries: (
        names[1:], entries)),
    "an_object_too_many": ("objects_extra", lambda names, entries: (
        names + ["0" * 64], entries)),
    "an_entry_gone": ("index_missing", lambda names, entries: (
        names, {k: v for k, v in entries.items() if k != "page"})),
    "an_entry_too_many": ("index_extra", lambda names, entries: (
        names, {**entries, "ghost": {"type": "dir"}})),
    "an_older_checksum": ("index_stale", lambda names, entries: (
        names, {**entries, "page": {**entries["page"],
                                    "digest": "0" * 64}})),
    "another_mode": ("index_meta", lambda names, entries: (
        names, {**entries, "page": {**entries["page"], "mode": 0o600}})),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_reference_sees_a_bucket_that_is_no_mirror(mirrored, fault):
    """The comparison has teeth: each way a bucket can fail to be the
    mirror of the tree shows under its own name, and only there."""
    stages, _, bucket, _ = mirrored
    tree = stages["back"]["state"]["root"]
    names, entries = _bucket(bucket)
    assert _clean(mirror.compare_bucket(names, entries, tree))
    where, break_it = FAULTS[fault]
    got = mirror.compare_bucket(*break_it(names, entries), tree)
    assert len(got.pop(where)) == 1 and _clean(got)


def test_the_cells_two_states_differ_as_its_file_says(tmp_path):
    """``derive_states`` at the cell's shares: each state lacks its own
    paths, the files both hold are rewritten at their size in the
    second, and ``other`` names each rewritten file's other bytes."""
    states = _make_states(tmp_path)
    a, b = (set(s["files"]) for s in states)
    assert len(a - b) == len(b - a) == 1 and len(a) == len(b) == 23
    assert set(states[0]["other"]) == set(states[1]["other"]) <= a & b
    for rel in states[0]["other"]:
        in_a = ref_blobid.file_sha256(states[0]["root"] / rel)
        in_b = ref_blobid.file_sha256(states[1]["root"] / rel)
        assert in_a != in_b
        assert states[0]["other"][rel] == in_b
        assert states[1]["other"][rel] == in_a
        assert states[0]["files"][rel] == states[1]["files"][rel]


def _walk_oracle(root: Path) -> list[tuple[str, dict]]:
    """``scan_tree``'s entries, in its order, by ``os.walk`` and
    ``os.lstat`` alone: a directory, its files by name, its symlinked
    directories, then its subdirectories by name, depth first."""
    def meta(p):
        st = os.lstat(p)
        return {"uid": st.st_uid, "gid": st.st_gid, "xattrs": {
            n: base64.b64encode(os.getxattr(
                p, n, follow_symlinks=False)).decode()
            for n in sorted(os.listxattr(p, follow_symlinks=False))}}

    def kept(p):
        st = os.lstat(p)
        return {"mode": st.st_mode & 0o7777, "mtime_ns": st.st_mtime_ns}

    out = []
    for top, dirnames, filenames in os.walk(root):
        dirnames.sort()
        rel_top = os.path.relpath(top, root)
        prefix = "" if rel_top == "." else rel_top + "/"
        if prefix:
            out.append((rel_top, {"type": "dir", **kept(top), **meta(top)}))
        for name in sorted(filenames):
            p = os.path.join(top, name)
            if os.path.islink(p):
                out.append((prefix + name, {
                    "type": "symlink", "target": os.readlink(p), **meta(p)}))
            elif os.path.isfile(p):
                out.append((prefix + name, {
                    "type": "file", "size": os.lstat(p).st_size,
                    **kept(p), **meta(p)}))
        for name in list(dirnames):
            p = os.path.join(top, name)
            if os.path.islink(p):
                dirnames.remove(name)
                out.append((prefix + name, {
                    "type": "symlink", "target": os.readlink(p), **meta(p)}))
    return out


@pytest.mark.parametrize("collect_meta", [True, False])
def test_the_scan_is_the_plain_walk_in_its_order(tmp_path, collect_meta):
    """What a hash pass batches together, and so the programs a warm-up
    meets, is a function of ``scan_tree``'s order: it is the plain
    walk's, entry for entry, on nested directories, a symlink to a
    file, one to a directory, a dangling one, an empty directory and a
    FIFO (which no mover carries)."""
    from volsync_tpu.movers.rclone.sync import scan_tree

    vol = tmp_path / "v"
    for rel in ("b/z", "b/a/deep/f", "b/a/e", "a.d/x", "a", "c", "b.x",
                "b/.hidden", "B/y"):
        (vol / rel).parent.mkdir(parents=True, exist_ok=True)
        (vol / rel).write_bytes(rel.encode() * 7)
    (vol / "hollow").mkdir()
    (vol / "b" / "void").mkdir()
    os.symlink("a", vol / "to_file")
    os.symlink("b/a", vol / "a_dir_link")
    os.symlink("../b", vol / "b" / "loop")
    os.symlink("nowhere", vol / "b" / "dangling")
    os.mkfifo(vol / "b" / "fifo")
    os.chmod(vol / "c", 0o4750)
    os.utime(vol / "b" / "z", ns=(1, 1_234_567_891_234_567_891))
    try:
        os.setxattr(vol / "a", "user.tag", b"\x00v")
    except OSError:
        pass  # a filesystem without user.*: both sides then read none
    want = _walk_oracle(vol)
    if not collect_meta:
        want = [(rel, {k: v for k, v in e.items() if k != "xattrs"})
                for rel, e in want]
    got = scan_tree(vol, collect_meta=collect_meta)
    assert list(got.items()) == want
    assert list(got)[:8] == ["a", "b.x", "c", "to_file", "a_dir_link",
                             "B", "B/y", "a.d"]
    kinds = {rel: e["type"] for rel, e in want}
    assert kinds["a_dir_link"] == kinds["b/loop"] == "symlink"
    assert kinds["b/dangling"] == kinds["to_file"] == "symlink"
    assert kinds["hollow"] == kinds["b/void"] == "dir"
    assert "b/fifo" not in kinds and "b/a/deep/f" in kinds
    assert not any(rel.startswith("a_dir_link/") for rel in kinds)


def _drift_mode(f: Path):
    os.chmod(f, 0o600)


def _drift_mtime(f: Path):
    os.utime(f, ns=(5, 5))


def _drift_xattr(f: Path):
    os.setxattr(f, "user.drift", b"1")


def _drift_xattr_value(f: Path):
    os.setxattr(f, "user.keep", b"other")


def _drift_content(f: Path):
    was = os.stat(f)
    f.write_bytes(bytes(reversed(f.read_bytes())))
    os.utime(f, ns=(was.st_atime_ns, was.st_mtime_ns))


#: drift -> (what it does to one file of the destination, the calls on
#: regular files that put it back)
DRIFTS = {
    "none": (lambda f: None, []),
    "mode": (_drift_mode, ["chmod"]),
    "mtime": (_drift_mtime, ["utime"]),
    # one name too many: the whole set is applied, as the restore does
    "xattr": (_drift_xattr, ["removexattr", "setxattr"]),
    "xattr_value": (_drift_xattr_value, ["setxattr"]),
    # fetched: a new inode gets every call, in the restore's order
    "content": (_drift_content, ["setxattr", "chown", "chmod", "utime"]),
}


@pytest.mark.parametrize("drift", sorted(DRIFTS))
def test_metadata_is_settled_by_the_calls_that_change_something(
        tmp_path, monkeypatch, drift):
    """The destination's metadata pass works from the scan's record: on
    a destination that already is the mirror no file gets a ``chown``,
    ``chmod`` or ``utime`` (``rclone.meta_kept`` == files); after a
    drift of one file the next sync makes exactly the calls that put it
    back, on that file, and the trees compare clean. Symlinks and
    directories get every call every time, as before."""
    vol, bucket, dest = tmp_path / "v", tmp_path / "bucket", tmp_path / "d"
    how, want_calls = DRIFTS[drift]
    rng = np.random.default_rng(SEED)
    names = ["f0", "sub/f1", "sub/f2", "sub/deeper/f3", "f4"]
    for i, rel in enumerate(names):
        (vol / rel).parent.mkdir(parents=True, exist_ok=True)
        (vol / rel).write_bytes(rng.bytes(3000 + 5000 * i))
    os.chmod(vol / "f0", 0o640)
    os.symlink("f0", vol / "link")
    try:  # the file that drifts carries an xattr where it can
        os.setxattr(vol / "sub" / "f2", "user.keep", b"v")
    except OSError:
        if drift.startswith("xattr"):
            pytest.skip("the filesystem takes no user.* xattr")
        want_calls = [name for name in want_calls if name != "setxattr"]
    assert _sync("source", bucket, vol) == 0
    assert _sync("destination", bucket, dest) == 0
    victim = dest / "sub" / "f2"
    how(victim)
    calls = []
    _record_calls(monkeypatch, ("chown", "chmod", "utime", "setxattr",
                                "removexattr"), calls)
    reset_spans()
    assert _sync("destination", bucket, dest) == 0
    monkeypatch.undo()
    # whether a path is a file is asked now, not while the sync ran: a
    # fetched file's temporary name is gone, its own name is the file
    on_files = [(name, str(path)) for name, path in calls
                if os.path.isfile(path) and not os.path.islink(path)]
    assert on_files == [(name, str(victim)) for name in want_calls]
    on_dirs = {str(path) for name, path in calls if os.path.isdir(path)
               and not os.path.islink(path) and name == "utime"}
    assert on_dirs == {str(dest / "sub"), str(dest / "sub" / "deeper")}
    c = counter_totals()
    assert c["rclone.meta_files"] == len(names)
    assert c["rclone.meta_kept"] == len(names) - bool(want_calls)
    assert c["rclone.files_fetched"] == (1 if drift == "content" else 0)
    assert c["rclone.files_hashed"] == len(names) + c["rclone.files_fetched"]
    assert _clean(treecmp.compare(vol, dest))
    assert os.listxattr(victim) == os.listxattr(vol / "sub" / "f2")
    st, src = os.stat(victim), os.stat(vol / "sub" / "f2")
    assert (st.st_mode, st.st_mtime_ns, st.st_uid, st.st_gid) == (
        src.st_mode, src.st_mtime_ns, src.st_uid, src.st_gid)
    assert victim.read_bytes() == (vol / "sub" / "f2").read_bytes()
