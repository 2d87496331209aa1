"""The rclone mover, both directions, against plain references
(``benchmark/reference/mirror.py``: what a correct mirror's bucket
holds, ``os`` + ``json`` + ``hashlib``; ``treecmp.py``: two trees;
``blobid.py``: hashlib ids): a small volume in the two states of the
benchmark's cell ``rclone-smallfiles.sync`` is synced source -> bucket
-> destination through ``movers/rclone/entry.rclone_entrypoint``, as
the cell does at its size, and the configuration's guarantees (a)-(d)
are held one by one. CPU, small sizes, seeded."""

import ast
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


@pytest.mark.parametrize("caller", ["verify_blob_batch", "hash_files"])
def test_the_stagers_two_callers_give_the_reference_ids(tmp_path, caller):
    """One stager (``engine/chunker.stage_page_aligned``), two callers:
    the same items come out with the ids of ``reference/blobid.py``,
    and both record the site and the counters the roofline and the
    useful share are read from."""
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
        assert hash_files(tmp_path, rels) == dict(zip(rels, want))
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
