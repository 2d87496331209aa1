"""The rsync mover through both entry points against the plain
reference (``benchmark/reference/rsyncdelta.py``: rsync's delta with
``numpy`` and ``hashlib`` alone; ``treecmp.py``: two trees), at a small
size and with the window and the part forced small, so that a file
spans several staged buffers and several frames, as the 512 MiB file of
the benchmark's cell ``rsync-1g.push`` does at its size: the
configuration's guarantees (a)-(c) one by one, the bounded set of
device programs, and the spans and counters the cell's metrics read.
CPU, seeded."""

import ast
import os
import threading
from pathlib import Path

import numpy as np
import pytest

from benchmark.drivers import rsync_push
from benchmark.reference import rsyncdelta, treecmp
from volsync_tpu.engine import deltasync
from volsync_tpu.movers.rsync import entry
from volsync_tpu.obs import (counter_totals, reset_spans, reset_trace,
                             span_totals, trace_context, trace_events)

SEED = 2147483659
WINDOW = 1 << 14   # four blocks of 4 KiB
PART = 6000        # a part of a file's op stream: under two blocks

#: how each file of state A differs in state B
KINDS = ("rewrite", "insertion", "truncation", "append", "unchanged",
         "tiny", "tiny_other", "empty", "same_size_other_bytes",
         "insertions", "zeros_shifted", "repeated_shifted")


@pytest.fixture
def small_window(monkeypatch):
    monkeypatch.setattr(deltasync, "WINDOW", WINDOW)
    monkeypatch.setattr(rsyncdelta, "WINDOW", 1 << 15)
    monkeypatch.setattr(entry, "PART_BYTES", PART)
    monkeypatch.setenv("VOLSYNC_SYNC_PROTO", "delta")


def _bytes(kind: str, rng) -> tuple[bytes, bytes]:
    """(the file in state A, the file in state B)."""
    base = rng.bytes(150_000)
    if kind == "rewrite":  # pages rewritten in place, off the blocks
        new = bytearray(base)
        for at in (1_000, 33_000, 70_500, 149_000):
            new[at: at + 1000] = rng.bytes(1000)
        return base, bytes(new)
    if kind == "insertion":  # not a multiple of the block
        return base, base[:75_000] + rng.bytes(777) + base[75_000:]
    if kind == "truncation":
        return base, base[:61_234]
    if kind == "append":
        return base[:30_000], base[:30_000] + rng.bytes(5_000)
    if kind == "unchanged":
        return base[:20_000], base[:20_000]
    if kind == "tiny":
        return base[:300], base[:300]
    if kind == "tiny_other":
        return base[:300], rng.bytes(300)
    if kind == "empty":
        return b"", b""
    if kind == "insertions":  # several inside one window, one deletion
        new, at = bytearray(base), 60_000
        for gap, n in ((0, 13), (3_000, 700), (2_500, 1), (5_000, 2_500),
                       (40_000, 333)):
            at += gap
            new[at: at] = rng.bytes(n)
            at += n
        del new[20_000: 20_123]
        return base, bytes(new)
    if kind == "zeros_shifted":
        # a zero-filled region moved by one byte: every offset of the
        # block before it is a candidate (the strong check's capacity
        # overflows), and the match there ends inside blocks that held
        old = base[:50_000] + bytes(40_000) + base[90_000:]
        return old, old[:49_000] + b"\x01" + old[49_000:]
    if kind == "repeated_shifted":
        # 512 bytes eighty times over, moved by 512: inside the run the
        # blocks hold at the old alignment and at the new one
        old = base[:37_000] + base[:512] * 80 + base[37_000:]
        return old, old[:36_900] + rng.bytes(512) + old[36_900:]
    return base[:50_000], rng.bytes(50_000)


def _write_states(work: Path):
    """Two states of one small volume: every kind of churn, a file only
    in A, a file only in B, a subdirectory, a symlink."""
    rng = np.random.default_rng(SEED)
    a, b = work / "a", work / "b"
    for root in (a, b):
        (root / "sub").mkdir(parents=True)
        os.symlink("sub", root / "link")
    for kind in KINDS:
        old, new = _bytes(kind, rng)
        (a / f"{kind}.bin").write_bytes(old)
        (b / f"{kind}.bin").write_bytes(new)
        # a copy keeps its mtime, as the cell's does; a file written
        # again has another, whatever the file system's clock resolves
        st = os.stat(a / f"{kind}.bin")
        mtime = st.st_mtime_ns + (0 if old == new else 10**9)
        os.utime(b / f"{kind}.bin", ns=(st.st_atime_ns, mtime))
    (a / "sub" / "only_a").write_bytes(rng.bytes(9_000))
    (b / "sub" / "only_b").write_bytes(rng.bytes(12_000))
    for root in (a, b):
        os.utime(root / "sub", ns=(10**18, 10**18))
    return a, b


def _state(work: Path):
    return rsync_push.relationship(rsync_push.State(), work / "d",
                                   {"VOLSYNC_SYNC_PROTO": "delta"})


def _quick_same(source: Path, dest: Path) -> dict:
    """{relative path: bytes} of the regular files of ``source`` that
    rsync's quick check leaves alone on ``dest``: a regular file there
    (by ``lstat``) of the same size and mtime."""
    import stat

    want, have = treecmp.entries(source), treecmp.entries(dest)
    return {rel: st.st_size for rel, st in want.items()
            if stat.S_ISREG(st.st_mode) and rel in have
            and stat.S_ISREG(have[rel].st_mode)
            and (st.st_size, st.st_mtime_ns)
            == (have[rel].st_size, have[rel].st_mtime_ns)}


@pytest.fixture(scope="module")
def pushed(tmp_path_factory):
    """The first sync of A, the push of B, the push back of A, each
    through both entries (``rsync_push.push``: the cell's own call);
    after each: both return codes, what the program counted, the
    reference's numbers for the transition, and the destination
    against the source state."""
    work = tmp_path_factory.mktemp("rsync")
    mp = pytest.MonkeyPatch()
    mp.setattr(deltasync, "WINDOW", WINDOW)
    mp.setattr(rsyncdelta, "WINDOW", 1 << 15)
    mp.setattr(entry, "PART_BYTES", PART)
    mp.setenv("VOLSYNC_SYNC_PROTO", "delta")
    try:
        a, b = _write_states(work)
        st = _state(work)
        out = {}
        held = work / "nothing"
        held.mkdir()
        for stage, root in (("first", a), ("churned", b), ("back", a)):
            want = rsyncdelta.tree_delta(root, held)
            same = _quick_same(root, held)
            reset_spans()
            got = rsync_push.push(st, {"root": root})
            out[stage] = {"got": got, "want": want, "same": same,
                          "tree": treecmp.compare(root, st.dest),
                          "counters": counter_totals(),
                          "spans": span_totals()}
            held = root
        out["dest"] = st.dest
        yield out
    finally:
        mp.undo()


def test_the_reference_imports_nothing_of_the_program():
    path = Path(rsyncdelta.__file__)
    for node in ast.walk(ast.parse(path.read_text())):
        names = ([a.name for a in node.names]
                 if isinstance(node, ast.Import)
                 else [node.module or ""]
                 if isinstance(node, ast.ImportFrom) else [])
        assert not any(n.startswith(("volsync_tpu", "jax")) for n in names)


@pytest.mark.parametrize("b", [4096, 8192, 16384, 32768, 65536, 131072])
def test_the_block_law_is_one_law_on_both_sides(b):
    """The program's block length and the reference's are one rule, at
    and around every perfect square where it steps: a file of
    b * b + 1,000 bytes (the cell's 64 MiB file with its insertion, at
    the full shape) has blocks of b on both sides."""
    for size in (b * b - 1, b * b, b * b + 1, b * b + 1_000, b * b + 2 * b,
                 (b + 1) * (b + 1) - 1, (b + 1) * (b + 1),
                 (b + 1) * (b + 1) + 1, 2 * b * b, 4 * b * b - 1):
        assert deltasync.pick_block_len(size) \
            == rsyncdelta.block_len_for(size), size
    assert rsyncdelta.block_len_for(b * b + 1_000) == b
    assert rsyncdelta.block_len_for((b + 1) * (b + 1) + 1) \
        == min(2 * b, rsyncdelta.MAX_BLOCK)
    for size in (0, 1, 4095, 4097, 1 << 40):
        assert deltasync.pick_block_len(size) \
            == rsyncdelta.block_len_for(size), size


@pytest.mark.parametrize("stage", ["first", "churned", "back"])
def test_the_destination_is_the_source_state(pushed, stage):
    """Guarantee (a): both entries returned 0 and the destination
    equals the pushed state: bytes, sizes, modes, mtimes, the symlink,
    the directory's mtime, nothing extra, no temporary."""
    got, tree = pushed[stage]["got"], pushed[stage]["tree"]
    assert (got["rc"], got["dst_rc"]) == (0, 0)
    assert tree["compared"] >= len(KINDS) + 3
    for kind in ("missing", "extra", "size", "content", "meta"):
        assert tree[kind] == [], (kind, tree[kind])


@pytest.mark.parametrize("stage", ["first", "churned", "back"])
def test_a_push_moves_the_references_literal_bytes(pushed, stage):
    """Guarantee (b): the literal bytes on the channel, the files with
    no basis and the entries pruned are the plain reference's for the
    transition; every file with a basis went by delta, none whole; the
    device was handed every byte a sender cannot skip."""
    c, want = pushed[stage]["got"]["counts"], pushed[stage]["want"]
    assert c["rsync.literal_bytes"] == want["literal_bytes"]
    assert c["rsync.files_new"] == want["files_new"]
    assert c["rsync.pruned"] == want["pruned"]
    assert c["rsync.files_delta"] + c["rsync.files_skipped"] \
        == want["files_basis"]
    assert c["rsync.files_delta"] + c["rsync.files_skipped"] \
        + c["rsync.files_new"] == c["rsync.files"] == want["files"]
    assert c["rsync.files_full"] == 0
    assert pushed[stage]["got"]["staged"] >= want["staged_floor"]
    # the quick check took the files of the same size and mtime, which
    # had no literal bytes to move, and no other
    same, counted = pushed[stage]["same"], pushed[stage]["counters"]
    assert c["rsync.files_skipped"] == len(same)
    assert counted.get("rsync.bytes_skipped", 0) == sum(same.values())
    assert counted["rsync.bytes_synced"] == want["bytes"]
    assert all(want["by_file"][rel] == 0 for rel in same)
    if stage != "first":
        assert 0 < want["literal_bytes"] < want["bytes"] // 2
        assert want["files_new"] == 1 and want["pruned"] == 1
        assert sorted(same) == ["empty.bin", "tiny.bin", "unchanged.bin"]


def test_a_file_over_one_frame_arrives_in_parts(pushed):
    """The first sync ships a 150,000-byte file with no basis in parts
    of at most ``PART`` literal bytes: more frames than files."""
    c = pushed["first"]["got"]["counts"]
    assert c["rsync.frames"] > c["rsync.files"] + 150_000 // PART


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("window", [WINDOW, 64 << 20])
def test_the_windowed_ops_are_the_references_and_the_oracles(
        small_window, monkeypatch, kind, window):
    """One file, both ways between its two states: the op stream of
    the staged-buffer scan (a window of four blocks: several buffers a
    file; and the mover's own window) equals the plain reference's and
    the unwindowed ``compute_delta``'s, and the reference's ``apply``
    rebuilds the file from it."""
    monkeypatch.setattr(deltasync, "WINDOW", window)
    old, new = _bytes(kind, np.random.default_rng(SEED))
    for src, dst in ((new, old), (old, new)):
        block_len = deltasync.pick_block_len(len(src))
        assert block_len == rsyncdelta.block_len_for(len(src))
        sig = deltasync.build_file_signature(dst, block_len)
        want_sig = rsyncdelta.signature(dst, block_len)
        assert sig.strong == want_sig["strong"]
        assert sig.weak.tolist() == want_sig["weak"].tolist()
        ops = deltasync.scan_ranges([(src, sig)])[0]
        assert ops == rsyncdelta.delta(src, want_sig)
        assert deltasync._materialize(ops, src) \
            == deltasync.compute_delta(src, sig)
        assert rsyncdelta.apply(ops, dst, src, block_len) == src


def _scan_counts(src: bytes, dst: bytes) -> tuple[list, dict]:
    block_len = deltasync.pick_block_len(len(src))
    sig = deltasync.build_file_signature(dst, block_len)
    before = counter_totals()
    ops = deltasync.scan_ranges([(src, sig)])[0]
    now = counter_totals()
    assert ops == rsyncdelta.delta(src, rsyncdelta.signature(dst, block_len))
    return ops, {k: now.get(k, 0) - before.get(k, 0)
                 for k in ("delta.batches", "delta.overflow_retries",
                           "delta.reprobes")}


def test_insertions_in_one_window_cost_no_buffer_each(small_window,
                                                      monkeypatch):
    """What a change of alignment costs is searches of the buffer that
    is staged, not a buffer of its own: a file with a dozen scattered
    insertions is staged in as many buffers as the file with one (a
    buffer is 64 MiB read, uploaded and signed)."""
    monkeypatch.setattr(deltasync, "WINDOW", 1 << 16)
    rng = np.random.default_rng(SEED)
    base = rng.bytes(300_000)
    one = base[:150_000] + rng.bytes(77) + base[150_000:]
    many = bytearray(base)
    for at in range(290_000, 10_000, -24_000):  # right to left: 12
        many[at: at] = rng.bytes(int(rng.integers(1, 900)))
    _ops, few = _scan_counts(one, base)
    _ops, got = _scan_counts(bytes(many), base)
    assert got["delta.batches"] <= few["delta.batches"] + 1
    assert got["delta.reprobes"] == few["delta.reprobes"] == 0
    assert few["delta.batches"] <= 300_000 // (1 << 16) + 3


@pytest.mark.parametrize("kind,retries", [("zeros_shifted", True),
                                          ("repeated_shifted", False)])
def test_a_moved_run_of_one_block_is_probed_again(small_window, kind,
                                                  retries):
    """A run of one repeated block moved off the blocks' alignment: the
    match found at the new alignment ends inside blocks that held, so
    the piece is cut there and probed again from that byte
    (``delta.reprobes``: at most a buffer more), and where every offset
    of a block is a candidate the strong check runs in rounds
    (``delta.overflow_retries``); the ops stay the reference's and all
    but the blocks around the insertion are copies."""
    old, new = _bytes(kind, np.random.default_rng(SEED))
    ops, got = _scan_counts(new, old)
    assert (got["delta.overflow_retries"] > 0) == retries
    assert got["delta.reprobes"] == 1
    copied = sum(op[2] for op in ops if op[0] == "copy") * 4096
    assert copied >= len(new) - 3 * 4096
    _ops, plain = _scan_counts(old, old)
    assert plain["delta.reprobes"] == 0
    assert got["delta.batches"] <= plain["delta.batches"] + 2


class _Chan:
    """Loopback channel: dispatch directly into the dest verb table."""

    def __init__(self, verbs):
        self.verbs = verbs
        self.reply = None

    def send(self, msg):
        self.reply = self.verbs[msg["verb"]](msg)

    def recv(self):
        return self.reply


def test_a_destination_killed_between_parts_keeps_the_old_file(
        tmp_path, small_window):
    """Guarantee (c): the old file stays under its name until the last
    part is in; what a killed destination leaves is a temporary that
    the next push's prune removes."""
    rng = np.random.default_rng(SEED)
    src, dst = tmp_path / "src", tmp_path / "dst"
    src.mkdir()
    dst.mkdir()
    old, new = rng.bytes(40_000), rng.bytes(40_000)
    (dst / "f.bin").write_bytes(old)
    (src / "f.bin").write_bytes(new)

    class Killed(Exception):
        pass

    verbs = entry._dest_verbs(dst)
    real_apply, seen = verbs["apply"], []

    def apply_then_die(msg):
        if seen:
            raise Killed()
        seen.append(msg)
        return real_apply(msg)

    verbs["apply"] = apply_then_die
    with pytest.raises(Killed):
        entry._push_tree(_Chan(verbs), src)
    assert seen and not seen[0]["last"]
    assert (dst / "f.bin").read_bytes() == old
    assert [p.name for p in dst.iterdir() if p.name != "f.bin"] \
        == [".f.bin.volsync-part"]
    # a fresh listener (the Job restarted): the push completes and
    # nothing is left beside the file
    entry._push_tree(_Chan(entry._dest_verbs(dst)), src)
    assert (dst / "f.bin").read_bytes() == new
    assert [p.name for p in dst.iterdir()] == ["f.bin"]


def test_a_link_dropped_between_parts_is_retried_from_the_first_part(
        tmp_path, small_window, monkeypatch):
    """Guarantees (a) and (c) across the source's documented retry: the
    link drops after two parts of a file are in, the SAME listener
    accepts the next attempt, and the file that arrives is the
    source's: nothing of the dropped session's half-built file is
    appended to, and no temporary stays."""
    rng = np.random.default_rng(SEED)
    src = tmp_path / "src"
    src.mkdir()
    (src / "new.bin").write_bytes(rng.bytes(40_000))       # no basis
    (src / "held.bin").write_bytes(rng.bytes(40_000))      # a basis
    st = rsync_push.relationship(
        rsync_push.State(), tmp_path / "d",
        {"VOLSYNC_SYNC_PROTO": "delta", "FAST_RETRY": "1"})
    (st.dest / "held.bin").write_bytes(rng.bytes(40_000))
    real, sent = entry._apply, []

    def apply_then_drop(ch, msg):
        out = real(ch, msg)
        if msg["verb"] == "apply" and not msg["last"]:
            sent.append(msg["path"])
            if len(sent) == 2:  # two parts of the first file are in
                ch.sock.close()
                raise OSError("the link dropped")
        return out

    monkeypatch.setattr(entry, "_apply", apply_then_drop)
    got = rsync_push.push(st, {"root": src})
    assert (got["rc"], got["dst_rc"]) == (0, 0)
    assert len(set(sent)) == 2 and sent.count(sent[0]) > 2  # it was retried
    tree = treecmp.compare(src, st.dest)
    for kind in ("missing", "extra", "size", "content", "meta"):
        assert tree[kind] == [], (kind, tree[kind])
    assert sorted(p.name for p in st.dest.iterdir()) \
        == ["held.bin", "new.bin"]


def test_a_part_out_of_turn_is_refused(tmp_path, small_window):
    """A part that is not the next of its file is refused, and a first
    part begins the file anew: the destination never appends to a file
    another attempt left half built."""
    dst = tmp_path / "dst"
    dst.mkdir()
    apply = entry._dest_verbs(dst)["apply"]
    part = {"verb": "apply", "path": "f", "block_len": 4096, "last": False}
    with pytest.raises(entry.channel.ChannelError):
        apply({**part, "part": 1, "ops": [["data", b"x"]]})
    apply({**part, "part": 0, "ops": [["data", b"stale"]]})
    with pytest.raises(entry.channel.ChannelError):
        apply({**part, "part": 2, "ops": [["data", b"x"]]})
    assert list(dst.iterdir()) == []  # the refused file's temporary went
    apply({**part, "part": 0, "ops": [["data", b"stale"]]})
    apply({**part, "part": 0, "ops": [["data", b"fresh "]]})
    apply({**part, "part": 1, "ops": [["data", b"bytes"]], "last": True,
           "mode": 0o600})
    assert (dst / "f").read_bytes() == b"fresh bytes"
    assert [p.name for p in dst.iterdir()] == ["f"]


@pytest.mark.parametrize("verb", ["dirmeta", "link", "apply", "sigs"])
def test_a_symlink_out_of_the_root_is_not_followed(tmp_path, verb):
    """Guarantee (d)'s other half: a peer-sent path whose last name is a
    symlink pointing out of the destination's root is never followed:
    no metadata, hard link, write or read lands outside."""
    outside, dst = tmp_path / "outside", tmp_path / "dst"
    outside.mkdir()
    dst.mkdir()
    (outside / "secret").write_bytes(b"s" * 5000)
    os.chmod(outside, 0o755)
    os.utime(outside, ns=(10**18, 10**18))
    os.symlink(outside, dst / "dir")
    os.symlink(outside / "secret", dst / "file")
    verbs = entry._dest_verbs(dst)
    before = os.stat(outside)
    if verb == "dirmeta":
        verbs["dirmeta"]({"dirs": [{"path": "dir", "mode": 0o700,
                                    "mtime_ns": 5 * 10**17}]})
    elif verb == "link":
        with pytest.raises(entry.channel.ChannelError):
            verbs["link"]({"path": "name", "to": "file"})
        assert not (dst / "name").exists()
    elif verb == "apply":
        with pytest.raises(entry.channel.ChannelError):
            verbs["apply"]({"path": "dir/x", "ops": [["data", b"x"]],
                            "block_len": 4096, "last": True})
        verbs["apply"]({"path": "file", "ops": [["data", b"mine"]],
                        "block_len": 4096, "last": True, "mode": 0o600})
        assert not (dst / "file").is_symlink()  # the name was replaced
        assert (dst / "file").read_bytes() == b"mine"
    else:  # what a stat() through the link would find is the request's
        was = os.stat(outside / "secret")
        out = verbs["sigs"]({"files": [
            {"path": "file", "block_len": 4096, "size": was.st_size,
             "mtime_ns": was.st_mtime_ns, "mode": 0o600}]})
        assert out["sigs"] == [{"exists": False}]
        assert os.stat(outside / "secret").st_mode == was.st_mode
    after = os.stat(outside)
    assert (after.st_mode, after.st_mtime_ns) \
        == (before.st_mode, before.st_mtime_ns)
    assert os.stat(outside / "secret").st_nlink == 1
    assert (outside / "secret").read_bytes() == b"s" * 5000
    assert sorted(os.listdir(outside)) == ["secret"]


# -- rsync's quick check (guarantee (b): no -c and no -I) ------------------

#: every call of ``os`` that asks the kernel about a file or changes it
ASKING = ("stat", "lstat", "open", "listxattr", "getxattr")
CHANGING = ("chown", "chmod", "utime", "setxattr", "removexattr")


def _record_calls(monkeypatch, names, calls):
    """Let ``os.<name>`` append (name, path) to ``calls`` before it
    does its work (``Path.lstat`` is ``os.stat``: both read ``stat``)."""
    for name in names:
        def recorded(path, *args, _name=name, _fn=getattr(os, name), **kw):
            if isinstance(path, (str, os.PathLike)):
                calls.append(("stat" if _name == "lstat" else _name,
                              os.fspath(path)))
            return _fn(path, *args, **kw)
        monkeypatch.setattr(os, name, recorded)


def _tree(root: Path, files: int = 6) -> dict[str, int]:
    """A small volume: ``files`` regular files over three directories
    (one of them empty, one under a block), a symlink."""
    rng = np.random.default_rng(SEED)
    sizes = {}
    for i in range(files):
        rel = ("", "sub/", "sub/deeper/")[i % 3] + f"f{i:02d}"
        (root / rel).parent.mkdir(parents=True, exist_ok=True)
        sizes[rel] = (0, 300, 9_000, 20_000, 40_000)[i % 5]
        (root / rel).write_bytes(rng.bytes(sizes[rel]))
    os.symlink("f00", root / "link")
    return sizes


def _push(src: Path, dst: Path, verbs=None) -> dict:
    """One push over the loopback channel; what it counted."""
    reset_spans()
    entry._push_tree(_Chan(verbs or entry._dest_verbs(dst)), src)
    return counter_totals()


def _clean(tree: dict) -> bool:
    return not any(tree[k] for k in ("missing", "extra", "size", "content",
                                     "meta"))


@pytest.fixture
def synced(tmp_path, small_window):
    """(source, destination, {relative path: bytes}) after a first
    sync."""
    src, dst = tmp_path / "src", tmp_path / "dst"
    src.mkdir()
    dst.mkdir()
    sizes = _tree(src)
    c = _push(src, dst)
    assert c["rsync.files_new"] == len(sizes)
    assert "rsync.files_skipped" not in c
    return src, dst, sizes


@pytest.mark.parametrize("files", [5, 40])
def test_a_second_push_of_an_unchanged_tree_opens_no_file(
        tmp_path, monkeypatch, files):
    """Every regular file is skipped: nothing is staged on either side,
    no file of either tree is opened, the frames are the directories',
    the symlink's and the batch's one ``sigs`` (with prune and
    directory metadata) however many files there are, and every
    destination file is the inode it was."""
    from volsync_tpu.obs import copies_by_site

    monkeypatch.setenv("VOLSYNC_SYNC_PROTO", "delta")
    monkeypatch.setattr(deltasync, "WINDOW", 64 << 20)  # the mover's: a batch
    src, dst = tmp_path / "src", tmp_path / "dst"
    src.mkdir()
    dst.mkdir()
    sizes = _tree(src, files)
    _push(src, dst)
    inodes = {rel: os.lstat(dst / rel).st_ino for rel in sizes}
    staged, calls = copies_by_site(), []
    _record_calls(monkeypatch, ("open",), calls)
    c = _push(src, dst)
    monkeypatch.undo()
    assert calls == []
    assert c["rsync.files_skipped"] == c["rsync.files"] == len(sizes)
    assert c["rsync.bytes_skipped"] == c["rsync.bytes_synced"] \
        == sum(sizes.values())
    for name in ("delta.batches", "delta.files", "rsync.files_delta",
                 "rsync.files_new", "rsync.files_full",
                 "rsync.copied_bytes"):
        assert name not in c, name
    assert c.get("rsync.literal_bytes", 0) == 0
    now = copies_by_site()
    for site in ("sig.stage", "delta.stage"):
        assert now.get(site, 0) == staged.get(site, 0), site
    # two mkdirs, one symlink, one sigs, prune, dirmeta
    assert c["rsync.frames"] == 6
    assert {rel: os.lstat(dst / rel).st_ino for rel in sizes} == inodes
    assert _clean(treecmp.compare(src, dst))


@pytest.mark.parametrize("other", ["mtime", "size"])
def test_another_size_or_mtime_moves_by_delta(synced, other):
    """The check is both: a file of the destination's size with another
    mtime, and one of its mtime with another size, move by delta with
    the reference's literal bytes; the rest is skipped."""
    src, dst, sizes = synced
    f = src / "sub" / "f04"
    was, body = os.stat(f), bytearray(f.read_bytes())
    if other == "mtime":
        body[10_000: 10_100] = bytes(100)
        f.write_bytes(bytes(body))
        os.utime(f, ns=(was.st_atime_ns, was.st_mtime_ns + 1))
    else:
        f.write_bytes(bytes(body) + b"more")
        os.utime(f, ns=(was.st_atime_ns, was.st_mtime_ns))
    want = rsyncdelta.tree_delta(src, dst)
    assert 0 < want["literal_bytes"] < 3 * 4096
    c = _push(src, dst)
    assert c["rsync.files_delta"] == 1
    assert c["rsync.files_skipped"] == len(sizes) - 1
    assert c["rsync.literal_bytes"] == want["literal_bytes"]
    assert _clean(treecmp.compare(src, dst))


def test_the_quick_check_comes_before_the_planners_choice(
        tmp_path, monkeypatch):
    """Where the planner sends files whole (``rsync -W``; here pinned)
    an unchanged file is skipped all the same, and a changed one is
    looked at by the destination (its one ``lstat``) but not signed:
    the request says ``sign`` false for it."""
    from volsync_tpu.obs import copies_by_site

    monkeypatch.setenv("VOLSYNC_SYNC_PROTO", "full")
    src, dst = tmp_path / "src", tmp_path / "dst"
    src.mkdir()
    dst.mkdir()
    sizes = _tree(src)
    _push(src, dst)
    (src / "sub" / "f04").write_bytes(b"another body")
    verbs, asked = entry._dest_verbs(dst), []
    real = verbs["sigs"]
    verbs["sigs"] = lambda msg: (asked.extend(msg["files"]), real(msg))[1]
    staged = copies_by_site().get("sig.stage", 0)
    c = _push(src, dst, verbs)
    assert [item["sign"] for item in asked] == [False] * len(sizes)
    assert c["rsync.files_skipped"] == len(sizes) - 1
    assert c["rsync.files_full"] == 1 and "rsync.files_delta" not in c
    assert c["rsync.literal_bytes"] == len(b"another body")
    assert copies_by_site().get("sig.stage", 0) == staged
    assert _clean(treecmp.compare(src, dst))


def _to_another_owner(f: Path) -> None:
    st = os.stat(f)
    if os.geteuid() == 0:
        os.chown(f, st.st_uid + 1234, st.st_gid + 4321)
        return
    groups = [g for g in os.getgroups() if g != st.st_gid]
    if not groups:
        pytest.skip("this user can give a file to no other group")
    os.chown(f, -1, groups[0])


def _xattr(f: Path, value: bytes = b"v") -> None:
    try:
        os.setxattr(f, "user.keep", value)
    except OSError:
        pytest.skip("the file system takes no user.* xattr")


#: what differs -> (before the first sync, at the source after it, the
#: changing calls the second sync makes on the destination's file, the
#: questions it asks about it)
SKIPPED_META = {
    "nothing": (None, None, [], ["stat"]),
    "mode": (None, lambda f: os.chmod(f, 0o600), ["chmod"], ["stat"]),
    # chown clears suid, so a chown brings its chmod
    "owner": (None, _to_another_owner, ["chown", "chmod"], ["stat"]),
    "xattr": (None, _xattr, ["setxattr"],
              ["stat", "listxattr", "listxattr"]),
    "xattr_value": (_xattr, lambda f: _xattr(f, b"other"), ["setxattr"],
                    ["stat", "listxattr", "getxattr", "listxattr"]),
    "xattr_held": (_xattr, None, [],
                   ["stat", "listxattr", "getxattr"]),
}


@pytest.mark.parametrize("differs", sorted(SKIPPED_META))
def test_a_skipped_files_metadata_converges(tmp_path, small_window,
                                            monkeypatch, differs):
    """``rsync -a`` sets permissions, owner and xattrs on a file its
    quick check skips: the file keeps its inode and its bytes are not
    read, its metadata ends as the source's, and the destination makes
    a changing call only where something differs. A file without
    xattrs at the source costs the destination ONE ``lstat``, and the
    source nothing after the walk's ``lstat`` and ``listxattr``."""
    before, drift, changing, asked = SKIPPED_META[differs]
    src, dst = tmp_path / "src", tmp_path / "dst"
    src.mkdir()
    dst.mkdir()
    sizes = _tree(src)
    f, g = src / "sub" / "f04", dst / "sub" / "f04"
    if before:
        before(f)
    _push(src, dst)
    was = os.stat(f)
    if drift:
        drift(f)
    assert os.stat(f).st_mtime_ns == was.st_mtime_ns  # ctime alone moved
    inode, calls = os.lstat(g).st_ino, []
    _record_calls(monkeypatch, ASKING + CHANGING, calls)
    c = _push(src, dst)
    monkeypatch.undo()
    assert c["rsync.files_skipped"] == len(sizes)
    there = [name for name, path in calls if path == str(g)]
    assert [n for n in there if n in CHANGING] == changing
    assert [n for n in there if n in ASKING] == asked
    assert [name for name, path in calls if path == str(f)] \
        == ["stat", "listxattr"] + ["getxattr"] * differs.startswith("xattr")
    # no other regular file got a changing call either
    assert {path for name, path in calls if name in CHANGING
            and os.path.isfile(path) and not os.path.islink(path)} \
        == ({str(g)} if changing else set())
    st, want = os.lstat(g), os.lstat(f)
    assert st.st_ino == inode
    assert (st.st_mode, st.st_uid, st.st_gid, st.st_mtime_ns) \
        == (want.st_mode, want.st_uid, want.st_gid, want.st_mtime_ns)
    assert {n: os.getxattr(g, n) for n in os.listxattr(g)} \
        == {n: os.getxattr(f, n) for n in os.listxattr(f)}
    assert _clean(treecmp.compare(src, dst))


@pytest.mark.parametrize("there", ["symlink", "directory", "nothing"])
def test_what_is_no_regular_file_there_is_not_skipped(synced, there):
    """The check is an ``lstat``: a symlink under the name whose target
    has the source's size and mtime, a directory or nothing is no
    basis, and the push puts the file there."""
    src, dst, sizes = synced
    g = dst / "sub" / "f04"
    os.unlink(g)
    if there == "symlink":
        held = dst / "sub" / "held"
        held.write_bytes((src / "sub" / "f04").read_bytes())
        was = os.stat(src / "sub" / "f04")
        os.utime(held, ns=(was.st_atime_ns, was.st_mtime_ns))
        os.symlink("held", g)
        assert os.stat(g).st_mtime_ns == was.st_mtime_ns
    elif there == "directory":
        (g / "inside").mkdir(parents=True)
    c = _push(src, dst)
    assert c["rsync.files_skipped"] == len(sizes) - 1
    assert c["rsync.files_new"] == 1
    assert g.is_file() and not g.is_symlink()
    assert _clean(treecmp.compare(src, dst))


def test_a_change_behind_an_equal_size_and_mtime_is_left(synced):
    """rsync's stated semantics, the configuration's guarantee (b), and
    no accident: a bit flipped at the destination with the size and
    mtime put back is NOT found by the next push (there is no ``-c``),
    and is found by the first push that sees another mtime."""
    src, dst, sizes = synced
    f, g = src / "sub" / "f04", dst / "sub" / "f04"
    was, body = os.stat(g), bytearray(g.read_bytes())
    body[len(body) // 2] ^= 0x10
    g.write_bytes(bytes(body))
    os.utime(g, ns=(was.st_atime_ns, was.st_mtime_ns))
    c = _push(src, dst)
    assert c["rsync.files_skipped"] == len(sizes)
    assert g.read_bytes() == bytes(body) != f.read_bytes()
    os.utime(g, ns=(was.st_atime_ns, was.st_mtime_ns - 1))
    c = _push(src, dst)
    assert (c["rsync.files_skipped"], c["rsync.files_delta"]) \
        == (len(sizes) - 1, 1)
    assert c["rsync.literal_bytes"] == 4096  # the block that holds the bit
    assert g.read_bytes() == f.read_bytes()
    assert _clean(treecmp.compare(src, dst))


@pytest.mark.parametrize("older", ["source", "destination"])
def test_the_quick_check_is_additive_on_the_wire(synced, older):
    """A mixed pair of movers scans: a ``sigs`` request without ``size``
    (an older source's) is never answered ``same`` but with the file's
    signature, and a reply without ``same`` (an older destination's: it
    reads ``path`` and ``block_len`` alone) is scanned as before."""
    src, dst, sizes = synced
    verbs = entry._dest_verbs(dst)
    real = verbs["sigs"]
    if older == "source":
        out = real({"files": [{"path": rel, "block_len": 4096}
                              for rel in sizes]})
        assert not any("same" in r for r in out["sigs"])
        assert [r["size"] for r in out["sigs"]] == list(sizes.values())
        assert all(len(r["strong"]) == 16 * -(-r["size"] // 4096)
                   for r in out["sigs"])
        return
    verbs["sigs"] = lambda msg: real({**msg, "files": [
        {"path": item["path"], "block_len": item["block_len"]}
        for item in msg["files"]]})
    c = _push(src, dst, verbs)
    assert c["rsync.files_delta"] == c["rsync.files"] == len(sizes)
    assert "rsync.files_skipped" not in c and c["rsync.literal_bytes"] == 0
    assert _clean(treecmp.compare(src, dst))


def test_a_skipped_first_name_still_gets_its_later_names_linked(synced):
    """rsync -H through the quick check: the first name of a hardlinked
    inode is skipped, and a later name the destination lost is linked
    to it again."""
    src, dst, sizes = synced
    os.link(src / "f03", src / "sub" / "f03.again")
    os.link(src / "f03", src / "z.third")  # the walk meets f03 first
    _push(src, dst)
    os.unlink(dst / "sub" / "f03.again")
    (dst / "z.third").unlink()
    (dst / "z.third").write_bytes(b"a file of its own")
    inode = os.lstat(dst / "f03").st_ino
    c = _push(src, dst)
    assert c["rsync.files_skipped"] == len(sizes)
    assert c["rsync.files"] == len(sizes) + 2
    for name in ("f03", "sub/f03.again", "z.third"):
        assert os.lstat(dst / name).st_ino == inode, name
    assert _clean(treecmp.compare(src, dst))


@pytest.mark.parametrize("place", ["first", "later"])
def test_sigs_holds_every_name_of_a_directory_inside_the_root(tmp_path,
                                                              place):
    """The once-a-directory resolve holds what ``_safe_join`` held: a
    destination directory that is a symlink out of the root is refused
    for the first name under it in a batch and for a later one (the
    directory already resolved), and nothing outside is asked about or
    changed."""
    outside, dst = tmp_path / "outside", tmp_path / "dst"
    outside.mkdir()
    (dst / "in").mkdir(parents=True)
    (dst / "in" / "ok").write_bytes(b"k" * 100)
    for name in ("x", "y"):
        (outside / name).write_bytes(b"s" * 5000)
    os.symlink(outside, dst / "dir")
    was = os.stat(outside / "y")

    def item(rel):
        return {"path": rel, "block_len": 4096, "size": was.st_size,
                "mtime_ns": was.st_mtime_ns, "mode": 0o600}

    names = {"first": ["dir/y", "in/ok"],
             "later": ["in/ok", "dir/x", "dir/y"]}[place]
    calls = []
    with pytest.MonkeyPatch.context() as mp:
        _record_calls(mp, ASKING + CHANGING, calls)
        with pytest.raises(entry.channel.ChannelError):
            entry._dest_verbs(dst)["sigs"](
                {"files": [item(rel) for rel in names]})
        # a request that begins with a good name of the directory
        # cannot carry a bad one in behind it
        with pytest.raises(entry.channel.ChannelError):
            entry._dest_verbs(dst)["sigs"](
                {"files": [item("in/ok"), item("in/../dir/y")]})
    assert not [c for c in calls if c[1].startswith(str(outside) + os.sep)]
    assert os.stat(outside / "y").st_mode == was.st_mode


def test_sigs_resolves_a_directory_once_a_call(synced, monkeypatch):
    """Six names in three directories: the root and each directory are
    resolved once in the call, not twice a file."""
    src, dst, sizes = synced
    resolved = []
    real = Path.resolve
    monkeypatch.setattr(
        Path, "resolve",
        lambda self, *a, **kw: (resolved.append(str(self)),
                                real(self, *a, **kw))[1])
    out = entry._dest_verbs(dst)["sigs"]({"files": [
        {"path": rel, "block_len": 4096, "size": n,
         "mtime_ns": os.lstat(src / rel).st_mtime_ns}
        for rel, n in sizes.items()]})
    monkeypatch.undo()
    assert out["sigs"] == [{"exists": True, "same": True}] * len(sizes)
    assert sorted(resolved) == sorted(
        str(dst / d) for d in ("", "", "sub", "sub/deeper"))


def _compiles():
    import jax.monitoring as mon

    seen = []
    mon.register_event_duration_secs_listener(
        lambda event, _secs, **_kw: seen.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    return seen


def test_forty_file_sizes_load_no_more_programs_than_four(
        tmp_path, monkeypatch):
    """Every device program of the path takes its shape from the block
    length, not from the data: a window no other test uses (so nothing
    is loaded yet), a tree of 4 file sizes pushed, churned and pushed
    again loads the path's programs; a tree of 40 other sizes, and a
    second push of it, load none."""
    monkeypatch.setattr(deltasync, "WINDOW", 1 << 15)
    monkeypatch.setenv("VOLSYNC_SYNC_PROTO", "delta")
    rng = np.random.default_rng(SEED)
    seen = _compiles()

    def two_pushes(name: str, sizes: list[int]) -> int:
        src, dst = tmp_path / name, tmp_path / f"{name}-dst"
        src.mkdir()
        dst.mkdir()
        for i, n in enumerate(sizes):
            (src / f"f{i:02d}").write_bytes(rng.bytes(n))
        before = len(seen)
        entry._push_tree(_Chan(entry._dest_verbs(dst)), src)
        for i, n in enumerate(sizes):  # an insertion and a rewrite each
            body = (src / f"f{i:02d}").read_bytes()
            (src / f"f{i:02d}").write_bytes(
                body[: n // 2] + rng.bytes(33) + body[n // 2: -100]
                + rng.bytes(100))
        entry._push_tree(_Chan(entry._dest_verbs(dst)), src)
        assert treecmp.compare(src, dst)["content"] == []
        return len(seen) - before

    four = two_pushes("four", [9_000, 20_000, 70_001, 33_333])
    assert 3 <= four <= 8  # signature, search, strong check (+ helpers)
    forty = two_pushes("forty", [5_000 + 1_777 * i for i in range(40)])
    assert forty == 0


SOURCE_SPANS = ("rsync.connect", "rsync.walk", "rsync.sig_wait",
                "delta.stage", "delta.launch", "delta.fetch",
                "delta.verify", "delta.select", "rsync.finish")
DEST_SPANS = ("rsync.sig", "sig.stage", "sig.launch", "sig.fetch",
              "rsync.prune", "rsync.dirmeta")
OFF_RING = ("rsync.read", "rsync.apply_wait", "rsync.apply")
COUNTERS = ("rsync.files", "rsync.bytes_synced", "rsync.literal_bytes",
            "rsync.copied_bytes", "rsync.files_delta", "rsync.files_new",
            "rsync.files_skipped", "rsync.bytes_skipped",
            "rsync.pruned", "rsync.frames", "delta.batches", "delta.files",
            "delta.bytes_valid", "delta.bytes_padded", "delta.candidates",
            "delta.verified")


def test_spans_and_counters_are_recorded_on_their_threads(
        tmp_path, small_window, monkeypatch):
    """A churned push under a sampled trace on both threads: the spans
    of PERF.md's table are in the flight recorder on the thread the
    table says (the once-a-file ones in the totals alone), and the
    counters the cell's metrics read are counted."""
    a, b = _write_states(tmp_path)
    st = _state(tmp_path)
    assert rsync_push.push(st, {"root": a})["rc"] == 0
    real = entry.rsync_destination_entrypoint
    listener = {}

    def traced(ctx):
        listener["tid"] = threading.get_ident()
        with trace_context(sampled=True):
            return real(ctx)

    monkeypatch.setattr(entry, "rsync_destination_entrypoint", traced)
    reset_spans()
    reset_trace()
    with trace_context(sampled=True):
        got = rsync_push.push(st, {"root": b})
    assert (got["rc"], got["dst_rc"]) == (0, 0)
    where: dict = {}
    for e in trace_events():
        where.setdefault(e["name"], set()).add(e["tid"])
    for name in SOURCE_SPANS:
        assert where.get(name) == {threading.get_ident()}, name
    for name in DEST_SPANS:
        assert where.get(name) == {listener["tid"]}, name
    totals = span_totals()
    for name in OFF_RING:
        assert name not in where and totals[name][0] > 0, name
    counts = counter_totals()
    for name in COUNTERS:
        assert counts.get(name, 0) > 0, name
    assert "rsync.files_full" not in counts
    # the strong check's three are inside it
    assert totals["delta.verify"][1] >= sum(
        totals[f"delta.verify_{k}"][1] for k in ("stage", "launch", "fetch"))
