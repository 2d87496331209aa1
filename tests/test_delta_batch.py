"""Batched delta scan: golden byte-identity vs the unwindowed oracle,
device dispatch accounting, and the bidirectional rsync convergence
scenario.

The oracle is ``compute_delta`` per file (one exact-shape scan of the
whole file): ``delta_scan_batch`` must emit the exact same op streams
(not merely equivalent ones) from its staged buffers, whatever the
window, because both share the host-side greedy selection and the flat
kernels reproduce the per-file verified sets.
"""

import os
import pathlib

import numpy as np
import pytest

from volsync_tpu.engine import deltasync
from volsync_tpu.engine.syncstats import reset_books
from volsync_tpu.obs import counter_totals
from volsync_tpu.ops import delta


@pytest.fixture(autouse=True)
def _clean_books():
    reset_books()
    yield
    reset_books()


def _corpus(rng):
    """(old_bytes, new_bytes) pairs covering the engine's edge cases."""
    base = rng.bytes(200_000)
    shifted = base[:50_000] + b"INSERT" + base[50_000:]
    edited = bytearray(base)
    edited[10_000:10_100] = rng.bytes(100)
    edited[150_000:150_001] = b""
    taily = rng.bytes(4096 * 3 + 789)  # partial tail block
    several = bytearray(base)
    for at, n in ((180_000, 5), (120_500, 1_300), (118_000, 1),
                  (117_000, 640), (40_000, 4_097)):
        several[at: at] = rng.bytes(n)
    del several[90_000: 90_777]
    zeros = base[:70_000] + bytes(60_000) + base[130_000:]
    periodic = base[:37_000] + base[:512] * 80 + base[37_000:]
    return [
        (base, bytes(several)),             # insertions inside one window
        (zeros, zeros[:69_000] + b"\x01" + zeros[69_000:]),  # zeros moved
        (periodic, periodic[:36_900] + rng.bytes(512) + periodic[36_900:]),
        (base, base),                       # identical -> zero DATA ops
        (base, shifted),                    # insertion, offsets slide
        (base, bytes(edited)),              # scattered edits
        (taily, taily[:4096 * 2] + rng.bytes(4096 + 789)),  # tail churn
        (b"", rng.bytes(10_000)),           # no basis blocks at dest
        (rng.bytes(10_000), b""),           # empty source
        (rng.bytes(512), rng.bytes(300)),   # sub-block source
        (rng.bytes(300), rng.bytes(512)),   # sub-block destination
        (rng.bytes(64_000), rng.bytes(64_000)),  # unrelated content
        (base, base[100_000:] + base[:100_000]),  # rotation
    ]


def _items(pairs):
    out = []
    for old, new in pairs:
        sig = deltasync.build_file_signature(
            old, deltasync.pick_block_len(max(len(old), len(new))))
        out.append((new, sig))
    return out


@pytest.mark.parametrize("window", [64 << 20, 1 << 16, 1 << 14])
def test_batch_matches_serial_oracle(rng, monkeypatch, window):
    """At the mover's window, and at windows of sixteen and four blocks
    (a file then spans several buffers, and the small files share
    one)."""
    monkeypatch.setattr(deltasync, "WINDOW", window)
    pairs = _corpus(rng)
    items = _items(pairs)
    batch = deltasync.delta_scan_batch(items)
    for (old, new), (src, sig), ops in zip(pairs, items, batch):
        oracle = deltasync.compute_delta(src, sig)
        assert ops == oracle, f"divergence for pair {len(old)}->{len(new)}"
        assert deltasync.apply_delta(ops, old, sig.block_len) == new


def test_identical_trees_ship_zero_literal_bytes(rng):
    files = [rng.bytes(n) for n in (5_000, 80_000, 4096 * 4)]
    items = _items([(f, f) for f in files])
    for (_, sig), ops, f in zip(items, deltasync.delta_scan_batch(items),
                                files):
        assert all(op[0] == "copy" for op in ops), ops
        assert deltasync.delta_stats(ops, sig.block_len)["literal_bytes"] == 0


def test_mixed_block_lengths_group_correctly(rng):
    # explicit caller-chosen block lengths force distinct device groups
    # interleaved in one batch (build_file_signature allows overrides)
    pairs, items = [], []
    for i, bl in enumerate([1024, 4096, 1024, 8192, 4096, 1024]):
        old = rng.bytes(40_000 + i * 1000)
        new = bytearray(old)
        new[5_000:5_050] = rng.bytes(50)
        pairs.append((old, bytes(new)))
        items.append((bytes(new),
                      deltasync.build_file_signature(old, bl)))
    sizes = {sig.block_len for _, sig in items}
    assert len(sizes) >= 2, "corpus failed to span block-length groups"
    batch = deltasync.delta_scan_batch(items)
    for (old, new), (src, sig), ops in zip(pairs, items, batch):
        assert ops == deltasync.compute_delta(src, sig)
        assert deltasync.apply_delta(ops, old, sig.block_len) == new


@pytest.mark.parametrize("window", [64 << 20, 1 << 20])
def test_batch_uses_fewer_dispatches_than_files(rng, monkeypatch, window):
    """N files, ONE aligned probe, ONE search and ONE verify dispatch
    a staged buffer, not one per file (each file here has an insertion:
    the selection is followed through the searched rows of the one
    buffer, none is staged again). At the small window the open rows
    pass one group of the search's loop (256 rows there too): a buffer
    is still searched in one dispatch."""
    monkeypatch.setattr(deltasync, "WINDOW", window)
    calls = {"probe": 0, "match": 0, "verify": 0}
    real_probe = deltasync.delta_sig_flat
    real_match = deltasync.delta_match_rows
    real_verify = deltasync.delta_md5_flat

    def spy_probe(*a, **kw):
        calls["probe"] += 1
        return real_probe(*a, **kw)

    def spy_match(*a, **kw):
        calls["match"] += 1
        return real_match(*a, **kw)

    def spy_verify(*a, **kw):
        calls["verify"] += 1
        return real_verify(*a, **kw)

    base = rng.bytes(60_000)
    pairs = []
    for i in range(8):
        mutated = bytearray(base)
        mutated[i * 1000:i * 1000] = rng.bytes(50)
        pairs.append((base, bytes(mutated)))
    items = _items(pairs)
    assert len({sig.block_len for _, sig in items}) == 1
    geo = deltasync._Geometry.of(items[0][1].block_len)
    monkeypatch.setattr(deltasync, "delta_sig_flat", spy_probe)
    monkeypatch.setattr(deltasync, "delta_match_rows", spy_match)
    monkeypatch.setattr(deltasync, "delta_md5_flat", spy_verify)
    before = counter_totals()
    batch = deltasync.delta_scan_batch(items)
    now = counter_totals()
    listed = now["delta.search_rows"] - before.get("delta.search_rows", 0)
    assert listed > geo.group_rows, "the case no longer passes one group"
    assert calls["probe"] == 1
    assert calls["match"] == 1
    assert 1 <= calls["verify"] < len(items)
    for (old, new), (src, sig), ops in zip(pairs, items, batch):
        assert ops == deltasync.compute_delta(src, sig)


# -- the search program against the every-offset oracle ----------------------

_BLOCK, _GROUP, _CAP = 2048, 8, 64


def _search_case(rng, zeros: bool = False):
    """One 64 KiB buffer (64 rows) and the sorted weak table of a
    destination whose blocks lie in it off the alignment (from byte 37,
    so nearly every row holds a match), and ``match_offsets``' answer.
    ``zeros``: a zero-filled half against a table that holds the zero
    block, so every offset of it is a hit."""
    data = bytearray(rng.bytes(1 << 16))
    if zeros:
        data[1 << 15:] = bytes(1 << 15)
    data = np.frombuffer(bytes(data), np.uint8)
    blocks = [data[at: at + _BLOCK]
              for at in range(37, len(data) - _BLOCK, 1500)]
    table = np.sort(np.array(
        [deltasync.weak_checksum_host(b) for b in blocks]
        + ([deltasync.weak_checksum_host(bytes(_BLOCK))] if zeros else []),
        np.uint32))
    cand, n = delta.match_offsets(data, table, window=_BLOCK,
                                  max_candidates=1 << 16)
    oracle = np.asarray(cand)[: int(n)]
    assert len(oracle) >= len(blocks)
    return data, table, oracle


def _search(data, table, rows, until):
    """The engine's loop over ``delta_match_rows`` at a group of
    ``_GROUP`` rows: (offsets, their weak checksums, groups run, calls)."""
    R = len(data) // 1024
    sw = np.full(64, 0xFFFFFFFF, np.uint32)
    sw[: len(table)] = table
    take, take_until = np.zeros(R, np.int32), np.zeros(R, np.int32)
    take[: len(rows)] = rows
    take_until[: len(rows)] = until
    groups = -(-len(rows) // _GROUP)
    group = lo = ran_all = calls = 0
    offs, weaks = [], []
    while group < groups:
        cand, weak, state = delta.delta_match_rows(
            data, sw, np.int32(len(table)), take, take_until,
            np.int32(groups), np.int32(group), np.int32(lo),
            window=_BLOCK, group_rows=_GROUP, max_candidates=_CAP,
            capacity=2 * _CAP)
        n, nxt, ran = np.asarray(state).tolist()
        assert nxt > group or (nxt == group and n == _CAP)
        cand = np.asarray(cand)
        assert (cand[n:] == len(data)).all()
        assert (np.diff(cand[:n]) > 0).all()
        offs += cand[:n].tolist()
        weaks += np.asarray(weak)[:n].tolist()
        group, ran_all, calls = nxt, ran_all + ran, calls + 1
        if group < groups:
            lo = offs[-1] + 1
    return offs, weaks, ran_all, calls


@pytest.mark.parametrize("listed", [0, 1, _GROUP - 1, _GROUP, _GROUP + 1,
                                    3 * _GROUP + 2, 64])
def test_search_finds_what_every_offset_scan_finds(rng, listed):
    """Rows listed: none, one, a group less one, a group, a group and
    one, several groups, every row of the buffer. The candidates are the
    oracle's offsets inside the listed rows, ascending, with their
    checksums, and the loop runs the groups that hold a row."""
    data, table, oracle = _search_case(rng)
    rows = np.sort(rng.choice(64, listed, replace=False)).astype(np.int32)
    last = len(data) - _BLOCK
    offs, weaks, ran, calls = _search(data, table, rows,
                                      [last + 1] * listed)
    want = oracle[np.isin(oracle // 1024, rows)]
    assert offs == want.tolist()
    assert listed == 0 or len(want) > 0
    assert weaks == deltasync._weak_at_offsets(
        data, np.asarray(offs, np.int64), _BLOCK).tolist()
    assert ran == -(-listed // _GROUP)
    assert calls == (1 if listed else 0)


@pytest.mark.parametrize("until", ["file_end", "padding", "mid_row"])
def test_search_masks_by_row_until(rng, until):
    """A window that would run past its file's end, and the padding
    between slots, are not searched: ``row_until`` cuts each listed row
    at its own file's last window start."""
    data, table, oracle = _search_case(rng)
    rows = np.arange(64, dtype=np.int32)
    if until == "file_end":  # two files of 32 rows: each ends its rows
        ends = np.where(rows < 32, 32 * 1024, 64 * 1024) - _BLOCK + 1
    elif until == "padding":  # every other eight rows lie between slots
        ends = np.where((rows // 8) % 2 == 0, 64 * 1024 - _BLOCK + 1, 0)
    else:  # the file ends inside row 40
        ends = np.full(64, 40 * 1024 + 300, np.int64)
    offs, _weaks, _ran, _calls = _search(data, table, rows, ends)
    want = oracle[oracle < ends[oracle // 1024]]
    assert 0 < len(want) < len(oracle)
    assert offs == want.tolist()


@pytest.mark.parametrize("listed", [64, 20])
def test_search_overflow_goes_on_from_lo(rng, listed):
    """A run of one repeated block (a zero-filled half whose block the
    table holds): every offset of it is a candidate, more than a call
    holds. Each call takes the next ones from ``lo`` on, from the group
    it stopped at, and together they are the oracle's."""
    data, table, oracle = _search_case(rng, zeros=True)
    rows = np.arange(64 - listed, 64, dtype=np.int32)
    last = len(data) - _BLOCK
    offs, _weaks, ran, calls = _search(data, table, rows,
                                       [last + 1] * listed)
    want = oracle[np.isin(oracle // 1024, rows)]
    assert len(want) > 16 * 1024
    assert offs == want.tolist()
    assert calls > len(want) // (2 * _CAP)
    assert ran >= calls


@pytest.mark.parametrize("open_blocks", [0, 1, 3, 16])
def test_search_counters_follow_the_open_rows(rng, monkeypatch, open_blocks):
    """A buffer with k open blocks counts the rows the engine listed
    (a block's rows each, here 4), and the rows the device looked up
    are whole groups: never under the rows listed."""
    monkeypatch.setattr(deltasync, "WINDOW", 1 << 16)
    old = rng.bytes(1 << 16)
    new = bytearray(old)
    for t in range(open_blocks):  # not the last block: its run ends early
        new[(3 * t) % 15 * 4096 + 5: (3 * t) % 15 * 4096 + 9] = b"\0\1\2\3"
    opened = len({(3 * t) % 15 for t in range(open_blocks)})
    sig = deltasync.build_file_signature(old, 4096)
    before = counter_totals()
    ops = deltasync.delta_scan_batch([(bytes(new), sig)])[0]
    now = counter_totals()
    assert ops == deltasync.compute_delta(bytes(new), sig)
    listed = now.get("delta.search_rows", 0) \
        - before.get("delta.search_rows", 0)
    ran = now.get("delta.search_rows_run", 0) \
        - before.get("delta.search_rows_run", 0)
    geo = deltasync._Geometry.of(4096)
    assert listed == 4 * opened
    assert ran >= listed
    assert ran == (geo.group_rows if opened else 0)


def test_serial_kernels_not_called_by_batch(rng, monkeypatch):
    """The batch path must never fall back to per-file device scans."""
    from volsync_tpu.engine import deltasync as ds

    def boom(*a, **kw):  # pragma: no cover - failure path
        raise AssertionError("serial kernel used by batch path")

    monkeypatch.setattr(ds, "match_offsets", boom)
    base = rng.bytes(50_000)
    items = _items([(base, base + b"tail")] * 4)
    out = ds.delta_scan_batch(items)
    assert len(out) == 4


# -- bidirectional sync scenario ---------------------------------------------


class _Chan:
    """Loopback channel: dispatch directly into the dest verb table."""

    def __init__(self, verbs):
        self.verbs = verbs
        self.reply = None

    def send(self, msg):
        self.reply = self.verbs[msg["verb"]](msg)

    def recv(self):
        return self.reply


def _tree_bytes(root: pathlib.Path) -> dict:
    out = {}
    for dirpath, _dirs, files in os.walk(root):
        for name in files:
            p = pathlib.Path(dirpath, name)
            out[str(p.relative_to(root))] = p.read_bytes()
    return out


def test_bidirectional_sync_converges_with_delta(tmp_path, rng, monkeypatch):
    """Two trees, pushed A->B then (after divergent edits) B->A: both
    directions run the DELTA path and the trees end byte-identical.
    The protocol is pinned as the rsync cell's ``mover_env`` pins it:
    under several test workers the planner prices FULL from the timings
    it takes itself, and this test is about the delta path."""
    from volsync_tpu.engine.syncstats import book_for
    from volsync_tpu.movers.rsync import entry

    monkeypatch.setenv("VOLSYNC_SYNC_PROTO", "delta")

    a = tmp_path / "a"
    b = tmp_path / "b"
    a.mkdir()
    b.mkdir()
    # Sized so transfer time dominates the loopback ack latency: on an
    # in-memory link the model CORRECTLY prices tiny files as FULL
    # (one round trip saved beats a few hundred KB), so a delta regime
    # needs megabyte files even here.
    payload = rng.bytes(4 << 20)
    (a / "data.bin").write_bytes(payload)
    (a / "logs").mkdir()
    (a / "logs" / "app.log").write_bytes(rng.bytes(1 << 20))

    # round 1: cold push A->B (planner probes delta; dest has no basis,
    # so everything ships as literals either way)
    stats = entry._push_tree(_Chan(entry._dest_verbs(b)), a)
    assert _tree_bytes(b) == _tree_bytes(a)
    assert stats["literal_bytes"] == stats["bytes"]

    # divergent edits on both sides
    edited = bytearray(payload)
    edited[1000:1050] = rng.bytes(50)
    (a / "data.bin").write_bytes(bytes(edited))
    with open(b / "logs" / "app.log", "ab") as f:
        f.write(rng.bytes(8_000))

    # round 2: A->B moves only data.bin's changed bytes as literals
    stats = entry._push_tree(_Chan(entry._dest_verbs(b)), a)
    assert _tree_bytes(b) == _tree_bytes(a)
    assert stats["copied_bytes"] > 0, "delta never engaged A->B"
    assert stats["literal_bytes"] < len(payload) // 4

    # round 3: B grows its own changes; push B->A must delta the other
    # way (a file B left as round 2 put it has A's size and mtime, and
    # rsync's quick check skips it: data.bin changes too)
    with open(b / "logs" / "app.log", "ab") as f:
        f.write(rng.bytes(8_000))
    with open(b / "data.bin", "r+b") as f:
        f.seek(2_000_000)
        f.write(rng.bytes(50))
    stats = entry._push_tree(_Chan(entry._dest_verbs(a)), b)
    assert _tree_bytes(a) == _tree_bytes(b)
    assert stats["copied_bytes"] > 0, "delta never engaged B->A"
    assert stats["literal_bytes"] < stats["bytes"]

    # the rsync book saw real delta runs and link samples
    s = book_for("rsync").snapshot()
    assert s.delta_samples > 0


def test_a_batch_shares_one_sigs_round_trip(tmp_path, rng):
    """Five files under one window of bytes go through ONE ``sigs``
    round trip (there is no per-file ``sig`` verb), and a resync after
    appends converges."""
    from volsync_tpu.movers.rsync import entry

    src = tmp_path / "src"
    dst = tmp_path / "dst"
    src.mkdir()
    dst.mkdir()
    for i in range(5):
        (src / f"f{i}.bin").write_bytes(rng.bytes(20_000))

    seen = {"sigs": 0}
    verbs = entry._dest_verbs(dst)
    assert "sig" not in verbs
    real_sigs = verbs["sigs"]
    verbs["sigs"] = lambda m: (seen.__setitem__("sigs", seen["sigs"] + 1),
                               real_sigs(m))[1]
    entry._push_tree(_Chan(verbs), src)
    assert _tree_bytes(dst) == _tree_bytes(src)

    for i in range(5):
        with open(src / f"f{i}.bin", "ab") as f:
            f.write(b"delta")
    seen.update(sigs=0)
    entry._push_tree(_Chan(verbs), src)
    assert seen["sigs"] == 1
    assert _tree_bytes(dst) == _tree_bytes(src)
