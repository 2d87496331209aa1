"""Metadata-plane property tests: CompactIndex and ShardedBlobIndex
fuzzed against a plain-dict model, batched-vs-scalar equivalence, the
eager-snapshot iteration contract, and the bloom prefilter's
no-false-negative guarantee.

The fuzz drives every mutating op (insert, replace, setdefault-insert,
remove, vacuum, copy) from tiny capacities so table rebuilds and
tombstone reuse happen constantly, then checks the index agrees with
the dict byte for byte. The bulk insert (``insert_many``, what
``load_index`` places a whole load with) is held to ``insert`` an entry
on the same steps, order of rows and of interned names included, and
the slot table's numpy placement to the list loop it replaced. Snapshot
keys are compared as raw 32-byte
values (S32), never via ``.hex()`` of a ``tolist()`` round-trip —
numpy strips trailing NULs from S32 scalars.
"""

import threading
import zlib

import numpy as np
import pytest

from volsync_tpu.repo.compactindex import (
    _EMPTY,
    CompactIndex,
    as_key_rows,
    id_bytes,
    place_slots,
)
from volsync_tpu.repo.shardedindex import (
    BloomPrefilter,
    ShardedBlobIndex,
    _SMALL_BATCH_PER_SHARD,
)


def hex_ids(rng, n):
    raw = rng.bytes(32 * n)
    return [raw[i * 32:(i + 1) * 32].hex() for i in range(n)]


def make_indexes():
    return [
        ("compact", CompactIndex(capacity=16)),
        ("sharded1", ShardedBlobIndex(shards=1, capacity=16)),
        ("sharded4", ShardedBlobIndex(shards=4, capacity=16)),
        ("sharded16-nofilter",
         ShardedBlobIndex(shards=16, capacity=16, prefilter=False)),
    ]


def check_equals_model(idx, model):
    assert len(idx) == len(model)
    assert dict(idx.items()) == model
    for k, v in model.items():
        assert k in idx
        assert idx.lookup(k) == v
    assert idx.live_packs() == {v[0] for v in model.values()}
    keys, codes, names = idx.snapshot_arrays()
    raw = keys.tobytes()  # S32 .tolist() would strip trailing NULs
    snap = {raw[i * 32:(i + 1) * 32]: names[c]
            for i, c in enumerate(codes.tolist())}
    want = {bytes.fromhex(k): v[0] for k, v in model.items()}
    assert snap == want


@pytest.mark.parametrize("name,idx", make_indexes())
def test_fuzz_against_dict_model(name, idx):
    rng = np.random.RandomState(zlib.crc32(name.encode()) % 2**31)
    universe = hex_ids(rng, 400)
    model = {}
    for step in range(3000):
        op = rng.randint(100)
        k = universe[rng.randint(len(universe))]
        if op < 55:
            entry = (f"p{rng.randint(6)}", "data", int(rng.randint(2**20)),
                     int(rng.randint(1, 2**16)), int(rng.randint(1, 2**16)))
            replace = bool(rng.randint(2))
            changed = idx.insert(k, *entry, replace=replace)
            if replace or k not in model:
                assert changed
                model[k] = entry
            else:
                assert not changed
        elif op < 85:
            assert idx.remove(k) == (k in model)
            model.pop(k, None)
        elif op < 93:
            assert idx.lookup(k) == model.get(k)
            assert (k in idx) == (k in model)
        elif op < 97:
            idx.vacuum()
        else:
            # copies are deep: mutating the original never leaks in
            snap = idx.copy()
            expect = dict(model)
            idx.insert(universe[0], "pX", "data", 1, 2, 3)
            idx.remove(universe[1])
            assert dict(snap.items()) == expect
            idx = snap
            model = expect
    check_equals_model(idx, model)
    idx.vacuum()
    check_equals_model(idx, model)


@pytest.mark.parametrize("name,idx", make_indexes())
def test_insert_after_vacuum_to_empty(name, idx):
    # regression: vacuum with zero live entries used to truncate the
    # entry block to length 0, and the next insert's doubling grow
    # (0 * 2 == 0) then indexed past it
    rng = np.random.RandomState(29)
    ids = hex_ids(rng, 8)
    for i, h in enumerate(ids):
        idx.insert(h, "p0", "data", i, 1, 1)
    for h in ids:
        idx.remove(h)
    idx.vacuum()
    assert len(idx) == 0
    for i, h in enumerate(ids):
        assert idx.insert(h, "p1", "data", i, 2, 2)
    check_equals_model(
        idx, {h: ("p1", "data", i, 2, 2) for i, h in enumerate(ids)})


def test_tombstone_reuse_and_rebuild_boundaries():
    idx = CompactIndex(capacity=16)
    rng = np.random.RandomState(3)
    ids = hex_ids(rng, 64)
    # churn one key through insert/remove cycles: tombstoned slots must
    # be reused, not accumulate until lookups degrade or break
    for i in range(200):
        assert idx.insert(ids[0], "p0", "data", i, 1, 1)
        assert idx.lookup(ids[0])[2] == i
        assert idx.remove(ids[0])
    assert len(idx) == 0 and ids[0] not in idx
    # grow through several table rebuilds from the minimum capacity
    for i, h in enumerate(ids):
        idx.insert(h, "p0", "data", i, 1, 1)
    assert len(idx) == 64
    for i, h in enumerate(ids):
        assert idx.lookup(h) == ("p0", "data", i, 1, 1)


@pytest.mark.parametrize("name,idx", make_indexes())
def test_items_survives_mutation_while_iterating(name, idx):
    rng = np.random.RandomState(7)
    ids = hex_ids(rng, 50)
    for i, h in enumerate(ids):
        idx.insert(h, "p0", "data", i, 1, 1)
    expect = dict(idx.items())
    it = idx.items()
    seen = {}
    for n, (k, v) in enumerate(it):
        seen[k] = v
        if n == 10:
            # mutate hard mid-iteration: the eager snapshot must hold
            for h in ids[:20]:
                idx.remove(h)
            idx.insert(hex_ids(rng, 1)[0], "p9", "data", 0, 1, 1)
            idx.vacuum()
    assert seen == expect


@pytest.mark.parametrize("shards,prefilter", [(1, True), (4, True),
                                              (16, True), (16, False)])
def test_batched_matches_scalar(shards, prefilter):
    idx = ShardedBlobIndex(shards=shards, capacity=16, prefilter=prefilter)
    rng = np.random.RandomState(11)
    present = hex_ids(rng, 600)
    absent = hex_ids(rng, 600)
    for i, h in enumerate(present):
        idx.insert(h, f"p{i % 5}", "data", i, 1, 1)
    for h in present[:100]:
        idx.remove(h)
    idx.vacuum()
    keys = [k for pair in zip(present, absent) for k in pair]
    # both code paths: a batch under the per-shard threshold (scalar
    # probes) and the full batch (vectorized partition + probe)
    small = keys[:max(1, _SMALL_BATCH_PER_SHARD * shards // 2)]
    for batch in (small, keys):
        got = idx.contains_many(batch)
        assert got.dtype == np.bool_ and got.shape == (len(batch),)
        assert got.tolist() == [k in idx for k in batch]
        entries = idx.lookup_many(batch)
        assert entries == [idx.lookup(k) for k in batch]


def test_batched_accepts_all_key_forms():
    idx = ShardedBlobIndex(shards=4, capacity=16)
    rng = np.random.RandomState(13)
    ids = hex_ids(rng, 40)
    for i, h in enumerate(ids):
        if i % 2 == 0:
            idx.insert(h, "p0", "data", i, 1, 1)
    expect = [h in idx for h in ids]
    raw = b"".join(bytes.fromhex(h) for h in ids)
    forms = [
        ids,
        np.frombuffer(raw, dtype=np.uint8).reshape(-1, 32),
        np.frombuffer(raw, dtype="S32"),
        as_key_rows(ids),
    ]
    for form in forms:
        assert idx.contains_many(form).tolist() == expect
    with pytest.raises(ValueError):
        idx.contains_many(["ab"])  # not 32 bytes


def test_prefilter_never_false_negative():
    f = BloomPrefilter(capacity=256)
    rng = np.random.RandomState(17)
    rows = as_key_rows(hex_ids(rng, 512))  # 2x capacity: saturate hard
    f.add_rows(rows[:256])
    for r in rows[256:384]:
        f.add_one(r)
    added = rows[:384]
    assert f.maybe_contains_rows(added).all()
    assert 0.0 < f.saturation() < 1.0
    # false positives exist but stay a small minority even oversubscribed
    fresh = as_key_rows(hex_ids(rng, 2000))
    fp = float(f.maybe_contains_rows(fresh).mean())
    assert fp < 0.25


def test_prefilter_rebuilds_on_vacuum_and_overflow():
    idx = ShardedBlobIndex(shards=1, capacity=16, prefilter=True)
    rng = np.random.RandomState(19)
    ids = hex_ids(rng, 5000)
    for i, h in enumerate(ids):
        idx.insert(h, "p0", "data", i, 1, 1)
    # growth forced filter rebuilds; everything must still be found
    assert idx.contains_many(ids).all()
    for h in ids[:4000]:
        idx.remove(h)
    idx.vacuum()
    assert not idx.contains_many(ids[:4000]).any()
    assert idx.contains_many(ids[4000:]).all()
    assert 0.0 <= idx.prefilter_saturation() < 0.5


def test_concurrent_inserts_are_all_visible():
    idx = ShardedBlobIndex(shards=8, capacity=16)
    rng = np.random.RandomState(23)
    parts = [hex_ids(rng, 300) for _ in range(4)]

    def writer(part, w):
        for i, h in enumerate(part):
            idx.insert(h, f"p{w}", "data", i, 1, 1)

    threads = [threading.Thread(target=writer, args=(p, w),
                                name=f"test-index-writer-{w}")
               for w, p in enumerate(parts)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    every = [h for p in parts for h in p]
    assert len(idx) == len(every)
    assert idx.contains_many(every).all()


def test_snapshot_arrays_remap_under_concurrent_inserts():
    """snapshot_arrays merges per-shard pack_names into one global list
    by remapping each shard's local pack codes. Four writer threads
    share a small pool of pack names, so every shard interns the SAME
    packs in a DIFFERENT local order — any remap bug (stale local code,
    off-by-one on the merged list) surfaces as a key attributed to the
    wrong pack. A snapshotter races the writers the whole time: each
    snapshot it takes need not be a point-in-time cut, but must always
    be internally consistent and never mis-attribute a key."""
    idx = ShardedBlobIndex(shards=8, capacity=16)
    rng = np.random.RandomState(29)
    parts = [hex_ids(rng, 300) for _ in range(4)]
    packs = [f"pack-{c}" for c in "abcdefg"]
    expect = {}  # hex id -> pack name, every id inserted exactly once
    for w, part in enumerate(parts):
        for i, h in enumerate(part):
            expect[h] = packs[(w + i) % len(packs)]
    expect_raw = {bytes.fromhex(k): v for k, v in expect.items()}

    stop = threading.Event()
    errors: list[str] = []

    def writer(part, w):
        for i, h in enumerate(part):
            idx.insert(h, expect[h], "data", i, 1, 1)

    def snapshotter():
        while not stop.is_set():
            keys, codes, names = idx.snapshot_arrays()
            if len(names) != len(set(names)):
                errors.append(f"duplicate pack names: {names}")
                return
            if codes.shape[0] and int(codes.max()) >= len(names):
                errors.append(
                    f"code {int(codes.max())} out of range {len(names)}")
                return
            raw = keys.tobytes()
            for i, c in enumerate(codes.tolist()):
                k = raw[i * 32:(i + 1) * 32]
                if names[c] != expect_raw[k]:
                    errors.append(
                        f"{k.hex()} attributed to {names[c]}, "
                        f"expected {expect_raw[k]}")
                    return

    threads = [threading.Thread(target=writer, args=(p, w),
                                name=f"test-remap-writer-{w}")
               for w, p in enumerate(parts)]
    snap = threading.Thread(target=snapshotter, name="test-remap-snap")
    snap.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    snap.join()
    assert errors == []

    # the settled snapshot IS a point-in-time cut: exact contents
    keys, codes, names = idx.snapshot_arrays()
    raw = keys.tobytes()
    got = {raw[i * 32:(i + 1) * 32]: names[c]
           for i, c in enumerate(codes.tolist())}
    assert got == expect_raw
    assert set(names) == set(packs)
    assert idx.live_packs() == set(packs)


# -- the bulk insert against insert an entry ----------------------------------

BULK_KINDS = {
    "compact": lambda: CompactIndex(capacity=16),
    **{f"sharded{n}-{'filter' if on else 'nofilter'}":
       (lambda n=n, on=on: ShardedBlobIndex(shards=n, capacity=16,
                                            prefilter=on))
       for n in (1, 4, 16) for on in (True, False)},
}


def _entries(ids, rng, replace, packs=7):
    """(id, pack, type, offset, length, raw_length, replace) an id."""
    n = len(ids)
    rep = (rng.random_sample(n) < 0.5 if replace == "mixed"
           else np.full(n, replace))
    return [(h, f"pack{rng.randint(packs)}", ("data", "tree")[i % 5 == 0],
             int(rng.randint(2**40)), int(rng.randint(2**32)),
             int(rng.randint(2**32)), bool(r))
            for i, (h, r) in enumerate(zip(ids, rep))]


def _bulk(idx, entries, keys=None):
    """``entries`` through ``insert_many``: names in their order of
    appearance, a code an entry."""
    packs, kinds = {}, {}
    codes = [packs.setdefault(e[1], len(packs)) for e in entries]
    tcodes = [kinds.setdefault(e[2], len(kinds)) for e in entries]
    if keys is None:
        keys = [e[0] for e in entries]
    if isinstance(idx, CompactIndex):
        keys = as_key_rows(keys)
    return idx.insert_many(
        keys, list(packs), codes, list(kinds), tcodes,
        [e[3] for e in entries], [e[4] for e in entries],
        [e[5] for e in entries], [e[6] for e in entries])


def _one_by_one(idx, entries):
    before = len(idx)
    for h, pack, kind, off, length, raw, rep in entries:
        idx.insert(h, pack, kind, off, length, raw, replace=rep)
    return len(idx) - before


def _run(idx, steps, bulk):
    added = []
    for step, arg in steps:
        if step == "batch":
            added.append((_bulk if bulk else _one_by_one)(idx, arg))
        elif step == "remove":
            assert all([idx.remove(h) for h in arg])
        else:
            idx.vacuum()
    return added


def _state(idx):
    keys, codes, names = idx.snapshot_arrays()
    return (list(idx.items()), len(idx), idx.live_packs(),
            keys.tobytes(), codes.tolist(), names)


def _assert_same(one, many, asked):
    assert _state(many) == _state(one)
    assert list(many) == list(one) and _state(many.copy()) == _state(one)
    if isinstance(one, CompactIndex):
        batches = [asked]
    else:  # the scalar path of a batched question, and the vectorized
        batches = [asked[:_SMALL_BATCH_PER_SHARD // 2], asked]
    for batch in batches:
        assert (many.contains_many(batch).tolist()
                == one.contains_many(batch).tolist()
                == [h in one for h in batch])
        assert (many.lookup_many(batch) == one.lookup_many(batch)
                == [many.lookup(h) for h in batch])


def _steps(case, rng):
    ids, absent = hex_ids(rng, 1400), hex_ids(rng, 700)
    if case == "duplicates-in-a-batch-replace":
        # an id three times in one batch: the last occurrence wins
        listed = ids[:900] + ids[100:500] + ids[300:400]
        steps = [("batch", _entries(listed, rng, True))]
    elif case == "duplicates-in-a-batch-keep":
        listed = ids[:900] + ids[100:500] + ids[300:400]
        steps = [("batch", _entries(listed, rng, False))]  # the first
    elif case == "duplicates-in-a-batch-mixed":
        # the last that replaces if any does, else the first
        listed = ids[:900] + ids[:900] + ids[200:700] + ids[850:1000]
        steps = [("batch", _entries(listed, rng, "mixed"))]
    elif case == "duplicates-across-two-batches":
        steps = [("batch", _entries(ids[:800], rng, "mixed")),
                 ("batch", _entries(ids[400:1200] + ids[300:500], rng,
                                    "mixed"))]
    elif case == "remove-then-bulk":
        # tombstones in the table and dead rows under the batch; a
        # removed id comes back as a new row
        steps = [("batch", _entries(ids[:600], rng, True)),
                 ("remove", ids[100:400]),
                 ("batch", _entries(ids[300:1000], rng, "mixed"))]
    elif case == "bulk-then-remove-then-vacuum":
        steps = [("batch", _entries(ids[:1000] + ids[:50], rng, "mixed")),
                 ("remove", ids[200:900:2]),
                 ("vacuum", None),
                 ("batch", _entries(ids[800:1400], rng, "mixed", packs=11)),
                 ("remove", ids[1300:1400]),
                 ("vacuum", None)]
    elif case == "an-empty-batch":
        steps = [("batch", []), ("batch", _entries(ids[:40], rng, True)),
                 ("batch", [])]
    else:
        raise AssertionError(case)
    return steps, ids + absent


@pytest.mark.parametrize("case", [
    "duplicates-in-a-batch-replace", "duplicates-in-a-batch-keep",
    "duplicates-in-a-batch-mixed", "duplicates-across-two-batches",
    "remove-then-bulk", "bulk-then-remove-then-vacuum", "an-empty-batch"])
@pytest.mark.parametrize("kind", list(BULK_KINDS))
def test_bulk_insert_leaves_what_insert_an_entry_leaves(kind, case):
    """The same steps with every batch through ``insert`` an entry and
    through ``insert_many``: the same items in the same order, the same
    snapshot arrays (the interned names' order too), the same answers
    to present and absent ids, the same count of ids added a batch."""
    steps, asked = _steps(case, np.random.RandomState(
        zlib.crc32(case.encode()) % 2**31))
    one, many = BULK_KINDS[kind](), BULK_KINDS[kind]()
    assert _run(many, steps, bulk=True) == _run(one, steps, bulk=False)
    _assert_same(one, many, asked)
    if case == "an-empty-batch":
        assert len(many) == 40


@pytest.mark.parametrize("bad", ["short-id", "not-hex", "length",
                                 "raw-length", "negative-length",
                                 "ragged-columns"])
@pytest.mark.parametrize("kind", list(BULK_KINDS))
def test_bulk_insert_refuses_a_bad_batch_whole(kind, bad):
    """A batch with one entry ``insert`` would refuse raises
    ``ValueError`` and leaves the index as it was: no shard has taken
    its part."""
    rng = np.random.RandomState(31)
    ids = hex_ids(rng, 300)
    one, many = BULK_KINDS[kind](), BULK_KINDS[kind]()
    held = [("batch", _entries(ids[:100], rng, True))]
    _run(one, held, bulk=False)
    _run(many, held, bulk=True)
    batch = _entries(ids[50:300], rng, True)
    keys = [e[0] for e in batch]
    at = 249  # the last entry: every shard's part comes before it
    if bad == "short-id":
        keys[at] = keys[at][:62]
    elif bad == "not-hex":
        keys[at] = "zz" + keys[at][2:]
    elif bad == "length":
        batch[at] = batch[at][:4] + (2**32,) + batch[at][5:]
    elif bad == "raw-length":
        batch[at] = batch[at][:5] + (2**32, True)
    elif bad == "negative-length":
        batch[at] = batch[at][:4] + (-1,) + batch[at][5:]
    with pytest.raises(ValueError):
        if bad == "ragged-columns":
            many.insert_many(as_key_rows(keys), ["p"], [0] * 249, ["data"],
                             [0] * 250, [0] * 250, [1] * 250, [1] * 250)
        else:
            _bulk(many, batch, keys=keys)
    _assert_same(one, many, ids)
    if bad in ("length", "raw-length"):  # what insert an entry says
        with pytest.raises(ValueError):
            _one_by_one(one, batch[at:])
    if bad in ("short-id", "not-hex"):
        with pytest.raises(ValueError):
            id_bytes(keys)
    # two ids whose lengths add up are not two ids
    with pytest.raises(ValueError):
        id_bytes([ids[0][:62], ids[1] + "00"])
    assert id_bytes(ids[:2]) == bytes.fromhex(ids[0] + ids[1])


@pytest.mark.parametrize("shards", [1, 4, 16])
def test_bulk_insert_never_makes_the_prefilter_say_absent(shards):
    """After a first batch that outgrows every shard's filter (built
    once, at the shard's final size) and a second that fits (added to
    it), the filter says "maybe" of every id either put in, and the
    vectorized question, which asks it first, finds them all."""
    idx = ShardedBlobIndex(shards=shards, capacity=16, prefilter=True)
    rng = np.random.RandomState(37)
    ids, absent = hex_ids(rng, 5000 * shards + 600), hex_ids(rng, 2000)
    first, second = ids[:5000 * shards], ids[5000 * shards - 50:]
    small = {f.capacity for f in idx._filters}
    assert _bulk(idx, _entries(first, rng, True)) == len(first)
    grown = [f.capacity for f in idx._filters]
    assert all(cap > max(small) for cap in grown)
    assert _bulk(idx, _entries(second, rng, "mixed")) == 600
    assert [f.capacity for f in idx._filters] == grown  # added, not rebuilt
    rows = as_key_rows(ids)
    sid = idx._shard_ids(rows)
    for s, f in enumerate(idx._filters):
        assert f.maybe_contains_rows(rows[sid == s]).all()
    assert idx.contains_many(ids).all()
    assert not idx.contains_many(absent).any()
    assert 0.0 < idx.prefilter_saturation() < 0.5
    for h in ids[::7]:  # a removed id stays "maybe"; vacuum rebuilds
        idx.remove(h)
    idx.vacuum()
    assert idx.contains_many(ids).tolist() == [i % 7 != 0
                                               for i in range(len(ids))]


def test_bulk_insert_takes_every_key_form_and_one_replace_for_all():
    rng = np.random.RandomState(41)
    ids = hex_ids(rng, 64)
    raw = id_bytes(ids)
    forms = [ids, np.frombuffer(raw, dtype=np.uint8).reshape(-1, 32),
             np.frombuffer(raw, dtype="S32"), as_key_rows(ids)]
    want = None
    for form in forms:
        idx = ShardedBlobIndex(shards=4, capacity=16)
        codes = np.arange(64) % 3
        assert idx.insert_many(form, ["a", "b", "c"], codes, ["data"],
                               np.zeros(64, dtype=np.uint8),
                               np.arange(64), np.full(64, 5),
                               np.full(64, 4, dtype=np.uint32)) == 64
        # replace=False for all: nothing moves
        assert idx.insert_many(form, ["z"], np.zeros(64, dtype=int),
                               ["tree"], np.zeros(64, dtype=int),
                               np.zeros(64), np.ones(64), np.ones(64),
                               replace=False) == 0
        assert idx.live_packs() == {"a", "b", "c"}
        got = list(idx.items())
        assert want is None or got == want
        want = got
    assert dict(want)[ids[4]] == ("b", "data", 4, 5, 4)


# -- the slot table's placement ------------------------------------------------


def _list_loop(rows, homes, size):
    """The loop ``_rebuild_table`` ran up to PR 51."""
    table, mask = [_EMPTY] * size, size - 1
    for j, i in zip(rows.tolist(), homes.tolist()):
        while table[i] != _EMPTY:
            i = (i + 1) & mask
        table[i] = j
    return np.asarray(table, dtype=np.int64)


def _homes(pattern, n, size, rng):
    if pattern == "random":
        return rng.randint(0, size, n)
    if pattern == "one-home":
        return np.full(n, size // 3)
    if pattern == "one-home-at-the-end":  # all but one position wrap
        return np.full(n, size - 1)
    if pattern == "the-last-slots":  # runs that pass the end and wrap
        return rng.randint(size - 8, size, n)
    if pattern == "both-ends":  # the wrapped meet keys whose home is 0..
        return np.concatenate([rng.randint(size - 4, size, n // 2),
                               rng.randint(0, 6, n - n // 2)])
    raise AssertionError(pattern)


@pytest.mark.parametrize("n,size", [(1, 32), (10, 32), (300, 1024),
                                    (341, 1024)])
@pytest.mark.parametrize("pattern", ["random", "one-home",
                                     "one-home-at-the-end",
                                     "the-last-slots", "both-ends"])
def test_place_slots_against_the_list_loop(pattern, n, size):
    """The numpy placement fills the slots the list loop fills (linear
    probing's occupied set does not depend on the order of insertion),
    every key is found from its home by ``_probe`` and by
    ``probe_rows``, and a key that is not there ends at an ``_EMPTY``
    slot: on random homes and on adversarial ones."""
    rng = np.random.RandomState(n * 7 + size)
    homes = _homes(pattern, n, size, rng).astype(np.int64)
    # rows out of order and not dense, as live rows among dead ones are
    rows = np.sort(rng.permutation(3 * n)[:n]).astype(np.int64)
    table = place_slots(rows, homes, size)
    loop = _list_loop(rows, homes, size)
    assert table.shape == loop.shape == (size,)
    assert ((table == _EMPTY) == (loop == _EMPTY)).all()
    assert sorted(table[table >= 0].tolist()) == rows.tolist()
    # an index over that table: word 0's low bits are the home
    idx = CompactIndex(capacity=3 * n)
    words = rng.randint(0, 2**62, (3 * n, 4)).astype(np.uint64)
    words[rows, 0] = ((words[rows, 0] & ~np.uint64(size - 1))
                      | homes.astype(np.uint64))
    idx._keys[: 3 * n] = words
    idx._n, idx._live = 3 * n, n
    idx._table, idx._mask = table, size - 1
    assert idx.probe_rows(words[rows]).tolist() == rows.tolist()
    for row in rows.tolist():
        assert idx._probe(words[row].tolist())[1] == row
    # the same homes, other ids: each walk ends at an _EMPTY, not found
    absent = words[rows].copy()
    absent[:, 3] ^= np.uint64(1)
    assert (idx.probe_rows(absent) == -1).all()
    for k4 in absent.tolist():
        slot, row = idx._probe(k4)
        assert row == -1 and table[slot] == _EMPTY


def test_place_slots_refuses_a_table_it_would_fill():
    with pytest.raises(ValueError):
        place_slots(np.arange(32), np.zeros(32, dtype=np.int64), 32)
    assert (place_slots(np.arange(0), np.arange(0), 32) == _EMPTY).all()
