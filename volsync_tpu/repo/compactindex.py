"""Compact in-memory blob index: bounded RAM at million-blob scale.

A 1 TiB repository at ~1 MiB average chunk size carries ~1M blobs. The
obvious ``dict[str, IndexEntry]`` costs ~500 bytes per blob (hex-string
key + dataclass + dict slot) — half a gigabyte of pure bookkeeping, and
the engine the reference wraps streams the same repository with O(1)
memory (reference: mover-restic/entry.sh:77 drives `restic` whose
in-memory index packs blob records into flat tables for exactly this
reason). This is the equivalent flat layout: parallel numpy arrays (32
raw key bytes + pack#/type/offset/length/raw_length ≈ 53 bytes per
entry) behind an open-addressed int32 slot table, with pack ids interned
once. ~10x less RAM than the dict, no per-entry Python objects, and a
``copy()`` that is three array copies instead of a million allocations.

Deletions (prune) leave tombstones in the slot table and a dead mark in
the entry arrays; ``vacuum()`` rebuilds both dense. The table rebuilds
automatically when live+tombstone load crosses ~2/3.

Two ways in: ``insert`` an entry (a writer's new blobs, the seal path)
and ``insert_many`` a batch given as columns (``Repository.load_index``:
a whole load's entries at once). The batch is resolved against what the
index holds and against itself, appended a column at a time after
ONE growth, and the slot table is placed once for the final live count by
``place_slots``, the numpy placement every table rebuild uses. Both
leave the same index behind.
"""

from __future__ import annotations

from typing import Iterator, Optional

import numpy as np

_EMPTY = -1
_TOMB = -2
_DEAD_PACK = np.uint32(0xFFFFFFFF)


def as_key_rows(keys) -> np.ndarray:
    """Normalize a batch of blob ids to an ``(N, 4)`` uint64 array of
    big-endian 8-byte words — the layout ``_keys`` stores.

    Accepts a sequence of 64-char hex ids, an ``(N, 32)`` uint8 array of
    raw digest bytes, an ``(N,)`` ``S32`` bytes array (what
    ``snapshot_arrays`` emits), or an already-converted ``(N, 4)``
    uint64 array (returned as-is).
    """
    if isinstance(keys, np.ndarray):
        if keys.dtype == np.uint64 and keys.ndim == 2 and keys.shape[1] == 4:
            return keys
        if keys.dtype == np.uint8 and keys.ndim == 2 and keys.shape[1] == 32:
            return (np.ascontiguousarray(keys).view(">u8")
                    .astype(np.uint64).reshape(-1, 4))
        if keys.dtype.kind == "S" and keys.dtype.itemsize == 32:
            return (np.frombuffer(keys.tobytes(), dtype=">u8")  # lint: ignore[VL106] 32 B id rows
                    .astype(np.uint64).reshape(-1, 4))
        raise ValueError(f"unsupported key array {keys.dtype}/{keys.shape}")
    ids = list(keys)
    if not ids:
        return np.zeros((0, 4), dtype=np.uint64)
    raw = bytes.fromhex("".join(ids))
    if len(raw) != 32 * len(ids):
        raise ValueError("blob ids must each be 32 bytes hex")
    return (np.frombuffer(raw, dtype=">u8").astype(np.uint64)
            .reshape(-1, 4))


def id_bytes(hex_ids: list) -> bytes:
    """The raw digests of a list of hex blob ids, 32 bytes an id in the
    list's order (what ``as_key_rows`` takes as an ``(N, 32)`` uint8
    array); ``ValueError`` for an id that is not 32 bytes of hex."""
    raw = bytes.fromhex("".join(hex_ids))
    if len(raw) != 32 * len(hex_ids) or set(map(len, hex_ids)) - {64}:
        raise ValueError("blob ids must each be 32 bytes hex")
    return raw


def place_slots(rows: np.ndarray, homes: np.ndarray,
                size: int) -> np.ndarray:
    """A linear-probing slot table of ``size`` slots holding ``rows``,
    each at or after its home slot with no ``_EMPTY`` between (all a
    lookup needs), placed by numpy: no loop over the entries.

    With the homes sorted (stably, so keys of one home keep their
    order), key ``i`` lands on the first slot that is both at or after
    its home and after key ``i - 1``'s: ``max(h[i], pos[i - 1] + 1)``,
    which unrolls to a running maximum of ``h - i``. The few whose slot
    runs past the table's end wrap: everything from their home to the
    end is taken, so they fill the first free slots from 0."""
    table = np.full((size,), _EMPTY, dtype=np.int64)
    n = int(rows.shape[0])
    if n == 0:
        return table
    if n >= size:
        raise ValueError(f"{n} rows do not fit a table of {size} slots")
    order = np.argsort(homes, kind="stable")
    ramp = np.arange(n, dtype=np.int64)
    pos = np.maximum.accumulate(homes[order] - ramp) + ramp
    fit = int(np.searchsorted(pos, size))  # pos is strictly rising
    rows = rows[order]
    table[pos[:fit]] = rows[:fit]
    if fit < n:
        table[np.flatnonzero(table == _EMPTY)[: n - fit]] = rows[fit:]
    return table


def _whole(values) -> np.ndarray:
    """``values`` as an array of whole numbers: an integer array as it
    is (a narrow one stays narrow)."""
    values = np.asarray(values)
    return values if values.dtype.kind in "iu" else values.astype(np.int64)


def batch_columns(n: int, pack_codes, type_codes, offset, length,
                  raw_length, replace) -> tuple:
    """The columns of an ``insert_many`` batch of ``n`` entries as
    arrays, the lengths as the entry arrays hold them (uint32);
    ``ValueError`` for a length ``insert`` would refuse."""
    lengths = [_whole(length), _whole(raw_length)]
    if any(col.dtype != np.uint32 and (col.astype(np.int64) >> 32).any()
           for col in lengths):
        raise ValueError("blob larger than 4 GiB cannot be indexed")
    cols = (_whole(pack_codes), _whole(type_codes),
            np.asarray(offset, dtype=np.uint64),
            *(col.astype(np.uint32, copy=False) for col in lengths),
            np.broadcast_to(np.asarray(replace, dtype=bool), (n,)))
    if any(col.shape != (n,) for col in cols):
        raise ValueError(f"every column of the batch holds {n} entries")
    return cols


def _occurrences(k4: np.ndarray,
                 replace: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """The distinct ids of a batch, in the order they first occur:
    -> (the position of each one's first occurrence, the position of
    its last occurrence that replaces or -1)."""
    n = int(k4.shape[0])
    by_word = np.sort(k4[:, 1])
    if not (by_word[1:] == by_word[:-1]).any():  # no id twice
        lead = np.arange(n, dtype=np.int64)
        return lead, np.where(replace, lead, -1)
    # stable, so the occurrences of one id stay in the batch's order
    order = np.lexsort((k4[:, 3], k4[:, 2], k4[:, 1], k4[:, 0]))
    ks = k4[order]
    starts = np.flatnonzero(np.concatenate(
        ([True], (ks[1:] != ks[:-1]).any(axis=1))))
    lead = order[starts]
    last = np.maximum.reduceat(np.where(replace[order], order, -1), starts)
    by_first = np.argsort(lead)
    return lead[by_first], last[by_first]


class CompactIndex:
    """Mapping-like store: 64-char hex blob id -> entry tuple.

    Values go in/out as ``(pack_id: str, type: str, offset: int,
    length: int, raw_length: int)``; the Repository wraps them in its
    IndexEntry dataclass at the boundary. Not thread-safe — callers hold
    the repository lock, as they did for the dict this replaces.
    """

    __slots__ = ("_keys", "_pack", "_type", "_off", "_len", "_raw",
                 "_n", "_live", "_table", "_mask", "_tombs",
                 "_packs", "_pack_idx", "_types", "_type_idx")

    def __init__(self, capacity: int = 1024):
        cap = max(16, capacity)
        self._keys = np.zeros((cap, 4), dtype=np.uint64)
        self._pack = np.zeros((cap,), dtype=np.uint32)
        self._type = np.zeros((cap,), dtype=np.uint8)
        self._off = np.zeros((cap,), dtype=np.uint64)
        self._len = np.zeros((cap,), dtype=np.uint32)
        self._raw = np.zeros((cap,), dtype=np.uint32)
        self._n = 0          # entry rows used (incl. dead)
        self._live = 0       # live entries
        ts = 1
        while ts < cap * 2:
            ts *= 2
        self._table = np.full((ts,), _EMPTY, dtype=np.int64)
        self._mask = ts - 1
        self._tombs = 0
        self._packs: list[str] = []
        self._pack_idx: dict[str, int] = {}
        self._types: list[str] = []
        self._type_idx: dict[str, int] = {}

    # -- key codec ----------------------------------------------------------

    @staticmethod
    def _key4(hex_id: str) -> tuple[int, int, int, int]:
        b = bytes.fromhex(hex_id)
        if len(b) != 32:
            raise ValueError(f"blob id must be 32 bytes hex: {hex_id!r}")
        return (int.from_bytes(b[0:8], "big"), int.from_bytes(b[8:16], "big"),
                int.from_bytes(b[16:24], "big"),
                int.from_bytes(b[24:32], "big"))

    @staticmethod
    def _hex(row: np.ndarray) -> str:
        return b"".join(int(w).to_bytes(8, "big") for w in row).hex()  # lint: ignore[VL106] one 32 B id

    # -- internals ----------------------------------------------------------

    def _intern(self, value: str, values: list, index: dict) -> int:
        i = index.get(value)
        if i is None:
            i = len(values)
            values.append(value)
            index[value] = i
        return i

    def _probe(self, k4) -> tuple[int, int]:
        """-> (slot, entry_row) with entry_row == -1 when absent; slot is
        the insertion point (first tombstone seen, else the empty)."""
        table = self._table
        keys = self._keys
        mask = self._mask
        i = k4[0] & mask
        first_tomb = -1
        while True:
            j = table[i]
            if j == _EMPTY:
                return (first_tomb if first_tomb >= 0 else i), -1
            if j == _TOMB:
                if first_tomb < 0:
                    first_tomb = i
            else:
                row = keys[j]
                if (row[0] == k4[0] and row[1] == k4[1]
                        and row[2] == k4[2] and row[3] == k4[3]):
                    return i, int(j)
            i = (i + 1) & mask

    def probe_rows(self, k4: np.ndarray) -> np.ndarray:
        """Vectorized ``_probe`` for a batch: ``(N, 4)`` uint64 key rows
        -> ``(N,)`` int64 entry rows, -1 where absent.

        One pass computes every key's home slot, gathers the slot table,
        and compares full keys; only the collision minority (occupied
        slot, different key — or a tombstone) advances to a masked
        reprobe. At healthy load (< 2/3) the unresolved set shrinks
        geometrically, so a 4K-key batch resolves in a handful of numpy
        passes instead of 4K Python probe loops.
        """
        n = int(k4.shape[0])
        out = np.full((n,), -1, dtype=np.int64)
        if n == 0 or self._n == 0:
            return out
        table = self._table
        keys = self._keys
        mask = self._mask
        pos = (k4[:, 0] & np.uint64(mask)).astype(np.int64)
        active = np.arange(n, dtype=np.int64)
        while active.size:
            j = table[pos]
            occ = j >= 0
            matched = np.zeros(active.shape, dtype=bool)
            if occ.any():
                matched[occ] = (keys[j[occ]] == k4[active[occ]]).all(axis=1)
            out[active[matched]] = j[matched]
            # empty slot -> definitively absent; tombstones and
            # mismatched occupants continue probing
            unresolved = ~matched & (j != _EMPTY)
            active = active[unresolved]
            pos = (pos[unresolved] + 1) & mask
        return out

    def _decode_row(self, j: int) -> tuple:
        return (self._packs[self._pack[j]], self._types[self._type[j]],
                int(self._off[j]), int(self._len[j]), int(self._raw[j]))

    def decode_rows(self, j: np.ndarray) -> list:
        """Entry tuples for an array of entry rows — bulk ``tolist()``
        column gathers, not per-row numpy scalar indexing (which would
        cost as much as the scalar probe the batch path replaces)."""
        pk = self._pack[j].tolist()
        tp = self._type[j].tolist()
        # zip() assembles the tuples in C — a Python-level per-row loop
        # here costs ~1us/key, more than the whole vectorized probe
        return list(zip(map(self._packs.__getitem__, pk),
                        map(self._types.__getitem__, tp),
                        self._off[j].tolist(), self._len[j].tolist(),
                        self._raw[j].tolist()))

    def contains_many(self, keys) -> np.ndarray:
        """Batched membership: blob-id batch (see ``as_key_rows``) ->
        ``(N,)`` bool mask."""
        return self.probe_rows(as_key_rows(keys)) >= 0

    def lookup_many(self, keys) -> list:
        """Batched ``lookup``: -> list of entry tuples, None where
        absent, aligned with the input order."""
        rows = self.probe_rows(as_key_rows(keys))
        hit = np.nonzero(rows >= 0)[0]
        if hit.size == rows.shape[0]:  # warm-repo fast path: all hits
            return self.decode_rows(rows)
        out: list = [None] * rows.shape[0]
        if hit.size:
            decoded = self.decode_rows(rows[hit])
            for i, gi in enumerate(hit.tolist()):
                out[gi] = decoded[i]
        return out

    def live_key_rows(self) -> np.ndarray:
        """``(live, 4)`` uint64 key rows of every live entry (a copy) —
        what a prefilter rebuild feeds on."""
        rows = np.nonzero(self._pack[: self._n] != _DEAD_PACK)[0]
        return self._keys[rows].copy()

    def _grow_entries(self, rows: int = 0):
        """Double the entry block, until it holds ``rows``."""
        # max() guards the vacuumed-to-empty index: doubling a
        # zero-length entry block would stay zero-length forever
        cap = max(16, self._keys.shape[0] * 2)
        while cap < rows:
            cap *= 2
        for name in ("_keys", "_pack", "_type", "_off", "_len", "_raw"):
            old = getattr(self, name)
            shape = (cap,) + old.shape[1:]
            new = np.zeros(shape, dtype=old.dtype)
            new[: self._n] = old[: self._n]
            setattr(self, name, new)

    def _rebuild_table(self, min_size: Optional[int] = None):
        ts = self._table.shape[0]
        want = max(min_size or 0, self._live * 3)
        while ts < want:
            ts *= 2
        mask = ts - 1
        rows = np.nonzero(self._pack[: self._n] != _DEAD_PACK)[0]
        homes = (self._keys[rows, 0] & np.uint64(mask)).astype(np.int64)
        self._table = place_slots(rows, homes, ts)
        self._mask = mask
        self._tombs = 0

    # -- mapping API --------------------------------------------------------

    def __len__(self) -> int:
        return self._live

    def __contains__(self, hex_id: str) -> bool:
        return self._probe(self._key4(hex_id))[1] >= 0

    def lookup(self, hex_id: str):
        """-> (pack, type, offset, length, raw_length) or None."""
        _, j = self._probe(self._key4(hex_id))
        if j < 0:
            return None
        return (self._packs[self._pack[j]], self._types[self._type[j]],
                int(self._off[j]), int(self._len[j]), int(self._raw[j]))

    def insert(self, hex_id: str, pack: str, btype: str, offset: int,
               length: int, raw_length: int, *, replace: bool = True,
               _k4=None) -> bool:
        """Insert/overwrite. With replace=False an existing entry is kept
        (dict.setdefault). Returns True if the mapping changed. ``_k4``
        lets a wrapper that already decoded the hex id (shard routing)
        skip the second ``bytes.fromhex``."""
        if length >= 2**32 or raw_length >= 2**32:
            raise ValueError("blob larger than 4 GiB cannot be indexed")
        k4 = _k4 if _k4 is not None else self._key4(hex_id)
        slot, j = self._probe(k4)
        if j >= 0:
            if not replace:
                return False
            self._pack[j] = self._intern(pack, self._packs, self._pack_idx)
            self._type[j] = self._intern(btype, self._types, self._type_idx)
            self._off[j] = offset
            self._len[j] = length
            self._raw[j] = raw_length
            return True
        if self._n == self._keys.shape[0]:
            self._grow_entries()
        j = self._n
        self._keys[j] = k4
        self._pack[j] = self._intern(pack, self._packs, self._pack_idx)
        self._type[j] = self._intern(btype, self._types, self._type_idx)
        self._off[j] = offset
        self._len[j] = length
        self._raw[j] = raw_length
        self._n += 1
        self._live += 1
        if self._table[slot] == _TOMB:
            self._tombs -= 1
        self._table[slot] = j
        if (self._live + self._tombs) * 3 > self._table.shape[0] * 2:
            self._rebuild_table()
        return True

    def insert_many(self, k4: np.ndarray, pack_names: list,
                    pack_codes: np.ndarray, type_names: list,
                    type_codes: np.ndarray, offset, length, raw_length,
                    replace=True) -> int:
        """``insert`` for a batch given as columns, in the batch's
        order: ``(N, 4)`` key rows, a code an entry into ``pack_names``
        and into ``type_names``, the three number columns, and
        ``replace`` an entry (or one for all). Returns the ids added;
        ``ValueError``, with nothing changed, for a length ``insert``
        would refuse.

        Leaves what ``insert`` an entry, in that order, would leave:
        an id listed several times ends at its LAST replacing
        occurrence, else stays what the index held, else takes its
        FIRST occurrence; a new id's row goes where its first
        occurrence puts it; names are interned in the order their first
        entry takes effect. But the entry arrays grow once, a column is
        stored at once, a name is interned once, and the slot table is
        placed once for the final live count."""
        return self.insert_columns(
            k4, pack_names, type_names, *batch_columns(
                int(k4.shape[0]), pack_codes, type_codes, offset, length,
                raw_length, replace))

    def insert_columns(self, k4: np.ndarray, pack_names: list,
                       type_names: list, pack_codes: np.ndarray,
                       type_codes: np.ndarray, offset: np.ndarray,
                       length: np.ndarray, raw_length: np.ndarray,
                       replace: np.ndarray) -> int:
        """``insert_many`` of columns ``batch_columns`` has passed (or
        a part of them: ``ShardedBlobIndex.insert_many`` checks a batch
        once and hands each shard its rows)."""
        n = int(k4.shape[0])
        if n == 0:
            return 0
        lead, last = _occurrences(k4, replace)
        held = self.probe_rows(k4)[lead]
        new = held < 0
        # an id's values come from its last replacing occurrence, else
        # (a new id) from its first
        src = np.where(last >= 0, last, lead)
        took = replace.copy()  # the entries that change the mapping
        took[lead[new]] = True
        packs = self._intern_codes(pack_names, pack_codes[took],
                                   self._packs, self._pack_idx)
        types = self._intern_codes(type_names, type_codes[took],
                                   self._types, self._type_idx)
        n0, added = self._n, int(new.sum())
        if n0 + added > self._keys.shape[0]:
            self._grow_entries(n0 + added)
        rows = held
        rows[new] = np.arange(n0, n0 + added)
        wrote = new | (last >= 0)
        rows, src = rows[wrote], src[wrote]
        self._pack[rows] = packs[pack_codes[src]]
        self._type[rows] = types[type_codes[src]]
        self._off[rows] = offset[src]
        self._len[rows] = length[src]
        self._raw[rows] = raw_length[src]
        if added:
            self._keys[n0: n0 + added] = k4[lead[new]]
            self._n += added
            self._live += added
            self._rebuild_table()
        return added

    def _intern_codes(self, names: list, codes: np.ndarray, values: list,
                      index: dict) -> np.ndarray:
        """Intern the ``names`` that ``codes`` uses, in the order it
        first uses them; -> the interned number of each code."""
        used, first = np.unique(codes, return_index=True)
        out = np.zeros((len(names),), dtype=np.uint32)
        for code in used[np.argsort(first)].tolist():
            out[code] = self._intern(names[code], values, index)
        return out

    def remove(self, hex_id: str) -> bool:
        slot, j = self._probe(self._key4(hex_id))
        if j < 0:
            return False
        self._table[slot] = _TOMB
        self._tombs += 1
        self._pack[j] = _DEAD_PACK
        self._live -= 1
        return True

    def clear(self):
        self.__init__(capacity=16)

    def _live_snapshot(self):
        """Copies of the live rows, taken eagerly at call time so the
        returned arrays are immune to later inserts/removes/vacuums."""
        rows = np.nonzero(self._pack[: self._n] != _DEAD_PACK)[0]
        return (self._keys[rows].copy(), self._pack[rows].copy(),
                self._type[rows].copy(), self._off[rows].copy(),
                self._len[rows].copy(), self._raw[rows].copy(),
                list(self._packs), list(self._types))

    def items(self) -> Iterator[tuple[str, tuple]]:
        """Yield (hex_id, (pack, type, offset, length, raw_length)) for
        every live entry. The arrays are snapshotted eagerly (at the
        ``items()`` call, not first ``next()``) so callers may mutate —
        insert, remove, even vacuum — while iterating."""
        keys, pack, btype, off, length, raw, packs, types = (
            self._live_snapshot())

        def gen():
            for j in range(keys.shape[0]):
                yield (self._hex(keys[j]),
                       (packs[pack[j]], types[btype[j]], int(off[j]),
                        int(length[j]), int(raw[j])))
        return gen()

    def keys(self) -> Iterator[str]:
        keys = self.live_key_rows()

        def gen():
            for j in range(keys.shape[0]):
                yield self._hex(keys[j])
        return gen()

    __iter__ = keys

    def copy(self) -> "CompactIndex":
        new = CompactIndex.__new__(CompactIndex)
        for name in ("_keys", "_pack", "_type", "_off", "_len", "_raw",
                     "_table"):
            setattr(new, name, getattr(self, name).copy())
        new._n = self._n
        new._live = self._live
        new._mask = self._mask
        new._tombs = self._tombs
        new._packs = list(self._packs)
        new._pack_idx = dict(self._pack_idx)
        new._types = list(self._types)
        new._type_idx = dict(self._type_idx)
        return new

    def vacuum(self):
        """Drop dead rows + retired pack ids; rebuild dense. Call after a
        prune that removed many entries."""
        keep = np.nonzero(self._pack[: self._n] != _DEAD_PACK)[0]
        live_packs = sorted({int(p) for p in self._pack[keep]})
        remap = np.zeros((len(self._packs) or 1,), dtype=np.uint32)
        new_packs: list[str] = []
        for p in live_packs:
            remap[p] = len(new_packs)
            new_packs.append(self._packs[p])
        self._keys = self._keys[keep].copy()
        self._pack = remap[self._pack[keep]].copy()
        self._type = self._type[keep].copy()
        self._off = self._off[keep].copy()
        self._len = self._len[keep].copy()
        self._raw = self._raw[keep].copy()
        self._n = self._live = int(keep.shape[0])
        self._packs = new_packs
        self._pack_idx = {p: i for i, p in enumerate(new_packs)}
        self._rebuild_table()

    def snapshot_arrays(self) -> tuple[np.ndarray, np.ndarray, list]:
        """(keys, pack_codes, pack_names) for live entries in entry
        order: keys is an (N,) ``S32`` array of 32-byte big-endian blob
        ids, pack_codes indexes pack_names. The vectorized view prune
        uses for whole-index liveness math without touching per-entry
        Python objects."""
        rows = np.nonzero(self._pack[: self._n] != _DEAD_PACK)[0]
        kb = self._keys[rows].astype(">u8").tobytes()  # lint: ignore[VL106] index metadata, not payload
        keys = np.frombuffer(kb, dtype="S32")
        return keys, self._pack[rows].copy(), list(self._packs)

    def live_packs(self) -> set[str]:
        """Distinct pack ids referenced by live entries — one vectorized
        pass over the pack column, no per-entry id decoding."""
        rows = self._pack[: self._n]
        used = np.unique(rows[rows != _DEAD_PACK])
        return {self._packs[int(p)] for p in used}

    def nbytes(self) -> int:
        """Approximate resident bytes of the index structures."""
        return sum(getattr(self, a).nbytes
                   for a in ("_keys", "_pack", "_type", "_off", "_len",
                             "_raw", "_table"))
