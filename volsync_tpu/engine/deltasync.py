"""rsync-style delta synchronization engine (device-accelerated).

The algorithm of the reference's `rsync -aAhHSxz --delete` hot loop
(mover-rsync/source.sh:54), re-expressed on TPU primitives
(ops/rolling.py, ops/delta.py, ops/md5.py):

  destination:  per-block signature = (weak32, MD5) per block_len block
  source:       rolling weak checksum at EVERY offset in one parallel
                pass -> membership vs the signature's sorted weak set ->
                batched MD5 verification of candidate windows -> greedy
                left-to-right op selection on host (sparse matches only)
  ops stream:   COPY(block_index, n_blocks) | DATA(bytes), applied on the
                destination against its current file

Block size follows rsync's heuristic (~sqrt(file size), bounded).

Every device program runs on ONE staged buffer of ``WINDOW`` bytes: a
batch of files laid into it at slot-aligned offsets, or one window of a
file longer than that (``_pack``). Signature capacity, candidate
capacities and the rows of a search's group are functions of the block
length alone, so a tree of any sizes meets three programs a block length
(``delta_sig_flat``, ``delta_match_rows``, ``delta_md5_flat``), and a
file of any length is signed and scanned a window at a time against its
whole signature.

The source does not search at every offset (a table lookup an offset is
the one thing the chip is slow at: 10 s a window, PERF.md). It first
signs its own buffer at the blocks' own alignment (``delta_sig_flat``,
the destination's program) and looks each block up in the file's
signature on the host; only where an aligned block fails is every
offset searched (``delta_match_rows`` over the rows between it and the
next aligned block that holds: all of a buffer's open rows in one
dispatch, whose cost follows their number). The selection's way through
the piece is then followed on the host from what the buffer knows
(``_walk_piece``): over the aligned blocks that held, and from match to
match inside the searched runs, at whatever alignment. So data that an
insertion moved off the blocks' alignment costs the searches of its
rows (every row of the rest of the window: the probe holds nowhere
after it), however many insertions there are, and no buffer of its own.
Only a match that ends inside blocks that held (a run of one repeated
block, a zero-filled region, moved by an insertion) puts the selection
where nothing was searched: the piece is cut there and staged and
probed again from that byte (``delta.reprobes``). Every offset greedy
selection can reach is so tested, and the op stream is the every-offset
scan's (``compute_delta``).
"""

from __future__ import annotations

import bisect
import dataclasses
import hashlib
from typing import Optional

import numpy as np

from volsync_tpu.obs import count, record_copy, span
from volsync_tpu.ops.delta import (
    delta_match_rows,
    delta_md5_flat,
    delta_sig_flat,
    match_offsets,
    verify_candidates,
)
from volsync_tpu.ops.rolling import weak_checksum_host

MIN_BLOCK = 4096
MAX_BLOCK = 128 * 1024

#: Bytes of one staged buffer, and so the most of one file that is on
#: the host or the device at a time (a power of two). 64 MiB: a scan's
#: uint32 prefix sums, positions and hits over it take ~2 GB of a
#: 16 GB chip. Tests shrink it to a few blocks.
WINDOW = 64 * 1024 * 1024

#: Wire cost of one signature block: weak32 + 16-byte MD5 (to_wire).
SIG_BYTES_PER_BLOCK = 4 + 16
#: Wire cost of a signature's fixed fields (size + block_len ints).
SIG_HEADER_BYTES = 16


def pick_block_len(size: int) -> int:
    """rsync-style block size: the least power of two in [4 KiB,
    128 KiB] not under floor(sqrt(size)) (a file of b * b + 1 to
    b * b + 2 * b bytes still has blocks of b)."""
    if size <= 0:
        return MIN_BLOCK
    target = int(size ** 0.5)
    b = MIN_BLOCK
    while b < target and b < MAX_BLOCK:
        b *= 2
    return b


@dataclasses.dataclass(frozen=True)
class SigGeometry:
    """The block geometry the engine would pick for a file of ``size``
    bytes, plus the exact signature wire cost that geometry implies.
    This is the pricing seam the protocol planner (engine/protoplan.py)
    uses: DELTA's first round trip ships ``sig_bytes`` for real, so the
    estimate must come from here, not a re-derived approximation."""

    block_len: int
    n_blocks: int      # includes the short tail block, matching to_wire
    sig_bytes: int


def signature_geometry(size: int,
                       block_len: Optional[int] = None) -> SigGeometry:
    """Geometry + signature wire size for a ``size``-byte destination
    file (``block_len`` overrides the heuristic, as build_file_signature
    allows)."""
    block_len = block_len or pick_block_len(size)
    n_blocks = 0 if size <= 0 else -(-size // block_len)
    return SigGeometry(block_len=block_len, n_blocks=n_blocks,
                       sig_bytes=SIG_HEADER_BYTES
                       + n_blocks * SIG_BYTES_PER_BLOCK)


@dataclasses.dataclass
class FileSignature:
    size: int
    block_len: int
    weak: np.ndarray          # [nb] uint32 (includes short tail block)
    strong: list[bytes]       # [nb] 16-byte MD5 digests

    def to_wire(self) -> dict:
        return {"size": self.size, "block_len": self.block_len,
                "weak": self.weak.tobytes(),  # lint: ignore[VL106] signature wire form
                "strong": b"".join(self.strong)}  # lint: ignore[VL106] signature wire form

    @classmethod
    def from_wire(cls, d: dict) -> "FileSignature":
        weak = np.frombuffer(d["weak"], dtype=np.uint32).copy()
        strong = [d["strong"][i : i + 16]
                  for i in range(0, len(d["strong"]), 16)]
        return cls(size=d["size"], block_len=d["block_len"], weak=weak,
                   strong=strong)


class BytesSource:
    """A file's bytes already in memory, read as ``scan_ranges`` and
    ``build_signatures`` read any source: by ``size`` and
    ``pread(offset, out)``, which fills the ``uint8`` array ``out``."""

    def __init__(self, data):
        self._arr = np.frombuffer(data, np.uint8)
        self.size = len(self._arr)

    def pread(self, offset: int, out: np.ndarray) -> None:
        out[:] = self._arr[offset: offset + len(out)]


def _source(obj):
    return obj if hasattr(obj, "pread") else BytesSource(obj)


def read_range(src, offset: int, n: int) -> bytes:
    """``n`` bytes of a source from ``offset``."""
    out = np.empty(n, np.uint8)
    src.pread(offset, out)
    return out.tobytes()  # lint: ignore[VL106] a tail or a literal, under one block or one part


@dataclasses.dataclass(frozen=True)
class _Geometry:
    """The one staged-buffer shape of a block length: ``window`` bytes
    in ``window // slot`` slots, files laid at slot starts; the
    signature table's floor and the candidate capacity follow."""

    block_len: int
    window: int
    slot: int

    @classmethod
    def of(cls, block_len: int) -> "_Geometry":
        if block_len < 1024 or block_len & (block_len - 1):
            raise ValueError(f"block length {block_len}: the delta "
                             f"programs take a power of two >= 1024")
        if WINDOW & (WINDOW - 1):
            raise ValueError(f"WINDOW {WINDOW} is not a power of two")
        window = max(WINDOW, 4 * block_len)
        return cls(block_len, window, max(block_len, window // 1024))

    @property
    def blocks(self) -> int:
        return self.window // self.block_len

    @property
    def cand_cap(self) -> int:
        # candidates of one strong check, and of one group of a search:
        # matches at other alignments than the blocks' own, and the
        # weak checksum's false hits
        return max(64, self.blocks // 8)

    @property
    def rows(self) -> int:
        return self.window // 1024

    @property
    def group_rows(self) -> int:
        # rows of 1024 offsets the search's loop takes at a time: what
        # a listed row more can cost (a sort of this many rows' offsets
        # with the table), all of a small buffer
        return min(self.rows, 256)

    @property
    def search_cap(self) -> int:
        # candidates of one search: a match at every block of the
        # buffer (data an insertion moved), and a group's beside them
        return max(self.blocks, self.cand_cap) + self.cand_cap

    def sig_cap(self, n: int) -> int:
        cap = self.blocks
        while cap < n:
            cap *= 4
        return cap


def _pack(pieces, geo: _Geometry):
    """Lay ``pieces`` [(item, file offset, length)] into buffers in
    order, each at the next slot start: yields [(item, offset, length,
    base)] a buffer. A piece is at most one window."""
    cur, used = [], 0
    for item, off, n in pieces:
        need = -(-n // geo.slot) * geo.slot
        if cur and used + need > geo.window:
            yield cur
            cur, used = [], 0
        cur.append((item, off, n, used))
        used += need
    if cur:
        yield cur


def _fill(buffer, sources, host: np.ndarray) -> None:
    """The buffer's pieces read into the zeroed window ``host`` (the
    reads are the sources' own spans)."""
    for item, off, n, base in buffer:
        sources[item].pread(off, host[base: base + n])


def _digests(states: np.ndarray) -> list[bytes]:
    """[k, 4] uint32 MD5 states -> k 16-byte digests."""
    raw = np.ascontiguousarray(states).astype("<u4").tobytes()  # lint: ignore[VL106] 16 B digests
    return [raw[i: i + 16] for i in range(0, len(raw), 16)]


def build_signatures(items) -> list[FileSignature]:
    """Destination side: the signature of every ``(source, block_len)``
    of ``items`` (a source: ``bytes`` or an object with ``size`` and
    ``pread``; ``block_len`` None: this file's own), the full blocks on
    the device a staged buffer at a time, short tails on the host."""
    import jax

    sources = [_source(src) for src, _bl in items]
    lens = [bl or pick_block_len(src.size)
            for src, (_s, bl) in zip(sources, items)]
    weak = [np.zeros(-(-src.size // bl), np.uint32)
            for src, bl in zip(sources, lens)]
    strong: list[list] = [[b""] * len(w) for w in weak]
    groups: dict[int, list] = {}
    for i, (src, bl) in enumerate(zip(sources, lens)):
        window = _Geometry.of(bl).window
        for off in range(0, src.size, window):
            groups.setdefault(bl, []).append(
                (i, off, min(window, src.size - off)))
    for bl, pieces in groups.items():
        geo = _Geometry.of(bl)
        for buffer in _pack(pieces, geo):
            with span("sig.stage"):
                host = np.zeros(geo.window, np.uint8)
                _fill(buffer, sources, host)
                dev = jax.device_put(host)  # lint: ignore[VL502] one staged window a dispatch
                # bytes put on the chip, zeros included
                record_copy("sig.stage", geo.window)
            with span("sig.launch"):
                out = delta_sig_flat(dev, block_len=bl)  # lint: ignore[VL502] one dispatch a staged window
            with span("sig.fetch"):
                w_all = np.asarray(out[0])  # lint: ignore[VL501] host-result contract: a window's signature
                s_all = np.asarray(out[1])  # lint: ignore[VL501] host-result contract: a window's signature
            for item, off, n, base in buffer:
                first, at, full = off // bl, base // bl, n // bl
                weak[item][first: first + full] = w_all[at: at + full]
                strong[item][first: first + full] = \
                    _digests(s_all[at: at + full])
                if n % bl:
                    tail = host[base + full * bl: base + n]
                    weak[item][first + full] = weak_checksum_host(tail)
                    strong[item][first + full] = hashlib.md5(tail).digest()
    return [FileSignature(src.size, bl, w, st)
            for src, bl, w, st in zip(sources, lens, weak, strong)]


def build_file_signature(data,
                         block_len: Optional[int] = None) -> FileSignature:
    """The signature of one file's bytes (``build_signatures`` of
    one)."""
    return build_signatures([(data, block_len)])[0]


# Delta ops: ("copy", first_block, n_blocks) | ("data", bytes); before
# the literals are read, ("lit", start, end) ranges of the source.
Op = tuple


def compute_delta(src: bytes, sig: FileSignature) -> list[Op]:
    """The unwindowed oracle the tests hold ``scan_ranges`` to: one
    file, one exact-shape scan over the whole of it (a program a file
    length: no mover calls this). Returns ops that rebuild ``src`` from
    the destination's blocks + literal data."""
    import jax.numpy as jnp

    L = len(src)
    if L == 0:
        return []
    block_len = sig.block_len
    n_full_dst = sig.size // block_len
    # Only full blocks participate in the rolling scan; the destination
    # tail block (if any) can only match at the very end of src.
    full_weak = sig.weak[:n_full_dst]
    source = BytesSource(src)
    if len(full_weak) == 0 or L < block_len:
        return _materialize(_with_tail_match(source, sig, [("lit", 0, L)]),
                            src)

    arr = np.frombuffer(src, np.uint8)
    dev = jnp.asarray(arr)
    sorted_weak = np.sort(full_weak, kind="stable")
    cap = max(1024, _pow2ceil(L // block_len * 4))
    while True:
        cand_dev, count_dev = match_offsets(
            dev, jnp.asarray(sorted_weak), window=block_len,
            max_candidates=cap,
        )
        n = int(count_dev)
        if n <= cap:
            cand = np.asarray(cand_dev)[:n]
            break
        cap = _pow2ceil(n)
    verified: dict[int, int] = {}
    if len(cand):
        # Strong verification, batched on device.
        strongs = _digests(verify_candidates(dev, cand, block_len=block_len))
        # weak -> destination block ids (handle duplicate weak values)
        by_weak: dict[int, list[int]] = {}
        for idx in range(len(full_weak)):
            by_weak.setdefault(int(full_weak[idx]), []).append(idx)
        weak_at = _weak_at_offsets(arr, cand, block_len)
        for i, off in enumerate(cand):
            for dst_block in by_weak.get(int(weak_at[i]), ()):
                if sig.strong[dst_block] == strongs[i]:
                    verified[int(off)] = dst_block
                    break
    return _materialize(
        _with_tail_match(source, sig, _select_ranges(L, block_len, verified)),
        src)


def _select_ranges(L: int, block_len: int, verified: dict) -> list[Op]:
    """Greedy left-to-right selection over the sparse verified offsets
    ({source offset: destination block}) of a ``L``-byte source: the
    host-side tail of the delta scan, shared by the windowed path and
    the oracle. Works from offsets alone; literals are ranges."""
    ops: list[Op] = []
    lit_start = 0
    pos = 0
    offsets = sorted(verified)
    oi = 0
    while pos + block_len <= L:
        while oi < len(offsets) and offsets[oi] < pos:
            oi += 1
        if oi < len(offsets) and offsets[oi] == pos:
            if lit_start < pos:
                ops.append(("lit", lit_start, pos))
            blk = verified[pos]
            if ops and ops[-1][0] == "copy" and (
                    ops[-1][1] + ops[-1][2] == blk):
                ops[-1] = ("copy", ops[-1][1], ops[-1][2] + 1)
            else:
                ops.append(("copy", blk, 1))
            pos += block_len
            lit_start = pos
        else:
            # No verified match at pos: jump straight to the next verified
            # offset instead of advancing byte-by-byte — the unmatched
            # region is already covered by lit_start, and a per-byte
            # Python loop would cost O(file bytes) interpreter steps.
            if oi < len(offsets) and offsets[oi] > pos:
                pos = offsets[oi]
            else:
                break
    if lit_start < L:
        ops.append(("lit", lit_start, L))
    return ops


def _with_tail_match(src, sig: FileSignature, ops: list[Op]) -> list[Op]:
    """If src's final bytes equal the destination's short tail block,
    replace the end of the trailing literal with a copy of the tail
    block."""
    n_full = sig.size // sig.block_len
    tail_len = sig.size - n_full * sig.block_len
    if tail_len == 0 or n_full >= len(sig.strong):
        return ops
    if not ops or ops[-1][0] != "lit" or ops[-1][2] - ops[-1][1] < tail_len:
        return ops
    _, start, end = ops[-1]
    if hashlib.md5(read_range(src, end - tail_len, tail_len)).digest() \
            == sig.strong[n_full]:
        ops = ops[:-1]
        if start < end - tail_len:
            ops.append(("lit", start, end - tail_len))
        ops.append(("copy", n_full, 1))
    return ops


def _materialize(ops: list[Op], src: bytes) -> list[Op]:
    return [("data", src[op[1]: op[2]]) if op[0] == "lit" else op
            for op in ops]


def _key_map(sig: FileSignature) -> dict:
    """{(weak, strong): the first full block of the signature with
    both}: which destination block a verified candidate copies."""
    out: dict = {}
    for idx in range(sig.size // sig.block_len):
        out.setdefault((int(sig.weak[idx]), sig.strong[idx]), idx)
    return out


def scan_ranges(items) -> list[list[Op]]:
    """Multi-file delta scan. ``items`` is a sequence of ``(source,
    FileSignature)`` pairs (a source: ``bytes``, or an object with
    ``size`` and ``pread``); returns one op stream per item, copies and
    ``("lit", start, end)`` ranges of the source, equal to
    ``compute_delta`` on each (tests/test_delta_batch.py).

    Files of one block length share staged buffers (``_pack``); a file
    longer than a window is taken a piece at a time, each piece from
    where the selection over the one before it stopped, against the
    file's whole signature. A buffer's signatures are merged into one
    sorted weak table: a hit on another file's block is a false
    candidate, dropped with the others when the candidate's own
    signature does not hold its (weak, strong). The verified offsets of
    all of a file's pieces go through one greedy selection. Empty
    files, sub-block files and signatures with no full block never
    reach the device."""
    sources = [_source(src) for src, _sig in items]
    results: list = [None] * len(items)
    groups: dict[int, list] = {}
    for i, (src, (_s, sig)) in enumerate(zip(sources, items)):
        if src.size == 0:
            results[i] = []
        elif sig.size // sig.block_len == 0 or src.size < sig.block_len:
            results[i] = _with_tail_match(src, sig, [("lit", 0, src.size)])
        else:
            groups.setdefault(sig.block_len, []).append(i)
    verified: dict[int, dict] = {}
    for block_len, scanned in groups.items():
        geo = _Geometry.of(block_len)
        count("delta.files", len(scanned))
        keys = {i: _key_map(items[i][1]) for i in scanned}
        for i in scanned:
            verified[i] = {}
        work = [(i, 0, min(geo.window, sources[i].size)) for i in scanned]
        while work:
            buffer = next(_pack(work, geo))
            work = work[len(buffer):] + _scan_buffer(
                buffer, sources, items, geo, keys, verified)
    with span("delta.select"):
        for i, found in verified.items():
            sig = items[i][1]
            results[i] = _with_tail_match(
                sources[i], sig,
                _select_ranges(sources[i].size, sig.block_len, found))
    return results


def _open_runs(held: np.ndarray) -> list[tuple[int, int]]:
    """The maximal runs [t, e) of aligned blocks that did not hold."""
    edges = np.flatnonzero(np.diff(np.concatenate(
        [[True], held, [True]]).astype(np.int8)))
    return list(zip(edges[0::2].tolist(), edges[1::2].tolist()))


def _scan_buffer(buffer, sources, items, geo: _Geometry, keys: dict,
                 verified: dict) -> list[tuple]:
    """One staged buffer: the aligned probe, then the search at every
    offset where it left blocks open, then the strong check; what holds
    goes into ``verified[item]`` by source offset. Returns the pieces
    that go on from here: the next window of a long file, or the rest
    of a piece from where the selection left what this buffer knows
    (``_walk_piece``)."""
    import jax

    B = geo.block_len
    with span("delta.stage"):
        host = np.zeros(geo.window, np.uint8)
    _fill(buffer, sources, host)
    with span("delta.stage"):
        dev = jax.device_put(host)
        # bytes put on the chip, zeros included
        record_copy("delta.stage", geo.window)
    valid = sum(p[2] for p in buffer)
    count("delta.batches")
    count("delta.bytes_valid", valid)
    count("delta.bytes_padded", geo.window - valid)
    with span("delta.launch"):
        out = delta_sig_flat(dev, block_len=B)
    with span("delta.fetch"):
        w_all = np.asarray(out[0])  # lint: ignore[VL501] host-result contract: a window's aligned probe
        s_all = np.asarray(out[1])  # lint: ignore[VL501] host-result contract: a window's aligned probe
    with span("delta.select"):
        probes = []  # a piece: its open runs
        rows, until = [], []
        for item, off, n, base in buffer:
            at, nbk = base // B, n // B
            strong = _digests(s_all[at: at + nbk])
            held = np.zeros(nbk, bool)
            for t, (w, st) in enumerate(zip(w_all[at: at + nbk].tolist(),
                                            strong)):
                block = keys[item].get((w, st))
                if block is not None:
                    verified[item][off + t * B] = block
                    held[t] = True
            count("delta.verified", int(held.sum()))
            runs = _open_runs(held)
            probes.append(runs)
            last = base + n - B  # the last window start inside the piece
            for t, e in runs:
                first = (base + t * B + 1) // 1024
                stop = min(base + e * B - 1, last) // 1024
                rows.extend(range(first, stop + 1))
                until.extend([last + 1] * (stop + 1 - first))
    found = _search_rows(buffer, dev, items, geo, keys, rows, until)
    with span("delta.select"):
        nexts = []
        for (item, off, n, _base), runs, hits in zip(buffer, probes, found):
            size = sources[item].size
            verified[item].update(hits)
            start = _walk_piece(off, n, B, runs, [c for c, _block in hits],
                                final=off + n >= size)
            if start is not None and size - start >= B:
                nexts.append((item, start, min(geo.window, size - start)))
    return nexts


def _walk_piece(off: int, n: int, B: int, runs: list, hits: list,
                final: bool) -> Optional[int]:
    """Greedy selection's way through one probed and searched piece
    [off, off + n) of a file, which it enters at ``off``: where the
    file's next piece starts, or None when the file is done.

    What the buffer knows: the aligned blocks outside ``runs`` hold;
    inside an open run [t, e) every offset was searched and ``hits``
    (ascending) are all its matches. So the selection is followed
    exactly (a copy moves it one block on, else it jumps to the next
    match) as long as it stands on the probe's alignment or inside an
    open run. A match at another alignment that ends inside blocks that
    held (a run of one repeated block, moved by an insertion) leaves it
    where nothing was searched: the piece is cut there and probed again
    from that byte. A piece searched to its end with no match goes on
    from its last block, which the next buffer searches across the
    seam."""
    nbk = n // B
    last = off + n - B  # the last offset a block starts at inside the piece
    starts = [t for t, _e in runs]
    pos = off
    while pos <= last:
        t = (pos - off) // B
        k = bisect.bisect_right(starts, t) - 1
        if k < 0 or runs[k][1] <= t:  # block t held
            if (pos - off) % B:
                count("delta.reprobes")
                return pos
            pos = off + (runs[k + 1][0] if k + 1 < len(runs) else nbk) * B
            continue
        e = runs[k][1]
        h = bisect.bisect_left(hits, pos)
        if h < len(hits) and hits[h] < off + e * B:
            pos = hits[h] + B
        elif e < nbk:
            pos = off + e * B
        else:  # searched to its end, no match
            return None if final else last
    return None if final else pos


def _search_rows(buffer, dev, items, geo: _Geometry, keys: dict,
                 rows: list, until: list) -> list[list]:
    """Every offset of the listed rows of the staged buffer against the
    buffer's signatures, all of them in one dispatch (another only
    where the candidates pass what one holds): a piece's verified
    (source offset, destination block) pairs, ascending."""
    import jax

    B = geo.block_len
    found: list[list] = [[] for _ in buffer]
    if not rows:
        return found
    count("delta.search_rows", len(rows))
    with span("delta.stage"):
        owners = sorted({p[0] for p in buffer})
        table = np.sort(np.concatenate(
            [items[i][1].weak[: items[i][1].size // B] for i in owners]))
        sorted_weak = np.full(geo.sig_cap(len(table)), 0xFFFFFFFF, np.uint32)
        sorted_weak[: len(table)] = table
        sw_dev = jax.device_put(sorted_weak)
        owner = np.zeros(geo.window // geo.slot, np.int32)
        for at, (_item, _off, n, base) in enumerate(buffer):
            owner[base // geo.slot: -(-(base + n) // geo.slot)] = at
        take = np.zeros(geo.rows, np.int32)
        take_until = np.zeros(geo.rows, np.int32)
        take[: len(rows)] = rows
        take_until[: len(rows)] = until
        take_dev = jax.device_put(take)
        until_dev = jax.device_put(take_until)
    groups = -(-len(rows) // geo.group_rows)
    group = lo = 0
    while group < groups:
        with span("delta.launch"):
            # every run of the program reads the staged window
            record_copy("delta.search", geo.window)
            out = delta_match_rows(  # lint: ignore[VL502] one dispatch a buffer, more where the candidates overflow
                dev, sw_dev, np.int32(len(table)), take_dev, until_dev,
                np.int32(groups), np.int32(group), np.int32(lo), window=B,
                group_rows=geo.group_rows, max_candidates=geo.cand_cap,
                capacity=geo.search_cap)
        with span("delta.fetch"):
            n, group, ran = np.asarray(out[2]).tolist()  # lint: ignore[VL501] host-result contract: a search's counts
            cand = np.asarray(out[0])[:n]  # lint: ignore[VL501] host-result contract: a search's candidates
            weak_at = np.asarray(out[1])[:n]  # lint: ignore[VL501] host-result contract: a search's candidates
        count("delta.search_rows_run", ran * geo.group_rows)
        count("delta.candidates", n)
        for first in range(0, n, geo.cand_cap):
            part = cand[first: first + geo.cand_cap]
            with span("delta.verify"):
                strongs = _verify(dev, part, geo)
            with span("delta.select"):
                held = 0
                at = owner[part // geo.slot]
                for c, w, strong, a in zip(
                        part.tolist(),
                        weak_at[first: first + geo.cand_cap].tolist(),
                        strongs, at.tolist()):
                    item, off, _n, base = buffer[a]
                    block = keys[item].get((w, strong))
                    if block is not None:
                        found[a].append((c - base + off, block))
                        held += 1
                count("delta.verified", held)
        if group < groups:
            # more candidates than one search holds (a run of one
            # repeated block inside an open run): again, from the group
            # it stopped at and past the last one taken
            count("delta.overflow_retries")
            lo = int(cand[-1]) + 1
    return found


def _verify(dev, cand: np.ndarray, geo: _Geometry) -> list[bytes]:
    """The strong check of one buffer's candidates at the fixed
    capacity: the MD5 digest of the block at each of ``cand``."""
    import jax

    with span("delta.verify_stage"):
        starts = np.zeros(geo.cand_cap, np.int32)
        starts[: len(cand)] = cand
        starts_dev = jax.device_put(starts)
        # the bytes the program gathers and hashes: every slot of the
        # capacity is a window, a padded one the window at offset 0
        record_copy("delta.verify", geo.cand_cap * geo.block_len)
    with span("delta.verify_launch"):
        out = delta_md5_flat(dev, starts_dev, block_len=geo.block_len)
    with span("delta.verify_fetch"):
        return _digests(np.asarray(out)[: len(cand)])


def delta_scan_batch(items) -> list[list[Op]]:
    """``scan_ranges`` over ``(bytes, FileSignature)`` pairs with the
    literals read: ("copy", first, n) | ("data", bytes) ops."""
    return [_materialize(ops, src)
            for ops, (src, _sig) in zip(scan_ranges(items), items)]


def apply_delta(ops: list[Op], dest: bytes, block_len: int) -> bytes:
    """Destination side: rebuild the file from its own blocks + literals."""
    out = bytearray()
    for op in ops:
        if op[0] == "data":
            out += op[1]
        else:
            _, first, count_ = op
            start = first * block_len
            out += dest[start : start + count_ * block_len]
    return bytes(out)  # lint: ignore[VL106] rebuilt file is the return contract


def literal_bytes(ops: list[Op]) -> int:
    return sum(len(op[1]) if op[0] == "data" else op[2] - op[1]
               for op in ops if op[0] != "copy")


def delta_stats(ops: list[Op], block_len: int) -> dict:
    copied = sum(op[2] * block_len for op in ops if op[0] == "copy")
    return {"copied_bytes": copied, "literal_bytes": literal_bytes(ops)}


def _pow2ceil(n: int) -> int:
    v = 1
    while v < n:
        v *= 2
    return v


def _weak_at_offsets(arr: np.ndarray, offsets, block_len: int) -> np.ndarray:
    """Weak checksums at given offsets via numpy prefix sums (vectorized;
    identical arithmetic to ops/rolling.py)."""
    if len(offsets) == 0:
        return np.zeros((0,), np.uint32)
    x = arr.astype(np.uint32)
    j = np.arange(len(arr), dtype=np.uint32)
    with np.errstate(over="ignore"):
        S = np.concatenate([[0], np.cumsum(x, dtype=np.uint32)])
        T = np.concatenate([[0], np.cumsum(j * x, dtype=np.uint32)])
        off = np.asarray(offsets, dtype=np.int64)
        dS = S[off + block_len] - S[off]
        dT = T[off + block_len] - T[off]
        a = dS & np.uint32(0xFFFF)
        b = ((off.astype(np.uint32) + np.uint32(block_len)) * dS - dT) & np.uint32(0xFFFF)
    return (a | (b << np.uint32(16))).astype(np.uint32)
