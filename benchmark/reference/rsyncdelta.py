"""rsync's delta transfer of one file against an older copy of it, with
``numpy`` and ``hashlib`` alone (nothing of ``volsync_tpu``): the plain
reference the rsync mover's pushes are held to.

As Tridgell and Mackerras describe it ("The rsync algorithm", 1996):
the receiver cuts its old file into blocks of one length and sends a
weak rolling checksum and a strong checksum of each; the sender
computes the weak checksum at EVERY offset of its new file, checks the
strong checksum wherever the weak one is in the receiver's list, and
sends the receiver's block number where both agree, literal bytes
elsewhere; after a match the search goes on at the end of the matched
block (greedy, leftmost).

    weak32:  a = sum x_i mod 2^16,  b = sum (n - i) x_i mod 2^16
             (i from 0, n the block's length),  s = a + 2^16 b
    strong:  MD5 of the block (``hashlib.md5``)

Departures from the paper and from the rsync binary, each the
program's own and stated in ``configs/rsync-1g.json``:

- the block length is the least power of two, within 4-128 KiB, that
  is not under the whole part of the square root of the sender's file
  size (rsync rounds the square root to a multiple of 8, from 700
  bytes up);
- the strong checksum is the whole 16-byte MD5 and is not seeded
  (rsync sends as few of its bytes as the file's length needs);
- the receiver's short last block takes part only at the very end of
  the sender's file (the tail-block rule), and a sender's file shorter
  than one block is compared with it alone;
- where several of the receiver's blocks carry one (weak, strong)
  pair, the lowest block number is sent;
- there is no whole-file checksum after the transfer.

The 16-bit tag table that narrows the search before the sorted list is
the paper's own first level. A file is looked at a window at a time, so
a file of any length fits; the windows overlap by a block less one byte,
so every offset is looked at once.
"""

from __future__ import annotations

import hashlib
import math
import os

import numpy as np

MIN_BLOCK = 4096
MAX_BLOCK = 128 * 1024
#: how much of a file is looked at at a time
WINDOW = 64 * 1024 * 1024


def block_len_for(size: int) -> int:
    """The least power of two from 4 KiB to 128 KiB that is not under
    the whole part of the square root of ``size``: a file of b * b + 1
    to b * b + 2 * b bytes still has blocks of b."""
    root = math.isqrt(max(size, 0))
    b = MIN_BLOCK
    while b < root and b < MAX_BLOCK:
        b *= 2
    return b


def _bytes_of(source) -> np.ndarray:
    """A file's bytes as a uint8 array: a path is mapped, not read."""
    if isinstance(source, (str, os.PathLike)):
        if os.path.getsize(source) == 0:
            return np.zeros(0, np.uint8)
        return np.memmap(source, dtype=np.uint8, mode="r")
    return np.frombuffer(source, np.uint8)


def weak32(block) -> int:
    x = np.asarray(block).astype(np.uint64)
    n = len(x)
    a = int(x.sum()) & 0xFFFF
    b = int((x * np.arange(n, 0, -1, dtype=np.uint64)).sum()) & 0xFFFF
    return a | (b << 16)


def signature(old, block_len: int) -> dict:
    """The receiver's list: weak and strong checksum of every block of
    ``old``, its short last block included at its own length."""
    arr = _bytes_of(old)
    weak, strong = [], []
    for at in range(0, len(arr), block_len):
        block = np.asarray(arr[at: at + block_len])
        weak.append(weak32(block))
        strong.append(hashlib.md5(block).digest())
    return {"size": len(arr), "block_len": block_len,
            "weak": np.array(weak, np.uint32), "strong": strong}


def _weak_everywhere(win: np.ndarray, block_len: int) -> np.ndarray:
    """weak32 of win[k: k + block_len] for every k (prefix sums in
    uint32 wraparound: 2^16 divides 2^32, so the residues are exact)."""
    x = win.astype(np.uint32)
    n = len(x)
    with np.errstate(over="ignore"):
        S = np.zeros(n + 1, np.uint32)
        np.cumsum(x, dtype=np.uint32, out=S[1:])
        T = np.zeros(n + 1, np.uint32)
        np.cumsum(np.arange(n, dtype=np.uint32) * x, dtype=np.uint32,
                  out=T[1:])
        dS = S[block_len:] - S[: n - block_len + 1]
        dT = T[block_len:] - T[: n - block_len + 1]
        k = np.arange(n - block_len + 1, dtype=np.uint32)
        a = dS & np.uint32(0xFFFF)
        b = ((k + np.uint32(block_len)) * dS - dT) & np.uint32(0xFFFF)
    return a | (b << np.uint32(16))


def _weak_hits(arr: np.ndarray, block_len: int, full_weak: np.ndarray):
    """Ascending offsets of ``arr`` whose weak checksum is one of the
    receiver's full blocks', with that checksum."""
    if len(full_weak) == 0 or len(arr) < block_len:
        return
    listed = np.unique(full_weak)
    tag_lo = np.zeros(1 << 16, bool)
    tag_lo[listed & 0xFFFF] = True
    tag_hi = np.zeros(1 << 16, bool)
    tag_hi[listed >> 16] = True
    step = WINDOW - block_len + 1
    for start in range(0, len(arr) - block_len + 1, step):
        weak = _weak_everywhere(np.asarray(arr[start: start + WINDOW]),
                                block_len)
        idx = np.flatnonzero(tag_lo[weak & 0xFFFF] & tag_hi[weak >> 16])
        idx = idx[np.isin(weak[idx], listed)]
        for k, w in zip(idx.tolist(), weak[idx].tolist()):
            yield start + k, w


def delta(new, sig: dict) -> list[tuple]:
    """The sender's side: ("copy", first block, blocks) and
    ("lit", start, end) ranges of ``new`` that rebuild it from the
    receiver's blocks."""
    arr = _bytes_of(new)
    L, B = len(arr), sig["block_len"]
    n_full = sig["size"] // B
    first_with: dict = {}
    for idx in range(n_full):
        first_with.setdefault((int(sig["weak"][idx]), sig["strong"][idx]),
                              idx)
    ops: list[tuple] = []
    lit, pos = 0, 0
    for off, w in _weak_hits(arr, B, sig["weak"][:n_full]):
        if off < pos:
            continue  # inside the block just matched
        strong = hashlib.md5(np.asarray(arr[off: off + B])).digest()
        block = first_with.get((w, strong))
        if block is None:
            continue
        if lit < off:
            ops.append(("lit", lit, off))
        if ops and ops[-1][0] == "copy" and ops[-1][1] + ops[-1][2] == block:
            ops[-1] = ("copy", ops[-1][1], ops[-1][2] + 1)
        else:
            ops.append(("copy", block, 1))
        pos = lit = off + B
    if lit < L:
        ops.append(("lit", lit, L))
    # the tail-block rule
    tail = sig["size"] - n_full * B
    if tail and ops and ops[-1][0] == "lit" and ops[-1][2] - ops[-1][1] >= tail:
        _, start, end = ops[-1]
        if hashlib.md5(np.asarray(arr[end - tail: end])).digest() \
                == sig["strong"][n_full]:
            ops.pop()
            if start < end - tail:
                ops.append(("lit", start, end - tail))
            ops.append(("copy", n_full, 1))
    return ops


def literal_bytes(ops: list[tuple]) -> int:
    return sum(op[2] - op[1] for op in ops if op[0] == "lit")


def apply(ops: list[tuple], old, new, block_len: int) -> bytes:
    """The receiver's side: its own blocks and the sender's literal
    ranges, in order."""
    old, new = _bytes_of(old), _bytes_of(new)
    out = bytearray()
    for op in ops:
        if op[0] == "copy":
            out += np.asarray(
                old[op[1] * block_len: (op[1] + op[2]) * block_len]).tobytes()
        else:
            out += np.asarray(new[op[1]: op[2]]).tobytes()
    return bytes(out)


def file_delta(new, old) -> list[tuple]:
    """``delta`` of ``new`` against ``old`` at the block length of
    ``new``'s size."""
    size = len(_bytes_of(new))
    return delta(new, signature(old, block_len_for(size)))


def tree_delta(source, dest) -> dict:
    """What one push of the tree ``source`` onto the tree ``dest`` has
    to do: per regular file of ``source`` the literal bytes of its
    delta against the same path of ``dest`` (all of it where ``dest``
    has no regular file there: ``files_new``), the entries of ``dest``
    that ``source`` lacks (``pruned``), and ``staged_floor``: the bytes
    of the files of one block or more that had a basis, of one block or
    more, of another size or mtime (a sender cannot skip those by
    rsync's quick check, and has a block to look for)."""
    from benchmark.reference import treecmp

    want, have = treecmp.entries(source), treecmp.entries(dest)
    out = {"files": 0, "bytes": 0, "literal_bytes": 0, "files_new": 0,
           "files_basis": 0, "staged_floor": 0,
           "pruned": len(set(have) - set(want)), "by_file": {}}
    import stat

    for rel, st in sorted(want.items()):
        if not stat.S_ISREG(st.st_mode):
            continue
        out["files"] += 1
        out["bytes"] += st.st_size
        old = have.get(rel)
        if old is None or not stat.S_ISREG(old.st_mode):
            out["files_new"] += 1
            lit = st.st_size
        else:
            out["files_basis"] += 1
            lit = literal_bytes(file_delta(os.path.join(source, rel),
                                           os.path.join(dest, rel)))
            changed = (st.st_size != old.st_size
                       or st.st_mtime_ns != old.st_mtime_ns)
            block = block_len_for(st.st_size)
            if changed and min(st.st_size, old.st_size) >= block:
                out["staged_floor"] += st.st_size
        out["literal_bytes"] += lit
        out["by_file"][rel] = lit
    return out
