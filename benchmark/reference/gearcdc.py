"""Chunk boundaries of the repository format's content-defined chunker,
from numpy alone.

Written from the format's description (the program's own copies are
``volsync_tpu/ops/gearcdc.py`` and the fused walk in
``volsync_tpu/ops/segment.py``; this file imports nothing of them). The
numbers (``min_size``, ``avg_size``, ``max_size``, ``seed``,
``norm_level``, ``align``) come from the configuration's file, which
states the chunker the deployment runs.

A cut may fall only after a byte at position p = r * align + align - 1.
The gear hash there covers the 32 bytes ending at p:

    h(p) = sum over m = 0..31 of  G[b[p - 31 + m]] << (31 - m)   (mod 2^32)
    G[b] = mix(b + seed),  mix = the Murmur3-style u32 finalizer below

p is a strict candidate where the top (e + norm) bits of h(p) are zero
and a lax one where the top (e - norm) are, e = log2(avg) - log2(align).
From a chunk's start s the cut is the first strict candidate in
[s + min - 1, s + avg - 2], else the first lax one in
[s + avg - 1, s + max - 1], else s + max - 1; the stream's end closes
the last chunk.
"""

from __future__ import annotations

import numpy as np

WINDOW = 32


def _mix(x: np.ndarray) -> np.ndarray:
    x = (x ^ (x >> np.uint32(16))) * np.uint32(0x7FEB352D)
    x = (x ^ (x >> np.uint32(15))) * np.uint32(0x846CA68B)
    return x ^ (x >> np.uint32(16))


def _top_bits(bits: int) -> np.uint32:
    bits = max(1, min(bits, 31))
    return np.uint32((((1 << bits) - 1) << (32 - bits)) & 0xFFFFFFFF)


def candidates(data, chunker: dict) -> tuple[np.ndarray, np.ndarray]:
    """(strict, lax) candidate positions, ascending."""
    align, seed = int(chunker["align"]), int(chunker["seed"])
    if align < WINDOW:
        raise ValueError("the reference covers align >= 32 only")
    buf = np.frombuffer(data, np.uint8)
    rows = len(buf) // align
    win = buf[: rows * align].reshape(rows, align)[:, align - WINDOW:]
    with np.errstate(over="ignore"):
        g = _mix(win.astype(np.uint32) + np.uint32(seed & 0xFFFFFFFF))
        shifts = np.arange(WINDOW - 1, -1, -1, dtype=np.uint32)
        h = np.zeros(rows, np.uint32)
        for m in range(WINDOW):
            h += g[:, m] << shifts[m]
    e = (int(chunker["avg_size"]).bit_length() - 1) - (align.bit_length() - 1)
    norm = int(chunker["norm_level"])
    pos = np.arange(rows, dtype=np.int64) * align + (align - 1)
    return (pos[(h & _top_bits(e + norm)) == 0],
            pos[(h & _top_bits(e - norm)) == 0])


def cuts(data, chunker: dict) -> list[tuple[int, int]]:
    """[(offset, length)] of the chunks of one whole stream."""
    n = len(data)
    lo_len, avg, hi_len = (int(chunker[k]) for k in
                           ("min_size", "avg_size", "max_size"))
    if n == 0:
        return []
    if n <= lo_len:
        return [(0, n)]
    strict, lax = candidates(data, chunker)
    out = []
    start = 0
    while start < n:
        first, mid, last = start + lo_len - 1, start + avg - 1, \
            min(start + hi_len - 1, n - 1)
        cut = None
        i = int(np.searchsorted(strict, first))
        if i < len(strict) and strict[i] <= min(mid - 1, last):
            cut = int(strict[i])
        if cut is None:
            j = int(np.searchsorted(lax, max(first, mid)))
            if j < len(lax) and lax[j] <= last:
                cut = int(lax[j])
        if cut is None:
            cut = last
        out.append((start, cut - start + 1))
        start = cut + 1
    return out
