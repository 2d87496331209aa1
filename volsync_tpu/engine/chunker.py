"""Streaming CDC chunk+hash pipeline: the mover's device hot path.

Replaces the chunk/hash core of the engine the reference wraps
(mover-restic/entry.sh:63 `restic backup` — Rabin CDC + per-blob SHA-256
on CPU): a segment of the input stream is uploaded to the device once,
gear-hash CDC candidates and per-chunk SHA-256 digests both run on that
resident buffer, and only (boundaries, digests) come back to the host.

Streaming determinism: each segment handed to the CDC starts exactly at a
chunk boundary, and no cut is eligible before min_size-1 >= 31 positions
in, so every eligible position sees its full 32-byte gear window within
the segment — boundaries are bit-identical to one-shot chunking of the
whole stream (see ops/gearcdc.py).
"""

from __future__ import annotations

import contextlib
import logging
import queue
import threading
from typing import Callable, Iterator, Optional

import numpy as np

from volsync_tpu import envflags
from volsync_tpu.engine import bufpool
from volsync_tpu.obs import count, off_ring, record_copy, span, use_context
from volsync_tpu.repo import blobid

from volsync_tpu.ops.gearcdc import (
    GearParams,
    cdc_candidates,
    cdc_candidates_aligned_packed,
    select_boundaries,
)
from volsync_tpu.ops.gearcdc import _pow2ceil_int as _pow2ceil
from volsync_tpu.ops.segment import (
    LEAF_SIZE,
    FusedSegmentHasher,
    _buffer_bucket,
)
from volsync_tpu.ops.sha256 import (
    sha256_chunks_device,
    sha256_leaves_device,
)

log = logging.getLogger("volsync_tpu.engine")


def params_from_config(cfg: dict) -> GearParams:
    # Repos written before the aligned-cut format carry no "align" key;
    # they keep the fully shift-invariant align=1 behavior forever so
    # their existing chunk boundaries (and dedup) stay valid.
    return GearParams(min_size=cfg["min_size"], avg_size=cfg["avg_size"],
                      max_size=cfg["max_size"], seed=cfg["seed"],
                      align=cfg.get("align", 1))


class DeviceChunkHasher:
    """chunk+hash a byte buffer with one host->device upload.

    All device call shapes are drawn from small bounded bucket sets
    (padded buffer sizes, fixed candidate capacity, size-classed chunk
    batches with pow2 lane counts) so the jit cache converges after a few
    segments regardless of workload shape.

    With the page-aligned format (align == 4096, the repo default) the
    whole segment runs as ONE fused device program with ONE small result
    fetch (ops/segment.py): candidates, the FastCDC walk, leaf hashing,
    and Merkle-root assembly all stay on device, and only the chunk
    table + 32-byte roots come back (~40 bytes per ~1 MiB chunk instead
    of 32 bytes per 4 KiB leaf plus a candidate round-trip). The chunk
    list is then known only at ``finish()`` — segments of ONE stream
    serialize on that fetch, and scaling comes from concurrent streams
    (many CRs per chip), matching the reference's concurrency model
    (reference: controllers/replicationsource_controller.go:145).
    64 <= align < 4096 keeps the split-phase pipeline (synchronous
    boundary walk, leaf hashing left in flight); align=1 the legacy
    shift-invariant path.

    The hasher protocol, as ``stream_chunk_batches`` and ``TreeBackup``
    use it (``parallel/sharded_chunker.MeshChunkHasher`` is the other
    implementation): ``process(buffer, eof=)`` -> the cut list;
    ``begin(buffer, eof=, valid_len=)`` -> a ``PendingSegment``, where
    ``buffer`` may already be padded with zeros to the hasher's bucket
    and ``valid_len`` says how much of it is data; optionally
    ``buffer_bucket(length)`` (the pad target, else ``_buffer_bucket``)
    and ``stream_segment_size(segment_size)`` (the fill a stream
    should use, else the caller's). An object with ``process`` alone is
    accepted as a test fake and gets exact-length views.
    """

    # Safe to drive from concurrent threads (the service's handlers
    # share one): no per-call mutable state; the fused hasher is
    # stateless and jit caches are global and locked.

    #: Owners that manage their own batching (MoverJaxServer) set this
    #: False so the process-wide VOLSYNC_BATCH_SEGMENTS hook cannot
    #: override their explicit per-request configuration.
    use_shared_batcher = True

    def __init__(self, params: GearParams):
        self.params = params
        # the page-aligned fused format, else split-phase or legacy
        self.fused = (FusedSegmentHasher(params)
                      if params.align == LEAF_SIZE else None)

    def process(self, buffer, *, eof: bool = True) -> list[tuple[int, int, str]]:
        """-> [(start, length, sha256-hex)] covering ``buffer`` (the tail
        is withheld when not ``eof`` — the caller re-feeds it)."""
        return self.begin(buffer, eof=eof).finish()

    def _batcher(self):
        """The process-wide microbatcher ``begin`` routes to, or None."""
        if self.use_shared_batcher and self.fused is not None:
            from volsync_tpu.ops.batcher import shared_batcher

            return shared_batcher(self.params)
        return None

    def producer(self):
        """What a stream holds while it feeds this hasher (a context
        manager): where ``begin`` routes to the shared batcher, the
        stream is one of its blocking producers for that long
        (ops/batcher.py ``producer``), so that a batch which holds a
        segment of every stream is not made to wait for more."""
        batcher = self._batcher()
        if batcher is None:
            return contextlib.nullcontext()
        return batcher.producer()

    def begin(self, buffer, *, eof: bool = True,
              valid_len: Optional[int] = None) -> "PendingSegment":
        """Upload + dispatch the segment's device work, leaving it IN
        FLIGHT. On the fused path the chunk table itself is part of the
        one in-flight result, so ``.chunks``/``.end`` block until the
        fetch; on the split-phase path (align < 4096) the boundary walk
        runs synchronously here and only the leaf digests stay in
        flight.

        ``buffer`` may be bytes/bytearray/memoryview or a uint8 ndarray.
        Callers that already hold a bucket-padded view
        (stream_chunk_batches' pooled segments) pass the padded view
        plus ``valid_len``: it then reaches the device with no host copy
        on either way (no np.pad here; through the batcher, alone in its
        dispatch, no staging copy either: ops/segment.py _hash_bucket).
        A buffer shorter than its bucket is copied once into zeroed
        padding (np.pad here, the batch's rows there).

        When batching is enabled (ops/batcher._batching_enabled:
        VOLSYNC_BATCH_SEGMENTS=1, or unset on a TPU backend — the
        backend-aware default) the fused path routes through the
        process-wide microbatcher: concurrent workers' segments —
        different files of one TreeBackup, different CRs' movers in one
        operator — coalesce into single cross-PVC batched
        dispatches."""
        import jax.numpy as jnp

        if isinstance(buffer, (bytes, bytearray, memoryview)):
            buffer = np.frombuffer(buffer, dtype=np.uint8)
        have = int(buffer.shape[0])
        length = have if valid_len is None else int(valid_len)
        if length == 0:
            return PendingSegment([], None, None)
        p = self.params
        if length <= p.min_size:
            if not eof:
                return PendingSegment([], None, None)
            # hashlib consumes the ndarray view directly — no tobytes()
            # round-trip for the small-buffer host path.
            return PendingSegment(
                [(0, length, blobid.blob_id(buffer[:length]))], None, None)

        batcher = self._batcher()
        if batcher is not None:
            # consumed == the last chunk's end by the walk's
            # semantics, which is exactly what PendingSegment.end
            # derives from the chunk list. The ndarray is handed
            # over as it is; whether it is copied is the batch's to
            # say (_hash_bucket: not when it is bucket-shaped and
            # alone). submit blocks until the result is fetched, so
            # it stays alive and unchanged for as long as it is read.
            chunks, _consumed = batcher.submit(buffer, length, eof)
            return PendingSegment(chunks, None, None)

        padded = _buffer_bucket(length)
        # the single-lane way to the device, timed and counted as the
        # batched one is (ops/segment.py _hash_bucket, count_dispatch)
        with span("ops.stage", lanes=1, bucket=padded):
            if have < padded:
                record_copy("device.pad", length)
                buffer = np.pad(buffer, (0, padded - have))
            elif have > padded:
                buffer = buffer[:padded]
            dev = jnp.asarray(buffer)
        if self.fused is not None:
            with span("engine.fused_dispatch"):
                inflight = self.fused.dispatch(dev, length, eof=eof)
            return PendingSegment.fused_segment(
                self.fused, dev, length, inflight, eof)
        with span("engine.candidates"):
            idx_s, idx_l = self._candidates(dev, length)
        with span("engine.boundary_walk"):
            chunks = select_boundaries(idx_s, idx_l, length, p, eof=eof)
        if not chunks:
            return PendingSegment([], None, None)
        if p.align >= 64:
            # Split-phase aligned path (64 <= align < 4096): leaf digests
            # stay in flight; chunks are known synchronously.
            plan = _leaf_plan(chunks)
            dev_digests = _dispatch_leaves(dev, plan[0], plan[1], plan[2])
            return PendingSegment.split_phase(chunks, (plan, dev_digests))
        # Legacy unaligned path: synchronous gather hashing.
        hexes = device_span_roots(dev, chunks, aligned=False)
        return PendingSegment(
            [(int(s), int(l), h) for (s, l), h in zip(chunks, hexes)],
            None, None)

    def _candidates(self, dev, length: int):
        p = self.params
        padded = int(dev.shape[0])
        if p.align > 1:
            cap = 4096  # expected count: padded/avg_size << 4096
            while True:
                packed = np.asarray(cdc_candidates_aligned_packed(  # lint: ignore[VL501] the split-phase protocol's candidate fetch: one compacted table a segment, metadata not payload
                    dev, seed=p.seed, mask_s=p.mask_s, mask_l=p.mask_l,
                    align=p.align, max_candidates=cap, valid_len=length))
                c = int(packed[-1])
                if c <= cap:
                    break
                cap = _pow2ceil(c, cap * 2)
            pos = packed[:c]
            flags = packed[cap: cap + c].astype(bool)
            return pos[flags], pos
        # Classic unaligned path: one candidate per 64 bytes covers any
        # mask down to 2^-6 density; denser (adversarial) data retries
        # with a doubled cap.
        cap = padded // 64
        while True:
            # valid_len masks the zero-padded tail on device: padding can
            # neither add candidates nor inflate the overflow counts.
            idx_s, count_s, idx_l, count_l = cdc_candidates(
                dev, seed=p.seed, mask_s=p.mask_s, mask_l=p.mask_l,
                max_candidates=cap, valid_len=length,
            )
            cs, cl = int(count_s), int(count_l)
            if cs <= cap and cl <= cap:
                break
            cap = _pow2ceil(max(cs, cl), cap * 2)
        return np.asarray(idx_s)[:cs], np.asarray(idx_l)[:cl]


def device_leaf_digests(dev, leaf_starts: list[int],
                        leaf_lengths: list[int]) -> list[bytes]:
    """SHA-256 digests of arbitrary <=4 KiB slices of a device buffer,
    every slice an independent lane (wide batch, 65-step scan, a single
    compiled shape per lane-count bucket)."""
    import jax.numpy as jnp

    lanes = _pow2ceil(len(leaf_starts), 128)
    starts = np.zeros((lanes,), np.int32)
    lengths = np.zeros((lanes,), np.int32)
    starts[: len(leaf_starts)] = leaf_starts
    lengths[: len(leaf_lengths)] = leaf_lengths
    digests = np.asarray(sha256_chunks_device(  # lint: ignore[VL501] one batched 32 B/lane digest download — this helper's contract
        dev, jnp.asarray(starts), jnp.asarray(lengths),
        max_len=blobid.LEAF_SIZE,
    )).astype(">u4")
    # Digest download: 32 B per lane, metadata not payload.
    leaf_bytes = digests.tobytes()  # lint: ignore[VL106] digest lanes
    return [leaf_bytes[32 * k : 32 * (k + 1)]
            for k in range(len(leaf_starts))]


def _leaf_plan(chunks: list[tuple[int, int]]):
    """Host-side leaf assignment for a chunk list (aligned cuts): which
    leaves are full (strided path) vs short tails (gather path), plus the
    bookkeeping to reassemble per-chunk leaf sequences afterwards."""
    full_rows: list[int] = []
    short_starts: list[int] = []
    short_lengths: list[int] = []
    slot: list[tuple[bool, int]] = []      # leaf -> (is_full, index)
    spans: list[tuple[int, int]] = []      # chunk -> (first leaf, count)
    for start, length in chunks:
        first = len(slot)
        n = blobid.leaf_count(length)
        for k in range(n):
            off = k * blobid.LEAF_SIZE
            s = start + off
            l = min(blobid.LEAF_SIZE, length - off)
            if l == blobid.LEAF_SIZE:
                assert s % 64 == 0, "aligned path requires 64B leaf starts"
                slot.append((True, len(full_rows)))
                full_rows.append(s // 64)
            else:
                slot.append((False, len(short_starts)))
                short_starts.append(s)
                short_lengths.append(l)
        spans.append((first, n))
    return full_rows, short_starts, short_lengths, slot, spans


def _dispatch_leaves(dev, full_rows, short_starts, short_lengths):
    """Launch the single fused leaf dispatch; returns the in-flight
    [F + T, 8] device array (callers fetch it as late as possible)."""
    import jax.numpy as jnp

    lanes_f = _pow2ceil(len(full_rows), 128)
    lanes_t = _pow2ceil(max(len(short_starts), 1), 8)
    rows = np.zeros((lanes_f,), np.int32)
    rows[: len(full_rows)] = full_rows
    ts = np.zeros((lanes_t,), np.int32)
    tl = np.zeros((lanes_t,), np.int32)
    ts[: len(short_starts)] = short_starts
    tl[: len(short_lengths)] = short_lengths
    return sha256_leaves_device(
        dev, jnp.asarray(rows), jnp.asarray(ts), jnp.asarray(tl),
        leaf_len=blobid.LEAF_SIZE), lanes_f


def _assemble_roots(chunks, plan, digests_np, lanes_f) -> list[str]:
    full_rows, short_starts, _, slot, spans = plan
    flat = digests_np.astype(">u4").tobytes()  # lint: ignore[VL106] 32 B/leaf digest wire form, metadata not payload

    def leaf(is_full: bool, i: int) -> bytes:
        base = (i if is_full else lanes_f + i) * 32
        return flat[base: base + 32]

    return [
        blobid.root_from_leaves(length,
                                [leaf(*slot[first + k]) for k in range(n)])
        for (first, n), (_, length) in zip(spans, chunks)
    ]


class PendingSegment:
    """A segment whose device work may still be in flight.

    Split-phase (64 <= align < 4096) and legacy (align=1) segments know
    their chunk list immediately; the fused path (align == 4096,
    ops/segment.py) learns it from the one result fetch, so ``chunks``
    / ``end`` force ``finish()`` there. Either way the public protocol
    is: ``.end`` = bytes consumed, ``finish()`` ->
    [(start, length, blob-id-hex)]."""

    def __init__(self, done, chunks, inflight):
        self._done = done
        self._inflight = inflight
        self._fused = None
        self._chunks = (chunks if chunks is not None
                        else [(s, l) for s, l, _ in (done or [])])

    @classmethod
    def fused_segment(cls, fsh, dev, length, inflight, eof):
        seg = cls([], None, None)
        seg._done = None
        seg._chunks = None
        seg._fused = (fsh, dev, length, inflight, eof)
        return seg

    @classmethod
    def split_phase(cls, chunks, inflight):
        seg = cls([], None, None)
        seg._done = None
        seg._chunks = list(chunks)
        seg._inflight = inflight
        return seg

    @property
    def chunks(self) -> list[tuple[int, int]]:
        if self._chunks is None:
            self.finish()
        return self._chunks

    @property
    def end(self) -> int:
        """One past the last covered byte (0 if nothing was emitted)."""
        if self._fused is not None and self._done is None:
            self.finish()
            return self._consumed
        if not self.chunks:
            return 0
        s, l = self.chunks[-1][0], self.chunks[-1][1]
        return int(s) + int(l)

    def finish(self) -> list[tuple[int, int, str]]:
        if self._done is not None:
            return self._done
        from volsync_tpu.obs import span

        if self._fused is not None:
            fsh, dev, length, inflight, eof = self._fused
            with span("engine.fused_fetch"):
                chunks, consumed = fsh.finish(dev, length, inflight, eof=eof)
            self._done = chunks
            self._chunks = [(s, l) for s, l, _ in chunks]
            self._consumed = consumed
            return self._done
        (plan, (dev_digests, lanes_f)) = self._inflight
        with span("engine.leaf_fetch_assemble"):
            hexes = _assemble_roots(self._chunks, plan,
                                    np.asarray(dev_digests), lanes_f)
        self._done = [(int(s), int(l), h)
                      for (s, l), h in zip(self._chunks, hexes)]
        self._inflight = None
        return self._done


def device_span_roots(dev, chunks: list[tuple[int, int]], *,
                      aligned: bool = False) -> list[str]:
    """Merkle blob ids for (start, length) slices of the device buffer
    (repo/blobid.py): every 4 KiB leaf of every chunk hashes as one
    independent lane, then the tiny roots combine host-side.

    ``aligned=True`` asserts every chunk start is 64-byte aligned
    (GearParams.align >= 64): full leaves then take the strided
    row-gather path and only each chunk's short tail leaf (<4 KiB)
    pays the generic gather kernel, in ONE fused dispatch.
    """
    if aligned:
        plan = _leaf_plan(chunks)
        dev_digests, lanes_f = _dispatch_leaves(
            dev, plan[0], plan[1], plan[2])
        return _assemble_roots(chunks, plan, np.asarray(dev_digests),
                               lanes_f)
    leaf_starts: list[int] = []
    leaf_lengths: list[int] = []
    spans: list[tuple[int, int]] = []  # (first leaf index, count) per chunk
    for start, length in chunks:
        first = len(leaf_starts)
        n = blobid.leaf_count(length)
        for k in range(n):
            off = k * blobid.LEAF_SIZE
            leaf_starts.append(start + off)
            leaf_lengths.append(min(blobid.LEAF_SIZE, length - off))
        spans.append((first, n))
    leaves = device_leaf_digests(dev, leaf_starts, leaf_lengths)
    return [
        blobid.root_from_leaves(length, leaves[first : first + n])
        for (first, n), (_, length) in zip(spans, chunks)
    ]


def _upload_padded(buffer):
    """Host bytes/array -> device array padded to a bucketed length.
    Already-bucketed inputs (the staging buffers callers preallocate)
    upload without any host-side pad copy."""
    import jax.numpy as jnp

    if isinstance(buffer, (bytes, bytearray, memoryview)):
        buffer = np.frombuffer(buffer, dtype=np.uint8)
    length = int(buffer.shape[0])
    padded = _buffer_bucket(max(length, 1))
    if padded != length:
        record_copy("device.pad", length)
        buffer = np.pad(buffer, (0, padded - length))
    return jnp.asarray(buffer)


def _spans_page_disjoint(spans: list[tuple[int, int]]) -> bool:
    """True iff every span starts on the 4 KiB page grid and no two
    spans touch the same page — the precondition for the shared
    page-digest table in ops/segment.span_roots_device (its per-span
    tail override mutates that table in place). Zero-length spans touch
    no pages (they're hashed host-side)."""
    last_page = -1
    for s, l in sorted(spans):
        if s % blobid.LEAF_SIZE != 0:
            return False
        if l <= 0:
            continue
        if s // blobid.LEAF_SIZE <= last_page:
            return False
        last_page = (s + l - 1) // blobid.LEAF_SIZE
    return True


def hash_spans(buffer, spans: list[tuple[int, int]]) -> list[str]:
    """Device-batched blob ids for (start, length) spans of one buffer.

    The checksum-compare primitive for the rclone-style mover (the
    reference's `rclone sync --checksum`, mover-rclone/active.sh:19).
    When every span start is 4 KiB-aligned (the mover's packer pads to
    the page grid), this is ONE fused dispatch + ONE [N, 8] fetch:
    all full leaves are pages of the buffer (contiguous hashing, no
    gather) and only each span's short tail goes through the tail
    stage (ops/segment.span_roots_device). Unaligned spans fall back to the
    generic per-leaf gather batch.
    """
    if not spans:
        return []
    if _spans_page_disjoint(spans):
        import jax.numpy as jnp

        from volsync_tpu.ops.segment import span_roots_device

        n_cap = _pow2ceil(len(spans), 128)
        starts = np.full((n_cap,), 0, np.int32)
        lengths = np.full((n_cap,), -1, np.int32)  # padding lanes
        starts[: len(spans)] = [s for s, _ in spans]
        lengths[: len(spans)] = [l for _, l in spans]
        # Zero-length spans consume no pages, so their device tail
        # override would collide with whatever span owns that page —
        # their id is a constant anyway.
        empty = lengths[: len(spans)] == 0
        lengths[: len(spans)][empty] = -1
        with span("verify.launch",
                  bucket=_buffer_bucket(max(len(buffer), 1)), lanes=n_cap):
            out = span_roots_device(
                _upload_padded(buffer), jnp.asarray(starts),
                jnp.asarray(lengths))
        with span("verify.fetch"):
            roots = np.asarray(out).astype(">u4")  # lint: ignore[VL501] one batched 32 B/span root download — metadata, not payload
        empty_id = blobid.blob_id(b"")
        return [empty_id if empty[i]
                else roots[i].tobytes().hex()  # lint: ignore[VL106] 32 B span-root ids, metadata not payload
                for i in range(len(spans))]
    return device_span_roots(_upload_padded(buffer), spans)


def _open_readahead(path, segment_size: int):
    """Open ``path`` through the native double-buffered readahead
    (native/volio.cpp) when available — disk IO for segment N+1
    overlaps the device hashing of segment N — else plain open()."""
    try:
        from volsync_tpu.io import ReadaheadReader, available

        if available():
            return ReadaheadReader(path, segment_size)
    except Exception as ex:  # noqa: BLE001 — native is optional
        log.debug("native readahead unavailable for %s, using plain "
                  "open(): %s", path, ex)
    return open(path, "rb")


def stage_page_aligned(lengths: list, fill, *,
                       filling=contextlib.nullcontext):
    """The one stager of a ``hash_spans`` batch: item ``i`` of
    ``lengths[i]`` bytes gets the next page-aligned slot of ONE zeroed
    bucket-sized buffer (``_buffer_bucket``: ``hash_spans`` then uploads
    it with no host-side pad) and ``fill(i, slot)`` puts its bytes
    there, once: a copy for a blob in hand (``verify_blob_batch``), the
    file's read itself for a file (``movers/rclone/sync.hash_files``,
    whose ``filling()`` puts a span of its own around those reads,
    inside ``verify.stage``). Returns (buffer, [(start, length)]).
    Records span ``verify.stage``, ledger site ``verify.stage`` (the
    valid bytes, what the device is credited with) and
    ``verify.bytes_valid`` / ``verify.bytes_padded``."""
    spans = []
    off = payload = 0
    for n in lengths:
        spans.append((off, n))
        payload += n
        off += n + (-n % blobid.LEAF_SIZE)
    with span("verify.stage"):
        staging = np.zeros((_buffer_bucket(max(off, 1)),), np.uint8)
        with filling():
            for i, (start, n) in enumerate(spans):
                if n:
                    fill(i, staging[start: start + n])
    record_copy("verify.stage", payload)
    count("verify.bytes_valid", payload)
    count("verify.bytes_padded", len(staging) - payload)
    return staging, spans


def verify_blob_batch(pairs: list) -> list:
    """Device-batch blob-id verification: ``pairs`` is
    [(expected-id-hex, plaintext bytes)]; returns the ids whose content
    re-derives to something else. One fused dispatch per call (blobs
    pack page-aligned through ``stage_page_aligned`` — hash_spans' fast
    path); decrypt/decompress stay with the caller, only the per-byte
    hashing rides the device.
    Shared by Repository.check's device path and TreeRestore."""
    if not pairs:
        return []

    def copy_blob(i, slot):
        slot[:] = np.frombuffer(pairs[i][1], np.uint8, count=len(slot))

    staging, spans = stage_page_aligned(
        [len(data) for _, data in pairs], copy_blob)
    got = hash_spans(staging, spans)
    return [bid for (bid, _), d in zip(pairs, got) if d != bid]


def hash_file_streaming(path, *, segment_size: int = 32 * 1024 * 1024) -> str:
    """Blob id of an arbitrarily large file with bounded memory: leaf
    digests are computed on device one ~32 MiB segment at a time and the
    root combines host-side (repo/blobid.py).

    Every leaf of a whole-file stream is a PAGE of its segment
    (segment_size % 4 KiB == 0), so the device hashes pages contiguously
    (ops/segment._page_digests_flat — no gather) and only the file's
    final partial leaf is hashed host-side from bytes already in hand.
    One digest fetch per segment, 32 bytes per 4 KiB; reads go through
    the native readahead so disk IO hides behind device time."""
    import hashlib

    from volsync_tpu.ops.segment import page_digests

    assert segment_size % blobid.LEAF_SIZE == 0
    leaves: list[bytes] = []
    total = 0
    # One reused pooled segment buffer for the whole file: readinto()
    # fills it in place (zero host copies for plain file readers);
    # read()-only sources pay the single sanctioned ingest copy into it.
    buf = bufpool.GLOBAL.acquire(segment_size)
    try:
        view = memoryview(buf)
        arr = np.frombuffer(buf, np.uint8)
        with _open_readahead(path, segment_size) as f:
            readinto = getattr(f, "readinto", None)
            while True:
                n = 0
                while n < segment_size:
                    if readinto is not None:
                        got = readinto(view[n:segment_size])
                        got = 0 if got is None else int(got)
                        if got == 0:
                            break
                    else:
                        piece = f.read(segment_size - n)
                        got = len(piece)
                        if got == 0:
                            break
                        view[n: n + got] = piece
                        record_copy("chunker.ingest", got)
                    n += got
                if n == 0:
                    break
                total += n
                full = n // blobid.LEAF_SIZE
                if full:
                    dev = _upload_padded(arr[: full * blobid.LEAF_SIZE])
                    dig = page_digests(dev)[:full].astype(">u4")
                    leaves.extend(
                        dig[k].tobytes()  # lint: ignore[VL106] 32 B leaf digest rows, metadata not payload
                        for k in range(full))
                if n % blobid.LEAF_SIZE:
                    leaves.append(hashlib.sha256(
                        view[full * blobid.LEAF_SIZE: n]).digest())
                if n < segment_size:
                    break  # EOF landed mid-segment
    finally:
        view.release()
        del arr
        bufpool.GLOBAL.release(buf)
    if total == 0:
        return blobid.blob_id(b"")
    return blobid.root_from_leaves(total, leaves)


def _resolve_reader(reader):
    """(read_fn, readinto_fn) for a stream source. ``reader`` is the
    classic ``reader(n) -> bytes`` callable; when it is a bound
    ``read`` method of an object that also exposes ``readinto`` (plain
    files, io.BytesIO, io.ReadaheadReader), segment fills go straight
    into the pooled buffer — zero host copies on ingest."""
    readinto = getattr(reader, "readinto", None)
    if readinto is None:
        readinto = getattr(getattr(reader, "__self__", None),
                           "readinto", None)
    read = getattr(reader, "read", None) or reader
    return read, readinto


class _SegmentFill:
    """Fills pooled segment buffers for stream_chunk_batches.

    Buffer layout: ``[0, head)`` is reserved for the previous segment's
    carried tail (head == max_size bounds it — a non-eof device walk
    always leaves less than max_size unconsumed); new stream bytes fill
    ``[head, head + target)`` where target == segment_size + max_size,
    the same per-dispatch window the pre-pool implementation
    accumulated. ``readinto()`` sources fill the buffer in place; plain
    ``read()`` sources pay one sanctioned ``chunker.ingest`` copy. The
    extra page-bucket slack past the fill window lets the consumer hand
    the device a pre-padded view with no np.pad copy.

    ``size_hint`` is what the source said it holds (a file's ``lstat``).
    A stream that holds exactly that and ends on a full fill window is
    closed WITH that fill: one read past it finds the end, so its tail
    is not a segment of its own (a device round trip, and a program of
    whatever small shape the tail has). A source that holds more than it
    said goes on as without the hint."""

    def __init__(self, reader: Callable[[int], bytes], piece_size: int,
                 max_size: int, bucket=_buffer_bucket,
                 size_hint: Optional[int] = None):
        self._read, self._readinto = _resolve_reader(reader)
        self._piece = piece_size
        self.head = max_size
        self.target = piece_size + max_size
        self.bucket = bucket  # the hasher's pad target of a length
        # head + fill window + bucket slack for the device pad lane
        # (bucket(tail + fill) never reaches past this).
        self.capacity = max_size + bucket(self.target + max_size)
        self._eof = False
        self._carry: Optional[memoryview] = None  # over-returned piece
        self._left = size_hint  # of what the source said it holds

    def _at_hinted_end(self) -> bool:
        """One read past the hinted size: nothing there is the end; a
        byte there is carried into the next fill."""
        if self._readinto is not None:
            one = bytearray(1)
            ahead = memoryview(one)[:self._readinto(memoryview(one)) or 0]
        else:
            ahead = memoryview(self._read(1))
        if len(ahead):
            self._carry = ahead
        return not len(ahead)

    def next_segment(self) -> tuple[bytearray, int, bool]:
        """-> (pooled buffer, fill end, eof). Data lives in
        ``[head, fill)``; at most one more segment follows eof=True."""
        buf = bufpool.GLOBAL.acquire(self.capacity)
        try:
            view = memoryview(buf)
            fill = self.head
            limit = self.head + self.target
            while not self._eof and fill < limit:
                if self._carry is not None:
                    take = min(len(self._carry), limit - fill)
                    view[fill: fill + take] = self._carry[:take]
                    record_copy("chunker.ingest", take)
                    self._carry = (self._carry[take:]
                                   if take < len(self._carry) else None)
                    fill += take
                    continue
                want = min(self._piece, limit - fill)
                with span("engine.read"):
                    if self._readinto is not None:
                        got = self._readinto(view[fill: fill + want])
                        got = 0 if got is None else int(got)
                        if got == 0:
                            self._eof = True
                        fill += got
                    else:
                        piece = self._read(want)
                        if not piece:
                            self._eof = True
                        else:
                            p = memoryview(piece)
                            take = min(len(p), limit - fill)
                            view[fill: fill + take] = p[:take]
                            record_copy("chunker.ingest", take)
                            if take < len(p):  # reader over-returned
                                self._carry = p[take:]
                            fill += take
            if self._left is not None:
                self._left -= fill - self.head
                if (self._left == 0 and fill == limit and not self._eof
                        and self._carry is None):
                    self._eof = self._at_hinted_end()
        except BaseException:
            # ownership only transfers to the caller on success — give
            # the slot back to the pool before propagating
            view.release()
            bufpool.GLOBAL.release(buf)
            raise
        view.release()
        return buf, fill, self._eof


class _SegmentReadahead:
    """Read-ahead stage of the backup pipeline: a producer thread runs
    _SegmentFill ahead of the consumer so the next segment's host read
    overlaps the current segment's device round-trip. Complements the
    native double-buffer (_open_readahead), which only covers file
    readers — this wraps ANY reader source. Fill exceptions propagate
    to the consumer; ``close()`` (or consumer GC) stops the thread."""

    def __init__(self, fill: _SegmentFill, depth: int):
        from volsync_tpu.metrics import GLOBAL as _METRICS

        self.head = fill.head
        self._fill = fill
        self._q: queue.Queue = queue.Queue(maxsize=max(1, depth))
        self._stop = threading.Event()
        self._gauge = _METRICS.pipeline_depth.labels(stage="read")
        # the consumer's trace context, handed across the thread seam
        # so engine.read spans attribute to the request being served
        from volsync_tpu.obs import current_context
        self._trace_ctx = current_context()
        self._thread = threading.Thread(
            target=self._produce, daemon=True, name="vtpk-readahead")
        self._thread.start()

    def _produce(self):
        with use_context(self._trace_ctx):
            self._produce_loop()

    def _produce_loop(self):
        try:
            while not self._stop.is_set():
                item = self._fill.next_segment()
                done = item[2]
                while not self._stop.is_set():
                    try:
                        self._q.put(item, timeout=0.1)
                        break
                    except queue.Full:
                        continue  # poll stop: a closed consumer must
                        # not leave this thread blocked forever
                self._gauge.set(self._q.qsize())
                if done:
                    return
        except Exception as ex:  # noqa: BLE001 — re-raised by consumer
            while not self._stop.is_set():
                try:
                    self._q.put(ex, timeout=0.1)
                    return
                except queue.Full:
                    continue

    def next_segment(self) -> tuple[bytearray, int, bool]:
        # the consumer's side of engine.read (the producer thread's)
        with span("engine.read_wait"):
            item = self._q.get()
        self._gauge.set(self._q.qsize())
        if isinstance(item, Exception):
            raise item
        return item

    def close(self):
        self._stop.set()
        self._thread.join(timeout=5.0)
        # Hand buffers the consumer never saw back to the pool.
        while True:
            try:
                item = self._q.get_nowait()
            except queue.Empty:
                return
            if not isinstance(item, Exception):
                bufpool.GLOBAL.release(item[0])


class _SegmentInline:
    """The fill of a stream that said it fits one segment, run where it
    is consumed: there is no segment N+1 whose read a thread could
    overlap with the device's work on segment N, so no thread and no
    queue. ``engine.read_wait`` stays the consumer's wait for its
    segment, which is now the read itself: ``engine.read`` inside it,
    on the same thread, and off the ring, where the wait around it
    already names the gap. A source that holds more than it said is
    read on, serially."""

    def __init__(self, fill: _SegmentFill):
        self.head = fill.head
        self._fill = fill

    def next_segment(self) -> tuple[bytearray, int, bool]:
        with span("engine.read_wait"), use_context(off_ring()):
            return self._fill.next_segment()


#: what a stream fills a segment at unless its caller or its hasher
#: says otherwise (``_segment_source``)
_STREAM_SEGMENT = 32 * 1024 * 1024


def stream_fill_bytes(params: GearParams, hasher) -> int:
    """The most bytes one segment of a default stream over ``hasher``
    takes in: a source of up to that many is one fill, one dispatch."""
    return _segment_source(None, params, _STREAM_SEGMENT, hasher).target


def _segment_source(reader, params: GearParams, segment_size: int,
                    hasher, size_hint: Optional[int] = None) -> _SegmentFill:
    """The fill of a stream over ``hasher``. A hasher that shards a
    segment over several chips says how large a segment it wants
    (``stream_segment_size``: every chip gets what one chip is
    dispatched) and how it pads one (``buffer_bucket``); the one-chip
    engine has neither and gets ``segment_size`` and ``_buffer_bucket``.
    A warm plan asks this for the same fill the stream will use."""
    scale = getattr(hasher, "stream_segment_size", None)
    if scale is not None:
        segment_size = scale(segment_size)
    return _SegmentFill(reader, segment_size, params.max_size,
                        getattr(hasher, "buffer_bucket", _buffer_bucket),
                        size_hint)


def stream_chunk_batches(reader: Callable[[int], bytes],
                         params: GearParams,
                         segment_size: int = _STREAM_SEGMENT,
                         hasher: Optional[DeviceChunkHasher] = None,
                         readahead: Optional[int] = None,
                         size_hint: Optional[int] = None,
                         ) -> Iterator[list[tuple[memoryview, str]]]:
    """Chunk an arbitrary-length stream -> per-segment batches of
    (chunk payload, sha256 hex).

    Each yielded list is one device segment's full cut list — the
    natural unit for the repository's batched dedup query
    (``Repository.add_blobs``): the device already hashes a whole
    segment per dispatch, so its chunks arrive together anyway.
    Flattening the batches reproduces ``stream_chunks`` exactly (same
    chunks, same digests, same order).

    Chunk payloads are zero-copy ``memoryview`` slices of pooled
    segment buffers (engine/bufpool.py) that the stream fills with
    ``readinto()`` when the reader supports it; the only per-segment
    host copy left on this path is the sub-max_size tail carried
    between segments (ledger site ``chunker.tail_carry``). Consumers
    may hold the views as long as they like — a pooled buffer is never
    recycled while any view of it is alive.

    ``reader(n)`` returns up to n bytes, b"" at EOF (a bound file
    ``read`` additionally unlocks the readinto fill). Segments are
    chunked on device; the unterminated tail of each segment is carried
    into the next so boundaries match one-shot chunking.

    On the fused path (align == 4096, the repo default) each segment is
    one device dispatch and one small result fetch; the buffer can only
    advance once that fetch lands, so segments of one stream serialize
    on a single round-trip each (sub-ms on a TPU VM). Aggregate
    throughput scales across concurrent streams — one per
    ReplicationSource, mirroring the reference's
    MaxConcurrentReconciles=100 concurrency model — and with the
    segment size. 64 <= align < 4096 keeps the split-phase pipeline
    (synchronous boundary walk, leaf digests in flight across loop
    iterations); align=1 the legacy synchronous path.

    ``readahead`` (default: env VOLSYNC_TPU_READAHEAD, 0 under
    VOLSYNC_TPU_PIPELINE=0) runs the segment fill that many buffers
    ahead on a producer thread so host reads overlap device work — the
    read-ahead stage of the backup pipeline. Chunk boundaries and
    digests are identical either way. With 0 and a ``size_hint`` of at
    most one fill (``TreeBackup``'s files that fit one segment) the one
    read is the consumer's ``engine.read_wait`` (``_SegmentInline``).

    ``size_hint`` (a file's size as the walk saw it) lets a stream that
    ends exactly on a segment's fill end with that segment
    (``_SegmentFill``); the chunks are the same with or without it.
    """
    hasher = hasher or DeviceChunkHasher(params)
    if readahead is None:
        readahead = envflags.readahead_segments()
    src = _segment_source(reader, params, segment_size, hasher, size_hint)
    bucket = src.bucket
    ra: Optional[_SegmentReadahead] = None
    if readahead > 0:
        ra = src = _SegmentReadahead(src, readahead)
    elif size_hint is not None and size_hint <= src.target:
        src = _SegmentInline(src)
    head = src.head
    begin = getattr(hasher, "begin", None)
    producer = getattr(hasher, "producer", contextlib.nullcontext)

    def _dispatch(buf, start, fill, eof):
        length = fill - start
        with span("engine.device"):
            if length == 0:
                return PendingSegment([], None, None)
            arr = np.frombuffer(buf, np.uint8)
            if begin is not None:
                # Hand the device a view already padded to its bucket:
                # zero the pad lane in place (a memset over recycled
                # buffer slack, not a payload copy) — no np.pad.
                plen = bucket(length)
                arr[fill: start + plen] = 0
                return begin(arr[start: start + plen], eof=eof,
                             valid_len=length)
            # a test fake: process() alone, on the exact view
            return PendingSegment(
                hasher.process(arr[start:fill], eof=eof), None, None)

    def _finish(prev):
        buf, start, token = prev
        with span("engine.device"):
            cuts = list(token.finish())
        if cuts:
            base = memoryview(buf).toreadonly()
            return [(base[start + s: start + s + length], digest)
                    for s, length, digest in cuts]
        return None

    registered = contextlib.ExitStack()
    try:
        # one of the batcher's producers for the life of the stream,
        # whichever way it ends (exhausted, closed early, an exception)
        registered.enter_context(producer())
        tail: Optional[memoryview] = None  # lives in prev's buffer
        prev = None  # (buf, start, token)
        while True:
            buf, fill, eof = src.next_segment()
            t = len(tail) if tail is not None else 0
            start = head - t
            if t:
                # The one inter-segment copy: the unterminated tail
                # (< max_size) moves into the next buffer's reserve.
                memoryview(buf)[start:head] = tail
                record_copy("chunker.tail_carry", t)
            tail = None
            token = _dispatch(buf, start, fill, eof)
            with span("engine.device"):
                # a fused segment that is still in flight (the mesh,
                # the one-chip engine without the batcher) learns its
                # cuts from its one fetch: the stream waits here
                consumed = token.end
            tail = memoryview(buf)[start + consumed: fill]
            if len(tail) == 0:
                tail = None
            if prev is not None:
                batch = _finish(prev)
                if batch:
                    yield batch
                bufpool.GLOBAL.release(prev[0])
            prev = (buf, start, token)
            if eof:
                batch = _finish(prev)
                if batch:
                    yield batch
                bufpool.GLOBAL.release(buf)
                return
            # A non-eof pass over more than max_size bytes always emits
            # at least one chunk (max_size forces a cut), so progress is
            # guaranteed; assert to fail loudly rather than loop forever.
            assert consumed > 0, "chunker made no progress"
    finally:
        registered.close()
        if ra is not None:
            ra.close()


def stream_chunks(reader: Callable[[int], bytes], params: GearParams,
                  segment_size: int = _STREAM_SEGMENT,
                  hasher: Optional[DeviceChunkHasher] = None,
                  readahead: Optional[int] = None,
                  ) -> Iterator[tuple[bytes, str]]:
    """Flattened ``stream_chunk_batches``: chunk a stream ->
    (chunk bytes, sha256 hex), one tuple per chunk. Byte-identical to
    the batched form; callers that can act on a whole segment at once
    (the backup engine's dedup query) should take the batches."""
    for batch in stream_chunk_batches(reader, params,
                                      segment_size=segment_size,
                                      hasher=hasher, readahead=readahead):
        yield from batch
