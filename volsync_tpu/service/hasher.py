"""The chunk+hash engine of a mover that holds no accelerator: a
``TreeBackup`` hasher that is a client of the mover-jax service.

``VOLSYNC_ENGINE=service`` (``movers/restic/entry.py``) hands
``TreeBackup`` a :class:`RemoteChunkHasher` in place of the in-process
``DeviceChunkHasher``. Every device-path file is ONE ``ChunkHash``
stream (``service/client.py`` ``chunk_batches``): the file's bytes go
out in frames as gRPC pulls them, the service cuts and hashes them on
its chip, and each answered ``ChunkBatch`` comes back as a list of
``(memoryview of the file's own bytes, id)`` for
``Repository.add_blobs``, exactly what ``stream_chunk_batches`` yields
in process. Host-path files, the tree, seal, upload and the index stay
in the mover. Nothing here touches JAX.

The bytes of a file that were sent and are not yet covered by an
answered chunk live in pooled blocks (``engine/bufpool.py``), because
the answer names offsets and the repository wants the bytes. What
bounds them is the service, not the file: the server reads at most
``stream_credits`` full segments of a stream ahead of the one on the
device (``server.py`` ``_serve_stream``), and gRPC holds a window's
worth of frames on the way (:func:`held_bytes_bound`,
``remote.held_bytes_max``).

An answer is held to the file: its chunks cover what was sent from 0
to its end, in order, without gap or overlap, each between ``min_size``
and ``max_size`` but the last (:class:`AnswerRefused` otherwise, and no
snapshot). A shed (sleeping its ``retry_after``), an ``UNAVAILABLE`` or
a stream that ends before its cover is whole sends the file again from
its first byte under ``RetryPolicy.from_env("service.client")``; the
chunks the repository already has are skipped in the replay, held to
what they were. When the policy gives up the backup fails
(:class:`ServiceHashError`) and saves no snapshot.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from typing import Callable, Optional

import grpc

from volsync_tpu.analysis import lockcheck
from volsync_tpu.engine import bufpool
from volsync_tpu.obs import (begin_span, count, count_max, off_ring,
                             record_copy, span)
from volsync_tpu.resilience import (DeadlineExceeded, RetryPolicy,
                                    TransientError)
from volsync_tpu.service.client import _SEND_CHUNK, MoverJaxClient

#: frames a pooled block holds: 15.75 MiB of a file beside the reserve
_BLOCK_FRAMES = 4
#: one stream's time limit: a 128 MiB file behind eleven other movers
#: and a first-use compile, not a unary call's 60 s
_STREAM_TIMEOUT = 600.0


class ServiceHashError(RuntimeError):
    """The service could not hash a file and the policy gave up (or the
    failure is not one a replay cures): the backup fails, no snapshot."""


class AnswerRefused(ServiceHashError):
    """The service's answer does not describe the bytes it was sent."""


class StreamEndedEarly(TransientError):
    """The stream ended before its chunks covered the file."""


def held_bytes_bound(segment_size: int, max_size: int, credits: int,
                     channel_frames: int = 8) -> int:
    """The most bytes of one file a mover holds sent and uncovered: the
    server buffers ``credits`` full segments (``segment_size`` +
    ``max_size`` each), and ``channel_frames`` frames are on the way:
    gRPC's flow-control window, the messages either side has queued and
    the frame the server was handed as it ran out of credit (5 to 6
    frames measured over loopback; PERF.md section 6, PR 48)."""
    return (credits * (segment_size + max_size)
            + channel_frames * _SEND_CHUNK)


class _Sent:
    """The bytes of one stream attempt that were handed to gRPC and are
    not covered yet, in pooled blocks.

    A block is ``[0, head)`` reserve + ``[head, head + room)`` file
    bytes, filled front to back by the sending thread. The receiving
    thread slices answered chunks out of them; a chunk that starts in
    one block and ends in the next is made whole by copying its START
    (under ``max_size``, what ``head`` reserves) in front of the next
    block's bytes, the tail carry of ``stream_chunk_batches``. A block
    goes back to the pool when the cover has passed it; the pool parks
    it while a chunk's view is alive."""

    def __init__(self, params):
        self.params = params
        self.head = params.max_size
        self.room = max(_BLOCK_FRAMES * _SEND_CHUNK, params.max_size)
        self._lock = lockcheck.make_lock("service.hasher.sent")
        self._blocks: deque = deque()  # [buf, first file offset, fill]
        self.sent = 0      # file bytes handed to gRPC
        self.covered = 0   # file bytes answered
        self.eof = False   # the sender saw the file's end
        self.error: Optional[BaseException] = None  # the sender's
        self._short = False  # a chunk under min_size has been answered
        self._cur = None  # the block the sender fills
        self._idle = threading.Event()  # the sender is outside a read
        self._idle.set()
        self._closed = False

    # -- the sending thread (gRPC's) ---------------------------------

    def frames(self, reader):
        """The file as frames of at most ``_SEND_CHUNK`` bytes, each
        read into a block and materialized once for protobuf."""
        quiet = off_ring()
        readinto = getattr(reader, "readinto", None)
        try:
            while True:
                h = begin_span("remote.send", ctx=quiet)
                piece = self._read_frame(reader, readinto)
                if piece is None:
                    h.finish("ok")
                    self.eof = not self._closed
                    return
                payload = bytes(piece)  # protobuf wants bytes
                piece.release()
                record_copy("svc.frame", len(payload))
                with self._lock:
                    self.sent += len(payload)
                    held = self.sent - self.covered
                count_max("remote.held_bytes_max", held)
                h.finish("ok")
                yield payload
        except Exception as ex:  # noqa: BLE001 — raised by the receiver
            self.error = ex
            raise

    def _read_frame(self, reader, readinto) -> Optional[memoryview]:
        with self._lock:
            if self._closed:
                return None
            self._idle.clear()
        blk = self._cur  # the sender's own: full before the cover drops it
        try:
            fresh = blk is None or blk[2] == self.head + self.room
            if fresh:
                start = 0 if blk is None else blk[1] + self.room
                blk = self._cur = [
                    bufpool.GLOBAL.acquire(self.head + self.room), start,
                    self.head]
            view = memoryview(blk[0])
            at = fill = blk[2]
            end = min(at + _SEND_CHUNK, self.head + self.room)
            while fill < end:
                if readinto is not None:
                    got = int(readinto(view[fill:end]) or 0)
                else:
                    data = reader.read(end - fill)
                    got = len(data)
                    view[fill: fill + got] = data
                    record_copy("chunker.ingest", got)
                if not got:
                    break
                fill += got
        except BaseException:
            self._idle.set()
            raise
        with self._lock:
            self._idle.set()
            if self._closed or fill == at:
                if fresh and not self._closed:
                    bufpool.GLOBAL.release(blk[0])
                return None
            blk[2] = fill
            if fresh:
                self._blocks.append(blk)
        return view[at:fill]

    # -- the receiving thread (the backup's) -------------------------

    def take(self, chunks) -> list:
        """[(view, digest)] of one answered batch, each chunk checked
        against the cover so far and the bytes sent."""
        p = self.params
        out = []
        with self._lock:
            sent = self.sent  # the service has seen no byte beyond it
        covered = self.covered
        for off, length, digest in chunks:
            if off != covered:
                raise AnswerRefused(
                    f"chunk at {off} where the cover ends at "
                    f"{covered}: a gap or an overlap")
            if not 0 < length <= p.max_size or off + length > sent:
                raise AnswerRefused(
                    f"chunk [{off}, +{length}) of {sent} bytes sent, "
                    f"max_size {p.max_size}")
            if self._short:
                raise AnswerRefused(
                    f"a chunk under min_size {p.min_size} before the "
                    f"chunk at {off}: only the last may be")
            self._short = length < p.min_size
            out.append((self._view(off, length), digest))
            covered = off + length
        with self._lock:
            self.covered = covered
        return out

    def _view(self, off: int, length: int) -> memoryview:
        blocks = self._blocks
        while off >= blocks[0][1] + self.room:
            self._drop_first()
        buf, first, _ = blocks[0]
        at = self.head + off - first
        over = off + length - (first + self.room)
        if over <= 0:
            return memoryview(buf).toreadonly()[at: at + length]
        # the chunk ends in the next block: its start moves in front of
        # that block's bytes (the one copy, under max_size)
        t = length - over
        nxt = blocks[1][0]
        memoryview(nxt)[self.head - t: self.head] = \
            memoryview(buf)[at: at + t]
        record_copy("chunker.tail_carry", t)
        self._drop_first()
        return memoryview(nxt).toreadonly()[self.head - t:
                                            self.head - t + length]

    def _drop_first(self) -> None:
        with self._lock:
            buf = self._blocks.popleft()[0]
        bufpool.GLOBAL.release(buf)

    def close(self) -> None:
        """The attempt is over: the sender reads no more (a broken
        stream may have left it inside a read: that one is waited out,
        so the caller may close the reader after this), and the blocks
        go back to the pool. Should the read not return, they are left
        to the collector: a pooled block is never written by a thread
        that no longer owns it."""
        with self._lock:
            self._closed = True
            blocks, self._blocks = list(self._blocks), deque()
        if self._idle.wait(timeout=30.0):
            for buf, _, _ in blocks:
                bufpool.GLOBAL.release(buf)


@dataclasses.dataclass
class _FileState:
    """What a file's attempts share: the chunks the repository has."""

    attempt: int = 0
    chunks: list = dataclasses.field(default_factory=list)  # (len, id)


def _backoff_sleep(seconds: float) -> None:
    with span("remote.backoff", ctx=off_ring()):
        time.sleep(seconds)


class RemoteChunkHasher:
    """``TreeBackup(hasher=...)`` over a :class:`MoverJaxClient`.
    ``params`` are the chunker parameters the service cuts with, which
    ``TreeBackup`` holds against the repository's."""

    def __init__(self, client: MoverJaxClient, params,
                 policy: Optional[RetryPolicy] = None):
        self.client = client
        self.params = params
        self._policy = policy or RetryPolicy.from_env(
            "service.client", sleep_fn=_backoff_sleep)

    def close(self) -> None:
        self.client.close()

    def hash_file(self, open_reader: Callable, sink: Callable) -> None:
        """Hash one device-path file through the service: ``sink``
        gets each answered batch as ``[(chunk view, id)]``, in order,
        each chunk once whatever was replayed. ``open_reader()`` is a
        context manager over the file from its first byte (one a
        attempt)."""
        state = _FileState()
        try:
            self._policy.call(self._attempt, open_reader, sink, state)
        except ServiceHashError:
            raise
        except (grpc.RpcError, TransientError, DeadlineExceeded) as ex:
            raise ServiceHashError(
                f"the mover-jax service did not hash the file in "
                f"{state.attempt} attempt(s): {ex}") from ex

    def _attempt(self, open_reader, sink, state: _FileState) -> None:
        state.attempt += 1
        if state.attempt > 1:
            count("remote.replays")
        sent = _Sent(self.params)
        handle = begin_span("remote.stream", attempt=state.attempt)
        outcome = "error"
        try:
            with open_reader() as reader:
                answers = self.client.chunk_batches(
                    sent.frames(reader), timeout=_STREAM_TIMEOUT)
                try:
                    chunks = self._receive(answers, sent, sink, state)
                finally:
                    answers.close()
                    sent.close()  # before the reader closes under it
            handle.attrs = {**(handle.attrs or {}), "bytes": sent.covered}
            outcome = "ok"
        finally:
            handle.finish(outcome)
        count("remote.streams")
        count("remote.bytes", sent.covered)
        count("remote.chunks", chunks)

    def _receive(self, answers, sent: _Sent, sink, state: _FileState) -> int:
        """Drain one attempt's answers into ``sink``; returns the
        chunks answered. Raises what ends the attempt."""
        quiet = off_ring()
        final = False
        n = 0
        while True:
            try:
                with span("remote.wait", ctx=quiet):
                    chunks, last = next(answers)
            except StopIteration:
                break
            except grpc.RpcError:
                if sent.error is not None:  # the file's read failed
                    raise sent.error
                raise
            if final:
                raise AnswerRefused("a batch after the final batch")
            final = last
            batch = []
            for view, digest in sent.take(chunks):
                if n < len(state.chunks):
                    # a replay: the repository has this chunk
                    if state.chunks[n] != (len(view), digest):
                        raise AnswerRefused(
                            f"chunk {n} of the replay is not the chunk "
                            f"the first answer gave")
                else:
                    batch.append((view, digest))
                n += 1
            if batch:
                sink(batch)
                state.chunks.extend((len(v), d) for v, d in batch)
        if sent.error is not None:
            raise sent.error
        if not (final and sent.eof and sent.covered == sent.sent):
            raise StreamEndedEarly(
                f"stream ended at {sent.covered} of {sent.sent} bytes "
                f"sent (final batch: {final})")
        return n


def open_hasher(address: str, token: str, tenant: Optional[str],
                want) -> RemoteChunkHasher:
    """The hasher of a mover whose repository cuts with ``want``
    (GearParams), over the service at ``address`` (``host:port``).
    ``Info`` says what the service cuts with; a service that cuts
    otherwise is refused here, before a byte is sent: its snapshot
    would share no boundary with the repository's others."""
    host, _, port = address.rpartition(":")
    client = MoverJaxClient(host, int(port), token, tenant=tenant)
    try:
        info = client.info()
        theirs = dataclasses.replace(
            want, min_size=int(info.min_size), avg_size=int(info.avg_size),
            max_size=int(info.max_size), align=int(info.align))
    except (grpc.RpcError, TransientError, DeadlineExceeded,
            AssertionError) as ex:  # no answer, or no chunker's numbers
        client.close()
        raise ServiceHashError(
            f"the mover-jax service at {address} gave no Info: {ex}") from ex
    if theirs != want:
        client.close()
        raise ServiceHashError(
            f"the mover-jax service at {address} cuts with {theirs}, the "
            f"repository with {want}")
    return RemoteChunkHasher(client, theirs)
