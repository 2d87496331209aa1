"""Cross-stream segment microbatching (shared by service + local engine).

Concurrent producers — gRPC ChunkHash handlers (service/server.py) or
the backups of one process (engine/backup.py) — submit segments
that coalesce into ONE batched device dispatch
(ops/segment.chunk_hash_segments): the service/engine-side form of
BASELINE configs[5]'s cross-PVC batching.

A batch that cannot grow is not made to wait. A *blocking* producer — a
stream of ``engine/chunker.stream_chunk_batches``, which has one segment
out and waits for it — says that it exists (``producer()``), and a
batch goes the moment it holds a segment of every one of them: a lone
stream's at once, two streams' as the second arrives. ``window_ms`` is
the longest a segment waits for a producer that is registered and
absent (between two files, or gone without saying so), or for anybody
on a batcher nobody registered with (the service's, fed through
``submit_async`` with many segments out at once, where a count of
producers says nothing); a busy pipeline pays it never (the queue is
already non-empty when the worker looks).
"""

from __future__ import annotations

import contextlib
import queue
import threading
from concurrent.futures import Future
from concurrent.futures import wait as futures_wait
from typing import Optional

from volsync_tpu import envflags
from volsync_tpu.analysis import lockcheck
from volsync_tpu.obs import (begin_span, count, current_context, span,
                             use_context)
from volsync_tpu.ops.gearcdc import GearParams


class BatcherStopped(RuntimeError):
    """submit() after stop(), or work stranded by shutdown. Typed so
    the service layer can map it to a clean UNAVAILABLE instead of
    pattern-matching a RuntimeError message."""


#: other lanes' trace ids kept on an ``ops.batch_dispatch`` ring event
_LANE_TRACES_KEPT = 8


def _dispatch_context(batch):
    """(context, attrs) for one batch's ``ops.batch_dispatch``: the
    first SAMPLED lane's context, so that the dispatch and its stages
    enter the flight recorder beside a request that waited for them
    (and are booked to that lane's tenant, where it has one), with the
    other sampled lanes' trace ids (bounded) as an attribute.
    (None, {}) when no lane is sampled — the span is then context-free
    and costs what it did."""
    sampled = [it.ctx for it in batch
               if it.ctx is not None and it.ctx.sampled]
    if not sampled:
        return None, {}
    ctx = sampled[0]
    others = sorted({c.trace_id for c in sampled[1:]} - {ctx.trace_id})
    attrs = ({"lane_traces": others[:_LANE_TRACES_KEPT]} if others else {})
    return ctx, attrs


class _Item:
    """One submitted segment: its lane, the caller's future, the
    caller's trace context, the open ``ops.queue_wait`` span (submit
    to the dispatch thread taking the batch: the collector's wait for
    companions, slot wait, hand-over) and whether its caller waits for
    it (``submit``: the way a registered producer comes)."""

    __slots__ = ("data", "length", "eof", "future", "ctx", "qspan",
                 "blocking")

    def __init__(self, data, length, eof, blocking):
        self.data, self.length, self.eof = data, length, eof
        self.blocking = blocking
        self.future: Future = Future()
        self.ctx = current_context()
        self.qspan = begin_span("ops.queue_wait", ctx=self.ctx)

    def fail(self, exc: BaseException) -> None:
        self.qspan.finish("error")
        if not self.future.done():
            self.future.set_exception(exc)


class SegmentMicroBatcher:
    """Queue + worker thread: the first item waits for companions until
    the batch is full (``max_batch``), holds a segment of every
    registered producer (``producer()``: at once for a lone stream), or
    ``window_ms`` has passed; the batch dispatches via
    BatchedSegmentHasher, and each caller's future resolves with its
    lane. ``stop()`` drains the queue — a future enqueued before stop
    is always resolved, never stranded."""

    def __init__(self, params: GearParams, *, max_batch: int = 16,
                 window_ms: float = 2.0, pipeline_depth: int = 2,
                 stage_limit: Optional[int] = None):
        from volsync_tpu.ops.segment import BatchedSegmentHasher

        # ``stage_limit``: the most bytes one coalesced dispatch stages
        # (BatchedSegmentHasher); None, as the engine's shared batcher
        # has it, coalesces whatever the window collected
        self._hasher = BatchedSegmentHasher(params, stage_limit)
        self._q: queue.Queue = queue.Queue()
        self._max_batch = max_batch
        self._window = window_ms / 1000.0
        # Up to ``pipeline_depth`` batches in flight: while one dispatch
        # waits out the device round trip, the collector assembles and
        # launches the next (the overlap's worth is not measured on the
        # current machine). The semaphore bounds in-flight batches so
        # producer backpressure (blocking submit) still holds. Depth 1
        # restores strict one-at-a-time dispatch.
        #
        # Dispatchers are hand-rolled DAEMON threads, not a
        # ThreadPoolExecutor: the executor's non-daemon workers register
        # an interpreter-exit join, so a shared_batcher (never stopped)
        # with a dispatch stuck in a device call would hang process
        # exit. Daemon threads preserve "the process can always exit".
        self._depth = max(1, pipeline_depth)
        self._inflight = threading.BoundedSemaphore(self._depth)
        self._dq: queue.Queue = queue.Queue()
        self._dispatchers = [
            threading.Thread(target=self._dispatch_loop, daemon=True,
                             name=f"segment-batch-{i}")
            for i in range(self._depth)]
        for t in self._dispatchers:
            t.start()
        # blocking producers that said they exist (producer()); the
        # collector's rule reads it, correctness never rests on it
        self._producers = 0
        self._producers_lock = lockcheck.make_lock("batcher.producers")
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="segment-microbatcher")
        self._thread.start()

    @contextlib.contextmanager
    def producer(self):
        """For as long as it is held, a blocking producer exists: one
        that has at most one segment out (``submit``) and waits for it.
        The collector stops waiting once it holds a segment of every
        such producer. A registration that outlives its producer costs
        the others the window, as before it existed; one that is
        missing costs a companion; every caller gets its own lane's
        result either way."""
        with self._producers_lock:
            self._producers += 1
        try:
            yield
        finally:
            with self._producers_lock:
                self._producers -= 1
                left = self._producers
            if left:
                # whoever waits for this producer need not any more
                self._q.put(None)

    def _complete(self, batch) -> bool:
        """The batch holds a segment of every registered producer and
        nothing else: nobody is left who could add to it."""
        with self._producers_lock:
            registered = self._producers
        return (0 < registered <= len(batch)
                and all(item.blocking for item in batch))

    def submit(self, data: bytes, length: int, eof: bool):
        """Blocking: returns (chunks, consumed) for this segment."""
        return self.wait(self._enqueue(data, length, eof, True))

    def wait(self, fut: Future):
        """Result of a future this batcher resolves. There is no
        wall-clock bound: the first dispatch of each (S, P) bucket
        compiles its program, which on a v5e takes from tens of seconds
        to minutes (ROADMAP Speed 4), and a slow compile is not a
        failed backup. The liveness bound is the worker threads
        themselves — they resolve every queued future, including at
        shutdown, so a producer can only be stranded if they died."""
        while not futures_wait([fut], timeout=5.0).done:
            if not self._workers_alive():
                raise BatcherStopped("microbatcher worker threads died")
        return fut.result()

    def _workers_alive(self) -> bool:
        # the collector exits on stop() only after draining the queue
        return ((self._thread.is_alive() or self._stop.is_set())
                and all(t.is_alive() for t in self._dispatchers))

    def submit_async(self, data: bytes, length: int, eof: bool) -> Future:
        """Non-blocking enqueue: the future resolves with
        (chunks, consumed) for this segment. The service scheduler
        (service/scheduler.py) feeds the batcher through this so its
        deficit-round-robin thread never blocks on a device round
        trip.

        ``data`` must not change until the future resolves: a lane that
        is alone in its dispatch and already bucket-shaped is handed to
        the device as it is (ops/segment.py _hash_bucket), and the
        transfer reads the caller's memory. The result is fetched
        before the future resolves, so the transfer has ended by
        then; the batcher lets go of ``data`` as it resolves."""
        return self._enqueue(data, length, eof, False)

    def _enqueue(self, data, length, eof, blocking: bool) -> Future:
        if self._stop.is_set():
            raise BatcherStopped("microbatcher stopped")
        item = _Item(data, length, eof, blocking)
        self._q.put(item)
        return item.future

    def _run(self):
        import time as time_mod

        while True:
            try:
                first = self._q.get(timeout=0.2)
            except queue.Empty:
                if self._stop.is_set():
                    return
                continue
            if first is None:  # a producer left, nobody was waiting
                continue
            batch = [first]
            deadline = time_mod.monotonic() + self._window
            while True:
                complete = self._complete(batch)
                if complete or len(batch) >= self._max_batch:
                    break
                remaining = deadline - time_mod.monotonic()
                if remaining <= 0:
                    break
                try:
                    item = self._q.get(timeout=remaining)
                except queue.Empty:
                    break
                if item is not None:  # else a producer left: look again
                    batch.append(item)
            count("ops.batches")
            if complete:
                count("ops.batches_complete")
            # Interruptible slot wait: if every dispatch slot stays
            # occupied for 30 s AFTER stop() fires (the same bound
            # stop() grants in-flight dispatches — a healthy-but-slow
            # pipeline frees a slot well within it), the pipeline is
            # wedged: fail the in-hand batch instead of blocking
            # forever with popped futures that stop()'s queue drain
            # can no longer reach.
            acquired = stop_deadline = None
            while True:
                if self._inflight.acquire(timeout=0.2):
                    acquired = True
                    break
                if not self._stop.is_set():
                    continue
                now = time_mod.monotonic()
                if stop_deadline is None:
                    stop_deadline = now + 30.0
                elif now >= stop_deadline:
                    break
            if not acquired:
                exc = BatcherStopped("microbatcher stopped")
                for item in batch:
                    item.fail(exc)
                return
            self._dq.put(batch)

    def _dispatch_loop(self):
        while True:
            batch = self._dq.get()
            try:
                for item in batch:
                    item.qspan.finish("ok")
                # One span per coalesced device dispatch. A batch mixes
                # segments from many streams/traces: the span runs under
                # the first sampled lane's context (_dispatch_context);
                # per-stream attribution happens in the scheduler's
                # svc.batch span around each future.
                ctx, attrs = _dispatch_context(batch)
                with use_context(ctx), \
                        span("ops.batch_dispatch", lanes=len(batch), **attrs):
                    results = self._hasher.hash_segments(
                        [(it.data, it.length, it.eof) for it in batch])
                for item, r in zip(batch, results):
                    # this thread holds its batch until the next one
                    # arrives: drop the lane, or a pooled buffer stays
                    # exported (engine/bufpool.py parks it) that long
                    item.data = None
                    item.future.set_result(r)
            except Exception as exc:  # noqa: BLE001 — per-caller delivery
                for item in batch:
                    item.fail(exc)
            finally:
                self._inflight.release()

    def stop(self):
        """Stop accepting work, then let the collector DRAIN the queue:
        it exits only via the empty-queue check, so a future enqueued
        before stop() is always resolved, never stranded. In-flight
        dispatches run on daemon threads — wait (bounded) for them to
        resolve their futures; a dispatch wedged past the bound can
        never block process exit."""
        self._stop.set()
        self._thread.join(timeout=30.0)
        # Drain the in-flight window by taking every slot (bounded wait).
        got = 0
        deadline = 30.0
        import time as time_mod
        t_end = time_mod.monotonic() + deadline
        for _ in range(self._depth):
            if self._inflight.acquire(
                    timeout=max(0.0, t_end - time_mod.monotonic())):
                got += 1
        for _ in range(got):
            self._inflight.release()
        # Belt-and-braces: if the collector died abnormally, fail
        # leftovers still queued.
        while True:
            try:
                item = self._q.get_nowait()
            except queue.Empty:
                break
            if item is not None:
                item.fail(BatcherStopped("microbatcher stopped"))


_SHARED: dict = {}
_SHARED_LOCK = lockcheck.make_lock("batcher.shared")


def _batching_enabled() -> bool:
    """VOLSYNC_BATCH_SEGMENTS: "1" forces on, "0"/"false"/"no" forces
    off. Unset -> backend-aware default: ON on real TPU backends
    (coalescing amortizes the fixed per-dispatch and per-fetch cost;
    neither is measured on the current machine), OFF on the CPU
    backend (compute-bound; batching loses there)."""
    forced = envflags.batch_segments_override()
    if forced is not None:
        return forced
    import jax

    return jax.default_backend() == "tpu"


def shared_batcher(params: GearParams):
    """Process-wide microbatcher per chunker-params (the local engine's
    batching path): TreeBackup workers hashing different files — and
    different CRs' movers in one operator process — coalesce through
    one instance. Returns None when batching is disabled (see
    _batching_enabled: default follows the backend) or the params
    aren't page-aligned."""
    if not _batching_enabled():
        return None
    if params.align != 4096:
        return None
    with _SHARED_LOCK:
        b = _SHARED.get(params)
        if b is None:
            b = _SHARED[params] = SegmentMicroBatcher(
                params,
                max_batch=envflags.batch_max(),
                window_ms=envflags.batch_window_ms(),
                pipeline_depth=envflags.batch_pipeline_depth())
        return b
