"""Tracing/profiling (SURVEY.md §5 A1 — greenfield: the reference has
only wall-clock echoes in its mover scripts).

Three layers:

- **Spans** — named timers (``span("engine.read")``) recording durations
  into a process-wide registry AND a Prometheus histogram
  (``volsync_stage_duration_seconds{stage,outcome}``) so stage timings
  ride the same /metrics endpoint as the sync metrics. Spans are
  hierarchical when a :class:`TraceContext` is active: each span becomes
  the parent of spans opened inside it, and tenant-tagged contexts also
  feed ``volsync_svc_stage_seconds{tenant,stage}``. Every ``span()``
  also has a **self time**: its duration less the ``span()``s that
  closed inside it on the same thread (:func:`span_self_totals`), so
  what no inner span covers is a number and not a guess.
- **Counters** — ``count("ops.lanes", n)`` beside the spans, for what
  is a quantity and not a time (:func:`counter_totals`); zeroed with
  the spans by :func:`reset_spans`.
- **Flight recorder** — when the active context is sampled
  (``VOLSYNC_TRACE_SAMPLE``), finished spans land in a bounded
  in-process ring buffer exported as Chrome-trace-event JSON
  (Perfetto-loadable) via :func:`dump_trace`, ``volsync trace dump``,
  and the ``/debug/trace`` endpoint. A span that runs once a file or
  a blob stays out of it (``ctx=off_ring()``: totals only) so that the
  ring holds what names a gap. :func:`record_trigger` marks
  shed / breaker-open / injected-fault / deadline events in the ring
  and auto-dumps an annotated trace file when ``VOLSYNC_TRACE_DUMP``
  is set (throttled per reason).

Context propagation: the current :class:`TraceContext` lives in a
``contextvars.ContextVar``. It does NOT cross thread boundaries by
itself — every pipeline seam hands it over explicitly
(:func:`carry_context` for pool submissions, :func:`use_context` when a
consumer thread processes an item that carried its producer's context)
and the gRPC client sends it to the server in ``x-volsync-trace``
metadata (:func:`format_trace_header` / :func:`parse_trace_header`).
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import functools
import json
import logging
import os
import random
import threading
import time
from collections import defaultdict, deque
from typing import Optional

from prometheus_client import Histogram

from volsync_tpu import envflags
from volsync_tpu.analysis import lockcheck
from volsync_tpu.metrics import GLOBAL as GLOBAL_METRICS

log = logging.getLogger(__name__)

_BUCKETS = (0.001, 0.005, 0.02, 0.05, 0.1, 0.25, 0.5, 1, 2, 5, 15, 60,
            float("inf"))

_lock = lockcheck.make_lock("obs.spans")
# name -> [n, secs, self secs]
_totals: dict[str, list] = defaultdict(lambda: [0, 0.0, 0.0])
# (name, outcome) -> [n, secs]; outcome is "ok" or "error"
_outcomes: dict[tuple, list] = defaultdict(lambda: [0, 0.0])
_counters: dict[str, int] = defaultdict(int)
_histogram: Optional[Histogram] = None

# The open span()s of each thread, innermost last: one [child seconds]
# cell a frame. begin_span() handles never enter it (they may end on
# another thread): they are waits, all self time and nobody's child.
_open = threading.local()

# Flight-recorder state. An event is a compact tuple
# (cat, name, t, dur, tid, ctx, span_id, outcome, attrs) with t and dur
# in perf_counter seconds (dur None for an instant); trace_events() and
# chrome_trace() render the Chrome trace event dicts, with timestamps
# in microseconds since this module's perf_counter epoch.
_EPOCH = time.perf_counter()
_PID = os.getpid()
_ring: deque = deque(maxlen=envflags.trace_ring_size())
_thread_names: dict[int, str] = {}
_trigger_last: dict[str, float] = {}  # reason -> perf_counter of last dump
_dump_seq = [0]
#: counter of events the full ring pushed out (0: the ring holds all
#: that was recorded since reset_spans())
RING_DROPPED = "obs.ring_dropped"


# -- trace context --------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TraceContext:
    """Identity of the request a span belongs to. ``span_id`` is the id
    of the *current* (innermost open) span — children record it as
    their parent."""

    trace_id: str
    span_id: str
    tenant: Optional[str] = None
    stream_id: Optional[str] = None
    sampled: bool = True

    def child(self, span_id: str) -> "TraceContext":
        # built directly: every sampled span() makes one, and
        # dataclasses.replace costs three times as much
        return TraceContext(self.trace_id, span_id, self.tenant,
                            self.stream_id, self.sampled)

    def evolve(self, **changes) -> "TraceContext":
        return dataclasses.replace(self, **changes)


_CTX: contextvars.ContextVar[Optional[TraceContext]] = \
    contextvars.ContextVar("volsync_trace_ctx", default=None)
_CURRENT = object()  # sentinel: "use whatever context is active"


# Span and trace ids are labels for correlation, not secrets: a private
# generator seeded from the OS once. os.urandom() a span is a system
# call that gives up the interpreter lock, and on a thread that feeds
# the device in a process whose other threads all want that lock, each
# one can cost a switch interval (5 ms) to get back.
_ids = random.Random()


def new_id() -> str:
    return f"{_ids.getrandbits(64):016x}"


def _sample_decision() -> bool:
    rate = envflags.trace_sample()
    if rate <= 0.0:
        return False
    if rate >= 1.0:
        return True
    return random.random() < rate


def current_context() -> Optional[TraceContext]:
    return _CTX.get()


def new_trace(tenant: Optional[str] = None,
              stream_id: Optional[str] = None,
              sampled: Optional[bool] = None) -> TraceContext:
    """Root context for a new request; the sampling decision is made
    once here and inherited by every span/child of the trace."""
    if sampled is None:
        sampled = _sample_decision()
    return TraceContext(trace_id=new_id(), span_id=new_id(), tenant=tenant,
                        stream_id=stream_id, sampled=sampled)


@contextlib.contextmanager
def trace_context(ctx: Optional[TraceContext] = None, *,
                  tenant: Optional[str] = None,
                  stream_id: Optional[str] = None,
                  sampled: Optional[bool] = None):
    """Activate ``ctx`` (or a fresh root trace) for the enclosed block."""
    if ctx is None:
        ctx = new_trace(tenant=tenant, stream_id=stream_id, sampled=sampled)
    token = _CTX.set(ctx)
    try:
        yield ctx
    finally:
        _CTX.reset(token)


@contextlib.contextmanager
def use_context(ctx: Optional[TraceContext]):
    """Like :func:`trace_context` but a no-op when ``ctx`` is None —
    the consumer-thread side of an explicit context handoff."""
    if ctx is None:
        yield None
        return
    token = _CTX.set(ctx)
    try:
        yield ctx
    finally:
        _CTX.reset(token)


def carry_context(fn, ctx: Optional[TraceContext] = None):
    """Wrap ``fn`` so it runs under the caller's current trace context
    (captured now) even when invoked later on a worker thread — the
    producer side of the thread-pool seam handoff. Returns ``fn``
    unchanged when there is nothing to carry."""
    captured = ctx if ctx is not None else _CTX.get()
    if captured is None:
        return fn

    @functools.wraps(fn)
    def _carried(*args, **kwargs):
        token = _CTX.set(captured)
        try:
            return fn(*args, **kwargs)
        finally:
            _CTX.reset(token)

    return _carried


# -- gRPC metadata wire format (x-volsync-trace) --------------------------

def format_trace_header(ctx: TraceContext) -> str:
    """``trace_id:span_id:stream_id:sampled`` — tenant deliberately
    omitted (the server trusts only its own token-derived tenant)."""
    return (f"{ctx.trace_id}:{ctx.span_id}:{ctx.stream_id or ''}:"
            f"{1 if ctx.sampled else 0}")


def parse_trace_header(value: Optional[str]) -> Optional[TraceContext]:
    """Inverse of :func:`format_trace_header`; None on anything
    malformed (an unparseable header degrades to a fresh root trace,
    never an error)."""
    if not value:
        return None
    parts = value.strip().split(":")
    if len(parts) != 4 or not parts[0] or not parts[1]:
        return None
    return TraceContext(trace_id=parts[0], span_id=parts[1], tenant=None,
                        stream_id=parts[2] or None, sampled=parts[3] != "0")


# -- spans ----------------------------------------------------------------

def _hist() -> Histogram:
    global _histogram
    with _lock:
        if _histogram is None:
            _histogram = Histogram(
                "volsync_stage_duration_seconds",
                "Duration of instrumented data-plane stages",
                ["stage", "outcome"], registry=GLOBAL_METRICS.registry,
                buckets=_BUCKETS)
    return _histogram


# Labeled-child lookup (prometheus_client .labels()) dominates the cost
# of a context-free span, so finish() goes through this cache; cleared
# by reset_spans() alongside the parents it indexes into.
_hist_children: dict = {}


def _hist_child(stage: str, outcome: str):
    child = _hist_children.get((stage, outcome))
    if child is None:
        child = _hist_children[(stage, outcome)] = \
            _hist().labels(stage=stage, outcome=outcome)
    return child


# the same for volsync_svc_stage_seconds{tenant,stage}: every span of a
# served stream finishes under a tenant
_tenant_children: dict = {}


def _tenant_child(tenant: str, stage: str):
    child = _tenant_children.get((tenant, stage))
    if child is None:
        child = _tenant_children[(tenant, stage)] = \
            GLOBAL_METRICS.svc_stage_seconds.labels(tenant=tenant,
                                                    stage=stage)
    return child


class _SpanHandle:
    """An open span. ``finish()`` is idempotent so error paths may
    finish eagerly and a ``finally`` can still call it."""

    __slots__ = ("name", "ctx", "span_id", "t0", "attrs", "_done")

    def __init__(self, name: str, ctx: Optional[TraceContext],
                 attrs: Optional[dict]):
        self.name = name
        self.ctx = ctx
        self.span_id = new_id() if ctx is not None else None
        self.attrs = attrs
        self._done = False
        self.t0 = time.perf_counter()

    def finish(self, outcome: str = "ok", child_seconds: float = 0.0):
        """Record the span; returns its duration (None when already
        finished). ``child_seconds`` is what span() saw close inside
        it on this thread — a handle finished by hand has none."""
        if self._done:
            return None
        self._done = True
        dt = time.perf_counter() - self.t0
        ctx = self.ctx
        event = None
        if ctx is not None and ctx.sampled:
            event = ("span", self.name, self.t0, dt, threading.get_ident(),
                     ctx, self.span_id, outcome, self.attrs)
        with _lock:
            acc = _totals[self.name]
            acc[0] += 1
            acc[1] += dt
            acc[2] += dt - child_seconds
            oacc = _outcomes[(self.name, outcome)]
            oacc[0] += 1
            oacc[1] += dt
            if event is not None:
                _ring_append(event)
        _hist_child(self.name, outcome).observe(dt)
        if ctx is not None and ctx.tenant:
            _tenant_child(ctx.tenant, self.name).inc(dt)
        return dt


def _ring_append(event: tuple) -> None:
    """Caller holds ``_lock``."""
    tid = event[4]
    if tid not in _thread_names:
        _thread_names[tid] = threading.current_thread().name
    if len(_ring) == _ring.maxlen:
        _counters[RING_DROPPED] += 1
    _ring.append(event)


def begin_span(name: str, ctx=_CURRENT, **attrs) -> _SpanHandle:
    """Open a span without a ``with`` block — for spans whose end lives
    on another thread (scheduler dispatch -> batcher done-callback) or
    inside a generator (gRPC stream handlers, where a contextvar set
    across ``yield`` would leak into the consuming thread). Pass
    ``ctx=None`` to force a context-free span, or a TraceContext to
    attribute the span to a request this thread is not running under."""
    if ctx is _CURRENT:
        ctx = _CTX.get()
    return _SpanHandle(name, ctx, attrs or None)


def off_ring() -> Optional[TraceContext]:
    """The active context with its sampling bit off, as the ``ctx`` of
    a span that runs once a file or a blob and lasts well under a
    millisecond: it keeps its totals, its self time and its tenant and
    leaves no event in the flight recorder, where thousands an
    operation would push out what names an idle gap. Spans opened
    inside such a span nest under its parent, on the ring as before."""
    ctx = _CTX.get()
    if ctx is None or not ctx.sampled:
        return ctx
    return TraceContext(ctx.trace_id, ctx.span_id, ctx.tenant,
                        ctx.stream_id, False)


@contextlib.contextmanager
def span(name: str, ctx=_CURRENT, **attrs):
    """Time a named stage; feeds the span registry + the histogram,
    and — when a sampled TraceContext is active — the flight recorder,
    with spans opened inside nesting under this one. Its self time is
    its duration less the span()s that closed inside it on this
    thread. ``ctx`` as for :func:`begin_span`."""
    h = begin_span(name, ctx, **attrs)
    token = None
    if h.ctx is not None and h.ctx.sampled:
        token = _CTX.set(h.ctx.child(h.span_id))
    try:
        stack = _open.stack
    except AttributeError:
        stack = _open.stack = []
    frame = [0.0]
    stack.append(frame)
    outcome = "ok"
    try:
        yield h
    except BaseException:
        outcome = "error"
        raise
    finally:
        stack.pop()
        if token is not None:
            _CTX.reset(token)
        dt = h.finish(outcome, frame[0])
        if stack and dt is not None:
            stack[-1][0] += dt


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to a named counter (literal dotted names, as spans)."""
    with _lock:
        _counters[name] += n


def count_max(name: str, n: int) -> None:
    """Keep the largest ``n`` seen under a counter's name: a gauge's
    high-water (bytes held, a queue's depth) beside the counters, read
    and zeroed with them."""
    with _lock:
        if n > _counters[name]:
            _counters[name] = n


def span_totals(by_outcome: bool = False) -> dict:
    """``{stage: (count, total seconds)}`` — inspection/tests/CLI.
    With ``by_outcome=True``: ``{(stage, outcome): (count, seconds)}``
    so failing stages are distinguishable from succeeding ones."""
    with _lock:
        if by_outcome:
            return {k: (v[0], v[1]) for k, v in _outcomes.items()}
        return {k: (v[0], v[1]) for k, v in _totals.items()}


def span_self_totals() -> dict:
    """``{stage: (count, self seconds)}``: each span's duration less
    the span()s that closed inside it on the same thread. A handle
    from begin_span() is a wait: all of it is self time."""
    with _lock:
        return {k: (v[0], v[2]) for k, v in _totals.items()}


def counter_totals() -> dict:
    """``{name: count}`` since the last reset_spans()."""
    with _lock:
        return dict(_counters)


def reset_spans():
    """Zero the span registry, the counters AND the Prometheus children
    the spans populated (volsync_stage_duration_seconds /
    volsync_svc_stage_seconds) so stage timings cannot bleed across
    tests/bench rounds."""
    with _lock:
        _totals.clear()
        _outcomes.clear()
        _counters.clear()
        _hist_children.clear()
        _tenant_children.clear()
        hist = _histogram
    if hist is not None:
        hist.clear()
    GLOBAL_METRICS.svc_stage_seconds.clear()


# -- flight recorder ------------------------------------------------------

def trace_instant(name: str, **args) -> None:
    """Thread-scoped instant event (Chrome ``ph="i"``) into the flight
    recorder when a SAMPLED trace context is active; no-op otherwise.
    The event lands at the current timestamp on the calling thread, so
    in Perfetto it nests visually under whatever stage span is open —
    the copy ledger uses this to attribute sanctioned host copies to
    the pipeline stage that paid them. Unlike spans these carry no
    Prometheus cost, so they are safe at per-segment frequency."""
    ctx = _CTX.get()
    if ctx is None or not ctx.sampled:
        return
    event = ("copy", name, time.perf_counter(), None, threading.get_ident(),
             ctx, None, None, args)
    with _lock:
        _ring_append(event)


def _chrome_event(event: tuple) -> dict:
    """One ring tuple as a Chrome trace event."""
    cat, name, t, dur, tid, ctx, span_id, outcome, attrs = event
    out = {"name": name, "cat": cat, "ts": (t - _EPOCH) * 1e6,
           "pid": _PID, "tid": tid}
    if cat == "span":
        args = {"trace_id": ctx.trace_id, "span_id": span_id,
                "parent_span_id": ctx.span_id, "outcome": outcome}
        if ctx.tenant:
            args["tenant"] = ctx.tenant
        if ctx.stream_id:
            args["stream_id"] = ctx.stream_id
        if attrs:
            args.update(attrs)
        out.update(ph="X", dur=dur * 1e6, args=args)
    elif cat == "copy":
        out.update(ph="i", s="t",
                   args={**attrs, "trace_id": ctx.trace_id,
                         "parent_span_id": ctx.span_id})
    else:  # trigger
        out.update(ph="i", s="g", args=dict(attrs))
    return out


def trace_events() -> list:
    """Snapshot of the ring buffer (Chrome trace events, oldest first)."""
    with _lock:
        events = list(_ring)
    return [_chrome_event(e) for e in events]


def chrome_trace(trigger: Optional[str] = None,
                 annotations: Optional[dict] = None) -> dict:
    """The ring buffer as a Chrome-trace-event JSON document (load in
    Perfetto / chrome://tracing). ``trigger`` stamps a top-level
    annotation describing why the dump was taken."""
    with _lock:
        events = list(_ring)
        threads = dict(_thread_names)
    meta = [{"name": "thread_name", "ph": "M", "pid": _PID, "tid": tid,
             "args": {"name": tname}}
            for tid, tname in sorted(threads.items())]
    doc = {"traceEvents": meta + [_chrome_event(e) for e in events],
           "displayTimeUnit": "ms"}
    if trigger is not None:
        # "reason" is the trigger's own key; annotations cannot shadow it
        doc["trigger"] = {**(annotations or {}), "reason": trigger}
    return doc


def dump_trace(path: Optional[str] = None, trigger: Optional[str] = None,
               annotations: Optional[dict] = None) -> Optional[str]:
    """Write the flight recorder to ``path`` (or an auto-numbered file
    under ``VOLSYNC_TRACE_DUMP``). Returns the path written, or None
    when no path was given and no dump dir is configured."""
    doc = chrome_trace(trigger=trigger, annotations=annotations)
    if path is None:
        dump_dir = envflags.trace_dump_dir()
        if not dump_dir:
            return None
        with _lock:
            _dump_seq[0] += 1
            seq = _dump_seq[0]
        path = os.path.join(dump_dir,
                            f"trace-{trigger or 'manual'}-{seq:04d}.json")
    parent = os.path.dirname(os.path.abspath(path))
    os.makedirs(parent, exist_ok=True)
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(doc, fh)
    return path


def record_trigger(reason: str, /, **annotations) -> Optional[str]:
    """Mark an operational event (shed, breaker_open, fault, deadline)
    as an instant event in the ring, and — when ``VOLSYNC_TRACE_DUMP``
    is set — auto-dump an annotated trace file, throttled per reason by
    ``VOLSYNC_TRACE_TRIGGER_INTERVAL_S``. Never raises: callers sit on
    error paths (often holding their own locks) and must not gain new
    failure modes from observability."""
    now = time.perf_counter()
    with _lock:
        _ring_append(("trigger", "trigger." + reason, now, None,
                      threading.get_ident(), None, None, None,
                      dict(annotations)))
    if envflags.trace_dump_dir() is None:
        return None
    interval = envflags.trace_trigger_interval()
    with _lock:
        last = _trigger_last.get(reason)
        if last is not None and now - last < interval:
            return None
        _trigger_last[reason] = now
    try:
        return dump_trace(trigger=reason, annotations=dict(annotations))
    except OSError as exc:
        log.warning("flight-recorder dump for trigger %r failed: %s",
                    reason, exc)
        return None


def reset_trace():
    """Clear the flight recorder (ring + thread map + trigger
    throttles); the ring is re-sized from VOLSYNC_TRACE_RING."""
    global _ring
    with _lock:
        _ring = deque(maxlen=envflags.trace_ring_size())
        _thread_names.clear()
        _trigger_last.clear()
