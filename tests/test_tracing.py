"""The tracing subsystem tested end to end (docs/observability.md):
span outcomes and registry/histogram reset, TraceContext nesting and
explicit thread-seam handoff, the x-volsync-trace wire format, the
flight recorder + trigger auto-dumps (shed / breaker-open / injected
fault / deadline), the closed-loop service acceptance (client ->
admission -> scheduler -> device batch spans nest under one trace with
tenant + stream id tags and the stage breakdown covering the measured
p50), the `volsync trace` CLI, and the tracing-disabled overhead gate; self
time and counters, the dispatch thread's spans in the ring under the
submitter's trace, the ring's compact events and its eviction count,
the names the segment program carries onto the device, the
program-load totals, and one trace across the seal and upload threads
of a pipelined backup.
"""

import glob
import hashlib
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import pytest

from volsync_tpu.obs import (
    begin_span,
    carry_context,
    chrome_trace,
    count,
    counter_totals,
    dump_trace,
    format_trace_header,
    new_trace,
    parse_trace_header,
    record_trigger,
    reset_spans,
    reset_trace,
    span,
    span_self_totals,
    span_totals,
    trace_context,
    trace_events,
    use_context,
)


@pytest.fixture(autouse=True)
def _clean_obs():
    reset_spans()
    reset_trace()
    yield
    reset_spans()
    reset_trace()


def _hist_sample(name: str, **labels) -> float:
    """One sample from the global registry's text exposition, or None
    when no labeled child matches (i.e. after a clear())."""
    from volsync_tpu.metrics import GLOBAL as M

    for line in M.expose().decode().splitlines():
        if not line.startswith(name + "{"):
            continue
        if all(f'{k}="{v}"' in line for k, v in labels.items()):
            return float(line.rpartition(" ")[2])
    return None


# -- satellite: outcome dimension -----------------------------------------

def test_span_outcome_dimension():
    with span("repo.seal"):
        pass
    with pytest.raises(ValueError):
        with span("repo.seal"):
            raise ValueError("boom")
    assert span_totals()["repo.seal"][0] == 2
    by = span_totals(by_outcome=True)
    assert by[("repo.seal", "ok")][0] == 1
    assert by[("repo.seal", "error")][0] == 1
    assert _hist_sample("volsync_stage_duration_seconds_count",
                        stage="repo.seal", outcome="ok") == 1
    assert _hist_sample("volsync_stage_duration_seconds_count",
                        stage="repo.seal", outcome="error") == 1


# -- satellite: reset_spans must clear the Prometheus children ------------

def test_reset_spans_clears_histogram_and_tenant_counter():
    with trace_context(tenant="gold"):
        with span("engine.read"):
            pass
    assert _hist_sample("volsync_stage_duration_seconds_count",
                        stage="engine.read", outcome="ok") == 1
    assert _hist_sample("volsync_svc_stage_seconds_total",
                        tenant="gold", stage="engine.read") > 0

    reset_spans()

    assert span_totals() == {}
    # the regression: labeled children used to survive the reset and
    # bleed stage timings into the next test/bench round
    assert _hist_sample("volsync_stage_duration_seconds_count",
                        stage="engine.read", outcome="ok") is None
    assert _hist_sample("volsync_svc_stage_seconds_total",
                        tenant="gold", stage="engine.read") is None


# -- context: nesting, handoff, wire format -------------------------------

def test_span_nesting_and_ring_tags():
    with trace_context(tenant="t1", stream_id="s1") as root:
        with span("svc.stream"):
            with span("svc.batch", lanes=3):
                pass
    spans = {e["name"]: e for e in trace_events() if e["ph"] == "X"}
    outer, inner = spans["svc.stream"], spans["svc.batch"]
    assert inner["args"]["parent_span_id"] == outer["args"]["span_id"]
    assert outer["args"]["parent_span_id"] == root.span_id
    for e in (outer, inner):
        assert e["args"]["trace_id"] == root.trace_id
        assert e["args"]["tenant"] == "t1"
        assert e["args"]["stream_id"] == "s1"
    assert inner["args"]["lanes"] == 3
    assert inner["dur"] <= outer["dur"]


def test_carry_context_across_pool_seam():
    def work():
        with span("repo.seal"):
            pass

    # nothing to carry -> fn returned unchanged
    assert carry_context(work) is work

    with trace_context(tenant="t2"):
        with span("svc.stream"):
            with ThreadPoolExecutor(1) as pool:
                pool.submit(carry_context(work)).result()
    spans = {e["name"]: e for e in trace_events() if e["ph"] == "X"}
    assert spans["repo.seal"]["args"]["parent_span_id"] == \
        spans["svc.stream"]["args"]["span_id"]
    assert spans["repo.seal"]["args"]["tenant"] == "t2"


def test_use_context_and_detached_spans():
    ctx = new_trace(tenant="t3", sampled=True)
    with use_context(None):  # explicit no-op side of the handoff
        assert begin_span("svc.queue_wait", ctx=None).ctx is None
    h = begin_span("svc.queue_wait", ctx=ctx)
    h.finish("error")
    h.finish("ok")  # idempotent: the first outcome stands
    by = span_totals(by_outcome=True)
    assert by[("svc.queue_wait", "error")][0] == 1
    assert ("svc.queue_wait", "ok") not in by
    (ev,) = [e for e in trace_events() if e["ph"] == "X"]
    assert ev["args"]["outcome"] == "error"
    assert ev["args"]["parent_span_id"] == ctx.span_id


def test_trace_header_roundtrip():
    ctx = new_trace(tenant="gold", stream_id="abc123", sampled=True)
    parsed = parse_trace_header(format_trace_header(ctx))
    assert parsed.trace_id == ctx.trace_id
    assert parsed.span_id == ctx.span_id
    assert parsed.stream_id == "abc123"
    assert parsed.sampled is True
    assert parsed.tenant is None  # never trusted from the wire
    unsampled = parse_trace_header(
        format_trace_header(ctx.evolve(sampled=False)))
    assert unsampled.sampled is False
    for bad in (None, "", "garbage", "a:b:c", ":x:y:1"):
        assert parse_trace_header(bad) is None


def test_sampling_disables_ring_but_not_totals(monkeypatch):
    monkeypatch.setenv("VOLSYNC_TRACE_SAMPLE", "0")
    with trace_context(tenant="t4"):
        with span("engine.read"):
            pass
    assert trace_events() == []
    assert span_totals()["engine.read"][0] == 1
    assert _hist_sample("volsync_svc_stage_seconds_total",
                        tenant="t4", stage="engine.read") > 0


# -- flight recorder: trigger auto-dumps ----------------------------------

def _trigger_files(dump_dir, reason):
    return sorted(glob.glob(os.path.join(dump_dir,
                                         f"trace-{reason}-*.json")))


def _arm_dumps(monkeypatch, tmp_path):
    monkeypatch.setenv("VOLSYNC_TRACE_DUMP", str(tmp_path))
    monkeypatch.setenv("VOLSYNC_TRACE_TRIGGER_INTERVAL_S", "0")


def test_shed_trigger_dumps_annotated_trace(monkeypatch, tmp_path):
    _arm_dumps(monkeypatch, tmp_path)
    from volsync_tpu.service import TenantConfig, TenantRegistry
    from volsync_tpu.service.admission import (
        AdmissionController, AdmissionRejected)

    adm = AdmissionController(
        TenantRegistry([TenantConfig(name="gold", weight=1)]),
        max_streams=1)
    ticket = adm.admit_stream("gold")
    with pytest.raises(AdmissionRejected):
        adm.admit_stream("gold")
    adm.release(ticket)

    (path,) = _trigger_files(tmp_path, "shed")
    doc = json.loads(Path(path).read_text())
    assert doc["trigger"]["reason"] == "shed"
    assert doc["trigger"]["tenant"] == "gold"
    assert doc["trigger"]["cause"] == "global_streams"
    assert any(e["name"] == "trigger.shed" for e in doc["traceEvents"])


def test_breaker_open_trigger_dumps(monkeypatch, tmp_path):
    _arm_dumps(monkeypatch, tmp_path)
    from volsync_tpu.resilience import CircuitBreaker, TransientError

    breaker = CircuitBreaker("dumptest", threshold=1, reset_seconds=60.0)
    breaker.record_failure(TransientError("forced"))
    assert breaker.open_remaining() > 0

    (path,) = _trigger_files(tmp_path, "breaker_open")
    doc = json.loads(Path(path).read_text())
    assert doc["trigger"] == {"reason": "breaker_open",
                              "backend": "dumptest"}


def test_injected_fault_trigger_dumps(monkeypatch, tmp_path):
    _arm_dumps(monkeypatch, tmp_path)
    from volsync_tpu.objstore.faultstore import maybe_wrap
    from volsync_tpu.objstore.store import MemObjectStore

    store = maybe_wrap(MemObjectStore(), seed=3, spec="latency:p=1,ms=1")
    store.put("k", b"x")

    files = _trigger_files(tmp_path, "fault")
    assert files, "injected fault produced no flight-recorder dump"
    doc = json.loads(Path(files[0]).read_text())
    assert doc["trigger"]["reason"] == "fault"
    assert doc["trigger"]["op"] == "put"
    assert doc["trigger"]["kinds"] == ["latency"]


def test_deadline_trigger_dumps(monkeypatch, tmp_path):
    _arm_dumps(monkeypatch, tmp_path)
    from volsync_tpu.resilience import (
        DeadlineExceeded, RetryPolicy, TransientError)

    policy = RetryPolicy(site="tracetest.deadline", max_attempts=10,
                         base_delay=0.05, max_delay=0.05, deadline=0.01)

    def always_fails():
        raise TransientError("nope")

    with pytest.raises(DeadlineExceeded):
        policy.call(always_fails)

    (path,) = _trigger_files(tmp_path, "deadline")
    doc = json.loads(Path(path).read_text())
    assert doc["trigger"]["reason"] == "deadline"
    assert doc["trigger"]["site"] == "tracetest.deadline"
    assert doc["trigger"]["attempt"] >= 1


def test_trigger_throttling(monkeypatch, tmp_path):
    monkeypatch.setenv("VOLSYNC_TRACE_DUMP", str(tmp_path))
    monkeypatch.setenv("VOLSYNC_TRACE_TRIGGER_INTERVAL_S", "3600")
    record_trigger("shed", tenant="a")
    record_trigger("shed", tenant="b")
    assert len(_trigger_files(tmp_path, "shed")) == 1  # second throttled
    # but both instants are in the ring
    marks = [e for e in trace_events() if e["name"] == "trigger.shed"]
    assert len(marks) == 2


# -- the closed-loop service acceptance -----------------------------------

def test_service_closed_loop_trace_acceptance():
    """A closed-loop run (tests/closed_loop.py): one stream's spans nest
    client -> admission -> scheduler queue -> device batch under a
    single trace id, tagged with tenant + stream id, and the summed
    component breakdown accounts for >= 90% of the enclosing
    server-side ``svc.stream`` time. Every second of the stream span
    is inside SOME component span — including the client-paced waits
    (svc.ingest frame pulls, svc.emit batch drains) — so ambient host
    load cannot open an unaccountable gap: it lands in ingest/emit
    instead. The metric used to divide by the client-measured p50
    with no wait instrumentation, and flaked this gate whenever the
    CPU was saturated (bronze coverage 0.74)."""
    from closed_loop import run_closed_loop
    from volsync_tpu.ops.gearcdc import GearParams

    params = GearParams(min_size=64 * 1024, avg_size=128 * 1024,
                        max_size=256 * 1024, align=4096)
    res = run_closed_loop(
        tenants=[{"name": "gold", "weight": 4, "clients": 1},
                 {"name": "bronze", "weight": 1, "clients": 1}],
        requests_per_client=4, mib_per_request=1, segment_kib=128,
        window_ms=5.0, params=params, warm=False,
        # this gate checks span NESTING, not latency: a starved host
        # must slow the run down, never abort it mid-stream
        client_timeout=600.0)
    assert res["mid_stream_aborts"] == []

    # per-tenant latency attribution in the report itself
    for name in ("gold", "bronze"):
        tn = res["tenants"][name]
        for stage in ("svc.stream", "svc.admit", "svc.batch"):
            assert tn["stages_s"].get(stage, 0) > 0, (name, tn["stages_s"])
        assert tn["stage_coverage"] >= 0.9, (name, tn)
    # the process-wide totals hold both ends of the stream
    totals = span_totals()
    assert "svc.batch" in totals and "client.chunk_stream" in totals

    # flight recorder: find one fully-nested stream
    evs = [e for e in trace_events() if e["ph"] == "X"]
    by_trace: dict = {}
    for e in evs:
        by_trace.setdefault(e["args"]["trace_id"], []).append(e)
    want = {"client.chunk_stream", "svc.stream", "svc.admit",
            "svc.queue_wait", "svc.batch"}
    nested = None
    for tevs in by_trace.values():
        if want <= {e["name"] for e in tevs}:
            nested = tevs
            break
    assert nested is not None, sorted(
        {e["name"] for e in evs})

    def one(name):
        return next(e for e in nested if e["name"] == name)

    client = one("client.chunk_stream")
    stream = one("svc.stream")
    assert stream["args"]["parent_span_id"] == client["args"]["span_id"]
    stream_sid = stream["args"]["span_id"]
    for child in ("svc.admit", "svc.queue_wait", "svc.batch"):
        assert one(child)["args"]["parent_span_id"] == stream_sid, child
    for e in nested:
        assert e["args"]["tenant"] in ("gold", "bronze")
        assert e["args"]["stream_id"]
    assert stream["args"]["stream_id"] == client["args"]["stream_id"]


# -- CLI + export ---------------------------------------------------------

def test_trace_cli_dump_and_summary(tmp_path):
    from volsync_tpu.cli.main import run as cli_run

    with trace_context(tenant="cli"):
        with span("engine.read"):
            pass
    out_file = tmp_path / "dump.json"
    lines: list = []
    assert cli_run(["trace", "dump", "--out", str(out_file)], {},
                   out=lines.append) == 0
    doc = json.loads(out_file.read_text())
    assert any(e.get("name") == "engine.read"
               for e in doc["traceEvents"])
    assert str(out_file) in lines[0]

    count("ops.lanes", 3)
    lines.clear()
    assert cli_run(["trace", "summary"], {}, out=lines.append) == 0
    assert any("engine.read" in ln and "ok" in ln for ln in lines)
    assert lines[0].split()[-1] == "self"
    (row,) = [ln for ln in lines if ln.startswith("engine.read")]
    assert float(row.split()[-1]) == pytest.approx(
        span_self_totals()["engine.read"][1], abs=1e-4)
    assert any(ln.split() == ["ops.lanes", "counter", "3"] for ln in lines)

    # dump to stdout when --out is omitted
    lines.clear()
    assert cli_run(["trace", "dump"], {}, out=lines.append) == 0
    assert json.loads("\n".join(lines))["traceEvents"]


def test_chrome_trace_shape_and_dump_trace(tmp_path):
    with trace_context(tenant="shape"):
        with span("engine.read"):
            pass
    doc = chrome_trace(trigger="manual", annotations={"who": "test"})
    assert doc["displayTimeUnit"] == "ms"
    assert doc["trigger"] == {"reason": "manual", "who": "test"}
    assert any(e["ph"] == "M" and e["name"] == "thread_name"
               for e in doc["traceEvents"])
    # explicit-path dump works with no dump dir configured
    path = dump_trace(path=str(tmp_path / "t.json"))
    assert json.loads(Path(path).read_text())["traceEvents"]
    # no path + no dump dir -> None, no file side effects
    assert dump_trace() is None


def test_one_trace_spans_the_seal_and_upload_threads(tmp_path):
    """A pipelined backup under one tenant-tagged context: the dump is
    what Perfetto loads, and the ``repo.*`` spans the seal and upload
    threads record carry the caller's trace id and tenant, a parent
    edge leading from each to the caller's outer span."""
    import numpy as np

    from volsync_tpu.engine.chunker import (
        DeviceChunkHasher, stream_chunk_batches)
    from volsync_tpu.objstore.store import MemObjectStore
    from volsync_tpu.ops.gearcdc import GearParams
    from volsync_tpu.repo.repository import Repository

    data = np.random.RandomState(3).randint(
        0, 256, size=(2 << 20,), dtype=np.uint8).tobytes()
    params = GearParams(min_size=64 * 1024, avg_size=128 * 1024,
                        max_size=256 * 1024, seed=7, align=4096)
    pos = [0]

    def reader(nbytes: int) -> bytes:
        piece = data[pos[0]: pos[0] + nbytes]
        pos[0] += len(piece)
        return piece

    repo = Repository.init(MemObjectStore())
    repo.pipelined = True
    with trace_context(tenant="smoke", stream_id="pipeline") as root:
        with span("smoke.pipeline"):
            for chunks in stream_chunk_batches(
                    reader, params, segment_size=512 * 1024,
                    hasher=DeviceChunkHasher(params), readahead=2):
                repo.add_blobs(
                    "data", [(digest, chunk) for chunk, digest in chunks])
            repo.flush()

    path = dump_trace(path=str(tmp_path / "trace.json"), trigger="smoke")
    doc = json.loads(Path(path).read_text())
    assert doc["trigger"]["reason"] == "smoke"
    events = doc["traceEvents"]
    assert isinstance(events, list)
    assert any(e["ph"] == "M" and e["name"] == "thread_name"
               for e in events)
    spans = [e for e in events if e["ph"] == "X"]
    for e in spans:
        assert {"name", "ts", "dur", "pid", "tid", "args"} <= set(e), e
    by_id = {e["args"]["span_id"]: e for e in spans}
    (outer,) = [e for e in spans if e["name"] == "smoke.pipeline"]
    assert {"engine.read", "engine.device", "repo.seal",
            "repo.pack_upload"} <= {e["name"] for e in spans}
    elsewhere = [e for e in spans if e["name"].startswith("repo.")
                 and e["tid"] != outer["tid"]]
    assert {"repo.seal", "repo.pack_upload"} <= {
        e["name"] for e in elsewhere}
    for e in elsewhere:
        assert e["args"]["trace_id"] == root.trace_id, e
        assert e["args"]["tenant"] == "smoke", e
        up = e
        while up is not outer:
            up = by_id.get(up["args"].get("parent_span_id"))
            assert up is not None, f"no way up from {e['name']}"


# -- self time and counters -----------------------------------------------

def _sleep_span(name, seconds, inner=()):
    with span(name):
        time.sleep(seconds)
        for child in inner:
            child()


@pytest.mark.parametrize("shape", ["nested", "siblings", "handle",
                                   "other_thread"])
def test_self_time(shape):
    """A span()'s self seconds are its duration less the span()s that
    closed inside it on the same thread; a begin_span() handle and a
    span on another thread are nobody's child."""
    if shape == "nested":
        _sleep_span("svc.stream", 0.02, [
            lambda: _sleep_span("svc.batch", 0.02, [
                lambda: _sleep_span("repo.seal", 0.03)])])
        children = {"svc.stream": ["svc.batch"], "svc.batch": ["repo.seal"],
                    "repo.seal": []}
    elif shape == "siblings":
        _sleep_span("svc.stream", 0.02, [
            lambda: _sleep_span("svc.batch", 0.02),
            lambda: _sleep_span("repo.seal", 0.03)])
        children = {"svc.stream": ["svc.batch", "repo.seal"],
                    "svc.batch": [], "repo.seal": []}
    elif shape == "handle":
        def wait():
            h = begin_span("svc.queue_wait", ctx=None)
            time.sleep(0.03)
            h.finish()

        _sleep_span("svc.stream", 0.02, [wait])
        children = {"svc.stream": [], "svc.queue_wait": []}
    else:
        def elsewhere():
            with ThreadPoolExecutor(1) as pool:
                pool.submit(_sleep_span, "repo.seal", 0.03).result()

        _sleep_span("svc.stream", 0.02, [elsewhere])
        children = {"svc.stream": [], "repo.seal": []}
    totals, own = span_totals(), span_self_totals()
    assert set(own) == set(totals) == set(children)
    for name, kids in children.items():
        assert own[name][0] == totals[name][0] == 1
        want = totals[name][1] - sum(totals[k][1] for k in kids)
        assert own[name][1] == pytest.approx(want, abs=1e-9)
        assert own[name][1] >= 0.019


def test_self_time_of_a_failing_child_still_leaves_the_parent():
    with pytest.raises(ValueError):
        with span("svc.stream"):
            with span("svc.batch"):
                time.sleep(0.02)
                raise ValueError("boom")
    totals, own = span_totals(), span_self_totals()
    assert own["svc.stream"][1] == pytest.approx(
        totals["svc.stream"][1] - totals["svc.batch"][1], abs=1e-9)
    # the stack is empty again: the next span is nobody's child
    with span("repo.seal"):
        pass
    assert span_self_totals()["repo.seal"][1] == \
        span_totals()["repo.seal"][1]


def test_counters_and_their_reset():
    assert counter_totals() == {}
    count("ops.dispatches")
    count("ops.lanes", 3)
    count("ops.lanes", 2)
    assert counter_totals() == {"ops.dispatches": 1, "ops.lanes": 5}
    with span("engine.read"):
        pass
    reset_spans()  # the benchmark's one call at window start zeroes both
    assert counter_totals() == {} and span_self_totals() == {}


# -- the dispatch thread, on the submitter's trace ------------------------

_DISPATCH_SPANS = ("ops.queue_wait", "ops.batch_dispatch", "ops.stage",
                   "ops.launch", "ops.fetch", "ops.decode")


@pytest.mark.parametrize("registered", [False, True],
                         ids=["unregistered", "registered"])
@pytest.mark.parametrize("sampled", [True, False])
def test_batched_dispatch_enters_the_ring_under_the_submitters_trace(
        sampled, registered):
    """A sampled submit puts the wait for the batcher, the dispatch and
    its four stages in the ring with the submitter's trace id — on the
    dispatch thread, which holds no context of its own, be the
    submitter a registered producer (its batch does not wait) or not;
    an unsampled one puts nothing there, and the registry and the
    counters record both."""
    import contextlib
    from volsync_tpu.ops.batcher import SegmentMicroBatcher
    from volsync_tpu.ops.gearcdc import GearParams

    params = GearParams(min_size=4096, avg_size=32768, max_size=65536,
                        seed=0x5EED_CDC1, align=4096)
    data = os.urandom(30_000)
    mb = SegmentMicroBatcher(params, max_batch=2, window_ms=1.0,
                             pipeline_depth=1)
    try:
        with mb.producer() if registered else contextlib.nullcontext(), \
                trace_context(sampled=sampled) as root:
            with span("engine.device"):
                chunks, consumed = mb.submit(data, len(data), True)
    finally:
        mb.stop()
    assert consumed == len(data) and chunks
    totals = span_totals()
    for name in _DISPATCH_SPANS:
        # ops.stage twice: the rows are filled, and later released
        assert totals[name][0] == (2 if name == "ops.stage" else 1), name
    stages = sum(totals[n][1] for n in _DISPATCH_SPANS[2:])
    assert stages <= totals["ops.batch_dispatch"][1]
    assert span_self_totals()["ops.batch_dispatch"][1] == pytest.approx(
        totals["ops.batch_dispatch"][1] - stages, abs=1e-9)
    assert counter_totals() == {
        "ops.batches": 1, **({"ops.batches_complete": 1} if registered
                             else {}),
        "ops.dispatches": 1, "ops.lanes": 1, "ops.lanes_padded": 1,
        "ops.bytes_valid": len(data), "ops.bytes_padded": 65536}

    spans = {e["name"]: e for e in trace_events() if e["ph"] == "X"}
    if not sampled:
        assert spans == {}
        return
    assert set(_DISPATCH_SPANS) <= set(spans)
    waiter = spans["engine.device"]
    for name in _DISPATCH_SPANS:
        assert spans[name]["args"]["trace_id"] == root.trace_id, name
    for name in ("ops.queue_wait", "ops.batch_dispatch"):
        assert spans[name]["args"]["parent_span_id"] == \
            waiter["args"]["span_id"]
    for name in _DISPATCH_SPANS[2:]:
        assert spans[name]["args"]["parent_span_id"] == \
            spans["ops.batch_dispatch"]["args"]["span_id"]
        assert spans[name]["args"]["bucket"] == 65536
        assert spans[name]["tid"] == spans["ops.batch_dispatch"]["tid"]
    assert spans["ops.batch_dispatch"]["tid"] != waiter["tid"]
    assert spans["ops.batch_dispatch"]["args"]["lanes"] == 1


def test_single_lane_dispatch_is_counted_like_a_batched_one():
    """The way to the device without the batcher: ops.stage around the
    pad and the upload, the five counters from the same function."""
    import numpy as np

    from volsync_tpu.engine.chunker import DeviceChunkHasher, _buffer_bucket
    from volsync_tpu.ops.gearcdc import GearParams

    params = GearParams(min_size=4096, avg_size=32768, max_size=65536,
                        seed=0x5EED_CDC1, align=4096)
    data = np.frombuffer(os.urandom(30_000), np.uint8)
    assert DeviceChunkHasher(params).process(data, eof=True)
    totals = span_totals()
    for name in ("ops.stage", "engine.fused_dispatch", "engine.fused_fetch"):
        assert totals[name][0] == 1, name
    assert counter_totals() == {
        "ops.dispatches": 1, "ops.lanes": 1, "ops.lanes_padded": 1,
        "ops.bytes_valid": 30_000,
        "ops.bytes_padded": _buffer_bucket(30_000)}


# -- the ring: compact events, rendered on export, evictions counted ------

def test_ring_events_render_to_chrome_keys_and_evictions_are_counted(
        monkeypatch):
    from volsync_tpu.obs.copyledger import record_copy

    monkeypatch.setenv("VOLSYNC_TRACE_RING", "16")
    reset_trace()
    with trace_context(tenant="t9", stream_id="s9") as root:
        with span("repo.seal", blob=7):
            record_copy("repo.seal", 10)
    record_trigger("shed", tenant="t9")
    by_ph = {}
    for e in trace_events():
        by_ph.setdefault((e["ph"], e["cat"]), e)
    x = by_ph[("X", "span")]
    assert set(x) == {"name", "cat", "ph", "ts", "dur", "pid", "tid", "args"}
    assert x["args"] == {
        "trace_id": root.trace_id, "span_id": x["args"]["span_id"],
        "parent_span_id": root.span_id, "outcome": "ok", "tenant": "t9",
        "stream_id": "s9", "blob": 7}
    i = by_ph[("i", "copy")]
    assert set(i) == {"name", "cat", "ph", "s", "ts", "pid", "tid", "args"}
    assert i["s"] == "t" and i["args"]["trace_id"] == root.trace_id
    assert x["ts"] <= i["ts"] <= x["ts"] + x["dur"]
    g = by_ph[("i", "trigger")]
    assert g["s"] == "g" and g["args"] == {"tenant": "t9"}
    assert json.loads(json.dumps(chrome_trace()))["traceEvents"]
    assert "obs.ring_dropped" not in counter_totals()

    with trace_context(sampled=True):
        for _ in range(20):
            with span("engine.read"):
                pass
    assert len(trace_events()) == 16
    assert counter_totals()["obs.ring_dropped"] == 3 + 20 - 16


def test_default_ring_holds_a_traced_window():
    from volsync_tpu import envflags

    assert envflags.trace_ring_size() == 65536


# -- names on the device --------------------------------------------------

def test_segment_program_names_its_stages_and_kernels(monkeypatch):
    """The six jax.named_scope stages are in the lowered program's
    locations and the Pallas kernels carry their names (lowered for
    the TPU, as tests/test_chip_compile.py steers it: the CPU arm has
    no kernel)."""
    import jax
    import jax.numpy as jnp

    from volsync_tpu.ops import segment
    from volsync_tpu.ops.gearcdc import GearParams

    p = GearParams(min_size=4096, avg_size=32768, max_size=65536,
                   seed=0x5EED_CDC1, align=4096)
    seg = 1 << 20
    cand_cap, chunk_cap = segment.segment_caps(seg, p)
    kw = dict(min_size=p.min_size, avg_size=p.avg_size, max_size=p.max_size,
              seed=p.seed, mask_s=p.mask_s, mask_l=p.mask_l, align=p.align,
              cand_cap=cand_cap, chunk_cap=chunk_cap)
    monkeypatch.setattr(segment, "use_pallas_leaves", lambda: True)
    scopes = ("gear_candidates", "compact", "boundary_walk", "page_sha",
              "tail_sha", "merkle_roots")
    batched = jax.jit(segment._chunk_hash_segments_impl,
                      static_argnames=segment._SEGMENTS_STATIC).trace(
        jax.ShapeDtypeStruct((2 * seg,), jnp.uint8),
        jax.ShapeDtypeStruct((2,), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.bool_), **kw)
    single = segment.chunk_hash_segment.trace(
        jax.ShapeDtypeStruct((seg,), jnp.uint8), 1000, eof=True, **kw)
    # the shared jit keeps this trace, kernels and all, for the next
    # caller with these arguments on this worker: a CPU test that then
    # runs the one-chip engine on a 1 MiB segment
    # (tests/test_mesh_reference.py) would run the Pallas kernels
    segment.chunk_hash_segment.clear_cache()
    for traced in (batched, single):
        text = traced.lower(lowering_platforms=("tpu",)).as_text(
            debug_info=True)
        for scope in scopes:
            assert f"/{scope}/" in text, scope
        for kernel in ("transpose_tiles", "sha256_pages"):
            assert f'kernel_name = "{kernel}"' in text, kernel


# -- program loads --------------------------------------------------------

def test_load_totals_counts_a_compile():
    import jax
    import jax.numpy as jnp

    from volsync_tpu import compile_cache

    compile_cache._listen()
    compile_cache._listen()  # registered once: one compile counts once
    x = jnp.arange(8)
    before = compile_cache.load_totals()
    salt = time.perf_counter_ns() % 1000 + 2  # a program not yet loaded
    inner = jax.jit(lambda v: jnp.sort(v * salt))  # traced inside outer
    t0 = time.perf_counter()
    jax.jit(lambda v: inner(v) + inner(v + 1))(x).block_until_ready()
    wall = time.perf_counter() - t0
    after = compile_cache.load_totals()
    assert after["compiles"] + after["cache_hits"] == \
        before["compiles"] + before["cache_hits"] + 1
    for key in ("trace_s", "lower_s", "backend_s"):
        assert after[key] > before[key], key
    # the inner jit's trace lies inside the outer's and counts in both
    # sums; load_s is the union: time that passed
    seconds = sum(after[k] - before[k]
                  for k in ("trace_s", "lower_s", "backend_s"))
    assert 0 < after["load_s"] - before["load_s"] <= min(seconds, wall)
    reset_spans()  # set-up is before the window: not zeroed with spans
    assert compile_cache.load_totals() == after


@pytest.mark.parametrize("spans, want", [
    ([(0, 1), (2, 3)], [[0, 1], [2, 3]]),
    ([(0, 3), (1, 2)], [[0, 3]]),                 # nested
    ([(1, 2), (0, 3)], [[0, 3]]),                 # the outer fires last
    ([(0, 2), (1, 3), (5, 6), (3, 5)], [[0, 6]]),  # two threads, a bridge
    ([(4, 5), (0, 1), (2, 3), (0.5, 2.5)], [[0, 3], [4, 5]]),
])
def test_load_regions_are_merged_into_their_union(monkeypatch, spans, want):
    from volsync_tpu import compile_cache

    monkeypatch.setattr(compile_cache, "_covered", [])
    for start, end in spans:
        compile_cache._cover(float(start), float(end))
    assert compile_cache._covered == want


# -- disabled-path overhead gate ------------------------------------------

def test_tracing_disabled_overhead_under_2pct(monkeypatch):
    """Acceptance: with sampling off and no active context (the
    pipeline smoke's disabled-tracing configuration) one span() costs
    < 2% of one segment-scale sha256 — the per-span workload unit of
    a backup, which opens one span per ~MiB-sized hash/seal/upload
    stage. The two costs are measured separately
    (min-of-5 each) because the span cost (~µs) is far below the
    run-to-run noise of a combined wall-clock comparison."""
    monkeypatch.setenv("VOLSYNC_TRACE_SAMPLE", "0")
    reset_spans()
    reset_trace()
    data = os.urandom(2 << 20)

    def unit_work():  # one pipeline-stage-sized unit of real work
        t0 = time.perf_counter()
        for _ in range(8):
            hashlib.sha256(data).digest()
        return (time.perf_counter() - t0) / 8

    def span_cost():
        t0 = time.perf_counter()
        for _ in range(2000):
            with span("engine.device"):
                pass
        return (time.perf_counter() - t0) / 2000

    unit_work(), span_cost()  # warm: page in data, create histogram
    unit = min(unit_work() for _ in range(5))
    per_span = min(span_cost() for _ in range(5))
    assert per_span <= unit * 0.02, (
        f"tracing-disabled span cost {per_span * 1e6:.1f} us is "
        f"{per_span / unit:.2%} of a {unit * 1e3:.2f} ms work unit "
        f"(gate: < 2%)")
    assert trace_events() == []  # sampling off: ring stayed empty
