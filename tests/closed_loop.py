"""Closed-loop multi-tenant driver for the mover-jax service plane.

N closed-loop clients across >= 2 tenants each drive sequential
ChunkHash streams against a server running the full admission +
weighted-DRR scheduling stack (service/admission.py,
service/scheduler.py). ``run_closed_loop`` reports per tenant (p50/p99
request latency, goodput, admitted/shed counts, where the time went)
plus the plane-wide evidence the acceptance tests assert on:
cross-tenant coalescing survived scheduling (device dispatches <
segments submitted) and overload was absorbed at admission (zero
mid-stream aborts). A test helper (tests/test_service_plane.py,
tests/test_tracing.py), not a benchmark: the served path is measured
by ``benchmark/`` (cell ``fleet-100.stream``).

Modes:
  - normal          closed loop; a shed client honors the server's
                    retry-after hint and retries (the shed still counts).
  - force_breaker   trips the wired circuit breaker open first and
                    measures the admission shed path's latency instead
                    of throughput (acceptance (c): shed in < 10 ms).
  - fault_spec      arms a seeded FaultSchedule over the DEVICE
                    DISPATCH path; latency-kind faults stall dispatches
                    (stressing the credit pause and the DRR backlog).
                    Error-kind faults are refused here: a CDC stream
                    cannot be replayed mid-flight, so error injection
                    lives in tests/test_service_chaos.py at the store
                    layer.
"""

from __future__ import annotations

import threading
import time

import numpy as np

_PIECE = 1024 * 1024  # stream in 1 MiB pieces (gRPC 4 MiB msg cap)


def _reader_for(buf: bytes):
    pos = [0]

    def read(nbytes: int) -> bytes:
        p = buf[pos[0]: pos[0] + min(nbytes, _PIECE)]
        pos[0] += len(p)
        return p

    return read


def _percentile(xs: list, q: float) -> float:
    return float(np.percentile(np.asarray(xs, dtype=np.float64), q)) \
        if xs else 0.0


class _TenantTally:
    """Per-tenant closed-loop accounting, shared by that tenant's
    client threads."""

    def __init__(self):
        self.lock = threading.Lock()
        self.latencies: list[float] = []
        self.shed_latencies: list[float] = []
        self.bytes = 0
        self.requests = 0
        self.sheds = 0
        self.mid_stream_aborts: list[str] = []


def _arm_dispatch_faults(srv, fault_spec: str, fault_seed: int,
                         dispatch_log: list):
    """Wrap the server batcher's device dispatch with a spy (always)
    and, when a spec is armed, seeded latency injection. Returns the
    wrapped-over hasher so callers can restore it."""
    from volsync_tpu.objstore.faultstore import FaultSchedule, parse_spec

    specs = parse_spec(fault_spec) if fault_spec else []
    bad = [s.kind for s in specs if s.kind != "latency"]
    if bad:
        raise ValueError(
            f"dispatch-path fault injection supports latency only "
            f"(got {bad}); error kinds belong to the store-layer chaos "
            f"tests")
    schedule = FaultSchedule(seed=fault_seed, specs=specs) if specs \
        else None
    hasher = srv._batcher._hasher
    inner = hasher.hash_segments
    calls = [0]
    log_lock = threading.Lock()

    def spy(items):
        with log_lock:
            calls[0] += 1
            n = calls[0]
            dispatch_log.append(len(items))
        if schedule is not None:
            for idx, spec in enumerate(specs):
                if schedule.roll(idx, "dispatch", f"b{len(items)}",
                                 n) < spec.p:
                    time.sleep(spec.latency)
        return inner(items)

    hasher.hash_segments = spy
    return hasher, inner


def _run_clients(make_client, tenants: list[dict], payload_for,
                 requests_per_client: int, tallies: dict) -> float:
    """Closed loop: every client drives ``requests_per_client``
    sequential streams, sleeping out the server's retry-after hint on a
    shed. Returns the wall time of the whole phase."""
    from volsync_tpu.service import ShedError

    def loop(tenant: str, gidx: int):
        tally: _TenantTally = tallies[tenant]
        payload = payload_for(gidx)
        with make_client(tenant) as c:
            done = 0
            while done < requests_per_client:
                t0 = time.perf_counter()
                got = 0
                try:
                    for _ in c.chunk_stream(_reader_for(payload)):
                        got += 1
                except ShedError as e:
                    dt = time.perf_counter() - t0
                    with tally.lock:
                        tally.sheds += 1
                        tally.shed_latencies.append(dt)
                    # Closed-loop shed handling IS the thing under
                    # measurement: honor the server's hint directly
                    # (capped so a long breaker cooldown cannot stall
                    # the run) rather than routing through
                    # RetryPolicy, whose jittered backoff would blur
                    # the per-request latency being reported.
                    time.sleep(min(e.retry_after, 0.2))  # lint: ignore[VL105]
                    continue
                except Exception as e:  # noqa: BLE001 — tallied, asserted on
                    with tally.lock:
                        tally.mid_stream_aborts.append(
                            f"{tenant}[{gidx}] after {got} batches: {e!r}")
                    done += 1
                    continue
                dt = time.perf_counter() - t0
                with tally.lock:
                    tally.latencies.append(dt)
                    tally.bytes += len(payload)
                    tally.requests += 1
                done += 1

    threads = []
    gidx = 0
    for t in tenants:
        for _ in range(t["clients"]):
            threads.append(threading.Thread(
                target=loop, args=(t["name"], gidx), daemon=True,
                name=f"closed-loop-{t['name']}-{gidx}"))
            gidx += 1
    t0 = time.perf_counter()
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    return time.perf_counter() - t0


def run_closed_loop(*, tenants: list[dict], requests_per_client: int = 3,
                    mib_per_request: int = 16, segment_kib: int = 4096,
                    window_ms: float = 2.0, max_streams: int = 0,
                    tenant_streams: int = 0, max_queued: int = 0,
                    stream_credits: int = 0, force_breaker: bool = False,
                    fault_spec: str = "", fault_seed: int = 0,
                    params=None, warm: bool = True,
                    client_timeout: float = 60.0) -> dict:
    """One closed-loop run (the acceptance tests drive it
    directly). ``tenants`` is [{name, weight, clients[, streams]}, ...];
    0 for any cap means "use the VOLSYNC_SVC_* default"."""
    from volsync_tpu.obs import reset_spans, reset_trace
    from volsync_tpu.ops.gearcdc import GearParams
    from volsync_tpu.repo import blobid
    from volsync_tpu.resilience import CircuitBreaker, TransientError
    from volsync_tpu.service import (
        MoverJaxClient, MoverJaxServer, TenantConfig, TenantRegistry)

    if params is None:
        params = GearParams(min_size=64 * 1024, avg_size=1024 * 1024,
                            max_size=4 * 1024 * 1024, align=4096)
    registry = TenantRegistry(
        TenantConfig(name=t["name"], weight=t["weight"],
                     max_streams=t.get("streams"))
        for t in tenants)
    total_clients = sum(t["clients"] for t in tenants)
    assert total_clients < 127, "salt space"

    breaker = None
    if force_breaker:
        breaker = CircuitBreaker("closed-loop", threshold=1,
                                 reset_seconds=60.0)
        breaker.record_failure(TransientError("closed-loop: forced open"))
        assert breaker.open_remaining() > 0

    n = mib_per_request * 1024 * 1024
    base = np.random.RandomState(7).randint(0, 256, size=(n,),
                                            dtype=np.uint8)
    # Per-client salted payloads, warm salts disjoint (128+i) from the
    # timed ones (i+1): no timed request repeats a warmed payload.
    payloads = [(base ^ np.uint8(i + 1)).tobytes()
                for i in range(total_clients)]
    warm_payloads = [(base ^ np.uint8(128 + i)).tobytes()
                     for i in range(total_clients)]

    dispatch_log: list[int] = []
    srv = MoverJaxServer(
        params=params, segment_size=segment_kib * 1024,
        batch_window_ms=window_ms,
        # enough handler threads that concurrency is bounded by
        # ADMISSION, not by gRPC's thread pool queueing ahead of it
        handlers=total_clients + 4,
        tenants=registry, breaker=breaker,
        max_streams=max_streams or None,
        tenant_streams=tenant_streams or None,
        max_queued=max_queued or None,
        stream_credits=stream_credits or None)
    hasher, inner_hash = _arm_dispatch_faults(
        srv, fault_spec, fault_seed, dispatch_log)

    def make_client(tenant: str) -> MoverJaxClient:
        return MoverJaxClient("127.0.0.1", srv.port, srv.token,
                              tenant=tenant, timeout=client_timeout)

    result: dict = {
        "tenants": {},
        "mib_per_request": mib_per_request,
        "segment_kib": segment_kib,
        "requests_per_client": requests_per_client,
        "max_streams": max_streams or None,
        "fault_spec": fault_spec or None,
    }
    try:
        with srv:
            if force_breaker:
                result.update(_breaker_shed_phase(srv, make_client))
            else:
                # Golden: one stream checked against hashlib before
                # timing (warm salt — never colliding with timed data).
                with make_client(tenants[0]["name"]) as cl:
                    g = list(cl.chunk_stream(
                        _reader_for(warm_payloads[0])))
                s0, l0, d0 = g[0]
                assert d0 == blobid.blob_id(
                    warm_payloads[0][s0:s0 + l0]), \
                    "service golden check failed"
                tallies = {t["name"]: _TenantTally() for t in tenants}
                if warm:
                    # full concurrency so every pow2 lane-count kernel
                    # the timed phase can hit is compiled up front
                    _run_clients(make_client, tenants,
                                 lambda i: warm_payloads[i], 1, tallies)
                    aborts = [a for tl in tallies.values()
                              for a in tl.mid_stream_aborts]
                    assert not aborts, aborts
                    tallies = {t["name"]: _TenantTally()
                               for t in tenants}
                # Per-tenant stage attribution must describe the TIMED
                # phase only — drop warm-phase spans and the warm
                # flight-recorder contents before measuring.
                reset_spans()
                reset_trace()
                dispatch_log.clear()
                wall = _run_clients(make_client, tenants,
                                    lambda i: payloads[i],
                                    requests_per_client, tallies)
                result.update(_report_load_phase(
                    tenants, tallies, wall, dispatch_log))
    finally:
        hasher.hash_segments = inner_hash
    return result


def _breaker_shed_phase(srv, make_client) -> dict:
    """Acceptance (c): with the breaker forced open, time the
    admission shed path directly (the in-process bound the <10 ms
    criterion pins) and once through a real client (the RPC-visible
    bound, network stack included)."""
    from volsync_tpu.service import ShedError
    from volsync_tpu.service.admission import AdmissionRejected

    direct: list[float] = []
    for _ in range(200):
        t0 = time.perf_counter()
        try:
            srv.admission.admit_stream("closed-loop-probe")
        except AdmissionRejected as rej:
            assert rej.reason == "breaker_open", rej.reason
        else:
            raise AssertionError("breaker open but stream admitted")
        direct.append(time.perf_counter() - t0)
    t0 = time.perf_counter()
    try:
        with make_client("closed-loop-probe") as c:
            list(c.chunk_stream(_reader_for(b"x" * 4096)))
    except ShedError as e:
        rpc_dt, retry_after = time.perf_counter() - t0, e.retry_after
    else:
        raise AssertionError("breaker open but RPC stream admitted")
    return {
        "breaker": {
            "direct_shed_p99_ms": round(_percentile(direct, 99) * 1e3, 4),
            "direct_shed_max_ms": round(max(direct) * 1e3, 4),
            "rpc_shed_ms": round(rpc_dt * 1e3, 3),
            "retry_after_s": round(retry_after, 3),
        },
    }


# The components of one stream: admission gate, client-paced frame
# pulls, DRR queue wait, device batch, client-paced batch drains
# (svc.schedule and svc.stream enclose/overlap these,
# client.chunk_stream is the client's view — all reported in stages_s
# but excluded from the coverage sum so no second is counted twice).
_COMPONENT_STAGES = ("svc.admit", "svc.ingest", "svc.queue_wait",
                     "svc.batch", "svc.emit")
# Coverage is components / svc.stream — the span that encloses them on
# the server — NOT components / client p50: the client number includes
# client-side work no server span can account for. svc.ingest and
# svc.emit matter for the same reason: the handler blocks on the
# client inside svc.stream, so under a saturated CPU those waits
# dominate and, uninstrumented, they flaked this gate (bronze
# coverage 0.74). Credit-based read-ahead lets svc.queue_wait /
# svc.batch overlap the client waits, so coverage can exceed 1.0.


def _report_load_phase(tenants: list[dict], tallies: dict, wall: float,
                       dispatch_log: list) -> dict:
    from volsync_tpu.metrics import GLOBAL as metrics

    # volsync_svc_stage_seconds{tenant,stage}, as an operator scrapes it
    tenant_stages = {
        (s.labels["tenant"], s.labels["stage"]): s.value
        for family in metrics.svc_stage_seconds.collect()
        for s in family.samples if s.name.endswith("_total")}
    per_tenant: dict = {}
    admitted = sheds = 0
    aborts: list[str] = []
    for t in tenants:
        tl: _TenantTally = tallies[t["name"]]
        admitted += tl.requests
        sheds += tl.sheds
        aborts.extend(tl.mid_stream_aborts)
        stages = {stage: round(secs, 4)
                  for (tn, stage), secs in sorted(tenant_stages.items())
                  if tn == t["name"]}
        p50_s = _percentile(tl.latencies, 50)
        comp = sum(stages.get(s, 0.0) for s in _COMPONENT_STAGES)
        per_tenant[t["name"]] = {
            "weight": t["weight"],
            "clients": t["clients"],
            "requests": tl.requests,
            "shed": tl.sheds,
            "p50_ms": round(p50_s * 1e3, 2),
            "p99_ms": round(_percentile(tl.latencies, 99) * 1e3, 2),
            "goodput_gibs": round(tl.bytes / wall / (1 << 30), 3)
            if wall > 0 else 0.0,
            # where each tenant's time went (seconds summed over the
            # timed phase, from the tenant-tagged span registry)
            "stages_s": stages,
            # component seconds over the enclosing server-span
            # seconds: >= 0.9 means the breakdown accounts for the
            # server-side latency (see _COMPONENT_STAGES comment)
            "stage_coverage": round(
                comp / stages["svc.stream"], 3)
            if stages.get("svc.stream", 0.0) > 0 else 0.0,
        }
    segments = sum(dispatch_log)
    return {
        "wall_s": round(wall, 3),
        "tenants": per_tenant,
        "requests_total": admitted,
        "shed_total": sheds,
        "mid_stream_aborts": aborts,
        "device_dispatches": len(dispatch_log),
        "segments_dispatched": segments,
        "max_batch_lanes": max(dispatch_log) if dispatch_log else 0,
        # the coalescing acceptance signal: scheduling preserved
        # cross-tenant batching (fewer dispatches than segments)
        "coalesced": bool(dispatch_log) and len(dispatch_log) < segments,
    }

