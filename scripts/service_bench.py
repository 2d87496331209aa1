"""mover-jax closed-loop multi-tenant service benchmark.

PR-1's open-loop form measured raw coalesced throughput; this is the
service-plane form: N closed-loop clients across >= 2 tenants each
drive sequential ChunkHash streams against a server running the full
admission + weighted-DRR scheduling stack (service/admission.py,
service/scheduler.py), and the report is per tenant — p50/p99
request latency, goodput, admitted/shed counts — plus plane-wide
evidence that cross-tenant coalescing survived scheduling (device
dispatches < segments submitted) and that overload was absorbed at
admission (zero mid-stream aborts). One JSON line, stamped with
bench.bench_provenance.

Modes:
  - normal          closed loop; a shed client honors the server's
                    retry-after hint and retries (the shed still counts).
  - force_breaker   trips the wired circuit breaker open first and
                    measures the admission shed path's latency instead
                    of throughput (acceptance (c): shed in < 10 ms).
  - fault schedule  VOLSYNC_SVCBENCH_FAULT_SPEC arms a seeded
                    FaultSchedule over the DEVICE DISPATCH path;
                    latency-kind faults stall dispatches (stressing the
                    credit pause and the DRR backlog). Error-kind
                    faults are refused here — a CDC stream cannot be
                    replayed mid-flight, so error injection lives in
                    tests/test_service_chaos.py at the store layer.

Fleet mode (VOLSYNC_SVCBENCH_REPLICAS >= 2): N replica servers behind
the real front door — each publishes heartbeat stamps (headroom,
backlog) through a shared bulletin board and a FleetRouter
(service/fleet.py) routes every request by advertised capacity.
Clients fail over across sheds (following the x-volsync-sibling hint)
and replica deaths; VOLSYNC_SVCBENCH_KILL=1 kills one replica mid-
phase (hard gRPC stop, heartbeat left to expire — annotated in the
flight recorder as a ``replica_kill`` trigger) and the closed loop
must finish every request on the survivors. The report adds a
per-replica breakdown plus fleet-wide p50/p99 and goodput.

Env knobs (main()):
  VOLSYNC_SVCBENCH_TENANTS    "name:weight:clients;..."  (gold:4:2;bronze:1:2)
  VOLSYNC_SVCBENCH_REQUESTS   closed-loop requests per client (default 3)
  VOLSYNC_SVCBENCH_MIB        MiB per request             (default 16)
  VOLSYNC_SVCBENCH_SEG_KIB    service segment KiB         (default 4096)
  VOLSYNC_SVCBENCH_WINDOW_MS  batcher window              (default 2)
  VOLSYNC_SVCBENCH_MAX_STREAMS  global stream cap         (default 0 = env)
  VOLSYNC_SVCBENCH_FORCE_BREAKER  1 = breaker-shed latency mode
  VOLSYNC_SVCBENCH_FAULT_SPEC/ _FAULT_SEED  seeded dispatch-latency faults
  VOLSYNC_SVCBENCH_REPLICAS   fleet mode: replica count   (default 1)
  VOLSYNC_SVCBENCH_KILL       1 = kill the last replica mid-phase
  VOLSYNC_SVCBENCH_SMOKE      1 = tiny CPU run + JSON-shape assertions
  VOLSYNC_SVCBENCH_CPU        1 = force the CPU backend (labeled)
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from volsync_tpu.envflags import (  # noqa: E402
    env_bool, env_float, env_int, env_str)

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


def parse_tenants(spec: str) -> list[dict]:
    """``name:weight:clients;...`` -> [{name, weight, clients}, ...]."""
    out = []
    for entry in filter(None, (e.strip() for e in spec.split(";"))):
        parts = entry.split(":")
        if len(parts) != 3:
            raise ValueError(
                f"tenant spec entry {entry!r} is not name:weight:clients")
        out.append({"name": parts[0], "weight": int(parts[1]),
                    "clients": int(parts[2])})
    if not out:
        raise ValueError("empty tenant spec")
    return out


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
                    # the bench) rather than routing through
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
                name=f"svcbench-{t['name']}-{gidx}"))
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
    """The importable benchmark core (the acceptance test drives it
    directly). ``tenants`` is [{name, weight, clients[, streams]}, ...];
    0 for any cap means "use the VOLSYNC_SVC_* default"."""
    from bench import bench_provenance
    from volsync_tpu.obs import (
        dump_trace, reset_spans, reset_trace, span_totals)
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
        breaker = CircuitBreaker("svcbench", threshold=1,
                                 reset_seconds=60.0)
        breaker.record_failure(TransientError("svcbench: forced open"))
        assert breaker.open_remaining() > 0

    n = mib_per_request * 1024 * 1024
    base = np.random.RandomState(7).randint(0, 256, size=(n,),
                                            dtype=np.uint8)
    # Per-client salted payloads, warm salts disjoint (128+i) from the
    # timed ones (i+1): no timed request repeats a warmed payload
    # (same invariant as bench.py's salted warm run).
    payloads = [(base ^ np.uint8(i + 1)).tobytes()
                for i in range(total_clients)]
    warm_payloads = [(base ^ np.uint8(128 + i)).tobytes()
                     for i in range(total_clients)]

    dispatch_log: list[int] = []
    srv = MoverJaxServer(
        params=params, segment_size=segment_kib * 1024,
        batch_window_ms=window_ms,
        # enough executor workers that concurrency is bounded by
        # ADMISSION, not by gRPC's thread pool queueing ahead of it
        max_workers=total_clients + 4,
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
        "metric": "service_closed_loop",
        "unit": "GiB/s",
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
                result["value"] = 0.0
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
    import jax

    result["backend"] = jax.default_backend()
    # Every BENCH_*.json self-describes where its time went (ROADMAP
    # item 1 follow-on): span summary inline, plus the flight-recorder
    # file when VOLSYNC_TRACE_DUMP names a directory to export into.
    result["provenance"] = bench_provenance(extra={"trace": {
        "spans": {name: {"count": c, "seconds": round(s, 4)}
                  for name, (c, s) in sorted(span_totals().items())},
        "trace_file": dump_trace(trigger="service_bench"),
    }})
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
            srv.admission.admit_stream("svcbench-probe")
        except AdmissionRejected as rej:
            assert rej.reason == "breaker_open", rej.reason
        else:
            raise AssertionError("breaker open but stream admitted")
        direct.append(time.perf_counter() - t0)
    t0 = time.perf_counter()
    try:
        with make_client("svcbench-probe") as c:
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
    from volsync_tpu.obs import stage_seconds_by_tenant

    tenant_stages = stage_seconds_by_tenant()
    per_tenant: dict = {}
    total_bytes = 0
    admitted = sheds = 0
    aborts: list[str] = []
    for t in tenants:
        tl: _TenantTally = tallies[t["name"]]
        total_bytes += tl.bytes
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
        "value": round(total_bytes / wall / (1 << 30), 3)
        if wall > 0 else 0.0,
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


# -- fleet mode --------------------------------------------------------------


class _ReplicaTally:
    """Per-replica closed-loop accounting (fleet mode)."""

    def __init__(self):
        self.lock = threading.Lock()
        self.latencies: list[float] = []
        self.bytes = 0
        self.requests = 0
        self.sheds = 0


def _run_fleet_clients(router, by_address, tenants, payload_for,
                       requests_per_client, tallies, rtallies,
                       failovers: list) -> float:
    """Fleet closed loop: every request is routed through the
    FleetRouter; a shed sleeps out the hint (the sibling it names gets
    the retry via the next pick), a dead replica is excluded and the
    request re-driven on a survivor. Returns phase wall time."""
    from volsync_tpu.service import MoverJaxClient, ShedError

    max_attempts = len(by_address) * 4

    def loop(tenant: str, gidx: int):
        tally: _TenantTally = tallies[tenant]
        payload = payload_for(gidx)
        conns: dict = {}
        dead: set = set()
        try:
            done = 0
            attempts = 0  # failed tries for the CURRENT request
            while done < requests_per_client:
                stamp = router.pick(exclude=dead)
                if stamp is None:
                    # stale stamps right after a kill: widen and retry
                    dead.clear()
                    time.sleep(0.01)  # lint: ignore[VL105]
                    continue
                rid, (host, port, token) = \
                    stamp.replica_id, by_address[stamp.address]
                c = conns.get(rid)
                if c is None:
                    c = conns[rid] = MoverJaxClient(host, port, token,
                                                    tenant=tenant)
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
                    with rtallies[rid].lock:
                        rtallies[rid].sheds += 1
                    # same closed-loop contract as the single-server
                    # mode; the sibling hint steers the NEXT pick via
                    # the router's headroom view
                    time.sleep(min(e.retry_after, 0.2))  # lint: ignore[VL105]
                    continue
                except Exception as e:  # noqa: BLE001 — replica death:
                    # fail the stream over to a survivor
                    dead.add(rid)
                    conns.pop(rid, None)
                    failovers.append(f"{tenant}[{gidx}] off {rid} "
                                     f"after {got} batches: {e!r}")
                    attempts += 1
                    if attempts >= max_attempts:
                        with tally.lock:
                            tally.mid_stream_aborts.append(
                                f"{tenant}[{gidx}]: failover budget "
                                f"exhausted: {e!r}")
                        done += 1
                        attempts = 0
                    continue
                attempts = 0
                dt = time.perf_counter() - t0
                with tally.lock:
                    tally.latencies.append(dt)
                    tally.bytes += len(payload)
                    tally.requests += 1
                with rtallies[rid].lock:
                    rtallies[rid].latencies.append(dt)
                    rtallies[rid].bytes += len(payload)
                    rtallies[rid].requests += 1
                done += 1
        finally:
            for c in conns.values():
                try:
                    c.close()
                except Exception as e:  # lint: ignore[VL003] — channel
                    # teardown on a possibly-killed replica; nothing to do
                    print(f"svcbench: client close: {e!r}",
                          file=sys.stderr)

    threads = []
    gidx = 0
    for t in tenants:
        for _ in range(t["clients"]):
            threads.append(threading.Thread(
                target=loop, args=(t["name"], gidx), daemon=True,
                name=f"svcbench-fleet-{t['name']}-{gidx}"))
            gidx += 1
    t0 = time.perf_counter()
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    return time.perf_counter() - t0


def run_fleet_closed_loop(*, replicas: int = 2, kill: bool = False,
                          tenants: list[dict],
                          requests_per_client: int = 3,
                          mib_per_request: int = 16,
                          segment_kib: int = 4096,
                          window_ms: float = 2.0, max_streams: int = 0,
                          params=None, warm: bool = True) -> dict:
    """Multi-replica closed loop: ``replicas`` MoverJaxServers behind a
    FleetRouter over an in-process bulletin board. ``kill=True`` kills
    the last replica once half the requests have completed; the loop
    must finish on the survivors (failover), and the kill lands in the
    flight recorder as a ``replica_kill`` trigger."""
    from bench import bench_provenance
    from volsync_tpu.objstore.store import MemObjectStore
    from volsync_tpu.obs import (
        dump_trace, record_trigger, reset_spans, reset_trace,
        span_totals)
    from volsync_tpu.ops.gearcdc import GearParams
    from volsync_tpu.repo import blobid
    from volsync_tpu.service import (
        MoverJaxClient, MoverJaxServer, TenantConfig, TenantRegistry)
    from volsync_tpu.service.fleet import FleetRouter, ReplicaHeartbeat

    assert replicas >= 2, "fleet mode needs >= 2 replicas"
    if params is None:
        params = GearParams(min_size=64 * 1024, avg_size=1024 * 1024,
                            max_size=4 * 1024 * 1024, align=4096)
    registry = TenantRegistry(
        TenantConfig(name=t["name"], weight=t["weight"],
                     max_streams=t.get("streams"))
        for t in tenants)
    total_clients = sum(t["clients"] for t in tenants)
    assert total_clients < 127, "salt space"

    board = MemObjectStore()  # the shared fleet/ stamp bulletin board
    router = FleetRouter(board, ttl_seconds=0.5)
    servers: list[MoverJaxServer] = []
    beats: list[ReplicaHeartbeat] = []
    rids: list[str] = []
    for i in range(replicas):
        rid = f"r{i:02d}"
        srv = MoverJaxServer(
            params=params, segment_size=segment_kib * 1024,
            batch_window_ms=window_ms, max_workers=total_clients + 4,
            tenants=registry, max_streams=max_streams or None,
            sibling_fn=(lambda r=rid: router.sibling_hint(r)))
        hb = ReplicaHeartbeat(
            board, rid, f"127.0.0.1:{srv.port}",
            headroom_fn=srv.admission.headroom,
            backlog_fn=(srv.scheduler.queued_total
                        if srv.scheduler is not None else None),
            beat_seconds=0.1)
        servers.append(srv)
        beats.append(hb)
        rids.append(rid)
    by_address = {f"127.0.0.1:{s.port}": ("127.0.0.1", s.port, s.token)
                  for s in servers}

    n = mib_per_request * 1024 * 1024
    base = np.random.RandomState(7).randint(0, 256, size=(n,),
                                            dtype=np.uint8)
    payloads = [(base ^ np.uint8(i + 1)).tobytes()
                for i in range(total_clients)]
    warm_payloads = [(base ^ np.uint8(128 + i)).tobytes()
                     for i in range(total_clients)]

    tallies = {t["name"]: _TenantTally() for t in tenants}
    rtallies = {rid: _ReplicaTally() for rid in rids}
    failovers: list[str] = []
    total_requests = requests_per_client * total_clients
    kill_event: dict = {}
    victim = rids[-1]
    stop_watch = threading.Event()

    def watcher(phase_t0: float):
        # kill the victim once half the timed requests have landed
        while not stop_watch.wait(0.005):
            done = sum(tl.requests for tl in tallies.values())
            if done >= max(1, total_requests // 2):
                record_trigger("replica_kill", replica=victim)
                beats[-1].stop(retire=False)
                servers[-1]._server.stop(0)
                kill_event.update({
                    "replica": victim,
                    "at_s": round(time.perf_counter() - phase_t0, 3),
                    "requests_done": done,
                })
                return

    try:
        for srv in servers:
            srv.start()
        for hb in beats:
            hb.start()
        # golden: one stream against hashlib through replica 0
        with MoverJaxClient("127.0.0.1", servers[0].port,
                            servers[0].token,
                            tenant=tenants[0]["name"]) as cl:
            g = list(cl.chunk_stream(_reader_for(warm_payloads[0])))
        s0, l0, d0 = g[0]
        assert d0 == blobid.blob_id(warm_payloads[0][s0:s0 + l0]), \
            "fleet golden check failed"
        if warm:
            _run_fleet_clients(router, by_address, tenants,
                               lambda i: warm_payloads[i], 1, tallies,
                               rtallies, failovers)
            tallies = {t["name"]: _TenantTally() for t in tenants}
            rtallies = {rid: _ReplicaTally() for rid in rids}
            failovers = []
        reset_spans()
        reset_trace()
        t0 = time.perf_counter()
        killer = None
        if kill:
            killer = threading.Thread(target=watcher, args=(t0,),
                                      daemon=True,
                                      name="svcbench-killer")
            killer.start()
        wall = _run_fleet_clients(router, by_address, tenants,
                                  lambda i: payloads[i],
                                  requests_per_client, tallies,
                                  rtallies, failovers)
        stop_watch.set()
        if killer is not None:
            killer.join(timeout=5.0)
    finally:
        stop_watch.set()
        for hb in beats:
            hb.stop(retire=True)
        for srv in servers:
            try:
                srv.stop()
            except Exception as e:  # lint: ignore[VL003] — the killed
                # replica's grpc server is already down
                print(f"svcbench: server stop: {e!r}", file=sys.stderr)

    total_bytes = sum(tl.bytes for tl in tallies.values())
    all_lat = [x for tl in tallies.values() for x in tl.latencies]
    aborts = [a for tl in tallies.values() for a in tl.mid_stream_aborts]
    per_replica = {
        rid: {
            "requests": rt.requests,
            "shed": rt.sheds,
            "p99_ms": round(_percentile(rt.latencies, 99) * 1e3, 2),
            "goodput_gibs": round(rt.bytes / wall / (1 << 30), 3)
            if wall > 0 else 0.0,
            "killed": rid == victim and bool(kill_event),
        }
        for rid, rt in rtallies.items()
    }
    result = {
        "metric": "service_fleet_closed_loop",
        "unit": "GiB/s",
        "value": round(total_bytes / wall / (1 << 30), 3)
        if wall > 0 else 0.0,
        "wall_s": round(wall, 3),
        "mib_per_request": mib_per_request,
        "segment_kib": segment_kib,
        "requests_per_client": requests_per_client,
        "replica_count": replicas,
        "replicas": per_replica,
        "fleet": {
            "p50_ms": round(_percentile(all_lat, 50) * 1e3, 2),
            "p99_ms": round(_percentile(all_lat, 99) * 1e3, 2),
            "goodput_gibs": round(total_bytes / wall / (1 << 30), 3)
            if wall > 0 else 0.0,
            "failovers": len(failovers),
        },
        "tenants": {
            t["name"]: {
                "weight": t["weight"],
                "clients": t["clients"],
                "requests": tallies[t["name"]].requests,
                "shed": tallies[t["name"]].sheds,
                "p50_ms": round(_percentile(
                    tallies[t["name"]].latencies, 50) * 1e3, 2),
                "p99_ms": round(_percentile(
                    tallies[t["name"]].latencies, 99) * 1e3, 2),
            }
            for t in tenants
        },
        "requests_total": sum(tl.requests for tl in tallies.values()),
        "shed_total": sum(tl.sheds for tl in tallies.values()),
        "mid_stream_aborts": aborts,
        "kill": kill_event or None,
    }
    import jax

    result["backend"] = jax.default_backend()
    result["provenance"] = bench_provenance(extra={"trace": {
        "spans": {name: {"count": c, "seconds": round(s, 4)}
                  for name, (c, s) in sorted(span_totals().items())},
        "trace_file": dump_trace(trigger="service_fleet_bench"),
    }})
    return result


def main() -> int:
    smoke = env_bool("VOLSYNC_SVCBENCH_SMOKE")
    if env_bool("VOLSYNC_SVCBENCH_CPU") or smoke:
        import jax

        jax.config.update("jax_platforms", "cpu")
    tenants = parse_tenants(env_str(
        "VOLSYNC_SVCBENCH_TENANTS", "gold:4:2;bronze:1:2"))
    replicas = env_int("VOLSYNC_SVCBENCH_REPLICAS", 1)
    if replicas >= 2:
        return _main_fleet(tenants, replicas, smoke)
    kwargs = dict(
        tenants=tenants,
        requests_per_client=env_int("VOLSYNC_SVCBENCH_REQUESTS", 3),
        mib_per_request=env_int("VOLSYNC_SVCBENCH_MIB", 16),
        segment_kib=env_int("VOLSYNC_SVCBENCH_SEG_KIB", 4096),
        window_ms=env_float("VOLSYNC_SVCBENCH_WINDOW_MS", 2.0),
        max_streams=env_int("VOLSYNC_SVCBENCH_MAX_STREAMS", 0),
        force_breaker=env_bool("VOLSYNC_SVCBENCH_FORCE_BREAKER"),
        fault_spec=env_str("VOLSYNC_SVCBENCH_FAULT_SPEC", "") or "",
        fault_seed=env_int("VOLSYNC_SVCBENCH_FAULT_SEED", 0),
    )
    if smoke:
        kwargs.update(requests_per_client=2, mib_per_request=2,
                      segment_kib=512)
    result = run_closed_loop(**kwargs)
    if smoke:
        # the JSON contract the Makefile smoke target pins
        for key in ("metric", "value", "unit", "tenants", "backend",
                    "provenance"):
            assert key in result, f"smoke: missing {key!r}"
        assert result["provenance"].get("git_rev"), "smoke: provenance"
        if not kwargs.get("force_breaker"):
            assert result["mid_stream_aborts"] == [], \
                result["mid_stream_aborts"]
            assert result["requests_total"] == 2 * sum(
                t["clients"] for t in tenants)
    print(json.dumps(result))
    return 0


def _main_fleet(tenants: list[dict], replicas: int, smoke: bool) -> int:
    kill = env_bool("VOLSYNC_SVCBENCH_KILL")
    kwargs = dict(
        replicas=replicas, kill=kill, tenants=tenants,
        requests_per_client=env_int("VOLSYNC_SVCBENCH_REQUESTS", 3),
        mib_per_request=env_int("VOLSYNC_SVCBENCH_MIB", 16),
        segment_kib=env_int("VOLSYNC_SVCBENCH_SEG_KIB", 4096),
        window_ms=env_float("VOLSYNC_SVCBENCH_WINDOW_MS", 2.0),
        max_streams=env_int("VOLSYNC_SVCBENCH_MAX_STREAMS", 0),
    )
    if smoke:
        kwargs.update(requests_per_client=2, mib_per_request=2,
                      segment_kib=512)
    result = run_fleet_closed_loop(**kwargs)
    if smoke:
        # the JSON contract the Makefile fleet smoke target pins
        for key in ("metric", "value", "unit", "replicas", "fleet",
                    "tenants", "backend", "provenance"):
            assert key in result, f"fleet smoke: missing {key!r}"
        assert result["metric"] == "service_fleet_closed_loop"
        assert result["provenance"].get("git_rev"), "smoke: provenance"
        assert result["replica_count"] == replicas
        assert set(result["replicas"]) == {
            f"r{i:02d}" for i in range(replicas)}
        for key in ("p50_ms", "p99_ms", "goodput_gibs", "failovers"):
            assert key in result["fleet"], f"fleet smoke: {key!r}"
        # the closed loop completed every request (failover included)
        assert result["mid_stream_aborts"] == [], \
            result["mid_stream_aborts"]
        expected = 2 * sum(t["clients"] for t in tenants)
        assert result["requests_total"] == expected
        assert sum(r["requests"]
                   for r in result["replicas"].values()) == expected
        if kill:
            assert result["kill"] and result["kill"]["replica"], \
                "fleet smoke: kill never landed"
            assert result["fleet"]["failovers"] >= 0
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
