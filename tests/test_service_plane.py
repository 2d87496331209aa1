"""The multi-tenant service plane: admission control, weighted
deficit-round-robin scheduling, credit-based streaming backpressure,
tenant-scoped auth, drain-then-stop, and the typed shed surface.

Acceptance (ISSUE 7): a closed-loop bench run with >= 2 tenants must
show (a) cross-tenant coalescing surviving the scheduler, (b) overload
absorbed at admission with admitted p99 bounded and zero mid-stream
aborts, (c) a forced-open breaker shedding at admission in < 10 ms.
All three are pinned here on the CPU backend via
tests/closed_loop.run_closed_loop.
"""

import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor

import grpc
import numpy as np
import pytest

from volsync_tpu.ops.gearcdc import GearParams
from volsync_tpu.service import (
    MoverJaxClient,
    MoverJaxServer,
    ShedError,
    TenantConfig,
    TenantRegistry,
)
from volsync_tpu.service.admission import (
    AdmissionController,
    AdmissionRejected,
)
from volsync_tpu.service.client import shed_from_rpc
from volsync_tpu.service.scheduler import SchedulerStopped, SegmentScheduler
from volsync_tpu.service.tenants import sanitize_tenant

P4K = GearParams(min_size=4096, avg_size=32768, max_size=65536, align=4096)


# -- tenancy model -----------------------------------------------------------

def test_tenant_spec_round_trip():
    reg = TenantRegistry.from_spec(
        "gold:weight=4,streams=8,queued=64,token=tk;bronze:weight=1;;")
    assert reg.names() == ["bronze", "gold"]
    gold = reg.config("gold")
    assert (gold.weight, gold.max_streams, gold.max_queued, gold.token) \
        == (4, 8, 64, "tk")
    # open registry: unknown tenants resolve to defaults
    assert reg.config("nobody") == TenantConfig(name="nobody")
    assert reg.token_for("bronze") is None


def test_tenant_spec_rejects_typos_and_bad_weight():
    with pytest.raises(ValueError, match="unknown tenant spec field"):
        TenantRegistry.from_spec("gold:wieght=4")
    with pytest.raises(ValueError, match="weight"):
        TenantConfig(name="x", weight=0)


def test_sanitize_tenant_bounds_label_values():
    assert sanitize_tenant("") == "default"
    assert sanitize_tenant("Team.a_1-x") == "Team.a_1-x"
    # hostile metadata cannot mint unbounded/unprintable label values
    assert sanitize_tenant("a\nb{evil}" + "c" * 200) == "abevil" + "c" * 58
    assert sanitize_tenant("\x00\x01") == "default"


# -- admission controller (unit) ---------------------------------------------

def _controller(**kw):
    kw.setdefault("max_streams", 3)
    kw.setdefault("tenant_streams", 2)
    kw.setdefault("max_queued", 10)
    kw.setdefault("retry_after", 0.05)
    return AdmissionController(TenantRegistry(), **kw)


def test_admission_caps_global_and_per_tenant():
    ctrl = _controller()
    t1 = ctrl.admit_stream("a")
    ctrl.admit_stream("a")
    with pytest.raises(AdmissionRejected) as ei:
        ctrl.admit_stream("a")  # tenant cap (2)
    assert ei.value.reason == "tenant_streams"
    # hints carry decorrelated jitter: within [base, 10x base]
    assert 0.05 <= ei.value.retry_after <= 0.5
    ctrl.admit_stream("b")
    with pytest.raises(AdmissionRejected) as ei:
        ctrl.admit_stream("b")  # global cap (3)
    assert ei.value.reason == "global_streams"
    ctrl.release(t1)
    ctrl.release(t1)  # idempotent: double release frees one slot only
    assert ctrl.active_streams() == 2
    ctrl.admit_stream("b")  # the freed slot is admittable again


def test_admission_tenant_override_beats_default():
    reg = TenantRegistry([TenantConfig(name="vip", max_streams=5)])
    ctrl = AdmissionController(reg, max_streams=10, tenant_streams=1,
                               max_queued=10)
    for _ in range(5):
        ctrl.admit_stream("vip")
    with pytest.raises(AdmissionRejected):
        ctrl.admit_stream("vip")


def test_admission_sheds_on_scheduler_backlog():
    depth = [0]
    ctrl = _controller(queue_depth_fn=lambda: depth[0])
    ctrl.admit_stream("a")
    depth[0] = 10
    with pytest.raises(AdmissionRejected) as ei:
        ctrl.admit_stream("a")
    assert ei.value.reason == "overload"


def test_admission_sheds_while_breaker_open_with_cooldown_hint():
    from volsync_tpu.resilience import CircuitBreaker, TransientError

    t = [100.0]
    brk = CircuitBreaker("svc-test", threshold=1, reset_seconds=30.0,
                         clock=lambda: t[0])
    brk.record_failure(TransientError("boom"))
    ctrl = _controller(breaker=brk, clock=lambda: t[0])
    t[0] += 10.0
    with pytest.raises(AdmissionRejected) as ei:
        ctrl.admit_stream("a")
    assert ei.value.reason == "breaker_open"
    # the hint is the REMAINING cooldown, not a canned constant
    assert ei.value.retry_after == pytest.approx(20.0)
    t[0] += 25.0  # past reset: the probe is due, admission reopens
    ctrl.release(ctrl.admit_stream("a"))


def test_admission_drain_then_idle():
    ctrl = _controller()
    ticket = ctrl.admit_stream("a")
    ctrl.begin_drain()
    with pytest.raises(AdmissionRejected) as ei:
        ctrl.admit_stream("b")
    assert ei.value.reason == "draining"
    assert not ctrl.wait_idle(0.05)
    ctrl.release(ticket)
    assert ctrl.wait_idle(1.0)


def test_admission_drain_release_race_never_loses_wakeup():
    """Regression (ISSUE 11): ``begin_drain`` racing the ``release``
    of the LAST ticket must always wake ``wait_idle`` — both paths
    set the idle Event under the lock, so no interleaving can leave a
    waiter hanging on an idle controller. Hammered across many
    iterations with begin_drain and release fired concurrently."""
    for i in range(200):
        ctrl = _controller()
        ticket = ctrl.admit_stream("a")
        start = threading.Barrier(3)
        woke = []

        def drainer():
            start.wait(timeout=5)
            ctrl.begin_drain()

        def releaser():
            start.wait(timeout=5)
            ctrl.release(ticket)

        def waiter():
            start.wait(timeout=5)
            woke.append(ctrl.wait_idle(5.0))

        threads = [threading.Thread(target=f)
                   for f in (drainer, releaser, waiter)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=10)
        assert woke == [True], f"lost wakeup on iteration {i}"
        assert ctrl.active_streams() == 0


# -- scheduler (unit, driven via service_round) ------------------------------

class _FakeBatcher:
    """Records submission order; resolves futures on demand."""

    _depth = 1
    _max_batch = 16

    def __init__(self):
        self.calls = []

    def submit_async(self, data, length, eof):
        f = Future()
        self.calls.append((data, length, eof, f))
        return f


def _drain_rounds(sched, limit=50):
    for _ in range(limit):
        if not sched.service_round():
            return


def test_wdrr_shares_follow_weights():
    """Equal backlogs, weights 3:1 -> dispatch order interleaves about
    3 gold segments per bronze one (classic DRR with equal costs)."""
    reg = TenantRegistry([TenantConfig(name="gold", weight=3),
                          TenantConfig(name="bronze", weight=1)])
    fb = _FakeBatcher()
    sched = SegmentScheduler(fb, reg, quantum=100, tenant_queued=64,
                             dispatch_window=1000, start=False)
    for i in range(12):
        sched.submit("gold", b"g%d" % i, 100, False)
        sched.submit("bronze", b"b%d" % i, 100, False)
    _drain_rounds(sched)
    order = [d[:1] for d, _, _, _ in fb.calls]
    assert len(fb.calls) == 24
    # after gold's backlog drains, the first 16 dispatches split 12:4
    head = order[:16]
    assert head.count(b"g") == 12 and head.count(b"b") == 4
    # within a tenant, FIFO order is preserved (CDC segments are
    # sequential within a stream — reordering would corrupt the tail)
    golds = [d for d, _, _, _ in fb.calls if d.startswith(b"g")]
    assert golds == sorted(golds, key=lambda s: int(s[1:]))
    sched.stop()


def test_wdrr_large_segment_waits_for_deficit():
    """A segment costlier than one round's quantum dispatches only
    after enough rounds accrue deficit — no starvation, no bypass."""
    reg = TenantRegistry()
    fb = _FakeBatcher()
    sched = SegmentScheduler(fb, reg, quantum=100, tenant_queued=8,
                             dispatch_window=100, start=False)
    sched.submit("t", b"big", 250, False)
    assert sched.service_round() and not fb.calls   # deficit 100
    assert sched.service_round() and not fb.calls   # deficit 200
    assert sched.service_round() and len(fb.calls) == 1  # 300 covers it
    assert not sched.service_round()
    sched.stop()


def test_scheduler_credit_pause_blocks_submit():
    """The credit-based pause: a tenant at its queue bound blocks in
    submit() until the scheduler drains a slot — the mechanism that
    stops a gRPC handler from pulling more request bytes."""
    reg = TenantRegistry()
    fb = _FakeBatcher()
    sched = SegmentScheduler(fb, reg, quantum=10**6, tenant_queued=2,
                             dispatch_window=100, start=False)
    sched.submit("t", b"1", 10, False)
    sched.submit("t", b"2", 10, False)
    entered = threading.Event()
    unblocked = threading.Event()

    def third():
        entered.set()
        sched.submit("t", b"3", 10, False)
        unblocked.set()

    th = threading.Thread(target=third, name="svc-test-blocked-submit")
    th.start()
    assert entered.wait(2.0)
    assert not unblocked.wait(0.3), "submit should block at the bound"
    _drain_rounds(sched)  # drains the queue, releasing credits
    assert unblocked.wait(2.0), "drain must unblock the producer"
    th.join(timeout=5.0)
    _drain_rounds(sched)
    assert len(fb.calls) == 3
    sched.stop()


def test_scheduler_stop_fails_stranded_work():
    reg = TenantRegistry()
    fb = _FakeBatcher()
    sched = SegmentScheduler(fb, reg, quantum=100, tenant_queued=8,
                             dispatch_window=100, start=False)
    f = sched.submit("t", b"x", 10, False)
    sched.stop()
    with pytest.raises(SchedulerStopped):
        f.result(timeout=1.0)
    with pytest.raises(SchedulerStopped):
        sched.submit("t", b"y", 10, False)


def test_scheduler_chains_batcher_results():
    reg = TenantRegistry()
    fb = _FakeBatcher()
    sched = SegmentScheduler(fb, reg, quantum=100, tenant_queued=8,
                             dispatch_window=100, start=False)
    f = sched.submit("t", b"x", 10, True)
    _drain_rounds(sched)
    fb.calls[0][3].set_result(([(0, 10, "d")], 10))
    assert f.result(timeout=1.0) == ([(0, 10, "d")], 10)
    assert sched.dispatched_total == 1
    sched.stop()


# -- auth (tenant-scoped, per-cardinality deny) ------------------------------

@pytest.fixture()
def secured_server():
    reg = TenantRegistry([TenantConfig(name="sec", token="tenant-secret")])
    with MoverJaxServer(params=P4K, segment_size=128 * 1024,
                        token="service-secret", tenants=reg) as srv:
        yield srv


def test_stream_denied_with_unauthenticated(secured_server):
    """A bad token on the STREAMING method must draw UNAUTHENTICATED —
    the deny handler must match the method's cardinality (a unary deny
    on a stream call surfaces as an opaque internal error)."""
    srv = secured_server
    with MoverJaxClient("127.0.0.1", srv.port, "wrong") as c:
        with pytest.raises(grpc.RpcError) as ei:
            c.chunk_bytes(b"z" * 8192)
    assert ei.value.code() == grpc.StatusCode.UNAUTHENTICATED


def test_tenant_scoped_token(secured_server):
    srv = secured_server
    # the tenant's own token opens its door...
    with MoverJaxClient("127.0.0.1", srv.port, "tenant-secret",
                        tenant="sec") as c:
        assert c.info().align == P4K.align
    # ...the shared service token no longer does for THAT tenant...
    with MoverJaxClient("127.0.0.1", srv.port, "service-secret",
                        tenant="sec") as c:
        with pytest.raises(grpc.RpcError) as ei:
            c.info()
    assert ei.value.code() == grpc.StatusCode.UNAUTHENTICATED
    # ...and untokened tenants still use the service token
    with MoverJaxClient("127.0.0.1", srv.port, "service-secret",
                        tenant="other") as c:
        assert c.info().align == P4K.align


# -- shed surface (client) ---------------------------------------------------

class _FakeRpcError(grpc.RpcError):
    def __init__(self, code, trailing=(), details_text="shed"):
        self._code = code
        self._trailing = trailing
        self._details = details_text

    def code(self):
        return self._code

    def trailing_metadata(self):
        return self._trailing

    def details(self):
        return self._details


def test_shed_from_rpc_classification():
    from volsync_tpu.resilience import ThrottleError, classify
    from volsync_tpu.service.server import RETRY_AFTER_METADATA_KEY

    err = _FakeRpcError(grpc.StatusCode.RESOURCE_EXHAUSTED,
                        ((RETRY_AFTER_METADATA_KEY, "250"),))
    shed = shed_from_rpc(err)
    assert isinstance(shed, ShedError)
    assert isinstance(shed, ThrottleError)   # the typed contract
    assert classify(shed)                    # retryable backpressure
    assert shed.retry_after == pytest.approx(0.25)
    # missing/garbled hints fall back, other codes pass through as None
    assert shed_from_rpc(_FakeRpcError(
        grpc.StatusCode.RESOURCE_EXHAUSTED)).retry_after == \
        pytest.approx(0.1)
    assert shed_from_rpc(_FakeRpcError(
        grpc.StatusCode.RESOURCE_EXHAUSTED,
        ((RETRY_AFTER_METADATA_KEY, "bogus"),))).retry_after == \
        pytest.approx(0.1)
    assert shed_from_rpc(
        _FakeRpcError(grpc.StatusCode.UNAVAILABLE)) is None


def test_client_surfaces_shed_as_typed_error():
    """End-to-end shed: server at max_streams=1, one stream parked in
    flight -> the second stream draws ShedError (not a raw RpcError)
    with the server's retry-after hint attached."""
    with MoverJaxServer(params=P4K, segment_size=128 * 1024,
                        max_streams=1, batch_window_ms=0.0) as srv:
        hold = threading.Event()
        started = threading.Event()

        def parked():
            def reader(n):
                if not started.is_set():
                    started.set()
                    return b"p" * 8192
                hold.wait(10.0)
                return b""

            with MoverJaxClient("127.0.0.1", srv.port, srv.token) as c:
                return list(c.chunk_stream(reader))

        with ThreadPoolExecutor(1) as pool:
            fut = pool.submit(parked)
            assert started.wait(5.0)
            deadline = time.monotonic() + 5.0
            while srv.admission.active_streams() == 0:
                assert time.monotonic() < deadline
                time.sleep(0.01)
            with MoverJaxClient("127.0.0.1", srv.port, srv.token) as c:
                with pytest.raises(ShedError) as ei:
                    c.chunk_bytes(b"q" * 8192)
            assert ei.value.retry_after > 0
            hold.set()
            assert fut.result(timeout=10.0)  # the parked stream finishes


# -- the handler pool: eight by default, apart from the batch limit ----------

class _HeldStreams:
    """N ChunkStreams parked in their handlers: each sends one frame
    and then holds its request iterator open until ``release``."""

    def __init__(self, srv, tenants: list[str]):
        self.hold = threading.Event()
        self._conns = [MoverJaxClient("127.0.0.1", srv.port, srv.token,
                                      tenant=t) for t in tenants]
        self._pool = ThreadPoolExecutor(len(tenants))
        self._futs = [self._pool.submit(self._parked, c)
                      for c in self._conns]

    def _parked(self, conn):
        sent = []

        def reader(n):
            if not sent:
                sent.append(1)
                return b"p" * 8192
            self.hold.wait(60.0)
            return b""

        return list(conn.chunk_stream(reader))

    def release(self) -> list:
        self.hold.set()
        try:
            return [f.result(timeout=60.0) for f in self._futs]
        finally:
            self._pool.shutdown()
            for c in self._conns:
                c.close()


def _wait_for(condition, what):
    deadline = time.monotonic() + 60.0
    while not condition():
        assert time.monotonic() < deadline, what()
        time.sleep(0.01)


#: held streams (tenant of each), the tenant of one more stream, and
#: the reason admission gives for refusing it (None: it is served)
HANDLER_POOL_CASES = {
    # more than the batch limit of eight, under both admission limits:
    # every one is IN its handler, none waits for a thread
    "twelve-held-all-in-their-handlers": (
        ["a"] * 6 + ["b"] * 6, "c", None),
    "over-tenant-streams": (["a"] * 16, "a", "tenant_streams"),
    "over-max-streams": (
        [t for t in "abcd" for _ in range(16)], "e", "global_streams"),
}


@pytest.mark.parametrize("held,extra,reason", HANDLER_POOL_CASES.values(),
                         ids=list(HANDLER_POOL_CASES))
def test_a_pool_as_wide_as_admission_has_a_handler_for_every_stream(
        held, extra, reason):
    """``handlers=72``: a thread for each of the 64 streams admission
    admits (16 a tenant) and eight to spare, so N > 8 held streams are
    all counted by admission at once and none waited for a thread; the
    stream over a limit reaches ``admit_stream`` and is refused
    RESOURCE_EXHAUSTED with its retry-after trailer (it does not queue
    unseen behind the held ones), and ``Info`` still answers."""
    from volsync_tpu import obs
    from volsync_tpu.service.server import RETRY_AFTER_METADATA_KEY

    obs.reset_spans()
    with MoverJaxServer(params=P4K, segment_size=128 * 1024,
                        handlers=72) as srv:
        adm = srv.admission
        assert (adm.max_streams, adm.tenant_streams) == (64, 16)
        streams = _HeldStreams(srv, held)
        try:
            _wait_for(lambda: adm.active_streams() >= len(held),
                      adm.active_streams)
            assert adm.active_streams() == len(held)
            count, total = obs.span_totals()["svc.accept_wait"]
            assert count == len(held)
            # a thread was free (or started) for each: with a pool of
            # eight the ninth would still be waiting, and never counted
            assert total < 0.25 * len(held)
            with MoverJaxClient("127.0.0.1", srv.port, srv.token,
                                tenant=extra) as c:
                assert c.info().align == P4K.align
                if reason is None:
                    assert c.chunk_bytes(b"q" * 8192)
                else:
                    t0 = time.monotonic()
                    with pytest.raises(ShedError) as ei:
                        c.chunk_bytes(b"q" * 8192)
                    assert time.monotonic() - t0 < 10.0
                    assert reason in str(ei.value)
                    rpc = ei.value.__cause__
                    assert rpc.code() == grpc.StatusCode.RESOURCE_EXHAUSTED
                    trailer = dict(rpc.trailing_metadata())
                    assert int(trailer[RETRY_AFTER_METADATA_KEY]) >= 1
                    assert ei.value.retry_after > 0
                assert c.info().align == P4K.align
            assert adm.active_streams() == len(held)
        finally:
            results = streams.release()
        assert all(results) and len(results) == len(held)
    assert adm.active_streams() == 0


def test_a_default_server_serves_eight_streams_and_queues_the_ninth():
    """The default pool is eight whatever the batch limit
    (``max_workers``) is: of twelve held streams eight are in
    their handlers, which is all admission ever counts, and four wait
    in the executor's queue (``svc.accept_wait`` still open) until a
    thread is free; every one is served in the end."""
    from volsync_tpu import obs

    obs.reset_spans()
    with MoverJaxServer(params=P4K, segment_size=128 * 1024,
                        max_workers=4) as srv:
        assert srv.handlers == 8
        streams = _HeldStreams(srv, ["a"] * 6 + ["b"] * 6)
        try:
            _wait_for(lambda: srv.admission.active_streams() >= 8,
                      srv.admission.active_streams)
            time.sleep(0.3)
            assert srv.admission.active_streams() == 8
            assert obs.span_totals()["svc.accept_wait"][0] == 8
        finally:
            results = streams.release()
        assert all(results) and len(results) == 12
        assert obs.span_totals()["svc.accept_wait"][0] == 12
    assert srv.admission.active_streams() == 0


@pytest.mark.parametrize("server_kwargs", [{}, {"handlers": 72}],
                         ids=["default-built", "a-handler-a-stream"])
def test_the_warm_plan_covers_what_the_server_dispatches(monkeypatch,
                                                         server_kwargs):
    """16 concurrent streams of two bucket sizes against a
    default-built ``MoverJaxServer()``, and against one with twice the
    batch limit in their handlers at once (``handlers=72``): no
    dispatch coalesces more than eight lanes, and every program it
    ran, (lanes padded to a power of two, bucket), is one that
    ``benchmark/warm.py``'s ``stream_plan`` loads for those sizes
    before the benchmark's window."""
    from benchmark import warm
    from volsync_tpu import obs
    from volsync_tpu.ops.segment import BatchedSegmentHasher

    sizes = [600_000, 1_500_000]  # buckets of 1 MiB and 2 MiB
    ran = []
    real = BatchedSegmentHasher._hash_bucket

    def hash_bucket(self, P, items):
        ran.append((warm._pow2ceil(len(items)), P))
        return real(self, P, items)

    monkeypatch.setattr(BatchedSegmentHasher, "_hash_bucket", hash_bucket)
    rs = np.random.RandomState(41)
    payloads = [rs.bytes(sizes[i % 2] + 4096 * i) for i in range(16)]
    obs.reset_trace()
    with MoverJaxServer(**server_kwargs) as srv:
        def run(i):
            with MoverJaxClient("127.0.0.1", srv.port, srv.token,
                                tenant=f"t{i % 4}", timeout=600.0) as c:
                return [c.chunk_bytes(payloads[i]) for _ in range(2)]

        with ThreadPoolExecutor(16) as pool:
            results = list(pool.map(run, range(16)))
    for data, (first, second) in zip(payloads, results):
        assert first == second
        assert sum(length for _, length, _ in first) == len(data)
    lanes = [e["args"]["lanes"] for e in obs.trace_events()
             if e["ph"] == "X" and e["name"] == "ops.batch_dispatch"]
    assert lanes and max(lanes) <= 8
    plan = set(map(tuple, warm.stream_plan(
        [len(p) for p in payloads], server_kwargs, 16)))
    assert ran and set(ran) <= plan, sorted(set(ran) - plan)


# -- byte identity through the scheduled path --------------------------------

def test_scheduled_streams_chunk_bit_identically(rng):
    """Tenant-tagged streams through admission + WDRR + microbatcher
    chunk exactly like a local scan — scheduling must be invisible to
    the CDC contract."""
    from volsync_tpu.engine.chunker import DeviceChunkHasher

    reg = TenantRegistry([TenantConfig(name="gold", weight=4),
                          TenantConfig(name="bronze", weight=1)])
    payloads = [rng.bytes(300_000 + 17 * i) for i in range(4)]
    with MoverJaxServer(params=P4K, segment_size=128 * 1024,
                        batch_window_ms=10.0, tenants=reg) as srv:
        assert srv.scheduler is not None

        def run(i):
            tenant = "gold" if i % 2 == 0 else "bronze"
            with MoverJaxClient("127.0.0.1", srv.port, srv.token,
                                tenant=tenant) as c:
                return c.chunk_bytes(payloads[i])

        with ThreadPoolExecutor(4) as pool:
            results = list(pool.map(run, range(4)))
    local = DeviceChunkHasher(P4K)
    for data, got in zip(payloads, results):
        assert got == local.process(np.frombuffer(data, np.uint8),
                                    eof=True)
        assert srv.admission.active_streams() == 0


# -- drain-then-stop ---------------------------------------------------------

def test_stop_drains_inflight_stream_to_completion(rng):
    """stop() called mid-stream: the in-flight stream COMPLETES with
    correct chunks (drain waits), while a stream arriving after drain
    began is refused with UNAVAILABLE."""
    from volsync_tpu.engine.chunker import DeviceChunkHasher

    data = rng.bytes(400_000)
    srv = MoverJaxServer(params=P4K, segment_size=128 * 1024,
                         batch_window_ms=2.0).start()
    reading = threading.Event()
    result: dict = {}

    def slow_reader():
        pos = [0]

        def read(n):
            reading.set()
            time.sleep(0.05)  # stretch the stream across stop()
            piece = data[pos[0]: pos[0] + min(n, 65536)]
            pos[0] += len(piece)
            return piece

        return read

    def run_stream():
        with MoverJaxClient("127.0.0.1", srv.port, srv.token) as c:
            result["chunks"] = list(c.chunk_stream(slow_reader()))

    th = threading.Thread(target=run_stream, name="svc-test-drain-stream")
    th.start()
    assert reading.wait(5.0)
    # the client pulls its request iterator before the server has
    # necessarily ADMITTED the stream — wait for the ticket, or the
    # drain window would see an idle server and stop under the stream
    admit_deadline = time.monotonic() + 5.0
    while srv.admission.active_streams() == 0:
        assert time.monotonic() < admit_deadline
        time.sleep(0.01)
    stopper = threading.Thread(target=lambda: srv.stop(drain=15.0),
                               name="svc-test-stopper")
    stopper.start()
    # late arrival during the drain window: shed, not queued
    deadline = time.monotonic() + 5.0
    while True:
        try:
            srv.admission.admit_stream("late")
        except AdmissionRejected as rej:
            assert rej.reason == "draining"
            break
        else:
            pytest.fail("admission still open after stop() began") \
                if time.monotonic() > deadline else time.sleep(0.01)
    th.join(timeout=30.0)
    stopper.join(timeout=30.0)
    assert not th.is_alive() and not stopper.is_alive()
    local = DeviceChunkHasher(P4K).process(
        np.frombuffer(data, np.uint8), eof=True)
    assert result["chunks"] == local


def test_stop_aborts_stuck_stream_cleanly():
    """A stream that never finishes cannot wedge stop(): past the drain
    window it is cut off with a clean terminal status (UNAVAILABLE from
    the scheduler teardown, or CANCELLED from the transport) — never a
    hang, never a half-written batch."""
    srv = MoverJaxServer(params=P4K, segment_size=128 * 1024).start()
    hold = threading.Event()
    started = threading.Event()
    outcome: dict = {}

    def stuck():
        def read(n):
            if not started.is_set():
                started.set()
                return b"s" * 8192
            hold.wait(20.0)
            return b""

        try:
            with MoverJaxClient("127.0.0.1", srv.port, srv.token) as c:
                outcome["chunks"] = list(c.chunk_stream(read))
        except grpc.RpcError as e:
            outcome["code"] = e.code()

    th = threading.Thread(target=stuck, name="svc-test-stuck-stream")
    th.start()
    assert started.wait(5.0)
    deadline = time.monotonic() + 5.0
    while srv.admission.active_streams() == 0:
        assert time.monotonic() < deadline
        time.sleep(0.01)
    t0 = time.monotonic()
    srv.stop(grace=0.5, drain=0.3)
    assert time.monotonic() - t0 < 15.0, "stop() must be bounded"
    hold.set()
    th.join(timeout=10.0)
    assert not th.is_alive()
    assert outcome.get("code") in (grpc.StatusCode.UNAVAILABLE,
                                   grpc.StatusCode.CANCELLED), outcome


# -- the ISSUE 7 acceptance criteria (closed-loop, CPU) ----------------------

def _bench_tenants():
    return [{"name": "gold", "weight": 4, "clients": 3},
            {"name": "bronze", "weight": 1, "clients": 3}]


def test_acceptance_coalescing_and_overload():
    """(a) cross-tenant coalescing survives scheduling; (b) under
    2x overload the excess is shed AT ADMISSION (zero mid-stream
    aborts) while admitted requests' p99 stays bounded."""
    from closed_loop import run_closed_loop

    # (a): 6 clients across 2 tenants, wide batch window, multiple
    # segments per stream -> fewer device dispatches than segments
    res = run_closed_loop(
        tenants=_bench_tenants(), requests_per_client=2,
        mib_per_request=1, segment_kib=128, window_ms=25.0,
        params=P4K, warm=False)
    assert res["mid_stream_aborts"] == []
    assert res["requests_total"] == 12
    assert res["coalesced"], (res["device_dispatches"],
                              res["segments_dispatched"])
    assert res["device_dispatches"] < res["segments_dispatched"]
    for name in ("gold", "bronze"):
        assert res["tenants"][name]["requests"] > 0

    # (b): 6 closed-loop clients against a 3-stream cap = 2x overload.
    # Excess sheds at admission (typed, counted), admitted work all
    # completes, and p99 stays within a bound far below what queuing
    # the overload would produce.
    res = run_closed_loop(
        tenants=_bench_tenants(), requests_per_client=2,
        mib_per_request=1, segment_kib=128, window_ms=2.0,
        max_streams=3, params=P4K, warm=False)
    assert res["mid_stream_aborts"] == [], res["mid_stream_aborts"]
    assert res["shed_total"] > 0, "2x overload must shed at admission"
    assert res["requests_total"] == 12  # every request retries to done
    for name in ("gold", "bronze"):
        p99 = res["tenants"][name]["p99_ms"]
        assert 0 < p99 < 10_000, (name, p99)


def test_acceptance_breaker_sheds_in_under_10ms():
    """(c) breaker forced open -> requests shed at admission in <10 ms
    (direct-path p99; the RPC-visible path gets a generous CI bound)."""
    from closed_loop import run_closed_loop

    res = run_closed_loop(tenants=_bench_tenants(), force_breaker=True,
                          mib_per_request=1, params=P4K)
    brk = res["breaker"]
    assert brk["direct_shed_p99_ms"] < 10.0, brk
    assert brk["rpc_shed_ms"] < 1_000.0, brk  # CI-tolerant RPC bound
    assert brk["retry_after_s"] > 0
