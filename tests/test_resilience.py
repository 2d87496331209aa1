"""Resilience layer unit tests: error classification, RetryPolicy
(attempt accounting, jitter bounds, deadline, metrics), CircuitBreaker
state machine, ResilientStore wrapping semantics, and the deterministic
fault-injection wrapper (objstore/faultstore.py)."""

import random

import pytest

from volsync_tpu.metrics import GLOBAL as GLOBAL_METRICS
from volsync_tpu.objstore.faultstore import (
    FaultInjected,
    FaultSchedule,
    FaultSpec,
    FaultStore,
    InjectedCrash,
    InjectedHang,
    InjectedPartition,
    InjectedThrottle,
    default_specs,
    maybe_wrap,
    parse_spec,
)
from volsync_tpu.objstore.store import MemObjectStore, NoSuchKey, unwrap
from volsync_tpu.resilience import (
    CircuitBreaker,
    CircuitOpen,
    DeadlineExceeded,
    ResilientStore,
    RetryPolicy,
    ThrottleError,
    TransientError,
    breaker_for,
    classify,
    decorrelated_jitter,
)


def _policy(**kw):
    kw.setdefault("sleep_fn", lambda s: None)
    kw.setdefault("rng", random.Random(42))
    return RetryPolicy(site="test", **kw)


def _counter_value(site, outcome):
    return GLOBAL_METRICS.retry_attempts.labels(
        site=site, outcome=outcome)._value.get()


# -- classification ---------------------------------------------------------

class _HttpStatus(Exception):
    def __init__(self, status):
        self.status = status


class _GrpcLike(Exception):
    class _Code:
        def __init__(self, name):
            self.name = name

    def __init__(self, name):
        self._name = name

    def code(self):
        return self._Code(self._name)


@pytest.mark.parametrize("exc,want", [
    (TransientError("x"), True),
    (ThrottleError("x"), True),
    (NoSuchKey("k"), False),          # KeyError: a fact, not a fault
    (ValueError("x"), False),
    (TypeError("x"), False),
    (_HttpStatus(503), True),
    (_HttpStatus(429), True),
    (_HttpStatus(404), False),
    (_HttpStatus(501), False),        # permanent 5xx stays fatal
    (_GrpcLike("UNAVAILABLE"), True),
    (_GrpcLike("RESOURCE_EXHAUSTED"), True),
    (_GrpcLike("UNAUTHENTICATED"), False),
    (_GrpcLike("NOT_FOUND"), False),
    (ConnectionResetError("x"), True),
    (TimeoutError("x"), True),
    (FileNotFoundError("x"), False),
    (PermissionError("x"), False),
    (OSError("reset"), True),         # generic transport OSError
    (RuntimeError("x"), False),
    (Exception("x"), False),
])
def test_classify(exc, want):
    assert classify(exc) is want


# -- RetryPolicy ------------------------------------------------------------

def test_retry_then_success():
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise TransientError("boom")
        return "ok"

    p = _policy(max_attempts=5)
    assert p.call(flaky) == "ok"
    assert len(calls) == 3
    assert p.last_attempts == 3


def test_fatal_raises_immediately():
    calls = []

    def fatal():
        calls.append(1)
        raise ValueError("bad request")

    with pytest.raises(ValueError):
        _policy(max_attempts=5).call(fatal)
    assert len(calls) == 1


def test_attempts_exhausted_raises_last():
    p = _policy(max_attempts=3)

    def always():
        raise TransientError("still down")

    with pytest.raises(TransientError):
        p.call(always)
    assert p.last_attempts == 3


def test_retryable_fatal_tuples_override_classifier():
    # RuntimeError is fatal by default; the retryable tuple opts it in
    p = _policy(max_attempts=2, retryable=(RuntimeError,))
    calls = []

    def f():
        calls.append(1)
        raise RuntimeError("opted in")

    with pytest.raises(RuntimeError):
        p.call(f)
    assert len(calls) == 2
    # ...and the fatal tuple wins over both
    p2 = _policy(max_attempts=5, retryable=(RuntimeError,),
                 fatal=(RuntimeError,))
    calls.clear()
    with pytest.raises(RuntimeError):
        p2.call(f)
    assert len(calls) == 1


def test_deadline_exceeded():
    # deadline 0: the first backoff would overrun it
    p = _policy(max_attempts=10, deadline=0.0)
    with pytest.raises(DeadlineExceeded) as ei:
        p.call(lambda: (_ for _ in ()).throw(TransientError("x")))
    assert isinstance(ei.value.last, TransientError)


def test_backoff_sleeps_recorded_and_bounded():
    slept = []
    p = RetryPolicy(site="test", max_attempts=4, base_delay=0.05,
                    max_delay=0.2, sleep_fn=slept.append,
                    rng=random.Random(7))
    with pytest.raises(TransientError):
        p.call(lambda: (_ for _ in ()).throw(TransientError("x")))
    assert len(slept) == 3  # between 4 attempts
    assert all(0.05 <= s <= 0.2 for s in slept)


def test_decorrelated_jitter_bounds():
    rng = random.Random(3)
    prev = 0.05
    for _ in range(200):
        nxt = decorrelated_jitter(prev, 0.05, 1.0, rng)
        assert 0.05 <= nxt <= 1.0
        prev = nxt


def test_backoffs_generator_capped():
    p = _policy(base_delay=0.1, max_delay=0.5)
    seq = [next(d) for d in [p.backoffs()] for _ in range(20)]
    assert all(0.1 <= s <= 0.5 for s in seq)


def test_retry_metrics_counted():
    before_ok = _counter_value("metrics-site", "ok")
    before_retried = _counter_value("metrics-site", "retried")
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 2:
            raise TransientError("x")
        return 1

    p = RetryPolicy(site="metrics-site", max_attempts=3,
                    sleep_fn=lambda s: None)
    p.call(flaky)
    assert _counter_value("metrics-site", "retried") == before_retried + 1
    assert _counter_value("metrics-site", "ok") == before_ok + 1


def test_retry_metrics_exhausted_outcome():
    """The final failed attempt of a retryable error counts as
    'exhausted', not 'retried' — budget exhaustion must be
    distinguishable from a retry that later succeeded."""
    site = "metrics-exhaust"
    before_retried = _counter_value(site, "retried")
    before_exhausted = _counter_value(site, "exhausted")
    p = RetryPolicy(site=site, max_attempts=3, sleep_fn=lambda s: None)
    with pytest.raises(TransientError):
        p.call(lambda: (_ for _ in ()).throw(TransientError("x")))
    assert _counter_value(site, "retried") == before_retried + 2
    assert _counter_value(site, "exhausted") == before_exhausted + 1


def test_from_env_overrides(monkeypatch):
    monkeypatch.setenv("VOLSYNC_RETRY_ATTEMPTS", "7")
    p = RetryPolicy.from_env("envsite")
    assert p.max_attempts == 7
    p2 = RetryPolicy.from_env("envsite", max_attempts=2)
    assert p2.max_attempts == 2


# -- CircuitBreaker ---------------------------------------------------------

class _Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def test_breaker_trip_cooldown_halfopen_close():
    clk = _Clock()
    br = CircuitBreaker("be", threshold=2, reset_seconds=10.0, clock=clk)
    assert br.state == "closed"
    br.record_failure(TransientError("x"))
    assert br.state == "closed"
    br.record_failure(TransientError("x"))
    assert br.state == "open"
    with pytest.raises(CircuitOpen):
        br.before_call()
    # cooldown elapses -> half-open admits exactly one probe
    clk.t += 11.0
    br.before_call()  # the probe slot
    with pytest.raises(CircuitOpen):
        br.before_call()  # second caller shunted while probing
    br.record_success()
    assert br.state == "closed"
    br.before_call()  # closed again: free passage


def test_breaker_halfopen_failure_reopens():
    clk = _Clock()
    br = CircuitBreaker("be2", threshold=1, reset_seconds=5.0, clock=clk)
    br.record_failure(TransientError("x"))
    assert br.state == "open"
    clk.t += 6.0
    br.before_call()
    br.record_failure(TransientError("x"))
    assert br.state == "open"
    with pytest.raises(CircuitOpen):
        br.before_call()  # new cooldown running


def test_breaker_ignores_fatal_errors():
    br = CircuitBreaker("be3", threshold=1, reset_seconds=5.0)
    br.record_failure(ValueError("caller bug"))
    br.record_failure(NoSuchKey("k"))
    assert br.state == "closed"


def test_breaker_halfopen_fatal_failure_releases_probe_slot():
    """A probe that dies on a FATAL error (NoSuchKey) must still free
    the probe slot and restart the cooldown — the regression wedged the
    breaker half-open with the slot taken, failing every call forever."""
    clk = _Clock()
    br = CircuitBreaker("be5", threshold=1, reset_seconds=5.0, clock=clk)
    br.record_failure(TransientError("x"))
    assert br.state == "open"
    clk.t += 6.0
    br.before_call()  # probe admitted
    br.record_failure(NoSuchKey("k"))  # fatal probe failure
    assert br.state == "open"  # new cooldown, slot released
    clk.t += 6.0
    br.before_call()  # a NEW probe gets through — breaker not wedged
    br.record_success()
    assert br.state == "closed"


def test_breaker_registry_shared_and_reset():
    a = breaker_for("same-backend")
    b = breaker_for("same-backend")
    assert a is b
    from volsync_tpu.resilience import reset_breakers

    reset_breakers()
    assert breaker_for("same-backend") is not a


def test_policy_with_breaker_fails_fast_while_open():
    clk = _Clock()
    br = CircuitBreaker("be4", threshold=1, reset_seconds=60.0, clock=clk)
    p = _policy(max_attempts=2, breaker=br)
    with pytest.raises(TransientError):
        p.call(lambda: (_ for _ in ()).throw(TransientError("x")))
    assert br.state == "open"
    # while open the callable is never invoked
    calls = []
    with pytest.raises(CircuitOpen):
        _policy(max_attempts=1, breaker=br).call(
            lambda: calls.append(1))
    assert calls == []


# -- ResilientStore ---------------------------------------------------------

class _FlakyStore:
    """MemObjectStore that fails the first N calls of selected ops."""

    def __init__(self, fail_first=0, ops=("put", "get")):
        self.inner = MemObjectStore()
        self.failures_left = {op: fail_first for op in ops}
        self.calls = []

    def __getattr__(self, name):
        target = getattr(self.inner, name)

        def op(*a, **kw):
            self.calls.append(name)
            if self.failures_left.get(name, 0) > 0:
                self.failures_left[name] -= 1
                raise TransientError(f"flaky {name}")
            return target(*a, **kw)

        return op


def _rstore(inner, **kw):
    kw.setdefault("policy", _policy(max_attempts=5))
    kw.setdefault("breaker", CircuitBreaker(
        "test-store", threshold=10**9, reset_seconds=0.01))
    return ResilientStore(inner, **kw)


def test_resilient_store_retries_ops():
    flaky = _FlakyStore(fail_first=2)
    rs = _rstore(flaky)
    rs.put("a/b", b"data")
    assert rs.get("a/b") == b"data"
    assert flaky.calls.count("put") == 3
    assert flaky.calls.count("get") == 3


def test_resilient_store_put_if_absent_single_attempt():
    flaky = _FlakyStore(fail_first=1, ops=("put_if_absent",))
    rs = _rstore(flaky)
    with pytest.raises(TransientError):
        rs.put_if_absent("k", b"v")
    assert flaky.calls.count("put_if_absent") == 1


def test_resilient_store_list_materialized_per_attempt():
    flaky = _FlakyStore(fail_first=1, ops=("list",))
    rs = _rstore(flaky)
    rs.put("p/one", b"1")
    rs.put("p/two", b"2")
    assert sorted(rs.list("p/")) == ["p/one", "p/two"]
    assert flaky.calls.count("list") == 2


def test_unwrap_peels_wrappers():
    mem = MemObjectStore()
    assert unwrap(_rstore(FaultStore(mem, FaultSchedule(0, [])))) is mem


# -- FaultStore -------------------------------------------------------------

def test_parse_spec_roundtrip():
    specs = parse_spec("transient:p=0.05,op=put;latency:p=0.1,ms=2;"
                       "crash:at=40,op=put,prefix=data/,landed=1")
    assert specs == [
        FaultSpec(kind="transient", p=0.05, op="put"),
        FaultSpec(kind="latency", p=0.1, latency=0.002),
        FaultSpec(kind="crash", at=40, op="put", key_prefix="data/",
                  landed=True),
    ]
    with pytest.raises(ValueError):
        parse_spec("meteor:p=1")
    with pytest.raises(ValueError):
        parse_spec("transient:wat=1")


def test_zero_schedule_is_transparent():
    fs = FaultStore(MemObjectStore(), FaultSchedule(seed=1, specs=[]))
    fs.put("a/k", b"v")
    assert fs.get("a/k") == b"v"
    assert fs.injected == []


def test_fault_determinism_same_seed():
    def run(seed):
        fs = FaultStore(MemObjectStore(),
                        FaultSchedule(seed=seed, specs=[
                            FaultSpec(kind="transient", p=0.3)]))
        for i in range(50):
            try:
                fs.put(f"k/{i}", b"x")
            except FaultInjected:
                pass
        return [(op, key, kind) for (_, op, key, kind) in fs.injected]

    a, b = run(7), run(7)
    assert a == b and len(a) > 0
    assert run(8) != a


def test_fault_at_n_and_crash_sticky():
    fs = FaultStore(MemObjectStore(),
                    FaultSchedule(seed=0, specs=[
                        FaultSpec(kind="crash", at=3, op="put")]))
    fs.put("k/1", b"a")
    fs.put("k/2", b"b")
    with pytest.raises(InjectedCrash):
        fs.put("k/3", b"c")
    assert fs.crashed
    # dead store refuses everything, including reads
    with pytest.raises(InjectedCrash):
        fs.get("k/1")
    # the crashed op did NOT land (landed=False default)
    assert not fs.inner.exists("k/3")


def test_fault_landed_write_then_error():
    fs = FaultStore(MemObjectStore(),
                    FaultSchedule(seed=0, specs=[
                        FaultSpec(kind="transient", at=1, op="put",
                                  landed=True)]))
    with pytest.raises(FaultInjected):
        fs.put("k", b"committed")
    # the PUT-committed/connection-died ambiguity: bytes are there
    assert fs.inner.get("k") == b"committed"


def test_fault_partial_put_torn_then_retry_overwrites():
    fs = FaultStore(MemObjectStore(),
                    FaultSchedule(seed=0, specs=[
                        FaultSpec(kind="partial_put", at=1, op="put")]))
    data = b"0123456789abcdef"
    with pytest.raises(FaultInjected):
        fs.put("k", data)
    assert fs.inner.get("k") == data[:8]  # torn half-object
    fs.put("k", data)  # the retry must overwrite
    assert fs.get("k") == data


def test_fault_throttle_kind():
    fs = FaultStore(MemObjectStore(),
                    FaultSchedule(seed=0, specs=[
                        FaultSpec(kind="throttle", at=1)]))
    with pytest.raises(InjectedThrottle):
        fs.put("k", b"v")


def test_fault_partition_window_then_heals():
    """``partition``: the store is unreachable for a DURATION, then
    heals — distinct from ``crash``'s sticky death. Every op inside
    the window raises InjectedPartition (retryable), none reaches the
    backing store, and the first op past the window succeeds."""
    clk = [0.0]
    fs = FaultStore(MemObjectStore(),
                    FaultSchedule(seed=0, specs=[
                        FaultSpec(kind="partition", at=1, op="put",
                                  latency=2.0)]),
                    clock=lambda: clk[0])
    with pytest.raises(InjectedPartition):
        fs.put("k", b"v")  # opens the window; the put never lands
    assert not fs.inner.exists("k")
    clk[0] = 1.0
    with pytest.raises(InjectedPartition):
        fs.get("k")  # still inside the window
    with pytest.raises(InjectedPartition):
        fs.put("k2", b"v")
    assert not fs.inner.exists("k2")
    clk[0] = 2.5  # window elapsed: healed, unlike crash
    fs.put("k", b"v")
    assert fs.get("k") == b"v"
    # a policy that keeps retrying past the window succeeds: partition
    # classifies as retryable (TransientError), crash as fatal
    assert isinstance(InjectedPartition("x"), TransientError)


def test_fault_partition_freezes_other_spec_counters():
    """While partitioned, ops never reach the store, so other specs'
    ``at=N`` arrival counters must NOT advance — the Nth real arrival
    still fires after the window."""
    clk = [0.0]
    fs = FaultStore(MemObjectStore(),
                    FaultSchedule(seed=0, specs=[
                        FaultSpec(kind="partition", at=1, op="put",
                                  latency=5.0),
                        FaultSpec(kind="transient", at=2, op="put")]),
                    clock=lambda: clk[0])
    with pytest.raises(InjectedPartition):
        fs.put("a", b"x")  # partition fires on put arrival #1
    for _ in range(5):  # blocked arrivals: counters frozen
        with pytest.raises(InjectedPartition):
            fs.put("b", b"x")
    clk[0] = 6.0
    with pytest.raises(FaultInjected):
        fs.put("c", b"x")  # put arrival #2 — transient still fires
    fs.put("d", b"x")
    assert fs.get("d") == b"x"


def test_fault_partition_parse_spec_and_default_duration():
    """Spec string round-trip (``ms=`` maps to the window duration)
    and the 5 s default when no duration is given."""
    from volsync_tpu.objstore.faultstore import _PARTITION_DEFAULT_S

    spec = parse_spec("partition:at=1,op=put,ms=2000")[0]
    assert (spec.kind, spec.at, spec.op, spec.latency) \
        == ("partition", 1, "put", 2.0)
    clk = [0.0]
    fs = FaultStore(MemObjectStore(),
                    FaultSchedule(seed=0, specs=[
                        FaultSpec(kind="partition", at=1)]),
                    clock=lambda: clk[0])
    with pytest.raises(InjectedPartition):
        fs.put("k", b"v")
    clk[0] = _PARTITION_DEFAULT_S - 0.1
    with pytest.raises(InjectedPartition):
        fs.get("k")
    clk[0] = _PARTITION_DEFAULT_S + 0.1
    fs.put("k", b"v")
    assert fs.get("k") == b"v"


def test_bitflip_parse_spec_roundtrip():
    spec = parse_spec("bitflip:p=0.01,op=get,nbytes=3,prefix=data/")[0]
    assert spec == FaultSpec(kind="bitflip", p=0.01, op="get",
                             nbytes=3, key_prefix="data/")
    assert parse_spec("bitflip:at=2")[0].nbytes == 1  # default: one byte


def test_bitflip_corrupts_silently_no_exception():
    """The silent fault class: the get SUCCEEDS, the payload is wrong,
    the stored object is untouched, and the injection is recorded."""
    fs = FaultStore(MemObjectStore(),
                    FaultSchedule(seed=3, specs=[
                        FaultSpec(kind="bitflip", at=1, op="get")]))
    fs.put("k", b"0123456789")
    rotten = fs.get("k")  # no exception — that IS the fault
    assert rotten != b"0123456789" and len(rotten) == 10
    # recorded only because corrupted bytes actually reached the caller
    assert fs.injected == [(2, "get", "k", "bitflip")]
    assert fs.get("k") == b"0123456789"  # at=1 consumed: clean again
    assert fs.inner.get("k") == b"0123456789"  # bytes at rest untouched


def test_bitflip_deterministic_same_seed():
    """Same seed, same op sequence => byte-identical corruption (the
    chaos drills replay exact rot); a different seed rots differently."""
    def run(seed):
        fs = FaultStore(MemObjectStore(),
                        FaultSchedule(seed=seed, specs=[
                            FaultSpec(kind="bitflip", p=0.5, op="get")]))
        for i in range(8):
            fs.put(f"k/{i}", bytes(64))
        # two reads per key: occurrence number feeds the hash, so the
        # SAME key may rot on one read and not the other
        return [fs.get(f"k/{i}") for i in range(8) for _ in range(2)]

    a, b = run(21), run(21)
    assert a == b
    assert run(22) != a
    assert any(r != bytes(64) for r in a)  # some reads rotted
    assert any(r == bytes(64) for r in a)  # ...and some stayed clean


def test_bitflip_nbytes_flips_multiple_positions():
    fs = FaultStore(MemObjectStore(),
                    FaultSchedule(seed=5, specs=[
                        FaultSpec(kind="bitflip", at=1, op="get",
                                  nbytes=4)]))
    fs.put("k", bytes(4096))
    rotten = fs.get("k")
    diffs = [i for i in range(4096) if rotten[i] != 0]
    # up to 4 distinct positions (hash collisions may coincide); every
    # mask has its low bit set, so at least one byte always differs
    assert 1 <= len(diffs) <= 4


def test_bitflip_matches_payload_ops_only():
    """bitflip exists only on payload-returning reads: a p=1.0 spec
    never touches puts / exists / size / list (which return non-bytes
    the corruptor could not even process), but rots every get and
    get_range."""
    fs = FaultStore(MemObjectStore(),
                    FaultSchedule(seed=0, specs=[
                        FaultSpec(kind="bitflip", p=1.0)]))
    fs.put("k", b"abcdef")
    assert fs.exists("k") is True
    assert fs.size("k") == 6
    assert list(fs.list("")) == ["k"]
    assert fs.get("k") != b"abcdef"
    assert fs.get_range("k", 1, 3) != b"bcd"
    assert fs.injected and all(
        op in ("get", "get_range") and kind == "bitflip"
        for (_, op, _, kind) in fs.injected)


def test_bitflip_counter_frozen_under_partition():
    """Reads blocked by a partition window never reach the store, so a
    bitflip spec's at=N read counter must not advance for them — the
    Nth REAL read still rots after the window heals."""
    clk = [0.0]
    fs = FaultStore(MemObjectStore(),
                    FaultSchedule(seed=0, specs=[
                        FaultSpec(kind="partition", at=1, op="get",
                                  latency=5.0),
                        FaultSpec(kind="bitflip", at=2, op="get")]),
                    clock=lambda: clk[0])
    fs.put("k", b"payload")
    with pytest.raises(InjectedPartition):
        fs.get("k")  # read arrival #1: window opens, bitflip count = 1
    for _ in range(4):  # blocked arrivals: counters frozen
        with pytest.raises(InjectedPartition):
            fs.get("k")
    clk[0] = 6.0
    assert fs.get("k") != b"payload"  # read arrival #2: bitflip fires
    assert fs.get("k") == b"payload"


def test_bitflip_masked_by_louder_fault_not_recorded():
    """When a loud spec fires on the same arrival, the op raises and no
    corrupted payload reaches the caller — so no bitflip is recorded
    (injected must equal what the caller actually observed)."""
    fs = FaultStore(MemObjectStore(),
                    FaultSchedule(seed=0, specs=[
                        FaultSpec(kind="bitflip", at=1, op="get"),
                        FaultSpec(kind="transient", at=1, op="get")]))
    fs.put("k", b"v")
    with pytest.raises(FaultInjected):
        fs.get("k")
    assert [k for (_, _, _, k) in fs.injected] == ["transient"]
    assert fs.get("k") == b"v"  # both at=1 counters consumed


def test_vanish_parse_spec_roundtrip():
    spec = parse_spec("vanish:at=2,op=put,prefix=ec/")[0]
    assert spec == FaultSpec(kind="vanish", at=2, op="put",
                             key_prefix="ec/")


def test_vanish_landed_then_lost_then_resurrected():
    """The lost-object fault class: the triggering op completes, the
    object physically lands, then every read of that key answers
    absence — until a later write resurrects it (the EC heal arm's
    backfill PUT)."""
    fs = FaultStore(MemObjectStore(),
                    FaultSchedule(seed=3, specs=[
                        FaultSpec(kind="vanish", at=1, op="put",
                                  key_prefix="ec/p/")]))
    fs.put("ec/p/0", b"shard-bytes")
    assert fs.inner.exists("ec/p/0")        # it DID land
    assert fs.exists("ec/p/0") is False     # ...and then was lost
    with pytest.raises(NoSuchKey):
        fs.get("ec/p/0")
    with pytest.raises(NoSuchKey):
        fs.get_range("ec/p/0", 0, 4)
    with pytest.raises(NoSuchKey):
        fs.size("ec/p/0")
    assert list(fs.list("ec/p/")) == []     # listings omit it too
    assert [k for (_, _, _, k) in fs.injected] == ["vanish"]
    fs.put("ec/p/0", b"healed")             # resurrection
    assert fs.get("ec/p/0") == b"healed"
    assert list(fs.list("ec/p/")) == ["ec/p/0"]


def test_vanish_distinct_from_crash_store_stays_alive():
    """vanish kills one KEY; crash kills the STORE. Other keys keep
    answering normally after a vanish."""
    fs = FaultStore(MemObjectStore(),
                    FaultSchedule(seed=0, specs=[
                        FaultSpec(kind="vanish", at=1, op="put",
                                  key_prefix="ec/a")]))
    fs.put("ec/a", b"x")
    fs.put("ec/b", b"y")
    with pytest.raises(NoSuchKey):
        fs.get("ec/a")
    assert fs.get("ec/b") == b"y"
    assert fs.crashed is False


def test_vanish_reads_do_not_advance_spec_counters():
    """Reads of a vanished key never reached an object, so they must
    not consume at=N budgets of other specs (the partition-freeze
    rule applied to lost keys)."""
    fs = FaultStore(MemObjectStore(),
                    FaultSchedule(seed=0, specs=[
                        FaultSpec(kind="vanish", at=1, op="put"),
                        FaultSpec(kind="transient", at=2, op="get")]))
    fs.put("k", b"v")
    for _ in range(5):  # five absent reads: counter must not move
        with pytest.raises(NoSuchKey):
            fs.get("k")
    fs.put("k", b"v2")  # resurrect
    assert fs.get("k") == b"v2"  # transient at=2 counts THIS as get #1
    with pytest.raises(FaultInjected):
        fs.get("k")  # ...and fires on get #2


def test_fault_latency_sleeps(monkeypatch):
    slept = []
    fs = FaultStore(MemObjectStore(),
                    FaultSchedule(seed=0, specs=[
                        FaultSpec(kind="latency", at=1, latency=0.005)]),
                    sleep_fn=slept.append)
    fs.put("k", b"v")
    assert slept == [0.005]
    assert fs.get("k") == b"v"


def test_faultstore_hang_blocks_then_raises_retryable():
    """The ``hang`` kind consumes the caller's patience on the injected
    sleep before surfacing as a retryable drop."""
    slept = []
    fs = FaultStore(
        MemObjectStore(),
        FaultSchedule(seed=3, specs=[
            FaultSpec(kind="hang", at=1, op="get", key_prefix="data/",
                      latency=120.0)]),
        sleep_fn=slept.append)
    fs.put("data/a", b"payload")
    with pytest.raises(InjectedHang):
        fs.get("data/a")
    assert slept == [120.0]
    assert classify(InjectedHang("x")) is True   # retryable
    assert fs.get("data/a") == b"payload"        # once only (at=1)


def test_faultstore_hang_default_duration():
    slept = []
    fs = FaultStore(
        MemObjectStore(),
        FaultSchedule(seed=3, specs=[
            FaultSpec(kind="hang", at=1, op="put")]),
        sleep_fn=slept.append)
    with pytest.raises(InjectedHang):
        fs.put("k", b"v")
    assert slept == [60.0]          # _HANG_DEFAULT_S
    assert fs.exists("k") is False  # the op never landed


def test_resilient_over_faultstore_masks_transients():
    """The layering open_store builds: retries absorb injected faults
    and the data comes back intact."""
    fs = FaultStore(MemObjectStore(),
                    FaultSchedule(seed=11, specs=[
                        FaultSpec(kind="transient", p=0.2)]))
    rs = _rstore(fs, policy=_policy(max_attempts=10))
    blobs = {f"d/{i}": bytes([i]) * 64 for i in range(30)}
    for k, v in blobs.items():
        rs.put(k, v)
    for k, v in blobs.items():
        assert rs.get(k) == v
    assert len(fs.injected) > 0  # schedule actually fired


def test_maybe_wrap_env_arming(monkeypatch):
    mem = MemObjectStore()
    assert maybe_wrap(mem) is mem  # unarmed: untouched
    monkeypatch.setenv("VOLSYNC_FAULT_SEED", "123")
    wrapped = maybe_wrap(mem)
    assert isinstance(wrapped, FaultStore)
    assert wrapped.schedule.seed == 123
    assert wrapped.schedule.specs == default_specs()
    monkeypatch.setenv("VOLSYNC_FAULT_SPEC", "throttle:p=0.5")
    wrapped2 = maybe_wrap(mem)
    assert wrapped2.schedule.specs == [FaultSpec(kind="throttle", p=0.5)]


def test_fault_seed_malformed_raises(monkeypatch):
    """A typo'd seed must fail loudly, not silently disarm the chaos
    harness and report a clean (fault-free) pass."""
    from volsync_tpu import envflags

    monkeypatch.setenv("VOLSYNC_FAULT_SEED", "forty-two")
    with pytest.raises(ValueError, match="VOLSYNC_FAULT_SEED"):
        envflags.fault_seed()
    with pytest.raises(ValueError, match="VOLSYNC_FAULT_SEED"):
        maybe_wrap(MemObjectStore())
    monkeypatch.setenv("VOLSYNC_FAULT_SEED", " 42 ")
    assert envflags.fault_seed() == 42


class _FailingPackStore(MemObjectStore):
    """Every pack put fails retryably; counts the attempts."""

    def __init__(self):
        super().__init__()
        self.pack_puts = 0

    def put(self, key, data):
        if key.startswith("data/"):
            self.pack_puts += 1
            raise TransientError("down")
        return super().put(key, data)


def _upload_one_pack(repo):
    repo._pl_upload_slots.acquire()
    # segments is a list of sealed-segment iovecs (one part here)
    repo._upload_pack([[b"x" * 16]], [{"id": "a" * 64, "type": "data",
                                       "offset": 0, "length": 16,
                                       "raw_length": 16}])


def test_repository_upload_no_retry_stacking():
    """A ResilientStore-wrapped store is the ONE retry layer for pack
    uploads — _upload_policy must not stack on top (the regression
    multiplied attempt budgets into ~16+ network tries per bad pack)."""
    from volsync_tpu.repo.repository import Repository

    mem = _FailingPackStore()
    rs = _rstore(mem, policy=_policy(max_attempts=2))
    repo = Repository.init(rs)
    with pytest.raises(TransientError):
        _upload_one_pack(repo)
    assert mem.pack_puts == 2  # store policy only, not *(_pl_retries+1)

    # a bare store still gets the historical upload policy
    mem2 = _FailingPackStore()
    repo2 = Repository.init(mem2)
    repo2._upload_policy.sleep_fn = lambda s: None
    with pytest.raises(TransientError):
        _upload_one_pack(repo2)
    assert mem2.pack_puts == repo2._upload_policy.max_attempts
