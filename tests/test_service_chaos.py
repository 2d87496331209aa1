"""Service-plane chaos: concurrent tenant streams riding the same
resilience stack a real mover uses — ``ResilientStore(FaultStore(
FsObjectStore))`` with seeded fault schedules — plus the wiring that
makes the service shed at ADMISSION when that stack's circuit breaker
opens.

The contract under fire:

- admitted streams stay byte-correct end to end (chunks bit-identical
  to a local scan, blobs landed through the faulted store restorable
  from the UNFAULTED layer),
- overload and breaker sheds happen ONLY at admission — a shed client
  sees a typed ShedError before its first chunk batch, never a
  mid-stream abort of work already in flight.
"""

import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from volsync_tpu.objstore.faultstore import (
    FaultSchedule,
    FaultSpec,
    FaultStore,
)
from volsync_tpu.objstore.store import FsObjectStore
from volsync_tpu.ops.gearcdc import GearParams
from volsync_tpu.resilience import (
    CircuitBreaker,
    ResilientStore,
    RetryPolicy,
    TransientError,
)
from volsync_tpu.service import (
    MoverJaxClient,
    MoverJaxServer,
    ShedError,
    TenantConfig,
    TenantRegistry,
)

P4K = GearParams(min_size=4096, avg_size=32768, max_size=65536, align=4096)


def _chaos_stack(root, seed, specs, *, breaker=None, attempts=10):
    """The open_store layering with a test-tuned policy (no wall-clock
    backoff) — same shape as tests/test_chaos.py's stack."""
    fs = FsObjectStore(str(root))
    faults = FaultStore(fs, FaultSchedule(seed=seed, specs=list(specs)))
    policy = RetryPolicy(site="svc-chaos", max_attempts=attempts,
                         base_delay=0.001, max_delay=0.01,
                         sleep_fn=lambda s: None)
    if breaker is None:
        breaker = CircuitBreaker("svc-chaos", threshold=10**9,
                                 reset_seconds=0.01)
    return fs, ResilientStore(faults, policy=policy, breaker=breaker)


def test_concurrent_streams_byte_correct_over_faulted_store(tmp_path, rng):
    """Four tenant streams chunk through the scheduled service while
    their blobs land through a transient-faulted resilient store: every
    retryable fault is absorbed, every stream's chunks match a local
    scan, and every blob read back through the UNFAULTED layer is the
    original bytes."""
    from volsync_tpu.engine.chunker import DeviceChunkHasher

    fs, top = _chaos_stack(tmp_path / "store", seed=11, specs=[
        FaultSpec(kind="transient", p=0.2),
        FaultSpec(kind="latency", p=0.1, latency=0.002),
    ])
    reg = TenantRegistry([TenantConfig(name="gold", weight=3),
                          TenantConfig(name="bronze", weight=1)])
    payloads = [rng.bytes(250_000 + 31 * i) for i in range(4)]
    with MoverJaxServer(params=P4K, segment_size=128 * 1024,
                        batch_window_ms=5.0, tenants=reg) as srv:
        def mover(i):
            tenant = "gold" if i % 2 == 0 else "bronze"
            data = payloads[i]
            with MoverJaxClient("127.0.0.1", srv.port, srv.token,
                                tenant=tenant) as c:
                chunks = c.chunk_bytes(data)
            for off, length, digest in chunks:
                top.put(f"chunks/{digest}", data[off:off + length])
            return chunks

        with ThreadPoolExecutor(4) as pool:
            results = list(pool.map(mover, range(4)))

    local = DeviceChunkHasher(P4K)
    for data, chunks in zip(payloads, results):
        assert chunks == local.process(np.frombuffer(data, np.uint8),
                                       eof=True)
        for off, length, digest in chunks:
            # read back through the UNFAULTED layer: the faulted writes
            # really landed, byte-for-byte
            assert fs.get(f"chunks/{digest}") == data[off:off + length]


def test_store_breaker_open_sheds_streams_at_admission(tmp_path):
    """The PR-5 breaker wired into admission: hammer the store until
    its breaker opens, then every new stream is shed at admission —
    typed ShedError carrying the breaker cooldown, delivered before any
    chunk batch, with the in-process decision itself far under the
    10 ms acceptance bound."""
    breaker = CircuitBreaker("svc-chaos-sick", threshold=2,
                             reset_seconds=60.0)
    _, top = _chaos_stack(
        tmp_path / "store", seed=3,
        specs=[FaultSpec(kind="transient", p=1.0, op="put")],
        breaker=breaker, attempts=2)
    with pytest.raises(TransientError):
        top.put("chunks/doomed", b"x")  # retries exhaust, breaker opens
    assert breaker.open_remaining() > 0

    with MoverJaxServer(params=P4K, segment_size=128 * 1024,
                        breaker=breaker) as srv:
        got_batches = [0]

        def reader(n):
            return b"z" * 8192 if got_batches[0] == 0 else b""

        with MoverJaxClient("127.0.0.1", srv.port, srv.token) as c:
            with pytest.raises(ShedError) as ei:
                for _ in c.chunk_stream(reader):
                    got_batches[0] += 1
        assert got_batches[0] == 0, "shed must precede any batch"
        # the hint is the breaker's remaining cooldown, not a constant
        assert 0 < ei.value.retry_after <= 60.0

        # the admission decision itself is micro-fast while open
        from volsync_tpu.service.admission import AdmissionRejected

        t0 = time.perf_counter()
        with pytest.raises(AdmissionRejected) as rej:
            srv.admission.admit_stream("anyone")
        assert rej.value.reason == "breaker_open"
        assert time.perf_counter() - t0 < 0.010


def test_overload_sheds_never_abort_admitted_work(rng):
    """Cap the server at 2 streams and throw 6 at it: some clients are
    shed (typed, zero batches seen), but every ADMITTED stream runs to
    byte-correct completion — overload never claws back work in
    flight."""
    from volsync_tpu.engine.chunker import DeviceChunkHasher

    payloads = [rng.bytes(200_000 + 13 * i) for i in range(6)]
    sheds = []
    shed_lock = threading.Lock()
    with MoverJaxServer(params=P4K, segment_size=128 * 1024,
                        batch_window_ms=5.0, max_streams=2,
                        handlers=10) as srv:
        def run(i):
            data = payloads[i]
            while True:
                got = []
                try:
                    with MoverJaxClient("127.0.0.1", srv.port,
                                        srv.token) as c:
                        for tup in c.chunk_stream(
                                _reader_for(data)):
                            got.append(tup)
                    return got
                except ShedError as e:
                    assert got == [], "shed must precede any batch"
                    with shed_lock:
                        sheds.append(e.retry_after)
                    time.sleep(min(e.retry_after, 0.05))

        def _reader_for(buf):
            pos = [0]

            def read(n):
                piece = buf[pos[0]: pos[0] + min(n, 65536)]
                pos[0] += len(piece)
                return piece

            return read

        with ThreadPoolExecutor(6) as pool:
            results = list(pool.map(run, range(6)))

    local = DeviceChunkHasher(P4K)
    for data, chunks in zip(payloads, results):
        assert chunks == local.process(np.frombuffer(data, np.uint8),
                                       eof=True)
    assert sheds, "6 clients vs 2 slots must shed"
    assert all(r > 0 for r in sheds)
