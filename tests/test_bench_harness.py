"""Unit tier for the driver bench's robustness machinery.

bench.py is driver-critical, so the pieces that keep it honest get the
same test treatment as product code: error classification, per-config
deadlines, the refusal to measure anything but a TPU, the
synthetic-volume generator, and the host gear reference.
"""

import json
import signal
import time

import numpy as np
import pytest

import bench


def test_classify_backend_errors():
    for msg in (
        "Unable to initialize backend 'tpu': UNAVAILABLE: TPU backend",
        "DEADLINE_EXCEEDED: something",
        "failed to connect to all addresses",
        "INTERNAL: stream terminated",
    ):
        assert bench._classify(RuntimeError(msg)) == "backend", msg


def test_classify_oom_errors():
    for msg in (
        "RESOURCE_EXHAUSTED: Out of memory allocating 268435456 bytes",
        "Attempting to allocate 2.0G",
        "allocation of 123 failed",
    ):
        assert bench._classify(RuntimeError(msg)) == "oom", msg


def test_classify_other_errors_reraise_class():
    assert bench._classify(ValueError("shape mismatch")) == "other"


def test_with_deadline_interrupts(monkeypatch):
    monkeypatch.setattr(bench, "CONFIG_DEADLINE_S", 1)
    t0 = time.perf_counter()
    with pytest.raises(bench._Deadline):
        bench._with_deadline(time.sleep, 30)
    assert time.perf_counter() - t0 < 5
    # the timer is disarmed afterwards
    assert signal.getitimer(signal.ITIMER_REAL)[0] == 0
    # and a fast fn passes its result through
    assert bench._with_deadline(lambda: 42) == 42


def test_make_data_redundancy():
    data = bench._make_data(1 << 20, redundancy=0.5)
    assert data.shape == (1 << 20,)
    assert data.dtype == np.uint8
    # the two halves are distinct streams (not a trivial repeat of one)
    assert not np.array_equal(data[: 1 << 19], data[1 << 19:])


def test_host_gear_candidates_match_library():
    """The bench's numpy gear reference must agree with the library's
    scalar reference — they gate the golden check and the CPU baseline."""
    from volsync_tpu.ops.gearcdc import DEFAULT_PARAMS, gear_at_aligned

    import jax.numpy as jnp

    p = DEFAULT_PARAMS
    host = bench._make_data(256 * 1024)
    strict, lax_c = bench._host_gear_candidates(host, p)
    h = np.asarray(gear_at_aligned(jnp.asarray(host), p.seed, p.align))
    pos = np.arange(h.shape[0], dtype=np.int64) * p.align + (p.align - 1)
    np.testing.assert_array_equal(
        strict, pos[(h & np.uint32(p.mask_s)) == 0])
    np.testing.assert_array_equal(
        lax_c, pos[(h & np.uint32(p.mask_l)) == 0])


def test_device_mode_refuses_without_tpu(monkeypatch, capsys):
    """With no TPU the device mode exits non-zero and prints no metric:
    no measurement child is started for a CPU probe result."""
    monkeypatch.setattr(bench.sys, "argv", ["bench.py"])
    monkeypatch.delenv("VOLSYNC_BENCH_INNER", raising=False)
    monkeypatch.setattr(bench, "_watchdog", lambda: None)
    monkeypatch.setattr(bench, "_probe_backend", lambda: "cpu")
    monkeypatch.setattr(
        bench, "_run_measurement_child",
        lambda *a, **k: pytest.fail("measured without a TPU"))
    with pytest.raises(SystemExit) as ex:
        bench.main()
    assert ex.value.code == 69
    assert capsys.readouterr().out == ""


def test_parse_config():
    assert bench._parse_config("64,8,6") == ("S", 64, 8, 6)
    assert bench._parse_config("S64,8,6") == ("S", 64, 8, 6)
    assert bench._parse_config("B:128,8,4") == ("B", 128, 8, 4)
    assert bench._parse_config("B32,8,8") == ("B", 32, 8, 8)


@pytest.mark.slow
def test_batched_throughput_golden_path():
    """Drive _try_batched_throughput end-to-end on the CPU backend at a
    tiny shape: exercises the batched dispatch, the on-TPU-style golden
    check against the host reference, and the pipelined thread pool."""
    out = bench._try_batched_throughput(2, 2, 1, pipelines=2)
    assert out > 0


@pytest.mark.slow
def test_device_throughput_golden_path():
    """Same for the single-segment path (its golden warm check runs the
    full host-reference comparison)."""
    out = bench._try_device_throughput(2, 1, 1)
    assert out > 0


def test_bench_provenance_shape(monkeypatch):
    """Every bench result embeds a provenance block; its jax_backend
    label must be honest — never force-initializing a backend just to
    report one (round 3's wedge started exactly that way)."""
    monkeypatch.setenv("VOLSYNC_INDEX_SHARDS", "8")
    prov = bench.bench_provenance()
    assert prov["platform"] and prov["python"]
    assert prov["git_rev"] != ""
    assert prov["volsync_flags"]["VOLSYNC_INDEX_SHARDS"] == "8"
    # jax imported + pinned to cpu in the test env => honest cpu label;
    # otherwise one of the not-initialized sentinels
    assert prov["jax_backend"] in ("cpu", "not-imported",
                                   "imported-uninitialized")
    extra = bench.bench_provenance(extra={"k": 1})
    assert extra["k"] == 1


def test_bench_provenance_session_block(monkeypatch):
    """Jobs launched through the session queue export VOLSYNC_SESSION_*
    into the child environment; provenance must echo them so every
    BENCH_*.json names the exact lease (and fencing epoch) it ran
    under. Outside a session the block is absent, not fabricated."""
    for var in ("VOLSYNC_SESSION_ID", "VOLSYNC_SESSION_EPOCH",
                "VOLSYNC_SESSION_BACKEND"):
        monkeypatch.delenv(var, raising=False)
    assert "session" not in bench.bench_provenance()

    monkeypatch.setenv("VOLSYNC_SESSION_ID", "fake-7")
    monkeypatch.setenv("VOLSYNC_SESSION_EPOCH", "3")
    monkeypatch.setenv("VOLSYNC_SESSION_BACKEND", "fake")
    sess = bench.bench_provenance()["session"]
    assert sess == {"id": "fake-7", "epoch": 3, "backend": "fake"}


def test_emit_refuses_provenance_less_results(capsys):
    """_emit is the choke point every bench result passes through; a
    result without a provenance block is refused outright rather than
    printed as an anonymous result line."""
    with pytest.raises(ValueError, match="no provenance block"):
        bench._emit({"metric": "m", "value": 1.0})
    assert capsys.readouterr().out == ""

    bench._emit({"metric": "m", "value": 1.0,
                 "provenance": bench.bench_provenance()})
    line = json.loads(capsys.readouterr().out)
    assert line["provenance"]["platform"]


def test_index_bench_smoke():
    """Tiny end-to-end run of the metadata-plane bench: all three index
    flavors execute, the batched path beats the scalar loop (loose 1.5x
    floor at this scale — acceptance tracks the full 1M run), and the
    provenance block rides along."""
    out = bench.index_bench(entries=4000, queries=4000, batch=1024,
                            shards=4)
    assert out["metric"] == "index_batched_lookup_speedup"
    assert out["value"] > 1.5
    assert out["entries"] == 4000 and out["shards"] == 4
    assert out["batched"]["hit_lookup_per_s"] > \
        out["scalar"]["hit_lookup_per_s"]
    assert out["sharded_batched"]["prefilter_skips"] > 0
    assert 0.0 < out["sharded_batched"]["prefilter_saturation"] < 1.0
    assert "provenance" in out


def test_kill_marked_children_hits_only_the_marker():
    """The session supervisor's force-release SIGKILLs exactly the
    processes carrying the measurement-child environment marker and
    nothing else. Uses a per-test sentinel marker so the sweep can
    never touch a real bench running elsewhere on the host."""
    import os
    import subprocess
    import sys

    from volsync_tpu.cluster.sessions import kill_marked_children

    sentinel = f"VOLSYNC_BENCH_TEST_{os.getpid()}"
    stale = subprocess.Popen(
        [sys.executable, "-c", "import time; time.sleep(120)"],
        env={**os.environ, "VOLSYNC_BENCH_SENTINEL": sentinel})
    bystander = subprocess.Popen(
        [sys.executable, "-c", "import time; time.sleep(120)"],
        env=dict(os.environ))
    try:
        time.sleep(0.3)
        killed = kill_marked_children(
            f"VOLSYNC_BENCH_SENTINEL={sentinel}")
        assert killed == 1
        assert stale.wait(timeout=10) == -signal.SIGKILL
        assert bystander.poll() is None  # untouched
    finally:
        for p in (stale, bystander):
            if p.poll() is None:
                p.kill()


def test_inner_main_refuses_cpu_backend(monkeypatch, capsys):
    """The measurement child itself checks the backend: on the CPU it
    exits non-zero before anything is measured or printed."""
    monkeypatch.setattr(
        bench, "_run_config_ladder",
        lambda: pytest.fail("measured on the CPU backend"))
    with pytest.raises(SystemExit) as ex:
        bench._inner_main()
    assert ex.value.code == 69
    assert capsys.readouterr().out == ""


def test_golden_failure_is_fatal(monkeypatch):
    """A golden-check failure is a failed run: the ladder stops at the
    first config, with no smaller config, no retry on another kernel
    path and no environment switch flipped behind the user."""
    import os

    calls = []

    def golden_fails(kind, seg_mib, streams, iters):
        calls.append((kind, seg_mib))
        raise AssertionError("fused blob id")

    monkeypatch.setattr(bench, "_try_config", golden_fails)
    monkeypatch.delenv("VOLSYNC_BENCH_CONFIG", raising=False)
    before = dict(os.environ)
    with pytest.raises(AssertionError):
        bench._run_config_ladder()
    assert len(calls) == 1
    assert dict(os.environ) == before
