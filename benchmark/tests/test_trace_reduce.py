"""``trace_reduce``: on planes made by hand, and on one small trace
recorded on the chip (``data/recorded.xplane.pb``: a rehearsal-size
``restic-10g.backup --trace 1`` on a TPU v5 lite, PR 25)."""

from pathlib import Path

import pytest

from benchmark import trace_reduce as tr

RECORDED = Path(__file__).parent / "data" / "recorded.xplane.pb"
MS = 1e6  # ns


def planes(ops, modules=(), host=()):
    return {"/device:TPU:0": {tr.OPS_LINE: list(ops),
                              tr.MODULES_LINE: list(modules)},
            "/host:CPU": {"main": [(tr.SYNC, 0.0, 10.0), *host]}}


@pytest.mark.parametrize("intervals, want", [
    ([(0, 10), (20, 30)], 20), ([(0, 10), (5, 30)], 30),
    ([(5, 6), (0, 10), (10, 12)], 12), ([], 0)])
def test_union(intervals, want):
    assert tr.union_seconds(intervals)[0] == pytest.approx(want / 1e9)


def test_busy_idle_programs_and_top_ops():
    out = tr.reduce(planes(
        ops=[("fusion.1", 100 * MS, 50 * MS), ("copy.2", 120 * MS, 10 * MS),
             ("fusion.1", 400 * MS, 100 * MS)],
        modules=[("jit_chunk_hash_segments(17)", 100 * MS, 50 * MS),
                 ("jit_chunk_hash_segments(18)", 400 * MS, 100 * MS),
                 ("jit_other(3)", 0, 1 * MS)]), window_s=0.6)
    assert out["devices"] == 1 and out["window_s"] == 0.6
    assert out["busy_s"] == pytest.approx(0.150)
    assert out["programs"]["jit_chunk_hash_segments"] == pytest.approx(0.150)
    assert out["device_ops"][0] == ["fusion.1", pytest.approx(0.150)]
    # gaps: 0-100, 150-400, 500-600 ms; none has a host name
    assert out["idle_gaps"] == [["host:unnamed", pytest.approx(0.25)]]


def test_gaps_take_the_name_of_the_host_span_that_covers_them():
    ring = [{"name": tr.SYNC, "ph": "X", "ts": 7_000_000.0, "dur": 1.0},
            # ring clock is us: 7.15 s .. 7.40 s -> trace 150 .. 400 ms
            {"name": "repo.seal", "ph": "X", "ts": 7_150_000.0,
             "dur": 250_000.0},
            {"name": "engine.read", "ph": "X", "ts": 7_500_000.0,
             "dur": 100_000.0}]
    out = tr.reduce(planes(
        ops=[("a", 100 * MS, 50 * MS), ("a", 400 * MS, 100 * MS),
             ("a", 900 * MS, 100 * MS)],
        host=[("bench.op", 0.0, 1000 * MS)]),
        window_s=1.0, ring=ring, sync_ring=7_000_000.0)
    names = dict(out["idle_gaps"])
    assert names["repo.seal"] == pytest.approx(0.250)
    # the 500-900 ms gap: bench.op covers all of it, engine.read a quarter
    assert names["bench.op"] == pytest.approx(0.4)


def test_a_trace_the_profiler_cut_is_reduced_over_what_it_holds():
    """Device events that stop a fifth of the way into the window: the
    idle share is of that fifth, not of the host's window."""
    from benchmark.readers import trace_idle_share

    out = tr.reduce(planes(ops=[("a", 0, 50 * MS), ("a", 100 * MS, 100 * MS)]),
                    window_s=1.0)
    assert out["window_s"] == pytest.approx(0.2)
    assert out["host_window_s"] == 1.0
    assert out["busy_s"] == pytest.approx(0.15)
    assert trace_idle_share.read({}, {"trace": out}) == pytest.approx(25.0)
    assert out["idle_gaps"] == [["host:unnamed", pytest.approx(0.05)]]


def test_two_devices_average():
    p = planes(ops=[("a", 0, 100 * MS)])
    p["/device:TPU:1"] = {tr.OPS_LINE: [("a", 0, 300 * MS)]}
    assert tr.reduce(p, window_s=1.0)["busy_s"] == pytest.approx(0.2)


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_the_recorded_trace_from_the_chip():
    loaded = tr.load(str(RECORDED))
    assert any(n.startswith("/device:TPU:") for n in loaded)
    out = tr.reduce(loaded, window_s=3.0)
    assert out["devices"] == 1
    assert 0 < out["busy_s"] < 3.0
    assert any("chunk_hash_segment" in name for name in out["programs"])
    assert out["device_ops"] and out["device_ops"][0][1] > 0
    assert out["idle_gaps"] and all(s > 0 for _, s in out["idle_gaps"])
