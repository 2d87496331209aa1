"""The per-layer metrics that read the program's dispatch stages, queue
wait, occupancy, self time and program loads: each comes as a file and
an entry, finds its reader, is printed by a ``--trace 1`` rehearsal of
its cells, and reads at the line what the window's end would have
read."""

import importlib
import json
from pathlib import Path

import pytest

from benchmark import run, trace_reduce

ROOT = Path(run.__file__).resolve().parent.parent
BM = json.loads((ROOT / "BENCHMARK.json").read_text())
B = ["restic-10g.backup", "smallfiles.backup"]
S = ["fleet-100.stream"]
NEW = {
    "dispatch_stage_s_per_gib": B + S, "dispatch_launch_s_per_gib": B + S,
    "dispatch_fetch_s_per_gib": B + S, "dispatch_decode_s_per_gib": B + S,
    "batch_queue_wait_ms": B + S, "lanes_per_dispatch": B + S,
    "staged_useful_share": B + S, "op_fixed_s_per_gib": B,
    "op_flush_s_per_gib": B, "file_self_s_per_gib": B,
    "svc_batch_ms": S, "svc_ingest_ms": S, "svc_emit_ms": S,
    "svc_stream_ms": S, "setup_program_load_s": B + S,
    "setup_programs_loaded": B + S, "ring_dropped_events": B + S,
}
NEW_READERS = {"counter_ratio", "span_self_seconds_per_gib", "program_load"}


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_new_metric_is_a_file_an_entry_and_a_reader(name):
    spec = json.loads((ROOT / "benchmark" / "layer_metrics"
                       / f"{name}.json").read_text())
    entry = next(m for m in BM["per_layer"] if m["name"] == name)
    assert entry["workloads"] == NEW[name]
    assert entry["source"] in ("program_span", "program_counter")
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert spec[key] == entry[key], key
    reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
    assert callable(reader.read)


def test_readers_read_nothing_from_a_program_without_the_registries(
        monkeypatch):
    """The parent commit has no counters, no self time and no load
    totals: the new readers return None there and do not raise."""
    from volsync_tpu import compile_cache, obs

    for module, attr in ((obs, "counter_totals"), (obs, "span_self_totals"),
                         (compile_cache, "load_totals")):
        monkeypatch.delattr(module, attr)
    seen = set()
    for name in NEW:
        spec = json.loads((ROOT / "benchmark" / "layer_metrics"
                           / f"{name}.json").read_text())
        if spec["reader"] in NEW_READERS:
            seen.add(spec["reader"])
            reader = importlib.import_module(
                f"benchmark.readers.{spec['reader']}")
            assert reader.read(spec["args"], {"gib_moved": 1.0}) is None
    assert seen == NEW_READERS


def _registries():
    from volsync_tpu import compile_cache
    from volsync_tpu.obs import counter_totals, span_self_totals

    return {"counters": counter_totals(), "self": span_self_totals(),
            "loads": compile_cache.load_totals()}


@pytest.mark.parametrize("cell", B + S)
def test_a_traced_rehearsal_prints_every_new_metric_of_the_cell(
        cell, monkeypatch):
    """One ``--trace 1`` rehearsal a cell. The batcher is switched on
    (off a TPU it is off by default) so that the run takes the chip's
    way to the device. The three registries the new readers read when
    the line is made hold what they held at the window's end (there:
    the start of ``verify``), so reading them late is the same reading;
    and a gap under a dispatch stage is named after it."""
    from volsync_tpu.obs import trace_events

    monkeypatch.setenv("VOLSYNC_BATCH_SEGMENTS", "1")
    driver = importlib.import_module(
        "benchmark.drivers." + ("stream" if cell in S else "backup"))
    at_window_end = {}
    real_verify = driver.verify

    def verify(state):
        at_window_end.update(_registries())
        return real_verify(state)

    monkeypatch.setattr(driver, "verify", verify)
    result = run.run_cell(cell, 2147483659, 1.0, True, size="rehearsal")
    assert result["correct"] and result["attempted"] > 0
    want = {name for name, cells in NEW.items() if cell in cells}
    assert want <= set(result["metrics"]), want - set(result["metrics"])
    assert at_window_end == _registries()
    assert at_window_end["counters"]["ops.dispatches"] > 0
    assert at_window_end["counters"].get("obs.ring_dropped", 0) == 0
    assert at_window_end["loads"]["compiles"] > 0

    ring = trace_events()
    spans = trace_reduce.host_spans_on_trace_clock(
        {"/host:CPU": {"annotations": [(trace_reduce.SYNC, 5e9, 1.0)]}},
        ring, ring[0]["ts"])
    staged = [(s, e) for name, s, e in spans if name == "ops.stage"]
    assert staged
    for gap in staged[:5]:
        assert trace_reduce.name_gap(gap, spans) == "ops.stage"
    launched = next((s, e) for name, s, e in spans if name == "ops.launch")
    assert trace_reduce.name_gap(launched, spans).startswith("ops.")
