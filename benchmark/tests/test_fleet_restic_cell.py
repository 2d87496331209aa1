"""``fleet-restic-10g.backup``: the cell's files by name, its metrics'
files and readers, a traced rehearsal on the CPU (the same code as a
chip run: three movers in child processes that hold no device, the
configuration's tiny shape, a small ``segment_size``) and the plan
function against what that rehearsal dispatched."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from benchmark import run, volumes

ROOT = Path(run.__file__).resolve().parent.parent
CELL = "fleet-restic-10g.backup"
BM = json.loads((ROOT / "BENCHMARK.json").read_text())
SERVER_SIDE = {
    "fleet_svc_stream_s_per_gib", "fleet_svc_accept_wait_s_per_gib",
    "fleet_svc_ingest_s_per_gib", "fleet_svc_batch_s_per_gib",
    "fleet_svc_queue_wait_s_per_gib", "fleet_segments_per_stream"}
MOVER_SIDE = {
    "fleet_mover_wall_s_per_gib", "fleet_remote_wait_s_per_gib",
    "fleet_remote_send_s_per_gib", "fleet_mover_fixed_s_per_gib",
    "fleet_mover_host_files_s_per_gib", "fleet_mover_seal_wait_s_per_gib",
    "fleet_mover_add_self_s_per_gib", "fleet_remote_replays_per_gib"}
MINE = SERVER_SIDE | MOVER_SIDE
SHARED = {
    "dispatches_per_gib", "dispatch_stage_s_per_gib",
    "dispatch_launch_s_per_gib", "dispatch_fetch_s_per_gib",
    "dispatch_decode_s_per_gib", "batch_queue_wait_ms",
    "lanes_per_dispatch", "staged_useful_share", "lanes_direct_share",
    "segment_hbm_roofline", "setup_program_load_s",
    "setup_programs_loaded", "ring_dropped_events"}
UNLISTED = {"compiles_in_window", "device_idle_share", "peak_hbm_bytes"}
TRACE_ONLY = {"segment_hbm_roofline", "device_idle_share", "peak_hbm_bytes"}
READERS = {"span_seconds_per_gib", "counter_ratio", "own_wall_per_gib",
           "child_span_seconds_per_gib", "child_counter_per_gib"}


def test_the_cells_files_are_found_by_name():
    _, entry, cell, config = run.find_cell(CELL)
    assert cell["why"] == entry["why"] and cell["driver"] == "backup_fleet"
    assert cell["control"] == "flip_pack_bit" and entry["chips"] == 1
    for name in ("drivers/backup_fleet.py", "drivers/backup_fleet_mover.py",
                 "warm_fleet.py", "readers/child_span_seconds_per_gib.py",
                 "readers/child_counter_per_gib.py"):
        assert (ROOT / "benchmark" / name).exists()
    conf = next(c for c in BM["configs"] if c["name"] == entry["config"])
    assert config["reduced"] == conf["reduced"] == [
        "movers", "chips", "volume_bytes", "store_latency", "link", "hosts"]
    assert set(config["source_scale"]) == set(config["reduced"])
    assert config["source"] == conf["source"] and len(conf["source"]) <= 200
    assert config["architecture"] is None and len(config["guarantees"]) == 6
    assert config["server"] == cell["params"]["server"] == {}
    # the chunker every restic configuration carries
    theirs = json.loads((ROOT / "benchmark/configs/restic-10g.json")
                        .read_text())
    assert config["chunker"] == theirs["chunker"]
    mine = {f["path"]: f["bytes"] for f in config["shape"]["files"]}
    # restic-10g's proportions at an eighth (the size rule: PERF.md §4)
    assert mine == {f["path"]: f["bytes"] // 8
                    for f in theirs["shape"]["files"]}
    assert config["shape"]["small"] == {
        **theirs["shape"]["small"], "count": 125, "dirs": 3}
    sizes = [n for _, n, _ in volumes.plan(config["shape"], 1)]
    assert sum(sizes) == config["volume_bytes"]
    p = cell["params"]
    assert (p["movers"], p["tenants"], p["verify_ops"], p["trace_seconds"]) \
        == (config["movers"], config["tenants"], 12, 15) == (12, 4, 12, 15)


@pytest.mark.parametrize("name", sorted(MINE))
def test_a_new_metric_is_a_file_an_entry_and_a_reader(name):
    spec = json.loads((ROOT / "benchmark" / "layer_metrics"
                       / f"{name}.json").read_text())
    listed = next(m for m in BM["per_layer"] if m["name"] == name)
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert spec[key] == listed[key], (name, key)
    assert listed["moves"] == "moved_mibps"
    assert listed["workloads"] == [CELL]
    assert spec["reader"] in READERS
    assert (ROOT / "benchmark" / "readers" / f"{spec['reader']}.py").exists()


def test_the_cell_reports_what_the_issue_lists_and_no_tail():
    assert {m["name"] for m in BM["per_layer"]
            if CELL in m.get("workloads", []) and m["name"] not in MINE} \
        == SHARED
    assert {m["name"] for m in BM["end_to_end"]
            if "workloads" not in m or CELL in m["workloads"]} \
        == {"moved_mibps", "stored_ratio", "setup_s"}


def test_the_cell_came_with_no_edit_to_the_harness():
    text = (ROOT / "benchmark" / "run.py").read_text()
    assert "fleet" not in text and "mover_spans" not in text


@pytest.fixture(scope="module")
def rehearsal():
    done = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"), "--workload",
         CELL, "--seed", "2147483659", "--seconds", "3", "--trace", "1",
         "--size", "rehearsal"],
        capture_output=True, text=True, cwd=str(ROOT), timeout=1200,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "VOLSYNC_BATCH_SEGMENTS": "1"})
    assert done.returncode == 0, done.stderr[-2000:]
    return [json.loads(ln) for ln in done.stdout.splitlines()
            if ln.startswith("{")]


def test_the_traced_rehearsal_is_correct_and_names_every_metric(rehearsal):
    last, info = rehearsal[-1], rehearsal[-2]
    assert last["correct"] is True and last["failed"] == 0
    assert info["operations"] >= 3 and info["in_window"]["compiles"] == 0
    assert (MINE | SHARED | UNLISTED) - TRACE_ONLY <= set(last["metrics"])
    assert all(m["value"] is None for m in last["metrics"].values())
    checks = {c["check"]: c for c in rehearsal if "check" in c}
    for name in ("ops_failed", "snapshots_wrong", "files_missing",
                 "check_problems", "blob_id_mismatches",
                 "file_sha_mismatches", "chunk_boundary_mismatches",
                 "read_errors", "mover_backends_initialized",
                 "streams_answered_elsewhere"):
        assert checks[name] == {"check": name, "value": 0, "limit": 0}
    for name in ("files_read_back", "svc_stream_bytes",
                 "device_staged_bytes"):
        assert checks[name]["value"] >= checks[name]["at_least"] > 0
    window = next(ln for ln in rehearsal
                  if "fleet_window" in ln)["fleet_window"]
    assert len(window["ops_by_mover"]) == 3
    assert all(n >= 1 for n in window["ops_by_mover"].values())
    assert set(window["host_cpu_s"]) == {"server", "movers", "stores"}
    # the named spans of the movers' backup threads are their wall
    assert 0.9 <= window["named_share"] <= 1.02
    counts = next(ln for ln in rehearsal
                  if "fleet_counts" in ln)["fleet_counts"]
    assert counts["remote.streams"] == counts["svc.streams"] > 0
    assert counts["remote.bytes"] == counts["svc.stream_bytes"]
    assert sum(v for k, v in counts.items()
               if k.startswith("svc.tenant_bytes.")) \
        == counts["svc.stream_bytes"]
    assert counts["svc.segments"] > counts["svc.streams"]  # a long file


def test_the_plan_lists_what_the_rehearsal_dispatched(rehearsal):
    """Every program the window or the warm-up operations ran was one
    set-up loaded from the plan: nothing compiled after it."""
    info = rehearsal[-2]
    plan = next(ln for ln in rehearsal if "warm_plan" in ln)["warm_plan"]
    assert info["in_window"]["compiles"] == 0
    assert info["in_window"]["programs"] == []
    batched = [p for p in info["warm_up"]["programs"]
               if "_chunk_hash_segments_impl" in p]
    assert len(batched) == len(plan) == len({tuple(p) for p in plan})
