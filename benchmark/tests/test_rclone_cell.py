"""``rclone-smallfiles.sync``: a rehearsal of the cell on the CPU (the
same code as a chip run, the configuration's tiny shape), its control,
the warm plan against the programs JAX loaded, and the cell's files by
name."""

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

from benchmark import run

ROOT = Path(run.__file__).resolve().parent.parent
CELL = "rclone-smallfiles.sync"
BM = json.loads((ROOT / "BENCHMARK.json").read_text())
MINE = {m["name"] for m in BM["per_layer"] if m.get("workloads") == [CELL]}
SHARED = {"mover_wall_s_per_gib", "verify_stage_s_per_gib",
          "verify_staged_useful_share", "setup_program_load_s",
          "setup_programs_loaded", "ring_dropped_events",
          "span_roots_hbm_roofline"}
UNLISTED = {"compiles_in_window", "device_idle_share", "peak_hbm_bytes"}


def _script(script, *argv):
    return subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / script), *argv],
        capture_output=True, text=True, cwd=str(ROOT), timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})


def _lines(done):
    return [json.loads(ln) for ln in done.stdout.splitlines()
            if ln.startswith("{")]


@pytest.fixture(scope="module")
def rehearsal():
    done = _script("run.py", "--workload", CELL, "--seed", "2147483659",
                   "--seconds", "2", "--trace", "1", "--size", "rehearsal")
    assert done.returncode == 0, done.stderr[-2000:]
    return _lines(done)


def test_the_rehearsal_is_correct_and_names_every_metric(rehearsal):
    last, info = rehearsal[-1], rehearsal[-2]
    assert last["correct"] is True and last["failed"] == 0
    assert info["operations"] >= 1
    # off a TPU the names are there and no number is; the trace's two
    # metrics have no device plane to read on the CPU and the memory
    # stat no backend to ask: they are left out
    assert MINE and MINE <= set(last["metrics"])
    assert (SHARED | UNLISTED) - {"span_roots_hbm_roofline",
                                  "device_idle_share", "peak_hbm_bytes"} \
        <= set(last["metrics"])
    assert all(m["value"] is None for m in last["metrics"].values())
    checks = {c["check"]: c for c in rehearsal if "check" in c}
    for name in ("calls_failed", "objects_missing", "objects_extra",
                 "index_missing", "index_extra", "index_stale", "index_meta",
                 "object_content_mismatch", "files_missing", "files_extra",
                 "size_mismatch", "content_mismatch", "meta_mismatch",
                 "stale_files"):
        assert checks[name] == {"check": name, "value": 0, "limit": 0}
    assert checks["files_compared"]["value"] >= 1
    assert checks["objects_read_back"]["value"] >= 1
    # the source's pass, the destination's, and the pass over what was
    # fetched: three launches a cycle at this size
    assert checks["verify_launches"]["value"] == 3 * info["operations"]
    # every cycle's wall is the two entry calls and the one listing
    for spans in info["op_spans"]:
        assert "bench.list" in spans and "rclone.hash" in spans


def test_the_warm_plan_lists_every_program_the_rehearsal_ran(rehearsal):
    """The plan is read from the program's own spans; what JAX loaded
    is read from its log by the harness: two sources, one set, and no
    program left for the window."""
    plan = next(ln for ln in rehearsal if "warm_plan" in ln)["warm_plan"]
    info = rehearsal[-2]
    loaded = set()
    for name in info["warm_up"]["programs"] + info["in_window"]["programs"]:
        if "span_roots_device" in name:
            bucket, lanes = re.search(
                r"uint8\[(\d+)\].*?int32\[(\d+)\]", name).groups()
            loaded.add((int(bucket), int(lanes)))
    assert plan and loaded == {tuple(k) for k in plan}
    assert info["in_window"]["compiles"] == 0


def test_the_control_reads_false_in_rehearsal():
    done = _script("control.py", "--workload", CELL, "--seeds", "3,4",
                   "--seconds", "1", "--size", "rehearsal")
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-2000:]
    lines = _lines(done)
    assert [c["correct"] for c in lines if "control" in c] == [False, False]
    assert sum(c.get("check") == "content_mismatch" and c["value"] == 1
               for c in lines) == 2


def test_the_cells_files_are_found_by_name():
    _, entry, cell, config = run.find_cell(CELL)
    assert cell["why"] == entry["why"] and cell["driver"] == "rclone_sync"
    assert cell["control"] == "flip_synced_bit" and entry["chips"] == 1
    assert (ROOT / "benchmark" / "drivers" / "rclone_sync.py").exists()
    conf = next(c for c in BM["configs"] if c["name"] == entry["config"])
    assert config["reduced"] == conf["reduced"] == [
        "volume_bytes", "files", "store_latency"]
    assert config["source"] == conf["source"]
    assert config["architecture"] is None and len(config["guarantees"]) == 4
    # the size law is smallfiles', uncut; the scale is this file's
    theirs = json.loads((ROOT / "benchmark" / "configs"
                         / "smallfiles.json").read_text())["shape"]["small"]
    mine = config["shape"]["small"]
    for key in ("lo", "hi", "size_seed"):
        assert mine[key] == theirs[key]
    assert mine["count"] == 100 * mine["dirs"]
    assert cell["params"]["rewrite_share"] == 0.05
    assert cell["params"]["remove_share"] == 0.01
    for name in MINE:
        spec = json.loads((ROOT / "benchmark" / "layer_metrics"
                           / f"{name}.json").read_text())
        listed = next(m for m in BM["per_layer"] if m["name"] == name)
        for key in ("name", "unit", "better", "source", "layer", "moves"):
            assert spec[key] == listed[key], (name, key)
        assert listed["moves"] == "moved_mibps"
        assert (ROOT / "benchmark" / "readers"
                / f"{spec['reader']}.py").exists()
    assert {m["name"] for m in BM["per_layer"]
            if CELL in m.get("workloads", []) and m["name"] not in MINE} \
        == SHARED
    assert {m["name"] for m in BM["end_to_end"]
            if "workloads" not in m or CELL in m["workloads"]} \
        == {"moved_mibps", "setup_s"}


def test_the_cell_came_with_no_edit_to_the_harness():
    """A cell is new files and entries: ``run.py`` names neither this
    cell, its driver nor its configuration."""
    text = (ROOT / "benchmark" / "run.py").read_text()
    assert "rclone" not in text
