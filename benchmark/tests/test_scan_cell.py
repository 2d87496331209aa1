"""``dedup-1t-indexed.scan``: the cell's files by name, its metrics'
files, a traced rehearsal on the CPU (the same code as a chip run, the
configuration's tiny shape, 4,096 history blobs) and the plain reference
against a hand-made run."""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from benchmark import run, scanstate
from benchmark.reference import dedupscan, gearcdc

ROOT = Path(run.__file__).resolve().parent.parent
CELL = "dedup-1t-indexed.scan"
BM = json.loads((ROOT / "BENCHMARK.json").read_text())
MINE = {m["name"] for m in BM["per_layer"] if m.get("workloads") == [CELL]}
SHARED = {
    "mover_wall_s_per_gib", "op_fixed_s_per_gib", "op_flush_s_per_gib",
    "engine_read_s_per_gib", "engine_read_wait_s_per_gib",
    "engine_device_s_per_gib", "file_self_s_per_gib", "file_open_s_per_gib",
    "repo_add_self_s_per_gib", "repo_seal_s_per_gib",
    "repo_upload_s_per_gib", "dispatch_stage_s_per_gib",
    "dispatch_launch_s_per_gib", "dispatch_fetch_s_per_gib",
    "dispatch_decode_s_per_gib", "lanes_per_dispatch", "lanes_direct_share",
    "staged_useful_share", "batch_queue_wait_ms", "dispatches_per_gib",
    "batch_complete_share", "segment_hbm_roofline", "setup_program_load_s",
    "setup_programs_loaded", "ring_dropped_events"}
UNLISTED = {"compiles_in_window", "device_idle_share", "peak_hbm_bytes"}
TRACE_ONLY = {"segment_hbm_roofline", "device_idle_share", "peak_hbm_bytes"}


def test_the_cells_files_are_found_by_name():
    _, entry, cell, config = run.find_cell(CELL)
    assert cell["why"] == entry["why"] and cell["driver"] == "backup_scan"
    assert cell["control"] == "flip_pack_bit" and entry["chips"] == 1
    for name in ("drivers/backup_scan.py", "drivers/scan_check.py",
                 "reference/dedupscan.py", "scanstate.py"):
        assert (ROOT / "benchmark" / name).exists()
    conf = next(c for c in BM["configs"] if c["name"] == entry["config"])
    assert config["reduced"] == conf["reduced"] == [
        "index_blobs", "history_blob_bytes", "volume_bytes", "store_latency"]
    assert config["source"] == conf["source"] and len(conf["source"]) <= 200
    assert config["architecture"] is None and len(config["guarantees"]) == 4
    assert set(config["assumed"]) >= {"churn", "bytes", "retain", "history"}
    assert config["chips"] == 1 and "mover_env" not in config
    # dedup-1t's shape and chunker, uncut
    theirs = json.loads((ROOT / "benchmark/configs/dedup-1t.json")
                        .read_text())
    assert config["shape"] == theirs["shape"]
    assert config["rehearsal"]["shape"] == theirs["rehearsal"]["shape"]
    assert config["chunker"] == theirs["chunker"]
    p = cell["params"]
    assert p["index_blobs"] == config["index_blobs"] >= 65536
    assert p["index_blobs"] in (65536, 131072, 262144, 524288, 1048576)
    assert (p["history_blob_bytes"], p["fresh_bytes"], p["warmup_ops"],
            p["verify_ops"], p["history_sample"], p["trace_seconds"]) \
        == (config["history_blob_bytes"], 134217728, 2, 2, 4096, 15)
    assert cell["rehearsal"]["params"] == {
        "index_blobs": 4096, "fresh_bytes": 1048576, "history_sample": 64}
    assert len(MINE) == 10
    for name in MINE:
        spec = json.loads((ROOT / "benchmark" / "layer_metrics"
                           / f"{name}.json").read_text())
        listed = next(m for m in BM["per_layer"] if m["name"] == name)
        for key in ("name", "unit", "better", "source", "layer", "moves"):
            assert spec[key] == listed[key], (name, key)
        assert (listed["moves"], listed["layer"]) \
            == ("moved_mibps", "repository")
        assert spec["reader"] in ("span_seconds_per_gib", "counter_ratio")
    assert {m["name"] for m in BM["per_layer"]
            if CELL in m.get("workloads", []) and m["name"] not in MINE} \
        == SHARED
    assert {m["name"] for m in BM["end_to_end"]
            if "workloads" not in m or CELL in m["workloads"]} \
        == {"moved_mibps", "setup_s"}


def test_the_cell_came_with_no_edit_to_the_harness():
    text = (ROOT / "benchmark" / "run.py").read_text()
    assert "scan" not in text and "indexed" not in text
    for name in ("counter_ratio", "span_seconds_per_gib"):
        assert "index" not in (ROOT / "benchmark" / "readers"
                               / f"{name}.py").read_text()


@pytest.fixture(scope="module")
def rehearsal():
    done = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"), "--workload",
         CELL, "--seed", "2147483659", "--seconds", "3", "--trace", "1",
         "--size", "rehearsal"],
        capture_output=True, text=True, cwd=str(ROOT), timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "VOLSYNC_BATCH_SEGMENTS": "1"})
    assert done.returncode == 0, done.stderr[-2000:]
    return [json.loads(ln) for ln in done.stdout.splitlines()
            if ln.startswith("{")]


def test_the_traced_rehearsal_is_correct_and_names_every_metric(rehearsal):
    last, info = rehearsal[-1], rehearsal[-2]
    assert last["correct"] is True and last["failed"] == 0
    assert info["operations"] >= 1 and info["in_window"]["compiles"] == 0
    # off a TPU the names are there and no number is; the trace's
    # metrics have no device plane to read on the CPU
    assert (MINE | SHARED | UNLISTED) - TRACE_ONLY <= set(last["metrics"])
    assert all(m["value"] is None for m in last["metrics"].values())
    for spans in info["op_spans"]:
        for name in ("repo.load_index", "repo.index_fetch",
                     "repo.index_decode", "repo.index_insert",
                     "backup.prepare", "repo.open"):
            assert name in spans, name
        parts = sum(spans[f"repo.index_{k}"]
                    for k in ("fetch", "decode", "insert"))
        assert parts <= spans["repo.load_index"] + 0.01
    (window,) = [ln["scan_window"] for ln in rehearsal
                 if "scan_window" in ln]
    (reference,) = [ln["scan_reference"] for ln in rehearsal
                    if "scan_reference" in ln]
    first = 1 + 2  # the first backup and the warm-up operations
    assert len(reference) == first + window["operations"]
    for counts, ref in zip(window["counts"], reference[first:]):
        assert counts["repo.index_loads"] == 2
        assert counts["repo.index_entries"] >= 2 * ref["held_before"]
        assert (counts["repo.blobs_new"], counts["repo.bytes_new"]) \
            == (ref["blobs_new"], ref["bytes_new"])
        assert counts["index.hits"] <= counts["index.queries"]
    limits = {c["check"]: c for c in rehearsal if "check" in c}
    assert {"new_blobs_off", "new_bytes_off", "dedup_off",
            "index_entries_short", "index_ids_missing", "index_ids_extra",
            "history_sample_mismatch", "check_problems",
            "parent_chain_breaks", "new_blob_mismatch"} <= set(limits)
    assert all(c["limit"] == 0 for c in limits.values() if "limit" in c)


def test_the_reference_on_a_hand_made_run():
    chunker = {"min_size": 16384, "avg_size": 32768, "max_size": 131072,
               "seed": 7, "norm_level": 2, "align": 4096}
    rng = np.random.default_rng(11)
    # whole pages: the cuts of a half that starts off the 4 KiB grid
    # would not be the first half's
    kept, first, second = (rng.bytes(n) for n in (614_400, 90_112, 90_112))
    states = [kept + first + kept + first, kept + second + kept + second]
    ops, held = dedupscan.scan({"history"}, states, chunker)
    a, b = ops
    assert a["lengths"] == [n for _, n in gearcdc.cuts(states[0], chunker)]
    assert a["hits_earlier"] == 0 and a["held_before"] == 1
    assert a["blobs_new"] + a["hits_inside"] == len(a["ids"])
    assert a["hits_inside"] > 0 and a["bytes_new"] < len(states[0])
    # the second state finds the bytes that stayed, in both halves, and
    # adds what is fresh once
    assert b["held_before"] == 1 + a["blobs_new"]
    assert b["hits_earlier"] > b["blobs_new"] > 0
    assert b["bytes_new"] < 2 * 90_112 + 4 * chunker["max_size"]
    assert not set(b["new"]) & set(a["new"])
    assert held == {"history"} | set(a["new"]) | set(b["new"])
    assert list(b["new"]) == [bid for i, bid in enumerate(b["ids"])
                              if bid not in a["new"]
                              and bid not in b["ids"][:i]]


def test_a_state_is_the_volume_the_driver_writes(tmp_path):
    shape = {"files": [{"path": "d/image.bin", "bytes": 1 << 20,
                        "repeat_half": True}]}
    fresh = 64 << 10
    files = scanstate.write_volume(tmp_path / "vol", shape, fresh, 5)
    assert files == {"d/image.bin": 1 << 20}
    states = scanstate.file_states(shape, fresh, 5, range(3))
    path = tmp_path / "vol" / "d/image.bin"
    kept = None
    for i, state in enumerate(states):
        if i:
            scanstate.churn(tmp_path / "vol", shape, fresh, 5, i)
        data = path.read_bytes()
        assert data == state.tobytes()
        half = len(data) // 2
        assert data[:half] == data[half:]
        assert kept in (None, data[:half - fresh])
        kept = data[:half - fresh]
    raw, ids = scanstate.history_blobs(5, 100, 64)
    assert len(raw) == 6400 and len(set(ids)) == 100
    assert scanstate.history_blobs(5, 100, 64)[1] == ids
    assert scanstate.history_blobs(6, 100, 64)[1] != ids
