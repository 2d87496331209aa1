"""``restic-sched-10g.incremental``: the cell's files by name, its
metrics' files, a traced rehearsal on the CPU (the same code as a chip
run, the configuration's tiny shape, three syncs of history) and the
plain reference against itself on hand-made states."""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from benchmark import run
from benchmark.reference import blobid, gearcdc, increment

ROOT = Path(run.__file__).resolve().parent.parent
CELL = "restic-sched-10g.incremental"
BM = json.loads((ROOT / "BENCHMARK.json").read_text())
MINE = {m["name"] for m in BM["per_layer"] if m.get("workloads") == [CELL]}
SHARED = {
    "mover_wall_s_per_gib", "op_fixed_s_per_gib", "op_flush_s_per_gib",
    "engine_read_s_per_gib", "engine_read_wait_s_per_gib",
    "engine_device_s_per_gib", "file_self_s_per_gib", "file_open_s_per_gib",
    "host_file_read_s_per_gib", "host_file_hash_s_per_gib",
    "repo_add_self_s_per_gib", "repo_seal_s_per_gib",
    "repo_upload_s_per_gib", "dispatch_stage_s_per_gib",
    "dispatch_launch_s_per_gib", "dispatch_fetch_s_per_gib",
    "dispatch_decode_s_per_gib", "lanes_per_dispatch", "lanes_direct_share",
    "staged_useful_share", "batch_queue_wait_ms", "dispatches_per_gib",
    "segment_hbm_roofline", "setup_program_load_s", "setup_programs_loaded",
    "ring_dropped_events"}
UNLISTED = {"compiles_in_window", "device_idle_share", "peak_hbm_bytes"}
TRACE_ONLY = {"segment_hbm_roofline", "device_idle_share", "peak_hbm_bytes"}


def test_the_cells_files_are_found_by_name():
    _, entry, cell, config = run.find_cell(CELL)
    assert cell["why"] == entry["why"] and cell["driver"] == "backup_sched"
    assert cell["control"] == "flip_pack_bit" and entry["chips"] == 1
    for name in ("drivers/backup_sched.py", "drivers/sched_check.py",
                 "reference/increment.py"):
        assert (ROOT / "benchmark" / name).exists()
    conf = next(c for c in BM["configs"] if c["name"] == entry["config"])
    assert config["reduced"] == conf["reduced"] \
        == ["volume_bytes", "store_latency", "syncs_since_prune"]
    assert config["source"] == conf["source"] and len(conf["source"]) <= 200
    assert config["architecture"] is None and len(config["guarantees"]) == 5
    assert set(config["assumed"]) >= {"churn", "retention",
                                      "syncs_since_prune"}
    # every shape of restic-10g, uncut; the chunker the others carry
    theirs = json.loads((ROOT / "benchmark/configs/restic-10g.json")
                        .read_text())
    assert config["shape"] == theirs["shape"]
    assert config["chunker"] == theirs["chunker"]
    p = cell["params"]
    assert config["mover_env"] == {"FORGET_LAST": str(p["retain_last"])}
    assert (p["history_syncs"], p["retain_last"], p["verify_syncs"],
            p["append_bytes"], p["rewrite_small_share"],
            p["trace_seconds"]) == (18, 18, 3, 8388608, 0.05, 15)
    assert config["syncs_since_prune"] == p["history_syncs"] + 1
    assert sum(f["path"].startswith("mid/")
               for f in config["shape"]["files"]) == 5
    assert len(MINE) == 7
    for name in MINE:
        spec = json.loads((ROOT / "benchmark" / "layer_metrics"
                           / f"{name}.json").read_text())
        listed = next(m for m in BM["per_layer"] if m["name"] == name)
        for key in ("name", "unit", "better", "source", "layer", "moves"):
            assert spec[key] == listed[key], (name, key)
        assert listed["moves"] == "moved_mibps"
        assert spec["reader"] in ("span_seconds_per_gib", "counter_ratio")
        assert (ROOT / "benchmark" / "readers"
                / f"{spec['reader']}.py").exists()
    assert {m["name"] for m in BM["per_layer"]
            if CELL in m.get("workloads", []) and m["name"] not in MINE} \
        == SHARED
    assert {m["name"] for m in BM["end_to_end"]
            if "workloads" not in m or CELL in m["workloads"]} \
        == {"moved_mibps", "setup_s"}


def test_the_cell_came_with_no_edit_to_the_harness():
    text = (ROOT / "benchmark" / "run.py").read_text()
    assert "sched" not in text and "incremental" not in text


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
        for name in ("repo.load_index", "repo.list_snapshots",
                     "backup.parent", "repo.forget", "backup.prepare"):
            assert name in spans, name
        assert spans["backup.prepare"] >= spans["repo.load_index"] / 2
    ref = [ln for ln in rehearsal if "reference_of_sync" in ln]
    assert ref and all(r["read"] == 3 and r["new_blobs"] >= 3 for r in ref)


def _state(root: Path, rels) -> dict:
    return {rel: (os.lstat(root / rel).st_size,
                  os.lstat(root / rel).st_mtime_ns) for rel in rels}


def _volume(root: Path, files: dict) -> dict:
    for rel, data in files.items():
        (root / rel).parent.mkdir(parents=True, exist_ok=True)
        (root / rel).write_bytes(data)
    return _state(root, files)


def test_the_reference_agrees_with_itself_on_a_hand_made_pair(tmp_path):
    chunker = {"min_size": 16384, "avg_size": 32768, "max_size": 131072,
               "seed": 7, "norm_level": 2, "align": 4096}
    rng = np.random.default_rng(11)
    log = rng.bytes(400_000)
    files = {"a/small": rng.bytes(5000), "a/kept": rng.bytes(9000),
             "log": log, "empty": b""}
    first = increment.increment(tmp_path, {}, _volume(tmp_path, files), set(),
                                chunker)
    assert first["read"] == ["a/kept", "a/small", "log"]
    assert first["unchanged"] == [] and "empty" not in first["files"]
    assert first["files"]["a/small"] == [(blobid.blob_id(files["a/small"]),
                                          5000)]
    assert [n for _, n in first["files"]["log"]] \
        == [n for _, n in gearcdc.cuts(log, chunker)]
    assert sum(first["new"].values()) == first["bytes_read"] == 414_000
    # the second state: one file rewritten at its size, the log grown,
    # one file new with bytes the repository holds already
    before = _state(tmp_path, files)
    os.utime(tmp_path / "a/small", ns=(1, before["a/small"][1] + 1))
    grown = log + rng.bytes(50_000)
    (tmp_path / "log").write_bytes(grown)
    (tmp_path / "copy").write_bytes(files["a/kept"])
    after = _state(tmp_path, [*files, "copy"])
    second = increment.increment(tmp_path, before, after, set(first["new"]),
                                 chunker)
    assert second["unchanged"] == ["a/kept", "empty"]
    assert second["read"] == ["a/small", "copy", "log"]
    # a rewrite at the old bytes and a copy add nothing; the append adds
    # from the log's last cut on
    old_cuts = first["files"]["log"]
    assert second["files"]["log"][:len(old_cuts) - 1] == old_cuts[:-1]
    assert set(second["new"]) == {bid for bid, _ in second["files"]["log"]} \
        - {bid for bid, _ in old_cuts}
    assert sum(second["new"].values()) \
        == len(grown) - sum(n for _, n in old_cuts[:-1])
    assert second["bytes_read"] == 5000 + 9000 + len(grown)
    # with nothing held, everything read is new, each id once
    alone = increment.increment(tmp_path, before, after, set(), chunker)
    assert sum(alone["new"].values()) == second["bytes_read"]
    assert list(alone["new"]) == [bid for rel in alone["read"]
                                  for bid, _ in alone["files"][rel]]
