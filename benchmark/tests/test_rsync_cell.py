"""``rsync-1g.push``: a rehearsal of the cell on the CPU (the same code
as a chip run, the configuration's tiny shape, the program's window and
part shrunk by the cell's ``rehearsal.params``), its control, the plain
reference against itself, and the cell's files by name."""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from benchmark import run
from benchmark.reference import rsyncdelta

ROOT = Path(run.__file__).resolve().parent.parent
CELL = "rsync-1g.push"
BM = json.loads((ROOT / "BENCHMARK.json").read_text())
MINE = {m["name"] for m in BM["per_layer"] if m.get("workloads") == [CELL]}
SHARED = {"mover_wall_s_per_gib", "setup_program_load_s",
          "setup_programs_loaded", "ring_dropped_events"}
UNLISTED = {"compiles_in_window", "device_idle_share", "peak_hbm_bytes"}
TRACE_ONLY = {"delta_scan_hbm_roofline", "delta_sig_hbm_roofline",
              "delta_md5_hbm_roofline", "device_idle_share",
              "peak_hbm_bytes"}


def _script(script, *argv):
    return subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / script), *argv],
        capture_output=True, text=True, cwd=str(ROOT), timeout=900,
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
    assert info["operations"] >= 2  # both transitions
    # off a TPU the names are there and no number is; the trace's
    # metrics have no device plane to read on the CPU and the memory
    # stat no backend to ask: they are left out
    assert len(MINE) == 16
    assert (MINE | SHARED | UNLISTED) - TRACE_ONLY <= set(last["metrics"])
    assert all(m["value"] is None for m in last["metrics"].values())
    checks = {c["check"]: c for c in rehearsal if "check" in c}
    for name in ("calls_failed", "literal_bytes_off", "files_new_off",
                 "pruned_off", "files_basis_off", "files_full",
                 "staged_short", "files_missing", "files_extra",
                 "size_mismatch", "content_mismatch", "meta_mismatch",
                 "temporaries_left"):
        assert checks[name] == {"check": name, "value": 0, "limit": 0}
    assert checks["files_compared"]["value"] >= 1
    assert checks["pushes"]["value"] == info["operations"]
    # a steady-state push: a basis for all but the 1% of files the
    # other state lacks, literals a few per cent of the bytes
    for ref in next(ln for ln in rehearsal if "reference" in ln)["reference"]:
        assert ref["files_new"] == 1 and ref["pruned"] == 1
        assert 0 < ref["literal_bytes"] < ref["bytes"] // 10
        assert ref["staged_floor"] > ref["bytes"] // 2
    assert info["in_window"]["compiles"] == 0
    for spans in info["op_spans"]:
        assert "rsync.sig_wait" in spans and "delta.launch" in spans


def test_the_control_reads_false_in_rehearsal():
    done = _script("control.py", "--workload", CELL, "--seeds", "3,4",
                   "--seconds", "1", "--size", "rehearsal")
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-2000:]
    lines = _lines(done)
    assert [c["correct"] for c in lines if "control" in c] == [False, False]
    assert sum(c.get("check") == "content_mismatch" and c["value"] == 1
               for c in lines) == 2


@pytest.mark.parametrize("kind", ["rewrite", "insertion", "truncation",
                                  "moved", "no_basis", "short"])
def test_the_reference_agrees_with_itself_under_apply(kind, monkeypatch):
    """``apply`` of the reference's own ops rebuilds the sender's file,
    at a window of a few blocks too (a file spans several)."""
    rng = np.random.default_rng(7)
    old = rng.bytes(120_000)
    new = {"rewrite": old[:50_000] + rng.bytes(3000) + old[53_000:],
           "insertion": old[:60_000] + rng.bytes(1001) + old[60_000:],
           "truncation": old[:77_777],
           "moved": old[60_000:] + old[:60_000],
           "no_basis": rng.bytes(120_000),
           "short": old[:1000]}[kind]
    out = []
    for window in (64 << 20, 1 << 15):
        monkeypatch.setattr(rsyncdelta, "WINDOW", window)
        block = rsyncdelta.block_len_for(len(new))
        ops = rsyncdelta.delta(new, rsyncdelta.signature(old, block))
        assert rsyncdelta.apply(ops, old, new, block) == new
        out.append(ops)
    assert out[0] == out[1]
    literal = rsyncdelta.literal_bytes(out[0])
    if kind in ("rewrite", "insertion"):
        assert 0 < literal <= 3000 + 2 * 4096
    if kind == "no_basis":
        assert literal == len(new)
    if kind == "moved":
        assert literal < 2 * 4096


def test_the_cells_files_are_found_by_name():
    _, entry, cell, config = run.find_cell(CELL)
    assert cell["why"] == entry["why"] and cell["driver"] == "rsync_push"
    assert cell["control"] == "flip_pushed_bit" and entry["chips"] == 1
    for name in ("drivers/rsync_push.py", "drivers/rsync_check.py",
                 "reference/rsyncdelta.py", "churn_pages.py"):
        assert (ROOT / "benchmark" / name).exists()
    conf = next(c for c in BM["configs"] if c["name"] == entry["config"])
    assert config["reduced"] == conf["reduced"]
    assert set(conf["reduced"]) <= {"peers", "link", "volume_bytes"}
    assert config["source"] == conf["source"]
    assert config["architecture"] is None and len(config["guarantees"]) == 4
    assert config["mover_env"] == {"VOLSYNC_SYNC_PROTO": "delta"}
    theirs = json.loads((ROOT / "benchmark" / "configs"
                         / "restic-10g.json").read_text())["shape"]
    if "volume_bytes" not in conf["reduced"]:
        # restic-10g's volume as it stands: three movers on one volume
        assert config["shape"] == theirs
    assert config["shape"]["small"]["size_seed"] == theirs["small"]["size_seed"]
    assert cell["params"]["page_share"] == 0.01
    assert cell["params"]["page_bytes"] == 16384
    assert cell["params"]["insert"]["bytes"] == 1000
    assert cell["params"]["insert"]["path"] in {
        f["path"] for f in config["shape"]["files"]}
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
    assert "rsync" not in text
