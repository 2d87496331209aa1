"""The harness is driven by data, prints the contract's line, and
measures a TPU or nothing."""

import json
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from benchmark import run

ROOT = Path(run.__file__).resolve().parent.parent
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _run_py(*argv):
    return subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"), *argv],
        capture_output=True, text=True, cwd=str(ROOT), timeout=300,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})


def test_a_cell_a_configuration_and_a_metric_come_as_files_only(tmp_path):
    """New files and one new entry each: no file that was there is
    edited, and the run reads the new metric from the new cell."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p: p.read_bytes() for p in tmp_path.rglob("*") if p.is_file()
              and p.name != "BENCHMARK.json"}
    here = tmp_path / "benchmark"
    config = json.loads((here / "configs" / "smallfiles.json").read_text())
    config["name"] = "tinyfiles"
    (here / "configs" / "tinyfiles.json").write_text(json.dumps(config))
    cell = json.loads(
        (here / "workloads" / "smallfiles.backup.json").read_text())
    cell.update(name="tinyfiles.backup", config="tinyfiles")
    (here / "workloads" / "tinyfiles.backup.json").write_text(
        json.dumps(cell))
    (here / "layer_metrics" / "dedup_query_s_per_gib.json").write_text(
        json.dumps({"name": "dedup_query_s_per_gib", "unit": "s/GiB",
                    "better": "lower", "source": "program_span",
                    "layer": "repository", "moves": "moved_mibps",
                    "reader": "span_seconds_per_gib",
                    "args": {"spans": ["repo.dedup_query"]}}))
    bm = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bm["configs"].append({"name": "tinyfiles", "source": "test",
                          "file": "benchmark/configs/tinyfiles.json",
                          "reduced": [], "why": "test"})
    bm["workloads"].append({"name": "tinyfiles.backup",
                            "config": "tinyfiles", "traffic": "backup",
                            "chips": 1, "why": "test"})
    bm["per_layer"].append({"name": "dedup_query_s_per_gib",
                            "unit": "s/GiB", "better": "lower",
                            "source": "program_span", "layer": "repository",
                            "moves": "moved_mibps",
                            "workloads": ["tinyfiles.backup"]})
    for m in bm["per_layer"] + bm["end_to_end"]:
        if "workloads" in m and "smallfiles.backup" in m["workloads"]:
            m["workloads"].append("tinyfiles.backup")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))

    result = run.run_cell("tinyfiles.backup", 5, 1.0, True,
                          size="rehearsal", root=tmp_path)
    assert result["correct"] and result["attempted"] > 0
    assert "dedup_query_s_per_gib" in result["metrics"]
    assert "repo_seal_s_per_gib" in result["metrics"]
    assert all(p.read_bytes() == body for p, body in before.items())


@pytest.mark.parametrize("trace", [0, 1])
def test_last_line_has_the_contracts_keys(trace):
    done = _run_py("--workload", "smallfiles.backup", "--seed",
                   "2147483659", "--seconds", "1", "--trace", str(trace),
                   "--size", "rehearsal")
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    want = CONTRACT_KEYS | ({"breakdown"} if trace else set())
    assert set(last) == want
    assert last["correct"] is True and last["failed"] == 0
    assert set(last["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    kind = "per_layer" if trace else "end_to_end"
    assert set(last["metrics"]) <= {m["name"] for m in bm[kind]}
    # off a TPU the names are there and no number is
    assert last["device"]["platform"] == "cpu"
    assert all(m["value"] is None for m in last["metrics"].values())
    if trace:
        assert {"busy_s", "window_s"} <= set(last["device"])


def test_refuses_a_device_that_is_no_tpu():
    done = _run_py("--workload", "smallfiles.backup", "--seed", "1",
                   "--seconds", "1", "--trace", "0")
    assert done.returncode != 0
    assert "not 'tpu'" in done.stderr
    assert not any(line.startswith("{") and '"correct"' in line
                   for line in done.stdout.splitlines())


def test_refuses_a_checkout_without_the_program(tmp_path):
    """BENCHMARK.json and benchmark/ alone measure nothing."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "smallfiles.backup", "--seed", "1", "--seconds", "1", "--trace",
         "0", "--size", "rehearsal"], capture_output=True, text=True,
        cwd=str(tmp_path), timeout=120,
        env={k: v for k, v in __import__("os").environ.items()
             if k != "PYTHONPATH"} | {"JAX_PLATFORMS": "cpu"})
    assert done.returncode != 0
    assert '"correct"' not in done.stdout


def test_every_cell_has_its_files():
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bm["workloads"]:
        _, entry, cell, config = run.find_cell(w["name"])
        assert cell["why"] == entry["why"]
        assert (ROOT / "benchmark" / "drivers"
                / f"{cell['driver']}.py").exists()
        assert config["reduced"] == next(
            c["reduced"] for c in bm["configs"] if c["name"] == w["config"])
    for m in bm["per_layer"]:
        spec = json.loads((ROOT / "benchmark" / "layer_metrics"
                           / f"{m['name']}.json").read_text())
        assert {k: spec[k] for k in ("unit", "better", "source", "layer",
                                     "moves")} == \
            {k: m[k] for k in ("unit", "better", "source", "layer", "moves")}
        assert (ROOT / "benchmark" / "readers"
                / f"{spec['reader']}.py").exists()


MiB = 1 << 20


@pytest.mark.parametrize("nbytes", [700_000, 32 * MiB, 48 * MiB, 80 * MiB,
                                    96 * MiB + 5, 512 * MiB])
def test_the_warm_plan_holds_every_bucket_the_engine_stages(nbytes,
                                                            monkeypatch):
    """The engine's own segment fill, driven over a stream of zeros with
    a hasher that leaves tails of every length: each staged length's
    bucket is in ``warm.file_buckets``' set for that size."""
    import numpy as np
    from volsync_tpu.engine import chunker
    from volsync_tpu.repo.repository import DEFAULT_CHUNKER

    from benchmark import warm

    monkeypatch.setenv("VOLSYNC_TPU_READAHEAD", "0")
    params = chunker.params_from_config(DEFAULT_CHUNKER)
    rng = np.random.default_rng(nbytes)
    staged = []

    class Tails:
        def process(self, arr, eof):
            n = len(arr)
            staged.append(n)
            if eof or n <= 4096:
                return [(0, n, "x")] if n else []
            tail = rng.choice([0, 4096, params.max_size - 4096,
                               4096 * int(rng.integers(params.max_size
                                                       // 4096))])
            return [(0, n - min(int(tail), n - 4096), "x")]

    for _ in range(4):
        left = [nbytes]

        def read(k):
            k = min(k, left[0])
            left[0] -= k
            return bytes(k)

        for _batch in chunker.stream_chunk_batches(read, params,
                                                   hasher=Tails()):
            pass
    fill = chunker._SegmentFill(lambda n: b"", 32 * MiB,
                                params.max_size).target
    planned = warm.file_buckets(nbytes, fill, params.max_size,
                                chunker._buffer_bucket)
    assert {chunker._buffer_bucket(n) for n in staged if n} <= planned


def test_the_cells_warm_plans():
    """What set-up loads today, from the cells' own sizes."""
    from volsync_tpu.engine.chunker import params_from_config
    from volsync_tpu.repo.repository import DEFAULT_CHUNKER

    from benchmark import volumes, warm
    from benchmark.drivers import stream

    params = params_from_config(DEFAULT_CHUNKER)
    plans = {}
    for name in ("restic-10g.backup", "smallfiles.backup"):
        _, _, _, config = run.find_cell(name)
        sizes = [n for _, n, _ in volumes.plan(config["shape"], 1)]
        plans[name] = warm.backup_plan(sizes, params)
    assert plans["smallfiles.backup"] == [(1, MiB), (2, MiB), (4, MiB)]
    assert {b // MiB for _, b in plans["restic-10g.backup"]} == \
        {1, 8, 16, 24, 32, 40, 48}
    assert {n for n, _ in plans["restic-10g.backup"]} == {1, 2, 4}
    _, _, cell, _ = run.find_cell("fleet-100.stream")
    sizes = {n for c in stream.deal_sizes(cell["params"], 1) for n in c}
    assert warm.stream_plan(sorted(sizes), {}, 48) == sorted(
        (n, b * MiB) for b in (2, 4) for n in (1, 2, 4, 8))


def test_a_traced_run_of_a_cell_with_trace_seconds_is_cut_to_them():
    done = _run_py("--workload", "fleet-100.stream", "--seed", "7",
                   "--seconds", "6", "--trace", "1", "--size", "rehearsal")
    assert done.returncode == 0, done.stderr[-2000:]
    lines = done.stdout.strip().splitlines()
    info = json.loads(lines[-2])
    assert info["seconds"] == 6 and info["window"] == 3
    assert json.loads(lines[-1])["correct"] is True
