"""``dedup-1t.backup-mesh4``: a rehearsal of the cell on four virtual
CPU devices (the same code as a chip run, the configuration's tiny
shape), its control, and the warm plan against what the engine ran."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from benchmark import run

ROOT = Path(run.__file__).resolve().parent.parent
CELL = "dedup-1t.backup-mesh4"
MiB = 1 << 20


def _script(script, *argv, devices=4):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}"}
    return subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / script), *argv],
        capture_output=True, text=True, cwd=str(ROOT), timeout=600, env=env)


def _lines(done):
    return [json.loads(ln) for ln in done.stdout.splitlines()
            if ln.startswith("{")]


@pytest.fixture(scope="module")
def rehearsal():
    done = _script("run.py", "--workload", CELL, "--seed", "2147483659",
                   "--seconds", "1", "--trace", "1", "--size", "rehearsal")
    assert done.returncode == 0, done.stderr[-2000:]
    return _lines(done)


def test_the_rehearsal_is_correct_and_names_the_mesh_metrics(rehearsal):
    last, info = rehearsal[-1], rehearsal[-2]
    assert last["correct"] is True and last["failed"] == 0
    assert last["device"]["count"] == 4
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    mine = {m["name"] for m in bm["per_layer"]
            if m.get("workloads") == [CELL]}
    assert len(mine) == 6
    # off a TPU the names are there and no number is; the trace's
    # metric has no device plane to read on the CPU and is left out
    assert mine - {"mesh_hbm_roofline"} <= set(last["metrics"])
    assert all(m["value"] is None for m in last["metrics"].values())
    assert info["in_window"]["compiles"] == 0
    checks = {c["check"]: c for c in rehearsal if "check" in c}
    assert checks["mesh_dispatches"]["value"] >= 1
    assert checks["mesh_shards_off_chips_a_dispatch"]["value"] == 0
    assert checks["stored_ratio"]["value"] <= checks["stored_ratio"]["limit"]
    assert checks["chunk_boundary_mismatches"]["value"] == 0
    assert checks["blob_id_mismatches"]["value"] == 0


def test_the_warm_plan_lists_every_program_the_rehearsal_ran(rehearsal):
    plan = next(ln for ln in rehearsal if "warm_plan" in ln)
    ran = next(ln for ln in rehearsal if "programs_after_warm_up" in ln)
    assert plan["shards"] == 4
    assert ran["programs_after_warm_up"]
    assert {tuple(k) for k in ran["programs_after_warm_up"]} \
        <= {tuple(k) for k in plan["warm_plan"]}


def test_the_control_reads_false_in_rehearsal():
    done = _script("control.py", "--workload", CELL, "--seeds", "3,4",
                   "--seconds", "1", "--size", "rehearsal")
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-2000:]
    controls = [ln for ln in _lines(done) if "control" in ln]
    assert [c["correct"] for c in controls] == [False, False]


def test_fewer_devices_than_the_cells_chips_read_incorrect():
    """On two devices the mesh has two shards: the run completes and
    is not ``correct`` (a chip run exits 3 before it gets here)."""
    done = _script("run.py", "--workload", CELL, "--seed", "5", "--seconds",
                   "1", "--trace", "0", "--size", "rehearsal", devices=2)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = _lines(done)
    assert lines[-1]["correct"] is False and lines[-1]["failed"] == 0
    off = next(c for c in lines
               if c.get("check") == "mesh_shards_off_chips_a_dispatch")
    assert off["value"] > 0


def test_exits_3_off_a_four_chip_host():
    done = _script("run.py", "--workload", CELL, "--seed", "1",
                   "--seconds", "1", "--trace", "0")
    assert done.returncode == 3
    assert '"correct"' not in done.stdout


def _four_shards(params):
    """The hasher's layout functions at four shards, without a mesh
    (this process has one CPU device)."""
    from volsync_tpu.parallel.sharded_chunker import MeshChunkHasher

    hasher = MeshChunkHasher.__new__(MeshChunkHasher)
    hasher.params, hasher.n_shards = params, 4
    return hasher


def test_the_cells_warm_plan():
    """What set-up loads today, from the configuration's one size: the
    full segments' program and the last segment's (8 MiB of new bytes
    and a carried tail of 0 to 8 MiB: a 2 MiB shard only if the segment
    before it was cut at its very end)."""
    from volsync_tpu.engine.chunker import params_from_config
    from volsync_tpu.repo.repository import DEFAULT_CHUNKER

    from benchmark import volumes, warm_mesh

    params = params_from_config(DEFAULT_CHUNKER)
    _, _, _, config = run.find_cell(CELL)
    sizes = [n for _, n, _ in volumes.plan(config["shape"], 1)]
    assert sizes == [2 << 30]
    plan = warm_mesh.mesh_plan(sizes, params, _four_shards(params))
    assert [(s // MiB, eof) for s, _, _, eof in plan] == \
        [(2, True), (4, True), (32, False)]


@pytest.mark.parametrize("nbytes", [700_000, 100 * MiB, 120 * MiB,
                                    240 * MiB + 5, 2 << 30])
def test_the_plan_holds_every_program_the_stream_stages(nbytes, monkeypatch):
    """The engine's own stream over zeros, with a hasher of four shards
    that leaves tails of every length: each staged (shard length, eof)
    is in ``mesh_plan`` for that size."""
    import numpy as np
    from volsync_tpu.engine import chunker
    from volsync_tpu.repo.repository import DEFAULT_CHUNKER

    from benchmark import warm_mesh

    monkeypatch.setenv("VOLSYNC_TPU_READAHEAD", "0")
    params = chunker.params_from_config(DEFAULT_CHUNKER)
    rng = np.random.default_rng(nbytes)
    layout = _four_shards(params)
    staged = set()

    class Tails:
        n_shards = 4
        stream_segment_size = layout.stream_segment_size
        buffer_bucket = layout.buffer_bucket

        def process(self, arr, eof):
            n = len(arr)
            if n > params.min_size:
                staged.add((layout.shard_bucket(n), eof))
            if eof or n <= 4096:
                return [(0, n, "x")] if n else []
            tail = rng.choice([0, 4096, params.max_size - 4096,
                               4096 * int(rng.integers(params.max_size
                                                       // 4096))])
            return [(0, n - min(int(tail), n - 4096), "x")]

    for _ in range(3):
        left = [nbytes]

        def read(k):
            k = min(k, left[0])
            left[0] -= k
            return bytes(k)

        for _batch in chunker.stream_chunk_batches(read, params,
                                                   hasher=Tails()):
            pass
    planned = {(s, eof) for s, _, _, eof
               in warm_mesh.mesh_plan([nbytes], params, layout)}
    assert staged and staged <= planned
