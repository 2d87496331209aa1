#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in ``BENCHMARK.json`` and its files by name
(``workloads/<cell>.json``, ``configs/<config>.json``,
``drivers/<driver>.py``, ``layer_metrics/<metric>.json``,
``readers/<reader>.py``), sets up, warms up, measures for ``--seconds``,
checks what the timed path produced, and prints one JSON object as its
last line. Exits non-zero, with no result, where JAX finds no TPU or
fewer chips than the cell asks for. ``--size rehearsal`` runs the cell's
tiny sizes through the same code on whatever device there is and prints
no number under a metric's name off a TPU.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()  # process start, as near as Python lets us

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

GiB = 1 << 30


class Ctx:
    """What a driver gets: the cell's parameters, its configuration
    (file and volume shape), the seed, a work directory, the run's child
    processes."""

    def __init__(self, params, shape, config, seed, work, children, tracer):
        self.params, self.shape, self.config = params, shape, config
        self.seed, self.work, self.children = seed, work, children
        self.tracer = tracer
        self._exit = []

    def annotate(self, name: str):
        if self.tracer is None:
            return contextlib.nullcontext()
        return self.tracer.annotate(name)

    def on_exit(self, fn) -> None:
        self._exit.append(fn)

    def close(self) -> None:
        while self._exit:
            self._exit.pop()()


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(name: str, root: Path = ROOT):
    """(BENCHMARK.json, its entry for the cell, the cell's file, the
    configuration's file) — all by name."""
    bm = load_json(root / "BENCHMARK.json")
    entry = next((w for w in bm["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    here = root / "benchmark"
    cell = load_json(here / "workloads" / f"{name}.json")
    conf = next(c for c in bm["configs"] if c["name"] == entry["config"])
    config = load_json(root / conf["file"])
    if cell["config"] != entry["config"] or cell["chips"] != entry["chips"]:
        raise SystemExit(f"{name}: the cell's file and BENCHMARK.json "
                         f"disagree on config or chips")
    return bm, entry, cell, config


def metrics_for(bm: dict, kind: str, cell_name: str) -> list[dict]:
    return [m for m in bm[kind]
            if "workloads" not in m or cell_name in m["workloads"]]


def peaks_for(kind: str, root: Path = ROOT) -> dict:
    peaks = load_json(root / "benchmark" / "peaks.json")
    if kind not in peaks:
        raise SystemExit(f"no peaks for device kind {kind!r} in "
                         f"benchmark/peaks.json: add them with their source")
    return peaks[kind]


def layer_values(bm, cell_name, obs, root: Path = ROOT) -> dict:
    out = {}
    for m in metrics_for(bm, "per_layer", cell_name):
        spec = load_json(root / "benchmark" / "layer_metrics"
                         / f"{m['name']}.json")
        reader = importlib.import_module(
            f"benchmark.readers.{spec['reader']}")
        value = reader.read(spec.get("args", {}), obs)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def check_passes(c: dict) -> bool:
    if "limit" in c:
        return c["value"] <= c["limit"]
    return c["value"] >= c["at_least"]


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             size: str = "cell", fault: str | None = None,
             root: Path = ROOT) -> dict:
    """The whole of one run; ``root`` is where BENCHMARK.json and the
    data files are read from (the code is this checkout's)."""
    bm, entry, cell, config = find_cell(name, root)
    rehearsal = size == "rehearsal"
    params = dict(cell["params"])
    shape = dict(config.get("shape", {}))
    if rehearsal:
        params.update(cell["rehearsal"].get("params", {}))
        shape.update(config.get("rehearsal", {}).get("shape", {}))

    from volsync_tpu import compile_cache

    cache_dir = compile_cache.configure()  # before the first use of JAX
    from benchmark import end_to_end, observe
    from benchmark.procs import Children

    try:
        device = observe.device_block(entry["chips"], allow_other=rehearsal)
    except observe.NoAccelerator as ex:
        print(f"benchmark: {ex}", file=sys.stderr)
        raise SystemExit(3)
    measured = device["platform"] == "tpu"
    peaks = peaks_for(device["kind"], root) if measured else {}
    counter = observe.CompileCounter().install()
    driver = importlib.import_module(f"benchmark.drivers.{cell['driver']}")

    from volsync_tpu.obs import (copies_by_site, reset_copies, reset_spans,
                                 span_totals)

    work = Path(tempfile.mkdtemp(prefix="bench_"))
    children = Children()
    tracer = None
    if trace:
        from benchmark.tracer import Tracer

        tracer = Tracer(str(work / "xplane"))
    ctx = Ctx(params, shape, config, seed, work, children, tracer)
    try:
        marks = {"start_to_device_s": time.perf_counter() - _T0}
        state = driver.setup(ctx)
        marks["driver_setup_s"] = time.perf_counter() - _T0
        if fault and driver.FAULT_AT == "before_warmup":
            driver.inject(state, fault)
        driver.warmup(state)
        marks["warmup_done_s"] = time.perf_counter() - _T0
        if fault and driver.FAULT_AT == "before_run":
            driver.inject(state, fault)
        warm = counter.snapshot()
        n_warm = len(counter.programs_since(0))
        reset_spans()
        reset_copies()
        if tracer:
            tracer.start()
        setup_s = time.perf_counter() - _T0
        window = seconds
        if tracer and "trace_seconds" in params:
            # a cell whose window gives the profiler more device events
            # than it keeps traces a shorter one (the cell's file says)
            window = min(seconds, float(params["trace_seconds"]))
        obs = driver.run(state, window)
        traced = tracer.stop() if tracer else None
        in_window = {k: v - warm[k] for k, v in counter.snapshot().items()}
        in_window["programs"] = counter.programs_since(n_warm)
        warm["programs"] = counter.programs_since(0)[:n_warm]
        obs.update({
            "spans": span_totals(), "copies": copies_by_site(),
            "monitoring": in_window, "trace": traced, "peaks": peaks,
            "gib_moved": end_to_end.moved_bytes(obs) / GiB,
            "memory": {"peak_bytes_in_use":
                       observe.memory_stat("peak_bytes_in_use")},
        })
        if fault and driver.FAULT_AT == "after_run":
            driver.inject(state, fault)
        t_verify = time.perf_counter()
        attempted, failed, checks = driver.verify(state)
        verify_s = time.perf_counter() - t_verify
    finally:
        try:
            ctx.close()
        finally:
            children.stop()
            shutil.rmtree(work, ignore_errors=True)

    for c in checks:
        print(json.dumps(c), flush=True)
    correct = attempted > 0 and failed == 0 and all(map(check_passes, checks))
    print(json.dumps({
        "cell": name, "seed": seed, "seconds": seconds, "window": window,
        "size": size,
        "operations": len(obs["ops"]), "gib_moved": obs["gib_moved"],
        "op_seconds": [round(op["t_done"] - op["t_start"], 3)
                       for op in obs["ops"][:12]],
        "op_spans": [op.get("spans") for op in obs["ops"][:6]],
        "setup_marks": marks,
        "latency_samples": len(obs.get("latencies_ms", [])),
        "setup_s": setup_s, "verify_s": verify_s,
        "compile_cache_dir": cache_dir, "warm_up": warm,
        "in_window": in_window,
        "trace_planes": traced["planes"] if traced else None,
        "trace_programs": traced["programs"] if traced else None}),
        flush=True)

    if trace:
        metrics = layer_values(bm, name, obs, root)
    else:
        metrics = {}
        for m in metrics_for(bm, "end_to_end", name):
            value = (setup_s if m["name"] == "setup_s"
                     else end_to_end.METRICS[m["name"]](obs))
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    if not measured:  # a rehearsal off the chip: names, no numbers
        metrics = {k: {"value": None, "unit": v["unit"]}
                   for k, v in metrics.items()}
    device["memory_peak_bytes"] = obs["memory"]["peak_bytes_in_use"]
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if traced:
        device["busy_s"] = traced["busy_s"] if measured else None
        device["window_s"] = traced["window_s"]
        result["breakdown"] = {"device_ops": traced["device_ops"],
                               "idle_gaps": traced["idle_gaps"]}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--size", choices=("cell", "rehearsal"), default="cell")
    args = ap.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), args.size)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        rc = main()
    except SystemExit as ex:
        rc = ex.code if isinstance(ex.code, int) else 2
        if not isinstance(ex.code, int) and ex.code:
            print(ex.code, file=sys.stderr)
    except BaseException:  # noqa: BLE001 — every failure is fatal: no result
        import traceback

        traceback.print_exc()
        rc = 1
    sys.stdout.flush()
    sys.stderr.flush()
    # the program's daemon dispatch threads may sit in a device call;
    # the children are already stopped and waited for
    os._exit(rc)
