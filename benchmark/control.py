#!/usr/bin/env python3
"""The control of a cell: the same run with the one guarantee broken
that the cell's file names (``control``), on several seeds in one
process. Every one has to come out with ``correct`` false; the exit
code is 0 only then. The benchmark's own runs never run this.

    python3 benchmark/control.py --workload <name> --seeds 1,2,3 --seconds <s>
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    from benchmark import run

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--size", choices=("cell", "rehearsal"), default="cell")
    args = ap.parse_args(argv)
    fault = run.find_cell(args.workload)[2]["control"]
    passed_wrongly = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        result = run.run_cell(args.workload, seed, args.seconds, False,
                              args.size, fault=fault)
        print(json.dumps({"control": fault, "seed": seed,
                          "correct": result["correct"],
                          "attempted": result["attempted"],
                          "failed": result["failed"]}), flush=True)
        passed_wrongly += bool(result["correct"])
    return 1 if passed_wrongly else 0


if __name__ == "__main__":
    try:
        rc = main()
    except BaseException:  # noqa: BLE001 — a crash is reported as one
        import traceback

        traceback.print_exc()
        rc = 2
    sys.stdout.flush()
    os._exit(rc)
