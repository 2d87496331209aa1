"""``volsync trace`` — flight-recorder access for the embedded CLI.

Verbs:

- ``volsync trace dump [--out FILE]`` — export the in-process flight
  recorder as Chrome-trace-event JSON (load the file in Perfetto /
  chrome://tracing). Without ``--out`` the JSON prints to stdout.
- ``volsync trace summary`` — the span registry as a table, split by
  outcome, with each stage's self seconds (its time less the spans that
  closed inside it on the same thread) on its first row, and the
  counters below, so a REPL/operator session can see where time went
  without leaving the terminal.

Like ``volsync lint``, the verb dispatches before the operator runtime
boots: reading the recorder must work in a half-broken process (that is
when you want the flight recorder). The recorder is process-local —
``dump`` here exports the CLI process's own spans; for a running
server, hit the ``/debug/trace`` endpoint on its MetricsServer.
"""

from __future__ import annotations

import argparse
import json


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="volsync trace",
        description="Inspect/export the in-process span flight recorder")
    sub = parser.add_subparsers(dest="verb", required=True)
    dump = sub.add_parser("dump", help="export Chrome-trace-event JSON")
    dump.add_argument("--out", default=None,
                      help="file to write (default: print to stdout)")
    sub.add_parser("summary", help="span totals by stage and outcome")
    return parser


def main(argv, out=print) -> int:
    from volsync_tpu.obs import (chrome_trace, counter_totals, dump_trace,
                                 span_self_totals, span_totals)

    args = build_parser().parse_args(list(argv))
    if args.verb == "dump":
        if args.out:
            path = dump_trace(path=args.out)
            out(f"trace written to {path}")
        else:
            out(json.dumps(chrome_trace(), indent=2))
        return 0
    totals = span_totals(by_outcome=True)
    if not totals:
        out("no spans recorded")
        return 0
    # self time is kept a stage, not an outcome: printed on the stage's
    # first row
    own = {stage: f"{secs:.4f}"
           for stage, (_, secs) in span_self_totals().items()}
    out(f"{'stage':<32} {'outcome':<8} {'count':>8} {'seconds':>12} "
        f"{'self':>12}")
    for (stage, outcome), (count, secs) in sorted(totals.items()):
        out(f"{stage:<32} {outcome:<8} {count:>8} {secs:>12.4f} "
            f"{own.pop(stage, ''):>12}")
    for name, n in sorted(counter_totals().items()):
        out(f"{name:<32} {'counter':<8} {n:>8}")
    return 0
