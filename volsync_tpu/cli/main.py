"""``volsync`` CLI frontend (the kubectl-volsync plugin analogue).

Command tree mirrors cmd/root.go:44-60:

    volsync replication create|delete|schedule|set-source|set-destination|sync
    volsync migration   create|delete|rsync

Parsing is argparse (cobra analogue); verbs dispatch to ReplicationCLI /
MigrationCLI over named cluster contexts. ``python -m volsync_tpu.cli``
runs in demo mode with one in-process cluster context ("default") booted
from the operator runtime; tests and the operator embed ``run()`` with
real contexts.
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

from volsync_tpu.api.common import CopyMethod
from volsync_tpu.cli.migration import MigrationCLI
from volsync_tpu.cli.relationship import RelationshipError
from volsync_tpu.cli.replication import ReplicationCLI

DEFAULT_CONFIG_DIR = Path.home() / ".volsync"


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="volsync",
        description="Drive VolSync-TPU replication/migration relationships",
    )
    parser.add_argument("--config-dir", default=str(DEFAULT_CONFIG_DIR),
                        help="directory holding relationship files")
    sub = parser.add_subparsers(dest="group", required=True)

    rep = sub.add_parser("replication",
                         help="asynchronous volume replication")
    repsub = rep.add_subparsers(dest="verb", required=True)

    r_create = repsub.add_parser("create")
    r_create.add_argument("name")

    r_setdst = repsub.add_parser("set-destination")
    r_setdst.add_argument("name")
    r_setdst.add_argument("--cluster", default="default")
    r_setdst.add_argument("--namespace", default="default")
    r_setdst.add_argument("--dest-name", required=True)
    r_setdst.add_argument("--copy-method", default="Snapshot",
                          choices=[m.value for m in CopyMethod])
    r_setdst.add_argument("--service-type", default=None)
    r_setdst.add_argument("--capacity", type=int, default=None)
    r_setdst.add_argument("--access-modes", nargs="*", default=None)

    r_setsrc = repsub.add_parser("set-source")
    r_setsrc.add_argument("name")
    r_setsrc.add_argument("--cluster", default="default")
    r_setsrc.add_argument("--namespace", default="default")
    r_setsrc.add_argument("--pvcname", required=True)
    r_setsrc.add_argument("--copy-method", default="Snapshot",
                          choices=[m.value for m in CopyMethod])

    r_sched = repsub.add_parser("schedule")
    r_sched.add_argument("name")
    r_sched.add_argument("cronspec")

    r_sync = repsub.add_parser("sync")
    r_sync.add_argument("name")
    r_sync.add_argument("--timeout", type=float, default=120.0)

    r_del = repsub.add_parser("delete")
    r_del.add_argument("name")

    mig = sub.add_parser("migration", help="one-way data migration")
    migsub = mig.add_subparsers(dest="verb", required=True)

    m_create = migsub.add_parser("create")
    m_create.add_argument("name")
    m_create.add_argument("--cluster", default="default")
    m_create.add_argument("--namespace", default="default")
    m_create.add_argument("--pvcname", required=True)
    m_create.add_argument("--capacity", type=int, default=None)
    m_create.add_argument("--access-modes", nargs="*", default=None)

    m_rsync = migsub.add_parser("rsync")
    m_rsync.add_argument("name")
    m_rsync.add_argument("source_dir")

    m_del = migsub.add_parser("delete")
    m_del.add_argument("name")

    # Registered for --help discoverability only; run() hands these
    # verbs (with all their options) straight to volsync_tpu.analysis.cli
    # / volsync_tpu.obs.cli, which own the real argument parsing.
    sub.add_parser(
        "lint", add_help=False,
        help="repo-invariant static analysis "
             "(python -m volsync_tpu.analysis)")
    sub.add_parser(
        "trace", add_help=False,
        help="span flight recorder: dump Chrome-trace JSON / summary "
             "(volsync_tpu.obs)")
    sub.add_parser(
        "repair", add_help=False,
        help="repository recovery: orphaned packs, expired "
             "pending-deletes, dangling index entries "
             "(volsync_tpu.cli.repair)")
    sub.add_parser(
        "scrub", add_help=False,
        help="integrity scrub: on-device pack verify, quarantine + "
             "mirror heal of silent corruption (volsync_tpu.cli.scrub)")
    sub.add_parser(
        "repack", add_help=False,
        help="online repack: rewrite mostly-dead packs into "
             "erasure-coded stripes, two-phase retire "
             "(volsync_tpu.cli.repack)")

    return parser


def run(argv, contexts: dict, out=print) -> int:
    """Parse + dispatch. ``contexts`` maps context names to Cluster
    handles (the kubeconfig analogue)."""
    if argv and argv[0] == "lint":
        from volsync_tpu.analysis.cli import main as lint_main

        return lint_main(list(argv[1:]), out=out)
    if argv and argv[0] == "trace":
        from volsync_tpu.obs.cli import main as trace_main

        return trace_main(list(argv[1:]), out=out)
    if argv and argv[0] == "repair":
        from volsync_tpu.cli.repair import main as repair_main

        return repair_main(list(argv[1:]), out=out)
    if argv and argv[0] == "scrub":
        from volsync_tpu.cli.scrub import main as scrub_main

        return scrub_main(list(argv[1:]), out=out)
    if argv and argv[0] == "repack":
        from volsync_tpu.cli.repack import main as repack_main

        return repack_main(list(argv[1:]), out=out)
    args = build_parser().parse_args(argv)
    config_dir = Path(args.config_dir)
    try:
        if args.group == "replication":
            cli = ReplicationCLI(contexts, config_dir, out=out)
            if args.verb == "create":
                cli.create(args.name)
            elif args.verb == "set-destination":
                cli.set_destination(
                    args.name, cluster=args.cluster,
                    namespace=args.namespace, dest_name=args.dest_name,
                    copy_method=CopyMethod(args.copy_method),
                    service_type=args.service_type, capacity=args.capacity,
                    access_modes=args.access_modes)
            elif args.verb == "set-source":
                cli.set_source(args.name, cluster=args.cluster,
                               namespace=args.namespace,
                               pvc_name=args.pvcname,
                               copy_method=CopyMethod(args.copy_method))
            elif args.verb == "schedule":
                cli.schedule(args.name, args.cronspec)
            elif args.verb == "sync":
                cli.sync(args.name, timeout=args.timeout)
            elif args.verb == "delete":
                cli.delete(args.name)
        else:
            cli = MigrationCLI(contexts, config_dir, out=out)
            if args.verb == "create":
                cli.create(args.name, cluster=args.cluster,
                           namespace=args.namespace, pvc_name=args.pvcname,
                           capacity=args.capacity,
                           access_modes=args.access_modes)
            elif args.verb == "rsync":
                cli.rsync(args.name, args.source_dir)
            elif args.verb == "delete":
                cli.delete(args.name)
        return 0
    except RelationshipError as e:
        out(f"error: {e}")
        return 1


def main(argv=None) -> int:
    """Demo-mode entry: boot a full in-process stack as the 'default'
    context (the operator's packaged entry point wires real state).
    ``volsync lint`` / ``volsync trace`` / ``volsync repair`` /
    ``volsync scrub`` / ``volsync repack`` never need the runtime —
    dispatch them before the boot so the linter runs in CI containers
    with no cluster state, the flight recorder is readable from a
    half-broken process, and repair/scrub can run against a
    store whose operator stack is exactly what crashed."""
    argv = argv if argv is not None else sys.argv[1:]
    if argv and argv[0] in ("lint", "trace", "repair", "scrub", "repack"):
        return run(argv, {})
    from volsync_tpu.operator import OperatorRuntime

    with OperatorRuntime() as rt:
        return run(argv, {"default": rt.cluster})


if __name__ == "__main__":
    raise SystemExit(main())
