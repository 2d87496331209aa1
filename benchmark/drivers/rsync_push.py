"""Whole pushes through the rsync mover's two entries
(``movers/rsync/entry.rsync_source_entrypoint`` and
``rsync_destination_entrypoint``), one after another until the window
closes; the one in flight is finished and counted. A push is a
ReplicationSource's sync of one state of a volume onto a
ReplicationDestination that still holds the other state: the steady
state of a schedule, every file signed on one side and scanned on the
other, a few per cent of the bytes moved as literals.

The destination's listener runs on a thread of this process beside the
source (one process holds the chip; the configuration's ``peers`` says
so): started for each push through its entry with the Service it
publishes its port on, it returns the source's ``shutdown <rc>``.

Set-up writes the seeded volume A, derives B (``derive``: the small
files' churn of ``rclone_sync.derive_states``, then ``churn_pages.py``
on the large files of B) and pushes A onto the empty destination (the
first sync: every file new, the largest in parts). Warm-up is one push
of B and one of A. Every call goes through the entries with every
default and the configuration's ``mover_env``; nothing of the check
runs in the window.

A program without the windowed delta path (``deltasync.scan_ranges``)
cannot run this deployment in a run's time (a device program a file
length): the driver refuses it when it is imported.
"""

from __future__ import annotations

import json
import os
import shutil
import threading
import time
from pathlib import Path

import numpy as np

from benchmark import churn_pages, volumes
from benchmark.drivers.rclone_sync import derive_states

try:
    from volsync_tpu.engine.deltasync import scan_ranges  # noqa: F401
except ImportError:
    raise SystemExit("benchmark: this program has no deltasync.scan_ranges: "
                     "its rsync mover compiles a program a file length")

#: where ``inject`` breaks the guarantee: after the window, before verify
FAULT_AT = "after_run"
NAMESPACE, SERVICE = "bench", "bench-rsync-dst"
COUNTED = ("rsync.literal_bytes", "rsync.files_new", "rsync.pruned",
           "rsync.files_delta", "rsync.files_skipped", "rsync.files_full",
           "rsync.files", "rsync.frames")


class State:
    pass


def derive(a: Path, b: Path, shape: dict, files: dict[str, int],
           params: dict, seed: int) -> list[dict]:
    """From the whole volume under ``a``: the two states the pushes
    alternate between. The small files churn as the rclone cell's do
    (each state lacks its own ``remove_share`` of them, the second has
    ``rewrite_share`` rewritten at their size); the second state's
    large files then have pages rewritten in place and bytes inserted
    (``churn_pages.py``). Returns root, {relative path: bytes} and
    bytes of each."""
    large = [f["path"] for f in shape.get("files", [])]
    small = {rel: n for rel, n in files.items() if rel not in large}
    states = derive_states(a, b, small, params, seed)
    big = {rel: files[rel] for rel in large}
    for st, held in zip(states, (big, churn_pages.apply(
            b, big, large, params, seed))):
        st["files"] = {**st["files"], **held}
        st["bytes"] = sum(st["files"].values())
    return states


def _context(name: str, env: dict, data: Path, keys: dict, cluster=None):
    from volsync_tpu.cluster.runner import JobContext

    return JobContext(name=name, namespace=NAMESPACE, env=env,
                      mounts={"data": Path(data)}, secrets={"keys": keys},
                      stop_event=threading.Event(), cluster=cluster)


def _entry(fn, ctx, out: list) -> None:
    """An entry that raises has failed its call (rc 70)."""
    try:
        out.append(fn(ctx))
    except Exception as ex:  # noqa: BLE001 — counted as a failed call
        print(json.dumps({"mover_error": ctx.name,
                          "error": repr(ex)[:300]}), flush=True)
        out.append(70)


def push(st: State, state: dict) -> dict:
    """One push of ``state`` onto the destination: the listener through
    its entry on a thread, the source through its entry here, until both
    have returned. Returns both exit codes, and what the program counted
    and staged meanwhile."""
    from volsync_tpu.movers.rsync.entry import (rsync_destination_entrypoint,
                                                rsync_source_entrypoint)
    from volsync_tpu.obs import copies_by_site, counter_totals

    counts, staged = counter_totals(), copies_by_site()
    began = st.cluster.generation
    dst_rc: list = []
    listener = threading.Thread(
        target=_entry, name="bench-rsync-dst", daemon=True,
        args=(rsync_destination_entrypoint,
              _context("bench-rsync-dst", {"SERVICE": SERVICE}, st.dest,
                       st.keys["dst"], st.cluster), dst_rc))
    listener.start()
    # the listener publishes its port on the Service as it binds
    if not st.cluster.wait_for(lambda: st.cluster.generation > began
                               or dst_rc, timeout=30.0, poll=0.005):
        raise RuntimeError("the destination listener did not bind")
    port = st.cluster.get("Service", NAMESPACE, SERVICE).status.bound_port
    src_rc: list = []
    _entry(rsync_source_entrypoint,
           _context("bench-rsync-src",
                    {"ADDRESS": "127.0.0.1", "PORT": str(port),
                     **st.mover_env}, state["root"], st.keys["src"]),
           src_rc)
    listener.join(timeout=120.0)
    if listener.is_alive():
        raise RuntimeError("the destination listener did not return")
    now, now_staged = counter_totals(), copies_by_site()
    return {"rc": src_rc[0], "dst_rc": dst_rc[0],
            "counts": {k: now.get(k, 0) - counts.get(k, 0) for k in COUNTED},
            "staged": now_staged.get("delta.stage", 0)
            - staged.get("delta.stage", 0)}


def _shrink(ctx) -> None:
    """A rehearsal's sizes (``window_bytes``, ``part_bytes`` in the
    cell's ``rehearsal.params`` alone): the program's window and part
    made small, so that the rehearsal's few MiB still span several
    windows and frames and the CPU takes seconds a program."""
    from volsync_tpu.engine import deltasync
    from volsync_tpu.movers.rsync import entry

    for key, module, name in (("window_bytes", deltasync, "WINDOW"),
                              ("part_bytes", entry, "PART_BYTES")):
        if key in ctx.params:
            was = getattr(module, name)
            setattr(module, name, int(ctx.params[key]))
            ctx.on_exit(lambda m=module, n=name, v=was: setattr(m, n, v))


def relationship(st: State, dest: Path, mover_env: dict) -> State:
    """What the operator gives the two movers of one replication
    relationship: each side's device key and the other's pinned id (the
    Secrets), and the Service the listener publishes its port on."""
    from volsync_tpu.api.common import ObjectMeta
    from volsync_tpu.cluster.cluster import Cluster
    from volsync_tpu.cluster.objects import Service
    from volsync_tpu.movers import devicetransport as dt

    st.mover_env, st.dest = dict(mover_env), dest
    dest.mkdir()
    src, dst = dt.generate_device_key(), dt.generate_device_key()
    st.keys = {
        "src": {"source": src,
                "destination-id": dt.device_id_from_private(dst).encode()},
        "dst": {"destination": dst,
                "source-id": dt.device_id_from_private(src).encode()}}
    st.cluster = Cluster()
    st.cluster.create(Service(metadata=ObjectMeta(name=SERVICE,
                                                  namespace=NAMESPACE)))
    return st


def setup(ctx) -> State:
    st = State()
    st.ctx = ctx
    _shrink(ctx)
    # the deployment's setting of the mover, in the Job's env and, since
    # the planner reads the process's, there for the run
    relationship(st, ctx.work / "d", ctx.config.get("mover_env", {}))
    was = {k: os.environ.get(k) for k in st.mover_env}
    os.environ.update(st.mover_env)
    ctx.on_exit(lambda: [os.environ.pop(k, None) if v is None
                         else os.environ.__setitem__(k, v)
                         for k, v in was.items()])
    seed = ctx.seed * 131
    a, b = ctx.work / "a", ctx.work / "b"
    st.states = derive(a, b, ctx.shape, volumes.write(a, ctx.shape, seed),
                       ctx.params, seed)
    st.ops = []
    # the first sync: the destination is empty
    t0 = time.monotonic()
    first = push(st, st.states[0])
    if first["rc"] or first["dst_rc"]:
        raise RuntimeError(f"the first sync exited {first['rc']}, "
                           f"{first['dst_rc']}")
    print(json.dumps({"push_setup": {
        "files": [len(s["files"]) for s in st.states],
        "bytes": [s["bytes"] for s in st.states],
        "first_sync_s": round(time.monotonic() - t0, 3),
        "first_sync": first["counts"],
        "work_free_bytes": shutil.disk_usage(ctx.work).free}}), flush=True)
    return st


def warmup(st: State) -> None:
    """One push of the second state and one of the first: the staged
    buffers of a push are a function of the tree's sizes alone, so the
    two run exactly the programs the window's pushes will."""
    took = []
    for state in (st.states[1], st.states[0]):
        t0 = time.monotonic()
        got = push(st, state)
        took.append(round(time.monotonic() - t0, 3))
        if got["rc"] or got["dst_rc"]:
            raise RuntimeError(f"a warm-up push exited {got['rc']}, "
                               f"{got['dst_rc']}")
    print(json.dumps({"warm_pushes_s": took}), flush=True)


def run(st: State, seconds: float) -> dict:
    from benchmark import mover
    from volsync_tpu.obs import span_totals

    t0 = time.monotonic()
    k = 0
    while True:
        which = (k + 1) % 2  # the destination holds the first state
        before = span_totals()
        ts = time.monotonic()
        with st.ctx.annotate("bench.op"):
            got = push(st, st.states[which])
        te = time.monotonic()
        st.ops.append({
            "state": which, "bytes": st.states[which]["bytes"],
            "rc": got["rc"] or got["dst_rc"], "dst_rc": got["dst_rc"],
            "src_rc": got["rc"], "counts": got["counts"],
            "staged": got["staged"], "t_start": ts - t0, "t_done": te - t0,
            "spans": mover.span_delta(before, span_totals(), top=24)})
        k += 1
        if te - t0 >= seconds:
            break
    return {"ops": st.ops}


def inject(st: State, fault: str) -> None:
    """The control: one bit flipped in one file of the destination (its
    mtime put back, so only its bytes tell), drawn from the seed."""
    if fault != "flip_pushed_bit":
        raise ValueError(f"rsync_push driver knows no fault {fault!r}")
    held = st.states[st.ops[-1]["state"]]["files"]
    filled = sorted(rel for rel, n in held.items() if n)
    rng = np.random.default_rng([st.ctx.seed, 0xF1])
    rel = filled[int(rng.integers(len(filled)))]
    path = os.path.join(st.dest, rel)
    was = os.stat(path)
    with open(path, "r+b") as f:
        f.seek(was.st_size // 2)
        byte = f.read(1)[0]
        f.seek(was.st_size // 2)
        f.write(bytes([byte ^ 0x10]))
    os.utime(path, ns=(was.st_atime_ns, was.st_mtime_ns))
    print(json.dumps({"fault": fault, "file": rel, "bytes": was.st_size}),
          flush=True)


def _check(st: State, job: dict):
    child = st.ctx.children.start("drivers/rsync_check.py")
    child.stdin.write(json.dumps(job) + "\n")
    child.stdin.flush()
    return child


def verify(st: State):
    """Every push's counters against the plain reference's numbers for
    its transition (a ``rsync_check.py`` child each way: a push of one
    state lands on the other, which the destination then equals), and
    the last push's destination against its source state in a third;
    side by side."""
    roots = [str(s["root"]) for s in st.states]
    refs = [_check(st, {"mode": "delta", "source": roots[i],
                        "dest": roots[1 - i]}) for i in (0, 1)]
    last = st.ops[-1]
    tree = _check(st, {"mode": "tree", "source": roots[last["state"]],
                       "dest": str(st.dest)})
    want = [st.ctx.children.read_json(c) for c in refs]
    print(json.dumps({"reference": [
        {k: w[k] for k in ("files", "bytes", "literal_bytes", "files_new",
                           "files_basis", "pruned", "staged_floor",
                           "seconds")} for w in want]}), flush=True)
    n = {"calls_failed": 0, "literal_bytes_off": 0, "files_new_off": 0,
         "pruned_off": 0, "files_basis_off": 0, "files_full": 0,
         "staged_short": 0}
    attempted = 0
    for op in st.ops:
        w, c = want[op["state"]], op["counts"]
        attempted += w["files"]
        n["calls_failed"] += (op["src_rc"] != 0) + (op["dst_rc"] != 0)
        n["literal_bytes_off"] += abs(c["rsync.literal_bytes"]
                                      - w["literal_bytes"])
        n["files_new_off"] += abs(c["rsync.files_new"] - w["files_new"])
        n["pruned_off"] += abs(c["rsync.pruned"] - w["pruned"])
        n["files_basis_off"] += abs(
            c["rsync.files_delta"] + c["rsync.files_skipped"]
            - w["files_basis"])
        n["files_full"] += c["rsync.files_full"]
        n["staged_short"] += max(0, w["staged_floor"] - op["staged"])
    got = st.ctx.children.read_json(tree)
    if got["failed"]:
        print(json.dumps({"failed": got["failed"], "first": got["first"]}),
              flush=True)
    n.update(got["counts"])
    attempted += got["compared"]
    failed = got["failed"] + sum(
        v for k, v in n.items() if k.endswith("_off")) \
        + n["files_full"] + (1 if n["staged_short"] else 0)
    if n["calls_failed"]:
        failed += len(st.states[last["state"]]["files"])
    checks = [{"check": k, "value": v, "limit": 0} for k, v in n.items()]
    checks += [{"check": "files_compared", "value": got["compared"],
                "at_least": 1},
               {"check": "pushes", "value": len(st.ops), "at_least": 1}]
    return attempted, failed, checks
