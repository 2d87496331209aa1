"""Whole restores through the restic mover entry (``DIRECTION=restore``,
no selector: the newest snapshot), one after another, each into a fresh
empty directory, until the window closes; the one in flight is finished
and counted.

Set-up builds what a ReplicationDestination meets: a seeded volume,
backed up through the same entry, changed as the configuration's
``history`` says (``churn.py``) and backed up again into the same
repository, ``snapshots`` times in all; the backups take the device
path and load the programs they meet. Warm-up is one whole restore of
that repository into a scratch directory (``warm_restore.py``: it runs,
and so loads, every program the window's restores will).

params: ``snapshots`` (the backups the repository holds), ``verify_ops``
(``"all"``: every operation's tree is compared). Every restored tree is
kept until ``verify``; nothing of the check runs inside the window.
"""

from __future__ import annotations

import json
import os
import shutil
import time

import numpy as np

from benchmark import churn, mover, volumes, warm_restore

#: where ``inject`` breaks the guarantee: after the window, before verify
FAULT_AT = "after_run"


class State:
    pass


def setup(ctx) -> State:
    st = State()
    st.ctx = ctx
    st.store = mover.Store(ctx.children)
    st.env = st.store.env("repo")
    st.root = ctx.work / "vol"
    seed = ctx.seed * 131
    st.files = volumes.write(st.root, ctx.shape, seed)
    planned = volumes.plan(ctx.shape, seed)
    small = [rel for rel, _, _ in planned[len(ctx.shape.get("files", [])):]]
    st.first_state = {}
    for k in range(int(ctx.params["snapshots"])):
        if k:
            st.files, before = churn.apply(
                st.root, st.files, small, ctx.shape["history"], seed + k)
            for rel, digest in before.items():
                st.first_state.setdefault(rel, digest)
        rc = mover.run_mover("backup", st.env, st.root)
        if rc != 0:
            raise RuntimeError(f"set-up backup {k} exited {rc}")
    st.nbytes = sum(st.files.values())
    # the bytes no restore can get around verifying: all but what a
    # backup may have found again, the repeated part of a repeat_half file
    st.unique_floor = st.nbytes - sum(min(n // 2, n - n // 2)
                                      for _, n, rep in planned if rep)
    st.ops = []
    print(json.dumps({"restore_setup": {
        "files": len(st.files), "bytes": st.nbytes,
        "unique_floor": st.unique_floor,
        "changed_since_first": len(st.first_state),
        "stored_bytes": st.store.usage("repo/"),
        "work_free_bytes": shutil.disk_usage(ctx.work).free}}), flush=True)
    return st


def _restore(st: State, dest) -> int:
    dest.mkdir()
    return mover.run_mover("restore", st.env, dest)


def warmup(st: State) -> None:
    """One whole restore of the window's own repository and snapshot
    into a scratch directory: every program a restore of the window
    runs, and everything else a first restore pays."""
    scratch = st.ctx.work / "warm"
    st.plan, rc = warm_restore.programs_of(lambda: _restore(st, scratch))
    print(json.dumps({"warm_plan": st.plan}), flush=True)
    if rc != 0:
        raise RuntimeError(f"warm-up restore exited {rc}")
    shutil.rmtree(scratch)


def run(st: State, seconds: float) -> dict:
    from volsync_tpu.obs import copies_by_site, span_totals

    t0 = time.monotonic()
    k = 0
    while True:
        dest = st.ctx.work / f"op{k:04d}"
        before = span_totals()
        ts = time.monotonic()
        with st.ctx.annotate("bench.op"):
            rc = _restore(st, dest)
        te = time.monotonic()
        st.ops.append({"dest": str(dest), "bytes": st.nbytes, "rc": rc,
                       "t_start": ts - t0, "t_done": te - t0,
                       "spans": mover.span_delta(before, span_totals())})
        k += 1
        if te - t0 >= seconds:
            break
    # nothing is recorded after the window: what verify holds the
    # operations' device verification to
    st.verify_spans = span_totals().get("restore.verify", (0, 0.0))[0]
    st.verify_bytes = copies_by_site().get("verify.stage", 0)
    return {"ops": st.ops}


def inject(st: State, fault: str) -> None:
    """The control: one bit flipped in one restored file (its mtime put
    back, so only its bytes tell) of one operation, both drawn from the
    seed."""
    if fault != "flip_restored_bit":
        raise ValueError(f"restore driver knows no fault {fault!r}")
    rng = np.random.default_rng([st.ctx.seed, 0xF1])
    op = st.ops[int(rng.integers(len(st.ops)))]
    filled = sorted(rel for rel, n in st.files.items() if n)
    rel = filled[int(rng.integers(len(filled)))]
    path = os.path.join(op["dest"], rel)
    was = os.stat(path)
    with open(path, "r+b") as f:
        f.seek(was.st_size // 2)
        byte = f.read(1)[0]
        f.seek(was.st_size // 2)
        f.write(bytes([byte ^ 0x10]))
    os.utime(path, ns=(was.st_atime_ns, was.st_mtime_ns))
    print(json.dumps({"fault": fault, "op": op["dest"], "file": rel,
                      "bytes": was.st_size}), flush=True)


def verify(st: State):
    """Every operation's tree in a child of its own
    (``restore_check.py``), side by side, against the source volume in
    its newest state; and, from the program's spans and copy ledger,
    that every operation's unique bytes went through the device's
    verify before they were written."""
    if st.ctx.params["verify_ops"] != "all":
        raise ValueError("the restore driver compares every operation")
    n = {"ops_failed": 0}
    attempted = failed = compared = done = 0
    checking = []
    for op in st.ops:
        attempted += len(st.files)
        if op["rc"] != 0:
            n["ops_failed"] += 1
            failed += len(st.files)
            continue
        done += 1
        child = st.ctx.children.start("drivers/restore_check.py")
        child.stdin.write(json.dumps({
            "source": str(st.root), "restored": op["dest"],
            "first_state": st.first_state}) + "\n")
        child.stdin.flush()
        checking.append((op, child))
    for op, child in checking:
        got = st.ctx.children.read_json(child)
        if got["failed"]:
            print(json.dumps({"op": op["dest"], "failed": got["failed"],
                              "first": got["first"]}), flush=True)
        for k, v in got["counts"].items():
            n[k] = n.get(k, 0) + v
        failed += got["failed"]
        compared += got["compared"]
    checks = [{"check": k, "value": v, "limit": 0} for k, v in n.items()]
    checks += [
        {"check": "files_compared", "value": compared, "at_least": 1},
        {"check": "restore_verify_spans", "value": st.verify_spans,
         "at_least": max(done, 1)},
        {"check": "verify_stage_bytes", "value": st.verify_bytes,
         "at_least": max(done, 1) * st.unique_floor},
    ]
    return attempted, failed, checks
