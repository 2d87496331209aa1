"""Whole first backups through the restic mover entry, one after
another, each of a seeded volume into a fresh repository prefix on the
store child, until the window closes; the one in flight is finished and
counted.

params: ``volumes`` (distinct volumes made at set-up and used in turn:
each operation meets a repository that has never seen its bytes),
``verify_ops`` (operations of which every file is read back: ``"all"``,
or a number of them drawn from the seed). The programs set-up loads are
worked out from the configuration's sizes (``warm.py``).
"""

from __future__ import annotations

import json
import time

import numpy as np

from benchmark import mover, volumes

#: where ``inject`` breaks the guarantee: after the window, before verify
FAULT_AT = "after_run"


class State:
    pass


def setup(ctx) -> State:
    st = State()
    st.ctx = ctx
    st.store = mover.Store(ctx.children)
    st.vols = []
    for k in range(int(ctx.params["volumes"])):
        root = ctx.work / f"vol{k}"
        files = volumes.write(root, ctx.shape, ctx.seed * 131 + k)
        st.vols.append((root, files, sum(files.values())))
    st.warm = ctx.work / "warm"
    volumes.write(st.warm, ctx.shape, ctx.seed * 131 + 127)
    st.ops = []
    return st


def warmup(st: State) -> None:
    """Every (lanes, bucket) program the engine's batcher can form from
    this shape's sizes (see ``warm.py``), then one whole operation of
    the cell's own shape on other bytes: everything else a first
    operation pays."""
    from volsync_tpu.engine.chunker import params_from_config
    from volsync_tpu.repo.repository import DEFAULT_CHUNKER

    from benchmark import warm

    chunker = params_from_config(DEFAULT_CHUNKER)
    sizes = [n for _, n, _ in volumes.plan(st.ctx.shape, st.ctx.seed)]
    st.plan = warm.backup_plan(sizes, chunker)
    print(json.dumps({"warm_plan": st.plan}), flush=True)
    warm.segment_programs(chunker, st.plan, st.ctx.seed)
    rc = mover.run_mover("backup", st.store.env("warm"), st.warm)
    if rc != 0:
        raise RuntimeError(f"warm-up backup exited {rc}")


def run(st: State, seconds: float) -> dict:
    from volsync_tpu.obs import span_totals

    t0 = time.monotonic()
    k = 0
    while True:
        root, files, nbytes = st.vols[k % len(st.vols)]
        prefix = f"op{k:04d}"
        before = span_totals()
        ts = time.monotonic()
        with st.ctx.annotate("bench.op"):
            rc = mover.run_mover("backup", st.store.env(prefix), root)
        te = time.monotonic()
        st.ops.append({"prefix": prefix, "vol": k % len(st.vols),
                       "bytes": nbytes, "rc": rc,
                       "t_start": ts - t0, "t_done": te - t0,
                       "spans": mover.span_delta(before, span_totals())})
        k += 1
        if te - t0 >= seconds:
            break
    stored = sum(st.store.usage(op["prefix"] + "/") for op in st.ops)
    return {"ops": st.ops, "stored_bytes": stored}


def inject(st: State, fault: str) -> None:
    """The control: one bit flipped in one stored pack (the largest
    object under its prefix) of one operation, drawn from the seed."""
    if fault != "flip_pack_bit":
        raise ValueError(f"backup driver knows no fault {fault!r}")
    k = int(np.random.default_rng([st.ctx.seed, 0xF1]).integers(len(st.ops)))
    st.store.flip_pack_bit(st.ops[k]["prefix"])


def verify(st: State):
    """Every operation in a child of its own (``backup_check.py``), side
    by side; ``verify_ops`` of them, or all, read every file back."""
    p = st.ctx.params
    n = {"ops_failed": 0}
    attempted = failed = read_back = 0
    deep = set(range(len(st.ops)))
    if p["verify_ops"] != "all":
        rng = np.random.default_rng([st.ctx.seed, 0xC4])
        deep = set(rng.permutation(len(st.ops))
                   [:max(1, int(p["verify_ops"]))].tolist())
    checking = []
    for i, op in enumerate(st.ops):
        root, files, _ = st.vols[op["vol"]]
        attempted += len(files)
        if op["rc"] != 0:
            n["ops_failed"] += 1
            failed += len(files)
            continue
        child = st.ctx.children.start("drivers/backup_check.py")
        child.stdin.write(json.dumps({
            "env": st.store.env(op["prefix"]), "root": str(root),
            "files": files, "chunker": st.ctx.config["chunker"],
            "deep": i in deep}) + "\n")
        child.stdin.flush()
        checking.append((op, child))
    for op, child in checking:
        got = st.ctx.children.read_json(child)
        for err in got["errors"]:
            print(json.dumps({**err, "op": op["prefix"]}), flush=True)
        for k, v in got["counts"].items():
            n[k] = n.get(k, 0) + v
        failed += got["failed"]
        read_back += got["read_back"]
    checks = [{"check": k, "value": v, "limit": 0} for k, v in n.items()]
    checks.append({"check": "files_read_back", "value": read_back,
                   "at_least": 1})
    return attempted, failed, checks
