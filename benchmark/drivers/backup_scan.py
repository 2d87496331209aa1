"""Scheduled backups of one large rewritten file through the restic
mover entry (``DIRECTION=backup``, every default), one after another
into ONE repository that already holds a history, until the window
closes; the one in flight is finished and counted.

Set-up builds what such a sync meets: the seeded volume (state 0 of
``scanstate.py``), every (lanes, bucket) program its one file can
present (``warm.py``'s plan, with ``backup_sched``'s one-byte-short
rule), an initialised repository with ``index_blobs`` history blobs of
``history_blob_bytes`` bytes in it, written through the program's own
writer and named by one snapshot of another path
(``scanstate.write_history``), then the first backup of the volume (the
only operation that stores the bytes that stay) and ``warmup_ops``
operations, each after its step of churn.

An operation is one entry call, the clock around it alone. The step of
churn before it (``fresh_bytes`` new bytes at the end of each half of
the file, the same in both) runs between operations, inside the window
(``churn_s`` on the run's ``scan_window`` line). ``bytes`` of an
operation are the file's: the volume protected.

Every number the check compares comes from outside the program but the
program's own counters: the plain reference ``reference/dedupscan.py``
over the history and the states as the seed makes them again, in
``scan_check.py`` children, which hold no chip and keep nothing of this
process's.

A program that does not count its index's queries
(``repo/shardedindex.py`` ``INDEX_COUNTERS``) has no layer metric for
what this cell is there to price: the driver refuses it when it is
imported.
"""

from __future__ import annotations

import json
import time
from types import SimpleNamespace

import numpy as np

from benchmark import mover, scanstate, warm
from benchmark.drivers import backup_sched

try:
    from volsync_tpu.repo.shardedindex import INDEX_COUNTERS
except ImportError:
    raise SystemExit("benchmark: this program does not count its index's "
                     "queries (repo/shardedindex.py INDEX_COUNTERS)")

#: where ``inject`` breaks the guarantee: after the window, before verify
FAULT_AT = "after_run"
PREFIX = "repo"
COUNTED = ("backup.files_changed", "backup.bytes_changed",
           "repo.blobs_new", "repo.bytes_new", "repo.blobs_dedup",
           "repo.index_loads", "repo.index_objects", "repo.index_entries",
           *INDEX_COUNTERS)
#: the index loads an operation makes: ``repo.open``, ``backup.prepare``
LOADS = 2
#: the spans of an operation its lines print for every one, in this order
SHOWN = ("repo.open", "backup.prepare", "repo.load_index",
         "repo.index_fetch", "repo.index_decode", "repo.index_insert",
         "backup.parent", "backup.hash", "engine.device",
         "engine.read_wait", "repo.dedup_query", "repo.flush")


class State:
    pass


def setup(ctx) -> State:
    from volsync_tpu.engine.chunker import params_from_config
    from volsync_tpu.objstore import open_store
    from volsync_tpu.repo.repository import DEFAULT_CHUNKER, Repository

    st = State()
    st.ctx, p = ctx, ctx.params
    st.store = mover.Store(ctx.children)
    st.env = {**st.store.env(PREFIX), **ctx.config.get("mover_env", {})}
    st.seed = ctx.seed * 131
    st.fresh = int(p["fresh_bytes"])
    st.root = ctx.work / "vol"
    st.files = scanstate.write_volume(st.root, ctx.shape, st.fresh, st.seed)
    st.chunker = params_from_config(DEFAULT_CHUNKER)
    sizes = backup_sched.reachable_sizes(SimpleNamespace(
        chunker=st.chunker, files=st.files, mids=[],
        ctx=SimpleNamespace(params={"append_bytes": 0})), 0)
    st.plan = warm.backup_plan(sizes, st.chunker)
    print(json.dumps({"warm_plan": st.plan}), flush=True)
    warm.segment_programs(st.chunker, st.plan, ctx.seed)
    repo = Repository.init(
        open_store(st.env["RESTIC_REPOSITORY"], env=st.env),
        password=mover.PASSWORD)
    st.history = scanstate.write_history(
        repo, st.seed, int(p["index_blobs"]), int(p["history_blob_bytes"]))
    st.ops = []  # every entry call; the first backup is number 0
    operation(st, churned=False)
    return st


def _rows(ops) -> list:
    """[operation, seconds, its step's seconds, the SHOWN spans']."""
    return [[op["op"], round(op["seconds"], 3), round(op["churn_s"], 3),
             *(op["spans"].get(k, 0.0) for k in SHOWN)] for op in ops]


def operation(st: State, churned: bool = True,
              t0: float | None = None) -> dict:
    """One step of churn (not before the first backup) and one entry
    call; returns the operation's record: what the program counted and
    staged, the clocks."""
    from volsync_tpu.obs import copies_by_site, counter_totals, span_totals

    i = len(st.ops)
    rec = {"op": i, "churn_s": 0.0, "bytes": sum(st.files.values())}
    if churned:
        tc = time.monotonic()
        scanstate.churn(st.root, st.ctx.shape, st.fresh, st.seed, i)
        rec["churn_s"] = time.monotonic() - tc
    counts, staged, spans = counter_totals(), copies_by_site(), span_totals()
    ts = time.monotonic()
    with st.ctx.annotate("bench.op"):
        rec["rc"] = mover.run_mover("backup", st.env, st.root)
    te = time.monotonic()
    now, now_staged = counter_totals(), copies_by_site()
    rec["counts"] = {k: now.get(k, 0) - counts.get(k, 0) for k in COUNTED}
    rec["staged"] = sum(now_staged.get(k, 0) - staged.get(k, 0)
                        for k in backup_sched.STAGED)
    rec["spans"] = mover.span_delta(spans, span_totals(), top=24)
    if t0 is not None:
        rec["t_start"], rec["t_done"] = ts - t0, te - t0
    rec["seconds"] = te - ts
    st.ops.append(rec)
    return rec


def warmup(st: State) -> None:
    for _ in range(int(st.ctx.params["warmup_ops"])):
        operation(st)
    for op in st.ops:
        if op["rc"] != 0:
            raise RuntimeError(f"set-up operation {op['op']} exited "
                               f"{op['rc']}")
    print(json.dumps({"scan_setup": {
        "history": st.history, "bytes": sum(st.files.values()),
        "warmup_mean_s": round(float(np.mean(
            [op["seconds"] for op in st.ops[1:]] or [0.0])), 3),
        "columns": ["op", "seconds", "churn_s", *SHOWN],
        "rows": _rows(st.ops)}}), flush=True)


def run(st: State, seconds: float) -> dict:
    st.first_op = len(st.ops)
    t0 = time.monotonic()
    while True:
        rec = operation(st, t0=t0)
        if rec["t_done"] >= seconds:
            break
    ops = st.ops[st.first_op:]
    churn_s = sum(op["churn_s"] for op in ops)
    print(json.dumps({"scan_window": {
        "operations": len(ops), "churn_s": round(churn_s, 3),
        "churn_share": round(churn_s / ops[-1]["t_done"], 4),
        "counts": [op["counts"] for op in ops[:3]],
        "rows": _rows(ops)}}), flush=True)
    return {"ops": ops}


def inject(st: State, fault: str) -> None:
    """The control: one bit flipped in the largest stored pack of the
    repository."""
    if fault != "flip_pack_bit":
        raise ValueError(f"backup_scan driver knows no fault {fault!r}")
    st.store.flip_pack_bit(PREFIX)


def _check(st: State, job: dict):
    p = st.ctx.params
    child = st.ctx.children.start("drivers/scan_check.py")
    child.stdin.write(json.dumps({
        "env": st.env, "seed": st.seed, "shape": st.ctx.shape,
        "fresh": st.fresh, "chunker": st.ctx.config["chunker"],
        "index_blobs": int(p["index_blobs"]),
        "history_blob_bytes": int(p["history_blob_bytes"]),
        "operations": len(st.ops), **job}) + "\n")
    child.stdin.flush()
    return child


def verify(st: State):
    """Every operation of the window against the plain reference (the
    program's counters), the repository's index and history at the end
    (one ``scan_check.py`` child, which also reads back every blob the
    reference says an operation added), and ``verify_ops`` snapshots,
    the last and others drawn from the seed, read back whole (a child
    each), all side by side."""
    from volsync_tpu.ops.batcher import shared_batcher

    p = st.ctx.params
    ops = st.ops[st.first_op:]
    size = sum(st.files.values())
    rng = np.random.default_rng([st.ctx.seed, 0xC4])
    earlier = [op["op"] for op in ops[:-1]]
    picked = sorted(rng.permutation(earlier)[
        :max(0, int(p["verify_ops"]) - 1)].tolist()) + [ops[-1]["op"]]
    children = [_check(st, {"mode": "index",
                            "sample": int(p["history_sample"])})] + [
        _check(st, {"mode": "snapshot", "operation": k}) for k in picked]
    n, attempted, failed, reference = {}, len(ops), 0, None
    for child in children:
        got = st.ctx.children.read_json(child)
        for line in got["errors"] + ([got["notes"]] if got["notes"] else []):
            print(json.dumps(line), flush=True)
        for k, v in got["counts"].items():
            n[k] = n.get(k, 0) + v
        attempted += got["attempted"]
        failed += got["failed"]
        reference = got.get("reference", reference)
    print(json.dumps({"scan_reference": reference}), flush=True)
    mine = dict.fromkeys((
        "ops_failed", "files_changed_off", "bytes_changed_off",
        "new_blobs_off", "new_bytes_off", "dedup_off", "index_loads_off",
        "index_entries_short"), 0)
    mine["ops_failed"] = sum(op["rc"] != 0 for op in st.ops)
    if reference is None:  # the index child did not get that far
        mine["reference_missing"] = len(ops)
    for op in ops if reference else ():
        c, ref = op["counts"], reference[op["op"]]
        mine["files_changed_off"] += abs(c["backup.files_changed"] - 1)
        mine["bytes_changed_off"] += abs(c["backup.bytes_changed"] - size)
        mine["new_blobs_off"] += abs(c["repo.blobs_new"] - ref["blobs_new"])
        mine["new_bytes_off"] += abs(c["repo.bytes_new"] - ref["bytes_new"])
        mine["dedup_off"] += abs(c["repo.blobs_dedup"] - ref["blobs_dedup"])
        mine["index_loads_off"] += abs(c["repo.index_loads"] - LOADS)
        mine["index_entries_short"] += max(
            0, LOADS * ref["held_before"] - c["repo.index_entries"])
    failed += sum(map(bool, mine.values()))
    n = {**mine, **n}
    staged = sum(op["staged"] for op in ops)
    # off the chip the engine hands the device its own pooled, padded
    # buffer, one lane at a time: nothing is staged, and the ledger has
    # nothing to hold the device path to
    floor = size * len(ops) if shared_batcher(st.chunker) else 0
    read_back = n.pop("files_read_back", 0)
    checks = [{"check": k, "value": v, "limit": 0} for k, v in n.items()]
    checks += [
        {"check": "files_read_back", "value": read_back, "at_least": 1},
        {"check": "snapshots_verified", "value": len(picked),
         "at_least": 1},
        {"check": "device_staged_bytes", "value": staged,
         "at_least": floor}]
    return attempted, failed, checks
