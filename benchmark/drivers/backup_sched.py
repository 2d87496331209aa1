"""Scheduled backups through the restic mover entry (``DIRECTION=backup``
with the ``FORGET_*`` the controller sets), one sync after another into
the repository the last one left, until the window closes; the one in
flight is finished and counted.

Set-up builds what a schedule's sync meets: a seeded volume, its first
backup, then ``history_syncs`` syncs, each after one step of churn
(``churn.py``: ``rewrite_small_share`` of the small files rewritten at
their size, ``append_bytes`` appended to ``mid/`` file ``i mod n`` for
sync ``i``). Every sync is one entry call: backup onto the parent the
last sync left, then ``forget`` under the retain policy; no prune. The
history syncs are the cell's own traffic and its warm-up; before them
every (lanes, bucket) program is loaded that a ``mid/`` file can present
at any size it reaches (``warm.py``).

An operation is one sync: the clock runs around the entry call alone.
The step of churn before it runs between operations, inside the window
(``churn_s`` on the run's ``sched_window`` line: a seventh of it, most
of that ``churn.apply``'s SHA-256 of the grown ``mid/`` file before it
appends). Writing steps worked out ahead instead took the share to 2.5%
and was tried (PERF.md, PR 44): the backup then read the grown file
cold, its syncs took 1.0 to 1.45 s by when the cache had warmed, and
whole runs spread by 14%; live, the step's own read leaves the file as
an application that has just written it would, and a sync takes 0.9 to
1.1 s on every run. ``bytes`` of an
operation are the regular-file bytes of the state it backed up;
``stored_bytes`` is what the store lists under the repository's prefix
when the window closes less what it listed when the window opened.

Every number the check compares comes from outside the program but the
program's own counters: the churn's record of each step, ``lstat`` of
the files it touched, the SHA-256 ``churn.apply`` took of a file before
it changed it, the plain reference ``reference/increment.py`` (in
``sched_check.py`` children, which hold no chip).

A program that does not count a sync's files (``backup.files_unchanged``
from ``BackupStats``) cannot be held to the check: the driver refuses it
when it is imported.
"""

from __future__ import annotations

import inspect
import json
import os
import time
from datetime import datetime, timezone

import numpy as np

from benchmark import churn, mover, volumes, warm

try:
    from volsync_tpu.repo.repository import BackupStats

    BackupStats().files_unchanged
except (ImportError, AttributeError):
    raise SystemExit("benchmark: this program does not count the files a "
                     "backup takes from its parent (backup.files_unchanged)")

#: where ``inject`` breaks the guarantee: after the window, before verify
FAULT_AT = "after_run"
PREFIX = "repo"
COUNTED = ("backup.files", "backup.files_unchanged", "backup.files_changed",
           "backup.bytes_unchanged", "backup.bytes_changed",
           "repo.blobs_new", "repo.bytes_new", "repo.blobs_dedup",
           "repo.forget_removed", "repo.index_loads", "repo.index_objects",
           "repo.snapshots_listed")
STAGED = ("device.stage", "device.pad")
#: the spans of a sync its lines print for every sync, in this order
SHOWN = ("backup.walk", "repo.open", "backup.prepare", "backup.hash",
         "backup.open", "engine.device", "engine.read_wait", "repo.flush",
         "repo.forget")


class State:
    pass


def _now() -> str:
    return datetime.now(timezone.utc).isoformat()


def _stat(root, rels) -> dict:
    """{relative path: [size, mtime_ns]} as ``lstat`` gives them."""
    out = {}
    for rel in rels:
        st = os.lstat(os.path.join(root, rel))
        out[rel] = [st.st_size, st.st_mtime_ns]
    return out


def reachable_sizes(st: State, syncs: int) -> list[int]:
    """Every file size a sync can meet up to sync number ``syncs``:
    ``mid/`` file ``j`` grows by ``append_bytes`` at syncs ``j``,
    ``j + n``, ``j + 2n``... A size that is a whole number of segment
    fills is planned one byte short: the engine closes such a stream
    with its last full segment (it reads past the size the walk saw and
    finds the end), where ``warm.file_buckets`` still plans a segment of
    the tail alone, at any of six small shapes."""
    from volsync_tpu.engine import chunker

    segment = inspect.signature(chunker.stream_chunk_batches) \
        .parameters["segment_size"].default
    fill = chunker._SegmentFill(lambda n: b"", segment,
                                st.chunker.max_size).target
    grow = int(st.ctx.params["append_bytes"])
    sizes = [n for rel, n in st.files.items() if rel not in st.mids]
    for j, rel in enumerate(st.mids):
        turns = len(range(j if j else len(st.mids), syncs + 1, len(st.mids)))
        sizes += [st.files[rel] + grow * m for m in range(turns + 1)]
    return [n - 1 if n % fill == 0 else n for n in sizes]


def setup(ctx) -> State:
    from volsync_tpu.engine.chunker import params_from_config
    from volsync_tpu.repo.repository import DEFAULT_CHUNKER

    st = State()
    st.ctx, p = ctx, ctx.params
    st.store = mover.Store(ctx.children)
    st.env = {**st.store.env(PREFIX), **ctx.config.get("mover_env", {}),
              "FORGET_LAST": str(p["retain_last"])}
    st.seed = ctx.seed * 131
    st.root = ctx.work / "vol"
    st.files = volumes.write(st.root, ctx.shape, st.seed)
    large = [f["path"] for f in ctx.shape.get("files", [])]
    st.mids = sorted(rel for rel in large if rel.startswith("mid/"))
    st.small = sorted(rel for rel in st.files if rel not in large)
    st.first_state = _stat(st.root, st.files)
    st.syncs = []  # every entry call, the first backup is number 0
    # a window of 30 s cannot hold more: a sync's fixed cost alone is
    # a quarter of a second
    horizon = int(p["history_syncs"]) + 120
    st.chunker = params_from_config(DEFAULT_CHUNKER)
    st.plan = warm.backup_plan(reachable_sizes(st, horizon), st.chunker)
    print(json.dumps({"warm_plan": st.plan}), flush=True)
    warm.segment_programs(st.chunker, st.plan, ctx.seed)
    sync(st, churned=False)
    return st


def _history(st: State, i: int) -> dict:
    p = st.ctx.params
    return {"rewrite_small_share": p["rewrite_small_share"],
            "append": {"path": st.mids[i % len(st.mids)],
                       "bytes": int(p["append_bytes"])}}


def _rows(syncs) -> list:
    """[sync, seconds, its step's seconds, the SHOWN spans' seconds]."""
    return [[s["sync"], round(s["seconds"], 3), round(s["churn_s"], 3),
             *(s["spans"].get(k, 0.0) for k in SHOWN)] for s in syncs]


def sync(st: State, churned: bool = True, t0: float | None = None) -> dict:
    """One step of churn (not before the first backup) and one entry
    call; returns the sync's record: what the churn did, by its own
    account and by ``lstat``, what the program counted and staged, the
    clocks."""
    from volsync_tpu.obs import copies_by_site, counter_totals, span_totals

    i = len(st.syncs)
    rec = {"sync": i, "changed": {}, "before": {}, "churn_s": 0.0}
    if churned:
        tc = time.monotonic()
        st.files, rec["before"] = churn.apply(
            st.root, st.files, st.small, _history(st, i), st.seed + i)
        rec["changed"] = _stat(st.root, rec["before"])
        rec["churn_s"] = time.monotonic() - tc
    rec["files"], rec["bytes"] = len(st.files), sum(st.files.values())
    counts, staged, spans = counter_totals(), copies_by_site(), span_totals()
    rec["began"] = _now()
    ts = time.monotonic()
    with st.ctx.annotate("bench.op"):
        rec["rc"] = mover.run_mover("backup", st.env, st.root)
    te = time.monotonic()
    rec["ended"] = _now()
    now, now_staged = counter_totals(), copies_by_site()
    rec["counts"] = {k: now.get(k, 0) - counts.get(k, 0) for k in COUNTED}
    rec["staged"] = sum(now_staged.get(k, 0) - staged.get(k, 0)
                        for k in STAGED)
    rec["spans"] = mover.span_delta(spans, span_totals(), top=24)
    if t0 is not None:
        rec["t_start"], rec["t_done"] = ts - t0, te - t0
    rec["seconds"] = te - ts
    st.syncs.append(rec)
    return rec


def warmup(st: State) -> None:
    """The history: ``history_syncs`` syncs, the cell's own traffic."""
    t0 = time.monotonic()
    for _ in range(int(st.ctx.params["history_syncs"])):
        rec = sync(st)
        if rec["rc"] != 0:
            raise RuntimeError(f"set-up sync {rec['sync']} exited "
                               f"{rec['rc']}")
    print(json.dumps({"sched_setup": {
        "files": len(st.files), "bytes": sum(st.files.values()),
        "history_s": round(time.monotonic() - t0, 3),
        "stored_bytes": st.store.usage(PREFIX + "/"),
        "columns": ["sync", "seconds", "churn_s", *SHOWN],
        "rows": _rows(st.syncs)}}), flush=True)


def run(st: State, seconds: float) -> dict:
    st.first_op = len(st.syncs)
    opened = st.store.usage(PREFIX + "/")
    t0 = time.monotonic()
    while True:
        rec = sync(st, t0=t0)
        if rec["t_done"] >= seconds:
            break
    ops = st.syncs[st.first_op:]
    stored = st.store.usage(PREFIX + "/") - opened
    churn_s = sum(op["churn_s"] for op in ops)
    print(json.dumps({"sched_window": {
        "syncs": len(ops), "churn_s": round(churn_s, 3),
        "churn_share": round(churn_s / ops[-1]["t_done"], 4),
        "stored_bytes": stored,
        "stored_per_protected": stored / max(1, sum(
            op["bytes"] for op in ops if op["rc"] == 0)),
        "counts": [op["counts"] for op in ops[:3]],
        "rows": _rows(ops)}}), flush=True)
    return {"ops": ops, "stored_bytes": stored}


def inject(st: State, fault: str) -> None:
    """The control: one bit flipped in the largest stored pack of the
    repository."""
    if fault != "flip_pack_bit":
        raise ValueError(f"backup_sched driver knows no fault {fault!r}")
    st.store.flip_pack_bit(PREFIX)


def _check(st: State, job: dict):
    child = st.ctx.children.start("drivers/sched_check.py")
    child.stdin.write(json.dumps({
        "env": st.env, "root": str(st.root), "work": str(st.ctx.work),
        "chunker": st.ctx.config["chunker"], **job}) + "\n")
    child.stdin.flush()
    return child


def _gather(st: State, children, n: dict) -> tuple[int, int]:
    attempted = failed = 0
    for child in children:
        got = st.ctx.children.read_json(child)
        for line in got["errors"] + ([got["notes"]] if got["notes"] else []):
            print(json.dumps(line), flush=True)
        for k, v in got["counts"].items():
            n[k] = n.get(k, 0) + v
        attempted += got["attempted"]
        failed += got["failed"]
    return attempted, failed


def verify(st: State):
    """The window's syncs against the churn's record (the program's
    counters), ``verify_syncs`` of them against the plain reference and
    the retained snapshots' chain (``sched_check.py`` children, side by
    side), then one ``DIRECTION=prune`` through the entry and, on a
    second fresh open, every file of the newest snapshot against the
    volume and the oldest retained snapshot against the churn's record
    of what it has changed since."""
    from volsync_tpu.ops.batcher import shared_batcher

    p = st.ctx.params
    ops = st.syncs[st.first_op:]
    retain = int(p["retain_last"])
    n = {"ops_failed": sum(s["rc"] != 0 for s in st.syncs),
         "files_unchanged_off": 0, "files_changed_off": 0,
         "bytes_changed_off": 0, "forget_removed_off": 0}
    floor = 0
    for op in ops:
        c, changed = op["counts"], op["changed"]
        nbytes = sum(size for size, _ in changed.values())
        n["files_changed_off"] += abs(c["backup.files_changed"]
                                      - len(changed))
        n["files_unchanged_off"] += abs(c["backup.files_unchanged"]
                                        - (op["files"] - len(changed)))
        n["bytes_changed_off"] += abs(c["backup.bytes_changed"] - nbytes)
        n["forget_removed_off"] += abs(
            c["repo.forget_removed"] - int(op["sync"] >= retain))
        floor += sum(size for size, _ in changed.values()
                     if size > st.chunker.min_size)
    staged = sum(op["staged"] for op in ops)
    if shared_batcher(st.chunker) is None:
        # off the chip the engine hands the device its own pooled,
        # padded buffer, one lane at a time: nothing is staged, and the
        # ledger has nothing to hold the device path to
        floor = 0
    # the syncs held to the reference: the window's last, and of those
    # before it whose parent is still retained some drawn from the seed
    last = st.syncs[-1]["sync"]
    older = [op["sync"] for op in ops[:-1]
             if op["sync"] - 1 > last - retain]
    rng = np.random.default_rng([st.ctx.seed, 0xC4])
    picked = sorted(rng.permutation(older)
                    [:max(0, int(p["verify_syncs"]) - 1)].tolist()) + [last]
    slim = [{k: s[k] for k in ("sync", "changed", "before", "files",
                               "began", "ended", "counts")}
            for s in st.syncs]
    history = {"first_state": st.first_state, "syncs": slim,
               "retain": retain}
    attempted, failed = _gather(st, [
        _check(st, {"mode": "chain", **history})] + [
        _check(st, {"mode": "sync", "sync": k, **history})
        for k in picked], n)
    rc = mover.run_mover("prune", st.env, st.root)
    n["ops_failed"] += rc != 0
    shares = 1 if len(st.files) < 64 else 4
    a2, f2 = _gather(st, [
        _check(st, {"mode": "content", "share": [k, shares], **history})
        for k in range(shares)] + [
        _check(st, {"mode": "old", **history})], n)
    attempted, failed = attempted + a2, failed + f2
    failed += sum(bool(v) for k, v in n.items() if k.endswith("_off"))
    if n["ops_failed"]:
        failed += len(st.files)
    read_back = n.pop("files_read_back", 0)
    checks = [{"check": k, "value": v, "limit": 0} for k, v in n.items()]
    checks += [
        {"check": "files_read_back", "value": read_back, "at_least": 1},
        {"check": "syncs_verified", "value": len(picked), "at_least": 1},
        {"check": "device_staged_bytes", "value": staged,
         "at_least": floor}]
    return attempted, failed, checks
