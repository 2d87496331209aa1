"""Whole sync cycles through the rclone mover's entry
(``movers/rclone/entry.rclone_entrypoint``), one after another until
the window closes; the one in flight is finished and counted. A cycle
is a ReplicationSource's sync of one state of a volume into the bucket
(``DIRECTION=source``), then a ReplicationDestination's sync of the
bucket into its own volume (``DIRECTION=destination``), which still
holds the other state: the steady state of a schedule, every file
hashed on both sides and a few per cent of them moved.

Set-up writes the seeded volume, derives the two states the cycles
alternate between (``derive_states``: each lacks ``remove_share`` of
the paths, the second has ``rewrite_share`` of the files both hold
rewritten at their size by ``churn.py``), and syncs the first state
into the empty bucket path and down into the empty destination.
Warm-up is one whole cycle of each state (``warm_restore.programs_of``
reads the programs they ran off the program's own spans).

params: ``rewrite_share``, ``remove_share``. Every call goes through
the entry with every default; nothing of the check runs in the window
but one listing of the bucket's object names after each cycle, inside
the cycle's clock and under the span ``bench.list``.
"""

from __future__ import annotations

import json
import os
import shutil
import threading
import time
from pathlib import Path

import numpy as np

from benchmark import churn, mover, volumes, warm_restore
from benchmark.reference.blobid import file_sha256

#: where ``inject`` breaks the guarantee: after the window, before verify
FAULT_AT = "after_run"
SECTION = "bench"
PREFIX = "mirror"


class State:
    pass


def rclone_conf(url: str, access: str = "", secret: str = "") -> bytes:
    """The ``rclone.conf`` of the mover's Secret: one remote."""
    lines = [f"[{SECTION}]", f"url = {url}"]
    if access:
        lines += [f"access_key_id = {access}",
                  f"secret_access_key = {secret}"]
    return ("\n".join(lines) + "\n").encode()


def run_entry(direction: str, conf: bytes, data: Path) -> int:
    """``rclone_entrypoint`` with config by env, the Secret by mount and
    the volume by mount, as the Job runner calls it. Returns its rc; an
    entry that raises has failed its call (rc 70)."""
    from volsync_tpu.cluster.runner import JobContext
    from volsync_tpu.movers.rclone.entry import rclone_entrypoint

    ctx = JobContext(name=f"bench-rclone-{direction}", namespace="bench",
                     env={"RCLONE_DEST_PATH": PREFIX, "DIRECTION": direction,
                          "RCLONE_CONFIG_SECTION": SECTION},
                     mounts={"data": Path(data)},
                     secrets={"rclone-secret": {"rclone.conf": conf}},
                     stop_event=threading.Event())
    try:
        return rclone_entrypoint(ctx)
    except Exception as ex:  # noqa: BLE001 — counted as a failed call
        print(json.dumps({"mover_error": direction,
                          "error": repr(ex)[:300]}), flush=True)
        return 70


def derive_states(a: Path, b: Path, files: dict[str, int], params: dict,
                  seed: int) -> list[dict]:
    """From the whole volume under ``a``: ``b`` becomes a copy with
    ``rewrite_share`` of the files both states hold rewritten, and each
    state then loses its own ``remove_share`` of the paths (what the
    other holds and it does not is what a sync adds). Returns the two
    states: root, {relative path: bytes}, bytes, and for every rewritten
    file the SHA-256 it has in the OTHER state (``other``)."""
    rels = sorted(files)
    n = max(1, round(len(rels) * float(params["remove_share"])))
    drawn = np.random.default_rng([seed, 0xA7]).permutation(len(rels))
    lacks = [{rels[i] for i in drawn[:n].tolist()},
             {rels[i] for i in drawn[n: 2 * n].tolist()}]
    shutil.copytree(a, b, symlinks=True)
    both = [rel for rel in rels if rel not in lacks[0] | lacks[1]]
    _, in_a = churn.apply(
        b, files, both, {"rewrite_small_share": params["rewrite_share"]},
        seed)
    in_b = {rel: file_sha256(b / rel) for rel in in_a}
    states = []
    for root, lack, other in ((a, lacks[0], in_b), (b, lacks[1], in_a)):
        for rel in lack:
            (root / rel).unlink()
        held = {rel: size for rel, size in files.items() if rel not in lack}
        states.append({"root": root, "files": held, "other": other,
                       "bytes": sum(held.values())})
    return states


def setup(ctx) -> State:
    st = State()
    st.ctx = ctx
    st.store = mover.Store(ctx.children)
    st.conf = rclone_conf(
        f"s3:http://127.0.0.1:{st.store.port}/{mover.BUCKET}",
        mover.ACCESS, mover.SECRET)
    st.bucket = st.store.open(PREFIX)
    seed = ctx.seed * 131
    a, b = ctx.work / "a", ctx.work / "b"
    st.states = derive_states(a, b, volumes.write(a, ctx.shape, seed),
                              ctx.params, seed)
    st.dest = ctx.work / "d"
    st.ops = []
    # the first sync: the bucket path and the destination are empty
    rc = _cycle(st, st.states[0])[0]
    if rc != 0:
        raise RuntimeError(f"the first sync exited {rc}")
    print(json.dumps({"sync_setup": {
        "files": [len(s["files"]) for s in st.states],
        "bytes": [s["bytes"] for s in st.states],
        "rewritten": len(st.states[0]["other"]),
        "added_and_removed":
            len(set(st.states[1]["files"]) - set(st.states[0]["files"])),
        "stored_bytes": st.store.usage(PREFIX + "/"),
        "work_free_bytes": shutil.disk_usage(ctx.work).free}}), flush=True)
    return st


def _cycle(st: State, state: dict) -> tuple[int, list[str]]:
    """One cycle: the source's sync of ``state``, the destination's
    sync, and the bucket's object names as the store lists them
    afterwards (span ``bench.list``). Returns (rc, names)."""
    from volsync_tpu.obs import span

    rc = run_entry("source", st.conf, state["root"]) \
        or run_entry("destination", st.conf, st.dest)
    with span("bench.list"):
        names = [key.rsplit("/", 1)[-1] for key in st.bucket.list("objects")]
    return rc, names


def warmup(st: State) -> None:
    """One whole cycle of the second state and one of the first: a
    pass's batches are a function of the tree alone, so the two run
    exactly the programs the window's cycles will, and load them."""
    def both():
        rc = 0
        for state in (st.states[1], st.states[0]):
            got, st.listed = _cycle(st, state)
            rc = rc or got
        return rc

    st.plan, rc = warm_restore.programs_of(both)
    print(json.dumps({"warm_plan": st.plan}), flush=True)
    if rc != 0:
        raise RuntimeError(f"a warm-up cycle exited {rc}")


def run(st: State, seconds: float) -> dict:
    from volsync_tpu.obs import span_totals

    t0 = time.monotonic()
    k = 0
    while True:
        which = (k + 1) % 2  # the destination holds the first state
        before = span_totals()
        ts = time.monotonic()
        with st.ctx.annotate("bench.op"):
            rc, names = _cycle(st, st.states[which])
        te = time.monotonic()
        st.ops.append({
            "state": which, "bytes": st.states[which]["bytes"], "rc": rc,
            "t_start": ts - t0, "t_done": te - t0, "names": names,
            "spans": mover.span_delta(before, span_totals(), top=24)})
        k += 1
        if te - t0 >= seconds:
            break
    # nothing is recorded after the window: what verify holds the
    # cycles' hash passes to
    st.launches = span_totals().get("verify.launch", (0, 0.0))[0]
    return {"ops": st.ops}


def inject(st: State, fault: str) -> None:
    """The control: one bit flipped in one file of the destination (its
    mtime put back, so only its bytes tell), drawn from the seed."""
    if fault != "flip_synced_bit":
        raise ValueError(f"rclone_sync driver knows no fault {fault!r}")
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
    child = st.ctx.children.start("drivers/rclone_check.py")
    child.stdin.write(json.dumps(job) + "\n")
    child.stdin.flush()
    return child


def verify(st: State):
    """Every cycle's listing against the reference's object names for
    that cycle's state; the last cycle's bucket and destination in a
    ``rclone_check.py`` child; the device's hash passes from the
    program's own spans. Three children, side by side."""
    n = {"calls_failed": sum(op["rc"] != 0 for op in st.ops),
         "objects_missing": 0, "objects_extra": 0}
    naming = [_check(st, {"mode": "objects", "tree": str(s["root"])})
              for s in st.states]
    last = st.ops[-1]
    before = st.ops[-2]["names"] if len(st.ops) > 1 else st.listed
    state = st.states[last["state"]]
    mirror = _check(st, {
        "mode": "mirror", "tree": str(state["root"]), "dest": str(st.dest),
        "env": st.store.env(PREFIX), "other": state["other"],
        "uploaded": sorted(set(last["names"]) - set(before))})
    want = [set(st.ctx.children.read_json(c)["objects"]) for c in naming]
    attempted = failed = 0
    for op in st.ops:
        names = set(op["names"])
        attempted += len(want[op["state"]])
        n["objects_missing"] += len(want[op["state"]] - names)
        n["objects_extra"] += len(names - want[op["state"]])
    failed += n["objects_missing"] + n["objects_extra"]
    got = st.ctx.children.read_json(mirror)
    if got["failed"]:
        print(json.dumps({"failed": got["failed"], "first": got["first"]}),
              flush=True)
    n.update(got["counts"])
    attempted += got["compared"]
    failed += got["failed"]
    if n["calls_failed"]:
        failed += len(state["files"])
    bucket = max(b for b, _ in st.plan) if st.plan else 0
    passes = 2 * (min(s["bytes"] for s in st.states) // bucket) \
        if bucket else 0
    checks = [{"check": k, "value": v, "limit": 0} for k, v in n.items()]
    checks += [
        {"check": "files_compared", "value": got["files_compared"],
         "at_least": 1},
        {"check": "objects_read_back", "value": got["objects_read"],
         "at_least": 1},
        {"check": "verify_launches", "value": st.launches,
         "at_least": len(st.ops) * passes},
    ]
    return attempted, failed, checks
