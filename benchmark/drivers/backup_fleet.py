"""N restic movers backing up at once through ONE mover-jax service:
the service (``MoverJaxServer(**server)``) runs in the benchmark's
process, which holds the chip; every mover is a child process that
holds none (``backup_fleet_mover.py``: a loop around the mover entry
with ``VOLSYNC_ENGINE=service``), with its own volume, repository
prefixes and tenant; the stores are children too, one a tenant. Closed
loop: each mover one whole first backup after another, all started
together, until the window closes; the one in flight is finished.

params: ``movers``, ``tenants`` (movers are dealt to tenants in turn
blocks: mover m belongs to tenant m // (movers / tenants)), ``server``
(keyword arguments of ``MoverJaxServer``; empty: defaults),
``verify_ops`` (operations read back whole: one of each mover, drawn
from the seed, while that many movers are left). The programs set-up
loads are worked out from the volumes' sizes and the service's own
numbers (``warm_fleet.py``).
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

from benchmark import mover

#: where ``inject`` breaks the guarantee: after the window, before verify
FAULT_AT = "after_run"

#: the backup thread's spans that lie one after another and add up to a
#: mover's operation (``backup.file`` by its self time: the spans inside
#: it are in the list)
SUMMED = ("repo.open", "backup.prepare", "backup.walk", "backup.tree",
          "repo.flush", "repo.save_snapshot", "backup.read",
          "backup.blob_id", "backup.open", "repo.add", "remote.wait",
          "remote.backoff")
#: counters of the movers that are a high-water, not a sum
MAXIMA = ("remote.held_bytes_max",)
#: check children running at once: each imports the product, and one
#: that reads a volume back holds its largest file and the reference
#: chunker's arrays over it (twelve at once, beside twelve movers and
#: the stores' objects, met the chip machine's 40 GiB; PR 48)
CHECKS_AT_ONCE = 6

_TICK = os.sysconf("SC_CLK_TCK")


class State:
    pass


class Store(mover.Store):
    """``mover.Store`` that remembers its child's pid (for /proc)."""

    def __init__(self, children):
        child = children.start("store_child.py", mover.ACCESS, mover.SECRET)
        self.pid = child.pid
        self.port = children.read_json(child)["port"]


def _cpu_seconds(pids) -> float:
    """User + system seconds the processes have used, from /proc."""
    total = 0.0
    for pid in pids:
        try:
            with open(f"/proc/{pid}/stat") as f:
                fields = f.read().rsplit(")", 1)[1].split()
        except OSError:
            continue
        total += (int(fields[11]) + int(fields[12])) / _TICK
    return total


def setup(ctx) -> State:
    from volsync_tpu.service.server import MoverJaxServer

    st = State()
    st.ctx = ctx
    p = ctx.params
    n, tenants = int(p["movers"]), int(p["tenants"])
    st.server = MoverJaxServer(**p.get("server", {}))
    st.server.start()
    ctx.on_exit(st.server.stop)
    st.stores = [Store(ctx.children) for _ in range(tenants)]
    st.movers = []
    for m in range(n):
        t = m // (n // tenants)
        name = f"mover{m:02d}"
        base = st.stores[t].env("")
        job = {"mover": name, "tenant": f"tenant{t}",
               "seed": ctx.seed * 131 + m, "shape": ctx.shape,
               "root": str(ctx.work / name),
               "repo_base": base.pop("RESTIC_REPOSITORY"),
               "env": {**base, "VOLSYNC_ENGINE": "service",
                       "MOVER_JAX_ADDRESS": f"127.0.0.1:{st.server.port}",
                       "MOVER_JAX_TOKEN": st.server.token,
                       "MOVER_JAX_TENANT": f"tenant{t}"}}
        child = ctx.children.start("drivers/backup_fleet_mover.py")
        child.stdin.write(json.dumps(job) + "\n")
        child.stdin.flush()
        st.movers.append({"name": name, "tenant": t, "child": child,
                          "root": job["root"]})
    for mv in st.movers:
        got = ctx.children.read_json(mv["child"])
        if not got["ready"]:
            raise RuntimeError(
                f"{mv['name']}: the program has no service engine "
                f"(VOLSYNC_ENGINE=service): {got['error']}")
        mv["files"] = got["files"]
        mv["bytes"] = sum(got["files"].values())
    st.warm_rcs = []
    st.ops = []
    return st


def _command(st: State, line: str) -> list[dict]:
    for mv in st.movers:
        mv["child"].stdin.write(line + "\n")
        mv["child"].stdin.flush()
    return [st.ctx.children.read_json(mv["child"]) for mv in st.movers]


def warmup(st: State) -> None:
    """Every (lanes, bucket) program the movers' files can make the
    service meet (``warm_fleet.py``), then one whole operation a mover,
    all together, through the service: everything else a first
    operation pays."""
    from benchmark import warm_fleet

    st.plan = warm_fleet.fleet_plan(
        [list(mv["files"].values()) for mv in st.movers], st.server)
    print(json.dumps({"warm_plan": st.plan}), flush=True)
    warm_fleet.load_programs(st.server.params, st.plan, st.ctx.seed)
    st.warm_rcs = [a["rc"] for a in _command(st, "warm")]
    if any(st.warm_rcs):
        raise RuntimeError(f"warm-up backups exited {st.warm_rcs}")


def _sum_children(answers, key: str) -> dict:
    out: dict = {}
    for a in answers:
        for name, (n, secs) in a[key].items():
            acc = out.setdefault(name, [0, 0.0])
            acc[0] += n
            acc[1] += secs
    return out


def run(st: State, seconds: float) -> dict:
    pids = {"movers": [mv["child"].pid for mv in st.movers],
            "stores": [s.pid for s in st.stores], "server": [os.getpid()]}
    cpu0 = {k: _cpu_seconds(v) for k, v in pids.items()}
    t0 = time.monotonic()
    with st.ctx.annotate("bench.window"):
        answers = _command(st, f"go {seconds} {t0!r}")
    wall = time.monotonic() - t0
    cpu = {k: round(_cpu_seconds(v) - cpu0[k], 3) for k, v in pids.items()}
    st.answers = answers
    st.ops = [op for a in answers for op in a["ops"]]
    counters: dict = {}
    for a in answers:
        for name, n in a["counters"].items():
            counters[name] = (max(counters.get(name, 0), n)
                              if name in MAXIMA
                              else counters.get(name, 0) + n)
    st.mover_counters = counters
    spans = _sum_children(answers, "spans")
    own = _sum_children(answers, "self")
    op_wall = sum(op["t_done"] - op["t_start"] for op in st.ops)
    named = (sum(spans.get(s, (0, 0.0))[1] for s in SUMMED)
             + own.get("backup.file", (0, 0.0))[1])
    print(json.dumps({"fleet_window": {
        "window_s": round(wall, 3), "host_cpu_s": cpu,
        "ops_by_mover": {mv["name"]: len(a["ops"])
                         for mv, a in zip(st.movers, answers)},
        "mover_wall_s": round(op_wall, 3),
        "mover_named_spans_s": round(named, 3),
        "named_share": round(named / op_wall, 4) if op_wall else None}}),
        flush=True)
    by_name = {mv["name"]: mv for mv in st.movers}
    stored = sum(
        st.stores[by_name[op["mover"]]["tenant"]].usage(op["prefix"] + "/")
        for op in st.ops)
    return {"ops": st.ops, "stored_bytes": stored, "mover_spans": spans,
            "mover_self_spans": own, "mover_counters": counters}


def deep_ops(st: State) -> set[int]:
    """Indices into ``st.ops`` of the operations read back whole: one of
    each mover, drawn from the seed, for the first ``verify_ops``
    movers in an order the seed draws too."""
    rng = np.random.default_rng([st.ctx.seed, 0xC4])
    mine: dict[str, list[int]] = {}
    for i, op in enumerate(st.ops):
        if op["rc"] == 0:
            mine.setdefault(op["mover"], []).append(i)
    names = sorted(mine)
    order = rng.permutation(len(names)).tolist()
    picked = [names[j] for j in order][:max(1, int(
        st.ctx.params["verify_ops"]))]
    return {mine[name][int(rng.integers(len(mine[name])))]
            for name in picked}


def inject(st: State, fault: str) -> None:
    """The control: one bit flipped in one stored pack (the largest
    object under its prefix) of one of the operations that are read
    back, drawn from the seed."""
    if fault != "flip_pack_bit":
        raise ValueError(f"fleet backup driver knows no fault {fault!r}")
    deep = sorted(deep_ops(st))
    k = deep[int(np.random.default_rng([st.ctx.seed, 0xF1])
                 .integers(len(deep)))]
    op = st.ops[k]
    by_name = {mv["name"]: mv for mv in st.movers}
    st.stores[by_name[op["mover"]]["tenant"]].flip_pack_bit(op["prefix"])


def _device_path_bytes(st: State) -> int:
    """Bytes of the completed operations' files that a mover streams
    (over the chunker's ``min_size``; the rest it hashes itself)."""
    floor = st.server.params.min_size
    per = {mv["name"]: sum(n for n in mv["files"].values() if n > floor)
           for mv in st.movers}
    return sum(per[op["mover"]] for op in st.ops if op["rc"] == 0)


def verify(st: State):
    """Every operation in a child of its own (``backup_check.py``),
    ``CHECKS_AT_ONCE`` side by side; the ``deep_ops`` read every file
    back. Then what the two sides counted, held against each other."""
    from volsync_tpu.metrics import GLOBAL as METRICS
    from volsync_tpu.obs import copies_by_site, counter_totals

    by_name = {mv["name"]: mv for mv in st.movers}
    n = {"ops_failed": sum(1 for rc in st.warm_rcs if rc)}
    attempted = failed = read_back = 0
    deep = deep_ops(st)
    todo = []
    for i, op in enumerate(st.ops):
        mv = by_name[op["mover"]]
        attempted += len(mv["files"])
        if op["rc"] != 0:
            n["ops_failed"] += 1
            failed += len(mv["files"])
            continue
        todo.append((i, op, mv))
    for at in range(0, len(todo), CHECKS_AT_ONCE):
        checking = []
        for i, op, mv in todo[at: at + CHECKS_AT_ONCE]:
            child = st.ctx.children.start("drivers/backup_check.py")
            child.stdin.write(json.dumps({
                "env": st.stores[mv["tenant"]].env(op["prefix"]),
                "root": mv["root"], "files": mv["files"],
                "chunker": st.ctx.config["chunker"],
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
            child.stdin.close()
            child.wait()
    n["mover_backends_initialized"] = sum(
        bool(a["backends_initialized"]) for a in st.answers)
    served = counter_totals()
    n["streams_answered_elsewhere"] = abs(
        st.mover_counters.get("remote.streams", 0)
        - served.get("svc.streams", 0))
    checks = [{"check": k, "value": v, "limit": 0} for k, v in n.items()]
    streamed = _device_path_bytes(st)
    copies = copies_by_site()
    checks += [
        {"check": "files_read_back", "value": read_back, "at_least": 1},
        {"check": "svc_stream_bytes", "at_least": streamed,
         "value": served.get("svc.stream_bytes", 0)},
        {"check": "device_staged_bytes", "at_least": streamed,
         "value": copies.get("device.stage", 0)
         + copies.get("device.pad", 0)}]
    sheds = sum(s.value for m in METRICS.svc_shed.collect()
                for s in m.samples if s.name.endswith("_total"))
    print(json.dumps({"fleet_counts": {
        **{k: v for k, v in st.mover_counters.items()
           if k.startswith("remote.")}, "svc.sheds": sheds,
        **{k: v for k, v in served.items() if k.startswith("svc.")}}}),
        flush=True)
    return attempted, failed, checks
