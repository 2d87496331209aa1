"""Closed-loop clients against one ``MoverJaxServer`` in the process
that holds the chip: every client sends one ChunkStream after another
and waits for each reply (movers wait for theirs, so the loop is
closed; no think time). The clients live in child processes, one per
tenant, that hold no chip.

params: ``tenants``, ``clients_per_tenant``, ``streams_per_client``
(the length of each client's list of sizes), ``size_lo`` / ``size_hi``
/ ``size_seed`` (the one list of log-uniform sizes every seed deals
out), ``warm_streams`` (per client, through the service, before the
window), ``sample_every`` (every n-th stream of a client has its chunk
boundaries and digests compared with the reference), ``server`` (keyword
arguments of ``MoverJaxServer``; empty: defaults). The programs set-up
loads are worked out from the sizes (``warm.py``).
"""

from __future__ import annotations

import json
import time

import numpy as np

#: the clients take their job, fault and all, with the first command
FAULT_AT = "before_warmup"


class State:
    pass


def deal_sizes(p: dict, seed: int) -> list[list[int]]:
    """One list of sizes per client: the same multiset for every seed
    (drawn from ``size_seed``), dealt out in an order the seed decides."""
    n_clients = int(p["tenants"]) * int(p["clients_per_tenant"])
    per = int(p["streams_per_client"])
    rng = np.random.default_rng(int(p["size_seed"]))
    sizes = np.exp(rng.uniform(np.log(p["size_lo"]), np.log(p["size_hi"]),
                               n_clients * per)).astype(np.int64)
    sizes = sizes[np.random.default_rng([seed, 0xD1]).permutation(len(sizes))]
    return sizes.reshape(n_clients, per).tolist()


def setup(ctx) -> State:
    from volsync_tpu.service.server import MoverJaxServer

    st = State()
    st.ctx = ctx
    p = ctx.params
    st.server = MoverJaxServer(**p.get("server", {}))
    st.server.start()
    ctx.on_exit(st.server.stop)
    sizes = deal_sizes(p, ctx.seed)
    st.sizes = sorted({n for client in sizes for n in client})
    per = int(p["clients_per_tenant"])
    st.children = []
    for t in range(int(p["tenants"])):
        child = ctx.children.start("drivers/stream_client.py")
        job = {"port": st.server.port, "token": st.server.token,
               "tenant": f"tenant{t}", "first": t * per,
               "sizes": sizes[t * per: (t + 1) * per], "seed": ctx.seed,
               "sample_every": int(p["sample_every"]), "fault": None,
               "chunker": ctx.config["chunker"]}
        st.children.append((child, job))
    st.started = False
    return st


def _start(st: State) -> None:
    if not st.started:
        for child, job in st.children:
            child.stdin.write(json.dumps(job) + "\n")
            child.stdin.flush()
        st.started = True


def inject(st: State, fault: str) -> None:
    """The control: the sampled streams reach the service with one bit
    flipped, and are held against the bytes the client meant."""
    if fault != "flip_payload_bit":
        raise ValueError(f"stream driver knows no fault {fault!r}")
    if st.started:
        raise RuntimeError("inject before the clients have their job")
    for _, job in st.children:
        job["fault"] = fault


def _command(st: State, line: str) -> list[dict]:
    _start(st)
    for child, _ in st.children:
        child.stdin.write(line + "\n")
        child.stdin.flush()
    return [st.ctx.children.read_json(child) for child, _ in st.children]


def warmup(st: State) -> None:
    """Every (lanes, bucket) program this traffic can form (see
    ``warm.py``), then the cell's own traffic at full concurrency
    through the service."""
    from benchmark import warm

    p = st.ctx.params
    st.plan = warm.stream_plan(st.sizes, p.get("server", {}),
                               len(st.children) * int(p["clients_per_tenant"]))
    print(json.dumps({"warm_plan": st.plan}), flush=True)
    warm.segment_programs(st.server.params, st.plan, st.ctx.seed)
    _command(st, f"warm {int(st.ctx.params['warm_streams'])}")


def run(st: State, seconds: float) -> dict:
    t0 = time.monotonic()
    with st.ctx.annotate("bench.window"):
        answers = _command(st, f"go {seconds} {t0!r}")
    st.answers = answers
    ops = [dict(s, rc=0 if s["error"] is None and s["covered"] else 1)
           for a in answers for s in a["streams"]]
    return {"ops": ops,
            "latencies_ms": [1e3 * (s["t_done"] - s["t_start"])
                             for s in ops if s["rc"] == 0]}


def verify(st: State):
    streams = [s for a in st.answers for s in a["streams"]]
    errors = [s["error"] for s in streams if s["error"]]
    for e in sorted(set(errors))[:5]:
        print(json.dumps({"stream_error": e, "count": errors.count(e)}),
              flush=True)
    n = {"stream_errors": len(errors),
         "streams_not_covered": sum(1 for s in streams
                                    if not s["error"] and not s["covered"]),
         "digests_wrong": sum(a["digests_wrong"] for a in st.answers),
         "boundaries_wrong": sum(a["boundaries_wrong"]
                                 for a in st.answers)}
    failed = n["stream_errors"] + n["streams_not_covered"]
    checks = [{"check": k, "value": v, "limit": 0} for k, v in n.items()]
    checks.append({"check": "digests_compared", "at_least": 1,
                   "value": sum(a["digests_compared"] for a in st.answers)})
    if n["digests_wrong"] or n["boundaries_wrong"]:
        failed = max(failed, 1)
    return len(streams), failed, checks
