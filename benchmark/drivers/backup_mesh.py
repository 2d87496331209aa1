"""The ``backup`` driver on a host of several chips: whole first backups
through the restic mover entry with the configuration's deployment
setting in the mover's env (``mover_env``: ``VOLSYNC_ENGINE=mesh``),
one after another, until the window closes.

Set-up, window, control and the check of every operation are
``drivers/backup.py``'s, by import. What differs: the mover's env, the
programs set-up loads (``warm_mesh.py``: the mesh hasher's, worked out
from the configuration's sizes), and three checks more, each printed
beside its limit, read from the program's counters and from the store:
segments went to the mesh at all (``mesh.dispatches``), each was laid
over as many devices as the configuration's ``chips``
(``mesh.shards``), and the store holds under ``stored_ratio_limit`` of
the bytes moved (the repeat was found across every shard seam).

A program without ``entry.mesh_hasher`` cannot have its mesh programs
loaded at set-up (they would compile inside the window): the driver
refuses it when it is imported, before anything is set up.
"""

from __future__ import annotations

import json

from benchmark import end_to_end, mover
from benchmark.drivers import backup
from benchmark.drivers.backup import FAULT_AT, inject  # noqa: F401

try:
    from volsync_tpu.movers.restic.entry import mesh_hasher
except ImportError:
    raise SystemExit("benchmark: this program has no entry.mesh_hasher: "
                     "its mesh programs cannot be loaded at set-up")


def setup(ctx):
    st = backup.setup(ctx)
    base, extra = st.store.env, dict(ctx.config["mover_env"])
    st.store.env = lambda prefix: {**base(prefix), **extra}
    return st


def warmup(st) -> None:
    """Every fused mesh program the shape's sizes can present (see
    ``warm_mesh.py``), then one whole operation of the cell's own shape
    on other bytes: everything else a first operation pays."""
    from volsync_tpu.engine.chunker import params_from_config
    from volsync_tpu.repo.repository import DEFAULT_CHUNKER

    from benchmark import volumes, warm_mesh

    chunker = params_from_config(DEFAULT_CHUNKER)
    hasher = mesh_hasher(chunker)
    sizes = [n for _, n, _ in volumes.plan(st.ctx.shape, st.ctx.seed)]
    st.plan = warm_mesh.mesh_plan(sizes, chunker, hasher)
    print(json.dumps({"warm_plan": st.plan, "shards": hasher.n_shards}),
          flush=True)
    warm_mesh.mesh_programs(hasher, st.plan, st.ctx.seed)
    rc = mover.run_mover("backup", st.store.env("warm"), st.warm)
    if rc != 0:
        raise RuntimeError(f"warm-up backup exited {rc}")
    print(json.dumps({"programs_after_warm_up": hasher.fused_programs()}),
          flush=True)


def run(st, seconds: float) -> dict:
    from volsync_tpu.obs import counter_totals

    obs = backup.run(st, seconds)
    st.counts = counter_totals()  # nothing counts after the window
    ratio = end_to_end.stored_ratio(obs)
    st.stored_ratio = 1.0 if ratio is None else ratio  # nothing moved
    return obs


def verify(st):
    attempted, failed, checks = backup.verify(st)
    staged = st.counts.get("mesh.dispatches", 0)
    chips = int(st.ctx.config["chips"])
    checks += [
        {"check": "mesh_dispatches", "value": staged, "at_least": 1},
        {"check": "mesh_shards_off_chips_a_dispatch",
         "value": abs(st.counts.get("mesh.shards", 0) - chips * staged),
         "limit": 0},
        {"check": "stored_ratio", "value": st.stored_ratio,
         "limit": float(st.ctx.params["stored_ratio_limit"])},
    ]
    return attempted, failed, checks
