"""restic mover data-plane entrypoint (the /entry.sh analogue).

Dispatches on DIRECTION the way mover-restic/entry.sh dispatches on its
argv verb: ``backup`` ensures the repository exists (probe, then init on
"no repository" — entry.sh:42-57), skips empty volumes, backs up with
the TPU engine, applies FORGET_* retention, and optionally prunes;
``restore`` selects a snapshot via RESTORE_AS_OF / SELECT_PREVIOUS and
materializes it. Config arrives exclusively via env + mounts, preserving
the reference's process boundary.
"""

from __future__ import annotations

import logging
import time
from datetime import datetime, timedelta

from volsync_tpu.engine import TreeBackup, restore_snapshot
from volsync_tpu.objstore import open_store
from volsync_tpu.obs import span
from volsync_tpu.repo.repository import (
    RepoError,
    RepoLockedError,
    Repository,
)

log = logging.getLogger("volsync_tpu.mover.restic")


def _parse_within(value: str) -> timedelta:
    """Duration strings like '3h30m', '2d', '1h' (restic --keep-within)."""
    units = {"d": 86400, "h": 3600, "m": 60, "s": 1}
    total = 0.0
    num = ""
    for ch in value:
        if ch.isdigit() or ch == ".":
            num += ch
        elif ch in units and num:
            total += float(num) * units[ch]
            num = ""
        else:
            raise ValueError(f"bad duration {value!r}")
    if num:  # bare number = seconds
        total += float(num)
    return timedelta(seconds=total)


def _open_or_init(env: dict) -> Repository:
    # env carries the full Secret passthrough (AWS_* credentials included),
    # exactly like the reference's mover pod (restic/mover.go:317-364).
    store = open_store(env["RESTIC_REPOSITORY"], env=env)
    password = env.get("RESTIC_PASSWORD") or None
    # Per-repo chunker-alignment knob (VOLSYNC_CHUNKER_ALIGN, set at
    # CREATION only — existing repos keep their stored config forever).
    # The default align=4096 runs the fused single-dispatch engine but
    # makes cuts content-defined only modulo the 4 KiB phase: inserting
    # a non-page-multiple length desynchronizes the rest of the file
    # from the parent's chunks. Insert-heavy workloads can pick align=1
    # (fully shift-invariant, classic engine) or 64 (split-phase).
    # See docs/usage.md "Chunker alignment".
    chunker = None
    if env.get("VOLSYNC_CHUNKER_ALIGN"):
        align = int(env["VOLSYNC_CHUNKER_ALIGN"])
        if align not in (1, 64, 4096):
            raise ValueError(
                f"VOLSYNC_CHUNKER_ALIGN={align}: must be 1 (shift-"
                "invariant), 64 (split-phase), or 4096 (fused page grid)")
        from volsync_tpu.repo.repository import DEFAULT_CHUNKER

        chunker = {**DEFAULT_CHUNKER, "align": align}
    try:
        repo = Repository.open(store, password=password)
    except RepoError:
        log.info("repository not initialized; creating (entry.sh:52-57)")
        try:
            repo = Repository.init(store, password=password,
                                   chunker=chunker)
        except RepoError:
            # Lost the init race to a concurrent mover sharing this
            # repository: open the winner's (init is atomic, so the
            # config is whole).
            repo = Repository.open(store, password=password)
    # Wait out a concurrent holder instead of failing the sync on first
    # contention (shared repositories across CRs are supported).
    repo.default_lock_wait = float(env.get("LOCK_WAIT_SECONDS", "120"))
    return repo


def _forget_kwargs(env: dict) -> dict:
    kw = {}
    for key, name in (("FORGET_LAST", "last"), ("FORGET_HOURLY", "hourly"),
                      ("FORGET_DAILY", "daily"), ("FORGET_WEEKLY", "weekly"),
                      ("FORGET_MONTHLY", "monthly"),
                      ("FORGET_YEARLY", "yearly")):
        if env.get(key):
            kw[name] = int(env[key])
    if env.get("FORGET_WITHIN"):
        kw["within"] = _parse_within(env["FORGET_WITHIN"])
    return kw


#: Mover exit code for "repository locked by another holder" — nonzero so
#: the Job backoff machinery retries the sync (movers/common.py), distinct
#: from the config errors (2) and no-matching-snapshot (3).
RC_LOCKED = 4


#: Mesh hashers memoized per chunker-param set: their shard_map jit caches
#: live on the instance, so rebuilding per Job would re-pay every XLA
#: compile each sync iteration.
_MESH_HASHERS: dict = {}


def mesh_hasher(params):
    """The process's one mesh hasher for a chunker-param set: what a
    ``VOLSYNC_ENGINE=mesh`` backup hashes with, and what a set-up that
    loads its programs ahead of the first Job has to load them into."""
    from volsync_tpu.parallel.sharded_chunker import MeshChunkHasher

    hasher = _MESH_HASHERS.get(params)
    if hasher is None:
        hasher = _MESH_HASHERS[params] = MeshChunkHasher(params)
    return hasher


#: Mover exit code for "the mover-jax service did not hash the volume"
#: (unreachable, wrong token, other chunker parameters, an answer that
#: does not cover a file, the retry policy exhausted): nonzero, no
#: snapshot saved, the Job's backoff retries the sync.
RC_SERVICE = 5

#: what VOLSYNC_ENGINE=service needs beside it, and cannot guess
_SERVICE_ENV = ("MOVER_JAX_ADDRESS", "MOVER_JAX_TOKEN")


def _select_hasher(env: dict, repo: Repository, namespace: str):
    """VOLSYNC_ENGINE=mesh shards the scan over the device mesh
    (parallel/sharded_chunker.py); VOLSYNC_ENGINE=service hashes
    through the mover-jax service at MOVER_JAX_ADDRESS, as tenant
    MOVER_JAX_TENANT (default: the mover's namespace), in a mover that
    holds no accelerator (service/hasher.py); default is the
    single-chip engine. All produce bit-identical snapshots, so the
    switch is purely a throughput/topology choice."""
    engine = env.get("VOLSYNC_ENGINE", "").lower()
    if engine not in ("mesh", "service"):
        return None
    from volsync_tpu.engine.chunker import params_from_config

    params = params_from_config(repo.chunker_params)
    if engine == "mesh":
        return mesh_hasher(params)
    from volsync_tpu.service.hasher import open_hasher

    return open_hasher(env["MOVER_JAX_ADDRESS"], env["MOVER_JAX_TOKEN"],
                       env.get("MOVER_JAX_TENANT") or namespace, params)


def restic_entrypoint(ctx) -> int:
    env = ctx.env
    direction = env.get("DIRECTION", "backup")
    for required in ("RESTIC_REPOSITORY",):
        if required not in env:
            log.error("missing env %s (entry.sh:232-240)", required)
            return 2
    service_errors: tuple = ()
    if (direction == "backup"
            and env.get("VOLSYNC_ENGINE", "").lower() == "service"):
        # never a silent fall back to a local device
        for required in _SERVICE_ENV:
            if not env.get(required):
                log.error("VOLSYNC_ENGINE=service without %s", required)
                return 2
        host, _, port = env["MOVER_JAX_ADDRESS"].rpartition(":")
        if not host or not port.isdigit():
            log.error("MOVER_JAX_ADDRESS %r is not host:port",
                      env["MOVER_JAX_ADDRESS"])
            return 2
        from volsync_tpu.service.hasher import ServiceHashError

        service_errors = (ServiceHashError,)
    try:
        return _dispatch(ctx, env, direction)
    except service_errors as ex:
        log.error("backup failed, no snapshot saved: %s", ex)
        return RC_SERVICE
    except RepoLockedError as ex:
        # Two CRs sharing one repository collide (shared backup vs
        # exclusive forget/prune): fail this attempt cleanly and let the
        # Job retry, don't crash the mover.
        log.warning("repository locked, retrying later: %s", ex)
        return RC_LOCKED


def _dispatch(ctx, env: dict, direction: str) -> int:
    data = ctx.mounts["data"]

    if direction == "backup":
        if not any(data.iterdir()):
            log.info("source is empty, skipping backup (entry.sh:44-50)")
            return 0
        with span("repo.open"):
            repo = _open_or_init(env)
        t0 = time.perf_counter()
        from volsync_tpu.movers.base import normalize_protocol

        # SYNC_PROTOCOL=auto delegates per-file full-vs-cdc storage to
        # the cost-model planner (engine/protoplan.py); default stays
        # the reference-equivalent CDC chunking. "delta" makes no sense
        # against a dedup repository and degrades to the default.
        proto = normalize_protocol(env.get("SYNC_PROTOCOL"), default="cdc")
        if proto == "delta":
            proto = "cdc"
        hasher = _select_hasher(env, repo, ctx.namespace)
        try:
            with span("mover.restic.backup"):
                snap_id, stats = TreeBackup(
                    repo, hasher=hasher, protocol=proto).run(
                    data, hostname=env.get("HOSTNAME", "volsync"))
        finally:
            close = getattr(hasher, "close", None)
            if close is not None:
                close()
        log.info("backup snapshot=%s stats=%s", snap_id, stats.as_dict())
        ctx.report_transfer(stats.bytes_scanned, time.perf_counter() - t0)
        # Maintenance after a durable snapshot must not fail the sync: a
        # lock collision here defers forget/prune to the next iteration
        # instead of discarding the successful backup (a retry would
        # duplicate the snapshot).
        try:
            kw = _forget_kwargs(env)
            if kw:
                removed = repo.forget(**kw)
                log.info("forget removed %d snapshots", len(removed))
            if env.get("PRUNE") == "1":
                report = repo.prune()
                log.info("prune: %s", report)
        except RepoLockedError as ex:
            log.warning("maintenance deferred (repository locked): %s", ex)
        return 0

    if direction == "prune":
        with span("repo.open"):
            repo = _open_or_init(env)
        log.info("prune: %s", repo.prune())
        return 0

    if direction == "restore":
        with span("repo.open"):
            repo = Repository.open(
                open_store(env["RESTIC_REPOSITORY"], env=env),
                password=env.get("RESTIC_PASSWORD") or None)
        repo.default_lock_wait = float(env.get("LOCK_WAIT_SECONDS", "120"))
        as_of = (datetime.fromisoformat(env["RESTORE_AS_OF"])
                 if env.get("RESTORE_AS_OF") else None)
        previous = int(env.get("SELECT_PREVIOUS", "0"))
        t0 = time.perf_counter()
        out = restore_snapshot(repo, data, restore_as_of=as_of,
                               previous=previous)
        if out is None:
            log.error("no snapshot matches the restore selectors")
            return 3
        log.info("restore: %s", out)
        ctx.report_transfer(out.get("bytes", 0), time.perf_counter() - t0)
        return 0

    log.error("unknown DIRECTION %r", direction)
    return 2
