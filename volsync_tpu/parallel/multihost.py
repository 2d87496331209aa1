"""Multi-host initialization for the data-plane mesh.

The reference scales across hosts with NCCL/MPI-free point-to-point
transports (SSH / HTTPS-S3 / TLS BEP — SURVEY.md §2.3); control fans out
as one operator per cluster driving mover pods anywhere. The TPU build
keeps that shape for the *movers* (one volsync-manager per TPU VM,
network movers between them — movers/rsync/standalone.py, service/), and
adds what the reference never had: a single logical device mesh spanning
hosts, so ONE volume's scan can shard over an entire pod slice.

``init_distributed()`` wires ``jax.distributed`` from the standard TPU
pod environment (or explicit arguments), after which ``jax.devices()``
returns every chip in the slice and the mesh builder
(parallel/mesh.make_stream_mesh) spans hosts transparently. The fused
sharded engine's only collectives are an all-gather of the
32B-per-4KiB digest stream and the candidate tables
(sharded_chunker._build_fused_fn) — XLA routes them over ICI within a
host and DCN between hosts; no framework code changes.

Single-host processes (the common case, and all tests) never call this:
jax.devices() already returns the local chips.
"""

from __future__ import annotations

import os
from typing import Optional


def init_distributed(coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None,
                     require: bool = False) -> dict:
    """Initialize jax.distributed for a multi-host mesh.

    With no arguments, defers to JAX's TPU-pod auto-detection (the
    metadata-provided coordinator), falling back to the standard
    ``JAX_COORDINATOR_ADDRESS`` / ``JAX_NUM_PROCESSES`` /
    ``JAX_PROCESS_ID`` env triplet. Returns a summary dict
    (process_index, process_count, local/global device counts) for the
    operator's startup log. Idempotent: calling twice is a no-op.

    ``require=True`` (the operator's VOLSYNC_DISTRIBUTED=1 path) turns
    the auto-detection warn-and-continue fallback into a hard failure:
    when the operator EXPLICITLY asked for distributed mode, silently
    proceeding single-host would leave the pod-slice peers that did
    join blocked at the coordinator barrier forever.
    """
    import logging

    import jax

    log = logging.getLogger("volsync.multihost")
    args = (coordinator_address, num_processes, process_id)
    prev = getattr(init_distributed, "_done_args", None)
    if prev is not None:
        if prev != args:
            raise RuntimeError(
                f"init_distributed already ran with {prev}; cannot "
                f"re-initialize with {args} (jax.distributed is "
                "once-per-process)")
        return _summary(jax)
    coordinator_address = coordinator_address or os.environ.get(
        "JAX_COORDINATOR_ADDRESS")
    if num_processes is None and os.environ.get("JAX_NUM_PROCESSES"):
        num_processes = int(os.environ["JAX_NUM_PROCESSES"])
    if process_id is None and os.environ.get("JAX_PROCESS_ID"):
        process_id = int(os.environ["JAX_PROCESS_ID"])
    if coordinator_address or num_processes is not None:
        # Explicit multi-host configuration: failures must propagate —
        # a worker silently degrading to single-host would leave its
        # peers blocked at the coordinator barrier.
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes, process_id=process_id)
    else:
        # No explicit configuration: TPU pod slices self-describe, and
        # single-host/CPU environments raise — treat that as "nothing
        # to join" but say so, since on a real slice it means this
        # worker is about to run alone while peers wait.
        try:
            jax.distributed.initialize()
        except Exception as e:  # noqa: BLE001
            if require:
                raise RuntimeError(
                    "distributed mode was explicitly requested "
                    "(VOLSYNC_DISTRIBUTED=1) but jax.distributed "
                    "initialization failed; refusing to run single-host "
                    "while pod-slice peers block at the coordinator "
                    f"barrier: {e}") from e
            log.warning(
                "jax.distributed auto-detection unavailable (%s) — "
                "continuing single-host; on a pod slice set "
                "JAX_COORDINATOR_ADDRESS/JAX_NUM_PROCESSES/"
                "JAX_PROCESS_ID explicitly", e)
            # Do NOT latch: a failed soft attempt must not satisfy a
            # later require=True call with a cached single-host summary
            # (the hard-fail guarantee would be silently bypassed).
            return _summary(jax)
    init_distributed._done_args = args
    return _summary(jax)


def _summary(jax) -> dict:
    return {
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "local_devices": len(jax.local_devices()),
        "global_devices": len(jax.devices()),
    }
