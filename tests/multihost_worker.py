"""Worker process for the 2-process multi-host execution test.

Launched by tests/test_multihost_exec.py with the standard env triplet
(JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES / JAX_PROCESS_ID) as
``multihost_worker.py treebackup <volume>``. Joins the coordinator
through the framework's own wiring (parallel/multihost.init_distributed)
and builds the ``seq`` mesh over the GLOBAL device set — collectives
here cross the process boundary, the DCN-analogue path.
"""

import sys

import jax

jax.config.update("jax_platforms", "cpu")
# Cross-process CPU collectives (the ICI/DCN stand-in for tests).
jax.config.update("jax_cpu_collectives_implementation", "gloo")

from volsync_tpu.parallel.multihost import init_distributed  # noqa: E402


def product_main(volume: str) -> int:
    """PRODUCT path across the process boundary: the real TreeBackup
    with a MeshChunkHasher whose mesh spans BOTH processes — every
    chunk boundary and blob id is computed by cross-process
    collectives. Process 0 writes a real on-disk repository (the
    parent restores from it); process 1's writes go to a throwaway
    in-memory store. Both print their snapshot's TREE id: content
    identity (the snapshot envelope itself carries wall time + a
    sealing nonce by design, like restic's)."""
    import os
    from pathlib import Path

    from volsync_tpu.engine import TreeBackup
    from volsync_tpu.engine.chunker import params_from_config
    from volsync_tpu.objstore.store import FsObjectStore, MemObjectStore
    from volsync_tpu.parallel.sharded_chunker import (
        MeshChunkHasher,
        make_stream_mesh,
    )
    from volsync_tpu.repo.repository import Repository

    info = init_distributed()
    assert info["process_count"] == 2, info
    pid = info["process_index"]
    store = (FsObjectStore(os.environ["VOLSYNC_REPO_OUT"]) if pid == 0
             else MemObjectStore())
    repo = Repository.init(store)
    mesh = make_stream_mesh(jax.devices())  # global: spans both procs
    hasher = MeshChunkHasher(params_from_config(repo.chunker_params),
                             mesh=mesh)
    snap, stats = TreeBackup(repo, hasher=hasher).run(Path(volume))
    assert snap is not None
    tree = repo.list_snapshots()[-1][1]["tree"]
    print(f"MULTIHOST-TREEBACKUP-OK p{pid} tree={tree} "
          f"files={stats.files} bytes={stats.bytes_scanned} "
          f"mesh={mesh.devices.size}", flush=True)
    return 0


if __name__ == "__main__":
    if len(sys.argv) != 3 or sys.argv[1] != "treebackup":
        sys.exit("usage: multihost_worker.py treebackup <volume>")
    sys.exit(product_main(sys.argv[2]))
