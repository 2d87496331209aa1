"""Mesh-parallel data plane: a single volume's bytes shard over the
``seq`` ring of devices with ppermute halo exchange at the seams
(parallel/sharded_chunker.py), within one host or, after
``multihost.init_distributed``, across hosts.
"""

from volsync_tpu.parallel.mesh import SEQ, make_stream_mesh

__all__ = ["SEQ", "make_stream_mesh"]
