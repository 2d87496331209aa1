"""The device mesh of the data plane: one ``seq`` ring.

The reference has *no* intra-volume parallel scan (SURVEY.md §5
long-context note); here a single volume's byte stream shards across
every chip of the ring (parallel/sharded_chunker.py). Chunk-boundary
continuity across a seam is a ``ppermute`` halo along ``seq``; the
candidate tables and digests are gathered with ``all_gather`` / ``psum``
over it.

The analyzer's VL205 checks every ``PartitionSpec`` entry and collective
axis name in the package against the axes this module declares.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh

SEQ = "seq"


def make_stream_mesh(devices=None) -> Mesh:
    """All devices as one ``seq`` ring: one big backup wants the whole
    machine."""
    if devices is None:
        devices = jax.devices()
    return Mesh(np.asarray(devices), (SEQ,))
