"""One home for JAX's persistent compilation cache directory.

Every cold process pays the device programs' compiles again (tens of
seconds per ``(S, P)`` bucket of the batched segment program on a
v5e), so each launcher — the operator, the mover-jax service,
``bench.py``, ``chip_smoke.py`` and the tuning scripts — calls
``configure()`` before its first use of JAX. Where the environment
places the cache (``JAX_COMPILATION_CACHE_DIR``) that directory is used
and no other is set; otherwise it lives at ``<checkout>/.jax_cache``:
a fixed path, because the path is part of what a cache hit depends on
across runs.
"""

from __future__ import annotations

import os
import sys
from pathlib import Path

from volsync_tpu.envflags import env_str

_ENV = "JAX_COMPILATION_CACHE_DIR"


def configure() -> str:
    """Place the compile cache; returns the directory in effect.

    An unset variable is exported (child processes inherit the same
    directory), and a jax that is already imported is told directly —
    it read the environment at import time."""
    path = env_str(_ENV)
    if path is None:
        path = str(Path(__file__).resolve().parent.parent / ".jax_cache")
        os.environ[_ENV] = path
    jax = sys.modules.get("jax")
    if jax is not None:
        jax.config.update("jax_compilation_cache_dir", path)
    return path
