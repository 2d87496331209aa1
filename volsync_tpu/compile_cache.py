"""One home for JAX's persistent compilation cache directory.

Every cold process pays the device programs' compiles again (tens of
seconds per ``(S, P)`` bucket of the batched segment program on a
v5e), so each launcher — the operator, the mover-jax service,
``chip_smoke.py`` and the profiling scripts — calls
``configure()`` before its first use of JAX. Where the environment
places the cache (``JAX_COMPILATION_CACHE_DIR``) that directory is used
and no other is set; otherwise it lives at ``<checkout>/.jax_cache``:
a fixed path, because the path is part of what a cache hit depends on
across runs.

``configure()`` also starts the count of what loading programs costs
the process (``load_totals()``): a program is traced, lowered and then
compiled or read back from the cache, and only the last shows in the
cache's own hit and miss counts.
"""

from __future__ import annotations

import bisect
import os
import threading
from pathlib import Path

from volsync_tpu.envflags import env_str

_ENV = "JAX_COMPILATION_CACHE_DIR"

#: jax.monitoring time-span events -> the key their seconds are summed
#: under. The backend event fires once a program whether it was
#: compiled or read back from the persistent cache.
_LOAD_SECONDS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_s",
}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"

_load_lock = threading.Lock()
_load = {"trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0,
         "backend_events": 0, "cache_hits": 0}
#: the union of every counted region: disjoint [start, end], by start
_covered: list = []
_listening = False


def _cover(start: float, end: float) -> None:
    """Merge [start, end] into ``_covered`` (caller holds the lock)."""
    i = bisect.bisect_left(_covered, [start, start])
    if i and _covered[i - 1][1] >= start:
        i -= 1
    j = i
    while j < len(_covered) and _covered[j][0] <= end:
        start = min(start, _covered[j][0])
        end = max(end, _covered[j][1])
        j += 1
    _covered[i:j] = [[start, end]]


def _on_span(event: str, start: float, end: float, **_kw) -> None:
    key = _LOAD_SECONDS.get(event)
    if key is not None:
        with _load_lock:
            _load[key] += end - start
            if key == "backend_s":
                _load["backend_events"] += 1
            _cover(start, end)


def _on_event(event: str, **_kw) -> None:
    if event == _CACHE_HIT:
        with _load_lock:
            _load["cache_hits"] += 1


def _listen() -> None:
    """Register the listeners, once a process."""
    global _listening
    import jax.monitoring as mon

    with _load_lock:
        if _listening:
            return
        _listening = True
    mon.register_event_time_span_listener(_on_span)
    mon.register_event_listener(_on_event)


def load_totals() -> dict:
    """What loading device programs has cost this process since
    ``configure()``. ``trace_s``, ``lower_s`` and ``backend_s`` (compile,
    or the persistent cache's read-back) are the seconds JAX reports an
    event, so a jit traced inside another counts in both and two
    threads count twice; ``load_s`` is the time that passed under any
    of them (the union of the regions). ``compiles`` and ``cache_hits``
    count the programs the backend compiled and the cache gave back.
    Never zeroed: set-up is before any measured window."""
    with _load_lock:
        out = dict(_load)
        out["load_s"] = sum(end - start for start, end in _covered)
    out["compiles"] = out.pop("backend_events") - out["cache_hits"]
    return out


def configure() -> str:
    """Place the compile cache; returns the directory in effect.

    An unset variable is exported (child processes inherit the same
    directory) before jax is imported here, and a jax that was already
    imported is told directly — it read the environment at import
    time."""
    path = env_str(_ENV)
    if path is None:
        path = str(Path(__file__).resolve().parent.parent / ".jax_cache")
        os.environ[_ENV] = path
    import jax

    jax.config.update("jax_compilation_cache_dir", path)
    _listen()
    return path
