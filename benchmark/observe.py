"""What the harness observes around the program: the device block,
compile events (``jax.monitoring``), peak device memory."""

from __future__ import annotations

import logging
import threading


class NoAccelerator(Exception):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def device_block(chips: int, allow_other: bool) -> dict:
    """platform / kind / count as JAX reports them. Off a TPU this
    raises unless a rehearsal size was named (``allow_other``): a
    rehearsal prints no number under a metric's name."""
    import jax

    devs = jax.devices()
    block = {"platform": devs[0].platform, "kind": devs[0].device_kind,
             "count": len(devs)}
    if allow_other:
        return block
    if block["platform"] != "tpu":
        raise NoAccelerator(f"jax.devices()[0].platform is "
                            f"{block['platform']!r}, not 'tpu'")
    if block["count"] != chips:
        raise NoAccelerator(f"the cell asks for {chips} chip(s), "
                            f"jax reports {block['count']}")
    return block


def memory_stat(key: str):
    """The largest ``memory_stats()[key]`` over the local devices
    (None where the backend reports none, as the CPU does)."""
    import jax

    vals = []
    for d in jax.local_devices():
        stats = d.memory_stats()
        if stats and key in stats:
            vals.append(int(stats[key]))
    return max(vals) if vals else None


class CompileCounter:
    """Backend-compile events (a persistent-cache read-back fires one
    too: it is what a run pays either way) and cache hits."""

    EVENTS = {"/jax/core/compile/backend_compile_duration": "compiles",
              "/jax/compilation_cache/cache_hits": "cache_hits"}

    def __init__(self):
        self._lock = threading.Lock()
        self.counts = {"compiles": 0, "cache_hits": 0}
        self.seconds = 0.0
        self.programs: list[str] = []

    def install(self) -> "CompileCounter":
        import jax.monitoring as mon

        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)
        # which program at which shapes: JAX says it at DEBUG level on
        # this logger, before it compiles or reads the cache
        log = logging.getLogger("jax._src.interpreters.pxla")
        log.setLevel(logging.DEBUG)
        log.addFilter(_ProgramNames(self))
        return self

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        name = self.EVENTS.get(event)
        if name:
            with self._lock:
                self.counts[name] += 1
                self.seconds += duration

    def _on_event(self, event: str, **_kw) -> None:
        name = self.EVENTS.get(event)
        if name:
            with self._lock:
                self.counts[name] += 1

    def snapshot(self) -> dict:
        with self._lock:
            return {**self.counts, "compile_seconds": self.seconds}

    def programs_since(self, n: int) -> list[str]:
        with self._lock:
            return self.programs[n:]


class _ProgramNames(logging.Filter):
    """Notes the program and shapes of each "Compiling ..." record and
    lets only what JAX would have shown anyway travel on."""

    def __init__(self, counter: CompileCounter):
        super().__init__()
        self.counter = counter

    def filter(self, record: logging.LogRecord) -> bool:
        if str(record.msg).startswith("Compiling %s with global"):
            name, shapes = record.args[0], str(record.args[1])
            with self.counter._lock:
                self.counter.programs.append(f"{name} {shapes[:120]}")
        return record.levelno > logging.DEBUG
