"""The JAX profiler around the measured window of a ``--trace 1`` run:
Python tracing off, TraceAnnotations on, the sync marker that lets
``trace_reduce`` lay the program's span ring on the trace's clock."""

from __future__ import annotations

import contextlib
import glob
import os
import time

from benchmark import trace_reduce


class Tracer:
    def __init__(self, out_dir: str):
        self.out_dir = out_dir
        self._t0 = None

    def start(self) -> None:
        import jax
        from volsync_tpu.obs import (reset_trace, span, trace_context,
                                     trace_events)

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        reset_trace()
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)
        self._t0 = time.perf_counter()
        with trace_context(sampled=True), \
                jax.profiler.TraceAnnotation(trace_reduce.SYNC), \
                span(trace_reduce.SYNC):
            pass
        self._sync_ring = next(
            (e["ts"] for e in trace_events()
             if e.get("name") == trace_reduce.SYNC), None)

    def stop(self) -> dict:
        import jax
        from volsync_tpu.obs import trace_events

        window_s = time.perf_counter() - self._t0
        ring = trace_events()
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(
            self.out_dir, "plugins", "profile", "*", "*.xplane.pb"))
        if not found:
            raise RuntimeError("the profiler wrote no xplane")
        return trace_reduce.reduce_file(found[0], window_s, ring,
                                        self._sync_ring)

    @contextlib.contextmanager
    def annotate(self, name: str):
        """The benchmark's own host span, in the profiler's trace, with
        a sampled trace context under it so that the program's spans
        land in its flight-recorder ring."""
        import jax
        from volsync_tpu.obs import trace_context

        with trace_context(sampled=True), jax.profiler.TraceAnnotation(name):
            yield
