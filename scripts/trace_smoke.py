"""Perfetto-loadability gate for the flight recorder (`make trace-smoke`).

Drives a tiny pipelined backup (stream_chunk_batches -> Repository ->
MemObjectStore) under a fresh TraceContext, exports the flight recorder
with ``dump_trace``, and asserts the Chrome-trace-event contract that
Perfetto / chrome://tracing require: a ``traceEvents`` list whose
complete ("X") events carry name/ts/dur/pid/tid/args, span args carry
the trace id + tenant tag, and at least one parent/child edge links two
recorded spans of the same trace. Fails loudly (nonzero exit, assertion
message) on any violation; prints one OK line otherwise. Wired into
scripts/static_check.sh so a dump that Perfetto would reject cannot
ship.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# Host-side only: the smoke gate must never touch (or wait on) a device.
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _run_tiny_pipeline() -> None:
    """One ~2 MiB pipelined backup under a tenant-tagged trace: enough
    to populate engine.read/engine.device/repo.* spans plus an outer
    smoke.pipeline span every other span parents to."""
    import numpy as np

    from volsync_tpu.engine.chunker import (
        DeviceChunkHasher, stream_chunk_batches)
    from volsync_tpu.objstore.store import MemObjectStore
    from volsync_tpu.obs import (
        reset_spans, reset_trace, span, trace_context)
    from volsync_tpu.ops.gearcdc import GearParams
    from volsync_tpu.repo.repository import Repository

    total = 2 << 20
    data = np.random.RandomState(3).randint(
        0, 256, size=(total,), dtype=np.uint8).tobytes()
    params = GearParams(min_size=64 * 1024, avg_size=128 * 1024,
                        max_size=256 * 1024, seed=7, align=4096)
    pos = [0]

    def reader(nbytes: int) -> bytes:
        piece = data[pos[0]: pos[0] + nbytes]
        pos[0] += len(piece)
        return piece

    repo = Repository.init(MemObjectStore())
    repo.pipelined = True
    reset_spans()
    reset_trace()
    with trace_context(tenant="smoke", stream_id="trace-smoke"):
        with span("smoke.pipeline"):
            for chunks in stream_chunk_batches(
                    reader, params, segment_size=512 * 1024,
                    hasher=DeviceChunkHasher(params),
                    readahead=2):
                repo.add_blobs(
                    "data", [(digest, chunk) for chunk, digest in chunks])
            repo.flush()


def main() -> int:
    _run_tiny_pipeline()
    from volsync_tpu.obs import dump_trace

    with tempfile.TemporaryDirectory() as tmp:
        path = dump_trace(path=os.path.join(tmp, "trace-smoke.json"),
                          trigger="trace_smoke")
        with open(path, encoding="utf-8") as fh:
            doc = json.load(fh)

    events = doc.get("traceEvents")
    assert isinstance(events, list) and events, "no traceEvents"
    spans = [e for e in events if e.get("ph") == "X"]
    assert spans, "no complete (ph=X) span events"
    for e in spans:
        for key in ("name", "ts", "dur", "pid", "tid", "args"):
            assert key in e, f"span event missing {key!r}: {e}"
    names = {e["name"] for e in spans}
    for want in ("smoke.pipeline", "engine.read", "engine.device",
                 "repo.seal", "repo.pack_upload"):
        assert want in names, f"missing span {want!r} (got {sorted(names)})"
    traces = {e["args"]["trace_id"] for e in spans}
    assert len(traces) == 1, f"expected one trace, got {traces}"
    tagged = [e for e in spans if e["args"].get("tenant") == "smoke"]
    assert tagged, "no tenant-tagged span"
    by_id = {e["args"]["span_id"] for e in spans}
    edges = [e for e in spans
             if e["args"].get("parent_span_id") in by_id]
    assert edges, "no parent/child edge between recorded spans"
    threads = [e for e in events if e.get("ph") == "M"
               and e.get("name") == "thread_name"]
    assert threads, "no thread_name metadata events"
    assert doc.get("trigger", {}).get("reason") == "trace_smoke", doc.get(
        "trigger")
    print(f"trace-smoke: OK ({len(spans)} spans across {len(names)} "
          f"stages, {len(threads)} threads, Perfetto-loadable)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
