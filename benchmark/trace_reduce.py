"""xplane -> the device's busy and idle time, per-program device time,
the operations that took most time, and the longest idle gaps named by
what the host was doing.

Reads a ``.xplane.pb`` with ``jax.profiler.ProfileData`` alone. A TPU
plane is ``/device:TPU:<n>``; its ``XLA Ops`` line holds one event per
HLO operation run, ``XLA Modules`` one per program run. Busy time is
the union of the ``XLA Ops`` intervals (of ``XLA Modules`` where a plane
has no ops line), averaged over the device planes.

Host spans (the program's flight-recorder ring, Chrome trace events on
the host's ``perf_counter``) are laid on the trace's clock through a
marker recorded in both: a ``bench.sync`` TraceAnnotation in the xplane
and a ``bench.sync`` span in the ring.

``python benchmark/trace_reduce.py <file.xplane.pb>`` prints the
reduction and the planes and lines it saw.
"""

from __future__ import annotations

import json
import re
import sys

SYNC = "bench.sync"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
_DEVICE = re.compile(r"^/device:TPU:\d+$")
_RUN_ID = re.compile(r"\(\d+\)$")
#: device events that end before this share of the window: a cut trace
KEPT_SHARE = 0.75


def union_seconds(intervals) -> tuple[float, list[tuple[float, float]]]:
    """(covered length, merged intervals) of [(start, end)] in ns ->
    seconds / ns."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return (sum(e - s for s, e in merged) / 1e9,
            [(s, e) for s, e in merged])


def short_name(name: str) -> str:
    """An op's event name is its whole HLO line; what precedes `` = ``
    (``%while.41``) names it."""
    return name.split(" = ", 1)[0].lstrip("%")[:80]


def load(path: str) -> dict:
    """{plane name: {line name: [(event name, start ns, duration ns)]}},
    keeping what the reduction reads: the ops and modules lines of the
    device planes and the ``bench.*`` annotations of the host planes.
    ``"#events"`` under a plane counts every event of every line seen."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out: dict = {}
    for plane in data.planes:
        lines = out.setdefault(plane.name, {})
        counts = lines.setdefault("#events", {})
        device = bool(_DEVICE.match(plane.name))
        host = plane.name.startswith("/host:")
        for line in plane.lines:
            keep = lines.setdefault(line.name, []) if (
                host or (device and line.name in (OPS_LINE, MODULES_LINE))
            ) else None
            n = 0
            for ev in line.events:
                n += 1
                if keep is None:
                    continue
                # an op's name is its whole HLO line (kilobytes): keep
                # the short one, or a million events fill the memory
                name = short_name(ev.name)
                if device or name.startswith("bench."):
                    keep.append((sys.intern(name), float(ev.start_ns),
                                 float(ev.duration_ns)))
            counts[line.name] = counts.get(line.name, 0) + n
    return out


def _top(totals: dict, k: int = 10) -> list:
    return [[name, secs] for name, secs in
            sorted(totals.items(), key=lambda kv: -kv[1])[:k]]


def host_spans_on_trace_clock(planes: dict, ring: list,
                              sync_ring: float | None) -> list:
    """[(name, start ns, end ns)] of the host's annotations: the
    benchmark's own (``bench.*`` TraceAnnotations, already on the
    trace's clock) and the program's ring spans moved onto it."""
    out = []
    sync_trace = None
    for name, lines in planes.items():
        if name.startswith("/host:"):
            for ln, evs in lines.items():
                if ln == "#events":
                    continue
                for ev, s, d in evs:
                    if ev == SYNC and sync_trace is None:
                        sync_trace = s
                    elif ev.startswith("bench."):
                        out.append((ev, s, s + d))
    if sync_trace is not None and sync_ring is not None:
        for e in ring:
            if e.get("ph") == "X" and e.get("name") != SYNC:
                s = (e["ts"] - sync_ring) * 1e3 + sync_trace
                out.append((e["name"], s, s + e["dur"] * 1e3))
    return out


def name_gap(gap: tuple[float, float], spans: list) -> str:
    """The host span that covers most of the gap; of equals, the
    shortest (the innermost). ``host:unnamed`` where none overlaps."""
    best, best_key = "host:unnamed", (0.0, 0.0)
    for name, s, e in spans:
        cover = min(e, gap[1]) - max(s, gap[0])
        if cover <= 0:
            continue
        key = (cover, -(e - s))
        if key > best_key:
            best, best_key = name, key
    return best


def reduce(planes: dict, window_s: float, ring: list | None = None,
           sync_ring: float | None = None) -> dict:
    """The reduction. ``window_s`` is the traced window's length on the
    host's clock; the window starts at the sync marker (or at the first
    event seen where there is none). ``ring`` holds the program's spans
    and ``sync_ring`` the marker's ``ts`` on the ring's clock (the ring
    is bounded, so the marker itself may have left it).

    Where the device events end before three quarters of the window has
    passed, the profiler has dropped the rest (it keeps a bounded number
    of events; a busy cell fills it in seconds), and the window reduced
    is cut to what the trace holds: ``window_s`` is then that extent,
    ``host_window_s`` the whole, and busy and idle time are shares of a
    stretch that has all its events. (A device that does nothing at all
    in a window's last quarter reads the same; no cell here has one.)"""
    devices = {n: ls for n, ls in planes.items() if _DEVICE.match(n)}
    spans = host_spans_on_trace_clock(planes, ring or [], sync_ring)
    sync = next((s for n, ls in planes.items() if n.startswith("/host:")
                 for ln, evs in ls.items() if ln != "#events"
                 for ev, s, _ in evs if ev == SYNC), None)
    host_window_s = window_s
    ends = [s + d for lines in devices.values()
            for _, s, d in (lines.get(OPS_LINE) or lines.get(MODULES_LINE)
                            or [])]
    if ends and sync is not None:
        held = (max(ends) - sync) / 1e9
        if 0 < held < KEPT_SHARE * window_s:
            window_s = held
    busy = []
    ops_total: dict = {}
    programs: dict = {}
    gaps_total: dict = {}
    gaps: list = []
    for lines in devices.values():
        ops = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        secs, merged = union_seconds((s, s + d) for _, s, d in ops)
        busy.append(secs)
        for name, _, d in ops:
            ops_total[name] = ops_total.get(name, 0.0) + d / 1e9
        for name, _, d in lines.get(MODULES_LINE, []):
            name = _RUN_ID.sub("", name)
            programs[name] = programs.get(name, 0.0) + d / 1e9
        start = sync if sync is not None else (merged[0][0] if merged else 0)
        end = start + window_s * 1e9
        edge = start
        for s, e in merged + [(end, end)]:
            if s > edge and s <= end:
                gaps.append((edge, s))
            edge = max(edge, e)
    for gap in sorted(gaps, key=lambda g: g[0] - g[1])[:200]:
        name = name_gap(gap, spans)
        gaps_total.setdefault(name, []).append((gap[1] - gap[0]) / 1e9)
    n = max(1, len(devices))
    return {
        "devices": len(devices),
        "busy_s": sum(busy) / n,
        "window_s": window_s,
        "host_window_s": host_window_s,
        "programs": programs,
        "device_ops": _top(ops_total),
        # the longest single gap under each host name, longest first
        "idle_gaps": _top({k: max(v) for k, v in gaps_total.items()}),
        "planes": {name: lines.get("#events", {})
                   for name, lines in planes.items()},
    }


def reduce_file(path: str, window_s: float, ring: list | None = None,
                sync_ring: float | None = None) -> dict:
    return reduce(load(path), window_s, ring, sync_ring)


if __name__ == "__main__":
    loaded = load(sys.argv[1])
    spans = [(s, s + d) for ls in loaded.values() for ln, evs in ls.items()
             if ln != "#events" for _, s, d in evs]
    length = (max(e for _, e in spans) - min(s for s, _ in spans)) / 1e9 \
        if spans else 0.0
    print(json.dumps(reduce(loaded, length), indent=1))
