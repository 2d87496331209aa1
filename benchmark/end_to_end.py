"""The end-to-end metrics, from what a driver's window observed.

Every driver returns ``ops``: one entry per operation completed (a
whole backup, a whole restore, one stream) with ``bytes``, ``t_start``
and ``t_done`` (seconds from window start) and ``rc`` (0: it counts).
A function returns None where the window has nothing for it.
"""

from __future__ import annotations

import statistics

MiB = 1 << 20


def moved_bytes(obs: dict) -> int:
    return sum(op["bytes"] for op in obs["ops"] if op["rc"] == 0)


def moved_mibps(obs: dict):
    """User bytes of all operations completed, over the time from
    window start to the last completion."""
    done = [op["t_done"] for op in obs["ops"] if op["rc"] == 0]
    if not done:
        return None
    return moved_bytes(obs) / MiB / max(done)


def stored_ratio(obs: dict):
    """Bytes in the store after the operations, every key family, per
    user byte they moved."""
    if "stored_bytes" not in obs or not moved_bytes(obs):
        return None
    return obs["stored_bytes"] / moved_bytes(obs)


def op_p95_ms(obs: dict):
    """95th percentile of one operation's latency (needs 20 samples
    to have one beyond it)."""
    lat = obs.get("latencies_ms")
    if not lat or len(lat) < 20:
        return None
    return statistics.quantiles(lat, n=20, method="inclusive")[-1]


METRICS = {"moved_mibps": moved_mibps, "stored_ratio": stored_ratio,
           "op_p95_ms": op_p95_ms}
