"""Sync-protocol planner: cost model, stats book, and mover wiring.

Table-driven decision boundaries for engine/protoplan.decide, EWMA
behavior and hostile-input guards for engine/syncstats.SyncStatsBook,
the measured-link feed from resilience.ResilientStore, and the
movers.common.plan_protocol front door.
"""

import math

import pytest

from volsync_tpu import envflags, resilience
from volsync_tpu.engine import protoplan, syncstats
from volsync_tpu.engine.deltasync import (
    SIG_BYTES_PER_BLOCK,
    SIG_HEADER_BYTES,
    signature_geometry,
)
from volsync_tpu.metrics import GLOBAL as METRICS


@pytest.fixture(autouse=True)
def _clean_state():
    syncstats.reset_books()
    resilience.reset_link_totals()
    yield
    syncstats.reset_books()
    resilience.reset_link_totals()


def _stats(change=1.0, dedup=0.0, bw=100e6, lat=1e-3,
           delta_n=1, dedup_n=1, link_n=1):
    return syncstats.SyncStats(
        change_rate=change, dedup_hit_ratio=dedup, bandwidth_bps=bw,
        latency_s=lat, delta_samples=delta_n, dedup_samples=dedup_n,
        link_samples=link_n)


# -- cost model decision table -----------------------------------------------


DECISION_TABLE = [
    # zero history: pessimistic cold priors price both fancy protocols
    # above a straight copy
    dict(size=1 << 20, stats=_stats(delta_n=0, dedup_n=0, link_n=0),
         want=protoplan.FULL_COPY),
    # high dedup ratio: most bytes never ship
    dict(size=64 << 20, stats=_stats(change=0.9, dedup=0.95),
         want=protoplan.CDC_DEDUP),
    # low churn on a good link: signature round trip + few literals win
    dict(size=64 << 20, stats=_stats(change=0.01, dedup=0.0),
         want=protoplan.DELTA),
    # everything changed: delta's sig overhead makes it strictly worse
    # than a copy, and no dedup means cdc pays metadata for nothing
    dict(size=8 << 20, stats=_stats(change=1.0, dedup=0.0),
         want=protoplan.FULL_COPY),
    # tiny file on a slow, laggy link: extra round trips dominate
    dict(size=512, stats=_stats(change=0.01, dedup=0.9, bw=1e6, lat=0.5),
         want=protoplan.FULL_COPY),
]


@pytest.mark.parametrize("case", DECISION_TABLE)
def test_decision_table(case):
    d = protoplan.decide(case["size"], case["stats"])
    assert d.protocol == case["want"], d.scores
    assert d.reason == protoplan.REASON_COST
    # every candidate was priced and is visible in the decision
    assert set(d.scores) == set(protoplan.PROTOCOLS)
    assert len(d.losing()) == len(protoplan.PROTOCOLS) - 1


def test_scores_are_finite_and_ordered():
    scores = protoplan.score_protocols(1 << 20, _stats())
    for s in scores.values():
        assert math.isfinite(s.cost_s) and s.cost_s >= 0
        assert math.isfinite(s.wire_bytes) and s.wire_bytes >= 0
    chosen = protoplan.decide(1 << 20, _stats()).protocol
    assert scores[chosen].cost_s == min(s.cost_s for s in scores.values())


def test_delta_wire_uses_signature_geometry():
    size = 10 << 20
    geo = signature_geometry(size)
    s = protoplan.score_protocols(size, _stats(change=0.0))[protoplan.DELTA]
    # zero churn: the wire cost is exactly the signature + op framing
    assert s.wire_bytes == pytest.approx(
        geo.sig_bytes + protoplan.DELTA_OP_OVERHEAD_PER_BLOCK * geo.n_blocks)


def test_signature_geometry_seam():
    geo = signature_geometry(0)
    assert geo.n_blocks == 0 and geo.sig_bytes == SIG_HEADER_BYTES
    geo = signature_geometry(1_000_000)
    assert geo.n_blocks == -(-1_000_000 // geo.block_len)
    assert geo.sig_bytes == (SIG_HEADER_BYTES
                             + geo.n_blocks * SIG_BYTES_PER_BLOCK)
    # explicit block length is honored
    geo = signature_geometry(8192, 1024)
    assert (geo.block_len, geo.n_blocks) == (1024, 8)


# -- hostile inputs ----------------------------------------------------------


@pytest.mark.parametrize("bw", [0.0, -1.0, float("nan"), float("inf")])
def test_no_division_by_hostile_bandwidth(bw):
    d = protoplan.decide(1 << 20, _stats(bw=bw))
    for s in d.scores.values():
        assert math.isfinite(s.cost_s)
    # degraded pricing still prefers fewer wire bytes
    assert d.protocol in protoplan.PROTOCOLS


def test_nan_rates_price_pessimistically():
    st = _stats(change=float("nan"), dedup=float("nan"))
    scores = protoplan.score_protocols(1 << 20, st)
    full = scores[protoplan.FULL_COPY]
    # NaN change reads as 1.0, NaN dedup as 0.0 -> both lose to FULL
    assert scores[protoplan.DELTA].wire_bytes > full.wire_bytes
    assert scores[protoplan.CDC_DEDUP].wire_bytes > full.wire_bytes


def test_zero_and_negative_size():
    for size in (0, -5):
        d = protoplan.decide(size, _stats())
        assert d.protocol in protoplan.PROTOCOLS
        for s in d.scores.values():
            assert math.isfinite(s.cost_s)


# -- decide() modifiers ------------------------------------------------------


def test_override_env_flag(monkeypatch):
    monkeypatch.setenv("VOLSYNC_SYNC_PROTO", "cdc")
    d = protoplan.decide(1 << 20, _stats(delta_n=0, dedup_n=0))
    assert (d.protocol, d.reason) == (protoplan.CDC_DEDUP,
                                      protoplan.REASON_OVERRIDE)
    # an override naming a protocol outside the candidate set is ignored
    monkeypatch.setenv("VOLSYNC_SYNC_PROTO", "delta")
    d = protoplan.decide(1 << 20, _stats(delta_n=0, dedup_n=0),
                         candidates=(protoplan.FULL_COPY,
                                     protoplan.CDC_DEDUP))
    assert d.protocol != protoplan.DELTA
    # unknown value degrades to auto
    monkeypatch.setenv("VOLSYNC_SYNC_PROTO", "warp")
    assert envflags.sync_protocol() == "auto"


def test_probe_seeds_cold_books():
    cold = _stats(delta_n=0, dedup_n=0, link_n=0)
    d = protoplan.decide(1 << 20, cold, allow_probe=True)
    assert (d.protocol, d.reason) == (protoplan.DELTA,
                                      protoplan.REASON_PROBE)
    # delta already sampled, dedup not: probe flips a FULL verdict to CDC
    st = _stats(change=1.0, dedup=0.0, delta_n=3, dedup_n=0)
    d = protoplan.decide(1 << 20, st, allow_probe=True)
    assert (d.protocol, d.reason) == (protoplan.CDC_DEDUP,
                                      protoplan.REASON_PROBE)
    # warm book: no probe, the model decides
    d = protoplan.decide(1 << 20, _stats(), allow_probe=True)
    assert d.reason == protoplan.REASON_COST


def test_no_basis_drops_delta():
    st = _stats(change=0.01)  # would pick DELTA with a basis
    d = protoplan.decide(64 << 20, st, basis_exists=False)
    assert d.protocol != protoplan.DELTA
    assert protoplan.DELTA not in d.scores
    assert d.reason == protoplan.REASON_NO_BASIS


def test_size_cap_demotes_full():
    cold = _stats(delta_n=0, dedup_n=0)
    d = protoplan.decide(64 << 20, cold, full_cap=8 << 20)
    assert d.protocol != protoplan.FULL_COPY
    assert d.reason == protoplan.REASON_SIZE_CAP
    # under the cap FULL stands
    d = protoplan.decide(1 << 20, cold, full_cap=8 << 20)
    assert d.protocol == protoplan.FULL_COPY


def test_decide_bumps_selected_metric():
    before = METRICS.svc_protocol_selected.labels(
        protocol="full", reason="cost")._value.get()
    protoplan.decide(1 << 20, _stats(delta_n=0, dedup_n=0))
    after = METRICS.svc_protocol_selected.labels(
        protocol="full", reason="cost")._value.get()
    assert after == before + 1


# -- SyncStatsBook -----------------------------------------------------------


def test_ewma_update_and_snapshot():
    b = syncstats.SyncStatsBook(alpha=0.5)
    b.observe_delta(100, 1000)   # 0.1
    assert b.snapshot().change_rate == pytest.approx(0.1)
    b.observe_delta(300, 1000)   # 0.5*0.3 + 0.5*0.1 = 0.2
    s = b.snapshot()
    assert s.change_rate == pytest.approx(0.2)
    assert s.delta_samples == 2
    b.observe_dedup(9, 10)
    b.observe_link(10 << 20, 0.1)
    b.observe_rtt(0.02)
    s = b.snapshot()
    assert s.dedup_hit_ratio == pytest.approx(0.9)
    assert s.bandwidth_bps == pytest.approx((10 << 20) / 0.1)
    assert s.latency_s == pytest.approx(0.02)


def test_cold_snapshot_uses_priors():
    s = syncstats.SyncStatsBook().snapshot()
    assert s.change_rate == syncstats.COLD_CHANGE_RATE
    assert s.dedup_hit_ratio == syncstats.COLD_DEDUP_RATIO
    assert s.bandwidth_bps == syncstats.COLD_BANDWIDTH
    assert s.latency_s == syncstats.COLD_LATENCY_S
    assert (s.delta_samples, s.dedup_samples, s.link_samples) == (0, 0, 0)


@pytest.mark.parametrize("lit,total", [
    (float("nan"), 100), (10, float("nan")), (10, 0), (10, -1),
    (-5, 100), (10, float("inf")),
])
def test_hostile_observations_dropped(lit, total):
    b = syncstats.SyncStatsBook()
    b.observe_delta(lit, total)
    b.observe_dedup(lit, total)
    b.observe_link(lit, total)
    s = b.snapshot()
    assert s.delta_samples == 0 and s.dedup_samples == 0
    assert s.link_samples == 0
    # and the cold snapshot still prices without dividing by zero
    d = protoplan.decide(1 << 20, s)
    assert all(math.isfinite(x.cost_s) for x in d.scores.values())


def test_zero_duration_timing_never_divides():
    b = syncstats.SyncStatsBook()
    b.observe_link(1 << 20, 0.0)
    b.observe_rtt(0.0)
    assert b.snapshot().link_samples == 0


def test_decay_moves_toward_priors():
    b = syncstats.SyncStatsBook(alpha=1.0)
    b.observe_delta(0, 100)    # change 0.0
    b.observe_dedup(100, 100)  # dedup 1.0
    b.decay(0.5)
    s = b.snapshot()
    assert s.change_rate == pytest.approx(0.5)   # toward 1.0
    assert s.dedup_hit_ratio == pytest.approx(0.5)  # toward 0.0
    assert s.delta_samples == 0  # 1 * (1 - 0.5) -> 0
    b.decay(1.0)
    s = b.snapshot()
    assert s.change_rate == pytest.approx(syncstats.COLD_CHANGE_RATE)
    assert s.dedup_hit_ratio == pytest.approx(syncstats.COLD_DEDUP_RATIO)


def test_book_registry_is_per_consumer():
    a = syncstats.book_for("rsync")
    assert syncstats.book_for("rsync") is a
    assert syncstats.book_for("restic") is not a
    a.observe_delta(1, 100)
    assert syncstats.book_for("restic").snapshot().delta_samples == 0


# -- live feeds --------------------------------------------------------------


class _MemStore:
    def __init__(self):
        self.d = {}

    def put(self, key, data):
        self.d[key] = data

    def get(self, key):
        return self.d[key]

    def delete(self, key):
        self.d.pop(key, None)


def test_resilient_store_feeds_link_totals():
    store = resilience.ResilientStore(
        _MemStore(), policy=resilience.RetryPolicy(max_attempts=1))
    payload = b"x" * (1 << 20)
    store.put("big", payload)
    store.get("big")
    t = resilience.link_totals()
    assert t["large_ops"] == 2
    assert t["large_bytes"] == 2 * len(payload)
    assert t["large_seconds"] > 0
    store.put("small", b"tiny")
    assert resilience.link_totals()["small_ops"] == 1

    b = syncstats.SyncStatsBook()
    b.pull_link_timings()
    s = b.snapshot()
    assert s.link_samples >= 1
    assert s.bandwidth_bps > 0
    # second pull with no traffic observes nothing new
    n = s.link_samples
    b.pull_link_timings()
    assert b.snapshot().link_samples == n


def test_pull_index_metrics_diffs_cursor():
    b = syncstats.SyncStatsBook(alpha=1.0)
    b.pull_index_metrics(METRICS)  # baseline cursor
    before = b.snapshot().dedup_samples
    METRICS.index_queries.labels(result="hit").inc(30)
    METRICS.index_queries.labels(result="miss").inc(10)
    b.pull_index_metrics(METRICS)
    s = b.snapshot()
    assert s.dedup_samples == before + 1
    assert s.dedup_hit_ratio == pytest.approx(0.75)
    # no new queries -> nothing observed
    b.pull_index_metrics(METRICS)
    assert b.snapshot().dedup_samples == before + 1


# -- mover front door --------------------------------------------------------


def test_plan_protocol_probes_then_settles():
    from volsync_tpu.movers import common

    d = common.plan_protocol("rsync", 1 << 20,
                             candidates=("full", "delta"))
    assert (d.protocol, d.reason) == ("delta", protoplan.REASON_PROBE)
    book = syncstats.book_for("rsync")
    for _ in range(3):
        book.observe_delta(99, 100)  # churn ~1.0: delta is pointless
    book.observe_link(100 << 20, 1.0)
    d = common.plan_protocol("rsync", 1 << 20,
                             candidates=("full", "delta"))
    assert (d.protocol, d.reason) == ("full", protoplan.REASON_COST)


def test_normalize_protocol():
    from volsync_tpu.movers.base import normalize_protocol

    assert normalize_protocol("Delta") == "delta"
    assert normalize_protocol(" cdc ") == "cdc"
    assert normalize_protocol("warp") == "auto"
    assert normalize_protocol(None, default="cdc") == "cdc"


# -- env knobs ---------------------------------------------------------------


def test_env_knobs(monkeypatch):
    monkeypatch.setenv("VOLSYNC_PLAN_EWMA", "2.5")
    assert envflags.plan_ewma_alpha() == 1.0  # clamped
    monkeypatch.setenv("VOLSYNC_PLAN_EWMA", "junk")
    assert envflags.plan_ewma_alpha() == pytest.approx(0.3)
    monkeypatch.setenv("VOLSYNC_PLAN_FULL_CAP", "1")
    assert envflags.plan_full_blob_cap() == 4096
