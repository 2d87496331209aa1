"""Prometheus metrics, mirroring controllers/metrics.go:38-72.

Namespace ``volsync``: ``missed_intervals_total`` (counter),
``volume_out_of_sync`` (gauge), ``sync_duration_seconds`` (histogram here —
prometheus_client has no server-side quantile summary; the reference's
.5/.9/.99 summary quantiles become histogram buckets sized for sync
durations), labeled obj_name/obj_namespace/role/method. A fourth,
TPU-specific family ``data_throughput_bytes_per_second`` tracks the
device-pipeline rate the reference could never observe.
"""

from __future__ import annotations

import dataclasses

from prometheus_client import (
    CollectorRegistry,
    Counter,
    Gauge,
    Histogram,
    generate_latest,
)

LABELS = ["obj_name", "obj_namespace", "role", "method"]

_BUCKETS = (0.1, 0.5, 1, 5, 15, 30, 60, 120, 300, 600, 1800, 3600, float("inf"))


class Metrics:
    def __init__(self, registry: CollectorRegistry | None = None):
        self.registry = registry or CollectorRegistry()
        self.missed_intervals = Counter(
            "volsync_missed_intervals_total",
            "The number of times a synchronization failed to complete "
            "before the next scheduled start",
            LABELS, registry=self.registry,
        )
        self.out_of_sync = Gauge(
            "volsync_volume_out_of_sync",
            "Set to 1 if the volume is not properly synchronized",
            LABELS, registry=self.registry,
        )
        self.sync_durations = Histogram(
            "volsync_sync_duration_seconds",
            "Duration of the synchronization interval in seconds",
            LABELS, registry=self.registry, buckets=_BUCKETS,
        )
        self.throughput = Gauge(
            "volsync_data_throughput_bytes_per_second",
            "Device data-plane throughput of the last completed transfer",
            LABELS, registry=self.registry,
        )
        # Backup-pipeline occupancy (repo/repository.py, engine/chunker.py):
        # per-stage queue depths, updated at every enqueue/dequeue. Stages:
        # "read" (segments prefetched ahead of the device), "seal" (blobs
        # queued for zstd+AES), "upload" (sealed packs in flight to the
        # object store).
        self.pipeline_depth = Gauge(
            "volsync_pipeline_queue_depth",
            "Current occupancy of each backup-pipeline stage queue",
            ["stage"], registry=self.registry,
        )
        # Resilience layer (resilience.py): per-site attempt outcomes
        # ("ok" — the attempt succeeded, "retried" — failed retryable
        # with attempts left, "exhausted" — failed retryable on the
        # final attempt, "fatal" — classified non-retryable), and per-backend
        # circuit-breaker state (0 closed / 1 open / 2 half-open) plus
        # state-transition counts.
        self.retry_attempts = Counter(
            "volsync_retry_attempts_total",
            "Resilient-call attempts by site and outcome",
            ["site", "outcome"], registry=self.registry,
        )
        self.breaker_state = Gauge(
            "volsync_breaker_state",
            "Circuit-breaker state per backend "
            "(0=closed, 1=open, 2=half-open)",
            ["backend"], registry=self.registry,
        )
        self.breaker_transitions = Counter(
            "volsync_breaker_transitions_total",
            "Circuit-breaker state transitions per backend",
            ["backend", "to"], registry=self.registry,
        )
        # Metadata plane (repo/shardedindex.py): dedup keys resolved by
        # the batched vectorized path, by result, plus the blocked-bloom
        # prefilter's decisions — "skip" (definitely absent, probe
        # avoided), "pass" (filter said maybe, probe found it),
        # "false_positive" (filter said maybe, probe missed) — and the
        # worst per-shard filter fill fraction (rebuilt on vacuum; near
        # 1.0 means every query degrades to a real probe). The scalar
        # per-key path is deliberately unmetered: a counter bump would
        # roughly double its cost.
        self.index_queries = Counter(
            "volsync_index_queries_total",
            "Batched dedup-index keys queried, by result",
            ["result"], registry=self.registry,
        )
        self.index_prefilter = Counter(
            "volsync_index_prefilter_total",
            "Prefilter decisions for batched dedup-index queries",
            ["outcome"], registry=self.registry,
        )
        self.index_prefilter_saturation = Gauge(
            "volsync_index_prefilter_saturation",
            "Max per-shard prefilter set-bit fraction (0..1)",
            registry=self.registry,
        )
        # Multi-tenant service plane (service/admission.py,
        # service/scheduler.py): per-tenant admission outcomes — every
        # ChunkHash stream is either admitted or shed AT ADMISSION with
        # a reason ("breaker_open", "global_streams", "tenant_streams",
        # "overload", "draining") — plus the scheduler's per-tenant
        # backlog and the queue-wait of the most recently dispatched
        # segment. Tenant label values come from client metadata; the
        # registry caps and sanitizes them so cardinality stays bounded
        # by the set of names clients actually present.
        self.svc_admitted = Counter(
            "volsync_svc_admitted_total",
            "ChunkHash streams admitted, by tenant",
            ["tenant"], registry=self.registry,
        )
        self.svc_shed = Counter(
            "volsync_svc_shed_total",
            "ChunkHash streams shed at admission, by tenant and reason",
            ["tenant", "reason"], registry=self.registry,
        )
        self.svc_active_streams = Gauge(
            "volsync_svc_active_streams",
            "Currently admitted ChunkHash streams, by tenant",
            ["tenant"], registry=self.registry,
        )
        self.svc_queue_depth = Gauge(
            "volsync_svc_queue_depth",
            "Segments queued in the service scheduler, by tenant",
            ["tenant"], registry=self.registry,
        )
        self.svc_sched_latency = Gauge(
            "volsync_svc_sched_latency_seconds",
            "Queue wait of the last segment the scheduler dispatched, "
            "by tenant",
            ["tenant"], registry=self.registry,
        )
        # Deadline-class scheduling (service/scheduler.py): segments
        # whose queue-wait deadline passed before dispatch, shed with
        # DeadlineExceeded instead of spending device work. A nonzero
        # rate on an interactive class means the fleet needs headroom,
        # not that the scheduler misbehaved — background classes
        # (deadline None) never appear here.
        self.svc_deadline_exceeded = Counter(
            "volsync_svc_deadline_exceeded_total",
            "Segments shed because their queue-wait deadline passed "
            "before dispatch, by tenant",
            ["tenant"], registry=self.registry,
        )
        # Per-stream latency attribution (obs/tracing.py): seconds spent
        # per pipeline stage, summed over spans that finished under a
        # tenant-tagged TraceContext — where an admitted stream's time
        # actually went (svc.admit / svc.queue_wait / svc.batch / ...).
        # Stage values are lint-bounded literals (VL301), tenant values
        # are registry-sanitized, so cardinality stays bounded.
        self.svc_stage_seconds = Counter(
            "volsync_svc_stage_seconds",
            "Seconds spent per stage by tenant-attributed spans",
            ["tenant", "stage"], registry=self.registry,
        )
        # Adaptive sync-protocol planner (engine/protoplan.py): which
        # protocol each plan.decide chose and why — "cost" (the model
        # won on price), "override" (VOLSYNC_SYNC_PROTO pinned it),
        # "probe" (forced exploration to seed an empty stat book),
        # "no_basis" (destination has no prior copy, delta impossible),
        # "size_cap" (file too large for a whole-file blob).
        # Label values are closed literal sets, so cardinality is fixed.
        self.svc_protocol_selected = Counter(
            "volsync_svc_protocol_selected_total",
            "Sync-protocol planner decisions, by protocol and reason",
            ["protocol", "reason"], registry=self.registry,
        )
        # Repository store locking (repo/repository.py): age of the
        # newest conflicting lock a waiter observed — a stale-holder
        # stall shows as this gauge climbing toward
        # VOLSYNC_LOCK_STALE_S instead of a silent 30-minute wait.
        self.repo_lock_age = Gauge(
            "volsync_repo_lock_age_seconds",
            "Age of the most recent conflicting repository lock "
            "observed while acquiring",
            registry=self.registry,
        )
        # Multi-writer repository protocol (repo/repository.py): the
        # writer's current fencing generation, packs parked in
        # pending-delete/ manifests awaiting their grace deadline,
        # stale-lock takeovers won (each bumps the generation and
        # fences the victim writer), and publishes refused because this
        # writer had been fenced by a peer's takeover.
        self.repo_writer_generation = Gauge(
            "volsync_repo_writer_generation",
            "Current repository fencing generation of this writer",
            registry=self.registry,
        )
        self.repo_pending_delete_packs = Gauge(
            "volsync_repo_pending_delete_packs",
            "Packs marked pending-delete and awaiting their sweep "
            "grace deadline",
            registry=self.registry,
        )
        self.repo_takeovers_total = Counter(
            "volsync_repo_takeovers_total",
            "Stale repository locks atomically taken over (victim "
            "writer fenced, generation bumped)",
            registry=self.registry,
        )
        self.repo_fenced_publishes_total = Counter(
            "volsync_repo_fenced_publishes_total",
            "Index/snapshot publishes refused because this writer was "
            "fenced by a stale-lock takeover",
            registry=self.registry,
        )
        # Fleet replica plane (service/fleet.py): per-replica advertised
        # headroom from the last heartbeat stamp the router read, where
        # the router sent each admitted stream, and how many streams
        # completed on a sibling after their first-choice replica shed
        # or died mid-stream. Replica label values are the group's own
        # replica ids (bounded by fleet size, never client-supplied).
        self.fleet_replica_headroom = Gauge(
            "volsync_fleet_replica_headroom",
            "Advertised admission headroom per replica, from its last "
            "heartbeat stamp",
            ["replica"], registry=self.registry,
        )
        self.fleet_routed_total = Counter(
            "volsync_fleet_routed_total",
            "Streams the fleet router sent to each replica",
            ["replica"], registry=self.registry,
        )
        self.fleet_failovers_total = Counter(
            "volsync_fleet_failovers_total",
            "Streams that completed on a sibling after a shed or a "
            "replica death",
            registry=self.registry,
        )
        # Restore data plane (engine/restorepipe.py, repo/packcache.py):
        # cache decisions and moved bytes. A "hit" is any request
        # served without its own store round trip — an LRU hit or a
        # follower sharing a single-flight leader's in-flight fetch;
        # the storm drill's GET accounting rides these.
        self.restore_cache_hits = Counter(
            "volsync_restore_cache_hits_total",
            "Pack requests served from the restore PackCache (LRU hit "
            "or shared single-flight fetch)",
            registry=self.registry,
        )
        self.restore_cache_misses = Counter(
            "volsync_restore_cache_misses_total",
            "Pack requests that paid a store GET (single-flight fetch "
            "leaders)",
            registry=self.registry,
        )
        self.restore_cache_evictions = Counter(
            "volsync_restore_cache_evictions_total",
            "Pack bodies evicted from the restore PackCache LRU to "
            "stay under the byte budget",
            registry=self.registry,
        )
        self.restore_bytes = Counter(
            "volsync_restore_bytes_total",
            "Plaintext bytes written to restore destinations by the "
            "pipelined restore data plane",
            registry=self.registry,
        )
        # Continuous GC service (service/gc.py): prune cycles by outcome
        # — "ok" (cycle ran, repo swept), "contended" (another writer
        # held a conflicting lock; normal under load), "fenced" (this
        # GC writer lost a takeover and reopened), "error" (anything
        # else; the service backs off and retries).
        self.gc_cycles = Counter(
            "volsync_gc_cycles_total",
            "Continuous-GC prune cycles, by outcome",
            ["outcome"], registry=self.registry,
        )
        # Integrity scrub (repo/scrub.py) + restore read-repair: packs
        # examined by outcome — "clean" (device verify passed), "healed"
        # (quarantined, then mirror heal + re-verify succeeded; restore
        # read-repair heals count here too), "quarantined" (corruption
        # detected, quarantine manifest written — every healed/unhealable
        # pack passes through this), "unhealable" (no healthy mirror;
        # the quarantine manifest stays and record_trigger escalates).
        self.scrub_packs = Counter(
            "volsync_scrub_packs_total",
            "Packs examined by the integrity scrub, by outcome",
            ["outcome"], registry=self.registry,
        )
        self.scrub_bytes = Counter(
            "volsync_scrub_bytes_total",
            "Pack bytes fetched and device-verified by the integrity "
            "scrub",
            registry=self.registry,
        )
        # Online repack (repo/repack.py): cycles by outcome — "ok"
        # (packs restriped and/or retired stripes swept), "clean"
        # (nothing fragmented enough), "contended", "fenced", "error"
        # (the ContinuousGC ladder) — plus packs rewritten into
        # erasure-coded stripes.
        self.repack_cycles = Counter(
            "volsync_repack_cycles_total",
            "Online-repack cycles, by outcome",
            ["outcome"], registry=self.registry,
        )
        self.repack_packs = Counter(
            "volsync_repack_packs_total",
            "Packs rewritten into erasure-coded stripes by the online "
            "repacker",
            registry=self.registry,
        )
        # Copy ledger (obs/copyledger.py): host bytes memcpy'd at the
        # SANCTIONED copy sites of the zero-copy data plane — every
        # remaining staging copy on the backup/restore hot paths is
        # wrapped in record_copy(site, n), so copy_ratio (host bytes
        # copied / payload bytes moved) is measurable and regressions
        # show up as new sites or growing counts. Site values are the
        # fixed dotted names listed in docs/performance.md.
        self.copy_bytes = Counter(
            "volsync_copy_bytes_total",
            "Host bytes copied at sanctioned data-plane copy sites",
            ["site"], registry=self.registry,
        )

    def for_object(self, name: str, namespace: str, role: str,
                   method: str) -> "BoundMetrics":
        labels = dict(obj_name=name, obj_namespace=namespace, role=role,
                      method=method)
        return BoundMetrics(
            missed_intervals=self.missed_intervals.labels(**labels),
            out_of_sync=self.out_of_sync.labels(**labels),
            sync_durations=self.sync_durations.labels(**labels),
            throughput=self.throughput.labels(**labels),
        )

    def expose(self) -> bytes:
        """Text exposition (the reference serves this on :8080/metrics)."""
        return generate_latest(self.registry)


@dataclasses.dataclass
class BoundMetrics:
    """Per-CR labeled children (what the state machine drives)."""

    missed_intervals: object
    out_of_sync: object
    sync_durations: object
    throughput: object


class MetricsServer:
    """HTTP exposition + probes, the analogue of the reference manager's
    metrics listener on :8080 and healthz/readyz probes on :8081
    (controllers/metrics.go:82-85, main.go:140-153). One server carries
    all the endpoints — /metrics, /healthz, /readyz, plus /debug/trace
    serving the obs flight recorder as Chrome-trace JSON; ``port=0``
    binds an ephemeral port (tests)."""

    def __init__(self, metrics: "Metrics", host: str = "127.0.0.1",
                 port: int = 8080,
                 ready_check=None):
        import http.server
        import threading

        outer = self

        class Handler(http.server.BaseHTTPRequestHandler):
            def do_GET(self):  # noqa: N802 (http.server API)
                if self.path == "/metrics":
                    body = outer.metrics.expose()
                    ctype = "text/plain; version=0.0.4"
                    code = 200
                elif self.path == "/healthz":
                    body, ctype, code = b"ok", "text/plain", 200
                elif self.path == "/readyz":
                    ok = outer.ready_check is None or outer.ready_check()
                    body = b"ok" if ok else b"not ready"
                    ctype, code = "text/plain", (200 if ok else 503)
                elif self.path == "/debug/trace":
                    # Imported lazily: obs depends on this module, so a
                    # top-level import here would be a cycle.
                    import json

                    from volsync_tpu import obs
                    body = json.dumps(obs.chrome_trace()).encode("utf-8")
                    ctype, code = "application/json", 200
                else:
                    body, ctype, code = b"not found", "text/plain", 404
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *args):  # quiet
                pass

        self.metrics = metrics
        self.ready_check = ready_check
        self._httpd = http.server.ThreadingHTTPServer((host, port), Handler)
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True,
            name="metrics-server")

    def start(self) -> "MetricsServer":
        self._thread.start()
        return self

    def stop(self):
        self._httpd.shutdown()
        self._httpd.server_close()
        self._thread.join(timeout=5)

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()


GLOBAL = Metrics()
