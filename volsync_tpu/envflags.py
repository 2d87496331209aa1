"""One home for env-knob parsing.

Every operational toggle (VOLSYNC_DEVICE_VERIFY, VOLSYNC_SPARSE,
VOLSYNC_BATCH_SEGMENTS, ...) parses through here so the falsy-token
set cannot drift between copies — "off" disabling one knob but
enabling another is exactly the bug class this prevents. The backup
pipeline's depth/worker knobs (VOLSYNC_TPU_PIPELINE and friends) live
here too, as the single catalogue of operator-facing tunables.
"""

from __future__ import annotations

import os
from typing import Optional

_FALSY = ("", "0", "false", "no", "off")


def env_bool(name: str, default: bool = False) -> bool:
    """True/False from the environment; unset -> ``default``."""
    raw = os.environ.get(name)
    if raw is None:
        return default
    return raw.strip().lower() not in _FALSY


def env_int(name: str, default: int, minimum: int = 0) -> int:
    """Integer knob; unset/unparsable -> ``default``, floored at
    ``minimum`` (a malformed operator value degrades to the default
    instead of crashing the mover mid-sync)."""
    raw = os.environ.get(name)
    if raw is None:
        return default
    try:
        return max(minimum, int(raw.strip()))
    except ValueError:
        return default


def env_float(name: str, default: float, minimum: float = 0.0) -> float:
    """Float knob with the same degrade-to-default contract as
    env_int."""
    raw = os.environ.get(name)
    if raw is None:
        return default
    try:
        return max(minimum, float(raw.strip()))
    except ValueError:
        return default


def env_str(name: str, default: Optional[str] = None) -> Optional[str]:
    """Raw string knob; empty string counts as unset (an operator
    clearing a knob with ``VAR=`` means "off", never "the empty
    path")."""
    raw = os.environ.get(name)
    if not raw:
        return default
    return raw


# -- backup data-plane pipeline knobs (repo/repository.py, engine/chunker.py)

def pipeline_enabled() -> bool:
    """Master switch for the pipelined backup data plane.
    ``VOLSYNC_TPU_PIPELINE=0`` falls back to the fully serial path."""
    return env_bool("VOLSYNC_TPU_PIPELINE", True)


def seal_workers() -> int:
    """Worker threads for async pack sealing (zstd+AES are pure CPU and
    release the GIL inside zstd)."""
    return env_int("VOLSYNC_TPU_SEAL_WORKERS", 2, minimum=1)


def seal_queue_limit() -> int:
    """Max blobs queued for sealing per repository before add_blob
    blocks — the backpressure bound on raw bytes held by the seal
    stage."""
    return env_int("VOLSYNC_TPU_SEAL_QUEUE", 16, minimum=1)


def upload_window() -> int:
    """Max sealed packs in flight to the object store per repository."""
    return env_int("VOLSYNC_TPU_UPLOAD_WINDOW", 4, minimum=1)


def upload_retries() -> int:
    """Retries (with exponential backoff) per failed pack upload before
    the error surfaces on the caller."""
    return env_int("VOLSYNC_TPU_UPLOAD_RETRIES", 2, minimum=0)


def readahead_segments() -> int:
    """Segments prefetched ahead of the device stage by stream_chunks'
    read-ahead thread; 0 disables the thread (inline reads)."""
    if not pipeline_enabled():
        return 0
    return env_int("VOLSYNC_TPU_READAHEAD", 2, minimum=0)


# -- cross-stream segment microbatching knobs (ops/batcher.py) -----------

def batch_segments_override() -> Optional[bool]:
    """VOLSYNC_BATCH_SEGMENTS tri-state: None when unset (callers fall
    back to the backend-aware default), else the forced bool."""
    if os.environ.get("VOLSYNC_BATCH_SEGMENTS") is None:
        return None
    return env_bool("VOLSYNC_BATCH_SEGMENTS")


def batch_max() -> int:
    """Max segments coalesced into one batched device dispatch."""
    return env_int("VOLSYNC_BATCH_MAX", 16, minimum=1)


def batch_window_ms() -> float:
    """The longest (ms) the first segment of a batch waits for
    companions: for a producer that is registered and absent, or for
    anybody on a batcher nobody registered with. A batch that holds a
    segment of every registered producer does not wait
    (ops/batcher.py)."""
    return env_float("VOLSYNC_BATCH_WINDOW_MS", 2.0, minimum=0.0)


def batch_pipeline_depth() -> int:
    """Batched dispatches in flight per microbatcher (ops/batcher.py
    and the gRPC server's per-process batcher share this knob)."""
    return env_int("VOLSYNC_BATCH_PIPELINE", 2, minimum=1)


# -- device kernel knobs (ops/) ------------------------------------------

def root_unroll() -> int:
    """SHA-256 root-loop unroll factor (ops/segment.py). Read at TRACE
    time and not part of any jit cache key — profiling runs must set it
    before the first compile of a shape. Clamped >= 1: U=0 would make
    the loop body a no-op that never advances n (device hang)."""
    return env_int("VOLSYNC_ROOT_UNROLL", 4, minimum=1)


# -- engine worker knobs (engine/restore.py) ----------------------------

def backup_workers() -> int:
    """Files one TreeBackup streams through the device at once: one
    (engine/backup.py hashes file after file; PERF.md section 6, PR 27).
    A function and not a flag: benchmark/warm.py plans the lanes of the
    batched segment programs from it."""
    return 1


def restore_workers() -> int:
    """Concurrent per-file restore workers for TreeRestore."""
    return env_int("VOLSYNC_RESTORE_WORKERS", 4, minimum=1)


# -- restore data plane (engine/restorepipe.py, repo/packcache.py) -------

def restore_pipeline_enabled() -> bool:
    """Master switch for the pipelined restore data plane
    (pack-granular fetches + device-batched verify).
    ``VOLSYNC_RESTORE_PIPELINE=0`` falls back to the serial per-blob
    path — the byte-identity golden oracle."""
    return env_bool("VOLSYNC_RESTORE_PIPELINE", True)


def restore_cache_mb() -> int:
    """VOLSYNC_RESTORE_CACHE_MB: byte budget (MiB) of the shared
    PackCache LRU in front of the object store. Concurrent restores
    sharing one cache fetch each pack once (single-flight) and evict
    oldest-first past this budget."""
    return env_int("VOLSYNC_RESTORE_CACHE_MB", 256, minimum=1)


def restore_fetchers() -> int:
    """VOLSYNC_RESTORE_FETCHERS: worker threads in the restore
    pipeline's async pack-fetch pool (store GETs overlap decode,
    device verify, and file writes)."""
    return env_int("VOLSYNC_RESTORE_FETCHERS", 4, minimum=1)


def restore_fetch_window() -> int:
    """VOLSYNC_RESTORE_FETCH_WINDOW: max pack fetches submitted ahead
    of the consuming verify/write stage — the backpressure bound on
    fetched-but-unwritten pack bytes (window x PACK_TARGET)."""
    return env_int("VOLSYNC_RESTORE_FETCH_WINDOW", 8, minimum=1)


# -- metadata plane (repo/shardedindex.py) -------------------------------

def index_shards() -> int:
    """Shard count for the repository blob index (rounded up to a power
    of two by the index). Each shard has its own lock, so concurrent
    writers contend on ~1/S of the keyspace; 1 degenerates to the
    single-lock layout."""
    return env_int("VOLSYNC_INDEX_SHARDS", 16, minimum=1)


def index_prefilter() -> bool:
    """VOLSYNC_INDEX_PREFILTER=0 disables the blocked-bloom cold-miss
    prefilter in front of the index shards (first-backup workloads are
    nearly all misses; the filter answers "definitely absent" without a
    probe)."""
    return env_bool("VOLSYNC_INDEX_PREFILTER", True)


# -- multi-tenant service plane (service/admission.py, scheduler.py) -----

def svc_max_streams() -> int:
    """Global cap on concurrently admitted ChunkHash streams; the
    stream that would exceed it is shed at admission with
    RESOURCE_EXHAUSTED (never wedged mid-stream)."""
    return env_int("VOLSYNC_SVC_MAX_STREAMS", 64, minimum=1)


def svc_tenant_streams() -> int:
    """Default per-tenant concurrent-stream cap (a TenantConfig
    max_streams overrides it per tenant)."""
    return env_int("VOLSYNC_SVC_TENANT_STREAMS", 16, minimum=1)


def svc_max_queued() -> int:
    """Global cap on segments queued in the service scheduler; new
    streams are shed at admission while the backlog is at the cap."""
    return env_int("VOLSYNC_SVC_MAX_QUEUED", 256, minimum=1)


def svc_tenant_queued() -> int:
    """Default per-tenant bound on scheduler-queued segments — the
    credit pool behind the per-stream backpressure pause (a
    TenantConfig max_queued overrides it per tenant)."""
    return env_int("VOLSYNC_SVC_TENANT_QUEUED", 32, minimum=1)


def svc_stream_credits() -> int:
    """Segments' worth of request bytes one stream may buffer in the
    server beyond the segment in flight before the handler stops
    reading (gRPC flow control then pauses the sender)."""
    return env_int("VOLSYNC_SVC_STREAM_CREDITS", 2, minimum=1)


def svc_retry_after_ms() -> float:
    """Base retry-after hint (milliseconds) stamped on quota sheds;
    breaker sheds carry the breaker's remaining cooldown instead."""
    return env_float("VOLSYNC_SVC_RETRY_AFTER_MS", 100.0, minimum=1.0)


def svc_quantum() -> int:
    """Deficit-round-robin quantum in bytes credited to each backlogged
    tenant per scheduler round (multiplied by the tenant weight)."""
    return env_int("VOLSYNC_SVC_QUANTUM", 256 * 1024, minimum=1)


def svc_dispatch_window() -> int:
    """Max scheduler-dispatched segments outstanding in the
    microbatcher at once; 0 derives it from the batcher geometry
    (max_batch * pipeline_depth)."""
    return env_int("VOLSYNC_SVC_DISPATCH_WINDOW", 0, minimum=0)


def svc_drain_seconds() -> float:
    """How long stop() waits for in-flight streams to finish before
    aborting the stragglers with UNAVAILABLE."""
    return env_float("VOLSYNC_SVC_DRAIN_S", 10.0, minimum=0.0)


def svc_tenants_spec() -> Optional[str]:
    """VOLSYNC_SVC_TENANTS: per-tenant quota/weight spec, e.g.
    ``gold:weight=4,streams=8,queued=64;bronze:weight=1`` (see
    service/tenants.py parse rules); None = all tenants on defaults."""
    return env_str("VOLSYNC_SVC_TENANTS")


def svc_breaker_backend() -> Optional[str]:
    """VOLSYNC_SVC_BREAKER_BACKEND: name of the resilience circuit
    breaker the admission controller watches — while that breaker is
    open, new streams shed at admission with the remaining cooldown as
    the retry-after hint. None = no breaker wired."""
    return env_str("VOLSYNC_SVC_BREAKER_BACKEND")


def svc_deadline_spec() -> Optional[str]:
    """VOLSYNC_SVC_DEADLINES: deadline-class map for the segment
    scheduler, e.g. ``interactive=0.5,standard=5,background=none`` (see
    scheduler.parse_deadline_classes); None = built-in defaults."""
    return env_str("VOLSYNC_SVC_DEADLINES")


# -- fleet replica plane (service/fleet.py, service/gc.py) ---------------

def fleet_beat_seconds() -> float:
    """VOLSYNC_FLEET_BEAT_S: interval between a replica's heartbeat
    stamps into the shared object store (``fleet/<replica-id>``). The
    stamp carries headroom + backlog, so the beat is also how fast the
    router's routing picture refreshes."""
    return env_float("VOLSYNC_FLEET_BEAT_S", 2.0, minimum=0.1)


def fleet_ttl_seconds() -> float:
    """VOLSYNC_FLEET_TTL_S: heartbeat-stamp TTL — a replica whose stamp
    is older than this is presumed dead: the router stops routing to it
    and ``volsync repair`` may clear the stale stamp. Keep it a few
    beats wide so one slow put does not declare a live replica dead."""
    return env_float("VOLSYNC_FLEET_TTL_S", 10.0, minimum=0.5)


def gc_interval_seconds() -> float:
    """VOLSYNC_GC_INTERVAL_S: pause between continuous-GC prune cycles
    (service/gc.py). Each cycle is the two-phase mark-then-sweep prune;
    the interval bounds how much garbage accumulates between cycles."""
    return env_float("VOLSYNC_GC_INTERVAL_S", 60.0, minimum=0.1)


# -- silent-corruption defense (repo/scrub.py, repo/repository.py) -------

def pack_copies() -> int:
    """VOLSYNC_PACK_COPIES: replicas written for every sealed pack.
    1 (the default) keeps the classic single-copy layout; 2 additionally
    writes each pack to ``mirror/<pack-id>`` through the same resilient
    upload path, giving the scrub and restore read-repair a healthy body
    to heal from. Values above 2 clamp to 2 (one mirror prefix)."""
    return min(env_int("VOLSYNC_PACK_COPIES", 1, minimum=1), 2)


def scrub_interval_seconds() -> float:
    """VOLSYNC_SCRUB_INTERVAL_S: pause between continuous-scrub cycles
    (repo/scrub.py). Each cycle verifies a bounded slice of packs
    on-device, so the interval trades detection latency for read load
    on the store."""
    return env_float("VOLSYNC_SCRUB_INTERVAL_S", 60.0, minimum=0.1)


def scrub_packs_per_cycle() -> int:
    """VOLSYNC_SCRUB_PACKS: packs verified per scrub cycle, walked
    round-robin so every pack is eventually visited. 0 (the default)
    scrubs the whole repository each cycle — right for tests and the
    one-shot ``volsync scrub`` verb; fleets set a budget."""
    return env_int("VOLSYNC_SCRUB_PACKS", 0, minimum=0)


def scrub_read_repair_enabled() -> bool:
    """VOLSYNC_SCRUB_READ_REPAIR: when a pipelined restore's device
    verify catches a corrupt blob, re-fetch the owning pack's mirror,
    heal the primary (verify-then-replace) and keep restoring instead
    of raising IntegrityError immediately. Default on; restores of
    single-copy repositories are unaffected (no mirror -> classic
    failure path)."""
    return env_bool("VOLSYNC_SCRUB_READ_REPAIR", True)


def device_verify_enabled() -> bool:
    """VOLSYNC_DEVICE_VERIFY: check(read_data=True) verifies blob
    payloads with the batched on-device hash path (packs cross the wire
    once, ~64 MiB fused verify dispatches) instead of serial host-side
    hashing. Default on since the scrub rides the same kernels; set 0
    to force the pure-host reference path."""
    return env_bool("VOLSYNC_DEVICE_VERIFY", True)


# -- erasure coding + online repack (repo/erasure.py, repo/repack.py) ----

def ec_scheme() -> Optional[tuple]:
    """VOLSYNC_EC_SCHEME: ``k+m`` (e.g. ``4+2``) arms Reed-Solomon
    striping — sealed packs are written as k data + m parity shards
    under ``ec/<pack-id>/<shard-idx>`` instead of primary+mirror, so any
    m shard losses reconstruct at (k+m)/k storage. None (the default)
    keeps the classic layout; malformed or out-of-range specs degrade
    to None (a typo'd scheme must not silently change the durability
    story — the pack_copies mirror fallback still applies)."""
    raw = env_str("VOLSYNC_EC_SCHEME")
    if raw is None:
        return None
    parts = raw.strip().split("+")
    if len(parts) != 2:
        return None
    try:
        k, m = int(parts[0]), int(parts[1])
    except ValueError:
        return None
    if not (2 <= k <= 16 and 1 <= m <= 8):
        return None
    return (k, m)


def repack_dead_ratio() -> float:
    """VOLSYNC_REPACK_DEAD_RATIO: fraction of a pack's entries that must
    be dead (unreferenced by the index) before RepackService rewrites
    its live blobs into a fresh erasure-coded stripe. Clamped to
    [0.05, 1.0]: 0 would repack every pack every cycle."""
    v = env_float("VOLSYNC_REPACK_DEAD_RATIO", 0.3, minimum=0.05)
    return min(v, 1.0)


def repack_interval_seconds() -> float:
    """VOLSYNC_REPACK_INTERVAL_S: pause between continuous-repack cycles
    (repo/repack.py). Each cycle is one bounded pick-rewrite-retire pass
    under the shared prune lock rules."""
    return env_float("VOLSYNC_REPACK_INTERVAL_S", 60.0, minimum=0.1)


def repack_packs_per_cycle() -> int:
    """VOLSYNC_REPACK_PACKS: packs rewritten per repack cycle. 0 (the
    default) repacks every eligible pack each cycle — right for tests
    and the one-shot ``volsync repack`` verb; fleets set a budget."""
    return env_int("VOLSYNC_REPACK_PACKS", 0, minimum=0)


# -- observability (obs/tracing.py) --------------------------------------

def trace_sample() -> float:
    """VOLSYNC_TRACE_SAMPLE: fraction of new root traces whose spans are
    recorded into the flight recorder (1.0 = every trace, 0 = flight
    recorder off; span totals + the stage histogram always record)."""
    return env_float("VOLSYNC_TRACE_SAMPLE", 1.0, minimum=0.0)


def trace_ring_size() -> int:
    """VOLSYNC_TRACE_RING: span events retained in the in-process
    flight-recorder ring buffer (oldest evicted first, and counted in
    the ``obs.ring_dropped`` counter). The default holds a traced
    benchmark window whole: one event a sealed blob, a ledgered copy, a
    file and a dispatch stage is some tens of thousands in 30 s."""
    return env_int("VOLSYNC_TRACE_RING", 65536, minimum=16)


def trace_dump_dir() -> Optional[str]:
    """VOLSYNC_TRACE_DUMP: directory where trigger events (shed,
    breaker-open, injected fault, deadline) auto-dump annotated
    Chrome-trace JSON files; None (the default) disables auto-dumps
    (the ring still records)."""
    return env_str("VOLSYNC_TRACE_DUMP")


def trace_trigger_interval() -> float:
    """VOLSYNC_TRACE_TRIGGER_INTERVAL_S: minimum seconds between
    auto-dumps for the SAME trigger reason, so a shed storm can't
    fill the dump dir."""
    return env_float("VOLSYNC_TRACE_TRIGGER_INTERVAL_S", 30.0, minimum=0.0)


# -- native accelerator (io/native.py) -----------------------------------

def no_native() -> bool:
    """VOLSYNC_NO_NATIVE=1 skips the native volio accelerator."""
    return env_bool("VOLSYNC_NO_NATIVE")


def volio_so() -> Optional[str]:
    """Path to a prebuilt libvolio.so (container images ship one)."""
    return env_str("VOLSYNC_VOLIO_SO")


def native_cache_dir() -> Optional[str]:
    """Build cache dir for the self-compiled native library."""
    return env_str("VOLSYNC_NATIVE_CACHE")


# -- repository store locking (repo/repository.py) -----------------------

def lock_stale_seconds() -> float:
    """VOLSYNC_LOCK_STALE_S: age after which another holder's repository
    lock object counts as a crashed process and is removed (default 30
    minutes — restic's staleness horizon). Operators shorten it when a
    known-dead holder would otherwise stall exclusive maintenance; the
    ``volsync_repo_lock_age_seconds`` gauge makes the wait visible."""
    return env_float("VOLSYNC_LOCK_STALE_S", 30.0 * 60.0, minimum=1.0)


def prune_grace_seconds() -> Optional[float]:
    """VOLSYNC_PRUNE_GRACE_S: grace a two-phase prune grants marked
    (pending-delete) victim packs before the sweep may delete them.
    Unset (the default) means "use the lock-staleness horizon", which
    guarantees any writer that could still dedup against a victim pack
    either shows a live lock (blocking the sweep) or has crashed. ``0``
    selects the classic stop-the-world prune: exclusive lock, victims
    swept in the same call."""
    raw = env_str("VOLSYNC_PRUNE_GRACE_S")
    if raw is None:
        return None
    try:
        return max(0.0, float(raw.strip()))
    except ValueError:
        return None


# -- sync-protocol planner knobs (engine/protoplan.py, syncstats.py) ------

def sync_protocol() -> str:
    """VOLSYNC_SYNC_PROTO: per-call override of the adaptive protocol
    planner — ``auto`` (cost model decides), ``full`` (whole-file copy),
    ``delta`` (rsync-style signature exchange), ``cdc`` (content-defined
    chunking + dedup). Unknown values degrade to ``auto`` (a typo'd
    override must not wedge a sync into a nonexistent protocol)."""
    raw = (env_str("VOLSYNC_SYNC_PROTO") or "auto").strip().lower()
    return raw if raw in ("auto", "full", "delta", "cdc") else "auto"


def plan_ewma_alpha() -> float:
    """VOLSYNC_PLAN_EWMA: smoothing factor for the SyncStatsBook's
    exponentially weighted moving averages (change rate, dedup ratio,
    link bandwidth/latency). Clamped to (0, 1]: 1.0 = last sample only."""
    v = env_float("VOLSYNC_PLAN_EWMA", 0.3, minimum=0.0)
    return min(max(v, 0.01), 1.0)


def plan_full_blob_cap() -> int:
    """VOLSYNC_PLAN_FULL_CAP: largest file (bytes) the planner may store
    as a single whole-file blob on the CDC side's FULL_COPY path; larger
    files always chunk (a monolithic blob past the segment bucket
    ceiling would blow pack sizing and device call shapes)."""
    return env_int("VOLSYNC_PLAN_FULL_CAP", 8 * 1024 * 1024, minimum=4096)


# -- resilience layer knobs (resilience.py) ------------------------------

def retry_attempts() -> int:
    """Total tries per resilient call (1 = no retry)."""
    return env_int("VOLSYNC_RETRY_ATTEMPTS", 4, minimum=1)


def retry_base_delay() -> float:
    """Backoff floor in seconds (VOLSYNC_RETRY_BASE_MS, milliseconds)."""
    return env_float("VOLSYNC_RETRY_BASE_MS", 50.0, minimum=1.0) / 1000.0


def retry_max_delay() -> float:
    """Backoff cap in seconds (VOLSYNC_RETRY_MAX_MS, milliseconds)."""
    return env_float("VOLSYNC_RETRY_MAX_MS", 5000.0, minimum=1.0) / 1000.0


def retry_deadline() -> Optional[float]:
    """Overall per-operation deadline in seconds
    (VOLSYNC_RETRY_DEADLINE_S); unset/0 = no deadline."""
    v = env_float("VOLSYNC_RETRY_DEADLINE_S", 0.0, minimum=0.0)
    return v or None


def breaker_threshold() -> int:
    """Consecutive retryable failures before a backend's circuit
    breaker opens."""
    return env_int("VOLSYNC_BREAKER_THRESHOLD", 5, minimum=1)


def breaker_reset_seconds() -> float:
    """Cooldown before an open breaker admits the half-open probe."""
    return env_float("VOLSYNC_BREAKER_RESET_S", 30.0, minimum=0.1)


def store_resilience_enabled() -> bool:
    """VOLSYNC_STORE_RESILIENCE=0 opts open_store() out of wrapping
    network backends in the shared retry/breaker layer."""
    return env_bool("VOLSYNC_STORE_RESILIENCE", True)


# -- deterministic fault injection (objstore/faultstore.py) ---------------

def fault_seed() -> Optional[int]:
    """VOLSYNC_FAULT_SEED arms the deterministic fault-injection store
    wrapper for stores opened via open_store(); None = disarmed."""
    raw = env_str("VOLSYNC_FAULT_SEED")
    if raw is None:
        return None
    try:
        return int(raw.strip())
    except ValueError:
        # Never disarm silently: a typo'd seed would let a "chaos" run
        # report a clean pass while injecting nothing.
        raise ValueError(
            f"VOLSYNC_FAULT_SEED={raw!r} is not an integer; fix or "
            "unset it (refusing to run with fault injection silently "
            "disarmed)") from None


def fault_spec() -> Optional[str]:
    """VOLSYNC_FAULT_SPEC: fault-schedule spec string (see
    objstore/faultstore.py parse_spec); None with a seed set means the
    default transient-heavy profile."""
    return env_str("VOLSYNC_FAULT_SPEC")


# -- debug/verification toggles (analysis/lockcheck.py) ------------------

def lockcheck_enabled() -> bool:
    """VOLSYNC_TPU_LOCKCHECK=1 swaps the data-plane locks for
    instrumented wrappers that record the per-thread lock-acquisition
    graph, fail fast on lock-order cycles (potential deadlock), and
    back the assert_held guards on pipeline shared state. Debug/test
    only — never on by default (every acquire pays a bookkeeping
    step)."""
    return env_bool("VOLSYNC_TPU_LOCKCHECK")
