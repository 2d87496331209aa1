"""Driver benchmark: the SHIPPED backup data path on one TPU chip.

Measures the fused single-dispatch segment pipeline (ops/segment.py) that
``DeviceChunkHasher`` / ``stream_chunks`` / ``TreeBackup`` run per
segment: aligned gear-CDC candidates, the on-device FastCDC boundary
walk, strided Merkle leaf SHA-256 (Pallas on TPU), on-device root
assembly, and the ONE small result fetch (chunk table + 32-byte blob ids)
— the restic-engine replacement (SURVEY.md §2.2 #25) on its real code
path, not a kernel microbenchmark.

Shape of the run: N concurrent streams (the reference's concurrency unit
is a mover pod per ReplicationSource, up to MaxConcurrentReconciles=100;
here many CRs share one chip) each drive segments of a synthetic
50%-redundant volume (BASELINE.json configs[4]). Data is device-resident
and salted per iteration (no two timed dispatches share arguments), and
the upload is excluded — the same basis as the CPU number, which also
reads from RAM.

The CPU baseline is the identical computation on one core the way the
reference's mover pod would do it: gear-CDC scan + per-chunk blob ids via
hashlib.

Process contract (a chip belongs to one process at a time):
  * This parent never initializes JAX. The backend is probed in a
    SUBPROCESS with a hard timeout, and the measurement runs in ONE
    killable child at a time — parent and child never hold the chip
    together.
  * The device mode measures a TPU or nothing: with no TPU backend it
    exits non-zero and prints no metric. There is no CPU stand-in for
    the device number, and a golden-check failure is a failure.
  * Only resource exhaustion (or a per-config deadline) walks the ladder
    down to smaller configs; each config runs under a SIGALRM deadline.
  * A global watchdog thread emits a completed measurement if the
    interpreter wedges on the way out, else exits 75.
  * The persistent compilation cache (volsync_tpu/compile_cache.py) is
    on; compile still counts against the config deadline on a cold
    machine, so the ladder is ordered by compile cost.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"} plus
diagnostics {"backend", "path", "config"}.
"""

from __future__ import annotations

import functools
import json
import os
import re
import signal
import subprocess
import sys
import threading
import time

import numpy as np

# envflags imports only os — safe before the JAX env setup below.
from volsync_tpu.compile_cache import configure as _configure_cache
from volsync_tpu.envflags import (
    env_bool,
    env_int,
    env_str,
    session_backend,
    session_epoch,
    session_id,
)

# Persistent compilation cache: later processes reuse compiled
# executables instead of paying the first compile again. main() places
# it (configure() imports jax, so the modes' JAX_PLATFORMS go first);
# children inherit it.
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")

#: modes that run on the host and pin the CPU backend
_HOST_MODES = ("pipeline", "restore", "copies-smoke", "ec", "syncplan",
               "index")

# Wall-clock budgets (seconds). Consistency invariant: probe worst case
# (sum(PROBE_TIMEOUTS)+backoffs, ~330s) + the device measurement
# subprocess (MEASURE_TIMEOUT_S) must fit inside GLOBAL_BUDGET_S, or the
# watchdog would kill a still-progressing run. The subprocess's own
# ladder (configs x per-config deadline) must fit inside its timeout.
PROBE_TIMEOUTS = (120, 200)
PROBE_BACKOFF_S = 15
CONFIG_DEADLINE_S = env_int("VOLSYNC_BENCH_CONFIG_DEADLINE", 420)
MEASURE_TIMEOUT_S = env_int("VOLSYNC_BENCH_MEASURE_TIMEOUT", 1800)
GLOBAL_BUDGET_S = env_int("VOLSYNC_BENCH_BUDGET_S", 3600)

_log = functools.partial(print, file=sys.stderr, flush=True)

# Best result seen so far: the watchdog prints this if the main thread
# wedges after a successful measurement (e.g. a stuck executor join).
_BEST: dict | None = None
_BEST_LOCK = threading.Lock()


def _emit(result: dict) -> None:
    """Print one result line — REFUSED unless it carries a provenance
    block. An unattributable number is worse than no number: a CPU
    figure was once recorded under the device metric's name and only
    its provenance said so. Callers stamp ``bench_provenance()``
    first."""
    if not result.get("provenance"):
        raise ValueError(
            "bench result refused: no provenance block "
            f"(keys: {sorted(result)})")
    print(json.dumps(result), flush=True)


# Copy-ratio regression thresholds (``bench.py copies-smoke``).
# copy_ratio = ledgered host copy bytes / payload bytes moved through
# the timed pipelined run; the smoke FAILS when a measured ratio
# exceeds its committed maximum, so a new unledgered copy path can't
# land silently. Raising a threshold is a reviewed change, like adding
# a record_copy site. Values carry ~20% headroom over the measured
# smoke-scale ratios — pipeline 2.0 (chunker.ingest for the read()-only
# bench reader + objstore.assemble for the contiguous Mem transport),
# restore 1.0 (verify.stage) — see docs/performance.md, "Zero-copy
# data movement" for what each remaining site pays.
COPY_RATIO_MAX = {"pipeline": 2.4, "restore": 1.2}


def _copy_report(total_bytes: int, kind: str, legacy_passes: float) -> dict:
    """Ledger snapshot for the timed window -> artifact block.

    ``copy_ratio`` is ledgered host copy bytes per payload byte;
    ``copy_ratio_pre`` is an ANALYTIC estimate (ratio + the full
    payload passes the legacy sites paid: monolithic pack-body
    assembly on backup, slice-of-pack-body segment extraction on
    restore) — documented in the artifact so the drop is visible
    without resurrecting the old code path."""
    from volsync_tpu.obs import copies_by_site

    sites = {k: int(v) for k, v in sorted(copies_by_site().items())}
    copied = sum(sites.values())
    ratio = round(copied / max(1, total_bytes), 3)
    return {
        "copy_bytes_by_site": sites,
        "copy_bytes_total": copied,
        "copy_ratio": ratio,
        "copy_ratio_pre_estimate": round(ratio + legacy_passes, 3),
        "copy_ratio_max": COPY_RATIO_MAX[kind],
    }


def bench_provenance(extra: Optional[dict] = None) -> dict:
    """Provenance block stamped into every bench JSON result: platform,
    git rev, the VOLSYNC_*/JAX_PLATFORMS knobs in effect, and — only
    when it can be read without side effects — the jax backend and
    device kind. A CPU number must never be mistakable for a chip
    number.

    Never *initializes* jax: the parent process must stay off the chip
    (one process per chip), so the backend is reported only if a
    backend already exists in this process or the env pins CPU;
    otherwise it is labeled honestly as not initialized."""
    import platform

    prov: dict = {
        "platform": platform.platform(),
        "python": platform.python_version(),
    }
    try:
        r = subprocess.run(
            ["git", "-C", os.path.dirname(os.path.abspath(__file__)),
             "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10)
        prov["git_rev"] = (r.stdout.strip() if r.returncode == 0
                           else "unknown")
    except OSError as e:
        _log(f"bench: git rev unavailable: {e}")
        prov["git_rev"] = "unknown"
    jx = sys.modules.get("jax")
    if jx is None:
        prov["jax_backend"] = "not-imported"
    else:
        bridge = getattr(getattr(jx, "_src", None), "xla_bridge", None)
        initialized = bool(getattr(bridge, "_backends", None))
        env = dict(os.environ)
        if initialized or env.get("JAX_PLATFORMS", "").strip() == "cpu":
            try:
                prov["jax_backend"] = jx.default_backend()
                prov["jax_device_kind"] = jx.devices()[0].device_kind
            except Exception as e:  # noqa: BLE001 — label, never hang/abort
                _log(f"bench: backend read failed: {e}")
                prov["jax_backend"] = f"error:{type(e).__name__}"
        else:
            prov["jax_backend"] = "imported-uninitialized"
    prov["volsync_flags"] = {
        k: v for k, v in sorted(dict(os.environ).items())
        if k.startswith("VOLSYNC_") or k == "JAX_PLATFORMS"}
    sid = session_id()
    if sid:
        # Stamped by the serialized bench queue (cluster/sessions.py)
        # into every job's environment: which supervised session, under
        # which fencing epoch, produced this number.
        prov["session"] = {"id": sid, "epoch": session_epoch(),
                           "backend": session_backend() or "unknown"}
    if extra:
        prov.update(extra)
    return prov


def _watchdog() -> None:
    time.sleep(GLOBAL_BUDGET_S)
    with _BEST_LOCK:
        best = _BEST
    if best is not None:
        _log("bench: WATCHDOG fired after measurement — emitting best result")
        try:
            _emit(best)
            os._exit(0)
        except ValueError as e:
            # Provenance refusal must not strand the watchdog short of
            # its os._exit — fall through to the no-result exit code.
            _log(f"bench: WATCHDOG result refused: {e}")
    _log(f"bench: WATCHDOG fired with no result after {GLOBAL_BUDGET_S}s")
    os._exit(75)


class _Deadline(Exception):
    """Per-config SIGALRM deadline expired."""


class _BackendDown(Exception):
    """Backend init / UNAVAILABLE — retrying smaller configs cannot help."""


def _classify(e: BaseException) -> str:
    s = f"{type(e).__name__}: {e}"
    if re.search(r"RESOURCE[_ ]EXHAUSTED|out of memory|OOM|"
                 r"[Aa]ttempting to allocate|[Aa]llocation.*failed", s):
        return "oom"
    if re.search(r"UNAVAILABLE|Unable to initialize|DEADLINE_EXCEEDED|"
                 r"failed to connect|[Cc]onnection|[Ss]ocket|INTERNAL:", s):
        return "backend"
    return "other"


_PROBE_SRC = """
import jax, jax.numpy as jnp
x = jnp.arange(64, dtype=jnp.float32)
y = jax.jit(lambda v: (v * 2 + 1).sum())(x)
y.block_until_ready()
print("probe-ok", jax.default_backend())
"""


def _probe_backend(timeouts=PROBE_TIMEOUTS) -> Optional[str]:
    """Probe backend init in a subprocess with a hard timeout; returns
    the default backend's platform name, or None if unreachable.

    Backend setup blocks in C++ where SIGALRM cannot reliably interrupt,
    and a parent that initialized JAX would hold the chip against its
    own measurement child — so the probe is a separate killable
    process that has exited before the child starts."""
    for i, tmo in enumerate(timeouts):
        t0 = time.perf_counter()
        try:
            r = subprocess.run(
                [sys.executable, "-c", _PROBE_SRC],
                timeout=tmo, capture_output=True, text=True,
                env=os.environ.copy())
            dt = time.perf_counter() - t0
            if r.returncode == 0 and "probe-ok" in r.stdout:
                name = r.stdout.strip().split()[-1]
                _log(f"bench: backend probe ok in {dt:.1f}s ({name})")
                return name
            _log(f"bench: probe attempt {i + 1} rc={r.returncode} in "
                 f"{dt:.1f}s: {(r.stderr or '').strip()[-300:]}")
        except subprocess.TimeoutExpired:
            _log(f"bench: probe attempt {i + 1} timed out after {tmo}s")
        if i + 1 < len(timeouts):
            # Device-settle pacing between subprocess probes, not an
            # error-retry of a store call — RetryPolicy doesn't apply.
            time.sleep(PROBE_BACKOFF_S)  # lint: ignore[VL105]
    return None


def _host_gear_candidates(host: np.ndarray, p) -> tuple[np.ndarray, np.ndarray]:
    """Pure-numpy aligned gear scan -> (strict, lax) candidate cut
    positions. The host reference for the device kernel
    (ops/gearcdc.gear_at_aligned): table value per byte, 32-byte window
    weighted by shifts 31..0, mod 2^32. Shared by the golden self-check
    and the CPU baseline so the two can never desynchronize."""
    n = host.shape[0] // p.align * p.align
    rows = host[:n].reshape(-1, p.align)[:, -32:]
    g = p.table[rows].astype(np.uint64)
    shifts = np.arange(31, -1, -1, dtype=np.uint64)
    h = ((g << shifts[None, :]).sum(axis=1) & 0xFFFFFFFF).astype(np.uint32)
    pos = np.arange(h.shape[0], dtype=np.int64) * p.align + (p.align - 1)
    return (pos[(h & np.uint32(p.mask_s)) == 0],
            pos[(h & np.uint32(p.mask_l)) == 0])


def _make_data(total: int, redundancy: float = 0.5) -> np.ndarray:
    """BASELINE.json configs[4]-style synthetic volume: ``redundancy`` of
    the stream is a repeated region (dedup finds it; boundaries/digests
    are computed for every byte either way)."""
    rng = np.random.RandomState(7)
    uniq = rng.randint(0, 256, size=(int(total * (1 - redundancy)),),
                       dtype=np.uint8)
    rep = rng.randint(0, 256, size=(total - uniq.shape[0],), dtype=np.uint8)
    return np.concatenate([uniq, rep])


def _try_device_throughput(seg_mib: int, streams: int, iters: int) -> float:
    import jax
    import jax.numpy as jnp

    from volsync_tpu.engine.chunker import DeviceChunkHasher
    from volsync_tpu.ops.gearcdc import DEFAULT_PARAMS
    from volsync_tpu.ops.segment import chunk_hash_segment

    p = DEFAULT_PARAMS
    n = seg_mib * 1024 * 1024
    host_np = _make_data(n)
    data = jnp.asarray(host_np)
    jax.block_until_ready(data)

    # The salt is composed INTO the one fused dispatch (d ^ s traces
    # through the identical library program), so every iteration hashes
    # distinct content with no data-sized transfer. Dispatch, retry
    # logic, decode, and the blob-id assembly are the unmodified shipped
    # code (FusedSegmentHasher drives this via its override hook).
    @functools.partial(jax.jit, static_argnames=("eof", "cand_cap",
                                                 "chunk_cap"))
    def salted(d, s, vl, *, eof, cand_cap, chunk_cap):
        return chunk_hash_segment(
            d ^ s, vl, min_size=p.min_size, avg_size=p.avg_size,
            max_size=p.max_size, seed=p.seed, mask_s=p.mask_s,
            mask_l=p.mask_l, align=p.align, eof=eof, cand_cap=cand_cap,
            chunk_cap=chunk_cap)

    def make_hasher(stream_id: int) -> DeviceChunkHasher:
        h = DeviceChunkHasher(p)
        h.salt = jnp.uint8(stream_id & 0xFF)

        def fn(dev, length, **kw):
            return salted(dev, h.salt, length, eof=kw["eof"],
                          cand_cap=kw["cand_cap"], chunk_cap=kw["chunk_cap"])

        h.fused.segment_device_fn = fn
        return h

    # Distinct uint8 salt per (stream, iteration): no two timed
    # dispatches hash the same content.
    assert streams * iters < 255, "salt space exhausted"

    # Deadline hygiene: a _Deadline fires in the MAIN thread; leaked
    # workers from the abandoned pool would keep dispatching and
    # contaminate the NEXT ladder config's measurement. They check this
    # flag between segments, so leakage is bounded to one in-flight
    # dispatch per worker.
    cancelled = threading.Event()

    def run_stream(stream_id: int) -> int:
        """One CR's backup loop over ``iters`` segments: dispatch + the
        single small fetch per segment (the shipped protocol)."""
        h = make_hasher(stream_id)
        emitted = 0
        for i in range(iters):
            if cancelled.is_set():
                break
            # Per-segment scalar salt upload is the shipped protocol
            # under measurement — batching it would change the workload.
            h.salt = jnp.uint8((stream_id - 1) * iters + i + 1)  # lint: ignore[VL502] measured protocol
            emitted += len(h.process_device(data, n))
        return emitted

    # Warm all shapes/compiles once — and use the (unsalted) warm run as
    # an on-TPU golden check against a PURE-HOST reference (numpy gear
    # scan + the scalar FastCDC walk + hashlib Merkle ids): no second
    # device program to compile, and nothing the device computes is
    # trusted to check itself.
    h0 = make_hasher(0)
    h0.salt = jnp.uint8(0)
    warm = h0.process_device(data, n)
    from volsync_tpu.ops.gearcdc import _select_boundaries_py
    from volsync_tpu.repo import blobid

    idx_s, idx_l = _host_gear_candidates(host_np, p)
    ref_bounds = _select_boundaries_py(idx_s, idx_l, n, p, eof=True)
    assert [(s, l) for s, l, _ in warm] == ref_bounds, "fused boundaries"
    view = host_np.tobytes()
    for s, l, d in warm[:4] + warm[-2:]:
        assert d == blobid.blob_id(view[s: s + l]), "fused blob id"

    from concurrent.futures import ThreadPoolExecutor

    t0 = time.perf_counter()
    pool = ThreadPoolExecutor(streams)
    try:
        emitted = sum(pool.map(run_stream, range(1, streams + 1)))
    finally:
        # Never join wedged workers under a deadline — the watchdog is
        # the backstop, not a hung interpreter exit.
        cancelled.set()
        pool.shutdown(wait=False, cancel_futures=True)
    dt = time.perf_counter() - t0
    assert emitted > 0
    return streams * iters * n / dt  # bytes/s, full shipped path


def _try_batched_throughput(seg_mib: int, streams: int, iters: int,
                            pipelines: Optional[int] = None) -> float:
    """The cross-PVC batched dispatch (ops/segment.chunk_hash_segments):
    all streams' segments in ONE device program per iteration — no
    per-stream dispatch/fetch round-trips at all. Lane content is the
    shared base buffer xor a per-lane salt, composed on device.

    ``pipelines`` concurrent dispatch threads overlap the fixed
    per-dispatch and per-fetch cost (not measured on the current
    machine) with device compute — the same overlap the shipped
    SegmentMicroBatcher gets from concurrent movers. Default 2;
    VOLSYNC_BENCH_PIPELINES overrides so bench_self rungs can A/B the
    depth on hardware."""
    if pipelines is None:
        pipelines = env_int("VOLSYNC_BENCH_PIPELINES", 2)
    import functools as _ft
    from concurrent.futures import ThreadPoolExecutor

    import jax
    import jax.numpy as jnp

    from volsync_tpu.ops.gearcdc import DEFAULT_PARAMS
    from volsync_tpu.ops.segment import chunk_hash_segments, segment_caps

    p = DEFAULT_PARAMS
    n = seg_mib * 1024 * 1024
    host_np = _make_data(n)
    base = jnp.asarray(host_np)
    jax.block_until_ready(base)
    cand_cap, chunk_cap = segment_caps(n, p)

    @_ft.partial(jax.jit, static_argnames=("cand_cap", "chunk_cap"))
    def salted(d, salts, vl, eof, *, cand_cap, chunk_cap):
        # [S*P] composed on device, flat like the shipped staging
        rows = jnp.tile(d, salts.shape[0]) ^ jnp.repeat(salts, d.shape[0])
        return chunk_hash_segments(
            rows, vl, eof, min_size=p.min_size, avg_size=p.avg_size,
            max_size=p.max_size, seed=p.seed, mask_s=p.mask_s,
            mask_l=p.mask_l, align=p.align, cand_cap=cand_cap,
            chunk_cap=chunk_cap)

    vl = jnp.full((streams,), n, jnp.int32)
    eof = jnp.ones((streams,), bool)
    # +1 round: run(iters) is the warm call, so salts reach
    # (iters+1)*streams; uint8 wraparound would let warm salts collide
    # with timed ones.
    assert streams * (iters + 1) < 255, "salt space exhausted"

    # On-TPU golden check, which doubles as the warm/compile run (its
    # salt range is disjoint from the timed ones): DISTINCT per-lane
    # salts — identical lanes would let a cross-lane indexing bug
    # (every row computed from lane 0) pass — with the first and last
    # lanes verified against the PURE-HOST reference (numpy gear scan,
    # scalar FastCDC walk, hashlib Merkle roots of head + tail chunks).
    from volsync_tpu.ops.gearcdc import _select_boundaries_py
    from volsync_tpu.ops.segment import decode_segment
    from volsync_tpu.repo import blobid

    salt0 = streams * (iters + 1) + 1
    assert salt0 + streams - 1 < 255, "golden salt space exhausted"
    g_out = np.asarray(salted(
        base, jnp.asarray(np.arange(salt0, salt0 + streams,
                                    dtype=np.uint8)), vl, eof,
        cand_cap=cand_cap, chunk_cap=chunk_cap))
    for lane in {0, streams - 1}:
        lane_np = host_np ^ np.uint8(salt0 + lane)
        idx_s, idx_l = _host_gear_candidates(lane_np, p)
        ref_bounds = _select_boundaries_py(idx_s, idx_l, n, p, eof=True)
        g_chunks, _, _, _ = decode_segment(g_out[lane], chunk_cap)
        assert [(s, l) for s, l, _ in g_chunks] == ref_bounds, \
            f"batched boundaries (lane {lane})"
        view = lane_np.tobytes()
        for s0, l0, d0 in g_chunks[:2] + g_chunks[-2:]:
            assert d0 == blobid.blob_id(view[s0:s0 + l0]), \
                f"batched blob id (lane {lane})"

    # Deadline hygiene (same contract as _try_device_throughput): a
    # _Deadline fires in the MAIN thread; never join possibly-wedged
    # workers — shutdown(wait=False) + a cancellation flag bound the
    # leakage to one in-flight dispatch per pipeline.
    cancelled = threading.Event()

    def run(i):
        if cancelled.is_set():
            return None
        salts = jnp.asarray(
            np.arange(1 + i * streams, 1 + (i + 1) * streams,
                      dtype=np.uint8))
        out = np.asarray(salted(base, salts, vl, eof, cand_cap=cand_cap,
                                chunk_cap=chunk_cap))
        assert int(out[0, 0]) > 0  # lanes produced chunks
        return out

    # (no separate warm run: the golden-check dispatch above compiled
    # and executed this exact program shape)
    t0 = time.perf_counter()
    if pipelines <= 1:
        for i in range(iters):
            run(i)
    else:
        pool = ThreadPoolExecutor(pipelines)
        try:
            done = sum(r is not None for r in pool.map(run, range(iters)))
            assert done == iters, "pipelined dispatches cancelled mid-run"
        finally:
            cancelled.set()
            pool.shutdown(wait=False, cancel_futures=True)
    dt = time.perf_counter() - t0
    return streams * iters * n / dt


def _with_deadline(fn, *args):
    """Run fn under a SIGALRM wall-clock deadline (main thread only)."""
    deadline = CONFIG_DEADLINE_S

    def _alarm(signum, frame):
        raise _Deadline(f"config exceeded {deadline}s")

    old = signal.signal(signal.SIGALRM, _alarm)
    signal.setitimer(signal.ITIMER_REAL, deadline)
    try:
        return fn(*args)
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old)


_START = time.monotonic()


def _budget_left() -> float:
    return GLOBAL_BUDGET_S - (time.monotonic() - _START)


def _try_config(kind: str, seg_mib: int, streams: int, iters: int) -> float:
    t0 = time.perf_counter()
    _log(f"bench: trying {kind}{seg_mib}x{streams}x{iters}")
    fn = (_try_batched_throughput if kind == "B"
          else _try_device_throughput)
    out = _with_deadline(fn, seg_mib, streams, iters)
    _log(f"bench: config ok -> {out / (1 << 30):.2f} GiB/s "
         f"({time.perf_counter() - t0:.0f}s)")
    return out


def _parse_config(s: str) -> tuple[str, int, int, int]:
    kind = "S"
    if s[:1] in ("B", "S"):
        kind, s = s[0], s[1:].lstrip(":")
    seg, st, it = map(int, s.split(","))
    return kind, seg, st, it


def _run_config_ladder() -> tuple[float, str]:
    # Primary metric: the cross-PVC batched program (shipped via the
    # mover-jax coalescer and VOLSYNC_BATCH_SEGMENTS). The first rung
    # is the LARGEST shape with a known-bounded compile: compile time
    # grows faster than S*P (ROADMAP Speed 4) and counts against the
    # config deadline on a cold machine — bigger shapes belong to the
    # upsize probes, which can deadline without losing the number in
    # hand. The single-segment path is the fallback rung.
    # Three rungs, not four: worst case (every rung eating its full
    # 420 s deadline) must stay inside the measurement child's
    # 1740 s watchdog with headroom for the golden checks and the CPU
    # baseline — 3x420 + overhead fits, 4x420 could clip the last rung.
    configs = [("B", 64, 8, 6), ("B", 32, 8, 8), ("S", 32, 4, 4)]
    pinned_config = env_str("VOLSYNC_BENCH_CONFIG")
    pinned = bool(pinned_config)
    if pinned_config:
        configs = [_parse_config(pinned_config)]
    last_err: BaseException | None = None
    best: Optional[tuple[float, str]] = None
    for kind, seg_mib, streams, iters in configs:
        t0 = time.perf_counter()
        try:
            out = _try_config(kind, seg_mib, streams, iters)
            best = (out, f"{kind}{seg_mib}x{streams}x{iters}")
            break
        except AssertionError:
            # a kernel that is wrong on the chip is a failed run, never
            # a smaller config or a slower number from another path
            raise
        except _Deadline as e:
            _log(f"bench: config deadline after "
                 f"{time.perf_counter() - t0:.0f}s — trying smaller")
            last_err = e
        except Exception as e:  # noqa: BLE001
            kind_e = _classify(e)
            _log(f"bench: config failed [{kind_e}] after "
                 f"{time.perf_counter() - t0:.0f}s: "
                 f"{type(e).__name__}: {str(e)[:300]}")
            if kind_e == "backend":
                # A smaller segment cannot fix a backend that is down.
                raise _BackendDown(str(e)) from e
            if kind_e != "oom":
                raise
            last_err = e
    if best is None:
        raise last_err if last_err else RuntimeError("no bench configs")
    # Opportunistic upsizing: one real-hardware run per round, so while
    # budget clearly remains, probe bigger shapes and keep the max. A
    # failure here never loses the number already in hand.
    if not pinned:
        kind, rest = best[1][0], best[1][1:]
        seg, streams, iters = map(int, rest.split("x"))
        for up in (
                # more bytes per dispatch first (the measured lever),
                (kind, seg * 2, streams, max(iters // 2, 1)),
                (kind, seg, streams * 2, max(iters // 2, 1)),
                # then the other program shape at the winning size
                ("S" if kind == "B" else "B", seg, streams, iters)):
            up_kind, up_seg, up_streams, up_iters = up
            if _budget_left() < 2 * CONFIG_DEADLINE_S:
                break
            if up_streams * (up_iters + 1) >= 255:
                continue  # salt space
            if (up_kind == "B"
                    and up_seg * (1 << 20) * up_streams >= 1 << 31):
                continue  # int32 gather index space (2 GiB batch cap)
            try:
                _log(f"bench: upsize probe {up_kind}{up_seg}x{up_streams}"
                     f"x{up_iters}")
                fn = (_try_batched_throughput if up_kind == "B"
                      else _try_device_throughput)
                out = _with_deadline(fn, up_seg, up_streams, up_iters)
                _log(f"bench: upsize ok -> {out / (1 << 30):.2f} GiB/s")
                if out > best[0]:
                    best = (out,
                            f"{up_kind}{up_seg}x{up_streams}x{up_iters}")
            except AssertionError as e:
                # The upsize shape FAILED its golden check: its number
                # is discarded (never emitted), the main config's
                # verified number stands — but this is a real kernel
                # correctness bug at that shape; flag it loudly.
                _log(f"bench: KERNEL BUG — golden check failed at "
                     f"{up_seg}x{up_streams}x{up_iters}: {e}; upsize "
                     f"result discarded, keeping verified {best[1]}")
            except _Deadline:
                _log("bench: upsize exceeded the config deadline — "
                     "keeping the measured number")
            except Exception as e:  # noqa: BLE001
                _log(f"bench: upsize failed [{_classify(e)}]: "
                     f"{str(e)[:200]}")
                if _classify(e) == "backend":
                    break  # keep the number we have; backend is down
    return best


def cpu_baseline(total_mib: int = 64) -> float:
    """The strongest plausible single-core implementation of the same
    work (the reference's unit of compute is one mover pod ~ one core):
    a numpy-vectorized gear candidate scan at aligned positions plus
    C-speed SHA-256 (hashlib, one call per ~avg-size chunk — no Python
    per-leaf loop, deliberately generous to the baseline)."""
    import hashlib

    from volsync_tpu.ops.gearcdc import DEFAULT_PARAMS

    p = DEFAULT_PARAMS
    n = total_mib * 1024 * 1024
    host = _make_data(n)
    t0 = time.perf_counter()
    _, cand = _host_gear_candidates(host, p)
    view = host.tobytes()
    pos = 0
    while pos < n:
        end = min(pos + p.avg_size, n)
        hashlib.sha256(view[pos:end]).digest()
        pos = end
    _ = cand
    dt = time.perf_counter() - t0
    return n / dt


class _HostSegmentHasher:
    """Fixed-grid host chunk+hash stand-in for the device stage, used by
    the pipeline bench: on a CPU backend the XLA sha256 path runs at
    ~4 MiB/s, which would drown the read/seal/upload overlap this bench
    exists to measure (on a TPU the device stage is sub-ms per segment
    and the same overlap applies). Conforms to stream_chunks' plain
    hasher protocol: process() -> [(start, length, digest)]."""

    def __init__(self, chunk_size: int = 1 << 20):
        self.chunk_size = chunk_size

    def process(self, buffer, *, eof: bool = True):
        import hashlib

        data = buffer.tobytes()
        end = (len(data) if eof
               else (len(data) // self.chunk_size) * self.chunk_size)
        out = []
        for pos in range(0, end, self.chunk_size):
            ln = min(self.chunk_size, end - pos)
            out.append((pos, ln,
                        hashlib.sha256(data[pos:pos + ln]).hexdigest()))
        return out


def _metric_value(name: str, labels: dict) -> float:
    """Read one sample from the global registry via the public text
    exposition (no private prometheus_client attribute access)."""
    from volsync_tpu.metrics import GLOBAL as M

    want = "{" + ",".join(f'{k}="{v}"' for k, v in sorted(labels.items())
                          ) + "}" if labels else ""
    for line in M.expose().decode().splitlines():
        if not line.startswith(name):
            continue
        head, _, val = line.rpartition(" ")
        if labels:
            lb = head[head.find("{"):]
            if sorted(lb.strip("{}").split(",")) != sorted(
                    want.strip("{}").split(",")):
                continue
        elif "{" in head:
            continue
        return float(val)
    return 0.0


def index_bench(entries: int = 1_000_000, queries: int = 200_000,
                batch: int = 4096, shards: Optional[int] = None) -> dict:
    """Metadata-plane microbench (``bench.py index``): batched
    vectorized dedup lookups vs the per-key scalar probe loop, and the
    sharded index + blocked-bloom prefilter vs the single flat table.

    Builds an index of ``entries`` random SHA-256-shaped keys, then
    measures (a) scalar ``lookup``/``in`` per-key rates, (b) batched
    ``lookup_many``/``contains_many`` rates in ``batch``-key slices for
    pure-hit, pure-miss, and mixed workloads, and (c) the sharded
    index's batched rates with prefilter skip/false-positive counts.
    The headline value is the batched-vs-scalar hit-lookup speedup.
    Host-side only — no jax, no device."""
    from volsync_tpu.repo.compactindex import CompactIndex
    from volsync_tpu.repo.shardedindex import ShardedBlobIndex

    rng = np.random.RandomState(11)
    raw = rng.bytes(32 * entries)
    ids = [raw[i * 32:(i + 1) * 32].hex() for i in range(entries)]
    raw_miss = rng.bytes(32 * queries)
    miss = [raw_miss[i * 32:(i + 1) * 32].hex() for i in range(queries)]
    hit_idx = rng.randint(0, entries, size=queries)
    hits = [ids[i] for i in hit_idx.tolist()]
    mixed = [h if i % 2 else m for i, (h, m) in
             enumerate(zip(hits, miss))]

    t0 = time.perf_counter()
    single = CompactIndex(capacity=entries)
    for i, h in enumerate(ids):
        single.insert(h, f"pack{i >> 12}", "data", i, 1024, 2048)
    build_single_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    sharded = ShardedBlobIndex(shards=shards, capacity=entries)
    for i, h in enumerate(ids):
        sharded.insert(h, f"pack{i >> 12}", "data", i, 1024, 2048)
    build_sharded_s = time.perf_counter() - t0

    nscalar = min(queries, 50_000)  # scalar loops are the slow side

    def rate(n, secs):
        return round(n / secs) if secs > 0 else 0

    def timed(fn):
        # One warmup pass first: the first touch of a ~66 MiB table
        # after build is page faults and cache fills, not probe cost,
        # and it would be billed to whichever workload ran first.
        fn()
        t0 = time.perf_counter()
        fn()
        return time.perf_counter() - t0

    def scalar_hits():
        for h in hits[:nscalar]:
            single.lookup(h)

    def scalar_misses():
        for m in miss[:nscalar]:
            m in single  # noqa: B015 — timing the membership probe

    scalar_hit_s = timed(scalar_hits)
    scalar_miss_s = timed(scalar_misses)

    def batched(index, keys, fn):
        def run():
            for i in range(0, len(keys), batch):
                fn(index, keys[i:i + batch])
        return timed(run)

    def lk(idx, ks):
        idx.lookup_many(ks)

    def ct(idx, ks):
        idx.contains_many(ks)

    batched_hit_s = batched(single, hits, lk)
    batched_miss_s = batched(single, miss, ct)
    batched_mixed_s = batched(single, mixed, ct)

    skip0 = _metric_value("volsync_index_prefilter_total",
                          {"outcome": "skip"})
    fp0 = _metric_value("volsync_index_prefilter_total",
                        {"outcome": "false_positive"})
    sh_hit_s = batched(sharded, hits, lk)
    sh_miss_s = batched(sharded, miss, ct)
    sh_mixed_s = batched(sharded, mixed, ct)
    # warmup+timed both ran: halve the counter deltas to report one pass
    skips = (_metric_value("volsync_index_prefilter_total",
                           {"outcome": "skip"}) - skip0) / 2
    fps = (_metric_value("volsync_index_prefilter_total",
                         {"outcome": "false_positive"}) - fp0) / 2

    scalar_rate = nscalar / scalar_hit_s if scalar_hit_s > 0 else 0.0
    batched_rate = queries / batched_hit_s if batched_hit_s > 0 else 0.0
    speedup = round(batched_rate / scalar_rate, 2) if scalar_rate else 0.0
    return {
        "metric": "index_batched_lookup_speedup",
        "value": speedup,
        "unit": "x",
        "entries": entries,
        "queries": queries,
        "batch": batch,
        "shards": sharded._nshards,
        "build": {
            "single_s": round(build_single_s, 3),
            "sharded_s": round(build_sharded_s, 3),
            "inserts_per_s": rate(entries, build_single_s),
        },
        "scalar": {
            "hit_lookup_per_s": rate(nscalar, scalar_hit_s),
            "miss_contains_per_s": rate(nscalar, scalar_miss_s),
        },
        "batched": {
            "hit_lookup_per_s": rate(queries, batched_hit_s),
            "miss_contains_per_s": rate(queries, batched_miss_s),
            "mixed_contains_per_s": rate(queries, batched_mixed_s),
        },
        "sharded_batched": {
            "hit_lookup_per_s": rate(queries, sh_hit_s),
            "miss_contains_per_s": rate(queries, sh_miss_s),
            "mixed_contains_per_s": rate(queries, sh_mixed_s),
            "prefilter_skips": int(skips),
            "prefilter_false_positives": int(fps),
            "prefilter_saturation": round(
                sharded.prefilter_saturation(), 4),
        },
        "index_mib": round(single.nbytes() / (1 << 20), 1),
        "provenance": bench_provenance(),
    }


def pipeline_bench(total_mib: int = 24, put_latency_s: float = 0.04,
                   segment_mib: int = 2,
                   fault_seed: Optional[int] = None) -> dict:
    """Serial-vs-pipelined backup data plane (``bench.py pipeline``).

    Streams a ``total_mib`` volume through stream_chunks ->
    Repository.add_blob -> flush twice — once with
    VOLSYNC_TPU_PIPELINE=0 semantics (inline seal, synchronous put) and
    once with the full pipeline (read-ahead thread, seal pool, bounded
    async upload window) — over a MemObjectStore wrapped in LatencyStore
    so every put costs ``put_latency_s`` like a real object store.
    Reports wall times, speedup, and the per-stage breakdown
    (read / device / seal / upload) from the obs span registry.

    Two measurement details matter on small hosts: a short pipelined
    warmup run is done first so thread-pool creation and module imports
    are not billed to the timed runs, and the interpreter switch
    interval is lowered for the duration of the bench — at the default
    5 ms a single-core box pays up to one full interval per cross-thread
    future/queue handoff, which swamps the IO latency the pipeline is
    hiding.

    ``fault_seed`` (``bench.py pipeline --faults SEED``) arms the
    deterministic fault-injection wrapper under the shared resilience
    layer — the reported number is then GOODPUT under the seeded fault
    schedule (VOLSYNC_FAULT_SPEC or the default transient+latency
    profile), not clean-path throughput.

    The serial run adds chunks one ``add_blob`` (one lock + one scalar
    probe) at a time; the pipelined run consumes per-segment batches
    through ``add_blobs`` (one lock + one vectorized dedup query per
    batch). ``dedup`` in the stage breakdown is the batched query time;
    ``dedup_compare`` re-times the same key set scalar-vs-batched on
    the finished repository."""
    from volsync_tpu.engine.chunker import stream_chunk_batches
    from volsync_tpu.objstore.store import LatencyStore, MemObjectStore
    from volsync_tpu.obs import (
        dump_trace,
        reset_copies,
        reset_spans,
        reset_trace,
        span_totals,
        trace_context,
    )
    from volsync_tpu.ops.gearcdc import GearParams
    from volsync_tpu.repo.repository import Repository

    total = total_mib << 20
    seg_size = segment_mib << 20
    data = _make_data(total, redundancy=0.0).tobytes()
    params = GearParams(min_size=256 * 1024, avg_size=512 * 1024,
                        max_size=1024 * 1024, seed=7, align=4096)

    def run(pipelined: bool, limit: int = 0):
        lat = LatencyStore(MemObjectStore(), put_latency=put_latency_s)
        if fault_seed is None:
            repo = Repository.init(lat)
        else:
            from volsync_tpu.objstore.faultstore import maybe_wrap
            from volsync_tpu.resilience import (
                CircuitBreaker,
                ResilientStore,
                RetryPolicy,
            )

            # init on the clean store (put_if_absent is single-attempt
            # by design), then run the data plane through the same
            # layering open_store builds: faults UNDER the retry layer.
            Repository.init(lat)
            store = ResilientStore(
                maybe_wrap(lat, seed=fault_seed),
                policy=RetryPolicy(site="bench.faults", max_attempts=10,
                                   base_delay=0.001, max_delay=0.01),
                breaker=CircuitBreaker("bench", threshold=10**9,
                                       reset_seconds=0.1))
            repo = Repository.open(store)
        repo.pipelined = pipelined
        repo.PACK_TARGET = 1024 * 1024
        end = limit or total
        pos = 0

        def reader(n):
            nonlocal pos
            piece = data[pos:min(pos + n, end)]
            pos += len(piece)
            return piece

        reset_spans()
        reset_trace()
        reset_copies()
        ids: list = []
        t0 = time.perf_counter()
        with trace_context(tenant="bench"):
            for chunks in stream_chunk_batches(
                    reader, params, segment_size=seg_size,
                    hasher=_HostSegmentHasher(),
                    readahead=(2 if pipelined else 0)):
                if pipelined:
                    repo.add_blobs(
                        "data",
                        [(digest, chunk) for chunk, digest in chunks])
                else:
                    for chunk, digest in chunks:
                        repo.add_blob("data", digest, chunk)
                ids.extend(digest for _, digest in chunks)
            repo.flush()
        elapsed = time.perf_counter() - t0
        injected = (len(repo.store.inner.injected)
                    if fault_seed is not None else 0)
        return elapsed, span_totals(), lat, injected, repo, ids

    prev_switch = sys.getswitchinterval()
    sys.setswitchinterval(0.0005)
    try:
        run(True, limit=4 << 20)  # warmup: pools, imports, first-call paths
        serial_s, serial_spans, _, _, _, _ = run(False)
        (pipe_s, pipe_spans, pipe_store, pipe_injected, pipe_repo,
         pipe_ids) = run(True)
        # snapshot the ledger before dedup_compare touches the repo —
        # legacy removed one full payload pass (monolithic pack-body
        # assembly), hence legacy_passes=1.0
        copies = _copy_report(total, "pipeline", legacy_passes=1.0)
    finally:
        sys.setswitchinterval(prev_switch)

    def stages(spans):
        return {name: round(spans.get(key, (0, 0.0))[1], 4)
                for name, key in (("read", "engine.read"),
                                  ("device", "engine.device"),
                                  ("dedup", "repo.dedup_query"),
                                  ("seal", "repo.seal"),
                                  ("upload", "repo.pack_upload"),
                                  ("upload_wait", "repo.upload_wait"))}

    def dedup_compare(repo, ids, rounds: int = 50):
        """Per-chunk locking (one repo-lock + scalar probe per key, the
        pre-batching dedup path) vs ONE has_blobs query per batch over
        the run's whole 50/50 hit/miss key set — the shape of a warm
        backup's unchanged-file check, which queries a file's entire
        content list at once."""
        rng = np.random.RandomState(5)
        absent = [rng.bytes(32).hex() for _ in range(len(ids))]
        keys = [k for pair in zip(ids, absent) for k in pair]
        repo.has_blobs(keys)  # warm both paths' caches
        t0 = time.perf_counter()
        for _ in range(rounds):
            for k in keys:
                repo.has_blob(k)
        scalar_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(rounds):
            repo.has_blobs(keys)
        batched_s = time.perf_counter() - t0
        n = rounds * len(keys)
        return {
            "keys_per_batch": len(keys),
            "scalar_us_per_key": round(scalar_s / n * 1e6, 3),
            "batched_us_per_key": round(batched_s / n * 1e6, 3),
            "speedup": (round(scalar_s / batched_s, 2)
                        if batched_s > 0 else 0.0),
        }

    result = {
        "metric": "pipeline_backup_speedup",
        "value": round(serial_s / pipe_s, 2),
        "unit": "x",
        "serial_s": round(serial_s, 3),
        "pipelined_s": round(pipe_s, 3),
        "throughput_mib_s": round(total_mib / pipe_s, 1),
        "segments": total_mib // segment_mib,
        "packs_uploaded": pipe_store.puts,
        "max_concurrent_puts": pipe_store.max_concurrent_puts,
        "put_latency_ms": round(put_latency_s * 1000, 1),
        "stages": stages(pipe_spans),
        "stages_serial": stages(serial_spans),
        "copy_ratio": copies["copy_ratio"],
        "copies": copies,
        "dedup_compare": dedup_compare(pipe_repo, pipe_ids),
        # ROADMAP item 1 follow-on: every bench JSON self-describes
        # where its time went. The flight recorder still holds the
        # pipelined (last) run; trace_file is null unless
        # VOLSYNC_TRACE_DUMP names a directory to export into.
        "provenance": bench_provenance(extra={"copies": copies, "trace": {
            "spans": {name: {"count": c, "seconds": round(s, 4)}
                      for name, (c, s) in sorted(pipe_spans.items())},
            "trace_file": dump_trace(trigger="bench_pipeline"),
        }}),
    }
    if fault_seed is not None:
        result["fault_seed"] = fault_seed
        result["faults_injected"] = pipe_injected
    return result


def restore_bench(total_mib: int = 24, get_latency_s: float = 0.04,
                  storm: int = 4, smoke: bool = False) -> dict:
    """Serial-vs-pipelined restore data plane (``bench.py restore``).

    Backs a synthetic tree into a MemObjectStore once, then restores it
    three ways through a LatencyStore where every GET costs
    ``get_latency_s`` like a real object store:

    - **serial**: the per-blob golden oracle (one ranged GET + host
      verify per blob, files in sequence);
    - **pipelined**: the pack-aware plane (engine/restorepipe.py) —
      whole-pack fetches through the PackCache, device-batched verify,
      positional writes;
    - **storm**: ``storm`` concurrent pipelined restores of the SAME
      snapshot sharing one PackCache (RestoreGroup) — the number that
      matters is pack fetches relative to a single restore (single-
      flight bound), reported as ``storm_fetch_ratio``.

    Same measurement hygiene as pipeline_bench: a warmup restore over
    a zero-latency store absorbs pool/JIT/first-call costs, and the
    interpreter switch interval is lowered for the timed runs."""
    import shutil
    import tempfile
    from pathlib import Path

    from volsync_tpu.engine import RestoreGroup, TreeBackup, TreeRestore
    from volsync_tpu.objstore.store import LatencyStore, MemObjectStore
    from volsync_tpu.obs import reset_copies, reset_spans, span_totals
    from volsync_tpu.repo.repository import Repository

    total = total_mib << 20
    file_mib = 2
    nfiles = max(1, total_mib // file_mib)
    data = _make_data(total, redundancy=0.0).tobytes()

    workdir = Path(tempfile.mkdtemp(prefix="volsync-restore-bench-"))
    try:
        src = workdir / "src"
        src.mkdir()
        step = len(data) // nfiles
        for i in range(nfiles):
            (src / f"f{i:03d}.bin").write_bytes(
                data[i * step:(i + 1) * step])

        mem = MemObjectStore()
        # restic-scale chunks (≈256 KiB) against 1 MiB packs: the
        # serial oracle pays one ranged GET per CHUNK, the pipelined
        # plane one whole GET per PACK — the batching this bench exists
        # to price. The default 1 MiB-avg chunker would make blobs ≈
        # packs and hide the difference.
        repo = Repository.init(mem, chunker={
            "min_size": 128 * 1024, "avg_size": 256 * 1024,
            "max_size": 512 * 1024, "seed": 7, "align": 4096})
        repo.PACK_TARGET = 1024 * 1024
        snap, _ = TreeBackup(repo).run(src)
        assert snap
        npacks = len(list(mem.list("data/")))

        def run(pipelined: bool, latency: float, dest: Path,
                workers=None):
            lat = LatencyStore(mem, get_latency=latency)
            r = Repository.open(lat)
            reset_spans()
            reset_copies()
            t0 = time.perf_counter()
            with r.lock(exclusive=False):
                r.load_index()
                snap_id, manifest = r.select_snapshot()
                TreeRestore(r, workers=workers,
                            pipeline=pipelined)._run_locked(
                    snap_id, manifest, dest)
            return time.perf_counter() - t0, span_totals(), lat

        def run_storm(latency: float):
            lat = LatencyStore(mem, get_latency=latency)
            group = RestoreGroup()
            for i in range(storm):
                group.add(Repository.open(lat),
                          workdir / f"storm{i}")
            t0 = time.perf_counter()
            group.run()
            return time.perf_counter() - t0, group.stats()[0], lat

        prev_switch = sys.getswitchinterval()
        sys.setswitchinterval(0.0005)
        try:
            run(True, 0.0, workdir / "warmup")
            # the golden oracle really is serial: one ranged GET per
            # blob, one file at a time (workers=1); the file-concurrent
            # variant (default worker pool) is reported alongside
            serial_s, serial_spans, _ = run(False, get_latency_s,
                                            workdir / "serial",
                                            workers=1)
            serial_conc_s, _, _ = run(False, get_latency_s,
                                      workdir / "serial-conc")
            pipe_s, pipe_spans, pipe_lat = run(True, get_latency_s,
                                               workdir / "pipe")
            # ledger snapshot before the storm muddies attribution —
            # legacy sliced every segment out of a bytes pack body
            # (one full payload pass), hence legacy_passes=1.0
            copies = _copy_report(total, "restore", legacy_passes=1.0)
            storm_s, cache_stats, storm_lat = run_storm(get_latency_s)
        finally:
            sys.setswitchinterval(prev_switch)

        def stages(spans):
            return {name: round(spans.get(key, (0, 0.0))[1], 4)
                    for name, key in (("plan", "restore.plan"),
                                      ("fetch", "restore.fetch"),
                                      ("verify", "restore.verify"),
                                      ("write", "restore.write"))}

        demand = cache_stats["hits"] + cache_stats["misses"]
        return {
            "metric": "restore_pipeline_speedup",
            "value": round(serial_s / pipe_s, 2),
            "unit": "x",
            "serial_s": round(serial_s, 3),
            "serial_concurrent_s": round(serial_conc_s, 3),
            "pipelined_s": round(pipe_s, 3),
            "throughput_mib_s": round(total_mib / pipe_s, 1),
            "gib_s": round(total_mib / 1024 / pipe_s, 3),
            "get_latency_ms": round(get_latency_s * 1000, 1),
            "packs": npacks,
            "single_pack_fetches": pipe_lat.pack_fetches,
            "storm": {
                "restores": storm,
                "elapsed_s": round(storm_s, 3),
                "pack_fetches": storm_lat.pack_fetches,
                # single-flight bound: a storm of N restores should
                # cost about the SAME wire fetches as one restore
                "storm_fetch_ratio": round(
                    storm_lat.pack_fetches
                    / max(1, pipe_lat.pack_fetches), 2),
                "cache_hit_ratio": round(
                    cache_stats["hits"] / max(1, demand), 3),
                "cache": cache_stats,
            },
            "stages": stages(pipe_spans),
            "stages_serial": stages(serial_spans),
            "copy_ratio": copies["copy_ratio"],
            "copies": copies,
            "smoke": smoke,
            "provenance": bench_provenance(extra={
                "copies": copies,
                "restore": {"total_mib": total_mib, "files": nfiles}}),
        }
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def copies_smoke() -> dict:
    """Copy-ledger contract gate (``bench.py copies-smoke``, wired into
    scripts/static_check.sh via ``make copies-smoke``).

    Runs the backup and restore data planes at smoke scale and asserts
    the zero-copy contract on both artifacts:

    - every ledgered site is in ``obs.SANCTIONED_SITES`` — a new
      ``record_copy`` call must also amend the canonical set;
    - the measured ``copy_ratio`` stays at or under the committed
      ``COPY_RATIO_MAX`` threshold stamped into the artifact — a new
      unledgered full-payload copy shows up here as a ratio jump;
    - the artifact carries the copies block (``copy_bytes_by_site``,
      ``copy_ratio``, the threshold) in both the result and its
      provenance, so the contract is self-describing.

    Exits nonzero on any violation."""
    from volsync_tpu.obs import SANCTIONED_SITES

    pipe = pipeline_bench(total_mib=8, put_latency_s=0.005)
    rest = restore_bench(total_mib=6, get_latency_s=0.005, storm=2,
                         smoke=True)
    failures: list = []
    for kind, res in (("pipeline", pipe), ("restore", rest)):
        block = res.get("copies") or {}
        if not block or "copy_bytes_by_site" not in block:
            failures.append(f"{kind}: artifact missing copies block")
            continue
        if res.get("copy_ratio") != block["copy_ratio"]:
            failures.append(f"{kind}: top-level copy_ratio missing or "
                            f"inconsistent with copies block")
        if block != (res.get("provenance", {}).get("copies")):
            failures.append(f"{kind}: provenance missing copies block")
        unknown = sorted(set(block["copy_bytes_by_site"])
                         - SANCTIONED_SITES)
        if unknown:
            failures.append(f"{kind}: unsanctioned copy sites {unknown}")
        if block["copy_ratio"] > block["copy_ratio_max"]:
            failures.append(
                f"{kind}: copy_ratio {block['copy_ratio']} exceeds the "
                f"committed max {block['copy_ratio_max']}")
    return {
        "metric": "copy_ledger_smoke",
        "value": len(failures),
        "unit": "violations",
        "ok": not failures,
        "failures": failures,
        "pipeline": {"copy_ratio": pipe.get("copy_ratio"),
                     "copies": pipe.get("copies"),
                     "throughput_mib_s": pipe.get("throughput_mib_s")},
        "restore": {"copy_ratio": rest.get("copy_ratio"),
                    "copies": rest.get("copies"),
                    "throughput_mib_s": rest.get("throughput_mib_s")},
        "provenance": bench_provenance(),
    }


def syncplan_bench(smoke: bool = True) -> dict:
    """Protocol-planner replay: three canned workloads scored against a
    measured oracle (``bench.py syncplan``).

    Each workload builds real trees, measures the TRUE wire cost of
    every protocol with the real engines — DELTA through the batched
    device scan (engine/deltasync.delta_scan_batch), CDC_DEDUP through
    two real TreeBackup runs against one repository (the second run's
    dedup stats are the measured hit ratio) — then replays the
    workload's history into a SyncStatsBook and asks the planner to
    choose. ``regret_ratio`` is the true cost of the chosen protocol
    over the true cost of the cheapest (1.0 = planner matched the
    oracle); the gate is <= 1.05 per workload, asserted here so the
    smoke target fails loudly on a cost-model regression. All transfer
    costs are priced against one canned reference link so the replay is
    deterministic; device terms use the model's own conservative
    constants.
    """
    import tempfile
    from pathlib import Path

    from volsync_tpu.engine import deltasync, protoplan, syncstats
    from volsync_tpu.engine.backup import TreeBackup
    from volsync_tpu.metrics import GLOBAL as METRICS
    from volsync_tpu.objstore import MemObjectStore
    from volsync_tpu.repo.repository import Repository

    LINK_BPS = 100.0 * (1 << 20)   # canned reference link: 100 MiB/s
    LINK_LAT = 0.010               # 10 ms per round trip
    # Sized so the three workloads land in three different optimal
    # regimes on the reference link: files big enough that wire bytes
    # beat round trips when churn/dedup allow it.
    n_files = 4 if smoke else 8
    fsize = (4 << 20) if smoke else (8 << 20)
    rng = np.random.RandomState(0x5EED)
    # Small chunker so even smoke-sized files span many CDC chunks.
    chunker = {"min_size": 16 * 1024, "avg_size": 64 * 1024,
               "max_size": 256 * 1024, "seed": 7}
    DEV_BPS = {protoplan.FULL_COPY: 0.0,
               protoplan.DELTA: protoplan.DEVICE_DELTA_BPS,
               protoplan.CDC_DEDUP: protoplan.DEVICE_CDC_BPS}
    RT = {protoplan.FULL_COPY: 1, protoplan.DELTA: 2,
          protoplan.CDC_DEDUP: 2}

    def true_cost(proto: str, wire: float, nbytes: int) -> float:
        dev = nbytes / DEV_BPS[proto] if DEV_BPS[proto] else 0.0
        return (wire / LINK_BPS + n_files * RT[proto] * LINK_LAT + dev)

    def measure_cdc(base_files, new_files):
        """Measured CDC wire bytes for syncing ``new_files`` into a
        repository that already holds ``base_files``."""
        with tempfile.TemporaryDirectory() as td:
            root = Path(td)
            repo = Repository.init(MemObjectStore(), chunker=chunker)
            for sub, files in (("base", base_files), ("new", new_files)):
                d = root / sub
                d.mkdir()
                for i, data in enumerate(files):
                    (d / f"f{i}.bin").write_bytes(data)
            if base_files:
                TreeBackup(repo).run(root / "base")
            _snap, stats = TreeBackup(repo).run(root / "new")
            blobs = stats.blobs_new + stats.blobs_dedup
            wire = (stats.bytes_scanned - stats.bytes_dedup
                    + protoplan.CDC_CHUNK_META_BYTES * blobs)
            return wire, stats.blobs_dedup, blobs

    def measure_delta(base_files, new_files):
        """Measured DELTA wire bytes via the batched device scan."""
        items, sig_cost = [], 0
        for old, new in zip(base_files, new_files):
            sig = deltasync.build_file_signature(
                old, deltasync.pick_block_len(max(len(old), len(new))))
            geo = deltasync.signature_geometry(len(old), sig.block_len)
            sig_cost += (geo.sig_bytes
                         + protoplan.DELTA_OP_OVERHEAD_PER_BLOCK
                         * geo.n_blocks)
            items.append((new, sig))
        literal = 0
        ratios = []
        for (new, sig), ops in zip(items,
                                   deltasync.delta_scan_batch(items)):
            lit = deltasync.delta_stats(ops, sig.block_len)["literal_bytes"]
            literal += lit
            ratios.append((lit, len(new)))
        return sig_cost + literal, ratios

    def replay_and_decide(book, *, basis_exists: bool):
        """One planner decision per (homogeneous) file; every file must
        agree, so the workload verdict is the per-file verdict."""
        chosen = {
            protoplan.decide(fsize, book.snapshot(),
                             basis_exists=basis_exists).protocol
            for _ in range(n_files)}
        assert len(chosen) == 1, f"unstable decisions: {chosen}"
        return chosen.pop()

    workloads: dict = {}

    # -- workload 1: cold full copy (fresh dest, zero history) ---------
    new = [rng.bytes(fsize) for _ in range(n_files)]
    total = n_files * fsize
    cdc_wire, _hits, _blobs = measure_cdc([], new)
    costs = {protoplan.FULL_COPY: true_cost("full", total, total),
             protoplan.CDC_DEDUP: true_cost("cdc", cdc_wire, total)}
    book = syncstats.SyncStatsBook()
    workloads["cold_full"] = (costs,
                              replay_and_decide(book, basis_exists=False))

    # -- workload 2: 1%-churn incremental (delta territory) ------------
    base = [rng.bytes(fsize) for _ in range(n_files)]
    new = []
    for data in base:
        buf = bytearray(data)
        for _ in range(4):  # ~1% of bytes across 4 scattered spots
            at = int(rng.randint(0, fsize - fsize // 400))
            buf[at:at + fsize // 400] = rng.bytes(fsize // 400)
        new.append(bytes(buf))
    delta_wire, ratios = measure_delta(base, new)
    cdc_wire, hits, blobs = measure_cdc(base, new)
    costs = {protoplan.FULL_COPY: true_cost("full", total, total),
             protoplan.DELTA: true_cost("delta", delta_wire, total),
             protoplan.CDC_DEDUP: true_cost("cdc", cdc_wire, total)}
    book = syncstats.SyncStatsBook()
    for lit, nbytes in ratios:        # replay: prior delta runs
        book.observe_delta(lit, nbytes)
    book.observe_dedup(hits, blobs)   # ... and the measured dedup rate
    book.observe_link(total, total / LINK_BPS)
    book.observe_rtt(LINK_LAT)
    workloads["churn_1pct"] = (costs,
                               replay_and_decide(book, basis_exists=True))

    # -- workload 3: high-dedup re-ingest (cdc territory) --------------
    # same content under new names: no per-file basis for delta, but
    # nearly every chunk already lives in the repository
    new = list(base)
    cdc_wire, hits, blobs = measure_cdc(base, new)
    costs = {protoplan.FULL_COPY: true_cost("full", total, total),
             protoplan.CDC_DEDUP: true_cost("cdc", cdc_wire, total)}
    book = syncstats.SyncStatsBook()
    book.observe_dedup(hits, blobs)
    book.observe_link(total, total / LINK_BPS)
    book.observe_rtt(LINK_LAT)
    workloads["high_dedup"] = (costs,
                               replay_and_decide(book, basis_exists=False))

    out: dict = {"bench": "syncplan", "smoke": smoke,
                 "link": {"bandwidth_bps": LINK_BPS, "latency_s": LINK_LAT},
                 "files": n_files, "file_bytes": fsize, "workloads": {}}
    worst = 0.0
    for name, (costs, chosen) in workloads.items():
        oracle = min(costs, key=costs.get)
        regret = costs[chosen] / costs[oracle]
        worst = max(worst, regret)
        out["workloads"][name] = {
            "chosen": chosen, "oracle": oracle,
            "regret_ratio": round(regret, 4),
            "cost_s": {p: round(c, 6) for p, c in costs.items()},
        }
        assert regret <= 1.05, (
            f"workload {name}: planner chose {chosen} "
            f"(regret {regret:.3f}) over oracle {oracle}")
    out["regret_ratio_max"] = round(worst, 4)
    METRICS.plan_regret.set(worst)
    out["provenance"] = bench_provenance(extra={
        "syncplan": {"files": n_files, "file_bytes": fsize}})
    return out


def ec_bench(smoke: bool = True, k: int = 4, m: int = 2) -> dict:
    """Erasure-coding data plane (``bench.py ec``, smoke wired into
    scripts/static_check.sh via ``make ec-bench-smoke``).

    Four numbers, one artifact (docs/robustness.md, "Erasure coding &
    online repack"):

    - **encode / decode throughput** — the batched GF(2^8) device
      matmul (ops/rs.py page grid) vs the pure-NumPy golden oracle,
      GiB/s over the same payload;
    - **reconstruct latency vs mirror fetch** — the read-path cost of
      losing m shards (any-k reconstruction + content-addressed proof)
      against the 2x-mirror alternative it replaces (fetch + sha256
      proof), both from a Mem store;
    - **measured storage overhead** — stored shard bytes (headers and
      page padding included) over the logical pack bytes, asserted at
      or under the committed 1.5x the scheme promises.
    """
    from volsync_tpu.ops import rs
    from volsync_tpu.repo import erasure

    total = (8 if smoke else 64) * (1 << 20) + 12_345  # off page grid
    iters = 3 if smoke else 8
    rng = np.random.RandomState(4242)
    body = rng.bytes(total)
    shard_len = (total + k - 1) // k
    flat = np.zeros(k * shard_len, dtype=np.uint8)
    flat[:total] = np.frombuffer(body, dtype=np.uint8)
    data2d = flat.reshape(k, shard_len)
    shard_bufs = [data2d[i].tobytes() for i in range(k)]

    def timed(fn, n=iters):
        fn()  # warm (device path: compile + transfer once)
        t0 = time.perf_counter()
        for _ in range(n):
            fn()
        return (time.perf_counter() - t0) / n

    grid, _L = rs.rs_pack_host(shard_bufs)
    enc_dev_s = timed(
        lambda: np.asarray(rs.rs_encode_device(grid, m)))
    enc_np_s = timed(lambda: rs.rs_encode_np(data2d, m), n=1)
    parity = np.asarray(rs.rs_encode_np(data2d, m))

    # decode with the first m DATA shards lost — the worst case: every
    # recovered row pays real field math, no identity passthrough
    have = {i: shard_bufs[i] for i in range(m, k)}
    have.update({k + i: parity[i].tobytes() for i in range(m)})
    have_np = {i: np.frombuffer(b, dtype=np.uint8)
               for i, b in have.items()}
    dec_dev_s = timed(
        lambda: rs.rs_reconstruct_device(have, k, m, shard_len))
    dec_np_s = timed(lambda: rs.rs_reconstruct_np(have_np, k, m), n=1)
    assert (rs.rs_reconstruct_np(have_np, k, m).reshape(-1)[:total]
            .tobytes() == body), "oracle decode mismatch"

    # read-path latency: any-k reconstruction vs mirror fetch, both
    # ending in the same content-addressed sha256 proof
    import hashlib

    pack_id = hashlib.sha256(body).hexdigest()
    shards = erasure.encode_pack_shards([body], k, m)
    stored = sum(len(s) for s in shards)
    surviving = {i: shards[i] for i in range(m, k + m)}

    def reconstruct():
        out = erasure.reconstruct_verified(surviving, pack_id)
        assert out is not None

    def mirror_fetch():
        assert hashlib.sha256(body).hexdigest() == pack_id

    rec_s = timed(reconstruct)
    mir_s = timed(mirror_fetch)

    gib = total / (1 << 30)
    overhead = stored / total
    result = {
        "metric": "ec_encode_throughput",
        "value": round(gib / enc_dev_s, 3),
        "unit": "GiB/s",
        "scheme": f"{k}+{m}",
        "payload_bytes": total,
        "encode": {
            "device_gib_s": round(gib / enc_dev_s, 3),
            "numpy_gib_s": round(gib / enc_np_s, 3),
            "speedup": round(enc_np_s / enc_dev_s, 1),
        },
        "decode": {
            "device_gib_s": round(gib / dec_dev_s, 3),
            "numpy_gib_s": round(gib / dec_np_s, 3),
            "speedup": round(dec_np_s / dec_dev_s, 1),
        },
        "reconstruct_vs_mirror": {
            "reconstruct_ms": round(rec_s * 1e3, 2),
            "mirror_fetch_ms": round(mir_s * 1e3, 2),
            "slowdown": round(rec_s / max(mir_s, 1e-9), 1),
        },
        "storage_overhead": {
            "measured": round(overhead, 4),
            "theoretical": erasure.storage_overhead(k, m),
            "mirror_alternative": 2.0,
        },
        "smoke": smoke,
        "provenance": bench_provenance(
            extra={"ec": {"k": k, "m": m, "iters": iters}}),
    }
    assert round(overhead, 3) <= 1.5, (
        f"measured EC overhead {overhead} exceeds the 1.5x contract")
    return result


def _pipeline_child(timeout_s: int = 180):
    """Run ``bench.py pipeline`` in a killable CPU-pinned subprocess and
    parse its JSON line; None on any failure (the main metric must
    never be lost to the stage-breakdown extra)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("VOLSYNC_BENCH_INNER", None)
    try:
        r = subprocess.run([sys.executable, __file__, "pipeline"],
                           timeout=timeout_s, capture_output=True,
                           text=True, env=env)
    except subprocess.TimeoutExpired:
        return None
    for line in reversed((r.stdout or "").strip().splitlines()):
        try:
            return json.loads(line)
        except ValueError:
            continue
    return None


def _inner_main():
    """Measure in THIS process — the one process that holds the chip.
    Any backend but a TPU is refused before anything is measured; any
    failure simply exits nonzero. The inner watchdog still emits a
    completed result if the interpreter wedges on the way out."""
    global _BEST
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        _log(f"bench: backend is {backend!r}, not a TPU — the device "
             f"mode measures nothing elsewhere")
        raise SystemExit(69)
    threading.Thread(target=_watchdog, name="bench-watchdog",
                     daemon=True).start()
    dev, config = _run_config_ladder()

    from volsync_tpu.ops import sha256 as _sha

    cpu = cpu_baseline()
    gib = dev / (1 << 30)
    result = {
        "metric": "backup_path_throughput_single_chip",
        "value": round(gib, 3),
        "unit": "GiB/s",
        "vs_baseline": round(dev / cpu, 2),
        "backend": backend,
        "path": "pallas" if _sha.use_pallas_leaves() else "xla",
        "config": config,
        "provenance": bench_provenance(),
    }
    with _BEST_LOCK:
        _BEST = result
    _emit(result)


def _run_measurement_child(extra_env: dict, timeout_s: int) -> Optional[dict]:
    """Run the measurement in a KILLABLE subprocess. SIGALRM cannot
    interrupt a C-blocked device call (a grpc upload wedging mid-run
    would ride out every in-process deadline), so the only hang-proof
    boundary is a process the parent can kill."""
    # The child's own watchdog must fire BEFORE the parent kill so a
    # completed-but-wedged measurement still emits its result; and a
    # result printed before a timeout kill is recovered from the
    # exception's captured stdout.
    env = dict(os.environ, VOLSYNC_BENCH_INNER="1",
               VOLSYNC_BENCH_BUDGET_S=str(max(timeout_s - 60, 60)),
               **extra_env)

    def parse(stdout) -> Optional[dict]:
        if isinstance(stdout, bytes):
            stdout = stdout.decode(errors="replace")
        for line in reversed((stdout or "").strip().splitlines()):
            try:
                return json.loads(line)
            except ValueError:
                continue
        return None

    try:
        r = subprocess.run([sys.executable, __file__], timeout=timeout_s,
                           capture_output=True, text=True, env=env)
    except subprocess.TimeoutExpired as e:
        out = parse(e.stdout)
        _log(f"bench: measurement subprocess exceeded {timeout_s}s — "
             f"killed (salvaged result: {out is not None})")
        return out
    tail = (r.stderr or "").strip()[-600:]
    if tail:
        _log(f"bench: child stderr tail:\n{tail}")
    if r.returncode == 0 and r.stdout.strip():
        out = parse(r.stdout)
        if out is None:
            _log(f"bench: child stdout unparsable: {r.stdout[-200:]!r}")
        return out
    _log(f"bench: measurement subprocess rc={r.returncode}")
    return None


def main():
    if len(sys.argv) > 1 and sys.argv[1] in _HOST_MODES:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    _configure_cache()
    if len(sys.argv) > 1 and sys.argv[1] == "pipeline":
        # Standalone stage-breakdown mode; host-side only (_HOST_MODES
        # pins the backend to the CPU). ``--faults SEED`` arms the
        # deterministic fault-injection wrapper so the number is
        # goodput under a seeded fault schedule.
        fault_seed = None
        if "--faults" in sys.argv[2:]:
            i = sys.argv.index("--faults")
            try:
                fault_seed = int(sys.argv[i + 1])
            except (IndexError, ValueError):
                print("usage: bench.py pipeline [--faults SEED]",
                      file=sys.stderr)
                return 2
        _emit(pipeline_bench(fault_seed=fault_seed))
        return 0
    if len(sys.argv) > 1 and sys.argv[1] == "restore":
        # Restore data plane: serial vs pipelined vs storm; host-side
        # (the verify kernel runs on the CPU backend).
        smoke = "--smoke" in sys.argv[2:]
        storm = 4
        if "--storm" in sys.argv[2:]:
            i = sys.argv.index("--storm")
            try:
                storm = int(sys.argv[i + 1])
            except (IndexError, ValueError):
                print("usage: bench.py restore [--smoke] [--storm N]",
                      file=sys.stderr)
                return 2
        _emit(restore_bench(total_mib=6 if smoke else 24,
                            storm=storm, smoke=smoke))
        return 0
    if len(sys.argv) > 1 and sys.argv[1] == "copies-smoke":
        # Zero-copy contract gate: both data planes at smoke scale,
        # site sanction + copy_ratio threshold asserted; host-side.
        res = copies_smoke()
        _emit(res)
        return 0 if res["ok"] else 1
    if len(sys.argv) > 1 and sys.argv[1] == "ec":
        # Erasure-coding data plane: device vs NumPy GF(2^8) kernels,
        # reconstruct-vs-mirror latency, measured storage overhead;
        # host-side (the RS matmul runs on the CPU backend).
        _emit(ec_bench(smoke="--smoke" in sys.argv[2:]))
        return 0
    if len(sys.argv) > 1 and sys.argv[1] == "syncplan":
        # Protocol-planner replay: host + CPU device kernels only.
        _emit(syncplan_bench(smoke="--smoke" in sys.argv[2:]))
        return 0
    if len(sys.argv) > 1 and sys.argv[1] == "index":
        # Metadata-plane microbench; host-side only (numpy, no device).
        kw: dict = {}
        argv = sys.argv[2:]
        spec = {"--entries": "entries", "--queries": "queries",
                "--batch": "batch", "--shards": "shards"}
        i = 0
        while i < len(argv):
            name = spec.get(argv[i])
            try:
                kw[name] = int(argv[i + 1])
            except (TypeError, IndexError, ValueError):
                print("usage: bench.py index [--entries N] [--queries N]"
                      " [--batch N] [--shards N]", file=sys.stderr)
                return 2
            i += 2
        _emit(index_bench(**kw))
        return 0
    if env_bool("VOLSYNC_BENCH_INNER"):
        return _inner_main()
    threading.Thread(target=_watchdog, name="bench-watchdog",
                     daemon=True).start()

    # Device mode: a TPU or nothing. The probe child has exited before
    # the measurement child starts, and this parent never touches JAX,
    # so exactly one process holds the chip at any time.
    probed = _probe_backend()
    if probed != "tpu":
        _log(f"bench: no TPU backend (probe={probed}) — the device mode "
             f"measures nothing elsewhere; no metric emitted")
        raise SystemExit(69)
    out = _run_measurement_child(
        {}, int(min(MEASURE_TIMEOUT_S, _budget_left() - 120)))
    if out is None:
        _log("bench: device measurement failed; no metric emitted")
        raise SystemExit(70)
    if _budget_left() > 300:
        pipe = _pipeline_child()
        if pipe is not None:
            out["pipeline"] = pipe
    _emit(out)
    return 0


if __name__ == "__main__":
    # os._exit everywhere: a device call stuck on a pool thread would
    # otherwise hang the interpreter's atexit thread-join forever.
    try:
        rc = main() or 0
    except SystemExit as e:
        rc = int(e.code or 0)
    except BaseException as e:  # noqa: BLE001 — fast, visible failure
        _log(f"bench: fatal: {type(e).__name__}: {str(e)[:400]}")
        rc = 1
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)
