#!/usr/bin/env python3
"""The delta programs alone on the device, one staged buffer at the
mover's real size: seconds a run of ``delta_sig_flat``,
``delta_match_rows`` and ``delta_md5_flat`` at each block length, the
upload of one buffer, and the stages of a search at every offset apart
(prefix sums, membership by each method, compaction: what made the
engine search only the rows an aligned probe leaves open). By hand,
outside the benchmark:

    python scripts/profile_delta.py

What a change of alignment costs the engine (``--insertions``): one
60 MiB file (one staged buffer, blocks of 8 KiB) scanned against its
old self with 1% of its pages rewritten in place, then with 1, 10, 100
and 1,000 scattered insertions, then with its middle third zero-filled
and moved by one byte: seconds a scan, staged buffers, dispatches of the
search, strong checks, pieces probed again, literal bytes.

    python scripts/profile_delta.py --insertions
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np  # noqa: E402


def timed(fn, *args, n=3, **kw):
    import jax

    out = fn(*args, **kw)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args, **kw)
        jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def main() -> int:
    import jax
    import jax.numpy as jnp

    from volsync_tpu.engine import deltasync
    from volsync_tpu.ops import delta

    dev0 = jax.devices()[0]
    print(json.dumps({"platform": dev0.platform, "kind": dev0.device_kind}))
    rng = np.random.default_rng(7)
    W = deltasync.WINDOW
    host = np.frombuffer(rng.bytes(W), np.uint8)
    t0 = time.perf_counter()
    for _ in range(3):
        dev = jax.device_put(host)
        dev.block_until_ready()
    print(json.dumps({"upload_s": (time.perf_counter() - t0) / 3,
                      "bytes": W}))
    for bl in (4096, 8192, 16384, 32768):
        geo = deltasync._Geometry.of(bl)
        sig_s = timed(delta.delta_sig_flat, dev, block_len=bl)
        weak, _strong = delta.delta_sig_flat(dev, block_len=bl)
        table = np.sort(np.asarray(weak))
        sw = np.full(geo.sig_cap(max(len(table), 16384)), 0xFFFFFFFF,
                     np.uint32)
        sw[: len(table)] = table
        G = geo.search_rows
        rows = np.arange(G, dtype=np.int32) * ((W // 1024) // G)
        until = np.full(G, W - bl + 1, np.int32)
        args = (dev, jax.device_put(sw), np.int32(len(table)),
                jax.device_put(rows), jax.device_put(until), np.int32(0))
        kw = dict(window=bl, max_candidates=geo.cand_cap)
        match_s = timed(delta.delta_match_rows, *args, **kw)
        cand, _w, total = delta.delta_match_rows(*args, **kw)
        starts = jax.device_put(np.minimum(np.asarray(cand), W - bl))
        md5_s = timed(delta.delta_md5_flat, dev, starts, block_len=bl)
        print(json.dumps({"block_len": bl, "sig_s": sig_s,
                          "match_s": match_s, "md5_s": md5_s,
                          "candidates": int(total), "rows": G,
                          "cand_cap": geo.cand_cap,
                          "sig_cap": len(sw)}), flush=True)

    # the match scan's stages apart, at the longest table
    R = W // 1024
    x = jnp.asarray(host).reshape(R, 1024).astype(jnp.uint32)
    prefix = jax.jit(lambda v: delta._flat_prefix(v))
    q = jax.device_put(rng.integers(0, 1 << 32, W, dtype=np.uint32))
    swd = jax.device_put(sw)
    stages = {"prefix_s": timed(prefix, x)}
    for method in ("scan", "scan_unrolled", "sort"):
        f = jax.jit(lambda t, v, m=method: jnp.searchsorted(t, v, method=m))
        stages[f"searchsorted_{method}_s"] = timed(f, swd, q, n=2)
    hit = jax.device_put(rng.random(W) < 1e-4)
    nz = jax.jit(lambda h: jnp.nonzero(h, size=geo.cand_cap,
                                       fill_value=W)[0])
    stages["nonzero_s"] = timed(nz, hit)
    print(json.dumps(stages), flush=True)
    print(json.dumps({"peak_bytes": (dev0.memory_stats() or {}).get(
        "peak_bytes_in_use")}))
    return 0


def insertions() -> int:
    import jax

    from volsync_tpu.engine import deltasync
    from volsync_tpu.obs import counter_totals, reset_spans, span_totals

    dev0 = jax.devices()[0]
    print(json.dumps({"platform": dev0.platform, "kind": dev0.device_kind}))
    rng = np.random.default_rng(7)
    mib = int(sys.argv[sys.argv.index("--mib") + 1]) \
        if "--mib" in sys.argv else 60  # a rehearsal on the CPU: --mib 3
    size, third = mib << 20, (mib // 3) << 20
    base = rng.bytes(size)
    zeroed = base[:third] + bytes(third) + base[2 * third:]

    def inserted(n: int) -> bytes:
        out, at = [], 0
        for cut in sorted(rng.integers(0, size, n).tolist()):
            out += [base[at: cut], rng.bytes(int(rng.integers(1, 900)))]
            at = cut
        return b"".join(out + [base[at:]])

    def rewritten() -> bytes:
        out = bytearray(base)
        for page in rng.choice(size // 16384, size // 16384 // 100,
                               replace=False).tolist():
            out[page * 16384: (page + 1) * 16384] = rng.bytes(16384)
        return bytes(out)

    cases = [("unchanged", base, base), ("pages_1pct", rewritten(), base)]
    upto = int(sys.argv[sys.argv.index("--upto") + 1]) \
        if "--upto" in sys.argv else 1000
    cases += [(f"insertions_{n}", inserted(n), base)
              for n in (1, 10, 100, 1000) if n <= upto]
    cases.append(("zeros_moved_by_one",
                  zeroed[: third - 5000] + b"\x01" + zeroed[third - 5000:],
                  zeroed))
    sigs = {}
    for warm in (True, False):  # the first round loads the programs
        for name, src, old in cases[:3] if warm else cases:
            block_len = deltasync.pick_block_len(len(src))
            key = (id(old), block_len)
            if key not in sigs:
                sigs[key] = deltasync.build_file_signature(old, block_len)
            reset_spans()
            before = counter_totals()
            t0 = time.perf_counter()
            ops = deltasync.scan_ranges([(src, sigs[key])])[0]
            took = time.perf_counter() - t0
            now, spans = counter_totals(), span_totals()
            if warm:
                continue
            print(json.dumps({
                "case": name, "scan_s": took, "block_len": block_len,
                "literal_bytes": deltasync.literal_bytes(ops),
                "ops": len(ops),
                "probes_and_searches": spans.get("delta.launch", (0, 0))[0],
                "strong_checks": spans.get("delta.verify", (0, 0))[0],
                **{k: now.get(k, 0) - before.get(k, 0) for k in (
                    "delta.batches", "delta.reprobes", "delta.candidates",
                    "delta.overflow_retries")},
                **{k + "_s": spans.get(k, (0, 0))[1] for k in (
                    "delta.stage", "delta.launch", "delta.fetch",
                    "delta.verify", "delta.select")}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(insertions() if "--insertions" in sys.argv[1:] else main())
