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
search, strong checks, pieces probed again, the rows listed and looked
up, literal bytes.

    python scripts/profile_delta.py --insertions

What a search costs by the rows it is given (``--split``):
``delta_match_rows`` at the mover's window for blocks of 4, 8 and 16 KiB
with 64, 700, 2,048 and a quarter of the window's rows listed; at 8 KiB
its operations' device times under the compiler's names from a trace,
with the compiled text written to ``chiprun_out/profile_delta/`` to
name them by.

    python scripts/profile_delta.py --split
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
        G = 2048  # rows listed: a thirty-second of the window
        rows, until = _listed(geo, G, geo.rows)
        args = (dev, jax.device_put(sw), np.int32(len(table)),
                jax.device_put(rows), jax.device_put(until),
                np.int32(-(-G // geo.group_rows)), np.int32(0), np.int32(0))
        kw = dict(window=bl, group_rows=geo.group_rows,
                  max_candidates=geo.cand_cap, capacity=geo.search_cap)
        match_s = timed(delta.delta_match_rows, *args, **kw)
        cand, _w, state = delta.delta_match_rows(*args, **kw)
        total = np.asarray(state)[0]
        starts = jax.device_put(np.minimum(
            np.asarray(cand)[: geo.cand_cap], W - bl))
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
                    "delta.overflow_retries", "delta.search_rows",
                    "delta.search_rows_run")},
                **{k + "_s": spans.get(k, (0, 0))[1] for k in (
                    "delta.stage", "delta.launch", "delta.fetch",
                    "delta.verify", "delta.select")}}), flush=True)
    return 0


def _listed(geo, n_rows: int, cap: int):
    """``n_rows`` rows spread over the window in runs of a block's rows
    (what the engine lists: open blocks), at a capacity of ``cap``."""
    W, per = geo.window, geo.rows // geo.blocks
    n_rows = min(n_rows, cap)  # a rehearsal's small window
    runs = -(-n_rows // per)
    starts = np.arange(runs, dtype=np.int64) * (geo.rows // runs)
    rows = (starts[:, None] + np.arange(per)[None, :]).reshape(-1)[:n_rows]
    take = np.full(cap, rows[0], np.int32)
    take[:n_rows] = rows
    until = np.zeros(cap, np.int32)
    until[:n_rows] = W - geo.block_len + 1
    return take, until


def _device_ops(fn, runs: int = 3, top: int = 14) -> dict:
    """Device seconds a run of each operation of the programs ``fn``
    runs, by the compiler's names, from a profiler trace."""
    import tempfile

    import jax

    from benchmark import trace_reduce

    fn()
    with tempfile.TemporaryDirectory() as tmp:
        with jax.profiler.trace(tmp):
            for _ in range(runs):
                fn()
        path = next(Path(tmp).rglob("*.xplane.pb"))
        planes = trace_reduce.load(str(path))
    ops: dict = {}
    for name, lines in planes.items():
        if not name.startswith("/device:"):
            continue
        for ev, _s, d in lines.get(trace_reduce.OPS_LINE, []):
            ops[ev] = ops.get(ev, 0.0) + d / 1e9 / runs
    return dict(sorted(ops.items(), key=lambda kv: -kv[1])[:top])


def split() -> int:
    import jax

    from volsync_tpu.engine import deltasync
    from volsync_tpu.ops import delta

    # the rows of a group of the search's loop: the engine's own, or
    # ``--groups 128,512`` to time others
    group_sizes = [int(g) for g in sys.argv[
        sys.argv.index("--groups") + 1].split(",")] \
        if "--groups" in sys.argv else None
    dev0 = jax.devices()[0]
    print(json.dumps({"platform": dev0.platform, "kind": dev0.device_kind}))
    out_dir = Path("chiprun_out/profile_delta")
    out_dir.mkdir(parents=True, exist_ok=True)
    rng = np.random.default_rng(7)
    W = deltasync.WINDOW
    dev = jax.device_put(np.frombuffer(rng.bytes(W), np.uint8))
    for bl in (4096, 8192, 16384):
        geo = deltasync._Geometry.of(bl)
        weak, _strong = delta.delta_sig_flat(dev, block_len=bl)
        table = np.sort(np.asarray(weak))
        sw = np.full(geo.sig_cap(max(len(table), 16384)), 0xFFFFFFFF,
                     np.uint32)
        sw[: len(table)] = table
        sw_dev, n_sig = jax.device_put(sw), np.int32(len(table))
        line = {"block_len": bl, "sig_cap": len(sw),
                "cand_cap": geo.cand_cap}
        for group_rows in group_sizes or [geo.group_rows]:
            for n_rows in (64, 700, 2048, geo.rows // 4):
                take, until = _listed(geo, n_rows, geo.rows)
                groups = -(-n_rows // group_rows)
                args = (dev, sw_dev, n_sig, jax.device_put(take),
                        jax.device_put(until), np.int32(groups),
                        np.int32(0), np.int32(0))
                kw = dict(window=bl, group_rows=group_rows,
                          max_candidates=geo.cand_cap,
                          capacity=geo.search_cap)
                key = f"group_{group_rows}_rows_{n_rows}"
                line[key + "_s"] = timed(delta.delta_match_rows,
                                         *args, **kw)
                state = delta.delta_match_rows(*args, **kw)[2]  # lint: ignore[VL502] one timed case a turn
                line[key + "_taken_next_ran"] = np.asarray(state).tolist()
        print(json.dumps(line), flush=True)
        if bl != 8192:
            continue
        (out_dir / "program.txt").write_text(delta.delta_match_rows.lower(
            *args, **kw).compile().as_text())
        print(json.dumps({"rows": geo.rows // 4, "device_ops_s": _device_ops(
            lambda: jax.block_until_ready(delta.delta_match_rows(
                *args, **kw)))}), flush=True)
    print(json.dumps({"peak_bytes": (dev0.memory_stats() or {}).get(
        "peak_bytes_in_use")}))
    return 0


if __name__ == "__main__":
    argv = sys.argv[1:]
    sys.exit(insertions() if "--insertions" in argv
             else split() if "--split" in argv else main())
