#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that volsync-tpu still runs on the chip.

One process, one chip, the entry points a user calls, at the defaults a
user gets:

  1. backup -> incremental backup -> restore of a seeded ~2 GiB volume
     through the restic mover entry (movers/restic/entry.py) into a
     filesystem repository, restored tree byte-identical;
  2. the mover-jax service (service/server.py) answering Info, one
     ChunkStream and one HashSpans batch over localhost gRPC;
  3. one golden run of each other kernel users reach — rsync delta,
     rclone span roots / MD5, Reed-Solomon 4+2 — against its plain
     host reference;
  4. proof that the Mosaic kernels are in the compiled segment
     programs (``tpu_custom_call``), not an XLA stand-in.

``--chips 4`` runs ONLY the four-chip phase: the same 1 GiB file backed
up with VOLSYNC_ENGINE=mesh and with the single-chip engine, snapshots
compared id for id.

Each phase prints one JSON line (seconds, bytes, programs compiled and
the seconds that took, peak device memory). The last line is
``{"ok": true, "device": {...}}`` — printed only if every phase passed.
Any failure propagates: non-zero exit, reason on stderr. Without a TPU
the script exits non-zero before any phase runs. It never sets
JAX_PLATFORMS and refuses to run under VOLSYNC_* overrides.
"""

from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
import os
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path

import numpy as np

from volsync_tpu import compile_cache

MiB = 1 << 20
KiB = 1 << 10


class SmokeFailure(Exception):
    """A comparison differed or a precondition does not hold."""


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Every size a phase uses. FULL is what the chip runs; the CPU
    rehearsal test (tests/test_chip_smoke.py) passes tiny ones through
    the same code."""

    big: int              # the one large file, 50% redundant
    dedup_min: int        # bytes of it the first backup must not store
    mids: tuple           # a handful of mid-size files
    n_small: int          # many small files, log-uniform sizes
    small_lo: int
    small_hi: int
    append: int           # bytes appended to the large file by the churn
    stream: int           # service ChunkStream payload
    delta: int            # rsync delta file
    spans: int            # rclone span-root batch: number of packed files
    rs_pack: int          # Reed-Solomon pack body
    mesh_file: int        # --chips 4: the sharded file


FULL = Sizes(
    big=1024 * MiB, dedup_min=256 * MiB,
    mids=(64 * MiB, 96 * MiB, 128 * MiB, 192 * MiB, 256 * MiB),
    n_small=2000, small_lo=1 * KiB, small_hi=1 * MiB,
    append=8 * MiB,
    stream=256 * MiB,
    delta=256 * MiB,
    spans=192,
    rs_pack=16 * MiB,
    mesh_file=1024 * MiB,
)


# -- observations ------------------------------------------------------------

class Observer:
    """Process-wide counters the phase lines are cut from: backend
    compiles (count, seconds) and persistent-cache hits."""

    def __init__(self):
        self.compiles = 0
        self.compile_seconds = 0.0
        self.cache_hits = 0
        self._lock = threading.Lock()

    def install(self) -> None:
        import jax.monitoring as mon

        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.compiles += 1
                self.compile_seconds += duration

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1

    def snapshot(self) -> tuple[int, float, int]:
        with self._lock:
            return self.compiles, self.compile_seconds, self.cache_hits


OBS = Observer()


def peak_device_bytes():
    import jax

    stats = jax.devices()[0].memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


class phase:
    """``with phase("name") as out:`` — times the block, attributes the
    compiles that happened inside it, prints one JSON line on success.
    An exception passes straight through (no line, no exit 0)."""

    def __init__(self, name: str):
        self.name = name
        self.out: dict = {}

    def __enter__(self) -> dict:
        self._t0 = time.perf_counter()
        self._c0 = OBS.snapshot()
        return self.out

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc_type is not None:
            return False
        c1 = OBS.snapshot()
        line = {"phase": self.name,
                "seconds": round(time.perf_counter() - self._t0, 3)}
        line.update(self.out)
        line.update({
            "compiles": c1[0] - self._c0[0],
            "compile_seconds": round(c1[1] - self._c0[1], 3),
            "compile_cache_hits": c1[2] - self._c0[2],
            "peak_device_bytes": peak_device_bytes(),
        })
        print(json.dumps(line), flush=True)
        return False


# -- preconditions -----------------------------------------------------------

def refuse_overrides(environ=None) -> None:
    """The smoke proves the defaults users get. Any VOLSYNC_* variable
    in the environment could steer a phase off the device path
    (batching, pipelines, verify, engine, workers...)."""
    environ = os.environ if environ is None else environ
    forced = sorted(k for k in environ if k.startswith("VOLSYNC_"))
    check(not forced, f"VOLSYNC_* overrides set: {forced}; unset them")


def require_tpu(chips: int) -> dict:
    """The device block of the last line; fails off a TPU."""
    import jax

    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"no accelerator: jax.devices()[0].platform is "
          f"{devs[0].platform!r}, not 'tpu'")
    check(len(devs) == chips,
          f"expected {chips} device(s), jax reports {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def native_state() -> str:
    """Did native/volio load (built on demand with g++), or is the
    Python fallback in use?"""
    root = Path(__file__).resolve().parent
    prebuilt = (root / "native" / "build" / "libvolio.so").exists()
    from volsync_tpu.io import available

    if not available():
        return "python-fallback"
    return "loaded-prebuilt" if prebuilt else "built-on-demand"


# -- seeded volume -----------------------------------------------------------

def _rand(rng: np.random.Generator, n: int) -> bytes:
    return rng.bytes(n)


def make_volume(root: Path, seed: int, sizes: Sizes) -> int:
    """One large 50%-redundant file (second half repeats the first, so
    dedup has exactly half of it to find), a handful of mid-size files,
    many small ones with log-uniform sizes. Returns bytes written."""
    rng = np.random.default_rng(seed)
    root.mkdir(parents=True)
    total = 0
    half = sizes.big // 2
    uniq = _rand(rng, half)
    with open(root / "big.bin", "wb") as f:
        f.write(uniq)
        f.write(uniq[: sizes.big - half])
    del uniq
    total += sizes.big
    (root / "mid").mkdir()
    for i, n in enumerate(sizes.mids):
        (root / "mid" / f"m{i:02d}.bin").write_bytes(_rand(rng, n))
        total += n
    lo, hi = np.log(sizes.small_lo), np.log(sizes.small_hi)
    small = np.exp(rng.uniform(lo, hi, sizes.n_small)).astype(np.int64)
    for i, n in enumerate(small.tolist()):
        d = root / "small" / f"d{i % 20:02d}"
        d.mkdir(parents=True, exist_ok=True)
        (d / f"f{i:05d}").write_bytes(_rand(rng, n))
        total += n
    return total


def churn_volume(root: Path, seed: int, sizes: Sizes) -> dict:
    """Rewrite ~5% of the files with new content, append to the large
    one."""
    rng = np.random.default_rng(seed + 1)
    files = sorted(p for p in (root / "small").rglob("*") if p.is_file())
    picks = rng.choice(len(files), max(1, len(files) // 20), replace=False)
    rewritten = 0
    for i in sorted(picks.tolist()):
        n = max(1, int(files[i].stat().st_size * rng.uniform(0.5, 1.5)))
        files[i].write_bytes(_rand(rng, n))
        rewritten += n
    with open(root / "big.bin", "ab") as f:
        f.write(_rand(rng, sizes.append))
    return {"files_rewritten": len(picks), "bytes_rewritten": rewritten,
            "bytes_appended": sizes.append}


def tree_digests(root: Path) -> dict:
    """rel path -> host SHA-256 of every regular file under ``root``."""
    out = {}
    for p in sorted(root.rglob("*")):
        if p.is_file() and not p.is_symlink():
            h = hashlib.sha256()
            with open(p, "rb") as f:
                while True:
                    piece = f.read(8 * MiB)
                    if not piece:
                        break
                    h.update(piece)
            out[str(p.relative_to(root))] = h.hexdigest()
    return out


def dir_bytes(root: Path) -> int:
    return sum(p.stat().st_size for p in root.rglob("*") if p.is_file())


# -- phase 1: backup -> incremental -> restore through the mover entry -------

def run_mover(direction: str, repo_dir: Path, data_dir: Path,
              extra_env: dict | None = None) -> None:
    """The restic mover's data-plane entry, as the Job runner calls it:
    config by env, the volume by mount."""
    from volsync_tpu.cluster.runner import JobContext
    from volsync_tpu.movers.restic.entry import restic_entrypoint

    env = {"RESTIC_REPOSITORY": str(repo_dir),
           "RESTIC_PASSWORD": "chip-smoke",
           "DIRECTION": direction, "HOSTNAME": "chip-smoke",
           **(extra_env or {})}
    ctx = JobContext(name=f"smoke-{direction}", namespace="smoke", env=env,
                     mounts={"data": Path(data_dir)}, secrets={},
                     stop_event=threading.Event())
    rc = restic_entrypoint(ctx)
    check(rc == 0, f"restic mover {direction} exited {rc}")


def open_repo(repo_dir: Path):
    from volsync_tpu.objstore import open_store
    from volsync_tpu.repo.repository import Repository

    return Repository.open(open_store(str(repo_dir)),
                           password="chip-smoke")


def check_blob_ids(repo, tree_id: str, want: int) -> int:
    """Blob ids the device computed == repo/blobid.py's host hashlib
    construction over the stored chunk bytes, for a sample of chunked
    files (first, middle and last chunk of each). That the chunks laid
    end to end ARE the file is what the byte-identical restore shows."""
    from volsync_tpu.repo import blobid

    checked = 0
    stack = [tree_id]
    while stack and checked < want:
        tree = json.loads(repo.read_blob(stack.pop()))
        for e in tree["entries"]:
            if e["type"] == "dir":
                stack.append(e["subtree"])
            elif e["type"] == "file" and len(e["content"]) > 1:
                ids = e["content"]
                for k in sorted({0, len(ids) // 2, len(ids) - 1}):
                    got = blobid.blob_id(repo.read_blob(ids[k]))
                    check(got == ids[k],
                          f"{e['name']} chunk {k}: device id {ids[k]} "
                          f"!= host id {got}")
                    checked += 1
    return checked


def phase_backup_restore(work: Path, seed: int, sizes: Sizes) -> None:
    src, repo_dir, dst = work / "src", work / "repo", work / "restored"
    with phase("make-volume") as out:
        out["bytes"] = make_volume(src, seed, sizes)
        out["files"] = sum(1 for p in src.rglob("*") if p.is_file())
        out["native_volio"] = native_state()

    with phase("backup") as out:
        run_mover("backup", repo_dir, src)
        out["bytes"] = dir_bytes(src)
        out["repo_bytes"] = first = dir_bytes(repo_dir)
        # the large file's second half repeats its first: dedup must
        # have found it (sealed random bytes do not compress)
        check(first < out["bytes"] - sizes.dedup_min,
              f"first backup stored {first} B of {out['bytes']} B: the "
              f"repeated half of big.bin was not deduplicated")

    with phase("churn") as out:
        out.update(churn_volume(src, seed, sizes))

    with phase("backup-incremental") as out:
        run_mover("backup", repo_dir, src)
        out["bytes"] = dir_bytes(src)
        out["repo_bytes_added"] = added = dir_bytes(repo_dir) - first
        check(added < first // 4,
              f"incremental stored {added} B, first stored {first} B: "
              f"not far fewer")

    with phase("restore") as out:
        dst.mkdir()
        run_mover("restore", repo_dir, dst)
        out["bytes"] = dir_bytes(dst)

    with phase("verify-restore") as out:
        want, got = tree_digests(src), tree_digests(dst)
        check(sorted(want) == sorted(got),
              "restored tree has a different file set")
        bad = [k for k in want if want[k] != got[k]]
        check(not bad, f"{len(bad)} restored files differ, e.g. {bad[:3]}")
        out["files"] = len(want)

    with phase("repo-check") as out:
        repo = open_repo(repo_dir)
        snaps = repo.list_snapshots()
        check(len(snaps) == 2, f"expected 2 snapshots, found {len(snaps)}")
        problems = repo.check(read_data=True)
        check(problems == [], f"Repository.check: {problems[:5]}")
        out["snapshots"] = len(snaps)
        out["blob_ids_checked"] = check_blob_ids(
            repo, snaps[-1][1]["tree"], want=12)
        check(out["blob_ids_checked"] >= 3, "no chunked file to sample")


# -- phase 2: the mover-jax service ------------------------------------------

def phase_service(seed: int, sizes: Sizes, platform: str) -> None:
    from volsync_tpu.repo import blobid
    from volsync_tpu.service.client import MoverJaxClient
    from volsync_tpu.service.server import MoverJaxServer

    rng = np.random.default_rng(seed + 2)
    payload = _rand(rng, sizes.stream)
    result: dict = {}

    def client(port: int, token: str) -> None:
        try:
            # the first ChunkStream compiles its buckets: minutes, cold
            with MoverJaxClient("127.0.0.1", port, token,
                                timeout=1000.0) as c:
                result["info"] = c.info()
                result["chunks"] = c.chunk_bytes(payload)
                spans, off = [], 0
                for n in (1, 4096, 5000, 70000, 300000, 1 << 20):
                    spans.append((off, n))
                    off += n + (-n % 4096)
                buf = payload[:off]
                result["spans"] = (buf, spans, c.hash_spans(buf, spans))
        except BaseException as ex:  # noqa: BLE001 — re-raised by caller
            result["error"] = ex

    with phase("service") as out:
        with MoverJaxServer() as srv:
            t = threading.Thread(target=client, args=(srv.port, srv.token),
                                 name="smoke-client")
            t.start()
            t.join()
        if "error" in result:
            raise result["error"]
        info = result["info"]
        check(info.backend == platform,
              f"service Info backend {info.backend!r} != {platform!r}")
        chunks = result["chunks"]
        pos = 0
        for off, length, digest in chunks:
            check(off == pos, f"stream chunk at {off}, expected {pos}")
            check(digest == blobid.blob_id(payload[off: off + length]),
                  f"stream chunk at {off}: digest != hashlib")
            pos += length
        check(pos == len(payload), "stream chunks do not cover the payload")
        buf, spans, digests = result["spans"]
        for (s, n), d in zip(spans, digests):
            check(d == blobid.blob_id(buf[s: s + n]),
                  f"HashSpans span {s},{n}: digest != hashlib")
        out.update({"bytes": len(payload), "backend": info.backend,
                    "stream_chunks": len(chunks), "spans": len(spans)})


# -- phase 3: the other kernels, each against its plain reference ------------

def phase_delta(seed: int, sizes: Sizes) -> None:
    """rsync delta of one file with ~1% of its blocks rewritten,
    through the mover's own staged-buffer programs (one window a
    buffer): signatures against hashlib, and the delta applied on the
    host gives the source back."""
    from volsync_tpu.engine import deltasync
    from volsync_tpu.ops.rolling import weak_checksum_host

    rng = np.random.default_rng(seed + 3)
    with phase("rsync-delta") as out:
        dest = _rand(rng, sizes.delta)
        bl = deltasync.pick_block_len(len(dest))
        nb = len(dest) // bl
        src = bytearray(dest)
        for b in rng.choice(nb, max(1, nb // 100), replace=False).tolist():
            src[b * bl: (b + 1) * bl] = _rand(rng, bl)
        src = bytes(src)
        sig = deltasync.build_file_signature(dest)
        check(sig.block_len == bl and len(sig.strong) == -(-len(dest) // bl),
              "signature geometry")
        for b in range(nb):
            check(sig.strong[b] == hashlib.md5(
                dest[b * bl: (b + 1) * bl]).digest(),
                f"signature block {b}: device MD5 != hashlib")
        for b in sorted({0, nb // 2, nb - 1}):
            check(int(sig.weak[b]) == weak_checksum_host(
                dest[b * bl: (b + 1) * bl]),
                f"signature block {b}: weak checksum != host")
        batched = deltasync.delta_scan_batch([(src, sig)])[0]
        check(deltasync.apply_delta(batched, dest, bl) == src,
              "applying the delta to the destination does not give the "
              "source")
        stats = deltasync.delta_stats(batched, bl)
        check(stats["literal_bytes"] <= len(src) // 20,
              f"delta ships {stats['literal_bytes']} literal bytes for "
              f"1% churn")
        out.update({"bytes": len(src), "block_len": bl,
                    "literal_bytes": stats["literal_bytes"]})


def phase_spans_md5(seed: int, sizes: Sizes) -> None:
    """rclone --checksum path: many whole files packed page-aligned into
    one buffer, one span-root dispatch; plus the batched MD5."""
    from volsync_tpu.engine.chunker import hash_spans
    from volsync_tpu.ops.md5 import md5_many
    from volsync_tpu.repo import blobid

    rng = np.random.default_rng(seed + 4)
    with phase("rclone-spans-md5") as out:
        lens = np.exp(rng.uniform(np.log(1), np.log(512 * KiB),
                                  sizes.spans)).astype(np.int64).tolist()
        lens[0], lens[1] = 0, 4096  # the empty file and the exact page
        spans, pieces, off = [], [], 0
        for n in lens:
            data = _rand(rng, n)
            spans.append((off, n))
            pieces += [data, bytes(-n % 4096)]
            off += n + (-n % 4096)
        buf = b"".join(pieces)
        for (s, n), d in zip(spans, hash_spans(buf, spans)):
            check(d == blobid.blob_id(buf[s: s + n]),
                  f"span {s},{n}: device root != hashlib")
        chunks = [buf[s: s + min(n, 64 * KiB)] for s, n in spans[:64]]
        for c, d in zip(chunks, md5_many(chunks)):
            check(d == hashlib.md5(c).digest(),
                  f"md5_many({len(c)} B) != hashlib")
        out.update({"bytes": len(buf), "spans": len(spans),
                    "md5_chunks": len(chunks)})


def phase_rs(seed: int, sizes: Sizes) -> None:
    """Reed-Solomon 4+2 over one pack body: parity against the NumPy
    oracle, then a reconstruct with two data shards lost."""
    from volsync_tpu.ops import rs
    from volsync_tpu.repo import erasure

    k, m = 4, 2
    rng = np.random.default_rng(seed + 5)
    with phase("rs-4+2") as out:
        body = _rand(rng, sizes.rs_pack)
        shards = erasure.encode_pack_shards([body], k, m)
        check(len(shards) == k + m, "shard count")
        slen = erasure.shard_len_for(len(body), k)
        payload = [bytes(erasure.parse_shard(s)[4]) for s in shards]
        grid = np.zeros((k, slen), np.uint8)
        flat = np.frombuffer(body, np.uint8)
        grid.reshape(-1)[: len(flat)] = flat
        want = rs.rs_encode_np(grid, m)
        for i in range(m):
            check(payload[k + i] == want[i].tobytes(),
                  f"parity shard {i}: device != NumPy oracle")
        lost = {0, 2}
        have = {i: s for i, s in enumerate(shards) if i not in lost}
        check(erasure.reconstruct_pack(have) == body,
              "reconstruct with two data shards lost != pack body")
        oracle = rs.rs_reconstruct_np(
            {i: np.frombuffer(payload[i], np.uint8)
             for i in range(k + m) if i not in lost}, k, m)
        check(oracle.reshape(-1)[: len(body)].tobytes() == body,
              "NumPy oracle reconstruct != pack body")
        out.update({"bytes": len(body), "k": k, "m": m,
                    "lost": sorted(lost)})


# -- phase 4: the kernels are in the compiled programs -----------------------

def host_gear_candidates(host: np.ndarray, p):
    """Pure-NumPy aligned gear scan -> (strict, lax) candidate cut
    positions: table value per byte, 32-byte window weighted by shifts
    31..0, mod 2^32 — the host reference for ops/gearcdc."""
    rows = host.reshape(-1, p.align)[:, -32:]
    g = p.table[rows].astype(np.uint64)
    shifts = np.arange(31, -1, -1, dtype=np.uint64)
    h = ((g << shifts[None, :]).sum(axis=1) & 0xFFFFFFFF).astype(np.uint32)
    pos = np.arange(h.shape[0], dtype=np.int64) * p.align + (p.align - 1)
    return (pos[(h & np.uint32(p.mask_s)) == 0],
            pos[(h & np.uint32(p.mask_l)) == 0])


def phase_kernel_proof(seed: int) -> None:
    """``tpu_custom_call`` in the compiled text of the fused segment
    program and of the batched program the main path dispatches, and
    the fused program's result against the host reference."""
    import jax
    import jax.numpy as jnp

    from volsync_tpu.engine.chunker import params_from_config
    from volsync_tpu.ops import segment as seg
    from volsync_tpu.ops.gearcdc import _select_boundaries_py
    from volsync_tpu.repo import blobid
    from volsync_tpu.repo.repository import DEFAULT_CHUNKER

    p = params_from_config(DEFAULT_CHUNKER)
    P = 2 * MiB
    cand_cap, chunk_cap = seg.segment_caps(P, p)
    kw = dict(min_size=p.min_size, avg_size=p.avg_size, max_size=p.max_size,
              seed=p.seed, mask_s=p.mask_s, mask_l=p.mask_l, align=p.align,
              cand_cap=cand_cap, chunk_cap=chunk_cap)
    with phase("kernel-proof") as out:
        single = seg.chunk_hash_segment.lower(
            jax.ShapeDtypeStruct((P,), jnp.uint8), np.int32(P), eof=True,
            **kw).compile()
        batched = seg.chunk_hash_segments.lower(
            jax.ShapeDtypeStruct((2 * P,), jnp.uint8),
            jax.ShapeDtypeStruct((2,), jnp.int32),
            jax.ShapeDtypeStruct((2,), jnp.bool_), **kw).compile()
        for name, c in (("chunk_hash_segment", single),
                        ("chunk_hash_segments", batched)):
            n = c.as_text().count("tpu_custom_call")
            check(n >= 2, f"{name}: {n} tpu_custom_call in the compiled "
                          f"program — the Pallas kernels are not in it")
            out[f"{name}_custom_calls"] = n
        # and the compiled fused program is right, not only present
        data = np.frombuffer(
            np.random.default_rng(seed + 6).bytes(P), np.uint8)
        chunks, consumed, _, _ = seg.decode_segment(
            np.asarray(single(jnp.asarray(data), np.int32(P))), chunk_cap)
        check(consumed == P, "fused program did not consume the segment")
        ref = _select_boundaries_py(*host_gear_candidates(data, p), P, p,
                                    eof=True)
        check([(s, n) for s, n, _ in chunks] == ref,
              "fused boundaries != host FastCDC walk")
        view = data.tobytes()
        for s, n, d in chunks:
            check(d == blobid.blob_id(view[s: s + n]),
                  f"fused blob id at {s} != hashlib")


# -- --chips 4: the mesh engine against the single-chip engine ---------------

def phase_mesh(work: Path, seed: int, sizes: Sizes, chips: int) -> None:
    import jax

    from volsync_tpu.obs import counter_totals

    check(jax.device_count() == chips,
          f"jax.device_count() is {jax.device_count()}, not {chips}")
    src = work / "mesh-src"
    rng = np.random.default_rng(seed + 7)
    with phase("mesh-make-file") as out:
        src.mkdir(parents=True)
        half = sizes.mesh_file // 2
        uniq = _rand(rng, half)
        with open(src / "big.bin", "wb") as f:
            f.write(uniq)
            f.write(uniq[: sizes.mesh_file - half])
        del uniq
        out["bytes"] = sizes.mesh_file

    with phase("mesh-backup") as out:
        before = counter_totals()
        run_mover("backup", work / "repo-mesh", src,
                  {"VOLSYNC_ENGINE": "mesh"})
        out["bytes"] = sizes.mesh_file
        # the program's own counters say what ran: segments staged onto
        # the mesh, and the devices that held a shard of each
        counts = {k: v - before.get(k, 0)
                  for k, v in counter_totals().items()}
        staged = counts.get("mesh.dispatches", 0)
        check(staged > 0, "the mesh engine was not selected")
        check(counts["mesh.shards"] == chips * staged,
              f"a staged segment has shards on "
              f"{counts['mesh.shards'] / staged:g} device(s), not {chips}")
        out.update({"mesh_dispatches": staged,
                    "shard_devices": counts["mesh.shards"] // staged,
                    "staged_useful_share": round(
                        counts["mesh.bytes_valid"]
                        / (counts["mesh.bytes_valid"]
                           + counts["mesh.bytes_padded"]), 4)})

    with phase("single-chip-backup") as out:
        run_mover("backup", work / "repo-single", src)
        out["bytes"] = sizes.mesh_file

    with phase("mesh-compare") as out:
        a, b = open_repo(work / "repo-mesh"), open_repo(work / "repo-single")
        ta = [m["tree"] for _, m in a.list_snapshots()]
        tb = [m["tree"] for _, m in b.list_snapshots()]
        check(len(ta) == 1 and ta == tb,
              f"snapshot tree ids differ: mesh {ta} single {tb}")
        ia, ib = set(a._index.copy()), set(b._index.copy())
        check(ia == ib, f"blob id sets differ: {len(ia ^ ib)} ids in one "
                        f"repository only")
        check(a.check() == [] and b.check() == [], "Repository.check")
        out.update({"tree": ta[0], "blobs": len(ia)})


# -- driver ------------------------------------------------------------------

def run(chips: int, seed: int, sizes: Sizes, work: Path) -> dict:
    cache_dir = compile_cache.configure()  # before the first use of JAX
    device = require_tpu(chips)
    OBS.install()
    print(json.dumps({"phase": "start", "device": device, "seed": seed,
                      "compile_cache_dir": cache_dir,
                      "workdir": str(work)}), flush=True)
    if chips == 4:
        phase_mesh(work, seed, sizes, chips)
    else:
        phase_backup_restore(work, seed, sizes)
        phase_service(seed, sizes, device["platform"])
        phase_delta(seed, sizes)
        phase_spans_md5(seed, sizes)
        phase_rs(seed, sizes)
        phase_kernel_proof(seed)
    c = OBS.snapshot()
    print(json.dumps({"phase": "total", "compiles": c[0],
                      "compile_seconds": round(c[1], 3),
                      "compile_cache_hits": c[2],
                      "peak_device_bytes": peak_device_bytes()}),
          flush=True)
    return device


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=20260927)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    refuse_overrides()
    work = Path(tempfile.mkdtemp(prefix="chip_smoke_"))
    try:
        device = run(args.chips, args.seed, FULL, work)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        rc = main()
    except SystemExit as ex:  # argparse
        rc = ex.code if isinstance(ex.code, int) else 2
    except BaseException as ex:  # noqa: BLE001 — every failure is fatal
        import traceback

        traceback.print_exc()
        print(f"chip_smoke: FAILED: {type(ex).__name__}: {ex}",
              file=sys.stderr, flush=True)
        rc = 1
    sys.stdout.flush()
    sys.stderr.flush()
    # daemon dispatch threads may sit in a device call; never let the
    # interpreter's exit wait on them
    os._exit(rc)
