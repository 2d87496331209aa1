"""Host time of the restore's write stage, split: the hole scan against
the open + seek + write it guards.

``restore.write`` (engine/restorepipe.py ``flush_batch``) is, a blob:
find the blob's holes, then write at each placement; up to PR 45 a
placement opened the target and sought (since PR 46 one ``pwrite`` on
a held descriptor: ``--files`` below). This script times those pieces
apart on the host it runs on, behind that open + seek, 256 MiB of
blobs a size, seconds a GiB:

- ``historical``: the writer up to PR 32, kept here (and as the oracle
  of tests/test_zerocopy.py): ``np.flatnonzero`` over the blob's bytes,
  an int64 index of every non-zero byte, then ``np.diff`` and ``max``;
- ``checkout``: this checkout's ``engine/restore._write_sparse``;
- ``plain``: open + seek + ``f.write`` alone.

and the scans alone, no file: ``scan_index`` (the historical one),
``scan_u64_any`` (``.any(axis=1)`` over a ``uint64`` view of the
pages), ``scan_u8_or`` (``np.bitwise_or.reduce`` over the ``uint8``
pages, what ``_sparse_runs`` does).

Blobs are random (no hole, as the benchmark's volumes) at 512 KiB, 1,
2 and 8 MiB, and one 2 MiB shape with holes (every fourth 64 KiB zero).
Runs on the host alone, no JAX; not part of the test suite.

``--files N`` times something else and nothing of the above: what the
pipeline's system calls cost a restored file. N one-blob files
(log-uniform 1 KiB-1 MiB, 50 a directory, as ``restic-dest-10g``'s
small files) are put down into fresh directories twice over: by the
call sequence of engine/restorepipe.py up to PR 45, kept here
(``files_before``: four ``stat``s that find nothing, a truncating open
to claim, a second open + seek + write, a third open + truncate,
``chown`` / ``chmod`` / ``utime`` by path; ~25 calls), and by the
sequence since PR 46 (``files_after``: an exclusive create, ``pwrite``,
``fchown`` / ``fchmod`` / ``futimens``, close; 6 calls), the same bytes
both ways; and one ``lstat`` a file beside them, as a host's price of
one call. Milliseconds a file.

Usage: python scripts/profile_restore_write.py [--mib 256] [--reps 3]
           [--dir DIR] [--out chiprun_out/profile_restore_write.json]
       python scripts/profile_restore_write.py --files 1000 [--reps 5]
           [--dir DIR] [--out ...]
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from volsync_tpu.engine.restore import _write_sparse  # noqa: E402

GIB = float(1 << 30)


def historical_write_sparse(f, data) -> None:
    """``_write_sparse`` as it stood up to PR 32."""
    view = memoryview(data).cast("B")
    n = len(view)
    if n == 0:
        f.write(view)
        return
    arr = np.frombuffer(view, np.uint8)
    nz = np.flatnonzero(arr)
    if nz.size == 0:
        if n < 4096:
            f.write(view)
        else:
            f.seek(n, os.SEEK_CUR)
        return
    gaps = np.diff(nz) - 1
    longest = max(int(nz[0]), int(n - 1 - nz[-1]),
                  int(gaps.max()) if gaps.size else 0)
    if longest < 4096:
        f.write(view)
        return
    full = n // 4096
    zero_pages = np.logical_not(
        arr[:full * 4096].reshape(full, 4096).any(axis=1))
    bounds = np.flatnonzero(np.diff(zero_pages)) + 1
    starts = np.concatenate(([0], bounds))
    ends = np.concatenate((bounds, [full]))
    for s, e in zip(starts, ends):
        if zero_pages[s]:
            f.seek((e - s) * 4096, os.SEEK_CUR)
        else:
            f.write(view[s * 4096:e * 4096])
    if full * 4096 < n:
        f.write(view[full * 4096:])


def plain_write(f, data) -> None:
    f.write(data)


def scan_index(arr: np.ndarray):
    nz = np.flatnonzero(arr)
    return (np.diff(nz) - 1).max() if nz.size > 1 else 0


def scan_u64_any(arr: np.ndarray):
    full = arr.size // 4096
    return arr[:full * 4096].view(np.uint64).reshape(full, 512).any(axis=1)


def scan_u8_or(arr: np.ndarray):
    full = arr.size // 4096
    return np.bitwise_or.reduce(
        arr[:full * 4096].reshape(full, 4096), axis=1) != 0


WRITERS = (("historical", historical_write_sparse),
           ("checkout", _write_sparse),
           ("plain", plain_write))
SCANS = (("scan_index", scan_index),
         ("scan_u64_any", scan_u64_any),
         ("scan_u8_or", scan_u8_or))


def make_blobs(shape: str, size: int, total: int, seed: int) -> list:
    """``total`` bytes of blobs, each its own object (a restore's
    blobs come fresh from the decoder, never the same memory twice)."""
    rng = np.random.RandomState(seed)
    blobs = []
    for _ in range(total // size):
        blob = bytearray(rng.bytes(size))
        if shape == "holes":
            for off in range(0, size, 4 * 65536):
                blob[off:off + 65536] = bytes(65536)
        blobs.append(bytes(blob))
    return blobs


def time_writer(write, blobs: list, target: str) -> float:
    """One file claimed, then every blob put down at its offset behind
    an open + seek of its own, as the pipeline did up to PR 45;
    seconds."""
    with open(target, "wb"):
        pass
    offset = 0
    t0 = time.perf_counter()
    for blob in blobs:
        with open(target, "r+b") as f:
            f.seek(offset)
            write(f, blob)
        offset += len(blob)
    dt = time.perf_counter() - t0
    os.unlink(target)
    return dt


def time_scan(scan, blobs: list) -> float:
    t0 = time.perf_counter()
    for blob in blobs:
        scan(np.frombuffer(blob, np.uint8))
    return time.perf_counter() - t0


def file_sizes(n: int, seed: int = 2) -> list:
    rng = np.random.RandomState(seed)
    return np.exp(rng.uniform(np.log(1 << 10), np.log(1 << 20),
                              n)).astype(np.int64).tolist()


def file_paths(root: str, n: int) -> list:
    """N targets, 50 a fresh directory under ``root``."""
    paths = []
    for i in range(n):
        if i % 50 == 0:
            os.mkdir(os.path.join(root, f"d{i // 50:03d}"))
        paths.append(Path(root, f"d{i // 50:03d}", f"f{i:05d}.bin"))
    return paths


def files_before(paths: list, sizes: list, buf, mtime_ns: int) -> None:
    """A one-blob file into an empty directory as the pipeline put it
    down up to PR 45: ``_plan`` (``_skip_unchanged``, ``_clear_target``,
    the claim), ``_write_at``, ``_finish_file`` + ``_finalize_file``."""
    for target, size in zip(paths, sizes):
        if target.is_file():  # _skip_unchanged: ENOENT
            raise RuntimeError("the directory was to be empty")
        if target.is_symlink() or target.is_dir():  # _clear_target
            raise RuntimeError("the directory was to be empty")
        elif target.exists():
            raise RuntimeError("the directory was to be empty")
        with open(target, "wb"):  # the claim
            pass
        with open(target, "r+b") as f:  # _write_at
            f.seek(0)
            f.write(buf[:size])
        with open(target, "r+b") as f:  # _finish_file
            f.truncate(size)
        os.chown(target, 0, 0, follow_symlinks=False)  # _finalize_file
        os.chmod(target, 0o644)
        os.utime(target, ns=(mtime_ns, mtime_ns))


def files_after(paths: list, sizes: list, buf, mtime_ns: int) -> None:
    """The same file since PR 46: created at its first write, written
    and stamped through that descriptor."""
    flags = os.O_WRONLY | os.O_CREAT | os.O_EXCL | os.O_CLOEXEC
    for target, size in zip(paths, sizes):
        fd = os.open(target, flags, 0o600)
        try:
            data, at = buf[:size], 0
            while len(data):
                n = os.pwrite(fd, data, at)
                data, at = data[n:], at + n
            os.chown(fd, 0, 0)
            os.chmod(fd, 0o644)
            os.utime(fd, ns=(mtime_ns, mtime_ns))
        finally:
            os.close(fd)


def profile_files(args) -> list:
    """Two lines: the cell's sizes, then one byte a file (the calls
    with next to nothing between them)."""
    buf = memoryview(np.random.RandomState(1).bytes(1 << 20))
    lines = []
    for payload, sizes in (("1KiB-1MiB", file_sizes(args.files)),
                           ("1B", [1] * args.files)):
        samples = {"before": [], "after": [], "lstat": []}
        for rep in range(args.reps):  # interleaved, as the blob timings
            for name, put in (("before", files_before),
                              ("after", files_after)):
                with tempfile.TemporaryDirectory(dir=args.dir) as work:
                    paths = file_paths(work, args.files)
                    t0 = time.perf_counter()
                    put(paths, sizes, buf, 1_600_000_000 * 10**9 + rep)
                    samples[name].append(time.perf_counter() - t0)
                    if name == "after":
                        t0 = time.perf_counter()
                        for target in paths:
                            os.lstat(target)
                        samples["lstat"].append(time.perf_counter() - t0)
        line = {"files": args.files, "payload": payload,
                "bytes": sum(sizes), "reps": args.reps, "unit": "ms/file",
                "calls_before": 25, "calls_after": 6}
        for name, vals in samples.items():
            line[name] = round(1e3 * statistics.median(vals) / args.files, 5)
            line[name + "_min"] = round(1e3 * min(vals) / args.files, 5)
        # what each of the 19 calls that went cost, beside a path's lstat
        line["saved_us_per_call"] = round(
            1e3 * (line["before"] - line["after"]) / 19, 2)
        print(json.dumps(line), flush=True)
        lines.append(line)
    return lines


def write_out(path, lines: list) -> None:
    if path:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as fh:
            for line in lines:
                fh.write(json.dumps(line) + "\n")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--files", type=int, default=0,
                    help="time the calls of N small restored files, "
                         "before PR 46 against since, and nothing else")
    ap.add_argument("--mib", type=int, default=256,
                    help="MiB of blobs a size")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--dir", default=None,
                    help="where the files go (default: a fresh "
                         "directory under the temporary directory)")
    ap.add_argument("--out", default=None, help="also write the lines here")
    args = ap.parse_args()
    if args.files:
        write_out(args.out, profile_files(args))
        return 0
    total = args.mib << 20
    lines = []
    with tempfile.TemporaryDirectory(dir=args.dir) as work:
        target = os.path.join(work, "blob.bin")
        shapes = [("random", s << 10) for s in (512, 1024, 2048, 8192)]
        shapes.append(("holes", 2048 << 10))
        for seed, (shape, size) in enumerate(shapes):
            blobs = make_blobs(shape, size, total, seed)
            gib = len(blobs) * size / GIB
            line = {"shape": shape, "blob_bytes": size,
                    "blobs": len(blobs), "unit": "s/GiB"}
            samples = {name: [] for name, _ in WRITERS + SCANS}
            for _ in range(args.reps):  # interleaved: a drifting
                # page cache lands on every column alike
                for name, write in WRITERS:
                    samples[name].append(
                        time_writer(write, blobs, target) / gib)
                for name, scan in SCANS:
                    samples[name].append(time_scan(scan, blobs) / gib)
            for name, vals in samples.items():
                line[name] = round(statistics.median(vals), 4)
                line[name + "_min"] = round(min(vals), 4)
            print(json.dumps(line), flush=True)
            lines.append(line)
    write_out(args.out, lines)
    return 0


if __name__ == "__main__":
    sys.exit(main())
