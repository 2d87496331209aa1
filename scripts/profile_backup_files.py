"""Host price of getting a small file's bytes into the backup, a file:
the two ways ``TreeBackup`` read one up to PR 48 against the one reader
it has since PR 49, in system calls and in milliseconds on the host
this runs on.

``smallfiles.backup`` hashes 7,013 files a GiB on one thread, every one
at most 1 MiB. A system call costs 0.13-0.16 ms on the chip's host and
a twentieth of that in the sandbox (PERF.md section 6, PR 38), so run
this where the cell runs. It writes a volume of the cell's size law
(``benchmark/configs/smallfiles.json``'s shape, ``--files`` of it) and
times, over every file of a kind and with no device in it:

- ``host.historical``: ``Path.read_bytes()`` and the closing
  ``Path.lstat()`` (files at or under the chunker's ``min_size``);
- ``host.direct``: ``engine/directread.py`` ``read_small``;
- ``device.historical``: a file over ``min_size`` as PR 48 read it into
  its one segment: ``_open_readahead`` (the native ``ReadaheadReader``),
  a ``_SegmentReadahead`` thread over the stream's ``_SegmentFill``, the
  one segment taken from its queue, thread joined, reader closed,
  ``Path.lstat()``;
- ``device.direct``: ``DirectReader`` and the same ``_SegmentFill`` run
  on this thread (``_SegmentInline``), the reader closed.

``ms_a_file`` is the median over ``--reps`` interleaved passes.
``calls_a_file`` of the ``direct`` rows is counted (every call of that
reader goes through ``os``); the ``historical`` rows make theirs in C,
where only the kernel counts them: ``reads_a_file`` is its count of
read calls (``/proc/self/io`` ``syscr``), for all four rows.

No device is touched and JAX is never asked for a backend; not part of
the test suite and on no cell's path.

Usage: python scripts/profile_backup_files.py [--files 2000] [--reps 5]
           [--seed 1] [--dir DIR]
           [--out chiprun_out/profile_backup_files.json]
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import statistics
import sys
import tempfile
import time
from pathlib import Path

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, ROOT)
from benchmark import volumes  # noqa: E402
from volsync_tpu.engine import bufpool, chunker  # noqa: E402
from volsync_tpu.engine.directread import DirectReader, read_small  # noqa: E402
from volsync_tpu.io import available as native_available  # noqa: E402

SEGMENT = 32 * 1024 * 1024  # stream_chunk_batches' and _open_stream's


def host_historical(path: Path, size: int) -> int:
    data = path.read_bytes()
    path.lstat()
    return len(data)


def host_direct(path: Path, size: int) -> int:
    data, _ = read_small(path, size)
    return len(data)


def _one_segment(src, size: int) -> int:
    buf, fill, eof = src.next_segment()
    bufpool.GLOBAL.release(buf)
    assert eof and fill - src.head == size
    return size


def device_historical(path: Path, size: int, params) -> int:
    with chunker._open_readahead(path, SEGMENT) as reader:
        ahead = chunker._SegmentReadahead(
            chunker._segment_source(reader.read, params, SEGMENT, None,
                                    size), 2)
        try:
            _one_segment(ahead, size)
        finally:
            ahead.close()
    path.lstat()
    return size


def device_direct(path: Path, size: int, params) -> int:
    with DirectReader(path) as reader:
        _one_segment(chunker._SegmentInline(chunker._segment_source(
            reader.read, params, SEGMENT, None, size)), size)
    return size


def reads_so_far() -> int:
    with open("/proc/self/io") as fh:
        return int(dict(line.split(": ") for line in fh)["syscr"])


def count_calls(fn, files: list) -> dict:
    """Calls a file that go through ``os``, by name (a pass of its own:
    the wrappers are not in the timed passes)."""
    names = ("open", "read", "readv", "fstat", "close", "lstat", "stat")
    real = {name: getattr(os, name) for name in names}
    seen: collections.Counter = collections.Counter()

    def counting(name):
        def call(*args, **kwargs):
            seen[name] += 1
            return real[name](*args, **kwargs)
        return call

    for name in names:
        setattr(os, name, counting(name))
    try:
        for args in files:
            fn(*args)
    finally:
        for name in names:
            setattr(os, name, real[name])
    return {name: round(n / len(files), 3) for name, n in seen.items()}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--files", type=int, default=2000)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--dir", default=None,
                    help="where the volume goes (default: a fresh "
                         "directory under the temporary directory)")
    ap.add_argument("--out", default=None, help="also write the lines here")
    args = ap.parse_args()
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "smallfiles.json")) as fh:
        config = json.load(fh)
    shape = config["shape"]
    shape["small"]["count"] = args.files
    params = chunker.params_from_config(config["chunker"])
    lines = []
    with tempfile.TemporaryDirectory(dir=args.dir) as work:
        root = Path(work) / "v"
        made = volumes.write(root, shape, args.seed)
        files = [(root / rel, n) for rel, n in sorted(made.items())]
        host = [f for f in files if f[1] <= params.min_size]
        device = [(p, n, params) for p, n in files if n > params.min_size]
        head = {"files": len(files), "bytes": sum(made.values()),
                "host_path_files": len(host),
                "device_path_files": len(device), "reps": args.reps,
                "native_readahead": native_available()}
        print(json.dumps(head), flush=True)
        lines.append(head)
        stages = {"host.historical": (host_historical, host),
                  "host.direct": (host_direct, host),
                  "device.historical": (device_historical, device),
                  "device.direct": (device_direct, device)}
        ms: dict = collections.defaultdict(list)
        reads: dict = {}
        for _ in range(args.reps):  # interleaved: a drifting page cache
            # lands on every row alike
            for name, (fn, over) in stages.items():
                r0, t0 = reads_so_far(), time.perf_counter()
                for call in over:
                    fn(*call)
                ms[name].append(
                    (time.perf_counter() - t0) / len(over) * 1e3)
                reads[name] = (reads_so_far() - r0 - 1) / len(over)
        for name, (fn, over) in stages.items():
            line = {"stage": name, "files": len(over),
                    "ms_a_file": round(statistics.median(ms[name]), 4),
                    "ms_a_file_min": round(min(ms[name]), 4),
                    "reads_a_file": round(reads[name], 3)}
            if name.endswith(".direct"):
                line["calls_a_file"] = count_calls(fn, over)
            print(json.dumps(line), flush=True)
            lines.append(line)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as fh:
            for line in lines:
                fh.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
