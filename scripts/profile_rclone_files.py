"""Host time of the rclone mover's per-file work, split: what a system
call costs on the host this runs on, and what a pass costs before and
after the scan's record carried it (PR 38).

A sync cycle of ``rclone-smallfiles.sync`` scans 8,000 entries, reads
8,116 files and settles the metadata of 3,960 on the entry's one
thread. This script writes the cell's volume (``benchmark/configs/
rclone-smallfiles.json``'s shape, less a seeded 1% of its paths: 3,960
files in 40 directories, log-uniform 1 KiB-1 MiB, ~581 MiB) and times,
on the host alone:

- ``calls``: microseconds a call of ``lstat``, ``listxattr``, ``open`` +
  ``close``, ``open`` + ``readv`` + ``close`` (the file's bytes into a
  buffer that is already faulted in), ``chown``, ``chmod``, ``utime``,
  each over every file, by string path, setting what is already there;
- ``passes``: seconds a pass over the tree, ``historical`` (the mover
  up to PR 34, kept here: ``os.walk`` + ``pathlib`` + ``lstat`` for the
  scan; a ``stat``, a ``Path`` and ``open(buffering=0)`` a file for the
  hash pass's loop; ``listxattr`` + ``chown`` + ``chmod`` + ``utime`` on
  every file for the metadata pass) against ``checkout`` (this
  checkout's ``scan_tree``, ``hash_files`` with the scan's sizes,
  ``_settle_meta`` from the scan's records, one file in seventeen
  treated as fetched: every call). Both hash passes run the real
  stager (``stage_page_aligned``: a fresh zeroed bucket a batch, its
  page faults under the reads) with the device's call stubbed out, so
  ``hash`` is the pass's host side whole, and ``read`` the seconds its
  ``rclone.read`` spans recorded.

No device is touched, and JAX is imported but never asked for a
backend; not part of the test suite.

Usage: python scripts/profile_rclone_files.py [--reps 3] [--seed 1]
           [--dir DIR] [--out chiprun_out/profile_rclone_files.json]
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

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, ROOT)
from benchmark import volumes  # noqa: E402
from volsync_tpu.engine.restore import (  # noqa: E402
    _apply_owner,
    _apply_xattrs,
)
from volsync_tpu.movers.rclone import sync  # noqa: E402
from volsync_tpu.obs import reset_spans, span, span_totals  # noqa: E402

FETCHED_EVERY = 17  # ~6% of the files, the cell's share a cycle


def historical_scan_tree(root: Path, *, collect_meta: bool = True) -> dict:
    """``scan_tree`` as it stood up to PR 34 (the regular-file and
    directory arms: the volume has nothing else)."""
    from volsync_tpu.engine.backup import _read_xattrs

    def meta(st, p):
        if not collect_meta:
            return {}
        return {"uid": st.st_uid, "gid": st.st_gid,
                "xattrs": _read_xattrs(p)}

    entries = {}
    root = Path(root)
    root_dev = root.stat().st_dev
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames.sort()
        filenames.sort()
        d = Path(dirpath)
        rel_dir = d.relative_to(root).as_posix()
        if rel_dir != ".":
            st = d.lstat()
            entries[rel_dir] = {"type": "dir", "mode": st.st_mode & 0o7777,
                                "mtime_ns": st.st_mtime_ns, **meta(st, d)}
        for name in filenames:
            p = d / name
            st = p.lstat()
            if st.st_dev != root_dev:
                continue
            rel = p.relative_to(root).as_posix()
            entries[rel] = {"type": "file", "size": st.st_size,
                            "mode": st.st_mode & 0o7777,
                            "mtime_ns": st.st_mtime_ns, **meta(st, p)}
        for name in list(dirnames):
            if (d / name).is_symlink():
                dirnames.remove(name)
    return entries


def historical_hash_files(root: Path, rels: list) -> None:
    """``hash_files``' host side as it stood up to PR 34: a ``stat`` and
    a ``Path`` a file, then ``open(buffering=0)`` + ``readinto`` into
    the stager's slot."""
    batch = []
    batch_bytes = 0

    def read_file(i, slot):
        rel, n = batch[i]
        view = memoryview(slot)
        got = 0
        with open(root / rel, "rb", buffering=0) as f:
            while got < n:
                k = f.readinto(view[got:])
                if not k:
                    break
                got += k
        assert got == n

    def flush():
        nonlocal batch, batch_bytes
        if batch:
            sync.stage_page_aligned([n for _, n in batch], read_file,
                                    filling=lambda: span("rclone.read"))
        batch, batch_bytes = [], 0

    for rel in rels:
        n = (root / rel).stat().st_size
        batch.append((rel, n))
        batch_bytes += n
        if batch_bytes >= sync._BATCH_BYTES:
            flush()
    flush()


def historical_apply_meta(root: Path, files: dict) -> None:
    for rel, entry in files.items():
        p = root / rel
        _apply_xattrs(p, entry)
        _apply_owner(p, entry)
        os.chmod(p, entry["mode"])
        os.utime(p, ns=(entry["mtime_ns"], entry["mtime_ns"]))


def checkout_apply_meta(root: Path, files: dict, local: dict) -> int:
    base = os.path.join(os.fspath(root), "")
    kept = 0
    for i, (rel, entry) in enumerate(files.items()):
        kept += sync._settle_meta(
            base + rel, entry, None if i % FETCHED_EVERY == 0 else local[rel])
    return kept


def timed(fn, *args, **kwargs):
    reset_spans()
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    dt = time.perf_counter() - t0
    return dt, span_totals().get("rclone.read", (0, 0.0))[1], out


def time_calls(paths: list, sizes: list) -> dict:
    """Microseconds a call, each over every file."""
    buf = np.zeros((max(sizes),), np.uint8)
    buf[:] = 1  # faulted in: the read below pays for the copy alone
    sts = [os.lstat(p) for p in paths]

    def open_close(p):
        os.close(os.open(p, os.O_RDONLY))

    def open_read_close(p, n):
        fd = os.open(p, os.O_RDONLY)
        os.readv(fd, [buf[:n]])
        os.close(fd)

    loops = {
        "lstat": lambda: [os.lstat(p) for p in paths],
        "listxattr": lambda: [os.listxattr(p, follow_symlinks=False)
                              for p in paths],
        "open_close": lambda: [open_close(p) for p in paths],
        "open_readv_close": lambda: [open_read_close(p, n)
                                     for p, n in zip(paths, sizes)],
        "chown": lambda: [os.chown(p, st.st_uid, st.st_gid,
                                   follow_symlinks=False)
                          for p, st in zip(paths, sts)],
        "chmod": lambda: [os.chmod(p, st.st_mode & 0o7777)
                          for p, st in zip(paths, sts)],
        "utime": lambda: [os.utime(p, ns=(st.st_mtime_ns, st.st_mtime_ns))
                          for p, st in zip(paths, sts)],
    }
    out = {}
    for name, loop in loops.items():
        t0 = time.perf_counter()
        loop()
        out[name] = (time.perf_counter() - t0) / len(paths) * 1e6
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--dir", default=None,
                    help="where the volume goes (default: a fresh "
                         "directory under the temporary directory)")
    ap.add_argument("--out", default=None, help="also write the lines here")
    args = ap.parse_args()
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "rclone-smallfiles.json")) as fh:
        shape = json.load(fh)["shape"]
    # the device's call: the digests are not what is timed here
    sync.hash_spans = lambda staging, spans: ["0" * 64] * len(spans)
    lines = []
    with tempfile.TemporaryDirectory(dir=args.dir) as work:
        root = Path(work) / "v"
        made = volumes.write(root, shape, args.seed)
        rels = sorted(made)
        drop = np.random.default_rng(args.seed).permutation(len(rels))
        for i in drop[: len(rels) // 100].tolist():
            (root / rels[i]).unlink()
        index = sync.scan_tree(root)  # what a source's sync would record
        files = {r: e for r, e in index.items() if e["type"] == "file"}
        rels = list(files)
        sizes = [files[r]["size"] for r in rels]
        paths = [os.path.join(root, r) for r in rels]
        head = {"files": len(rels), "entries": len(index),
                "bytes": sum(sizes), "reps": args.reps}
        print(json.dumps(head), flush=True)
        lines.append(head)

        calls = [time_calls(paths, sizes) for _ in range(args.reps)]
        line = {"calls_us": {name: round(statistics.median(
            c[name] for c in calls), 3) for name in calls[0]}}
        print(json.dumps(line), flush=True)
        lines.append(line)

        samples: dict = {}
        meta_kept = None
        for _ in range(args.reps):  # interleaved: a drifting page
            # cache lands on both columns alike
            local = sync.scan_tree(root, collect_meta=False)
            runs = {
                "scan_source.historical": (historical_scan_tree, root),
                "scan_source.checkout": (sync.scan_tree, root),
                "scan_dest.historical": (
                    lambda: historical_scan_tree(root, collect_meta=False),),
                "scan_dest.checkout": (
                    lambda: sync.scan_tree(root, collect_meta=False),),
                "hash.historical": (historical_hash_files, root, rels),
                "hash.checkout": (sync.hash_files, root, rels, sizes),
                "meta.historical": (historical_apply_meta, root, files),
                "meta.checkout": (checkout_apply_meta, root, files, local),
            }
            for name, (fn, *fn_args) in runs.items():
                dt, read_s, out = timed(fn, *fn_args)
                samples.setdefault(name, []).append(dt)
                if name.startswith("hash."):
                    samples.setdefault(
                        name.replace("hash.", "read."), []).append(read_s)
                if name == "meta.checkout":
                    meta_kept = out
        line = {"passes_s": {name: round(statistics.median(vals), 4)
                             for name, vals in sorted(samples.items())},
                "passes_s_min": {name: round(min(vals), 4)
                                 for name, vals in sorted(samples.items())},
                "meta_kept": meta_kept}
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
