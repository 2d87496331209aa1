"""One backup operation held to its guarantee, as a process that holds
no chip: a fresh ``Repository.open`` on the store lists exactly one
snapshot, every file of the volume is in its tree with its size,
``check()`` is empty, and (``deep``) every file reads back: each blob id
equals the hashlib reference over the stored bytes, the chunks laid end
to end are the source file, cut where the reference chunker cuts it.

stdin, one line: the job (JSON): env (the mover's), root (the volume),
files ({relative path: bytes}), chunker, deep. stdout, one line: the
counts, the files read back, the files that failed, the first errors.
One process an operation, side by side: the reference hashes 4 KiB
leaves one ``hashlib`` call each, which threads of one process would
spend handing the interpreter lock to and fro.
"""

from __future__ import annotations

import hashlib
import json
import sys
from pathlib import Path

from benchmark import mover
from benchmark.reference import blobid as ref
from benchmark.reference import gearcdc


def snapshot_files(repo, tree_id: str) -> dict:
    """{relative path: file entry} of one snapshot tree."""
    out = {}
    stack = [("", tree_id)]
    while stack:
        base, tid = stack.pop()
        for e in json.loads(repo.read_blob(tid))["entries"]:
            rel = f"{base}{e['name']}"
            if e["type"] == "dir":
                stack.append((rel + "/", e["subtree"]))
            elif e["type"] == "file":
                out[rel] = e
    return out


def read_back(repo, entry, source: Path, chunker: dict) -> tuple[int, int, int]:
    """(blob ids that differ from the hashlib reference over the stored
    bytes, 1 if the chunks laid end to end are not the source file, 1 if
    they are not cut where the reference chunker cuts the source)."""
    bad_ids = 0
    whole = hashlib.sha256()
    lengths = []
    for bid in entry["content"]:
        data = repo.read_blob(bid)
        if ref.blob_id(data) != bid:
            bad_ids += 1
        whole.update(data)
        lengths.append(len(data))
    src = source.read_bytes()
    return (bad_ids,
            int(whole.digest() != hashlib.sha256(src).digest()),
            int(lengths != [n for _, n in gearcdc.cuts(src, chunker)]))


def check(job: dict) -> dict:
    n = {"snapshots_wrong": 0, "files_missing": 0, "check_problems": 0,
         "blob_id_mismatches": 0, "file_sha_mismatches": 0,
         "chunk_boundary_mismatches": 0, "read_errors": 0}
    files, root = job["files"], Path(job["root"])
    out = {"counts": n, "read_back": 0, "failed": 0, "errors": []}
    repo = mover.open_repo(job["env"])
    snaps = repo.list_snapshots()
    if len(snaps) != 1:
        n["snapshots_wrong"] = 1
        out["failed"] = len(files)
        return out
    entries = snapshot_files(repo, snaps[0][1]["tree"])
    bad = {rel for rel, size in files.items()
           if rel not in entries or entries[rel]["size"] != size}
    n["files_missing"] = len(bad)
    problems = repo.check()
    n["check_problems"] = len(problems)
    for rel in sorted(files) if job["deep"] else ():
        if rel in bad:
            continue
        out["read_back"] += 1
        try:
            ids, sha, cut = read_back(repo, entries[rel], root / rel,
                                      job["chunker"])
        except Exception as ex:  # noqa: BLE001 — counted, reported
            n["read_errors"] += 1
            out["errors"].append({"read_error": rel,
                                  "error": repr(ex)[:200]})
            bad.add(rel)
            continue
        n["blob_id_mismatches"] += ids
        n["file_sha_mismatches"] += sha
        n["chunk_boundary_mismatches"] += cut
        if ids or sha or cut:
            bad.add(rel)
    out["failed"] = len(files) if problems else len(bad)
    out["errors"] = out["errors"][:5]
    return out


if __name__ == "__main__":
    print(json.dumps(check(json.loads(sys.stdin.readline()))), flush=True)
