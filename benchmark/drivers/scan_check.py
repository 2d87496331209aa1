"""A repository that held a history before a run's backups, held to the
configuration's guarantees, as a process that holds no chip and keeps
nothing of the driver's: the history and every state of the file are
made again from the seed (``scanstate.py``). One job (a JSON line on
stdin), one answer (a JSON line on stdout): counts (each 0 where the
guarantee holds), attempted, failed, the first errors, and notes for the
run's output. Every job opens the repository afresh.

The repository's snapshots, oldest first, are the history's and then one
an operation, operation 0 being the first backup of the volume.

modes:

- ``index``: the plain reference ``reference/dedupscan.py`` over the
  history's ids and states 0 to ``operations`` - 1, whose counts go back
  to the driver (``reference``: what each operation had to add, find and
  meet in the index); while a state's bytes are at hand, every blob the
  reference says that operation added is read back and compared with the
  bytes it was cut from (each stored, under its id; a flipped bit in any
  pack a run wrote is met here). Then the end: the index a fresh open
  loaded holds exactly the history's ids, every operation's new ones and
  the snapshots' trees (``index_ids_missing``, ``index_ids_extra``), a
  sample of the history's blobs drawn from the seed reads back to its
  bytes, and ``check()`` is empty.
- ``snapshot``: operation k's snapshot names the one before as its
  parent, as does every other; its tree holds the one file at its size;
  the file's content list is the reference's ids for state k, and each
  blob reads back to the bytes the reference cut for it: byte for byte,
  cut where ``gearcdc`` cuts, under ``blobid``'s ids.
"""

from __future__ import annotations

import json
import sys

from benchmark import mover, scanstate
from benchmark.drivers.backup_check import snapshot_files
from benchmark.reference import dedupscan
from benchmark.reference.increment import file_blobs


def _states(job: dict, numbers):
    return scanstate.file_states(job["shape"], job["fresh"], job["seed"],
                                 numbers)


def _same(repo, bid: str, want, out: dict, where) -> bool:
    """Blob ``bid`` reads back to the bytes ``want``."""
    try:
        got = repo.read_blob(bid)
    except Exception as ex:  # noqa: BLE001 — counted, reported
        out["counts"]["read_errors"] += 1
        out["errors"].append({"read_error": bid, "at": where,
                              "error": repr(ex)[:200]})
        return False
    return got == bytes(want)


def trees(repo, snaps) -> set:
    """The id of every tree blob the listed snapshots name."""
    out, stack = set(), [man["tree"] for _, man in snaps]
    while stack:
        tid = stack.pop()
        if tid not in out:
            out.add(tid)
            stack += [e["subtree"]
                      for e in json.loads(repo.read_blob(tid))["entries"]
                      if e["type"] == "dir"]
    return out


def index(job: dict, repo, out: dict) -> None:
    n = out["counts"]
    n.update(dict.fromkeys((
        "new_blob_mismatch", "index_ids_missing", "index_ids_extra",
        "history_sample_mismatch", "check_problems", "snapshots_listed_off",
        "read_errors"), 0))
    count, nbytes = job["index_blobs"], job["history_blob_bytes"]
    raw, ids = scanstate.history_blobs(job["seed"], count, nbytes)

    def added(k: int, data, op: dict) -> None:
        off, seen = 0, set()
        for bid, length in zip(op["ids"], op["lengths"]):
            if bid in op["new"] and bid not in seen:  # its first sight
                seen.add(bid)
                if not _same(repo, bid, data[off: off + length], out, k):
                    n["new_blob_mismatch"] += 1
            off += length

    ops, held = dedupscan.scan(ids, _states(job, range(job["operations"])),
                               job["chunker"], each=added)
    new = sum(op["blobs_new"] for op in ops)
    out["reference"] = [{k: op[k] for k in (
        "blobs_new", "bytes_new", "blobs_dedup", "hits_earlier",
        "hits_inside", "held_before")} for op in ops]
    snaps = repo.list_snapshots()
    n["snapshots_listed_off"] = abs(len(snaps) - 1 - job["operations"])
    want = held | trees(repo, snaps)
    have = repo.blob_ids()
    n["index_ids_missing"] = len(want - have)
    n["index_ids_extra"] = len(have - want)
    sample = scanstate.history_sample(job["seed"], count, job["sample"])
    for j in sample:
        if not _same(repo, ids[j], raw[j * nbytes: (j + 1) * nbytes], out,
                     "history"):
            n["history_sample_mismatch"] += 1
    problems = repo.check()
    n["check_problems"] = len(problems)
    out["errors"] += [{"check_problem": p[:200]} for p in problems[:3]]
    out["notes"] = {"index_ids": len(have), "history_sampled": len(sample),
                    "new_blobs_read_back": new}
    out["attempted"] = len(sample) + new
    out["failed"] = min(out["attempted"], sum(n.values()))


def snapshot(job: dict, repo, out: dict) -> None:
    k = job["operation"]
    n = out["counts"]
    n.update(dict.fromkeys((
        "parent_chain_breaks", "files_missing", "blob_id_mismatches",
        "chunk_boundary_mismatches", "content_mismatch", "read_errors",
        "files_read_back"), 0))
    rel, half, _ = scanstate.layout(job["shape"], job["fresh"])
    out["attempted"] = 1
    snaps = repo.list_snapshots()
    n["parent_chain_breaks"] = sum(
        snaps[j][1].get("parent") != snaps[j - 1][0]
        for j in range(1, len(snaps)))
    entry = snapshot_files(repo, snaps[k + 1][1]["tree"]).get(rel) \
        if k + 1 < len(snaps) else None
    if entry is None or entry["size"] != 2 * half:
        n["files_missing"] = out["failed"] = 1
        return
    (data,) = _states(job, [k])
    blobs = file_blobs(data, job["chunker"])
    n["blob_id_mismatches"] = int(entry["content"]
                                  != [bid for bid, _ in blobs])
    n["files_read_back"] = 1
    off, read = 0, {}
    for bid, length in blobs:
        if bid not in read:
            try:
                read[bid] = repo.read_blob(bid)
            except Exception as ex:  # noqa: BLE001 — counted, reported
                n["read_errors"] += 1
                out["errors"].append({"read_error": bid, "operation": k,
                                      "error": repr(ex)[:200]})
                read[bid] = b""
        got = read[bid]
        if len(got) != length:
            n["chunk_boundary_mismatches"] += 1
        elif got != bytes(data[off: off + length]):
            n["content_mismatch"] += 1
        off += length
    out["notes"] = {"snapshot_of_operation": k, "blobs": len(blobs),
                    "blobs_fetched": len(read)}
    out["failed"] = int(any(v for name, v in n.items()
                            if name != "files_read_back"))


def check(job: dict) -> dict:
    out = {"counts": {}, "attempted": 0, "failed": 0, "errors": [],
           "notes": None}
    repo = mover.open_repo(job["env"])
    try:
        {"index": index, "snapshot": snapshot}[job["mode"]](job, repo, out)
    except Exception as ex:  # noqa: BLE001 — a tree that does not read
        out["counts"]["check_aborted"] = 1
        out["errors"].insert(0, {"check_aborted": job["mode"],
                                 "error": repr(ex)[:300]})
        out["attempted"] = max(1, out["attempted"])
        out["failed"] = out["attempted"]
    out["errors"] = out["errors"][:5]
    return out


if __name__ == "__main__":
    print(json.dumps(check(json.loads(sys.stdin.readline()))), flush=True)
