"""A repository with a history held to its guarantees, as a process that
holds no chip. One job (a JSON line on stdin), one answer (a JSON line
on stdout): counts (each 0 where the guarantee holds), attempted,
failed, the first errors, and notes for the run's output (what the
reference made of a sync). Every job opens the repository afresh.

The job carries the run's record of the volume: ``first_state`` ({path:
[size, mtime_ns]} by ``lstat`` after the volume was written), and for
every sync the files its step of churn touched (``changed``, by
``lstat``), the SHA-256 ``churn.apply`` took of each before it touched
it (``before``), the clock around the entry call and what the program
counted. The state of the volume at sync k is ``first_state`` overlaid
with ``changed`` of syncs 1..k; the bytes a file held at sync k are
known by the SHA-256 the next step that touched it recorded, or, where
none did, by the volume as it stands.

modes:

- ``chain``: ``retain`` snapshots are listed, each taken inside the
  clock of its sync (so they are the newest), each naming the one
  before as its parent; ``check()`` is empty.
- ``sync``: snapshot k against ``reference/increment.py``: its tree
  holds state k; the files the reference says a sync must read are the
  churn's; each of them reads back to the bytes it held then, stored
  under the reference's ids at the reference's cuts; a file the
  reference takes from the parent has the parent's content list; the
  blobs the sync added (``repo.blobs_new``, ``repo.bytes_new``) are the
  reference's new set: the read files' blobs less what the retained
  snapshots before it held.
- ``content`` (after the prune): a share of the newest snapshot's files,
  each read back chunk by chunk, against the volume's SHA-256; the first
  share also that the snapshots are still ``retain`` and ``check()``
  empty, and the tree's paths and sizes against the volume's.
- ``old`` (after the prune): of the oldest retained snapshot every file
  the churn has touched since, against the SHA-256 recorded of it
  before the step that changed it.
"""

from __future__ import annotations

import hashlib
import json
import sys
from datetime import datetime
from pathlib import Path

from benchmark import mover
from benchmark.drivers.backup_check import snapshot_files
from benchmark.reference import increment
from benchmark.reference.blobid import file_sha256


def state_at(job: dict, k: int) -> dict:
    state = {rel: tuple(v) for rel, v in job["first_state"].items()}
    for s in job["syncs"][1: k + 1]:
        state.update({rel: tuple(v) for rel, v in s["changed"].items()})
    return state


def sha_at(job: dict, k: int, rel: str) -> str:
    """SHA-256 of the bytes ``rel`` held when sync k backed it up."""
    for s in job["syncs"][k + 1:]:
        if rel in s["before"]:
            return s["before"][rel]
    return file_sha256(Path(job["root"]) / rel)


def read_file(repo, entry) -> tuple[bytes, list[int]]:
    blobs = [repo.read_blob(bid) for bid in entry["content"]]
    return b"".join(blobs), [len(b) for b in blobs]


def retained(job: dict, snaps: list) -> dict:
    """{sync number: (snapshot id, manifest)} of the listed snapshots,
    the newest being the last sync's."""
    last = job["syncs"][-1]["sync"]
    return {last - j: snap for j, snap in enumerate(reversed(snaps))}


def chain(job: dict, repo, out: dict) -> None:
    n = out["counts"]
    snaps = repo.list_snapshots()
    n["snapshots_listed_off"] = abs(len(snaps) - job["retain"])
    n["parent_chain_breaks"] = sum(
        snaps[j][1].get("parent") != snaps[j - 1][0]
        for j in range(1, len(snaps)))
    n["snapshots_not_newest"] = 0
    for k, (_sid, man) in retained(job, snaps).items():
        s = job["syncs"][k] if 0 <= k < len(job["syncs"]) else None
        taken = datetime.fromisoformat(man["time"])
        if s is None or not (datetime.fromisoformat(s["began"]) <= taken
                             <= datetime.fromisoformat(s["ended"])):
            n["snapshots_not_newest"] += 1
    problems = repo.check()
    n["check_problems"] = len(problems)
    out["errors"] += [{"check_problem": p[:200]} for p in problems[:3]]
    out["attempted"] = len(snaps)
    out["failed"] = min(len(snaps), sum(n.values()))


def one_sync(job: dict, repo, out: dict) -> None:
    k = job["sync"]
    n = out["counts"]
    n.update(dict.fromkeys((
        "tree_state_off", "state_bytes_off", "read_set_off",
        "blob_id_mismatches", "chunk_boundary_mismatches",
        "unchanged_content_off", "new_blobs_missing", "new_blobs_extra",
        "new_bytes_off", "read_errors"), 0))
    snaps = retained(job, repo.list_snapshots())
    before, after = state_at(job, k - 1), state_at(job, k)
    out["attempted"] = len(after)
    if k not in snaps or k - 1 not in snaps:
        n["tree_state_off"] = out["failed"] = len(after)
        return
    entries = snapshot_files(repo, snaps[k][1]["tree"])
    parent = snapshot_files(repo, snaps[k - 1][1]["tree"])
    held = set()
    for j, (_sid, man) in snaps.items():
        if j < k:
            for e in (parent if j == k - 1
                      else snapshot_files(repo, man["tree"])).values():
                held.update(e["content"])
    bad = {rel for rel in set(after) | set(entries)
           if rel not in entries or rel not in after
           or (entries[rel]["size"], entries[rel]["mtime_ns"]) != after[rel]}
    n["tree_state_off"] = len(bad)
    # the bytes the changed files held at sync k, from the snapshot,
    # each proved by the SHA-256 taken of it on the volume
    changed = job["syncs"][k]["changed"]
    scratch = Path(job["work"]) / f"state{k:04d}"
    lengths = {}
    for rel in sorted(set(changed) - bad):
        try:
            data, lengths[rel] = read_file(repo, entries[rel])
        except Exception as ex:  # noqa: BLE001 — counted, reported
            n["read_errors"] += 1
            out["errors"].append({"read_error": rel, "sync": k,
                                  "error": repr(ex)[:200]})
            bad.add(rel)
            continue
        if hashlib.sha256(data).hexdigest() != sha_at(job, k, rel):
            n["state_bytes_off"] += 1
            bad.add(rel)
        path = scratch / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_bytes(data)
    for rel in bad & set(changed):  # the reference needs a file to read
        path = scratch / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_bytes(b"\0" * after.get(rel, (0, 0))[0])
    ref = increment.increment(scratch, before, after, held, job["chunker"])
    n["read_set_off"] = len(set(ref["read"]) ^ set(changed))
    for rel, blobs in ref["files"].items():
        if rel in bad:
            continue
        ids = [bid for bid, _ in blobs]
        off = int(ids != entries[rel]["content"])
        cut = int(lengths.get(rel) != [length for _, length in blobs])
        n["blob_id_mismatches"] += off
        n["chunk_boundary_mismatches"] += cut
        if off or cut:
            bad.add(rel)
    for rel in ref["unchanged"]:
        if rel not in bad and (rel not in parent or entries[rel]["content"]
                               != parent[rel]["content"]):
            n["unchanged_content_off"] += 1
            bad.add(rel)
    counts = job["syncs"][k]["counts"]
    new = ref["new"]
    absent = int((~repo.has_blobs(list(new))).sum()) if new else 0
    n["new_blobs_missing"] = absent + max(
        0, len(new) - counts["repo.blobs_new"])
    n["new_blobs_extra"] = max(0, counts["repo.blobs_new"] - len(new))
    n["new_bytes_off"] = abs(counts["repo.bytes_new"] - sum(new.values()))
    out["notes"] = {
        "reference_of_sync": k, "read": len(ref["read"]), "unchanged":
        len(ref["unchanged"]), "bytes_read": ref["bytes_read"],
        "blobs": sum(map(len, ref["files"].values())),
        "new_blobs": len(new), "new_bytes": sum(new.values()),
        "held": len(held)}
    out["failed"] = len(bad) + n["new_blobs_missing"] \
        + n["new_blobs_extra"] + (1 if n["new_bytes_off"] else 0) \
        + n["read_set_off"]


def _against(repo, entry, want_sha: str, out: dict, name: str,
             rel: str) -> bool:
    try:
        whole = hashlib.sha256()
        for bid in entry["content"]:
            whole.update(repo.read_blob(bid))
    except Exception as ex:  # noqa: BLE001 — counted, reported
        out["counts"]["read_errors"] += 1
        out["errors"].append({"read_error": rel, "error": repr(ex)[:200]})
        return False
    if whole.hexdigest() != want_sha:
        out["counts"][name] += 1
        return False
    return True


def content(job: dict, repo, out: dict) -> None:
    n = out["counts"]
    n.update(dict.fromkeys(("content_mismatch", "size_mismatch",
                            "files_missing", "files_extra", "read_errors",
                            "files_read_back"), 0))
    snaps = repo.list_snapshots()
    root = Path(job["root"])
    state = state_at(job, job["syncs"][-1]["sync"])
    entries = snapshot_files(repo, snaps[-1][1]["tree"])
    k, shares = job["share"]
    bad = set()
    if k == 0:
        n["snapshots_after_prune_off"] = abs(len(snaps) - job["retain"])
        problems = repo.check()
        n["check_problems_after_prune"] = len(problems)
        out["errors"] += [{"check_problem": p[:200]} for p in problems[:3]]
        n["files_missing"] = len(set(state) - set(entries))
        n["files_extra"] = len(set(entries) - set(state))
        for rel in set(state) & set(entries):
            if entries[rel]["size"] != state[rel][0]:
                n["size_mismatch"] += 1
                bad.add(rel)
    mine = sorted(set(state) & set(entries),
                  key=lambda rel: (-state[rel][0], rel))[k::shares]
    for rel in mine:
        n["files_read_back"] += 1
        if not _against(repo, entries[rel], file_sha256(root / rel), out,
                        "content_mismatch", rel):
            bad.add(rel)
    out["attempted"] = len(mine)
    out["failed"] = len(bad) + n["files_missing"] + n["files_extra"] \
        + n.get("snapshots_after_prune_off", 0) \
        + n.get("check_problems_after_prune", 0)


def old(job: dict, repo, out: dict) -> None:
    n = out["counts"]
    n.update({"old_state_mismatch": 0, "read_errors": 0})
    snaps = retained(job, repo.list_snapshots())
    oldest = min(snaps)
    entries = snapshot_files(repo, snaps[oldest][1]["tree"])
    touched = {rel for s in job["syncs"][oldest + 1:] for rel in s["before"]}
    bad = 0
    for rel in sorted(touched):
        if rel not in entries:
            n["old_state_mismatch"] += 1
            bad += 1
        elif not _against(repo, entries[rel], sha_at(job, oldest, rel), out,
                          "old_state_mismatch", rel):
            bad += 1
    out["notes"] = {"oldest_retained_sync": oldest,
                    "old_files_compared": len(touched)}
    out["attempted"], out["failed"] = len(touched), bad


def check(job: dict) -> dict:
    out = {"counts": {}, "attempted": 0, "failed": 0, "errors": [],
           "notes": None}
    repo = mover.open_repo(job["env"])
    {"chain": chain, "sync": one_sync, "content": content,
     "old": old}[job["mode"]](job, repo, out)
    out["errors"] = out["errors"][:5]
    return out


if __name__ == "__main__":
    print(json.dumps(check(json.loads(sys.stdin.readline()))), flush=True)
