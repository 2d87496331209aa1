"""One restore operation held to its guarantee, as a process that holds
no chip: the restored tree against the source volume as it stood at the
newest snapshot, by ``reference/treecmp.py`` (``os`` and ``hashlib``
alone), never against the repository.

stdin, one line: the job (JSON): source, restored (the two roots),
first_state ({relative path: SHA-256 the file had at the FIRST
snapshot}, for the files changed since). stdout, one line: the counts,
the entries compared, the entries that failed, the first of them. A
restored file that carries its first state is counted twice: a content
mismatch, and ``stale_files`` (the restore took the older snapshot or
an older blob). One process an operation, side by side.
"""

from __future__ import annotations

import json
import sys

from benchmark.reference import treecmp


def check(job: dict) -> dict:
    got = treecmp.compare(job["source"], job["restored"])
    stale = [rel for rel in got["content"]
             if got["digests"].get(rel) == job["first_state"].get(rel)]
    n = {"files_missing": len(got["missing"]),
         "files_extra": len(got["extra"]),
         "size_mismatch": len(got["size"]),
         "content_mismatch": len(got["content"]),
         "meta_mismatch": len(got["meta"]),
         "stale_files": len(stale)}
    bad = sorted(set(got["missing"]) | set(got["extra"]) | set(got["size"])
                 | set(got["content"]) | set(got["meta"]))
    return {"counts": n, "compared": got["compared"], "failed": len(bad),
            "first": bad[:5]}


if __name__ == "__main__":
    print(json.dumps(check(json.loads(sys.stdin.readline()))), flush=True)
