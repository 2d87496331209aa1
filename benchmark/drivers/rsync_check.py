"""The rsync mover's pushes held to their guarantees, as a process that
holds no chip, by ``reference/rsyncdelta.py`` and
``reference/treecmp.py`` (``os``, ``numpy`` and ``hashlib``).

stdin, one line: the job (JSON). ``mode`` ``delta``: ``source`` and
``dest`` trees -> what one push of the first onto the second has to do
by the plain reference (``rsyncdelta.tree_delta``: literal bytes, files
new and with a basis, entries pruned, the bytes a sender has to look
at), and the seconds it took. ``mode`` ``tree``: the destination
``dest`` against the source state ``source`` entry by entry, and the
temporaries (``.*.volsync-part``) left in it. stdout, one line.
"""

from __future__ import annotations

import json
import os
import sys
import time

from benchmark.reference import rsyncdelta, treecmp


def check_tree(job: dict) -> dict:
    tree = treecmp.compare(job["source"], job["dest"])
    left = [rel for rel in treecmp.entries(job["dest"])
            if os.path.basename(rel).endswith(".volsync-part")]
    n = {"files_missing": len(tree["missing"]),
         "files_extra": len(tree["extra"]),
         "size_mismatch": len(tree["size"]),
         "content_mismatch": len(tree["content"]),
         "meta_mismatch": len(tree["meta"]),
         "temporaries_left": len(left)}
    bad = sorted(set().union(tree["missing"], tree["extra"], tree["size"],
                             tree["content"], tree["meta"], left))
    return {"counts": n, "failed": len(bad), "first": bad[:5],
            "compared": tree["compared"]}


def check(job: dict) -> dict:
    if job["mode"] == "delta":
        t0 = time.monotonic()
        out = rsyncdelta.tree_delta(job["source"], job["dest"])
        out.pop("by_file")
        return {**out, "seconds": round(time.monotonic() - t0, 3)}
    return check_tree(job)


if __name__ == "__main__":
    print(json.dumps(check(json.loads(sys.stdin.readline()))), flush=True)
