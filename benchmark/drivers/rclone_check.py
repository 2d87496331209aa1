"""The rclone mover's mirror held to its guarantees, as a process that
holds no chip, by ``reference/mirror.py`` and ``reference/treecmp.py``
(``os``, ``json`` and ``hashlib``).

stdin, one line: the job (JSON). ``mode`` ``objects``: ``tree`` ->
``{"objects": [...]}``, the checksums a correct mirror of the tree
stores. ``mode`` ``mirror``: the bucket the mover's ``env`` names (a
fresh listing and a fresh read of the index, through the S3 client and
``json`` alone) against ``tree``, the objects named in ``uploaded`` and
every eighth other read back and hashed to their names, and the synced
volume ``dest`` against ``tree``; ``other`` is {relative path: the
SHA-256 the file has in the state the destination held before}, so a
synced file still in those bytes is counted twice: a content mismatch,
and ``stale_files``. stdout, one line: the counts, the entries
compared, the entries that failed, the first of them.
"""

from __future__ import annotations

import json
import sys

from benchmark.reference import mirror, treecmp
from benchmark.reference.blobid import blob_id


def check_mirror(job: dict) -> dict:
    from volsync_tpu.objstore import open_store

    env = job["env"]
    store = open_store(env["RESTIC_REPOSITORY"], env=env)
    listing = sorted(key.rsplit("/", 1)[-1] for key in store.list("objects"))
    got = mirror.compare_bucket(listing, mirror.parse_index(store.get),
                                job["tree"])
    sample = sorted((set(job["uploaded"]) | set(listing[::8]))
                    & set(listing))
    unreadable = [name for name in sample
                  if blob_id(store.get(f"objects/{name}")) != name]
    tree = treecmp.compare(job["tree"], job["dest"])
    stale = [rel for rel in tree["content"]
             if tree["digests"].get(rel) == job["other"].get(rel)]
    n = {"index_missing": len(got["index_missing"]),
         "index_extra": len(got["index_extra"]),
         "index_stale": len(got["index_stale"]),
         "index_meta": len(got["index_meta"]),
         "object_content_mismatch": len(unreadable),
         "files_missing": len(tree["missing"]),
         "files_extra": len(tree["extra"]),
         "size_mismatch": len(tree["size"]),
         "content_mismatch": len(tree["content"]),
         "meta_mismatch": len(tree["meta"]),
         "stale_files": len(stale)}
    bad = sorted(set().union(
        got["index_missing"], got["index_extra"], got["index_stale"],
        got["index_meta"], unreadable, tree["missing"], tree["extra"],
        tree["size"], tree["content"], tree["meta"]))
    return {"counts": n, "failed": len(bad), "first": bad[:5],
            "compared": tree["compared"] + len(sample),
            "files_compared": tree["compared"],
            "objects_read": len(sample)}


def check(job: dict) -> dict:
    if job["mode"] == "objects":
        return {"objects": sorted(mirror.expected_objects(job["tree"]))}
    return check_mirror(job)


if __name__ == "__main__":
    print(json.dumps(check(json.loads(sys.stdin.readline()))), flush=True)
