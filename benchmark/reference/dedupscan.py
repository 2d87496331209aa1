"""What a sequence of backups of one file must add to a repository that
already holds a history, from ``hashlib``, numpy, a Python ``set`` and
this directory's ``gearcdc`` and ``blobid`` alone (nothing of the
program).

The repository is, as far as a backup's dedup can tell, the set of blob
ids it holds: the history's, then whatever each operation added. An
operation reads the file whole (its mtime moved: restic's size-and-mtime
rule cannot take it from the parent), stores it the format's way (the
chunks the reference chunker cuts, each under ``blobid.blob_id``; one
blob at or under ``min_size``) and adds those of its blobs the
repository does not hold, each id once, at its first sight. A chunk the
repository held when the operation began is a hit on an earlier entry; a
chunk whose id the operation itself added further up the file is a hit
inside the operation. One mover, one process: the program's advisory
dedup has nothing to race, so these counts are exact.
"""

from __future__ import annotations

from benchmark.reference.increment import file_blobs


def operation(held: set, blobs: list[tuple[str, int]]) -> dict:
    """One backup of a file whose content is ``blobs`` ([(id, length)],
    in order) into a repository holding ``held``: ``ids`` (in order),
    ``new`` ({id: length}, in the order met), ``blobs_new``,
    ``bytes_new``, ``hits_earlier``, ``hits_inside`` and ``blobs_dedup``
    (the two kinds of hit together)."""
    new: dict[str, int] = {}
    earlier = inside = 0
    for bid, n in blobs:
        if bid in held:
            earlier += 1
        elif bid in new:
            inside += 1
        else:
            new[bid] = n
    return {"ids": [bid for bid, _ in blobs],
            "lengths": [n for _, n in blobs], "new": new,
            "blobs_new": len(new), "bytes_new": sum(new.values()),
            "hits_earlier": earlier, "hits_inside": inside,
            "blobs_dedup": earlier + inside, "held_before": len(held)}


def scan(history_ids, states, chunker: dict, each=None
         ) -> tuple[list[dict], set]:
    """Every operation of a run, in order, and the data-blob ids a fresh
    open must hold at its end. ``states`` yields the file's bytes as
    each operation meets them (any buffer; one is held at a time, and
    ``each(number, bytes, operation)`` is called while it is)."""
    held = set(history_ids)
    ops = []
    for data in states:
        op = operation(held, file_blobs(data, chunker))
        if each is not None:
            each(len(ops), data, op)
        held.update(op["new"])
        ops.append(op)
    return ops, held
