"""What one scheduled backup must add to a repository with a history,
from ``hashlib``, numpy and this directory's ``gearcdc`` and ``blobid``
alone (nothing of the program).

A state is ``{relative path: (size, mtime_ns)}`` of a volume's regular
files. Between two states a sync

- takes from its parent every file whose size and mtime are its parent
  entry's (restic's rule; the file is not opened),
- records a file that is new and empty without reading it,
- reads every other file whole and stores it the format's way: one blob
  at or under the chunker's ``min_size``, else the chunks the reference
  chunker cuts, each under the id of ``blobid.blob_id``,

and adds to the repository those blobs of the files it read that the
repository's index did not hold: each id once, at its first sight.
``held`` is that index as far as it matters: the data-blob ids of every
snapshot taken since the last prune (a forget removes a snapshot, not a
blob).
"""

from __future__ import annotations

from pathlib import Path

from benchmark.reference import blobid, gearcdc


def split(before: dict, after: dict) -> tuple[list[str], list[str]]:
    """(files taken from the parent, files read), each sorted."""
    unchanged, read = [], []
    for rel, meta in after.items():
        if rel in before and tuple(before[rel]) == tuple(meta):
            unchanged.append(rel)
        elif meta[0]:
            read.append(rel)
    return sorted(unchanged), sorted(read)


def file_blobs(data, chunker: dict) -> list[tuple[str, int]]:
    """[(blob id, length)] of one file's content, in order."""
    if len(data) <= int(chunker["min_size"]):
        return [(blobid.blob_id(data), len(data))] if len(data) else []
    view = memoryview(data)
    return [(blobid.blob_id(view[off: off + n]), n)
            for off, n in gearcdc.cuts(data, chunker)]


def increment(root, before: dict, after: dict, held, chunker: dict) -> dict:
    """One sync of the volume under ``root`` (in state ``after``) onto a
    parent in state ``before``: ``unchanged``, ``read``, ``files``
    ({path: [(id, length)]} of the files read), ``new`` ({id: length} of
    the blobs the sync adds, in the order it meets them) and
    ``bytes_read``."""
    unchanged, read = split(before, after)
    files, new, nbytes = {}, {}, 0
    for rel in read:
        data = (Path(root) / rel).read_bytes()
        nbytes += len(data)
        files[rel] = file_blobs(data, chunker)
        for bid, n in files[rel]:
            if bid not in held and bid not in new:
                new[bid] = n
    return {"unchanged": unchanged, "read": read, "files": files,
            "new": new, "bytes_read": nbytes}
