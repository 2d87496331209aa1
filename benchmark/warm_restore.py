"""The programs a restore's verify batches run, read off one whole
restore of the same repository before the window.

A restore verifies its blobs on the device in batches
(``engine/chunker.verify_blob_batch`` -> ``hash_spans`` ->
``ops/segment.span_roots_device``), and that program is keyed by the
staging bucket of a batch and the padded number of its blobs. Which
keys a snapshot presents follows its tree, its packs and the seed's
sizes; but the plan of a restore is a function of the repository alone
(files in tree order, packs by first need, consumed in plan order, a
batch flushed at the engine's own size), so one whole restore of the
same snapshot into a scratch directory runs exactly the programs every
restore of the window will, and loads them.

The keys are read from the program's own ``verify.launch`` spans
(attrs ``bucket`` and ``lanes``) in its flight recorder: nothing of the
engine's is a constant here or in a cell's file. A program that
records no such span (the parent of the PR that brought this file) is
warmed all the same and has no plan to print. ``compiles_in_window``
says when a restore of the window met a program this one did not.
"""

from __future__ import annotations


def programs_of(restore) -> tuple[list[tuple[int, int]] | None, int]:
    """Calls ``restore()`` (one whole restore through the mover's entry)
    under a sampled trace. Returns the sorted (bucket, lanes) keys of
    the verify program it launched (None where the program records
    none) and what ``restore()`` returned."""
    from volsync_tpu.obs import reset_trace, trace_context, trace_events

    reset_trace()
    with trace_context(sampled=True):
        rc = restore()
    keys = {(int(e["args"]["bucket"]), int(e["args"]["lanes"]))
            for e in trace_events()
            if e.get("name") == "verify.launch" and "bucket" in e["args"]}
    return sorted(keys) or None, rc
