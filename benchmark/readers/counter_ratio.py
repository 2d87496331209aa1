"""A ratio of the program's counters (``volsync_tpu.obs.count``), read
when the metric is read: the sum of ``numerator`` over the sum of
``denominator``, times ``scale`` (1 where not given). Without a
``denominator`` the value is the numerator's sum itself, and 0 where
nothing was counted. A program without counters reads nothing.

``run.py`` snapshots only the span totals and the copy ledger at the
window's end; the counters are zeroed with the spans at window start and
nothing counts after the window, so reading them here is the same
reading (``tests/test_tracing_metrics.py`` holds it to that)."""


def read(args: dict, obs: dict):
    from volsync_tpu import obs as program

    totals = getattr(program, "counter_totals", None)
    if totals is None:
        return None
    counts = totals()
    top = sum(counts.get(name, 0) for name in args["numerator"])
    if "denominator" not in args:
        return top
    bottom = sum(counts.get(name, 0) for name in args["denominator"])
    if not bottom:
        return None
    return args.get("scale", 1) * top / bottom
