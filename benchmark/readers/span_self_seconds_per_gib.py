"""Self seconds of the named spans (a span's duration less the spans
that closed inside it on the same thread; ``volsync_tpu.obs``
``span_self_totals()``, read when the metric is read) per GiB the window
moved. A program without self time reads nothing."""


def read(args: dict, obs: dict):
    from volsync_tpu import obs as program

    totals = getattr(program, "span_self_totals", None)
    if totals is None or not obs["gib_moved"]:
        return None
    own = totals()
    found = [own[s][1] for s in args["spans"] if s in own]
    if not found:
        return None
    return sum(found) / obs["gib_moved"]
