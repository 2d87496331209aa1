"""Busy seconds of the named spans in the run's CHILD processes (the
movers of a fleet cell, which return their ``span_totals()`` with their
answer; the driver sums them into ``obs["mover_spans"]``, and their
``span_self_totals()`` into ``obs["mover_self_spans"]``), per GiB the
window moved. With ``"self": true`` the spans' self seconds. A span no
child entered took no time: 0 (a wait that never came is a reading). A
cell whose driver gathers no child spans reads nothing."""


def read(args: dict, obs: dict):
    totals = obs.get("mover_self_spans" if args.get("self")
                     else "mover_spans")
    if not totals or not obs["gib_moved"]:
        return None
    return sum(totals[s][1] for s in args["spans"]
               if s in totals) / obs["gib_moved"]
