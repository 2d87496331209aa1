"""The sum of the named counters of the run's CHILD processes (the
movers of a fleet cell return their ``counter_totals()`` with their
answer; the driver sums them into ``obs["mover_counters"]``), per GiB
the window moved; 0 where none of them was counted. A cell whose driver
gathers no child counters reads nothing."""


def read(args: dict, obs: dict):
    counts = obs.get("mover_counters")
    if counts is None or not obs["gib_moved"]:
        return None
    return sum(counts.get(n, 0) for n in args["counters"]) / obs["gib_moved"]
