"""Busy seconds of the named spans (summed over threads, host clock)
per GiB the window moved."""


def read(args: dict, obs: dict):
    found = [obs["spans"][s][1] for s in args["spans"] if s in obs["spans"]]
    if not found or not obs["gib_moved"]:
        return None
    return sum(found) / obs["gib_moved"]
