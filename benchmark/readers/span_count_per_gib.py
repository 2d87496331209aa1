"""How often the named spans were entered, per GiB the window moved."""


def read(args: dict, obs: dict):
    found = [obs["spans"][s][0] for s in args["spans"] if s in obs["spans"]]
    if not found or not obs["gib_moved"]:
        return None
    return sum(found) / obs["gib_moved"]
