"""The benchmark's own span around each call into the program's entry
(wall seconds of one thread), per GiB the window moved."""


def read(args: dict, obs: dict):
    ops = obs["ops"]
    if not ops or not obs["gib_moved"] or "t_start" not in ops[0]:
        return None
    return sum(op["t_done"] - op["t_start"] for op in ops) / obs["gib_moved"]
