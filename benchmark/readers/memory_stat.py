"""``device.memory_stats()[key]``, the largest over the local devices."""


def read(args: dict, obs: dict):
    return obs["memory"].get(args["key"])
