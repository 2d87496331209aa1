"""1 - (union of the device-op intervals / traced window), in percent."""


def read(args: dict, obs: dict):
    tr = obs.get("trace")
    if not tr or not tr["devices"] or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
