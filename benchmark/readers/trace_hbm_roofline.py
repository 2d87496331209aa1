"""A program's share of its HBM roofline, in percent: the least time
the chip could take to read once the bytes the program was handed
(copy-ledger sites, counted over the traced window) at the device's
peak HBM bandwidth, over the device time of the named programs in the
trace. The bound is named: HBM bytes. (SHA-256 and the gear scan are
integer VPU work, for which the chip has no published peak.)"""


def read(args: dict, obs: dict):
    tr = obs.get("trace")
    if not tr:
        return None
    secs = sum(s for name, s in tr["programs"].items()
               if any(p in name for p in args["programs"]))
    nbytes = sum(obs["copies"].get(site, 0) for site in args["byte_sites"])
    if not secs or not nbytes:
        return None
    return 100.0 * (nbytes / obs["peaks"]["hbm_bytes_per_s"]) / secs
