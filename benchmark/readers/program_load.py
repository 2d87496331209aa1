"""What loading device programs cost the process from its start to the
read (``volsync_tpu.compile_cache.load_totals()``): the sum of the named
``keys`` (``load_s``: the time that passed while a program was traced,
lowered, compiled or read back from the cache; ``compiles`` and
``cache_hits``: the programs). Set-up loads every program a cell can
meet, so this is set-up's, as long as ``compiles_in_window`` reads 0. A
program that keeps no such totals reads nothing."""


def read(args: dict, obs: dict):
    from volsync_tpu import compile_cache

    totals = getattr(compile_cache, "load_totals", None)
    if totals is None:
        return None
    loaded = totals()
    return sum(loaded[key] for key in args["keys"])
