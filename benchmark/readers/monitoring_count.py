"""A ``jax.monitoring`` count between window start and end (see
``observe.CompileCounter``: ``compiles``, ``cache_hits``)."""


def read(args: dict, obs: dict):
    return obs["monitoring"].get(args["event"])
