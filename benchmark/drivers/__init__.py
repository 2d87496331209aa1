"""One module per kind of traffic: ``setup(ctx) -> state``,
``warmup(state)``, ``run(state, seconds) -> observations``,
``verify(state) -> (attempted, failed, checks)``, ``inject(state, fault)``.
A workload file names its driver; a new kind of traffic is a new file."""
