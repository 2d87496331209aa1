"""One restic mover of a fleet, as a process that holds no chip: a loop
around ``mover.run_mover("backup", env, root)`` with
``VOLSYNC_ENGINE=service`` in its env, so every device-path file is
hashed by the mover-jax service the benchmark's process runs.

stdin, first line: the job (JSON): mover (its name), tenant, seed (the
volume's), shape, root (where to write the volume), repo_base (the
store's URL up to the prefix), env (the rest of the mover's env:
credentials, the service's address, token and tenant). The child first
asks the program for the service engine by name; a program without it
answers ``{"ready": false, ...}`` at once. Else it writes its volume and
answers ``{"ready": true, "files": {...}}``. Then ``warm`` (one whole
backup into ``<mover>/warm``; answer ``{"rc": n}``) and ``go <seconds>
<t0>`` (one whole first backup after another, each into a fresh prefix
``<mover>/op<k>``, until the window closes; the one in flight is
finished; the answer carries the operations, this process's span
totals, self totals and counters since ``go``, and whether it ever
initialised a JAX backend).
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path


def backends_initialized() -> bool:
    from jax._src import xla_bridge

    return bool(xla_bridge.backends_are_initialized())


def main() -> int:
    job = json.loads(sys.stdin.readline())
    try:
        # by name, before anything else: a program without the service
        # engine must say so now, not hash a volume on a CPU backend
        from volsync_tpu.service.hasher import RemoteChunkHasher  # noqa: F401
    except ImportError as ex:
        print(json.dumps({"ready": False, "error": repr(ex)}), flush=True)
        return 0
    from volsync_tpu.obs import (counter_totals, reset_spans,
                                 span_self_totals, span_totals)

    from benchmark import mover, volumes

    root = Path(job["root"])
    files = volumes.write(root, job["shape"], int(job["seed"]))
    nbytes = sum(files.values())
    print(json.dumps({"ready": True, "files": files}), flush=True)

    def backup(prefix: str) -> int:
        env = {**job["env"],
               "RESTIC_REPOSITORY": job["repo_base"] + prefix}
        return mover.run_mover("backup", env, root)

    for line in sys.stdin:
        cmd = line.split()
        if not cmd:
            continue
        if cmd[0] == "warm":
            print(json.dumps({"rc": backup(f"{job['mover']}/warm")}),
                  flush=True)
        elif cmd[0] == "go":
            seconds, t0 = float(cmd[1]), float(cmd[2])
            reset_spans()
            cpu0 = time.process_time()
            ops = []
            while True:
                prefix = f"{job['mover']}/op{len(ops):04d}"
                ts = time.monotonic()
                rc = backup(prefix)
                te = time.monotonic()
                ops.append({"prefix": prefix, "mover": job["mover"],
                            "bytes": nbytes, "rc": rc,
                            "t_start": ts - t0, "t_done": te - t0})
                if te - t0 >= seconds:
                    break
            print(json.dumps({
                "ops": ops, "spans": span_totals(),
                "self": span_self_totals(), "counters": counter_totals(),
                "cpu_s": time.process_time() - cpu0,
                "backends_initialized": backends_initialized()}),
                flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
