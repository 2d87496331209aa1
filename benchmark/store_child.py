"""The object store of the backup cells, as a process of
its own that never imports JAX: ``objstore/fakes3.py``'s SigV4-verifying
server (the MinIO of ``hack/run-minio.sh``), objects in memory.

usage: store_child.py <access-key> <secret-key>; prints ``{"port": n}``
and serves until its stdin closes.
"""

from __future__ import annotations

import json
import sys


def main() -> int:
    from volsync_tpu.objstore.fakes3 import FakeS3Server

    if "jax" in sys.modules:
        raise RuntimeError("the store process imported jax")
    with FakeS3Server(access_key=sys.argv[1], secret_key=sys.argv[2]) as srv:
        print(json.dumps({"port": srv.port}), flush=True)
        sys.stdin.read()
    return 0


if __name__ == "__main__":
    sys.exit(main())
