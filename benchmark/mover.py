"""The entry a mover Job calls, and the store it talks to, as the
backup driver uses them."""

from __future__ import annotations

import json
import threading
from pathlib import Path

ACCESS, SECRET = "bench-access", "bench-secret"
PASSWORD = "bench-password"
BUCKET = "bench"


class Store:
    """The store child plus the URL and env a mover reaches it by."""

    def __init__(self, children):
        p = children.start("store_child.py", ACCESS, SECRET)
        self.port = children.read_json(p)["port"]

    def env(self, prefix: str) -> dict:
        return {
            "RESTIC_REPOSITORY":
                f"s3:http://127.0.0.1:{self.port}/{BUCKET}/{prefix}",
            "RESTIC_PASSWORD": PASSWORD,
            "AWS_ACCESS_KEY_ID": ACCESS,
            "AWS_SECRET_ACCESS_KEY": SECRET,
            "HOSTNAME": "bench",
        }

    def open(self, prefix: str):
        from volsync_tpu.objstore import open_store

        env = self.env(prefix)
        return open_store(env["RESTIC_REPOSITORY"], env=env)

    def flip_pack_bit(self, prefix: str) -> None:
        """The backup cells' control: one bit flipped in
        the largest stored pack under the prefix."""
        store = self.open(prefix)
        key = max((k for k in store.list("") if k.startswith("data/")),
                  key=store.size)
        body = bytearray(store.get(key))
        body[len(body) // 2] ^= 0x10
        store.put(key, bytes(body))
        print(json.dumps({"fault": "flip_pack_bit", "key": key,
                          "bytes": len(body)}), flush=True)

    def usage(self, prefix: str) -> int:
        """Bytes under the prefix, every key family, as the store
        lists them."""
        store = self.open(prefix)
        return sum(store.size(k) for k in store.list(""))


def open_repo(env: dict):
    """A fresh ``Repository.open`` on the store a mover's env names:
    what the guarantee is stated against, never the instance that
    wrote."""
    from volsync_tpu.objstore import open_store
    from volsync_tpu.repo.repository import Repository

    return Repository.open(open_store(env["RESTIC_REPOSITORY"], env=env),
                           password=PASSWORD)


def run_mover(direction: str, env: dict, data: Path) -> int:
    """``movers/restic/entry.restic_entrypoint`` with config by env and
    the volume by mount, as the Job runner calls it. Returns its rc; an
    entry that raises has failed its operation (rc 70), as a Job whose
    container died would have."""
    from volsync_tpu.cluster.runner import JobContext
    from volsync_tpu.movers.restic.entry import restic_entrypoint

    ctx = JobContext(name=f"bench-{direction}", namespace="bench",
                     env={**env, "DIRECTION": direction},
                     mounts={"data": Path(data)}, secrets={},
                     stop_event=threading.Event())
    try:
        return restic_entrypoint(ctx)
    except Exception as ex:  # noqa: BLE001 — counted as a failed operation
        print(json.dumps({"mover_error": direction,
                          "error": repr(ex)[:300]}), flush=True)
        return 70


def span_delta(before: dict, after: dict, top: int = 6) -> dict:
    """The spans with most busy seconds between two ``span_totals()``
    (for the run's info line: where one operation's time went)."""
    delta = {k: round(v[1] - before.get(k, (0, 0.0))[1], 3)
             for k, v in after.items()}
    return dict(sorted(delta.items(), key=lambda kv: -kv[1])[:top])
