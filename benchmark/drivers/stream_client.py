"""One tenant's closed-loop clients, as a process that holds no chip.

stdin, first line: the job (JSON): port, token, tenant, first client
index, clients, seed, sizes (one list per client, streams in order,
used round and round), sample_every, fault. Then ``warm <n>`` (each
client sends n streams; answer ``{"warmed": n}``) and ``go <seconds>``
(each client sends one ChunkStream after another until the window
closes, the one in flight is finished; then the sampled digests are
and chunk boundaries are compared with the reference, outside the
window, and the answer is ``{"streams": [...], ...}``).

A client is a thread with one ``MoverJaxClient`` (what a remote mover
links against). Payloads are random bytes from the seed; no two streams
of a client carry the same bytes.
"""

from __future__ import annotations

import json
import sys
import threading
import time

import numpy as np

from benchmark.reference import blobid as ref
from benchmark.reference import gearcdc


class Client:
    def __init__(self, job: dict, index: int, sizes: list[int]):
        from volsync_tpu.service.client import MoverJaxClient

        self.job, self.index, self.sizes = job, index, sizes
        self.base = np.frombuffer(np.random.default_rng(
            [job["seed"], index]).bytes(max(sizes) + 8), np.uint8)
        self.conn = MoverJaxClient("127.0.0.1", job["port"], job["token"],
                                   tenant=job["tenant"], timeout=600.0)
        self.sent = 0
        self.streams: list[dict] = []
        self.sampled: list[tuple[int, int, list]] = []

    def payload(self, k: int) -> bytes:
        """Stream k of this client: the base bytes from offset k % 8,
        whitened with k, stamped with k — distinct for every k."""
        n = self.sizes[k % len(self.sizes)]
        body = self.base[k % 8: k % 8 + n] ^ np.uint8(k * 37 & 0xFF)
        body[:8] = np.frombuffer(k.to_bytes(8, "little"), np.uint8)
        return body.tobytes()

    def one(self, t0: float | None) -> None:
        """One ChunkStream, timed from the send of its first frame to
        the receipt of its last chunk batch."""
        from volsync_tpu.service.client import ShedError

        k = self.sent
        self.sent += 1
        data = self.payload(k)
        wire = data
        if (self.job.get("fault") == "flip_payload_bit" and t0 is not None
                and k % self.job["sample_every"] == 0):
            # the control: what reaches the service is not what the
            # client meant to send
            bad = bytearray(data)
            bad[len(bad) // 2] ^= 0x10
            wire = bytes(bad)
        ts = time.monotonic()
        err = None
        chunks = []
        try:
            chunks = self.conn.chunk_bytes(wire)
        except ShedError as ex:
            err = f"shed: {ex}"
        except Exception as ex:  # noqa: BLE001 — counted as a failure
            err = repr(ex)[:200]
        te = time.monotonic()
        if t0 is None:
            if err:
                raise RuntimeError(f"warm-up stream failed: {err}")
            return
        pos = 0
        for off, length, _ in chunks:
            if off != pos:
                break
            pos += length
        self.streams.append({"bytes": len(data), "t_start": ts - t0,
                             "t_done": te - t0, "error": err,
                             "covered": err is None and pos == len(data)})
        if err is None and k % self.job["sample_every"] == 0:
            self.sampled.append((k, len(self.streams) - 1, chunks))

    def check_sampled(self) -> tuple[int, int, int]:
        """(digests compared, digests that differ from the reference,
        streams not cut where the reference chunker cuts them)"""
        compared = wrong = miscut = 0
        for k, _, chunks in self.sampled:
            data = memoryview(self.payload(k))
            for off, length, digest in chunks:
                compared += 1
                wrong += ref.blob_id(data[off: off + length]) != digest
            miscut += [(off, length) for off, length, _ in chunks] != \
                gearcdc.cuts(data, self.job["chunker"])
        return compared, wrong, miscut


def main() -> int:
    job = json.loads(sys.stdin.readline())
    clients = [Client(job, job["first"] + i, sizes)
               for i, sizes in enumerate(job["sizes"])]

    def phase(body) -> None:
        threads = [threading.Thread(target=body, args=(c,)) for c in clients]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

    for line in sys.stdin:
        cmd = line.split()
        if not cmd:
            continue
        if cmd[0] == "warm":
            n = int(cmd[1])
            phase(lambda c: [c.one(None) for _ in range(n)])
            print(json.dumps({"warmed": n}), flush=True)
        elif cmd[0] == "go":
            seconds, t0 = float(cmd[1]), float(cmd[2])

            def loop(c: Client) -> None:
                while time.monotonic() - t0 < seconds:
                    c.one(t0)

            phase(loop)
            compared = wrong = miscut = 0
            for c in clients:
                a, b, m = c.check_sampled()
                compared, wrong, miscut = compared + a, wrong + b, miscut + m
            print(json.dumps({
                "streams": [s for c in clients for s in c.streams],
                "digests_compared": compared, "digests_wrong": wrong,
                "boundaries_wrong": miscut}), flush=True)
    for c in clients:
        c.conn.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
