"""The restic mover that holds no accelerator: ``VOLSYNC_ENGINE=service``
through ``movers/restic/entry.restic_entrypoint`` against an in-process
``MoverJaxServer``, held to the in-process engine's snapshot and to the
plain references (``benchmark/reference/gearcdc.py``, ``blobid.py``);
the server's segment rule and program bound; the replay contract; the
answers the hasher refuses. Holds guarantees (a)-(f) of
``benchmark/configs/fleet-restic-10g.json`` at small sizes on the CPU.
"""

import json
import os
import subprocess
import sys
import threading
import time
from concurrent.futures import Future
from pathlib import Path

import numpy as np
import pytest

from benchmark.drivers.backup_check import snapshot_files
from benchmark.reference import blobid as ref
from benchmark.reference import gearcdc
from volsync_tpu.cluster.runner import JobContext
from volsync_tpu.movers.restic.entry import RC_SERVICE, restic_entrypoint
from volsync_tpu.obs import counter_totals
from volsync_tpu.ops.gearcdc import GearParams

ROOT = Path(__file__).resolve().parent.parent
KiB = 1024
CHUNKER = {"min_size": 4096, "avg_size": 32768, "max_size": 65536,
           "seed": 0x5EEDCDC1, "norm_level": 2, "align": 4096}
PARAMS = GearParams(min_size=4096, avg_size=32768, max_size=65536,
                    seed=0x5EEDCDC1, align=4096)
SEGMENT = 128 * KiB
CUT = SEGMENT + PARAMS.max_size  # 192 KiB
#: the satellite's five files: hashed in the mover, one segment, three,
#: ending exactly on the service's cut, empty
CASES = {"under_min": 3000, "one_segment": 100 * KiB,
         "three_segments": 500 * KiB, "on_a_cut": CUT, "empty": 0}


def _server(**kw):
    from volsync_tpu.service.server import MoverJaxServer

    return MoverJaxServer(params=PARAMS, segment_size=SEGMENT, **kw)


@pytest.fixture(scope="module")
def server():
    with _server() as srv:
        yield srv


def _repo(path: Path, chunker=CHUNKER):
    from volsync_tpu.objstore import open_store
    from volsync_tpu.repo.repository import Repository

    return Repository.init(open_store(str(path)), password="pw",
                           chunker={k: v for k, v in chunker.items()
                                    if k != "norm_level"})


def _open(path: Path):
    from volsync_tpu.objstore import open_store
    from volsync_tpu.repo.repository import Repository

    return Repository.open(open_store(str(path)), password="pw")


def _service_env(srv, **extra) -> dict:
    return {"VOLSYNC_ENGINE": "service",
            "MOVER_JAX_ADDRESS": f"127.0.0.1:{srv.port}",
            "MOVER_JAX_TOKEN": srv.token, **extra}


def _backup(repo: Path, vol: Path, env: dict, namespace="ns") -> int:
    ctx = JobContext(
        name="t", namespace=namespace,
        env={"RESTIC_REPOSITORY": str(repo), "RESTIC_PASSWORD": "pw",
             "HOSTNAME": "t", **env},
        mounts={"data": vol}, secrets={}, stop_event=threading.Event())
    return restic_entrypoint(ctx)


def _volume(root: Path, files: dict, seed=5) -> Path:
    rng = np.random.default_rng(seed)
    root.mkdir(parents=True)
    for rel, n in files.items():
        (root / rel).parent.mkdir(parents=True, exist_ok=True)
        (root / rel).write_bytes(rng.bytes(n))
    return root


def _snapshot(repo_path: Path):
    repo = _open(repo_path)
    snaps = repo.list_snapshots()
    assert len(snaps) == 1
    tree = snaps[0][1]["tree"]
    return tree, {rel: e["content"]
                  for rel, e in snapshot_files(repo, tree).items()}


# -- (1) the snapshot: service == in-process == the references ------------

@pytest.mark.parametrize("case", sorted(CASES))
def test_a_service_backup_writes_the_in_process_snapshot(case, server,
                                                         tmp_path):
    vol = _volume(tmp_path / "vol", {"f.bin": CASES[case], "keep": 100})
    for name in ("svc", "own"):
        _repo(tmp_path / name)
    before = counter_totals()
    assert _backup(tmp_path / "svc", vol, _service_env(server)) == 0
    after = counter_totals()
    assert _backup(tmp_path / "own", vol, {}) == 0
    tree, content = _snapshot(tmp_path / "svc")
    assert (tree, content) == _snapshot(tmp_path / "own")
    data = (vol / "f.bin").read_bytes()
    if case == "empty":
        assert content["f.bin"] == []
    elif case == "under_min":
        assert content["f.bin"] == [ref.blob_id(data)]
    else:
        assert content["f.bin"] == [
            ref.blob_id(data[off: off + n])
            for off, n in gearcdc.cuts(data, CHUNKER)]
    # (8) a stream a device-path file, counted alike on both sides
    streams = int(CASES[case] > PARAMS.min_size)
    for name in ("remote.streams", "svc.streams"):
        assert after.get(name, 0) - before.get(name, 0) == streams, name
    for name in ("remote.bytes", "svc.stream_bytes", "svc.tenant_bytes.ns"):
        assert after.get(name, 0) - before.get(name, 0) \
            == streams * CASES[case], name
    want = {"one_segment": 1, "three_segments": 3, "on_a_cut": 1}
    assert after.get("svc.segments", 0) - before.get("svc.segments", 0) \
        == want.get(case, 0)


# -- (2) the server: segments follow the bytes; the programs are planned --

def _record_segments(srv, monkeypatch, delay=0.0):
    seen = []
    submit = srv._submit_segment

    def slow(ticket, data, eof):
        seen.append((len(data), eof))
        if delay:
            time.sleep(delay)
        return submit(ticket, data, eof)

    monkeypatch.setattr(srv, "_submit_segment", slow)
    return seen


@pytest.mark.parametrize("timing", ["slow_reader", "slow_device"])
def test_a_long_streams_segments_follow_its_bytes(timing, monkeypatch):
    from volsync_tpu.service import client as client_mod
    from volsync_tpu.service.client import MoverJaxClient

    monkeypatch.setattr(client_mod, "_SEND_CHUNK", 50_000)
    data = np.random.default_rng(9).bytes(1_000_000)
    cuts = gearcdc.cuts(data, CHUNKER)
    # what the rule gives from the bytes alone
    want, base = [], 0
    while len(data) - base > CUT:
        want.append((CUT, False))
        base = max(off + n for off, n in cuts if off + n <= base + CUT
                   and off + n - base <= CUT)
    want.append((len(data) - base, True))
    with _server(stream_credits=1) as srv:
        seen = _record_segments(
            srv, monkeypatch, delay=0.05 if timing == "slow_device" else 0)
        with MoverJaxClient("127.0.0.1", srv.port, srv.token) as cli:
            pos = [0]

            def read(n):
                if timing == "slow_reader":
                    time.sleep(0.002)
                piece = data[pos[0]: pos[0] + n]
                pos[0] += len(piece)
                return piece

            got = list(cli.chunk_stream(read))
    assert [(off, n) for off, n, _ in got] == cuts
    assert seen == want and len(want) >= 5


def test_concurrent_streams_meet_only_planned_programs(monkeypatch):
    from benchmark import warm_fleet
    from volsync_tpu.service.client import MoverJaxClient

    sizes = [[700 * KiB, 30 * KiB], [150 * KiB, 9 * KiB, 64 * KiB],
             [400 * KiB, 5 * KiB], [20 * KiB, 250 * KiB]]
    with _server() as srv:
        ran = []
        hasher = srv._batcher._hasher
        bucket = hasher._hash_bucket
        monkeypatch.setattr(
            hasher, "_hash_bucket",
            lambda P, items: ran.append((len(items), P)) or bucket(P, items))
        plan = warm_fleet.fleet_plan(sizes, srv)

        def mover(k):
            rng = np.random.default_rng(k)
            with MoverJaxClient("127.0.0.1", srv.port, srv.token,
                                tenant=f"t{k}") as cli:
                for _ in range(2):
                    for n in sizes[k]:
                        cli.chunk_bytes(rng.bytes(n))

        threads = [threading.Thread(target=mover, args=(k,))
                   for k in range(len(sizes))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    from benchmark.warm import _pow2ceil

    assert ran and {(_pow2ceil(n), P) for n, P in ran} <= set(plan)
    # one dispatch stages no more than one full segment does
    assert all(_pow2ceil(n) * P <= srv.stage_limit or n == 1
               for n, P in ran)
    assert max(P for _, P in plan) == srv.stage_limit


# -- (3) a shed replays; a stopped service fails the backup ---------------

def test_a_shed_stream_is_replayed_and_both_backups_complete(monkeypatch,
                                                             tmp_path):
    monkeypatch.setenv("VOLSYNC_RETRY_ATTEMPTS", "40")
    monkeypatch.setenv("VOLSYNC_RETRY_MAX_MS", "200")
    files = {"f.bin": 500 * KiB, "g.bin": 300 * KiB}
    vols = [_volume(tmp_path / f"vol{k}", files, seed=k) for k in (0, 1)]
    before = counter_totals()
    with _server(tenant_streams=1) as srv:
        _record_segments(srv, monkeypatch, delay=0.05)
        rcs = [None, None]

        def mover(k):
            _repo(tmp_path / f"svc{k}")
            rcs[k] = _backup(tmp_path / f"svc{k}", vols[k],
                             _service_env(srv, MOVER_JAX_TENANT="shared"))

        threads = [threading.Thread(target=mover, args=(k,)) for k in (0, 1)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    after = counter_totals()
    assert rcs == [0, 0]
    assert after.get("remote.replays", 0) - before.get("remote.replays", 0) \
        >= 1
    for k in (0, 1):
        _repo(tmp_path / f"own{k}")
        assert _backup(tmp_path / f"own{k}", vols[k], {}) == 0
        assert _snapshot(tmp_path / f"svc{k}") == _snapshot(
            tmp_path / f"own{k}")


def test_a_service_stopped_mid_file_fails_the_backup(monkeypatch, tmp_path):
    monkeypatch.setenv("VOLSYNC_RETRY_ATTEMPTS", "2")
    monkeypatch.setenv("VOLSYNC_RETRY_MAX_MS", "50")
    vol = _volume(tmp_path / "vol", {"f.bin": 900 * KiB})
    _repo(tmp_path / "svc")
    srv = _server().start()
    first = threading.Event()
    submit = srv._submit_segment

    def slow(ticket, data, eof):
        first.set()
        time.sleep(0.3)
        return submit(ticket, data, eof)

    monkeypatch.setattr(srv, "_submit_segment", slow)
    rc = []
    t = threading.Thread(target=lambda: rc.append(
        _backup(tmp_path / "svc", vol, _service_env(srv))))
    t.start()
    assert first.wait(60)
    srv.stop(grace=0, drain=0)
    t.join(120)
    assert rc == [RC_SERVICE]
    assert _open(tmp_path / "svc").list_snapshots() == []


# -- (4) an answer that does not cover the file is refused ----------------

class _FakeClient:
    """``chunk_batches`` that drains the frames and answers as told."""

    def __init__(self, answer):
        self.answer = answer

    def chunk_batches(self, payloads, timeout=None):
        total = sum(len(p) for p in payloads)
        yield from self.answer(total)

    def close(self):
        pass


def _whole(total, size=PARAMS.max_size):
    return [(off, min(size, total - off), "x")
            for off in range(0, total, size)]


ANSWERS = {
    "gap": lambda n: [(_whole(n)[:1] + _whole(n)[2:], True)],
    "overlap": lambda n: [([(0, 65536, "x"), (60000, 65536, "x")], True)],
    "short_cover": lambda n: [(_whole(n)[:-1], True)],
    "no_final_batch": lambda n: [(_whole(n), False)],
    "over_max_size": lambda n: [([(0, 65537, "x")], True)],
    "small_chunk_inside": lambda n: [
        ([(0, 100, "x")] + [(o + 100, s, d) for o, s, d in _whole(n - 100)],
         True)],
    "past_the_end": lambda n: [(_whole(n + 5), True)],
}


@pytest.mark.parametrize("fault", sorted(ANSWERS))
def test_an_answer_that_does_not_cover_the_file_is_refused(fault, tmp_path):
    from volsync_tpu.resilience import RetryPolicy
    from volsync_tpu.service.hasher import (AnswerRefused,
                                            RemoteChunkHasher,
                                            ServiceHashError)

    path = tmp_path / "f"
    path.write_bytes(bytes(300_000))
    policy = RetryPolicy(site="t", max_attempts=2, sleep_fn=lambda s: None)
    hasher = RemoteChunkHasher(_FakeClient(ANSWERS[fault]), PARAMS, policy)
    given = []
    replayed = fault in ("short_cover", "no_final_batch")
    with pytest.raises(ServiceHashError if replayed else AnswerRefused):
        hasher.hash_file(lambda: open(path, "rb"), given.extend)
    assert policy.last_attempts == (2 if replayed else 1)
    # a replay hands the repository no chunk twice
    if replayed:
        whole = len(_whole(300_000))
        assert len(given) == (whole - 1 if fault == "short_cover" else whole)
    # and the right answer is taken
    good = RemoteChunkHasher(_FakeClient(lambda n: [(_whole(n), True)]),
                             PARAMS, policy)
    given.clear()
    good.hash_file(lambda: open(path, "rb"), given.extend)
    assert sum(len(v) for v, _ in given) == 300_000


# -- (5) the mover initialises no JAX backend -----------------------------

_CHILD = """
import json, sys, threading
from pathlib import Path
from volsync_tpu.cluster.runner import JobContext
from volsync_tpu.movers.restic.entry import restic_entrypoint
job = json.loads(sys.argv[1])
ctx = JobContext(name="t", namespace="ns", env=job["env"],
                 mounts={"data": Path(job["vol"])}, secrets={},
                 stop_event=threading.Event())
rc = restic_entrypoint(ctx)
from jax._src import xla_bridge
print(json.dumps({"rc": rc,
                  "backends": bool(xla_bridge.backends_are_initialized())}))
"""


def test_a_service_mover_initialises_no_jax_backend(server, tmp_path):
    vol = _volume(tmp_path / "vol", {"f.bin": 300 * KiB, "small": 2000})
    _repo(tmp_path / "svc")
    env = {"RESTIC_REPOSITORY": str(tmp_path / "svc"),
           "RESTIC_PASSWORD": "pw", **_service_env(server)}
    done = subprocess.run(
        [sys.executable, "-c", _CHILD,
         json.dumps({"env": env, "vol": str(vol)})],
        capture_output=True, text=True, timeout=300, cwd=str(ROOT),
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": os.pathsep.join(
                 p for p in (str(ROOT), os.environ.get("PYTHONPATH")) if p)})
    assert done.returncode == 0, done.stderr[-2000:]
    assert json.loads(done.stdout.splitlines()[-1]) \
        == {"rc": 0, "backends": False}
    assert len(_open(tmp_path / "svc").list_snapshots()) == 1


# -- (6) the bytes held for a file in flight are bounded ------------------

def test_held_bytes_stay_under_the_bound_on_a_slow_device(monkeypatch,
                                                          tmp_path):
    """Ten segments of the default chunker's cut behind a device that
    takes its time: what the mover holds is the credits and the
    channel, not the file. (The device is a stand-in that cuts at
    max_size: the bound is about bytes in flight, not about hashing.)"""
    from volsync_tpu.ops.gearcdc import DEFAULT_PARAMS as P
    from volsync_tpu.service.client import MoverJaxClient
    from volsync_tpu.service.hasher import (RemoteChunkHasher,
                                            held_bytes_bound)
    from volsync_tpu.service.server import MoverJaxServer

    seg = 1 << 20
    size = 10 * (seg + P.max_size) + 12345
    path = tmp_path / "f"
    with open(path, "wb") as f:
        f.truncate(size)
    before = counter_totals().get("remote.held_bytes_max", 0)
    with MoverJaxServer(segment_size=seg) as srv:
        def device(ticket, data, eof):
            out, pos, n = [], 0, len(data)
            while n - pos > P.max_size or (eof and pos < n):
                out.append((pos, min(P.max_size, n - pos), "x"))
                pos += out[-1][1]
            fut = Future()
            threading.Timer(0.05, fut.set_result, ((out, 0),)).start()
            return fut

        monkeypatch.setattr(srv, "_submit_segment", device)
        monkeypatch.setattr(srv._batcher, "wait", lambda fut: fut.result())
        hasher = RemoteChunkHasher(
            MoverJaxClient("127.0.0.1", srv.port, srv.token), P)
        got = []
        hasher.hash_file(lambda: open(path, "rb"),
                         lambda b: got.append(sum(len(v) for v, _ in b)))
        hasher.close()
    assert sum(got) == size
    held = counter_totals()["remote.held_bytes_max"]
    bound = held_bytes_bound(seg, P.max_size, 2)
    assert before <= held <= bound < 0.6 * size


# -- (7) configuration errors ---------------------------------------------

@pytest.mark.parametrize("missing", ["MOVER_JAX_ADDRESS", "MOVER_JAX_TOKEN",
                                     "port"])
def test_service_without_address_or_token_is_a_config_error(missing, server,
                                                            tmp_path):
    vol = _volume(tmp_path / "vol", {"f.bin": 50 * KiB})
    env = _service_env(server)
    if missing == "port":
        env["MOVER_JAX_ADDRESS"] = "127.0.0.1"
    else:
        del env[missing]
    assert _backup(tmp_path / "svc", vol, env) == 2
    assert not (tmp_path / "svc").exists() or \
        not any((tmp_path / "svc").iterdir())


def test_a_wrong_token_fails_the_backup_and_saves_no_snapshot(server,
                                                              tmp_path):
    vol = _volume(tmp_path / "vol", {"f.bin": 50 * KiB})
    _repo(tmp_path / "svc")
    env = _service_env(server, MOVER_JAX_TOKEN="not-the-token")
    assert _backup(tmp_path / "svc", vol, env) == RC_SERVICE
    assert _open(tmp_path / "svc").list_snapshots() == []


def test_other_chunker_parameters_than_the_services_are_refused(server,
                                                                tmp_path):
    vol = _volume(tmp_path / "vol", {"f.bin": 50 * KiB})
    _repo(tmp_path / "svc", {**CHUNKER, "max_size": 131072})
    assert _backup(tmp_path / "svc", vol, _service_env(server)) == RC_SERVICE
    assert _open(tmp_path / "svc").list_snapshots() == []
    # TreeBackup's own refusal still stands behind it
    from volsync_tpu.engine import TreeBackup
    from volsync_tpu.service.hasher import RemoteChunkHasher

    with pytest.raises(ValueError, match="hasher params"):
        TreeBackup(_open(tmp_path / "svc"),
                   hasher=RemoteChunkHasher(None, PARAMS))


# -- the cell's rehearsal -------------------------------------------------

def test_the_cells_rehearsal_ends_correct(tmp_path):
    done = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"), "--workload",
         "fleet-restic-10g.backup", "--size", "rehearsal", "--seed",
         "2147483660", "--seconds", "3", "--trace", "0"],
        capture_output=True, text=True, cwd=str(ROOT), timeout=1200,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             # the cells run the batched program, the suite pins it off
             "VOLSYNC_BATCH_SEGMENTS": "1",
             "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax_cache")})
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(ln) for ln in done.stdout.splitlines()
             if ln.startswith("{")]
    last, info = lines[-1], lines[-2]
    assert last["correct"] is True and last["failed"] == 0
    assert info["operations"] >= 3 and info["in_window"]["compiles"] == 0
    checks = {c["check"]: c for c in lines if "check" in c}
    for name in ("ops_failed", "snapshots_wrong", "files_missing",
                 "check_problems", "blob_id_mismatches",
                 "file_sha_mismatches", "chunk_boundary_mismatches",
                 "read_errors", "mover_backends_initialized",
                 "streams_answered_elsewhere"):
        assert checks[name] == {"check": name, "value": 0, "limit": 0}
    for name in ("files_read_back", "svc_stream_bytes",
                 "device_staged_bytes"):
        assert checks[name]["value"] >= checks[name]["at_least"] > 0
