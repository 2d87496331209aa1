"""S3 client vs the in-process verifying fake server.

The reference reaches S3-compatible endpoints through env passthrough
(restic/mover.go:317-364) and tests against MinIO (hack/run-minio.sh);
here the SigV4 client is exercised against a server that *recomputes*
every signature, plus a full restic-mover e2e whose repository lives in
the fake bucket.
"""

import http.client

import pytest

from volsync_tpu.objstore import NoSuchKey, open_store
from volsync_tpu.objstore.fakes3 import FakeS3Server
from volsync_tpu.objstore.s3 import S3Error, S3ObjectStore, SinkRetryRefused


@pytest.fixture
def server():
    with FakeS3Server() as srv:
        yield srv


@pytest.fixture
def store(server):
    return S3ObjectStore(server.endpoint, "bucket", "repo",
                         access_key=server.access_key,
                         secret_key=server.secret_key)


def test_put_get_roundtrip(store):
    store.put("data/ab/abcd", b"hello s3")
    assert store.get("data/ab/abcd") == b"hello s3"
    assert store.exists("data/ab/abcd")
    assert store.size("data/ab/abcd") == 8
    assert not store.exists("data/ab/missing")
    with pytest.raises(NoSuchKey):
        store.get("data/ab/missing")


def test_range_get(store):
    store.put("k", bytes(range(200)))
    assert store.get_range("k", 10, 5) == bytes(range(10, 15))
    assert store.get_range("k", 190, 50) == bytes(range(190, 200))
    assert store.get_range("k", 0, 0) == b""


def test_delete_idempotent(store):
    store.put("k", b"x")
    store.delete("k")
    store.delete("k")  # no error on missing (S3 semantics)
    assert not store.exists("k")


def test_list_with_pagination(server):
    server.max_keys = 7  # force several pages
    store = S3ObjectStore(server.endpoint, "bucket", "p",
                          access_key=server.access_key,
                          secret_key=server.secret_key)
    keys = [f"objects/{i:03d}" for i in range(23)]
    for k in keys:
        store.put(k, b"v")
    assert sorted(store.list("objects/")) == keys
    assert sorted(store.list()) == keys


def test_prefix_isolation(server):
    a = S3ObjectStore(server.endpoint, "bucket", "a",
                      access_key=server.access_key,
                      secret_key=server.secret_key)
    b = S3ObjectStore(server.endpoint, "bucket", "b",
                      access_key=server.access_key,
                      secret_key=server.secret_key)
    a.put("k", b"from-a")
    b.put("k", b"from-b")
    assert a.get("k") == b"from-a"
    assert list(b.list()) == ["k"]


def test_bad_signature_rejected(server):
    bad = S3ObjectStore(server.endpoint, "bucket", "",
                        access_key=server.access_key,
                        secret_key="wrong-secret")
    with pytest.raises(S3Error) as ei:
        bad.put("k", b"x")
    assert ei.value.status == 403


def test_open_store_url_forms(server):
    env = {"AWS_ACCESS_KEY_ID": server.access_key,
           "AWS_SECRET_ACCESS_KEY": server.secret_key}
    # restic-style URL with inline endpoint
    s1 = open_store(f"s3:{server.endpoint}/bucket/pfx", env=env)
    s1.put("k", b"v1")
    # bare s3:// with endpoint from env
    s2 = open_store("s3://bucket/pfx",
                    env={**env, "AWS_S3_ENDPOINT": server.endpoint})
    assert s2.get("k") == b"v1"


def test_exists_raises_on_auth_error_not_false(server):
    """A transient non-404 must never read as 'absent' — Repository.init
    keys its don't-clobber guard on exists()."""
    bad = S3ObjectStore(server.endpoint, "bucket", "",
                        access_key=server.access_key,
                        secret_key="wrong-secret")
    with pytest.raises(S3Error):
        bad.exists("config")


def test_schemeless_restic_url_form():
    s = S3ObjectStore.from_url(
        "s3:s3.amazonaws.com/bucket/repo",
        env={"AWS_ACCESS_KEY_ID": "a", "AWS_SECRET_ACCESS_KEY": "s"})
    assert s.scheme == "https"
    assert s.host == "s3.amazonaws.com"
    assert s.bucket == "bucket"
    assert s.prefix == "repo"


def test_file_transfer_streams(server, tmp_path, rng):
    store = S3ObjectStore(server.endpoint, "bucket", "xfer",
                          access_key=server.access_key,
                          secret_key=server.secret_key)
    src = tmp_path / "big.bin"
    data = rng.bytes(3 * 1024 * 1024)
    src.write_bytes(data)
    store.put_file("objects/big", src)
    assert store.size("objects/big") == len(data)
    dst = tmp_path / "out.bin"
    n = store.get_file("objects/big", dst)
    assert n == len(data)
    assert dst.read_bytes() == data
    with pytest.raises(NoSuchKey):
        store.get_file("objects/missing", tmp_path / "nope")
    assert not (tmp_path / "nope").exists()


class _DyingResponse:
    """Streams a prefix of the body into the sink, then the connection
    'drops' (IncompleteRead — an http.client.HTTPException, so the
    transport policy classifies it retryable)."""

    status = 200

    def __init__(self, prefix: bytes):
        self._chunks = [prefix]

    def read(self, n=-1):
        if self._chunks:
            return self._chunks.pop()
        raise http.client.IncompleteRead(b"")

    def getheaders(self):
        return []


class _DyingConn:
    def __init__(self, prefix: bytes):
        self._prefix = prefix

    def request(self, *args, **kwargs):
        pass

    def getresponse(self):
        return _DyingResponse(self._prefix)


def test_get_file_rewinds_sink_on_mid_body_retry(store, monkeypatch,
                                                 tmp_path):
    """A connection drop AFTER the sink has drained bytes must not
    replay them: the retry rewinds a seekable sink to its pre-request
    position, so the final file carries no duplicated prefix."""
    payload = bytes(range(256)) * 512  # 128 KiB
    store.put("obj", payload)
    real_conn = store._conn
    attempts = []

    def flaky_conn():
        attempts.append(1)
        if len(attempts) == 1:
            return _DyingConn(payload[:4096])
        return real_conn()

    monkeypatch.setattr(store, "_conn", flaky_conn)
    dst = tmp_path / "out.bin"
    n = store.get_file("obj", dst)
    assert len(attempts) == 2  # first died mid-body, second completed
    assert n == len(payload)
    assert dst.read_bytes() == payload


def test_unseekable_sink_refuses_mid_body_retry(store, monkeypatch):
    """An unseekable sink that already consumed bytes cannot be rewound;
    the retry must be refused (fatal), not silently duplicate data."""

    class _Unseekable:
        def __init__(self):
            self.drained = bytearray()

        def write(self, b):
            self.drained += b

        def tell(self):  # pipe-like: no position
            raise OSError("unseekable")

    store.put("obj", b"x" * 1024)
    attempts = []

    def flaky_conn():
        attempts.append(1)
        return _DyingConn(b"x" * 100)

    monkeypatch.setattr(store, "_conn", flaky_conn)
    sink = _Unseekable()
    with pytest.raises(SinkRetryRefused):
        store._request("GET", "obj", sink=sink)
    assert len(attempts) == 1  # fatal on the first attempt — no blind retry
    assert bytes(sink.drained) == b"x" * 100  # partial bytes, never replayed


def test_repository_over_s3(server, tmp_path, rng):
    """Full backup->restore round-trip with the repo in the fake bucket."""
    from volsync_tpu.engine import TreeBackup, restore_snapshot
    from volsync_tpu.repo.repository import Repository

    store = S3ObjectStore(server.endpoint, "bucket", "repo",
                          access_key=server.access_key,
                          secret_key=server.secret_key)
    src = tmp_path / "src"
    (src / "sub").mkdir(parents=True)
    (src / "a.bin").write_bytes(rng.bytes(300_000))
    (src / "sub" / "b.txt").write_bytes(b"beta" * 2000)

    repo = Repository.init(store, password="pw")
    snap_id, stats = TreeBackup(repo).run(src)
    assert snap_id is not None

    dest = tmp_path / "dest"
    repo2 = Repository.open(
        S3ObjectStore(server.endpoint, "bucket", "repo",
                      access_key=server.access_key,
                      secret_key=server.secret_key), password="pw")
    out = restore_snapshot(repo2, dest)
    assert out is not None
    assert (dest / "a.bin").read_bytes() == (src / "a.bin").read_bytes()
    assert (dest / "sub" / "b.txt").read_bytes() == b"beta" * 2000


def test_restic_mover_e2e_over_s3(server, tmp_path, rng):
    """The mover reaches the bucket purely via the Secret->env passthrough,
    like the reference's ~35 AWS env vars."""
    from volsync_tpu.api.common import CopyMethod, ObjectMeta
    from volsync_tpu.api.types import (
        ReplicationSource,
        ReplicationSourceResticSpec,
        ReplicationSourceSpec,
        ReplicationTrigger,
    )
    from volsync_tpu.cluster.cluster import Cluster
    from volsync_tpu.cluster.objects import Secret, Volume, VolumeSpec
    from volsync_tpu.cluster.runner import EntrypointCatalog, JobRunner
    from volsync_tpu.cluster.storage import StorageProvider
    from volsync_tpu.controller.manager import Manager
    from volsync_tpu.metrics import Metrics
    from volsync_tpu.movers import restic as restic_mover
    from volsync_tpu.movers.base import Catalog

    cluster = Cluster(storage=StorageProvider(tmp_path / "storage"))
    catalog = Catalog()
    rc = EntrypointCatalog()
    restic_mover.register(catalog, rc)
    runner = JobRunner(cluster, rc).start()
    manager = Manager(cluster, catalog=catalog, metrics=Metrics()).start()
    try:
        vol = cluster.create(Volume(
            metadata=ObjectMeta(name="d", namespace="default"),
            spec=VolumeSpec(capacity=1 << 30)))
        import pathlib

        pathlib.Path(vol.status.path, "f.bin").write_bytes(rng.bytes(100_000))
        cluster.create(Secret(
            metadata=ObjectMeta(name="sec", namespace="default"),
            data={"RESTIC_REPOSITORY":
                  f"s3:{server.endpoint}/bucket/repo2".encode(),
                  "RESTIC_PASSWORD": b"pw",
                  "AWS_ACCESS_KEY_ID": server.access_key.encode(),
                  "AWS_SECRET_ACCESS_KEY": server.secret_key.encode()}))
        cluster.create(ReplicationSource(
            metadata=ObjectMeta(name="bk", namespace="default"),
            spec=ReplicationSourceSpec(
                source_pvc="d", trigger=ReplicationTrigger(manual="go"),
                restic=ReplicationSourceResticSpec(
                    repository="sec", copy_method=CopyMethod.CLONE))))
        assert cluster.wait_for(lambda: (
            (cr := cluster.try_get("ReplicationSource", "default", "bk"))
            and cr.status and cr.status.last_manual_sync == "go"),
            timeout=60, poll=0.05)
        # The snapshot objects really live in the bucket.
        assert any(k.startswith("repo2/snapshots/")
                   for (b, k) in server._objects)
    finally:
        manager.stop()
        runner.stop()


def test_parallel_backup_restore_through_s3(tmp_path, rng):
    """Worker-pool backup + restore against the S3 store: exercises the
    SigV4 client's thread-local connections under real concurrency (the
    reference's restic mover speaks HTTPS-S3 the same way)."""
    from volsync_tpu.engine.backup import TreeBackup
    from volsync_tpu.engine.restore import TreeRestore
    from volsync_tpu.repo.repository import Repository

    with FakeS3Server() as srv:
        store = S3ObjectStore(srv.endpoint, "bucket", "repo",
                              access_key=srv.access_key,
                              secret_key=srv.secret_key)
        src = tmp_path / "vol"
        src.mkdir()
        for i in range(10):
            (src / f"f{i}.bin").write_bytes(rng.bytes(120_000))
        repo = Repository.init(store, password="s3cret")
        sid, stats = TreeBackup(repo).run(src)
        assert stats.files == 10
        snaps = dict(repo.list_snapshots())
        dest = tmp_path / "out"
        TreeRestore(repo, workers=6).run(sid, snaps[sid], dest)
        for i in range(10):
            assert (dest / f"f{i}.bin").read_bytes() \
                == (src / f"f{i}.bin").read_bytes()
