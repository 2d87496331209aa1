"""Cluster substrate: CRUD semantics, storage PiT images, job runner."""

import threading

import pytest

from volsync_tpu.api.common import ObjectMeta
from volsync_tpu.cluster import (
    Cluster,
    Conflict,
    EntrypointCatalog,
    Job,
    JobRunner,
    JobSpec,
    NotFound,
    Secret,
    StorageProvider,
    Volume,
    VolumeSnapshot,
    VolumeSnapshotSpec,
    VolumeSpec,
)


@pytest.fixture
def cluster(tmp_path):
    return Cluster(storage=StorageProvider(tmp_path / "csi"))


def test_crud_and_resource_versions(cluster):
    v = Volume(metadata=ObjectMeta(name="pvc-a", namespace="ns"))
    cluster.create(v)
    with pytest.raises(Conflict):
        cluster.create(Volume(metadata=ObjectMeta(name="pvc-a", namespace="ns")))
    got = cluster.get("Volume", "ns", "pvc-a")
    assert got.status.phase == "Bound"  # dynamic provisioner bound it
    rv = got.metadata.resource_version
    cluster.update(got)
    assert got.metadata.resource_version > rv
    with pytest.raises(Conflict):
        cluster.update(got, expect_version=rv)
    with pytest.raises(NotFound):
        cluster.get("Volume", "ns", "missing")


def test_label_selector_delete(cluster):
    for i in range(3):
        cluster.create(Volume(metadata=ObjectMeta(
            name=f"v{i}", namespace="ns",
            labels={"volsync.backube/cleanup": "uid-1"} if i < 2 else {},
        )))
    n = cluster.delete_all_of("Volume", "ns", {"volsync.backube/cleanup": "uid-1"})
    assert n == 2
    assert [v.metadata.name for v in cluster.list("Volume", "ns")] == ["v2"]


def test_snapshot_is_point_in_time(cluster, tmp_path):
    vol = cluster.create(Volume(metadata=ObjectMeta(name="data", namespace="ns")))
    p = tmp_path / "csi" / "volumes" / "ns" / "data"
    (p / "f.txt").write_text("v1")
    snap = cluster.create(VolumeSnapshot(
        metadata=ObjectMeta(name="snap", namespace="ns"),
        spec=VolumeSnapshotSpec(source_volume="data"),
    ))
    assert snap.status.ready_to_use
    # mutate the source *after* the snapshot: replace-style write
    (p / "f.txt").unlink()
    (p / "f.txt").write_text("v2")
    restored = cluster.create(Volume(
        metadata=ObjectMeta(name="restored", namespace="ns"),
        spec=VolumeSpec(data_source={"kind": "VolumeSnapshot", "name": "snap"}),
    ))
    restored_path = restored.status.path
    assert (p / "f.txt").read_text() == "v2"
    assert open(f"{restored_path}/f.txt").read() == "v1"


def test_apply_immutable_job_delete_recreate(cluster):
    job = Job(metadata=ObjectMeta(name="j", namespace="ns"),
              spec=JobSpec(entrypoint="a"))
    cluster.create(job)
    uid0 = job.metadata.uid
    # same entrypoint: plain update
    cluster.apply(Job(metadata=ObjectMeta(name="j", namespace="ns"),
                      spec=JobSpec(entrypoint="a", env={"X": "1"})))
    assert cluster.get("Job", "ns", "j").metadata.uid == uid0
    # changed entrypoint: immutable -> delete+recreate (new uid)
    cluster.apply(Job(metadata=ObjectMeta(name="j", namespace="ns"),
                      spec=JobSpec(entrypoint="b")))
    fresh = cluster.get("Job", "ns", "j")
    assert fresh.spec.entrypoint == "b"
    assert fresh.metadata.uid != uid0


def test_runner_executes_and_retries(cluster):
    catalog = EntrypointCatalog()
    attempts = []

    @catalog.register("flaky")
    def flaky(ctx):
        attempts.append(ctx.attempt)
        if len(attempts) < 2:
            raise RuntimeError("transient")
        (ctx.mounts["data"] / "done").write_text(ctx.env["MSG"])
        return 0

    cluster.create(Volume(metadata=ObjectMeta(name="data", namespace="ns")))
    cluster.create(Secret(metadata=ObjectMeta(name="s", namespace="ns"),
                          data={"k": b"v"}))
    job = Job(
        metadata=ObjectMeta(name="move", namespace="ns"),
        spec=JobSpec(entrypoint="flaky", env={"MSG": "hi"},
                     volumes={"data": "data"}, secrets={"creds": "s"},
                     backoff_limit=3),
    )
    cluster.create(job)
    with JobRunner(cluster, catalog):
        ok = cluster.wait_for(
            lambda: cluster.get("Job", "ns", "move").status.succeeded > 0,
            timeout=15,
        )
    assert ok
    final = cluster.get("Job", "ns", "move")
    assert final.status.failed == 1 and final.status.exit_code == 0
    vol = cluster.get("Volume", "ns", "data")
    assert open(f"{vol.status.path}/done").read() == "hi"


def test_runner_respects_backoff_limit_and_pause(cluster):
    catalog = EntrypointCatalog()
    runs = []

    @catalog.register("alwaysfail")
    def alwaysfail(ctx):
        runs.append(1)
        raise RuntimeError("nope")

    cluster.create(Job(metadata=ObjectMeta(name="bad", namespace="ns"),
                       spec=JobSpec(entrypoint="alwaysfail", backoff_limit=1)))
    cluster.create(Job(metadata=ObjectMeta(name="paused", namespace="ns"),
                       spec=JobSpec(entrypoint="alwaysfail", parallelism=0)))
    with JobRunner(cluster, catalog):
        cluster.wait_for(
            lambda: cluster.get("Job", "ns", "bad").status.failed > 1,
            timeout=15,
        )
        import time
        time.sleep(0.5)  # give the runner a chance to (incorrectly) re-run
    assert len(runs) == 2  # initial + 1 retry, then backoff limit reached
    assert cluster.get("Job", "ns", "paused").status.succeeded == 0


def test_owner_references_and_events(cluster):
    owner = Volume(metadata=ObjectMeta(name="owner", namespace="ns"))
    cluster.create(owner)
    child = Volume(metadata=ObjectMeta(name="child", namespace="ns"))
    cluster.set_owner(child, owner)
    cluster.create(child)
    assert cluster.is_owned_by(child, owner)
    cluster.record_event(owner, "Normal", "PersistentVolumeClaimCreated",
                         "created child")
    evs = cluster.events_for(owner)
    assert len(evs) == 1 and evs[0].reason == "PersistentVolumeClaimCreated"


def test_late_binding_chain(cluster):
    # snapshot of a not-yet-existing volume, volume restored from that
    # snapshot: everything binds once the root volume appears (CSI late
    # binding analogue).
    snap = cluster.create(VolumeSnapshot(
        metadata=ObjectMeta(name="s", namespace="ns"),
        spec=VolumeSnapshotSpec(source_volume="root"),
    ))
    restored = cluster.create(Volume(
        metadata=ObjectMeta(name="r", namespace="ns"),
        spec=VolumeSpec(data_source={"kind": "VolumeSnapshot", "name": "s"}),
    ))
    assert not snap.status.ready_to_use
    assert restored.status.phase == "Pending"
    root = cluster.create(Volume(metadata=ObjectMeta(name="root", namespace="ns")))
    assert root.status.phase == "Bound"
    assert cluster.get("VolumeSnapshot", "ns", "s").status.ready_to_use
    assert cluster.get("Volume", "ns", "r").status.phase == "Bound"


def test_multihost_init_single_process():
    """Single-host: init_distributed is a safe no-op returning a sane
    summary, and is idempotent."""
    from volsync_tpu.parallel.multihost import init_distributed

    info = init_distributed()
    assert info["process_count"] >= 1
    assert info["global_devices"] >= info["local_devices"] >= 1
    assert init_distributed() == info  # idempotent


def test_multihost_require_fails_hard(monkeypatch):
    """VOLSYNC_DISTRIBUTED=1 is an explicit operator request: a failed
    jax.distributed auto-init must abort, not silently run single-host
    while pod peers block at the coordinator barrier."""
    import jax

    from volsync_tpu.parallel import multihost

    fn = multihost.init_distributed
    saved = getattr(fn, "_done_args", None)
    try:
        if saved is not None:
            del fn._done_args

        def boom():
            raise RuntimeError("no coordinator reachable")

        monkeypatch.setattr(jax.distributed, "initialize", boom)
        with pytest.raises(RuntimeError, match="explicitly requested"):
            multihost.init_distributed(require=True)
        # the implicit path still warns-and-continues — and must NOT
        # latch, or a later require=True would get the cached
        # single-host summary instead of the hard failure
        info = multihost.init_distributed()
        assert info["process_count"] >= 1
        assert getattr(fn, "_done_args", None) is None
        with pytest.raises(RuntimeError, match="explicitly requested"):
            multihost.init_distributed(require=True)
    finally:
        if saved is not None:
            fn._done_args = saved
