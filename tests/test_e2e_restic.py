"""End-to-end: ReplicationSource backup -> ReplicationDestination restore.

The in-process analogue of the reference's restic e2e playbooks
(test-e2e/test_restic_manual_*.yml): real cluster substrate, real
storage provider, real runner executing the data-plane entrypoint, real
repository — only the hardware is the test CPU mesh.
"""

import json
import time

import pytest

from volsync_tpu.api.common import CopyMethod, ObjectMeta
from volsync_tpu.api.types import (
    ReplicationDestination,
    ReplicationDestinationResticSpec,
    ReplicationDestinationSpec,
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
from volsync_tpu.movers.base import Catalog
from volsync_tpu.movers import restic as restic_mover
from volsync_tpu.obs import reset_spans


@pytest.fixture
def world(tmp_path):
    """cluster + storage + runner + manager with the restic mover."""
    cluster = Cluster(storage=StorageProvider(tmp_path / "storage"))
    catalog = Catalog()
    runner_catalog = EntrypointCatalog()
    restic_mover.register(catalog, runner_catalog)
    runner = JobRunner(cluster, runner_catalog).start()
    manager = Manager(cluster, catalog=catalog, metrics=Metrics()).start()
    yield cluster, tmp_path
    manager.stop()
    runner.stop()


def make_volume(cluster, name, files: dict, ns="default"):
    vol = cluster.create(Volume(metadata=ObjectMeta(name=name, namespace=ns),
                                spec=VolumeSpec(capacity=1 << 30)))
    import pathlib

    root = pathlib.Path(vol.status.path)
    for rel, content in files.items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_bytes(content)
    return vol


def repo_secret(cluster, tmp_path, name="repo-secret", ns="default"):
    return cluster.create(Secret(
        metadata=ObjectMeta(name=name, namespace=ns),
        data={"RESTIC_REPOSITORY": str(tmp_path / "repo").encode(),
              "RESTIC_PASSWORD": b"hunter2"},
    ))


def wait(cluster, pred, timeout=30.0):
    assert cluster.wait_for(pred, timeout=timeout, poll=0.05), "timed out"


def assert_stored_as_the_reference_cuts(tmp_path, files: dict) -> None:
    """Every snapshot of the test's repository lists each file with the
    ids of the benchmark's plain references (numpy gear CDC, hashlib
    blob ids) over its bytes: the same whichever way the engine went."""
    from benchmark.reference import blobid as ref_blobid
    from benchmark.reference import gearcdc as ref_gearcdc
    from volsync_tpu.engine.chunker import params_from_config
    from volsync_tpu.objstore import FsObjectStore
    from volsync_tpu.repo.repository import DEFAULT_CHUNKER, Repository

    p = params_from_config(DEFAULT_CHUNKER)
    chunker = {**DEFAULT_CHUNKER, "norm_level": p.norm_level}
    want = {rel: [ref_blobid.blob_id(data[s: s + n]) for s, n in (
                ref_gearcdc.cuts(data, chunker) if len(data) > p.min_size
                else [(0, len(data))])]
            for rel, data in files.items()}
    repo = Repository.open(FsObjectStore(tmp_path / "repo"),
                           password="hunter2")

    def walk(tree_id, prefix=""):
        for e in json.loads(repo.read_blob(tree_id))["entries"]:
            if e["type"] == "dir":
                yield from walk(e["subtree"], prefix + e["name"] + "/")
            else:
                yield prefix + e["name"], e["content"]

    snaps = repo.list_snapshots()
    assert snaps
    for _, snap in snaps:
        assert dict(walk(snap["tree"])) == want


def assert_way_to_the_device(batched: bool) -> None:
    """A file went to the device, and by the way the case names."""
    from volsync_tpu.obs import span_totals

    spans = span_totals()
    assert ("ops.batch_dispatch" in spans) == batched
    assert ("engine.fused_dispatch" in spans) != batched


def test_backup_then_restore_roundtrip(world, rng, batched):
    cluster, tmp_path = world
    reset_spans()
    # sub/b.bin is above the default chunker's min_size: a device file
    files = {"a.txt": b"alpha" * 1000, "sub/b.bin": rng.bytes(700_000)}
    make_volume(cluster, "app-data", files)
    repo_secret(cluster, tmp_path)

    rs = ReplicationSource(
        metadata=ObjectMeta(name="backup", namespace="default"),
        spec=ReplicationSourceSpec(
            source_pvc="app-data",
            trigger=ReplicationTrigger(manual="first"),
            restic=ReplicationSourceResticSpec(
                repository="repo-secret", copy_method=CopyMethod.SNAPSHOT),
        ),
    )
    cluster.create(rs)
    wait(cluster, lambda: (
        (cr := cluster.try_get("ReplicationSource", "default", "backup"))
        and cr.status and cr.status.last_manual_sync == "first"))

    cr = cluster.get("ReplicationSource", "default", "backup")
    assert cr.status.last_sync_time is not None
    assert cr.status.last_sync_duration is not None

    # destination: restore into a fresh volume
    rd = ReplicationDestination(
        metadata=ObjectMeta(name="restore", namespace="default"),
        spec=ReplicationDestinationSpec(
            trigger=ReplicationTrigger(manual="first"),
            restic=ReplicationDestinationResticSpec(
                repository="repo-secret", copy_method=CopyMethod.SNAPSHOT),
        ),
    )
    cluster.create(rd)
    wait(cluster, lambda: (
        (cr := cluster.try_get("ReplicationDestination", "default", "restore"))
        and cr.status and cr.status.last_manual_sync == "first"))

    cr = cluster.get("ReplicationDestination", "default", "restore")
    assert cr.status.latest_image is not None
    assert cr.status.latest_image.kind == "VolumeSnapshot"
    snap = cluster.get("VolumeSnapshot", "default",
                       cr.status.latest_image.name)
    assert snap.status.ready_to_use
    import pathlib

    restored = pathlib.Path(snap.status.bound_content)
    for rel, content in files.items():
        assert (restored / rel).read_bytes() == content

    # cleanup happened: the mover Job was collected after the iteration
    wait(cluster, lambda: cluster.try_get("Job", "default",
                                          "volsync-src-backup") is None)
    assert_way_to_the_device(batched)
    assert_stored_as_the_reference_cuts(tmp_path, files)


def test_second_manual_sync_is_incremental(world, rng, batched):
    cluster, tmp_path = world
    reset_spans()
    files = {"f.bin": rng.bytes(600_000)}  # above min_size: a device file
    vol = make_volume(cluster, "data2", files)
    repo_secret(cluster, tmp_path)
    rs = ReplicationSource(
        metadata=ObjectMeta(name="inc", namespace="default"),
        spec=ReplicationSourceSpec(
            source_pvc="data2",
            trigger=ReplicationTrigger(manual="one"),
            restic=ReplicationSourceResticSpec(
                repository="repo-secret", copy_method=CopyMethod.CLONE),
        ),
    )
    cluster.create(rs)
    wait(cluster, lambda: (
        (cr := cluster.try_get("ReplicationSource", "default", "inc"))
        and cr.status and cr.status.last_manual_sync == "one"))

    # trigger again with a new tag
    cr = cluster.get("ReplicationSource", "default", "inc")
    cr.spec.trigger = ReplicationTrigger(manual="two")
    cluster.update(cr)
    wait(cluster, lambda: (
        (cr := cluster.try_get("ReplicationSource", "default", "inc"))
        and cr.status and cr.status.last_manual_sync == "two"))

    from volsync_tpu.objstore import FsObjectStore
    from volsync_tpu.repo.repository import Repository

    repo = Repository.open(FsObjectStore(tmp_path / "repo"),
                           password="hunter2")
    snaps = repo.list_snapshots()
    assert len(snaps) == 2
    # second snapshot deduped everything (parent skip or blob dedup)
    assert snaps[1][1]["stats"]["bytes_new"] == 0
    assert snaps[0][1]["tree"] == snaps[1][1]["tree"]
    assert_way_to_the_device(batched)
    assert_stored_as_the_reference_cuts(tmp_path, files)


def test_misconfigured_spec_surfaces_error(world):
    cluster, tmp_path = world
    rs = ReplicationSource(
        metadata=ObjectMeta(name="broken", namespace="default"),
        spec=ReplicationSourceSpec(source_pvc="nope"),  # no mover section
    )
    cluster.create(rs)
    wait(cluster, lambda: (
        (cr := cluster.try_get("ReplicationSource", "default", "broken"))
        and cr.status and any(
            c.reason == "Error" for c in cr.status.conditions)))


@pytest.mark.slow
def test_point_in_time_restore_selectors(world):
    """The reference's test_restic_restore_previous / restoreAsOf
    playbooks: three backups of evolving content, then destinations
    selecting (a) previous=1 (one before latest) and (b) restoreAsOf a
    timestamp between backup 1 and 2 — each restored image must hold
    exactly that epoch's content."""
    import pathlib
    from datetime import datetime, timezone

    cluster, tmp_path = world
    vol = make_volume(cluster, "app-data", {"f.txt": b"epoch-1"})
    repo_secret(cluster, tmp_path)
    root = pathlib.Path(vol.status.path)

    rs = ReplicationSource(
        metadata=ObjectMeta(name="backup", namespace="default"),
        spec=ReplicationSourceSpec(
            source_pvc="app-data",
            trigger=ReplicationTrigger(manual="s1"),
            restic=ReplicationSourceResticSpec(
                repository="repo-secret", copy_method=CopyMethod.SNAPSHOT),
        ),
    )
    cluster.create(rs)

    def backed_up(tag):
        return lambda: (
            (cr := cluster.try_get("ReplicationSource", "default", "backup"))
            and cr.status and cr.status.last_manual_sync == tag)

    wait(cluster, backed_up("s1"))
    t_between = datetime.now(timezone.utc)
    time.sleep(0.05)

    for tag, content in (("s2", b"epoch-2"), ("s3", b"epoch-3")):
        (root / "f.txt").write_bytes(content)
        cr = cluster.get("ReplicationSource", "default", "backup")
        cr.spec.trigger.manual = tag
        cluster.update(cr)
        wait(cluster, backed_up(tag))

    def restore(name, **sel):
        rd = ReplicationDestination(
            metadata=ObjectMeta(name=name, namespace="default"),
            spec=ReplicationDestinationSpec(
                trigger=ReplicationTrigger(manual="go"),
                restic=ReplicationDestinationResticSpec(
                    repository="repo-secret",
                    copy_method=CopyMethod.SNAPSHOT, **sel),
            ),
        )
        cluster.create(rd)
        wait(cluster, lambda: (
            (cr := cluster.try_get("ReplicationDestination", "default", name))
            and cr.status and cr.status.last_manual_sync == "go"))
        cr = cluster.get("ReplicationDestination", "default", name)
        snap = cluster.get("VolumeSnapshot", "default",
                           cr.status.latest_image.name)
        return (pathlib.Path(snap.status.bound_content) / "f.txt").read_bytes()

    assert restore("r-latest") == b"epoch-3"
    assert restore("r-prev", previous=1) == b"epoch-2"
    assert restore("r-asof", restore_as_of=t_between) == b"epoch-1"


def test_chunker_align_knob(tmp_path):
    """VOLSYNC_CHUNKER_ALIGN selects the CDC alignment at repo CREATION
    (insert-heavy workloads trade the fused engine for shift-invariant
    cuts); existing repos keep their stored chunker config."""
    from volsync_tpu.movers.restic.entry import _open_or_init

    env = {"RESTIC_REPOSITORY": f"file://{tmp_path / 'r1'}",
           "VOLSYNC_CHUNKER_ALIGN": "64"}
    repo = _open_or_init(env)
    assert repo.chunker_params["align"] == 64
    # reopen WITHOUT the knob: stored config wins
    repo2 = _open_or_init({"RESTIC_REPOSITORY": f"file://{tmp_path / 'r1'}"})
    assert repo2.chunker_params["align"] == 64

    import pytest as _pytest

    with _pytest.raises(ValueError, match="CHUNKER_ALIGN"):
        _open_or_init({"RESTIC_REPOSITORY": f"file://{tmp_path / 'r2'}",
                       "VOLSYNC_CHUNKER_ALIGN": "512"})


@pytest.mark.slow
def test_cr_path_preserves_fidelity(world, rng):
    """Fidelity through the FULL operator path (CR -> mover Job ->
    engine -> restore CR): hardlinks, xattrs, sparse files, and a FIFO
    survive the round trip — proving the mover glue passes the
    engine's -aAhHSxz surface through untouched."""
    import os
    import pathlib
    import stat as stat_mod

    cluster, tmp_path = world
    make_volume(cluster, "fid-data", {"a.bin": rng.bytes(120_000)})
    vol = cluster.get("Volume", "default", "fid-data")
    root = pathlib.Path(vol.status.path)
    os.link(root / "a.bin", root / "a_link.bin")
    os.setxattr(root / "a.bin", "user.team", b"storage")
    os.mkfifo(root / "queue.fifo", 0o600)
    with open(root / "sparse.img", "wb") as f:
        f.write(b"S" * 4096)
        f.seek(6 << 20, os.SEEK_CUR)
        f.write(b"E" * 4096)
    repo_secret(cluster, tmp_path)

    rs = ReplicationSource(
        metadata=ObjectMeta(name="fid", namespace="default"),
        spec=ReplicationSourceSpec(
            source_pvc="fid-data",
            trigger=ReplicationTrigger(manual="one"),
            restic=ReplicationSourceResticSpec(
                repository="repo-secret", copy_method=CopyMethod.SNAPSHOT),
        ),
    )
    cluster.create(rs)
    wait(cluster, lambda: (
        (cr := cluster.try_get("ReplicationSource", "default", "fid"))
        and cr.status and cr.status.last_manual_sync == "one"))

    rd = ReplicationDestination(
        metadata=ObjectMeta(name="fid-rst", namespace="default"),
        spec=ReplicationDestinationSpec(
            trigger=ReplicationTrigger(manual="one"),
            restic=ReplicationDestinationResticSpec(
                repository="repo-secret", copy_method=CopyMethod.SNAPSHOT),
        ),
    )
    cluster.create(rd)
    wait(cluster, lambda: (
        (cr := cluster.try_get("ReplicationDestination", "default",
                               "fid-rst"))
        and cr.status and cr.status.last_manual_sync == "one"))

    cr = cluster.get("ReplicationDestination", "default", "fid-rst")
    snap = cluster.get("VolumeSnapshot", "default",
                       cr.status.latest_image.name)
    restored = pathlib.Path(snap.status.bound_content)
    assert (restored / "a.bin").read_bytes() \
        == (root / "a.bin").read_bytes()
    assert (restored / "a.bin").stat().st_ino \
        == (restored / "a_link.bin").stat().st_ino
    assert os.getxattr(restored / "a.bin", "user.team") == b"storage"
    assert stat_mod.S_ISFIFO((restored / "queue.fifo").lstat().st_mode)
    sp = restored / "sparse.img"
    assert sp.stat().st_size == 8192 + (6 << 20)
    assert sp.stat().st_blocks * 512 < sp.stat().st_size // 2


def test_cr_path_over_swift_repository(world, rng):
    """The CR -> builder -> mover-job -> engine stack against a Swift
    repository: the Secret carries restic's swift URL + the OS_* env
    family, the builder passes every key through to the mover env
    (mover.go:331-363 passthrough), and backup + restore round-trip
    over Keystone-authenticated object storage."""
    from volsync_tpu.objstore.fakeswift import FakeSwiftServer

    cluster, tmp_path = world
    files = {"a.txt": b"swift" * 2000, "sub/b.bin": rng.bytes(250_000)}
    make_volume(cluster, "swift-data", files)
    with FakeSwiftServer() as srv:
        cluster.create(Secret(
            metadata=ObjectMeta(name="swift-secret", namespace="default"),
            data={"RESTIC_REPOSITORY": b"swift:backups:/cr-repo",
                  "RESTIC_PASSWORD": b"hunter2",
                  "OS_AUTH_URL": f"{srv.endpoint}/v3".encode(),
                  "OS_USERNAME": srv.username.encode(),
                  "OS_PASSWORD": srv.password.encode(),
                  "OS_PROJECT_NAME": srv.project.encode(),
                  "OS_REGION_NAME": srv.region.encode()},
        ))
        rs = ReplicationSource(
            metadata=ObjectMeta(name="swift-backup", namespace="default"),
            spec=ReplicationSourceSpec(
                source_pvc="swift-data",
                trigger=ReplicationTrigger(manual="first"),
                restic=ReplicationSourceResticSpec(
                    repository="swift-secret",
                    copy_method=CopyMethod.SNAPSHOT),
            ),
        )
        cluster.create(rs)
        wait(cluster, lambda: (
            (cr := cluster.try_get("ReplicationSource", "default",
                                   "swift-backup"))
            and cr.status and cr.status.last_manual_sync == "first"))

        rd = ReplicationDestination(
            metadata=ObjectMeta(name="swift-restore", namespace="default"),
            spec=ReplicationDestinationSpec(
                trigger=ReplicationTrigger(manual="first"),
                restic=ReplicationDestinationResticSpec(
                    repository="swift-secret",
                    copy_method=CopyMethod.SNAPSHOT),
            ),
        )
        cluster.create(rd)
        wait(cluster, lambda: (
            (cr := cluster.try_get("ReplicationDestination", "default",
                                   "swift-restore"))
            and cr.status and cr.status.last_manual_sync == "first"))

        cr = cluster.get("ReplicationDestination", "default",
                         "swift-restore")
        snap = cluster.get("VolumeSnapshot", "default",
                           cr.status.latest_image.name)
        import pathlib

        restored = pathlib.Path(snap.status.bound_content)
        for rel, content in files.items():
            assert (restored / rel).read_bytes() == content
