"""Lifecycle e2e breadth: schedule triggers, paused CRs, backoff-limit
recreate, and do-not-delete snapshots — end-to-end through the real
substrate (the reference covers these in its envtest + Ansible tiers;
a review record since deleted flagged them as unit-only here).
"""

import pathlib
import time
from datetime import datetime, timezone

import pytest

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
from volsync_tpu.controller import utils
from volsync_tpu.controller.manager import Manager
from volsync_tpu.controller.reconcilers import ReplicationSourceReconciler
from volsync_tpu.metrics import Metrics
from volsync_tpu.movers import restic as restic_mover
from volsync_tpu.movers.base import Catalog
from volsync_tpu.objstore import FsObjectStore
from volsync_tpu.repo.repository import Repository


@pytest.fixture
def world(tmp_path):
    cluster = Cluster(storage=StorageProvider(tmp_path / "storage"))
    catalog = Catalog()
    rc = EntrypointCatalog()
    restic_mover.register(catalog, rc)
    runner = JobRunner(cluster, rc).start()
    yield cluster, catalog, tmp_path
    runner.stop()


def _volume(cluster, name, payload: bytes):
    vol = cluster.create(Volume(
        metadata=ObjectMeta(name=name, namespace="default"),
        spec=VolumeSpec(capacity=1 << 30)))
    pathlib.Path(vol.status.path, "f.bin").write_bytes(payload)
    return vol


def _secret(cluster, tmp_path, name="sec", repo="repo"):
    return cluster.create(Secret(
        metadata=ObjectMeta(name=name, namespace="default"),
        data={"RESTIC_REPOSITORY": str(tmp_path / repo).encode(),
              "RESTIC_PASSWORD": b"pw"}))


def _drive(reconciler, name, now, *, until, timeout=30.0):
    """Reconcile repeatedly at the injected wall-clock instant until the
    predicate holds (the mover Jobs run concurrently on the real
    runner)."""
    end = time.monotonic() + timeout
    while time.monotonic() < end:
        reconciler.reconcile("default", name, now=now)
        if until():
            return True
        time.sleep(0.05)
    return until()


def test_schedule_trigger_fires_per_cron(world, rng):
    """Cron schedule end-to-end with an injected clock: a sync fires when
    the schedule comes due, not before; nextSyncTime is published; the
    next tick produces a second snapshot (test_replication_schedule.yml
    analogue)."""
    cluster, catalog, tmp_path = world
    _volume(cluster, "d", rng.bytes(100_000))
    _secret(cluster, tmp_path)
    rec = ReplicationSourceReconciler(cluster, catalog, Metrics())
    rs = ReplicationSource(
        metadata=ObjectMeta(name="sched", namespace="default"),
        spec=ReplicationSourceSpec(
            source_pvc="d",
            trigger=ReplicationTrigger(schedule="*/2 * * * *"),
            restic=ReplicationSourceResticSpec(
                repository="sec", copy_method=CopyMethod.CLONE)),
    )
    cluster.create(rs)
    # Pin the schedule anchor (the machine anchors nextSyncTime to the
    # CR's creation, machine.go:280-297) into the injected clock's epoch.
    cr = cluster.get("ReplicationSource", "default", "sched")
    cr.metadata.creation_timestamp = datetime(
        2026, 1, 1, 12, 0, 0, tzinfo=timezone.utc)
    cluster.update(cr)

    # Before the slot comes due: the machine waits, publishing the slot.
    t0 = datetime(2026, 1, 1, 12, 0, 30, tzinfo=timezone.utc)
    for _ in range(5):
        rec.reconcile("default", "sched", now=t0)
    cr = cluster.get("ReplicationSource", "default", "sched")
    assert cr.status.last_sync_time is None
    assert cr.status.next_sync_time == datetime(
        2026, 1, 1, 12, 2, tzinfo=timezone.utc)
    assert any(c.reason == "WaitingForSchedule"
               for c in cr.status.conditions)
    assert cluster.try_get("Job", "default", "volsync-src-sched") is None

    # The slot fires: a real mover Job runs and a snapshot lands.
    t1 = datetime(2026, 1, 1, 12, 2, 5, tzinfo=timezone.utc)
    assert _drive(rec, "sched", t1, until=lambda: (
        (c := cluster.get("ReplicationSource", "default", "sched")).status
        and c.status.last_sync_time is not None))
    repo = Repository.open(FsObjectStore(tmp_path / "repo"), password="pw")
    assert len(repo.list_snapshots()) == 1

    # The next tick produces a second snapshot.
    t2 = datetime(2026, 1, 1, 12, 4, 5, tzinfo=timezone.utc)
    assert _drive(rec, "sched", t2, until=lambda: (
        len(Repository.open(FsObjectStore(tmp_path / "repo"),
                            password="pw").list_snapshots()) == 2))


def test_paused_cr_holds_job_until_unpaused(world, rng):
    """paused=true parks the mover Job at parallelism 0 (the runner never
    starts it); unpausing releases the sync (rsync/mover.go:366-370)."""
    cluster, catalog, tmp_path = world
    _volume(cluster, "d2", rng.bytes(50_000))
    _secret(cluster, tmp_path, repo="repo2")
    manager = Manager(cluster, catalog=catalog, metrics=Metrics()).start()
    try:
        rs = ReplicationSource(
            metadata=ObjectMeta(name="pz", namespace="default"),
            spec=ReplicationSourceSpec(
                source_pvc="d2", paused=True,
                trigger=ReplicationTrigger(manual="go"),
                restic=ReplicationSourceResticSpec(
                    repository="sec", copy_method=CopyMethod.CLONE)),
        )
        cluster.create(rs)
        assert cluster.wait_for(lambda: (
            (j := cluster.try_get("Job", "default", "volsync-src-pz"))
            is not None and j.spec.parallelism == 0), timeout=20, poll=0.05)
        time.sleep(0.5)  # runner must NOT pick it up
        job = cluster.get("Job", "default", "volsync-src-pz")
        assert job.status.succeeded == 0 and job.status.active == 0
        cr = cluster.get("ReplicationSource", "default", "pz")
        assert not (cr.status and cr.status.last_manual_sync == "go")

        cr.spec.paused = False
        cluster.update(cr)
        assert cluster.wait_for(lambda: (
            (c := cluster.try_get("ReplicationSource", "default", "pz"))
            and c.status and c.status.last_manual_sync == "go"),
            timeout=30, poll=0.05)
    finally:
        manager.stop()


@pytest.mark.slow
def test_backoff_limit_recreates_job_and_recovers(world, rng):
    """A misconfigured mover fails past its backoff limit: the Job is
    deleted + recreated fresh with a TransferFailed event
    (rsync/mover.go:436-443); fixing the config lets the sync complete."""
    cluster, catalog, tmp_path = world
    _volume(cluster, "d3", rng.bytes(50_000))
    # Broken: repository points at an unwritable path.
    cluster.create(Secret(
        metadata=ObjectMeta(name="sec", namespace="default"),
        data={"RESTIC_REPOSITORY": b"/proc/definitely/not/writable",
              "RESTIC_PASSWORD": b"pw"}))
    manager = Manager(cluster, catalog=catalog, metrics=Metrics()).start()
    try:
        rs = ReplicationSource(
            metadata=ObjectMeta(name="bk", namespace="default"),
            spec=ReplicationSourceSpec(
                source_pvc="d3", trigger=ReplicationTrigger(manual="go"),
                restic=ReplicationSourceResticSpec(
                    repository="sec", copy_method=CopyMethod.CLONE)),
        )
        cluster.create(rs)
        first = None

        def saw_recreate():
            nonlocal first
            job = cluster.try_get("Job", "default", "volsync-src-bk")
            if job is None:
                return False
            if first is None and job.status.failed > 0:
                first = job.metadata.uid
            return (first is not None
                    and job.metadata.uid != first)

        assert cluster.wait_for(saw_recreate, timeout=60, poll=0.05), \
            "job was never recreated after exhausting its backoff limit"
        evs = cluster.events_for(
            cluster.get("ReplicationSource", "default", "bk"))
        assert any(e.reason == "TransferFailed"
                   and "backoff" in e.message for e in evs)

        # Fix the config: the retry machinery completes the sync.
        sec = cluster.get("Secret", "default", "sec")
        sec.data["RESTIC_REPOSITORY"] = str(tmp_path / "repo3").encode()
        cluster.update(sec)
        assert cluster.wait_for(lambda: (
            (c := cluster.try_get("ReplicationSource", "default", "bk"))
            and c.status and c.status.last_manual_sync == "go"),
            timeout=60, poll=0.05)
    finally:
        manager.stop()


def test_do_not_delete_snapshot_is_relinquished(world, rng):
    """A user-labeled do-not-delete snapshot survives being superseded:
    VolSync relinquishes ownership instead of deleting it
    (utils/cleanup.go:95-117; test via RD latestImage swap)."""
    from volsync_tpu.api.types import (
        ReplicationDestination,
        ReplicationDestinationResticSpec,
        ReplicationDestinationSpec,
    )

    cluster, catalog, tmp_path = world
    _volume(cluster, "seed", rng.bytes(60_000))
    _secret(cluster, tmp_path, repo="repo4")
    manager = Manager(cluster, catalog=catalog, metrics=Metrics()).start()
    try:
        # Seed the repository with one snapshot.
        rs = ReplicationSource(
            metadata=ObjectMeta(name="seed", namespace="default"),
            spec=ReplicationSourceSpec(
                source_pvc="seed", trigger=ReplicationTrigger(manual="one"),
                restic=ReplicationSourceResticSpec(
                    repository="sec", copy_method=CopyMethod.CLONE)),
        )
        cluster.create(rs)
        assert cluster.wait_for(lambda: (
            (c := cluster.try_get("ReplicationSource", "default", "seed"))
            and c.status and c.status.last_manual_sync == "one"),
            timeout=60, poll=0.05)

        rd = ReplicationDestination(
            metadata=ObjectMeta(name="rst", namespace="default"),
            spec=ReplicationDestinationSpec(
                trigger=ReplicationTrigger(manual="one"),
                restic=ReplicationDestinationResticSpec(
                    repository="sec", copy_method=CopyMethod.SNAPSHOT)),
        )
        cluster.create(rd)
        assert cluster.wait_for(lambda: (
            (c := cluster.try_get("ReplicationDestination", "default",
                                  "rst"))
            and c.status and c.status.latest_image is not None
            # the iteration has ended: an update of the spec below may
            # not race the controller's last writes of this one
            and c.status.last_manual_sync == "one"),
            timeout=60, poll=0.05)
        cr = cluster.get("ReplicationDestination", "default", "rst")
        protected = cr.status.latest_image.name
        snap = cluster.get("VolumeSnapshot", "default", protected)
        snap.metadata.labels[utils.DO_NOT_DELETE_LABEL] = "true"
        cluster.update(snap)

        # Supersede it with a second restore iteration.
        cr.spec.trigger = ReplicationTrigger(manual="two")
        cluster.update(cr)
        assert cluster.wait_for(lambda: (
            (c := cluster.try_get("ReplicationDestination", "default",
                                  "rst"))
            and c.status and c.status.last_manual_sync == "two"
            and c.status.latest_image
            and c.status.latest_image.name != protected),
            timeout=60, poll=0.05)

        # The protected snapshot still exists, unowned (relinquished).
        assert cluster.wait_for(lambda: (
            (s := cluster.try_get("VolumeSnapshot", "default", protected))
            is not None
            and utils.CREATED_BY_LABEL not in s.metadata.labels
            and not s.metadata.owner_references), timeout=60, poll=0.05)
    finally:
        manager.stop()
