"""Mesh-sharded chunk+hash vs the single-chip engine: bit-identity.

The product-path guarantee (SURVEY.md §7 step 5): a backup sharded over
the 8-device mesh must produce exactly the chunks, blob ids, and
snapshots of the single-device path — seams (gear halo, leaf crossings)
are where it would break, so the data here is sized to cross them.
"""

import numpy as np
import pytest

from volsync_tpu.engine.chunker import DeviceChunkHasher, stream_chunks
from volsync_tpu.ops.gearcdc import GearParams
from volsync_tpu.parallel.sharded_chunker import MeshChunkHasher
from volsync_tpu.repo import blobid

PARAMS = GearParams(min_size=4096, avg_size=16384, max_size=65536)


@pytest.fixture(scope="module")
def mesh_hasher():
    return MeshChunkHasher(PARAMS)


@pytest.mark.slow
def test_identical_to_single_chip(mesh_hasher, rng):
    buf = rng.randint(0, 256, size=(2 * 1024 * 1024 + 777,), dtype=np.uint8)
    single = DeviceChunkHasher(PARAMS).process(buf)
    sharded = mesh_hasher.process(buf)
    assert sharded == single
    # coverage: chunks tile the buffer exactly
    pos = 0
    for start, length, _ in sharded:
        assert start == pos
        pos += length
    assert pos == buf.shape[0]


def test_identical_without_eof(mesh_hasher, rng):
    buf = rng.randint(0, 256, size=(1 * 1024 * 1024 + 5,), dtype=np.uint8)
    assert (mesh_hasher.process(buf, eof=False)
            == DeviceChunkHasher(PARAMS).process(buf, eof=False))


def test_pathological_zeros_cut_at_max(mesh_hasher):
    """All-zeros has no candidates anywhere: every cut is a forced
    max_size cut, identically on both engines."""
    buf = np.zeros((512 * 1024 + 3,), dtype=np.uint8)
    sharded = mesh_hasher.process(buf)
    assert sharded == DeviceChunkHasher(PARAMS).process(buf)
    lengths = {length for _, length, _ in sharded[:-1]}
    assert lengths == {PARAMS.max_size}


def test_digests_match_hashlib(mesh_hasher, rng):
    buf = rng.randint(0, 256, size=(700_000,), dtype=np.uint8)
    for start, length, hexd in mesh_hasher.process(buf):
        assert blobid.blob_id(buf[start:start + length].tobytes()) == hexd


def test_small_and_empty_buffers(mesh_hasher):
    assert mesh_hasher.process(np.zeros((0,), np.uint8)) == []
    tiny = np.arange(100, dtype=np.uint8)
    out = mesh_hasher.process(tiny)
    assert out == [(0, 100, blobid.blob_id(tiny.tobytes()))]
    assert mesh_hasher.process(tiny, eof=False) == []


def test_stream_chunks_through_mesh(mesh_hasher, rng):
    """The real streaming path (what TreeBackup calls) over the mesh,
    with a segment size that forces several carry-the-tail iterations."""
    data = rng.bytes(3 * 1024 * 1024 + 999)
    reads = [0]

    def reader_factory(blob):
        view = memoryview(blob)

        def read(n):
            chunk = view[reads[0]: reads[0] + n]
            reads[0] += len(chunk)
            return bytes(chunk)
        return read

    mesh_out = list(stream_chunks(reader_factory(data), PARAMS,
                                  segment_size=1024 * 1024,
                                  hasher=mesh_hasher))
    reads[0] = 0
    single_out = list(stream_chunks(reader_factory(data), PARAMS,
                                    segment_size=1024 * 1024,
                                    hasher=DeviceChunkHasher(PARAMS)))
    assert [(len(c), d) for c, d in mesh_out] == \
        [(len(c), d) for c, d in single_out]
    assert b"".join(c for c, _ in mesh_out) == data


def test_tree_backup_snapshots_bit_identical(tmp_path, rng):
    """Full product path: TreeBackup through the mesh engine produces a
    snapshot whose TREE ID equals the single-device one (tree ids commit
    to every chunk id, so equality here is equality of everything)."""
    from volsync_tpu.engine import TreeBackup, restore_snapshot
    from volsync_tpu.objstore import FsObjectStore
    from volsync_tpu.repo.repository import Repository

    src = tmp_path / "src"
    (src / "d").mkdir(parents=True)
    (src / "big.bin").write_bytes(rng.bytes(2 * 1024 * 1024))
    (src / "d" / "small.txt").write_bytes(b"volsync" * 100)

    def mk_repo(name):
        return Repository.init(FsObjectStore(tmp_path / name), password="pw",
                               chunker={"min_size": 4096, "avg_size": 16384,
                                        "max_size": 65536,
                                        "seed": PARAMS.seed,
                                        "align": PARAMS.align})

    r_single = mk_repo("repo-single")
    snap1, _ = TreeBackup(r_single).run(src)
    r_mesh = mk_repo("repo-mesh")
    hasher = MeshChunkHasher(PARAMS)
    snap2, _ = TreeBackup(r_mesh, hasher=hasher).run(src)

    t1 = dict(r_single.list_snapshots())[snap1]["tree"]
    t2 = dict(r_mesh.list_snapshots())[snap2]["tree"]
    assert t1 == t2

    # and the mesh-written repo restores bit-exactly
    dest = tmp_path / "restored"
    restore_snapshot(r_mesh, dest)
    assert (dest / "big.bin").read_bytes() == (src / "big.bin").read_bytes()


@pytest.mark.slow
def test_restic_mover_e2e_mesh_engine(tmp_path, rng):
    """VOLSYNC_ENGINE=mesh in the mover env routes the real backup Job
    through the sharded engine (SURVEY §7 step 5 done-condition)."""
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
    from volsync_tpu.objstore import FsObjectStore
    from volsync_tpu.repo.repository import Repository

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

        pathlib.Path(vol.status.path, "f.bin").write_bytes(
            rng.bytes(2 * 1024 * 1024))
        cluster.create(Secret(
            metadata=ObjectMeta(name="sec", namespace="default"),
            data={"RESTIC_REPOSITORY": str(tmp_path / "meshrepo").encode(),
                  "RESTIC_PASSWORD": b"pw",
                  "VOLSYNC_ENGINE": b"mesh"}))
        cluster.create(ReplicationSource(
            metadata=ObjectMeta(name="bk", namespace="default"),
            spec=ReplicationSourceSpec(
                source_pvc="d", trigger=ReplicationTrigger(manual="go"),
                restic=ReplicationSourceResticSpec(
                    repository="sec", copy_method=CopyMethod.CLONE))))
        assert cluster.wait_for(lambda: (
            (cr := cluster.try_get("ReplicationSource", "default", "bk"))
            and cr.status and cr.status.last_manual_sync == "go"),
            timeout=120, poll=0.05)
        repo = Repository.open(FsObjectStore(tmp_path / "meshrepo"),
                               password="pw")
        snaps = repo.list_snapshots()
        assert len(snaps) == 1
        assert repo.check() == []
    finally:
        manager.stop()
        runner.stop()


# ---------------------------------------------------------------------------
# Fused page-aligned mesh path (align == LEAF): one dispatch, one fetch,
# replicated walk+roots over all-gathered page digests.
# ---------------------------------------------------------------------------

FUSED = GearParams(min_size=4096, avg_size=32768, max_size=65536, align=4096)


@pytest.fixture(scope="module")
def fused_mesh_hasher():
    return MeshChunkHasher(FUSED)


@pytest.mark.slow
def test_fused_mesh_identical_to_single_chip(fused_mesh_hasher, rng):
    buf = rng.randint(0, 256, size=(2 * 1024 * 1024 + 777,), dtype=np.uint8)
    single = DeviceChunkHasher(FUSED).process(buf)
    sharded = fused_mesh_hasher.process(buf)
    assert sharded == single
    pos = 0
    for start, length, _ in sharded:
        assert start == pos
        pos += length
    assert pos == buf.shape[0]
    for s, l, d in sharded[:3]:
        assert d == blobid.blob_id(buf.tobytes()[s: s + l])


@pytest.mark.slow
def test_fused_mesh_without_eof(fused_mesh_hasher, rng):
    buf = rng.randint(0, 256, size=(1_500_000,), dtype=np.uint8)
    single = DeviceChunkHasher(FUSED).process(buf, eof=False)
    sharded = fused_mesh_hasher.process(buf, eof=False)
    assert sharded == single
    end = sum(l for _, l, _ in sharded)
    assert 0 < end < buf.shape[0] and end % 4096 == 0


@pytest.mark.slow
def test_fused_mesh_zero_entropy_max_cuts(fused_mesh_hasher):
    buf = np.zeros((1_000_000,), np.uint8)
    sharded = fused_mesh_hasher.process(buf)
    assert sharded == DeviceChunkHasher(FUSED).process(buf)
    assert all(l <= FUSED.max_size for _, l, _ in sharded)
    # constant data -> every chunk identical -> total dedup
    assert len({d for _, _, d in sharded[:-1]}) == 1


@pytest.mark.slow
def test_fused_mesh_capacity_retry(rng):
    # chunk_cap starts far too small for the chunk count this data
    # produces; the in-band counts must drive the doubling retry.
    h = MeshChunkHasher(FUSED)
    buf = rng.randint(0, 256, size=(2 * 1024 * 1024,), dtype=np.uint8)
    out_normal = h.process(buf)
    h2 = MeshChunkHasher(FUSED)
    import volsync_tpu.ops.segment as seg
    real_caps = seg.segment_caps

    def tiny_caps(padded, params):
        return 1024 * 8, 16  # chunk_cap=16 << ~64 chunks

    seg.segment_caps = tiny_caps
    try:
        out_tiny = h2.process(buf)
    finally:
        seg.segment_caps = real_caps
    assert out_tiny == out_normal


@pytest.mark.slow
def test_graft_entry_dryrun():
    import jax

    import __graft_entry__ as ge

    fn, args = ge.entry()
    jax.jit(fn).lower(*args)  # compiles
    ge.dryrun_multichip(8)
