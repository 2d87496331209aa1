"""File-fidelity parity with the reference's rsync -H -S flags
(mover-rsync/source.sh:54): hardlink preservation and sparse
materialization through the backup->restore engine."""

import os

import numpy as np
import pytest

from volsync_tpu.engine import TreeBackup, restore_snapshot
from volsync_tpu.objstore import MemObjectStore
from volsync_tpu.repo.repository import Repository

CHUNKER = {"min_size": 4096, "avg_size": 32768, "max_size": 65536,
           "seed": 11, "align": 4096}


def _mkrepo():
    return Repository.init(MemObjectStore(), chunker=CHUNKER)


@pytest.mark.slow
def test_hardlinks_roundtrip(tmp_path, rng):
    src = tmp_path / "src"
    src.mkdir()
    payload = rng.bytes(150_000)
    (src / "a.bin").write_bytes(payload)
    os.link(src / "a.bin", src / "b_link.bin")
    (src / "sub").mkdir()
    os.link(src / "a.bin", src / "sub" / "c_link.bin")
    (src / "solo.bin").write_bytes(rng.bytes(60_000))

    repo = _mkrepo()
    snap, stats = TreeBackup(repo).run(src)
    # linked copies are not re-hashed (one content walk for the inode)
    assert stats.bytes_scanned == 150_000 + 60_000

    dst = tmp_path / "dst"
    restore_snapshot(repo, dst)
    assert (dst / "a.bin").read_bytes() == payload
    assert (dst / "b_link.bin").read_bytes() == payload
    assert (dst / "sub" / "c_link.bin").read_bytes() == payload
    ino = (dst / "a.bin").stat().st_ino
    assert (dst / "b_link.bin").stat().st_ino == ino
    assert (dst / "sub" / "c_link.bin").stat().st_ino == ino
    assert (dst / "a.bin").stat().st_nlink == 3
    assert (dst / "solo.bin").stat().st_ino != ino

    # idempotent second restore: everything skips, links stay intact
    stats2 = restore_snapshot(repo, dst)
    assert stats2["files"] == 0
    assert (dst / "b_link.bin").stat().st_ino == ino


@pytest.mark.slow
def test_hardlink_first_path_removed_between_backups(tmp_path, rng):
    """The secondary's parent entry must NOT feed unchanged-file dedup:
    removing the first-seen name drops nlink 2->1 WITHOUT touching the
    survivor's mtime, and a naive parent match would restore it empty."""
    src = tmp_path / "src"
    src.mkdir()
    payload = rng.bytes(120_000)
    (src / "a.bin").write_bytes(payload)
    os.link(src / "a.bin", src / "b.bin")

    repo = _mkrepo()
    TreeBackup(repo).run(src)

    os.unlink(src / "a.bin")  # b.bin survives, mtime untouched
    snap2, _ = TreeBackup(repo).run(src)

    dst = tmp_path / "dst"
    restore_snapshot(repo, dst)
    assert not (dst / "a.bin").exists()
    assert (dst / "b.bin").read_bytes() == payload


@pytest.mark.slow
def test_sparse_restore_materializes_holes(tmp_path, rng):
    src = tmp_path / "src"
    src.mkdir()
    head = rng.bytes(1 << 20)
    tail = rng.bytes(1 << 20)
    hole = 24 << 20
    # write the source sparsely too (so the test also covers reading one)
    with open(src / "vm.img", "wb") as f:
        f.write(head)
        f.seek(hole, os.SEEK_CUR)
        f.write(tail)

    repo = _mkrepo()
    TreeBackup(repo).run(src)
    dst = tmp_path / "dst"
    restore_snapshot(repo, dst)

    out = dst / "vm.img"
    size = (1 << 20) * 2 + hole
    assert out.stat().st_size == size
    with open(out, "rb") as f:
        assert f.read(1 << 20) == head
        f.seek(hole, os.SEEK_CUR)
        assert f.read() == tail
    # the hole is a hole: allocation far below the logical size
    allocated = out.stat().st_blocks * 512
    assert allocated < size // 2, (allocated, size)


@pytest.mark.slow
def test_sparse_disabled_writes_dense(tmp_path, rng, monkeypatch):
    src = tmp_path / "src"
    src.mkdir()
    data = bytes(8 << 20)  # all zeros
    (src / "z.bin").write_bytes(data)
    repo = _mkrepo()
    TreeBackup(repo).run(src)

    monkeypatch.setenv("VOLSYNC_SPARSE", "0")
    dst = tmp_path / "dense"
    restore_snapshot(repo, dst)
    out = dst / "z.bin"
    assert out.read_bytes() == data
    assert out.stat().st_blocks * 512 >= len(data)


def test_diverged_hardlink_restore_over_linked_dest(tmp_path, rng):
    """Restoring a snapshot where a formerly-linked pair diverged, over
    a destination that still HAS them linked, must break the link
    instead of writing both paths through the shared inode (which would
    corrupt under the worker pool)."""
    src = tmp_path / "src"
    src.mkdir()
    payload = rng.bytes(100_000)
    (src / "a.bin").write_bytes(payload)
    os.link(src / "a.bin", src / "b.bin")
    repo = _mkrepo()
    TreeBackup(repo).run(src)
    dst = tmp_path / "dst"
    restore_snapshot(repo, dst)
    assert (dst / "a.bin").stat().st_ino == (dst / "b.bin").stat().st_ino

    # diverge: b becomes independent content
    os.unlink(src / "b.bin")
    other = rng.bytes(90_000)
    (src / "b.bin").write_bytes(other)
    TreeBackup(repo).run(src)

    restore_snapshot(repo, dst)
    assert (dst / "a.bin").read_bytes() == payload
    assert (dst / "b.bin").read_bytes() == other
    assert (dst / "a.bin").stat().st_ino != (dst / "b.bin").stat().st_ino


def test_xattrs_roundtrip(tmp_path, rng):
    """Extended attributes (the ACL carrier) round-trip through
    backup->restore, reapply on drifted-but-unchanged files, and
    drifted extras are removed."""
    src = tmp_path / "src"
    src.mkdir()
    f = src / "f.bin"
    f.write_bytes(rng.bytes(50_000))
    os.setxattr(f, "user.color", b"blue")
    os.setxattr(f, "user.owner2", b"alice")
    d = src / "sub"
    d.mkdir()
    os.setxattr(d, "user.dtag", b"dir-attr")

    repo = _mkrepo()
    TreeBackup(repo).run(src)
    dst = tmp_path / "dst"
    restore_snapshot(repo, dst)

    out = dst / "f.bin"
    assert os.getxattr(out, "user.color") == b"blue"
    assert os.getxattr(out, "user.owner2") == b"alice"
    assert os.getxattr(dst / "sub", "user.dtag") == b"dir-attr"

    # drift: change one, add an extra — the skipped-unchanged path must
    # still converge the xattrs (they don't touch mtime)
    os.setxattr(out, "user.color", b"red")
    os.setxattr(out, "user.stray", b"x")
    stats = restore_snapshot(repo, dst)
    assert stats["files"] == 0  # content skipped
    assert os.getxattr(out, "user.color") == b"blue"
    assert "user.stray" not in os.listxattr(out)


@pytest.mark.skipif(os.geteuid() != 0, reason="chown needs root")
def test_owner_and_specials_roundtrip(tmp_path, rng):
    """uid/gid (rsync -o -g) and FIFO/socket specials (rsync -D)
    round-trip; device nodes degrade gracefully without CAP_MKNOD."""
    import socket
    import stat as stat_mod

    src = tmp_path / "src"
    src.mkdir()
    f = src / "owned.bin"
    f.write_bytes(rng.bytes(30_000))
    os.chown(f, 1234, 5678)
    os.mkfifo(src / "pipe", 0o640)
    s = socket.socket(socket.AF_UNIX)
    s.bind(str(src / "sock"))
    s.close()

    repo = _mkrepo()
    TreeBackup(repo).run(src)
    dst = tmp_path / "dst"
    restore_snapshot(repo, dst)

    st = (dst / "owned.bin").stat()
    assert (st.st_uid, st.st_gid) == (1234, 5678)
    pst = (dst / "pipe").lstat()
    assert stat_mod.S_ISFIFO(pst.st_mode)
    assert pst.st_mode & 0o7777 == 0o640
    assert stat_mod.S_ISSOCK((dst / "sock").lstat().st_mode)

    # idempotent: second restore skips the specials, keeps them intact
    stats2 = restore_snapshot(repo, dst)
    assert stats2["files"] == 0
    assert stat_mod.S_ISFIFO((dst / "pipe").lstat().st_mode)

    # owner drift on an unchanged file converges (ctime-only change)
    os.chown(dst / "owned.bin", 0, 0)
    restore_snapshot(repo, dst)
    st = (dst / "owned.bin").stat()
    assert (st.st_uid, st.st_gid) == (1234, 5678)


def test_special_replaced_by_file_between_snapshots(tmp_path, rng):
    """Snapshot A has a FIFO at x; snapshot B a regular file. Restoring
    B over A's output must replace the node — opening the FIFO in place
    would block forever on a reader-less pipe."""
    import stat as stat_mod

    src = tmp_path / "src"
    src.mkdir()
    os.mkfifo(src / "x")
    repo = _mkrepo()
    TreeBackup(repo).run(src)
    dst = tmp_path / "dst"
    restore_snapshot(repo, dst)
    assert stat_mod.S_ISFIFO((dst / "x").lstat().st_mode)

    os.unlink(src / "x")
    payload = rng.bytes(20_000)
    (src / "x").write_bytes(payload)
    TreeBackup(repo).run(src)
    restore_snapshot(repo, dst)
    assert (dst / "x").read_bytes() == payload


def test_write_sparse_property(rng, tmp_path):
    """_write_sparse must reproduce EXACT bytes for arbitrary
    compositions of zero runs and data, at every alignment. Uses a
    real file: BytesIO.truncate does NOT zero-extend past EOF the way
    ftruncate does, so it cannot model the trailing-hole contract."""
    from volsync_tpu.engine.restore import _write_sparse

    cases = [
        b"",
        bytes(4096),
        bytes(8192),
        b"x" * 4096,
        bytes(4095),
        bytes(4097),
        b"a" + bytes(4096) + b"b",
        bytes(2048) + b"mid" + bytes(8192),
        rng.bytes(10_000),
    ]
    for _ in range(20):
        parts = []
        for _ in range(int(rng.randint(1, 6))):
            if rng.rand() < 0.5:
                parts.append(bytes(int(rng.randint(0, 3 * 4096))))
            else:
                parts.append(rng.bytes(int(rng.randint(1, 9000))))
        cases.append(b"".join(parts))
    target = tmp_path / "sparse_case"
    for data in cases:
        with open(target, "wb") as f:
            _write_sparse(f, data)
            f.truncate(len(data))  # the caller's trailing-hole truncate
        assert target.read_bytes() == data, len(data)
