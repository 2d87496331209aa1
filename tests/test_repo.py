"""Repository + engine tests: round-trips, dedup, retention, prune,
encryption, point-in-time selection.

Mirrors the semantics the reference exercises in its restic e2e
playbooks (test-e2e/test_restic_*: manual trigger, previous,
restoreAsOf) but at the unit tier against the in-memory store.
"""

import json
import os
import time
from datetime import datetime, timedelta, timezone

import numpy as np
import pytest

from volsync_tpu.engine import TreeBackup, restore_snapshot
from volsync_tpu.objstore import FsObjectStore, MemObjectStore
from volsync_tpu.repo import crypto
from volsync_tpu.repo.repository import Repository

SMALL_CHUNKER = {"min_size": 1024, "avg_size": 4096, "max_size": 16384,
                 "seed": 7}


def make_repo(store=None, password=None):
    return Repository.init(store or MemObjectStore(), password=password,
                           chunker=SMALL_CHUNKER)


def write_tree(root, files: dict):
    for rel, content in files.items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_bytes(content)


def trees_equal(a, b):
    for root, other in ((a, b), (b, a)):
        for dirpath, _, files in os.walk(root):
            for f in files:
                src = os.path.join(dirpath, f)
                rel = os.path.relpath(src, root)
                dst = os.path.join(other, rel)
                if not os.path.exists(dst):
                    return False
                with open(src, "rb") as fa, open(dst, "rb") as fb:
                    if fa.read() != fb.read():
                        return False
    return True


def test_backup_restore_roundtrip(tmp_path, rng):
    src, dst = tmp_path / "src", tmp_path / "dst"
    src.mkdir()
    write_tree(src, {
        "a.txt": b"hello world\n" * 100,
        "big.bin": rng.bytes(150_000),
        "sub/deep/c.bin": rng.bytes(30_000),
        "empty": b"",
    })
    (src / "link").symlink_to("a.txt")
    os.chmod(src / "a.txt", 0o640)

    repo = make_repo()
    snap_id, stats = TreeBackup(repo).run(src)
    assert snap_id is not None
    assert stats.files == 4
    assert stats.bytes_scanned == sum(
        (src / f).stat().st_size for f in ("a.txt", "big.bin",
                                           "sub/deep/c.bin", "empty"))
    out = restore_snapshot(repo, dst)
    assert out is not None and out["files"] == 4
    assert trees_equal(src, dst)
    assert os.readlink(dst / "link") == "a.txt"
    assert (dst / "a.txt").stat().st_mode & 0o777 == 0o640
    assert (dst / "a.txt").stat().st_mtime_ns == (src / "a.txt").stat().st_mtime_ns


def test_incremental_backup_dedups_unchanged(tmp_path, rng):
    src = tmp_path / "src"
    src.mkdir()
    write_tree(src, {"stable.bin": rng.bytes(100_000),
                     "mut.bin": rng.bytes(50_000)})
    repo = make_repo()
    _, s1 = TreeBackup(repo).run(src)
    assert s1.blobs_new > 0
    (src / "mut.bin").write_bytes(rng.bytes(50_000))
    _, s2 = TreeBackup(repo).run(src)
    # stable.bin skipped wholesale via parent size+mtime match
    assert s2.bytes_dedup >= 100_000
    assert s2.bytes_new <= 60_000


def test_content_dedup_across_names(tmp_path, rng):
    src = tmp_path / "src"
    src.mkdir()
    payload = rng.bytes(120_000)
    write_tree(src, {"one.bin": payload, "two.bin": payload})
    repo = make_repo()
    _, stats = TreeBackup(repo).run(src)
    # identical content -> second file entirely deduped by blob hash
    assert stats.bytes_dedup >= len(payload)
    assert stats.bytes_new < 2 * len(payload)


def test_restore_is_idempotent_and_deletes_extras(tmp_path, rng):
    src, dst = tmp_path / "src", tmp_path / "dst"
    src.mkdir()
    write_tree(src, {"keep.bin": rng.bytes(10_000)})
    repo = make_repo()
    TreeBackup(repo).run(src)
    dst.mkdir()
    write_tree(dst, {"stale.bin": b"should disappear"})
    out1 = restore_snapshot(repo, dst)
    assert out1["deleted"] == 1 and not (dst / "stale.bin").exists()
    out2 = restore_snapshot(repo, dst)
    assert out2["files"] == 0 and out2["skipped"] == 1  # second run no-ops
    assert trees_equal(src, dst)


def test_empty_volume_skips_backup(tmp_path):
    src = tmp_path / "src"
    src.mkdir()
    repo = make_repo()
    snap_id, _ = TreeBackup(repo).run(src)
    assert snap_id is None
    assert repo.list_snapshots() == []


def test_encrypted_repo_roundtrip_and_wrong_password(tmp_path, rng):
    store = FsObjectStore(tmp_path / "repo")
    src, dst = tmp_path / "src", tmp_path / "dst"
    src.mkdir()
    write_tree(src, {"secret.bin": rng.bytes(60_000)})
    repo = Repository.init(store, password="hunter2", chunker=SMALL_CHUNKER)
    TreeBackup(repo).run(src)
    # ciphertext at rest: the plaintext must not appear in any object
    plain = (src / "secret.bin").read_bytes()
    for key in store.list():
        assert plain[:4096] not in store.get(key)
    reopened = Repository.open(store, password="hunter2")
    assert restore_snapshot(reopened, dst)["files"] == 1
    assert trees_equal(src, dst)
    with pytest.raises(crypto.WrongPassword):
        Repository.open(store, password="nope")
    with pytest.raises(crypto.WrongPassword):
        Repository.open(store)


def test_snapshot_selection_previous_and_as_of(tmp_path):
    src = tmp_path / "src"
    src.mkdir()
    repo = make_repo()
    ids = []
    for i, when in enumerate(("2026-01-01T00:00:00+00:00",
                              "2026-02-01T00:00:00+00:00",
                              "2026-03-01T00:00:00+00:00")):
        (src / "f.txt").write_bytes(f"gen {i}".encode())
        sid, _ = TreeBackup(repo).run(src)
        _, manifest = repo.list_snapshots()[-1]
        # pin deterministic times (manifests are content-addressed)
        repo.delete_snapshot(sid)
        manifest["time"] = when
        ids.append(repo.save_snapshot(manifest))
    assert repo.select_snapshot()[0] == ids[2]
    assert repo.select_snapshot(previous=1)[0] == ids[1]
    as_of = datetime(2026, 2, 15, tzinfo=timezone.utc)
    assert repo.select_snapshot(restore_as_of=as_of)[0] == ids[1]
    assert repo.select_snapshot(restore_as_of=as_of, previous=1)[0] == ids[0]
    assert repo.select_snapshot(
        restore_as_of=datetime(2020, 1, 1, tzinfo=timezone.utc)) is None


def _snap_at(repo, tree_id, when: str):
    return repo.save_snapshot({"tree": tree_id, "time": when,
                               "hostname": "t", "paths": [], "tags": []})


def test_forget_retain_policy(tmp_path, rng):
    src = tmp_path / "src"
    src.mkdir()
    write_tree(src, {"f.bin": rng.bytes(5000)})
    repo = make_repo()
    sid, _ = TreeBackup(repo).run(src)
    _, manifest = repo.list_snapshots()[0]
    repo.delete_snapshot(sid)
    tree = manifest["tree"]
    # 10 daily snapshots
    for d in range(1, 11):
        _snap_at(repo, tree, f"2026-07-{d:02d}T12:00:00+00:00")
    removed = repo.forget(daily=3)
    snaps = repo.list_snapshots()
    assert len(snaps) == 3 and len(removed) == 7
    assert [s[1]["time"][:10] for s in snaps] == [
        "2026-07-08", "2026-07-09", "2026-07-10"]
    # keep-last overrides buckets
    removed = repo.forget(last=1)
    assert len(repo.list_snapshots()) == 1


def test_prune_drops_unreferenced_blobs(tmp_path, rng):
    src = tmp_path / "src"
    src.mkdir()
    write_tree(src, {"a.bin": rng.bytes(40_000)})
    repo = make_repo()
    TreeBackup(repo).run(src)
    (src / "a.bin").write_bytes(rng.bytes(40_000))
    TreeBackup(repo).run(src)
    all_blobs = len(repo.blob_ids())
    # drop the first snapshot, prune, and verify its blobs are gone
    first = repo.list_snapshots()[0][0]
    repo.delete_snapshot(first)
    report = repo.prune(grace_seconds=0)  # stop-the-world semantics
    assert report["blobs_removed"] > 0
    assert len(repo.blob_ids()) < all_blobs
    assert repo.check(read_data=True) == []
    # survivor still restores
    dst = tmp_path / "dst"
    assert restore_snapshot(repo, dst)["files"] == 1
    assert trees_equal(src, dst)


def test_check_detects_missing_pack(tmp_path, rng):
    store = MemObjectStore()
    src = tmp_path / "src"
    src.mkdir()
    write_tree(src, {"a.bin": rng.bytes(30_000)})
    repo = Repository.init(store, chunker=SMALL_CHUNKER)
    TreeBackup(repo).run(src)
    victim = next(store.list("data/"))
    store.delete(victim)
    assert repo.check() != []


def test_repo_reopen_loads_index(tmp_path, rng):
    store = FsObjectStore(tmp_path / "repo")
    src = tmp_path / "src"
    src.mkdir()
    write_tree(src, {"a.bin": rng.bytes(80_000)})
    repo = Repository.init(store, chunker=SMALL_CHUNKER)
    _, s1 = TreeBackup(repo).run(src)
    repo2 = Repository.open(store)
    _, s2 = TreeBackup(repo2).run(src)
    # same content, fresh process: everything dedups against loaded index
    assert s2.blobs_new <= 1  # only the (identical) tree blob may rewrite
    assert s2.bytes_dedup >= 80_000


def test_lock_shared_blocks_exclusive_and_vice_versa():
    repo = make_repo()
    from volsync_tpu.repo.repository import RepoLockedError

    with repo.lock(exclusive=False):
        with pytest.raises(RepoLockedError):
            with repo.lock(exclusive=True):
                pass
        # shared + shared coexist
        with repo.lock(exclusive=False):
            pass
    with repo.lock(exclusive=True):
        with pytest.raises(RepoLockedError):
            with repo.lock(exclusive=False):
                pass
    # all locks released
    assert list(repo.store.list("locks/")) == []


def test_lock_stale_holder_is_removed():
    repo = make_repo()
    own = repo._write_lock("exclusive")
    info = json.loads(repo.store.get(own))
    info["time"] = (datetime.now(timezone.utc)
                    - timedelta(seconds=Repository.LOCK_STALE_SECONDS + 60)
                    ).isoformat()
    repo.store.put(own, json.dumps(info).encode())
    with repo.lock(exclusive=True):  # stale lock must not block
        pass
    assert list(repo.store.list("locks/")) == []


def test_snapshot_written_after_packs_are_durable(tmp_path, rng):
    """Crash-safety invariant: by the time a snapshot object appears in
    the store, every pack/index object it references must already be
    there (flush-before-save_snapshot ordering)."""
    store = MemObjectStore()
    orig_put = store.put
    seen_at_snapshot = {}

    def spying_put(key, data):
        if key.startswith("snapshots/"):
            seen_at_snapshot[key] = {
                k for k in store.list("data/")} | {
                k for k in store.list("index/")}
        return orig_put(key, data)

    store.put = spying_put
    repo = make_repo(store)
    src = tmp_path / "src"
    src.mkdir()
    write_tree(src, {"f.bin": rng.bytes(60_000)})
    snap, _ = TreeBackup(repo).run(src)
    assert snap is not None
    # reopen from the store alone and verify the snapshot restores
    repo2 = Repository.open(store)
    assert repo2.check() == []
    # the packs/index the snapshot needs were durable before it appeared
    keys_then = seen_at_snapshot[f"snapshots/{snap}"]
    assert any(k.startswith("data/") for k in keys_then)
    assert any(k.startswith("index/") for k in keys_then)


def test_lock_contenders_back_out_and_one_proceeds():
    """Two waiters must not deadlock on each other's lock objects: the
    holder releases, and a waiting contender (wait_seconds>0) acquires."""
    import threading

    repo = make_repo()
    order = []
    with repo.lock(exclusive=True):
        def waiter():
            with repo.lock(exclusive=True, wait_seconds=10):
                order.append("waiter-in")

        t = threading.Thread(target=waiter)
        t.start()
        time.sleep(0.3)
        assert order == []  # still blocked while we hold it
        order.append("holder-out")
    t.join(timeout=10)
    assert not t.is_alive()
    assert order == ["holder-out", "waiter-in"]
    assert list(repo.store.list("locks/")) == []


@pytest.mark.slow
def test_backup_bit_identical_and_consistent(tmp_path, rng):
    """Two backups of one volume into two repositories produce the
    identical tree, store identical files exactly once, and keep stats
    consistent."""
    import shutil

    from volsync_tpu.engine.backup import TreeBackup
    from volsync_tpu.objstore import FsObjectStore
    from volsync_tpu.repo.repository import Repository

    src = tmp_path / "vol"
    src.mkdir()
    big = rng.bytes(700_000)
    for i in range(6):
        d = src / f"d{i % 2}"
        d.mkdir(exist_ok=True)
        (d / f"f{i}.bin").write_bytes(big)          # 6 identical files
    (src / "small.txt").write_bytes(b"tiny")
    (src / "empty").write_bytes(b"")

    def snap(run):
        root = tmp_path / f"repo-{run}"
        repo = Repository.init(FsObjectStore(root))
        sid, stats = TreeBackup(repo).run(src)
        assert repo.check() == []
        tree = dict(repo.list_snapshots())[sid]["tree"]
        return tree, stats, root

    # Snapshot ids embed wall time; the TREE id is the content identity.
    tree1, stats1, _ = snap(1)
    tree4, stats4, root4 = snap(4)
    assert tree1 == tree4
    # identical content stored once
    assert stats1.blobs_new == stats4.blobs_new
    assert stats4.blobs_new + stats4.blobs_dedup \
        == stats1.blobs_new + stats1.blobs_dedup
    assert stats4.bytes_scanned == stats1.bytes_scanned == 6 * 700_000 + 4
    shutil.rmtree(root4)


def test_parallel_restore_equivalent(tmp_path, rng):
    """Worker-pool restore must materialize the identical tree (bytes,
    modes, mtimes incl. directory mtimes) as the serial path."""
    import os

    from volsync_tpu.engine.backup import TreeBackup
    from volsync_tpu.engine.restore import TreeRestore
    from volsync_tpu.objstore import FsObjectStore
    from volsync_tpu.repo.repository import Repository

    src = tmp_path / "vol"
    (src / "deep" / "er").mkdir(parents=True)
    (src / "a.bin").write_bytes(rng.bytes(700_000))
    (src / "deep" / "b.bin").write_bytes(rng.bytes(5000))
    (src / "deep" / "er" / "c.txt").write_bytes(b"leaf")
    os.symlink("a.bin", src / "link")

    repo = Repository.init(FsObjectStore(tmp_path / "repo"))
    sid, _ = TreeBackup(repo).run(src)
    snaps = dict(repo.list_snapshots())

    def restore(workers):
        dest = tmp_path / f"out-w{workers}"
        TreeRestore(repo, workers=workers).run(sid, snaps[sid], dest)
        out = {}
        for root, _, files in os.walk(dest):
            for f in files:
                p = os.path.join(root, f)
                rel = os.path.relpath(p, dest)
                st = os.lstat(p)
                body = None if os.path.islink(p) else open(p, "rb").read()
                out[rel] = (body, st.st_mode, st.st_mtime_ns)
            if root != str(dest):  # the dest root isn't snapshot metadata
                st = os.lstat(root)
                out[os.path.relpath(root, dest) + "/"] = (None, st.st_mode,
                                                          st.st_mtime_ns)
        return out

    assert restore(1) == restore(4)


def test_parallel_restore_compressible_blobs(tmp_path):
    """Compressible content exercises the zstd path (\\x01 marker) from
    concurrent restore workers — the shared-decompressor race this
    guards against corrupted output nondeterministically."""
    from volsync_tpu.engine.backup import TreeBackup
    from volsync_tpu.engine.restore import TreeRestore
    from volsync_tpu.objstore import FsObjectStore
    from volsync_tpu.repo.repository import Repository

    src = tmp_path / "vol"
    src.mkdir()
    for i in range(12):
        # highly compressible, distinct per file
        (src / f"t{i}.json").write_bytes(
            (f'{{"k{i}": "v"}},' * 20_000).encode())
    repo = Repository.init(FsObjectStore(tmp_path / "repo"))
    sid, _ = TreeBackup(repo).run(src)
    snaps = dict(repo.list_snapshots())
    dest = tmp_path / "out"
    TreeRestore(repo, workers=8).run(sid, snaps[sid], dest)
    for i in range(12):
        assert (dest / f"t{i}.json").read_bytes() \
            == (src / f"t{i}.json").read_bytes()
