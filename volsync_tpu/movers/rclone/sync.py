"""Checksum-based bucket sync engine (the `rclone sync --checksum` core).

What the reference's data plane does with a wrapped rclone binary
(mover-rclone/active.sh:19-31: checksum compare, both directions,
--transfers 10 concurrent streams, POSIX-metadata round-trip via a
getfacl dump file, delete-extraneous mirror semantics), rebuilt around
the TPU hash pipeline:

  - every file's checksum is a Merkle blob id (repo/blobid.py) computed
    on device, with many files packed per upload batch
    (engine/chunker.py hash_spans) — the per-byte work that rclone does
    on CPU cores is the batched-lane SHA-256 kernel here;
  - bucket layout is content-addressed: ``<prefix>/objects/<digest>``
    holds file bytes, ``<prefix>/index/manifest.json`` names the shards
    under ``<prefix>/index/shards/`` that map relpath -> metadata (type,
    size, mode, mtime_ns, owner, xattrs, digest / symlink target). The
    index is the facl-dump analogue: metadata round-trips through it.
    The layout is this mover's own: a stock ``rclone`` binary reads the
    objects as files named by checksum, not as the tree;
  - transfers fan out over a thread pool (the --transfers 10 analogue;
    object-store puts/gets are IO-bound);
  - mirror semantics: objects no longer referenced by the new index are
    deleted (source direction), local files not in the index are deleted
    (destination direction); empty directories are preserved
    (--create-empty-src-dirs);
  - ``--checksum`` on both sides: every checksum compared was computed
    on this run from the file's bytes, and a fetched file is hashed on
    the device, under a temporary name, before it is left under its own
    (a mismatch fails the sync: ``rclone.fetch_mismatch``);
  - a pass asks the kernel about a file once: the scan's record of a
    path (its relative name as a string, the ``lstat`` it took) is what
    the hash pass sizes its slots from and reads by, and what the
    destination's metadata pass compares the index with, so that a file
    left in place gets only the calls that change something
    (``rclone.meta_kept`` of ``rclone.meta_files`` got none).

Spans and counters (``rclone.*``; docs/observability.md): the entry's
thread records scan, hash, lease, list, transfer_wait, index_read,
index_write, sweep, delete_local, place and apply_meta, which add up to
the call's wall; the transfer pool's threads record one ``rclone.put`` /
``rclone.get`` an object under the caller's trace.
"""

from __future__ import annotations

import base64
import hashlib
import json
import logging
import operator
import os
import shutil
import stat as stat_mod
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

from volsync_tpu.engine.chunker import (
    hash_file_streaming,
    hash_spans,
    stage_page_aligned,
)
from volsync_tpu.engine.restore import _apply_owner, _apply_xattrs
from volsync_tpu.obs import carry_context, count, span
from volsync_tpu.objstore.store import (
    NoSuchKey,
    ObjectStore,
    get_file,
    put_file,
)
from volsync_tpu.resilience import RetryPolicy

log = logging.getLogger("volsync_tpu.movers.rclone")

INDEX_KEY = "index.json"  # legacy v1 single-object index (read-only)
INDEX_MANIFEST = "index/manifest.json"
INDEX_SHARDS = "index/shards"
OBJECTS = "objects"
DEFAULT_TRANSFERS = 10  # mover-rclone/active.sh:19
_BATCH_BYTES = 64 * 1024 * 1024
#: Files above this hash via the segmented streaming path instead of
#: being packed whole into a batch buffer (bounded host+device memory).
_STREAM_THRESHOLD = 256 * 1024 * 1024


class SyncError(RuntimeError):
    pass


class BucketLockedError(SyncError):
    """Another writer holds the bucket prefix's mirror lease."""


def _key(prefix: str, *parts: str) -> str:
    prefix = prefix.strip("/")
    return "/".join((prefix, *parts)) if prefix else "/".join(parts)


LOCKS = "locks"
LOCK_STALE_SECONDS = 10 * 60
LOCK_REFRESH_SECONDS = LOCK_STALE_SECONDS / 3


class _MirrorLease:
    """Writer lease over one bucket prefix.

    Two sources mirroring into one prefix would otherwise sweep each
    other's objects (each's index only references its own files). The
    protocol is the repository layer's restic-style one (see
    repo/repository.py), which needs NO compare-and-swap from the store:
    write your OWN uniquely-named lock object under ``<prefix>/locks/``,
    then scan; any other fresh lock means back off (remove your own,
    raise BucketLockedError — the Job's backoff machinery retries).
    Crashed holders go stale after LOCK_STALE_SECONDS and are swept by
    the next contender; LIVE holders re-stamp their lock every
    LOCK_REFRESH_SECONDS from a heartbeat thread, so a long mirror is
    never mistaken for a crash. Two simultaneous contenders can both
    back off (safe, retried) — never both proceed.
    """

    def __init__(self, store: ObjectStore, prefix: str):
        self.store = store
        self.prefix = prefix
        self.holder = f"{os.getpid()}-{os.urandom(4).hex()}"
        self.key = _key(prefix, LOCKS, f"{self.holder}.json")
        self._stop = None

    def _stamp(self):
        self.store.put(self.key, json.dumps(
            {"holder": self.holder, "time": time.time()}).encode())

    def _others_fresh(self) -> list:
        fresh = []
        for key in list(self.store.list(_key(self.prefix, LOCKS))):
            if key == self.key:
                continue
            try:
                held = json.loads(self.store.get(key))
            except (NoSuchKey, ValueError):
                continue
            if time.time() - held.get("time", 0) > LOCK_STALE_SECONDS:
                self.store.delete(key)  # crashed holder: sweep
            else:
                fresh.append(held.get("holder"))
        return fresh

    def __enter__(self):
        with span("rclone.lease"):
            self._stamp()
            others = self._others_fresh()
            if others:
                self.store.delete(self.key)  # back off: only our own lock
                raise BucketLockedError(
                    f"{self.prefix}: mirror held by {others}")
        stop = threading.Event()
        self._stop = stop
        restamp_policy = RetryPolicy.from_env(
            "rclone.lease_restamp", max_attempts=2, base_delay=0.05,
            max_delay=0.5, deadline=LOCK_REFRESH_SECONDS)

        def heartbeat():
            while not stop.wait(LOCK_REFRESH_SECONDS):
                try:
                    restamp_policy.call(self._stamp)
                except Exception as ex:  # noqa: BLE001 — log, don't
                    # swallow silently; keep mirroring (staleness only
                    # bites after LOCK_STALE_SECONDS of failed beats)
                    log.debug("mirror lease re-stamp failed "
                              "(retrying next beat): %s", ex)
        threading.Thread(target=heartbeat, daemon=True,
                         name="mirror-lease").start()
        return self

    def __exit__(self, *exc):
        if self._stop is not None:
            self._stop.set()
        with span("rclone.lease"):
            self.store.delete(self.key)  # only ever our own lock object


_by_name = operator.attrgetter("name")


def _safe_rel(rel: str) -> bool:
    """Remote index relpaths are untrusted input: reject anything that
    could escape the volume root (absolute paths, '..', empty segments) —
    a corrupted or hostile index must not be able to write, chmod, or
    symlink outside the mount."""
    if not rel or rel.startswith("/"):
        return False
    return not any(p in ("", ".", "..") for p in rel.split("/"))


def _validated_entries(entries: dict) -> dict:
    bad = [r for r in entries if not _safe_rel(r)]
    if bad:
        raise SyncError(f"index contains unsafe paths: {bad[:3]}")
    return entries


def scan_tree(root, *, collect_meta: bool = True) -> dict[str, dict]:
    """Walk a volume -> {relpath: entry} with file metadata (no digests
    yet). Sockets/devices are skipped, as the reference movers do.

    One ``lstat`` an entry, the one ``DirEntry`` takes, and every later
    stage of the pass works from what it said (size, mode, mtime_ns,
    uid, gid) and from the relative name as a string: no ``Path`` is
    built a file. uid/gid + xattrs are the reference rclone mover's
    `getfacl -R` dump analogue (active.sh:24), which records owner and
    ACLs; ACLs travel inside system.posix_acl_* xattrs.
    ``collect_meta=False`` skips the xattr calls — for sync_down's local
    inventory, which never reaches an index. With it, ``xattrs`` is
    ALWAYS present (possibly {}) in this index format: removing the last
    xattr at the source must strip it at the destination too
    (pre-format indexes are recognized by the absent uid key and left
    alone).

    Order: a directory's entry, its files by name, its symlinked
    directories by name, then its subdirectories by name, depth first —
    what a hash pass batches together is then a function of the tree
    alone, not of the filesystem's order. An explicit stack: depth is
    bounded by memory, not by the interpreter's recursion limit."""
    from volsync_tpu.engine.backup import _read_xattrs

    def meta(st, path) -> dict:
        owner = {"uid": st.st_uid, "gid": st.st_gid}
        if collect_meta:
            owner["xattrs"] = _read_xattrs(path)
        return owner

    def link(e, st) -> dict:
        return {"type": "symlink", "target": os.readlink(e.path),
                **meta(st, e.path)}

    entries: dict[str, dict] = {}
    root = os.fspath(root)
    # --one-file-system (active.sh:19). stat(), not lstat(): a
    # symlinked volume root must anchor at the walked filesystem or the
    # whole inventory reads as foreign (and a later mirror pass would
    # delete real data from the empty index).
    root_dev = os.stat(root).st_dev
    stack = [(root, "", None)]  # path, relative name, its lstat
    while stack:
        path, rel_dir, st = stack.pop()
        try:
            with os.scandir(path) as it:
                listed = sorted(it, key=_by_name)
        except OSError:
            continue  # unreadable: left out, as os.walk leaves it out
        if st is not None:
            if st.st_dev != root_dev:
                listed = []  # mount point: an empty dir, don't descend
            entries[rel_dir] = {"type": "dir", "mode": st.st_mode & 0o7777,
                                "mtime_ns": st.st_mtime_ns,
                                **meta(st, path)}
        prefix = rel_dir + "/" if rel_dir else ""
        dirs = []
        for e in listed:
            try:
                if e.is_dir():  # through a symlink too: sorted out below
                    dirs.append(e)
                    continue
            except OSError:
                pass
            st = e.stat(follow_symlinks=False)
            if st.st_dev != root_dev:
                continue  # foreign device (bind-mounted file)
            if stat_mod.S_ISLNK(st.st_mode):
                entries[prefix + e.name] = link(e, st)
            elif stat_mod.S_ISREG(st.st_mode):
                entries[prefix + e.name] = {
                    "type": "file", "size": st.st_size,
                    "mode": st.st_mode & 0o7777,
                    "mtime_ns": st.st_mtime_ns, **meta(st, e.path)}
        below = []
        for e in dirs:
            st = e.stat(follow_symlinks=False)
            if e.is_symlink():  # record as symlink, don't descend
                entries[prefix + e.name] = link(e, st)
            else:
                below.append((e.path, prefix + e.name, st))
        stack.extend(reversed(below))  # popped by name, depth first
    return entries


def hash_files(root, rels: list[str],
               sizes: list[int] | None = None) -> dict[str, str]:
    """Device digests for the given files. Small files are read straight
    into the page-aligned slots of ~64 MiB staging buffers (the stager
    of the restore's verify batches, engine/chunker.stage_page_aligned;
    one upload + one batched SHA-256 call per buffer — hash_spans);
    large files hash segment-by-segment with bounded memory
    (hash_file_streaming).

    ``sizes`` are the files' lengths as the caller's scan (or the GET)
    gave them, one a name; without them each file is stat-ed here. A
    file is read by one open, one ``readv`` into its slot and one
    close: shorter than its size, it changed under the pass and fails
    it; longer, it is cut at the size the index records for it."""
    out: dict[str, str] = {}
    batch: list[tuple[str, int]] = []
    batch_bytes = total = 0
    base = os.path.join(os.fspath(root), "")

    def read_file(i, slot):
        rel, n = batch[i]
        fd = os.open(base + rel, os.O_RDONLY)
        try:
            got = os.readv(fd, [slot])
            while 0 < got < n:  # a short read: go on to the file's end
                k = os.readv(fd, [slot[got:]])
                if not k:
                    break
                got += k
        finally:
            os.close(fd)
        if got != n:
            raise SyncError(f"{rel}: changed while it was hashed "
                            f"({got} of {n} bytes read)")

    def flush():
        nonlocal batch, batch_bytes
        if not batch:
            return
        # Files sit at 4 KiB-aligned offsets (<=4095B zero fill each),
        # which puts every Merkle leaf on the buffer's page grid — the
        # hash_spans fused fast path (ops/segment.span_roots_device):
        # one dispatch + one [N, 8] fetch, no per-leaf gathers.
        staging, spans = stage_page_aligned(
            [n for _, n in batch], read_file,
            filling=lambda: span("rclone.read"))
        for (rel, _), digest in zip(batch, hash_spans(staging, spans)):
            out[rel] = digest
        count("rclone.hash_batches")
        batch, batch_bytes = [], 0

    with span("rclone.hash"):
        if sizes is None:
            sizes = [os.stat(base + rel).st_size for rel in rels]
        for rel, n in zip(rels, sizes):
            total += n
            if n > _STREAM_THRESHOLD:
                out[rel] = hash_file_streaming(base + rel)
                continue
            batch.append((rel, n))
            batch_bytes += n
            if batch_bytes >= _BATCH_BYTES:
                flush()
        flush()
    count("rclone.files_hashed", len(rels))
    count("rclone.bytes_hashed", total)
    return out


def _shard_of(rel: str) -> str:
    """Index shard for a relpath: all entries of one DIRECTORY share a
    shard (a changed file dirties exactly its directory's shard), hashed
    into at most 256 buckets so huge flat trees still bound shard count."""
    d = rel.rsplit("/", 1)[0] if "/" in rel else ""
    return hashlib.sha256(d.encode()).hexdigest()[:2]


def write_index(store: ObjectStore, prefix: str,
                entries: dict[str, dict]) -> dict:
    """Persist the index as per-directory shards + a small manifest.

    BASELINE configs[2] (100 GiB, many small files) is metadata-heavy:
    a monolithic index.json re-uploads every entry on every sync. Here
    a sync touches O(changed directories) index bytes: each shard's
    object name embeds its content hash, so unchanged shards are simply
    re-referenced by the new manifest and never re-serialized past the
    grouping pass. Returns {"shards": total, "written": uploaded}.
    """
    groups: dict[str, dict[str, dict]] = {}
    for rel, e in entries.items():
        groups.setdefault(_shard_of(rel), {})[rel] = e
    try:
        old_shards = json.loads(
            store.get(_key(prefix, INDEX_MANIFEST))).get("shards", {})
    except (NoSuchKey, ValueError):
        old_shards = {}
    shards: dict[str, str] = {}
    written = 0
    for sk in sorted(groups):
        payload = json.dumps({"entries": groups[sk]},
                             sort_keys=True).encode()
        name = f"{sk}-{hashlib.sha256(payload).hexdigest()[:16]}.json"
        shards[sk] = name
        if old_shards.get(sk) != name:
            store.put(_key(prefix, INDEX_SHARDS, name), payload)
            written += 1
    # Superseded shards are GC'd ONE GENERATION LATE: a reader holding
    # the previous manifest must still find every shard it references
    # (sync_down takes no lease — the v1 single-object index gave
    # readers that atomicity for free). The manifest records the
    # previous generation's retired names; THIS sync deletes only the
    # generation before that.
    retiring = sorted(set(old_shards.values()) - set(shards.values()))
    store.put(_key(prefix, INDEX_MANIFEST), json.dumps(
        {"version": 2, "shards": shards, "retiring": retiring},
        sort_keys=True).encode())
    keep = set(shards.values()) | set(retiring)
    for key in list(store.list(_key(prefix, INDEX_SHARDS))):
        if key.rsplit("/", 1)[-1] not in keep:
            store.delete(key)
    try:
        store.delete(_key(prefix, INDEX_KEY))
    except NoSuchKey:
        pass
    return {"shards": len(shards), "written": written}


def read_index(store: ObjectStore, prefix: str) -> dict[str, dict]:
    """Merge the sharded index (v2); fall back to the legacy single
    index.json written by older syncs.

    Readers take no lease, so a sync may supersede the manifest while
    this runs. The one-generation-late GC keeps the just-read
    manifest's shards alive through one concurrent sync; if a reader
    slept through TWO syncs it restarts from the fresh manifest once
    before declaring corruption.
    """
    for attempt in (0, 1):
        try:
            manifest = json.loads(store.get(_key(prefix, INDEX_MANIFEST)))
        except NoSuchKey:
            manifest = None
        if manifest is None:
            break
        entries: dict[str, dict] = {}
        try:
            for name in manifest.get("shards", {}).values():
                payload = json.loads(
                    store.get(_key(prefix, INDEX_SHARDS, name)))
                entries.update(payload.get("entries", {}))
            return entries
        except NoSuchKey as e:
            if attempt:
                # Fresh manifest and still missing a referenced shard —
                # real corruption (or a writer violating the mirror
                # lease), not a reason to serve a partial tree.
                raise SyncError(
                    f"index shard missing from bucket: {e}") from None
            continue  # superseded mid-read: retry from the new manifest
    try:
        payload = json.loads(store.get(_key(prefix, INDEX_KEY)))
    except NoSuchKey:
        return {}
    return payload.get("entries", {})


def sync_up(root: Path, store: ObjectStore, prefix: str, *,
            transfers: int = DEFAULT_TRANSFERS) -> dict:
    """Volume -> bucket mirror (DIRECTION=source, active.sh:23-27).

    Checksum compare: a file uploads only if its digest object is absent;
    unreferenced objects are deleted afterwards (mirror semantics).
    """
    root = Path(root)
    with span("rclone.scan"):
        entries = scan_tree(root)
    files = [r for r, e in entries.items() if e["type"] == "file"]
    digests = hash_files(root, files, [entries[r]["size"] for r in files])
    for rel in files:
        entries[rel]["digest"] = digests[rel]

    with _MirrorLease(store, prefix):
        return _mirror_up(root, store, prefix, entries, files, digests,
                          transfers)


def _put_object(store, key: str, src: Path) -> None:
    with span("rclone.put"):
        put_file(store, key, src)


def _mirror_up(root, store, prefix, entries, files, digests,
               transfers) -> dict:
    wanted = set(digests.values())
    with span("rclone.list"):
        have = {k.rsplit("/", 1)[-1]
                for k in store.list(_key(prefix, OBJECTS))}
    to_upload = wanted - have
    uploaded_bytes = 0
    put = carry_context(_put_object)
    with ThreadPoolExecutor(max_workers=transfers) as pool:
        futs = []
        seen: set[str] = set()
        for rel in files:
            d = digests[rel]
            if d in to_upload and d not in seen:
                seen.add(d)
                uploaded_bytes += entries[rel]["size"]
                futs.append(pool.submit(
                    put, store, _key(prefix, OBJECTS, d), root / rel))
        with span("rclone.transfer_wait"):
            for f in futs:
                f.result()
            pool.shutdown()
        uploaded = len(futs)

    with span("rclone.index_write"):
        idx_stats = write_index(store, prefix, entries)

    # mirror: drop objects the new index no longer references
    with span("rclone.list"):
        stored = list(store.list(_key(prefix, OBJECTS)))
    deleted = 0
    with span("rclone.sweep"):
        for key in stored:
            if key.rsplit("/", 1)[-1] not in wanted:
                store.delete(key)
                deleted += 1
    nbytes = sum(entries[rel]["size"] for rel in files)
    count("rclone.files_uploaded", uploaded)
    count("rclone.bytes_uploaded", uploaded_bytes)
    count("rclone.files_skipped", len(files) - uploaded)
    count("rclone.objects_deleted", deleted)
    count("rclone.bytes_synced", nbytes)
    return {"files": len(files), "uploaded": uploaded,
            "deduped": len(files) - uploaded, "deleted_objects": deleted,
            "index_shards": idx_stats["shards"],
            "index_shards_written": idx_stats["written"],
            "bytes": nbytes}


def _clear_path(p: Path) -> None:
    """Remove whatever stands at ``p``. unlink, not rmtree, for a
    symlink: rmtree silently refuses symlinks, and a surviving symlink
    would make the next write follow it (possibly out of the volume)
    instead of replacing it."""
    if p.is_dir() and not p.is_symlink():
        shutil.rmtree(p, ignore_errors=True)
    elif p.is_symlink() or p.exists():
        p.unlink()


def _fetch_name(rel: str) -> str:
    """Where a fetched file waits, beside its own name, until its bytes
    have been hashed to the checksum the index gives."""
    head, _, name = rel.rpartition("/")
    tmp = f".volsync.fetch.{os.getpid()}.{name}"
    return f"{head}/{tmp}" if head else tmp


def _fetch_object(store, prefix: str, root: Path, rel: str, entry: dict,
                  tmp: str) -> None:
    with span("rclone.get"):
        dst = root / tmp
        dst.parent.mkdir(parents=True, exist_ok=True)
        try:
            n = get_file(store, _key(prefix, OBJECTS, entry["digest"]), dst)
        except NoSuchKey:
            # e.g. a concurrent source-direction mirror swept an object
            # the index we read still references — retryable sync failure,
            # not a crash
            raise SyncError(f"{rel}: object {entry['digest']} missing "
                            "from bucket") from None
        if n != entry["size"]:
            raise SyncError(f"{rel}: object size mismatch")


def _fetch_verified(store, prefix: str, root: Path, wanted: dict,
                    transfers: int) -> None:
    """Fetch ``wanted`` ({relpath: index entry}) under temporary names
    on the transfer pool, hash what arrived on the device (hash_files:
    the same batches as the local pass), and leave a file under its own
    name only if its bytes hash to the checksum the index gives
    (``rclone sync --checksum`` checks the hash after the transfer). A
    mismatch fails the sync; the files that matched are in place, no
    temporary is left either way."""
    tmp_of = {rel: _fetch_name(rel) for rel in wanted}
    fetch = carry_context(_fetch_object)
    try:
        with ThreadPoolExecutor(max_workers=transfers) as pool:
            futs = [pool.submit(fetch, store, prefix, root, rel, entry,
                                tmp_of[rel])
                    for rel, entry in wanted.items()]
            with span("rclone.transfer_wait"):
                for f in futs:
                    f.result()
                pool.shutdown()
        got = hash_files(root, list(tmp_of.values()),
                         [entry["size"] for entry in wanted.values()])
        bad = []
        with span("rclone.place"):
            for rel, entry in wanted.items():
                if got[tmp_of[rel]] != entry["digest"]:
                    bad.append(rel)
                    continue
                _clear_path(root / rel)
                os.replace(root / tmp_of.pop(rel), root / rel)
        if bad:
            count("rclone.fetch_mismatch", len(bad))
            raise SyncError(
                f"{len(bad)} fetched file(s) do not hash to the index's "
                f"checksum (first: {bad[0]}): left as they were")
    finally:  # what did not take its name: a mismatch, or a failure
        for tmp in tmp_of.values():
            (root / tmp).unlink(missing_ok=True)


def _xattrs_differ(path: str, want: dict) -> bool:
    """One ``listxattr``; values are read only where the names are the
    wanted ones and there are any."""
    try:
        have = os.listxattr(path, follow_symlinks=False)
    except OSError:
        return False  # no xattrs here: _apply_xattrs would stop too
    if set(have) != set(want):
        return True
    try:
        return any(os.getxattr(path, n, follow_symlinks=False)
                   != base64.b64decode(v) for n, v in want.items())
    except OSError:
        return True


def _settle_meta(path: str, entry: dict, have: dict | None) -> bool:
    """Bring a file's metadata to the index entry's. ``have`` is the
    scan's record of this very inode (None for a file this run fetched,
    which gets every call): then only the calls that change something
    are made, attribute by attribute, as ``rclone sync`` sets a modtime
    only where it differs. Returns whether none was.

    xattrs before chmod (read-only modes block setxattr), chown before
    chmod (chown clears suid, so a chown brings its chmod) — the engine
    restore's ordering; the index carries the facl-dump analogue (owner
    + ACL xattrs). Absent keys are a pre-format index: left alone."""
    if have is None:
        xattrs = owner = mode = times = True
    else:
        xattrs = "xattrs" in entry and _xattrs_differ(path, entry["xattrs"])
        owner = "uid" in entry and (
            (entry["uid"], entry["gid"]) != (have["uid"], have["gid"]))
        mode = owner or entry["mode"] != have["mode"]
        times = entry["mtime_ns"] != have["mtime_ns"]
    if xattrs:
        _apply_xattrs(path, entry)
    if owner:
        _apply_owner(path, entry)
    if mode:
        os.chmod(path, entry["mode"])
    if times:
        os.utime(path, ns=(entry["mtime_ns"], entry["mtime_ns"]))
    return not (xattrs or mode or times)


def sync_down(store: ObjectStore, prefix: str, root: Path, *,
              transfers: int = DEFAULT_TRANSFERS) -> dict:
    """Bucket -> volume mirror (DIRECTION=destination, active.sh:28-33).

    Local files whose digest already matches are untouched (checksum
    compare, the digest computed on this run from the file's bytes); the
    others are fetched and hashed before they take their name
    (_fetch_verified). Metadata (mode, mtime, owner, xattrs) is brought
    to the index's either way — the setfacl --restore analogue: applied
    whole to what this run made (fetched files, symlinks) and to
    directories, and for a file left in place by the calls that change
    something (_settle_meta, from the scan's record of it). Extraneous
    local paths are deleted.
    """
    root = Path(root)
    root.mkdir(parents=True, exist_ok=True)
    with span("rclone.index_read"):
        got = read_index(store, prefix)
        if not got and not store.exists(_key(prefix, INDEX_MANIFEST)) \
                and not store.exists(_key(prefix, INDEX_KEY)):
            raise SyncError(
                f"no index at {prefix!r}: nothing has been synced here")
    entries = _validated_entries(got)

    with span("rclone.scan"):
        local = scan_tree(root, collect_meta=False)
    local_files = [r for r, e in local.items() if e["type"] == "file"
                   and r in entries and entries[r]["type"] == "file"
                   and entries[r]["size"] == e["size"]]
    local_digests = hash_files(root, local_files,
                               [local[r]["size"] for r in local_files])

    # delete extraneous paths first (files, then emptied dirs bottom-up)
    deleted = 0
    with span("rclone.delete_local"):
        for rel in sorted(local, key=len, reverse=True):
            if rel not in entries:
                _clear_path(root / rel)
                deleted += 1

    # directories (create-empty-src-dirs), shallow-first
    for rel in sorted((r for r, e in entries.items() if e["type"] == "dir"),
                      key=len):
        p = root / rel
        if p.is_symlink() or (p.exists() and not p.is_dir()):
            p.unlink()
        p.mkdir(parents=True, exist_ok=True)

    files = {r: e for r, e in entries.items() if e["type"] == "file"}
    wanted = {r: e for r, e in files.items()
              if local_digests.get(r) != e["digest"]}
    _fetch_verified(store, prefix, root, wanted, transfers)

    base = os.path.join(os.fspath(root), "")
    kept = 0
    with span("rclone.apply_meta"):
        for rel, entry in entries.items():
            if entry["type"] == "symlink":
                p = root / rel
                _clear_path(p)
                p.parent.mkdir(parents=True, exist_ok=True)
                os.symlink(entry["target"], p)
                _apply_xattrs(p, entry)
                _apply_owner(p, entry)
            elif entry["type"] == "file":
                # a file not fetched is the inode the scan saw: its
                # record says which of the calls would change anything
                kept += _settle_meta(
                    base + rel, entry,
                    None if rel in wanted else local[rel])
    # dir metadata last (child writes bump parent mtimes), deepest first
    with span("rclone.apply_meta"):
        for rel in sorted((r for r, e in entries.items()
                           if e["type"] == "dir"), key=len, reverse=True):
            entry = entries[rel]
            _apply_xattrs(root / rel, entry)
            _apply_owner(root / rel, entry)
            os.chmod(root / rel, entry["mode"])
            os.utime(root / rel, ns=(entry["mtime_ns"], entry["mtime_ns"]))
    nbytes = sum(e["size"] for e in files.values())
    count("rclone.meta_files", len(files))
    if kept:
        count("rclone.meta_kept", kept)
    count("rclone.files_fetched", len(wanted))
    count("rclone.bytes_fetched", sum(e["size"] for e in wanted.values()))
    count("rclone.files_skipped", len(files) - len(wanted))
    count("rclone.local_deleted", deleted)
    count("rclone.bytes_synced", nbytes)
    return {"files": len(files), "fetched": len(wanted),
            "skipped": len(files) - len(wanted), "deleted_local": deleted,
            "bytes": nbytes}
