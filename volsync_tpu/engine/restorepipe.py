"""Pipelined restore data plane: pack-aware fetch, device verify, write.

The serial seed-era restore (engine/restore.py) issues one
``repo.read_blob()`` store round trip per chunk — fine on a local
filesystem, ruinous against an object store with tens of milliseconds
per GET, and exactly the shape PR 1 removed from the *write* path. This
module mirrors that work for reads, in four stages:

1. **Plan** (``restore.plan``): resolve every file's content list
   through the index, derive each blob's byte offset within its file
   (``raw_length`` is the plaintext length, known before any fetch),
   group needed blobs by the pack that holds them, and order pack
   fetches by first need — each pack is downloaded ONCE and all ranges
   within it coalesce into that one GET. A target whose name the walk
   found ABSENT from its directory's listing costs this stage no
   system call: nothing to compare, nothing to clear, nothing to
   claim. A name that was present is compared (skip-unchanged),
   cleared and claimed by a truncating open, as the serial path does.
2. **Fetch** (``restore.fetch``): a bounded async pool
   (``VOLSYNC_RESTORE_FETCHERS`` threads, ``VOLSYNC_RESTORE_FETCH_WINDOW``
   packs submitted ahead) pulls whole packs through the shared
   ``PackCache`` (repo/packcache.py) — LRU with a byte budget,
   single-flight across concurrent restores.
3. **Verify** (``restore.verify``): chunk hashes re-derive DEVICE-SIDE
   in ~64 MiB batches (engine/chunker.verify_blob_batch — the same
   page-grid kernel repository check uses) while later fetches are
   still in flight. A batch's bytes reach disk only after the batch
   verifies. A mismatch first attempts READ-REPAIR
   (``VOLSYNC_SCRUB_READ_REPAIR``, default on): one fetch of the
   owning pack's mirror copy (``VOLSYNC_PACK_COPIES=2``), proven
   byte-perfect against the content-addressed pack id, heals the
   primary with one overwriting PUT (verify-then-replace — the
   repo/scrub.py protocol) and the corrupt blobs re-decode from the
   healthy body — so a restore storm survives bit-rot the scrubber
   has not reached yet. When no byte-perfect mirror exists the heal
   falls through to Reed-Solomon RECONSTRUCTION from any k healthy
   shards of the pack's ``ec/`` stripe (``repo.ec_reconstruct``,
   which proves the content-addressed pack id before returning).
   Only when neither arm yields a provable body does the mismatch
   raise, before any byte of that batch is written, and the failed
   restore leaves no partial file behind.
4. **Write** (``restore.write``): verified blobs are written at their
   planned offsets with the serial path's sparse semantics (aligned
   all-zero pages become holes; chunk boundaries are page-aligned, so
   the hole grid matches the serial writer's byte for byte). A target
   is opened ONCE, when its first verified blob lands (an absent one
   is created there, ``O_EXCL``), and that descriptor takes every
   ``pwrite`` and, as the last blob lands, the metadata stamp
   (``restore.finalize``: ``ftruncate`` for a trailing hole, xattrs,
   ``fchown``, ``fchmod``, ``futimens``), then closes. At most
   ``_MAX_OPEN`` targets are held open; past that the least recently
   written is closed and reopens at its next blob. A failed restore
   closes them all and unlinks every target it had created and not
   finished.

The pipeline runs under the caller's shared-mode repository lock for
its WHOLE fetch window, so a concurrent two-phase pruner can mark packs
pending-delete mid-restore but never sweep them out from under the
fetch stage — pending-delete packs stay readable through their grace
period by design (docs/robustness.md, "Multi-writer protocol").

``RestoreGroup`` runs N snapshot restores in parallel sharing ONE
PackCache: a restore storm over the same snapshot fetches each pack
once for the whole group (the chaos drill asserts store GET counts).

Byte identity with the serial oracle is pinned by
tests/test_restorepipe.py; VOLSYNC_RESTORE_PIPELINE=0 selects the
serial path at runtime.
"""

from __future__ import annotations

import hashlib
import os
import threading
from collections import OrderedDict, deque
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path
from typing import Optional

from volsync_tpu import envflags
from volsync_tpu.engine.restore import _sparse_runs
from volsync_tpu.metrics import GLOBAL as GLOBAL_METRICS
from volsync_tpu.objstore.store import NoSuchKey
from volsync_tpu.obs import (
    count,
    current_context,
    record_trigger,
    span,
    use_context,
)
from volsync_tpu.repo import crypto
from volsync_tpu.repo.packcache import PackCache
from volsync_tpu.repo.repository import (
    RepoError,
    mirror_key,
    pack_key,
)

_M_RESTORE_BYTES = GLOBAL_METRICS.restore_bytes
# read-repair shares the scrub's heal accounting (PR 6/8 cached-child
# convention): a restore-side mirror heal is the same event as a
# scrub-side one, just detected earlier
_M_HEALED = GLOBAL_METRICS.scrub_packs.labels(outcome="healed")

#: sentinel pack key for blobs still buffered in an active write
#: pipeline (IndexEntry.pack == "") — read via the repository, no GET
_BUFFERED = ""


#: target files held open at once. Blobs arrive in pack order, which
#: is mostly file order, but dedup can leave a file unfinished for
#: long: past the bound the least recently written target is closed
#: and reopens at its next blob
_MAX_OPEN = 64

_O_WRITE = os.O_WRONLY | os.O_CLOEXEC


class _FilePlan:
    """One file's restore state: where it goes, how many blob writes
    remain, the final length, and the descriptor it is written through
    while one is open on it."""

    __slots__ = ("entry", "target", "total", "remaining", "claimed",
                 "existed", "fd", "written", "finished")

    def __init__(self, entry: dict, target: Path):
        self.entry = entry
        self.target = target
        self.total = 0
        self.remaining = 0
        # the restore made (or emptied) the file: a failure unlinks it
        self.claimed = False
        # a name stood at the target before this restore: cleared and
        # claimed by a truncating open, its length set at the finish
        self.existed = False
        self.fd = -1
        self.written = 0  # one past the highest byte written
        self.finished = False  # stamped and closed: it stays


class _OpenTargets:
    """The descriptors a restore holds on its targets: one a file from
    its first write to its stamp, at most ``_MAX_OPEN`` at once (least
    recently written out first). Owned by one ``restore_files_pipelined``
    call and used from its thread alone."""

    def __init__(self, tr):
        self._tr = tr
        self._open: "OrderedDict[_FilePlan, None]" = OrderedDict()

    def claim(self, plan: _FilePlan) -> None:
        """The present-name path: whatever stands at the target is
        cleared and the file created or emptied NOW, so a failure
        anywhere later knows to remove it."""
        self._tr._clear_target(plan.target)
        with open(plan.target, "wb"):
            pass
        count("restore.opens")
        plan.claimed = plan.existed = True

    def fd(self, plan: _FilePlan) -> int:
        """The descriptor ``plan`` is written through, opened here if
        it has none: an absent target is created (exclusively: a name
        that has appeared since the walk's listing is claimed like any
        present one), a claimed one reopened."""
        if plan.fd >= 0:
            self._open.move_to_end(plan)
            return plan.fd
        if len(self._open) >= _MAX_OPEN:
            self._close(self._open.popitem(last=False)[0])
        if not plan.claimed:
            try:
                plan.fd = os.open(
                    plan.target, _O_WRITE | os.O_CREAT | os.O_EXCL, 0o600)
                plan.claimed = True
            except FileExistsError:
                self.claim(plan)
        if plan.fd < 0:
            plan.fd = os.open(plan.target, _O_WRITE)
        count("restore.opens")
        self._open[plan] = None
        return plan.fd

    def write(self, plan: _FilePlan, offset: int, view, runs) -> None:
        """One blob placement: ``runs`` are the serial path's sparse
        semantics (``_sparse_runs``), or one dense run with sparse
        writes off. A data run is one ``pwrite`` on the held
        descriptor, a hole nothing. No open, seek or close a
        placement: a restore is priced in system calls (on the chip's
        host 0.10 ms one on a path and a fifth of it one on a
        descriptor, beside a write of 0.25 s/GiB), and an open a
        placement made seven of one
        (scripts/profile_restore_write.py --files)."""
        fd = self.fd(plan)
        for start, stop, hole in runs:
            if hole:
                continue
            data, at = view[start:stop], offset + start
            while len(data):
                n = os.pwrite(fd, data, at)
                data, at = data[n:], at + n
            plan.written = max(plan.written, offset + stop)

    def finish(self, plan: _FilePlan, stats: dict) -> None:
        """All content written: materialize a trailing hole and stamp
        metadata exactly as the serial writer does, through the
        descriptor, then close it."""
        with span("restore.finalize"):
            fd = self.fd(plan)  # an empty file is created here
            if plan.existed or plan.written < plan.total:
                os.ftruncate(fd, plan.total)
            self._tr._finalize_file(plan.entry, fd)
            del self._open[plan]
            self._close(plan)
        plan.finished = True
        count("restore.files_finished")
        stats["files"] += 1
        stats["bytes"] += plan.entry["size"]

    def _close(self, plan: _FilePlan) -> None:
        fd, plan.fd = plan.fd, -1
        os.close(fd)

    def close_all(self) -> None:
        while self._open:
            self._close(self._open.popitem()[0])


def restore_files_pipelined(tr, jobs: list, stats: dict) -> None:
    """Restore every (entry, target, absent) file job through the
    four-stage pipeline. ``tr`` is the owning TreeRestore (skip/clear/
    finalize semantics and the sparse toggle are ITS methods, so the
    two paths cannot drift); must run under the repo's shared store
    lock."""
    repo = tr.repo
    cache = tr.pack_cache
    if cache is None:
        cache = PackCache(repo.store, rescue=repo.ec_reconstruct)
    targets = _OpenTargets(tr)
    plans: list[_FilePlan] = []
    try:
        with span("restore.plan"):
            placements, groups = _plan(tr, jobs, stats, targets, plans)
        for plan in plans:
            if plan.remaining == 0:  # an empty file: nothing to fetch
                targets.finish(plan, stats)
        if plans:
            _execute(tr, repo, cache, placements, groups, stats, targets)
    except BaseException:
        # zero partial files and no descriptor left on a failed
        # restore: finished files stay, every target created or
        # emptied and not finished is removed
        targets.close_all()
        for plan in plans:
            if plan.claimed and not plan.finished:
                plan.target.unlink(missing_ok=True)
        raise


def _plan(tr, jobs: list, stats: dict, targets: _OpenTargets,
          plans: list):
    """Stage 1: skip-unchanged filtering and claiming of the targets
    whose names were present, offset derivation, and pack grouping
    (module docstring). Appends to ``plans`` as it goes, so the caller
    can clean up after a failure part-way."""
    repo = tr.repo
    # blob_id -> [(plan, offset_in_file)] across ALL files (dedup means
    # one fetched blob may land in many places)
    placements: dict[str, list] = {}
    # pack id (or _BUFFERED) -> [(blob_id, offset_in_pack, length)],
    # ordered by first need so early files' packs fetch first
    groups: "OrderedDict[str, list]" = OrderedDict()
    for entry, target, absent in jobs:
        plan = _FilePlan(entry, target)
        if not absent:
            if tr._skip_unchanged(entry, target):
                stats["skipped"] += 1
                continue
            targets.claim(plan)
        plans.append(plan)
        offset = 0
        for blob_id in entry["content"]:
            ie = repo._entry(blob_id)
            if ie is None:
                raise RepoError(f"blob {blob_id} not in index")
            known = placements.get(blob_id)
            if known is None:
                placements[blob_id] = [(plan, offset)]
                grp = groups.get(ie.pack)
                if grp is None:
                    grp = groups[ie.pack] = []
                grp.append((blob_id, ie.offset, ie.length, ie.raw_length))
            else:
                known.append((plan, offset))
            offset += ie.raw_length
            plan.remaining += 1
        plan.total = offset
    return placements, groups


def _mirror_heal(repo, cache: PackCache, pack_id: str) -> Optional[bytes]:
    """Read-repair heal: fetch the mirror copy, prove it byte-perfect
    (the pack id is the SHA-256 of the whole sealed blob) — falling
    through to Reed-Solomon reconstruction from the pack's ``ec/``
    stripe when no provable mirror exists — then heal the primary with
    one overwriting PUT (verify-then-replace, never delete-first) and
    evict the poisoned cache body so every later fetch sees healthy
    bytes. The mirror arm runs FIRST (one GET beats k shard GETs plus
    a decode) and costs exactly one mirror fetch. Returns the healthy
    body, or None when neither arm proves out (single-copy repository,
    swept mirror, fewer than k provable shards)."""
    body = None
    try:
        mirror = repo.store.get(mirror_key(pack_id))
        if hashlib.sha256(mirror).hexdigest() == pack_id:
            body = mirror
    except NoSuchKey:
        pass
    if body is None:
        try:
            body = repo.ec_reconstruct(pack_id)
        except NoSuchKey:
            return None
    with span("scrub.heal"):
        repo.store.put(pack_key(pack_id), body)
    cache.invalidate(pack_id)
    _M_HEALED.inc()
    record_trigger("scrub_corruption", pack=pack_id,
                   source="read_repair", healed=True)
    return body


def _execute(tr, repo, cache: PackCache, placements,
             groups: "OrderedDict[str, list]", stats: dict,
             targets: _OpenTargets) -> None:
    """Stages 2-4: bounded async pack fetch -> decode -> device-batched
    verify -> positional writes, consuming packs in plan order."""
    ctx = current_context()

    def fetch(pack_id: str) -> Optional[bytes]:
        # pool thread: re-enter the caller's trace so restore.fetch
        # spans attribute to the restore being served
        with use_context(ctx):
            if pack_id == _BUFFERED:
                return None
            return cache.get_pack(pack_id)

    window = envflags.restore_fetch_window()
    batch: list[tuple[str, bytes]] = []
    batch_bytes = 0
    # read-repair state: blob -> (pack, offset, length, raw_length)
    # provenance for everything in ``batch``, and a per-pack memo of
    # heal attempts (None = no healthy mirror) so a corrupt pack costs
    # exactly ONE mirror re-fetch however many blobs/batches it spans
    src: dict[str, tuple[str, int, int, int]] = {}
    healed: dict[str, Optional[bytes]] = {}
    repair_on = envflags.scrub_read_repair_enabled()

    def healthy_body(pack_id: str) -> Optional[bytes]:
        if not repair_on:
            return None
        if pack_id not in healed:
            healed[pack_id] = _mirror_heal(repo, cache, pack_id)
        return healed[pack_id]

    def decode_member(body, blob_id: str, p_off: int, p_len: int,
                      raw_len: int):
        # zero-copy slice: the sealed segment decodes straight off the
        # cached pack body; on the unencrypted+incompressible path
        # ``data`` stays a memoryview all the way to the positional
        # file write
        data = repo._decode_blob(memoryview(body)[p_off:p_off + p_len])
        if len(data) != raw_len:
            raise crypto.IntegrityError(
                f"restore: blob {blob_id} length "
                f"{len(data)} != indexed {raw_len}")
        return data

    def repair_batch(bad: list) -> None:
        """Re-decode the corrupt entries of ``batch`` in place from
        healed pack bodies and re-verify exactly those; raises
        IntegrityError when any blob stays bad (no healthy mirror)."""
        from volsync_tpu.engine.chunker import verify_blob_batch

        bad_set = set(bad)
        repaired: list[tuple[str, bytes]] = []
        for i, (blob_id, _data) in enumerate(batch):
            if blob_id not in bad_set:
                continue
            prov = src.get(blob_id)
            body = healthy_body(prov[0]) if prov is not None else None
            if body is None:
                record_trigger("restore_verify_fail", blob=blob_id)
                raise crypto.IntegrityError(
                    f"restore: blob {blob_id} content hash mismatch")
            batch[i] = (blob_id, decode_member(body, blob_id, *prov[1:]))
            repaired.append(batch[i])
        with span("restore.verify"):
            still_bad = verify_blob_batch(repaired)
        if still_bad:
            record_trigger("restore_verify_fail", blob=still_bad[0])
            raise crypto.IntegrityError(
                f"restore: blob {still_bad[0]} content hash mismatch")

    def flush_batch():
        nonlocal batch, batch_bytes
        if not batch:
            return
        from volsync_tpu.engine.chunker import verify_blob_batch

        with span("restore.verify"):
            bad = verify_blob_batch(batch)
        if bad:
            # device verify caught wrong bytes: heal from the mirror
            # before giving up (module docstring, stage 3)
            repair_batch(bad)
        count("restore.blobs", len(batch))
        for blob_id, data in batch:
            # one span a blob's placements, the hole scan included; a
            # file its last write completes is finished outside it
            # (restore.finalize), so the two add up on this thread
            done = []
            with span("restore.write"):
                # where the holes are is a property of the blob: found
                # once, applied at each place the blob lands
                view = memoryview(data).cast("B")
                runs = (_sparse_runs(view) if tr.sparse
                        else [(0, len(view), False)])
                for plan, offset in placements[blob_id]:
                    targets.write(plan, offset, view, runs)
                    plan.remaining -= 1
                    if plan.remaining == 0:
                        done.append(plan)
            for plan in done:
                targets.finish(plan, stats)
            places = len(placements[blob_id])
            count("restore.writes", places)
            if len(runs) == 1 and not runs[0][2]:
                # went down as one write at every place
                count("restore.writes_dense", places)
            _M_RESTORE_BYTES.inc(len(view) * places)
            count("restore.bytes_restored", len(view) * places)
        batch, batch_bytes = [], 0

    order = deque(groups.items())
    pending: "deque[tuple[str, list, object]]" = deque()
    with ThreadPoolExecutor(max_workers=envflags.restore_fetchers(),
                            thread_name_prefix="restore-fetch") as pool:
        try:
            while order or pending:
                while order and len(pending) < window:
                    pack_id, members = order.popleft()
                    pending.append(
                        (pack_id, members, pool.submit(fetch, pack_id)))
                pack_id, members, fut = pending.popleft()
                with span("restore.fetch_wait"):
                    body = fut.result()
                # the pack's members decode under ONE span, then join
                # the verify batch (same order, same flush rule)
                decoded = []
                with span("restore.decode"):
                    for blob_id, p_off, p_len, raw_len in members:
                        if body is None:
                            # buffered in an active write pipeline of
                            # this process — no pack object to fetch yet
                            data = repo.read_blob_raw(blob_id)
                            if len(data) != raw_len:
                                raise crypto.IntegrityError(
                                    f"restore: blob {blob_id} length "
                                    f"{len(data)} != indexed {raw_len}")
                        else:
                            src[blob_id] = (pack_id, p_off, p_len, raw_len)
                            try:
                                data = decode_member(body, blob_id, p_off,
                                                     p_len, raw_len)
                            except Exception:  # noqa: BLE001 — an
                                # undecodable segment (torn seal,
                                # decompress error, wrong length) is the
                                # same silent-corruption class the verify
                                # stage catches; try the mirror before
                                # dying
                                mbody = healthy_body(pack_id)
                                if mbody is None:
                                    raise
                                data = decode_member(mbody, blob_id,
                                                     p_off, p_len, raw_len)
                        decoded.append((blob_id, data))
                for blob_id, data in decoded:
                    batch.append((blob_id, data))
                    batch_bytes += len(data)
                    if batch_bytes >= tr._VERIFY_BATCH:
                        flush_batch()
            flush_batch()
        except BaseException:
            for _, _, fut in pending:
                fut.cancel()
            for _, _, fut in pending:
                try:
                    fut.exception()
                except BaseException:  # lint: ignore[VL003] — draining
                    # cancelled/failed stragglers so no fetch thread
                    # outlives the pipeline; the primary error below
                    # carries the failure
                    pass
            raise


class RestoreGroup:
    """Parallel multi-snapshot restore sharing one PackCache.

    Queue jobs with :meth:`add`, run them with :meth:`run`. Every job
    gets its own shared-mode repository lock and its own thread; all
    pack fetches for jobs over the same store funnel through one
    single-flight cache, so N restores of one snapshot cost each pack
    ONE store GET for the whole group. Pass each job its OWN
    Repository handle — handles are cheap, and per-job locks/indices
    must not interleave on one object."""

    def __init__(self, *, budget_bytes: Optional[int] = None):
        self._budget = budget_bytes
        # safe unlocked: run() pre-populates per-store caches before
        # any thread starts; job threads only read (Thread.start() is
        # the happens-before edge)
        self._caches: dict[int, PackCache] = {}  # lint: ignore[VL404]
        self._jobs: list[tuple] = []

    def cache_for(self, store, rescue=None) -> PackCache:
        """The group's shared cache for ``store`` (one per distinct
        store object). ``rescue`` (first caller wins) is the cache's
        missing-primary fallback — ec_reconstruct is content-addressed
        and store-scoped, so any job's repository handle over the same
        store derives identical bodies."""
        cache = self._caches.get(id(store))
        if cache is None:
            cache = PackCache(store, budget_bytes=self._budget,
                              rescue=rescue)
            self._caches[id(store)] = cache
        return cache

    def add(self, repo, dest, *, restore_as_of=None, previous: int = 0,
            delete_extra: bool = True) -> None:
        self._jobs.append((repo, dest, restore_as_of, previous,
                           delete_extra))

    def stats(self) -> list[dict]:
        return [c.stats() for c in self._caches.values()]

    def run(self) -> list[Optional[dict]]:
        """Run every queued job concurrently; returns per-job stats
        (None where no snapshot matched) in add() order. The first
        job failure re-raises after EVERY thread has joined — no
        orphaned fetch pool keeps reading behind the caller's back."""
        from volsync_tpu.engine.restore import TreeRestore

        results: list = [None] * len(self._jobs)
        errors: list = [None] * len(self._jobs)
        # caches are created up front, single-threaded: cache_for is
        # not synchronized and must not race inside the job threads
        for repo, *_ in self._jobs:
            self.cache_for(repo.store, rescue=repo.ec_reconstruct)

        def one(i: int, repo, dest, as_of, previous, delete_extra):
            try:
                with repo.lock(exclusive=False):
                    repo.load_index()
                    selected = repo.select_snapshot(
                        restore_as_of=as_of, previous=previous)
                    if selected is None:
                        return
                    snap_id, manifest = selected
                    tr = TreeRestore(repo, pipeline=True)
                    tr.pack_cache = self.cache_for(repo.store)
                    results[i] = tr._run_locked(
                        snap_id, manifest, dest,
                        delete_extra=delete_extra)
            except BaseException as e:  # noqa: BLE001 — collected and
                errors[i] = e           # re-raised by the coordinator

        threads: list[threading.Thread] = []
        for i, job in enumerate(self._jobs):
            t = threading.Thread(target=one, args=(i, *job),
                                 name=f"restore-group-{i}")
            threads.append(t)
            t.start()
        for t in threads:
            t.join()
        for e in errors:
            if e is not None:
                raise e
        return results
