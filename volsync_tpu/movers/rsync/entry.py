"""rsync mover data-plane entrypoints (source.sh / destination.sh
analogues).

Destination: bind a listener, publish the bound port on the mover
Service, then serve authenticated sessions restricted to the sync verb
table until the source's ``shutdown <rc>`` arrives — the process exits
with that rc, exactly like the forced-command sshd wrapper
(mover-rsync/destination.sh:19-27, destination-command.sh:4-17).

Source: connect with bounded exponential-backoff retries
(mover-rsync/source.sh:43-62), push a whole-tree delta (TPU delta scan,
engine/deltasync.py), then send shutdown with the transfer rc.

A file of any length moves in bounded memory: both sides read it a
window at a time (``deltasync.WINDOW``), its op stream crosses the
channel in parts of at most ``PART_BYTES`` of literals, and the
destination builds the new file beside the old one and renames it over
the name on the last part (rsync's temporary-then-rename).

A file whose size and mtime at the destination are the source's is
left as it is (rsync's quick check, decided by the destination in the
batch's ``sigs`` round trip): neither side opens it.
"""

from __future__ import annotations

import logging
import os
import socket
import stat as stat_mod
import time
from pathlib import Path
from typing import Iterator, Optional

from volsync_tpu.engine import deltasync
from volsync_tpu.movers.rsync import channel
from volsync_tpu.obs import count, off_ring, span
from volsync_tpu.resilience import RetryPolicy

log = logging.getLogger("volsync_tpu.mover.rsync")

MAX_RETRIES = 5  # source.sh:43 (5 attempts, doubling backoff)

#: Literal bytes in one part of a file's op stream. A part is one frame
#: (channel.py refuses one over 256 MiB) and is packed, compressed and
#: sealed whole on one side and opened whole on the other.
PART_BYTES = 32 * 1024 * 1024
#: Ops in one part: bounds a part of a delta with many short copies.
PART_OPS = 65536
#: A source batch: files that share one ``sigs`` round trip and the
#: scan's staged buffers, up to one window of bytes or this many files.
BATCH_FILES = 1024
#: The destination reads a copied run of its basis this much at a time.
_COPY_CHUNK = 8 * 1024 * 1024


class _FileSource:
    """A regular file read by offset, a piece at a time, as the delta
    engine reads a source; the source side's reads are ``rsync.read``."""

    def __init__(self, path, size: int, source_side: bool = False):
        self.path, self.size, self._timed = os.fspath(path), size, source_side

    def pread(self, offset: int, out) -> None:
        if self._timed:
            with span("rsync.read", ctx=off_ring()):
                self._pread(offset, out)
        else:
            self._pread(offset, out)

    def _pread(self, offset: int, out) -> None:
        view = memoryview(out)
        fd = os.open(self.path, os.O_RDONLY)
        try:
            got = 0
            while got < len(view):
                n = os.preadv(fd, [view[got:]], offset + got)
                if n == 0:
                    raise channel.ChannelError(
                        f"{self.path} changed while it was read")
                got += n
        finally:
            os.close(fd)


# ---------------------------------------------------------------------------
# Destination
# ---------------------------------------------------------------------------


def _apply_meta(path, msg: dict, *, utime: bool = True):
    """xattrs -> chown -> chmod -> utime (the engine's restore order:
    xattrs before a possibly-read-only mode; chown clears suid so
    chmod follows it). Absent keys are skipped — same degrade-to-
    what-the-wire-carries contract as engine/restore."""
    from volsync_tpu.engine.restore import _apply_owner, _apply_xattrs

    _apply_xattrs(path, msg)
    _apply_owner(path, msg)
    if "mode" in msg:
        os.chmod(path, msg["mode"])
    if utime and "mtime_ns" in msg:
        os.utime(path, ns=(msg["mtime_ns"], msg["mtime_ns"]))


class _Incoming:
    """A file on its way in: the temporary it is built in, and the old
    file it copies blocks from."""

    def __init__(self, path: Path):
        self.path = path
        self.basis = None
        if path.is_file() and not path.is_symlink():
            self.basis = os.open(path, os.O_RDONLY)
        self.had_basis = self.basis is not None
        path.parent.mkdir(parents=True, exist_ok=True)
        # where the file is built until its last part has arrived
        self.tmp = path.with_name(f".{path.name}.volsync-part")
        self.f = open(self.tmp, "wb")
        self.size = 0
        self.parts = 0  # parts written: the index of the one expected

    def write(self, ops, block_len: int) -> None:
        from volsync_tpu.engine.restore import _write_sparse

        for op in ops:
            if op[0] == "copy":
                at, end = op[1] * block_len, (op[1] + op[2]) * block_len
                if self.basis is None:
                    raise channel.ChannelError(
                        f"copy op for {self.path.name} with no basis")
                while at < end:
                    piece = os.pread(self.basis, min(_COPY_CHUNK, end - at),
                                     at)
                    if not piece:
                        break  # the basis's short tail block
                    _write_sparse(self.f, piece)  # rsync -S semantics
                    self.size += len(piece)
                    at += len(piece)
            else:
                _write_sparse(self.f, op[1])
                self.size += len(op[1])

    def close(self) -> None:
        self.f.close()
        if self.basis is not None:
            os.close(self.basis)
            self.basis = None

    def abandon(self) -> None:
        """The file will not be finished by the session that began it:
        nothing of it stays, the old file keeps its name."""
        self.close()
        try:
            os.unlink(self.tmp)
        except FileNotFoundError:
            pass

    def commit(self, msg: dict) -> None:
        """Metadata onto the temporary, then the rename over the name:
        no half-written file is ever under it."""
        self.f.truncate(self.size)
        self.close()
        _apply_meta(self.tmp, msg)
        if self.path.is_dir() and not self.path.is_symlink():
            _rm(self.path)
        # over a regular file, a symlink, a special (writing "into" a
        # FIFO would hang) or one name of a hardlinked inode (the other
        # names keep the old bytes): the name alone is replaced
        os.replace(self.tmp, self.path)


def _dest_verbs(root: Path, incoming: Optional[dict] = None):
    """The verb table of ONE session. ``incoming`` holds the files the
    session has begun and not finished: the caller that serves the
    session abandons them when it ends (``serve_destination``), so the
    next session, the source's retry after a dropped link, never finds
    a half-built file to append to."""
    if incoming is None:
        incoming = {}

    def sigs(msg):
        """A batch of files in ONE round trip: rsync's quick check,
        then the signatures of the files it did not settle.

        A name costs one ``lstat``. A regular file whose size and mtime
        are the request's is answered ``same`` and is not opened: its
        permissions, owner and xattrs are brought to the request's by
        the calls that change something (``rsync -a`` without ``-c`` or
        ``-I``; a request without ``size``, an older source's, is never
        ``same``). The other regular files are signed on the device in
        padded dispatches a batch (deltasync ``build_signatures``), a
        long file a window at a time; one the source will send whole
        (``sign`` false) is only looked at. The directories above a
        name are resolved once a directory of the call, not once a
        file: nothing else runs at the destination meanwhile."""
        from volsync_tpu.movers.rclone.sync import _settle_meta

        with span("rsync.sig"):
            top = root.resolve()
            parents: dict = {}  # a name's directory -> resolved
            replies, have = [], []
            for item in msg["files"]:
                p = top / item["path"]
                above = p.parent
                parent = parents.get(above)
                if parent is None:
                    parent = parents[above] = above.resolve()
                path = os.fspath(_held(top, parent, p.name, item["path"]))
                try:
                    st = os.lstat(path)
                except OSError:
                    st = None
                if st is None or not stat_mod.S_ISREG(st.st_mode):
                    replies.append({"exists": False})
                elif (item.get("size") == st.st_size
                        and item.get("mtime_ns") == st.st_mtime_ns):
                    at = {"mode": st.st_mode & 0o7777, "uid": st.st_uid,
                          "gid": st.st_gid, "mtime_ns": st.st_mtime_ns}
                    _settle_meta(path, {**at, **item}, at)
                    replies.append({"exists": True, "same": True})
                else:
                    reply = {"exists": True}
                    replies.append(reply)
                    if item.get("sign", True):
                        have.append((reply, _FileSource(path, st.st_size),
                                     item.get("block_len") or None))
            built = deltasync.build_signatures(
                [(src, block_len) for _reply, src, block_len in have])
            for (reply, _src, _bl), sig in zip(have, built):
                reply.update(sig.to_wire())
            return {"verb": "sigs", "sigs": replies}

    def apply(msg):
        """One part of a file's op stream; the file appears under its
        name when the part marked ``last`` is in."""
        with span("rsync.apply", ctx=off_ring()):
            rel, part = msg["path"], msg.get("part", 0)
            inc = incoming.pop(rel, None)
            if part == 0:
                if inc is not None:
                    inc.abandon()  # the source began the file again
                inc = _Incoming(_safe_join(root, rel))
            elif inc is None or inc.parts != part:
                if inc is not None:
                    inc.abandon()
                raise channel.ChannelError(
                    f"part {part} of {rel!r} where part "
                    f"{0 if inc is None else inc.parts} was expected")
            incoming[rel] = inc
            last = msg.get("last", True)
            try:
                inc.write(msg["ops"], msg["block_len"])
                inc.parts += 1
                if last:
                    inc.commit(msg)
            except BaseException:
                del incoming[rel]
                inc.abandon()
                raise
            if not last:
                return {"verb": "ok"}
            del incoming[rel]
            return {"verb": "ok", "size": inc.size,
                    "basis": inc.had_basis}

    def mkdir(msg):
        path = _safe_join(root, msg["path"])
        if path.is_symlink() or (path.exists() and not path.is_dir()):
            _rm(path)
        path.mkdir(parents=True, exist_ok=True)
        os.chmod(path, msg["mode"])  # full meta arrives via dirmeta
        return {"verb": "ok"}

    def symlink(msg):
        path = _safe_join(root, msg["path"])
        if path.is_symlink() or path.exists():
            _rm(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        os.symlink(msg["target"], path)
        from volsync_tpu.engine.restore import _apply_owner, _apply_xattrs

        _apply_xattrs(path, msg)
        _apply_owner(path, msg)
        if "mtime_ns" in msg:
            os.utime(path, ns=(msg["mtime_ns"], msg["mtime_ns"]),
                     follow_symlinks=False)
        return {"verb": "ok"}

    def link(msg):
        """Hardlink (rsync -H): target becomes another name of the
        already-transferred first-sighting path."""
        path = _safe_join(root, msg["path"])
        source = _safe_join(root, msg["to"])
        # the first sighting is a regular file this sync put there: a
        # symlink under that name is not followed out of the root
        if not stat_mod.S_ISREG(source.lstat().st_mode):
            raise channel.ChannelError(
                f"link target is no regular file: {msg['to']!r}")
        if path.exists() and not path.is_symlink() \
                and os.path.samestat(path.lstat(), source.lstat()):
            return {"verb": "ok"}
        if path.is_symlink() or path.exists():
            _rm(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        os.link(source, path, follow_symlinks=False)
        return {"verb": "ok"}

    def special(msg):
        """FIFO/socket/device nodes (rsync -D)."""
        path = _safe_join(root, msg["path"])
        fmt = msg["fmt"]
        if path.is_symlink() or path.exists():
            st = path.lstat()
            if (stat_mod.S_IFMT(st.st_mode) == fmt
                    and st.st_rdev == msg.get("rdev", 0)):
                _apply_meta(path, msg)
                return {"verb": "ok"}
            _rm(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        if stat_mod.S_ISFIFO(fmt):
            os.mkfifo(path, msg["mode"])
        else:
            try:
                os.mknod(path, fmt | msg["mode"], msg.get("rdev", 0))
            except PermissionError:
                return {"verb": "ok", "skipped": True}  # no CAP_MKNOD
        _apply_meta(path, msg)
        return {"verb": "ok"}

    def dirmeta(msg):
        """Directory metadata, bottom-up AFTER all children are written
        (a child write would bump the parent's restored mtime)."""
        with span("rsync.dirmeta"):
            for d in msg["dirs"]:
                path = _safe_join(root, d["path"]) if d["path"] else root
                # chmod, chown and utime follow a symlink: only a
                # directory that is itself under the name gets them
                # (the root is the mount's own and may be a link)
                if path.is_dir() and not (d["path"] and path.is_symlink()):
                    _apply_meta(path, d)
        return {"verb": "ok"}

    def prune(msg):
        """--delete semantics: remove everything not in the keep set."""
        keep = set(msg["paths"])
        removed = 0
        with span("rsync.prune"):
            for dirpath, dirs, files in os.walk(root, topdown=False):
                for name in files + dirs:
                    p = Path(dirpath, name)
                    rel = str(p.relative_to(root))
                    if rel not in keep:
                        _rm(p)
                        removed += 1
        return {"verb": "ok", "removed": removed}

    return {"sigs": sigs, "apply": apply, "mkdir": mkdir,
            "symlink": symlink, "link": link, "special": special,
            "dirmeta": dirmeta, "prune": prune}


def serve_destination(root: Path, dst_private: bytes, source_id: str,
                      *, bind: str = "127.0.0.1", preferred_port: int = 0,
                      stop_event=None, on_port=None) -> int:
    """The listener proper: accept device-authenticated sessions from the
    pinned source device and serve the sync verb table until the source's
    ``shutdown <rc>`` arrives; that rc becomes the exit code, exactly like
    the forced-command sshd wrapper (destination.sh:19-27).

    ``bind`` un-loopbacks the listener for cross-host deployment
    (BIND_ADDRESS env in the mover contract; the standalone listener
    binds 0.0.0.0)."""
    from volsync_tpu.movers import devicetransport as dt

    try:
        server = socket.create_server((bind, preferred_port))
    except OSError:
        server = socket.create_server((bind, 0))
    port = server.getsockname()[1]
    if on_port is not None:
        on_port(port)
    log.info("rsync destination listening on %s:%d", bind, port)
    server.settimeout(0.5)
    try:
        while stop_event is None or not stop_event.is_set():
            try:
                conn, _ = server.accept()
            except socket.timeout:
                continue
            out = dt.accept_device(conn, dst_private, {source_id})
            if out is None:
                continue  # unknown/failed device: refused at handshake
            ch, _peer = out
            # a session's half-built files are the session's: a link
            # that drops between a file's parts leaves none of them to
            # the source's next attempt, which sends the file from its
            # first part
            incoming: dict = {}
            try:
                rc = channel.serve_channel(
                    ch, _dest_verbs(Path(root), incoming))
            finally:
                for inc in incoming.values():
                    inc.abandon()
            if rc is not None:  # source sent shutdown <rc>
                return rc
        return 1  # stopped without a completed transfer
    finally:
        server.close()


def rsync_destination_entrypoint(ctx) -> int:
    root = ctx.mounts["data"]
    keys = ctx.secrets["keys"]
    # Reuse the previously-published port so the address the source was
    # configured with stays valid across sync iterations (the reference's
    # Service port is stable for the same reason); fall back to an
    # ephemeral port only on first start or if the old port is taken.
    preferred = 0
    svc_name = ctx.env.get("SERVICE")
    if svc_name and ctx.cluster is not None:
        svc = ctx.cluster.try_get("Service", ctx.namespace, svc_name)
        if svc is not None and svc.status.bound_port:
            preferred = svc.status.bound_port
    return serve_destination(
        Path(root), keys["destination"], keys["source-id"].decode(),
        bind=ctx.env.get("BIND_ADDRESS", "127.0.0.1"),
        preferred_port=preferred, stop_event=ctx.stop_event,
        on_port=lambda port: _publish_port(ctx, port))


def _publish_port(ctx, port: int):
    """Publish the bound port on the mover Service (the pod's analogue of
    a named containerPort feeding Service endpoints)."""
    svc_name = ctx.env.get("SERVICE")
    if not svc_name or ctx.cluster is None:
        return
    svc = ctx.cluster.try_get("Service", ctx.namespace, svc_name)
    if svc is not None:
        svc.status.bound_port = port
        if svc.spec.type == "LoadBalancer":
            svc.status.load_balancer_ip = "127.0.0.1"
        svc.status.cluster_ip = "127.0.0.1"
        ctx.cluster.update_status(svc)


# ---------------------------------------------------------------------------
# Source
# ---------------------------------------------------------------------------


class _PushCancelled(Exception):
    """stop_event fired between attempts — classified fatal, so the
    retry policy aborts instead of backing off."""


def rsync_source_entrypoint(ctx) -> int:
    from volsync_tpu.movers import devicetransport as dt

    root = Path(ctx.mounts["data"])
    keys = ctx.secrets["keys"]
    src_private = keys["source"]
    dest_id = keys["destination-id"].decode()
    address = ctx.env["ADDRESS"]
    port = int(ctx.env["PORT"])

    # source.sh:43-62 semantics via the shared layer: MAX_RETRIES
    # attempts, 2s-based growing backoff; FAST_RETRY (tests) caps every
    # sleep at 1s exactly as the old inline min(delay, 1.0) did.
    policy = RetryPolicy.from_env(
        "rsync.push", max_attempts=MAX_RETRIES, base_delay=2.0,
        max_delay=(1.0 if ctx.env.get("FAST_RETRY") else 60.0),
        retryable=(OSError, channel.ChannelError))

    def push_once() -> int:
        if ctx.stop_event.is_set():
            raise _PushCancelled()
        # Mutual device auth: we pin the destination's ID, it pins
        # ours — neither side ever held the other's private key.
        with span("rsync.connect"):
            ch = dt.connect_device(address, port, src_private, dest_id)
        try:
            t0 = time.perf_counter()
            stats = _push_tree(ch, root)
            with span("rsync.finish"):
                ch.send({"verb": "shutdown", "rc": 0})
                ch.recv()
            log.info("rsync push complete: %s", stats)
            ctx.report_transfer(stats.get("bytes", 0),
                                time.perf_counter() - t0)
            return 0
        finally:
            ch.close()

    try:
        return policy.call(push_once)
    except _PushCancelled:
        return 1
    except (OSError, channel.ChannelError) as e:
        log.error("rsync push failed after %d attempts: %s", MAX_RETRIES, e)
        return 1


def _meta_of(st, p=None) -> dict:
    """Wire metadata for one node: mode/mtime always, uid/gid always
    (root:root must converge at the destination too), xattrs
    only-when-present — mirrors engine/backup's tree-entry contract."""
    from volsync_tpu.engine.backup import _read_xattrs

    out = {"mode": st.st_mode & 0o7777, "mtime_ns": st.st_mtime_ns,
           "uid": st.st_uid, "gid": st.st_gid}
    if p is not None:
        xs = _read_xattrs(p)
        if xs:
            out["xattrs"] = xs
    return out


def _call(ch, msg: dict) -> dict:
    """One verb and its ack: the frame packed, compressed, sealed and
    sent, then the wait for the destination's reply."""
    count("rsync.frames")
    ch.send(msg)
    return ch.recv()


def _apply(ch, msg: dict) -> dict:
    """``_call`` of a verb that puts one entry, or one part of a file,
    at the destination (once an entry: off the ring)."""
    with span("rsync.apply_wait", ctx=off_ring()):
        return _call(ch, msg)


def _walk(root: Path) -> list[tuple]:
    """The tree in push order as (kind, relative path, path, lstat,
    wire metadata): a directory, then its files and symlinks by name,
    then its subdirectories."""
    # rsync -x: one file system. stat(), not lstat(): a SYMLINKED
    # replication root (mount indirection) must anchor the device id at
    # the walk's actual filesystem, or every entry looks foreign and
    # prune would wipe the destination.
    root_dev = root.stat().st_dev
    out = []
    for dirpath, dirs, files in os.walk(root):
        dirs.sort()
        for name in sorted(files) + dirs:
            p = Path(dirpath, name)
            rel = str(p.relative_to(root))
            st = p.lstat()
            if st.st_dev != root_dev:
                # -x semantics: a mount point appears as an EMPTY dir
                # (created below if a dir), its contents never cross
                if stat_mod.S_ISDIR(st.st_mode):
                    dirs.remove(name)  # don't descend
                else:
                    continue  # foreign non-dir: skip entirely
            fmt = stat_mod.S_IFMT(st.st_mode)
            kind = {stat_mod.S_IFLNK: "symlink", stat_mod.S_IFDIR: "dir",
                    stat_mod.S_IFREG: "file"}.get(fmt, "special")
            out.append((kind, rel, p, st, _meta_of(st, p)))
    return out


def _push_tree(ch, root: Path) -> dict:
    stats = {"files": 0, "literal_bytes": 0, "copied_bytes": 0, "bytes": 0}
    dirmeta: list[dict] = []
    inode_first: dict = {}  # (dev, ino) -> rel (rsync -H)
    # Regular files accumulate into batches: one sigs round trip and the
    # scan's staged buffers a batch (deltasync.scan_ranges).
    pending: list[tuple] = []
    pending_bytes = 0

    def flush():
        nonlocal pending_bytes
        if pending:
            _push_files_batch(ch, pending, stats)
            pending.clear()
            pending_bytes = 0

    with span("rsync.walk"):
        entries = _walk(root)
    for kind, rel, p, st, meta in entries:
        if kind == "symlink":
            _apply(ch, {"verb": "symlink", "path": rel,
                        "target": os.readlink(p), **meta})
        elif kind == "dir":
            _apply(ch, {"verb": "mkdir", "path": rel,
                        "mode": st.st_mode & 0o7777})
            dirmeta.append({"path": rel, **meta})
        elif kind == "file":
            if st.st_nlink > 1:
                ino = (st.st_dev, st.st_ino)
                first = inode_first.get(ino)
                if first is not None:
                    # the link target must already exist at the
                    # destination — drain any batch holding it
                    flush()
                    _apply(ch, {"verb": "link", "path": rel, "to": first})
                    stats["files"] += 1
                    count("rsync.files")
                    continue
                inode_first[ino] = rel
            pending.append((p, rel, st, meta))
            pending_bytes += st.st_size
            if pending_bytes >= deltasync.WINDOW \
                    or len(pending) >= BATCH_FILES:
                flush()
        else:
            msg = {"verb": "special", "path": rel,
                   "fmt": stat_mod.S_IFMT(st.st_mode), **meta}
            if stat_mod.S_ISBLK(st.st_mode) or stat_mod.S_ISCHR(st.st_mode):
                msg["rdev"] = st.st_rdev
            _apply(ch, msg)
    flush()
    with span("rsync.finish"):
        out = _call(ch, {"verb": "prune",
                         "paths": [e[1] for e in entries]})
        count("rsync.pruned", int(out.get("removed", 0)))
        # Directory metadata last, children-first (deepest paths first),
        # with the replication ROOT itself last of all (path "" — rsync
        # -a with a trailing slash replicates the root dir's meta too):
        # every write above would have bumped the parent's mtime.
        dirmeta.sort(key=lambda d: d["path"].count(os.sep), reverse=True)
        dirmeta.append({"path": "", **_meta_of(root.lstat(), root)})
        _call(ch, {"verb": "dirmeta", "dirs": dirmeta})
    return stats


def _parts(ops: list, source) -> Iterator[list]:
    """A file's op stream cut into parts of at most ``PART_BYTES`` of
    literals and ``PART_OPS`` ops, the literals read as their part is
    made: at least one part, possibly empty (an empty file)."""
    part, held = [], 0
    for op in ops:
        if op[0] == "copy":
            part.append(list(op))
        else:
            at, end = op[1], op[2]
            while at < end:
                n = min(end - at, PART_BYTES - held)
                part.append(["data", deltasync.read_range(source, at, n)])
                at += n
                held += n
                if held >= PART_BYTES:
                    yield part
                    part, held = [], 0
        if len(part) >= PART_OPS:
            yield part
            part, held = [], 0
    yield part


def _push_files_batch(ch, jobs: list, stats: dict):
    """Planner-driven batch push: price FULL vs DELTA per file
    (movers.common.plan_protocol -> engine/protoplan), put the batch's
    files to the destination in ONE ``sigs`` round trip, run the delta
    scan for the files it signed through the staged buffers of
    ``deltasync.scan_ranges``, then apply per file, each in parts.

    The request carries each file's size and wire metadata: a file the
    destination answers ``same`` (rsync's quick check: its size and
    mtime there are the source's) is done: not read, scanned or framed.
    A reply without ``same``, an older destination's, is scanned.

    Every completed delta and timed round trip feeds the rsync
    ``SyncStatsBook``, so the planner's next batch prices against what
    this one actually cost."""
    from volsync_tpu.engine.syncstats import book_for
    from volsync_tpu.movers import common

    book = book_for("rsync")
    sources = [_FileSource(p, st.st_size, source_side=True)
               for p, _rel, st, _meta in jobs]
    plans = []
    for src in sources:
        block_len = deltasync.pick_block_len(src.size)
        decision = common.plan_protocol(
            "rsync", src.size, candidates=("full", "delta"),
            block_len=block_len)
        plans.append((decision.protocol, block_len))
    # NOT timed as a latency sample: the reply embeds the destination's
    # signature computation (and, first time, its jit compile), which
    # would poison the rtt EWMA by orders of magnitude. Small apply
    # acks below are the latency proxy.
    with span("rsync.sig_wait"):
        reply = _call(ch, {"verb": "sigs", "files": [
            {"path": rel, "block_len": block_len, "size": src.size,
             "sign": proto == "delta", **meta}
            for (_p, rel, _st, meta), src, (proto, block_len)
            in zip(jobs, sources, plans)]})
    same = {i for i, r in enumerate(reply["sigs"]) if r.get("same")}
    sig_by_idx = {i: deltasync.FileSignature.from_wire(r)
                  for i, r in enumerate(reply["sigs"])
                  if r.get("exists") and i not in same
                  and plans[i][0] == "delta"}
    scanned = sorted(sig_by_idx)
    ops_by_idx = dict(zip(scanned, deltasync.scan_ranges(
        [(sources[i], sig_by_idx[i]) for i in scanned]))) if scanned else {}

    def synced(size: int) -> None:
        count("rsync.files")
        count("rsync.bytes_synced", size)
        stats["files"] += 1
        stats["bytes"] += size

    for idx, ((_p, rel, _st, meta), src) in enumerate(zip(jobs, sources)):
        if idx in same:
            count("rsync.files_skipped")
            count("rsync.bytes_skipped", src.size)
            synced(src.size)
            continue
        _proto, block_len = plans[idx]
        if idx in ops_by_idx:
            ops = ops_by_idx[idx]
            block_len = sig_by_idx[idx].block_len
        else:
            # planner said FULL, or the destination has no basis: the
            # whole file ships as literals (still delta framing)
            ops = [("lit", 0, src.size)] if src.size else []
        literal = deltasync.literal_bytes(ops)
        t0 = time.perf_counter()
        parts = _parts(ops, src)
        part, index = next(parts), 0
        while part is not None:
            ahead = next(parts, None)
            msg = {"verb": "apply", "path": rel, "ops": part,
                   "block_len": block_len, "part": index,
                   "last": ahead is None}
            if ahead is None:
                msg.update(meta)
            out = _apply(ch, msg)
            if out.get("verb") != "ok":
                raise channel.ChannelError(f"apply failed for {rel}: {out}")
            part, index = ahead, index + 1
        elapsed = time.perf_counter() - t0
        if idx in ops_by_idx:
            book.observe_delta(literal, src.size)
            count("rsync.files_delta")
            count("rsync.copied_bytes", src.size - literal)
            stats["copied_bytes"] += src.size - literal
        elif out.get("basis"):
            count("rsync.files_full")
        else:
            count("rsync.files_new")
        # same small/large split as resilience.link_totals(): bulk
        # applies sample bandwidth, near-empty ones sample latency
        if literal >= 16 * 1024:
            book.observe_link(literal, elapsed)
        else:
            book.observe_rtt(elapsed)
        count("rsync.literal_bytes", literal)
        stats["literal_bytes"] += literal
        synced(src.size)


# ---------------------------------------------------------------------------


def _safe_join(root: Path, rel: str) -> Path:
    """``root / rel`` with the directories above the last name
    resolved and held inside ``root``. The last name is not followed: a
    verb replaces the entry itself (``apply``, ``mkdir``, ``symlink``,
    ``special``, ``link``'s new name, prune) or reads it only if it is
    a regular file (``sigs``), and following a symlink that a first
    sync put there would aim the second sync's ``symlink`` verb at its
    target. The verbs that act ON an entry, ``dirmeta`` and ``link``'s
    ``to``, check by ``lstat`` that it is no symlink: a link under a
    peer-sent name is never followed out of the root."""
    top = root.resolve()
    p = top / rel
    return _held(top, p.parent.resolve(), p.name, rel)


def _held(top: Path, parent: Path, name: str, rel: str) -> Path:
    """``parent / name`` for a ``parent`` already resolved, or the
    refusal of a name that is none or lies outside ``top``
    (``_safe_join``'s check; ``sigs`` resolves a directory once for the
    names it holds)."""
    p = parent / name
    if name in ("", ".", "..") or (
            not str(p).startswith(str(top) + os.sep) and p != top):
        raise channel.ChannelError(f"path escapes root: {rel!r}")
    return p


def _rm(path: Path):
    import shutil

    if path.is_dir() and not path.is_symlink():
        shutil.rmtree(path, ignore_errors=True)
    else:
        # symlinks, regular files, AND specials (FIFO/socket/device:
        # is_file() is False for those — the same fix as
        # engine/restore._rmtree; a no-op here would make the
        # replacement verbs raise FileExistsError and prune leave
        # stale specials behind while still counting them removed)
        path.unlink(missing_ok=True)
