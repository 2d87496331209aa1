"""What a correct mirror of a directory tree holds in its bucket, with
``os``, ``json`` and ``hashlib`` alone (file ids through
``reference/blobid.py``): the plain reference the rclone mover's source
direction is held to. Written from the layout's description
(``docs/usage/rclone.md``): a file's bytes are one object
``objects/<checksum>``, the checksum being the blob id of the whole
file; ``index/manifest.json`` names, under ``shards``, the objects of
``index/shards/`` whose ``entries`` together map every relative path
to its kind, size, mode, mtime, owner and xattrs, and its checksum (a
file) or target (a symlink). This file imports nothing of the program.

The destination side needs no second reference: a volume synced down
is compared with the source tree by ``reference/treecmp.py``.
"""

from __future__ import annotations

import base64
import json
import os
import stat

from benchmark.reference.blobid import blob_id


def _xattrs(path: str) -> dict:
    try:
        names = os.listxattr(path, follow_symlinks=False)
    except OSError:
        return {}
    out = {}
    for name in names:
        try:
            out[name] = base64.b64encode(
                os.getxattr(path, name, follow_symlinks=False)).decode()
        except OSError:
            pass
    return out


def expected_index(tree) -> dict[str, dict]:
    """{relative path: what a correct index says of it}, of every
    directory, regular file and symlink under ``tree`` (sockets and
    devices are not mirrored; the root itself is not an entry)."""
    tree = os.fspath(tree)
    out = {}
    for dirpath, dirnames, filenames in os.walk(tree):
        for name in dirnames + filenames:
            full = os.path.join(dirpath, name)
            st = os.lstat(full)
            entry = {"uid": st.st_uid, "gid": st.st_gid,
                     "xattrs": _xattrs(full)}
            if stat.S_ISLNK(st.st_mode):
                entry.update(type="symlink", target=os.readlink(full))
            elif stat.S_ISDIR(st.st_mode):
                entry.update(type="dir", mode=stat.S_IMODE(st.st_mode),
                             mtime_ns=st.st_mtime_ns)
            elif stat.S_ISREG(st.st_mode):
                with open(full, "rb") as f:
                    digest = blob_id(f.read())
                entry.update(type="file", size=st.st_size,
                             mode=stat.S_IMODE(st.st_mode),
                             mtime_ns=st.st_mtime_ns, digest=digest)
            else:
                continue
            out[os.path.relpath(full, tree)] = entry
    return out


def expected_objects(tree, index: dict | None = None) -> set[str]:
    """The checksums a correct mirror of ``tree`` stores, each once."""
    index = expected_index(tree) if index is None else index
    return {e["digest"] for e in index.values() if e["type"] == "file"}


def parse_index(get) -> dict[str, dict]:
    """The index as the bucket holds it, by the layout's description:
    ``get(key)`` returns an object's bytes under the mirror's prefix."""
    manifest = json.loads(get("index/manifest.json"))
    entries: dict[str, dict] = {}
    for name in manifest["shards"].values():
        entries.update(json.loads(get(f"index/shards/{name}"))["entries"])
    return entries


_CONTENT = ("type", "size", "digest", "target")
_META = ("mode", "mtime_ns", "uid", "gid", "xattrs")


def compare_bucket(listing, index_entries: dict, tree,
                   want: dict | None = None) -> dict:
    """A bucket against the reference. ``listing`` is the names under
    ``objects/``, ``index_entries`` the index read back
    (``parse_index``), ``tree`` the source directory (``want``: its
    ``expected_index``, where the caller has it already). Returns the
    relative paths or names that are ``objects_missing`` and
    ``objects_extra`` (the listing against ``expected_objects``),
    ``index_missing`` and ``index_extra`` (paths), ``index_stale`` (an
    entry whose kind, size, checksum or target is not the tree's) and
    ``index_meta`` (mode, mtime, owner or xattrs differ)."""
    want = expected_index(tree) if want is None else want
    names = {key.rsplit("/", 1)[-1] for key in listing}
    objects = expected_objects(tree, want)
    out = {"objects_missing": sorted(objects - names),
           "objects_extra": sorted(names - objects),
           "index_missing": sorted(set(want) - set(index_entries)),
           "index_extra": sorted(set(index_entries) - set(want)),
           "index_stale": [], "index_meta": []}
    for rel in sorted(set(want) & set(index_entries)):
        a, b = want[rel], index_entries[rel]
        if any(a.get(k) != b.get(k) for k in _CONTENT):
            out["index_stale"].append(rel)
        elif any(a.get(k) != b.get(k) for k in _META):
            out["index_meta"].append(rel)
    return out
