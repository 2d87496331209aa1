"""Copy ledger: accounting for the SANCTIONED host copies that remain
after the zero-copy data-plane refactor (docs/performance.md,
"Zero-copy data movement").

The data plane moves payload bytes as memoryviews over pooled buffers:
chunker segments are filled with ``readinto()``, chunk payloads are
memoryview slices of those segments, the pack seal keeps the segment
list as an iovec all the way into ``ObjectStore.put``, and the restore
path decodes pack slices served as memoryviews by the PackCache. A few
copies are load-bearing and stay — moving bytes onto the device, the
small pending-tail carry between chunker segments, materializing an
iovec for network-backend HTTP bodies. Each of those sites calls
``record_copy(site, nbytes)``:

- ``volsync_copy_bytes_total{site}`` (metrics.py) counts them for
  Prometheus, one fixed label value per site;
- a process-local table feeds ``copies_by_site()`` so benches compute
  ``copy_ratio`` = host bytes copied / payload bytes moved without
  scraping;
- when a sampled trace is active, a flight-recorder instant event
  attributes the copy to the stage span that paid it
  (obs.tracing.trace_instant).

Site names are literal dotted lowercase strings (same discipline as
span names — they become Prometheus label values). The lint rule VL106
(analysis/rules.py) flags byte-materializing calls on hot-path modules
OUTSIDE these sanctioned sites; adding a new copy site means adding a
``record_copy`` call and a reasoned suppression, which reviews see.
"""

from __future__ import annotations

from collections import defaultdict

from volsync_tpu.analysis import lockcheck
from volsync_tpu.metrics import GLOBAL as GLOBAL_METRICS

_lock = lockcheck.make_lock("obs.copyledger")
_by_site: defaultdict = defaultdict(int)
_children: dict = {}  # site -> cached Prometheus label child

# Every site allowed to call record_copy. `volsync lint` (VL505,
# analysis/bufflow.py) fails on a ledgered site outside this set —
# adding one is a reviewed change, same as adding the record_copy call
# itself.
SANCTIONED_SITES = frozenset({
    "chunker.ingest",      # read()-only source copied into the pooled segment
    "chunker.tail_carry",  # sub-min_size tail carried between segments
    "device.pad",          # host buffer staged into the padded device lane
    "device.stage",        # lanes' bytes staged for the batched kernel
    "mesh.pad",            # unpadded segment copied out to the mesh's bucket
    "mesh.stage",          # segment laid out over the seq mesh's chips
    "verify.stage",        # restore verify staging onto the device
    "delta.stage",         # rsync source: a window staged for the match scan
    "sig.stage",           # rsync destination: a window staged for signing
    "delta.search",        # a staged window read again by an every-offset search
    "delta.verify",        # candidate windows the strong check gathers
    "objstore.assemble",   # iovec joined for a contiguous-transport backend
    "repo.buffered_read",  # blob read back while still in the write pipeline
    "svc.frame",           # gRPC frame materialization (protobuf wants bytes)
    "ec.encode",           # field-lane packing + shard blob materialization
    "ec.decode",           # device->host shard copy-out + body assembly
})


def record_copy(site: str, nbytes: int) -> None:
    """Account ``nbytes`` host bytes copied at sanctioned site
    ``site``. Cheap enough for per-segment frequency: one cached
    counter child inc + one dict add; the flight-recorder event is a
    no-op unless a sampled trace is active."""
    if nbytes <= 0:
        return
    child = _children.get(site)
    if child is None:
        # benign race: two threads may both build the child; labels()
        # returns the same underlying child object for the same value
        child = _children[site] = GLOBAL_METRICS.copy_bytes.labels(
            site=site)
    child.inc(nbytes)
    with _lock:
        _by_site[site] += nbytes
    from volsync_tpu.obs.tracing import trace_instant

    trace_instant("copy", site=site, nbytes=nbytes)


def copies_by_site() -> dict:
    """``{site: bytes copied}`` since process start / last reset."""
    with _lock:
        return dict(_by_site)


def total_copied() -> int:
    with _lock:
        return sum(_by_site.values())


def reset_copies() -> None:
    """Zero the process-local table (bench rounds, tests). The
    Prometheus counter is monotonic by contract and is left alone."""
    with _lock:
        _by_site.clear()
