"""Mesh-sharded CDC chunk+hash: the multi-chip product path.

``MeshChunkHasher`` is a drop-in for ``engine.chunker.DeviceChunkHasher``
(same ``process(buffer, eof)`` protocol), so ``stream_chunks`` /
``TreeBackup`` — the real backup path — run sharded over a device mesh
with no orchestration changes. The reference has *no* intra-volume
parallelism at all (SURVEY.md §5 long-context note: rsync/restic stream
single-threaded). What the chip read of it is in PERF.md §5-6
(``dedup-1t.backup-mesh4``): a first backup of one 2 GiB stream moves
at 1.7 times the one-chip engine's rate on the same four-chip host,
mostly because a segment goes from the pooled buffer to four chips at
once with no staging copy; the host still holds the chips back.

Per segment, two shard_map kernels over a 1-D ``seq`` ring of devices:

1. **Candidates** — each shard gear-hashes its slice with a 31-byte left
   halo from its neighbor (``ppermute``; the same seam pattern ring
   attention uses), masks strict/lax CDC candidates, and compacts them to
   per-shard index lists. Shard 0 zeroes its halo contribution so
   positions hash exactly as the unsharded recurrence started from h=0.
2. **Leaf digests** — after the host's sparse FastCDC boundary walk
   (identical to the single-chip walk, so boundaries are bit-identical),
   every 4 KiB Merkle leaf of every chunk is assigned to the shard its
   start falls in; each shard takes a 4095-byte *right* halo so leaves
   crossing the seam read their tail from the neighbor, and hashes its
   leaves as independent gather lanes (ops/sha256.sha256_chunks_device).

Blob ids then assemble host-side from the leaf digests (repo/blobid.py),
byte-identical to the single-device path — golden tests enforce equality
against both DeviceChunkHasher and hashlib.
"""

from __future__ import annotations

import numpy as np

from volsync_tpu.engine.chunker import PendingSegment, _pow2ceil
from volsync_tpu.obs import count, record_copy, span
from volsync_tpu.ops.gearcdc import GearParams, _mix_u32, select_boundaries
from volsync_tpu.parallel.mesh import SEQ, make_stream_mesh
from volsync_tpu.repo import blobid

_HALO = 31              # gear window is 32 bytes -> 31 bytes of left context
_LEAF = blobid.LEAF_SIZE


class MeshChunkHasher:
    """chunk+hash a byte buffer sharded over a device mesh.

    Compile-count discipline matches DeviceChunkHasher: shard lengths are
    drawn from pow2 buckets, candidate/leaf capacities from doubling
    buckets, so steady-state streaming reuses a handful of compiled
    programs regardless of workload shape.
    """

    # NOT safe for concurrent process() calls: sharded dispatches issue
    # mesh collectives whose per-device enqueue order must match across
    # the ring, and the compiled-fn caches race. A TreeBackup hashes one
    # file at a time, so one backup never makes two; two backups at once
    # may not share one of these.

    def __init__(self, params: GearParams, mesh=None):
        import jax

        self.params = params
        self.mesh = mesh if mesh is not None else make_stream_mesh()
        self.n_shards = self.mesh.devices.size
        self._cand_cache: dict = {}
        self._leaf_cache: dict = {}
        self._fused_cache: dict = {}
        self._jax = jax

    # -- what a stream (and a warm plan) needs to know of the layout --------

    def shard_bucket(self, length: int) -> int:
        """Bytes a shard holds of a ``length``-byte segment: a pow2
        bucket, so that streaming reuses a handful of programs."""
        S = self.n_shards
        return _pow2ceil((length + S - 1) // S, max(_LEAF, 64 * 1024))

    def buffer_bucket(self, length: int) -> int:
        """Pad target of a segment (the mesh's ``_buffer_bucket``)."""
        return self.n_shards * self.shard_bucket(length)

    def stream_segment_size(self, segment_size: int) -> int:
        """The ``segment_size`` a stream over this hasher fills at:
        each shard gets what one chip is dispatched, and a full
        segment (fill window + max_size + a carried tail under
        max_size) just fits the shards' bucket instead of spilling
        into the next power of two."""
        return max(segment_size,
                   self.buffer_bucket(self.n_shards * segment_size)
                   - 2 * self.params.max_size)

    def fused_caps(self, shard_len: int) -> tuple[int, int]:
        """(cand_cap, chunk_cap) of the fused program at ``shard_len``.
        cand_cap is per shard (compaction is local; the header's
        candidate slot carries the WORST shard's true count)."""
        from volsync_tpu.ops.segment import segment_caps

        cand_cap, chunk_cap = segment_caps(self.n_shards * shard_len,
                                           self.params)
        return max(1024, cand_cap // self.n_shards), chunk_cap

    def fused_programs(self) -> list[tuple[int, int, int, bool]]:
        """(shard_len, cand_cap, chunk_cap, eof) of every fused program
        built so far (what a warm plan has to have listed)."""
        return sorted(self._fused_cache)

    # -- public protocol (mirrors DeviceChunkHasher) ------------------------

    def process(self, buffer, *, eof: bool = True) -> list[tuple[int, int, str]]:
        return self.begin(buffer, eof=eof).finish()

    def begin(self, buffer, *, eof: bool = True,
              valid_len: int | None = None) -> PendingSegment:
        """Stage the segment onto the shards and launch its program,
        leaving it IN FLIGHT (fused path; the split-phase paths walk
        the boundaries on the host and are done when this returns).
        ``buffer`` may already be padded to ``buffer_bucket(valid_len)``
        with a zeroed pad lane."""
        if isinstance(buffer, (bytes, bytearray, memoryview)):
            buffer = np.frombuffer(buffer, dtype=np.uint8)
        length = (int(buffer.shape[0]) if valid_len is None
                  else int(valid_len))
        if length == 0:
            return PendingSegment([], None, None)
        p = self.params
        if length <= p.min_size:
            if not eof:
                return PendingSegment([], None, None)
            return PendingSegment(
                [(0, length, blobid.blob_id(buffer[:length]))], None, None)

        data, shard_len = self._upload(buffer, length)
        if p.align == _LEAF:
            return PendingSegment.fused_segment(
                self, data, length, self.dispatch(data, length, eof=eof),
                eof)
        idx_s, idx_l = self._candidates(data, shard_len, length)
        chunks = select_boundaries(idx_s, idx_l, length, p, eof=eof)
        if not chunks:
            return PendingSegment([], None, None)
        hexes = self._span_roots(data, shard_len, chunks)
        return PendingSegment(
            [(int(s), int(l), h) for (s, l), h in zip(chunks, hexes)],
            None, None)

    # -- fused page-aligned path (one dispatch, one small fetch) ------------

    def dispatch(self, data, length: int, *, eof: bool,
                 cand_cap: int | None = None, chunk_cap: int | None = None):
        """The ops/segment.py one-round-trip protocol, sharded: page
        digests and candidates compute per shard (pages never cross
        seams — shard_len % LEAF == 0 — so there is NO halo at all),
        the 32-bytes-per-4KiB digest stream all-gathers over the seq
        ring (1/128th of the data volume, riding ICI), and the FastCDC
        walk + root assembly run replicated on the gathered table. ONE
        replicated ~20 KiB result comes back. Returns the in-flight
        handle and the capacities it was compiled at (the
        FusedSegmentHasher.dispatch protocol)."""
        shard_len = int(data.shape[1])
        cc, kc = self.fused_caps(shard_len)
        cand_cap = cand_cap or cc
        chunk_cap = chunk_cap or kc
        fn = self._fused_fn(shard_len, cand_cap, chunk_cap, eof)
        with span("mesh.launch", shards=self.n_shards, shard_len=shard_len):
            handle = fn(data, np.int32(length))
        return handle, (cand_cap, chunk_cap)

    def finish(self, data, length: int, inflight, *, eof: bool):
        """Fetch + decode; capacity overflows are reported in-band and
        re-dispatched with doubled tables, exactly like the single-chip
        FusedSegmentHasher."""
        from volsync_tpu.ops.segment import decode_with_overflow_check

        handle, (cand_cap, chunk_cap) = inflight
        with span("mesh.fetch"):
            packed = np.asarray(handle)
        with span("mesh.decode"):
            chunks, consumed, grown = decode_with_overflow_check(
                packed, length, cand_cap, chunk_cap)
        if grown is None:
            assert not eof or consumed == length
            return chunks, consumed
        with span("mesh.overflow_retry", shard_len=int(data.shape[1])):
            return self.finish(
                data, length,
                self.dispatch(data, length, eof=eof, cand_cap=grown[0],
                              chunk_cap=grown[1]),
                eof=eof)

    def _fused_fn(self, shard_len: int, cand_cap: int, chunk_cap: int,
                  eof: bool):
        key = (shard_len, cand_cap, chunk_cap, eof)
        fn = self._fused_cache.get(key)
        if fn is None:
            fn = _build_fused_fn(self.mesh, self.params, shard_len,
                                 cand_cap, chunk_cap, eof)
            self._fused_cache[key] = fn
        return fn

    # -- upload -------------------------------------------------------------

    def _upload(self, buffer: np.ndarray, length: int):
        """Lay the segment out [S, Ls] with shard i holding bytes
        [i*Ls, (i+1)*Ls), Ls the shards' pow2 bucket, and put each
        shard on its chip. A buffer that is not yet ``buffer_bucket``
        long is padded here (one host copy, ledger site ``mesh.pad``);
        what goes to the chips is ledgered as ``mesh.stage``."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        S = self.n_shards
        shard_len = self.shard_bucket(length)
        padded = S * shard_len
        have = int(buffer.shape[0])
        with span("mesh.stage", shards=S, shard_len=shard_len):
            if have < padded:
                record_copy("mesh.pad", length)
                buffer = np.pad(buffer, (0, padded - have))
            elif have > padded:
                buffer = buffer[:padded]
            data = jax.device_put(
                buffer.reshape(S, shard_len),
                NamedSharding(self.mesh, P(SEQ, None)))
            record_copy("mesh.stage", padded)
        # one segment staged = one dispatch (a capacity retry runs the
        # program again on the same shards: span mesh.overflow_retry)
        count("mesh.dispatches")
        count("mesh.shards",
              len({s.device for s in data.addressable_shards}))
        count("mesh.bytes_valid", length)
        count("mesh.bytes_padded", padded - length)
        return data, shard_len

    # -- kernel 1: CDC candidates -------------------------------------------

    def _cand_fn(self, key):
        fn = self._cand_cache.get(key)
        if fn is None:
            if isinstance(key, tuple) and key[0] == "aligned":
                fn = _build_cand_aligned_fn(self.mesh, self.params,
                                            key[1], key[2])
            else:
                fn = _build_cand_fn(self.mesh, self.params, *key)
            self._cand_cache[key] = fn
        return fn

    def _candidates(self, data, shard_len: int, length: int):
        if self.params.align > 1:
            return self._candidates_aligned(data, shard_len, length)
        # Expected strict-candidate density is 2^-(bits+norm); 1/64 bytes
        # covers any mask down to 2^-6 (same bound as DeviceChunkHasher).
        cap = max(_pow2ceil(shard_len // 64, 1024), 1024)
        while True:
            idx_s, cnt_s, idx_l, cnt_l = self._cand_fn((shard_len, cap))(
                data, np.int32(length))
            cnt_s = np.asarray(cnt_s)
            cnt_l = np.asarray(cnt_l)
            worst = int(max(cnt_s.max(), cnt_l.max()))
            if worst <= cap:
                break
            cap = _pow2ceil(worst, cap * 2)  # dense data: retry, recompile
        idx_s = np.asarray(idx_s)
        idx_l = np.asarray(idx_l)
        # Per-shard compacted lists -> one globally sorted list (shards
        # are contiguous byte ranges in order, so concatenation sorts).
        out_s = np.concatenate([idx_s[i, : int(cnt_s[i])]
                                for i in range(self.n_shards)])
        out_l = np.concatenate([idx_l[i, : int(cnt_l[i])]
                                for i in range(self.n_shards)])
        return out_s, out_l

    def _candidates_aligned(self, data, shard_len: int, length: int):
        """Aligned cuts need NO halo: the gear window at an eligible
        position sits inside one align-byte row, which never crosses a
        shard seam (shard_len % align == 0) — the collective disappears
        and each shard compacts its own row lanes."""
        cap = 1024
        while True:
            pos, flags, cnt = self._cand_fn(("aligned", shard_len, cap))(
                data, np.int32(length))
            cnt = np.asarray(cnt)
            worst = int(cnt.max())
            if worst <= cap:
                break
            cap = _pow2ceil(worst, cap * 2)
        pos = np.asarray(pos)
        flags = np.asarray(flags)
        out_l = []
        out_s = []
        for i in range(self.n_shards):
            n = int(cnt[i])
            p = pos[i, :n]
            out_l.append(p)
            out_s.append(p[flags[i, :n]])
        return np.concatenate(out_s), np.concatenate(out_l)

    # -- kernel 2: Merkle leaf digests --------------------------------------

    def _leaf_fn(self, shard_len: int, cap: int):
        key = (shard_len, cap)
        fn = self._leaf_cache.get(key)
        if fn is None:
            fn = _build_leaf_fn(self.mesh, shard_len, cap)
            self._leaf_cache[key] = fn
        return fn

    def _span_roots(self, data, shard_len: int,
                    chunks: list[tuple[int, int]]) -> list[str]:
        S = self.n_shards
        # Assign every leaf to the shard its start falls in; record
        # (shard, slot) per leaf for reassembly.
        per_shard: list[list[tuple[int, int]]] = [[] for _ in range(S)]
        placement: list[tuple[int, int]] = []  # leaf -> (shard, slot)
        spans: list[tuple[int, int]] = []      # chunk -> (first leaf, count)
        for start, clen in chunks:
            first = len(placement)
            n = blobid.leaf_count(clen)
            for k in range(n):
                off = start + k * _LEAF
                llen = min(_LEAF, start + clen - off)
                shard = off // shard_len
                slot = len(per_shard[shard])
                per_shard[shard].append((off - shard * shard_len, llen))
                placement.append((shard, slot))
            spans.append((first, n))

        cap = _pow2ceil(max((len(v) for v in per_shard), default=1),
                        max(shard_len // _LEAF // 8, 128))
        starts = np.zeros((S, cap), np.int32)
        lengths = np.zeros((S, cap), np.int32)
        for s in range(S):
            for slot, (off, llen) in enumerate(per_shard[s]):
                starts[s, slot] = off
                lengths[s, slot] = llen
        digests = np.asarray(
            self._leaf_fn(shard_len, cap)(data, starts, lengths)
        ).astype(">u4")  # [S, cap, 8] big-endian
        flat = digests.tobytes()

        def leaf_bytes(shard: int, slot: int) -> bytes:
            base = (shard * cap + slot) * 32
            return flat[base: base + 32]

        out = []
        for (first, n), (_, clen) in zip(spans, chunks):
            leaves = [leaf_bytes(*placement[first + k]) for k in range(n)]
            out.append(blobid.root_from_leaves(clen, leaves))
        return out


def _build_fused_fn(mesh, params: GearParams, shard_len: int,
                    cand_cap: int, chunk_cap: int, eof: bool):
    """shard_map kernel for the fused page-aligned segment protocol.

    Layout: data [S, Ls] with shard i holding bytes [i*Ls, (i+1)*Ls);
    Ls % LEAF == 0, so pages (== full Merkle leaves, align == LEAF)
    never cross seams and per-shard page hashing needs no collective.
    Per shard: page digests (ops/segment._page_digests_flat — the
    Pallas transpose + SHA lane kernel on TPU, the XLA scan on CPU) and
    aligned gear candidates. Then: all_gather of the digest words and
    the compacted candidate lists (sentinel-padded, re-sorted), psum'd
    counts, and the ops/segment walk + root loop on the replicated
    tables — every shard computes the identical ~20 KiB packed result.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    from volsync_tpu.ops.gearcdc import gear_at_aligned
    from volsync_tpu.ops.segment import (
        _page_digests_flat,
        _root_digests_loop,
        _select_boundaries_device,
        _tail_leaf_digests,
    )
    from volsync_tpu.ops.sha256 import _LANE_TILE, use_pallas_leaves

    p = params
    S = mesh.devices.size
    align = p.align
    npp = shard_len // _LEAF  # real pages per shard
    npps = ((npp + _LANE_TILE - 1) // _LANE_TILE * _LANE_TILE
            if use_pallas_leaves() else npp)  # padded (Pallas lane grid)
    R = shard_len // align
    mask_s = np.uint32(p.mask_s)
    mask_l = np.uint32(p.mask_l)
    sentinel = jnp.int32(2**31 - 2)

    def local(data, valid_len):  # data: [1, Ls]
        i = jax.lax.axis_index(SEQ)
        row = data[0]
        valid_len = valid_len.astype(jnp.int32)

        # Each stage under a jax.named_scope (metadata only), the
        # one-chip program's six names (ops/segment.py) plus the two
        # gathers, so a device trace names the stage an op belongs to.
        # --- per-shard page digests (no halo: pages don't cross seams)
        # Word-major per shard: the cross-shard word_index below
        # assumes the per-shard kernel layout.
        with jax.named_scope("page_sha"):
            flat_local = _page_digests_flat(row, npps)  # [8 * npps]
        with jax.named_scope("digest_all_gather"):
            flat_g = jax.lax.all_gather(flat_local, SEQ, axis=0)
            flat_g = flat_g.reshape(S * 8 * npps)  # [S, 8*npps] flat

        def word_index(j, page):  # word j of GLOBAL page p
            return (page // npp) * (8 * npps) + j * npps + page % npp

        # --- per-shard aligned candidates -> global sorted tables
        with jax.named_scope("gear_candidates"):
            h = gear_at_aligned(row, p.seed, align)  # [R]
            pos = (i * shard_len
                   + jnp.arange(R, dtype=jnp.int32) * align + (align - 1))
            ok = pos < valid_len
            is_s = ((h & mask_s) == 0) & ok
            is_l = ((h & mask_l) == 0) & ok
        with jax.named_scope("compact"):
            ridx_l = jnp.nonzero(is_l, size=cand_cap, fill_value=R)[0]
            safe = jnp.clip(ridx_l, 0, R - 1)
            lpos = jnp.where(ridx_l < R, pos[safe], sentinel)
            lstrict = jnp.where(ridx_l < R, is_s[safe], False)
            spos = jnp.where(lstrict, lpos, sentinel)
        with jax.named_scope("candidate_all_gather"):
            pos_l = jnp.sort(
                jax.lax.all_gather(lpos, SEQ, axis=0).reshape(-1))
            pos_s = jnp.sort(
                jax.lax.all_gather(spos, SEQ, axis=0).reshape(-1))
            nl = jax.lax.psum(jnp.sum(is_l).astype(jnp.int32), SEQ)
            ns = jax.lax.psum(jnp.sum(is_s).astype(jnp.int32), SEQ)
            worst = jax.lax.pmax(jnp.sum(is_l).astype(jnp.int32), SEQ)

        # --- replicated FastCDC walk (global positions are multiples of
        # align too, so the successor-table fast form applies with the
        # GLOBAL row count S*R)
        with jax.named_scope("boundary_walk"):
            starts, lens, count, consumed = _select_boundaries_device(
                pos_s, jnp.minimum(ns, S * cand_cap),
                pos_l, jnp.minimum(nl, S * cand_cap),
                valid_len, min_size=p.min_size, avg_size=p.avg_size,
                max_size=p.max_size, chunk_cap=chunk_cap, eof=eof,
                align=align, n_rows=S * R)

        # --- the ONE possibly-partial tail leaf: hashed by its owner
        # shard, psum-broadcast, spliced into the gathered table.
        live = jnp.arange(chunk_cap, dtype=jnp.int32) < count
        end = jnp.where(count > 0,
                        starts[jnp.maximum(count - 1, 0)]
                        + lens[jnp.maximum(count - 1, 0)], 0)
        has_tail = (count > 0) & (end % _LEAF != 0)
        tail_page = jnp.maximum(end - 1, 0) // _LEAF
        tail_len = end - tail_page * _LEAF
        owner = tail_page // npp
        mine = has_tail & (owner == i)
        with jax.named_scope("tail_sha"):
            t_dig = _tail_leaf_digests(
                row, (tail_page % npp)[None],
                jnp.where(mine, tail_len, 0)[None])[0]
            t_dig = jax.lax.psum(
                jnp.where(mine, t_dig, jnp.uint32(0)), SEQ)
            ovr = jnp.where(has_tail,
                            word_index(jnp.arange(8, dtype=jnp.int32),
                                       tail_page),
                            S * 8 * npps)  # OOB -> dropped
            flat_g = flat_g.at[ovr].set(t_dig, mode="drop")

        # --- replicated roots + packed result
        with jax.named_scope("merkle_roots"):
            nleaves = jnp.where(live, (lens + (_LEAF - 1)) // _LEAF, 0)
            page0 = starts // _LEAF
            roots = _root_digests_loop(flat_g, S * npp, page0, nleaves,
                                       lens, live, word_index=word_index)
        header = jnp.stack([count.astype(jnp.uint32),
                            consumed.astype(jnp.uint32),
                            worst.astype(jnp.uint32),
                            jnp.sum(nleaves).astype(jnp.uint32)])
        return jnp.concatenate([header, starts.astype(jnp.uint32),
                                lens.astype(jnp.uint32), roots.reshape(-1)])

    sharded = shard_map(
        local, mesh=mesh,
        in_specs=(P(SEQ, None), P()),
        out_specs=P(),
        check_vma=False,
    )

    def mesh_fused_segment(data, valid_len):  # the program's trace name
        return sharded(data, valid_len)

    return jax.jit(mesh_fused_segment)


def _gear_doubling(g):
    """The 5 shift-scale-add passes turning per-byte table values ([L]
    uint32) into the 32-byte-window gear hash (see ops/gearcdc.py
    ``gear_hash_positions``, which starts from the bytes and so cannot
    take a halo whose table values were zeroed)."""
    import jax.numpy as jnp

    h = g
    for m in (1, 2, 4, 8, 16):
        shifted = jnp.pad(h[:-m], (m, 0))
        h = h + (shifted << np.uint32(m))
    return h


def _build_cand_fn(mesh, params: GearParams, shard_len: int, cap: int):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    seed = np.uint32(params.seed & 0xFFFFFFFF)
    mask_s = np.uint32(params.mask_s)
    mask_l = np.uint32(params.mask_l)

    def local(data, valid_len):  # data: [1, Ls] this shard's slice
        n = jax.lax.axis_size(SEQ)
        i = jax.lax.axis_index(SEQ)
        row = data[0]
        # Left halo: previous shard's 31-byte tail, shifted right around
        # the ring; shard 0 (true stream start) contributes zero table
        # values for its halo positions, because the unsharded
        # recurrence starts from h=0 (zeroing the halo BYTES would
        # still contribute _mix_u32(seed) a position).
        halo = jax.lax.ppermute(
            row[-_HALO:], SEQ, [(j, (j + 1) % n) for j in range(n)])
        ext = jnp.concatenate([halo, row])
        g = _mix_u32(ext.astype(jnp.uint32) + seed)
        g = jnp.where((i == 0)
                      & (jnp.arange(ext.shape[0], dtype=jnp.int32) < _HALO),
                      jnp.uint32(0), g)
        h = _gear_doubling(g)[_HALO:]  # [Ls]
        pos = i * shard_len + jnp.arange(shard_len, dtype=jnp.int32)
        ok = pos < valid_len
        is_s = ((h & mask_s) == 0) & ok
        is_l = ((h & mask_l) == 0) & ok
        loc_s = jnp.nonzero(is_s, size=cap, fill_value=shard_len)[0]
        loc_l = jnp.nonzero(is_l, size=cap, fill_value=shard_len)[0]
        # Global positions; fill lanes fall off the end harmlessly (the
        # host slices each shard's list by its true count).
        return ((i * shard_len + loc_s)[None],
                jnp.sum(is_s)[None],
                (i * shard_len + loc_l)[None],
                jnp.sum(is_l)[None])

    sharded = shard_map(
        local, mesh=mesh,
        in_specs=(P(SEQ, None), P()),
        out_specs=(P(SEQ, None), P(SEQ), P(SEQ, None), P(SEQ)),
    )
    return jax.jit(sharded)


def _build_cand_aligned_fn(mesh, params: GearParams, shard_len: int,
                           cap: int):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    from volsync_tpu.ops.gearcdc import gear_at_aligned

    align = params.align
    mask_s = np.uint32(params.mask_s)
    mask_l = np.uint32(params.mask_l)
    R = shard_len // align

    def local(data, valid_len):  # data: [1, Ls]
        i = jax.lax.axis_index(SEQ)
        h = gear_at_aligned(data[0], params.seed, align)  # [R], no halo
        pos = (i * shard_len
               + jnp.arange(R, dtype=jnp.int32) * align + (align - 1))
        ok = pos < valid_len
        is_s = ((h & mask_s) == 0) & ok
        is_l = ((h & mask_l) == 0) & ok
        ridx = jnp.nonzero(is_l, size=cap, fill_value=R)[0]
        safe = jnp.clip(ridx, 0, R - 1)
        flags = jnp.where(ridx < R, is_s[safe], False)
        out_pos = (i * shard_len + ridx.astype(jnp.int32) * align
                   + (align - 1))
        return out_pos[None], flags[None], jnp.sum(is_l)[None]

    sharded = shard_map(
        local, mesh=mesh,
        in_specs=(P(SEQ, None), P()),
        out_specs=(P(SEQ, None), P(SEQ, None), P(SEQ)),
    )
    return jax.jit(sharded)


def _build_leaf_fn(mesh, shard_len: int, cap: int):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    from volsync_tpu.ops.sha256 import sha256_chunks_device

    assert shard_len >= _LEAF, "shards must cover at least one leaf"

    def local(data, starts, lengths):  # [1, Ls], [1, cap], [1, cap]
        n = jax.lax.axis_size(SEQ)
        row = data[0]
        # Right halo: my leaves may run up to LEAF-1 bytes past my slice;
        # fetch the next shard's head (ring: the last shard's wrap-around
        # halo is never referenced — the stream ends inside it).
        halo = jax.lax.ppermute(
            row[: _LEAF - 1], SEQ, [(j, (j - 1) % n) for j in range(n)])
        ext = jnp.concatenate([row, halo])
        digests = sha256_chunks_device(
            ext, starts[0], lengths[0], max_len=_LEAF)
        return digests[None]  # [1, cap, 8]

    sharded = shard_map(
        local, mesh=mesh,
        in_specs=(P(SEQ, None), P(SEQ, None), P(SEQ, None)),
        out_specs=P(SEQ, None, None),
    )
    return jax.jit(sharded)
