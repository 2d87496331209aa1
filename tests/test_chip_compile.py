"""Compile the main path's device programs for a DESCRIBED v5e chip.

Nothing runs: the TPU compiler installed in the sandbox compiles for a
chip that is described, not attached, and raises what the chip's
compiler would raise (a Mosaic layout refusal, a program that does not
fit). The suite otherwise forces the CPU, where every
``jax.default_backend() == "tpu"`` arm is dead — these are the only
tests that see the Pallas kernels at real widths.

All in ONE file, the topology described inside a module-scoped fixture
(never at import, never in conftest): only the xdist worker that is
handed this file loads the TPU library.
"""

import numpy as np
import pytest

MiB = 1 << 20


@pytest.fixture(scope="module")
def topo():
    import os

    # the compiler would otherwise write its logs beside the temp dir
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no chip compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache(topo):
    """A described-chip compile is written to the persistent cache but
    cannot be read back without a chip; keep the cache out of it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture
def tpu_arms(monkeypatch, no_compile_cache):
    """Steer the backend gates onto their TPU arms for the trace: the
    Pallas leaf path and the unrolled compressions, as on the chip.
    ``jax.default_backend()`` itself still answers "cpu" here."""
    from volsync_tpu.ops import md5, segment, sha256

    for mod in (sha256, segment):
        monkeypatch.setattr(mod, "use_pallas_leaves", lambda: True)
    monkeypatch.setattr(sha256, "_compress", sha256._compress_unrolled)
    monkeypatch.setattr(segment, "_compress", sha256._compress_unrolled)
    monkeypatch.setattr(md5, "_compress", md5._compress_unrolled)


def _params():
    from volsync_tpu.engine.chunker import params_from_config
    from volsync_tpu.repo.repository import DEFAULT_CHUNKER

    return params_from_config(DEFAULT_CHUNKER)


def _segment_kw(P):
    from volsync_tpu.ops.segment import segment_caps

    p = _params()
    cand_cap, chunk_cap = segment_caps(P, p)
    return dict(min_size=p.min_size, avg_size=p.avg_size,
                max_size=p.max_size, seed=p.seed, mask_s=p.mask_s,
                mask_l=p.mask_l, align=p.align, cand_cap=cand_cap,
                chunk_cap=chunk_cap)


def _sds(shape, dtype, sharding):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernels(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def test_fused_segment_32mib(tpu_arms, one_chip):
    import jax.numpy as jnp

    from volsync_tpu.ops.segment import chunk_hash_segment

    P = 32 * MiB
    c = chunk_hash_segment.lower(
        _sds((P,), jnp.uint8, one_chip), np.int32(P), eof=True,
        **_segment_kw(P)).compile()
    assert _kernels(c) >= 2  # tile transpose + SHA lane kernel


@pytest.mark.parametrize("S,P", [(1, 32 * MiB), (2, 8 * MiB)],
                         ids=["1x32MiB", "2x8MiB"])
def test_batched_segments(tpu_arms, one_chip, S, P):
    """The batched program the main path dispatches on a TPU, at the
    flat [S*P] staging it is really given (an [S, P] input made the
    same 2x8 MiB compile take 48 s; this is ~20 s)."""
    import jax.numpy as jnp

    from volsync_tpu.ops.segment import chunk_hash_segments

    c = chunk_hash_segments.lower(
        _sds((S * P,), jnp.uint8, one_chip),
        _sds((S,), jnp.int32, one_chip), _sds((S,), jnp.bool_, one_chip),
        **_segment_kw(P)).compile()
    assert _kernels(c) >= 2


def test_sha256_rows_pallas(tpu_arms, one_chip):
    import jax
    import jax.numpy as jnp

    from volsync_tpu.ops.sha256 import _LANE_TILE, _sha256_rows_pallas

    B = 4 * _LANE_TILE
    c = jax.jit(_sha256_rows_pallas).lower(
        _sds((B * 64, 16), jnp.uint32, one_chip),
        _sds((B,), jnp.int32, one_chip)).compile()
    assert _kernels(c) >= 1


def test_pallas_transpose(tpu_arms, one_chip):
    import jax
    import jax.numpy as jnp

    from volsync_tpu.ops.segment import _pallas_transpose

    c = jax.jit(_pallas_transpose).lower(
        _sds((8192, 1024), jnp.uint32, one_chip)).compile()
    assert _kernels(c) >= 1


@pytest.mark.parametrize("P,N", [(32 * MiB, 128), (72 * MiB, 128),
                                 (72 * MiB, 1024), (72 * MiB, 512)])
def test_span_roots_device(tpu_arms, one_chip, P, N):
    """The rclone checksum / restore-verify program at one 32 MiB
    staging bucket, and at what a restore's 64 MiB verify batch
    presents (``restic-dest-10g.restore``): the 72 MiB bucket, a batch
    of large blobs and one of a thousand small files' blobs; and the
    ~444 whole files of a hash pass of ``rclone-smallfiles.sync``."""
    import jax.numpy as jnp

    from volsync_tpu.ops.segment import span_roots_device

    c = span_roots_device.lower(
        _sds((P,), jnp.uint8, one_chip),
        _sds((N,), jnp.int32, one_chip),
        _sds((N,), jnp.int32, one_chip)).compile()
    assert _kernels(c) >= 2


def test_rs_encode_4_plus_2(tpu_arms, one_chip):
    """No kernel expected: the GF(2^8) product is plain XLA gathers —
    this guards only that it compiles at a 16 MiB pack's grid."""
    import jax.numpy as jnp

    from volsync_tpu.ops import rs

    gm = rs.rs_generator_matrix(4, 2)
    key = tuple(np.asarray(gm, np.uint8).reshape(-1).tolist())
    c = rs._gf_matmul_fn(key, 2, 4).lower(
        _sds((4, 1024, 4096), jnp.uint8, one_chip)).compile()
    assert c.memory_analysis().output_size_in_bytes == 2 * 1024 * 4096


def test_delta_match_rows_moves_no_element_an_offset(no_compile_cache,
                                                     one_chip):
    """The rsync source's every-offset search at the mover's 64 MiB
    window and 8 KiB blocks (``rsync-1g.push``): it compiles, loops on
    the device over groups of rows by two sorts, and no scatter or
    gather in it moves an element an offset (only whole rows of bytes,
    the rows' bases and the hits come by a gather): what made PR 44's
    program cost 75 ms whatever it was given."""
    import re

    import jax.numpy as jnp

    from volsync_tpu.engine.deltasync import _Geometry
    from volsync_tpu.ops.delta import delta_match_rows

    geo = _Geometry.of(8192)
    i32 = _sds((), jnp.int32, one_chip)
    text = delta_match_rows.lower(
        _sds((geo.window,), jnp.uint8, one_chip),
        _sds((geo.sig_cap(1),), jnp.uint32, one_chip), i32,
        _sds((geo.rows,), jnp.int32, one_chip),
        _sds((geo.rows,), jnp.int32, one_chip), i32, i32, i32,
        window=8192, group_rows=geo.group_rows,
        max_candidates=geo.cand_cap, capacity=geo.search_cap,
    ).compile().as_text()
    assert " while(" in text and len(re.findall(r" sort\(", text)) == 2
    assert " scatter(" not in text
    gathered = [(dtype, [int(d) for d in dims.split(",") if d])
                for dtype, dims in re.findall(
                    r"= (\w+)\[([\d,]*)\]\S* gather\(", text)]
    assert gathered
    for dtype, dims in gathered:
        elements = int(np.prod(dims))
        assert (dtype == "u8" and dims == [geo.group_rows, 1024]) \
            or elements <= max(geo.group_rows, geo.cand_cap), (dtype, dims)


def test_mesh_fused_fn_four_devices(tpu_arms, topo):
    """parallel/sharded_chunker's fused program on a 4-device ``seq``
    mesh of the described chips, 4 x 8 MiB: the per-shard kernels plus
    the all-gathers of the digest stream and candidate tables."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from volsync_tpu.ops.segment import segment_caps
    from volsync_tpu.parallel.sharded_chunker import (
        SEQ,
        _build_fused_fn,
        make_stream_mesh,
    )

    p = _params()
    mesh = make_stream_mesh(topo.devices[:4])
    shard_len = 8 * MiB
    cand_cap, chunk_cap = segment_caps(4 * shard_len, p)
    fn = _build_fused_fn(mesh, p, shard_len, max(1024, cand_cap // 4),
                         chunk_cap, True)
    c = fn.lower(
        _sds((4, shard_len), jnp.uint8,
             NamedSharding(mesh, PartitionSpec(SEQ, None))),
        _sds((), jnp.int32, NamedSharding(mesh, PartitionSpec()))).compile()
    text = c.as_text()
    assert text.count("tpu_custom_call") >= 2
    assert "all-gather" in text


def test_mesh_fused_segment_at_the_streams_shard(tpu_arms, topo):
    """The same program at the shard a four-chip host's stream gives
    each chip since the segment follows the shards: 4 x 32 MiB (what
    one chip is dispatched without the mesh), not the last segment."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from volsync_tpu.parallel.sharded_chunker import (
        SEQ, MeshChunkHasher, make_stream_mesh)

    hasher = MeshChunkHasher(_params(), make_stream_mesh(topo.devices[:4]))
    shard_len = 32 * MiB
    assert hasher.shard_bucket(4 * shard_len) == shard_len
    fn = hasher._fused_fn(shard_len, *hasher.fused_caps(shard_len), False)
    c = fn.lower(
        _sds((4, shard_len), jnp.uint8,
             NamedSharding(hasher.mesh, PartitionSpec(SEQ, None))),
        _sds((), jnp.int32,
             NamedSharding(hasher.mesh, PartitionSpec()))).compile()
    assert _kernels(c) >= 2 and "all-gather" in c.as_text()
