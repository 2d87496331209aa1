"""The network data plane: mover-jax gRPC service, cross-process rsync,
and the asymmetric key split.

Covers the done-conditions of a review record since deleted: an rsync e2e across TWO OS
processes via a real network address, and a gRPC client getting
(boundaries, digests) for a streamed buffer, identical to local chunking.
"""

import io
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from volsync_tpu.movers import devicetransport as dt
from volsync_tpu.ops.gearcdc import GearParams
from volsync_tpu.service import MoverJaxClient, MoverJaxServer

PARAMS = GearParams(min_size=4096, avg_size=16384, max_size=65536)


@pytest.fixture(scope="module")
def service():
    with MoverJaxServer(params=PARAMS, segment_size=256 * 1024) as srv:
        yield srv


def test_chunk_stream_matches_local(service, rng):
    """The north-star contract: a remote stream chunks bit-identically
    to a local scan of the same bytes."""
    from volsync_tpu.engine.chunker import DeviceChunkHasher

    data = rng.bytes(1_200_000)
    with MoverJaxClient("127.0.0.1", service.port, service.token) as client:
        remote = client.chunk_bytes(data)
    local = DeviceChunkHasher(PARAMS).process(
        np.frombuffer(data, np.uint8))
    assert remote == local
    assert b"".join(data[o: o + l] for o, l, _ in remote) == data


@pytest.mark.parametrize("known,size,frames", [
    (True, 0, [(0, True)]),
    (True, 1, [(1, False), (0, True)]),
    (True, 250_000, [(100_000, False), (100_000, False), (50_000, False),
                     (0, True)]),
    (True, 200_000, [(100_000, False), (100_000, False), (0, True)]),
    (False, 100_000, [(100_000, False), (0, True)]),
], ids=["empty", "one-byte", "three-frames", "exact-two", "a-reader"])
def test_what_a_stream_is_on_the_wire(monkeypatch, known, size, frames):
    """What ``chunk_stream`` puts on the wire, from ``chunk_bytes`` and
    from a reader: the pieces in order, every byte once, in frames of
    ``_SEND_CHUNK``, each sent as it is read; then an empty frame with
    the end marker."""
    from volsync_tpu.service import client as client_mod

    monkeypatch.setattr(client_mod, "_SEND_CHUNK", 100_000)
    sent = []

    class Call:
        def __init__(self, requests):
            sent.extend((bytes(r.data), r.eof) for r in requests)

        def __iter__(self):
            return iter(())

    c = MoverJaxClient("127.0.0.1", 1, "t")
    monkeypatch.setattr(c, "_chunk_hash", lambda it, **kw: Call(it))
    data = np.random.RandomState(size).bytes(size)
    if known:
        assert c.chunk_bytes(data) == []
    else:
        assert list(c.chunk_stream(io.BytesIO(data).read)) == []
    c.close()
    assert [(len(d), eof) for d, eof in sent] == frames
    assert b"".join(d for d, _ in sent) == data


def test_a_full_frame_passes_grpcs_receive_cap(service):
    """``_SEND_CHUNK`` is as large as gRPC's default 4 MiB receive cap
    allows: a stream of exactly one full frame, and one of a byte
    more, are both served whole."""
    from volsync_tpu.service.client import _SEND_CHUNK

    assert 4 * 1024 * 1024 - 128 * 1024 < _SEND_CHUNK < 4 * 1024 * 1024
    with MoverJaxClient("127.0.0.1", service.port, service.token) as client:
        for n in (_SEND_CHUNK, _SEND_CHUNK + 1):
            chunks = client.chunk_bytes(b"\x5a" * n)
            assert sum(length for _, length, _ in chunks) == n


@pytest.mark.slow
def test_streaming_segmentation_is_invisible(service, rng):
    """Feeding the stream in awkward piece sizes must not change
    boundaries (the carry-the-tail protocol)."""
    data = rng.bytes(700_001)
    with MoverJaxClient("127.0.0.1", service.port, service.token) as client:
        whole = client.chunk_bytes(data)
        pos = [0]

        def dribble(n):
            piece = data[pos[0]: pos[0] + min(n, 37_777)]
            pos[0] += len(piece)
            return piece

        dribbled = list(client.chunk_stream(dribble))
    assert dribbled == whole


def test_hash_spans_and_info(service, rng):
    from volsync_tpu.repo import blobid

    blobs = [b"", b"x", rng.bytes(5000), rng.bytes(70_000)]
    buf = b"".join(blobs)
    spans, off = [], 0
    for b in blobs:
        spans.append((off, len(b)))
        off += len(b)
    with MoverJaxClient("127.0.0.1", service.port, service.token) as client:
        got = client.hash_spans(buf, spans)
        info = client.info()
    assert got == [blobid.blob_id(b) for b in blobs]
    assert info.avg_size == PARAMS.avg_size
    assert info.align == PARAMS.align


def test_a_span_past_the_data_is_refused(service):
    import grpc

    with MoverJaxClient("127.0.0.1", service.port, service.token) as client:
        assert len(client.hash_spans(b"abcd" * 1024, [(0, 4096)])) == 1
        with pytest.raises(grpc.RpcError) as ei:
            client.hash_spans(b"abcd" * 1024, [(0, 4096), (4000, 97)])
    assert ei.value.code() == grpc.StatusCode.INVALID_ARGUMENT


def test_bad_token_unauthenticated(service):
    import grpc

    with MoverJaxClient("127.0.0.1", service.port, "wrong") as client:
        with pytest.raises(grpc.RpcError) as ei:
            client.info()
    assert ei.value.code() == grpc.StatusCode.UNAUTHENTICATED


def test_rsync_across_two_processes(tmp_path, rng):
    """A REAL second OS process runs the standalone destination listener
    on a network address; this process pushes a tree into it with the
    source half of the key split (the destination's private key never
    present here)."""
    from volsync_tpu.movers.rsync.entry import _push_tree

    src_priv = dt.generate_device_key()
    dst_priv = dt.generate_device_key()
    dest_root = tmp_path / "dest"
    dest_root.mkdir()
    key_file = tmp_path / "dst.key"
    key_file.write_bytes(dst_priv)

    proc = subprocess.Popen(
        [sys.executable, "-m", "volsync_tpu.movers.rsync.standalone",
         "--root", str(dest_root), "--key-file", str(key_file),
         "--source-id", dt.device_id_from_private(src_priv),
         "--bind", "127.0.0.1", "--port", "0"],
        stdout=subprocess.PIPE, text=True, cwd="/root/repo",
        env={"PATH": "/usr/bin:/bin", "PYTHONPATH": "/root/repo",
             "JAX_PLATFORMS": "cpu", "HOME": str(tmp_path)},
    )
    try:
        line = proc.stdout.readline().strip()
        assert line.startswith("PORT "), line
        port = int(line.split()[1])

        src_root = tmp_path / "src"
        (src_root / "sub").mkdir(parents=True)
        files = {"a.bin": rng.bytes(120_000), "sub/b.txt": b"beta" * 999}
        for rel, content in files.items():
            (src_root / rel).write_bytes(content)

        # A WRONG device must be refused at handshake.
        stranger = dt.generate_device_key()
        from volsync_tpu.movers.rsync.channel import ChannelError

        with pytest.raises(ChannelError):
            dt.connect_device("127.0.0.1", port, stranger,
                              dt.device_id_from_private(dst_priv),
                              timeout=3.0)

        ch = dt.connect_device("127.0.0.1", port, src_priv,
                               dt.device_id_from_private(dst_priv))
        stats = _push_tree(ch, src_root)
        ch.send({"verb": "shutdown", "rc": 0})
        ch.recv()
        ch.close()
        assert stats["files"] == 2
        assert proc.wait(timeout=10) == 0  # exit code = transferred rc
        for rel, content in files.items():
            assert (dest_root / rel).read_bytes() == content
    finally:
        if proc.poll() is None:
            proc.kill()


@pytest.mark.slow
def test_service_microbatches_concurrent_streams(rng):
    """Concurrent ChunkHash RPCs coalesce into multi-lane device
    dispatches (SegmentMicroBatcher), and every stream still chunks
    bit-identically to a local scan."""
    from concurrent.futures import ThreadPoolExecutor

    from volsync_tpu.engine.chunker import DeviceChunkHasher
    from volsync_tpu.ops.gearcdc import GearParams

    p4k = GearParams(min_size=4096, avg_size=32768, max_size=65536,
                     align=4096)
    batch_sizes = []
    with MoverJaxServer(params=p4k, segment_size=128 * 1024,
                        batch_window_ms=25.0) as srv:
        assert srv._batcher is not None
        real = srv._batcher._hasher.hash_segments

        def spy(items):
            batch_sizes.append(len(items))
            return real(items)

        srv._batcher._hasher.hash_segments = spy
        payloads = [rng.bytes(200_000 + 13 * i) for i in range(6)]

        def run(data):
            with MoverJaxClient("127.0.0.1", srv.port, srv.token) as cl:
                return cl.chunk_bytes(data)

        with ThreadPoolExecutor(6) as pool:
            results = list(pool.map(run, payloads))

    local = DeviceChunkHasher(p4k)
    for data, got in zip(payloads, results):
        import numpy as _np

        want = local.process(_np.frombuffer(data, _np.uint8), eof=True)
        assert got == want
    # concurrency actually coalesced: at least one multi-lane dispatch
    assert any(s > 1 for s in batch_sizes), batch_sizes


def test_channel_rejects_malformed_frames(rng):
    """Adversarial frames at the sealed-channel decoder: wrong flag,
    corrupt zstd body, truncated seal — every shape must surface as
    ChannelError, never an unhandled exception type."""
    import socket as socket_mod
    import struct as struct_mod

    import pytest

    from volsync_tpu.movers.rsync import channel

    key = b"q" * 32
    box = channel.box_from_key(key)

    def framed_pair():
        a, b = socket_mod.socketpair()
        return a, channel.Framed(b, box)

    # unknown flag byte inside a valid seal
    a, fb = framed_pair()
    payload = box.seal(b"\x07" + b"junk")
    a.sendall(struct_mod.pack(">I", len(payload)) + payload)
    with pytest.raises(channel.ChannelError, match="unknown frame flag"):
        fb.recv()
    a.close()

    # zstd flag with garbage body
    a, fb = framed_pair()
    payload = box.seal(channel._FLAG_ZSTD + rng.bytes(64))
    a.sendall(struct_mod.pack(">I", len(payload)) + payload)
    with pytest.raises(channel.ChannelError, match="bad compressed"):
        fb.recv()
    a.close()

    # empty plaintext
    a, fb = framed_pair()
    payload = box.seal(b"")
    a.sendall(struct_mod.pack(">I", len(payload)) + payload)
    with pytest.raises(channel.ChannelError, match="empty frame"):
        fb.recv()
    a.close()

    # bit-flipped seal (authentication failure)
    a, fb = framed_pair()
    payload = bytearray(box.seal(b"\x00" + b"hi"))
    payload[-1] ^= 0xFF
    a.sendall(struct_mod.pack(">I", len(payload)) + bytes(payload))
    with pytest.raises(channel.ChannelError, match="authentication"):
        fb.recv()
    a.close()


def test_channel_version_negotiation():
    """A mixed-version source/destination pair must fail with an
    EXPLICIT version-mismatch error, not an opaque msgpack/unknown-flag
    failure mid-sync: the hello/hello-ack carry CHANNEL_VERSION and a
    mismatched hello draws a version-mismatch refusal."""
    import socket as socket_mod
    import threading

    from volsync_tpu.movers.rsync import channel

    key = b"v" * 32

    # Same-version pair handshakes fine through the public entry points.
    srv = socket_mod.create_server(("127.0.0.1", 0))
    port = srv.getsockname()[1]
    rc_holder = {}

    def serve_one():
        conn, _ = srv.accept()
        rc_holder["rc"] = channel.serve_session(conn, key, {})

    t = threading.Thread(target=serve_one)
    t.start()
    ch = channel.client_connect("127.0.0.1", port, key)
    ch.send({"verb": "shutdown", "rc": 0})
    assert ch.recv() == {"verb": "ok"}
    t.join(timeout=10)
    assert rc_holder["rc"] == 0

    # An old-version client is refused BEFORE any sealed frame: the
    # preamble layout is version-independent, so this works even
    # across framing changes (the whole point of the mechanism).
    import struct as struct_mod

    def serve_two():
        conn, _ = srv.accept()
        rc_holder["rc2"] = channel.serve_session(conn, key, {})

    t = threading.Thread(target=serve_two)
    t.start()
    def read_exact(s, n):
        buf = b""
        while len(buf) < n:
            piece = s.recv(n - len(buf))
            if not piece:
                break
            buf += piece
        return buf

    sock = socket_mod.create_connection(("127.0.0.1", port), timeout=10)
    sock.settimeout(10)
    sock.sendall(b"VSCH" + struct_mod.pack(
        ">I", channel.CHANNEL_VERSION - 1))
    peer = read_exact(sock, 8)  # server's preamble still arrives readable
    assert peer[:4] == b"VSCH"
    assert struct_mod.unpack(">I", peer[4:])[0] == channel.CHANNEL_VERSION
    assert sock.recv(1) == b""  # then the server hangs up
    sock.close()
    t.join(timeout=10)
    assert rc_holder["rc2"] is None

    # Client side: a future-version server draws an explicit
    # version-mismatch ChannelError, not an opaque framing failure.
    import pytest

    def serve_future():
        conn, _ = srv.accept()
        conn.sendall(b"VSCH" + struct_mod.pack(
            ">I", channel.CHANNEL_VERSION + 1))
        conn.recv(8)
        conn.close()

    t = threading.Thread(target=serve_future)
    t.start()
    with pytest.raises(channel.ChannelError, match="version mismatch"):
        channel.client_connect("127.0.0.1", port, key)
    t.join(timeout=10)
    srv.close()
