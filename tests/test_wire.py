"""Zero-copy scatter-gather data plane (docs/wire.md): big-frame round
trips over every comm backend, the send-path zero-copy counter contract,
receive-pool reuse/ownership, dumps/loads parity across compression
codecs and the opaque forwarding path, and the corrupt-header guards."""

from __future__ import annotations

import asyncio
import struct

import numpy as np
import pytest

from distributed_tpu import config
from distributed_tpu.comm.core import connect, listen
from distributed_tpu.exceptions import CommClosedError
from distributed_tpu.protocol.buffers import WIRE, BufferPool, recv_pool
from distributed_tpu.protocol.core import dumps, loads
from distributed_tpu.protocol.serialize import Serialize, Serialized, ToPickle

from conftest import gen_test


def _rewrap(msg):
    """Re-mark array payloads for the return hop (a deserializing read
    hands the handler plain ndarrays)."""
    if isinstance(msg, dict):
        return {
            k: Serialize(v) if isinstance(v, np.ndarray) else v
            for k, v in msg.items()
        }
    return msg


async def _echo_listener(scheme: str):
    async def echo(comm):
        try:
            while True:
                msg = await comm.read()
                await comm.write(_rewrap(msg))
        except Exception:
            pass

    listener = listen(f"{scheme}://127.0.0.1:0", echo)
    await listener.start()
    return listener


def _tls_security_or_skip():
    from distributed_tpu.security import Security

    try:
        return Security.temporary()
    except ImportError:
        pytest.skip("cryptography not available for tls://")


# ------------------------------------------------- backend round trips


@pytest.mark.parametrize("scheme", ["tcp", "ws", "inproc"])
def test_big_frame_roundtrip_over_backend(scheme):
    """Frames larger than comm.shard survive every backend: the shard
    split, the scatter write, the pooled read and the adjacency merge
    are all exercised by a payload that must fragment."""

    @gen_test()
    async def run():
        arr = np.random.default_rng(0).integers(
            0, 255, 1_500_000, dtype=np.uint8
        )
        with config.set({"comm.shard": "256KiB"}):
            listener = await _echo_listener(scheme)
            comm = await connect(listener.contact_address)
            try:
                await comm.write({"op": "blob", "data": Serialize(arr)})
                out = await comm.read()
                np.testing.assert_array_equal(out["data"], arr)
            finally:
                await comm.close()
                listener.stop()

    run()


@gen_test()
async def test_big_frame_roundtrip_over_tls():
    sec = _tls_security_or_skip()
    arr = np.arange(200_000, dtype=np.int64)
    listener = listen(
        "tls://127.0.0.1:0",
        lambda comm: _echo_forever(comm),
        **sec.get_listen_args("scheduler"),
    )
    await listener.start()
    comm = await connect(
        listener.contact_address, **sec.get_connection_args("client")
    )
    try:
        await comm.write({"data": Serialize(arr)})
        out = await comm.read()
        np.testing.assert_array_equal(out["data"], arr)
    finally:
        await comm.close()
        listener.stop()


async def _echo_forever(comm):
    try:
        while True:
            await comm.write(_rewrap(await comm.read()))
    except Exception:
        pass


# ------------------------------------------- zero-copy send contract


@gen_test()
async def test_tcp_send_path_records_zero_payload_copies():
    """The acceptance contract: a payload of any size (1 KB to 8 MB)
    crosses the TCP send path with dtpu_wire_payload_copies == 0 — no
    bytes(frame), no joins, straight memoryview hand-off to the
    transport."""
    listener = await _echo_listener("tcp")
    comm = await connect(listener.contact_address)
    try:
        for size in (1024, 65536, 2_000_000, 8 * 2**20):
            arr = np.random.default_rng(1).integers(
                0, 255, size, dtype=np.uint8)
            before = WIRE.snapshot()
            await comm.write({"op": "blob", "data": Serialize(arr)})
            out = await comm.read()
            after = WIRE.snapshot()
            np.testing.assert_array_equal(out["data"], arr)
            # the echo round trip covers BOTH sides' send paths (client
            # and server live in this process): zero copies total
            assert after["payload_copies"] - before["payload_copies"] == 0
            assert after["bytes_sent"] - before["bytes_sent"] >= 2 * size
            assert after["bytes_recv"] - before["bytes_recv"] >= 2 * size
    finally:
        await comm.close()
        listener.stop()


@gen_test()
async def test_sharded_opaque_forwarding_merges_zero_copy():
    """deserialize=False: sharded frames reassemble as ONE zero-copy
    slice of the contiguous receive buffer (the store-and-forward path
    the scheduler depends on), and a forwarding hop preserves bytes."""
    arr = np.random.default_rng(2).integers(0, 255, 1_000_000, dtype=np.uint8)
    with config.set({"comm.shard": "128KiB"}):
        async def handle(comm):
            try:
                while True:
                    await comm.write(await comm.read())
            except Exception:
                pass

        listener = listen("tcp://127.0.0.1:0", handle, deserialize=False)
        await listener.start()
        comm = await connect(listener.contact_address, deserialize=False)
        try:
            before = WIRE.snapshot()
            await comm.write({"op": "blob", "data": Serialize(arr)})
            out = await comm.read()
            after = WIRE.snapshot()
            opaque = out["data"]
            assert isinstance(opaque, Serialized)
            # the sharded leaf merged into a single zero-copy view
            assert len(opaque.frames) == 1
            assert isinstance(opaque.frames[0], memoryview)
            assert after["payload_copies"] - before["payload_copies"] == 0
            # final consumer sees the original bytes
            final = loads(dumps({"x": opaque}))["x"]
            np.testing.assert_array_equal(final, arr)
        finally:
            await comm.close()
            listener.stop()


# ----------------------------------------------------- receive pool


@gen_test()
async def test_pool_reuse_on_control_plane_and_drop_on_pinned_views():
    listener = await _echo_listener("tcp")
    comm = await connect(listener.contact_address)
    try:
        # warm the pool classes
        await comm.write({"op": "warm"})
        await comm.read()
        before = WIRE.snapshot()
        for i in range(8):
            await comm.write({"op": "ctl", "i": i})
            await comm.read()
        after = WIRE.snapshot()
        # control messages fully deserialize (msgpack copies), so their
        # buffers return to the pool and get reused: hits, no drops
        assert after["pool_hits"] - before["pool_hits"] >= 8
        assert after["pool_drops"] - before["pool_drops"] == 0
        # a numpy payload pins its zero-copy view of the receive buffer:
        # the pool must DROP that buffer, never recycle it under the view
        before = WIRE.snapshot()
        arr = np.arange(50_000, dtype=np.int64)
        await comm.write({"data": Serialize(arr)})
        out = await comm.read()
        after = WIRE.snapshot()
        assert after["pool_drops"] - before["pool_drops"] >= 1
        np.testing.assert_array_equal(out["data"], arr)
        # ... and the received array still reads correctly afterwards
        # even as the pool keeps serving other messages
        for i in range(4):
            await comm.write({"op": "ctl", "i": i})
            await comm.read()
        np.testing.assert_array_equal(out["data"], arr)
    finally:
        await comm.close()
        listener.stop()


def test_buffer_pool_classes_and_export_probe():
    pool = BufferPool(max_bytes=1 << 20)
    b1 = pool.acquire(10_000)
    assert len(b1) == 1 << 14  # next pow2 class
    pool.release(b1)
    assert pool.pooled_bytes == len(b1)
    b2 = pool.acquire(12_000)
    assert b2 is b1  # class hit
    # a live export keeps the buffer out of the pool
    view = memoryview(b2)
    pool.release(b2)
    assert pool.pooled_bytes == 0
    view.release()
    pool.release(b2)
    assert pool.pooled_bytes == len(b2)
    # giants bypass pooling entirely (exact alloc)
    g = pool.acquire((1 << pool.MAX_CLASS) + 1)
    assert len(g) == (1 << pool.MAX_CLASS) + 1
    pool.release(g)
    assert pool.pooled_bytes == len(b2)
    # budget cap: releases beyond max_bytes are dropped
    small_pool = BufferPool(max_bytes=1 << 14)
    c1 = small_pool.acquire(1 << 14)
    c2 = small_pool.acquire(1 << 14)
    small_pool.release(c1)
    small_pool.release(c2)
    assert small_pool.pooled_bytes == 1 << 14


# ------------------------------------------------- dumps/loads parity


def _random_message(rng: np.random.Generator, depth: int = 0):
    kind = rng.integers(0, 8 if depth < 2 else 6)
    if kind == 0:
        return {"k": int(rng.integers(0, 100)), "s": "x" * int(rng.integers(0, 50))}
    if kind == 1:
        return rng.integers(0, 255, int(rng.integers(0, 200_000)),
                            dtype=np.uint8).tobytes()
    if kind == 2:
        return Serialize(rng.random(int(rng.integers(1, 100_000))))
    if kind == 3:
        return Serialize(
            rng.integers(0, 255, int(rng.integers(1, 300_000)), dtype=np.uint8)
        )
    if kind == 4:
        return ToPickle({"fn": len, "args": [1, 2, 3]})
    if kind == 5:
        return [int(x) for x in rng.integers(0, 10, 5)]
    if kind == 6:
        return {f"key-{i}": _random_message(rng, depth + 1) for i in range(3)}
    return [_random_message(rng, depth + 1) for i in range(3)]


def _assert_parity(a, b):
    if isinstance(a, np.ndarray):
        np.testing.assert_array_equal(a, b)
    elif isinstance(a, dict):
        assert set(a) == set(b)
        for k in a:
            _assert_parity(a[k], b[k])
    elif isinstance(a, (list, tuple)):
        assert len(a) == len(b)
        for x, y in zip(a, b):
            _assert_parity(x, y)
    else:
        assert a == b


@pytest.mark.parametrize("compression", [None, "zlib", "zstd"])
def test_loads_dumps_parity_property(compression):
    """Property test: random nested messages survive dumps/loads
    bit-identically across codecs, shard sizes, and the opaque
    (deserialize=False) forwarding hop."""
    if compression == "zstd":
        pytest.importorskip("zstandard")
    rng = np.random.default_rng(42)
    for trial in range(10):
        msg = {"op": "prop", "body": _random_message(rng)}
        expect = loads(dumps(msg, compression=None))  # reference decode
        for shard in ("64KiB", "64MiB"):
            with config.set({"comm.shard": shard}):
                frames = dumps(msg, compression=compression)
                # frames always satisfy the wire contract
                assert all(
                    isinstance(f, (bytes, bytearray, memoryview))
                    for f in frames
                )
                _assert_parity(loads(frames), expect)
                # opaque hop: loads without deserializers, re-dump, load
                opaque = loads(
                    dumps(msg, compression=compression), deserializers=False
                )
                _assert_parity(loads(dumps(opaque)), expect)


# ------------------------------------------------- corrupt-header guards


async def _malicious_server(payload: bytes):
    """A raw TCP server that writes ``payload`` and half-closes."""

    async def handle(reader, writer):
        writer.write(payload)
        try:
            await writer.drain()
            writer.write_eof()
        except Exception:
            pass

    server = await asyncio.start_server(handle, "127.0.0.1", 0)
    return server, server.sockets[0].getsockname()[1]


@gen_test()
async def test_oversized_lengths_header_rejected():
    """One corrupt/hostile header must not trigger an arbitrary-size
    allocation: the lengths sum is capped by comm.max-message-bytes."""
    from distributed_tpu.comm.tcp import TCP

    bogus = struct.pack("<Q", 2) + struct.pack("<QQ", 2**40, 2**40)
    server, port = await _malicious_server(bogus)
    try:
        with config.set({"comm.max-message-bytes": "64MiB"}):
            reader, writer = await asyncio.open_connection("127.0.0.1", port)
            comm = TCP(reader, writer, "tcp://local", "tcp://peer")
            with pytest.raises(CommClosedError, match="max-message-bytes"):
                await comm.read()
            assert comm.closed
    finally:
        server.close()


@gen_test()
async def test_bad_frame_count_rejected():
    from distributed_tpu.comm.tcp import TCP, MAX_FRAME_COUNT

    bogus = struct.pack("<Q", MAX_FRAME_COUNT + 1)
    server, port = await _malicious_server(bogus)
    try:
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        comm = TCP(reader, writer, "tcp://local", "tcp://peer")
        with pytest.raises(CommClosedError, match="bad frame count"):
            await comm.read()
    finally:
        server.close()


@pytest.mark.parametrize("scheme", ["tcp", "ws"])
def test_cancelled_idle_read_leaves_comm_usable(scheme):
    """Teardown paths cancel pending reads on comms they then close in
    an orderly way: a cancel while idle-waiting at a message boundary
    (readexactly is all-or-nothing) must NOT abort the comm — only a
    cancel once header bytes are consumed desyncs the stream."""

    @gen_test()
    async def run():
        listener = await _echo_listener(scheme)
        comm = await connect(listener.contact_address)
        try:
            reader = asyncio.ensure_future(comm.read())
            await asyncio.sleep(0.05)  # parked on the idle header wait
            reader.cancel()
            with pytest.raises(asyncio.CancelledError):
                await reader
            await comm.write({"op": "ping", "n": 42})
            out = await comm.read()
            assert out["n"] == 42
        finally:
            await comm.close()
            listener.stop()

    run()


@gen_test()
async def test_unexpected_acquire_error_aborts_comm():
    """MemoryError from the pool acquire (a legitimate under-cap message
    on a memory-tight process) escapes the CommClosedError/OSError arms;
    the header is already consumed, so the comm must abort — a later
    read would parse payload bytes as a frame count."""
    from distributed_tpu.comm.tcp import TCP
    from distributed_tpu.protocol.buffers import recv_pool

    bogus = struct.pack("<Q", 1) + struct.pack("<Q", 4096) + b"x" * 4096
    server, port = await _malicious_server(bogus)
    pool = recv_pool()

    def boom(n):
        raise MemoryError(f"cannot allocate {n}")

    orig, pool.acquire = pool.acquire, boom
    try:
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        comm = TCP(reader, writer, "tcp://local", "tcp://peer")
        with pytest.raises(MemoryError):
            await comm.read()
        assert comm._closed  # aborted, not merely at_eof
    finally:
        pool.acquire = orig
        server.close()


@gen_test()
async def test_truncated_payload_raises_comm_closed():
    """Header promises more bytes than the peer ever sends: the pooled
    readinto path must surface CommClosedError, not hang or mis-frame."""
    from distributed_tpu.comm.tcp import TCP

    bogus = struct.pack("<Q", 1) + struct.pack("<Q", 4096) + b"x" * 100
    server, port = await _malicious_server(bogus)
    try:
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        comm = TCP(reader, writer, "tcp://local", "tcp://peer")
        with pytest.raises(CommClosedError, match="read failed"):
            await comm.read()
    finally:
        server.close()


def test_scatter_frames_never_mutates_caller_bytearray():
    """Coalescing small frames must only extend scratch buffers
    scatter_frames itself created: a large caller-owned bytearray frame
    sits in the scatter list as-is, and a following small frame must
    not be appended INTO it."""
    from distributed_tpu.comm.tcp import COALESCE_MAX, scatter_frames

    big = bytearray(b"x" * (COALESCE_MAX + 1))
    small = b"tail"
    n_before = len(big)
    bufs, total = scatter_frames([big, small])
    assert len(big) == n_before, "caller-owned frame was mutated"
    assert total == 8 + 2 * 8 + len(big) + len(small)
    assert sum(len(b) for b in bufs) == total
    assert b"".join(bytes(b) for b in bufs).endswith(b"x" * 5 + b"tail")


@gen_test()
async def test_ws_control_frame_length_capped():
    """RFC 6455 caps control payloads at 125 bytes: a hostile ping
    header advertising an extended length must raise, not allocate."""
    from distributed_tpu.comm.ws import _read_ws_message

    reader = asyncio.StreamReader()
    reader.feed_data(bytes([0x89, 127]) + struct.pack(">Q", 1 << 40))
    with pytest.raises(CommClosedError, match="control frame"):
        await asyncio.wait_for(_read_ws_message(reader), timeout=5)


@gen_test()
async def test_ws_corrupt_preamble_rejected():
    """A well-formed ws frame whose payload preamble is garbage must
    surface as CommClosedError (orderly disconnect, same as the tcp
    guards), not a raw struct.error, and a bogus frame count is capped
    before the lengths unpack."""
    from distributed_tpu.comm.ws import WS

    async def read_with(payload):
        reader = asyncio.StreamReader()
        reader.feed_data(bytes([0x82, len(payload)]) + payload)
        comm = WS(reader, None, "ws://local", "ws://peer", is_client=False)
        return await asyncio.wait_for(comm.read(), timeout=5)

    with pytest.raises(CommClosedError, match="bad frame count"):
        await read_with(struct.pack("<Q", 1 << 40))
    with pytest.raises(CommClosedError, match="corrupt preamble"):
        await read_with(b"\x01\x02\x03")  # too short for the u64 count


def test_cloudpickle_fallback_drops_stale_oob_buffers():
    """Plain pickle can emit out-of-band buffers for early objects and
    THEN raise on an unpicklable one (lambda): the stale buffers must
    not reach the caller's frame list, or every out-of-band payload
    after them shifts at load time — silent corruption."""
    pytest.importorskip("cloudpickle")
    # a buffer-bearing object BEFORE the lambda (its buffer goes stale
    # when plain pickle raises) and one AFTER, same size/dtype so the
    # stale-shift manifests as wrong DATA, not a length error
    arr_a = np.arange(1000, dtype=np.float64)
    arr_b = np.arange(1000, dtype=np.float64) * -1.0
    fn = lambda: 1  # noqa: E731 - the unpicklable-by-plain-pickle leaf
    frames = dumps({"op": "x", "data": Serialize(("x", arr_a, fn, arr_b))})
    out = loads(frames)
    tag, a2, fn2, b2 = out["data"]
    assert tag == "x"
    np.testing.assert_array_equal(a2, arr_a)
    np.testing.assert_array_equal(b2, arr_b)
    assert fn2() == 1


def test_compact_frames_releases_receive_buffer():
    """A long-lived Serialized (e.g. a scheduler run_spec) must stop
    pinning the pooled receive buffer it was carved from: compaction
    copies view frames to owned bytes and drops the export."""
    from distributed_tpu.protocol.serialize import compact_frames

    buf = bytearray(8192)
    s = Serialized({"serializer": "pickle"}, [memoryview(buf)[100:200]])
    with pytest.raises(BufferError):
        buf.append(0)  # the view pins the buffer
    compact_frames(s)
    assert all(isinstance(f, bytes) for f in s.frames)
    assert len(s.frames[0]) == 100
    buf.append(0)  # no exports left: the pool could take this back
    # non-wrappers pass through untouched
    assert compact_frames(123) == 123


@gen_test()
async def test_readinto_exactly_raises_stored_exception():
    """A connection error recorded while no waiter is pending
    (``set_exception`` with an empty buffer and ``_eof`` unset) must
    raise out of ``readinto_exactly`` immediately — ``_wait_for_data``
    has no exception check, so waiting would hang forever."""
    from distributed_tpu.comm.tcp import readinto_exactly

    reader = asyncio.StreamReader()
    reader.set_exception(ConnectionResetError("peer RST mid-message"))
    with pytest.raises(ConnectionResetError):
        await asyncio.wait_for(
            readinto_exactly(reader, memoryview(bytearray(16))), timeout=5
        )


@gen_test()
async def test_ws_message_size_cap():
    """The ws backend honours comm.max-message-bytes on its fragment
    accounting too."""
    listener = await _echo_listener("ws")
    comm = await connect(listener.contact_address)
    try:
        with config.set({"comm.max-message-bytes": "1KiB"}):
            blob = np.zeros(1_000_000, dtype=np.uint8)
            with pytest.raises(CommClosedError):
                # the server aborts on its oversized read; depending on
                # timing the client sees it on its write or its read
                await comm.write({"data": Serialize(blob)})
                await comm.read()
    finally:
        await comm.close()
        listener.stop()


# ----------------------------------------------------------- metrics


def test_wire_metrics_exposition():
    from distributed_tpu.http.server import wire_metric_lines

    text = "\n".join(wire_metric_lines())
    for name in (
        "dtpu_wire_bytes_sent_total",
        "dtpu_wire_bytes_recv_total",
        "dtpu_wire_payload_copies_total",
        "dtpu_wire_pool_hits_total",
        "dtpu_wire_pool_misses_total",
        "dtpu_wire_pool_bytes",
    ):
        assert name in text
