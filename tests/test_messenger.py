"""Messenger v2 protocol tests: handshake/auth, crc, compression, lossless
replay with exactly-once dispatch, dispatch throttle, fault injection
(reference src/msg/async/ProtocolV2.cc behaviors)."""

import asyncio
import struct
import time
import zlib

import pytest

from ceph_tpu.rados.auth import AESGCM
from ceph_tpu.rados.messenger import (
    ACK_TYPE,
    BadFrame,
    Messenger,
    Policy,
    _HDR,
    message,
)


@message(900)
class MTest:
    text: str = ""
    blob: bytes = b""
    seqno: int = 0


def run(coro):
    # bounded: under injected socket failures a handshake here sometimes
    # never completes (an fd closed under its transport; seen on the
    # parent of PR 23 too), and one unbounded hang cuts the whole suite
    return asyncio.run(asyncio.wait_for(coro, 30))


def _needs_native():
    from ceph_tpu.utils import wirepath
    if wirepath.impl() is None:
        pytest.skip("no native wirepath arm on this host")
    return wirepath.impl()


# ms_secure_mode needs the (gated) AES-GCM backend, as tests/test_auth.py
requires_crypto = pytest.mark.skipif(
    AESGCM is None, reason="the `cryptography` package is not installed")

# the wires a messenger has (messenger.py "One loop, one wire"): plaintext
# TCP framed by FrameReceiver and written by CorkedWriter, on the native
# arm and on the python arm, and SecureStream's readexactly chain
_TRANSPORTS = ("tcp-native", "tcp-python",
               pytest.param("tcp-secure", marks=requires_crypto))


def _transport_conf(transport: str, **more) -> dict:
    """What both ends of a pair set to talk over `transport`."""
    if transport == "tcp-native":
        _needs_native()
        conf = {}
    elif transport == "tcp-python":
        conf = {"ms_wirepath_native": False}
    else:
        conf = {"ms_auth_secret": "s3", "ms_secure_mode": True}
    return dict(conf, **more)


async def _pair(server_conf=None, client_conf=None, server_type="osd",
                client_type="osd"):
    server = Messenger("server", server_conf or {}, entity_type=server_type)
    client = Messenger("client", client_conf or {}, entity_type=client_type)
    addr = await server.bind()
    return server, client, addr


async def _pair_on(transport: str, server_more=None, client_more=None):
    return await _pair(_transport_conf(transport, **(server_more or {})),
                       _transport_conf(transport, **(client_more or {})))


class TestHandshakeAuth:
    def test_plain_connect_and_exchange(self):
        async def go():
            server, client, addr = await _pair()
            got = asyncio.Queue()

            async def dispatch(conn, msg):
                await got.put(msg)

            server.dispatcher = dispatch
            await client.send(addr, MTest(text="hello"))
            msg = await asyncio.wait_for(got.get(), 2)
            assert msg.text == "hello"
            await client.shutdown()
            await server.shutdown()

        run(go())

    def test_peer_name_flows_through_handshake(self):
        async def go():
            server, client, addr = await _pair()
            names = []
            server.dispatcher = lambda conn, msg: names.append(conn.peer_name) or _noop()
            conn = await client.connect(addr)
            assert conn.peer_name == "server"
            await client.shutdown()
            await server.shutdown()

        async def _noop():
            return None

        run(go())

    def test_auth_mutual_success(self):
        async def go():
            conf = {"ms_auth_secret": "sesame"}
            server, client, addr = await _pair(conf, conf)
            got = asyncio.Queue()

            async def dispatch(conn, msg):
                await got.put(msg)

            server.dispatcher = dispatch
            await client.send(addr, MTest(text="authed"))
            assert (await asyncio.wait_for(got.get(), 2)).text == "authed"
            await client.shutdown()
            await server.shutdown()

        run(go())

    def test_auth_reject_bad_secret(self):
        async def go():
            server, client, addr = await _pair({"ms_auth_secret": "right"},
                                               {"ms_auth_secret": "wrong"})
            with pytest.raises((PermissionError, ConnectionError, OSError)):
                await client.send(addr, MTest(text="nope"), retries=0)
            await client.shutdown()
            await server.shutdown()

        run(go())

    def test_auth_reject_secretless_client(self):
        async def go():
            server, client, addr = await _pair({"ms_auth_secret": "right"}, {})
            with pytest.raises((PermissionError, ConnectionError, OSError)):
                await client.send(addr, MTest(text="nope"), retries=0)
            await client.shutdown()
            await server.shutdown()

        run(go())


class TestFrames:
    def test_crc_detects_corruption(self):
        async def go():
            server, client, addr = await _pair()
            got = asyncio.Queue()

            async def dispatch(conn, msg):
                await got.put(msg)

            server.dispatcher = dispatch
            conn = await client.connect(addr)
            # hand-corrupt a frame: flip a payload byte after framing
            from ceph_tpu.rados.messenger import encode_payload

            payload = encode_payload(MTest(text="x" * 100))
            crc = zlib.crc32(payload)
            frame = bytearray(_HDR.pack(len(payload), 900, 1, 0, crc, 1) + payload)
            frame[-1] ^= 0xFF
            conn.writer.write(bytes(frame))
            await conn.writer.drain()
            # server must drop the connection, not dispatch garbage
            with pytest.raises(asyncio.TimeoutError):
                await asyncio.wait_for(got.get(), 0.3)
            await client.shutdown()
            await server.shutdown()

        run(go())

    @pytest.mark.parametrize("transport", _TRANSPORTS)
    def test_compression_roundtrip(self, transport):
        async def go():
            conf = {"ms_compress_min_size": 64}
            server, client, addr = await _pair_on(transport, conf, conf)
            got = asyncio.Queue()

            async def dispatch(conn, msg):
                await got.put(msg)

            server.dispatcher = dispatch
            blob = b"A" * 100_000  # compressible
            await client.send(addr, MTest(text="big", blob=blob))
            msg = await asyncio.wait_for(got.get(), 2)
            assert msg.blob == blob
            await client.shutdown()
            await server.shutdown()

        run(go())


@pytest.mark.parametrize("transport", _TRANSPORTS)
class TestLosslessReplay:
    def test_exactly_once_under_injected_failures(self, transport):
        async def go():
            # every ~6th send attempt severs the connection; lossless policy
            # must reconnect + replay, and dedupe must prevent double dispatch
            server, client, addr = await _pair(
                _transport_conf(transport),
                _transport_conf(transport, ms_inject_socket_failures=6))
            received = []

            async def dispatch(conn, msg):
                received.append(msg.seqno)

            server.dispatcher = dispatch
            n = 60
            for i in range(n):
                await client.send(addr, MTest(seqno=i), retries=8)
            # acks drain asynchronously; wait for all dispatches
            for _ in range(100):
                if len(set(received)) == n:
                    break
                await asyncio.sleep(0.05)
            assert sorted(set(received)) == list(range(n))
            assert len(received) == len(set(received)), "duplicate dispatch"
            await client.shutdown()
            await server.shutdown()

        run(go())

    def test_bidirectional_rpc_exactly_once_under_failures(self, transport):
        async def go():
            # failures injected on BOTH sides: requests and replies each get
            # dropped mid-flight; session replay must deliver every request
            # once to the server and every reply once to the client
            server, client, addr = await _pair(
                _transport_conf(transport, ms_inject_socket_failures=8),
                _transport_conf(transport, ms_inject_socket_failures=8))
            served = []
            replies = []

            async def server_dispatch(conn, msg):
                served.append(msg.seqno)
                for attempt in range(8):
                    try:
                        await conn.send(MTest(text="reply", seqno=msg.seqno))
                        return
                    except ConnectionError:
                        await asyncio.sleep(0.02)

            async def client_dispatch(conn, msg):
                replies.append(msg.seqno)

            server.dispatcher = server_dispatch
            client.dispatcher = client_dispatch
            n = 40
            for i in range(n):
                await client.send(addr, MTest(seqno=i), retries=10)
            for _ in range(200):
                if len(replies) >= n:
                    break
                await asyncio.sleep(0.05)
            assert sorted(served) == list(range(n)), "request loss/dup"
            assert sorted(replies) == list(range(n)), "reply loss/dup"
            await client.shutdown()
            await server.shutdown()

        run(go())

    def test_unacked_queue_trims_on_ack(self, transport):
        async def go():
            server, client, addr = await _pair_on(transport)
            server.dispatcher = _swallow
            conn = await client.connect(addr, peer_type="osd")
            assert conn.policy.replay
            for i in range(10):
                await client.send(addr, MTest(seqno=i))
            for _ in range(100):
                if not conn.unacked:
                    break
                await asyncio.sleep(0.02)
            assert not conn.unacked
            await client.shutdown()
            await server.shutdown()

        run(go())

    def test_acceptor_session_loss_resets_dedupe_floor(self, transport):
        async def go():
            # the acceptor forgetting a session (restart/LRU eviction) must
            # not leave the initiator deaf to the fresh reply stream
            server, client, addr = await _pair_on(transport)
            replies = []

            async def server_dispatch(conn, msg):
                await conn.send(MTest(text="reply", seqno=msg.seqno))

            async def client_dispatch(conn, msg):
                replies.append(msg.seqno)

            server.dispatcher = server_dispatch
            client.dispatcher = client_dispatch
            for i in range(5):
                await client.send(addr, MTest(seqno=i))
            for _ in range(100):
                if len(replies) == 5:
                    break
                await asyncio.sleep(0.02)
            assert sorted(replies) == list(range(5))
            conn = client._conns[tuple(addr)]
            assert conn.in_seq >= 5
            # acceptor drops the session and severs the transport
            for sess in server._sessions.values():
                await sess.close()
            server._sessions.clear()
            for _ in range(100):
                if conn.closed:
                    break
                await asyncio.sleep(0.02)
            # reconnect happens automatically; new replies (seq restarting
            # at 1 on the server's fresh session) must still dispatch
            await client.send(addr, MTest(seqno=100), retries=8)
            for _ in range(200):
                if 100 in replies:
                    break
                await asyncio.sleep(0.02)
            assert 100 in replies, "reply stream deaf after session loss"
            await client.shutdown()
            await server.shutdown()

        run(go())

    def test_lossy_client_does_not_queue(self, transport):
        async def go():
            server, client, addr = await _pair_on(transport)
            server.dispatcher = _swallow
            conn = await client.connect(addr, peer_type="client")
            assert not conn.policy.replay
            await conn.send(MTest(seqno=1))
            assert not conn.unacked
            await client.shutdown()
            await server.shutdown()

        run(go())


async def _swallow(conn, msg):
    return None


@pytest.mark.parametrize("transport", _TRANSPORTS)
class TestDispatchThrottle:
    def test_throttle_applies_backpressure(self, transport):
        async def go():
            server, client, addr = await _pair_on(
                transport, {"ms_dispatch_throttle_bytes": 1})
            # 1-byte budget: each frame exceeds it, but an idle throttle
            # admits one oversize request at a time -> strictly serial
            inflight = []
            peak = []

            async def dispatch(conn, msg):
                inflight.append(1)
                peak.append(len(inflight))
                await asyncio.sleep(0.02)
                inflight.pop()

            server.dispatcher = dispatch
            await asyncio.gather(
                *(client.send(addr, MTest(blob=b"x" * 100)) for _ in range(5))
            )
            await asyncio.sleep(0.5)
            assert peak and max(peak) == 1
            await client.shutdown()
            await server.shutdown()

        run(go())


class TestPolicyTable:
    def test_defaults(self):
        m = Messenger("x", {})
        assert m.policy_for("client").lossy
        assert not m.policy_for("osd").lossy
        assert m.policy_for("mon").replay
        assert m.policy_for("unknown").lossy


class TestCorkedOutbox:
    """The corked wire data plane: per-connection outbox coalescing,
    sendmsg writev (CorkedWriter), piggybacked/batched acks, and the
    replay-queue interaction under injected faults."""

    @pytest.mark.parametrize("transport", _TRANSPORTS)
    def test_concurrent_senders_share_flush_windows(self, transport):
        async def go():
            server, client, addr = await _pair_on(transport)
            got = []

            async def dispatch(conn, msg):
                got.append(msg.seqno)

            server.dispatcher = dispatch
            conn = await client.connect(addr)
            # prime the connection (cork swap happens at first flush)
            await conn.send(MTest(seqno=-1))
            n = 64
            await asyncio.gather(
                *(conn.send(MTest(seqno=i)) for i in range(n)))
            for _ in range(100):
                if len(got) >= n + 1:
                    break
                await asyncio.sleep(0.02)
            assert sorted(got) == [-1] + list(range(n))
            d = client.perf.dump()
            # coalescing: the 64-send burst must NOT pay 64 flush
            # windows — concurrent senders share writelines+drain
            assert d["tx_flushes"] < d["tx_msgs"], d
            hist = d["tx_flush_frames"]
            assert hist["count"] == d["tx_flushes"]
            assert hist["sum"] >= d["tx_msgs"]  # every frame flushed once
            await client.shutdown()
            await server.shutdown()

        run(go())

    @pytest.mark.parametrize("transport", ["tcp-native", "tcp-python"])
    def test_corked_writer_engages_on_plaintext(self, transport):
        async def go():
            from ceph_tpu.rados.messenger import CorkedWriter

            server, client, addr = await _pair_on(transport)
            got = asyncio.Queue()

            async def dispatch(c, m):
                await got.put(m)

            server.dispatcher = dispatch
            conn = await client.connect(addr)
            # the cork swap happens at flush time, once the transport's
            # own buffer (handshake tail) is empty — poll a few sends
            for _ in range(10):
                await conn.send(MTest(text="x"))
                await asyncio.wait_for(got.get(), 5)
                if isinstance(conn.writer, CorkedWriter):
                    break
            assert isinstance(conn.writer, CorkedWriter), \
                "plaintext TCP connection should swap to sendmsg writev"
            # a large blob crosses the corked path intact
            blob = bytes(range(256)) * 4096  # 1 MiB
            await conn.send(MTest(text="big", blob=blob))
            m = await asyncio.wait_for(got.get(), 5)
            assert bytes(m.blob) == blob
            await client.shutdown()
            await server.shutdown()

        run(go())

    @pytest.mark.parametrize("transport", _TRANSPORTS)
    def test_acks_batch_and_piggyback(self, transport):
        async def go():
            server, client, addr = await _pair_on(transport)
            server.dispatcher = _swallow
            conn = await client.connect(addr)
            n = 40
            await asyncio.gather(
                *(conn.send(MTest(seqno=i)) for i in range(n)))
            for _ in range(100):
                if not conn.unacked:
                    break
                await asyncio.sleep(0.02)
            assert not conn.unacked, "cumulative acks must drain unacked"
            d = server.perf.dump()
            # batched acks: the server dispatched ~n frames but wrote
            # far fewer ACK frames (one cumulative ack per flush window)
            assert d["tx_acks"] + d["tx_acks_coalesced"] >= 1
            assert d["tx_acks"] < n, d
            await client.shutdown()
            await server.shutdown()

        run(go())

    @pytest.mark.parametrize("transport", _TRANSPORTS)
    def test_burst_exactly_once_in_order_under_failures(self, transport):
        """The ISSUE's outbox-ordering-under-faults gate: lossless
        sessions with ms_inject_socket_failures must deliver COALESCED
        frames (concurrent burst senders sharing flush windows) exactly
        once and in seq order across reconnect replay."""

        async def go():
            server, client, addr = await _pair_on(
                transport, None, {"ms_inject_socket_failures": 10})
            received = []

            async def dispatch(conn, msg):
                received.append(msg.seqno)

            server.dispatcher = dispatch
            n = 0
            for burst in range(12):
                await asyncio.gather(
                    *(client.send(addr, MTest(seqno=n + i), retries=8)
                      for i in range(8)))
                n += 8
            for _ in range(200):
                if len(set(received)) == n:
                    break
                await asyncio.sleep(0.05)
            assert sorted(set(received)) == list(range(n))
            assert len(received) == len(set(received)), \
                "duplicate dispatch across replay"
            # ordering: every burst's seqs arrive in order relative to
            # each other (receiver dedupe floor forbids regressions)
            conn = client._conns[tuple(addr)]
            seqs = [s for s in received]
            assert all(seqs[i] != seqs[i + 1] for i in range(len(seqs) - 1))
            assert not conn.unacked or conn.policy.replay
            await client.shutdown()
            await server.shutdown()

        run(go())

    @pytest.mark.parametrize("transport", _TRANSPORTS)
    def test_close_fails_pending_window(self, transport):
        async def go():
            server, client, addr = await _pair_on(transport)
            server.dispatcher = _swallow
            conn = await client.connect(addr, peer_type="client")
            assert not conn.policy.replay
            await conn.send(MTest(seqno=1))
            await conn.close()
            with pytest.raises((ConnectionError, OSError)):
                await conn.send(MTest(seqno=2))
            await client.shutdown()
            await server.shutdown()

        run(go())


class TestBufferListBlob:
    @pytest.mark.parametrize("transport", _TRANSPORTS)
    def test_scatter_blob_roundtrips_over_socket(self, transport):
        async def go():
            from ceph_tpu.rados.messenger import BufferList

            server, client, addr = await _pair_on(transport)
            got = asyncio.Queue()

            async def dispatch(conn, msg):
                await got.put(msg)

            server.dispatcher = dispatch
            pieces = [bytes([i]) * 4096 for i in range(8)]
            bl = BufferList([memoryview(p) for p in pieces])
            assert len(bl) == 8 * 4096
            await client.send(addr, MTest(text="bl", blob=bl))
            m = await asyncio.wait_for(got.get(), 5)
            # the receiver sees ONE contiguous blob == the concatenation
            assert bytes(m.blob) == b"".join(pieces)
            await client.shutdown()
            await server.shutdown()

        run(go())

    def test_small_bufferlist_rides_pickle_as_bytes(self):
        async def go():
            from ceph_tpu.rados.messenger import BufferList

            server, client, addr = await _pair()
            got = asyncio.Queue()

            async def dispatch(conn, msg):
                await got.put(msg)

            server.dispatcher = dispatch
            bl = BufferList([b"tiny", b"blob"])  # far below BLOB_MIN
            await client.send(addr, MTest(text="s", blob=bl))
            m = await asyncio.wait_for(got.get(), 5)
            assert m.blob == b"tinyblob"
            assert isinstance(m.blob, bytes)
            await client.shutdown()
            await server.shutdown()

        run(go())


@message(910)
class MCrcBlob:
    chunk: bytes = b""
    chunk_crc: int = 0


MCrcBlob.BLOB_ATTR = "chunk"
MCrcBlob.BLOB_CRC_ATTR = "chunk_crc"


class TestBlobCrcReuse:
    def test_precomputed_crc_skips_wire_pass_and_marks_verified(self):
        async def go():
            from ceph_tpu.utils.checksum import checksum

            server, client, addr = await _pair()
            got = asyncio.Queue()

            async def dispatch(conn, msg):
                await got.put(msg)

            server.dispatcher = dispatch
            blob = bytes(range(256)) * 256  # 64 KiB >= BLOB_MIN
            crc = checksum(blob) & 0xFFFFFFFF
            await client.send(addr, MCrcBlob(chunk=blob, chunk_crc=crc))
            m = await asyncio.wait_for(got.get(), 5)
            assert bytes(m.chunk) == blob
            assert getattr(m, "_wire_verified", False), \
                "frame-verified blob should carry the verified mark"
            assert client.perf.dump()["tx_crc_reused"] >= 1
            await client.shutdown()
            await server.shutdown()

        run(go())

    def test_wrong_precomputed_crc_is_rejected(self):
        async def go():
            server, client, addr = await _pair()
            got = asyncio.Queue()

            async def dispatch(conn, msg):
                await got.put(msg)

            server.dispatcher = dispatch
            blob = b"Z" * 65536
            await client.send(addr, MCrcBlob(chunk=blob, chunk_crc=123))
            # the receiver must DROP the corrupt-claimed frame (crc
            # mismatch kills the transport), never dispatch it
            with pytest.raises(asyncio.TimeoutError):
                await asyncio.wait_for(got.get(), 0.4)
            await client.shutdown()
            await server.shutdown()

        run(go())


# -- the receiver frames the stream (ISSUE 32): FrameReceiver alone ----------

def _wirepath_native() -> bool:
    from ceph_tpu.utils import wirepath

    return wirepath.kind() == "native"


class _FakeTransport:
    """Records what the receiver asks of its transport."""

    def __init__(self):
        self.calls = []

    def pause_reading(self):
        self.calls.append("pause")

    def resume_reading(self):
        self.calls.append("resume")


def _framer_conn(native: bool = True):
    """A minimal Connection whose reader is a FrameReceiver over a fake
    transport: the unit under test is the framer alone (parse, one-pass
    verify, land, stash), driven through the transport's own two calls,
    with no socket or serve loop underneath."""
    import collections

    from ceph_tpu.rados.messenger import (Connection, FrameReceiver,
                                          _build_wire_perf)
    from ceph_tpu.utils import wirepath
    from ceph_tpu.utils.checksum import checksum

    class _Msgr:
        perf = _build_wire_perf()

    class _Throttle:
        async def get(self, cost):
            pass

    conn = object.__new__(Connection)
    conn.messenger = _Msgr()
    conn.throttle = _Throttle()
    conn.crc_enabled = True
    conn.crc_fn = checksum
    conn.wp = wirepath.impl() if native else None
    conn.lane_group = None
    conn.in_seq = 0
    conn.unacked = collections.deque()
    conn._rx_stash = collections.deque()
    conn._rx_error = None
    conn.reader = FrameReceiver(conn, _FakeTransport(), None)
    return conn


def _feed(conn, raw: bytes, chunk=None) -> None:
    """What the transport does with a stream: get_buffer, recv_into of at
    most `chunk` bytes, buffer_updated."""
    r = conn.reader
    if chunk is None:
        return r.feed(raw)
    mv = memoryview(raw)
    while len(mv) and not r._dead:
        buf = r.get_buffer(-1)
        assert len(buf) > 0, "the transport must always get room"
        n = min(len(buf), len(mv), chunk)
        buf[:n] = mv[:n]
        r.buffer_updated(n)
        mv = mv[n:]


def _mk_frame(msg, seq: int, type_id: int = 900, compress: bool = False
              ) -> bytes:
    from ceph_tpu.rados.messenger import FLAG_COMPRESSED
    from ceph_tpu.utils.checksum import checksum

    payload = encode_payload(msg)
    flags = 0
    if compress:
        payload, flags = zlib.compress(payload, 1), FLAG_COMPRESSED
    crc = checksum(payload) & 0xFFFFFFFF
    return _HDR.pack(len(payload), type_id, 1, flags, crc, seq) + payload


def _mk_blob_frame(blob: bytes, seq: int, type_id: int = 910,
                   blob_crc=None) -> bytes:
    import pickle

    from ceph_tpu.rados.messenger import FLAG_BLOB, _BLOB_PFX
    from ceph_tpu.utils.checksum import checksum

    crc = checksum(blob) & 0xFFFFFFFF if blob_crc is None else blob_crc
    pickled = pickle.dumps({"chunk_crc": crc})
    head = _BLOB_PFX.pack(len(pickled), crc) + pickled
    return _HDR.pack(len(head) + len(blob), type_id, 1, FLAG_BLOB,
                     checksum(head) & 0xFFFFFFFF, seq) + head + blob


def _mk_ack(seq: int) -> bytes:
    from ceph_tpu.utils.checksum import checksum

    payload = struct.pack("<Q", seq)
    return _HDR.pack(8, ACK_TYPE, 1, 0, checksum(payload), 0) + payload


def _plain(stash) -> list:
    """The stash with every buffer as bytes, for comparisons."""
    return [tuple(bytes(x) if isinstance(x, (bytearray, memoryview)) else x
                  for x in f) for f in stash]


from ceph_tpu.rados.messenger import encode_payload  # noqa: E402


@message(912)
class MViewBlob:
    chunk: bytes = b""
    chunk_crc: int = 0


MViewBlob.BLOB_ATTR = "chunk"
MViewBlob.BLOB_VIEW_OK = True


def _mixed_stream():
    """Small frames, acks, blobs of both destination kinds, a payload
    larger than the head and a compressed one."""
    import os

    rnd = os.urandom
    parts = [
        _mk_frame(MTest(text="a", seqno=1), 1),
        _mk_ack(7),
        _mk_blob_frame(rnd(75 * 1024), 2),             # bytearray dest
        _mk_frame(MTest(text="b" * 300, seqno=2), 3),
        _mk_blob_frame(rnd(300 * 1024 + 13), 4, 912),  # np.empty dest
        _mk_ack(9),
        _mk_frame(MTest(text="big", blob=rnd(40000)), 5),  # > the head
        _mk_frame(MTest(text="z" * 5000), 6, compress=True),
        _mk_blob_frame(rnd(16 * 1024), 7, 912),
        _mk_frame(MTest(text="tail"), 8),
    ]
    return b"".join(parts)


@pytest.mark.skipif(not _wirepath_native(), reason="native wirepath absent")
class TestFramerBursts:
    """TestNativeRxDrain's four cases (ISSUE 12), on the framer."""

    def test_burst_stashes_every_complete_frame(self):
        frames = [MTest(text=f"t{i}", seqno=i) for i in range(5)]
        raw = b"".join(_mk_frame(m, i + 1) for i, m in enumerate(frames))
        # a trailing HALF frame must stay in the head, not parse
        half = _mk_frame(MTest(text="partial"), 9)[:-7]
        conn = _framer_conn()
        _feed(conn, raw + half)
        assert len(conn._rx_stash) == 5
        assert conn._rx_error is None
        for i, (type_id, version, seq, payload, cost, blob, fixed,
                verified) in enumerate(conn._rx_stash):
            assert type_id == 900 and seq == i + 1
            from ceph_tpu.rados.messenger import decode_message

            m = decode_message(type_id, version, payload, blob, fixed)
            assert m.text == f"t{i}" and m.seqno == i
        assert conn.reader.unframed() == half
        perf = conn.messenger.perf.dump()
        assert perf["rx_framed"] == 5
        assert perf["native_rx_calls"] == 1  # one verify for the burst

    def test_corrupt_mid_burst_fails_after_the_good_frames(self):
        """A per-frame reader dispatches every frame before the corrupt
        one, then kills the session — the framer keeps exactly that
        order: predecessors stash, the BadFrame parks, nothing after
        the corrupt frame is looked at."""
        good0 = _mk_frame(MTest(text="ok0"), 1)
        bad = bytearray(_mk_frame(MTest(text="dead"), 2))
        bad[-1] ^= 0xFF  # corrupt the payload tail: crc must catch it
        good1 = _mk_frame(MTest(text="ok1"), 3)
        conn = _framer_conn()
        _feed(conn, good0 + bytes(bad) + good1)
        assert len(conn._rx_stash) == 1  # only the pre-corruption frame
        assert isinstance(conn._rx_error, BadFrame)
        assert conn.reader._transport.calls == ["pause"]
        # nothing more is framed on a stream that lost its framing
        _feed(conn, good1)
        assert len(conn._rx_stash) == 1

        async def go():
            first = await conn.read_frame()
            assert first[2] == 1
            with pytest.raises(BadFrame):
                await conn.read_frame()

        run(go())

    def test_blob_frame_lands_and_verifies(self):
        from ceph_tpu.rados.messenger import decode_message

        blob = bytes(range(256)) * 300  # 75 KiB
        conn = _framer_conn()
        _feed(conn, _mk_blob_frame(blob, 1))
        assert conn._rx_error is None
        assert len(conn._rx_stash) == 1
        (type_id, version, seq, payload, cost, got_blob, fixed,
         verified) = conn._rx_stash[0]
        assert verified  # the blob crc section was checked
        out = decode_message(type_id, version, payload, got_blob, fixed)
        assert bytes(out.chunk) == blob
        perf = conn.messenger.perf.dump()
        assert perf["native_rx_calls"] == 2  # the front, then the body
        # what came with the head was copied, the rest landed in place
        assert 0 < perf["rx_copied_bytes"] <= conn.reader._HEAD

    def test_corrupt_blob_is_never_handed_over(self):
        """The body lands (in a buffer nobody sees) and fails its crc:
        the error parks, nothing reaches the stash."""
        from ceph_tpu.utils.checksum import checksum

        blob = b"Q" * 70000
        wrong = (checksum(blob) ^ 1) & 0xFFFFFFFF
        conn = _framer_conn()
        _feed(conn, _mk_blob_frame(blob, 1, blob_crc=wrong))
        assert isinstance(conn._rx_error, BadFrame)
        assert not conn._rx_stash

        async def go():
            with pytest.raises(BadFrame):
                await conn.read_frame()

        run(go())


class TestFramer:
    @pytest.mark.parametrize("chunk", [1, 29, 4096, 256 * 1024, None])
    def test_any_chunking_gives_the_same_frames(self, chunk):
        """The same byte stream through get_buffer/buffer_updated in any
        chunking: same frames, order and bytes; acks applied, not
        stashed; at most one head buffer copied a frame with a body."""
        raw = _mixed_stream()
        want = _framer_conn()
        _feed(want, raw)
        conn = _framer_conn()
        conn.unacked.extend((s, b"") for s in range(1, 12))
        _feed(conn, raw, chunk)
        assert conn._rx_error is None
        assert _plain(conn._rx_stash) == _plain(want._rx_stash)
        assert [f[2] for f in conn._rx_stash] == list(range(1, 9))
        assert [f[7] for f in conn._rx_stash] == [
            False, True, False, True, False, False, True, False]
        # a blob is what its class's consumers expect: a bytearray, or
        # (BLOB_VIEW_OK) a view of an uninitialised array
        assert [type(f[5]) for f in conn._rx_stash if f[5] is not None] \
            == [bytearray, memoryview, memoryview]
        # the compressed frame arrived decompressed, the large one whole
        from ceph_tpu.rados.messenger import decode_message

        msgs = [decode_message(f[0], f[1], f[3], f[5], f[6])
                for f in conn._rx_stash]
        assert msgs[5].text == "z" * 5000 and len(msgs[4].blob) == 40000
        assert [s for s, _ in conn.unacked] == [10, 11]  # acks 7 and 9
        r = conn.reader
        assert r.unframed() == b"" and r._body is None
        perf = conn.messenger.perf.dump()
        assert perf["rx_bytes"] == len(raw)
        assert perf["rx_framed"] == 10 and perf["rx_inline_acks"] == 2
        # four frames had a body (three blobs, one large payload)
        assert perf["rx_copied_bytes"] <= 4 * r._HEAD
        if chunk == 1:
            assert perf["rx_copied_bytes"] == 0  # every body landed

    def test_eof_inside_a_body_drops_the_partial_frame(self):
        conn = _framer_conn()
        frame = _mk_blob_frame(b"E" * 200_000, 2)
        _feed(conn, _mk_frame(MTest(text="whole"), 1) + frame[:90_000])
        r = conn.reader
        assert r._body is not None and len(conn._rx_stash) == 1
        assert r.unframed() == frame[:90_000]
        r.eof_received()

        async def go():
            assert (await conn.read_frame())[2] == 1
            with pytest.raises(asyncio.IncompleteReadError):
                await conn.read_frame()

        run(go())
        assert not conn._rx_stash  # the half blob went with its transport

    def test_pause_above_the_limit_and_resume_below_half(self):
        conn = _framer_conn()
        r = conn.reader
        r._LIMIT = 8192
        calls = r._transport.calls
        one = _mk_frame(MTest(text="x" * 1000), 1)
        # one frame alone never pauses its own connection
        _feed(conn, _mk_frame(MTest(text="y" * 12000), 1))
        assert calls == [] and r._held > r._LIMIT
        conn._rx_stash.clear()
        r._held = 0
        n = 0
        while not calls:
            n += 1
            _feed(conn, one)
            assert n < 20
        assert calls == ["pause"] and r._held > r._LIMIT

        async def pop_until_resumed():
            popped = 0
            while calls == ["pause"]:
                # nothing resumes it but a pop that brings it below half
                assert r._held >= r._LIMIT // 2
                await conn.read_frame()
                popped += 1
            return popped

        popped = run(pop_until_resumed())
        assert calls == ["pause", "resume"]
        assert 1 < popped < n and r._held < r._LIMIT // 2

    def test_body_in_flight_counts_and_cannot_deadlock(self):
        conn = _framer_conn()
        r = conn.reader
        r._LIMIT = 8192
        big = _mk_blob_frame(b"B" * 100_000, 2)
        _feed(conn, _mk_frame(MTest(text="first"), 1) + big[:50_000])
        assert r._transport.calls == ["pause"]  # one stashed + the body

        async def go():
            await conn.read_frame()

        run(go())
        # the body alone resumes: nobody could pop what has not landed
        assert r._transport.calls == ["pause", "resume"]
        _feed(conn, big[50_000:])
        assert len(conn._rx_stash) == 1 and conn._rx_error is None

    @pytest.mark.parametrize("crc", [True, False], ids=["crc", "nocrc"])
    @pytest.mark.parametrize("left", [1000, 40_000, 99_000])
    def test_leftover_with_a_blob_front_then_the_socket_keeps_order(
            self, crc, left):
        """What the StreamReader held at the swap (after a reconnect: the
        front of a replayed blob frame, more than one head of it) is fed
        whole before any byte the socket still holds: the transport's
        read is the only other way in, so the body's bytes stay in
        stream order, crc checked or not."""
        data = bytes(range(256)) * 400  # 100 KiB
        frame = _mk_blob_frame(data, 1) + _mk_frame(MTest(text="next"), 2)
        conn = _framer_conn()
        conn.crc_enabled = crc
        r = conn.reader
        r.feed(frame[:left])  # 40 000 and 99 000 are over one head
        assert r._body is not None and not conn._rx_stash
        assert r.unframed() == frame[:left]
        _feed(conn, frame[left:], 64 << 10)  # the transport's reads
        assert conn._rx_error is None and len(conn._rx_stash) == 2
        blob_frame, nxt = conn._rx_stash
        assert bytes(blob_frame[5]) == data and blob_frame[7] is crc
        assert nxt[2] == 2
        assert conn.messenger.perf.dump()["rx_bytes"] == len(frame)

    def test_swap_with_a_backlog_on_a_real_socket_keeps_the_stream(self):
        """enable_fast_read over a live TCP transport whose StreamReader
        already holds more than a head of a 1 MiB blob frame while the
        kernel holds the rest (a reconnect's replay right behind the
        handshake): read_frame gives the same bytes in the same order."""
        import os

        data = os.urandom(1 << 20)
        raw = _mk_blob_frame(data, 1) + _mk_frame(MTest(text="after"), 2)
        out = []

        async def go():
            served = asyncio.Event()

            async def on_conn(reader, writer):
                while len(reader._buffer) <= head:
                    await asyncio.sleep(0.01)
                conn = _framer_conn()
                conn.reader, conn.writer = reader, writer
                conn.enable_fast_read()
                assert conn.reader is not reader
                for _ in range(2):
                    out.append(await conn.read_frame())
                served.set()

            server = await asyncio.start_server(on_conn, "127.0.0.1", 0)
            port = server.sockets[0].getsockname()[1]
            _, w = await asyncio.open_connection("127.0.0.1", port)
            w.write(raw)
            await w.drain()
            await asyncio.wait_for(served.wait(), 20)
            w.close()
            server.close()
            await server.wait_closed()

        from ceph_tpu.rados.messenger import FrameReceiver

        head = FrameReceiver._HEAD
        run(go())
        assert [f[2] for f in out] == [1, 2]
        assert bytes(out[0][5]) == data and out[0][7] is True

    def test_received_ack_trims_unacked_without_waking_the_serve_task(self):
        conn = _framer_conn()
        conn.unacked.extend([(1, b"a"), (2, b"b"), (3, b"c")])

        async def go():
            task = asyncio.ensure_future(conn.read_frame())
            await asyncio.sleep(0)
            waiter = conn.reader._waiter
            assert waiter is not None
            _feed(conn, _mk_ack(2))
            await asyncio.sleep(0)
            assert list(conn.unacked) == [(3, b"c")]
            assert not conn._rx_stash
            assert not waiter.done() and not task.done()
            _feed(conn, _mk_frame(MTest(text="data"), 4))
            frame = await asyncio.wait_for(task, 2)
            assert frame[2] == 4

        run(go())
        perf = conn.messenger.perf.dump()
        assert perf["rx_inline_acks"] == 1 and perf["rx_framed"] == 2

    @pytest.mark.skipif(not _wirepath_native(),
                        reason="native wirepath absent")
    @pytest.mark.parametrize("chunk", [29, None])
    def test_both_crc_arms_give_identical_tuples(self, chunk, monkeypatch):
        """The arm is what utils.wirepath resolves (CEPH_TPU_WIREPATH=0:
        python), nothing is set on the framer: same tuples either way,
        and the same refusal of a corrupt stream."""
        from ceph_tpu.utils import wirepath

        raw = _mixed_stream()
        bad = bytearray(raw)
        bad[len(raw) // 2] ^= 0x01  # inside the 300 KiB blob
        native = _framer_conn()
        assert native.wp is not None
        monkeypatch.setenv("CEPH_TPU_WIREPATH", "0")
        wirepath._reset_for_tests()
        try:
            python = _framer_conn()
            assert python.wp is None
            python_bad = _framer_conn()
        finally:
            monkeypatch.delenv("CEPH_TPU_WIREPATH")
            wirepath._reset_for_tests()
        native_bad = _framer_conn()
        _feed(native, raw, chunk)
        _feed(python, raw, chunk)
        assert _plain(native._rx_stash) == _plain(python._rx_stash)
        assert len(python._rx_stash) == 8
        assert python.messenger.perf.dump()["native_rx_calls"] == 0
        _feed(native_bad, bytes(bad), chunk)
        _feed(python_bad, bytes(bad), chunk)
        assert _plain(native_bad._rx_stash) == _plain(python_bad._rx_stash)
        assert len(python_bad._rx_stash) == 3
        assert isinstance(native_bad._rx_error, BadFrame)
        assert isinstance(python_bad._rx_error, BadFrame)


@pytest.mark.parametrize("name, cells, moves", [
    ("rx_copy_share.put",
     ["k8m3.write4m", "k4m2.write4m", "k10m4c.write4m",
      "k8m3.mixed-small", "k8m3.rbd-randwrite4k",
      "k8m3.write4m-bluestore", "k8m4clay.write4m"], "put_MBps"),
    ("rx_copy_share.get", ["k8m3.randread4m", "k8m3.randread4m-cold"],
     "get_MBps"),
])
def test_the_engagement_metric_reads_copied_over_received(name, cells, moves):
    """The benchmark's data files (ISSUE 32): bytes copied after the
    kernel delivered them over bytes received, in percent, from a
    window's counter delta; a program without the counter (the parent)
    reports nothing."""
    import json
    import os

    from benchmarks import layers

    def read(counters):
        return layers.read(name, {"counters": counters})

    assert read({"wire.rx_bytes": 5_000_000}) is None
    assert read({"wire.rx_bytes": 5_000_000,
                 "wire.rx_copied_bytes": 0}) == 0.0
    assert read({"wire.rx_bytes": 5_000_000,
                 "wire.rx_copied_bytes": 200_000}) == 4.0
    assert read({"wire.rx_bytes": 0, "wire.rx_copied_bytes": 0}) is None
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        entry = [e for e in json.load(f)["per_layer"] if e["name"] == name]
    assert len(entry) == 1 and entry[0]["workloads"] == cells
    assert entry[0]["layer"] == "messenger" and entry[0]["moves"] == moves
    assert entry[0]["source"] == "program_counter"


class TestWirepathParity:
    """Satellite (ISSUE 12): the injected-failure replay loops must
    behave identically — same exactly-once dispatch, byte-identical
    payloads — with the wirepath forced native and forced python."""

    N = 48

    def _arm(self, native: bool):
        async def go():
            conf = {"ms_wirepath_native": native,
                    "ms_inject_socket_failures": 9,
                    "ms_inject_dup_frames": 5}
            server, client, addr = await _pair(dict(conf), dict(conf))
            got = []
            async def dispatch(conn, msg):
                got.append((msg.seqno, bytes(msg.blob)))
            server.dispatcher = dispatch
            for i in range(self.N):
                blob = bytes([(i * 7 + j) & 0xFF for j in range(512)]) \
                    * (1 + i % 3)
                await client.send(addr, MTest(seqno=i, blob=blob),
                                  retries=10)
            for _ in range(200):
                if len({s for s, _ in got}) == self.N:
                    break
                await asyncio.sleep(0.05)
            tx_native = client.perf.dump()["native_tx_calls"]
            await client.shutdown()
            await server.shutdown()
            return got, tx_native

        return run(go())

    def test_native_and_python_arms_dispatch_identically(self):
        native_got, native_tx = self._arm(True)
        python_got, python_tx = self._arm(False)
        want = [(i, bytes([(i * 7 + j) & 0xFF for j in range(512)])
                 * (1 + i % 3)) for i in range(self.N)]
        # exactly-once, in order, byte-identical — on BOTH arms
        assert native_got == want
        assert python_got == want
        assert python_tx == 0  # the forced-python arm stayed python
        if _wirepath_native():
            assert native_tx > 0  # the native arm actually ran native

    def test_env_knob_forces_python_arm(self, monkeypatch):
        """CEPH_TPU_WIREPATH=0 (the CI parity knob) must force the
        python arm process-wide, whatever the config says."""
        from ceph_tpu.utils import wirepath

        monkeypatch.setenv("CEPH_TPU_WIREPATH", "0")
        wirepath._reset_for_tests()
        try:
            assert wirepath.kind() == "python"
            assert wirepath.impl() is None
            m = Messenger("knob", {"ms_wirepath_native": True})
            assert m.wirepath is None
            assert m.perf.dump()["wirepath_kind"] == 0
        finally:
            monkeypatch.delenv("CEPH_TPU_WIREPATH")
            wirepath._reset_for_tests()

    def test_config_knob_forces_python_arm(self):
        m = Messenger("off", {"ms_wirepath_native": False})
        assert m.wirepath is None
        assert m.perf.dump()["wirepath_kind"] == 0


class TestLoopCharges:
    """What a message costs the loop (ISSUE 40): where the messenger knows
    a frame's type it charges the loop meter, which keeps the step's time
    as `loop.msg_<Type>` and `loop.for_<family>`."""

    def test_every_registered_message_class_has_a_family(self):
        """A class without a family fails HERE; in a run it is booked as
        `control`.  (Classes tests register themselves are not the
        program's.)"""
        import importlib
        import pkgutil

        import ceph_tpu
        from ceph_tpu.common.tracing import FAMILIES
        from ceph_tpu.rados.messenger import _MSG_TYPES, MSG_FAMILY

        for mod in pkgutil.walk_packages(ceph_tpu.__path__, "ceph_tpu."):
            if mod.name.endswith("__main__"):
                continue
            try:
                importlib.import_module(mod.name)
            except Exception:
                pass  # an optional dependency; its messages cannot run
        ours = {cls.__name__ for cls in _MSG_TYPES.values()
                if cls.__module__.startswith("ceph_tpu.")}
        assert len(ours) >= 79
        assert sorted(ours - set(MSG_FAMILY)) == []
        assert sorted(set(MSG_FAMILY) - ours) == []  # no class that is gone
        assert set(MSG_FAMILY.values()) == set(FAMILIES) - {"ack", "none"}
        for name, family in (("MECSubWriteReply", "op"), ("MPing", "liveness"),
                             ("MOSDPGHitSet", "tier"), ("MPushShard", "recovery"),
                             ("MScrubShard", "recovery"), ("MMapReply", "control")):
            assert MSG_FAMILY[name] == family

    def test_pings_sub_writes_and_acks_are_booked_to_their_families(self):
        from ceph_tpu.common import tracing
        from ceph_tpu.rados.types import (MECSubWrite, MECSubWriteReply,
                                          MOSDPing)

        async def go():
            meter = tracing.install_loop_meter()
            meter.sample_every = 1
            server, client, addr = await _pair()
            replies = asyncio.Queue()

            async def serve(conn, msg):
                if isinstance(msg, MECSubWrite):
                    assert bytes(msg.chunk[:4]) == b"\x07" * 4
                    await conn.send(MECSubWriteReply(tid=msg.tid, ok=True))

            async def collect(conn, msg):
                await replies.put(msg)
            server.dispatcher, client.dispatcher = serve, collect
            conn = await client.connect(addr)
            await conn.send(MOSDPing(from_osd=1))  # the cork swap's flush
            await asyncio.sleep(0.05)
            before = tracing.LOOP_PERF.dump()
            wire0 = client.perf.dump()
            chunk = memoryview(bytes([7]) * (512 << 10))
            for i in range(4):
                # a ping and a blob-carrying sub-write in ONE flush window
                await asyncio.gather(
                    conn.send(MOSDPing(from_osd=1, stamp=float(i))),
                    conn.send(MECSubWrite(oid="o", shard=i, chunk=chunk,
                                          tid=f"t{i}")))
                assert (await asyncio.wait_for(replies.get(), 5)).tid \
                    == f"t{i}"
            for _ in range(100):  # the reply's ack rides a window alone
                if not conn.unacked and not any(
                        c.unacked for c in server._conns.values()
                        if hasattr(c, "unacked")):
                    break
                await asyncio.sleep(0.02)
            after = tracing.LOOP_PERF.dump()
            wire1 = client.perf.dump()
            srv = server.perf.dump()
            meter.remove()
            await client.shutdown()
            await server.shutdown()
            return before, after, wire0, wire1, srv
        before, after, wire0, wire1, srv = run(go())

        def moved(key, part="sum"):
            return after[key][part] - before.get(key, {}).get(part, 0)
        for key in ("for_liveness", "for_op", "for_ack", "msg_MOSDPing",
                    "msg_MECSubWrite", "msg_MECSubWriteReply", "msg_ack"):
            assert moved(key) > 0 and moved(key, "avgcount") > 0, key
        # a type is in one family, a family is its types
        assert moved("for_liveness") == pytest.approx(moved("msg_MOSDPing"))
        assert moved("for_op") == pytest.approx(
            moved("msg_MECSubWrite") + moved("msg_MECSubWriteReply"))
        assert moved("for_ack") == pytest.approx(moved("msg_ack"))
        assert moved("for_tier") == moved("for_recovery") == 0
        # 2 MiB of sub-writes cost the loop more than four pings
        assert moved("msg_MECSubWrite") > moved("msg_MOSDPing")
        # both cuts of the same seconds
        families = sum(moved(k) for k in after if k.startswith("for_"))
        layers = sum(moved(k) for k in after if k.startswith("self_"))
        assert families == pytest.approx(moved("busy"), rel=1e-6)
        assert layers == pytest.approx(moved("busy"), rel=1e-6)
        # the windows that held a ping beside a sub-write were split by a
        # rule (bytes) and say so; per-type wire counters read as before
        assert wire1["tx_flush_mixed"] - wire0["tx_flush_mixed"] >= 1
        assert wire1["tx_MOSDPing"] - wire0["tx_MOSDPing"] == 4
        assert wire1["tx_MECSubWrite"] == 4
        assert wire1["tx_bytes_MECSubWrite"] > 4 * (512 << 10)
        assert wire1["rx_MECSubWriteReply"] == 4
        assert srv["rx_MECSubWrite"] == 4 and srv["rx_MOSDPing"] == 5
        assert srv["rx_bytes_MECSubWrite"] == wire1["tx_bytes_MECSubWrite"]
        assert srv["tx_flush_ack"] >= 1


async def _until(cond, seconds=3.0, step=0.01):
    """Poll `cond` for at most `seconds`; says whether it came true."""
    for _ in range(int(seconds / step)):
        if cond():
            return True
        await asyncio.sleep(step)
    return cond()


def _sweep_timers(loop, messenger):
    """The loop's live timer handles that are `messenger`'s ack sweep."""
    from ceph_tpu.rados.messenger import _AckSweep

    return [h for h in loop._scheduled if not h.cancelled()
            and getattr(h._callback, "__func__", None) is _AckSweep._tick
            and h._callback.__self__.messenger is messenger]


class TestAcksWaitForCompany:
    """An owed ack wakes nobody: it rides the connection's next data
    window, leaves alone at the bound on the bytes owed, or is written by
    the messenger's one sweep at the deadline (module docstring "Acks WAIT
    FOR COMPANY")."""

    @pytest.mark.parametrize("server_conf,inline", [
        ({}, True), ({"ms_corked_writev": False}, False)])
    def test_idle_reverse_direction_acks_within_the_deadline(
            self, server_conf, inline):
        from ceph_tpu.rados.messenger import CorkedWriter

        async def go():
            server, client, addr = await _pair(server_conf=server_conf)
            got = asyncio.Queue()

            async def dispatch(conn, msg):
                await got.put(conn)
            server.dispatcher = dispatch
            conn = await client.connect(addr)
            t0 = time.monotonic()
            await conn.send(MTest(seqno=1))
            sconn = await asyncio.wait_for(got.get(), 5)
            assert await _until(lambda: not conn.unacked)
            waited = time.monotonic() - t0
            # a timer cannot fire early: no ack before the sweep's first
            # tick, three quarters of the deadline after the debt began
            assert waited >= 0.75 * server.ACK_DELAY_S - 0.02, waited
            d = server.perf.dump()
            assert d["tx_acks"] == d["tx_acks_swept"] == 1, d
            assert d["tx_acks_rode"] == d["tx_acks_bound"] == 0, d
            assert d["tx_flush_ack"] == d["tx_flushes"] == 1, d
            assert d["ack_frames_covered"] == 1
            assert d["tx_io"]["avgcount"] == 1 and d["tx_bytes"] == 29
            # plaintext TCP: the sweep's own step wrote the window; a
            # writer whose write is not the socket's hands it to a task
            assert isinstance(sconn.writer, CorkedWriter) is inline
            assert (sconn._flusher is None) is inline
            await client.shutdown()
            await server.shutdown()

        run(go())

    def test_frames_inside_one_deadline_share_one_ack(self):
        async def go():
            server, client, addr = await _pair()
            server.ACK_DELAY_S = 1.0  # room for a slow host's seven sends
            got = asyncio.Queue()

            async def dispatch(conn, msg):
                await got.put(msg)
            server.dispatcher = dispatch
            conn = await client.connect(addr)
            n = 7
            for i in range(n):  # one rx batch, one debt entry each
                await conn.send(MTest(seqno=i))
                await asyncio.wait_for(got.get(), 5)
            assert len(conn.unacked) == n
            assert await _until(lambda: not conn.unacked, 4.0)
            d = server.perf.dump()
            assert d["tx_acks"] == d["tx_acks_swept"] == 1, d
            assert d["ack_frames_covered"] / d["tx_acks"] == n
            assert d["tx_acks_coalesced"] == n - 1
            await client.shutdown()
            await server.shutdown()

        run(go())

    def test_ack_rides_a_data_window_and_the_sweep_writes_nothing(self):
        async def go():
            server, client, addr = await _pair()
            server.ACK_DELAY_S = 0.4
            got = asyncio.Queue()

            async def dispatch(conn, msg):
                await got.put(conn)
            server.dispatcher = dispatch
            client.dispatcher = _swallow
            conn = await client.connect(addr)
            await conn.send(MTest(seqno=1))
            sconn = await asyncio.wait_for(got.get(), 5)
            assert conn.unacked
            await sconn.send(MTest(text="data"))  # before the deadline
            assert await _until(lambda: not conn.unacked)
            d = server.perf.dump()
            assert d["tx_acks"] == d["tx_acks_rode"] == 1, d
            assert d["tx_flush_mixed"] == d["tx_flush_data"] == 1, d
            await asyncio.sleep(server.ACK_DELAY_S + 0.1)  # the tick came
            sweep = server._ack_sweep
            d = server.perf.dump()
            assert sweep.ticks == 1 and sweep.timer is None
            assert not sweep.owing
            assert d["tx_acks"] == 1 and d["tx_acks_swept"] == 0, d
            assert d["tx_flush_ack"] == 0 and d["tx_flushes"] == 1, d
            await client.shutdown()
            await server.shutdown()

        run(go())

    def test_bytes_owed_at_the_bound_are_acked_without_waiting(self):
        async def go():
            server, client, addr = await _pair()
            server.ACK_DELAY_S = 30.0  # only the bound can send this ack
            got = asyncio.Queue()

            async def dispatch(conn, msg):
                await got.put(msg)
            server.dispatcher = dispatch
            conn = await client.connect(addr)
            blob = bytes(3 << 19)  # 1.5 MiB: the third passes 4 MiB
            for i in range(2):
                await conn.send(MTest(seqno=i, blob=blob))
                await asyncio.wait_for(got.get(), 5)
            await asyncio.sleep(0.05)
            assert len(conn.unacked) == 2
            assert server.perf.dump()["tx_acks"] == 0
            await conn.send(MTest(seqno=2, blob=blob))
            assert await _until(lambda: not conn.unacked)
            d = server.perf.dump()
            assert d["tx_acks"] == d["tx_acks_bound"] == 1, d
            assert d["ack_frames_covered"] == 3
            assert d["tx_flush_ack"] == 1
            sweep = server._ack_sweep
            assert sweep.ticks == 0
            await client.shutdown()
            await server.shutdown()

        run(go())

    def test_one_timer_a_messenger_however_many_connections_owe(self):
        async def go():
            server = Messenger("server", {}, entity_type="osd")
            addr = await server.bind()
            server.dispatcher = _swallow
            clients = [Messenger(f"client{i}", {}, entity_type="osd")
                       for i in range(5)]
            conns = [await c.connect(addr) for c in clients]
            loop = asyncio.get_running_loop()
            for conn in conns:
                await conn.send(MTest(seqno=1))
            assert await _until(lambda: server.perf.dump()["rx_msgs"] == 5)
            sweep = server._ack_sweep
            assert len(sweep.owing) == 5
            assert len(_sweep_timers(loop, server)) == 1
            assert await _until(
                lambda: not any(conn.unacked for conn in conns))
            d = server.perf.dump()
            assert d["tx_acks"] == d["tx_acks_swept"] == 5, d
            # debts a few ms apart share a tick: far fewer steps than acks
            assert 1 <= sweep.ticks <= 2, sweep.ticks
            assert await _until(lambda: sweep.timer is None)
            assert not _sweep_timers(loop, server) and not sweep.owing
            for c in clients:
                await c.shutdown()
            await server.shutdown()

        run(go())

    def test_transport_dropped_under_a_debt_replays_exactly_once(self):
        async def go():
            server, client, addr = await _pair()
            server.ACK_DELAY_S = 0.4
            received = []

            async def dispatch(conn, msg):
                received.append(msg.seqno)
            server.dispatcher = dispatch
            conn = await client.connect(addr)
            for i in range(3):
                await conn.send(MTest(seqno=i))
            assert await _until(lambda: len(received) == 3)
            sconn, = server._sessions.values()
            assert sconn._ack_pending == 3 and len(conn.unacked) == 3
            await conn.close()  # the debt goes with the server's transport
            # the initiator redials and replays all three; the dedupe floor
            # keeps them from the dispatcher and owes their ack again
            assert await _until(lambda: not conn.closed)
            assert await _until(lambda: not conn.unacked)
            assert received == [0, 1, 2]
            for i in range(3, 5):
                await client.send(addr, MTest(seqno=i))
            assert await _until(lambda: len(received) == 5
                                and not conn.unacked)
            assert received == [0, 1, 2, 3, 4]
            d = server.perf.dump()
            assert d["tx_acks"] == d["tx_acks_swept"] >= 1, d
            # a re-ack covers nothing new: five frames, each counted once
            assert d["ack_frames_covered"] == 5
            await client.shutdown()
            await server.shutdown()

        run(go())

    def test_injected_drops_under_standing_debts_deliver_exactly_once(self):
        async def go():
            server, client, addr = await _pair(
                client_conf={"ms_inject_socket_failures": 6})
            received = []

            async def dispatch(conn, msg):
                received.append(msg.seqno)
            server.dispatcher = dispatch
            n = 60
            for i in range(n):  # nearly every drop finds an ack owed
                await client.send(addr, MTest(seqno=i), retries=8)
            assert await _until(lambda: len(set(received)) == n, 10.0, 0.05)
            assert received == list(range(n)), "loss, duplicate or reorder"
            conn = client._conns[tuple(addr)]
            assert await _until(lambda: not conn.unacked, 10.0, 0.05)
            d = server.perf.dump()
            assert d["tx_acks"] < n, d
            assert d["tx_acks"] == d["tx_acks_rode"] + d["tx_acks_bound"] \
                + d["tx_acks_swept"]
            await client.shutdown()
            await server.shutdown()

        run(go())

    def test_shutdown_leaves_no_pending_timer(self):
        async def go():
            server, client, addr = await _pair()
            server.ACK_DELAY_S = 30.0
            server.dispatcher = _swallow
            conn = await client.connect(addr)
            await conn.send(MTest(seqno=1))
            loop = asyncio.get_running_loop()
            assert await _until(lambda: _sweep_timers(loop, server))
            sweep = server._ack_sweep
            sconn, = server._sessions.values()
            await server.shutdown()
            assert sweep.timer is None and not sweep.owing
            assert not _sweep_timers(loop, server)
            sconn.closed = False  # a straggler's debt re-arms nothing
            sconn.queue_ack(2)
            assert sweep.timer is None
            assert not _sweep_timers(loop, server)
            await client.shutdown()

        run(go())


# -- a window's bytes leave on the sender thread (PR 49) ---------------------

_ARMS = ("native", "python")


def _arm_conf(arm: str) -> dict:
    return {} if arm == "native" else {"ms_wirepath_native": False}


async def _corked(conn, server_got=None):
    """Send small messages until `conn` writes through a CorkedWriter (the
    swap happens at a flush that finds the transport's own buffer empty)
    and its receiver is in place, so that the writer hears of a loss."""
    from ceph_tpu.rados.messenger import CorkedWriter
    for _ in range(20):
        await conn.send(MTest(text="prime", seqno=-1))
        await asyncio.sleep(0.01)
        w = conn.writer
        if isinstance(w, CorkedWriter) \
                and (conn.wp is None or w._off is not None):
            return w
    raise AssertionError("the connection never corked")


class _Gate:
    """A dispatcher that parks its first message until released, so the
    receiver's backpressure pauses the socket under a sender."""

    def __init__(self):
        self.got = []
        self.parked = asyncio.Event()
        self.release = asyncio.Event()

    async def __call__(self, conn, msg):
        if msg.seqno >= 0 and not self.release.is_set():
            self.parked.set()
            await self.release.wait()
        if msg.seqno >= 0:
            self.got.append((msg.seqno, bytes(msg.blob)))


def _blob(i: int, n: int) -> bytes:
    return bytes([i % 251]) * n


class TestOffloopSend:
    """CorkedWriter "Off the loop": which windows leave on the process's
    sender thread, that order and bytes are what they were, and that the
    thread never outlives its fd.  Each case runs on the native arm and,
    where it applies, on the python arm, where nothing changed."""

    @pytest.mark.parametrize("arm", _ARMS)
    def test_big_and_small_windows_interleaved_keep_seq_order(self, arm):
        async def go():
            from ceph_tpu.rados.messenger import CorkedWriter
            if arm == "native":
                _needs_native()
            server, client, addr = await _pair(client_conf=_arm_conf(arm))
            got = []

            async def dispatch(conn, msg):
                if msg.seqno >= 0:
                    got.append((msg.seqno, bytes(msg.blob)))
            server.dispatcher = dispatch
            conn = await client.connect(addr)
            await _corked(conn)
            sizes = [256 << 10, 10, 1 << 20, 0, 64, 300 << 10, 7, 2 << 20,
                     33, 128 << 10, 5, 5]
            msgs = [MTest(seqno=i, blob=_blob(i, n))
                    for i, n in enumerate(sizes)]
            # three waves of concurrent senders: windows of every mix
            for lo in range(0, len(msgs), 4):
                await asyncio.gather(*(conn.send(m)
                                       for m in msgs[lo:lo + 4]))
            assert await _until(lambda: len(got) == len(msgs), 10.0)
            assert [s for s, _ in got] == list(range(len(msgs)))
            assert all(b == _blob(i, sizes[i]) for i, b in got)
            d = client.perf.dump()
            if arm == "native":
                assert d["tx_offloop_windows"] >= 3, d
                assert d["tx_offloop_bytes"] >= sum(
                    n for n in sizes if n >= CorkedWriter.OFFLOOP_MIN_BYTES)
                assert d["tx_offloop_lat"]["avgcount"] \
                    == d["tx_offloop_windows"]
            else:
                assert d["tx_offloop_windows"] == 0
                assert d["tx_offloop_bytes"] == 0
            assert d["tx_bytes"] > sum(sizes)
            await client.shutdown()
            await server.shutdown()

        run(go())

    @pytest.mark.parametrize("arm", _ARMS)
    def test_a_full_socket_holds_back_its_own_connection_only(self, arm):
        async def go():
            import socket
            if arm == "native":
                _needs_native()
            slow, client, slow_addr = await _pair(
                client_conf=_arm_conf(arm))
            fast = Messenger("fast", {}, entity_type="osd")
            fast_addr = await fast.bind()
            gate, fast_got = _Gate(), []

            async def fast_dispatch(conn, msg):
                if msg.seqno >= 0:
                    fast_got.append(msg.seqno)
            slow.dispatcher, fast.dispatcher = gate, fast_dispatch
            a = await client.connect(slow_addr)
            b = await client.connect(fast_addr)
            wa = await _corked(a)
            await _corked(b)
            wa._sock.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 16384)
            # 24 MiB at a reader that parks on the first message: more
            # than the receiver's 1 MiB and any socket buffer take
            n = 24
            sends = [asyncio.ensure_future(
                a.send(MTest(seqno=i, blob=_blob(i, 1 << 20))))
                for i in range(n)]
            await asyncio.wait_for(gate.parked.wait(), 10)
            assert await _until(lambda: wa._buffered > 0 and (
                wa._off_jobs > 0 or wa._writer_on), 10.0)
            await asyncio.sleep(0.2)
            assert not all(s.done() for s in sends), \
                "the paused reader took 24 MiB"
            # the other connection keeps sending meanwhile, big windows too
            for i in range(6):
                await asyncio.wait_for(
                    b.send(MTest(seqno=i, blob=_blob(i, 512 << 10))), 5)
            assert await _until(lambda: len(fast_got) == 6, 10.0)
            assert not all(s.done() for s in sends)
            gate.release.set()
            await asyncio.wait_for(asyncio.gather(*sends), 20)
            assert await _until(lambda: len(gate.got) == n, 20.0)
            assert [s for s, _ in gate.got] == list(range(n))
            assert all(blob == _blob(i, 1 << 20) for i, blob in gate.got)
            d = client.perf.dump()
            if arm == "native":
                assert d["tx_offloop_eagain"] >= 1, d
                assert d["tx_offloop_windows"] >= 4
            else:
                assert d["tx_offloop_windows"] == 0
            assert wa._buffered == 0 and wa._off_jobs == 0
            for m in (client, slow, fast):
                await m.shutdown()

        run(go())

    @pytest.mark.parametrize("arm", _ARMS)
    def test_a_reset_mid_window_surfaces_at_drain_and_replays_once(
            self, arm):
        async def go():
            import socket
            if arm == "native":
                _needs_native()
            server, client, addr = await _pair(client_conf=_arm_conf(arm))
            gate = _Gate()
            server_conns = []

            async def dispatch(conn, msg):
                server_conns.append(conn)
                await gate(conn, msg)
            server.dispatcher = dispatch
            conn = await client.connect(addr)
            w = await _corked(conn)
            w._sock.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 16384)
            # seq 0 parks the reader; the 4 MiB windows behind it cannot
            # all leave: one of them is in the middle when the peer resets
            first = asyncio.ensure_future(
                client.send(addr, MTest(seqno=0, blob=b"a"), retries=8))
            await asyncio.wait_for(gate.parked.wait(), 10)
            big = [asyncio.ensure_future(client.send(
                addr, MTest(seqno=i, blob=_blob(i, 4 << 20)), retries=8))
                for i in (1, 2, 3)]
            assert await _until(lambda: w._buffered > 0 and (
                w._off_jobs > 0 or w._writer_on), 10.0)
            await asyncio.sleep(0.1)
            unacked_before = len(conn.unacked)
            assert unacked_before >= 1
            sconn = server_conns[0]
            sconn.writer.transport.abort()  # RST under the window
            with pytest.raises((ConnectionError, OSError)):
                await asyncio.wait_for(w.drain(), 10)
            assert w._exc is not None and w._off_jobs == 0
            gate.release.set()
            await asyncio.wait_for(asyncio.gather(first, *big), 30)
            assert await _until(lambda: len(gate.got) == 4, 20.0)
            assert [s for s, _ in gate.got] == [0, 1, 2, 3], \
                "loss, duplicate or reorder across the replay"
            assert all(blob == _blob(i, 4 << 20)
                       for i, blob in gate.got[1:])
            await client.shutdown()
            await server.shutdown()

        run(go())

    def test_close_with_a_job_pending_leaves_the_fd_number_clean(self):
        async def go():
            import socket
            wp = _needs_native()
            server, client, addr = await _pair(client_type="client")
            other = Messenger("other", {}, entity_type="osd")
            other_addr = await other.bind()
            gate, other_got = _Gate(), []

            async def other_dispatch(conn, msg):
                other_got.append((msg.seqno, bytes(msg.blob)))
            server.dispatcher, other.dispatcher = gate, other_dispatch
            conn = await client.connect(addr, peer_type="client")
            w = await _corked(conn)
            fd = w._fd
            w._sock.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 16384)
            sends = [asyncio.ensure_future(
                conn.send(MTest(seqno=i, blob=_blob(i, 4 << 20))))
                for i in range(3)]
            await asyncio.wait_for(gate.parked.wait(), 10)
            assert await _until(lambda: w._off_jobs > 0, 10.0)
            before = wp.wire_sender_stats()
            await conn.close()
            after = wp.wire_sender_stats()
            assert after["cancelled"] > before["cancelled"]
            assert w._off_jobs == 0
            # nothing of the fd is left on the thread: a cancel finds none
            assert wp.wire_sender_cancel(fd) == 0
            for s in sends:
                with pytest.raises((ConnectionError, OSError)):
                    await s
            # the number's next owner: a connection that gets the same fd
            conn2 = await client.connect(other_addr)
            w2 = await _corked(conn2)
            if w2._fd != fd:
                pytest.skip(f"fd {fd} was not handed out again "
                            f"(got {w2._fd})")
            for i in range(4):
                await conn2.send(MTest(seqno=i, blob=_blob(i + 7, 1 << 20)))
            assert await _until(
                lambda: len([s for s, _ in other_got if s >= 0]) == 4, 10.0)
            assert [(s, b) for s, b in other_got if s >= 0] == [
                (i, _blob(i + 7, 1 << 20)) for i in range(4)]
            assert not conn2.closed
            gate.release.set()
            for m in (client, server, other):
                await m.shutdown()

        run(go())

    @pytest.mark.parametrize("case", [
        (TestLosslessReplay, "test_exactly_once_under_injected_failures",
         "tcp-native"),
        (TestLosslessReplay,
         "test_bidirectional_rpc_exactly_once_under_failures", "tcp-native"),
        (TestCorkedOutbox,
         "test_burst_exactly_once_in_order_under_failures", "tcp-native"),
        (None, "test_injected_drops_under_standing_debts_deliver_"
               "exactly_once"),
    ], ids=lambda c: c[1][5:45])
    def test_injected_failures_with_every_window_on_the_thread(
            self, case, monkeypatch):
        from ceph_tpu.rados.messenger import CorkedWriter
        wp = _needs_native()
        cls, name, *args = case
        cls = cls or TestAcksWaitForCompany
        monkeypatch.setattr(CorkedWriter, "OFFLOOP_MIN_BYTES", 0)
        before = wp.wire_sender_stats()
        getattr(cls(), name)(*args)
        after = wp.wire_sender_stats()
        assert after["submitted"] - before["submitted"] >= 10
        assert after["submitted"] == after["completed"] + after["failed"] \
            + after["cancelled"], "a job outlived its messengers"

    def test_the_engagement_metric_reads_offloop_over_sent(self):
        async def go():
            import json
            import os
            from benchmarks import layers
            _needs_native()
            spec_path = os.path.join(layers.DIR, "tx_offloop_share.put.json")
            with open(spec_path) as f:
                spec = json.load(f)
            with open(spec_path.replace(".put.", ".get.")) as f:
                assert json.load(f) == spec
            assert spec["num"] == ["wire.tx_offloop_bytes"]
            assert spec["den"] == ["wire.tx_bytes"]
            server, client, addr = await _pair()
            server.dispatcher = _swallow
            conn = await client.connect(addr)
            await _corked(conn)

            def snap():
                d = client.perf.dump()
                return {"wire." + k: d[k]
                        for k in ("tx_offloop_bytes", "tx_bytes")}

            def share(a, b):
                return layers._perf_counter(
                    spec, {"counters": {k: b[k] - a[k] for k in a}})
            s0 = snap()
            for i in range(8):  # a 4 KiB write's windows: all under the line
                await conn.send(MTest(seqno=i, blob=_blob(i, 4096)))
            s1 = snap()
            assert share(s0, s1) == 0.0
            for i in range(4):
                await conn.send(MTest(seqno=i, blob=_blob(i, 1 << 20)))
            s2 = snap()
            moved = s2["wire.tx_offloop_bytes"] - s1["wire.tx_offloop_bytes"]
            assert moved >= 4 << 20
            assert share(s1, s2) == pytest.approx(
                100.0 * moved / (s2["wire.tx_bytes"] - s1["wire.tx_bytes"]))
            assert share(s1, s2) > 99.0
            # a program without the counter reports nothing
            assert layers._perf_counter(
                spec, {"counters": {"wire.tx_bytes": 5}}) is None
            await client.shutdown()
            await server.shutdown()

        run(go())

    def test_only_a_writer_that_hears_of_a_loss_hands_over(self):
        async def go():
            from ceph_tpu.rados.messenger import CorkedWriter, _offloop_of
            wp = _needs_native()
            loop = asyncio.get_running_loop()
            got = bytearray()

            async def sink(reader, writer):
                while chunk := await reader.read(1 << 20):
                    got.extend(chunk)
            srv = await asyncio.start_server(sink, "127.0.0.1", 0)
            port = srv.sockets[0].getsockname()[1]
            _, sw = await asyncio.open_connection("127.0.0.1", port)
            sock = sw.transport.get_extra_info("socket")
            w = CorkedWriter(sw.transport, getattr(sock, "_sock", sock),
                             sw, wp=wp)
            blob = _blob(3, 1 << 20)
            assert not w.offloop_takes(len(blob))
            w.writelines([blob])  # nobody forwards a loss: the inline arm
            await w.drain()
            assert w._off_jobs == 0
            off = _offloop_of(loop, wp)
            w.hears_loss(off)
            assert w.offloop_takes(len(blob)) and not w.offloop_takes(100)
            w.writelines([blob])
            assert w._off_jobs == 1 and w.offloop_takes(100)  # order
            w.writelines([b"tail"])
            assert w._off_jobs == 2
            await w.drain()
            assert w._off_jobs == 0 and w._buffered == 0
            assert await _until(lambda: len(got) == 2 * len(blob) + 4, 5.0)
            assert bytes(got) == blob + blob + b"tail"
            w.close()
            srv.close()
            off.close()
            assert off.closed

        run(go())

    def test_a_loop_closed_under_its_jobs_is_abandoned_by_the_next(self):
        """No shutdown, no close: the loop ends with a window parked on
        the thread.  The next loop's first hand-over drops what the dead
        loop left, through its channel, and its own jobs are untouched."""
        import socket
        from ceph_tpu.rados.messenger import (CorkedWriter, _OFFLOOPS,
                                              _offloop_of)
        wp = _needs_native()
        before = wp.wire_sender_stats()
        keep = []  # the dead loop's sockets stay open: their numbers too

        async def writer_to(port):
            _, sw = await asyncio.open_connection("127.0.0.1", port)
            sock = sw.transport.get_extra_info("socket")
            sock = getattr(sock, "_sock", sock)
            w = CorkedWriter(sw.transport, sock, sw, wp=wp)
            w.hears_loss(_offloop_of(asyncio.get_running_loop(), wp))
            return w

        async def first():
            # a socket pair whose far end nobody reads and nobody closes
            a, b = socket.socketpair()
            a.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 16384)
            keep.extend((b, a.dup()))
            _, sw = await asyncio.open_connection(sock=a)
            sock = sw.transport.get_extra_info("socket")
            w = CorkedWriter(sw.transport, getattr(sock, "_sock", sock),
                             sw, wp=wp)
            w.hears_loss(_offloop_of(asyncio.get_running_loop(), wp))
            w.writelines([_blob(1, 8 << 20)])
            assert w._off_jobs == 1
            await asyncio.sleep(0.1)
            assert w._buffered > 0, "8 MiB went into a socket nobody reads"
            keep.append(w)
            return w._off

        old = asyncio.run(asyncio.wait_for(first(), 30))
        assert not old.closed and old.jobs and old.loop.is_closed()

        async def second():
            got = bytearray()

            async def reading(reader, writer):
                while chunk := await reader.read(1 << 20):
                    got.extend(chunk)
            srv = await asyncio.start_server(reading, "127.0.0.1", 0)
            w = await writer_to(srv.sockets[0].getsockname()[1])
            assert old.closed and not old.jobs  # abandoned on the way
            assert w._off is not old and not w._off.closed
            blob = _blob(2, 2 << 20)
            w.writelines([blob])
            await asyncio.wait_for(w.drain(), 10)
            assert await _until(lambda: len(got) == len(blob), 5.0)
            assert bytes(got) == blob
            w.close()
            srv.close()
            w._off.close()

        run(second())
        after = wp.wire_sender_stats()
        assert after["cancelled"] - before["cancelled"] == 1
        assert after["submitted"] - before["submitted"] == 2
        assert after["submitted"] == after["completed"] + after["failed"] \
            + after["cancelled"]
        assert all(off.closed for off in _OFFLOOPS.values())

    def test_the_last_shutdown_stops_the_thread_and_the_next_use_starts_it(
            self):
        async def go():
            from ceph_tpu.rados.messenger import _OFFLOOPS
            wp = _needs_native()
            loop = asyncio.get_running_loop()
            for round_ in range(2):
                server, client, addr = await _pair()
                server.dispatcher = _swallow
                conn = await client.connect(addr)
                await _corked(conn)
                starts = wp.wire_sender_stats()["starts"]
                await conn.send(MTest(seqno=1, blob=_blob(1, 1 << 20)))
                assert wp.wire_sender_stats()["starts"] == starts + 1
                off = _OFFLOOPS[loop]
                assert not off.closed and client in off.users
                await client.shutdown()
                # a messenger whose connections hand over keeps it open
                assert off.closed == (server not in off.users)
                await server.shutdown()
                assert off.closed and not off.jobs
                st = wp.wire_sender_stats()
                assert st["submitted"] == st["completed"] + st["failed"] \
                    + st["cancelled"]
                # stopped: stopping again finds no thread
                assert wp.wire_sender_stop() == 0

        run(go())

    def test_loops_on_threads_get_their_ends_under_one_lock(self):
        """Loops that start together on several threads (a client on a
        thread of its own beside the daemons' loop) each get an end of
        their own, the table is never walked
        while it grows, the hooks are registered once (a second
        thread_source would count loop.thread_messenger twice) and the
        last end to close stops the thread."""
        import sys
        import threading
        from ceph_tpu.common import tracing
        from ceph_tpu.rados import messenger as msgr
        wp = _needs_native()
        n, rounds = 8, 12
        gate = threading.Barrier(n)
        errors, ends = [], []

        def one():
            loop = asyncio.new_event_loop()
            try:
                for _ in range(rounds):
                    gate.wait(10)
                    off = msgr._offloop_of(loop, wp)
                    assert not off.closed and off.loop is loop
                    ends.append(off)
                    gate.wait(10)
                    off.close()
            except Exception as e:  # reported on the test's thread
                errors.append(e)
                gate.abort()
            finally:
                loop.close()

        was = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        threads = [threading.Thread(target=one) for _ in range(n)]
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(60)
        finally:
            sys.setswitchinterval(was)
        assert not errors, errors
        assert not any(t.is_alive() for t in threads)
        assert len(ends) == n * rounds and all(off.closed for off in ends)
        assert len({id(off) for off in ends}) == n * rounds
        assert sum(1 for layer, _, _ in tracing._THREAD_SOURCES
                   if layer == "messenger") == 1
        assert wp.wire_sender_stop() == 0  # the last close stopped it

    def test_a_secure_stream_and_the_python_arm_never_hand_over(self):
        async def go():
            from ceph_tpu.rados.messenger import CorkedWriter
            conf = {"ms_auth_secret": "s3", "ms_secure_mode": True}
            server, client, addr = await _pair(dict(conf), dict(conf))
            got = []

            async def dispatch(conn, msg):
                got.append(bytes(msg.blob))
            server.dispatcher = dispatch
            conn = await client.connect(addr)
            for i in range(3):
                await conn.send(MTest(seqno=i, blob=_blob(i, 1 << 20)))
            assert await _until(lambda: len(got) == 3, 10.0)
            assert got == [_blob(i, 1 << 20) for i in range(3)]
            assert client.perf.dump()["tx_offloop_windows"] == 0
            if isinstance(conn.writer, CorkedWriter):
                assert conn.writer._off is None or not conn.writer._off_jobs
            await client.shutdown()
            await server.shutdown()

        run(go())


# -- every wire a messenger has (ISSUE 50): faults, teardown and a cluster's
# round trip on plaintext TCP (both arms) and on SecureStream ---------------

def _is_on(conn, transport: str) -> bool:
    """`conn` reads through the reader kind `transport` names."""
    from ceph_tpu.rados.auth import SecureStream
    from ceph_tpu.rados.messenger import FrameReceiver
    if transport == "tcp-secure":
        return isinstance(conn.reader, SecureStream)
    return (isinstance(conn.reader, FrameReceiver)
            and (conn.wp is not None) == (transport == "tcp-native"))


@pytest.mark.parametrize("transport", _TRANSPORTS)
class TestEveryTransport:
    def test_injected_dup_frames_keep_order_and_the_other_planes_once(
            self, transport):
        """ms_inject_dup_frames = 1 sends every MOSDOp as two frames with
        two seqs (the receiver's dedupe cannot drop the second: the PG
        log's reqid set does); a type outside the op plane keeps the
        session's exactly-once.  Both arrive in the order they were sent."""
        async def go():
            from ceph_tpu.rados.types import MOSDOp
            server, client, addr = await _pair_on(
                transport, None, {"ms_inject_dup_frames": 1})
            got = []

            async def dispatch(conn, msg):
                got.append(("op", msg.reqid) if isinstance(msg, MOSDOp)
                           else ("plain", msg.seqno))
            server.dispatcher = dispatch
            n = 30
            for i in range(n):
                await client.send(addr, MOSDOp(op="read", oid=f"o{i}",
                                               reqid=f"r{i:03d}"))
                await client.send(addr, MTest(seqno=i, blob=b"d" * 4096))
            assert await _until(lambda: len(got) >= 3 * n, 10.0)
            await asyncio.sleep(0.1)  # nothing more comes
            want = []
            for i in range(n):
                want += [("op", f"r{i:03d}")] * 2 + [("plain", i)]
            assert got == want
            assert _is_on(client._conns[tuple(addr)], transport)
            await client.shutdown()
            await server.shutdown()

        run(go())

    def test_a_connection_torn_mid_frame_returns_its_throttle_cost(
            self, transport):
        """A frame in dispatch holds its cost; the transport dies with the
        next frame half arrived (on a SecureStream its header has charged
        the throttle already); when the serve loop ends, the dispatch
        throttle holds nothing."""
        async def go():
            server, client, addr = await _pair_on(transport)
            gate = _Gate()
            server.dispatcher = gate
            conn = await client.connect(addr, peer_type="client")
            assert not conn.policy.replay  # nothing replays the torn frame
            await conn.send(MTest(seqno=0, blob=b"a" * 3000))
            await asyncio.wait_for(gate.parked.wait(), 5)
            assert server.dispatch_throttle.current >= 3000
            assert _is_on(conn, transport)
            frame = _mk_frame(MTest(seqno=1, blob=b"b" * 9000), 2)
            conn.writer.write(frame[:len(frame) // 2])
            await conn.writer.drain()
            await conn.close()
            gate.release.set()
            assert await _until(
                lambda: server.dispatch_throttle.current == 0, 5.0), \
                server.dispatch_throttle.current
            assert gate.got == [(0, b"a" * 3000)]
            await client.shutdown()
            await server.shutdown()
            assert server.dispatch_throttle.current == 0

        run(go())

    def test_a_tcp_cluster_round_trips_4mib_byte_identical(self, transport):
        """The cells' wire (`ms_local_fastpath: False`): a 4 MiB object
        through an EC pool of a vstart cluster, on each transport."""
        async def go():
            import os

            from ceph_tpu.rados.vstart import Cluster
            cluster = Cluster(n_osds=4, conf=_transport_conf(
                transport, ms_local_fastpath=False, osd_auto_repair=False))
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("p", profile={
                    "plugin": "jerasure", "technique": "reed_sol_van",
                    "k": "2", "m": "1"})
                blob = os.urandom(4 << 20)
                await c.put(pool, "big", blob)
                assert bytes(await c.get(pool, "big")) == blob
                for osd in cluster.osds.values():
                    m = osd.messenger
                    assert not m._local_conns
                    assert m.perf.get("local_msgs") == 0
                    assert all(_is_on(conn, transport)
                               for conn in m._sessions.values()
                               if not conn.closed)
                assert sum(o.messenger.perf.get("rx_bytes")
                           for o in cluster.osds.values()) > 4 << 20
                await c.stop()
            finally:
                await cluster.stop()

        asyncio.run(asyncio.wait_for(go(), 120))


@pytest.mark.parametrize("native", [True, False], ids=["native", "python"])
def test_a_zlib_negotiated_connection_verifies_with_zlib(native):
    """A pair whose hosts resolved different checksum kinds negotiates
    zlib frame crcs (`Messenger._negotiated_crc`); the FrameReceiver then
    has to verify with zlib on either arm: the native crc32c pass would
    refuse every frame and loop the session through BadFrame."""
    if native:
        _needs_native()
    import pickle

    from ceph_tpu.rados.messenger import FLAG_BLOB, _BLOB_PFX
    conn = _framer_conn(native)
    conn.crc_fn = zlib.crc32
    payload = encode_payload(MTest(text="z", seqno=5))
    small = _HDR.pack(len(payload), 900, 1, 0, zlib.crc32(payload), 1) \
        + payload
    blob = bytes(range(256)) * 512
    pickled = pickle.dumps({"chunk_crc": 0})
    head = _BLOB_PFX.pack(len(pickled), zlib.crc32(blob)) + pickled
    big = _HDR.pack(len(head) + len(blob), 910, 1, FLAG_BLOB,
                    zlib.crc32(head), 2) + head + blob
    _feed(conn, small + big)
    assert conn._rx_error is None
    assert [f[2] for f in conn._rx_stash] == [1, 2]
    assert bytes(conn._rx_stash[1][5]) == blob
    assert conn._rx_stash[1][7]  # the blob's crc was checked, with zlib
    # the same frames with the OTHER kind's crcs are refused
    from ceph_tpu.utils.checksum import checksum, checksum_kind
    if checksum_kind() != "zlib":
        other = _framer_conn(native)
        other.crc_fn = zlib.crc32
        _feed(other, _HDR.pack(len(payload), 900, 1, 0,
                               checksum(payload) & 0xFFFFFFFF, 1) + payload)
        assert isinstance(other._rx_error, BadFrame)
        assert not other._rx_stash


@pytest.mark.parametrize("wire", ["tcp", "default"])
def test_which_connections_a_cluster_runs_on(wire):
    """A cluster told `ms_local_fastpath: False` (the benchmark's cells)
    talks through CorkedWriter + FrameReceiver; a default vstart cluster
    (most of the suite) hands messages over through LocalConnection."""
    async def go():
        from ceph_tpu.rados.messenger import (CorkedWriter, FrameReceiver,
                                              LocalConnection)
        from ceph_tpu.rados.vstart import Cluster
        conf = {"osd_auto_repair": False}
        if wire == "tcp":
            conf["ms_local_fastpath"] = False
        cluster = Cluster(n_osds=3, conf=conf)
        await cluster.start()
        try:
            c = await cluster.client()
            pool = await c.create_pool("p", profile={
                "plugin": "jerasure", "technique": "reed_sol_van",
                "k": "2", "m": "1"})
            for i in range(4):
                await c.put(pool, f"o{i}", b"x" * 65536)
            msgrs = [o.messenger for o in cluster.osds.values()]
            if wire == "tcp":
                assert not any(m._local_conns for m in msgrs)
                out = [conn for m in msgrs for conn in m._conns.values()
                       if conn.out_seq > 2 and not conn.closed]
                assert out and all(isinstance(conn.writer, CorkedWriter)
                                   for conn in out)
                served = [conn for m in msgrs
                          for conn in m._sessions.values() if not conn.closed]
                assert served and all(isinstance(conn.reader, FrameReceiver)
                                      for conn in served)
                assert all(m.perf.get("local_msgs") == 0 for m in msgrs)
            else:
                local = [conn for m in msgrs
                         for conn in m._local_conns.values()]
                assert local and all(isinstance(conn, LocalConnection)
                                     for conn in local)
                assert sum(m.perf.get("local_msgs") for m in msgrs) > 0
                assert all(m.perf.get("tx_bytes") == 0 for m in msgrs)
            await c.stop()
        finally:
            await cluster.stop()

    asyncio.run(asyncio.wait_for(go(), 120))
