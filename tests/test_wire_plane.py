"""The messenger's lane layer: multi-lane peer striping with gseq
reassembly and fragmentation, per-(peer,type) ordering under fault
injection, single-lane-dead failover, dump_reactors + its renderer, and
the golden pre-lane frame compatibility rule."""

from __future__ import annotations

import asyncio
import os

from ceph_tpu.rados.messenger import (PROC_TOKEN, LaneGroup, Messenger,
                                      MLaneHello, MLaneSegment, _MSG_TYPES,
                                      decode_message, encode_payload_parts,
                                      message)


# a striped test type mirroring the data-plane declaration pattern
@message(9801)
class MWire:
    seq: int = 0
    kind: str = "a"
    data: bytes = b""
    gseq: int = 0


MWire.LANE_STRIPE = True
MWire.BLOB_ATTR = "data"
MWire.BLOB_VIEW_OK = True
MWire.FIXED_FIELDS = [("seq", "q"), ("kind", "s"), ("data", "y"),
                      ("gseq", "Q")]


@message(9802)
class MCtl:
    seq: int = 0


async def _pair(conf_a=None, conf_b=None):
    a = Messenger("a", dict(conf_a or {}))
    b = Messenger("b", dict(conf_b or {}), entity_type="osd")
    await a.bind()
    addr_b = await b.bind()
    return a, b, tuple(addr_b)


class TestLaneStriping:
    def test_negotiates_lane_group_and_stripes(self):
        async def go():
            a, b, addr_b = await _pair({"ms_lanes_per_peer": 4},
                                       {"ms_lanes_per_peer": 4})
            got = []
            done = asyncio.Event()
            async def disp(conn, msg):
                # handlers see the GROUP (replies stripe too)
                assert isinstance(conn, LaneGroup)
                got.append(msg.seq)
                if len(got) >= 90:
                    done.set()
            b.dispatcher = disp
            for i in range(90):
                await a.send(addr_b, MWire(seq=i, data=b"y" * 4096))
            await asyncio.wait_for(done.wait(), 15)
            assert got == list(range(90))
            group = a._conns[addr_b]
            assert isinstance(group, LaneGroup)
            assert group.n_lanes == 4
            # round-robin used every data lane; lane 0 carried none
            perf = a.perf.dump()
            for lane in (1, 2, 3):
                assert perf.get(f"tx_lane{lane}_msgs", 0) > 0
            assert "tx_lane0_msgs" not in perf
            await a.shutdown()
            await b.shutdown()
        asyncio.run(go())

    def test_control_plane_rides_lane_zero(self):
        async def go():
            a, b, addr_b = await _pair({"ms_lanes_per_peer": 3},
                                       {"ms_lanes_per_peer": 3})
            got = []
            async def disp(conn, msg):
                got.append(msg)
            b.dispatcher = disp
            await a.send(addr_b, MCtl(seq=1))
            await asyncio.sleep(0.2)
            group = a._conns[addr_b]
            # no gseq stamped, no lane counters: control went on lane 0
            assert group._tx_gseq == 0
            assert "tx_lane1_msgs" not in a.perf.dump()
            assert len(got) == 1
            await a.shutdown()
            await b.shutdown()
        asyncio.run(go())

    def test_large_blob_fragments_and_reassembles_byte_exact(self):
        async def go():
            a, b, addr_b = await _pair({"ms_lanes_per_peer": 4},
                                       {"ms_lanes_per_peer": 4})
            got = []
            done = asyncio.Event()
            async def disp(conn, msg):
                got.append(msg)
                done.set()
            b.dispatcher = disp
            payload = bytes(range(256)) * (3 << 12)  # 3 MiB, patterned
            await a.send(addr_b, MWire(seq=7, data=payload))
            await asyncio.wait_for(done.wait(), 15)
            assert bytes(got[0].data) == payload
            assert a.perf.get("lane_frag_tx") == 3  # one per data lane
            assert b.perf.get("lane_frag_rx") == 3
            await a.shutdown()
            await b.shutdown()
        asyncio.run(go())

    def test_old_peer_without_lanes_gets_single_connection(self):
        async def go():
            # acceptor that never advertises lanes_ok (old build)
            a, b, addr_b = await _pair({"ms_lanes_per_peer": 4}, {})
            orig = b._handshake_in

            async def no_lanes(reader, writer):
                out = list(await orig(reader, writer))
                return tuple(out)
            got = []
            async def disp(conn, msg):
                got.append(msg)
            b.dispatcher = disp
            # strip the capability on the wire: monkeypatch the OUT side
            orig_out = a._handshake_out

            async def patched(reader, writer, lossless, session_id):
                (peer_name, resumed, ckind, _lanes_ok,
                 r, w) = await orig_out(reader, writer, lossless,
                                        session_id)
                return (peer_name, resumed, ckind, False, r, w)
            a._handshake_out = patched
            await a.send(addr_b, MWire(seq=0, data=b"z" * 2048))
            await asyncio.sleep(0.2)
            assert not isinstance(a._conns[addr_b], LaneGroup)
            assert len(got) == 1
            await a.shutdown()
            await b.shutdown()
        asyncio.run(go())


class TestLaneOrderingUnderFaults:
    def test_per_peer_type_order_under_socket_failures(self):
        """Satellite: per-(peer,type) ordering with striping enabled
        while ms_inject_socket_failures severs lanes mid-burst."""
        async def go():
            conf = {"ms_lanes_per_peer": 3,
                    "ms_inject_socket_failures": 40}
            a, b, addr_b = await _pair(dict(conf), dict(conf))
            got = []
            done = asyncio.Event()
            N = 120
            async def disp(conn, msg):
                got.append((msg.kind, msg.seq))
                if len(got) >= N:
                    done.set()
            b.dispatcher = disp
            for i in range(N):
                await a.send(addr_b, MWire(seq=i, kind="ab"[i % 2],
                                           data=b"q" * 8192))
            await asyncio.wait_for(done.wait(), 30)
            # exactly-once AND total order (stronger than per-type)
            seqs = [s for _, s in got]
            assert seqs == list(range(N))
            await a.shutdown()
            await b.shutdown()
        asyncio.run(go())

    def test_single_lane_dead_failover(self):
        """Satellite: one dead lane revives and replays ONLY its own
        unacked frames while the remaining lanes keep draining."""
        async def go():
            a, b, addr_b = await _pair({"ms_lanes_per_peer": 3},
                                       {"ms_lanes_per_peer": 3})
            got = []
            async def disp(conn, msg):
                got.append(msg.seq)
            b.dispatcher = disp
            for i in range(8):
                await a.send(addr_b, MWire(seq=i, data=b"z" * 30000))
            await asyncio.sleep(0.3)
            group = a._conns[addr_b]
            victim = group.lanes[2]
            survivor = group.lanes[1]
            await victim.close()
            # sends through the dead window: the victim lane's frames
            # queue in ITS unacked replay queue; the others drain live
            for i in range(8, 28):
                await a.send(addr_b, MWire(seq=i, data=b"z" * 30000))
            assert len(victim.unacked) > 0
            # the survivor lane's queue keeps turning over (acks drain
            # it) — only the dead lane pins frames for replay
            await asyncio.sleep(2.0)
            assert got == list(range(28))
            assert a.perf.get("lane_revivals") >= 1
            assert not group.closed
            assert len(victim.unacked) == 0  # replayed + acked
            assert len(survivor.unacked) == 0
            await a.shutdown()
            await b.shutdown()
        asyncio.run(go())


class TestLaneTeardown:
    def test_group_close_returns_the_costs_its_fifo_holds(self):
        """A message the pump has not dispatched yet holds its
        dispatch-throttle cost; a group closed under it gives it back."""
        async def go():
            from ceph_tpu.rados.messenger import Policy

            m = Messenger("t", {"ms_lanes_per_peer": 3})
            group = LaneGroup(m, ("127.0.0.1", 1), "g" * 16, 3,
                              outbound=False, policy=Policy.lossless_peer())

            cost = 4096
            await m.dispatch_throttle.get(cost)
            msg = MWire(seq=0, data=b"x")
            msg.gseq = 1  # in order: lands in the dispatch fifo
            group.rx_push(None, msg, cost)
            assert m.dispatch_throttle.current == cost  # held by the fifo
            parked = MWire(seq=2, data=b"y")
            parked.gseq = 3  # a hole before it: parks, its cost returns now
            await m.dispatch_throttle.get(cost)
            group.rx_push(None, parked, cost)
            assert m.dispatch_throttle.current == cost
            await group.close()
            assert m.dispatch_throttle.current == 0
            await m.shutdown()
        asyncio.run(go())


class TestWirePlaneIntrospection:
    def test_dump_reactors_shape_and_renderer(self):
        async def go():
            a, b, addr_b = await _pair(
                {"ms_lanes_per_peer": 3}, {"ms_lanes_per_peer": 3})
            async def disp(conn, msg):
                pass
            b.dispatcher = disp
            await a.send(addr_b, MWire(seq=0, data=b"d" * 4096))
            await asyncio.sleep(0.2)
            dump = a.dump_reactors()
            assert dump["lanes_per_peer"] == 3
            assert dump["wirepath"] in ("native", "python")
            assert len(dump["peers"]) == 1
            lanes = dump["peers"][0]["lanes"]
            assert [ln["lane"] for ln in lanes] == [0, 1, 2]
            assert lanes[0]["control"] is True
            from ceph_tpu.tools.ceph import render_reactors

            lines = render_reactors(dump)
            text = "\n".join(lines)
            assert "3 lanes/peer" in text
            assert "lane 0 [ctl ]" in text
            assert "lane 1 [data]" in text
            await a.shutdown()
            await b.shutdown()
        asyncio.run(go())

    def test_osd_asok_dump_reactors(self):
        async def go():
            from ceph_tpu.rados.vstart import Cluster

            cluster = Cluster(n_osds=2, conf={
                "osd_auto_repair": False,
                "ms_local_fastpath": False,
                "ms_lanes_per_peer": 2})
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("p", profile={
                    "plugin": "jerasure", "technique": "reed_sol_van",
                    "k": "1", "m": "1"})
                await c.put(pool, "o", b"x" * 4096)
                osd = next(iter(cluster.osds.values()))
                dump = osd.ctx.asok.execute("dump_reactors")
                assert dump["lanes_per_peer"] == 2
                assert isinstance(dump["peers"], list)
                await c.stop()
            finally:
                await cluster.stop()
        asyncio.run(go())


class TestLaneWireCompat:
    def test_mlanehello_in_registry_and_corpus(self):
        assert _MSG_TYPES[71] is MLaneHello
        assert _MSG_TYPES[72] is MLaneSegment
        base = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "corpus", "wire")
        for name in ("MLaneHello", "MLaneSegment"):
            assert os.path.exists(os.path.join(base, name + ".frame")), \
                f"{name} missing from the wire corpus"

    def test_lane_hello_roundtrip(self):
        m = MLaneHello(group="gg", lane=3, n_lanes=8, proc="pp", flags=2)
        payload, blob, fixed = encode_payload_parts(m)
        assert fixed and blob is None
        back = decode_message(71, MLaneHello.VERSION, payload, None, True)
        assert back.__dict__ == m.__dict__

    def test_golden_prelane_frames_decode_with_default_gseq(self):
        """Satellite: pre-lane golden frames (no gseq tail) decode via
        the truncated-tail rule with gseq defaulting to 0."""
        import struct

        from ceph_tpu.tools.wire_corpus import _FRAME_HDR

        base = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "corpus", "wire", "golden")
        names = [n for n in os.listdir(base)
                 if n.endswith(".v_prelane.frame")]
        assert len(names) >= 8
        for name in names:
            with open(os.path.join(base, name), "rb") as f:
                raw = f.read()
            type_id, version, fixed, plen = _FRAME_HDR.unpack_from(raw, 0)
            off = _FRAME_HDR.size
            payload = raw[off:off + plen]
            off += plen
            (blen,) = struct.unpack_from("<I", raw, off)
            blob = raw[off + 4:off + 4 + blen] if blen else None
            msg = decode_message(type_id, version, payload, blob,
                                 bool(fixed))
            assert getattr(msg, "gseq", 0) == 0

    def test_proc_token_stable_within_process(self):
        from ceph_tpu.rados import messenger

        assert messenger.PROC_TOKEN == PROC_TOKEN
        assert len(PROC_TOKEN) == 32


class TestWirepathLaneParity:
    """Satellite (ISSUE 12): the lane-striped fragmentation path —
    MLaneSegment fragments scattering into the group assembly buffer —
    must replay/dedupe identically and serve byte-identical blobs with
    the wirepath forced native and forced python, under injected socket
    failures AND duplicated frames."""

    N = 30

    def _arm(self, native: bool):
        import hashlib

        async def go():
            conf = {"ms_lanes_per_peer": 3,
                    "ms_wirepath_native": native,
                    "ms_inject_socket_failures": 25,
                    "ms_inject_dup_frames": 6}
            a, b, addr_b = await _pair(dict(conf), dict(conf))
            got = []
            done = asyncio.Event()
            async def disp(conn, msg):
                got.append((msg.seq,
                            hashlib.sha256(bytes(msg.data)).hexdigest()))
                if len(got) >= self.N:
                    done.set()
            b.dispatcher = disp
            for i in range(self.N):
                # sizes straddle the fragmentation threshold so some
                # messages stripe across lanes and some ride whole
                data = bytes([(i * 11 + j) & 0xFF
                              for j in range(256)]) * (1 + (i % 5) * 120)
                await a.send(addr_b, MWire(seq=i, data=data))
            await asyncio.wait_for(done.wait(), 60)
            # tx is the deterministic engagement signal: every flush
            # window on the native arm rides wirepy_writev; rx drain
            # counts only fully-buffered bursts, which timing can starve
            tx_native = (a.perf.dump()["native_tx_calls"]
                         + b.perf.dump()["native_tx_calls"])
            await a.shutdown()
            await b.shutdown()
            return got, tx_native

        return asyncio.run(go())

    def test_lane_replay_parity_native_vs_python(self):
        import hashlib

        from ceph_tpu.utils import wirepath

        native_got, native_tx = self._arm(True)
        python_got, python_tx = self._arm(False)
        if wirepath.kind() == "native":
            # the native arm must actually have engaged — a wirepath
            # that silently never wires into lane connections would
            # make this parity test compare python against itself
            assert native_tx > 0
        assert python_tx == 0
        want = [(i, hashlib.sha256(
            bytes([(i * 11 + j) & 0xFF for j in range(256)])
            * (1 + (i % 5) * 120)).hexdigest()) for i in range(self.N)]
        # exactly-once, total order, byte-identical payloads, both arms
        assert native_got == want
        assert python_got == want
