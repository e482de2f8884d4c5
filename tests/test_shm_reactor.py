"""Process-sharded reactor plane (ms_reactor_mode=process): shm ring
pipe semantics, worker fork/reap/respawn, messenger delegation with
byte-identity + ordering, fault-injection parity on the process arm,
kill-a-worker-mid-burst revival, whole-plane perf aggregation, the
teardown throttle-cost return, and the cross-process-seam lint rules."""

from __future__ import annotations

import asyncio
import os
import signal

import pytest

from ceph_tpu.common.throttle import Throttle
from ceph_tpu.rados.messenger import LaneGroup, Messenger, Policy, message
from ceph_tpu.rados.reactor import ReactorPool
from ceph_tpu.rados.shm_ring import REC_FRAME, ShmRingPipe

pytestmark = pytest.mark.skipif(
    not hasattr(os, "fork"), reason="process reactors need fork")


def _shm_ok() -> bool:
    try:
        from multiprocessing import shared_memory

        s = shared_memory.SharedMemory(create=True, size=1024)
        s.close()
        s.unlink()
        return True
    except Exception:
        return False


if not _shm_ok():  # pragma: no cover - host without /dev/shm
    pytestmark = pytest.mark.skip(reason="no shared memory on this host")


# striped test type mirroring the data-plane declaration pattern
@message(9810)
class MProc:
    seq: int = 0
    kind: str = "a"
    data: bytes = b""
    gseq: int = 0


MProc.LANE_STRIPE = True
MProc.BLOB_ATTR = "data"
MProc.BLOB_VIEW_OK = True
MProc.FIXED_FIELDS = [("seq", "q"), ("kind", "s"), ("data", "y"),
                      ("gseq", "Q")]

PCONF = {"ms_reactor_mode": "process", "ms_lanes_per_peer": 3,
         "ms_async_op_threads": 2}


async def _pair(conf_a=None, conf_b=None):
    a = Messenger("a", dict(conf_a if conf_a is not None else PCONF))
    b = Messenger("b", dict(conf_b if conf_b is not None else PCONF),
                  entity_type="osd")
    await a.bind()
    addr_b = await b.bind()
    return a, b, tuple(addr_b)


def _assert_reaped(pids) -> None:
    """No zombie (or live) worker survives shutdown — reap pinned."""
    for pid in pids:
        if pid is None:
            continue
        try:
            os.kill(pid, 0)
        except ProcessLookupError:
            continue
        # pid exists: it must not be OUR zombie child (waitpid would
        # find it); a reaped-and-recycled pid belongs to someone else
        try:
            done, _ = os.waitpid(pid, os.WNOHANG)
        except ChildProcessError:
            continue  # not our child (recycled pid)
        raise AssertionError(f"worker {pid} still ours after shutdown")


class TestShmRingPipe:
    def test_stream_wrap_and_records(self):
        async def go():
            pipe, name, peer_db = ShmRingPipe.create(256)
            rx = ShmRingPipe.attach(name, 256, peer_db, producer=False)
            pipe.as_role(producer=True)
            # records larger than the ring stream through in pieces
            payload = os.urandom(1000)

            async def produce():
                await pipe.put_record(REC_FRAME, [payload])
                await pipe.put_record(REC_FRAME, [b"x" * 300])

            async def consume():
                out = []
                for _ in range(2):
                    kind, length = await rx.read_record_hdr()
                    assert kind == REC_FRAME
                    out.append(await rx.read_exact(length))
                return out

            _, got = await asyncio.gather(produce(), consume())
            assert got[0] == payload
            assert got[1] == b"x" * 300
            pipe.close()
            rx.close()

        asyncio.run(go())

    def test_backpressure_parks_producer_until_consumed(self):
        async def go():
            pipe, name, peer_db = ShmRingPipe.create(128)
            rx = ShmRingPipe.attach(name, 128, peer_db, producer=False)
            state = {"done": False}

            async def produce():
                await pipe.send_bytes([b"a" * 512])
                state["done"] = True

            task = asyncio.get_running_loop().create_task(produce())
            await asyncio.sleep(0.05)
            assert not state["done"]  # parked: ring is 128B
            buf = bytearray(512)
            await rx.read_into(buf, 512)
            await asyncio.wait_for(task, 5)
            assert state["done"] and bytes(buf) == b"a" * 512
            pipe.close()
            rx.close()

        asyncio.run(go())

    def test_close_wakes_parked_ends_and_unlinks(self):
        async def go():
            pipe, name, peer_db = ShmRingPipe.create(64)
            rx = ShmRingPipe.attach(name, 64, peer_db, producer=False)
            consumer = asyncio.get_running_loop().create_task(
                rx.read_exact(16))
            await asyncio.sleep(0.02)
            rx.close()  # local close must wake the parked read
            with pytest.raises(ConnectionResetError):
                await asyncio.wait_for(consumer, 5)
            pipe.close()
            assert not os.path.exists(f"/dev/shm/{name}")  # unlinked
            # producer blocked on a full ring wakes on ITS close too
            pipe2, name2, peer_db2 = ShmRingPipe.create(64)
            rx2 = ShmRingPipe.attach(name2, 64, peer_db2, producer=False)
            await pipe2.send_bytes([b"z" * 64])
            producer = asyncio.get_running_loop().create_task(
                pipe2.send_bytes([b"z" * 64]))
            await asyncio.sleep(0.02)
            pipe2.close()
            with pytest.raises(ConnectionResetError):
                await asyncio.wait_for(producer, 5)
            rx2.close()
            assert not os.path.exists(f"/dev/shm/{name2}")

        asyncio.run(go())


class TestProcessPool:
    def test_spawn_dump_shutdown_reaps(self):
        pool = ReactorPool("t", 2, mode="process")
        pool.start()
        pids = []
        try:
            for w in pool.workers:
                assert w.is_alive()
                assert w.pid is not None
                pids.append(w.pid)
            assert len(set(pids)) == 2
            d = pool.dump()
            assert all(e["mode"] == "process" and e["pid"] for e in d)
            # stable hash binding holds for process workers too
            w = pool.worker_for(("127.0.0.1", 6800), 2)
            for _ in range(8):
                assert pool.worker_for(("127.0.0.1", 6800), 2) is w
        finally:
            pool.shutdown()
        _assert_reaped(pids)

    def test_ensure_worker_respawns_dead_slot(self):
        pool = ReactorPool("t", 1, mode="process")
        pool.start()
        try:
            w = pool.workers[0]
            old = w.pid
            os.kill(old, signal.SIGKILL)
            import time

            deadline = time.monotonic() + 5
            while w.is_alive() and time.monotonic() < deadline:
                time.sleep(0.01)
            assert pool.ensure_worker(w)
            assert w.pid is not None and w.pid != old
            assert w.respawns == 1
        finally:
            pool.shutdown()

    def test_env_knob_forces_mode(self, monkeypatch):
        monkeypatch.setenv("CEPH_TPU_REACTOR", "process")
        m = Messenger("envtest", {})
        assert m.reactor_mode == "process"
        assert m.reactors is not None and m.reactors.mode == "process"
        monkeypatch.setenv("CEPH_TPU_REACTOR", "thread")
        m2 = Messenger("envtest2", {"ms_reactor_mode": "process"})
        assert m2.reactor_mode == "thread"
        monkeypatch.delenv("CEPH_TPU_REACTOR")
        m3 = Messenger("envtest3", {"ms_reactor_mode": "process"})
        assert m3.reactor_mode == "process"
        m4 = Messenger("envtest4", {})
        assert m4.reactor_mode == "thread"
        assert m4.reactors is None  # thread mode keeps 0 = no pool


class TestProcessDelegation:
    def test_exchange_ordering_and_delegation(self):
        async def go():
            a, b, addr_b = await _pair()
            got = []
            done = asyncio.Event()

            async def disp(conn, msg):
                # dispatch stays on the daemon's single home loop
                assert asyncio.get_running_loop() is b.home_loop
                got.append(msg.seq)
                if len(got) >= 48:
                    done.set()

            b.dispatcher = disp
            for i in range(48):
                await a.send(addr_b, MProc(seq=i, data=b"x" * 4096))
            await asyncio.wait_for(done.wait(), 20)
            assert got == list(range(48))
            # data lanes were actually delegated to worker processes
            da = a.dump_reactors()
            assert da["reactor_mode"] == "process"
            assert all(p for p in da["worker_pids"])
            assert a.perf.get("proc_delegated_conns") >= 2
            agg = da["proc_perf"]
            assert agg.get("tx_bytes", 0) > 48 * 4096
            pids = da["worker_pids"] + b.dump_reactors()["worker_pids"]
            await a.shutdown()
            await b.shutdown()
            return pids

        pids = asyncio.run(go())
        _assert_reaped(pids)

    def test_fragmentation_byte_identity_across_seam(self):
        async def go():
            conf = dict(PCONF, ms_lanes_per_peer=4,
                        ms_lane_stripe_min=64 << 10)
            a, b, addr_b = await _pair(conf, conf)
            payload = os.urandom(2 << 20)
            got = []
            done = asyncio.Event()

            async def disp(conn, msg):
                got.append(bytes(msg.data))
                done.set()

            b.dispatcher = disp
            await a.send(addr_b, MProc(seq=0, data=payload))
            await asyncio.wait_for(done.wait(), 20)
            assert got[0] == payload
            assert a.perf.get("lane_frag_tx") >= 3
            assert b.perf.get("lane_frag_rx") >= 3
            await a.shutdown()
            await b.shutdown()

        asyncio.run(go())

    def test_mixed_modes_interop(self):
        """A process-mode dialer against a thread-mode acceptor (and
        the reverse direction of replies): the wire protocol is
        identical, only the local substrate differs."""
        async def go():
            tconf = {"ms_lanes_per_peer": 3, "ms_async_op_threads": 2}
            a, b, addr_b = await _pair(PCONF, tconf)
            got = []
            done = asyncio.Event()

            async def disp(conn, msg):
                got.append(msg.seq)
                if len(got) >= 24:
                    done.set()

            b.dispatcher = disp
            for i in range(24):
                await a.send(addr_b, MProc(seq=i, data=b"m" * 2048))
            await asyncio.wait_for(done.wait(), 20)
            assert got == list(range(24))
            assert b.reactor_mode == "thread"
            await a.shutdown()
            await b.shutdown()

        asyncio.run(go())


class TestProcessFaultParity:
    def test_socket_failures_exactly_once_in_order(self):
        """Satellite: ms_inject_socket_failures parity on the process
        arm — exactly-once, total data-plane order, byte-identical."""
        async def go():
            conf = dict(PCONF, ms_inject_socket_failures=40)
            a, b, addr_b = await _pair(conf, conf)
            got = []
            done = asyncio.Event()
            N = 96
            blob = os.urandom(8192)

            async def disp(conn, msg):
                assert bytes(msg.data) == blob
                got.append((msg.kind, msg.seq))
                if len(got) >= N:
                    done.set()

            b.dispatcher = disp
            for i in range(N):
                await a.send(addr_b, MProc(seq=i, kind="ab"[i % 2],
                                           data=blob))
            await asyncio.wait_for(done.wait(), 60)
            assert [s for _, s in got] == list(range(N))
            await a.shutdown()
            await b.shutdown()

        asyncio.run(go())

    def test_dup_frames_plane_survives(self):
        async def go():
            conf = dict(PCONF, ms_inject_dup_frames=3)
            a, b, addr_b = await _pair(conf, conf)
            got = []
            done = asyncio.Event()

            async def disp(conn, msg):
                got.append(msg.seq)
                if len(got) >= 40:
                    done.set()

            b.dispatcher = disp
            for i in range(40):
                await a.send(addr_b, MProc(seq=i, data=b"d" * 4096))
            await asyncio.wait_for(done.wait(), 30)
            # dup injection is scoped to MOSDOp/MOSDOpReply: other
            # planes keep the session's exactly-once here
            assert got[:40] == list(range(40))
            await a.shutdown()
            await b.shutdown()

        asyncio.run(go())

    def test_kill_worker_mid_burst_revives_no_loss(self):
        """Satellite: SIGKILL a worker process mid-burst — the owning
        shard revives in a FRESH worker, replays only its pinned
        frames (no acked-op loss), and shutdown leaves no zombies."""
        async def go():
            a, b, addr_b = await _pair()
            got = []

            async def disp(conn, msg):
                got.append(msg.seq)

            b.dispatcher = disp
            for i in range(8):
                await a.send(addr_b, MProc(seq=i, data=b"z" * 30000))
            await asyncio.sleep(0.4)
            # kill a worker that actually OWNS a delegated lane (the
            # stable hash may have bound both data lanes to one slot)
            d0 = a.dump_reactors()
            owners = [ln["shm"]["worker_pid"] for p in d0["peers"]
                      for ln in p["lanes"] if ln.get("shm")]
            assert owners, "no delegated lane to kill"
            victim = owners[0]
            os.kill(victim, signal.SIGKILL)
            for i in range(8, 32):
                await a.send(addr_b, MProc(seq=i, data=b"z" * 30000))
            deadline = asyncio.get_running_loop().time() + 20
            while len(got) < 32 \
                    and asyncio.get_running_loop().time() < deadline:
                await asyncio.sleep(0.1)
            assert got == list(range(32))
            d = a.dump_reactors()
            assert sum(w.get("respawns", 0) for w in d["workers"]) >= 1
            assert all(w["alive"] for w in d["workers"])
            pids = [victim] + d["worker_pids"] \
                + b.dump_reactors()["worker_pids"]
            await a.shutdown()
            await b.shutdown()
            return pids

        pids = asyncio.run(go())
        _assert_reaped(pids)


class TestWholePlanePerf:
    def test_perf_dump_aggregates_worker_counters(self):
        async def go():
            a, b, addr_b = await _pair()
            done = asyncio.Event()
            got = []

            async def disp(conn, msg):
                if msg.seq < 0:
                    return
                got.append(msg.seq)
                if len(got) >= 16:
                    done.set()

            b.dispatcher = disp
            # open the lanes first and wait until b's workers own them:
            # what reaches b's parent before its lane is delegated is
            # framed there (FrameReceiver), not by a worker
            await a.send(addr_b, MProc(seq=-1))
            deadline = asyncio.get_running_loop().time() + 20
            while b.perf.dump()["proc_delegated_conns"] < 2 \
                    and asyncio.get_running_loop().time() < deadline:
                await asyncio.sleep(0.05)
            for i in range(16):
                await a.send(addr_b, MProc(seq=i, data=b"p" * 8192))
            await asyncio.wait_for(done.wait(), 20)
            # presample folds worker shm counters into the wire set
            pa = a.perf.dump()
            pb = b.perf.dump()
            assert pa["proc_workers"] == 2
            assert pa["proc_delegated_conns"] >= 2
            assert pa["proc_tx_bytes"] > 16 * 8192
            assert pb["proc_rx_frames"] >= 16
            # rx records crossed the seam: the parent's wire counters
            # still carry the frames (decode/dispatch happen here)
            assert pb["rx_msgs"] >= 16
            await a.shutdown()
            await b.shutdown()

        asyncio.run(go())

    def test_dump_reactors_and_renderer(self):
        async def go():
            a, b, addr_b = await _pair()
            done = asyncio.Event()

            async def disp(conn, msg):
                done.set()

            b.dispatcher = disp
            await a.send(addr_b, MProc(seq=0, data=b"r" * 4096))
            await asyncio.wait_for(done.wait(), 20)
            d = a.dump_reactors()
            assert d["reactor_mode"] == "process"
            assert len(d["worker_pids"]) == 2
            shm_lanes = [ln for p in d["peers"]
                         for ln in p["lanes"] if ln.get("shm")]
            assert shm_lanes, "no delegated lane in dump_reactors"
            assert all("rx_ring_fill" in ln["shm"] for ln in shm_lanes)
            from ceph_tpu.tools.ceph import render_reactors

            text = "\n".join(render_reactors(d))
            assert "process mode" in text
            assert "pid" in text
            await a.shutdown()
            await b.shutdown()

        asyncio.run(go())


class TestTeardownCostReturn:
    def test_group_close_returns_fifo_costs_for_delegated_conns(self):
        """Satellite bugfix leg: queued dispatch-throttle costs return
        at teardown on the process plane too (the r13 fix covered the
        in-process ring path)."""
        async def go():
            m = Messenger("t", dict(PCONF))
            group = LaneGroup(m, ("127.0.0.1", 1), "g" * 16, 3,
                              outbound=False, policy=Policy.lossless_peer())

            class _C:  # the slice of Connection rx_push touches
                loop = asyncio.get_running_loop()
                throttle = Throttle("t", 1 << 20)
                lane_group = None
                lane_idx = 1

            conn = _C()
            cost = 4096
            await conn.throttle.get(cost)
            msg = MProc(seq=0, data=b"x")
            msg.gseq = 1  # in-order: lands in the dispatch fifo
            group.rx_push(conn, msg, cost)
            assert conn.throttle.current == cost  # held by the fifo
            await group.close()
            assert conn.throttle.current == 0  # returned at teardown
            await m.shutdown()

        asyncio.run(go())

    def test_read_frame_shm_returns_cost_on_torn_ring(self):
        """A record whose payload dies mid-read (worker death) must
        put its throttle charge back — the serve loop's finally only
        covers costs of frames that RETURNED."""
        async def go():
            from ceph_tpu.rados.reactor_proc import ShmConnEndpoint
            from ceph_tpu.rados.shm_ring import FRAME_HDR, REC_HDR

            m = Messenger("t2", dict(PCONF))
            pipe, name, peer_db = ShmRingPipe.create(4096)
            tx = ShmRingPipe.attach(name, 4096, peer_db, producer=True)
            pipe.as_role(producer=False)

            class _W:
                index = 0
                pid = None

                def send_close(self, conn_id):
                    pass

            ep = ShmConnEndpoint(_W(), 1, pipe, pipe)
            ep.rx = pipe

            class _Conn:
                reader = ep
                throttle = Throttle("t2", 1 << 20)
                lane_group = None
                in_seq = 0
                messenger = m

            from ceph_tpu.rados.messenger import Connection

            conn = _Conn()
            # a frame record claiming a 1000-byte payload, but only the
            # header lands before the producer dies
            rec = FRAME_HDR.pack(9810, 1, 0, 1, 1000, 0)
            await tx.send_bytes([REC_HDR.pack(len(rec), 1), rec])
            read = asyncio.get_running_loop().create_task(
                Connection._read_frame_shm(conn))
            await asyncio.sleep(0.1)
            assert conn.throttle.current == 1000  # charged after hdr
            tx.close()  # producer (worker) dies mid-payload
            with pytest.raises(ConnectionResetError):
                await asyncio.wait_for(read, 5)
            assert conn.throttle.current == 0  # charge returned
            ep.close()
            await m.shutdown()

        asyncio.run(go())


class TestWorkerRxArms:
    def test_zlib_negotiated_conn_verifies_with_zlib(self):
        """Review fix pin: a mixed-host connection negotiates
        zlib frame crcs (messenger._negotiated_crc degrade); the
        worker's burst verifier must then use zlib too — the native
        crc32c pass would refuse every frame and loop the lane through
        BadFrame forever."""
        async def go():
            import socket as socket_mod
            import struct
            import zlib

            from ceph_tpu.rados import reactor_proc as rp
            from ceph_tpu.rados.shm_ring import FRAME_HDR, REC_HDR
            from ceph_tpu.utils import wirepath as _wirepath

            loop = asyncio.get_running_loop()
            feed, sock = socket_mod.socketpair()
            sock.setblocking(False)
            rx_parent, name, peer_db = ShmRingPipe.create(1 << 16)
            rx_parent.as_role(producer=False)
            rx_child = ShmRingPipe.attach(name, 1 << 16, peer_db,
                                          producer=True)
            tx_pipe, tname, tdb = ShmRingPipe.create(1 << 12)
            tx_child = ShmRingPipe.attach(tname, 1 << 12, tdb,
                                          producer=False)
            st = rp._WConn(1, sock, tx_child, rx_child,
                           crc_mode="zlib", leftover_chunks=0)
            from multiprocessing import shared_memory

            ctr_shm = shared_memory.SharedMemory(
                create=True, size=rp.COUNTER_SLOTS * 8)
            ctr = rp._Counters(ctr_shm.buf)
            task = loop.create_task(
                rp._rx_task(st, loop, _wirepath.impl(), ctr))
            # one wire frame with a ZLIB payload crc
            payload = b"p" * 64
            hdr = struct.Struct("<IHHBIQ").pack(
                len(payload), 9810, 1, 0, zlib.crc32(payload), 7)
            feed.sendall(hdr + payload)
            kind, length = await asyncio.wait_for(
                rx_parent.read_record_hdr(), 10)
            assert kind == REC_FRAME, "zlib frame refused by the worker"
            rec = await rx_parent.read_exact(length)
            type_id, _v, _f, seq, plen, _b = FRAME_HDR.unpack(
                rec[:FRAME_HDR.size])
            assert (type_id, seq, plen) == (9810, 7, 64)
            task.cancel()
            feed.close()
            st.close()
            rx_parent.close()
            tx_pipe.close()
            ctr_shm.close()
            ctr_shm.unlink()

        asyncio.run(go())


class TestCrossProcessSeamLint:
    """The new tpu-lint rules (async-safety family, cross-process
    seam): live objects may not ride a shm ring; SharedMemory opens
    pair with close+unlink."""

    @staticmethod
    def _run(src: str):
        from ceph_tpu.tools.lint import async_safety

        return async_safety.check([("fix.py", src)])

    def test_object_payload_flagged(self):
        bad = ("async def f(ring, msg, conn):\n"
               "    await ring.put_record(1, [msg])\n"
               "    await ring.send_bytes([conn])\n")
        found = self._run(bad)
        assert sum(1 for f in found
                   if f.check == "async-safety/shm-ring-payload") == 2

    def test_byte_payload_clean(self):
        good = ("async def f(ring, msg, parts, hdr):\n"
                "    await ring.put_record(1, [hdr, *parts])\n"
                "    await ring.send_bytes([msg.data, bytes(msg.hdr)])\n")
        assert not [f for f in self._run(good)
                    if f.check == "async-safety/shm-ring-payload"]

    def test_shm_open_without_unlink_flagged(self):
        bad = ("from multiprocessing import shared_memory\n"
               "def f():\n"
               "    s = shared_memory.SharedMemory(create=True, size=8)\n"
               "    s.close()\n")
        found = [f for f in self._run(bad)
                 if f.check == "async-safety/shm-lifecycle"]
        assert found and "unlink" in found[0].message

    def test_shm_open_with_pair_clean(self):
        good = ("from multiprocessing import shared_memory\n"
                "def f():\n"
                "    s = shared_memory.SharedMemory(create=True, size=8)\n"
                "    s.close()\n"
                "    s.unlink()\n")
        assert not [f for f in self._run(good)
                    if f.check == "async-safety/shm-lifecycle"]

    def test_shipped_shm_modules_clean(self):
        import pathlib

        from ceph_tpu.tools.lint import async_safety

        root = pathlib.Path(__file__).resolve().parent.parent
        srcs = []
        for rel in ("ceph_tpu/rados/shm_ring.py",
                    "ceph_tpu/rados/reactor_proc.py"):
            srcs.append((rel, (root / rel).read_text()))
        assert not [f for f in async_safety.check(srcs)
                    if f.check.startswith("async-safety/shm")]


class TestProcessModeE2E:
    def test_cluster_put_get_byte_identity(self):
        """A small EC cluster entirely on the process plane: put/get
        byte-identity over real TCP with delegated data lanes."""
        async def go():
            import numpy as np

            from ceph_tpu.rados.vstart import Cluster

            cluster = Cluster(n_osds=3, conf={
                "osd_auto_repair": False,
                "ms_local_fastpath": False,
                "ms_colocated_ring": False,
                "ms_reactor_mode": "process",
                "ms_lanes_per_peer": 3,
                "ms_async_op_threads": 2})
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("p", profile={
                    "plugin": "jerasure", "technique": "reed_sol_van",
                    "k": "2", "m": "1"})
                payload = np.random.default_rng(11).integers(
                    0, 256, 2 << 20, dtype=np.uint8).tobytes()
                await c.put(pool, "obj", payload)
                got = await c.get(pool, "obj")
                assert bytes(got) == payload
                # the plane actually engaged on some daemon
                engaged = any(
                    (o.messenger.dump_reactors().get("proc_perf") or {})
                    .get("conns", 0) > 0
                    for o in cluster.osds.values())
                assert engaged or (c.messenger.dump_reactors()
                                   .get("proc_perf") or {}).get("conns")
                await c.stop()
            finally:
                await cluster.stop()

        asyncio.run(go())
