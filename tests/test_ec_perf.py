"""EC data-plane observability (ISSUE 2): the `ec_tpu` / `pagestore` /
`gf2_sched` / `wire` counter sets, the dispatch timeline admin command,
trace-span propagation through the batching queue, the `perf reset`
command, and the mgr prometheus histogram rendering."""

import asyncio
import time

import numpy as np
import pytest

from ceph_tpu.common.context import Context
from ceph_tpu.common.perf_counters import (PerfCountersBuilder,
                                           PerfCountersCollection)
from ceph_tpu.common.tracing import Tracer
from ceph_tpu.ec.matrices import matrix_to_bitmatrix, vandermonde_coding_matrix
from ceph_tpu.ec.registry import registry
from ceph_tpu.parallel.service import (LANES, BatchingQueue,
                                       subchunk_geometry)
from ceph_tpu.rados.pagestore import PagedResidentStore

K, M, W = 2, 1, 8
B = 1024  # pow2, multiple of 32: every lane accepts it unmodified


def _bm(dtype=np.int8) -> np.ndarray:
    return matrix_to_bitmatrix(
        vandermonde_coding_matrix(K, M, W), W).astype(dtype)


def _rows(rng=None) -> np.ndarray:
    rng = rng or np.random.default_rng(7)
    return rng.integers(0, 256, size=(K, B), dtype=np.uint8)


# -- satellite: PerfCounters primitives --------------------------------------


class TestPerfCounterPrimitives:
    def test_time_avg_records_even_on_raise(self):
        pc = (PerfCountersBuilder("t").add_time_avg("lat")
              .create_perf_counters())
        with pc.time_avg("lat"):
            pass
        with pytest.raises(ValueError):
            with pc.time_avg("lat"):
                raise ValueError("boom")
        count, total = pc.get("lat")
        assert count == 2 and total >= 0.0

    def test_ensure_declares_dynamic_counters_idempotently(self):
        pc = PerfCountersBuilder("t").create_perf_counters()
        pc.ensure("tx_MTest")
        pc.ensure("tx_MTest")  # idempotent
        pc.inc("tx_MTest", 3)
        assert pc.dump()["tx_MTest"] == 3

    def test_reset_zeroes_every_kind(self):
        pc = (PerfCountersBuilder("t").add_u64("g").add_time_avg("lat")
              .add_histogram("h").create_perf_counters())
        pc.set("g", 9)
        pc.tinc("lat", 1.5)
        pc.hinc("h", 12)
        pc.reset()
        d = pc.dump()
        assert d["g"] == 0
        assert d["lat"] == {"avgcount": 0, "sum": 0.0}
        assert d["h"]["count"] == 0 and not any(d["h"]["buckets"])

    def test_collection_reset_by_name_and_all(self):
        coll = PerfCountersCollection()
        a = coll.add(PerfCountersBuilder("a").add_u64("x")
                     .create_perf_counters())
        b = coll.add(PerfCountersBuilder("b").add_u64("x")
                     .create_perf_counters())
        a.inc("x"), b.inc("x")
        assert coll.reset("a") == ["a"]
        assert a.get("x") == 0 and b.get("x") == 1
        assert sorted(coll.reset("all")) == ["a", "b"]
        assert b.get("x") == 0
        assert coll.reset("nope") == []


# -- ec_tpu: per-lane counters, flush causes, latency, timeline --------------


class TestEcTpuCounters:
    def test_every_lane_counts_submits_bytes_and_dispatches(self):
        q = BatchingQueue(max_delay=60.0)  # worker idle: flush() drives
        try:
            rows = _rows()
            # B is whole W*16-byte blocks for the packet-layout lane, and
            # one chunk of 4 sub-chunks for the sub-chunk lane, whose
            # request carries a code's geometry (clay k=2 m=2 d=3)
            clay = registry.factory("clay", "", {"plugin": "clay", "k": "2",
                                                 "m": "2", "d": "3"})
            futs = [q.submit(subchunk_geometry(*clay.encode_geometry()),
                             rows, W, 2, lane, B) if lane == "subchunk"
                    else
                    q.submit(_bm(np.int8 if lane in ("packed", "resident")
                                 else np.uint8),
                             rows, W, M, lane,
                             16 if lane == "packetrows" else 0)
                    for lane in LANES]
            q.flush()
            for f in futs:
                f.result(timeout=120)
            d = q.perf.dump()
            for lane in LANES:
                assert d[f"submit_{lane}"] == 1, lane
                # every lane counts PACKED bytes: K rows x B
                assert d[f"bytes_{lane}"] == K * B, lane
            assert d["submit"] == len(LANES)
            # distinct (matrix-dtype, lane) groups: a dispatch a lane
            assert d["dispatch"] == len(LANES)
            assert d["flush_forced"] == 1  # ONE flush() drained them all
            assert d["dispatch_dev"]["avgcount"] == len(LANES)
            assert d["queue_wait"]["avgcount"] == len(LANES)
            assert d["group_size"]["count"] == len(LANES)
            # the legacy bare-int views read through to the perf set
            assert q.submits == len(LANES)
            assert q.dispatches == len(LANES)
            assert q.bytes_dispatched == d["bytes"] > 0
        finally:
            q.close()

    def test_flush_cause_delay_and_bytes(self):
        bm8 = _bm()
        q = BatchingQueue(max_delay=0.005)
        try:
            q.submit(bm8, _rows(), W, M).result(timeout=120)
            assert q.perf.get("flush_delay") >= 1
        finally:
            q.close()
        q = BatchingQueue(max_pending_bytes=1, max_delay=60.0)
        try:
            q.submit(bm8, _rows(), W, M).result(timeout=120)
            assert q.perf.get("flush_bytes") >= 1
        finally:
            q.close()

    def test_timeline_via_admin_socket_execute(self):
        ctx = Context("osd.test")
        q = BatchingQueue(max_delay=60.0)
        try:
            q.register_asok(ctx.asok)
            bm8 = _bm()
            for _ in range(3):
                f = q.submit(bm8, _rows(), W, M)
                q.flush()
                f.result(timeout=120)
            got = ctx.asok.execute("dump_ec_batch_timeline")
            assert len(got) == 3
            rec = got[0]  # most recent first
            assert rec["lane"] == "packed"
            assert rec["group_size"] == 1
            assert rec["bytes"] == K * B
            assert rec["device_s"] >= 0 and rec["queue_wait_s"] >= 0
            assert ctx.asok.execute("dump_ec_batch_timeline", count=2) \
                == got[:2]
        finally:
            q.close()

    def test_perf_reset_admin_command(self):
        ctx = Context("osd.test")
        q = BatchingQueue(max_delay=60.0)
        try:
            ctx.perf.add(q.perf)
            f = q.submit(_bm(), _rows(), W, M)
            q.flush()
            f.result(timeout=120)
            assert ctx.perf.dump()["ec_tpu"]["submit"] == 1
            out = ctx.asok.execute("perf reset", name="ec_tpu")
            assert out["success"] and out["reset"] == ["ec_tpu"]
            d = ctx.perf.dump()["ec_tpu"]
            assert d["submit"] == 0 and d["dispatch"] == 0
            assert d["queue_wait"]["avgcount"] == 0
        finally:
            q.close()

    def test_spans_thread_submit_coalesce_dispatch_fanout(self):
        tracer = Tracer()
        q = BatchingQueue(max_delay=60.0)
        try:
            span = tracer.new_trace("ec write")
            f = q.submit(_bm(), _rows(), W, M, span=span)
            q.flush()
            f.result(timeout=120)
            span.finish()
            events = [e["event"] for e in span.events]
            assert "ec submit lane=packed" in events
            assert any(e.startswith("ec coalesced lane=packed")
                       for e in events)
            assert "ec fan-out lane=packed" in events
            dumped = tracer.dump()
            child = next(s for s in dumped
                         if s["name"] == "ec batch dispatch")
            assert child["trace_id"] == span.trace_id
            assert child["parent_id"] == span.span_id
            assert child["tags"] == {"lane": "packed", "group_size": 1,
                                     "bytes": K * B}
            child_events = [e["event"] for e in child["events"]]
            assert child_events == ["launched", "fan-out"]
        finally:
            q.close()

    def test_queue_tracer_roots_orphan_dispatches(self):
        tracer = Tracer()
        q = BatchingQueue(max_delay=60.0)
        try:
            q.tracer = tracer  # the OSD attaches its ctx tracer this way
            f = q.submit(_bm(), _rows(), W, M)  # no submitter span
            q.flush()
            f.result(timeout=120)
            names = [s["name"] for s in tracer.dump()]
            assert "ec batch dispatch" in names
        finally:
            q.close()


# -- gf2_sched: schedule-cache accounting ------------------------------------


class TestScheduleCacheCounters:
    def _delta(self, fn):
        from ceph_tpu.ops.gf2 import SCHED_PERF

        before = SCHED_PERF.dump()
        fn()
        after = SCHED_PERF.dump()
        return {k: after[k] - before[k]
                for k in ("hit", "miss", "evict", "compile",
                          "xor_ops_naive", "xor_ops_final")}

    def test_hit_miss_compile_accounting(self):
        from ceph_tpu.ops.gf2 import gf2_xor_packed

        rng = np.random.default_rng(123)
        bm = rng.integers(0, 2, size=(8, 16), dtype=np.uint8)
        bm[0, :3] = 1  # at least one nontrivial row
        planes = np.zeros((16, 4), dtype=np.uint32)

        d = self._delta(lambda: (gf2_xor_packed(bm, planes),
                                 gf2_xor_packed(bm, planes)))
        assert d["miss"] == 1 and d["compile"] == 1
        assert d["hit"] == 1
        assert 0 < d["xor_ops_final"] <= d["xor_ops_naive"]

    def test_lru_eviction_counts(self, monkeypatch):
        from ceph_tpu.ops import gf2

        monkeypatch.setattr(gf2, "_XOR_SCHEDULE_CAPACITY", 2)
        rng = np.random.default_rng(99)
        mats = [rng.integers(0, 2, size=(8, 8), dtype=np.uint8) | np.eye(
            8, dtype=np.uint8) for _ in range(3)]
        planes = np.zeros((8, 2), dtype=np.uint32)

        def go():
            for bm in mats:
                gf2.gf2_xor_packed(bm, planes)

        d = self._delta(go)
        assert d["miss"] == 3 and d["compile"] == 3
        assert d["evict"] >= 1
        assert gf2.SCHED_PERF.get("entries") <= 2


# -- pagestore: residency stats ----------------------------------------------


class TestResidentStoreCounters:
    def test_admit_hit_miss_and_boundary_latencies(self):
        store = PagedResidentStore(capacity_bytes=64 << 20)
        rows = _rows()
        store.admit("obj1", rows, w=W)
        assert store.read("obj1") is not None
        assert store.read("absent") is None
        d = store.perf.dump()
        assert d["admit"] == 1 and d["hit"] == 1 and d["miss"] == 1
        assert d["entries"] == 1
        assert d["resident_bytes"] == store.resident_bytes > 0
        assert d["unpack_s"]["avgcount"] == 1  # one admit boundary
        assert d["pack_s"]["avgcount"] == 1  # one read boundary

    def test_eviction_updates_counters_and_gauges(self):
        rows = _rows()
        planar_sz = K * W * B  # int8 planes: w bytes per packed byte
        store = PagedResidentStore(
            capacity_bytes=planar_sz + planar_sz // 2, page_bytes=4096)
        store.admit("a", rows, w=W)
        store.admit("b", rows, w=W)  # over budget: "a" evicts
        d = store.perf.dump()
        assert d["evict"] == 1
        assert d["entries"] == 1
        assert "a" not in store and "b" in store
        store.drop("b")
        d = store.perf.dump()
        assert d["entries"] == 0 and d["resident_bytes"] == 0


# -- wire: messenger framing vs io split -------------------------------------

from ceph_tpu.rados.messenger import Messenger, message  # noqa: E402


@message(901)
class MPerfTest:
    text: str = ""


@message(902)
class MPerfLocal:
    text: str = ""


class TestWireCounters:
    def test_round_trip_counts_and_latency_split(self):
        async def go():
            server = Messenger("server", {}, entity_type="osd")
            client = Messenger("client", {}, entity_type="osd")
            addr = await server.bind()
            got = asyncio.Queue()

            async def dispatch(conn, msg):
                await got.put(msg)

            server.dispatcher = dispatch
            await client.send(addr, MPerfTest(text="hello"))
            await asyncio.wait_for(got.get(), 2)
            tx, rx = client.perf.dump(), server.perf.dump()
            assert tx["tx_msgs"] == 1 and tx["tx_bytes"] > 0
            assert tx["tx_MPerfTest"] == 1
            assert tx["tx_bytes_MPerfTest"] == tx["tx_bytes"]
            assert tx["tx_framing"]["avgcount"] == 1
            assert tx["tx_io"]["avgcount"] == 1
            assert rx["rx_msgs"] == 1
            assert rx["rx_MPerfTest"] == 1
            assert rx["rx_bytes"] >= tx["tx_bytes"]
            assert rx["rx_framing"]["avgcount"] == 1
            assert rx["rx_io"]["avgcount"] >= 1
            await client.shutdown()
            await server.shutdown()

        asyncio.run(go())

    def test_local_fastpath_counts_handoffs_not_frames(self):
        async def go():
            conf = {"ms_local_fastpath": True}
            server = Messenger("server", conf, entity_type="osd")
            client = Messenger("client", conf, entity_type="osd")
            addr = await server.bind()
            got = asyncio.Queue()

            async def dispatch(conn, msg):
                await got.put(msg)

            server.dispatcher = dispatch
            await client.send(addr, MPerfLocal(text="hi"))
            await asyncio.wait_for(got.get(), 2)
            d = client.perf.dump()
            assert d["local_msgs"] == 1
            assert d["tx_msgs"] == 0  # no framing happened
            await client.shutdown()
            await server.shutdown()

        asyncio.run(go())


# -- mgr prometheus: histogram rendering -------------------------------------


class TestPrometheusHistograms:
    def test_buckets_render_cumulative_with_sum_and_count(self):
        from ceph_tpu.mgr.daemon import MgrDaemon, MMgrReport

        pc = (PerfCountersBuilder("ec_tpu").add_u64_counter("submit")
              .add_time_avg("queue_wait").add_histogram("group_size")
              .create_perf_counters())
        pc.inc("submit", 5)
        pc.tinc("queue_wait", 0.25)
        for v in (1, 3, 7, 130):
            pc.hinc("group_size", v)
        mgr = MgrDaemon()
        mgr.reports["osd.0"] = MMgrReport(
            name="osd.0", perf={"ec_tpu": pc.dump()}, status={}, stamp=0.0)
        text = mgr.prometheus_text()
        assert "# TYPE ceph_ec_tpu_group_size histogram" in text
        # le bounds are the LARGEST member of each pow2 slot (2^i - 1):
        # bucket{le=x} must count every observation <= x, including exact
        # powers of two
        assert 'ceph_ec_tpu_group_size_bucket{daemon="osd.0",le="1"} 1' \
            in text
        assert 'ceph_ec_tpu_group_size_bucket{daemon="osd.0",le="7"} 3' \
            in text
        assert ('ceph_ec_tpu_group_size_bucket{daemon="osd.0",le="255"} 4'
                in text)
        assert ('ceph_ec_tpu_group_size_bucket{daemon="osd.0",le="+Inf"} 4'
                in text)
        # trailing always-empty buckets are elided, not rendered
        assert 'le="511"' not in text
        assert 'ceph_ec_tpu_group_size_sum{daemon="osd.0"} 141.0' in text
        assert 'ceph_ec_tpu_group_size_count{daemon="osd.0"} 4' in text
        # scalars and longrunavgs unchanged alongside
        assert 'ceph_ec_tpu_submit{daemon="osd.0"} 5' in text
        assert 'ceph_ec_tpu_queue_wait_count{daemon="osd.0"} 1' in text

    def test_empty_histogram_renders_inf_bucket_only(self):
        from ceph_tpu.mgr.daemon import MgrDaemon, MMgrReport

        pc = (PerfCountersBuilder("s").add_histogram("h")
              .create_perf_counters())
        mgr = MgrDaemon()
        mgr.reports["osd.1"] = MMgrReport(
            name="osd.1", perf={"s": pc.dump()}, status={}, stamp=0.0)
        text = mgr.prometheus_text()
        assert 'ceph_s_h_bucket{daemon="osd.1",le="+Inf"} 0' in text
        assert 'ceph_s_h_count{daemon="osd.1"} 0' in text


# -- end to end: perf dump on an OSD after EC traffic ------------------------


class TestOsdPerfDumpEndToEnd:
    def test_perf_dump_carries_pipeline_sets_after_ec_traffic(
            self, monkeypatch, force_batching):
        import os

        from ceph_tpu.rados.vstart import Cluster

        monkeypatch.setenv("CEPH_TPU_BATCH_DELAY", "0.05")

        async def go():
            cluster = Cluster(n_osds=3, conf={
                "osd_auto_repair": False, "client_op_timeout": 60.0})
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("perf", profile={
                    "plugin": "jerasure", "technique": "reed_sol_van",
                    "k": "2", "m": "1"})
                blob = os.urandom(8192)
                await c.put(pool, "o", blob)
                assert await c.get(pool, "o") == blob
                osd = next(iter(cluster.osds.values()))
                # the queue's worker fans a dispatch out BEFORE it books
                # it (dispatch_dev, group_size, the timeline, in that
                # order), so the put can return first: wait for the books
                for _ in range(200):
                    if osd._ec_queue.timeline:
                        break
                    await asyncio.sleep(0.05)
                d = osd.ctx.perf.dump()
                # ONE dump carries the whole pipeline: queue lanes,
                # schedule cache, residency store, wire split
                assert d["ec_tpu"]["submit"] > 0
                assert any(d["ec_tpu"][f"submit_{ln}"] for ln in LANES)
                assert d["ec_tpu"]["dispatch_dev"]["avgcount"] > 0
                assert "gf2_sched" in d
                assert "ec_plugin" in d
                assert "pagestore" in d
                wire = d["wire"]
                assert wire["rx_msgs"] + wire["local_msgs"] > 0
                tl = osd.ctx.asok.execute("dump_ec_batch_timeline")
                assert tl and tl[0]["group_size"] >= 1
                await c.stop()
            finally:
                await cluster.stop()

        asyncio.run(asyncio.wait_for(go(), 120))
