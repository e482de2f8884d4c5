"""Objecter-grade op resilience (reference src/osdc/Objecter.cc +
src/messages/MOSDBackoff.h): resend pacing, MOSDBackoff park/release,
paused-map queueing, duplicate-delivery reqid dedup, and the
BatchingQueue device-dispatch circuit breaker."""

import asyncio
import os
import time

import numpy as np
import pytest

from ceph_tpu.rados.client import RadosClient
from ceph_tpu.rados.types import MOSDBackoff
from ceph_tpu.rados.vstart import Cluster

CONF = {
    "mon_osd_report_grace": 0.8,
    "osd_heartbeat_interval": 0.2,
    "osd_repair_delay": 0.2,
    "client_op_timeout": 2.0,
    "client_op_deadline": 12.0,
}

PROFILE = {"plugin": "jerasure", "technique": "reed_sol_van",
           "k": "2", "m": "1"}


def run(coro, timeout=90):
    asyncio.run(asyncio.wait_for(coro, timeout))


def _locate(c, pool, oid):
    p = c.osdmap.pools[pool]
    pg = c.osdmap.object_to_pg(p, oid)
    acting = c.osdmap.pg_to_acting(p, pg)
    primary = c.osdmap.primary_of(acting, seed=(pool << 20) | pg)
    return p, pg, acting, primary


class TestRetrySchedule:
    def test_capped_exponential_with_jitter(self):
        """The retry pacing contract: min(base * 2^k, cap) scaled by a
        uniform [0.5, 1.5) jitter draw — exponential up to the cap, and
        never degenerate (zero) pauses."""
        c = RadosClient(("127.0.0.1", 1),
                        {"client_backoff_base": 0.1,
                         "client_backoff_cap": 2.0})
        for attempt in range(10):
            base = min(0.1 * (2 ** attempt), 2.0)
            samples = [c._retry_pause(attempt) for _ in range(200)]
            assert min(samples) >= base * 0.5 - 1e-9, (attempt, min(samples))
            assert max(samples) < base * 1.5 + 1e-9, (attempt, max(samples))
        # the cap holds: attempt 30 pauses no longer than the cap * 1.5
        assert c._retry_pause(30) < 2.0 * 1.5 + 1e-9

    def test_deadline_defaults_scale_with_op_timeout(self):
        c = RadosClient(("127.0.0.1", 1), {"client_op_timeout": 20.0})
        assert c.op_deadline == 60.0
        c = RadosClient(("127.0.0.1", 1), {"client_op_timeout": 1.0})
        assert c.op_deadline == 15.0  # floor
        c = RadosClient(("127.0.0.1", 1), {"client_op_deadline": 7.5})
        assert c.op_deadline == 7.5


class TestBackoffParkRelease:
    def test_block_parks_until_unblock_and_order_holds(self):
        """A block for the op's PG parks it (no completion, no failure);
        the unblock releases it — park BEFORE release, completion only
        AFTER release (the MOSDBackoff contract)."""
        async def go():
            cluster = Cluster(n_osds=3, conf=dict(CONF))
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("bk", profile=dict(PROFILE))
                await c.put(pool, "obj", b"a" * 2000)
                p, pg, acting, primary = _locate(c, pool, "obj")
                # inject the block exactly as the wire would deliver it
                await c._dispatch(None, MOSDBackoff(
                    op="block", pool_id=pool, pg=pg, id="b1",
                    epoch=c.osdmap.epoch, duration=30.0))
                assert c.perf.get("backoffs_received") == 1
                t = asyncio.get_running_loop().create_task(
                    c.put(pool, "obj", b"b" * 2000))
                await asyncio.sleep(0.5)
                assert not t.done(), "op completed through an active block"
                released_at = time.monotonic()
                await c._dispatch(None, MOSDBackoff(
                    op="unblock", pool_id=pool, pg=pg, id="b1",
                    epoch=c.osdmap.epoch))
                await asyncio.wait_for(t, timeout=10)
                assert time.monotonic() >= released_at
                assert c.perf.get("backoffs_released") == 1
                count, total = c.perf.get("backoff_wait_s")
                assert count >= 1 and total >= 0.4, (count, total)
                assert await c.get(pool, "obj") == b"b" * 2000
                await c.stop()
            finally:
                await cluster.stop()

        run(go())

    def test_new_block_displaces_old_and_releases_parked_ops(self):
        """A block from a NEW interval (different id) replaces the old
        entry; ops parked on the displaced event must wake and re-park
        on the new block — not sleep out the dead entry's expiry."""
        async def go():
            cluster = Cluster(n_osds=3, conf=dict(CONF))
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("bk4", profile=dict(PROFILE))
                await c.put(pool, "obj", b"a" * 1000)
                p, pg, acting, primary = _locate(c, pool, "obj")
                await c._dispatch(None, MOSDBackoff(
                    op="block", pool_id=pool, pg=pg, id="old",
                    epoch=c.osdmap.epoch, duration=30.0))
                t = asyncio.get_running_loop().create_task(
                    c.put(pool, "obj", b"b" * 1000))
                await asyncio.sleep(0.3)
                assert not t.done()
                # new interval's block displaces the old one
                await c._dispatch(None, MOSDBackoff(
                    op="block", pool_id=pool, pg=pg, id="new",
                    epoch=c.osdmap.epoch, duration=30.0))
                await asyncio.sleep(0.3)
                assert not t.done(), "op escaped through the block swap"
                # releasing the NEW block releases the op (the old
                # block's 30s expiry must not still be holding it)
                await c._dispatch(None, MOSDBackoff(
                    op="unblock", pool_id=pool, pg=pg, id="new",
                    epoch=c.osdmap.epoch))
                await asyncio.wait_for(t, timeout=5)
                await c.stop()
            finally:
                await cluster.stop()

        run(go())

    def test_block_expiry_is_the_liveness_bound(self):
        """A lost unblock must not park ops forever: the block's
        duration caps the park, after which the op resends anyway."""
        async def go():
            cluster = Cluster(n_osds=3, conf=dict(CONF))
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("bk2", profile=dict(PROFILE))
                await c.put(pool, "obj", b"a" * 1000)
                p, pg, acting, primary = _locate(c, pool, "obj")
                await c._dispatch(None, MOSDBackoff(
                    op="block", pool_id=pool, pg=pg, id="b1",
                    epoch=c.osdmap.epoch, duration=0.5))
                t0 = time.monotonic()
                await c.put(pool, "obj", b"c" * 1000)  # no unblock ever
                assert time.monotonic() - t0 >= 0.4
                assert c.perf.get("backoffs_released") == 0
                await c.stop()
            finally:
                await cluster.stop()

        run(go())

    def test_osd_blocks_mutations_while_peering_after_failover(self):
        """End to end: a PG whose machine is mid-peering in a failover
        interval (unknown prior primary) BLOCKS mutations via
        MOSDBackoff and releases them when peering completes."""
        async def go():
            # the op deadline must comfortably outlast this test's own
            # timeline (0.6s forge window + a get + the 10s release
            # wait): under full-suite load a slow get let the 12s
            # deadline expire while the put was still parked, failing
            # the op with the backoff error instead of releasing it
            conf = dict(CONF, client_op_deadline=40.0)
            cluster = Cluster(n_osds=4, conf=conf)
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("bk3", profile=dict(PROFILE))
                await c.put(pool, "obj", b"a" * 3000)
                p, pg, acting, primary = _locate(c, pool, "obj")
                prim = cluster.osds[primary]
                key = (pool, pg)
                # forge the dangerous window: peering in progress, prior
                # interval's primary unknown (failover)
                m = prim._machine(pool, pg)
                m.state = "GetInfo"
                m.task = asyncio.get_running_loop().create_task(
                    asyncio.sleep(30))
                prim._prior_acting[key] = []
                t = asyncio.get_running_loop().create_task(
                    c.put(pool, "obj", b"b" * 3000))
                await asyncio.sleep(0.6)
                assert not t.done(), "mutation served mid-failover-peering"
                assert prim.perf.get("backoffs_sent") >= 1
                assert c.perf.get("backoffs_received") >= 1
                # reads are NOT gated by the peering window
                assert await c.get(pool, "obj") == b"a" * 3000
                # peering "completes": release the block
                m.task.cancel()
                m.task = None
                m.state = "Active"
                prim._release_backoffs(key)
                await asyncio.wait_for(t, timeout=10)
                assert prim.perf.get("backoffs_released") >= 1
                assert await c.get(pool, "obj") == b"b" * 3000
                await c.stop()
            finally:
                await cluster.stop()

        run(go())


class TestPausedMap:
    def test_pausewr_queues_writes_reads_flow(self):
        async def go():
            cluster = Cluster(n_osds=3, conf=dict(CONF))
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("pw", profile=dict(PROFILE))
                await c.put(pool, "a", b"x" * 1000)
                await c.osd_set_flag("pausewr", True)
                assert "pausewr" in c.osdmap.flags
                # reads flow
                assert await c.get(pool, "a") == b"x" * 1000
                # writes queue, not fail
                t = asyncio.get_running_loop().create_task(
                    c.put(pool, "b", b"y" * 500))
                await asyncio.sleep(0.6)
                assert not t.done(), "write completed through pausewr"
                assert c.perf.get("paused_ops") == 1
                await c.osd_set_flag("pausewr", False)
                await asyncio.wait_for(t, timeout=10)
                assert await c.get(pool, "b") == b"y" * 500
                await c.stop()
            finally:
                await cluster.stop()

        run(go())

    def test_pausewr_gates_class_calls_too(self):
        """op="call" mutates via object classes (cls_rbd/cls_rgw
        metadata): it must freeze under pausewr like any write."""
        async def go():
            from ceph_tpu.rados.client import RadosError
            from ceph_tpu.rados.types import MOSDOp

            cluster = Cluster(n_osds=3, conf=dict(CONF))
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("cls", profile=dict(PROFILE))
                await c.put(pool, "obj", b"x" * 500)
                await c.osd_set_flag("pausewr", True)
                t = asyncio.get_running_loop().create_task(c._op(MOSDOp(
                    op="call", pool_id=pool, oid="obj",
                    cls="version", method="read")))
                await asyncio.sleep(0.5)
                assert not t.done(), "class call ran through pausewr"
                await c.osd_set_flag("pausewr", False)
                # EC pools answer calls with a definitive EOPNOTSUPP —
                # what matters is the op RAN only after the unpause
                try:
                    await asyncio.wait_for(t, timeout=10)
                except RadosError:
                    pass
                await c.stop()
            finally:
                await cluster.stop()

        run(go())

    def test_full_flag_gates_writes_and_pauserd_gates_reads(self):
        async def go():
            cluster = Cluster(n_osds=3, conf=dict(CONF))
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("fl", profile=dict(PROFILE))
                await c.put(pool, "a", b"x" * 800)
                await c.osd_set_flag("full", True)
                tw = asyncio.get_running_loop().create_task(
                    c.put(pool, "b", b"z" * 100))
                await asyncio.sleep(0.4)
                assert not tw.done(), "write completed through full flag"
                assert await c.get(pool, "a") == b"x" * 800  # reads flow
                await c.osd_set_flag("full", False)
                await asyncio.wait_for(tw, timeout=10)
                await c.osd_set_flag("pauserd", True)
                tr = asyncio.get_running_loop().create_task(
                    c.get(pool, "a"))
                await asyncio.sleep(0.4)
                assert not tr.done(), "read completed through pauserd"
                await c.osd_set_flag("pauserd", False)
                assert await asyncio.wait_for(tr, timeout=10) == b"x" * 800
                await c.stop()
            finally:
                await cluster.stop()

        run(go())


class TestDupFrameDedup:
    def test_every_op_duplicated_executes_once(self):
        """ms_inject_dup_frames=1: EVERY client-plane message is
        delivered twice (fresh seqs, so the messenger cannot filter
        them).  The PG log's reqid dedup must absorb the op duplicates
        and the client's pop-once futures the reply duplicates — each
        logical write executes exactly once."""
        async def go():
            cluster = Cluster(n_osds=3, conf={**CONF,
                                              "ms_inject_dup_frames": 1})
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("dup", profile=dict(PROFILE))
                blobs = {}
                for i in range(6):
                    blob = os.urandom(2000 + i)
                    await c.put(pool, f"o{i}", blob)
                    blobs[f"o{i}"] = blob
                for oid, blob in blobs.items():
                    assert await c.get(pool, oid) == blob
                # every log holds each reqid AT MOST once (dup absorbed)
                p = c.osdmap.pools[pool]
                for osd in cluster.osds.values():
                    for pg in range(p.pg_num):
                        log = osd._pglog(pool, pg)
                        reqids = [e.reqid for e in log.entries if e.reqid]
                        assert len(reqids) == len(set(reqids)), \
                            f"duplicate reqid executed on osd{osd.osd_id}"
                await c.stop()
            finally:
                await cluster.stop()

        run(go())


class TestDispatchBreaker:
    """The BatchingQueue device-dispatch watchdog: trip on slow/raising
    dispatch, byte-identical CPU failover, half-open re-probe."""

    def _queue(self):
        from ceph_tpu.parallel.service import BatchingQueue

        q = BatchingQueue(max_delay=0.001, mesh=False)
        q.dispatch_timeout = 30.0
        return q

    def _payload(self):
        from ceph_tpu.ec.matrices import (matrix_to_bitmatrix,
                                          vandermonde_coding_matrix)

        bm = matrix_to_bitmatrix(
            vandermonde_coding_matrix(4, 2, 8), 8).astype(np.int8)
        regions = np.random.default_rng(3).integers(
            0, 256, (4, 4096), dtype=np.uint8)
        from ceph_tpu.ops.gf2 import gf2_apply_bytes

        expect = np.asarray(gf2_apply_bytes(bm, regions, 8, 2))
        return bm, regions, expect

    def test_slow_dispatch_trips_then_cpu_serves_then_probe_recovers(self):
        q = self._queue()
        try:
            bm, regions, expect = self._payload()
            # healthy
            assert np.array_equal(
                q.submit(bm, regions, 8, 2).result(timeout=60), expect)
            assert q.perf.get("breaker_trip") == 0
            # injected slow dispatch blows the watchdog budget: the
            # results still land (byte-identical) but the lane trips
            q.dispatch_timeout = 0.05
            q.inject_dispatch_delay = 0.12
            assert np.array_equal(
                q.submit(bm, regions, 8, 2).result(timeout=60), expect)
            # the queue's thread sets the result BEFORE it trips the lane
            deadline = time.monotonic() + 5
            while not q.perf.get("breaker_trip") \
                    and time.monotonic() < deadline:
                time.sleep(0.01)
            assert q.perf.get("breaker_trip") == 1
            assert q.perf.get("breaker_open_lanes") == 1
            # while open: the CPU path serves, byte-identical
            q.inject_dispatch_delay = 0.0
            with q._breaker_lock:
                q._breakers["packed"].open_until = time.monotonic() + 60
            assert np.array_equal(
                q.submit(bm, regions, 8, 2).result(timeout=60), expect)
            assert q.perf.get("breaker_fallback") >= 1
            # cooldown elapsed: ONE half-open probe re-engages the device
            with q._breaker_lock:
                q._breakers["packed"].open_until = time.monotonic() - 1
            assert np.array_equal(
                q.submit(bm, regions, 8, 2).result(timeout=60), expect)
            assert q.perf.get("breaker_probe") == 1
            assert q.perf.get("breaker_recover") == 1
            assert q.perf.get("breaker_open_lanes") == 0
        finally:
            q.close()

    def test_raising_dispatch_is_rescued_not_failed(self):
        """A device launch that raises must resolve the submitters'
        futures with the CPU result — ops never see the device die."""
        q = self._queue()
        try:
            bm, regions, expect = self._payload()

            def boom(_g):
                raise RuntimeError("device dead")

            q._launch = boom
            got = q.submit(bm, regions, 8, 2).result(timeout=60)
            assert np.array_equal(got, expect)
            assert q.perf.get("breaker_trip") == 1
            assert q.perf.get("breaker_fallback") == 1
            # timeline records the failover
            assert any(rec.get("cpu_fallback")
                       for rec in q.dump_timeline(8))
        finally:
            q.close()

    def test_resident_lane_fallback_matches_device_products(self):
        """The residency lanes fan out TWO products (packed parity +
        resident planes): the CPU failover must match both, or a sick
        device would poison the residency cache."""
        from ceph_tpu.ops.gf2 import gf2_encode_packedbit_resident
        from ceph_tpu.parallel.service import _cpu_apply_request

        bm, regions, _ = self._payload()
        pk, planes = _cpu_apply_request(
            "packedbit_resident", bm, regions, 8, 2)
        dpk, dplanes = gf2_encode_packedbit_resident(bm, regions)
        assert np.array_equal(pk, np.asarray(dpk))
        assert np.array_equal(planes, np.asarray(dplanes))

    def test_straggler_success_does_not_close_an_open_breaker(self):
        """A pre-trip dispatch completing fine is not evidence the lane
        recovered: only the designated half-open probe may close the
        breaker (a straggler close would zero the escalating cooldown
        and flap a sick lane closed/open forever)."""
        q = self._queue()
        try:
            q._breaker_failure("packed")
            assert q.perf.get("breaker_open_lanes") == 1
            q._breaker_success("packed")  # straggler: not a probe
            assert q.perf.get("breaker_open_lanes") == 1
            assert q.perf.get("breaker_recover") == 0
            # the designated probe DOES close it
            with q._breaker_lock:
                q._breakers["packed"].open_until = time.monotonic() - 1
            assert not q._breaker_route_cpu("packed")  # probe admitted
            q._breaker_success("packed")
            assert q.perf.get("breaker_open_lanes") == 0
            assert q.perf.get("breaker_recover") == 1
        finally:
            q.close()

    def test_env_knobs_seed_queue_attrs(self, monkeypatch):
        from ceph_tpu.parallel.service import BatchingQueue

        monkeypatch.setenv("CEPH_TPU_DISPATCH_TIMEOUT", "3.5")
        monkeypatch.setenv("CEPH_TPU_INJECT_DISPATCH_DELAY", "0.25")
        q = BatchingQueue(max_delay=0.001, mesh=False)
        try:
            assert q.dispatch_timeout == 3.5
            assert q.inject_dispatch_delay == 0.25
        finally:
            q.inject_dispatch_delay = 0.0
            q.close()


class TestResendPerf:
    def test_transport_death_resends_and_counts(self):
        """Kill the primary mid-stream: the op rides out the failure via
        resend (zero client-visible errors) and the objecter counters
        record the recovery."""
        async def go():
            cluster = Cluster(n_osds=4, conf=dict(CONF))
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("rs", profile=dict(PROFILE))
                await c.put(pool, "obj", b"v1" * 1000)
                p, pg, acting, primary = _locate(c, pool, "obj")
                await cluster.kill_osd(primary)
                # no mark_osd_down: the client discovers the death via
                # transport errors/timeouts + failure detection
                data = os.urandom(4000)
                await c.put(pool, "obj", data)
                assert await c.get(pool, "obj") == data
                d = c.perf.dump()
                assert d["resends"] >= 1 or d["timeouts"] >= 1, d
                await c.stop()
            finally:
                await cluster.stop()

        run(go())
