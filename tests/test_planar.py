"""Bit-planar HBM residency (VERDICT r03 #1): shards stay on the device
as bit-planes (u32 plane words for w=8, int8 planes otherwise) across
encode -> decode -> recovery, and the pack/unpack boundary is paid once
at the host boundary.  These tests pin the planar paths byte-identical
to the packed/CPU oracle paths and exercise the residency lifecycle
(admission, version gating, eviction, invalidation) through both the
resident store and the OSD data path."""

import asyncio
import os
import time

import numpy as np

from ceph_tpu.ec.registry import registry
from ceph_tpu.ops.gf2 import from_planar, gf2_matmul, to_planar
from ceph_tpu.parallel.service import BatchingQueue
from ceph_tpu.rados import osd as osdmod
from ceph_tpu.rados.ecutil import (StripeInfo, batched_encode,
                                   planar_encode_async, planar_object_bytes,
                                   planar_rows)
from ceph_tpu.rados.pagestore import PagedResidentStore
from ceph_tpu.rados.vstart import Cluster

PROFILE = {"plugin": "jerasure", "technique": "reed_sol_van",
           "k": "8", "m": "3"}


def _codec():
    return registry.factory("jerasure", "", dict(PROFILE))


def run(coro, timeout=180):
    asyncio.run(asyncio.wait_for(coro, timeout))


class TestPlanarBoundary:
    def test_to_from_planar_roundtrip(self):
        rng = np.random.default_rng(3)
        for w, rows, cols in ((8, 8, 4096), (16, 4, 2048), (4, 3, 1024)):
            data = rng.integers(0, 256, size=(rows, cols), dtype=np.uint8)
            bits = to_planar(data, w)
            back = np.asarray(from_planar(bits, w, rows))
            assert np.array_equal(back, data), f"w={w}"

    def test_planar_matmul_matches_packed_path(self):
        """encode as unpack-once -> matmul -> pack-once must be
        byte-identical to the fused packed kernel and the CPU oracle."""
        from ceph_tpu.ec.gf import gf
        from ceph_tpu.ec.matrices import (matrix_to_bitmatrix,
                                          vandermonde_coding_matrix)

        k, m, w = 8, 3, 8
        mat = vandermonde_coding_matrix(k, m, w)
        bm = matrix_to_bitmatrix(mat, w).astype(np.int8)
        rng = np.random.default_rng(5)
        data = rng.integers(0, 256, size=(k, 8192), dtype=np.uint8)
        bits = to_planar(data, w)
        parity = np.asarray(from_planar(gf2_matmul(bm, bits), w, m))
        want = gf(w).matmul(mat, data)
        assert np.array_equal(parity, want)


class TestPlanarQueueLane:
    def test_packed_and_packedbit_groups_do_not_mix(self):
        """One matrix and one buffer on two lanes are two dispatches
        (the layouts differ), and the same bytes."""
        from ceph_tpu.ec.matrices import (matrix_to_bitmatrix,
                                          vandermonde_coding_matrix)

        k, m, w = 4, 2, 8
        bm = matrix_to_bitmatrix(vandermonde_coding_matrix(k, m, w), w)
        rng = np.random.default_rng(9)
        q = BatchingQueue(max_delay=60.0)
        try:
            d = rng.integers(0, 256, (k, 1024), dtype=np.uint8)
            f1 = q.submit(bm.astype(np.int8), d, w, m)
            f2 = q.submit(bm.astype(np.uint8), d, w, m, "packedbit")
            q.flush()
            assert np.array_equal(f1.result(timeout=60),
                                  f2.result(timeout=60))
            assert q.dispatches == 2
        finally:
            q.close()


class TestResidentStoreBoundary:
    def test_admit_read_roundtrip_and_stats(self):
        store = PagedResidentStore(capacity_bytes=64 << 20, page_bytes=4096)
        rng = np.random.default_rng(11)
        rows = rng.integers(0, 256, (11, 4096), dtype=np.uint8)
        store.admit("obj1", rows)
        got = store.read("obj1")
        assert np.array_equal(got, rows)
        assert store.read("nope") is None
        s = store.stats()
        assert s["admits"] == 1 and s["hits"] == 1 and s["misses"] == 1
        assert s["resident_bytes"] == rows.size * 8  # 8x planar footprint

    def test_lru_eviction_under_byte_budget(self):
        rows = np.zeros((4, 1024), dtype=np.uint8)
        planar_sz = rows.size * 8
        store = PagedResidentStore(capacity_bytes=planar_sz * 2,
                                   page_bytes=4096)
        store.admit("a", rows)
        store.admit("b", rows)
        assert "a" in store and "b" in store
        store.get_planar("a")  # refresh a: b becomes LRU
        store.admit("c", rows)
        assert "b" not in store and "a" in store and "c" in store
        assert store.evictions == 1
        assert store.resident_bytes <= store.capacity_bytes


class TestPlanarEcutil:
    def test_planar_encode_matches_batched_encode(self):
        codec = _codec()
        sinfo = StripeInfo(k=8, stripe_width=8 * 4096)
        for size in (100_000, 8 * 4096, 1_000_001):
            data = os.urandom(size)
            want = batched_encode(codec, sinfo, data)

            async def go():
                return await planar_encode_async(codec, sinfo, data)

            got = asyncio.run(go())
            assert got is not None
            blobs, all_bits, n_rows, n_cols, w = got
            assert n_rows == 11 and w == 8
            for a, b in zip(want, blobs):
                assert np.array_equal(np.asarray(a), np.asarray(b)), size
            # the resident packs back to exactly the shard rows
            store = PagedResidentStore(capacity_bytes=256 << 20)
            store.put_planar("k", all_bits, n_rows=n_rows,
                             meta=(7, n_cols))
            rows = planar_rows(store, "k", 7)
            assert rows is not None
            for a, b in zip(want, rows):
                assert np.array_equal(np.asarray(a), b)
            # and the data rows de-interleave to the original bytes
            obj = planar_object_bytes(store, "k", 7, 8,
                                      sinfo.chunk_size, size)
            assert obj == data
            # version gating: a stale resident never serves
            assert planar_rows(store, "k", 8) is None
            assert planar_object_bytes(store, "k", 8, 8,
                                       sinfo.chunk_size, size) is None

    def test_planar_encode_w16_records_field_width(self):
        """w=16 pools unpack to a different plane layout: the resident
        must be recorded with the codec's w (ADVICE-class r4 review
        finding — a w=8 default would serve silently corrupt bytes)."""
        codec = registry.factory("jerasure", "", {
            "plugin": "jerasure", "technique": "reed_sol_van",
            "k": "4", "m": "2", "w": "16"})
        assert getattr(codec, "w", 8) == 16
        sinfo = StripeInfo(k=4, stripe_width=4 * 4096)
        data = os.urandom(120_000)
        want = batched_encode(codec, sinfo, data)

        async def go():
            return await planar_encode_async(codec, sinfo, data)

        got = asyncio.run(go())
        assert got is not None
        blobs, all_bits, n_rows, n_cols, w = got
        assert w == 16
        for a, b in zip(want, blobs):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        store = PagedResidentStore(capacity_bytes=256 << 20)
        store.put_planar("k16", all_bits, w=w, n_rows=n_rows,
                         meta=(3, n_cols))
        rows = planar_rows(store, "k16", 3)
        assert rows is not None
        for a, b in zip(want, rows):
            assert np.array_equal(np.asarray(a), b)
        obj = planar_object_bytes(store, "k16", 3, 4,
                                  sinfo.chunk_size, len(data))
        assert obj == data


class TestOsdPlanarResidency:
    def test_write_read_repair_ride_residents(self, force_batching):
        """Full-object EC writes leave planar residents; reads at the
        written version serve from them (no decode), repair re-encodes
        pack from them (no matmul), and overwrites/deletes invalidate."""
        async def go():
            cluster = Cluster(n_osds=4, conf={"osd_auto_repair": False,
                                              "client_op_timeout": 60.0})
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("pl", profile={
                    "plugin": "jerasure", "technique": "reed_sol_van",
                    "k": "2", "m": "1"})
                store = osdmod.shared_planar_store()
                assert store is not None
                blob = os.urandom(100_000)
                await c.put(pool, "obj", blob)
                # some OSD now holds the object planar-resident
                assert any(
                    o._planar is not None
                    and o._planar_key(pool, "obj") in store
                    for o in cluster.osds.values())
                hits0 = store.hits
                subr0 = sum(o.perf.get("subop_r")
                            for o in cluster.osds.values())
                pl0 = sum(o.perf.get("planar_read_hits")
                          for o in cluster.osds.values())
                assert await c.get(pool, "obj") == blob
                assert store.hits > hits0, "read did not touch residents"
                # the fast path is a TRUE zero-shard-read: the primary
                # served from its log-matched resident without any
                # sub-read fan-out
                assert sum(o.perf.get("planar_read_hits")
                           for o in cluster.osds.values()) == pl0 + 1
                assert sum(o.perf.get("subop_r")
                           for o in cluster.osds.values()) == subr0
                # overwrite invalidates + re-installs at the new version;
                # reads serve the NEW bytes
                blob2 = os.urandom(90_000)
                await c.put(pool, "obj", blob2)
                assert await c.get(pool, "obj") == blob2
                # delete drops the residency
                await c.delete(pool, "obj")
                assert all(
                    o._planar_key(pool, "obj") not in store
                    for o in cluster.osds.values() if o._planar is not None)
                await c.stop()
            finally:
                await cluster.stop()

        run(go())

    def test_planar_residency_can_be_disabled(self, force_batching):
        async def go():
            cluster = Cluster(n_osds=3, conf={
                "osd_auto_repair": False,
                "osd_ec_planar_residency": False})
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("npl", profile={
                    "plugin": "jerasure", "technique": "reed_sol_van",
                    "k": "2", "m": "1"})
                assert all(o._planar is None for o in cluster.osds.values())
                blob = os.urandom(40_000)
                await c.put(pool, "o", blob)
                assert await c.get(pool, "o") == blob
                await c.stop()
            finally:
                await cluster.stop()

        run(go())


class TestTransferOverlap:
    """VERDICT r03 #4: the queue worker double-buffers — round N+1's
    device staging and compute launch happen BEFORE round N's results
    are fetched, so H2D transfer overlaps dispatch."""

    def test_split_phase_launch_complete_is_byte_exact(self):
        from ceph_tpu.ec.gf import gf
        from ceph_tpu.ec.matrices import (matrix_to_bitmatrix,
                                          vandermonde_coding_matrix)
        from ceph_tpu.parallel.service import _Group, _Request

        k, m, w = 4, 2, 8
        mat = vandermonde_coding_matrix(k, m, w)
        bm = matrix_to_bitmatrix(mat, w).astype(np.int8)
        fgf = gf(w)
        rng = np.random.default_rng(21)
        q = BatchingQueue(max_delay=60.0)  # worker stays idle
        try:
            from concurrent.futures import Future

            def group(datas):
                g = _Group(mbits=bm, w=w, out_rows=m)
                futs = []
                for d in datas:
                    f = Future()
                    g.requests.append(
                        _Request(d, f, time.monotonic(), None))
                    futs.append(f)
                return g, futs

            d1 = [rng.integers(0, 256, (k, 1024), dtype=np.uint8)
                  for _ in range(3)]
            d2 = [rng.integers(0, 256, (k, 2048), dtype=np.uint8)
                  for _ in range(2)]
            g1, f1 = group(d1)
            g2, f2 = group(d2)
            # launch BOTH rounds before completing either: round 2's
            # staging must not disturb round 1's in-flight results
            l1 = q._launch_safe([g1])
            l2 = q._launch_safe([g2])
            q._complete_safe(l1)
            q._complete_safe(l2)
            for d, f in zip(d1, f1):
                assert np.array_equal(f.result(timeout=5),
                                      fgf.matmul(mat, d))
            for d, f in zip(d2, f2):
                assert np.array_equal(f.result(timeout=5),
                                      fgf.matmul(mat, d))
        finally:
            q.close()

    def test_backlog_holds_round_in_flight_and_overlaps(self):
        from ceph_tpu.ec.gf import gf
        from ceph_tpu.ec.matrices import (matrix_to_bitmatrix,
                                          vandermonde_coding_matrix)

        k, m, w = 4, 2, 8
        mat = vandermonde_coding_matrix(k, m, w)
        bm = matrix_to_bitmatrix(mat, w).astype(np.int8)
        fgf = gf(w)
        rng = np.random.default_rng(22)
        q = BatchingQueue(max_pending_bytes=1, max_delay=0.001)
        try:
            late = []

            def inject_backlog():
                # runs on the WORKER thread right after a round launches:
                # queue the next round so the backlog check sees pending
                # work and holds the launched round in flight
                q._launch_hook = None  # once
                late.append(q.submit(
                    bm, rng.integers(0, 256, (k, 2048), dtype=np.uint8),
                    w, m))

            q._launch_hook = inject_backlog
            d0 = rng.integers(0, 256, (k, 1024), dtype=np.uint8)
            f0 = q.submit(bm, d0, w, m)
            out0 = f0.result(timeout=60)
            assert np.array_equal(out0, fgf.matmul(mat, d0))
            late[0].result(timeout=60)
            assert q.overlapped_rounds >= 1, \
                "backlogged round did not overlap the in-flight fetch"
        finally:
            q.close()

    def test_deep_backlog_splits_into_budgeted_rounds(self):
        """A backlog far above max_pending_bytes must dispatch as
        MULTIPLE budget-sized rounds (which the worker can pipeline),
        not one oversized round nothing overlaps with — and every
        request must still resolve byte-exactly in FIFO order."""
        from ceph_tpu.ec.gf import gf
        from ceph_tpu.ec.matrices import (matrix_to_bitmatrix,
                                          vandermonde_coding_matrix)

        k, m, w = 4, 2, 8
        mat = vandermonde_coding_matrix(k, m, w)
        bm = matrix_to_bitmatrix(mat, w).astype(np.int8)
        fgf = gf(w)
        rng = np.random.default_rng(23)
        # budget = one request's bytes: 8 queued requests => >= 8 rounds
        q = BatchingQueue(max_pending_bytes=k * 1024, max_delay=10.0)
        try:
            with q._cv:  # stall the worker while the backlog forms
                datas = [rng.integers(0, 256, (k, 1024), dtype=np.uint8)
                         for _ in range(8)]
            futs = [q.submit(bm, d, w, m) for d in datas]
            d0 = q.dispatches
            for d, f in zip(datas, futs):
                assert np.array_equal(f.result(timeout=60),
                                      fgf.matmul(mat, d))
            assert q.dispatches - d0 >= 4, \
                f"backlog dispatched as {q.dispatches - d0} round(s)"
        finally:
            q.close()

    def test_flush_takes_everything_regardless_of_budget(self):
        from ceph_tpu.ec.matrices import (matrix_to_bitmatrix,
                                          vandermonde_coding_matrix)

        k, m, w = 4, 2, 8
        bm = matrix_to_bitmatrix(
            vandermonde_coding_matrix(k, m, w), w).astype(np.int8)
        rng = np.random.default_rng(24)
        q = BatchingQueue(max_pending_bytes=16, max_delay=10.0)
        try:
            futs = [q.submit(bm, rng.integers(0, 256, (k, 512),
                                              dtype=np.uint8), w, m)
                    for _ in range(4)]
            q.flush()
            for f in futs:
                f.result(timeout=60)
        finally:
            q.close()


class TestPackedbitResidency:
    """The packed-bit (u32-word) resident layout — the production layout
    for w=8: 1/8th the int8-plane HBM footprint, static XOR schedules per
    matrix, byte-identical to every oracle path.  (Its round trip at
    widths that are not whole words is tests/test_pagestore.py's
    ragged-tail case.)"""

    def test_packedbit_resident_is_8x_denser(self):
        """The layout's capacity win: a u32 resident accounts 1 byte
        per data byte where int8 planes account 8 — same budget, 8x the
        objects."""
        rng = np.random.default_rng(43)
        rows = rng.integers(0, 256, size=(4, 1024), dtype=np.uint8)
        s_planes = PagedResidentStore(capacity_bytes=8 << 20,
                                      page_bytes=4096)
        s_packed = PagedResidentStore(capacity_bytes=8 << 20,
                                      page_bytes=4096)
        s_planes.admit("x", rows, w=8, layout="planes")
        s_packed.admit("x", rows, w=8, layout="packedbit")
        assert s_planes.resident_bytes == 8 * s_packed.resident_bytes

    def test_planar_encode_async_installs_packedbit_residents(self):
        """The w=8 write path admits u32 residents end-to-end: encode
        rides the packedbit_resident queue lane, planar_rows and
        planar_object_bytes read the u32 layout back byte-exactly."""
        codec = _codec()
        sinfo = StripeInfo(k=8, stripe_width=8 * 4096)
        data = os.urandom(3 * 8 * 4096 + 100)
        want = batched_encode(codec, sinfo, data)
        q = BatchingQueue(max_delay=0.001)
        try:

            async def go():
                return await planar_encode_async(codec, sinfo, data,
                                                 queue=q)

            got = asyncio.run(go())
        finally:
            q.close()
        assert got is not None
        blobs, all_bits, n_rows, n_cols, w = got
        assert np.asarray(all_bits).dtype == np.uint32, \
            "w=8 write path must install packed-bit residents"
        for a, b in zip(want, blobs):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        store = PagedResidentStore(capacity_bytes=256 << 20)
        store.put_planar("k", all_bits, n_rows=n_rows, meta=(7, n_cols))
        rows = planar_rows(store, "k", 7)
        assert rows is not None
        for a, b in zip(want, rows):
            assert np.array_equal(np.asarray(a), b)
        obj = planar_object_bytes(store, "k", 7, 8, sinfo.chunk_size,
                                  len(data))
        assert obj == data
