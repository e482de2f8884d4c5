"""Multi-stripe object layout end-to-end (reference ECUtil.cc:123-160 +
ECTransaction.cc:37-95 semantics on the TPU-native data path).

Covers: stripe-sequence shard blobs, the single-dispatch batched encode
feeding client writes, stripe-scoped partial-overwrite RMW (a small
overwrite of a large object reads ~one stripe, not the object), eversion
(PG-log-ordered) shard versions replacing wall clocks, and the persisted
HashInfo (hinfo_key) cumulative crcs driving deep scrub.
"""

import asyncio

import numpy as np
import pytest

from ceph_tpu.rados.ecutil import HashInfo, StripeInfo, batched_encode, decode_object
from ceph_tpu.rados.pglog import pack_eversion
from ceph_tpu.rados.store import ShardMeta, shard_crc
from ceph_tpu.rados.vstart import Cluster

CONF = {
    "mon_osd_report_grace": 0.8,
    "osd_heartbeat_interval": 0.2,
    "osd_repair_delay": 0.3,
    "client_op_timeout": 2.0,
    "osd_repair_full_sweep": False,
}

PROFILE = {"plugin": "jerasure", "technique": "reed_sol_van",
           "k": "2", "m": "2", "stripe_unit": "4096"}


def payload(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n, dtype=np.uint8).tobytes()


def run(coro, timeout=60):
    asyncio.run(asyncio.wait_for(coro, timeout))


def _primary_of(cluster, c, pool, oid):
    p = c.osdmap.pools[pool]
    pg = c.osdmap.object_to_pg(p, oid)
    acting = c.osdmap.pg_to_acting(p, pg)
    primary = c.osdmap.primary_of(acting, seed=(pool << 20) | pg)
    return p, pg, acting, cluster.osds[primary]


class TestStripeLayout:
    def test_multistripe_blob_layout_and_roundtrip(self):
        async def go():
            cluster = Cluster(n_osds=5, conf=dict(CONF))
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("ms", profile=dict(PROFILE))
                data = payload(300_000, seed=1)  # ~37 stripes at 8K width
                await c.put(pool, "obj", data)
                assert await c.get(pool, "obj") == data
                p, pg, acting, primary = _primary_of(cluster, c, pool, "obj")
                sinfo = primary._sinfo(p)
                assert sinfo.stripe_width == 8192
                n_stripes = -(-len(data) // sinfo.stripe_width)
                for shard, osd_id in enumerate(acting):
                    if osd_id < 0:
                        continue
                    got = cluster.osds[osd_id].store.read((pool, "obj", shard))
                    assert got is not None
                    blob, meta = got
                    # shard blob = that shard's per-stripe chunks, concatenated
                    assert len(blob) == n_stripes * sinfo.chunk_size
                    assert meta.object_size == len(data)
            finally:
                await cluster.stop()

        run(go())

    def test_batched_encode_matches_per_stripe_reference_layout(self):
        from ceph_tpu.ec.registry import registry

        codec = registry.factory("jerasure", "", {
            "plugin": "jerasure", "technique": "cauchy_good", "k": "3",
            "m": "2", "packetsize": "64"})
        cs = codec.get_chunk_size(3 * 1024)
        sinfo = StripeInfo(3, cs * 3)
        data = payload(7 * sinfo.stripe_width - 123, seed=2)
        blobs = batched_encode(codec, sinfo, data)
        padded = sinfo.pad_to_stripe(data)
        n = codec.get_chunk_count()
        for s in range(7):
            stripe = padded[s * sinfo.stripe_width:(s + 1) * sinfo.stripe_width]
            enc = codec.encode(set(range(n)), stripe)
            for i in range(n):
                assert np.array_equal(
                    np.asarray(blobs[i])[s * cs:(s + 1) * cs],
                    np.asarray(enc[i])), (s, i)
        # decode with losses reproduces the object
        avail = {i: blobs[i] for i in range(n) if i not in (0, 4)}
        assert decode_object(codec, sinfo, avail, len(data)) == data


class TestStripeRMW:
    def test_partial_overwrite_reads_one_stripe(self):
        async def go():
            cluster = Cluster(n_osds=5, conf=dict(CONF))
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("rmw", profile=dict(PROFILE))
                data = bytearray(payload(1 << 20, seed=3))  # 1 MiB, 128 stripes
                await c.put(pool, "obj", bytes(data))
                # cold caches: force the stripe-scoped read path
                for osd in cluster.osds.values():
                    osd._extent_cache.clear()
                p, pg, acting, primary = _primary_of(cluster, c, pool, "obj")
                before = primary.perf.get("rmw_read_bytes")
                patch = payload(100, seed=4)
                off = 512 * 1024 + 37
                await c.put(pool, "obj", patch, offset=off)
                data[off:off + len(patch)] = patch
                assert await c.get(pool, "obj") == bytes(data)
                assert primary.perf.get("rmw_partial") >= 1
                read_bytes = primary.perf.get("rmw_read_bytes") - before
                sinfo = primary._sinfo(p)
                # the RMW read moved ~one stripe, not the megabyte object
                assert 0 < read_bytes <= 2 * sinfo.stripe_width, read_bytes
            finally:
                await cluster.stop()

        run(go())

    def test_overwrite_grows_object_and_gap_is_zero(self):
        async def go():
            cluster = Cluster(n_osds=5, conf=dict(CONF))
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("grow", profile=dict(PROFILE))
                await c.put(pool, "obj", payload(10_000, seed=5))
                for osd in cluster.osds.values():
                    osd._extent_cache.clear()
                tail = payload(500, seed=6)
                off = 100_000  # far past EOF: gap stripes must read as zeros
                await c.put(pool, "obj", tail, offset=off)
                got = await c.get(pool, "obj")
                assert len(got) == off + len(tail)
                assert got[:10_000] == payload(10_000, seed=5)
                assert got[10_000:off] == b"\x00" * (off - 10_000)
                assert got[off:] == tail
            finally:
                await cluster.stop()

        run(go())

    def test_back_to_back_rmw_uses_cache_and_splices(self):
        async def go():
            cluster = Cluster(n_osds=5, conf=dict(CONF))
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("hot", profile=dict(PROFILE))
                data = bytearray(payload(200_000, seed=7))
                await c.put(pool, "obj", bytes(data))
                _p, _pg, _acting, primary = _primary_of(cluster, c, pool, "obj")
                for i in range(4):
                    patch = payload(64, seed=10 + i)
                    off = i * 40_000 + 11
                    await c.put(pool, "obj", patch, offset=off)
                    data[off:off + len(patch)] = patch
                assert await c.get(pool, "obj") == bytes(data)
                assert primary.perf.get("rmw_partial") >= 4
                # cache hits: no stripe read traffic at all
                assert primary.perf.get("rmw_read_bytes") == 0
            finally:
                await cluster.stop()

        run(go())


class TestSplicePrecondition:
    def test_stale_shard_rejects_splice_and_recovers(self):
        """A shard that missed an intermediate write must NOT have an RMW
        delta spliced into its stale blob (it would stamp corrupt bytes as
        newest with a self-consistent crc).  It rejects; recovery re-pushes
        the full blob."""
        async def go():
            cluster = Cluster(n_osds=5, conf=dict(CONF))
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("sp", profile=dict(PROFILE))
                v1 = payload(60_000, seed=20)
                await c.put(pool, "obj", v1)
                _p, _pg, acting, _primary = _primary_of(cluster, c, pool, "obj")
                # save a parity shard's v1 state, then advance the object
                shard = max(s for s, o in enumerate(acting) if o >= 0)
                osd = cluster.osds[acting[shard]]
                saved = osd.store.read((pool, "obj", shard))
                v2 = bytearray(payload(60_000, seed=21))
                await c.put(pool, "obj", bytes(v2))
                # simulate the missed write: rewind that shard to v1
                osd.store._data[(pool, "obj", shard)] = saved
                for o in cluster.osds.values():
                    o._extent_cache.clear()
                # RMW splice: the stale shard must refuse the delta
                patch = payload(64, seed=22)
                await c.put(pool, "obj", patch, offset=8192 + 7)
                v2[8192 + 7:8192 + 7 + 64] = patch
                stale = osd.store.read((pool, "obj", shard))
                assert stale[1].version == saved[1].version, \
                    "stale shard accepted a splice it could not compose"
                assert await c.get(pool, "obj") == bytes(v2)
                # recovery restores the shard wholesale at the new version
                await c.repair_pool(pool)
                await asyncio.sleep(0.4)
                healed = osd.store.read((pool, "obj", shard))
                assert healed[1].version > saved[1].version
                summary = await c.deep_scrub(pool)
                assert summary["errors"] == 0, summary
                assert await c.get(pool, "obj") == bytes(v2)
            finally:
                await cluster.stop()

        run(go())


class TestEversion:
    def test_pack_eversion_orders_by_log_not_clock(self):
        # higher epoch (failover primary, slow clock) always outranks
        assert pack_eversion((3, 1)) > pack_eversion((2, 999))
        assert pack_eversion((2, 8)) > pack_eversion((2, 7))

    def test_shard_versions_are_log_versions(self):
        async def go():
            cluster = Cluster(n_osds=5, conf=dict(CONF))
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("ev", profile=dict(PROFILE))
                await c.put(pool, "obj", b"first version here")
                await c.put(pool, "obj", b"second version here!")
                p, pg, acting, primary = _primary_of(cluster, c, pool, "obj")
                log = primary._pglog(pool, pg)
                want = pack_eversion(log.entries[-1].version)
                got = primary.store.read((pool, "obj", 0)) or \
                    primary.store.read((pool, "obj", 1))
                # whichever shard the primary holds carries the log eversion
                found = False
                for shard, osd_id in enumerate(acting):
                    if osd_id < 0:
                        continue
                    stored = cluster.osds[osd_id].store.read((pool, "obj", shard))
                    if stored is not None:
                        assert stored[1].version == want
                        found = True
                assert found
                assert got is None or got[1].version == want
            finally:
                await cluster.stop()

        run(go())

    def test_write_after_failover_wins_despite_skewed_clock(self):
        async def go():
            import time as _time
            cluster = Cluster(n_osds=5, conf=dict(CONF))
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("skew", profile=dict(PROFILE))
                await c.put(pool, "obj", b"pre-failover data")
                _p, _pg, _acting, primary = _primary_of(cluster, c, pool, "obj")
                # the new primary's wall clock runs BEHIND: must not matter
                real_ns = _time.time_ns
                _time.time_ns = lambda: real_ns() - 3_600_000_000_000
                try:
                    await cluster.kill_osd(primary.osd_id)
                    await asyncio.sleep(1.2)  # failure detection + remap
                    await c.refresh_map()
                    await c.put(pool, "obj", b"post-failover data!!")
                    assert await c.get(pool, "obj") == b"post-failover data!!"
                finally:
                    _time.time_ns = real_ns
            finally:
                await cluster.stop()

        run(go(), timeout=90)


class TestHashInfo:
    def test_hinfo_persisted_and_correct(self):
        async def go():
            cluster = Cluster(n_osds=5, conf=dict(CONF))
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("hi", profile=dict(PROFILE))
                data = payload(100_000, seed=8)
                await c.put(pool, "obj", data)
                _p, _pg, acting, _primary = _primary_of(cluster, c, pool, "obj")
                checked = 0
                for shard, osd_id in enumerate(acting):
                    if osd_id < 0:
                        continue
                    osd = cluster.osds[osd_id]
                    raw = osd.store.getattr((pool, "obj", shard),
                                            HashInfo.XATTR_KEY)
                    assert raw, f"osd.{osd_id} shard {shard} missing hinfo"
                    h = HashInfo.decode(raw)
                    blob, _meta = osd.store.read((pool, "obj", shard))
                    assert h.crcs[shard] == shard_crc(blob)
                    assert h.total_chunk_size == len(blob)
                    assert not h.dirty
                    checked += 1
                assert checked >= 3
            finally:
                await cluster.stop()

        run(go())

    def test_append_chains_hinfo_crc(self):
        async def go():
            cluster = Cluster(n_osds=5, conf=dict(CONF))
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("app", profile=dict(PROFILE))
                base = payload(8192 * 3, seed=9)  # 3 whole stripes
                await c.put(pool, "obj", base)
                for osd in cluster.osds.values():
                    osd._extent_cache.clear()
                tail = payload(8192, seed=10)  # stripe-aligned append
                await c.put(pool, "obj", tail, offset=len(base))
                assert await c.get(pool, "obj") == base + tail
                _p, _pg, acting, _primary = _primary_of(cluster, c, pool, "obj")
                for shard, osd_id in enumerate(acting):
                    if osd_id < 0:
                        continue
                    osd = cluster.osds[osd_id]
                    raw = osd.store.getattr((pool, "obj", shard),
                                            HashInfo.XATTR_KEY)
                    h = HashInfo.decode(raw)
                    blob, _meta = osd.store.read((pool, "obj", shard))
                    # chained crc over the append equals the whole-blob crc
                    assert h.crcs[shard] == shard_crc(blob)
                    assert h.total_chunk_size == len(blob)
                    assert h.dirty  # spliced: non-self entries went stale
            finally:
                await cluster.stop()

        run(go())

    def test_scrub_cross_check_catches_fully_colluding_shard(self):
        """A shard whose blob, meta crc AND own hinfo entry were all
        consistently rewritten passes every self-check; only the primary's
        cross-shard comparison against its own clean hinfo record
        (HashInfo.dirty gating) catches it."""
        async def go():
            cluster = Cluster(n_osds=5, conf=dict(CONF))
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("coll", profile=dict(PROFILE))
                data = payload(40_000, seed=12)
                await c.put(pool, "obj", data)
                p, pg, acting, primary = _primary_of(cluster, c, pool, "obj")
                # pick a NON-primary acting shard and rewrite everything
                shard, osd_id = next(
                    (s, o) for s, o in enumerate(acting)
                    if o >= 0 and o != primary.osd_id)
                osd = cluster.osds[osd_id]
                key = (pool, "obj", shard)
                blob, meta = osd.store.read(key)
                bad = bytearray(blob)
                bad[0] ^= 0x5A
                bad = bytes(bad)
                osd.store._data[key] = (
                    bad, ShardMeta(version=meta.version,
                                   object_size=meta.object_size,
                                   chunk_crc=shard_crc(bad)))
                h = HashInfo.decode(
                    osd.store.getattr(key, HashInfo.XATTR_KEY))
                h.crcs[shard] = shard_crc(bad)
                osd.store.setattr(key, HashInfo.XATTR_KEY, h.encode())
                summary = await c.deep_scrub(pool)
                assert summary["errors"] >= 1
                assert summary["repaired"] >= 1
                for o in cluster.osds.values():
                    o._extent_cache.clear()
                assert await c.get(pool, "obj") == data
            finally:
                await cluster.stop()

        run(go())

    def test_scrub_detects_flip_via_hinfo_when_meta_colludes(self):
        async def go():
            cluster = Cluster(n_osds=5, conf=dict(CONF))
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("scr", profile=dict(PROFILE))
                data = payload(50_000, seed=11)
                await c.put(pool, "obj", data)
                _p, _pg, acting, _primary = _primary_of(cluster, c, pool, "obj")
                # corrupt one shard AND rewrite its meta crc to match, so
                # only the stored cumulative hinfo can catch it
                shard, osd_id = next((s, o) for s, o in enumerate(acting)
                                     if o >= 0)
                osd = cluster.osds[osd_id]
                blob, meta = osd.store.read((pool, "obj", shard))
                bad = bytearray(blob)
                bad[100] ^= 0xFF
                bad = bytes(bad)
                osd.store._data[(pool, "obj", shard)] = (
                    bad, ShardMeta(version=meta.version,
                                   object_size=meta.object_size,
                                   chunk_crc=shard_crc(bad)))
                summary = await c.deep_scrub(pool)
                assert summary["errors"] >= 1
                assert summary["repaired"] >= 1
                for o in cluster.osds.values():
                    o._extent_cache.clear()
                assert await c.get(pool, "obj") == data
            finally:
                await cluster.stop()

        run(go())


class TestExtentCache:
    def test_extent_merge_and_range_reads(self):
        from ceph_tpu.rados.extent_cache import ExtentCache

        c = ExtentCache(max_objects=4)
        key = (1, "o")
        c.put_extent(key, 5, 100, b"a" * 50, size_hint=1000)
        c.put_extent(key, 5, 150, b"b" * 50)
        got = c.get_range(key, 100, 100)
        assert got is not None
        v, data, size = got
        assert (v, size) == (5, 1000)
        assert data == b"a" * 50 + b"b" * 50
        # partial coverage misses
        assert c.get_range(key, 90, 20) is None
        assert c.get_range(key, 180, 40) is None
        # stale version put is refused; newer put supersedes
        c.put_extent(key, 4, 0, b"old")
        assert c.get_range(key, 0, 3) is None
        c.put_extent(key, 6, 100, b"c" * 10)
        assert c.get_range(key, 100, 10)[1] == b"c" * 10
        assert c.get_range(key, 150, 10) is None  # older extents dropped

    def test_carry_forward_upgrades_in_place(self):
        from ceph_tpu.rados.extent_cache import ExtentCache

        c = ExtentCache()
        key = (1, "o")
        c.put_extent(key, 5, 0, b"x" * 100, size_hint=300)
        # the primary's own RMW step: version 5 -> 7, only [200,250) changed
        c.put_extent(key, 7, 200, b"y" * 50, carry_from=5)
        assert c.get_range(key, 0, 100) == (7, b"x" * 100, 300)
        assert c.get_range(key, 200, 50)[1] == b"y" * 50

    def test_full_entries_preserve_whole_object_behavior(self):
        from ceph_tpu.rados.extent_cache import ExtentCache

        c = ExtentCache()
        key = (1, "o")
        c.put_full(key, 9, b"hello world")
        assert c.get_full(key) == (9, b"hello world")
        assert c.get_range(key, 6, 5)[1] == b"world"
        c.drop(key)
        assert c.get_full(key) is None

    @pytest.mark.parametrize("case", [
        "bytes", "whole_readonly_view", "whole_view_of_bytearray",
        "slice_of_larger", "window_on_larger_array", "writable_view",
        "bytearray", "wide_items"])
    def test_put_full_keeps_what_it_can_and_copies_the_rest(self, case):
        """ISSUE 34: a whole-object put keeps the buffer it is handed
        when that is bytes or a read-only view of all of a buffer that
        owns its memory; a writable view can change under the cache and
        a view of part of something larger would pin the rest: those
        are copied."""
        from ceph_tpu.rados.extent_cache import ExtentCache

        arr = np.arange(4096, dtype=np.uint8)
        keeps = True
        if case == "bytes":
            data = arr.tobytes()
        elif case == "whole_readonly_view":
            # how the wire delivers a blob (FrameReceiver._blob_dest)
            data = memoryview(arr).cast("B").toreadonly()
        elif case == "whole_view_of_bytearray":
            data = memoryview(bytearray(arr.tobytes())).toreadonly()
        elif case == "slice_of_larger":
            # a lane fragment of its group's assembly buffer
            data = memoryview(arr).cast("B")[1024:2048].toreadonly()
            keeps = False
        elif case == "window_on_larger_array":
            data = memoryview(arr[:1024]).toreadonly()
            keeps = False
        elif case == "writable_view":
            data = memoryview(arr).cast("B")
            keeps = False
        elif case == "bytearray":
            data = bytearray(arr.tobytes())
            keeps = False
        else:
            data = memoryview(np.arange(64, dtype=np.uint16)).toreadonly()
            keeps = False
        want = bytes(data)
        c = ExtentCache()
        key = (1, "o")
        assert c.put_full(key, 3, data) is keeps
        version, got = c.get_full(key)
        assert version == 3 and bytes(got) == want
        if keeps:
            assert got is data
            if isinstance(data, memoryview):
                assert got.obj is data.obj and got.readonly
        else:
            assert type(got) is bytes
            if case != "wide_items":
                # the caller's later write does not reach the cache
                if case == "bytearray":
                    data[:] = bytes(len(data))
                else:
                    arr[:] = 0
                assert c.get_full(key)[1] == want
        # readers take any buffer: ranges, and a run spliced at the
        # same version (bytes semantics are made where they are needed)
        assert bytes(c.get_range(key, 8, 16)[1]) == want[8:24]
        assert c.get_range(key, len(want), 4)[1] == b""
        c.put_extent(key, 3, 4, b"zz")
        assert bytes(c.get_full(key)[1]) == want[:4] + b"zz" + want[6:]
        # a stale put costs nothing and caches nothing
        assert c.put_full(key, 2, bytearray(b"old")) is True
        assert c.get_full(key)[0] == 3

    def test_wire_full_write_is_kept_not_copied_and_splices(self):
        """Over the wire messenger (not the fast path) a put's payload
        lands as a view of a buffer of its own: the primary caches THAT
        (no copy), a partial overwrite splices against exactly those
        bytes, and a read returns the spliced object."""
        async def go():
            cluster = Cluster(n_osds=4, conf={**CONF,
                                              "ms_local_fastpath": False})
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("wire", profile=dict(PROFILE))
                data = payload(512 * 1024, seed=5)

                def total(key):
                    return sum(o.perf.get(key)
                               for o in cluster.osds.values())

                await c.put(pool, "obj", data)
                assert total("write_adopted_bytes") == len(data)
                assert total("write_copied_bytes") == 0
                _p, _pg, _acting, primary = _primary_of(
                    cluster, c, pool, "obj")
                _v, cached = primary._cache_get(pool, "obj")
                assert isinstance(cached, memoryview) and cached.readonly
                assert isinstance(cached.obj, np.ndarray) \
                    and cached.obj.base is None \
                    and cached.obj.nbytes == len(data)
                assert cached == data
                with pytest.raises(TypeError):
                    cached[0] = 0
                patch = payload(5000, seed=6)
                off = 70_001
                reads0 = total("rmw_read_bytes")
                await c.put(pool, "obj", patch, offset=off)
                want = data[:off] + patch + data[off + len(patch):]
                assert total("rmw_read_bytes") == reads0  # cache served
                # the offset write hands the cache no new object: it
                # splits the cached one around the stripes it wrote
                assert total("write_adopted_bytes") == len(data)
                assert total("write_copied_bytes") == 0
                v2, patched = primary._cache_get(pool, "obj")
                assert v2 > _v and patched == want
                assert cached == data  # the payload itself is untouched
                assert await c.get(pool, "obj") == want
                for o in cluster.osds.values():
                    o._extent_cache.clear()
                assert await c.get(pool, "obj") == want  # from the shards
                await c.stop()
            finally:
                await cluster.stop()

        run(go(), timeout=90)

    def test_rmw_pipeline_hits_extent_cache(self):
        """Back-to-back partial overwrites to one region: the second+
        RMW must serve its read from the pinned extents (reference
        ExtentCache reserve/present pipelining)."""
        import asyncio as _a
        import os as _os

        from ceph_tpu.rados.vstart import Cluster

        async def go():
            cluster = Cluster(n_osds=3, conf={"osd_auto_repair": False})
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("ec-pipe", profile={
                    "plugin": "jerasure", "technique": "reed_sol_van",
                    "k": "2", "m": "1"})
                big = _os.urandom(64 * 4096)
                await c.put(pool, "obj", big)
                for o in cluster.osds.values():
                    o._extent_cache.clear()  # force the segment path
                buf = bytearray(big)
                for i in range(4):
                    patch = _os.urandom(1000)
                    off = 8192 + i * 100
                    buf[off:off + 1000] = patch
                    await c.put(pool, "obj", bytes(patch), offset=off)
                assert await c.get(pool, "obj") == bytes(buf)
                hits = sum(o.perf.get("rmw_extent_hits")
                           for o in cluster.osds.values())
                assert hits >= 2, hits
                await c.stop()
            finally:
                await cluster.stop()

        _a.run(_a.wait_for(go(), 90))


# -- the benchmark's engagement metrics (ISSUE 34) ---------------------------

WRITE_CELLS = ["k8m3.write4m", "k4m2.write4m", "k10m4c.write4m",
               "k8m3.mixed-small",  # puts beside gets and deletes (PR 38)
               "k8m3.write4m-bluestore",  # the same puts on a disk store
               "k8m4clay.write4m"]  # ... and on the regenerating code
READ_CELLS = ["k8m3.randread4m", "k8m3.randread4m-cold"]


@pytest.mark.parametrize("name, cells, moves, full, empty, gone", [
    ("write_copy_share.put", WRITE_CELLS, "put_MBps",
     ({"osd.write_copied_bytes": 1 << 20,
       "osd.write_adopted_bytes": 3 << 20}, 25.0),
     {"osd.write_copied_bytes": 0, "osd.write_adopted_bytes": 1 << 22},
     {"osd.write_copied_bytes": 0, "osd.write_adopted_bytes": 0}),
    ("hitset_scan_bits_per_op.put", WRITE_CELLS, "put_MBps",
     ({"tier.hitset_bits_scanned": 1_000_000, "objecter.op": 500}, 2000.0),
     {"tier.hitset_bits_scanned": 0, "objecter.op": 500},
     {"tier.hitset_bits_scanned": 0, "objecter.op": 0}),
    ("hitset_scan_bits_per_op.get", READ_CELLS, "get_MBps",
     ({"tier.hitset_bits_scanned": 1_000_000, "objecter.op": 500}, 2000.0),
     {"tier.hitset_bits_scanned": 0, "objecter.op": 500},
     {"tier.hitset_bits_scanned": 0, "objecter.op": 0}),
])
def test_the_engagement_metrics_resolve_and_read_their_counters(
        name, cells, moves, full, empty, gone):
    """The benchmark's data files: a window's counter delta gives the
    share / the bits per op; a window in which the mechanism holds reads
    0.0 and not nothing; a program without the counters (the parent)
    reports nothing."""
    import json
    import os

    from benchmarks import layers, manifest

    def read(counters):
        return layers.read(name, {"counters": counters})

    assert read(full[0]) == full[1]
    assert read(empty) == 0.0
    assert read(gone) is None
    assert read({"objecter.op": 500, "osd.op_w": 500}) is None
    man = manifest.load()
    entry = [e for e in man["per_layer"] if e["name"] == name]
    assert len(entry) == 1 and entry[0]["workloads"] == cells
    assert entry[0]["layer"] == "OSD op path" and entry[0]["moves"] == moves
    assert entry[0]["source"] == "program_counter"
    for cell in man["workloads"]:
        reported = {m["name"] for m in
                    manifest.metrics_of(man, cell["name"])[1]}
        assert (name in reported) == (cell["name"] in cells)
    with open(os.path.join(layers.DIR, name + ".json")) as f:
        assert json.load(f)["source"] == "perf_counter"


def test_a_rehearsed_put_cell_copies_no_payload_and_walks_no_filter():
    """benchmarks/run.py --rehearse (the cell's own path at a tiny size,
    over TCP): every put's payload is kept, not copied, and no hit-set
    bit is walked; both metrics are in the traced line, at 0."""
    import json
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("CEPH_TPU_FORCE_BATCH", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "run.py"),
         "--workload", "k4m2.write4m", "--seed", "3000000019",
         "--seconds", "2", "--trace", "1", "--rehearse"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    lines = [json.loads(ln) for ln in proc.stdout.splitlines() if ln.strip()]
    assert lines, proc.stderr[-2000:]
    last = lines[-1]
    assert proc.returncode == 3 and last["rehearsal"]
    assert last["would_be_correct"] is True, lines
    assert last["attempted"] > 0 and last["failed"] == 0
    assert last["metrics"]["write_copy_share.put"] == \
        {"value": 0.0, "unit": "%"}
    assert last["metrics"]["hitset_scan_bits_per_op.put"] == \
        {"value": 0.0, "unit": "bits/op"}
