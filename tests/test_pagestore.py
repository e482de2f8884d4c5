"""Paged resident store (ceph_tpu/rados/pagestore.py) + writeback tier
semantics: page-table math and ragged tails, trim/fragmentation
accounting, per-page dirty bits with the flush-before-evict discipline,
partial (parity-shed) residency, page-granular memo accounting, the
generic planar_* helpers over the paged protocol, and the end-to-end
writeback lifecycle — dirty install, agent flush byte identity,
primary-failover flush-on-demote, the write-heat gate, and the
mon-validated cache_mode/dirty-ratio pool opts."""

import asyncio
import os

import numpy as np
import pytest

from ceph_tpu.rados import osd as osdmod
from ceph_tpu.rados.ecutil import (planar_object_bytes, planar_rows,
                                   planar_shard_bytes)
from ceph_tpu.rados.pagestore import PagedResidentStore, WritebackRecord
from ceph_tpu.rados.tiering import HitSetArchive
from ceph_tpu.rados.vstart import Cluster

PROFILE = {"plugin": "jerasure", "technique": "reed_sol_van",
           "k": "2", "m": "1"}


def run(coro, timeout=180):
    asyncio.run(asyncio.wait_for(coro, timeout))


@pytest.fixture()
def force_batching(monkeypatch):
    monkeypatch.setenv("CEPH_TPU_FORCE_BATCH", "1")


def _rows(n, B, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, (n, B), dtype=np.uint8)


# -- page table / ragged tails -----------------------------------------------


class TestPageTable:
    @pytest.mark.parametrize("widths", [
        (3000, 4096, 4128, 12256),
        # not whole u32 words either: admit pads to words, read trims
        (100, 1024, 1000)])
    def test_ragged_tail_roundtrip_non_page_multiple(self, widths):
        """Satellite pin: residents whose byte size is NOT a multiple of
        the page size round-trip byte-identically through the ragged
        last page, at several awkward widths."""
        store = PagedResidentStore(capacity_bytes=1 << 20,
                                   page_bytes=4096)
        for i, B in enumerate(widths):
            rows = _rows(3, B, seed=i)
            store.admit(f"o{i}", rows, w=8, layout="packedbit")
            got = store.read(f"o{i}")
            assert got is not None
            np.testing.assert_array_equal(got, rows)
        assert store.pages_used <= store.pages_total

    def test_planes_layout_word_aligns_odd_widths(self):
        """Review pin: an int8 'planes' resident whose byte width is
        not a multiple of 4 must still gather/read — row widths pad up
        to whole pool words, trim restores the true width."""
        store = PagedResidentStore(capacity_bytes=1 << 20,
                                   page_bytes=4096)
        rows = _rows(3, 3001, seed=13)
        store.admit("o", rows, w=8, layout="planes")
        got = store.read("o")
        assert got is not None
        np.testing.assert_array_equal(got, rows)
        assert store.gather_rows("o", 8, 16) is not None

    def test_pages_used_matches_ceil_of_footprint(self):
        store = PagedResidentStore(capacity_bytes=1 << 20,
                                   page_bytes=4096)
        rows = _rows(3, 4096)  # packedbit: 24 bit-rows x 128 words
        store.admit("o", rows, w=8, layout="packedbit")
        total_words = 24 * (4096 // 32)
        want = -(-total_words * 4 // 4096)
        assert store.pages_used == want
        assert store.resident_bytes == want * 4096

    def test_trim_drops_pad_and_counts_frag(self):
        """put_planar(trim=) stores only the true columns; the
        monolithic-equivalent accounting keeps the padded width, so
        frag_saved goes positive when the pad was real."""
        from ceph_tpu.ops.gf2 import to_packedbit

        store = PagedResidentStore(capacity_bytes=1 << 20,
                                   page_bytes=4096)
        B, B_padded = 4096, 8192  # a pow2-padded encode output
        rows = _rows(3, B, seed=3)
        padded = np.zeros((3, B_padded), dtype=np.uint8)
        padded[:, :B] = rows
        bits = np.asarray(to_packedbit(padded))
        assert store.put_planar("o", bits, w=8, n_rows=3,
                                meta=(1, B, B * 2), trim=B)
        # gather excludes the pad
        got = store.gather_rows("o", 0, 24)
        assert got.shape[1] == B // 32
        assert store.stats()["monolithic_equiv_bytes"] == 24 * (B_padded
                                                                // 32) * 4
        assert store.frag_saved_signed > 0

    def test_gather_rows_partial_ranges(self):
        store = PagedResidentStore(capacity_bytes=1 << 20,
                                   page_bytes=4096)
        rows = _rows(4, 2048, seed=4)
        store.admit("o", rows, w=8, layout="packedbit")
        from ceph_tpu.ops.gf2 import from_packedbit

        mid = store.gather_rows("o", 8, 16)  # rows 1..2's bit-rows
        got = np.asarray(from_packedbit(mid, 1))
        np.testing.assert_array_equal(got[0], rows[1])

    def test_lru_eviction_makes_room(self):
        store = PagedResidentStore(capacity_bytes=64 << 10,
                                   page_bytes=4096)
        # each resident: 24 bit-rows x 64 words x 4B = 6144 B -> 2 pages
        for i in range(12):
            store.admit(f"o{i}", _rows(3, 2048, seed=i), w=8,
                        layout="packedbit")
        assert store.pages_used <= store.pages_total
        assert store.evictions > 0
        assert "o0" not in store  # oldest went first
        assert "o11" in store

    def test_oversized_install_refused(self):
        store = PagedResidentStore(capacity_bytes=8 << 10,
                                   page_bytes=4096)
        bits = np.zeros((24, 1024), dtype=np.uint32)  # 96 KiB > pool
        assert not store.put_planar("big", bits, w=8, n_rows=3,
                                    meta=(1, 1024 * 32, 0))
        assert "big" not in store
        assert store.perf.get("install_refused") == 1

    def test_capacity_only_grows(self):
        store = PagedResidentStore(capacity_bytes=64 << 10,
                                   page_bytes=4096)
        store.capacity_bytes = 128 << 10
        assert store.pages_total == 32
        store.capacity_bytes = 4096  # shrink attempts are ignored
        assert store.pages_total == 32


# -- dirty lifecycle ---------------------------------------------------------


def _dirty_install(store, key="o", seed=9, version=7):
    from ceph_tpu.ops.gf2 import to_packedbit

    rows = _rows(3, 2048, seed=seed)
    bits = np.asarray(to_packedbit(rows))
    rec = WritebackRecord(pool_id=1, oid=key, pg=0, version=version,
                          object_size=4096, hinfo=b"", shards=(1,))
    assert store.put_planar(key, bits, w=8, n_rows=3,
                            meta=(version, 2048, 4096), trim=2048,
                            data_rows=16,
                            dirty_rows=[(8, 16)], dirty_info=rec)
    return rows, rec


class TestDirtyLifecycle:
    def test_dirty_install_refuses_drop_until_clean(self):
        store = PagedResidentStore(capacity_bytes=1 << 20,
                                   page_bytes=4096)
        _dirty_install(store)
        assert store.dirty_pages > 0
        assert store.is_dirty("o")
        assert not store.drop("o")  # flush-before-evict holds
        assert store.perf.get("evict_refused_dirty") == 1
        info, gen = store.peek_dirty("o")
        assert info.shards == (1,)
        assert store.clear_dirty("o", gen)
        assert not store.is_dirty("o")
        assert store.dirty_pages == 0
        assert store.drop("o")

    def test_force_drop_overrides_dirty(self):
        store = PagedResidentStore(capacity_bytes=1 << 20,
                                   page_bytes=4096)
        _dirty_install(store)
        assert store.drop("o", force=True)
        assert store.dirty_pages == 0

    def test_stale_flush_token_cannot_clear_new_dirt(self):
        """An overwrite that re-installed mid-flush keeps ITS dirt: the
        old generation token is refused."""
        store = PagedResidentStore(capacity_bytes=1 << 20,
                                   page_bytes=4096)
        _dirty_install(store, seed=1, version=7)
        _info, old_gen = store.peek_dirty("o")
        _dirty_install(store, seed=2, version=8)  # overwrite, new dirt
        assert not store.clear_dirty("o", old_gen)
        assert store.is_dirty("o")
        _info2, new_gen = store.peek_dirty("o")
        assert new_gen != old_gen
        assert store.clear_dirty("o", new_gen)

    def test_install_refused_when_pool_all_dirty(self):
        store = PagedResidentStore(capacity_bytes=16 << 10,
                                   page_bytes=4096)
        _dirty_install(store, key="a", seed=1)  # 2 pages, dirty
        _dirty_install(store, key="b", seed=2)
        # nothing clean to evict: a third install must refuse, and both
        # dirty entries must survive untouched
        from ceph_tpu.ops.gf2 import to_packedbit

        bits = np.asarray(to_packedbit(_rows(3, 2048, seed=3)))
        assert not store.put_planar("c", bits, w=8, n_rows=3,
                                    meta=(1, 2048, 0))
        assert store.is_dirty("a") and store.is_dirty("b")


# -- partial residency (parity shed) -----------------------------------------


class TestParityShed:
    def test_shed_frees_suffix_data_keeps_serving(self):
        store = PagedResidentStore(capacity_bytes=1 << 20,
                                   page_bytes=4096)
        rows = _rows(3, 4096, seed=5)
        from ceph_tpu.ops.gf2 import to_packedbit

        bits = np.asarray(to_packedbit(rows))
        assert store.put_planar("o", bits, w=8, n_rows=3,
                                meta=(1, 4096, 8192), trim=4096,
                                data_rows=16)  # k=2 of n=3
        before = store.entry_nbytes("o")
        freed = store.shed_parity("o")
        assert freed > 0
        assert store.entry_nbytes("o") == before - freed
        assert store.perf.get("parity_sheds") == 1
        # data rows still gather; the whole resident does not
        assert store.gather_rows("o", 0, 16) is not None
        assert store.get_planar("o") is None
        assert store.page_stats()["partial_residents"] == 1
        # second shed is a no-op
        assert store.shed_parity("o") == 0

    def test_shed_skips_dirty_pages(self):
        store = PagedResidentStore(capacity_bytes=1 << 20,
                                   page_bytes=4096)
        _dirty_install(store)  # shard 1 (parity range rows 8..16) dirty
        from ceph_tpu.ops.gf2 import to_packedbit  # noqa: F401

        # data_rows=16 -> parity suffix overlaps the dirty rows: the
        # dirty pages must survive the shed
        dirty_before = store.dirty_pages
        store.shed_parity("o")
        assert store.dirty_pages == dirty_before


# -- memo accounting ---------------------------------------------------------


class TestMemo:
    def test_memo_page_rounded_and_dies_with_entry(self):
        store = PagedResidentStore(capacity_bytes=64 << 10,
                                   page_bytes=4096)
        store.admit("o", _rows(3, 2048, seed=6), w=8, layout="packedbit",
                    meta=(5, 2048, 4000))
        store.memo_put("o", 5, b"x" * 100)
        assert store.memo_bytes == 4096  # page-rounded charge
        assert store.memo_get("o", 5) == b"x" * 100
        assert store.memo_get("o", 6) is None  # version-tagged
        store.drop("o")
        assert store.memo_bytes == 0
        assert store.memo_get("o", 5) is None

    def test_memo_cap_refuses_over_budget(self):
        store = PagedResidentStore(capacity_bytes=8 << 10,
                                   page_bytes=4096)
        store.admit("o", _rows(1, 32, seed=7), w=8, layout="packedbit")
        store.memo_put("o", None, b"y" * 9000)  # 3 pages > 2-page pool
        assert store.memo_bytes == 0


# -- generic planar_* helpers over the paged protocol ------------------------


class TestPlanarHelpersOverPages:
    def test_shard_and_object_bytes_match_rows(self):
        store = PagedResidentStore(capacity_bytes=1 << 20,
                                   page_bytes=4096)
        k, n, B, cs = 2, 3, 4096, 1024
        rows = _rows(n, B, seed=8)
        store.admit("o", rows, w=8, layout="packedbit",
                    meta=(42, B, k * B))
        for s in range(n):
            assert planar_shard_bytes(store, "o", 42, s) \
                == rows[s].tobytes()
        assert planar_shard_bytes(store, "o", 41, 0) is None  # stale
        got = planar_object_bytes(store, "o", 42, k, cs, k * B)
        want = rows[:k].reshape(k, B // cs, cs).transpose(1, 0, 2) \
            .reshape(-1).tobytes()
        assert got == want
        # memoized second read
        assert planar_object_bytes(store, "o", 42, k, cs, k * B) == want
        lst = planar_rows(store, "o", 42)
        assert lst is not None and len(lst) == n
        np.testing.assert_array_equal(lst[2], rows[2])

    def test_object_bytes_survive_parity_shed_rows_do_not(self):
        store = PagedResidentStore(capacity_bytes=1 << 20,
                                   page_bytes=4096)
        k, B, cs = 2, 4096, 1024
        rows = _rows(3, B, seed=9)
        from ceph_tpu.ops.gf2 import to_packedbit

        bits = np.asarray(to_packedbit(rows))
        store.put_planar("o", bits, w=8, n_rows=3, meta=(7, B, k * B),
                         trim=B, data_rows=k * 8)
        store.shed_parity("o")
        want = rows[:k].reshape(k, B // cs, cs).transpose(1, 0, 2) \
            .reshape(-1).tobytes()
        assert planar_object_bytes(store, "o", 7, k, cs, k * B) == want
        assert planar_rows(store, "o", 7) is None  # parity gone


# -- temperatures survive pool param changes ---------------------------------


class TestRetune:
    def test_retune_preserves_heat(self):
        arch = HitSetArchive(period=10.0, count=8, now=0.0)
        arch.record("hot", now=1.0)
        arch.rotate(now=2.0)
        arch.record("hot", now=3.0)
        t_before = arch.temperature("hot")
        assert t_before > 0
        arch.retune(period=5.0, count=4, target_size=256, fpp=0.01)
        # the archived interval still scores; future sizing changed
        assert arch.temperature("hot") == t_before
        assert arch.params_key() == (5.0, 4, 256, 0.01)
        assert arch.archived.maxlen == 4


# -- end-to-end: writeback lifecycle -----------------------------------------


WB_CONF = {"osd_auto_repair": False, "client_op_timeout": 60.0,
           "osd_hit_set_period": 30.0,
           "osd_min_read_recency_for_promote": 1,
           "osd_tier_cache_mode": "writeback",
           "osd_tier_agent_interval": 0.1,
           "osd_tier_flush_age": 0.4}


class TestWritebackEndToEnd:
    def test_dirty_flush_evict_reread_byte_identity(self, force_batching):
        """The writeback lifecycle gate: a put installs DIRTY pages and
        defers the local store apply; the resident serves reads; the
        agent's age-driven flush lands the deferred applies at the
        exact pinned versions; evicting then re-reading cold serves the
        flushed bytes."""
        async def go():
            cluster = Cluster(n_osds=3, conf=dict(WB_CONF))
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("wb", profile=dict(PROFILE))
                store = osdmod.shared_planar_store()
                assert store is not None and hasattr(store, "dirty_items")
                blob = os.urandom(120_000)
                await c.put(pool, "obj", blob)
                assert store.dirty_pages > 0, \
                    "writeback put left no dirty pages"
                pinned = [(key, info) for key, info, _g, _s
                          in store.dirty_items()]
                assert pinned
                # resident read serves the acked (dirty) bytes
                assert await c.get(pool, "obj") == blob
                # age-driven agent flush drains the dirt
                for _ in range(200):
                    if not store.has_dirty():
                        break
                    await asyncio.sleep(0.05)
                assert store.dirty_pages == 0, "flush never drained"
                # the deferred applies landed at their pinned versions.
                # A WritebackRecord pins its deferred local shards; a
                # fast-ack CacheDirtyRecord defers the WHOLE k+m encode,
                # so the flush lands this OSD's acting shards.
                flushed = 0
                for key, info in pinned:
                    o = cluster.osds[key[0]]
                    shards = getattr(info, "shards", None)
                    if shards is None:
                        p = o.osdmap.pools[info.pool_id]
                        acting = o.osdmap.pg_to_acting(p, info.pg)
                        shards = [s for s, osd in enumerate(acting)
                                  if osd == key[0]]
                    for shard in shards:
                        got = o._store_read((info.pool_id, info.oid,
                                             shard))
                        assert got is not None
                        assert got[1].version >= info.version
                        flushed += 1
                assert flushed > 0
                # evict everything; the cold path must serve the
                # flushed bytes byte-identically
                for o in cluster.osds.values():
                    if o._planar is not None:
                        o._planar.drop(o._planar_key(pool, "obj"),
                                       force=True)
                assert await c.get(pool, "obj",
                                   fadvise="dontneed") == blob
                assert sum(o._planar.perf.get("flushes")
                           for o in cluster.osds.values()
                           if o._planar is not None) > 0
                await c.stop()
            finally:
                await cluster.stop()

        run(go())

    def test_flush_on_demote_primary_failover(self, force_batching):
        """Satellite pin: a primary holding dirty residents that loses
        primaryship (admin out) flushes them on the map change —
        writeback is never the only copy once the PG moved — and the
        new primary serves the acked bytes."""
        async def go():
            conf = dict(WB_CONF)
            conf["osd_tier_flush_age"] = 60.0  # only demote may flush
            cluster = Cluster(n_osds=4, conf=conf)
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("wb", profile=dict(PROFILE))
                store = osdmod.shared_planar_store()
                blobs = {f"o{i}": os.urandom(90_000) for i in range(6)}
                for oid, blob in blobs.items():
                    await c.put(pool, oid, blob)
                dirty = store.dirty_items()
                assert dirty, "no writeback dirt to fail over"
                victim = dirty[0][0][0]  # osd id of a dirty primary

                def victim_owned():
                    # dirt the victim INSTALLED as primary (an adopted
                    # copy it holds for a live primary legitimately
                    # stays until that owner's flush + clear)
                    return [key for key, info, _g, _s
                            in store.dirty_items()
                            if key[0] == victim
                            and getattr(info, "primary", victim)
                            == victim]

                assert victim_owned(), "victim owned no writeback dirt"
                await c.osd_out(victim)
                # the demoted primary's own dirt must move on the map
                # change: sync flush (WritebackRecord) or push to the
                # new primary, who destages and clears (fast-ack raw)
                for _ in range(200):
                    if not victim_owned():
                        break
                    await asyncio.sleep(0.05)
                assert not victim_owned(), \
                    "demoted primary kept dirty residents it installed"
                # the dirt moved by one of the two demote planes:
                # legacy sync flush (WritebackRecord) or the fast-ack
                # replay — push to the new primary, who encodes there
                assert (cluster.osds[victim].tier_perf.get(
                            "flush_demote") > 0
                        or sum(o.tier_perf.get("flush_encodes")
                               for o in cluster.osds.values()) > 0)
                # acked bytes survive the failover
                for oid, blob in blobs.items():
                    assert await c.get(pool, oid) == blob
                await c.osd_in(victim)
                await c.stop()
            finally:
                await cluster.stop()

        run(go())

    def test_gated_overwrite_supersedes_dirty_resident(
            self, force_batching):
        """Review pin: a full overwrite whose resident install is GATED
        must kill the previous write's dirty resident — otherwise the
        agent's later flush would replay the OLD deferred shard bytes
        over the newer committed write (version regression)."""
        async def go():
            conf = dict(WB_CONF)
            conf["osd_tier_flush_age"] = 60.0  # keep v1's dirt parked
            cluster = Cluster(n_osds=3, conf=conf)
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("sv", profile=dict(PROFILE))
                store = osdmod.shared_planar_store()
                v1 = os.urandom(100_000)
                await c.put(pool, "obj", v1)
                assert store.dirty_pages > 0
                # the primary's own record (a fast-ack put also leaves
                # ADOPTED copies on cache peers — same oid, other osds)
                key, info = next(
                    (k, i) for k, i, _g, _s in store.dirty_items()
                    if getattr(i, "primary", k[0]) == k[0])
                # gate the SECOND write's install at runtime
                await c.pool_set(pool, "min_write_recency_for_promote",
                                 "99")
                o = cluster.osds[key[0]]
                for _ in range(100):
                    p = o.osdmap.pools.get(pool) if o.osdmap else None
                    if p is not None and (getattr(p, "opts", {})
                                          or {}).get(
                            "min_write_recency_for_promote") == "99":
                        break
                    await asyncio.sleep(0.02)
                v2 = os.urandom(104_000)
                await c.put(pool, "obj", v2)
                # the superseded dirty resident died with the overwrite
                assert not store.is_dirty(key), \
                    "stale writeback dirt survived a gated overwrite"
                assert key not in store
                # ...and so did every peer's adopted copy of v1 (the
                # v2 sub-write's version-aware drop): no process may
                # later replay v1 bytes anywhere
                await asyncio.sleep(0.5)
                assert not any(i.oid == info.oid
                               for _k, i, _g, _s in store.dirty_items()
                               if i is not None), \
                    "stale adopted copy survived a gated overwrite"
                shards = getattr(info, "shards", None)
                if shards is None:
                    p = o.osdmap.pools[info.pool_id]
                    acting = o.osdmap.pg_to_acting(p, info.pg)
                    shards = [s for s, osd in enumerate(acting)
                              if osd == key[0]]
                for shard in shards:
                    got = o._store_read((info.pool_id, info.oid, shard))
                    assert got is not None
                    assert got[1].version > info.version, \
                        "local shard regressed to the superseded version"
                assert await c.get(pool, "obj") == v2
                await c.stop()
            finally:
                await cluster.stop()

        run(go())

    def test_write_heat_gate_blocks_cold_write_installs(
            self, force_batching):
        """Satellite pin (the r10 OPEN tail): with
        min_write_recency_for_promote=2 a cold object's writes do NOT
        install residents (gated, counted), while reads stay correct."""
        async def go():
            conf = {"osd_auto_repair": False, "client_op_timeout": 60.0,
                    "osd_hit_set_period": 30.0,
                    "osd_min_write_recency_for_promote": 2}
            cluster = Cluster(n_osds=3, conf=conf)
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("g", profile=dict(PROFILE))
                store = osdmod.shared_planar_store()
                blob = os.urandom(60_000)
                await c.put(pool, "obj", blob)
                await c.put(pool, "obj", blob)  # same interval: still 1
                assert not any(
                    o._planar is not None
                    and o._planar_key(pool, "obj") in store
                    for o in cluster.osds.values()), \
                    "cold write installed a resident through the gate"
                gated = sum(o.tier_perf.get("write_install_gated")
                            for o in cluster.osds.values())
                recorded = sum(o.tier_perf.get("write_hits_recorded")
                               for o in cluster.osds.values())
                assert gated >= 2 and recorded >= 2
                assert await c.get(pool, "obj") == blob
                await c.stop()
            finally:
                await cluster.stop()

        run(go())

    def test_mon_validates_writeback_pool_opts(self, force_batching):
        async def go():
            cluster = Cluster(n_osds=3, conf={
                "osd_auto_repair": False, "client_op_timeout": 60.0})
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("m", profile=dict(PROFILE))
                await c.pool_set(pool, "cache_mode", "bogus")
                await c.refresh_map()
                opts = getattr(c.osdmap.pools[pool], "opts", {}) or {}
                assert opts.get("cache_mode") is None
                for key, val in (("cache_mode", "writeback"),
                                 ("cache_target_dirty_ratio", "0.5"),
                                 ("min_write_recency_for_promote", "3")):
                    await c.pool_set(pool, key, val)
                await c.refresh_map()
                opts = getattr(c.osdmap.pools[pool], "opts", {}) or {}
                assert opts.get("cache_mode") == "writeback"
                assert opts.get("cache_target_dirty_ratio") == "0.5"
                assert opts.get("min_write_recency_for_promote") == "3"
                await c.stop()
            finally:
                await cluster.stop()

        run(go())

    def test_tier_status_carries_pages_and_cache_mode(
            self, force_batching):
        async def go():
            cluster = Cluster(n_osds=3, conf=dict(WB_CONF))
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("s", profile=dict(PROFILE))
                await c.put(pool, "obj", os.urandom(50_000))
                osd = next(iter(cluster.osds.values()))
                # the fast-ack put returns before the pool's map has
                # necessarily reached every OSD: wait for this one
                for _ in range(200):
                    if osd.osdmap is not None \
                            and pool in osd.osdmap.pools:
                        break
                    await asyncio.sleep(0.02)
                status = osd.tier_status()
                ps = status["pagestore"]
                assert ps is not None
                for key in ("page_bytes", "pages_total", "pages_used",
                            "dirty_pages", "dirty_bytes",
                            "frag_saved_bytes", "partial_residents"):
                    assert key in ps
                assert status["cache_mode"].get("s") == "writeback"
                assert "cache_target_dirty_ratio" in status
                from ceph_tpu.tools.ceph import render_tier_status

                lines = render_tier_status(status)
                assert any("pages:" in ln for ln in lines)
                assert any("cache_mode" in ln for ln in lines)
                await c.stop()
            finally:
                await cluster.stop()

        run(go())


# -- fast-ack replicated writeback -------------------------------------------


class TestFastAckWriteback:
    """The r18 tentpole: a writeback put acks at the CACHE quorum
    (raw dirty copies on osd_cache_min_size processes), the k+m encode
    moves wholesale to the flush path.  These legs pin the durability
    surgery: replica adoption + kill-primary replay, the flush/overwrite
    generation race, quorum-short degradation to write-through, the
    RMW/sub-read fences, and the MCacheDirty truncated-tail ABI."""

    def test_replica_adopt_and_kill_primary_replay(self, force_batching):
        """A fast-ack put leaves the raw object dirty on the primary
        AND adopted on cache_min_size-1 peers; SIGKILLing the primary
        before any flush must not lose the acked write — a surviving
        replica replays its copy to the PG's new primary, who destages
        and serves the bytes."""
        async def go():
            conf = dict(WB_CONF)
            conf["osd_tier_flush_age"] = 60.0  # park: only replay flushes
            conf["mon_osd_report_grace"] = 0.8
            conf["osd_heartbeat_interval"] = 0.2
            conf["client_op_timeout"] = 5.0
            cluster = Cluster(n_osds=4, conf=conf)
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("ka", profile=dict(PROFILE))
                store = osdmod.shared_planar_store()
                blob = os.urandom(120_000)
                await c.put(pool, "obj", blob)
                # the primary's own record names its replica roster
                owned = [(k, i) for k, i, _g, _s in store.dirty_items()
                         if getattr(i, "primary", None) == k[0]
                         and i.oid == "obj"]
                assert owned, "fast-ack put left no owned dirty record"
                (pkey, rec), = owned
                primary = pkey[0]
                assert rec.peers[0] == primary and len(rec.peers) >= 2
                # every non-primary roster member adopted the raw copy
                for peer in rec.peers[1:]:
                    assert store.is_dirty((peer, pool, "obj")), \
                        f"peer {peer} never adopted the dirty copy"
                assert sum(o.tier_perf.get("wb_dirty_adopted")
                           for o in cluster.osds.values()) \
                    >= len(rec.peers) - 1
                assert cluster.osds[primary].tier_perf.get(
                    "wb_repl_acks") >= 1
                assert cluster.osds[primary].tier_perf.get(
                    "wb_repl_bytes") >= len(blob) * (len(rec.peers) - 1)
                await cluster.kill_osd(primary)
                # detection -> replay sweep -> recovery destage: the
                # acked bytes must come back from a surviving replica
                got = None
                for _ in range(300):
                    await asyncio.sleep(0.1)
                    try:
                        got = await c.get(pool, "obj")
                        if got == blob:
                            break
                    except Exception:
                        continue
                assert got == blob, \
                    "acked write lost after kill-primary-before-flush"
                # the destage's clear broadcast releases the survivors'
                # adopted copies (the dead primary's keys were dropped
                # by its stop)
                for _ in range(100):
                    if not any(i.oid == "obj"
                               for _k, i, _g, _s in store.dirty_items()
                               if i is not None):
                        break
                    await asyncio.sleep(0.1)
                assert not any(i.oid == "obj"
                               for _k, i, _g, _s in store.dirty_items()
                               if i is not None), \
                    "adopted copies never released after the replay"
                assert sum(o.tier_perf.get("flush_encodes")
                           for o in cluster.osds.values()) > 0, \
                    "no survivor destaged the replayed copy"
                # the destaged shards serve the bytes cold, with every
                # resident evicted
                for o in cluster.osds.values():
                    if o._planar is not None:
                        o._planar.drop(o._planar_key(pool, "obj"),
                                       force=True)
                assert await c.get(pool, "obj",
                                   fadvise="dontneed") == blob
                await c.stop()
            finally:
                await cluster.stop()

        run(go())

    def test_raw_flush_race_overwrite_generation_token(
            self, force_batching):
        """A destage whose encode raced a newer fast-ack overwrite must
        neither stamp the OLD bytes over any shard nor clear the NEW
        write's dirt — the generation token moved, so the in-flight
        flush stands down and the overwrite keeps custody."""
        async def go():
            conf = dict(WB_CONF)
            conf["osd_tier_flush_age"] = 60.0
            cluster = Cluster(n_osds=3, conf=conf)
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("rc", profile=dict(PROFILE))
                store = osdmod.shared_planar_store()
                v1 = os.urandom(100_000)
                await c.put(pool, "obj", v1)
                pkey, rec1 = next(
                    ((k, i) for k, i, _g, _s in store.dirty_items()
                     if getattr(i, "primary", None) == k[0]))
                o = cluster.osds[pkey[0]]
                snap = store.peek_dirty(pkey)
                assert snap is not None
                gen1 = snap[1]
                p = o.osdmap.pools[rec1.pool_id]
                acting = o.osdmap.pg_to_acting(p, rec1.pg)
                ent1 = o._pglog(rec1.pool_id, rec1.pg).latest_entry("obj")
                # the overwrite lands while the (captured) flush state
                # is mid-encode
                v2 = os.urandom(100_000)
                await c.put(pool, "obj", v2)
                snap2 = store.peek_dirty(pkey)
                assert snap2 is not None and snap2[1] != gen1, \
                    "overwrite did not re-dirty under a new generation"
                # replay the stale flush exactly as the in-flight task
                # would resume: it must detect the moved token and bow
                # out without clearing or fanning out v1's shards
                done = await o._tier_flush_raw_inner(
                    pkey, store, rec1, gen1, p, acting, ent1, v1, False)
                assert done is True
                snap3 = store.peek_dirty(pkey)
                assert snap3 is not None and snap3[1] == snap2[1] \
                    and snap3[0].version == snap2[0].version, \
                    "stale flush disturbed the overwrite's dirt"
                assert await c.get(pool, "obj") == v2
                # the legitimate flush destages v2, and no shard ever
                # regressed to v1
                assert await o._tier_flush_raw_key(pkey)
                for shard, osd in enumerate(acting):
                    if osd < 0:
                        continue
                    got = cluster.osds[osd]._store_read(
                        (rec1.pool_id, "obj", shard))
                    assert got is not None
                    assert got[1].version > rec1.version
                for oo in cluster.osds.values():
                    if oo._planar is not None:
                        oo._planar.drop(oo._planar_key(pool, "obj"),
                                        force=True)
                assert await c.get(pool, "obj",
                                   fadvise="dontneed") == v2
                await c.stop()
            finally:
                await cluster.stop()

        run(go())

    def test_quorum_short_degrades_to_writethrough(self, force_batching):
        """When fewer than osd_cache_min_size-1 live peers exist the
        fast ack's durability claim cannot hold: the put must degrade
        to the synchronous write-through bar (counted wb_quorum_short),
        leaving no deferred dirt behind — and still ack correct bytes."""
        async def go():
            conf = dict(WB_CONF)
            conf["osd_cache_min_size"] = 4  # > acting size: never forms
            cluster = Cluster(n_osds=3, conf=conf)
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("qs", profile=dict(PROFILE))
                store = osdmod.shared_planar_store()
                blob = os.urandom(90_000)
                await c.put(pool, "obj", blob)
                assert sum(o.tier_perf.get("wb_quorum_short")
                           for o in cluster.osds.values()) >= 1
                # no raw fast-ack dirt anywhere: the write went through
                # the synchronous EC path
                from ceph_tpu.rados.pagestore import CacheDirtyRecord
                assert not any(isinstance(i, CacheDirtyRecord)
                               for _k, i, _g, _s in store.dirty_items())
                assert sum(o.tier_perf.get("wb_repl_acks")
                           for o in cluster.osds.values()) == 0
                assert await c.get(pool, "obj") == blob
                # the shards are already EC-durable (write-through)
                placed = 0
                for o in cluster.osds.values():
                    p = o.osdmap.pools.get(pool) if o.osdmap else None
                    if p is None:
                        continue
                    acting = o.osdmap.pg_to_acting(
                        p, o.osdmap.object_to_pg(p, "obj"))
                    for shard, osd in enumerate(acting):
                        if osd == o.osd_id and o._store_read(
                                (pool, "obj", shard)) is not None:
                            placed += 1
                assert placed >= int(PROFILE["k"])
                await c.stop()
            finally:
                await cluster.stop()

        run(go())

    def test_rmw_and_subread_fences_flush_first(self, force_batching):
        """Fence ordering: a partial overwrite (RMW) against parked raw
        dirt must destage the acked full-object write FIRST, then apply
        the patch — and a cold sub-read path against dirty replicas
        serves the acked version, never stale or torn bytes."""
        async def go():
            conf = dict(WB_CONF)
            conf["osd_tier_flush_age"] = 60.0  # park: only fences flush
            cluster = Cluster(n_osds=3, conf=conf)
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("fe", profile=dict(PROFILE))
                store = osdmod.shared_planar_store()
                base = bytearray(os.urandom(96_000))
                await c.put(pool, "obj", bytes(base))
                assert any(getattr(i, "primary", None) == k[0]
                           and i.oid == "obj"
                           for k, i, _g, _s in store.dirty_items())
                # cold read while the dirt is parked: the sub-read
                # fence must serve the acked bytes
                assert await c.get(pool, "obj",
                                   fadvise="dontneed") == bytes(base)
                patch = os.urandom(1024)
                off = 40_000
                await c.put(pool, "obj", patch, offset=off)
                base[off:off + len(patch)] = patch
                # the RMW fence destaged the raw record before patching
                assert sum(o.tier_perf.get("flush_encodes")
                           + o._planar.perf.get("flushes")
                           for o in cluster.osds.values()
                           if o._planar is not None) > 0, \
                    "partial overwrite never forced the destage"
                from ceph_tpu.rados.pagestore import CacheDirtyRecord
                assert not any(isinstance(i, CacheDirtyRecord)
                               and i.oid == "obj"
                               for _k, i, _g, _s in store.dirty_items()), \
                    "raw dirt survived the RMW fence"
                assert await c.get(pool, "obj") == bytes(base)
                for o in cluster.osds.values():
                    if o._planar is not None:
                        o._planar.drop(o._planar_key(pool, "obj"),
                                       force=True)
                assert await c.get(pool, "obj",
                                   fadvise="dontneed") == bytes(base)
                await c.stop()
            finally:
                await cluster.stop()

        run(go())

    def test_mcachedirty_truncated_tail_golden_decode(self):
        """ABI pin: the archived pre-tail MCacheDirty frame (packed
        without the peers/gseq tail) must decode under TODAY's field
        list with the trailing fields defaulting — the append-only
        rule that lets a mixed-version cluster run the fast-ack
        plane."""
        import struct

        import ceph_tpu.rados.types as t
        from ceph_tpu.rados.messenger import decode_message

        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "corpus", "wire", "golden",
            "MCacheDirty.v_pretail.frame")
        with open(path, "rb") as f:
            raw = f.read()
        hdr = struct.Struct("<HHBI")
        type_id, version, fixed, plen = hdr.unpack_from(raw, 0)
        assert type_id == t.MCacheDirty.TYPE_ID
        off = hdr.size
        payload = raw[off:off + plen]
        off += plen
        (blen,) = struct.unpack_from("<I", raw, off)
        blob = raw[off + 4:off + 4 + blen] if blen else None
        msg = decode_message(type_id, version, payload, blob,
                             bool(fixed))
        assert isinstance(msg, t.MCacheDirty)
        assert msg.oid == "wb/obj" and msg.op == "install"
        assert bytes(msg.data) == b"rawdirty" and msg.version == 41
        assert msg.reply_to == ("127.0.0.1", 6802)
        # the truncated tail defaults — never garbage, never a shifted
        # mis-read of earlier fields
        assert msg.peers == [] and msg.gseq == 0


# -- device arm (jitted slab kernels on jax-cpu) ------------------------------


def _fresh_slab_cache():
    from ceph_tpu.ops import slab

    slab._reset_for_tests()


def _own(got, cols):
    """A device-arm gather's own columns: it comes back as wide as the
    pow2 bucket of its row width, zero past the resident's columns (a
    width is data, not a compile key), and its readers trim."""
    got = np.asarray(got)
    assert got.shape[1] >= cols and not got[:, cols:].any()
    return got[:, :cols]


class TestDeviceArm:
    """The pagestore's DEVICE arm forced onto the jax-cpu backend: the
    exact jitted install/gather call structure a real device runs, with
    byte-identity pinned against the host-numpy arm."""

    WIDTHS = (100, 3000, 4096, 4128, 12256, 13)

    def test_device_host_parity_ragged_tails(self):
        """Satellite pin: non-page-multiple sizes round-trip through
        the device arm's zero-padded ragged tail byte-identically to
        the host arm, on every gather shape."""
        _fresh_slab_cache()
        host = PagedResidentStore(capacity_bytes=1 << 20,
                                  page_bytes=4096, device=False)
        dev = PagedResidentStore(capacity_bytes=1 << 20,
                                 page_bytes=4096, device=True)
        for i, B in enumerate(self.WIDTHS):
            rows = _rows(6, B, seed=i)
            for st in (host, dev):
                st.admit(f"o{i}", rows, w=8, layout="packedbit")
        for i in range(len(self.WIDTHS)):
            h, d = host.read(f"o{i}"), dev.read(f"o{i}")
            assert h is not None and d is not None
            np.testing.assert_array_equal(h, d)
            hg = host.gather_rows(f"o{i}", 8, 40)
            dg = dev.gather_rows(f"o{i}", 8, 40)
            np.testing.assert_array_equal(np.asarray(hg),
                                          _own(dg, hg.shape[1]))
        s = dev.stats()
        assert s["device_arm"] == 1 and s["device_slabs"] >= 1
        assert s["h2d_installs"] + s["device_installs"] >= len(self.WIDTHS)
        assert s["d2h_gathers"] >= len(self.WIDTHS)
        assert host.stats()["device_arm"] == 0

    def test_device_planes_layout_parity(self):
        """int8 planes residents ride the bitcast path on gathers."""
        _fresh_slab_cache()
        host = PagedResidentStore(capacity_bytes=1 << 20,
                                  page_bytes=4096, device=False)
        dev = PagedResidentStore(capacity_bytes=1 << 20,
                                 page_bytes=4096, device=True)
        rows = _rows(8, 3001, seed=21)
        for st in (host, dev):
            st.admit("pl", rows, w=8, layout="planes")
        np.testing.assert_array_equal(host.read("pl"), dev.read("pl"))
        hg = np.asarray(host.gather_rows("pl", 8, 16))
        np.testing.assert_array_equal(
            hg, _own(dev.gather_rows("pl", 8, 16), hg.shape[1]))

    @pytest.mark.filterwarnings("ignore:.*[Dd]onat.*")
    def test_donation_safety_gather_survives_later_install(self,
                                                           monkeypatch):
        """A gather result is a FRESH buffer: installs that later donate
        the same sub-slab must not invalidate it (the jax-cpu backend
        ignores donation but runs the identical call structure)."""
        monkeypatch.setenv("CEPH_TPU_SLAB_DONATE", "1")
        _fresh_slab_cache()
        store = PagedResidentStore(capacity_bytes=1 << 20,
                                   page_bytes=4096, device=True)
        rows_a = _rows(3, 2048, seed=31)
        store.admit("a", rows_a, w=8, layout="packedbit")
        early = store.gather_rows("a", 0, 24)
        early_np = np.asarray(early)  # materialize the pre-install view
        # a burst of donated installs into the SAME sub-slab
        for i in range(8):
            store.admit(f"b{i}", _rows(3, 2048, seed=40 + i), w=8,
                        layout="packedbit")
        np.testing.assert_array_equal(np.asarray(early), early_np)
        np.testing.assert_array_equal(store.read("a"), rows_a)

    @pytest.mark.filterwarnings("ignore:.*[Dd]onat.*")
    def test_install_racing_gather_same_subslab(self, monkeypatch):
        """Threads hammering donated installs while readers gather a
        pinned key on the same sub-slab: every gather must return the
        pinned key's exact bytes (the lock sequences donation)."""
        import threading

        monkeypatch.setenv("CEPH_TPU_SLAB_DONATE", "1")
        _fresh_slab_cache()
        store = PagedResidentStore(capacity_bytes=1 << 20,
                                   page_bytes=4096, device=True)
        rows = _rows(3, 2048, seed=50)
        store.admit("pin", rows, w=8, layout="packedbit")
        want = store.read("pin")
        errors = []
        stop = threading.Event()

        def installer():
            i = 0
            while not stop.is_set():
                store.admit(f"w{i % 4}", _rows(3, 2048, seed=60 + i % 4),
                            w=8, layout="packedbit")
                i += 1

        def reader():
            while not stop.is_set():
                got = store.read("pin")
                if got is None or not np.array_equal(got, want):
                    errors.append("torn read")
                    return

        threads = [threading.Thread(target=installer),
                   threading.Thread(target=reader),
                   threading.Thread(target=reader)]
        for t in threads:
            t.start()
        import time as _time

        _time.sleep(1.0)
        stop.set()
        for t in threads:
            t.join(timeout=30)
        assert not errors

    def test_device_dirty_flush_replay_identity(self):
        """Writeback flush replay (planar_shard_bytes) off the device
        arm is byte-identical to the host arm's — the flush path's
        gather rides the same kernels as reads."""
        _fresh_slab_cache()
        host = PagedResidentStore(capacity_bytes=1 << 20,
                                  page_bytes=4096, device=False)
        dev = PagedResidentStore(capacity_bytes=1 << 20,
                                 page_bytes=4096, device=True)
        _dirty_install(host, seed=9)
        _dirty_install(dev, seed=9)
        for shard in range(3):
            hb = planar_shard_bytes(host, "o", 7, shard)
            db = planar_shard_bytes(dev, "o", 7, shard)
            assert hb is not None and hb == db
        info, gen = dev.peek_dirty("o")
        assert dev.clear_dirty("o", gen)
        assert dev.drop("o")

    def test_device_shed_parity_data_keeps_serving(self):
        _fresh_slab_cache()
        from ceph_tpu.ops.gf2 import to_packedbit

        dev = PagedResidentStore(capacity_bytes=1 << 20,
                                 page_bytes=4096, device=True)
        rows = _rows(3, 4096, seed=5)
        bits = np.asarray(to_packedbit(rows))
        assert dev.put_planar("o", bits, w=8, n_rows=3,
                              meta=(1, 4096, 8192), trim=4096,
                              data_rows=16)
        assert dev.shed_parity("o") > 0
        assert dev.get_planar("o") is None  # whole resident is partial
        got = dev.gather_rows("o", 0, 16)  # data prefix still serves
        assert got is not None
        from ceph_tpu.ops.gf2 import from_packedbit

        data = np.asarray(from_packedbit(got, 2))[:, :4096]
        np.testing.assert_array_equal(data, rows[:2])

    def test_device_native_install_from_queue_product(self):
        """A jax-array (queue-shaped) input installs device-native —
        no host bounce, counted as device_installs."""
        _fresh_slab_cache()
        from ceph_tpu.ops.gf2 import to_packedbit

        dev = PagedResidentStore(capacity_bytes=1 << 20,
                                 page_bytes=4096, device=True)
        rows = _rows(3, 2048, seed=77)
        bits = to_packedbit(rows)  # stays a jax array
        assert dev.put_planar("q", bits, w=8, n_rows=3,
                              meta=(1, 2048, 0), trim=2048)
        assert dev.stats()["device_installs"] == 1
        assert dev.stats()["h2d_installs"] == 0
        np.testing.assert_array_equal(dev.read("q"), rows)

    # -- the fused install: one program per touched sub-slab ---------------

    @staticmethod
    def _pair(capacity, page_bytes):
        _fresh_slab_cache()
        return (PagedResidentStore(capacity_bytes=capacity,
                                   page_bytes=page_bytes, device=False),
                PagedResidentStore(capacity_bytes=capacity,
                                   page_bytes=page_bytes, device=True))

    @staticmethod
    def _words(rows, cols, seed):
        return np.random.default_rng(seed).integers(
            0, 1 << 32, (rows, cols), dtype=np.uint32)

    @staticmethod
    def _subslabs(store, key):
        return {pid >> 8 for pid in store._entries[key].pages}

    def _fragment(self, host, dev):
        """Fill three sub-slabs with one-page residents and free pages
        of each in turn, so that the LIFO free list hands a later
        install pages of all three, interleaved."""
        import jax.numpy as jnp

        for i in range(768):
            one = self._words(1, 64, seed=1000 + i)
            host.put_planar(f"f{i}", one, w=8, n_rows=1)
            dev.put_planar(f"f{i}", jnp.asarray(one), w=8, n_rows=1)
        for j in range(6):
            for base in (10, 300, 600):
                for st in (host, dev):
                    assert st.drop(f"f{base + j}")

    @pytest.mark.parametrize("case", [
        "one_page_a_row", "two_pages_a_row", "trimmed_ragged_tail",
        "three_subslabs", "host_image", "put_raw"])
    def test_fused_install_matches_host_arm(self, case):
        """The device arm's fused install leaves a store that reads
        back byte-identical to the host arm's memcpy install, for every
        shape the served paths hand it."""
        import jax.numpy as jnp

        if case == "put_raw":
            host, dev = self._pair(1 << 20, 4096)
            raw = _rows(1, 10001, seed=6).tobytes()
            for st in (host, dev):
                assert st.put_raw("o", raw)
            assert dev.read_raw("o") == host.read_raw("o") == raw
            assert dev.stats()["h2d_installs"] == 1
            return
        trim, native, neighbours = None, True, ()
        if case == "one_page_a_row":      # k=8 m=3, 4 MiB: 88 x 64 KiB
            host, dev = self._pair(8 << 20, 64 << 10)
            bits = self._words(88, 16384, seed=1)
        elif case == "two_pages_a_row":   # k=4 m=2, 4 MiB: 48 x 128 KiB
            host, dev = self._pair(8 << 20, 64 << 10)
            bits = self._words(48, 32768, seed=2)
        elif case == "trimmed_ragged_tail":
            host, dev = self._pair(1 << 20, 4096)
            bits, trim = self._words(24, 128, seed=3), 3000  # 94 of 128
        elif case == "three_subslabs":
            host, dev = self._pair(256 << 10, 256)
            self._fragment(host, dev)
            bits, trim = self._words(8, 128, seed=4), 96 * 32
            neighbours = ("f9", "f16", "f299", "f306", "f599", "f606",
                          "f767")
        else:                             # host_image: a promote's
            host, dev = self._pair(1 << 20, 4096)   # numpy planes, one h2d
            bits, trim, native = self._words(16, 200, seed=5), 6000, False
        assert host.put_planar("o", bits, w=8, n_rows=bits.shape[0] // 8,
                               trim=trim)
        assert dev.put_planar("o", jnp.asarray(bits) if native else bits,
                              w=8, n_rows=bits.shape[0] // 8, trim=trim)
        assert dev.stats()["device_installs" if native
                           else "h2d_installs"] >= 1
        cols = host._entries["o"].cols
        want = bits[:, :cols]
        for st in (host, dev):
            np.testing.assert_array_equal(
                _own(st.get_planar("o")[0], cols), want)
            np.testing.assert_array_equal(
                _own(st.gather_rows("o", 3, 8), cols), want[3:8])
        assert dev._entries["o"].pages == host._entries["o"].pages
        if case == "three_subslabs":
            assert len(self._subslabs(dev, "o")) >= 3
        # the pad rows of a group repeat one of its own pages: nobody
        # else's page may change
        for key in neighbours:
            theirs = host.get_planar(key)[0]
            np.testing.assert_array_equal(
                _own(dev.get_planar(key)[0], theirs.shape[1]), theirs)

    def test_install_programs_counts_subslabs_one_compile_a_shape(self):
        """`install_programs` moves by exactly the sub-slabs an install
        touched (one launch each, nothing else), and installs of one
        shape share one compiled program whatever their group sizes."""
        import jax.numpy as jnp

        from ceph_tpu.ops.slab import SLAB_PERF

        host, dev = self._pair(256 << 10, 256)
        self._fragment(host, dev)
        assert dev.perf.get("install_programs") == 768  # one page each
        compiles = []
        for n, seed in enumerate((7, 8, 9)):
            before = dev.perf.get("install_programs")
            built = SLAB_PERF.get("compile")
            bits = self._words(8, 128, seed=seed)
            # the free list shrinks: 4 + 4 + 4 pages, then 2 + 2 + 2 + 6
            # fresh ones, then 12 in one sub-slab
            assert dev.put_planar(f"o{n}", jnp.asarray(bits), w=8,
                                  n_rows=1, trim=96 * 32)
            compiles.append(SLAB_PERF.get("compile") - built)
            touched = len(self._subslabs(dev, f"o{n}"))
            assert dev.perf.get("install_programs") - before == touched
            np.testing.assert_array_equal(
                _own(dev.get_planar(f"o{n}")[0], 96), bits[:, :96])
        assert len({len(self._subslabs(dev, f"o{n}")) for n in range(3)}) \
            > 1  # the group sizes did differ
        assert compiles == [1, 0, 0]
        assert dev.perf.get("device_installs") == 768 + 3

    def test_fused_gather_compiles_per_bucket_never_per_split(self):
        """Residents of one shape whose pages lie in three, four and one
        sub-slabs read back byte-identical to the host arm, and only the
        first read compiles: the gather's programs are keyed by the page
        bucket and the row range, not by how the free list split the
        span (an eager slice or concatenate compiled once per split, on
        the event loop, inside a served window: PERF.md, PR 33)."""
        import jax
        import jax.numpy as jnp

        from ceph_tpu.ops.slab import SLAB_PERF
        from ceph_tpu.utils.jaxdev import compile_meter

        host, dev = self._pair(256 << 10, 256)
        self._fragment(host, dev)
        meter = compile_meter()
        splits, compiles, kernels = [], [], []
        for n, seed in enumerate((7, 8, 9)):
            bits = self._words(8, 128, seed=seed)
            for st, src in ((host, bits), (dev, jnp.asarray(bits))):
                assert st.put_planar(f"o{n}", src, w=8, n_rows=1,
                                     trim=96 * 32)
            splits.append(len(self._subslabs(dev, f"o{n}")))
            before, built = meter.count, SLAB_PERF.get("compile")
            for r0, r1 in ((0, 8), (0, 5)):
                got = dev.gather_rows(f"o{n}", r0, r1)
                assert isinstance(got, jax.Array)
                np.testing.assert_array_equal(
                    _own(got, 96), host.gather_rows(f"o{n}", r0, r1))
            compiles.append(meter.count - before)
            kernels.append(SLAB_PERF.get("compile") - built)
        assert len(set(splits)) > 1 and max(splits) >= 3, splits
        # first reads: rows 0-8 span 12 pages (bucket 16), rows 0-5 span
        # 8: the two gather programs of each bucket and one program a row
        # range; afterwards nothing, whatever the split
        assert kernels == [6, 0, 0], kernels
        assert compiles[1:] == [0, 0], compiles

    def test_prewarm_compiles_both_gather_programs_of_every_bucket(self):
        from ceph_tpu.ops import slab

        _fresh_slab_cache()
        assert slab.prewarm(64, max_rows=8) == 4 + 3  # 1,2,4,8 and 2,4,8
        assert slab.prewarm(64, max_rows=8) == 0

    def test_env_override_pins_arms(self, monkeypatch):
        monkeypatch.setenv("CEPH_TPU_DEVICE_SLAB", "0")
        st = PagedResidentStore(capacity_bytes=1 << 20,
                                page_bytes=4096, device=True)
        assert not st.device_arm
        monkeypatch.setenv("CEPH_TPU_DEVICE_SLAB", "1")
        st = PagedResidentStore(capacity_bytes=1 << 20,
                                page_bytes=4096, device=False)
        assert st.device_arm
