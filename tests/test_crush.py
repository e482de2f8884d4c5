"""CRUSH + OSDMap tests: hierarchical straw2, firstn/indep rule steps,
chooseleaf failure domains, tester validation, pg_temp/affinity,
incremental maps (reference src/crush/mapper.c, src/osd/OSDMap.cc)."""

import pickle

from ceph_tpu.rados.crush import CRUSH_ITEM_NONE, CrushMap, CrushTester
from ceph_tpu.rados.types import OSDMap, OSDMapIncremental, OsdInfo, PoolInfo


def alive(devs):
    return {d: 1.0 for d in devs}


class TestFlat:
    def test_determinism(self):
        m = CrushMap.flat(list(range(8)))
        m.add_simple_rule("r", mode="indep")
        w = alive(range(8))
        for x in (0, 1, 7, 12345):
            assert m.do_rule("r", x, 5, w) == m.do_rule("r", x, 5, w)

    def test_indep_distinct_and_sized(self):
        m = CrushMap.flat(list(range(10)))
        m.add_simple_rule("r", mode="indep")
        w = alive(range(10))
        for x in range(200):
            acting = m.do_rule("r", x, 6, w)
            assert len(acting) == 6
            live = [a for a in acting if a != CRUSH_ITEM_NONE]
            assert len(live) == len(set(live)) == 6

    def test_indep_hole_when_unplaceable(self):
        m = CrushMap.flat([0, 1, 2])
        m.add_simple_rule("r", mode="indep")
        acting = m.do_rule("r", 42, 5, alive(range(3)))
        assert len(acting) == 5
        assert acting.count(CRUSH_ITEM_NONE) == 2

    def test_firstn_compacts(self):
        m = CrushMap.flat([0, 1, 2])
        m.add_simple_rule("r", mode="firstn")
        out = m.do_rule("r", 42, 5, alive(range(3)))
        assert len(out) == 3  # firstn returns what it found, no holes
        assert CRUSH_ITEM_NONE not in out

    def test_dead_device_never_chosen(self):
        m = CrushMap.flat(list(range(6)))
        m.add_simple_rule("r", mode="indep")
        w = alive(range(6))
        w[3] = 0.0
        for x in range(100):
            assert 3 not in m.do_rule("r", x, 4, w)

    def test_balance(self):
        m = CrushMap.flat(list(range(12)))
        m.add_simple_rule("r", mode="indep")
        stats = CrushTester(m).test("r", 4, n_inputs=2048)
        assert stats["holes"] == 0
        assert len(stats["per_device"]) == 12
        assert stats["max_deviation"] < 0.35  # straw2 balance

    def test_weight_bias(self):
        m = CrushMap.flat([0, 1])
        m.add_simple_rule("r", mode="indep")
        w = {0: 3.0, 1: 1.0}
        counts = {0: 0, 1: 0}
        for x in range(2000):
            counts[m.do_rule("r", x, 1, w)[0]] += 1
        assert counts[0] > 2.2 * counts[1]  # ~3x expected


class TestIndepStability:
    def test_minimal_movement_on_failure(self):
        m = CrushMap.flat(list(range(10)))
        m.add_simple_rule("r", mode="indep")
        stats = CrushTester(m).indep_stability("r", 6, kill=4, n_inputs=400)
        # collateral movement (positions not holding the dead device) must
        # be a small fraction — indep never compacts
        assert stats["collateral_ratio"] < 0.12, stats
        assert stats["affected"] > 0


class TestHierarchy:
    def test_chooseleaf_spreads_over_hosts(self):
        # 12 OSDs on 6 hosts; failure_domain=host => one OSD per host
        m = CrushMap.with_hosts(list(range(12)), 6)
        m.add_simple_rule("r", failure_domain="host", mode="indep")
        w = alive(range(12))
        for x in range(200):
            acting = m.do_rule("r", x, 4, w)
            live = [a for a in acting if a != CRUSH_ITEM_NONE]
            assert len(live) == 4
            hosts = {a % 6 for a in live}  # osd i lives on host i%6
            assert len(hosts) == 4, f"two shards share a host: {acting}"

    def test_chooseleaf_firstn(self):
        m = CrushMap.with_hosts(list(range(8)), 4)
        m.add_simple_rule("rep", failure_domain="host", mode="firstn")
        out = m.do_rule("rep", 7, 3, alive(range(8)))
        assert len(out) == 3
        assert len({a % 4 for a in out}) == 3

    def test_host_failure_reroutes_within_other_hosts(self):
        m = CrushMap.with_hosts(list(range(12)), 6)
        m.add_simple_rule("r", failure_domain="host", mode="indep")
        w = alive(range(12))
        # kill host1 entirely (osds 1 and 7)
        w[1] = w[7] = 0.0
        for x in range(100):
            acting = m.do_rule("r", x, 4, w)
            live = [a for a in acting if a != CRUSH_ITEM_NONE]
            assert 1 not in live and 7 not in live

    def test_more_domains_than_needed_unplaceable(self):
        m = CrushMap.with_hosts(list(range(4)), 2)
        m.add_simple_rule("r", failure_domain="host", mode="indep")
        acting = m.do_rule("r", 11, 3, alive(range(4)))
        # only 2 hosts exist: third position must be a hole
        assert acting.count(CRUSH_ITEM_NONE) == 1

    def test_editing_api(self):
        m = CrushMap()
        root = m.add_bucket("root", "default")
        h0 = m.add_bucket("host", "h0")
        m.add_item(root, h0)
        m.add_item(h0, 0, 1.0)
        m.add_item(h0, 1, 1.0)
        assert m.devices() == [0, 1]
        m.remove_item(1)
        assert m.devices() == [0]
        h1 = m.add_bucket("host", "h1")
        m.add_item(root, h1)
        m.move_item(0, h1)
        assert 0 in m.buckets[h1].items and 0 not in m.buckets[h0].items

    def test_remove_bucket_unlinks_and_drops(self):
        m = CrushMap.with_hosts(list(range(6)), 3)
        m.add_simple_rule("r", failure_domain="host", mode="indep")
        w = alive(range(6))
        before = [m.do_rule("r", x, 3, w) for x in range(50)]
        h2 = m.bucket_by_name("host2")
        gone = list(h2.items)
        m.remove_bucket(h2.id)
        assert h2.id not in m.buckets and m.parent_of(h2.id) is None
        assert h2.id not in m.buckets[m.root_id].items
        after = [m.do_rule("r", x, 3, w) for x in range(50)]
        assert after != before
        assert not any(d in acting for acting in after for d in gone)
        m.remove_bucket(h2.id)  # idempotent, like remove_item


class TestTester:
    def test_a_repeated_sweep_gives_the_same_statistics(self):
        """The tester's second pass over a map is all lookups where it
        fits the memo, all draws where it does not: same numbers."""
        m = CrushMap.with_hosts(list(range(12)), 4)
        m.add_simple_rule("r", failure_domain="host", mode="indep")
        t = CrushTester(m)
        for n in (64, 2048):
            assert t.test("r", 4, n_inputs=n) == t.test("r", 4, n_inputs=n)
        assert t.indep_stability("r", 4, kill=5) \
            == t.indep_stability("r", 4, kill=5)


class TestHostDomainCluster:
    def test_ec_pool_over_host_failure_domain(self):
        import asyncio
        import os

        from ceph_tpu.rados.vstart import Cluster

        async def go():
            conf = {"crush_num_hosts": 4, "osd_heartbeat_interval": 0.2,
                    "mon_osd_report_grace": 1.5, "osd_auto_repair": False}
            cluster = Cluster(n_osds=8, conf=conf)
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("hostec", profile={
                    "plugin": "jerasure", "technique": "reed_sol_van",
                    "k": "2", "m": "1", "crush-failure-domain": "host"})
                blob = os.urandom(20_000)
                await c.put(pool, "obj", blob)
                # shards must sit on 3 distinct hosts (osd i -> host i%4)
                p = c.osdmap.pools[pool]
                pg = c.osdmap.object_to_pg(p, "obj")
                acting = c.osdmap.pg_to_acting(p, pg)
                live = [a for a in acting if a >= 0]
                assert len({a % 4 for a in live}) == len(live) == 3
                assert await c.get(pool, "obj") == blob
            finally:
                await cluster.stop()

        asyncio.run(go())


class TestOSDMapFeatures:
    def _map(self, n=6):
        m = OSDMap(epoch=5, crush=CrushMap.flat(list(range(n))))
        for i in range(n):
            m.osds[i] = OsdInfo(osd_id=i, addr=("127.0.0.1", 7000 + i))
        m.crush.add_simple_rule("p-rule", mode="indep")
        m.pools[1] = PoolInfo(pool_id=1, name="p", pool_type="ec", pg_num=8,
                              size=4, min_size=3, rule="p-rule")
        return m

    def test_pg_temp_overrides_crush(self):
        m = self._map()
        pool = m.pools[1]
        natural = m.pg_to_acting(pool, 3)
        override = [5, 4, 1, 0]
        m.pg_temp[(1, 3)] = override
        assert m.pg_to_acting(pool, 3) == override
        assert m.pg_to_acting(pool, 4) != override or natural == override
        del m.pg_temp[(1, 3)]
        assert m.pg_to_acting(pool, 3) == natural

    def test_primary_affinity_demotes(self):
        m = self._map()
        pool = m.pools[1]
        acting = m.pg_to_acting(pool, 0)
        first = acting[0]
        m.primary_affinity[first] = 0.0  # never primary if alternatives
        p = m.primary_of(acting)
        assert p != first
        m.primary_affinity[first] = 1.0
        assert m.primary_of(acting) == first

    def test_incremental_roundtrip(self):
        old = self._map()
        new = pickle.loads(pickle.dumps(old))
        new.epoch = 6
        new.osds[0].up = False
        new.osds[0].in_cluster = False
        new.pools[2] = PoolInfo(pool_id=2, name="q", pool_type="ec", pg_num=4,
                                size=3, min_size=2, rule="p-rule")
        new.pg_temp[(1, 2)] = [3, 2, 1, 0]
        new.primary_affinity[5] = 0.5
        inc = OSDMapIncremental.diff(old, new)
        replica = pickle.loads(pickle.dumps(old))
        assert replica.apply_incremental(inc)
        assert replica.epoch == 6
        assert not replica.osds[0].up
        assert replica.pools[2].name == "q"
        assert replica.pg_temp[(1, 2)] == [3, 2, 1, 0]
        assert replica.primary_affinity[5] == 0.5

    def test_incremental_chain_gap_rejected(self):
        old = self._map()
        inc = OSDMapIncremental(epoch=9, base_epoch=7)
        assert not old.apply_incremental(inc)  # our epoch is 5, not 7
        assert old.epoch == 5
