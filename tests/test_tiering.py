"""Cache-tier subsystem (ceph_tpu/rados/tiering.py + the OSD hooks):
BloomHitSet statistics and binary encoding, HitSetArchive rotation /
expiry / temperature, the promotion throttle, coldest-first eviction
candidates, the resident store's agent/LRU race discipline, and the
end-to-end promote -> resident-hit -> evict lifecycle — including the
byte-identity gate (every resident-hit read equals the cold-path read)
and bounded residency under a hot set larger than target_max_bytes."""

import asyncio
import os

import numpy as np
import pytest

from ceph_tpu.rados.pagestore import PagedResidentStore
from ceph_tpu.rados import osd as osdmod
from ceph_tpu.rados.tiering import (BloomHitSet, HitSetArchive,
                                    PromoteThrottle, build_tier_perf,
                                    eviction_candidates)
from ceph_tpu.rados.vstart import Cluster

PROFILE = {"plugin": "jerasure", "technique": "reed_sol_van",
           "k": "2", "m": "1"}


def run(coro, timeout=180):
    asyncio.run(asyncio.wait_for(coro, timeout))


@pytest.fixture()
def force_batching(monkeypatch):
    monkeypatch.setenv("CEPH_TPU_FORCE_BATCH", "1")


# -- BloomHitSet -------------------------------------------------------------


class TestBloomHitSet:
    def test_no_false_negatives(self):
        hs = BloomHitSet(256, 0.05, seed=3)
        oids = [f"obj-{i}" for i in range(256)]
        for oid in oids:
            hs.insert(oid)
        assert all(oid in hs for oid in oids)

    @pytest.mark.parametrize("target_fpp", [0.01, 0.05, 0.1])
    def test_measured_fpp_within_2x_of_target(self, target_fpp):
        """At the design insert count, the MEASURED false-positive rate
        over a large disjoint probe set stays within 2x the configured
        target (the sizing math holds)."""
        hs = BloomHitSet(target_size=512, fpp=target_fpp, seed=11)
        for i in range(512):
            hs.insert(f"member-{i}")
        probes = 20_000
        fp = sum(1 for i in range(probes) if f"stranger-{i}" in hs)
        measured = fp / probes
        assert measured <= 2.0 * target_fpp, (
            f"measured fpp {measured} > 2x target {target_fpp}")
        # the estimator gauge tracks the same reality
        assert hs.estimated_fpp() <= 2.0 * target_fpp

    def test_encode_decode_roundtrip(self):
        hs = BloomHitSet(64, 0.02, seed=99)
        for i in range(64):
            hs.insert(f"o{i}")
        blob = hs.encode()
        back, off = BloomHitSet.decode(blob)
        assert off == len(blob)
        assert (back.seed, back.nhash, back.nbits, back.inserted,
                back.target_size, back.fpp) == \
               (hs.seed, hs.nhash, hs.nbits, hs.inserted,
                hs.target_size, hs.fpp)
        assert all(f"o{i}" in back for i in range(64))
        # decoded filter answers identically on non-members too
        for i in range(500):
            assert (f"x{i}" in back) == (f"x{i}" in hs)

    def test_decode_rejects_garbage(self):
        import struct

        with pytest.raises(ValueError):
            BloomHitSet.decode(b"short")
        good = BloomHitSet(8, 0.1).encode()
        with pytest.raises(ValueError):
            BloomHitSet.decode(b"\x00\x00" + good[2:])  # bad magic
        with pytest.raises(ValueError):
            BloomHitSet.decode(good[:-1])  # truncated bits
        # valid magic but implausible params: nbits=0 would divide by
        # zero on record(), nhash=0 makes contains() vacuously True
        # (every object reads hot) — both must fail loudly at decode
        hdr = struct.Struct("<HHQHIIId")
        for nhash, nbits in ((0, 64), (5, 0), (500, 64)):
            blob = hdr.pack(0xB1F5, 1, 0, nhash, nbits, 0, 8, 0.05) \
                + b"\x00" * ((nbits + 7) // 8)
            with pytest.raises(ValueError):
                BloomHitSet.decode(blob)

    def test_seed_varies_hashing(self):
        a, b = BloomHitSet(8, 0.05, seed=1), BloomHitSet(8, 0.05, seed=2)
        a.insert("x")
        b.insert("x")
        assert a.encode() != b.encode()


def _popcount(hs):
    """The set bits of a filter, walked afresh: what the parent's
    fill_ratio summed on every call."""
    return sum(bin(b).count("1") for b in hs._bits)


def _old_fpp(hs):
    return (_popcount(hs) / hs.nbits) ** hs.nhash


def _filters_after_inserts():
    hs = BloomHitSet(128, 0.05, seed=11)
    for i in range(300):
        hs.insert(f"obj-{i % 90}")  # repeats flip nothing twice
    return [hs]


def _filters_after_roundtrip():
    hs = _filters_after_inserts()[0]
    back, _ = BloomHitSet.decode(hs.encode())
    assert back.encode() == hs.encode()  # the count is not on the wire
    back.insert("one-more")  # a decoded filter keeps counting
    # a foreign blob's stray bits past nbits count as the parent's
    # per-byte sum counted them
    odd = BloomHitSet(5, 0.3, seed=2)
    assert odd.nbits % 8
    blob = bytearray(odd.encode())
    blob[-1] |= 0x80
    stray, _ = BloomHitSet.decode(bytes(blob))
    return [back, stray]


def _filters_after_retune_and_rotation():
    arch = HitSetArchive(period=1.0, count=4, target_size=32, fpp=0.05,
                         seed=7, now=0.0)
    for t in range(6):
        for i in range(20 + t):
            arch.record(f"o{i}", now=t * 1.1)
    arch.retune(2.0, 2, 64, 0.01)
    assert len(arch.archived) == 2
    arch.record("after-retune", now=20.0)  # rotates: a fresh, resized set
    assert arch.current.target_size == 64
    back = HitSetArchive.decode(arch.encode(now=21.0), now=5.0)
    assert back.estimated_fpp() == arch.estimated_fpp()
    assert back.dump()["archived"] == [
        {**d, "start": b["start"], "end": b["end"]}
        for d, b in zip(arch.dump()["archived"], back.dump()["archived"])]
    sets = [arch.current] + [h for _, _, h in arch.archived]
    # the admin dump prints the parent's numbers
    assert [(d["fill_ratio"], d["estimated_fpp"])
            for d in [arch.dump()["current"]] + arch.dump()["archived"]] \
        == [(round(_popcount(h) / h.nbits, 4), round(_old_fpp(h), 6))
            for h in sets]
    return sets + [back.current] + [h for _, _, h in back.archived]


@pytest.mark.parametrize("build", [
    _filters_after_inserts, _filters_after_roundtrip,
    _filters_after_retune_and_rotation], ids=lambda f: f.__name__[9:])
def test_running_count_equals_a_fresh_popcount(build):
    """ISSUE 34: a filter's running count of set bits is what a walk of
    the bits gives, however the bits came to be, and the fill ratio and
    the estimated fpp are the parent's values to the last bit."""
    filters = build()
    assert any(hs._ones for hs in filters)
    for hs in filters:
        assert hs._ones == _popcount(hs)
        assert hs.fill_ratio() == _popcount(hs) / hs.nbits
        assert hs.estimated_fpp() == _old_fpp(hs)


def test_a_rotation_with_30_archives_present_walks_no_filter():
    """The gauge after a rotation asks every archive the OSD holds
    (~29 x 9 filters on a k=8 m=3 OSD) for its fpp: no filter's bits are
    walked for it, and `hitset_fpp_ppm` reads what the walk gave."""
    from types import SimpleNamespace

    walked = [0]

    class Walked(bytearray):
        def __iter__(self):
            walked[0] += len(self)
            return super().__iter__()

        def __getitem__(self, i):
            walked[0] += 1
            return super().__getitem__(i)

    archives = {}
    for pg in range(30):
        arch = HitSetArchive(period=1.0, count=8, seed=pg, now=0.0)
        for t in range(10):
            for i in range(3 * pg + t):
                arch.record(f"pg{pg}/o{i}", now=float(t) * 1.01)
        assert len(arch.archived) == 8
        archives[(1, pg)] = arch
    sets = [h for a in archives.values()
            for h in [a.current] + [h for _, _, h in a.archived]]
    assert len(sets) == 270
    want = int(max(_old_fpp(h) for h in sets) * 1e6)
    assert want > 0
    for h in sets:
        h._bits = Walked(h._bits)
    pushed = []
    osd = SimpleNamespace(
        tier_perf=build_tier_perf(), _hit_sets=archives,
        _replicate_hit_set=lambda *a: pushed.append(a))
    arch = archives[(1, 3)]
    assert arch.record("rotates", now=100.0)
    osdmod.OSD._tier_rotated(osd, None, 3, [0, 1, 2], arch)
    assert pushed == [(None, 3, [0, 1, 2], arch)]
    assert osd.tier_perf.get("hitset_rotations") == 1
    assert osd.tier_perf.get("hitset_fpp_ppm") == want
    assert osd.tier_perf.get("hitset_bits_scanned") == 0
    assert walked[0] == 0
    assert _popcount(sets[0]) and walked[0] == len(sets[0]._bits)  # it sees


# -- HitSetArchive -----------------------------------------------------------


class TestHitSetArchive:
    def test_rotation_and_expiry(self):
        arch = HitSetArchive(period=1.0, count=3, now=0.0)
        assert not arch.record("a", now=0.5)
        assert arch.record("a", now=1.5)  # crossed the period: rotated
        # drive 5 more rotations: the deque must hold only `count`
        for i in range(5):
            arch.record("a", now=3.0 + i * 1.5)
        assert len(arch.archived) == 3
        # archived intervals are contiguous, newest first
        starts = [s for s, _e, _h in arch.archived]
        assert starts == sorted(starts, reverse=True)

    def test_recency_semantics(self):
        arch = HitSetArchive(period=1.0, count=4, now=0.0)
        assert arch.recency("a") == 0
        arch.record("a", now=0.1)
        assert arch.recency("a") == 1  # current interval
        arch.rotate(now=1.1)
        arch.record("a", now=1.2)
        assert arch.recency("a") == 2  # current + previous
        arch.rotate(now=2.2)
        # not in the (empty) current interval: recency resets to 0
        assert arch.recency("a") == 0
        arch.record("b", now=2.3)
        assert arch.recency("b") == 1

    def test_temperature_monotone_across_intervals(self):
        """More intervals containing an object => strictly higher
        temperature; a hit in a newer interval outweighs the same hit
        in an older one."""
        arch = HitSetArchive(period=1.0, count=4, now=0.0)
        # interval layout (oldest..newest archived, then current):
        #   old_only   hits interval 0 only
        #   new_only   hits interval 2 only
        #   everywhere hits every interval
        arch.record("old_only", now=0.1)
        arch.record("everywhere", now=0.1)
        arch.rotate(now=1.0)
        arch.record("everywhere", now=1.1)
        arch.rotate(now=2.0)
        arch.record("new_only", now=2.1)
        arch.record("everywhere", now=2.1)
        arch.rotate(now=3.0)
        arch.record("everywhere", now=3.1)
        t_cold = arch.temperature("never_seen")
        t_old = arch.temperature("old_only")
        t_new = arch.temperature("new_only")
        t_all = arch.temperature("everywhere")
        assert t_cold == 0.0
        assert t_cold < t_old < t_new < t_all <= 1.0

    def test_empty_intervals_archive_too(self):
        arch = HitSetArchive(period=1.0, count=4, now=0.0)
        arch.record("a", now=0.1)
        arch.rotate(now=1.0)
        arch.rotate(now=2.0)  # empty interval archived
        assert len(arch.archived) == 2
        assert arch.recency("a") == 0  # the idle gap breaks recency

    def test_encode_decode_preserves_scores(self):
        arch = HitSetArchive(period=2.0, count=4, target_size=32,
                             fpp=0.05, seed=7, now=0.0)
        arch.record("hot", now=0.5)
        arch.record("hot", now=2.5)  # rotates
        arch.record("warm", now=2.6)
        blob = arch.encode(now=3.0)
        back = HitSetArchive.decode(blob)
        for oid in ("hot", "warm", "cold"):
            assert back.recency(oid) == arch.recency(oid)
            assert back.temperature(oid) == arch.temperature(oid)
        assert back.params_key() == arch.params_key()
        with pytest.raises(ValueError):
            HitSetArchive.decode(blob[:10])

    def test_decode_rebases_to_receiver_clock(self):
        """Monotonic clocks are per-boot: a decoded archive's intervals
        rebase so the sender's 'now' maps to the receiver's 'now' —
        rotation keeps working on a host whose clock reads smaller (or
        far larger) than the sender's."""
        arch = HitSetArchive(period=2.0, count=4, now=1_000_000.0)
        arch.record("hot", now=1_000_000.5)
        blob = arch.encode(now=1_000_001.0)  # sender uptime ~11 days
        back = HitSetArchive.decode(blob, now=50.0)  # receiver: 50s up
        assert back.recency("hot") == 1
        # the adopted current interval is ~1s old in RECEIVER time: not
        # yet due, and due after one period elapses locally
        assert not back.rotate_due(now=50.5)
        assert back.rotate_due(now=52.1)

    def test_corpus_frame_pins_archive_encoding(self):
        """The archived MOSDPGHitSet wire frame's blob decodes with
        TODAY's HitSetArchive and still answers the canned membership
        questions — the BloomHitSet binary layout is pinned by the
        corpus exactly like the message layouts."""
        import struct

        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "corpus", "wire",
            "MOSDPGHitSet.frame")
        with open(path, "rb") as f:
            raw = f.read()
        hdr = struct.Struct("<HHBI")
        _tid, _ver, _fixed, plen = hdr.unpack_from(raw, 0)
        off = hdr.size + plen
        (blen,) = struct.unpack_from("<I", raw, off)
        blob = raw[off + 4:off + 4 + blen]
        # the frame's blob lane carries `archive` (BLOB-less fixed
        # messages embed it in the payload; find it either way)
        from ceph_tpu.rados.messenger import decode_message
        import ceph_tpu.rados.types  # noqa: F401

        msg = decode_message(_tid, _ver, raw[hdr.size:hdr.size + plen],
                             blob if blen else None, bool(_fixed))
        arch = HitSetArchive.decode(bytes(msg.archive))
        # wire_corpus.py recorded: hot in current AND previous interval,
        # warm in current only
        assert arch.recency("corpus/hot") == 2
        assert arch.recency("corpus/warm") == 1
        assert arch.recency("corpus/cold") == 0


# -- PromoteThrottle ---------------------------------------------------------


class TestPromoteThrottle:
    def test_object_and_byte_buckets(self):
        t = PromoteThrottle(max_objects_sec=2, max_bytes_sec=1000,
                            now=0.0)
        assert t.allow(400, now=0.0)
        assert t.allow(400, now=0.0)
        assert not t.allow(100, now=0.0)  # object bucket empty
        assert t.allow(100, now=1.0)  # refilled
        # byte bucket binds even with objects available
        t2 = PromoteThrottle(max_objects_sec=100, max_bytes_sec=1000,
                             now=0.0)
        assert t2.allow(900, now=0.0)
        assert not t2.allow(900, now=0.0)

    def test_zero_disables_dimension(self):
        t = PromoteThrottle(max_objects_sec=0, max_bytes_sec=0, now=0.0)
        for _ in range(100):
            assert t.allow(1 << 30, now=0.0)

    def test_fractional_object_rate_admits_slowly(self):
        """0.5 objects/sec must admit one promotion every 2 seconds —
        not zero ever (the bucket holds at least one whole object)."""
        t = PromoteThrottle(max_objects_sec=0.5, max_bytes_sec=0,
                            now=0.0)
        assert t.allow(100, now=0.0)
        assert not t.allow(100, now=0.5)
        assert not t.allow(100, now=1.5)
        assert t.allow(100, now=2.1)

    def test_no_unbounded_banking(self):
        t = PromoteThrottle(max_objects_sec=2, max_bytes_sec=10_000,
                            now=0.0)
        # a long idle period banks at most one second's budget
        allowed = sum(1 for _ in range(10) if t.allow(1, now=100.0))
        assert allowed == 2


# -- eviction candidates -----------------------------------------------------


class TestEvictionCandidates:
    def test_coldest_first_until_covered(self):
        temps = {"a": 0.9, "b": 0.1, "c": 0.5, "d": 0.0}
        entries = [("a", 100), ("b", 100), ("c", 100), ("d", 100)]
        plan = eviction_candidates(entries, temps.__getitem__, 150)
        assert plan == [("d", 100), ("b", 100)]

    def test_temperature_tie_breaks_toward_lru_older(self):
        entries = [("older", 100), ("newer", 100)]
        plan = eviction_candidates(entries, lambda k: 0.5, 50)
        assert plan == [("older", 100)]

    def test_no_need_no_plan(self):
        assert eviction_candidates([("a", 1)], lambda k: 0.0, 0) == []


# -- resident store agent discipline -----------------------------------------


class TestStoreAgentRace:
    def _store_with(self, keys, capacity=1 << 30):
        # one 2 KiB page a resident: [8, 64] u32 plane words
        store = PagedResidentStore(capacity_bytes=capacity, page_bytes=2048)
        for k in keys:
            store.put_planar(k, np.zeros((8, 64), dtype=np.uint32),
                             w=8, n_rows=8, meta=(1, 64, 64))
        return store

    def test_drop_reports_and_tolerates_absence(self):
        store = self._store_with(["a"])
        assert store.drop("a") is True
        assert store.drop("a") is False  # counted no-op, no error
        assert store.drop("never") is False

    def test_agent_evict_of_lru_dropped_entry_is_counted_noop(self):
        """The regression for the agent/LRU race: the agent plans an
        eviction, the LRU (or a concurrent write/delete) drops the entry
        first — applying the plan must count a no-op, never raise, and
        the perf counters must reflect exactly what happened."""
        store = self._store_with(["a", "b"])
        perf = build_tier_perf()
        plan = eviction_candidates(store.entries_snapshot(),
                                   lambda k: 0.0, 1 << 30)
        assert len(plan) == 2
        store.drop("a")  # the LRU wins the race for one entry
        for key, nbytes in plan:
            if store.drop(key):
                perf.inc("agent_evict")
                perf.inc("agent_evict_bytes", nbytes)
            else:
                perf.inc("agent_evict_noop")
        d = perf.dump()
        assert d["agent_evict"] == 1
        assert d["agent_evict_noop"] == 1
        assert store.resident_bytes == 0

    def test_lru_eviction_of_agent_planned_entry(self):
        """The inverse race: capacity pressure LRU-evicts an entry the
        agent already ranked; the snapshot stays a plain list and the
        drop is a no-op."""
        store = self._store_with(["a"], capacity=8 * 64 * 4 + 1)
        plan = eviction_candidates(store.entries_snapshot(),
                                   lambda k: 0.0, 1 << 30)
        # a second admit LRU-evicts "a" under the byte budget
        store.put_planar("b", np.zeros((8, 64), dtype=np.uint32),
                         w=8, n_rows=8, meta=(1, 64, 64))
        assert "a" not in store
        assert store.drop(plan[0][0]) is False

    def test_memo_lifecycle(self):
        """The exit-boundary memo lives and dies with its entry: set on
        a resident, invalidated by re-put / drop / LRU evict, refused
        for non-residents, version-gated on read."""
        store = self._store_with(["a"])
        store.memo_put("a", 1, b"packed-at-v1")
        assert store.memo_get("a", 1) == b"packed-at-v1"
        assert store.memo_get("a", 2) is None  # version-gated
        # re-put at a new version kills the memo
        store.put_planar("a", np.zeros((8, 64), dtype=np.uint32),
                         w=8, n_rows=8, meta=(2, 64, 64))
        assert store.memo_get("a", 1) is None
        # memo for a non-resident key is refused
        store.memo_put("ghost", 1, b"x")
        assert store.memo_get("ghost", 1) is None
        # drop kills the memo
        store.memo_put("a", 2, b"v2")
        store.drop("a")
        assert store.memo_get("a", 2) is None
        assert store.memo_bytes == 0

    def test_memo_bytes_accounted_and_capped(self):
        """Memo host RAM is tracked (memo_bytes gauge) and bounded by
        the store's capacity: a memo that would blow the budget is
        refused (costs a re-pack, never correctness), and replacing or
        dropping an entry returns its bytes."""
        store = self._store_with(["a", "b"], capacity=10_000)
        store.memo_put("a", 1, b"x" * 6_000)
        assert store.memo_bytes == 6_144  # charged in whole pages
        # over budget: refused, accounting unchanged
        store.memo_put("b", 1, b"y" * 6_000)
        assert store.memo_get("b", 1) is None
        assert store.memo_bytes == 6_144
        # replacement returns the old bytes first
        store.memo_put("a", 2, b"z" * 2_000)
        assert store.memo_bytes == 2_048
        store.drop("a")
        assert store.memo_bytes == 0


# -- end-to-end through a cluster --------------------------------------------


class TestTierEndToEnd:
    def test_promotion_serves_byte_identical_resident_hits(
            self, force_batching):
        """The byte-identity gate: a cold-path read, the promoted
        resident-hit read, and the original bytes all agree; promotion
        is recency-gated and recorded in the `tier` perf set."""
        async def go():
            cluster = Cluster(n_osds=4, conf={
                "osd_auto_repair": False, "client_op_timeout": 60.0,
                "osd_hit_set_period": 30.0,
                "osd_min_read_recency_for_promote": 1})
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("t", profile=dict(PROFILE))
                store = osdmod.shared_planar_store()
                assert store is not None
                blob = os.urandom(120_000)
                await c.put(pool, "obj", blob)
                # drop the write-path residency so the READ path must
                # promote (not inherit) the resident
                for o in cluster.osds.values():
                    if o._planar is not None:
                        o._planar.drop(o._planar_key(pool, "obj"))
                cold = await c.get(pool, "obj")
                assert cold == blob
                for _ in range(200):
                    if any(o._planar is not None
                           and o._planar_key(pool, "obj") in store
                           for o in cluster.osds.values()):
                        break
                    await asyncio.sleep(0.01)
                else:
                    raise AssertionError("promotion never landed")
                hits0 = sum(o.tier_perf.get("resident_hit")
                            for o in cluster.osds.values())
                hot = await c.get(pool, "obj")
                assert hot == cold == blob
                assert sum(o.tier_perf.get("resident_hit")
                           for o in cluster.osds.values()) == hits0 + 1
                assert sum(o.tier_perf.get("promote")
                           for o in cluster.osds.values()) == 1
                # overwrite invalidates: both paths serve the NEW bytes
                blob2 = os.urandom(110_000)
                await c.put(pool, "obj", blob2)
                assert await c.get(pool, "obj") == blob2
                await c.stop()
            finally:
                await cluster.stop()

        run(go())

    def test_recency_gate_and_fadvise(self, force_batching):
        """min_read_recency_for_promote=2 defers promotion to the
        second interval; dontneed reads never record or promote;
        willneed promotes immediately.  The interval is one the test
        cannot outlast and ends when the test says so: under a loaded
        host a put and a get 0.3 s apart fell into two intervals and
        the first get promoted."""
        async def go():
            cluster = Cluster(n_osds=3, conf={
                "osd_auto_repair": False, "client_op_timeout": 60.0,
                "osd_hit_set_period": 3600.0,
                "osd_min_read_recency_for_promote": 2})
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("t", profile=dict(PROFILE))
                store = osdmod.shared_planar_store()
                blob = os.urandom(60_000)
                await c.put(pool, "obj", blob)

                def drop():
                    for o in cluster.osds.values():
                        if o._planar is not None:
                            o._planar.drop(o._planar_key(pool, "obj"))

                def resident():
                    return any(o._planar is not None
                               and o._planar_key(pool, "obj") in store
                               for o in cluster.osds.values())

                def counters(name):
                    return sum(o.tier_perf.get(name)
                               for o in cluster.osds.values())

                drop()
                # dontneed: no record, no promote
                assert await c.get(pool, "obj",
                                   fadvise="dontneed") == blob
                await asyncio.sleep(0.05)
                assert counters("read_hits_recorded") == 0
                assert not resident()
                # recency 1 < 2: recorded but not promoted yet
                assert await c.get(pool, "obj") == blob
                await asyncio.sleep(0.05)
                assert counters("read_hits_recorded") == 1
                assert not resident()
                # next interval: recency reaches 2 -> promoted
                for o in cluster.osds.values():
                    for arch in o._hit_sets.values():
                        arch.rotate()
                assert await c.get(pool, "obj") == blob
                for _ in range(200):
                    if resident():
                        break
                    await asyncio.sleep(0.01)
                assert resident()
                assert counters("promote") == 1
                # willneed bypasses recency outright
                drop()
                assert await c.get(pool, "obj",
                                   fadvise="willneed") == blob
                for _ in range(200):
                    if resident():
                        break
                    await asyncio.sleep(0.01)
                assert resident()
                await c.stop()
            finally:
                await cluster.stop()

        run(go())

    def test_promotion_survives_trimmed_pg_log(self, force_batching):
        """A long-lived hot object whose write entry aged out of the
        per-PG log window must STILL promote: an absent log entry means
        'no recent write', not 'stale' (the serving paths re-validate
        the resident's version on every read regardless)."""
        async def go():
            cluster = Cluster(n_osds=3, conf={
                "osd_auto_repair": False, "client_op_timeout": 60.0,
                "osd_hit_set_period": 30.0,
                "osd_min_read_recency_for_promote": 1})
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("t", profile=dict(PROFILE))
                store = osdmod.shared_planar_store()
                blob = os.urandom(80_000)
                await c.put(pool, "ancient", blob)
                # simulate the log window aging the entry out, and drop
                # the write-path residency so the READ must promote
                for o in cluster.osds.values():
                    for log in o._pglogs.values():
                        log.entries.clear()
                    if o._planar is not None:
                        o._planar.drop(o._planar_key(pool, "ancient"))
                assert await c.get(pool, "ancient") == blob
                for _ in range(200):
                    if any(o._planar is not None
                           and o._planar_key(pool, "ancient") in store
                           for o in cluster.osds.values()):
                        break
                    await asyncio.sleep(0.01)
                else:
                    raise AssertionError(
                        "trimmed-log object never promoted")
                assert sum(o.tier_perf.get("promote_stale")
                           for o in cluster.osds.values()) == 0
                assert await c.get(pool, "ancient") == blob
                await c.stop()
            finally:
                await cluster.stop()

        run(go())

    def test_promotion_throttle_counts_refusals(self, force_batching):
        async def go():
            cluster = Cluster(n_osds=3, conf={
                "osd_auto_repair": False, "client_op_timeout": 60.0,
                "osd_hit_set_period": 30.0,
                "osd_min_read_recency_for_promote": 1,
                # one object per 5 seconds: of a 4-read burst exactly
                # one promotion is admitted; the rest are refused and
                # counted (a refill can't sneak in on a slow host).
                # Write installs ride the SAME throttle since the
                # write-heat gate landed — gate them off so the seed
                # writes can't spend the one token this test counts
                "osd_min_write_recency_for_promote": 99,
                "osd_tier_promote_max_objects_sec": 0.2,
                "osd_tier_promote_max_bytes_sec": 0})
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("t", profile=dict(PROFILE))
                blobs = {f"o{i}": os.urandom(50_000) for i in range(4)}
                for oid, blob in blobs.items():
                    await c.put(pool, oid, blob)
                for o in cluster.osds.values():
                    if o._planar is not None:
                        for oid in blobs:
                            o._planar.drop(o._planar_key(pool, oid))
                for oid, blob in blobs.items():
                    assert await c.get(pool, oid) == blob

                def counts():
                    names = ("promote", "promote_throttled",
                             "promote_stale", "promote_skipped")
                    return {n: sum(o.tier_perf.get(n)
                                   for o in cluster.osds.values())
                            for n in names}

                # every read either funded a promote task (which lands
                # asynchronously — poll, don't sleep: the encode can
                # outlast a fixed nap under full-suite load) or was
                # refused by the throttle at read time
                for _ in range(1000):
                    got = counts()
                    if sum(got.values()) >= 4:
                        break
                    await asyncio.sleep(0.01)
                got = counts()
                assert got["promote"] >= 1, got
                assert got["promote_throttled"] >= 1, (
                    f"burst promotions were not throttled: {got}")
                await c.stop()
            finally:
                await cluster.stop()

        run(go())

    def test_agent_bounds_residency_under_oversized_hot_set(
            self, force_batching):
        """The enforcement gate: a hot set larger than target_max_bytes
        keeps reading successfully while the best-effort agent holds
        resident_bytes at/below the target."""
        async def go():
            target = 2 << 20
            cluster = Cluster(n_osds=3, conf={
                "osd_auto_repair": False, "client_op_timeout": 60.0,
                "osd_heartbeat_interval": 0.1,
                "osd_hit_set_period": 0.5,
                "osd_tier_agent_interval": 0.1,
                "osd_tier_target_max_bytes": target,
                "osd_cache_target_full_ratio": 0.8})
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("t", profile=dict(PROFILE))
                store = osdmod.shared_planar_store()
                blobs = {}
                for i in range(40):  # ~8 MB logical >> 2 MB target
                    blobs[f"o{i}"] = os.urandom(200_000)
                    await c.put(pool, f"o{i}", blobs[f"o{i}"])
                # enforcement is on the agent cadence (0.1s passes, one
                # at a time through the best-effort queue): poll to a
                # deadline instead of a fixed sleep — a loaded host can
                # leave the agent a pass behind at any fixed instant
                async def settle():
                    deadline = asyncio.get_event_loop().time() + 6.0
                    while store.resident_bytes > target:
                        if asyncio.get_event_loop().time() > deadline:
                            break
                        await asyncio.sleep(0.1)
                await settle()
                assert store.resident_bytes <= target, (
                    f"agent failed: {store.resident_bytes} > {target}")
                for oid, blob in blobs.items():
                    assert await c.get(pool, oid) == blob
                await settle()
                assert store.resident_bytes <= target
                evicted = sum(o.tier_perf.get("agent_evict")
                              for o in cluster.osds.values())
                assert evicted > 0
                # status surfaces reflect the same numbers
                some = next(iter(cluster.osds.values()))
                st = some.tier_status()
                assert st["target_max_bytes"] == target
                assert "perf" in st and "agent_evict" in st["perf"]
                await c.stop()
            finally:
                await cluster.stop()

        run(go())

    def test_hit_set_replication_and_asok(self, force_batching):
        """Rotation pushes the encoded archive to acting peers
        (MOSDPGHitSet): a non-primary ends up holding temperature state;
        dump_hit_sets / tier status answer on the admin socket seam."""
        async def go():
            cluster = Cluster(n_osds=3, conf={
                "osd_auto_repair": False, "client_op_timeout": 60.0,
                "osd_hit_set_period": 0.2,
                "osd_min_read_recency_for_promote": 1})
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("t", profile=dict(PROFILE))
                blob = os.urandom(40_000)
                await c.put(pool, "obj", blob)
                # reads across two+ periods force a rotation (and with
                # it the archive push)
                for _ in range(3):
                    assert await c.get(pool, "obj") == blob
                    await asyncio.sleep(0.25)
                rotations = sum(o.tier_perf.get("hitset_rotations")
                                for o in cluster.osds.values())
                assert rotations >= 1
                holders = [o for o in cluster.osds.values()
                           if o._hit_sets]
                assert len(holders) >= 2, (
                    "archive was not replicated off the primary")
                # every holder can answer the asok commands
                for o in holders:
                    dump = o.ctx.asok.execute("dump_hit_sets")
                    assert any("current" in v for v in dump.values())
                    st = o.ctx.asok.execute("tier status")
                    assert st["hit_set_archives"] >= 1
                await c.stop()
            finally:
                await cluster.stop()

        run(go())

    def test_mon_settable_pool_tier_params(self, force_batching):
        """`pool set` tier keys validate at the mon, land in pool.opts,
        propagate via the map, and rebuild archives with the new
        sizing; garbage values are refused."""
        async def go():
            cluster = Cluster(n_osds=3, conf={
                "osd_auto_repair": False, "client_op_timeout": 60.0})
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("t", profile=dict(PROFILE))
                await c.pool_set(pool, "hit_set_period", "0.5")
                await c.pool_set(pool, "hit_set_count", "3")
                await c.pool_set(pool, "min_read_recency_for_promote",
                                 "2")
                await c.pool_set(pool, "target_max_bytes",
                                 str(4 << 20))
                await c.pool_set(pool, "cache_target_full_ratio", "0.5")
                # invalid values must be refused, not stored
                await c.pool_set(pool, "hit_set_period", "not-a-number")
                await c.pool_set(pool, "cache_target_full_ratio", "7")
                await c.refresh_map()
                pi = c.osdmap.pools[pool]
                assert pi.opts["hit_set_period"] == "0.5"
                assert pi.opts["hit_set_count"] == "3"
                assert pi.opts["cache_target_full_ratio"] == "0.5"
                # the OSD-side archive adopts the pool's sizing
                blob = os.urandom(30_000)
                await c.put(pool, "obj", blob)
                assert await c.get(pool, "obj") == blob
                osd = next(o for o in cluster.osds.values()
                           if o._hit_sets)
                arch = next(iter(osd._hit_sets.values()))
                assert arch.period == 0.5
                assert arch.count == 3
                # effective target honors the pool's bound
                assert osd._tier_effective_target() <= (4 << 20) \
                    or osd._planar is None
                await c.stop()
            finally:
                await cluster.stop()

        run(go())

    def test_tier_disabled_records_nothing(self, force_batching):
        async def go():
            cluster = Cluster(n_osds=3, conf={
                "osd_auto_repair": False, "client_op_timeout": 60.0,
                "osd_tier_enabled": False})
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("t", profile=dict(PROFILE))
                blob = os.urandom(30_000)
                await c.put(pool, "obj", blob)
                assert await c.get(pool, "obj") == blob
                assert sum(o.tier_perf.get("read_hits_recorded")
                           for o in cluster.osds.values()) == 0
                await c.stop()
            finally:
                await cluster.stop()

        run(go())
