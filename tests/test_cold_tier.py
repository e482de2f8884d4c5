"""A data set three times the resident store, read uniformly: the path
below the store's memo.  A get that finds no resident reads k shards,
answers, and promotes the object through the queue's resident lane into
device pages while installs and the tier agents evict to make room.

One small cluster on the CPU backend (device arm of the store forced on)
runs a few rounds of seeded uniform gets; the tests below hold what it
saw to a dict of what was written, to the plain numpy reference
(benchmarks/references/reed_sol_van.py) and to the throttle's arithmetic.
"""

import asyncio
import json
import os
import time

import numpy as np
import pytest

from benchmarks.references import reed_sol_van
from ceph_tpu.rados import osd as osdmod
from ceph_tpu.rados.ecutil import planar_shard_bytes
from ceph_tpu.rados.tiering import PromoteThrottle
from ceph_tpu.rados.vstart import Cluster
from ceph_tpu.utils.jaxdev import compile_meter

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROFILE = {"plugin": "tpu", "technique": "reed_sol_van", "k": "4", "m": "2"}
K, M, STRIPE_UNIT = 4, 2, 4096
OBJECT, OBJECTS, PAGE = 64 << 10, 24, 4096
STORE = OBJECT * OBJECTS // 3          # 128 pages; 24 an object, 16 shed
N_OSDS, ROUNDS = 7, 4
RATE = 256 << 10                       # promote bytes a second, an OSD
CONF = {"osd_auto_repair": False, "client_op_timeout": 60.0,
        "osd_ec_planar_bytes": STORE, "osd_tier_page_bytes": PAGE,
        "osd_hit_set_period": 1200.0, "osd_hit_set_count": 4,
        "osd_tier_promote_max_objects_sec": 25,
        "osd_tier_promote_max_bytes_sec": RATE,
        "osd_cache_target_full_ratio": 0.8}


def _tier(cluster, name):
    return sum(o.tier_perf.get(name) for o in cluster.osds.values())


def _subread_waits(cluster):
    return sum(
        o.ctx.perf.get("optracker").dump()["lat_subop_wait"]["avgcount"]
        for o in cluster.osds.values())


def _rows_against(store, key, ref):
    """Per shard: the device rows equal the reference's, or None where
    the pages were shed."""
    version = store.resident_meta(key)[0]
    got = [planar_shard_bytes(store, key, version, s)
           for s in range(len(ref))]
    return [None if g is None else g == r for g, r in zip(got, ref)]


async def _settle(cluster):
    """Until no promotion is in flight: one get, one promotion, one
    group of one on the queue, so what compiles does not hang on timing."""
    for _ in range(2000):
        if not any(o._promoting for o in cluster.osds.values()):
            return
        await asyncio.sleep(0.005)
    raise AssertionError("a promotion never ended")


async def _scenario():
    seen = {"rounds": [], "bad_gets": 0}
    rng = np.random.default_rng(33)
    written = {f"cold_{i}": rng.bytes(OBJECT) for i in range(OBJECTS)}
    meter = compile_meter()
    cluster = Cluster(n_osds=N_OSDS, conf=dict(CONF))
    t_start = time.monotonic()
    await cluster.start()
    try:
        c = await cluster.client()
        pool = await c.create_pool("cold", pg_num=8, profile=dict(PROFILE))
        store = osdmod.shared_planar_store()
        seen["device_arm"] = store.device_arm
        seen["pages_total"] = store.pages_total
        for oid, data in written.items():
            await c.put(pool, oid, data)
        seen["write_installs"] = _tier(cluster, "write_installs")
        for rnd in range(ROUNDS):
            before = {key for key, _n in store.entries_snapshot()}
            for i in rng.integers(OBJECTS, size=OBJECTS):
                oid = f"cold_{int(i)}"
                if await c.get(pool, oid) != written[oid]:
                    seen["bad_gets"] += 1
                await _settle(cluster)
            seen["rounds"].append({
                "compiles": meter.count, "before": before,
                "after": {key for key, _n in store.entries_snapshot()},
                "promote": _tier(cluster, "promote"),
                "miss": store.perf.get("miss"), "hit": store.perf.get("hit"),
                "evict": store.perf.get("evict")})
            await asyncio.sleep(0.3)  # the buckets refill a little
        seen["elapsed"] = time.monotonic() - t_start
        seen["admitted"] = (_tier(cluster, "promote")
                            + _tier(cluster, "promote_skipped")
                            + _tier(cluster, "promote_stale")
                            + _tier(cluster, "write_installs"))
        seen["throttled"] = _tier(cluster, "promote_throttled")
        seen["promote_lat_count"] = sum(
            o.tier_perf.dump()["promote_lat"]["avgcount"]
            for o in cluster.osds.values())
        seen["subread_waits"] = _subread_waits(cluster)

        # device pages of what the last round promoted, shard by shard
        last = seen["rounds"][-1]
        seen["rows"] = [
            _rows_against(store, key, reed_sol_van.shards(
                PROFILE, STRIPE_UNIT, written[key[2]]))
            for key in sorted(last["after"] - last["before"])]

        # an object that was resident and is not: its memo went with it,
        # and its next get is a miss that reads shards
        ever = set().union(*(r["before"] | r["after"]
                             for r in seen["rounds"]))
        gone = sorted(ever - {k for k, _n in store.entries_snapshot()})
        seen["gone"] = len(gone)
        key = gone[0]
        seen["gone_memo"] = store._memo.get(key)
        seen["memo_bytes"] = (store.memo_bytes, sum(
            store._memo_charge(n) for n in store._memo_raw.values()))
        seen["memo_keys_resident"] = all(k in store for k in store._memo)
        miss0, waits0 = store.perf.get("miss"), seen["subread_waits"]
        seen["gone_get_ok"] = await c.get(pool, key[2]) == written[key[2]]
        seen["gone_get_missed"] = store.perf.get("miss") - miss0
        seen["gone_get_subreads"] = _subread_waits(cluster) - waits0
        await _settle(cluster)
        await c.stop()
    finally:
        await cluster.stop()
    return seen


@pytest.fixture(scope="module")
def seen():
    from tests.conftest import _drop_shared_ec_service

    patch = pytest.MonkeyPatch()
    patch.setenv("CEPH_TPU_FORCE_BATCH", "1")
    patch.setenv("CEPH_TPU_DEVICE_SLAB", "1")
    _drop_shared_ec_service()
    try:
        return asyncio.run(asyncio.wait_for(_scenario(), 300))
    finally:
        _drop_shared_ec_service()
        patch.undo()


def test_the_store_is_a_third_of_the_data_set_and_on_its_device_arm(seen):
    assert seen["device_arm"]
    assert seen["pages_total"] * PAGE * 3 == OBJECT * OBJECTS


def test_every_get_of_every_round_is_what_was_written(seen):
    assert seen["bad_gets"] == 0
    assert len(seen["rounds"]) == ROUNDS


def test_the_rounds_missed_promoted_and_evicted(seen):
    first, last = seen["rounds"][0], seen["rounds"][-1]
    assert last["miss"] - first["miss"] >= OBJECTS  # ~3 in 4 of 3 rounds
    assert last["hit"] > first["hit"]
    assert last["promote"] - first["promote"] >= 8
    assert last["evict"] - first["evict"] >= 8
    assert seen["promote_lat_count"] == last["promote"]
    # a get that missed waited for its shard reads, and only such a get
    # (the phase is the puts' wait for their sub-writes too)
    assert seen["subread_waits"] == last["miss"] + OBJECTS


def test_device_rows_of_promoted_residents_equal_the_reference(seen):
    rows = seen["rows"]
    assert len(rows) >= 2
    for shards in rows:
        assert all(same is True for same in shards[:K]), shards
        assert all(same in (True, None) for same in shards[K:]), shards
    assert any(same is True for shards in rows for same in shards[K:])


def test_an_evicted_objects_memo_is_gone_and_its_next_get_reads_shards(seen):
    assert seen["gone"] >= 1
    assert seen["gone_memo"] is None
    assert seen["memo_keys_resident"]
    assert seen["memo_bytes"][0] == seen["memo_bytes"][1]
    assert seen["gone_get_ok"]
    assert seen["gone_get_missed"] == 1 and seen["gone_get_subreads"] == 1


def test_the_throttles_ceiling_is_honoured(seen):
    """Each OSD's bucket holds one second's bytes and refills at RATE;
    write installs and promotions draw on the same bucket."""
    ceiling = N_OSDS * (RATE + RATE * seen["elapsed"]) / OBJECT
    assert 0 < seen["admitted"] <= ceiling
    assert seen["write_installs"] <= N_OSDS * (RATE + RATE * seen["elapsed"]) \
        / OBJECT


def test_nothing_compiles_after_the_first_round(seen):
    counts = [r["compiles"] for r in seen["rounds"]]
    assert counts[1:] == [counts[0]] * (ROUNDS - 1), counts


def test_the_deployments_throttle_passes_five_objects_in_four_seconds():
    """ec-k8m3-rs-tier3x: 25 objects and 5 MiB a second an OSD, 4 MiB
    objects.  A full bucket passes one object; the next needs 4 MiB
    again: 1.25 a second an OSD, which the file's derived block states
    for 12 OSDs."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "ec-k8m3-rs-tier3x.json")) as f:
        cfg = json.load(f)
    conf, size = cfg["conf"], cfg["data_set"]["object_bytes"]
    throttle = PromoteThrottle(conf["osd_tier_promote_max_objects_sec"],
                               conf["osd_tier_promote_max_bytes_sec"],
                               now=0.0)
    passed = sum(throttle.allow(size, now=t / 100.0)
                 for t in range(100 * 100))          # 100 s, every 10 ms
    per_osd = conf["osd_tier_promote_max_bytes_sec"] / size
    assert per_osd == 1.25
    assert abs(passed - 100 * per_osd) <= 1  # the first comes 0.2 s early
    assert cfg["derived"]["promote_ceiling_objects_per_s"] == \
        per_osd * cfg["osds"]
    # an object of twice the size never passes: no put or get can run the
    # resident lane at a wide group's width (the traffic file's warm-up)
    assert not any(throttle.allow(2 * size, now=200.0 + t) for t in range(9))
