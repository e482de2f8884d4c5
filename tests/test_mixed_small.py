"""Small objects beside large ones, hot keys, overwrites and deletes under
their readers (the deployment `ec-k8m3-rs-mixed`, cell `k8m3.mixed-small`).

(a) one small cluster on the CPU backend (device arm of the store forced
on) runs a seeded history of a few hundred mixed ops, same-name overlaps
among them, through the benchmark's own open-loop generator, and the plain
object model (benchmarks/references/object_model.py) admits every answer;
two controls show the checker refusing; (b) shards of small and ragged
objects equal the plain Reed-Solomon reference; (c) residents of 40 widths
build one install program a bucket; (d) a round of unequal widths resolves
every request with what a dispatch of its own gives; (e) the generator's
pure parts.
"""

import asyncio
import json
import math
import os
import time
from types import SimpleNamespace

import numpy as np
import pytest

from benchmarks import verify
from benchmarks.generators import open_loop_mixed
from benchmarks.references import object_model as om
from benchmarks.references import reed_sol_van
from ceph_tpu.ops import slab
from ceph_tpu.parallel.service import BatchingQueue, _cpu_apply_request
from ceph_tpu.rados import ecutil
from ceph_tpu.rados import osd as osdmod
from ceph_tpu.rados.pagestore import PagedResidentStore
from ceph_tpu.rados.vstart import Cluster
from ceph_tpu.utils.jaxdev import compile_meter

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROFILE = {"plugin": "tpu", "technique": "reed_sol_van", "k": "8", "m": "3"}
K, M, STRIPE_UNIT = 8, 3, 4096
RAGGED = (4096, 36 << 10, (1 << 20) + 4096)  # 1, 2 and 33 stripes
CONF = {"osd_auto_repair": False, "client_op_timeout": 60.0,
        "osd_ec_planar_bytes": 32 << 20, "osd_cache_target_full_ratio": 0.8}
with open(os.path.join(ROOT, "benchmarks", "traffic",
                       "mixed-small-zipf-open.json")) as f:
    TRAFFIC = json.load(f)


def traffic(**over):
    """The cell's traffic file at a tiny size."""
    t = dict(TRAFFIC, population=24, schedule_ops=4000, max_outstanding=512,
             sizes=dict(TRAFFIC["sizes"], max_bytes=256 << 10),
             verify={"readback_newest": 8, "readback_drawn": 8,
                     "shard_objects": 6})
    t.update(over)
    return t


# -- (a), (b): a cluster against the model and the reference ------------------


async def _scenario():
    seen = {}
    cluster = Cluster(n_osds=12, conf=dict(CONF), n_mons=1)
    await cluster.start()
    try:
        client = await cluster.client()
        pool = await client.create_pool("mixed", pg_num=8,
                                        profile=dict(PROFILE))
        store = osdmod.shared_planar_store()
        env = SimpleNamespace(
            cell=SimpleNamespace(traffic=traffic()), seed=3800000017,
            cluster=cluster, client=client, pool=pool, n_shards=K + M,
            store=store, emit=lambda *a, **kw: seen.setdefault(
                "lines", []).append((a, kw)),
            reference=lambda data: reed_sol_van.shards(
                PROFILE, STRIPE_UNIT, data),
            live_osds=lambda: list(cluster.osds.values()))
        seen["device_arm"] = store.device_arm
        gen = open_loop_mixed.Generator(env)
        for rank in range(24):
            await gen._put(rank)
        # a few hundred ops on 24 names, Zipf 0.99: about one in four on
        # the hottest, in flight together
        await gen.play(2.5, 140.0, recorded=True)
        # and deletes that stand alone, whatever the timing above made:
        # each is looked at the moment its ack arrives
        for rank in (3, 11, 19):
            await gen._put(rank)
            await gen._get(rank)
            await gen._delete(rank)
        seen["checks"] = await gen.verify()
        seen["ops"] = gen.history.ops
        seen["failed"] = gen.failed
        seen["overlaps"] = sum(
            1 for per in gen.history._names.values() for w in per.writes
            for x in per.writes + per.gets
            if x is not w and x.t_issue < w.t_ack and w.t_issue < x.t_ack)
        seen["quiet_deletes"] = sum(
            1 for per in gen.history._names.values() for w in per.writes
            if w.kind == om.DELETE and gen.history.quiet_delete(w))

        # (b) ragged objects: what the OSDs' stores hold, and a get
        rng = np.random.default_rng(38)
        ragged = {f"ragged_{n}": rng.bytes(n) for n in RAGGED}
        for oid, data in ragged.items():
            await client.put(pool, oid, data)
        seen["ragged"] = ragged
        seen["ragged_held"] = verify.stored_shards(
            cluster.osds.values(), pool, ragged)
        seen["ragged_gets"] = {oid: bytes(await client.get(pool, oid))
                               for oid in ragged}
        # a deep scrub whose listing is older than a delete: the name is
        # gone by the time its turn comes
        gone = gen.schedule.names[19]
        info = client.osdmap.pools[pool]
        lead = next(o for o in cluster.osds.values() if o._primary(
            info, *o._acting(info, gone)) == o.osd_id)

        async def stale_listing(pool_id, pg=-1):
            return [(gone, 0, 1)]

        lead._list_all_shards = stale_listing
        seen["scrub_of_deleted"] = await lead.deep_scrub_pool(
            info, only_pg=lead._acting(info, gone)[0])
        seen["scrub_errors"] = dict(lead._scrub_errors)
        perf = [o.perf.dump() for o in cluster.osds.values()]
        seen["osd"] = {key: sum(p[key] if not isinstance(p[key], dict)
                                else p[key]["avgcount"] for p in perf)
                       for key in ("op", "op_r", "op_w", "op_d", "op_lat",
                                   "op_r_lat", "op_w_lat", "op_d_lat")}
        seen["objecter"] = client.perf.dump()
        await client.stop()
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


def test_the_model_admits_every_answer_of_a_mixed_history(seen):
    assert seen["device_arm"] and not seen["failed"]
    assert seen["ops"] >= 300 and seen["overlaps"] >= 10
    checks = {c["name"]: c for c in seen["checks"]}
    assert checks["gets_checked"]["value"] >= 150
    bad = [c for c in seen["checks"] if not c["ok"]]
    assert not bad, bad


def test_after_a_delete_stands_alone_nothing_of_the_name_is_left(seen):
    checks = {c["name"]: c for c in seen["checks"]}
    assert seen["quiet_deletes"] >= 3
    assert checks["deletes_left_something_at_ack"]["value"] == 0
    assert checks["deleted_names_checked"]["value"] >= 1
    assert checks["deleted_names_left_behind"]["value"] == 0


def test_a_scrub_that_listed_a_name_before_its_delete_reports_nothing(seen):
    assert seen["scrub_of_deleted"]["errors"] == 0
    assert seen["scrub_of_deleted"]["repaired"] == 0
    assert not seen["scrub_errors"]


def test_stored_shards_and_device_pages_equal_the_reference(seen):
    checks = {c["name"]: c for c in seen["checks"]}
    assert checks["shard_objects_compared"]["value"] >= 3
    assert checks["shards_missing"]["value"] == 0
    assert checks["shards_differing_from_reference"]["value"] == 0
    assert checks["residents_compared_on_the_device"]["value"] >= 1
    assert checks["resident_rows_differing_from_reference"]["value"] == 0


def test_op_kinds_are_counted_apart_on_both_sides(seen):
    osd, obj = seen["osd"], seen["objecter"]
    assert osd["op_r"] > 0 and osd["op_w"] > 0 and osd["op_d"] > 0
    for kind in ("op_r", "op_w", "op_d"):
        assert osd[kind + "_lat"] == osd[kind]
        assert obj[kind + "_lat"]["avgcount"] == obj[kind] > 0
        # an op the objecter sent again (a timeout on a busy host) arrives
        # twice and is one op to its caller
        assert obj[kind] <= osd[kind] <= obj[kind] + obj["resends"]
    assert osd["op_r"] + osd["op_w"] + osd["op_d"] <= osd["op"]
    assert obj["op_r"] + obj["op_w"] + obj["op_d"] == obj["op"]


@pytest.mark.parametrize("size", RAGGED)
def test_shards_of_a_small_or_ragged_object_equal_the_reference(seen, size):
    oid = f"ragged_{size}"
    want = reed_sol_van.shards(PROFILE, STRIPE_UNIT, seen["ragged"][oid])
    stripes = -(-size // (K * STRIPE_UNIT))
    assert len(want) == K + M and len(want[0]) == stripes * STRIPE_UNIT
    held = seen["ragged_held"][oid]
    for pos, ref in enumerate(want):
        assert held[pos] and all(copy == ref for copy in held[pos]), pos
    assert seen["ragged_gets"][oid] == seen["ragged"][oid]


def _history(*ops):
    hist = om.History()
    for name, kind, version, t_issue, t_ack in ops:
        op = hist.issue(name, kind, t_issue, version)
        if t_ack is not None:
            om.History.ack(op, t_ack, version if kind == om.GET else None)
    return hist


def test_control_a_reply_one_version_stale_is_refused():
    ok = _history(("a", om.PUT, 1, 0, 1), ("a", om.PUT, 2, 2, 3),
                  ("a", om.GET, 2, 4, 5))
    assert ok.check_gets()["gets_not_admitted"] == 0
    stale = _history(("a", om.PUT, 1, 0, 1), ("a", om.PUT, 2, 2, 3),
                     ("a", om.GET, 1, 4, 5))
    assert stale.check_gets()["gets_not_admitted"] == 1
    # in flight with the get, either is a legal answer
    for answer in (1, 2):
        racing = _history(("a", om.PUT, 1, 0, 1), ("a", om.PUT, 2, 2, 6),
                          ("a", om.GET, answer, 4, 5))
        assert racing.check_gets()["gets_not_admitted"] == 0
    # a get may not go backwards behind one that was answered before it
    back = _history(("a", om.PUT, 1, 0, 1), ("a", om.PUT, 2, 2, 9),
                    ("a", om.GET, 2, 3, 4), ("a", om.GET, 1, 5, 6))
    assert back.check_gets()["gets_gone_backwards"] == 1


def test_control_a_name_that_comes_back_after_its_delete_is_refused():
    gone = _history(("a", om.PUT, 1, 0, 1), ("a", om.DELETE, 0, 2, 3),
                    ("a", om.GET, om.ABSENT, 4, 5))
    assert gone.check_gets()["gets_not_admitted"] == 0
    assert gone.must_be_absent() == ["a"] and gone.must_hold() == {}
    back = _history(("a", om.PUT, 1, 0, 1), ("a", om.DELETE, 0, 2, 3),
                    ("a", om.GET, 1, 4, 5))
    assert back.check_gets()["gets_not_admitted"] == 1
    # "no such object" where an object stands
    lost = _history(("a", om.PUT, 1, 0, 1), ("a", om.GET, om.ABSENT, 2, 3))
    assert lost.check_gets()["gets_not_admitted"] == 1
    # a put issued while the delete was in flight may come after it
    both = _history(("a", om.DELETE, 0, 0, 3), ("a", om.PUT, 7, 1, 2))
    assert both.final("a") == {om.ABSENT, 7} and not both.must_be_absent()
    # a write that never came back may have happened, and supersedes nothing
    hung = _history(("a", om.PUT, 1, 0, 1), ("a", om.PUT, 2, 2, None))
    assert hung.final("a") == {1, 2}


def test_a_reply_is_checked_in_full_against_the_version_it_claims():
    pay = om.Payloads(38, 1 << 16)
    data = pay.data(5, 9, 8192)
    assert len(data) == 8192 and pay.version_of(data, 5, 8192) == 9
    assert pay.version_of(memoryview(bytearray(data)), 5, 8192) == 9
    assert pay.data(5, 10, 8192)[16:] != data[16:]  # a version's own bytes
    assert pay.version_of(data, 6, 8192) == om.CORRUPT       # other name
    assert pay.version_of(data[:-1], 5, 8192) == om.CORRUPT  # short
    for at in (0, 8, 16, 4096, 8191):
        bad = bytearray(data)
        bad[at] ^= 1
        assert pay.version_of(bad, 5, 8192) == om.CORRUPT, at


# -- (c): the store's programs are keyed by bucket, not by width ---------------


def test_residents_of_forty_widths_build_a_program_a_bucket_and_evict_none():
    import jax.numpy as jnp

    from ceph_tpu.ops.gf2 import bucket_columns, to_packedbit

    slab._reset_for_tests()
    before = {key: slab.SLAB_PERF.get(key) for key in ("compile", "evict")}
    meter = compile_meter()
    dev = PagedResidentStore(capacity_bytes=64 << 20, device=True)
    host = PagedResidentStore(capacity_bytes=64 << 20, device=False)
    rng = np.random.default_rng(40)
    widths = list(range(1, 41))  # stripes: 40 distinct widths, 7 buckets
    rows = {}
    for n in widths:
        data = rng.integers(0, 256, (K + M, n * STRIPE_UNIT), dtype=np.uint8)
        wide = np.zeros((K + M, bucket_columns(n * STRIPE_UNIT)), np.uint8)
        wide[:, :data.shape[1]] = data
        bits = to_packedbit(wide)  # what the encode lane hands the store
        assert dev.put_planar(n, jnp.asarray(bits), w=8, n_rows=K + M,
                              meta=(1, data.shape[1]), trim=data.shape[1])
        assert host.put_planar(n, np.asarray(bits), w=8, n_rows=K + M,
                               meta=(1, data.shape[1]), trim=data.shape[1])
        rows[n] = data
    compiled_by_installs = meter.count
    for n in widths:
        # the served read: data rows, padded to the bucket, packed, trimmed
        got = ecutil._pack_rows(dev.gather_rows(n, 0, K * 8),
                                8, K, rows[n].shape[1], store=dev)
        assert np.array_equal(got, rows[n][:K]), n
        one = ecutil.planar_shard_bytes(dev, n, 1, K + 1)
        assert one == rows[n][K + 1].tobytes(), n
        # arm against arm: the device's rows are the host's, then zeros
        theirs = host.gather_rows(n, 8, 24)
        ours = np.asarray(dev.gather_rows(n, 8, 24))
        assert np.array_equal(ours[:, :theirs.shape[1]], theirs), n
        assert not ours[:, theirs.shape[1]:].any(), n
    kinds = [key[0] for key in slab._KERNELS]
    buckets = len({bucket_columns(n * STRIPE_UNIT) for n in widths})
    assert buckets == 7
    assert kinds.count("install") == buckets
    # a cut per (page bucket, row count, column bucket): three row counts
    # here (8, 16, 64), and the pages of a range vary by at most a factor
    # of two inside a column bucket
    assert kinds.count("rows") <= 3 * 2 * buckets
    assert slab.SLAB_PERF.get("evict") == before["evict"]
    assert len(kinds) <= slab._KERNEL_CAPACITY // 4
    assert dev.perf.get("install_programs") >= len(widths)
    assert dev.perf.get("install_page_bytes") \
        == dev.pages_used * dev.page_bytes
    # again, other bytes at the same widths: nothing is built
    built, misses = meter.count, slab.SLAB_PERF.get("miss")
    for n in widths:
        bits = to_packedbit(np.ascontiguousarray(
            np.pad(rows[n][::-1], ((0, 0), (0, bucket_columns(
                rows[n].shape[1]) - rows[n].shape[1])))))
        assert dev.put_planar(n, jnp.asarray(bits), w=8, n_rows=K + M,
                              meta=(2, rows[n].shape[1]),
                              trim=rows[n].shape[1])
        got = ecutil._pack_rows(dev.gather_rows(n, 0, K * 8),
                                8, K, rows[n].shape[1], store=dev)
        assert np.array_equal(got, rows[n][::-1][:K]), n
    assert slab.SLAB_PERF.get("miss") == misses
    assert meter.count == built >= compiled_by_installs


# -- (d): one round of unequal widths -----------------------------------------


@pytest.mark.parametrize("kind", ["packedbit", "packedbit_resident"])
def test_a_round_of_unequal_widths_gives_each_what_its_own_dispatch_gives(kind):
    from ceph_tpu.ec.registry import registry

    codec = registry.factory("jerasure", "", dict(PROFILE, plugin="jerasure"))
    mbits = np.asarray(codec.bit_generator()).astype(np.uint8)
    rng = np.random.default_rng(4)
    widths = [STRIPE_UNIT, 9 * STRIPE_UNIT, 3 * STRIPE_UNIT,
              33 * STRIPE_UNIT, 2 * STRIPE_UNIT, STRIPE_UNIT]
    items = [(mbits, rng.integers(0, 256, (K, n), dtype=np.uint8), 8, M, kind)
             for n in widths]
    q = BatchingQueue(max_delay=60.0, mesh=False)
    try:
        futs = q.submit_group(items)
        q.flush()
        grouped = [f.result(timeout=120) for f in futs]
        d = q.perf.dump()
        assert d["dispatch"] == 1 and d["submit"] == len(widths)
        assert d["group_size"]["sum"] == len(widths) > 1
        assert d["pad_bytes"] == K * (64 * STRIPE_UNIT - sum(widths))
        alone = []
        for item in items:
            fut = q.submit(*item)
            q.flush()
            alone.append(fut.result(timeout=120))
        assert q.perf.dump()["dispatch"] == 1 + len(widths)
        slab_misses = slab.SLAB_PERF.get("miss")
        # another round, the same widths in another order: other offsets,
        # and not one program more
        again = [f.result(timeout=120) for f in (
            q.submit_group(items[::-1]), q.flush())[0]][::-1]
        assert slab.SLAB_PERF.get("miss") == slab_misses
    finally:
        q.close()
    for item, got, own, twice in zip(items, grouped, alone, again):
        mirror = _cpu_apply_request(kind, *item[:4])
        parity = np.asarray(codec.encode_chunks(item[1]))
        if kind == "packedbit_resident":
            (got, got_rows), (own, own_rows) = got, own
            (twice, twice_rows), (mirror, mirror_rows) = twice, mirror
            for rows in (got_rows, twice_rows, mirror_rows):
                assert np.array_equal(np.asarray(rows), np.asarray(own_rows))
            packed = ecutil._pack_rows(got_rows, 8, K + M, item[1].shape[1])
            assert np.array_equal(packed[:K], item[1])
            assert np.array_equal(packed[K:], parity)
        for out in (got, own, twice, mirror):
            assert out.dtype == np.uint8 and np.array_equal(out, parity)


# -- (e): the generator's pure parts ------------------------------------------


def test_the_schedule_is_a_function_of_schedule_seed_alone():
    a = open_loop_mixed.Schedule(traffic())
    b = open_loop_mixed.Schedule(traffic())
    for field in ("sizes", "unit_due", "kinds", "ranks"):
        assert np.array_equal(getattr(a, field), getattr(b, field)), field
    assert a.names == b.names and len(set(a.names)) == 24
    other = open_loop_mixed.Schedule(traffic(schedule_seed=39))
    assert not np.array_equal(a.ranks, other.ranks)
    assert not np.array_equal(a.sizes, other.sizes)
    # --seed makes the payloads, and nothing of the schedule
    gens = [open_loop_mixed.Generator(SimpleNamespace(
        cell=SimpleNamespace(traffic=traffic()), seed=seed))
        for seed in (1, 2)]
    assert np.array_equal(gens[0].schedule.ranks, gens[1].schedule.ranks)
    assert gens[0].payloads.data(0, 1, 4096) != gens[1].payloads.data(0, 1,
                                                                      4096)
    # a segment's due times scale with the rate and start at the segment
    picked, due = a.segment(100, 50.0, 4.0)
    again, due2 = a.segment(100, 100.0, 2.0)
    assert picked[0] == 100 and np.array_equal(picked, again)
    assert np.allclose(due, 2 * due2) and 0 < due[0] and due[-1] < 4.0


def test_rank_frequencies_follow_zipf():
    sched = open_loop_mixed.Schedule(dict(TRAFFIC))
    assert len(sched.ranks) == TRAFFIC["schedule_ops"]
    n_names = TRAFFIC["population"]
    counts = np.bincount(sched.ranks, minlength=n_names)
    p = 1.0 / np.arange(1, n_names + 1) ** 0.99
    p /= p.sum()
    n = len(sched.ranks)
    for rank in (0, 1, 2, 9, 99):
        sigma = math.sqrt(n * p[rank] * (1 - p[rank]))
        assert abs(counts[rank] - n * p[rank]) < 5 * sigma, rank
    assert abs(counts[0] / n - p[0]) < 0.01 and 0.09 < p[0] < 0.12
    assert abs(counts[:10].sum() / n - p[:10].sum()) < 0.01
    mix = np.bincount(sched.kinds, minlength=3) / n
    assert np.allclose(mix, [0.70, 0.25, 0.05], atol=0.01)
    gaps = np.diff(sched.unit_due)
    assert abs(gaps.mean() - 1.0) < 0.02 and abs(gaps.std() - 1.0) < 0.03


def test_sizes_keep_their_bounds_and_the_pareto_octaves():
    sched = open_loop_mixed.Schedule(dict(TRAFFIC))
    s = sched.sizes
    assert len(s) == TRAFFIC["population"] and s.min() == 4096
    assert s.max() <= 4 << 20
    assert not (s % 4096).any()
    # shape 1.0: an octave [x, 2x) holds half the objects of the one
    # below, and so equal bytes: before the rounding up, that is
    raw = open_loop_mixed.object_sizes(38, 200000, 4096, 4 << 20, 1, 1.0)
    octave = np.floor(np.log2(raw / 4096.0)).astype(int).clip(0, 9)
    share = np.bincount(octave, minlength=10) / len(raw)
    assert abs(share[0] - 0.5005) < 0.005
    for i in range(6):
        assert abs(share[i + 1] / share[i] - 0.5) < 0.05, i
    by_bytes = np.bincount(octave, weights=raw.astype(float), minlength=10)
    assert by_bytes[:8].max() / by_bytes[:8].min() < 1.25
    # the population itself: mostly one stripe, a fifth of the bytes in
    # the few objects over 1 MiB
    assert 0.85 < (s <= 32768).mean() < 0.90
    assert 0.1 < s[s > 1 << 20].sum() / s.sum() < 0.3


class _SlowClient:
    """Answers after `seconds`, whatever is asked."""

    def __init__(self, seconds):
        self.seconds, self.puts = seconds, 0

    async def put(self, pool, oid, data):
        self.puts += 1
        await asyncio.sleep(self.seconds)

    async def delete(self, pool, oid):
        await asyncio.sleep(self.seconds)


def _fake_generator(cap, seconds=0.05):
    env = SimpleNamespace(
        cell=SimpleNamespace(traffic=traffic(max_outstanding=cap, mix={
            "get": 0, "put": 90, "delete": 10})),
        seed=7, client=_SlowClient(seconds), pool=1, n_shards=0, store=None,
        cluster=SimpleNamespace(osds={}))
    return open_loop_mixed.Generator(env)


def test_latency_counts_from_the_due_time():
    gen = _fake_generator(cap=1000)

    async def run():
        # hold the loop for 60 ms once the play has begun: the ops due
        # meanwhile are issued late, and are late
        asyncio.get_running_loop().call_later(0.02, time.sleep, 0.06)
        return await gen.play(0.25, 200.0, recorded=True)

    t0, t1, offered = asyncio.run(run())
    _picked, due = gen.schedule.segment(0, 200.0, 0.25)
    assert offered == len(due) and t1 - t0 == 0.25
    puts = sorted(gen.records)
    assert len(puts) == gen.env.client.puts and all(r[3] for r in puts)
    by_index = {r[0]: r for r in puts}
    for i, at in enumerate(due):
        if i in by_index:  # a record's clock starts when the op was DUE
            assert by_index[i][1] == t0 + at
    lat = [r[2] - r[1] for r in puts]
    assert min(lat) >= 0.05                  # the service time
    held = [r[2] - r[1] for r in puts if 0.02 < r[1] - t0 < 0.06]
    assert held and max(held) >= 0.05 + 0.02  # and the wait before issue
    assert gen.late.worst_s >= 0.02 and gen.late.shed == 0
    assert len(gen.window_lat[om.PUT]) == len(puts)


def test_an_arrival_over_the_cap_is_shed_and_counts_as_failed():
    gen = _fake_generator(cap=4, seconds=0.1)
    asyncio.run(gen.play(0.3, 200.0, recorded=True))
    assert gen.late.shed > 0 and gen.late.peak_outstanding == 4
    shed_puts = [r for r in gen.records if not r[3]]
    assert shed_puts and all(r[4] == 0 for r in shed_puts)
    from benchmarks import stats

    seen = stats.window_metrics(gen.records, 0.0, 1e18)
    assert seen["failed"] == len(shed_puts)
