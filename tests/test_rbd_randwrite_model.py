"""4 KiB random overwrites of a block image whose data pool is erasure-coded
(the deployment `ec-k8m3-rs-rbd`, cell `k8m3.rbd-randwrite4k`), held to the
plain block model (benchmarks/references/block_image.py) on the CPU.

One small cluster (device arm of the store forced on) runs the benchmark's
own generator at a tiny size: an image of 8 x 1 MiB objects over
reed_sol_van k=8 m=3, filled, then a seeded stream of 4 KiB writes, 8 in
flight, then writes to one stripe and to one object in flight together.
Every block and every stripe neighbour reads back as the model says, the
stored shards after splices equal the reference's, nothing older than a
splice is served by the tier, and each of the four arms an offset write
can take its base from is taken and counted.  The model is held to a plain
bytearray, and the stream to its seed."""

import asyncio
import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

from benchmarks import counters
from benchmarks.generators import closed_loop_rbd_write as gen_mod
from benchmarks.loop import closed_loop
from benchmarks.references import reed_sol_van
from benchmarks.references.block_image import (BlockImage, Payloads,
                                               block_payload)
from ceph_tpu.rados import osd as osdmod
from ceph_tpu.rados.vstart import Cluster
from ceph_tpu.utils.jaxdev import compile_meter

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROFILE = {"plugin": "tpu", "technique": "reed_sol_van", "k": "8", "m": "3"}
K, M, UNIT, BLOCK = 8, 3, 4096, 4096
ORDER, IMAGE = 20, 8 << 20
CONF = {"osd_auto_repair": False, "client_op_timeout": 60.0,
        "osd_ec_planar_bytes": 32 << 20, "osd_cache_target_full_ratio": 0.8}
SEED = 4300000043
with open(os.path.join(ROOT, "benchmarks", "traffic",
                       "fio-rbd-randwrite-4k-qd32.json")) as f:
    TRAFFIC = json.load(f)
ARMS = [a.split(".", 1)[1] for a in gen_mod.ARMS]


def osd_counters(cluster) -> dict:
    return {key: sum(o.perf.get(key) for o in cluster.osds.values())
            for key in ARMS + ["rmw_partial", "rmw_full_rewrite",
                               "rmw_copied_bytes", "splice_copied_bytes",
                               "splice_crc_bytes", "splice_refused",
                               "splice_in_place", "splice_rebuilt"]}


async def _scenario():
    seen = {"lines": []}
    cluster = Cluster(n_osds=12, conf=dict(CONF), n_mons=1)
    await cluster.start()
    try:
        client = await cluster.client()
        pool = await client.create_pool("bench", pg_num=8,
                                        profile=dict(PROFILE))
        store = osdmod.shared_planar_store()
        traffic = dict(TRAFFIC, in_flight=8, verify=dict(
            TRAFFIC["verify"], last_acked=16, drawn=48, objects_newest=2,
            objects_drawn=2, tier_objects=4, all_shards_at_ack_every=2))
        env = SimpleNamespace(
            cell=SimpleNamespace(traffic=traffic, config={
                "image": {"bytes": IMAGE, "order": ORDER,
                          "meta_pool": {"type": "replicated", "size": 3,
                                        "pg_num": 8}},
                "stripe_unit": UNIT}),
            seed=SEED, cluster=cluster, client=client, pool=pool,
            profile=PROFILE, n_shards=K + M, store=store,
            meter=compile_meter(),
            emit=lambda phase, **kw: seen["lines"].append((phase, kw)),
            reference=lambda data: reed_sol_van.shards(PROFILE, UNIT, data),
            live_osds=lambda: list(cluster.osds.values()),
            snapshot=lambda: counters.snapshot(
                [o.ctx.perf for o in cluster.osds.values()], [client.perf]),
            store_device_arm=lambda: True)
        gen = gen_mod.Generator(env)
        model = gen.model
        await gen._make_image()
        seen["image_pools"] = (gen.image.ioctx.pool_name,
                               gen.image.data_ioctx.pool_name)
        fills = await closed_loop(4, gen._fill,
                                  lambda i: i < model.n_objects)
        assert all(r[3] for r in fills)
        seen["cached_after_fill"] = gen._cached_whole()
        # half of the objects lose their cached payload, as most of a
        # volume larger than the caches has: their writes read k shards
        for obj in range(0, model.n_objects, 2):
            oid, _acting = gen._placed(obj)
            for osd in cluster.osds.values():
                osd._extent_cache.drop((pool, oid))
        before = env.snapshot()
        arms0 = osd_counters(cluster)
        gen.resident_before = {key[2] for key, _n in store.entries_snapshot()
                               if key[1] == pool}
        seen["residents_before"] = len(gen.resident_before)

        # the seeded stream, 8 in flight
        records = await closed_loop(8, gen._write, lambda i: i < 160)
        offset_writes = len(records)
        # one stripe of an object that is not cached, all 8 blocks in
        # flight together; then 16 stripes of one object together
        taken = {int(b) for b in gen.stream[:160]}
        stripe0 = next(
            s for s in range(0, model.n_blocks, K)
            if (s // gen.per_object) % 2 == 0
            and not taken & set(range(s, s + K)))
        in_stripe = list(range(stripe0, stripe0 + K))
        obj1 = 5
        in_object = [b for b in range(obj1 * gen.per_object,
                                      (obj1 + 1) * gen.per_object, K)
                     if b not in taken][:16]
        extra = in_stripe + in_object
        first = len(gen.stream)
        gen.stream = np.concatenate([gen.stream, np.array(extra)])
        together = []
        for blocks in (in_stripe, in_object):
            got = await closed_loop(
                len(blocks), gen._write,
                lambda i, n=first + len(blocks): i < n, first)
            first += len(blocks)
            together += got
        offset_writes += len(together)
        gen.records = records + together
        seen["failed"] = [r for r in gen.records if not r[3]]
        seen["during"] = {k: v - arms0[k]
                          for k, v in osd_counters(cluster).items()}
        seen["offset_writes"] = offset_writes
        seen["moved"] = counters.delta(env.snapshot(), before)
        seen["stale"] = [
            (key, store.resident_meta(key))
            for key, _n in store.entries_snapshot() if key[1] == pool]

        seen["checks"] = await gen.verify() + gen.counter_checks(
            dict(seen["moved"], **{"compile_meter.compiles": 0}))
        # every block of the image, not a sample
        whole = await gen.image.read(0, IMAGE)
        seen["image_equals_model"] = whole == model.read(0, IMAGE)
        seen["written_blocks"] = int(
            (model._generation == gen_mod.WRITTEN).sum())

        # an offset write to an object that does not exist finds no cut
        # to splice into: it reads the object whole (nothing) and writes
        # it whole
        arms1 = osd_counters(cluster)
        await gen.data_io.write("stray", b"x" * BLOCK, offset=2 * BLOCK)
        seen["stray"] = bytes(await gen.data_io.read("stray"))
        seen["stray_arms"] = {k: v - arms1[k]
                              for k, v in osd_counters(cluster).items()}
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


def checks_of(seen) -> dict:
    return {c["name"]: c for c in seen["checks"]}


def test_every_comparison_of_the_cell_holds_on_a_small_image(seen):
    assert seen["image_pools"] == ("rbd", "bench")
    assert not seen["failed"]
    bad = [c for c in seen["checks"] if not c["ok"]]
    assert not bad, bad


def test_every_block_of_the_image_reads_as_the_model_says(seen):
    assert seen["image_equals_model"]
    assert seen["written_blocks"] == seen["offset_writes"] == 160 + 8 + 16


@pytest.mark.parametrize("name, at_least", [
    ("blocks_compared", 64), ("objects_compared", 4),
    ("shard_objects_compared", 4), ("acks_checked_on_all_shards", 20)])
def test_the_comparisons_were_made(seen, name, at_least):
    assert checks_of(seen)[name]["value"] >= at_least


@pytest.mark.parametrize("name", [
    "blocks_not_the_latest_acked", "stripe_neighbours_changed",
    "objects_not_identical", "shards_missing",
    "shards_differing_from_reference", "acked_with_a_shard_behind",
    "acked_before_data_shard_committed", "acked_without_all_shards"])
def test_no_block_neighbour_object_or_shard_differs(seen, name):
    """Writes to one stripe and to one object in flight together among
    them: both survive, whole, on every shard."""
    assert checks_of(seen)[name]["value"] == 0


def test_nothing_older_than_a_splice_is_served_by_the_tier(seen):
    checks = checks_of(seen)
    assert seen["residents_before"] >= 4
    assert checks["tier_objects_compared"]["value"] >= 1
    assert checks["tier_serves_an_older_version"]["value"] == 0
    assert checks["tier_reads_differing"]["value"] == 0
    assert checks["resident_rows_differing_from_reference"]["value"] == 0
    tier = next(kw for phase, kw in seen["lines"] if phase == "model")["tier"]
    # the primary's resident outlives the splice, at the version before
    # it: what the check exists for
    assert tier["at_an_older_version"] >= 1


@pytest.mark.parametrize("arm", ARMS[:3])
def test_each_arm_a_healthy_write_can_take_is_taken(seen, arm):
    assert seen["during"][arm] >= 1, seen["during"]


def test_the_arms_sum_to_the_offset_writes(seen):
    during = seen["during"]
    assert sum(during[a] for a in ARMS) == seen["offset_writes"]
    assert during["rmw_partial"] == seen["offset_writes"]
    assert during["rmw_base_full_read"] == during["rmw_full_rewrite"] == 0
    assert during["splice_refused"] == 0
    assert seen["moved"]["objecter.op_w"] == seen["offset_writes"]
    assert seen["moved"]["rbd.wr"] == seen["offset_writes"]
    assert seen["moved"]["rbd.wr_bytes"] == seen["offset_writes"] * BLOCK
    # the 8 writes to one stripe: the first read its shards, the others
    # found the stripe it left in the extent cache
    assert during["rmw_extent_hits"] >= K - 1


def test_copies_and_crcs_of_a_write_are_counted(seen):
    """A write costs its stripe, not its object (PR 44).  Every one of
    the k+m splices moves its 4 KiB extent three times (out for the crc,
    out for the rollback slot, in) and checksums it twice (as it was, as
    it is); a shard is copied whole (128 KiB of a 1 MiB object) only at
    its first splice, when the store makes the buffer it writes in place
    from then on.  The primary copies the 32 KiB segment twice on every
    arm (and cuts it out of its run on the extent arm), and nothing of
    the rest of a cached object: its runs are split around the stripe."""
    during, n = seen["during"], seen["offset_writes"]
    shard = (1 << ORDER) // K
    splices = n * (K + M)
    rebuilt = during["splice_rebuilt"]
    assert during["splice_in_place"] + rebuilt == splices
    # a first touch a shard of the 8 objects, and no more
    assert K + M <= rebuilt <= 8 * (K + M)
    assert during["splice_copied_bytes"] == \
        rebuilt * shard + splices * 3 * UNIT
    assert during["splice_crc_bytes"] == splices * 2 * UNIT
    stripe = K * UNIT
    assert during["rmw_copied_bytes"] == (
        during["rmw_base_cached"] * 2 * stripe
        + during["rmw_extent_hits"] * 3 * stripe
        + during["rmw_base_shards"] * 2 * stripe)


def test_an_offset_write_to_an_absent_object_rewrites_it_whole(seen):
    arms = seen["stray_arms"]
    assert arms["rmw_base_full_read"] == arms["rmw_full_rewrite"] == 1
    assert arms["rmw_partial"] == 0
    assert sum(arms[a] for a in ARMS) == 1
    assert seen["stray"] == b"\x00" * (2 * BLOCK) + b"x" * BLOCK


# -- the model and the stream ----------------------------------------------------


def test_the_model_agrees_with_a_plain_bytearray():
    model = BlockImage(7, 4 << 20, BLOCK, 20)
    plain = bytearray(4 << 20)
    rng = np.random.default_rng(43)
    model.stamp_run(0, model.n_blocks // 2, 0)  # half filled, half zeros
    for b in range(model.n_blocks // 2):
        plain[b * BLOCK:(b + 1) * BLOCK] = model.payloads.block(b, 0)
    for _ in range(120):  # byte-granular writes across blocks
        off = int(rng.integers(0, (4 << 20) - 10000))
        data = rng.bytes(int(rng.integers(1, 9000)))
        model.write(off, data)
        plain[off:off + len(data)] = data
    for b in rng.choice(model.n_blocks, 200, replace=False):
        model.stamp(int(b), 1)
        plain[int(b) * BLOCK:(int(b) + 1) * BLOCK] = \
            model.payloads.block(int(b), 1)
    assert model.read(0, 4 << 20) == bytes(plain)
    assert model.read(12345, 70000) == bytes(plain[12345:82345])
    assert model.read((4 << 20) - 10, 100) == bytes(plain[-10:])
    for obj in range(model.n_objects):
        assert model.object_bytes(obj) == bytes(
            plain[obj << 20:(obj + 1) << 20])
    with pytest.raises(ValueError):
        model.write((4 << 20) - 1, b"ab")


def test_a_block_payload_is_a_function_of_seed_block_and_generation():
    a, b = Payloads(SEED, BLOCK), Payloads(SEED, BLOCK)
    assert a.block(5, 1) == b.block(5, 1) == block_payload(SEED, 5, 1)
    assert len(a.block(5, 1)) == BLOCK
    distinct = {a.block(blk, gen) for blk in range(64) for gen in (0, 1)}
    assert len(distinct) == 128
    assert a.block(5, 1) != Payloads(SEED + 1, BLOCK).block(5, 1)


def test_the_stream_is_a_function_of_the_seed_and_repeats_no_block():
    one, two = (gen_mod.block_stream(SEED, 1 << 16) for _ in range(2))
    assert (one == two).all()
    assert sorted(one.tolist()) == list(range(1 << 16))
    assert (gen_mod.block_stream(SEED + 1, 1 << 16) != one).any()
