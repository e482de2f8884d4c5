"""Coupled-layer (CLAY) pools on the served path: the BatchingQueue's
`subchunk` lane — uncouple, the scalar code over every plane, couple, one
device program — against the plain reference the benchmark uses
(benchmarks/references/clay.py, which imports nothing of the program) and
against the tree's CPU codec (ErasureCodeClay.encode_chunks), byte for
byte; the reference itself pinned by decoding and by repair; the
geometries the lane refuses; and one small cluster named for the
benchmark's configuration `ec-k8m4-clay`.  All on the CPU backend at small
sizes."""

import asyncio
import itertools
import json
import os

import numpy as np
import pytest

from benchmarks.references import clay as ref
from ceph_tpu.ec.plugins.tpu import PLUGIN_PERF
from ceph_tpu.ec.registry import registry
from ceph_tpu.parallel import service
from ceph_tpu.parallel.service import BatchingQueue
from ceph_tpu.rados import ecutil
from ceph_tpu.rados import osd as osdmod
from ceph_tpu.rados.ecutil import StripeInfo

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STRIPE_UNIT = 4096

#: (k, m, d) the lane takes: q = d-k+1 divides k and m, nu = 0.  The
#: last has two parity rows (m = 2q): every plane scores 2, one round
GEOMETRIES = [(8, 4, 11), (4, 2, 5), (6, 3, 8), (4, 4, 5)]


def make(k, m, d, **more):
    profile = {"plugin": "clay", "k": str(k), "m": str(m), "d": str(d),
               **more}
    codec = registry.factory("clay", "", dict(profile))
    sinfo = StripeInfo(k, k * codec.get_chunk_size(k * STRIPE_UNIT))
    return profile, codec, sinfo


def payload(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, n, dtype=np.uint8).tobytes()


def cpu_codec_shards(codec, sinfo, data):
    """The tree's CPU path: the per-stripe loop over codec.encode."""
    return [bytes(s) for s in ecutil.batched_encode(codec, sinfo, data)]


@pytest.fixture
def queue():
    q = BatchingQueue(mesh=False)
    yield q
    q.close()


def lane_counts(q):
    d = q.perf.dump()
    return d["submit_subchunk"], d["dispatch"]


# -- (i) the lane against the plain reference and the CPU codec ----------------


@pytest.mark.parametrize("size", ["1byte", "1stripe", "3stripes", "ragged"])
@pytest.mark.parametrize("kmd", GEOMETRIES, ids=str)
def test_lane_stores_the_references_shards(queue, kmd, size):
    profile, codec, sinfo = make(*kmd)
    n = {"1byte": 1, "1stripe": sinfo.stripe_width,
         "3stripes": 3 * sinfo.stripe_width,
         "ragged": 2 * sinfo.stripe_width + 1234}[size]
    data = payload(n, seed=n)
    shapes = ref.shapes(profile, STRIPE_UNIT, n)
    assert sinfo.chunk_size == shapes["chunk_size"]
    assert codec.get_sub_chunk_count() == shapes["sub_chunks"]
    got = [bytes(s) for s in
           ecutil.batched_encode(codec, sinfo, data, queue=queue)]
    assert got == ref.shards(profile, STRIPE_UNIT, data)
    assert got == cpu_codec_shards(codec, sinfo, data)
    assert lane_counts(queue) == (1, 1)
    d = queue.perf.dump()
    assert d["bytes_subchunk"] == shapes["padded_bytes_per_object"]
    assert d["staged_layout_bytes"] == d["bytes_subchunk"]
    assert d["breaker_fallback"] == 0


@pytest.mark.parametrize("kmd", GEOMETRIES, ids=str)
def test_cpu_mirror_gives_the_lanes_bytes(queue, kmd):
    profile, codec, sinfo = make(*kmd)
    data = payload(2 * sinfo.stripe_width + 99, seed=3)
    on_device = ecutil.batched_encode(codec, sinfo, data, queue=queue)
    queue._breaker_failure("subchunk")  # the lane's breaker is OPEN now
    assert queue.open_lanes() == ["subchunk"]
    mirrored = ecutil.batched_encode(codec, sinfo, data, queue=queue)
    d = queue.perf.dump()
    assert d["breaker_fallback"] == 1 and d["dispatch"] == 1
    assert [bytes(s) for s in mirrored] == [bytes(s) for s in on_device] \
        == ref.shards(profile, STRIPE_UNIT, data)


def test_lane_choice_and_request():
    _, codec, sinfo = make(8, 4, 11)
    assert ecutil.lane_for(codec) == ("subchunk", np.uint8)
    assert ecutil.lane_for(codec, resident=True) is None
    assert ecutil._lane(codec, sinfo) == ("subchunk", np.uint8, 4096)
    assert not ecutil.concat_safe(codec)
    assert not ecutil.planar_eligible(codec)  # nothing of it is resident
    item, _ = ecutil._encode_plan_parts(codec, sinfo, payload(40000))
    geom, rows, w, out_rows, kind, chunk = item
    assert (w, out_rows, kind, chunk) == (8, 4, "subchunk", 4096)
    assert isinstance(rows, service.StripeRows)
    assert rows.shape == (8, 2 * 4096)
    q, t, pair, pair_inv, generator = service._read_geometry(geom)
    assert (q, t) == (4, 3) and generator.shape == (4, 8)
    assert np.array_equal(generator, np.asarray(codec.mds.matrix))
    assert np.array_equal(pair, np.asarray(codec.pft.matrix))
    # the width a batch is staged at: a power of two of whole chunks
    assert [service.staged_cols("subchunk", 8, 4096, c * 4096)
            for c in (1, 2, 3, 5, 128, 129)] == \
        [c * 4096 for c in (1, 2, 4, 8, 128, 256)]
    # sub-chunks that are not whole u32 plane words: no lane
    assert ecutil._lane(codec, StripeInfo(8, 8 * 64 * 16)) is None
    # a decode has no lane: the codec's own paths (tests/test_clay.py)
    assert ecutil._queue_decode_plan(
        codec, sinfo, {i: np.zeros(4096, np.uint8) for i in range(1, 12)},
        4096, queue=None) is None


def test_ragged_width_is_refused_at_submission(queue):
    _, codec, sinfo = make(4, 2, 5)
    item, _ = ecutil._encode_plan_parts(codec, sinfo, payload(100))
    geom = item[0]
    with pytest.raises(ValueError, match="whole chunks"):
        queue.submit(geom, np.zeros((4, 4096 + 32), np.uint8), 8, 2,
                     "subchunk", 4096)
    with pytest.raises(ValueError, match="w=8"):
        queue.submit(geom, np.zeros((4, 4096), np.uint8), 16, 2,
                     "subchunk", 4096)
    assert queue.submits == 0


# -- (ii) coalescing --------------------------------------------------------------


@pytest.mark.parametrize("group", [2, 4])
@pytest.mark.parametrize("kmd", GEOMETRIES[:3], ids=str)
def test_group_equals_each_alone(kmd, group):
    """A group of puts is ONE dispatch over their chunks side by side
    (the pad of the bucket is zero chunks), and each gets its own shards."""
    profile, codec, sinfo = make(*kmd)
    sizes = [(1, 0), (3, 7), (2, 0), (1, 4000)][:group]
    bufs = [payload(n * sinfo.stripe_width - cut, seed=10 * group + n)
            for n, cut in sizes]

    async def go(q):
        return await ecutil.batched_encode_group_async(
            codec, sinfo, bufs, queue=q)

    q = BatchingQueue(max_delay=60.0, mesh=False)
    try:
        task = asyncio.run(_flush_when_queued(q, go, group))
        d = q.perf.dump()
        assert d["submit_subchunk"] == group and d["dispatch"] == 1
        assert d["submit_group"] == 1
        for buf, got in zip(bufs, task):
            assert [bytes(s) for s in got] == \
                ref.shards(profile, STRIPE_UNIT, buf)
    finally:
        q.close()


async def _flush_when_queued(q, go, n):
    task = asyncio.ensure_future(go(q))
    while q.submits < n:
        await asyncio.sleep(0.005)
    await asyncio.get_running_loop().run_in_executor(None, q.flush)
    return await task


def test_two_pools_do_not_share_a_dispatch(queue):
    """Another geometry, or another chunk, is another group."""
    cases = [make(4, 2, 5), make(6, 3, 8), make(8, 4, 11)]
    items = [ecutil._encode_plan_parts(codec, sinfo,
                                       payload(sinfo.stripe_width, seed=i))[0]
             for i, (_, codec, sinfo) in enumerate(cases)]
    futs = queue.submit_group(items)
    queue.flush()
    for (profile, codec, sinfo), item, fut in zip(cases, items, futs):
        parity, rows = fut.result(timeout=120)
        assert np.array_equal(parity, np.asarray(codec.encode_chunks(rows)))
    assert queue.perf.dump()["dispatch"] == 3


# -- (iii) what the lane refuses ---------------------------------------------------


@pytest.mark.parametrize("kmd,more,why", [
    ((4, 3, 6), {}, "nu"),                      # q=3, k+m=7: nu=2
    ((5, 3, 6), {}, "rows"),                    # q=2, nu=0, m % q = 1
    ((4, 2, 5), {"scalar_mds": "jerasure", "technique": "cauchy_good"},
     "packet"),                                 # no byte-layout generator
    ((4, 2, 5), {"scalar_mds": "shec"}, "shec"),
], ids=lambda v: v if isinstance(v, str) else None)
def test_a_geometry_the_lane_refuses_takes_todays_path(queue, kmd, more, why):
    profile, codec, sinfo = make(*kmd, **more)
    assert {"nu": codec.nu > 0, "rows": codec.nu == 0 and codec.m % codec.q,
            "packet": codec.mds.bit_layout == "packet",
            "shec": True}[why]
    assert codec.encode_geometry() is None
    assert ecutil.lane_for(codec) is None
    assert ecutil._lane(codec, sinfo) is None
    data = payload(2 * sinfo.stripe_width + 5, seed=7)
    assert ecutil._encode_plan_parts(codec, sinfo, data) is None
    got = ecutil.batched_encode(codec, sinfo, data, queue=queue)
    assert queue.submits == 0, "a refused geometry reached the queue"
    assert [bytes(s) for s in got] == cpu_codec_shards(codec, sinfo, data)
    if not more:
        assert [bytes(s) for s in got] == \
            ref.shards(profile, STRIPE_UNIT, data)


def test_the_program_refuses_a_grid_it_cannot_run():
    from ceph_tpu.ops.gf2 import encode_subchunk_fn

    _, codec, _ = make(4, 2, 5)
    g = codec.encode_geometry()
    with pytest.raises(ValueError, match="whole rows"):
        encode_subchunk_fn(3, 2, 4096, g.pair, g.pair_inv, g.generator)
    with pytest.raises(ValueError, match="plane words"):
        encode_subchunk_fn(g.q, g.t, 8 * 16, g.pair, g.pair_inv,
                           g.generator)


# -- (iv) the reference, pinned without upstream's bytes ----------------------------


def _losses(n, m, limit, seed):
    every = list(itertools.combinations(range(n), m))
    if len(every) <= limit:
        return every
    pick = np.random.default_rng(seed).choice(len(every), limit,
                                              replace=False)
    return [every[i] for i in sorted(pick)]


@pytest.mark.parametrize("kmd,limit", [((4, 2, 5), 15), ((6, 3, 8), 12),
                                       ((8, 4, 11), 6)], ids=str)
def test_reference_shards_decode_back_through_the_plugin(kmd, limit):
    """Any m chunks of the reference's stripe lost, the tree's codec
    rebuilds them from the other k: the reference's parities ARE the
    code's."""
    profile, codec, sinfo = make(*kmd)
    k, m = kmd[:2]
    data = payload(sinfo.stripe_width, seed=5)
    want = [np.frombuffer(s, dtype=np.uint8)
            for s in ref.shards(profile, STRIPE_UNIT, data)]
    for lost in _losses(k + m, m, limit, seed=k):
        have = {i: want[i] for i in range(k + m) if i not in lost}
        got = codec.decode(set(lost), have, sinfo.chunk_size)
        for i in lost:
            assert np.array_equal(got[i], want[i]), (lost, i)


@pytest.mark.parametrize("kmd", [(4, 2, 5), (8, 4, 11)], ids=str)
def test_reference_shards_have_the_msr_property(kmd):
    """One chunk lost: the plugin rebuilds it from the repair sub-chunks
    alone, a q-th of each of d helpers."""
    profile, codec, sinfo = make(*kmd)
    k, m, d = kmd
    data = payload(sinfo.stripe_width, seed=6)
    want = [np.frombuffer(s, dtype=np.uint8)
            for s in ref.shards(profile, STRIPE_UNIT, data)]
    sc = ref.shapes(profile, STRIPE_UNIT, 1)["sub_chunk_bytes"]
    for lost in range(k + m):
        plan = codec.minimum_to_decode({lost}, set(range(k + m)) - {lost})
        assert len(plan) == d
        helpers = {}
        for chunk, runs in plan.items():
            assert sum(n for _, n in runs) * codec.q == \
                codec.get_sub_chunk_count()
            helpers[chunk] = np.concatenate(
                [want[chunk][off * sc:(off + n) * sc] for off, n in runs])
        got = codec.decode({lost}, helpers, sinfo.chunk_size)
        assert np.array_equal(got[lost], want[lost]), lost


def test_reference_refuses_what_it_does_not_know():
    with pytest.raises(ValueError, match="reed_sol_van"):
        ref.geometry({"k": "4", "m": "2", "technique": "cauchy_good"})
    with pytest.raises(ValueError, match="within"):
        ref.geometry({"k": "4", "m": "2", "d": "7"})


# -- (v) the configuration, served ---------------------------------------------------

CONFIG = "ec-k8m4-clay"


def test_cluster_of_configuration_ec_k8m4_clay(monkeypatch):
    """The benchmark's configuration `ec-k8m4-clay` (its profile, its 13
    OSDs) as a small in-process cluster: puts of 4 KiB to 1 MiB store the
    reference's shards through the lane with no dispatch beside the
    queue and read back; then one shard is lost and the repair that is
    there (sub-chunk reads from d helpers, the codec's own decode) puts
    the same bytes back."""
    from ceph_tpu.rados.store import Transaction
    from ceph_tpu.rados.vstart import Cluster

    with open(os.path.join(REPO, "benchmarks", "configs",
                           CONFIG + ".json")) as f:
        cfg = json.load(f)
    assert cfg["profile"] == {"plugin": "clay", "k": "8", "m": "4",
                              "d": "11"}
    derived = cfg["derived"]
    monkeypatch.setenv("CEPH_TPU_FORCE_BATCH", "1")
    monkeypatch.setattr(osdmod, "_BATCH_QUEUE", None)
    monkeypatch.setattr(osdmod, "_PLANAR_STORE", None)
    sizes = [4096, 40000, derived["stripe_width"], 262144 + 5, 1 << 20]
    objects = {f"obj{i}": payload(n, seed=100 + i)
               for i, n in enumerate(sizes)}

    def stored(cluster, pool, oid):
        held = {}
        for osd in cluster.osds.values():
            for name, shard in osd.store.list_objects(pool):
                if name == oid:
                    got = osd.store.read((pool, oid, shard))
                    held[shard] = bytes(getattr(got[0], "view", got[0]))
        return held

    async def go():
        cluster = Cluster(n_osds=int(cfg["osds"]), n_mons=1,
                          conf={"osd_auto_repair": False,
                                "client_op_timeout": 120.0})
        await cluster.start()
        try:
            c = await cluster.client()
            pool = await c.create_pool("clay", pg_num=8,
                                       profile=dict(cfg["profile"]))
            info = c.osdmap.pools[pool]
            assert info.stripe_width == derived["stripe_width"]
            q = osdmod.shared_batching_queue()
            direct0 = (PLUGIN_PERF.get("apply"),
                       PLUGIN_PERF.get("apply_rows"))
            await asyncio.gather(*(c.put(pool, oid, data)
                                   for oid, data in objects.items()))
            assert q.perf.get("dispatch") > 0
            assert q.perf.get("submit_subchunk") == len(objects) \
                == q.perf.get("submit")
            assert (PLUGIN_PERF.get("apply"),
                    PLUGIN_PERF.get("apply_rows")) == direct0
            assert q.perf.get("breaker_trip") == 0
            for oid, data in objects.items():
                want = ref.shards(cfg["profile"], int(cfg["stripe_unit"]),
                                  data)
                held = stored(cluster, pool, oid)
                assert sorted(held) == list(range(derived["shards"]))
                assert [held[i] for i in sorted(held)] == want, oid
                assert bytes(await c.get(pool, oid)) == data, oid
            # one shard of the largest object lost, and repaired
            oid = "obj4"
            pg = c.osdmap.object_to_pg(info, oid)
            acting = c.osdmap.pg_to_acting(info, pg)
            lost = 9  # a parity chunk: node (1, 2)
            victim = cluster.osds[acting[lost]]
            before = stored(cluster, pool, oid)
            txn = Transaction()
            txn.delete((pool, oid, lost))
            victim.store.queue_transaction(txn)
            assert lost not in stored(cluster, pool, oid)
            await c.repair_pool(pool)
            for _ in range(100):
                if lost in stored(cluster, pool, oid):
                    break
                await asyncio.sleep(0.1)  # pushes are fire-and-forget
            assert stored(cluster, pool, oid) == before
            for osd in cluster.osds.values():
                osd._extent_cache.clear()
            assert bytes(await c.get(pool, oid)) == objects[oid]
            assert (PLUGIN_PERF.get("apply"),
                    PLUGIN_PERF.get("apply_rows")) == direct0
            await c.stop()
        finally:
            await cluster.stop()
            q = osdmod._BATCH_QUEUE
            if q is not None:
                q.close()

    asyncio.run(asyncio.wait_for(go(), 300))
