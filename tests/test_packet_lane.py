"""Packet-layout codecs (cauchy_orig/good, liberation, blaum_roth,
liber8tion) on the served path: the BatchingQueue's `packetrows` lane — the
packed-bit lane with block transposes for layout stages — against the
plain reference the benchmark uses (benchmarks/references/cauchy_good.py,
which imports nothing of the program), its coalescing, its reconstructing
decode, its CPU mirror, and one small cluster named for the benchmark's
configuration `ec-k10m4-cauchy`.  All on the CPU backend at small sizes."""

import asyncio
import itertools
import json
import os

import numpy as np
import pytest

from benchmarks.references import cauchy_good as ref
from ceph_tpu.ec.plugins.tpu import PLUGIN_PERF
from ceph_tpu.ec.registry import registry
from ceph_tpu.parallel import service
from ceph_tpu.parallel.service import BatchingQueue
from ceph_tpu.rados import ecutil
from ceph_tpu.rados import osd as osdmod
from ceph_tpu.rados.ecutil import StripeInfo

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STRIPE_UNIT = 4096

#: every packet-layout technique of plugin=tpu, at a small packet size;
#: blocks are w*packetsize bytes (liberation's 7*16 is no power of two)
CODECS = {
    "cauchy_good-k10m4": {"technique": "cauchy_good", "k": "10", "m": "4",
                          "w": "8", "packetsize": "16"},
    "cauchy_good-k4m2": {"technique": "cauchy_good", "k": "4", "m": "2",
                         "w": "8", "packetsize": "16"},
    "cauchy_orig-k6m3": {"technique": "cauchy_orig", "k": "6", "m": "3",
                         "w": "8", "packetsize": "16"},
    "liberation-w7": {"technique": "liberation", "k": "5", "m": "2",
                      "w": "7", "packetsize": "16"},
    "blaum_roth-w6": {"technique": "blaum_roth", "k": "5", "m": "2",
                      "w": "6", "packetsize": "16"},
    "liber8tion": {"technique": "liber8tion", "k": "6", "m": "2",
                   "w": "8", "packetsize": "16"},
}


def make(name):
    profile = {"plugin": "tpu", **CODECS[name]}
    codec = registry.factory("tpu", "", dict(profile))
    k = codec.get_data_chunk_count()
    sinfo = StripeInfo(k, k * codec.get_chunk_size(k * STRIPE_UNIT))
    return profile, codec, sinfo


def payload(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, n, dtype=np.uint8).tobytes()


def reference_shards(profile, codec, data):
    """The plain reference's shards.  It builds the cauchy matrices
    itself; for the liberation family it is handed the codec's bit-matrix
    and supplies the chunk-size rule and the packet-wise encode."""
    if profile["technique"].startswith("cauchy"):
        return ref.shards(profile, STRIPE_UNIT, data)
    k, m = int(profile["k"]), int(profile["m"])
    rows = ref.data_rows(profile, STRIPE_UNIT, data)
    coding = ref.bitmatrix_encode(
        np.asarray(codec.bitmatrix), k, m, int(profile["w"]),
        int(profile["packetsize"]), rows)
    return [r.tobytes() for r in rows] + [r.tobytes() for r in coding]


@pytest.fixture
def queue():
    q = BatchingQueue(mesh=False)
    yield q
    q.close()


def lane_counts(q):
    d = q.perf.dump()
    return d["submit_packetrows"], d["dispatch"]


# -- (i) the lane against the plain reference ----------------------------------


@pytest.mark.parametrize("size", ["1stripe", "2stripes", "7stripes", "ragged"])
@pytest.mark.parametrize("name", sorted(CODECS))
def test_lane_stores_the_references_shards(queue, name, size):
    profile, codec, sinfo = make(name)
    n = {"1stripe": sinfo.stripe_width, "2stripes": 2 * sinfo.stripe_width,
         "7stripes": 7 * sinfo.stripe_width,
         "ragged": 2 * sinfo.stripe_width + 1234}[size]
    data = payload(n, seed=n)
    assert sinfo.chunk_size == ref.shapes(profile, STRIPE_UNIT, n)["chunk_size"]
    direct0 = PLUGIN_PERF.get("apply_rows")
    got = ecutil.batched_encode(codec, sinfo, data, queue=queue)
    assert [bytes(s) for s in got] == reference_shards(profile, codec, data)
    assert lane_counts(queue) == (1, 1)
    assert PLUGIN_PERF.get("apply_rows") == direct0, \
        "the codec's own seam dispatched beside the queue"


def test_lane_choice_follows_the_bit_layout():
    _, codec, sinfo = make("cauchy_good-k10m4")
    assert ecutil._lane(codec, sinfo) == ("packetrows", np.uint8, 16)
    assert not ecutil.planar_eligible(codec)  # nothing of it is resident
    rs = registry.factory("tpu", "", {"plugin": "tpu", "k": "4", "m": "2",
                                      "technique": "reed_sol_van"})
    assert ecutil._lane(rs, StripeInfo(4, 4 * 4096)) == ("packedbit", np.uint8)
    rs16 = registry.factory("tpu", "", {"plugin": "tpu", "k": "4", "m": "2",
                                        "technique": "reed_sol_van",
                                        "w": "16"})
    assert ecutil._lane(rs16, StripeInfo(4, 4 * 4096)) == ("packed", np.int8)
    # chunks that are not whole blocks: no lane, the codec's own path
    assert ecutil._lane(codec, StripeInfo(10, 10 * 4100)) is None


def test_ragged_width_is_refused_at_submission(queue):
    _, codec, _ = make("liberation-w7")
    bm = np.asarray(codec.bitmatrix, dtype=np.uint8)
    with pytest.raises(ValueError, match="whole w\\*packetsize"):
        queue.submit(bm, np.zeros((5, 4096), np.uint8), 7, 2, "packetrows", 16)


# -- (ii) coalescing -------------------------------------------------------------


@pytest.mark.parametrize("name", ["cauchy_good-k10m4", "liberation-w7"])
def test_group_of_three_equals_three_alone(name):
    profile, codec, sinfo = make(name)
    bufs = [payload(n * sinfo.stripe_width - cut, seed=n)
            for n, cut in ((1, 0), (3, 7), (2, 0))]
    # a window wide enough that the three submissions make ONE round
    q = BatchingQueue(mesh=False, max_delay=0.5)
    try:
        grouped = asyncio.run(ecutil.batched_encode_group_async(
            codec, sinfo, bufs, queue=q))
        assert lane_counts(q) == (3, 1), "three puts, one device program"
        assert q.perf.dump()["group_size"]["buckets"][2] == 1  # 2..3
        for buf, got in zip(bufs, grouped):
            alone = ecutil.batched_encode(codec, sinfo, buf, queue=q)
            rows = ref.data_rows(profile, STRIPE_UNIT, buf)
            by_codec = np.asarray(codec.encode_chunks(rows))
            k = codec.get_data_chunk_count()
            assert all(np.array_equal(a, b) for a, b in zip(got, alone))
            assert np.array_equal(np.stack(got[k:]), by_codec)
            assert np.array_equal(np.stack(got[:k]), rows)
    finally:
        q.close()


# -- (iii) reconstructing decode -------------------------------------------------

K, M_ = 10, 4
_ALL = list(range(K + M_))
_RNG = np.random.default_rng(28)
SIGNATURES = (
    [c for r in (1, 2) for c in itertools.combinations(_ALL, r)]
    + [tuple(sorted(_RNG.choice(K + M_, size=r, replace=False).tolist()))
       for r in (3, 4) for _ in range(6)])


@pytest.fixture(scope="module")
def k10m4_object():
    profile, codec, sinfo = make("cauchy_good-k10m4")
    data = payload(2 * sinfo.stripe_width - 99, seed=5)
    shards = [np.frombuffer(s, dtype=np.uint8)
              for s in ref.shards(profile, STRIPE_UNIT, data)]
    q = BatchingQueue(mesh=False)
    yield codec, sinfo, data, shards, q
    q.close()


@pytest.mark.parametrize("lost", SIGNATURES,
                         ids=["-".join(map(str, s)) for s in SIGNATURES])
def test_decode_plan_rebuilds_the_payload(k10m4_object, lost):
    codec, sinfo, data, shards, q = k10m4_object
    arrays = {i: shards[i] for i in _ALL if i not in lost}
    before = lane_counts(q)
    direct0 = PLUGIN_PERF.get("apply_rows")
    planned = ecutil._queue_decode_plan(codec, sinfo, arrays, len(data), q)
    if all(c >= K for c in lost):
        assert planned is None  # every data shard is there: de-interleave
    else:
        fut, finish = planned
        assert finish(fut.result(timeout=120)) == data
        assert lane_counts(q) == (before[0] + 1, before[1] + 1)
    assert ecutil.decode_object(codec, sinfo, arrays, len(data),
                                queue=q) == data
    assert PLUGIN_PERF.get("apply_rows") == direct0


def test_decode_selection_is_decode_chunks_rule():
    _, codec, _ = make("cauchy_good-k4m2")
    chosen, inv = codec.decode_selection({0, 1, 2, 3}, {0, 2, 3, 4, 5})
    assert chosen == (0, 2, 3, 4)
    assert inv.shape == (4 * 8, 4 * 8)
    assert np.array_equal(inv, codec._decode_bitmatrix(chosen))


# -- (iv) the breaker's CPU mirror -----------------------------------------------


@pytest.mark.parametrize("name", sorted(CODECS))
def test_cpu_mirror_gives_the_lanes_bytes(queue, name):
    profile, codec, sinfo = make(name)
    data = payload(3 * sinfo.stripe_width, seed=3)
    on_device = ecutil.batched_encode(codec, sinfo, data, queue=queue)
    queue._breaker_failure("packetrows")  # the lane's breaker is OPEN now
    assert queue.open_lanes() == ["packetrows"]
    mirrored = ecutil.batched_encode(codec, sinfo, data, queue=queue)
    d = queue.perf.dump()
    assert d["breaker_fallback"] == 1 and d["dispatch"] == 1
    assert [bytes(s) for s in mirrored] == [bytes(s) for s in on_device] \
        == reference_shards(profile, codec, data)


def test_cpu_mirror_request_shape():
    _, codec, sinfo = make("blaum_roth-w6")
    rows = ref.data_rows({"plugin": "tpu", **CODECS["blaum_roth-w6"]},
                         STRIPE_UNIT, payload(sinfo.stripe_width))
    out = service._cpu_apply_request(
        "packetrows", np.asarray(codec.bitmatrix), rows, 6, 2, 16)
    assert out.shape == (2, rows.shape[1]) and out.dtype == np.uint8
    assert np.array_equal(out, np.asarray(codec.encode_chunks(rows)))


# -- (v) the configuration, served ------------------------------------------------

CONFIG = "ec-k10m4-cauchy"


def test_cluster_of_configuration_ec_k10m4_cauchy(monkeypatch):
    """The benchmark's configuration `ec-k10m4-cauchy` (its profile, its 15
    OSDs) as a small in-process cluster: puts store the reference's
    shards through the lane with no dispatch beside the queue; then with
    one OSD down the objects read back through the decode lane, and
    recovery restores every shard without a dispatch beside the queue."""
    from ceph_tpu.rados.vstart import Cluster

    with open(os.path.join(REPO, "benchmarks", "configs",
                           CONFIG + ".json")) as f:
        cfg = json.load(f)
    assert cfg["profile"]["technique"] == "cauchy_good"
    derived = cfg["derived"]
    monkeypatch.setenv("CEPH_TPU_FORCE_BATCH", "1")
    monkeypatch.setattr(osdmod, "_BATCH_QUEUE", None)
    monkeypatch.setattr(osdmod, "_PLANAR_STORE", None)
    objects = {f"obj{i}": payload(derived["stripe_width"] * (1 + i % 2) - 11 * i,
                                  seed=100 + i) for i in range(6)}

    async def go():
        cluster = Cluster(n_osds=int(cfg["osds"]), n_mons=1,
                          conf={"osd_auto_repair": False,
                                "client_op_timeout": 120.0})
        await cluster.start()
        try:
            c = await cluster.client()
            pool = await c.create_pool("archive", pg_num=8,
                                       profile=dict(cfg["profile"]))
            info = c.osdmap.pools[pool]
            assert info.stripe_width == derived["stripe_width"]
            q = osdmod.shared_batching_queue()
            direct0 = PLUGIN_PERF.get("apply_rows")
            await asyncio.gather(*(c.put(pool, oid, data)
                                   for oid, data in objects.items()))
            assert q.perf.get("dispatch") > 0
            assert q.perf.get("submit_packetrows") == len(objects)
            assert PLUGIN_PERF.get("apply_rows") == direct0
            for oid, data in objects.items():
                want = ref.shards(cfg["profile"], int(cfg["stripe_unit"]),
                                  data)
                held = {}
                for osd in cluster.osds.values():
                    for name, shard in osd.store.list_objects(pool):
                        if name == oid:
                            got = osd.store.read((pool, oid, shard))
                            held[shard] = bytes(getattr(got[0], "view",
                                                        got[0]))
                assert sorted(held) == list(range(derived["shards"]))
                assert [held[i] for i in sorted(held)] == want, oid
            # lose the OSD that holds data shard 0 of the first object
            victim_oid = next(iter(objects))
            pg = c.osdmap.object_to_pg(info, victim_oid)
            victim = c.osdmap.pg_to_acting(info, pg)[0]
            await cluster.kill_osd(victim)
            await c.mark_osd_down(victim)
            lane0 = q.perf.get("submit_packetrows")
            for oid, data in objects.items():
                assert bytes(await c.get(pool, oid)) == data, oid
            assert q.perf.get("submit_packetrows") > lane0, \
                "no degraded read took the decode lane"
            assert PLUGIN_PERF.get("apply_rows") == direct0
            # recovery: the 14 live OSDs take the lost shards back, the
            # decode and the re-encode through the queue too
            await asyncio.sleep(0.2)
            await c.refresh_map()
            lane1 = q.perf.get("submit_packetrows")
            await c.repair_pool(pool)
            for _ in range(100):
                held = {oid: set() for oid in objects}
                for osd in cluster.osds.values():
                    for name, shard in osd.store.list_objects(pool):
                        held[name].add(shard)
                if all(len(v) == derived["shards"] for v in held.values()):
                    break
                await asyncio.sleep(0.1)  # pushes are fire-and-forget
            assert all(len(v) == derived["shards"] for v in held.values())
            assert q.perf.get("submit_packetrows") > lane1
            assert PLUGIN_PERF.get("apply_rows") == direct0
            assert q.perf.get("breaker_trip") == 0
            await c.stop()
        finally:
            await cluster.stop()
            q = osdmod._BATCH_QUEUE
            if q is not None:
                q.close()

    asyncio.run(asyncio.wait_for(go(), 300))
