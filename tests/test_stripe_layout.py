"""A put's bytes are laid out once, by the queue's thread.

An encode plan (rados/ecutil) names its data rows in stripe order
(parallel/service.StripeRows) and copies nothing on its caller's thread;
BatchingQueue._launch writes pad, stripe order and bucket padding straight
into the staging buffer, and the fan-out hands the staged data rows back.
Held here: every shard against the per-stripe path, the CPU codec and the
benchmark's numpy references, on every lane and at every ragged size; a
group of unequal and mixed requests against lone dispatches; what the data
rows alias; the breaker's CPU route; the rule for which sources another
thread may read later; and a served put's counters."""

import asyncio

import numpy as np
import pytest

from benchmarks.references import cauchy_good, reed_sol_van
from ceph_tpu.ec.registry import registry
from ceph_tpu.parallel.service import (LANES, BatchingQueue, StripeRows,
                                       _cpu_apply_request, staged_cols)
from ceph_tpu.rados import ecutil
from ceph_tpu.rados import osd as osdmod
from ceph_tpu.rados.ecutil import ECPLAN_PERF, StripeInfo

K, M, CHUNK = 4, 2, 256
PACKETSIZE = 16

#: lane -> (profile beyond k/m, the plan that rides it, its numpy
#: reference or None): the five lanes that take a bit-matrix, as
#: rados/ecutil.lane_for picks them (the sixth: tests/test_clay_lane.py)
LANE_PLANS = {
    "packedbit": ({"technique": "reed_sol_van", "w": "8"}, "bytes",
                  reed_sol_van),
    "packedbit_resident": ({"technique": "reed_sol_van", "w": "8"}, "planar",
                           reed_sol_van),
    "packetrows": ({"technique": "cauchy_good", "w": "8",
                    "packetsize": str(PACKETSIZE)}, "bytes", cauchy_good),
    "packed": ({"technique": "reed_sol_van", "w": "16"}, "bytes", None),
    "resident": ({"technique": "reed_sol_van", "w": "16"}, "planar", None),
}


def plan_case(kind: str, k: int = K, m: int = M, stripe_unit: int = CHUNK,
              plugin: str = "jerasure", **more):
    """(codec, sinfo, profile, reference module) of the pool whose plans
    ride lane `kind`."""
    extra, _, ref = LANE_PLANS[kind]
    profile = {"plugin": plugin, "k": str(k), "m": str(m), **extra, **more}
    codec = registry.factory(plugin, "", dict(profile))
    chunk = stripe_unit
    if profile["technique"] == "cauchy_good":
        # jerasure's chunk-size rule pads the stripe
        chunk = cauchy_good.shapes(profile, stripe_unit, 1)["chunk_size"]
    return codec, StripeInfo(k, k * chunk), profile, ref


def payload(size: int, seed: int = 0) -> bytes:
    return np.random.default_rng(seed).integers(
        0, 256, size, dtype=np.uint8).tobytes()


def encode_on(kind: str, codec, sinfo, data, q):
    """The shard list of `data` through the queue, by the plan that rides
    lane `kind`."""
    if LANE_PLANS[kind][1] == "planar":
        planar = asyncio.run(
            ecutil.planar_encode_async(codec, sinfo, data, queue=q))
        assert planar is not None
        return planar[0]
    return ecutil.batched_encode(codec, sinfo, data, queue=q)


def counters(q):
    return (q.perf.get("staged_layout_bytes"),
            ECPLAN_PERF.get("loop_layout_bytes"))


@pytest.fixture
def queue():
    q = BatchingQueue(max_delay=0.001, mesh=False)
    yield q
    q.close()


# -- (a) byte identity at every ragged size, on every lane --------------------

#: name -> object bytes as a function of (chunk, stripe width)
SIZES = {
    "one_byte": lambda c, sw: 1,
    "one_chunk": lambda c, sw: c,
    "one_stripe": lambda c, sw: sw,
    "whole_stripes": lambda c, sw: 3 * sw,
    "tail_shorter_than_a_chunk": lambda c, sw: 2 * sw + c // 2 - 3,
    "tail_spanning_chunks": lambda c, sw: 2 * sw + 2 * c + c // 3,
    "tail_one_byte_short_of_a_stripe": lambda c, sw: 2 * sw - 1,
}


@pytest.mark.parametrize("size", list(SIZES))
@pytest.mark.parametrize("kind", list(LANE_PLANS))
def test_every_shard_equals_the_per_stripe_path_and_the_reference(
        queue, kind, size):
    codec, sinfo, profile, ref = plan_case(kind)
    data = payload(SIZES[size](sinfo.chunk_size, sinfo.stripe_width),
                   seed=len(kind) + len(size))
    staged0, loop0 = counters(queue)
    got = encode_on(kind, codec, sinfo, data, queue)
    want = ecutil.batched_encode(codec, sinfo, data, queue=None)
    assert len(got) == K + M
    for g, w in zip(got, want):
        assert bytes(g) == bytes(w)
    if ref is not None:
        assert [bytes(g) for g in got] == ref.shards(profile, CHUNK, data)
    padded = sinfo.logical_to_next_stripe_offset(len(data))
    assert counters(queue) == (staged0 + padded, loop0)
    assert queue.perf.get(f"submit_{kind}") == 1
    assert queue.perf.get("breaker_fallback") == 0


@pytest.mark.parametrize("kind,k,m,more", [
    ("packedbit", 8, 3, {}),
    ("packedbit_resident", 8, 3, {}),
    ("packedbit", 4, 2, {}),
    ("packedbit_resident", 4, 2, {}),
    ("packetrows", 10, 4, {"packetsize": "2048"}),
])
def test_a_4mib_put_of_each_benchmark_pool_stores_the_references_shards(
        queue, kind, k, m, more):
    # the cells' own shapes: stripe unit 4096, 4 MiB, plugin=tpu; the
    # cauchy pool's object is 6.4 stripes of 655360 B
    codec, sinfo, profile, ref = plan_case(kind, k, m, 4096, plugin="tpu",
                                           **more)
    data = payload(4 << 20, seed=k)
    staged0, loop0 = counters(queue)
    got = encode_on(kind, codec, sinfo, data, queue)
    assert [bytes(g) for g in got] == ref.shards(profile, 4096, data)
    padded = sinfo.logical_to_next_stripe_offset(len(data))
    assert (padded != len(data)) == (kind == "packetrows")
    assert counters(queue) == (staged0 + padded, loop0)
    for row in got:
        assert row.flags["C_CONTIGUOUS"] and len(row) == padded // k


# -- (b), (c) groups of unequal and mixed requests; what the rows alias -------

#: (lane, w): as tests/test_lanes.py has them
LANE_CASES = [("packed", 8), ("packed", 16), ("resident", 8),
              ("resident", 16), ("packedbit", 8), ("packedbit_resident", 8),
              ("packetrows", 8)]


def lane_request(kind: str, w: int, nbytes: int, seed: int, named: bool):
    """One request on lane `kind` over an object of `nbytes`: (item, the
    object's bytes, its StripeRows) — the rows by name, or laid out."""
    profile = {"plugin": "jerasure", "k": str(K), "m": str(M), "w": str(w)}
    if kind == "packetrows":
        profile.update(technique="cauchy_good", packetsize=str(PACKETSIZE))
    else:
        profile.update(technique="reed_sol_van")
    codec = registry.factory("jerasure", "", profile)
    data = payload(nbytes, seed)
    src = StripeRows(np.frombuffer(data, dtype=np.uint8), K, CHUNK)
    dtype = np.int8 if kind in ("packed", "resident") else np.uint8
    item = (np.asarray(codec.bit_generator()).astype(dtype),
            src if named else src.rows(), w, M, kind)
    return (item + ((PACKETSIZE,) if kind == "packetrows" else ()),
            data, src)


def split(kind: str, result, named: bool):
    """(parity, resident rows or None, data rows or None) of a result."""
    result = result if isinstance(result, tuple) else (result,)
    parity, *rest = result
    planes = rest.pop(0) if LANES[kind].resident else None
    rows = rest.pop(0) if named else None
    assert not rest
    return parity, planes, rows


def assert_same_result(kind: str, got, want, got_named, want_named=True):
    gp, gplanes, grows = split(kind, got, got_named)
    wp, wplanes, wrows = split(kind, want, want_named)
    assert gp.dtype == np.uint8 and np.array_equal(gp, wp)
    if wplanes is not None:
        assert np.array_equal(np.asarray(gplanes), np.asarray(wplanes))
    if grows is not None and wrows is not None:
        assert np.array_equal(grows, wrows)


#: object sizes of a group: a ragged tail inside one chunk, one across
#: chunks, whole stripes, a single byte — four widths (in stripes: 3, 2, 2, 1)
GROUP_SIZES = (2 * K * CHUNK + 100, K * CHUNK + 2 * CHUNK + 9, 2 * K * CHUNK,
               1)


@pytest.mark.parametrize("kind,w", LANE_CASES)
def test_a_group_of_unequal_stripe_order_requests_equals_lone_dispatches(
        kind, w):
    q = BatchingQueue(max_delay=60.0, mesh=False)
    try:
        cases = [lane_request(kind, w, n, seed=i, named=True)
                 for i, n in enumerate(GROUP_SIZES)]
        lone = []
        for item, _, _ in cases:
            fut = q.submit(*item)
            q.flush()
            lone.append(fut.result(timeout=120))
        d0 = q.perf.get("dispatch")
        futs = q.submit_group([item for item, _, _ in cases])
        q.flush()
        assert q.perf.get("dispatch") == d0 + 1  # one coalesced dispatch
        grouped = [f.result(timeout=120) for f in futs]
        for (item, data, src), one, many in zip(cases, lone, grouped):
            assert_same_result(kind, many, one, got_named=True)
            # and against the mirror of the request as rows
            assert_same_result(
                kind, many, _cpu_apply_request(kind, item[0], src.rows(),
                                               *item[2:4], *item[5:]),
                got_named=True, want_named=False)
            rows = split(kind, many, True)[2]
            assert np.array_equal(rows, src.rows())
            assert bytes(src.buf) == data  # the source is not written
        assert q.perf.get("staged_layout_bytes") == 2 * sum(
            src.nbytes for _, _, src in cases)
    finally:
        q.close()


@pytest.mark.parametrize("kind,w", LANE_CASES)
def test_a_group_mixing_both_forms_gives_each_request_its_lone_result(
        kind, w):
    q = BatchingQueue(max_delay=60.0, mesh=False)
    try:
        forms = (True, False, True, False)
        cases = [lane_request(kind, w, n, seed=10 + i, named=named)
                 for i, (n, named) in enumerate(zip(GROUP_SIZES, forms))]
        lone = []
        for item, _, _ in cases:
            fut = q.submit(*item)
            q.flush()
            lone.append(fut.result(timeout=120))
        futs = q.submit_group([item for item, _, _ in cases])
        q.flush()
        for (item, _, src), named, one, fut in zip(cases, forms, lone, futs):
            many = fut.result(timeout=120)
            assert_same_result(kind, many, one, named, named)
            # a request that handed rows gets exactly what it always got
            if not named:
                assert isinstance(many, tuple) == LANES[kind].resident
                assert not LANES[kind].resident or len(many) == 2
        assert q.perf.get("staged_layout_bytes") == 2 * sum(
            src.nbytes for (_, _, src), named in zip(cases, forms) if named)
    finally:
        q.close()


@pytest.mark.parametrize("kind,w", LANE_CASES)
def test_data_rows_are_contiguous_and_a_grouped_requests_own(kind, w):
    q = BatchingQueue(max_delay=60.0, mesh=False)
    try:
        a, b = (lane_request(kind, w, n, seed=20 + i, named=True)
                for i, n in enumerate(GROUP_SIZES[:2]))
        fut = q.submit(*a[0])
        q.flush()
        lone_rows = split(kind, fut.result(timeout=120), True)[2]
        # alone in its dispatch: views of the staging buffer, bucket wide
        width = a[2].shape[1]
        assert lone_rows.shape == (K, width)
        assert lone_rows.base is not None
        assert lone_rows.base.shape == (
            K, staged_cols(kind, w, PACKETSIZE, width))
        futs = q.submit_group([a[0], b[0]])
        q.flush()
        rows = [split(kind, f.result(timeout=120), True)[2] for f in futs]
        for r, case in zip(rows, (a, b)):
            # copied out of the shared buffer: nothing of the batch pinned
            assert r.flags["C_CONTIGUOUS"] and r.flags["OWNDATA"]
            assert np.array_equal(r, case[2].rows())
        assert not np.shares_memory(rows[0], rows[1])
        for r in (lone_rows, *rows):
            for i in range(K):
                assert r[i].flags["C_CONTIGUOUS"]
    finally:
        q.close()


def test_lay_into_writes_pad_and_stripe_order_over_stale_bytes():
    # the staging buffer is np.empty: every byte of a request's columns,
    # the ragged stripe's zeros included, has to be written
    data = payload(2 * K * CHUNK + CHUNK + 7, seed=3)
    src = StripeRows(np.frombuffer(data, dtype=np.uint8), K, CHUNK)
    assert src.n_stripes == 3 and src.shape == (K, 3 * CHUNK)
    assert src.nbytes == 3 * K * CHUNK
    batch = np.full((K, 5 * CHUNK), 0xAA, dtype=np.uint8)
    src.lay_into(batch[:, CHUNK:4 * CHUNK])
    padded = np.zeros(3 * K * CHUNK, dtype=np.uint8)
    padded[:len(data)] = np.frombuffer(data, dtype=np.uint8)
    want = padded.reshape(3, K, CHUNK).transpose(1, 0, 2).reshape(K, -1)
    assert np.array_equal(batch[:, CHUNK:4 * CHUNK], want)
    assert np.array_equal(src.rows(), want)
    # and nothing beside them
    assert (batch[:, :CHUNK] == 0xAA).all() and (batch[:, 4 * CHUNK:] == 0xAA).all()


# -- (d) the breaker's CPU route ----------------------------------------------


@pytest.mark.parametrize("kind,w", LANE_CASES)
def test_the_cpu_route_returns_what_the_device_route_does(kind, w):
    q = BatchingQueue(max_delay=60.0, mesh=False)
    try:
        item, data, src = lane_request(kind, w, GROUP_SIZES[0], seed=30,
                                       named=True)
        fut = q.submit(*item)
        q.flush()
        device = fut.result(timeout=120)
        assert q.perf.get("breaker_fallback") == 0
        # the mirror, called as the rescue calls it
        assert_same_result(kind, _cpu_apply_request(kind, *item[:4],
                                                    *item[5:]),
                           device, got_named=True)
        # and through the queue with the lane's breaker open
        q._breaker_failure(kind)
        staged0 = q.perf.get("staged_layout_bytes")
        fut = q.submit(*item)
        q.flush()
        rescued = fut.result(timeout=120)
        assert q.perf.get("breaker_fallback") == 1
        assert_same_result(kind, rescued, device, got_named=True)
        rows = split(kind, rescued, True)[2]
        assert all(rows[i].flags["C_CONTIGUOUS"] for i in range(K))
        assert q.perf.get("staged_layout_bytes") == staged0 + src.nbytes
        assert bytes(src.buf) == data
    finally:
        q.close()


# -- (e) which sources another thread may read later --------------------------


def _whole_owned_view(data: bytes):
    a = np.empty(len(data), dtype=np.uint8)
    a[:] = np.frombuffer(data, dtype=np.uint8)
    return memoryview(a).cast("B").toreadonly()  # what the wire delivers


def _slice_of_larger(data: bytes):
    return memoryview(b"head" + data + b"tail")[4:4 + len(data)]


SOURCES = {
    "bytes": (lambda d: d, True),
    "whole_owned_readonly_view": (_whole_owned_view, True),
    "writable_view": (lambda d: memoryview(bytearray(d)), False),
    "bytearray": (bytearray, False),
    "slice_of_a_larger_buffer": (_slice_of_larger, False),
}


@pytest.mark.parametrize("plan", ["bytes", "planar", "group"])
@pytest.mark.parametrize("source", list(SOURCES))
def test_only_a_stable_source_is_left_for_the_queue_to_lay_out(
        queue, source, plan):
    make, deferred = SOURCES[source]
    codec, sinfo, profile, ref = plan_case("packedbit")
    data = payload(2 * sinfo.stripe_width + 300, seed=5)
    buf = make(data)
    padded = sinfo.logical_to_next_stripe_offset(len(data))
    staged0, loop0 = counters(queue)
    if plan == "planar":
        got = asyncio.run(ecutil.planar_encode_async(
            codec, sinfo, buf, queue=queue))[0]
    elif plan == "group":
        got, = asyncio.run(ecutil.batched_encode_group_async(
            codec, sinfo, [buf], queue=queue))
    else:
        got = asyncio.run(ecutil.batched_encode_async(
            codec, sinfo, buf, queue=queue))
    assert [bytes(g) for g in got] == ref.shards(profile, CHUNK, data)
    assert counters(queue) == (
        (staged0 + padded, loop0) if deferred else (staged0, loop0 + padded))
    assert bytes(buf) == data


def test_a_plan_with_no_queue_lays_out_on_its_callers_thread():
    codec, sinfo, profile, ref = plan_case("packedbit_resident")
    data = payload(sinfo.stripe_width + 77, seed=6)
    loop0 = ECPLAN_PERF.get("loop_layout_bytes")
    planar = asyncio.run(ecutil.planar_encode_async(codec, sinfo, data,
                                                    queue=None))
    assert [bytes(b) for b in planar[0]] == ref.shards(profile, CHUNK, data)
    assert ECPLAN_PERF.get("loop_layout_bytes") == loop0 + 2 * sinfo.stripe_width


def test_neither_plan_pads_or_copies_a_request_the_queue_can_stage(
        queue, monkeypatch):
    # the acceptance line itself: on a stable source the plans call
    # neither pad_to_stripe nor np.ascontiguousarray
    def refuse(*a, **k):
        raise AssertionError("a plan copied the object on its caller")

    codec, sinfo, _, _ = plan_case("packedbit")
    data = payload(2 * sinfo.stripe_width + 300, seed=7)
    want = ecutil.batched_encode(codec, sinfo, data, queue=None)
    monkeypatch.setattr(StripeInfo, "pad_to_stripe", refuse)
    monkeypatch.setattr(ecutil.np, "ascontiguousarray", refuse)
    for got in (
            asyncio.run(ecutil.batched_encode_async(codec, sinfo, data,
                                                    queue=queue)),
            asyncio.run(ecutil.planar_encode_async(codec, sinfo, data,
                                                   queue=queue))[0],
            asyncio.run(ecutil.batched_encode_group_async(
                codec, sinfo, [data], queue=queue))[0]):
        assert [bytes(g) for g in got] == [bytes(w) for w in want]


# -- (f) a served put ----------------------------------------------------------


@pytest.mark.parametrize("wire", ["tcp", "fastpath"])
def test_a_served_put_is_laid_out_by_the_queue_and_compiles_nothing_new(
        monkeypatch, wire):
    from ceph_tpu.rados.vstart import Cluster
    from ceph_tpu.utils.jaxdev import compile_meter

    monkeypatch.setenv("CEPH_TPU_FORCE_BATCH", "1")
    monkeypatch.setattr(osdmod, "_BATCH_QUEUE", None)
    monkeypatch.setattr(osdmod, "_PLANAR_STORE", None)
    profile = {"plugin": "tpu", "technique": "reed_sol_van", "k": "2",
               "m": "1"}
    size = 5 * 8192 + 4096 + 33  # 5.5 stripes and a bit: ragged

    async def go():
        cluster = Cluster(n_osds=4, n_mons=1,
                          conf={"ms_local_fastpath": wire == "fastpath",
                                "client_op_timeout": 120.0})
        await cluster.start()
        try:
            c = await cluster.client()
            pool = await c.create_pool("p", pg_num=4, profile=dict(profile))
            sw = c.osdmap.pools[pool].stripe_width
            padded = -(-size // sw) * sw
            q = osdmod.shared_batching_queue()
            meter = compile_meter()
            # a parent-shaped put: the same geometry handed over as rows,
            # on the lane a put with an install rides and the one without
            codec = registry.factory("tpu", "", dict(profile))
            named = StripeRows(np.frombuffer(payload(size, 1), np.uint8),
                               2, sw // 2)
            lanes = [ecutil.lane_for(codec, resident=r, cols=named.shape[1])
                     for r in (False, True)]
            for form in (named.rows(), named):
                for kind, dtype in lanes:
                    q.submit(np.asarray(codec.bit_generator()).astype(dtype),
                             form, 8, 1, kind).result(timeout=120)
                if form is not named:
                    # the same requests by name run the programs the
                    # rows compiled: same shape, dtype and bytes staged
                    compiled = meter.snapshot()["compiles"]
            assert meter.snapshot()["compiles"] == compiled
            await c.put(pool, "warm", payload(size, 2))  # the store's own
            compiled = meter.snapshot()["compiles"]
            staged0, loop0 = counters(q)
            copied0 = sum(o.perf.get("write_copied_bytes")
                          for o in cluster.osds.values())
            data = payload(size, 3)
            await c.put(pool, "obj", data)
            assert counters(q) == (staged0 + padded, loop0)
            assert meter.snapshot()["compiles"] == compiled
            assert sum(o.perf.get("write_copied_bytes")
                       for o in cluster.osds.values()) == copied0
            assert q.perf.get("breaker_fallback") == 0
            want = reed_sol_van.shards(profile, sw // 2, data)
            held = {}
            for osd in cluster.osds.values():
                for name, shard in osd.store.list_objects(pool):
                    if name == "obj":
                        got = osd.store.read((pool, "obj", shard))
                        held[shard] = bytes(getattr(got[0], "view", got[0]))
            assert [held[i] for i in sorted(held)] == want
            assert bytes(await c.get(pool, "obj")) == data
            await c.stop()
        finally:
            await cluster.stop()
            q = osdmod._BATCH_QUEUE
            if q is not None:
                q.close()

    asyncio.run(asyncio.wait_for(go(), 300))
