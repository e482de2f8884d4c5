#!/usr/bin/env python3
"""chip_smoke.py — the served EC path, end to end, on one TPU chip.

The quickest proof that the system still starts on the chip: ONE process,
which owns the chip, drives the main path through the entry points a user
calls — the codec registry, then an in-process `vstart.Cluster` and its
`client()` (client -> messenger -> OSD -> BatchingQueue -> device ->
sub-writes -> store) — and checks every byte against references that share
no code with the device path (plugin=jerasure for chunks, a dict for
objects).  It sets no CEPH_TPU_* variable and no JAX_PLATFORMS: on a TPU
backend the shared queue and the paged resident store engage by themselves.

    python chip_smoke.py              one chip (what the driver runs)
    python chip_smoke.py --multichip  four chips: ONLY the mesh EC step and
                                      what it is compared with

Each phase prints one JSON line; the LAST line of stdout is exactly
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
and the exit code is 0 — or "ok": false and a nonzero exit if any phase
failed, any fallback/breaker counter moved, or JAX found no TPU.  Nothing
here retries on the CPU.  `--rehearse` (builders, no chip) runs the same
phases at a tiny size on the CPU backend to find wrong paths; it can only
ever end in "ok": false and exit 3.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import logging
import os
import statistics
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

K, M = 8, 3
OBJECT_BYTES = 4 << 20   # upstream `rados bench` default object size
IN_FLIGHT = 16           # upstream `rados bench` default concurrency
N_OBJECTS = 128          # 512 MiB
N_DEGRADED = 16
PLANAR_BYTES = 1 << 30   # resident store budget: ~1.6e4 pages of 64 KiB
DEADLINE_S = 1140.0      # the driver allows 1200: dump stacks and fail first


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def check(cond, what: str) -> None:
    if not cond:
        raise AssertionError(what)


# -- phase 1: device -----------------------------------------------------------


def phase_device(want_count: int, rehearse: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if not rehearse:
        check(device["platform"] == "tpu",
              f"no TPU: jax.devices() is {device}")
        check(device["count"] == want_count,
              f"need {want_count} chip(s), jax.devices() has "
              f"{device['count']}")
    from ceph_tpu.utils.jaxdev import enable_compile_cache

    cache_dir = None if rehearse else enable_compile_cache()

    # the three boundary numbers ROADMAP S1 asks the first chip run for
    bump = jax.jit(lambda x: x + 1)
    x = bump(jnp.zeros((), jnp.int32)).block_until_ready()
    rtts = []
    for _ in range(200):
        t0 = time.perf_counter()
        x = bump(x).block_until_ready()
        rtts.append(time.perf_counter() - t0)
    host = np.random.default_rng(0).integers(0, 256, 16 << 20, dtype=np.uint8)
    bump8 = jax.jit(lambda a: a + jnp.uint8(1))
    h2d, d2h = [], []
    for _ in range(10):
        t0 = time.perf_counter()
        dev = jax.device_put(host).block_until_ready()
        h2d.append(time.perf_counter() - t0)
        fresh = bump8(dev).block_until_ready()  # no cached host copy
        t0 = time.perf_counter()
        back = np.asarray(fresh)
        d2h.append(time.perf_counter() - t0)
    check(np.array_equal(back, host + np.uint8(1)), "H2D/D2H round trip")
    gbps = lambda secs: host.nbytes / statistics.median(secs) / 1e9  # noqa: E731
    emit("device", ok=True, **device, host_cpu_count=os.cpu_count(),
         dispatch_rtt_ms=statistics.median(rtts) * 1e3,
         h2d_GBps_16MiB=gbps(h2d), d2h_GBps_16MiB=gbps(d2h),
         compile_cache_dir=cache_dir)
    return device


# -- phase 2: codec ------------------------------------------------------------


def phase_codec(seed: int, object_bytes: int) -> None:
    import numpy as np

    from ceph_tpu.ec.plugins.tpu import PLUGIN_PERF
    from ceph_tpu.ec.registry import registry
    from ceph_tpu.native import bridge
    from ceph_tpu.utils.jaxdev import compile_meter

    # the jerasure reference's w=8 region kernels and the store's crc32c
    # are native code built HERE from the committed sources (native/build*/
    # is not in git): a host without g++ fails this line, by name
    t0 = time.perf_counter()
    bridge.lib()
    native_s = time.perf_counter() - t0
    meter = compile_meter()
    c0 = meter.snapshot()
    t0 = time.perf_counter()

    def pair(technique, k, m):
        prof = {"technique": technique, "k": str(k), "m": str(m)}
        return (registry.factory("tpu", "", dict(prof, plugin="tpu")),
                registry.factory("jerasure", "",
                                 dict(prof, plugin="jerasure")))

    obj = np.random.default_rng(seed).integers(
        0, 256, object_bytes, dtype=np.uint8).tobytes()
    tpu, ref = pair("reed_sol_van", K, M)
    n = K + M
    got, want = tpu.encode(set(range(n)), obj), ref.encode(set(range(n)), obj)
    for c in range(n):
        check(np.array_equal(got[c], want[c]), f"encode chunk {c} differs")
    erased = {1, 4, 9}
    avail = {c: got[c] for c in range(n) if c not in erased}
    back = tpu.decode(erased, avail, len(got[0]))
    for c in erased:
        check(np.array_equal(back[c], want[c]), f"decoded chunk {c} differs")
    check(tpu.decode_concat(avail)[:len(obj)] == obj, "decode_concat differs")

    ctpu, cref = pair("cauchy_good", 10, 4)
    rows0 = PLUGIN_PERF.get("apply_rows")
    got, want = ctpu.encode(set(range(14)), obj), cref.encode(set(range(14)), obj)
    for c in range(14):
        check(np.array_equal(got[c], want[c]),
              f"cauchy_good chunk {c} differs")
    check(PLUGIN_PERF.get("apply_rows") > rows0,
          "cauchy_good did not cross the _apply_rows seam")

    perf = PLUGIN_PERF.dump()
    c1 = meter.snapshot()
    emit("codec", ok=True, object_bytes=object_bytes,
         seconds=time.perf_counter() - t0, native_build_s=native_s,
         native_simd=bridge.simd_kind(),
         ec_plugin={k: perf[k] for k in
                    ("apply", "apply_rows", "cpu_fallback", "device_failed")},
         **{k: c1[k] - c0[k] for k in c1})
    check(perf["apply"] > 0, "ec_plugin.apply == 0: no device dispatch")
    check(perf["cpu_fallback"] == 0, "ec_plugin.cpu_fallback != 0")
    check(perf["device_failed"] == 0, "ec_plugin.device_failed != 0")


# -- phase 3: cluster ----------------------------------------------------------


async def _wait_for(pred, seconds: float, what: str) -> None:
    deadline = time.monotonic() + seconds
    while time.monotonic() < deadline:
        if await pred():
            return
        await asyncio.sleep(0.2)
    raise TimeoutError(f"timed out after {seconds:.0f}s waiting for {what}")


async def _bounded(n_in_flight: int, jobs) -> float:
    """Run coroutine thunks with at most n in flight; wall seconds."""
    sem = asyncio.Semaphore(n_in_flight)

    async def one(job):
        async with sem:
            await job()

    t0 = time.perf_counter()
    await asyncio.gather(*(one(j) for j in jobs))
    return time.perf_counter() - t0


async def phase_cluster(seed: int, n_objects: int, object_bytes: int,
                        n_degraded: int) -> None:
    import numpy as np

    from ceph_tpu.rados.vstart import Cluster
    from ceph_tpu.utils.jaxdev import compile_meter

    meter = compile_meter()
    marks = {"start": meter.snapshot()}
    t_start = time.perf_counter()
    conf = {
        "osd_ec_planar_bytes": PLANAR_BYTES,
        # a cold first compile holds an op for tens of seconds: the client
        # waits for it instead of resending
        "client_op_timeout": 300.0,
    }
    cluster = Cluster(n_osds=K + M + 1, conf=conf, n_mons=3)
    await cluster.start()
    try:
        c = await cluster.client()
        pool = await c.create_pool("smoke", profile={
            "plugin": "tpu", "technique": "reed_sol_van",
            "k": str(K), "m": str(M)})
        startup_s = time.perf_counter() - t_start
        marks["started"] = meter.snapshot()

        rng = np.random.default_rng(seed)
        model = {f"benchmark_data_{i:08d}":
                 rng.integers(0, 256, object_bytes, dtype=np.uint8).tobytes()
                 for i in range(n_objects)}

        async def verify(oid):
            got = await c.get(pool, oid)
            check(bytes(got) == model[oid], f"{oid} NOT byte-identical")

        put_s = await _bounded(IN_FLIGHT, [
            (lambda o=o, d=d: c.put(pool, o, d)) for o, d in model.items()])
        marks["put"] = meter.snapshot()
        get_s = await _bounded(IN_FLIGHT, [
            (lambda o=o: verify(o)) for o in model])
        marks["get"] = meter.snapshot()
        osd0 = next(iter(cluster.osds.values()))
        store, queue = osd0._planar, osd0._ec_queue
        check(queue is not None, "no BatchingQueue engaged on this backend")
        check(store is not None, "no resident store engaged on this backend")
        hits0 = store.perf.get("hit")
        get2_s = await _bounded(IN_FLIGHT, [
            (lambda o=o: verify(o)) for o in model])
        resident_hits = store.perf.get("hit") - hits0
        marks["get2"] = meter.snapshot()

        # kill the primary of the fullest PG: reads of its objects lose the
        # primary's residents and are decoded, on the device, from the
        # survivors' shards
        await c.refresh_map()
        pinfo = c.osdmap.pools[pool]
        by_pg: dict = {}
        for oid in model:
            by_pg.setdefault(c.osdmap.object_to_pg(pinfo, oid), []).append(oid)
        pg = max(by_pg, key=lambda p: len(by_pg[p]))
        victim = c.osdmap.pg_to_acting(pinfo, pg)[0]
        degraded = by_pg[pg][:n_degraded]
        for p in sorted(by_pg, key=lambda p: -len(by_pg[p])):
            if len(degraded) >= n_degraded:
                break
            if p != pg and victim in c.osdmap.pg_to_acting(pinfo, p):
                degraded += by_pg[p][:n_degraded - len(degraded)]
        decodes0 = queue.perf.get("submit_packedbit")
        await cluster.kill_osd(victim)
        degraded_s = await _bounded(IN_FLIGHT, [
            (lambda o=o: verify(o)) for o in degraded])
        decodes = queue.perf.get("submit_packedbit") - decodes0
        marks["degraded"] = meter.snapshot()

        async def outed():
            await c.refresh_map()
            info = c.osdmap.osds[victim]
            return (not info.up) and (not info.in_cluster)

        def shards_on_live_osds():
            have: dict = {}
            for osd in cluster.osds.values():
                for oid, shard in osd.store.list_objects(pool):
                    if shard < K + M:  # not a rollback slot
                        have.setdefault(oid, set()).add(shard)
            return sum(len(have.get(oid, ())) for oid in model)

        async def recovered():
            # full redundancy by the stores' own listing (health lags the
            # kill, so "not degraded" alone can be true too early)
            if shards_on_live_osds() != n_objects * (K + M):
                return False
            await c.refresh_map()
            if not (await c.osd_safe_to_destroy(victim)).safe:
                return False
            checks = (await c.get_health()).get("checks") or {}
            return "PG_DEGRADED" not in checks

        shards_lost = n_objects * (K + M) - shards_on_live_osds()
        t0 = time.perf_counter()
        await _wait_for(outed, 60.0, f"osd.{victim} marked down and out")
        await _wait_for(recovered, 600.0, "recovery to clean")
        recovery_s = time.perf_counter() - t0
        marks["recovered"] = meter.snapshot()
        reread_s = await _bounded(IN_FLIGHT, [
            (lambda o=o: verify(o)) for o in degraded])

        from ceph_tpu.ops.slab import SLAB_PERF
        from ceph_tpu.rados.pagestore import device_slab_resolved

        device_arm = (bool(getattr(store, "device_arm", False))
                      and device_slab_resolved(None))
        live = next(iter(cluster.osds.values()))
        perf = live.ctx.perf.dump()
        ec, sched = perf["ec_tpu"], perf["gf2_sched"]
        plug, st = perf["ec_plugin"], perf[store.perf.name]
        lanes = {k[len("submit_"):]: v for k, v in ec.items()
                 if k.startswith("submit_packedbit") and v}
        names = list(marks)
        emit("cluster", ok=True, osds=K + M + 1, mons=3,
             profile=f"tpu reed_sol_van k={K} m={M}",
             objects=n_objects, object_bytes=object_bytes,
             in_flight=IN_FLIGHT,
             startup_s=startup_s, put_s=put_s, get_s=get_s,
             get_resident_s=get2_s, resident_hits=resident_hits,
             degraded_objects=len(degraded), degraded_s=degraded_s,
             degraded_decode_submits=decodes,
             shards_lost_with_the_osd=shards_lost, recovery_s=recovery_s,
             reread_s=reread_s,
             dispatch=ec["dispatch"], packedbit_lanes=lanes,
             mean_group_size=(ec["submit"] / ec["dispatch"]
                              if ec["dispatch"] else 0.0),
             dispatch_dev=ec["dispatch_dev"],
             dispatch_compile=ec["dispatch_compile"],
             breaker={k: ec[k] for k in
                      ("breaker_trip", "breaker_fallback",
                       "breaker_open_lanes", "breaker_probe")},
             ec_plugin={k: plug[k] for k in
                        ("apply", "apply_rows", "cpu_fallback",
                         "device_failed")},
             store={"name": store.perf.name, "device_arm": device_arm,
                    **{k: st[k] for k in
                       ("admit", "hit", "miss", "evict", "pages_used",
                        "pages_total", "device_installs", "h2d_installs",
                        "d2h_gathers") if k in st}},
             gf2_sched={k: sched[k] for k in
                        ("compile", "hit", "miss", "evict")},
             slab_kernels_compiled=SLAB_PERF.get("compile"),
             compiles_by_step={
                 b: {k: marks[b][k] - marks[a][k] for k in marks[b]}
                 for a, b in zip(names, names[1:])})
        check(ec["dispatch"] > 0 and lanes,
              "ec_tpu.dispatch == 0 on the packedbit lanes")
        for k in ("breaker_trip", "breaker_fallback", "breaker_open_lanes"):
            check(ec[k] == 0, f"ec_tpu.{k} == {ec[k]}: a lane left the device")
        check(plug["cpu_fallback"] == 0, "ec_plugin.cpu_fallback != 0")
        check(plug["device_failed"] == 0, "ec_plugin.device_failed != 0")
        check(st["admit"] > 0, f"{store.perf.name}.admit == 0")
        check(resident_hits > 0, "second read pass hit no resident")
        check(decodes > 0, "degraded reads decoded nothing on the device")
        check(device_arm, "the resident store's device arm did not engage")
        await c.stop()
    finally:
        await cluster.stop()


# -- --multichip: the mesh EC step and what it is compared with ----------------


def phase_multichip(seed: int, n_devices: int, object_bytes: int) -> None:
    """One EC pipeline step — coalesced encodes, a 3-erasure decode, the
    resident repair re-encode — through a BatchingQueue on the n-device
    mesh and again on mesh=False, both held to the CPU oracle."""
    import jax
    import numpy as np

    from ceph_tpu.ec.gf import gf
    from ceph_tpu.ec.registry import registry
    from ceph_tpu.parallel.mesh import MeshDispatcher
    from ceph_tpu.parallel.service import BatchingQueue
    from ceph_tpu.rados.ecutil import (StripeInfo, decode_object,
                                       planar_encode_async, planar_rows)
    from ceph_tpu.rados.pagestore import PagedResidentStore

    codec = registry.factory("jerasure", "", {
        "plugin": "jerasure", "technique": "reed_sol_van",
        "k": str(K), "m": str(M)})
    sinfo = StripeInfo(k=K, stripe_width=K * 4096)
    rng = np.random.default_rng(seed)
    # ragged on purpose: the last stripe of every object is mostly padding
    objects = [rng.integers(0, 256, size=object_bytes + 100,
                            dtype=np.uint8).tobytes() for _ in range(4)]
    mat = np.asarray(codec.matrix, dtype=np.int64)
    oracle = []
    for obj in objects:
        flat = np.frombuffer(sinfo.pad_to_stripe(obj), np.uint8).reshape(
            -1, K, sinfo.chunk_size).transpose(1, 0, 2).reshape(K, -1)
        oracle.append(gf(8).matmul(mat, flat))

    def step(mesh):
        queue = BatchingQueue(max_delay=0.02, mesh=mesh if mesh else False)
        store = PagedResidentStore(capacity_bytes=64 << 20, queue=queue)
        try:
            async def encodes():
                return await asyncio.gather(*(
                    planar_encode_async(codec, sinfo, obj, queue=queue)
                    for obj in objects))

            device_sets = []
            shard_lists = []
            for i, res in enumerate(asyncio.run(encodes())):
                blobs, all_bits, n_rows, n_cols, w = res
                device_sets.append(len(all_bits.sharding.device_set))
                store.put_planar(("obj", i), all_bits, w=w, n_rows=n_rows,
                                 meta=(1, n_cols, len(objects[i])))
                shard_lists.append(blobs)
                got = np.stack([np.asarray(b) for b in blobs[K:]])
                check(np.array_equal(got, oracle[i]),
                      f"obj{i} parity differs from the CPU oracle")
            for i, (obj, blobs) in enumerate(zip(objects, shard_lists)):
                avail = {s: np.asarray(b) for s, b in enumerate(blobs)
                         if s not in (0, 4, 10)}
                check(decode_object(codec, sinfo, avail, len(obj),
                                    queue=queue) == obj,
                      f"obj{i} 3-erasure decode differs")
                rows = planar_rows(store, ("obj", i), 1)
                check(rows is not None, f"obj{i} resident missing")
                for a, b in zip(blobs, rows):
                    check(np.array_equal(np.asarray(a), b),
                          f"obj{i} repair re-encode rows differ")
            perf = queue.perf.dump()
            return {"dispatch": perf["dispatch"],
                    "sharded_dispatch": perf["sharded_dispatch"],
                    "mesh_shard_failed": perf["mesh_shard_failed"],
                    "breaker_trip": perf["breaker_trip"],
                    "breaker_fallback": perf["breaker_fallback"],
                    "resident_device_sets": device_sets}
        finally:
            queue.close()

    devices = jax.devices()[:n_devices]
    check(len(devices) == n_devices,
          f"need {n_devices} devices, have {len(devices)}")
    meshed = step(MeshDispatcher(devices))
    single = step(None)
    emit("multichip", ok=True, n_devices=n_devices, objects=len(objects),
         object_bytes=len(objects[0]), mesh=meshed, single=single)
    check(meshed["dispatch"] > 0
          and meshed["sharded_dispatch"] == meshed["dispatch"],
          f"mesh step: sharded_dispatch != dispatch ({meshed})")
    check(all(n == n_devices for n in meshed["resident_device_sets"]),
          f"mesh step: an output does not span {n_devices} devices")
    check(single["sharded_dispatch"] == 0, "mesh=False step ran sharded")
    for run in (meshed, single):
        check(run["breaker_trip"] == 0 and run["breaker_fallback"] == 0
              and run["mesh_shard_failed"] == 0,
              f"a dispatch left the device: {run}")


# -- driver --------------------------------------------------------------------


def _arm_deadline(seconds: float) -> None:
    """A hung phase must not hold the chip past the run's time limit: dump
    all stacks (to see where), print the failing last line, exit."""
    import faulthandler
    import threading

    def expire():
        faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
        print(json.dumps({"ok": False, "device": None,
                          "error": f"deadline of {seconds:.0f}s passed"}),
              flush=True)
        os._exit(1)

    timer = threading.Timer(seconds, expire)
    timer.daemon = True
    timer.start()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=23)
    ap.add_argument("--multichip", action="store_true",
                    help="four chips: only the mesh EC step and its "
                         "single-device comparison")
    ap.add_argument("--rehearse", action="store_true",
                    help="no chip: tiny sizes on the CPU backend, to find "
                         "wrong paths; always ends ok=false, exit 3")
    args = ap.parse_args(argv)
    _arm_deadline(DEADLINE_S)
    logging.basicConfig(
        level=logging.WARNING, stream=sys.stderr,
        format="%(asctime)s %(name)s %(levelname)s %(message)s")

    n_objects, object_bytes, n_degraded = N_OBJECTS, OBJECT_BYTES, N_DEGRADED
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["CEPH_TPU_FORCE_BATCH"] = "1"
        os.environ["CEPH_TPU_DEVICE_SLAB"] = "1"
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=4")
        n_objects, object_bytes, n_degraded = 8, 256 << 10, 4

    device = None
    t0 = time.perf_counter()
    try:
        device = phase_device(4 if args.multichip else 1, args.rehearse)
        if args.multichip:
            phase_multichip(args.seed, 4, object_bytes)
        else:
            phase_codec(args.seed, object_bytes)
            asyncio.run(phase_cluster(args.seed, n_objects, object_bytes,
                                      n_degraded))
    except Exception as e:
        traceback.print_exc()
        print(json.dumps({"ok": False, "device": device,
                          "error": f"{type(e).__name__}: {e}"}), flush=True)
        return 1
    from ceph_tpu.utils.jaxdev import compile_meter

    emit("total", seconds=time.perf_counter() - t0,
         **compile_meter().snapshot())
    if args.rehearse:
        print(json.dumps({"ok": False, "rehearsal": True, "device": device}),
              flush=True)
        return 3
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
