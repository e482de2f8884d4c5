#!/usr/bin/env python3
"""One run of one cell of the benchmark (BENCHMARK.json), on the chip.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

ONE process, which owns the chip: import, device line, cluster start, pool,
warm-up, timed window, verification, last line, exit.  It sets no CEPH_TPU_*
variable and no JAX_PLATFORMS, starts no child process and never falls back
to the CPU: without a TPU, or with another number of chips than the cell
asks for, it prints a failing last line with no metric and exits nonzero.

Every line of stdout is one JSON object; all but the last carry a "phase".
The last is {"correct", "attempted", "failed", "metrics", "device"} and, in
a traced run, "breakdown".  --trace 0 reports the cell's end-to-end metrics,
--trace 1 its per-layer metrics.

    --rehearse   builders, no chip: the same path at tiny sizes on the CPU
                 backend (CEPH_TPU_FORCE_BATCH=1), to find wrong paths;
                 always ends "correct": false and exit 3
    --control K  break the path underneath (benchmarks/control.py) to see
                 `correct` come out false; never part of a measurement
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # as near to process start as Python lets us

import argparse  # noqa: E402
import asyncio  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import (control, counters, layers, manifest, stats,  # noqa: E402
                        trace_reduce, verify)

DEADLINE_S = 1150.0  # a first run may take 1200 s: dump stacks and fail first
TRACE_DIR = os.path.join(HERE, ".trace")  # rewritten by each traced run
SPAN_NAME = "benchmark_traced_span"
NAME_CHARS = 96  # of an operation's name in the breakdown (HLO text is long)


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def last_line(correct: bool, attempted: int = 0, failed: int = 0,
              metrics=None, device=None, **more) -> None:
    print(json.dumps({"correct": bool(correct), "attempted": attempted,
                      "failed": failed, "metrics": metrics or {},
                      "device": device, **more}), flush=True)


def arm_deadline(seconds: float) -> None:
    """A hung phase must not hold the chip past the run's time limit: dump
    all stacks (to see where), print a failing last line, exit."""
    import faulthandler
    import threading

    def expire():
        faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
        last_line(False, error=f"deadline of {seconds:.0f}s passed")
        os._exit(1)

    timer = threading.Timer(seconds, expire)
    timer.daemon = True
    timer.start()


class NoChip(Exception):
    pass


def phase_device(want_count: int, rehearse: bool) -> dict:
    """The device as JAX reports it, and the three boundary numbers every
    dispatch pays: round trip, H2D, D2H."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if not rehearse:
        if device["platform"] != "tpu":
            raise NoChip(f"no TPU: jax.devices() is {device}")
        if device["count"] != want_count:
            raise NoChip(f"the cell needs {want_count} chip(s), "
                         f"jax.devices() has {device['count']}")
    bump = jax.jit(lambda x: x + 1)
    x = bump(jnp.zeros((), jnp.int32)).block_until_ready()
    rtts = []
    for _ in range(100):
        t0 = time.perf_counter()
        x = bump(x).block_until_ready()
        rtts.append(time.perf_counter() - t0)
    host = np.random.default_rng(0).integers(0, 256, 16 << 20, dtype=np.uint8)
    bump8 = jax.jit(lambda a: a + jnp.uint8(1))
    h2d, d2h = [], []
    for _ in range(4):
        t0 = time.perf_counter()
        dev = jax.device_put(host).block_until_ready()
        h2d.append(time.perf_counter() - t0)
        fresh = bump8(dev).block_until_ready()  # no cached host copy
        t0 = time.perf_counter()
        np.asarray(fresh)
        d2h.append(time.perf_counter() - t0)
    emit("device", **device, host_cpu_count=os.cpu_count(),
         dispatch_rtt_ms=statistics.median(rtts) * 1e3,
         h2d_GBps_16MiB=host.nbytes / statistics.median(h2d) / 1e9,
         d2h_GBps_16MiB=host.nbytes / statistics.median(d2h) / 1e9)
    return device


class Env:
    """What a generator sees of the run: the cell, the seed, the started
    cluster's client and pool, and the few looks into the cluster that the
    verification needs."""

    def __init__(self, cell, seed, cluster, client, pool, meter) -> None:
        self.cell, self.seed = cell, int(seed)
        self.cluster, self.client, self.pool = cluster, client, pool
        self.meter = meter
        self.emit = emit
        cfg = cell.config
        self.profile = cfg["profile"]
        self.n_shards = int(self.profile["k"]) + int(self.profile["m"])
        ref = importlib.import_module(
            "benchmarks.references." + cfg["reference"])
        self.reference = lambda payload: ref.shards(
            self.profile, int(cfg["stripe_unit"]), payload)
        osd = next(iter(cluster.osds.values()))
        self.queue, self.store = osd._ec_queue, osd._planar
        self.store_set = self.store.perf.name if self.store is not None \
            else "no_resident_store"
        self.acked_without_all_shards = 0

    def live_osds(self):
        return list(self.cluster.osds.values())

    async def put(self, oid: str, data: bytes) -> None:
        """client.put, and the guarantee looked at the moment the ack
        arrives, before this task yields: every one of the k+m shard
        positions is in some live OSD's object store."""
        await self.client.put(self.pool, oid, data)
        held = sum(
            1 for pos in range(self.n_shards)
            if any(osd.store.read((self.pool, oid, pos)) is not None
                   for osd in self.cluster.osds.values()))
        if held < self.n_shards:
            self.acked_without_all_shards += 1

    def group_sizes(self) -> list:
        if self.queue is None:
            return []
        return list(self.queue.perf.dump()["group_size"]["buckets"][:8])

    def store_device_arm(self) -> bool:
        from ceph_tpu.rados.pagestore import device_slab_resolved

        return (bool(getattr(self.store, "device_arm", False))
                and bool(device_slab_resolved(None)))

    def resident_room(self) -> dict:
        """How far the resident store is from the line above which the
        tier agents shed parity pages and evict (the configuration's
        osd_cache_target_full_ratio of the store's pages)."""
        ratio = float(self.cell.config["conf"]["osd_cache_target_full_ratio"])
        total, used = self.store.pages_total, self.store.pages_used
        line = int(ratio * total)
        return {"pages_total": total, "pages_used": used,
                "evict_line_pages": line, "pages_below_line": line - used}

    def snapshot(self) -> dict:
        return counters.snapshot(
            [osd.ctx.perf for osd in self.cluster.osds.values()],
            [self.client.perf, self.client.messenger.perf], self.meter)


async def health(env: Env, at: str) -> dict:
    """The cluster as the mons see it: a run with an OSD down, or with
    recovery under way, is not the deployment the configuration states."""
    client = env.client
    await client.refresh_map()
    osds = client.osdmap.osds.values()
    checks = (await client.get_health()).get("checks") or {}
    seen = env.snapshot()
    out = {"epoch": client.osdmap.epoch,
           "osds_up": sum(1 for o in osds if o.up),
           "osds_in": sum(1 for o in osds if o.in_cluster),
           "checks": sorted(checks),
           "heartbeat_failures": seen.get("osd.heartbeat_failures", 0),
           "recovery_push": seen.get("osd.recovery_push", 0)}
    emit("health", at=at, **out)
    return out


async def wait_healthy(env: Env, n_osds: int, seconds: float) -> dict:
    deadline = time.monotonic() + seconds
    while True:
        seen = await health(env, "before_window")
        if seen["osds_up"] == seen["osds_in"] == n_osds \
                and "PG_DEGRADED" not in seen["checks"]:
            return seen
        if time.monotonic() > deadline:
            raise RuntimeError(f"cluster not healthy before the window: {seen}")
        await asyncio.sleep(1.0)


async def traced_span(env: Env, offset_s: float, seconds: float) -> dict:
    """Profile `seconds` of the running window, `offset_s` into it.  Only
    this process can trace the chip.  The Python tracer stays off: twelve
    OSDs' Python in one process would make the trace, not the run."""
    import jax

    loop = asyncio.get_running_loop()
    await asyncio.sleep(offset_s)
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    t0 = time.perf_counter()
    await loop.run_in_executor(None, lambda: jax.profiler.start_trace(
        TRACE_DIR, profiler_options=options))
    before = env.snapshot()
    t1 = time.perf_counter()
    with jax.profiler.TraceAnnotation(SPAN_NAME):
        await asyncio.sleep(seconds)
    t2 = time.perf_counter()
    after = env.snapshot()
    await loop.run_in_executor(None, jax.profiler.stop_trace)
    t3 = time.perf_counter()
    return {"counters": counters.delta(after, before), "span_s": t2 - t1,
            "start_s": t1 - t0, "stop_s": t3 - t2}


def reduce_trace(span: dict) -> dict:
    """The traced span's device busy time, operations and idle gaps, cut to
    the annotated span where the trace has it."""
    t0 = time.perf_counter()
    path = trace_reduce.find_xplane(TRACE_DIR)
    trace = trace_reduce.from_xplane(path)
    bounds = [(s, s + d) for p in trace["planes"] for line in p["lines"]
              for name, s, d in line["events"] if name == SPAN_NAME]
    red = trace_reduce.reduce(trace, *(bounds[0] if bounds else (None, None)),
                              ignore=(SPAN_NAME,))
    emit("trace", xplane_bytes=os.path.getsize(path),
         span_found=bool(bounds), span_s=span["span_s"],
         start_trace_s=span["start_s"], stop_trace_s=span["stop_s"],
         window_s=red["window_s"], busy_s=red["busy_s"],
         devices=red["devices"], reduce_s=time.perf_counter() - t0,
         modules=sorted(trace_reduce.time_by_name(
             red["modules"], red["t0"], red["t1"]).items(),
             key=lambda kv: -kv[1])[:10])
    # what the trace holds, for a human: planes, lines, names by time
    with open(os.path.join(TRACE_DIR, "summary.json"), "w") as f:
        json.dump(trace_reduce.summary(trace, top=15), f, indent=1)
    return red


async def run_cell(cell, args, device: dict) -> dict:
    """Cluster, pool, warm-up, window, verification: everything of a run
    but the look for a chip.  Returns the last line's fields."""
    import jax

    from ceph_tpu.rados.vstart import Cluster
    from ceph_tpu.utils.jaxdev import compile_meter

    cfg = cell.config
    for key, val in cfg.get("jax_config", {}).items():
        jax.config.update(key, val)
    meter = compile_meter()
    marks = [("process", meter.snapshot())]
    gen_mod = importlib.import_module(
        "benchmarks.generators." + cell.traffic["kind"])
    t0 = time.perf_counter()
    cluster = Cluster(n_osds=int(cfg["osds"]), conf=dict(cfg["conf"]),
                      n_mons=int(cfg["mons"]))
    await cluster.start()
    try:
        client = await cluster.client()
        pool = await client.create_pool(
            "bench", pg_num=int(cfg["pg_num"]), profile=dict(cfg["profile"]))
        env = Env(cell, args.seed, cluster, client, pool, meter)
        if args.control:
            emit("control", broke=control.apply(args.control, cluster, client))
        marks.append(("cluster", meter.snapshot()))
        emit("cluster", osds=cfg["osds"], mons=cfg["mons"],
             pg_num=cfg["pg_num"], profile=cfg["profile"],
             seconds=time.perf_counter() - t0,
             queue=type(env.queue).__name__, store=type(env.store).__name__)
        gen = gen_mod.Generator(env)
        await gen.setup()
        marks.append(("warmup", meter.snapshot()))
        await wait_healthy(env, int(cfg["osds"]), 120.0)

        before = env.snapshot()
        groups_before = env.group_sizes()
        setup_s = time.perf_counter() - T_PROCESS
        tracer = None
        if args.trace:
            spec = cell.traffic["trace"]
            # "seconds": null traces the whole window
            tracer = asyncio.ensure_future(traced_span(
                env, float(spec["offset_s"]),
                min(float(spec["seconds"] or args.seconds), args.seconds)))
        records, w0, w1 = await gen.window(args.seconds)
        drained_s = time.perf_counter() - w1
        span = await tracer if tracer is not None else None
        moved = counters.delta(env.snapshot(), before)
        marks.append(("window", meter.snapshot()))

        after = await health(env, "after_window")
        checks = await gen.verify() + gen.counter_checks(moved) + [
            verify.at_least("osds_up_and_in",
                            min(after["osds_up"], after["osds_in"]),
                            int(cfg["osds"])),
            verify.at_most("osd.heartbeat_failures",
                           after["heartbeat_failures"])]
        marks.append(("verify", meter.snapshot()))
        emit("verify", checks=checks)
        seen = stats.window_metrics(records, w0, w1)
        emit("window", op=gen_mod.OP, seconds=w1 - w0, drained_s=drained_s,
             **seen, window_compiles=moved.get("compile_meter.compiles"),
             window_compile_s=moved.get("compile_meter.compile_s"),
             window_recovery_push=moved.get("osd.recovery_push", 0),
             window_resends=moved.get("objecter.resends", 0))
        emit("counters", moved={k: v for k, v in moved.items() if v})
        emit("compiles_by_step", **{
            b[0]: {k: round(b[1][k] - a[1][k], 3) for k in b[1]}
            for a, b in zip(marks, marks[1:])})
        store_bytes = sum(osd.store.statfs()["used"]
                          for osd in cluster.osds.values())
        # dispatches of the window by log2 of their group size: index i
        # counts groups of 2**(i-1) .. 2**i - 1 coalesced encodes
        emit("queue", window_group_size_log2=[
            b - a for a, b in zip(groups_before, env.group_sizes())])
        emit("stores", object_store_bytes=store_bytes,
             resident={k: v for k, v in env.snapshot().items()
                       if k.startswith(env.store_set + ".") and v})

        stats_of = jax.devices()[0].memory_stats() or {}
        device = dict(device,
                      memory_peak_bytes=stats_of.get("peak_bytes_in_use"))
        out = {"correct": all(c["ok"] for c in checks)
               and seen["failed"] == 0 and seen["completed_in_window"] > 0,
               "attempted": seen["attempted"], "failed": seen["failed"],
               "device": device}
        op = gen_mod.OP
        if not args.trace:
            have = {f"{op}_MBps": seen["MBps"], f"{op}_p95_ms": seen["p95_ms"],
                    "setup_s": setup_s}
            names, read = cell.end_to_end, have.get
        else:
            red = reduce_trace(span)
            device.update(busy_s=red["busy_s"], window_s=red["window_s"])
            out["breakdown"] = {
                key: [[name[:NAME_CHARS], secs] for name, secs in red[key]]
                for key in ("device_ops", "idle_gaps")}
            ctx = {"counters": moved, "trace_counters": span["counters"],
                   "trace": red, "window": seen,
                   "device_kind": device["kind"],
                   "profile": cfg["profile"]}
            names = cell.per_layer
            read = lambda name: layers.read(name, ctx)  # noqa: E731
        out["metrics"] = {}
        for m in names:
            value = read(m["name"])
            if value is not None:
                out["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
            elif not args.trace:
                raise RuntimeError(
                    f"no value for end-to-end metric {m['name']!r}: either "
                    f"no {op} completed in the window or the harness does "
                    f"not take it for a {op} mix (it takes {sorted(have)})")
        await client.stop()
        return out
    finally:
        await cluster.stop()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="length of the timed window (default: the "
                         "manifest's run_seconds)")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", choices=control.KINDS, default=None)
    args = ap.parse_args(argv)
    arm_deadline(DEADLINE_S)
    logging.basicConfig(
        level=logging.WARNING, stream=sys.stderr,
        format="%(asctime)s %(name)s %(levelname)s %(message)s")
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["CEPH_TPU_FORCE_BATCH"] = "1"
        os.environ["CEPH_TPU_DEVICE_SLAB"] = "1"

    device = None
    try:
        spec = manifest.load()
        cell = manifest.resolve(spec, args.workload, args.rehearse)
        if args.seconds is None:
            args.seconds = float(spec["run_seconds"])
        from ceph_tpu.utils.jaxdev import enable_compile_cache

        cache_dir = None if args.rehearse else enable_compile_cache()
        device = phase_device(cell.chips, args.rehearse)
        emit("start", workload=cell.name, config=cell.config_name,
             traffic=cell.traffic_name, seed=args.seed, seconds=args.seconds,
             trace=args.trace, compile_cache_dir=cache_dir,
             import_s=time.perf_counter() - T_PROCESS)
        out = asyncio.run(run_cell(cell, args, device))
    except NoChip as e:
        last_line(False, device=device, error=str(e))
        return 2
    except Exception as e:
        traceback.print_exc()
        last_line(False, device=device, error=f"{type(e).__name__}: {e}")
        return 1
    if args.rehearse:
        out["rehearsal"], out["would_be_correct"] = True, out["correct"]
        out["correct"] = False
    last_line(**out)
    if args.rehearse:
        return 3
    return 0 if out["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
