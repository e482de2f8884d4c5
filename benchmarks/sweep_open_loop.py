#!/usr/bin/env python3
"""The sweep that finds the rate an open-loop cell sustains (once, by a
builder, on the chip; never part of a measurement or of the driver's runs).

    python3 benchmarks/sweep_open_loop.py --workload <cell> --seed <n> \
        --rates 39.0625,48.828125 --windows 2,3

ONE process and ONE set-up (run.py's: device line, cluster, pool, the
generator's own set-up at the traffic file's rate), then at each rate ONE
stretch of `--windows` windows of `--seconds`, played without a pause: what
a window leaves unanswered is the next window's backlog (the generator
drains only between rates).  One JSON line a window: ops due in it, ops
answered in it, ops outstanding at its end, the p95 from due time of the
puts due in it; one line a rate.  `--rates` lists the rates; without it
they rise from `--first-rate` by `--factor`, `--steps` of them, and the
sweep stops two rates past the first that is not sustained.  A rate is
sustained where the median over its windows (over every seed's, where the
builder runs the sweep under several `--seed`s) of ops answered over ops
due is at least 0.97 and nothing was shed.  Every get of the whole run is
held to the object model at the end.  `--rehearse` as run.py's (CPU, tiny
sizes)."""

from __future__ import annotations

import argparse
import asyncio
import importlib
import os
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import manifest, run, stats  # noqa: E402

SUSTAINED = 0.97


async def sweep(cell, args) -> int:
    import jax

    from ceph_tpu.rados.vstart import Cluster
    from ceph_tpu.utils.jaxdev import compile_meter

    cfg = cell.config
    for key, val in cfg.get("jax_config", {}).items():
        jax.config.update(key, val)
    meter = compile_meter()
    gen_mod = importlib.import_module(
        "benchmarks.generators." + cell.traffic["kind"])
    cluster = Cluster(n_osds=int(cfg["osds"]), conf=dict(cfg["conf"]),
                      n_mons=int(cfg["mons"]))
    await cluster.start()
    try:
        client = await cluster.client()
        pool = await client.create_pool(
            "bench", pg_num=int(cfg["pg_num"]), profile=dict(cfg["profile"]))
        env = run.Env(cell, args.seed, cluster, client, pool, meter)
        gen = gen_mod.Generator(env)
        await gen.setup()
        await run.wait_healthy(env, int(cfg["osds"]), 120.0)
        if args.rates:
            rates = [float(r) for r in args.rates.split(",")]
        else:
            rates = [args.first_rate * args.factor ** i
                     for i in range(args.steps)]
        counts = [int(w) for w in str(args.windows).split(",")]
        counts += counts[-1:] * (len(rates) - len(counts))
        failing, knee = 0, None
        for rate, windows in zip(rates, counts):
            gen.records, gen.late = [], gen_mod.Lateness()
            gen.window_lat = {k: [] for k in gen_mod.KINDS}
            compiles, before = meter.count, env.snapshot()
            t0, _t1, offered = await gen.play(args.seconds * windows, rate,
                                              True)
            moved = run.counters.delta(env.snapshot(), before)
            # (due, done) of every op answered, on the stretch's clock
            ops = {k: [(done - lat - t0, done - t0)
                       for lat, done in gen.window_lat[k]]
                   for k in gen_mod.KINDS}
            flat = [x for k in ops for x in ops[k]]
            ratios = []
            for w in range(windows):
                lo, hi = w * args.seconds, (w + 1) * args.seconds
                due = sum(1 for d, _ in flat if lo <= d < hi)
                done = sum(1 for _, a in flat if lo <= a < hi)
                puts = [(a - d) * 1e3 for d, a in ops["put"] if lo <= d < hi]
                gets = [(a - d) * 1e3 for d, a in ops["get"] if lo <= d < hi]
                ratios.append(done / max(1, due))
                run.emit(
                    "sweep_window", rate=rate, window=w,
                    due_ops_per_s=due / args.seconds,
                    answered_ops_per_s=done / args.seconds,
                    answered_of_due=ratios[-1],
                    answered_of_nominal=done / (rate * args.seconds),
                    outstanding_at_end=sum(1 for d, a in flat
                                           if d < hi <= a),
                    put_p50_ms=stats.percentile(puts, 50),
                    put_p95_ms=stats.percentile(puts, 95),
                    get_p95_ms=stats.percentile(gets, 95))
            ok = (statistics.median(ratios) >= SUSTAINED
                  and gen.late.shed == 0)
            run.emit("sweep_step", rate=rate, sustained=ok, windows=windows,
                     offered=offered, answered=len(flat),
                     median_answered_of_due=statistics.median(ratios),
                     shed=gen.late.shed, failed=len(gen.failed),
                     worst_lateness_ms=gen.late.worst_s * 1e3,
                     peak_outstanding=gen.late.peak_outstanding,
                     compiles=meter.count - compiles,
                     loop_busy_s=moved.get("loop.busy.sum"),
                     loop_select_s=moved.get("loop.select.sum"),
                     dispatches=moved.get("ec_tpu.dispatch"),
                     encodes=moved.get("ec_tpu.submit"))
            if ok and not failing:
                knee = rate
            failing += not ok
            if failing >= 2 and not args.rates:
                break
        judged = gen.history.check_gets()
        run.emit("sweep", highest_sustained_rate=knee,
                 four_fifths=None if knee is None else 0.8 * knee,
                 gets_checked=judged["gets_checked"],
                 gets_not_admitted=judged["gets_not_admitted"],
                 gets_corrupt=judged["gets_corrupt"],
                 ops_failed=len(gen.failed))
        await client.stop()
        return 0
    finally:
        await cluster.stop()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", default="",
                    help="comma list; else first-rate x factor^i")
    ap.add_argument("--first-rate", type=float, default=20.0)
    ap.add_argument("--factor", type=float, default=1.25)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--windows", default="3",
                    help="windows a rate, played without a pause; a comma "
                         "list gives each rate its own")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    run.arm_deadline(3500.0)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["CEPH_TPU_FORCE_BATCH"] = "1"
        os.environ["CEPH_TPU_DEVICE_SLAB"] = "1"
    cell = manifest.resolve(manifest.load(), args.workload, args.rehearse)
    from ceph_tpu.utils.jaxdev import enable_compile_cache

    if not args.rehearse:
        enable_compile_cache()
    run.phase_device(cell.chips, args.rehearse)
    return asyncio.run(sweep(cell, args))


if __name__ == "__main__":
    sys.exit(main())
