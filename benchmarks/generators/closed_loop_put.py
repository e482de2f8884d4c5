"""`rados bench <s> write`: full-object puts to new names, closed loop."""

from __future__ import annotations

import time

import numpy as np

from benchmarks import verify
from benchmarks.loop import closed_loop
from benchmarks.payload import Payloads

OP = "put"


class Generator:
    def __init__(self, env) -> None:
        self.env = env
        t = self.t = env.cell.traffic
        self.payloads = Payloads(env.seed, t["object_bytes"],
                                 t["payload_pool"], t["name_prefix"])
        self.next_index = 0
        self.records: list = []

    async def _put(self, i: int):
        await self.env.put(self.payloads.name(i), self.payloads.data(i))
        return True, self.t["object_bytes"]

    async def _wide_puts(self) -> dict:
        """One put of each multiple of the object size.  The queue runs a
        group of g coalesced encodes as ONE program over g objects' columns,
        rounded up to a power of two, and how large a group gets is a matter
        of timing: a width first seen inside the window would compile there
        for up to a minute.  A put of a g-fold object runs the same program,
        so these compile every width that `in_flight` puts can coalesce to."""
        env, took = self.env, {}
        rng = np.random.default_rng(env.seed)
        for mult in self.t["warmup"]["wide_multiples"]:
            t0 = time.perf_counter()
            await env.put(f"{self.payloads.prefix}_{env.seed}_wide_{mult}",
                          rng.bytes(mult * self.t["object_bytes"]))
            took[str(mult)] = time.perf_counter() - t0
        return took

    async def setup(self) -> None:
        """Warm-up: the wide puts, then put until no program has compiled
        for `still_puts` puts in a row (at least min_puts, at most
        max_puts)."""
        w, meter = self.t["warmup"], self.env.meter
        wide = await self._wide_puts()
        state = {"done": 0, "still": 0, "compiles": meter.count}

        async def op(i):
            out = await self._put(i)
            state["done"] += 1
            if meter.count != state["compiles"]:
                state["compiles"], state["still"] = meter.count, 0
            else:
                state["still"] += 1
            return out

        def go_on(i):
            if i >= w["max_puts"]:
                return False
            return state["done"] < w["min_puts"] \
                or state["still"] < w["still_puts"]

        t0 = time.perf_counter()
        records = await closed_loop(self.t["in_flight"], op, go_on)
        self.next_index = len(records)
        self.env.emit("warmup", op=OP, puts=len(records),
                      failed=sum(1 for r in records if not r[3]),
                      seconds=time.perf_counter() - t0, wide_put_seconds=wide,
                      stood_still=state["still"] >= w["still_puts"])
        if any(not r[3] for r in records):
            raise RuntimeError("a warm-up put failed")

    async def window(self, seconds: float):
        t0 = time.perf_counter()
        t1 = t0 + seconds
        self.records = await closed_loop(
            self.t["in_flight"], self._put,
            lambda _i: time.perf_counter() < t1, self.next_index)
        return self.records, t0, t1

    async def verify(self) -> list:
        """After the window: a seeded sample of the objects acknowledged in
        it, the last acknowledged always among them, reads back identical;
        the stored shards of some of them equal the plain reference's."""
        env, v = self.env, self.t["verify"]
        acked = [r[0] for r in sorted(self.records, key=lambda r: r[2])
                 if r[3]]
        picked = verify.sample(acked, v["sample"], v["last_acked"], env.seed)
        index_of = {self.payloads.name(i): i for i in picked}

        def payload_of(oid):
            return self.payloads.data(index_of[oid])

        # half of the shard comparisons on the newest acks, half on the
        # seeded draw (a stale shard is likeliest where commits are fresh)
        n = v["shard_objects"]
        for_shards = [self.payloads.name(i) for i in
                      picked[:n // 2] + picked[len(picked) - (n - n // 2):]]
        held = verify.stored_shards(env.live_osds(), env.pool, for_shards)
        return [
            verify.at_least("objects_compared", len(picked),
                            max(1, min(v["sample"], len(acked)))),
            await verify.readback(env.client, env.pool, index_of, payload_of),
            verify.at_least("shard_objects_compared", len(held),
                            max(1, min(n, len(acked)))),
            *verify.shards(held, payload_of, env.reference),
            verify.at_most("acked_without_all_shards",
                           env.acked_without_all_shards)]

    def counter_checks(self, moved: dict) -> list:
        """A run that the CPU served is not a result."""
        return [*verify.fallbacks(moved),
                verify.at_least("ec_tpu.dispatch",
                                moved.get("ec_tpu.dispatch", 0))]
