"""`rados bench <s> rand` after `write --no-cleanup`: set-up writes the
objects, the window gets names drawn uniformly from them, closed loop, and
compares every reply in full with its payload as it arrives.

The objects have to fit the resident store with room to spare: set-up
prints how many pages lie between them and the line above which the tier
agents shed and evict, and a miss inside the window is not correct."""

from __future__ import annotations

import time

import numpy as np

from benchmarks import verify
from benchmarks.loop import closed_loop
from benchmarks.payload import Payloads

OP = "get"


class Generator:
    def __init__(self, env) -> None:
        self.env = env
        t = self.t = env.cell.traffic
        self.payloads = Payloads(env.seed, t["object_bytes"],
                                 t["payload_pool"], t["name_prefix"])
        # kept whole: the window compares every reply with one memcmp
        self.data = [self.payloads.data(i) for i in range(t["objects"])]
        self.draws = np.random.default_rng(env.seed)
        self.not_identical = 0
        self.records: list = []

    async def _put(self, i: int):
        await self.env.put(self.payloads.name(i), self.data[i])
        return True, len(self.data[i])

    async def _get(self, i: int):
        got = await self.env.client.get(self.env.pool, self.payloads.name(i))
        if got != self.data[i]:
            self.not_identical += 1
            return False, 0
        return True, len(got)

    async def setup(self) -> None:
        """Write the objects, then get the first `warm_gets` of them, which
        compiles what a get runs on the device.  The others are left as the
        write left them: their first get, in the window, gathers them on the
        device and copies them to the host (the same objects - warm_gets
        device reads in every run); every later get of an object is served
        from the store's host memo, as the program serves any resident."""
        t, n = self.t, self.t["objects"]
        t0 = time.perf_counter()
        puts = await closed_loop(t["in_flight"], self._put, lambda i: i < n)
        t1 = time.perf_counter()
        gets = await closed_loop(t["in_flight"], self._get,
                                 lambda i: i < t["warm_gets"])
        self.env.emit("warmup", op=OP, puts=len(puts), put_seconds=t1 - t0,
                      gets=len(gets), get_seconds=time.perf_counter() - t1,
                      first_gets_left_for_the_window=n - len(gets),
                      resident_store=self.env.resident_room())
        # a warm-up get that compares unequal is counted (not_identical) and
        # comes out in verify(); a put that failed leaves nothing to read
        if any(not r[3] for r in puts):
            raise RuntimeError("a warm-up put failed")

    async def window(self, seconds: float):
        n = self.t["objects"]
        t0 = time.perf_counter()
        t1 = t0 + seconds
        self.records = await closed_loop(
            self.t["in_flight"],
            lambda _i: self._get(int(self.draws.integers(n))),
            lambda _i: time.perf_counter() < t1)
        return self.records, t0, t1

    async def verify(self) -> list:
        """Every get of the window was compared as it arrived.  A read
        served from residents never touches the shards, so the stored
        shards of a seeded sample are held to the plain reference too."""
        env, n = self.env, self.t["verify"]["shard_objects"]
        picked = np.random.default_rng(env.seed).choice(
            self.t["objects"], size=n, replace=False)
        index_of = {self.payloads.name(int(i)): int(i) for i in picked}
        held = verify.stored_shards(env.live_osds(), env.pool, index_of)
        return [
            verify.at_least("gets_compared",
                            sum(1 for r in self.records if r[3])
                            + self.not_identical),
            verify.at_most("gets_not_identical", self.not_identical),
            verify.at_least("shard_objects_compared", len(held), n),
            *verify.shards(held, lambda oid: self.data[index_of[oid]],
                           env.reference),
            verify.at_most("acked_without_all_shards",
                           env.acked_without_all_shards)]

    def counter_checks(self, moved: dict) -> list:
        """Every get has to come from the resident store, and its device
        arm has to be on: a get decoded from shards (a miss, then a promote
        and an evict), or a store kept in host memory, is another cell."""
        store = self.env.store_set
        return [*verify.fallbacks(moved),
                verify.at_least(f"{store}.hit", moved.get(f"{store}.hit", 0)),
                verify.at_most(f"{store}.miss", moved.get(f"{store}.miss", 0)),
                verify.at_least("store_device_arm",
                                int(self.env.store_device_arm()))]
