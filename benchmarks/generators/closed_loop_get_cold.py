"""`rados bench <s> rand` over a `write --no-cleanup` that is several times
the resident store: set-up writes the objects, the window gets names drawn
uniformly from them, closed loop, and compares every reply in full as it
arrives.

Most gets miss: the primary reads k shards off the OSDs' stores, answers,
and promotes the object into device pages while the tier agents shed
parity and evict to make room.  A run in which that did not happen (no
miss, no promotion or no eviction inside the window) is the cell whose
objects fit, and not correct here.  A promotion also seeds the store's
host memo, so a wrong install would never be read back by a get: after
the window the rows of objects promoted inside it are read off the device
pages and held to the plain reference's shards."""

from __future__ import annotations

import struct
import time

import numpy as np

from benchmarks import verify
from benchmarks.loop import closed_loop
from benchmarks.payload import Payloads

OP = "get"
STAMP = struct.Struct("<Q")  # payload.Payloads stamps the index there


class Generator:
    def __init__(self, env) -> None:
        self.env = env
        t = self.t = env.cell.traffic
        self.payloads = Payloads(env.seed, t["object_bytes"],
                                 t["payload_pool"], t["name_prefix"])
        # the pool's buffers, joined once; object i is buffer i % pool
        # under its own stamp, so a reply is held to a stamp and a tail
        # and no copy of the whole data set is kept
        self.tails = [memoryview(self.payloads.data(j))[STAMP.size:]
                      for j in range(t["payload_pool"])]
        self.draws = np.random.default_rng(env.seed)
        self.not_identical = 0
        self.records: list = []
        self.resident_before: set = set()

    def _identical(self, got, i: int) -> bool:
        if not isinstance(got, (bytes, bytearray)):
            got = bytes(got)
        return (len(got) == self.t["object_bytes"]
                and got.startswith(STAMP.pack(i))
                and got.endswith(self.tails[i % len(self.tails)]))

    async def _put(self, i: int):
        await self.env.put(self.payloads.name(i), self.payloads.data(i))
        return True, self.t["object_bytes"]

    async def _get(self, i: int):
        got = await self.env.client.get(self.env.pool, self.payloads.name(i))
        if not self._identical(got, i):
            self.not_identical += 1
            return False, 0
        return True, len(got)

    def _draw(self) -> int:
        return int(self.draws.integers(self.t["objects"]))

    async def _coalesced_encodes(self) -> dict:
        """Both encode lanes once for each group size the run can meet.
        Encodes that reach the queue while a round is in flight run as ONE
        program over their columns together, rounded up to a power of
        two, and fan out as slices of its product; whether a group forms
        is a matter of timing (about one encode in a hundred), and a
        width first met compiles for up to a minute on the queue's one
        thread: inside the window if it is a promotion's, and half a
        set-up's length if it is a put's (my chip runs, PR 33).  No put
        or get of the mix can make a group beforehand: the throttle
        refuses an object of twice the size.  So groups of `group_sizes`
        objects' rows go to the queue as one submission each (its group
        seam, `submit_group`) on the lanes `lane_for` names for the
        pool's codec: the resident lane, which promotions and installing
        puts ride, and the plain one of the puts the throttle keeps out
        of the store.  The results are dropped: the compiles are what is
        wanted."""
        import asyncio

        from ceph_tpu.ec.registry import registry
        from ceph_tpu.rados.ecutil import lane_for

        profile = dict(self.env.profile)
        codec = registry.factory(profile["plugin"], "", profile)
        k, m = int(profile["k"]), int(profile["m"])
        unit = int(self.env.cell.config["stripe_unit"])
        cols = -(-self.t["object_bytes"] // (k * unit)) * unit
        rows = np.random.default_rng(self.env.seed).integers(
            0, 256, (k, cols), dtype=np.uint8)
        took = {}
        for resident in (True, False):
            kind, dtype = lane_for(codec, resident=resident, cols=cols)
            item = (np.asarray(codec.bit_generator()).astype(dtype), rows,
                    getattr(codec, "w", 8), m, kind)
            for size in self.t["warmup"]["group_sizes"]:
                t0 = time.perf_counter()
                await asyncio.gather(*(
                    asyncio.wrap_future(fut)
                    for fut in self.env.queue.submit_group([item] * size)))
                took[f"{kind}.{size}"] = time.perf_counter() - t0
        return took

    async def setup(self) -> None:
        """Run both encode lanes at each group size, write the objects,
        then get `warm_gets` names from the seed's stream: misses,
        promotions, evictions and device gathers, which compile what the
        window runs and leave the store at its evict line."""
        t, n = self.t, self.t["objects"]
        groups_before = self.env.group_sizes()
        grouped = await self._coalesced_encodes()
        groups = [b - a for a, b in zip(groups_before, self.env.group_sizes())]
        t0 = time.perf_counter()
        puts = await closed_loop(t["in_flight"], self._put, lambda i: i < n)
        t1 = time.perf_counter()
        gets = await closed_loop(t["in_flight"],
                                 lambda _i: self._get(self._draw()),
                                 lambda i: i < t["warm_gets"])
        self.env.emit("warmup", op=OP, group_seconds=grouped,
                      group_size_log2=groups, puts=len(puts),
                      put_seconds=t1 - t0, gets=len(gets),
                      get_seconds=time.perf_counter() - t1,
                      residents=len(self.env.store.entries_snapshot()),
                      resident_store=self.env.resident_room())
        # a warm-up get that compares unequal is counted (not_identical) and
        # comes out in verify(); a put that failed leaves nothing to read
        if any(not r[3] for r in puts):
            raise RuntimeError("a warm-up put failed")

    async def window(self, seconds: float):
        self.resident_before = {
            key for key, _nbytes in self.env.store.entries_snapshot()}
        t0 = time.perf_counter()
        t1 = t0 + seconds
        self.records = await closed_loop(
            self.t["in_flight"], lambda _i: self._get(self._draw()),
            lambda _i: time.perf_counter() < t1)
        return self.records, t0, t1

    def _index_of(self, oid: str) -> int:
        return int(oid.rsplit("_", 1)[1])

    def _promoted_rows(self) -> list:
        """Device pages against the reference, for a seeded sample of the
        objects that are resident now and were not when the window began
        (nothing but a promotion installs in a window of gets).  Each
        shard's bit-rows are gathered off the page table and packed on
        the device (never the memo): the k data rows of every one, the m
        parity rows where the agents have not shed them.  No await
        between the look at the store and the reads, so no agent runs in
        between."""
        from ceph_tpu.rados.ecutil import planar_shard_bytes

        env, store = self.env, self.env.store
        want = self.t["verify"]["promoted_objects"]
        k = int(env.profile["k"])
        fresh = sorted(
            (key for key, _nbytes in store.entries_snapshot()
             if key not in self.resident_before and key[1] == env.pool
             and store.resident_meta(key)),
            key=lambda key: key[2])
        rng = np.random.default_rng(env.seed)
        picked = [fresh[j] for j in rng.choice(
            len(fresh), size=min(want, len(fresh)), replace=False)]
        data_missing = differing = parity_compared = 0
        for key in picked:
            version = store.resident_meta(key)[0]
            ref = env.reference(self.payloads.data(self._index_of(key[2])))
            for shard, expect in enumerate(ref):
                got = planar_shard_bytes(store, key, version, shard)
                if got is None:
                    data_missing += shard < k  # parity may have been shed
                    continue
                parity_compared += shard >= k
                differing += got != expect
        return [
            verify.at_least("promoted_residents_compared", len(picked), want),
            verify.at_most("promoted_data_rows_missing", data_missing),
            verify.at_most("promoted_rows_differing_from_reference",
                           differing),
            verify.at_least("promoted_parity_rows_compared", parity_compared,
                            0)]

    async def verify(self) -> list:
        """Every get of the window was compared as it arrived.  The device
        pages of promoted residents and the stored shards of a seeded
        sample are held to the plain reference."""
        env, n = self.env, self.t["verify"]["shard_objects"]
        promoted = self._promoted_rows()  # first: before anything awaits
        picked = np.random.default_rng(env.seed).choice(
            self.t["objects"], size=n, replace=False)
        index_of = {self.payloads.name(int(i)): int(i) for i in picked}
        held = verify.stored_shards(env.live_osds(), env.pool, index_of)
        return [
            verify.at_least("gets_compared",
                            sum(1 for r in self.records if r[3])
                            + self.not_identical),
            verify.at_most("gets_not_identical", self.not_identical),
            *promoted,
            verify.at_least("shard_objects_compared", len(held), n),
            *verify.shards(held,
                           lambda oid: self.payloads.data(index_of[oid]),
                           env.reference),
            verify.at_most("acked_without_all_shards",
                           env.acked_without_all_shards)]

    def counter_checks(self, moved: dict) -> list:
        """The mechanism ran: gets missed the resident store, objects were
        promoted and residents evicted inside the window, some gets found
        a resident, the store's device arm is on, and the CPU served
        nothing."""
        store = self.env.store_set
        return [*verify.fallbacks(moved),
                *(verify.at_least(key, moved.get(key, 0))
                  for key in (f"{store}.hit", f"{store}.miss",
                              "tier.promote", f"{store}.evict")),
                verify.at_least("store_device_arm",
                                int(self.env.store_device_arm()))]
