"""fio's `ioengine=rbd rw=randwrite bs=4k iodepth=32` on an image whose data
pool is the cell's EC pool: 4 KiB writes at random aligned offsets of a
block image, closed loop, through `services/rbd.py`.

Set-up makes what `rbd create --data-pool` needs (a replicated pool for
the header, the image, the EC pool of the run as its data pool), hands the
queue every encode width a window can meet, fills the image once in 4 MiB
pieces (the precondition: every data object exists, so a 4 KiB write is a
write at an offset, an RMW on the EC pool), and then writes 4 KiB blocks
from the run's own stream, unrecorded, until nothing compiles and every
RMW arm the stream can reach was taken.  The window goes on with the same
stream.  A record is one `Image.write` of 4096 bytes.

The stream is a permutation of the image's blocks drawn from `--seed`: no
block twice in a run.  A block's bytes are a function of (seed, block,
generation) and nothing is kept: `benchmarks/references/block_image.py` is
the model every comparison holds the image to.

    at each ack     the data shard holds the block at its chunk offset;
                    every few acks, where no other write to the object is
                    in flight, all k+m shards hold the reference's chunk
                    of the model's stripe (ack_after_commit, for a splice)
    after the run   what the tier holds of objects spliced while resident;
                    stored shards of 8 objects; blocks and their stripe
                    neighbours through Image.read; 8 objects whole
"""

from __future__ import annotations

import asyncio
import resource
import time

import numpy as np

from benchmarks import counters, verify
from benchmarks.loop import closed_loop
from benchmarks.references.block_image import BlockImage

OP = "put"  # the op family of the end-to-end metrics (run.py `have`)
FILL, WRITTEN = 0, 1  # a block's generation after the fill, after its write
ARMS = ("osd.rmw_base_cached", "osd.rmw_extent_hits", "osd.rmw_base_shards",
        "osd.rmw_base_full_read")


def memory() -> dict:
    """This process's peak resident set and what the host has left, in
    bytes: the fill keeps every shard, and MemStore the previous version
    of every spliced one."""
    out = {"peak_rss_bytes":
           resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024}
    for path, keys in (("/proc/self/status", ("VmRSS", "RssAnon", "RssFile",
                                              "RssShmem")),
                       ("/proc/meminfo", ("MemTotal", "MemAvailable"))):
        try:
            with open(path) as f:
                for line in f:
                    key, _, rest = line.partition(":")
                    if key in keys:
                        out[key + "_bytes"] = int(rest.split()[0]) * 1024
        except OSError:
            pass
    return out


def block_stream(seed: int, n_blocks: int) -> np.ndarray:
    """The order the run writes blocks in: a function of the seed alone,
    every block once (fio's random map)."""
    return np.random.default_rng(int(seed)).permutation(n_blocks)


class Generator:
    def __init__(self, env) -> None:
        self.env = env
        t = self.t = env.cell.traffic
        cfg = env.cell.config
        self.block = int(t["block_bytes"])
        self.model = BlockImage(env.seed, int(cfg["image"]["bytes"]),
                                self.block, int(cfg["image"]["order"]))
        self.stream = block_stream(env.seed, self.model.n_blocks)
        self.next_index = 0
        self.unit = int(cfg["stripe_unit"])
        self.k = int(env.profile["k"])
        self.stripe = self.k * self.unit
        self.per_object = self.model.object_size // self.block
        self.image = None
        self.data_io = None
        self.writing: dict = {}  # data object -> writes in flight
        self.acting: dict = {}  # data object -> (wire oid, acting set)
        self.acks = 0
        self.acked_without_all_shards = 0
        self.acked_before_data_shard = 0
        self.acks_checked_on_all_shards = 0
        self.acked_with_a_shard_behind = 0
        self.records: list = []
        self.resident_before: set = set()

    # -- the cluster under the image -----------------------------------------

    def _placed(self, obj: int):
        """(wire oid, acting set) of a data object in the EC pool."""
        got = self.acting.get(obj)
        if got is None:
            osdmap = self.env.client.osdmap
            pool = osdmap.pools[self.env.pool]
            oid = self.data_io._full(self.image._data_oid(obj))
            got = self.acting[obj] = (oid, osdmap.pg_to_acting(
                pool, osdmap.object_to_pg(pool, oid)))
        return got

    def _stored(self, obj: int, shard: int):
        """Shard `shard` of a data object as its acting OSD's store holds
        it: (buffer, meta), or None."""
        oid, acting = self._placed(obj)
        osd = self.env.cluster.osds.get(acting[shard])
        got = osd.store.read((self.env.pool, oid, shard)) \
            if osd is not None else None
        if got is None:
            return None
        return memoryview(getattr(got[0], "view", got[0])), got[1]

    def _all_held(self, obj: int) -> bool:
        return all(self._stored(obj, s) is not None
                   for s in range(self.env.n_shards))

    def _cached_whole(self) -> int:
        """Data objects whose primary holds them whole in its cache (a
        look, not a get: the cache's order stays as it is)."""
        return sum(1 for osd in self.env.cluster.osds.values()
                   for key, ent in osd._extent_cache._entries.items()
                   if key[0] == self.env.pool and ent.full)

    def _arm_counts(self) -> dict:
        return {arm: sum(osd.perf.get(arm.split(".", 1)[1])
                         for osd in self.env.cluster.osds.values())
                for arm in ARMS}

    # -- one op ----------------------------------------------------------------

    def _fill_payload(self, obj: int) -> bytes:
        first = obj * self.per_object
        return b"".join(self.model.payloads.block(b, FILL) for b in
                        range(first, min(first + self.per_object,
                                         self.model.n_blocks)))

    async def _fill(self, obj: int):
        data = self._fill_payload(obj)
        await self.image.write(obj * self.model.object_size, data)
        self.model.stamp_run(obj * self.per_object, len(data) // self.block,
                             FILL)
        if not self._all_held(obj):
            self.acked_without_all_shards += 1
        return True, len(data)

    def _at_ack(self, obj: int, block: int, payload: bytes) -> None:
        """The guarantee looked at the moment the ack arrives, before this
        task yields.  The block sits on data shard b % k of its object at
        chunk offset (b // k) * unit: that range of the stored shard is
        the payload.  Every few acks, where no other write to the object
        is in flight (so that the model's stripe is what every shard must
        hold), the same range of all k+m shards is the reference's chunk
        of the model's stripe."""
        b = block % self.per_object
        at = (b // self.k) * self.unit
        got = self._stored(obj, b % self.k)
        if got is None or got[0][at:at + self.unit] != payload:
            self.acked_before_data_shard += 1
        self.acks += 1
        every = int(self.t["verify"]["all_shards_at_ack_every"])
        if self.acks % every or self.writing[obj]:
            return
        first = block - b % self.k
        want = self.env.reference(
            self.model.read(first * self.block, self.stripe))
        self.acks_checked_on_all_shards += 1
        for shard, chunk in enumerate(want):
            got = self._stored(obj, shard)
            if got is None or got[0][at:at + self.unit] != chunk:
                self.acked_with_a_shard_behind += 1

    async def _write(self, i: int):
        block = int(self.stream[i])
        obj = block // self.per_object
        payload = self.model.payloads.block(block, WRITTEN)
        self.writing[obj] = self.writing.get(obj, 0) + 1
        try:
            await self.image.write(block * self.block, payload)
        finally:
            self.writing[obj] -= 1
        self.model.stamp(block, WRITTEN)
        self._at_ack(obj, block, payload)
        return True, self.block

    # -- set-up ------------------------------------------------------------------

    async def _make_image(self) -> None:
        """`rbd create --size <bytes> --data-pool bench <meta>/<image>`,
        on the run's own client: the window's `objecter.op` are the
        image's writes."""
        from ceph_tpu.rados.librados import Rados
        from ceph_tpu.services.rbd import RBD

        env, img = self.env, self.env.cell.config["image"]
        meta = img["meta_pool"]
        await env.client.create_pool(
            self.t["meta_pool_name"], pool_type=meta["type"],
            pg_num=int(meta["pg_num"]), profile={"size": str(meta["size"])})
        rados = Rados.from_client(env.client)
        meta_io = await rados.open_ioctx(self.t["meta_pool_name"])
        pool_name = env.client.osdmap.pools[env.pool].name
        self.data_io = await rados.open_ioctx(pool_name)
        # the image's id names its data objects and so places them: one
        # id for every run, as one image is written by every job
        self.image = await RBD(meta_io).create(
            self.t["image_name"], int(img["bytes"]), order=int(img["order"]),
            data_pool=self.data_io, image_id=self.t["image_id"])
        # the image's `rbd` counter set beside the client's own, in every
        # snapshot the harness takes
        inner = env.snapshot
        env.snapshot = lambda: {
            **inner(), **counters.snapshot([], [self.image.perf])}

    async def _every_width(self) -> dict:
        """Each encode width a window can meet, handed to the queue as one
        submission (its group seam, `submit_group`; results dropped), as
        the mixed and the cold cell's set-ups do: what coalesces is a
        matter of timing, and a width first met compiles for up to a
        minute on the queue's one thread.  A 4 KiB write encodes one
        32 KiB stripe on the plain lane, and 1 to `in_flight` of them in
        a round stage at a power of two; the fill's 4 MiB encodes go in
        groups of 1, 2 and 4 on the resident and the plain lane."""
        from ceph_tpu.ec.registry import registry
        from ceph_tpu.rados.ecutil import lane_for

        profile = dict(self.env.profile)
        codec = registry.factory(profile["plugin"], "", profile)
        m, w = int(profile["m"]), self.t["warmup"]
        cols = self.model.object_size // self.k
        rows = np.random.default_rng(self.env.seed).integers(
            0, 256, (self.k, cols), dtype=np.uint8)
        took = {}

        async def group(kind, dtype, width, size):
            mbits = np.asarray(codec.bit_generator()).astype(dtype)
            item = (mbits, rows[:, :width], getattr(codec, "w", 8), m, kind)
            t0 = time.perf_counter()
            await asyncio.gather(*(
                asyncio.wrap_future(fut)
                for fut in self.env.queue.submit_group([item] * size)))
            took[f"{kind}.{width // self.unit}x{size}"] = \
                time.perf_counter() - t0

        kind, dtype = lane_for(codec, resident=False, cols=self.unit)
        for size in w["one_stripe_rounds"]:
            await group(kind, dtype, self.unit, int(size))
        for resident in (True, False):
            kind, dtype = lane_for(codec, resident=resident, cols=cols)
            for size in w["object_groups"]:
                await group(kind, dtype, cols, int(size))
        return took

    async def _warm_writes(self) -> dict:
        """4 KiB writes from the stream, unrecorded, until `still_writes`
        in a row compiled nothing and every arm the stream can reach was
        taken: the cached base while some object is cached whole, the
        shard read while some object is not."""
        w, meter = self.t["warmup"], self.env.meter
        cached, before = self._cached_whole(), self._arm_counts()
        reach = [arm for arm, can in (
            ("osd.rmw_base_cached", cached > 0),
            ("osd.rmw_base_shards", cached < self.model.n_objects)) if can]
        state = {"done": 0, "still": 0, "compiles": meter.count,
                 "armed": False}

        async def op(i):
            out = await self._write(i)
            state["done"] += 1
            if meter.count != state["compiles"]:
                state["compiles"], state["still"] = meter.count, 0
            else:
                state["still"] += 1
            if not state["armed"] and state["done"] % 16 == 0:
                now = self._arm_counts()
                state["armed"] = all(now[a] > before[a] for a in reach)
            return out

        def go_on(i):
            if state["done"] >= w["max_writes"]:
                return False
            return (state["done"] < w["min_writes"]
                    or state["still"] < w["still_writes"]
                    or not state["armed"])

        t0 = time.perf_counter()
        records = await closed_loop(self.t["in_flight"], op, go_on,
                                   self.next_index)
        self.next_index += len(records)
        if any(not r[3] for r in records):
            raise RuntimeError("a warm-up write failed")
        now = self._arm_counts()
        return {"writes": len(records), "seconds": time.perf_counter() - t0,
                "stood_still": state["still"] >= w["still_writes"],
                "cached_whole_objects_before": cached,
                "arms_reachable": reach,
                "arms_taken": {a: now[a] - before[a] for a in ARMS}}

    async def setup(self) -> None:
        env, t = self.env, self.t
        await self._make_image()
        groups_before = env.group_sizes()
        grouped = await self._every_width()
        groups = [b - a for a, b in zip(groups_before, env.group_sizes())]
        t0 = time.perf_counter()
        fills = await closed_loop(t["precondition"]["in_flight"], self._fill,
                                  lambda i: i < self.model.n_objects)
        fill_s = time.perf_counter() - t0
        if any(not r[3] for r in fills):
            raise RuntimeError("a piece of the fill failed")
        after_fill = memory()
        warm = await self._warm_writes()
        env.emit("warmup", op=OP, group_seconds=grouped,
                 group_size_log2=groups, fill_pieces=len(fills),
                 fill_seconds=fill_s,
                 fill_MBps=self.model.image_bytes / 1e6 / fill_s,
                 warm_writes=warm, memory_after_fill=after_fill,
                 memory=memory(),
                 object_map_blocks=len(self.image._hdr["object_map"]),
                 residents=len(env.store.entries_snapshot()),
                 resident_store=env.resident_room())

    # -- the window ----------------------------------------------------------------

    async def window(self, seconds: float):
        env = self.env
        self.resident_before = {
            key[2] for key, _n in env.store.entries_snapshot()
            if key[1] == env.pool}
        cached = self._cached_whole()
        t0 = time.perf_counter()
        t1 = t0 + seconds
        self.records = await closed_loop(
            self.t["in_flight"], self._write,
            lambda _i: time.perf_counter() < t1, self.next_index)
        self.next_index += len(self.records)
        env.emit("rbd", cached_whole_objects_before=cached,
                 cached_whole_objects_after=self._cached_whole(),
                 residents_before=len(self.resident_before),
                 acks_checked_on_all_shards=self.acks_checked_on_all_shards,
                 memory=memory())
        return self.records, t0, t1

    # -- verification --------------------------------------------------------------

    def _object_shards(self, obj: int) -> list:
        return self.env.reference(self.model.object_bytes(obj))

    def _tier(self, objs: list) -> tuple:
        """What the tier holds of objects the window spliced while a
        resident of them existed.  A splice leaves the primary's pages
        and memo at the version before it; nothing of that may be
        served.  `planar_object_bytes` is what a read asks the store,
        under the version the read found: under the object's stored
        version it answers nothing, or the model's bytes (memo or
        pages); a resident AT that version holds the reference's rows.
        No await in here, so nothing installs or evicts in between."""
        from ceph_tpu.rados.ecutil import (planar_object_bytes,
                                           planar_shard_bytes)

        env, store = self.env, self.env.store
        seen = {"residents": 0, "at_an_older_version": 0,
                "at_the_stored_version": 0, "memo_entries": 0}
        served_wrong = rows_differing = 0
        for obj in objs:
            oid, _acting = self._placed(obj)
            version = self._stored(obj, 0)[1].version
            want = self.model.object_bytes(obj)
            for osd in env.cluster.osds.values():
                key = (osd.osd_id, env.pool, oid)
                seen["memo_entries"] += key in getattr(store, "_memo", ())
                meta = store.resident_meta(key)
                if not meta:
                    continue
                seen["residents"] += 1
                got = planar_object_bytes(store, key, version, self.k,
                                          self.unit, len(want))
                served_wrong += got is not None and bytes(got) != want
                if meta[0] != version:
                    seen["at_an_older_version"] += 1
                    continue
                seen["at_the_stored_version"] += 1
                for shard, expect in enumerate(self._object_shards(obj)):
                    rows = planar_shard_bytes(store, key, version, shard)
                    rows_differing += rows is not None and rows != expect
        return seen, [
            verify.at_most("tier_serves_an_older_version", served_wrong),
            verify.at_most("resident_rows_differing_from_reference",
                           rows_differing)]

    async def verify(self) -> list:
        env, v, model = self.env, self.t["verify"], self.model
        acked = [int(self.stream[r[0]]) for r in
                 sorted(self.records, key=lambda r: r[2]) if r[3]]
        touched, seen = [], set()  # data objects, newest first
        for block in reversed(acked):
            obj = block // self.per_object
            if obj not in seen:
                seen.add(obj)
                touched.append(obj)
        # first, before anything awaits: the tier as the window left it
        spliced_resident = [o for o in touched
                            if self._placed(o)[0] in self.resident_before]
        tier_objs = spliced_resident[:v["tier_objects"]]
        tier_seen, tier_checks = self._tier(tier_objs)

        # stored shards, and later the objects whole: the most recently
        # touched and a seeded draw of all the image's objects
        rng = np.random.default_rng(env.seed)
        objs = touched[:v["objects_newest"]]
        rest = [o for o in range(model.n_objects) if o not in objs]
        objs += [rest[j] for j in rng.choice(
            len(rest), size=min(v["objects_drawn"], len(rest)),
            replace=False)]
        index_of = {self._placed(o)[0]: o for o in objs}
        held = verify.stored_shards(env.live_osds(), env.pool, index_of)
        shard_checks = verify.shards(
            held, lambda oid: model.object_bytes(index_of[oid]),
            env.reference)
        # a store whose shards already differ is not read through: the
        # run is not correct whatever the reads say, and a read that
        # meets a corrupt shard takes the messenger's crc-reset path and
        # the gather's 5 s, minutes for the reads below
        if not all(c["ok"] for c in shard_checks):
            env.emit("model", reads_skipped="stored shards differ",
                     tier=tier_seen, memory=memory())
            return [verify.at_least("shard_objects_compared", len(held),
                                    len(objs)),
                    *shard_checks, *tier_checks,
                    verify.at_least("blocks_compared", 0, 1)]

        tier_reads_differing = 0
        for obj in tier_objs:
            got = await env.client.get(env.pool, self._placed(obj)[0])
            tier_reads_differing += bytes(got) != model.object_bytes(obj)

        # blocks and their stripe neighbours, through Image.read
        picked = verify.sample(acked, v["last_acked"] + v["drawn"],
                               v["last_acked"], env.seed)
        bad = {"blocks": 0, "neighbours": 0, "reads": 0}

        async def read_stripe(j):
            block = picked[j]
            first = block - block % self.k
            try:
                got = await self.image.read(first * self.block, self.stripe)
            except Exception:
                bad["reads"] += 1
                return False, 0
            want = model.read(first * self.block, self.stripe)
            lo = (block - first) * self.block
            bad["blocks"] += got[lo:lo + self.block] \
                != want[lo:lo + self.block]
            bad["neighbours"] += (got[:lo] != want[:lo]
                                  or got[lo + self.block:]
                                  != want[lo + self.block:])
            return True, len(got)

        await closed_loop(16, read_stripe, lambda j: j < len(picked))
        objects_differing = 0
        for obj in objs:
            got = await self.image.read(obj * model.object_size,
                                        model.object_size)
            objects_differing += got != model.object_bytes(obj)
        env.emit("model", blocks_compared=len(picked),
                 stripe_neighbours_compared=len(picked) * (self.k - 1),
                 objects_compared=len(objs),
                 objects_spliced_while_resident=len(spliced_resident),
                 tier=tier_seen, memory=memory())
        return [
            verify.at_least("blocks_compared", len(picked),
                            max(1, min(v["last_acked"] + v["drawn"],
                                       len(acked)))),
            verify.at_most("block_reads_failed", bad["reads"]),
            verify.at_most("blocks_not_the_latest_acked", bad["blocks"]),
            verify.at_most("stripe_neighbours_changed", bad["neighbours"]),
            verify.at_least("objects_compared", len(objs),
                            min(v["objects_newest"] + v["objects_drawn"],
                                model.n_objects)),
            verify.at_most("objects_not_identical", objects_differing),
            verify.at_least("shard_objects_compared", len(held), len(objs)),
            *shard_checks,
            verify.at_least("tier_objects_compared", len(tier_objs)),
            *tier_checks,
            verify.at_most("tier_reads_differing", tier_reads_differing),
            verify.at_least("acks_checked_on_all_shards",
                            self.acks_checked_on_all_shards),
            verify.at_most("acked_with_a_shard_behind",
                           self.acked_with_a_shard_behind),
            verify.at_most("acked_before_data_shard_committed",
                           self.acked_before_data_shard),
            verify.at_most("acked_without_all_shards",
                           self.acked_without_all_shards)]

    def counter_checks(self, moved: dict) -> list:
        """The mechanism ran, on the device, in a healthy steady window:
        every write was a stripe RMW whose base came from one of the
        four arms, none was refused or rewrote its object, nothing
        compiled, nothing was dispatched outside the queue or fell back
        to the CPU."""
        acked = sum(1 for r in self.records if r[3])
        arms = sum(moved.get(arm, 0) for arm in ARMS)
        offset_writes = moved.get("objecter.op_w", 0)
        return [*verify.fallbacks(moved),
                verify.at_least("osd.rmw_partial",
                                moved.get("osd.rmw_partial", 0),
                                max(1, acked)),
                verify.at_least("rbd.wr", moved.get("rbd.wr", 0),
                                max(1, acked)),
                verify.check("rmw_arms_minus_offset_writes",
                             arms - offset_writes, 0,
                             arms == offset_writes),
                verify.at_most("osd.rmw_full_rewrite",
                               moved.get("osd.rmw_full_rewrite", 0)),
                verify.at_most("osd.splice_refused",
                               moved.get("osd.splice_refused", 0)),
                verify.at_least("ec_tpu.dispatch",
                                moved.get("ec_tpu.dispatch", 0)),
                verify.at_most("compile_meter.compiles",
                               moved.get("compile_meter.compiles", 0)),
                verify.at_most("dispatches_outside_the_queue",
                               moved.get("ec_plugin.apply", 0)
                               + moved.get("ec_plugin.apply_rows", 0)),
                verify.at_least("store_device_arm",
                                int(self.env.store_device_arm()))]
