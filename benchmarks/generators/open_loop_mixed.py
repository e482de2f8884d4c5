"""An application's bucket on the default EC pool: a fixed population of
named objects with heavy-tailed sizes, keys drawn by Zipf, gets, whole-object
overwrites and deletes mixed in one window, arrivals on a clock.

The schedule is a replayed trace: gaps, kinds, ranks and sizes come from
the traffic file's constant `schedule_seed`, never from `--seed`, so every
run offers the same ops on the same names at the same sizes; `--seed` makes
the payload bytes.  An op is DUE at a time of the schedule and its latency
counts from then: an op issued late is late (no coordinated omission).  An
arrival that finds `max_outstanding` ops unanswered is shed, and counts as
failed.

Every answer is held to `benchmarks/references/object_model.py`: a get's
reply is checked in full against the (rank, version) stamp it carries, and
after the window the history checker says whether some order of the ops
admits every answer; names whose last word was a delete must have left no
shard, page, memo or cached bytes; stored shards and device pages of
names written in the window are held to the plain Reed-Solomon
reference."""

from __future__ import annotations

import asyncio
import errno
import time

import numpy as np

from benchmarks import stats, verify
from benchmarks.loop import closed_loop
from benchmarks.references import object_model as om

OP = "put"  # the op family of the end-to-end metrics (run.py `have`)
KINDS = (om.GET, om.PUT, om.DELETE)


# -- the schedule: pure functions of the traffic file ------------------------


def object_sizes(seed: int, n: int, lo: int, hi: int, unit: int,
                 shape: float) -> np.ndarray:
    """Size of each rank: bounded Pareto(shape) on [lo, hi], rounded up
    to a multiple of `unit` — a function of (seed, rank) alone."""
    u = np.random.default_rng([int(seed), 1]).random(n)
    ratio = (lo / hi) ** shape
    x = lo / (1.0 - u * (1.0 - ratio)) ** (1.0 / shape)
    return np.minimum(hi, -(-x.astype(np.int64) // unit) * unit)


def zipf_cdf(n: int, theta: float) -> np.ndarray:
    """P(rank <= i) with P(rank = i) ~ 1 / (i + 1) ** theta: the
    distribution of YCSB's ZipfianGenerator over n items."""
    p = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** theta
    return np.cumsum(p / p.sum())


class Schedule:
    """The op stream: for op i, a time on a unit-rate Poisson clock
    (`unit_due[i]`), a kind and a rank; for each rank a size and a name."""

    def __init__(self, t: dict) -> None:
        seed, n = int(t["schedule_seed"]), int(t["population"])
        s = t["sizes"]
        self.sizes = object_sizes(seed, n, s["min_bytes"], s["max_bytes"],
                                  s["round_up_to"], s["pareto_shape"])
        # rank -> name by a fixed scramble, so that hot names fall on
        # different PGs whatever hashes their names
        scramble = np.random.default_rng([seed, 2]).permutation(n)
        self.names = [f"{t['name_prefix']}_{int(j)}" for j in scramble]
        count = int(t["schedule_ops"])
        self.unit_due = np.cumsum(
            np.random.default_rng([seed, 3]).exponential(1.0, count))
        mix = t["mix"]
        self.kinds = np.random.default_rng([seed, 4]).choice(
            len(KINDS), size=count,
            p=[mix[k] / 100.0 for k in KINDS]).astype(np.int8)
        self.ranks = np.searchsorted(
            zipf_cdf(n, float(t["keys"]["zipf_constant"])),
            np.random.default_rng([seed, 5]).random(count)).astype(np.int64)
        np.minimum(self.ranks, n - 1, out=self.ranks)

    def segment(self, first: int, rate: float, seconds: float):
        """(indices, due seconds from the segment's start) of the ops from
        `first` on that arrive within `seconds` at `rate` ops a second."""
        base = self.unit_due[first - 1] if first else 0.0
        due = (self.unit_due[first:] - base) / rate
        n = int(np.searchsorted(due, seconds))
        if n == len(due):
            raise ValueError("the schedule is too short: raise schedule_ops")
        return np.arange(first, first + n), due[:n]


class Lateness:
    """What an open loop reports besides its latencies."""

    def __init__(self) -> None:
        self.worst_s = 0.0
        self.peak_outstanding = 0
        self.shed = 0


# -- the generator -------------------------------------------------------------


class Generator:
    def __init__(self, env) -> None:
        self.env = env
        t = self.t = env.cell.traffic
        self.schedule = Schedule(t)
        self.payloads = om.Payloads(env.seed, t["sizes"]["max_bytes"])
        self.history = om.History()
        self.version = 0
        self.pos = 0              # next op of the schedule
        self.outstanding = 0
        self.late = Lateness()
        self.writing: dict = {}   # name -> puts and deletes in flight
        self.acked_without_all_shards = 0
        self.deletes_left_something = 0
        self.failed: list = []    # (kind, name, error) of ops that raised
        self.records: list = []   # the window's puts, for stats
        self.window_lat: dict = {k: [] for k in KINDS}
        self.window_bounds = (0.0, 0.0)

    # -- one op --------------------------------------------------------------

    def _holders(self, name: str) -> int:
        env = self.env
        return sum(
            1 for pos in range(env.n_shards)
            if any(osd.store.read((env.pool, name, pos)) is not None
                   for osd in env.cluster.osds.values()))

    def _left_behind(self, name: str) -> int:
        """Things a deleted name still has: shard positions in a live
        OSD's object store, resident pages, memo bytes, cached bytes."""
        env, left = self.env, self._holders(name)
        store = env.store
        for osd in env.cluster.osds.values():
            key = (osd.osd_id, env.pool, name)
            if store is not None:
                left += (key in store) + (key in getattr(store, "_memo", ()))
            left += osd._extent_cache.get_full((env.pool, name)) is not None
        return left

    async def _put(self, rank: int):
        name, size = self.schedule.names[rank], int(self.schedule.sizes[rank])
        self.version += 1
        op = self.history.issue(name, om.PUT, time.perf_counter(),
                                self.version)
        self.writing[name] = self.writing.get(name, 0) + 1
        try:
            await self.env.client.put(
                self.env.pool, name,
                self.payloads.data(rank, op.version, size))
            om.History.ack(op, time.perf_counter())
        finally:
            self.writing[name] -= 1
        # the guarantee looked at the moment the ack arrives, before this
        # task yields: every shard position is in some live OSD's store
        # (unless another write to the name, a delete maybe, is under way)
        if not self.writing[name] and self._holders(name) < self.env.n_shards:
            self.acked_without_all_shards += 1
        return op, size

    async def _get(self, rank: int):
        name, size = self.schedule.names[rank], int(self.schedule.sizes[rank])
        op = self.history.issue(name, om.GET, time.perf_counter())
        try:
            got = await self.env.client.get(self.env.pool, name)
            answer = self.payloads.version_of(got, rank, size)
        except Exception as e:
            if getattr(e, "code", None) != -errno.ENOENT:
                raise
            answer, got = om.ABSENT, b""
        om.History.ack(op, time.perf_counter(), answer)
        return op, len(got)

    async def _delete(self, rank: int):
        name = self.schedule.names[rank]
        op = self.history.issue(name, om.DELETE, time.perf_counter())
        self.writing[name] = self.writing.get(name, 0) + 1
        try:
            await self.env.client.delete(self.env.pool, name)
            om.History.ack(op, time.perf_counter())
        finally:
            self.writing[name] -= 1
        if self.history.quiet_delete(op) \
                and self._left_behind(name):
            self.deletes_left_something += 1
        return op, 0

    async def _one(self, i: int, kind: str, rank: int, due: float,
                   recorded: bool) -> None:
        """Op `i` of the schedule, due at `due` on perf_counter's clock."""
        self.late.worst_s = max(self.late.worst_s,
                                time.perf_counter() - due)
        do = {om.PUT: self._put, om.GET: self._get,
              om.DELETE: self._delete}[kind]
        try:
            op, nbytes = await do(rank)
            ok, done = True, op.t_ack
        except asyncio.CancelledError:
            raise
        except Exception as e:
            self.failed.append((kind, self.schedule.names[rank], repr(e)))
            ok, done, nbytes = False, time.perf_counter(), 0
        finally:
            self.outstanding -= 1
        if recorded:
            if ok:
                self.window_lat[kind].append((done - due, done))
            if kind == OP:
                self.records.append((i, due, done, ok, nbytes))

    async def play(self, seconds: float, rate: float, recorded: bool):
        """Offer the schedule's next `seconds` at `rate` ops a second and
        wait for every op offered to end.  Returns (t0, t1, offered)."""
        sched, cap = self.schedule, int(self.t["max_outstanding"])
        picked, due = sched.segment(self.pos, rate, seconds)
        self.pos += len(picked)
        t0 = time.perf_counter()
        tasks = []
        for n, (i, at) in enumerate(zip(picked.tolist(), due.tolist())):
            wait = t0 + at - time.perf_counter()
            if wait > 0:
                await asyncio.sleep(wait)
            elif n % 32 == 31:
                await asyncio.sleep(0)  # behind: let the cluster run too
            kind = KINDS[sched.kinds[i]]
            if self.outstanding >= cap:
                self.late.shed += 1
                if recorded and kind == OP:
                    now = time.perf_counter()
                    self.records.append((i, t0 + at, now, False, 0))
                continue
            # outstanding from here, not from when its task first runs
            self.outstanding += 1
            self.late.peak_outstanding = max(self.late.peak_outstanding,
                                             self.outstanding)
            tasks.append(asyncio.ensure_future(self._one(
                i, kind, int(sched.ranks[i]), t0 + at, recorded)))
        rest = t0 + seconds - time.perf_counter()
        if rest > 0:
            await asyncio.sleep(rest)
        if tasks:
            await asyncio.gather(*tasks)
        return t0, t0 + seconds, len(picked)

    # -- set-up --------------------------------------------------------------

    async def _every_width(self) -> dict:
        """Both RS lanes once for every staged width a round can have,
        with requests of every pow2 width inside.  Encodes that reach the
        queue together run as ONE program over their columns, staged at a
        power of two, and a request's plane rows are cut out of the
        product by a program per (product width, request bucket).  What
        coalesces is a matter of timing, so no put or get can be counted
        on to meet these programs before the window does: the groups go
        to the queue as one submission each (its group seam,
        `submit_group`; results dropped), as the cold cell's set-up hands
        it groups of 2 and 4."""
        from ceph_tpu.ec.registry import registry
        from ceph_tpu.rados.ecutil import lane_for

        profile = dict(self.env.profile)
        codec = registry.factory(profile["plugin"], "", profile)
        k, m = int(profile["k"]), int(profile["m"])
        unit = int(self.env.cell.config["stripe_unit"])
        w = self.t["warmup"]
        widest = int(w["widest_request_stripes"])
        rng = np.random.default_rng(self.env.seed)
        rows = rng.integers(0, 256, (k, widest * unit), dtype=np.uint8)
        took = {}
        for stripes in w["round_stripes"]:
            # one request of each pow2 width under half the round, then
            # the widest again and again until the round is past half
            # (its staged width is then `stripes`), smallest first: the
            # queue takes requests into a round while it is under its
            # byte budget, so the last one may carry it past 16 MiB
            sizes, b = [], 1
            while b <= min(widest, stripes // 2):
                sizes.append(b)
                b *= 2
            sizes = sizes or [1]
            while sum(sizes) <= stripes // 2:
                sizes.append(min(sizes[-1], stripes - sum(sizes)))
            for resident in (True, False):
                kind, dtype = lane_for(codec, resident=resident, cols=unit)
                mbits = np.asarray(codec.bit_generator()).astype(dtype)
                items = [(mbits, rows[:, :n * unit], getattr(codec, "w", 8),
                          m, kind) for n in sizes]
                t0 = time.perf_counter()
                await asyncio.gather(*(
                    asyncio.wrap_future(fut)
                    for fut in self.env.queue.submit_group(items)))
                took[f"{kind}.{stripes}"] = time.perf_counter() - t0
        return took

    async def setup(self) -> None:
        """Every staged width through both lanes; the whole population
        put and then got once (closed loop); the schedule's first
        `warm_seconds` unrecorded, and on in steps of `still_seconds`
        until one has compiled nothing."""
        t, w, env = self.t, self.t["warmup"], self.env
        n = int(t["population"])
        # what set-up itself pads, for its line: the first read of a
        # counter this cell's metrics need.  A program without it (one
        # that keys its install, row-cut and fan-out programs by an
        # object's width cannot hold a steady window of 128 widths; the
        # deployment is not one it runs) fails here, at once, and not
        # after minutes of compiles
        padded_before = env.queue.perf.dump()["pad_bytes"]
        groups_before = env.group_sizes()
        grouped = await self._every_width()
        groups = [b - a for a, b in zip(groups_before, env.group_sizes())]
        t0 = time.perf_counter()

        async def put(rank):
            _op, size = await self._put(rank)
            return True, size

        async def get(rank):
            _op, size = await self._get(rank)  # the model judges the answer
            return True, size

        puts = await closed_loop(w["in_flight"], put, lambda i: i < n)
        t1 = time.perf_counter()
        gets = await closed_loop(w["in_flight"], get, lambda i: i < n)
        t2 = time.perf_counter()
        if any(not r[3] for r in puts + gets):
            raise RuntimeError("a set-up put or get failed")
        rate, meter = float(t["rate_ops_per_s"]), env.meter
        played = 0.0
        await self.play(float(w["warm_seconds"]) - float(w["still_seconds"]),
                        rate, recorded=False)
        while True:
            compiled = meter.count
            await self.play(float(w["still_seconds"]), rate, recorded=False)
            played += float(w["still_seconds"])
            if meter.count == compiled or played >= w["max_still_seconds"]:
                break
        env.emit("warmup", op=OP, group_seconds=grouped,
                 group_size_log2=groups, puts=len(puts),
                 put_seconds=t1 - t0, gets=len(gets), get_seconds=t2 - t1,
                 warm_schedule_seconds=float(w["warm_seconds"])
                 - float(w["still_seconds"]) + played,
                 stood_still=meter.count == compiled,
                 pad_bytes=env.queue.perf.dump()["pad_bytes"]
                 - padded_before,
                 user_bytes=int(self.schedule.sizes.sum()),
                 residents=len(env.store.entries_snapshot()),
                 resident_store=env.resident_room())

    # -- the window ------------------------------------------------------------

    async def window(self, seconds: float):
        rate = float(self.t["rate_ops_per_s"])
        self.late = Lateness()
        t0, t1, offered = await self.play(seconds, rate, recorded=True)
        self.window_bounds = (t0, t1)
        by_kind = {}
        for kind in KINDS:
            lat_ms = [lat * 1e3 for lat, _ in self.window_lat[kind]]
            by_kind[kind] = {
                "completed": len(lat_ms),
                "ops_per_s": sum(1 for _, done in self.window_lat[kind]
                                 if done <= t1) / seconds,
                "p50_ms": stats.percentile(lat_ms, 50),
                "p95_ms": stats.percentile(lat_ms, 95)}
        self.env.emit("open_loop", rate_ops_per_s=rate, offered=offered,
                      from_due_time=by_kind, shed=self.late.shed,
                      worst_lateness_ms=self.late.worst_s * 1e3,
                      peak_outstanding=self.late.peak_outstanding,
                      failed=self.failed[:4])
        return self.records, t0, t1

    # -- verification ----------------------------------------------------------

    def _payload_of(self, name: str, version: int) -> bytes:
        rank = self._ranks[name]
        return self.payloads.data(rank, version,
                                  int(self.schedule.sizes[rank]))

    def _device_pages(self, held: dict) -> list:
        """The device pages of residents against the reference: each
        shard's bit-rows gathered off the page table and packed on the
        device (never the memo).  No await in here, so nothing installs
        or evicts in between."""
        from ceph_tpu.rados.ecutil import planar_shard_bytes

        env, store = self.env, self.env.store
        compared = differing = 0
        for name, version in held.items():
            ref = env.reference(self._payload_of(name, version))
            for osd in env.cluster.osds.values():
                key = (osd.osd_id, env.pool, name)
                meta = store.resident_meta(key)
                if not meta:
                    continue
                compared += 1
                for shard, expect in enumerate(ref):
                    got = planar_shard_bytes(store, key, meta[0], shard)
                    differing += got is not None and got != expect
        return [verify.at_least("residents_compared_on_the_device", compared),
                verify.at_most("resident_rows_differing_from_reference",
                               differing)]

    async def verify(self) -> list:
        env, v, hist = self.env, self.t["verify"], self.history
        self._ranks = {name: r for r, name in enumerate(self.schedule.names)}
        t0, t1 = self.window_bounds
        # the names the window wrote last, newest first, and a seeded
        # draw of the others: read back through the normal path (the
        # model judges these gets with all the others)
        written = [r[0] for r in sorted(self.records, key=lambda r: -r[2])
                   if r[3]]
        names, seen = [], set()
        for i in written:
            name = self.schedule.names[int(self.schedule.ranks[i])]
            if name not in seen:
                seen.add(name)
                names.append(name)
        rng = np.random.default_rng(env.seed)
        extra = [self.schedule.names[int(r)] for r in rng.choice(
            len(self.schedule.names), size=v["readback_drawn"],
            replace=False)]
        readback = names[:v["readback_newest"]] + extra
        failed_before = len(self.failed)

        async def get(j):
            await self._get(self._ranks[readback[j]])
            return True, 0

        got = await closed_loop(16, get, lambda j: j < len(readback))
        readback_failed = sum(1 for r in got if not r[3])

        judged = hist.check_gets()
        must_hold = hist.must_hold()
        for_shards = [n for n in names if n in must_hold][:v["shard_objects"]]
        held = verify.stored_shards(env.live_osds(), env.pool, for_shards)
        pages = self._device_pages({n: must_hold[n] for n in for_shards})
        gone = hist.must_be_absent()
        present = {oid for osd in env.live_osds()
                   for oid, _shard in osd.store.list_objects(env.pool)}
        left = sum(1 for n in gone if n in present or self._left_behind(n))
        in_window = {k: len(self.window_lat[k]) for k in KINDS}
        env.emit("model", **{k: judged[k] for k in judged},
                 names_that_must_be_gone=len(gone),
                 names_with_one_final_version=len(must_hold),
                 completed_in_window=in_window)
        return [
            verify.at_least("gets_checked", judged["gets_checked"]),
            verify.at_most("gets_not_admitted", judged["gets_not_admitted"]),
            verify.at_most("gets_corrupt", judged["gets_corrupt"]),
            verify.at_most("gets_gone_backwards",
                           judged["gets_gone_backwards"]),
            verify.at_most("readback_gets_failed", readback_failed),
            verify.at_most("ops_failed", failed_before),
            verify.at_most("arrivals_shed", self.late.shed),
            *(verify.at_least(f"{k}s_completed_in_window", in_window[k])
              for k in KINDS),
            verify.at_least("deleted_names_checked", len(gone)),
            verify.at_most("deleted_names_left_behind", left),
            verify.at_most("deletes_left_something_at_ack",
                           self.deletes_left_something),
            verify.at_least("shard_objects_compared", len(held),
                            min(v["shard_objects"], max(1, len(names)))),
            *verify.shards(held, lambda n: self._payload_of(n, must_hold[n]),
                           env.reference),
            *pages,
            verify.at_most("acked_without_all_shards",
                           self.acked_without_all_shards)]

    def counter_checks(self, moved: dict) -> list:
        """The mechanism ran, on the device, and a steady window built
        nothing: installs from the encode lane, some dispatch of more
        than one request, no compile, no slab program rebuilt, no
        dispatch outside the queue, no fallback."""
        store = self.env.store_set
        coalesced = (moved.get("ec_tpu.group_size.sum", 0)
                     - moved.get("ec_tpu.group_size.count", 0))
        return [*verify.fallbacks(moved),
                verify.at_least(f"{store}.device_installs",
                                moved.get(f"{store}.device_installs", 0)),
                verify.at_least("requests_beyond_one_a_dispatch", coalesced),
                verify.at_most("compile_meter.compiles",
                               moved.get("compile_meter.compiles", 0)),
                verify.at_most("slab_kernels.miss",
                               moved.get("slab_kernels.miss", 0)),
                verify.at_most("dispatches_outside_the_queue",
                               moved.get("ec_plugin.apply", 0)
                               + moved.get("ec_plugin.apply_rows", 0)),
                verify.at_least("store_device_arm",
                                int(self.env.store_device_arm()))]
