"""The placement memo (rados/crush.py `do_rule`): a CRUSH draw is made once
per VALUE of everything it reads and looked up afterwards.  This is
placement — a stale answer is a write fenced by, or sent to, the wrong OSD
— so the memo is held to an unmemoised draw through every way a map
changes: the mon's handlers (which mutate in place, then bump the epoch),
`apply_incremental` on a subscriber's copy, and bare attribute flips with
no epoch at all.  It must also stay bounded, stay off the wire, out of
`sig()` and out of equality, and count its misses exactly."""

import asyncio
import contextlib
import copy
import os
import pickle
import random

import pytest

from ceph_tpu.rados import crush as crush_mod
from ceph_tpu.rados.crush import (CRUSH_ITEM_NONE, CRUSH_PERF, CrushMap,
                                  CrushTester)
from ceph_tpu.rados.messenger import encode_payload
from ceph_tpu.rados.mon import Monitor
from ceph_tpu.rados.types import (MCreatePool, MCrushOp, MDeletePool,
                                  MECSubWrite, MMapReply, MMarkDown,
                                  MOSDPGTemp, MOsdMembership, MPoolSet,
                                  MSetUpmap, OSDMap, OSDMapIncremental,
                                  OsdInfo, PoolInfo)
from ceph_tpu.rados.vstart import Cluster


class _NoMemo(dict):
    """Stands in for the memo: never hits, never keeps."""

    def get(self, key, default=None):
        return default

    def __setitem__(self, key, value):
        pass


@contextlib.contextmanager
def no_memo():
    saved = crush_mod._memo
    crush_mod._memo = _NoMemo()
    try:
        yield
    finally:
        crush_mod._memo = saved


def answers(osdmap):
    """Every placement answer the map gives, for every PG of every pool."""
    out = {}
    for pool in osdmap.pools.values():
        for pg in range(pool.pg_num):
            out[(pool.pool_id, pg)] = (osdmap.pg_to_acting(pool, pg),
                                       osdmap.pg_to_raw(pool, pg),
                                       osdmap.pg_to_placed(pool, pg))
    return out


def fresh_answers(osdmap):
    """The same from a deep copy whose every draw is really made."""
    with no_memo():
        return answers(copy.deepcopy(osdmap))


def _draws():
    return CRUSH_PERF.get("draws")


def _lookups():
    return CRUSH_PERF.get("lookups")


# -- (a) every mutation path, against an unmemoised draw ---------------------


def _mon(n_osds, n_hosts):
    mon = Monitor(conf={"crush_num_hosts": n_hosts} if n_hosts else {})

    async def committed():  # no quorum to ask: the map surgery is the test
        mon._clean_pg_temps()

    mon._commit_state = committed
    for i in range(n_osds):
        mon.osdmap.osds[i] = OsdInfo(osd_id=i, addr=("127.0.0.1", 6800 + i))
        mon._crush_add_osd(i)
    mon.osdmap.epoch = 1
    return mon


class _Driver:
    """Random walks over the ways a map changes."""

    def __init__(self, seed, n_hosts):
        self.rng = random.Random(seed)
        self.mon = _mon(9, n_hosts)
        self.n_pools = 0
        self.n_buckets = 0
        self.write(MCreatePool(name="ec0", pool_type="ec", pg_num=8,
                               profile={"plugin": "jerasure",
                                        "technique": "reed_sol_van",
                                        "k": "3", "m": "2"}))
        self.write(MCreatePool(name="rep0", pool_type="replicated",
                               pg_num=4, profile={"size": "3"}))
        assert len(self.mon.osdmap.pools) == 2
        # a subscriber's copy, kept current by incrementals alone
        self.follower = pickle.loads(pickle.dumps(self.mon.osdmap))

    @property
    def m(self) -> OSDMap:
        return self.mon.osdmap

    def write(self, msg):
        return asyncio.run(self.mon._process_write_inner(msg))

    def osd(self):
        return self.rng.choice(sorted(self.m.osds))

    def pool_pg(self):
        pool = self.rng.choice(list(self.m.pools.values()))
        return pool, self.rng.randrange(pool.pg_num)

    def some_acting(self, pool):
        ids = sorted(self.m.osds)
        self.rng.shuffle(ids)
        acting = (ids + [CRUSH_ITEM_NONE] * pool.size)[:pool.size]
        return acting

    # the mon's handlers: mutate in place, then bump the epoch
    def op_membership(self):
        op = self.rng.choice(["out", "in", "reweight", "crush-reweight"])
        self.write(MOsdMembership(op=op, osd_id=self.osd(),
                                  weight=self.rng.choice(
                                      [0.0, 0.25, 0.5, 1.0, 2.0])))

    def op_down_up(self):
        osd = self.osd()
        if self.m.osds[osd].up:
            self.write(MMarkDown(osd_id=osd))
        else:  # what a boot does to the record
            self.m.osds[osd].up = True
            self.m.epoch += 1

    def op_purge(self):
        if len(self.m.osds) <= 6:
            return
        osd = self.osd()
        self.m.osds[osd].up = False
        self.write(MOsdMembership(op="purge-force", osd_id=osd))
        assert osd not in self.m.osds

    def op_new_osd(self):
        osd = max(self.m.osds) + 1
        self.m.osds[osd] = OsdInfo(osd_id=osd, addr=("127.0.0.1", 6800 + osd))
        self.mon._crush_add_osd(osd)
        self.m.epoch += 1

    def op_crush(self):
        buckets = [b.name for b in self.m.crush.buckets.values()
                   if b.id != self.m.crush.root_id]
        kind = self.rng.choice(["add-bucket", "move-osd", "move-bucket",
                                "set", "rm-osd", "rm-bucket"])
        if kind == "add-bucket":
            self.n_buckets += 1
            msg = MCrushOp(op="add-bucket", name=f"b{self.n_buckets}",
                           bucket_type=self.rng.choice(["host", "rack"]),
                           dest=self.rng.choice(buckets + [""]))
        elif kind == "move-osd":
            msg = MCrushOp(op="move", name=f"osd.{self.osd()}",
                           dest=self.rng.choice(buckets + ["default"]))
        elif kind == "move-bucket" and buckets:
            msg = MCrushOp(op="move", name=self.rng.choice(buckets),
                           dest=self.rng.choice(buckets + ["default"]))
        elif kind == "set":
            msg = MCrushOp(op="set", name=f"osd.{self.osd()}",
                           weight=self.rng.choice([0.5, 1.0, 3.0]),
                           dest=self.rng.choice(buckets + [""]))
        elif kind == "rm-osd":
            msg = MCrushOp(op="rm", name=f"osd.{self.osd()}")
        elif kind == "rm-bucket" and buckets:
            msg = MCrushOp(op="rm", name=self.rng.choice(buckets),
                           force=True)
        else:
            return
        reply = self.mon._apply_crush_op(msg)
        if reply.ok:  # as _process_write_inner does
            self.m.epoch += 1

    def op_pool(self):
        if self.rng.random() < 0.3 and len(self.m.pools) > 2:
            pool = self.rng.choice(list(self.m.pools.values()))
            self.write(MDeletePool(pool_id=pool.pool_id,
                                   confirm_name=pool.name))
            return
        self.n_pools += 1
        fd = "host" if any(b.type == "host"
                           for b in self.m.crush.buckets.values()) else "osd"
        self.write(MCreatePool(
            name=f"p{self.n_pools}", pool_type="ec", pg_num=4,
            profile={"plugin": "jerasure", "technique": "reed_sol_van",
                     "k": "2", "m": "1",
                     "crush-failure-domain":
                         self.rng.choice(["osd", fd])}))

    def op_pg_num(self):
        pool, _ = self.pool_pg()
        self.write(MPoolSet(pool_id=pool.pool_id, key="pg_num",
                            value=str(pool.pg_num * 2)))

    def op_pg_temp(self):
        pool, pg = self.pool_pg()
        acting = [] if self.rng.random() < 0.3 else self.some_acting(pool)
        self.write(MOSDPGTemp(pool_id=pool.pool_id, pg=pg, acting=acting,
                              from_osd=self.osd()))

    def op_upmap(self):
        pool, pg = self.pool_pg()
        acting = [] if self.rng.random() < 0.3 else self.some_acting(pool)
        self.write(MSetUpmap(pool_id=pool.pool_id, pg=pg, acting=acting))

    def op_affinity(self):
        self.m.primary_affinity[self.osd()] = self.rng.choice([0.0, 0.5, 1.0])
        self.m.epoch += 1

    # bare flips: no handler, no epoch (tests and tools do this)
    def op_bare_state(self):
        info = self.m.osds[self.osd()]
        attr = self.rng.choice(["up", "in_cluster", "weight", "crush_weight"])
        if attr in ("up", "in_cluster"):
            setattr(info, attr, not getattr(info, attr))
        else:
            setattr(info, attr, self.rng.choice([0.0, 0.3, 1.0, 1.7]))

    def op_bare_crush(self):
        crush = self.m.crush
        kind = self.rng.choice(["reorder", "device_weight", "drop_item",
                                "rule_mode", "rule_steps", "type"])
        bucket = self.rng.choice(list(crush.buckets.values()))
        if kind == "reorder":
            self.rng.shuffle(bucket.items)
        elif kind == "device_weight":  # read for a device with no record
            crush.device_weights[self.rng.choice(crush.devices())] = \
                self.rng.choice([0.0, 0.4, 2.0])
        elif kind == "drop_item" and len(bucket.items) > 1:
            bucket.items.pop()
        elif kind == "rule_mode":
            rule = crush.rules[self.rng.choice(list(crush.rules))]
            rule["mode"] = "firstn" if rule["mode"] == "indep" else "indep"
        elif kind == "rule_steps":
            rule = crush.rules[self.rng.choice(list(crush.rules))]
            rule["steps"] = [
                s if s[0] not in ("choose", "chooseleaf")
                else (s[0], "firstn" if s[1] == "indep" else "indep") + s[2:]
                for s in map(tuple, rule["steps"])]
        elif kind == "type" and bucket.id != crush.root_id:
            bucket.type = "rack" if bucket.type == "host" else "host"

    def op_bare_forget(self):
        """The record goes, the device stays in the tree (what a
        subscriber holds after `removed_osds` with no crush delta)."""
        if len(self.m.osds) > 6:
            del self.m.osds[self.osd()]

    def op_bare_override(self):
        pool, pg = self.pool_pg()
        table = self.rng.choice([self.m.pg_temp, self.m.pg_upmap])
        if (pool.pool_id, pg) in table:
            del table[(pool.pool_id, pg)]
        else:
            table[(pool.pool_id, pg)] = self.some_acting(pool)

    OPS = ("membership", "membership", "down_up", "down_up", "purge",
           "new_osd", "crush", "crush", "crush", "pool", "pg_num",
           "pg_temp", "upmap", "affinity", "bare_state", "bare_state",
           "bare_crush", "bare_crush", "bare_forget", "bare_override")

    def step(self):
        name = self.rng.choice(self.OPS)
        getattr(self, "op_" + name)()
        return name

    def ship(self):
        """Bring the subscriber's copy up by a delta, over the wire's
        encoding."""
        inc = OSDMapIncremental.diff(self.follower, self.m)
        inc = pickle.loads(pickle.dumps(inc, protocol=5))
        assert self.follower.apply_incremental(inc)


@pytest.mark.parametrize("n_hosts", [0, 3])
@pytest.mark.parametrize("seed", range(4))
def test_memo_matches_a_fresh_draw_through_every_mutation(seed, n_hosts):
    d = _Driver(seed * 7919 + n_hosts, n_hosts)
    seen = set()
    assert answers(d.m) == fresh_answers(d.m)
    for _ in range(60):
        seen.add(d.step())
        got = answers(d.m)  # warm from every earlier state of the map
        assert got == fresh_answers(d.m), sorted(seen)
        assert answers(d.m) == got  # and now all lookups
        d.ship()
        assert answers(d.follower) == fresh_answers(d.follower)
    assert len(seen) >= 10


def test_each_incremental_field_moves_the_answer_it_should():
    """`apply_incremental` field by field on a warm map."""
    m = OSDMap(epoch=1,
               osds={i: OsdInfo(i, ("127.0.0.1", 6800 + i)) for i in range(8)},
               crush=CrushMap.flat(list(range(8))))
    m.crush.add_simple_rule("r")
    m.pools[1] = PoolInfo(1, "p", "ec", 16, 5, 4, rule="r")
    pool = m.pools[1]
    victim = m.pg_to_raw(pool, 0)[0]
    other = CrushMap.with_hosts(list(range(8)), 4)
    other.add_simple_rule("r", failure_domain="host")
    incs = [
        dict(osd_states={victim: (False, True)}),           # down: a hole
        dict(osd_states={victim: (True, False)}),           # out: redrawn
        dict(new_osds={victim: OsdInfo(victim, ("h", 1), weight=0.0)}),
        dict(new_osds={8: OsdInfo(8, ("h", 8))}),           # not in crush
        dict(removed_osds=[victim]),
        dict(new_pools={2: PoolInfo(2, "q", "replicated", 4, 3, 2, rule="r")}),
        dict(removed_pools=[2]),
        dict(new_pg_temp={(1, 0): [1, 2, 3, 4, 5]}),
        dict(new_pg_temp={(1, 0): []}),
        dict(new_pg_upmap={(1, 1): [5, 4, 3, 2, 1]}),
        dict(new_primary_affinity={1: 0.0}),
        dict(crush=other),
        dict(new_flags=["pausewr"]),
    ]
    assert answers(m) == fresh_answers(m)
    for fields in incs:
        inc = OSDMapIncremental(epoch=m.epoch + 1, base_epoch=m.epoch,
                                **fields)
        assert m.apply_incremental(inc)
        assert answers(m) == fresh_answers(m), fields
        if fields.get("osd_states") == {victim: (True, False)}:
            assert victim not in m.pg_to_raw(pool, 0)


def _two_racks():
    crush = CrushMap()
    root = crush.add_bucket("root", "default")
    for r in range(2):
        rack = crush.add_bucket("rack", f"rack{r}")
        crush.add_item(root, rack, 0.0)
        for h in range(2):
            host = crush.add_bucket("host", f"host{r}{h}")
            crush.add_item(rack, host, 0.0)
            for d in range(2):
                crush.add_item(host, (r * 2 + h) * 2 + d, 1.0)
    crush.add_simple_rule("r", failure_domain="host")
    return crush


def _flip_steps(crush):
    rule = crush.rules["r"]
    rule["steps"] = [("take", crush.bucket_by_name("rack1").id)] \
        + list(rule["steps"][1:])


def _short_firstn(crush):
    crush.rules["r"]["steps"] = [
        s if s[0] != "chooseleaf" else ("chooseleaf", "firstn", 0, "host")
        for s in crush.rules["r"]["steps"]]
    _draw_all(crush, "r")  # the state just before the flip, warm
    crush.rules["r"]["mode"] = "firstn"  # 4 hosts: no padding to 6 now


def _draw_all(crush, rule):
    w = {d: 1.0 for d in range(6)}  # devices 6, 7: stored weight only
    return [crush.do_rule(rule, x, 6, w) for x in range(24)]


@pytest.mark.parametrize("edit", [
    lambda c: c.device_weights.update({6: 0.0, 7: 0.0}),
    lambda c: setattr(c.buckets[c.bucket_by_name("host00").id], "type",
                      "chassis"),
    lambda c: c.buckets[c.bucket_by_name("host10").id].items.pop(),
    lambda c: c.remove_bucket(c.bucket_by_name("rack1").id),
    lambda c: c.move_item(0, c.bucket_by_name("host11").id, 1.0),
    lambda c: c.add_item(c.bucket_by_name("host00").id, 8, 1.0),
    lambda c: c.set_weight(7, 0.0),
    lambda c: c.add_simple_rule("r", failure_domain="rack"),
    _flip_steps, _short_firstn,
], ids=["device_weights", "bucket_type", "item_dropped",
        "remove_bucket", "move_item", "add_item", "set_weight",
        "rule_replaced", "rule_steps", "rule_mode"])
def test_every_input_of_the_draw_is_in_the_key(edit):
    """One edit at a time, made behind the map's back where there is a
    way to: the warm map answers as a map that never drew."""
    crush = _two_racks()
    before = _draw_all(crush, "r")
    edit(crush)
    with no_memo():
        want = _draw_all(copy.deepcopy(crush), "r")
    assert _draw_all(crush, "r") == want
    assert want != before, "the edit did not move placement: a weak case"


def test_the_default_rule_reads_the_root():
    crush = _two_racks()
    before = _draw_all(crush, "no-such-rule")
    crush.root_id = crush.bucket_by_name("rack0").id
    with no_memo():
        want = _draw_all(copy.deepcopy(crush), "no-such-rule")
    assert _draw_all(crush, "no-such-rule") == want != before


# -- (b) the answers are the caller's own ------------------------------------


def test_returned_lists_are_independent():
    m = OSDMap(epoch=1,
               osds={i: OsdInfo(i, ("127.0.0.1", 6800 + i)) for i in range(6)},
               crush=CrushMap.flat(list(range(6))))
    m.crush.add_simple_rule("r")
    m.pools[1] = PoolInfo(1, "p", "ec", 4, 4, 3, rule="r")
    pool = m.pools[1]
    m.pg_upmap[(1, 1)] = [3, 2, 1, 0]
    m.pg_temp[(1, 2)] = [0, 1, 2, 3]
    for pg in range(3):
        for fn in (m.pg_to_acting, m.pg_to_raw, m.pg_to_placed):
            first = fn(pool, pg)
            want = list(first)
            first[0] = 99
            first.append(7)
            second = fn(pool, pg)
            assert second == want and second is not first
    w = m.osd_effective_weights()
    a = m.crush.do_rule("r", 5, 4, w)
    a.clear()
    assert len(m.crush.do_rule("r", 5, 4, w)) == 4
    assert m.pg_upmap[(1, 1)] == [3, 2, 1, 0]
    assert m.pg_temp[(1, 2)] == [0, 1, 2, 3]


# -- (c) bounded --------------------------------------------------------------


def test_memo_stays_within_its_bound_under_a_sweep():
    crush = CrushMap.flat(list(range(12)))
    crush.add_simple_rule("r")
    hot = [crush.do_rule("r", x, 11, {d: 1.0 for d in range(12)})
           for x in range(32)]
    stats = CrushTester(crush).test("r", 4, n_inputs=4096)
    assert stats["placed"] == 4096 * 4
    assert len(crush_mod._memo) <= crush_mod._MEMO_MAX < 4096
    # the sweep pushed the hot PGs out; they come back the same
    assert hot == [crush.do_rule("r", x, 11, {d: 1.0 for d in range(12)})
                   for x in range(32)]
    assert len(crush_mod._memo) <= crush_mod._MEMO_MAX


def test_threads_share_the_memo_without_losing_the_bound(monkeypatch):
    """More threads than cores draw and evict at once (a tiny bound, a
    short switch interval): every answer is the draw's, the bound holds,
    nobody trips over the dict changing size under an eviction."""
    import sys
    import threading

    monkeypatch.setattr(crush_mod, "_MEMO_MAX", 8)
    monkeypatch.setattr(crush_mod, "_memo", {})
    crush = CrushMap.flat(list(range(10)))
    crush.add_simple_rule("r")
    w = {d: 1.0 for d in range(10)}
    w[2] = 0.654321
    with no_memo():
        want = [crush.do_rule("r", x, 4, w) for x in range(24)]
    monkeypatch.setattr(crush_mod, "_memo", {})
    errors, over = [], []

    def worker(k):
        try:
            for i in range(150):
                x = (i * 7 + k) % 24
                if crush.do_rule("r", x, 4, w) != want[x]:
                    errors.append((k, x))
                if len(crush_mod._memo) > 8:
                    over.append(len(crush_mod._memo))
        except Exception as e:  # noqa: BLE001 - reported below
            errors.append(repr(e))

    threads = [threading.Thread(target=worker, args=(k,))
               for k in range((os.cpu_count() or 4) + 4)]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert not errors and not over
    assert 0 < len(crush_mod._memo) <= 8


# -- (d) not on the wire, not in equality ------------------------------------

# encode_payload(MMapReply(osdmap=_wire_map(), tid="t")) as the commit
# before the memo wrote it (PR 28, 245ce94)
GOLDEN_MMAPREPLY = bytes.fromhex(
    "80059567050000000000007d94288c066f73646d6170948c14636570685f7470752e7261"
    "646f732e7479706573948c064f53444d61709493942981947d94288c0565706f6368944b"
    "078c046f736473947d94284b0068028c074f7364496e666f9493942981947d94288c066f"
    "73645f6964944b008c0461646472948c093132372e302e302e31944d901a86948c027570"
    "94888c0a696e5f636c757374657294888c0677656967687494473ff00000000000008c0c"
    "63727573685f77656967687494473ff000000000000075624b01680b2981947d9428680e"
    "4b01680f68104d911a86946812886813886814473ff00000000000006815473ff0000000"
    "00000075624b02680b2981947d9428680e4b02680f68104d921a86946812886813886814"
    "473ff00000000000006815473ff000000000000075624b03680b2981947d9428680e4b03"
    "680f68104d931a86946812886813886814473ff00000000000006815473ff00000000000"
    "007562758c05706f6f6c73947d944b0168028c08506f6f6c496e666f9493942981947d94"
    "288c07706f6f6c5f6964944b018c046e616d65948c0170948c09706f6f6c5f7479706594"
    "8c026563948c0670675f6e756d944b088c0473697a65944b038c086d696e5f73697a6594"
    "4b028c0770726f66696c65947d948c0472756c65948c0172948c0c7374726970655f7769"
    "647468944b008c0d637265617465645f65706f6368944b008c08736e61705f736571944b"
    "008c0d72656d6f7665645f736e6170739468028c0b496e74657276616c53657494939429"
    "81945d94628c09736e61705f6d6f6465948c046e6f6e65948c0a706f6f6c5f736e617073"
    "947d948c046f707473947d947562738c056372757368948c14636570685f7470752e7261"
    "646f732e6372757368948c0843727573684d61709493942981947d94288c076275636b65"
    "7473947d94284affffffff68408c064275636b65749493942981947d94288c026964944a"
    "ffffffff8c0474797065948c04726f6f749468268c0764656661756c74948c056974656d"
    "73945d94284afeffffff4afdffffff658c0777656967687473947d94284afeffffff4700"
    "000000000000004afdffffff4700000000000000007575624afeffffff68482981947d94"
    "28684b4afeffffff684c8c04686f73749468268c05686f73743094684f5d94284b004b02"
    "6568517d94284b00473ff00000000000004b02473ff00000000000007575624afdffffff"
    "68482981947d9428684b4afdffffff684c685568268c05686f73743194684f5d94284b01"
    "4b036568517d94284b01473ff00000000000004b03473ff0000000000000757562758c05"
    "72756c6573947d9468307d9428684b4b008c046d6f6465948c05696e646570948c057374"
    "657073945d94288c0474616b65944affffffff8694288c0a63686f6f73656c6561669468"
    "624b00685574948c04656d69749485946575738c07726f6f745f6964944affffffff8c0f"
    "5f6e6578745f6275636b65745f6964944afcffffff8c0d5f6e6578745f72756c655f6964"
    "944b018c0e6465766963655f77656967687473947d94284b00473ff00000000000004b01"
    "473ff00000000000004b02473ff00000000000004b03473ff00000000000007575628c05"
    "666c616773945d948c0966756c6c5f6f736473947d948c0e6e65617266756c6c5f726174"
    "696f94473feb3333333333338c126261636b66696c6c66756c6c5f726174696f94473fec"
    "cccccccccccd8c0a66756c6c5f726174696f94473fee6666666666668c0770675f74656d"
    "70947d944b014b0286945d94284b004b014b0265738c0870675f75706d6170947d948c10"
    "7072696d6172795f616666696e697479947d944b01473fe00000000000007375628c0c69"
    "6e6372656d656e74616c73945d948c03746964948c017494752e"
)


def _wire_map():
    m = OSDMap(epoch=7,
               osds={i: OsdInfo(i, ("127.0.0.1", 6800 + i)) for i in range(4)},
               crush=CrushMap.with_hosts([0, 1, 2, 3], 2))
    m.crush.add_simple_rule("r", failure_domain="host")
    m.pools[1] = PoolInfo(1, "p", "ec", 8, 3, 2, rule="r")
    m.pg_temp[(1, 2)] = [0, 1, 2]
    m.primary_affinity[1] = 0.5
    return m


class TestNotOnTheWire:
    def test_encoded_maps_are_the_parents_bytes_warm_or_cold(self):
        cold = _wire_map()
        assert encode_payload(MMapReply(osdmap=cold, tid="t")) \
            == GOLDEN_MMAPREPLY
        warm = _wire_map()
        before = _lookups()
        answers(warm)
        assert _lookups() > before
        assert encode_payload(MMapReply(osdmap=warm, tid="t")) \
            == GOLDEN_MMAPREPLY
        for proto in (2, 4, 5):
            assert pickle.dumps(warm, protocol=proto) \
                == pickle.dumps(cold, protocol=proto)
            assert pickle.dumps(warm.crush, protocol=proto) \
                == pickle.dumps(cold.crush, protocol=proto)

    def test_incremental_encodes_the_same_warm_or_cold(self):
        def inc_bytes(warm):
            old, new = _wire_map(), _wire_map()
            new.epoch = 8
            new.crush.add_bucket("rack", "rackA")
            new.osds[2].in_cluster = False
            if warm:
                answers(old), answers(new)
            inc = OSDMapIncremental.diff(old, new)
            assert inc.crush is new.crush
            return (pickle.dumps(inc, protocol=5),
                    encode_payload(MMapReply(incrementals=[inc], tid="t")))
        assert inc_bytes(True) == inc_bytes(False)

    def test_a_map_pickled_before_the_memo_loads_and_places(self):
        reply = MMapReply.__new__(MMapReply)
        reply.__dict__.update(pickle.loads(GOLDEN_MMAPREPLY))
        m = reply.osdmap
        assert answers(m) == fresh_answers(m) == answers(_wire_map())

    def test_nothing_rides_the_instances(self):
        m = _wire_map()
        cold_sig = m.crush.sig()
        crush_attrs, map_attrs = set(vars(m.crush)), set(vars(m))
        bucket_attrs = set(vars(m.crush.buckets[m.crush.root_id]))
        pool_before = copy.deepcopy(m.pools[1])
        answers(m)
        assert m.crush.sig() == cold_sig == _wire_map().crush.sig()
        assert set(vars(m.crush)) == crush_attrs
        assert set(vars(m)) == map_attrs
        assert set(vars(m.crush.buckets[m.crush.root_id])) == bucket_attrs
        assert m.pools[1] == pool_before
        # the balancer's deep copy carries nothing either, and agrees
        twin = copy.deepcopy(m)
        assert set(vars(twin.crush)) == crush_attrs
        assert answers(twin) == answers(m)
        d = OSDMapIncremental.diff(twin, m)
        assert d.crush is None and not d.new_pools and not d.new_osds


# -- (e) the counters ---------------------------------------------------------


def test_draws_counts_exactly_the_misses():
    crush = CrushMap.flat(list(range(10)))
    crush.add_simple_rule("r")
    w = {d: 1.0 for d in range(10)}
    w[3] = 0.123456  # a state no other test has drawn
    l0, d0 = _lookups(), _draws()
    first = [crush.do_rule("r", x, 6, w) for x in range(20)]
    assert (_lookups() - l0, _draws() - d0) == (20, 20)
    again = [crush.do_rule("r", x, 6, dict(w)) for x in range(20)]
    assert again == first
    assert (_lookups() - l0, _draws() - d0) == (40, 20)
    # an equal map in another object is the same state: no draw
    twin = copy.deepcopy(crush)
    assert [twin.do_rule("r", x, 6, w) for x in range(20)] == first
    assert (_lookups() - l0, _draws() - d0) == (60, 20)
    # any input that differs is a miss
    crush.do_rule("r", 0, 5, w)
    crush.do_rule("r", 0, 6, {**w, 4: 0.5})
    crush.set_weight(9, 2.0)
    crush.do_rule("r", 0, 6, {d: v for d, v in w.items() if d != 9})
    assert (_lookups() - l0, _draws() - d0) == (63, 23)
    with no_memo():
        assert [crush.do_rule("r", x, 6, w) for x in range(20)] == first


@pytest.mark.parametrize("name, cells", [
    ("crush_draws_per_op.put",
     ["k8m3.write4m", "k4m2.write4m", "k10m4c.write4m",
      "k8m3.mixed-small", "k8m3.rbd-randwrite4k",
      "k8m3.write4m-bluestore", "k8m4clay.write4m"]),
    ("crush_draws_per_op.get", ["k8m3.randread4m", "k8m3.randread4m-cold"]),
])
def test_the_engagement_metric_reads_draws_per_op(name, cells):
    """The benchmark's data files: draws over client ops from a window's
    counter delta; a program without the counter (the parent) reports
    nothing, a steady window reports 0.0 and not nothing."""
    import json

    from benchmarks import layers

    def read(counters):
        return layers.read(name, {"counters": counters})

    assert read({"objecter.op": 500}) is None
    assert read({"objecter.op": 500, "crush.draws": 0,
                 "crush.lookups": 9000}) == 0.0
    assert read({"objecter.op": 500, "crush.draws": 9500}) == 19.0
    assert read({"objecter.op": 0, "crush.draws": 3}) is None
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        entry = [e for e in json.load(f)["per_layer"] if e["name"] == name]
    assert len(entry) == 1 and entry[0]["workloads"] == cells
    assert entry[0]["layer"] == "OSD op path"
    assert entry[0]["source"] == "program_counter"


# -- the fence reads the map it is given -------------------------------------

CONF = {
    "mon_osd_report_grace": 0.8,
    "osd_heartbeat_interval": 0.2,
    "osd_repair_delay": 0.2,
    "client_op_timeout": 1.5,
}

PROFILE = {"plugin": "jerasure", "technique": "reed_sol_van",
           "k": "2", "m": "1"}


def test_fence_refuses_the_second_subwrite_after_the_map_moves():
    """Two sub-writes from one primary with the replica's map changed in
    place between them (no epoch): the first lands, the second is from
    a deposed primary and is refused — the fence's placement is of the
    map as it is now, not as it was when the PG was last drawn."""
    async def go():
        cluster = Cluster(n_osds=4, conf=dict(CONF))
        await cluster.start()
        try:
            c = await cluster.client()
            pool = await c.create_pool("fence", profile=dict(PROFILE))
            data = os.urandom(6000)
            await c.put(pool, "obj", data)
            p = c.osdmap.pools[pool]
            pg = c.osdmap.object_to_pg(p, "obj")
            acting = c.osdmap.pg_to_acting(p, pg)
            primary = c.osdmap.primary_of(acting, seed=(pool << 20) | pg)
            replica_id = next(a for a in acting if a >= 0 and a != primary)
            replica = cluster.osds[replica_id]
            shard = acting.index(replica_id)
            chunk, meta = replica.store.read((pool, "obj", shard))

            def sub_write(version, fill):
                return MECSubWrite(
                    pool_id=pool, pg=pg, oid="obj", shard=shard,
                    chunk=fill * len(chunk), version=version,
                    object_size=meta.object_size, tid=f"t{version}",
                    reply_to=("127.0.0.1", 1), from_osd=primary,
                    epoch=replica.osdmap.epoch)

            d0 = _draws()
            r1 = await replica._apply_sub_write(sub_write(
                meta.version + 1, b"\x01"))
            assert r1.ok
            assert replica.store.read((pool, "obj", shard))[1].version \
                == meta.version + 1
            assert _draws() == d0, "a steady map draws nothing per message"
            # the primary leaves placement in the replica's map, in place
            replica.osdmap.osds[primary].in_cluster = False
            assert primary not in replica.osdmap.pg_to_acting(
                replica.osdmap.pools[pool], pg)
            r2 = await replica._apply_sub_write(sub_write(
                meta.version + 2, b"\x02"))
            assert not r2.ok
            assert replica.store.read((pool, "obj", shard))[1].version \
                == meta.version + 1
            # the counters are where the daemons and the client dump
            assert replica.ctx.perf.dump()["crush"]["lookups"] > 0
            assert c.perf_dump()["crush"] == CRUSH_PERF.dump()
        finally:
            await cluster.stop()

    asyncio.run(asyncio.wait_for(go(), 90))
