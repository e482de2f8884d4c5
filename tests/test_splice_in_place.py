"""An offset write costs its stripe, not its object (PR 44).

The store writes at an offset (`Transaction.write_at`): MemStore in place,
in a buffer of its own, with the outgoing version kept at the rollback
slot as the extent it overwrote; the other stores read, rebuild and write
whole.  The shard's crc is made from the crc it had and the bytes that
changed.  The primary cuts the segment out of its cached object and
patches the cached object after the commit.

Held here: every sequence of splices against a rebuild-the-blob
reference (bytes, `ShardMeta.chunk_crc`, the hinfo entry, the rollback
slot), under both checksum kinds; a refused splice leaves store and log
as they were; the rule that protects a reader (a view that is alive reads
what it read: the store copies before it writes); the primary's cache over
N offset writes.
"""

import asyncio
import zlib

import numpy as np
import pytest

from ceph_tpu.rados.ecutil import HashInfo
from ceph_tpu.rados.extent_cache import ExtentCache
from ceph_tpu.rados.osd import PREV_SLOT
from ceph_tpu.rados.pglog import LogEntry
from ceph_tpu.rados.store import (DirStore, ENOSPCError, MemStore, Owned,
                                  ShardMeta, Transaction, live, shard_crc,
                                  splice, viewed)
from ceph_tpu.rados.types import MECSubRead
from ceph_tpu.rados.vstart import Cluster
from ceph_tpu.utils import checksum as cs

UNIT = 4096
KEY = (3, "obj", 2)
SLOT = (3, "obj", 2 + PREV_SLOT)
PROFILE = {"plugin": "jerasure", "technique": "reed_sol_van",
           "k": "2", "m": "1", "stripe_unit": str(UNIT)}
CONF = {"mon_osd_report_grace": 0.8, "osd_heartbeat_interval": 0.2,
        "client_op_timeout": 5.0, "osd_auto_repair": False}


def payload(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, n, dtype=np.uint8).tobytes()


def run(coro, timeout=60):
    return asyncio.run(asyncio.wait_for(coro, timeout))


# the splices of one shard of 8 chunks, in order: (chunk offset, length,
# shard_size).  Aligned inside; the same chunk again (two in a row); at the
# end (an append); past the end (a gap of zeros); shard_size beyond the
# extent (zero-extension after it); inside once more; a short tail.
SEQUENCE = [(2 * UNIT, UNIT, 0), (2 * UNIT, UNIT, 0), (0, UNIT, 0),
            (8 * UNIT, UNIT, 0), (11 * UNIT, UNIT, 0),
            (5 * UNIT, 2 * UNIT, 16 * UNIT), (15 * UNIT, UNIT, 16 * UNIT),
            (16 * UNIT, 100, 0), (7 * UNIT + 17, 33, 0)]


# -- the checksum's shift -----------------------------------------------------


@pytest.mark.parametrize("n", [0, 1, 7, 4096, 8191, 65536, 3 * 8192 + 5,
                               520192, 1 << 22])
def test_the_crc_shift_is_the_crc_of_that_many_zero_bytes(n):
    from ceph_tpu.native import bridge

    rng = np.random.default_rng(n)
    for size in (0, 1, int(rng.integers(2, 9000))):
        head = rng.bytes(size)
        direct = bridge.crc32c(head + bytes(n))
        state = ~bridge.crc32c(head) & 0xFFFFFFFF
        assert ~bridge.crc32c_shift(state, n) & 0xFFFFFFFF == direct
    # linear: the operator of a sum is the sum of the operators
    a, b = (int(x) for x in rng.integers(0, 1 << 32, 2))
    assert bridge.crc32c_shift(a ^ b, n) == \
        bridge.crc32c_shift(a, n) ^ bridge.crc32c_shift(b, n)


@pytest.mark.parametrize("seed", range(6))
def test_a_spliced_checksum_is_the_checksum_of_the_spliced_buffer(seed):
    rng = np.random.default_rng(seed)
    for _ in range(60):
        size = int(rng.integers(0, 70000))
        old = rng.bytes(size)
        off = int(rng.integers(0, 90000))
        now = rng.bytes(int(rng.integers(0, 9000)))
        new_size = max(size, off + len(now), int(rng.integers(0, 120000)))
        got = cs.spliced(cs.checksum(old) & 0xFFFFFFFF, size, new_size, off,
                         old[off:off + len(now)], now)
        assert got == shard_crc(splice(old, off, now, new_size))


def test_without_the_native_shift_a_caller_is_told_to_make_the_pass(
        monkeypatch):
    cs.checksum(b"resolve")
    monkeypatch.setattr(cs, "_IMPL", zlib.crc32)
    monkeypatch.setattr(cs, "_KIND", "zlib")
    assert cs.spliced(zlib.crc32(b"abcd"), 4, 4, 1, b"b", b"x") is None


# -- the store ----------------------------------------------------------------


def stored_as(form: str, blob: bytes):
    """(what the transaction is handed, the sender's buffer or None)."""
    if form == "bytes":
        return blob, None
    arr = np.frombuffer(bytearray(blob), dtype=np.uint8)
    view = memoryview(arr)
    return Owned(view.toreadonly() if form == "readonly_view" else view), arr


def put_whole(store, key, chunk, version):
    txn = Transaction()
    txn.write(key, chunk, ShardMeta(version=version, object_size=1,
                                    chunk_crc=version))
    store.queue_transaction(txn)


def write_at(store, off, data, size, version, prev=SLOT) -> Transaction:
    txn = Transaction()
    txn.write_at(KEY, off, data, size,
                 ShardMeta(version=version, object_size=2,
                           chunk_crc=version), prev=prev)
    store.queue_transaction(txn)
    return txn


@pytest.mark.parametrize("form", ["bytes", "owned_view", "readonly_view"])
def test_a_sequence_of_writes_at_an_offset_against_the_rebuilt_blob(form):
    """Stored bytes, meta and the rollback slot after every step; the
    first write copies the shard (whatever form it arrived in: the
    sender's buffer is never written), the later ones copy nothing."""
    store = MemStore()
    blob = payload(8 * UNIT, seed=1)
    chunk, senders = stored_as(form, blob)
    put_whole(store, KEY, chunk, 1)
    ref, version = blob, 1
    for step, (off, n, size) in enumerate(SEQUENCE):
        data = payload(n, seed=100 + step)
        before, before_meta = ref, store.stat(KEY)[1]
        txn = write_at(store, off, data, size, version + 1)
        ref, version = splice(ref, off, data, size), version + 1
        assert txn.copied == (len(before) if step == 0 else 0)
        got = store.read(KEY)
        assert bytes(got[0]) == ref and got[1].version == version
        assert store.stat(KEY) == (len(ref), got[1])
        slot = store.read(SLOT)
        assert bytes(slot[0]) == before and slot[1] == before_meta
        assert isinstance(slot[0], bytes)
        assert store.stat(SLOT) == (len(before), before_meta)
        assert (KEY[1], SLOT[2]) in set(store.list_objects(KEY[0]))
        del got
    if senders is not None:
        assert senders.tobytes() == blob  # handed over, never written


def test_a_view_taken_before_a_write_reads_what_it_read():
    """The rule that protects a reader: while a view `read` handed out is
    alive, the store copies before it writes (and leaves the old buffer
    to the view); when it is gone, the store writes in place again."""
    store = MemStore()
    blob = payload(8 * UNIT, seed=2)
    put_whole(store, KEY, blob, 1)
    assert write_at(store, 0, b"a" * UNIT, 0, 2).copied == len(blob)
    first = splice(blob, 0, b"a" * UNIT)
    whole = store.read(KEY)[0]  # a recovery push, a whole-shard reply
    part = whole[UNIT:3 * UNIT]  # a reply's extent view
    assert live(whole) and whole.readonly and not live(blob)
    with pytest.raises(TypeError):
        whole[0] = 1
    assert write_at(store, UNIT, b"b" * UNIT, 0, 3).copied == len(blob)
    assert bytes(whole) == first and bytes(part) == first[UNIT:3 * UNIT]
    second = splice(first, UNIT, b"b" * UNIT)
    assert bytes(store.read(KEY)[0]) == second
    assert bytes(store.read(SLOT)[0]) == first
    # a slice outlives the view it was cut from, and still counts
    del whole
    assert write_at(store, 2 * UNIT, b"c" * UNIT, 0, 4).copied == 0
    assert bytes(part) == first[UNIT:3 * UNIT]
    del part
    assert write_at(store, 3 * UNIT, b"d" * UNIT, 0, 5).copied == 0
    # an extent of a shard that is written in place is handed out as a
    # copy, of one that is not as a view
    assert isinstance(store.read_range(KEY, 0, UNIT), bytes)
    assert store.read_range(KEY, 7 * UNIT, 3 * UNIT) == second[7 * UNIT:]
    put_whole(store, (3, "other", 0), blob, 1)
    cut = store.read_range((3, "other", 0), UNIT, UNIT)
    assert isinstance(cut, memoryview) and cut == blob[UNIT:2 * UNIT]


def test_viewed_knows_while_a_view_is_alive():
    buf = bytearray(b"0123456789")
    assert not viewed(buf) and buf == b"0123456789"
    view = memoryview(buf)[2:4]
    assert viewed(buf) and buf == b"0123456789"
    view.release()
    assert not viewed(buf) and len(buf) == 10


def test_the_rollback_slot_survives_what_follows_a_splice():
    """A whole write moves the spliced shard to the slot whole; a rollback
    (the slot written back over the shard, the slot deleted) restores the
    version before the splice; deletes take the slot's record with them;
    the store's byte count is what it holds."""
    store = MemStore()
    blob = payload(8 * UNIT, seed=3)
    put_whole(store, KEY, blob, 1)
    write_at(store, 4 * UNIT, b"x" * UNIT, 0, 2)
    spliced = splice(blob, 4 * UNIT, b"x" * UNIT)
    assert store.statfs()["used"] == len(blob) + UNIT  # shard + extent
    # rollback: as _handle_sub_rollback does it
    prev = store.read(SLOT)
    txn = Transaction()
    txn.write(KEY, prev[0], prev[1])
    txn.delete(SLOT)
    store.queue_transaction(txn)
    assert bytes(store.read(KEY)[0]) == blob and store.read(SLOT) is None
    assert store.read(KEY)[1].version == 1
    assert store.statfs()["used"] == len(blob)
    # a splice, then a whole write as _apply_shard_write's full branch
    write_at(store, 0, b"y" * UNIT, 0, 3)
    after = splice(blob, 0, b"y" * UNIT)
    old = store.read(KEY)
    txn = Transaction()
    txn.write(SLOT, Owned(old[0]), old[1])
    txn.write(KEY, b"new" * 100, ShardMeta(version=4))
    store.queue_transaction(txn)
    del old
    assert bytes(store.read(SLOT)[0]) == after
    assert store.read(SLOT)[1].version == 3
    assert store.statfs()["used"] == len(after) + 300
    # the next write at an offset starts from the whole write
    write_at(store, 1, b"zz", 0, 5)
    assert bytes(store.read(KEY)[0]) == splice(b"new" * 100, 1, b"zz")
    assert bytes(store.read(SLOT)[0]) == b"new" * 100
    txn = Transaction()
    txn.delete(KEY)
    txn.delete(SLOT)
    store.queue_transaction(txn)
    assert store.statfs() == {"total": 0, "used": 0, "avail": 0,
                              "num_objects": 0}


def test_a_write_at_an_offset_without_a_slot_never_strands_one():
    """No `prev`: the slot of an earlier write reads through the buffer,
    so the store may not change it in place."""
    store = MemStore()
    blob = payload(4 * UNIT, seed=4)
    put_whole(store, KEY, blob, 1)
    write_at(store, 0, b"a" * UNIT, 0, 2)
    one = splice(blob, 0, b"a" * UNIT)
    assert write_at(store, UNIT, b"b" * UNIT, 0, 3, prev=None).copied \
        == len(blob)
    assert bytes(store.read(SLOT)[0]) == blob  # still the version before 2
    assert bytes(store.read(KEY)[0]) == splice(one, UNIT, b"b" * UNIT)
    # an object that is not there is made
    txn = Transaction()
    txn.write_at((3, "fresh", 0), UNIT, b"q" * 10, 0, ShardMeta(version=1),
                 prev=(3, "fresh", PREV_SLOT))
    store.queue_transaction(txn)
    assert bytes(store.read((3, "fresh", 0))[0]) == bytes(UNIT) + b"q" * 10
    assert store.read((3, "fresh", PREV_SLOT)) is None


def test_a_refused_transaction_leaves_the_store_as_it_was():
    store = MemStore(capacity_bytes=10 * UNIT)
    blob = payload(8 * UNIT, seed=5)
    put_whole(store, KEY, blob, 1)
    write_at(store, 0, b"a" * UNIT, 0, 2)
    held = {k: (bytes(store.read(k)[0]), store.read(k)[1])
            for k in (KEY, SLOT)}
    with pytest.raises(ENOSPCError):
        write_at(store, 0, b"b" * (2 * UNIT), 0, 3)
    assert {k: (bytes(store.read(k)[0]), store.read(k)[1])
            for k in (KEY, SLOT)} == held
    assert store.statfs()["used"] == 9 * UNIT


@pytest.mark.parametrize("make", ["dir", "blue"])
def test_a_store_with_no_write_at_an_offset_rebuilds_to_the_same_bytes(
        tmp_path, make):
    if make == "dir":
        store = DirStore(str(tmp_path))
    else:
        from ceph_tpu.rados.bluestore import BlueStore

        store = BlueStore(str(tmp_path))
    mem = MemStore()
    blob = payload(8 * UNIT, seed=6)
    for s in (store, mem):
        put_whole(s, KEY, blob, 1)
    ref, version = blob, 1
    for step, (off, n, size) in enumerate(SEQUENCE):
        data = payload(n, seed=200 + step)
        before = ref
        ref, version = splice(ref, off, data, size), version + 1
        for s in (store, mem):
            txn = write_at(s, off, data, size, version)
            # the store that rebuilds says what that cost, every time
            assert s is mem or (txn.copied == len(before) + len(ref)
                                and not txn.ranged)
            assert bytes(s.read(KEY)[0]) == ref
            assert s.read(KEY)[1].version == version
            assert bytes(s.read(SLOT)[0]) == before
            assert s.read(SLOT)[1].version == version - 1
            assert s.stat(KEY)[0] == len(ref)
        assert txn.copied == (len(blob) if step == 0 else 0)  # mem
    assert store.read((9, "none", 0)) is None


# -- the shard side of an offset write ----------------------------------------


def _hinfo_entry(osd, key):
    raw = osd.store.getattr(key, HashInfo.XATTR_KEY)
    h = HashInfo.decode(raw)
    return h.crcs[key[2]], h.total_chunk_size, h.dirty


def _held(osd, pool):
    return {(oid, shard): (bytes(osd.store.read((pool, oid, shard))[0]),
                           osd.store.read((pool, oid, shard))[1])
            for oid, shard in osd.store.list_objects(pool)}


async def _shard_scenario(kind: str):
    """One OSD's `_apply_shard_write`, driven as a sub-write drives it: a
    whole shard with its hinfo record, then SEQUENCE as splices, then the
    refusals."""
    if kind == "zlib":
        cs.checksum(b"resolve")
        cs._IMPL, cs._KIND = zlib.crc32, "zlib"
    seen = {"steps": []}
    cluster = Cluster(n_osds=3, conf=dict(CONF))
    await cluster.start()
    try:
        c = await cluster.client()
        pool = await c.create_pool("splice", profile=dict(PROFILE))
        osd = cluster.osds[0]
        shard, oid = 1, "shard-under-test"
        key, slot = (pool, oid, shard), (pool, oid, shard + PREV_SLOT)
        blob = payload(8 * UNIT, seed=7)
        # as the wire delivers a sub-write's chunk: a view of a buffer of
        # the message's own
        wire = np.frombuffer(bytearray(blob), dtype=np.uint8)
        record = HashInfo(3, total_chunk_size=len(blob),
                          crcs=[11, shard_crc(blob), 33]).encode()
        assert osd._apply_shard_write(pool, oid, shard, memoryview(wire), 1,
                                      16 * UNIT, hinfo=record,
                                      chunk_crc=shard_crc(blob))
        ref, version = blob, 1
        perf0 = {k: osd.perf.get(k) for k in (
            "splice_in_place", "splice_rebuilt", "splice_refused",
            "splice_copied_bytes", "splice_crc_bytes")}
        pg = 0
        for step, (off, n, size) in enumerate(SEQUENCE):
            data = payload(n, seed=300 + step)
            before, before_meta = ref, osd.store.stat(key)[1]
            entry = LogEntry(version=(1, 100 + step), op="write", oid=oid,
                             prior_version=(1, 99 + step))
            ok = osd._apply_shard_write(
                pool, oid, shard, memoryview(data), version + 1, 16 * UNIT,
                pg=pg, entry=entry, chunk_off=off, shard_size=size,
                prior_version=version)
            ref, version = splice(ref, off, data, size), version + 1
            got, prev = osd.store.read(key), osd.store.read(slot)
            seen["steps"].append({
                "ok": ok, "bytes": bytes(got[0]) == ref,
                "version": got[1].version == version,
                "crc": got[1].chunk_crc == shard_crc(ref),
                "hinfo": _hinfo_entry(osd, key) == (
                    shard_crc(ref), len(ref), True),
                "slot": bytes(prev[0]) == before and prev[1] == before_meta,
                "logged": osd._pglog(pool, pg).head == (1, 100 + step)})
            del got, prev
        seen["wire_untouched"] = wire.tobytes() == blob
        seen["moved"] = {k: osd.perf.get(k) - v for k, v in perf0.items()}
        # refused: the shard is not at the version the primary read
        held, head = _held(osd, pool), osd._pglog(pool, pg).head
        entry = LogEntry(version=(1, 500), op="write", oid=oid,
                         prior_version=head)
        seen["stale_refused"] = osd._apply_shard_write(
            pool, oid, shard, b"x" * UNIT, version + 1, 16 * UNIT, pg=pg,
            entry=entry, chunk_off=0, prior_version=version - 1) is False
        seen["absent_refused"] = osd._apply_shard_write(
            pool, "no-such-object", shard, b"x" * UNIT, 2, UNIT, pg=pg,
            entry=entry, chunk_off=0, prior_version=1) is False
        # refused: the store is full
        osd._failsafe_full = lambda extra=0: True
        try:
            osd._apply_shard_write(pool, oid, shard, b"x" * UNIT,
                                   version + 1, 16 * UNIT, pg=pg,
                                   entry=entry, chunk_off=0,
                                   prior_version=version)
            seen["enospc"] = False
        except ENOSPCError:
            seen["enospc"] = True
        del osd._failsafe_full
        seen["refusals_left_no_trace"] = (
            _held(osd, pool) == held
            and osd._pglog(pool, pg).head == head
            and _hinfo_entry(osd, key) == (shard_crc(ref), len(ref), True))
        seen["refused"] = osd.perf.get("splice_refused") \
            - perf0["splice_refused"]
        await c.stop()
    finally:
        await cluster.stop()
    return seen


@pytest.fixture(scope="module", params=["crc32c", "zlib"])
def shard_seen(request):
    was = (cs._IMPL, cs._KIND)
    try:
        seen = run(_shard_scenario(request.param), timeout=120)
    finally:
        cs._IMPL, cs._KIND = was
    seen["kind"] = request.param
    return seen


@pytest.mark.parametrize("what", ["ok", "bytes", "version", "crc", "hinfo",
                                  "slot", "logged"])
def test_after_every_splice_the_shard_is_the_rebuilt_blob(shard_seen, what):
    """`crc`: ShardMeta.chunk_crc == shard_crc(whole shard); `hinfo`: the
    shard's own entry is the same value over the same length, the record
    dirty; `slot`: the rollback slot reads back the exact previous shard
    and its meta."""
    assert [s[what] for s in shard_seen["steps"]] == [True] * len(SEQUENCE)


def test_a_shard_as_the_wire_delivered_it_is_never_written(shard_seen):
    assert shard_seen["wire_untouched"]


def test_splices_are_counted_by_what_they_cost(shard_seen):
    moved, n = shard_seen["moved"], len(SEQUENCE)
    sizes = [length for _off, length, _size in SEQUENCE]
    assert moved["splice_refused"] == 0
    assert moved["splice_copied_bytes"] == 8 * UNIT + 3 * sum(sizes)
    if shard_seen["kind"] == "crc32c":
        # the first copied the shard; every crc came from the delta
        assert (moved["splice_in_place"], moved["splice_rebuilt"]) \
            == (n - 1, 1)
        assert moved["splice_crc_bytes"] <= 2 * sum(sizes)
    else:
        # no shift for this kind: a pass over the whole shard each time
        assert (moved["splice_in_place"], moved["splice_rebuilt"]) == (0, n)
        assert moved["splice_crc_bytes"] >= n * 8 * UNIT


@pytest.mark.parametrize("what", ["stale_refused", "absent_refused",
                                  "enospc", "refusals_left_no_trace"])
def test_a_refused_splice_leaves_store_and_log_as_they_were(shard_seen,
                                                            what):
    assert shard_seen[what] is True
    assert shard_seen["refused"] == 2


# -- readers, and the primary's cache -----------------------------------------


def _primary_of(cluster, c, pool, oid):
    p = c.osdmap.pools[pool]
    pg = c.osdmap.object_to_pg(p, oid)
    acting = c.osdmap.pg_to_acting(p, pg)
    return acting, cluster.osds[c.osdmap.primary_of(
        acting, seed=(pool << 20) | pg)]


def _total(cluster, key):
    return sum(o.perf.get(key) for o in cluster.osds.values())


async def _cache_scenario(fastpath: bool):
    seen = {}
    cluster = Cluster(n_osds=4, conf={**CONF, "ms_local_fastpath": fastpath})
    await cluster.start()
    try:
        c = await cluster.client()
        pool = await c.create_pool("cache", profile=dict(PROFILE))
        stripe = 2 * UNIT
        model = bytearray(payload(64 * UNIT, seed=8))
        await c.put(pool, "obj", bytes(model))
        acting, primary = _primary_of(cluster, c, pool, "obj")
        key = (pool, "obj")
        seen["adopted_by_the_put"] = _total(cluster, "write_adopted_bytes")
        arm0 = primary.perf.get("rmw_base_cached")
        copied, whole_reads = [], []
        rng = np.random.default_rng(9)
        offsets = [int(b) * UNIT for b in rng.permutation(64)[:12]]
        for i, off in enumerate(offsets):
            data = payload(UNIT, seed=400 + i)
            before = primary.perf.get("rmw_copied_bytes")
            await c.put(pool, "obj", data, offset=off)
            model[off:off + UNIT] = data
            copied.append(primary.perf.get("rmw_copied_bytes") - before)
            ent = primary._extent_cache._entries[key]
            whole_reads.append(
                ent.full and ent.size == len(model)
                and primary._extent_cache.get_whole(key, off, UNIT)[1]
                == data
                # every third write somebody wants it whole (a join)
                and (i % 3 != 0 or primary._extent_cache.get_full(key)[1]
                     == bytes(model)))
        seen["copied"] = copied
        seen["get_full_is_the_model"] = whole_reads
        seen["cached_arm"] = primary.perf.get("rmw_base_cached") - arm0
        seen["each"] = 2 * stripe
        seen["whole_after"] = bytes(
            primary._extent_cache.get_full(key)[1]) == bytes(model)
        seen["splices"] = (_total(cluster, "splice_in_place"),
                           _total(cluster, "splice_rebuilt"))
        seen["refused"] = _total(cluster, "splice_refused")

        # a reader's views across a splice: a sub-read reply built from
        # the stored shard, extents and whole, before the next write
        holder = cluster.osds[acting[0]]
        skey = (pool, "obj", 0)
        was = bytes(holder.store.read(skey)[0])
        extents = [(0, UNIT), (5 * UNIT, 2 * UNIT)]
        by_extent = holder._sub_read_reply(
            MECSubRead(pool_id=pool, oid="obj", shard=0, tid="t",
                       extents=extents), holder.store.read(skey))
        rebuilt0 = holder.perf.get("splice_rebuilt")
        data = payload(UNIT, seed=500)
        await c.put(pool, "obj", data, offset=0)  # stripe 0: chunk 0 of it
        model[0:UNIT] = data
        seen["an_extent_reply_costs_the_store_nothing"] = \
            holder.perf.get("splice_rebuilt") == rebuilt0
        seen["extents_hold"] = [bytes(s) for s in by_extent.chunk.segments] \
            == [was[o:o + n] for o, n in extents]
        was = bytes(holder.store.read(skey)[0])
        whole = holder._sub_read_reply(
            MECSubRead(pool_id=pool, oid="obj", shard=0, tid="t"),
            holder.store.read(skey))
        seen["whole_reply_is_a_view_with_the_stored_crc"] = (
            live(whole.chunk) and whole.chunk_crc == shard_crc(was)
            == holder.store.read(skey)[1].chunk_crc)
        data = payload(UNIT, seed=501)
        await c.put(pool, "obj", data, offset=0)
        model[0:UNIT] = data
        seen["a_whole_view_costs_one_copy"] = \
            holder.perf.get("splice_rebuilt") == rebuilt0 + 1
        seen["whole_holds"] = bytes(whole.chunk) == was
        del whole
        await c.put(pool, "obj", data, offset=UNIT)
        model[UNIT:2 * UNIT] = data
        seen["and_then_none"] = \
            holder.perf.get("splice_rebuilt") == rebuilt0 + 1
        seen["read_back"] = await c.get(pool, "obj") == bytes(model)

        # a full write after splices hands its buffer over as before
        adopted = _total(cluster, "write_adopted_bytes")
        copied_w = _total(cluster, "write_copied_bytes")
        fresh = payload(64 * UNIT, seed=10)
        await c.put(pool, "obj", fresh)
        seen["full_write_adopts"] = (
            _total(cluster, "write_adopted_bytes") - adopted,
            _total(cluster, "write_copied_bytes") - copied_w)
        seen["full_write_reads_back"] = await c.get(pool, "obj") == fresh
        seen["cache_holds_the_payload_itself"] = \
            primary._extent_cache.get_full(key)[1] == fresh

        # a write that fails drops the entry
        for osd in cluster.osds.values():
            if osd is not primary:
                osd._apply_shard_write = lambda *a, **kw: False
        try:
            await c.put(pool, "obj", data, offset=0)
            seen["failed_write"] = "acked"
        except Exception as e:
            seen["failed_write"] = type(e).__name__
        seen["dropped"] = primary._extent_cache.get_full(key) is None
        await c.stop()
    finally:
        await cluster.stop()
    return seen


@pytest.fixture(scope="module", params=["wire", "fastpath"])
def cache_seen(request):
    seen = run(_cache_scenario(request.param == "fastpath"), timeout=120)
    seen["wire"] = request.param == "wire"
    return seen


def test_the_cached_arm_costs_the_stripe_from_an_objects_first(cache_seen):
    """Every offset write found the whole object cached and was counted
    so, and copied the segment twice: nothing of the rest of the object,
    the first time or later."""
    assert cache_seen["cached_arm"] == 12
    assert cache_seen["copied"] == [cache_seen["each"]] * 12


def test_get_full_is_the_whole_current_object_after_every_write(cache_seen):
    assert cache_seen["get_full_is_the_model"] == [True] * 12
    assert cache_seen["whole_after"]
    assert cache_seen["read_back"] and cache_seen["refused"] == 0


def test_splices_land_in_place_after_a_shards_first(cache_seen):
    in_place, rebuilt = cache_seen["splices"]
    assert in_place + rebuilt == 12 * 3
    # the 3 shards' first splice copied them; nothing after
    assert rebuilt == 3


@pytest.mark.parametrize("what", [
    "an_extent_reply_costs_the_store_nothing", "extents_hold",
    "whole_reply_is_a_view_with_the_stored_crc",
    "a_whole_view_costs_one_copy", "whole_holds", "and_then_none"])
def test_a_readers_view_reads_what_it_read(cache_seen, what):
    """The rule: a sub-read reply's extents of a shard the store writes
    in place are copies (the splice the read was made for finds no view
    out); a whole-shard reply is a view, with the stored crc as its wire
    crc, and a splice while it is alive copies the shard first."""
    assert cache_seen[what] is True


def test_a_full_write_after_splices_adopts_its_buffer_as_before(cache_seen):
    adopted, copied = cache_seen["full_write_adopts"]
    assert copied == 0 and adopted == 64 * UNIT
    assert cache_seen["adopted_by_the_put"] == 64 * UNIT
    assert cache_seen["full_write_reads_back"]
    assert cache_seen["cache_holds_the_payload_itself"]


def test_a_failed_write_drops_the_cached_object(cache_seen):
    assert cache_seen["failed_write"] != "acked"
    assert cache_seen["dropped"]


# -- the cache by itself ------------------------------------------------------


def test_patch_full_splits_the_runs_and_copies_nothing():
    cache = ExtentCache()
    key = (1, "o")
    wire = memoryview(np.frombuffer(b"0123456789", dtype=np.uint8)
                      .copy()).toreadonly()
    assert cache.put_full(key, 5, wire) is True
    assert cache.patch_full(key, 4, 6, 0, b"x") is False  # not that version
    assert cache.patch_full((1, "other"), 5, 6, 0, b"x") is False
    assert cache.patch_full(key, 5, 6, 2, b"ab") is True
    ent = cache._entries[key]
    assert [(s, bytes(b)) for s, b in ent.extents] == \
        [(0, b"01"), (2, b"ab"), (4, b"456789")]
    assert ent.extents[0][1].obj is wire.obj  # views of the payload
    assert ent.full and ent.size == 10 and ent.version == 6
    assert cache.get_whole(key, 1, 4) == (6, b"1ab4", 10)  # across runs
    assert cache.get_whole(key, 8, 4) == (6, b"89", 10)  # cut at the end
    assert cache.get_whole(key, 12, 4) == (6, b"", 10)
    assert cache.get_range(key, 0, 12) is not None  # full: the short tail
    # past the end: zeros between, the object grows
    assert cache.patch_full(key, 6, 7, 12, b"cd") is True
    assert cache.get_whole(key, 8, 8) == (7, b"89\x00\x00cd", 14)
    # over several runs at once, and the same extent again
    assert cache.patch_full(key, 7, 8, 1, b"WXYZ") is True
    assert cache.patch_full(key, 8, 9, 1, b"wxyz") is True
    assert [s for s, _b in cache._entries[key].extents] == [0, 1, 5, 10, 12]
    version, full = cache.get_full(key)  # joined for whoever wants it
    assert (version, full) == (9, b"0wxyz56789\x00\x00cd")
    assert len(cache._entries[key].extents) == 1 and cache._entries[key].full
    assert bytes(wire) == b"0123456789"  # the payload is never written
    cache.put_extent(key, 9, 2, b"--")
    assert bytes(cache.get_full(key)[1]) == b"0w--z56789\x00\x00cd"
    cache.put_extent(key, 3, 0, b"stale")  # older: ignored
    assert cache.get_full(key)[0] == 9
    # not whole: nothing to patch, and no whole range to give
    cache.put_extent((1, "part"), 2, 0, b"abcd")
    assert cache.patch_full((1, "part"), 2, 3, 0, b"x") is False
    assert cache.get_whole((1, "part"), 0, 2) is None
    assert cache.get_range((1, "part"), 1, 2) == (2, b"bc", 0)
    cache.put_extent((1, "part"), 2, 4, b"efgh")  # touching runs join
    assert cache.get_range((1, "part"), 2, 4) == (2, b"cdef", 0)
    assert cache.get_range((1, "part"), 6, 4) is None  # runs out
