"""BlueStore under a power cut: a file layer that keeps, for every file of a
store, the image its last sync covered and drops everything written after
it; seeded transaction streams (big writes, deferred writes, overwrites,
deletes, writes at an offset, omap, xattrs) applied to a BlueStore and to
the plain reference `benchmarks/references/durable_store.py`, cut before
every write, sync and rename the store makes; the store reopened from
what the cut left has to equal the reference: every transaction whose
commit was reported is there, the one in flight wholly there or wholly
absent.  The same stream on a store that does not sync its block file
before the KV batch (the order before PR 46) has to fail the case.
"""

import asyncio
import os
import shutil
import threading
from functools import partial

import numpy as np
import pytest

from benchmarks.references.durable_store import DurableStore
from ceph_tpu.rados.bluestore import BS_PERF, BlueStore, EIOError
from ceph_tpu.rados.kv import SyncedFile, WalDB
from ceph_tpu.rados.store import ShardMeta, Transaction

CONF = {"bluestore_prefer_deferred_size": 4096}
PGMETA = (1, "pgmeta_3", -1)


class PowerCut(Exception):
    pass


class CrashFS:
    """The `files` of a store under test: opens `_File`s that report every
    write, sync and rename here.  `durable` holds each file's image at its
    last sync (what was on disk when it was first opened counts);
    `cut_at=n` raises PowerCut in place of the n-th event; `crash(dst)`
    writes the images out, with `torn` half of each file's unsynced
    appended tail as well (a record the disk had begun)."""

    def __init__(self, root, cut_at=None):
        self.root = str(root)
        self.cut_at = cut_at
        self.events = []
        self.durable = {}

    def __call__(self, path, mode):
        if path not in self.durable and os.path.exists(path):
            with open(path, "rb") as f:
                self.durable[path] = f.read()
        return _File(self, path, mode)

    def tick(self, kind, path):
        self.events.append((kind, os.path.basename(path)))
        if self.cut_at is not None and len(self.events) == self.cut_at:
            raise PowerCut(f"before event {self.cut_at}: {self.events[-1]}")

    def replace(self, src, dst):
        self.tick("replace", dst)
        os.replace(src, dst)
        if src in self.durable:
            self.durable[dst] = self.durable.pop(src)

    def crash(self, dst, torn=False):
        for path, image in self.durable.items():
            if torn and os.path.exists(path):
                with open(path, "rb") as f:
                    now = f.read()
                if now.startswith(image):
                    image = now[:len(image) + (len(now) - len(image)) // 2]
            out = os.path.join(str(dst), os.path.relpath(path, self.root))
            os.makedirs(os.path.dirname(out), exist_ok=True)
            with open(out, "wb") as f:
                f.write(image)


class _File(SyncedFile):
    def __init__(self, fs, path, mode):
        super().__init__(path, mode)
        self.fs = fs

    def write(self, data):
        self.fs.tick("write", self.path)
        super().write(data)

    def pwrite(self, off, data):
        self.fs.tick("write", self.path)
        super().pwrite(off, data)

    def sync(self, data_only=False):
        self.fs.tick("sync", self.path)
        super().sync(data_only)
        with open(self.path, "rb") as f:
            self.fs.durable[self.path] = f.read()


class NoBlockSync(BlueStore):
    """The commit order before PR 46: extents written, never synced, then
    the KV batch that names them."""

    def _sync_block(self):
        self._block_dirty = False


# -- streams -----------------------------------------------------------------

def stream(seed, n=28):
    """Transactions as the reference takes them; metas are tuples
    (version, object_size, chunk_crc)."""
    rng = np.random.default_rng(seed)
    live, out = set(), []
    for v in range(1, n + 1):
        key = (1, f"o{int(rng.integers(6))}", int(rng.integers(2)))
        kind = rng.choice(["big", "small", "small", "delete", "write_at",
                           "omap", "setattr", "rmattr"])
        meta = (v, int(rng.integers(1 << 20)), int(rng.integers(1 << 32)))
        if kind in ("big", "small") or (kind in ("setattr", "rmattr")
                                        and key not in live):
            size = int(rng.integers(5000, 60000) if kind == "big"
                       else rng.integers(1, 4096))
            ops = [("write", key, rng.bytes(size), meta),
                   ("omap_set", PGMETA, {f"log.{v}": rng.bytes(24)})]
            if v % 5 == 0:
                ops.append(("omap_rm", PGMETA, [f"log.{v - 4}"]))
            live.add(key)
        elif kind == "delete":
            ops = [("delete", key),
                   ("omap_set", PGMETA, {f"log.{v}": b"delete"})]
            live.discard(key)
        elif kind == "write_at":
            prev = (key[0], key[1], key[2] + 1000)
            off = int(rng.integers(0, 20000))
            ops = [("write_at", key, off, rng.bytes(int(rng.integers(
                1, 9000))), int(rng.integers(0, 30000)), meta, prev)]
            live.add(key)
        elif kind == "omap":
            ops = [("omap_set", key, {"a": rng.bytes(8), "b": b"x"}),
                   ("omap_rm", key, ["b"])]
        elif kind == "setattr":
            ops = [("setattr", key, "hinfo_key", rng.bytes(40))]
        else:
            ops = [("rmattr", key, "hinfo_key")]
        out.append(ops)
    return out


def apply(store, ops, on_commit=None):
    """A transaction of one `setattr` or `rmattr` is the store's call of
    that name (committed when it returns); a `setattr` among other
    operations is set in their transaction."""
    if ops[0][0] in ("setattr", "rmattr"):
        getattr(store, ops[0][0])(*ops[0][1:])
        return on_commit() if on_commit is not None else None
    txn = Transaction()
    for op in ops:
        kind = op[0]
        if kind == "write":
            txn.write(op[1], op[2], ShardMeta(*op[3]))
        elif kind == "write_at":
            _, key, off, data, size, meta, prev = op
            txn.write_at(key, off, data, size, ShardMeta(*meta), prev)
        elif kind == "delete":
            txn.delete(op[1])
        elif kind == "omap_set":
            txn.omap_set(op[1], op[2])
        elif kind == "setattr":
            txn.setattr(*op[1:])
        else:
            txn.omap_rm(op[1], op[2])
    store.queue_transaction(txn, on_commit)


def with_hinfo(txns):
    """Every write carries the shard's hinfo record in its transaction,
    as `OSD._apply_shard_write` sends it."""
    return [ops + [("setattr", op[1], "hinfo_key",
                    bytes([op[-2 if op[0] == "write_at" else -1][0] % 256])
                    * 40) for op in ops if op[0] in ("write", "write_at")]
            for ops in txns]


def state_of(store, omap_keys):
    """(objects, xattrs, omap) as DurableStore's State.as_dicts gives
    them; an object whose read fails its checksum reads "EIO"."""
    objects, xattrs, omap = {}, {}, {}
    for pid in store.list_pools():
        for oid, shard in store.list_objects(pid):
            key = (pid, oid, shard)
            try:
                data, meta = store.read(key)
                objects[key] = (bytes(data), (meta.version, meta.object_size,
                                              meta.chunk_crc))
            except EIOError:
                objects[key] = "EIO"
            if store.getattrs(key):
                xattrs[key] = store.getattrs(key)
    for key in omap_keys:
        if store.omap_get(key):
            omap[key] = store.omap_get(key)
    return objects, xattrs, omap


def omap_keys_of(txns):
    return {op[1] for ops in txns for op in ops
            if op[0] in ("omap_set", "omap_rm")}


def run(tmp, txns, cut_at=None, torn=False, cls=BlueStore, compact=False):
    """The stream on a fresh store under a CrashFS, cut before event
    `cut_at`; returns (events made, the reopened store's state, the
    reference)."""
    live, left = os.path.join(tmp, "live"), os.path.join(tmp, "left")
    for d in (live, left):
        shutil.rmtree(d, ignore_errors=True)
    fs, ref = CrashFS(live, cut_at), DurableStore()
    path = os.path.join(live, "osd.0")
    db = WalDB(os.path.join(path, "db"), compact_bytes=3000, perf=BS_PERF,
               files=fs) if compact else None
    store = cls(path, dict(CONF), db=db, files=fs)
    try:
        for ops in txns:
            index = ref.submit(ops)
            apply(store, ops)
            ref.commit_reported(index)
    except PowerCut:
        pass
    fs.crash(left, torn)
    store.abandon()
    again = BlueStore(os.path.join(left, "osd.0"), dict(CONF))
    try:
        return fs.events, state_of(again, omap_keys_of(txns)), ref
    finally:
        again.abandon()


def admissible(state, ref):
    return any(state == s.as_dicts() for s in ref.admissible_after_crash())


WAVE = 3  # transactions handed over before the loop waits for them


def run_on_the_thread(tmp, txns, cut_at=None, torn=False):
    """As `run`, from a running loop with callbacks: the store's thread
    commits, up to WAVE transactions in flight, and the cut falls where
    the thread is (or on the loop, in a call that commits on its
    caller).  Returns (events, reopened state, reference, the threads
    that made events)."""
    live, left = os.path.join(tmp, "live"), os.path.join(tmp, "left")
    for d in (live, left):
        shutil.rmtree(d, ignore_errors=True)
    fs, ref = CrashFS(live, cut_at), DurableStore()
    path = os.path.join(live, "osd.0")
    store = BlueStore(path, dict(CONF), files=fs)
    threads = set()
    tick = fs.tick
    fs.tick = lambda kind, p: (threads.add(threading.current_thread().name),
                               tick(kind, p))[1]

    async def go():
        for i, ops in enumerate(txns):
            # several in flight: the reference's own `submit` allows one
            ref.log.append(list(ops))
            try:
                apply(store, ops, partial(ref.commit_reported, i))
            except (PowerCut, IOError):
                return
            if i % WAVE == WAVE - 1 or i == len(txns) - 1:
                while ref.reported <= i and store.failed is None:
                    await asyncio.sleep(0.001)
            if store.failed is not None:
                return

    asyncio.run(go())
    try:
        store._finish_done()  # what the thread committed before the cut
    except PowerCut:
        pass
    fs.crash(left, torn)
    store.abandon()
    assert not [t for t in threading.enumerate()
                if t.name.startswith("bluestore-commit")]
    again = BlueStore(os.path.join(left, "osd.0"), dict(CONF))
    try:
        return fs.events, state_of(again, omap_keys_of(txns)), ref, threads
    finally:
        again.abandon()


# -- the cases ---------------------------------------------------------------

@pytest.mark.parametrize("compact", [False, True],
                         ids=["plain", "compacting"])
@pytest.mark.parametrize("torn", [False, True], ids=["dropped", "torn"])
@pytest.mark.parametrize("seed", [4601, 4602, 4603])
def test_cut_before_every_event_reopens_to_the_reference(tmp_path, seed,
                                                         torn, compact):
    txns = stream(seed)
    events, state, ref = run(str(tmp_path), txns, compact=compact)
    assert state == ref.crash().as_dicts() == ref.now().as_dicts()
    assert {"write", "sync"} <= {kind for kind, _ in events}
    if compact:
        assert ("replace", "snapshot.db") in events
    for cut in range(1, len(events) + 1):
        _, state, ref = run(str(tmp_path), txns, cut, torn, compact=compact)
        assert admissible(state, ref), (
            f"cut before event {cut} {events[cut - 1]}: the reopened store "
            f"holds neither the {ref.reported} reported transactions nor "
            f"those and the one in flight")


@pytest.mark.parametrize("torn", [False, True], ids=["dropped", "torn"])
@pytest.mark.parametrize("seed", [4801, 4802])
def test_cut_inside_the_threads_sequence_reopens_to_the_reference(
        tmp_path, seed, torn):
    """The store's thread commits, transactions wait behind each other,
    and the power goes before every write and sync it makes: what is left
    is the transactions whose callback ran and, at most, the one the
    thread had in hand, whole; none of those queued behind it."""
    txns = with_hinfo(stream(seed))
    events, state, ref, threads = run_on_the_thread(str(tmp_path), txns)
    assert state == ref.crash().as_dicts() == ref.now().as_dicts()
    assert "bluestore-commit-osd.0" in threads and "MainThread" in threads
    assert any("hinfo_key" in attrs for attrs in state[1].values())
    for cut in range(1, len(events) + 1):
        _, state, ref, _ = run_on_the_thread(str(tmp_path), txns, cut, torn)
        assert ref.reported < len(ref.log)
        assert admissible(state, ref), (
            f"cut before event {cut} {events[cut - 1]}: the reopened store "
            f"holds neither the {ref.reported} reported transactions nor "
            f"those and the one in hand")


@pytest.mark.parametrize("cut,point,version", [
    (5, "before the block write", 1), (6, "before the block sync", 1),
    (7, "before the WAL write", 1), (8, "before the WAL sync", 1),
    (9, "after the WAL sync, before anybody is told", 2)])
def test_a_shard_and_its_hinfo_are_one_commit(tmp_path, cut, point,
                                              version):
    """The thread's five stops in a shard write's commit: at each the
    reopened store has the old shard with the old record or the new
    shard with the new one."""
    key = (1, "obj", 0)
    txns = with_hinfo([big_write(version=1),
                       big_write(size=30000, version=2),
                       big_write(key=(1, "next", 0))])
    events, state, ref, _ = run_on_the_thread(str(tmp_path), txns,
                                              cut_at=cut)
    objects, xattrs, _ = state
    assert objects[key][1][0] == version, point
    assert xattrs[key] == {"hinfo_key": bytes([version]) * 40}, point
    assert (1, "next", 0) not in objects
    assert admissible(state, ref)


@pytest.mark.parametrize("seed", [4601, 4602, 4603])
def test_without_the_block_sync_a_cut_loses_acknowledged_bytes(tmp_path,
                                                               seed):
    """The parent's `_write_extents` flushed to the page cache and let the
    WAL's sync make the onode durable: after a cut the onode is there
    and its bytes are not."""
    txns = stream(seed)
    events, _, _ = run(str(tmp_path), txns, cls=NoBlockSync)
    assert ("sync", "block") not in events
    broken = [cut for cut in range(1, len(events) + 1)
              if not admissible(*run(str(tmp_path), txns, cut,
                                     cls=NoBlockSync)[1:])]
    assert broken, "a store that never syncs its block file passed"


def big_write(key=(1, "obj", 0), size=20000, version=1):
    return [("write", key, bytes(range(256)) * (size // 256),
             (version, size, 7))]


def test_a_commit_is_block_write_block_sync_wal_write_wal_sync(tmp_path):
    events, state, ref = run(str(tmp_path), [big_write()])
    assert events == [("write", "block"), ("sync", "block"),
                      ("write", "wal.log"), ("sync", "wal.log")]
    assert state == ref.now().as_dicts()


@pytest.mark.parametrize("cut,point", [
    (2, "after the block write"), (3, "after the block sync"),
    (4, "after the WAL write, before its sync")])
@pytest.mark.parametrize("torn", [False, True], ids=["dropped", "torn"])
def test_a_cut_inside_a_commit_leaves_the_old_object(tmp_path, cut, point,
                                                     torn):
    old, new = big_write(version=1), big_write(size=30000, version=2)
    _, state, ref = run(str(tmp_path), [old, new], cut_at=4 + cut, torn=torn)
    assert ref.reported == 1
    assert state == ref.crash().as_dicts(), point
    assert state[0][(1, "obj", 0)][1][0] == 1


def test_a_cut_after_the_wal_sync_leaves_the_new_object(tmp_path):
    old, new = big_write(version=1), big_write(size=30000, version=2)
    more = big_write(key=(1, "next", 0))
    _, state, ref = run(str(tmp_path), [old, new, more], cut_at=9)
    assert ref.reported == 2
    assert state == ref.crash().as_dicts()
    assert state[0][(1, "obj", 0)][1][0] == 2


def test_deferred_payloads_ride_the_wal_and_are_replayed(tmp_path):
    """16 small writes: none syncs the block file before its commit; the
    16th drains the batch (block write, block sync, then the batch that
    takes the payloads out of the WAL); a cut anywhere leaves them all."""
    txns = [[("write", (1, f"s{i}", 0), bytes([i]) * 100, (i + 1, 100, i))]
            for i in range(16)]
    events, state, ref = run(str(tmp_path), txns)
    assert events[:30] == [("write", "wal.log"), ("sync", "wal.log")] * 15
    assert events.count(("sync", "block")) == 1
    assert events.index(("sync", "block")) < len(events) - 2
    assert events[-2:] == [("write", "wal.log"), ("sync", "wal.log")]
    for cut in range(31, len(events) + 1):
        _, state, ref = run(str(tmp_path), txns, cut)
        assert admissible(state, ref), events[cut - 1]


def test_the_reference_keeps_what_was_reported_and_no_more():
    ref = DurableStore()
    ref.commit([("write", "a", b"one", 1)])
    ref.submit([("write", "a", b"two", 2), ("omap_set", "a", {"k": b"v"})])
    assert ref.now().as_dicts()[0] == {"a": (b"two", 2)}
    assert ref.crash().as_dicts() == ({"a": (b"one", 1)}, {}, {})
    assert ref.crash(in_flight=True).as_dicts() == (
        {"a": (b"two", 2)}, {}, {"a": {"k": b"v"}})
    assert len(ref.admissible_after_crash()) == 2
    with pytest.raises(RuntimeError):
        ref.submit([("delete", "a")])


def test_the_reference_imports_nothing_of_the_program():
    import benchmarks.references.durable_store as mod

    with open(mod.__file__) as f:
        source = f.read()
    assert "ceph_tpu" not in source.replace("ceph_tpu/rados", "")
    assert "import" not in source.split('"""')[2].replace(
        "from __future__ import annotations", "").replace(
        "from typing import Dict, List, Tuple", "")


# -- the store's own books ---------------------------------------------------

def counters():
    dump = BS_PERF.dump()
    return {k: (v["sum"] if isinstance(v, dict) else v)
            for k, v in dump.items()}


def test_counters_tell_a_big_write_from_a_deferred_one(tmp_path):
    store = BlueStore(str(tmp_path / "osd"), dict(CONF))
    before = counters()
    apply(store, big_write(size=20000))
    mid = counters()
    apply(store, [("write", (1, "small", 0), b"x" * 100, (1, 100, 0))])
    after = counters()
    store.close()

    def moved(a, b, *keys):
        return [b[k] - a[k] for k in keys]

    keys = ("txns", "big_writes", "deferred_writes", "block_syncs",
            "wal_syncs", "block_write_bytes", "deferred_bytes",
            "csum_bytes", "alloc_extents", "commit_under_sync",
            "commit_unsynced")
    assert moved(before, mid, *keys) == [1, 1, 0, 1, 1, 19968, 0, 19968, 1,
                                         1, 0]
    assert moved(mid, after, *keys) == [1, 0, 1, 0, 1, 0, 100, 100, 1, 1, 0]
    assert after["wal_bytes"] > mid["wal_bytes"] > before["wal_bytes"]
    assert after["sync_s"] > before["sync_s"]
    assert after["loop_sync_s"] == before["loop_sync_s"]  # no loop here
    assert after["commit_lat"] > before["commit_lat"]


def test_a_commit_ahead_of_its_block_sync_is_counted(tmp_path):
    store = NoBlockSync(str(tmp_path / "osd"), dict(CONF))
    store._sync_block = lambda: None  # the extents stay unsynced
    before = counters()
    apply(store, big_write())
    assert counters()["commit_unsynced"] - before["commit_unsynced"] == 1
    store.abandon()


def test_stat_reads_no_byte(tmp_path, monkeypatch):
    store = BlueStore(str(tmp_path / "osd"), dict(CONF))
    apply(store, big_write(size=20000, version=9))
    apply(store, [("write", (1, "small", 0), b"x" * 100, (3, 100, 0))])
    monkeypatch.setattr(store, "_read_extents", None)
    monkeypatch.setattr(store.db, "get", None)
    assert store.stat((1, "obj", 0)) == (19968, ShardMeta(9, 20000, 7))
    assert store.stat((1, "small", 0)) == (100, ShardMeta(3, 100, 0))
    assert store.stat((1, "nothing", 0)) is None
    store.abandon()


def test_synced_lengths_are_what_a_sync_covered(tmp_path):
    path = str(tmp_path / "osd")
    store = BlueStore(path, dict(CONF))
    apply(store, big_write(size=20000))
    lengths = store.synced_lengths()
    assert lengths["block"] == os.path.getsize(os.path.join(path, "block"))
    assert lengths["db/wal.log"] == os.path.getsize(
        os.path.join(path, "db", "wal.log"))
    store._block.pwrite(lengths["block"], b"unsynced")
    assert store.synced_lengths()["block"] == lengths["block"]
    assert os.path.getsize(os.path.join(path, "block")) \
        == lengths["block"] + 8
    store.db.compact()
    assert "db/snapshot.db" in store.synced_lengths()
    assert store.synced_lengths()["db/wal.log"] == 0
    store.abandon()


def test_abandon_leaves_deferred_payloads_to_the_next_open(tmp_path):
    path = str(tmp_path / "osd")
    store = BlueStore(path, dict(CONF))
    apply(store, [("write", (1, "small", 0), b"x" * 100, (3, 100, 0))])
    assert store._onodes[(1, "small", 0)].deferred
    store.abandon()
    again = BlueStore(path, dict(CONF))
    assert not again._onodes[(1, "small", 0)].deferred
    assert again.read((1, "small", 0))[0] == b"x" * 100
    again.close()
