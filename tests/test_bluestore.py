"""BlueStore-lite + KeyValueDB tests: WAL commit/recovery, checksums,
allocator, deferred writes, xattr/omap, EIO injection end-to-end
(reference src/os/bluestore/, src/kv/)."""

import asyncio
import os
import pickle

import pytest

from ceph_tpu.rados.bluestore import Allocator, BlueStore, EIOError
from ceph_tpu.rados.bluestore import _zstandard

# zstd rides the optional `zstandard` package (gated in bluestore like
# auth gates `cryptography`): hosts without it run the whole suite minus
# the zstd-exercising cases
needs_zstd = pytest.mark.skipif(
    _zstandard is None, reason="zstandard package not installed")
from ceph_tpu.rados.kv import MemDB, WalDB, WriteBatch
from ceph_tpu.rados.store import ShardMeta, Transaction


class TestWalDB:
    def test_commit_survives_reopen(self, tmp_path):
        db = WalDB(str(tmp_path / "db"))
        b = WriteBatch()
        b.set("O", "k1", b"v1")
        b.set("M", "k2", b"v2")
        db.submit(b)
        db.close()
        db2 = WalDB(str(tmp_path / "db"))
        assert db2.get("O", "k1") == b"v1"
        assert db2.get("M", "k2") == b"v2"

    def test_torn_tail_discarded(self, tmp_path):
        db = WalDB(str(tmp_path / "db"))
        b = WriteBatch()
        b.set("O", "good", b"committed")
        db.submit(b)
        db.close()
        # simulate a crash mid-append: garbage tail bytes
        with open(str(tmp_path / "db" / "wal.log"), "ab") as f:
            f.write(b"\x40\x00\x00\x00\x99\x99\x99\x99partial-record")
        db2 = WalDB(str(tmp_path / "db"))
        assert db2.get("O", "good") == b"committed"
        # a commit AFTER torn-tail recovery must survive the next reopen
        # (recovery truncates the garbage so appends chain correctly)
        b2 = WriteBatch()
        b2.set("O", "after", b"x")
        db2.submit(b2)
        db2.close()
        db3 = WalDB(str(tmp_path / "db"))
        assert db3.get("O", "after") == b"x"
        assert db3.get("O", "good") == b"committed"

    def test_compaction_preserves_state(self, tmp_path):
        db = WalDB(str(tmp_path / "db"), compact_bytes=1024)
        for i in range(100):
            b = WriteBatch()
            b.set("O", f"k{i}", b"v" * 50)
            db.submit(b)
        assert os.path.exists(str(tmp_path / "db" / "snapshot.db"))
        db.close()
        db2 = WalDB(str(tmp_path / "db"))
        assert db2.get("O", "k99") == b"v" * 50
        assert len(list(db2.iterate("O"))) == 100

    def test_rm_and_rm_prefix(self):
        db = MemDB()
        b = WriteBatch()
        b.set("A", "x", b"1")
        b.set("A", "y", b"2")
        b.set("B", "z", b"3")
        db.submit(b)
        b2 = WriteBatch()
        b2.rm("A", "x")
        b2.rm_prefix("B")
        db.submit(b2)
        assert db.get("A", "x") is None
        assert db.get("A", "y") == b"2"
        assert list(db.iterate("B")) == []


class TestAllocator:
    def test_alloc_free_merge(self):
        a = Allocator(1000)
        o1 = a.allocate(100)
        o2 = a.allocate(200)
        assert o1 != o2
        a.release(o1, 100)
        a.release(o2, 200)
        assert a.free == [(0, 1000)]  # merged back

    def test_grows_when_exhausted(self):
        a = Allocator(100)
        a.allocate(100)
        off = a.allocate(500)
        assert off >= 100
        assert a.size >= 600

    def test_reserve_carves(self):
        a = Allocator(1000)
        a.reserve(100, 200)
        assert (0, 100) in a.free
        assert any(o == 300 for o, _ in a.free)


class TestBlueStore:
    def _txn(self, key, data, version=1):
        t = Transaction()
        t.write(key, data, ShardMeta(version=version, object_size=len(data)))
        return t

    def test_roundtrip_ram(self):
        bs = BlueStore()
        key = (1, "obj", 0)
        bs.queue_transaction(self._txn(key, b"hello world"))
        data, meta = bs.read(key)
        assert data == b"hello world"
        assert meta.version == 1
        assert list(bs.list_objects(1)) == [("obj", 0)]

    def test_commit_callback(self):
        bs = BlueStore()
        fired = []
        bs.queue_transaction(self._txn((1, "o", 0), b"x"),
                             on_commit=lambda: fired.append(1))
        assert fired == [1]

    def test_persistence_small_and_large(self, tmp_path):
        path = str(tmp_path / "osd0")
        bs = BlueStore(path, {"bluestore_prefer_deferred_size": 1024})
        small = (1, "small", 0)
        large = (1, "large", 1)
        bs.queue_transaction(self._txn(small, b"s" * 100))  # deferred
        bs.queue_transaction(self._txn(large, b"L" * 100_000))  # direct
        bs.close()
        bs2 = BlueStore(path, {"bluestore_prefer_deferred_size": 1024})
        assert bs2.read(small)[0] == b"s" * 100
        assert bs2.read(large)[0] == b"L" * 100_000
        bs2.close()

    def test_deferred_replay_after_crash_before_flush(self, tmp_path):
        path = str(tmp_path / "osd1")
        bs = BlueStore(path, {"bluestore_prefer_deferred_size": 4096})
        key = (1, "d", 0)
        # commit the deferred write but simulate dying before the block
        # flush: rewrite the onode as still-deferred and zero the block file
        bs.queue_transaction(self._txn(key, b"deferred-payload"))
        from ceph_tpu.rados.bluestore import PREFIX_DEFERRED, PREFIX_OBJ, _okey

        onode = bs._onodes[key]
        onode.deferred = True
        b = WriteBatch()
        b.set(PREFIX_OBJ, _okey(key), pickle.dumps(onode, protocol=5))
        b.set(PREFIX_DEFERRED, _okey(key), b"deferred-payload")
        bs.db.submit(b)
        with open(os.path.join(path, "block"), "r+b") as f:
            f.truncate(0)  # the flush never happened
        bs.close()
        bs2 = BlueStore(path, {"bluestore_prefer_deferred_size": 4096})
        data, _ = bs2.read(key)
        assert data == b"deferred-payload"
        assert not bs2._onodes[key].deferred  # replay completed it
        bs2.close()

    def test_checksum_detects_bitrot(self, tmp_path):
        path = str(tmp_path / "osd2")
        bs = BlueStore(path, {"bluestore_prefer_deferred_size": 0})
        key = (1, "rot", 0)
        bs.queue_transaction(self._txn(key, b"A" * 8192))
        onode = bs._onodes[key]
        off = onode.extents[0][0]
        # flip a byte on "disk"
        bs._block.seek(off + 100)
        bs._block.write(b"Z")
        bs._block.flush()
        with pytest.raises(EIOError):
            bs.read(key)
        bs.close()

    def test_injected_read_err(self):
        bs = BlueStore(conf={"bluestore_debug_inject_read_err": True})
        key = (1, "x", 0)
        bs.queue_transaction(self._txn(key, b"data"))
        with pytest.raises(EIOError):
            bs.read(key)

    def test_xattr_and_omap(self, tmp_path):
        path = str(tmp_path / "osd3")
        bs = BlueStore(path)
        key = (2, "o", 0)
        bs.queue_transaction(self._txn(key, b"body"))
        bs.setattr(key, "hinfo_key", b"\x01\x02")
        bs.omap_set(key, {"0000000001": b"log-entry-1",
                          "0000000002": b"log-entry-2"})
        bs.close()
        bs2 = BlueStore(path)
        assert bs2.getattr(key, "hinfo_key") == b"\x01\x02"
        omap = bs2.omap_get(key)
        assert omap["0000000002"] == b"log-entry-2"
        bs2.omap_rm(key, ["0000000001"])
        assert "0000000001" not in bs2.omap_get(key)
        # delete clears omap too
        t = Transaction()
        t.delete(key)
        bs2.queue_transaction(t)
        assert bs2.omap_get(key) == {}
        assert bs2.read(key) is None
        bs2.close()

    def test_overwrite_frees_extents(self):
        bs = BlueStore(conf={"bluestore_prefer_deferred_size": 0})
        key = (1, "ow", 0)
        bs.queue_transaction(self._txn(key, b"1" * 10_000, version=1))
        used1 = bs.statfs()["used"]
        bs.queue_transaction(self._txn(key, b"2" * 10_000, version=2))
        assert bs.read(key)[0] == b"2" * 10_000
        assert bs.statfs()["used"] == used1  # old extents recycled

    def test_statfs(self):
        bs = BlueStore()
        bs.queue_transaction(self._txn((1, "a", 0), b"x" * 1000))
        st = bs.statfs()
        assert st["num_objects"] == 1
        assert st["used"] >= 1000


class TestEIOEndToEnd:
    def test_degraded_read_on_shard_eio(self):
        """A shard hitting EIO must not fail the client read: the primary
        reconstructs from the remaining shards (test-erasure-eio.sh role)."""

        async def go():
            import os as _os

            from ceph_tpu.rados.vstart import Cluster

            cluster = Cluster(n_osds=4, conf={"osd_auto_repair": False})
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("eio", profile={
                    "plugin": "jerasure", "technique": "reed_sol_van",
                    "k": "2", "m": "1"})
                blob = _os.urandom(40_000)
                await c.put(pool, "obj", blob)
                # poison ONE osd's store with read errors
                victim = next(iter(cluster.osds.values()))
                victim.store.__class__ = _PoisonedMemStore
                assert await c.get(pool, "obj") == blob
            finally:
                await cluster.stop()

        asyncio.run(go())


from ceph_tpu.rados.store import MemStore


class _PoisonedMemStore(MemStore):
    """MemStore whose reads always raise EIO (class-swapped in the test)."""

    def read(self, key):
        raise EIOError(f"injected EIO on {key}")


class TestCompression:
    """Per-pool blob compression + csum selection (VERDICT r4 #7;
    reference BlueStore _do_write compression, csum handling)."""

    def _store(self, tmp_path=None, conf=None):
        return BlueStore(str(tmp_path) if tmp_path else None, conf or {})

    def test_aggressive_mode_compresses_and_roundtrips(self):
        bs = self._store(conf={"bluestore_compression_mode": "aggressive"})
        blob = b"compressible " * 8000  # ~100 KiB, very redundant
        txn = Transaction()
        txn.write((1, "o", 0), blob, ShardMeta(object_size=len(blob)))
        bs.queue_transaction(txn)
        onode = bs._onodes[(1, "o", 0)]
        assert onode.compression == "zlib"
        assert onode.raw_len == len(blob)
        stored = sum(n for _, n in onode.extents)
        assert stored < len(blob) * 0.5
        data, meta = bs.read((1, "o", 0))
        assert data == blob

    def test_required_ratio_keeps_incompressible_raw(self):
        bs = self._store(conf={"bluestore_compression_mode": "aggressive"})
        blob = os.urandom(64 * 1024)  # incompressible
        txn = Transaction()
        txn.write((1, "r", 0), blob, ShardMeta())
        bs.queue_transaction(txn)
        onode = bs._onodes[(1, "r", 0)]
        assert onode.compression is None
        assert bs.read((1, "r", 0))[0] == blob

    def test_passive_mode_stores_raw_without_hints(self):
        """passive compresses only on a client compressible-hint; no
        hint plumbing exists, so passive must store raw (treating it
        as aggressive would invert its meaning)."""
        bs = self._store(conf={"bluestore_compression_mode": "passive"})
        blob = b"very compressible " * 8000
        txn = Transaction()
        txn.write((1, "p", 0), blob, ShardMeta())
        bs.queue_transaction(txn)
        assert bs._onodes[(1, "p", 0)].compression is None
        assert bs.read((1, "p", 0))[0] == blob

    @needs_zstd
    def test_algorithms_zstd_lzma(self):
        for algo in ("zstd", "lzma"):
            bs = self._store(conf={
                "bluestore_compression_mode": "aggressive",
                "bluestore_compression_algorithm": algo})
            blob = (b"pattern-%d " % 7) * 9000
            txn = Transaction()
            txn.write((1, algo, 0), blob, ShardMeta())
            bs.queue_transaction(txn)
            assert bs._onodes[(1, algo, 0)].compression == algo
            assert bs.read((1, algo, 0))[0] == blob

    @needs_zstd
    def test_per_pool_opts_override_conf(self):
        bs = self._store()  # global mode: none
        bs.set_pool_opts(7, {"compression_mode": "aggressive",
                             "compression_algorithm": "zstd"})
        blob = b"tenant data " * 8000
        txn = Transaction()
        txn.write((7, "a", 0), blob, ShardMeta())
        txn.write((8, "b", 0), blob, ShardMeta())  # pool 8: no opts
        bs.queue_transaction(txn)
        assert bs._onodes[(7, "a", 0)].compression == "zstd"
        assert bs._onodes[(8, "b", 0)].compression is None
        assert bs.read((7, "a", 0))[0] == blob

    def test_restart_recovery_over_compressed_blobs(self, tmp_path):
        """The r4 done-bar: compressed blobs survive close + reopen,
        including one still DEFERRED (in the KV WAL) at shutdown."""
        conf = {"bluestore_compression_mode": "aggressive",
                "bluestore_prefer_deferred_size": 32768}
        bs = BlueStore(str(tmp_path), conf)
        big = b"large compressible block " * 40000   # ~1 MiB raw
        small = b"tiny deferred payload " * 100      # compresses < 32 KiB
        txn = Transaction()
        txn.write((1, "big", 0), big, ShardMeta(object_size=len(big)))
        txn.write((1, "small", 0), small, ShardMeta())
        bs.queue_transaction(txn)
        assert bs._onodes[(1, "big", 0)].compression == "zlib"
        assert bs._onodes[(1, "small", 0)].deferred  # not yet flushed
        bs.db.close()            # simulate crash: skip the batch flush
        bs._block.close()
        bs2 = BlueStore(str(tmp_path), conf)
        assert bs2.read((1, "big", 0))[0] == big
        assert bs2.read((1, "small", 0))[0] == small
        assert not bs2._onodes[(1, "small", 0)].deferred  # replayed
        bs2.close()

    def test_corrupted_compressed_extent_fails_csum(self, tmp_path):
        """A flipped byte inside a compressed extent raises EIO at the
        csum check (before the decompressor) — the shard-level error
        scrub turns into a repair."""
        bs = BlueStore(str(tmp_path),
                       {"bluestore_compression_mode": "aggressive",
                        "bluestore_prefer_deferred_size": 0})
        blob = b"scrubbed content " * 9000
        txn = Transaction()
        txn.write((1, "c", 0), blob, ShardMeta())
        bs.queue_transaction(txn)
        onode = bs._onodes[(1, "c", 0)]
        assert onode.compression == "zlib"
        off, length = onode.extents[0]
        with open(os.path.join(str(tmp_path), "block"), "r+b") as f:
            f.seek(off + length // 2)
            orig = f.read(1)
            f.seek(off + length // 2)
            f.write(bytes([orig[0] ^ 0xFF]))
        with pytest.raises(EIOError, match="checksum mismatch"):
            bs.read((1, "c", 0))
        bs.close()

    def test_csum_type_selection(self, tmp_path):
        # zlib crc selected at write: verify_any still reads it
        bs = BlueStore(None, {"bluestore_csum_type": "zlib"})
        txn = Transaction()
        txn.write((1, "z", 0), b"x" * 100, ShardMeta())
        bs.queue_transaction(txn)
        import zlib as _z
        assert bs._onodes[(1, "z", 0)].csums[0] == \
            _z.crc32(b"x" * 100) & 0xFFFFFFFF
        assert bs.read((1, "z", 0))[0] == b"x" * 100
        # none: no verification, bitrot goes undetected BY DESIGN
        bs2 = BlueStore(str(tmp_path), {"bluestore_csum_type": "none",
                                        "bluestore_prefer_deferred_size": 0})
        txn = Transaction()
        txn.write((1, "n", 0), os.urandom(4096), ShardMeta())
        bs2.queue_transaction(txn)
        assert bs2._onodes[(1, "n", 0)].csums == [0]
        off, _ = bs2._onodes[(1, "n", 0)].extents[0]
        with open(os.path.join(str(tmp_path), "block"), "r+b") as f:
            f.seek(off)
            f.write(b"\x00\x00")
        bs2.read((1, "n", 0))  # no EIO: csum_type none skips the check
        bs2.close()


class TestCompressionClusterPath:
    @needs_zstd
    def test_pool_opts_flow_map_to_store_and_scrub_repairs(self, tmp_path):
        """End to end: `pool set compression_mode` rides the OSDMap into
        every OSD's BlueStore; a corrupted compressed shard EIOs and
        deep scrub REPAIRS it from the surviving shards."""
        import numpy as np

        from ceph_tpu.rados.vstart import Cluster

        async def go():
            cluster = Cluster(n_osds=4, conf={
                "osd_auto_repair": False,
                # straight-to-block writes: the corruption below targets
                # the block file, not the KV WAL's deferred payloads
                "bluestore_prefer_deferred_size": 0,
            }, data_dir=str(tmp_path))
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("comp", profile={
                    "plugin": "jerasure", "technique": "reed_sol_van",
                    "k": "2", "m": "1"})
                await c.pool_set(pool, "compression_mode", "aggressive")
                await c.pool_set(pool, "compression_algorithm", "zstd")
                # wait for every OSD to see the opts epoch
                for _ in range(100):
                    if all(o.store.pool_opts.get(pool, {}).get(
                            "compression_mode") == "aggressive"
                           for o in cluster.osds.values()):
                        break
                    await asyncio.sleep(0.05)
                blob = b"cluster compressible payload " * 30000
                await c.put(pool, "obj", blob)
                # at least one stored shard is compressed
                comp_osds = [
                    o for o in cluster.osds.values()
                    for key in [(pool, "obj", s) for s in range(3)]
                    if key in o.store._onodes
                    and o.store._onodes[key].compression == "zstd"]
                assert comp_osds, "no shard stored compressed"
                # corrupt one compressed shard's extent on disk
                victim = comp_osds[0]
                vkey = next(k for k in victim.store._onodes
                            if k[0] == pool and k[1] == "obj"
                            and victim.store._onodes[k].compression)
                onode = victim.store._onodes[vkey]
                off, length = onode.extents[0]
                victim.store._block.seek(off)
                raw = victim.store._block.read(length)
                victim.store._block.seek(off)
                victim.store._block.write(
                    bytes([raw[0] ^ 0xFF]) + raw[1:])
                victim.store._block.flush()
                with pytest.raises(Exception):
                    victim.store.read(vkey)
                # reads still serve (degraded reconstruction), and deep
                # scrub repairs the corrupted shard in place
                assert await c.get(pool, "obj") == blob
                stats = await c.deep_scrub(pool)
                assert stats["repaired"] >= 1, stats
                data, _ = victim.store.read(vkey)  # EIO gone
                assert await c.get(pool, "obj") == blob
                await c.stop()
            finally:
                await cluster.stop()

        asyncio.run(go())


# -- the commit pipeline: a store on a path commits on a thread of its own ---

import struct  # noqa: E402
import threading  # noqa: E402

from benchmarks.generators.closed_loop_put_durable import (  # noqa: E402
    crash_copy)
from ceph_tpu.rados.bluestore import BS_PERF  # noqa: E402
from ceph_tpu.rados.kv import SyncedFile  # noqa: E402
from ceph_tpu.rados.store import MemStore  # noqa: E402

BIG = 20000  # over bluestore_prefer_deferred_size below: a block write
PIPE_CONF = {"bluestore_prefer_deferred_size": 4096}


class _Files:
    """`files` of a store under test: every sync waits while `hold` is
    set, and every file says which thread wrote or synced what (the WAL's
    records decoded)."""

    def __init__(self):
        self.go = threading.Event()
        self.go.set()
        self.events = []  # (kind, file, thread name, detail)

    def __call__(self, path, mode):
        return _File(self, path, mode)

    replace = staticmethod(SyncedFile.replace)

    def of(self, kind, name):
        return [e for e in self.events if e[:2] == (kind, name)]


class _File(SyncedFile):
    def __init__(self, files, path, mode):
        super().__init__(path, mode)
        self.files, self.name = files, os.path.basename(path)

    def _note(self, kind, detail=None):
        self.files.events.append(
            (kind, self.name, threading.current_thread().name, detail))

    def write(self, data):
        ops = None
        if self.name == "wal.log":
            ops = pickle.loads(bytes(data)[struct.calcsize("<II"):])
        self._note("write", ops)
        super().write(data)

    def pwrite(self, off, data):
        self._note("write", off)
        super().pwrite(off, data)

    def sync(self, data_only=False):
        assert self.files.go.wait(20), "the test never let the sync go"
        super().sync(data_only)
        self._note("sync")


def _big(key, fill, version=1, hinfo=None, omap=None):
    t = Transaction()
    t.write(key, bytes([fill]) * BIG, ShardMeta(version=version,
                                                object_size=BIG))
    if hinfo is not None:
        t.setattr(key, "hinfo_key", hinfo)
    if omap is not None:
        t.omap_set((1, "pgmeta", -1), omap)
    return t


def _crash_copy(store, dst, lengths=None):
    """What a power cut now would leave of `store`, opened again."""
    lengths = lengths or {"block": store._block.synced,
                          "db/wal.log": store.db._log.synced}
    snap = os.path.join(store.path, "db", "snapshot.db")
    if os.path.exists(snap) and "db/snapshot.db" not in lengths:
        lengths["db/snapshot.db"] = os.path.getsize(snap)
    crash_copy(store.path, dst, lengths)
    return BlueStore(dst, dict(PIPE_CONF))


def _commit_threads():
    return [t for t in threading.enumerate()
            if t.name.startswith("bluestore-commit")]


async def _until(cond, seconds=20.0):
    for _ in range(int(seconds / 0.002)):
        if cond():
            return
        await asyncio.sleep(0.002)
    raise AssertionError("timed out")


def _moved(before, *keys):
    dump = BS_PERF.dump()
    return [dump[k] - before[k] for k in keys]


class TestCommitThread:
    def test_what_a_store_says_of_its_commit(self, tmp_path):
        on_disk = BlueStore(str(tmp_path / "osd"))
        assert on_disk.commit_blocks is True
        assert BlueStore().commit_blocks is False
        assert MemStore().commit_blocks is False
        on_disk.close()

    def test_on_commit_runs_after_the_wal_sync_on_the_submitting_loop(
            self, tmp_path):
        files = _Files()
        store = BlueStore(str(tmp_path / "osd"), dict(PIPE_CONF),
                          files=files)
        key, seen = (1, "obj", 0), []
        before = dict(BS_PERF.dump())

        async def go():
            me = threading.current_thread()
            files.go.clear()
            store.queue_transaction(
                _big(key, 7, hinfo=b"h" * 40),
                on_commit=lambda: seen.append(
                    (threading.current_thread() is me, list(files.events))))
            # the call is back, nothing is on the disk, nobody was told
            assert store.stat(key) == (BIG, ShardMeta(1, BIG, 0))
            assert store.getattr(key, "hinfo_key") == b"h" * 40
            assert store.db._log.synced == 0 and seen == []
            await asyncio.sleep(0.05)
            assert seen == []
            files.go.set()
            await _until(lambda: seen)

        asyncio.run(go())
        on_loop, events = seen[0]
        assert on_loop
        assert [e[:2] for e in events] == [
            ("write", "block"), ("sync", "block"), ("write", "wal.log"),
            ("sync", "wal.log")]
        assert {e[2] for e in events} == {"bluestore-commit-osd"}
        assert _moved(before, "txns", "offloop_commits", "block_syncs",
                      "wal_syncs", "commit_under_sync",
                      "commit_unsynced") == [1, 1, 1, 1, 1, 0]
        dump = BS_PERF.dump()
        assert dump["commit_queue_wait"]["avgcount"] \
            == before["commit_queue_wait"]["avgcount"] + 1
        assert dump["loop_sync_s"] == before["loop_sync_s"]
        store.close()
        assert not _commit_threads()

    def test_without_a_loop_a_callback_runs_before_the_call_returns(
            self, tmp_path):
        store = BlueStore(str(tmp_path / "osd"), dict(PIPE_CONF))
        fired = []
        store.queue_transaction(_big((1, "obj", 0), 1),
                                on_commit=lambda: fired.append(1))
        assert fired == [1] and store._thread is None
        assert store.db._log.synced == store.db._log.end > 0
        store.close()

    def test_commits_are_made_in_the_order_of_the_calls(self, tmp_path):
        """With a callback and without, xattrs and omap: one log, in the
        order the store was called; a call without a callback finishes
        what was handed over before it."""
        files = _Files()
        store = BlueStore(str(tmp_path / "osd"), dict(PIPE_CONF),
                          files=files)
        fired = []

        async def go():
            store.queue_transaction(_big((1, "a", 0), 1),
                                    on_commit=lambda: fired.append("a"))
            store.queue_transaction(_big((1, "b", 0), 2))  # no callback
            assert fired == ["a"]
            assert store.db._log.synced == store.db._log.end
            store.queue_transaction(_big((1, "c", 0), 3),
                                    on_commit=lambda: fired.append("c"))
            store.setattr((1, "a", 0), "hinfo_key", b"late")
            assert fired == ["a", "c"]
            store.queue_transaction(_big((1, "d", 0), 4, omap={"k": b"v"}),
                                    on_commit=lambda: fired.append("d"))
            store.omap_set((1, "pgmeta", -1), {"k2": b"v2"})
            store.queue_transaction(_big((1, "e", 0), 5),
                                    on_commit=lambda: fired.append("e"))
            store.rmattr((1, "a", 0), "hinfo_key")
            store.omap_rm((1, "pgmeta", -1), ["k"])
            await _until(lambda: len(fired) == 4)

        asyncio.run(go())
        assert fired == ["a", "c", "d", "e"]

        def what(ops):
            keys = sorted({op[2] for op in ops if op[1] == "O"})
            omap = sorted(op[2] for op in ops if op[1].startswith("M"))
            return keys, omap

        okey = {c: f"1/{c.encode().hex()}/0" for c in "abcde"}
        assert [what(e[3]) for e in files.of("write", "wal.log")] == [
            ([okey["a"]], []), ([okey["b"]], []), ([okey["c"]], []),
            ([okey["a"]], []), ([okey["d"]], ["k"]), ([], ["k2"]),
            ([okey["e"]], []), ([okey["a"]], []), ([], ["k"])]
        by = [e[2] for e in files.of("sync", "wal.log")]
        assert by == ["bluestore-commit-osd", "MainThread"] * 4 \
            + ["MainThread"]
        assert store.omap_get((1, "pgmeta", -1)) == {"k2": b"v2"}
        store.close()

    def test_a_call_without_a_callback_is_durable_when_it_returns(
            self, tmp_path):
        store = BlueStore(str(tmp_path / "osd"), dict(PIPE_CONF))
        told = []

        async def go():
            for i in range(4):
                store.queue_transaction(_big((1, f"early{i}", 0), i),
                                        on_commit=lambda: told.append(1))
            store.queue_transaction(_big((1, "now", 0), 9, hinfo=b"rec"))
            # this instant, by the files alone (no drain of ours)
            left = _crash_copy(store, str(tmp_path / "left"))
            assert left.read((1, "now", 0))[0] == bytes([9]) * BIG
            assert left.getattr((1, "now", 0), "hinfo_key") == b"rec"
            assert all(left.stat((1, f"early{i}", 0)) for i in range(4))
            assert len(told) == 4
            left.abandon()

        asyncio.run(go())
        store.close()

    def test_a_read_finds_a_write_whose_commit_is_under_way(self, tmp_path):
        files = _Files()
        store = BlueStore(str(tmp_path / "osd"), dict(PIPE_CONF),
                          files=files)
        key, told = (1, "obj", 0), []
        store.queue_transaction(_big(key, 1, hinfo=b"old"))

        async def go():
            files.go.clear()
            store.queue_transaction(
                _big(key, 2, version=2, hinfo=b"new", omap={"log.2": b"e"}),
                on_commit=lambda: told.append(1))
            await _until(lambda: files.of("write", "block")[1:])
            # the thread is inside the block sync; the onode names
            # extents no sync has covered, and a read never goes there
            store._read_extents = None
            data, meta = store.read(key)
            assert bytes(data) == bytes([2]) * BIG and meta.version == 2
            assert store.getattr(key, "hinfo_key") == b"new"
            assert store.omap_get((1, "pgmeta", -1)) == {"log.2": b"e"}
            del store._read_extents
            # the old object's extent is not free while a cut leaves it
            old_extent = files.of("write", "block")[0][3]
            assert all(not (off <= old_extent < off + n)
                       for off, n in store.alloc.free)
            assert not told
            files.go.set()
            await _until(lambda: told)
            assert key not in store._inflight
            assert bytes(store.read(key)[0]) == bytes([2]) * BIG
            assert any(off <= old_extent < off + n
                       for off, n in store.alloc.free)

        asyncio.run(go())
        store.close()

    @pytest.mark.parametrize("positioned", [False, True],
                             ids=["one_cursor", "positioned"])
    def test_a_reader_on_the_loop_never_moves_the_threads_write(
            self, tmp_path, positioned):
        """The thread is between finding its place and writing when the
        loop reads another object.  On one shared cursor (`seek` then
        `write`, the block file before PR 48) the write lands where the
        reader left the cursor; by position it lands where it belongs."""
        seeked, read_done = threading.Event(), threading.Event()

        class Racy(SyncedFile):
            def pwrite(self, off, data):
                if threading.current_thread().name != "MainThread":
                    if positioned:
                        seeked.set()
                        assert read_done.wait(20)
                        return super().pwrite(off, data)
                    self.f.seek(off)
                    seeked.set()
                    assert read_done.wait(20)
                    self.f.write(data)
                    self.f.flush()
                    self.end = max(self.end, self.f.tell())
                    return None
                return super().pwrite(off, data)

            def pread(self, off, n):
                if positioned:
                    return super().pread(off, n)
                self.f.seek(off)
                return self.f.read(n)

        store = BlueStore(str(tmp_path / "osd"), dict(PIPE_CONF),
                          files=Racy)
        store.queue_transaction(_big((1, "there", 0), 1))
        store.queue_transaction(_big((1, "other", 0), 3))  # then "new"
        told = []

        async def go():
            store.queue_transaction(_big((1, "new", 0), 2),
                                    on_commit=lambda: told.append(1))
            assert seeked.wait(20)
            assert bytes(store.read((1, "there", 0))[0]) == bytes([1]) * BIG
            read_done.set()
            await _until(lambda: told)

        asyncio.run(go())
        intact = True
        try:
            for name, fill in (("there", 1), ("other", 3), ("new", 2)):
                intact &= bytes(store.read((1, name, 0))[0]) \
                    == bytes([fill]) * BIG
        except EIOError:
            intact = False
        assert intact is positioned
        store.close()

    def test_compaction_under_submits_that_do_not_wait(self, tmp_path):
        """The log passes its limit again and again while the loop keeps
        handing transactions over and reading: every snapshot is the
        copy the loop took for it, and what a cut leaves is whole."""
        path = str(tmp_path / "osd")
        db = WalDB(os.path.join(path, "db"), compact_bytes=3000,
                   perf=BS_PERF)
        store = BlueStore(path, dict(PIPE_CONF), db=db)
        before, told, n = dict(BS_PERF.dump()), [], 120

        async def go():
            for i in range(n):
                key = (1, f"o{i % 9}", 0)
                store.queue_transaction(
                    _big(key, i % 251, version=i + 1, hinfo=b"h%d" % i,
                         omap={f"log.{i}": b"x" * 40}),
                    on_commit=lambda: told.append(1))
                if i % 3 == 0:
                    assert bytes(store.read(key)[0]) \
                        == bytes([i % 251]) * BIG
                    assert f"log.{i}" in store.omap_get((1, "pgmeta", -1))
                if i % 4 == 3:  # four in flight, as a busy OSD has
                    await _until(lambda: len(told) >= i - 3)
            await _until(lambda: len(told) == n)

        asyncio.run(go())
        assert store.failed is None
        assert _moved(before, "compactions")[0] >= 5
        assert _moved(before, "offloop_commits", "commit_unsynced") == [n, 0]
        left = _crash_copy(store, str(tmp_path / "left"),
                           store.synced_lengths())
        assert sorted(left.omap_get((1, "pgmeta", -1))) \
            == sorted(f"log.{i}" for i in range(n))
        for j in range(9):
            last = max(i for i in range(n) if i % 9 == j)
            data, meta = left.read((1, f"o{j}", 0))
            assert bytes(data) == bytes([last % 251]) * BIG
            assert meta.version == last + 1
            assert left.getattr((1, f"o{j}", 0), "hinfo_key") \
                == b"h%d" % last
        left.abandon()
        store.close()
        assert not _commit_threads()

    def test_abandon_with_work_queued_leaves_files_a_reopen_accepts(
            self, tmp_path):
        files = _Files()
        path = str(tmp_path / "osd")
        store = BlueStore(path, dict(PIPE_CONF), files=files)
        store.queue_transaction(_big((1, "kept", 0), 1, hinfo=b"rec"))
        told = []

        async def go():
            files.go.clear()
            for i in range(4):
                store.queue_transaction(
                    _big((1, f"q{i}", 0), i, hinfo=b"q"),
                    on_commit=lambda: told.append(1))
            await _until(lambda: files.of("write", "block")[1:])
            threading.Timer(0.05, files.go.set).start()
            store.abandon()  # the one in hand ends; three are dropped
            assert store._thread is None and not _commit_threads()
            await asyncio.sleep(0.05)

        asyncio.run(go())
        assert told == []  # a killed daemon acknowledges nothing
        assert len(files.of("write", "wal.log")) == 2
        again = BlueStore(path, dict(PIPE_CONF))
        assert sorted(again.list_objects(1)) == [("kept", 0), ("q0", 0)]
        for name, fill in (("kept", 1), ("q0", 0)):
            assert bytes(again.read((1, name, 0))[0]) == bytes([fill]) * BIG
        assert again.getattr((1, "q0", 0), "hinfo_key") == b"q"
        again.queue_transaction(_big((1, "after", 0), 5))
        again.close()

    def test_synced_lengths_cover_every_call_that_has_returned(
            self, tmp_path):
        store = BlueStore(str(tmp_path / "osd"), dict(PIPE_CONF))
        told = []

        async def go():
            for i in range(6):
                store.queue_transaction(
                    _big((1, f"o{i}", 0), i, hinfo=b"h"),
                    on_commit=lambda: told.append(1))
            lengths = store.synced_lengths()  # no await since the calls
            assert len(told) == 6
            left = _crash_copy(store, str(tmp_path / "left"), lengths)
            for i in range(6):
                assert bytes(left.read((1, f"o{i}", 0))[0]) \
                    == bytes([i]) * BIG
                assert left.getattr((1, f"o{i}", 0), "hinfo_key") == b"h"
            left.abandon()

        asyncio.run(go())
        store.close()

    def test_a_commit_that_fails_fails_the_store(self, tmp_path):
        class Full(SyncedFile):
            def sync(self, data_only=False):
                if threading.current_thread().name != "MainThread":
                    raise OSError(28, "No space left on device")
                super().sync(data_only)

        store = BlueStore(str(tmp_path / "osd"), dict(PIPE_CONF), files=Full)
        told = []

        async def go():
            for i in range(3):
                store.queue_transaction(_big((1, f"o{i}", 0), i),
                                        on_commit=lambda: told.append(1))
            await _until(lambda: store.failed is not None)
            await asyncio.sleep(0.02)

        asyncio.run(go())
        assert told == []
        assert store.db._log.end == 0  # nothing behind the failure went on
        with pytest.raises(IOError, match="takes no more"):
            store.queue_transaction(_big((1, "late", 0), 9))
        with pytest.raises(IOError, match="takes no more"):
            store.setattr((1, "o0", 0), "k", b"v")
        store.abandon()
        assert not _commit_threads()

    def test_a_sync_error_is_told_once_with_waiters_queued_behind_it(
            self, tmp_path):
        """The second transaction's WAL sync fails with two more queued
        behind it: the first is told it committed; no callback of the
        other three runs, and the store's owner is told why, once, on the
        submitting loop (`on_failure`), where it lets its waiters go;
        nothing stays counted as in flight, so a drain and a close
        return."""
        files = _Files()

        class Fails(_File):
            def sync(self, data_only=False):
                if self.name == "wal.log" \
                        and len(files.of("sync", "wal.log")) == 1:
                    assert files.go.wait(20)
                    raise OSError(5, "Input/output error")
                super().sync(data_only)

        store = BlueStore(str(tmp_path / "osd"), dict(PIPE_CONF),
                          files=lambda path, mode: Fails(files, path, mode))
        told = []

        async def go():
            me = threading.current_thread()
            loop = asyncio.get_running_loop()
            waiters = [loop.create_future() for _ in range(4)]

            def failed(why):  # as the OSD's: every waiter is refused
                told.append(("failed", why, threading.current_thread() is me,
                             store._unfinished))
                for done in waiters:
                    if not done.done():
                        done.set_result(False)

            store.on_failure = failed
            files.go.clear()
            for i, done in enumerate(waiters):
                store.queue_transaction(
                    _big((1, f"o{i}", 0), i, hinfo=b"h"),
                    on_commit=lambda i=i, done=done: (
                        told.append(("committed", i)),
                        done.set_result(True)))
            assert store._unfinished == 4
            files.go.set()
            assert await asyncio.wait_for(asyncio.gather(*waiters), 20) \
                == [True, False, False, False]
            await _until(lambda: store._unfinished == 0)

        asyncio.run(go())
        assert told[0] == ("committed", 0)
        assert len(told) == 2 and told[1][0] == "failed"
        assert isinstance(told[1][1], OSError) and told[1][2]
        assert store._unfinished == 0 and not store._done
        assert store.failed is told[1][1]
        # one record went under a sync; nothing behind the failure did
        assert len(files.of("sync", "wal.log")) == 1
        with pytest.raises(IOError, match="takes no more"):
            store.queue_transaction(_big((1, "late", 0), 9))
        store.close()
        assert not _commit_threads()
        # the files hold the first transaction, whole, and open again
        left = BlueStore(str(tmp_path / "osd"), dict(PIPE_CONF))
        assert left.read((1, "o0", 0))[0] == bytes([0]) * BIG
        assert left.getattr((1, "o0", 0), "hinfo_key") == b"h"
        left.close()

    def test_one_wake_up_serves_many_stores(self, tmp_path):
        """Twelve stores' threads finish while the loop is busy: the loop
        is woken once for all of them, not once a commit."""
        stores = [BlueStore(str(tmp_path / f"osd.{i}"), dict(PIPE_CONF))
                  for i in range(12)]
        told, wakes = [], []

        async def go():
            loop = asyncio.get_running_loop()
            threadsafe = loop.call_soon_threadsafe
            loop.call_soon_threadsafe = lambda *a: (wakes.append(a[0]),
                                                    threadsafe(*a))[1]
            for i, store in enumerate(stores):
                store.queue_transaction(_big((1, "o", 0), i),
                                        on_commit=lambda: told.append(1))
            import time
            while sum(len(s._done) for s in stores) < 12:
                time.sleep(0.005)  # the loop does not turn meanwhile
            await _until(lambda: len(told) == 12)

        asyncio.run(go())
        assert len(wakes) < 12
        for store in stores:
            store.close()


@pytest.mark.parametrize("size", [0, 1, 4097, 524288])
def test_an_extents_checksum_is_the_one_a_read_verifies(size):
    """Whatever buffer a transaction hands over (bytes, a bytearray, a
    read-only view, an array's): the stored crc32c is `checksum`'s."""
    import numpy as np

    from ceph_tpu.utils.checksum import checksum, verify_any

    raw = os.urandom(size)
    for buf in (raw, bytearray(raw), memoryview(bytearray(raw)).toreadonly(),
                memoryview(np.frombuffer(raw, dtype=np.uint8))):
        assert BlueStore._csum("crc32c", buf) == checksum(raw) & 0xFFFFFFFF
        assert verify_any(raw, BlueStore._csum("crc32c", buf))
