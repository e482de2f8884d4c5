"""BlueStore-lite: block-file object store with WAL, checksums, allocator.

Role-equivalent of the reference's BlueStore (reference
src/os/bluestore/BlueStore.cc): object data lives in one raw block file
carved by an extent allocator; all metadata (object -> extents, per-extent
crc32c checksums, shard meta, xattrs, omap) lives in a KeyValueDB whose WAL
provides the commit point — a transaction is durable exactly when its
metadata batch hits the KV WAL.  Small writes are DEFERRED
(bluestore_prefer_deferred_size): the data rides inside the KV record and
is flushed to the block file after commit, saving the block-file sync on
the latency path; large writes go to freshly allocated extents first
(copy-on-write — crash before KV commit leaves the old object intact),
the block file is SYNCED, and only then the metadata flips atomically
(reference _kv_sync_thread: bdev->flush() before
db->submit_transaction_sync): an onode never names bytes a power cut
could take.

A commit has two halves.  PREPARE, on the caller's thread, is what a
reader needs: the failsafe check, compression, the extents allocated,
each extent's checksum, the onode into `_onodes` and the KV batch into
the KV's tables, and a big write's bytes reachable from its key until
they are on the disk (`_inflight`: a read of an object whose commit is
under way is served from them, never from an extent not yet written).
COMMIT is, in this order and one transaction at a time: block write,
block sync, WAL append, WAL sync, the `commit_under_sync` look; then
FINISH, on the caller's thread again: `on_commit`, and the extents the
transaction freed go back to the allocator (only now: until the WAL
sync the old onode is what a power cut leaves).  The caller acks after
on_commit.

Which thread commits: a store on a path (`commit_blocks`) has a thread
of its own, started at the first transaction that comes with an
`on_commit` from a running event loop.  Such a transaction is prepared,
queued and the call returns; the thread commits what it is handed in
the order it was handed, and the loop runs `on_commit` (one
`call_soon_threadsafe` for however many commits of however many stores
finished since the loop last looked).  The thread is handed immutable
bytes only (the chunks, the batch's operations, a copy of the tables
where a compaction is due): it reads no onode and no table, and holds
no lock.  That is why the checksum is still made in prepare: the
onode's record has to be whole when it enters the tables.  Every other
call (no `on_commit`, no loop, `setattr`, `omap_set`, a deferred flush,
`synced_lengths`) first waits until the thread has nothing left
(`_drain`), then commits on its caller as ever: committed when it
returns, and in the order of the calls.  A commit that fails on the
thread (the disk, a closed file) fails the store: that transaction and
the ones queued behind it are not committed, as a power cut would leave
them, and no `on_commit` of theirs runs; the store's owner is told once,
on its loop (`on_failure`), and every later call raises.  The OSD answers
whoever waits for a commit with a refusal and dies (the reference aborts
the daemon there).  The block file is read and written with positioned
I/O alone (`SyncedFile.pread` / `pwrite`): a reader on the loop and the
writer on the thread share no cursor.

Checksums: per-extent, algorithm selected by bluestore_csum_type
(crc32c default, zlib, none — reference csum_type per blob), verified
on every read BEFORE decompression; bluestore_debug_inject_read_err /
_csum_err_probability inject failures for the EIO-handling tests
(reference src/common/options/global.yaml.in:4977,5017).

Compression (reference BlueStore _do_write compression at blob
granularity): per-POOL mode/algorithm from pool opts (`ceph osd pool
set NAME compression_mode aggressive` -> pg_pool_t::opts -> OSDMap ->
set_pool_opts here), falling back to bluestore_compression_mode/
_algorithm conf.  zlib / zstd / lzma; a blob is stored compressed only
when >= bluestore_compression_min_blob_size and the result beats
bluestore_compression_required_ratio (default 0.875) — otherwise raw,
exactly the reference's required-ratio discipline.  Checksums cover
the STORED (compressed) bytes, so a corrupted compressed extent fails
the csum before the decompressor ever sees it.

Recovery contract: open() replays the KV WAL (WalDB does this), then
flushes any deferred writes recorded-but-not-flushed.  The allocator
rebuilds its free map from the extent metadata.
"""

from __future__ import annotations

import asyncio
import logging
import os
import pickle
import queue
import random
import threading
import time
import weakref
import zlib
from collections import deque

from ceph_tpu.common import tracing
from ceph_tpu.common.perf_counters import PerfCounters, PerfCountersBuilder
from ceph_tpu.utils.checksum import checksum
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterable, List, Optional, Tuple

from ceph_tpu.rados.kv import (KeyValueDB, MemDB, SyncedFile, WalDB,
                               WriteBatch, timed_sync)
from ceph_tpu.rados.store import (ENOSPCError,  # noqa: F401 (re-export)
                                  Key, ObjectStore, ShardMeta, Transaction,
                                  unwrap as store_unwrap)

log = logging.getLogger("ceph_tpu.bluestore")

PREFIX_OBJ = "O"  # object metadata (extents, csums, ShardMeta, xattrs)
PREFIX_DEFERRED = "D"  # deferred write payloads awaiting block flush
PREFIX_OMAP = "M"  # per-object sorted key/value (PG log lives here)
PREFIX_SUPER = "S"  # store-wide state (size watermark)


def build_bluestore_perf(name: str = "bluestore") -> PerfCounters:
    b = PerfCountersBuilder(name)
    b.add_u64_counter("txns", "transactions committed")
    b.add_time_avg("commit_lat", "queue_transaction entered -> on_commit "
                                 "run")
    b.add_u64_counter("block_write_bytes", "bytes written to block files")
    b.add_u64_counter("wal_bytes", "bytes appended to KV write-ahead logs")
    b.add_u64_counter("deferred_bytes", "payload bytes that rode a WAL "
                                        "record (deferred writes)")
    b.add_u64_counter("deferred_writes", "writes at or under "
                                         "bluestore_prefer_deferred_size")
    b.add_u64_counter("big_writes", "writes that went to fresh extents "
                                    "before their commit")
    b.add_u64_counter("block_syncs", "syncs of a block file")
    b.add_u64_counter("wal_syncs", "syncs of a KV write-ahead log")
    b.add_time_avg("sync_s", "seconds inside fsync/fdatasync, whichever "
                             "thread")
    b.add_time_avg("loop_sync_s", "the part of sync_s made on a thread "
                                  "that runs an event loop")
    b.add_time_avg("group_txns", "transactions a WAL sync committed, over "
                                 "the syncs that committed any")
    b.add_u64_counter("compactions", "KV snapshots written (WalDB.compact)")
    b.add_time_avg("compact_s", "seconds inside KV compactions")
    b.add_u64_counter("csum_bytes", "bytes checksummed for extents "
                                    "written")
    b.add_u64_counter("alloc_extents", "extents allocated")
    b.add_u64_counter("commit_under_sync",
                      "transactions whose on_commit found every block "
                      "byte and the WAL record they made under a sync")
    b.add_u64_counter("commit_unsynced",
                      "transactions whose on_commit ran ahead of a sync "
                      "that has to cover them: the broken guarantee")
    b.add_u64_counter("offloop_commits",
                      "transactions committed on a store's own thread "
                      "(of txns: the share that left the caller's loop)")
    b.add_time_avg("commit_queue_wait", "handed to the store's thread -> "
                                        "the thread takes it")
    b.add_time_avg("commit_queue_depth",
                   "transactions of the store not yet finished, as each "
                   "one handed to its thread found them (sum: of depths)")
    return b.create_perf_counters()


# ONE set per process, listed by every OSD whose store is a BlueStore, as
# the resident store's set is: the stores of a vstart cluster share a disk
BS_PERF = build_bluestore_perf()


class EIOError(IOError):
    """Read failed checksum / injected EIO (the OSD turns this into the
    shard-level error path the reference tests with test-erasure-eio.sh)."""


@dataclass
class _Onode:
    """Object metadata record (BlueStore onode role)."""

    extents: List[Tuple[int, int]] = field(default_factory=list)  # (off, len)
    csums: List[int] = field(default_factory=list)  # per-extent, of STORED bytes
    meta: ShardMeta = field(default_factory=ShardMeta)
    deferred: bool = False  # data still only in the KV (deferred write)
    xattrs: Dict[str, bytes] = field(default_factory=dict)
    # blob compression (reference bluestore_blob_t compressed flag):
    # algorithm name or None; raw_len pins the decompressed size
    compression: Optional[str] = None
    raw_len: int = -1
    csum_type: str = "crc32c"


# gated like auth.py's `cryptography` import: hosts without `zstandard`
# still run every non-zstd cluster shape — only the actual use of a
# zstd-compressed blob raises (writes degrade to raw with a warning at
# the caller; reads of an EXISTING zstd blob must raise, never return
# garbage)
try:
    import zstandard as _zstandard
except ImportError:
    _zstandard = None


def _require_zstd():
    if _zstandard is None:
        raise ImportError(
            "the `zstandard` package is required for zstd-compressed "
            "blobs but is not installed; pick compression_algorithm "
            "zlib/lzma or install zstandard")
    return _zstandard


def _compress(algo: str, raw) -> bytes:
    if algo == "zstd":
        return _require_zstd().ZstdCompressor(level=1).compress(bytes(raw))
    if algo == "lzma":
        import lzma

        return lzma.compress(bytes(raw), preset=0)
    return zlib.compress(bytes(raw), 1)


def _decompress(algo: str, data: bytes) -> bytes:
    if algo == "zstd":
        return _require_zstd().ZstdDecompressor().decompress(data)
    if algo == "lzma":
        import lzma

        return lzma.decompress(data)
    return zlib.decompress(data)


def _okey(key: Key) -> str:
    pid, oid, shard = key
    return f"{pid}/{oid.encode().hex()}/{shard}"


def _unokey(s: str) -> Key:
    pid, oid_hex, shard = s.split("/")
    return int(pid), bytes.fromhex(oid_hex).decode(), int(shard)


class Allocator:
    """Free-extent allocator (AvlAllocator role): first-fit with merge."""

    def __init__(self, size: int):
        self.size = size
        self.free: List[Tuple[int, int]] = [(0, size)] if size else []

    def allocate(self, want: int) -> int:
        for i, (off, length) in enumerate(self.free):
            if length >= want:
                if length == want:
                    self.free.pop(i)
                else:
                    self.free[i] = (off + want, length - want)
                return off
        # grow the device (file-backed: sparse growth is free); the grown
        # region beyond this allocation joins the free list
        off = self.size
        grow = max(want, 1 << 20)
        self.size += grow
        if grow > want:
            self.release(off + want, grow - want)
        return off

    def release(self, off: int, length: int) -> None:
        self.free.append((off, length))
        self.free.sort()
        merged: List[Tuple[int, int]] = []
        for o, l in self.free:
            if merged and merged[-1][0] + merged[-1][1] == o:
                merged[-1] = (merged[-1][0], merged[-1][1] + l)
            else:
                merged.append((o, l))
        self.free = merged

    def reserve(self, off: int, length: int) -> None:
        """Mark [off, off+len) used (startup rebuild)."""
        out = []
        for o, l in self.free:
            if off >= o + l or off + length <= o:
                out.append((o, l))
                continue
            if o < off:
                out.append((o, off - o))
            if off + length < o + l:
                out.append((off + length, o + l - off - length))
        self.free = out
        self.size = max(self.size, off + length)


class _Commit:
    """One prepared transaction on its way to the disk.  What the
    committing thread reads of it is immutable: `blocks` (extents and
    the bytes for them), `ops` (the KV batch's operations) and
    `snapshot` (a copy of the KV's tables where a compaction is due
    after this record, else None)."""

    __slots__ = ("blocks", "ops", "snapshot", "freed", "deferred",
                 "inflight", "on_commit", "error", "t_enter", "t_queued",
                 "waker")

    def __init__(self, on_commit, t_enter: float) -> None:
        self.blocks: List[Tuple[List[Tuple[int, int]], bytes]] = []
        self.ops: list = []
        self.snapshot = None
        self.freed: List[Tuple[int, int]] = []
        self.deferred: List[Tuple[Key, "_Onode", bytes]] = []
        self.inflight: List[Tuple[Key, "_Onode"]] = []
        self.on_commit = on_commit
        self.error: Optional[BaseException] = None  # why it never committed
        self.t_enter = t_enter


class _Waker:
    """An event loop's one wake-up for the commits its stores' threads
    have finished: a thread that finds it armed adds its store and
    leaves the loop alone."""

    def __init__(self, loop) -> None:
        self.loop = loop
        self.stores: deque = deque()
        self.armed = False

    def notify(self, store: "BlueStore") -> None:
        """Any thread: `store` has commits to finish on the loop."""
        self.stores.append(store)
        if not self.armed:
            self.armed = True
            try:
                self.loop.call_soon_threadsafe(self.run)
            except RuntimeError:
                pass  # the loop is closed: nobody is left to tell

    def run(self) -> None:
        # disarm BEFORE looking: a store added after the last look arms
        # a wake-up of its own
        self.armed = False
        while self.stores:
            self.stores.popleft()._finish_done()


_WAKERS: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _waker_of(loop) -> _Waker:
    waker = _WAKERS.get(loop)
    if waker is None:
        waker = _WAKERS[loop] = _Waker(loop)
    return waker


class BlueStore(ObjectStore):
    perf = BS_PERF

    def __init__(self, path: Optional[str] = None,
                 conf: Optional[dict] = None,
                 db: Optional[KeyValueDB] = None,
                 files=SyncedFile):
        self.conf = conf or {}
        self.path = path
        if path is not None:
            os.makedirs(path, exist_ok=True)
            self.db: KeyValueDB = db or WalDB(
                os.path.join(path, "db"), perf=self.perf, files=files)
            self._block_path = os.path.join(path, "block")
            if not os.path.exists(self._block_path):
                open(self._block_path, "wb").close()
            # r+b: positioned writes (a+b would append whatever the
            # offset); read and written through pread / pwrite alone
            self._block = files(self._block_path, "r+b")
        else:
            self.db = db or MemDB()
            self._block = None
            self._blob: Dict[int, bytes] = {}  # off -> data (RAM mode)
        self.alloc = Allocator(0)
        # configured byte ceiling + failsafe (reference bluestore
        # bluefs/statfs capacity + osd_failsafe_full_ratio): 0 = grow
        # forever (the pre-capacity behavior, default)
        self.capacity_bytes = int(self.conf.get(
            "osd_store_capacity_bytes", 0) or 0)
        self.failsafe_ratio = float(self.conf.get(
            "osd_failsafe_full_ratio", 0.97) or 0.97)
        self._onodes: Dict[Key, _Onode] = {}
        # per-pool store options pushed from the OSDMap (pg_pool_t::opts
        # role): compression_mode/algorithm/ratio/min_blob_size
        self.pool_opts: Dict[int, Dict[str, str]] = {}
        self._compress_warned: set = set()
        # committed-but-unflushed deferred writes, drained in batches off
        # the commit latency path (bluestore deferred_batch semantics)
        self._deferred_pending: List[Tuple[Key, _Onode, bytes]] = []
        self._deferred_batch_max = 16
        self._block_dirty = False  # extents written since the last sync
        # the commit pipeline (module docstring).  `_inflight`: big
        # writes whose bytes may not be in their extents yet, by key
        self.commit_blocks = path is not None
        self.failed: Optional[BaseException] = None
        self._failure_told = False
        self._inflight: Dict[Key, Tuple[_Onode, bytes]] = {}
        self._thread: Optional[threading.Thread] = None
        self._queue: "queue.SimpleQueue" = queue.SimpleQueue()
        self._done: deque = deque()  # committed, to finish on the caller
        self._unfinished = 0  # handed to the thread and not finished yet
        self._dropping = False  # abandon: what is queued is not committed
        self._load()
        self._flush_deferred()

    # -- startup -------------------------------------------------------------

    def _load(self) -> None:
        for k, v in self.db.iterate(PREFIX_OBJ):
            onode: _Onode = pickle.loads(v)
            key = _unokey(k)
            self._onodes[key] = onode
            for off, length in onode.extents:
                self.alloc.reserve(off, length)

    def _flush_deferred(self) -> None:
        """Finish deferred writes that committed but weren't flushed to the
        block file before shutdown (BlueStore deferred replay)."""
        for k, v in list(self.db.iterate(PREFIX_DEFERRED)):
            key = _unokey(k)
            onode = self._onodes.get(key)
            if onode is not None and onode.deferred:
                self._write_extents(onode.extents, v)
                self._sync_block()
                onode.deferred = False
                batch = WriteBatch()
                batch.set(PREFIX_OBJ, _okey(key),
                          pickle.dumps(onode, protocol=5))
                batch.rm(PREFIX_DEFERRED, k)
                self.db.submit(batch)
            else:
                batch = WriteBatch()
                batch.rm(PREFIX_DEFERRED, k)
                self.db.submit(batch)

    # -- block IO ------------------------------------------------------------

    def _write_extents(self, extents: List[Tuple[int, int]], data: bytes) -> None:
        """The bytes to their extents; `_sync_block` has to follow before
        a KV batch that names them."""
        with tracing.section("store", "bs_block_write"):
            pos = 0
            for off, length in extents:
                piece = data[pos:pos + length]
                if self._block is not None:
                    self._block.pwrite(off, piece)
                else:
                    self._blob[off] = piece
                pos += length
        self.perf.inc("block_write_bytes", pos)
        self._block_dirty = True

    def _sync_block(self) -> None:
        """Every extent written since the last sync, onto the disk."""
        if not self._block_dirty:
            return
        if self._block is not None:
            timed_sync(self._block, self.perf, "bs_block_sync",
                       data_only=True)
            self.perf.inc("block_syncs")
        self._block_dirty = False

    def _read_extents(self, extents: List[Tuple[int, int]]) -> bytes:
        out = []
        with tracing.section("store", "bs_read"):
            for off, length in extents:
                if self._block is not None:
                    out.append(self._block.pread(off, length))
                else:
                    out.append(self._blob.get(off, b"")[:length])
        return b"".join(out)

    # -- ObjectStore interface -----------------------------------------------

    def queue_transaction(self, txn: Transaction,
                          on_commit: Optional[Callable[[], None]] = None) -> None:
        """Apply atomically: ONE KV batch is the commit point for every
        write, delete, omap and xattr change in the transaction
        (ObjectStore::queue_transactions with register_on_commit
        semantics).  Committed at return, or at `on_commit` where the
        store's thread commits it (module docstring; rados/store.py)."""
        t_enter = time.perf_counter()
        loop = (asyncio._get_running_loop()
                if on_commit is not None and self.commit_blocks else None)
        if loop is None:
            self._drain()
        self._check_alive()
        item = self._prepare(txn, on_commit, t_enter,
                             hand_over=loop is not None)
        if loop is not None:
            self._hand_over(item, loop)
            return
        try:
            self._commit(item)
            if self.db.log_full():
                self.db.compact()
            self._finish(item)
        except BaseException as e:
            self._fail(e)
            raise

    def _fail(self, error: BaseException) -> None:
        """What was prepared is in the tables and may not be on the disk:
        the store stops where a power cut would have stopped it."""
        if self.failed is None:
            self.failed = error

    def _tell_failure(self) -> None:
        """On the owner's loop, once: commits it waits for will not come
        (`ObjectStore.on_failure`)."""
        if self.on_failure is not None and not self._failure_told:
            self._failure_told = True
            self.on_failure(self.failed)

    def _check_alive(self) -> None:
        if self.failed is not None:
            raise IOError(f"bluestore {self.path}: a commit failed "
                          f"({self.failed!r}); the store takes no more")

    def _prepare(self, txn: Transaction, on_commit, t_enter: float,
                 hand_over: bool = False) -> _Commit:
        """The caller's half of a commit: everything a reader finds, and
        a `_Commit` that holds what is left to do."""
        item = _Commit(on_commit, t_enter)
        prefer_deferred = int(self.conf.get("bluestore_prefer_deferred_size",
                                            32768) or 0)
        self._ranged_as_whole(txn)
        # failsafe BEFORE any mutation (KV batch, allocator, block file):
        # a refused transaction leaves the store byte-identical.  The
        # common no-ceiling config skips both sums (the free-list walk
        # would otherwise tax every write for a guaranteed no-op check).
        if self.capacity_bytes:
            self._check_failsafe(
                sum(len(store_unwrap(c)) for _k, c, _m in txn.writes),
                self.alloc.size - sum(l for _, l in self.alloc.free))
        batch = WriteBatch()
        freed = item.freed
        for key in txn.deletes:
            onode = self._onodes.pop(key, None)
            if onode is not None:
                freed.extend(onode.extents)
            batch.rm(PREFIX_OBJ, _okey(key))
            batch.rm(PREFIX_DEFERRED, _okey(key))
            batch.rm_prefix(PREFIX_OMAP + _okey(key))
        for key, entries in txn.omap_sets:
            for k, v in entries.items():
                batch.set(PREFIX_OMAP + _okey(key), k, v)
        for key, keys in txn.omap_rms:
            for k in keys:
                batch.rm(PREFIX_OMAP + _okey(key), k)
        touched: Dict[Key, _Onode] = {}  # onodes whose record the batch sets
        for key, chunk, meta in txn.writes:
            chunk = store_unwrap(chunk)  # disk store copies to media anyway
            old = self._onodes.get(key)
            if old is not None:
                freed.extend(old.extents)
            onode = _Onode(meta=meta,
                           xattrs=dict(old.xattrs) if old else {})
            # blob compression decision (reference _do_write + the
            # required-ratio gate): per-pool opts override global conf
            raw_len = len(chunk)
            popts = self.pool_opts.get(key[0], {})
            mode = popts.get("compression_mode",
                             self.conf.get("bluestore_compression_mode",
                                           "none")) or "none"
            # passive = compress only on a client compressible-hint
            # (reference alloc-hint plumbing); no hints exist in this
            # transaction format, so passive stores raw — treating it
            # as aggressive would invert its documented meaning
            if mode in ("aggressive", "force"):
                algo = popts.get(
                    "compression_algorithm",
                    self.conf.get("bluestore_compression_algorithm",
                                  "zlib"))
                min_blob = int(popts.get(
                    "compression_min_blob_size",
                    self.conf.get("bluestore_compression_min_blob_size",
                                  4096)))
                ratio = float(popts.get(
                    "compression_required_ratio",
                    self.conf.get("bluestore_compression_required_ratio",
                                  0.875)))
                if raw_len >= min_blob:
                    try:
                        cand = _compress(algo, chunk)
                    except Exception as e:
                        cand = None
                        # loudly, once per (pool, algo): a missing
                        # compressor module must not silently store a
                        # "compressed" pool raw forever
                        warn_key = (key[0], algo)
                        if warn_key not in self._compress_warned:
                            self._compress_warned.add(warn_key)
                            print(f"bluestore: pool {key[0]} "
                                  f"compression_algorithm={algo} "
                                  f"unavailable ({e}); storing raw")
                    if cand is not None and len(cand) <= raw_len * ratio:
                        chunk = cand
                        onode.compression = algo
                        onode.raw_len = raw_len
            onode.csum_type = str(self.conf.get("bluestore_csum_type",
                                                "crc32c") or "crc32c")
            with tracing.section("store", "bs_alloc"):
                off = self.alloc.allocate(max(1, len(chunk)))
            onode.extents = [(off, len(chunk))]
            with tracing.section("store", "bs_csum"):
                onode.csums = [self._csum(onode.csum_type, chunk)]
            self.perf.inc("alloc_extents")
            self.perf.inc("csum_bytes", len(chunk))
            if len(chunk) <= prefer_deferred:
                # deferred: payload rides the KV WAL (pickled) — needs
                # real bytes, a memoryview cannot serialize
                if not isinstance(chunk, bytes):
                    chunk = bytes(chunk)
                onode.deferred = True
                batch.set(PREFIX_DEFERRED, _okey(key), chunk)
                item.deferred.append((key, onode, chunk))
                self.perf.inc("deferred_writes")
                self.perf.inc("deferred_bytes", len(chunk))
            else:
                # large write: data to fresh extents BEFORE commit (COW);
                # until it is there a read finds it here
                item.blocks.append((onode.extents, chunk))
                item.inflight.append((key, onode))
                self._inflight[key] = (onode, chunk)
                self.perf.inc("big_writes")
            self._onodes[key] = onode
            touched[key] = onode
        for key, name, value in txn.xattr_sets:
            onode = self._onodes.get(key)
            if onode is None:
                onode = self._onodes[key] = _Onode()
            onode.xattrs[name] = value
            touched[key] = onode
        for key, onode in touched.items():
            batch.set(PREFIX_OBJ, _okey(key), pickle.dumps(onode, protocol=5))
        item.ops = batch.ops
        self.db.apply(batch)
        if hand_over and self.db.log_full():
            item.snapshot = self.db.snapshot()
        return item

    def _commit(self, item: _Commit) -> None:
        """The disk's half, on the store's thread or, with nothing left
        there, on the caller: the extents' bytes onto the disk before the
        batch that names them (a power cut between the two leaves the old
        object), then the batch: THE commit point."""
        for extents, chunk in item.blocks:
            self._write_extents(extents, chunk)
        self._sync_block()
        wal_seq = getattr(self.db, "wal_seq", None)  # a RAM KV has none
        self.db.log(item.ops)
        self.perf.inc("txns")
        self.perf.tinc("group_txns", 1)
        # the guarantee, looked at where the caller is about to be told:
        # no extent of this store waits for a sync, and the WAL was
        # synced after this batch was handed over
        self.perf.inc("commit_unsynced" if self._block_dirty
                      or (wal_seq is not None and self.db.wal_seq <= wal_seq)
                      else "commit_under_sync")
        if item.snapshot is not None:
            self.db.compact(item.snapshot)

    def _finish(self, item: _Commit) -> None:
        """After the commit, on the caller's thread: the caller is told,
        and what the transaction replaced is let go of."""
        for key, onode in item.inflight:
            got = self._inflight.get(key)
            if got is not None and got[0] is onode:
                del self._inflight[key]
        if item.on_commit is not None:
            item.on_commit()
        self.perf.tinc("commit_lat", time.perf_counter() - item.t_enter)
        # post-commit: deferred payloads drain in batches so a small write
        # costs ONE fsync on the latency path (the open-time replay covers
        # anything pending at a crash)
        self._deferred_pending.extend(item.deferred)
        if len(self._deferred_pending) >= self._deferred_batch_max:
            self.flush_deferred_batch()
        for off, length in item.freed:
            self.alloc.release(off, length)

    # -- the store's own thread ----------------------------------------------

    def _hand_over(self, item: _Commit, loop) -> None:
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._commit_loop, daemon=True,
                name=f"bluestore-commit-{os.path.basename(self.path)}")
            self._thread.start()
        item.waker = _waker_of(loop)
        self.perf.tinc("commit_queue_depth", self._unfinished)
        self._unfinished += 1
        item.t_queued = time.perf_counter()
        self._queue.put(item)

    def _commit_loop(self) -> None:
        """The thread: one transaction at a time, in the order handed."""
        take = self._queue.get
        while True:
            item = take()
            if item is None:
                return
            if isinstance(item, threading.Event):
                item.set()  # `_drain`: everything before it is done with
                continue
            if self._dropping:
                continue  # as a power cut: neither committed nor told
            waited = time.perf_counter() - item.t_queued
            if self.failed is None:
                try:
                    self._commit(item)
                except BaseException as e:
                    self._fail(e)
                    log.error("bluestore %s: commit failed, the store "
                              "takes no more: %r", self.path, e)
            # a failed store commits nothing more; whoever waits is told
            item.error = self.failed
            if item.error is None:
                self.perf.inc("offloop_commits")
                self.perf.tinc("commit_queue_wait", waited)
            self._done.append(item)
            item.waker.notify(self)

    def _finish_done(self) -> None:
        """On the caller's thread: whatever the store's thread has
        committed since the last look is finished, in order; at the first
        one it could not commit the owner is told (`on_failure`).  A flush
        that fails or a callback that raises fails the store too; the
        loop's wake-up goes on to the other stores."""
        while self._done:
            item = self._done.popleft()
            self._unfinished -= 1
            if item.error is None:
                try:
                    self._finish(item)
                    continue
                except Exception as e:
                    # the ones behind it are on the disk all the same:
                    # their waiters are still told
                    self._fail(e)
                    log.exception("bluestore %s: after a commit", self.path)
            self._tell_failure()

    def _drain(self) -> None:
        """Wait until the thread has nothing queued or in hand, and finish
        what it committed: after this the files, the books and `every
        call that has returned` describe one instant, and the caller may
        commit on its own thread."""
        if self._unfinished:
            if self._thread is not None:
                reached = threading.Event()
                self._queue.put(reached)
                reached.wait()
            self._finish_done()

    def _stop_thread(self, drop: bool) -> None:
        """`drop`: what is still queued is neither committed nor told, as
        at a power cut; the transaction in hand ends as it ends."""
        thread, self._thread = self._thread, None
        if thread is not None:
            self._dropping = drop
            self._queue.put(None)
            thread.join()
        if drop:
            self._done.clear()
            self._unfinished = 0
        else:
            self._finish_done()

    def flush_deferred_batch(self) -> None:
        if not self._deferred_pending:
            return
        self._drain()
        self._check_alive()
        pending, self._deferred_pending = self._deferred_pending, []
        b2 = WriteBatch()
        for key, onode, chunk in pending:
            if self._onodes.get(key) is not onode:
                continue  # overwritten/deleted since; its extents are gone
            self._write_extents(onode.extents, chunk)
            onode.deferred = False
            b2.set(PREFIX_OBJ, _okey(key), pickle.dumps(onode, protocol=5))
            b2.rm(PREFIX_DEFERRED, _okey(key))
        if b2.ops:
            # as a commit's: the payloads leave the WAL only once the
            # block file holds them
            self._sync_block()
            self.db.submit(b2)

    @staticmethod
    def _csum(ctype: str, data) -> int:
        if ctype == "none":
            return 0
        if ctype == "zlib":
            return zlib.crc32(bytes(data)) & 0xFFFFFFFF
        return checksum(data) & 0xFFFFFFFF

    def set_pool_opts(self, pool_id: int, opts: Dict[str, str]) -> None:
        """OSDMap pool-opts push (pg_pool_t::opts role)."""
        if opts:
            self.pool_opts[pool_id] = dict(opts)
        else:
            self.pool_opts.pop(pool_id, None)

    def read(self, key: Key) -> Optional[Tuple[bytes, ShardMeta]]:
        onode = self._onodes.get(key)
        if onode is None:
            return None
        if self.conf.get("bluestore_debug_inject_read_err", False):
            raise EIOError(f"injected read error on {key}")
        flying = self._inflight.get(key)
        if flying is not None and flying[0] is onode:
            # its commit is under way: the extents may not hold it yet
            data = flying[1] if isinstance(flying[1], bytes) \
                else bytes(flying[1])
        elif onode.deferred:
            data = self.db.get(PREFIX_DEFERRED, _okey(key)) or b""
        else:
            data = self._read_extents(onode.extents)
        prob = float(self.conf.get(
            "bluestore_debug_inject_csum_err_probability", 0.0) or 0.0)
        if prob and random.random() < prob:
            raise EIOError(f"injected csum error on {key}")
        # verify BEFORE decompression, over the stored bytes: a
        # corrupted compressed extent must fail here, never feed the
        # decompressor garbage (pre-selection onode pickles lack the
        # csum_type field; verify_any keeps them readable)
        if getattr(onode, "csum_type", "crc32c") != "none":
            pos = 0
            for (off, length), want in zip(onode.extents, onode.csums):
                from ceph_tpu.utils.checksum import verify_any

                if not verify_any(data[pos:pos + length], want):
                    raise EIOError(f"checksum mismatch on {key} @{off}")
                pos += length
        comp = getattr(onode, "compression", None)
        if comp:
            try:
                data = _decompress(comp, data)
            except Exception as e:
                raise EIOError(
                    f"decompression failed on {key} ({comp}): {e}")
            raw_len = getattr(onode, "raw_len", -1)
            if raw_len >= 0 and len(data) != raw_len:
                raise EIOError(
                    f"decompressed length mismatch on {key}: "
                    f"{len(data)} != {raw_len}")
        return data, onode.meta

    def stat(self, key: Key) -> Optional[Tuple[int, ShardMeta]]:
        """From the onode alone: no byte is read or checksummed."""
        onode = self._onodes.get(key)
        if onode is None:
            return None
        size = sum(length for _, length in onode.extents)
        if getattr(onode, "compression", None) \
                and getattr(onode, "raw_len", -1) >= 0:
            size = onode.raw_len  # the extents hold the compressed blob
        return size, onode.meta

    def list_objects(self, pool_id: int) -> Iterable[Tuple[str, int]]:
        for (pid, oid, shard) in list(self._onodes):
            if pid == pool_id:
                yield oid, shard

    def list_pools(self) -> Iterable[int]:
        return sorted({pid for (pid, _o, _s) in self._onodes})

    # -- xattrs / omap (HashInfo + PG log substrate) -------------------------

    def setattr(self, key: Key, name: str, value: bytes) -> None:
        self._drain()
        self._check_alive()
        onode = self._onodes.get(key)
        if onode is None:
            onode = _Onode()
            self._onodes[key] = onode
        onode.xattrs[name] = value
        batch = WriteBatch()
        batch.set(PREFIX_OBJ, _okey(key), pickle.dumps(onode, protocol=5))
        self.db.submit(batch)

    def getattr(self, key: Key, name: str) -> Optional[bytes]:
        onode = self._onodes.get(key)
        return onode.xattrs.get(name) if onode else None

    def rmattr(self, key: Key, name: str) -> None:
        onode = self._onodes.get(key)
        if onode is None or name not in onode.xattrs:
            return
        self._drain()
        self._check_alive()
        del onode.xattrs[name]
        batch = WriteBatch()
        batch.set(PREFIX_OBJ, _okey(key), pickle.dumps(onode, protocol=5))
        self.db.submit(batch)

    def getattrs(self, key: Key) -> Dict[str, bytes]:
        onode = self._onodes.get(key)
        return dict(onode.xattrs) if onode else {}

    def omap_set(self, key: Key, entries: Dict[str, bytes]) -> None:
        self._drain()
        self._check_alive()
        batch = WriteBatch()
        for k, v in entries.items():
            batch.set(PREFIX_OMAP + _okey(key), k, v)
        self.db.submit(batch)

    def omap_get(self, key: Key) -> Dict[str, bytes]:
        return dict(self.db.iterate(PREFIX_OMAP + _okey(key)))

    def omap_rm(self, key: Key, keys: List[str]) -> None:
        self._drain()
        self._check_alive()
        batch = WriteBatch()
        for k in keys:
            batch.rm(PREFIX_OMAP + _okey(key), k)
        self.db.submit(batch)

    # -- admin ----------------------------------------------------------------

    def statfs(self) -> Dict[str, int]:
        free = sum(l for _, l in self.alloc.free)
        used = self.alloc.size - free
        total = int(self.capacity_bytes or 0)
        # uniform shape first (total/used/avail, total==0 = unlimited);
        # size/free kept for the allocator-view consumers
        return {"total": total, "used": used,
                "avail": max(0, total - used) if total else 0,
                "num_objects": len(self._onodes),
                "size": self.alloc.size, "free": free}

    def synced_lengths(self) -> Dict[str, int]:
        """Bytes of each of the store's files that a sync has covered, by
        path under `self.path`: a copy of the directory cut to them is
        what a power cut now would leave.  The store's thread is waited
        for first: the lengths are those of an instant at which every
        call that has returned, with a callback or without, is on the
        disk."""
        self._drain()
        out = {"block": self._block.synced} if self._block is not None \
            else {}
        lengths = getattr(self.db, "synced_lengths", None)
        if lengths is not None:
            out.update(("db/" + name, n) for name, n in lengths().items())
        return out

    def close(self) -> None:
        """Everything handed over is committed and told, the deferred
        payloads are flushed, the thread ends; then the files close."""
        self._drain()
        if self.failed is None:
            self.flush_deferred_batch()
        self._stop_thread(drop=False)
        self._close_files()

    def abandon(self) -> None:
        """Let go of the files as they are, as a killed daemon does:
        deferred payloads that were not flushed stay in the WAL for the
        next open to replay.  The thread takes no more work: the commit
        it has in hand ends as it ends, what is queued behind it is
        dropped as a power cut would drop it (no callback runs), the
        thread is joined, and only then are the files closed."""
        self._stop_thread(drop=True)
        self._close_files()

    def _close_files(self) -> None:
        self.db.close()
        if self._block is not None:
            self._block.close()
