"""KeyValueDB: the metadata-store abstraction under BlueStore-lite.

Role-equivalent of the reference's KeyValueDB over RocksDB (reference
src/kv/KeyValueDB.h, RocksDBStore.cc): prefixed keyspaces, atomic write
batches, prefix iteration.  The durable implementation is a write-ahead
log + in-memory table with snapshot compaction — the same recovery
contract as the reference (a committed batch survives crash; a torn tail
record is discarded), sized for metadata volumes, not a general LSM.

Record format in the WAL: [u32 len][u32 crc][pickled batch].  Compaction
writes a full snapshot and truncates the log.

Every file of a store is a `SyncedFile`: it knows how many of its bytes
a sync has covered, which is what a power cut leaves of it.

A batch has two halves, and a store that commits on a thread of its own
(BlueStore on a path) calls them apart: `apply` puts it into the tables,
where `get` and `iterate` find it, and is the SUBMITTING thread's;
`log` makes it durable, and is the committing thread's, which sees
nothing but the batch's own bytes.  `submit` is both on one thread.
"""

from __future__ import annotations

import asyncio
import os
import pickle
import struct
import time

from ceph_tpu.common import tracing
from ceph_tpu.utils.checksum import checksum
from typing import Dict, Iterator, List, Optional, Tuple

_REC = struct.Struct("<II")


class SyncedFile:
    """A store's file and the two lengths that matter at a power cut:
    `end`, the bytes written, and `synced`, the bytes that were there at
    the last `sync` (what was on the disk at `open` counts as synced).
    The tests' crash layer subclasses it to keep the synced image."""

    def __init__(self, path: str, mode: str):
        self.path = path
        self.f = open(path, mode)
        self.end = self.synced = os.path.getsize(path)

    def seek(self, off: int) -> None:
        self.f.seek(off)

    def read(self, n: int) -> bytes:
        return self.f.read(n)

    def write(self, data) -> None:
        self.f.write(data)
        self.end = max(self.end, self.f.tell())

    def flush(self) -> None:
        self.f.flush()

    def pwrite(self, off: int, data) -> None:
        """`data` at `off`, whatever any other thread reads or writes of
        the file meanwhile: the descriptor's cursor is neither read nor
        moved (a file written here is not written through `write`)."""
        fd, view = self.f.fileno(), memoryview(data).cast("B")
        done = 0
        while done < len(view):
            done += os.pwrite(fd, view[done:], off + done)
        self.end = max(self.end, off + done)

    def pread(self, off: int, n: int) -> bytes:
        """Up to `n` bytes from `off`, short where the file ends."""
        fd, out = self.f.fileno(), []
        while n > 0:
            piece = os.pread(fd, n, off)
            if not piece:
                break
            out.append(piece)
            off += len(piece)
            n -= len(piece)
        return out[0] if len(out) == 1 else b"".join(out)

    def sync(self, data_only: bool = False) -> None:
        self.f.flush()
        (os.fdatasync if data_only else os.fsync)(self.f.fileno())
        self.synced = self.end

    def close(self) -> None:
        self.f.close()

    @staticmethod
    def replace(src: str, dst: str) -> None:
        os.replace(src, dst)


def timed_sync(f: SyncedFile, perf, name: str,
               data_only: bool = False) -> None:
    """`f.sync()` as the section `name` of the store layer, its seconds
    in the `bluestore` set: `sync_s` whichever thread made it,
    `loop_sync_s` when that thread runs an event loop (every daemon of
    an in-process cluster waits behind it)."""
    with tracing.section("store", name):
        t0 = time.perf_counter()
        f.sync(data_only)
        took = time.perf_counter() - t0
    if perf is not None:
        perf.tinc("sync_s", took)
        if asyncio._get_running_loop() is not None:
            perf.tinc("loop_sync_s", took)


class WriteBatch:
    """Atomic batch (reference KeyValueDB::Transaction)."""

    def __init__(self):
        self.ops: List[Tuple[str, str, str, Optional[bytes]]] = []

    def set(self, prefix: str, key: str, value: bytes) -> None:
        self.ops.append(("set", prefix, key, value))

    def rm(self, prefix: str, key: str) -> None:
        self.ops.append(("rm", prefix, key, None))

    def rm_prefix(self, prefix: str) -> None:
        self.ops.append(("rmpfx", prefix, "", None))


class KeyValueDB:
    def submit(self, batch: WriteBatch) -> None:
        """`log` then `apply`, on the caller's thread."""
        raise NotImplementedError

    def apply(self, batch: WriteBatch) -> None:
        """The batch into the tables `get` and `iterate` read."""
        raise NotImplementedError

    def log(self, ops: list) -> None:
        """A batch's operations made durable (a RAM table has nothing to
        do)."""

    def log_full(self) -> bool:
        """True when a commit should end in `compact` (of a `snapshot`,
        where another thread commits): never, without a log."""
        return False

    def get(self, prefix: str, key: str) -> Optional[bytes]:
        raise NotImplementedError

    def iterate(self, prefix: str) -> Iterator[Tuple[str, bytes]]:
        raise NotImplementedError

    def close(self) -> None:
        pass


class MemDB(KeyValueDB):
    def __init__(self):
        self._tables: Dict[str, Dict[str, bytes]] = {}

    def _apply(self, batch: WriteBatch) -> None:
        for op, prefix, key, value in batch.ops:
            table = self._tables.setdefault(prefix, {})
            if op == "set":
                table[key] = value
            elif op == "rm":
                table.pop(key, None)
            elif op == "rmpfx":
                table.clear()

    apply = _apply

    def submit(self, batch: WriteBatch) -> None:
        self._apply(batch)

    def get(self, prefix: str, key: str) -> Optional[bytes]:
        return self._tables.get(prefix, {}).get(key)

    def iterate(self, prefix: str) -> Iterator[Tuple[str, bytes]]:
        yield from sorted(self._tables.get(prefix, {}).items())


class WalDB(MemDB):
    """Durable MemDB: every batch is WAL-appended before apply; snapshot +
    log truncation when the log grows past `compact_bytes`.  `perf` is
    the owning store's counter set (BlueStore's `bluestore`), `files`
    the class its files are opened with."""

    def __init__(self, path: str, compact_bytes: int = 4 << 20,
                 perf=None, files=SyncedFile):
        super().__init__()
        self.path = path
        self.compact_bytes = compact_bytes
        self.perf = perf
        self._files = files
        self.wal_seq = 0  # the number of the log's last sync
        self._snapshot_out = False  # a `snapshot` waits for its `compact`
        os.makedirs(path, exist_ok=True)
        self._snap_path = os.path.join(path, "snapshot.db")
        self._log_path = os.path.join(path, "wal.log")
        self._recover()
        self._log = files(self._log_path, "ab")

    # -- recovery ------------------------------------------------------------

    def _recover(self) -> None:
        if os.path.exists(self._snap_path):
            with open(self._snap_path, "rb") as f:
                self._tables = pickle.load(f)
        if os.path.exists(self._log_path):
            valid_end = 0
            with open(self._log_path, "rb") as f:
                while True:
                    hdr = f.read(_REC.size)
                    if len(hdr) < _REC.size:
                        break
                    length, crc = _REC.unpack(hdr)
                    blob = f.read(length)
                    if len(blob) < length:
                        break  # torn tail: committed prefix only
                    # algorithm-agnostic verify: a WAL written by a build
                    # whose checksum resolved differently (crc32c vs
                    # zlib, either direction) must not be mistaken for a
                    # torn tail — that would TRUNCATE committed batches
                    from ceph_tpu.utils.checksum import verify_any

                    if not verify_any(blob, crc):
                        break
                    valid_end = f.tell()
                    batch = WriteBatch()
                    batch.ops = pickle.loads(blob)
                    self._apply(batch)
            # truncate the torn tail: appends after it would sit behind
            # garbage and be unreachable to the NEXT recovery
            if valid_end < os.path.getsize(self._log_path):
                with open(self._log_path, "r+b") as f:
                    f.truncate(valid_end)

    # -- commits -------------------------------------------------------------

    def log(self, ops: list) -> None:
        """One record appended and synced.  Touches the log file and
        nothing else of this object, so the thread that commits may
        call it while another reads the tables."""
        with tracing.section("store", "bs_wal_submit"):
            blob = pickle.dumps(ops, protocol=5)
            self._log.write(_REC.pack(len(blob), checksum(blob)) + blob)
        timed_sync(self._log, self.perf, "bs_wal_sync")
        self.wal_seq += 1
        if self.perf is not None:
            self.perf.inc("wal_bytes", _REC.size + len(blob))
            self.perf.inc("wal_syncs")

    def submit(self, batch: WriteBatch) -> None:
        self.log(batch.ops)
        self._apply(batch)
        if self.log_full():
            self.compact()

    def log_full(self) -> bool:
        return self._log.end >= self.compact_bytes \
            and not self._snapshot_out

    def snapshot(self) -> Dict[str, Dict[str, bytes]]:
        """The tables as they are now, for a `compact` on another thread:
        a copy of each dict (the values are bytes nobody changes), so the
        thread pickles what no batch applied later can touch.  Whoever
        takes one owes the `compact`; until then `log_full` is False."""
        self._snapshot_out = True
        return {prefix: dict(table)
                for prefix, table in self._tables.items()}

    def compact(self, tables=None) -> None:
        """The whole table set to a new snapshot, then an empty log, on
        the thread that commits: every key the store holds is pickled
        there, and `compact_s` says for how long.  Where that thread is
        not the one that applies batches, the applying thread hands it a
        consistent copy (`snapshot`, taken right after the batch whose
        record is the log's last when this runs: the committing thread
        takes its work in order); no lock is shared.  Without `tables`
        the caller is the applying thread itself."""
        t0 = time.perf_counter()
        with tracing.section("store", "bs_compact"):
            tmp = self._snap_path + ".tmp"
            snap = self._files(tmp, "wb")
            pickle.dump(self._tables if tables is None else tables, snap,
                        protocol=5)
            timed_sync(snap, self.perf, "bs_wal_sync")
            snap.close()
            self._files.replace(tmp, self._snap_path)
            self._log.close()
            self._log = self._files(self._log_path, "wb")
        self._snapshot_out = False
        if self.perf is not None:
            self.perf.inc("compactions")
            self.perf.tinc("compact_s", time.perf_counter() - t0)

    def synced_lengths(self) -> Dict[str, int]:
        """Bytes of each file a sync has covered, by name under `path`
        (the snapshot is replaced whole, after its own sync)."""
        out = {"wal.log": self._log.synced}
        if os.path.exists(self._snap_path):
            out["snapshot.db"] = os.path.getsize(self._snap_path)
        return out

    def close(self) -> None:
        try:
            self._log.close()
        except Exception:
            pass
