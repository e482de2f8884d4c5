"""Object stores: the per-OSD persistence layer.

Equivalent role to the reference's ObjectStore hierarchy (reference
src/os/ObjectStore.h:229 queue_transactions): atomic transactions over
(object, shard) -> bytes + metadata, with commit callbacks.  MemStore is
the RAM store the reference also ships for testing (src/os/memstore/);
DirStore persists shards as files (a minimal filestore) so OSD restart
tests survive process death.

`queue_transaction(txn, on_commit)`, the contract every store keeps:

    without `on_commit`   the transaction is committed when the call
                          returns: applied, and on a disk store on the
                          disk.  Whoever goes on to acknowledge after
                          the call relies on that.
    with `on_commit`      it is APPLIED when the call returns (a read, a
                          stat, an omap_get find it) and COMMITTED when
                          `on_commit()` runs, on the caller's thread: for
                          a store whose commit does not block, before the
                          call returns; for one whose commit does
                          (`commit_blocks`: a BlueStore on a path) and
                          whose caller runs an event loop, later, from
                          that loop, after the store's own thread has
                          made it durable.  Commits are made in the order
                          of the calls, whatever their kind; nothing is
                          acknowledged before its callback.  Where that
                          thread cannot commit (the disk fails under it)
                          the store takes no more: no `on_commit` of that
                          transaction or of one queued behind it runs, and
                          `on_failure(why)`, which the store's owner sets,
                          runs once on that loop: whoever waits for a
                          callback of this store stops waiting there.

`commit_blocks` is what a store says of itself, and the one thing the OSD
reads to decide whether a shard write waits for a callback or for the
call (rados/osd.py `_commit_shard`): MemStore False, BlueStore on a path
True, in RAM False.

A transaction carries xattr sets too (`Transaction.setattr`; the
reference's ECTransaction sets hinfo_key in the shard's own
ObjectStore::Transaction): applied after its writes, so a shard and its
hinfo record are one commit, and a power cut leaves both or neither.
"""

from __future__ import annotations

import errno
import json
import os
import zlib
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterable, List, Optional, Tuple

Key = Tuple[int, str, int]  # (pool_id, oid, shard)


class ENOSPCError(OSError):
    """Typed out-of-space failure (reference -ENOSPC from
    BlueStore::_do_alloc_write past osd_failsafe_full_ratio): raised by a
    store BEFORE it mutates anything, so a refused transaction leaves the
    store byte-identical.  The OSD turns this into a typed ENOSPC reply
    the client treats as definitive (no resend loop)."""

    def __init__(self, message: str):
        super().__init__(errno.ENOSPC, message)


class Owned:
    """Write-ownership marker (reference bufferlist move semantics on
    queue_transactions): the writer guarantees the wrapped buffer is
    never read or written by it again, so a RAM-backed store may keep
    the view as-is instead of taking the defensive freeze copy it
    otherwise needs — with local fast dispatch, sub-write chunks arrive
    by reference over encode-output arrays, and copying 16 MiB per
    shard per write is the single largest cost on the daemon data
    path.  Disk-backed stores unwrap and copy to media regardless."""

    __slots__ = ("view",)

    def __init__(self, buf):
        self.view = buf if isinstance(buf, memoryview) else memoryview(buf)


def unwrap(chunk):
    return chunk.view if isinstance(chunk, Owned) else chunk


@dataclass
class ShardMeta:
    version: int = 0
    object_size: int = 0  # original (untrimmed) object length
    chunk_crc: int = 0  # crc32 of the shard (HashInfo role,
    # reference src/osd/ECUtil.h:101-160)


@dataclass
class Transaction:
    """Atomic batch of shard writes/deletes plus omap mutations (the PG
    log rides omap in the same transaction as the data, the reference's
    log_operation + queue_transactions coupling)."""

    writes: List[Tuple[Key, bytes, ShardMeta]] = field(default_factory=list)
    # writes at an offset: (key, off, data, size, meta, prev)
    ranged: List[tuple] = field(default_factory=list)
    deletes: List[Key] = field(default_factory=list)
    omap_sets: List[Tuple[Key, Dict[str, bytes]]] = field(default_factory=list)
    omap_rms: List[Tuple[Key, List[str]]] = field(default_factory=list)
    # xattrs set after the writes: (key, name, value)
    xattr_sets: List[Tuple[Key, str, bytes]] = field(default_factory=list)
    # the store's answer, set when it applies `ranged`: bytes of whole
    # objects it had to copy for them (0: every one landed in place)
    copied: int = 0

    def write(self, key: Key, chunk: bytes, meta: ShardMeta) -> None:
        self.writes.append((key, chunk, meta))

    def write_at(self, key: Key, off: int, data: bytes, size: int,
                 meta: ShardMeta, prev: Optional[Key] = None) -> None:
        """`data` over [off, off + len(data)) of the object at `key`
        (reference ObjectStore::Transaction::write(oid, off, len, bl)),
        the object zero-extended to `size` and to the extent's end, its
        meta replaced.  With `prev`, the object as it was before this
        write, bytes and meta, reads back at `prev` afterwards (the
        reference clones the outgoing extent into a rollback object in
        the same transaction, ECTransaction rollback_extents); what the
        store keeps there is its own business."""
        self.ranged.append((key, off, data, size, meta, prev))

    def delete(self, key: Key) -> None:
        self.deletes.append(key)

    def omap_set(self, key: Key, entries: Dict[str, bytes]) -> None:
        self.omap_sets.append((key, dict(entries)))

    def omap_rm(self, key: Key, keys: List[str]) -> None:
        self.omap_rms.append((key, list(keys)))

    def setattr(self, key: Key, name: str, value: bytes) -> None:
        """An xattr of the object at `key`, set with the transaction's
        writes (reference ObjectStore::Transaction::setattr); a store
        with no xattrs leaves it out, as its `setattr` refuses."""
        self.xattr_sets.append((key, name, value))


class ObjectStore:
    # True where a commit waits for a disk: such a store commits on a
    # thread of its own for a caller that passes `on_commit` from an
    # event loop (module docstring)
    commit_blocks: bool = False
    # set by the owner of a store whose commit blocks: called once, on
    # the owner's loop, with the error that failed the store's thread
    on_failure: Optional[Callable[[BaseException], None]] = None

    # byte ceiling (0 = unlimited) + the last-resort guard protecting the
    # store itself (reference osd_failsafe_full_ratio): a transaction
    # whose writes would push used bytes past failsafe_ratio * capacity
    # is refused with a typed ENOSPCError BEFORE anything mutates.
    # Deletes always pass — they are the only way back out of full.
    capacity_bytes: int = 0
    failsafe_ratio: float = 0.97

    def queue_transaction(self, txn: Transaction, on_commit=None) -> None:
        raise NotImplementedError

    def statfs(self) -> Dict[str, int]:
        """Uniform utilization shape every store reports (reference
        ObjectStore::statfs): {total, used, avail, num_objects}.
        total == 0 means no configured capacity (unlimited)."""
        n = sum(1 for p in self.list_pools()
                for _ in self.list_objects(p))
        return {"total": int(self.capacity_bytes), "used": 0,
                "avail": int(self.capacity_bytes), "num_objects": n}

    def _check_failsafe(self, incoming_bytes: int, used_bytes: int) -> None:
        """Refuse (typed ENOSPC) when accepting ``incoming_bytes`` more
        would cross the failsafe ceiling.  Conservative: freed bytes from
        same-transaction deletes/overwrites are not credited — near the
        failsafe line the store errs on refusal (delete-only transactions
        carry no writes and always pass)."""
        cap = int(self.capacity_bytes or 0)
        if cap <= 0 or incoming_bytes <= 0:
            return
        ceiling = int(cap * float(self.failsafe_ratio))
        if used_bytes + incoming_bytes > ceiling:
            raise ENOSPCError(
                f"failsafe full: used {used_bytes} + incoming "
                f"{incoming_bytes} > {ceiling} "
                f"({self.failsafe_ratio:g} of {cap})")

    def _ranged_as_whole(self, txn: Transaction) -> None:
        """A store with no write at an offset applies one as it always
        has: read, rebuild, write whole, the outgoing object whole at
        `prev`.  Turns `txn.ranged` into `txn.writes` before anything
        mutates."""
        pending: Dict[Key, Tuple[bytes, ShardMeta]] = {}
        for key, off, data, size, meta, prev in txn.ranged:
            got = pending.get(key) or self.read(key)
            old = bytes(unwrap(got[0])) if got is not None else b""
            if got is not None and prev is not None:
                txn.writes.append((prev, old, got[1]))
            blob = splice(old, off, data, size)
            txn.writes.append((key, blob, meta))
            pending[key] = (blob, meta)
            txn.copied += len(old) + len(blob)
        txn.ranged = []

    def read(self, key: Key) -> Optional[Tuple[bytes, ShardMeta]]:
        raise NotImplementedError

    def stat(self, key: Key) -> Optional[Tuple[int, ShardMeta]]:
        """(length, meta) of the object, without handing its bytes out."""
        got = self.read(key)
        if got is None:
            return None
        return memoryview(unwrap(got[0])).nbytes, got[1]

    def list_objects(self, pool_id: int) -> Iterable[Tuple[str, int]]:
        """Yield (oid, shard) pairs stored for a pool."""
        raise NotImplementedError

    def list_pools(self) -> Iterable[int]:
        """Pool ids with at least one stored shard (boot-time sweep for
        pools deleted while this OSD was down)."""
        raise NotImplementedError

    def omap_get(self, key: Key) -> Dict[str, bytes]:
        return {}

    def omap_set(self, key: Key, entries: Dict[str, bytes]) -> None:
        raise NotImplementedError

    def omap_rm(self, key: Key, keys: List[str]) -> None:
        raise NotImplementedError

    def getattr(self, key: Key, name: str) -> Optional[bytes]:
        return None

    def setattr(self, key: Key, name: str, value: bytes) -> None:
        raise NotImplementedError

    def rmattr(self, key: Key, name: str) -> None:
        raise NotImplementedError

    def getattrs(self, key: Key) -> Dict[str, bytes]:
        return {}


def splice(old, off: int, data, size: int = 0) -> bytes:
    """`old` zero-extended to `size` and to the extent's end, with `data`
    over [off, off + len(data)): the whole-buffer form of a write at an
    offset (what a store without one does, and what tests hold one to)."""
    buf = bytearray(old)
    want = max(size, off + len(data), len(buf))
    if len(buf) < want:
        buf.extend(bytes(want - len(buf)))
    buf[off:off + len(data)] = data
    return bytes(buf)


class _Spliced(bytearray):
    """A shard the store has written at an offset and may write again IN
    PLACE.  The store allocated it, so nobody else holds the object; what
    others hold are the read-only views `read` hands out, and a bytearray
    knows while one is alive (`viewed`): then, and only then, the next
    write at an offset copies first and leaves this buffer to its
    viewers.  `undo_at` is the key whose `_Undo` reads through this
    buffer, if any."""

    __slots__ = ("undo_at",)


def live(chunk) -> bool:
    """True when `chunk`, as a store's `read` gave it, is a view of a
    buffer the store may write in place again.  It will not while the
    view is alive (it copies first), so the view reads what it read; a
    reader who keeps a SMALL part across an await copies the part instead
    and spares the store the whole."""
    return isinstance(chunk, memoryview) and type(chunk.obj) is _Spliced


def viewed(buf: bytearray) -> bool:
    """True while a memoryview of `buf` is alive anywhere (a sub-read
    reply in a connection's outbox or replay queue, a recovery push, a
    reader between two awaits).  A bytearray refuses to change its
    length while it is exported, and that is the one way Python answers
    the question.  The probe needs a spare byte of allocation, or it
    reallocates (and may copy) the whole buffer once: `private` makes
    buffers that have one."""
    try:
        buf.append(0)
    except BufferError:
        return True
    buf.pop()
    return False


def private(src) -> "_Spliced":
    """A copy of `src` to write in place and to ask `viewed` about:
    allocated one byte longer than it is, in one pass over the bytes."""
    n = memoryview(src).nbytes
    buf = _Spliced(n + 1)
    buf[:n] = src
    buf.pop()
    buf.undo_at = None
    return buf


class _Undo:
    """A spliced shard's previous version, kept as what the splice
    overwrote: the shard as it is now with `was` back at `off`, cut to
    its old `length`.  O(extent) to keep; `whole` costs the shard and is
    paid by whoever reads the rollback slot (a rollback, recovery, a
    shard hunt).  It reads through `buf`, so it holds as long as no
    later write at an offset changes `buf` without replacing it
    (`MemStore._write_at` sees to that)."""

    __slots__ = ("buf", "off", "was", "length")

    def __init__(self, buf: bytearray, off: int, was: bytes, length: int):
        self.buf, self.off, self.was, self.length = buf, off, was, length

    def __len__(self) -> int:
        return len(self.was)  # the bytes it holds, for the store's books

    def whole(self) -> bytes:
        now = memoryview(self.buf)
        end = self.off + len(self.was)
        return b"".join((now[:min(self.off, self.length)], self.was,
                         now[end:self.length]))


class MemStore(ObjectStore):
    def __init__(self, capacity_bytes: int = 0,
                 failsafe_ratio: float = 0.97) -> None:
        self.capacity_bytes = int(capacity_bytes or 0)
        self.failsafe_ratio = float(failsafe_ratio or 0.97)
        self._data: Dict[Key, Tuple[bytes, ShardMeta]] = {}
        self._omap: Dict[Key, Dict[str, bytes]] = {}
        self._xattrs: Dict[Key, Dict[str, bytes]] = {}
        self._used_bytes = 0  # data bytes held (incremental, O(1) statfs)

    def queue_transaction(self, txn: Transaction, on_commit=None) -> None:
        # failsafe BEFORE any mutation: a refused transaction must leave
        # the store byte-identical (the test pins this).  Guarded like
        # the disk stores: the unlimited config skips even the cheap sum.
        if self.capacity_bytes:
            self._check_failsafe(
                sum(len(unwrap(c)) for _k, c, _m in txn.writes)
                + sum(len(r[2]) for r in txn.ranged),
                self._used_bytes)
        for key in txn.deletes:
            old = self._data.pop(key, None)
            if old is not None:
                self._used_bytes -= len(old[0])
            self._omap.pop(key, None)
        for key, chunk, meta in txn.writes:
            if isinstance(chunk, Owned):
                # ownership handed over: keep the view, no copy
                chunk = chunk.view
            elif not isinstance(chunk, bytes):
                # freeze at the durability boundary: with local fast
                # dispatch chunks arrive BY REFERENCE (memoryview over
                # a sender buffer) — a real store copies to media here,
                # the RAM store must copy too or later buffer reuse
                # would corrupt "persisted" data
                chunk = bytes(chunk)
            prev = self._data.get(key)
            if prev is not None:
                self._used_bytes -= len(prev[0])
            self._used_bytes += len(chunk)
            self._data[key] = (chunk, meta)
        for ranged in txn.ranged:
            self._write_at(txn, *ranged)
        for key, entries in txn.omap_sets:
            self._omap.setdefault(key, {}).update(entries)
        for key, keys in txn.omap_rms:
            table = self._omap.get(key)
            if table:
                for k in keys:
                    table.pop(k, None)
        for key, name, value in txn.xattr_sets:
            self._xattrs.setdefault(key, {})[name] = value
        if on_commit is not None:
            on_commit()

    def _write_at(self, txn: Transaction, key: Key, off: int, data,
                  size: int, meta: ShardMeta, prev: Optional[Key]) -> None:
        """A write at an offset, in place where the store may: the object
        is a `_Spliced` nobody views and no `_Undo` but `prev`'s reads
        through.  Else one private copy first (`txn.copied`): the first
        such write to an object stored as it arrived (bytes, an adopted
        view: those are shared with the sender on the in-process paths
        and are never written), a view out, a missing object (created).
        The outgoing version goes to `prev` as an `_Undo`, replacing
        whatever was there: the extent, not the shard."""
        got = self._data.get(key)
        cur = got[0] if got is not None else b""
        held = len(cur)
        if type(cur) is _Spliced and cur.undo_at in (None, prev) \
                and not viewed(cur):
            buf = cur
        else:
            buf = private(cur.whole() if type(cur) is _Undo else cur)
            txn.copied += len(buf)
        length = len(buf)
        end = off + len(data)
        was = bytes(memoryview(buf)[off:end])
        if max(size, end) > length:
            buf.extend(bytes(max(size, end) - length))
        buf[off:end] = data
        self._used_bytes += len(buf) - held
        self._data[key] = (buf, meta)
        if prev is not None and got is not None:
            slot = self._data.get(prev)
            if slot is not None:
                self._used_bytes -= len(slot[0])
            undo = _Undo(buf, off, was, length)
            self._used_bytes += len(undo)
            self._data[prev] = (undo, got[1])
            buf.undo_at = prev

    def omap_get(self, key: Key) -> Dict[str, bytes]:
        return dict(self._omap.get(key, {}))

    def omap_set(self, key: Key, entries: Dict[str, bytes]) -> None:
        self._omap.setdefault(key, {}).update(entries)

    def omap_rm(self, key: Key, keys: List[str]) -> None:
        table = self._omap.get(key)
        if table:
            for k in keys:
                table.pop(k, None)

    def getattr(self, key: Key, name: str) -> Optional[bytes]:
        return self._xattrs.get(key, {}).get(name)

    def setattr(self, key: Key, name: str, value: bytes) -> None:
        self._xattrs.setdefault(key, {})[name] = value

    def rmattr(self, key: Key, name: str) -> None:
        self._xattrs.get(key, {}).pop(name, None)

    def getattrs(self, key: Key) -> Dict[str, bytes]:
        return dict(self._xattrs.get(key, {}))

    def read(self, key: Key) -> Optional[Tuple[bytes, ShardMeta]]:
        got = self._data.get(key)
        if got is not None:
            kind = type(got[0])
            if kind is _Spliced:
                # the one door to a buffer that may be written in place:
                # a view, so that `viewed` knows while it is held
                return memoryview(got[0]).toreadonly(), got[1]
            if kind is _Undo:
                return got[0].whole(), got[1]
        return got

    def stat(self, key: Key) -> Optional[Tuple[int, ShardMeta]]:
        got = self._data.get(key)
        if got is None:
            return None
        chunk = got[0]
        return (chunk.length if type(chunk) is _Undo
                else memoryview(chunk).nbytes), got[1]

    def read_range(self, key: Key, off: int, length: int):
        """The object's bytes in [off, off + length), cut short where it
        ends: a buffer that stays as it is whatever is written to the
        object later."""
        got = self._data.get(key)
        if got is None:
            return b""
        chunk = got[0]
        if type(chunk) is _Undo:
            return chunk.whole()[off:off + length]
        cut = memoryview(chunk)[off:off + length]
        # of a buffer that may be written in place, a copy: a view held
        # would cost the next write there a copy of the whole
        return bytes(cut) if type(chunk) is _Spliced else cut

    def list_objects(self, pool_id: int):
        for (pid, oid, shard) in list(self._data):
            if pid == pool_id:
                yield oid, shard

    def list_pools(self):
        return sorted({pid for (pid, _o, _s) in self._data})

    def statfs(self) -> Dict[str, int]:
        total = int(self.capacity_bytes or 0)
        used = self._used_bytes
        return {"total": total, "used": used,
                "avail": max(0, total - used) if total else 0,
                "num_objects": len(self._data)}


class DirStore(ObjectStore):
    """File-per-shard store with a sidecar json for metadata; writes are
    tmp+rename atomic."""

    def __init__(self, path: str, capacity_bytes: int = 0,
                 failsafe_ratio: float = 0.97) -> None:
        self.path = path
        self.capacity_bytes = int(capacity_bytes or 0)
        self.failsafe_ratio = float(failsafe_ratio or 0.97)
        os.makedirs(path, exist_ok=True)

    def _file(self, key: Key) -> str:
        # hex-encode the oid: filenames stay unambiguous for ANY oid bytes
        # (slashes, '__', unicode) and list parsing can invert exactly
        pid, oid, shard = key
        return os.path.join(self.path, f"{pid}__{oid.encode().hex()}__{shard}")

    def queue_transaction(self, txn: Transaction, on_commit=None) -> None:
        self._ranged_as_whole(txn)
        if self.capacity_bytes:
            # _used_bytes is a directory sweep: only pay it when a
            # ceiling is actually configured
            self._check_failsafe(
                sum(len(unwrap(c)) for _k, c, _m in txn.writes),
                self._used_bytes())
        for key in txn.deletes:
            for suffix in ("", ".meta"):
                try:
                    os.unlink(self._file(key) + suffix)
                except FileNotFoundError:
                    pass
        for key, chunk, meta in txn.writes:
            chunk = unwrap(chunk)  # file write copies to media anyway
            path = self._file(key)
            tmp = path + ".tmp"
            with open(tmp, "wb") as f:
                f.write(chunk)
            os.replace(tmp, path)
            with open(path + ".meta.tmp", "w") as f:
                json.dump(meta.__dict__, f)
            os.replace(path + ".meta.tmp", path + ".meta")
        # legacy filestore: no omap support (BlueStore carries the PG log)
        if on_commit is not None:
            on_commit()

    def read(self, key: Key) -> Optional[Tuple[bytes, ShardMeta]]:
        path = self._file(key)
        try:
            with open(path, "rb") as f:
                chunk = f.read()
            with open(path + ".meta") as f:
                meta = ShardMeta(**json.load(f))
            return chunk, meta
        except FileNotFoundError:
            return None

    def list_objects(self, pool_id: int):
        prefix = f"{pool_id}__"
        for name in os.listdir(self.path):
            if name.startswith(prefix) and not name.endswith((".meta", ".tmp")):
                try:
                    _, oid_hex, shard = name.rsplit("__", 2)
                    yield bytes.fromhex(oid_hex).decode(), int(shard)
                except ValueError:
                    # foreign or legacy-named file in the store dir: never
                    # poison listing/repair for every other object
                    continue

    def list_pools(self):
        pools = set()
        for name in os.listdir(self.path):
            if name.endswith((".meta", ".tmp")):
                continue
            pid, sep, _ = name.partition("__")
            if sep and pid.isdigit():
                pools.add(int(pid))
        return sorted(pools)

    def _used_bytes(self) -> int:
        used = n = 0
        for name in os.listdir(self.path):
            if name.endswith((".meta", ".tmp")):
                continue
            try:
                used += os.stat(os.path.join(self.path, name)).st_size
                n += 1
            except OSError:
                pass
        self._last_count = n
        return used

    def statfs(self) -> Dict[str, int]:
        total = int(self.capacity_bytes or 0)
        used = self._used_bytes()
        return {"total": total, "used": used,
                "avail": max(0, total - used) if total else 0,
                "num_objects": getattr(self, "_last_count", 0)}


def shard_crc(chunk: bytes) -> int:
    """crc32 of a shard chunk (deep-scrub comparison value)."""
    from ceph_tpu.utils.checksum import checksum

    return checksum(chunk) & 0xFFFFFFFF
