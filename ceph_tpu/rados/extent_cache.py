"""Primary-side extent cache (reference src/osd/ExtentCache.{h,cc}).

The reference pins the stripe extents an in-flight RMW read/wrote so
back-to-back partial overwrites to one object pipeline instead of
re-reading (`reserve_extents_for_rmw` / `present_rmw_update`, used at
ECBackend.cc:1952,2070).  This cache is its role-equivalent at the
granularity the RMW path actually uses: per-object EXTENT maps, versioned
— a partial overwrite caches only the stripes it decoded and wrote, and
the next overlapping write serves its RMW read from those extents without
touching the shards.

Entries are versioned: a get at the wrong version misses (the object
moved under us — failover, recovery push, concurrent interval), and any
put at a newer version drops the stale extents.  Whole-object entries are
extents covering [0, size) with `full=True`, preserving the previous
whole-object behavior for reads and full writes.

A whole-object put copies nothing it can keep (`put_full`): the put path
pays for no reader that may never come.  So a cached run is a BUFFER —
`bytes`, or a read-only `memoryview` of a payload as the wire delivered
it — and a reader that needs `bytes` semantics (concatenation, hashing)
normalises where it reads.

A write at an offset into a cached whole object costs its stripes
(`patch_full`): the entry's runs are split around them (views of the old
runs on both sides, the new stripes between) and nothing of the rest is
copied, the first time or ever.  Every run stays immutable.  `get_full`
still returns the whole current object in one buffer: it joins a split
entry when somebody asks (one whole-object copy, kept as the single run
again), and the offset-write path, which needs the stripes only, asks
`get_whole` instead.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import List, Optional, Tuple

Key = Tuple[int, str]  # (pool_id, oid)


def keepable(data) -> bool:
    """True when keeping `data` itself is as good as keeping a copy:
    `bytes`, or a read-only flat byte view of the WHOLE of a buffer that
    owns its memory.  A writable view can change under its keeper; a view
    of part of something larger (a lane fragment of its group's assembly
    buffer) would pin the rest of it.  The cache below asks it for a put
    it keeps, the EC plan (ecutil._stripe_rows) for one it lets another
    thread read later."""
    if isinstance(data, bytes):
        return True
    if not (isinstance(data, memoryview) and data.readonly
            and data.format == "B" and data.ndim == 1 and data.contiguous):
        return False
    owner = data.obj
    return getattr(owner, "base", None) is None \
        and memoryview(owner).nbytes == data.nbytes


class _Entry:
    __slots__ = ("version", "extents", "full", "size")

    def __init__(self, version: int):
        self.version = version
        # sorted non-overlapping [start, bytes] runs
        self.extents: List[Tuple[int, bytes]] = []
        self.full = False  # extents cover the whole object
        self.size = 0  # object size when full; else last known size hint

    def insert(self, start: int, data: bytes) -> None:
        """Insert/overwrite a run, merging overlaps and adjacency."""
        merged: List[Tuple[int, bytes]] = []
        placed = False
        new_start, new_data = start, data
        for s, b in self.extents:
            e = s + len(b)
            if e < new_start or s > new_start + len(new_data):
                merged.append((s, b))
                continue
            # overlap/adjacent: splice the old run around the new bytes
            lo = min(s, new_start)
            pre = b[: max(0, new_start - s)]
            post = b[max(0, new_start + len(new_data) - s):]
            new_data = b"".join((pre, new_data, post))
            new_start = lo
        for i, (s, _b) in enumerate(merged):
            if s > new_start:
                merged.insert(i, (new_start, new_data))
                placed = True
                break
        if not placed:
            merged.append((new_start, new_data))
        self.extents = merged

    def read(self, start: int, length: int) -> Optional[bytes]:
        """The bytes of [start, start+length) iff FULLY covered, by one
        run or by runs that touch."""
        end = start + length
        if self.full and start >= self.size:
            return b""  # past EOF on a fully-known object reads as empty
        parts = []
        pos = start
        for s, b in self.extents:
            e = s + len(b)
            if e <= pos:
                continue
            if s > pos:
                break  # a hole
            parts.append(b[pos - s: min(end, e) - s])
            pos = min(end, e)
            if pos == end:
                break
        if pos < end and not (parts and self.full and pos == self.size):
            # (short tail of a fully-known object: zero-extend is NOT
            # valid for RMW reads — stripes past EOF are synthesized by
            # the caller — so what exists is returned)
            return None
        return parts[0] if len(parts) == 1 else b"".join(parts)


class ExtentCache:
    def __init__(self, max_objects: int = 64):
        self.max_objects = max_objects
        self._entries: "OrderedDict[Key, _Entry]" = OrderedDict()

    def _entry_for_put(self, key: Key, version: int) -> Optional[_Entry]:
        ent = self._entries.get(key)
        if ent is not None and ent.version > version:
            return None  # stale write-back: newer state already cached
        if ent is None or ent.version < version:
            ent = _Entry(version)
            self._entries[key] = ent
        self._entries.move_to_end(key)
        while len(self._entries) > self.max_objects:
            self._entries.popitem(last=False)
        return ent

    def put_full(self, key: Key, version: int, data) -> bool:
        """Cache the whole object.  Returns False when that cost a copy
        of `data`, True when it did not: `data` itself is cached (see
        `keepable`; the caller writes into it no more), or the put was
        stale and nothing is."""
        ent = self._entry_for_put(key, version)
        if ent is None:
            return True
        kept = keepable(data)
        run = data if kept else bytes(data)
        ent.extents = [(0, run)]
        ent.full = True
        ent.size = len(run)
        return kept

    def put_extent(self, key: Key, version: int, start: int,
                   data: bytes, size_hint: int = 0,
                   carry_from: int = 0) -> None:
        """Cache one extent at `version`.  ``carry_from``: when the cached
        entry sits at exactly that (older) version, upgrade it in place
        and KEEP its other extents — valid only when the caller knows the
        version step changed nothing outside this extent (the primary's
        own RMW write, serialized per PG).  ``size_hint`` records the
        object size the caller learned (shard metadata) so later RMW
        planners need not re-stat."""
        ent = self._entries.get(key)
        if (carry_from and ent is not None and not ent.full
                and ent.version == carry_from and version > carry_from):
            ent.version = version
            self._entries.move_to_end(key)
        else:
            ent = self._entry_for_put(key, version)
            if ent is None:
                return
        ent.insert(start, bytes(data))
        if ent.full:
            ent.size = max(ent.size, start + len(data))
        elif size_hint:
            ent.size = max(ent.size, size_hint)

    def patch_full(self, key: Key, base_version: int, version: int,
                   start: int, data: bytes) -> bool:
        """Bring a cached WHOLE object from `base_version` to `version`
        by laying `data` over [start, start + len(data)): valid only
        when the caller knows the version step changed nothing else (the
        primary's own offset write, serialized per PG).  The runs are
        split around the extent, nothing is copied; `data` is kept as it
        is (the caller writes into it no more).  False where there is no
        such entry (evicted, moved on, not whole): nothing was done."""
        ent = self._entries.get(key)
        if ent is None or not ent.full or ent.version != base_version \
                or version <= base_version:
            return False
        end = start + len(data)
        runs: List[Tuple[int, bytes]] = []
        for s, b in ent.extents:
            e = s + len(b)
            if e <= start or s >= end:
                runs.append((s, b))
                continue
            flat = memoryview(b)
            if s < start:
                runs.append((s, flat[:start - s]))
            if e > end:
                runs.append((end, flat[end - s:]))
        if start > ent.size:
            runs.append((ent.size, bytes(start - ent.size)))
        runs.append((start, data))
        runs.sort(key=lambda run: run[0])
        ent.extents = runs
        ent.size = max(ent.size, end)
        ent.version = version
        self._entries.move_to_end(key)
        return True

    def get_full(self, key: Key) -> Optional[Tuple[int, bytes]]:
        ent = self._entries.get(key)
        if ent is None or not ent.full:
            return None
        self._entries.move_to_end(key)
        if len(ent.extents) > 1:
            # split by offset writes: joined for the first reader who
            # wants it whole, and kept so
            ent.extents = [(0, b"".join(run for _s, run in ent.extents))]
        return ent.version, ent.extents[0][1] if ent.extents else b""

    def get_whole(self, key: Key, start: int,
                  length: int) -> Optional[Tuple[int, bytes, int]]:
        """(version, bytes, object size) of [start, start+length), cut
        short at the object's end, iff the WHOLE object is cached: what
        an offset write needs of it, at the cost of the range."""
        ent = self._entries.get(key)
        if ent is None or not ent.full:
            return None
        got = ent.read(start, length)
        if got is None:
            return None
        self._entries.move_to_end(key)
        return ent.version, got, ent.size

    def get_range(self, key: Key, start: int,
                  length: int) -> Optional[Tuple[int, bytes, int]]:
        """(version, bytes, size_hint) for [start, start+length) when
        fully cached (size_hint 0 = unknown)."""
        ent = self._entries.get(key)
        if ent is None:
            return None
        got = ent.read(start, length)
        if got is None:
            return None
        self._entries.move_to_end(key)
        return ent.version, got, ent.size

    def drop(self, key: Key) -> None:
        self._entries.pop(key, None)

    def clear(self) -> None:
        self._entries.clear()

    def __len__(self) -> int:
        return len(self._entries)
