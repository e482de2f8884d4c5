"""Paged resident store: page-table HBM residency for the cache tier.

Role-equivalent of the KV-cache page pool in a production inference
stack (the Ragged Paged Attention idiom, arXiv:2604.15464: fixed-size
pages, a per-object page table, ragged last pages) applied to EC shard
residency.  A store of ONE monolithic device buffer per resident (the
r10 design), its width pow2-bucketed for the encode lane, lets mixed
object sizes fragment the budget (a 68 KiB stripe pays for 128 KiB) and
makes eviction all-or-nothing per object.  Here the budget is
ONE preallocated u32 slab carved into fixed-size pages
(``osd_tier_page_bytes``): a resident's packed-bit plane words are
TRIMMED to their true width and flattened row-major across a page table
(ordered page ids, ragged last page), so

- millions of mixed-size objects share the pool at O(page) granularity
  (the pow2 pad never lands; ``frag_saved_bytes`` gauges the win),
- eviction frees exactly the pages it needs — including PARTIAL
  eviction: ``shed_parity`` drops the page suffix holding the parity
  rows while the data-row prefix keeps serving reads,
- every page carries a DIRTY bit, the substrate for writeback cache
  mode: a writeback install pins a :class:`WritebackRecord` (the
  deferred local store apply) with its dirty pages, ``drop`` refuses
  dirty entries until the owner flushes (flush-before-evict), and
  ``clear_dirty`` is generation-tokened so a flush that raced an
  overwrite can never mark the NEWER write clean.

The slab is committed lazily (fixed-size sub-slabs allocate on first
touch) and has TWO arms behind one page table:

- the HOST arm: sub-slabs are numpy arrays, installs/gathers are
  memcpys, the pack/unpack device boundaries
  (``to_packedbit``/``from_packedbit``) are paid at the page-table
  edge.  Byte-identical to the r20 behavior, and the only arm when no
  device backend is live.
- the DEVICE arm (``osd_tier_device_slab`` / ``CEPH_TPU_DEVICE_SLAB``,
  auto-on when a real device backend is live): sub-slabs are
  ``jax.Array``s and installs/gathers run through the jitted,
  donation-annotated scatter/take kernels in ``ceph_tpu/ops/slab.py``
  (the Ragged Paged Attention idiom, arXiv:2604.15464).  A promote's
  pack->install is ONE async H2D (``h2d_installs``); a queue-produced
  resident (``all_bits`` from the encode lane) installs device-native
  with ZERO host copies (``device_installs``); gathers stay on device
  and feed decode through the jitted ``from_packedbit`` path, so bytes
  leave HBM only at the declared exit boundaries (``d2h_gathers`` —
  see ``SLAB_IO_BOUNDARY`` and the codec/slab-host-roundtrip lint
  rule).  Eviction, dirty bits, shed_parity and the memo are PAGE
  TABLE bookkeeping — identical across both arms by construction.

Thread-safe under one mutex; the
OSD event loop, the batching worker, and tests may touch it
concurrently.  Device kernel dispatches run under that mutex too — the
lock sequences donated installs against gathers, which is what makes
donation safe (a gather can only ever see the pre- or post-install
slab reference, never the donated buffer after it was consumed).
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass, field
from collections import OrderedDict
from typing import Any, Dict, Iterable, List, Optional, Tuple

import numpy as np

from ceph_tpu.common import tracing
from ceph_tpu.common.perf_counters import PerfCounters, PerfCountersBuilder

_SLAB_SHIFT = 8  # 2**8 pages per lazily-committed sub-slab

# functions allowed to materialize slab-gather results on the host (the
# codec/slab-host-roundtrip lint rule's per-module exemption list): the
# pagestore's own packed-byte exit is read()
SLAB_IO_BOUNDARY = ("read",)


def device_slab_resolved(flag: Optional[bool] = None) -> bool:
    """Whether the store's device arm engages.  CEPH_TPU_DEVICE_SLAB=1
    forces it on (CPU-backend tests exercise the jitted kernels on
    jax-cpu arrays), =0 forces the host arm; otherwise the config flag
    (``osd_tier_device_slab``; False pins the host arm) gates the AUTO
    rule — device arm only when a real device backend is live
    (jaxdev.accelerator_live, the shared_batching_queue discipline)."""
    env = os.environ.get("CEPH_TPU_DEVICE_SLAB", "")
    if env == "1":
        return True
    if env == "0":
        return False
    if flag is not None and not flag:
        return False
    from ceph_tpu.utils.jaxdev import accelerator_live

    return accelerator_live()


@dataclass
class WritebackRecord:
    """The flush contract a writeback install pins with its dirty pages:
    everything the owner needs to replay the DEFERRED local store apply
    later — byte-identically to the write-through path — without the
    original write in hand.  Opaque to the store itself."""

    pool_id: int
    oid: str
    pg: int
    version: int
    object_size: int
    hinfo: bytes
    shards: Tuple[int, ...]           # local shards whose apply deferred
    crcs: Dict[int, int] = field(default_factory=dict)


@dataclass
class CacheDirtyRecord:
    """The flush contract a fast-ack writeback put pins with its RAW
    dirty object (w=0 entry, whole-object bytes — no EC encode happened
    yet): the k+m encode and sub-write fan-out are deferred entirely to
    the flush path.  ``primary`` names the OSD that installed the write
    (on a replica's adopted copy it is the writeback primary, not the
    holder); ``peers`` is the full cache replica set, primary included —
    the new primary replays the freshest copy from it after a primary
    death.  Generation-tokened and version-fenced exactly like
    :class:`WritebackRecord`; opaque to the store itself."""

    pool_id: int
    oid: str
    pg: int
    version: int
    object_size: int
    primary: int
    peers: Tuple[int, ...] = ()


class _Entry:
    __slots__ = ("pages", "dtype", "rows", "cols", "itemsize", "w",
                 "n_rows", "meta", "trim", "data_rows", "mono_bytes",
                 "total_words", "live_pages", "dirty", "dirty_info",
                 "dirty_since", "dirty_gen")


def build_pagestore_perf() -> PerfCounters:
    """The `pagestore` counter set (perf dump -> mgr /metrics -> BENCH)."""
    return (
        PerfCountersBuilder("pagestore")
        .add_u64_counter("admit", "residents installed into pages")
        .add_u64_counter("hit", "resident lookups served")
        .add_u64_counter("miss", "lookups that fell to the cold path")
        .add_u64_counter("evict", "whole residents evicted")
        .add_u64_counter("page_evictions", "pages freed by eviction "
                                           "(partial sheds included)")
        .add_u64_counter("parity_sheds",
                         "partial evictions that dropped only the "
                         "parity-row page suffix (data keeps serving)")
        .add_u64_counter("writeback_installs",
                         "dirty installs that deferred a local store "
                         "apply to flush")
        .add_u64_counter("flushes", "dirty residents flushed clean")
        .add_u64_counter("flush_bytes", "shard bytes written back by "
                                        "flushes")
        .add_u64_counter("evict_refused_dirty",
                         "drops refused because pages were dirty "
                         "(flush-before-evict held)")
        .add_u64_counter("install_refused",
                         "installs refused (pool full of dirty or "
                         "oversized resident)")
        .add_u64("pages_total", "page pool size (gauge)")
        .add_u64("pages_used", "pages currently owned by residents "
                               "(gauge)")
        .add_u64("dirty_pages", "pages carrying unflushed writeback "
                                "data (gauge)")
        .add_u64("dirty_bytes", "page bytes carrying unflushed "
                                "writeback data (gauge)")
        .add_u64("resident_bytes", "page bytes held by residents "
                                   "(gauge)")
        .add_u64("entries", "resident objects (gauge)")
        .add_u64("memo_bytes", "exit-boundary memo footprint, "
                               "page-rounded (gauge)")
        .add_u64("frag_saved_bytes",
                 "bytes the paged layout saves vs the monolithic "
                 "pow2-bucketed layout for the live residents (gauge, "
                 "floored at 0)")
        .add_u64("device_slabs", "committed device sub-slabs (gauge; 0 "
                                 "on the host arm)")
        .add_u64_counter("h2d_installs",
                         "installs whose page image crossed host->device "
                         "as ONE async copy (host-sourced bytes)")
        .add_u64_counter("device_installs",
                         "installs consumed device-native (queue-"
                         "produced residents; zero host copies)")
        .add_u64_counter("install_programs",
                         "device programs launched by installs: one per "
                         "sub-slab an install touched, so over the two "
                         "counters above it reads sub-slabs per install "
                         "(more means an eager op crept back)")
        .add_u64_counter("install_page_bytes",
                         "page bytes those programs landed (pages x page "
                         "size: what an install has to move at least)")
        .add_u64_counter("d2h_gathers",
                         "device->host materializations of gathered "
                         "slab bytes at the declared exit boundaries")
        .add_time_avg("pack_s", "device->host pack seconds at the exit "
                                "boundary")
        .add_time_avg("unpack_s", "host->device unpack seconds at "
                                  "admission")
        .create_perf_counters()
    )


class PagedResidentStore:
    """The residency manager behind the tier (the residency protocol
    ecutil's planar_* helpers and the OSD tier paths speak:
    put_planar/get_planar/touch/gather_rows/drop/peek/memo), backed by
    the page pool above."""

    def __init__(self, capacity_bytes: int = 256 << 20,
                 page_bytes: int = 64 << 10, queue: Optional[Any] = None,
                 device: Optional[bool] = None,
                 prewarm: bool = False):
        from ceph_tpu.common.lockdep import make_mutex

        page_bytes = max(256, int(page_bytes))
        page_bytes -= page_bytes % 4  # whole u32 words per page
        self.page_bytes = page_bytes
        self.page_words = page_bytes // 4
        self._pages_total = max(1, int(capacity_bytes) // page_bytes)
        self.queue = queue
        self._lock = make_mutex("pagestore")
        # arm selection: env override wins both ways, then an EXPLICIT
        # constructor choice (tests force the device arm on jax-cpu),
        # then the auto rule (device arm iff a real backend is live);
        # callers resolving a config flag pass device=None (auto) or
        # False (pinned host) via device_slab_resolved
        env = os.environ.get("CEPH_TPU_DEVICE_SLAB", "")
        if env in ("0", "1"):
            self.device_arm = env == "1"
        elif device is not None:
            self.device_arm = bool(device)
        else:
            self.device_arm = device_slab_resolved(None)
        self._slabs: List[Optional[np.ndarray]] = []
        self._dev_slabs: List[Optional[Any]] = []
        self.h2d_installs = 0
        self.device_installs = 0
        self.d2h_gathers = 0
        self._free: List[int] = []
        self._next_page = 0
        self._entries: "OrderedDict[Any, _Entry]" = OrderedDict()
        self._memo: Dict[Any, Tuple[Any, Any]] = {}
        self.memo_bytes = 0          # page-rounded (the r10 gauge could
        self._memo_raw: Dict[Any, int] = {}   # drift from residency)
        self._pages_used = 0
        self._dirty_page_count = 0
        self._gen = 0  # install generations: flush tokens never collide
        self._mono_bytes = 0         # monolithic-equivalent footprint
        self.admits = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.perf = build_pagestore_perf()
        self.perf.set("pages_total", self._pages_total)
        self.perf.resync = self._resync_gauges
        self.prewarmed = False
        if prewarm and self.device_arm:
            # compile the gather kernels for this page geometry (every
            # pow2 row bucket) at store build — a served window must
            # never pay an in-line XLA compile.  The install compiles
            # per source geometry, at the first install of each
            from ceph_tpu.ops.slab import prewarm as _slab_prewarm

            _slab_prewarm(self.page_words)
            self.prewarmed = True

    # -- capacity ------------------------------------------------------------

    @property
    def capacity_bytes(self) -> int:
        return self._pages_total * self.page_bytes

    @capacity_bytes.setter
    def capacity_bytes(self, value: int) -> None:
        # the budget is one shared pool: it only ever GROWS (the
        # shared_planar_store raise-the-budget rule); sub-slabs commit
        # lazily so raising the ceiling costs nothing up front
        with self._lock:
            self._pages_total = max(self._pages_total,
                                    max(1, int(value) // self.page_bytes))
            self.perf.set("pages_total", self._pages_total)

    @property
    def pages_total(self) -> int:
        return self._pages_total

    @property
    def pages_used(self) -> int:
        return self._pages_used

    @property
    def resident_bytes(self) -> int:
        return self._pages_used * self.page_bytes

    @property
    def dirty_pages(self) -> int:
        return self._dirty_page_count

    @property
    def dirty_bytes(self) -> int:
        return self._dirty_page_count * self.page_bytes

    # -- page pool (callers hold the lock) -----------------------------------

    def _page(self, pid: int) -> np.ndarray:
        slab = pid >> _SLAB_SHIFT
        while len(self._slabs) <= slab:
            self._slabs.append(None)
        if self._slabs[slab] is None:
            self._slabs[slab] = np.empty(
                (1 << _SLAB_SHIFT, self.page_words), dtype=np.uint32)
        return self._slabs[slab][pid & ((1 << _SLAB_SHIFT) - 1)]

    def _dev_slab(self, s: int):
        """Lazily-committed device sub-slab ``s`` (lock held).  The
        device arm's sibling of :meth:`_page`'s host commit — zeroed so
        the ragged install tail is well-defined."""
        from ceph_tpu.ops.slab import new_subslab

        while len(self._dev_slabs) <= s:
            self._dev_slabs.append(None)
        if self._dev_slabs[s] is None:
            self._dev_slabs[s] = new_subslab(1 << _SLAB_SHIFT,
                                             self.page_words)
            self.perf.set("device_slabs", self._device_slab_count())
        return self._dev_slabs[s]

    def _device_slab_count(self) -> int:
        return sum(1 for x in self._dev_slabs if x is not None)

    def _available_pages(self) -> int:
        return len(self._free) + (self._pages_total - self._next_page)

    def _alloc_page(self) -> Optional[int]:
        if self._free:
            return self._free.pop()
        if self._next_page < self._pages_total:
            pid = self._next_page
            self._next_page += 1
            return pid
        return None

    def _free_entry_pages(self, e: _Entry) -> int:
        freed = 0
        for i, pid in enumerate(e.pages):
            if pid is not None:
                self._free.append(pid)
                e.pages[i] = None
                freed += 1
        self._pages_used -= freed
        self._dirty_page_count -= len(e.dirty)
        e.dirty.clear()
        e.live_pages = 0
        return freed

    def _remove_entry(self, key: Any) -> int:
        """Free a key's pages and bookkeeping; lock held.  Returns pages
        freed."""
        e = self._entries.pop(key, None)
        if e is None:
            return 0
        freed = self._free_entry_pages(e)
        self._mono_bytes -= e.mono_bytes
        self._memo_discard(key)
        return freed

    def _sync_gauges(self) -> None:
        """Lock held."""
        self.perf.set("pages_used", self._pages_used)
        self.perf.set("dirty_pages", self._dirty_page_count)
        self.perf.set("dirty_bytes",
                      self._dirty_page_count * self.page_bytes)
        self.perf.set("resident_bytes",
                      self._pages_used * self.page_bytes)
        self.perf.set("entries", len(self._entries))
        self.perf.set("memo_bytes", self.memo_bytes)
        self.perf.set("pages_total", self._pages_total)
        self.perf.set("frag_saved_bytes", max(0, self.frag_saved_signed))
        self.perf.set("device_slabs", self._device_slab_count())

    def _resync_gauges(self) -> None:
        with self._lock:
            self._sync_gauges()

    @property
    def frag_saved_signed(self) -> int:
        """Monolithic-equivalent footprint minus actual page footprint.
        Positive = the pow2 pad the paged layout never allocated minus
        the ragged-tail waste it did; can go (slightly) negative for
        tiny residents whose tail waste exceeds their pad."""
        return self._mono_bytes - self._pages_used * self.page_bytes

    # -- install -------------------------------------------------------------

    @staticmethod
    def _trim_cols(dtype: np.dtype, cols: int, trim: Optional[int]) -> int:
        """Array columns to keep for a pre-pad packed byte width of
        ``trim``: u32 plane words carry 32 packed byte columns each;
        int8 plane columns are byte columns, rounded up to whole words
        so any bit-row range stays word-aligned in the flattened pool."""
        if not trim or trim <= 0:
            return cols
        if np.dtype(dtype) == np.uint32:
            return min(cols, -(-int(trim) // 32))
        return min(cols, ((int(trim) + 3) // 4) * 4)

    def _install_pages_locked(self, src, cols: int, total_words: int,
                              from_device: bool) -> List[Optional[int]]:
        """Device-arm install (lock held): allocate page ids, land the
        source as page rows with ONE fused program per touched sub-slab
        (ceph_tpu.ops.slab.slab_install: trim to ``cols``, flatten, zero
        pad, page view, row selection and donated scatter all inside
        it), and swap the donated sub-slab references under the lock.
        Nothing else here touches the device: the index arrays are
        numpy.  A device-native ``src`` (the queue's ``u32[rows,
        cols_full]``) never touches host memory; a host-sourced one is
        the flat word image, which crosses h2d as ONE copy of the whole
        zero-padded page image and goes through the same program.
        Returns the page-id list."""
        from ceph_tpu.ops.slab import bucket_rows, slab_install

        npages = -(-total_words // self.page_words) if total_words else 0
        pages: List[Optional[int]] = []
        for _ in range(npages):
            pid = self._alloc_page()
            assert pid is not None  # _available_pages said so
            pages.append(pid)
        if not npages:
            return pages
        if from_device:
            self.device_installs += 1
            self.perf.inc("device_installs")
        else:
            import jax

            # pow2 rows: the image's shape is the install's compile key
            host = np.zeros((bucket_rows(npages), self.page_words),
                            dtype=np.uint32)
            host.reshape(-1)[:total_words] = src
            src, cols = jax.device_put(host), self.page_words  # the ONE h2d
            self.h2d_installs += 1
            self.perf.inc("h2d_installs")
        pids = np.array(pages, dtype=np.int32)
        slab_of = pids >> _SLAB_SHIFT
        dst = pids & ((1 << _SLAB_SHIFT) - 1)
        touched = np.unique(slab_of).tolist()
        for s in touched:
            order = np.flatnonzero(slab_of == s)
            # the old sub-slab reference is dropped HERE, under the
            # lock, before any gather can observe it — the donation
            # safety contract (slab.py docstring)
            self._dev_slabs[s] = slab_install(self._dev_slab(s), src, cols,
                                              order, dst[order])
        self.perf.inc("install_programs", len(touched))
        self.perf.inc("install_page_bytes", npages * self.page_bytes)
        return pages

    @tracing.sectioned("store", "resident_install")
    def put_planar(self, key: Any, bits, w: int = 8,
                   n_rows: Optional[int] = None, meta: Any = None,
                   trim: Optional[int] = None,
                   data_rows: Optional[int] = None,
                   dirty_rows: Optional[Iterable[Tuple[int, int]]] = None,
                   dirty_info: Any = None,
                   now: Optional[float] = None) -> bool:
        """Install a resident into pages.  ``trim`` (pre-pad packed byte
        width) drops the encode lane's pow2 pad before paging — the
        fragmentation win.  ``data_rows`` marks the bit-row prefix that
        is data (shed_parity boundary).  ``dirty_rows`` marks bit-row
        ranges whose backing-store apply is DEFERRED (writeback);
        ``dirty_info`` carries the owner's flush contract.  Returns
        False — nothing installed — when the pool cannot fit the
        resident even after evicting every clean colder entry (the
        caller falls back to the uninstalled path; refusal is counted,
        never an error)."""
        from_device = False
        if self.device_arm:
            from ceph_tpu.ops.slab import is_device_array

            from_device = (is_device_array(bits)
                           and str(bits.dtype) == "uint32")
        if from_device:
            # device-native install: a queue-produced resident (the
            # encode lane's packed-bit planes) never bounces through
            # host numpy, and nothing is dispatched here — the trim and
            # the flatten happen inside the install's one program, which
            # takes the array as the queue produced it
            rows, cols_full = int(bits.shape[0]), int(bits.shape[1])
            if n_rows is None:
                n_rows = rows // w
            itemsize = 4
            dtype = np.dtype(np.uint32)
            mono_bytes = rows * cols_full * itemsize
            cols = self._trim_cols(dtype, cols_full, trim)
            src = bits
        else:
            arr = np.asarray(bits)
            if n_rows is None:
                n_rows = arr.shape[0] // w
            rows, cols_full = int(arr.shape[0]), int(arr.shape[1])
            itemsize = arr.dtype.itemsize
            mono_bytes = rows * cols_full * itemsize
            cols = self._trim_cols(arr.dtype, cols_full, trim)
            if cols < cols_full:
                arr = arr[:, :cols]
            if np.dtype(arr.dtype) != np.uint32 and cols % 4:
                # non-u32 rows must stay word-aligned in the flattened
                # pool (gather addresses bit-rows as cols*itemsize//4
                # words) — pad the row width up to whole words; `trim`
                # keeps the true byte width for read()'s final slice
                pad = 4 - cols % 4
                arr = np.pad(np.asarray(arr), ((0, 0), (0, pad)))
                cols += pad
            dtype = np.dtype(arr.dtype)
            src = np.ascontiguousarray(arr).reshape(-1)
            if src.dtype != np.uint32:
                src = src.view(np.uint32)  # rows % 4 == 0 (w >= 4)
        total_words = rows * cols * itemsize // 4
        npages = max(1, -(-total_words // self.page_words))
        with self.perf.time_avg("unpack_s"), self._lock:
            self._remove_entry(key)
            if npages > self._pages_total:
                self.perf.inc("install_refused")
                self._sync_gauges()
                return False
            while self._available_pages() < npages:
                victim = None
                for k, e in self._entries.items():  # LRU-oldest first
                    if not e.dirty:
                        victim = k
                        break
                if victim is None:
                    self.perf.inc("install_refused")
                    self._sync_gauges()
                    return False
                freed = self._remove_entry(victim)
                self.evictions += 1
                self.perf.inc("evict")
                self.perf.inc("page_evictions", freed)
            e = _Entry()
            if self.device_arm:
                e.pages = self._install_pages_locked(
                    src, cols, total_words, from_device)
            else:
                e.pages = []
                off = 0
                while off < total_words:
                    pid = self._alloc_page()
                    assert pid is not None  # _available_pages said so
                    n = min(self.page_words, total_words - off)
                    self._page(pid)[:n] = src[off:off + n]
                    e.pages.append(pid)
                    off += n
            e.dtype = dtype
            e.rows = rows
            e.cols = cols
            e.itemsize = itemsize
            e.w = w
            e.n_rows = n_rows
            e.meta = meta
            e.trim = trim
            e.data_rows = data_rows
            e.mono_bytes = mono_bytes
            e.total_words = total_words
            e.live_pages = len(e.pages)
            e.dirty = set()
            e.dirty_info = dirty_info
            e.dirty_since = time.monotonic() if now is None else now
            self._gen += 1
            e.dirty_gen = self._gen
            self._pages_used += len(e.pages)
            self._mono_bytes += mono_bytes
            if dirty_rows:
                row_words = cols * itemsize // 4
                for r0, r1 in dirty_rows:
                    p0 = (r0 * row_words) // self.page_words
                    p1 = -(-(r1 * row_words) // self.page_words)
                    e.dirty.update(range(p0, min(p1, len(e.pages))))
                self._dirty_page_count += len(e.dirty)
            self._entries[key] = e
            self._entries.move_to_end(key)
            self.admits += 1
            self._sync_gauges()
        self.perf.inc("admit")
        if dirty_rows and e.dirty:
            self.perf.inc("writeback_installs")
        return True

    # -- raw dirty objects (writeback fast-ack path) -------------------------

    @tracing.sectioned("store", "resident_install_raw")
    def put_raw(self, key: Any, data: bytes, meta: Any = None,
                dirty_info: Any = None,
                now: Optional[float] = None) -> bool:
        """Install the WHOLE-OBJECT bytes as a raw dirty resident — the
        writeback fast-ack path's unit of replication (no EC encode has
        happened; the flush path owns the k+m destage).  Layout: one
        uint8 bit-row padded to a whole word, ``w=0`` as the raw
        sentinel (planar_rows/planar_shard_bytes see a zero-height
        gather range and fall through; ``trim`` keeps the true byte
        length).  Every page is dirty.  Same refusal contract as
        put_planar."""
        raw = np.frombuffer(bytes(data), dtype=np.uint8)
        if len(raw) % 4:
            raw = np.pad(raw, (0, 4 - len(raw) % 4))
        return self.put_planar(key, raw.reshape(1, -1), w=0, n_rows=1,
                               meta=meta, trim=len(data),
                               dirty_rows=[(0, 1)], dirty_info=dirty_info,
                               now=now)

    def is_raw(self, key: Any) -> bool:
        with self._lock:
            e = self._entries.get(key)
            return e is not None and e.w == 0

    @tracing.sectioned("store", "resident_read_raw")
    def read_raw(self, key: Any) -> Optional[bytes]:
        """The raw entry's object bytes (None when absent, partial, or
        not a raw entry).  On the device arm the single materialization
        here is the declared d2h exit."""
        with self._lock:
            e = self._entries.get(key)
            if e is None or e.w != 0:
                return None
            trim = e.trim
            bits = self._gather_locked(e, 0, e.rows)
        if bits is None:
            return None
        out = np.asarray(bits).view(np.uint8).reshape(-1)
        self.note_d2h()
        return out[:trim].tobytes()

    # -- lookup --------------------------------------------------------------

    def _gather_locked(self, e: _Entry, r0: int, r1: int):
        row_words = e.cols * e.itemsize // 4
        w0, w1 = r0 * row_words, r1 * row_words
        if w1 > e.total_words or w0 < 0 or w1 <= w0:
            return None
        p0, p1 = w0 // self.page_words, -(-w1 // self.page_words)
        span = e.pages[p0:p1]
        if any(p is None for p in span):
            return None
        if self.device_arm:
            return self._gather_device_locked(e, r0, r1, w0, p0, span)
        out = np.empty(w1 - w0, dtype=np.uint32)
        pos = 0
        for i, pid in enumerate(span):
            page = self._page(pid)
            start = (w0 - p0 * self.page_words) if i == 0 else 0
            avail = min(self.page_words,
                        e.total_words - (p0 + i) * self.page_words)
            take = min(avail - start, (w1 - w0) - pos)
            out[pos:pos + take] = page[start:start + take]
            pos += take
        if np.dtype(e.dtype) != np.uint32:
            return out.view(e.dtype).reshape(r1 - r0, e.cols)
        return out.reshape(r1 - r0, e.cols)

    def _gather_device_locked(self, e: _Entry, r0: int, r1: int,
                              w0: int, p0: int, span: List[int]):
        """Device-arm gather (lock held): one jitted program per touched
        sub-slab lands the span's pages in one buffer, one more cuts the
        bit-rows out of it (ceph_tpu.ops.slab) — no eager op, so a span
        split over sub-slabs in a new way compiles nothing, and neither
        does a resident of a new width: the cut is keyed by the pow2
        bucket of the row width and comes back THAT wide, zero past the
        resident's own columns.  Every reader packs the rows and trims
        the bytes on the host, to the width it recorded.  The result
        is a fresh device buffer (never a slab view) — it stays valid
        across later donated installs and feeds the jitted decode path
        without leaving HBM; the host exit is read()/ecutil's
        ``_pack_rows`` (counted as ``d2h_gathers`` via note_d2h)."""
        from ceph_tpu.ops.slab import slab_gather, span_rows

        pids = np.array(span, dtype=np.int32)
        pages = slab_gather(self._dev_slab, pids >> _SLAB_SHIFT,
                            pids & ((1 << _SLAB_SHIFT) - 1))
        return span_rows(pages, w0 - p0 * self.page_words, r1 - r0,
                         e.cols * e.itemsize // 4,
                         bool(np.dtype(e.dtype) != np.uint32))

    @tracing.sectioned("store", "resident_gather")
    def gather_rows(self, key: Any, r0: int, r1: int):
        """[r1-r0, cols] array gathered from the page table — on the
        device arm a device array whose columns are zero-padded to their
        pow2 bucket (a new width compiles nothing; the reader trims after
        its pack) — or None
        when the entry is absent or any needed page was evicted (a
        partial resident can still serve any fully-covered row range —
        the data-row prefix after a parity shed).  No LRU side effects
        (``touch`` owns those)."""
        with self._lock:
            e = self._entries.get(key)
            if e is None:
                return None
            return self._gather_locked(e, r0, r1)

    @tracing.sectioned("store", "resident_probe")
    def touch(self, key: Any):
        """(w, n_rows, meta) with LRU refresh + hit/miss counting — the
        read path's entry probe, materializing nothing."""
        with self._lock:
            e = self._entries.get(key)
            if e is None:
                self.misses += 1
            else:
                self._entries.move_to_end(key)
                self.hits += 1
        self.perf.inc("hit" if e is not None else "miss")
        return None if e is None else (e.w, e.n_rows, e.meta)

    def entry_info(self, key: Any):
        """(w, n_rows, meta) without LRU/counter side effects."""
        with self._lock:
            e = self._entries.get(key)
        return None if e is None else (e.w, e.n_rows, e.meta)

    def resident_meta(self, key: Any):
        """The entry's caller meta (the OSD stores (version, n_cols,
        object_size)), or None — the policy probe shape."""
        info = self.entry_info(key)
        return None if info is None else info[2]

    def get_planar(self, key: Any):
        """(bits, w, n_rows, meta) or None; refreshes LRU position.
        Gathers the WHOLE resident — None when partial (parity shed);
        bucket-wide on the device arm, as gather_rows."""
        got = self.touch(key)
        if got is None:
            return None
        w, n_rows, meta = got
        with self._lock:
            e = self._entries.get(key)
            if e is None:
                return None
            bits = self._gather_locked(e, 0, e.rows)
        if bits is None:
            return None
        return (bits, w, n_rows, meta)

    def peek(self, key: Any):
        """get_planar without LRU order / counter side effects."""
        with self._lock:
            e = self._entries.get(key)
            if e is None:
                return None
            bits = self._gather_locked(e, 0, e.rows)
        if bits is None:
            return None
        return (bits, e.w, e.n_rows, e.meta)

    def __contains__(self, key: Any) -> bool:
        with self._lock:
            return key in self._entries

    def entry_nbytes(self, key: Any) -> int:
        """Live page footprint of one entry (0 when absent)."""
        with self._lock:
            e = self._entries.get(key)
            return e.live_pages * self.page_bytes if e is not None else 0

    def entries_snapshot(self) -> List[Tuple[Any, int]]:
        """(key, page-footprint bytes) in LRU order, oldest first — the
        tier agent's eviction-candidate input."""
        with self._lock:
            return [(k, e.live_pages * self.page_bytes)
                    for k, e in self._entries.items()]

    # -- host boundary (tests and chip_smoke: bytes in, bytes out) -----------

    def admit(self, key: Any, rows: np.ndarray, w: int = 8,
              meta: Any = None, layout: str = "planes"):
        """Unpack packed [n, B] uint8 rows and keep them page-resident
        under `key`; returns the resident bit-rows.  layout="planes"
        stores int8 bit-planes (any w); "packedbit" stores u32 plane
        words (w=8 only, 1/8th the footprint — the production layout),
        padding B out to whole words and trimming on read."""
        if layout == "packedbit":
            from ceph_tpu.ops.gf2 import to_packedbit

            assert w == 8, "packed-bit residency is the w=8 byte layout"
            B = rows.shape[1]
            buf = np.ascontiguousarray(rows)
            if B % 32:
                buf = np.pad(buf, ((0, 0), (0, 32 - B % 32)))
            bits = to_packedbit(buf)
            self.put_planar(key, bits, w=w, n_rows=rows.shape[0],
                            meta=meta, trim=B)
        else:
            from ceph_tpu.ops.gf2 import to_planar

            bits = to_planar(np.ascontiguousarray(rows), w)
            self.put_planar(key, bits, w=w, n_rows=rows.shape[0],
                            meta=meta, trim=rows.shape[1])
        return bits

    def note_d2h(self) -> None:
        """Count ONE device->host materialization at a declared exit
        boundary (this module's read(); ecutil's ``_pack_rows``
        callers).  No-op on the host arm — nothing left the device."""
        if self.device_arm:
            self.d2h_gathers += 1
            self.perf.inc("d2h_gathers")

    def read(self, key: Any) -> Optional[np.ndarray]:
        """Pack the resident rows back to [n, B] uint8 host bytes; None
        when absent or partial.  On the device arm the gather feeds the
        jitted unpack on device and np.asarray here is the single d2h
        (the SLAB_IO_BOUNDARY exit)."""
        got = self.get_planar(key)
        if got is None:
            return None
        bits, w, n_rows, _meta = got
        with self._lock:
            e = self._entries.get(key)
            trim = e.trim if e is not None else None
            cols = e.cols if e is not None else bits.shape[1]
        if w == 0:
            # raw whole-object entry (put_raw): no planar decode exists;
            # the single uint8 bit-row IS the bytes
            out = np.asarray(bits).view(np.uint8).reshape(1, -1)
            self.note_d2h()
            return out if trim is None else out[:, :trim]
        if np.dtype(bits.dtype) == np.uint32:
            from ceph_tpu.ops.gf2 import from_packedbit

            with self.perf.time_avg("pack_s"):
                out = np.asarray(from_packedbit(bits, n_rows))
        else:
            from ceph_tpu.ops.gf2 import from_planar

            with self.perf.time_avg("pack_s"):
                out = np.asarray(from_planar(bits, w, n_rows))
        self.note_d2h()
        if trim is None:  # the device arm gathers bucket-wide
            trim = out.shape[1] * cols // bits.shape[1]
        return out[:, :trim]

    # -- eviction ------------------------------------------------------------

    @tracing.sectioned("store", "resident_evict")
    def drop(self, key: Any, force: bool = False) -> bool:
        """Remove `key` if resident; True when an entry was actually
        dropped.  A DIRTY entry refuses (flush-before-evict: writeback
        pages must never be the only copy of acked data) unless
        ``force`` — deletes and overwrite-failure cleanup force, because
        there the data itself is going away.  Dropping an absent key is
        a supported no-op (the agent/LRU race rule)."""
        with self._lock:
            e = self._entries.get(key)
            if e is None:
                self._memo_discard(key)
                self._sync_gauges()
                return False
            if e.dirty and not force:
                self.perf.inc("evict_refused_dirty")
                return False
            freed = self._remove_entry(key)
            self.evictions += 1
            self._sync_gauges()
        self.perf.inc("evict")
        self.perf.inc("page_evictions", freed)
        return True

    @tracing.sectioned("store", "resident_shed")
    def shed_parity(self, key: Any) -> int:
        """Partial eviction: free the CLEAN page suffix past the
        data-row boundary (the parity rows).  The data prefix keeps
        serving reads through gather_rows; get_planar/planar_rows see a
        partial resident and fall back.  Returns bytes freed (0 when no
        boundary was recorded, nothing to shed, or the suffix holds
        dirty pages)."""
        with self._lock:
            e = self._entries.get(key)
            if e is None or e.data_rows is None or e.data_rows >= e.rows:
                return 0
            row_words = e.cols * e.itemsize // 4
            boundary = -(-(e.data_rows * row_words) // self.page_words)
            freed = 0
            for i in range(boundary, len(e.pages)):
                if e.pages[i] is None or i in e.dirty:
                    continue
                self._free.append(e.pages[i])
                e.pages[i] = None
                e.live_pages -= 1
                freed += 1
            self._pages_used -= freed
            if freed:
                self._sync_gauges()
        if freed:
            self.perf.inc("parity_sheds")
            self.perf.inc("page_evictions", freed)
        return freed * self.page_bytes

    # -- dirty lifecycle (writeback) -----------------------------------------

    def is_dirty(self, key: Any) -> bool:
        with self._lock:
            e = self._entries.get(key)
            return bool(e is not None and e.dirty)

    def has_dirty(self) -> bool:
        return self._dirty_page_count > 0

    def peek_dirty(self, key: Any):
        """(dirty_info, generation token) or None.  The token pins the
        exact install the caller is about to flush."""
        with self._lock:
            e = self._entries.get(key)
            if e is None or not e.dirty:
                return None
            return (e.dirty_info, e.dirty_gen)

    def dirty_items(self) -> List[Tuple[Any, Any, int, float]]:
        """Snapshot of (key, dirty_info, generation, dirty_since),
        oldest-dirty first — the flush agent's input."""
        with self._lock:
            items = [(k, e.dirty_info, e.dirty_gen, e.dirty_since)
                     for k, e in self._entries.items() if e.dirty]
        items.sort(key=lambda t: t[3])
        return items

    def clear_dirty(self, key: Any, gen: int) -> bool:
        """Mark the entry clean after a successful flush — only when
        ``gen`` still names the install the caller flushed (an
        overwrite re-installed and bumped the generation: its dirt is
        NOT flushed, and clearing it would lose acked data)."""
        with self._lock:
            e = self._entries.get(key)
            if e is None or e.dirty_gen != gen or not e.dirty:
                return False
            self._dirty_page_count -= len(e.dirty)
            e.dirty.clear()
            e.dirty_info = None
            self._gen += 1
            e.dirty_gen = self._gen
            self._sync_gauges()
        return True

    # -- exit-boundary memo (page-granular accounting) -----------------------

    def _memo_charge(self, nbytes: int) -> int:
        return -(-nbytes // self.page_bytes) * self.page_bytes

    def _memo_discard(self, key: Any) -> None:
        """Lock held."""
        got = self._memo.pop(key, None)
        if got is not None:
            self.memo_bytes -= self._memo_charge(self._memo_raw.pop(key))

    @tracing.sectioned("store", "memo_get")
    def memo_get(self, key: Any, version: Any):
        with self._lock:
            if key not in self._entries:
                return None
            got = self._memo.get(key)
        if got is None or got[0] != version:
            return None
        return got[1]

    @tracing.sectioned("store", "memo_put")
    def memo_put(self, key: Any, version: Any, value: Any) -> None:
        """Record the packed host result of this resident at `version`
        (one entry per key, latest version wins): later resident hits
        skip the device pack — 'pack once per resident lifetime' held
        under repeated reads.  Ignored when the entry is not resident (a
        drop/evict raced the pack: the memo must not outlive the entry)
        and when the memo pool is at its budget (capacity_bytes of host
        RAM, so the operator's total footprint is bounded by ~2x
        capacity; a refused memo only costs a re-pack on the next read).
        The cap accounting is in PAGE units against the pool's byte
        size — the memo gauge can never drift from the granularity
        actual residency is budgeted in."""
        charge = self._memo_charge(len(value))
        with self._lock:
            if key not in self._entries:
                return
            self._memo_discard(key)
            if self.memo_bytes + charge > self.capacity_bytes:
                self.perf.set("memo_bytes", self.memo_bytes)
                return
            self._memo[key] = (version, value)
            self._memo_raw[key] = len(value)
            self.memo_bytes += charge
            self.perf.set("memo_bytes", self.memo_bytes)

    # -- introspection -------------------------------------------------------

    def page_stats(self) -> Dict[str, int]:
        with self._lock:
            partial = sum(1 for e in self._entries.values()
                          if e.live_pages < len(e.pages))
            return {
                "page_bytes": self.page_bytes,
                "pages_total": self._pages_total,
                "pages_used": self._pages_used,
                "dirty_pages": self._dirty_page_count,
                "dirty_bytes": self._dirty_page_count * self.page_bytes,
                "dirty_entries": sum(1 for e in self._entries.values()
                                     if e.dirty),
                "partial_residents": partial,
                "frag_saved_bytes": max(0, self.frag_saved_signed),
                "monolithic_equiv_bytes": self._mono_bytes,
                "device_arm": int(self.device_arm),
                "device_slabs": self._device_slab_count(),
                "h2d_installs": self.h2d_installs,
                "device_installs": self.device_installs,
                "d2h_gathers": self.d2h_gathers,
            }

    def stats(self) -> Dict[str, int]:
        return {"resident_bytes": self.resident_bytes,
                "memo_bytes": self.memo_bytes,
                "entries": len(self._entries), "admits": self.admits,
                "hits": self.hits, "misses": self.misses,
                "evictions": self.evictions,
                "pages_total": self._pages_total,
                "pages_used": self._pages_used,
                "dirty_pages": self._dirty_page_count,
                "frag_saved_bytes": self.frag_saved_signed,
                "monolithic_equiv_bytes": self._mono_bytes,
                "device_arm": int(self.device_arm),
                "device_slabs": self._device_slab_count(),
                "h2d_installs": self.h2d_installs,
                "device_installs": self.device_installs,
                "d2h_gathers": self.d2h_gathers}
