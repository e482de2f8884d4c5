"""Device page-slab kernels: jitted in-place installs and gathers for
the paged resident store's DEVICE arm (ceph_tpu/rados/pagestore.py).

The pagestore's layout was designed for exactly this module (its r20
writeup: "one contiguous pool indexed by page id, the exact layout a
``dynamic_update_slice`` device path wants"): each lazily-committed
sub-slab is a [2**_SLAB_SHIFT, page_words] u32 array, and a resident's
pages are rows of those arrays.  The idiom is Ragged Paged Attention
(arXiv:2604.15464) — a device-resident paged pool mutated IN PLACE by
jitted scatter updates with buffer donation, ragged tails handled by
the page table above, host copies only at the true I/O boundary:

- ``slab_install(slab, src, cols, src_rows, dst_rows)`` lands pages of
  ONE install in ONE sub-slab with ONE jitted program, the only device
  program an install launches per touched sub-slab.  ``src`` is the
  install's source exactly as its producer left it — the encode lane's
  ``u32[rows, cols_full]`` plane words (a ``jax.Array``), or the
  host-sourced page image after its one h2d copy.  Inside the program:
  the trim to ``cols``, the row-major flatten, the zero pad of the
  ragged tail, the view as ``[npages, page_words]``, the selection of
  source page rows ``idx[0]`` and the scatter to sub-slab rows
  ``idx[1]``.  STATIC (compile key): the source's shape,
  ``page_words``, donate.  DYNAMIC: the slab, the source and the one
  ``int32[2, npages + 1]`` index array, built in numpy and passed as a
  numpy argument, whose last column is ``cols`` — no eager jnp op,
  slice, reshape or index upload runs on the calling (event-loop)
  thread.  A source used at its full width takes the flatten as a
  reshape, a narrower one is compacted row by row: two branches of the
  one program (``lax.cond``), so a pool of one object size pays nothing
  for the others.  The source's shape is the
  encode lane's pow2 column bucket, so a pool has one install program
  per bucket however many object sizes it holds (an object store's
  sizes are heavy-tailed: keyed by ``cols``, 4 KiB-4 MiB objects on a
  32 KiB stripe would be 128 programs against an LRU of 64, each
  rebuilt on the event loop when it came round again).  The slab argument is
  DONATED when the backend supports it, so the update is genuinely in
  place — no 2x-slab copy per install.  Donation discipline: the
  CALLER must drop its reference to the donated slab immediately (the
  pagestore swaps ``_dev_slabs[s]`` under its lock before anyone can
  gather), and the source is NEVER donated — resident-lane fan-out
  slices may alias the batching queue's shared product
  (parallel/service.py), and an install spread over sub-slabs feeds
  the same source to each of its programs.
- ``slab_gather(slab_at, slab_of, rows)`` reads a span's pages back
  into ONE ``[bucket, page_words]`` buffer in span order, one jitted
  program per touched sub-slab: the first sub-slab's take fills every
  position (row 0 where a page lies elsewhere), each further sub-slab's
  program overwrites the positions it owns.  ``span_rows`` then cuts the
  bit-rows out of that buffer (flatten, slice, bitcast, reshape) as one
  more program.  Nothing here is an eager jnp op: in a store that stays
  at its evict line a span's pages come off a scattered free list, split
  over sub-slabs another way every time, and an eager slice or
  concatenate compiles once per split, on the event loop, inside a
  served window (PERF.md, PR 33).  The result is a fresh device buffer
  (never a view of the slab), so a gather that raced a later donated
  install still holds the bytes it read.

The install compiles per SOURCE GEOMETRY only, never per group size: an
install whose pages come off a fragmented free list lands a different
number of pages in each sub-slab it touches, and a program first seen
inside a served window compiles there.  So the index array always has
``npages`` columns — every page the untrimmed source could fill — and
a group that owns fewer pads it by REPEATING its last (source row,
destination row) pair:
duplicate scatter updates with identical payloads are deterministic,
and the redundant HBM writes cost nothing beside a host dispatch.  One
program per (source shape, ``page_words``, donate) serves
every group size and trim width, so there is nothing to enumerate ahead of time: the
first install of a geometry compiles it (the served path's warm-up),
and ``prewarm`` covers the gathers alone.  The two gather programs
compile per (page_words, pow2-bucketed page count) and never per split;
``span_rows`` per (page bucket, row count, pow2 column bucket), told
the range's start and the row width at run time; ``plane_window`` (a
request's columns out of a coalesced group's product, for the queue's
fan-out) per (product shape, column bucket).  All sit behind the same OrderedDict-LRU discipline as
gf2's XOR-schedule cache, with the ``slab_kernels`` counter set
mirroring SCHED_PERF.

Donation resolution: ``CEPH_TPU_SLAB_DONATE=1`` forces it on (tests),
``=0`` forces it off, default = only when a real device backend is
live.  On the CPU backend XLA ignores donation (with a warning per
compile), so the auto default keeps the tier-1 environment quiet while
preserving the exact call structure the device path runs.
"""

from __future__ import annotations

import os
import threading
from collections import OrderedDict
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ceph_tpu.common.perf_counters import PerfCountersBuilder

SLAB_PERF = (
    PerfCountersBuilder("slab_kernels")
    .add_u64_counter("hit", "compiled slab-kernel LRU hits")
    .add_u64_counter("miss", "compiled slab-kernel LRU misses")
    .add_u64_counter("evict", "compiled slab kernels evicted at capacity")
    .add_u64_counter("compile", "slab kernels compiled (per geometry)")
    .add_u64("entries", "live compiled slab kernels (gauge)")
    .create_perf_counters())

# what a pool of mixed sizes can need, with room: ten source shapes of
# the install, two gathers a page bucket, a row cut per (page bucket, row
# count, column bucket), and the queue's plane windows, one per (product
# width, request bucket): ~50 of those alone (PERF.md, PR 38)
_KERNEL_CAPACITY = 256
_KERNELS: "OrderedDict" = OrderedDict()
_LOCK = threading.Lock()


def _resync() -> None:
    with _LOCK:
        SLAB_PERF.set("entries", len(_KERNELS))


SLAB_PERF.resync = _resync

_DONATE: Optional[bool] = None


def donate_enabled() -> bool:
    """Whether install kernels annotate the slab argument for donation.
    CEPH_TPU_SLAB_DONATE=1/0 overrides; default = a real (non-cpu)
    backend is live — the CPU backend ignores donation and would warn
    on every compile."""
    env = os.environ.get("CEPH_TPU_SLAB_DONATE", "")
    if env == "1":
        return True
    if env == "0":
        return False
    global _DONATE
    if _DONATE is None:
        from ceph_tpu.utils.jaxdev import accelerator_live

        _DONATE = accelerator_live()
    return _DONATE


def _reset_for_tests() -> None:
    global _DONATE
    _DONATE = None
    with _LOCK:
        _KERNELS.clear()
        SLAB_PERF.set("entries", 0)


def bucket_rows(n: int) -> int:
    """Pow2 row-count bucket (>= 1) bounding recompiles across install /
    gather sizes — the page-geometry sibling of gf2.bucket_columns."""
    b = 1
    while b < n:
        b <<= 1
    return b


def _kernel(key, build):
    with _LOCK:
        fn = _KERNELS.get(key)
        if fn is not None:
            _KERNELS.move_to_end(key)
    SLAB_PERF.inc("hit" if fn is not None else "miss")
    if fn is None:
        fn = build()
        SLAB_PERF.inc("compile")
        evicted = 0
        with _LOCK:
            _KERNELS[key] = fn
            _KERNELS.move_to_end(key)
            while len(_KERNELS) > _KERNEL_CAPACITY:
                _KERNELS.popitem(last=False)
                evicted += 1
            SLAB_PERF.set("entries", len(_KERNELS))
        if evicted:
            SLAB_PERF.inc("evict", evicted)
    return fn


def install_pages(src_shape, cols: int, page_words: int) -> int:
    """Pages the fused install makes of a ``src_shape`` u32 source
    trimmed to ``cols`` words a row (ragged tail included)."""
    return -(-(int(src_shape[0]) * int(cols)) // int(page_words))


def slab_install(slab, src, cols: int, src_rows: np.ndarray,
                 dst_rows: np.ndarray):
    """Land page rows ``src_rows`` of the install's page view of ``src``
    (u32 [rows, cols_full], trimmed to ``cols``, flattened, zero-padded
    to whole pages) at rows ``dst_rows`` of the sub-slab — ONE jitted
    in-place program, donation-annotated when the backend supports it,
    and no other device call.  ``cols`` is told to the program at run
    time: one program serves every width a source shape can carry.
    Returns the NEW slab array; the caller must forget the old one (it
    may be freed).  ``src`` is never donated (it may alias a shared
    batch product)."""
    page_words = int(slab.shape[1])
    shape = (int(src.shape[0]), int(src.shape[1]))
    n = len(src_rows)
    npages = install_pages(shape, shape[1], page_words)
    idx = np.empty((2, npages + 1), dtype=np.int32)
    idx[0, :n] = src_rows
    idx[1, :n] = dst_rows
    idx[:, n:npages] = idx[:, n - 1:n]  # repeat one real pair: same bytes
    idx[:, npages] = cols  # the trim width rides along: ONE host argument
    return install_fn(shape, page_words, donate_enabled())(slab, src, idx)


def install_fn(src_shape, page_words: int, donate: bool):
    """The jitted (LRU-cached) fused install for one source SHAPE:
    (slab, src u32[src_shape], idx int32[2, npages + 1]) -> slab.  The
    trim width is data, not part of the key: it is the index array's
    last column (one host argument a call, as before it was told).  A
    source used at its full width (a 4 MiB object fills its bucket) takes
    the plain flatten, a reshape; a narrower one is compacted: row r of
    the source written, whole, at word ``r * cols`` of the flat page
    image, rows in order, so each row overwrites the pad columns of the
    one before, and what the last row leaves past ``rows * cols`` is
    zeroed.  Both are branches of ONE program (``lax.cond``: only the
    branch taken runs).  ``npages`` is what the untrimmed source would
    fill; an install of fewer pages repeats its last index pair (module
    docstring)."""
    rows, cols_full = src_shape
    npages = install_pages(src_shape, cols_full, page_words)
    words = npages * page_words

    def build():
        def _whole(src, cols):
            flat = src.reshape(-1)
            if words > rows * cols_full:
                flat = jnp.concatenate([flat, jnp.zeros(
                    words - rows * cols_full, dtype=jnp.uint32)])
            return flat

        def _compact(src, cols):
            def place(r, flat):
                row = jax.lax.dynamic_index_in_dim(src, r, 0, False)
                return jax.lax.dynamic_update_slice(flat, row, (r * cols,))

            flat = jax.lax.fori_loop(
                0, rows, place, jnp.zeros(words, dtype=jnp.uint32))
            live = jnp.arange(words, dtype=jnp.int32) < rows * cols
            return jnp.where(live, flat, jnp.uint32(0))

        def _install(s, src, idx):
            with jax.named_scope("slab_install"):
                cols = idx[0, npages]
                pages = jax.lax.cond(cols == cols_full, _whole, _compact,
                                     src, cols).reshape(npages, page_words)
                return s.at[idx[1, :npages]].set(pages[idx[0, :npages]])

        if donate:
            return jax.jit(_install, donate_argnums=(0,))
        return jax.jit(_install)

    return _kernel(("install", rows, cols_full, page_words, donate), build)


def gather_fn(page_words: int, nb: int):
    """The jitted (LRU-cached) take for one page geometry: a span's first
    sub-slab."""
    def _gather(s, i):
        with jax.named_scope("slab_gather"):
            return s[i]

    return _kernel(("gather", page_words, nb), lambda: jax.jit(_gather))


def gather_into_fn(page_words: int, nb: int):
    """The jitted (LRU-cached) take of a span's FURTHER sub-slab: rows
    ``i`` of ``s`` where ``own``, what ``acc`` holds elsewhere."""
    def _gather_into(acc, s, i, own):
        with jax.named_scope("slab_gather"):
            return jnp.where(own[:, None], s[i], acc)

    return _kernel(("gather_into", page_words, nb),
                   lambda: jax.jit(_gather_into))


def slab_gather(slab_at, slab_of: np.ndarray, rows: np.ndarray):
    """The pages (sub-slab ``slab_of[j]``, row ``rows[j]``) as ONE fresh
    ``[bucket_rows(n), page_words]`` device buffer in that order (rows
    past ``n`` are filler): one jitted program per touched sub-slab,
    whatever the split, and no other device call.  ``slab_at(s)`` hands
    over sub-slab ``s``; the index and mask arrays are numpy arguments of
    the programs."""
    n = len(rows)
    nb = bucket_rows(n)
    acc = None
    for s in np.unique(slab_of).tolist():
        own = np.zeros(nb, dtype=bool)
        own[:n] = slab_of == s
        idx = np.zeros(nb, dtype=np.int32)
        idx[:n] = np.where(own[:n], rows, 0)
        slab = slab_at(s)
        if acc is None:
            acc = gather_fn(int(slab.shape[1]), nb)(slab, idx)
        else:
            acc = gather_into_fn(int(slab.shape[1]), nb)(acc, slab, idx, own)
    return acc


def span_rows_fn(nb: int, page_words: int, n_rows: int, cols_b: int,
                 planes8: bool):
    """The jitted (LRU-cached) cut of bit-rows out of gathered pages, for
    one (page bucket, row count, pow2 column bucket): where the range
    starts and how wide a row is are data (``at`` = int32[2]).  Rows as
    wide as their bucket (a 4 MiB object's) are one contiguous slice
    and a reshape; narrower ones are cut row by row and masked — two
    branches of ONE program."""
    def build():
        def _whole(p, start, cols):
            return jax.lax.dynamic_slice(
                p.reshape(-1), (start,),
                (n_rows * cols_b,)).reshape(n_rows, cols_b)

        def _ragged(p, start, cols):
            # cols_b words of slack: a slice never clamps its start
            flat = jnp.concatenate(
                [p.reshape(-1), jnp.zeros(cols_b, dtype=p.dtype)])
            offs = start + jnp.arange(n_rows, dtype=jnp.int32) * cols
            out = jax.vmap(lambda o: jax.lax.dynamic_slice(
                flat, (o,), (cols_b,)))(offs)
            return jnp.where(
                jnp.arange(cols_b, dtype=jnp.int32)[None, :] < cols,
                out, jnp.uint32(0))

        def _rows(p, at):
            with jax.named_scope("slab_gather"):
                start, cols = at[0], at[1]
                if n_rows * cols_b <= nb * page_words:
                    out = jax.lax.cond(cols == cols_b, _whole, _ragged,
                                       p, start, cols)
                else:  # the pages cannot hold full-width rows
                    out = _ragged(p, start, cols)
                if planes8:
                    out = jax.lax.bitcast_convert_type(
                        out, jnp.int8).reshape(n_rows, cols_b * 4)
                return out

        return jax.jit(_rows)

    return _kernel(("rows", nb, page_words, n_rows, cols_b, planes8), build)


def span_rows(pages, start: int, n_rows: int, cols: int, planes8: bool):
    """``n_rows`` bit-rows of ``cols`` u32 words each, read from word
    ``start`` of the gathered pages' flat image, as ONE jitted program.
    The result is ``[n_rows, bucket_rows(cols)]`` (``planes8``: the int8
    plane layout, four columns a word, LSB first as numpy's view on the
    little-endian hosts this runs on), zero past the true width: the
    program is keyed by the pow2 bucket and told ``start`` and ``cols``
    at run time, so residents of every width share a handful of
    programs, and so do the unpack programs downstream.  Callers trim to
    the width they know."""
    return span_rows_fn(int(pages.shape[0]), int(pages.shape[1]), n_rows,
                        bucket_rows(cols), planes8)(
        pages, np.array([start, cols], dtype=np.int32))


def plane_window_fn(rows: int, cols_full: int, cols_b: int):
    """The jitted (LRU-cached) cut of ONE request's columns out of a
    coalesced group's plane rows: (product [rows, cols_full], at =
    int32[3]: start, shift, cols) -> [rows, cols_b], zero past ``cols``."""
    def build():
        def _window(product, at):
            with jax.named_scope("plane_window"):
                start, shift, cols = at[0], at[1], at[2]
                block = jax.lax.dynamic_slice(product, (0, start),
                                              (rows, cols_b))
                # the block began `shift` columns early where the window
                # would have run off the product's edge: rotate it back
                block = jax.lax.dynamic_slice(
                    jnp.concatenate([block, block], axis=1), (0, shift),
                    (rows, cols_b))
                return jnp.where(
                    jnp.arange(cols_b, dtype=jnp.int32)[None, :] < cols,
                    block, jnp.zeros((), dtype=product.dtype))

        return jax.jit(_window)

    return _kernel(("window", rows, cols_full, cols_b), build)


def plane_window(product, off: int, cols: int, cols_b: int):
    """Columns ``[off, off + cols)`` of a group's plane rows as a fresh
    ``[rows, cols_b]`` device buffer (``cols <= cols_b <= cols_full``),
    zero past ``cols`` — with ``cols_b`` the width a dispatch of the
    request alone would have bucketed to, exactly what that dispatch
    hands back.  ONE jitted program per (product shape, ``cols_b``):
    offset and width are data (an eager slice compiles per offset
    and width, on the queue's thread, inside a served window)."""
    rows, cols_full = int(product.shape[0]), int(product.shape[1])
    start = min(int(off), cols_full - cols_b)
    return plane_window_fn(rows, cols_full, cols_b)(
        product, np.array([start, int(off) - start, cols], dtype=np.int32))


def prewarm(page_words: int, max_rows: int = 256) -> int:
    """Compile the two gather programs for every pow2 page bucket up to
    ``max_rows`` (one sub-slab's worth) at store build, OFF the read
    path — the AOT discipline: a served window must never pay an
    in-line XLA compile for a geometry the configured page size makes
    inevitable.  The install has nothing to enumerate here: it compiles
    per source geometry, whatever the group size (module docstring).
    Returns the number of kernels compiled (0 when everything was
    already cached)."""
    before = SLAB_PERF.get("compile")
    slab = new_subslab(max_rows, page_words)
    nb = 1
    while nb <= max_rows:
        # every other page "in a second sub-slab": both programs run
        jax.block_until_ready(slab_gather(
            lambda _s: slab, np.arange(nb) % 2,
            np.arange(nb, dtype=np.int32) % max_rows))
        nb <<= 1
    return int(SLAB_PERF.get("compile") - before)


def new_subslab(n_pages: int, page_words: int):
    """A zeroed device sub-slab.  Zeroing (vs uninitialized) costs one
    fill but makes the ragged install tail well-defined: the flat page
    image is zero-padded, so a later whole-page gather never observes
    uninitialized device memory."""
    return jnp.zeros((n_pages, page_words), dtype=jnp.uint32)


def is_device_array(x) -> bool:
    """True for jax arrays (the device-native install input probe —
    a queue-produced resident must not bounce through host numpy)."""
    return isinstance(x, jax.Array)
