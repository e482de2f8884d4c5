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
  ``idx[1]``.  STATIC (compile key): the source's shape, ``cols``,
  ``page_words``, donate.  DYNAMIC: the slab, the source and the one
  ``int32[2, npages]`` index array, built in numpy and passed as a
  numpy argument — no eager jnp op, slice, reshape or index upload
  runs on the calling (event-loop) thread.  The slab argument is
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
``npages`` columns — every page of the install — and a group that owns
fewer pads it by REPEATING its last (source row, destination row) pair:
duplicate scatter updates with identical payloads are deterministic,
and the redundant HBM writes cost nothing beside a host dispatch.  One
program per (source shape, ``cols``, ``page_words``, donate) serves
every group size, so there is nothing to enumerate ahead of time: the
first install of a geometry compiles it (the served path's warm-up),
and ``prewarm`` covers the gathers alone.  The two gather programs
compile per (page_words, pow2-bucketed page count) and never per split;
``span_rows`` per (bucket, row range, resident geometry), at the first
device read of a geometry.  All sit behind the same OrderedDict-LRU discipline as
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

_KERNEL_CAPACITY = 64
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
    and no other device call.  Returns the NEW slab array; the caller
    must forget the old one (it may be freed).  ``src`` is never donated
    (it may alias a shared batch product)."""
    page_words = int(slab.shape[1])
    shape = (int(src.shape[0]), int(src.shape[1]))
    n = len(src_rows)
    idx = np.empty((2, install_pages(shape, cols, page_words)),
                   dtype=np.int32)
    idx[0, :n] = src_rows
    idx[1, :n] = dst_rows
    idx[:, n:] = idx[:, n - 1:n]  # repeat one real pair: same bytes again
    return install_fn(shape, int(cols), page_words,
                      donate_enabled())(slab, src, idx)


def install_fn(src_shape, cols: int, page_words: int, donate: bool):
    """The jitted (LRU-cached) fused install for one source geometry:
    (slab, src u32[src_shape], idx int32[2, npages]) -> slab."""
    rows, cols_full = src_shape
    npages = install_pages(src_shape, cols, page_words)
    pad = npages * page_words - rows * cols

    def build():
        def _install(s, src, idx):
            with jax.named_scope("slab_install"):
                flat = (src[:, :cols] if cols < cols_full
                        else src).reshape(-1)
                if pad:
                    flat = jnp.concatenate(
                        [flat, jnp.zeros(pad, dtype=jnp.uint32)])
                pages = flat.reshape(npages, page_words)
                return s.at[idx[1]].set(pages[idx[0]])

        if donate:
            return jax.jit(_install, donate_argnums=(0,))
        return jax.jit(_install)

    return _kernel(("install", rows, cols_full, cols, page_words, donate),
                   build)


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


def span_rows_fn(nb: int, page_words: int, start: int, length: int,
                 n_rows: int, cols: int, planes8: bool):
    """The jitted (LRU-cached) cut of bit-rows out of gathered pages, for
    one (bucket, range, resident geometry)."""
    def build():
        def _rows(p):
            with jax.named_scope("slab_gather"):
                out = p.reshape(-1)[start:start + length]
                if planes8:
                    out = jax.lax.bitcast_convert_type(out, jnp.int8)
                return out.reshape(n_rows, cols)

        return jax.jit(_rows)

    return _kernel(("rows", nb, page_words, start, length, n_rows, cols,
                    planes8), build)


def span_rows(pages, start: int, length: int, n_rows: int, cols: int,
              planes8: bool):
    """Words ``[start, start + length)`` of the gathered pages' flat image
    as the resident's ``[n_rows, cols]`` bit-rows (``planes8``: the int8
    plane layout, each u32 word four bytes, LSB first as numpy's view on
    the little-endian hosts this runs on) — ONE jitted program."""
    return span_rows_fn(int(pages.shape[0]), int(pages.shape[1]), start,
                        length, n_rows, cols, planes8)(pages)


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
