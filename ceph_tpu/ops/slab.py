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

- ``slab_install(slab, data, idx)`` scatters [n, page_words] page rows
  into the sub-slab at row indices ``idx`` in ONE jitted
  ``slab.at[idx].set(data)`` call (XLA lowers this to
  dynamic-update-slice / scatter).  The slab argument is DONATED when
  the backend supports it, so the update is genuinely in place — no
  2x-slab copy per install.  Donation discipline: the CALLER must drop
  its reference to the donated slab immediately (the pagestore swaps
  ``_dev_slabs[s]`` under its lock before anyone can gather), and the
  data argument is NEVER donated — resident-lane fan-out slices may
  alias the batching queue's shared product (parallel/service.py).
- ``slab_gather(slab, idx)`` reads rows back as one jitted take; the
  result is a fresh device buffer (never a view of the slab), so a
  gather that raced a later donated install still holds the bytes it
  read.

Both kernels compile per PAGE GEOMETRY — (page_words, pow2-bucketed row
count, donate) — behind the same OrderedDict-LRU discipline as gf2's
XOR-schedule cache, with the ``slab_kernels`` counter set mirroring
SCHED_PERF.  Row-count bucketing pads ``idx`` by repeating the LAST
index and ``data`` by repeating the last row: duplicate scatter updates
with identical payloads are deterministic, and the pad rows write bytes
that were being written anyway.

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


def _pad_rows(idx: np.ndarray, data, nb: int):
    """Pad (idx, data) up to the bucketed row count by repeating the
    last row: duplicate identical scatter updates are deterministic."""
    n = int(idx.shape[0])
    if n == nb:
        return idx, data
    idx = np.concatenate([idx, np.full(nb - n, idx[-1], dtype=idx.dtype)])
    data = jnp.concatenate(
        [data, jnp.broadcast_to(data[-1], (nb - n,) + data.shape[1:])])
    return idx, data


def slab_install(slab, data, idx: np.ndarray):
    """Scatter [n, page_words] u32 page rows into the sub-slab at row
    indices ``idx`` (int32 host array) — one jitted in-place update,
    donation-annotated when the backend supports it.  Returns the NEW
    slab array; the caller must forget the old one (it may be freed).
    ``data`` is never donated (it may alias a shared batch product)."""
    nb = bucket_rows(int(idx.shape[0]))
    idx = np.asarray(idx, dtype=np.int32)
    data = jnp.asarray(data, dtype=jnp.uint32)
    idx, data = _pad_rows(idx, data, nb)
    fn = install_fn(int(slab.shape[1]), nb, donate_enabled())
    return fn(slab, data, jnp.asarray(idx))


def install_fn(page_words: int, nb: int, donate: bool):
    """The jitted (LRU-cached) install kernel for one page geometry."""

    def build():
        def _install(s, d, i):
            with jax.named_scope("slab_install"):
                return s.at[i].set(d)

        if donate:
            return jax.jit(_install, donate_argnums=(0,))
        return jax.jit(_install)

    return _kernel(("install", page_words, nb, donate), build)


def gather_fn(page_words: int, nb: int):
    """The jitted (LRU-cached) gather kernel for one page geometry."""
    def _gather(s, i):
        with jax.named_scope("slab_gather"):
            return s[i]

    return _kernel(("gather", page_words, nb), lambda: jax.jit(_gather))


def slab_gather(slab, idx: np.ndarray):
    """Gather rows ``idx`` from the sub-slab as a fresh [n, page_words]
    device array (never a view — safe across later donated installs)."""
    n = int(idx.shape[0])
    nb = bucket_rows(n)
    idx = np.asarray(idx, dtype=np.int32)
    if nb != n:
        idx = np.concatenate(
            [idx, np.full(nb - n, idx[-1], dtype=idx.dtype)])
    out = gather_fn(int(slab.shape[1]), nb)(slab, jnp.asarray(idx))
    return out if nb == n else out[:n]


def prewarm(page_words: int, max_rows: int = 256) -> int:
    """Compile the install/gather kernels for every pow2 row bucket up
    to ``max_rows`` (one sub-slab's worth) at store build, OFF the put
    path — the AOT discipline: the put window must never pay an in-line
    XLA compile for a geometry the configured page size makes
    inevitable.  Chained through one scratch sub-slab so donation stays
    exercised exactly as the live path will.  Returns the number of
    kernels compiled (0 when everything was already cached)."""
    before = SLAB_PERF.get("compile")
    slab = new_subslab(max_rows, page_words)
    nb = 1
    while nb <= max_rows:
        idx = np.arange(nb, dtype=np.int32) % max_rows
        data = jnp.zeros((nb, page_words), dtype=jnp.uint32)
        slab = slab_install(slab, data, idx)
        jax.block_until_ready(slab_gather(slab, idx))
        nb <<= 1
    jax.block_until_ready(slab)
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
