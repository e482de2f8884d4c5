"""Stripe-batching dispatch queue — amortizing many small EC ops into one
device call.

The reference dispatches its codec once per 4 KiB-unit stripe inside
ECUtil::encode (reference src/osd/ECUtil.cc:123-160) and per 1 MiB buffer in
the benchmark; a TPU dispatch has fixed launch latency, so the >=10x target
"lives or dies on the batching queue" (SURVEY.md §7 hard part 2).  This
queue aggregates encode/decode requests from many objects/ops, concatenates
them column-wise into one [rows, sum(B)] buffer per (matrix, lane) group,
runs ONE device program, and fans completions back out — the same
submit -> aggregate -> dispatch -> completion-fan-out pipeline ECBackend's
write path drives (submit_transaction -> ... -> try_reads_to_commit,
ECBackend.cc:1525->1989).

Threading model: submit() is non-blocking and returns a Future; a worker
thread flushes when pending bytes cross `max_pending_bytes` or `max_delay`
elapses, whichever first.  flush() forces a synchronous drain (used by
tests and by timed sections).

LANES: a request names its lane (`kind`), and LANES below holds one row
per lane — the device program, its numpy mirror, the fan-out shape, the
column alignment and the submit-time check.  Every lane takes packed
[n, B] uint8 rows; they differ in the stages around the GF(2)
product and in what comes back (ceph_tpu/ops/gf2.py has the kernels and
their measurements; rados/ecutil.lane_for picks the lane for a codec).
A request hands its rows over as that [n, B] array, or NAMES them in
stripe order (StripeRows: the object's own buffer, nothing copied yet);
_launch writes every request once, into the staging buffer device_put
reads — pad to whole stripes, stripe-major to shard rows and pad to the
bucket are that one pass, on the queue's thread:

    packed              int8 bit-planes, matrix as a matmul operand (any
                        matrix, no recompile; w=4/8/16): bytes out
    resident            the same, and the int8 planes (data ‖ parity)
                        come back as a device buffer for the resident store
    packedbit           w=8 byte layout as u32 plane words under a static
                        XOR schedule compiled per matrix: bytes out
    packedbit_resident  the same, and the u32 planes come back (1 HBM
                        byte per data byte) for the resident store
                        (rados/pagestore.py)
    packetrows          packet-layout codes (cauchy_orig/good, liberation,
                        blaum_roth, liber8tion): a packet IS a bit-row, so
                        the layout stages are block transposes
                        ([n, nb, w, p] <-> [n*w, nb*p]) around the same
                        schedule; whole w*packetsize blocks, the width
                        buckets to a power of two of blocks; no residents
    subchunk            coupled-layer (CLAY) codes, encode: the request
                        carries no bit-matrix but the code's geometry
                        (subchunk_geometry: q, t, the 2x2 pairwise
                        transform both ways, the scalar code's generator)
                        and, where the packet lane has its packet size,
                        the chunk; three stages on u32 plane words
                        (uncouple, the packed-bit schedule, couple) in
                        one program; whole chunks, the width buckets to a
                        power of two of chunks; no residents

Encode generators and the inverted bit-matrices of decode signatures ride
the same lanes (ecutil's plans), so no served op dispatches from the
event loop; a sub-chunk code's decode and repair stay its codec's.

DEVICE-DISPATCH CIRCUIT BREAKER (the robustness layer): every lane owns a
breaker with three states.  CLOSED: dispatches go to the device; one that
RAISES is rescued host-side (the group's futures resolve with
byte-identical numpy GF(2) results — submitters never see the device
die), is LOGGED with its traceback (a compile refusal or OOM on a lane's
first dispatch must not pass for a working device) and trips the lane
OPEN; one that completes but exceeds ``dispatch_timeout`` — XLA compile
seconds inside it not counted, a first compile is not a sick lane —
trips it after the fact.  OPEN: the lane's groups
are served by the CPU mirrors (``_cpu_apply_request``) until the
cooldown elapses (doubling per consecutive trip, capped).  HALF-OPEN:
one group re-probes the device; success closes the breaker, failure
re-opens it.  ``inject_dispatch_delay`` (osd_debug_inject_dispatch_delay
/ CEPH_TPU_INJECT_DISPATCH_DELAY) slows dispatches to exercise the
watchdog.  Counted in `ec_tpu`: breaker_trip / breaker_probe /
breaker_recover / breaker_fallback + the breaker_open_lanes gauge.

OBSERVABILITY (the `ec_tpu` counter set): the queue owns a PerfCounters
set — name -> meaning -> kind in _build_ec_tpu_perf — with per-lane
submit/byte counters (submit_<lane>/bytes_<lane>, u64), queue-wait
and device-dispatch longrunavg latencies (queue_wait, dispatch_dev), a
coalesced-group-size histogram (group_size), and flush-cause counters
(flush_bytes/flush_delay/flush_forced, u64).  Daemons add the set to their
PerfCountersCollection (`perf dump`, mgr prometheus); `dump_timeline()`
backs the `dump_ec_batch_timeline` asok command with the last 128
dispatches (lane, group size, bytes, wait, device seconds).  Trace spans
ride submissions: a `span=` parent (the OSD's `ec write` trace) gets
submit/coalesce/fan-out events plus a per-dispatch child span tagged with
lane/group_size/bytes.
"""

from __future__ import annotations

import collections
import functools
import logging
import threading
import time
from concurrent.futures import Future, InvalidStateError
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from ceph_tpu.common import tracing
from ceph_tpu.common.perf_counters import PerfCounters, PerfCountersBuilder

log = logging.getLogger("ceph_tpu.ec.batch")

def _build_ec_tpu_perf() -> PerfCounters:
    """The `ec_tpu` counter set (COUNTER SCHEMA below; dumped via `perf
    dump` on any daemon sharing the process queue, exported by the mgr
    prometheus module):

      submit               u64         requests accepted, all lanes
      submit_<lane>        u64         requests accepted per lane
      bytes_<lane>         u64         packed-equivalent bytes submitted per lane
      dispatch             u64         device calls issued
      sharded_dispatch     u64         dispatches laid across the mesh
      overlapped_rounds    u64         rounds whose launch overlapped a fetch
      bytes                u64         bytes dispatched (incl. bucket padding)
      pad_bytes            u64         of `bytes`, the bucket padding
      staged_layout_bytes  u64         row bytes the queue's thread laid out
                                       from stripe-order sources (StripeRows)
      queue_wait           longrunavg  submit -> launch wait per request
      dispatch_dev         longrunavg  launch -> fan-out device seconds per dispatch
      dispatch_compile     longrunavg  XLA compile seconds inside a dispatch
      launch               longrunavg  of dispatch_dev, on the queue's thread:
                                       host array prep, device_put, program
                                       enqueue (returns before the device ran)
      fetch                longrunavg  of dispatch_dev: np.asarray of the
                                       result = program wait + D2H
      h2d_bytes, d2h_bytes u64         bytes staged to / fetched from the device
      mesh_shard_failed    u64         batches the mesh could not lay out
      group_size           histogram   coalesced requests per dispatch (pow2 buckets)
      submit_group         u64         multi-item submit_group() calls (the
                                       whole-stripe-group handoff seam)
      group_submit_size    histogram   items per submit_group() call
      flush_bytes          u64         rounds cut by the bytes threshold
      flush_delay          u64         rounds cut by max_delay expiry
      flush_forced         u64         rounds cut by an explicit flush()/close()
    """
    b = PerfCountersBuilder("ec_tpu")
    b.add_u64_counter("submit", "requests accepted across all lanes")
    b.add_u64_counter("dispatch", "device calls issued")
    b.add_u64_counter("sharded_dispatch",
                      "dispatches that ran across the device mesh")
    b.add_u64_counter("overlapped_rounds",
                      "rounds whose launch overlapped the previous fetch")
    b.add_u64_counter("bytes",
                      "bytes dispatched to the device (incl. padding)")
    b.add_u64_counter("pad_bytes",
                      "of those, zeros that pad a batch up to its pow2 "
                      "column bucket")
    b.add_u64_counter("staged_layout_bytes",
                      "row bytes laid out of stripe-order sources, on the "
                      "queue's thread, straight into a staging buffer")
    for lane in LANES:
        b.add_u64_counter(f"submit_{lane}", f"requests on the {lane} lane")
        b.add_u64_counter(f"bytes_{lane}",
                          f"packed-equivalent bytes submitted on {lane}")
    b.add_time_avg("queue_wait", "submit -> launch coalescing wait")
    b.add_time_avg("dispatch_dev", "launch -> fan-out device time")
    b.add_time_avg("dispatch_compile",
                   "XLA compile seconds inside a dispatch (per dispatch "
                   "that compiled; excluded from the watchdog)")
    b.add_time_avg("launch", "host prep + device_put + program enqueue "
                             "per dispatch (queue thread)")
    b.add_time_avg("fetch", "np.asarray of a dispatch's result: program "
                            "wait + D2H (queue thread)")
    b.add_u64_counter("h2d_bytes", "bytes staged to the device")
    b.add_u64_counter("d2h_bytes", "bytes fetched from the device")
    b.add_u64_counter("mesh_shard_failed",
                      "batches the mesh could not lay out (served on one "
                      "device instead)")
    b.add_histogram("group_size", "coalesced requests per dispatch")
    b.add_u64_counter("submit_group", "multi-item group submits")
    b.add_histogram("group_submit_size", "items per group submit")
    b.add_u64_counter("flush_bytes", "rounds flushed by the bytes threshold")
    b.add_u64_counter("flush_delay", "rounds flushed by max_delay expiry")
    b.add_u64_counter("flush_forced", "rounds flushed by explicit flush()")
    b.add_u64_counter("breaker_trip",
                      "lane breaker trips (dispatch raised or exceeded "
                      "dispatch_timeout)")
    b.add_u64_counter("breaker_probe", "half-open device re-probes")
    b.add_u64_counter("breaker_recover",
                      "breakers closed by a successful probe")
    b.add_u64_counter("breaker_fallback",
                      "groups served by the host CPU path (breaker open "
                      "or dispatch failure rescue)")
    b.add_u64("breaker_open_lanes", "lanes currently tripped open (gauge)")
    return b.create_perf_counters()


# -- host-side GF(2) mirrors (the circuit-breaker CPU fallback path) ---------
# Byte-for-byte numpy mirrors of the device lanes in ceph_tpu/ops/gf2.py:
# GF(2) arithmetic is exact, so a group served here fans out results
# BYTE-IDENTICAL to what the device lane would have produced (the content
# gates in tests/test_batching.py hold across the failover).  Kept
# jax-free on purpose — this path must work when the device stack is the
# thing that is broken.


def _np_unpack_bits(data: np.ndarray, w: int) -> np.ndarray:
    """[n, B] uint8 chunks -> [n*w, Bc] int8 bit-planes (mirror of
    ops/gf2.unpack_bits_bytes for w in 4/8/16)."""
    n, B = data.shape
    if w == 16:
        pairs = data.reshape(n, B // 2, 2)
        planes = [((pairs[:, :, x // 8] >> (x % 8)) & 1) for x in range(16)]
        return np.stack(planes, axis=1).reshape(n * 16, B // 2).astype(np.int8)
    if w == 4:
        shifts = np.arange(4, dtype=np.uint8)
        lo = (data[:, None, :] >> shifts[None, :, None]) & 1
        hi = (data[:, None, :] >> (shifts + 4)[None, :, None]) & 1
        return np.stack([lo, hi], axis=-1).reshape(n * 4, B * 2).astype(np.int8)
    shifts = np.arange(8, dtype=np.uint8)
    return (((data[:, None, :] >> shifts[None, :, None]) & 1)
            .reshape(n * 8, B).astype(np.int8))


def _np_pack_bits(bits: np.ndarray, w: int, out_rows: int) -> np.ndarray:
    """Inverse of _np_unpack_bits (mirror of ops/gf2.pack_bits_bytes)."""
    if w == 16:
        Bc = bits.shape[1]
        planes = bits.reshape(out_rows, 16, Bc).astype(np.int32)
        lo = np.zeros((out_rows, Bc), np.int32)
        hi = np.zeros((out_rows, Bc), np.int32)
        for x in range(8):
            lo = lo + (planes[:, x] << x)
            hi = hi + (planes[:, x + 8] << x)
        return np.stack([lo, hi], axis=-1).reshape(out_rows, Bc * 2) \
            .astype(np.uint8)
    if w == 4:
        Bc2 = bits.shape[1]
        planes = bits.reshape(out_rows, 4, Bc2 // 2, 2).astype(np.int32)
        shifts = np.arange(4, dtype=np.int32)
        lo = np.sum(planes[..., 0] << shifts[None, :, None], axis=1)
        hi = np.sum(planes[..., 1] << shifts[None, :, None], axis=1)
        return (lo | (hi << 4)).astype(np.uint8)
    Bc = bits.shape[1]
    planes = bits.reshape(out_rows, 8, Bc).astype(np.int32)
    shifts = np.arange(8, dtype=np.int32)
    return np.sum(planes << shifts[None, :, None], axis=1).astype(np.uint8)


def _np_matmul_gf2(mbits: np.ndarray, bits: np.ndarray) -> np.ndarray:
    return ((np.asarray(mbits, dtype=np.int32)
             @ np.asarray(bits, dtype=np.int32)) & 1).astype(np.int8)


def _np_words(bits: np.ndarray) -> np.ndarray:
    """[R, B] 0/1 bit rows -> [R, B//32] uint32 plane words (mirror of
    ops/gf2._bits_to_words / pack_bitplanes_u32's word layout)."""
    return np.packbits(bits.astype(np.uint8), axis=1,
                       bitorder="little").view(np.uint32)


def _np_xor_rows(mb: np.ndarray, rows: np.ndarray) -> np.ndarray:
    """out[r] = XOR of the rows that bit-matrix row r selects (mirror of
    ops/gf2._schedule_apply; any element type)."""
    out = np.zeros((mb.shape[0],) + rows.shape[1:], dtype=rows.dtype)
    for r in range(mb.shape[0]):
        cols = np.nonzero(mb[r])[0]
        if len(cols):
            out[r] = np.bitwise_xor.reduce(rows[cols], axis=0)
    return out


def _mirror_bytes(mb, data, w, out_rows, packetsize=0):
    bits = _np_unpack_bits(data, w)
    return _np_pack_bits(_np_matmul_gf2(mb, bits), w, out_rows)


def _mirror_resident(mb, data, w, out_rows, packetsize=0, rows=lambda b: b):
    bits = _np_unpack_bits(data, w)
    pbits = _np_matmul_gf2(mb, bits)
    return (_np_pack_bits(pbits, w, out_rows),
            np.concatenate([rows(bits), rows(pbits)], axis=0))


def _mirror_packetrows(mb, data, w, out_rows, packetsize):
    # mirror of ops/gf2.apply_packetrows_fn: block transpose in, XOR of
    # whole packets, block transpose out
    n, cols = data.shape
    nb = cols // (w * packetsize)
    rows = (data.reshape(n, nb, w, packetsize).transpose(0, 2, 1, 3)
            .reshape(n * w, nb * packetsize))
    return (_np_xor_rows(mb, rows)
            .reshape(out_rows, w, nb, packetsize).transpose(0, 2, 1, 3)
            .reshape(out_rows, cols))


# -- the sub-chunk lane's request: a code's geometry instead of a matrix ------

_GEOMETRY_HEAD = 10  # q, t, the pair transform (4), its inverse (4)


def subchunk_geometry(q: int, t: int, pair, pair_inv,
                      generator) -> np.ndarray:
    """What a "subchunk" request carries where the other lanes carry a
    bit-matrix: a coupled-layer code's geometry as ONE uint8 array, so
    that requests of one code group by its bytes like requests of one
    matrix — [m, 10 + k]: row 0 opens with q, t, the 2x2 pairwise
    transform over GF(2^8) (U pair from C pair, index 0 the node with
    the larger x) and its inverse, row-major; columns 10.. of every row
    are the scalar MDS code's [m, k] generator."""
    generator = np.asarray(generator, dtype=np.uint8)
    geom = np.zeros((generator.shape[0], _GEOMETRY_HEAD + generator.shape[1]),
                    dtype=np.uint8)
    geom[0, :_GEOMETRY_HEAD] = [
        q, t, *np.asarray(pair, dtype=np.uint8).reshape(4),
        *np.asarray(pair_inv, dtype=np.uint8).reshape(4)]
    geom[:, _GEOMETRY_HEAD:] = generator
    return geom


def _read_geometry(geom: np.ndarray):
    """(q, t, pair [2, 2], pair_inv [2, 2], generator [m, k]) of a
    subchunk_geometry array."""
    head = np.asarray(geom[0, :_GEOMETRY_HEAD], dtype=np.uint8)
    return (int(head[0]), int(head[1]), head[2:6].reshape(2, 2),
            head[6:10].reshape(2, 2),
            np.asarray(geom[:, _GEOMETRY_HEAD:], dtype=np.uint8))


def _np_pair_transform(row: np.ndarray, y: int, q: int,
                       pair: np.ndarray) -> np.ndarray:
    """Mirror of ops/gf2._pair_transform on bytes: `row` is [q(x), S,
    q(z_0) .. q(z_t-1), sc]; a node and its partner (the x axis and the
    z_y axis swapped) go through the 2x2 transform, as index 0 where
    x > z_y and as index 1 where x < z_y; the diagonal stays."""
    from ceph_tpu.ec.gf import gf  # numpy only, as this module is

    mul, partner = gf(8).mul_region, np.swapaxes(row, 0, 2 + y)
    (a, b), (c, d) = np.asarray(pair, dtype=np.int64)
    as_first = mul(a, row) ^ mul(b, partner)
    as_second = mul(c, partner) ^ mul(d, row)
    x = np.arange(q).reshape((q,) + (1,) * (row.ndim - 1))
    zy = np.arange(q).reshape((q,) + (1,) * (row.ndim - 3 - y))
    return np.where(x > zy, as_first, np.where(x < zy, as_second, row))


def _mirror_subchunk(geom, data, w, out_rows, chunk):
    # mirror of ops/gf2.encode_subchunk_fn: uncouple the data rows of the
    # grid, the scalar code over every plane, couple the parity rows
    from ceph_tpu.ec.gf import gf

    q, t, pair, pair_inv, generator = _read_geometry(geom)
    k, cols = data.shape
    grid = (cols // chunk,) + (q,) * t + (chunk // q ** t,)
    c = data.reshape((k // q, q) + grid)
    u = np.stack([_np_pair_transform(c[y], y, q, pair)
                  for y in range(k // q)])
    pu = gf(8).matmul(generator, u.reshape((k,) + grid))
    pu = pu.reshape((out_rows // q, q) + grid)
    pc = np.stack([_np_pair_transform(pu[j], k // q + j, q, pair_inv)
                   for j in range(out_rows // q)])
    return pc.reshape(out_rows, cols)


# -- the lanes' device programs: (group, staged batch) -> device result ------
# ops/gf2.py imports jax; this module stays importable without it.


def _device_packed(g, batch):
    from ceph_tpu.ops.gf2 import gf2_apply_bytes

    return gf2_apply_bytes(g.mbits, batch, g.w, g.out_rows)


def _device_resident(g, batch):
    from ceph_tpu.ops.gf2 import gf2_encode_resident

    return gf2_encode_resident(g.mbits, batch, g.w, g.out_rows)


def _device_packedbit(g, batch):
    from ceph_tpu.ops.gf2 import gf2_apply_packedbit

    return gf2_apply_packedbit(g.mbits, batch)


def _device_packedbit_resident(g, batch):
    from ceph_tpu.ops.gf2 import gf2_encode_packedbit_resident

    return gf2_encode_packedbit_resident(g.mbits, batch)


def _device_packetrows(g, batch):
    from ceph_tpu.ops.gf2 import gf2_apply_packetrows

    return gf2_apply_packetrows(g.mbits, batch, g.w, g.packetsize)


def _device_subchunk(g, batch):
    from ceph_tpu.ops.gf2 import gf2_encode_subchunk

    q, t, pair, pair_inv, generator = _read_geometry(g.mbits)
    return gf2_encode_subchunk(q, t, g.packetsize, pair, pair_inv,
                               generator, batch)


# -- a request's rows, named before they are laid out -------------------------


class StripeRows:
    """A request's [n, n_stripes x chunk] shard rows NAMED in stripe
    order: `buf` is the object's own flat uint8 buffer, stripe after
    stripe of n chunks of `chunk` bytes, the last stripe as short as the
    object left it.  Row i is chunk i of every stripe, zeros past the
    buffer's end.  Nothing is copied until `lay_into` writes the rows
    where they are wanted — for a queued request that is _launch, on the
    queue's thread, into the staging buffer.  `shape` and `nbytes` are
    the rows', so every check, counter and grouping rule reads a request
    of either form alike.  Whoever submits one hands over a buffer that
    nobody writes until the future resolves (rados/ecutil._stripe_rows
    decides), and the request keeps it referenced until then."""

    __slots__ = ("buf", "n", "chunk", "n_stripes")

    def __init__(self, buf: np.ndarray, n: int, chunk: int):
        self.buf, self.n, self.chunk = buf, n, chunk
        self.n_stripes = max(1, -(-len(buf) // (n * chunk)))

    @property
    def shape(self) -> Tuple[int, int]:
        return self.n, self.n_stripes * self.chunk

    @property
    def nbytes(self) -> int:
        return self.n * self.n_stripes * self.chunk

    def lay_into(self, dst: np.ndarray) -> None:
        """Write the rows into `dst`, [n, n_stripes x chunk] uint8 whose
        rows are contiguous (a column range of a staging buffer): one
        pass over the whole stripes, then the ragged last stripe — its
        whole chunks, the chunk the object ends in, and the zeros.  A
        handful of numpy calls whatever n is: each may hand the GIL to
        a busy event loop and wait to get it back."""
        n, chunk = self.n, self.chunk
        dst = dst.reshape(n, self.n_stripes, chunk)  # a view: rows split
        whole = len(self.buf) // (n * chunk)
        if whole:
            np.copyto(dst[:, :whole],
                      self.buf[:whole * n * chunk]
                      .reshape(whole, n, chunk).transpose(1, 0, 2))
        if whole < self.n_stripes:
            tail = self.buf[whole * n * chunk:]
            last = dst[:, whole]
            full, rest = divmod(len(tail), chunk)
            if full:
                last[:full] = tail[:full * chunk].reshape(full, chunk)
            if rest:
                last[full, :rest] = tail[full * chunk:]
                last[full, rest:] = 0
                full += 1
            last[full:] = 0

    def rows(self) -> np.ndarray:
        """The rows laid out now, on the caller's thread."""
        out = np.empty(self.shape, dtype=np.uint8)
        self.lay_into(out)
        return out


# -- submit-time checks: a request that cannot run is refused before it can
#    coalesce, or its launch would fail every innocent request grouped with it


def _check_packedbit(regions, w, packetsize=0):
    if w != 8:
        raise ValueError(f"the packed-bit lanes are the w=8 byte layout, "
                         f"got w={w}")


def _check_packedbit_resident(regions, w, packetsize=0):
    _check_packedbit(regions, w, packetsize)
    if regions.shape[1] % 32:
        # the plane fan-out slices whole u32 words
        raise ValueError("packedbit_resident requests must be 32-byte-column "
                         f"aligned, got width {regions.shape[1]}")


def _check_packetrows(regions, w, packetsize=0):
    if packetsize < 1 or regions.shape[1] % (w * packetsize):
        raise ValueError(
            f"packetrows requests are whole w*packetsize={w}*{packetsize}"
            f"-byte blocks, got width {regions.shape[1]}")


def _check_subchunk(regions, w, chunk=0):
    _check_packedbit(regions, w)
    if chunk < 1 or regions.shape[1] % chunk:
        raise ValueError(f"subchunk requests are whole chunks of {chunk} "
                         f"bytes, got width {regions.shape[1]}")


class Lane(NamedTuple):
    """All the queue knows about one lane.  A request is packed [n, B]
    uint8 rows (or a StripeRows that names them) under a [out_rows*w,
    n*w] GF(2) bit-matrix (on "subchunk": a code's geometry)."""

    #: the lane's one fused program over a staged batch (async: returns a
    #: device handle)
    device: Callable
    #: numpy mirror: (mbits u8, rows u8, w, out_rows, packetsize) -> exactly
    #: what the device fan-out resolves a request's future with
    mirror: Callable
    #: fan-out shape: [out_rows, B] bytes, or (bytes, resident bit-rows
    #: [(n+out_rows)*w, ...] that stay on the device)
    resident: bool = False
    #: column unit a staged batch pads to; None = the group's own, `block`
    align: Optional[int] = 1
    check: Optional[Callable] = None
    #: the column unit of a group whose lane has no fixed one, from its w
    #: and the sixth field of its requests (a packet size; a chunk)
    block: Callable = lambda w, packetsize: w * packetsize


LANES: Dict[str, Lane] = {
    "packed": Lane(_device_packed, _mirror_bytes),
    "resident": Lane(_device_resident, _mirror_resident, resident=True),
    # pow2 bucketing of 32-column units keeps whole u32 words per plane row
    "packedbit": Lane(_device_packedbit, _mirror_bytes, align=32,
                      check=_check_packedbit),
    "packedbit_resident": Lane(
        _device_packedbit_resident,
        functools.partial(_mirror_resident, rows=_np_words),
        resident=True, align=32, check=_check_packedbit_resident),
    "packetrows": Lane(_device_packetrows, _mirror_packetrows, align=None,
                       check=_check_packetrows),
    # whole chunks: a bucket's pad is zero chunks, which encode to zero
    "subchunk": Lane(_device_subchunk, _mirror_subchunk, align=None,
                     check=_check_subchunk, block=lambda w, chunk: chunk),
}


def staged_cols(kind: str, w: int, packetsize: int, cols: int) -> int:
    """The width a batch of `cols` byte columns is staged at on lane
    `kind`: a power of two of the lane's column units (the packet lane's
    unit is its w*packetsize block, the others' divides the 1024-column
    floor, so theirs is the plain pow2 width; ops/gf2.bucket_columns is
    the same policy) — what bounds XLA recompiles across object sizes."""
    align = LANES[kind].align or LANES[kind].block(w, packetsize)
    units, bucket = -(-cols // align), max(1, 1024 // align)
    while bucket < units:
        bucket <<= 1
    return align * bucket


def _cpu_apply_request(kind: str, mbits: np.ndarray, regions, w: int,
                       out_rows: int, packetsize: int = 0):
    """Serve ONE lane request host-side; returns exactly what the device
    lane's fan-out would have resolved the request's future with (device
    buffers become numpy arrays — every consumer accepts both; a resident
    lane's plane rows zero-padded to the request's staged width, as
    _complete_resident hands them out; a stripe-order request laid out
    here, its data rows last)."""
    named = isinstance(regions, StripeRows)
    regions = (regions.rows() if named
               else np.asarray(regions, dtype=np.uint8))
    out = LANES[kind].mirror(np.asarray(mbits, dtype=np.uint8), regions,
                             w, out_rows, packetsize)
    if LANES[kind].resident:
        packed, rows = out
        cols = regions.shape[1]
        pad = (rows.shape[1] * staged_cols(kind, w, packetsize, cols)
               // cols - rows.shape[1])
        if pad:
            out = packed, np.pad(rows, ((0, 0), (0, pad)))
    return _with_data_rows(out, regions) if named else out


def _with_data_rows(result, rows: np.ndarray):
    """What a stripe-order request's future resolves to: what a request
    of rows gets, then its data rows as the staging pass laid them out
    ([n, B], every row C-contiguous) — the plan never had them."""
    return (*result, rows) if isinstance(result, tuple) else (result, rows)


class _LaneBreaker:
    """Per-lane circuit breaker state.  closed -> (trip) -> open ->
    (cooldown elapses) -> one half-open probe -> closed on success, or
    re-open with doubled cooldown on failure."""

    __slots__ = ("state", "open_until", "cooldown", "probing")

    CLOSED = "closed"
    OPEN = "open"

    def __init__(self):
        self.state = self.CLOSED
        self.open_until = 0.0
        self.cooldown = 0.0
        self.probing = False


class _Request(NamedTuple):
    """One queued lane submission: `regions` is the [n, B] rows, or the
    StripeRows that names them (and keeps the source buffer referenced
    until the future resolves).  t_submit feeds the queue_wait
    latency; span threads the submitter's trace (the OSD's `ec write`)
    through coalesce -> dispatch -> fan-out."""

    regions: Any
    future: Future
    t_submit: float
    span: Any = None


@dataclass
class _Group:
    mbits: np.ndarray
    w: int
    out_rows: int
    # the lane (a key of LANES) and, where the lane's column unit is the
    # group's own, what Lane.block makes it from
    kind: str = "packed"
    packetsize: int = 0  # packet layout; on "subchunk" the chunk
    requests: List[_Request] = field(default_factory=list)
    pending_bytes: int = 0


@dataclass
class _Launched:
    """One launched dispatch awaiting completion (fan-out)."""

    group: _Group
    state: Any
    t_launch: float
    span: Any = None  # child of a submitter's trace, or queue-tracer root
    wait_s: float = 0.0  # mean submit->launch wait across the group
    compile_mark: float = 0.0  # worker-thread compile seconds at launch


class BatchingQueue:
    def __init__(
        self,
        # 16 MiB/dispatch: the measured HBM sweet spot of the int8-plane
        # lanes (round-4 sweep on v5e — their 8x bit-plane expansion makes
        # 64 MiB batches HBM-bound; 2 MiB of columns at k=8 wins)
        max_pending_bytes: int = 16 << 20,
        max_delay: Optional[float] = None,
        mesh=None,
    ):
        import os as _os

        self.max_pending_bytes = max_pending_bytes
        # the DEFAULT coalescing window is tunable (CEPH_TPU_BATCH_DELAY
        # seconds): loaded CI hosts widen it so coalescing tests assert
        # the MECHANISM rather than the 2ms default's luck.  An explicit
        # max_delay argument always wins, and a malformed value falls
        # back rather than crashing the first EC write.
        if max_delay is None:
            try:
                max_delay = float(
                    _os.environ.get("CEPH_TPU_BATCH_DELAY") or 0.002)
            except ValueError:
                max_delay = 0.002
        self.max_delay = max_delay
        # device-mesh execution (ceph_tpu/parallel/mesh.py): when a mesh
        # is attached (or auto-engages on a multi-chip backend), every
        # dispatch lane lays its batch out across the mesh's column axis
        # — the same compiled ops run SPMD over all devices, collectives
        # inserted by XLA where a consumer needs them.  mesh=None means
        # auto-detect; mesh=False pins the queue single-device
        # (single-device comparisons that must not auto-engage).
        if mesh is None:
            from ceph_tpu.parallel.mesh import shared_mesh

            mesh = shared_mesh()
        self.mesh = mesh or None
        from ceph_tpu.utils.jaxdev import compile_meter

        self._compiles = compile_meter()
        # the ec_tpu perf counter set (schema: _build_ec_tpu_perf).  The
        # legacy bare ints (submits/dispatches/bytes_dispatched/...) are
        # now read-only views over it — daemons add this set to their
        # PerfCountersCollection so `perf dump` carries the full breakdown.
        self.perf = _build_ec_tpu_perf()
        # optional per-daemon Tracer: dispatch spans with no submitter
        # parent (e.g. repair traffic) root here; the OSD attaches its ctx
        # tracer so spans land in its dump_traces ring
        self.tracer = None
        # bounded ring of recent dispatches for `dump_ec_batch_timeline`
        self.timeline: "collections.deque" = collections.deque(maxlen=128)
        # -- device-dispatch watchdog + per-lane circuit breaker ------------
        # A dispatch that RAISES is rescued host-side immediately (its
        # requests resolve with byte-identical numpy results) and trips
        # the lane's breaker; one that completes but exceeds
        # dispatch_timeout trips it after the fact (the results were
        # fine, the lane is slow/sick).  While a breaker is OPEN the
        # lane's groups are served by the CPU mirrors; after
        # breaker_cooldown (doubling per consecutive trip, capped at
        # breaker_cooldown_max) ONE group re-probes the device —
        # success closes the breaker (half-open re-engage).
        try:
            self.dispatch_timeout = float(
                _os.environ.get("CEPH_TPU_DISPATCH_TIMEOUT") or 30.0)
        except ValueError:
            self.dispatch_timeout = 30.0
        # osd_debug_inject_dispatch_delay: slow every device dispatch by
        # this many seconds (exercises the watchdog/breaker; 0 = off)
        try:
            self.inject_dispatch_delay = float(
                _os.environ.get("CEPH_TPU_INJECT_DISPATCH_DELAY") or 0.0)
        except ValueError:
            self.inject_dispatch_delay = 0.0
        self.breaker_cooldown = 1.0
        self.breaker_cooldown_max = 30.0
        self._breakers: Dict[str, _LaneBreaker] = {}
        self._breaker_lock = threading.Lock()
        # test seam: invoked (worker thread) after a round is launched,
        # before the backlog check — lets tests inject a standing backlog
        # deterministically instead of racing thread schedulers
        self._launch_hook = None
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._groups: Dict[Tuple, _Group] = {}
        self._pending = 0
        self._oldest: Optional[float] = None
        self._stop = False
        self._worker = threading.Thread(target=self._run, daemon=True, name="ec-batch")
        self._worker.start()

    # -- legacy counter views (the pre-instrumentation bare ints) ------------

    @property
    def submits(self) -> int:
        return self.perf.get("submit")

    @property
    def dispatches(self) -> int:
        return self.perf.get("dispatch")

    @property
    def bytes_dispatched(self) -> int:
        return self.perf.get("bytes")

    @property
    def sharded_dispatches(self) -> int:
        return self.perf.get("sharded_dispatch")

    @property
    def overlapped_rounds(self) -> int:
        return self.perf.get("overlapped_rounds")

    def dump_timeline(self, count: int = 32) -> List[Dict[str, Any]]:
        """Most-recent-first dispatch records for the asok command
        `dump_ec_batch_timeline`: lane, group size, bytes, queue wait,
        device time, and whether the dispatch ran sharded."""
        return list(self.timeline)[-max(1, int(count)):][::-1]

    def register_asok(self, asok) -> None:
        """Expose the dispatch timeline on a daemon's admin socket
        (`dump_ec_batch_timeline [count=N]`)."""
        asok.register(
            "dump_ec_batch_timeline",
            lambda a: self.dump_timeline(int(a.get("count", 32))),
            "recent EC batch dispatches (lane, group size, wait, device s)")

    # -- client side ---------------------------------------------------------

    def submit(self, mbits: np.ndarray, regions: np.ndarray, w: int,
               out_rows: int, kind: str = "packed", packetsize: int = 0,
               *, span=None) -> Future:
        """Queue ONE lane request: the [out_rows*w, n*w] bit-matrix `mbits`
        (on "subchunk" the code's subchunk_geometry, and its chunk where
        the packet lane has its packet size)
        over packed [n, B] uint8 `regions` on lane `kind` (LANES).  The
        future resolves to the [out_rows, B] parity/reconstruction bytes,
        or on a resident lane to (those bytes, the data ‖ parity bit-rows
        as a device buffer).  `regions` may be a StripeRows instead: the
        rows are then laid out by the queue's thread, and the future
        resolves to the same with the [n, B] data rows appended (views of
        the staging buffer for a request alone in its dispatch, copied
        out of it otherwise).  Non-blocking: no device work and no copy
        on the caller's thread, so concurrent ops coalesce.  Raises
        ValueError for a request its lane cannot run."""
        return self.submit_group(
            [(mbits, regions, w, out_rows, kind, packetsize)], span=span)[0]

    @tracing.sectioned("ecplan", "queue_submit")
    def submit_group(self, items, span=None) -> List[Future]:
        """Group-aware submit (the messenger/recovery whole-stripe-group
        handoff seam): queue a LIST of lane requests — each item is
        (mbits, regions, w, out_rows, kind[, packetsize]), submit()'s
        arguments — under ONE lock acquisition and ONE worker wakeup, so
        a coalesced group of objects reaches the EC tier as a single
        buffer-list submission instead of N contended submits.  Items
        sharing a dispatch signature land in the same _Group; returns the
        per-item futures, index-aligned.  A refused item (ValueError)
        refuses the call before anything is queued."""
        for _, regions, w, _, kind, *packetsize in items:
            check = LANES[kind].check
            if check is not None:
                check(regions, w, *packetsize)
        futs: List[Future] = [Future() for _ in items]
        now = time.monotonic()
        if span is not None:
            span.event(f"ec submit lane={items[0][4]}" if len(items) == 1
                       else f"ec submit group n={len(items)}")
        with self._cv:
            if self._stop:
                raise RuntimeError("BatchingQueue is closed")
            for item, fut in zip(items, futs):
                self._queue_locked(*item, fut=fut, now=now, span=span)
            if items:
                self._cv.notify()
        for _, regions, _, _, kind, *_ in items:
            self.perf.inc("submit")
            self.perf.inc(f"submit_{kind}")
            self.perf.inc(f"bytes_{kind}", regions.nbytes)
        if len(items) > 1:
            self.perf.inc("submit_group")
            self.perf.hinc("group_submit_size", len(items))
        return futs

    def _queue_locked(self, mbits, regions, w, out_rows, kind,
                      packetsize: int = 0, *, fut, now, span) -> None:
        """Insert one request into its dispatch group (caller holds the
        lock)."""
        # the full dispatch signature: identical matrix BYTES under a
        # different w, packet size or output arity is a different
        # computation; the lanes never share a dispatch (different layouts)
        key = (w, out_rows, kind, packetsize, mbits.shape, mbits.tobytes())
        group = self._groups.get(key)
        if group is None:
            group = self._groups[key] = _Group(
                mbits=mbits, w=w, out_rows=out_rows, kind=kind,
                packetsize=packetsize)
        group.requests.append(_Request(regions, fut, now, span))
        group.pending_bytes += regions.nbytes
        self._pending += regions.nbytes
        if self._oldest is None:
            self._oldest = now

    def flush(self) -> None:
        """Synchronously drain everything queued right now."""
        with self._cv:
            groups = self._take_locked()
        if groups:
            self.perf.inc("flush_forced")
        self._dispatch(groups)

    def close(self) -> None:
        with self._cv:
            self._stop = True
            self._cv.notify()
        self._worker.join(timeout=5)
        self.flush()

    # -- worker side ---------------------------------------------------------

    @tracing.sectioned("queue", "group_build")
    def _take_locked(self, budget: Optional[int] = None) -> List[_Group]:
        """Detach queued work for one round.  With a `budget`, the round
        is bounded to ~budget packed bytes (whole requests; at least
        one) and the remainder STAYS QUEUED: a deep backlog becomes a
        sequence of sweet-spot-sized rounds the worker can pipeline,
        instead of one oversized dispatch that nothing overlaps with and
        that sits off the measured HBM batch optimum."""
        if budget is None:
            groups = [g for g in self._groups.values() if g.requests]
            self._groups = {}
            self._pending = 0
            self._oldest = None
            return groups
        taken: List[_Group] = []
        taken_bytes = 0
        for key in list(self._groups):
            if taken_bytes >= budget:
                break
            g = self._groups[key]
            if not g.requests:
                del self._groups[key]
                continue
            if taken_bytes + g.pending_bytes <= budget:
                taken.append(g)
                taken_bytes += g.pending_bytes
                del self._groups[key]
                continue
            # split the group: take a FIFO prefix of its requests, and
            # move the remainder to the BACK of the dict — a lane hot
            # enough to saturate every round must not starve the other
            # (matrix, kind) lanes behind it (round-robin across lanes)
            part = _Group(mbits=g.mbits, w=g.w, out_rows=g.out_rows,
                          kind=g.kind, packetsize=g.packetsize)
            while g.requests and (taken_bytes < budget
                                  or not part.requests):
                req = g.requests.pop(0)
                n = req.regions.nbytes
                part.requests.append(req)
                part.pending_bytes += n
                g.pending_bytes -= n
                taken_bytes += n
            if part.requests:
                taken.append(part)
            del self._groups[key]
            if g.requests:
                self._groups[key] = g  # re-insert at tail
            break
        self._pending = sum(g.pending_bytes
                            for g in self._groups.values())
        if self._pending <= 0:
            self._oldest = None
        # else: keep _oldest — the remainder is at least as old as the
        # round just taken, so its window is already (nearly) expired and
        # the next loop iteration dispatches it immediately (pipelining)
        return taken

    def _run(self) -> None:
        # double-buffered pipeline (VERDICT r03 #4): each round's batches
        # are STAGED to the device and their computations launched (JAX
        # dispatch is async — device_put and jitted calls return before
        # the work finishes) WITHOUT blocking; the previous round's
        # results are then fetched while round N's H2D transfer and
        # compute proceed underneath.  A launched round is held in-flight
        # only while more work is already queued, so an isolated batch
        # still completes immediately.
        inflight: Optional[list] = None
        while True:
            cause = None  # why this round was cut: bytes | delay
            with self._cv:
                while not self._stop:
                    if self._pending >= self.max_pending_bytes:
                        cause = "bytes"
                        break
                    if self._oldest is not None:
                        # pending work fills its normal coalescing window
                        # even while a round is in flight — that round's
                        # compute is proceeding on-device regardless, and
                        # an eager take here would fragment batches
                        remaining = self.max_delay - (time.monotonic() - self._oldest)
                        if remaining <= 0:
                            cause = "delay"
                            break
                        self._cv.wait(timeout=remaining)
                    elif inflight is not None:
                        break  # nothing queued: fetch the in-flight round
                    else:
                        self._cv.wait()
                if self._stop:
                    if inflight is not None:
                        self._complete_safe(inflight)
                    return
                groups = self._take_locked(budget=self.max_pending_bytes)
            if groups and cause is not None:
                self.perf.inc(f"flush_{cause}")
            launched = self._launch_safe(groups)
            if inflight is not None:
                if launched:
                    self.perf.inc("overlapped_rounds")
                self._complete_safe(inflight)
                inflight = None
            with self._cv:
                more = self._pending > 0 and not self._stop
            if launched and more:
                inflight = launched  # overlap with the next round
            elif launched:
                self._complete_safe(launched)

    def _dispatch_span(self, g: _Group):
        """A span for one device dispatch: child of the first submitter's
        trace when one rode in (the OSD's `ec write`), else a root on the
        queue's own tracer; None when neither exists (tracing off)."""
        parent = next((req.span for req in g.requests
                       if req.span is not None), None)
        if parent is not None:
            sp = parent.child("ec batch dispatch")
        elif self.tracer is not None:
            sp = self.tracer.new_trace("ec batch dispatch")
        else:
            return None
        return (sp.tag("lane", g.kind)
                  .tag("group_size", len(g.requests))
                  .tag("bytes", g.pending_bytes))

    # -- circuit breaker (device-dispatch watchdog) --------------------------

    def _breaker(self, kind: str) -> _LaneBreaker:
        br = self._breakers.get(kind)
        if br is None:
            br = self._breakers[kind] = _LaneBreaker()
        return br

    def open_lanes(self) -> List[str]:
        """Lane names whose breaker is currently OPEN (serving from the
        CPU mirrors) — the BREAKER_OPEN health check's feed."""
        with self._breaker_lock:
            return [k for k, b in self._breakers.items()
                    if b.state == _LaneBreaker.OPEN]

    def _gauge_open_lanes_locked(self) -> None:
        self.perf.set("breaker_open_lanes",
                      sum(1 for b in self._breakers.values()
                          if b.state == _LaneBreaker.OPEN))

    def _breaker_route_cpu(self, kind: str) -> bool:
        """True = serve this group host-side (breaker open); False =
        dispatch to the device (closed, or the half-open probe)."""
        with self._breaker_lock:
            br = self._breakers.get(kind)
            if br is None or br.state != _LaneBreaker.OPEN:
                return False
            if time.monotonic() >= br.open_until and not br.probing:
                br.probing = True  # half-open: ONE group probes the device
                self.perf.inc("breaker_probe")
                return False
            return True

    def _breaker_failure(self, kind: str) -> None:
        with self._breaker_lock:
            br = self._breaker(kind)
            br.cooldown = (min(br.cooldown * 2, self.breaker_cooldown_max)
                           if br.cooldown else self.breaker_cooldown)
            br.state = _LaneBreaker.OPEN
            br.open_until = time.monotonic() + br.cooldown
            br.probing = False
            self.perf.inc("breaker_trip")
            self._gauge_open_lanes_locked()

    def _breaker_success(self, kind: str) -> None:
        with self._breaker_lock:
            br = self._breakers.get(kind)
            if br is None or br.state == _LaneBreaker.CLOSED:
                return
            if not br.probing:
                # a STRAGGLER from before the trip completing fine must
                # not close the breaker (and zero the escalating
                # cooldown) — only the designated half-open probe is
                # evidence about the lane's CURRENT health
                return
            br.state = _LaneBreaker.CLOSED
            br.cooldown = 0.0
            br.probing = False
            self.perf.inc("breaker_recover")
            self._gauge_open_lanes_locked()

    def _complete_cpu(self, g: _Group, wait_s: float = 0.0) -> None:
        """Serve a whole group on the host CPU mirrors (breaker open, or
        rescue after a device failure): every request resolves with the
        byte-identical numpy result.  A CPU-path error fails the group's
        futures like any dispatch error would."""
        t0 = time.monotonic()
        try:
            results = [
                _cpu_apply_request(g.kind, g.mbits, req.regions, g.w,
                                   g.out_rows, g.packetsize)
                for req in g.requests
            ]
        except Exception as e:
            self._fail_group(g, e)
            return
        self.perf.inc("staged_layout_bytes",
                      sum(req.regions.nbytes for req in g.requests
                          if isinstance(req.regions, StripeRows)))
        for req, res in zip(g.requests, results):
            try:
                req.future.set_result(res)
            except InvalidStateError:
                pass
        self.perf.inc("breaker_fallback")
        self.timeline.append({
            "ts": time.time(), "lane": g.kind,
            "group_size": len(g.requests),
            "bytes": g.pending_bytes,
            "queue_wait_s": round(wait_s, 6),
            "device_s": round(time.monotonic() - t0, 6),
            "cpu_fallback": True})

    def _launch_safe(self, groups: List[_Group]) -> list:
        launched = []
        for g in groups:
            if not g.requests:
                continue
            now = time.monotonic()
            # queue-wait: how long each request coalesced before launch
            wait_s = 0.0
            for req in g.requests:
                w = now - req.t_submit
                self.perf.tinc("queue_wait", w)
                wait_s += w
                if req.span is not None:
                    req.span.event(f"ec coalesced lane={g.kind} "
                                   f"group={len(g.requests)}")
            wait_s /= len(g.requests)
            if self._breaker_route_cpu(g.kind):
                # lane breaker open: the device is sick — serve the whole
                # group host-side, byte-identical
                self._complete_cpu(g, wait_s)
                continue
            sp = self._dispatch_span(g)
            compile_mark = self._compiles.thread_seconds()
            if self.inject_dispatch_delay:
                # osd_debug_inject_dispatch_delay: counted into the
                # dispatch elapsed (t_launch = now, above) so the
                # watchdog sees the slow dispatch
                time.sleep(self.inject_dispatch_delay)
            try:
                with tracing.section("devbound", "launch"), \
                        self.perf.time_avg("launch"):
                    state = self._launch(g)
                if sp is not None:
                    sp.event("launched")
                launched.append(_Launched(g, state, now, sp, wait_s,
                                          compile_mark))
            except Exception as e:
                # device launch failure: trip the breaker and RESCUE the
                # group host-side — submitters never see the device die,
                # the log does
                log.error("ec batch launch failed on lane %s (%d requests); "
                          "served from the CPU, breaker tripped", g.kind,
                          len(g.requests), exc_info=e)
                if sp is not None:
                    sp.event(f"launch failed: {type(e).__name__}")
                    sp.finish()
                self._breaker_failure(g.kind)
                self._complete_cpu(g, wait_s)
        if launched and self._launch_hook is not None:
            self._launch_hook()
        return launched

    def _complete_safe(self, launched: list) -> None:
        for lc in launched:
            g, state = lc.group, lc.state
            try:
                if LANES[g.kind].resident:
                    self._complete_resident(g, state)
                else:
                    self._complete_packed(g, state)
            except Exception as e:
                # device completion failure: trip the breaker and rescue
                # the group host-side (byte-identical CPU mirrors)
                log.error("ec batch completion failed on lane %s (%d "
                          "requests); served from the CPU, breaker tripped",
                          g.kind, len(g.requests), exc_info=e)
                if lc.span is not None:
                    lc.span.event(f"complete failed: {type(e).__name__}")
                    lc.span.finish()
                self._breaker_failure(g.kind)
                self._complete_cpu(g, lc.wait_s)
                continue
            device_s = time.monotonic() - lc.t_launch
            # XLA compiles this thread ran since the launch (this
            # dispatch's own first compile, or a later round's while this
            # one was in flight) are host work, not evidence about the
            # lane: the watchdog judges what is left
            compile_s = self._compiles.thread_seconds() - lc.compile_mark
            if compile_s > 0:
                self.perf.tinc("dispatch_compile", compile_s)
            if (self.dispatch_timeout
                    and device_s - compile_s > self.dispatch_timeout):
                # the dispatch COMPLETED (results are good) but blew the
                # watchdog budget: the lane is sick — trip so the next
                # groups take the CPU path until a probe proves it healthy
                log.error("ec batch dispatch on lane %s took %.1fs (%.1fs "
                          "of it compiling) against dispatch_timeout %.1fs; "
                          "breaker tripped", g.kind, device_s, compile_s,
                          self.dispatch_timeout)
                self._breaker_failure(g.kind)
            else:
                self._breaker_success(g.kind)
            self.perf.tinc("dispatch_dev", device_s)
            self.perf.hinc("group_size", len(g.requests))
            if lc.span is not None:
                lc.span.event("fan-out")
                lc.span.finish()
            for req in g.requests:
                if req.span is not None:
                    req.span.event(f"ec fan-out lane={g.kind}")
            self.timeline.append({
                "ts": time.time(), "lane": g.kind,
                "group_size": len(g.requests),
                "bytes": g.pending_bytes,
                "queue_wait_s": round(lc.wait_s, 6),
                "device_s": round(device_s, 6)})

    @staticmethod
    def _fail_group(g: _Group, e: Exception) -> None:
        for req in g.requests:
            try:
                req.future.set_exception(e)
            except InvalidStateError:
                pass

    def _dispatch(self, groups: List[_Group]) -> None:
        # synchronous drain (flush()/close()): launch then complete
        self._complete_safe(self._launch_safe(groups))

    def _fetch(self, result) -> np.ndarray:
        """A dispatch's result on the host.  np.asarray blocks until the
        program ran and its output crossed D2H; nothing else waits for the
        device, so this is the `fetch` half of dispatch_dev."""
        with tracing.section("devbound", "fetch"), \
                self.perf.time_avg("fetch"):
            out = np.asarray(result)
        self.perf.inc("d2h_bytes", out.nbytes)
        return out

    def _note_dispatch(self, nbytes: int, sharded: bool) -> None:
        """Dispatch-complete accounting shared by every lane."""
        self.perf.inc("dispatch")
        if sharded:
            self.perf.inc("sharded_dispatch")
        self.perf.inc("bytes", nbytes)

    def _maybe_shard(self, batch, align: int = 1):
        """Lay a dispatch batch across the mesh when one is attached.
        Columns pad out to a device-grid multiple (bucket_columns gives
        powers of two, which a 6-device grid would never divide) — the
        pad is zeros beyond every request's slice, so fan-out offsets
        are unaffected.  `align` additionally rounds the padded width to
        a multiple of lcm(grid, align): the packed-bit lanes need whole
        u32 words per plane row (align=32) even after grid padding.
        Returns (batch, sharded)."""
        if self.mesh is None:
            return batch, False
        try:
            want = self.mesh.pad_cols(batch.shape[1])
            if align > 1:
                import math

                lcm = (align * self.mesh.n_devices
                       // math.gcd(align, self.mesh.n_devices))
                want = -(-want // lcm) * lcm
            if want != batch.shape[1]:
                batch = np.pad(
                    batch, ((0, 0), (0, want - batch.shape[1])))
            return self.mesh.shard_batch(batch), True
        except Exception as e:
            # sick mesh: single-device still serves, but never in silence
            # (sharded_dispatch < dispatch and this counter say so)
            self.perf.inc("mesh_shard_failed")
            log.error("mesh layout of a %s batch failed; dispatching on "
                      "one device", batch.shape, exc_info=e)
            return batch, False

    def _launch(self, g: _Group):
        """Launch one group on its lane: stage the requests column-wise
        in ONE buffer of the bucketed width (staged_cols: bounds XLA
        recompiles), each written once — a request of rows by a slice
        copy, a stripe-order one laid out in place, then the bucket's
        zeros — shard across the mesh
        when one is attached, and otherwise start the H2D transfer NOW so
        it overlaps the previous round's result fetch; then enqueue the
        lane's one fused program (async: a device handle comes back).
        Returns (widths, out, sharded, nbytes, staged): `staged` is the
        host buffer, whose column ranges are the data rows a stripe-order
        request gets back."""
        import jax

        lane = LANES[g.kind]
        align = lane.align or lane.block(g.w, g.packetsize)
        widths = [req.regions.shape[1] for req in g.requests]
        cols = sum(widths)
        staged = np.empty(
            (g.requests[0].regions.shape[0],
             staged_cols(g.kind, g.w, g.packetsize, cols)), dtype=np.uint8)
        off = laid_out = 0
        for width, req in zip(widths, g.requests):
            dst = staged[:, off : off + width]
            if isinstance(req.regions, StripeRows):
                req.regions.lay_into(dst)
                laid_out += req.regions.nbytes
            else:
                np.copyto(dst, req.regions)
            off += width
        if laid_out:
            self.perf.inc("staged_layout_bytes", laid_out)
        if cols != staged.shape[1]:
            staged[:, cols:] = 0
            self.perf.inc("pad_bytes",
                          (staged.shape[1] - cols) * staged.shape[0])
        batch, nbytes = staged, staged.nbytes
        self.perf.inc("h2d_bytes", nbytes)
        if g.kind == "packetrows" and g.packetsize % 4 == 0:
            # a packet is XORed whole, so the device gets it as u32 words
            # when its size allows
            batch, align = batch.view(np.uint32), align // 4
        batch, sharded = self._maybe_shard(batch, align=align)
        if not sharded:
            batch = jax.device_put(batch)  # async H2D staging
        return widths, lane.device(g, batch), sharded, nbytes, staged

    @staticmethod
    def _resolve(g: _Group, req: _Request, result, staged: np.ndarray,
                 off: int, width: int) -> None:
        """Resolve one request's future; a stripe-order request also gets
        its data rows, the columns _launch laid out for it."""
        if isinstance(req.regions, StripeRows):
            rows = staged[:, off : off + width]
            # alone in its dispatch, the rows are views: what they pin is
            # the request's own bytes and its bucket's zeros.  Out of a
            # shared buffer they are copied, as every output is: a view
            # would pin the whole batch for as long as one object lives
            result = _with_data_rows(
                result, rows if len(g.requests) == 1 else rows.copy())
        # a submitter may have been CANCELLED while waiting (an async op
        # torn down mid-flight propagates cancellation into the future
        # via asyncio.wrap_future): its slice is simply dropped
        try:
            req.future.set_result(result)
        except InvalidStateError:
            pass  # cancelled in the check-to-set window

    def _complete_packed(self, g: _Group, state) -> None:
        widths, out, sharded, nbytes, staged = state
        out = self._fetch(out).view(np.uint8)  # the packet lane's u32 words
        self._note_dispatch(nbytes, sharded)
        off = 0
        for width, req in zip(widths, g.requests):
            # copy: a view would pin the whole batch buffer for as
            # long as any single result stays alive
            self._resolve(g, req, out[:, off : off + width].copy(),
                          staged, off, width)
            off += width

    def _complete_resident(self, g: _Group, state) -> None:
        """Fan out a resident lane's two products per request: packed
        parity bytes for persistence, and the request's columns of the
        data ‖ parity bit-rows, which stay on the device."""
        # DONATION SAFETY: every fan-out below is a device-side SLICE of
        # the one batched `rows` product — consumers (the pagestore's
        # device-arm install, ceph_tpu/ops/slab.py) must never donate
        # the DATA argument of their kernels, because sibling requests
        # alias the same underlying buffer; only the slab argument,
        # which this plane never hands out, is donatable.
        widths, (packed, rows), sharded, nbytes, staged = state
        packed = self._fetch(packed)
        self._note_dispatch(nbytes, sharded)
        # THE CONTRACT of the resident half: a request's plane rows come
        # back as wide as a dispatch of the request alone stages them —
        # its columns, then zeros up to the lane's pow2 bucket — so what
        # a consumer compiles for (the store's install) is keyed by the
        # bucket and told the width, never by the width (rados/
        # pagestore.py put_planar's `trim`).
        if len(g.requests) == 1:
            # the product IS that: no op on the device graph, and the
            # install's flatten sees one contiguous buffer
            width = widths[0]
            self._resolve(g, g.requests[0],
                          (packed if packed.shape[1] == width
                           else packed[:, :width].copy(), rows),
                          staged, 0, width)
            return
        from ceph_tpu.ops.slab import plane_window

        # resident columns per packed byte column, after any mesh
        # grid-padding: 1/32 for u32 plane words (request widths are whole
        # words, _check_packedbit_resident), 1/2, 1 or 2 for int8 planes
        # of w=16, 8, 4
        cols, rcols = packed.shape[1], rows.shape[1]
        off = 0
        for width, req in zip(widths, g.requests):
            # one jitted program per (product shape, bucket), told the
            # offset and the width: requests of unequal widths land at
            # another offset in every group, and an eager slice compiles
            # for each, here, inside a served window
            lo = off * rcols // cols
            self._resolve(g, req, (
                packed[:, off : off + width].copy(),
                plane_window(
                    rows, lo, (off + width) * rcols // cols - lo,
                    min(rcols, staged_cols(g.kind, g.w, g.packetsize,
                                           width) * rcols // cols))),
                staged, off, width)
            off += width
