"""Stripe-batching dispatch queue — amortizing many small EC ops into one
device call.

The reference dispatches its codec once per 4 KiB-unit stripe inside
ECUtil::encode (reference src/osd/ECUtil.cc:123-160) and per 1 MiB buffer in
the benchmark; a TPU dispatch has fixed launch latency, so the >=10x target
"lives or dies on the batching queue" (SURVEY.md §7 hard part 2).  This
queue aggregates encode/decode requests from many objects/ops, concatenates
them column-wise into one [rows, sum(B)] buffer per (matrix, layout) group,
runs ONE bit-plane matmul, and fans completions back out — the same
submit -> aggregate -> dispatch -> completion-fan-out pipeline ECBackend's
write path drives (submit_transaction -> ... -> try_reads_to_commit,
ECBackend.cc:1525->1989).

Threading model: submit() is non-blocking and returns a Future; a worker
thread flushes when pending bytes cross `max_pending_bytes` or `max_delay`
elapses, whichever first.  flush() forces a synchronous drain (used by
tests and by the benchmark's timed sections).

BIT-PLANAR RESIDENCY (the measured ~1.6x win, ceph_tpu/ops/gf2.py
writeup): `submit_planar` dispatches over shards that already live in HBM
as int8 bit-planes — matmul only, no unpack/pack — and resolves to planar
device buffers, so encode -> decode -> recovery chain on-device.
`PlanarShardStore` is the residency manager: an LRU-bounded HBM cache of
planar shard rows where bytes pay the pack/unpack boundary exactly once,
when they enter or leave the device tier (the reference's analog is the
buffer staying in L2/registers across ECUtil::encode's per-stripe loop,
reference src/osd/ECUtil.cc:123-160; on a TPU the "stay resident" scope
is HBM across whole pipeline stages).

PACKED-BIT PRODUCTION LANE (the measured 1.45x over int8 planes,
ceph_tpu/ops/gf2.py lane-promotion writeup): for w=8 byte-layout codes
the resident trio has a u32-word mirror — `submit_packedbit` (bytes in,
bytes out), `submit_packedbit_resident` (bytes in, parity bytes + u32
planes out), `submit_packedbit_planes` (resident planes in/out) — each
dispatch running the matrix as a static XOR schedule compiled per matrix
(encode generators and decode signatures alike) behind the gf2 LRU.
Residents store at 1 HBM byte per data byte instead of 8, so the same
store budget holds 8x the objects.

PACKET-LAYOUT LANE (`submit_packetrows`, lane name "packetrows"): the
bit-matrix codes (cauchy_orig/good, liberation, blaum_roth, liber8tion)
lay a chunk out as w*packetsize-byte blocks of w packets, and a packet IS
a bit-row.  Their lane is the packed-bit lane with another pair of layout
stages, chosen by the codec's bit_layout, w and packetsize: the same
launch/complete/mirror, the same static XOR schedule behind the same LRU,
block transposes ([n, nb, w, p] <-> [n*w, nb*p]) on the device where the
byte layout has bit transposes.  Requests coalesce by columns (a chunk is
whole blocks); the width buckets to a power of two of blocks.  Encode
generators and the inverted bit-matrices of decode signatures ride it
(ecutil's plans), so no served op of such a pool dispatches from the
event loop.  It keeps no residents.

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

OBSERVABILITY (the `ec_tpu` + `planar_store` counter sets): the queue owns
a PerfCounters set — name -> meaning -> kind in _build_ec_tpu_perf — with
per-lane submit/byte counters (submit_<lane>/bytes_<lane>, u64), queue-wait
and device-dispatch longrunavg latencies (queue_wait, dispatch_dev), a
coalesced-group-size histogram (group_size), and flush-cause counters
(flush_bytes/flush_delay/flush_forced, u64).  Daemons add the set to their
PerfCountersCollection (`perf dump`, mgr prometheus); `dump_timeline()`
backs the `dump_ec_batch_timeline` asok command with the last 128
dispatches (lane, group size, bytes, wait, device seconds).  Trace spans
ride submissions: a `span=` parent (the OSD's `ec write` trace) gets
submit/coalesce/fan-out events plus a per-dispatch child span tagged with
lane/group_size/bytes.  PlanarShardStore mirrors its residency stats into
a `planar_store` set: admit/hit/miss/evict (u64), resident_bytes + entries
(gauges), and pack_s/unpack_s longrunavg — the host<->device boundary
seconds paid at admit()/read().
"""

from __future__ import annotations

import collections
import logging
import threading
import time
from collections import OrderedDict
from concurrent.futures import Future, InvalidStateError
from dataclasses import dataclass, field
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from ceph_tpu.common import tracing
from ceph_tpu.common.perf_counters import PerfCounters, PerfCountersBuilder

log = logging.getLogger("ceph_tpu.ec.batch")

#: the dispatch lanes, in promotion order (int8 trio, packed-bit trio, and
#: the packed-bit lane's packet-layout form)
LANES = ("packed", "planar", "resident",
         "packedbit", "packedbit_resident", "packedbit_planes",
         "packetrows")


def _build_ec_tpu_perf() -> PerfCounters:
    """The `ec_tpu` counter set (COUNTER SCHEMA below; dumped via `perf
    dump` on any daemon sharing the process queue, exported by the mgr
    prometheus module, snapshotted into BENCH records):

      submit               u64         requests accepted, all lanes
      submit_<lane>        u64         requests accepted per lane
      bytes_<lane>         u64         packed-equivalent bytes submitted per lane
      dispatch             u64         device calls issued
      sharded_dispatch     u64         dispatches laid across the mesh
      overlapped_rounds    u64         rounds whose launch overlapped a fetch
      bytes                u64         bytes dispatched (incl. bucket padding)
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


def _cpu_apply_request(kind: str, mbits: np.ndarray, regions, w: int,
                       out_rows: int, packetsize: int = 0):
    """Serve ONE lane request host-side; returns exactly what the device
    lane's fan-out would have resolved the request's future with (device
    buffers become numpy arrays — every consumer accepts both)."""
    mb = np.asarray(mbits, dtype=np.uint8)
    if kind == "packetrows":
        # mirror of ops/gf2.apply_packetrows_fn: block transpose in, XOR
        # of whole packets, block transpose out
        data = np.asarray(regions, dtype=np.uint8)
        n, cols = data.shape
        nb = cols // (w * packetsize)
        rows = (data.reshape(n, nb, w, packetsize).transpose(0, 2, 1, 3)
                .reshape(n * w, nb * packetsize))
        return (_np_xor_rows(mb, rows)
                .reshape(out_rows, w, nb, packetsize).transpose(0, 2, 1, 3)
                .reshape(out_rows, cols))
    if kind in ("packed", "packedbit"):
        bits = _np_unpack_bits(np.asarray(regions, dtype=np.uint8), w)
        return _np_pack_bits(_np_matmul_gf2(mb, bits), w, out_rows)
    if kind == "planar":
        return _np_matmul_gf2(mb, np.asarray(regions))
    if kind == "resident":
        bits = _np_unpack_bits(np.asarray(regions, dtype=np.uint8), w)
        pbits = _np_matmul_gf2(mb, bits)
        return (_np_pack_bits(pbits, w, out_rows),
                np.concatenate([bits, pbits], axis=0))
    if kind == "packedbit_resident":
        bits = _np_unpack_bits(np.asarray(regions, dtype=np.uint8), 8)
        pbits = _np_matmul_gf2(mb, bits)
        return (_np_pack_bits(pbits, 8, out_rows),
                np.concatenate([_np_words(bits), _np_words(pbits)], axis=0))
    if kind == "packedbit_planes":
        return _np_xor_rows(mb, np.asarray(regions))
    raise ValueError(f"unknown lane kind {kind!r}")


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
    """One queued lane submission.  t_submit feeds the queue_wait
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
    # dispatch lane: "packed" (unpack+matmul+pack fused per dispatch),
    # "planar" (matmul-only over resident int8 bit-planes), "resident"
    # (packed in -> packed parity + planar rows out, the write path);
    # plus the packed-bit production trio mirroring them over u32 plane
    # words + static XOR schedules (ceph_tpu/ops/gf2.py lane promotion):
    # "packedbit", "packedbit_planes", "packedbit_resident"; and
    # "packetrows", the packed-bit lane for PACKET-layout codes (its
    # layout stages are block transposes of `packetsize`-byte packets)
    kind: str = "packed"
    packetsize: int = 0  # packet layout only
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
        # 16 MiB/dispatch: the measured HBM sweet spot for the planar
        # pipeline (bench.py r4 sweep — the 8x bit-plane expansion makes
        # 64 MiB batches HBM-bound on v5e; 2 MiB of columns at k=8 wins)
        max_pending_bytes: int = 16 << 20,
        max_delay: Optional[float] = None,
        use_pallas: Optional[bool] = None,
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
        self._use_pallas = use_pallas
        # device-mesh execution (ceph_tpu/parallel/mesh.py): when a mesh
        # is attached (or auto-engages on a multi-chip backend), every
        # dispatch lane lays its batch out across the mesh's column axis
        # — the same compiled ops run SPMD over all devices, collectives
        # inserted by XLA where a consumer needs them.  mesh=None means
        # auto-detect; mesh=False pins the queue single-device (bench
        # arms and single-device comparisons that must not auto-engage).
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
        # parent (e.g. bench traffic) root here; the OSD attaches its ctx
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

    def submit(
        self, mbits: np.ndarray, regions: np.ndarray, w: int, out_rows: int,
        span=None,
    ) -> "Future[np.ndarray]":
        """Queue (mbits @ regions) over the byte layout; resolves to the
        [out_rows, B] parity/reconstruction buffer."""
        return self._submit(mbits, regions, w, out_rows, "packed", span)

    def submit_planar(
        self, mbits: np.ndarray, bits, w: int, out_rows: int, span=None
    ) -> "Future[object]":
        """Queue (mbits @ bits) over ALREADY-PLANAR device bit-planes
        ([rows*w, Bcols] int8); resolves to the [out_rows*w, Bcols] planar
        device buffer — no pack, the result stays HBM-resident for the
        next pipeline stage."""
        return self._submit(mbits, bits, w, out_rows, "planar", span)

    def submit_resident(
        self, mbits: np.ndarray, rows: np.ndarray, w: int, out_rows: int,
        span=None,
    ) -> "Future[object]":
        """The residency WRITE path: packed [n, B] uint8 rows in, ONE
        fused batched device call (unpack + matmul + parity pack), and
        the future resolves to (packed_parity np [out_rows, B],
        all_bits planar [(n+out_rows)*w, Bc]) — parity bytes for
        persistence, planar rows to keep HBM-resident.  Submission is
        non-blocking (no device work on the caller's thread), so
        concurrent ops coalesce exactly like the packed lane."""
        return self._submit(mbits, rows, w, out_rows, "resident", span)

    # -- packed-bit lanes (the production w=8 trio, ceph_tpu/ops/gf2.py
    #    lane-promotion writeup: u32-word bit-planes + static XOR
    #    schedules compiled per matrix behind the LRU) ----------------------

    def submit_packedbit(
        self, mbits: np.ndarray, regions: np.ndarray, w: int, out_rows: int,
        span=None,
    ) -> "Future[np.ndarray]":
        """Queue a [out_rows*8, n*8] GF(2) bit-matrix over packed [n, B]
        uint8 rows through the packed-bit XOR-schedule lane (one fused
        unpack -> u32 words -> schedule -> byte pack device call per
        coalesced group); resolves to the [out_rows, B] parity or
        reconstruction buffer.  Encode generators AND per-decode-
        signature matrices both land here — each matrix is its own
        dispatch group and its own LRU-cached compiled schedule."""
        assert w == 8, "packed-bit lane is the w=8 byte-layout lane"
        return self._submit(mbits, regions, w, out_rows, "packedbit", span)

    def submit_packedbit_resident(
        self, mbits: np.ndarray, rows: np.ndarray, w: int, out_rows: int,
        span=None,
    ) -> "Future[object]":
        """Packed-bit residency WRITE path: packed [n, B] uint8 rows in
        (B % 32 == 0), resolves to (packed_parity np [out_rows, B],
        all_planes u32 [(n+out_rows)*8, B//32]) — parity bytes for
        persistence, u32 plane words to stay HBM-resident at 1/8th the
        int8-plane footprint."""
        assert w == 8, "packed-bit lane is the w=8 byte-layout lane"
        if rows.shape[1] % 32:
            # reject at SUBMISSION: a misaligned request that reached
            # launch would fail every innocent request coalesced with it
            raise ValueError(
                "packedbit_resident requests must be 32-byte-column "
                f"aligned, got width {rows.shape[1]}")
        return self._submit(mbits, rows, w, out_rows, "packedbit_resident",
                            span)

    def submit_packedbit_planes(
        self, mbits: np.ndarray, planes, w: int, out_rows: int, span=None
    ) -> "Future[object]":
        """Queue an XOR schedule over ALREADY-RESIDENT u32 plane words
        ([rows*8, Wc] uint32); resolves to the [out_rows*8, Wc] device
        buffer — no pack, the result stays resident for the next stage
        (the packed-bit mirror of submit_planar)."""
        assert w == 8, "packed-bit lane is the w=8 byte-layout lane"
        return self._submit(mbits, planes, w, out_rows, "packedbit_planes",
                            span)

    def submit_packetrows(
        self, mbits: np.ndarray, regions: np.ndarray, w: int,
        packetsize: int, out_rows: int, span=None,
    ) -> "Future[np.ndarray]":
        """The packed-bit lane for PACKET-layout codes (cauchy_orig/good,
        liberation, blaum_roth, liber8tion): queue a [out_rows*w, n*w]
        GF(2) bit-matrix over [n, B] uint8 chunks, B a whole number of
        w*packetsize-byte blocks.  One fused device call per coalesced
        group — block transpose to packet rows, the same static XOR
        schedule, block transpose back (ops/gf2.apply_packetrows_fn) —
        resolving to the [out_rows, B] parity or reconstruction buffer.
        Requests coalesce by columns like the byte lanes': a chunk is
        whole blocks, so the concatenation is a valid chunk set."""
        if packetsize < 1 or regions.shape[1] % (w * packetsize):
            # reject at SUBMISSION, as submit_packedbit_resident does
            raise ValueError(
                f"packetrows requests are whole w*packetsize={w}*"
                f"{packetsize}-byte blocks, got width {regions.shape[1]}")
        return self._submit(mbits, regions, w, out_rows, "packetrows", span,
                            packetsize=packetsize)

    @tracing.sectioned("ecplan", "queue_submit")
    def submit_group(self, items, span=None) -> List[Future]:
        """Group-aware submit (the messenger/recovery whole-stripe-group
        handoff seam): queue a LIST of lane submissions — each item is
        (mbits, regions, w, out_rows, kind), plus the packetsize on the
        packet-layout lane — under ONE lock acquisition
        and ONE worker wakeup, so a coalesced group of objects reaches
        the EC tier as a single buffer-list submission instead of N
        contended submits.  Items sharing a dispatch signature land in
        the same _Group exactly as per-item submits would; returns the
        per-item futures, index-aligned."""
        futs: List[Future] = []
        sizes: List[int] = []
        now = time.monotonic()
        if span is not None:
            span.event(f"ec submit group n={len(items)}")
        with self._cv:
            if self._stop:
                raise RuntimeError("BatchingQueue is closed")
            for mbits, regions, w, out_rows, kind, *packetsize in items:
                fut: Future = Future()
                futs.append(fut)
                sizes.append(self._queue_locked(
                    mbits, regions, w, out_rows, kind, fut, now, span,
                    *packetsize))
            if items:
                self._cv.notify()
        for (_, _, _, _, kind, *_), nbytes in zip(items, sizes):
            self.perf.inc("submit")
            self.perf.inc(f"submit_{kind}")
            self.perf.inc(f"bytes_{kind}", nbytes)
        if len(items) > 1:
            self.perf.inc("submit_group")
            self.perf.hinc("group_submit_size", len(items))
        return futs

    def _queue_locked(self, mbits, regions, w, out_rows, kind, fut,
                      now, span, packetsize: int = 0) -> int:
        """Insert one request into its dispatch group (caller holds the
        lock).  Returns the packed-equivalent byte size counted."""
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
        # planar bit-plane submissions are 8x-expanded int8: count
        # their packed-equivalent size or the lane would flush at 1/8
        # the measured batch sweet spot
        nbytes = self._req_bytes(kind, mbits, regions)
        group.pending_bytes += nbytes
        self._pending += nbytes
        if self._oldest is None:
            self._oldest = now
        return nbytes

    @tracing.sectioned("ecplan", "queue_submit")
    def _submit(self, mbits, regions, w, out_rows, kind,
                span=None, packetsize: int = 0) -> Future:
        fut: Future = Future()
        now = time.monotonic()
        if span is not None:
            span.event(f"ec submit lane={kind}")
        with self._cv:
            if self._stop:
                raise RuntimeError("BatchingQueue is closed")
            nbytes = self._queue_locked(mbits, regions, w, out_rows, kind,
                                        fut, now, span, packetsize)
            self._cv.notify()
        self.perf.inc("submit")
        self.perf.inc(f"submit_{kind}")
        self.perf.inc(f"bytes_{kind}", nbytes)
        return fut

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

    @staticmethod
    def _req_bytes(kind: str, mbits: np.ndarray, regions) -> int:
        # flush thresholds are tuned in PACKED bytes (see _submit)
        if kind == "planar":
            return regions.shape[1] * mbits.shape[1] // 8
        if kind == "packedbit_planes":
            # u32 plane words carry exactly 1 bit/bit: total plane bytes
            # == packed bytes (the layout's whole point)
            return int(regions.shape[0]) * int(regions.shape[1]) * 4
        return regions.nbytes

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
                n = self._req_bytes(g.kind, g.mbits, req.regions)
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
                    if g.kind == "planar":
                        state = self._launch_planar(g)
                    elif g.kind == "resident":
                        state = self._launch_resident(g)
                    elif g.kind in ("packedbit", "packetrows"):
                        state = self._launch_packedbit(g)
                    elif g.kind == "packedbit_resident":
                        state = self._launch_packedbit_resident(g)
                    elif g.kind == "packedbit_planes":
                        state = self._launch_packedbit_planes(g)
                    else:
                        state = self._launch_packed(g)
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
                if g.kind == "planar":
                    self._complete_planar(g, state)
                elif g.kind == "resident":
                    self._complete_resident(g, state)
                elif g.kind == "packedbit_resident":
                    self._complete_packedbit_resident(g, state)
                elif g.kind == "packedbit_planes":
                    self._complete_packedbit_planes(g, state)
                else:
                    # "packed", "packedbit" and "packetrows": all fan
                    # packed uint8 byte columns back out
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


    def _maybe_shard(self, batch, pad_np: bool, align: int = 1):
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
                extra = want - batch.shape[1]
                if pad_np:
                    batch = np.pad(batch, ((0, 0), (0, extra)))
                else:
                    import jax.numpy as jnp

                    batch = jnp.pad(batch, ((0, 0), (0, extra)))
            return self.mesh.shard_batch(batch), True
        except Exception as e:
            # sick mesh: single-device still serves, but never in silence
            # (sharded_dispatch < dispatch and this counter say so)
            self.perf.inc("mesh_shard_failed")
            log.error("mesh layout of a %s batch failed; dispatching on "
                      "one device", batch.shape, exc_info=e)
            return batch, False

    def _stage_packed_batch(self, g: _Group, align: int = 1,
                            words: bool = False):
        """The shared launch preamble for packed-byte request groups:
        coalesce the requests column-wise, bucket the width to a power of
        two of `align`-column units (bounds XLA recompiles; the packet
        lane's unit is its w*packetsize block, the others' divides the
        1024-column floor, so theirs is the plain pow2 width), shard
        across the mesh when one is attached, and otherwise start the H2D
        transfer NOW so it overlaps the previous round's result fetch.
        `words` hands the device the same bytes as uint32 columns.
        Returns (widths, batch, sharded, nbytes)."""
        import jax

        from ceph_tpu.ops.gf2 import bucket_columns as _bucket

        widths = [req.regions.shape[1] for req in g.requests]
        batch = np.concatenate([req.regions for req in g.requests], axis=1)
        cols = batch.shape[1]
        pad = align * _bucket(-(-cols // align),
                              lo=max(1, 1024 // align)) - cols
        if pad:
            batch = np.pad(batch, ((0, 0), (0, pad)))
        nbytes = batch.nbytes
        self.perf.inc("h2d_bytes", nbytes)
        if words:
            batch, align = batch.view(np.uint32), align // 4
        batch, sharded = self._maybe_shard(batch, pad_np=True, align=align)
        if not sharded:
            batch = jax.device_put(batch)  # async H2D staging
        return widths, batch, sharded, nbytes

    def _launch_packed(self, g: _Group):
        from ceph_tpu.ops.gf2 import gf2_apply_bytes

        widths, batch, sharded, nbytes = self._stage_packed_batch(g)
        use_pallas = self._use_pallas and not sharded
        if use_pallas is None:
            from ceph_tpu.ops.gf2 import pallas_enabled
            from ceph_tpu.ops.pallas_gf2 import TILE_B
            from ceph_tpu.utils.jaxdev import probe_backend

            # pallas_call does not run under GSPMD sharding (it would
            # need a shard_map wrapper); sharded batches take XLA
            use_pallas = (
                not sharded
                and pallas_enabled()
                and probe_backend() == "tpu"
                and batch.shape[1] % TILE_B == 0
            )
        # async launch: the jitted call returns a device handle
        out = gf2_apply_bytes(g.mbits, batch, g.w, g.out_rows,
                              use_pallas=use_pallas)
        return widths, out, sharded, nbytes

    def _complete_packed(self, g: _Group, state) -> None:
        widths, out, sharded, nbytes = state
        out = self._fetch(out).view(np.uint8)  # the packet lane's u32 words
        self._note_dispatch(nbytes, sharded)
        off = 0
        for width, req in zip(widths, g.requests):
            # a submitter may have been CANCELLED while waiting (an
            # async op torn down mid-flight propagates cancellation
            # into the future via asyncio.wrap_future): its slice is
            # simply dropped
            try:
                # copy: a view would pin the whole batch buffer for as
                # long as any single result stays alive
                req.future.set_result(out[:, off : off + width].copy())
            except InvalidStateError:
                pass  # cancelled in the check-to-set window
            off += width

    def _launch_planar(self, g: _Group):
        """Matmul-only dispatch over HBM-resident bit-planes: ONE batched
        device call per (matrix) group; results are handed back as planar
        device buffers so the next stage chains without a host bounce."""
        import jax.numpy as jnp

        from ceph_tpu.ops.gf2 import bucket_columns as _bucket
        from ceph_tpu.ops.gf2 import gf2_matmul

        widths = [req.regions.shape[1] for req in g.requests]
        batch = (g.requests[0].regions if len(g.requests) == 1
                 else jnp.concatenate([req.regions
                                       for req in g.requests], axis=1))
        # pow2 column bucketing, same as the other lanes: varying
        # coalesced widths must not each compile a fresh gf2_matmul
        pad = _bucket(batch.shape[1]) - batch.shape[1]
        if pad:
            batch = jnp.pad(batch, ((0, 0), (0, pad)))
        batch, sharded = self._maybe_shard(batch, pad_np=False)
        out = gf2_matmul(jnp.asarray(g.mbits), batch)
        return widths, out, sharded

    def _complete_planar(self, g: _Group, state) -> None:
        widths, out, sharded = state
        self._note_dispatch(
            sum(w for w in widths) * g.mbits.shape[1] // 8, sharded)
        off = 0
        for width, req in zip(widths, g.requests):
            try:
                # device-side slice: stays planar-resident; no host copy
                req.future.set_result(out[:, off : off + width])
            except InvalidStateError:
                pass
            off += width

    def _launch_resident(self, g: _Group):
        """Residency write path: ONE fused batched call — unpack the
        concatenated packed rows, matmul, pack the parity — and fan both
        products out per request: (packed parity for persistence, planar
        rows to stay HBM-resident)."""
        from ceph_tpu.ops.gf2 import gf2_encode_resident

        widths, batch, sharded, nbytes = self._stage_packed_batch(g)
        # AFTER any mesh grid-padding: the planar fan-out factor must
        # relate all_bits' columns to the columns the matmul actually saw
        cols = batch.shape[1]
        packed, all_bits = gf2_encode_resident(
            g.mbits, batch, g.w, g.out_rows)
        return widths, packed, all_bits, sharded, nbytes, cols

    def _complete_resident(self, g: _Group, state) -> None:
        widths, packed, all_bits, sharded, nbytes, cols = state
        packed = self._fetch(packed)
        self._note_dispatch(nbytes, sharded)
        # planar columns per packed byte-column depends on w (w=16: B//2)
        cfac = all_bits.shape[1] / cols
        off = 0
        for width, req in zip(widths, g.requests):
            try:
                c0, c1 = int(off * cfac), int((off + width) * cfac)
                req.future.set_result((packed[:, off : off + width].copy(),
                                   all_bits[:, c0:c1]))
            except InvalidStateError:
                pass
            off += width

    # -- packed-bit lanes (u32 plane words + static XOR schedules) -----------

    def _launch_packedbit(self, g: _Group):
        """One fused schedule call over the coalesced packed rows:
        unpack -> u32 words -> XOR schedule -> byte pack, compiled per
        matrix behind the gf2 LRU.  Fan-out is byte columns, so requests
        of ANY width coalesce (pow2 bucketing keeps B % 32 == 0).

        The packet-layout form ("packetrows") is this lane with the other
        pair of layout stages: whole w*packetsize blocks in, a block
        transpose on the device where the byte layout has a bit
        transpose, the same schedule.  A packet is XORed whole, so the
        device gets it as u32 words when its size allows."""
        from ceph_tpu.ops.gf2 import gf2_apply_packedbit, gf2_apply_packetrows

        if g.kind == "packetrows":
            widths, batch, sharded, nbytes = self._stage_packed_batch(
                g, align=g.w * g.packetsize, words=g.packetsize % 4 == 0)
            out = gf2_apply_packetrows(g.mbits, batch, g.w, g.packetsize)
        else:
            widths, batch, sharded, nbytes = self._stage_packed_batch(
                g, align=32)
            out = gf2_apply_packedbit(g.mbits, batch)
        return widths, out, sharded, nbytes

    # completion: _complete_packed (identical packed-byte fan-out)

    def _launch_packedbit_resident(self, g: _Group):
        """Packed-bit residency write path: one fused batched call, both
        products fanned out per request — packed parity bytes for
        persistence, u32 plane words to stay HBM-resident.  Request
        widths must be whole u32 words (B % 32 == 0) so the plane
        fan-out slices stay word-aligned; submit_packedbit_resident
        rejects misaligned requests before they can coalesce."""
        from ceph_tpu.ops.gf2 import gf2_encode_packedbit_resident

        widths, batch, sharded, nbytes = self._stage_packed_batch(g, align=32)
        packed, planes = gf2_encode_packedbit_resident(g.mbits, batch)
        return widths, packed, planes, sharded, nbytes

    def _complete_packedbit_resident(self, g: _Group, state) -> None:
        # DONATION SAFETY: every fan-out below is a device-side SLICE of
        # the one batched `planes` product — consumers (the pagestore's
        # device-arm install, ceph_tpu/ops/slab.py) must never donate
        # the DATA argument of their kernels, because sibling requests
        # alias the same underlying buffer; only the slab argument,
        # which this plane never hands out, is donatable.
        widths, packed, planes, sharded, nbytes = state
        packed = self._fetch(packed)
        self._note_dispatch(nbytes, sharded)
        if len(g.requests) == 1 and packed.shape[1] == widths[0]:
            # single-request group covering the full (unpadded) batch:
            # hand the whole product back — no slice op on the device
            # graph, and the install's flatten sees one contiguous
            # buffer
            try:
                g.requests[0].future.set_result((packed, planes))
            except InvalidStateError:
                pass
            return
        off = 0
        for width, req in zip(widths, g.requests):
            try:
                # 32 byte columns per u32 plane word (integer exact: the
                # launch asserted width % 32 == 0)
                req.future.set_result((packed[:, off : off + width].copy(),
                                   planes[:, off // 32 : (off + width) // 32]))
            except InvalidStateError:
                pass
            off += width

    def _launch_packedbit_planes(self, g: _Group):
        """Schedule-only dispatch over resident u32 plane words — the
        packed-bit mirror of the planar lane: results stay device-side
        plane buffers, chaining without a host bounce."""
        import jax.numpy as jnp

        from ceph_tpu.ops.gf2 import bucket_columns as _bucket
        from ceph_tpu.ops.gf2 import gf2_xor_packed

        widths = [req.regions.shape[1] for req in g.requests]  # u32 words
        batch = (g.requests[0].regions if len(g.requests) == 1
                 else jnp.concatenate([req.regions
                                       for req in g.requests], axis=1))
        # pow2 word bucketing (lo=32 words == the byte lanes' 1024 cols)
        pad = _bucket(batch.shape[1], lo=32) - batch.shape[1]
        if pad:
            batch = jnp.pad(batch, ((0, 0), (0, pad)))
        batch, sharded = self._maybe_shard(batch, pad_np=False)
        out = gf2_xor_packed(g.mbits, batch)
        return widths, out, sharded

    def _complete_packedbit_planes(self, g: _Group, state) -> None:
        widths, out, sharded = state
        # u32 plane words carry 1 bit/bit, so plane bytes == packed-
        # equivalent bytes (same arithmetic as _req_bytes: C rows x Wc
        # words x 4 B/word; no 8x int8 expansion to divide back out)
        self._note_dispatch(sum(widths) * 4 * g.mbits.shape[1], sharded)
        off = 0
        for width, req in zip(widths, g.requests):
            try:
                req.future.set_result(out[:, off : off + width])  # stays resident
            except InvalidStateError:
                pass
            off += width


class PlanarShardStore:
    """HBM-resident planar shard cache — the residency manager behind the
    measured ~1.6x pack-elimination win (ceph_tpu/ops/gf2.py writeup).

    Rows of packed uint8 shard bytes are admitted ONCE (one on-device
    unpack) and then live in HBM as int8 bit-planes; every subsequent EC
    op on them — encode, decode-reconstruct, scrub re-encode, recovery —
    is a pure GF(2) matmul chaining planar buffers, and bytes are packed
    back exactly once, when they leave for the wire/store.  The
    reference's analog is the stripe buffer staying cache-resident across
    ECUtil::encode's loop (reference src/osd/ECUtil.cc:123-160); here the
    residency scope is HBM across whole pipeline stages.

    Capacity is a hard byte budget over the PLANAR footprint (w x the
    packed bytes): least-recently-used entries are evicted, so the store
    degrades to the packed path, never to an OOM.  Thread-safe — the OSD
    event loop, the batching worker, and tests may touch it concurrently.
    """

    def __init__(self, capacity_bytes: int = 256 << 20,
                 queue: Optional[BatchingQueue] = None):
        from ceph_tpu.common.lockdep import make_mutex

        self.capacity_bytes = capacity_bytes
        self.queue = queue
        self._lock = make_mutex("planar-store")
        self._entries: "OrderedDict[Any, Any]" = OrderedDict()
        self._bytes: Dict[Any, int] = {}
        self._trim: Dict[Any, int] = {}  # packedbit admits: pre-pad width
        # exit-boundary memo: key -> (version, packed host result).  The
        # store's contract is "pack exactly once per resident lifetime",
        # but a cache-tier resident is READ many times — without a memo
        # every resident-hit read re-pays the device pack.  Lives and
        # dies WITH the entry (cleared on put/drop/LRU-evict), so a
        # memo can never outlive or contradict its resident.  Host RAM,
        # not HBM — tracked separately (memo_bytes gauge) and capped at
        # the store's capacity so the total footprint the operator
        # budgets for is at most 2x capacity_bytes, never unbounded.
        self._memo: Dict[Any, Tuple[Any, Any]] = {}
        self.memo_bytes = 0
        self.resident_bytes = 0
        self.admits = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        # the `planar_store` perf set mirrors the bare ints above (kept:
        # eviction logic and tests read them) and adds the boundary
        # latencies the ints can't carry (module-docstring schema)
        self.perf = (
            PerfCountersBuilder("planar_store")
            .add_u64_counter("admit", "packed rows admitted (one unpack)")
            .add_u64_counter("hit", "resident lookups served")
            .add_u64_counter("miss", "lookups that fell to the packed path")
            .add_u64_counter("evict", "LRU evictions under the byte budget")
            .add_u64("resident_bytes", "planar HBM footprint (gauge)")
            .add_u64("entries", "resident objects (gauge)")
            .add_u64("memo_bytes",
                     "exit-boundary packed memo host footprint (gauge)")
            .add_time_avg("pack_s",
                          "device->host pack seconds at the exit boundary")
            .add_time_avg("unpack_s",
                          "host->device unpack seconds at admission")
            .create_perf_counters())
        # `perf reset` re-reads the live gauges instead of leaving the
        # residency footprint misreported as 0 until the next admit
        self.perf.resync = self._resync_gauges

    def _resync_gauges(self) -> None:
        # gauges are written INSIDE the store lock everywhere (here,
        # put_planar, drop): an unlocked write could overwrite a newer
        # value with a stale snapshot.  Lock order is store -> perf.
        with self._lock:
            self.perf.set("resident_bytes", self.resident_bytes)
            self.perf.set("entries", len(self._entries))
            self.perf.set("memo_bytes", self.memo_bytes)

    # -- host boundary (pack/unpack paid here, once) -------------------------

    def admit(self, key: Any, rows: np.ndarray, w: int = 8,
              meta: Any = None, layout: str = "planes"):
        """Unpack packed [n, B] uint8 rows onto the device and keep them
        resident under `key`.  Returns the resident device buffer.
        layout="planes" stores int8 bit-planes (any w); "packedbit"
        stores u32 plane words (w=8 only, 1/8th the footprint — the
        production lane), padding B out to whole words and trimming on
        read."""
        with self.perf.time_avg("unpack_s"):
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
                                meta=meta)
        self.admits += 1
        self.perf.inc("admit")
        return bits

    def read(self, key: Any) -> Optional[np.ndarray]:
        """Pack the resident rows back to [n, B] uint8 host bytes — the
        EXIT boundary.  None when not resident.  Handles both layouts
        (entry dtype tells them apart: uint32 words vs int8 planes)."""
        got = self.get_planar(key)
        if got is None:
            return None
        bits, w, n_rows, _meta = got
        if np.dtype(bits.dtype) == np.uint32:
            from ceph_tpu.ops.gf2 import from_packedbit

            with self.perf.time_avg("pack_s"):
                out = np.asarray(from_packedbit(bits, n_rows))
            with self._lock:
                trim = self._trim.get(key)
            return out if trim is None else out[:, :trim]
        from ceph_tpu.ops.gf2 import from_planar

        with self.perf.time_avg("pack_s"):
            return np.asarray(from_planar(bits, w, n_rows))

    # -- resident side (no pack/unpack anywhere below) -----------------------

    def put_planar(self, key: Any, bits, w: int = 8,
                   n_rows: Optional[int] = None, meta: Any = None,
                   trim: Optional[int] = None) -> None:
        """`meta` is caller state carried with the entry (the OSD stores
        the object VERSION there, so a read can reject a stale resident).
        `trim` is the pre-pad byte width of a packed-bit admit, installed
        under the same lock as the entry so a concurrent read never sees
        the entry without its trim."""
        if n_rows is None:
            n_rows = bits.shape[0] // w
        # HBM footprint by element width: int8 planes are 1 B/element
        # (8x the packed bytes), u32 packed-bit words 4 B/element (1x)
        nbytes = int(np.prod(bits.shape)) * np.dtype(bits.dtype).itemsize
        with self._lock:
            if key in self._entries:
                self.resident_bytes -= self._bytes[key]
            self._entries[key] = (bits, w, n_rows, meta)
            self._entries.move_to_end(key)
            self._bytes[key] = nbytes
            self._memo_discard(key)  # new rows: stale packed memo dies
            if trim is None:
                self._trim.pop(key, None)  # re-put resets admit-time trim
            else:
                self._trim[key] = trim
            self.resident_bytes += nbytes
            evicted = 0
            while self.resident_bytes > self.capacity_bytes and self._entries:
                old_key, _ = self._entries.popitem(last=False)
                self.resident_bytes -= self._bytes.pop(old_key)
                self._trim.pop(old_key, None)
                self._memo_discard(old_key)
                self.evictions += 1
                evicted += 1
            # gauge writes stay under the store lock (see _resync_gauges)
            self.perf.set("resident_bytes", self.resident_bytes)
            self.perf.set("entries", len(self._entries))
        if evicted:
            self.perf.inc("evict", evicted)

    def get_planar(self, key: Any):
        """(bits, w, n_rows, meta) or None; refreshes LRU position."""
        with self._lock:
            ent = self._entries.get(key)
            if ent is None:
                self.misses += 1
            else:
                self._entries.move_to_end(key)
                self.hits += 1
        self.perf.inc("hit" if ent is not None else "miss")
        return ent

    # -- the residency protocol shared with PagedResidentStore ---------------
    # (ceph_tpu/rados/pagestore.py): ecutil's planar_* helpers and the
    # OSD tier paths speak these four shapes so either store can sit
    # behind the cache tier.

    def touch(self, key: Any):
        """(w, n_rows, meta) with LRU refresh + hit/miss counting,
        materializing nothing."""
        ent = self.get_planar(key)
        return None if ent is None else (ent[1], ent[2], ent[3])

    def entry_info(self, key: Any):
        """(w, n_rows, meta) without LRU/counter side effects."""
        with self._lock:
            ent = self._entries.get(key)
        return None if ent is None else (ent[1], ent[2], ent[3])

    def resident_meta(self, key: Any):
        """The entry's caller meta, or None — the policy probe shape."""
        info = self.entry_info(key)
        return None if info is None else info[2]

    def gather_rows(self, key: Any, r0: int, r1: int):
        """The resident's bit-rows [r0, r1) (a device-buffer slice
        here; the paged store gathers from its page table), or None.
        No LRU side effects — ``touch`` owns those."""
        with self._lock:
            ent = self._entries.get(key)
        if ent is None or r1 > ent[0].shape[0]:
            return None
        return ent[0][r0:r1]

    def apply(self, key: Any, mbits: np.ndarray, out_rows: int,
              out_key: Any = None):
        """Apply a bit-matrix to the resident planar rows (encode with a
        generator, reconstruct with an inverted signature matrix, scrub
        re-encode, ...).  Pure matmul; the result stays planar, stored
        under `out_key` when given.  Returns the planar device buffer, or
        None when `key` is not resident.  Routes through the batching
        queue when one is attached (cross-object coalescing)."""
        got = self.get_planar(key)
        if got is None:
            return None
        bits, w, _, _meta = got
        if np.dtype(bits.dtype) == np.uint32:
            # packed-bit resident: the matrix runs as a static XOR
            # schedule over the u32 plane words (compiled per matrix
            # behind the gf2 LRU — decode signatures included)
            mb = np.asarray(mbits, dtype=np.uint8)
            if self.queue is not None:
                out = self.queue.submit_packedbit_planes(
                    mb, bits, w, out_rows).result()
            else:
                from ceph_tpu.ops.gf2 import gf2_xor_packed

                out = gf2_xor_packed(mb, bits)
        elif self.queue is not None:
            out = self.queue.submit_planar(
                np.asarray(mbits), bits, w, out_rows).result()
        else:
            import jax.numpy as jnp

            from ceph_tpu.ops.gf2 import gf2_matmul

            out = gf2_matmul(jnp.asarray(np.asarray(mbits)), bits)
        if out_key is not None:
            self.put_planar(out_key, out, w=w, n_rows=out_rows)
        return out

    def drop(self, key: Any, force: bool = False) -> bool:
        """Remove `key` if resident; True when an entry was actually
        dropped.  Dropping an absent key is a supported no-op (the tier
        agent races the LRU here: either side may have evicted first,
        and the loser must count a no-op, not error).  ``force`` is the
        paged store's dirty-override knob — a no-op here, where nothing
        is ever dirty — accepted so callers can speak one surface."""
        with self._lock:
            dropped = key in self._entries
            if dropped:
                del self._entries[key]
                self.resident_bytes -= self._bytes.pop(key)
                self._trim.pop(key, None)
            self._memo_discard(key)
            self.perf.set("resident_bytes", self.resident_bytes)
            self.perf.set("entries", len(self._entries))
        return dropped

    def peek(self, key: Any):
        """(bits, w, n_rows, meta) or None WITHOUT touching LRU order or
        the hit/miss counters — policy probes (the tier promotion gate
        asking "already resident at this version?") must not make an
        entry look recently used or pollute the hit ratio."""
        with self._lock:
            return self._entries.get(key)

    def entries_snapshot(self) -> List[Tuple[Any, int]]:
        """(key, planar nbytes) pairs in LRU order, oldest first — the
        tier agent's eviction-candidate input.  A point-in-time copy:
        the agent ranks against it and tolerates entries that vanish
        before its drop lands (drop() reports the no-op)."""
        with self._lock:
            return [(k, self._bytes[k]) for k in self._entries]

    def _memo_discard(self, key: Any) -> None:
        """Drop a key's memo and its byte accounting.  Caller holds the
        store lock."""
        got = self._memo.pop(key, None)
        if got is not None:
            self.memo_bytes -= len(got[1])

    def memo_get(self, key: Any, version: Any):
        """The exit-boundary memo for `key` at `version`, or None.  Only
        valid while the entry is RESIDENT (callers validate residency
        via get_planar first); the memo is version-tagged so a re-put at
        a newer version can never serve yesterday's bytes."""
        with self._lock:
            if key not in self._entries:
                return None
            got = self._memo.get(key)
        if got is None or got[0] != version:
            return None
        return got[1]

    def memo_put(self, key: Any, version: Any, value: Any) -> None:
        """Record the packed host result of this resident at `version`
        (one entry per key, latest version wins): subsequent resident
        hits skip the device pack entirely — the 'pack once per
        resident lifetime' contract made true under repeated reads.
        Ignored when the entry is not resident (a drop/evict raced the
        pack: the memo must not outlive the entry), and when the memo
        pool is at its budget (capacity_bytes: host RAM stays the same
        order as the HBM budget, so the operator's total footprint is
        bounded by ~2x capacity — a refused memo only costs a re-pack
        on the next read, never correctness)."""
        nbytes = len(value)
        with self._lock:
            if key not in self._entries:
                return
            self._memo_discard(key)
            if self.memo_bytes + nbytes > self.capacity_bytes:
                self.perf.set("memo_bytes", self.memo_bytes)
                return
            self._memo[key] = (version, value)
            self.memo_bytes += nbytes
            self.perf.set("memo_bytes", self.memo_bytes)

    def __contains__(self, key: Any) -> bool:
        with self._lock:
            return key in self._entries

    def stats(self) -> Dict[str, int]:
        return {"resident_bytes": self.resident_bytes,
                "memo_bytes": self.memo_bytes,
                "entries": len(self._entries), "admits": self.admits,
                "hits": self.hits, "misses": self.misses,
                "evictions": self.evictions}
