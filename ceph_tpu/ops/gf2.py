"""Bit-plane GF(2) matmul — the one TPU kernel behind every codec.

A GF(2^w) linear code is a GF(2) linear map on bit-planes, so the parity
computation the reference dispatches per-stripe to CPU SIMD
(jerasure_matrix_encode / jerasure_schedule_encode, reference
src/erasure-code/jerasure/ErasureCodeJerasure.cc:105-138) becomes ONE batched
MXU matmul here:

    out_bits[R, B] = (M_bits[R, C] @ data_bits[C, B]) & 1

with int8 0/1 operands (int8 matmul maps natively onto the MXU) and the
matrix as an *operand* — so the same compiled kernel serves encode (generator
bit-matrix), decode (inverted signature matrix), and recovery, exactly the
"one kernel" shape the north star asks for.

Two data layouts feed it (see ceph_tpu/ec/codecs.py):
  * byte layout  (reed_sol codes): bit-row j*w+x = bit x of chunk j's bytes;
  * packet layout (cauchy/liberation): bit-row j*w+l = packet l of chunk j,
    further unpacked bit-columns-within-bytes to reach the MXU.

The pure-XLA path below is correct everywhere (CPU tests included); the
Pallas kernel (ceph_tpu/ops/pallas_gf2.py) fuses unpack+matmul+pack in VMEM
to avoid materializing the 8x-expanded bit arrays in HBM.

BIT-PLANAR RESIDENCY (measured, v5e, k=8 m=3, 8 MiB batches, 256 encodes
per timed dispatch, dispatch RTT subtracted; round-4/5 records, since
deleted — ROADMAP's table keeps their numbers):

    packed-resident (unpack+matmul+pack per dispatch) .... 48.6 GB/s
    bit-planar resident (matmul only per dispatch) ....... 76.3 GB/s
    planar input, packed output .......................... 47.1 GB/s

(Those three used a full jnp.sum anti-DCE consumer; with the cheaper
MXU-matvec consumer the bench records ~55 packed vs ~93 planar — same
~1.6-1.7x conclusion, slightly higher absolutes.)

Two pack-acceleration alternatives were tried and REFUTED (same rig):
  * MXU pack (plane-major matrix rows so the output reshapes to
    [8, M*B] and a pow2-weight dot packs it): 8.7 GB/s vs 49 — the
    plane-major relayout plus a contraction dim of 8 starve the MXU
    and the int32 plane materialization adds HBM traffic.
  * uint8 shift-accumulate pack (narrower lanes than the int32 plane
    sum): 48.5 vs 49 — XLA already narrows the existing pack.
Planar residency (skip the output pack entirely) remains the only
measured pack win.

Keeping shards bit-planar in HBM across the pipeline — pack/unpack paid
once at the host/wire boundary — is worth ~1.57x.  The middle row
pinpoints WHERE: unpack fuses into the matmul almost for free, while the
output PACK (8 int32 plane-shifts + adds per byte) is the dominant VPU
stage; eliminating it is the entire win.

ADOPTED (round 4): residency is now the production path —
PlanarShardStore + BatchingQueue.submit_planar
(ceph_tpu/parallel/service.py), ecutil.planar_encode_async/planar_rows/
planar_object_bytes, and the OSD write/read/repair integration.  bench.py's
headline is the resident pipeline (unpack once on entry, matmul per op,
pack once on exit, both boundaries in the timed window): 83.9 GB/s vs
52.8 packed-per-op on the same run (k=8 m=3, 16x1MiB stripe batches).

The 8x HBM footprint DOES bite at large batches: a round-4 sweep of the
resident pipeline found 64-stripe batches HBM-bound (4->89.5, 8->90.9,
16->93.7, 32->89.9, 64->84.5 GB/s), so the batch default is 16 stripes
(2 MiB of columns; BatchingQueue.max_pending_bytes=16 MiB matches).

Pallas RE-TESTED under planar residency (round 4, v5e): the matmul-only
kernel (pallas_gf2_matmul) reaches 24.7 GB/s vs XLA's 83.4 on the same
resident loop — with pack/unpack gone the op is HBM-streaming-bound and
XLA's pipelined fori_loop beats the per-call pallas grid by ~3.4x.  The
kernel stays opt-in (CEPH_TPU_PALLAS=1); verdict recorded per VERDICT
r03 #9.

ROOFLINE OF THE INT8-PLANE LAYOUT (round 5, measured v5e, k=8 m=3 w=8,
16 MiB batches, RTT-subtracted):

    empirical HBM streaming bandwidth (chained adds) ...... 761 GB/s
                                            (spec ~819; 93% achieved)
    HBM bytes moved per DATA byte, int8-plane matmul loop:
      read data planes    8     (k*w int8 rows / k bytes)
      write parity planes 3     (m*w int8 rows / k bytes) — when the
                                parity planes persist (residency);
                                0 when the consumer fuses them in VMEM
      => traffic 8–11 B/byte, roofline band 761/11..761/8
                                          = 69.2 .. 95.1 GB/s data
    measured int8-plane matmul loop ....................... 86.9 GB/s

86.9 sits INSIDE the band — 91% of the fused-parity bound, 126% of the
written-parity bound — i.e. the int8-plane layout is saturated; no
constant-factor tuning of this layout buys another 2x.  (The r4
headline's 76.3 used the heavier full-sum consumer; same conclusion.)

PACKED-BIT PLANES EXPERIMENT (the traffic-cutting layout, r4 verdict
ask; 1 bit/bit => 1.375 HBM B/byte, roofline 553 GB/s):
  * matrix-as-OPERAND mask-AND-XOR over u32 words: 92.6 GB/s — only
    1.07x.  The dense formulation does k*w AND+XOR per output row
    regardless of matrix density (48 byte-ops per data byte): VPU-bound
    at almost exactly the int8-MXU rate.  REFUTED as an operand-matrix
    kernel.
  * STATIC XOR SCHEDULE (matrix baked at trace time, XLA prunes zero
    terms; 465 XOR terms at the Vandermonde density of 0.30 vs 1536
    dense): **126.2 GB/s, 1.45x over int8-planes, byte-exact** vs the
    oracle.  Still VPU/schedule-bound (23% of the packed roofline), so
    a schedule-CSE pass (jerasure "smart scheduling" role) has more
    headroom.
ADOPTED (round 6): the packed-bit static-XOR-schedule lane IS the
production lane for w=8 byte-layout codes.  Packed-bit residents (u32
words) run end to end — BatchingQueue grew packedbit/packedbit_resident/
packedbit_planes lanes mirroring the int8 packed/resident/planar trio,
PlanarShardStore holds u32 residents (at 1/8th the int8-plane HBM
footprint, so the same budget holds 8x the objects), and ecutil's
encode/decode/resident plans plus the tpu plugin's _apply/_apply_rows
seams route through the schedule cache.  Decode and recovery ride it
too: per-decode-signature schedules compile behind the same LRU (the
ErasureCodeIsaTableCache design at compile scope) — the signature set
an OSD sees converges in a handful of erasure patterns, exactly the
access pattern that cache was built for.  The int8-plane lanes remain
as the w=16/w=4 path and the CEPH_TPU_PACKEDBIT=0 fallback: they serve
every matrix without recompilation and the MXU does their reduction
for free.

SCHEDULE-CSE EXPERIMENT (jerasure "smart scheduling" role) — ADOPTED:
xor_schedule_program's greedy pairwise pass factors the term pair
co-occurring in the most output rows into a shared temp, repeatedly.
Measured on the k=8 m=3 w=8 Vandermonde bit-matrix: 441 XOR ops naive
-> 230 with CSE (82 temps; -48%).  CPU wall time is IDENTICAL (12.0 vs
12.1 ms on the 2 MiB-column batch): XLA fuses the whole schedule into
one traffic-bound loop, so ALU count is invisible there — which is the
point, the r5 measurement put the TPU lane at 23% of its roofline,
VPU-ISSUE-bound, precisely where halving issued ops pays.  Default ON
(CEPH_TPU_XOR_CSE=0 reverts); bench.py measures BOTH arms every run
(ec_encode_packedbit_cse_GBps / ec_encode_packedbit_nocse_GBps) so the
on-TPU verdict is re-recorded each round rather than frozen here.
Risk noted: temps lengthen dependency chains; if a future TPU run
shows nocse > cse, flip the env default and this paragraph.

ROOFLINE RECONCILIATION (why r5 printed roofline_fraction_hi 1.13 —
a physical impossibility): the r5 bench measured the HBM-bandwidth
denominator (chained-adds loop) MINUTES before the headline matmul
loop, on a shared chip; the bw probe caught a bad window (668 GB/s
vs the 761 measured on the same rig in a clean window) while the
headline loop caught a good one, so 94.8 / (668/8) = 1.13.  The r6 bench measures bw IMMEDIATELY before
and after the headline loop (same run window) and takes the best of
the two (timeit's min discipline, same as every other section), with
one extra re-measure if the fraction still exceeds 1.0 — the
denominator now shares the numerator's congestion conditions.  With
the packed-bit lane as headline the margin is wide anyway: traffic is
1 HBM byte per data byte when the parity planes are consumed fused
(1.375 when they persist), so the roofline band is bw/1.375..bw and
the measured 126.2 GB/s sits at ~23% of it — fraction well under 1.0.

OBSERVABILITY — the `gf2_sched` counter set (COUNTER SCHEMA: name ->
meaning -> kind), owned by this module because the schedule LRU is
process-global; daemons that engage the device tier add it to their
PerfCountersCollection so `perf dump` / the mgr prometheus exporter
carry it:

    hit            u64         compiled-schedule LRU hits
    miss           u64         LRU misses (a compile follows)
    evict          u64         entries dropped at capacity
    compile        u64         schedules compiled (program build + trace)
    compile_s      longrunavg  seconds per schedule compile
    xor_ops_naive  u64         pre-CSE XOR op count, summed over compiles
    xor_ops_final  u64         post-CSE (as-configured) XOR op count
    entries        u64         live LRU entries (gauge)

xor_ops_final / xor_ops_naive is the realized CSE saving; compile_s
times the Python program build + greedy CSE (the XLA trace happens
lazily at first call).  `perf reset` (admin socket) zeroes the set so
bench warmup/timed windows can isolate measurement intervals.
"""

from __future__ import annotations

import functools
import os
import threading
import time
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from ceph_tpu.common.perf_counters import PerfCountersBuilder

# Schedule-cache observability: the `gf2_sched` counter set (schema in
# the module docstring's OBSERVABILITY section).
SCHED_PERF = (
    PerfCountersBuilder("gf2_sched")
    .add_u64_counter("hit", "compiled-schedule LRU hits")
    .add_u64_counter("miss", "compiled-schedule LRU misses")
    .add_u64_counter("evict", "compiled schedules evicted at capacity")
    .add_u64_counter("compile", "schedules compiled")
    .add_time_avg("compile_s", "schedule program build seconds per matrix")
    .add_u64_counter("xor_ops_naive",
                     "XOR ops before CSE, summed over compiled matrices")
    .add_u64_counter("xor_ops_final",
                     "XOR ops after the configured CSE pass")
    .add_u64("entries", "live compiled schedules (gauge)")
    .create_perf_counters())


def pallas_enabled() -> bool:
    """Whether dispatchers should route w=8 byte-layout ops to the Pallas
    kernel.  Off by default — measured conclusion (v5e, k=8 m=3, 8 MiB
    batches, 512 encodes per timed dispatch so dispatch RTT amortizes out):

      old kernel (stack/reshape bit-plane unpack) .... 13 GB/s
      tuned kernel (repeat + iota-shift unpack,
        TILE_B 8192 -> 32768) ........................ ~40 GB/s
      XLA fused unpack+matmul+pack ................... ~52 GB/s

    The tuning round found the old kernel's cost was the [k,8,B] ->
    [k*8,B] sublane-interleave relayout, not the matmul; replacing it
    with elementwise repeat+shift tripled the kernel.  The remaining
    ~1.3x gap is not HBM (both paths sit far below the bandwidth
    roofline at ~1.4 bytes moved per data byte): the [m*8, k*8] x
    [k*8, B] product leaves the 128x128 MXU ~90% idle, so the op is
    VPU-bound on pack/unpack — exactly the stage XLA fuses across
    surrounding ops while Pallas pays per-kernel boundaries.  XLA stays
    the production path; set CEPH_TPU_PALLAS=1 to opt in when re-tuning
    (a different generation or a wider m*k could flip the verdict)."""
    return os.environ.get("CEPH_TPU_PALLAS", "") == "1"


def bucket_columns(n: int, lo: int = 1024) -> int:
    """Round a column count up to a power of two (>= lo) — the shared
    batching policy bounding XLA recompilation across object sizes."""
    b = lo
    while b < n:
        b <<= 1
    return b


def unpack_bits_bytes(data: jnp.ndarray, w: int) -> jnp.ndarray:
    """[n, B] uint8 byte chunks -> [n*w, B] int8 bit-planes (byte layout).

    For w=8 bit-row n*8+x is bit x of every byte.  For w=16 symbols are
    little-endian byte pairs: row n*16+x is bit x of each uint16.  For w=4
    each byte holds two symbols (lo nibble then hi nibble as consecutive
    columns), matching the packed-nibble region semantics of the CPU
    oracle (GF._mul_row w=4)."""
    n, B = data.shape
    if w == 16:
        pairs = data.reshape(n, B // 2, 2)
        planes = [((pairs[:, :, x // 8] >> (x % 8)) & 1) for x in range(16)]
        bits = jnp.stack(planes, axis=1)  # [n, 16, B//2]
        return bits.reshape(n * 16, B // 2).astype(jnp.int8)
    if w == 4:
        shifts = jnp.arange(4, dtype=jnp.uint8)
        lo = (data[:, None, :] >> shifts[None, :, None]) & 1  # [n, 4, B]
        hi = (data[:, None, :] >> (shifts + 4)[None, :, None]) & 1
        bits = jnp.stack([lo, hi], axis=-1)  # [n, 4, B, 2]
        return bits.reshape(n * 4, B * 2).astype(jnp.int8)
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = (data[:, None, :] >> shifts[None, :, None]) & 1  # [n, 8, B]
    return bits.reshape(n * 8, B).astype(jnp.int8)


def pack_bits_bytes(bits: jnp.ndarray, w: int, out_rows: int) -> jnp.ndarray:
    """Inverse of unpack_bits_bytes: [out_rows*w, Bcols] -> [out_rows, B]."""
    if w == 16:
        Bc = bits.shape[1]
        planes = bits.reshape(out_rows, 16, Bc).astype(jnp.int32)
        lo = jnp.zeros((out_rows, Bc), jnp.int32)
        hi = jnp.zeros((out_rows, Bc), jnp.int32)
        for x in range(8):
            lo = lo + (planes[:, x] << x)
            hi = hi + (planes[:, x + 8] << x)
        out = jnp.stack([lo, hi], axis=-1).reshape(out_rows, Bc * 2)
        return out.astype(jnp.uint8)
    if w == 4:
        Bc2 = bits.shape[1]  # B*2 nibble columns
        planes = bits.reshape(out_rows, 4, Bc2 // 2, 2).astype(jnp.int32)
        shifts = jnp.arange(4, dtype=jnp.int32)
        lo = jnp.sum(planes[..., 0] << shifts[None, :, None], axis=1)
        hi = jnp.sum(planes[..., 1] << shifts[None, :, None], axis=1)
        return (lo | (hi << 4)).astype(jnp.uint8)
    Bc = bits.shape[1]
    planes = bits.reshape(out_rows, 8, Bc).astype(jnp.int32)
    shifts = jnp.arange(8, dtype=jnp.int32)
    out = jnp.sum(planes << shifts[None, :, None], axis=1)
    return out.astype(jnp.uint8)


# -- host-boundary converters for planar residency ---------------------------
#
# The EC service keeps shards BIT-PLANAR in HBM across encode -> decode ->
# recovery (the measured ~1.6x win in the writeup above): these two jitted
# entry points are the ONLY places bytes cross between packed host layout
# and planar device layout.  Everything between them is gf2_matmul.


@functools.partial(jax.jit, static_argnames=("w",))
def to_planar(data: jnp.ndarray, w: int = 8) -> jnp.ndarray:
    """Packed [rows, B] uint8 chunks -> planar [rows*w, Bcols] int8 —
    paid once when bytes ENTER the device tier."""
    return unpack_bits_bytes(data, w)


@functools.partial(jax.jit, static_argnames=("w", "out_rows"))
def from_planar(bits: jnp.ndarray, w: int, out_rows: int) -> jnp.ndarray:
    """Planar [out_rows*w, Bcols] int8 -> packed [out_rows, B] uint8 —
    paid once when bytes LEAVE for the wire/store."""
    return pack_bits_bytes(bits, w, out_rows)


@functools.partial(jax.jit, static_argnames=("w", "out_rows"))
def gf2_encode_resident(mbits: jnp.ndarray, data: jnp.ndarray, w: int,
                        out_rows: int):
    """One fused device call for the residency write path: unpack the
    packed [n, B] batch once, matmul for parity, pack the parity for
    persistence — and ALSO return the full planar rows (data ‖ parity)
    so they stay HBM-resident for later decode/recovery/scrub.
    Returns (packed_parity [out_rows, B], all_bits [(n+out_rows)*w, Bc])."""
    bits = unpack_bits_bytes(data, w)
    pbits = gf2_matmul(mbits, bits)
    packed = pack_bits_bytes(pbits, w, out_rows)
    return packed, jnp.concatenate([bits, pbits], axis=0)


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def gf2_matmul(mbits: jnp.ndarray, bits: jnp.ndarray, use_pallas: bool = False) -> jnp.ndarray:
    """(M @ bits) & 1 with int8 operands, int32 MXU accumulation."""
    if use_pallas:
        from ceph_tpu.ops.pallas_gf2 import pallas_gf2_matmul

        return pallas_gf2_matmul(mbits, bits)
    acc = jax.lax.dot_general(
        mbits.astype(jnp.int8),
        bits.astype(jnp.int8),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    return (acc & 1).astype(jnp.int8)


# -- packed-bit static-schedule XOR: THE PRODUCTION LANE (measured 1.45x
#    over int8 planes; see the writeup's packed-bit experiment and the
#    lane-promotion note) ----------------------------------------------------
#
# The resident EC pipeline keeps shards as u32-word bit-planes (1 bit/bit,
# 1 HBM byte per data byte — 8x denser than the int8-plane layout) and
# applies GF(2) matrices as STATIC XOR SCHEDULES: the matrix is baked at
# trace time, XLA prunes every zero term, and one compiled schedule per
# (matrix, cse) pair lives behind the LRU below — the reference isa
# plugin's ErasureCodeIsaTableCache design (ErasureCodeIsaTableCache.cc)
# lifted from decode-matrix scope to XLA-compile scope, covering encode
# (fixed pool generator) AND per-decode-signature matrices alike.

_XOR_SCHEDULE_CAPACITY = 64
_XOR_SCHEDULES: "OrderedDict" = OrderedDict()
_XOR_LOCK = threading.Lock()

# `perf reset` must not leave the entries GAUGE lying at 0 while the LRU
# still holds compiled schedules: resync re-reads the live size (under
# the cache lock, same as _sched_cache_put's gauge write)


def _sched_resync() -> None:
    with _XOR_LOCK:
        SCHED_PERF.set("entries", len(_XOR_SCHEDULES))


SCHED_PERF.resync = _sched_resync


def packedbit_enabled() -> bool:
    """Whether the packed-bit static-XOR-schedule lane is the production
    lane for w=8 byte-layout dispatch (service lanes, ecutil plans, the
    tpu plugin's seams).  Default ON — the measured 1.45x; set
    CEPH_TPU_PACKEDBIT=0 to pin the int8-plane lanes (the proven
    fallback layout that serves every matrix without recompilation)."""
    return os.environ.get("CEPH_TPU_PACKEDBIT", "1") != "0"


def xor_cse_enabled() -> bool:
    """Whether XOR schedules run the common-subexpression pass (the
    jerasure "smart scheduling" role; see the CSE writeup above).
    Default ON; CEPH_TPU_XOR_CSE=0 pins the naive per-row schedules."""
    return os.environ.get("CEPH_TPU_XOR_CSE", "1") != "0"


def xor_schedule_program(bitmatrix: np.ndarray, cse: "bool | None" = None):
    """Compile a [R, C] GF(2) bit-matrix into a straight-line XOR program:
    returns (ops, outs, n_xors) where `ops` is a list of (a, b) pairs —
    op i computes temp C+i = term_a ^ term_b — and `outs[r]` is the term
    list (inputs 0..C-1, temps C+...) XORed together for output row r.
    n_xors counts total XOR instructions (the schedule-cost metric).

    With cse=True the greedy pairwise pass factors the pair of terms
    co-occurring in the most rows into a shared temp, repeatedly — the
    jerasure "smart scheduling" role, one level up: jerasure schedules
    per-operation SIMD XOR regions, this schedules the whole matrix as a
    DAG that XLA then fuses.  Deterministic (ties break to the smallest
    pair), so the compiled-schedule cache key stays stable."""
    if cse is None:
        cse = xor_cse_enabled()
    bm = np.asarray(bitmatrix, dtype=np.uint8)
    R, C = bm.shape
    sets = [set(np.nonzero(bm[r])[0].tolist()) for r in range(R)]
    naive = sum(max(0, len(s) - 1) for s in sets)
    ops: list = []
    if cse and naive <= 4096:  # pathological profiles skip the greedy pass
        # Incremental greedy factoring: the pair histogram is built ONCE
        # and updated only for the rows each factoring touches (a full
        # rebuild per iteration is O(R*t^2) Python on the dispatch path —
        # seconds at k=20 m=6).  A lazy-deletion heap orders candidates
        # by (count desc, a asc, b asc), the SAME deterministic tie-break
        # as the max() it replaces, so compiled programs (and the
        # schedule-cache keys derived from them) are bit-identical.
        import heapq

        counts: dict = {}
        occ: dict = {}  # term -> set of row indices containing it
        for r, s in enumerate(sets):
            elems = sorted(s)
            for x in elems:
                occ.setdefault(x, set()).add(r)
            for i in range(len(elems)):
                for j in range(i + 1, len(elems)):
                    p = (elems[i], elems[j])
                    counts[p] = counts.get(p, 0) + 1
        heap = [(-c, a, b) for (a, b), c in counts.items() if c >= 2]
        heapq.heapify(heap)

        def bump(p, d):
            c = counts.get(p, 0) + d
            if c > 0:
                counts[p] = c
                if c >= 2:
                    heapq.heappush(heap, (-c, p[0], p[1]))
            else:
                counts.pop(p, None)

        while heap:
            negc, a, b = heapq.heappop(heap)
            if counts.get((a, b), 0) != -negc:
                continue  # stale entry: the pair's count has changed
            t = C + len(ops)
            ops.append((a, b))
            for r in sorted(occ[a] & occ[b]):
                s = sets[r]
                for x in s:
                    if x != a and x != b:
                        bump((min(a, x), max(a, x)), -1)
                        bump((min(b, x), max(b, x)), -1)
                bump((a, b), -1)
                s.discard(a)
                s.discard(b)
                occ[a].discard(r)
                occ[b].discard(r)
                for x in s:  # t > every existing term
                    bump((x, t), +1)
                s.add(t)
                occ.setdefault(t, set()).add(r)
    outs = [sorted(s) for s in sets]
    n_xors = len(ops) + sum(max(0, len(o) - 1) for o in outs)
    return ops, outs, n_xors


def _schedule_apply(ops, outs, n_inputs, planes):
    """Trace the XOR program over the first `n_inputs` rows of `planes`
    (any dtype — u32 bit-plane words, or raw uint8 packet rows: XOR is
    XOR).  `n_inputs` MUST be the program's column count: temps are
    numbered from there, so an operand with extra rows (e.g. a full
    data‖parity resident under a [R, k*w] matrix) must not shift them."""
    vals = [planes[i] for i in range(n_inputs)]
    for a, b in ops:
        vals.append(vals[a] ^ vals[b])
    rows = []
    for terms in outs:
        if not terms:
            rows.append(jnp.zeros_like(planes[0]))
            continue
        acc = vals[terms[0]]
        for t in terms[1:]:
            acc = acc ^ vals[t]
        rows.append(acc)
    return jnp.stack(rows)


def _sched_cache_get(key):
    with _XOR_LOCK:
        fn = _XOR_SCHEDULES.get(key)
        if fn is not None:
            _XOR_SCHEDULES.move_to_end(key)  # true LRU: hits refresh
    SCHED_PERF.inc("hit" if fn is not None else "miss")
    return fn


def _sched_cache_put(key, fn):
    evicted = 0
    with _XOR_LOCK:
        _XOR_SCHEDULES[key] = fn
        _XOR_SCHEDULES.move_to_end(key)
        while len(_XOR_SCHEDULES) > _XOR_SCHEDULE_CAPACITY:
            _XOR_SCHEDULES.popitem(last=False)
            evicted += 1
        # gauge write stays under the cache lock: an unlocked set could
        # overwrite a newer value with a stale snapshot (lock order is
        # cache -> perf, same as the resync lambda)
        SCHED_PERF.set("entries", len(_XOR_SCHEDULES))
    if evicted:
        SCHED_PERF.inc("evict", evicted)


def _compiled_schedule(tag: str, bitmatrix, build, cse=None):
    """LRU-cached compiled function per (tag, matrix bytes, cse): the
    ErasureCodeIsaTableCache design at compile scope.  Thread-safe —
    the batching worker, OSD event loops, and tests all land here."""
    bm = np.asarray(bitmatrix, dtype=np.uint8)
    if cse is None:
        cse = xor_cse_enabled()
    key = (tag, bm.shape, bm.tobytes(), cse)
    fn = _sched_cache_get(key)
    if fn is None:
        with SCHED_PERF.time_avg("compile_s"):
            ops, outs, n_xors = xor_schedule_program(bm, cse=cse)
            fn = build(ops, outs)
        SCHED_PERF.inc("compile")
        # naive cost is row popcounts alone (no temps): the CSE saving
        # is visible as xor_ops_final / xor_ops_naive across compiles
        naive = int(np.maximum(
            (bm != 0).sum(axis=1).astype(np.int64) - 1, 0).sum())
        SCHED_PERF.inc("xor_ops_naive", naive)
        SCHED_PERF.inc("xor_ops_final", int(n_xors))
        _sched_cache_put(key, fn)
    return fn


def gf2_xor_packed(bitmatrix: np.ndarray, planes, cse=None) -> "jnp.ndarray":
    """[R, C] GF(2) bit-matrix applied to C rows by a static XOR schedule
    (matrix baked at trace time; XLA prunes zero terms — 465 XOR terms
    instead of 1536 dense AND+XORs at the k=8 m=3 Vandermonde density,
    fewer still under CSE).  Rows are dtype-agnostic: [C, Bw] uint32
    packed bit-planes (bit b of word i = bit column 32i+b) for byte-layout
    codes, or raw uint8 packet rows for the bitmatrix codec family.  One
    compiled schedule per (matrix, cse), LRU-cached — encode generators
    AND per-decode-signature matrices both ride it."""

    return xor_packed_fn(bitmatrix, cse=cse)(planes)


def xor_packed_fn(bitmatrix: np.ndarray, cse=None):
    """The compiled (LRU-cached) jitted schedule behind gf2_xor_packed —
    split out so an AOT compile can lower it at a shape without data."""
    C = np.asarray(bitmatrix).shape[1]

    def build(ops, outs):
        @jax.jit
        def _apply(p):
            return _schedule_apply(ops, outs, C, p)

        return _apply

    return _compiled_schedule("xor", bitmatrix, build, cse=cse)


# -- device-side packed-bit converters (the jitted host-boundary pair for
#    u32 residents, mirroring to_planar/from_planar for int8 planes) ---------


def _bits_to_words(bits: jnp.ndarray) -> jnp.ndarray:
    """[R, B] int8 0/1 bit-planes -> [R, B//32] uint32 words (bit b of
    word i = bit column 32i+b).  B % 32 == 0."""
    R, B = bits.shape
    v = bits.astype(jnp.uint32).reshape(R, B // 32, 32)
    return jnp.sum(v << jnp.arange(32, dtype=jnp.uint32)[None, None, :],
                   axis=-1, dtype=jnp.uint32)


def _words_to_bits(words: jnp.ndarray) -> jnp.ndarray:
    """[R, Wc] uint32 -> [R, Wc*32] int8 bit-planes."""
    R, Wc = words.shape
    b = (words[:, :, None]
         >> jnp.arange(32, dtype=jnp.uint32)[None, None, :]) & jnp.uint32(1)
    return b.reshape(R, Wc * 32).astype(jnp.int8)


@jax.jit
def to_packedbit(data: jnp.ndarray) -> jnp.ndarray:
    """Packed [n, B] uint8 chunks (w=8 byte layout, B % 32 == 0) ->
    [n*8, B//32] uint32 plane words — the ENTRY boundary for packed-bit
    residency, paid once per object."""
    with jax.named_scope("to_packedbit"):
        return _bits_to_words(unpack_bits_bytes(data, 8))


@functools.partial(jax.jit, static_argnames=("out_rows",))
def from_packedbit(planes: jnp.ndarray, out_rows: int) -> jnp.ndarray:
    """[out_rows*8, Wc] uint32 plane words -> packed [out_rows, Wc*32]
    uint8 — the EXIT boundary, paid once when bytes leave for the
    wire/store."""
    with jax.named_scope("from_packedbit"):
        return pack_bits_bytes(_words_to_bits(planes), 8, out_rows)


def gf2_apply_packedbit(bitmatrix: np.ndarray, data) -> "jnp.ndarray":
    """[out_rows*8, n*8] GF(2) bit-matrix applied to packed [n, B] uint8
    chunks (w=8 byte layout, B % 32 == 0) through the packed-bit lane:
    ONE fused jitted call — on-device bit unpack, u32 word pack, static
    XOR schedule, byte pack — compiled per matrix behind the LRU.  The
    one-shot (non-resident) shape of the production lane; byte-compatible
    with gf2_apply_bytes(bm, data, 8, out_rows)."""
    return apply_packedbit_fn(bitmatrix)(data)


def apply_packedbit_fn(bitmatrix: np.ndarray):
    """The compiled (LRU-cached) jitted call behind gf2_apply_packedbit."""
    out_rows = np.asarray(bitmatrix).shape[0] // 8
    C = np.asarray(bitmatrix).shape[1]

    def build(ops, outs):
        @jax.jit
        def _run(x):
            # the three stages as named scopes: each op's name in the TPU
            # plane's "XLA Ops" line carries its scope (PERF.md section 3)
            with jax.named_scope("to_packedbit"):
                planes = _bits_to_words(unpack_bits_bytes(x, 8))
            with jax.named_scope("xor_apply"):
                pouts = _schedule_apply(ops, outs, C, planes)
            with jax.named_scope("from_packedbit"):
                return pack_bits_bytes(_words_to_bits(pouts), 8, out_rows)

        return _run

    return _compiled_schedule("apply", bitmatrix, build)


def gf2_encode_packedbit_resident(bitmatrix: np.ndarray, data):
    """The packed-bit residency write path (mirrors gf2_encode_resident):
    packed [n, B] uint8 rows in, ONE fused device call — unpack, u32
    word pack, XOR schedule, parity byte pack — returning
    (packed_parity [out_rows, B], all_planes [(n+out_rows)*8, B//32]
    uint32): parity bytes for persistence, u32 planes (data ‖ parity) to
    stay HBM-resident at 1/8th the int8-plane footprint."""
    return encode_packedbit_resident_fn(bitmatrix)(data)


def encode_packedbit_resident_fn(bitmatrix: np.ndarray):
    """The compiled (LRU-cached) jitted call behind
    gf2_encode_packedbit_resident."""
    out_rows = np.asarray(bitmatrix).shape[0] // 8
    C = np.asarray(bitmatrix).shape[1]

    def build(ops, outs):
        @jax.jit
        def _run(x):
            with jax.named_scope("to_packedbit"):
                planes = _bits_to_words(unpack_bits_bytes(x, 8))
            with jax.named_scope("xor_apply"):
                pouts = _schedule_apply(ops, outs, C, planes)
            with jax.named_scope("from_packedbit"):
                packed = pack_bits_bytes(_words_to_bits(pouts), 8,
                                         out_rows)
            return packed, jnp.concatenate([planes, pouts], axis=0)

        return _run

    return _compiled_schedule("resident", bitmatrix, build)


def gf2_apply_packetrows(bitmatrix: np.ndarray, data, w: int,
                         packetsize: int) -> "jnp.ndarray":
    """[out_rows*w, n*w] GF(2) bit-matrix applied to [n, B] chunks in the
    PACKET layout (cauchy/liberation family; B a whole number of
    w*packetsize-byte blocks): ONE fused jitted call, the packed-bit
    lane with another pair of layout stages.  A packet IS a bit-row
    already, so no bit is moved: the rows-in stage is the block transpose
    [n, nb, w, p] -> [n*w, nb*p], the same static XOR schedule runs over
    those rows, and the rows-out stage transposes back to [out_rows, B].
    `data` is uint8, or the same bytes viewed as uint32 words when
    packetsize is a multiple of 4 (XOR is bitwise, so the word view
    changes nothing but the element count of a packet).  Compatible,
    byte for byte, with jerasure_bitmatrix_encode."""
    return apply_packetrows_fn(bitmatrix, w, packetsize)(data)


def apply_packetrows_fn(bitmatrix: np.ndarray, w: int, packetsize: int):
    """The compiled (LRU-cached) jitted call behind gf2_apply_packetrows:
    one per (matrix, w, packetsize)."""
    out_rows = np.asarray(bitmatrix).shape[0] // w
    C = np.asarray(bitmatrix).shape[1]

    def build(ops, outs):
        @jax.jit
        def _run(x):
            n, cols = x.shape
            p = packetsize // x.dtype.itemsize  # elements in a packet
            nb = cols // (w * p)
            with jax.named_scope("to_packetrows"):
                rows = (x.reshape(n, nb, w, p).transpose(0, 2, 1, 3)
                        .reshape(n * w, nb * p))
            with jax.named_scope("xor_apply"):
                pouts = _schedule_apply(ops, outs, C, rows)
            with jax.named_scope("from_packetrows"):
                return (pouts.reshape(out_rows, w, nb, p)
                        .transpose(0, 2, 1, 3).reshape(out_rows, cols))

        return _run

    return _compiled_schedule(f"packetrows.{w}.{packetsize}", bitmatrix,
                              build)


def pack_bitplanes_u32(data: np.ndarray, w: int = 8) -> np.ndarray:
    """Host-side packed-bit layout: [n, B] uint8 chunks -> [n*w, ceil(B/32)]
    uint32 words (bit b of word i = bit-plane value at column 32i+b) —
    the 1-byte-per-data-byte layout the packed XOR kernel consumes.
    Arbitrary B: columns pad out with zero bits to whole u32 words
    (unpack_bitplanes_u32 trims them back via its B argument).  Byte
    layout, w=8 production shape (w<8 packs the low w bit-planes)."""
    n, B = data.shape
    if B % 32:
        data = np.pad(data, ((0, 0), (0, 32 - B % 32)))
    bits = ((data[:, None, :] >> np.arange(w, dtype=np.uint8)[None, :, None])
            & 1).reshape(n * w, data.shape[1])
    return np.packbits(bits, axis=1, bitorder="little").view(np.uint32)


def unpack_bitplanes_u32(planes: np.ndarray, w: int, out_rows: int,
                         B: int) -> np.ndarray:
    """Inverse of pack_bitplanes_u32 for the parity rows: [out_rows*w, Wc]
    u32 words -> [out_rows, B] uint8, trimming any pad columns."""
    bits = np.unpackbits(np.ascontiguousarray(planes).view(np.uint8), axis=1,
                         bitorder="little")[:, :B]
    out = np.zeros((out_rows, B), np.uint8)
    for x in range(w):
        out |= (bits[x::w].astype(np.uint8) << x)
    return out


@functools.partial(jax.jit, static_argnames=("w", "out_rows", "use_pallas"))
def gf2_apply_bytes(
    mbits: jnp.ndarray,
    data: jnp.ndarray,
    w: int,
    out_rows: int,
    use_pallas: bool = False,
) -> jnp.ndarray:
    """Byte layout: apply a [out_rows*w, n*w] bit-matrix to [n, B] chunks."""
    if use_pallas and w == 8:
        from ceph_tpu.ops.pallas_gf2 import pallas_apply_bytes_w8

        return pallas_apply_bytes_w8(mbits, data, out_rows)
    bits = unpack_bits_bytes(data, w)
    out = gf2_matmul(mbits, bits)
    return pack_bits_bytes(out, w, out_rows)


@functools.partial(jax.jit, static_argnames=("w", "packetsize", "out_rows", "use_pallas"))
def gf2_apply_packets(
    mbits: jnp.ndarray,
    data: jnp.ndarray,
    w: int,
    packetsize: int,
    out_rows: int,
    use_pallas: bool = False,
) -> jnp.ndarray:
    """Packet layout: [n, chunk] chunks, chunk = nb*w*packetsize, apply
    [out_rows*w, n*w] bit-matrix over packet rows."""
    n, chunk = data.shape
    wp = w * packetsize
    nb = chunk // wp
    rows = data.reshape(n, nb, w, packetsize).transpose(0, 2, 1, 3).reshape(n * w, nb * packetsize)
    # bytes -> bit columns so the combine is an MXU matmul
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = ((rows[:, :, None] >> shifts[None, None, :]) & 1).reshape(n * w, nb * packetsize * 8)
    out = gf2_matmul(mbits, bits, use_pallas=use_pallas)
    out = out.reshape(out_rows * w, nb * packetsize, 8).astype(jnp.int32)
    packed = jnp.sum(out << jnp.arange(8, dtype=jnp.int32)[None, None, :], axis=-1).astype(jnp.uint8)
    return (
        packed.reshape(out_rows, w, nb, packetsize)
        .transpose(0, 2, 1, 3)
        .reshape(out_rows, chunk)
    )
