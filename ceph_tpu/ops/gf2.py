"""Bit-plane GF(2) matmul — the one TPU kernel behind every codec.

A GF(2^w) linear code is a GF(2) linear map on bit-planes, so the parity
computation the reference dispatches per-stripe to CPU SIMD
(jerasure_matrix_encode / jerasure_schedule_encode, reference
src/erasure-code/jerasure/ErasureCodeJerasure.cc:105-138) becomes ONE batched
device call here:

    out_bits[R, B] = (M_bits[R, C] @ data_bits[C, B]) & 1

Two data layouts feed it (see ceph_tpu/ec/codecs.py):
  * byte layout  (reed_sol codes): bit-row j*w+x = bit x of chunk j's bytes;
  * packet layout (cauchy/liberation): bit-row j*w+l = packet l of chunk j.

THE SIX LANES (ceph_tpu/parallel/service.py runs them, one row each in
its LANES table; rados/ecutil.lane_for picks one per codec).  Every
device program is plain XLA, correct on the CPU backend too.

  int8-plane pair — the matrix is an OPERAND of an int8 MXU matmul, so one
  compiled program serves every matrix; w=4/8/16.  Serves the w=16/w=4
  pools and is the CEPH_TPU_PACKEDBIT=0 reference layout.
    packed    gf2_apply_bytes: unpack -> matmul -> pack, bytes in and out.
    resident  gf2_encode_resident: the same, and the int8 planes
              (data ‖ parity) come back to stay in HBM.
    Measured v5e, k=8 m=3, 16 MiB batches (round 5): 86.9 GB/s against an
    HBM band of 69..95 GB/s (8-11 HBM bytes moved per data byte, 761 GB/s
    streaming) — the layout is saturated; the output PACK (8 int32
    plane-shifts + adds per byte) is its dominant VPU stage.

  packed-bit trio — the matrix is baked at trace time as a STATIC XOR
  SCHEDULE over rows (XLA prunes the zero terms: 465 XOR terms at the
  k=8 m=3 Vandermonde density against 1536 dense), one compiled program
  per matrix behind the LRU below (the ErasureCodeIsaTableCache design at
  compile scope: encode generators and per-decode-signature matrices
  alike).  The production lanes: 126.2 GB/s on the same rig, 1.45x the
  int8 planes, byte-exact.  A matrix-as-operand mask-AND-XOR over the
  same words measured 92.6 GB/s and was refuted.
    packedbit           apply_packedbit_fn: w=8 byte layout; bytes ->
                        u32 plane words (1 HBM byte per data byte) ->
                        schedule -> bytes.
    packedbit_resident  encode_packedbit_resident_fn: the same, and the
                        u32 planes come back for the resident store
                        (rados/pagestore.py) at 1/8th the int8 footprint.
    packetrows          apply_packetrows_fn: packet layout; a packet IS a
                        bit-row, so the layout stages are block
                        transposes and no bit moves.

  sub-chunk lane — a coupled-layer (CLAY) code's encode: no matrix of the
  caller's but the code's geometry, and three stages on the packed-bit
  plane words instead of one product.
    subchunk            encode_subchunk_fn: bytes -> u32 plane words ->
                        uncouple (a transpose, a 16x16 schedule, a mask)
                        -> the scalar code's schedule over every plane ->
                        couple -> bytes; one program per (geometry,
                        chunk), no residents.

SCHEDULE CSE (jerasure "smart scheduling" role): xor_schedule_program's
greedy pairwise pass factors the term pair co-occurring in the most
output rows into a shared temp, repeatedly.  k=8 m=3 w=8 Vandermonde:
441 XOR ops naive -> 230 (82 temps).  Every compiled schedule runs it;
the `cse=` argument stays so tests/test_gf.py can hold it to the naive
schedule.

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
    xor_ops_final  u64         post-CSE XOR op count
    entries        u64         live LRU entries (gauge)

xor_ops_final / xor_ops_naive is the realized CSE saving; compile_s
times the Python program build + greedy CSE (the XLA trace happens
lazily at first call).  `perf reset` (admin socket) zeroes the set so
warmup/timed windows can isolate measurement intervals.
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


# -- host-boundary converters for int8-plane residents (w=16/w=4 pools) -----
#
# These two jitted entry points are the only places bytes cross between
# the packed host layout and the int8-plane device layout.


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


@jax.jit
def gf2_matmul(mbits: jnp.ndarray, bits: jnp.ndarray) -> jnp.ndarray:
    """(M @ bits) & 1 with int8 operands, int32 MXU accumulation."""
    acc = jax.lax.dot_general(
        mbits.astype(jnp.int8),
        bits.astype(jnp.int8),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    return (acc & 1).astype(jnp.int8)


# -- packed-bit static-schedule XOR: the production lanes (measured 1.45x
#    over int8 planes; module docstring) -------------------------------------
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


def xor_schedule_program(bitmatrix: np.ndarray, cse: bool = True):
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


def _compiled_schedule(tag: str, bitmatrix, build, cse: bool = True):
    """LRU-cached compiled function per (tag, matrix bytes, cse): the
    ErasureCodeIsaTableCache design at compile scope.  Thread-safe —
    the batching worker, OSD event loops, and tests all land here."""
    bm = np.asarray(bitmatrix, dtype=np.uint8)
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


def gf2_xor_packed(bitmatrix: np.ndarray, planes,
                   cse: bool = True) -> "jnp.ndarray":
    """[R, C] GF(2) bit-matrix applied to C rows by a static XOR schedule
    (matrix baked at trace time; XLA prunes zero terms — 465 XOR terms
    instead of 1536 dense AND+XORs at the k=8 m=3 Vandermonde density,
    fewer still under CSE).  Rows are dtype-agnostic: [C, Bw] uint32
    packed bit-planes (bit b of word i = bit column 32i+b) for byte-layout
    codes, or raw uint8 packet rows for the bitmatrix codec family.  One
    compiled schedule per (matrix, cse), LRU-cached — encode generators
    AND per-decode-signature matrices both ride it."""

    return xor_packed_fn(bitmatrix, cse=cse)(planes)


def xor_packed_fn(bitmatrix: np.ndarray, cse: bool = True):
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


def subchunk_pair_bits(pair: np.ndarray) -> np.ndarray:
    """The [16, 16] GF(2) bit-matrix of a coupled-layer code's 2x2 pairwise
    transform `pair` (GF(2^8); index 0 of a pair is its node with the
    larger x) over the inputs (a node's own 8 bit-rows, its partner's 8):
    rows 0-7 are the node's result where it is the pair's index 0, rows
    8-15 where it is index 1.  A node on the diagonal takes neither."""
    from ceph_tpu.ec.matrices import matrix_to_bitmatrix

    bm = matrix_to_bitmatrix(np.asarray(pair, dtype=np.int64), 8)
    return np.vstack([bm[:8], np.hstack([bm[8:, 8:], bm[8:, :8]])]) \
        .astype(np.uint8)


def _pair_transform(row, y: int, q: int, ops, outs):
    """One grid row of a coupled-layer code through its pairwise
    transform.  `row` is [q(x), 8(bit), q(z_0) .. q(z_t-1), M] u32 plane
    words; node x's partner in plane z is node z_y in the plane whose
    y-th digit is x, i.e. `row` with its x axis and its z_y axis swapped,
    so the whole row meets its partners in one transpose and the 16x16
    schedule (subchunk_pair_bits) runs over all of it; which half of the
    result a node takes is a static mask over (x, z_y)."""
    partner = jnp.swapaxes(row, 0, 2 + y)
    both = _schedule_apply(
        ops, outs, 16,
        [row[:, b] for b in range(8)] + [partner[:, b] for b in range(8)])
    x = np.arange(q).reshape((q,) + (1,) * (row.ndim - 2))
    zy = np.arange(q).reshape((q,) + (1,) * (row.ndim - 3 - y))
    out = [jnp.where(x > zy, both[b], jnp.where(x < zy, both[8 + b],
                                               row[:, b]))
           for b in range(8)]
    return jnp.stack(out, axis=1)


def gf2_encode_subchunk(q: int, t: int, chunk: int, pair, pair_inv,
                        generator, data) -> "jnp.ndarray":
    """A coupled-layer (CLAY) code's encode of [k, n_stripes*chunk] uint8
    rows to [m, n_stripes*chunk] parity rows, ONE fused jitted call
    (encode_subchunk_fn)."""
    return encode_subchunk_fn(q, t, chunk, pair, pair_inv, generator)(data)


def encode_subchunk_fn(q: int, t: int, chunk: int, pair, pair_inv,
                       generator):
    """The compiled (LRU-cached) jitted call behind gf2_encode_subchunk,
    one per (geometry, chunk); XLA compiles it once a staged width.

    The code (Vajha et al., FAST 2018; ec/plugins/clay.py has the CPU
    form): nodes on a q x t grid, node (x, y) = chunk y*q + x, a chunk
    cut into q^t sub-chunks ("planes") whose index has the base-q digits
    z_0 .. z_t-1.  With nu = 0 and k, m whole rows of the grid, the data
    are rows y < k/q and the parities the rows above, every plane has the
    same intersection score and the layered decode that IS the encode is
    one round of three stages, all on u32 plane words between one
    to_packedbit and one from_packedbit:

      clay_uncouple  every data node's U: itself on the diagonal
                     (z_y = x), else the 2x2 transform `pair` of it and
                     its partner (_pair_transform: a transpose, a 16x16
                     XOR schedule, a mask);
      xor_apply      the scalar MDS code `generator` [m, k] over all
                     planes of all stripes at once: the packed-bit lane's
                     static XOR schedule over [k*8, words];
      clay_couple    the parity rows' U back to C: the same transpose and
                     `pair_inv`.

    A constant of GF(2^8) is an 8x8 bit-matrix on bit-rows, so no stage
    leaves the plane words.  The sub-chunk axis is brought in front of
    the stripes first ([.., stripe, plane, word] -> [.., plane, stripe x
    word]): the transposes then move whole rows of stripes and the minor
    dimension stays as wide as the batch."""
    from ceph_tpu.ec.matrices import matrix_to_bitmatrix

    generator = np.asarray(generator, dtype=np.int64)
    m, k = generator.shape
    if k % q or m % q or k + m != q * t:
        raise ValueError(f"k={k} m={m} are not whole rows of a {q}x{t} grid")
    n_planes = q ** t
    if chunk % (n_planes * 32):
        raise ValueError(f"a chunk of {chunk} B is not {n_planes} "
                         "sub-chunks of whole u32 bit-plane words")
    scw = chunk // n_planes // 32  # plane words a sub-chunk

    def planes_first(p, rows):
        # [rows*8, S*planes*scw] -> [rows/q, q, 8, q.., S*scw]
        s = p.shape[1] // (n_planes * scw)
        p = p.reshape(rows * 8, s, n_planes, scw).transpose(0, 2, 1, 3)
        return p.reshape((rows // q, q, 8) + (q,) * t + (s * scw,))

    def build(ops, outs):
        unc = xor_schedule_program(subchunk_pair_bits(pair))[:2]
        cpl = xor_schedule_program(subchunk_pair_bits(pair_inv))[:2]

        @jax.jit
        def _clay_encode(x):
            with jax.named_scope("to_packedbit"):
                planes = _bits_to_words(unpack_bits_bytes(x, 8))
            with jax.named_scope("clay_uncouple"):
                c = planes_first(planes, k)
                u = jnp.stack([_pair_transform(c[y], y, q, *unc)
                               for y in range(k // q)])
            with jax.named_scope("xor_apply"):
                pu = _schedule_apply(ops, outs, k * 8,
                                     u.reshape(k * 8, -1))
            with jax.named_scope("clay_couple"):
                pu = pu.reshape((m // q, q, 8) + u.shape[3:])
                pc = jnp.stack([_pair_transform(pu[j], k // q + j, q, *cpl)
                                for j in range(m // q)])
                s = pc.shape[-1] // scw
                pc = (pc.reshape(m * 8, n_planes, s, scw)
                      .transpose(0, 2, 1, 3).reshape(m * 8, -1))
            with jax.named_scope("from_packedbit"):
                return pack_bits_bytes(_words_to_bits(pc), 8, m)

        return _clay_encode

    tag = (f"subchunk.{q}.{t}.{chunk}."
           + np.asarray(pair, dtype=np.uint8).tobytes().hex()
           + np.asarray(pair_inv, dtype=np.uint8).tobytes().hex())
    return _compiled_schedule(tag, matrix_to_bitmatrix(generator, 8), build)


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


@functools.partial(jax.jit, static_argnames=("w", "out_rows"))
def gf2_apply_bytes(
    mbits: jnp.ndarray,
    data: jnp.ndarray,
    w: int,
    out_rows: int,
) -> jnp.ndarray:
    """Byte layout: apply a [out_rows*w, n*w] bit-matrix to [n, B] chunks."""
    bits = unpack_bits_bytes(data, w)
    out = gf2_matmul(mbits, bits)
    return pack_bits_bytes(out, w, out_rows)
