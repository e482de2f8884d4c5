#!/usr/bin/env python
"""Headline benchmark: plugin=tpu Reed-Solomon encode throughput.

Reproduces the reference's measurement protocol
(ceph_erasure_code_benchmark, reference
src/test/erasure-code/ceph_erasure_code_benchmark.cc: encode of --size
bytes per iteration, throughput = bytes/seconds) for the north-star config
k=8, m=3, 1 MiB stripes (BASELINE.md), with the TPU twist the design is
built around: many stripes are batched into ONE device dispatch
(SURVEY.md §5.7).

Methodology — device-resident measurement. The reference's tool times
encode() over buffers in host RAM because its codec runs on the CPU next
to them; the analogous measurement for a TPU codec is encode over stripes
resident in HBM, which is exactly what the stripe-batching service sees in
steady state (pinned staging buffers + async DMA overlap transfer with
compute; the queue keeps the device fed). The HEADLINE is the
PACKED-BIT resident pipeline the service actually runs (u32-word
bit-planes + static XOR schedules — the production lane promoted in
round 6, ceph_tpu/ops/gf2.py lane-promotion writeup): stripes pack to
u32 plane words ONCE on entry, every resident op is a per-matrix
compiled XOR schedule (encode generator or per-decode-signature
inverse), and bytes pack ONCE on exit — both boundaries inside the
timed window, amortized over the resident ops. The int8-plane resident
pipeline (r4/r5 headline) and the per-op pack/unpack numbers are kept
as continuity fields. To time the kernel rather than the dispatch, the
bench (a) loops the encode N times inside ONE jitted call, varying the
input each iteration so XLA cannot hoist it, and folding every parity byte
into a checksum so nothing is dead-code-eliminated, and (b) subtracts one
measured dispatch round-trip from the wall time. Correctness is gated
first: the device parity must be byte-identical to the CPU GF(2^8) oracle.

This file measures the chip: with no TPU backend it exits nonzero. Its
cluster arms (--daemon-path etc.) are CPU children by design
(JAX_PLATFORMS=cpu); they do not need, and must not take, the chip the
parent holds.

Baseline: the reference publishes no absolute GB/s (BASELINE.md), so
vs_baseline is measured locally against the native C++ jerasure-equivalent
codec (same matrices, byte-identical output) on this host — the same A/B
the reference's bench.sh performs between its plugins.

Prints ONE JSON line:
  {"metric": ..., "value": GB/s, "unit": "GB/s", "vs_baseline": ratio}
"""

import json
import os
import sys
import time

import numpy as np

K, M, W = 8, 3, 8
STRIPE = 1 << 20  # 1 MiB object per stripe, reference default --size
# 16 stripes/dispatch (2 MiB of columns): the measured HBM sweet spot for
# the planar pipeline on v5e (r4 sweep: 4->89.5, 8->90.9, 16->93.7,
# 32->89.9, 64->84.5 GB/s — the 8x planar expansion makes bigger batches
# HBM-bound); the BatchingQueue default budget matches.
N_STRIPES = int(os.environ.get("BENCH_STRIPES", "16"))  # batched per dispatch
CPU_ITERS = int(os.environ.get("BENCH_CPU_ITERS", "2"))


def sched_perf_snapshot() -> dict:
    """Compact `gf2_sched` counter snapshot for the BENCH record: the
    schedule-cache hit rate, compile cost, and realized CSE saving ride
    the perf trajectory files instead of living only in `perf dump`."""
    try:
        from ceph_tpu.ops.gf2 import SCHED_PERF

        d = SCHED_PERF.dump()
        lookups = d["hit"] + d["miss"]
        return {
            "hit_rate": round(d["hit"] / lookups, 3) if lookups else 0.0,
            "compiles": d["compile"],
            "compile_s_avg": round(SCHED_PERF.avg("compile_s"), 5),
            "evictions": d["evict"],
            "xor_ops_naive": d["xor_ops_naive"],
            "xor_ops_final": d["xor_ops_final"],
        }
    except Exception as e:  # never sink the bench run, but never silently
        print(f"bench: gf2_sched snapshot failed: {e!r}", file=sys.stderr)
        return {}


def queue_perf_snapshot(q) -> dict:
    """Compact `ec_tpu` counter snapshot of a BatchingQueue: per-lane
    submit/byte counts (non-zero lanes only), latency averages, and
    flush causes — the breakdown the BENCH record carries each run."""
    try:
        from ceph_tpu.parallel.service import LANES

        d = q.perf.dump()
        return {
            "submits": d["submit"], "dispatches": d["dispatch"],
            "bytes": d["bytes"],
            "queue_wait_s_avg": round(q.perf.avg("queue_wait"), 6),
            "dispatch_dev_s_avg": round(q.perf.avg("dispatch_dev"), 6),
            "flush_causes": {c: d[f"flush_{c}"]
                             for c in ("bytes", "delay", "forced")},
            "lane_submits": {ln: d[f"submit_{ln}"] for ln in LANES
                             if d[f"submit_{ln}"]},
            "lane_bytes": {ln: d[f"bytes_{ln}"] for ln in LANES
                           if d[f"bytes_{ln}"]},
        }
    except Exception as e:  # a counter rename must not erase the record
        print(f"bench: ec_tpu snapshot failed: {e!r}", file=sys.stderr)
        return {}


def main() -> int:
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import jax

    from ceph_tpu.utils.jaxdev import enable_compile_cache, probe_backend

    backend = probe_backend()
    if backend != "tpu":
        print(f"bench.py measures the chip and jax's backend here is "
              f"{backend!r}: no result (tests run on JAX_PLATFORMS=cpu, "
              f"measurements do not)", file=sys.stderr)
        return 2
    enable_compile_cache()

    import jax.numpy as jnp
    from jax import lax

    from ceph_tpu.ec.gf import gf
    from ceph_tpu.ec.matrices import matrix_to_bitmatrix, vandermonde_coding_matrix
    from ceph_tpu.ops.gf2 import (gf2_apply_bytes, gf2_matmul, pack_bits_bytes,
                                  pallas_enabled, unpack_bits_bytes)

    mat = vandermonde_coding_matrix(K, M, W)
    bm = matrix_to_bitmatrix(mat, W)

    chunk = STRIPE // K  # 128 KiB per data chunk
    B = chunk * N_STRIPES  # batched columns per dispatch
    rng = np.random.default_rng(0)
    data = rng.integers(0, 256, size=(K, B), dtype=np.uint8)
    d = jax.device_put(data)
    bmd = jax.device_put(bm.astype(np.int8))

    # the production dispatch path (same routing the plugin/service use)
    use_pallas = pallas_enabled()

    def encode(m, x):
        return gf2_apply_bytes(m, x, W, M, use_pallas=use_pallas)

    # correctness gate before any timing: byte-identical vs the oracle
    parity = np.asarray(encode(bmd, d)[:, :chunk])
    want = gf(W).matmul(mat, data[:, :chunk])
    if not np.array_equal(parity, want):
        print(json.dumps({"metric": "encode_correctness", "value": 0, "unit": "bool",
                          "vs_baseline": 0}))
        return 1

    # per-dispatch round-trip floor
    trivial = jax.jit(lambda: jnp.int32(1))
    int(trivial())
    rtts = []
    for _ in range(9):
        t0 = time.perf_counter()
        int(trivial())
        rtts.append(time.perf_counter() - t0)
    # the FLOOR is the honest subtraction: each timed section is ONE
    # dispatch, and we remove only its unavoidable RPC latency.  The
    # validity guard below (wall > 2x floor) rejects measurements where
    # jitter, not compute, set the wall time.
    rtt = min(rtts)

    # enough iterations that compute time >> the dispatch floor (the
    # validity guard in measure_net wants wall > 2x floor)
    iters = int(os.environ.get("BENCH_ITERS", "1024"))

    ones_b = jnp.ones((B,), jnp.int8)

    def fold(out, carry):
        # anti-DCE consumer: a full-width MXU matvec touches every output
        # column at negligible VPU cost (a plain jnp.sum over the output
        # is VPU work of the same order as the pack stage and would bias
        # the packed-vs-planar comparison; a slice would let XLA narrow
        # the matmul itself)
        colsum = jax.lax.dot_general(
            out.astype(jnp.int8), ones_b, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        return carry ^ jnp.sum(colsum)

    @jax.jit
    def loop(m, x):
        def body(i, carry):
            out = encode(m, x ^ i.astype(jnp.uint8))
            return fold(out, carry)
        return lax.fori_loop(0, iters, body, jnp.int32(0))

    def timed(fn, *a) -> float:
        """Best-of-2 wall time (timeit's min discipline)."""
        best = None
        for _ in range(2):
            t0 = time.perf_counter()
            int(fn(*a))
            w = time.perf_counter() - t0
            best = w if best is None else min(best, w)
        return best

    def fresh_rtt() -> float:
        samples = []
        for _ in range(5):
            t0 = time.perf_counter()
            int(trivial())
            samples.append(time.perf_counter() - t0)
        return min(samples)

    def measure_net(fn, *a):
        """Net compute time with the dispatch floor subtracted,
        self-retrying: a wall within 2x the floor (where jitter rather
        than compute sets the time) re-measures both the section and the
        floor.  None when every attempt stayed rtt-dominated."""
        floor = rtt
        for _ in range(3):
            wall = timed(fn, *a)
            if wall > floor * 2.0:
                return wall - floor
            floor = fresh_rtt()
        return None

    int(loop(bmd, d))  # warm / compile
    dt = measure_net(loop, bmd, d)
    if dt is None:
        # compute is lost in RPC jitter (tiny BENCH_STRIPES/ITERS overrides):
        # report a measurement failure rather than an absurd GB/s
        print(json.dumps({"metric": "measurement_invalid_rtt_dominated",
                          "value": 0, "unit": "GB/s", "vs_baseline": 0}))
        return 1
    total_bytes = iters * K * B  # data bytes encoded (reference counts in_size)
    packed_gbps = total_bytes / dt / 1e9

    # int8-plane resident pipeline (the r4/r5 HEADLINE, kept as a
    # continuity field now that the packed-bit lane is production —
    # ops/gf2.py lane-promotion writeup): stripes pay the unpack
    # boundary ONCE on entry, every EC op while resident is a pure
    # GF(2) matmul on HBM bit-planes, and bytes pack ONCE when they
    # leave.  The timed window includes both boundaries, amortized over
    # the `iters` resident ops.
    @jax.jit
    def resident_pipeline(m, x):
        bits = unpack_bits_bytes(x, W)  # entry boundary, paid once

        def body(i, carry):
            out = gf2_matmul(m, bits ^ (i & 1).astype(jnp.int8))
            return fold(out, carry)

        acc = lax.fori_loop(0, iters - 1, body, jnp.int32(0))
        out = gf2_matmul(m, bits)
        packed = pack_bits_bytes(out, W, M)  # exit boundary, paid once
        return acc ^ jnp.sum(packed.astype(jnp.int32))

    # correctness gate for the planar path vs the CPU oracle
    planar_parity = np.asarray(pack_bits_bytes(
        gf2_matmul(bmd, unpack_bits_bytes(d, W)), W, M))[:, :chunk]
    if not np.array_equal(planar_parity, want):
        print(json.dumps({"metric": "planar_correctness", "value": 0,
                          "unit": "bool", "vs_baseline": 0}))
        return 1
    int(resident_pipeline(bmd, d))  # warm / compile
    res_wall = measure_net(resident_pipeline, bmd, d)
    if res_wall is None:
        print(json.dumps({"metric": "measurement_invalid_rtt_dominated",
                          "value": 0, "unit": "GB/s", "vs_baseline": 0}))
        return 1
    int8_resident_gbps = total_bytes / res_wall / 1e9

    # TPU DECODE: the other half of the headline metric ("encode+decode
    # GB/s", BASELINE.md; reference decode workload
    # ceph_erasure_code_benchmark.cc:202-316).  Per iteration a random
    # erasure signature (1..M chunks lost) picks a CPU-inverted decode
    # matrix (LRU-by-construction: the signature set is precomputed once,
    # as the ISA table cache would converge to); the device applies the
    # inverted bit-matrix to the K surviving chunks — the SAME kernel as
    # encode with a different operand, which is the whole design.
    import random as _random

    fgf = gf(W)
    full = np.vstack([np.eye(K, dtype=np.int64), mat])
    rng_sig = _random.Random(7)
    sigs = []
    all_ids = list(range(K + M))
    while len(sigs) < 8:
        nlost = rng_sig.randint(1, M)
        lost = tuple(sorted(rng_sig.sample(all_ids, nlost)))
        if lost in sigs:
            continue
        sigs.append(lost)
    # Per signature, the device reconstructs ONLY the erased chunks
    # (reference decode semantics; the codec path does the same): lost
    # DATA rows come from the inverted matrix, lost CODING rows compose
    # generator @ inverse on the CPU.  Signatures with fewer than M
    # losses pad by repeating a row so the fori_loop stays uniform —
    # a CONSERVATIVE overcount of the work.
    rec_bms = []
    for lost in sigs:
        chosen = [c for c in all_ids if c not in lost][:K]
        inv = fgf.invert_matrix(full[chosen])
        rows = []
        for c in lost:
            if c < K:
                rows.append(inv[c])
            else:
                rows.append(fgf.matmul(mat[c - K:c - K + 1],
                                       inv.astype(np.uint8))[0])
        while len(rows) < M:
            rows.append(rows[0])  # pad: uniform [M, K] per signature
        rec_bms.append(matrix_to_bitmatrix(
            np.stack(rows).astype(np.int64), W).astype(np.int8))
    inv_stack = jax.device_put(np.stack(rec_bms))  # [S, M*W, K*W]

    @jax.jit
    def encode_like_decode(mb, x):
        return gf2_apply_bytes(mb, x, W, M, use_pallas=use_pallas)

    @jax.jit
    def decode_loop(mstack, x):
        def body(i, carry):
            mb = jax.lax.dynamic_index_in_dim(
                mstack, i % mstack.shape[0], keepdims=False)
            out = gf2_apply_bytes(mb, x ^ i.astype(jnp.uint8), W, M,
                                  use_pallas=use_pallas)
            return fold(out, carry)
        return lax.fori_loop(0, iters, body, jnp.int32(0))

    # correctness gate through the SAME kernel configuration the timed
    # loop runs: reconstruct signature 0's erased chunks and compare
    # against the originals (data rows vs data, coding rows vs parity)
    surv0 = [c for c in all_ids if c not in sigs[0]][:K]
    enc_full = fgf.matmul(mat, data)
    chunks0 = np.vstack([data[c][None] if c < K
                         else enc_full[c - K][None] for c in surv0])
    dec0 = np.asarray(encode_like_decode(jnp.asarray(rec_bms[0]),
                                         jnp.asarray(chunks0)))
    want0 = np.vstack([
        (data[c][None] if c < K else enc_full[c - K][None])
        for c in sigs[0]])
    if not np.array_equal(dec0[:len(sigs[0])], want0):
        print(json.dumps({"metric": "decode_correctness", "value": 0,
                          "unit": "bool", "vs_baseline": 0}))
        return 1
    int(decode_loop(inv_stack, d))  # warm
    dec_wall = measure_net(decode_loop, inv_stack, d)
    if dec_wall is None:
        print(json.dumps({"metric": "measurement_invalid_rtt_dominated",
                          "value": 0, "unit": "GB/s", "vs_baseline": 0}))
        return 1
    dec_packed_gbps = (iters * K * B) / dec_wall / 1e9

    # planar-resident decode (production shape under residency): the
    # survivors were admitted as bit-planes at write time, each decode is
    # a matmul with a rotating inverted signature matrix, and the
    # reconstruction packs once when it leaves to the client.
    @jax.jit
    def planar_decode_loop(mstack, x):
        bits = unpack_bits_bytes(x, W)  # admission (write time), once

        def body(i, carry):
            mb = jax.lax.dynamic_index_in_dim(
                mstack, i % mstack.shape[0], keepdims=False)
            out = gf2_matmul(mb, bits ^ (i & 1).astype(jnp.int8))
            return fold(out, carry)

        acc = lax.fori_loop(0, iters - 1, body, jnp.int32(0))
        out = gf2_matmul(mstack[0], bits)
        packed = pack_bits_bytes(out, W, M)  # departure to the client
        return acc ^ jnp.sum(packed.astype(jnp.int32))

    int(planar_decode_loop(inv_stack, d))  # warm
    pdec_wall = measure_net(planar_decode_loop, inv_stack, d)
    if pdec_wall is None:
        print(json.dumps({"metric": "measurement_invalid_rtt_dominated",
                          "value": 0, "unit": "GB/s", "vs_baseline": 0}))
        return 1
    dec_int8_gbps = (iters * K * B) / pdec_wall / 1e9

    # BIT-PLANAR RESIDENCY: the steady-state rate when shards stay
    # bit-planar in HBM across the pipeline and pack/unpack is paid once
    # at the host boundary (ops/gf2.py writeup) — the matmul-only rate,
    # the ceiling a residency-aware EC service reaches.
    bits = jax.jit(lambda x: unpack_bits_bytes(x, W))(d)
    bits.block_until_ready()

    @jax.jit
    def planar_loop(m, xb):
        def body(i, carry):
            x = xb ^ (i & 1).astype(jnp.int8)  # vary input, stay 0/1
            out = gf2_matmul(m, x)
            return fold(out, carry)
        return lax.fori_loop(0, iters, body, jnp.int32(0))

    int(planar_loop(bmd, bits))  # warm
    planar_wall = measure_net(planar_loop, bmd, bits)
    if planar_wall is None:
        print(json.dumps({"metric": "measurement_invalid_rtt_dominated",
                          "value": 0, "unit": "GB/s", "vs_baseline": 0}))
        return 1
    planar_gbps = (iters * K * B) / planar_wall / 1e9

    # Pallas re-test under planar residency (VERDICT r03 #9): the fused
    # kernel lost to XLA when pack/unpack dominated; with residency the
    # op is a bare matmul, so measure the Pallas matmul kernel head to
    # head on the resident loop and record the verdict either way.
    pallas_planar_gbps = 0.0
    from ceph_tpu.ops.pallas_gf2 import TILE_B as TILE_CHECK
    from ceph_tpu.ops.pallas_gf2 import pallas_gf2_matmul

    @jax.jit
    def pallas_planar_loop(m, xb):
        def body(i, carry):
            out = pallas_gf2_matmul(m, xb ^ (i & 1).astype(jnp.int8))
            return fold(out, carry)
        return lax.fori_loop(0, iters, body, jnp.int32(0))

    # correctness gate: kernel output == XLA planar output
    pk = np.asarray(pallas_gf2_matmul(bmd, bits[:, :TILE_CHECK]))
    xk = np.asarray(gf2_matmul(bmd, bits[:, :TILE_CHECK]))
    if np.array_equal(pk, xk):
        int(pallas_planar_loop(bmd, bits))  # warm
        pw = measure_net(pallas_planar_loop, bmd, bits)
        if pw is not None:
            pallas_planar_gbps = (iters * K * B) / pw / 1e9
    del bits

    # HEADLINE — the PACKED-BIT resident pipeline (the production lane
    # promoted this round, ops/gf2.py lane-promotion writeup): stripes
    # pack to u32 plane words ONCE on entry, every resident op is a
    # static XOR schedule compiled per matrix behind the gf2 LRU —
    # encode runs the fixed pool generator, decode a rotating set of
    # per-signature inverted matrices (each its own compiled schedule,
    # the ErasureCodeIsaTableCache access pattern) — and bytes pack ONCE
    # on exit.  Both boundaries sit inside the timed window, amortized
    # over the resident ops, exactly like the int8 pipeline above.
    #
    # ROOFLINE RECONCILIATION (r5 printed roofline_fraction_hi 1.13;
    # ops/gf2.py writeup): the HBM-bandwidth denominator is measured
    # IMMEDIATELY before and after the headline loops — the same run
    # window, sharing the numerator's congestion conditions — taking
    # the best probe (timeit's min discipline), with one extra
    # re-measure if the fraction still lands above 1.0.
    from ceph_tpu.ops.gf2 import (from_packedbit, gf2_apply_packedbit,
                                  gf2_xor_packed, pack_bitplanes_u32,
                                  to_packedbit, xor_schedule_program)

    # byte-exact gates through the SAME entry points the plugin/service
    # dispatch: encode (pool generator) AND decode (signature 0 inverse)
    pb_parity = np.asarray(gf2_apply_packedbit(bm, data))[:, :chunk]
    if not np.array_equal(pb_parity, want):
        print(json.dumps({"metric": "packedbit_encode_correctness",
                          "value": 0, "unit": "bool", "vs_baseline": 0}))
        return 1
    pb_dec = np.asarray(gf2_apply_packedbit(
        rec_bms[0].astype(np.uint8), chunks0))
    if not np.array_equal(pb_dec[:len(sigs[0])], want0):
        print(json.dumps({"metric": "packedbit_decode_correctness",
                          "value": 0, "unit": "bool", "vs_baseline": 0}))
        return 1

    bw_iters = 1024
    try:
        bw_x = jax.device_put(rng.integers(0, 255, (128 << 20,),
                                           dtype=np.uint8))

        @jax.jit
        def bw_loop(x):
            def body(i, y):
                return y + jnp.uint8(1)
            y = lax.fori_loop(0, bw_iters, body, x)
            return jnp.sum(y[::4097].astype(jnp.int32))

        int(bw_loop(bw_x))  # warm / compile

        def measure_bw() -> float:
            dt = measure_net(bw_loop, bw_x)
            return bw_iters * 2 * bw_x.size / dt / 1e9 if dt else 0.0
    except Exception:
        bw_x = None

        def measure_bw() -> float:
            # bandwidth probe unavailable: roofline fields report 0
            # rather than killing the headline measurement
            return 0.0

    bw_probes = [measure_bw()]  # denominator probe #1: before the loops

    @jax.jit
    def packedbit_pipeline(x):
        planes = to_packedbit(x)  # entry boundary, paid once

        def body(i, carry):
            out = gf2_xor_packed(bm, planes ^ i.astype(jnp.uint32))
            return carry ^ jnp.sum(out.astype(jnp.int32))

        acc = lax.fori_loop(0, iters - 1, body, jnp.int32(0))
        out = gf2_xor_packed(bm, planes)
        packed = from_packedbit(out, M)  # exit boundary, paid once
        return acc ^ jnp.sum(packed.astype(jnp.int32))

    int(packedbit_pipeline(d))  # warm / compile
    pb_wall = measure_net(packedbit_pipeline, d)
    if pb_wall is None:
        print(json.dumps({"metric": "measurement_invalid_rtt_dominated",
                          "value": 0, "unit": "GB/s", "vs_baseline": 0}))
        return 1
    gbps = total_bytes / pb_wall / 1e9

    # packed-bit resident DECODE: survivors were admitted as u32 planes
    # at write time; the loop rotates through the 8 precomputed erasure
    # signatures, each signature's inverted matrix running as its OWN
    # compiled schedule (unrolled segments — a static schedule cannot be
    # indexed dynamically, and per-signature compilation is precisely
    # what the LRU amortizes in production), reconstruction packing once
    # on exit to the client.
    sig_iters = max(1, iters // len(rec_bms))

    @jax.jit
    def packedbit_decode_pipeline(x):
        planes = to_packedbit(x)  # admission (write time), once
        acc = jnp.int32(0)
        for sig_bm in rec_bms:  # unrolled: one baked schedule per sig
            def body(i, carry, _bm=sig_bm):
                out = gf2_xor_packed(_bm, planes ^ i.astype(jnp.uint32))
                return carry ^ jnp.sum(out.astype(jnp.int32))

            acc = lax.fori_loop(0, sig_iters, body, acc)
        out = gf2_xor_packed(rec_bms[0], planes)
        packed = from_packedbit(out, M)  # departure to the client
        return acc ^ jnp.sum(packed.astype(jnp.int32))

    int(packedbit_decode_pipeline(d))  # warm / compile
    pbdec_wall = measure_net(packedbit_decode_pipeline, d)
    if pbdec_wall is None:
        print(json.dumps({"metric": "measurement_invalid_rtt_dominated",
                          "value": 0, "unit": "GB/s", "vs_baseline": 0}))
        return 1
    dec_gbps = (sig_iters * len(rec_bms) * K * B + K * B) / pbdec_wall / 1e9

    bw_probes.append(measure_bw())  # denominator probe #2: after
    hbm_bw_gbps = max(bw_probes)
    # packed-bit traffic: 1 HBM byte per data byte when parity planes
    # are consumed fused, 1.375 when they persist (ops/gf2.py writeup)
    hbm_remeasures = 0
    if hbm_bw_gbps and gbps / hbm_bw_gbps > 1.0:
        bw_probes.append(measure_bw())  # one congestion re-measure
        hbm_bw_gbps = max(bw_probes)
        hbm_remeasures = 1
    del bw_x

    # SCHEDULE-CSE A/B (jerasure "smart scheduling" role; writeup in
    # ops/gf2.py records the adopted-or-refuted verdict): the SAME
    # resident schedule loop with the CSE pass pinned on vs off, so the
    # on-TPU verdict is re-recorded every round.  Program sizes are
    # reported too — the op-count delta is the mechanism.
    _, _, xors_cse = xor_schedule_program(bm, cse=True)
    _, _, xors_nocse = xor_schedule_program(bm, cse=False)
    cse_arm_gbps = {"cse": 0.0, "nocse": 0.0}
    pb = jax.device_put(pack_bitplanes_u32(data, W))
    for arm, flag in (("cse", True), ("nocse", False)):
        @jax.jit
        def arm_loop(planes, _flag=flag):
            def body(i, carry):
                out = gf2_xor_packed(bm, planes ^ i.astype(jnp.uint32),
                                     cse=_flag)
                return carry ^ jnp.sum(out.astype(jnp.int32))
            return lax.fori_loop(0, iters, body, jnp.int32(0))

        int(arm_loop(pb))  # warm / compile
        adt = measure_net(arm_loop, pb)
        cse_arm_gbps[arm] = total_bytes / adt / 1e9 if adt else 0.0
    del pb
    packedbit_gbps = cse_arm_gbps["cse"]  # continuity field (r5 name)

    # CPU A/B baseline: the native C++ jerasure-equivalent codec (same
    # matrices, byte-identical output).  The default build vectorizes the
    # GF region kernel (GFNI affine or AVX2 pshufb split tables, cache-
    # tiled) so vs_baseline is an HONEST ratio against an isa-l-class
    # single-core encode, not a scalar strawman; the scalar nibble-table
    # rate is also measured (subprocess with CEPH_TPU_NO_SIMD=1) and
    # reported as vs_scalar for continuity with earlier rounds.
    # The baseline working set is FIXED at 64 MiB regardless of the
    # device batch parameter: the reference protocol streams fresh
    # buffers through RAM (1 MiB per iteration, total >> cache), so a
    # cache-resident one-shot encode would flatter the CPU number when
    # the device batch happens to be small.
    simd_kind = "numpy"
    cpu_B = (1 << 20) // K * 64  # 64 MiB baseline working set
    cpu_data = (data if B == cpu_B
                else rng.integers(0, 256, size=(K, cpu_B), dtype=np.uint8))

    def cpu_once() -> float:
        nonlocal simd_kind
        try:
            from ceph_tpu.native import bridge

            t0 = time.perf_counter()
            bridge.rs_encode("reed_sol_van", cpu_data, M)
            dt = time.perf_counter() - t0
            simd_kind = bridge.simd_kind()
            return dt
        except Exception:
            t0 = time.perf_counter()
            gf(W).matmul(mat, cpu_data)
            return time.perf_counter() - t0

    cpu_once()  # warm tables / build
    cpu_dt = min(cpu_once() for _ in range(CPU_ITERS))
    cpu_gbps = (K * cpu_B) / cpu_dt / 1e9

    # SOCKET baseline (the north star's own unit: "isa-l single-socket").
    # Threaded native encode, one core per column range.  This host
    # exposes os.cpu_count() cores; socket_threads records the actual
    # parallelism so the denominator is auditable.  modeled_socket is
    # per-core x os.cpu_count() — a LINEAR-scaling upper bound on THIS
    # host (real sockets scale sublinearly on this memory-bound kernel).
    # The old modeled_socket_8c field silently assumed 8 cores whatever
    # the host had (ISSUE 12 satellite); the record now derives the
    # multiplier from the real core count and LABELS the assumption.
    socket_gbps = 0.0
    socket_threads = 0
    try:
        from ceph_tpu.native import bridge as _bridge

        _bridge.rs_encode_mt("reed_sol_van", cpu_data, M)  # warm
        best = None
        for _ in range(CPU_ITERS):
            t0 = time.perf_counter()
            _, socket_threads = _bridge.rs_encode_mt("reed_sol_van",
                                                     cpu_data, M)
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        socket_gbps = (K * cpu_B) / best / 1e9
    except Exception:
        pass
    modeled_cores = os.cpu_count() or 1
    modeled_socket = cpu_gbps * modeled_cores

    def scalar_gbps() -> float:
        import subprocess

        code = (
            "import numpy as np, timeit;"
            "from ceph_tpu.native import bridge;"
            "d = np.random.default_rng(0).integers(0, 256, (%d, 1 << 20),"
            " dtype=np.uint8);"
            "bridge.rs_encode('reed_sol_van', d, %d);"
            "dt = min(timeit.repeat(lambda: bridge.rs_encode("
            "'reed_sol_van', d, %d), number=1, repeat=3));"
            "print(d.size / dt / 1e9)" % (K, M, M))
        try:
            out = subprocess.run(
                [sys.executable, "-c", code],
                env=dict(os.environ, CEPH_TPU_NO_SIMD="1"),
                capture_output=True, text=True, timeout=120, check=True)
            return float(out.stdout.strip().splitlines()[-1])
        except Exception:
            return 0.0

    scalar = scalar_gbps()

    # end-to-end host-memory path: bytes start in host RAM, parity lands
    # back in host RAM (what the batching queue amortizes): recorded so
    # the transfer cost is never invisible in the methodology.
    t0 = time.perf_counter()
    host_parity = np.asarray(encode(jax.device_put(bm.astype(np.int8)),
                                    jax.device_put(data)))
    e2e_dt = time.perf_counter() - t0
    e2e_gbps = (K * B) / e2e_dt / 1e9
    del host_parity

    # BATCHING QUEUE on the device: many concurrent stripe-sized submits
    # coalescing into few dispatches (the daemon data path's shape).
    # Records ops/dispatch + host-memory GB/s with the queue on.  The
    # queue worker
    # double-buffers rounds (VERDICT r03 #4): e2e_pipelined_GBps streams
    # 8 rounds back-to-back so round N+1's H2D staging overlaps round
    # N's fetch, vs the serial single-shot e2e number above;
    # overlapped_rounds records how many rounds actually pipelined.
    batch_ops_per_dispatch = 0.0
    batch_gbps = 0.0
    pipelined_gbps = 0.0
    overlapped = 0
    ec_tpu_perf = {}
    from concurrent.futures import ThreadPoolExecutor

    from ceph_tpu.parallel.service import BatchingQueue

    q = BatchingQueue(max_delay=0.01, use_pallas=use_pallas)
    bm8 = bm.astype(np.int8)
    n_ops = 64
    stripe_cols = chunk  # one 1 MiB object per op
    bufs = [rng.integers(0, 256, size=(K, stripe_cols), dtype=np.uint8)
            for _ in range(n_ops)]
    with ThreadPoolExecutor(max_workers=16) as pool:
        futs = list(pool.map(
            lambda b: q.submit(bm8, b, W, M), bufs))
    for f in futs:
        f.result(timeout=120)
    d0 = q.dispatches
    t0 = time.perf_counter()
    with ThreadPoolExecutor(max_workers=16) as pool:
        futs = list(pool.map(
            lambda b: q.submit(bm8, b, W, M), bufs))
    for f in futs:
        f.result(timeout=120)
    dt = time.perf_counter() - t0
    disp = q.dispatches - d0
    batch_ops_per_dispatch = n_ops / max(disp, 1)
    batch_gbps = (n_ops * K * stripe_cols) / dt / 1e9
    # pipelined stream: rounds submitted back-to-back from a pump
    # thread so a backlog stands and the worker overlaps rounds
    import threading

    rounds = 8
    stream = [rng.integers(0, 256, size=(K, B), dtype=np.uint8)
              for _ in range(rounds)]
    pf = []

    def pump():
        for s in stream:
            pf.append(q.submit(bm8, s, W, M))

    q.submit(bm8, stream[0], W, M).result(timeout=120)  # warm shape
    ov0 = q.overlapped_rounds
    t0 = time.perf_counter()
    th = threading.Thread(target=pump)
    th.start()
    th.join(timeout=300)
    for f in list(pf):
        f.result(timeout=300)
    dt = time.perf_counter() - t0
    pipelined_gbps = (rounds * K * B) / dt / 1e9
    overlapped = q.overlapped_rounds - ov0
    ec_tpu_perf = queue_perf_snapshot(q)
    q.close()

    # ON-HOST overlap benchmark (VERDICT r4 #3): the same serial vs
    # pipelined comparison in a CPU-backend child, so the double-buffer
    # mechanism is judged without the device's per-round dispatch
    # floor.  On host, overlap can only win where two engines run
    # concurrently (device DMA/compute vs host staging); a 1-core host
    # shares one engine for everything, so the honest expectation there
    # is ratio ~1.0 with overlap engaged, and >1 only on multi-core
    # hosts.
    got = _run_child_bench("--onhost-overlap")
    onhost_serial_gbps = got.get("serial_GBps", 0.0)
    onhost_pipelined_gbps = got.get("pipelined_GBps", 0.0)
    onhost_overlapped = got.get("overlapped_rounds", 0)

    # DAEMON-PATH throughput: rados put+get of a 64 MiB object through a
    # 6-OSD in-process cluster on the CPU backend (a CPU child: the
    # Python messenger tax, not the accelerator, is what this measures).
    got = _run_child_bench("--daemon-path", timeout=600,
                           parse_on_fail=True)
    daemon_put_mbps = got.get("put_MBps", 0.0)
    daemon_get_mbps = got.get("get_MBps", 0.0)
    daemon_wire_put_mbps = got.get("wire_put_MBps", 0.0)
    daemon_wire_get_mbps = got.get("wire_get_MBps", 0.0)
    daemon_wire_put_py_mbps = got.get("wire_put_MBps_python", 0.0)
    daemon_wire_get_py_mbps = got.get("wire_get_MBps_python", 0.0)
    daemon_wirepath_kind = got.get("wirepath_kind", "")
    daemon_local_put_mbps = got.get("local_put_MBps", 0.0)
    daemon_local_get_mbps = got.get("local_get_MBps", 0.0)
    daemon_wire_perf: dict = got.get("wire_perf", {})
    daemon_wire_plane: dict = got.get("wire_plane", {})
    daemon_objecter_perf: dict = got.get("objecter_perf", {})
    daemon_phase_pcts: dict = got.get("op_phase_percentiles", {})
    daemon_cluster_log: dict = got.get("cluster_log", {})
    daemon_fullness: dict = got.get("fullness", {})
    daemon_reactor_mode: str = str(got.get("reactor_mode") or "thread")
    daemon_arm_failed = bool(got.get("_failed"))

    # multi-lane scaling curve (1/2/4/8 lanes) on BOTH reactor modes
    # (thread + process): recorded every run so the lane plane's
    # scaling is a trajectory, not a one-off claim — 16 cluster
    # bring-ups, hence the longer leash
    lanes_sweep: dict = _run_child_bench(
        "--lanes-sweep", timeout=1500).get("lanes_sweep", {})

    # pure-messenger single-stream: native wirepath arm vs forced-python
    # arm in one child process/window (the ISSUE 12 acceptance ratio)
    msgr_stream: dict = _run_child_bench(
        "--msgr-stream", timeout=600).get("msgr_stream", {})

    # CACHE-TIER hot-read arm (CPU child with the planar store
    # forced on): resident-hit read MB/s vs the cold decode path on the
    # same run window + the aggregated `tier` perf snapshot
    got = _run_child_bench("--hot-read",
                           extra_env={"CEPH_TPU_FORCE_BATCH": "1"})
    tier_hot_mbps = got.get("tier_hot_read_MBps", 0.0)
    tier_cold_mbps = got.get("tier_cold_read_MBps", 0.0)
    tier_ratio = got.get("tier_hot_vs_cold", 0.0)
    tier_perf: dict = got.get("tier_perf", {})
    tier_pagestore: dict = got.get("tier_pagestore") or {}

    # SLAB-ARM e2e arm: the SAME put -> resident-read workload run once
    # per slab arm (CEPH_TPU_DEVICE_SLAB=1 child vs =0 child, same
    # BENCH window) — e2e_device_GBps vs e2e_host_GBps is the measured
    # cost/win of the jitted device-slab path on this host; on a CPU-
    # only host both ride the jax-cpu backend (call-structure parity,
    # honest numbers, no pretend-HBM)
    e2e_device: dict = _run_child_bench(
        "--e2e-device", extra_env={"CEPH_TPU_FORCE_BATCH": "1",
                                   "CEPH_TPU_DEVICE_SLAB": "1"}
    ).get("e2e", {})
    e2e_host: dict = _run_child_bench(
        "--e2e-device", extra_env={"CEPH_TPU_FORCE_BATCH": "1",
                                   "CEPH_TPU_DEVICE_SLAB": "0"}
    ).get("e2e", {})

    # MIXED-SIZE-POPULATION arm: a working set whose monolithic (pow2-
    # bucketed) residency footprint exceeds the tier budget must fit
    # entirely under the paged layout (frag_saved_bytes > 0, bounded
    # pages_used) — the page table's acceptance criterion
    tier_mixed: dict = _run_child_bench(
        "--tier-mixed", extra_env={"CEPH_TPU_FORCE_BATCH": "1"})

    # ELASTIC-MEMBERSHIP arm: MB/s moved and the reserved client's p99
    # impact DURING an out -> rebalance -> in cycle (CLASS_REBALANCE
    # dmClock-throttled drain) — the operational cost of a membership
    # change, measured, not assumed
    rebalance: dict = _run_child_bench("--rebalance", timeout=600)

    print(json.dumps({
        "metric": f"ec_encode_GBps_k{K}m{M}_1MiB_stripes_batch{N_STRIPES}"
                  f"_packedbit_resident_{backend}",
        "value": round(gbps, 3),
        "unit": "GB/s",
        "vs_baseline": round(gbps / cpu_gbps, 2),
        "ec_encode_packed_GBps": round(packed_gbps, 3),
        "ec_decode_GBps": round(dec_gbps, 3),
        "ec_decode_packed_GBps": round(dec_packed_gbps, 3),
        # int8-plane lane continuity (the r4/r5 headline pair)
        "ec_encode_int8planar_resident_GBps": round(int8_resident_gbps, 3),
        "ec_decode_int8planar_GBps": round(dec_int8_gbps, 3),
        "ec_encode_bitplanar_GBps": round(planar_gbps, 3),
        "ec_planar_pallas_GBps": round(pallas_planar_gbps, 3),
        "baseline_GBps": round(cpu_gbps, 3),
        "baseline_kind": f"native-{simd_kind}",
        "baseline_socket_GBps": round(socket_gbps, 3),
        "socket_threads": socket_threads,
        "host_cpu_count": os.cpu_count(),
        "vs_socket": round(gbps / socket_gbps, 2) if socket_gbps else 0,
        # linear-scaling extrapolation from measured per-core GB/s to
        # THIS host's core count (replaces modeled_socket_8c, which
        # silently assumed 8 cores; the assumption is now explicit)
        "modeled_socket_GBps": round(modeled_socket, 3),
        "modeled_socket_cores": modeled_cores,
        "modeled_socket_assumption":
            f"measured per-core x os.cpu_count()={modeled_cores}, "
            f"linear scaling",
        "vs_modeled_socket": round(gbps / modeled_socket, 2)
        if modeled_socket else 0,
        "scalar_GBps": round(scalar, 3),
        "vs_scalar": round(gbps / scalar, 2) if scalar else 0,
        # roofline accounting (ops/gf2.py writeup): the packed-bit
        # headline moves 1 HBM byte per data byte when parity planes
        # are consumed fused, 1.375 when they persist — band
        # [BW/1.375, BW].  The bandwidth denominator is measured in
        # the SAME run window as the headline loops (best of the
        # before/after probes; the r5 1.13 reconciliation), so the
        # fraction is physically bounded by 1.0.  Int8-plane roofline
        # fields stay for continuity (8-11 B/byte).
        "hbm_bw_GBps_empirical": round(hbm_bw_gbps, 1),
        "hbm_bw_probes_GBps": [round(p, 1) for p in bw_probes],
        "hbm_bw_congestion_remeasures": hbm_remeasures,
        "roofline_packedbit_GBps_lo": round(hbm_bw_gbps / 1.375, 1)
        if hbm_bw_gbps else 0,
        "roofline_packedbit_GBps_hi": round(hbm_bw_gbps, 1)
        if hbm_bw_gbps else 0,
        "roofline_fraction_hi": round(gbps / hbm_bw_gbps, 2)
        if hbm_bw_gbps else 0,
        "roofline_int8planes_GBps_lo": round(hbm_bw_gbps / 11, 1)
        if hbm_bw_gbps else 0,
        "roofline_int8planes_GBps_hi": round(hbm_bw_gbps / 8, 1)
        if hbm_bw_gbps else 0,
        "roofline_fraction_int8_hi": round(
            int8_resident_gbps / (hbm_bw_gbps / 8), 2)
        if hbm_bw_gbps else 0,
        # schedule-CSE A/B (verdict re-recorded every round; the
        # xor-op counts are the mechanism being measured)
        "ec_encode_packedbit_cse_GBps": round(cse_arm_gbps["cse"], 3),
        "ec_encode_packedbit_nocse_GBps": round(cse_arm_gbps["nocse"], 3),
        "xor_schedule_ops_nocse": xors_nocse,
        "xor_schedule_ops_cse": xors_cse,
        "ec_encode_packedbit_xor_GBps": round(packedbit_gbps, 3),
        # e2e_*: host RAM -> device -> host RAM, single-shot and as a
        # pipelined stream (which pays the dispatch floor per round).
        # The e2e_onhost_* pair is the same two paths on the CPU backend.
        "e2e_hostmem_GBps": round(e2e_gbps, 3),
        "e2e_pipelined_GBps": round(pipelined_gbps, 3),
        "pipelined_overlapped_rounds": overlapped,
        # on-host: pipelined/serial ratio with the overlap
        # mechanism engaged.  On a 1-core host the ratio's ceiling is
        # 1.0 — overlap needs a second engine (device DMA/compute vs
        # host staging) and a single core IS both engines; the signal
        # here is "mechanism engages and costs nothing", and >1 is
        # only reachable on multi-core hosts.
        "e2e_onhost_serial_GBps": round(onhost_serial_gbps, 3),
        "e2e_onhost_pipelined_GBps": round(onhost_pipelined_gbps, 3),
        "e2e_onhost_ratio": round(
            onhost_pipelined_gbps / onhost_serial_gbps, 2)
        if onhost_serial_gbps else 0,
        "e2e_onhost_overlapped_rounds": onhost_overlapped,
        "batch_ops_per_dispatch": round(batch_ops_per_dispatch, 1),
        "batch_hostmem_GBps": round(batch_gbps, 3),
        # EC data-plane counter snapshots (ISSUE 2): the trajectory
        # files carry the per-lane/cache breakdown each round
        "ec_tpu_perf": ec_tpu_perf,
        "gf2_sched_perf": sched_perf_snapshot(),
        "daemon_put_MBps": round(daemon_put_mbps, 1),
        "daemon_get_MBps": round(daemon_get_mbps, 1),
        "daemon_wire_put_MBps": round(daemon_wire_put_mbps, 1),
        "daemon_wire_get_MBps": round(daemon_wire_get_mbps, 1),
        # BOTH wirepath arms, every run: the headline daemon_wire_* pair
        # rode `wirepath_kind`; the _python pair is the forced-python
        # arm of the same window (non_regression --wire-floor compares
        # like-for-like arms only)
        "daemon_wire_put_MBps_python": round(daemon_wire_put_py_mbps, 1),
        "daemon_wire_get_MBps_python": round(daemon_wire_get_py_mbps, 1),
        "wirepath_kind": daemon_wirepath_kind,
        # which reactor substrate the daemon_wire_* arm ran (thread |
        # process): non_regression --wire-floor compares like-for-like
        # modes only, mirroring the wirepath-arm rule above
        "reactor_mode": daemon_reactor_mode,
        # pure-messenger single-stream, native vs forced-python arm in
        # one process/window — the GIL-escape ratio itself, without the
        # EC/OSD layers around it
        "msgr_stream": msgr_stream,
        # negotiated colocated ring transport (connect-time in-process
        # ring, no TCP/framing): acceptance bar within 1.5x of the
        # fastpath daemon_put/get above
        "daemon_local_put_MBps": round(daemon_local_put_mbps, 1),
        "daemon_local_get_MBps": round(daemon_local_get_mbps, 1),
        # multi-lane scaling curve (ms_lanes_per_peer 1/2/4/8, reactor
        # pool on): put/get MB/s per lane count, byte-identity asserted
        "lanes_sweep": lanes_sweep,
        # the `wire` perf snapshot of the daemon TCP run (framing-vs-io
        # averages, per-type counts, per-lane byte split, flush-size
        # histogram): the framing/io split trends round over round
        "wire_perf": daemon_wire_perf,
        # per-reactor/per-lane dump_reactors view of the same run
        # (reactor socket/rx balance, lane queue depths)
        "wire_plane": daemon_wire_plane,
        # the client `objecter` snapshot of the same run (resends,
        # timeouts, backoffs, paused ops): nonzero resilience counters
        # flag that a wire number was measured through recovery noise
        "objecter_perf": daemon_objecter_perf,
        # per-phase op-latency percentiles (p50/p99/p999 µs) of the TCP
        # daemon arm, for both put and get: queue_wait / ec_dispatch /
        # subop_wait from the OSD op trackers' sample rings, wire tx/rx
        # from the `wire` µs histograms — EC-cluster behavior is
        # characterized by per-phase TAILS, not throughput averages
        # (arXiv:1709.05365), and the ROADMAP wire work is judged here
        "op_phase_percentiles": daemon_phase_pcts,
        # cache-tier hot-read arm: zipfian re-reads on a small hot set,
        # resident-hit path vs cold decode path on the SAME window (same
        # schedule, same cluster); tier_perf is the aggregated `tier`
        # counter snapshot of that window (promotes, evictions,
        # resident hits, throttle refusals, agent pass latency)
        "tier_hot_read_MBps": round(tier_hot_mbps, 1),
        "tier_cold_read_MBps": round(tier_cold_mbps, 1),
        "tier_hot_vs_cold": round(tier_ratio, 2),
        "tier_perf": tier_perf,
        # `pagestore` occupancy snapshot of the hot-read arm (page
        # pool / dirty / frag_saved gauges while the set is resident)
        "tier_pagestore": tier_pagestore,
        # slab-arm e2e: put -> resident-read GB/s per slab arm, same
        # workload same record — the device-datapath claim is judged
        # here (and each arm's pagestore snapshot proves which install/
        # gather path ran: device_installs vs h2d, d2h_gathers)
        "e2e_device_GBps": e2e_device.get("e2e_GBps", 0.0),
        "e2e_host_GBps": e2e_host.get("e2e_GBps", 0.0),
        "e2e_device": e2e_device,
        "e2e_host": e2e_host,
        # mixed-size-population arm: monolithic-equivalent vs paged
        # footprint of the same residents, and whether the set fits
        "tier_mixed": tier_mixed,
        # elastic-membership arm: data-movement rate and the reserved
        # client's p99 while an out -> rebalance -> in cycle drains and
        # refills one OSD under the background dmClock classes; the
        # full child record (window, bytes, class counters, solo p99)
        # rides in "rebalance"
        "rebalance_MBps_moved": rebalance.get("rebalance_MBps_moved", 0.0),
        "client_get_p99_ms_during_rebalance": rebalance.get(
            "client_get_p99_ms_during_rebalance", 0.0),
        "rebalance": rebalance,
        # cluster-log tail summary of the daemon arms (warning+ counts
        # by channel) + every crash report the bench mons collected —
        # a crashed daemon FAILS the bench below instead of passing as
        # a noisy sample inside the ±40% band
        "cluster_log": daemon_cluster_log,
        # per-OSD utilization + fullness states of the measured window
        # (the mon's aggregated `osd df` view): a bench run on a
        # nearfull host explains its own anomalies
        "fullness": daemon_fullness,
    }))
    crashed = (daemon_cluster_log.get("crashes") or []) \
        if isinstance(daemon_cluster_log, dict) else []
    if crashed or daemon_arm_failed:
        print(f"FAIL bench: daemon crashed mid-bench "
              f"({[c.get('entity') for c in crashed]})", file=sys.stderr)
        return 1
    return 0


def _wire_perf_summary(dumps) -> dict:
    """Aggregate the `wire` perf sets of every daemon in the bench
    cluster into the BENCH-record snapshot: the framing-vs-io split
    (tx_framing/rx_framing/tx_io/rx_io longrunavgs), per-message-type
    byte/message counts, and the corked-outbox flush-size histogram —
    so the framing/io trend and the flush batching are visible round
    over round, not just the headline MB/s."""
    avgs = {}
    for name in ("tx_framing", "tx_io", "rx_io", "rx_framing"):
        c = sum(d.get(name, {}).get("avgcount", 0) for d in dumps)
        s = sum(d.get(name, {}).get("sum", 0.0) for d in dumps)
        avgs[name] = {"avgcount": c, "sum_s": round(s, 6),
                      "avg_us": round(s / c * 1e6, 3) if c else 0.0}
    counters = {}
    for name in ("tx_msgs", "tx_bytes", "rx_msgs", "rx_bytes",
                 "tx_flushes", "tx_flush_data", "tx_flush_ack",
                 "tx_acks", "tx_acks_coalesced", "tx_crc_reused",
                 "rx_batches", "local_msgs", "ring_msgs",
                 "lane_rx_parked", "lane_frag_tx", "lane_frag_rx",
                 "lane_revivals", "native_tx_calls", "native_rx_calls",
                 "native_bytes"):
        counters[name] = sum(d.get(name, 0) for d in dumps
                             if isinstance(d.get(name, 0), int))
    # per-lane byte split (dynamic tx_lane<k>_* counters): how evenly
    # the stripe round-robin + fragmentation spread the data lanes
    lane_split = {}
    for d in dumps:
        for k, v in d.items():
            if k.startswith("tx_lane") and isinstance(v, int):
                lane_split[k] = lane_split.get(k, 0) + v
    # which wirepath arm ran + how much hot-loop work it carried (the
    # wirepath_kind gauge, aggregated: any native messenger -> native)
    wirepath = {
        "kind": "native" if any(d.get("wirepath_kind") for d in dumps)
                else "python",
        "native_tx_calls": counters["native_tx_calls"],
        "native_rx_calls": counters["native_rx_calls"],
        "native_bytes": counters["native_bytes"],
    }
    # per-message socket time: the number the corked outbox moves —
    # tx_io is per FLUSH WINDOW, so batching drives this down while
    # tx_msgs stays put
    tx_msgs = counters["tx_msgs"]
    per_msg = {
        "tx_io_per_msg_us": round(
            avgs["tx_io"]["sum_s"] / tx_msgs * 1e6, 3) if tx_msgs else 0.0,
        "tx_framing_per_msg_us": round(
            avgs["tx_framing"]["sum_s"] / tx_msgs * 1e6, 3)
        if tx_msgs else 0.0,
    }
    hists = {}
    for name in ("tx_flush_frames", "tx_flush_bytes", "rx_batch_msgs"):
        buckets = [0] * 32
        count = 0
        total = 0.0
        for d in dumps:
            h = d.get(name)
            if isinstance(h, dict) and "buckets" in h:
                for i, v in enumerate(h["buckets"]):
                    buckets[i] += v
                count += h.get("count", 0)
                total += h.get("sum", 0.0)
        while buckets and not buckets[-1]:
            buckets.pop()
        hists[name] = {"count": count, "sum": total, "buckets": buckets,
                       "mean": round(total / count, 2) if count else 0.0}
    per_type = {}
    for d in dumps:
        for k, v in d.items():
            if not isinstance(v, int):
                continue
            if k.startswith(("tx_bytes_", "rx_bytes_")) or (
                    k.startswith(("tx_", "rx_"))
                    and k.split("_", 1)[1][:1].isupper()):
                per_type[k] = per_type.get(k, 0) + v
    return {"avgs": avgs, "counters": counters, "per_msg": per_msg,
            "lane_split": lane_split, "wirepath": wirepath,
            "flush_hist": hists, "per_type": per_type}


def _run_child_bench(flag: str, timeout: int = 300,
                     extra_env: dict = None,
                     parse_on_fail: bool = False) -> dict:
    """Run one CPU-only child-bench arm of this file (--daemon-path,
    --lanes-sweep, --hot-read, --onhost-overlap) and parse the JSON on
    its last stdout line; {} on any failure — a broken arm must never
    take the whole BENCH record down.  ``parse_on_fail`` still parses a
    nonzero-exit child's record (tagged ``_failed``): the daemon arm
    exits nonzero when a daemon CRASHED mid-bench, and that verdict —
    with its cluster_log evidence — must reach the caller, not vanish."""
    import subprocess

    from ceph_tpu.utils.jaxdev import cpu_child_env

    env = cpu_child_env()
    env.update(extra_env or {})
    try:
        child = subprocess.run(
            [sys.executable, os.path.abspath(__file__), flag],
            env=env, capture_output=True, text=True, timeout=timeout)
        if (child.returncode == 0 or parse_on_fail) \
                and child.stdout.strip():
            out = json.loads(child.stdout.strip().splitlines()[-1])
            if child.returncode != 0 and isinstance(out, dict):
                out["_failed"] = True
            return out
    except Exception:
        pass
    return {}


def _bench_reactor_mode(conf: dict = None) -> str:
    """The reactor substrate a bench cluster's messengers resolve:
    CEPH_TPU_REACTOR overrides, then the conf's ms_reactor_mode,
    default thread — the same precedence Messenger applies."""
    env = os.environ.get("CEPH_TPU_REACTOR", "").strip().lower()
    if env in ("thread", "process"):
        return env
    if conf is None:  # None = "the daemon-path shape"; {} = no conf
        conf = WIRE_PLANE_CONF
    m = str(conf.get("ms_reactor_mode", "thread")
            or "thread").strip().lower()
    return m if m in ("thread", "process") else "thread"


# the production wire shape for THIS bench host: 2 lanes per peer
# (control isolated from data) on 2 reactor workers per messenger —
# measured best on the 2-core CI container, where wider fan-outs pay
# GIL/core contention (the --lanes-sweep arm records the full 1/2/4/8
# curve every run; hosts with more cores should raise both knobs).
# The daemon_wire_* numbers are measured WITH the plane on (native
# wirepath included when it builds); the modeled_socket ceiling is what
# it chases (ROADMAP wire gap).  The forced-python wirepath arm is
# measured in the same window so both arms land in every BENCH record.
WIRE_PLANE_CONF = {"ms_lanes_per_peer": 2, "ms_async_op_threads": 2}


def daemon_path_bench() -> int:
    """64 MiB rados put+get through a 6-OSD in-process cluster — the
    cluster-path number (VERDICT r02 #7).  Measured on THREE transports:
    the colocated-daemons fast dispatch (ms_local_fastpath, by-reference
    handoff + ownership-transferring stores), the real TCP wire with the
    sharded multi-reactor plane on (WIRE_PLANE_CONF: reactor workers +
    multi-lane striping — the cross-host shape), and the negotiated
    colocated RING transport (ms_colocated_ring with the fastpath off:
    the connect-time in-process ring, acceptance bar within 1.5x of the
    no-wire fastpath).  The headline put/get numbers are the fastpath;
    wire numbers carry the _wire suffix, ring numbers _local, so no
    transport's tax hides in another's."""
    import asyncio

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from ceph_tpu.rados.vstart import Cluster

    size = 64 << 20

    async def go(fastpath: bool, extra_conf: dict = None,
                 want_plane: bool = False):
        # k=4 m=2 on 6 OSDs: every shard gets a distinct daemon, the
        # representative fan-out shape without an 11-daemon cluster
        conf = {"osd_auto_repair": False,
                "ms_local_fastpath": fastpath,
                "ms_colocated_ring": False}
        conf.update(extra_conf or {})
        cluster = Cluster(n_osds=6, conf=conf)
        await cluster.start()
        try:
            c = await cluster.client()
            pool = await c.create_pool("bench", profile={
                "plugin": "jerasure", "technique": "reed_sol_van",
                "k": "4", "m": "2"})
            payload = np.random.default_rng(0).integers(
                0, 256, size, dtype=np.uint8).tobytes()
            await c.put(pool, "warm", payload[:1 << 20])
            # isolate the measured window in the wire counters: the
            # warm put's handshake/boot traffic is not the data plane
            for osd in cluster.osds.values():
                osd.messenger.perf.reset()
            c.messenger.perf.reset()
            # best-of-3 (timeit's min discipline): single-core hosts
            # swing 3x run to run on page-allocation churn; the delete
            # between trials returns the buffers so each trial measures
            # the path, not the allocator's cold-page luck
            put_dt = get_dt = float("inf")
            c.perf.reset()
            for _ in range(3):
                t0 = time.perf_counter()
                await c.put(pool, "big", payload)
                put_dt = min(put_dt, time.perf_counter() - t0)
                t0 = time.perf_counter()
                got = await c.get(pool, "big")
                get_dt = min(get_dt, time.perf_counter() - t0)
                assert bytes(got) == payload
                await c.delete(pool, "big")
            wire_perf = _wire_perf_summary(
                [o.messenger.perf.dump() for o in cluster.osds.values()]
                + [c.messenger.perf.dump()])
            objecter_perf = c.perf.dump()
            # wire-plane introspection for the BENCH record: per-reactor
            # socket/rx balance + per-peer lane state (dump_reactors)
            wire_plane = {}
            if want_plane:
                wire_plane = {
                    "client": c.messenger.dump_reactors(),
                    "osds": {f"osd.{i}": o.messenger.dump_reactors()
                             for i, o in cluster.osds.items()},
                }
            # per-phase op-latency percentiles (p50/p99/p999 for
            # queue_wait / ec_dispatch / subop_wait + wire tx/rx tails),
            # one burst of small ops per arm: the OSD op trackers'
            # raw-sample rings give exact phase percentiles, the `wire`
            # µs histograms give the socket-io tails of the same window
            phase_pcts = {}
            if want_plane:
                burst = 24
                small = payload[:512 << 10]
                wires = [o.messenger for o in cluster.osds.values()] \
                    + [c.messenger]

                def _clear():
                    for o in cluster.osds.values():
                        o.ctx.op_tracker.clear_samples()
                    for w in wires:
                        w.perf.reset()

                def _collect():
                    merged = {}
                    for o in cluster.osds.values():
                        for ph, ss in \
                                o.ctx.op_tracker.phase_samples().items():
                            merged.setdefault(ph, []).extend(ss)
                    out = {ph: _sample_percentiles(ss)
                           for ph, ss in merged.items()}
                    out["wire_tx_io_us"] = _hist_percentiles(
                        [w.perf.get("tx_io_us") for w in wires])
                    out["wire_rx_io_us"] = _hist_percentiles(
                        [w.perf.get("rx_io_us") for w in wires])
                    return out

                _clear()
                for i in range(burst):
                    await c.put(pool, f"p{i}", small)
                phase_pcts["put"] = _collect()
                _clear()
                for i in range(burst):
                    await c.get(pool, f"p{i}")
                phase_pcts["get"] = _collect()
            # cluster-log + crash summary of this arm (read straight off
            # the in-process mon's LogMonitor): a daemon that died
            # mid-bench must FAIL the run, not hide as throughput noise
            # in the ±40% band
            clog = {
                "warn_counts_by_channel":
                    cluster.mon.logm.channel_counts(),
                "crashes": cluster.mon.logm.crash_ls(),
            }
            # per-OSD utilization + fullness of the measured window
            # (the mon's aggregated view, straight off the in-process
            # leader): embedded in the BENCH record
            fullness = {str(osd_id): row for osd_id, row in
                        cluster.mon._osd_utilization().items()}
            # mon membership/lifecycle counters of the same window
            # (auto-outs, crush moves, safety-predicate traffic): all
            # four should be ZERO on a healthy bench host — a nonzero
            # auto_outs means an OSD went dark mid-window
            membership = {k: cluster.mon.perf.get(k) for k in
                          ("auto_outs", "crush_moves",
                           "predicate_queries", "predicate_refusals")}
            await c.stop()
            return (put_dt, get_dt, wire_perf, objecter_perf, phase_pcts,
                    wire_plane, clog, fullness, membership)
        finally:
            await cluster.stop()

    from ceph_tpu.utils import wirepath as _wp

    put_dt, get_dt, _, _, _, _, clog_fast, _, _ = asyncio.run(go(True))
    (wire_put_dt, wire_get_dt, wire_perf, objecter_perf,
     phase_pcts, wire_plane, clog_wire, fullness,
     membership) = asyncio.run(go(False, WIRE_PLANE_CONF,
                                  want_plane=True))
    # forced-python wirepath arm, same window: BOTH arms land in every
    # BENCH record (when the native wirepath never built, the two arms
    # are the same code path and the record says so via wirepath_kind)
    (wire_py_put_dt, wire_py_get_dt, wire_py_perf, _, _, _,
     clog_wire_py, _, _) = asyncio.run(
        go(False, dict(WIRE_PLANE_CONF, ms_wirepath_native=False)))
    # colocated ring arm: fastpath OFF, ring ON — the negotiated
    # in-process transport serves every byte
    (local_put_dt, local_get_dt, local_perf, _, _, _,
     clog_local, _, _) = asyncio.run(go(False,
                                        {"ms_colocated_ring": True}))
    # merge the arms' cluster-log summaries; ANY crash fails the
    # bench (a silently dead OSD must not pass as a noisy sample)
    warn_counts: dict = {}
    crashes: list = []
    for arm, cl in (("fastpath", clog_fast), ("wire", clog_wire),
                    ("wire_python", clog_wire_py), ("ring", clog_local)):
        for ch, n in (cl.get("warn_counts_by_channel") or {}).items():
            warn_counts[ch] = warn_counts.get(ch, 0) + n
        for cr in cl.get("crashes") or []:
            crashes.append({"arm": arm, **cr})
    print(json.dumps({
        "put_MBps": round(size / put_dt / 1e6, 1),
        "get_MBps": round(size / get_dt / 1e6, 1),
        "wire_put_MBps": round(size / wire_put_dt / 1e6, 1),
        "wire_get_MBps": round(size / wire_get_dt / 1e6, 1),
        # forced-python wirepath arm of the same window (like-for-like
        # baseline for the native arm above; identical code path when
        # the native layer never built)
        "wire_put_MBps_python": round(size / wire_py_put_dt / 1e6, 1),
        "wire_get_MBps_python": round(size / wire_py_get_dt / 1e6, 1),
        # which wirepath arm the headline wire numbers ran on
        "wirepath_kind": _wp.kind(),
        # which reactor substrate the wire arm's messengers ran
        # (CEPH_TPU_REACTOR / ms_reactor_mode; wire-floor compares
        # like-for-like modes only)
        "reactor_mode": _bench_reactor_mode(),
        # negotiated colocated ring (no TCP, no framing): acceptance bar
        # is within 1.5x of the no-wire fastpath put/get above
        "local_put_MBps": round(size / local_put_dt / 1e6, 1),
        "local_get_MBps": round(size / local_get_dt / 1e6, 1),
        "local_ring_msgs": int((local_perf.get("counters") or {})
                               .get("ring_msgs", 0)),
        "wire_perf": wire_perf,
        # the forced-python arm's wirepath engagement counters: native
        # calls must be ZERO there (the same check the parity tests
        # assert), so a record where they aren't is self-diagnosing
        "wire_python_wirepath": (wire_py_perf or {}).get("wirepath"),
        # per-reactor/per-lane state of the wire arm (reactor balance,
        # lane byte split, reassembly depth) — the dump_reactors view
        "wire_plane": wire_plane,
        # the client `objecter` set for the measured window: resends /
        # timeouts / backoffs should be ZERO on a healthy bench host —
        # a nonzero count explains an anomalous MB/s sample
        "objecter_perf": objecter_perf,
        # per-phase p50/p99/p999 (µs) from the TCP arm's op trackers +
        # wire histograms — where each op's time goes, as tails
        "op_phase_percentiles": phase_pcts,
        # cluster-log summary of the bench clusters (warning+ entry
        # counts per channel) and every crash report the mon collected:
        # the fleet-forensics view of the measured window
        "cluster_log": {"warn_counts_by_channel": warn_counts,
                        "crashes": crashes},
        # per-OSD utilization + fullness states of the wire arm's
        # cluster (mon aggregated view) — the capacity-plane snapshot
        "fullness": fullness,
        # mon membership-plane counters of the wire arm (auto-outs,
        # crush moves, safety-predicate queries/refusals): all zero on
        # a healthy bench host; a nonzero auto_outs means an OSD went
        # dark mid-window and the throughput sample is suspect
        "mon_membership": membership}))
    if crashes:
        print(f"FAIL daemon-path bench: {len(crashes)} daemon crash"
              f"(es) during the measured window: "
              f"{[c['entity'] for c in crashes]}", file=sys.stderr)
        return 1
    return 0


def lanes_sweep_bench() -> int:
    """``--lanes-sweep``: the multi-lane scaling curve (1/2/4/8 lanes,
    reactor pool on) — 32 MiB put+get through a 6-OSD TCP cluster per
    lane count, best-of-2 — measured on BOTH reactor substrates
    (``ms_reactor_mode=thread`` and ``process``), so the process-sharded
    plane's scaling shape lands next to the thread arm's in every BENCH
    record.  On a 2-core host the thread curve collapses past 2 lanes
    (the interpreter halves of the shards contend); the process arm is
    the one that can spread when cores exist.  Recorded every bench run
    so lane scaling is a tracked trajectory, not a one-off claim."""
    import asyncio

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from ceph_tpu.rados.vstart import Cluster

    size = 32 << 20

    async def run_lanes(mode: str, lanes: int):
        cluster = Cluster(n_osds=6, conf={
            "osd_auto_repair": False,
            "ms_local_fastpath": False,
            "ms_colocated_ring": False,
            "ms_reactor_mode": mode,
            "ms_lanes_per_peer": lanes,
            "ms_async_op_threads": 2})
        await cluster.start()
        try:
            c = await cluster.client()
            pool = await c.create_pool("sweep", profile={
                "plugin": "jerasure", "technique": "reed_sol_van",
                "k": "4", "m": "2"})
            payload = np.random.default_rng(7).integers(
                0, 256, size, dtype=np.uint8).tobytes()
            put_dt = get_dt = float("inf")
            for _ in range(2):
                t0 = time.perf_counter()
                await c.put(pool, "big", payload)
                put_dt = min(put_dt, time.perf_counter() - t0)
                t0 = time.perf_counter()
                got = await c.get(pool, "big")
                get_dt = min(get_dt, time.perf_counter() - t0)
                assert bytes(got) == payload  # byte-identity gate
                await c.delete(pool, "big")
            await c.stop()
            return put_dt, get_dt
        finally:
            await cluster.stop()

    sweep = {}
    for mode in ("thread", "process"):
        curve = {}
        for lanes in (1, 2, 4, 8):
            try:
                put_dt, get_dt = asyncio.run(run_lanes(mode, lanes))
                curve[str(lanes)] = {
                    "put_MBps": round(size / put_dt / 1e6, 1),
                    "get_MBps": round(size / get_dt / 1e6, 1)}
            except Exception as e:  # one bad arm must not hide the others
                curve[str(lanes)] = {"error": f"{type(e).__name__}: {e}"}
        sweep[mode] = {"reactor_mode": mode, "curve": curve}
    print(json.dumps({"lanes_sweep": sweep}))
    return 0


def msgr_stream_bench() -> int:
    """``--msgr-stream``: pure-messenger single-stream throughput — one
    TCP connection, a pipelined one-way stream of 64 KiB blob frames —
    measured on the native wirepath arm AND the forced-python arm in
    the same process/window (ISSUE 12's acceptance ratio).  64 KiB sits
    in the regime the GIL actually binds: per-frame interpreter work is
    a real fraction of the byte cost, bursts buffer on the receiver so
    the rx drain batches, and the corked tx window coalesces frames
    into single native writev calls.  Byte identity is asserted on a
    sampled checksum (every 64th frame): a per-frame bytes()+crc in the
    dispatcher is identical GIL-bound work on both arms, so verifying
    everything inside the timed window dilutes the very ratio this
    bench exists to measure (the full-coverage identity gates live in
    the parity tests and wire_corpus, not here)."""
    import asyncio

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from ceph_tpu.rados.messenger import Messenger, message
    from ceph_tpu.utils import wirepath as wp
    from ceph_tpu.utils.checksum import checksum

    @message(903)  # bench-local, like the test suite's MTest (id 900);
    # 901/902 are taken by test_ec_perf's probes and the registry is
    # process-global (test_ec_perf imports bench)
    class MStreamProbe:
        seqno: int = 0
        blob: bytes = b""
        FIXED_FIELDS = [("seqno", "q"), ("blob", "y")]
        BLOB_ATTR = "blob"
        BLOB_VIEW_OK = True

    size = 64 << 20
    frame = 64 << 10
    window = 32
    payload = np.random.default_rng(11).integers(
        0, 256, frame, dtype=np.uint8).tobytes()
    want_crc = checksum(payload)

    async def run_arm(native: bool):
        server = Messenger("s", {"ms_wirepath_native": native},
                           entity_type="osd")
        client = Messenger("c", {"ms_wirepath_native": native})
        state = {"bytes": 0, "bad": 0, "done": asyncio.Event()}

        async def disp(conn, msg):
            state["bytes"] += len(msg.blob)
            if msg.seqno % 64 == 0 \
                    and checksum(bytes(msg.blob)) != want_crc:
                state["bad"] += 1
            if state["bytes"] >= size:
                state["done"].set()

        server.dispatcher = disp
        addr = await server.bind("127.0.0.1", 0)
        conn = await client.connect(addr)
        # warm: engage the cork swap + fast read before timing
        for _ in range(4):
            await conn.send(MStreamProbe(seqno=-1, blob=payload))
        await asyncio.sleep(0.05)
        state["bytes"] = 0
        n = size // frame
        t0 = time.perf_counter()
        for base in range(0, n, window):
            await asyncio.gather(
                *(conn.send(MStreamProbe(seqno=i, blob=payload))
                  for i in range(base, min(base + window, n))))
        await asyncio.wait_for(state["done"].wait(), 180)
        dt = time.perf_counter() - t0
        if state["bad"]:
            raise AssertionError(
                f"{state['bad']} corrupt frames on the "
                f"{'native' if native else 'python'} arm")
        perf = server.perf.dump()
        out = {
            "MBps": round(size / dt / 1e6, 1),
            "native_rx_calls": perf.get("native_rx_calls", 0),
            "native_bytes": perf.get("native_bytes", 0),
            "native_tx_calls": client.perf.dump().get(
                "native_tx_calls", 0),
        }
        await client.shutdown()
        await server.shutdown()
        return out

    arms = {}
    for label, native in (("native", True), ("python", False)):
        best = None
        for _ in range(2):  # best-of-2 (timeit min discipline)
            got = asyncio.run(run_arm(native))
            if best is None or got["MBps"] > best["MBps"]:
                best = got
        arms[label] = best
    ratio = (arms["native"]["MBps"] / arms["python"]["MBps"]
             if arms["python"]["MBps"] else 0.0)
    print(json.dumps({"msgr_stream": {
        "frame_bytes": frame,
        "stream_bytes": size,
        "wirepath_kind": wp.kind(),
        "reactor_mode": _bench_reactor_mode({}),
        "native": arms["native"],
        "python": arms["python"],
        "native_vs_python": round(ratio, 2),
    }}))
    return 0


def _sample_percentiles(samples) -> dict:
    """p50/p99/p999 (µs) over raw per-phase seconds samples (the shared
    tracked_op reduction; bench merges across OSDs first)."""
    from ceph_tpu.common.tracked_op import percentile_summary

    return percentile_summary(samples)


def _hist_percentiles(bucket_lists) -> dict:
    """Approximate p50/p99/p999 from summed power-of-2 µs histograms
    (bucket i counts observations with bit_length == i; the reported
    value is the bucket's upper bound, 2^i - 1)."""
    buckets = [0] * 32
    for bl in bucket_lists:
        if isinstance(bl, list):
            for i, v in enumerate(bl):
                buckets[i] += v
    total = sum(buckets)

    def pct(q: float) -> int:
        if not total:
            return 0
        need = q * total
        cum = 0
        for i, v in enumerate(buckets):
            cum += v
            if cum >= need:
                return (1 << i) - 1
        return (1 << 31) - 1

    return {"p50_us": pct(0.50), "p99_us": pct(0.99),
            "p999_us": pct(0.999), "count": total}


def hot_read_bench() -> int:
    """Cache-tier hot-read arm: zipfian re-reads over a small hot set
    through a 6-OSD TCP cluster, measured on BOTH serving paths in the
    SAME run window — the resident-hit fast path (objects promoted to
    device residency by the tier: zero shard reads, zero decode) vs the
    cold decode path (residents dropped before every read, fadvise
    dontneed so the scan never heats the hit sets).  Byte-identity is
    asserted on every measured read.  Emits the aggregated `tier` perf
    snapshot for the BENCH record."""
    import asyncio

    # the planar store engages only on an accelerator backend; this arm
    # runs in a CPU child, so force the CPU override BEFORE any
    # OSD asks for the shared queue
    os.environ["CEPH_TPU_FORCE_BATCH"] = "1"
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from ceph_tpu.rados.vstart import Cluster
    import ceph_tpu.rados.osd as osdmod

    n_hot = 8
    obj_size = 4 << 20
    n_reads = 64

    async def go():
        cluster = Cluster(n_osds=6, conf={
            "osd_auto_repair": False,
            "ms_local_fastpath": False,
            "client_op_timeout": 60.0,
            "osd_hit_set_period": 1.0,
            "osd_min_read_recency_for_promote": 1,
            # promotion must not throttle the warmup of an 8-object set
            "osd_tier_promote_max_objects_sec": 64,
            "osd_tier_promote_max_bytes_sec": 512 << 20,
            "osd_tier_agent_interval": 0.5})
        await cluster.start()
        try:
            c = await cluster.client()
            pool = await c.create_pool("hot", profile={
                "plugin": "jerasure", "technique": "reed_sol_van",
                "k": "4", "m": "2"})
            store = osdmod.shared_planar_store()
            assert store is not None
            rng = np.random.default_rng(7)
            blobs = {f"h{i}": rng.integers(0, 256, obj_size,
                                           dtype=np.uint8).tobytes()
                     for i in range(n_hot)}
            for oid, blob in blobs.items():
                await c.put(pool, oid, blob)

            def drop_residents(oid):
                for o in cluster.osds.values():
                    if o._planar is not None:
                        o._planar.drop(o._planar_key(pool, oid))

            def resident(oid):
                return any(o._planar is not None
                           and o._planar_key(pool, oid) in store
                           for o in cluster.osds.values())

            # zipfian re-read schedule over the hot set (rank-weighted):
            # the same schedule drives both arms, so the windows compare
            # the PATH, not the access pattern
            weights = np.array([1.0 / (r + 1) for r in range(n_hot)])
            weights /= weights.sum()
            schedule = [f"h{i}" for i in rng.choice(
                n_hot, size=n_reads, p=weights)]

            # COLD arm first (it leaves nothing resident): drop
            # residents before every read, advise dontneed
            for oid in blobs:  # warm TCP connections outside the window
                drop_residents(oid)
                await c.get(pool, oid, fadvise="dontneed")
            t0 = time.perf_counter()
            for oid in schedule:
                drop_residents(oid)
                got = await c.get(pool, oid, fadvise="dontneed")
                assert got == blobs[oid]
            cold_dt = time.perf_counter() - t0

            # PROMOTE the hot set, then the resident-hit arm
            for oid in blobs:
                await c.get(pool, oid, fadvise="willneed")
            for _ in range(200):
                if all(resident(oid) for oid in blobs):
                    break
                await asyncio.sleep(0.02)
            hits0 = sum(o.tier_perf.get("resident_hit")
                        for o in cluster.osds.values())
            t0 = time.perf_counter()
            for oid in schedule:
                got = await c.get(pool, oid)
                assert got == blobs[oid]
            hot_dt = time.perf_counter() - t0
            hits = sum(o.tier_perf.get("resident_hit")
                       for o in cluster.osds.values()) - hits0

            tier_perf: dict = {}
            for o in cluster.osds.values():
                for k, v in o.tier_perf.dump().items():
                    if isinstance(v, int):
                        tier_perf[k] = tier_perf.get(k, 0) + v
                    elif isinstance(v, dict) and "avgcount" in v:
                        # longrunavg dump shape (agent_pass_s):
                        # {"avgcount": N, "sum": seconds}
                        agg = tier_perf.setdefault(
                            k, {"sum_s": 0.0, "count": 0})
                        agg["sum_s"] += v.get("sum", 0.0)
                        agg["count"] += v.get("avgcount", 0)
            pagestore = (store.page_stats()
                         if hasattr(store, "page_stats") else None)
            await c.stop()
            return cold_dt, hot_dt, hits, tier_perf, pagestore
        finally:
            await cluster.stop()

    cold_dt, hot_dt, hits, tier_perf, pagestore = asyncio.run(go())
    total = n_reads * obj_size
    print(json.dumps({
        "tier_hot_read_MBps": round(total / hot_dt / 1e6, 1),
        "tier_cold_read_MBps": round(total / cold_dt / 1e6, 1),
        "tier_hot_vs_cold": round(cold_dt / hot_dt, 2),
        "tier_resident_hits_in_window": hits,
        "tier_window_reads": n_reads,
        # page-pool occupancy snapshot while the hot set is resident
        # (None = monolithic store forced via CEPH_TPU_PAGESTORE=0)
        "tier_pagestore": pagestore,
        "tier_perf": tier_perf}))
    return 0


def e2e_device_bench() -> int:
    """Slab-arm end-to-end arm (bench.py --e2e-device): put ->
    resident-read through a real TCP cluster with the pagestore's slab
    arm pinned by CEPH_TPU_DEVICE_SLAB (the parent runs this child once
    per arm, SAME workload, so the two windows compare the SLAB PATH —
    install/gather kernels — not the wire).  Byte identity asserted on
    every measured read.  ``e2e_GBps`` is total bytes moved over the
    put+read window; the per-window rates ride alongside, with the
    pagestore snapshot (device_slabs / h2d_installs / device_installs /
    d2h_gathers) as evidence of WHICH path actually ran."""
    import asyncio

    os.environ["CEPH_TPU_FORCE_BATCH"] = "1"
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from ceph_tpu.rados.vstart import Cluster
    import ceph_tpu.rados.osd as osdmod

    n_hot = 8
    obj_size = 2 << 20
    n_reads = 48

    async def go():
        cluster = Cluster(n_osds=4, conf={
            "osd_auto_repair": False,
            "ms_local_fastpath": False,
            "client_op_timeout": 60.0,
            "osd_hit_set_period": 1.0,
            "osd_min_read_recency_for_promote": 1,
            "osd_tier_promote_max_objects_sec": 64,
            "osd_tier_promote_max_bytes_sec": 512 << 20,
            "osd_tier_agent_interval": 0.5})
        await cluster.start()
        try:
            c = await cluster.client()
            pool = await c.create_pool("e2e", profile={
                "plugin": "jerasure", "technique": "reed_sol_van",
                "k": "2", "m": "1"})
            store = osdmod.shared_planar_store()
            assert store is not None
            rng = np.random.default_rng(11)
            blobs = {f"e{i}": rng.integers(0, 256, obj_size,
                                           dtype=np.uint8).tobytes()
                     for i in range(n_hot)}
            # connection warmup outside the windows — at the window's
            # object size: the fused install compiles per source
            # geometry, at the first install of each (ops/slab.py)
            await c.put(pool, "warm", bytes(obj_size))

            # PUT window: encode + wire + install.  The gather kernels
            # were pre-warmed at store build (osd_tier_slab_prewarm)
            # and the warm-up put compiled this size's install, so the
            # compile-counter delta across the window is the
            # AOT-discipline evidence: 0 in-line XLA compiles.
            from ceph_tpu.ops.slab import SLAB_PERF
            prewarmed = bool(getattr(store, "prewarmed", False))
            c0 = SLAB_PERF.get("compile")
            t0 = time.perf_counter()
            for oid, blob in blobs.items():
                await c.put(pool, oid, blob)
            put_dt = time.perf_counter() - t0
            put_compiles = int(SLAB_PERF.get("compile") - c0)
            if prewarmed:
                assert put_compiles == 0, \
                    f"{put_compiles} in-line slab compiles in the put " \
                    f"window despite pre-warm"

            def resident(oid):
                return any(o._planar is not None
                           and o._planar_key(pool, oid) in store
                           for o in cluster.osds.values())

            for oid in blobs:
                await c.get(pool, oid, fadvise="willneed")
            for _ in range(200):
                if all(resident(oid) for oid in blobs):
                    break
                await asyncio.sleep(0.02)
            schedule = [f"e{i}" for i in rng.integers(
                0, n_hot, size=n_reads)]

            # RESIDENT-READ window: slab gather -> pack -> wire
            t0 = time.perf_counter()
            for oid in schedule:
                got = await c.get(pool, oid)
                assert got == blobs[oid]
            read_dt = time.perf_counter() - t0

            pagestore = (store.page_stats()
                         if hasattr(store, "page_stats") else None)
            await c.stop()
            return put_dt, read_dt, pagestore, prewarmed, put_compiles
        finally:
            await cluster.stop()

    put_dt, read_dt, pagestore, prewarmed, put_compiles = asyncio.run(go())
    put_bytes = n_hot * obj_size
    read_bytes = n_reads * obj_size
    arm = "device" if (pagestore or {}).get("device_arm") else "host"
    print(json.dumps({"e2e": {
        "arm": arm,
        "put_MBps": round(put_bytes / put_dt / 1e6, 1),
        "resident_read_MBps": round(read_bytes / read_dt / 1e6, 1),
        "e2e_GBps": round((put_bytes + read_bytes)
                          / (put_dt + read_dt) / 1e9, 3),
        "put_bytes": put_bytes, "read_bytes": read_bytes,
        "slab_prewarmed": prewarmed,
        "put_window_compiles": put_compiles,
        "pagestore": pagestore}}))
    return 0


def tier_mixed_bench() -> int:
    """Mixed-size-population arm (bench.py --tier-mixed): the paged
    layout's reason to exist.  A working set of mixed object sizes is
    chosen so its FULL-STRIPE residency footprint — the only shape the
    monolithic r10 store can hold, all k+m shard rows or nothing —
    exceeds the tier budget, while its data-row footprint fits.  The
    paged store's agent resolves the pressure at O(page) granularity:
    it SHEDS the parity-row page suffixes of cold residents (partial-
    stripe residency) so every object stays read-resident at ~k/n of
    its full footprint; the monolithic store at the same budget must
    evict whole objects forever.  The arm promotes the set, lets the
    agent settle, re-promotes anything dropped in the churn, and then
    asserts: every read is byte-identical, every object is resident,
    frag_saved_bytes > 0 (full-stripe-equivalent minus actual pages),
    and pages_used is bounded by the pool."""
    import asyncio

    os.environ["CEPH_TPU_FORCE_BATCH"] = "1"
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from ceph_tpu.rados.vstart import Cluster
    import ceph_tpu.rados.osd as osdmod

    # ~24 objects x 144..240 KiB at k=2,m=1: full-stripe residency
    # needs ~7.1 MiB, the data rows alone ~4.7 MiB — a budget of 6 MiB
    # holds the whole set only with parity shed
    capacity = 6 << 20
    page_bytes = 16 << 10
    n_obj = 24
    sizes = [(144 << 10) + 4096 * i for i in range(n_obj)]

    async def go():
        cluster = Cluster(n_osds=3, conf={
            "osd_auto_repair": False,
            "client_op_timeout": 60.0,
            "osd_hit_set_period": 5.0,
            "osd_min_read_recency_for_promote": 1,
            "osd_tier_promote_max_objects_sec": 256,
            "osd_tier_promote_max_bytes_sec": 1 << 30,
            "osd_ec_planar_bytes": capacity,
            "osd_tier_page_bytes": page_bytes,
            "osd_tier_target_max_bytes": capacity,
            "osd_cache_target_full_ratio": 0.9,
            "osd_tier_agent_interval": 0.1})
        await cluster.start()
        try:
            c = await cluster.client()
            pool = await c.create_pool("mixed", profile={
                "plugin": "jerasure", "technique": "reed_sol_van",
                "k": "2", "m": "1"})
            store = osdmod.shared_planar_store()
            assert store is not None
            rng = np.random.default_rng(11)
            blobs = {}
            for i, size in enumerate(sizes):
                oid = f"m{i}"
                blobs[oid] = rng.integers(0, 256, size,
                                          dtype=np.uint8).tobytes()
                await c.put(pool, oid, blobs[oid])

            def residents():
                return sum(
                    1 for oid in blobs
                    if any(o._planar is not None
                           and o._planar_key(pool, oid) in store
                           for o in cluster.osds.values()))

            # promote rounds: the first pass over-commits (full-stripe
            # installs), the agent sheds parity on its cadence, and
            # re-reads re-promote whatever churned out — converges to
            # everything-resident-data-only within a few rounds
            for _ in range(6):
                for oid, blob in blobs.items():
                    got = await c.get(pool, oid, fadvise="willneed")
                    assert got == blob
                await asyncio.sleep(0.4)
                if residents() == n_obj \
                        and store.resident_bytes <= capacity:
                    break
            for oid, blob in blobs.items():  # resident-hit identity
                assert await c.get(pool, oid) == blob
            stats = store.stats()
            pagestore = (store.page_stats()
                         if hasattr(store, "page_stats") else None)
            held = residents()
            await c.stop()
            return stats, pagestore, held
        finally:
            await cluster.stop()

    stats, pagestore, residents = asyncio.run(go())

    # -- same-window put-mode comparison: the replicated-writeback fast
    # ack (raw object on a cache quorum, EC encode deferred to the
    # background flush) vs the synchronous write-through shape (inline
    # k+m encode + sub-write fan-out, ack at pool min_size).  Same
    # cluster, same pool, same object size, distinct oid sets; the mode
    # flips via the mon-validated `cache_mode` pool opt with per-OSD
    # propagation polling so neither window straddles the switch.
    put_obj = 256 << 10
    n_put = 12

    async def go_putmode():
        cluster = Cluster(n_osds=4, conf={
            "osd_auto_repair": False,
            "client_op_timeout": 60.0,
            "osd_hit_set_period": 30.0,
            "osd_min_read_recency_for_promote": 1,
            "osd_tier_promote_max_objects_sec": 256,
            "osd_tier_promote_max_bytes_sec": 1 << 30,
            # destage stays out of both measured windows; dropped for
            # the drain below
            "osd_tier_flush_age": 60.0,
            "osd_tier_agent_interval": 0.2})
        await cluster.start()
        try:
            c = await cluster.client()
            pool = await c.create_pool("putmode", profile={
                "plugin": "jerasure", "technique": "reed_sol_van",
                "k": "2", "m": "1"})
            store = osdmod.shared_planar_store()
            rng = np.random.default_rng(7)
            payloads: dict = {}
            rates: dict = {}
            for mode, prefix in (("writethrough", "wt"),
                                 ("writeback", "wb")):
                await c.pool_set(pool, "cache_mode", mode)
                for _ in range(200):
                    if all((getattr(o.osdmap.pools.get(pool), "opts",
                                    {}) or {}).get("cache_mode") == mode
                           for o in cluster.osds.values()):
                        break
                    await asyncio.sleep(0.02)
                blobs = {f"{prefix}{i}": rng.integers(
                    0, 256, put_obj, dtype=np.uint8).tobytes()
                    for i in range(n_put)}
                payloads.update(blobs)
                await c.put(pool, f"{prefix}-warm", b"x" * 4096)
                t0 = time.perf_counter()
                for oid, blob in blobs.items():
                    await c.put(pool, oid, blob)
                dt = time.perf_counter() - t0
                rates[mode] = n_put * put_obj / dt / 1e6
            for oid, blob in payloads.items():  # acked-read identity
                assert await c.get(pool, oid) == blob
            # drain the fast-ack dirt (the deferred EC destage) before
            # teardown, then re-verify the flushed bytes
            for o in cluster.osds.values():
                o.conf["osd_tier_flush_age"] = 0.1
            for _ in range(300):
                if store is None or not any(
                        True for _k, _i, _g, _s in store.dirty_items()):
                    break
                await asyncio.sleep(0.05)
            for oid, blob in payloads.items():
                assert await c.get(pool, oid) == blob
            await c.stop()
            return rates
        finally:
            await cluster.stop()

    rates = asyncio.run(go_putmode())
    wb = rates.get("writeback", 0.0)
    wt = rates.get("writethrough", 0.0)

    mono = int(stats.get("monolithic_equiv_bytes", 0))
    paged_bytes = int(stats.get("resident_bytes", 0))
    print(json.dumps({
        "writeback_put_MBps": round(wb, 1),
        "writethrough_put_MBps": round(wt, 1),
        "writeback_vs_writethrough": round(wb / wt, 2) if wt else 0.0,
        "put_window_objects": n_put,
        "put_window_object_bytes": put_obj,
        "tier_mixed_objects": n_obj,
        "tier_mixed_residents_held": residents,
        "tier_mixed_capacity_bytes": capacity,
        "tier_mixed_page_bytes": page_bytes,
        # the acceptance pair: what the SAME residents would cost as
        # monolithic full-stripe buffers vs what the pages actually
        # hold after parity shed
        "tier_mixed_monolithic_equiv_bytes": mono,
        "tier_mixed_paged_bytes": paged_bytes,
        "tier_mixed_frag_saved_bytes": max(0, mono - paged_bytes),
        "tier_mixed_fits_paged": paged_bytes <= capacity
        and residents == n_obj,
        "tier_mixed_fits_monolithic": mono <= capacity,
        "tier_mixed_pagestore": pagestore}))
    return 0


def rebalance_bench() -> int:
    """Elastic-membership arm (bench.py --rebalance): the number
    operators actually care about — MB/s of data moved and the reserved
    client's p99 impact DURING an out -> rebalance -> in cycle, not in a
    quiet cluster.  A reserved tenant (qos_class:gold) paces gets
    against a 5-OSD mclock cluster; its solo p99 is measured first, then
    one OSD is marked out and the same traffic runs while CLASS_REBALANCE
    sweeps drain the leaver (throttled by the background dmClock
    profile).  MB/s moved = the OSDs' rebalance_bytes_moved delta over
    the drain window.  The cycle completes with `osd in` + refill and
    every byte verified."""
    import asyncio

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from ceph_tpu.rados.vstart import Cluster

    # enough data volume that the drain window is seconds, not
    # milliseconds — the during-rebalance p99 needs a real sample count
    n_objects = 48
    obj_size = 256 << 10

    async def go():
        cluster = Cluster(n_osds=5, conf={
            "osd_op_queue": "mclock",
            "osd_mclock_profile": "balanced",
            "osd_auto_repair": True,
            "osd_heartbeat_interval": 0.1,
            "osd_repair_delay": 0.1,
            "osd_recovery_retry": 0.3,
            "ms_local_fastpath": False,
            "mon_osd_report_grace": 2.0,
            "client_op_timeout": 30.0,
            "client_op_deadline": 60.0})
        await cluster.start()
        try:
            c = await cluster.client()
            pool = await c.create_pool("rebal", profile={
                "plugin": "jerasure", "technique": "reed_sol_van",
                "k": "2", "m": "1"})
            await c.pool_set(pool, "qos_class:gold", "100:20:0:0.5")
            rng = np.random.default_rng(13)
            blobs = {f"r{i}": rng.integers(0, 256, obj_size,
                                           dtype=np.uint8).tobytes()
                     for i in range(n_objects)}
            for oid, blob in blobs.items():
                await c.put(pool, oid, blob)
            gold = await cluster.client()

            async def traffic(samples, stop):
                oids = list(blobs)
                i = 0
                while not stop.is_set():
                    oid = oids[i % len(oids)]
                    i += 1
                    t0 = time.perf_counter()
                    got = await gold.get(pool, oid,
                                         client="client.gold.0")
                    samples.append(time.perf_counter() - t0)
                    assert bytes(got) == blobs[oid]
                    await asyncio.sleep(0.02)  # ~50 ops/s paced

            async def run_window(seconds_or_pred):
                samples: list = []
                stop = asyncio.Event()
                t = asyncio.get_running_loop().create_task(
                    traffic(samples, stop))
                t0 = time.perf_counter()
                if callable(seconds_or_pred):
                    while not seconds_or_pred() \
                            and time.perf_counter() - t0 < 60.0:
                        await asyncio.sleep(0.1)
                else:
                    await asyncio.sleep(seconds_or_pred)
                stop.set()
                await t
                return samples, time.perf_counter() - t0

            victim_id = sorted(cluster.osds)[0]
            victim = cluster.osds[victim_id]

            def victim_shards():
                return sum(1 for (p, _o, _s) in victim.store._data
                           if p == pool)

            for _ in range(100):
                if victim_shards():
                    break
                await asyncio.sleep(0.05)
            shards_before = victim_shards()

            solo_samples, _ = await run_window(3.0)

            # the measured window is the FULL cycle: out -> drain
            # converged -> in -> refill converged, all with the gold
            # client reading throughout
            moved0 = sum(o.perf.get("rebalance_bytes_moved")
                         for o in cluster.osds.values())
            drained = {"ok": False}

            async def cycle():
                await c.osd_out(victim_id)
                for _ in range(600):
                    if victim_shards() == 0:
                        break
                    await asyncio.sleep(0.1)
                drained["ok"] = victim_shards() == 0
                await c.osd_in(victim_id)
                for _ in range(600):
                    if victim_shards() >= max(1, shards_before // 2):
                        break
                    await asyncio.sleep(0.1)

            cyc = asyncio.get_running_loop().create_task(cycle())
            rebal_samples, window_s = await run_window(
                lambda: cyc.done())
            await cyc
            moved = sum(o.perf.get("rebalance_bytes_moved")
                        for o in cluster.osds.values()) - moved0
            converged = drained["ok"] and victim_shards() > 0
            for oid, blob in blobs.items():
                assert bytes(await c.get(pool, oid)) == blob

            classed = {
                cls: sum(o.sched_perf.get(f"enqueue_{cls}")
                         for o in cluster.osds.values())
                for cls in ("rebalance", "recovery", "scrub")}
            await gold.stop()
            await c.stop()
            return (solo_samples, rebal_samples, window_s, moved,
                    converged, classed)
        finally:
            await cluster.stop()

    (solo_samples, rebal_samples, window_s, moved, converged,
     classed) = asyncio.run(go())

    def p99_ms(samples):
        if not samples:
            return 0.0
        return round(float(np.percentile(np.array(samples), 99)) * 1e3, 2)

    solo_p99 = p99_ms(solo_samples)
    rebal_p99 = p99_ms(rebal_samples)
    print(json.dumps({
        "rebalance_MBps_moved": round(moved / max(window_s, 1e-9) / 1e6, 2),
        "rebalance_bytes_moved": int(moved),
        "rebalance_window_s": round(window_s, 2),
        "rebalance_converged": bool(converged),
        "client_get_p99_ms_solo": solo_p99,
        "client_get_p99_ms_during_rebalance": rebal_p99,
        "rebalance_p99_impact": round(rebal_p99 / solo_p99, 2)
        if solo_p99 else 0.0,
        "rebalance_sched_classes": classed,
    }))
    return 0 if converged else 1


def macro_bench() -> int:
    """Multi-tenant macro traffic arm (bench.py --macro): thousands of
    simulated tenants over a handful of client processes drive zipfian
    mixed-phase traffic (write-heavy / read-heavy / degraded-read under
    a downed OSD / repair-concurrent — the arXiv:1709.05365 workload
    shape) at a TCP cluster running the mClock scheduler with per-client
    dmClock QoS.  Emits per-tenant-class end-to-end op percentiles per
    phase, the OSDs' per-class op-phase p50/p99/p999 (the optracker
    cls:<name>|<phase> rings), the aggregated `osd_scheduler` snapshot,
    and the ISOLATION EXPERIMENT: the reserved class's solo-run get p99
    vs its p99 with a noisy neighbor offering ~10x its limit — the
    flooder must be the one backoff-shed, the reserved tenant must see
    zero acked-op failures and a bounded p99."""
    import asyncio

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from ceph_tpu.rados.vstart import Cluster
    from ceph_tpu.tools.traffic import (TenantClass, TrafficHarness,
                                        merge_osd_class_phases)

    phase_secs = float(os.environ.get("MACRO_PHASE_SECS", "2.0"))
    flood_limit = 40.0

    async def go():
        cluster = Cluster(n_osds=4, conf={
            "osd_auto_repair": False,
            "ms_local_fastpath": False,
            "osd_op_queue": "mclock",
            "osd_backoff_queue_depth": 6,
            "osd_qos_shed_grace": 0.05,
            "osd_backoff_secs": 0.5,
            "client_op_timeout": 30.0,
            "client_op_deadline": 90.0})
        await cluster.start()
        try:
            c0 = await cluster.client()
            pool = await c0.create_pool("macro", profile={
                "plugin": "jerasure", "technique": "reed_sol_van",
                "k": "2", "m": "1"})
            # mon-validated per-pool QoS profiles, osdmap-distributed:
            # gold is the reserved class, flood is capped hard; the
            # pool-wide defaults cover the anonymous bulk tenants
            await c0.pool_set(pool, "qos_reservation", "100")
            await c0.pool_set(pool, "qos_weight", "10")
            await c0.pool_set(pool, "qos_class:gold", "150:20:0")
            await c0.pool_set(pool, "qos_class:flood",
                              f"0:1:{flood_limit:g}")
            # one client PROCESS per tenant class: a backoff aimed at
            # the flooding class parks its connection, not its neighbors
            c_gold, c_bulk = [await cluster.client() for _ in range(2)]
            # the flooding class runs with a SHORT op deadline: an
            # over-limit tenant seeing timeouts while shed is the honest
            # outcome, and it bounds every phase's straggler tail
            from ceph_tpu.rados.client import RadosClient

            fconf = dict(cluster.conf)
            fconf["client_op_deadline"] = 5.0
            c_flood = RadosClient(cluster.mon_addrs, fconf)
            await c_flood.start()
            await c_flood.refresh_map()
            gold = TenantClass("gold", c_gold, tenants=300, workers=4,
                              rate=60.0)
            bulk = TenantClass("", c_bulk, tenants=1000, workers=4,
                              rate=80.0)
            flood = TenantClass("flood", c_flood, tenants=2, workers=64,
                                rate=0.0)  # unpaced: offers >> limit
            h = TrafficHarness([gold, bulk, flood], pool,
                               n_objects=48, obj_size=32 << 10)
            await h.preload()
            for o in cluster.osds.values():
                o.ctx.op_tracker.clear_samples()

            # -- isolation experiment (healthy cluster) ----------------
            solo = await h.run_phase("solo", phase_secs, 0.2,
                                     classes=[gold])
            shed0 = sum(o.sched_perf.get("qos_shed")
                        for o in cluster.osds.values())
            contended = await h.run_phase("contended", phase_secs, 0.2,
                                          classes=[gold, flood])
            sheds = sum(o.sched_perf.get("qos_shed")
                        for o in cluster.osds.values()) - shed0
            flood_backoffs = c_flood.perf.get("backoffs_received")
            gold_backoffs = c_gold.perf.get("backoffs_received")

            # -- mixed phases ------------------------------------------
            phases = {}
            phases["write_heavy"] = (await h.run_phase(
                "write_heavy", phase_secs, 0.8)).summary()
            phases["read_heavy"] = (await h.run_phase(
                "read_heavy", phase_secs, 0.2)).summary()
            # snapshot BEFORE the kill: kill_osd pops the victim from
            # cluster.osds, but its trackers still hold the first four
            # phases' samples — the report must aggregate all 4 daemons
            all_osds = list(cluster.osds.values())
            victim = sorted(cluster.osds)[-1]
            await cluster.kill_osd(victim)
            await c0.mark_osd_down(victim)
            for c in (c_gold, c_bulk, c_flood):
                await c.refresh_map()
            phases["degraded_read"] = (await h.run_phase(
                "degraded_read", phase_secs, 0.1)).summary()
            repair_task = asyncio.get_running_loop().create_task(
                c0.repair_pool(pool))
            phases["repair_concurrent"] = (await h.run_phase(
                "repair_concurrent", phase_secs, 0.3)).summary()
            try:
                await asyncio.wait_for(repair_task, timeout=30)
            except asyncio.TimeoutError:
                repair_task.cancel()

            osd_phase_pcts = merge_osd_class_phases(all_osds)
            sched = {}
            for o in all_osds:
                for k, v in o.sched_perf.dump().items():
                    if isinstance(v, int):
                        sched[k] = sched.get(k, 0) + v
            solo_s, cont_s = solo.summary(), contended.summary()
            solo_p99 = solo_s.get("gold", {}).get("get", {}).get(
                "p99_us", 0.0)
            cont_p99 = cont_s.get("gold", {}).get("get", {}).get(
                "p99_us", 0.0)
            flood_ops = cont_s.get("flood", {}).get("ops", 0)
            # served = COMPLETED ops only (the per-kind sample counts
            # exclude failures; "ops" counts attempts incl. timeouts)
            flood_done = sum(
                v.get("count", 0)
                for v in cont_s.get("flood", {}).values()
                if isinstance(v, dict))
            served = flood_done / max(contended.seconds, 1e-9)
            # attempts = tries + shed drops: the flooder's offered
            # pressure (64 unpaced workers; parks suppress it)
            attempted = (flood_ops + flood_backoffs) \
                / max(contended.seconds, 1e-9)
            isolation = {
                "solo_get_p99_us": solo_p99,
                "contended_get_p99_us": cont_p99,
                "p99_ratio": round(cont_p99 / solo_p99, 2)
                if solo_p99 else 0.0,
                "reserved_failures":
                    cont_s.get("gold", {}).get("failures", 0)
                    + solo_s.get("gold", {}).get("failures", 0),
                "flooder_limit_ops_sec": flood_limit,
                "flooder_workers": flood.workers,
                "flooder_attempted_ops_sec": round(attempted, 1),
                "flooder_served_ops_sec": round(served, 1),
                "flooder_served_vs_limit": round(served / flood_limit, 2),
                "qos_sheds": sheds,
                "flooder_backoffs_received": flood_backoffs,
                "reserved_backoffs_received": gold_backoffs,
                "isolation_ok": bool(
                    sheds > 0 and flood_backoffs > 0
                    and cont_s.get("gold", {}).get("failures", 0) == 0
                    and solo_p99 and cont_p99 <= 2.0 * solo_p99),
            }
            total_tenants = sum(
                tc.tenants for tc in (gold, bulk, flood))
            for c in (c0, c_gold, c_bulk, c_flood):
                await c.stop()
            return (total_tenants, phases, osd_phase_pcts, sched,
                    isolation, solo_s, cont_s)
        finally:
            await cluster.stop()

    (tenants, phases, osd_pcts, sched, isolation,
     solo_s, cont_s) = asyncio.run(go())
    print(json.dumps({
        # per-tenant-class end-to-end percentiles per traffic phase
        # (client-side), plus the OSDs' per-class op-phase tails from
        # the optracker rings — the numbers QoS regressions move
        "macro_tenants": tenants,
        "macro_phases": phases,
        "macro_isolation_phases": {"solo": solo_s, "contended": cont_s},
        "macro_osd_phase_percentiles": osd_pcts,
        "macro_scheduler_perf": sched,
        "qos_isolation": isolation}))
    return 0


def onhost_overlap_bench() -> int:
    """Serial vs pipelined batching-queue rounds on the CPU backend: the
    double-buffer mechanism measured on its own.  Serial
    awaits each round before submitting the next (no standing backlog,
    overlap never engages); pipelined pumps the whole stream so the
    worker overlaps round N+1's staging with round N's completion."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import numpy as _np

    from ceph_tpu.ec.matrices import (matrix_to_bitmatrix,
                                      vandermonde_coding_matrix)
    from ceph_tpu.parallel.service import BatchingQueue

    bm8 = matrix_to_bitmatrix(
        vandermonde_coding_matrix(K, M, W), W).astype(_np.int8)
    # BUDGET-sized rounds (16 MiB = BatchingQueue.max_pending_bytes):
    # both arms then dispatch identical shapes immediately — a smaller
    # round would make the serial arm pay the coalescing window and a
    # different jit shape, conflating batching with the overlap
    # mechanism under test
    B = (1 << 20) // K * 16
    rng = _np.random.default_rng(3)
    rounds = 4
    stream = [rng.integers(0, 256, size=(K, B), dtype=_np.uint8)
              for _ in range(rounds)]
    q = BatchingQueue(max_delay=0.005)
    try:
        # warm BOTH paths untimed: the pipelined backlog coalesces
        # rounds into larger dispatch shapes than the serial path, and
        # a first-touch jit compile inside the timed window would be
        # measured as a 5x "mechanism cost" (the r5 debugging note)
        q.submit(bm8, stream[0], W, M).result(timeout=300)
        for f in [q.submit(bm8, s, W, M) for s in stream]:
            f.result(timeout=300)
        # serial: each round completes before the next is submitted
        t0 = time.perf_counter()
        for s in stream:
            q.submit(bm8, s, W, M).result(timeout=300)
        serial_dt = time.perf_counter() - t0
        # pipelined: standing backlog, worker double-buffers rounds
        ov0 = q.overlapped_rounds
        t0 = time.perf_counter()
        futs = [q.submit(bm8, s, W, M) for s in stream]
        for f in futs:
            f.result(timeout=300)
        pipe_dt = time.perf_counter() - t0
        overlapped = q.overlapped_rounds - ov0
    finally:
        q.close()
    total = rounds * K * B
    print(json.dumps({
        "serial_GBps": round(total / serial_dt / 1e9, 3),
        "pipelined_GBps": round(total / pipe_dt / 1e9, 3),
        "overlapped_rounds": overlapped,
        "cpu_count": os.cpu_count()}))
    return 0


if __name__ == "__main__":
    if "--daemon-path" in sys.argv:
        sys.exit(daemon_path_bench())
    if "--lanes-sweep" in sys.argv:
        sys.exit(lanes_sweep_bench())
    if "--msgr-stream" in sys.argv:
        sys.exit(msgr_stream_bench())
    if "--hot-read" in sys.argv:
        sys.exit(hot_read_bench())
    if "--e2e-device" in sys.argv:
        sys.exit(e2e_device_bench())
    if "--tier-mixed" in sys.argv:
        sys.exit(tier_mixed_bench())
    if "--rebalance" in sys.argv:
        sys.exit(rebalance_bench())
    if "--macro" in sys.argv:
        sys.exit(macro_bench())
    if "--onhost-overlap" in sys.argv:
        sys.exit(onhost_overlap_bench())
    sys.exit(main())
