"""plugin=tpu — the flagship backend: GF(2^8) Reed-Solomon on the TPU MXU.

Registered through the same registry as every other plugin (the north-star
seam, BASELINE.json): profiles say ``plugin=tpu technique=reed_sol_van k=8
m=3`` and the codec produces chunks byte-identical to the jerasure-equivalent
CPU codec — same matrices, same padding/alignment rules (it *subclasses* the
jerasure technique classes, so get_chunk_size et al. are literally shared) —
while encode/decode/recovery run as one bit-plane GF(2) product on the device
(ceph_tpu/ops/gf2.py).

Failure semantics: the device is a new failure domain the in-process dlopen
model never had (SURVEY.md §7 hard part 5).  Every dispatch falls back to
the inherited CPU path on any JAX error, so EC I/O never wedges on a sick
accelerator; the fallback flips a flag once, logs the exception with its
traceback (a compile refusal at start-up must not pass for a working
device) and ticks `ec_plugin.device_failed`.

Batching: column counts are bucketed to powers of two (min 1024) to bound
XLA recompilation; full cross-object stripe batching lives in
ceph_tpu.parallel.service.BatchingQueue, which concatenates many
encode_chunks calls into one device dispatch.

Where the seams below run: the SERVED path (OSD writes, degraded reads,
recovery) of every technique here plans through rados/ecutil.py onto the
queue's lanes — byte-layout codes on "packedbit", the packet-layout five
(cauchy_orig, cauchy_good, liberation, blaum_roth, liber8tion) on
"packetrows" — and never calls `_apply`/`_apply_rows`.  The seams are the
direct path: the benchmark CLI, the corpus tool, a daemon without a queue
(CPU backend), mapped or ragged shapes no lane takes.  They dispatch on
the caller's thread and tick `ec_plugin.apply`/`apply_rows`; the
benchmark's `direct_dispatch_per_op.put` holds both at 0 per served op.
"""

from __future__ import annotations

import errno
import logging
from typing import Dict

import numpy as np

from ceph_tpu import PLUGIN_ABI_VERSION
from ceph_tpu.ec.interface import ErasureCodeError, ErasureCodeProfile
from ceph_tpu.ec.matrices import matrix_to_bitmatrix
from ceph_tpu.ec.plugins.jerasure import (
    BlaumRoth,
    CauchyGood,
    CauchyOrig,
    Liber8tion,
    Liberation,
    ReedSolomonR6Op,
    ReedSolomonVandermonde,
)
from ceph_tpu.common.perf_counters import PerfCountersBuilder
from ceph_tpu.ec.registry import ErasureCodePlugin

log = logging.getLogger("ceph_tpu.ec.tpu")

# The `ec_plugin` counter set: the NON-queue dispatch path (direct codec
# calls through the _apply/_apply_rows seams — benchmark CLI, per-stripe
# paths, recovery helpers).  Process-global like the codec classes;
# daemons add it next to `ec_tpu`/`gf2_sched`.  COUNTER SCHEMA:
#   apply / apply_rows        u64         device dispatches per seam
#   apply_s / apply_rows_s    longrunavg  device seconds per dispatch
#                                         (includes first-call compiles)
#   cpu_fallback              u64         seam calls served by the CPU
#                                         oracle (device off/sick)
#   device_failed             u64         dispatch exceptions that flipped
#                                         a codec to its CPU fallback
PLUGIN_PERF = (
    PerfCountersBuilder("ec_plugin")
    .add_u64_counter("apply", "byte-layout seam device dispatches")
    .add_u64_counter("apply_rows", "packet-layout seam device dispatches")
    .add_time_avg("apply_s", "byte-layout seam device seconds")
    .add_time_avg("apply_rows_s", "packet-layout seam device seconds")
    .add_u64_counter("cpu_fallback", "seam calls served by the CPU path")
    .add_u64_counter("device_failed",
                     "dispatch exceptions flipping a codec to CPU")
    .create_perf_counters())


class _TpuDispatch:
    """Mixin overriding the codec compute seams with device dispatches."""

    plugin_name = "tpu"

    def _device_ok(self) -> bool:
        if getattr(self, "_tpu_failed", False):
            return False
        from ceph_tpu.utils.jaxdev import backend_available

        # hang-proof: if backend init wedged, the probe pins "unavailable"
        # (and logs it) and every dispatch takes the CPU path — a codec
        # must return, never hang (registry contract)
        return backend_available()

    def _mark_failed(self, exc: Exception) -> None:
        if not getattr(self, "_tpu_failed", False):
            log.error("tpu dispatch failed; this codec serves from the CPU "
                      "from here on", exc_info=exc)
        PLUGIN_PERF.inc("device_failed")
        self._tpu_failed = True

    def _bm_cache(self) -> Dict[bytes, np.ndarray]:
        cache = getattr(self, "_bitmatrix_cache", None)
        if cache is None:
            cache = self._bitmatrix_cache = {}
        return cache

    # seam override: GF(2^w) matrix applied to symbol regions
    def _apply(self, matrix: np.ndarray, regions: np.ndarray) -> np.ndarray:
        if not self._device_ok():
            PLUGIN_PERF.inc("cpu_fallback")
            return super()._apply(matrix, regions)
        try:
            from ceph_tpu.ops.gf2 import bucket_columns as _bucket
            from ceph_tpu.ops.gf2 import gf2_apply_bytes, gf2_apply_packedbit
            from ceph_tpu.rados.ecutil import lane_for

            cache = self._bm_cache()
            key = matrix.tobytes()
            bm = cache.get(key)
            if bm is None:
                bm = cache[key] = matrix_to_bitmatrix(matrix, self.w)
            rows, B = regions.shape
            out_rows = matrix.shape[0]
            padded = _bucket(B)
            buf = regions
            if padded != B:
                buf = np.zeros((rows, padded), dtype=np.uint8)
                buf[:, :B] = regions
            # the same program the queue's lane of this codec would run
            with PLUGIN_PERF.time_avg("apply_s"):
                if lane_for(self)[0] == "packedbit":
                    # one fused static-XOR-schedule call, compiled per
                    # matrix behind the gf2 LRU — encode generators AND
                    # decode signature matrices alike (pow2 bucketing
                    # keeps B a whole number of u32 words)
                    out = gf2_apply_packedbit(bm, buf)
                else:
                    out = gf2_apply_bytes(bm, buf, self.w, out_rows)
                out = np.asarray(out)
            PLUGIN_PERF.inc("apply")
            return out[:, :B]
        except Exception as e:  # any device/compile failure -> CPU fallback
            self._mark_failed(e)
            return super()._apply(matrix, regions)

    # seam override: GF(2) bit-matrix applied to packet rows
    def _apply_rows(self, bm: np.ndarray, rows: np.ndarray) -> np.ndarray:
        if not self._device_ok():
            PLUGIN_PERF.inc("cpu_fallback")
            return super()._apply_rows(bm, rows)
        try:
            from ceph_tpu.ops.gf2 import bucket_columns as _bucket
            from ceph_tpu.ops.gf2 import gf2_xor_packed

            # a packet-row combine IS a GF(2) XOR of whole rows, so the
            # static XOR schedule applies DIRECTLY to the packet bytes —
            # the "packetrows" lane's middle stage, no bit expansion at
            # all (this is jerasure_schedule_encode's shape, compiled by
            # XLA)
            R, nb, p = rows.shape
            flat = np.ascontiguousarray(rows.reshape(R, nb * p))
            padded = _bucket(flat.shape[1])
            if padded != flat.shape[1]:
                buf = np.zeros((R, padded), dtype=np.uint8)
                buf[:, :flat.shape[1]] = flat
                flat = buf
            with PLUGIN_PERF.time_avg("apply_rows_s"):
                out = np.asarray(gf2_xor_packed(
                    np.asarray(bm, dtype=np.uint8), flat))
            PLUGIN_PERF.inc("apply_rows")
            return out[:, :nb * p].reshape(bm.shape[0], nb, p)
        except Exception as e:
            self._mark_failed(e)
            return super()._apply_rows(bm, rows)


class TpuReedSolomonVandermonde(_TpuDispatch, ReedSolomonVandermonde):
    pass


class TpuReedSolomonR6Op(_TpuDispatch, ReedSolomonR6Op):
    pass


class TpuCauchyOrig(_TpuDispatch, CauchyOrig):
    pass


class TpuCauchyGood(_TpuDispatch, CauchyGood):
    pass


class TpuLiberation(_TpuDispatch, Liberation):
    pass


class TpuBlaumRoth(_TpuDispatch, BlaumRoth):
    pass


class TpuLiber8tion(_TpuDispatch, Liber8tion):
    pass


TECHNIQUES = {
    "reed_sol_van": TpuReedSolomonVandermonde,
    "reed_sol_r6_op": TpuReedSolomonR6Op,
    "cauchy_orig": TpuCauchyOrig,
    "cauchy_good": TpuCauchyGood,
    "liberation": TpuLiberation,
    "blaum_roth": TpuBlaumRoth,
    "liber8tion": TpuLiber8tion,
}


class TpuPlugin(ErasureCodePlugin):
    def factory(self, profile: ErasureCodeProfile):
        technique = profile.get("technique", "reed_sol_van")
        cls = TECHNIQUES.get(technique)
        if cls is None:
            raise ErasureCodeError(
                -errno.ENOENT,
                f"technique={technique} is not a valid tpu technique "
                f"(have {sorted(TECHNIQUES)})",
            )
        codec = cls()
        codec.init(dict(profile, technique=technique))
        return codec


def __erasure_code_version__() -> str:
    return PLUGIN_ABI_VERSION


def __erasure_code_init__(name: str, registry) -> int:
    registry.add(name, TpuPlugin())
    return 0
