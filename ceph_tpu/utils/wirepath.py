"""Process-wide wirepath resolver: the native messenger hot loop
(native/wirepath.cc via the ctypes bridge) when the native layer builds,
the pure-Python arm otherwise.

Frame crc, fragment memcpy and writev segment assembly in Python cost
the one loop an interpreter iteration a segment.  The native wirepath
batches that per-byte work into single foreign calls — ctypes drops the
GIL around them — so a flush window's writev, a burst's crc verify, and
a striped blob's scatter each cost ONE released-GIL call instead of N
interpreter iterations (checksum.py's discipline, applied to the whole
wire loop).

Resolution mirrors utils/checksum.py: probe once per process, fall back
silently (hosts without a C++ toolchain run the full suite on the
python arm), and expose ``kind()`` so BENCH records and /metrics report
which arm actually ran.  ``CEPH_TPU_WIREPATH=0`` forces the python arm
process-wide (the CI parity knob); the per-messenger config option
``ms_wirepath_native`` gates it per daemon.

The native arm only engages when the process checksum resolver is
crc32c (checksum.checksum_kind() == "crc32c"): the wirepath's crc
entry points compute crc32c, and a zlib-resolved host must keep
byte-identical zlib frames.  In practice the two resolve together —
they live in the same .so.
"""

from __future__ import annotations

import os
from typing import Optional

_IMPL = None  # the bridge module when native resolved
_KIND: Optional[str] = None


def _resolve() -> None:
    global _IMPL, _KIND
    if os.environ.get("CEPH_TPU_WIREPATH", "") == "0":
        _IMPL, _KIND = None, "python"
        return
    try:
        from ceph_tpu.utils import checksum

        if checksum.checksum_kind() != "crc32c":
            _IMPL, _KIND = None, "python"
            return
        from ceph_tpu.native import bridge

        # probe every entry point against the python arm once: a stale
        # or miscompiled .so must degrade to python, never ship bytes
        if bridge.wirepath_kind() != "native":
            raise RuntimeError("wirepath symbols missing")
        probe = b"wirepath-probe-0123456789abcdef" * 8
        want = bridge.crc32c(probe)
        if bridge.wire_crc_batch([[probe[:31], probe[31:]]]) != [want]:
            raise RuntimeError("wire_crc_batch mismatch")
        out = bytearray(len(probe))
        if bridge.wire_gather([probe[:7], probe[7:]], out) != len(probe) \
                or bytes(out) != probe:
            raise RuntimeError("wire_gather mismatch")
        dst = bytearray(len(probe))
        if bridge.wire_copy_crc32c(probe, dst) != want \
                or bytes(dst) != probe:
            raise RuntimeError("wire_copy_crc32c mismatch")
        rc, _bad = bridge.wire_scatter(
            [probe[16:], probe[:16]], [16, 0], dst,
            want_crcs=[bridge.crc32c(probe[16:]),
                       bridge.crc32c(probe[:16])])
        if rc != 2 or bytes(dst) != probe:
            raise RuntimeError("wire_scatter mismatch")
        if bridge.wire_verify_regions(
                probe, [0, 16], [16, len(probe) - 16],
                [bridge.crc32c(probe[:16]),
                 bridge.crc32c(probe[16:])]) != -1:
            raise RuntimeError("wire_verify_regions mismatch")
        if bridge.wirepath_selftest() != 0:
            raise RuntimeError("wirepath selftest failed")
        # the PyDLL shim is REQUIRED for the native arm: the tx hot
        # loop's segment-list parsing lives there (hosts with g++ but
        # no Python headers run the python arm — one arm per process,
        # never a half-native mix)
        if not bridge.has_wirepy():
            raise RuntimeError("wirepy shim unavailable")
        if bridge.wirepy_crc_chain([probe[:5], probe[5:]]) != want:
            raise RuntimeError("wirepy_crc_chain mismatch")
        out2 = bytearray(len(probe))
        if bridge.wirepy_gather([probe[:9], probe[9:]], out2) \
                != len(probe) or bytes(out2) != probe:
            raise RuntimeError("wirepy_gather mismatch")
        if bridge.wirepy_verify_regions(
                probe, [0, 16], [16, len(probe) - 16],
                [bridge.crc32c(probe[:16]),
                 bridge.crc32c(probe[16:])]) != -1:
            raise RuntimeError("wirepy_verify_regions mismatch")
        d1, d2 = bytearray(16), bytearray(len(probe) - 16)
        if bridge.wirepy_scatter_from(probe, [16, 0], [d2, d1]) \
                != len(probe) or bytes(d1) != probe[:16] \
                or bytes(d2) != probe[16:]:
            raise RuntimeError("wirepy_scatter_from mismatch")
        _IMPL, _KIND = bridge, "native"
    except Exception:
        import logging

        logging.getLogger("ceph_tpu.wirepath").warning(
            "native wirepath unavailable; messenger runs the python arm")
        _IMPL, _KIND = None, "python"


def impl():
    """The bridge module when the native wirepath resolved, else None —
    messengers branch on this once per connection, never per byte.
    First call may BUILD the native library (seconds of g++): daemons
    resolve at construction, like checksum_kind()."""
    if _KIND is None:
        _resolve()
    return _IMPL


def kind() -> str:
    """"native" | "python" — the arm this process resolved (BENCH's
    ``wirepath_kind``, checksum.checksum_kind's sibling)."""
    if _KIND is None:
        _resolve()
    return _KIND  # type: ignore[return-value]


def _reset_for_tests() -> None:
    """Drop the cached resolution so tests can exercise the
    CEPH_TPU_WIREPATH knob without a subprocess."""
    global _IMPL, _KIND
    _IMPL, _KIND = None, None
