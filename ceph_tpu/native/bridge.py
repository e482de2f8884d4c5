"""ctypes bridge to libceph_tpu_ec.so.

Loads the native core built from native/ (cmake+ninja or the build()
helper below compiles it on demand with g++).  Used by tests to assert the
native GF/RS core is byte-identical to the numpy oracle, and available as
a fast CPU fallback for the tpu plugin."""

from __future__ import annotations

import ctypes
import os
import subprocess
from typing import List, Optional, Sequence

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_NATIVE = os.path.join(_REPO, "native")
_BUILD = os.path.join(_NATIVE, "build")
_LIB = os.path.join(_BUILD, "libceph_tpu_ec.so")

_lib: Optional[ctypes.CDLL] = None

# the tree compiles warning-clean and must stay that way (CMake enforces
# the same set via CEPH_TPU_WERROR, ON by default).  The env
# CEPH_TPU_NATIVE_WERROR=0 drops -Werror only — the escape hatch for a
# future compiler whose new warning class would otherwise brick lib()'s
# on-demand build (CMake users have -DCEPH_TPU_WERROR=OFF).
WARN_FLAGS = ["-Wall", "-Wextra"] + (
    ["-Werror"] if os.environ.get("CEPH_TPU_NATIVE_WERROR") != "0" else [])

# ASan/UBSan build flavor (CMake: -DCEPH_TPU_SANITIZE=ON, or the env
# CEPH_TPU_NATIVE_SANITIZE=1; tests/test_native.py's slow sanitize leg
# reuses exactly this flag set).  UBSan is -fno-sanitize-recover so the
# first finding aborts the process instead of scrolling past in a log.
SANITIZE_FLAGS = ["-fsanitize=address,undefined",
                  "-fno-sanitize-recover=all",
                  "-fno-omit-frame-pointer", "-g"]

_LIB_SRCS = ("gf256.cc", "rs.cc", "registry.cc", "capi.cc", "crc32c.cc",
             "wirepath.cc")


def _host_fingerprint() -> str:
    """What `-march=native` resolved against: the CPU's feature flags.  A
    .so built on another CPU (a copied tree, a shared volume) may use
    instructions this one traps on (SIGILL, not an exception), so the
    build is stamped with this and rebuilt on a mismatch."""
    import hashlib
    import platform

    flags = platform.machine() + platform.processor()
    try:
        with open("/proc/cpuinfo") as f:
            flags += next((ln for ln in f if ln.startswith("flags")), "")
    except OSError:
        pass
    return hashlib.sha1(flags.encode()).hexdigest()


def _up_to_date(out: str, deps) -> bool:
    """`out` exists, is newer than every dep, and was built on this CPU."""
    try:
        with open(out + ".host") as f:
            if f.read() != _host_fingerprint():
                return False
        lib_mtime = os.path.getmtime(out)
    except OSError:
        return False
    return all(os.path.getmtime(d) <= lib_mtime
               for d in deps if os.path.exists(d))


def _stamp(out: str) -> None:
    with open(out + ".host", "w") as f:
        f.write(_host_fingerprint())


def build(force: bool = False, sanitize: Optional[bool] = None) -> str:
    """Compile the native library (idempotent; rebuilds when any source
    is newer than the .so, so an old build can never miss symbols the
    bridge expects).

    ``sanitize`` (default: the CEPH_TPU_NATIVE_SANITIZE=1 env) emits an
    ASan/UBSan flavor into build/sanitize/ — a SEPARATE artifact,
    because an asan .so cannot be dlopen'd into a plain python process
    (the asan runtime must be first in the initial library list);
    ``lib()`` below only ever loads the plain build.
    """
    if sanitize is None:
        sanitize = os.environ.get("CEPH_TPU_NATIVE_SANITIZE") == "1"
    srcs = [os.path.join(_NATIVE, f) for f in _LIB_SRCS]
    out = os.path.join(_BUILD, "sanitize", "libceph_tpu_ec.so") \
        if sanitize else _LIB
    hdrs = [os.path.join(_NATIVE, f)
            for f in ("gf256.h", "rs.h", "ec_api.h", "plugin_common.h",
                      "wirepath.h")]
    if not force and _up_to_date(out, srcs + hdrs):
        return out
    os.makedirs(os.path.dirname(out), exist_ok=True)
    cmd = [
        "g++", "-std=c++17", "-O3", "-march=native", "-fPIC", "-shared",
        *WARN_FLAGS, *(SANITIZE_FLAGS if sanitize else []),
        "-o", out, *srcs, "-ldl", "-pthread",
    ]
    try:
        subprocess.run(cmd, check=True, capture_output=True)
    except subprocess.CalledProcessError as e:
        # surface the compiler diagnostics (capture_output would swallow
        # them) and the -Werror escape hatch
        raise RuntimeError(
            f"native build failed (rc {e.returncode}); if these are "
            f"warnings from a newer compiler, set "
            f"CEPH_TPU_NATIVE_WERROR=0:\n"
            f"{(e.stderr or b'').decode(errors='replace')}") from e
    _stamp(out)
    return out


def lib() -> ctypes.CDLL:
    global _lib
    if _lib is None:
        # configure on a LOCAL before publishing: a failure mid-setup
        # (e.g. a stale .so missing a symbol) must not leave a
        # half-configured CDLL behind for the next caller.  Always the
        # plain flavor — see build() on why sanitize cannot load here.
        _local = ctypes.CDLL(build(sanitize=False))
        try:
            _configure(_local)
        except AttributeError:
            _local = ctypes.CDLL(build(force=True, sanitize=False))
            _configure(_local)
        _lib = _local
    return _lib


def _configure(_lib: ctypes.CDLL) -> None:
    """Declare every exported symbol's signature; raises AttributeError
    when the loaded .so predates a symbol (caller rebuilds)."""
    _lib.ceph_tpu_gf_mul.restype = ctypes.c_uint8
    _lib.ceph_tpu_gf_mul.argtypes = [ctypes.c_uint8, ctypes.c_uint8]
    _lib.ceph_tpu_rs_encode.restype = ctypes.c_int
    _lib.ceph_tpu_rs_encode.argtypes = [
        ctypes.c_char_p, ctypes.c_int, ctypes.c_int,
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_size_t,
    ]
    _lib.ceph_tpu_simd_kind.restype = ctypes.c_char_p
    _lib.ceph_tpu_simd_kind.argtypes = []
    _lib.ceph_tpu_gf_apply.restype = ctypes.c_int
    _lib.ceph_tpu_gf_apply.argtypes = [
        ctypes.c_char_p, ctypes.c_int, ctypes.c_int,
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_size_t,
    ]
    _lib.ceph_tpu_rs_encode_mt.restype = ctypes.c_int
    _lib.ceph_tpu_rs_encode_mt.argtypes = [
        ctypes.c_char_p, ctypes.c_int, ctypes.c_int,
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_size_t,
        ctypes.c_int,
    ]
    _lib.ceph_tpu_crc32c.restype = ctypes.c_uint32
    _lib.ceph_tpu_crc32c.argtypes = [
        ctypes.c_uint32, ctypes.c_char_p, ctypes.c_size_t]
    _lib.ceph_tpu_crc32c_shift.restype = ctypes.c_uint32
    _lib.ceph_tpu_crc32c_shift.argtypes = [ctypes.c_uint32, ctypes.c_size_t]
    _lib.ceph_tpu_crc32c_kind.restype = ctypes.c_char_p
    _lib.ceph_tpu_crc32c_kind.argtypes = []
    _lib.ceph_tpu_rs_decode.restype = ctypes.c_int
    _lib.ceph_tpu_rs_decode.argtypes = [
        ctypes.c_char_p, ctypes.c_int, ctypes.c_int,
        ctypes.POINTER(ctypes.c_int), ctypes.c_char_p,
        ctypes.c_int, ctypes.POINTER(ctypes.c_int),
        ctypes.c_char_p, ctypes.c_size_t,
    ]
    # -- wirepath (native/wirepath.h): the messenger hot loop ------------
    _pp = ctypes.POINTER(ctypes.c_void_p)
    _sp = ctypes.POINTER(ctypes.c_size_t)
    _ip = ctypes.POINTER(ctypes.c_int32)
    _up = ctypes.POINTER(ctypes.c_uint32)
    _lib.ceph_tpu_wirepath_kind.restype = ctypes.c_char_p
    _lib.ceph_tpu_wirepath_kind.argtypes = []
    _lib.ceph_tpu_wire_crc_batch.restype = ctypes.c_int32
    _lib.ceph_tpu_wire_crc_batch.argtypes = [
        _pp, _sp, ctypes.c_int32, _ip, ctypes.c_int32, _up, _up]
    _lib.ceph_tpu_wire_gather.restype = ctypes.c_int64
    _lib.ceph_tpu_wire_gather.argtypes = [
        _pp, _sp, ctypes.c_int32, ctypes.c_char_p, ctypes.c_size_t]
    _lib.ceph_tpu_wire_copy_crc32c.restype = ctypes.c_uint32
    _lib.ceph_tpu_wire_copy_crc32c.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_size_t, ctypes.c_uint32]
    _lib.ceph_tpu_wire_writev.restype = ctypes.c_int64
    _lib.ceph_tpu_wire_writev.argtypes = [
        ctypes.c_int, _pp, _sp, ctypes.c_int32, ctypes.c_size_t]
    _lib.ceph_tpu_wire_scatter.restype = ctypes.c_int32
    _lib.ceph_tpu_wire_scatter.argtypes = [
        _pp, _sp, ctypes.c_int32, ctypes.POINTER(ctypes.c_int64),
        ctypes.c_void_p, ctypes.c_size_t, _up, ctypes.c_int32, _ip]
    _lib.ceph_tpu_wire_verify_regions.restype = ctypes.c_int32
    _lib.ceph_tpu_wire_verify_regions.argtypes = [
        ctypes.c_void_p, ctypes.c_size_t,
        ctypes.POINTER(ctypes.c_int64), _sp, _up, ctypes.c_int32]
    _lib.ceph_tpu_wirepath_selftest.restype = ctypes.c_int32
    _lib.ceph_tpu_wirepath_selftest.argtypes = []
    # the off-loop sender (native/wirepath.h)
    _u64p = ctypes.POINTER(ctypes.c_uint64)
    _lib.ceph_tpu_wire_sender_submit.restype = ctypes.c_int32
    _lib.ceph_tpu_wire_sender_submit.argtypes = [
        ctypes.c_int, ctypes.c_int, ctypes.c_uint64, _pp, _sp,
        ctypes.c_int32]
    _lib.ceph_tpu_wire_sender_reap.restype = ctypes.c_int32
    _lib.ceph_tpu_wire_sender_reap.argtypes = [
        ctypes.c_int, _u64p, ctypes.POINTER(ctypes.c_int64), _up,
        ctypes.c_int32]
    for _fn in (_lib.ceph_tpu_wire_sender_cancel,
                _lib.ceph_tpu_wire_sender_close_chan):
        _fn.restype = ctypes.c_int32
        _fn.argtypes = [ctypes.c_int]
    _lib.ceph_tpu_wire_sender_stop.restype = ctypes.c_int32
    _lib.ceph_tpu_wire_sender_stop.argtypes = []
    _lib.ceph_tpu_wire_sender_stats.restype = None
    _lib.ceph_tpu_wire_sender_stats.argtypes = [_u64p]


def gf_mul(a: int, b: int) -> int:
    return lib().ceph_tpu_gf_mul(a, b)


def simd_kind() -> str:
    """Which vectorized region kernel the native core dispatched to
    ("gfni" | "avx2" | "scalar") — the bench reports it so the CPU A/B
    ratio is auditable."""
    return lib().ceph_tpu_simd_kind().decode()


def rs_encode(technique: str, data: np.ndarray, m: int) -> np.ndarray:
    """[k, chunk] uint8 -> [m, chunk] parity via the native core."""
    k, chunk = data.shape
    data = np.ascontiguousarray(data, dtype=np.uint8)
    parity = np.zeros((m, chunk), dtype=np.uint8)
    rc = lib().ceph_tpu_rs_encode(
        technique.encode(), k, m,
        data.ctypes.data_as(ctypes.c_char_p),
        parity.ctypes.data_as(ctypes.c_char_p), chunk,
    )
    if rc != 0:
        raise RuntimeError(f"native encode failed ({rc})")
    return parity


def gf_apply(matrix: np.ndarray, data: np.ndarray) -> np.ndarray:
    """out[rows, chunk] = matrix[rows, cols] (x) data[cols, chunk] over
    GF(2^8) with the vectorized region kernels — the codec _apply seam's
    native fast path (any matrix: generator, inverted decode, recovery)."""
    rows, cols = matrix.shape
    k2, chunk = data.shape
    assert cols == k2, (matrix.shape, data.shape)
    matrix = np.ascontiguousarray(matrix, dtype=np.uint8)
    data = np.ascontiguousarray(data, dtype=np.uint8)
    out = np.zeros((rows, chunk), dtype=np.uint8)
    rc = lib().ceph_tpu_gf_apply(
        matrix.ctypes.data_as(ctypes.c_char_p), rows, cols,
        data.ctypes.data_as(ctypes.c_char_p),
        out.ctypes.data_as(ctypes.c_char_p), chunk,
    )
    if rc != 0:
        raise RuntimeError(f"native gf_apply failed ({rc})")
    return out


def rs_encode_mt(technique: str, data: np.ndarray, m: int,
                 nthreads: int = 0) -> tuple:
    """Socket-level encode: every core runs the region kernel on its own
    column range.  Returns (parity, threads_used) — the denominator the
    north star's 'single-socket' clause actually means (a socket is not
    one core)."""
    k, chunk = data.shape
    data = np.ascontiguousarray(data, dtype=np.uint8)
    parity = np.zeros((m, chunk), dtype=np.uint8)
    rc = lib().ceph_tpu_rs_encode_mt(
        technique.encode(), k, m,
        data.ctypes.data_as(ctypes.c_char_p),
        parity.ctypes.data_as(ctypes.c_char_p), chunk, nthreads,
    )
    if rc < 0:
        raise RuntimeError(f"native mt encode failed ({rc})")
    return parity, rc


def rs_decode(
    technique: str, k: int, m: int, sources: Sequence[int],
    source_data: np.ndarray, targets: Sequence[int],
) -> np.ndarray:
    """Reconstruct `targets` chunks from k source chunks [k, chunk]."""
    chunk = source_data.shape[1]
    source_data = np.ascontiguousarray(source_data, dtype=np.uint8)
    out = np.zeros((len(targets), chunk), dtype=np.uint8)
    src = (ctypes.c_int * k)(*sources)
    tgt = (ctypes.c_int * len(targets))(*targets)
    rc = lib().ceph_tpu_rs_decode(
        technique.encode(), k, m, src,
        source_data.ctypes.data_as(ctypes.c_char_p),
        len(targets), tgt,
        out.ctypes.data_as(ctypes.c_char_p), chunk,
    )
    if rc != 0:
        raise RuntimeError(f"native decode failed ({rc})")
    return out


def _buf_arg(data):
    """Zero-copy ctypes argument for any contiguous buffer: bytes pass
    through; bytearray/writable memoryview wrap via from_buffer (a c_char
    array is accepted where c_char_p is declared); anything else copies."""
    if isinstance(data, bytes):
        return data
    if isinstance(data, bytearray):
        return (ctypes.c_char * len(data)).from_buffer(data)
    if isinstance(data, memoryview):
        if not data.contiguous:
            return bytes(data)
        if data.readonly:
            obj = getattr(data, "obj", None)
            if isinstance(obj, bytes) and data.nbytes == len(obj):
                return obj  # whole-bytes view: pass the bytes directly
            return bytes(data)
        return (ctypes.c_char * data.nbytes).from_buffer(data)
    try:
        return _buf_arg(memoryview(data))  # numpy arrays et al.
    except TypeError:
        return bytes(data)


def crc32c(data, seed: int = 0) -> int:
    """Seedable hardware CRC32C (SSE4.2, table fallback) — the native
    checksum behind the messenger frames and BlueStore extents (reference
    src/common/crc32c.cc role)."""
    if isinstance(data, (bytes, bytearray)):
        n = len(data)
    else:
        # nbytes, NOT len(): a 2-D or wider-dtype buffer's len() is its
        # row/element count and would silently checksum a prefix
        n = memoryview(data).nbytes
    return lib().ceph_tpu_crc32c(seed, _buf_arg(data), n)


def crc32c_shift(state: int, n: int) -> int:
    """The raw crc32c register `state` advanced over `n` zero bytes
    (native/crc32c.cc ZerosOp; linear, no inversion on either side)."""
    return lib().ceph_tpu_crc32c_shift(state & 0xFFFFFFFF, n)


def crc32c_kind() -> str:
    return lib().ceph_tpu_crc32c_kind().decode()


# -- wirepath (native/wirepath.h): messenger hot-loop batch calls ------------
# Segment arguments accept bytes / bytearray / contiguous 1-D memoryview /
# numpy arrays.  The CALLER keeps every segment alive across the call (the
# address is of the segment's own buffer — nothing is copied here).


def _seg_addr(s, writable: bool = False) -> tuple:
    """(address, nbytes) of a contiguous byte buffer, zero-copy.  With
    ``writable`` the buffer must be mutable — destinations the C side
    will memcpy into refuse bytes/readonly views HERE, mirroring the
    PyBUF_WRITABLE refusal of the wirepy arm (a readonly dst silently
    corrupted through its raw address is the worst failure mode)."""
    if isinstance(s, bytes):
        if writable:
            raise TypeError("destination buffer is readonly (bytes)")
        if not s:
            return 0, 0
        return ctypes.cast(ctypes.c_char_p(s), ctypes.c_void_p).value, len(s)
    mv = s if isinstance(s, memoryview) else memoryview(s)
    if mv.ndim != 1 or mv.itemsize != 1:
        mv = mv.cast("B")
    if writable and mv.readonly:
        raise TypeError("destination buffer is readonly")
    if not mv.nbytes:
        return 0, 0
    # np.frombuffer wraps readonly AND writable buffers; .ctypes.data is
    # the address of the ORIGINAL memory either way
    return int(np.frombuffer(mv, dtype=np.uint8).ctypes.data), mv.nbytes


def _seg_arrays(segs):
    n = len(segs)
    ptrs = (ctypes.c_void_p * n)()
    lens = (ctypes.c_size_t * n)()
    total = 0
    for i, s in enumerate(segs):
        a, ln = _seg_addr(s)
        ptrs[i] = a
        lens[i] = ln
        total += ln
    return ptrs, lens, total


def wirepath_kind() -> str:
    """"native" when the wirepath symbols loaded — the arm gauge the
    BENCH record and /metrics report (crc32c_kind's sibling)."""
    return lib().ceph_tpu_wirepath_kind().decode()


def wirepath_selftest() -> int:
    """The in-library adversarial geometry battery (0 = clean); also run
    under ASan/UBSan by the slow native test leg."""
    return lib().ceph_tpu_wirepath_selftest()


def wire_crc_batch(groups, seeds=None):
    """Chained crc32c per group of segments, ONE released-GIL call for
    the whole batch: groups is a list of segment lists (a frame's crc
    sections, a flush window's blobs), seeds an optional per-group seed
    list.  Returns the list of crcs."""
    flat: list = []
    starts = (ctypes.c_int32 * (len(groups) + 1))()
    for g, segs in enumerate(groups):
        starts[g] = len(flat)
        flat.extend(segs)
    starts[len(groups)] = len(flat)
    ptrs, lens, _ = _seg_arrays(flat)
    out = (ctypes.c_uint32 * len(groups))()
    sd = None
    if seeds is not None:
        sd = (ctypes.c_uint32 * len(groups))(
            *(s & 0xFFFFFFFF for s in seeds))
    rc = lib().ceph_tpu_wire_crc_batch(
        ptrs, lens, len(flat), starts, len(groups), sd, out)
    if rc != 0:
        raise ValueError(f"wire_crc_batch failed ({rc})")
    return list(out)


def wire_gather(segs, out) -> int:
    """Gather segments into the writable buffer ``out`` (native memcpy
    walk); returns total bytes.  Raises when out is too small."""
    ptrs, lens, total = _seg_arrays(segs)
    dst, cap = _seg_addr(out, writable=True)
    rc = lib().ceph_tpu_wire_gather(ptrs, lens, len(segs),
                                    ctypes.c_char_p(dst), cap)
    if rc < 0:
        raise ValueError(f"wire_gather failed ({rc}): {total} > {cap}")
    return int(rc)


def wire_copy_crc32c(src, dst, seed: int = 0) -> int:
    """Fused copy+crc32c: land ``src`` in ``dst`` (None = checksum only)
    and return the chained crc of the bytes, one released-GIL pass."""
    sa, n = _seg_addr(src)
    da = 0
    if dst is not None:
        da, dn = _seg_addr(dst, writable=True)
        if dn < n:
            raise ValueError(f"wire_copy_crc32c: dst {dn} < src {n}")
    return int(lib().ceph_tpu_wire_copy_crc32c(sa, da, n,
                                               seed & 0xFFFFFFFF))


def wire_writev(fd: int, segs, skip: int = 0) -> int:
    """writev the segment list onto a nonblocking fd — partial writes,
    EINTR and IOV_MAX batching loop natively with the GIL released.
    Returns bytes written (0 = would-block); raises OSError on a hard
    socket error (the sendmsg surface CorkedWriter expects)."""
    ptrs, lens, _ = _seg_arrays(segs)
    rc = lib().ceph_tpu_wire_writev(fd, ptrs, lens, len(segs), skip)
    if rc < 0:
        err = int(-rc)
        raise OSError(err, os.strerror(err))
    return int(rc)


def wire_verify_regions(base, offs, lens, wants) -> int:
    """Burst crc verify over regions of ONE buffer (the rx backlog):
    region i is base[offs[i]:offs[i]+lens[i]] and must crc32c to
    wants[i].  Returns -1 when every region matches, else the first
    mismatching index.  Offsets are plain ints — no per-region buffer
    marshalling, so the Python-side cost is O(1) small arrays."""
    ba, blen = _seg_addr(base)
    n = len(offs)
    rc = lib().ceph_tpu_wire_verify_regions(
        ba, blen, (ctypes.c_int64 * n)(*offs),
        (ctypes.c_size_t * n)(*lens),
        (ctypes.c_uint32 * n)(*(w & 0xFFFFFFFF for w in wants)), n)
    if rc < -1:
        raise ValueError(f"wire_verify_regions bad geometry ({rc})")
    return rc


def wire_scatter(srcs, offs, dst, want_crcs=None) -> tuple:
    """Guarded scatter of fragments into ``dst`` at ``offs`` with
    optional per-fragment crc verification (crc runs over the source
    BEFORE any copy).  Returns (rc, bad_idx): rc == len(srcs) on
    success, else -22 (geometry: bounds/overlap) or -74 (crc) with
    bad_idx naming the refused fragment."""
    n = len(srcs)
    ptrs, lens, _ = _seg_arrays(srcs)
    o = (ctypes.c_int64 * n)(*offs)
    da, dlen = _seg_addr(dst, writable=True)
    crcs = None
    if want_crcs is not None:
        crcs = (ctypes.c_uint32 * n)(*(c & 0xFFFFFFFF for c in want_crcs))
    bad = ctypes.c_int32(-1)
    rc = lib().ceph_tpu_wire_scatter(
        ptrs, lens, n, o, da, dlen, crcs,
        1 if want_crcs is not None else 0, ctypes.byref(bad))
    return int(rc), int(bad.value)


# -- the off-loop sender (native/wirepath.h): ONE native thread a process ----
# writes the flush windows handed to it, off every event loop and without the
# GIL.  The raw entry points take addresses: the caller keeps every segment
# alive and unchanged until the job's completion was reaped.  The messenger
# hands over through the PyDLL shim below (wirepy_sender_submit / _reap),
# which pins the segments' buffers itself.

SENDER_STATS = ("submitted", "completed", "failed", "cancelled", "bytes",
                "writev_calls", "eagains", "writev_ns", "starts", "signals")


def wire_sender_submit(fd: int, chan: int, token: int, segs) -> int:
    """Queue `segs` for `fd`; the completion goes to the eventfd `chan`.
    Returns the jobs the thread had unfinished; raises OSError (EINVAL:
    bad geometry, nothing queued)."""
    ptrs, lens, _ = _seg_arrays(segs)
    rc = lib().ceph_tpu_wire_sender_submit(fd, chan, token, ptrs, lens,
                                           len(segs))
    if rc < 0:
        raise OSError(-rc, os.strerror(-rc))
    return int(rc)


def wire_sender_reap(chan: int) -> list:
    """[(token, bytes written or -errno, EAGAINs)] of the jobs of `chan`
    that ended since the last reap; resets the eventfd."""
    cap = 64
    tokens = (ctypes.c_uint64 * cap)()
    results = (ctypes.c_int64 * cap)()
    eagains = (ctypes.c_uint32 * cap)()
    out: list = []
    while True:
        n = lib().ceph_tpu_wire_sender_reap(chan, tokens, results, eagains,
                                            cap)
        if n < 0:
            raise OSError(-n, os.strerror(-n))
        out.extend((tokens[i], results[i], eagains[i]) for i in range(n))
        if n < cap:
            return out


def wire_sender_cancel(fd: int) -> int:
    """Drop every job of `fd`; returns, with the jobs dropped, only when
    the thread is in no system call on it (the fd may be closed then).
    Each dropped job completes with -ECANCELED on its channel."""
    return int(lib().ceph_tpu_wire_sender_cancel(fd))


def wire_sender_close_chan(chan: int) -> int:
    """Drop every job that would complete on `chan`; the channel is
    forgotten once nothing of it waits to be reaped (reap, then call
    again).  Call BEFORE closing the eventfd."""
    return int(lib().ceph_tpu_wire_sender_close_chan(chan))


def wire_sender_stop() -> int:
    """Stop the sender thread (joined on return); queued jobs complete
    with -ECANCELED.  The next submit starts a new one."""
    return int(lib().ceph_tpu_wire_sender_stop())


def wire_sender_stats() -> dict:
    """The sender's counters since load (SENDER_STATS); unfinished jobs =
    submitted - completed - failed - cancelled."""
    out = (ctypes.c_uint64 * len(SENDER_STATS))()
    lib().ceph_tpu_wire_sender_stats(out)
    return dict(zip(SENDER_STATS, out))


# -- wirepy: the PyDLL shim (native/wirepath_py.cc) --------------------------
# Separate .so because it needs Python headers; loaded via ctypes.PyDLL
# so the C side parses the SEGMENT LIST itself (PyObject_GetBuffer walk,
# ~100ns/segment under the held GIL) and then releases the GIL around
# the byte work.  Building per-segment pointer arrays in ctypes costs
# more than the syscall it feeds — this shim is why the tx hot loop can
# afford a native call per flush window at all.

_PYLIB = os.path.join(_BUILD, "libceph_tpu_wirepy.so")
_WIREPY_SRCS = ("wirepath_py.cc", "wirepath.cc", "crc32c.cc")

_pylib: Optional[ctypes.PyDLL] = None
_pylib_failed = False


def build_wirepy(force: bool = False) -> Optional[str]:
    """Compile the PyDLL shim (idempotent, like build()); None when the
    host lacks Python development headers — the base library and the
    pure-ctypes entry points keep working without it."""
    import sysconfig

    inc = sysconfig.get_paths().get("include") or ""
    if not os.path.exists(os.path.join(inc, "Python.h")):
        return None
    srcs = [os.path.join(_NATIVE, f) for f in _WIREPY_SRCS]
    hdrs = [os.path.join(_NATIVE, "wirepath.h")]
    if not force and _up_to_date(_PYLIB, srcs + hdrs):
        return _PYLIB
    os.makedirs(os.path.dirname(_PYLIB), exist_ok=True)
    cmd = [
        "g++", "-std=c++17", "-O3", "-march=native", "-fPIC", "-shared",
        *WARN_FLAGS, f"-I{inc}", "-o", _PYLIB, *srcs, "-pthread",
    ]
    try:
        subprocess.run(cmd, check=True, capture_output=True)
    except subprocess.CalledProcessError as e:
        raise RuntimeError(
            f"wirepy build failed (rc {e.returncode}); if these are "
            f"warnings from a newer compiler, set "
            f"CEPH_TPU_NATIVE_WERROR=0:\n"
            f"{(e.stderr or b'').decode(errors='replace')}") from e
    _stamp(_PYLIB)
    return _PYLIB


def pylib() -> Optional[ctypes.PyDLL]:
    """The PyDLL shim, or None when it cannot build (missing Python
    headers / compiler): callers fall back to the pure arms."""
    global _pylib, _pylib_failed
    if _pylib is None and not _pylib_failed:
        try:
            path = build_wirepy()
            if path is None:
                _pylib_failed = True
                return None
            _l = ctypes.PyDLL(path)
            _l.ceph_tpu_wirepy_writev.restype = ctypes.c_longlong
            _l.ceph_tpu_wirepy_writev.argtypes = [
                ctypes.c_int, ctypes.py_object, ctypes.c_ulonglong]
            _l.ceph_tpu_wirepy_crc_chain.restype = ctypes.c_longlong
            _l.ceph_tpu_wirepy_crc_chain.argtypes = [
                ctypes.py_object, ctypes.c_uint]
            _l.ceph_tpu_wirepy_gather.restype = ctypes.c_longlong
            _l.ceph_tpu_wirepy_gather.argtypes = [
                ctypes.py_object, ctypes.py_object]
            _l.ceph_tpu_wirepy_verify_regions.restype = ctypes.c_longlong
            _l.ceph_tpu_wirepy_verify_regions.argtypes = [
                ctypes.py_object, ctypes.py_object, ctypes.py_object,
                ctypes.py_object]
            _l.ceph_tpu_wirepy_scatter_from.restype = ctypes.c_longlong
            _l.ceph_tpu_wirepy_scatter_from.argtypes = [
                ctypes.py_object, ctypes.py_object, ctypes.py_object]
            _l.ceph_tpu_wirepy_sender_submit.restype = ctypes.c_longlong
            _l.ceph_tpu_wirepy_sender_submit.argtypes = [
                ctypes.c_int, ctypes.c_int, ctypes.c_ulonglong,
                ctypes.py_object]
            _l.ceph_tpu_wirepy_sender_reap.restype = ctypes.c_longlong
            _l.ceph_tpu_wirepy_sender_reap.argtypes = [
                ctypes.c_int, ctypes.py_object]
            # the sender thread is the BASE library's, one a process: the
            # shim (which carries a copy of wirepath.cc that never runs)
            # reaches it through these two addresses
            _l.ceph_tpu_wirepy_sender_bind.restype = None
            _l.ceph_tpu_wirepy_sender_bind.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p]
            base = lib()
            _l.ceph_tpu_wirepy_sender_bind(
                ctypes.cast(base.ceph_tpu_wire_sender_submit,
                            ctypes.c_void_p),
                ctypes.cast(base.ceph_tpu_wire_sender_reap,
                            ctypes.c_void_p))
            _pylib = _l
        except Exception:
            _pylib_failed = True
    return _pylib


def has_wirepy() -> bool:
    return pylib() is not None


def _pyl() -> ctypes.PyDLL:
    l = pylib()
    if l is None:
        # a host with g++ but no Python.h builds the CDLL arm yet not
        # this shim: fail with the actual condition, not an
        # AttributeError off the None
        raise RuntimeError("wirepy shim unavailable (missing Python "
                           "headers or compiler)")
    return l


def wirepy_writev(fd: int, segs, skip: int = 0) -> int:
    """One PyDLL call writev's the whole segment LIST onto a nonblocking
    fd: segment parsing happens in C under the held GIL, the I/O loop
    runs with it released.  Returns bytes written (0 = would-block);
    raises OSError on a hard socket error."""
    rc = _pyl().ceph_tpu_wirepy_writev(fd, segs, skip)
    if rc < 0:
        err = int(-rc)
        raise OSError(err, os.strerror(err))
    return int(rc)


def wirepy_sender_submit(fd: int, chan: int, token: int, segs) -> int:
    """Hand the segment LIST to the sender thread in one PyDLL call: the
    buffers are pinned in C under the held GIL and stay pinned until
    wirepy_sender_reap saw the job end; nothing here lets go of the GIL.
    Returns the jobs the thread had unfinished; raises OSError."""
    rc = _pyl().ceph_tpu_wirepy_sender_submit(fd, chan, token, segs)
    if rc < 0:
        raise OSError(int(-rc), os.strerror(int(-rc)))
    return int(rc)


def wirepy_sender_reap(chan: int) -> list:
    """[(token, bytes written or -errno, EAGAINs)] of `chan`'s jobs that
    ended since the last reap, their buffers released (on this thread);
    resets the eventfd."""
    out: list = []
    rc = _pyl().ceph_tpu_wirepy_sender_reap(chan, out)
    if rc < 0:
        raise OSError(int(-rc), os.strerror(int(-rc)))
    return out


def wirepy_crc_chain(segs, seed: int = 0) -> int:
    """Chained crc32c over a LIST of buffers in one PyDLL call (a
    BufferList's pieces) — no per-piece ctypes round-trips."""
    rc = _pyl().ceph_tpu_wirepy_crc_chain(segs, seed & 0xFFFFFFFF)
    if rc < 0:
        raise ValueError(f"wirepy_crc_chain failed ({rc})")
    return int(rc)


def wirepy_gather(segs, out) -> int:
    """Gather a LIST of buffers into writable ``out`` in one PyDLL
    call; returns total bytes, raises when out is too small."""
    rc = _pyl().ceph_tpu_wirepy_gather(segs, out)
    if rc < 0:
        raise ValueError(f"wirepy_gather failed ({rc})")
    return int(rc)


def wirepy_verify_regions(base, offs, lens, wants) -> int:
    """Burst crc32c verify over regions of ONE buffer: region i is
    base[offs[i]:offs[i]+lens[i]] and must checksum to wants[i].  The
    geometry rides plain Python int LISTS (C-side walk, no ctypes
    array builds) and the crc loop runs with the GIL released.
    Returns -1 when every region matches, else the first mismatching
    index; raises on out-of-bounds geometry."""
    rc = _pyl().ceph_tpu_wirepy_verify_regions(base, offs, lens, wants)
    if rc < -1:
        raise ValueError(f"wirepy_verify_regions bad geometry ({rc})")
    return int(rc)


def wirepy_scatter_from(base, soffs, dsts) -> int:
    """Burst scatter OUT of one source buffer: fill each writable
    buffer dsts[i] (its own length) from base[soffs[i]:] — a whole rx
    burst's blob bytes leave the backlog in one released-GIL memcpy
    loop.  Bounds are validated before any byte moves; returns total
    bytes copied, raises on bad geometry."""
    rc = _pyl().ceph_tpu_wirepy_scatter_from(base, soffs, dsts)
    if rc < 0:
        raise ValueError(f"wirepy_scatter_from bad geometry ({rc})")
    return int(rc)
