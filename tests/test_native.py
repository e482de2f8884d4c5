"""Native C++ core tests: build, byte-equality vs the numpy oracle, the
dlopen plugin registry with its failure modes, and the reference-compatible
benchmark CLI (the native twin of TestErasureCodePlugin.cc + the benchmark
protocol)."""

import os
import shutil
import subprocess

import numpy as np
import pytest

NATIVE = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "native")
BUILD = os.path.join(NATIVE, "build")

pytestmark = pytest.mark.skipif(
    shutil.which("g++") is None, reason="no native toolchain"
)


@pytest.fixture(scope="module")
def native_build():
    """Build the full native tree (core lib + plugins + benchmark)."""
    from ceph_tpu.native import bridge

    bridge.build()
    # plugins + benchmark via direct g++ (cmake works too; this is faster)
    plugs = {
        "libec_jerasure.so": ["plugin_jerasure.cc", "gf256.cc", "rs.cc"],
        "libec_isa.so": ["plugin_isa.cc", "gf256.cc", "rs.cc"],
    }
    for out, srcs in plugs.items():
        target = os.path.join(BUILD, out)
        if not os.path.exists(target):
            subprocess.run(
                ["g++", "-std=c++17", "-O3", "-march=native", "-fPIC", "-shared",
                 "-o", target] + [os.path.join(NATIVE, s) for s in srcs],
                check=True, capture_output=True,
            )
    bench = os.path.join(BUILD, "ceph_erasure_code_benchmark")
    if not os.path.exists(bench):
        subprocess.run(
            ["g++", "-std=c++17", "-O3", "-march=native",
             "-o", bench, os.path.join(NATIVE, "bench.cc"),
             os.path.join(BUILD, "libceph_tpu_ec.so"),
             f"-Wl,-rpath,{BUILD}", "-ldl"],
            check=True, capture_output=True,
        )
    return BUILD


def test_native_gf_matches_oracle(native_build):
    from ceph_tpu.ec.gf import gf
    from ceph_tpu.native import bridge

    f = gf(8)
    rng = np.random.default_rng(0)
    for a, b in rng.integers(0, 256, size=(64, 2)):
        assert bridge.gf_mul(int(a), int(b)) == f.mul(int(a), int(b))


@pytest.mark.parametrize(
    "technique,plugin,pytech,k,m",
    [
        ("reed_sol_van", "jerasure", "reed_sol_van", 8, 3),
        ("reed_sol_van", "jerasure", "reed_sol_van", 4, 2),
        ("reed_sol_r6_op", "jerasure", "reed_sol_r6_op", 6, 2),
        ("isa_reed_sol_van", "isa", "reed_sol_van", 8, 3),
        ("isa_cauchy", "isa", "cauchy", 5, 3),
    ],
)
def test_native_encode_byte_identical(native_build, technique, plugin, pytech, k, m):
    """Native RS chunks must memcmp-equal the Python codec chunks."""
    from ceph_tpu.native import bridge
    from tests.test_codecs import make

    codec = make(plugin, technique=pytech, k=k, m=m)
    rng = np.random.default_rng(1)
    data = rng.integers(0, 256, size=(k, 4096), dtype=np.uint8)
    want = codec.encode_chunks(data)
    got = bridge.rs_encode(technique, data, m)
    assert np.array_equal(got, want)


def test_native_decode_roundtrip(native_build):
    from ceph_tpu.native import bridge

    k, m = 8, 3
    rng = np.random.default_rng(2)
    data = rng.integers(0, 256, size=(k, 2048), dtype=np.uint8)
    parity = bridge.rs_encode("reed_sol_van", data, m)
    full = np.vstack([data, parity])
    erased = [0, 4, 10]
    sources = [i for i in range(k + m) if i not in erased][:k]
    out = bridge.rs_decode("reed_sol_van", k, m, sources, full[sources], erased)
    for i, e in enumerate(erased):
        assert np.array_equal(out[i], full[e])


def test_benchmark_cli(native_build):
    """Reference protocol: '<seconds>\\t<KB>' on stdout, encode+decode."""
    bench = os.path.join(native_build, "ceph_erasure_code_benchmark")
    for workload in ("encode", "decode"):
        r = subprocess.run(
            [bench, "--plugin", "jerasure", "--workload", workload,
             "--iterations", "4", "--size", "1048576",
             "-P", "k=8", "-P", "m=3", "-P", "technique=reed_sol_van",
             "--directory", native_build],
            capture_output=True, text=True, check=True,
        )
        seconds, kb = r.stdout.strip().split("\t")
        assert float(seconds) > 0
        assert kb == "4096"


def test_benchmark_unknown_plugin(native_build):
    bench = os.path.join(native_build, "ceph_erasure_code_benchmark")
    r = subprocess.run(
        [bench, "--plugin", "doesnotexist", "--directory", native_build],
        capture_output=True, text=True,
    )
    assert r.returncode != 0
    assert "failed" in r.stderr


def test_native_registry_version_mismatch(native_build, tmp_path):
    """A plugin built with a different ABI version string must be refused
    with -EXDEV (the reference's version-handshake behavior)."""
    src = os.path.join(tmp_path, "bad.cc")
    with open(src, "w") as f:
        f.write("""
        extern "C" {
        const char* __erasure_code_version() { return "9.9.9"; }
        int __erasure_code_init(const char*, void*) { return 0; }
        }
        """)
    out = os.path.join(tmp_path, "libec_badversion.so")
    subprocess.run(["g++", "-std=c++17", "-fPIC", "-shared", "-o", out, src],
                   check=True, capture_output=True)
    bench = os.path.join(native_build, "ceph_erasure_code_benchmark")
    r = subprocess.run(
        [bench, "--plugin", "badversion", "--directory", str(tmp_path)],
        capture_output=True, text=True,
    )
    assert r.returncode != 0
    assert "-18" in r.stderr  # -EXDEV


def test_simd_region_kernel_byte_identical(native_build):
    """The vectorized region kernel (GFNI affine / AVX2 pshufb) must be
    byte-identical to the scalar nibble tables across awkward lengths
    (vector tails) and all coefficient classes — the honest-baseline
    requirement: a fast-but-wrong baseline would corrupt every consumer."""
    import subprocess
    import sys

    from ceph_tpu.ec.gf import gf
    from ceph_tpu.ec.matrices import vandermonde_coding_matrix
    from ceph_tpu.native import bridge

    kind = bridge.simd_kind()
    assert kind in ("gfni", "avx2", "scalar")
    rng = np.random.default_rng(9)
    for chunk in (1, 31, 64, 65, 4096 + 17):
        data = rng.integers(0, 256, (8, chunk), dtype=np.uint8)
        parity = bridge.rs_encode("reed_sol_van", data, 3)
        want = gf(8).matmul(vandermonde_coding_matrix(8, 3, 8), data)
        assert np.array_equal(parity, want), (kind, chunk)
    # the scalar escape hatch (CEPH_TPU_NO_SIMD=1) produces the same bytes
    code = (
        "import numpy as np; from ceph_tpu.native import bridge;"
        "d = np.arange(8 * 1000, dtype=np.uint8).reshape(8, 1000);"
        "print(bridge.simd_kind());"
        "import sys; sys.stdout.buffer.write("
        "bridge.rs_encode('reed_sol_van', d, 3).tobytes())")
    out = subprocess.run([sys.executable, "-c", code],
                         env=dict(os.environ, CEPH_TPU_NO_SIMD="1"),
                         capture_output=True, timeout=120, check=True)
    lines = out.stdout.split(b"\n", 1)
    assert lines[0].strip() == b"scalar"
    d = np.arange(8 * 1000, dtype=np.uint8).reshape(8, 1000)
    assert lines[1] == bridge.rs_encode("reed_sol_van", d, 3).tobytes()


def test_mt_encode_byte_identical_and_reports_threads():
    """The socket-baseline encode (per-thread column ranges) must produce
    byte-identical parity to the single-threaded kernel."""
    bridge = pytest.importorskip("ceph_tpu.native.bridge")
    try:
        bridge.build()
    except Exception as e:
        pytest.skip(f"native build unavailable: {e}")
    rng = np.random.default_rng(3)
    # chunk sizes chosen to hit range-split edge cases: non-64-multiples,
    # chunks smaller than 64B*threads, and thread counts that don't
    # divide the chunk (a floor-divided range once left the tail
    # unencoded — silent zero parity)
    for chunk in (1 << 20, 4096, 4097, 64, 63, 130):
        data = rng.integers(0, 256, (8, chunk), dtype=np.uint8)
        p1 = bridge.rs_encode("reed_sol_van", data, 3)
        for nthreads in (0, 1, 3, 4, 7):
            p2, used = bridge.rs_encode_mt("reed_sol_van", data, 3,
                                           nthreads=nthreads)
            assert used >= 1
            assert np.array_equal(p1, p2), f"chunk={chunk} nt={nthreads}"


@pytest.mark.slow
def test_sanitized_native_build_runs_clean(tmp_path):
    """Satellite sanitizer gate: rebuild the native tree as the
    ASan/UBSan flavor (bridge.SANITIZE_FLAGS — the same set CMake's
    CEPH_TPU_SANITIZE / the CEPH_TPU_NATIVE_SANITIZE=1 env enables) and
    run encode + decode workloads under it.  Any heap misuse, UB, or
    leak in the gf/rs/registry/capi core aborts the bench nonzero.

    Skips cleanly when the toolchain cannot link the sanitizers (probe
    compile), since CI images vary."""
    from ceph_tpu.native import bridge

    # probe: can this toolchain produce a runnable sanitized binary?
    probe = tmp_path / "probe.cc"
    probe.write_text("int main() { return 0; }\n")
    r = subprocess.run(
        ["g++", *bridge.SANITIZE_FLAGS, "-o", str(tmp_path / "probe"),
         str(probe)], capture_output=True)
    if r.returncode != 0 or subprocess.run(
            [str(tmp_path / "probe")], capture_output=True).returncode != 0:
        pytest.skip("toolchain lacks a runnable ASan/UBSan")

    sdir = tmp_path / "sanitize"
    sdir.mkdir()
    srcs = [os.path.join(NATIVE, s) for s in bridge._LIB_SRCS]
    # bench + the whole core in ONE sanitized exe; -rdynamic so the
    # dlopen'd plugin resolves ec_registry_add from the exe's symtab
    subprocess.run(
        ["g++", "-std=c++17", "-O1", *bridge.WARN_FLAGS,
         *bridge.SANITIZE_FLAGS, "-rdynamic", "-o", str(sdir / "bench"),
         os.path.join(NATIVE, "bench.cc"), *srcs, "-ldl", "-pthread"],
        check=True, capture_output=True)
    subprocess.run(
        ["g++", "-std=c++17", "-O1", "-fPIC", "-shared",
         *bridge.WARN_FLAGS, *bridge.SANITIZE_FLAGS,
         "-o", str(sdir / "libec_jerasure.so"),
         os.path.join(NATIVE, "plugin_jerasure.cc"),
         os.path.join(NATIVE, "gf256.cc"), os.path.join(NATIVE, "rs.cc")],
        check=True, capture_output=True)
    for workload, extra in (("encode", []), ("decode", ["-e", "2"])):
        out = subprocess.run(
            [str(sdir / "bench"), "-p", "jerasure", "-w", workload,
             "-i", "3", "-s", "65536", "-d", str(sdir),
             "-P", "k=4", "-P", "m=2", *extra],
            capture_output=True, timeout=300)
        assert out.returncode == 0, (
            f"sanitized {workload} failed:\n{out.stderr.decode()}")

    # wirepath leg (ISSUE 12): the scatter/gather + crc entry points
    # under ASan/UBSan, driven by the in-library adversarial battery
    # (truncated, overlapping, corrupt-offset and oversize fragment
    # geometries — wirepath.cc's selftest).  An asan .so cannot be
    # dlopen'd into a plain python process, so a sanitized exe wraps
    # the battery, same discipline as the bench exe above.
    wrapper = tmp_path / "wirepath_main.cc"
    wrapper.write_text(
        '#include <cstdint>\n'
        '#include <cstdio>\n'
        'extern "C" int32_t ceph_tpu_wirepath_selftest();\n'
        'int main() {\n'
        '  int32_t rc = ceph_tpu_wirepath_selftest();\n'
        '  if (rc) std::fprintf(stderr, "wirepath selftest case %d "\n'
        '                       "failed\\n", rc);\n'
        '  return rc;\n'
        '}\n')
    subprocess.run(
        ["g++", "-std=c++17", "-O1", *bridge.WARN_FLAGS,
         *bridge.SANITIZE_FLAGS, "-o", str(sdir / "wirepath_selftest"),
         str(wrapper), os.path.join(NATIVE, "wirepath.cc"),
         os.path.join(NATIVE, "crc32c.cc"), "-pthread"],
        check=True, capture_output=True)
    out = subprocess.run([str(sdir / "wirepath_selftest")],
                         capture_output=True, timeout=300)
    assert out.returncode == 0, (
        f"sanitized wirepath battery failed:\n{out.stderr.decode()}")

    # the bridge's own sanitize flavor builds into a separate artifact
    # (never the one lib() loads)
    so = bridge.build(sanitize=True)
    assert so.endswith(os.path.join("sanitize", "libceph_tpu_ec.so"))
    assert os.path.exists(so)


# -- native wirepath (ISSUE 12) ----------------------------------------------

CORPUS_WIRE = os.path.join(os.path.dirname(NATIVE), "corpus", "wire")


def test_wirepath_smoke_corpus_byte_identity():
    """Tier-1 smoke: build-or-skip the wirepath symbols, then pin the
    native arm against the python arm on a fixed sample of the golden
    frame corpus — every crc the native batch computes and every byte
    the native gather/scatter moves must equal the per-segment
    interpreter loop's result on the same frames."""
    from ceph_tpu.native import bridge

    try:
        bridge.build()
        assert bridge.wirepath_kind() == "native"
    except Exception as e:
        pytest.skip(f"native wirepath unavailable: {e}")
    assert bridge.wirepath_selftest() == 0
    # a host with g++ but no Python.h has the CDLL arm only (the
    # resolver runs such hosts on the python arm): still smoke the
    # CDLL entry points, skip the shim's
    wirepy = bridge.has_wirepy()

    names = sorted(n for n in os.listdir(CORPUS_WIRE)
                   if n.endswith(".frame"))[:12]
    assert len(names) >= 8, "frame corpus sample missing"
    frames = []
    for n in names:
        with open(os.path.join(CORPUS_WIRE, n), "rb") as f:
            frames.append(f.read())

    for raw in frames:
        # split into awkward segments (odd boundaries, empty tail)
        cut1, cut2 = max(1, len(raw) // 3), max(2, (2 * len(raw)) // 3)
        segs = [raw[:cut1], raw[cut1:cut2], raw[cut2:], b""]
        # python arm: one interpreter iteration + crc call per segment
        py_crc = 0
        for s in segs:
            py_crc = bridge.crc32c(s, py_crc)
        # native arms: one batched call each
        assert bridge.wire_crc_batch([segs]) == [py_crc]
        if wirepy:
            assert bridge.wirepy_crc_chain(list(segs)) == py_crc
        # gather == join, both entry points
        out = bytearray(len(raw))
        assert bridge.wire_gather(segs, out) == len(raw)
        assert bytes(out) == raw
        if wirepy:
            out2 = bytearray(len(raw))
            assert bridge.wirepy_gather(list(segs), out2) == len(raw)
            assert bytes(out2) == raw
        # fused copy+crc == copy then crc
        dst = bytearray(len(raw))
        assert bridge.wire_copy_crc32c(raw, dst) == bridge.crc32c(raw)
        assert bytes(dst) == raw
        # region verify over the original frame's own geometry
        offs = [0, cut1, cut2]
        lens = [cut1, cut2 - cut1, len(raw) - cut2]
        wants = [bridge.crc32c(raw[o:o + ln]) for o, ln in zip(offs, lens)]
        assert bridge.wire_verify_regions(raw, offs, lens, wants) == -1
        if wirepy:
            assert bridge.wirepy_verify_regions(raw, offs, lens,
                                                wants) == -1
        # scatter reassembly (arrival order != offset order) lands the
        # frame byte-identical through the guarded path
        back = bytearray(len(raw))
        rc, bad = bridge.wire_scatter(
            [segs[2], segs[0], segs[1]], [cut2, 0, cut1], back,
            want_crcs=[bridge.crc32c(segs[2]), bridge.crc32c(segs[0]),
                       bridge.crc32c(segs[1])])
        assert (rc, bad) == (3, -1)
        assert bytes(back) == raw
        if wirepy:
            back2 = [bytearray(ln) for ln in lens]
            assert bridge.wirepy_scatter_from(raw, offs,
                                              back2) == sum(lens)
            assert b"".join(bytes(b) for b in back2) == raw


def test_wirepath_hostile_geometry_refused():
    """The FRAG_MAX overlap guard must hold in C: overlapping,
    out-of-bounds, and corrupt-offset fragment geometries are refused
    before a byte moves, on every scatter/gather entry point."""
    from ceph_tpu.native import bridge

    try:
        bridge.build()
    except Exception as e:
        pytest.skip(f"native wirepath unavailable: {e}")
    wirepy = bridge.has_wirepy()
    data = bytes(range(256)) * 16
    dst = bytearray(len(data))
    # overlap within one batch
    rc, bad = bridge.wire_scatter([data[:2048], data[:2048]], [0, 1024],
                                  dst)
    assert rc == -22 and bad == 1
    # out-of-bounds tail
    rc, bad = bridge.wire_scatter([data], [len(data) - 100], dst)
    assert rc == -22 and bad == 0
    # negative offset
    rc, bad = bridge.wire_scatter([data[:16]], [-1], dst)
    assert rc == -22 and bad == 0
    # crc mismatch refuses BEFORE the copy
    marker = bytearray(b"\x55" * len(data))
    rc, bad = bridge.wire_scatter([data], [0], marker,
                                  want_crcs=[bridge.crc32c(data) ^ 1])
    assert rc == -74 and bad == 0
    assert bytes(marker) == b"\x55" * len(data)
    # gather into an undersized destination refuses, never spills
    with pytest.raises(ValueError):
        bridge.wire_gather([data], bytearray(len(data) - 1))
    if wirepy:
        with pytest.raises(ValueError):
            bridge.wirepy_gather([data], bytearray(len(data) - 1))
    # a READONLY destination refuses on every arm: the ctypes entry
    # points must not silently memcpy into an immutable buffer's
    # address (the wirepy arm refuses via PyBUF_WRITABLE)
    ro = bytes(len(data))
    with pytest.raises(TypeError):
        bridge.wire_scatter([data[:16]], [0], ro)
    with pytest.raises(TypeError):
        bridge.wire_gather([data[:16]], ro)
    with pytest.raises(TypeError):
        bridge.wire_copy_crc32c(data[:16], ro)
    if wirepy:
        with pytest.raises(ValueError):
            bridge.wirepy_gather([data[:16]], ro)
        with pytest.raises(ValueError):
            bridge.wirepy_scatter_from(data, [0], [ro[:16]])
    # verify regions past the buffer refuse before any read
    with pytest.raises(ValueError):
        bridge.wire_verify_regions(data, [len(data) - 8], [64], [0])
    if wirepy:
        with pytest.raises(ValueError):
            bridge.wirepy_verify_regions(data, [len(data) - 8], [64],
                                         [0])
        with pytest.raises(ValueError):
            bridge.wirepy_scatter_from(data, [len(data) - 8],
                                       [bytearray(64)])


# -- the off-loop sender (ISSUE 49): native/wirepath.h, through ctypes --------


@pytest.fixture
def sender():
    """The bridge with the process's sender thread idle before and after:
    a socket pair whose far end nobody reads unless the test does, and an
    eventfd as the completion channel."""
    import socket

    from ceph_tpu.native import bridge

    try:
        bridge.build()
    except Exception as e:
        pytest.skip(f"native wirepath unavailable: {e}")
    if not hasattr(os, "eventfd"):
        pytest.skip("no eventfd on this platform")
    a, b = socket.socketpair()
    a.setblocking(False)
    b.setblocking(False)
    chan = os.eventfd(0, os.EFD_NONBLOCK | os.EFD_CLOEXEC)
    try:
        yield bridge, a, b, chan
    finally:
        bridge.wire_sender_cancel(a.fileno())
        bridge.wire_sender_reap(chan)  # or the channel is kept for them
        bridge.wire_sender_close_chan(chan)
        os.close(chan)
        a.close()
        b.close()


def _reaped(bridge, chan, want, seconds=10.0, reap=None):
    import time
    out = []
    deadline = time.monotonic() + seconds
    while len(out) < want and time.monotonic() < deadline:
        out.extend((reap or bridge.wire_sender_reap)(chan))
        if len(out) < want:
            time.sleep(0.002)
    return out


def _unfinished(st):
    return st["submitted"] - st["completed"] - st["failed"] - st["cancelled"]


def _recv_all(sock, n, seconds=10.0):
    import time
    got = bytearray()
    deadline = time.monotonic() + seconds
    while len(got) < n and time.monotonic() < deadline:
        try:
            chunk = sock.recv(1 << 20)
        except BlockingIOError:
            time.sleep(0.001)
            continue
        if not chunk:
            break
        got += chunk
    return bytes(got)


def test_sender_writes_jobs_of_one_fd_in_the_order_handed(sender):
    bridge, a, b, chan = sender
    before = bridge.wire_sender_stats()
    rng = np.random.default_rng(49)
    jobs = []
    for token in range(1, 9):
        segs = [rng.integers(0, 256, n, dtype=np.uint8).tobytes()
                for n in (26, 8, 100 + token, 70000 * (token % 3))]
        depth = bridge.wire_sender_submit(a.fileno(), chan, token, segs)
        assert 0 <= depth < token
        jobs.append((token, segs))
    want = b"".join(b"".join(segs) for _, segs in jobs)
    assert _recv_all(b, len(want)) == want
    done = _reaped(bridge, chan, len(jobs))
    assert [t for t, _, _ in done] == [t for t, _ in jobs]
    assert [r for _, r, _ in done] == [sum(map(len, segs))
                                       for _, segs in jobs]
    # the eventfd was reset by the reap; nothing is left to take
    with pytest.raises(BlockingIOError):
        os.eventfd_read(chan)
    assert bridge.wire_sender_reap(chan) == []
    after = bridge.wire_sender_stats()
    assert after["submitted"] - before["submitted"] == len(jobs)
    assert after["completed"] - before["completed"] == len(jobs)
    assert after["bytes"] - before["bytes"] == len(want)
    assert after["writev_calls"] - before["writev_calls"] >= len(jobs)
    assert after["writev_ns"] > before["writev_ns"]
    assert after["signals"] - before["signals"] <= len(jobs)  # per batch
    assert _unfinished(after) == _unfinished(before)


def test_sender_a_reap_resets_an_eventfd_written_late(sender):
    """The thread writes a channel's eventfd after it let go of its
    mutex, so the write may land after the reap that took its
    completions: the next reap finds nothing and still resets the
    eventfd, or an event loop's reader would spin on it."""
    import select
    bridge, a, b, chan = sender
    for token in range(1, 201):
        bridge.wire_sender_submit(a.fileno(), chan, token, [b"x" * 64])
        assert [t for t, _, _ in _reaped(bridge, chan, 1)] == [token]
    assert len(_recv_all(b, 200 * 64)) == 200 * 64
    st = bridge.wire_sender_stats()
    assert _unfinished(st) == 0
    os.eventfd_write(chan, 1)  # the late write, made by hand
    assert bridge.wire_sender_reap(chan) == []
    assert not select.select([chan], [], [], 0.05)[0]
    # and whatever the 200 real ones left behind goes with one reap too
    assert bridge.wire_sender_reap(chan) == []
    assert not select.select([chan], [], [], 0.05)[0]


def test_sender_refuses_bad_geometry_and_queues_nothing(sender):
    import ctypes
    bridge, a, b, chan = sender
    before = bridge.wire_sender_stats()
    for fd, ch, segs in ((-1, chan, [b"x"]), (a.fileno(), -1, [b"x"]),
                         (a.fileno(), chan, []),
                         (a.fileno(), chan, [b"", b""])):
        with pytest.raises(OSError) as e:
            bridge.wire_sender_submit(fd, ch, 7, segs)
        assert e.value.errno == 22
    lib = bridge.lib()
    ptrs = (ctypes.c_void_p * 1)(None)
    lens = (ctypes.c_size_t * 1)(64)  # a null segment that claims bytes
    assert lib.ceph_tpu_wire_sender_submit(
        a.fileno(), chan, 7, ptrs, lens, 1) == -22
    assert lib.ceph_tpu_wire_sender_submit(
        a.fileno(), chan, 7, None, None, 1) == -22
    assert lib.ceph_tpu_wire_sender_reap(chan, None, None, None, 4) == -22
    assert bridge.wire_sender_cancel(a.fileno()) == 0
    after = bridge.wire_sender_stats()
    assert after["submitted"] == before["submitted"]
    assert bridge.wire_sender_reap(chan) == []
    if bridge.has_wirepy():
        with pytest.raises(OSError):
            bridge.wirepy_sender_submit(a.fileno(), chan, 7, [b"x", 5])
        with pytest.raises(OSError):
            bridge.wirepy_sender_submit(a.fileno(), chan, 7, [])
        assert bridge.wire_sender_stats()["submitted"] \
            == before["submitted"]


def test_sender_cancel_while_a_job_is_half_written(sender):
    import socket
    import time
    bridge, a, b, chan = sender
    a.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 16384)
    before = bridge.wire_sender_stats()
    big = bytes(8 << 20)
    bridge.wire_sender_submit(a.fileno(), chan, 1, [b"head", big])
    bridge.wire_sender_submit(a.fileno(), chan, 2, [b"behind"])
    deadline = time.monotonic() + 10
    while bridge.wire_sender_stats()["eagains"] == before["eagains"] \
            and time.monotonic() < deadline:
        time.sleep(0.002)
    mid = bridge.wire_sender_stats()
    assert mid["eagains"] > before["eagains"], "the socket never filled"
    assert 0 < mid["bytes"] - before["bytes"] < len(big)
    assert bridge.wire_sender_reap(chan) == []  # nothing ended yet
    # the cancel returns with the thread off the fd: both jobs end
    # -ECANCELED, in order, and the fd has nothing left
    assert bridge.wire_sender_cancel(a.fileno()) == 2
    done = _reaped(bridge, chan, 2)
    assert [(t, r) for t, r, _ in done] == [(1, -125), (2, -125)]
    assert done[0][2] >= 1  # the first found the socket full
    assert bridge.wire_sender_cancel(a.fileno()) == 0
    # what the far end gets is a prefix of the first job, then nothing
    sent = bridge.wire_sender_stats()["bytes"] - before["bytes"]
    got = _recv_all(b, sent)
    assert got == (b"head" + big)[:sent]
    time.sleep(0.05)
    with pytest.raises(BlockingIOError):
        b.recv(1)
    after = bridge.wire_sender_stats()
    assert after["cancelled"] - before["cancelled"] == 2
    assert _unfinished(after) == _unfinished(before)


def test_sender_a_full_socket_holds_back_its_own_fd_only(sender):
    import socket
    import time
    bridge, a, b, chan = sender
    c, d = socket.socketpair()
    c.setblocking(False)
    d.setblocking(False)
    try:
        a.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 16384)
        before = bridge.wire_sender_stats()
        big = bytes([7]) * (4 << 20)
        bridge.wire_sender_submit(a.fileno(), chan, 1, [big])
        deadline = time.monotonic() + 10
        while bridge.wire_sender_stats()["eagains"] == before["eagains"] \
                and time.monotonic() < deadline:
            time.sleep(0.002)
        # the parked fd waits in the thread's epoll; another fd's jobs pass
        for token in (10, 11, 12):
            bridge.wire_sender_submit(c.fileno(), chan, token,
                                      [b"x" * 1000, bytes([token]) * 50000])
        done = _reaped(bridge, chan, 3)
        assert [t for t, _, _ in done] == [10, 11, 12]
        assert len(_recv_all(d, 3 * 51000)) == 3 * 51000
        # the reader resumes: EPOLLOUT wakes the job, it ends whole
        assert _recv_all(b, len(big)) == big
        done = _reaped(bridge, chan, 1)
        assert [(t, r) for t, r, _ in done] == [(1, len(big))]
        assert done[0][2] >= 1
        after = bridge.wire_sender_stats()
        assert after["completed"] - before["completed"] == 4
        assert _unfinished(after) == _unfinished(before)
    finally:
        bridge.wire_sender_cancel(c.fileno())
        c.close()
        d.close()


def test_sender_stop_with_jobs_queued_then_a_new_thread(sender):
    import socket
    import time
    bridge, a, b, chan = sender
    a.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 16384)
    before = bridge.wire_sender_stats()
    big = bytes(8 << 20)
    bridge.wire_sender_submit(a.fileno(), chan, 1, [big])
    bridge.wire_sender_submit(a.fileno(), chan, 2, [b"two"])
    bridge.wire_sender_submit(a.fileno(), chan, 3, [b"three"])
    deadline = time.monotonic() + 10
    while bridge.wire_sender_stats()["eagains"] == before["eagains"] \
            and time.monotonic() < deadline:
        time.sleep(0.002)
    assert bridge.wire_sender_stop() == 3
    done = _reaped(bridge, chan, 3)
    assert [(t, r) for t, r, _ in done] == [(1, -125), (2, -125), (3, -125)]
    assert bridge.wire_sender_stop() == 0  # no thread to stop
    mid = bridge.wire_sender_stats()
    assert _unfinished(mid) == _unfinished(before)
    # drain what the first job got out, then hand over again
    sent = mid["bytes"] - before["bytes"]
    assert _recv_all(b, sent) == big[:sent]
    bridge.wire_sender_submit(a.fileno(), chan, 4, [b"after the stop"])
    assert [(t, r) for t, r, _ in _reaped(bridge, chan, 1)] == [(4, 14)]
    assert _recv_all(b, 14) == b"after the stop"
    after = bridge.wire_sender_stats()
    assert after["starts"] == mid["starts"] + 1
    assert after["cancelled"] - before["cancelled"] == 3


def test_sender_keeps_a_jobs_buffers_pinned_until_the_reap(sender):
    import socket
    import time
    bridge, a, b, chan = sender
    if not bridge.has_wirepy():
        pytest.skip("wirepy shim unavailable")
    a.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 16384)
    before = bridge.wire_sender_stats()
    buf = bytearray(b"p" * (4 << 20))
    bridge.wirepy_sender_submit(a.fileno(), chan, 1, [b"hdr", buf])
    with pytest.raises(OSError) as e:  # a token in flight is not reused
        bridge.wirepy_sender_submit(a.fileno(), chan, 1, [b"again"])
    assert e.value.errno == 17
    # pinned: a bytearray with an exported view cannot change size
    with pytest.raises(BufferError):
        buf.extend(b"x")
    want = b"hdr" + bytes(buf)
    assert _recv_all(b, len(want)) == want
    deadline = time.monotonic() + 10
    while bridge.wire_sender_stats()["completed"] == before["completed"] \
            and time.monotonic() < deadline:
        time.sleep(0.002)
    with pytest.raises(BufferError):  # ended on the thread, not yet reaped
        buf.extend(b"x")
    done = _reaped(bridge, chan, 1, reap=bridge.wirepy_sender_reap)
    assert [(t, r) for t, r, _ in done] == [(1, len(want))]
    buf.extend(b"x")  # released by the reap, on this thread
    assert bridge.wirepy_sender_reap(chan) == []


def test_sender_is_not_inherited_across_a_fork(sender):
    bridge, a, b, chan = sender
    bridge.wire_sender_submit(a.fileno(), chan, 1, [b"parent"])
    assert [(t, r) for t, r, _ in _reaped(bridge, chan, 1)] == [(1, 6)]
    assert bridge.wire_sender_stats()["starts"] >= 1
    pid = os.fork()
    if pid == 0:
        code = 1
        try:
            st = bridge.wire_sender_stats()
            if any(st.values()):
                code = 2  # the child starts from nothing
            else:
                bridge.wire_sender_submit(a.fileno(), chan, 2, [b"child"])
                done = _reaped(bridge, chan, 1)
                st = bridge.wire_sender_stats()
                ok = [(t, r) for t, r, _ in done] == [(2, 5)] \
                    and st["starts"] == 1 and st["completed"] == 1
                code = 0 if ok else 3
                bridge.wire_sender_stop()
        finally:
            os._exit(code)
    _, status = os.waitpid(pid, 0)
    assert os.waitstatus_to_exitcode(status) == 0
    assert _recv_all(b, 11) == b"parentchild"
    # the parent's thread is the parent's still
    bridge.wire_sender_submit(a.fileno(), chan, 3, [b"parent again"])
    assert [(t, r) for t, r, _ in _reaped(bridge, chan, 1)] == [(3, 12)]


def test_sender_many_threads_hand_over_at_once(sender):
    """More submitting threads than cores, a short switch interval: every
    job ends once, each fd's bytes are its own jobs' in the order handed,
    and the counters close."""
    import socket
    import sys
    import threading
    bridge, _a, _b, chan = sender
    workers, jobs_each = 2 * (os.cpu_count() or 4), 60
    before = bridge.wire_sender_stats()
    pairs = [socket.socketpair() for _ in range(workers)]
    for x, y in pairs:
        x.setblocking(False)
        y.setblocking(False)
    errors, got = [], [bytearray() for _ in range(workers)]
    stop = threading.Event()
    # the raw entry point pins nothing: the caller keeps every segment
    # alive until its job was reaped
    bodies = [[bytes([w]) * (50 + 997 * (j % 7)) + bytes([j])
               for j in range(jobs_each)] for w in range(workers)]

    def submit(w):
        try:
            fd = pairs[w][0].fileno()
            for j in range(jobs_each):
                bridge.wire_sender_submit(fd, chan, w * 1000 + j + 1,
                                          [b"<", bodies[w][j], b">"])
        except Exception as e:  # reported below, on the test's thread
            errors.append(e)

    def drain():
        while not stop.is_set():
            for w, (_, y) in enumerate(pairs):
                try:
                    got[w] += y.recv(1 << 20)
                except BlockingIOError:
                    pass
            stop.wait(0.001)

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    reader = threading.Thread(target=drain)
    threads = [threading.Thread(target=submit, args=(w,))
               for w in range(workers)]
    try:
        reader.start()
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        assert not any(t.is_alive() for t in threads)
        assert not errors, errors
        done = _reaped(bridge, chan, workers * jobs_each, 30.0)
    finally:
        sys.setswitchinterval(was)
        stop.set()
        reader.join(10)
        for x, y in pairs:
            bridge.wire_sender_cancel(x.fileno())
    assert not reader.is_alive()
    assert sorted(t for t, _, _ in done) == sorted(
        w * 1000 + j + 1 for w in range(workers) for j in range(jobs_each))
    assert all(r > 0 for _, r, _ in done)
    for w in range(workers):
        mine = [t for t, _, _ in done if t // 1000 == w]
        assert mine == sorted(mine), "an fd's jobs ended out of order"
        for _, y in pairs[w:w + 1]:
            try:
                got[w] += y.recv(1 << 20)
            except BlockingIOError:
                pass
        want = b"".join(b"<" + body + b">" for body in bodies[w])
        assert bytes(got[w]) == want
    for x, y in pairs:
        x.close()
        y.close()
    after = bridge.wire_sender_stats()
    assert after["completed"] - before["completed"] == workers * jobs_each
    assert _unfinished(after) == _unfinished(before)
