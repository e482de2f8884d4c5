"""plugin=tpu tests: byte-equality vs the jerasure CPU oracle (the repo's
non-regression contract, BASELINE.md), exhaustive-erasure decode through the
device path, CPU fallback semantics, and
the stripe-batching queue."""

import numpy as np
import pytest

from ceph_tpu.ec.registry import registry
from tests.test_codecs import make, payload, roundtrip_exhaustive


@pytest.fixture(autouse=True)
def pinned_backend(monkeypatch):
    """Pin the hang-proof backend probe to a live verdict so every test in
    this file exercises the device dispatch seam deterministically (a probe
    that timed out earlier in the suite would silently flip the plugin to
    its CPU path and make these tests vacuous)."""
    from ceph_tpu.utils import jaxdev

    verdict = jaxdev._result if jaxdev._result not in (None, jaxdev.UNAVAILABLE) else "cpu"
    monkeypatch.setattr(jaxdev, "_result", verdict)


@pytest.mark.parametrize(
    "profile",
    [
        dict(technique="reed_sol_van", k=2, m=2),
        dict(technique="reed_sol_van", k=4, m=2),
        dict(technique="reed_sol_van", k=8, m=3),
        dict(technique="reed_sol_van", k=3, m=2, w=16),
        dict(technique="reed_sol_van", k=4, m=2, w=4),
        dict(technique="reed_sol_r6_op", k=4),
        dict(technique="cauchy_orig", k=3, m=2, packetsize=8),
        dict(technique="cauchy_good", k=4, m=2, packetsize=8),
    ],
)
def test_tpu_byte_identical_to_jerasure(profile):
    """plugin=tpu chunks must memcmp-equal plugin=jerasure chunks — the
    A/B property the reference's non-regression corpus enforces."""
    t = make("tpu", **profile)
    j = make("jerasure", **profile)
    data = payload(1 << 16, seed=42)
    n = t.get_chunk_count()
    et = t.encode(set(range(n)), data)
    ej = j.encode(set(range(n)), data)
    assert not getattr(t, "_tpu_failed", False), "tpu path silently fell back"
    for c in range(n):
        assert np.array_equal(et[c], ej[c]), f"chunk {c} differs from jerasure"


def test_tpu_exhaustive_decode():
    codec = make("tpu", technique="reed_sol_van", k=4, m=2)
    roundtrip_exhaustive(codec, payload(1 << 14))
    assert not getattr(codec, "_tpu_failed", False)


def test_tpu_decode_uses_device_path():
    """Reconstruction (decode matrix as operand) must ride the same dispatch
    seam as encode."""
    codec = make("tpu", technique="reed_sol_van", k=8, m=3)
    data = payload(1 << 18, seed=9)
    enc = codec.encode(set(range(11)), data)
    avail = {c: enc[c] for c in range(11) if c not in (0, 4, 10)}
    out = codec.decode({0, 4, 10}, avail, len(enc[0]))
    for c in (0, 4, 10):
        assert np.array_equal(out[c], enc[c])
    assert not getattr(codec, "_tpu_failed", False), "decode fell back to CPU"


def test_tpu_cpu_fallback(monkeypatch):
    """A sick device must not wedge EC I/O: dispatch errors flip to the
    inherited CPU path and results stay correct (SURVEY.md §7 hard part 5)."""
    import ceph_tpu.ops.gf2 as gf2

    codec = make("tpu", technique="reed_sol_van", k=4, m=2)

    def boom(*a, **kw):
        raise RuntimeError("injected device failure")

    # break BOTH dispatch seams: the packed-bit XOR-schedule production
    # lane and the int8-plane fallback lane
    monkeypatch.setattr(gf2, "gf2_apply_packedbit", boom)
    monkeypatch.setattr(gf2, "gf2_apply_bytes", boom)
    data = payload(1 << 14, seed=3)
    enc = codec.encode(set(range(6)), data)
    assert codec._tpu_failed
    j = make("jerasure", technique="reed_sol_van", k=4, m=2)
    ej = j.encode(set(range(6)), data)
    for c in range(6):
        assert np.array_equal(enc[c], ej[c])


def test_batching_queue():
    """Many small encodes -> few device dispatches, identical bytes."""
    from ceph_tpu.ec.matrices import matrix_to_bitmatrix, vandermonde_coding_matrix
    from ceph_tpu.ec.gf import gf
    from ceph_tpu.parallel.service import BatchingQueue

    k, m = 4, 2
    mat = vandermonde_coding_matrix(k, m, 8)
    bm = matrix_to_bitmatrix(mat, 8)
    q = BatchingQueue(max_pending_bytes=1 << 30, max_delay=60)
    rng = np.random.default_rng(2)
    reqs = [rng.integers(0, 256, size=(k, 4096), dtype=np.uint8) for _ in range(32)]
    futs = [q.submit(bm, r, 8, m) for r in reqs]
    assert not any(f.done() for f in futs)  # nothing dispatched yet
    q.flush()
    for r, f in zip(reqs, futs):
        out = f.result(timeout=10)
        assert np.array_equal(out, gf(8).matmul(mat, r))
    assert q.dispatches == 1  # 32 requests, one device call
    q.close()


def test_batching_queue_delay_flush():
    from ceph_tpu.ec.matrices import matrix_to_bitmatrix, vandermonde_coding_matrix
    from ceph_tpu.parallel.service import BatchingQueue

    bm = matrix_to_bitmatrix(vandermonde_coding_matrix(2, 1, 8), 8)
    q = BatchingQueue(max_delay=0.01)
    fut = q.submit(bm, np.zeros((2, 1024), dtype=np.uint8), 8, 1)
    # generous timeout: under full-suite load the worker's first dispatch
    # can sit behind a slow jit compile; the assertion is that the flush
    # happens WITHOUT another submit, not that it is fast
    out = fut.result(timeout=60)  # worker must flush on its own
    assert np.array_equal(out, np.zeros((1, 1024), dtype=np.uint8))
    q.close()


def test_batching_queue_closed_submit():
    from ceph_tpu.parallel.service import BatchingQueue

    q = BatchingQueue()
    q.close()
    with pytest.raises(RuntimeError):
        q.submit(np.ones((8, 16), np.uint8), np.zeros((2, 64), np.uint8), 8, 1)


def test_tpu_encode_rides_packedbit_lane(monkeypatch):
    """w=8 byte-layout dispatch must route through the packed-bit
    XOR-schedule production lane (ops/gf2.py lane promotion), and the
    output must stay byte-identical to jerasure."""
    import ceph_tpu.ops.gf2 as gf2

    calls = []
    real = gf2.gf2_apply_packedbit

    def spy(bm, data):
        calls.append(np.asarray(bm).shape)
        return real(bm, data)

    monkeypatch.setattr(gf2, "gf2_apply_packedbit", spy)
    codec = make("tpu", technique="reed_sol_van", k=4, m=2)
    j = make("jerasure", technique="reed_sol_van", k=4, m=2)
    data = payload(1 << 14, seed=21)
    enc = codec.encode(set(range(6)), data)
    assert calls, "encode did not ride the packed-bit lane"
    assert not getattr(codec, "_tpu_failed", False)
    ej = j.encode(set(range(6)), data)
    for c in range(6):
        assert np.array_equal(enc[c], ej[c])
    # decode rides it too: the inverted signature matrix compiles to its
    # own schedule (per-decode-signature compilation behind the LRU)
    del calls[:]
    avail = {c: enc[c] for c in range(6) if c not in (1, 4)}
    out = codec.decode({1, 4}, avail, len(enc[0]))
    assert calls, "decode did not ride the packed-bit lane"
    for c in (1, 4):
        assert np.array_equal(out[c], enc[c])


def test_tpu_packedbit_kill_switch(monkeypatch):
    """CEPH_TPU_PACKEDBIT=0 pins the int8-plane lanes (the proven
    fallback layout) — packed-bit must never be dispatched, bytes stay
    identical."""
    import ceph_tpu.ops.gf2 as gf2

    monkeypatch.setenv("CEPH_TPU_PACKEDBIT", "0")

    def forbidden(*a, **kw):
        raise AssertionError("packed-bit lane dispatched while disabled")

    monkeypatch.setattr(gf2, "gf2_apply_packedbit", forbidden)
    codec = make("tpu", technique="reed_sol_van", k=4, m=2)
    j = make("jerasure", technique="reed_sol_van", k=4, m=2)
    data = payload(1 << 14, seed=22)
    enc = codec.encode(set(range(6)), data)
    assert not getattr(codec, "_tpu_failed", False)
    ej = j.encode(set(range(6)), data)
    for c in range(6):
        assert np.array_equal(enc[c], ej[c])


def test_tpu_bitmatrix_family_packedbit_rows(monkeypatch):
    """The cauchy/liberation packet-row path applies the XOR schedule
    DIRECTLY to packet bytes (no 8x bit expansion) — byte-identical to
    jerasure, and the schedule seam must actually be exercised."""
    import ceph_tpu.ops.gf2 as gf2

    calls = []
    real = gf2.gf2_xor_packed

    def spy(bm, rows, cse=None):
        calls.append(np.asarray(rows).dtype)
        return real(bm, rows, cse=cse)

    monkeypatch.setattr(gf2, "gf2_xor_packed", spy)
    profile = dict(technique="cauchy_good", k=4, m=2, packetsize=8)
    t = make("tpu", **profile)
    j = make("jerasure", **profile)
    data = payload(1 << 14, seed=23)
    n = t.get_chunk_count()
    et = t.encode(set(range(n)), data)
    ej = j.encode(set(range(n)), data)
    assert not getattr(t, "_tpu_failed", False)
    assert calls and all(d == np.uint8 for d in calls), calls
    for c in range(n):
        assert np.array_equal(et[c], ej[c])
