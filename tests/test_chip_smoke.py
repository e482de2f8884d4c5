"""CPU-only tests of the pieces chip_smoke.py leans on: the placeable
compile cache, the no-chip exits, and the start-up failures that used to
fall back to the CPU in silence."""

import json
import logging
import os
import subprocess
import sys

import numpy as np
import pytest

from ceph_tpu.utils import jaxdev

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- compile cache -------------------------------------------------------------


@pytest.fixture
def cache_calls(monkeypatch):
    """enable_compile_cache() with jax.config.update captured, not applied."""
    import jax

    calls = []
    monkeypatch.setattr(jaxdev, "_cache_dir", None)
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    return calls


def test_cache_env_set_sets_nothing_in_code(cache_calls, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert jaxdev.enable_compile_cache() == "/some/dir"
    assert cache_calls == []


def test_cache_env_unset_is_fixed_checkout_path(cache_calls, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert jaxdev.enable_compile_cache() == want
    assert jaxdev.enable_compile_cache() == want  # idempotent
    assert cache_calls == [("jax_compilation_cache_dir", want)]


def test_cpu_forced_process_never_reaches_the_cache(cache_calls):
    assert os.environ["JAX_PLATFORMS"] == "cpu"  # conftest
    assert jaxdev.accelerator_live() is False
    assert cache_calls == [] and jaxdev._cache_dir is None


def test_cpu_child_env(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    monkeypatch.setenv("XLA_FLAGS", "--foo "
                       "--xla_force_host_platform_device_count=8")
    env = jaxdev.cpu_child_env(n_cpu_devices=4)
    assert env["JAX_PLATFORMS"] == "cpu"
    assert env["XLA_FLAGS"] == \
        "--foo --xla_force_host_platform_device_count=4"
    assert jaxdev.cpu_child_env()["XLA_FLAGS"] == os.environ["XLA_FLAGS"]


def test_compile_meter_counts_this_threads_compiles():
    import jax
    import jax.numpy as jnp

    meter = jaxdev.compile_meter()
    n0, s0 = meter.count, meter.thread_seconds()
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
    assert meter.count > n0 and meter.thread_seconds() > s0
    assert meter.snapshot()["compiles"] == meter.count


# -- no chip, no result --------------------------------------------------------


def test_chip_smoke_without_a_chip_fails_with_ok_false():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=jaxdev.cpu_child_env(), capture_output=True, text=True,
        timeout=120)
    assert out.returncode != 0
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["ok"] is False and "no TPU" in last["error"]


# -- start-up failures are loud ------------------------------------------------


def test_codec_first_dispatch_failure_is_logged_and_counted(
        monkeypatch, caplog):
    from ceph_tpu.ec.plugins.tpu import PLUGIN_PERF
    from ceph_tpu.ec.registry import registry
    from ceph_tpu.ops import gf2

    def refuse(*_a, **_kw):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(gf2, "gf2_apply_packedbit", refuse)
    prof = {"technique": "reed_sol_van", "k": "4", "m": "2"}
    tpu = registry.factory("tpu", "", dict(prof, plugin="tpu"))
    ref = registry.factory("jerasure", "", dict(prof, plugin="jerasure"))
    obj = np.random.default_rng(3).integers(
        0, 256, 1 << 16, dtype=np.uint8).tobytes()
    failed0 = PLUGIN_PERF.get("device_failed")
    fallback0 = PLUGIN_PERF.get("cpu_fallback")
    with caplog.at_level(logging.ERROR, logger="ceph_tpu.ec.tpu"):
        got = tpu.encode(set(range(6)), obj)
        tpu.encode(set(range(6)), obj)
    want = ref.encode(set(range(6)), obj)
    assert all(np.array_equal(got[c], want[c]) for c in range(6))
    assert PLUGIN_PERF.get("device_failed") == failed0 + 1
    assert PLUGIN_PERF.get("cpu_fallback") == fallback0 + 1  # 2nd encode
    errors = [r for r in caplog.records if r.exc_info]
    assert len(errors) == 1  # once, with the traceback
    assert "Mosaic failed" in str(errors[0].exc_info[1])


def _bm_rows():
    from ceph_tpu.ec.matrices import (matrix_to_bitmatrix,
                                      vandermonde_coding_matrix)

    bm = matrix_to_bitmatrix(vandermonde_coding_matrix(4, 2, 8), 8)
    rows = np.random.default_rng(5).integers(
        0, 256, (4, 2048), dtype=np.uint8)
    return bm.astype(np.uint8), rows


def test_lane_first_launch_failure_is_logged_and_counted(
        monkeypatch, caplog):
    from ceph_tpu.parallel import service
    from ceph_tpu.parallel.service import BatchingQueue, _cpu_apply_request

    def refuse(g, batch):
        raise RuntimeError("RESOURCE_EXHAUSTED: out of HBM")

    monkeypatch.setitem(service.LANES, "packedbit",
                        service.LANES["packedbit"]._replace(device=refuse))
    bm, rows = _bm_rows()
    q = BatchingQueue(max_delay=60.0, mesh=False)
    try:
        with caplog.at_level(logging.ERROR, logger="ceph_tpu.ec.batch"):
            fut = q.submit(bm, rows, 8, 2, "packedbit")
            q.flush()
            got = fut.result(timeout=60)
        assert np.array_equal(
            got, _cpu_apply_request("packedbit", bm, rows, 8, 2))
        assert q.perf.get("breaker_trip") == 1
        assert q.perf.get("breaker_fallback") == 1
        assert q.open_lanes() == ["packedbit"]
        errors = [r for r in caplog.records if r.exc_info]
        assert errors and "out of HBM" in str(errors[0].exc_info[1])
    finally:
        q.close()


class _FakeMeter:
    """thread_seconds() as if `jump` seconds of compile ran after the
    first reading (the launch mark)."""

    def __init__(self, jump):
        self.jump, self.reads = jump, 0

    def thread_seconds(self):
        self.reads += 1
        return 0.0 if self.reads == 1 else self.jump


@pytest.mark.parametrize("compile_s,trips", [(10.0, 0), (0.0, 1)])
def test_watchdog_does_not_count_compile_seconds(compile_s, trips):
    from ceph_tpu.parallel.service import BatchingQueue

    bm, rows = _bm_rows()
    q = BatchingQueue(max_delay=60.0, mesh=False)
    try:
        q.submit(bm, rows, 8, 2, "packedbit")
        q.flush()  # warm: the real compile happens here
        q._compiles = _FakeMeter(compile_s)
        q.dispatch_timeout = 0.05
        q.inject_dispatch_delay = 0.1  # every dispatch now "takes" > timeout
        fut = q.submit(bm, rows, 8, 2, "packedbit")
        q.flush()
        fut.result(timeout=60)
        assert q.perf.get("breaker_trip") == trips
        assert q.perf.dump()["dispatch_compile"]["sum"] >= compile_s
    finally:
        q.close()


def test_mesh_layout_failure_is_counted_not_swallowed(caplog):
    from ceph_tpu.parallel.service import BatchingQueue, _cpu_apply_request

    class SickMesh:
        n_devices = 4

        def pad_cols(self, n):
            return n

        def shard_batch(self, batch):
            raise RuntimeError("device 3 is gone")

    bm, rows = _bm_rows()
    q = BatchingQueue(max_delay=60.0, mesh=SickMesh())
    try:
        with caplog.at_level(logging.ERROR, logger="ceph_tpu.ec.batch"):
            fut = q.submit(bm, rows, 8, 2, "packedbit")
            q.flush()
            got = fut.result(timeout=60)
        assert np.array_equal(
            got, _cpu_apply_request("packedbit", bm, rows, 8, 2))
        assert q.perf.get("mesh_shard_failed") == 1
        assert q.perf.get("sharded_dispatch") == 0
        assert q.perf.get("dispatch") == 1
        assert any(r.exc_info for r in caplog.records)
    finally:
        q.close()


# -- a native build from another CPU is rebuilt, not loaded --------------------


def test_native_build_is_stamped_with_the_host_cpu(tmp_path):
    from ceph_tpu.native import bridge

    src, out = tmp_path / "a.cc", tmp_path / "lib.so"
    src.write_text("// source")
    out.write_text("built")
    assert not bridge._up_to_date(str(out), [str(src)])  # no stamp
    bridge._stamp(str(out))
    assert bridge._up_to_date(str(out), [str(src)])
    (tmp_path / "lib.so.host").write_text("another-cpu")
    assert not bridge._up_to_date(str(out), [str(src)])
