"""Test configuration: force JAX onto a virtual 8-device CPU mesh.

Tests never touch an accelerator: multi-chip sharding is validated on a
virtual CPU mesh, and what only a chip can show is `chip_smoke.py`'s job.
Must run before jax initializes."""

import os

# Run the whole suite with runtime lockdep armed (common/lockdep.py):
# every make_mutex/make_async_mutex lock joins the global order graph and
# an ABBA inversion raises LockOrderError the first time the ORDER is
# violated, not the run the threads actually deadlock.  setdefault, so
# perf-sensitive invocations opt out with CEPH_TPU_LOCKDEP=0 (and tests
# that measure hot-path latency can monkeypatch lockdep.disable()).
os.environ.setdefault("CEPH_TPU_LOCKDEP", "1")

# Hard-set (not setdefault): a chip host's environment may name the TPU
# first; tests must never depend on, or take, the chip.
os.environ["JAX_PLATFORMS"] = "cpu"
# Under full-suite load the default 30s backend probe can time out and pin
# "unavailable" for the whole process, silently flipping plugin=tpu tests to
# their CPU path.  The CPU backend always comes up; give it ample time.
os.environ.setdefault("CEPH_TPU_PROBE_TIMEOUT", "300")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()


def pytest_configure(config):
    # tier-1 runs `-m "not slow"`; register the marker so slow legs
    # (e.g. the sanitized native rebuild) don't warn as unknown
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 fast suite")


import pytest  # noqa: E402


def _drop_shared_ec_service() -> None:
    from ceph_tpu.rados import osd as osdmod

    q, osdmod._BATCH_QUEUE, osdmod._PLANAR_STORE = osdmod._BATCH_QUEUE, None, None
    if q is not None:
        q.close()


@pytest.fixture()
def force_batching(monkeypatch):
    """Engage the device EC service on the CPU backend, where the queue
    normally stays off, and give the test the process-wide queue and
    resident store to itself: whatever an earlier test left there is
    closed on the way in, and what this test made on the way out."""
    monkeypatch.setenv("CEPH_TPU_FORCE_BATCH", "1")
    _drop_shared_ec_service()
    yield
    _drop_shared_ec_service()
