"""Multi-chip as a framework capability (VERDICT r03 #2): the
BatchingQueue lays dispatch batches out over a jax.sharding.Mesh
(ceph_tpu/parallel/mesh.py), so every EC dispatch runs SPMD across the
device grid — validated here on the conftest's virtual 8-device CPU
mesh (`python chip_smoke.py --multichip` runs the same step on four
real chips)."""

import asyncio
import os

import numpy as np
import pytest

from ceph_tpu.parallel.mesh import MeshDispatcher
from ceph_tpu.parallel.service import BatchingQueue
from ceph_tpu.rados import osd as osdmod
from ceph_tpu.rados.vstart import Cluster

PROFILE = {"plugin": "jerasure", "technique": "reed_sol_van",
           "k": "2", "m": "1"}


def _mesh():
    import jax

    pool = jax.devices("cpu")
    if len(pool) < 8:
        pytest.skip("needs the 8-device virtual CPU mesh")
    return MeshDispatcher(pool[:8])


def run(coro, timeout=180):
    asyncio.run(asyncio.wait_for(coro, timeout))


class TestMeshDispatcher:
    def test_axes_and_padding(self):
        mesh = _mesh()
        assert mesh.n_devices == 8
        assert dict(zip(mesh.mesh.axis_names, mesh.mesh.devices.shape)) == \
            {"stripe": 2, "col": 4}
        assert mesh.pad_cols(1000) == 1000  # already divisible
        assert mesh.pad_cols(1001) == 1008

    def test_sharded_batch_lands_on_all_devices(self):
        mesh = _mesh()
        batch = np.random.default_rng(0).integers(
            0, 256, (4, 4096), dtype=np.uint8)
        sharded = mesh.shard_batch(batch)
        held = {d for s in sharded.addressable_shards for d in [s.device]}
        assert len(held) == 8, "batch not spread across the mesh"


class TestQueueOnMesh:
    def test_all_lanes_dispatch_sharded_and_stay_byte_exact(self):
        from tests.test_lanes import LANE_CASES, check_lane_result, lane_case

        mesh = _mesh()
        q = BatchingQueue(max_delay=0.05, mesh=mesh)
        try:
            # 1152 columns: no multiple of the 8-device grid's pad unit
            for kind, w in LANE_CASES:
                codec, item = lane_case(kind, w, cols=1152)
                check_lane_result(codec, item,
                                  q.submit(*item).result(timeout=120))
            assert q.sharded_dispatches == q.dispatches == len(LANE_CASES)
            assert mesh.shard_puts == len(LANE_CASES)
            assert q.perf.get("mesh_shard_failed") == 0
            assert q.perf.get("breaker_fallback") == 0
        finally:
            q.close()

    def test_multichip_phase_step_on_the_virtual_mesh(self, capsys):
        """chip_smoke.py --multichip's phase as the four-chip host runs
        it — encodes on the mesh, residents through the paged store,
        3-erasure decodes, the repair re-encode, the same again on one
        device — with CPU devices for chips.  Its checks raise."""
        import json

        import chip_smoke

        chip_smoke.phase_multichip(seed=3, n_devices=4,
                                   object_bytes=64 << 10)
        line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert line["phase"] == "multichip" and line["ok"] is True
        assert line["mesh"]["sharded_dispatch"] == line["mesh"]["dispatch"] > 0
        assert line["mesh"]["resident_device_sets"] == [4] * 4
        assert line["single"]["sharded_dispatch"] == 0


@pytest.fixture()
def force_mesh(monkeypatch):
    """Engage the forced mesh + batching for the daemon path, with fresh
    process singletons so earlier tests' mesh-less queue is not reused."""
    monkeypatch.setenv("CEPH_TPU_FORCE_BATCH", "1")
    monkeypatch.setenv("CEPH_TPU_MESH", "1")
    import ceph_tpu.parallel.mesh as meshmod

    monkeypatch.setattr(osdmod, "_BATCH_QUEUE", None)
    monkeypatch.setattr(osdmod, "_PLANAR_STORE", None)
    monkeypatch.setattr(meshmod, "_SHARED", None)
    monkeypatch.setattr(meshmod, "_SHARED_FAILED", False)
    yield
    q = osdmod._BATCH_QUEUE
    if q is not None:
        q.close()
    monkeypatch.setattr(osdmod, "_BATCH_QUEUE", None)
    monkeypatch.setattr(osdmod, "_PLANAR_STORE", None)


class TestOsdOnMesh:
    def test_concurrent_osd_encodes_land_on_virtual_mesh(self, force_mesh):
        """Concurrent client writes through a live cluster coalesce into
        few dispatches AND those dispatches run across the 8-device
        mesh — the production daemon path, multi-chip (VERDICT r03 #2
        done criterion)."""
        async def go():
            cluster = Cluster(n_osds=3, conf={"osd_auto_repair": False,
                                              "client_op_timeout": 60.0})
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("mq", profile=PROFILE)
                q = osdmod.shared_batching_queue()
                assert q is not None and q.mesh is not None
                assert q.mesh.n_devices == 8
                await c.put(pool, "warm", os.urandom(8192))
                before_d = q.dispatches
                before_s = q.sharded_dispatches
                n = 12
                blobs = [os.urandom(50_000) for _ in range(n)]
                await asyncio.gather(
                    *(c.put(pool, f"o{i}", blobs[i]) for i in range(n)))
                dispatches = q.dispatches - before_d
                sharded = q.sharded_dispatches - before_s
                assert dispatches < n, (dispatches, n)  # coalesced
                assert sharded == dispatches, \
                    f"only {sharded}/{dispatches} dispatches rode the mesh"
                for i in range(n):
                    assert await c.get(pool, f"o{i}") == blobs[i]
                await c.stop()
            finally:
                await cluster.stop()

        run(go())
