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
from ceph_tpu.parallel.service import BatchingQueue, PlanarShardStore
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
        from ceph_tpu.ec.gf import gf
        from ceph_tpu.ec.matrices import (matrix_to_bitmatrix,
                                          vandermonde_coding_matrix)
        from ceph_tpu.ops.gf2 import from_planar, to_planar

        k, m, w = 4, 2, 8
        mat = vandermonde_coding_matrix(k, m, w)
        bm = matrix_to_bitmatrix(mat, w).astype(np.int8)
        fgf = gf(w)
        mesh = _mesh()
        q = BatchingQueue(max_delay=0.05, mesh=mesh)
        try:
            rng = np.random.default_rng(2)
            d = rng.integers(0, 256, (k, 4096), dtype=np.uint8)
            # packed lane
            out = q.submit(bm, d, w, m).result(timeout=120)
            assert np.array_equal(out, fgf.matmul(mat, d))
            # resident lane
            parity, all_bits = q.submit_resident(bm, d, w, m).result(
                timeout=120)
            assert np.array_equal(parity, fgf.matmul(mat, d))
            # planar lane chains on the sharded resident bits
            data_bits = all_bits[:k * w]
            pb = q.submit_planar(bm, data_bits, w, m).result(timeout=120)
            assert np.array_equal(np.asarray(from_planar(pb, w, m)),
                                  fgf.matmul(mat, d))
            assert q.sharded_dispatches >= 3, q.sharded_dispatches
            assert mesh.shard_puts >= 3
        finally:
            q.close()


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
