"""The object store follows the conf: `osd_objectstore` picks MemStore or
BlueStore where vstart builds an OSD's store, `osd_data` says where the
cluster makes the directory that it owns and removes; a caller's
`data_dir` still wins.  A small EC cluster on BlueStores is killed whole
and comes back on its directories with every acknowledged object."""

import asyncio
import os
import tempfile

import pytest

from ceph_tpu.rados.bluestore import BS_PERF, BlueStore
from ceph_tpu.rados.store import MemStore
from ceph_tpu.rados.vstart import Cluster

FAST = {"osd_heartbeat_grace": 20.0, "mon_osd_report_grace": 20.0,
        "mon_osd_down_out_interval": 600.0}
K2M1 = {"plugin": "jerasure", "technique": "reed_sol_van", "k": "2",
        "m": "1"}


@pytest.mark.parametrize("conf", [{}, {"osd_objectstore": "memstore"},
                                  {"osd_objectstore": ""},
                                  {"osd_data": "/nowhere"}],
                         ids=["unnamed", "memstore", "empty", "data_alone"])
def test_a_conf_that_names_no_disk_store_gets_memstore(conf):
    cluster = Cluster(n_osds=1, conf=dict(conf))
    store = cluster._osd_store(0)
    assert type(store) is MemStore
    assert cluster._own_data_dir is None


def test_memstore_still_gets_its_capacity_from_the_conf():
    cluster = Cluster(n_osds=1, conf={"osd_store_capacity_bytes": 1 << 20,
                                      "osd_failsafe_full_ratio": 0.5})
    store = cluster._osd_store(0)
    assert (store.capacity_bytes, store.failsafe_ratio) == (1 << 20, 0.5)


def test_bluestore_by_conf_lives_in_a_directory_the_cluster_removes():
    cluster = Cluster(n_osds=2, conf={"osd_objectstore": "bluestore"})
    stores = [cluster._osd_store(n) for n in range(2)]
    root = cluster._own_data_dir
    try:
        assert all(type(s) is BlueStore for s in stores)
        assert os.path.dirname(root) == tempfile.gettempdir()
        assert [s.path for s in stores] == [f"{root}/osd.0", f"{root}/osd.1"]
        assert sorted(os.listdir(root)) == ["osd.0", "osd.1"]
    finally:
        for s in stores:
            s.abandon()
        cluster._remove_own_data_dir()
    assert not os.path.exists(root)
    assert cluster._own_data_dir is None


def test_osd_data_says_where_the_directory_is_made(tmp_path):
    cluster = Cluster(n_osds=1, conf={"osd_objectstore": "bluestore",
                                      "osd_data": str(tmp_path)})
    store = cluster._osd_store(0)
    store.abandon()
    assert os.path.dirname(cluster._own_data_dir) == str(tmp_path)
    cluster._remove_own_data_dir()
    assert os.listdir(tmp_path) == []


@pytest.mark.parametrize("conf", [{}, {"osd_objectstore": "memstore"},
                                  {"osd_objectstore": "bluestore"}],
                         ids=["unnamed", "memstore", "bluestore"])
def test_a_callers_data_dir_wins_and_is_not_removed(tmp_path, conf):
    cluster = Cluster(n_osds=1, conf=dict(conf), data_dir=str(tmp_path))
    store = cluster._osd_store(0)
    store.abandon()
    assert type(store) is BlueStore
    assert store.path == f"{tmp_path}/osd.0"
    assert cluster._own_data_dir is None
    cluster._remove_own_data_dir()
    assert os.path.isdir(store.path)


def test_an_unknown_store_is_refused():
    with pytest.raises(ValueError, match="filestore"):
        Cluster(n_osds=1, conf={"osd_objectstore": "filestore"})._osd_store(0)


def test_a_start_that_fails_leaves_no_directory(tmp_path, monkeypatch):
    cluster = Cluster(n_osds=1, conf={"osd_objectstore": "bluestore",
                                      "osd_data": str(tmp_path)})

    async def fails():
        cluster._osd_store(0).abandon()
        assert os.listdir(tmp_path)
        raise RuntimeError("no quorum")

    monkeypatch.setattr(cluster, "_start", fails)
    with pytest.raises(RuntimeError, match="no quorum"):
        asyncio.run(cluster.start())
    assert os.listdir(tmp_path) == []


def test_the_two_options_are_registered():
    from ceph_tpu.common.config import DEFAULT_SCHEMA as by_name

    assert by_name["osd_objectstore"].default == "memstore"
    assert by_name["osd_data"].default == ""


class TestAClusterOnBlueStores:
    def test_killed_whole_it_comes_back_with_every_acknowledged_object(
            self, tmp_path):
        async def go():
            conf = dict(FAST, osd_objectstore="bluestore",
                        osd_data=str(tmp_path),
                        bluestore_prefer_deferred_size=4096)
            cluster = Cluster(n_osds=4, conf=conf, n_mons=1)
            await cluster.start()
            root = cluster._own_data_dir
            was = dict(BS_PERF.dump())  # one set a process, other tests' too
            try:
                client = await cluster.client()
                pool = await client.create_pool("p", pg_num=8,
                                                profile=dict(K2M1))
                # shards above and below the deferred threshold
                data = {f"obj{i}": os.urandom(3000 + 9000 * i)
                        for i in range(10)}
                for oid, blob in data.items():
                    await client.put(pool, oid, blob)
                before = {i: osd for i, osd in cluster.osds.items()}
                perf = next(iter(before.values())).ctx.perf.dump()
                moved = {k: v - was[k] for k, v in perf["bluestore"].items()
                         if not isinstance(v, dict)}
                assert moved["txns"] >= 30
                assert moved["commit_under_sync"] == moved["txns"]
                assert moved["commit_unsynced"] == 0
                assert moved["deferred_writes"] > 0
                assert moved["big_writes"] > 0
                pending = sum(len(o.store._deferred_pending)
                              for o in before.values())
                assert pending > 0  # a kill leaves these to the replay
                await cluster.restart_osds()
                assert sorted(cluster.osds) == sorted(before)
                assert all(cluster.osds[i] is not before[i]
                           and cluster.osds[i].store.path
                           == before[i].store.path for i in before)
                for _ in range(100):
                    await client.refresh_map()
                    health = await client.get_health()
                    if not health.get("checks"):
                        break
                    await asyncio.sleep(0.1)
                for oid, blob in data.items():
                    assert bytes(await client.get(pool, oid)) == blob, oid
                await client.stop()
            finally:
                await cluster.stop()
            assert not os.path.exists(root)

        asyncio.run(go())

    def test_killed_whole_no_daemon_outlives_another_to_report_it(
            self, tmp_path, monkeypatch):
        """A power cut takes every OSD in one instant.  Were they stopped
        one after another with their loops still running, one not yet
        stopped finds a stopped peer's address refusing, reports it, the
        mon marks it down, and the cluster that comes back has an
        interval to peer over (and, at a cell's size, shards to push)
        where nothing was lost: the map moves by one epoch a boot."""
        from ceph_tpu.rados.osd import OSD

        async def go():
            conf = dict(FAST, osd_objectstore="bluestore",
                        osd_data=str(tmp_path), osd_heartbeat_interval=0.05,
                        ms_local_fastpath=False)  # sockets, as deployed
            cluster = Cluster(n_osds=4, conf=conf, n_mons=1)
            await cluster.start()
            try:
                client = await cluster.client()
                pool = await client.create_pool("p", pg_num=8,
                                                profile=dict(K2M1))
                await client.put(pool, "obj", b"x" * 50000)
                stop = OSD.stop

                async def slow_stop(self, **kw):
                    await stop(self, **kw)
                    await asyncio.sleep(0.2)  # four heartbeats of the rest

                monkeypatch.setattr(OSD, "stop", slow_stop)
                await client.refresh_map()
                epoch = client.osdmap.epoch
                old = list(cluster.osds.values())
                for osd in old:  # daemons long past their first grace
                    osd._booted_at -= 2 * conf["osd_heartbeat_grace"]
                await cluster.restart_osds()
                monkeypatch.setattr(OSD, "stop", stop)
                assert sum(o.perf.get("heartbeat_failures")
                           for o in old) == 0
                await client.refresh_map()
                assert client.osdmap.epoch == epoch + len(old)
                assert bytes(await client.get(pool, "obj")) == b"x" * 50000
                await client.stop()
            finally:
                await cluster.stop()

        asyncio.run(go())

    def test_one_osd_restarted_alone_keeps_its_id_and_shards(self, tmp_path):
        async def go():
            conf = dict(FAST, osd_objectstore="bluestore",
                        osd_data=str(tmp_path))
            cluster = Cluster(n_osds=4, conf=conf, n_mons=1)
            await cluster.start()
            try:
                client = await cluster.client()
                pool = await client.create_pool("p", pg_num=8,
                                                profile=dict(K2M1))
                blob = os.urandom(200000)
                await client.put(pool, "obj", blob)
                victim = min(cluster.osds)
                held = sorted(cluster.osds[victim].store.list_objects(pool))
                await cluster.restart_osds([victim])
                assert sorted(
                    cluster.osds[victim].store.list_objects(pool)) == held
                assert cluster.osds[victim].osd_id == victim
                assert bytes(await client.get(pool, "obj")) == blob
                await client.stop()
            finally:
                await cluster.stop()

        asyncio.run(go())
