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


# -- PR 48: the OSD waits for a disk store's callback, and for nothing on
#    a store that commits in microseconds --------------------------------------

import threading  # noqa: E402

from ceph_tpu.rados.ecutil import HashInfo  # noqa: E402
from ceph_tpu.rados.osd import OSD  # noqa: E402


def _watch_commit_shard(monkeypatch, seen):
    """Every `OSD._commit_shard` call: what it returned, whether the store
    was handed a callback, how many futures the loop made inside it."""
    inner = OSD._commit_shard

    def watched(self, txn, defer):
        loop = asyncio.get_running_loop()
        made, create = [], loop.create_future
        loop.create_future = lambda: (made.append(1), create())[1]
        queue, callbacks = self.store.queue_transaction, []

        def queue_transaction(txn, on_commit=None):
            # the signature the benchmark's tap and controls wrap it with
            callbacks.append(on_commit)
            return queue(txn, on_commit)

        self.store.queue_transaction = queue_transaction
        try:
            out = inner(self, txn, defer)
        finally:
            del loop.create_future
            self.store.queue_transaction = queue
        seen.append((out, callbacks, len(made), defer,
                     [x[:2] for x in txn.xattr_sets]))
        return out

    monkeypatch.setattr(OSD, "_commit_shard", watched)


def _bs_counters():
    dump = BS_PERF.dump()
    return {k: (v["sum"] if isinstance(v, dict) else v)
            for k, v in dump.items()}


class TestTheOsdWaitsForTheCallbackNotTheCall:
    def test_a_memstore_shard_write_makes_no_future_and_no_loop_step(
            self, monkeypatch):
        seen = []
        _watch_commit_shard(monkeypatch, seen)

        async def go():
            cluster = Cluster(n_osds=4, conf=dict(FAST), n_mons=1)
            await cluster.start()
            try:
                client = await cluster.client()
                pool = await client.create_pool("p", pg_num=8,
                                                profile=dict(K2M1))
                before = _bs_counters()
                for i in range(6):
                    await client.put(pool, f"obj{i}", os.urandom(40000))
                assert _bs_counters() == before
                for osd in cluster.osds.values():
                    assert osd.store.commit_blocks is False
                    for oid, shard in osd.store.list_objects(pool):
                        if shard < 3:
                            assert HashInfo.decode(osd.store.getattr(
                                (pool, oid, shard), HashInfo.XATTR_KEY))
                await client.stop()
            finally:
                await cluster.stop()

        asyncio.run(go())
        served = [s for s in seen if s[3]]
        assert len(served) >= 18  # 6 puts x (k + m)
        for out, callbacks, futures, _defer, xattrs in served:
            # committed at the call's return: nothing to await, no
            # callback, no future, and the hinfo record inside the one
            # transaction (no store call of its own)
            assert out is True and callbacks == [None] and futures == 0
            assert [name for _key, name in xattrs] == [HashInfo.XATTR_KEY]

    def test_a_bluestore_shard_write_is_acked_after_its_callback(
            self, tmp_path, monkeypatch):
        seen = []
        _watch_commit_shard(monkeypatch, seen)

        async def go():
            conf = dict(FAST, osd_objectstore="bluestore",
                        osd_data=str(tmp_path), ms_local_fastpath=False)
            cluster = Cluster(n_osds=4, conf=conf, n_mons=1)
            await cluster.start()
            try:
                client = await cluster.client()
                pool = await client.create_pool("p", pg_num=8,
                                                profile=dict(K2M1))
                await client.put(pool, "warm", os.urandom(150000))
                before = _bs_counters()
                data = {f"obj{i}": os.urandom(150000) for i in range(8)}
                await asyncio.gather(*[client.put(pool, oid, blob)
                                       for oid, blob in data.items()])
                moved = {k: v - before[k]
                         for k, v in _bs_counters().items()}
                # 8 puts x 3 shards, each ONE transaction under one block
                # sync and one WAL sync, all on the stores' threads: the
                # loop slept in none of them
                assert moved["txns"] == moved["offloop_commits"] == 24
                assert moved["block_syncs"] == moved["wal_syncs"] == 24
                assert moved["commit_under_sync"] == 24
                assert moved["commit_unsynced"] == 0
                assert moved["loop_sync_s"] == 0
                assert moved["commit_queue_wait"] > 0
                held = 0
                for osd in cluster.osds.values():
                    assert osd.store.commit_blocks is True
                    for oid, shard in osd.store.list_objects(pool):
                        if oid in data and shard < 3:
                            held += 1
                            rec = HashInfo.decode(osd.store.getattr(
                                (pool, oid, shard), HashInfo.XATTR_KEY))
                            assert rec.total_chunk_size > 0
                assert held == 24
                for oid, blob in data.items():
                    assert bytes(await client.get(pool, oid)) == blob
                await client.stop()
            finally:
                await cluster.stop()

        asyncio.run(go())
        served = [s for s in seen if s[3]]
        assert len(served) >= 27
        for out, callbacks, futures, _defer, _xattrs in served:
            # handed over with a callback; what the handler awaited is a
            # future, made here and nowhere on a MemStore
            assert isinstance(out, asyncio.Future) and futures == 1
            assert len(callbacks) == 1 and callbacks[0] is not None
        assert not [t for t in threading.enumerate()
                    if t.name.startswith("bluestore-commit")]

    def test_killed_with_commits_in_flight_no_thread_is_left(self, tmp_path):
        """Writers are running when every OSD is halted and its store
        abandoned: the kill awaits no commit, no thread of an abandoned
        store lives on, the directories open again, and what had been
        acknowledged before the kill reads back."""
        async def go():
            conf = dict(FAST, osd_objectstore="bluestore",
                        osd_data=str(tmp_path))
            cluster = Cluster(n_osds=4, conf=conf, n_mons=1)
            await cluster.start()
            try:
                client = await cluster.client()
                pool = await client.create_pool("p", pg_num=8,
                                                profile=dict(K2M1))
                acked = {}

                async def writer(w):
                    for i in range(200):
                        oid, blob = f"w{w}.{i}", os.urandom(120000)
                        try:
                            await client.put(pool, oid, blob)
                        except Exception:
                            return
                        acked[oid] = blob

                writers = [asyncio.ensure_future(writer(w))
                           for w in range(4)]
                while len(acked) < 8:
                    await asyncio.sleep(0.01)
                snapshot = dict(acked)
                old = [o.store for o in cluster.osds.values()]
                assert any(s._thread is not None for s in old)
                await cluster.restart_osds()
                assert all(s._thread is None for s in old)
                live = {o.store._thread for o in cluster.osds.values()}
                assert all(t in live for t in threading.enumerate()
                           if t.name.startswith("bluestore-commit"))
                for w in writers:
                    w.cancel()
                await asyncio.gather(*writers, return_exceptions=True)
                for _ in range(200):
                    await client.refresh_map()
                    health = await client.get_health()
                    if not health.get("checks"):
                        break
                    await asyncio.sleep(0.1)
                for oid, blob in snapshot.items():
                    assert bytes(await client.get(pool, oid)) == blob, oid
                await client.stop()
            finally:
                await cluster.stop()

        asyncio.run(go())
        assert not [t for t in threading.enumerate()
                    if t.name.startswith("bluestore-commit")]


class TestAGroupOfSubWritesAndAStoreThatFails:
    def test_a_group_is_handed_over_whole_before_any_commit_is_awaited(
            self, tmp_path):
        """A run of sub-writes from one rx batch: every one is applied and
        on the store's queue while the first is still inside its block
        sync, so the thread goes from one commit to the next with no turn
        of the loop between them; the replies go out together, in order,
        each after its own commit."""
        from ceph_tpu.rados.types import MECSubWrite

        async def go():
            conf = dict(FAST, osd_objectstore="bluestore",
                        osd_data=str(tmp_path))
            cluster = Cluster(n_osds=4, conf=conf, n_mons=1)
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("p", pg_num=8, profile=dict(K2M1))
                await c.put(pool, "obj", os.urandom(150000))
                p = c.osdmap.pools[pool]
                pg = c.osdmap.object_to_pg(p, "obj")
                acting = c.osdmap.pg_to_acting(p, pg)
                primary = c.osdmap.primary_of(acting,
                                              seed=(pool << 20) | pg)
                rid = next(a for a in acting if a >= 0 and a != primary)
                replica, shard = cluster.osds[rid], acting.index(rid)
                chunk, meta = replica.store.read((pool, "obj", shard))
                msgs = [MECSubWrite(
                    pool_id=pool, pg=pg, oid="obj", shard=shard,
                    chunk=bytes([n]) * len(chunk), version=meta.version + n,
                    object_size=meta.object_size, tid=f"t{n}",
                    reply_to=("127.0.0.1", 1), from_osd=primary,
                    epoch=replica.osdmap.epoch) for n in (1, 2, 3)]
                gate, sync = threading.Event(), replica.store._block.sync
                synced, sent = [], []

                def held(data_only=False):
                    assert gate.wait(20)
                    synced.append(replica.store._unfinished)
                    sync(data_only)

                async def send(addr, reply):
                    sent.append((reply.tid, reply.ok, len(synced)))

                replica.store._block.sync = held
                replica.messenger.send = send
                before = _bs_counters()
                group = asyncio.ensure_future(
                    replica._handle_sub_write_group(msgs))
                for _ in range(2000):
                    if replica.store._unfinished == 3:
                        break
                    await asyncio.sleep(0.005)
                # all three with the thread, none synced, nobody told
                assert replica.store._unfinished == 3
                assert synced == [] and sent == [] and not group.done()
                assert replica.store.stat((pool, "obj", shard))[1].version \
                    == meta.version + 3
                gate.set()
                await asyncio.wait_for(group, 20)
                del replica.store._block.sync, replica.messenger.send
                assert synced[0] == 3
                assert sent == [("t1", True, 3), ("t2", True, 3),
                                ("t3", True, 3)]
                moved = {k: v - before[k] for k, v in _bs_counters().items()}
                assert moved["offloop_commits"] == moved["txns"] == 3
                assert moved["commit_unsynced"] == 0
                got, now = replica.store.read((pool, "obj", shard))
                assert bytes(got) == bytes([3]) * len(chunk)
                assert now.version == meta.version + 3
                await c.stop()
            finally:
                await cluster.stop()

        asyncio.run(asyncio.wait_for(go(), 120))

    def test_an_osd_whose_disk_fails_refuses_its_waiters_and_dies(
            self, tmp_path):
        """One OSD's WAL sync starts to fail with shard writes queued on
        its store's thread: every waiter is answered (a refusal, so no put
        hangs on it), the store takes no more, and the daemon stops as on
        any fatal error; its thread is gone and the mon marks it down."""
        async def go():
            conf = dict(FAST, osd_objectstore="bluestore",
                        osd_data=str(tmp_path), mon_osd_report_grace=0.8,
                        osd_heartbeat_interval=0.2, osd_heartbeat_grace=1.0,
                        client_op_timeout=3.0)
            cluster = Cluster(n_osds=4, conf=conf, n_mons=1)
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("p", pg_num=8, profile=dict(K2M1))
                await c.put(pool, "warm", os.urandom(150000))
                victim = cluster.osds[1]
                store = victim.store

                def broken(data_only=False):
                    raise OSError(5, "Input/output error")

                store.db._log.sync = broken
                puts = [asyncio.ensure_future(
                    c.put(pool, f"obj{i}", os.urandom(150000)))
                    for i in range(8)]
                done, hanging = await asyncio.wait(puts, timeout=60)
                assert not hanging
                for t in puts:
                    t.exception()  # acked or failed: either, but answered
                for _ in range(400):
                    if victim._stopped and store._thread is None:
                        break
                    await asyncio.sleep(0.05)
                assert isinstance(store.failed, OSError)
                assert victim._stopped and store._unfinished == 0
                assert store._thread is None
                with pytest.raises(IOError, match="takes no more"):
                    store.setattr((pool, "warm", 0), "k", b"v")
                # the rest of the cluster learns it as of any dead daemon
                for _ in range(400):
                    await c.refresh_map()
                    if not c.osdmap.osds[1].up:
                        break
                    await asyncio.sleep(0.05)
                assert not c.osdmap.osds[1].up
                assert len(bytes(await c.get(pool, "warm"))) == 150000
                await c.stop()
            finally:
                await cluster.stop()

        asyncio.run(asyncio.wait_for(go(), 180))
