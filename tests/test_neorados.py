"""Compound atomic operations: the neorados-style WriteOp/ReadOp API and
the OSD's all-or-nothing multi executor (reference src/neorados/RADOS.cc,
MOSDOp vector<OSDOp>, PrimaryLogPG::do_osd_ops)."""

import asyncio
import errno

import pytest

from ceph_tpu.rados.client import RadosClient, RadosError
from ceph_tpu.rados.librados import Rados
from ceph_tpu.rados.neorados import RADOS, IOContext, ReadOp, WriteOp
from ceph_tpu.rados.vstart import Cluster

CONF = {"osd_auto_repair": False}
EC_PROFILE = {"plugin": "jerasure", "technique": "reed_sol_van",
              "k": "2", "m": "1"}


def run(coro):
    return asyncio.run(coro)


async def _cluster(pool="neo", pool_type="replicated", n_osds=4):
    cluster = Cluster(n_osds=n_osds, conf=dict(CONF))
    await cluster.start()
    client = RadosClient(cluster.mon_addrs, CONF)
    await client.start()
    if pool_type == "ec":
        pool_id = await client.create_pool(pool, "ec", profile=EC_PROFILE)
    else:
        pool_id = await client.create_pool(pool, pool_type="replicated")
    neo = RADOS(None, client=client)
    return cluster, client, neo, IOContext(pool_id)


class TestWriteOp:
    def test_atomic_write_xattr_omap(self):
        """One compound op lands data + xattr + omap together."""
        async def go():
            cluster, client, neo, ioc = await _cluster()
            try:
                op = (WriteOp()
                      .create(exclusive=True)
                      .write_full(b"payload")
                      .setxattr("owner", b"alice")
                      .omap_set({"k1": b"v1", "k2": b"v2"}))
                await neo.execute("obj", ioc, op)
                rd = (ReadOp().read().getxattr("owner")
                      .omap_get_vals().stat())
                res = await neo.execute("obj", ioc, rd)
                assert res[0][1] == b"payload"
                assert res[1][1] == b"alice"
                assert res[2][1] == {"k1": b"v1", "k2": b"v2"}
                assert res[3][1]["size"] == 7
            finally:
                await client.stop()
                await cluster.stop()
        run(go())

    def test_meta_replication_retries_after_transport_failure(self):
        """r4 advisor regression: a transient send failure while
        replicating xattr/omap mutations to acting peers must be
        RETRIED, not swallowed — a failover primary would otherwise
        serve stale omap (RGW bucket indexes ride this path)."""
        async def go():
            # generous heartbeat grace: on a loaded 1-core host, missed
            # heartbeats mark peers down, and the retry pump (by
            # design) parks a down peer's queue — that liveness
            # interplay is another test's subject; THIS test pins the
            # retry mechanism itself, so peers must stay up
            cluster = Cluster(n_osds=3, conf={
                "osd_auto_repair": False,
                "osd_heartbeat_grace": 300.0})
            await cluster.start()
            client = RadosClient(cluster.mon_addrs, CONF)
            await client.start()
            pool_id = await client.create_pool("neo",
                                               pool_type="replicated")
            neo = RADOS(None, client=client)
            ioc = IOContext(pool_id)
            try:
                # land the object first so the acting set is known
                await neo.execute("robj", ioc,
                                  WriteOp().write_full(b"seed"))
                # find the primary for this object
                primary = None
                for osd in cluster.osds.values():
                    pool = osd.osdmap.pools[ioc.pool_id]
                    pg, acting = osd._acting(pool, "robj")
                    if osd._primary(pool, pg, acting) == osd.osd_id:
                        primary = osd
                        peers = [a for a in acting
                                 if a != osd.osd_id]
                        break
                assert primary is not None and peers
                # wedge sends of metadata-replication messages only
                from ceph_tpu.rados.types import MSetOmap, MSetXattrs
                real_send = primary.messenger.send
                fail = {"n": 3}

                async def flaky(addr, msg, *a, **kw):
                    if isinstance(msg, (MSetOmap, MSetXattrs)) \
                            and fail["n"] > 0:
                        fail["n"] -= 1
                        raise ConnectionError("injected")
                    return await real_send(addr, msg, *a, **kw)

                primary.messenger.send = flaky
                await neo.execute("robj", ioc,
                                  WriteOp().setxattr("who", b"x")
                                  .omap_set({"idx": b"entry"}))
                # the failed sends were queued, and the pump drains
                # them (generous window: on a loaded 1-core host the
                # pump's backoff interleaves with heartbeat churn)
                for _ in range(600):
                    if not primary._meta_repl_pending:
                        break
                    await asyncio.sleep(0.05)
                assert not primary._meta_repl_pending
                assert fail["n"] == 0  # injection actually fired
                # every acting peer now holds the replicated metadata
                # (the pump's sends are fire-and-forget: the queue is
                # empty when the last one is SENT, the peer applies it
                # a loop turn or a few later)
                key = (ioc.pool_id, "robj", 0)
                for peer_id in peers:
                    peer = cluster.osds[peer_id]
                    for _ in range(100):
                        if peer.store.omap_get(key).get("idx") \
                                and peer.store.getattr(key, "who"):
                            break
                        await asyncio.sleep(0.05)
                    assert peer.store.omap_get(key).get("idx") == b"entry"
                    assert peer.store.getattr(key, "who") == b"x"
            finally:
                await client.stop()
                await cluster.stop()
        run(go())

    def test_failing_assert_applies_nothing(self):
        """cmpxattr mismatch mid-vector: earlier staged sub-ops must NOT
        land (all-or-nothing)."""
        async def go():
            cluster, client, neo, ioc = await _cluster()
            try:
                await neo.execute("obj", ioc,
                                  WriteOp().write_full(b"v1")
                                  .setxattr("tag", b"old"))
                bad = (WriteOp()
                       .write_full(b"v2")          # staged first...
                       .omap_set({"x": b"y"})
                       .cmpxattr("tag", b"WRONG")  # ...then the guard fails
                       .setxattr("tag", b"new"))
                with pytest.raises(RadosError) as ei:
                    await neo.execute("obj", ioc, bad)
                assert ei.value.code == -errno.ECANCELED
                res = await neo.execute(
                    "obj", ioc, ReadOp().read().getxattr("tag")
                    .omap_get_vals())
                assert res[0][1] == b"v1"      # write_full did not land
                assert res[1][1] == b"old"     # xattr unchanged
                assert res[2][1] == {}         # omap unchanged
            finally:
                await client.stop()
                await cluster.stop()
        run(go())

    def test_create_exclusive_and_assert_exists(self):
        async def go():
            cluster, client, neo, ioc = await _cluster()
            try:
                await neo.execute("obj", ioc, WriteOp().create(exclusive=True)
                                  .write_full(b"x"))
                with pytest.raises(RadosError) as ei:
                    await neo.execute("obj", ioc,
                                      WriteOp().create(exclusive=True))
                assert ei.value.code == -errno.EEXIST
                with pytest.raises(RadosError) as ei:
                    await neo.execute("ghost", ioc,
                                      WriteOp().assert_exists()
                                      .write_full(b"y"))
                assert ei.value.code == -errno.ENOENT
                # the guarded write must not have created the object
                with pytest.raises(RadosError):
                    await neo.execute("ghost", ioc, ReadOp().stat())
            finally:
                await client.stop()
                await cluster.stop()
        run(go())

    def test_ordering_read_sees_staged_write(self):
        """Reads inside the vector observe earlier sub-ops (reference
        do_osd_ops executes the vector in order against the txn)."""
        async def go():
            cluster, client, neo, ioc = await _cluster()
            try:
                op = (WriteOp()
                      .write_full(b"AAAA")
                      .append(b"BB")
                      .zero(1, 2)
                      .truncate(5))
                await neo.execute("obj", ioc, op)
                res = await neo.execute("obj", ioc, ReadOp().read())
                assert res[0][1] == b"A\x00\x00AB"
            finally:
                await client.stop()
                await cluster.stop()
        run(go())

    def test_assert_version_cas_loop(self):
        """Optimistic concurrency: two writers race read-modify-write
        with assert_version; every increment lands exactly once."""
        async def go():
            cluster, client, neo, ioc = await _cluster()
            try:
                await neo.execute("ctr", ioc, WriteOp().write_full(b"0"))

                async def incr(times):
                    for _ in range(times):
                        while True:
                            results, ver = await neo.execute_versioned(
                                "ctr", ioc, ReadOp().read())
                            val = int(results[0][1])
                            try:
                                await neo.execute(
                                    "ctr", ioc,
                                    WriteOp().assert_version(ver)
                                    .write_full(str(val + 1).encode()))
                                break
                            except RadosError as e:
                                if e.code != -errno.ERANGE:
                                    raise

                await asyncio.gather(incr(5), incr(5))
                results, _ = await neo.execute_versioned(
                    "ctr", ioc, ReadOp().read())
                assert int(results[0][1]) == 10
            finally:
                await client.stop()
                await cluster.stop()
        run(go())

    def test_remove_and_omap_lifecycle(self):
        async def go():
            cluster, client, neo, ioc = await _cluster()
            try:
                await neo.execute("obj", ioc, WriteOp().write_full(b"d")
                                  .omap_set({"a": b"1", "b": b"2",
                                             "c": b"3"}))
                await neo.execute("obj", ioc,
                                  WriteOp().omap_rm_keys(["a"]))
                res = await neo.execute("obj", ioc, ReadOp().omap_get_keys())
                assert res[0][1] == ["b", "c"]
                await neo.execute("obj", ioc, WriteOp().omap_clear()
                                  .omap_set({"z": b"9"}))
                res = await neo.execute("obj", ioc, ReadOp().omap_get_vals())
                assert res[0][1] == {"z": b"9"}
                await neo.execute("obj", ioc, WriteOp().remove())
                with pytest.raises(RadosError) as ei:
                    await neo.execute("obj", ioc, ReadOp().read())
                assert ei.value.code == -errno.ENOENT
            finally:
                await client.stop()
                await cluster.stop()
        run(go())

    def test_exec_cls_inside_vector(self):
        """A class call rides the vector; its failure aborts the op."""
        async def go():
            cluster, client, neo, ioc = await _cluster()
            try:
                import json
                await neo.execute(
                    "obj", ioc,
                    WriteOp().create()
                    .exec_("lock", "lock",
                           json.dumps({"owner": "me", "ttl": 30}).encode())
                    .setxattr("claimed", b"1"))
                res = await neo.execute("obj", ioc,
                                        ReadOp().getxattr("claimed"))
                assert res[0][1] == b"1"
                # second locker: cls returns -EBUSY -> xattr must not land
                with pytest.raises(RadosError):
                    await neo.execute(
                        "obj", ioc,
                        WriteOp()
                        .exec_("lock", "lock",
                               json.dumps({"owner": "thief",
                                           "ttl": 30}).encode())
                        .setxattr("claimed", b"2"))
                res = await neo.execute("obj", ioc,
                                        ReadOp().getxattr("claimed"))
                assert res[0][1] == b"1"
            finally:
                await client.stop()
                await cluster.stop()
        run(go())

    def test_resend_replays_original_result(self):
        """Appends are not idempotent: the server must dedupe by reqid
        (same discipline as cls calls)."""
        async def go():
            cluster, client, neo, ioc = await _cluster()
            try:
                from ceph_tpu.rados.types import MOSDOp
                await client.refresh_map()
                op = MOSDOp(op="multi", pool_id=ioc.pool_id, oid="obj",
                            ops=[("append", {"data": b"X"})],
                            reqid="fixed-reqid-1",
                            epoch=client.osdmap.epoch)
                _pg, primary = client._calc_target(op)

                async def send_same_reqid():
                    # _op_direct would mint a fresh reqid; a true resend
                    # keeps the original (reference one-reqid discipline)
                    fut = asyncio.get_running_loop().create_future()
                    client._replies[op.reqid] = fut
                    try:
                        await client.messenger.send(
                            client.osdmap.addr_of(primary), op)
                        return await asyncio.wait_for(fut, timeout=10)
                    finally:
                        client._replies.pop(op.reqid, None)

                r1 = await send_same_reqid()
                r2 = await send_same_reqid()  # resend
                assert r1.ok and r2.ok
                res = await neo.execute("obj", ioc, ReadOp().read())
                assert res[0][1] == b"X"  # applied once, not twice
            finally:
                await client.stop()
                await cluster.stop()
        run(go())


class TestReviewFindings:
    """Regressions for the staged-executor edge cases: serialization,
    remove purging metadata, metadata-only create, fast-path version."""

    def test_concurrent_multis_serialize(self):
        """Two concurrent read-modify-write multis on one object must not
        lose an update (the per-object critical section)."""
        async def go():
            cluster, client, neo, ioc = await _cluster()
            try:
                await neo.execute("obj", ioc, WriteOp().write_full(b""))
                # appends are read-modify-write inside the executor: if
                # the critical section were missing, interleaved stages
                # would drop bytes
                await asyncio.gather(*[
                    neo.execute("obj", ioc, WriteOp().append(b"x"))
                    for _ in range(8)])
                res = await neo.execute("obj", ioc, ReadOp().read())
                assert res[0][1] == b"x" * 8
            finally:
                await client.stop()
                await cluster.stop()
        run(go())

    def test_remove_purges_metadata(self):
        """remove inside a vector drops earlier-staged and persisted
        metadata; a later create of the same oid must not inherit it."""
        async def go():
            cluster, client, neo, ioc = await _cluster()
            try:
                await neo.execute("obj", ioc, WriteOp().write_full(b"d")
                                  .setxattr("a", b"1")
                                  .omap_set({"k": b"v"}))
                # staged setxattr before remove: must NOT survive
                await neo.execute("obj", ioc,
                                  WriteOp().setxattr("b", b"2").remove())
                await neo.execute("obj", ioc, WriteOp().write_full(b"new"))
                res = await neo.execute("obj", ioc,
                                        ReadOp().getxattrs()
                                        .omap_get_vals())
                assert res[0][1] == {}
                assert res[1][1] == {}
            finally:
                await client.stop()
                await cluster.stop()
        run(go())

    def test_remove_then_recreate_in_one_vector(self):
        """create / write-class sub-ops AFTER remove recreate the object
        fresh (reference do_osd_ops: remove clears, later ops rebuild)."""
        async def go():
            cluster, client, neo, ioc = await _cluster()
            try:
                await neo.execute("obj", ioc, WriteOp().write_full(b"old")
                                  .setxattr("a", b"1"))
                await neo.execute("obj", ioc,
                                  WriteOp().remove().create()
                                  .setxattr("b", b"2"))
                res = await neo.execute("obj", ioc, ReadOp().stat()
                                        .getxattrs())
                assert res[0][1]["size"] == 0      # fresh, not b"old"
                assert res[1][1] == {"b": b"2"}    # old xattr gone
                # remove then setxattr (no explicit create) also recreates
                await neo.execute("obj", ioc,
                                  WriteOp().remove().setxattr("c", b"3"))
                res = await neo.execute("obj", ioc, ReadOp().getxattrs())
                assert res[0][1] == {"c": b"3"}
            finally:
                await client.stop()
                await cluster.stop()
        run(go())

    def test_metadata_mutation_bumps_version(self):
        """Two assert_version CAS writers racing on XATTRS: the loser
        must fail -ERANGE (metadata commits bump the version)."""
        async def go():
            cluster, client, neo, ioc = await _cluster()
            try:
                await neo.execute("obj", ioc, WriteOp().write_full(b"d"))
                _r, v1 = await neo.execute_versioned(
                    "obj", ioc, ReadOp().getxattrs())
                await neo.execute("obj", ioc,
                                  WriteOp().assert_version(v1)
                                  .setxattr("winner", b"A"))
                _r, v2 = await neo.execute_versioned(
                    "obj", ioc, ReadOp().getxattrs())
                assert v2 > v1
                with pytest.raises(RadosError) as ei:
                    await neo.execute("obj", ioc,
                                      WriteOp().assert_version(v1)
                                      .setxattr("winner", b"B"))
                assert ei.value.code == -errno.ERANGE
                res = await neo.execute("obj", ioc,
                                        ReadOp().getxattr("winner")
                                        .read())
                assert res[0][1] == b"A"
                assert res[1][1] == b"d"  # data preserved by the bump
            finally:
                await client.stop()
                await cluster.stop()
        run(go())

    def test_metadata_only_create(self):
        """setxattr/omap_set on a nonexistent object creates it
        (reference: every write-class op creates the object)."""
        async def go():
            cluster, client, neo, ioc = await _cluster()
            try:
                await neo.execute("obj", ioc,
                                  WriteOp().setxattr("k", b"v"))
                res = await neo.execute("obj", ioc, ReadOp().stat()
                                        .getxattr("k"))
                assert res[0][1]["size"] == 0
                assert res[1][1] == b"v"
            finally:
                await client.stop()
                await cluster.stop()
        run(go())

    def test_fast_path_version_is_real(self):
        """A metadata-only multi still reports the object's version, so
        assert_version loops built on it work."""
        async def go():
            cluster, client, neo, ioc = await _cluster()
            try:
                await neo.execute("obj", ioc, WriteOp().write_full(b"d"))
                _res, ver = await neo.execute_versioned(
                    "obj", ioc, ReadOp().getxattrs())
                assert ver > 0
                # the reported version is usable as an assert_version guard
                await neo.execute("obj", ioc,
                                  WriteOp().assert_version(ver)
                                  .setxattr("ok", b"1"))
            finally:
                await client.stop()
                await cluster.stop()
        run(go())

    def test_metadata_reads_on_absent_object(self):
        async def go():
            cluster, client, neo, ioc = await _cluster()
            try:
                for op in (ReadOp().getxattrs(), ReadOp().omap_get_vals(),
                           ReadOp().getxattr("x")):
                    with pytest.raises(RadosError) as ei:
                        await neo.execute("ghost", ioc, op)
                    assert ei.value.code == -errno.ENOENT
            finally:
                await client.stop()
                await cluster.stop()
        run(go())


class TestECPools:
    def test_ec_data_ops_allowed_omap_rejected(self):
        async def go():
            cluster, client, neo, ioc = await _cluster(pool_type="ec")
            try:
                await neo.execute("obj", ioc,
                                  WriteOp().write_full(b"ec-bytes")
                                  .setxattr("tag", b"t"))
                res = await neo.execute("obj", ioc,
                                        ReadOp().read().getxattr("tag"))
                assert res[0][1] == b"ec-bytes"
                assert res[1][1] == b"t"
                with pytest.raises(RadosError) as ei:
                    await neo.execute("obj", ioc,
                                      WriteOp().omap_set({"k": b"v"}))
                assert ei.value.code == -errno.EOPNOTSUPP
                with pytest.raises(RadosError) as ei:
                    await neo.execute("obj", ioc,
                                      WriteOp().exec_("lock", "lock"))
                assert ei.value.code == -errno.EOPNOTSUPP
            finally:
                await client.stop()
                await cluster.stop()
        run(go())


class TestIoCtxConveniences:
    def test_xattr_omap_over_librados(self):
        async def go():
            cluster = Cluster(n_osds=4, conf=dict(CONF))
            await cluster.start()
            rados = await Rados(cluster.mon_addrs, CONF).connect()
            try:
                await rados.pool_create("neolib", pool_type="replicated")
                io = await rados.open_ioctx("neolib")
                await io.write_full("o", b"data")
                await io.setxattr("o", "user.a", b"1")
                assert await io.getxattr("o", "user.a") == b"1"
                assert await io.getxattrs("o") == {"user.a": b"1"}
                await io.rmxattr("o", "user.a")
                with pytest.raises(RadosError) as ei:
                    await io.getxattr("o", "user.a")
                assert ei.value.code == -errno.ENODATA
                await io.omap_set("o", {"x": b"y"})
                assert await io.omap_get_vals("o") == {"x": b"y"}
                await io.omap_rm_keys("o", ["x"])
                assert await io.omap_get_vals("o") == {}
                # operate(): neorados op through the classic ioctx
                await io.operate("o", WriteOp().append(b"+more"))
                assert await io.read("o") == b"data+more"
            finally:
                await rados.shutdown()
                await cluster.stop()
        run(go())

    def test_reserved_xattr_names_rejected(self):
        async def go():
            cluster = Cluster(n_osds=4, conf=dict(CONF))
            await cluster.start()
            rados = await Rados(cluster.mon_addrs, CONF).connect()
            try:
                await rados.pool_create("neores", pool_type="replicated")
                io = await rados.open_ioctx("neores")
                await io.write_full("o", b"d")
                with pytest.raises(RadosError) as ei:
                    await io.setxattr("o", "snapset_key", b"evil")
                assert ei.value.code == -errno.EINVAL
            finally:
                await rados.shutdown()
                await cluster.stop()
        run(go())
