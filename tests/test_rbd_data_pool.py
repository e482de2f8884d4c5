"""An RBD image with a data pool (`rbd create --data-pool`): header, object
map, snapshots' bookkeeping and every `rbd` class call in the image's own
replicated pool and only there, `rbd_data.*` in the erasure-coded data pool
and only there; open, resize, snapshots, clones, trash and remove with a
data pool; an image without one as it always was."""

import asyncio
import json
import os

import pytest

from ceph_tpu.rados.librados import IoCtx, Rados
from ceph_tpu.rados.vstart import Cluster
from ceph_tpu.services.rbd import RBD, RbdError

CONF = {"osd_auto_repair": False}
EC_PROFILE = {"plugin": "jerasure", "technique": "reed_sol_van",
              "k": "2", "m": "1"}
ORDER = 16  # 64 KiB objects
OBJ = 1 << ORDER


class Spy:
    """What each pool was asked: (pool, verb, oid) of every IoCtx call
    that names an object."""

    VERBS = ("read", "write", "write_full", "remove", "execute")

    def __init__(self, patch) -> None:
        self.calls = []
        for verb in self.VERBS:
            inner = getattr(IoCtx, verb)

            def spied(io, oid, *args, _inner=inner, _verb=verb, **kwargs):
                self.calls.append((io.pool_name, _verb, oid))
                return _inner(io, oid, *args, **kwargs)

            patch.setattr(IoCtx, verb, spied)


def pools_of(calls, prefix: str) -> set:
    return {pool for pool, _verb, oid in calls if oid.startswith(prefix)}


async def _scenario(patch):
    seen = {}
    cluster = Cluster(n_osds=4, conf=dict(CONF))
    await cluster.start()
    try:
        rados = await Rados(cluster.mon_addrs, CONF).connect()
        await rados.pool_create("meta", pool_type="replicated")
        await rados.pool_create("data", profile=EC_PROFILE)
        await rados.pool_create("solo", profile=EC_PROFILE)
        meta, data = (await rados.open_ioctx("meta"),
                      await rados.open_ioctx("data"))
        spy = Spy(patch)
        rbd = RBD(meta)

        # create, write (whole objects, a partial overwrite, a sparse
        # head), read back through a second handle
        img = await rbd.create("vm", 8 * OBJ, order=ORDER, data_pool=data)
        seen["created_data_pool"] = img.data_ioctx.pool_name
        blob = os.urandom(3 * OBJ)
        await img.write(OBJ // 2, blob)
        await img.write(OBJ, b"PATCH")
        want = bytearray(8 * OBJ)
        want[OBJ // 2:OBJ // 2 + len(blob)] = blob
        want[OBJ:OBJ + 5] = b"PATCH"
        again = await rbd.open("vm")
        seen["opened_data_pool"] = again.data_ioctx.pool_name
        seen["opened_meta_pool"] = again.ioctx.pool_name
        seen["read_ok"] = await again.read(0, 8 * OBJ) == bytes(want)
        seen["meta_objects"] = sorted(await meta.list_objects())
        seen["data_objects"] = sorted(await data.list_objects())
        seen["header"] = json.loads(await meta.read("rbd_header.vm"))
        seen["perf"] = img.perf.dump()

        # a snapshot: its id comes from the data pool, its bookkeeping
        # stays in the header, its read resolves in the data pool
        await again.snap_create("s1")
        await again.write(OBJ, b"AFTER")
        seen["snap_read"] = await again.read_snap("s1", OBJ, 5)
        seen["head_read"] = await again.read(OBJ, 5)
        seen["snap_in_header"] = sorted(json.loads(
            await meta.read("rbd_header.vm")).get("snaps", {}))

        # a clone with a data pool of its own, over a parent with one
        await again.snap_protect("s1")
        child = await rbd.clone("vm", "s1", "kid", data_pool=data)
        seen["child_through_parent"] = await child.read(OBJ, 5)
        await child.write(OBJ + 1, b"kid")  # copy-up, then a splice
        seen["child_after_write"] = await child.read(OBJ, 5)
        seen["parent_untouched"] = await again.read_snap("s1", OBJ, 5)
        kid = await rbd.open("kid")
        seen["child_data_pool"] = kid.data_ioctx.pool_name
        await kid.flatten()
        seen["child_flat"] = await kid.read(OBJ, 5)
        await rbd.remove("kid")
        await again.snap_unprotect("s1")
        await again.snap_remove("s1")

        # resize: the dropped objects leave the data pool
        await again.resize(2 * OBJ)
        seen["after_shrink"] = sorted(await data.list_objects())
        seen["shrunk_read"] = await again.read(0, 2 * OBJ) \
            == bytes(want[:OBJ]) + b"AFTER" + bytes(want[OBJ + 5:2 * OBJ])
        await again.resize(4 * OBJ)
        seen["regrown_zeros"] = await again.read(2 * OBJ, 100)

        # the object map, rebuilt by listing the DATA pool
        lost = await rbd.open("vm")
        lost._hdr["object_map"] = []
        seen["rebuilt"] = await lost.rebuild_object_map()

        # trash and back, then remove
        trash_id = await rbd.trash_mv("vm")
        seen["trashed_list"] = await rbd.list()
        back = await rbd.trash_restore(trash_id)
        seen["restored_data_pool"] = back.data_ioctx.pool_name
        seen["restored_read"] = await back.read(OBJ, 5)
        await rbd.remove("vm")
        seen["calls"] = list(spy.calls)  # of the image with a data pool
        seen["meta_after_remove"] = sorted(await meta.list_objects())
        seen["data_after_remove"] = sorted(await data.list_objects())

        # an image without a data pool, on an EC pool alone, as always
        solo_io = await rados.open_ioctx("solo")
        solo = await RBD(solo_io).create("plain", 4 * OBJ, order=ORDER)
        await solo.write(100, b"hello")
        seen["solo_header"] = json.loads(
            await solo_io.read("rbd_header.plain"))
        seen["solo_objects"] = sorted(await solo_io.list_objects())
        seen["solo_read"] = await (await RBD(solo_io).open("plain")).read(
            100, 5)
        seen["solo_same_ioctx"] = solo.data_ioctx is solo.ioctx
        # naming one's own pool as the data pool is having none
        own = await rbd.create("own", OBJ, order=ORDER, data_pool=meta)
        seen["own_header"] = dict(own._hdr)
        # a data pool that is gone
        try:
            await _open_missing(meta)
        except RbdError as e:
            seen["missing"] = str(e)
        await rados.shutdown()
    finally:
        await cluster.stop()
    return seen


async def _open_missing(meta):
    await meta.write_full("rbd_header.ghost", json.dumps(
        {"id": "abc", "size": OBJ, "order": ORDER, "object_map": [],
         "data_pool": "no-such-pool"}).encode())
    await RBD(meta).open("ghost")


@pytest.fixture(scope="module")
def seen():
    patch = pytest.MonkeyPatch()
    try:
        return asyncio.run(asyncio.wait_for(_scenario(patch), 240))
    finally:
        patch.undo()


def test_create_and_open_find_the_data_pool_through_the_header(seen):
    assert seen["created_data_pool"] == "data"
    assert seen["opened_data_pool"] == "data"
    assert seen["opened_meta_pool"] == "meta"
    assert seen["header"]["data_pool"] == "data"
    assert seen["read_ok"]


def test_header_in_the_replicated_pool_and_data_objects_in_the_ec_pool(seen):
    assert seen["meta_objects"] == ["rbd_header.vm"]
    assert len(seen["data_objects"]) == 4
    assert all(o.startswith("rbd_data.") for o in seen["data_objects"])
    assert sorted(seen["header"]["object_map"]) == [0, 1, 2, 3]


@pytest.mark.parametrize("prefix, pool", [
    ("rbd_data.", "data"), ("rbd_header.", "meta"),
    ("rbd_children", "meta"), ("rbd_trash_header.", "meta")])
def test_every_call_that_names_an_object_goes_to_its_pool_only(seen, prefix,
                                                               pool):
    assert pools_of(seen["calls"], prefix) == {pool}


def test_class_calls_land_in_the_replicated_pool_and_succeed(seen):
    calls = [(p, oid) for p, verb, oid in seen["calls"] if verb == "execute"]
    assert calls and {p for p, _ in calls} == {"meta"}
    # with a data pool the client never rewrites the header whole for an
    # object-map update, a snapshot or a resize: the in-OSD class does
    # (trash_restore's one write_full puts the trashed header back)
    assert len([1 for _p, verb, oid in seen["calls"]
                if verb == "write_full" and oid == "rbd_header.vm"]) == 1


def test_writes_are_counted_under_librbd_names(seen):
    perf = seen["perf"]
    assert perf["wr"] == 2 and perf["wr_bytes"] == 3 * OBJ + 5
    assert perf["wr_lat"]["avgcount"] == 2 and perf["wr_lat"]["sum"] > 0


def test_a_snapshot_reads_the_data_pool_at_its_snap_id(seen):
    assert seen["snap_read"] == b"PATCH" and seen["head_read"] == b"AFTER"
    assert seen["snap_in_header"] == ["s1"]


def test_a_clone_with_a_data_pool_copies_up_and_flattens(seen):
    assert seen["child_through_parent"] == b"PATCH"
    assert seen["child_after_write"] == b"PkidH"
    assert seen["parent_untouched"] == b"PATCH"
    assert seen["child_data_pool"] == "data"
    assert seen["child_flat"] == seen["child_after_write"]


def test_resize_drops_and_regrows_in_the_data_pool(seen):
    assert len(seen["after_shrink"]) == 2 and seen["shrunk_read"]
    assert seen["regrown_zeros"] == b"\x00" * 100


def test_the_object_map_is_rebuilt_from_the_data_pool(seen):
    assert seen["rebuilt"] == 2


def test_trash_keeps_the_data_pool_and_remove_empties_both(seen):
    assert seen["trashed_list"] == []
    assert seen["restored_data_pool"] == "data"
    assert seen["restored_read"] == b"AFTER"
    assert seen["meta_after_remove"] == ["rbd_children"]  # the registry
    assert seen["data_after_remove"] == []


def test_an_image_without_a_data_pool_is_unchanged(seen):
    assert "data_pool" not in seen["solo_header"]
    assert seen["solo_same_ioctx"] and seen["solo_read"] == b"hello"
    assert "rbd_header.plain" in seen["solo_objects"]
    assert any(o.startswith("rbd_data.") for o in seen["solo_objects"])
    assert "data_pool" not in seen["own_header"]


def test_a_header_naming_a_pool_that_is_gone_refuses_to_open(seen):
    assert "no-such-pool" in seen["missing"]
