"""`rados bench <s> write` on a pool whose OSDs keep their shards on a disk
store: the window is `closed_loop_put`'s (full-object puts to new names,
closed loop), and the run is then held to what the store is for:

    reopen    every OSD's directory is copied, each file cut to the bytes
              its store had synced (what a power cut now would leave), and
              a second BlueStore opens the copy: WAL replay, deferred
              flush, allocator rebuilt.  It has to hold, for every object
              acknowledged in the window and some of set-up's, each shard
              the OSD held, equal to the plain reference's chunk, passing
              the extent's checksum and the crc in the shard's meta.  One
              OSD's store is also followed operation by operation by
              references/durable_store.py, and its copy has to equal that
              reference after a crash, key for key.
    restart   the OSDs are killed (no flush, no goodbye to their stores)
              and started again on their directories; acknowledged
              objects come back through the client, whole.

At each ack every shard position is in some live OSD's store by `stat`
(the onode is there), not by reading 5.8 MB back through checksums inside
the timed loop.  A program that does not build the configuration's object
store fails in set-up, with no metric."""

from __future__ import annotations

import asyncio
import os
import shutil
import time

from benchmarks import verify
from benchmarks.generators import closed_loop_put
from benchmarks.references.durable_store import DurableStore

OP = closed_loop_put.OP
COPY_CHUNK = 16 << 20
HEALTHY_WAIT_S = 120.0


def meta_of(meta) -> tuple:
    return (meta.version, meta.object_size, meta.chunk_crc)


def tap(store, ref: DurableStore) -> None:
    """Every mutation of `store` goes to `ref` as well, with copies of the
    bytes; a commit is reported when the store's call returns, which is
    when its caller goes on to acknowledge."""
    def commits(fn, ops_of):
        def call(*args, **kwargs):
            index = ref.submit(ops_of(*args))
            try:
                out = fn(*args, **kwargs)
            except BaseException:
                ref.log.pop()  # refused before anything changed
                raise
            ref.commit_reported(index)
            return out
        return call

    def txn_ops(txn, on_commit=None):
        return ([("delete", key) for key in txn.deletes]
                + [("write", key, bytes(getattr(chunk, "view", chunk)),
                    meta_of(meta)) for key, chunk, meta in txn.writes]
                + [("write_at", key, off, bytes(data), size, meta_of(meta),
                    prev) for key, off, data, size, meta, prev in txn.ranged]
                + [("omap_set", key, dict(kv)) for key, kv in txn.omap_sets]
                + [("omap_rm", key, list(ks)) for key, ks in txn.omap_rms])

    store.queue_transaction = commits(store.queue_transaction, txn_ops)
    store.setattr = commits(
        store.setattr, lambda key, name, value: [("setattr", key, name,
                                                  value)])
    store.rmattr = commits(
        store.rmattr, lambda key, name: [("rmattr", key, name)])
    store.omap_set = commits(
        store.omap_set, lambda key, kv: [("omap_set", key, dict(kv))])
    store.omap_rm = commits(
        store.omap_rm, lambda key, ks: [("omap_rm", key, list(ks))])


def crash_copy(src: str, dst: str, lengths: dict) -> int:
    """`dst` = the files of `src` that a sync has covered, each cut to the
    bytes it covered; returns the bytes copied."""
    copied = 0
    for rel, n in lengths.items():
        os.makedirs(os.path.dirname(os.path.join(dst, rel)), exist_ok=True)
        with open(os.path.join(src, rel), "rb") as fin, \
                open(os.path.join(dst, rel), "wb") as fout:
            left = n
            while left > 0:
                buf = fin.read(min(COPY_CHUNK, left))
                if not buf:
                    break
                fout.write(buf)
                left -= len(buf)
            copied += n - left
    return copied


class Generator(closed_loop_put.Generator):
    def __init__(self, env) -> None:
        super().__init__(env)
        stores = {i: osd.store for i, osd in env.cluster.osds.items()}
        kinds = sorted({type(s).__name__ for s in stores.values()})
        paths = [getattr(s, "path", None) for s in stores.values()]
        if kinds != ["BlueStore"] or not all(paths):
            raise RuntimeError(
                f"the configuration states osd_objectstore "
                f"{env.cell.config['conf'].get('osd_objectstore')!r}; the "
                f"program built {kinds} (on disk: {all(paths)}): it does "
                f"not run this deployment")
        self.root = os.path.dirname(paths[0])
        free = shutil.disk_usage(self.root).free
        need = int(self.t["disk"]["min_free_bytes"])
        env.emit("object_store", store=kinds[0], osds=len(stores),
                 data_dir=self.root, free_bytes=free, needs_free_bytes=need)
        if free < need:
            raise RuntimeError(
                f"{free} bytes free under {self.root}, the cell needs "
                f"{need}: not filling the disk")
        # the guarantee at the ack, by the onode
        env.put = self._put_by_stat
        self.tapped = min(stores)
        self.model = DurableStore()
        tap(stores[self.tapped], self.model)
        self.setup_names: dict = {}
        self._tails: dict = {}  # pool buffer -> its shards past a stripe
        self._heads: dict = {}  # object -> its shards' first stripe

    async def _put_by_stat(self, oid: str, data: bytes) -> None:
        env = self.env
        await env.client.put(env.pool, oid, data)
        held = sum(
            1 for pos in range(env.n_shards)
            if any(osd.store.stat((env.pool, oid, pos)) is not None
                   for osd in env.cluster.osds.values()))
        if held < env.n_shards:
            env.acked_without_all_shards += 1

    async def setup(self) -> None:
        await super().setup()
        n = int(self.t["verify"]["reopen_setup_objects"])
        first = max(0, self.next_index - n)
        self.setup_names = {self.payloads.name(i): i
                            for i in range(first, self.next_index)}

    # -- the reference's shards, without encoding 700 objects whole ---------

    def _equals_reference(self, i: int, shard: int, data) -> bool:
        """Object i is buffer i % pool under its own 8-byte stamp, and a
        shard is the concatenation of its chunk of every stripe: so the
        reference's shards of object i are its shards of the first stripe
        of object i, then its shards of the pool's buffer from the second
        stripe on (32 whole encodes a run, and a stripe an object)."""
        cfg = self.env.cell.config
        unit = int(cfg["stripe_unit"])
        width = unit * int(cfg["profile"]["k"])
        if self.t["object_bytes"] < width:
            return bytes(data) == bytes(
                self.env.reference(self.payloads.data(i))[shard])
        j = i % self.t["payload_pool"]
        if j not in self._tails:
            self._tails[j] = [memoryview(s)[unit:] for s in
                              self.env.reference(self.payloads.data(j))]
        if i not in self._heads:
            self._heads[i] = [bytes(s) for s in self.env.reference(
                self.payloads.data(i)[:width])]
        data = memoryview(data)
        return data[:unit] == self._heads[i][shard] \
            and data[unit:] == self._tails[j][shard]

    # -- after the window ----------------------------------------------------

    def _acked(self) -> list:
        return [r[0] for r in sorted(self.records, key=lambda r: r[2])
                if r[3]]

    def _reopen_osd(self, src: str, dst: str, lengths: dict, names: dict,
                    model, found: dict, bad: dict) -> tuple:
        """One OSD's store from a copy of its directory cut to `lengths`
        (on a thread of its own: the cluster's loop goes on beating).
        Returns (bytes copied, shards compared)."""
        from ceph_tpu.rados.bluestore import BlueStore
        from ceph_tpu.utils.checksum import checksum

        env = self.env
        copied, compared = crash_copy(src, dst, lengths), 0
        again = BlueStore(dst, dict(env.cell.config["conf"]))
        try:
            for oid, shard in again.list_objects(env.pool):
                if oid not in names or not 0 <= shard < env.n_shards:
                    continue
                try:
                    data, meta = again.read((env.pool, oid, shard))
                except IOError:
                    bad["read_failed"] += 1
                    continue
                compared += 1
                found[oid].add(shard)
                if not self._equals_reference(names[oid], shard, data):
                    bad["differing"] += 1
                if checksum(data) & 0xFFFFFFFF != meta.chunk_crc:
                    bad["meta_crc"] += 1
            if model is not None:
                self._against_model(again, model, bad)
        finally:
            again.abandon()
            shutil.rmtree(dst, ignore_errors=True)
        return copied, compared

    async def _reopen(self, names: dict) -> list:
        """Every OSD's store from a copy of its directory cut to its
        synced bytes, held to the reference's shards of `names`
        ({oid: index}); the tapped OSD's to the durable-store reference
        besides."""
        env, t0 = self.env, time.perf_counter()
        loop = asyncio.get_running_loop()
        found = {oid: set() for oid in names}
        bad = {"differing": 0, "meta_crc": 0, "read_failed": 0,
               "no_synced_lengths": 0, "model": 0}
        copied = compared = 0
        for osd_id, osd in sorted(env.cluster.osds.items()):
            store = osd.store
            lengths = getattr(store, "synced_lengths", None)
            if lengths is None:
                bad["no_synced_lengths"] += 1
                continue
            # the lengths and the reference's state at one instant
            lengths = lengths()
            model = self.model.crash().as_dicts() \
                if osd_id == self.tapped else None
            nbytes, n = await loop.run_in_executor(
                None, self._reopen_osd, store.path,
                os.path.join(self.root, f"reopen.{osd_id}"), lengths,
                names, model, found, bad)
            copied += nbytes
            compared += n
        missing = sum(env.n_shards - len(s) for s in found.values())
        env.emit("reopen", osds=len(env.cluster.osds), objects=len(names),
                 shards_compared=compared, copied_bytes=copied,
                 tapped_osd=self.tapped,
                 model_transactions=len(self.model.log),
                 seconds=time.perf_counter() - t0)
        return [
            verify.at_least("reopen_objects", len(names),
                            max(1, len(self._acked()))),
            verify.at_least("reopen_shards_compared", compared,
                            env.n_shards * len(names)),
            verify.at_most("reopen_shards_missing", missing),
            verify.at_most("reopen_shards_differing_from_reference",
                           bad["differing"]),
            verify.at_most("reopen_shards_failing_stored_checksum",
                           bad["read_failed"] + bad["meta_crc"]),
            verify.at_most("reopen_stores_without_synced_lengths",
                           bad["no_synced_lengths"]),
            verify.at_least("durable_model_transactions",
                            len(self.model.log)),
            verify.at_most("reopen_differs_from_durable_model",
                           bad["model"])]

    @staticmethod
    def _against_model(again, model: tuple, bad: dict) -> None:
        """The reopened store of the tapped OSD against what the
        reference holds after a crash: every key, its bytes and meta, its
        xattrs, its omap."""
        objects, xattrs, omap = model
        have = {}
        for pid in again.list_pools():
            for oid, shard in again.list_objects(pid):
                key = (pid, oid, shard)
                try:
                    data, meta = again.read(key)
                except IOError:
                    have[key] = None
                    continue
                have[key] = (bytes(data), meta_of(meta))
        # an xattr set on a name that holds no object makes an empty one
        # in this store; the reference keeps the xattr alone
        have = {k: v for k, v in have.items()
                if k in objects or v != (b"", (0, 0, 0))}
        bad["model"] += sum(
            1 for k in set(have) | set(objects)
            if have.get(k) != objects.get(k))
        bad["model"] += sum(
            1 for k, want in xattrs.items() if again.getattrs(k) != want)
        bad["model"] += sum(
            1 for k, want in omap.items() if again.omap_get(k) != want)

    async def _restart(self, acked: list) -> list:
        """Kill every OSD, start it again on its directory, and read
        acknowledged objects back through the client."""
        env, v, t0 = self.env, self.t["verify"], time.perf_counter()
        cluster, client = env.cluster, env.client
        n_osds = len(cluster.osds)
        await cluster.restart_osds()
        restarted_s = time.perf_counter() - t0
        deadline = time.monotonic() + HEALTHY_WAIT_S
        while True:
            await client.refresh_map()
            osds = client.osdmap.osds.values()
            up_in = sum(1 for o in osds if o.up and o.in_cluster)
            checks = sorted((await client.get_health()).get("checks") or {})
            healthy = up_in == n_osds and "PG_DEGRADED" not in checks
            if healthy or time.monotonic() > deadline:
                break
            await asyncio.sleep(0.5)
        healthy_s = time.perf_counter() - t0
        picked = verify.sample(acked, v["restart_objects"],
                               v["restart_last_acked"], env.seed + 1)
        index_of = {self.payloads.name(i): i for i in picked}
        back = await verify.readback(
            client, env.pool, index_of,
            lambda oid: self.payloads.data(index_of[oid]))
        # of the daemons started on the directories: shards that recovery
        # wrote anew (none, or the read proves less), shards read for gets
        pushed, shard_reads = (sum(o.perf.get(key)
                                   for o in cluster.osds.values())
                               for key in ("recovery_push", "subop_r"))
        env.emit("restart", osds=n_osds, restarted_s=restarted_s,
                 healthy=healthy, healthy_s=healthy_s, up_and_in=up_in,
                 health_checks=checks, epoch=client.osdmap.epoch,
                 recovery_push=pushed, shard_reads=shard_reads,
                 objects_read=len(picked),
                 seconds=time.perf_counter() - t0,
                 stores=sorted({type(o.store).__name__
                                for o in cluster.osds.values()}))
        return [
            verify.check("restart_healthy", healthy, True, healthy),
            verify.at_most("restart_recovery_push", pushed),
            verify.at_least("restart_shard_reads", shard_reads),
            verify.at_least("restart_objects_compared", len(picked),
                            max(1, min(v["restart_objects"], len(acked)))),
            verify.check("restart_" + back["name"], back["value"],
                         back["limit"], back["ok"])]

    async def verify(self) -> list:
        checks = await super().verify()
        acked = self._acked()
        names = dict(self.setup_names)
        names.update((self.payloads.name(i), i) for i in acked)
        checks += await self._reopen(names)
        checks += await self._restart(acked)
        return checks

    def counter_checks(self, moved: dict) -> list:
        """The window's transactions were all committed under a sync of
        the block file and of the WAL, none of a shard's bytes rode a WAL
        record, and nothing compiled or left the queue."""
        puts = sum(1 for r in self.records if r[3])
        per_put = self.env.n_shards

        def got(key):
            return moved.get(key, 0)

        return super().counter_checks(moved) + [
            verify.at_least("bluestore.txns", got("bluestore.txns"),
                            per_put * puts),
            verify.at_least("bluestore.commit_under_sync",
                            got("bluestore.commit_under_sync"),
                            per_put * puts),
            verify.at_most("bluestore.commit_unsynced",
                           got("bluestore.commit_unsynced")),
            verify.at_least("bluestore.block_syncs",
                            got("bluestore.block_syncs"), per_put * puts),
            verify.at_least("bluestore.wal_syncs",
                            got("bluestore.wal_syncs"), per_put * puts),
            verify.at_most("bluestore.deferred_writes",
                           got("bluestore.deferred_writes")),
            verify.at_most("window_compile_s",
                           got("compile_meter.compile_s"),
                           self.t["verify"]["window_compile_s_at_most"]),
            verify.at_most("direct_dispatches",
                           got("ec_plugin.apply")
                           + got("ec_plugin.apply_rows"))]
