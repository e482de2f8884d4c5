"""Tests of what PR 46 adds to the benchmark: the configuration
`ec-k8m3-rs-bluestore`, the traffic `rados-bench-write-4m-t16-durable`
with its generator, the reference `durable_store.py`, the cell
`k8m3.write4m-bluestore` and its seven `.bs` metrics.  CPU only; the run of
the whole harness goes through `run.py --rehearse` in a child process.
(tests/test_bluestore_crash.py holds BlueStore to the reference under
power cuts; tests/test_store_seam.py how the conf picks the store.)

    python -m pytest benchmarks/tests/test_bluestore_cell.py -q
"""

from __future__ import annotations

import asyncio
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import counters, layers, manifest  # noqa: E402
from benchmarks.generators import (closed_loop_put,  # noqa: E402
                                   closed_loop_put_durable)

CELL, CONFIG, TRAFFIC = ("k8m3.write4m-bluestore", "ec-k8m3-rs-bluestore",
                         "rados-bench-write-4m-t16-durable")
BS_METRICS = {"commit_ms.bs": "put_p95_ms", "syncs_per_op.bs": "put_MBps",
              "sync_ms_per_op.bs": "put_MBps", "txns_per_sync.bs": "put_MBps",
              "disk_bytes_per_user_byte.bs": "put_MBps",
              "compact_ms_per_op.bs": "put_p95_ms",
              "loop_sync_share.bs": "put_MBps"}


def config(name=CONFIG):
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           name + ".json")) as f:
        return json.load(f)


def test_the_cell_resolves_to_its_files_by_name():
    spec = manifest.load()
    cell = manifest.resolve(spec, CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        CONFIG, TRAFFIC, 1)
    assert cell.traffic["kind"] == "closed_loop_put_durable"
    assert closed_loop_put_durable.OP == "put"
    assert issubclass(closed_loop_put_durable.Generator,
                      closed_loop_put.Generator)
    assert [m["name"] for m in cell.end_to_end] == [
        "put_MBps", "put_p95_ms", "setup_s"]
    names = {m["name"] for m in cell.per_layer}
    assert set(BS_METRICS) <= names <= set(layers.available())
    by_name = {m["name"]: m for m in spec["per_layer"]}
    for name, moves in BS_METRICS.items():
        m = by_name[name]
        assert (m["moves"], m["layer"], m["workloads"], m["source"]) == (
            moves, "stores", [CELL], "program_counter")
        with open(os.path.join(layers.DIR, name + ".json")) as f:
            assert json.load(f)["source"] == "perf_counter"


def test_the_cell_reads_every_put_metric_of_the_memstore_cell():
    spec = manifest.load()
    mine = {m["name"] for m in manifest.resolve(spec, CELL).per_layer}
    theirs = {m["name"] for m in
              manifest.resolve(spec, "k8m3.write4m").per_layer}
    assert theirs <= mine
    assert mine - theirs == set(BS_METRICS)
    for m in spec["per_layer"] + spec["end_to_end"]:
        if CELL in m.get("workloads", []):
            assert m["workloads"][-1] == CELL  # appended, nothing moved


def test_the_traffic_is_the_memstore_cells_window():
    spec = manifest.load()
    mine = manifest.resolve(spec, CELL).traffic
    theirs = manifest.resolve(spec, "k8m3.write4m").traffic
    for key in ("object_bytes", "in_flight", "payload_pool", "name_prefix",
                "warmup", "trace"):
        assert mine[key] == theirs[key], key
    assert (mine["object_bytes"], mine["in_flight"]) == (4194304, 16)
    for key, val in theirs["verify"].items():
        assert mine["verify"][key] == val
    assert mine["verify"]["restart_objects"] == 64
    assert mine["verify"]["restart_last_acked"] == 16
    assert mine["verify"]["reopen_setup_objects"] == 32
    assert mine["verify"]["window_compile_s_at_most"] == 0.0
    assert mine["disk"]["min_free_bytes"] == 12_000_000_000


def test_the_deployment_is_ec_k8m3_rs_on_another_store():
    mine, base = config(), config("ec-k8m3-rs")
    for key, val in base.items():
        if key in ("source", "deployment", "conf", "guarantees", "assumed",
                   "reduced", "rehearse"):
            continue
        assert mine[key] == val, key
    for key, val in base["conf"].items():
        assert mine["conf"][key] == val, key
    assert {k: v for k, v in mine["conf"].items()
            if k not in base["conf"]} == {
        "osd_objectstore": "bluestore", "bluestore_csum_type": "crc32c",
        "bluestore_compression_mode": "none",
        "bluestore_prefer_deferred_size": 32768}
    for key, val in base["guarantees"].items():
        assert mine["guarantees"][key] == val
    assert set(mine["guarantees"]) - set(base["guarantees"]) == {
        "durable_at_ack", "reopens_exact"}
    for key, val in base["reduced"].items():
        assert mine["reduced"][key] == val
    assert set(mine["reduced"]) - set(base["reduced"]) == {"block_devices"}
    assert "tmpfs" not in json.dumps(mine["reduced"])
    assert "MemStore" not in mine["assumed"]["object_store"]
    assert len(mine["source"]) <= 200
    entry = {c["name"]: c for c in manifest.load()["configs"]}[CONFIG]
    assert entry["source"] == mine["source"]
    assert sorted(entry["reduced"]) == sorted(mine["reduced"])
    for name in ("osd_objectstore", "osd_data"):  # the old files name neither
        for other in manifest.load()["configs"]:
            if other["name"] != CONFIG:
                assert name not in config(other["name"])["conf"]


def test_the_derived_block_follows_from_the_profile_and_the_store():
    from ceph_tpu.rados.ecutil import StripeInfo

    cfg = config()
    d, k, m = cfg["derived"], int(cfg["profile"]["k"]), int(
        cfg["profile"]["m"])
    si = StripeInfo(k, k * int(cfg["stripe_unit"]))
    size = 4194304
    assert size % si.stripe_width == 0
    shard = size // si.stripe_width * si.chunk_size
    assert d["shard_bytes"] == shard == 524288
    assert d["transactions_per_put"] == d["big_writes_per_put"] == k + m
    assert d["extent_bytes_per_put"] == (k + m) * shard
    assert shard > cfg["conf"]["bluestore_prefer_deferred_size"]
    assert d["deferred_data_writes_per_put"] == 0
    assert d["block_syncs_per_put"] == k + m
    assert d["wal_syncs_per_put"] == d["kv_batches_per_put"] == 2 * (k + m)
    assert d["syncs_per_put"] == 3 * (k + m)
    assert d["disk_bytes_per_user_byte_at_least"] == (k + m) / k
    assert (d["extent_bytes_per_put"] + d["wal_bytes_per_put_at_most"]) \
        / size <= d["disk_bytes_per_user_byte_at_most"]


def test_the_derived_block_is_what_a_served_put_does_to_the_stores():
    """12 OSDs on BlueStores as the conf builds them, the pool's shapes
    on the CPU plugin: the window's counters per put are the file's."""
    from ceph_tpu.rados.vstart import Cluster

    cfg = config()
    d = cfg["derived"]
    conf = {k: v for k, v in cfg["conf"].items()
            if k.startswith(("osd_objectstore", "bluestore_",
                             "osd_heartbeat", "mon_osd"))}
    profile = dict(cfg["profile"], plugin="jerasure")
    puts = 3

    async def go():
        cluster = Cluster(n_osds=cfg["osds"], conf=conf, n_mons=1)
        await cluster.start()
        try:
            client = await cluster.client()
            pool = await client.create_pool("bench", pg_num=cfg["pg_num"],
                                            profile=profile)

            def snap():
                return counters.snapshot(
                    [o.ctx.perf for o in cluster.osds.values()])

            await client.put(pool, "benchmark_data_4600000000_0",
                             os.urandom(4194304))
            before = snap()
            for i in range(1, puts + 1):
                await client.put(pool, f"benchmark_data_4600000000_{i}",
                                 os.urandom(4194304))
            moved = counters.delta(snap(), before)
            kinds = {type(o.store).__name__ for o in cluster.osds.values()}
            await client.stop()
            return moved, kinds
        finally:
            await cluster.stop()

    moved, kinds = asyncio.run(go())
    assert kinds == {"BlueStore"}

    def per_put(key):
        return moved["bluestore." + key] / puts

    assert per_put("txns") == d["transactions_per_put"]
    assert per_put("big_writes") == d["big_writes_per_put"]
    assert per_put("deferred_writes") == d["deferred_data_writes_per_put"]
    assert per_put("block_write_bytes") == d["extent_bytes_per_put"]
    assert per_put("block_syncs") == d["block_syncs_per_put"]
    assert per_put("wal_syncs") == d["wal_syncs_per_put"]
    assert per_put("sync_s.count") == d["syncs_per_put"]
    assert per_put("commit_under_sync") == d["transactions_per_put"]
    assert moved["bluestore.commit_unsynced"] == 0
    assert 0 < per_put("wal_bytes") <= d["wal_bytes_per_put_at_most"]
    ratio = (per_put("block_write_bytes") + per_put("wal_bytes")) / 4194304
    assert d["disk_bytes_per_user_byte_at_least"] <= ratio \
        <= d["disk_bytes_per_user_byte_at_most"]


def test_the_bs_metrics_read_the_bluestore_set_and_nothing_without_it():
    moved = {"bluestore.commit_lat.sum": 0.5, "bluestore.commit_lat.count": 250,
             "bluestore.block_syncs": 110, "bluestore.wal_syncs": 220,
             "bluestore.sync_s.sum": 0.33, "bluestore.txns": 110,
             "bluestore.block_write_bytes": 57671680,
             "bluestore.wal_bytes": 140000, "bluestore.compact_s.sum": 0.0,
             "bluestore.loop_sync_s.sum": 0.33, "loop.busy.sum": 3.3,
             "objecter.op": 10, "osd.write_adopted_bytes": 41943040,
             "osd.write_copied_bytes": 0}
    ctx = {"counters": moved}
    got = {name: layers.read(name, ctx) for name in BS_METRICS}
    assert got["commit_ms.bs"] == pytest.approx(2.0)
    assert got["syncs_per_op.bs"] == 33
    assert got["sync_ms_per_op.bs"] == pytest.approx(33.0)
    assert got["txns_per_sync.bs"] == 0.5
    assert got["disk_bytes_per_user_byte.bs"] == pytest.approx(
        (57671680 + 140000) / 41943040)
    assert got["compact_ms_per_op.bs"] == 0.0
    assert got["loop_sync_share.bs"] == pytest.approx(10.0)
    # the parent program has no such set: every reader finds nothing
    bare = {"counters": {k: v for k, v in moved.items()
                         if not k.startswith("bluestore.")}}
    assert all(layers.read(name, bare) is None for name in BS_METRICS)


def test_a_program_that_builds_another_store_fails_in_set_up():
    from types import SimpleNamespace

    from ceph_tpu.rados.store import MemStore

    spec = manifest.load()
    cell = manifest.resolve(spec, CELL, rehearse=True)
    osds = {i: SimpleNamespace(store=MemStore()) for i in range(3)}
    env = SimpleNamespace(cell=cell, seed=1, emit=lambda *a, **k: None,
                          cluster=SimpleNamespace(osds=osds))
    with pytest.raises(RuntimeError, match="does not run this deployment"):
        closed_loop_put_durable.Generator(env)


def test_a_crash_copy_is_cut_to_the_synced_lengths(tmp_path):
    src, dst = tmp_path / "src", tmp_path / "dst"
    os.makedirs(src / "db")
    (src / "block").write_bytes(b"0123456789")
    (src / "db" / "wal.log").write_bytes(b"abcdef")
    (src / "db" / "never_synced").write_bytes(b"zzz")
    n = closed_loop_put_durable.crash_copy(
        str(src), str(dst), {"block": 4, "db/wal.log": 6})
    assert n == 10
    assert (dst / "block").read_bytes() == b"0123"
    assert (dst / "db" / "wal.log").read_bytes() == b"abcdef"
    assert not (dst / "db" / "never_synced").exists()


def rehearse(*more):
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", "4600000007", "--seconds", "3",
         "--rehearse", *more],
        capture_output=True, text=True, timeout=600, cwd=ROOT)
    lines = [json.loads(line) for line in out.stdout.splitlines()]
    return out.returncode, lines


def test_the_cell_rehearses_on_bluestore_and_would_be_correct():
    rc, lines = rehearse()
    last = lines[-1]
    assert rc == 3 and last["rehearsal"], last
    by_phase = {line.get("phase"): line for line in lines[:-1]}
    assert by_phase["object_store"]["store"] == "BlueStore"
    assert not os.path.exists(by_phase["object_store"]["data_dir"])
    checks = {c["name"]: c for c in by_phase["verify"]["checks"]}
    assert [c for c in checks.values() if not c["ok"]] == []
    assert last["would_be_correct"] is True and last["failed"] == 0
    assert set(last["metrics"]) == {"put_MBps", "put_p95_ms", "setup_s"}
    n = by_phase["reopen"]
    assert n["osds"] == 12 and n["shards_compared"] == 11 * n["objects"]
    assert checks["reopen_objects"]["value"] >= last["attempted"] - 16
    assert by_phase["restart"]["stores"] == ["BlueStore"]
    assert checks["restart_objects_compared"]["value"] == 8
    assert checks["bluestore.txns"]["value"] >= 11 * (last["attempted"] - 16)
    assert checks["bluestore.commit_unsynced"]["value"] == 0
    assert checks["bluestore.deferred_writes"]["value"] == 0
    assert checks["durable_model_transactions"]["value"] > 0


@pytest.mark.parametrize("kind", ["store_flip", "store_drop", "reply_flip"])
def test_a_control_ends_not_correct(kind):
    rc, lines = rehearse("--control", kind)
    # 3 as a rule; a broken cluster may still be recovering on the queue's
    # thread when the interpreter goes, and that teardown can abort (134)
    # after the last line
    assert rc != 0
    assert lines[-1]["would_be_correct"] is False
    failed = [c["name"] for c in
              next(line for line in lines if line.get("phase") == "verify")[
                  "checks"] if not c["ok"]]
    if kind == "reply_flip":
        assert "readback_objects_not_identical" in failed
    else:
        assert "reopen_shards_differing_from_reference" in failed
    if kind == "store_flip":
        assert "reopen_shards_failing_stored_checksum" in failed
