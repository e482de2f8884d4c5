"""Tests of what PR 43 adds to the benchmark: the configuration
`ec-k8m3-rs-rbd`, the traffic `fio-rbd-randwrite-4k-qd32` with its generator
and its block model, the cell `k8m3.rbd-randwrite4k` and its per-layer
metrics.  CPU only; the runs of the whole harness go through `run.py
--rehearse` in a child process.  (tests/test_rbd_randwrite_model.py holds
the program to the model and the generator's pure parts;
tests/test_rbd_data_pool.py the image's two pools.)

    python -m pytest benchmarks/tests/test_rbd.py -q
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import layers, manifest  # noqa: E402
from benchmarks.generators import closed_loop_rbd_write  # noqa: E402

CELL, CONFIG, TRAFFIC = ("k8m3.rbd-randwrite4k", "ec-k8m3-rs-rbd",
                         "fio-rbd-randwrite-4k-qd32")
NEW_METRICS = {
    "rmw_cached_share.rbd", "rmw_extent_share.rbd",
    "rmw_shard_read_share.rbd", "rmw_full_rewrite_share.rbd",
    "rmw_read_ms.rbd", "rmw_copied_bytes_per_op.rbd",
    "splice_crc_bytes_per_op.rbd", "subwrite_bytes_per_user_byte.rbd",
    "rbd_wr_ms.rbd", "osd_op_w_ms.rbd", "put_p95_ms.rbd"}
ARM_SHARES = ("rmw_cached_share.rbd", "rmw_extent_share.rbd",
              "rmw_shard_read_share.rbd", "rmw_full_rewrite_share.rbd")
NEW_GUARANTEES = {"block_latest_acked", "neighbours_untouched",
                  "any_source_exact"}


def test_the_cell_resolves_to_its_files_by_name():
    spec = manifest.load()
    cell = manifest.resolve(spec, CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        CONFIG, TRAFFIC, 1)
    assert cell.traffic["kind"] == "closed_loop_rbd_write"
    assert closed_loop_rbd_write.OP == "put"
    assert [m["name"] for m in cell.end_to_end] == ["put_MBps", "setup_s"]
    names = {m["name"] for m in cell.per_layer}
    assert NEW_METRICS <= names <= set(layers.available())
    assert all(m["moves"] == "put_MBps" for m in cell.per_layer)
    # the layers a put crosses here as in the write cells; the encode is
    # apply_packedbit_fn's program and has no roofline share of its own
    assert {"loop_busy_share.put", "osd_self_ms.put", "op_msg_loop_ms.put",
            "group_size.put", "dispatch_dev_ms.put",
            "ec_kernel_hbm_share.put", "device_idle_share.put",
            "rx_copy_share.put", "ack_frames_per_op.put"} <= names
    for m in spec["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL] and m["moves"] == "put_MBps"
    entry = next(c for c in spec["configs"] if c["name"] == CONFIG)
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert entry["source"] == cell.config["source"]
    assert len(entry["source"]) <= 200
    assert sorted(entry["reduced"]) == sorted(cell.config["reduced"])


def test_the_configuration_is_ec_k8m3_rs_with_an_image_and_three_guarantees():
    spec = manifest.load()
    base = manifest.resolve(spec, "k8m3.write4m").config
    cell = manifest.resolve(spec, CELL)
    cfg, t = cell.config, cell.traffic
    for key in ("profile", "stripe_unit", "osds", "mons", "pg_num", "chips",
                "conf", "jax_config", "reference"):
        assert cfg[key] == base[key], key
    assert set(cfg["reduced"]) - set(base["reduced"]) == {"image.bytes"}
    assert cfg["image"] == {"bytes": 2 << 30, "order": 22, "meta_pool": {
        "type": "replicated", "size": 3, "pg_num": 32}}
    for name, text in base["guarantees"].items():
        assert cfg["guarantees"][name].startswith(text)
    assert set(cfg["guarantees"]) - set(base["guarantees"]) == NEW_GUARANTEES
    assert len(cfg["guarantees"]) == 7
    assert set(base["assumed"]) <= set(cfg["assumed"])
    assert (t["block_bytes"], t["in_flight"]) == (4096, 32)
    assert t["precondition"] == dict(t["precondition"], piece_bytes=4 << 20,
                                     in_flight=16)
    assert t["warmup"]["one_stripe_rounds"] == [1, 2, 4, 8, 16, 32]
    assert t["warmup"]["still_writes"] == 256
    assert (t["verify"]["last_acked"], t["verify"]["drawn"]) == (32, 256)
    # the rehearsal keeps every shape and cuts the scale
    small = manifest.resolve(spec, CELL, rehearse=True)
    assert small.config["image"] == dict(cfg["image"], bytes=32 << 20)
    assert small.config["conf"]["osd_ec_planar_bytes"] == 64 << 20
    assert small.traffic["block_bytes"] == 4096


def test_derived_block_equals_what_the_code_computes():
    """The image's figures, on the program's own StripeInfo, resident
    store (host arm, one object) and extent cache."""
    from ceph_tpu.ec.registry import registry
    from ceph_tpu.rados.ecutil import StripeInfo
    from ceph_tpu.rados.extent_cache import ExtentCache
    from ceph_tpu.rados.pagestore import PagedResidentStore

    cfg = manifest.resolve(manifest.load(), CELL).config
    d, image = cfg["derived"], cfg["image"]
    k, m = int(cfg["profile"]["k"]), int(cfg["profile"]["m"])
    codec = registry.factory("jerasure", "", dict(cfg["profile"],
                                                  plugin="jerasure"))
    sinfo = StripeInfo(k, codec.get_chunk_size(k * cfg["stripe_unit"]) * k)
    obj = 1 << image["order"]
    assert obj == d["object_bytes"]
    assert image["bytes"] // obj == d["data_objects"] == 512
    assert image["bytes"] // d["block_bytes"] == d["blocks"] == 1 << 19
    assert sinfo.stripe_width == d["stripe_bytes"] == 32768
    assert image["bytes"] // sinfo.stripe_width == d["stripes"] == 65536
    assert obj // sinfo.stripe_width == d["stripes_per_object"]
    assert sinfo.stripe_width // d["block_bytes"] == d["blocks_per_stripe"]
    # block b of an object: stripe b // 8, data shard b % 8, chunk offset
    # (b // 8) * 4096 of every shard; one stripe is touched and re-encoded
    for b in (0, 7, 8, 1023, 517):
        s0, slen = sinfo.offset_len_to_stripe_bounds(b * 4096, 4096)
        assert (s0, slen) == (b // 8 * 32768, 32768)
        assert sinfo.aligned_logical_offset_to_chunk_offset(s0) \
            == (b // 8) * 4096
        assert (b * 4096 - s0) // sinfo.chunk_size == b % 8
    assert d["splices_per_write"] == k + m
    assert (k + m) * sinfo.chunk_size == d["subwrite_payload_bytes_per_write"]
    assert d["subwrite_payload_bytes_per_write"] / d["block_bytes"] \
        == d["subwrite_bytes_per_user_byte"] == 11.0
    shard = sinfo.logical_to_next_chunk_offset(obj)
    assert shard == d["shard_bytes"]
    assert d["data_objects"] * (k + m) * shard == d["shard_store_bytes"]

    store = PagedResidentStore(capacity_bytes=8 << 20,
                               page_bytes=d["page_bytes"], device=False)
    planes = np.zeros(((k + m) * 8, shard // 32), dtype=np.uint32)
    assert store.put_planar("o", planes, w=8, n_rows=k + m, meta=(1,),
                            trim=shard, data_rows=k * 8)
    assert store.pages_used == d["pages_per_object_with_parity"]
    tier = cfg["conf"]["osd_ec_planar_bytes"] // d["page_bytes"]
    line = int(cfg["conf"]["osd_cache_target_full_ratio"] * tier)
    assert (tier, line) == (d["tier_pages"], d["evict_line_pages"])
    assert line // d["pages_per_object_with_parity"] \
        == d["max_resident_objects_with_parity"] == 148

    per_osd = ExtentCache().max_objects
    assert per_osd == d["primary_cache_objects_per_osd"]
    assert per_osd * cfg["osds"] == d["primary_cache_objects_at_most"]
    assert min(100.0, 100.0 * d["primary_cache_objects_at_most"]
               / d["data_objects"]) == d["cached_share_at_most_percent"]


def test_the_stream_is_a_function_of_the_seed_alone_and_repeats_no_block():
    n = 1 << 20
    one = closed_loop_rbd_write.block_stream(4300000043, n)
    assert (one == closed_loop_rbd_write.block_stream(4300000043, n)).all()
    assert np.array_equal(np.sort(one), np.arange(n))
    assert (one != closed_loop_rbd_write.block_stream(4300000044, n)).any()


def test_the_new_metrics_read_the_new_counters_and_nothing_from_a_parent():
    moved = {"osd.rmw_base_cached": 60, "osd.rmw_extent_hits": 5,
             "osd.rmw_base_shards": 35, "osd.rmw_base_full_read": 0,
             "osd.rmw_full_rewrite": 0, "osd.rmw_read_lat.sum": 0.5,
             "osd.rmw_read_lat.count": 100, "osd.rmw_copied_bytes": 5e8,
             "osd.splice_copied_bytes": 11e8, "osd.splice_crc_bytes": 11e8,
             "objecter.op": 100, "wire.tx_bytes_MECSubWrite": 4500000,
             "rbd.wr": 100, "rbd.wr_bytes": 409600, "rbd.wr_lat.sum": 60.0,
             "rbd.wr_lat.count": 100, "osd.op_w_lat.sum": 40.0,
             "osd.op_w_lat.count": 100}
    ctx = {"counters": moved, "trace_counters": {}, "trace": None,
           "window": {}, "device_kind": "TPU v5 lite", "profile": {}}
    got = {name: layers.read(name, ctx) for name in NEW_METRICS}
    assert [got[n] for n in ARM_SHARES] == [60.0, 5.0, 35.0, 0.0]
    assert got["rmw_read_ms.rbd"] == 5.0
    assert got["rmw_copied_bytes_per_op.rbd"] == 16e6
    assert got["splice_crc_bytes_per_op.rbd"] == 11e6
    assert abs(got["subwrite_bytes_per_user_byte.rbd"] - 10.986) < 1e-3
    assert got["rbd_wr_ms.rbd"] == 600.0 and got["osd_op_w_ms.rbd"] == 400.0
    # a program without the counters: nothing, and no raise
    old = {"objecter.op": 100, "wire.tx_bytes_MECSubWrite": 4500000,
           "osd.rmw_extent_hits": 5, "osd.op_w_lat.sum": 40.0,
           "osd.op_w_lat.count": 100}
    for name in NEW_METRICS - {"osd_op_w_ms.rbd"}:
        assert layers.read(name, dict(ctx, counters=old)) is None, name


def run_py(*args, timeout=600):
    env = dict(os.environ)
    env.pop("CEPH_TPU_FORCE_BATCH", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), *args],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=timeout)
    lines = [json.loads(ln) for ln in proc.stdout.splitlines() if ln.strip()]
    assert lines, proc.stderr[-2000:]
    return proc.returncode, lines


def test_rehearsal_of_the_cell_would_be_correct():
    rc, lines = run_py("--workload", CELL, "--seed", "4300000043",
                       "--seconds", "4", "--trace", "1", "--rehearse")
    last = lines[-1]
    assert rc == 3 and last["rehearsal"]
    assert last["would_be_correct"] is True, lines
    assert last["attempted"] > 0 and last["failed"] == 0
    moved = next(ln for ln in lines if ln.get("phase") == "counters")["moved"]
    assert moved["osd.rmw_partial"] == moved["objecter.op_w"] \
        == moved["objecter.op"] == moved["rbd.wr"] == last["attempted"]
    assert moved["rbd.wr_bytes"] == 4096 * last["attempted"]
    assert moved["ec_tpu.submit_packedbit"] == last["attempted"]
    assert moved["wire.tx_MECSubWrite"] == 10 * last["attempted"]
    assert "compile_meter.compiles" not in moved  # nothing built
    assert "osd.splice_refused" not in moved
    # every new metric reads a number from a rehearsal's counters, and
    # the four arms' shares are the whole
    for name in NEW_METRICS:
        assert name in last["metrics"], name
    assert abs(sum(last["metrics"][n]["value"] for n in ARM_SHARES)
               - 100.0) < 1e-9
    assert last["metrics"]["rmw_full_rewrite_share.rbd"]["value"] == 0.0
    assert 10.5 < last["metrics"]["subwrite_bytes_per_user_byte.rbd"][
        "value"] < 12.0
    window = next(ln for ln in lines if ln.get("phase") == "window")
    assert window["window_compiles"] == 0 and window["op"] == "put"
    warm = next(ln for ln in lines if ln.get("phase") == "warmup")
    assert warm["fill_pieces"] == warm["object_map_blocks"] == 8
    assert warm["warm_writes"]["stood_still"]
    assert set(warm["group_seconds"]) == {
        *(f"packedbit.1x{n}" for n in (1, 2, 4, 8)),
        *(f"{lane}.128x{n}" for lane in ("packedbit", "packedbit_resident")
          for n in (1, 2))}
    assert all(warm["memory"][key] > 0 for key in (
        "peak_rss_bytes", "VmRSS_bytes", "MemTotal_bytes",
        "MemAvailable_bytes"))
    model = next(ln for ln in lines if ln.get("phase") == "model")
    assert model["stripe_neighbours_compared"] == 7 * model["blocks_compared"]


def test_a_store_that_drops_its_writes_ends_not_correct():
    rc, lines = run_py("--workload", CELL, "--seed", "12", "--seconds", "2",
                       "--trace", "0", "--rehearse", "--control",
                       "store_drop")
    last = lines[-1]
    assert rc in (1, 3) and last["correct"] is False
    assert not last.get("would_be_correct")
