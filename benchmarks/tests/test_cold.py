"""Tests of what PR 33 adds to the benchmark: the configuration
`ec-k8m3-rs-tier3x`, the traffic `rados-bench-rand-4m-t16-cold` with its
generator, the cell `k8m3.randread4m-cold` and its per-layer metrics.  CPU
only; the runs of the whole harness go through `run.py --rehearse` in a
child process.

    python -m pytest benchmarks/tests -q
"""

from __future__ import annotations

import json
import os
import struct
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import layers, manifest  # noqa: E402
from benchmarks.generators import closed_loop_get_cold  # noqa: E402

CELL, CONFIG = "k8m3.randread4m-cold", "ec-k8m3-rs-tier3x"
FIT_CELL = "k8m3.randread4m"
NEW_METRICS = {
    "promote_per_miss.get", "promote_throttled_share.get",
    "evicted_pages_per_op.get", "osd_subread_wait_ms.get", "promote_ms.get",
    "install_programs.get", "window_compile_s.get",
    "promote_kernel_hbm_share.get"}
TIER_CONF = {
    "osd_tier_promote_max_objects_sec", "osd_tier_promote_max_bytes_sec",
    "osd_hit_set_count", "osd_hit_set_period",
    "osd_min_read_recency_for_promote", "osd_min_write_recency_for_promote"}


# -- the configuration -----------------------------------------------------------


def test_configuration_is_the_fit_cells_plus_the_tiers_policy():
    spec = manifest.load()
    cell = manifest.resolve(spec, CELL)
    cfg, base = cell.config, manifest.resolve(spec, FIT_CELL).config
    assert cell.config_name == CONFIG and cell.chips == 1
    for same in ("profile", "stripe_unit", "osds", "mons", "pg_num", "chips",
                 "jax_config", "reference"):
        assert cfg[same] == base[same], same
    assert {k: v for k, v in cfg["conf"].items() if k not in TIER_CONF} \
        == base["conf"]
    assert set(cfg["conf"]) - set(base["conf"]) == TIER_CONF
    assert set(cfg["guarantees"]) - set(base["guarantees"]) == \
        {"any_source_exact"}
    for key, text in base["guarantees"].items():
        assert cfg["guarantees"][key] == text
    # every tier value is under `assumed`, marked as not checked, with the
    # program's own default beside it
    for key in TIER_CONF | {"osd_cache_target_full_ratio"}:
        said = cfg["assumed"][key]
        assert "AS REMEMBERED" in said and "program" in said, key
    entry = next(c for c in spec["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == sorted(cfg["reduced"]) == \
        ["conf.osd_ec_planar_bytes", "hosts"]
    assert entry["source"] == cfg["source"]
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"


@pytest.mark.parametrize("rehearse", [False, True])
def test_the_data_set_is_three_times_the_tier(rehearse):
    cell = manifest.resolve(manifest.load(), CELL, rehearse=rehearse)
    cfg, t = cell.config, cell.traffic
    ds = cfg["data_set"]
    assert ds["objects"] == t["objects"]
    assert ds["object_bytes"] == t["object_bytes"]
    assert ds["user_bytes"] == ds["objects"] * ds["object_bytes"]
    assert ds["tier_bytes"] == cfg["conf"]["osd_ec_planar_bytes"]
    assert ds["user_bytes"] == 3 * ds["tier_bytes"]
    assert ds["data_set_over_tier"] == 3.0
    assert t["warm_gets"] < t["objects"] and t["in_flight"] == 16
    assert t["verify"]["promoted_objects"] >= (2 if rehearse else 8)


def test_derived_block_equals_what_the_code_computes():
    """The page counts, on the program's own StripeInfo and resident
    store (host arm, one object): an install with parity, then a shed."""
    from ceph_tpu.ec.registry import registry
    from ceph_tpu.rados.ecutil import StripeInfo
    from ceph_tpu.rados.pagestore import PagedResidentStore

    cell = manifest.resolve(manifest.load(), CELL)
    cfg, d, ds = cell.config, cell.config["derived"], cell.config["data_set"]
    k, m = int(cfg["profile"]["k"]), int(cfg["profile"]["m"])
    codec = registry.factory("jerasure", "", dict(cfg["profile"],
                                                  plugin="jerasure"))
    sinfo = StripeInfo(k, codec.get_chunk_size(k * cfg["stripe_unit"]) * k)
    stripes = -(-ds["object_bytes"] // sinfo.stripe_width)
    shard_bytes = stripes * sinfo.chunk_size
    assert shard_bytes == d["shard_bytes"]
    assert ds["objects"] * (k + m) * shard_bytes == d["shard_store_bytes"]

    store = PagedResidentStore(capacity_bytes=8 << 20,
                               page_bytes=d["page_bytes"], device=False)
    assert store.page_bytes == d["page_bytes"]
    planes = np.zeros(((k + m) * 8, shard_bytes // 32), dtype=np.uint32)
    assert store.put_planar("o", planes, w=8, n_rows=k + m, meta=(1,),
                            trim=shard_bytes, data_rows=k * 8)
    assert store.pages_used == d["pages_per_object_with_parity"]
    assert store.shed_parity("o") == (d["pages_per_object_with_parity"]
                                      - d["pages_per_object_data_only"]) \
        * d["page_bytes"]
    assert store.pages_used == d["pages_per_object_data_only"]
    assert store.gather_rows("o", 0, k * 8) is not None  # data still served

    tier_pages = cfg["conf"]["osd_ec_planar_bytes"] // d["page_bytes"]
    line = int(cfg["conf"]["osd_cache_target_full_ratio"] * tier_pages)
    assert (tier_pages, line) == (d["tier_pages"], d["evict_line_pages"])
    assert ds["objects"] * d["pages_per_object_with_parity"] == \
        d["data_set_pages"]
    assert line // d["pages_per_object_with_parity"] == \
        d["max_resident_objects_with_parity"]
    assert line // d["pages_per_object_data_only"] == \
        d["max_resident_objects_parity_shed"]
    lo, hi = d["expected_resident_hit_share_percent"]
    assert lo == int(100 * d["max_resident_objects_with_parity"]
                     / ds["objects"])
    assert hi == round(100 * d["max_resident_objects_parity_shed"]
                       / ds["objects"])


# -- the cell and its metrics ------------------------------------------------------


def test_every_cell_resolves_with_one_of_the_three_generators():
    spec = manifest.load()
    kinds = {w["name"]: manifest.resolve(spec, w["name"]).traffic["kind"]
             for w in spec["workloads"]}
    assert kinds.pop(CELL) == "closed_loop_get_cold"
    assert set(kinds.values()) == {"closed_loop_put", "closed_loop_get"}
    assert len(spec["workloads"]) == 5 and len(spec["configs"]) == 4


def test_cell_reports_the_get_metrics_and_the_miss_paths():
    spec = manifest.load()
    cell, fit = manifest.resolve(spec, CELL), manifest.resolve(spec, FIT_CELL)
    assert cell.traffic_name == "rados-bench-rand-4m-t16-cold"
    assert {m["name"] for m in cell.end_to_end} == {"get_MBps", "setup_s"}
    assert {m["name"] for m in fit.end_to_end} == {"get_MBps", "setup_s"}
    mine = {m["name"] for m in cell.per_layer}
    assert mine - {m["name"] for m in fit.per_layer} == NEW_METRICS
    assert {m["name"] for m in fit.per_layer} <= mine
    by_name = {m["name"]: m for m in spec["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "get_MBps"
        assert name in layers.available()
    for name in mine - NEW_METRICS:
        assert by_name[name]["workloads"] == [FIT_CELL, CELL]
    assert spec["workloads"][-1]["name"] == CELL
    assert spec["configs"][-1]["name"] == CONFIG


def test_miss_path_metrics_on_a_counter_delta():
    moved = {"tier.promote": 300, "tier.promote_throttled": 600,
             "tier.promote_skipped": 0, "tier.promote_stale": 0,
             "pagestore.miss": 1000, "pagestore.page_evictions": 26400,
             "osd.op_r": 1320, "optracker.lat_subop_wait.sum": 80.0,
             "optracker.lat_subop_wait.count": 1000,
             "tier.promote_lat.sum": 12.0, "tier.promote_lat.count": 300,
             "pagestore.install_programs": 1200,
             "pagestore.device_installs": 300, "pagestore.h2d_installs": 0,
             "compile_meter.compile_s": 0.0}
    ctx = {"counters": moved, "trace": None}
    assert layers.read("promote_per_miss.get", ctx) == pytest.approx(0.3)
    assert layers.read("promote_throttled_share.get", ctx) == \
        pytest.approx(100 * 600 / 900)
    assert layers.read("evicted_pages_per_op.get", ctx) == pytest.approx(20.0)
    assert layers.read("osd_subread_wait_ms.get", ctx) == pytest.approx(80.0)
    assert layers.read("promote_ms.get", ctx) == pytest.approx(40.0)
    assert layers.read("install_programs.get", ctx) == pytest.approx(4.0)
    assert layers.read("window_compile_s.get", ctx) == 0.0
    assert layers.read("promote_kernel_hbm_share.get", ctx) is None  # no trace


def test_miss_path_metrics_on_a_program_without_the_counter():
    """The parent commit has no `tier.promote_lat`: the reader returns
    nothing and does not raise; a window without a miss reads no ratio."""
    parent = {"counters": {"tier.promote": 3, "pagestore.miss": 0,
                           "tier.promote_throttled": 0,
                           "tier.promote_skipped": 0,
                           "tier.promote_stale": 0}}
    assert layers.read("promote_ms.get", parent) is None
    assert layers.read("promote_per_miss.get", parent) is None
    assert layers.read("promote_throttled_share.get", parent) == 0.0
    assert layers.read("evicted_pages_per_op.get", parent) is None


def test_the_resident_encodes_share_of_the_roofline_stays_under_100():
    mods = [["jit__run(7)", 0, 2000], ["jit__install(3)", 2100, 300]]
    red = {"window_s": 1e-5, "devices": 1, "busy_s": 2.3e-6, "modules": mods,
           "t0": 0, "t1": 10000}
    ctx = {"trace": red, "device_kind": "TPU v5 lite",
           "trace_counters": {"ec_tpu.bytes_packedbit_resident": 1000},
           "profile": {"k": "8", "m": "3"}}
    share = layers.read("promote_kernel_hbm_share.get", ctx)
    assert share == pytest.approx(100 * (1375 / 819e9) / 2000e-9)
    assert 0 < share <= 100
    assert layers.read("promote_kernel_hbm_share.get",
                       dict(ctx, trace_counters={})) is None


# -- the generator -------------------------------------------------------------------


def _generator(seed=7):
    traffic = manifest.resolve(manifest.load(), CELL, rehearse=True).traffic
    env = SimpleNamespace(cell=SimpleNamespace(traffic=traffic), seed=seed,
                          store_set="pagestore",
                          store_device_arm=lambda: True)
    return closed_loop_get_cold.Generator(env)


def test_a_reply_is_held_to_its_stamp_and_its_tail_without_a_copy_kept():
    gen = _generator()
    for i in (0, 3, 4, 47):
        data = gen.payloads.data(i)
        assert gen._identical(data, i) and gen._identical(bytearray(data), i)
        assert gen._identical(memoryview(data), i)
        assert not gen._identical(data, i + 4)       # same buffer, other stamp
        assert not gen._identical(data[:-1], i)
        for at in (0, 7, 8, len(data) // 2, len(data) - 1):
            bad = bytearray(data)
            bad[at] ^= 1
            assert not gen._identical(bad, i), at
    assert struct.unpack("<Q", gen.payloads.data(5)[:8]) == (5,)
    assert len(gen.tails) == gen.t["payload_pool"]    # and not `objects`
    assert gen._index_of(gen.payloads.name(31)) == 31


def test_a_window_in_which_the_mechanism_did_not_run_is_not_correct():
    gen = _generator()
    ran = {"pagestore.hit": 60, "pagestore.miss": 200, "tier.promote": 90,
           "pagestore.evict": 80}
    assert all(c["ok"] for c in gen.counter_checks(ran))
    for key in ran:
        got = {c["name"]: c for c in gen.counter_checks(dict(ran, **{key: 0}))}
        assert not got[key]["ok"], key
    got = gen.counter_checks(dict(ran, **{"ec_plugin.cpu_fallback": 1}))
    assert not all(c["ok"] for c in got)


# -- the whole harness, rehearsed on the CPU backend ------------------------------------


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
    rc, lines = run_py("--workload", CELL, "--seed", "4000000007",
                       "--seconds", "3", "--trace", "1", "--rehearse")
    last = lines[-1]
    assert rc == 3 and last["rehearsal"]
    assert last["would_be_correct"] is True, lines
    assert last["attempted"] > 0 and last["failed"] == 0
    moved = next(ln for ln in lines if ln.get("phase") == "counters")["moved"]
    for key in ("pagestore.miss", "pagestore.hit", "pagestore.evict",
                "tier.promote", "ec_tpu.submit_packedbit_resident"):
        assert moved[key] > 0, key
    assert moved["tier.promote_lat.count"] == moved["tier.promote"]
    assert moved["optracker.lat_subop_wait.count"] == moved["pagestore.miss"]
    checks = {c["name"]: c for c in next(
        ln for ln in lines if ln.get("phase") == "verify")["checks"]}
    assert checks["promoted_residents_compared"]["value"] >= 2
    assert checks["promoted_rows_differing_from_reference"]["value"] == 0
    metrics = last["metrics"]
    for name in NEW_METRICS - {"promote_kernel_hbm_share.get"}:  # no device
        assert name in metrics, name
    assert metrics["window_compile_s.get"]["value"] == 0.0
    assert 0 < metrics["promote_per_miss.get"]["value"] <= 1.0
    assert 0 < metrics["resident_hit_share.get"]["value"] < 60
    warm = next(ln for ln in lines if ln.get("phase") == "warmup")
    assert warm["puts"] == 48 and warm["gets"] == 16
    # set-up handed the queue a group of 2 and one of 4 on each encode
    # lane (log2 buckets of the queue's group-size histogram)
    assert set(warm["group_seconds"]) == {
        "packedbit_resident.2", "packedbit_resident.4", "packedbit.2",
        "packedbit.4"}
    assert warm["group_size_log2"][2:4] == [2, 2]


@pytest.mark.parametrize("kind,failing", [
    # 16 min here (979 s, PR 33): the run waits out its own deadline
    pytest.param("store_flip", "shards_differing_from_reference",
                 marks=pytest.mark.slow),
    ("store_drop", "acked_without_all_shards"),
    ("reply_flip", "gets_not_identical"),
])
def test_the_three_controls_end_not_correct(kind, failing):
    """A get that misses reads the stored shards, so here the cluster meets
    a broken store itself.  A dropped shard is rebuilt from the others and
    the run reaches its verification.  A flipped one fails the blob crc of
    its sub-read reply (a MemStore's stored crc rides the frame): the
    connection resets, the gather waits out its 5 s, the PG stays degraded
    and the run ends at `wait_healthy` (minutes; run.py's own deadline
    bounds it) with no window: not correct either way."""
    rc, lines = run_py("--workload", CELL, "--seed", "12", "--seconds", "2",
                       "--trace", "0", "--rehearse", "--control", kind,
                       timeout=1200)
    last = lines[-1]
    assert last["correct"] is False and not last.get("would_be_correct")
    verified = [ln for ln in lines if ln.get("phase") == "verify"]
    if kind == "store_flip" and not verified:
        assert rc == 1 and last["metrics"] == {}
        assert "not healthy" in last["error"] or "deadline" in last["error"]
        return
    assert rc == 3
    bad = {c["name"] for c in verified[0]["checks"] if not c["ok"]}
    assert failing in bad, bad
