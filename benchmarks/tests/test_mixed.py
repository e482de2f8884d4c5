"""Tests of what PR 38 adds to the benchmark: the configuration
`ec-k8m3-rs-mixed`, the traffic `mixed-small-zipf-open` with its generator
and its object model, the cell `k8m3.mixed-small` and its per-layer metrics.
CPU only; the run of the whole harness goes through `run.py --rehearse` in
a child process.  (tests/test_mixed_small.py holds the program to the model
and the generator's pure parts.)

    python -m pytest benchmarks/tests -q
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
from benchmarks.generators import open_loop_mixed  # noqa: E402

CELL, CONFIG, TRAFFIC = ("k8m3.mixed-small", "ec-k8m3-rs-mixed",
                         "mixed-small-zipf-open")
NEW_METRICS = {"put_p95_ms.mix", "osd_op_r_ms.mix", "osd_op_w_ms.mix",
               "client_get_ms.mix", "pad_share.mix", "slab_rebuild_per_op.mix",
               "ops_per_dispatch.mix", "install_kernel_hbm_share.mix"}


def test_the_cell_resolves_to_its_files_by_name():
    spec = manifest.load()
    cell = manifest.resolve(spec, CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        CONFIG, TRAFFIC, 1)
    assert cell.traffic["kind"] == "open_loop_mixed"
    assert open_loop_mixed.OP == "put"
    # put_p95_ms (from the DUE time here) spreads too widely between runs
    # to be admitted end to end: a per-layer number (PERF.md section 2),
    # and the metrics that move it in the write cells are not this cell's
    assert [m["name"] for m in cell.end_to_end] == ["put_MBps", "setup_s"]
    names = {m["name"] for m in cell.per_layer}
    assert NEW_METRICS <= names
    assert all(m["moves"] == "put_MBps" for m in cell.per_layer)
    # the layers a put crosses here as in the write cells
    assert {"crush_draws_per_op.put", "rx_copy_share.put",
            "write_copy_share.put", "hitset_scan_bits_per_op.put",
            "wire_us_per_msg.put", "direct_dispatch_per_op.put",
            "ec_kernel_hbm_share.put"} <= names
    assert names <= set(layers.available())
    for m in spec["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL] and m["moves"] == "put_MBps"
    entry = next(c for c in spec["configs"] if c["name"] == CONFIG)
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert entry["source"] == cell.config["source"]
    assert sorted(entry["reduced"]) == sorted(cell.config["reduced"])


def test_the_configuration_is_ec_k8m3_rs_with_a_population_and_two_guarantees():
    spec = manifest.load()
    base = manifest.resolve(spec, "k8m3.write4m").config
    cfg = manifest.resolve(spec, CELL).config
    for key in ("profile", "stripe_unit", "osds", "mons", "pg_num", "chips",
                "conf", "jax_config", "reference"):
        assert cfg[key] == base[key], key
    assert set(cfg["reduced"]) - set(base["reduced"]) == {"population"}
    for name, text in base["guarantees"].items():
        assert cfg["guarantees"][name] == text
    assert set(cfg["guarantees"]) - set(base["guarantees"]) == {
        "latest_acked_wins", "delete_is_complete"}
    assert set(base["assumed"]) <= set(cfg["assumed"])
    t = manifest.resolve(spec, CELL).traffic
    assert cfg["population"]["names"] == t["population"] == 4096
    assert cfg["population"]["deployment_names"] == 8192
    assert "population" in cfg["reduced"]
    assert cfg["population"]["schedule_seed"] == t["schedule_seed"]
    assert t["mix"]["get"], t["mix"]["put"] == (70, 25)
    assert t["keys"]["zipf_constant"] == 0.99 and t["max_outstanding"] == 1024
    assert isinstance(t["rate_ops_per_s"], float)


def test_derived_block_equals_what_the_code_computes():
    """The data set's figures, on the program's own StripeInfo and resident
    store (host arm): every size the population has, installed once."""
    from ceph_tpu.ec.registry import registry
    from ceph_tpu.rados.ecutil import StripeInfo
    from ceph_tpu.rados.pagestore import PagedResidentStore

    cell = manifest.resolve(manifest.load(), CELL)
    cfg, d = cell.config, cell.config["derived"]
    k, m = int(cfg["profile"]["k"]), int(cfg["profile"]["m"])
    codec = registry.factory("jerasure", "", dict(cfg["profile"],
                                                  plugin="jerasure"))
    sinfo = StripeInfo(k, codec.get_chunk_size(k * cfg["stripe_unit"]) * k)
    sizes = open_loop_mixed.Schedule(cell.traffic).sizes
    assert int(sizes.sum()) == d["user_bytes"]
    assert float(sizes.mean()) == d["mean_object_bytes"]
    assert int(np.median(sizes)) == d["median_object_bytes"]
    assert int(sizes.max()) == d["largest_object_bytes"]
    padded = [sinfo.logical_to_next_stripe_offset(int(s)) for s in sizes]
    assert sum(padded) == d["padded_user_bytes"]
    assert sum(padded) // sinfo.stripe_width == d["stripes"]
    assert sum(p // k * (k + m) for p in padded) == d["shard_store_bytes"]
    one = sum(1 for p in padded if p == sinfo.stripe_width)
    assert round(100 * one / len(sizes), 2) == d["one_stripe_objects_percent"]

    store = PagedResidentStore(capacity_bytes=64 << 20,
                               page_bytes=d["page_bytes"], device=False)
    pages = {}
    for p in sorted(set(padded)):
        planes = np.zeros(((k + m) * 8, p // k // 32), dtype=np.uint32)
        before = store.pages_used
        assert store.put_planar(p, planes, w=8, n_rows=k + m, meta=(1,),
                                trim=p // k)
        pages[p] = store.pages_used - before
        n = p // sinfo.stripe_width
        assert pages[p] == -(-44 * n // 64)  # the block's stated rule
        store.drop(p)
    assert sum(pages[p] for p in padded) == d["population_pages"]
    assert d["page_bytes_held"] == d["population_pages"] * d["page_bytes"]
    tier = cfg["conf"]["osd_ec_planar_bytes"] // d["page_bytes"]
    line = int(cfg["conf"]["osd_cache_target_full_ratio"] * tier)
    assert (tier, line) == (d["tier_pages"], d["evict_line_pages"])
    assert d["pages_below_line"] == line - d["population_pages"] > 0


def test_the_new_metrics_read_the_new_counters_and_nothing_from_a_parent():
    moved = {"osd.op_r_lat.sum": 0.5, "osd.op_r_lat.count": 100,
             "osd.op_w_lat.sum": 2.0, "osd.op_w_lat.count": 50,
             "objecter.op_r_lat.sum": 1.0, "objecter.op_r_lat.count": 100,
             "ec_tpu.pad_bytes": 25, "ec_tpu.bytes": 100,
             "ec_tpu.dispatch": 40, "objecter.op": 160,
             "slab_kernels.miss": 0}
    ctx = {"counters": moved, "trace_counters": {}, "trace": None,
           "window": {"p95_ms": 437.0}, "device_kind": "TPU v5 lite",
           "profile": {}}
    assert layers.read("put_p95_ms.mix", ctx) == 437.0
    assert layers.read("osd_op_r_ms.mix", ctx) == 5.0
    assert layers.read("osd_op_w_ms.mix", ctx) == 40.0
    assert layers.read("client_get_ms.mix", ctx) == 10.0
    assert layers.read("pad_share.mix", ctx) == 25.0
    assert layers.read("ops_per_dispatch.mix", ctx) == 4.0
    assert layers.read("slab_rebuild_per_op.mix", ctx) == 0.0
    assert layers.read("install_kernel_hbm_share.mix", ctx) is None
    # the parent's program has none of the counters: nothing, and no raise
    old = {"objecter.op": 160, "ec_tpu.bytes": 100, "ec_tpu.dispatch": 40}
    for name in NEW_METRICS - {"ops_per_dispatch.mix", "put_p95_ms.mix"}:
        assert layers.read(name, dict(ctx, counters=old)) is None, name
    # the install's share: least bytes over the install programs' time
    # (times in ns: 2 ms of installs, and an encode that is none)
    red = {"window_s": 1.0, "busy_s": 0.1, "devices": ["/device:TPU:0"],
           "t0": 0, "t1": int(1e9),
           "modules": [("jit__install(123)", 0, int(2e6)),
                       ("jit__run(7)", int(3e6), int(5e6))]}
    traced = dict(ctx, trace=red, trace_counters={
        "pagestore.install_page_bytes": 819e9 * 2e-3 / 2 / 4})
    got = layers.read("install_kernel_hbm_share.mix", traced)
    assert abs(got - 25.0) < 1e-6


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
    rc, lines = run_py("--workload", CELL, "--seed", "4000000011",
                       "--seconds", "5", "--trace", "1", "--rehearse")
    last = lines[-1]
    assert rc == 3 and last["rehearsal"]
    assert last["would_be_correct"] is True, lines
    assert last["attempted"] > 0 and last["failed"] == 0
    moved = next(ln for ln in lines if ln.get("phase") == "counters")["moved"]
    for key in ("osd.op_r", "osd.op_w", "osd.op_d", "pagestore.device_installs",
                "ec_tpu.submit_packedbit_resident", "objecter.op_d"):
        assert moved[key] > 0, key
    assert "slab_kernels.miss" not in moved and "compile_meter.compiles" \
        not in moved  # nothing built in the window
    loop = next(ln for ln in lines if ln.get("phase") == "open_loop")
    assert loop["shed"] == 0 and loop["peak_outstanding"] >= 1
    assert set(loop["from_due_time"]) == {"get", "put", "delete"}
    for name in NEW_METRICS - {"install_kernel_hbm_share.mix"}:  # no device
        assert name in last["metrics"], name
    assert last["metrics"]["slab_rebuild_per_op.mix"]["value"] == 0.0
    window = next(ln for ln in lines if ln.get("phase") == "window")
    assert window["window_compiles"] == 0
    # the tail counts from the due time: the generator's own line agrees
    assert last["metrics"]["put_p95_ms.mix"]["value"] == window["p95_ms"] \
        == loop["from_due_time"]["put"]["p95_ms"]
    warm = next(ln for ln in lines if ln.get("phase") == "warmup")
    assert warm["puts"] == warm["gets"] == 192 and warm["stood_still"]
    assert set(warm["group_seconds"]) == {
        f"{lane}.{n}" for lane in ("packedbit", "packedbit_resident")
        for n in (1, 2, 4, 8, 16, 32)}


def test_the_controls_end_not_correct():
    for kind, failing in (("reply_flip", "gets_corrupt"),
                          ("store_drop", "acked_without_all_shards")):
        rc, lines = run_py("--workload", CELL, "--seed", "12", "--seconds",
                           "3", "--trace", "0", "--rehearse", "--control",
                           kind)
        last = lines[-1]
        assert rc == 3 and last["correct"] is False
        assert not last.get("would_be_correct")
        checks = next(ln for ln in lines
                      if ln.get("phase") == "verify")["checks"]
        assert failing in {c["name"] for c in checks if not c["ok"]}, kind
