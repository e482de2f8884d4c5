"""Tests of what PR 52 adds to the benchmark: the configuration
`ec-k8m4-clay`, its plain reference, the cell `k8m4clay.write4m` and the
two per-layer metrics on the sub-chunk lane's counters and program.  CPU
only; the runs of the whole harness go through `run.py --rehearse` in a
child process.

    python -m pytest benchmarks/tests -q
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import control, layers, manifest  # noqa: E402
from benchmarks.references import clay  # noqa: E402

CELL, CONFIG = "k8m4clay.write4m", "ec-k8m4-clay"


# -- the configuration and the cell ---------------------------------------------


def test_configuration_states_the_shapes_reference_and_program_derive():
    from ceph_tpu.ec.registry import registry
    from ceph_tpu.rados import ecutil
    from ceph_tpu.rados.ecutil import StripeInfo

    spec = manifest.load()
    cell = manifest.resolve(spec, CELL)
    cfg = cell.config
    archive = manifest.resolve(spec, "k10m4c.write4m").config
    assert cell.config_name == CONFIG and cell.chips == 1
    assert cell.traffic_name == "rados-bench-write-4m-t16"
    assert cfg["profile"] == {"plugin": "clay", "k": "8", "m": "4",
                              "d": "11"}
    # what differs from ec-k10m4-cauchy is the codec and the cluster's width
    for same in ("conf", "jax_config", "mons", "pg_num", "stripe_unit",
                 "rehearse", "reduced"):
        assert cfg[same] == archive[same], same
    assert set(cfg["guarantees"]) == set(archive["guarantees"])
    assert cfg["osds"] == int(cfg["profile"]["k"]) \
        + int(cfg["profile"]["m"]) + 1
    assert cfg["reference"] == "clay"
    derived = cfg["derived"]
    got = clay.shapes(cfg["profile"], cfg["stripe_unit"],
                      cell.traffic["object_bytes"])
    assert {key: derived[key] for key in got} == got
    assert (got["stripe_width"], got["chunk_size"], got["sub_chunks"],
            got["sub_chunk_bytes"], got["stripes_per_object"],
            got["shards"], got["shard_bytes"],
            got["stored_bytes_per_object"], got["remote_sub_writes"]) == \
        (32768, 4096, 64, 64, 128, 12, 524288, 6 << 20, 11)
    # ... and the program's own sizes for a pool of that profile
    codec = registry.factory("clay", "", dict(cfg["profile"]))
    k = codec.get_data_chunk_count()
    chunk = codec.get_chunk_size(k * cfg["stripe_unit"])
    sinfo = StripeInfo(k, k * chunk)
    assert (sinfo.stripe_width, sinfo.chunk_size) == \
        (got["stripe_width"], got["chunk_size"])
    assert codec.get_sub_chunk_count() == got["sub_chunks"]
    assert (codec.q, codec.t, codec.nu) == (got["q"], got["t"], got["nu"])
    assert ecutil._lane(codec, sinfo) == ("subchunk", np.uint8,
                                          got["chunk_size"])
    entry = next(c for c in spec["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == sorted(cfg["reduced"]) == ["hosts"]
    assert entry["source"] == cfg["source"]
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"


def test_cell_reports_the_put_metrics_of_a_pool_without_residents():
    spec = manifest.load()
    cell = manifest.resolve(spec, CELL)
    e2e, layer = manifest.metrics_of(spec, CELL)
    assert (e2e, layer) == (cell.end_to_end, cell.per_layer)
    assert {m["name"] for m in e2e} == {"put_MBps", "put_p95_ms", "setup_s"}
    mine = {m["name"] for m in layer}
    archive = {m["name"] for m in
               manifest.resolve(spec, "k10m4c.write4m").per_layer}
    # the archive cell's rows, but for the two that read ITS lane and its
    # program's name; and the two that read this one's
    assert archive - mine == {"packet_lane_share.put",
                              "ec_kernel_hbm_share.put"}
    assert mine - archive == {"subchunk_lane_share.put",
                              "clay_kernel_hbm_share.put"}
    assert "install_programs.put" not in mine
    assert all(name.endswith(".put") for name in mine)
    by_name = {m["name"]: m for m in spec["per_layer"]}
    for name, layer_name, source in (
            ("subchunk_lane_share.put", "BatchingQueue", "program_counter"),
            ("clay_kernel_hbm_share.put", "kernels", "device_trace")):
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["moves"] == "put_MBps"
        assert (m["layer"], m["source"], m["unit"]) == \
            (layer_name, source, "%")
    assert by_name["direct_dispatch_per_op.put"]["workloads"][-1] == CELL
    assert spec["workloads"][-1]["name"] == CELL
    assert len(spec["workloads"][-1]["why"]) <= 200
    assert mine <= set(layers.available())  # every name has a reader


# -- the two metrics on fixture counters and a fixture trace ------------------------


def test_lane_share_on_a_counter_delta():
    on_the_lane = {"counters": {"ec_tpu.submit": 300,
                                "ec_tpu.submit_subchunk": 300}}
    assert layers.read("subchunk_lane_share.put", on_the_lane) == 100.0
    mixed = {"counters": {"ec_tpu.submit": 300,
                          "ec_tpu.submit_subchunk": 75}}
    assert layers.read("subchunk_lane_share.put", mixed) == 25.0


def _trace_ctx(module: str, seconds: float, counters: dict) -> dict:
    ns = int(seconds * 1e9)
    return {"trace": {"window_s": 5.0, "busy_s": seconds,
                      "devices": ["/device:TPU:0"], "t0": 0,
                      "t1": 5 * 10 ** 9,
                      "modules": [[module, 10 ** 9, ns]]},
            "trace_counters": counters, "device_kind": "TPU v5 lite",
            "profile": {"k": "8", "m": "4"}}


def test_kernel_share_reads_the_work_not_the_passes():
    """100 puts of 4 MiB in the span: least bytes 1.5 x 400 MiB = k chunks
    read, m written; at 819 GB/s that is 0.768 ms, over 0.5 s of the
    lane's programs."""
    nbytes = 100 * (4 << 20)
    ctx = _trace_ctx("jit__clay_encode(1234)", 0.5,
                     {"ec_tpu.bytes_subchunk": nbytes})
    want = 100.0 * (nbytes * 12 / 8 / 819e9) / 0.5
    assert layers.read("clay_kernel_hbm_share.put", ctx) == \
        pytest.approx(want)
    assert 0 < want < 100
    # another lane's program in the span is not this lane's time
    assert layers.read("clay_kernel_hbm_share.put", _trace_ctx(
        "jit__run(99)", 0.5, {"ec_tpu.bytes_subchunk": nbytes})) is None


def test_lane_metrics_on_a_program_without_the_lane():
    """The parent commit has neither the counters nor the program: both
    readers report nothing and do not raise."""
    parent = {"counters": {"ec_tpu.submit": 0, "objecter.op": 9}}
    assert layers.read("subchunk_lane_share.put", parent) is None
    assert layers.read("clay_kernel_hbm_share.put", _trace_ctx(
        "jit__run(99)", 0.5, {"ec_tpu.bytes": 1 << 20})) is None
    assert layers.read("clay_kernel_hbm_share.put",
                       {"trace": None, "trace_counters": {}}) is None


# -- the whole harness, rehearsed on the CPU backend ------------------------------


def _rehearse(*more):
    env = dict(os.environ)
    env.pop("CEPH_TPU_FORCE_BATCH", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", "4000000007", "--seconds", "2",
         "--rehearse", *more],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    lines = [json.loads(ln) for ln in proc.stdout.splitlines() if ln.strip()]
    assert lines, proc.stderr[-2000:]
    assert proc.returncode == 3 and lines[-1]["rehearsal"], \
        (lines[-1], proc.stderr[-2000:])
    return lines


def test_rehearsal_of_the_cell_would_be_correct():
    lines = _rehearse("--trace", "1")
    last = lines[-1]
    assert last["would_be_correct"] is True, lines
    assert last["attempted"] > 0 and last["failed"] == 0
    metrics = last["metrics"]
    assert metrics["subchunk_lane_share.put"]["value"] == 100.0
    assert metrics["direct_dispatch_per_op.put"]["value"] == 0.0
    # (window_compile_s.put may move here: at the rehearsal's 256 KiB a
    # round takes more than four puts, widths the wide puts do not warm)
    assert "window_compile_s.put" in metrics
    assert metrics["plan_copy_share.put"]["value"] == 0.0
    for absent in ("install_programs.put", "packet_lane_share.put",
                   "clay_kernel_hbm_share.put"):  # the last needs a chip
        assert absent not in metrics
    moved = next(ln for ln in lines if ln.get("phase") == "counters")["moved"]
    assert moved["ec_tpu.dispatch"] > 0
    assert moved["ec_tpu.submit_subchunk"] == moved["ec_tpu.submit"] \
        == moved["ecplan.plans"]
    assert moved["ec_tpu.staged_layout_bytes"] == \
        moved["ec_tpu.bytes_subchunk"]
    assert not moved.get("ec_plugin.apply")
    assert not moved.get("ec_plugin.apply_rows")
    assert not moved.get("ecplan.loop_layout_bytes")
    checks = next(ln for ln in lines if ln.get("phase") == "verify")["checks"]
    by_name = {c["name"]: c for c in checks}
    assert by_name["shard_objects_compared"]["value"] >= 1
    assert by_name["shards_differing_from_reference"]["value"] == 0
    cluster = next(ln for ln in lines if ln.get("phase") == "cluster")
    assert cluster["osds"] == 13 and cluster["profile"]["plugin"] == "clay"


@pytest.mark.parametrize("kind", control.KINDS)
def test_a_control_ends_not_correct(kind):
    lines = _rehearse("--control", kind)
    last = lines[-1]
    assert last["would_be_correct"] is False, (kind, last)
    broke = next(ln for ln in lines if ln.get("phase") == "control")
    assert kind.split("_")[0] in broke["broke"]
