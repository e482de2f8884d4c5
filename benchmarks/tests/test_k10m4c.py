"""Tests of what PR 28 adds to the benchmark: the configuration
`ec-k10m4-cauchy`, its plain reference, the cell `k10m4c.write4m` and the
two per-layer metrics on the packet lane's counters.  CPU only; the run of
the whole harness goes through `run.py --rehearse` in a child process.

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

from benchmarks import layers, manifest  # noqa: E402
from benchmarks.references import cauchy_good  # noqa: E402

CELL, CONFIG = "k10m4c.write4m", "ec-k10m4-cauchy"
WRITE_CELLS = ["k8m3.write4m", "k4m2.write4m", CELL]


# -- the reference -------------------------------------------------------------


def test_reference_equals_the_corpus_archive_of_cauchy_good_k4_m2():
    """The committed corpus entry is one stripe of 4096 B encoded with
    jerasure's defaults (w=8, packetsize=2048): k chunks of 65536 B cut
    from the zero-padded content.  With stripe_unit = 4096 / k the
    reference's stripe shape is that shape."""
    directory = os.path.join(
        ROOT, "corpus",
        "plugin=jerasure stripe-width=4096 technique=cauchy_good k=4 m=2")
    with open(os.path.join(directory, "content"), "rb") as f:
        content = f.read()
    profile = {"plugin": "jerasure", "technique": "cauchy_good",
               "k": "4", "m": "2"}
    got = cauchy_good.shards(profile, len(content) // 4, content)
    assert len(got) == 6
    for i, shard in enumerate(got):
        with open(os.path.join(directory, str(i)), "rb") as f:
            assert shard == f.read(), f"chunk {i}"


@pytest.mark.parametrize("technique,k,m,w", [
    ("cauchy_good", 10, 4, 8), ("cauchy_good", 4, 2, 8),
    ("cauchy_orig", 10, 4, 8), ("cauchy_good", 5, 3, 4),
    ("cauchy_good", 6, 3, 16)])
def test_reference_equals_the_programs_cpu_jerasure_plugin(technique, k, m, w):
    """The reference shares no code with the program; they agree on the
    matrix, the bit-matrix, the chunk-size rule and the packet layout."""
    from ceph_tpu.ec.registry import registry

    profile = {"plugin": "jerasure", "technique": technique, "k": str(k),
               "m": str(m), "w": str(w), "packetsize": "32"}
    codec = registry.factory("jerasure", "", dict(profile))
    assert np.array_equal(cauchy_good.bitmatrix(technique, k, m, w),
                          np.asarray(codec.bitmatrix))
    payload = np.random.default_rng(k * m).integers(
        0, 256, 3 * k * 4096 + 77, dtype=np.uint8).tobytes()
    shapes = cauchy_good.shapes(profile, 4096, len(payload))
    assert shapes["chunk_size"] == codec.get_chunk_size(k * 4096)
    rows = cauchy_good.data_rows(profile, 4096, payload)
    want = cauchy_good.shards(profile, 4096, payload)
    assert [r.tobytes() for r in rows] == want[:k]
    assert [bytes(r) for r in np.asarray(codec.encode_chunks(rows))] \
        == want[k:]


def test_reference_refuses_what_it_does_not_know():
    with pytest.raises(ValueError, match="liberation"):
        cauchy_good.coding_matrix("liberation", 4, 2, 8)
    with pytest.raises(ValueError, match="whole"):
        cauchy_good.bitmatrix_encode(
            cauchy_good.bitmatrix("cauchy_good", 4, 2, 8), 4, 2, 8, 32,
            np.zeros((4, 100), np.uint8))


# -- the configuration and the cell ---------------------------------------------


def test_configuration_states_the_shapes_the_reference_derives():
    spec = manifest.load()
    cell = manifest.resolve(spec, CELL)
    cfg, base = cell.config, manifest.resolve(spec, "k8m3.write4m").config
    assert cell.config_name == CONFIG and cell.chips == 1
    assert cell.traffic_name == "rados-bench-write-4m-t16"
    # what differs from ec-k8m3-rs is the codec and the cluster's width
    for same in ("conf", "jax_config", "mons", "pg_num", "stripe_unit",
                 "rehearse"):
        assert cfg[same] == base[same], same
    assert set(cfg["guarantees"]) == set(base["guarantees"])
    assert cfg["osds"] == int(cfg["profile"]["k"]) \
        + int(cfg["profile"]["m"]) + 1
    derived = cfg["derived"]
    got = cauchy_good.shapes(cfg["profile"], cfg["stripe_unit"],
                             cell.traffic["object_bytes"])
    assert got == {"stripe_width": derived["stripe_width"],
                   "chunk_size": derived["chunk_size"],
                   "stripes": derived["stripes_per_object"],
                   "padded_bytes": derived["padded_bytes_per_object"],
                   "shards": derived["shards"],
                   "shard_bytes": derived["shard_bytes"]}
    assert derived["padding_bytes_per_object"] == \
        derived["padded_bytes_per_object"] - derived["object_bytes"]
    assert derived["padding_over_object_bytes"] == pytest.approx(
        derived["padding_bytes_per_object"] / derived["object_bytes"])
    entry = next(c for c in spec["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == sorted(cfg["reduced"]) == ["hosts"]
    assert entry["source"] == cfg["source"]


def test_cell_reports_the_put_metrics_and_not_the_installs():
    spec = manifest.load()
    cell = manifest.resolve(spec, CELL)
    assert {m["name"] for m in cell.end_to_end} == \
        {"put_MBps", "put_p95_ms", "setup_s"}
    mine = {m["name"] for m in cell.per_layer}
    base = {m["name"] for m in manifest.resolve(spec, "k8m3.write4m").per_layer}
    # nothing of this pool installs; only this cell has the packet lane
    assert base - mine == {"install_programs.put"}
    assert mine - base == {"packet_lane_share.put"}
    assert "ec_kernel_hbm_share.put" in mine and "store_self_ms.put" in mine
    assert all(name.endswith(".put") for name in mine)
    by_name = {m["name"]: m for m in spec["per_layer"]}
    assert by_name["direct_dispatch_per_op.put"]["workloads"] == WRITE_CELLS
    assert by_name["packet_lane_share.put"]["workloads"] == [CELL]
    assert by_name["direct_dispatch_per_op.put"]["layer"] == "device boundary"
    assert by_name["packet_lane_share.put"]["layer"] == "BatchingQueue"


# -- the two metrics on a fixture counter delta ----------------------------------


def test_lane_metrics_on_a_counter_delta():
    on_the_lane = {"counters": {
        "ec_tpu.submit": 400, "ec_tpu.submit_packetrows": 400,
        "ec_plugin.apply": 0, "ec_plugin.apply_rows": 0,
        "objecter.op": 400}}
    assert layers.read("packet_lane_share.put", on_the_lane) == 100.0
    assert layers.read("direct_dispatch_per_op.put", on_the_lane) == 0.0
    mixed = {"counters": dict(on_the_lane["counters"],
                              **{"ec_tpu.submit_packetrows": 100,
                                 "ec_plugin.apply_rows": 300})}
    assert layers.read("packet_lane_share.put", mixed) == 25.0
    assert layers.read("direct_dispatch_per_op.put", mixed) == 0.75


def test_lane_metrics_on_a_program_without_the_lane():
    """The parent commit has no `submit_packetrows` counter and serves
    every put by a direct dispatch: the share reports nothing and does
    not raise, the guard reads 1."""
    parent = {"counters": {"ec_tpu.submit": 0, "ec_plugin.apply": 0,
                           "ec_plugin.apply_rows": 138, "objecter.op": 138}}
    assert layers.read("packet_lane_share.put", parent) is None
    assert layers.read("direct_dispatch_per_op.put", parent) == 1.0
    assert layers.read("direct_dispatch_per_op.put",
                       {"counters": {"objecter.op": 0}}) is None


# -- the whole harness, rehearsed on the CPU backend ------------------------------


def test_rehearsal_of_the_cell_would_be_correct():
    env = dict(os.environ)
    env.pop("CEPH_TPU_FORCE_BATCH", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", "4000000007", "--seconds", "2",
         "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    lines = [json.loads(ln) for ln in proc.stdout.splitlines() if ln.strip()]
    assert lines, proc.stderr[-2000:]
    last = lines[-1]
    assert proc.returncode == 3 and last["rehearsal"]
    assert last["would_be_correct"] is True, lines
    assert last["attempted"] > 0 and last["failed"] == 0
    metrics = last["metrics"]
    assert metrics["packet_lane_share.put"]["value"] == 100.0
    assert metrics["direct_dispatch_per_op.put"]["value"] == 0.0
    assert "install_programs.put" not in metrics
    moved = next(ln for ln in lines if ln.get("phase") == "counters")["moved"]
    assert moved["ec_tpu.dispatch"] > 0
    assert moved["ec_tpu.submit_packetrows"] == moved["ec_tpu.submit"]
    assert not moved.get("ec_plugin.apply_rows")
