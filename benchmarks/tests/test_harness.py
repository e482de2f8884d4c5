"""Tests of the benchmark harness.  CPU only:

    python -m pytest benchmarks/tests -q

Nothing here describes a TPU topology or loads libtpu; the runs of the whole
harness go through `run.py --rehearse` in a child process, which holds JAX
to the CPU backend before it is imported.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import (counters, layers, manifest, peaks, stats,  # noqa: E402
                        trace_reduce, verify)
from benchmarks.payload import Payloads  # noqa: E402
from benchmarks.references import reed_sol_van  # noqa: E402

RUN = os.path.join(ROOT, "benchmarks", "run.py")


def run_py(*args, env=None, timeout=300):
    """run.py in a child; (exit code, parsed lines of stdout)."""
    child_env = dict(os.environ, **(env or {}))
    child_env.pop("CEPH_TPU_FORCE_BATCH", None)
    proc = subprocess.run([sys.executable, RUN, *args], cwd=ROOT,
                          env=child_env, capture_output=True, text=True,
                          timeout=timeout)
    lines = [json.loads(ln) for ln in proc.stdout.splitlines() if ln.strip()]
    assert lines, proc.stderr[-2000:]
    return proc.returncode, lines


# -- manifest and files by name ------------------------------------------------


def test_every_cell_resolves_to_its_files_by_name():
    spec = manifest.load()
    assert spec["paths"] == ["benchmarks"]
    for w in spec["workloads"]:
        cell = manifest.resolve(spec, w["name"])
        assert cell.config["profile"]["plugin"] == "tpu"
        assert cell.traffic["kind"] in ("closed_loop_put", "closed_loop_get")
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:  # every metric has a reader of its own
            assert m["name"] in layers.available()
            assert m["moves"] in names


def test_a_metric_without_workloads_goes_where_the_contract_sends_it():
    """Later PRs add entries and may not edit manifest.py: an end-to-end
    metric without `workloads` is every cell's, a per-layer one belongs to
    every cell that reports the end-to-end metric it moves."""
    spec = json.loads(json.dumps(manifest.load()))
    for m in spec["per_layer"]:
        if m["name"] == "group_size.put":
            del m["workloads"]
    for w in spec["workloads"]:
        e2e, layer = manifest.metrics_of(spec, w["name"])
        assert "setup_s" in {m["name"] for m in e2e}
        assert ("group_size.put" in {m["name"] for m in layer}) == \
            ("put_MBps" in {m["name"] for m in e2e}), w["name"]


def test_missing_files_fail_with_the_list_of_what_exists():
    spec = manifest.load()
    with pytest.raises(manifest.ManifestError, match="k8m3.write4m"):
        manifest.resolve(spec, "no-such-cell")
    broken = json.loads(json.dumps(spec))
    broken["workloads"][0]["traffic"] = "no-such-mix"
    with pytest.raises(manifest.ManifestError,
                       match="rados-bench-write-4m-t16"):
        manifest.resolve(broken, broken["workloads"][0]["name"])
    broken = json.loads(json.dumps(spec))
    broken["configs"][0]["file"] = "benchmarks/configs/no-such.json"
    with pytest.raises(manifest.ManifestError, match="ec-k4m2-rs.json"):
        manifest.resolve(broken, broken["workloads"][0]["name"])
    with pytest.raises(FileNotFoundError, match="group_size.put"):
        layers.read("no_such_metric", {})


def test_no_cell_or_configuration_is_named_in_the_harness_code():
    spec = manifest.load()
    names = [w["name"] for w in spec["workloads"]] \
        + [c["name"] for c in spec["configs"]]
    bench = os.path.join(ROOT, "benchmarks")
    for dirpath, _dirs, files in os.walk(bench):
        if os.path.basename(dirpath) in ("tests", "__pycache__"):
            continue
        for f in files:
            if f.endswith(".py"):
                text = open(os.path.join(dirpath, f)).read()
                for name in names:
                    assert name not in text, (f, name)


# -- arithmetic ------------------------------------------------------------------


def test_percentile_and_mbps_on_a_fixed_list():
    vals = [float(v) for v in range(1, 101)]
    assert stats.percentile(vals, 95) == 95.0
    assert stats.percentile(vals, 50) == 50.0
    assert stats.percentile([7.0], 95) == 7.0
    assert stats.percentile([], 95) is None
    assert stats.mb_per_s(4194304 * 100, 10.0) == pytest.approx(41.94304)
    # four ops issued; one acknowledged after the window, one failed
    recs = [(0, 0.0, 1.0, True, 1_000_000), (1, 0.5, 2.0, True, 1_000_000),
            (2, 1.0, 11.0, True, 1_000_000), (3, 2.0, 3.0, False, 0)]
    seen = stats.window_metrics(recs, 0.0, 10.0)
    assert seen["attempted"] == 4 and seen["failed"] == 1
    assert seen["completed_in_window"] == 2
    assert seen["MBps"] == pytest.approx(0.2)
    assert seen["p95_ms"] == pytest.approx(1500.0)


def test_traffic_is_a_function_of_the_seed():
    a = Payloads(2**31 + 5, 4096, 4, "benchmark_data")
    b = Payloads(2**31 + 5, 4096, 4, "benchmark_data")
    c = Payloads(2**31 + 6, 4096, 4, "benchmark_data")
    assert [a.data(i) for i in range(9)] == [b.data(i) for i in range(9)]
    assert a.name(3) == b.name(3) != c.name(3)
    assert a.data(0) != c.data(0)
    assert len({a.data(i) for i in range(9)}) == 9  # the stamp tells them apart
    assert len(a.data(5)) == 4096
    acked = list(range(100))
    s1 = verify.sample(acked, 32, 16, 11)
    assert s1 == verify.sample(acked, 32, 16, 11)
    assert set(range(84, 100)) <= set(s1) and len(set(s1)) == 32
    assert verify.sample([1, 2, 3], 32, 16, 0) == [3, 2, 1]


# -- the reference and the verifier ---------------------------------------------


@pytest.mark.parametrize("k,m", [(8, 3), (4, 2)])
def test_reference_equals_the_native_jerasure_plugin(k, m):
    import numpy as np

    from ceph_tpu.ec.registry import registry

    prof = {"technique": "reed_sol_van", "k": str(k), "m": str(m)}
    codec = registry.factory("jerasure", "", dict(prof, plugin="jerasure"))
    assert np.array_equal(np.asarray(codec.matrix),
                          np.array(reed_sol_van.coding_matrix(k, m)))
    obj = np.random.default_rng(k).integers(
        0, 256, k * 4096 * 3, dtype=np.uint8).tobytes()
    want = codec.encode(set(range(k + m)), obj)
    # one stripe unit of 4096 * 3 bytes here, so that chunk i is contiguous
    got = reed_sol_van.shards(prof, 4096 * 3, obj)
    for i in range(k + m):
        assert bytes(np.asarray(want[i])) == got[i], i


def _held(payloads, names, profile):
    return {oid: {pos: [s] for pos, s in enumerate(
        reed_sol_van.shards(profile, 4096, payloads[oid]))} for oid in names}


def test_verifier_turns_false_on_a_flipped_byte_a_missing_shard_a_fallback():
    prof = {"k": "4", "m": "2"}
    payloads = {f"o{i}": bytes([i]) * 40000 for i in range(3)}

    def ref(data):
        return reed_sol_van.shards(prof, 4096, data)

    held = _held(payloads, payloads, prof)
    assert all(c["ok"] for c in verify.shards(held, payloads.get, ref))
    flipped = bytearray(held["o1"][5][0])
    flipped[17] ^= 1
    held["o1"][5] = [bytes(flipped)]
    got = {c["name"]: c for c in verify.shards(held, payloads.get, ref)}
    assert got["shards_differing_from_reference"]["value"] == 1
    assert not got["shards_differing_from_reference"]["ok"]
    assert got["shards_missing"]["ok"]
    held = _held(payloads, payloads, prof)
    del held["o2"][0]
    got = {c["name"]: c for c in verify.shards(held, payloads.get, ref)}
    assert got["shards_missing"]["value"] == 1
    assert not got["shards_missing"]["ok"]
    assert all(c["ok"] for c in verify.fallbacks({"ec_tpu.dispatch": 9}))
    for key in verify.FALLBACK_KEYS:
        assert not all(c["ok"] for c in verify.fallbacks({key: 1})), key


def test_a_miss_in_the_read_window_is_not_correct():
    """The read mix claims that every get is resident: one that was decoded
    from shards (a miss, a promote, an evict) makes the run another cell."""
    from types import SimpleNamespace

    from benchmarks.generators import closed_loop_get

    traffic = manifest.resolve(manifest.load(), "k8m3.randread4m",
                               rehearse=True).traffic
    env = SimpleNamespace(cell=SimpleNamespace(traffic=traffic), seed=7,
                          store_set="pagestore",
                          store_device_arm=lambda: True)
    gen = closed_loop_get.Generator(env)
    sound = gen.counter_checks({"pagestore.hit": 300})
    assert all(c["ok"] for c in sound), sound
    got = {c["name"]: c for c in gen.counter_checks(
        {"pagestore.hit": 299, "pagestore.miss": 1})}
    assert not got["pagestore.miss"]["ok"]
    assert not all(c["ok"] for c in gen.counter_checks({"pagestore.hit": 0}))


# -- counters and per-layer readers ---------------------------------------------


class _Set:
    def __init__(self, name, dump):
        self.name, self._dump = name, dump

    def dump(self):
        return self._dump


class _Coll:
    def __init__(self, *sets):
        self._sets = {s.name: s for s in sets}

    def dump(self):
        return {n: s.dump() for n, s in self._sets.items()}

    def get(self, name):
        return self._sets.get(name)


def test_a_shared_counter_set_counts_once_and_a_daemons_own_set_each():
    shared = _Set("ec_tpu", {"dispatch": 5,
                             "queue_wait": {"avgcount": 5, "sum": 0.5}})
    colls = [_Coll(shared, _Set("wire", {"tx_msgs": 10})) for _ in range(12)]
    flat = counters.snapshot(colls, [_Set("wire", {"tx_msgs": 7})])
    assert flat["ec_tpu.dispatch"] == 5
    assert flat["ec_tpu.queue_wait.count"] == 5
    assert flat["wire.tx_msgs"] == 127
    moved = counters.delta(flat, {"wire.tx_msgs": 100})
    assert moved["wire.tx_msgs"] == 27 and moved["ec_tpu.dispatch"] == 5


def test_perf_counter_readers_and_nothing_to_read():
    moved = {"ec_tpu.queue_wait.sum": 0.5, "ec_tpu.queue_wait.count": 100,
             "ec_tpu.submit": 130, "ec_tpu.dispatch": 100,
             "pagestore.hit": 0, "pagestore.miss": 0}
    ctx = {"counters": moved, "trace": None}
    assert layers.read("queue_wait_ms.put", ctx) == pytest.approx(5.0)
    assert layers.read("group_size.put", ctx) == pytest.approx(1.3)
    assert layers.read("resident_hit_share.get", ctx) is None  # none moved
    assert layers.read("dispatch_dev_ms.put", ctx) is None     # not there
    assert layers.read("device_idle_share.put", ctx) is None   # no trace
    assert layers.read("client_p95_ms.get", ctx) is None       # no window
    ctx["window"] = stats.window_metrics(
        [(i, 0.0, 0.001 * (i + 1), True, 10) for i in range(100)], 0.0, 1.0)
    assert layers.read("client_p95_ms.get", ctx) == pytest.approx(95.0)


# -- the trace reduction ---------------------------------------------------------


def _trace():
    ops = [["fusion.1", 0, 100], ["fusion.2", 50, 100],   # overlap: 150 busy
           ["copy.3", 300, 100], ["fusion.1", 900, 200]]  # runs past the span
    mods = [["jit__run(123)", 0, 150], ["jit_from_packedbit(9)", 300, 100],
            ["jit__run(123)", 900, 200]]
    host = [["PjitFunction(_run)", 140, 170], ["np.asarray", 850, 20],
            ["benchmark_traced_span", 0, 1000]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": mods},
            {"name": "Steps", "events": [["0", 0, 1000]]}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": host}]}]}


def test_busy_union_idle_share_and_gaps_on_a_synthetic_trace():
    assert trace_reduce.union([(0, 100), (50, 150), (300, 400), (7, 7)]) == \
        [(0, 150), (300, 400)]
    red = trace_reduce.reduce(_trace(), 0, 1000,
                              ignore=("benchmark_traced_span",))
    assert red["devices"] == 1
    assert red["window_s"] == pytest.approx(1000e-9)
    assert red["busy_s"] == pytest.approx(350e-9)  # 150 + 100 + 100 clipped
    assert dict(map(tuple, red["device_ops"]))["fusion.1"] == \
        pytest.approx(200e-9)
    gaps = dict(map(tuple, red["idle_gaps"]))
    assert sum(gaps.values()) == pytest.approx(650e-9)
    assert gaps["PjitFunction(_run)"] == pytest.approx(150e-9)
    # an event explains the part of a gap that it covers and no more
    assert gaps["np.asarray"] == pytest.approx(20e-9)
    assert gaps["unattributed"] == pytest.approx(480e-9)
    ctx = {"trace": red, "counters": {}}
    assert layers.read("device_idle_share.put", ctx) == pytest.approx(65.0)
    empty = trace_reduce.reduce({"planes": []})
    assert empty["busy_s"] == 0.0 and empty["devices"] == 0


def test_roofline_share_stays_under_100_and_an_unknown_device_is_an_error():
    red = trace_reduce.reduce(_trace(), 0, 1000)
    # two encode programs took 150 + 100 ns; at 819 GB/s that is ~205 bytes
    ctx = {"trace": red, "trace_counters": {"ec_tpu.bytes": 100},
           "device_kind": "TPU v5 lite", "profile": {"k": "8", "m": "3"}}
    share = layers.read("ec_kernel_hbm_share.put", ctx)
    least = peaks.ec_encode_min_bytes(8, 3, 100)
    assert least == pytest.approx(137.5)
    assert share == pytest.approx(100 * (137.5 / 819e9) / 250e-9)
    assert 0 < share <= 100
    assert layers.read("ec_kernel_hbm_share.put",
                       dict(ctx, trace_counters={})) is None
    with pytest.raises(KeyError, match="TPU v5 lite"):
        layers.read("ec_kernel_hbm_share.put",
                    dict(ctx, device_kind="TPU v9 imaginary"))


def test_recorded_v5e_trace_reduces_within_its_bounds():
    path = os.path.join(HERE, "fixtures", "trace_v5e.json")
    with open(path) as f:
        trace = json.load(f)
    red = trace_reduce.reduce(trace)
    assert red["devices"] == 1
    assert 0 < red["busy_s"] < red["window_s"]
    assert red["device_ops"] and red["modules"]
    assert any(re.search("^jit_from_packedbit", n)
               for n, _s, _d in red["modules"])
    ctx = {"trace": red, "counters": {}}
    assert 90 < layers.read("device_idle_share.get", ctx) < 100


# -- the whole harness, rehearsed on the CPU backend ------------------------------

LAST_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def test_rehearsal_runs_a_traced_cell_and_ends_false_with_exit_3():
    rc, lines = run_py("--workload", "k4m2.write4m", "--seed", "4000000007",
                       "--seconds", "2", "--trace", "1", "--rehearse")
    last = lines[-1]
    assert rc == 3 and last["correct"] is False and last["rehearsal"]
    assert LAST_KEYS | {"breakdown"} <= set(last)
    assert last["would_be_correct"] is True, lines
    assert last["attempted"] > 0 and last["failed"] == 0
    assert {"busy_s", "window_s", "platform", "kind", "count"} \
        <= set(last["device"])
    assert "group_size.put" in last["metrics"]
    assert "put_MBps" not in last["metrics"]  # per-layer metrics only
    assert all("phase" in ln for ln in lines[:-1])
    checks = next(ln for ln in lines if ln.get("phase") == "verify")["checks"]
    assert all({"name", "value", "limit", "ok"} <= set(c) for c in checks)


@pytest.mark.parametrize("cell,kind,failing", [
    ("k4m2.write4m", "store_flip", "shards_differing_from_reference"),
    ("k4m2.write4m", "store_drop", "acked_without_all_shards"),
    ("k8m3.randread4m", "reply_flip", "gets_not_identical"),
])
def test_the_timed_path_broken_underneath_comes_out_not_correct(
        cell, kind, failing):
    rc, lines = run_py("--workload", cell, "--seed", "12", "--seconds", "2",
                       "--trace", "0", "--rehearse", "--control", kind)
    last = lines[-1]
    assert rc == 3 and last["would_be_correct"] is False
    checks = next(ln for ln in lines if ln.get("phase") == "verify")["checks"]
    bad = {c["name"] for c in checks if not c["ok"]}
    assert failing in bad, bad


def test_without_a_tpu_the_runner_exits_nonzero_and_prints_no_metric():
    rc, lines = run_py("--workload", "k8m3.write4m", "--seed", "1",
                       "--seconds", "1", "--trace", "0",
                       env={"JAX_PLATFORMS": "cpu"})
    last = lines[-1]
    assert rc not in (0, 3)
    assert last["correct"] is False and last["metrics"] == {}
    assert "no TPU" in last["error"]
