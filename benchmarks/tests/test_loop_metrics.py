"""Tests of the per-layer metrics PR 26 added: every new `.json` reader on
a synthetic counter delta, `idle_unnamed_share` on a synthetic trace in the
plain-data form trace_reduce documents, and a rehearsal that has to show
`ceph.*` events in the profiler's host plane.  CPU only."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import idle_sections, layers, manifest  # noqa: E402

BASES = (
    "loop_busy_share", "loop_cpu_share", "loop_lag_ms", "client_self_ms",
    "messenger_self_ms", "osd_self_ms", "ecplan_self_ms", "store_self_ms",
    "background_self_ms", "unnamed_self_ms", "dispatch_launch_ms",
    "dispatch_fetch_ms", "osd_queue_wait_ms", "osd_ec_wait_ms",
    "osd_subop_wait_ms", "idle_unnamed_share")
NEW = [m for m in manifest.load()["per_layer"]
       if m["name"].rsplit(".", 1)[0] in BASES]
JSON_READERS = [m for m in NEW if m["name"].split(".")[0] != "idle_unnamed_share"]

# a window's counter delta as counters.snapshot names it
DELTA = {
    "loop.busy.sum": 27.0, "loop.busy.count": 300000,
    "loop.select.sum": 3.0, "loop.cpu.sum": 21.6,
    "loop.lag.sum": 6.0, "loop.lag.count": 1200,
    "loop.self_client.sum": 1.0, "loop.self_messenger.sum": 12.0,
    "loop.self_osd.sum": 8.0, "loop.self_ecplan.sum": 2.0,
    "loop.self_store.sum": 1.5, "loop.self_background.sum": 1.5,
    "loop.self_unnamed.sum": 1.0, "objecter.op": 500,
    "ec_tpu.launch.sum": 1.0, "ec_tpu.launch.count": 500,
    "ec_tpu.fetch.sum": 2.5, "ec_tpu.fetch.count": 500,
    "optracker.lat_queue_wait.sum": 100.0,
    "optracker.lat_queue_wait.count": 500,
    "optracker.lat_ec_dispatch.sum": 40.0,
    "optracker.lat_ec_dispatch.count": 500,
    "optracker.lat_subop_wait.sum": 130.0,
    "optracker.lat_subop_wait.count": 500,
}
WANT = {
    "loop_busy_share": 90.0, "loop_cpu_share": 80.0, "loop_lag_ms": 5.0,
    "client_self_ms": 2.0, "messenger_self_ms": 24.0, "osd_self_ms": 16.0,
    "ecplan_self_ms": 4.0, "store_self_ms": 3.0, "background_self_ms": 3.0,
    "unnamed_self_ms": 2.0, "dispatch_launch_ms": 2.0,
    "dispatch_fetch_ms": 5.0, "osd_queue_wait_ms": 200.0,
    "osd_ec_wait_ms": 80.0, "osd_subop_wait_ms": 260.0,
}


def test_the_manifest_gained_the_metrics_the_issue_names():
    names = {m["name"] for m in NEW}
    assert len(NEW) == 28
    for base in WANT:
        assert base + ".put" in names
    for base in ("loop_busy_share", "loop_cpu_share", "loop_lag_ms",
                 "osd_queue_wait_ms", "idle_unnamed_share",
                 *(b for b in WANT if b.endswith("_self_ms"))):
        assert base + ".get" in names
    cells = {w["name"] for w in manifest.load()["workloads"]}
    e2e = {m["name"]: m for m in manifest.load()["end_to_end"]}
    for m in NEW:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert set(m["workloads"]) <= cells
        for cell in m["workloads"]:  # the cell reports what the metric moves
            assert cell in e2e[m["moves"]]["workloads"]


@pytest.mark.parametrize("metric", JSON_READERS, ids=lambda m: m["name"])
def test_json_reader_on_a_synthetic_delta(metric):
    ctx = {"counters": dict(DELTA), "trace_counters": {}, "trace": None,
           "window": {}}
    base = metric["name"].rsplit(".", 1)[0]
    assert layers.read(metric["name"], ctx) == pytest.approx(WANT[base])
    # a program without the counters (the parent commit): nothing, no raise
    old = {k: v for k, v in DELTA.items()
           if not k.startswith(("loop.", "ec_tpu.launch", "ec_tpu.fetch"))}
    got = layers.read(metric["name"], {**ctx, "counters": old})
    assert got is None or base.startswith("osd_")  # optracker was there


def test_self_ms_metrics_close_on_busy():
    ctx = {"counters": dict(DELTA)}
    total = sum(layers.read(f"{layer}_self_ms.put", ctx) for layer in (
        "client", "messenger", "osd", "ecplan", "store", "background",
        "unnamed"))
    assert total * DELTA["objecter.op"] / 1000.0 == pytest.approx(
        DELTA["loop.busy.sum"])


# -- idle_unnamed_share ----------------------------------------------------------

LOOP = [["ceph.loop.task_osd_run_item", 0, 100],
        ["ceph.osd.write_commit", 10, 40], ["ceph.store.commit", 20, 10],
        ["ceph.loop.select", 100, 50], ["ceph.loop.io_read", 160, 10],
        ["ceph.clock.1000000.5000", 5, 0], ["ceph.clock.1000150.5150", 155, 0]]
TRACE = {"planes": [
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [["fusion", 40, 20],
                                       ["fusion", 120, 10]]},
        {"name": "XLA Modules", "events": [["jit__run(1)", 40, 20]]}]},
    {"name": "/host:CPU", "lines": [
        {"name": "python3", "events": LOOP},
        {"name": "queue", "events": [["ceph.devbound.fetch", 150, 30],
                                     ["PjitFunction(_run)", 0, 200]]}]}]}


def test_flatten_names_each_instant_by_its_innermost_event():
    pieces = idle_sections.flatten([e for e in LOOP if "clock" not in e[0]])
    assert pieces == [
        (0, 10, "ceph.loop.task_osd_run_item"),
        (10, 20, "ceph.osd.write_commit"), (20, 30, "ceph.store.commit"),
        (30, 50, "ceph.osd.write_commit"),
        (50, 100, "ceph.loop.task_osd_run_item"),
        (100, 150, "ceph.loop.select"), (160, 170, "ceph.loop.io_read")]


def test_idle_report_on_a_synthetic_trace():
    got = idle_sections.report(TRACE, 0, 200)
    # idle: [0,40) [60,120) [130,200) = 170 ns; the ceph events of both
    # threads cover [0,180) of it but not [180,200)
    assert got["idle_s"] == pytest.approx(170e-9)
    assert got["unnamed_s"] == pytest.approx(20e-9)
    by = dict(got["by_section"])
    assert by["ceph.loop.select"] == pytest.approx(40e-9)
    assert by["ceph.devbound.fetch"] == pytest.approx(30e-9)
    assert by["ceph.osd.write_commit"] == pytest.approx(20e-9)
    assert "PjitFunction(_run)" not in by and "ceph.clock" not in str(by)
    clock = got["clock"]
    assert clock["anchors"] == 2
    assert clock["time_ns_minus_start_ns"] == {
        "first": 999995, "last": 999995, "spread_ns": 0}
    assert clock["perf_counter_ns_minus_start_ns"]["spread_ns"] == 0


def test_idle_unnamed_share_reader(tmp_path, monkeypatch):
    from benchmarks import trace_reduce

    monkeypatch.setattr(trace_reduce, "find_xplane", lambda d: "x")
    monkeypatch.setattr(trace_reduce, "from_xplane", lambda p: TRACE)
    out = tmp_path / "idle_by_section.json"
    red = {"window_s": 200e-9, "devices": 1, "t0": 0, "t1": 200}
    share = idle_sections.read({"trace": red}, str(tmp_path), str(out))
    assert share == pytest.approx(100.0 * 20 / 170)
    assert json.loads(out.read_text())["threads"] == 2
    # through layers.read, by the metric's name, as run.py does
    mod_dir = os.path.join(layers.DIR, "idle_unnamed_share.put.py")
    assert os.path.exists(mod_dir)
    # nothing to read: no trace, no device, or a program without sections
    assert idle_sections.read({"trace": None}, str(tmp_path), str(out)) is None
    assert idle_sections.read({"trace": dict(red, devices=0)},
                              str(tmp_path), str(out)) is None
    bare = {"planes": [TRACE["planes"][0], {
        "name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ["PjitFunction(_run)", 0, 200]]}]}]}
    monkeypatch.setattr(trace_reduce, "from_xplane", lambda p: bare)
    assert idle_sections.read({"trace": red}, str(tmp_path), str(out)) is None
    assert layers.read("idle_unnamed_share.get", {"trace": None}) is None


# -- the whole path, rehearsed ------------------------------------------------------


def test_a_rehearsed_traced_run_shows_ceph_events_in_the_host_plane():
    env = dict(os.environ)
    env.pop("CEPH_TPU_FORCE_BATCH", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "k8m3.write4m", "--seed", "2147483659",
         "--seconds", "4", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=400)
    lines = [json.loads(ln) for ln in proc.stdout.splitlines() if ln.strip()]
    assert proc.returncode == 3, proc.stderr[-2000:]
    last = lines[-1]
    assert last["would_be_correct"], lines[-6:]
    metrics = last["metrics"]
    for base in WANT:  # every counter-read metric of the PR reports
        assert base + ".put" in metrics, (base, sorted(metrics))
    busy = metrics["loop_busy_share.put"]["value"]
    assert 0 < busy <= 100.0
    moved = next(ln["moved"] for ln in lines if ln.get("phase") == "counters")
    window = next(ln for ln in lines if ln.get("phase") == "window")
    trace = next(ln for ln in lines if ln.get("phase") == "trace")
    # closure: busy + select is the loop thread's wall time between the two
    # snapshots, which are the window, its drain and what of the profiler's
    # stop fell after it
    wall = moved["loop.busy.sum"] + moved["loop.select.sum"]
    least = window["seconds"] + window["drained_s"]
    assert least * 0.98 <= wall <= least + trace["stop_trace_s"] + 0.5
    layers_sum = sum(v for k, v in moved.items()
                     if k.startswith("loop.self_") and k.endswith(".sum"))
    assert layers_sum == pytest.approx(moved["loop.busy.sum"], rel=0.02)
    assert moved["ec_tpu.launch.count"] == moved["ec_tpu.fetch.count"] > 0
    assert moved["ec_tpu.h2d_bytes"] > 0 and moved["ec_tpu.d2h_bytes"] > 0
    assert moved["ecplan.plans"] >= last["attempted"] > 0
    with open(os.path.join(ROOT, "benchmarks", ".trace",
                           "summary.json")) as f:
        summary = json.load(f)
    host = [ln for ln in summary if ln["plane"].startswith("/host:")]
    names = {n for ln in host for n, _s in ln["top"]}
    assert any(n.startswith("ceph.loop.") for n in names), names
    assert any(n.startswith("ceph.") and not n.startswith("ceph.loop.")
               for n in names), names
