"""Tests of the per-layer metrics PR 40 added: the loop's busy seconds by
the family of the message they served (`loop.for_<family>`, booked by
`tracing.charge` from inside the messenger), read off a window's counter
delta by eight `.json` readers.  CPU only.

    python -m pytest benchmarks/tests/test_message_charges.py -q
"""

from __future__ import annotations

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import layers, manifest  # noqa: E402

WRITE_CELLS = ["k8m3.write4m", "k4m2.write4m", "k10m4c.write4m",
               "k8m3.mixed-small"]
READ_CELLS = ["k8m3.randread4m", "k8m3.randread4m-cold"]

# a window's counter delta as counters.snapshot names it: 30 s of a loop
# that was busy for 27, cut by layer and, the same seconds, by cause
DELTA = {
    "loop.busy.sum": 27.0, "loop.busy.count": 300000,
    "loop.select.sum": 3.0,
    "loop.self_messenger.sum": 20.0, "loop.self_osd.sum": 7.0,
    "loop.for_op.sum": 15.0, "loop.for_liveness.sum": 2.7,
    "loop.for_tier.sum": 1.35, "loop.for_recovery.sum": 0.27,
    "loop.for_control.sum": 1.08, "loop.for_ack.sum": 2.16,
    "loop.for_none.sum": 4.44,
    "loop.msg_MECSubWrite.sum": 9.0, "loop.msg_MECSubWrite.count": 6600,
    "objecter.op": 600,
}
WANT = {"op_msg_loop_ms": 25.0, "housekeeping_loop_share": 20.0,
        "ack_loop_share": 8.0, "unmessaged_loop_share": 4.44 / 0.27}
NAMES = [base + kind for base in WANT for kind in (".put", ".get")]


def test_the_fixture_is_two_cuts_of_the_same_seconds():
    for prefix in ("loop.for_", "loop.self_"):
        assert sum(v for k, v in DELTA.items() if k.startswith(prefix)
                   and k.endswith(".sum")) == pytest.approx(
            DELTA["loop.busy.sum"])


@pytest.mark.parametrize("name", NAMES)
def test_manifest_entry_and_reader(name):
    spec = manifest.load()
    entry = [m for m in spec["per_layer"] if m["name"] == name]
    assert len(entry) == 1
    entry = entry[0]
    put = name.endswith(".put")
    assert entry == {
        "name": name, "unit": "ms" if name.startswith("op_msg") else "%",
        "better": "lower", "source": "program_counter",
        "layer": "host loop", "moves": "put_MBps" if put else "get_MBps",
        "workloads": WRITE_CELLS if put else READ_CELLS}
    # every listed cell reports the end-to-end metric the metric moves,
    # and prints the metric
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    for cell in entry["workloads"]:
        assert cell in e2e[entry["moves"]]["workloads"]
        assert name in [m["name"] for m in manifest.metrics_of(spec, cell)[1]]
    # data, no reader code
    assert os.path.exists(os.path.join(layers.DIR, name + ".json"))
    assert not os.path.exists(os.path.join(layers.DIR, name + ".py"))
    ctx = {"counters": dict(DELTA), "trace_counters": {}, "trace": None,
           "window": {}}
    assert layers.read(name, ctx) == pytest.approx(
        WANT[name.rsplit(".", 1)[0]])


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_charges_reports_nothing(name):
    """The parent commit under this PR's benchmark files: no `for_*` key,
    so no value and no raise; and a window in which a family did not move
    still reads (the keys exist from build_loop_perf on)."""
    old = {k: v for k, v in DELTA.items()
           if not k.startswith(("loop.for_", "loop.msg_"))}
    assert layers.read(name, {"counters": old}) is None
    quiet = {**DELTA, "loop.for_tier.sum": 0.0, "loop.for_recovery.sum": 0.0,
             "loop.for_ack.sum": 0.0}
    got = layers.read(name, {"counters": quiet})
    assert got == 0.0 if name.startswith("ack") else got > 0
    idle = {**DELTA, "loop.busy.sum": 0.0, "objecter.op": 0}
    assert layers.read(name, {"counters": idle}) is None


def test_the_shares_and_the_op_row_account_for_the_whole_loop():
    ctx = {"counters": dict(DELTA)}
    shares = sum(layers.read(base + ".put", ctx) for base in (
        "housekeeping_loop_share", "ack_loop_share", "unmessaged_loop_share"))
    op_share = 100.0 * (layers.read("op_msg_loop_ms.put", ctx)
                        * DELTA["objecter.op"] / 1000.0) / DELTA["loop.busy.sum"]
    assert shares + op_share == pytest.approx(100.0)


def test_the_program_makes_the_keys_the_readers_name():
    """The readers' keys are the `loop` set's, by name: a renamed family
    would silence a metric without failing a run."""
    import json

    from ceph_tpu.common import tracing

    made = {f"loop.for_{family}.sum" for family in tracing.FAMILIES}
    read = set()
    for name in NAMES:
        with open(os.path.join(layers.DIR, name + ".json")) as f:
            spec = json.load(f)
        read |= {k for k in spec["num"] if k.startswith("loop.for_")}
    assert read == made
    dump = tracing.build_loop_perf("loop.t").dump()
    assert all(k.split(".")[1] in dump for k in made)
