"""Per-layer metrics: each is a file of its own under layer_metrics/, found
by the metric's name in BENCHMARK.json.

    <name>.json  {"source": "perf_counter", "num": [...], "den": [...],
                  "scale": 1000.0}
                 sum of the window's deltas of the `num` keys over that of
                 the `den` keys, times scale; without `den`, the delta
                 itself.  Keys are "set.key" / "set.key.sum" / ".count" as
                 counters.snapshot names them.
                 {"source": "trace", "reduce": "idle_share"}
                 {"source": "trace", "reduce": "hbm_share", "match": regex
                  on program names, "min_bytes": function in peaks.py,
                  "bytes_key": counter of bytes the programs worked on}
                 {"source": "window", "key": "p95_ms"}
                 a statistic of the window's own op records, as
                 stats.window_metrics names them: a client-side number
                 that is too unsteady to carry a bound stands here.
    <name>.py    read(ctx) -> number or None, for anything else.

A reader that finds nothing to read (a denominator that did not move, a
trace without device events) returns None and the metric is left out.

`ctx` is a dict: "counters" (delta over the window), "trace_counters"
(delta over the traced span), "trace" (trace_reduce.reduce's result, or
None), "window" (stats.window_metrics' result), "device_kind", "profile"
(the pool's EC profile).
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Optional

from benchmarks import peaks, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
DIR = os.path.join(HERE, "layer_metrics")


def available() -> list:
    return sorted(os.path.splitext(f)[0] for f in os.listdir(DIR)
                  if f.endswith((".json", ".py")))


def _sum(counters: dict, keys: list) -> Optional[float]:
    if any(k not in counters for k in keys):
        return None
    return sum(counters[k] for k in keys)


def _perf_counter(spec: dict, ctx: dict) -> Optional[float]:
    counters = ctx["counters"]
    num = _sum(counters, spec["num"])
    if num is None:
        return None
    scale = float(spec.get("scale", 1.0))
    if not spec.get("den"):
        return scale * num
    den = _sum(counters, spec["den"])
    if not den:
        return None
    return scale * num / den


def _trace(spec: dict, ctx: dict) -> Optional[float]:
    red = ctx.get("trace")
    if not red or red["window_s"] <= 0 or not red["devices"]:
        return None
    if spec["reduce"] == "idle_share":
        return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
    if spec["reduce"] == "hbm_share":
        by_name = trace_reduce.time_by_name(
            red["modules"], red["t0"], red["t1"], spec["match"])
        kernel_s = sum(by_name.values())
        nbytes = ctx["trace_counters"].get(spec["bytes_key"], 0)
        if kernel_s <= 0 or nbytes <= 0:
            return None
        prof = ctx["profile"]
        least = getattr(peaks, spec["min_bytes"])(
            int(prof["k"]), int(prof["m"]), nbytes)
        return peaks.roofline_share(least, kernel_s, ctx["device_kind"])
    raise ValueError(f"unknown trace reduction {spec['reduce']!r}")


def read(name: str, ctx: dict) -> Optional[float]:
    """The metric's value in this run, or None where there is nothing to
    read.  A metric with no file is an error that lists what exists."""
    as_json = os.path.join(DIR, name + ".json")
    as_py = os.path.join(DIR, name + ".py")
    if os.path.exists(as_json):
        with open(as_json) as f:
            spec = json.load(f)
        if spec["source"] == "perf_counter":
            return _perf_counter(spec, ctx)
        if spec["source"] == "trace":
            return _trace(spec, ctx)
        if spec["source"] == "window":
            return (ctx.get("window") or {}).get(spec["key"])
        raise ValueError(f"{as_json}: unknown source {spec['source']!r}")
    if os.path.exists(as_py):
        spec = importlib.util.spec_from_file_location(
            "benchmarks.layer_metrics." + name.replace(".", "_"), as_py)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read(ctx)
    raise FileNotFoundError(
        f"no reader for per-layer metric {name!r}: there is no "
        f"{name}.json or {name}.py under {DIR}; it has {available()}")
