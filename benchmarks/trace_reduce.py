"""From a profiler trace to numbers: device busy union, device time by
operation, idle gaps and what the host was doing in them.

A trace here is plain data, so that a recorded or synthetic one tests the
same code a chip run uses:

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, duration_ns], ...]}]}]}

`from_xplane(path)` makes that from the `.xplane.pb` the JAX profiler
writes (jax.profiler.ProfileData reads it with nothing but JAX).
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = re.compile(r"^/host:")
# on a TPU plane the line "XLA Ops" holds one event per operation that ran
# on the chip and "XLA Modules" one per whole program; the other lines
# ("Steps", "Framework Ops", name scopes) are groupings of those
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Interval = Tuple[int, int]


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def from_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    return {"planes": [
        {"name": plane.name,
         "lines": [{"name": line.name,
                    "events": [[ev.name, int(ev.start_ns),
                                int(ev.duration_ns)] for ev in line.events]}
                   for line in plane.lines]}
        for plane in data.planes]}


def summary(trace: dict, top: int = 12) -> list:
    """What is in a trace, for a human: planes, lines, event counts and the
    names that took most time on each line."""
    out = []
    for plane in trace["planes"]:
        for line in plane["lines"]:
            by_name: Dict[str, int] = {}
            for name, _s, dur in line["events"]:
                by_name[name] = by_name.get(name, 0) + dur
            names = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
            out.append({"plane": plane["name"], "line": line["name"],
                        "events": len(line["events"]),
                        "top": [[n, d / 1e9] for n, d in names]})
    return out


def device_planes(trace: dict) -> List[dict]:
    return [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]


def line_events(plane: dict, line_name: str) -> list:
    return [ev for line in plane["lines"] if line["name"] == line_name
            for ev in line["events"]]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge overlapping [start, end) intervals."""
    merged: List[List[int]] = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def clip(intervals: Iterable[Interval], t0: int, t1: int) -> List[Interval]:
    return [(max(a, t0), min(b, t1)) for a, b in intervals
            if min(b, t1) > max(a, t0)]


def span(trace: dict) -> Optional[Interval]:
    """[first start, last end) over every event of every plane."""
    starts = [s for p in trace["planes"] for line in p["lines"]
              for _n, s, _d in line["events"]]
    ends = [s + d for p in trace["planes"] for line in p["lines"]
            for _n, s, d in line["events"]]
    return (min(starts), max(ends)) if starts else None


def busy(plane: dict, t0: int, t1: int) -> List[Interval]:
    """The intervals inside [t0, t1) in which an operation ran on this
    device: the union of its "XLA Ops" events."""
    return union(clip(((s, s + d) for _n, s, d in
                       line_events(plane, OPS_LINE)), t0, t1))


def seconds(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals) / 1e9


def time_by_name(events: Iterable, t0: int, t1: int,
                 match: Optional[str] = None) -> Dict[str, float]:
    """Summed device seconds by event name, events clipped to [t0, t1)."""
    pat = re.compile(match) if match else None
    out: Dict[str, float] = {}
    for name, s, d in events:
        if pat is not None and not pat.search(name):
            continue
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out[name] = out.get(name, 0.0) + (b - a) / 1e9
    return out


def gaps(busy_intervals: List[Interval], t0: int, t1: int) -> List[Interval]:
    out, at = [], t0
    for a, b in busy_intervals:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if t1 > at:
        out.append((at, t1))
    return out


def attribute_gaps(gap_list: List[Interval], trace: dict, top: int = 10,
                   longest: int = 256, ignore: tuple = ()) -> List[list]:
    """Idle seconds by what the host was doing: of each of the `longest`
    gaps, the part that the longest-overlapping host event covers goes to
    that event's name, and the rest to "unattributed" (the host was in no
    event the trace has: the program's own Python); the shorter gaps are
    summed under one name.  The `top` names by summed idle seconds."""
    import numpy as np

    host = [(s, s + d, name) for p in trace["planes"]
            if HOST_PLANE.match(p["name"]) for line in p["lines"]
            for name, s, d in line["events"] if d > 0 and name not in ignore]
    starts = np.array([h[0] for h in host], dtype=np.int64)
    ends = np.array([h[1] for h in host], dtype=np.int64)
    ranked = sorted(gap_list, key=lambda g: g[0] - g[1])
    by_name: Dict[str, float] = {}
    for a, b in ranked[:longest]:
        covered = 0
        if host:
            over = np.minimum(ends, b) - np.maximum(starts, a)
            i = int(over.argmax())
            if over[i] > 0:
                covered, name = int(over[i]), host[i][2]
                by_name[name] = by_name.get(name, 0.0) + covered / 1e9
        if b - a > covered:
            by_name["unattributed"] = by_name.get("unattributed", 0.0) \
                + (b - a - covered) / 1e9
    rest = sum(b - a for a, b in ranked[longest:]) / 1e9
    if rest:
        by_name[f"gaps_beyond_the_{longest}_longest"] = rest
    return [[n, s] for n, s in
            sorted(by_name.items(), key=lambda kv: -kv[1])[:top]]


def reduce(trace: dict, t0: Optional[int] = None, t1: Optional[int] = None,
           ignore: tuple = ()) -> dict:
    """Everything the harness reads from one trace.  [t0, t1) defaults to
    the trace's own span; host events named in `ignore` (the harness's own
    annotation of the span) explain no gap.  Busy seconds are averaged over the device
    planes; the breakdown is of the busiest plane."""
    whole = span(trace)
    planes = device_planes(trace)
    if whole is None:
        return {"window_s": 0.0, "busy_s": 0.0, "devices": len(planes),
                "device_ops": [], "idle_gaps": [], "modules": [],
                "t0": 0, "t1": 0}
    t0 = whole[0] if t0 is None else t0
    t1 = whole[1] if t1 is None else t1
    per_plane = [busy(p, t0, t1) for p in planes]
    busy_s = (sum(seconds(b) for b in per_plane) / len(per_plane)
              if per_plane else 0.0)
    out = {"window_s": (t1 - t0) / 1e9, "busy_s": busy_s,
           "devices": len(planes), "t0": t0, "t1": t1,
           "device_ops": [], "idle_gaps": [], "modules": []}
    if planes:
        i = max(range(len(planes)), key=lambda j: seconds(per_plane[j]))
        ops = line_events(planes[i], OPS_LINE)
        out["modules"] = line_events(planes[i], MODULES_LINE)
        ranked = sorted(time_by_name(ops, t0, t1).items(),
                        key=lambda kv: -kv[1])[:10]
        out["device_ops"] = [[n, s] for n, s in ranked]
        out["idle_gaps"] = attribute_gaps(
            gaps(per_plane[i], t0, t1), trace, ignore=ignore)
    return out
