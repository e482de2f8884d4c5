"""The device's idle time by what the program's own threads were doing.

The program writes its sections and its loop's steps into the profiler's
host plane as `ceph.<layer>.<name>` events (ceph_tpu/common/tracing.py):
sections of synchronous work, `ceph.loop.<kind>` around every handle the
event loop runs, `ceph.loop.select` around its wait for I/O.  On one
thread they nest, so each instant of a thread belongs to its innermost
event; `flatten` gives those pieces.  `report` lays them over the gaps
between the device's "XLA Ops" events:

    idle_s      the device's idle seconds in [t0, t1)
    unnamed_s   of them, those covered by no ceph.* event on any thread
    by_section  idle seconds under each name (innermost event), every
                thread's summed: threads overlap, so the rows can add up
                to more than idle_s
    clock       the `ceph.clock.<time_ns>.<perf_counter_ns>` anchors:
                each clock's reading minus the event's start_ns, first
                and last anchor (the offset that puts a Span's time_ns on
                the trace's clock) and how far the offsets spread

`trace_reduce.attribute_gaps` credits a gap to its single longest host
event; this is the full account.  A trace without any ceph.* event (the
program has no sections: a parent commit) gives None.
"""

from __future__ import annotations

import json
from typing import Dict, List, Optional, Tuple

import numpy as np

from benchmarks import trace_reduce

PREFIX = "ceph."
CLOCK = "ceph.clock."
TOP = 60  # rows of by_section kept


def flatten(events: List[list]) -> List[Tuple[int, int, str]]:
    """Nested [name, start, duration] events of ONE thread -> disjoint
    (start, end, name) pieces, each named by its innermost event."""
    pieces: List[Tuple[int, int, str]] = []
    stack: List[Tuple[int, str]] = []  # (end, name), outermost first
    at = 0

    def unwind(until: Optional[int]) -> None:
        nonlocal at
        while stack and (until is None or stack[-1][0] <= until):
            end, name = stack.pop()
            if end > at:
                pieces.append((at, end, name))
                at = end

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        unwind(start)
        if stack:
            if start > at:
                pieces.append((at, start, stack[-1][1]))
            # a child may end a rounding step after its parent: cut it
            end = min(start + dur, stack[0][0])
        else:
            end = start + dur
        at = max(at, start)
        if end > start:
            stack.append((end, name))
    unwind(None)
    return pieces


def _overlap_with(gaps: List[Tuple[int, int]]):
    """f(starts, ends) -> ns of each [start, end) that lies inside the
    (disjoint, sorted) gaps."""
    g0 = np.array([g[0] for g in gaps], dtype=np.int64)
    g1 = np.array([g[1] for g in gaps], dtype=np.int64)
    before = np.concatenate([[0], np.cumsum(g1 - g0)])  # gap ns before gap i

    def gap_ns_until(t: np.ndarray) -> np.ndarray:
        i = np.searchsorted(g0, t, side="right") - 1
        inside = np.where(i >= 0, np.minimum(t, g1[i]) - g0[i], 0)
        return before[np.maximum(i, 0)] * (i >= 0) + np.maximum(inside, 0)

    return lambda s, e: gap_ns_until(e) - gap_ns_until(s)


def report(trace: dict, t0: int, t1: int) -> Optional[dict]:
    planes = trace_reduce.device_planes(trace)
    if not planes:
        return None
    busy = max((trace_reduce.busy(p, t0, t1) for p in planes),
               key=trace_reduce.seconds)
    gaps = trace_reduce.gaps(busy, t0, t1)
    idle_ns = sum(b - a for a, b in gaps)
    threads = [[ev for ev in line["events"] if ev[0].startswith(PREFIX)]
               for p in trace["planes"]
               if trace_reduce.HOST_PLANE.match(p["name"])
               for line in p["lines"]]
    threads = [t for t in threads if t]
    if not threads or not gaps:
        return None
    anchors = [(ev[1], *map(int, ev[0][len(CLOCK):].split(".")))
               for t in threads for ev in t if ev[0].startswith(CLOCK)]
    overlap = _overlap_with(gaps)
    by_name: Dict[str, float] = {}
    covered: List[Tuple[int, int]] = []
    for events in threads:
        pieces = flatten([ev for ev in events
                          if not ev[0].startswith(CLOCK)])
        if not pieces:
            continue
        starts = np.array([p[0] for p in pieces], dtype=np.int64)
        ends = np.array([p[1] for p in pieces], dtype=np.int64)
        for (_s, _e, name), ns in zip(pieces, overlap(starts, ends)):
            if ns > 0:
                by_name[name] = by_name.get(name, 0.0) + float(ns) / 1e9
        covered.extend((int(s), int(e)) for s, e in zip(starts, ends))
    merged = trace_reduce.union(covered)
    named_ns = int(overlap(np.array([m[0] for m in merged], dtype=np.int64),
                           np.array([m[1] for m in merged],
                                    dtype=np.int64)).sum()) if merged else 0
    out = {"idle_s": idle_ns / 1e9,
           "unnamed_s": (idle_ns - named_ns) / 1e9,
           "threads": len(threads),
           "by_section": sorted(([n, s] for n, s in by_name.items()),
                                key=lambda r: -r[1])[:TOP]}
    if anchors:
        anchors.sort()
        offs = {"time_ns": [a[1] - a[0] for a in anchors],
                "perf_counter_ns": [a[2] - a[0] for a in anchors]}
        out["clock"] = {
            "anchors": len(anchors),
            **{f"{k}_minus_start_ns": {"first": v[0], "last": v[-1],
                                       "spread_ns": max(v) - min(v)}
               for k, v in offs.items()}}
    return out


def read(ctx: dict, trace_dir: str, out_path: str) -> Optional[float]:
    """The per-layer metric `idle_unnamed_share`: re-reads the run's
    xplane, cut to the span the harness reduced, and writes the whole
    account beside it."""
    red = ctx.get("trace")
    if not red or red.get("window_s", 0) <= 0 or not red.get("devices"):
        return None
    try:
        path = trace_reduce.find_xplane(trace_dir)
    except FileNotFoundError:
        return None
    got = report(trace_reduce.from_xplane(path), red["t0"], red["t1"])
    if got is None or got["idle_s"] <= 0:
        return None
    with open(out_path, "w") as f:
        json.dump(got, f, indent=1)
    return 100.0 * got["unnamed_s"] / got["idle_s"]
