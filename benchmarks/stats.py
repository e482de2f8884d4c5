"""The arithmetic of the end-to-end metrics (pure functions, no clock)."""

from __future__ import annotations

import math
from typing import Iterable, Optional, Sequence

MB = 1_000_000  # rados bench reports MB/s in 10^6 bytes


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank percentile (the smallest value with at least q% of the
    sample at or below it); None for an empty sample."""
    if not values:
        return None
    if not 0 < q <= 100:
        raise ValueError(f"percentile {q} not in (0, 100]")
    ordered = sorted(values)
    return ordered[max(0, math.ceil(q / 100.0 * len(ordered)) - 1)]


def mb_per_s(nbytes: int, seconds: float) -> float:
    if seconds <= 0:
        raise ValueError("window of no length")
    return nbytes / MB / seconds


def window_metrics(records: Iterable, t0: float, t1: float) -> dict:
    """Reduce a window's op records to what a client of `rados bench` sees.

    A record is (index, t_issue, t_done, ok, nbytes), times on one clock.
    Bytes count where the op was acknowledged inside [t0, t1]; the tail is
    over those same ops; every op issued in the window is attempted, and
    one that raised, timed out or compared unequal has failed."""
    records = list(records)
    done = [r for r in records if r[3] and r[2] <= t1]
    lat_ms = [(r[2] - r[1]) * 1e3 for r in done]
    return {
        "attempted": len(records),
        "failed": sum(1 for r in records if not r[3]),
        "completed_in_window": len(done),
        "MBps": mb_per_s(sum(r[4] for r in done), t1 - t0),
        "p95_ms": percentile(lat_ms, 95),
        "p50_ms": percentile(lat_ms, 50),
        "max_ms": max(lat_ms) if lat_ms else None,
    }
