"""Perf counters: cheap in-process metrics with admin-socket dumps.

Role-equivalent of the reference's PerfCounters/PerfCountersCollection
(reference src/common/perf_counters.cc): a daemon builds named counter sets
(PerfCountersBuilder), bumps them on the hot path (inc/dec/set/tinc/hinc),
and operators read them via ``perf dump`` on the admin socket and via the
mgr's prometheus exporter.  Three kinds mirror the reference:

- u64 counters/gauges (PERFCOUNTER_U64)
- time/long-run averages: (sum, count) pairs dumped as avgcount+sum
  (PERFCOUNTER_LONGRUNAVG — l_osd_op_lat style, src/osd/osd_perf_counters.cc:49)
- 2D histograms of (value, count) power-of-2 buckets (PERFCOUNTER_HISTOGRAM)
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Any, Dict, List, Optional

U64 = "u64"
LONGRUNAVG = "longrunavg"
HISTOGRAM = "histogram"


class _Counter:
    __slots__ = ("name", "kind", "desc", "value", "sum", "count", "buckets")

    def __init__(self, name: str, kind: str, desc: str):
        self.name = name
        self.kind = kind
        self.desc = desc
        self.value = 0
        self.sum = 0.0
        self.count = 0
        self.buckets: Optional[List[int]] = [0] * 32 if kind == HISTOGRAM else None


class PerfCounters:
    """One named set of counters (e.g. 'osd', 'ec_tpu', 'messenger')."""

    def __init__(self, name: str):
        self.name = name
        self._counters: Dict[str, _Counter] = {}
        self._lock = threading.Lock()
        # optional owner callback invoked after reset(): gauge-style
        # counters (cache entries, resident bytes) mirror LIVE state that
        # zeroing misreports until the next mutation — the owner re-sets
        # them here so `perf reset` restarts rates without lying gauges
        self.resync: Optional[Any] = None
        # optional owner callback invoked BEFORE dump() and reset():
        # counters whose source of truth lives outside the set (the loop
        # meters' running sums) fold in here so every dump is up to now
        self.presample: Optional[Any] = None

    # -- hot path ------------------------------------------------------------

    def inc(self, name: str, amount: int = 1) -> None:
        c = self._counters[name]
        with self._lock:
            c.value += amount

    def dec(self, name: str, amount: int = 1) -> None:
        c = self._counters[name]
        with self._lock:
            c.value -= amount

    def set(self, name: str, value: int) -> None:
        c = self._counters[name]
        with self._lock:
            c.value = value

    def tinc(self, name: str, seconds: float, count: int = 1) -> None:
        """Add one latency observation to a longrunavg (or `count` of
        them that sum to `seconds`: a meter that sums locally and folds
        in at dump time)."""
        c = self._counters[name]
        with self._lock:
            c.sum += seconds
            c.count += count

    @contextlib.contextmanager
    def time_avg(self, name: str):
        """Time a block into a longrunavg — ``with pc.time_avg("op_lat"):``
        instead of hand-rolled time.monotonic() pairs at every call site.
        The observation is recorded even when the block raises (a failed
        op still spent the time)."""
        t0 = time.monotonic()
        try:
            yield
        finally:
            self.tinc(name, time.monotonic() - t0)

    def ensure(self, name: str, kind: str = U64,
               desc: str = "") -> None:
        """Declare a counter after build time (dynamic families, e.g. the
        messenger's per-message-type counts).  Idempotent; thread-safe
        against dump()."""
        if name in self._counters:
            return
        with self._lock:
            if name not in self._counters:
                self._counters[name] = _Counter(name, kind, desc)

    def hinc(self, name: str, value: float) -> None:
        """Add an observation to a power-of-2-bucketed histogram."""
        c = self._counters[name]
        v = int(value)
        bucket = 0 if v <= 0 else min(31, v.bit_length())
        with self._lock:
            c.buckets[bucket] += 1
            c.count += 1
            c.sum += value

    def hmerge(self, name: str, buckets: List[int], total: float) -> None:
        """Add observations already bucketed as hinc buckets them (index =
        bit length of the value), `total` being their sum."""
        c = self._counters[name]
        with self._lock:
            for i, n in enumerate(buckets):
                c.buckets[i] += n
            c.count += sum(buckets)
            c.sum += total

    def get(self, name: str) -> Any:
        c = self._counters[name]
        if c.kind == U64:
            return c.value
        if c.kind == LONGRUNAVG:
            return (c.count, c.sum)
        return list(c.buckets)

    def avg(self, name: str) -> float:
        c = self._counters[name]
        return c.sum / c.count if c.count else 0.0

    def reset(self) -> None:
        """Zero every counter in the set (the `perf reset` admin command):
        tests and bench warmup/timed windows isolate measurement intervals
        instead of diffing snapshots by hand."""
        if self.presample is not None:
            try:
                self.presample()  # what a meter summed until now goes too
            except Exception:
                pass
        with self._lock:
            for c in self._counters.values():
                c.value = 0
                c.sum = 0.0
                c.count = 0
                if c.buckets is not None:
                    c.buckets = [0] * len(c.buckets)
        if self.resync is not None:
            try:
                self.resync()  # outside the lock: resync calls set()
            except Exception:
                pass

    # -- dump ----------------------------------------------------------------

    def dump(self) -> Dict[str, Any]:
        if self.presample is not None:
            try:
                self.presample()  # outside the lock: presample calls set()
            except Exception:
                pass
        out: Dict[str, Any] = {}
        # snapshot under the lock: ensure() may add counters concurrently
        with self._lock:
            counters = list(self._counters.values())
        for c in counters:
            if c.kind == U64:
                out[c.name] = c.value
            elif c.kind == LONGRUNAVG:
                out[c.name] = {"avgcount": c.count, "sum": c.sum}
            else:
                out[c.name] = {
                    "count": c.count,
                    "sum": c.sum,
                    "buckets": list(c.buckets),
                }
        return out

    def schema(self) -> Dict[str, Dict[str, str]]:
        # snapshot under the lock, same ensure() race as dump()
        with self._lock:
            counters = list(self._counters.values())
        return {c.name: {"type": c.kind, "description": c.desc}
                for c in counters}


class PerfCountersBuilder:
    """Declare-then-build, as the reference does (add_u64_counter/add_time_avg)."""

    def __init__(self, name: str):
        self._pc = PerfCounters(name)

    def add_u64(self, name: str, desc: str = "") -> "PerfCountersBuilder":
        self._pc._counters[name] = _Counter(name, U64, desc)
        return self

    def add_u64_counter(self, name: str, desc: str = "") -> "PerfCountersBuilder":
        return self.add_u64(name, desc)

    def add_time_avg(self, name: str, desc: str = "") -> "PerfCountersBuilder":
        self._pc._counters[name] = _Counter(name, LONGRUNAVG, desc)
        return self

    def add_histogram(self, name: str, desc: str = "") -> "PerfCountersBuilder":
        self._pc._counters[name] = _Counter(name, HISTOGRAM, desc)
        return self

    def create_perf_counters(self) -> PerfCounters:
        return self._pc


class PerfCountersCollection:
    """All counter sets of one daemon; the admin socket dumps this."""

    def __init__(self):
        self._sets: Dict[str, PerfCounters] = {}
        self._lock = threading.Lock()

    def add(self, pc: PerfCounters) -> PerfCounters:
        with self._lock:
            self._sets[pc.name] = pc
        return pc

    def remove(self, name: str) -> None:
        with self._lock:
            self._sets.pop(name, None)

    def get(self, name: str) -> Optional[PerfCounters]:
        return self._sets.get(name)

    def dump(self) -> Dict[str, Dict[str, Any]]:
        with self._lock:
            return {name: pc.dump() for name, pc in self._sets.items()}

    def reset(self, name: Optional[str] = None) -> List[str]:
        """Zero one named set, or every set when name is None/"all".
        Returns the names of the sets that were reset."""
        with self._lock:
            if name and name != "all":
                targets = [self._sets[name]] if name in self._sets else []
            else:
                targets = list(self._sets.values())
        for pc in targets:
            pc.reset()
        return [pc.name for pc in targets]

    def schema(self) -> Dict[str, Any]:
        with self._lock:
            return {name: pc.schema() for name, pc in self._sets.items()}
