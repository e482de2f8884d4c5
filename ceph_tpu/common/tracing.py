"""Distributed-trace spans (zipkin/blkin + jaeger wrapper role).

Role-equivalent of the reference's ZTracer/jaeger integration (reference
src/common/zipkin_trace.h, src/common/tracer.{h,cc}): ops carry a trace
with named spans; pipeline stages open child spans ("start ec write",
per-shard sub-writes, ECBackend.cc:2027,2113) and annotate events.

Cross-daemon stitching: ids are RANDOM 64-bit hex strings (unique across
processes and hosts, not a per-process counter), and a (trace_id,
parent span_id) pair rides the wire on the data-plane messages
(MOSDOp, MECSubWrite/Reply, MOSDBackoff, MOSDPGHitSet — types.py).  The
receiving daemon calls ``Tracer.join`` to open a child span of the
remote parent, so a client write stitches into ONE tree:
client_op -> osd_op -> ec write -> k+m ec_sub_write spans, each span
recorded in its OWN daemon's ring.

Spans land in a bounded per-daemon ring dumped via the admin socket
(``dump_traces``; ``dump_trace`` filters one trace_id) — the in-process
stand-in for shipping to a collector.  ``tools/trace_export.py`` gathers
the per-daemon rings and emits Jaeger-compatible JSON for a whole op.

Two instruments say where a loop thread's wall time goes (PERF.md §3):

- ``section(layer, name)`` times SYNCHRONOUS work where it happens.  A
  section never holds an ``await`` (tools/lint refuses one): per thread
  the open sections form a stack, and on exit a section's SELF time —
  its duration minus its child sections — goes to the time-avg
  ``self_<layer>`` of the loop's counter set (``thread_<layer>`` off a
  loop, so loop time and thread time never mix; a NATIVE thread's time
  comes in there through ``thread_source``).  While a profiler
  session records, the section is also a ``ceph.<layer>.<name>`` event
  in the profiler's host plane, beside the device's "XLA Ops" line and
  on its clock.  Op-level ``Span``s cross awaits and stay out of that
  trace: a span holding an await covers every idle gap and explains none.
- ``LoopMeter`` (``install_loop_meter``) accounts every handle the event
  loop runs: wall and thread-CPU seconds (``busy``, ``cpu``, ``steps``,
  ``step_us``), time in the selector (``select``), a probe's
  due-versus-ran delay (``lag``, ``lag_us``).  ``busy + select`` is the
  thread's whole wall time, exactly; which layer the busy time belongs to
  is sampled, one turn of the loop in sixteen (every turn while a
  profiler records), and scaled, so the ``self_*`` keys sum to ``busy``.  The part of a step no
  section covers is kept by the handle's kind (``kind_io_read``,
  ``kind_timer``, ``kind_task_<daemon>_<coroutine>``...) and added to
  the layer that kind belongs to; only what maps to no layer is
  ``self_unnamed``.  ``mark(layer)`` re-labels the rest of the current
  step (the messenger calls it where it hands a message to its daemon,
  and a send where the daemon calls into the messenger).
  ``charge(key)`` books the same busy seconds a second time, by CAUSE
  beside by layer: the messenger, where it knows a frame's type, says
  whose message a stretch of a step served, and the meter keeps it as
  ``msg_<Type>`` and ``for_<family>`` (``FAMILIES``; ``for_none`` is what
  nobody charged).  The ``for_*`` keys sum to ``busy`` as the ``self_*``
  keys do.  A charge is a counter, never an event of the host plane.

``jax`` is never imported here: the profiler annotation is used only when
the process already imported ``jax.profiler``.  Span times are integer
nanoseconds of ``time.time_ns()``, the clock the profiler stamps its
events with (an xplane's ``start_ns`` counts from the session's start on
it; while a session records, the loop meter emits a
``ceph.clock.<time_ns>.<perf_counter_ns>`` event about once a second to
anchor the two).
"""

from __future__ import annotations

import asyncio
import collections
import functools
import os
import sys
import threading
import time
import weakref
from typing import Any, Deque, Dict, List, Optional, Tuple

from ceph_tpu.common.perf_counters import PerfCounters, PerfCountersBuilder


def _new_id() -> str:
    """Random 64-bit hex id: unique across daemons/hosts (a per-process
    counter would collide the moment two daemons' spans stitch)."""
    return os.urandom(8).hex()


class Span:
    """``start_ns``/``end_ns`` and each event's ``time_ns`` are integer
    nanoseconds of ``time.time_ns()``: the profiler's clock (module
    docstring).  ``start``/``end`` and ``dump()`` give the same instants
    as float seconds, as they always have."""

    __slots__ = ("tracer", "trace_id", "span_id", "parent_id", "name",
                 "start_ns", "end_ns", "events", "tags")

    def __init__(self, tracer: "Tracer", name: str, trace_id: str,
                 parent_id: Optional[str]):
        self.tracer = tracer
        self.trace_id = trace_id
        self.span_id = _new_id()
        self.parent_id = parent_id
        self.name = name
        self.start_ns = time.time_ns()
        self.end_ns: Optional[int] = None
        self.events: List[Dict[str, Any]] = []
        self.tags: Dict[str, Any] = {}

    @property
    def start(self) -> float:
        return self.start_ns / 1e9

    @property
    def end(self) -> Optional[float]:
        return None if self.end_ns is None else self.end_ns / 1e9

    def event(self, name: str) -> None:
        self.events.append({"time_ns": time.time_ns(), "event": name})

    def tag(self, key: str, value: Any) -> "Span":
        """Attach a key/value annotation (zipkin binary-annotation role):
        the batching queue tags dispatch spans with lane, group size, and
        byte counts so the asok timeline is self-describing."""
        self.tags[key] = value
        return self

    def child(self, name: str) -> "Span":
        return self.tracer._span(name, self.trace_id, self.span_id)

    def context(self):
        """(trace_id, span_id) — what rides the wire so the receiving
        daemon can ``join`` as a child of this span."""
        return self.trace_id, self.span_id

    def finish(self) -> None:
        if self.end_ns is None:
            self.end_ns = time.time_ns()
            self.tracer._record(self)

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, *exc) -> None:
        self.finish()

    def dump(self) -> Dict[str, Any]:
        return {"trace_id": self.trace_id, "span_id": self.span_id,
                "parent_id": self.parent_id, "name": self.name,
                "start": self.start,
                "duration": ((self.end_ns or time.time_ns())
                             - self.start_ns) / 1e9,
                "events": [{"time": e["time_ns"] / 1e9, "event": e["event"]}
                           for e in self.events],
                "tags": dict(self.tags)}


class Tracer:
    def __init__(self, max_spans: int = 256, enabled: bool = True,
                 service: str = ""):
        self.enabled = enabled
        # the daemon name, stamped into every dumped span so a
        # cross-daemon trace export can label processes (jaeger's
        # processes map) without knowing which ring a span came from
        self.service = service
        self._ring: Deque[Span] = collections.deque(maxlen=max_spans)
        self._lock = threading.Lock()

    def new_trace(self, name: str) -> Span:
        return self._span(name, _new_id(), None)

    def join(self, name: str, trace_id: str,
             parent_id: Optional[str] = None) -> Span:
        """Open a span under a REMOTE parent: the receiving half of
        cross-daemon propagation (the wire carried (trace_id,
        parent span_id); this daemon's span becomes its child)."""
        return self._span(name, trace_id, parent_id or None)

    def _span(self, name: str, trace_id: str, parent_id: Optional[str]) -> Span:
        return Span(self, name, trace_id, parent_id)

    def _record(self, span: Span) -> None:
        if self.enabled:
            with self._lock:
                self._ring.append(span)

    def dump(self) -> List[Dict[str, Any]]:
        # snapshot FIRST: worker threads finish spans concurrently, and
        # iterating the live deque from the asok thread would raise
        # "deque mutated during iteration" mid-dump
        with self._lock:
            spans = list(self._ring)
        out = []
        for s in spans:
            d = s.dump()
            if self.service:
                d["service"] = self.service
            out.append(d)
        return out

    def spans_for(self, trace_id: str) -> List[Dict[str, Any]]:
        """Every recorded span of one trace (the `dump_trace <id>` asok
        answer; tools/trace_export.py stitches these across daemons)."""
        return [d for d in self.dump() if d["trace_id"] == trace_id]

    def register_asok(self, asok) -> None:
        asok.register("dump_traces", lambda a: self.dump(),
                      "recent trace spans")
        asok.register(
            "dump_trace",
            lambda a: {"trace_id": a.get("trace_id", ""),
                       "spans": self.spans_for(a.get("trace_id", ""))},
            "spans of one trace (trace_id=<hex>)")


# -- sections and the loop meter ---------------------------------------------

# the layers of PERF.md section 3 that run on a daemon's event loop; each is
# a `self_<layer>` time-avg of the `loop` set from the start, so a reader
# finds the key before the layer's first section ran
LOOP_LAYERS = ("client", "messenger", "osd", "ecplan", "store", "background",
               "unnamed")
# which layer the uncovered part of a loop step belongs to, by the first
# part of its task's label: an explicit `<daemon>[.<id>]/<role>`, or
# `<module>/<coroutine>` for a task nobody named (_task_label)
_LAYER_OF_TASK = {
    "client": "client", "librados": "client", "striper": "client",
    "benchmarks": "client",
    "messenger": "messenger",
    "osd": "osd", "scheduler": "osd", "service": "osd", "peering": "osd",
    "ecutil": "ecplan",
    "pagestore": "store", "memstore": "store", "bluestore": "store",
    "objectstore": "store",
    "mon": "background", "mgr": "background", "mds": "background",
    "tiering": "background", "logclient": "background", "vstart": "background",
    "admin_socket": "background", "log": "background",
}
# whose message a stretch of the loop's time served (PERF.md section 3): a
# client op's own, the cluster's liveness, the tier's hit sets, recovery and
# scrub, the control plane, standalone acks; `none` is in no message's hand.
# Each is a `for_<family>` time-avg of the `loop` set from the start
FAMILIES = ("op", "liveness", "tier", "recovery", "control", "ack", "none")
_LAG_PROBE_S = 0.02


def build_loop_perf(name: str = "loop") -> PerfCounters:
    b = PerfCountersBuilder(name)
    b.add_time_avg("busy", "wall seconds of the loop's thread outside the "
                           "selector's select: its handles and its own "
                           "bookkeeping between them")
    b.add_time_avg("cpu", "thread CPU seconds of the same stretches: the "
                          "rest of busy is the GIL and blocking calls")
    b.add_time_avg("select", "wall seconds inside the selector's select")
    b.add_time_avg("lag", "seconds a periodic probe ran after it was due")
    b.add_time_avg("sampled", "busy seconds (and turns of the loop) whose "
                              "handles and sections were timed; cpu, "
                              "steps, self_* and kind_* are those turns' "
                              "sums scaled by busy / sampled")
    b.add_u64_counter("steps", "handles the loop ran (scaled)")
    b.add_histogram("step_us", "wall µs per handle, sampled turns")
    b.add_histogram("lag_us", "probe delay µs")
    for layer in LOOP_LAYERS:
        b.add_time_avg("self_" + layer,
                       f"loop-thread self seconds of layer {layer}: its "
                       f"sections, and the uncovered part of the steps "
                       f"whose kind belongs to it")
    for family in FAMILIES:
        b.add_time_avg("for_" + family,
                       f"loop-thread seconds charged to messages of family "
                       f"{family} (tracing.charge): the same busy seconds as "
                       f"self_*, cut by cause; each type is a msg_<Type>")
    return b.create_perf_counters()


# ONE set per process, listed by every daemon's collection (Context) and the
# client's perf_dump, as `ec_tpu` is: the daemons of a vstart cluster share
# one loop, so its time is counted once
LOOP_PERF = build_loop_perf()


class _State:
    """One thread's open sections and, on a metered loop, the turn of the
    loop it is in.  Plain attributes: the thread-local lookup is paid
    once."""

    __slots__ = ("depth", "child", "covered", "meter", "skip", "null",
                 "cursor", "base", "acc", "marked", "loose",
                 "charged", "charge_from", "claimable")

    def __init__(self) -> None:
        self.depth = 0        # open sections
        self.child = 0.0      # seconds of the open section's ended children
        self.covered = 0.0    # seconds under top-level sections since cursor
        self.meter: Optional["LoopMeter"] = None  # in a SAMPLED turn of its
        self.skip = False     # in a turn the meter does not sample
        # what section() hands out in such a turn: a context manager that
        # does nothing, in C.  A re-entrant lock of this thread's own is
        # one (sections nest; no other thread ever sees it)
        self.null = threading.RLock()
        self.cursor = 0.0     # where the step's uncovered stretch began
        self.base = [0.0]     # the sum of the layer of the step's kind
        self.acc = [0.0]      # the sum that stretch goes to: base, or
        self.marked: Optional[str] = None  # the layer mark() named
        self.loose = 0.0      # uncovered seconds of the step already booked
        self.charged: Any = None  # whom the step's time is charged to now
        self.charge_from = 0.0    # since when
        self.claimable = False    # no charge was made in this step yet


_tls = threading.local()


def _state() -> _State:
    try:
        return _tls.state
    except AttributeError:
        st = _tls.state = _State()
        return st


_trace_me = None  # jax.profiler.TraceAnnotation, once this process has jax


def _annotation():
    """jax.profiler.TraceAnnotation while a profiler session records, else
    None.  Never imports jax."""
    global _trace_me
    if _trace_me is None:
        mod = sys.modules.get("jax.profiler")
        if mod is None:
            return None
        _trace_me = mod.TraceAnnotation
    return _trace_me if _trace_me.is_enabled() else None


def _tally(perf: PerfCounters, key: str, seconds: float,
           count: int = 1) -> None:
    perf.ensure(key, "longrunavg")
    perf.tinc(key, seconds, count)


def section(layer: str, name: str):
    """``with tracing.section("messenger", "crc"):`` — synchronous work of
    one layer (module docstring).  No ``await`` inside."""
    try:
        st = _tls.state
    except AttributeError:
        st = _state()
    if st.skip:
        return st.null  # a turn of the loop its meter does not sample
    return _Section(layer, name)


class _Section:
    __slots__ = ("layer", "name", "_t0", "_outer", "_ta")

    def __init__(self, layer: str, name: str) -> None:
        self.layer = layer
        self.name = name

    def __enter__(self) -> "_Section":
        st = _tls.state
        st.depth += 1
        self._outer, st.child = st.child, 0.0
        ta = _trace_me
        if ta is None or not ta.is_enabled():
            ta = _annotation()  # None, unless jax came in just now
        if ta is not None:
            ta = ta(f"ceph.{self.layer}.{self.name}")
            ta.__enter__()
        self._ta = ta
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        took = time.perf_counter() - self._t0
        if self._ta is not None:
            self._ta.__exit__(None, None, None)
        st = _tls.state
        own = took - st.child
        st.depth -= 1
        if st.depth <= 0:
            st.depth, st.child = 0, 0.0
            st.covered += took
        else:
            st.child = self._outer + took
        meter = st.meter
        if meter is not None:
            # in a sampled turn of a metered loop: summed without a lock,
            # scaled and folded in by flush
            slot = meter._sections.get(self.layer)
            if slot is None:
                slot = meter._sections[self.layer] = [0.0, 0]
            slot[0] += own
            slot[1] += 1
        elif asyncio._get_running_loop() is not None:
            _tally(LOOP_PERF, "self_" + self.layer, own)  # unmetered loop
        else:
            _tally(LOOP_PERF, "thread_" + self.layer, own)


def sectioned(layer: str, name: str):
    """Decorator form for a synchronous function that is one section."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with section(layer, name):
                return fn(*args, **kwargs)
        return inner
    return wrap


def mark(layer: Optional[str]) -> Optional[str]:
    """From here to the end of the current loop step, what no section
    covers belongs to `layer` (None: to the layer of the step's kind
    again); returns what it was, for the caller to put back.  It is how a
    coroutine of one layer calls into another's: the messenger marks its
    daemon's layer around the dispatcher, a send marks "messenger".  A
    mark ends with its step (a coroutine that resumes after an await is
    its task's kind again until the next mark: those stretches are
    short), so there is nothing to undo in the turns the meter does not
    sample, where this is a no-op, as it is off a metered loop."""
    try:
        st = _tls.state
    except AttributeError:
        return None
    meter = st.meter
    if meter is None:
        return None
    now = time.perf_counter()
    loose = (now - st.cursor) - st.covered
    st.acc[0] += loose
    st.loose += loose
    st.cursor, st.covered = now, 0.0
    was, st.marked = st.marked, layer
    st.acc = st.base if layer is None else meter._layer_acc(layer)
    return was


def charge(key: Optional[Tuple[str, str]], claim: bool = True):
    """From here to the end of the current loop step, or to the next
    charge, the step's time (sections and uncovered stretches alike) is
    booked to `key`, a (family, type) pair: `loop.for_<family>` and
    `loop.msg_<type>`; None is nobody's (`for_none`, what a step is until
    somebody charges it).  Returns what it was, for a caller that puts it
    back, as mark does.  The FIRST charge of a step claims the step from
    its start (a receive step's recv_into ran inside asyncio before
    anybody could read a type) unless `claim` is false (a send made from
    a continuation: what the step did before is not the message's).  Like
    a mark, a charge ends with its step.  Off a metered loop and in the
    turns the meter does not sample this returns None after one
    thread-local read: no clock, no allocation."""
    try:
        st = _tls.state
    except AttributeError:
        return None
    meter = st.meter
    if meter is None:
        return None
    if not (st.claimable and claim):
        now = time.perf_counter()
        meter._book(st.charged, now - st.charge_from)
        st.charge_from = now
    st.claimable = False
    was, st.charged = st.charged, key
    return was


def charge_many(weights: Dict[Tuple[str, str], float], claim: bool = True):
    """charge(), the stretch divided among several keys by weight: a burst
    of frames by the bytes each landed, a flush window by its bytes by
    type.  `weights` is kept, not copied."""
    return charge(weights or None, claim)


def metered() -> bool:
    """In a sampled turn of a metered loop: a charge made now is booked.
    For a caller whose KEY costs something to work out."""
    try:
        return _tls.state.meter is not None
    except AttributeError:
        return False


def last_lag() -> Optional[float]:
    """Seconds the running loop's newest lag probe ran after it was due
    (at most 20 ms old); None off a metered loop."""
    meter = getattr(asyncio._get_running_loop(), "_ceph_meter", None)
    return None if meter is None else meter.last_lag


def _task_label(task) -> str:
    """`<daemon>[.<id>]/<role>` if the task was given such a name, else
    `<module>/<coroutine>` of the code it runs (`osd/OSD._run_op`)."""
    name = task.get_name()
    if "/" in name:
        return name
    coro = task.get_coro()
    frame = getattr(coro, "cr_frame", None) or getattr(coro, "gi_frame", None)
    module = frame.f_globals.get("__name__", "") if frame is not None else ""
    if module.startswith("benchmarks."):
        module = "benchmarks"
    return (f"{module.rpartition('.')[2] or 'other'}/"
            f"{getattr(coro, '__qualname__', type(coro).__name__)}")


_handle_run = asyncio.Handle._run
_Handle, _Task = asyncio.Handle, asyncio.Task


def _metered_run(handle):
    """asyncio.Handle._run, metered in the sampled turns of a loop that has
    a meter.  A handle's wall time runs from the end of the one before it
    (or of the select), so the loop's own bookkeeping between handles is
    accounted, with one clock reading per handle."""
    meter = getattr(handle._loop, "_ceph_meter", None)
    if meter is None or not meter._sampling:
        return _handle_run(handle)
    cb = handle._callback
    owner = getattr(cb, "__self__", None)
    kind = getattr(owner, "_ceph_kind", None)  # a task keeps its kind
    if kind is None:
        kind = meter._kinds.get(getattr(cb, "__func__", None)
                                or getattr(cb, "__name__", None))
        if kind is None or handle.__class__ is not _Handle:
            kind = meter._kind_of(handle, cb, owner)
    ta = meter._recording and _trace_me
    if ta:
        ta = ta("ceph.loop." + kind.key[5:])
        ta.__enter__()
    st = meter._st
    st.base = st.acc = kind.acc
    st.marked = None
    st.covered = st.loose = 0.0
    st.charged, st.claimable = None, True
    t0 = st.cursor = st.charge_from = meter._t_mark
    _handle_run(handle)  # logs what the callback raises; raises nothing
    meter._t_mark = t1 = time.perf_counter()
    meter._book(st.charged, t1 - st.charge_from)
    if ta:
        ta.__exit__(None, None, None)
    loose = (t1 - st.cursor) - st.covered
    st.acc[0] += loose
    kind.seconds += st.loose + loose
    kind.steps += 1
    meter._step_us[min(31, int((t1 - t0) * 1e6).bit_length())] += 1


class _Kind:
    """What runs in a handle: its counter key, its layer's sum, and the
    uncovered seconds and steps of such handles since the last flush."""

    __slots__ = ("key", "acc", "seconds", "steps")

    def __init__(self, key: str, acc: List[float]) -> None:
        self.key, self.acc = key, acc
        self.seconds, self.steps = 0.0, 0


class LoopMeter:
    """Where one event loop's thread spends its wall time (module
    docstring).

    `busy` and `select` are exact: every turn of the loop passes through
    the timed select.  Which layer and which kind of handle the busy time
    belongs to is SAMPLED: one turn in `sample_every` (every turn while a
    profiler session records) has each of its handles and sections timed,
    the other turns run as if there were no meter; flush scales what the
    sampled turns summed by busy / sampled busy seconds.  On a host where
    the cluster's buffers keep the caches cold, timing every handle and
    section cost 11 % of a saturated loop (PERF.md, PR 26); a turn runs
    some hundred handles of every kind, so one in sixteen is a fair
    sample.  Sums
    are kept as plain attributes and folded into `perf` when somebody dumps
    it: a sampled step costs one clock reading and a few additions."""

    SAMPLE_EVERY = 16

    def __init__(self, loop, perf: PerfCounters) -> None:
        self.loop = loop
        self.perf = perf
        self.sample_every = self.SAMPLE_EVERY
        self._turn = 0
        self._sampling = False  # this turn of the loop is a sampled one
        self._recording = False  # a profiler session records
        self._st: Optional[_State] = None  # the loop thread's
        self._select = self._lag = 0.0
        self._selects = self._lags = 0
        self._in_select: Optional[float] = None  # since when, if it is
        self._t_flush = 0.0  # up to when busy and select are folded in
        # of the sampled turns: their busy seconds, their count, the
        # thread's CPU seconds in them, and the marks both are read from
        self._sampled = self._cpu = 0.0
        self._turns = 0
        self._t_turn = self._t_mark = self._c_mark = 0.0
        self._step_us = [0] * 32
        self._lag_us = [0] * 32
        self._layers: Dict[str, List[float]] = {}  # layer -> [seconds]
        self._sections: Dict[str, list] = {}  # layer -> [self seconds, n]
        self._kinds: Dict[Any, _Kind] = {}  # callback or task label -> kind
        # (family, type) or None -> [seconds charged, stretches]
        self._charges: Dict[Any, list] = {}
        # the loop's own time between its last handle and the select
        self._loop_itself = self._kind("kind_loop_itself", "unnamed")
        self._probe = None
        self._due = 0.0
        self.last_lag = 0.0  # the newest probe's delay, seconds
        self._selector = None

    # -- install / remove ----------------------------------------------------

    def install(self) -> "LoopMeter":
        loop = self.loop
        asyncio.Handle._run = _metered_run  # once per process; idempotent
        loop._ceph_meter = self
        self._t_flush = time.perf_counter()
        selector = getattr(loop, "_selector", None)
        if selector is not None:
            self._selector = selector
            selector.select = self._timed_select(selector.select)
        close = loop.close

        def closing() -> None:
            self._leave_thread()  # sections of this thread: off a loop again
            close()
        loop.close = closing
        # the probe arms itself on the loop's own thread (a loop may be
        # metered from another, before it runs)
        loop.call_soon_threadsafe(self._arm_probe)
        _METERS.add(self)
        return self

    def remove(self) -> None:
        """Stop metering (from the loop's own thread)."""
        self._end_turn(time.perf_counter())
        self._leave_thread()
        self.loop.__dict__.pop("close", None)
        self.flush()
        _METERS.discard(self)
        if self._probe is not None:
            self._probe.cancel()
        if self._selector is not None:
            self._selector.__dict__.pop("select", None)
        self.loop.__dict__.pop("_ceph_meter", None)

    def _leave_thread(self) -> None:
        self._sampling = False
        if self._st is not None:
            self._st.meter, self._st.skip = None, False

    # -- a turn of the loop --------------------------------------------------

    def _end_turn(self, now: float) -> None:
        if self._sampling:
            self._cpu += time.thread_time() - self._c_mark
            self._sampled += now - self._t_turn
            self._turns += 1
            # since its last handle (timers, its ready queue): the loop's
            # own time, of no layer
            itself = self._loop_itself
            itself.seconds += now - self._t_mark
            itself.acc[0] += now - self._t_mark
            self._book(None, now - self._t_mark)

    def _timed_select(self, select):
        def timed(timeout=None):
            t0 = self._in_select = time.perf_counter()
            self._end_turn(t0)
            ta = _annotation()
            if ta is None:
                self._recording = False
                events = select(timeout)
            else:
                if not self._recording:
                    self._recording = True
                    self._clock_anchor(ta)
                with ta("ceph.loop.select"):
                    events = select(timeout)
            t1 = time.perf_counter()
            self._in_select = None
            self._select += t1 - t0
            self._selects += 1
            # the turn that starts now: sampled, or left alone
            self._turn += 1
            sampling = self._recording \
                or self._turn % self.sample_every == 0
            st = self._st
            if st is None:
                st = self._st = _state()
            self._sampling = sampling
            st.skip = not sampling
            if sampling:
                st.meter = self
                self._t_turn = self._t_mark = t1
                self._c_mark = time.thread_time()
            else:
                st.meter = None
            return events
        return timed

    def _arm_probe(self) -> None:
        loop = asyncio.get_running_loop()
        self._due = loop.time() + _LAG_PROBE_S
        self._probe = loop.call_later(_LAG_PROBE_S, self._lag_probe)

    def _clock_anchor(self, ta) -> None:
        """One event whose NAME carries this instant on time.time_ns() and
        time.perf_counter_ns(): read back beside its start_ns it ties the
        trace's clock to the program's (Span times, the harness's)."""
        with ta(f"ceph.clock.{time.time_ns()}.{time.perf_counter_ns()}"):
            pass

    def _lag_probe(self) -> None:
        late = self.last_lag = max(0.0, self.loop.time() - self._due)
        self._lag += late
        self._lags += 1
        self._lag_us[min(31, int(late * 1e6).bit_length())] += 1
        self._arm_probe()
        if self._recording and self._lags % 50 == 0 and _trace_me:
            self._clock_anchor(_trace_me)  # about one a second

    # -- one handle ----------------------------------------------------------

    def _layer_acc(self, layer: str) -> List[float]:
        acc = self._layers.get(layer)
        if acc is None:
            acc = self._layers[layer] = [0.0]
        return acc

    def _book(self, whom, seconds: float) -> None:
        """`seconds` of a sampled step to whom they were charged: a key,
        None, or charge_many's weights."""
        if type(whom) is dict:
            total = sum(whom.values())
            if total > 0:
                for key, weight in whom.items():
                    self._book(key, seconds * weight / total)
                return
            whom = None
        slot = self._charges.get(whom)
        if slot is None:
            slot = self._charges[whom] = [0.0, 0]
        slot[0] += seconds
        slot[1] += 1

    def _kind(self, key: str, layer: str) -> _Kind:
        kind = self._kinds.get(key)
        if kind is None:
            kind = self._kinds[key] = _Kind(key, self._layer_acc(layer))
        return kind

    def _kind_of(self, handle, cb, owner) -> _Kind:
        """Whose code runs in this handle (the slow path: a task keeps
        its kind, and other callbacks are looked up by function)."""
        if isinstance(owner, _Task):
            head, _, role = _task_label(owner).partition("/")
            daemon = head.partition(".")[0]
            layer = _LAYER_OF_TASK.get(daemon, "unnamed")
            label = "".join(c if c.isalnum() else "_"
                            for c in f"{daemon}_{role}")
            kind = owner._ceph_kind = self._kind("kind_task_" + label, layer)
            return kind
        if isinstance(handle, asyncio.TimerHandle):
            # a sleep's or a timeout's wake-up: it sets a future, the
            # woken task's step is a handle of its own
            return self._kind("kind_timer", "unnamed")
        key = getattr(cb, "__func__", None) or getattr(cb, "__name__", cb)
        kind = self._kinds.get(key)
        if kind is None:
            name = getattr(cb, "__name__", type(cb).__name__)
            module = getattr(cb, "__module__", None) or ""
            if isinstance(owner, asyncio.BaseTransport) \
                    or "selector_events" in module:
                # asyncio's own socket work; every socket of these loops
                # is a messenger's (the asok server aside, when set up)
                kind = self._kind(
                    "kind_io_write" if "write" in name or "send" in name
                    else "kind_io_read", "messenger")
            else:
                kind = self._kind(
                    "kind_call_" + name,
                    _LAYER_OF_TASK.get(module.rpartition(".")[2], "unnamed"))
            if len(self._kinds) < 1024:
                self._kinds[key] = kind
        return kind

    # -- into the counter set ------------------------------------------------

    def flush(self) -> None:
        """Fold what was summed since the last flush into `perf` (the
        set's presample hook: every dump sees the loop up to now).  What
        the sampled turns summed is scaled to the whole busy time; until a
        turn was sampled, busy waits with it, so that the `self_*` keys
        and the `for_*` keys each always sum to `busy`."""
        perf = self.perf
        lag, self._lag = self._lag, 0.0
        lags, self._lags = self._lags, 0
        perf.tinc("lag", lag, lags)
        buckets, self._lag_us = self._lag_us, [0] * 32
        perf.hmerge("lag_us", buckets, lag * 1e6)
        now = self._in_select or time.perf_counter()
        st = self._st
        if self._sampling and st is not None and st.meter is self \
                and getattr(_tls, "state", None) is st:
            # asked from inside a sampled turn (a step of this loop dumps
            # the set): what the turn and its current step have used so far
            # belongs to this interval, the rest to the next
            loose = (now - st.cursor) - st.covered
            st.acc[0] += loose
            st.loose += loose
            st.cursor, st.covered = now, 0.0
            self._book(st.charged, now - st.charge_from)
            st.charge_from = now
            c_now = time.thread_time()
            self._cpu += c_now - self._c_mark
            self._sampled += now - self._t_turn
            self._t_turn, self._c_mark = now, c_now
        sampled = self._sampled
        if sampled <= 0.0:
            return
        select, self._select = self._select, 0.0
        selects, self._selects = self._selects, 0
        perf.tinc("select", select, selects)
        # busy is the thread's wall time outside the select
        busy = max(0.0, (now - self._t_flush) - select)
        self._t_flush = now
        scale = busy / sampled
        turns, self._turns, self._sampled = self._turns, 0, 0.0
        perf.tinc("sampled", sampled, turns)
        buckets, self._step_us = self._step_us, [0] * 32
        steps = round(sum(buckets) * scale)
        cpu, self._cpu = self._cpu, 0.0
        perf.tinc("busy", busy, steps)
        perf.tinc("cpu", cpu * scale, steps)
        perf.inc("steps", steps)
        perf.hmerge("step_us", buckets, sampled * 1e6)
        for layer, acc in self._layers.items():
            seconds, acc[0] = acc[0], 0.0
            _tally(perf, "self_" + layer, seconds * scale, 0)
        for layer, slot in list(self._sections.items()):
            (seconds, count), slot[:] = slot, (0.0, 0)
            _tally(perf, "self_" + layer, seconds * scale,
                   round(count * scale))
        for kind in set(self._kinds.values()):
            if kind.steps or kind.seconds:
                _tally(perf, kind.key, kind.seconds * scale,
                       round(kind.steps * scale))
                kind.seconds, kind.steps = 0.0, 0
        for key, slot in self._charges.items():
            (seconds, count), slot[:] = slot, (0.0, 0)
            if not (seconds or count):
                continue
            family, name = key or ("none", "")
            _tally(perf, "for_" + family, seconds * scale,
                   round(count * scale))
            if name:
                _tally(perf, "msg_" + name, seconds * scale,
                       round(count * scale))


_METERS: "weakref.WeakSet[LoopMeter]" = weakref.WeakSet()


# (layer, read, [seconds, calls] folded in so far): what a NATIVE thread
# did for a layer, kept by its library (the messenger's sender thread:
# seconds inside writev)
_THREAD_SOURCES: List[list] = []


def thread_source(layer: str, read) -> None:
    """`read()` gives (seconds, calls) a thread that runs no Python has
    worked for `layer` so far; every dump of the `loop` set folds what is
    new into `thread_<layer>`, beside the sections Python threads time."""
    _THREAD_SOURCES.append([layer, read, [0.0, 0]])


def _presample() -> None:
    for meter in list(_METERS):
        meter.flush()
    for layer, read, seen in _THREAD_SOURCES:
        seconds, calls = read()
        if seconds < seen[0] or calls < seen[1]:
            seen[:] = 0.0, 0  # the library began anew (a fork's child)
        if calls > seen[1]:
            _tally(LOOP_PERF, "thread_" + layer, seconds - seen[0],
                   calls - seen[1])
            seen[:] = seconds, calls


LOOP_PERF.presample = _presample


def install_loop_meter(loop=None) -> LoopMeter:
    """Meter `loop` (default: the running one) into the process's `loop`
    set.  Idempotent."""
    loop = loop or asyncio.get_running_loop()
    meter = getattr(loop, "_ceph_meter", None)
    if meter is None:
        meter = LoopMeter(loop, LOOP_PERF).install()
    return meter
