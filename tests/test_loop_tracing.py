"""The loop instrument (common/tracing.py): sections of synchronous work
with self time, the event-loop meter and its closure, task-sticky marks,
the lint rule that keeps awaits out of sections, Span on the profiler's
clock, and the gather counters that say when a put was acknowledged with
fewer sub-write acks than shards."""

import asyncio
import os
import re
import threading
import time

import pytest

from ceph_tpu.common import tracing
from ceph_tpu.common.context import Context
from ceph_tpu.common.perf_counters import PerfCountersBuilder
from ceph_tpu.common.tracing import LOOP_PERF, Tracer
from ceph_tpu.tools import trace_export
from ceph_tpu.tools.lint import async_safety


def spin(seconds: float) -> None:
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


def moved(before: dict, after: dict, key: str, part: str = "sum") -> float:
    def val(d):
        v = d.get(key, 0)
        return v.get(part, v.get("avgcount", 0)) if isinstance(v, dict) else v
    return val(after) - val(before)


def metered(coro_fn, timeout: float = 60.0, sample_every: int = 1):
    """Run `coro_fn()` on a fresh metered loop; its result, the `loop` set
    before and after, and the run's wall seconds as (least, most): clocks
    read inside and outside the two dumps, so that `busy + select` of the
    delta lies between them however long this process was kept off its
    core in between.  Every turn of the loop is sampled, unless the test
    is about the sampling."""
    async def go():
        t_out = time.perf_counter()  # before the meter: busy waits for a
        meter = tracing.install_loop_meter()  # sampled turn (flush)
        meter.sample_every = sample_every
        await asyncio.sleep(0)  # the meter sees whole turns from here
        before = LOOP_PERF.dump()
        t0 = time.perf_counter()
        out = await asyncio.wait_for(coro_fn(), timeout)
        await asyncio.sleep(0)  # a dump sees the turns that have ended
        least = time.perf_counter() - t0
        after = LOOP_PERF.dump()
        most = time.perf_counter() - t_out
        meter.remove()
        return out, before, after, (least, most)
    return asyncio.run(go())


def closes_on(total: float, wall) -> bool:
    least, most = wall
    return least * 0.999 <= total <= most * 1.001


def holds(value: float, own: float, wall, every: float) -> bool:
    """A sum that contains `own` seconds of spinning holds at least those,
    and at most those plus what the whole run (`wall`, by its outer clock)
    took beyond `every` second it spun: the loop's sums are parts of its
    busy time and each holds its own spins, so a busy host can stretch
    one by that much and no further."""
    return own <= value <= own + (wall[1] - every)


# -- sections ------------------------------------------------------------------


class TestSections:
    def test_self_time_is_duration_minus_children(self):
        async def work():
            with tracing.section("osd", "outer"):
                spin(0.02)
                with tracing.section("ecplan", "inner"):
                    spin(0.03)
                    with tracing.section("store", "innermost"):
                        spin(0.01)
                spin(0.01)
        _, b, a, wall = metered(work)
        assert holds(moved(b, a, "self_osd"), 0.03, wall, 0.07)
        assert holds(moved(b, a, "self_ecplan"), 0.03, wall, 0.07)
        assert holds(moved(b, a, "self_store"), 0.01, wall, 0.07)
        assert moved(b, a, "self_osd", "avgcount") == 1

    def test_siblings_both_leave_their_parent(self):
        async def work():
            with tracing.section("osd", "outer"):
                for _ in range(2):
                    with tracing.section("store", "commit"):
                        spin(0.01)
        _, b, a, wall = metered(work)
        assert holds(moved(b, a, "self_store"), 0.02, wall, 0.02)
        assert holds(moved(b, a, "self_osd"), 0.0, wall, 0.02)
        assert moved(b, a, "self_osd") < moved(b, a, "self_store")

    def test_sectioned_decorator_is_one_section(self):
        @tracing.sectioned("ecplan", "plan")
        def plan(n):
            spin(0.01)
            return n + 1

        async def work():
            return plan(1)
        out, b, a, wall = metered(work)
        assert out == 2 and plan.__name__ == "plan"
        assert holds(moved(b, a, "self_ecplan"), 0.01, wall, 0.01)
        assert moved(b, a, "self_ecplan", "avgcount") == 1

    def test_a_section_on_another_thread_lands_in_thread_keys(self):
        before = LOOP_PERF.dump()

        def worker():
            with tracing.section("devbound", "fetch"):
                spin(0.02)
        t = threading.Thread(target=worker)
        t0 = time.perf_counter()
        t.start()
        t.join()
        around = time.perf_counter() - t0
        after = LOOP_PERF.dump()
        assert 0.02 <= moved(before, after, "thread_devbound") <= around
        assert "self_devbound" not in after

    def test_loop_and_thread_time_never_mix(self):
        """Each section is held between the spin inside it and a clock
        read around it, on its own thread: a busy host stretches both
        alike, and the two never add up in one key."""
        around = {}

        async def work():
            def off_loop():
                t0 = time.perf_counter()
                with tracing.section("store", "x"):
                    spin(0.02)
                around["thread"] = time.perf_counter() - t0
            await asyncio.get_running_loop().run_in_executor(None, off_loop)
            t0 = time.perf_counter()
            with tracing.section("store", "x"):
                spin(0.01)
            around["loop"] = time.perf_counter() - t0
        _, b, a, _ = metered(work)
        assert 0.02 <= moved(b, a, "thread_store") <= around["thread"]
        assert 0.01 <= moved(b, a, "self_store") <= around["loop"]
        assert moved(b, a, "thread_store", "avgcount") == 1
        assert moved(b, a, "self_store", "avgcount") == 1

    def test_a_section_raising_still_counts_and_unwinds(self):
        async def work():
            try:
                with tracing.section("osd", "boom"):
                    spin(0.01)
                    raise ValueError("x")
            except ValueError:
                pass
            with tracing.section("store", "after"):
                spin(0.01)
        _, b, a, wall = metered(work)
        assert holds(moved(b, a, "self_osd"), 0.01, wall, 0.02)
        assert holds(moved(b, a, "self_store"), 0.01, wall, 0.02)

    def test_importing_tracing_imports_no_jax(self):
        import subprocess
        import sys
        code = ("import sys\n"
                "from ceph_tpu.common import tracing\n"
                "from ceph_tpu.common.context import Context\n"
                "Context('mon.a')\n"
                "with tracing.section('osd', 'x'):\n    pass\n"
                "print('jax' in sys.modules)\n")
        out = subprocess.run([sys.executable, "-c", code], text=True,
                             capture_output=True, timeout=60,
                             cwd=os.path.dirname(os.path.dirname(
                                 os.path.abspath(__file__))))
        assert out.stdout.strip() == "False", out.stderr[-500:]


# -- the loop meter ------------------------------------------------------------


class TestLoopMeter:
    def test_busy_plus_select_closes_on_wall(self):
        spun = [0.0]  # thread CPU seconds the spinning itself took

        async def toy():
            async def worker(i):
                for _ in range(40):
                    c0 = time.thread_time()
                    spin(0.001)
                    spun[0] += time.thread_time() - c0
                    await asyncio.sleep(0.002)
            await asyncio.gather(*(
                asyncio.get_running_loop().create_task(
                    worker(i), name=f"osd.{i}/toy") for i in range(3)))
        _, b, a, wall = metered(toy)
        busy, select = moved(b, a, "busy"), moved(b, a, "select")
        assert closes_on(busy + select, wall)
        assert busy >= 0.12  # 120 steps of 1 ms on the wall clock, at least
        assert moved(b, a, "steps") >= 120
        assert moved(b, a, "step_us", "count") == moved(b, a, "steps")
        # CPU time of the loop thread: what the spinning used of it at
        # least (how much of a wall millisecond that is, the host decides),
        # and never more than the wall time it is a part of
        cpu = moved(b, a, "cpu")
        assert spun[0] * 0.999 <= cpu <= busy * 1.001

    def test_one_turn_in_sixteen_is_sampled_and_scaled_to_busy(self):
        """The default (one in sixteen): most turns run as if there were no
        meter, and what
        the sampled ones summed is scaled, so that the layers still sum to
        the (exact) busy time and keep their proportions."""
        # the worker's own clock over the turns the meter sampled (it can
        # ask: tracing.metered): what a busy host adds to a sampled turn,
        # it adds to both
        own = {"store": 0.0, "turn": 0.0, "turns": 0}

        async def toy():
            async def worker():
                for _ in range(800):
                    t0 = time.perf_counter()
                    with tracing.section("store", "x"):
                        spin(0.0001)
                    t1 = time.perf_counter()
                    spin(0.0001)
                    if tracing.metered():
                        own["store"] += t1 - t0
                        own["turn"] += time.perf_counter() - t0
                        own["turns"] += 1
                    await asyncio.sleep(0)  # a turn of the loop each
            await asyncio.get_running_loop().create_task(
                worker(), name="osd.0/toy")
        _, b, a, wall = metered(toy, sample_every=tracing.LoopMeter.SAMPLE_EVERY)
        busy, sampled = moved(b, a, "busy"), moved(b, a, "sampled")
        assert closes_on(busy + moved(b, a, "select"), wall)
        # one turn in sixteen, by count; their seconds are a part of busy
        assert own["turns"] == pytest.approx(800 / 16, abs=2)
        assert moved(b, a, "sampled", "avgcount") == pytest.approx(
            own["turns"], abs=2)
        assert own["turn"] <= sampled <= busy
        total = sum(moved(b, a, k) for k in a if k.startswith("self_"))
        assert total == pytest.approx(busy, rel=1e-6)
        # the layers keep the proportions the sampled turns had, scaled
        # to busy: each at least its spins, the section at most the
        # worker's clock around it, the rest of a turn the task's kind
        scale = busy / sampled
        spins = own["turns"] * 0.0001
        assert spins * scale <= moved(b, a, "self_store") \
            <= own["store"] * scale
        assert spins * scale <= moved(b, a, "self_osd") \
            <= (sampled - spins) * scale
        # a step per turn and the loop's few: the sampled ones, scaled
        assert moved(b, a, "step_us", "count") >= own["turns"]
        assert moved(b, a, "steps") == pytest.approx(
            moved(b, a, "step_us", "count") * scale, abs=1)

    def test_a_dump_in_the_middle_of_a_sampled_turn_loses_nothing(self):
        """The harness snapshots the counters from inside a step.  What
        the turn used before the dump belongs to the dump's interval, the
        rest to the next: or the scaled layers would drift off busy."""
        async def toy():
            async def worker():
                for i in range(60):
                    spin(0.001)
                    if i % 5 == 0:
                        LOOP_PERF.dump()
                    with tracing.section("store", "x"):
                        spin(0.001)
                    await asyncio.sleep(0)
            await asyncio.get_running_loop().create_task(
                worker(), name="osd.0/toy")
        _, b, a, _ = metered(toy, sample_every=3)
        busy = moved(b, a, "busy")
        total = sum(moved(b, a, k) for k in a if k.startswith("self_"))
        assert total == pytest.approx(busy, rel=1e-6)
        # half the spinning is the section's, half the task's kind's; a
        # busy host moves the shares, it cannot empty one
        assert 0.15 < moved(b, a, "self_store") / busy < 0.85
        assert 0.15 < moved(b, a, "self_osd") / busy < 0.85

    def test_self_times_sum_to_busy(self):
        async def toy():
            async def worker():
                for _ in range(20):
                    with tracing.section("store", "x"):
                        spin(0.001)
                    spin(0.001)
                    await asyncio.sleep(0)
            await asyncio.get_running_loop().create_task(
                worker(), name="osd.0/toy")
        _, b, a, wall = metered(toy)
        total = sum(moved(b, a, k) for k in a if k.startswith("self_"))
        assert total == pytest.approx(moved(b, a, "busy"), rel=1e-6)
        assert holds(moved(b, a, "self_store"), 0.02, wall, 0.04)
        assert holds(moved(b, a, "self_osd"), 0.02, wall, 0.04)

    def test_lag_grows_when_a_callback_blocks(self):
        async def quiet():
            await asyncio.sleep(0.3)

        async def blocked():
            for _ in range(3):
                time.sleep(0.1)  # noqa: the point of the test
                await asyncio.sleep(0.01)
        _, b, a, _ = metered(quiet)
        calm = moved(b, a, "lag") / max(1, moved(b, a, "lag", "avgcount"))
        _, b, a, _ = metered(blocked)
        stalled = moved(b, a, "lag") / max(1, moved(b, a, "lag", "avgcount"))
        assert moved(b, a, "lag_us", "count") >= 3
        assert stalled > 0.03 and stalled > 2 * calm  # calm: ~0 unloaded

    @pytest.mark.parametrize("name,kind,layer", [
        ("osd.3/reader", "kind_task_osd_reader", "self_osd"),
        ("client/objecter", "kind_task_client_objecter", "self_client"),
        ("mon.0/tick", "kind_task_mon_tick", "self_background"),
        ("Task-77", "kind_task_test_loop_tracing_TestLoopMeter_test_uncovered"
                    "_step_time_goes_to_the_kinds_layer__locals__toy__locals_"
                    "_body", "self_unnamed"),
    ])
    def test_uncovered_step_time_goes_to_the_kinds_layer(self, name, kind,
                                                         layer):
        async def toy():
            async def body():
                spin(0.02)
            await asyncio.get_running_loop().create_task(body(), name=name)
        _, b, a, wall = metered(toy)
        assert holds(moved(b, a, kind), 0.02, wall, 0.02)
        assert moved(b, a, layer) >= moved(b, a, kind)

    def test_unnamed_tasks_are_kinds_of_the_code_they_run(self):
        async def toy():
            async def helper():
                spin(0.01)
            await asyncio.get_running_loop().create_task(helper())
        _, b, a, _ = metered(toy)
        kinds = [k for k in a if k.startswith("kind_task_test_loop_tracing")
                 and "helper" in k]
        assert kinds and moved(b, a, kinds[0]) >= 0.008

    def test_timers_and_transport_io_are_kinds(self):
        async def toy():
            async def echo(reader, writer):
                writer.write(await reader.readexactly(4))
                await writer.drain()
                writer.close()
            server = await asyncio.start_server(echo, "127.0.0.1", 0)
            port = server.sockets[0].getsockname()[1]
            r, w = await asyncio.open_connection("127.0.0.1", port)
            w.write(b"ping")
            assert await r.readexactly(4) == b"ping"
            w.close()
            server.close()
            await asyncio.sleep(0.01)
        _, b, a, _ = metered(toy)
        assert moved(b, a, "kind_io_read", "avgcount") >= 2
        assert moved(b, a, "kind_timer", "avgcount") >= 1
        # asyncio's own socket work is named: it is the messenger's
        assert moved(b, a, "self_messenger") > 0

    def test_mark_relabels_the_rest_of_a_step_and_restores(self):
        async def toy():
            async def send():
                was = tracing.mark("messenger")
                try:
                    spin(0.01)
                    await asyncio.sleep(0)
                    spin(0.01)  # a later step: the task's kind (osd) again
                finally:
                    assert tracing.mark(was) is None  # nothing to undo here

            async def handler():
                spin(0.01)
                was = tracing.mark("store")
                spin(0.01)
                assert tracing.mark(was) == "store"
                await send()
                spin(0.01)
            await asyncio.get_running_loop().create_task(
                handler(), name="osd.1/op")
        _, b, a, wall = metered(toy)
        assert holds(moved(b, a, "self_messenger"), 0.01, wall, 0.05)
        assert holds(moved(b, a, "self_store"), 0.01, wall, 0.05)
        assert holds(moved(b, a, "self_osd"), 0.03, wall, 0.05)

    def test_mark_off_a_metered_loop_is_a_noop(self):
        assert tracing.mark("osd") is None

    def test_the_loop_set_is_in_every_daemons_collection_once(self):
        a, b = Context("osd.0"), Context("mon.a")
        assert a.perf.get("loop") is LOOP_PERF is b.perf.get("loop")
        assert set(LOOP_PERF.dump()) >= {
            "busy", "cpu", "select", "lag", "steps", "step_us", "lag_us",
            *("self_" + layer for layer in tracing.LOOP_LAYERS)}

    def test_reset_keeps_nothing_from_before(self):
        async def toy():
            spin(0.02)
            await asyncio.sleep(0)
            LOOP_PERF.reset()
            await asyncio.sleep(0)
            return LOOP_PERF.dump()
        got, _, _, _ = metered(toy)
        assert got["busy"]["sum"] < 0.01


# -- charges: the same busy seconds, by cause ------------------------------------

A, B, ACK = ("op", "MTestA"), ("liveness", "MTestB"), ("ack", "ack")


def families(before: dict, after: dict) -> dict:
    return {f: moved(before, after, "for_" + f) for f in tracing.FAMILIES}


class TestCharges:
    @pytest.mark.parametrize("sample_every", [1, 16])
    def test_for_keys_close_on_busy_as_the_layers_do(self, sample_every):
        """Every sampled second is booked once by layer and once by cause,
        scaled alike: both cuts sum to the (exact) busy time, with
        charges, sections, marks, a dump from inside a step and steps
        nobody charges all in the run."""
        async def toy():
            async def worker(i):
                for n in range(160):
                    spin(0.0001)
                    if n % 3 == 0:
                        was = tracing.charge(A if i else B)
                        with tracing.section("messenger", "x"):
                            spin(0.0001)
                        tracing.charge_many({A: 1, B: 2, ACK: 1},
                                            claim=False)
                        spin(0.0001)
                        tracing.charge(was, claim=False)
                    if n % 40 == 7:
                        LOOP_PERF.dump()
                    spin(0.0001)
                    await asyncio.sleep(0)
            await asyncio.gather(*(
                asyncio.get_running_loop().create_task(
                    worker(i), name=f"osd.{i}/toy") for i in range(2)))
        _, b, a, _ = metered(toy, sample_every=sample_every)
        busy = moved(b, a, "busy")
        by_cause = families(b, a)
        assert sum(by_cause.values()) == pytest.approx(busy, rel=1e-6)
        assert sum(moved(b, a, k) for k in a if k.startswith("self_")) \
            == pytest.approx(busy, rel=1e-6)
        assert all(by_cause[f] > 0 for f in ("op", "liveness", "ack", "none"))
        assert by_cause["tier"] == by_cause["recovery"] == 0
        # a type is in one family, and a family is the sum of its types
        assert moved(b, a, "msg_MTestA") == pytest.approx(by_cause["op"])
        assert moved(b, a, "msg_MTestB") == pytest.approx(
            by_cause["liveness"])
        assert moved(b, a, "msg_ack") == pytest.approx(by_cause["ack"])

    def test_every_family_has_its_key_before_anything_is_charged(self):
        dump = tracing.build_loop_perf("loop.fresh").dump()
        for family in tracing.FAMILIES:
            assert dump["for_" + family] == {"avgcount": 0, "sum": 0.0}, \
                family

    def test_the_first_charge_claims_its_step_a_later_one_does_not(self):
        own = {}

        async def toy():
            async def receive():
                await asyncio.sleep(0)  # a step of its own from here
                t0 = time.perf_counter()
                spin(0.004)  # recv_into, before anybody can read a type
                assert tracing.charge(A) is None  # the step was nobody's
                spin(0.004)
                t1 = time.perf_counter()
                assert tracing.charge(B) == A  # what it was, as mark does
                spin(0.004)
                await asyncio.sleep(0)
                own["a"], own["b"] = t1 - t0, time.perf_counter() - t1
                spin(0.004)  # a charge ends with its step: nobody's again
                assert tracing.charge(None) is None

            async def continuation():
                await asyncio.sleep(0)
                spin(0.004)  # an op's own work after an await
                t0 = time.perf_counter()
                assert tracing.charge(A, claim=False) is None  # then a send
                spin(0.002)
                tracing.charge(None)
                own["send"] = time.perf_counter() - t0
            loop = asyncio.get_running_loop()
            await loop.create_task(receive(), name="osd.0/toy")
            before = LOOP_PERF.dump()
            await loop.create_task(continuation(), name="osd.0/toy")
            return before
        mid, b, a, _ = metered(toy)
        # the claim: both spins, the one before the charge included
        assert 0.008 <= moved(b, mid, "msg_MTestA")
        assert own["a"] <= moved(b, mid, "msg_MTestA")
        assert 0.004 <= moved(b, mid, "msg_MTestB") <= own["b"]
        assert moved(b, mid, "for_none") >= 0.004  # the step after the await
        # no claim: what the step did before the send stays nobody's
        assert 0.002 <= moved(mid, a, "msg_MTestA") <= own["send"]
        assert moved(mid, a, "for_none") >= 0.004

    def test_charge_many_divides_a_stretch_by_weight(self):
        async def toy():
            async def flush_window():
                await asyncio.sleep(0)
                tracing.charge_many({A: 1, B: 3})
                spin(0.008)
                await asyncio.sleep(0)
                assert tracing.charge_many({}) is None  # nobody's: none
                spin(0.002)
            await asyncio.get_running_loop().create_task(
                flush_window(), name="messenger/toy")
        _, b, a, _ = metered(toy)
        assert moved(b, a, "msg_MTestA") >= 0.002
        assert moved(b, a, "msg_MTestB") == pytest.approx(
            3 * moved(b, a, "msg_MTestA"), rel=1e-9)
        assert moved(b, a, "msg_MTestA", "avgcount") == 1
        assert moved(b, a, "for_none") >= 0.002

    def test_off_a_loop_and_in_an_unsampled_turn_a_charge_is_nothing(self):
        lone = ("op", "MTestUnsampled")
        assert tracing.charge(lone) is None  # no loop at all
        assert tracing.charge_many({lone: 1}) is None
        assert not tracing.metered()
        turns = {"sampled": 0, "not": 0}

        async def toy():
            for _ in range(64):
                if tracing.metered():
                    turns["sampled"] += 1
                else:
                    turns["not"] += 1
                    assert tracing.charge(lone) is None
                    assert tracing.charge_many({lone: 1}, claim=False) is None
                    assert tracing.charge(None) is None  # nothing was kept
                spin(0.0001)
                await asyncio.sleep(0)
        _, b, a, _ = metered(toy, sample_every=16)
        assert turns["sampled"] >= 3 and turns["not"] >= 56
        assert "msg_MTestUnsampled" not in a
        assert moved(b, a, "for_op") == 0
        assert moved(b, a, "for_none") == pytest.approx(moved(b, a, "busy"),
                                                        rel=1e-6)


# -- tinc with a count, hmerge ---------------------------------------------------


def test_perf_counters_fold_in_presummed_observations():
    pc = (PerfCountersBuilder("t").add_time_avg("lat")
          .add_histogram("us").create_perf_counters())
    pc.tinc("lat", 0.5)
    pc.tinc("lat", 1.5, 3)
    assert pc.get("lat") == (4, 2.0)
    pc.hinc("us", 5)
    buckets = [0] * 32
    buckets[3] = 2
    pc.hmerge("us", buckets, 11.0)
    got = pc.dump()["us"]
    assert got["count"] == 3 and got["sum"] == 16.0
    assert got["buckets"][3] == 3


# -- the lint rule ---------------------------------------------------------------


class TestLintRefusesAwaitInSection:
    @pytest.mark.parametrize("body,bad", [
        ("async def f(self):\n"
         "    with tracing.section('osd', 'x'):\n"
         "        await g()\n", True),
        ("async def f(self):\n"
         "    with section('osd', 'x'), other():\n"
         "        async with lock:\n"
         "            pass\n", True),
        ("async def f(self):\n"
         "    with tracing.section('osd', 'x'):\n"
         "        y = g()\n"
         "    await y\n", False),
        ("@tracing.sectioned('osd', 'x')\n"
         "async def f(self):\n"
         "    return 1\n", True),
        ("@tracing.sectioned('osd', 'x')\n"
         "def f(self):\n"
         "    return 1\n", False),
    ])
    def test_cases(self, body, bad):
        found = [f for f in async_safety.check([("x.py", body)])
                 if f.check == "async-safety/await-in-section"]
        assert bool(found) == bad, found


# -- Span on the profiler's clock --------------------------------------------------


class TestSpanClock:
    def test_times_are_integer_ns_of_time_ns(self):
        tr = Tracer(service="osd.0")
        t0 = time.time_ns()
        sp = tr.new_trace("op")
        sp.event("a")
        sp.finish()
        t1 = time.time_ns()
        assert isinstance(sp.start_ns, int) and isinstance(sp.end_ns, int)
        assert t0 <= sp.start_ns <= sp.events[0]["time_ns"] <= sp.end_ns <= t1
        assert sp.start == sp.start_ns / 1e9 and sp.end == sp.end_ns / 1e9

    def test_dump_and_trace_export_keep_their_shape(self):
        tr = Tracer(service="osd.0")
        root = tr.new_trace("client_op")
        child = root.child("osd_op")
        child.event("queued")
        child.tag("osd", 3).finish()
        root.finish()
        dumped = tr.dump()
        assert [set(d) for d in dumped] == [
            {"trace_id", "span_id", "parent_id", "name", "start", "duration",
             "events", "tags", "service"}] * 2
        d = dumped[0]
        assert isinstance(d["start"], float) and isinstance(d["duration"],
                                                            float)
        assert set(d["events"][0]) == {"time", "event"}
        assert isinstance(d["events"][0]["time"], float)
        assert abs(d["start"] - time.time()) < 5.0  # seconds since the epoch
        j = trace_export.to_jaeger(root.trace_id, dumped)["data"][0]
        assert [s["operationName"] for s in j["spans"]] == [
            "client_op", "osd_op"]
        for s in j["spans"]:
            assert abs(s["startTime"] - time.time() * 1e6) < 5e6  # µs
            assert s["duration"] >= 1
        assert j["spans"][1]["logs"][0]["fields"][0]["value"] == "queued"
        assert trace_export.resolve_parents(dumped)["__orphans__"] == 0


# -- gather counters on a cluster ----------------------------------------------------


class TestGatherCounters:
    def test_short_gather_acks_counts_a_withheld_sub_write_reply(self):
        from ceph_tpu.rados.types import MECSubWriteReply
        from ceph_tpu.rados.vstart import Cluster

        async def go():
            cluster = Cluster(n_osds=5, conf={
                "osd_auto_repair": False, "osd_heartbeat_interval": 0.2})
            await cluster.start()
            tracing.install_loop_meter().sample_every = 1  # a short run
            try:
                c = await cluster.client()
                pool = await c.create_pool("p", pg_num=4, profile={
                    "plugin": "jerasure", "technique": "reed_sol_van",
                    "k": "2", "m": "2"})
                await c.put(pool, "warm", os.urandom(20_000))
                total = lambda key: sum(  # noqa: E731
                    o.perf.get(key) for o in cluster.osds.values())
                assert total("short_gather_acks") == 0
                assert total("gather_timeouts") == 0
                assert all("op_queue_lat" not in o.perf.dump()
                           for o in cluster.osds.values())

                # one replica answers its sub-writes to nobody
                pg, acting = next(iter(cluster.osds.values()))._acting(
                    c.osdmap.pools[pool], "held")
                primary = cluster.osds[acting[0]]._primary(
                    c.osdmap.pools[pool], pg, acting)
                mute = cluster.osds[next(a for a in acting if a != primary)]
                real_send = mute.messenger.send

                async def swallow(addr, msg, *a, **kw):
                    if isinstance(msg, MECSubWriteReply):
                        return
                    await real_send(addr, msg, *a, **kw)
                mute.messenger.send = swallow
                await c.put(pool, "held", os.urandom(20_000))  # acked
                assert cluster.osds[primary].perf.get(
                    "short_gather_acks") == 1
                assert cluster.osds[primary].perf.get("gather_timeouts") == 1
                assert total("short_gather_acks") == 1
                # the put's tracked op says who did not answer, what this
                # end still held for it and how late the loop ran
                gave_up = [ev["event"] for o in cluster.osds[
                    primary].ctx.op_tracker.dump_historic_ops()["ops"]
                    for ev in o["type_data"]["events"]
                    if ev["event"].startswith("gather_timeout")]
                assert len(gave_up) == 1
                assert re.fullmatch(
                    rf"gather_timeout tid=\S+ no_reply=osd\.{mute.osd_id}"
                    r"\(shard=\d+ unacked=\d+ outbox_bytes=\d+\) "
                    r"loop_lag_ms=\d+\.\d", gave_up[0]), gave_up
                # and the loop set saw the cluster's work, layer by layer
                loop = cluster.osds[primary].ctx.perf.dump()["loop"]
                assert loop["self_osd"]["sum"] > 0
                assert loop["self_store"]["avgcount"] > 0
                assert loop is not None and "loop" in c.perf_dump()
            finally:
                await cluster.stop()
        asyncio.run(asyncio.wait_for(go(), 90))
