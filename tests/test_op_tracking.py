"""Cluster-scope op observability (reference src/common/TrackedOp.h +
HealthMonitor + jaeger trace propagation): full OpTracker timelines,
bounded rings, thread-safe seq/state, cross-daemon trace stitching for
an EC write, slow-op health raise/clear/mute lifecycle, old-frame
(pre-trace-id) wire decode, and `ceph -s` rendering of the new checks."""

import asyncio
import os
import struct
import threading
import time

import pytest

from ceph_tpu.common.tracked_op import OpTracker, percentile
from ceph_tpu.common.tracing import Tracer
from ceph_tpu.rados.vstart import Cluster
from ceph_tpu.tools import trace_export

CONF = {
    "mon_osd_report_grace": 5.0,
    "osd_heartbeat_interval": 0.1,
    "osd_auto_repair": False,
    "ms_local_fastpath": False,
}

PROFILE = {"plugin": "jerasure", "technique": "reed_sol_van",
           "k": "4", "m": "2"}


def run(coro, timeout=120):
    asyncio.run(asyncio.wait_for(coro, timeout))


# -- OpTracker unit behavior -------------------------------------------------


class TestOpTrackerUnit:
    def test_seq_is_per_tracker(self):
        """Two trackers allocate independent seqs (the module-level
        counter is gone): daemon A's op numbering can't be perturbed by
        daemon B's load."""
        a, b = OpTracker(), OpTracker()
        assert a.create("x").seq == 1
        assert a.create("y").seq == 2
        assert b.create("z").seq == 1

    def test_thread_safe_create_finish(self):
        """Concurrent create/mark/finish from many threads: no lost
        ops, no exceptions, in-flight map empty at the end."""
        tr = OpTracker(history_size=4096)
        errors = []

        def worker():
            try:
                for _ in range(200):
                    op = tr.create("w")
                    op.mark_event("reached_pg")
                    op.finish()
            except Exception as e:  # pragma: no cover - the regression
                errors.append(e)

        threads = [threading.Thread(target=worker) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors
        assert tr.dump_ops_in_flight()["num_ops"] == 0
        assert tr.perf.get("op_created") == 8 * 200
        assert tr.perf.get("op_done") == 8 * 200
        # seqs never collided: 1600 distinct ops were numbered
        assert next(tr._seq) == 8 * 200 + 1

    def test_events_bounded_for_stuck_op(self):
        """A stuck op polled forever cannot grow its timeline without
        bound: events cap at max_events, the overflow is counted and
        surfaced in the dump."""
        tr = OpTracker(max_events=16)
        op = tr.create("stuck")
        for i in range(100):
            op.mark_event(f"poll_{i}")
        assert len(op.events) == 16
        assert tr.perf.get("events_dropped") == 84
        assert op.dump()["events_dropped"] == 84

    def test_history_and_slow_ring_bounds(self):
        tr = OpTracker(history_size=5, history_slow_size=3,
                       slow_threshold=0.05)
        for i in range(20):
            op = tr.create(f"fast{i}")
            op.finish()
        assert tr.dump_historic_ops()["num_ops"] == 5
        assert tr.dump_historic_slow_ops()["num_ops"] == 0
        for i in range(7):
            op = tr.create(f"slow{i}")
            op.initiated_at -= 1.0  # aged past the threshold
            op.finish()
        assert tr.dump_historic_slow_ops()["num_ops"] == 3  # ring bound
        assert tr.perf.get("slow_ops_observed") == 7
        # historic ring keeps the most recent completions
        descs = [o["description"]
                 for o in tr.dump_historic_ops()["ops"]]
        assert descs == [f"slow{i}" for i in range(2, 7)]

    def test_slow_op_summary_reports_inflight_aging(self):
        tr = OpTracker(slow_threshold=0.2)
        young = tr.create("young")
        old = tr.create("old_op")
        old.initiated_at -= 5.0
        old.mark_event("waiting_for_subops")
        s = tr.slow_op_summary()
        assert s["count"] == 1
        assert s["oldest_age"] >= 5.0
        assert s["ops"][0]["description"] == "old_op"
        assert s["ops"][0]["last_event"] == "waiting_for_subops"
        young.finish()
        old.finish()

    def test_phase_latencies_and_percentiles(self):
        tr = OpTracker()
        for dt in (0.01, 0.02, 0.03):
            op = tr.create("w")
            t0 = op.initiated_at
            op.events = [
                {"time": t0 + 0.001, "event": "queued_for_pg"},
                {"time": t0 + 0.001 + dt, "event": "reached_pg"},
                {"time": t0 + 0.010, "event": "ec_encode_dispatched"},
                {"time": t0 + 0.015, "event": "encoded"},
            ]
            op.finish()
        pct = tr.phase_percentiles()
        assert pct["queue_wait"]["count"] == 3
        assert pct["queue_wait"]["p50_us"] == pytest.approx(20_000, rel=0.1)
        assert pct["queue_wait"]["p999_us"] == pytest.approx(30_000,
                                                            rel=0.1)
        assert pct["ec_dispatch"]["p50_us"] == pytest.approx(5_000,
                                                             rel=0.1)
        tr.clear_samples()
        assert tr.phase_percentiles() == {}

    def test_percentile_helper(self):
        xs = [float(i) for i in range(1, 101)]
        assert percentile(xs, 0.5) == pytest.approx(50.0, abs=1.0)
        assert percentile(xs, 0.99) == pytest.approx(99.0, abs=1.0)
        assert percentile([], 0.5) == 0.0


class TestTracerUnit:
    def test_ids_are_unique_hex(self):
        t = Tracer()
        a, b = t.new_trace("a"), t.new_trace("b")
        assert a.trace_id != b.trace_id
        int(a.trace_id, 16)  # hex
        assert len(a.trace_id) == 16

    def test_join_makes_remote_child(self):
        t1, t2 = Tracer(service="client"), Tracer(service="osd.0")
        root = t1.new_trace("client_op")
        child = t2.join("osd_op", *root.context())
        child.finish()
        root.finish()
        got = t2.spans_for(root.trace_id)
        assert len(got) == 1
        assert got[0]["parent_id"] == root.span_id
        assert got[0]["service"] == "osd.0"

    def test_dump_trace_asok_filter(self):
        t = Tracer()
        keep = t.new_trace("keep")
        keep.finish()
        t.new_trace("other").finish()
        spans = t.spans_for(keep.trace_id)
        assert [s["name"] for s in spans] == ["keep"]


# -- end-to-end: timeline completeness + trace stitching ---------------------


class TestWriteTimelineAndStitching:
    # the ISSUE's event vocabulary for a TCP EC write
    EXPECTED = ["queued_for_pg", "reached_pg", "ec_encode_dispatched",
                "encoded", "sub_writes_sent", "waiting_for_subops",
                "commit_gathered", "commit_sent", "done"]

    def test_tcp_ec_write_timeline_and_one_stitched_trace(self):
        async def go():
            cluster = Cluster(n_osds=6, conf=dict(CONF))
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("trk", profile=dict(PROFILE))
                await c.put(pool, "obj", os.urandom(300_000))
                got = await c.get(pool, "obj")
                assert len(got) == 300_000

                # -- timeline completeness (write) --------------------
                timelines = []
                for o in cluster.osds.values():
                    for op in o.ctx.op_tracker.dump_historic_ops()["ops"]:
                        if op["description"].startswith("osd_op(write"):
                            timelines.append(op)
                assert timelines, "no tracked write op on any OSD"
                op = timelines[-1]
                events = op["type_data"]["events"]
                names = [e["event"] for e in events]
                for want in self.EXPECTED:
                    assert want in names, (want, names)
                # timeline order matches the vocabulary order
                idx = [names.index(w) for w in self.EXPECTED]
                assert idx == sorted(idx)
                # timestamps are monotonic
                stamps = [e["time"] for e in events]
                assert stamps == sorted(stamps)

                # -- read timeline ------------------------------------
                read_ops = []
                for o in cluster.osds.values():
                    for op in o.ctx.op_tracker.dump_historic_ops()["ops"]:
                        if op["description"].startswith("osd_op(read"):
                            read_ops.append(op)
                assert read_ops
                rnames = [e["event"]
                          for e in read_ops[-1]["type_data"]["events"]]
                for want in ("queued_for_pg", "reached_pg",
                             "sub_reads_sent", "decode_dispatched",
                             "decoded", "commit_sent", "done"):
                    assert want in rnames, (want, rnames)

                # -- sub-writes are first-class tracked ops -----------
                sub_tracked = 0
                for o in cluster.osds.values():
                    for op in o.ctx.op_tracker.dump_historic_ops()["ops"]:
                        if op["description"].startswith("ec_sub_write("):
                            sub_tracked += 1
                assert sub_tracked >= 5  # k+m-1 remote peers

                # -- ONE stitched trace -------------------------------
                roots = [d for d in c.tracer.dump()
                         if d["name"] == "client_op write obj"]
                assert roots
                trace_id = roots[-1]["trace_id"]
                sources = [c.tracer] + [o.ctx.tracer
                                        for o in cluster.osds.values()]
                spans = trace_export.collect_spans(sources, trace_id)
                names = [s["name"] for s in spans]
                assert "client_op write obj" in names
                assert "osd_op write" in names
                assert "ec write" in names
                # all k+m sub-write spans under one trace_id (5 remote
                # peers + the primary's local shard)
                subw = [s for s in spans
                        if s["name"].startswith("ec_sub_write")]
                assert len(subw) == 6, names
                # every parent link resolves inside the collected set
                links = trace_export.resolve_parents(spans)
                assert links["__orphans__"] == 0
                # exactly one root: the client span
                roots_in = [s for s in spans if not s["parent_id"]]
                assert len(roots_in) == 1
                assert roots_in[0]["name"] == "client_op write obj"

                # -- jaeger export shape ------------------------------
                doc = trace_export.to_jaeger(trace_id, spans)
                data = doc["data"][0]
                assert data["traceID"] == trace_id
                assert len(data["spans"]) == len(spans)
                assert data["processes"]  # client + osds labeled
                child = next(s for s in data["spans"]
                             if s["operationName"] == "osd_op write")
                assert child["references"][0]["refType"] == "CHILD_OF"
                assert child["references"][0]["spanID"] == \
                    roots_in[0]["span_id"]

                # -- asok answers dump_trace --------------------------
                primary = next(
                    o for o in cluster.osds.values()
                    if any(s["service"].startswith("osd")
                           and s["name"] == "ec write"
                           for s in o.ctx.tracer.spans_for(trace_id)))
                reply = primary.ctx.asok.execute("dump_trace",
                                                 trace_id=trace_id)
                assert reply["trace_id"] == trace_id
                assert any(s["name"] == "ec write"
                           for s in reply["spans"])
                await c.stop()
            finally:
                await cluster.stop()

        run(go())

    def test_trace_propagation_feature_gate(self):
        """ms_trace_propagation=False: the client stamps no context, so
        the wire carries empty trace fields and the OSD roots its own
        trace — nothing breaks, nothing stitches."""
        async def go():
            conf = dict(CONF)
            conf["ms_trace_propagation"] = False
            cluster = Cluster(n_osds=6, conf=conf)
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("gate", profile=dict(PROFILE))
                await c.put(pool, "o", b"x" * 50_000)
                assert await c.get(pool, "o") == b"x" * 50_000
                assert not c.tracer.dump()  # no client root span
                # OSD-side spans exist but root locally (no client id)
                osd_ops = [d for o in cluster.osds.values()
                           for d in o.ctx.tracer.dump()
                           if d["name"] == "osd_op write"]
                assert osd_ops
                assert all(d["parent_id"] is None for d in osd_ops)
                await c.stop()
            finally:
                await cluster.stop()

        run(go())


# -- golden replay: pre-trace-id frames still decode -------------------------


class TestOldFrameDecode:
    def test_truncated_tail_defaults(self):
        """A frame packed with the PRE-trace FIXED_FIELDS list (an old
        sender) decodes with the new fields at their defaults."""
        from ceph_tpu.rados import types as t
        from ceph_tpu.rados.messenger import _pack_fixed, decode_message

        m = t.MOSDOp(op="write", pool_id=3, oid="o", data=b"d",
                     epoch=4, reqid="r")
        payload = _pack_fixed(m, t.MOSDOp.FIXED_FIELDS[:-2])
        back = decode_message(20, 4, payload, None, True)
        assert back.oid == "o" and back.reqid == "r"
        assert back.trace_id == "" and back.span_id == ""

        w = t.MECSubWrite(pool_id=1, pg=2, oid="x", shard=3,
                          chunk=b"c", version=9, tid="t")
        payload = _pack_fixed(w, t.MECSubWrite.FIXED_FIELDS[:-2])
        back = decode_message(30, 4, payload, None, True)
        assert back.oid == "x" and back.version == 9
        assert back.trace_id == ""

    def test_golden_corpus_frames_decode(self):
        """The archived pre-trace frames (corpus/wire/golden) decode
        under today's registry — the on-disk half of the golden replay
        (wire_corpus --check runs the same assertion in CI)."""
        import ceph_tpu.rados.types  # noqa: F401 — registers the set
        from ceph_tpu.rados.messenger import decode_message
        from ceph_tpu.tools.wire_corpus import CORPUS_DIR, _FRAME_HDR

        golden = os.path.join(CORPUS_DIR, "golden")
        frames = sorted(n for n in os.listdir(golden)
                        if n.endswith(".frame"))
        assert frames, "golden corpus is empty"
        for name in frames:
            with open(os.path.join(golden, name), "rb") as f:
                raw = f.read()
            type_id, version, fixed, plen = _FRAME_HDR.unpack_from(raw, 0)
            off = _FRAME_HDR.size
            payload = raw[off:off + plen]
            off += plen
            (blen,) = struct.unpack_from("<I", raw, off)
            blob = raw[off + 4:off + 4 + blen] if blen else None
            msg = decode_message(type_id, version, payload, blob,
                                 bool(fixed))
            if "pretrace" in name:
                # archived before the trace tail existed: the truncated-
                # tail rule must default it
                assert getattr(msg, "trace_id", "") == ""
            if "preqos" in name:
                # archived before the MOSDOp v6 client tail existed
                assert getattr(msg, "client", "") == ""


# -- health model: raise / clear / mute lifecycle ----------------------------


class TestHealthModelUnit:
    def _mon(self):
        from ceph_tpu.rados.mon import Monitor
        from ceph_tpu.rados.types import OsdInfo

        mon = Monitor()
        for i in range(3):
            mon.osdmap.osds[i] = OsdInfo(osd_id=i, addr=("h", 1 + i))
        return mon

    def _report(self, mon, osd_id, checks):
        mon._health_reports[osd_id] = {"checks": checks,
                                       "stamp": time.monotonic()}

    def test_daemon_check_raise_and_clear(self):
        from ceph_tpu.rados.types import MPing

        mon = self._mon()
        assert mon.health_summary()["status"] == "HEALTH_OK"
        self._report(mon, 0, {"SLOW_OPS": {
            "severity": "warning", "summary": "2 slow ops",
            "count": 2, "oldest_age": 4.2,
            "detail": ["osd_op(write 1:a) age 4.2s"]}})
        self._report(mon, 1, {"SLOW_OPS": {
            "severity": "warning", "summary": "1 slow ops",
            "count": 1, "oldest_age": 1.0}})
        h = mon.health_summary(detail=True)
        assert h["status"] == "HEALTH_WARN"
        chk = h["checks"]["SLOW_OPS"]
        assert chk["count"] == 3
        assert chk["oldest_age"] == pytest.approx(4.2)
        assert "osd.0" in chk["summary"] and "osd.1" in chk["summary"]
        assert any("age 4.2s" in d for d in chk["detail"])
        # an EMPTY health report on the next ping clears the OSD's checks
        asyncio.run(mon._process_ping(MPing(osd_id=0, health={})))
        h = mon.health_summary()
        assert h["checks"]["SLOW_OPS"]["count"] == 1
        asyncio.run(mon._process_ping(MPing(osd_id=1, health={})))
        assert mon.health_summary()["status"] == "HEALTH_OK"

    def test_stale_and_down_reports_drop(self):
        mon = self._mon()
        self._report(mon, 0, {"BREAKER_OPEN": {
            "severity": "warning", "summary": "1 lane open",
            "lanes": ["packedbit"]}})
        assert "BREAKER_OPEN" in mon.health_summary()["checks"]
        # stale: a dead OSD's last report must expire, not wedge raised
        mon._health_reports[0]["stamp"] -= 1e9
        assert mon.health_summary()["status"] == "HEALTH_OK"
        # down: map authority overrides the report
        self._report(mon, 1, {"TIER_OVER_TARGET": {
            "severity": "warning", "summary": "over",
            "resident_bytes": 10, "target_bytes": 5}})
        mon.osdmap.osds[1].up = False
        h = mon.health_summary()
        assert "TIER_OVER_TARGET" not in h["checks"]
        assert "OSD_DOWN" in h["checks"]  # map-derived check raised

    def test_mute_lifecycle(self):
        from ceph_tpu.rados.types import MHealthMute

        mon = self._mon()
        self._report(mon, 0, {"SLOW_OPS": {
            "severity": "warning", "summary": "1 slow ops", "count": 1,
            "oldest_age": 3.0}})
        assert mon.health_summary()["status"] == "HEALTH_WARN"
        # mute: status returns to OK, the check moves to "muted"
        reply = mon._handle_health_mute(MHealthMute(check="SLOW_OPS"))
        assert reply.health["status"] == "HEALTH_OK"
        assert "SLOW_OPS" in reply.health["muted"]
        assert "SLOW_OPS" not in reply.health["checks"]
        # unmute: degrades again
        reply = mon._handle_health_mute(
            MHealthMute(check="SLOW_OPS", unmute=True))
        assert reply.health["status"] == "HEALTH_WARN"
        # ttl mute expires on its own
        mon._handle_health_mute(MHealthMute(check="SLOW_OPS", ttl=0.05))
        assert mon.health_summary()["status"] == "HEALTH_OK"
        time.sleep(0.08)
        assert mon.health_summary()["status"] == "HEALTH_WARN"

    def test_mutes_survive_leader_change(self):
        """Mutes replicate in the paxos snapshot (rebased remaining
        ttl): a new leader applying the committed state keeps them."""
        from ceph_tpu.rados.types import MHealthMute

        mon1 = self._mon()
        mon1._handle_health_mute(MHealthMute(check="SLOW_OPS"))
        mon1._handle_health_mute(MHealthMute(check="OSD_DOWN", ttl=60.0))
        state = mon1._snapshot_state()
        mon2 = self._mon()
        mon2._apply_committed(1, state)
        assert mon2._health_mutes["SLOW_OPS"] == float("inf")
        remaining = mon2._health_mutes["OSD_DOWN"] - time.monotonic()
        assert 50.0 < remaining <= 60.0
        self._report(mon2, 0, {"SLOW_OPS": {
            "severity": "warning", "summary": "1 slow ops"}})
        assert mon2.health_summary()["status"] == "HEALTH_OK"

    def test_pg_sweep_memoized_per_epoch(self):
        mon = self._mon()
        mon.osdmap.osds[0].up = False  # a hole somewhere is irrelevant
        first = mon._pg_health_checks()
        assert mon._pg_health_memo[0] == mon.osdmap.epoch
        cached = mon._pg_health_checks()
        assert cached == first
        # annotating a returned entry must not pollute the memo
        if cached:
            next(iter(cached.values()))["expires_in"] = 1.0
            assert "expires_in" not in next(
                iter(mon._pg_health_memo[1].values()))
        # an epoch bump invalidates
        mon.osdmap.epoch += 1
        mon._pg_health_checks()
        assert mon._pg_health_memo[0] == mon.osdmap.epoch

    def test_map_flags_and_severity(self):
        mon = self._mon()
        mon.osdmap.flags = ["pausewr"]
        h = mon.health_summary()
        assert h["checks"]["OSDMAP_FLAGS"]["flags"] == ["pausewr"]
        assert h["status"] == "HEALTH_WARN"
        # an error-severity daemon check escalates to HEALTH_ERR
        self._report(mon, 0, {"STORE_FAIL": {
            "severity": "error", "summary": "store dead"}})
        assert mon.health_summary()["status"] == "HEALTH_ERR"


class TestHealthE2E:
    def test_flag_check_and_mute_over_the_wire(self):
        async def go():
            cluster = Cluster(n_osds=3, conf=dict(CONF))
            await cluster.start()
            try:
                c = await cluster.client()
                h = await c.get_health()
                assert h["status"] == "HEALTH_OK"
                await c.osd_set_flag("pausewr", True)
                h = await c.get_health(detail=True)
                assert h["status"] == "HEALTH_WARN"
                assert "OSDMAP_FLAGS" in h["checks"]
                # mute over the wire
                h = await c.health_mute("OSDMAP_FLAGS")
                assert h["status"] == "HEALTH_OK"
                assert "OSDMAP_FLAGS" in h["muted"]
                h = await c.health_mute("OSDMAP_FLAGS", unmute=True)
                assert h["status"] == "HEALTH_WARN"
                # clearing the flag clears the check
                await c.osd_set_flag("pausewr", False)
                h = await c.get_health()
                assert h["status"] == "HEALTH_OK"
                await c.stop()
            finally:
                await cluster.stop()

        run(go())

    def test_slow_ops_raises_from_osd_reports(self):
        """An OSD whose tracker holds an aged in-flight op reports
        SLOW_OPS on its next ping and the mon raises it; finishing the
        op (next ping reports empty) clears it."""
        async def go():
            conf = dict(CONF)
            conf["osd_op_complaint_time"] = 0.2
            cluster = Cluster(n_osds=3, conf=conf)
            await cluster.start()
            try:
                c = await cluster.client()
                osd = next(iter(cluster.osds.values()))
                stuck = osd.ctx.op_tracker.create("osd_op(write 1:wedge)")
                stuck.mark_event("waiting_for_subops")
                stuck.initiated_at -= 5.0
                deadline = time.monotonic() + 10
                raised = None
                while time.monotonic() < deadline:
                    h = await c.get_health(detail=True)
                    if "SLOW_OPS" in h["checks"]:
                        raised = h["checks"]["SLOW_OPS"]
                        break
                    await asyncio.sleep(0.05)
                assert raised is not None, "SLOW_OPS never raised"
                assert raised["oldest_age"] >= 5.0
                assert f"osd.{osd.osd_id}" in raised["summary"]
                stuck.finish()
                deadline = time.monotonic() + 10
                while time.monotonic() < deadline:
                    h = await c.get_health()
                    if "SLOW_OPS" not in h["checks"]:
                        break
                    await asyncio.sleep(0.05)
                assert "SLOW_OPS" not in h["checks"], \
                    "SLOW_OPS wedged after the op finished"
                await c.stop()
            finally:
                await cluster.stop()

        run(go())


# -- `ceph -s` / `ceph health detail` rendering ------------------------------


class TestCephRendering:
    HEALTH = {
        "status": "HEALTH_WARN",
        "checks": {
            "SLOW_OPS": {"severity": "warning",
                         "summary": "3 slow ops, oldest one blocked for "
                                    "12.0 sec, daemons ['osd.1'] have "
                                    "slow ops",
                         "count": 3, "oldest_age": 12.0,
                         "detail": ["osd.1: osd_op(write 1:a) age 12.0s "
                                    "last event waiting_for_subops"]},
            "BREAKER_OPEN": {"severity": "warning",
                             "summary": "BREAKER_OPEN on ['osd.2']"},
            "TIER_OVER_TARGET": {"severity": "warning",
                                 "summary": "TIER_OVER_TARGET on "
                                            "['osd.0']"},
            "OSDMAP_FLAGS": {"severity": "warning",
                             "summary": "flags set: pausewr"},
            "PG_DEGRADED": {"severity": "warning",
                            "summary": "2 pgs degraded"},
            "PG_INCOMPLETE": {"severity": "error",
                              "summary": "1 pgs below min_size "
                                         "(unserviceable)"},
        },
        "muted": {"OSD_DOWN": {"summary": "1 osds down: [3]",
                               "expires_in": 30.0}},
    }

    def test_render_health_every_check(self):
        from ceph_tpu.tools.ceph import render_health

        lines = render_health(self.HEALTH, detail=True)
        text = "\n".join(lines)
        assert lines[0] == "HEALTH_WARN"
        for name in ("SLOW_OPS", "BREAKER_OPEN", "TIER_OVER_TARGET",
                     "OSDMAP_FLAGS", "PG_DEGRADED", "PG_INCOMPLETE"):
            assert name in text
        # severity markers + slow-op aging render
        assert "[ERR] PG_INCOMPLETE" in text
        assert "[WRN] SLOW_OPS" in text
        assert "oldest one blocked for 12.0 sec" in text
        # detail lines render under the check
        assert "last event waiting_for_subops" in text
        # muted checks render separately with their expiry
        assert "(muted) OSD_DOWN" in text and "expires in 30" in text

    def test_ceph_status_uses_mon_health(self, capsys):
        from ceph_tpu.tools import ceph as ceph_cli

        async def go():
            cluster = Cluster(n_osds=3, conf=dict(CONF))
            await cluster.start()
            try:
                host, port = cluster.mon_addrs[0]
                args = ceph_cli.parse_args(
                    ["--mon", f"{host}:{port}", "status"])
                assert await ceph_cli.run(args) == 0
                args = ceph_cli.parse_args(
                    ["--mon", f"{host}:{port}", "health", "detail"])
                assert await ceph_cli.run(args) == 0
            finally:
                await cluster.stop()

        run(go())
        out = capsys.readouterr().out
        assert "health: HEALTH_OK" in out
        assert "HEALTH_OK" in out.splitlines()[-1] \
            or "HEALTH_OK" in out


# -- bench percentile helpers ------------------------------------------------


class TestMgrHealthMetrics:
    def test_stale_health_exports_mon_unreachable(self):
        from ceph_tpu.mgr.daemon import MgrDaemon

        m = MgrDaemon()
        m.latest_health = {"status": "HEALTH_OK", "checks": {}}
        m._health_stamp = time.monotonic()
        assert "ceph_health_status 0" in m.prometheus_text()
        # a poll that hasn't succeeded for many intervals must not keep
        # exporting the frozen last-known OK through a mon outage
        m._health_stamp = time.monotonic() - 1000.0
        t = m.prometheus_text()
        assert "ceph_health_status 2" in t
        assert 'check="MON_UNREACHABLE"' in t


class TestWireHistograms:
    def test_wire_io_histograms_populate(self):
        from ceph_tpu.rados.messenger import _build_wire_perf

        perf = _build_wire_perf()
        perf.hinc("tx_io_us", 100)
        perf.hinc("rx_io_us", 10)
        assert sum(perf.get("tx_io_us")) == 1
        assert sum(perf.get("rx_io_us")) == 1
