"""Tool CLI tests: benchmark output protocol, exhaustive-erasure verify,
non-regression corpus create/check (models the reference's benchmark and
ceph_erasure_code_non_regression usage in qa scripts)."""

import asyncio
import os

import pytest

from ceph_tpu.tools import bench_suite, benchmark, non_regression


def run(coro, timeout=180):
    asyncio.run(asyncio.wait_for(coro, timeout))


def run_bench(capsys, argv):
    code = benchmark.main(argv)
    out = capsys.readouterr().out.strip()
    return code, out


def test_benchmark_encode_output(capsys):
    code, out = run_bench(capsys, [
        "--plugin", "jerasure", "-P", "k=4", "-P", "m=2",
        "--size", "65536", "--iterations", "3",
    ])
    assert code == 0
    seconds, kb = out.split("\t")
    assert float(seconds) > 0
    assert int(kb) == 3 * 64


def test_benchmark_decode_random(capsys):
    code, out = run_bench(capsys, [
        "--plugin", "jerasure", "-P", "k=4", "-P", "m=2",
        "--size", "65536", "--iterations", "2",
        "--workload", "decode", "--erasures", "2",
    ])
    assert code == 0
    assert int(out.split("\t")[1]) == 2 * 64


def test_benchmark_decode_exhaustive_verifies(capsys):
    code, out = run_bench(capsys, [
        "--plugin", "jerasure", "-P", "k=3", "-P", "m=2",
        "--size", "16384", "--iterations", "1",
        "--workload", "decode", "--erasures", "2",
        "--erasures-generation", "exhaustive",
    ])
    assert code == 0


def test_benchmark_decode_erased_list(capsys):
    code, out = run_bench(capsys, [
        "--plugin", "jerasure", "-P", "k=4", "-P", "m=2",
        "--size", "16384", "--workload", "decode",
        "--erased", "0", "--erased", "5",
    ])
    assert code == 0


def test_benchmark_unknown_plugin(capsys):
    code = benchmark.main(["--plugin", "doesnotexist"])
    assert code == 1


def test_benchmark_tpu_plugin(capsys):
    code, out = run_bench(capsys, [
        "--plugin", "tpu", "-P", "k=8", "-P", "m=3",
        "--size", "262144", "--iterations", "2",
    ])
    assert code == 0


def test_non_regression_create_check(tmp_path):
    base = str(tmp_path)
    argv = ["--plugin", "jerasure", "--base", base, "--stripe-width", "8192",
            "-P", "k=4", "-P", "m=2", "-P", "technique=reed_sol_van"]
    assert non_regression.main(argv + ["--create"]) == 0
    # the corpus dir is profile-keyed like the reference
    d = os.path.join(base, "plugin=jerasure stripe-width=8192 k=4 m=2 "
                           "technique=reed_sol_van")
    assert os.path.exists(os.path.join(d, "content"))
    assert os.path.exists(os.path.join(d, "0"))
    assert non_regression.main(argv + ["--check"]) == 0
    # corrupt one chunk -> check must fail
    with open(os.path.join(d, "2"), "r+b") as f:
        f.write(b"\xff\xff")
    assert non_regression.main(argv + ["--check"]) == 1


@pytest.mark.parametrize("plugin,params", [
    ("shec", ["-P", "k=4", "-P", "m=3", "-P", "c=2"]),
    ("lrc", ["-P", "k=4", "-P", "m=2", "-P", "l=3"]),
    ("clay", ["-P", "k=4", "-P", "m=2", "-P", "d=5"]),
])
def test_non_regression_all_plugins(tmp_path, plugin, params):
    argv = ["--plugin", plugin, "--base", str(tmp_path)] + params
    assert non_regression.main(argv + ["--create"]) == 0
    assert non_regression.main(argv + ["--check"]) == 0


def test_bench_suite_small(capsys):
    code = bench_suite.main([
        "--size", "16384", "--iterations", "1",
        "--plugins", "jerasure", "--ks", "2", "--workloads", "encode",
    ])
    out = capsys.readouterr().out.strip().splitlines()
    assert code == 0
    import json

    rows = [json.loads(line) for line in out]
    assert len(rows) == 4  # 2 techniques x m in {1,2}
    assert all(r["mbps"] > 0 for r in rows)


def test_parameter_values_may_contain_equals(tmp_path):
    """lrc layers profiles embed k=v strings in the value; -P must split
    only on the first '=' (code-review regression)."""
    import json

    layers = json.dumps([["DDc", "plugin=jerasure technique=reed_sol_van"]])
    argv = ["--plugin", "lrc", "--base", str(tmp_path),
            "-P", f"layers={layers}", "-P", "mapping=DD_"]
    assert non_regression.main(argv + ["--create"]) == 0
    assert non_regression.main(argv + ["--check"]) == 0


def test_non_regression_error_is_exit_code(tmp_path):
    """Profile errors exit 1 with a message, not a raw traceback."""
    argv = ["--plugin", "lrc", "--base", str(tmp_path), "--create"]
    assert non_regression.main(argv) == 1


class TestCephStatusCli:
    """`ceph` status CLI (VERDICT r03 #10, reference src/ceph.in):
    status / health / osd tree / pg dump / df round-trip against a live
    vstart cluster."""

    def test_status_commands_round_trip(self, capsys):
        async def go():
            import json as _json

            from ceph_tpu.rados.vstart import Cluster
            from ceph_tpu.tools import ceph as ceph_cli

            cluster = Cluster(n_osds=4, conf={"osd_auto_repair": False})
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("st", profile={
                    "plugin": "jerasure", "technique": "reed_sol_van",
                    "k": "2", "m": "1"})
                for i in range(3):
                    await c.put(pool, f"o{i}", os.urandom(9000))
                mon = f"{cluster.mons[0].addr[0]}:{cluster.mons[0].addr[1]}"

                async def cli(*words, fmt="json"):
                    rc = await ceph_cli.run(ceph_cli.parse_args(
                        ["--mon", mon, "--format", fmt, *words]))
                    assert rc == 0
                    return capsys.readouterr().out

                st = _json.loads(await cli("status"))
                assert st["health"] == "HEALTH_OK"
                assert st["osdmap"]["num_up_osds"] == 4
                assert st["pgmap"]["active_clean"] == st["pgmap"]["num_pgs"]
                health = _json.loads(await cli("health"))
                assert health["status"] == "HEALTH_OK"
                tree = _json.loads(await cli("osd", "tree"))
                osd_rows = [r for r in tree if r["type"] == "osd"]
                assert len(osd_rows) == 4
                assert all(r["status"] == "up" for r in osd_rows)
                pgs = _json.loads(await cli("pg", "dump"))
                assert all(r["state"] == "active+clean" for r in pgs)
                assert all(len(r["acting"]) == 3 for r in pgs
                           if r["pgid"].startswith(f"{pool}."))
                df = _json.loads(await cli("df"))
                st_pool = [r for r in df if r["pool"] == "st"][0]
                assert st_pool["objects"] == 3
                # kill an OSD: health degrades, tree shows it down
                victim = next(iter(cluster.osds))
                await cluster.kill_osd(victim)
                for _ in range(100):
                    health = _json.loads(await cli("health"))
                    if health["status"] != "HEALTH_OK":
                        break
                    await asyncio.sleep(0.1)
                assert health["status"] in ("HEALTH_WARN", "HEALTH_ERR")
                # mon-backed health (HealthMonitor aggregation): checks
                # keyed by name, not the old client-side list
                assert "OSD_DOWN" in health["checks"]
                tree = _json.loads(await cli("osd", "tree"))
                down = [r for r in tree if r.get("name") == f"osd.{victim}"]
                assert down and down[0]["status"] == "down"
                # human-readable layout renders without error
                plain = await cli("status", fmt="plain")
                assert "health:" in plain and "osdmap:" in plain
                await c.stop()
            finally:
                await cluster.stop()

        run(go())


class TestCephadmDeploy:
    """cephadm-lite (reference src/cephadm/ role): bootstrap a cluster
    of real OS processes, register it, query it with the ceph CLI, stop,
    restart-from-data, and destroy."""

    def test_bootstrap_ls_stop_rm_lifecycle(self, tmp_path):
        import json as _json
        import subprocess
        import sys as _sys

        from ceph_tpu.tools import cephadm

        root = str(tmp_path / "clusters")

        def adm(*argv):
            return cephadm.main(["--data-root", root, *argv])

        assert adm("bootstrap", "--name", "c1", "--osds", "3") == 0
        spec = _json.load(open(f"{root}/c1/cluster.json"))
        assert spec["osds"] == 3 and spec["pid"] > 0
        try:
            # registry sees it running
            assert adm("ls") == 0
            # the ceph CLI reaches the deployed cluster cross-process
            mon = f"{spec['mons'][0][0]}:{spec['mons'][0][1]}"
            out = subprocess.run(
                [_sys.executable, "-m", "ceph_tpu.tools.ceph",
                 "--mon", mon, "--format", "json", "status"],
                capture_output=True, text=True, timeout=120,
                env=__import__(
                    "ceph_tpu.utils.jaxdev",
                    fromlist=["cpu_child_env"]
                ).cpu_child_env())
            assert out.returncode == 0, out.stderr[-300:]
            st = _json.loads(out.stdout)
            assert st["osdmap"]["num_up_osds"] == 3
            # durable data landed under the cluster dir
            assert (tmp_path / "clusters" / "c1" / "data").is_dir()
            # duplicate bootstrap refused
            assert adm("bootstrap", "--name", "c1") == 1
            # stop: process exits, data retained
            assert adm("stop", "--name", "c1") == 0
            import time as _time
            for _ in range(50):
                if not cephadm._alive(spec["pid"]):
                    break
                _time.sleep(0.1)
            assert not cephadm._alive(spec["pid"])
            assert (tmp_path / "clusters" / "c1" / "data").is_dir()
            # rm-cluster requires --force, then removes everything
            assert adm("rm-cluster", "--name", "c1") == 1
            assert adm("rm-cluster", "--name", "c1", "--force") == 0
            assert not (tmp_path / "clusters" / "c1").exists()
        finally:
            # belt-and-braces: never leak the daemon host
            if cephadm._alive(spec["pid"]):
                os.kill(spec["pid"], 9)

    def test_orch_apply_converges_osd_count(self, tmp_path):
        """`ceph orch apply osd` role: the daemon host's reconciliation
        loop converges the live daemon set to the written spec, both
        directions."""
        import asyncio
        import json as _json
        import time as _time

        from ceph_tpu.tools import cephadm

        root = str(tmp_path / "clusters")

        def adm(*argv):
            return cephadm.main(["--data-root", root, *argv])

        assert adm("bootstrap", "--name", "c2", "--osds", "2") == 0
        spec = _json.load(open(f"{root}/c2/cluster.json"))
        try:
            assert adm("orch-apply", "--name", "c2", "--osds", "4") == 0

            def published_osds():
                try:
                    return _json.load(
                        open(f"{root}/c2/mons.json"))["osds"]
                except (OSError, ValueError):
                    return -1

            deadline = _time.monotonic() + 60
            while published_osds() != 4 and _time.monotonic() < deadline:
                _time.sleep(0.5)
            assert published_osds() == 4
            # the mon's map agrees: 4 up OSDs
            mon = spec["mons"][0]

            async def up_count():
                from ceph_tpu.rados.client import RadosClient
                c = RadosClient((mon[0], int(mon[1])))
                await c.start()
                try:
                    await c.refresh_map()
                    return sum(1 for o in c.osdmap.osds.values() if o.up)
                finally:
                    await c.stop()

            deadline = _time.monotonic() + 30
            while _time.monotonic() < deadline:
                if asyncio.run(up_count()) == 4:
                    break
                _time.sleep(0.5)
            assert asyncio.run(up_count()) == 4
            # live daemon table
            import io
            from contextlib import redirect_stdout
            buf = io.StringIO()
            with redirect_stdout(buf):
                assert adm("orch-ps", "--name", "c2",
                           "--format", "json") == 0
            rows = _json.loads(buf.getvalue())
            assert sum(1 for r in rows if r["daemon"] == "osd"
                       and r["status"] == "running") == 4
            # scale back down: daemon-host truth converges
            assert adm("orch-apply", "--name", "c2", "--osds", "2") == 0
            deadline = _time.monotonic() + 60
            while published_osds() != 2 and _time.monotonic() < deadline:
                _time.sleep(0.5)
            assert published_osds() == 2
        finally:
            adm("rm-cluster", "--name", "c2", "--force")
            if cephadm._alive(spec["pid"]):
                os.kill(spec["pid"], 9)


class TestPoolLifecycleCli:
    def test_pool_create_set_rm_via_ceph_cli(self):
        """`ceph osd pool create/set/ls/rm`: deletion needs the
        double-name + flag guard, and OSDs purge the pool's data."""
        import asyncio
        import io
        import json as _json
        from contextlib import redirect_stdout

        from ceph_tpu.rados.vstart import Cluster

        async def go():
            cluster = Cluster(n_osds=4, conf={"osd_auto_repair": False})
            await cluster.start()
            try:
                from ceph_tpu.tools.ceph import parse_args
                from ceph_tpu.tools.ceph import run as ceph_run

                mon = f"{cluster.mons[0].addr[0]}:{cluster.mons[0].addr[1]}"

                async def ceph(*words, fmt="plain"):
                    buf = io.StringIO()
                    with redirect_stdout(buf):
                        rc = await ceph_run(parse_args(
                            ["--mon", mon, "--format", fmt, *words]))
                    return rc, buf.getvalue()

                rc, _ = await ceph("osd", "pool", "create", "data",
                                   "k=2", "m=1")
                assert rc == 0
                rc, out = await ceph("osd", "pool", "ls", fmt="json")
                pools = _json.loads(out)
                assert [p["name"] for p in pools] == ["data"]
                rc, _ = await ceph("osd", "pool", "set", "data",
                                   "pg_num", "16")
                assert rc == 0
                rc, out = await ceph("osd", "pool", "ls", fmt="json")
                assert _json.loads(out)[0]["pg_num"] == 16
                # write an object, then remove the pool
                c = await cluster.client()
                pid = _json.loads(out)[0]["id"]
                await c.put(pid, "doomed", b"bytes" * 100)
                assert await c.get(pid, "doomed") == b"bytes" * 100
                # guard: no flag / name mismatch refused
                rc, _ = await ceph("osd", "pool", "rm", "data", "data")
                assert rc == 1
                rc, _ = await ceph("osd", "pool", "rm", "data", "typo",
                                   "--yes-i-really-really-mean-it")
                assert rc == 1
                rc, _ = await ceph("osd", "pool", "rm", "data", "data",
                                   "--yes-i-really-really-mean-it")
                assert rc == 0
                rc, out = await ceph("osd", "pool", "ls", fmt="json")
                assert _json.loads(out) == []
                # OSDs purged the stored shards once the map caught up
                await c.refresh_map()
                import time as _time
                deadline = _time.monotonic() + 10
                def residue():
                    return sum(
                        1 for osd in cluster.osds.values()
                        for _o in osd.store.list_objects(pid))
                while residue() and _time.monotonic() < deadline:
                    await asyncio.sleep(0.2)
                assert residue() == 0
                await c.stop()
            finally:
                await cluster.stop()

        asyncio.run(go())

    def test_rados_bench(self):
        import asyncio
        import io
        import json as _json
        from contextlib import redirect_stdout

        from ceph_tpu.rados.vstart import Cluster

        async def go():
            cluster = Cluster(n_osds=4, conf={"osd_auto_repair": False})
            await cluster.start()
            try:
                from ceph_tpu.tools.rados import parse_args
                from ceph_tpu.tools.rados import run as rados_run

                mon = f"{cluster.mons[0].addr[0]}:{cluster.mons[0].addr[1]}"

                async def rados(*argv):
                    buf = io.StringIO()
                    with redirect_stdout(buf):
                        rc = await rados_run(parse_args(
                            ["--mon", mon, *argv]))
                    return rc, buf.getvalue()

                rc, _ = await rados("mkpool", "bp", "k=2", "m=1")
                assert rc == 0
                rc, out = await rados(
                    "bench", "bp", "2", "write",
                    "--object-size", str(64 * 1024),
                    "--concurrency", "4", "--no-cleanup")
                assert rc == 0
                stats = _json.loads(out)
                assert stats["ops"] > 0 and stats["bandwidth_MBps"] > 0
                rc, out = await rados(
                    "bench", "bp", "2", "seq",
                    "--object-size", str(64 * 1024), "--concurrency", "4")
                assert rc == 0
                stats = _json.loads(out)
                assert stats["mode"] == "seq" and stats["ops"] > 0
            finally:
                await cluster.stop()

        asyncio.run(go())

    def test_boot_sweep_purges_pool_deleted_while_down(self):
        """An OSD that missed the `osd pool rm` epoch purges the dead
        pool's shards from its persistent store on its FIRST map."""
        import asyncio

        from ceph_tpu.rados.store import MemStore, ShardMeta, Transaction
        from ceph_tpu.rados.types import OSDMap, PoolInfo
        from ceph_tpu.rados.crush import CrushMap

        async def go():
            from ceph_tpu.rados.osd import OSD

            osd = OSD(("127.0.0.1", 1), store=MemStore(), osd_id=0)
            txn = Transaction()
            meta = ShardMeta(version=1, object_size=4)
            txn.write((7, "ghost", 0), b"dead", meta)   # deleted pool
            txn.write((1, "alive", 0), b"live", meta)   # surviving pool
            osd.store.queue_transaction(txn)
            live_pool = PoolInfo(pool_id=1, name="keep",
                                 pool_type="replicated", pg_num=8,
                                 size=2, min_size=1)
            osd._on_map(OSDMap(epoch=5, pools={1: live_pool},
                               crush=CrushMap.flat([0])))
            assert list(osd.store.list_objects(7)) == []
            assert list(osd.store.list_objects(1)) == [("alive", 0)]

        asyncio.run(go())


class _CorruptingDecode:
    """Delegates to a real codec but flips a byte in every recovered
    chunk — the fast-but-wrong decoder the post-loop content check
    exists to catch."""

    def __init__(self, real):
        self._real = real

    def __getattr__(self, name):
        return getattr(self._real, name)

    def decode(self, want, available, chunk_size):
        out = self._real.decode(want, available, chunk_size)
        return {c: bytes([b[0] ^ 0xFF]) + bytes(b[1:])
                if c not in available else b
                for c, b in out.items()}


def test_benchmark_decode_random_verifies_content(capsys, monkeypatch):
    """Random-erasure decode must fail loudly when recovered bytes are
    wrong — the reference CLI only content-checked exhaustive mode."""
    import numpy as _np

    real_make = benchmark.make_codec
    monkeypatch.setattr(benchmark, "make_codec",
                        lambda a, p: _CorruptingDecode(real_make(a, p)))
    code = benchmark.main([
        "--plugin", "jerasure", "-P", "k=4", "-P", "m=2",
        "--size", "16384", "--iterations", "2",
        "--workload", "decode", "--erasures", "1",
    ])
    assert code == 1
    assert "recovered content are different" in capsys.readouterr().err


def test_benchmark_decode_erased_verifies_content(capsys, monkeypatch):
    real_make = benchmark.make_codec
    monkeypatch.setattr(benchmark, "make_codec",
                        lambda a, p: _CorruptingDecode(real_make(a, p)))
    code = benchmark.main([
        "--plugin", "jerasure", "-P", "k=4", "-P", "m=2",
        "--size", "16384", "--workload", "decode",
        "--erased", "0", "--erased", "5",
    ])
    assert code == 1
    assert "recovered content are different" in capsys.readouterr().err


def test_benchmark_decode_verification_caps_signatures(monkeypatch):
    """The post-loop check re-decodes each DISTINCT signature once,
    capped — verification work must stay O(signatures), not
    O(iterations)."""
    real_make = benchmark.make_codec
    counting = {}

    class _Counting:
        def __init__(self, real):
            self._real = real

        def __getattr__(self, name):
            return getattr(self._real, name)

        def decode(self, want, available, chunk_size):
            counting["calls"] = counting.get("calls", 0) + 1
            return self._real.decode(want, available, chunk_size)

    monkeypatch.setattr(benchmark, "make_codec",
                        lambda a, p: _Counting(real_make(a, p)))
    iters = 40
    code = benchmark.main([
        "--plugin", "jerasure", "-P", "k=4", "-P", "m=2",
        "--size", "16384", "--iterations", str(iters),
        "--workload", "decode", "--erasures", "1",
    ])
    assert code == 0
    # loop decodes + at most C(6,1)=6 distinct verification decodes
    assert counting["calls"] <= iters + 6


class TestWireFloor:
    """non_regression --wire-floor: the FAILING daemon-wire gate — a
    throughput floor against the previous round's BENCH record plus the
    multi-lane byte-identity loop (stubbed here; the real loop is
    exercised by the CI invocation and the lane tests)."""

    def _write(self, path, put, get, wrapped=False, kind=None,
               put_py=None, get_py=None):
        import json

        rec = {"daemon_wire_put_MBps": put, "daemon_wire_get_MBps": get}
        if kind is not None:
            rec["wirepath_kind"] = kind
        if put_py is not None:
            rec["daemon_wire_put_MBps_python"] = put_py
        if get_py is not None:
            rec["daemon_wire_get_MBps_python"] = get_py
        if wrapped:
            rec = {"n": 5, "parsed": rec}
        path.write_text(json.dumps(rec))

    @pytest.fixture(autouse=True)
    def _stub_lane_identity(self, monkeypatch):
        # the cluster-spinning lane half is its own integration surface;
        # these tests pin the record-comparison half's exit codes
        self.lane_calls = []
        monkeypatch.setattr(non_regression, "_wire_lane_identity",
                            lambda: self.lane_calls.append(1) or 0)

    def test_regression_fails_healthy_passes(self, tmp_path, capsys):
        prev = tmp_path / "prev.json"
        cur = tmp_path / "cur.json"
        self._write(prev, 200.0, 300.0, wrapped=True)
        # regression on get only: now a FAILING gate (was warn-only)
        self._write(cur, 210.0, 100.0)
        argv = ["--wire-floor", "--bench", str(cur), "--prev", str(prev)]
        assert non_regression.main(argv) == 1
        out = capsys.readouterr().out
        assert "FAIL wire-floor: daemon_wire_get_MBps" in out
        assert "daemon_wire_put_MBps [python arms] 210.0" in out
        # healthy record: green, and the lane-identity half ran too
        self._write(cur, 210.0, 290.0)
        assert non_regression.main(argv) == 0
        out = capsys.readouterr().out
        assert "FAIL" not in out
        assert len(self.lane_calls) == 2

    def test_lane_identity_failure_fails_gate(self, tmp_path,
                                              monkeypatch):
        prev = tmp_path / "prev.json"
        cur = tmp_path / "cur.json"
        self._write(prev, 200.0, 300.0)
        self._write(cur, 210.0, 290.0)
        monkeypatch.setattr(non_regression, "_wire_lane_identity",
                            lambda: 1)
        assert non_regression.main(
            ["--wire-floor", "--bench", str(cur),
             "--prev", str(prev)]) == 1

    def test_missing_previous_metric_skips(self, tmp_path, capsys):
        prev = tmp_path / "prev.json"
        cur = tmp_path / "cur.json"
        prev.write_text("{}")
        self._write(cur, 100.0, 100.0)
        assert non_regression.main(
            ["--wire-floor", "--bench", str(cur), "--prev", str(prev)]) == 0
        assert "skipping" in capsys.readouterr().out

    def test_lane_identity_runs_without_records(self, capsys):
        assert non_regression.main(["--wire-floor"]) == 0
        assert len(self.lane_calls) == 1

    def test_unreadable_record_fails(self, tmp_path):
        cur = tmp_path / "cur.json"
        self._write(cur, 1.0, 1.0)
        assert non_regression.main(
            ["--wire-floor", "--bench", str(cur),
             "--prev", str(tmp_path / "nope.json")]) == 1

    def test_differing_arms_compare_python_numbers(self, tmp_path,
                                                   capsys):
        """Satellite (ISSUE 12): a native-arm record against a
        python-arm record must compare the python numbers of each —
        the arm speedup must not mask a real wire regression."""
        prev = tmp_path / "prev.json"
        cur = tmp_path / "cur.json"
        # pre-ISSUE-12 record: no wirepath_kind == the python arm
        self._write(prev, 200.0, 300.0)
        # native headline LOOKS healthy (400 > 200) but the python arm
        # of the same window regressed (90 < 0.8 * 200) — must FAIL
        self._write(cur, 400.0, 500.0, kind="native",
                    put_py=90.0, get_py=290.0)
        argv = ["--wire-floor", "--bench", str(cur), "--prev", str(prev)]
        assert non_regression.main(argv) == 1
        out = capsys.readouterr().out
        assert "wirepath_kind differs" in out
        assert "FAIL wire-floor: daemon_wire_put_MBps" in out
        # healthy python arm: green even though arms differ
        self._write(cur, 400.0, 500.0, kind="native",
                    put_py=195.0, get_py=290.0)
        assert non_regression.main(argv) == 0
        assert "FAIL" not in capsys.readouterr().out

    def test_matching_native_arms_compare_headline(self, tmp_path,
                                                   capsys):
        prev = tmp_path / "prev.json"
        cur = tmp_path / "cur.json"
        self._write(prev, 400.0, 500.0, wrapped=True, kind="native",
                    put_py=200.0, get_py=250.0)
        # both native: the headline pair is like-for-like; a native-arm
        # regression fails even with a healthy python arm
        self._write(cur, 250.0, 480.0, kind="native",
                    put_py=210.0, get_py=260.0)
        argv = ["--wire-floor", "--bench", str(cur), "--prev", str(prev)]
        assert non_regression.main(argv) == 1
        out = capsys.readouterr().out
        assert "[native arms]" in out
        assert "FAIL wire-floor: daemon_wire_put_MBps" in out

    def test_native_record_missing_python_arm_fails(self, tmp_path,
                                                    capsys):
        """A native-arm record that never measured its python arm
        cannot be compared like-for-like against a python record —
        that's a broken record, not a pass."""
        prev = tmp_path / "prev.json"
        cur = tmp_path / "cur.json"
        self._write(prev, 200.0, 300.0)
        self._write(cur, 400.0, 500.0, kind="native")
        assert non_regression.main(
            ["--wire-floor", "--bench", str(cur),
             "--prev", str(prev)]) == 1
        assert "missing in the current record" in \
            capsys.readouterr().out
