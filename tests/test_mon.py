"""Mon consensus tests: elections, Paxos replication, leader failover,
request forwarding, centralized config, store recovery (reference
src/mon/{Paxos,Elector,ConfigMonitor,OSDMonitor}.cc behaviors)."""

import asyncio
import os

import pytest

from ceph_tpu.rados.paxos import ElectionLogic, MonitorDBStore
from ceph_tpu.rados.vstart import Cluster

FAST = {
    "mon_lease": 1.0,
    "mon_election_timeout": 0.25,
    "osd_heartbeat_interval": 0.2,
    "mon_osd_report_grace": 1.5,
    "osd_auto_repair": False,
}


def run(coro):
    return asyncio.run(coro)


# -- pure logic --------------------------------------------------------------


class TestElectionLogic:
    def test_lowest_rank_wins(self):
        a, b = ElectionLogic(0, 3), ElectionLogic(1, 3)
        ea = a.start()
        assert b.receive_propose(0, ea) == "ack"  # rank 0 beats rank 1
        assert a.receive_propose(1, ea) == "counter"  # we'd rather run

    def test_majority_count(self):
        logic = ElectionLogic(0, 3)
        epoch = logic.start()
        assert not logic.receive_ack(1, epoch - 1)  # stale epoch ignored
        assert logic.receive_ack(1, epoch)  # self + 1 = 2 of 3
        epoch2, quorum = logic.declare_victory()
        assert epoch2 % 2 == 0 and quorum == {0, 1}
        assert logic.is_leader

    def test_victory_overrides(self):
        logic = ElectionLogic(2, 3)
        logic.start()
        assert logic.receive_victory(0, logic.epoch + 1, {0, 1, 2})
        assert not logic.is_leader and logic.in_quorum and logic.leader == 0


class TestPaxosEpochFencing:
    """A deposed leader (healed partition, lost lease) must not be able to
    commit a value concurrently with the new leader: peons promise the
    election epoch and nack lower-epoch begin/commit (the reference's
    accepted_pn machinery, src/mon/Paxos.cc handle_collect/handle_begin)."""

    def _paxos_pair(self):
        from ceph_tpu.rados.paxos import Paxos

        sent = []

        def make(rank):
            async def send(peer, payload):
                sent.append((rank, peer, payload))
            return Paxos(MonitorDBStore(), rank, send)

        return make, sent

    def test_peon_nacks_stale_begin_and_ignores_stale_commit(self):
        async def go():
            make, sent = self._paxos_pair()
            peon = make(1)
            peon.promise(6)  # new leader's collect/victory at epoch 6
            # old leader (epoch 4) tries begin: peon must nack, not accept
            await peon.handle_begin(0, 1, b"old-value", epoch=4)
            assert sent[-1][2]["op"] == "nack"
            assert sent[-1][2]["epoch"] == 6
            assert peon.pending is None
            # and its commit must not land either
            peon.handle_commit(1, b"old-value", epoch=4)
            assert peon.store.last_committed == 0
            # the rightful leader's round at epoch 6 proceeds
            await peon.handle_begin(2, 1, b"new-value", epoch=6)
            assert sent[-1][2]["op"] == "accept"
            peon.handle_commit(1, b"new-value", epoch=6)
            assert peon.store.get(1) == b"new-value"

        run(go())

    def test_leader_abandons_on_nack(self):
        async def go():
            make, _sent = self._paxos_pair()
            leader = make(0)
            await leader.propose(b"v", {0, 1, 2}, epoch=4)
            leader.handle_nack(6)
            assert leader.nacked
            assert leader.proposing is None
            # accepts for a foreign epoch are not counted
            await leader.propose(b"v2", {0, 1, 2}, epoch=8)
            assert not leader.handle_accept(1, leader.proposing[0], epoch=6)
            assert leader.handle_accept(1, leader.proposing[0], epoch=8)

        run(go())

    def test_deposed_mid_round_leader_cannot_commit(self):
        """A leader whose proposal is in flight when it promises a NEWER
        leadership (victory/collect from the new leader) must abandon the
        round: otherwise its commit would carry the new epoch and land on
        the new leader's peons as a divergent value."""
        async def go():
            make, sent = self._paxos_pair()
            leader = make(0)
            await leader.propose(b"stale", {0, 1, 2}, epoch=2)
            assert leader.handle_accept(1, leader.proposing[0], epoch=2)
            # new leader wins at epoch 4; we promise it before committing
            assert leader.promise(4)
            assert leader.proposing is None and leader.nacked
            # the depose-nack for our old round arrives late: already-known
            # leadership, must not be treated as a fresh deposition
            assert not leader.handle_nack(4)

        run(go())

    def test_stale_nack_ignored_after_rewin(self):
        """A re-elected leader must not be torn down by a delayed nack from
        the leadership it just superseded — even before its first propose()
        stamps the new epoch (the promise() at victory sets the floor)."""
        async def go():
            make, _sent = self._paxos_pair()
            leader = make(0)
            await leader.propose(b"old", {0, 1, 2}, epoch=2)
            assert leader.handle_nack(4)  # genuinely deposed by epoch 4
            # we re-elect and win at epoch 6; promise() precedes propose()
            assert leader.promise(6)
            assert not leader.handle_nack(4), "stale nack must be ignored"
            await leader.propose(b"new", {0, 1, 2}, epoch=6)
            assert leader.handle_accept(1, leader.proposing[0], epoch=6)
            # a genuine newer deposition still lands
            assert leader.handle_nack(8)

        run(go())

    def test_divergent_concurrent_commit_is_impossible(self):
        async def go():
            from ceph_tpu.rados.paxos import Paxos

            # one shared peon, two would-be leaders — the advisor scenario
            wires = []

            def mk(rank):
                async def send(peer, payload):
                    wires.append((rank, peer, payload))
                return Paxos(MonitorDBStore(), rank, send)

            old_leader, new_leader, peon = mk(0), mk(1), mk(2)
            # new leader collected at epoch 6; old leader stuck at 4
            peon.promise(6)
            await old_leader.propose(b"A", {0, 2}, epoch=4)
            await new_leader.propose(b"B", {1, 2}, epoch=6)
            # deliver both begins to the shared peon
            for frm, _to, p in list(wires):
                if p["op"] == "begin":
                    await peon.handle_begin(frm, p["version"], p["value"],
                                            p["epoch"])
            # peon acked exactly ONE of them (the epoch-6 proposal)
            accepts = [(f, t, p) for f, t, p in wires if p["op"] == "accept"]
            nacks = [(f, t, p) for f, t, p in wires if p["op"] == "nack"]
            assert len(accepts) == 1 and accepts[0][1] == 1
            assert len(nacks) == 1 and nacks[0][1] == 0
            assert peon.pending[1] == b"B"

        run(go())


class TestMonitorDBStore:
    def test_commit_persist_recover(self, tmp_path):
        path = str(tmp_path / "store.db")
        s = MonitorDBStore(path)
        s.commit(1, b"v1")
        s.commit(2, b"v2")
        s2 = MonitorDBStore(path)
        assert s2.latest() == (2, b"v2")
        assert s2.get(1) == b"v1"

    def test_trim(self, tmp_path):
        s = MonitorDBStore(None, keep_versions=5)
        for v in range(1, 20):
            s.commit(v, b"x%d" % v)
        assert s.get(1) is None
        assert s.get(19) is not None
        assert s.last_committed - s.first_committed < 5


# -- daemon-level ------------------------------------------------------------


class TestMonQuorum:
    def test_three_mons_form_quorum(self):
        async def go():
            cluster = Cluster(n_osds=3, conf=dict(FAST), n_mons=3)
            await cluster.start()
            try:
                leaders = [m for m in cluster.mons if m.is_leader]
                assert len(leaders) == 1
                assert leaders[0].rank == 0  # lowest rank wins
                status = leaders[0].quorum_status()
                assert len(status["quorum"]) >= 2
            finally:
                await cluster.stop()

        run(go())

    def test_start_waits_until_the_quorum_follows_its_leader(self):
        """A leader that has only declared itself is not a quorum: the
        victory has to have reached the peers before the OSDs' boot holds
        the loop (vstart.wait_for_quorum; PERF.md section 6, PR 41)."""
        class _Mon:
            def __init__(self, rank):
                self.rank, self.logic = rank, ElectionLogic(rank, 3)
                self.is_leader = False

        async def go():
            cluster = Cluster(n_osds=0, conf=dict(FAST), n_mons=3)
            cluster.mons = mons = [_Mon(r) for r in range(3)]
            waiter = asyncio.ensure_future(cluster.wait_for_quorum(5.0))
            mons[0].logic.leader, mons[0].logic.quorum = 0, {0, 1}
            mons[0].is_leader = True  # declared; victory on its way
            await asyncio.sleep(0.2)
            assert not waiter.done()
            mons[1].logic.receive_victory(0, mons[0].logic.epoch, {0, 1})
            # mon.2 acked too late to be in the quorum: nobody waits for it
            await asyncio.wait_for(waiter, 1.0)
            with pytest.raises(TimeoutError):
                mons[0].is_leader = False
                await cluster.wait_for_quorum(0.2)

        run(go())

    def test_write_through_peon_is_forwarded(self):
        async def go():
            cluster = Cluster(n_osds=4, conf=dict(FAST), n_mons=3)
            await cluster.start()
            try:
                c = await cluster.client()
                # aim the client at a PEON: forwarding must reach the leader
                from ceph_tpu.rados.monclient import MonTargets

                peon = next(m for m in cluster.mons if not m.is_leader)
                c.mons = MonTargets(peon.addr)
                pool = await c.create_pool("fwd", profile={
                    "plugin": "jerasure", "technique": "reed_sol_van",
                    "k": "2", "m": "1"})
                await c.put(pool, "obj", b"forwarded-write" * 100)
                assert await c.get(pool, "obj") == b"forwarded-write" * 100
                # the pool exists on every mon (replicated state) —
                # DEADLINE-polled, not a fixed sleep: paxos round latency
                # under host load is unbounded, replication is not
                for _ in range(200):
                    if all(m.osdmap.pool_by_name("fwd") is not None
                           for m in cluster.mons):
                        break
                    await asyncio.sleep(0.05)
                for m in cluster.mons:
                    assert m.osdmap.pool_by_name("fwd") is not None
                await c.stop()
            finally:
                await cluster.stop()

        run(go())

    def test_leader_failover(self):
        async def go():
            cluster = Cluster(n_osds=4, conf=dict(FAST), n_mons=3)
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("p1", profile={
                    "plugin": "jerasure", "technique": "reed_sol_van",
                    "k": "2", "m": "1"})
                await c.put(pool, "before", b"pre-failover data")
                old_leader = next(m for m in cluster.mons if m.is_leader)
                await cluster.kill_mon(old_leader.rank)
                # a new leader must emerge among survivors
                survivors = [m for m in cluster.mons if m.rank != old_leader.rank]
                for _ in range(100):
                    if any(m.is_leader for m in survivors):
                        break
                    await asyncio.sleep(0.1)
                new_leader = next(m for m in survivors if m.is_leader)
                assert new_leader.rank != old_leader.rank
                # replicated state survived: old pool visible, new writes work
                assert new_leader.osdmap.pool_by_name("p1") is not None
                pool2 = await c.create_pool("p2", profile={
                    "plugin": "jerasure", "technique": "reed_sol_van",
                    "k": "2", "m": "1"})
                await c.put(pool2, "after", b"post-failover data")
                assert await c.get(pool, "before") == b"pre-failover data"
                assert await c.get(pool2, "after") == b"post-failover data"
                await c.stop()
            finally:
                await cluster.stop()

        run(go())

    def test_no_quorum_blocks_writes(self):
        async def go():
            cluster = Cluster(n_osds=2, conf=dict(FAST), n_mons=3)
            await cluster.start()
            try:
                c = await cluster.client()
                # kill two mons: 1 of 3 left, no majority possible
                ranks = [m.rank for m in cluster.mons]
                await cluster.kill_mon(ranks[0])
                await cluster.kill_mon(ranks[1])
                await asyncio.sleep(2.5 * FAST["mon_lease"])
                survivor = cluster.mons[0]
                assert not survivor.is_leader
                with pytest.raises(Exception):
                    await asyncio.wait_for(c.create_pool("nope"), timeout=8)
                await c.stop()
            finally:
                await cluster.stop()

        run(go())


class TestMonRejoin:
    def test_restarted_mon_rejoins_and_syncs(self):
        async def go():
            cluster = Cluster(n_osds=3, conf=dict(FAST), n_mons=3)
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("pre", profile={
                    "plugin": "jerasure", "technique": "reed_sol_van",
                    "k": "2", "m": "1"})
                monmap = list(cluster.mons[0].monmap)
                await cluster.kill_mon(2)
                await c.put(pool, "while-down", b"written at 2/3 mons")
                pool2 = await c.create_pool("during", profile={
                    "plugin": "jerasure", "technique": "reed_sol_van",
                    "k": "2", "m": "1"})
                # rank 2 comes back with an empty store and a stale epoch
                from ceph_tpu.rados.mon import Monitor

                mon2 = Monitor(dict(FAST), rank=2, monmap=monmap)
                await mon2.start()
                cluster.mons.append(mon2)
                for _ in range(300):  # generous: suite load slows elections
                    if mon2.logic.in_quorum and \
                            mon2.osdmap.pool_by_name("during") is not None:
                        break
                    await asyncio.sleep(0.1)
                assert mon2.logic.in_quorum, mon2.quorum_status()
                # synced the state it missed
                assert mon2.osdmap.pool_by_name("pre") is not None
                assert mon2.osdmap.pool_by_name("during") is not None
                # and the full quorum keeps serving writes
                pool3 = await c.create_pool("after", profile={
                    "plugin": "jerasure", "technique": "reed_sol_van",
                    "k": "2", "m": "1"})
                await c.put(pool3, "x", b"post-rejoin")
                assert await c.get(pool3, "x") == b"post-rejoin"
                await c.stop()
            finally:
                await cluster.stop()

        run(go())


class TestConfigMonitor:
    def test_config_set_replicates_and_distributes(self):
        async def go():
            cluster = Cluster(n_osds=2, conf=dict(FAST), n_mons=3)
            await cluster.start()
            try:
                c = await cluster.client()
                await c.config_set("osd_scrub_auto", "true")
                await c.config_set("debug_osd", "5")
                got = await c.config_get()
                assert got["osd_scrub_auto"] == "true"
                # replicated to every mon (deadline-polled, not a
                # fixed sleep: paxos latency under load is unbounded)
                for _ in range(200):
                    if all(m.cluster_conf.get("debug_osd") == "5"
                           for m in cluster.mons):
                        break
                    await asyncio.sleep(0.05)
                for m in cluster.mons:
                    assert m.cluster_conf.get("debug_osd") == "5"
                # a NEW osd boots with the centralized config applied
                osd = await cluster.add_osd()
                assert osd.conf.get("debug_osd") == "5"
                await c.stop()
            finally:
                await cluster.stop()

        run(go())

    def test_profile_less_ec_pool_rides_the_default_profile(self):
        """Regression pin for a lint dead-option finding: the schema
        declared osd_pool_default_erasure_code_profile but pool creation
        never consumed it — a profile-less `osd pool create NAME
        erasure` silently fell back to the codec's own k=2 m=1 defaults.
        The mon must seed an empty EC profile from the option (reference
        OSDMonitor default-profile semantics)."""
        async def go():
            conf = dict(FAST)
            # k=3 m=2 is NOT the jerasure codec's own default (k=2 m=1),
            # so the assertion below can only pass via the option
            conf["osd_pool_default_erasure_code_profile"] = (
                "plugin=jerasure technique=reed_sol_van k=3 m=2")
            cluster = Cluster(n_osds=5, conf=conf, n_mons=1)
            await cluster.start()
            try:
                c = await cluster.client()
                pool = await c.create_pool("defprof")  # no profile arg
                info = cluster.mons[0].osdmap.pools[pool]
                assert info.profile.get("plugin") == "jerasure"
                assert info.profile.get("k") == "3"
                assert info.profile.get("m") == "2"
                assert info.size == 5
                await c.put(pool, "obj", b"default-profile bytes" * 64)
                assert await c.get(pool, "obj") \
                    == b"default-profile bytes" * 64
                await c.stop()
            finally:
                await cluster.stop()

        run(go())


class TestMonStoreRecovery:
    def test_single_mon_restart_recovers_state(self, tmp_path):
        async def go():
            path = str(tmp_path)
            conf = dict(FAST)
            cluster = Cluster(n_osds=3, conf=conf, n_mons=1, data_dir=path)
            await cluster.start()
            c = await cluster.client()
            pool = await c.create_pool("durable", profile={
                "plugin": "jerasure", "technique": "reed_sol_van",
                "k": "2", "m": "1"})
            await c.config_set("debug_ec", "3")
            await c.stop()
            await cluster.stop()
            assert os.path.exists(f"{path}/mon.0/store.db")
            # new mon process, same store: state must come back
            from ceph_tpu.rados.mon import Monitor

            mon2 = Monitor(conf, data_path=f"{path}/mon.0/store.db")
            await mon2.start()
            try:
                assert mon2.osdmap.pool_by_name("durable") is not None
                assert mon2.cluster_conf.get("debug_ec") == "3"
                assert mon2.osdmap.pools[pool].profile.get("plugin") == "jerasure"
            finally:
                await mon2.stop()

        run(go())


class TestConnectivityElections:
    def test_beats_prefers_score_then_rank(self):
        from ceph_tpu.rados.paxos import ElectionLogic

        logic = ElectionLogic(rank=1, n_mons=3)
        logic.score = 0.5
        # meaningfully better-connected higher rank wins
        assert logic._beats(0.9, 2)
        # same QUANTIZED bucket falls back to rank (quantization keeps
        # the ordering transitive, unlike a pairwise margin)
        assert logic._beats(0.45, 0)
        assert not logic._beats(0.45, 2)
        # meaningfully worse loses even with lower rank
        assert not logic._beats(0.1, 0)
        # unreported score (old peer): pure rank
        assert logic._beats(-1.0, 0)
        assert not logic._beats(-1.0, 2)
        # transitivity: bucketed comparison is a total preorder
        b = ElectionLogic._bucket
        for a_, b_, c_ in [(0.50, 0.59, 0.68), (0.1, 0.19, 0.95)]:
            assert not (b(a_) >= b(b_) and b(b_) >= b(c_)
                        and b(c_) > b(a_))

    def test_poorly_connected_mon_loses_leadership(self):
        """A mon that cannot reach its peers must stop winning elections
        (reference CONNECTIVITY election strategy, ConnectionTracker.h:80):
        rank 0 gets a degraded network; after re-election a better
        connected mon leads."""
        async def go():
            cluster = Cluster(n_osds=3, conf=dict(FAST), n_mons=3)
            await cluster.start()
            try:
                mon0 = next(m for m in cluster.mons if m.rank == 0)
                assert mon0.is_leader  # rank tiebreak on equal scores
                # degrade mon0's connectivity measurements (the tracker
                # would converge here after repeated send failures); pin
                # the tracker so the healthy test network cannot heal the
                # simulated lossy one mid-election
                mon0._conn_scores = {1: 0.1, 2: 0.1}
                mon0._track_peer = lambda *a, **k: None
                # force a REAL re-election (a standing quorum makes
                # _run_election a no-op): drop everyone out of quorum
                # first, as a lease lapse would
                for m in cluster.mons:
                    m.logic.electing = True
                    m.logic.leader = None
                    m.logic.quorum = set()
                for m in cluster.mons:
                    m._spawn_election()
                for _ in range(100):
                    leaders = [m.rank for m in cluster.mons if m.is_leader]
                    if leaders and leaders[0] != 0:
                        break
                    await asyncio.sleep(0.1)
                leaders = [m.rank for m in cluster.mons if m.is_leader]
                assert leaders and leaders[0] != 0, \
                    f"poorly-connected mon kept leadership: {leaders}"
                # the cluster still serves writes under the new leader
                c = await cluster.client()
                pool = await c.create_pool("ce", profile={
                    "plugin": "jerasure", "technique": "reed_sol_van",
                    "k": "2", "m": "1"})
                await c.put(pool, "o", b"elected")
                assert await c.get(pool, "o") == b"elected"
                await c.stop()
            finally:
                await cluster.stop()

        run(go())
