"""Consensus observatory: per-entry commit attribution (the telescoping
property), election episodes, the pooled /debug/raft report, Raft.*
metric families (absent-never-zero native parity), growth watchdogs and
shard heat/skew."""
import logging

import pytest

from corda_tpu.consensus.raft import LEADER, RaftNode
from corda_tpu.consensus.raft_uniqueness import DistributedImmutableMap
from corda_tpu.consensus.raftcore import NATIVE_RAFT_AVAILABLE
from corda_tpu.consensus.sharded_uniqueness import CoordinatorLog, skew_index
from corda_tpu.core.contracts.structures import StateRef
from corda_tpu.core.crypto.secure_hash import SecureHash
from corda_tpu.network.inmemory import InMemoryMessagingNetwork
from corda_tpu.observability.consensus_obs import (
    ATTRIBUTION_COMPONENTS, GrowthWatch, install_raft_collector,
    pool_attribution, raft_report, sample_timeseries)
from corda_tpu.observability.timeseries import TimeSeriesStore
from corda_tpu.utils.metrics import MetricRegistry


def make_cluster(n=3):
    bus = InMemoryMessagingNetwork()
    names = [f"raft{i}" for i in range(n)]
    maps = [DistributedImmutableMap() for _ in range(n)]
    nodes = [RaftNode(name, list(names), bus.create_node(name),
                      maps[i].apply, seed=i)
             for i, name in enumerate(names)]
    return bus, nodes, maps


def pump(bus, nodes, ticks=10):
    for _ in range(ticks):
        for node in nodes:
            node.tick()
        bus.run_network()


def run_until_leader(bus, nodes, max_ticks=400):
    for _ in range(max_ticks):
        pump(bus, nodes, 1)
        leaders = [n for n in nodes if n.role == LEADER]
        if len(leaders) == 1:
            pump(bus, nodes, 5)
            return leaders[0]
    raise AssertionError("no leader elected")


def commit(leader, bus, nodes, tx, ref):
    fut = leader.submit(("put_all", [[tx], [ref], "obs-test"]))
    for _ in range(200):
        if fut.done():
            break
        pump(bus, nodes, 1)
    return fut.result(timeout=1)


def committed_cluster(n_commits=5):
    bus, nodes, _ = make_cluster(3)
    leader = run_until_leader(bus, nodes)
    for i in range(n_commits):
        ref = StateRef(SecureHash.sha256(b"obs%d" % i), 0)
        out = commit(leader, bus, nodes, f"tx{i}", ref)
        assert out["committed"] is True
    return bus, nodes, leader


def test_attribution_telescopes_to_total():
    """Per committed entry, append_wait + fsync + replicate + apply must
    sum exactly to the retained total — the contiguous-clock construction
    the bench conservation probe (sum vs measured round p50) leans on."""
    _, nodes, leader = committed_cluster()
    samples = leader.attribution_samples()
    assert samples["total"], "leader attributed no commits"
    n = len(samples["total"])
    for comp in ATTRIBUTION_COMPONENTS:
        assert len(samples[comp]) == n, comp
    for i in range(n):
        parts = sum(samples[comp][i] for comp in ATTRIBUTION_COMPONENTS)
        assert parts == pytest.approx(samples["total"][i], abs=1e-9)
        assert samples["total"][i] > 0


def test_forwarded_round_conserves_against_attribution():
    """A submit through a FOLLOWER forwards to the leader. The client's
    submit stamp rides the ClientRequest (forward hop → append_wait) and
    the leader's apply-end stamp rides the ClientResponse back (delivery
    hop cancels out of the round), so the leader's attributed total still
    equals the round the submitting node measures — the conservation
    probe broke 45% on full bench runs when post-election rounds forwarded
    and both hops went unattributed."""
    import time as _t

    bus, nodes, _ = make_cluster(3)
    leader = run_until_leader(bus, nodes)
    follower = next(n for n in nodes if n is not leader)
    before = len(leader.attribution_samples()["total"])

    ref = StateRef(SecureHash.sha256(b"fwd"), 0)
    t0 = _t.perf_counter()
    fut = follower.submit(("put_all", [["tx-fwd"], [ref], "obs-test"]))
    for _ in range(200):
        if fut.done():
            break
        pump(bus, nodes, 1)
    assert fut.result(timeout=1)["committed"] is True

    # the round resolves against the leader's apply-end stamp...
    resolved = fut.raft_resolved_perf
    assert isinstance(resolved, float) and resolved > t0
    samples = leader.attribution_samples()
    assert len(samples["total"]) == before + 1
    total = samples["total"][-1]
    # ...and the attributed total telescopes over the SAME interval: both
    # start at the client's submit stamp (t0 is taken a hair earlier on
    # this side of the submit() call) and end at apply-end
    round_s = resolved - t0
    assert total == pytest.approx(round_s, abs=1e-3)
    # the forward hop is real waiting and must land in append_wait, not
    # vanish: it spans at least the pump iteration that delivered it
    assert samples["append_wait"][-1] > 0


def test_stats_surface_and_election_episode():
    _, nodes, leader = committed_cluster(n_commits=2)
    stats = leader.stats()
    assert stats["impl"] == "python"
    assert stats["role"] == LEADER
    assert stats["elections_total"] >= 1
    episode = stats["elections"][0]
    assert episode["cause"] == "startup"       # term was 0 at candidacy
    assert episode["duration_s"] > 0
    # the startup election can win inside the first tick window
    assert episode["ticks"] >= 0
    assert stats["leader_tenure_s"] > 0
    assert stats["log_entries"] >= 2
    assert set(stats["peer_lag"]) == {n.node_id for n in nodes
                                      if n is not leader}
    attrib = stats["attribution"]
    for comp in ATTRIBUTION_COMPONENTS + ("total",):
        assert attrib[comp]["n"] >= 2
        assert attrib[comp]["p99_ms"] >= attrib[comp]["p50_ms"] >= 0
    # followers never attribute commits (clocks live on the submit node)
    follower = next(n for n in nodes if n is not leader)
    assert follower.stats()["attribution"] == {}


def test_raft_report_shape_and_pooling():
    _, nodes, leader = committed_cluster(n_commits=3)
    report = raft_report({"s0": nodes})
    group = report["groups"]["s0"]
    assert len(group["nodes"]) == 3
    assert group["leader"]["node"] == leader.node_id
    assert group["log_entries"] >= 3
    assert group["elections_total"] >= 1
    assert group["attribution"]["total"]["n"] >= 3
    assert "shards" not in report
    # pooling across replicas = union (followers contribute nothing here)
    pooled = pool_attribution(nodes)
    assert len(pooled["total"]) == len(
        leader.attribution_samples()["total"])


def test_raft_report_defensive():
    class Broken:
        def stats(self):
            raise RuntimeError("dead node")

    class NoSurface:
        pass

    report = raft_report({"g": [Broken(), NoSurface()]})
    group = report["groups"]["g"]
    assert group["nodes"] == [] and group["leader"] is None
    assert group["log_entries"] == 0 and group["elections_total"] == 0
    assert "attribution" not in group
    assert raft_report({}) == {"groups": {}}

    class BadShards:
        def heat_stats(self):
            raise RuntimeError("boom")

    assert raft_report({}, sharded=BadShards())["shards"] is None


def test_raft_collector_families_and_native_parity():
    """The Raft.* labeled families ride a registry snapshot; fields a
    node cannot attribute (the native core's stats carry no attribution
    or peer_lag) are ABSENT from the snapshot — never rendered as 0."""
    _, nodes, leader = committed_cluster(n_commits=2)

    class NativeLike:
        """stats() shaped like NativeRaftNode's: no attribution, no
        peer_lag, no election episode list."""

        def stats(self):
            return {"impl": "native", "node": "n0", "role": LEADER,
                    "term": 3, "leader_id": "n0", "commit_index": 9,
                    "log_entries": 9, "elections_total": 1,
                    "leader_tenure_s": 1.5, "leader_tenure_last_s": 0.0,
                    "pending_requests": 0}

    reg = MetricRegistry()
    install_raft_collector(
        reg, lambda: {"s0": nodes, "s1": [NativeLike()]})
    snap = reg.snapshot()
    for family in ("Raft.LogEntries", "Raft.Elections", "Raft.CommitIndex",
                   "Raft.Term", "Raft.LeaderTenureSeconds"):
        for label in ("s0", "s1"):
            assert f'{family}{{group="{label}"}}' in snap, (family, label)
    entries = snap['Raft.LogEntries{group="s0"}']
    # gauge_fn, not gauge: prometheus_text's gauge branch renders a max
    # sample that collector-emitted entries don't carry
    assert entries["type"] == "gauge_fn" and entries["value"] >= 2
    assert entries["labels"] == {"group": "s0"}
    # python leader attributes: fsync/replicate p99 + replication lag live
    assert 'Raft.FsyncP99Ms{group="s0"}' in snap
    assert 'Raft.ReplicateP99Ms{group="s0"}' in snap
    assert 'Raft.ReplLagMax{group="s0"}' in snap
    # native parity: the same fields are absent for s1, never zero
    assert 'Raft.FsyncP99Ms{group="s1"}' not in snap
    assert 'Raft.ReplicateP99Ms{group="s1"}' not in snap
    assert 'Raft.ReplLagMax{group="s1"}' not in snap


@pytest.mark.skipif(not NATIVE_RAFT_AVAILABLE,
                    reason="libraftcore.so not built")
def test_native_stats_absent_fields_parity():
    from corda_tpu.consensus.raftcore import NativeRaftNode
    bus = InMemoryMessagingNetwork()
    names = ["n0", "n1", "n2"]
    nodes = [NativeRaftNode(name, list(names), bus.create_node(name),
                            lambda e: None, seed=i)
             for i, name in enumerate(names)]
    run_until_leader(bus, nodes)
    for node in nodes:
        stats = node.stats()
        assert stats["impl"] == "native"
        # the core cannot attribute: the fields are absent, never 0
        for missing in ("attribution", "peer_lag", "elections"):
            assert missing not in stats
        for present in ("term", "commit_index", "log_entries",
                        "elections_total", "leader_tenure_s"):
            assert present in stats


def test_growth_watch_doubles(caplog):
    watch = GrowthWatch(logger=logging.getLogger(
        "test.consensus_obs.growth"), floor=100.0)
    caplog.set_level(logging.WARNING, "test.consensus_obs.growth")
    assert watch.observe("g", 50) is False        # under the floor
    assert watch.observe("g", 120) is False       # baseline
    assert watch.observe("g", 200) is False       # < 2× baseline
    assert watch.observe("g", 240) is True        # 2× → warn, re-arm @ 240
    assert watch.observe("g", 400) is False
    assert watch.observe("g", 480) is True        # 2× again (4× baseline)
    assert watch.warnings == 2
    # junk values never count or raise
    assert watch.observe("g", None) is False
    assert watch.observe("g", True) is False
    assert watch.observe_many({"g": 960, "h": 10}) == 1
    assert watch.warnings == 3
    # the doubling rides jlog as a WARNING event, not print/debug noise
    warned = [r for r in caplog.records
              if r.levelno == logging.WARNING
              and "consensus.growth.doubled" in r.getMessage()]
    assert len(warned) == 3


def test_sample_timeseries_records_and_flushes():
    _, nodes, leader = committed_cluster(n_commits=2)
    store = TimeSeriesStore(resolutions=((0.5, 16), (5.0, 16)))
    watch = GrowthWatch(floor=1.0)
    values = sample_timeseries(store, {"s0": nodes}, watch=watch, t=100.0)
    assert values['Raft.LogEntries{group="s0"}'] >= 2
    assert 'Raft.Elections{group="s0"}' in values
    sample_timeseries(store, {"s0": nodes}, watch=watch, t=101.0)
    store.flush()
    snap = store.snapshot()
    levels = snap["series"]['Raft.LogEntries{group="s0"}']
    assert sum(1 for lvl in levels if lvl["points"]) >= 2, \
        "flush must seal every resolution"


def test_sample_timeseries_with_resource_registry():
    """Satellite (ISSUE 19): the sampling tick takes the resource
    accounting plane — every registered structure lands as a
    ``Resource.*`` series and rides the SAME growth watchdog (doubling
    warnings for free), while the two historical hazards keep their
    exact jlog series names."""
    from corda_tpu.observability.resprof import ResourceRegistry

    _, nodes, leader = committed_cluster(n_commits=2)
    reg = ResourceRegistry()
    size = {"v": 200.0}
    reg.register("Some.Pool", lambda: size["v"], kind="bounded")
    store = TimeSeriesStore(resolutions=((0.5, 16),))
    watch = GrowthWatch(floor=1.0)
    values = sample_timeseries(store, {"s0": nodes}, watch=watch, t=100.0,
                               resources=reg)
    # byte-compat: the historical hazard series names are unchanged
    assert 'Raft.LogEntries{group="s0"}' in values
    assert values["Resource.Some.Pool"] == 200.0
    size["v"] = 500.0                             # ≥ 2× the armed baseline
    before = watch.warnings
    sample_timeseries(store, {"s0": nodes}, watch=watch, t=101.0,
                      resources=reg)
    assert watch.warnings == before + 1
    store.flush()
    assert "Resource.Some.Pool" in store.snapshot()["series"]

    # a registry whose sample() blows up loses only the Resource.* rows,
    # never the consensus gauges
    class Broken:
        def sample(self, **kw):
            raise RuntimeError("boom")

    values = sample_timeseries(store, {"s0": nodes}, t=102.0,
                               resources=Broken())
    assert 'Raft.LogEntries{group="s0"}' in values


def test_skew_index():
    assert skew_index([]) == 0.0
    assert skew_index([0, 0]) == 0.0
    assert skew_index([5, 5, 5]) == pytest.approx(1.0)
    assert skew_index([12, 0, 0]) == pytest.approx(3.0)
    assert skew_index([3, 1]) == pytest.approx(1.5)


def test_coordinator_log_bytes_counted_and_replayed(tmp_path):
    path = str(tmp_path / "decisions.log")
    ref = StateRef(SecureHash.sha256(b"xs"), 0)
    log = CoordinatorLog(path)
    assert log.bytes_appended == 0
    log.begin("tx1", {0: [ref], 1: [ref]})
    after_begin = log.bytes_appended
    assert after_begin > 0
    log.decide("tx1", "commit")
    log.complete("tx1")
    total = log.bytes_appended
    assert total > after_begin
    # replay reconstructs the byte count from the durable file
    replayed = CoordinatorLog(path)
    assert replayed.bytes_appended == total
    assert len(replayed) == 0                 # tx1 completed
    # an in-memory record still counts logical bytes (the soak gauge
    # must not read 0 just because durability is off)
    mem = CoordinatorLog()
    mem.begin("tx2", {0: [ref]})
    assert mem.bytes_appended > 0
