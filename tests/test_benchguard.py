"""Bench regression gate (tools/benchguard.py): floors fit from the
trajectory must fail a synthetic regression, pass a recorded
BENCH_r*.json history, and degrade to a schema check on smoke artifacts.
"""
import json

import pytest

from corda_tpu.tools import benchguard


def _artifact(**over):
    """A minimal full-run artifact satisfying the required-field schema."""
    base = {
        "metric": "ecdsa_secp256k1_verifies_per_sec_per_chip",
        "value": 100.0, "unit": "verifies/s", "vs_baseline": 10.0,
        "ed25519_verifies_per_sec_per_chip": 1000.0,
        "secp256r1_verifies_per_sec_per_chip": 50.0,
        "service_path_verifies_per_sec": 200.0,
        "ed25519_service_path_verifies_per_sec": 400.0,
        "secp256r1_service_path_verifies_per_sec": 80.0,
        "mixed_service_path_verifies_per_sec": 150.0,
        "tx_verify_p50_ms_batch1": 1.0,
        "tx_verify_p50_ms_batch1k": 20.0,
        "tx_verify_p90_ms_batch1k": 30.0,
        "tx_verify_p99_ms_batch1k": 45.0,
        "service_to_kernel_ratio_k1": 0.8,
        "service_to_kernel_ratio_ed25519": 0.7,
        "service_to_kernel_ratio_r1": 0.75,
        "post_warmup_compiles": 0,
        "bucket_ladder": [256, 512, 1024],
        "compile_s_total": 5.0, "compile_cache_hits": 7,
        "occupancy_pct_per_scheme": {"ed25519": 90.0},
        "prep_overlap_pct": 40.0,
    }
    base.update(over)
    return base


def test_synthetic_regressing_trajectory_fails():
    trajectory = [_artifact(), _artifact(value=120.0)]
    guards = benchguard.fit_guards(trajectory)
    # best=120, floor=120*0.85=102 — a drop to 90 must trip the gate
    regressed = _artifact(value=90.0)
    problems = benchguard.check(regressed, guards)
    assert problems, "regression not caught"
    assert any("value: 90" in p and "floor" in p for p in problems)


def test_latency_regression_fails_against_ceiling():
    guards = benchguard.fit_guards([_artifact(tx_verify_p50_ms_batch1=1.0)])
    slow = _artifact(tx_verify_p50_ms_batch1=1.5)   # ceiling = 1.35
    problems = benchguard.check(slow, guards)
    assert any("tx_verify_p50_ms_batch1" in p and "ceiling" in p
               for p in problems)


def test_within_tolerance_passes():
    guards = benchguard.fit_guards([_artifact(value=100.0)])
    assert benchguard.check(_artifact(value=90.0), guards) == []


def test_smoke_artifact_gets_schema_check_only():
    guards = benchguard.fit_guards([_artifact(value=1000.0)])
    # values way below the floors, but smoke => schema-only
    smoke = _artifact(value=0.0, smoke=True)
    assert benchguard.check(smoke, guards) == []
    # ... and the schema check still bites on a missing field
    broken = dict(smoke)
    del broken["prep_overlap_pct"]
    problems = benchguard.check(broken, guards)
    assert any("prep_overlap_pct" in p for p in problems)


def test_schema_rejects_wrong_shapes():
    bad = _artifact(occupancy_pct_per_scheme=[1, 2],
                    compile_s_total="fast")
    problems = benchguard.schema_violations(bad)
    assert any("occupancy_pct_per_scheme" in p and "dict" in p
               for p in problems)
    assert any("compile_s_total" in p for p in problems)


def test_smoke_and_zero_rounds_do_not_drag_floors():
    trajectory = [
        _artifact(value=0.0, smoke=True),    # smoke round: skipped outright
        _artifact(value=0.0),                # dead metric: not a floor of 0
        _artifact(value=100.0),
    ]
    guards = benchguard.fit_guards(trajectory)
    assert guards["value"]["best"] == 100.0


@pytest.fixture
def trajectory_paths(tmp_path):
    """A three-round trajectory in the driver's artifact wrapping, written
    where default_trajectory_paths(root) finds it — the checkout keeps no
    BENCH_r*.json of its own."""
    paths = []
    for i, value in enumerate((100.0, 118.0, 112.0), start=1):
        p = tmp_path / f"BENCH_r{i:02d}.json"
        p.write_text(json.dumps(
            {"n": i, "rc": 0, "parsed": _artifact(value=value)}))
        paths.append(str(p))
    assert benchguard.default_trajectory_paths(str(tmp_path)) == paths
    return paths


def test_real_trajectory_passes_self_replay(trajectory_paths):
    """Every recorded round must clear the guards fit from the rounds
    before it."""
    trajectory = benchguard.load_trajectory(trajectory_paths)
    assert len(trajectory) == 3
    for i, run in enumerate(trajectory):
        guards = benchguard.fit_guards(trajectory[:i])
        value_problems = [p for p in benchguard.check(run, guards)
                          if "<" in p or ">" in p]
        assert value_problems == [], \
            f"round {trajectory_paths[i]}: {value_problems}"


def test_cli_replays_trajectory(capsys, monkeypatch, trajectory_paths):
    monkeypatch.setattr(benchguard, "default_trajectory_paths",
                        lambda root=None: trajectory_paths)
    assert benchguard.main([]) == 0
    assert "ok" in capsys.readouterr().out


def test_guard_current_with_explicit_paths(tmp_path):
    p = tmp_path / "BENCH_r01.json"
    p.write_text(json.dumps({"parsed": _artifact(value=200.0)}))
    problems = benchguard.guard_current(_artifact(value=100.0), [str(p)])
    assert any("value: 100" in x for x in problems)
    assert benchguard.guard_current(_artifact(value=190.0), [str(p)]) == []


# ---------------------------------------------------------------------------
# MULTICHIP (fleet) gate
# ---------------------------------------------------------------------------

def _fleet(**over):
    base = {
        "fleet_verifies_per_sec": 50000.0,
        "scaling_efficiency_pct": 92.0,
        "n_workers": 8, "n_devices": 8,
        "fleet_steals": 3, "fleet_stolen": 12,
        "worker_busy_skew_pct": 4.0, "steals_total": 3,
        "stitched_trace_depth": 4,
        "recovery_s": 0.0, "controller_actions": 0,
        "per_worker_sigs": {"w0": 4096, "w1": 4096},
    }
    base.update(over)
    return base


def test_multichip_tail_parsed_from_last_json_line():
    """The fleet stage prints its JSON LAST; earlier stdout lines (even
    JSON-looking ones without the fleet fields) must not win."""
    tail = ('some dry-run chatter\n{"not": "the fleet line"}\n'
            + json.dumps(_fleet(fleet_verifies_per_sec=1234.5)) + "\n")
    parsed = benchguard.parse_multichip_artifact(
        {"n_devices": 8, "rc": 0, "ok": True, "tail": tail})
    assert parsed is not None
    assert parsed["fleet_verifies_per_sec"] == 1234.5


def test_multichip_empty_tail_is_pre_fleet():
    assert benchguard.parse_multichip_artifact(
        {"n_devices": 8, "rc": 0, "ok": True, "tail": ""}) is None


def test_multichip_regression_fails_against_trajectory(tmp_path):
    p = tmp_path / "MULTICHIP_r06.json"
    p.write_text(json.dumps(
        {"n_devices": 8, "rc": 0, "ok": True,
         "tail": json.dumps(_fleet()) + "\n"}))
    # floors: 50000*0.85=42500 and 92*0.85=78.2
    bad_rate = benchguard.guard_multichip(
        _fleet(fleet_verifies_per_sec=40000.0), [str(p)])
    assert any("fleet_verifies_per_sec" in x and "floor" in x
               for x in bad_rate)
    bad_eff = benchguard.guard_multichip(
        _fleet(scaling_efficiency_pct=70.0), [str(p)])
    assert any("scaling_efficiency_pct" in x for x in bad_eff)
    assert benchguard.guard_multichip(_fleet(), [str(p)]) == []


def test_multichip_smoke_schema_only():
    smoke = _fleet(fleet_verifies_per_sec=3.0, smoke=True)
    assert benchguard.guard_multichip(smoke, []) == []
    broken = dict(smoke)
    del broken["scaling_efficiency_pct"]
    problems = benchguard.guard_multichip(broken, [])
    assert any("scaling_efficiency_pct" in p for p in problems)


def test_multichip_real_trajectory_accepts_historical_artifacts():
    """Pre-fleet rounds have empty tails: they contribute nothing to the
    guards and must not crash the fit."""
    paths = benchguard.multichip_trajectory_paths()
    if not paths:
        pytest.skip("no MULTICHIP_r*.json artifacts in this checkout")
    assert benchguard.guard_multichip(_fleet(), paths) == []


# ---------------------------------------------------------------------------
# LEDGER (end-to-end ledger scenario) gate


def _ledger(**over):
    base = {
        "metric": "committed_tx_per_sec", "value": 10.0, "unit": "tx/s",
        "committed_tx_per_sec": 10.0, "offered_tx_per_sec": 40.0,
        "parties": 24, "raft_replicas": 3,
        "ops_total": 240, "ops_committed": 230, "ops_failed": 10,
        "notarised_tx_count": 158, "duration_s": 24.5,
        "e2e_ms_p50": 8300.0, "e2e_ms_p90": 15000.0, "e2e_ms_p99": 18000.0,
        "ledger_stage_flow_run_ms_p99": 500.0,
        "ledger_stage_tx_verify_ms_p99": 20.0,
        "ledger_stage_notary_uniqueness_ms_p99": 100.0,
        "ledger_stage_raft_commit_ms_p99": 90.0,
        "ledger_stage_vault_update_ms_p99": 5.0,
        "notary_uniqueness_p99_ms": 100.0,
        "slo_error_budget_pct": 0.0,
        "chaos_enabled": True, "chaos_windows": [],
        "exactly_once_ok": True, "replicas_agree": True,
        "stitched_traces": 183,
        # group-commit pipeline fields (ISSUE 11)
        "committed_tx_count": 810, "self_issue_tx_count": 144,
        "notarised_input_tx_count": 522, "counter_invariant_ok": True,
        "node_concurrency": 4, "max_concurrent_flows_per_node": 4,
        "flows_launched": 810,
        "commit_batch_occupancy_mean": 4.76,
        "commit_batch_occupancy_p99": 22.0,
        "ledger_commit_batch_count": 140, "group_commit_raft_appends": 140,
        "group_commit_committed": 666, "group_commit_rejected": 0,
        "group_commit_prescreened": 0, "group_commit_deferred": 0,
        "raft_appends_per_committed_tx": 0.21,
        "e2e_ms_p50_issue": 100.0, "e2e_ms_p90_issue": 200.0,
        "e2e_ms_p99_issue": 300.0,
        "e2e_ms_p50_pay": 400.0, "e2e_ms_p90_pay": 800.0,
        "e2e_ms_p99_pay": 1200.0,
        "e2e_ms_p50_settle": 500.0, "e2e_ms_p90_settle": 1000.0,
        "e2e_ms_p99_settle": 1500.0,
        "flow_ms_p50_issue": 50.0, "flow_ms_p90_issue": 90.0,
        "flow_ms_p99_issue": 120.0,
        "flow_ms_p50_pay": 200.0, "flow_ms_p90_pay": 400.0,
        "flow_ms_p99_pay": 600.0,
        "flow_ms_p50_settle": 250.0, "flow_ms_p90_settle": 500.0,
        "flow_ms_p99_settle": 700.0,
        # tail-forensics critical-path fields (ISSUE 14): each p50 blame
        # vector sums exactly to its class's critpath e2e (conservation)
        "ledger_critpath_traces": 183,
        "ledger_critpath_top": [],
        "ledger_critpath_blame_p50_issue": {"flow.compute": 60.0,
                                            "raft.commit": 40.0},
        "ledger_critpath_blame_p99_issue": {"raft.commit": 300.0},
        "ledger_critpath_e2e_p50_ms_issue": 100.0,
        "ledger_critpath_dominant_issue": "flow.compute",
        "ledger_critpath_blame_p50_pay": {"scheduler.wait": 250.0,
                                          "notary.batch_wait": 150.0},
        "ledger_critpath_blame_p99_pay": {"scheduler.wait": 1200.0},
        "ledger_critpath_e2e_p50_ms_pay": 400.0,
        "ledger_critpath_dominant_pay": "scheduler.wait",
        "ledger_critpath_blame_p50_settle": {"notary.batch_wait": 500.0},
        "ledger_critpath_blame_p99_settle": {"notary.batch_wait": 1500.0},
        "ledger_critpath_e2e_p50_ms_settle": 500.0,
        "ledger_critpath_dominant_settle": "notary.batch_wait",
        # sharded-notary fields (ISSUE 15)
        "ledger_shard_count": 2,
        "ledger_shard_commit_counts": {"s0": 340, "s1": 326},
        "ledger_shard_cross_committed": 60,
        "ledger_shard_cross_aborted": 2,
        "ledger_shard_cross_recovered": 0,
        "ledger_shard_reserved_leftover": 0,
        "ledger_shard_recovered_in_doubt": 0,
        "ledger_shard_finalize_conflicts": 0,
        "cross_shard_abort_rate": 0.032,
        "cross_shard_pct": 0.15,
        # consensus-observatory fields (ISSUE 16): raft commit attribution
        # telescopes — append_wait+fsync+replicate+apply p50s sum to the
        # attribution-sum p50, which matches the measured round p50
        "ledger_raft_append_wait_ms_p50": 0.4,
        "ledger_raft_append_wait_ms_p99": 2.0,
        "ledger_raft_fsync_ms_p50": 1.1, "ledger_raft_fsync_ms_p99": 4.0,
        "ledger_raft_replicate_ms_p50": 6.0,
        "ledger_raft_replicate_ms_p99": 30.0,
        "ledger_raft_apply_ms_p50": 0.5, "ledger_raft_apply_ms_p99": 2.0,
        "ledger_raft_attrib_samples": 140,
        "ledger_raft_attrib_sum_ms_p50": 8.0,
        "ledger_raft_round_ms_p50": 8.3,
        "ledger_raft_elections_total": 2,
        "ledger_raft_pump_busy_frac": 0.12,
        "ledger_shard_skew_index": 1.05,
        "ledger_coordinator_log_bytes": 4096,
        "ledger_timeseries_resolutions": 3,
        "ledger_growth_warnings": 0,
        # bounded-state consensus fields (ISSUE 20): snapshot compaction,
        # InstallSnapshot catch-up, restart recovery, CoordinatorLog GC
        "ledger_raft_snapshot_index": 180,
        "ledger_raft_snapshots_taken": 4,
        "ledger_raft_installs_sent": 1,
        "ledger_raft_installs_received": 1,
        "ledger_raft_snapshot_bytes": 8192,
        "ledger_raft_snapshot_threshold": 192,
        "ledger_raft_log_entries_peak": 210,
        "ledger_raft_restarts": 1,
        "ledger_growth_compactions": 4,
        "ledger_coordinator_compactions": 1,
        "host_cpus": 8,
    }
    base.update(over)
    return base


def test_ledger_schema_locks_every_required_field():
    assert benchguard.ledger_schema_violations(_ledger()) == []
    for field in benchguard.LEDGER_REQUIRED:
        broken = _ledger()
        del broken[field]
        assert benchguard.ledger_schema_violations(broken), field


def test_ledger_schema_rejects_wrong_shapes():
    bad = _ledger(exactly_once_ok="yes", chaos_windows="none",
                  committed_tx_per_sec="fast")
    problems = benchguard.ledger_schema_violations(bad)
    assert len(problems) == 3


def test_ledger_regression_fails_against_trajectory(tmp_path):
    good = tmp_path / "LEDGER_r01.json"
    good.write_text(json.dumps(_ledger(committed_tx_per_sec=10.0)))
    # throughput collapse breaches the floor
    slow = _ledger(committed_tx_per_sec=10.0 * (1 - 0.16))
    problems = benchguard.guard_ledger(slow, [str(good)])
    assert any("committed_tx_per_sec" in p for p in problems)
    # uniqueness-tail blowup breaches the ceiling (tolerance 6.0 → 7x
    # best — one straddled re-election is a coin flip, not a regression;
    # see the LEDGER_GUARDED comment and the r04/r05/r06 rolls)
    tail = _ledger(notary_uniqueness_p99_ms=100.0 * 7.1)
    problems = benchguard.guard_ledger(tail, [str(good)])
    assert any("notary_uniqueness_p99_ms" in p for p in problems)
    # within tolerance passes
    assert benchguard.guard_ledger(
        _ledger(committed_tx_per_sec=9.0), [str(good)]) == []


def test_ledger_group_commit_guards(tmp_path):
    """The amortization locks: appends-per-tx sliding back toward 1.0
    (re-serialization) breaches its ceiling; an occupancy collapse
    breaches its floor; a per-class p99 blowup names its class."""
    good = tmp_path / "LEDGER_r01.json"
    good.write_text(json.dumps(_ledger()))
    problems = benchguard.guard_ledger(
        _ledger(raft_appends_per_committed_tx=0.21 * 1.6), [str(good)])
    assert any("raft_appends_per_committed_tx" in p for p in problems)
    problems = benchguard.guard_ledger(
        _ledger(commit_batch_occupancy_mean=4.76 * (1 - 0.16)), [str(good)])
    assert any("commit_batch_occupancy_mean" in p for p in problems)
    # class tails carry a metric-specific 2.0 tolerance (chaos-straddle
    # survivorship — see LEDGER_GUARDED): breach needs more than 3x best
    problems = benchguard.guard_ledger(
        _ledger(e2e_ms_p99_settle=1500.0 * 3.1), [str(good)])
    assert any("e2e_ms_p99_settle" in p for p in problems)
    assert benchguard.guard_ledger(
        _ledger(e2e_ms_p99_settle=1500.0 * 2.9), [str(good)]) == []
    # within tolerance passes clean
    assert benchguard.guard_ledger(
        _ledger(raft_appends_per_committed_tx=0.25,
                commit_batch_occupancy_mean=4.2), [str(good)]) == []


def test_ledger_smoke_gets_schema_check_only(tmp_path):
    fast = tmp_path / "LEDGER_r01.json"
    fast.write_text(json.dumps(_ledger(committed_tx_per_sec=1000.0)))
    smoke = _ledger(committed_tx_per_sec=0.5, smoke=True)
    assert benchguard.guard_ledger(smoke, [str(fast)]) == []


def test_ledger_floors_fit_within_host_class_only(tmp_path):
    """Floors recorded on a bigger box are not held against a smaller
    one: trajectory rounds with a different host_cpus contribute no
    floors, same-class rounds do, and rounds predating the field (both
    sides absent) keep guarding each other."""
    big = tmp_path / "LEDGER_r01.json"
    big.write_text(json.dumps(_ledger(committed_tx_per_sec=100.0,
                                      host_cpus=64)))
    # a 64-core round sets no floor for an 8-core run
    assert benchguard.guard_ledger(
        _ledger(committed_tx_per_sec=10.0), [str(big)]) == []
    # a same-class round still does
    peer = tmp_path / "LEDGER_r02.json"
    peer.write_text(json.dumps(_ledger(committed_tx_per_sec=20.0)))
    problems = benchguard.guard_ledger(
        _ledger(committed_tx_per_sec=10.0), [str(big), str(peer)])
    assert any("committed_tx_per_sec" in p for p in problems)
    # pre-field rounds (no host_cpus on either side) stay comparable
    legacy = _ledger(committed_tx_per_sec=20.0)
    legacy.pop("host_cpus")
    old = tmp_path / "LEDGER_r03.json"
    old.write_text(json.dumps(legacy))
    cur = _ledger(committed_tx_per_sec=10.0)
    cur.pop("host_cpus")
    problems = benchguard.guard_ledger(cur, [str(old)])
    assert any("committed_tx_per_sec" in p for p in problems)


def test_ledger_critpath_blame_conservation_probe(tmp_path):
    # the helper's vectors sum exactly to their e2e: clean
    assert benchguard.ledger_critpath_violations(_ledger()) == []
    # a vector that lost 20% of its e2e (dropped spans) is INVALID
    broken = _ledger(
        ledger_critpath_blame_p50_pay={"scheduler.wait": 320.0})
    problems = benchguard.ledger_critpath_violations(broken)
    assert len(problems) == 1 and "pay" in problems[0]
    # an empty class (never ran in this round) is skipped, not a breach
    assert benchguard.ledger_critpath_violations(
        _ledger(ledger_critpath_blame_p50_settle={},
                ledger_critpath_e2e_p50_ms_settle=0.0)) == []
    # non-smoke guard_ledger enforces it; smoke stays schema-only
    good = tmp_path / "LEDGER_r01.json"
    good.write_text(json.dumps(_ledger()))
    problems = benchguard.guard_ledger(broken, [str(good)])
    assert any("lost spans" in p for p in problems)
    assert benchguard.guard_ledger(dict(broken, smoke=True),
                                   [str(good)]) == []


def test_ledger_real_artifact_passes_self_replay():
    paths = benchguard.ledger_trajectory_paths()
    if not paths:
        pytest.skip("no LEDGER_r*.json artifacts in this checkout")
    with open(sorted(paths)[-1], encoding="utf-8") as f:
        latest = json.load(f)
    assert benchguard.guard_ledger(latest, paths) == []


# ---------------------------------------------------------------------------
# SHARD-SCALING gate


def _sweep_point(shards, rate, **over):
    base = {
        "shards": shards, "committed_tx_per_sec": rate,
        "exactly_once_ok": True, "replicas_agree": True,
        "reserved_leftover": 0,
        "cross_shard_committed": 0 if shards == 1 else 12,
        "cross_shard_aborted": 0 if shards == 1 else 1,
    }
    base.update(over)
    return base


def _sharded(**over):
    points = [_sweep_point(1, 700.0), _sweep_point(2, 1300.0),
              _sweep_point(4, 2300.0)]
    base = _ledger(
        shard_sweep=points,
        committed_tx_per_sec_shards_1=700.0,
        committed_tx_per_sec_shards_2=1300.0,
        committed_tx_per_sec_shards_4=2300.0,
        shard_scaling_x=2300.0 / 700.0,
        shard_scaling_efficiency_pct=100.0 * (2300.0 / 700.0) / 4,
        shard_sweep_abort_rate=0.032,
        shard_sweep_skew_index=1.05,
        shard_sweep_ok=True)
    base.update(over)
    return base


def test_shard_guard_schema_and_hard_invariants():
    assert benchguard.guard_shards(_sharded(), []) == []
    # every required scaling field is locked in
    for field in benchguard.SHARD_REQUIRED:
        broken = _sharded()
        del broken[field]
        assert benchguard.guard_shards(broken, []), field
    # safety invariants are HARD — smoke does not excuse them
    bad = _sharded(smoke=True)
    bad["shard_sweep"] = [_sweep_point(1, 700.0),
                          _sweep_point(2, 1300.0, exactly_once_ok=False)]
    assert any("exactly_once_ok" in p
               for p in benchguard.guard_shards(bad, []))
    leak = _sharded(smoke=True)
    leak["shard_sweep"][2]["reserved_leftover"] = 3
    assert any("reserved_leftover" in p
               for p in benchguard.guard_shards(leak, []))
    # a multi-shard sweep that never committed cross-shard is a breach
    no_cross = _sharded(smoke=True)
    for p in no_cross["shard_sweep"]:
        p["cross_shard_committed"] = 0
    assert any("cross-shard" in p
               for p in benchguard.guard_shards(no_cross, []))


def test_shard_guard_locks_scaling_floors(tmp_path):
    good = tmp_path / "LEDGER_r04.json"
    good.write_text(json.dumps(_sharded()))
    # scaling efficiency collapse breaches its floor (the whole curve
    # uses SWEEP_RATE_TOLERANCE=0.45 — see benchguard)
    worse = _sharded(shard_scaling_efficiency_pct=
                     100.0 * (2300.0 / 700.0) / 4 * (1 - 0.46))
    assert any("shard_scaling_efficiency_pct" in p
               for p in benchguard.guard_shards(worse, [str(good)]))
    # a per-shard-count committed-rate collapse names its count (the
    # sweep rates use SWEEP_RATE_TOLERANCE=0.45 — the measured 4-shard
    # noise band spans 544.9–361.6 tx/s across r04–r06; see benchguard)
    slow4 = _sharded(committed_tx_per_sec_shards_4=2300.0 * (1 - 0.46))
    assert any("committed_tx_per_sec_shards_4" in p
               for p in benchguard.guard_shards(slow4, [str(good)]))
    assert benchguard.guard_shards(
        _sharded(committed_tx_per_sec_shards_4=2300.0 * (1 - 0.44)),
        [str(good)]) == []
    # sweep abort-rate blowup breaches the ceiling (tail tolerance 0.5);
    # the guarded field is the SWEEP aggregate, not the flows scenario's
    # cross_shard_abort_rate (a different workload sharing the artifact)
    aborts = _sharded(shard_sweep_abort_rate=0.032 * 1.6)
    assert any("shard_sweep_abort_rate" in p
               for p in benchguard.guard_shards(aborts, [str(good)]))
    # within tolerance passes; smoke gets invariants only, no floors
    assert benchguard.guard_shards(
        _sharded(committed_tx_per_sec_shards_4=2100.0,
                 shard_scaling_x=3.0), [str(good)]) == []
    assert benchguard.guard_shards(
        _sharded(smoke=True, committed_tx_per_sec_shards_4=10.0),
        [str(good)]) == []
