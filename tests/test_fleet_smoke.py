"""The in-process fleet wiring check for tier-1.

Two host-route workers behind the out-of-process queue's load-aware
router must both receive and complete work, every future must resolve
(``fleet_bench`` reads each result), and one trace must cross the process
seam. What a live fleet serves over HTTP is tests/test_traces_endpoint.py's.
"""
from corda_tpu.verifier.fleet import fleet_bench


def test_fleet_smoke_two_workers_share_the_run():
    out = fleet_bench(2, groups=24, group_size=16, use_device=False)
    assert out["n_workers"] == 2
    assert out["fleet_verifies_per_sec"] > 0
    assert 0 < out["scaling_efficiency_pct"] <= 100
    # the router dealt to BOTH workers and both did real work — a fleet
    # where one worker starves is the regression this test exists to catch
    sigs = out["per_worker_sigs"]
    assert len(sigs) == 2 and all(c > 0 for c in sigs.values()), sigs
    # timed groups + the warm-up group all landed somewhere
    assert sum(sigs.values()) == (out["groups"] + 1) * out["group_size"]
    # the observability plane saw the run: at least oop_submit →
    # device_dispatch crossed the process seam under one trace id
    assert out["stitched_trace_depth"] >= 2
    assert 0 <= out["worker_busy_skew_pct"] <= 100
    # a controller that acts on a healthy fleet is a regression
    assert out["controller_state"] == "steady"
    assert out["controller_actions"] == 0
