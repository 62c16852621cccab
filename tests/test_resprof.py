"""Resource accounting plane + leak detector + subsystem CPU profiler
(observability/resprof.py) and the live /debug/soak payload over them —
all over synthetic series and injected frames, no real sleeping (tier-1
discipline)."""
import pytest

from corda_tpu.observability.consensus_obs import GrowthWatch
from corda_tpu.observability.resprof import (
    COMMIT_PATH_COMPONENTS, CPU_COMPONENTS, ResourceRegistry,
    SubsystemProfiler, classify_stack, get_resources, is_wait_frame,
    leak_verdict, process_rss_bytes, set_resources, soak_report,
    theil_sen_slope, verdict_rows)
from corda_tpu.observability.timeseries import TimeSeriesStore, set_timeseries


def rows(pts):
    """Synthetic retained-ring rows [t, n, min, max, mean, last]."""
    return [[t, 1, v, v, v, v] for t, v in pts]


# ---------------------------------------------------------------------------
# Theil–Sen trend fit
# ---------------------------------------------------------------------------

def test_theil_sen_exact_on_linear():
    pts = [(float(t), 3.0 + 2.0 * t) for t in range(10)]
    assert theil_sen_slope(pts) == pytest.approx(2.0)


def test_theil_sen_robust_to_outlier():
    # a single chaos-window spike barely moves the median of pairwise
    # slopes — the property a least-squares fit does not have
    pts = [(float(t), float(t)) for t in range(20)]
    pts[10] = (10.0, 500.0)
    assert theil_sen_slope(pts) == pytest.approx(1.0, abs=0.15)


def test_theil_sen_degenerate():
    assert theil_sen_slope([]) == 0.0
    assert theil_sen_slope([(1.0, 5.0)]) == 0.0
    assert theil_sen_slope([(1.0, 5.0), (1.0, 9.0)]) == 0.0  # same t


# ---------------------------------------------------------------------------
# leak_verdict over synthetic bounded / linear / step series
# ---------------------------------------------------------------------------

def test_verdict_flat_series_is_bounded():
    v = leak_verdict(rows((float(t), 100.0) for t in range(60)))
    assert v["verdict"] == "bounded"
    assert v["slope_per_s"] == pytest.approx(0.0)


def test_verdict_noisy_flat_series_is_bounded():
    # ±5% sawtooth around a constant level: noise, not growth
    v = leak_verdict(rows((float(t), 100.0 + 5.0 * (-1) ** t)
                          for t in range(60)))
    assert v["verdict"] == "bounded"


def test_verdict_linear_growth_leaks_when_declared_bounded():
    v = leak_verdict(rows((float(t), 10.0 + 2.0 * t) for t in range(60)),
                     kind="bounded")
    assert v["verdict"] == "leaking"
    assert v["slope_per_s"] == pytest.approx(2.0, rel=0.05)
    # doubling time is level / slope over the recent-half window
    assert v["doubling_s"] == pytest.approx(v["level"] / 2.0, rel=0.05)


def test_verdict_linear_growth_caps_at_growing_when_declared_grows():
    v = leak_verdict(rows((float(t), 10.0 + 2.0 * t) for t in range(60)),
                     kind="grows")
    assert v["verdict"] == "growing"
    assert v["doubling_s"] is not None and v["doubling_s"] > 0


def test_verdict_step_then_plateau_is_bounded():
    # the chaos-window signature: one step up, then flat — the recent-half
    # fit must NOT read the old step as a trend
    pts = [(float(t), 10.0 if t < 20 else 500.0) for t in range(80)]
    v = leak_verdict(rows(pts), kind="bounded")
    assert v["verdict"] == "bounded"


def test_verdict_declared_bound_growth_under_cap_is_filling():
    # a fresh span ring filling toward capacity is NOT a leak
    pts = [(float(t), 10.0 * t) for t in range(60)]     # level ≈ 450
    v = leak_verdict(rows(pts), kind="bounded", bound=100_000.0)
    assert v["verdict"] == "bounded"
    assert v.get("filling") is True
    assert v["slope_per_s"] > 0
    # ...but growth AT/ABOVE the declared cap has lost its bound
    v = leak_verdict(rows(pts), kind="bounded", bound=400.0)
    assert v["verdict"] == "leaking"
    assert "filling" not in v


def test_verdict_growth_that_drains_at_quiescence_is_backlog():
    # in-flight structures (checkpoint stores, reservation maps) grow
    # with open-loop backlog and empty at drain: a leak by definition
    # PERSISTS at quiescence, so a final level back near zero downgrades
    pts = [(float(t), 2.0 * t) for t in range(60)]
    v = leak_verdict(rows(pts), kind="bounded", final_level=0.0)
    assert v["verdict"] == "bounded"
    assert v.get("drained") is True
    # ...while growth still standing after drain keeps the leak verdict
    v = leak_verdict(rows(pts), kind="bounded", final_level=120.0)
    assert v["verdict"] == "leaking"
    assert "drained" not in v


def test_verdict_too_few_points_is_honest_bounded():
    v = leak_verdict(rows((float(t), 1000.0 * t) for t in range(3)))
    assert v["verdict"] == "bounded"
    assert v["points"] == 3


def test_verdict_tolerates_malformed_rows():
    bad = [None, [], [1.0], ["x", 1, 2, 3, "y", 5], [0.0, 1, 2, 3, 4.0, 5]]
    v = leak_verdict(bad)
    assert v["verdict"] == "bounded" and v["points"] == 1
    assert leak_verdict(None)["verdict"] == "bounded"


# ---------------------------------------------------------------------------
# ResourceRegistry
# ---------------------------------------------------------------------------

def test_registry_register_sample_and_introspect():
    reg = ResourceRegistry()
    items = [1, 2, 3]
    reg.register("Test.List", lambda: len(items), kind="bounded", bound=10)
    reg.register("Test.Counter", lambda: 100.0, kind="grows")
    assert reg.names() == ["Test.Counter", "Test.List"]
    assert reg.kinds() == {"Test.List": "bounded", "Test.Counter": "grows"}
    assert reg.bounds() == {"Test.List": 10}
    store = TimeSeriesStore(resolutions=((1.0, 8),))
    values = reg.sample(store=store, t=0.0)
    assert values == {"Resource.Test.List": 3.0,
                      "Resource.Test.Counter": 100.0}
    assert reg.sizes()["Test.List"] == 3.0
    store.flush()
    snap = store.snapshot()
    assert sorted(snap["series"]) == ["Resource.Test.Counter",
                                      "Resource.Test.List"]
    reg.unregister("Test.List")
    assert reg.names() == ["Test.Counter"]
    assert "Test.List" not in reg.sizes()


def test_registry_rejects_bad_registrations():
    reg = ResourceRegistry()
    with pytest.raises(ValueError):
        reg.register("x", lambda: 0, kind="unbounded")
    with pytest.raises(ValueError):
        reg.register("x", 42)


def test_registry_rate_probe_windowed_delta():
    reg = ResourceRegistry()
    cum = {"v": 100.0}
    reg.register("Drops", lambda: cum["v"], kind="grows", rate=True)
    first = reg.sample(t=0.0)
    assert "Resource.Drops.Rate" not in first    # no window yet
    cum["v"] = 150.0
    second = reg.sample(t=10.0)
    assert second["Resource.Drops.Rate"] == pytest.approx(5.0)
    # a counter reset (restart) clamps to zero, never a negative rate
    cum["v"] = 0.0
    third = reg.sample(t=20.0)
    assert third["Resource.Drops.Rate"] == 0.0


def test_registry_broken_probe_does_not_stall_sampling():
    reg = ResourceRegistry()
    reg.register("Broken", lambda: 1 / 0)
    reg.register("NotANumber", lambda: "many")
    reg.register("Fine", lambda: 7.0)
    values = reg.sample(t=0.0)
    assert values == {"Resource.Fine": 7.0}


def test_registry_feeds_growth_watch_doubling_for_free():
    """Satellite: ANY registered structure gets doubling warnings —
    GrowthWatch is no longer limited to its two hard-coded hazards."""
    reg = ResourceRegistry()
    size = {"v": 2000.0}
    reg.register("Anything.AtAll", lambda: size["v"], kind="grows")
    cum = {"v": 5000.0}
    reg.register("Some.Counter", lambda: cum["v"], kind="grows", rate=True)
    watch = GrowthWatch()
    reg.sample(watch=watch, t=0.0)               # baseline armed
    size["v"] = 5000.0                           # ≥ 2× the baseline
    cum["v"] = 5001.0
    reg.sample(watch=watch, t=1.0)
    assert watch.warnings == 1                   # .Rate series never fed


def test_global_registry_seam():
    mine = ResourceRegistry()
    prev = set_resources(mine)
    try:
        assert get_resources() is mine
    finally:
        set_resources(prev)
    assert get_resources() is not mine


def test_process_rss_probe_reads_something():
    assert process_rss_bytes() > 0


# ---------------------------------------------------------------------------
# stack classification + CPU profiler (injected frames, no timing)
# ---------------------------------------------------------------------------

def test_classify_stack_thread_rules_win():
    # a dedicated subsystem thread is that subsystem's time no matter
    # which helper it is inside
    frames = [("corda_tpu/core/serialization/codec.py", "encode")]
    assert classify_stack("ledger-raft-pump-0", frames) == "raft_pump"
    assert classify_stack("sig-batcher-prep-1", frames) == "batcher_prep"
    assert classify_stack("sig-batcher-0", frames) == "batcher_dispatch"
    assert classify_stack("tcp-messaging-3", frames) == "network"
    assert classify_stack("soak-cpu-profiler", frames) == "observability"


def test_classify_stack_innermost_frame_rule():
    assert classify_stack("worker", [
        ("corda_tpu/consensus/raft.py", "tick"),
        ("corda_tpu/flows/runner.py", "run"),
    ]) == "raft_pump"
    assert classify_stack("worker", [
        ("corda_tpu/observability/tracing.py", "span"),
        ("corda_tpu/consensus/raft.py", "tick"),
    ]) == "observability"
    assert classify_stack("worker", [
        ("corda_tpu/node/statemachine.py", "step")]) == "flow_scheduler"
    assert classify_stack("worker", [("mymodule.py", "f")]) == "other"
    assert classify_stack("", []) == "other"


def test_is_wait_frame_stdlib_and_linecache(tmp_path):
    assert is_wait_frame("/usr/lib/python3.11/threading.py", "wait")
    assert is_wait_frame("/usr/lib/python3.11/queue.py", "get")
    assert not is_wait_frame("corda_tpu/consensus/raft.py", "tick")
    # C-level blocks leave the CALLER's frame innermost: the source-line
    # peek catches them
    src = tmp_path / "caller.py"
    src.write_text("import time\ntime.sleep(0.5)\nx = 1 + 1\n")
    assert is_wait_frame(str(src), "body", 2)
    assert not is_wait_frame(str(src), "body", 3)


class _Frame:
    """Just enough of a frame for SubsystemProfiler.sample_once."""

    class _Code:
        def __init__(self, filename, name):
            self.co_filename = filename
            self.co_name = name

    def __init__(self, filename, func, lineno=0, back=None):
        self.f_code = self._Code(filename, func)
        self.f_lineno = lineno
        self.f_back = back


def test_profiler_shares_sum_to_100_of_busy_samples():
    prof = SubsystemProfiler()
    busy_raft = _Frame("corda_tpu/consensus/raft.py", "tick")
    busy_ser = _Frame("corda_tpu/core/serialization/codec.py", "encode")
    waiting = _Frame("/usr/lib/python3.11/threading.py", "wait")
    frames = {1: busy_raft, 2: busy_ser, 3: waiting}
    names = {1: "pump", 2: "worker", 3: "parked"}
    for _ in range(4):
        prof.sample_once(current_frames=frames, thread_names=names)
    snap = prof.snapshot()
    assert snap["ticks"] == 4
    assert snap["samples"] == 12
    assert snap["busy_samples"] == 8 and snap["idle_samples"] == 4
    assert snap["busy_frac"] == pytest.approx(8 / 12, abs=1e-3)
    assert snap["shares_pct"]["raft_pump"] == pytest.approx(50.0)
    assert snap["shares_pct"]["serialization"] == pytest.approx(50.0)
    assert snap["share_sum_pct"] == pytest.approx(100.0, abs=0.1)
    assert snap["top_commit_path"] in ("raft_pump", "serialization")
    assert set(snap["shares_pct"]) == set(CPU_COMPONENTS)


def test_profiler_thread_name_beats_frame_for_dedicated_threads():
    prof = SubsystemProfiler()
    frames = {1: _Frame("corda_tpu/core/serialization/codec.py", "encode")}
    prof.sample_once(current_frames=frames,
                     thread_names={1: "ledger-raft-pump"})
    assert prof.snapshot()["shares_pct"]["raft_pump"] == 100.0


def test_profiler_empty_snapshot_is_well_formed():
    snap = SubsystemProfiler().snapshot()
    assert snap["samples"] == 0 and snap["busy_frac"] == 0.0
    assert snap["share_sum_pct"] == 0.0
    assert snap["top_commit_path"] is None
    assert all(c in CPU_COMPONENTS for c in COMMIT_PATH_COMPONENTS)


def test_profiler_walks_caller_chain_for_classification():
    # innermost frame unmatched, but its caller sits in consensus/raft:
    # the innermost MATCHING frame decides
    inner = _Frame("helperlib.py", "crunch",
                   back=None)
    inner.f_back = _Frame("corda_tpu/consensus/raft.py", "tick")
    prof = SubsystemProfiler()
    prof.sample_once(current_frames={1: inner}, thread_names={1: "t"})
    assert prof.snapshot()["shares_pct"]["raft_pump"] == 100.0


# ---------------------------------------------------------------------------
# the ring a leak fit reads
# ---------------------------------------------------------------------------

def test_verdict_rows_prefers_coarsest_populated_ring():
    fine = [[float(t), 1, 0, 0, float(t), 0] for t in range(100)]
    coarse = [[60.0 * t, 10, 0, 0, float(t), 0] for t in range(8)]
    rings = [{"bucket_s": 0.5, "points": fine},
             {"bucket_s": 60.0, "points": coarse}]
    assert verdict_rows(rings) == coarse       # coarsest with ≥5 points
    # a smoke run never fills the 60 s ring: fall back to the fine one
    rings = [{"bucket_s": 0.5, "points": fine},
             {"bucket_s": 60.0, "points": coarse[:2]}]
    assert verdict_rows(rings) == fine
    assert verdict_rows([]) == []
    assert verdict_rows([{"bucket_s": 0.5}, "junk", None]) == []


# ---------------------------------------------------------------------------
# the live /debug/soak payload
# ---------------------------------------------------------------------------

def test_soak_report_composes_live_registry_and_retained_series():
    reg = ResourceRegistry()
    size = {"v": 5.0}
    reg.register("Live.Thing", lambda: size["v"], kind="bounded")
    store = TimeSeriesStore(resolutions=((1.0, 16),))
    prev_reg, prev_store = set_resources(reg), set_timeseries(store)
    try:
        for t in range(10):
            reg.sample(store=store, t=float(t))
        store.flush()
        out = soak_report()
        assert list(out["resources"]) == ["Live.Thing"]
        r = out["resources"]["Live.Thing"]
        assert r["size"] == 5.0 and r["kind"] == "bounded"
        assert r["verdict"] == "bounded"
        assert out["leaking"] == []
        assert out["cpu"] is None              # no profiler running
    finally:
        set_resources(prev_reg)
        set_timeseries(prev_store)


def test_soak_report_empty_node_is_well_formed():
    prev_reg = set_resources(ResourceRegistry())
    try:
        out = soak_report()
        assert out == {"resources": {}, "leaking": [], "cpu": None}
    finally:
        set_resources(prev_reg)
