"""The yardstick's own tests: spec limits, the trace reduction against a
recorded chip trace, each reader against a fixed snapshot, the generators'
determinism, and a tiny-size CPU rehearsal of each driver that prints no
device metric, sees the control fail and sees a broken timed path fail."""
import json
import pathlib
import re

import pytest

import run as bench_run
import trace_reduce

BENCH = pathlib.Path(__file__).resolve().parents[1]
SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
LEDGER_TINY = {"parties": 4, "coins_per_party": 3, "warmup_ops": 16,
               "rate_tx_per_s": 10.0, "hostile_ops": 4,
               "reference_sample": 16, "drain_limit_s": 30.0}
WAVES_TINY = {"wave_size": 16, "party_keys": 4, "corrupt_every": 4,
              "batcher_args": {"max_batch": 16, "host_crossover": 0}}


def load(kind, name):
    return bench_run.load_module(kind, name)


# -- BENCHMARK.json within the contract's limits -----------------------------------

def test_spec_names_units_lengths():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for row in SPEC[group]:
            assert NAME.match(row["name"]), row["name"]
            names.append((group in ("end_to_end", "per_layer"), row["name"]))
    assert len(names) == len(set(names))
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
    cells = {w["name"] for w in SPEC["workloads"]}
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    for m in SPEC["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and 1 <= len(m["layer"]) <= 200
        for c in m["workloads"]:
            assert c in cells
            assert c in e2e[m["moves"]].get("workloads", cells)
    for c in SPEC["configs"]:
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
        assert (BENCH.parent / c["file"]).is_file()
        assert len(c["reduced"]) <= 16 and all(NAME.match(k)
                                               for k in c["reduced"])
    for w in SPEC["workloads"]:
        assert 1 <= len(w["why"]) <= 200 and w["chips"] in (1, 4)
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
    assert len((BENCH.parent / "BENCHMARK.json").read_bytes()) <= 65536


def test_every_cell_has_its_files_and_metrics():
    files = {p.stem: json.loads(p.read_text())
             for p in (BENCH / "layer_metrics").glob("*.json")}
    listed = {m["name"]: m for m in SPEC["per_layer"]}
    assert set(files) == set(listed)
    for name, lm in files.items():
        for key in ("layer", "unit", "moves", "workloads"):
            assert lm[key] == listed[name][key], (name, key)
        assert (BENCH / "readers" / f"{lm['reader']}.py").is_file()
    for w in SPEC["workloads"]:
        cell = bench_run.Cell(w["name"], SPEC)
        assert (BENCH / "drivers" / f"{cell.driver_name}.py").is_file()
        assert "setup_s" in cell.end_to_end_names()
        assert len(cell.end_to_end_names()) >= 2
        assert cell.layer_metric_files()


# -- the trace reduction against a recorded chip trace ------------------------------

def test_trace_reduction_on_recorded_chip_trace():
    ev = trace_reduce.load_events(BENCH / "fixtures" /
                                  "wave8k_v5e_trace.json.gz")
    lo, hi = trace_reduce._window_of(ev)
    # the profiler's buffer filled 3.37 s into the 4.0 s window
    assert ev["window"][1] / 1e9 == pytest.approx(4.0007, abs=1e-3)
    assert (hi - lo) / 1e9 == pytest.approx(3.36564, abs=1e-4)
    spans = [{"name": "batcher.device_wait", "start_s": 100.5,
              "duration_s": 1.0},
             {"name": "flow.run", "start_s": 100.0, "duration_s": 3.0}]
    red = trace_reduce.reduce(ev, spans, 100.0, ("batcher.",))
    # 8192-row kernels run back to back: the device is never idle
    assert red["busy_s"] == pytest.approx(red["window_s"], rel=1e-3)
    assert red["breakdown"]["device_ops"][0][0].startswith(
        "jit_verify_core_split")
    kernels = trace_reduce.kernel_events(ev, "verify_core_split")
    assert len(kernels) == 19        # the two cut at the window's ends left out
    assert sum(kernels) / len(kernels) == pytest.approx(0.163502, abs=1e-5)
    data = {"trace": red, "device": {"kind": "TPU v5 lite"},
            "peaks": json.loads((BENCH / "peaks.json").read_text()),
            "cell": bench_run.Cell("genledger-ed25519.wave8k", SPEC)}
    assert load("readers", "trace_idle_share").read(data) == \
        pytest.approx(0.0, abs=0.1)
    assert load("readers", "trace_kernel_time").read(
        data, program="verify_core_split") == pytest.approx(163.502, abs=0.01)
    share = load("readers", "trace_roofline_share").read(
        data, program="verify_core_split", cost="ed25519_split",
        rows_param="wave_size")
    assert share == pytest.approx(100 * (8192 * 1889 / 819e9) / 0.163502,
                                  rel=1e-4)
    data["device"] = {"kind": "TPU v9"}
    with pytest.raises(KeyError):
        load("readers", "trace_roofline_share").read(
            data, program="verify_core_split", cost="ed25519_split",
            rows_param="wave_size")


def test_idle_gaps_are_charged_to_the_spans_open_in_them():
    ev = {"window": [0.0, 10e9], "devices": {"/device:TPU:0": {
        "XLA Ops": [["%a", 1e9, 1e9], ["%b", 1.5e9, 1e9], ["%c", 6e9, 2e9]],
        "XLA Modules": [["jit_f(1)", 1e9, 1.5e9], ["jit_g(2)", 6e9, 2e9]]}}}
    spans = [{"name": "host.flows", "start_s": 50.0, "duration_s": 1.0},
             {"name": "host.sweep", "start_s": 52.5, "duration_s": 2.0},
             {"name": "other.thing", "start_s": 50.0, "duration_s": 10.0}]
    red = trace_reduce.reduce(ev, spans, 50.0, ("host.",))
    assert red["busy_s"] == pytest.approx(3.5)       # [1,2.5] and [6,8]
    assert red["window_s"] == pytest.approx(10.0)
    gaps = dict(red["breakdown"]["idle_gaps"])
    assert gaps["host.flows"] == pytest.approx(1.0)   # gap [0,1]
    assert gaps["host.sweep"] == pytest.approx(2.0)   # inside gap [2.5,6]
    assert gaps["host: no span open"] == pytest.approx(6.5 - 3.0)
    assert dict(red["breakdown"]["device_ops"])["jit_g(2)"] == \
        pytest.approx(2.0)
    # a window in which no operation ran is one idle gap, charged the same way
    idle = trace_reduce.reduce({"window": [0.0, 10e9], "devices": {}}, spans,
                               50.0, ("host.",))
    assert idle["busy_s"] == 0.0 and idle["window_s"] == pytest.approx(10.0)
    assert dict(idle["breakdown"]["idle_gaps"])["host.sweep"] == \
        pytest.approx(2.0)


# -- each reader against a fixed snapshot --------------------------------------------

def _hist(buckets, total, count, mx=0.0):
    return {"type": "histogram", "count": count, "sum": total, "max": mx,
            "buckets": buckets}


def test_registry_readers_use_the_window_only():
    snap0 = {"SigBatcher.DeviceChecked": {"count": 100},
             "SigBatcher.HostRouted": {"count": 900},
             "h": _hist([["0.001", 10], ["+Inf", 10]], 0.005, 10)}
    snap1 = {"SigBatcher.DeviceChecked": {"count": 400},
             "SigBatcher.HostRouted": {"count": 1000},
             "h": _hist([["0.001", 10], ["0.01", 30], ["+Inf", 30]],
                        0.165, 30)}
    data = {"snap0": snap0, "snap1": snap1}
    ratio = load("readers", "registry_ratio").read(
        data, numerator=["SigBatcher.DeviceChecked"],
        denominator=["SigBatcher.DeviceChecked", "SigBatcher.HostRouted"],
        scale=100.0)
    assert ratio == pytest.approx(75.0)
    # the window's 20 samples all sit in (0.00562, 0.01]: the median is
    # interpolated half way up that bucket
    q = load("readers", "histogram_quantile").read(data, metric="h", q=0.5,
                                                   scale=1000.0)
    lo = 0.01 / 10 ** 0.25
    assert q == pytest.approx(1000 * (lo + 0.5 * (0.01 - lo)))
    assert load("readers", "histogram_mean").read(data, metric="h") == \
        pytest.approx(0.16 / 20)
    assert load("readers", "histogram_mean").read(data, metric="nope") is None
    assert load("readers", "registry_ratio").read(
        {"snap0": {}, "snap1": {}}, numerator=["a"], denominator=["a"]) is None
    assert load("readers", "samples_quantile").read(
        {"samples": {"w": [0.3, 0.1, 0.2]}}, samples="w", q=0.5,
        scale=1000.0) == pytest.approx(200.0)
    assert load("readers", "samples_quantile").read(
        {"samples": {}}, samples="w", q=0.5) is None


def test_span_self_time_walks_the_blocking_chain():
    def span(sid, parent, name, start, dur, **tags):
        return {"trace_id": "t1", "span_id": sid, "parent_id": parent,
                "name": name, "start_s": start, "duration_s": dur,
                "tags": tags}
    spans = [span("r", None, "flow.run", 10.0, 1.0,
                  flow_type="CashPaymentFlow"),
             span("v", "r", "tx.verify", 10.1, 0.2),
             span("n", "r", "wait.await_future", 10.5, 0.4,
                  wait_kind="notary.commit"),
             span("c", "n", "raft.commit", 10.6, 0.1)]
    reader = load("readers", "span_self_time")
    root, parts = reader.blame(spans)
    assert root["span_id"] == "r"
    assert parts["flow.compute"] == pytest.approx(0.4)
    assert parts["verify"] == pytest.approx(0.2)
    assert parts["raft.commit"] == pytest.approx(0.1)
    assert parts["notary.batch_wait"] == pytest.approx(0.3)
    assert sum(parts.values()) == pytest.approx(1.0)
    data = {"spans": spans, "window_wall": (9.0, 12.0)}
    assert reader.read(data, component="flow.compute", q=0.5,
                       flow_types=["CashPaymentFlow"]) == pytest.approx(400.0)
    assert reader.read(data, component="flow.compute", q=0.5,
                       flow_types=["SellerFlow"]) is None
    assert reader.read({"spans": []}, component="x", q=0.5) is None


# -- the generators are a function of the seed ---------------------------------------

def test_op_schedule_is_byte_identical_for_a_seed_and_balanced():
    ledger = load("drivers", "ledger")
    a = ledger.build_schedule(3_000_000_017, 240, 24.0, 24, 0.15, "window")
    b = ledger.build_schedule(3_000_000_017, 240, 24.0, 24, 0.15, "window")
    c = ledger.build_schedule(3_000_000_018, 240, 24.0, 24, 0.15, "window")
    assert ledger.schedule_digest(a) == ledger.schedule_digest(b)
    assert ledger.schedule_digest(a) != ledger.schedule_digest(c)
    for ops in (a, c):      # the same work whatever the seed
        assert sum(op.kind == "settle" for op in ops) == 36
        assert [op.intended_s for op in ops] == [i / 24.0 for i in range(240)]
        for party in range(24):
            assert sum(op.initiator == party for op in ops) == 10
            assert sum(op.counterparty == party for op in ops) == 10
        assert all(op.initiator != op.counterparty for op in ops)


def test_wave_pool_is_byte_identical_for_a_seed():
    waves = load("drivers", "sigwaves")
    ref = load("reference", "genledger_ed25519")
    a, bad_a = waves.build_pool(3_000_000_019, 2, 64, 4, 16)
    b, _ = waves.build_pool(3_000_000_019, 2, 64, 4, 16)
    c, _ = waves.build_pool(3_000_000_020, 2, 64, 4, 16)
    assert waves.pool_digest(a) == waves.pool_digest(b)
    assert waves.pool_digest(a) != waves.pool_digest(c)
    assert [len(s) for s in bad_a] == [4, 4]
    for rows, bad in zip(a, bad_a):
        got = ref.verdicts(rows)
        assert [i for i, ok in enumerate(got) if not ok] == sorted(bad)
        assert len({msg for _p, _s, msg in rows}) == len(rows)


# -- the wave cells' one rate rule, on made-up completion times ------------------------

def _steady(rate_hz, until, start=0.0):
    """Completion seconds of a steady stream of waves, ``rate_hz`` a second
    (each half way through its interval: none on a slice's edge)."""
    n = int(round((until - start) * rate_hz))
    return [start + (k + 0.5) / rate_hz for k in range(n)]


RATE_CASES = {
    # 10 waves a second for 30 s: every slice alike, the rule is the mean
    "steady": (_steady(10, 30.0), 30.0,
               dict(sigs_per_s=10 * 8192, inside=300, after=0, last=29.95,
                    slices=[10 * 8192] * 6, median=10 * 8192)),
    # the third slice is frozen: no verdict returns from 10 s to 15 s. The
    # metric pays for the frozen seconds (all the time of the window); the
    # median of slices, a note, does not
    "one_frozen_slice": (_steady(10, 10.0) + _steady(10, 30.0, 15.0), 30.0,
                         dict(sigs_per_s=250 * 8192 / 30.0, inside=250,
                              after=0, last=29.95,
                              slices=[81920, 81920, 0, 81920, 81920, 81920],
                              median=81920)),
    # a wave whose last verdict returns after the close counts for nothing,
    # however little after
    "a_wave_ends_after_the_close": ([1.0, 2.0, 29.5, 30.0, 30.0001, 31.0],
                                    30.0,
                                    dict(sigs_per_s=4 * 8192 / 30.0, inside=4,
                                         after=2, last=30.0,
                                         slices=[2 * 8192 / 5.0, 0, 0, 0, 0,
                                                 2 * 8192 / 5.0],
                                         median=0.0)),
    # no wave inside: a rate of 0 (the drivers' check then fails the run)
    "no_wave": ([], 30.0, dict(sigs_per_s=0.0, inside=0, after=0, last=0.0,
                               slices=[0.0] * 6, median=0.0)),
    "every_wave_late": ([30.5, 31.0], 30.0,
                        dict(sigs_per_s=0.0, inside=0, after=2, last=0.0,
                             slices=[0.0] * 6, median=0.0)),
    # the clock's window is what the clock read, not --seconds
    "a_window_the_clock_stretched": (_steady(10, 30.6), 30.6,
                                     dict(sigs_per_s=306 * 8192 / 30.6,
                                          inside=306, after=0, last=30.55,
                                          slices=[51 * 8192 / 5.1] * 6,
                                          median=51 * 8192 / 5.1)),
}


@pytest.mark.parametrize("case", sorted(RATE_CASES))
def test_window_rate_on_made_up_completion_times(case):
    from bench_common import window_rate
    done_s, window_s, want = RATE_CASES[case]
    got = window_rate(done_s, window_s, 8192)
    assert got["sigs_per_s"] == pytest.approx(want["sigs_per_s"])
    assert got["waves_completed_inside"] == want["inside"]
    assert got["waves_finished_after"] == want["after"]
    assert got["window_s"] == window_s
    assert got["last_verdict_s"] == pytest.approx(want["last"])
    assert got["slice_rates"] == pytest.approx(want["slices"])
    assert got["rate_median_of_slices"] == pytest.approx(want["median"])
    # order does not matter (four clients append as they finish)
    assert window_rate(done_s[::-1], window_s, 8192) == got
    if want["inside"]:
        assert got["rate_to_last_verdict"] == pytest.approx(
            want["inside"] * 8192 / want["last"])
        # all the work over all the time: never above the rate to the last
        # verdict, and under it by less than one wave's share
        assert got["sigs_per_s"] <= got["rate_to_last_verdict"]
    else:
        assert got["rate_to_last_verdict"] == 0.0


def test_a_stall_costs_the_metric_what_it_took():
    """What the bound is about: the same stream with 2 s frozen reads
    2 / 30 lower, in ``sigs_per_s`` and in no more than one slice."""
    from bench_common import window_rate
    sound = window_rate(_steady(18, 30.0), 30.0, 8192)
    stalled = window_rate(_steady(18, 12.0) + _steady(18, 30.0, 14.0), 30.0,
                          8192)
    assert stalled["sigs_per_s"] / sound["sigs_per_s"] == pytest.approx(
        28 / 30, abs=1e-3)
    low = [a < b for a, b in zip(stalled["slice_rates"],
                                 sound["slice_rates"])]
    assert low == [False, False, True, False, False, False]
    assert stalled["rate_median_of_slices"] == sound["rate_median_of_slices"]


@pytest.mark.parametrize("workload", ["genledger-ed25519.wave8k",
                                      "genledger-secp256k1.wave8k"])
def test_both_wave_cells_resolve_to_the_one_driver_and_the_one_rule(workload):
    import bench_common
    cell = bench_run.Cell(workload, SPEC)
    driver = load("drivers", cell.driver_name)
    assert pathlib.Path(driver.run.__code__.co_filename) \
        == BENCH / "drivers" / "sigwaves.py"
    assert driver.run.__globals__["window_rate"] is bench_common.window_rate
    # the rule is written once: no driver divides by the window itself
    for name in ("sigwaves", "ecdsawaves"):
        source = (BENCH / "drivers" / f"{name}.py").read_text()
        assert "/ (t_close - t_open)" not in source
    assert "sigs_per_s" in cell.end_to_end_names()


def test_gc_watch_times_the_collections_between_start_and_stop():
    import gc

    from bench_common import GcWatch
    watch = GcWatch().start()
    gc.collect(0)
    gc.collect(2)
    seen = watch.stop()
    assert watch not in gc.callbacks
    assert seen["gc_collections"][0] >= 1 and seen["gc_collections"][2] >= 1
    assert 0 < seen["gc_longest_ms"] / 1e3 <= seen["gc_s"]
    gc.collect()
    assert watch.stop() == seen          # stopped: counts no more, twice is fine


# -- tiny-size CPU rehearsals: control flow only, no device metric printed ------------

def rehearse(workload, scale, seconds, capsys, control=None, trace=False):
    cell = bench_run.Cell(workload, SPEC)
    result = bench_run.run_cell(cell, 3_000_000_021, seconds, trace, CPU,
                                control=control, scale=scale, quiet=True)
    assert capsys.readouterr().out == ""      # nothing under a metric's name
    return result


@pytest.mark.parametrize("workload", ["crosscash-raft.steady",
                                      "crosscash-raft.saturated"])
def test_ledger_rehearsal(workload, capsys):
    result = rehearse(workload, LEDGER_TINY, 3.0, capsys)
    assert result["correct"] and result["attempted"] == 30
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "checks"]
    # each number compared beside its limit, last in the line
    assert result["checks"] and all(
        ok for _value, _limit, ok in result["checks"].values())
    assert json.loads(json.dumps(result, default=str))["checks"].keys() \
        == result["checks"].keys()
    cell = bench_run.Cell(workload, SPEC)
    assert set(result["metrics"]) == set(cell.end_to_end_names())


def test_ledger_traced_rehearsal_reads_its_layer_metrics(capsys):
    result = rehearse("crosscash-raft.saturated", LEDGER_TINY, 3.0, capsys,
                      trace=True)
    assert result["correct"]
    assert {"flow_self_ms_p50.saturated", "device_route_share.saturated",
            "raft_round_ms_p50", "notary_batch_mean"} <= set(result["metrics"])
    # two traced segments: the window's sub-window, then the id check
    assert result["device"]["window_s"] > 1.0


def test_ledger_control_comes_out_not_correct(capsys):
    result = rehearse("crosscash-raft.steady", LEDGER_TINY, 3.0, capsys,
                      control="notary_accepts_replays")
    assert result["correct"] is False


def test_ledger_altered_device_root_comes_out_not_correct(capsys, monkeypatch):
    """An answer altered where it is produced: a root of the id check that
    follows the drain (the ledger cells' one device call)."""
    from corda_tpu.core.crypto.secure_hash import SecureHash
    from corda_tpu.core.transactions import batch_merkle
    sound = batch_merkle.batch_roots

    def altered(leaf_lists, **kw):
        roots = sound(leaf_lists, **kw)
        first = roots[0].bytes
        return [SecureHash(first[:-1] + bytes([first[-1] ^ 1])), *roots[1:]]

    monkeypatch.setattr(batch_merkle, "batch_roots", altered)
    result = rehearse("crosscash-raft.steady", LEDGER_TINY, 3.0, capsys)
    assert result["correct"] is False


def test_ledger_lost_vault_update_comes_out_not_correct(capsys, monkeypatch):
    """A step that leaves its state unchanged: one party's vault ignores
    every update, so its acknowledged commits are not read back."""
    from corda_tpu.node.vault import NodeVaultService
    sound = NodeVaultService.notify_all

    def forgetful(self, txs):
        if "Party 1" in str(self.hub.my_info.legal_identity.name):
            return []
        return sound(self, txs)

    monkeypatch.setattr(NodeVaultService, "notify_all", forgetful)
    result = rehearse("crosscash-raft.steady", LEDGER_TINY, 3.0, capsys)
    assert result["correct"] is False


def test_waves_rehearsal_control_and_broken_path(capsys, monkeypatch):
    sound = rehearse("genledger-ed25519.wave8k", WAVES_TINY, 2.0, capsys)
    assert sound["correct"] and set(sound["metrics"]) == {"sigs_per_s",
                                                          "setup_s"}
    import gc
    assert gc.get_freeze_count() == 0         # the driver thawed what it froze
    control = rehearse("genledger-ed25519.wave8k", WAVES_TINY, 1.0, capsys,
                       control="unchecked_rows")
    assert control["correct"] is False
    # a verdict altered where it is produced
    from corda_tpu.verifier.batcher import SignatureBatcher
    resolve = SignatureBatcher._resolve

    def flipped(self, bucket, items, verdicts, bctx=None):
        verdicts = list(verdicts)
        verdicts[0] = not verdicts[0]
        return resolve(self, bucket, items, verdicts, bctx)

    monkeypatch.setattr(SignatureBatcher, "_resolve", flipped)
    broken = rehearse("genledger-ed25519.wave8k", WAVES_TINY, 1.0, capsys)
    assert broken["correct"] is False


def test_run_refuses_without_a_tpu(capsys):
    assert bench_run.main(["--workload", "crosscash-raft.steady", "--seed",
                           "1", "--seconds", "1", "--trace", "0"]) == 2
    assert capsys.readouterr().out == ""
