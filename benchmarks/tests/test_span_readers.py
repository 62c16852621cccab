"""The readers ISSUE 25 added, each against a fixed span list; what they do
with the spans of a program that has none of the new ones (nothing, and no
exception); and a traced tiny rehearsal that has to list every new metric of
its cell and to count the window's transactions as the driver does."""
import json
import pathlib

import pytest

import run as bench_run
import span_walk

BENCH = pathlib.Path(__file__).resolve().parents[1]
SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
LEDGER_TINY = {"parties": 4, "coins_per_party": 3, "warmup_ops": 16,
               "rate_tx_per_s": 10.0, "hostile_ops": 4,
               "reference_sample": 16, "drain_limit_s": 30.0}
NEW = {"crosscash-raft.steady": {
           "flow_step_ms_p50.steady", "runnable_wait_ms_p50.steady",
           "flow_unnamed_ms_p50.steady", "verify_span_ms_p50.steady"},
       "crosscash-raft.saturated": {
           "runnable_wait_ms_p50.saturated", "flow_step_ms_per_tx.saturated",
           "sign_ms_per_tx.saturated", "checkpoint_ms_per_tx.saturated",
           "session_ms_per_tx.saturated", "flow_unnamed_ms_p50.saturated",
           "node_thread_busy_share.saturated"}}


def load(name):
    return bench_run.load_module("readers", name)


def span(sid, parent, name, start, dur, thread="node", trace="t1", **tags):
    return {"trace_id": trace, "span_id": sid, "parent_id": parent,
            "name": name, "start_s": start, "duration_s": dur,
            "thread": thread, "tags": tags}


def payment(trace="t1", at=10.0, pre=""):
    """One op, 1.0 s long: step, wait for the thread, receive, step; a
    responder's run as a remote child; 0.05 s that no span names."""
    r = pre + "r"
    return [
        span(r, None, "flow.run", at, 1.0, trace=trace,
             flow_type="finance.flows.CashPaymentFlow"),
        span(pre + "s1", r, "flow.step", at, 0.20, trace=trace,
             sign_s=0.05, n_sign=1, checkpoint_s=0.01, exit="SendAndReceive"),
        span(pre + "o1", pre + "s1", "session.send", at + 0.10, 0.04,
             trace=trace, bytes=900),
        span(pre + "n", r, "flow.run", at + 0.20, 0.40, trace=trace,
             flow_type="flows.library.NotaryServiceFlow"),
        span(pre + "ns", pre + "n", "flow.step", at + 0.22, 0.30,
             trace=trace, sign_s=0.02, n_sign=1, exit="done"),
        span(pre + "w", r, "wait.runnable", at + 0.60, 0.25, trace=trace,
             wait_kind="scheduler.runnable", source="message"),
        span(pre + "i", r, "session.receive", at + 0.85, 0.03, trace=trace,
             bytes=400),
        span(pre + "s2", r, "flow.step", at + 0.88, 0.07, trace=trace,
             exit="done"),
    ]


def test_span_chain_ms_charges_the_chain_by_span_name():
    reader = load("span_chain_ms")
    data = {"spans": payment(), "window_wall": (9.0, 12.0)}
    types = ["CashPaymentFlow"]

    def chain(name, **kw):
        return reader.read(data, span=name, q=0.5, flow_types=types, **kw)
    # s1 less its send (0.16) + the notary's step (0.30) + s2 (0.07)
    assert chain("flow.step") == pytest.approx(530.0)
    assert chain("wait.runnable") == pytest.approx(250.0)
    assert chain("session.send") == pytest.approx(40.0)
    assert chain("session.receive") == pytest.approx(30.0)
    # un-named: 0.05 at the root's end + the nested run's 0.02 + 0.08
    assert chain("flow.run", needs="flow.step") == pytest.approx(150.0)
    total = sum(chain(n) for n in ("flow.step", "wait.runnable", "flow.run",
                                   "session.send", "session.receive"))
    assert total == pytest.approx(1000.0)      # every millisecond, once
    assert reader.read(data, span="flow.step", q=0.5,
                       flow_types=["SellerFlow"]) is None
    # the unedited walk under its own name still charges components
    old = load("span_self_time")
    assert old.read(data, component="flow.compute", q=0.5,
                    flow_types=types) == pytest.approx(680.0)
    assert old.read(data, component="other", q=0.5,
                    flow_types=types) == pytest.approx(250.0)


def test_span_sum_per_tx_self_time_tags_and_the_count_it_checks():
    reader = load("span_sum_per_tx")
    spans = payment("t1", 10.0, "a") + payment("t2", 10.5, "b") \
        + payment("t3", 11.05, "c")        # c ends at 12.05, in the drain
    snaps = {"snap0": {"GroupCommit.Committed": {"count": 40}},
             "snap1": {"GroupCommit.Committed": {"count": 43}}}
    data = {"spans": spans, "window_wall": (9.0, 12.0), **snaps}
    kw = {"counter": "GroupCommit.Committed",
          "flow_types": ["CashPaymentFlow", "SellerFlow", "FinalityFlow"]}
    assert span_walk.committed(data, kw["flow_types"]) == 2
    assert span_walk.committed(data, kw["flow_types"], 9.0,
                               float("inf")) == 3
    # all 9 steps start in the window; each op's are 0.16 + 0.30 + 0.07 of
    # self time (the 0.04 send inside a step is its child, not its own)
    assert reader.read(data, spans=["flow.step"], self_time=True, **kw) == \
        pytest.approx(1000 * 3 * 0.53 / 2)
    assert reader.read(data, spans=["flow.step"], tag="sign_s", **kw) == \
        pytest.approx(1000 * 3 * 0.07 / 2)
    assert reader.read(data, spans=["flow.step"], tag="checkpoint_s",
                       **kw) == pytest.approx(1000 * 3 * 0.01 / 2)
    assert reader.read(data, spans=["session.send", "session.receive"],
                       **kw) == pytest.approx(1000 * 3 * 0.07 / 2)
    # the program counted 6 commits, the spans show 3: no number
    data["snap1"] = {"GroupCommit.Committed": {"count": 46}}
    assert reader.read(data, spans=["flow.step"], self_time=True,
                       **kw) is None
    data["snap1"] = {}
    assert reader.read(data, spans=["flow.step"], self_time=True,
                       **kw) is None
    assert reader.read({"spans": spans}, spans=["flow.step"], **kw) is None


def test_thread_busy_share_is_one_threads_union_over_the_window():
    reader = load("thread_busy_share")
    spans = [
        span("r", None, "flow.run", 0.0, 10.0),
        span("a", "r", "flow.step", 1.0, 2.0),
        span("a1", "a", "session.send", 1.5, 0.5),        # inside its step
        span("b", "r", "session.receive", 3.0, 1.0),
        span("c", "r", "flow.step", 8.0, 4.0),            # cut at the window
        span("w", "r", "wait.runnable", 4.0, 4.0),        # waiting: not busy
        # another thread, busier in all, but with less step time
        span("x", None, "flow.step", 0.0, 1.0, thread="other"),
        span("y", None, "tx.verify", 0.0, 9.0, thread="other"),
        span("z", None, "session.receive", 4.0, 4.0, thread="other"),
    ]
    data = {"spans": spans, "window_wall": (0.0, 10.0)}
    kw = {"by": "flow.step", "prefixes": ["session."]}
    assert reader.read(data, **kw) == pytest.approx(100 * (2 + 1 + 2) / 10)
    assert reader.read(data, by="flow.step") == pytest.approx(100 * 4 / 10)
    assert reader.read(data, by="tx.verify") == pytest.approx(90.0)
    for s in spans:                     # spans from before the thread tag
        del s["thread"]
    assert reader.read(data, **kw) is None
    assert reader.read({"spans": spans}, **kw) is None


def test_span_duration_quantile_is_exact_and_of_the_window():
    reader = load("span_duration_quantile")
    spans = [span(f"v{i}", None, "tx.verify", 10.0 + i, d, thread="pool")
             for i, d in enumerate([0.004, 0.0071, 0.0072, 0.009, 0.5])]
    spans.append(span("late", None, "tx.verify", 99.0, 7.0))
    data = {"spans": spans, "window_wall": (9.0, 20.0)}
    assert reader.read(data, span="tx.verify", q=0.5) == pytest.approx(7.2)
    assert reader.read(data, span="tx.verify", q=1.0) == pytest.approx(500.0)
    assert reader.read(data, span="nope", q=0.5) is None


def test_a_program_without_the_new_spans_gives_nothing_and_no_error():
    """PR 24's span shape: no thread, no flow.step, session markers of zero
    length. Every new metric file's reader returns None over it."""
    old = [{k: v for k, v in s.items() if k != "thread"}
           for s in payment() if s["name"] not in ("flow.step",
                                                   "wait.runnable")]
    for s in old:
        if s["name"].startswith("session."):
            s["duration_s"] = 0.0
            s["parent_id"] = "r"
    data = {"spans": old, "window_wall": (9.0, 12.0),
            "snap0": {"GroupCommit.Committed": {"count": 0}},
            "snap1": {"GroupCommit.Committed": {"count": 1}}}
    for path in sorted((BENCH / "layer_metrics").glob("*.json")):
        lm = json.loads(path.read_text())
        if lm["name"] not in NEW["crosscash-raft.steady"] \
                | NEW["crosscash-raft.saturated"]:
            continue
        assert load(lm["reader"]).read(data, **lm["args"]) is None, \
            lm["name"]


def test_the_new_metrics_are_listed_in_their_cells_only():
    listed = {m["name"]: m for m in SPEC["per_layer"]}
    for cell, names in NEW.items():
        for name in names:
            assert listed[name]["workloads"] == [cell]
            assert listed[name]["source"] == "program_span"
    order = [m["name"] for m in SPEC["per_layer"]]
    both = NEW["crosscash-raft.steady"] | NEW["crosscash-raft.saturated"]
    # appended after PR 24's fifteen, none of which moved
    assert min(order.index(n) for n in both) == 15
    assert order[0] == "flow_self_ms_p50.steady" \
        and order[14] == "device_idle_share.wave8k"


@pytest.mark.parametrize("workload", sorted(NEW))
def test_traced_rehearsal_lists_every_new_metric_of_its_cell(workload,
                                                             capsys):
    seen = {}
    read = bench_run.read_layer_metrics

    def spy(cell, data):
        seen.update(data)
        return read(cell, data)

    bench_run.read_layer_metrics = spy
    notes: list = []
    try:
        result = bench_run.run_cell(
            bench_run.Cell(workload, SPEC), 3_000_000_021, 3.0, True, CPU,
            scale=LEDGER_TINY, quiet=True, notes=notes)
    finally:
        bench_run.read_layer_metrics = read
    assert capsys.readouterr().out == ""
    assert result["correct"]
    assert NEW[workload] <= set(result["metrics"])
    # the readers' count of the window's transactions is the driver's
    window = next(n for n in notes if n.get("note") == "window")
    types = ["CashPaymentFlow", "SellerFlow", "FinalityFlow"]
    assert span_walk.committed(seen, types) == \
        window["tx_committed_in_window"]
    m = {k: v["value"] for k, v in result["metrics"].items()}
    if workload.endswith("saturated"):
        assert 0 < m["node_thread_busy_share.saturated"] <= 100
        assert m["sign_ms_per_tx.saturated"] \
            < m["flow_step_ms_per_tx.saturated"]
    assert all(s.get("thread") for s in seen["spans"])
