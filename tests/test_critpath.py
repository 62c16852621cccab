"""Critical-path extractor (observability/critpath.py) on synthetic span
trees: blame conservation, overlapping children, pre-root admission
waits, orphans, zero-duration spans, and parent-pointer cycles (must
terminate, never hang). Plus the critpath CLI renderer."""
import pytest

from corda_tpu.observability.critpath import (COMPONENTS, WAIT_KINDS,
                                              aggregate_critpaths,
                                              component_of, critical_path,
                                              critpath_report, flow_kind)

PAY = "corda_tpu.finance.cash.CashPaymentFlow"


def _span(name, span_id, parent_id=None, start=0.0, dur=0.0, **tags):
    return {"name": name, "trace_id": "t1", "span_id": span_id,
            "parent_id": parent_id, "start_s": start, "duration_s": dur,
            "tags": tags}


def _commit_tree():
    """flow.run [0,10] with verify [1,4], a notary park [4,9], and a
    scheduler-admission wait [-2,0] that precedes the root (submit
    happens before launch)."""
    return [
        _span("flow.run", "r", start=0.0, dur=10.0, flow_type=PAY),
        _span("wait.scheduler_admission", "a", "r", start=-2.0, dur=2.0,
              wait_kind="scheduler.admission"),
        _span("tx.verify", "v", "r", start=1.0, dur=3.0),
        _span("wait.await_future", "n", "r", start=4.0, dur=5.0,
              wait_kind="notary.commit"),
    ]


def test_blame_conserves_e2e_and_extends_to_submit():
    cp = critical_path(_commit_tree())
    # e2e spans submit (-2) to resolution (10), not launch to resolution
    assert cp["e2e_ms"] == pytest.approx(12000.0)
    assert cp["flow_type"] == PAY
    assert sum(cp["blame_ms"].values()) == pytest.approx(cp["e2e_ms"])
    assert cp["blame_ms"] == {
        "scheduler.wait": pytest.approx(2000.0),
        "flow.compute": pytest.approx(2000.0),    # [0,1] + [9,10] self-time
        "verify": pytest.approx(3000.0),
        "notary.batch_wait": pytest.approx(5000.0),
    }
    assert cp["dominant"] == "notary.batch_wait"
    # chronological chain, annotated with wait kinds
    assert [s["name"] for s in cp["segments"]] == [
        "wait.scheduler_admission", "flow.run", "tx.verify",
        "wait.await_future", "flow.run"]
    assert cp["segments"][3]["wait_kind"] == "notary.commit"


def test_overlapping_children_charge_the_blocking_one():
    """Two verify children overlap [2,6); the blocking chain charges each
    instant to exactly one span (the last-finishing one wins the overlap),
    so blame still sums to e2e."""
    spans = [
        _span("flow.run", "r", start=0.0, dur=10.0, flow_type=PAY),
        _span("tx.verify", "v1", "r", start=1.0, dur=5.0),   # [1,6]
        _span("tx.verify", "v2", "r", start=2.0, dur=7.0),   # [2,9]
    ]
    cp = critical_path(spans)
    assert cp["e2e_ms"] == pytest.approx(10000.0)
    assert sum(cp["blame_ms"].values()) == pytest.approx(10000.0)
    # v2 owns [2,9], v1 only its unshadowed prefix [1,2], root [0,1]+[9,10]
    assert cp["blame_ms"] == {"flow.compute": pytest.approx(2000.0),
                              "verify": pytest.approx(8000.0)}


def test_orphan_and_foreign_spans_do_not_claim_time():
    spans = _commit_tree() + [
        _span("worker.device_dispatch", "o1", parent_id="never-arrived",
              start=0.0, dur=50.0),
        {"bogus": "not a span"},
        _span("", "z"),   # zero-duration, nameless
    ]
    cp = critical_path(spans)
    assert cp["root_name"] == "flow.run"   # orphan is longer but not root
    assert cp["e2e_ms"] == pytest.approx(12000.0)
    assert sum(cp["blame_ms"].values()) == pytest.approx(12000.0)


def test_foreign_admission_waits_cannot_inflate_the_chain():
    """Regression pin: a stitched trace carries the responder and notary
    flows' own wait.scheduler_admission spans too. Only the ROOT flow's
    admission wait (parented to the root) extends the chain to submit —
    counting the others stacked overlapping pre-root segments and blew
    blame past e2e on full ledger runs."""
    resp = _span("flow.run", "rr", "n", start=5.0, dur=2.0)
    spans = _commit_tree() + [
        resp,
        # responder's admission wait: parented to ITS flow.run, and it
        # started before the root's launch — must NOT be prepended
        _span("wait.scheduler_admission", "ra", "rr", start=-1.5, dur=6.5,
              wait_kind="scheduler.admission"),
        # stray parentless admission wait (its flow.run was evicted)
        _span("wait.scheduler_admission", "sa", None, start=-3.0, dur=2.5,
              wait_kind="scheduler.admission"),
    ]
    cp = critical_path(spans)
    assert cp["e2e_ms"] == pytest.approx(12000.0)
    assert sum(cp["blame_ms"].values()) == pytest.approx(cp["e2e_ms"])
    assert cp["blame_ms"]["scheduler.wait"] == pytest.approx(2000.0)


def test_child_starting_before_parent_is_clamped():
    """Regression pin: retroactive wait spans and stitched responder
    flows can START before their parent span. The walk clamps every
    child's window inside its parent's, so the early overhang cannot be
    charged twice (it blew pay blame to 4× e2e on full ledger runs)."""
    spans = [
        _span("flow.run", "r", start=0.0, dur=10.0, flow_type=PAY),
        _span("tx.verify", "a", "r", start=2.0, dur=4.0),     # [2,6]
        # recorded retroactively: starts 2s before its parent
        _span("wait.verify_park", "g", "a", start=0.0, dur=5.0,
              wait_kind="verify.park"),                        # [0,5]
    ]
    cp = critical_path(spans)
    assert cp["e2e_ms"] == pytest.approx(10000.0)
    assert sum(cp["blame_ms"].values()) == pytest.approx(10000.0)
    assert cp["blame_ms"] == {"flow.compute": pytest.approx(6000.0),
                              "verify": pytest.approx(4000.0)}


def test_zero_duration_children_are_safe():
    spans = [
        _span("flow.run", "r", start=0.0, dur=1.0, flow_type=PAY),
        _span("vault.update", "z", "r", start=0.5, dur=0.0),
    ]
    cp = critical_path(spans)
    assert cp["blame_ms"] == {"flow.compute": pytest.approx(1000.0)}


def test_parent_pointer_cycle_terminates():
    # x and y point at each other under a healthy root: the walk must not
    # hang, and the root's decomposition stays conserved
    spans = _commit_tree() + [
        _span("raft.append", "x", "y", start=3.0, dur=1.0),
        _span("raft.append", "y", "x", start=3.0, dur=1.0),
    ]
    cp = critical_path(spans)
    assert sum(cp["blame_ms"].values()) == pytest.approx(cp["e2e_ms"])
    # a PURE cycle has no root at all: None, not an infinite loop
    cycle_only = [_span("raft.append", "x", "y", start=0.0, dur=1.0),
                  _span("raft.append", "y", "x", start=0.0, dur=1.0)]
    assert critical_path(cycle_only) is None


def test_empty_and_rootless_traces_return_none():
    assert critical_path([]) is None
    assert critical_path([{"bogus": 1}]) is None
    # root with zero duration and no pre-root wait: nothing to decompose
    assert critical_path([_span("flow.run", "r")]) is None


def test_component_taxonomy():
    # every wait_kind maps into the fixed component set
    for kind, comp in WAIT_KINDS.items():
        assert comp in COMPONENTS
        assert component_of(_span("wait.x", "s", wait_kind=kind)) == comp
    assert component_of(_span("flow.run", "s")) == "flow.compute"
    assert component_of(_span("vault.update", "s")) == "vault"
    assert component_of(_span("session.send", "s")) == "network"
    assert component_of(_span("mystery.thing", "s")) == "other"


def test_flow_kind_classification():
    assert flow_kind("corda_tpu.finance.cash.CashIssueFlow") == "issue"
    assert flow_kind(PAY) == "pay"
    assert flow_kind("corda_tpu.finance.trade.SellerFlow") == "settle"
    assert flow_kind("x.CommercialPaperIssueFlow") == "settle"
    assert flow_kind("corda_tpu.flows.library.NotaryServiceFlow") is None
    assert flow_kind(None) is None


def _traces_of(kind_durations):
    """One single-span flow.run trace per (flow_type, duration)."""
    traces = {}
    for i, (ftype, dur) in enumerate(kind_durations):
        tid = f"t{i}"
        s = _span("flow.run", f"s{i}", start=0.0, dur=dur, flow_type=ftype)
        s["trace_id"] = tid
        traces[tid] = [s]
    return traces


def test_aggregate_per_class_percentile_vectors():
    issue = "corda_tpu.finance.cash.CashIssueFlow"
    traces = _traces_of([(PAY, d) for d in (1.0, 2.0, 3.0, 4.0, 5.0)]
                        + [(issue, 9.0)])
    agg = aggregate_critpaths(traces, top_k=2)
    assert agg["traces"] == 6
    pay = agg["per_class"]["pay"]
    assert pay["n"] == 5
    assert pay["e2e_ms_p50"] == pytest.approx(3000.0)
    assert pay["e2e_ms_p99"] == pytest.approx(5000.0)
    # the p50 VECTOR is the p50 transaction's own decomposition: conserved
    assert sum(pay["blame_p50"].values()) == pytest.approx(3000.0)
    assert agg["per_class"]["issue"]["dominant"] == "flow.compute"
    # top-K slowest first, capped
    assert [cp["e2e_ms"] for cp in agg["top"]] == [9000.0, 5000.0]


def test_critpath_cli_render_is_pure_and_tolerant():
    from corda_tpu.tools.critpath import render
    report = critpath_report({"t1": _commit_tree()}, top_k=3)
    text = render(report)
    assert "critical paths over 1 traces" in text
    assert "pay" in text and "notary.batch_wait" in text
    assert "[notary.commit]" in text
    # malformed / empty payloads render, never raise
    assert "0 traces" in render({})
    assert render({"per_class": "junk", "top": [None, {"segments": "x"}]})


def test_critpath_cli_jsonl_replay(tmp_path):
    from corda_tpu.tools.critpath import report_from_jsonl
    import json
    p = tmp_path / "spans.jsonl"
    lines = [json.dumps(s) for s in _commit_tree()] + ["{not json", ""]
    p.write_text("\n".join(lines), encoding="utf-8")
    report = report_from_jsonl(str(p), top_k=5)
    assert report["traces"] == 1
    assert report["per_class"]["pay"]["dominant"] == "notary.batch_wait"