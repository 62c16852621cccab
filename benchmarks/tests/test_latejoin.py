"""The ``crosscash-deepchain`` deployment's own tests: the plain reference's
unit tests (ids, signatures, ancestry, order, conservation), the two readers
the cell brings, and tiny-size CPU rehearsals of the ``latejoin`` driver: a
sound run, the traced run's per-layer metrics, the control coming out
``correct: false`` and a timed path broken underneath."""
import hashlib
import json
import pathlib

import pytest

import run as bench_run
from reference import crosscash_deepchain as ref

BENCH = pathlib.Path(__file__).resolve().parents[1]
SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
CELL = "crosscash-deepchain.latejoin"
TINY = {"parties": 4, "chain_depth": 24, "history_flow_moves": 6,
        "hostile_chain_depth": 6, "warmup_ops": 2, "rate_tx_per_s": 1.0,
        "drain_limit_s": 30.0}


# -- the plain reference ---------------------------------------------------------------

def _keys(n):
    from cryptography.hazmat.primitives import serialization
    from cryptography.hazmat.primitives.asymmetric.ed25519 import \
        Ed25519PrivateKey
    out = []
    for i in range(n):
        sk = Ed25519PrivateKey.from_private_bytes(bytes([i + 1]) * 32)
        out.append((sk, sk.public_key().public_bytes(
            serialization.Encoding.Raw, serialization.PublicFormat.Raw)))
    return out


def _tx(tag, inputs, amounts, signers):
    leaves = [hashlib.sha256(f"{tag}/{k}".encode()).digest() for k in range(3)]
    tx_id = ref.merkle_root(leaves)
    return ref.raw(tx_id, leaves, inputs,
                   [(pub, sk.sign(tx_id)) for sk, pub in signers], amounts)


def _chain(depth):
    """An issue of 1000 and ``depth`` moves of 10 out of it, change first."""
    (w, n) = _keys(2)
    txs, order = {}, []
    issue = _tx("issue", [], [1000], [w])
    txs[issue["id"]] = issue
    order.append(issue["id"])
    left = 1000
    for i in range(depth):
        left -= 10
        tx = _tx(f"move{i}", [(order[-1], 0)], [left, 10], [w, n])
        txs[tx["id"]] = tx
        order.append(tx["id"])
    return txs, order


def test_reference_ids_are_hashlib_merkle_roots_over_padded_leaves():
    leaves = [hashlib.sha256(bytes([k])).digest() for k in range(3)]
    h = lambda a, b: hashlib.sha256(a + b).digest()     # noqa: E731
    assert ref.merkle_root(leaves) == h(h(leaves[0], leaves[1]),
                                        h(leaves[2], bytes(32)))
    txs, order = _chain(4)
    assert ref.bad_ids(txs, order) == 0
    txs[order[2]]["leaves"][0] = bytes(32)
    assert ref.bad_ids(txs, order) == 1


def test_reference_checks_every_signature_of_every_transaction():
    txs, order = _chain(5)
    assert ref.bad_signatures(txs, order) == 0
    pub, sig = txs[order[3]]["sigs"][1]
    txs[order[3]]["sigs"][1] = (pub, bytes([sig[0] ^ 0xFF]) + sig[1:])
    assert ref.bad_signatures(txs, order) == 1
    other = txs[order[4]]["sigs"][0][0]
    txs[order[2]]["sigs"][1] = (other, txs[order[2]]["sigs"][1][1])
    assert ref.bad_signatures(txs, order) == 2
    assert ref.bad_signatures(txs, order[:2]) == 0


def test_reference_ancestry_order_and_conservation():
    txs, order = _chain(6)
    assert ref.ancestry(txs, order[4]) == set(order[:5])
    assert ref.descendants(txs, order[4]) == set(order[4:])
    clean = {"missing": 0, "extra": 0, "recorded_twice": 0,
             "order_violations": 0, "bad_ids": 0, "bad_signatures": 0,
             "unbalanced": 0}
    assert ref.judge_join(txs, order[-1], order) == clean
    # a child recorded before its parent, one left out, one too many
    swapped = order[:2] + [order[3], order[2]] + order[4:]
    assert ref.judge_join(txs, order[-1], swapped)["order_violations"] == 1
    assert ref.judge_join(txs, order[-1], order[1:])["missing"] == 1
    assert ref.judge_join(txs, order[-1], order[1:])["order_violations"] == 1
    assert ref.judge_join(txs, order[3], order)["extra"] == 3
    assert ref.judge_join(txs, order[-1], order + order[:1])[
        "recorded_twice"] == 1
    # a diamond: two paths to one ancestor, walked once
    (w, _n) = _keys(2)
    top = _tx("merge", [(order[-1], 0), (order[-1], 1), (order[-2], 1)],
              [txs[order[-1]]["amounts"][0] + 20], [w])
    txs[top["id"]] = top
    assert ref.ancestry(txs, top["id"]) == set(order) | {top["id"]}
    assert ref.unbalanced(txs, list(txs)) == 0
    txs[order[2]]["amounts"][0] += 1        # keeps more than it holds
    assert ref.unbalanced(txs, list(txs)) == 2      # itself, and its spender
    assert ref.judge_refusal(txs, order[3], order[:3]) == 0
    assert ref.judge_refusal(txs, order[3], order[:5]) == 2
    assert ref.consumed_set([(b"a", [1, 2]), (b"b", [2, 3])]) == \
        {1: b"a", 2: b"a", 3: b"b"}


# -- the readers the cell brings --------------------------------------------------------

def test_span_readers_of_the_cell():
    def span(name, start, dur, **tags):
        return {"trace_id": "t", "span_id": name + str(start),
                "parent_id": None, "name": name, "start_s": start,
                "duration_s": dur, "tags": tags}
    spans = [span("resolve.walk", 10.0, 2.0, hops=1000),
             span("resolve.walk", 11.0, 0.004, hops=1),
             span("resolve.walk", 13.0, 4.0, hops=1001),
             span("resolve.walk", 31.0, 3.0, hops=1002),     # in the drain
             span("resolve.walk", 5.0, 9.0, hops=999),       # the warm-up's
             span("flow.step", 12.0, 0.5, checkpoint_s=0.2,
                  flow_type="x.NotifyTransactionHandler"),
             span("flow.step", 12.5, 0.5, checkpoint_s=0.4,
                  flow_type="x.FetchTransactionsHandler")]
    data = {"spans": spans, "window_wall": (9.0, 30.0),
            "snap0": {"Resolve.Hops": {"count": 1000}},
            "snap1": {"Resolve.Hops": {"count": 4004}}}
    where = bench_run.load_module("readers", "span_duration_quantile_where")
    assert where.read(data, span="resolve.walk", q=0.5, tag="hops",
                      at_least=16) == pytest.approx(2000.0)
    assert where.read(data, span="resolve.fetch", q=0.5, tag="hops",
                      at_least=16) is None
    per = bench_run.load_module("readers", "span_sum_per_count")
    assert per.read(data, spans=["resolve.walk"], counter="Resolve.Hops") == \
        pytest.approx(1000 * 9.004 / 3004)
    assert per.read(data, spans=["flow.step"], counter="Resolve.Hops",
                    tag="checkpoint_s",
                    flow_types=["NotifyTransactionHandler"]) == \
        pytest.approx(1000 * 0.2 / 3004)
    assert per.read(data, spans=["nope"], counter="Resolve.Hops") is None
    assert per.read({"spans": spans}, spans=["resolve.walk"],
                    counter="Resolve.Hops") is None
    assert per.read(dict(data, snap1=data["snap0"]), spans=["resolve.walk"],
                    counter="Resolve.Hops") is None


def test_the_cell_has_its_files():
    cell = bench_run.Cell(CELL, SPEC)
    assert cell.driver_name == "latejoin" and cell.chips == 1
    assert set(cell.end_to_end_names()) == {"commit_ms_p50", "setup_s"}
    assert len(cell.layer_metric_files()) == 11
    cfg = cell.config
    assert cfg["chain_depth"] == cell.traffic["chain_depth"]
    assert set(cfg["reduced"]) == set(next(
        c for c in SPEC["configs"]
        if c["name"] == "crosscash-deepchain")["reduced"])
    assert len(cfg["source"]) <= 200
    text = (BENCH / "reference" / "crosscash_deepchain.py").read_text()
    assert "corda_tpu" not in text.replace("``corda_tpu``", "")


# -- tiny-size CPU rehearsals: control flow only, no device metric printed ------------

def rehearse(capsys, seconds=4.0, control=None, trace=False, notes=None):
    """``notes``, when given, gets every earlier line of the run: the notes,
    and each number compared beside its limit."""
    cell = bench_run.Cell(CELL, SPEC)
    result = bench_run.run_cell(cell, 3_000_000_023, seconds, trace, CPU,
                                control=control, scale=TINY,
                                quiet=notes is None)
    out = capsys.readouterr().out
    if notes is None:
        assert out == ""
    else:
        notes.extend(json.loads(line) for line in out.splitlines())
    return result


def test_latejoin_rehearsal(capsys):
    notes: list = []
    result = rehearse(capsys, notes=notes)
    checks = {c["check"]: c for c in notes if "check" in c}
    assert result["correct"], [c for c in checks.values() if not c["ok"]]
    assert result["attempted"] == 4 and result["failed"] == 0
    assert set(result["metrics"]) == {"commit_ms_p50", "setup_s"}
    assert all(c["limit"] == 0 for c in checks.values())
    assert {"joiner_missing", "joiner_order_violations",
            "joiner_bad_signatures", "hostile_joins_accepted",
            "hostile_joins_held_at_or_below_bad", "device_id_mismatches",
            "reference_consumed_set_diff", "compiles_after_mark_warm",
            "cash_issued_minus_held_minus_refused"} <= set(checks)
    hostile = next(n for n in notes if n.get("note") == "hostile")
    assert [j["kind"] for j in hostile["joins"]] == [
        "flipped_signature", "wrong_signer_key", "withheld"]
    assert all(j["refused"] for j in hostile["joins"])
    reference = next(n for n in notes if n.get("note") == "reference")
    # 2 warm-up joins + 4 in the window, each the whole chain so far
    assert reference["joins"] == 6
    assert reference["joiner_transactions"] == sum(26 + k for k in range(6))


def test_latejoin_traced_rehearsal_reads_its_layer_metrics(capsys):
    result = rehearse(capsys, trace=True)
    assert result["correct"]
    assert {"resolve_ms_p50.latejoin", "resolve_fetch_ms_p50.latejoin",
            "resolve_verify_ms_p50.latejoin", "resolve_record_ms_p50.latejoin",
            "resolve_order_ms_p50.latejoin", "resolve_hop_ms.latejoin",
            "checkpoint_ms_per_hop.latejoin", "verify_span_ms_p50.latejoin",
            "host_inline_share.latejoin", "device_route_share.latejoin",
            "device_idle_share.latejoin"} <= set(result["metrics"])
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert m["resolve_ms_p50.latejoin"] >= m["resolve_fetch_ms_p50.latejoin"]
    assert m["device_route_share.latejoin"] == 0.0


def test_latejoin_control_comes_out_not_correct(capsys):
    notes: list = []
    result = rehearse(capsys, control="unchecked_backchain", notes=notes)
    assert result["correct"] is False
    failed = {c["check"] for c in notes if "check" in c and not c["ok"]}
    assert "hostile_joins_accepted" in failed


def test_latejoin_skipped_verify_comes_out_not_correct(capsys, monkeypatch):
    """A timed path broken underneath: a walk that records what it fetched
    without verifying it accepts the hostile chains."""
    from corda_tpu.node.statemachine import StateMachineManager

    def waved_through(self, fsm, request):
        return self._log(fsm, ("value", None))

    monkeypatch.setattr(StateMachineManager, "_do_verify_many", waved_through)
    result = rehearse(capsys)
    assert result["correct"] is False


def test_latejoin_lost_record_comes_out_not_correct(capsys, monkeypatch):
    """A step that leaves its state unchanged: the joiners' stores forget
    every tenth transaction they are handed."""
    from corda_tpu.node.services import TransactionStorage
    sound = TransactionStorage.add_transaction
    seen = {"n": 0}

    def forgetful(self, stx, notify=True):
        seen["n"] += 1
        if seen["n"] % 10 == 0 and len(stx.inputs) == 1:
            return False
        return sound(self, stx, notify)

    monkeypatch.setattr(TransactionStorage, "add_transaction", forgetful)
    result = rehearse(capsys)
    assert result["correct"] is False
