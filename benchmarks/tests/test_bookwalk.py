"""The ``traderdemo-replay`` deployment's own tests: the cell's files
(wherever its entries stand in ``BENCHMARK.json``'s lists), the plain
reference (imports nothing of the program, judges each altered kind for its
own reason), and tiny-size CPU rehearsals of the ``bookwalk`` driver: a sound
run, both controls, the traced run's span and counter metrics, and a
parent-shaped program refused before a book is made or a kernel loaded.
Every rehearsal dispatches the secp256k1 kernel at 8 rows, the one rung of
the ladder here (a shape the k1 and mixed rehearsals compile too)."""
import json
import pathlib

import pytest

import run as bench_run
import trader_books
from reference import traderdemo_replay as ref

BENCH = pathlib.Path(__file__).resolve().parents[1]
SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
CELL = "traderdemo-replay.bookwalk"
TINY = {"books": 8, "pool_books": 8, "book_trades": 8, "request_trades": 8,
        "banks": 6, "clients": 2, "warm_answers": 6, "altered_every": 2,
        "batcher_args": {"max_batch": 8, "host_crossover": 0,
                         "bucket_ladder": [8]}}
METRICS = ["device_idle_share.bookwalk", "device_route_share.bookwalk",
           "secp256k1_kernel_ms.bookwalk", "secp256k1_roofline.bookwalk",
           "batch_rows_mean.bookwalk", "verify_levels_ms_p50.bookwalk",
           "levels_verdict_wait_ms_per_tx.bookwalk",
           "wave_rules_ms_per_tx.bookwalk", "contract_ms_per_tx.bookwalk"]
KERNEL_METRICS = {"secp256k1_kernel_ms.bookwalk",
                  "secp256k1_roofline.bookwalk"}


def rehearse(capsys, seconds=3.0, control=None, trace=False,
             seed=3_000_000_049, scale=TINY):
    cell = bench_run.Cell(CELL, SPEC)
    notes: list = []
    result = bench_run.run_cell(cell, seed, seconds, trace, CPU,
                                control=control, scale=scale, quiet=True,
                                notes=notes)
    assert capsys.readouterr().out == ""      # nothing under a metric's name
    return result, {n["note"]: n for n in notes}


def test_the_cell_has_its_files_wherever_its_entries_stand():
    cell = bench_run.Cell(CELL, SPEC)
    assert cell.driver_name == "bookwalk" and cell.chips == 1
    assert cell.end_to_end_names() == ["tx_per_s", "setup_s"]
    assert sorted(lm["name"] for lm in cell.layer_metric_files()) \
        == sorted(METRICS)
    for lm in cell.layer_metric_files():
        assert lm["workloads"] == [CELL] and lm["moves"] == "tx_per_s"
        assert (BENCH / "readers" / f"{lm['reader']}.py").is_file()
    listed = {m["name"]: m for m in SPEC["per_layer"]}
    for name in METRICS:
        assert listed[name]["workloads"] == [CELL]
        assert listed[name]["moves"] == "tx_per_s"
        assert "bound" not in listed[name]
    # one unbroken run in their order, wherever later metrics are appended
    order = [m["name"] for m in SPEC["per_layer"]]
    at = order.index(METRICS[0])
    assert order[at:at + len(METRICS)] == METRICS
    assert len(order) == len(set(order)) <= 128
    assert [w["name"] for w in SPEC["workloads"]].count(CELL) == 1
    (tx,) = [m for m in SPEC["end_to_end"] if m["name"] == "tx_per_s"]
    assert CELL in tx["workloads"] and tx["bound"] == 0.05
    config, traffic = cell.config, cell.traffic
    assert config["batcher_args"]["max_batch"] == 8192
    ladder = config["batcher_args"]["bucket_ladder"]
    assert ladder[0] == 256 and ladder[-1] == 8192 and len(ladder) <= 3
    assert config["schemes"] == ["secp256k1"] and config["banks"] == 64
    # 8 requests of 1,280 transactions outstanding, four times that distinct
    assert (traffic["clients"], traffic["request_trades"],
            traffic["pool_books"], traffic["warm_answers"]) == (8, 256, 32, 24)
    assert (traffic["pool_books"], traffic["request_trades"]) \
        == (config["books"], config["book_trades"])
    assert 5 * traffic["request_trades"] < 5000     # one walk's cap
    assert config["books"] * 5 * config["book_trades"] == 40960 \
        == 4 * traffic["clients"] * 5 * traffic["request_trades"]
    assert (config["altered_every"], config["altered_kinds"]) == (8, 4)
    assert len(config["altered_kind_names"]) == 4
    assert traffic["loop"] == "closed"
    assert 2 <= traffic["trace_seconds"] <= 8
    assert set(config["reduced"]) == {"schemes", "flows"}
    assert {"banks", "book", "cash", "attachment", "redemption", "amounts",
            "signer", "altered", "max_batch", "collector"} \
        <= set(config["assumed"])
    assert config["architecture"] is None
    (row,) = [c for c in SPEC["configs"] if c["name"] == "traderdemo-replay"]
    assert row["reduced"] == ["schemes", "flows"]
    assert row["source"] == config["source"] and len(row["source"]) <= 200
    assert "TraderDemoClientApi.kt" in row["source"]
    assert (BENCH / "traderdemo-replay.md").is_file()


def test_the_reference_imports_nothing_of_the_program_or_the_benchmark():
    source = (BENCH / "reference" / "traderdemo_replay.py").read_text()
    imports = [line.split()[1] for line in source.splitlines()
               if line.lstrip().startswith(("import ", "from "))]
    assert set(imports) <= {"__future__", "functools", "hashlib",
                            "cryptography.exceptions",
                            "cryptography.hazmat.primitives",
                            "cryptography.hazmat.primitives.asymmetric"}


@pytest.mark.parametrize("kind", [None, *range(len(trader_books.KINDS))])
def test_the_reference_judges_each_altered_kind_for_its_own_reason(kind):
    made = trader_books.make_book((5, 8, 6, kind))
    facts = made["facts"]
    assert ref.judge(facts) == tuple(made["expect"])
    if kind is None:
        assert made["expect"] == (40, ref.VALID)
        # a signature is over the id and nothing else
        blobs = facts[0]["blobs"]
        tampered = dict(facts[0], blobs=[
            blobs[0][:-1] + bytes([blobs[0][-1] ^ 1]), *blobs[1:]])
        assert ref.judge([tampered]) == (0, ref.BAD_SIGNATURE)
        assert ref.judge([dict(facts[0], sigs=[])]) == (0, ref.MISSING)
        # inputs resolve from EARLIER members only
        assert ref.judge(facts[24:]) == (0, ref.RESOLUTION)
        assert ref.judge(facts[:24] + facts[32:]) == (24, ref.RESOLUTION)
        return
    at, why = made["expect"]
    assert why == trader_books.CLASSES[kind]
    level = trader_books.LEVELS[kind]
    assert (0, 24, 32)[level] <= at < (24, 32, 40)[level]
    fact = facts[at]
    tx_id = ref.transaction_id(fact["blobs"])
    valid = [ref.signature_valid(*sig, tx_id) for sig in fact["sigs"]]
    signers = {sig[:2] for sig in fact["sigs"]}
    if why == ref.BAD_SIGNATURE:
        assert valid == [False, True, True]
    else:
        # every signature there verifies: it fails for its own reason
        assert all(valid)
        assert (set(fact["required"]) <= signers) == (why != ref.MISSING)
    if kind == 3:       # every NAMED signer signed; the owner is not named
        assert len(fact["sigs"]) == 2
    # the honest book's member at that place passes
    honest = trader_books.make_book((5, 8, 6, None))["facts"]
    assert ref.judge(facts[:at] + [honest[at]]) == (at + 1, ref.VALID)


def test_bookwalk_rehearsal(capsys):
    result, notes = rehearse(capsys)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 6
    assert set(result["metrics"]) == {"tx_per_s", "setup_s"}
    window = notes["window"]
    assert result["metrics"]["tx_per_s"]["value"] == window["tx_per_s"] > 0
    assert window["verified_inside"] \
        == round(window["tx_per_s"] * window["window_s"])
    # a failed book counts what passed before its altered member
    assert window["verified_inside"] < 40 * window["answers_inside"]
    assert notes["books"]["level_transactions"] == [24, 8, 8]
    assert notes["books"]["level_rows"] == [32, 24, 24]
    assert sorted(notes["books"]["altered"]) == [1, 3, 5, 7]
    assert set(map(int, notes["reference"]["altered_judged_by_kind"])) \
        == set(range(4))
    assert notes["warm"]["padded_rows_run"] == ["8"]
    assert set(notes["warm"]["first_call_s"]) == {"secp256k1@8"}
    assert set(notes["batcher"]["flushes_by_padded_rows"]) == {"8"}
    assert notes["batcher"]["host_rows_in_window"] == 0
    assert notes["batcher"]["device_rows_in_window"] > 0


@pytest.mark.parametrize("control,blind_to", [
    ("unchecked_rows", {0}), ("rules_skipped", {1, 2, 3})])
def test_bookwalk_control_comes_out_not_correct(capsys, control, blind_to):
    result, notes = rehearse(capsys, control=control)
    assert not result["correct"]
    value, limit, ok = result["checks"]["answers_differing_from_reference"]
    assert value > 0 and limit == 0 and not ok
    # the kinds the broken rule cannot see are the ones it lets through
    judged = set(map(int, notes["reference"]["altered_judged_by_kind"]))
    assert judged == set(range(4)) - blind_to
    assert result["checks"]["reference_disagrees_with_altered_set"][2]
    from corda_tpu.finance.cash import Cash
    from corda_tpu.finance.commercial_paper import CommercialPaper
    assert Cash.verify.__name__ == CommercialPaper.verify.__name__ == "verify"


def test_bookwalk_traced_rehearsal_reads_its_layer_metrics(capsys):
    result, notes = rehearse(capsys, trace=True)
    assert result["correct"], result["checks"]
    # the CPU's trace holds no device program: the kernel's two metrics
    # read nothing and are left out
    assert set(result["metrics"]) == set(METRICS) - KERNEL_METRICS
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert m["device_route_share.bookwalk"] == 100.0
    assert 0 < m["batch_rows_mean.bookwalk"] <= 8
    assert notes["spans"]["walks"] >= result["attempted"]
    for name in ("verify_levels_ms_p50.bookwalk",
                 "levels_verdict_wait_ms_per_tx.bookwalk",
                 "wave_rules_ms_per_tx.bookwalk",
                 "contract_ms_per_tx.bookwalk"):
        assert 0 < m[name] < 60000, name
    # the contracts are a part of the rules pass, resolution the rest
    assert m["contract_ms_per_tx.bookwalk"] \
        < m["wave_rules_ms_per_tx.bookwalk"]


@pytest.mark.parametrize("lacks", ["verify_levels", "generate_redeem",
                                   "trader_ledger"])
def test_a_parent_shaped_program_is_refused(capsys, monkeypatch, lacks):
    """Any parent of PR 49: the run ends on set-up's first call (exit 2 from
    the command), before a book is made or a kernel loaded."""
    import sys
    from corda_tpu.finance.commercial_paper import CommercialPaper
    from corda_tpu.verifier.batcher import SignatureBatcher
    from corda_tpu.verifier.service import TpuTransactionVerifierService
    if lacks == "verify_levels":
        monkeypatch.delattr(TpuTransactionVerifierService, "verify_levels")
    elif lacks == "generate_redeem":
        monkeypatch.delattr(CommercialPaper, "generate_redeem")
    else:
        import corda_tpu.testing
        monkeypatch.setitem(sys.modules, "corda_tpu.testing.trader_ledger",
                            None)
        monkeypatch.delattr(corda_tpu.testing, "trader_ledger",
                            raising=False)
    monkeypatch.setattr(SignatureBatcher, "__init__",
                        lambda *a, **k: pytest.fail("a batcher was built"))
    monkeypatch.setattr(trader_books, "make_book",
                        lambda job: pytest.fail("a book was made"))
    with pytest.raises(bench_run.BenchError, match=lacks):
        rehearse(capsys)


def test_the_kernel_is_read_at_the_rung_a_level_runs_at():
    """``readers/trace_kernel_at_rung.py``: the shape that ran most often,
    held to the rung the traffic names by the program's own dispatch spans;
    the rare merged flush at the top rung does not enter the mean."""
    import kernel_cost_ecdsa
    cell = bench_run.Cell(CELL, SPEC)
    files = {lm["name"]: lm for lm in cell.layer_metric_files()}
    ms, roof = (files["secp256k1_kernel_ms.bookwalk"],
                files["secp256k1_roofline.bookwalk"])
    assert {m["reader"] for m in (ms, roof)} == {"trace_kernel_at_rung"}
    rung = cell.traffic[ms["args"]["rung_param"]]
    assert rung in cell.config["batcher_args"]["bucket_ladder"][1:-1]
    reader = bench_run.load_module("readers", "trace_kernel_at_rung")
    modules = [["jit_verify_core_hybrid_wide(1)", 1e9, 5.0e6],
               ["jit_verify_core_hybrid_wide(1)", 2e9, 5.4e6],
               ["jit_verify_core_hybrid_wide(2)", 3e9, 29.0e6],  # merged
               ["jit_verify_core_hybrid_wide(1)", 4e9, 5.2e6],
               ["jit_other(3)", 6e9, 1e9]]
    events = {"window": [0.0, 10e9],
              "devices": {"/device:TPU:0": {"XLA Modules": modules}}}

    def span(rows, end):
        return {"name": "batcher.dispatch", "start_s": 1000.0 + end - 0.01,
                "duration_s": 0.01,
                "tags": {"route": "device", "batch_size": rows}}

    data = {"trace": {"events": events, "window_s": 10.0},
            "device": {"kind": "TPU v5 lite"},
            "peaks": json.loads((BENCH / "peaks.json").read_text()),
            "cell": cell, "trace_wall_t0": 1000.0,
            "spans": [span(rung, 1.0), span(rung - 256, 2.0),
                      span(3 * rung - 512, 3.0), span(rung, 4.0),
                      span(rung, 11.0)]}
    least_s = kernel_cost_ecdsa.secp256k1_hybrid(rung)["bytes"] / 819e9
    assert reader.read(data, **ms["args"]) == pytest.approx(5.2)
    assert reader.read(data, **roof["args"]) == pytest.approx(
        100 * least_s / 5.2e-3)
    assert reader.read(data, **roof["args"]) < 100
    # the spans say the window mostly ran ANOTHER rung: not this metric's
    merged = dict(data, spans=[span(3 * rung, e) for e in (1.0, 2.0, 3.0)])
    assert reader.read(merged, **ms["args"]) is None
    # no spans, no sub-window's start, a tie, no call: nothing to read
    assert reader.read(dict(data, spans=[]), **ms["args"]) is None
    assert reader.read(dict(data, trace_wall_t0=None), **ms["args"]) is None
    del modules[3]
    del modules[1]
    assert reader.read(data, **ms["args"]) is None          # one call each
    del modules[:2]
    assert reader.read(data, **roof["args"]) is None
    assert reader.read({"trace": None}, **ms["args"]) is None
