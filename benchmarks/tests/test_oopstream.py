"""The ``genledger-oop`` deployment's own tests: the cell's files, the plain
reference's judgement of each invalid kind, and tiny-size CPU rehearsals of
the ``oopstream`` driver with the requestor as a real child process over TCP
loopback: a sound run, the control, the traced run's span and counter
metrics, and a program whose batcher does not meter its flushes' shapes
refused after set-up. Every
rehearsal dispatches the Ed25519 kernel at 8 and 16 rows (the ladder's two
rungs here), which the file compiles once."""
import json
import pathlib

import pytest

import oop_ledgers
import run as bench_run
from reference import genledger_oop as ref

BENCH = pathlib.Path(__file__).resolve().parents[1]
SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
CELL = "genledger-oop.stream"
TINY = {"ledgers": 2, "ledger_transactions": 64, "invalid_every": 8,
        "party_keys": 4, "outstanding": 16, "warm_responses": 32,
        "batcher_args": {"max_batch": 16, "host_crossover": 0,
                         "bucket_ladder": [8, 16]}}
METRICS = ["device_route_share.stream", "device_idle_share.stream",
           "batch_rows_mean.stream", "flush_full_share.stream",
           "batch_prep_ms_p50.stream", "ed25519_kernel_ms.stream",
           "ed25519_roofline.stream", "worker_decode_ms_per_tx.stream",
           "worker_host_verify_ms_per_tx.stream",
           "worker_reply_ms_per_tx.stream",
           "worker_backlog_wait_ms_p50.stream",
           "ed25519_partial_call_share.stream"]
KERNEL_METRICS = {"ed25519_kernel_ms.stream", "ed25519_roofline.stream",
                  "ed25519_partial_call_share.stream"}


def rehearse(capsys, seconds=2.0, control=None, trace=False,
             seed=3_000_000_023, scale=TINY):
    cell = bench_run.Cell(CELL, SPEC)
    notes: list = []
    result = bench_run.run_cell(cell, seed, seconds, trace, CPU,
                                control=control, scale=scale, quiet=True,
                                notes=notes)
    assert capsys.readouterr().out == ""      # nothing under a metric's name
    return result, {n["note"]: n for n in notes}


def test_the_cell_has_its_files():
    cell = bench_run.Cell(CELL, SPEC)
    assert cell.driver_name == "oopstream" and cell.chips == 1
    assert cell.end_to_end_names() == ["tx_per_s", "setup_s"]
    assert sorted(lm["name"] for lm in cell.layer_metric_files()) \
        == sorted(METRICS)
    for lm in cell.layer_metric_files():
        assert lm["workloads"] == [CELL] and lm["moves"] == "tx_per_s"
    listed = {m["name"]: m for m in SPEC["per_layer"]}
    for name in METRICS:
        assert listed[name]["workloads"] == [CELL]
        assert "bound" not in listed[name]
    config = cell.config
    assert config["batcher_args"]["max_batch"] == 8192
    assert config["batcher_args"]["bucket_ladder"][-1] == 8192
    assert len(config["batcher_args"]["bucket_ladder"]) <= 3
    assert config["ledgers"] * config["ledger_transactions"] == 32768 \
        == 2 * cell.traffic["outstanding"]
    assert set(config["reduced"]) == {"schemes"}
    assert {"transactions", "outstanding", "party_keys", "generator",
            "invalid", "max_batch"} <= set(config["assumed"])
    assert cell.traffic["bucket_rows"] == config["batcher_args"]["max_batch"]
    (row,) = [c for c in SPEC["configs"] if c["name"] == "genledger-oop"]
    assert row["reduced"] == ["schemes"] and row["source"] == config["source"]
    assert "VerifierTests.kt:37-100" in row["source"]


def test_the_reference_imports_nothing_of_the_program():
    for name in ("genledger_oop.py", "crosscash_raft.py"):
        source = (BENCH / "reference" / name).read_text()
        assert "import corda_tpu" not in source
        assert "from corda_tpu" not in source


def test_the_reference_judges_each_invalid_kind_for_its_own_reason():
    made = oop_ledgers.make_ledger((5, 64, 4, 8, 0))
    want = ref.verdicts(made["facts"])
    assert sorted(made["kinds"].values()) == [0, 0, 1, 1, 2, 2, 3, 3]
    for i, v in enumerate(want):
        assert v == (oop_ledgers.VERDICTS[made["kinds"][i]]
                     if i in made["kinds"] else ref.VALID), i
    # a signature is over the id and nothing else: one altered component
    # makes every signature of a valid transaction fail
    blobs, sigs, required = made["facts"][
        next(i for i in range(64) if i not in made["kinds"])]
    tampered = [blobs[0][:-1] + bytes([blobs[0][-1] ^ 1]), *blobs[1:]]
    assert ref.verdict((tampered, sigs, required)) == ref.BAD_SIGNATURE
    assert ref.verdict((blobs, sigs, required)) == ref.VALID
    assert ref.verdict((blobs, sigs[:0], required)) == ref.MISSING_SIGNER


def test_the_kernel_is_read_by_shape_and_at_the_top_rung_alone():
    """A partial bucket's cuts run the same program at 256 rows under
    another fingerprint: the mean and the roofline share are the top
    rung's, whatever the calls took, and the lower rungs' calls are a
    share of their own."""
    cell = bench_run.Cell(CELL, SPEC)
    files = {lm["name"]: lm for lm in cell.layer_metric_files()}
    ms, roof, below = (files["ed25519_kernel_ms.stream"],
                       files["ed25519_roofline.stream"],
                       files["ed25519_partial_call_share.stream"])
    assert {m["reader"] for m in (ms, roof, below)} == {"trace_kernel_where"}
    reader = bench_run.load_module("readers", "trace_kernel_where")
    modules = [["jit_verify_core_split(1)", 1e9, 23.0e6],
               ["jit_verify_core_split(2)", 2e9, 3.0e6],
               ["jit_verify_core_split(2)", 3e9, 30.0e6],   # a stretched one
               ["jit_verify_core_split(1)", 4e9, 24.0e6],
               ["jit_verify_core_split(2)", 5e9, 3.2e6],
               ["jit_other(3)", 6e9, 1e9]]
    events = {"window": [0.0, 10e9],
              "devices": {"/device:TPU:0": {"XLA Modules": modules}}}
    data = {"trace": {"events": events, "window_s": 10.0},
            "device": {"kind": "TPU v5 lite"},
            "peaks": json.loads((BENCH / "peaks.json").read_text()),
            "cell": cell}
    import kernel_cost
    least_s = kernel_cost.ed25519_split(8192)["bytes"] / 819e9
    assert reader.read(data, **ms["args"]) == pytest.approx(23.5)
    assert reader.read(data, **roof["args"]) == pytest.approx(
        100 * least_s / 23.5e-3)
    assert reader.read(data, **below["args"]) == pytest.approx(60.0)
    # a kernel made ten times faster is still found: no time is written down
    fast = [[n, s, d / 10] for n, s, d in modules]
    quick = dict(data, trace=dict(data["trace"], events=dict(
        events, devices={"/device:TPU:0": {"XLA Modules": fast}})))
    assert reader.read(quick, **ms["args"]) == pytest.approx(2.35)
    assert len(modules) == 6 and modules[2][2] == 30.0e6    # nothing edited

    def span(rows, end):
        return {"name": "batcher.dispatch", "start_s": 1000.0 + end - 0.01,
                "duration_s": 0.01,
                "tags": {"route": "device", "batch_size": rows}}

    # ONE shape in the sub-window: the dispatch spans inside it say which
    del modules[3], modules[0]
    assert reader.read(data, **ms["args"]) is None          # cannot be told
    small = dict(data, trace_wall_t0=1000.0,
                 spans=[span(256, 2.0), span(200, 3.0), span(8192, 11.0)])
    assert reader.read(small, **ms["args"]) is None
    assert reader.read(small, **roof["args"]) is None
    assert reader.read(small, **below["args"]) == 100.0
    full = dict(data, trace_wall_t0=1000.0,
                spans=[span(8192, 2.0), span(7000, 3.0), span(256, -1.0)])
    assert reader.read(full, **ms["args"]) == pytest.approx((3 + 30 + 3.2) / 3)
    assert reader.read(full, **below["args"]) == 0.0
    both = dict(data, trace_wall_t0=1000.0,
                spans=[span(8192, 2.0), span(256, 3.0)])
    assert reader.read(both, **below["args"]) is None
    del modules[:3]                             # no call of the program
    assert reader.read(full, **below["args"]) is None
    assert reader.read({"trace": None}, **ms["args"]) is None


def test_oopstream_rehearsal(capsys):
    result, notes = rehearse(capsys)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 32
    assert set(result["metrics"]) == {"tx_per_s", "setup_s"}
    assert result["metrics"]["tx_per_s"]["value"] > 0
    window = notes["window"]
    assert window["responses_inside"] \
        == round(window["tx_per_s"] * window["window_s"])
    assert window["unanswered"] == 0 and window["drained"]
    assert set(notes["reference"]["invalid_refused_by_kind"]) \
        == {"0", "1", "2", "3"} or set(
            notes["reference"]["invalid_refused_by_kind"]) == {0, 1, 2, 3}
    assert notes["warm"]["padded_rows_run"] == ["8", "16"]
    assert set(notes["batcher"]["flushes_by_padded_rows"]) <= {"8", "16"}
    assert notes["batcher"]["host_rows_in_window"] == 0


def test_oopstream_control_comes_out_not_correct(capsys):
    result, _notes = rehearse(capsys, control="unchecked_rows")
    assert not result["correct"]
    value, limit, ok = result["checks"]["answers_differing_from_reference"]
    assert value > 0 and limit == 0 and not ok
    # the requestor's own refusals still match: only the worker was blinded
    assert result["checks"]["requests_not_answered_exactly_once"][2]


def test_oopstream_traced_rehearsal_reads_its_layer_metrics(capsys):
    result, _notes = rehearse(capsys, trace=True)
    assert result["correct"], result["checks"]
    # the CPU's trace holds no device program: the kernel's three metrics
    # read nothing and are left out
    assert set(result["metrics"]) == set(METRICS) - KERNEL_METRICS
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert m["device_route_share.stream"] == 100.0
    assert 0 < m["batch_rows_mean.stream"] <= 16
    assert 0 < m["flush_full_share.stream"] <= 100
    for name in ("worker_decode_ms_per_tx.stream",
                 "worker_host_verify_ms_per_tx.stream",
                 "worker_reply_ms_per_tx.stream",
                 "worker_backlog_wait_ms_p50.stream",
                 "batch_prep_ms_p50.stream"):
        assert 0 < m[name] < 1000, name


def test_a_program_that_does_not_meter_its_flushes_shapes_is_refused(
        capsys, monkeypatch):
    """Any parent of PR 37: the check of the ladder has nothing to read, and
    the run ends after set-up's first calls (exit 2 from the command)."""
    from corda_tpu.verifier.batcher import SignatureBatcher
    monkeypatch.setattr(SignatureBatcher, "_mark_device_flush",
                        lambda self, rows, reason: None)
    with pytest.raises(bench_run.BenchError, match="padded row counts"):
        rehearse(capsys)
