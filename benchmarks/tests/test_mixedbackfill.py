"""The ``genledger-mixed`` deployment's own tests: the cell's files, the plain
reference (imports nothing of the program, judges each altered kind for its
own reason), and tiny-size CPU rehearsals of the ``mixedbackfill`` driver: a
sound run, both controls, the traced run's span and counter metrics, and a
program whose service has no wave entry point refused before a kernel loads.
Every rehearsal dispatches both EC kernels at 8 rows, the one rung of the
ladder here (shapes the k1 and oop rehearsals compile too)."""
import json
import pathlib

import pytest

import mixed_ledgers
import run as bench_run
from reference import genledger_mixed as ref

BENCH = pathlib.Path(__file__).resolve().parents[1]
SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
CELL = "genledger-mixed.backfill"
TINY = {"ledgers": 3, "ledger_transactions": 64, "pool_waves": 3,
        "wave_transactions": 64, "invalid_every": 8, "party_keys": 8,
        "composite_parties": 4, "nested_composites": 1, "clients": 2,
        "warm_verdicts": 128,
        "batcher_args": {"max_batch": 8, "host_crossover": 0,
                         "bucket_ladder": [8]}}
METRICS = ["device_route_share.backfill", "device_idle_share.backfill",
           "ed25519_kernel_ms.backfill", "ed25519_roofline.backfill",
           "secp256k1_kernel_ms.backfill", "secp256k1_roofline.backfill",
           "batch_rows_mean.backfill", "flush_full_share.backfill",
           "batch_prep_ms_p50.backfill", "dispatch_offcpu_share.backfill",
           "ed25519_words_prep_share.backfill",
           "ecdsa_words_prep_share.backfill", "k1_row_share.backfill",
           "wave_bulk_share.backfill", "verify_wave_ms_p50.backfill",
           "wave_submit_ms_per_tx.backfill",
           "wave_coverage_ms_per_tx.backfill",
           "wave_rules_ms_per_tx.backfill",
           "composite_required_share.backfill"]
KERNEL_METRICS = {"ed25519_kernel_ms.backfill", "ed25519_roofline.backfill",
                  "secp256k1_kernel_ms.backfill",
                  "secp256k1_roofline.backfill"}


def rehearse(capsys, seconds=3.0, control=None, trace=False,
             seed=3_000_000_042, scale=TINY):
    cell = bench_run.Cell(CELL, SPEC)
    notes: list = []
    result = bench_run.run_cell(cell, seed, seconds, trace, CPU,
                                control=control, scale=scale, quiet=True,
                                notes=notes)
    assert capsys.readouterr().out == ""      # nothing under a metric's name
    return result, {n["note"]: n for n in notes}


def test_the_cell_has_its_files():
    cell = bench_run.Cell(CELL, SPEC)
    assert cell.driver_name == "mixedbackfill" and cell.chips == 1
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
    # the spec gained entries only: the new ones close their lists
    assert [m["name"] for m in SPEC["per_layer"]][-len(METRICS):] == METRICS
    assert SPEC["workloads"][-1]["name"] == CELL
    assert SPEC["configs"][-1]["name"] == "genledger-mixed"
    (tx,) = [m for m in SPEC["end_to_end"] if m["name"] == "tx_per_s"]
    assert tx["workloads"][-1] == CELL and tx["bound"] == 0.05
    config, traffic = cell.config, cell.traffic
    assert config["batcher_args"] == {"max_batch": 8192,
                                      "bucket_ladder": [256, 8192]}
    assert config["schemes"] == ["ed25519", "secp256k1"]
    assert (config["party_keys"], config["composite_parties"],
            config["nested_composites"], config["notary_replicas"]) \
        == (64, 16, 4, 3)
    assert config["ledgers"] * config["ledger_transactions"] == 32768 \
        == 2 * traffic["clients"] * traffic["wave_transactions"]
    assert (traffic["pool_waves"], traffic["wave_transactions"]) \
        == (config["ledgers"], config["ledger_transactions"])
    assert traffic["wave_transactions"] < 5000      # one walk's cap
    assert traffic["warm_verdicts"] == 49152 and traffic["loop"] == "closed"
    assert traffic["bucket_rows"] == config["batcher_args"]["max_batch"]
    assert 2 <= traffic["trace_seconds"] <= 8
    assert len(config["invalid_kinds"]) == mixed_ledgers.N_INVALID == 8
    assert len(config["valid_shapes"]) == 2
    assert set(config["reduced"]) == {"schemes"}
    assert {"parties", "composite_owners", "notary", "transactions",
            "generator", "invalid", "contract", "signer", "max_batch"} \
        <= set(config["assumed"])
    assert config["collector_thresholds"] == [1000000, 10, 1000000]
    assert "collector" in config["assumed"]
    (row,) = [c for c in SPEC["configs"] if c["name"] == "genledger-mixed"]
    assert row["reduced"] == ["schemes"] and row["source"] == config["source"]
    assert len(row["source"]) <= 200 and "CompositeKey.kt:35" in row["source"]


def test_the_reference_imports_nothing_of_the_program():
    for name in ("genledger_mixed.py", "genledger_secp256k1.py",
                 "crosscash_raft.py"):
        source = (BENCH / "reference" / name).read_text()
        assert "import corda_tpu" not in source
        assert "from corda_tpu" not in source


def test_the_reference_judges_each_altered_kind_for_its_own_reason():
    made = mixed_ledgers.make_ledger((5, 128, 8, 4, 1, 3, 8, 0))
    want = ref.verdicts(made["facts"])
    assert sorted(made["kinds"].values()) \
        == sorted(list(range(8)) * 2 + [8, 8, 9, 9])
    for i, v in enumerate(want):
        assert v == (mixed_ledgers.VERDICTS[made["kinds"][i]]
                     if i in made["kinds"] else ref.VALID), i
    by_kind = {k: i for i, k in made["kinds"].items()}
    # a flat owner with ONE leaf: both signatures verify, coverage fails
    blobs, sigs, required = made["facts"][by_kind[4]]
    tx_id = ref.transaction_id(blobs)
    assert all(ref.signature_valid(s, pub, sig, tx_id)
               for s, pub, sig in sigs)
    assert required[0][0] == ref.COMPOSITE
    threshold, children = ref.decode_composite(required[0][1])
    assert threshold == 2 and [w for w, _s, _c in children] == [1, 1, 1]
    # the nested owner: A alone carries weight 2 of 3
    _blobs, sigs, required = made["facts"][by_kind[6]]
    threshold, children = ref.decode_composite(required[0][1])
    assert threshold == 3 and sorted(w for w, _s, _c in children) == [1, 2]
    assert sum(s == ref.COMPOSITE for _w, s, _c in children) == 1
    # s + n: the secp256k1 leaf alone fails, the other leaf's verifies
    blobs, sigs, _required = made["facts"][by_kind[5]]
    tx_id = ref.transaction_id(blobs)
    assert [ref.signature_valid(s, pub, sig, tx_id)
            for s, pub, sig in sigs][:2] == [False, True]
    assert sigs[0][0] == ref.SECP256K1
    # the high-s leaf is high, and valid
    from cryptography.hazmat.primitives.asymmetric.utils import \
        decode_dss_signature
    blobs, sigs, _required = made["facts"][by_kind[9]]
    assert decode_dss_signature(sigs[0][2])[1] > mixed_ledgers.K1_ORDER // 2
    # a signature is over the id and nothing else
    i = next(i for i in range(128) if i not in made["kinds"])
    blobs, sigs, required = made["facts"][i]
    tampered = [blobs[0][:-1] + bytes([blobs[0][-1] ^ 1]), *blobs[1:]]
    assert ref.verdict((tampered, sigs, required)) == ref.BAD_SIGNATURE
    assert ref.verdict((blobs, sigs[:0], required)) == ref.MISSING


def test_mixedbackfill_rehearsal(capsys):
    result, notes = rehearse(capsys)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 128
    assert set(result["metrics"]) == {"tx_per_s", "setup_s"}
    assert result["metrics"]["tx_per_s"]["value"] > 0
    window = notes["window"]
    assert window["verdicts_inside"] \
        == round(window["tx_per_s"] * window["window_s"])
    assert set(map(int, notes["reference"]["altered_judged_by_kind"])) \
        == set(range(10))
    assert notes["warm"]["padded_rows_run"] == ["8"]
    assert set(notes["warm"]["first_call_s"]) == {"ed25519@8", "secp256k1@8"}
    assert set(notes["batcher"]["flushes_by_padded_rows"]) == {"8"}
    assert notes["batcher"]["host_rows_in_window"] == 0
    by_bucket = notes["batcher"]["device_rows_by_bucket"]
    assert by_bucket["ed25519"] > 0 and by_bucket["secp256k1"] > 0
    assert 0.2 < notes["ledgers"]["k1_row_share"] < 0.5


@pytest.mark.parametrize("control,blind_to", [
    ("unchecked_rows", {0, 1, 2, 5}), ("thresholds_ignored", {4, 6})])
def test_mixedbackfill_control_comes_out_not_correct(capsys, control,
                                                     blind_to):
    result, notes = rehearse(capsys, control=control)
    assert not result["correct"]
    value, limit, ok = result["checks"]["verdicts_differing_from_reference"]
    assert value > 0 and limit == 0 and not ok
    # the kinds the broken rule cannot see are the ones it lets through
    judged = set(map(int, notes["reference"]["altered_judged_by_kind"]))
    assert judged == set(range(10)) - blind_to
    assert result["checks"]["members_not_answered_exactly_once"][2]
    from corda_tpu.core.crypto.composite import CompositeKey
    assert CompositeKey.is_fulfilled_by.__name__ == "is_fulfilled_by"


def test_mixedbackfill_traced_rehearsal_reads_its_layer_metrics(capsys):
    result, _notes = rehearse(capsys, trace=True)
    assert result["correct"], result["checks"]
    # the CPU's trace holds no device program: the kernels' four metrics
    # read nothing and are left out
    assert set(result["metrics"]) == set(METRICS) - KERNEL_METRICS
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert m["device_route_share.backfill"] == 100.0
    assert m["wave_bulk_share.backfill"] == 100.0
    assert m["ed25519_words_prep_share.backfill"] == 100.0
    assert m["ecdsa_words_prep_share.backfill"] == 100.0
    assert 20 < m["k1_row_share.backfill"] < 50
    assert 40 < m["composite_required_share.backfill"] < 90
    assert 0 < m["batch_rows_mean.backfill"] <= 8
    for name in ("verify_wave_ms_p50.backfill",
                 "wave_submit_ms_per_tx.backfill",
                 "wave_coverage_ms_per_tx.backfill",
                 "wave_rules_ms_per_tx.backfill",
                 "batch_prep_ms_p50.backfill"):
        assert 0 < m[name] < 60000, name


def test_a_service_without_the_wave_entry_point_is_refused(capsys,
                                                           monkeypatch):
    """Any parent of PR 42: the run ends on set-up's first call (exit 2 from
    the command), before a ledger is made or a kernel loaded."""
    from corda_tpu.verifier.batcher import SignatureBatcher
    from corda_tpu.verifier.service import TpuTransactionVerifierService
    monkeypatch.delattr(TpuTransactionVerifierService, "verify_wave")
    monkeypatch.setattr(SignatureBatcher, "__init__",
                        lambda *a, **k: pytest.fail("a batcher was built"))
    monkeypatch.setattr(mixed_ledgers, "make_ledger",
                        lambda job: pytest.fail("a ledger was made"))
    with pytest.raises(bench_run.BenchError, match="wave entry point"):
        rehearse(capsys)
