"""The ``genledger-secp256k1`` deployment's own tests: the seeded pool (byte
identical for a seed, on one core or several, all five kinds of corrupted
rows refused by the plain reference and no other row), the cost function's
bytes against a hand count, and tiny-size CPU rehearsals of the
``ecdsawaves`` driver: a sound run, the control, a verdict flipped where it
is produced, the low-s rule put back under the device prep and under the
host route, and the traced run's span and counter metrics. Every rehearsal
dispatches the secp256k1 kernel at its smallest bucket (8 rows), so the file
compiles it once."""
import json
import pathlib

import numpy as np
import pytest

import ecdsa_pool
import kernel_cost_ecdsa
import run as bench_run
from reference import genledger_secp256k1 as ref

BENCH = pathlib.Path(__file__).resolve().parents[1]
SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
CELL = "genledger-secp256k1.wave8k"
TINY = {"wave_size": 8, "party_keys": 4, "corrupt_every": 4,
        "batcher_args": {"max_batch": 8, "host_crossover": 0}}
METRICS = ["secp256k1_kernel_ms", "secp256k1_roofline",
           "batch_prep_ms_p50.k1wave8k", "ecdsa_der_ms_p50.k1wave8k",
           "ecdsa_digest_ms_p50.k1wave8k", "ecdsa_scalars_ms_p50.k1wave8k",
           "ecdsa_words_prep_share.k1wave8k", "wave_ms_p50.k1wave8k",
           "device_route_share.k1wave8k", "device_idle_share.k1wave8k"]
N = ecdsa_pool.ORDERS["secp256k1"]


def rehearse(seconds, capsys, control=None, trace=False, seed=3_000_000_023):
    """One tiny run: the result object and the run's earlier lines, the
    ``say`` rows and (which ``run_cell`` keeps to itself) the checks."""
    cell = bench_run.Cell(CELL, SPEC)
    notes: list = []
    check = bench_run.RunContext.check

    def noted(self, name, value, limit, ok=None):
        passed = check(self, name, value, limit, ok)
        notes.append(self.checks[-1])
        return passed

    bench_run.RunContext.check = noted
    try:
        result = bench_run.run_cell(cell, seed, seconds, trace, CPU,
                                    control=control, scale=TINY, quiet=True,
                                    notes=notes)
    finally:
        bench_run.RunContext.check = check
    assert capsys.readouterr().out == ""      # nothing under a metric's name
    return result, notes


def test_the_cell_has_its_files_and_the_spec_gained_entries_only():
    cell = bench_run.Cell(CELL, SPEC)
    assert cell.driver_name == "ecdsawaves" and cell.chips == 1
    assert cell.traffic["name"] == "wave8k"
    assert cell.end_to_end_names() == ["sigs_per_s", "setup_s"]
    assert sorted(lm["name"] for lm in cell.layer_metric_files()) \
        == sorted(METRICS)
    for lm in cell.layer_metric_files():
        assert lm["workloads"] == [CELL] and lm["moves"] == "sigs_per_s"
    # appended: the configuration, the cell and the ten metrics are the
    # last entries of their lists
    assert SPEC["configs"][-1]["name"] == "genledger-secp256k1"
    assert SPEC["configs"][-1]["reduced"] == ["schemes"]
    assert SPEC["workloads"][-1]["name"] == CELL
    assert [m["name"] for m in SPEC["per_layer"][-10:]] == METRICS
    config = cell.config
    assert config["schemes"] == ["secp256k1"]
    assert config["batcher_args"] == {"max_batch": 8192}
    assert config["corruptions"][3:] and len(config["corruptions"]) == 5
    assert set(config["reduced"]) == {"schemes"}
    assert {"max_batch", "party_keys", "signer", "strict_der"} \
        <= set(config["assumed"])


def test_the_reference_imports_nothing_of_the_program():
    source = (BENCH / "reference" / "genledger_secp256k1.py").read_text()
    assert "corda_tpu" not in source.replace(
        "which\nshares nothing with the program", "")
    pool = (BENCH / "ecdsa_pool.py").read_text()
    assert "import corda_tpu" not in pool and "from corda_tpu" not in pool


def test_k1_pool_is_byte_identical_for_a_seed_and_refused_where_corrupted():
    from drivers.sigwaves import pool_digest
    a, bad_a = ecdsa_pool.build_pool(3_000_000_019, 2, 64, 4, 8)
    b, _ = ecdsa_pool.build_pool(3_000_000_019, 2, 64, 4, 8)
    c, _ = ecdsa_pool.build_pool(3_000_000_020, 2, 64, 4, 8)
    assert pool_digest(a) == pool_digest(b)
    assert pool_digest(a) != pool_digest(c)
    assert [len(s) for s in bad_a] == [8, 8]
    # the five kinds in rotation through the whole pool
    assert [k for bad in bad_a for _i, k in sorted(bad.items())] \
        == [i % 5 for i in range(16)]
    for rows, bad in zip(a, bad_a):
        got = ref.verdicts(rows)
        assert [i for i, ok in enumerate(got) if not ok] == sorted(bad)
        assert len({msg for _p, _s, msg in rows}) == len(rows)
        assert all(len(pub) == 33 and pub[0] in (2, 3) for pub, _s, _m in rows)
    # the signer does not normalise s: both halves are there
    assert 0.25 < ecdsa_pool.high_s_share(a, "secp256k1") < 0.75


def test_each_corruption_is_refused_for_its_own_reason():
    from cryptography.hazmat.primitives.asymmetric.utils import \
        decode_dss_signature
    pubs = ecdsa_pool.public_keys("secp256k1", [11, 12])
    msg = b"\x07" * 32
    (sig,) = ecdsa_pool.sign_rows(("secp256k1", [11, 12], [0], [msg]))
    assert ref.ecdsa_valid(pubs[0], sig, msg)
    r, s = decode_dss_signature(sig)
    for kind in range(5):
        pub2, sig2, msg2 = ecdsa_pool.corrupt(kind, "secp256k1", pubs[0], sig,
                                              msg, pubs[1])
        assert not ref.ecdsa_valid(pub2, sig2, msg2), kind
        assert [pub2 != pubs[0], sig2 != sig, msg2 != msg] \
            == [kind == 1, kind in (0, 3, 4), kind == 2]
    # kind 3 carries the same (r, s) in a longer encoding; kind 4 an s that
    # is congruent to the valid one and out of range
    _p, padded, _m = ecdsa_pool.corrupt(3, "secp256k1", pubs[0], sig, msg,
                                        pubs[1])
    assert len(padded) == len(sig) + 1 and padded[4] == 0
    assert decode_dss_signature(
        ecdsa_pool.corrupt(4, "secp256k1", pubs[0], sig, msg, pubs[1])[1]) \
        == (r, s + N)
    # and the n - s twin, which no corruption makes, is VALID
    twin = ecdsa_pool.der_sig(ecdsa_pool.der_int(r),
                              ecdsa_pool.der_int(N - s))
    assert ref.ecdsa_valid(pubs[0], twin, msg)


def test_a_pool_signed_by_worker_processes_is_the_same_pool(monkeypatch):
    from drivers.sigwaves import pool_digest
    serial, _ = ecdsa_pool.build_pool(3_000_000_021, 3, 16, 4, 4)
    monkeypatch.setattr(ecdsa_pool, "PARALLEL_FROM", 1)
    spread, _ = ecdsa_pool.build_pool(3_000_000_021, 3, 16, 4, 4)
    assert pool_digest(spread) == pool_digest(serial)
    assert ecdsa_pool.parallel_map(
        "reference.genledger_secp256k1:verdicts", spread, 48) \
        == [ref.verdicts(rows) for rows in serial]
    with pytest.raises(RuntimeError, match="worker"):
        ecdsa_pool.parallel_map("reference.genledger_secp256k1:no_such",
                                spread, 48)


def test_cost_function_counts_the_call_s_shapes():
    # by hand, per row: g_idx 16 x 4, q_bits 16 x 4 x 1, pts 4 x 16 x 2,
    # r_limbs 16 x 2, the verdict 1, and 16 gathered table rows of
    # x 32 + y 32 + flag 1
    per_row = 64 + 64 + 128 + 32 + 1 + 16 * 65
    assert per_row == 1329
    need = kernel_cost_ecdsa.secp256k1_hybrid(8192)
    assert need == {"bytes": 8192 * 1329, "ops": None, "ops_peak": None}
    # the shapes are the ones the program's prep hands the kernel
    from corda_tpu.core.crypto import ecmath
    from corda_tpu.ops import weierstrass as wc
    curve = ecmath.SECP256K1
    items = [(curve.g, b"m", 5, 7)] * 8
    g_idx, q_bits, pts, r_limbs, tab_x, tab_y, tab_ok, _pre = \
        wc.prepare_batch_hybrid_wide(items, wc.HYBRID_G_WINDOW)
    wire = sum(np.asarray(a).nbytes for a in (g_idx, q_bits, pts, r_limbs))
    table_row = sum(np.asarray(t[0]).nbytes for t in (tab_x, tab_y, tab_ok))
    assert (wire // 8, table_row, g_idx.shape[0]) == (288, 65, 16)
    assert 288 + 1 + g_idx.shape[0] * table_row == per_row
    # the reader: bytes over the peak over the kernel's mean time
    reader = bench_run.load_module("readers", "trace_roofline_share")
    args = dict(program="verify_core_hybrid_wide",
                cost_module="kernel_cost_ecdsa", cost="secp256k1_hybrid",
                rows_param="wave_size")
    events = {"window": [0.0, 10e9], "devices": {"/device:TPU:0": {
        "XLA Modules": [["jit_verify_core_hybrid_wide(1)", 1e9, 0.2e9],
                        ["jit_other(2)", 3e9, 1e9]]}}}
    data = {"trace": {"events": events}, "device": {"kind": "TPU v5 lite"},
            "peaks": json.loads((BENCH / "peaks.json").read_text()),
            "cell": bench_run.Cell(CELL, SPEC)}
    assert reader.read(data, **args) == pytest.approx(
        100 * (8192 * 1329 / 819e9) / 0.2)
    assert reader.read({"trace": None}, **args) is None
    events["devices"]["/device:TPU:0"]["XLA Modules"].pop(0)
    assert reader.read(data, **args) is None      # the parent's ed25519 cell


def test_k1_rehearsal_control_and_broken_path(capsys, monkeypatch):
    sound, notes = rehearse(2.0, capsys)
    assert sound["correct"] and sound["failed"] == 0
    assert set(sound["metrics"]) == {"sigs_per_s", "setup_s"}
    said = {n["note"]: n for n in notes if "note" in n}
    assert said["pool"]["scheme"] == "secp256k1"
    # the rate is sigwaves' own: the window's verdicts over the clock's window
    w = said["window"]
    assert 0 < w["last_verdict_s"] <= w["window_s"]
    assert sound["metrics"]["sigs_per_s"]["value"] == pytest.approx(
        w["waves_completed_inside"] * 8 / w["window_s"])
    assert 0.25 < said["pool"]["high_s_share"] < 0.75
    assert said["batcher"]["EcdsaItemsPrep"] == 0
    assert said["batcher"]["EcdsaWordsPrep"] == said["batcher"]["DeviceChecked"]
    # of the 16 corrupted rows, kind 3 (x3) is refused by its encoding and
    # kind 4 (x3) by its length or the range precheck: before the kernel
    assert said["batcher"]["EcdsaRefusedEncoding"] \
        + said["batcher"]["EcdsaRefusedRange"] > 0
    checks = {c["check"]: c for c in notes if "check" in c}
    assert checks["rows_refused_before_the_kernel_beside_the_pools"]["ok"]
    assert checks["rows_prepared_by_the_item_form_fallback"]["ok"]
    assert checks["verdicts_differing_from_reference"]["value"] == 0
    control, _ = rehearse(1.0, capsys, control="unchecked_rows")
    assert control["correct"] is False
    # a verdict altered where it is produced
    from corda_tpu.verifier.batcher import SignatureBatcher
    resolve = SignatureBatcher._resolve

    def flipped(self, bucket, items, verdicts, bctx=None):
        verdicts = list(verdicts)
        verdicts[0] = not verdicts[0]
        return resolve(self, bucket, items, verdicts, bctx)

    monkeypatch.setattr(SignatureBatcher, "_resolve", flipped)
    broken, _ = rehearse(1.0, capsys)
    assert broken["correct"] is False


WINDOW_KEYS = {"note", "sigs_per_s", "waves_completed_inside",
               "waves_finished_after", "window_s", "last_verdict_s",
               "rate_to_last_verdict", "slice_rates", "rate_median_of_slices",
               "wave_ms_p50", "wave_ms_max", "gc_s", "gc_longest_ms",
               "gc_collections"}


def test_both_wave_cells_print_the_same_window_line(capsys):
    """One driver, one rule: the ``window`` line of the Ed25519 cell and of
    this one carry the same keys, and each is ``window_rate``'s reading of
    the run's own completion times."""
    _result, notes = rehearse(1.0, capsys)
    ed_notes: list = []
    ed = bench_run.run_cell(
        bench_run.Cell("genledger-ed25519.wave8k", SPEC), 3_000_000_023, 1.0,
        False, CPU, quiet=True, notes=ed_notes,
        scale={"wave_size": 16, "party_keys": 4, "corrupt_every": 4,
               "batcher_args": {"max_batch": 16, "host_crossover": 0}})
    assert capsys.readouterr().out == ""
    for rows, result, wave in ((notes, _result, 8), (ed_notes, ed, 16)):
        (w,) = [n for n in rows if n.get("note") == "window"]
        assert set(w) == WINDOW_KEYS
        assert result["metrics"]["sigs_per_s"]["value"] == w["sigs_per_s"] \
            == pytest.approx(w["waves_completed_inside"] * wave / w["window_s"])
        assert len(w["slice_rates"]) == 6
        assert sum(w["slice_rates"]) * w["window_s"] / 6 == pytest.approx(
            w["waves_completed_inside"] * wave)
        assert sum(w["gc_collections"]) >= 0 and w["gc_s"] >= 0.0
    (pool,) = [n for n in ed_notes if n.get("note") == "pool"]
    assert pool["scheme"] == "ed25519" and "high_s_share" not in pool
    assert not [c for c in ed["checks"] if "fallback" in c or "refused" in c]
    assert {"rows_prepared_by_the_item_form_fallback",
            "rows_refused_before_the_kernel_beside_the_pools"} \
        <= set(_result["checks"])


def test_k1_rehearsal_with_the_low_s_rule_back_is_not_correct(capsys,
                                                               monkeypatch):
    """The parent's rule under the device prep: every high-s row of the
    reference's signer is refused, and the run says so."""
    from corda_tpu.ops import scalarprep as sp
    if not sp.available():
        pytest.skip("no libscalarmath.so: the item-form check fails first")
    real = sp.k1_prep

    def low_s_only(e_words, r_words, s_words, pub_words):
        out = list(real(e_words, r_words, s_words, pub_words))
        s = [int.from_bytes(np.ascontiguousarray(row).tobytes(), "little")
             for row in s_words]
        out[-1] = out[-1] & np.asarray([v <= N // 2 for v in s])
        return tuple(out)

    monkeypatch.setattr(sp, "k1_prep", low_s_only)
    result, notes = rehearse(1.0, capsys)
    assert result["correct"] is False
    failed = [c["check"] for c in notes if "check" in c and not c["ok"]]
    # by its verdicts, and by the meters: the precheck refused more rows
    # than the pool's unparsable ones
    assert failed == ["verdicts_differing_from_reference",
                      "rows_refused_before_the_kernel_beside_the_pools"]


def test_a_program_that_refuses_high_s_cannot_run_the_deployment(
        capsys, monkeypatch):
    """The parent's rule on the host route: the run ends at once with
    ``BenchError`` (exit 2 from the command line), before it signs a pool
    or compiles a kernel."""
    from corda_tpu.core.crypto.signatures import Crypto
    from cryptography.hazmat.primitives.asymmetric.utils import \
        decode_dss_signature
    real = Crypto.is_valid

    def low_s_only(public, signature, content):
        _r, s = decode_dss_signature(signature)
        return s <= N // 2 and real(public, signature, content)

    monkeypatch.setattr(Crypto, "is_valid", staticmethod(low_s_only))
    monkeypatch.setattr(ecdsa_pool, "build_pool", None)   # never reached
    with pytest.raises(bench_run.BenchError, match="s > n/2"):
        rehearse(1.0, capsys)


def test_without_a_deterministic_signer_the_run_refuses(capsys, monkeypatch):
    def missing():
        raise ecdsa_pool.SignerUnavailable("no RFC 6979 here")

    monkeypatch.setattr(ecdsa_pool, "_algorithm", missing)
    with pytest.raises(bench_run.BenchError, match="RFC 6979"):
        rehearse(1.0, capsys)


def test_the_item_form_fallback_comes_out_not_correct(capsys, monkeypatch):
    """No native library: the program takes the pure-Python item prep in
    silence, every verdict is still right, and the run is not correct."""
    from corda_tpu.ops import scalarprep as sp
    monkeypatch.setattr(sp, "_LIB", None)
    result, notes = rehearse(1.0, capsys)
    assert result["correct"] is False
    failed = [c["check"] for c in notes if "check" in c and not c["ok"]]
    assert failed == ["rows_prepared_by_the_item_form_fallback"]


def test_k1_traced_rehearsal_prints_the_span_and_counter_metrics(capsys):
    result, _ = rehearse(2.0, capsys, trace=True)
    assert result["correct"]
    m = result["metrics"]
    # what the host can read; the device trace's four need a chip
    for name in ("batch_prep_ms_p50.k1wave8k", "ecdsa_der_ms_p50.k1wave8k",
                 "ecdsa_digest_ms_p50.k1wave8k",
                 "ecdsa_scalars_ms_p50.k1wave8k", "wave_ms_p50.k1wave8k"):
        assert m[name]["value"] > 0 and m[name]["unit"] == "ms", name
    assert m["ecdsa_words_prep_share.k1wave8k"]["value"] == 100.0
    assert m["device_route_share.k1wave8k"]["value"] == 100.0
    assert set(m) <= set(METRICS)
    # the three parts lie inside the prep they are parts of
    parts = sum(m[f"ecdsa_{p}_ms_p50.k1wave8k"]["value"]
                for p in ("der", "digest", "scalars"))
    assert parts < 4 * m["batch_prep_ms_p50.k1wave8k"]["value"]
