"""PR 39's two readers on synthetic spans, and the metric files that read a
device-routed batch's life: which cells list them, which end-to-end metric
each moves, and what they do with the spans of a program from before the new
spans (nothing where there is nothing to read, and no exception)."""
import json
import pathlib

import pytest

import run as bench_run

BENCH = pathlib.Path(__file__).resolve().parents[1]
SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
ED, K1, OOP = ("genledger-ed25519.wave8k", "genledger-secp256k1.wave8k",
               "genledger-oop.stream")
BATCH = ["batch_submit_ms_p50", "batch_queue_wait_ms_p50",
         "batch_pool_wait_ms_p50", "batch_launch_ms_p50",
         "batch_device_wait_ms_p50", "batch_resolve_ms_p50",
         "dispatch_offcpu_share", "dispatch_unnamed_ms_p50"]
ED_PREP = [f"ed25519_{p}_ms_p50"
           for p in ("items", "sig", "keys", "digest", "scalars", "handover")]
K1_PREP = ["ecdsa_keys_ms_p50", "ecdsa_pad_ms_p50"]
#: metric -> (cells, the end-to-end metric it moves)
NEW = {**{n: ([ED, K1], "sigs_per_s") for n in BATCH},
       **{n: ([ED], "sigs_per_s") for n in ED_PREP},
       **{n: ([K1], "sigs_per_s") for n in K1_PREP},
       **{f"{n}.stream": ([OOP], "tx_per_s") for n in BATCH + ED_PREP}}
DEVICE = {"span": "batcher.dispatch", "tag": "route", "equals": "device"}


def load(name):
    return bench_run.load_module("readers", name)


def span(sid, parent, name, start, dur, cpu=None, **tags):
    return {"trace_id": "t", "span_id": sid, "parent_id": parent,
            "name": name, "start_s": start, "duration_s": dur, "cpu_s": cpu,
            "thread": "sig-batcher-prep_0", "tags": tags}


def batch(pre, at, cpu):
    """One device-routed dispatch, 100 ms long: two phases that OVERLAP by
    10 ms, a launch, and 20 ms that no child names."""
    d = pre + "d"
    return [
        span(d, pre + "f", "batcher.dispatch", at, 0.100, cpu, route="device"),
        span(pre + "a", d, "ed25519.prep.keys", at + 0.010, 0.040, 0.030),
        span(pre + "b", d, "ed25519.prep.digest", at + 0.040, 0.030, 0.010),
        span(pre + "l", d, "batcher.launch", at + 0.085, 0.005, 0.004),
        # a grandchild and a sibling's child name nothing of this span
        span(pre + "g", pre + "a", "inner", at + 0.090, 0.010),
        span(pre + "w", pre + "f", "batcher.device_wait", at + 0.070, 0.050),
    ]


def test_unnamed_takes_the_union_of_the_direct_children():
    reader = load("span_unnamed_quantile")
    data = {"spans": batch("x", 10.0, 0.06), "window_wall": (9.0, 12.0)}
    # 100 less [10, 70] and [85, 90]: the overlap counts once
    assert reader.read(data, q=0.5, **DEVICE) == pytest.approx(35.0)
    # a child that runs past its parent's end is cut to the parent
    data["spans"].append(span("xo", "xd", "late", 10.095, 0.500))
    assert reader.read(data, q=0.5, **DEVICE) == pytest.approx(30.0)
    # a dispatch with no child at all is unnamed from end to end (a program
    # before the spans), and a host-routed one is not this metric's
    data["spans"] += [span("h", None, "batcher.dispatch", 10.5, 0.300,
                           route="host"),
                      span("p", None, "batcher.dispatch", 10.6, 0.080,
                           route="device")]
    assert reader.read(data, q=1.0, **DEVICE) == pytest.approx(80.0)
    assert reader.read(data, q=0.5, span="batcher.dispatch") \
        == pytest.approx(80.0)          # no tag asked: all three


def test_offcpu_share_is_a_ratio_of_sums_over_the_spans_that_carry_cpu():
    reader = load("span_offcpu_share")
    spans = batch("x", 10.0, 0.060) + batch("y", 10.2, 0.090) \
        + batch("z", 10.4, None)        # no cpu_s: out of BOTH sums
    spans.append(span("h", None, "batcher.dispatch", 10.5, 1.0, 0.0,
                      route="host"))    # another route: not read
    data = {"spans": spans, "window_wall": (9.0, 12.0)}
    # (40 + 10) of 200 ms
    assert reader.read(data, **DEVICE) == pytest.approx(25.0)
    # a tick of the thread clock can put cpu_s over the duration: no less
    # than nothing was spent off the CPU
    spans.append(span("t", None, "batcher.dispatch", 10.6, 0.050, 0.060,
                      route="device"))
    assert reader.read(data, **DEVICE) == pytest.approx(100 * 50 / 250)
    # and any span name will do
    assert reader.read(data, span="ed25519.prep.digest") \
        == pytest.approx(100 * (3 * 20) / (3 * 30))


@pytest.mark.parametrize("reader,args", [
    ("span_offcpu_share", DEVICE),
    ("span_unnamed_quantile", dict(DEVICE, q=0.5))])
def test_an_empty_window_gives_none(reader, args):
    spans = batch("x", 10.0, 0.06)
    assert load(reader).read({"spans": [], "window_wall": (9.0, 12.0)},
                             **args) is None
    assert load(reader).read({"spans": spans, "window_wall": (20.0, 30.0)},
                             **args) is None
    assert load(reader).read({"window_wall": (9.0, 12.0)}, **args) is None


def test_a_program_before_cpu_s_gives_the_share_nothing_to_read():
    old = [dict(s) for s in batch("x", 10.0, None)]
    for s in old:
        del s["cpu_s"]                  # the key itself is PR 39's
    data = {"spans": old, "window_wall": (9.0, 12.0)}
    assert load("span_offcpu_share").read(data, **DEVICE) is None


def test_the_new_metrics_are_listed_in_their_cells_and_appended():
    listed = {m["name"]: m for m in SPEC["per_layer"]}
    files = {p.stem: json.loads(p.read_text())
             for p in (BENCH / "layer_metrics").glob("*.json")}
    order = [m["name"] for m in SPEC["per_layer"]]
    assert sorted(order[-len(NEW):]) == sorted(NEW)    # after all the others
    for name, (cells, moves) in NEW.items():
        row, lm = listed[name], files[name]
        assert row["workloads"] == lm["workloads"] == cells
        assert row["moves"] == lm["moves"] == moves
        assert row["source"] == "program_span" and row["better"] == "lower"
        assert "bound" not in row
        assert row["layer"] == lm["layer"] == "batcher (verifier/batcher.py)"
        assert row["unit"] == lm["unit"] \
            == ("%" if name.startswith("dispatch_offcpu") else "ms")
    # one quantity, two files only where the cells report different
    # end-to-end metrics: the same reader and arguments in both
    for name in BATCH + ED_PREP:
        wave, stream = files[name], files[f"{name}.stream"]
        assert (wave["reader"], wave["args"]) \
            == (stream["reader"], stream["args"])


def test_the_parents_spans_give_the_new_metrics_nothing_but_the_unnamed():
    """PR 38's program: dispatch, device_wait and resolve spans, no
    ``cpu_s``, none of the new names. Every new metric file reads None but
    the three that read spans the parent had."""
    spans = [span("d", "f", "batcher.dispatch", 10.0, 0.080, route="device"),
             span("w", "f", "batcher.device_wait", 10.08, 0.030),
             span("r", "f", "batcher.resolve", 10.11, 0.004)]
    for s in spans:
        del s["cpu_s"]
    data = {"spans": spans, "window_wall": (9.0, 12.0)}
    read = {}
    for name in NEW:
        lm = json.loads((BENCH / "layer_metrics" / f"{name}.json")
                        .read_text())
        read[name] = load(lm["reader"]).read(data, **lm["args"])
    had = {n: v for n, v in read.items() if v is not None}
    assert had == {
        "batch_device_wait_ms_p50": pytest.approx(30.0),
        "batch_device_wait_ms_p50.stream": pytest.approx(30.0),
        "batch_resolve_ms_p50": pytest.approx(4.0),
        "batch_resolve_ms_p50.stream": pytest.approx(4.0),
        "dispatch_unnamed_ms_p50": pytest.approx(80.0),
        "dispatch_unnamed_ms_p50.stream": pytest.approx(80.0)}


@pytest.mark.parametrize("workload", [ED, K1, OOP])
def test_every_cell_lists_the_batchs_life(workload):
    cell = bench_run.Cell(workload, SPEC)
    names = {lm["name"] for lm in cell.layer_metric_files()}
    mine = {n for n, (cells, _m) in NEW.items() if workload in cells}
    assert mine <= names
    assert len(mine) == {ED: 14, K1: 10, OOP: 14}[workload]


CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
TINY = {ED: {"wave_size": 16, "party_keys": 4, "corrupt_every": 4,
             "batcher_args": {"max_batch": 16, "host_crossover": 0}},
        K1: {"wave_size": 8, "party_keys": 4, "corrupt_every": 4,
             "batcher_args": {"max_batch": 8, "host_crossover": 0}}}


@pytest.mark.parametrize("workload", [ED, K1])
def test_a_traced_wave_rehearsal_prints_every_new_metric(workload, capsys):
    """(The stream cell's rehearsal is held to its whole list by
    ``test_oopstream``.) On the CPU, at the tiny size other tests compile."""
    cell = bench_run.Cell(workload, SPEC)
    result = bench_run.run_cell(cell, 3_000_000_039, 2.0, True, CPU,
                                scale=TINY[workload], quiet=True)
    assert capsys.readouterr().out == ""
    assert result["correct"], result["checks"]
    m = {k: v["value"] for k, v in result["metrics"].items()}
    mine = {n for n, (cells, _m) in NEW.items() if workload in cells}
    assert mine <= set(m)
    assert all(m[n] >= 0.0 for n in mine)
    assert 0.0 <= m["dispatch_offcpu_share"] <= 100.0
    # the phases lie inside the prep they are phases of, and what they do
    # not name is less than the whole
    prep = m["batch_prep_ms_p50" + (".k1wave8k" if workload == K1 else "")]
    assert m["dispatch_unnamed_ms_p50"] < prep * 1.78    # a bucket's width
    assert m["batch_launch_ms_p50"] < prep * 1.78
