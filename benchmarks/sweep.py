#!/usr/bin/env python3
"""benchmarks/sweep.py: find a deployment's knee once, on the chip.

    python3 benchmarks/sweep.py --workload <cell> --rates 10,20,30 --seconds 15 --seed 1

Runs the cell's driver once per offered rate in one process (the rate
overrides the traffic file's), prints one line per point, and decides
nothing: the knee is read off the points and written into the traffic files
as a number (PERF.md records the points). Not part of a check.
"""
from __future__ import annotations

import argparse
import json
import sys

import run as bench_run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    cell = bench_run.Cell(args.workload)
    bench_run.ensure_native()
    device = bench_run.find_device(cell.chips)
    from corda_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        notes: list = []
        result = bench_run.run_cell(
            cell, args.seed + i, args.seconds, False, device, quiet=True,
            scale={"rate_tx_per_s": rate, "require_all_committed": False},
            notes=notes)
        window = next((n for n in notes if n["note"] == "window"), {})
        print(json.dumps({
            "point": rate, "correct": result["correct"],
            "failed": result["failed"], "attempted": result["attempted"],
            **{k: window.get(k) for k in (
                "commit_ms_p50", "commit_ms_p95", "commit_ms_max",
                "tx_per_s", "tx_committed_in_window",
                "ops_open_at_window_end", "drained_s",
                "generator_late_ms_max")}}), flush=True)
        if not result["correct"]:
            print(json.dumps(notes, default=str), flush=True)
    return 0


if __name__ == "__main__":
    import os
    rc = main()
    sys.stdout.flush()
    os._exit(rc)
