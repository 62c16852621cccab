"""``trace_roofline_share`` with the cost function's module named by the
metric (``cost_module``, a file beside ``kernel_cost.py``): a kernel's share
of its roofline, in %, the least time the chip could take for one call over
the kernel's mean device time from the trace. Where the cost function gives
no operation count the bound is the memory one alone, and PERF.md says so."""
import importlib

import trace_reduce


def read(data, program, cost_module, cost, rows_param):
    tr = data.get("trace")
    if not tr:
        return None
    durs = trace_reduce.kernel_events(tr["events"], program)
    if not durs:
        return None
    kind = data["device"]["kind"]
    peaks = data["peaks"].get(kind)
    if peaks is None:
        raise KeyError(f"peaks.json has no device kind {kind!r}")
    rows = int(data["cell"].traffic[rows_param])
    need = getattr(importlib.import_module(cost_module), cost)(rows)
    least_s = need["bytes"] / peaks["hbm_bytes_per_s"]
    if need.get("ops") is not None and peaks.get(need["ops_peak"]):
        least_s = max(least_s, need["ops"] / peaks[need["ops_peak"]])
    return 100.0 * least_s / (sum(durs) / len(durs))
