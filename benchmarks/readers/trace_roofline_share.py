"""A kernel's share of its roofline, in %: the least time the chip could
take for one call (the larger of operations over peak operations/s and bytes
over peak bytes/s, both from a cost function of the call's shapes, against
``peaks.json`` for this device kind) over the kernel's mean device time from
the trace. The cost function is ``cost`` of ``cost_module``, a file beside
``kernel_cost.py`` (which it is unless the metric names another). Where the
cost function gives no operation count the bound is the memory one alone,
and PERF.md says so."""
import importlib

import trace_reduce


def read(data, program, cost, rows_param, cost_module="kernel_cost"):
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
