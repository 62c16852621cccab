"""A kernel's share of its roofline, in %: the least time the chip could
take for one call (the larger of operations over peak operations/s and bytes
over peak bytes/s, both from ``kernel_cost``'s functions of the call's
shapes, against ``peaks.json`` for this device kind) over the kernel's mean
device time from the trace. Where the cost function gives no operation count
the bound is the memory one alone, and PERF.md says so."""
import kernel_cost
import trace_reduce


def read(data, program, cost, rows_param):
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
    need = getattr(kernel_cost, cost)(rows)
    least_s = need["bytes"] / peaks["hbm_bytes_per_s"]
    if need.get("ops") is not None and peaks.get(need["ops_peak"]):
        least_s = max(least_s, need["ops"] / peaks[need["ops_peak"]])
    return 100.0 * least_s / (sum(durs) / len(durs))
