"""Mean device duration of one compiled program per call, from the trace:
the per-program line's events whose name holds ``program``."""
import trace_reduce


def read(data, program, scale=1000.0):
    tr = data.get("trace")
    if not tr:
        return None
    durs = trace_reduce.kernel_events(tr["events"], program)
    if not durs:
        return None
    return scale * sum(durs) / len(durs)
