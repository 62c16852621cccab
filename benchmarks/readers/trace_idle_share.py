"""Device idle share of the traced sub-window: 100 * (1 - busy / window),
busy being the union of the intervals in which an operation ran on the
device (trace_reduce.reduce)."""


def read(data):
    tr = data.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
