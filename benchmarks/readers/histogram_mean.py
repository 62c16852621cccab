"""Mean of one registry histogram over the window: d(sum) / d(count)."""


def read(data, metric, scale=1.0):
    h1 = data.get("snap1", {}).get(metric)
    if not h1:
        return None
    h0 = data.get("snap0", {}).get(metric) or {}
    n = h1.get("count", 0) - h0.get("count", 0)
    if n <= 0:
        return None
    return scale * (h1.get("sum", 0.0) - h0.get("sum", 0.0)) / n
