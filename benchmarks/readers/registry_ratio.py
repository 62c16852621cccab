"""Ratio of meter counts over the window: sum(numerator) / sum(denominator)."""


def _delta(data, name):
    return (data["snap1"].get(name, {}).get("count", 0)
            - data["snap0"].get(name, {}).get("count", 0))


def read(data, numerator, denominator, scale=1.0):
    if "snap1" not in data:
        return None
    den = sum(_delta(data, n) for n in denominator)
    if den <= 0:
        return None
    return scale * sum(_delta(data, n) for n in numerator) / den
