"""A quantile of exact samples the driver collected during the window (the
benchmark's own clock, or a list the program keeps, cut to the window)."""
from bench_common import nearest_rank


def read(data, samples, q, scale=1.0):
    vals = sorted(data.get("samples", {}).get(samples) or [])
    return scale * nearest_rank(vals, q) if vals else None
