"""An exact quantile of the durations of the spans of one name that start in
the window (nearest rank, the benchmark's one quantile rule): the program's
histograms keep quarter-decade buckets, the ring keeps the durations."""
import span_walk
from bench_common import nearest_rank


def read(data, span, q, scale=1000.0):
    vals = sorted(max(0.0, s.get("duration_s") or 0.0)
                  for s in span_walk.named(data, {span}))
    return scale * nearest_rank(vals, q) if vals else None
