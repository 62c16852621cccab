"""``span_duration_quantile`` over the spans of one name whose tag ``tag``
is at least ``at_least`` (the walks of a late joiner are a thousand hops
long, the notary's one, and a median over both is neither's). Returns None
where no such span exists: a program without the span has nothing to read."""
import span_walk
from bench_common import nearest_rank


def read(data, span, q, tag, at_least, scale=1000.0):
    vals = sorted(max(0.0, s.get("duration_s") or 0.0)
                  for s in span_walk.named(data, {span})
                  if (span_walk.tags_of(s).get(tag) or 0) >= at_least)
    return scale * nearest_rank(vals, q) if vals else None
