"""What a span's children do not name: a quantile (nearest rank) over the
spans of one name that start in the window of the span's duration less the
union of its direct children's intervals, each cut to the span's own.
Children that overlap count once. ``tag`` / ``equals`` keep the spans whose
tag holds that value. None where the window holds no such span."""
import span_walk
from bench_common import nearest_rank


def read(data, span, q, tag=None, equals=None, scale=1000.0):
    mine = {s["span_id"]: s for s in span_walk.named(data, {span})
            if s.get("span_id") is not None
            and (tag is None or span_walk.tags_of(s).get(tag) == equals)}
    if not mine:
        return None
    children: dict = {}
    for s in data.get("spans") or []:
        if s.get("parent_id") in mine and s.get("start_s") is not None:
            children.setdefault(s["parent_id"], []).append(s)
    vals = []
    for sid, s in mine.items():
        lo, hi = s["start_s"], span_walk.end_of(s)
        named = span_walk.union_s(
            (max(lo, c["start_s"]), min(hi, span_walk.end_of(c)))
            for c in children.get(sid, ()))
        vals.append(max(0.0, (hi - lo) - named))
    return scale * nearest_rank(sorted(vals), q)
