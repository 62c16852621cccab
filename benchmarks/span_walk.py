"""What the span readers added after the first share: the window's spans by
name, a span's tags, the union of intervals, and the count of transactions
the window committed as the spans show it."""
from __future__ import annotations


def tags_of(span) -> dict:
    tags = span.get("tags")
    return tags if isinstance(tags, dict) else {}


def window_of(data) -> tuple[float, float]:
    return tuple(data.get("window_wall", (float("-inf"), float("inf"))))


def end_of(span) -> float:
    return span["start_s"] + max(0.0, span.get("duration_s") or 0.0)


def named(data, names, lo=None, hi=None):
    """Spans whose name is in ``names`` and which START in [lo, hi]."""
    w_lo, w_hi = window_of(data)
    lo = w_lo if lo is None else lo
    hi = w_hi if hi is None else hi
    return [s for s in data.get("spans") or []
            if s.get("name") in names and s.get("start_s") is not None
            and lo <= s["start_s"] <= hi]


def union_s(intervals) -> float:
    """Total length of the union of (start, end) intervals."""
    total, cur_lo, cur_hi = 0.0, None, None
    for lo, hi in sorted(i for i in intervals if i[1] > i[0]):
        if cur_hi is None or lo > cur_hi:
            if cur_hi is not None:
                total += cur_hi - cur_lo
            cur_lo, cur_hi = lo, hi
        elif hi > cur_hi:
            cur_hi = hi
    if cur_hi is not None:
        total += cur_hi - cur_lo
    return total


def committed(data, flow_types=None, lo=None, hi=None) -> int:
    """Transactions committed in [lo, hi] as the spans show them: top-level
    ``flow.run`` spans (no parent: an op's own flow, not a responder) of
    ``flow_types`` that ENDED there. Each such flow commits one."""
    w_lo, w_hi = window_of(data)
    lo = w_lo if lo is None else lo
    hi = w_hi if hi is None else hi
    n = 0
    for s in data.get("spans") or []:
        if s.get("name") != "flow.run" or s.get("parent_id") is not None \
                or s.get("start_s") is None:
            continue
        kind = str(tags_of(s).get("flow_type", ""))
        if flow_types and not any(t in kind for t in flow_types):
            continue
        n += lo <= end_of(s) <= hi
    return n
