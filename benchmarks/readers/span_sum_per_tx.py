"""Thread time per committed transaction: a sum over the window's spans of
some names, divided by the transactions the window committed.

The sum is, for every span of ``spans`` that STARTS in the window, one of:
the tag ``tag`` (a cost the program carried on the span, in seconds); the
span's self time (``self_time``: its duration less the durations of the
spans parented to it, so a ``session.send`` inside a ``flow.step`` is not
counted twice); or its duration.

The denominator is ``span_walk.committed``: top-level ``flow.run`` spans
that ended in the window. The driver's own count (``tx_committed_in_window``
on its ``window`` line) does not reach a reader, so the rule is checked
against the one count of commits that does: over the window AND the drain
the same rule has to count what the program's ``counter`` counted between
the two registry snapshots, within ``tolerance``; a ring that dropped spans,
or a flow that commits none or two, reads None instead of a wrong number."""
import span_walk


def read(data, spans, counter, tag=None, self_time=False, flow_types=None,
         tolerance=0.01, scale=1000.0):
    mine = span_walk.named(data, set(spans))
    if not mine or "snap1" not in data:
        return None
    lo, _hi = span_walk.window_of(data)
    counted = data["snap1"].get(counter, {}).get("count", 0) \
        - data.get("snap0", {}).get(counter, {}).get("count", 0)
    by_rule = span_walk.committed(data, flow_types, lo, float("inf"))
    if counted <= 0 or abs(by_rule - counted) > tolerance * counted:
        return None
    n_tx = span_walk.committed(data, flow_types)
    if n_tx <= 0:
        return None
    if tag is not None:
        total = sum(float(span_walk.tags_of(s).get(tag) or 0.0) for s in mine)
    elif self_time:
        child_s: dict = {}
        for s in data["spans"]:
            if s.get("parent_id") is not None:
                child_s[s["parent_id"]] = child_s.get(s["parent_id"], 0.0) \
                    + max(0.0, s.get("duration_s") or 0.0)
        total = sum(max(0.0, (s.get("duration_s") or 0.0)
                        - child_s.get(s.get("span_id"), 0.0)) for s in mine)
    else:
        total = sum(max(0.0, s.get("duration_s") or 0.0) for s in mine)
    # nothing but zero-length markers (PR 24's session.*): nothing to read
    return scale * total / n_tx if total > 0 else None
