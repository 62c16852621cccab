"""Time per counted thing: a sum over the spans of some names that START
in the window or in the drain after it (their durations, or the tag ``tag``
they carry, in seconds), divided by what the meter ``counter`` counted
between the two registry snapshots (which span window and drain too).
``flow_types`` keeps the spans whose ``flow_type`` tag holds one of them.
None where the program has no such span or counted nothing."""
import span_walk


def read(data, spans, counter, tag=None, flow_types=None, scale=1000.0):
    if "snap1" not in data:
        return None
    lo, _hi = span_walk.window_of(data)
    mine = span_walk.named(data, set(spans), lo, float("inf"))
    if flow_types:
        mine = [s for s in mine if any(
            t in str(span_walk.tags_of(s).get("flow_type", ""))
            for t in flow_types)]
    counted = data["snap1"].get(counter, {}).get("count", 0) \
        - data.get("snap0", {}).get(counter, {}).get("count", 0)
    if not mine or counted <= 0:
        return None
    if tag is not None:
        total = sum(float(span_walk.tags_of(s).get(tag) or 0.0) for s in mine)
    else:
        total = sum(max(0.0, s.get("duration_s") or 0.0) for s in mine)
    return scale * total / counted
