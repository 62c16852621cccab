"""How much of the window one thread spent in the program's own spans, %.

The spans carry the name of the thread that opened them. The thread is the
one with most ``by`` time (``flow.step``: the node's thread, on which every
flow of every node runs); the share is the union of its ``spans`` and of
its spans under ``prefixes``, cut to the window, over the window's length.
Waiting spans (``wait.*``) are left out by naming none. Spans without a
thread (a program before the tag) give None."""
import span_walk


def read(data, by, spans=(), prefixes=(), scale=100.0):
    lo, hi = span_walk.window_of(data)
    if not lo < hi < float("inf"):
        return None
    per_thread: dict = {}
    for s in span_walk.named(data, {by}):
        if s.get("thread"):
            per_thread[s["thread"]] = per_thread.get(s["thread"], 0.0) \
                + max(0.0, s.get("duration_s") or 0.0)
    if not per_thread:
        return None
    thread = max(per_thread, key=per_thread.get)
    names, prefixes = set(spans) | {by}, tuple(prefixes)
    busy = span_walk.union_s(
        (max(lo, s["start_s"]), min(hi, span_walk.end_of(s)))
        for s in data["spans"]
        if s.get("thread") == thread and s.get("start_s") is not None
        and (s.get("name") in names
             or str(s.get("name", "")).startswith(prefixes)))
    return scale * busy / (hi - lo)
