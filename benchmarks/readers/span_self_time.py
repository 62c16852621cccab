"""Per-transaction time one component holds on the blocking chain.

The benchmark's own reduction from the program's span trees (one trace id per
flow) to a number, after the standard trace critical-path walk: from the root
span's end, step to the child that was running at the cursor and finished
last; what lies between consecutive blocking children is the parent's self
time. Every millisecond of the chain is charged to exactly one span, and a
span to a component by its name. The quantile is taken over the traces whose
root is a ``flow.run`` of one of ``flow_types`` and which started inside the
window."""
from bench_common import nearest_rank

#: span-name prefix -> component (first match wins)
RULES = (("wait.scheduler_admission", "scheduler.wait"),
         ("wait.verif", "verify"), ("wait.group_commit_round", "raft.commit"),
         ("wait.group_commit", "notary.batch_wait"),
         ("wait.raft_leaderless", "raft.leaderless"),
         ("wait.await_future", "notary.batch_wait"),
         ("flow.", "flow.compute"), ("tx.verify", "verify"),
         ("verifier.", "verify"), ("batcher.", "verify"),
         ("notary.", "notary.batch_wait"), ("raft.", "raft.commit"),
         ("vault.", "vault"), ("session.", "network"), ("net.", "network"))
WAIT_KINDS = {"scheduler.admission": "scheduler.wait", "verify.park": "verify",
              "verify.gather": "verify", "verifier.admission": "verify",
              "notary.commit": "notary.batch_wait",
              "group_commit.queue": "notary.batch_wait",
              "group_commit.defer": "notary.batch_wait",
              "group_commit.round": "raft.commit",
              "raft.leaderless": "raft.leaderless"}


def component_of(span) -> str:
    tags = span.get("tags") if isinstance(span.get("tags"), dict) else {}
    comp = WAIT_KINDS.get(tags.get("wait_kind"))
    if comp is not None:
        return comp
    name = str(span.get("name", ""))
    for prefix, comp in RULES:
        if name.startswith(prefix):
            return comp
    return "other"


def _end(s):
    return s["start_s"] + max(0.0, s.get("duration_s") or 0.0)


def blame(spans) -> tuple[dict, dict] | None:
    """(root span, {component: seconds on the blocking chain}) of one trace."""
    nodes = {s["span_id"]: s for s in spans
             if s.get("span_id") and s.get("start_s") is not None}
    roots = [s for s in nodes.values() if s.get("name") == "flow.run"
             and s.get("parent_id") not in nodes]
    if not roots:
        return None
    root = max(roots, key=lambda s: s.get("duration_s") or 0.0)
    kids: dict = {}
    for s in nodes.values():
        kids.setdefault(s.get("parent_id"), []).append(s)
    out: dict = {}
    seen = {root["span_id"]}
    stack = [(root, root["start_s"], _end(root))]
    while stack:
        span, t_lo, t_hi = stack.pop()
        start = max(span["start_s"], t_lo)
        cursor = min(_end(span), t_hi)
        own = 0.0
        for child in sorted(kids.get(span["span_id"], ()), key=_end,
                            reverse=True):
            if cursor <= start:
                break
            if child["span_id"] in seen or child["start_s"] >= cursor:
                continue
            c_end = min(_end(child), cursor)
            c_start = max(child["start_s"], start)
            if c_end <= c_start:
                continue
            own += cursor - c_end
            seen.add(child["span_id"])
            stack.append((child, c_start, c_end))
            cursor = c_start
        own += max(0.0, cursor - start)
        comp = component_of(span)
        out[comp] = out.get(comp, 0.0) + own
    return root, out


def read(data, component, q, flow_types=None, scale=1000.0):
    spans = data.get("spans") or []
    if not spans:
        return None
    by_trace: dict = {}
    for s in spans:
        by_trace.setdefault(s.get("trace_id"), []).append(s)
    lo, hi = data.get("window_wall", (float("-inf"), float("inf")))
    vals = []
    for trace_spans in by_trace.values():
        got = blame(trace_spans)
        if got is None:
            continue
        root, parts = got
        tags = root.get("tags") if isinstance(root.get("tags"), dict) else {}
        kind = str(tags.get("flow_type", ""))
        if flow_types and not any(t in kind for t in flow_types):
            continue
        if not lo <= root["start_s"] <= hi:
            continue
        vals.append(parts.get(component, 0.0))
    if not vals:
        return None
    return scale * nearest_rank(sorted(vals), q)
