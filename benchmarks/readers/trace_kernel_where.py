"""``trace_kernel_time`` or ``trace_roofline_share`` over ONE shape of a
compiled program that runs at several under one name (a bucket ladder's
rungs), or the share of the program's device calls that ran below the top
rung.

Calls are told apart by SHAPE, not by how long they took: the per-program
line names an event ``jit_<program>(<fingerprint>)``, and the fingerprint is
the executable's, so every rung is a name of its own. Which name is the top
rung: the one whose calls take longest on average (a kernel over more rows
of the same program takes longer; no time is written down here, so a kernel
made twice as fast is still found). Where the sub-window holds ONE name
only, the program's own ``batcher.dispatch`` spans that ended inside it say
which rung that was (their ``batch_size``, padded to the configuration's
``bucket_ladder``); where they do not settle it the answer is None.

``what``: ``"top_ms"`` the mean device time per call at the top rung, in
ms; ``"top_roofline"`` that rung's share of its roofline (``cost``
of ``cost_module`` at the traffic's ``rows_param`` rows); ``"below_top_share"``
100 x the calls at lower rungs / all the program's calls (0 where every
call was a full bucket). None where the trace holds no call of the program.
"""
import trace_reduce
from readers import trace_kernel_time, trace_roofline_share


def calls_by_shape(events: dict, program: str) -> dict:
    """{event name: [duration_s, ...]} of the program's calls inside the
    window, over all device planes."""
    lo, hi = trace_reduce._window_of(events)
    out: dict = {}
    for lines in events["devices"].values():
        for name, start, dur in lines.get(trace_reduce.MODULES_LINE, ()):
            if program in name and start >= lo and start + dur < hi:
                out.setdefault(name, []).append(dur / 1e9)
    return out


def rungs_dispatched(data, ladder) -> set:
    """The ladder's rungs that device-route ``batcher.dispatch`` spans ending
    inside the traced sub-window were padded to; None where the run does not
    say when the sub-window was."""
    t0 = data.get("trace_wall_t0")
    if t0 is None:
        return None
    t1 = t0 + data["trace"]["window_s"]
    seen = set()
    for s in data.get("spans", ()):
        tags = s.get("tags") or {}
        if s.get("name") != "batcher.dispatch" \
                or tags.get("route") != "device":
            continue
        end = s["start_s"] + (s.get("duration_s") or 0.0)
        if t0 <= end <= t1:
            rows = int(tags.get("batch_size", 0))
            seen.add(next((r for r in ladder if r >= rows), ladder[-1]))
    return seen


def top_shape(data, shapes: dict):
    """The event name of the ladder's top rung among ``shapes``, ``""``
    where every call ran below it, None where that cannot be told."""
    ladder = sorted(int(r) for r in
                    data["cell"].config["batcher_args"]["bucket_ladder"])
    if len(shapes) > 1 or len(ladder) == 1:
        return max(shapes, key=lambda n: sum(shapes[n]) / len(shapes[n]))
    seen = rungs_dispatched(data, ladder)
    if not seen or len(seen) > 1:
        return None
    return next(iter(shapes)) if seen == {ladder[-1]} else ""


def read(data, program, what, cost=None, cost_module="kernel_cost",
         rows_param=None):
    tr = data.get("trace")
    if not tr:
        return None
    shapes = calls_by_shape(tr["events"], program)
    if not shapes:
        return None
    top = top_shape(data, shapes)
    if top is None:
        return None
    if what == "below_top_share":
        calls = sum(len(d) for d in shapes.values())
        return 100.0 * (calls - len(shapes.get(top, ()))) / calls
    if not top:
        return None
    line = trace_reduce.MODULES_LINE
    devices = {
        plane: dict(lines, **{line: [
            ev for ev in lines.get(line, ())
            if program not in ev[0] or ev[0] == top]})
        for plane, lines in tr["events"]["devices"].items()}
    picked = dict(data, trace=dict(tr, events=dict(tr["events"],
                                                   devices=devices)))
    if what == "top_ms":
        return trace_kernel_time.read(picked, program)
    if what == "top_roofline":
        return trace_roofline_share.read(picked, program, cost, rows_param,
                                         cost_module)
    raise ValueError(f"trace_kernel_where: what={what!r}")
