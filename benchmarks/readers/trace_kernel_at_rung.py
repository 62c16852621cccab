"""``trace_kernel_time`` or ``trace_roofline_share`` over the ONE rung of a
bucket ladder that a cell's traffic mostly runs at, where that is NOT the
ladder's top (``trace_kernel_where`` reads the top rung, and None where the
sub-window holds no call of it: a cell whose levels fill a middle rung would
read its kernel only when three levels happen to leave in one flush).

The traffic names the rung (``rung_param``: the row count a level's flush is
padded to). Calls are told apart by SHAPE, as ``trace_kernel_where`` tells
them (an event is named ``jit_<program>(<fingerprint>)``, one name a rung),
and the rung's name is the one that RAN MOST OFTEN in the sub-window; no time
is written down here. That the modal shape IS the traffic's rung is checked
against the program's own ``batcher.dispatch`` spans that ended inside the
sub-window: most of them have to have been padded to it (their
``batch_size``, padded to the configuration's ``bucket_ladder``). Where they
say otherwise, where two shapes tie, or where the run does not say when the
sub-window was, the answer is None.

``what``: ``"ms"`` the mean device time per call at that rung, in ms;
``"roofline"`` that rung's share of its roofline (``cost`` of
``cost_module`` at the traffic's ``rung_param`` rows). None where the trace
holds no call of the program."""
import trace_reduce
from readers import trace_kernel_time, trace_kernel_where, trace_roofline_share


def modal_rung_dispatched(data, ladder):
    """The ladder's rung that most device-route ``batcher.dispatch`` spans
    ending inside the traced sub-window were padded to; None where the run
    does not say when the sub-window was, or holds no such span."""
    t0 = data.get("trace_wall_t0")
    if t0 is None:
        return None
    t1 = t0 + data["trace"]["window_s"]
    counts: dict = {}
    for s in data.get("spans", ()):
        tags = s.get("tags") or {}
        if s.get("name") != "batcher.dispatch" \
                or tags.get("route") != "device":
            continue
        if t0 <= s["start_s"] + (s.get("duration_s") or 0.0) <= t1:
            rows = int(tags.get("batch_size", 0))
            rung = next((r for r in ladder if r >= rows), ladder[-1])
            counts[rung] = counts.get(rung, 0) + 1
    return max(counts, key=counts.get) if counts else None


def read(data, program, what, rung_param, cost=None,
         cost_module="kernel_cost"):
    tr = data.get("trace")
    if not tr:
        return None
    shapes = trace_kernel_where.calls_by_shape(tr["events"], program)
    if not shapes:
        return None
    by_calls = sorted(shapes, key=lambda n: len(shapes[n]), reverse=True)
    if len(by_calls) > 1 \
            and len(shapes[by_calls[0]]) == len(shapes[by_calls[1]]):
        return None
    ladder = sorted(int(r) for r in
                    data["cell"].config["batcher_args"]["bucket_ladder"])
    if modal_rung_dispatched(data, ladder) \
            != int(data["cell"].traffic[rung_param]):
        return None
    line = trace_reduce.MODULES_LINE
    devices = {
        plane: dict(lines, **{line: [
            ev for ev in lines.get(line, ())
            if program not in ev[0] or ev[0] == by_calls[0]]})
        for plane, lines in tr["events"]["devices"].items()}
    picked = dict(data, trace=dict(tr, events=dict(tr["events"],
                                                   devices=devices)))
    if what == "ms":
        return trace_kernel_time.read(picked, program)
    if what == "roofline":
        return trace_roofline_share.read(picked, program, cost, rung_param,
                                         cost_module)
    raise ValueError(f"trace_kernel_at_rung: what={what!r}")
