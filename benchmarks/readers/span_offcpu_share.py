"""How much of their wall time the spans of one name spent OFF the CPU, %:
100 x sum(max(0, duration_s - cpu_s)) / sum(duration_s) over the spans that
start in the window and carry ``cpu_s`` (the tracer's ``cpu=True``: the
opening thread's CPU clock over the span). A ratio of SUMS, because the
thread clock of the chip's host ticks coarsely: one span's difference is
noise, a window's is not. ``tag`` / ``equals`` keep the spans whose tag holds
that value. A span without ``cpu_s`` is left out of both sums; None where no
span carries one (a program before the argument)."""
import span_walk


def read(data, span, tag=None, equals=None, scale=100.0):
    wall = off = 0.0
    for s in span_walk.named(data, {span}):
        if tag is not None and span_walk.tags_of(s).get(tag) != equals:
            continue
        cpu, dur = s.get("cpu_s"), s.get("duration_s")
        if cpu is None or dur is None:
            continue
        wall += max(0.0, dur)
        off += max(0.0, dur - cpu)
    return scale * off / wall if wall > 0 else None
