"""From a profiler trace to numbers: device busy/idle, kernel time, breakdown.

The JAX profiler writes an ``.xplane.pb``; ``load_xplane`` flattens it into a
plain ``events`` dict (also the form of the recorded fixture under
``fixtures/``) and ``reduce`` turns that into the numbers the result line and
the trace readers use:

- busy = union of the intervals in which an operation ran on the device,
  clipped to the traced window, averaged over the device planes; where the
  profiler's buffer filled up ("Trace Buffers Dropped") the window ends at
  that mark, because nothing after it was recorded;
- each kernel's events (the per-program line) by name, with durations;
- the longest idle gaps, each named by the host span (the benchmark's own or
  the program's tracer's, both on the wall clock) that covers most of it.

Events are ``[name, start_ns, duration_ns]`` on the profiler's own clock. The
window is the ``WINDOW_EVENT`` annotation the runner holds open between
``start_trace`` and ``stop_trace``; its start is also where the wall clock and
the profiler's clock are tied together.
"""
from __future__ import annotations

import gzip
import json

WINDOW_EVENT = "bench.trace_window"
#: device lines, in order of preference for "an operation ran"
OPS_LINES = ("XLA Ops", "XLA Modules")
MODULES_LINE = "XLA Modules"
#: the profiler's own mark on a device plane once its buffer is full: what
#: follows it was not recorded, so the window ends there
DROPPED_EVENT = "Trace Buffers Dropped"
TOP = 10
#: idle gaps shorter than this are not listed (they are still idle time)
MIN_GAP_S = 0.0005


def load_xplane(path) -> dict:
    """``{"devices": {plane: {line: [[name, start_ns, dur_ns], ...]}},
    "window": [start_ns, dur_ns] | None}`` from an ``.xplane.pb``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    devices: dict = {}
    window = None
    for plane in data.planes:
        is_device = plane.name.startswith("/device:") and \
            "CPU" not in plane.name
        if is_device:
            lines = devices.setdefault(plane.name, {})
            for line in plane.lines:
                # an op's name is its whole HLO text: keep "%fusion.12".
                # Of a line no reader reads (a traced EC kernel leaves
                # millions of events on each) only the dropped mark is kept
                wanted = line.name in OPS_LINES
                evs = [[ev.name.split(" = ", 1)[0], float(ev.start_ns),
                        float(ev.duration_ns)] for ev in line.events
                       if wanted or ev.name == DROPPED_EVENT]
                if wanted or evs:
                    lines[line.name] = evs
        elif window is None:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_EVENT:
                        window = [float(ev.start_ns), float(ev.duration_ns)]
                        break
                if window is not None:
                    break
    return {"devices": devices, "window": window}


def save_events(events: dict, path, per_line: int | None = 4000) -> None:
    """Write the flattened trace, each line cut to its first ``per_line``
    events (a traced EC kernel leaves millions of op events)."""
    cut = {"window": events.get("window"), "devices": {
        plane: {line: evs[:per_line] for line, evs in lines.items()}
        for plane, lines in events["devices"].items()}}
    with gzip.open(path, "wt") as f:
        json.dump(cut, f)


def load_events(path) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def merge_intervals(intervals) -> list:
    """Sorted, disjoint union of (start, end) pairs."""
    merged: list = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def _clip(events, lo, hi):
    for _name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            yield s, e


def _busy_line(lines: dict) -> str | None:
    for name in OPS_LINES:
        if lines.get(name):
            return name
    return None


def _window_of(events: dict):
    """(lo_ns, hi_ns): the held-open annotation, else the span of all
    device events."""
    if events.get("window"):
        lo, dur = events["window"]
        hi = lo + dur
        for lines in events["devices"].values():
            for evs in lines.values():
                for name, start, _d in evs:
                    if name == DROPPED_EVENT and lo < start < hi:
                        hi = start
        return lo, hi
    starts, ends = [], []
    for lines in events["devices"].values():
        for evs in lines.values():
            for _n, s, d in evs:
                starts.append(s)
                ends.append(s + d)
    if not starts:
        return 0.0, 0.0
    return min(starts), max(ends)


def kernel_events(events: dict, substring: str) -> list:
    """Durations (s) of every per-program event whose name holds
    ``substring``, over all device planes, inside the window."""
    lo, hi = _window_of(events)
    out = []
    for lines in events["devices"].values():
        for name, start, dur in lines.get(MODULES_LINE, ()):
            if substring in name and start >= lo and start + dur < hi:
                out.append(dur / 1e9)
    return out


def reduce(events: dict, host_spans=(), wall_t0: float | None = None,
           gap_prefixes=("host.",)) -> dict:
    """See the module docstring. ``host_spans`` are dicts with ``name``,
    ``start_s`` (wall clock) and ``duration_s``; ``wall_t0`` is the wall
    clock at the window annotation's start; only spans whose name starts
    with one of ``gap_prefixes`` are charged with idle gaps."""
    lo, hi = _window_of(events)
    window_s = max(0.0, (hi - lo) / 1e9)
    busy_total = 0.0
    n_events = 0
    used_line = None
    op_time: dict[str, float] = {}
    gaps: list = []
    planes = [ls for ls in events["devices"].values() if _busy_line(ls)]
    if not planes and hi > lo:
        gaps.append((lo, hi))   # no operation ran: the window is one idle gap
    for lines in planes:
        used_line = _busy_line(lines)
        evs = lines[used_line]
        n_events += len(evs)
        merged = merge_intervals(_clip(evs, lo, hi))
        busy_total += sum(e - s for s, e in merged) / 1e9
        cursor = lo
        for s, e in merged + [[hi, hi]]:
            if (s - cursor) / 1e9 >= MIN_GAP_S:
                gaps.append((cursor, s))
            cursor = max(cursor, e)
        # time by operation: the per-program line where there is one (an
        # op line nests: a while loop holds its body's ops)
        named = lines.get(MODULES_LINE) or evs
        for name, start, dur in named:
            s, e = max(start, lo), min(start + dur, hi)
            if e > s:
                op_time[name] = op_time.get(name, 0.0) + (e - s) / 1e9
    n_planes = max(1, len(planes))
    clock_known = bool(events.get("window")) and wall_t0 is not None
    idle_by: dict[str, float] = {}
    if clock_known:
        # wall-clock gaps; every eligible host span open during a gap is
        # charged its overlap (spans of different threads overlap, so the
        # names can add up to more than the idle time)
        wall_gaps = [(wall_t0 + (a - lo) / 1e9, wall_t0 + (b - lo) / 1e9)
                     for a, b in gaps]
        covered = [0.0] * len(wall_gaps)
        for s in host_spans:
            if s.get("start_s") is None or not str(s.get("name", "")) \
                    .startswith(tuple(gap_prefixes)):
                continue
            s_lo = s["start_s"]
            s_hi = s_lo + (s.get("duration_s") or 0.0)
            for i, (g_lo, g_hi) in enumerate(wall_gaps):
                cover = min(g_hi, s_hi) - max(g_lo, s_lo)
                if cover > 0:
                    idle_by[s["name"]] = idle_by.get(s["name"], 0.0) + cover
                    covered[i] += cover
        bare = sum(max(0.0, (g_hi - g_lo) - c)
                   for (g_lo, g_hi), c in zip(wall_gaps, covered))
        if bare > 0:
            idle_by["host: no span open"] = bare
    else:
        idle_by["host: clock not tied"] = sum(b - a for a, b in gaps) / 1e9

    def top(d):
        return [[k, v / n_planes] for k, v in
                sorted(d.items(), key=lambda kv: kv[1], reverse=True)[:TOP]]

    return {"busy_s": busy_total / n_planes, "window_s": window_s,
            "busy_line": used_line, "n_device_events": n_events,
            "clock_offset_known": clock_known,
            "breakdown": {"device_ops": top(op_time),
                          "idle_gaps": top(idle_by)},
            "events": events}
