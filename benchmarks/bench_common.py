"""What the drivers and several readers share: one quantile rule, the wave
cells' one rate rule, a watch on the interpreter's collector, and the checks
every cell makes on the program's device path."""
from __future__ import annotations

import gc
import statistics
import time

BATCHER_METERS = ("DeviceChecked", "HostRouted", "BatchFailure",
                  "BreakerRouted", "DeviceBatches")


def nearest_rank(sorted_vals, q: float) -> float:
    """Nearest-rank quantile of an ascending list (nan when empty)."""
    if not sorted_vals:
        return float("nan")
    idx = min(len(sorted_vals) - 1,
              max(0, int(round(q * (len(sorted_vals) - 1)))))
    return sorted_vals[idx]


def window_rate(done_s, window_s: float, rows_per_wave: int,
                slices: int = 6) -> dict:
    """The ONE rule of ``sigs_per_s``, for every wave cell: signature
    verdicts returned inside the window over the whole of the clock's window.

    ``done_s`` holds, for every wave a client of the window handed over, the
    second after the window opened at which its last verdict was back. A wave
    counts whole or not at all: it is inside if that second is at most
    ``window_s``, the length of the clock's window, and a wave that returns
    later counts for nothing. ``sigs_per_s`` = waves inside x
    ``rows_per_wave`` / ``window_s``: all the work over all the time, a
    frozen second included; its step is one wave.

    Beside it, as notes of the ``window`` line and never as the metric: when
    the last verdict inside returned (``last_verdict_s``) and the rate up to
    then, the rate of each of ``slices`` equal slices of the window (a wave
    belongs to the slice its last verdict returned in) and their median. A
    stall shows as one low slice; a run whose level differs shows in all.
    """
    inside = [t for t in done_s if t <= window_s]
    last = max(inside, default=0.0)
    width = window_s / slices
    counts = [0] * slices
    for t in inside:
        counts[min(slices - 1, int(t / width))] += 1
    slice_rates = [n * rows_per_wave / width for n in counts]
    return {"sigs_per_s": len(inside) * rows_per_wave / window_s,
            "waves_completed_inside": len(inside),
            "waves_finished_after": len(done_s) - len(inside),
            "window_s": window_s,
            "last_verdict_s": last,
            "rate_to_last_verdict": len(inside) * rows_per_wave / last
            if inside else 0.0,
            "slice_rates": slice_rates,
            "rate_median_of_slices": statistics.median(slice_rates)}


class GcWatch:
    """Times the interpreter's collections between ``start()`` and
    ``stop()``: a collection stops every thread of the process."""

    def __init__(self):
        self.seconds = 0.0
        self.longest_s = 0.0
        self.collections = [0, 0, 0]        # by generation
        self._t0 = 0.0

    def __call__(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            took = time.perf_counter() - self._t0
            self.seconds += took
            self.longest_s = max(self.longest_s, took)
            self.collections[info.get("generation", 0)] += 1

    def start(self):
        gc.callbacks.append(self)
        return self

    def stop(self) -> dict:
        if self in gc.callbacks:
            gc.callbacks.remove(self)
        return {"gc_s": self.seconds, "gc_longest_ms": self.longest_s * 1e3,
                "gc_collections": list(self.collections)}


def batcher_counts(registry) -> dict:
    return {n: registry.meter(f"SigBatcher.{n}").count
            for n in BATCHER_METERS}


def check_device_path(ctx, registry, batcher) -> dict:
    """No device batch failed or was breaker-routed to the host, every
    breaker is closed, nothing compiled after ``mark_warm()``. Returns the
    batcher's counters for the caller's note."""
    from corda_tpu.observability import get_profiler
    counts = batcher_counts(registry)
    ctx.check("batcher_batch_failures", counts["BatchFailure"], 0)
    ctx.check("batcher_breaker_routed", counts["BreakerRouted"], 0)
    tripped = sum(1 for st in batcher.breaker_status().values()
                  if st["state"] != "closed" or st["trips"])
    ctx.check("breakers_not_closed", tripped, 0)
    ctx.check("compiles_after_mark_warm",
              get_profiler().compiles_since_warm(), 0)
    return counts
