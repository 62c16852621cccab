"""What both drivers and several readers share: one quantile rule, and the
checks every cell makes on the program's device path."""
from __future__ import annotations

BATCHER_METERS = ("DeviceChecked", "HostRouted", "BatchFailure",
                  "BreakerRouted", "DeviceBatches")


def nearest_rank(sorted_vals, q: float) -> float:
    """Nearest-rank quantile of an ascending list (nan when empty)."""
    if not sorted_vals:
        return float("nan")
    idx = min(len(sorted_vals) - 1,
              max(0, int(round(q * (len(sorted_vals) - 1)))))
    return sorted_vals[idx]


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
