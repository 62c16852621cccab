"""A quantile of one registry histogram over the window only.

The program's histograms are cumulative since process start and keep
log-scaled buckets (quarter decades), so the window's distribution is the
difference of two snapshots' bucket counts and the quantile is interpolated
inside one bucket: good to a factor of 10**0.25 at worst."""


def window_buckets(data, metric):
    """[(upper_edge, count_in_window)] in ascending order, +Inf last."""
    h1 = data.get("snap1", {}).get(metric)
    if not h1 or "buckets" not in h1:
        return []
    h0 = data.get("snap0", {}).get(metric) or {}

    def per_bucket(snap):
        out, prev = {}, 0
        for le, cum in snap.get("buckets", []):
            out[le] = cum - prev
            prev = cum
        return out

    b1, b0 = per_bucket(h1), per_bucket(h0)
    rows = [(float("inf") if le == "+Inf" else float(le), n - b0.get(le, 0))
            for le, n in b1.items()]
    return sorted((edge, n) for edge, n in rows if n > 0)


def read(data, metric, q, scale=1.0):
    rows = window_buckets(data, metric)
    total = sum(n for _e, n in rows)
    if total <= 0:
        return None
    target = max(1.0, q * total)
    cum = 0
    for edge, n in rows:
        if cum + n >= target:
            if edge == float("inf"):
                return scale * data["snap1"][metric]["max"]
            lo = edge / 10 ** 0.25
            return scale * (lo + (target - cum) / n * (edge - lo))
        cum += n
    return None
