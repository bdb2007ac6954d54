"""Metrics from the engine's ``/stats`` counters, as deltas over the
window (the counters themselves run from the engine's start and include
the warm-up)."""


def _delta(ctx, key):
    a, b = ctx.stats_before.get(key), ctx.stats_after.get(key)
    if a is None or b is None:
        return None
    return b - a


def read(ctx, kind: str, num: str = None, den: str = None, key: str = None,
         field: str = None):
    """kind = "ratio_pct": 100 x delta(num) / delta(den).
    kind = "window_per_ms": window length in ms over delta(den).
    kind = "gauge": stats_after[key][field] (a value the program already
    reduces, such as host_gap_ms.p50).
    kind = "sampled_peak_pct": 100 x the largest sampled stats[num] over
    the pool's usable blocks (den names the engine size)."""
    if kind == "ratio_pct":
        n, d = _delta(ctx, num), _delta(ctx, den)
        return None if n is None or not d else 100.0 * n / d
    if kind == "window_per_ms":
        d = _delta(ctx, den)
        return None if not d else 1e3 * ctx.window_s / d
    if kind == "gauge":
        v = ctx.stats_after.get(key)
        if isinstance(v, dict):
            v = v.get(field)
        return None if v is None else float(v)
    if kind == "sampled_peak_pct":
        xs = [s[num] for s in ctx.stats_samples if s.get(num) is not None]
        total = ctx.cell.engine[den] - 1        # one block is the trash block
        return None if not xs else 100.0 * max(xs) / total
    raise ValueError(f"unknown kind {kind!r}")
