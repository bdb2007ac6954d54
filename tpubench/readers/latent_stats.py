"""Metrics from the ``/stats`` counters that only the latent family
has (``tpushare.models.latent.LatentSlotServer.family_stats``). A
program that lacks a counter gives None, and the metric is left out."""

from tpubench.readers.stats_delta import _delta


def read(ctx, kind: str, num: str = None, den: str = None, key: str = None):
    """kind = "ratio": delta(num) / delta(den) over the window.
    kind = "load_max_over_mean": over the deltas of the list
    stats[key] (assignments a held expert of a sparse layer took), the
    largest over the mean.
    kind = "window_dead_pct": of the bytes of latent rows the slots'
    tables hold, the share that lies behind every window still to come
    on the sliding layers (what freeing behind the window would return):
    the mean over the window's ``/stats`` samples in which a slot holds
    anything (the sample after the window can find the loop's streams
    finished)."""
    if kind == "ratio":
        n, d = _delta(ctx, num), _delta(ctx, den)
        return None if n is None or not d else n / d
    if kind == "load_max_over_mean":
        a, b = ctx.stats_before.get(key), ctx.stats_after.get(key)
        if a is None or b is None or len(a) != len(b):
            return None
        loads = [y - x for x, y in zip(a, b)]
        total = sum(loads)
        return None if not total else max(loads) * len(loads) / total
    if kind == "window_dead_pct":
        shares = []
        for st in [*getattr(ctx, "stats_samples", []), ctx.stats_after]:
            live, dead = st.get("latent_rows_live"), st.get("window_rows_dead")
            width = st.get("latent_row_bytes")
            if live is None or dead is None or width is None:
                continue
            held = sum(live[k] * width[k] for k in live)
            if held:
                shares.append(100.0 * dead * width["sliding"] / held)
        return sum(shares) / len(shares) if shares else None
    raise ValueError(f"unknown kind {kind!r}")
