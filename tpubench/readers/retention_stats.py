"""Metrics from the ``/stats`` counters that only the retention family
has (``tpushare.models.retention.RetentionSlotServer.family_stats``). A
program that lacks a counter gives None, and the metric is left out."""

from tpubench.readers.stats_delta import _delta


def read(ctx, kind: str):
    """kind = "state_share_pct": of the bytes the window's ticks had to
    move, the share that is recurrent state: delta of
    ``retention_state_bytes_moved`` (every active slot's state read and
    written once a layer a tick, counted by the program on the host)
    over itself plus the window's forwards times the weights one forward
    reads (``peaks.forward_weight_bytes``, weights alone).
    kind = "state_live_gb": the state of the active slots, GB: the mean
    over the window's ``/stats`` samples (and the one at its end)."""
    if kind == "state_share_pct":
        from tpubench import peaks
        moved = _delta(ctx, "retention_state_bytes_moved")
        fwd = _delta(ctx, "model_forwards")
        if moved is None or fwd is None or not moved + fwd:
            return None
        weights = fwd * peaks.forward_weight_bytes(ctx.cell.config)
        return 100.0 * moved / (moved + weights)
    if kind == "state_live_gb":
        xs = [st.get("retention_state_bytes_live")
              for st in [*getattr(ctx, "stats_samples", []), ctx.stats_after]]
        xs = [x for x in xs if x is not None]
        return sum(xs) / len(xs) / 1e9 if xs else None
    raise ValueError(f"unknown kind {kind!r}")
