"""Metrics from the ``/stats`` counters of a latent server that attends
every cached row and drafts with its own multi-token-prediction module
(``tpushare.models.latent.LatentSlotServer.family_stats``:
``latent_rows_read``, ``mtp_*``). A program that lacks a counter gives
None, and the metric is left out."""

from tpubench.readers.stats_delta import _delta


def read(ctx, kind: str):
    """kind = "cache_share_pct": of the bytes the window's ticks had to
    read, the share that is cached latent rows: delta of
    ``latent_rows_read`` (every cached layer's rows of every active slot
    up to the tick's last write, counted by the program on the host)
    times a row's bytes (``latent_row_bytes.full``), over itself plus the
    window's forwards times the weights one forward reads
    (``peaks.forward_weight_bytes``, weights alone: a lower bound, so the
    share reads high rather than low)."""
    if kind != "cache_share_pct":
        raise ValueError(f"unknown kind {kind!r}")
    from tpubench import peaks
    rows = _delta(ctx, "latent_rows_read")
    fwd = _delta(ctx, "model_forwards")
    width = (ctx.stats_after.get("latent_row_bytes") or {}).get("full")
    if rows is None or fwd is None or width is None or not rows + fwd:
        return None
    moved = rows * width
    return 100.0 * moved / (
        moved + fwd * peaks.forward_weight_bytes(ctx.cell.config))
