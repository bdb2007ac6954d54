"""Metrics from the load generator's own stamps."""


def read(ctx, value: str):
    """``value`` names an entry of ``metrics.end_to_end()['values']``
    (ttft_cold_p50_ms, late_p99_ms, ...)."""
    return ctx.client["values"].get(value)
