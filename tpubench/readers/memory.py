"""Peak device memory, from ``memory_stats()`` after the window."""


def read(ctx, unit_bytes: float = 1e9):
    peak = ctx.device.get("memory_peak_bytes")
    return None if peak is None else peak / unit_bytes
