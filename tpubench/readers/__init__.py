"""One reader per file, found by the ``reader`` name in a
``layers/<metric>.json``. A reader is

    read(ctx, **args) -> float | None

where ``ctx`` (``tpubench.run.Context``) holds what one run gathered:
the client's rows and their reduction, ``/stats`` before and after the
window and once a second inside it, the reduced device trace, memory
statistics, the configuration and the peak table. A reader that finds
nothing to read returns None and the metric is left out of the line.
"""
