"""Metrics from the engine thread's stage clocks and the admission
counters in ``/stats``, as deltas over the window.

What this reader touches inside the program (``tpushare/cli/serve.py``;
a refactor that renames one moves the metric that reads it):

  ``engine_thread_ms``   {stage: cumulative ms}: ``ENGINE_STAGES``, the
                         engine thread's loop cut into ``preamble``,
                         ``admit``, ``finalize``, ``apply``,
                         ``schedule``, ``dispatch``, ``plan``,
                         ``journal``, ``idle``
                         (``utils/profiling.StageClock``)
  ``work_ticks``         ticks that ran a model forward
  ``queue_wait_ms_sum``, ``queue_wait_n``
                         submit to the admission that placed the
                         request, once a request
  ``admit_ms_sum``, ``admit_n``
                         that admission's start to the request's first
                         token

"host" leaves out ``finalize`` (the deferred fetch) and ``idle`` (the
sleep of an empty engine). What remains is the thread's time outside
those two, an upper bound on the host's own work and not that work
alone: ``admit`` and ``dispatch`` wait for the device too. An
admission's eager pool scatter (span ``slot.admit.scatter``) returns
only when the prefill and the pool-sized copy before it have run, its
``slot.admit.lookup`` reads the prompt back from the device, every
admission fetches its first token (``slot.admit.first_token``), and the
serial tick's ``dispatch`` holds the step's fetch. No host clock can
tell such a wait from work without a barrier; the device's idle time
under each span (``readers/program_trace.py``) says how much of the
"host" time the chip in fact waited for.
"""

import json

from tpubench.readers.stats_delta import _delta

#: stages left out of the "host" sum (see above)
NOT_HOST = ("finalize", "idle")


def read(ctx, kind: str, stage: str = None):
    """kind = "ms_per_tick": delta ``engine_thread_ms[stage]`` over delta
    ``work_ticks``; ``stage="host"`` sums every stage but ``finalize``
    and ``idle``.
    kind = "ms_per": delta ``<stage>_ms_sum`` over delta ``<stage>_n``
    (``stage``: ``queue_wait`` | ``admit``).
    None where the program has no such key, or nothing was counted."""
    if kind == "ms_per":
        total, n = _delta(ctx, stage + "_ms_sum"), _delta(ctx, stage + "_n")
        return None if total is None or not n else total / n
    if kind == "ms_per_tick":
        ms0 = ctx.stats_before.get("engine_thread_ms")
        ms1 = ctx.stats_after.get("engine_thread_ms")
        ticks = _delta(ctx, "work_ticks")
        if ms0 is None or ms1 is None or not ticks:
            return None
        names = ([s for s in ms1 if s not in NOT_HOST] if stage == "host"
                 else [stage])
        if any(s not in ms0 or s not in ms1 for s in names):
            return None
        if stage == "host":
            # Once a run (one metric reads "host"), before its last
            # line: the whole split, and how much of the window's wall
            # clock the stages account for.
            d = {s: ms1[s] - ms0.get(s, 0.0) for s in ms1}
            print("[tpubench stage] ms per work tick: " + json.dumps(
                {s: round(v / ticks, 3) for s, v in d.items()})
                + f"; stages hold {sum(d.values()):.0f} of the window's "
                f"{1e3 * ctx.window_s:.0f} ms of the engine's thread, "
                f"{ticks} work ticks", flush=True)
        return sum(ms1[s] - ms0[s] for s in names) / ticks
    raise ValueError(f"unknown kind {kind!r}")
