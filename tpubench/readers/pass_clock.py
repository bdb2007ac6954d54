"""Metrics from the engine's pass ledger and the thread-CPU clocks in
``/stats``, as deltas over the whole window.

What this reader touches inside the program (``tpushare/cli/serve.py``;
a refactor that renames one moves the metric that reads it):

  ``engine_pass``        {kind: {"n", "wall_ms", "wait_ms", "cpu_ms"}},
                         cumulative: one pass is one ``_loop_once`` of
                         the engine's thread, filed under exactly one
                         of ``PASS_KINDS`` (``idle``, ``decode``,
                         ``admit``, ``fused``). ``wall_ms`` the pass's
                         monotonic span, ``wait_ms`` its ``finalize``
                         stage (the host waiting for the device),
                         ``cpu_ms`` the thread's CPU time outside it
  ``engine_thread_cpu_ms``
                         {stage: cumulative ms}: ``ENGINE_STAGES`` on
                         the thread's CPU clock (``StageClock``)
  ``http_turn_cpu_ms``, ``http_turns``
                         the handler threads' stream turns (wake-up to
                         the next wait: drain, encode, write, flush) on
                         their CPU clocks, and their count
  ``work_ticks``         ticks that ran a model forward

A pass's wall less its wait less its CPU is the engine's thread off the
CPU outside the fetch: the interpreter's lock held by another thread,
a device sync inside ``admit`` or a serial ``dispatch``, the OS.
"""

import json

from tpubench.readers import pass_trace
from tpubench.readers.stage import NOT_HOST
from tpubench.readers.stats_delta import _delta

PASS_FIELDS = ("n", "wall_ms", "wait_ms", "cpu_ms")


def _passes(ctx):
    """{kind: {field: delta}} of ``engine_pass`` over the window, or None
    where the program has no pass ledger."""
    p0 = ctx.stats_before.get("engine_pass")
    p1 = ctx.stats_after.get("engine_pass")
    if not isinstance(p0, dict) or not isinstance(p1, dict):
        return None
    out = {}
    for kind, r1 in p1.items():
        r0 = p0.get(kind)
        if not isinstance(r0, dict) or not isinstance(r1, dict) or any(
                f not in r0 or f not in r1 for f in PASS_FIELDS):
            return None
        out[kind] = {f: r1[f] - r0[f] for f in PASS_FIELDS}
    return out


def _log(d, ticks, turns):
    """Once a run, before its last line: every kind's split a pass, and
    the handler turns a tick."""
    rows = {k: {"n": r["n"], **{
        f: round(v / r["n"], 3) for f, v in (
            ("wall", r["wall_ms"]), ("wait", r["wait_ms"]),
            ("cpu", r["cpu_ms"]),
            ("offcpu", r["wall_ms"] - r["wait_ms"] - r["cpu_ms"]))}}
        for k, r in d.items() if r["n"]}
    wall = sum(r["wall_ms"] for r in d.values())
    print("[tpubench pass_clock] ms a pass by kind: " + json.dumps(rows)
          + f"; passes hold {wall:.0f} ms of the engine's thread; "
          f"{ticks} work ticks; handler turns a tick "
          + (f"{turns / ticks:.2f}" if turns is not None and ticks
             else "none"), flush=True)


def read(ctx, value: str, kind: str = "decode"):
    """value = "pass_ms" | "wait_ms" | "offcpu_ms": over passes of
    ``kind``, delta ``wall_ms``, ``wait_ms`` or ``wall_ms - wait_ms -
    cpu_ms`` over delta ``n``; "pass_ms" also logs every kind's split,
    and in a traced run the slice's (``pass_trace``).
    value = "host_cpu_ms_per_tick": delta ``engine_thread_cpu_ms`` over
    every stage but ``finalize`` and ``idle``, over delta
    ``work_ticks``.
    value = "handler_cpu_ms_per_tick": delta ``http_turn_cpu_ms`` over
    delta ``work_ticks``.
    None where the program has no such key, or nothing was counted."""
    ticks = _delta(ctx, "work_ticks")
    if value in ("pass_ms", "wait_ms", "offcpu_ms"):
        d = _passes(ctx)
        if d is None or kind not in d:
            return None
        if value == "pass_ms":
            _log(d, ticks, _delta(ctx, "http_turns"))
            if getattr(ctx, "trace", None) is not None:
                # the slice's split by kind, also in a cell whose list
                # ``device.decode_idle_ms`` leaves out
                pass_trace.split(ctx)
        r = d[kind]
        if not r["n"]:
            return None
        v = {"pass_ms": r["wall_ms"], "wait_ms": r["wait_ms"],
             "offcpu_ms": r["wall_ms"] - r["wait_ms"] - r["cpu_ms"]}[value]
        return v / r["n"]
    if value == "host_cpu_ms_per_tick":
        c0 = ctx.stats_before.get("engine_thread_cpu_ms")
        c1 = ctx.stats_after.get("engine_thread_cpu_ms")
        if not isinstance(c0, dict) or not isinstance(c1, dict) or not ticks:
            return None
        names = [s for s in c1 if s not in NOT_HOST]
        if not names or any(s not in c0 for s in names):
            return None
        return sum(c1[s] - c0[s] for s in names) / ticks
    if value == "handler_cpu_ms_per_tick":
        cpu = _delta(ctx, "http_turn_cpu_ms")
        return None if cpu is None or not ticks else cpu / ticks
    raise ValueError(f"unknown value {value!r}")
