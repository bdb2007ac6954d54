"""The program's own spans and program names in the traced run's
``.xplane.pb``, beside the device's busy time: which span of the
engine's thread the device was idle under, how many programs a tick
launches, and what share of the device's busy time admissions take.

``readers/trace.py`` keeps the harness's own ``tpubench.*`` wrappers
and the ledger's ``breakdown``; this reader finds the same file again
(``tpubench_out/<cell>.trace[.rehearse]/trace``) and reads what the
program wrote there itself. On a program that writes none of it (the
parent of the PR that added the spans) every value is None.

What this reader touches inside the program (a refactor that renames
one moves the metric that reads it):

  host spans ``tpushare.<name>`` (``tpushare/utils/profiling.span``),
  on the engine's thread:
    ``engine.<stage>``   the stages of ``cli/serve.ENGINE_STAGES``;
                         ``engine.dispatch`` is counted as one tick
    ``slot.grow``, ``slot.launch``, ``slot.sample``, ``slot.mirror``
                         inside ``step_async`` / ``_fused_tick_async``
                         (``models/paged.py``)
    ``slot.admit.lookup``, ``slot.admit.row``, ``slot.admit.prefill``,
    ``slot.admit.scatter``, ``slot.admit.first_token``
                         inside ``admit_start`` / ``admit_step``
    ``slot.fetch``       ``models/serving.PendingStep.finalize``
  on the handler threads:
    ``http.accept``      request read to ``submit``; with ``engine.admit``
                         and the request's first ``http.write`` (one
                         ``rid``) it is a row of the request table
    ``http.write``       one SSE event; summed over every handler thread
                         it is ``http.write_ms_per_tick``
  the stats ``rid``, ``prompt_tokens``, ``cached_tokens``, ``chunked``
  of ``engine.admit`` are the request table's columns
  device programs, line ``XLA Modules`` of a ``/device:`` plane:
    ``jit_paged_decode``, ``jit_paged_prefill``, ``jit_paged_fused``
                         (``models/paged._program``)

idle        the gaps of the device's ``XLA Ops`` line, as in trace.py,
            between the first and last event of that line and of the
            engine's thread
under       idle time is shared out by overlap, not whole gaps by
            majority as ``trace.name_gaps`` does for the six coarse
            wrappers: one gap of a tick runs from the last sampler
            program through the fetch, the engine's bookkeeping, block
            growth and the launch, and no inner span covers half of it
innermost   of the spans open on the engine's thread at a moment, the
            one opened last; "(no span)" where none is
request     a request whose ``http.accept`` and placing ``engine.admit``
            both lie in the slice: the time in accept, from there to the
            admission (the queue), in the admission's span, and from the
            admission's start to the first event written (a chunked
            admission's span is its first chunk only; the last column
            holds the rest)
"""

from __future__ import annotations

import functools
import os
import statistics
import sys
from typing import Any, Dict, List, Optional, Tuple

from tpubench.readers import trace

SPAN_PREFIX = "tpushare."
TICK_SPAN = "engine.dispatch"
ACCEPT, ADMIT, WRITE = "http.accept", "engine.admit", "http.write"
MODULES_LINE = "XLA Modules"
NO_SPAN = "(no span)"

Interval = Tuple[float, float]
Span = Tuple[str, float, float]         # name, start ns, end ns
Tagged = Tuple[str, float, float, Dict[str, Any]]   # and the span's stats


def load(path: str) -> Dict[str, Any]:
    """{"ops": {plane: [(start, end)]}, "modules": {plane: [Span]},
    "threads": [[Span]], "tagged": [Tagged]}: the device lines, the
    ``tpushare.*`` spans of every host thread that has any, prefix
    taken off, and again with their stats the spans that carry a
    request's ``rid``."""
    from jax.profiler import ProfileData
    ops: Dict[str, List[Interval]] = {}
    modules: Dict[str, List[Span]] = {}
    threads: List[List[Span]] = []
    tagged: List[Tagged] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    ops.setdefault(plane.name, []).extend(
                        (float(e.start_ns),
                         float(e.start_ns + e.duration_ns))
                        for e in line.events)
                elif line.name == MODULES_LINE:
                    modules.setdefault(plane.name, []).extend(
                        (e.name, float(e.start_ns),
                         float(e.start_ns + e.duration_ns))
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans = []
                for e in line.events:
                    if not e.name.startswith(SPAN_PREFIX):
                        continue
                    sp = (e.name[len(SPAN_PREFIX):], float(e.start_ns),
                          float(e.start_ns + e.duration_ns))
                    spans.append(sp)
                    if sp[0] in (ACCEPT, ADMIT, WRITE):
                        stats = dict(e.stats)
                        if "rid" in stats:
                            tagged.append(sp + (stats,))
                if spans:
                    threads.append(spans)
    return {"ops": ops, "modules": modules, "threads": threads,
            "tagged": tagged}


def intersect(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """The parts two sorted lists of disjoint intervals share."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return out


def innermost(spans: List[Span]) -> List[Span]:
    """One thread's properly nested spans cut into disjoint pieces, each
    under the name of the span opened last at that moment."""
    out: List[Span] = []
    stack: List[List] = []              # [name, end, covered up to]

    def close(until: float) -> None:
        while stack and stack[-1][1] <= until:
            name, end, at = stack.pop()
            if end > at:
                out.append((name, at, end))
            if stack:
                stack[-1][2] = max(stack[-1][2], end)

    for name, start, end in sorted(spans, key=lambda s: (s[1], -s[2])):
        close(start)
        if stack:
            parent = stack[-1]
            if start > parent[2]:
                out.append((parent[0], parent[2], start))
            parent[2] = max(parent[2], start)
        stack.append([name, end, start])
    close(float("inf"))
    return sorted(out, key=lambda s: s[1])


def request_paths(tagged: List[Tagged]) -> List[Dict[str, Any]]:
    """One row a request, in order of arrival (see "request" above)."""
    by_rid: Dict[str, Dict[str, List[Tagged]]] = {}
    for sp in tagged:
        by_rid.setdefault(sp[3]["rid"], {}).setdefault(sp[0], []).append(sp)
    rows = []
    for rid, spans in by_rid.items():
        if ACCEPT not in spans or ADMIT not in spans:
            continue
        _, a0, a1, _ = spans[ACCEPT][0]
        # the pop that placed it is the last: a held pop came before
        _, m0, m1, stats = max(spans[ADMIT], key=lambda sp: sp[1])
        first = min((sp[2] for sp in spans.get(WRITE, [])), default=None)
        rows.append({
            "rid": rid, "at_ms": a0 / 1e6,
            "prompt_tokens": int(stats.get("prompt_tokens", 0)),
            "cached_tokens": int(stats.get("cached_tokens", 0)),
            "chunked": int(stats.get("chunked", 0)),
            "accept_ms": (a1 - a0) / 1e6, "queue_ms": (m0 - a1) / 1e6,
            "admit_ms": (m1 - m0) / 1e6,
            "admit_to_first_write_ms":
                None if first is None else (first - m0) / 1e6})
    return sorted(rows, key=lambda r: r["at_ms"])


def reduce(t: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The numbers ``read`` serves and ``table`` prints, or None where
    the trace holds no span of the engine's thread or no device
    operation."""
    engine = max(t["threads"], default=None, key=lambda spans: sum(
        n == TICK_SPAN for n, _, _ in spans))
    devs = sorted(p for p, evs in t["ops"].items() if evs)
    if not devs or not engine:
        return None
    ticks = sum(n == TICK_SPAN for n, _, _ in engine)
    if not ticks:
        return None
    busy = trace.union(t["ops"][devs[0]])
    lo = min(busy[0][0], min(s for _, s, _ in engine))
    hi = max(busy[-1][1], max(e for _, _, e in engine))
    idle = trace.gaps(busy, lo, hi)

    def idle_under(intervals: List[Interval]) -> float:
        return trace.total(intersect(trace.union(intervals), idle))

    by_name: Dict[str, List[Interval]] = {}
    for name, s, e in engine:
        by_name.setdefault(name, []).append((s, e))
    inner: Dict[str, List[Interval]] = {}
    for name, s, e in innermost(engine):
        inner.setdefault(name, []).append((s, e))
    inner_idle = {n: idle_under(iv) for n, iv in inner.items()}
    inner_idle[NO_SPAN] = trace.total(idle) - sum(inner_idle.values())
    mods = t["modules"].get(devs[0], [])
    by_program: Dict[str, List[Interval]] = {}
    for name, s, e in mods:
        # ``jit_paged_decode(1613...)``: the fingerprint off
        by_program.setdefault(name.split("(")[0], []).append((s, e))
    mod_busy = {n: trace.total(intersect(trace.union(iv), busy))
                for n, iv in by_program.items()}
    writes = [(s, e) for spans in t["threads"] if spans is not engine
              for n, s, e in spans if n == WRITE]
    return {
        "window_ms": (hi - lo) / 1e6,
        "busy_ms": trace.total(busy) / 1e6,
        "idle_ms": trace.total(idle) / 1e6,
        "ticks": ticks,
        "programs": len(mods),
        "span_ms": {n: trace.total(trace.union(iv)) / 1e6
                    for n, iv in by_name.items()},
        "idle_innermost_ms": {
            n: v / 1e6 for n, v in sorted(inner_idle.items(),
                                          key=lambda kv: -kv[1])},
        "module_busy_ms": {n: v / 1e6 for n, v in sorted(
            mod_busy.items(), key=lambda kv: -kv[1])},
        # every handler thread's SSE writes: their summed time, the
        # median one, and the device's idle time while any is open
        "writes": len(writes),
        "write_ms": sum(e - s for s, e in writes) / 1e6,
        "write_p50_ms": (statistics.median(e - s for s, e in writes) / 1e6
                         if writes else None),
        "idle_under_writes_ms": idle_under(writes) / 1e6,
        "requests": request_paths(t.get("tagged", [])),
    }


def table(red: Dict[str, Any]) -> str:
    """The innermost-span idle table, a line a span (what a ``perf_opt``
    issue on the host's share of a tick is written from), then the
    handler threads' writes and a line a request."""
    ticks = red["ticks"]
    rows = [f"{'idle under the innermost span':34s} {'ms':>9s} "
            f"{'ms/tick':>8s} {'of idle':>8s}   (the span's own ms/tick)"]
    for name, ms in red["idle_innermost_ms"].items():
        own = red["span_ms"].get(name)
        rows.append(
            f"{name:34s} {ms:9.2f} {ms / ticks:8.3f} "
            f"{100 * ms / max(red['idle_ms'], 1e-9):7.1f}%"
            + (f"   ({own / ticks:.3f})" if own is not None else ""))
    rows.append(f"{ticks} ticks, {red['programs']} programs "
                f"({red['programs'] / ticks:.1f} a tick), idle "
                f"{red['idle_ms']:.1f} of {red['window_ms']:.1f} ms")
    top = list(red["module_busy_ms"].items())[:6]
    rows.append("device busy ms by program: " + ", ".join(
        f"{n} {ms:.1f}" for n, ms in top))
    if red["writes"]:
        rows.append(
            f"http.write on the handler threads: {red['writes']} events "
            f"({red['writes'] / ticks:.1f} a tick), "
            f"{red['write_ms'] / ticks:.3f} ms a tick summed, p50 "
            f"{red['write_p50_ms']:.3f} ms; device idle while one is "
            f"open {red['idle_under_writes_ms'] / ticks:.3f} ms a tick")
    if red["requests"]:
        rows.append(f"{'request':10s} {'prompt':>6s} {'cached':>6s} "
                    f"{'chunked':>7s} {'accept':>8s} {'queue':>8s} "
                    f"{'admit':>8s} {'admit -> first write':>21s}   (ms)")
    for r in red["requests"]:
        first = r["admit_to_first_write_ms"]
        rows.append(
            f"{r['rid'][:8]:10s} {r['prompt_tokens']:6d} "
            f"{r['cached_tokens']:6d} {r['chunked']:7d} "
            f"{r['accept_ms']:8.3f} {r['queue_ms']:8.3f} "
            f"{r['admit_ms']:8.3f} "
            + (f"{first:21.3f}" if first is not None else f"{'-':>21s}"))
    return "\n".join(rows)


@functools.lru_cache(maxsize=2)
def _reduced(path: str) -> Optional[Dict[str, Any]]:
    """One load a run, however many metrics ask; the table goes to
    standard output here, so once, and before the run's last line."""
    red = reduce(load(path))
    if red is None:
        print(f"[tpubench program_trace] {path}: no tpushare.* span of "
              f"an engine thread beside a device line; nothing to read",
              flush=True)
        return None
    print(f"[tpubench program_trace] {path}\n{table(red)}", flush=True)
    return red


def read(ctx, value: str, spans: List[str] = (), prefixes: List[str] = ()):
    """value = "idle_ms_per_tick": device idle time whose innermost span
    on the engine's thread is one of ``spans`` (for a span that holds no
    other, all the idle time under it), over the number of
    ``engine.dispatch`` spans in the slice.
    value = "programs_per_tick": ``XLA Modules`` events over that count.
    value = "module_busy_pct": device busy time inside the programs
    whose name starts ``jit_<prefix>`` for a prefix in ``prefixes``,
    over busy time; None where the program names none of its programs
    ``jit_paged_*`` (0 where it does and none of these ran).
    value = "write_ms_per_tick": time inside ``http.write`` spans summed
    over the handler threads, over that count; None where none is."""
    from tpubench import spec
    path = trace.find(os.path.join(
        spec.ROOT, "tpubench_out", ctx.cell.name + ".trace"
        + (".rehearse" if ctx.cell.rehearse else ""), "trace"))
    red = _reduced(path) if path else None
    if red is None:
        return None
    if value == "programs_per_tick":
        return red["programs"] / red["ticks"] if red["programs"] else None
    if value == "idle_ms_per_tick":
        if not any(s in red["span_ms"] for s in spans):
            return None
        return sum(red["idle_innermost_ms"].get(s, 0.0)
                   for s in spans) / red["ticks"]
    if value == "module_busy_pct":
        if not any(n.startswith("jit_paged_")
                   for n in red["module_busy_ms"]):
            return None
        return 100.0 * sum(
            ms for n, ms in red["module_busy_ms"].items()
            if any(n.startswith("jit_" + p) for p in prefixes)
        ) / red["busy_ms"]
    if value == "write_ms_per_tick":
        return red["write_ms"] / red["ticks"] if red["writes"] else None
    raise ValueError(f"unknown value {value!r}")


def main(argv=None) -> int:
    """Print what this file sees in a trace (a file, or a directory as
    ``jax.profiler.start_trace`` leaves it)."""
    path = (argv or sys.argv[1:])[0]
    if os.path.isdir(path):
        path = trace.find(path)
    red = reduce(load(path))
    if red is None:
        print("no tpushare.* span of an engine thread beside a device line")
        return 1
    print(table(red))
    return 0


if __name__ == "__main__":
    sys.exit(main())
