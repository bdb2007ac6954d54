"""Reduction of a ``jax.profiler`` trace (``*.xplane.pb``) to numbers.

Read with ``jax.profiler.ProfileData`` only. What a v5e trace holds
(looked at by hand, PR 22; ``python -m tpubench.readers.trace FILE``
prints the same view of any trace):

  planes ``/device:TPU:<n>``: one per chip. Its line ``XLA Ops`` holds
      one event per executed HLO operation under the operation's own
      whole HLO line (``%fusion.12 = bf16[...] fusion(...)``), properly
      nested (a ``while`` contains the operations of its body). A
      Mosaic kernel is a custom call under the kernel's name (see
      MOSAIC below). ``XLA Modules`` holds one event per program run
      (``jit_scatter(...)``), ``Async XLA Ops`` the copies in flight.
  plane ``/host:CPU``: one line per thread; ``TraceAnnotation`` spans
      appear there under their names, on the same clock.

busy      union of the ``XLA Ops`` intervals of a device
window    first start to last end over every device operation and every
          ``tpubench.*`` host span
idle      window minus busy; each idle gap is named after the host span
          that covers most of it, "engine loop" where none does
self time an operation's duration minus that of the operations nested
          in it; the top operations are ranked by it
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import sys
from typing import Any, Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "tpubench."
#: how a Mosaic (Pallas) kernel shows in the trace: an event is named
#: by its whole HLO line, and a kernel's reads ``%flash_attention.6 =
#: bf16[...] custom-call(...), custom_call_target="tpu_custom_call"``
MOSAIC = 'custom_call_target="tpu_custom_call"'
_HLO = re.compile(r"^%?(?P<name>\S+) = (?P<shape>\(?[a-z0-9]+\[[0-9,]*\])?"
                  r".*?\s(?P<op>[a-z][a-z0-9\-]*)\(")

Interval = Tuple[float, float]


def find(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def load(path: str) -> Dict[str, Any]:
    """{"devices": {plane: [(name, start_ns, dur_ns, is_mosaic)]},
    "spans": [(name, start_ns, dur_ns)]} from an xplane file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, List] = {}
    spans: List = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                ops = devices.setdefault(plane.name, [])
                for e in line.events:
                    ops.append((short_name(e.name), float(e.start_ns),
                                float(e.duration_ns), MOSAIC in e.name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name[len(SPAN_PREFIX):],
                                      float(e.start_ns),
                                      float(e.duration_ns)))
    return {"devices": devices, "spans": spans}


def short_name(hlo: str) -> str:
    """``copy.4 copy bf16[16,3072,16,8,128]`` from an event's HLO line:
    the result's name, the opcode, the result's shape."""
    m = _HLO.match(hlo)
    if not m:
        return hlo[:80]
    op = "mosaic" if MOSAIC in hlo else m["op"]
    return " ".join(x for x in (m["name"], op, m["shape"]) if x)[:80]


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(intervals: List[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    out, at = [], lo
    for a, b in busy:
        if a > at:
            out.append((at, min(a, hi)))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return [(a, b) for a, b in out if b > a]


def self_times(ops: List[Tuple]) -> List[Tuple[str, float, bool]]:
    """(name, self ns, is_mosaic) per event of one properly nested line."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    child = [0.0] * len(ops)
    stack: List[int] = []
    for i in order:
        _, s, d, _ = ops[i]
        while stack and ops[stack[-1]][1] + ops[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            child[stack[-1]] += d
        stack.append(i)
    return [(ops[i][0], max(0.0, ops[i][2] - child[i]), ops[i][3])
            for i in range(len(ops))]


def _overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


def name_gaps(idle: List[Interval], spans: List[Tuple]) -> Dict[str, float]:
    """Seconds of idle time by what the host was doing: a gap goes,
    whole, to the span name that covers most of it (spans nest: ``step``
    contains ``step_async``, so shares would count twice), and to
    "engine loop" where no name covers half of it."""
    out: Dict[str, float] = {}
    spans = sorted(spans, key=lambda s: s[1])
    starts = [s[1] for s in spans]
    longest = max([s[2] for s in spans], default=0.0)
    for g in idle:
        cover: Dict[str, List[Interval]] = {}
        i = bisect.bisect_left(starts, g[0] - longest)
        while i < len(spans) and spans[i][1] < g[1]:
            n, s, d = spans[i]
            if s + d > g[0]:
                cover.setdefault(n, []).append((s, s + d))
            i += 1
        best, best_s = "engine loop", 0.0
        for name, ivs in cover.items():
            s = sum(_overlap(g, iv) for iv in union(ivs))
            if s > best_s:
                best, best_s = name, s
        if best_s < 0.5 * (g[1] - g[0]):
            best = "engine loop"
        out[best] = out.get(best, 0.0) + (g[1] - g[0]) / 1e9
    return out


def reduce(trace: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The numbers the trace readers and ``device``/``breakdown`` use,
    or None where no operation ran on a device."""
    devs = {k: v for k, v in trace["devices"].items() if v}
    if not devs:
        return None
    edges = [(s, s + d) for ops in devs.values() for _, s, d, _ in ops]
    edges += [(s, s + d) for _, s, d in trace["spans"]]
    lo, hi = min(a for a, _ in edges), max(b for _, b in edges)
    per_dev = {}
    for name, ops in sorted(devs.items()):
        busy = union([(s, s + d) for _, s, d, _ in ops])
        st = self_times(ops)
        per_dev[name] = {
            "busy_s": total(busy) / 1e9,
            "busy": busy,
            "mosaic_s": sum(t for _, t, m in st if m) / 1e9,
            "self": st,
        }
    first = per_dev[sorted(per_dev)[0]]
    by_op: Dict[str, float] = {}
    for n, t, _ in first["self"]:
        by_op[n] = by_op.get(n, 0.0) + t / 1e9
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]
    named = name_gaps(gaps(first["busy"], lo, hi), trace["spans"])
    window_s = (hi - lo) / 1e9
    busy_s = sum(d["busy_s"] for d in per_dev.values()) / len(per_dev)
    return {
        "window_s": window_s,
        "busy_s": busy_s,                       # averaged over the chips
        "idle_pct": 100.0 * (1.0 - busy_s / window_s),
        "mosaic_busy_pct": 100.0 * first["mosaic_s"] / first["busy_s"],
        "n_devices": len(per_dev),
        "device_ops": [[n, s] for n, s in top],
        "idle_gaps": [[n, s] for n, s in
                      sorted(named.items(), key=lambda kv: -kv[1])[:10]],
    }


def read(ctx, value: str):
    """``value``: idle_pct | mosaic_busy_pct, or hbm_floor_pct: the
    bytes the window's forwards had to read (weights once per forward,
    from the shapes, ``peaks.forward_weight_bytes``) over peak bytes/s,
    against the
    device's busy time in the window (the traced slice's busy share
    times the window). An end-to-end utilization, not a kernel's
    roofline share."""
    red = ctx.trace
    if red is None:
        return None
    if value != "hbm_floor_pct":
        return red[value]
    from tpubench import peaks
    fwd = (ctx.stats_after["model_forwards"]
           - ctx.stats_before["model_forwards"])
    if not fwd:
        return None
    floor_s = (fwd * peaks.forward_weight_bytes(ctx.cell.config)
               / ctx.peaks["hbm_bytes_per_s"])
    busy_in_window = ctx.window_s * red["busy_s"] / red["window_s"]
    return 100.0 * floor_s / busy_in_window


def main(argv=None) -> int:
    """Print a trace as this file sees it: planes, lines, the heaviest
    names of each device line with one event's stats, and the reduction."""
    from jax.profiler import ProfileData
    path = (argv or sys.argv[1:])[0]
    if os.path.isdir(path):
        path = find(path)
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print(f"  LINE {line.name!r}: {len(evs)} events")
            if not plane.name.startswith("/device:"):
                names = sorted({e.name for e in evs
                                if e.name.startswith(SPAN_PREFIX)})
                if names:
                    print("     spans:", names)
                continue
            agg: Dict[str, List] = {}
            for e in evs:
                a = agg.setdefault(e.name, [0.0, 0, None])
                a[0] += e.duration_ns
                a[1] += 1
                a[2] = a[2] or [(k, str(v)[:80]) for k, v in e.stats]
            for n, (t, c, st) in sorted(agg.items(),
                                        key=lambda kv: -kv[1][0])[:25]:
                print(f"     {t / 1e6:10.3f} ms {c:6d}x {n[:60]}  {st}")
    red = reduce(load(path))
    if red:
        print(red)
    return 0


if __name__ == "__main__":
    sys.exit(main())
