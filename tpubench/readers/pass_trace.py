"""The device's idle time inside the engine's passes, by kind, in the
traced run's ``.xplane.pb``: how long a pass of each kind made the
device wait, whatever else the slice holds.

What this reader touches inside the program (a refactor that renames
one moves the metric that reads it):

  host span ``tpushare.pass`` (``cli/serve.ServeEngine._loop_once``, the
      engine's thread): one pass of the thread, with the stat ``kind``,
      one of ``cli/serve.PASS_KINDS`` (``idle``, ``decode``, ``admit``,
      ``fused``); passes do not overlap
  device line ``XLA Ops`` of the first ``/device:`` plane, as
      ``readers/trace.py`` reads it

idle        the gaps of the device's busy time (the union of its ``XLA
            Ops``), between the first and last of those operations and
            passes, as ``program_trace`` takes them
under       the part of that idle time inside a pass of the kind, over
            the number of such passes in the slice

On a program that writes no ``tpushare.pass`` span (the parent of the
PR that added it), or a slice with no pass of the kind, the value is
None and the log says which.
"""

from __future__ import annotations

import functools
import os
from typing import Any, Dict, List, Optional, Tuple

from tpubench.readers import trace
from tpubench.readers.program_trace import intersect

PASS_SPAN = "tpushare.pass"

Interval = Tuple[float, float]


def load(path: str) -> Dict[str, Any]:
    """{"ops": [(start, end)] of the first device's ``XLA Ops``,
    "passes": {kind: [(start, end)]}} from an xplane file."""
    from jax.profiler import ProfileData
    ops: Dict[str, List[Interval]] = {}
    passes: Dict[str, List[Interval]] = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    ops.setdefault(plane.name, []).extend(
                        (float(e.start_ns),
                         float(e.start_ns + e.duration_ns))
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name != PASS_SPAN:
                        continue
                    kind = str(dict(e.stats).get("kind", "?"))
                    passes.setdefault(kind, []).append(
                        (float(e.start_ns),
                         float(e.start_ns + e.duration_ns)))
    devs = sorted(p for p, evs in ops.items() if evs)
    return {"ops": ops[devs[0]] if devs else [], "passes": passes}


def reduce(t: Dict[str, Any]) -> Optional[Dict[str, Dict[str, float]]]:
    """{kind: {"n", "wall_ms", "idle_ms"}} summed over the slice's passes
    of each kind, or None where the slice holds no pass or no device
    operation."""
    spans = [iv for ivs in t["passes"].values() for iv in ivs]
    if not spans or not t["ops"]:
        return None
    busy = trace.union(t["ops"])
    lo = min(busy[0][0], min(s for s, _ in spans))
    hi = max(busy[-1][1], max(e for _, e in spans))
    idle = trace.gaps(busy, lo, hi)
    return {kind: {"n": len(ivs),
                   "wall_ms": sum(e - s for s, e in ivs) / 1e6,
                   "idle_ms": trace.total(intersect(trace.union(ivs),
                                                    idle)) / 1e6}
            for kind, ivs in sorted(t["passes"].items())}


def table(red: Dict[str, Dict[str, float]]) -> str:
    """A line a kind: passes, wall and device idle ms a pass."""
    return "; ".join(
        f"{k}: {r['n']} passes, {r['wall_ms'] / r['n']:.3f} ms wall and "
        f"{r['idle_ms'] / r['n']:.3f} ms device idle a pass"
        for k, r in red.items())


@functools.lru_cache(maxsize=2)
def _reduced(path: str) -> Optional[Dict[str, Dict[str, float]]]:
    """One load a run; the split goes to standard output here, once."""
    red = reduce(load(path))
    if red is None:
        print(f"[tpubench pass_trace] {path}: no {PASS_SPAN} span beside "
              f"a device line; nothing to read", flush=True)
    else:
        print(f"[tpubench pass_trace] {path}: {table(red)}", flush=True)
    return red


def split(ctx) -> Optional[Dict[str, Dict[str, float]]]:
    """The traced slice's ``reduce``, logged once a run; None where the
    run wrote no trace or the trace holds no pass."""
    from tpubench import spec
    path = trace.find(os.path.join(
        spec.ROOT, "tpubench_out", ctx.cell.name + ".trace"
        + (".rehearse" if ctx.cell.rehearse else ""), "trace"))
    return _reduced(path) if path else None


def read(ctx, kind: str = "decode"):
    """Device idle ms inside the slice's ``tpushare.pass`` spans of
    ``kind``, over their number; None where there are none."""
    red = split(ctx)
    if red is None:
        return None
    if kind not in red:
        print(f"[tpubench pass_trace] the slice holds no {kind} pass: "
              f"no value", flush=True)
        return None
    return red[kind]["idle_ms"] / red[kind]["n"]

