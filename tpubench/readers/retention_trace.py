"""``retention.step_roofline_pct``: the decode kernel's share of its
roofline, from the traced slice.

The kernel is a Mosaic custom call named ``retention_step``
(``tpushare/ops/retention.py``: the ``name`` of its ``pallas_call``), so
its events on the device's ``XLA Ops`` line are found by that name
(``readers/trace.py`` shortens an event to ``<name> mosaic <shape>``).
One call is one layer of one tick. What it must move is computed here,
from the shapes (``step_bytes``): for every active slot and kv head the
state and the normaliser read once and written once, the step's rows
(the features of the group's queries and of the key, the value tile as
the kernel takes it, broadcast along lanes) read, the group's numerators
and denominators written. The kernel is bound by bytes (its arithmetic,
6 float32 operations a state element, is a tenth of the byte time at the
v5e's peaks), so the roofline is bytes over the HBM peak.

How many slots a call found active is not in the trace. The program
counts, on the host, the state bytes its ticks moved
(``retention_state_bytes_moved``: 2 x a slot's state x active slots a
tick) and the ticks that ran the kernel (``retention_ticks``); their
ratio gives the mean active slots a call. It is taken between the two
``/stats`` samples that enclose the traced slice (a sample a second;
the slice is ``SLICE_S`` seconds mid-window), over the whole window
where those are missing: a closed loop's admissions leave fewer slots
active in some seconds than in others. None where the program has no
such counters, the configuration no such state, or the trace no such
kernel.
"""

from __future__ import annotations

import functools
import os
from typing import Any, Dict, List, Optional

from tpubench.readers import trace
from tpubench.readers.stats_delta import _delta

KERNEL = "retention_step"
#: the traced slice (``run.TRACE_SLICE_S``), mid-window
SLICE_S = 3.0


def layout(config: Dict[str, Any]) -> Dict[str, int]:
    """The kernel's sizes from the configuration's keys: F features as
    laid out on the chip ((D/2 + 1) x D), G query heads a kv head."""
    d = config["head_dim"]
    return {"D": d, "F": (d // 2 + 1) * d,
            "Hkv": config["num_key_value_heads"],
            "G": config["num_attention_heads"] // config["num_key_value_heads"]}


def step_bytes(config: Dict[str, Any], n_active: float) -> float:
    """Bytes one kernel call (one layer, one tick) must move for
    ``n_active`` slots, float32."""
    s = layout(config)
    d, f, g = s["D"], s["F"], s["G"]
    per_head = (2 * 4 * (d * f + f)         # S and z: in, and out
                + 4 * (g * f + f)           # phi(q) of the group, phi(k)
                + 4 * d * d                 # v, broadcast along lanes
                + 4 * g * (d + 1))          # numerators, denominators
    return n_active * s["Hkv"] * per_head


def state_row_bytes(config: Dict[str, Any]) -> int:
    """A slot's state over every layer, as the program holds it."""
    s = layout(config)
    return (4 * config["num_hidden_layers"] * s["Hkv"]
            * (s["D"] * s["F"] + s["F"]))


def kernel_events(ops: List[tuple]) -> List[float]:
    """Durations (ns) of the kernel's events among one device's
    ``(short name, start, duration, is_mosaic)``."""
    return [d for name, _, d, mosaic in ops
            if mosaic and name.startswith(KERNEL)]


def roofline_pct(durations: List[float], config: Dict[str, Any],
                 mean_active: float, hbm_bytes_per_s: float
                 ) -> Optional[float]:
    if not durations or not mean_active:
        return None
    floor_s = len(durations) * step_bytes(config, mean_active) / hbm_bytes_per_s
    return 100.0 * floor_s / (sum(durations) / 1e9)


@functools.lru_cache(maxsize=2)
def _ops(path: str):
    devs = trace.load(path)["devices"]
    return devs[sorted(devs)[0]] if devs else None


def around_slice(ctx):
    """(state bytes moved, kernel ticks) between the samples that
    enclose the slice (sample k is taken k seconds into the window),
    else over the window."""
    lo = int((ctx.window_s - SLICE_S) / 2)
    hi = int((ctx.window_s + SLICE_S) / 2) + 1
    samples = getattr(ctx, "stats_samples", None) or []
    keys = ("retention_state_bytes_moved", "retention_ticks")
    if 1 <= lo and hi <= len(samples) and all(
            samples[i - 1].get(k) is not None for i in (lo, hi) for k in keys):
        return tuple(samples[hi - 1][k] - samples[lo - 1][k] for k in keys)
    return tuple(_delta(ctx, k) for k in keys)


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    moved, ticks = around_slice(ctx)
    if not moved or not ticks:
        return None
    from tpubench import spec
    path = trace.find(os.path.join(
        spec.ROOT, "tpubench_out", ctx.cell.name + ".trace"
        + (".rehearse" if ctx.cell.rehearse else ""), "trace"))
    ops = _ops(path) if path else None
    if not ops:
        return None
    mean_active = moved / (2.0 * state_row_bytes(ctx.cell.config) * ticks)
    return roofline_pct(kernel_events(ops), ctx.cell.config, mean_active,
                        ctx.peaks["hbm_bytes_per_s"])
