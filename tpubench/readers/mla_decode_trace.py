"""``mla.decode_roofline_pct``: the paged latent decode kernel's share of
its roofline, from the traced slice.

The kernel is a Mosaic custom call named ``latent_paged_decode``
(``tpushare/ops/latent_decode.py``: the ``name`` of its ``pallas_call``),
so its events on the device's ``XLA Ops`` line are found by that name
(``readers/trace.py`` shortens an event to ``<name> mosaic <shape>``).
One call is one cached layer of one drafting round: every live row of
every active slot attended by the round's queries.

**Useful work is the published widths', whatever implements it.** A
cached row is ``kv_lora_rank + qk_rope_head_dim`` values (576; the chip
pads it to 640) and the output is ``kv_lora_rank`` wide (512), so a row
costs a live query ``2 x heads x (576 + 512)`` operations and is
``576 x dtype`` bytes, read once. A call's floor is the LARGER of its
operations over the matrix unit's peak and its bytes over the HBM peak
(at 128 heads the operations, by far: some 480 a byte against the chip's
ridge of 240). A kernel that ran the padded row through both products at
the matrix unit's peak would read 85, one that cuts the output to the
latent 94.4; nothing can read over 100.

How many rows a call attended and how many of its queries were live is
not in the trace. The program counts both on the host: rows in
``latent_rows_read`` (every cached layer's rows of every active slot up
to the round's last write) over ``latent_decode_calls`` (the kernel
calls its rounds dispatched: one a cached layer a round where the kernel
is the program's choice, none where it gathers). Queries: the main
layers verify ``1 + num_nextn_predict_layers`` positions a slot; the
module's own call runs the positions the slot's last round committed,
``mtp_emitted`` / ``mtp_proposed`` of them on average (1 where no draft
was accepted: its second query is dead and counts for nothing here).
All taken between the two ``/stats`` samples that enclose the traced
slice (``retention_trace.around_slice``'s rule), over the whole window
where those are missing. None where the program has no such counter (a
program from before the kernel), counted no call (it gathers), or the
trace holds no such kernel.
"""

from __future__ import annotations

import os
from typing import Any, Dict, List, Optional

from tpubench import peaks as peaks_table
from tpubench.readers import trace
from tpubench.readers.retention_trace import SLICE_S, _ops
from tpubench.readers.stats_delta import _delta

KERNEL = "latent_paged_decode"
KEYS = ("latent_rows_read", "latent_decode_calls", "mtp_emitted",
        "mtp_proposed")


def row_widths(config: Dict[str, Any]) -> Dict[str, int]:
    """The published widths of a cached row and of the output."""
    rank = config["kv_lora_rank"]
    return {"key": rank + config["qk_rope_head_dim"], "value": rank,
            "heads": config["num_attention_heads"],
            "bytes": peaks_table.DTYPE_BYTES[config["torch_dtype"]]}


def mean_queries(config: Dict[str, Any], per_round: float) -> float:
    """Live queries a slot of a call, averaged over a round's calls: the
    main layers' verify every position of the round, the module's run
    ``per_round`` (what the last round committed)."""
    n_main = config["num_hidden_layers"]
    n_mtp = config.get("num_nextn_predict_layers", 0)
    return (n_main * (1 + n_mtp) + n_mtp * per_round) / (n_main + n_mtp)


def call_floor_s(config: Dict[str, Any], rows: float, queries: float,
                 peaks: Dict[str, Any]) -> float:
    """The least time one call can take for ``rows`` cached rows under
    ``queries`` live queries a slot."""
    w = row_widths(config)
    flops = rows * 2.0 * queries * w["heads"] * (w["key"] + w["value"])
    return max(flops / peaks["bf16_flops"],
               rows * w["key"] * w["bytes"] / peaks["hbm_bytes_per_s"])


def kernel_events(ops: List[tuple]) -> List[float]:
    """Durations (ns) of the kernel's events among one device's
    ``(short name, start, duration, is_mosaic)``."""
    return [d for name, _, d, mosaic in ops
            if mosaic and name.startswith(KERNEL)]


def roofline_pct(durations: List[float], config: Dict[str, Any],
                 rows_a_call: float, queries: float,
                 peaks: Dict[str, Any]) -> Optional[float]:
    if not durations or not rows_a_call:
        return None
    floor_s = len(durations) * call_floor_s(config, rows_a_call, queries,
                                            peaks)
    return 100.0 * floor_s / (sum(durations) / 1e9)


def around_slice(ctx) -> tuple:
    """The deltas of ``KEYS`` between the samples that enclose the slice
    (sample k is taken k seconds into the window), else over the
    window; None for a counter the program lacks."""
    lo = int((ctx.window_s - SLICE_S) / 2)
    hi = int((ctx.window_s + SLICE_S) / 2) + 1
    samples = getattr(ctx, "stats_samples", None) or []
    if 1 <= lo and hi <= len(samples) and all(
            samples[i - 1].get(k) is not None for i in (lo, hi)
            for k in KEYS):
        return tuple(samples[hi - 1][k] - samples[lo - 1][k] for k in KEYS)
    return tuple(_delta(ctx, k) for k in KEYS)


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    if "kv_lora_rank" not in ctx.cell.config:
        return None
    rows, calls, emitted, proposed = around_slice(ctx)
    if not rows or not calls:
        return None
    from tpubench import spec
    path = trace.find(os.path.join(
        spec.ROOT, "tpubench_out", ctx.cell.name + ".trace"
        + (".rehearse" if ctx.cell.rehearse else ""), "trace"))
    ops = _ops(path) if path else None
    if not ops:
        return None
    per_round = emitted / proposed if emitted and proposed else 1.0
    return roofline_pct(kernel_events(ops), ctx.cell.config, rows / calls,
                        mean_queries(ctx.cell.config, per_round), ctx.peaks)
