"""``mla.attend_busy_pct``: the share of the device's busy time that is
attention over EVERY cached latent row (a model with no key selector and
no window), on the main layers and the multi-token-prediction module's,
from the traced slice.

The trace names a device operation by its HLO line and nothing else
(``readers/trace.py``; the program's ``latent_attend`` scope is in the
HLO's metadata, which the profiler leaves out at the harness's options:
``readers/select_trace.py``), so the operations are found by kind and
result shape, from the configuration's, the engine's and the program's
own numbers. A key axis is as long as a slot holds
(``max_blocks_per_slot`` x ``block_size``) or, in an admission chunk, a
whole number of 256-block steps of it:

  scores, softmax    a result whose last axis is a key axis and whose
                     axis before it is queries x attention heads: 1 or 2
                     queries a slot in a decode or drafting step, a
                     block of ``q_block_full`` queries in a chunk (the
                     program merges queries and heads into one axis):
                     the normalised weights, and the masks beside them
                     [.., heads, keys]. The compiler fuses the score
                     product into the softmax's row maxima and sums, so
                     most of a chunk's attention shows as results of the
                     query-and-head axis ALONE: f32[queries a block x
                     heads], and [slots, queries x heads] in a step
  the output         [.., queries a block, heads, kv_lora_rank] (a
                     chunk: the weights times the rows, cut to the
                     latent) and the block of absorbed queries it was
                     computed from [.., queries a block, heads, row];
                     [slots, queries x heads, row] in a step
  the row gather     a slot's rows read through the block table: a
                     result [keys, slots, row], [slots, keys, row] or
                     [slots, blocks, block_size, row]

NOT the absorbed queries of every token [tokens, heads, row] (a
projection, ``latent._project``), nor anything [tokens, width].

The recorded slice these were read off is
``tests/benchmark/data/v5e_longdoc_mla_ops.json``. Self time of the
matches, over the busy time of the first device. None where the
configuration has a selector (``attn.select_busy_pct`` reads that
family), the program has no such family, or the trace has no such
operation.
"""

from __future__ import annotations

import os
import re
from typing import Any, Dict, List, Optional

from tpubench.readers import select_trace, trace


def patterns(config: Dict[str, Any], engine: Dict[str, Any]
             ) -> Optional[List["re.Pattern"]]:
    if "index_topk" in config or "kv_lora_rank" not in config:
        return None
    try:
        from tpushare.models.latent import LatentConfig
    except ImportError:                 # a program without the family
        return None
    if not hasattr(LatentConfig, "n_mtp"):
        return None                     # one that attends through a selector
    H = config["num_attention_heads"]
    bs, slots = engine["block_size"], engine["n_slots"]
    mb = engine.get("max_blocks_per_slot") or engine["n_blocks"]
    keys = "|".join(str(n * bs) for n in sorted({mb, *range(256, mb, 256)}))
    row = -(-(config["kv_lora_rank"] + config["qk_rope_head_dim"])
            // 128) * 128
    rank = config["kv_lora_rank"]
    qb = LatentConfig.q_block_full
    step = f"{H}|{2 * H}"               # queries x heads of a step
    head = r"^\S+ \S+ \(?"
    return [re.compile(head + p) for p in (
        # weights and scores [.., queries x heads, keys]
        rf"[a-z0-9]+\[(\d+,)*({step}|{qb * H}),({keys})\]",
        # masks [.., heads, keys]
        rf"pred\[(\d+,)*{H},({keys})\]",
        # the softmax's row statistics with the score product fused in
        rf"f32\[({qb * H}|{slots},({step}))\]",
        # a chunk's output and its block of absorbed queries
        rf"[a-z0-9]+\[(\d+,)?{qb},{H},({rank}|{row})\]",
        # a step's output
        rf"[a-z0-9]+\[{slots},({step}),{row}\]",
        # a slot's rows through the block table
        rf"[a-z0-9]+\[({mb * bs},{slots}|{slots},{mb * bs}"
        rf"|{slots},{mb},{bs}),{row}\]")]


#: percent of one device's busy time under operations that match
attend_share = select_trace.selector_share


def read(ctx):
    if ctx.trace is None:
        return None
    pats = patterns(ctx.cell.config, ctx.cell.engine)
    from tpubench import spec
    path = trace.find(os.path.join(
        spec.ROOT, "tpubench_out", ctx.cell.name + ".trace"
        + (".rehearse" if ctx.cell.rehearse else ""), "trace"))
    ops = select_trace._ops(path) if path and pats else None
    return attend_share(ops, pats) if ops else None
