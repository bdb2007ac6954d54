"""``attn.select_busy_pct``: the learned key selector's share of the
device's busy time, from the traced slice.

The trace names a device operation by its HLO line and nothing else
(``readers/trace.py``; ``jax.named_scope`` does not reach it: the
program's ``latent_select`` scope is in the HLO's metadata, which the
profiler leaves out at the harness's options), so the selector's
operations are found by kind and result shape, from the configuration's,
the engine's and the program's own numbers. A key axis is as long as a
slot holds (``max_blocks_per_slot`` x ``block_size``) or, in an
admission chunk, a whole number of 256-block steps of it:

  scores, top-k      a float32, integer or boolean result of two axes
                     whose last is a key axis: the scores [slots, keys]
                     of a decode step and their sort; of a chunk the
                     score product [queries x index heads, keys], its sum
                     over heads [queries, keys], the threshold's
                     compare-and-count passes and the mask (these also
                     as [blocks, queries a block, keys]). NOT a result
                     [queries x attention heads, keys]: that is
                     attention's own score over a chunk's block of
                     queries (the program merges queries and heads into
                     one axis), which the compiler fuses into the softmax
                     today and might not tomorrow
  the key gather     a result [.., n, block_size, index_head_dim]
  the row gather     a result [slots x index_topk, row]

The recorded slice these were read off is
``tests/benchmark/data/v5e_longdoc_ops.json``. The shapes are this
family's: in another model a hidden size can equal a key axis, which is
why the metric lists its cells. Self time of the matches, over the busy
time of the first device. None where the configuration has no selector,
the program has no such family, or the trace has no such operation.
"""

from __future__ import annotations

import functools
import os
import re
from typing import Any, Dict, List, Optional

from tpubench.readers import trace


def patterns(config: Dict[str, Any], engine: Dict[str, Any]
             ) -> Optional[List["re.Pattern"]]:
    if "index_topk" not in config:
        return None
    try:
        from tpushare.models.latent import LatentConfig
    except ImportError:                 # a program without the family
        return None
    idim, topk = config["index_head_dim"], config["index_topk"]
    bs = engine["block_size"]
    mb = engine.get("max_blocks_per_slot") or engine["n_blocks"]
    keys = "|".join(str(n * bs) for n in
                    sorted({mb, *range(256, mb, 256)}))
    row = -(-(config["kv_lora_rank"] + config["qk_rope_head_dim"]) // 128) * 128
    qb = LatentConfig.q_block_full
    attn = qb * config["num_attention_heads"]
    head = r"^\S+ \S+ \(?"
    return [re.compile(head + p) for p in (
        # scores are float32, the threshold's keys u32, masks pred: a
        # bf16 [tokens, heads x v_head_dim] can be as long as a key axis
        rf"(f32|u32|s32|pred)\[(?!{attn},)\d+,({keys})\]",
        # the same by block of queries [blocks, queries a block, keys];
        # attention's mask is [queries a block, heads, keys]
        rf"(f32|u32|s32|pred)\[\d+,{qb},({keys})\]",
        rf"[a-z0-9]+\[(\d+,)+{bs},{idim}\]",
        rf"[a-z0-9]+\[{engine['n_slots'] * topk},{row}\]")]


def selector_share(ops: List[tuple], pats: List["re.Pattern"]
                   ) -> Optional[float]:
    """ops: one device's ``(short name, start, duration, is_mosaic)``
    events (``trace.load``). Percent of busy time, or None where nothing
    matches."""
    busy = trace.total(trace.union([(s, s + d) for _, s, d, _ in ops]))
    hit = sum(t for name, t, _ in trace.self_times(ops)
              if any(p.search(name) for p in pats))
    return 100.0 * hit / busy if hit and busy else None


@functools.lru_cache(maxsize=2)
def _ops(path: str):
    devs = trace.load(path)["devices"]
    return devs[sorted(devs)[0]] if devs else None


def read(ctx):
    if ctx.trace is None:
        return None
    pats = patterns(ctx.cell.config, ctx.cell.engine)
    from tpubench import spec
    path = trace.find(os.path.join(
        spec.ROOT, "tpubench_out", ctx.cell.name + ".trace"
        + (".rehearse" if ctx.cell.rehearse else ""), "trace"))
    ops = _ops(path) if path and pats else None
    return selector_share(ops, pats) if ops else None
