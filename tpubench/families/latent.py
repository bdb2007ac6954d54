"""Latent attention with a learned key selector, windowed latent layers
between the global ones, a gated output and one chip's share of a wide
expert layer: ``tpushare.models.latent`` configured from the published
keys, checked against ``tpubench/references/latent.py``."""

from __future__ import annotations

import json
import sys
from typing import Any, Dict, Optional, Sequence

import numpy as np

from tpubench.peaks import DTYPE_BYTES
from tpubench.references import latent as reference
from tpubench.references.latent import FULL, attention_dims, expert_offset

MODEL_FAMILY = "latent"

# Relative error, against the largest |logit| of the reference, that a
# position may show whose routers no tie excuses. Both sides hold the
# same bf16 weights; the program keeps activations, the residual stream
# and the cached rows in bf16, runs the absorbed form of the attention,
# pages and batches; the reference is float32 throughout and expands
# keys and values to heads. The readings behind it are under
# SELECT_BAND.
TOLERANCE = 2.0e-2

# The check runs at 4,096 tokens, where the selector of each full layer
# drops half of the keys of the checked positions and of the 2,048
# before them. Among thousands of keys the last kept and the first
# dropped score closer than bf16 resolves, so the program and a float32
# reference never keep quite the same set, and logits alone cannot tell
# those exchanges from a fault in the score, the top-k or the gather.
# So the program says which keys it kept (``latent.SelectionLog``, the
# tap ``program_config`` sets), and the reference attends the
# program's choice for the keys whose reference score lies within
# SELECT_BAND of the line between its last kept and first dropped (in
# units of the spread of the query's scores), and its own choice for
# every other key (``references/latent._adopt``). A selection that is
# wrong beyond rounding therefore meets a reference that did not follow
# it, and fails by TOLERANCE like any other fault.
#
# The readings behind both limits, on the v5e at the published widths
# (my chip run, PR 28, c7; PERF.md section 6 has them by seed): at the
# stated precision 23 held positions of 17 prompts read 0.0079 to 0.0138
# and the farthest disagreement of a prompt 0.070 to 0.095 (its second
# full layer; 0.021 to 0.029 on the first), none outside the band; the
# same answers against the reference's own selection 0.040 to 0.073.
# Under the control (every weight's mantissa cut to float8_e4m3's three
# bits) 8 positions read 0.141 to 0.194 and the farthest disagreement
# 1.02 to 1.23, with 56 thousand keys of a prompt outside the band. So
# the tolerance is 1.45 times the largest stated reading and a seventh
# of the least control reading, and the band 2.6 times the largest
# stated and a quarter of the least control reading.
SELECT_BAND = 0.25

# The margin (``references/latent.py``): a router's gap between the last
# chosen and the first unchosen of score + bias over the spread of the
# position's 256 values, counted only where one of the two is an expert
# this chip holds (an exchange between two absent experts moves nothing
# here), times 4 (``router_margin_scale``). A position under the line
# is held to ``reference.TIE_TOLERANCE`` only; the check takes prompts
# until two are held. The selector excuses nothing: its selection is
# compared (above).
HELD_POSITIONS = 2

#: the tap of the last configuration built here (``program_config``):
#: what the engine under check writes and ``program_selection`` reads
_TAP = None
#: ``_adopt``'s numbers for the last sequence judged with the program's
#: selection (``selection_report``)
LAST_REPORT: Optional[Dict[str, Any]] = None


def tolerance(config: Dict[str, Any]) -> float:
    return TOLERANCE


def program_selection(tokens: Sequence[int], tap=None):
    """The keys the engine's selectors kept when it served ``tokens``: a
    bool mask [full layer, query, key], or None unless the tap (the last
    built here, unless given) holds exactly that, a cold serial
    admission of all but the last token and one decode step of the last
    (what ``system.check_correct`` drives). Read on the host."""
    tap, n = tap or _TAP, len(tokens) - 1
    if (tap is None or tap.step is None or tap.prompt is None
            or list(tap.prompt) != list(tokens[:n])):
        return None
    pos, active, toks, idx = (np.asarray(a) for a in tap.step)
    rows = np.flatnonzero(active & (pos == n) & (toks[:, 0] == tokens[n]))
    if len(rows) != 1:
        return None
    masks = np.zeros((idx.shape[0], n + 1, n + 1), bool)
    covered = 0
    for first, bits in tap.admission:
        if first != covered:
            return None
        stop = min(n, first + bits.shape[1])
        m = np.unpackbits(np.asarray(bits[:, :stop - first]), axis=-1,
                          bitorder="little")
        masks[:, first:stop] = m[:, :, :n + 1]
        covered = stop
    if covered != n:
        return None
    for layer, k in enumerate(idx[:, rows[0]]):
        masks[layer, n, k[k >= 0]] = True
    return masks


def selection_report(report, n: int) -> Dict[str, Any]:
    """``_adopt``'s numbers, a full layer: over every query, and for the
    two checked positions (the last two)."""
    out = {"band": SELECT_BAND, "layers": []}
    for stats in report:
        s = np.asarray(stats)
        out["layers"].append({
            "taken": int(s[:, 0].sum()), "outside": int(s[:, 1].sum()),
            "farthest": float(s[:, 2].max()),
            "checked": [{"taken": int(r[0]), "outside": int(r[1]),
                         "farthest": float(r[2])} for r in s[n - 2:n]]})
    return out


def forward_with_margins(params, tokens, config, kept="tap"):
    """The reference on ``tokens``, attending the selection the engine
    made when it served them: ``kept`` is that selection
    (``program_selection``'s mask), or "tap" to read it now (the
    reference's own where the tap does not hold it), or None for the
    reference's own."""
    global LAST_REPORT
    if isinstance(kept, str):
        kept = program_selection(tokens)
    report = []
    out = reference.forward_with_margins(
        params, tokens, config, kept=kept, band=SELECT_BAND, report=report)
    LAST_REPORT = selection_report(report, len(tokens)) if report else None
    if LAST_REPORT:
        print("tpubench.families.latent: selection vs the reference: "
              + json.dumps(LAST_REPORT), file=sys.stderr, flush=True)
    return out


def program_config(config: Dict[str, Any], dtype):
    """``LatentConfig`` from the published keys, with the tap the check
    reads the engine's selection from (``program_selection``)."""
    global _TAP
    from tpushare.models.latent import AttnDims, LatentConfig, SelectionLog
    c = config
    _TAP = SelectionLog()

    def dims(kind):
        d = attention_dims(c, kind)
        return AttnDims(n_heads=d["H"], q_rank=d["q_rank"],
                        kv_rank=d["kv_rank"], nope=d["nope"], rope=d["rope"],
                        v_dim=d["v"], rope_base=d["theta"])

    held = c["n_routed_experts"]
    return LatentConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        layer_types=tuple(c["layer_types"][:c["num_hidden_layers"]]),
        full=dims(FULL), swa=dims("sliding_attention"),
        window=c["sliding_window_size"],
        index_heads=c["index_n_heads"], index_dim=c["index_head_dim"],
        index_topk=c["index_topk"], n_dense=c["first_k_dense_replace"],
        d_ff=c["intermediate_size"], d_expert=c["moe_intermediate_size"],
        n_experts=c["router_width"], experts_held=held,
        expert_offset=expert_offset(c, held),
        top_k=c["num_experts_per_tok"], n_shared=c["n_shared_experts"],
        routed_scale=float(c["routed_scaling_factor"]),
        qkv_rescale=bool(c["apply_mla_qkv_lora_rescale"]),
        norm_eps=float(c["rms_norm_eps"]), dtype=dtype, select_log=_TAP)


def init_params(key, cfg):
    from tpushare.models import latent
    return latent.init_params(key, cfg)


def weight_elements(config: Dict[str, Any]) -> Dict[str, int]:
    """Parameters by part, from the shapes alone: every layer outside its
    routed experts, one routed expert, and the output head with the final
    norm. The embedding is a gather of a few rows and is left out."""
    c = config
    d = c["hidden_size"]
    kinds = c["layer_types"][:c["num_hidden_layers"]]
    outside = 0
    for i, kind in enumerate(kinds):
        a = attention_dims(c, kind)
        attn = (d * a["q_rank"] + a["q_rank"]
                + a["q_rank"] * a["H"] * (a["nope"] + a["rope"])
                + d * (a["kv_rank"] + a["rope"]) + a["kv_rank"]
                + a["kv_rank"] * a["H"] * (a["nope"] + a["v"])
                + d * a["H"] + a["H"] * a["v"] * d + 2 * d)
        if kind == FULL:
            ih, idim = c["index_n_heads"], c["index_head_dim"]
            attn += a["q_rank"] * ih * idim + d * idim + 2 * idim + d * ih
        if i < c["first_k_dense_replace"]:
            ffn = 3 * d * c["intermediate_size"]
        else:
            ffn = (d * c["router_width"] + c["router_width"]
                   + 3 * d * c["moe_intermediate_size"]
                   * c["n_shared_experts"])
        outside += attn + ffn
    n_sparse = len(kinds) - c["first_k_dense_replace"]
    return {"outside_experts": outside,
            "one_expert": 3 * d * c["moe_intermediate_size"],
            "sparse_layers": n_sparse,
            "head": d * c["vocab_size"] + d}


def forward_weight_bytes(config: Dict[str, Any]) -> int:
    """A lower bound on what one forward reads, whatever it routes:
    everything outside the routed experts, the head, and one expert a
    sparse layer. A forward that touches more experts reads more, so
    ``forward.hbm_floor_pct`` under-reads and can never pass 100 %."""
    w = weight_elements(config)
    return DTYPE_BYTES[config.get("torch_dtype", "bfloat16")] * (
        w["outside_experts"] + w["head"]
        + w["sparse_layers"] * w["one_expert"])
