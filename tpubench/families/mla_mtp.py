"""Latent attention (MLA) over the whole cache on every layer, a norm on
each sublayer's output before the residual add, one chip's share of a
wide expert layer and a multi-token-prediction module that drafts for
its own model: ``tpushare.models.latent`` configured from the published
keys (no selector, no window, no gate), checked against
``tpubench/references/mla_mtp.py``."""

from __future__ import annotations

from typing import Any, Dict

from tpubench.peaks import DTYPE_BYTES
from tpubench.references.mla_mtp import (expert_offset,  # noqa: F401
                                         forward_all, forward_with_margins)

#: the program serves it as a configuration of its latent family
MODEL_FAMILY = "latent"

# Relative error, against the largest |logit| of the reference, that a
# position may show whose routers no tie excuses. Both sides hold the
# same bf16 weights; the program keeps activations, the residual stream
# and the cached rows in bf16, runs the absorbed form of the attention
# over rows read through a block table, two positions a slot in a
# drafting step; the reference is float32 throughout and expands keys
# and values to heads.
#
# The readings, on the v5e at the published widths and 300-token prompts
# (my chip runs, PR 35, call 2; PERF.md section 6 has them by seed): at
# the stated precision the 14 held positions of the cell's six runs and
# the 4 of the control's stated half read 0.0084 to 0.0118 (two positions
# whose router margins were 0.035 and 0.075, under the 0.08 line, 0.0100
# and 0.0087: held to TIE_TOLERANCE only); under the control (every
# weight's mantissa cut to float8_e4m3fn's three bits, ``python -m
# tpubench.control``) four positions read 0.111 to 0.141: NOT correct, by
# this limit and no other. 0.02 is 1.7 times the largest stated reading
# and under a fifth of the least control reading. (PR 34's builder, the
# same program on other seeds: 0.0097-0.0116 stated, a flipped expert at
# margin 0.012 0.093, the control 0.139-0.200.)
TOLERANCE = 2.0e-2

# The module's draft logits are held to the same limit against
# ``forward_all``'s ``mtp_logits`` by ``python -m
# tests.benchmark.test_openpangu_ultra_l5_ep32`` on the chip
# (``system.check_correct`` sees only what the sampler is handed, and the
# module's logits never reach it): 0.0094 and 0.0082 at the two checked
# rounds (my chip run, PR 35, seed 35; PR 34's builder read 0.0086 and
# 0.0084 on seed 7).

#: Two checked positions a seeded prompt (its last position out of the
#: serial prefill, the first decode step's first verified position), and
#: a router margin that excuses some of them (two of sixteen in PR 35's
#: chip runs): prompts are taken until two are held.
HELD_POSITIONS = 2


def tolerance(config: Dict[str, Any]) -> float:
    return TOLERANCE


def program_config(config: Dict[str, Any], dtype):
    """``LatentConfig`` from the published keys: every layer a full
    latent layer with no selector, no gate and no rescale; the sandwich
    norms; a router with no bias; the module."""
    from tpushare.models.latent import FULL, AttnDims, LatentConfig
    c = config
    dims = AttnDims(n_heads=c["num_attention_heads"], q_rank=c["q_lora_rank"],
                    kv_rank=c["kv_lora_rank"], nope=c["qk_nope_head_dim"],
                    rope=c["qk_rope_head_dim"], v_dim=c["v_head_dim"],
                    rope_base=float(c["rope_theta"]),
                    row_align=c.get("row_align", 128))
    held = c["n_routed_experts"]
    return LatentConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        layer_types=(FULL,) * c["num_hidden_layers"], full=dims, swa=dims,
        n_dense=c["first_k_dense_replace"], d_ff=c["intermediate_size"],
        d_expert=c["moe_intermediate_size"], n_experts=c["router_width"],
        experts_held=held, expert_offset=expert_offset(c, held),
        top_k=c["num_experts_per_tok"], n_shared=c["n_shared_experts"],
        routed_scale=float(c["routed_scaling_factor"]), qkv_rescale=False,
        norm_eps=float(c["rms_norm_eps"]), dtype=dtype, selector=False,
        gate=False, sandwich_norm=bool(c["sandwich_norm"]),
        router_bias=False, n_mtp=c["num_nextn_predict_layers"])


def init_params(key, cfg):
    from tpushare.models import latent
    return latent.init_params(key, cfg)


def weight_elements(config: Dict[str, Any]) -> Dict[str, int]:
    """Parameters by part, from the shapes alone: a layer's attention
    (with its four norms and the two latent norms), the dense FFN, a
    sparse layer outside its routed experts (router, shared expert), one
    routed expert, the module's own (two norms, the joining projection,
    its final norm), the embedding, and the head with the final norm."""
    c = config
    d, H = c["hidden_size"], c["num_attention_heads"]
    nope, rope, v = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["v_head_dim"])
    q, kv = c["q_lora_rank"], c["kv_lora_rank"]
    norms = 4 * d if c.get("sandwich_norm") else 2 * d
    attn = (d * q + q + q * H * (nope + rope) + d * (kv + rope) + kv
            + kv * H * (nope + v) + H * v * d + norms)
    expert = 3 * d * c["moe_intermediate_size"]
    return {"attention": attn,
            "dense_ffn": 3 * d * c["intermediate_size"],
            "sparse_outside": (d * c["router_width"]
                               + expert * c["n_shared_experts"]),
            "one_expert": expert,
            "module_own": 2 * d * d + 3 * d,
            "embed": d * c["vocab_size"],
            "head": d * c["vocab_size"] + d}


def layer_counts(config: Dict[str, Any]) -> Dict[str, int]:
    dense = config["first_k_dense_replace"]
    return {"dense": dense, "sparse": config["num_hidden_layers"] - dense,
            "module": config["num_nextn_predict_layers"]}


def parameters(config: Dict[str, Any]) -> int:
    """Every parameter this chip holds."""
    w, n = weight_elements(config), layer_counts(config)
    sparse = (w["attention"] + w["sparse_outside"]
              + config["n_routed_experts"] * w["one_expert"])
    return (n["dense"] * (w["attention"] + w["dense_ffn"])
            + (n["sparse"] + n["module"]) * sparse
            + n["module"] * w["module_own"] + w["embed"] + w["head"])


def forward_weight_bytes(config: Dict[str, Any]) -> int:
    """A lower bound on what one drafting step reads, whatever it
    routes: every main layer outside its routed experts, the module's
    own weights and its layer outside its experts, the head, and one
    expert a sparse layer (the module's too). A step that touches more
    experts reads more, and a fused step runs the module twice, so
    ``forward.hbm_floor_pct`` under-reads and can never pass 100 %."""
    w, n = weight_elements(config), layer_counts(config)
    sparse = n["sparse"] + n["module"]
    return DTYPE_BYTES[config.get("torch_dtype", "bfloat16")] * (
        n["dense"] * (w["attention"] + w["dense_ffn"])
        + sparse * (w["attention"] + w["sparse_outside"] + w["one_expert"])
        + n["module"] * w["module_own"] + w["head"])


def cached_bytes_per_token(config: Dict[str, Any]) -> int:
    """What a token caches on the chip: a latent row (c_kv and k_r, padded
    with zeros to whole 128-lane tiles) a main layer and one for the
    module."""
    row = -(-(config["kv_lora_rank"] + config["qk_rope_head_dim"])
            // 128) * 128
    layers = (config["num_hidden_layers"]
              + config["num_nextn_predict_layers"])
    return layers * row * DTYPE_BYTES[config.get("torch_dtype", "bfloat16")]
