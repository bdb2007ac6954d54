"""The dense decoder: ``tpushare.models.transformer`` configured from the
published Mistral keys, checked against the Mistral block of
``reference.py``."""

from __future__ import annotations

from typing import Any, Dict

from tpubench.peaks import DTYPE_BYTES
from tpubench.reference import forward_with_margins    # noqa: F401

MODEL_FAMILY = "dense"

# Relative error, against the largest |logit| of the reference, that the
# served logits may show. Both sides hold the same bf16 weights; the
# system keeps activations and the residual stream in bf16 and pads,
# pages and batches, the reference runs float32 throughout. bf16 rounds
# to 2^-8 about twice a layer, a random walk in the residual stream.
#
# 0.005 x sqrt(layers), 0.020 at 16 layers: 1.55 times the largest of
# the 50 positions the v5e measured over 25 runs (0.0084 to 0.0129,
# PR 22; PR 21 measured 0.0040 over 18 layers of Gemma-2B, whose norms
# differ). A wrong mask, offset, block index or rotary layout shows as
# O(1). What int8 shows is measured in tests/benchmark/
# test_tpubench_reference.py at toy widths, the int8 error alone: int8
# weights 0.016 to 0.020 over two layers, which fails; an int8 cache
# 0.0045, which this bound does not see.
TOLERANCE_PER_SQRT_LAYER = 5e-3

#: Two checked positions a seeded prompt (its last position from
#: prefill, the first decode step), no router to excuse either: one
#: prompt.
HELD_POSITIONS = 2


def tolerance(config: Dict[str, Any]) -> float:
    return TOLERANCE_PER_SQRT_LAYER * config["num_hidden_layers"] ** 0.5


def program_config(config: Dict[str, Any], dtype):
    """``TransformerConfig`` from the published keys."""
    from tpushare.models.transformer import TransformerConfig
    c = config
    return TransformerConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        head_dim=c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"],
        d_ff=c["intermediate_size"], rope_base=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]), act=c["hidden_act"],
        tie_embeddings=bool(c["tie_word_embeddings"]),
        sliding_window=c.get("sliding_window"), dtype=dtype, remat=False)


def init_params(key, cfg):
    from tpushare.models import transformer
    return transformer.init_params(key, cfg)


def block_weight_elements(config: Dict[str, Any], ffn: int) -> int:
    """Weights one forward of the Mistral block must read, in elements:
    every layer's attention matrices and the ``ffn`` elements of its
    feed-forward once, the two norms, the final norm and the output
    head. The embedding is a gather of a few rows and is left out."""
    d = config["hidden_size"]
    hd = config.get("head_dim") or d // config["num_attention_heads"]
    q = config["num_attention_heads"] * hd
    kv = config["num_key_value_heads"] * hd
    per_layer = d * (q + 2 * kv) + q * d + 2 * d + ffn
    head = d * config["vocab_size"] + d
    return config["num_hidden_layers"] * per_layer + head


def forward_weight_bytes(config: Dict[str, Any]) -> int:
    """Bytes of weights one forward must read from HBM, from the shapes
    alone: ``block_weight_elements`` with the three SwiGLU matrices. No
    cache traffic, no activations: this is the floor, not an estimate."""
    ffn = 3 * config["hidden_size"] * config["intermediate_size"]
    return (DTYPE_BYTES[config.get("torch_dtype", "bfloat16")]
            * block_weight_elements(config, ffn))
