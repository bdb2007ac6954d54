"""Power retention (Brumby): ``tpushare.models.retention`` configured
from the published keys, checked against
``tpubench/references/retention.py`` (the quadratic form: no feature
map, no chunks, no state)."""

from __future__ import annotations

from typing import Any, Dict

from tpubench.peaks import DTYPE_BYTES
from tpubench.references.retention import forward_with_margins  # noqa: F401

MODEL_FAMILY = "retention"

# Relative error, against the largest |logit| of the reference, that a
# checked position may show. Both sides hold the same bf16 weights; the
# program keeps activations and the residual stream in bf16, computes the
# layer as a recurrence over a float32 state (chunks of 128 through
# phi(Q) S and phi(K)^T V, then one step of the Pallas kernel), and the
# reference the quadratic form in float32 throughout.
#
# The readings, on the v5e at the published widths and 2,048-token
# prompts (my chip runs, PR 32; PERF.md section 6 has them by seed): at
# the stated precision 14 positions of seven seeds read 0.0152 to 0.0177
# (0.0173 to 0.0199 on four positions before q, k and v were kept in
# float32 out of their projections), the prefill's position and the
# kernel's alike; under the control (every weight's mantissa cut to
# float8_e4m3fn's three bits, ``python -m tpubench.control``) four
# positions read 0.223 to 0.263: NOT correct, by this limit. 0.05 is 2.8
# times the largest stated reading and a quarter of the least control
# reading. (Twice the dense family's reading a layer: the weights of a
# retention sum are (q.k)^2, which doubles the relative rounding of q.k,
# and the output divides two such sums.)
TOLERANCE = 5.0e-2

#: Two checked positions a seeded prompt (its last position out of the
#: chunked prefill, the first decode step out of the kernel), no router
#: to excuse either: one prompt.
HELD_POSITIONS = 2


def tolerance(config: Dict[str, Any]) -> float:
    return TOLERANCE


def program_config(config: Dict[str, Any], dtype):
    """``RetentionConfig`` from the published keys."""
    from tpushare.models.retention import RetentionConfig
    c = config
    return RetentionConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], rope_base=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]), dtype=dtype,
        inner_chunk=c["inner_chunk"], prefill_chunk=c["serial_chunk"])


def init_params(key, cfg):
    from tpushare.models import retention
    return retention.init_params(key, cfg)


def weight_elements(config: Dict[str, Any]) -> Dict[str, int]:
    """Parameters by part, from the shapes alone: one layer (the four
    projections, the gate and its bias, the two head norms, SwiGLU, the
    two norms), the embedding, and the head with the final norm."""
    c = config
    d, hd = c["hidden_size"], c["head_dim"]
    q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    layer = (d * (q + 2 * kv) + q * d
             + (d + 1) * c["num_key_value_heads"] + 2 * hd
             + 3 * d * c["intermediate_size"] + 2 * d)
    return {"layer": layer, "embed": d * c["vocab_size"],
            "head": d * c["vocab_size"] + d}


def parameters(config: Dict[str, Any]) -> int:
    w = weight_elements(config)
    return config["num_hidden_layers"] * w["layer"] + w["embed"] + w["head"]


def state_bytes_per_layer_stream(config: Dict[str, Any]) -> int:
    """A stream's state in one layer by the equations: S [D (D + 1) / 2,
    D] and z [D (D + 1) / 2] a kv head, float32. (On the chip each is
    padded to (D / 2 + 1) D rows, whole lane tiles: 0.8 % more at 128.)"""
    hd = config["head_dim"]
    feats = hd * (hd + 1) // 2
    return 4 * config["num_key_value_heads"] * feats * (hd + 1)


def forward_weight_bytes(config: Dict[str, Any]) -> int:
    """Bytes of WEIGHTS one forward must read: every layer and the head
    once; the embedding is a gather of a few rows. The state a tick
    reads and writes is not in here: it has its own metric
    (``retention.state_share_of_step_bytes_pct``), so
    ``forward.hbm_floor_pct`` under-reads on this family and cannot
    pass 100 %."""
    w = weight_elements(config)
    return DTYPE_BYTES[config.get("torch_dtype", "bfloat16")] * (
        config["num_hidden_layers"] * w["layer"] + w["head"])


def warm_growth(engine) -> None:
    """Nothing to warm: a tick's growth is host bookkeeping here (no
    block-table scatter, no program whose shape traffic decides)."""
