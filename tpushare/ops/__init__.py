"""tpushare.ops — TPU-first numeric primitives for the workload harness.

The plugin (tpushare.plugin) schedules JAX pods; these ops are the
compute path of the workloads those pods run (BASELINE.md: Gemma-2B,
BERT-base, ResNet-50, Llama-3-8B). jnp reference implementations are
the semantic ground truth everywhere; pallas kernels take over on TPU
for the ops XLA cannot fuse optimally (attention's score matrix).
"""

from tpushare.ops.attention import attention, mha_reference
from tpushare.ops.flash_attention import (
    flash_attention, flash_attention_partial, flash_eligible,
    partial_reference,
)
from tpushare.ops.norms import layer_norm, rms_norm
from tpushare.ops.q8_expert import (
    q8_expert_dispatch, q8_expert_eligible, q8_expert_ffn,
    q8_expert_ffn_reference,
)
from tpushare.ops.retention import phi, retention_step
from tpushare.ops.rotary import apply_rotary, rotary_embedding

__all__ = [
    "attention", "mha_reference", "flash_attention",
    "flash_attention_partial", "flash_eligible", "partial_reference",
    "layer_norm", "rms_norm", "apply_rotary", "rotary_embedding",
    "q8_expert_dispatch", "q8_expert_eligible", "q8_expert_ffn",
    "q8_expert_ffn_reference", "phi", "retention_step",
]
