"""The Mixtral-style sparse decoder: ``tpushare.models.moe`` configured
through ``convert.moe_config_from_hf``, checked against the Mixtral
block of ``reference.py`` (the dense family's attention, softmax top-k
experts)."""

from __future__ import annotations

import types
from typing import Any, Dict

from tpubench.families.dense import block_weight_elements
from tpubench.peaks import DTYPE_BYTES
from tpubench.reference import forward_with_margins    # noqa: F401

MODEL_FAMILY = "moe"

# A position that no router tie excuses (``reference.ROUTER_TIE_MARGIN``)
# is held to this, each one, no median. Measured on the v5e over 13
# runs, 78 positions (PR 22): 6 positions between 0.239 and 0.665, 72
# between 0.0085 and 0.0356, nothing in between. A flip at an EARLIER
# position reaches a checked one only through attention, one key among
# 300: that is the spread from 0.009 to 0.036 among the positions that
# did not flip themselves (16 dense layers: 0.008 to 0.013), and no
# margin of the checked position sees it. The bound is 1.5 times the
# largest of the 72. It does not see int8 experts: the test beside the
# dense one (tests/benchmark/test_tpubench_reference.py) measures 0.015
# to 0.028 for them at toy widths, under this bound.
TOLERANCE = 5.5e-2

#: The margin excuses about half of all positions, so the check takes
#: prompts until it has four that no router tie excuses: four prompts as
#: a rule, ``reference.MAX_CHECK_PROMPTS`` at most.
HELD_POSITIONS = 4


def tolerance(config: Dict[str, Any]) -> float:
    return TOLERANCE


def program_config(config: Dict[str, Any], dtype):
    """``MoEConfig`` by the program's own converter, from the scalar
    keys: it reads attributes, and none of those it reads is a list or a
    group."""
    from tpushare.models.convert import moe_config_from_hf
    hf = types.SimpleNamespace(**{k: v for k, v in config.items()
                                  if not isinstance(v, (dict, list))})
    return moe_config_from_hf(hf, dtype=dtype)


def init_params(key, cfg):
    from tpushare.models import moe
    return moe.init_params(key, cfg)


def forward_weight_bytes(config: Dict[str, Any]) -> int:
    """As the dense family's, with all experts of a layer and its
    router: a batch of a dozen tokens and more touches every one of 8
    experts, and the psum dispatch reads them regardless."""
    d, n_exp = config["hidden_size"], config["num_local_experts"]
    ffn = n_exp * 3 * d * config["intermediate_size"] + d * n_exp
    return (DTYPE_BYTES[config.get("torch_dtype", "bfloat16")]
            * block_weight_elements(config, ffn))
