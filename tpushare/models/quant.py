"""Int8 weight quantization for the decoder LM (serving).

Decode streams the full weight set from HBM per token — at bf16 that
stream IS the latency floor. Symmetric per-output-channel int8 halves
it, and halves resident param HBM, which composes with this
framework's whole point: a quantized tenant fits a smaller
``aliyun.com/tpu-mem`` grant, so more tenants bin-pack per chip.

TPU-first mechanism — no model surgery: the quantized layer stack
stores int8 weights + f32 scales and rides ``forward``'s existing
``layers_hook`` seam (models/transformer.py): the hook dequantizes ONE
layer inside the scan body, so weights live in HBM as int8 and the
bf16 view is transient (XLA fuses convert·scale into the consuming
matmul where it can). Norm vectors and the embedding stay full
precision (norms are tiny; the embed gather needs rows, and its
matmul role as the tied head keeps logits precision).

Quality: symmetric per-output-channel int8 on attention/MLP weights is
the standard serving recipe; tests bound the logit error against the
full-precision model and check greedy decode agreement on tiny
models.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from tpushare.models.transformer import TransformerConfig, forward

# Layer leaves that get quantized (2-D [in, out] per layer, stacked
# [L, in, out]); everything else (norms) passes through.
_QUANT_KEYS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
# The MoE expert stacks — the leaves the fused dequant×GEMM kernel
# (ops/q8_expert.py) consumes as raw int8; fused_expert_hook passes
# these through while dequantizing everything else.
_EXPERT_KEYS = ("w_gate", "w_up", "w_down")
_SUFFIX_Q = "#q8"
_SUFFIX_S = "#scale"


def quantize_layers(layers: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
    """Stacked layer tree -> quantized storage tree.

    Each quantized leaf ``k`` [L, In, Out] becomes ``k#q8`` int8 plus
    ``k#scale`` f32 [L, 1, Out] (symmetric, per output channel).
    """
    out: Dict[str, jnp.ndarray] = {}
    for k, w in layers.items():
        if k in _QUANT_KEYS:
            s = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=-2,
                        keepdims=True) / 127.0
            s = jnp.maximum(s, 1e-12)
            q = jnp.clip(jnp.round(w.astype(jnp.float32) / s),
                         -127, 127).astype(jnp.int8)
            out[k + _SUFFIX_Q] = q
            out[k + _SUFFIX_S] = s
        else:
            out[k] = w
    return out


@functools.lru_cache(maxsize=None)
def dequant_hook(cfg: TransformerConfig):
    """``layers_hook`` for forward(): per-layer int8 -> cfg.dtype.

    Memoized per cfg: generate() keys its jit cache on the hook's
    IDENTITY (static argname), so a fresh closure per call would
    recompile the whole generation program every request."""
    def hook(layer: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
        out: Dict[str, jnp.ndarray] = {}
        for k, v in layer.items():
            if k.endswith(_SUFFIX_Q):
                base = k[: -len(_SUFFIX_Q)]
                s = layer[base + _SUFFIX_S]
                out[base] = (v.astype(jnp.float32) * s).astype(cfg.dtype)
            elif k.endswith(_SUFFIX_S):
                continue
            else:
                out[k] = v
        return out
    return hook


@functools.lru_cache(maxsize=None)
def fused_expert_hook(cfg: TransformerConfig):
    """``layers_hook`` for the fused int8 MoE expert path: attention
    leaves dequantize per layer exactly like dequant_hook, but the
    EXPERT stacks (w_gate/w_up/w_down) stay int8 — their ``#q8`` +
    ``#scale`` leaves pass through untouched and models/moe.py's
    _moe_ffn feeds them straight to ops/q8_expert.q8_expert_dispatch,
    so no wide expert copy is ever materialized (the r5 roofline-gap
    culprit: dequant_hook rebuilt the full-width expert tree inside
    the scan body every decode step).

    MoE-ONLY: the dense LM's FFN leaves share these names but have no
    expert axis and no fused consumer — models/transformer.py reads
    ``layer["w_gate"]`` directly and would fail loudly on the passed-
    through ``#q8`` leaves; dense int8 trees keep dequant_hook.

    Memoized per cfg for the same reason as dequant_hook: generate()
    and the slot servers key their jit caches on the hook's IDENTITY
    (JC801 pins this seam)."""
    def hook(layer: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
        out: Dict[str, jnp.ndarray] = {}
        for k, v in layer.items():
            if k.endswith(_SUFFIX_Q):
                base = k[: -len(_SUFFIX_Q)]
                if base in _EXPERT_KEYS:
                    out[k] = v                       # stay int8
                else:
                    s = layer[base + _SUFFIX_S]
                    out[base] = (v.astype(jnp.float32) * s).astype(
                        cfg.dtype)
            elif k.endswith(_SUFFIX_S):
                if k[: -len(_SUFFIX_S)] in _EXPERT_KEYS:
                    out[k] = v                       # kernel scales
            else:
                out[k] = v
        return out
    return hook


def dequant_expert_leaves(layer: Dict[str, jnp.ndarray],
                          dtype: Any) -> Dict[str, jnp.ndarray]:
    """Widen a layer dict's int8 expert leaves in-graph — EXACTLY the
    dequant_hook math ((q·s).astype(dtype)) — for the dispatch paths
    the fused kernel does not cover (dropless/a2a/expert_choice fall
    back to this; see _moe_ffn)."""
    out = {k: v for k, v in layer.items()
           if not (k.endswith(_SUFFIX_Q) or k.endswith(_SUFFIX_S))}
    for k, v in layer.items():
        if k.endswith(_SUFFIX_Q):
            base = k[: -len(_SUFFIX_Q)]
            s = layer[base + _SUFFIX_S]
            out[base] = (v.astype(jnp.float32) * s).astype(dtype)
    return out


def quantize_params(params: Dict[str, Any],
                    cfg: TransformerConfig) -> Dict[str, Any]:
    """Full param tree with the layer stack quantized (embed/norms
    full precision). Use with ``quantized_forward`` or pass
    ``layers_hook=dequant_hook(cfg)`` to forward()."""
    out = dict(params)
    out["layers"] = quantize_layers(params["layers"])
    return out


def quant_layer_specs(layer_specs: Dict[str, Any],
                      layers: Optional[Dict[str, Any]] = None
                      ) -> Dict[str, Any]:
    """PartitionSpec tree for quantize_layers storage, derived from the
    full-precision layer specs: ``k#q8`` shards exactly like ``k``
    (same shape), ``k#scale`` is the per-output-channel tensor with
    the reduced input axis (-2) collapsed to 1 — keep every other
    axis's sharding, drop the input axis's (a row-shard cannot split a
    size-1 axis). Rank-generic like quantize_layers itself: dense
    leaves are [L, In, Out] -> scale [L, 1, Out]; MoE expert stacks
    are [L, E, In, Out] -> scale [L, E, 1, Out] with the ep sharding
    on E preserved.

    Specs must be EXPLICIT full rank: the scale spec is built
    positionally from the right, so a JAX-legal truncated spec (e.g.
    P(None, "ep", None) on a rank-4 expert leaf, trailing axes
    implicitly replicated) would silently drop the ep sharding from
    the scale. Pass ``layers`` (the full-precision layer tree, or any
    tree with the same leaf ranks) to have that enforced."""
    from jax.sharding import PartitionSpec as P
    out: Dict[str, Any] = {}
    for k, sp in layer_specs.items():
        if k in _QUANT_KEYS:
            entries = tuple(sp)
            if len(entries) < 3:
                raise ValueError(
                    f"quantized leaf {k!r} needs an explicit rank>=3 "
                    f"spec [L, ..., In, Out]; got {sp}")
            if layers is not None and k in layers and \
                    len(entries) != layers[k].ndim:
                raise ValueError(
                    f"quantized leaf {k!r} is rank {layers[k].ndim} "
                    f"but its spec {sp} has {len(entries)} entries; "
                    f"truncated specs would mis-place the scale "
                    f"sharding — spell out every axis")
            out[k + _SUFFIX_Q] = sp
            out[k + _SUFFIX_S] = P(*entries[:-2], None, entries[-1])
        else:
            out[k] = sp
    return out


def quant_param_specs(cfg: TransformerConfig,
                      **param_specs_kw) -> Dict[str, Any]:
    """PartitionSpec tree for a quantize_params tree — the placement
    contract for quantized serving (what make_tp_decoder(quantized=
    True) uses internally; place params with THIS, not the
    full-precision param_specs)."""
    from tpushare.models.transformer import param_specs
    specs = param_specs(cfg, **param_specs_kw)
    return dict(specs, layers=quant_layer_specs(specs["layers"]))


def quant_moe_param_specs(cfg, **param_specs_kw) -> Dict[str, Any]:
    """PartitionSpec tree for a quantized MoE tree (quantize_params on
    moe.init_params) — the MoE analog of quant_param_specs and the
    one placement contract for int8 MoE serving (serving.
    make_moe_decoder, the dryrun gate, tests). moe.param_specs emits
    explicit full-rank specs, which quant_layer_specs' positional
    scale construction requires."""
    from tpushare.models.moe import param_specs as moe_param_specs
    specs = moe_param_specs(cfg, **param_specs_kw)
    return dict(specs, layers=quant_layer_specs(specs["layers"]))


def param_bytes(params: Dict[str, Any]) -> int:
    return sum(leaf.nbytes for leaf in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# Int8 KV cache (serving): halve (bf16) or quarter (f32) the resident
# cache so a tenant fits ~2x the concurrent sequences into the same
# ``tpu-mem`` grant. Symmetric per-(position, kv-head) scales over the
# head dim; the dequantized view is materialized one layer at a time
# inside forward's scan (transient, like dequant_hook's weights), so
# this is a STORAGE win — decode read traffic is unchanged until the
# flash kernels grow an int8 path (documented seam, not claimed).
#
# Exactness property the tests pin: with absmax scales the max-|x|
# entry quantizes to exactly +/-127, so requantizing a dequantized row
# reproduces the same (int8, scale) pair bit-for-bit — rows a step
# does not write never drift, no matter how many steps run.
# ---------------------------------------------------------------------------


def init_cache_q8(cfg: TransformerConfig, batch: int, max_len: int,
                  n_kv_heads: int = None) -> Dict[str, jnp.ndarray]:
    """Int8 KV cache: {"k","v"} int8 [L,B,M,Hkv,Dh] +
    {"k_scale","v_scale"} f32 [L,B,M,Hkv]. Drop-in for
    transformer.init_cache on the single-device forward path (``n_kv_heads`` overrides for tp-local caches, matching
    init_cache's signature). The tp shard_map serving factories
    (serving.make_tp_decoder / cache_specs) do not yet carry the scale
    leaves — that composition is a documented seam, like kvq+paged."""
    hkv = cfg.n_kv_heads if n_kv_heads is None else n_kv_heads
    shape = (cfg.n_layers, batch, max_len, hkv, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, jnp.int8),
        "v": jnp.zeros(shape, jnp.int8),
        "k_scale": jnp.zeros(shape[:-1], jnp.float32),
        "v_scale": jnp.zeros(shape[:-1], jnp.float32),
    }


def kv_quantize(rows: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """[..., Dh] -> (int8 [..., Dh], f32 scale [...]); absmax over Dh."""
    x = rows.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1), 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / s[..., None]), -127, 127).astype(jnp.int8)
    return q, s


def kv_dequantize(q: jnp.ndarray, s: jnp.ndarray,
                  dtype: Any) -> jnp.ndarray:
    """(int8 [..., Dh], scale [...]) -> dtype [..., Dh]."""
    return (q.astype(jnp.float32) * s[..., None]).astype(dtype)


# Paged-pool scale layout: the pallas paged-decode kernel wants scale
# pages as [n_blocks, Hkv_pad, block_size] — block_size on the lane dim
# (Mosaic rejects a short minor axis) with the kv-head dim padded to a
# sublane multiple. Scales are STORED in this layout from pool init on
# (ADVICE r3: transposing the whole pool per decode step was O(pool)
# work per token and skewed the int8 dispatch crossover); the row-major
# [..., bs, Hkv] view exists only transiently at gather/scatter edges.

def kv_scale_pad(hkv: int) -> int:
    """Padded kv-head count of the pool scale layout (sublane dim)."""
    return max(8, -(-hkv // 8) * 8)


def scales_to_pool_layout(s: jnp.ndarray) -> jnp.ndarray:
    """Row-major scales [..., bs, Hkv] -> kernel layout
    [..., Hkv_pad, bs] (zero-padded heads)."""
    *lead, bs, hkv = s.shape
    hp = kv_scale_pad(hkv)
    out = jnp.zeros((*lead, hp, bs), jnp.float32)
    return out.at[..., :hkv, :].set(
        jnp.swapaxes(s.astype(jnp.float32), -1, -2))


def pool_scales_to_rows(s: jnp.ndarray, hkv: int) -> jnp.ndarray:
    """Kernel layout [..., Hkv_pad, bs] -> row-major [..., bs, Hkv]."""
    return jnp.swapaxes(s[..., :hkv, :], -1, -2)


def quantized_forward(qparams: Dict[str, Any], tokens: jnp.ndarray,
                      cfg: TransformerConfig, **kw) -> Tuple[jnp.ndarray, Any]:
    """forward() over a quantize_params tree (training-free serving)."""
    return forward(qparams, tokens, cfg, layers_hook=dequant_hook(cfg), **kw)
