"""The plain reference of the ``mla_mtp`` family: latent attention (MLA)
over every cached position on every layer, a norm on each sublayer's
output before the residual add, one chip's share of a wide expert layer,
and a multi-token-prediction module. Written from the published keys of
``configs/openpangu-ultra-l5-ep32.json`` and the publications that say
what three of them name (``sandwich_norm``: Pangu Ultra, arXiv:2504.07866
and 2505.04519; ``num_nextn_predict_layers``: DeepSeek-V3,
arXiv:2412.19437, section 2.2; the absorbed-free form of MLA:
DeepSeek-V2, arXiv:2405.04434):

  N is RMSNorm with its own weight, eps rms_norm_eps.
  Layer   u = x + N_post_attn(Attn(N_in(x)));
          y = u + N_post_mlp(FFN(N_pre_mlp(u)))
  Attn    c_q = N(h W_qa); q = c_q W_qb -> H x (nope + rope), rotary
          (rope_theta, no scaling) on the rope part; [c_kv ; k_r] = h W_kva,
          c_kv = N(c_kv), rotary on k_r, one for all heads;
          k_h = [c_kv W_kb,h ; k_r], v_h = c_kv W_vb,h; causal softmax of
          q_h . k_h / sqrt(nope + rope) over every position;
          out = concat_h(o_h) W_o. No gate, no selector, no rescale.
  FFN     the first first_k_dense_replace layers: SwiGLU. After them:
          s = sigmoid(h W_r) over router_width; the num_experts_per_tok
          largest s (no bias, no groups); weights s_e / sum(chosen s) x
          routed_scaling_factor; y = sum over chosen AND held of
          weight_e E_e(h), plus the shared expert, unweighted.
  Head    logits = N_final(x_L) W_head (untied).
  MTP     h'_i = [N_e(Emb(t_{i+1})) ; N_h(x_L,i)] W_eh, x_L,i the last main
          layer's output at position i before the final norm; one layer
          of the expert kind over h'_0 .. h'_i (its own keys and values);
          logits = N_mtp(.) W_head with the model's embedding and head: a
          guess at t_{i+2}.

The share: a sparse layer's ``w_gate`` / ``w_up`` / ``w_down`` hold the
experts this chip holds (the configuration's ``n_routed_experts``), its
router every expert (``router_width``); ``expert_share`` says which. What
the other chips' experts would add is left out, here as in the program.

float32 ``jax.numpy`` under ``default_matmul_precision("highest")``; no
cache, no kernel, no batching, nothing imported from ``tpushare``. Keys
and values are expanded to heads (the program runs the absorbed form);
attention runs a block of heads and a block of queries at a time and one
expert is upcast at a time, so prompts of 300 tokens and of thousands fit
beside the served weights. The weights come as served (bf16, a dict a
layer, W_kb and W_vb stored a head: a storage layout and no arithmetic).
Rotary rotates the pairs (i, i + d/2).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp

# RMSNorm, rotary on pairs (i, i + d/2) and SwiGLU are the other latent
# reference's, float32 ``jax.numpy`` like everything here
from tpubench.references.latent import _rms, _rotate, _swiglu

_F32 = jnp.float32
HEAD_BLOCK = 16
QUERY_BLOCK = 256


@functools.partial(jax.jit, static_argnames=("scale",))
def _attend_block(q, k, v, q0, *, scale):
    """q [Q, h, d] at positions q0.., k [S, h, d], v [S, h, dv], causal
    -> [Q, h, dv]."""
    s = jnp.einsum("qhd,khd->hqk", q, k) * scale
    keep = (jnp.arange(k.shape[0])[None, :]
            <= q0 + jnp.arange(q.shape[0])[:, None])
    s = jnp.where(keep[None], s, -jnp.inf)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)


def _attention(h, w, config):
    """Attention output [S, hidden] of one layer from its normed input
    h [S, hidden] and its weights w (float32)."""
    c = config
    S = h.shape[0]
    H, nope, rope = (c["num_attention_heads"], c["qk_nope_head_dim"],
                     c["qk_rope_head_dim"])
    rank, theta = c["kv_lora_rank"], float(c["rope_theta"])
    eps = float(c["rms_norm_eps"])
    pos = jnp.arange(S)
    cq = _rms(h @ w["w_qa"], w["q_norm"], eps)
    q = (cq @ w["w_qb"]).reshape(S, H, nope + rope)
    q = jnp.concatenate(
        [q[..., :nope], _rotate(q[..., nope:], pos, theta)], -1)
    kv = h @ w["w_kva"]
    ckv = _rms(kv[:, :rank], w["kv_norm"], eps)
    kr = _rotate(kv[:, None, rank:], pos, theta)                # [S, 1, rope]
    k = jnp.concatenate([jnp.einsum("sc,hdc->shd", ckv, w["w_kb"]),
                         jnp.broadcast_to(kr, (S, H, rope))], -1)
    v = jnp.einsum("sc,hcv->shv", ckv, w["w_vb"])
    out = []
    for q0 in range(0, S, QUERY_BLOCK):
        qs = slice(q0, min(S, q0 + QUERY_BLOCK))
        out.append(jnp.concatenate(
            [_attend_block(q[qs, h0:h0 + HEAD_BLOCK],
                           k[:, h0:h0 + HEAD_BLOCK], v[:, h0:h0 + HEAD_BLOCK],
                           q0, scale=1.0 / math.sqrt(nope + rope))
             for h0 in range(0, H, HEAD_BLOCK)], axis=1))
    return jnp.concatenate(out, axis=0).reshape(S, -1) @ w["w_o"]


@functools.partial(jax.jit, static_argnames=("top_k", "offset", "held",
                                             "scale"))
def _route(h, router, *, top_k, offset, held, scale):
    """(mix [S, held]: the weight of each held expert, 0 where it is not
    chosen; margin [S]: the gap between the last chosen and the first
    unchosen score over the spread of the position's scores, counted
    only where a held expert is on either side)."""
    s = jax.nn.sigmoid(h @ router.astype(_F32))
    _, idx = jax.lax.top_k(s, top_k + 1)
    chosen = idx[:, :top_k]
    ws = jnp.take_along_axis(s, chosen, axis=1)
    ws = ws / jnp.sum(ws, axis=1, keepdims=True) * scale
    E = router.shape[-1]
    mix = jnp.sum(jax.nn.one_hot(chosen, E, dtype=_F32) * ws[..., None], 1)
    ranked = jnp.take_along_axis(s, idx, axis=1)
    gap = (ranked[:, top_k - 1] - ranked[:, top_k]) / jnp.std(s, axis=1)
    edge = idx[:, top_k - 1:top_k + 1]
    ours = jnp.any((edge >= offset) & (edge < offset + held), axis=1)
    return mix[:, offset:offset + held], jnp.where(ours, gap, jnp.inf)


def expert_offset(config: Dict[str, Any], held: int) -> int:
    return int(config.get("expert_share", {}).get("index", 0)) * held


def router_margin_scale(config: Dict[str, Any]) -> float:
    """What a router's relative gap is multiplied by before it is held
    against ``reference.ROUTER_TIE_MARGIN``: that line was set where a
    flip exchanges one of TWO chosen experts; here it exchanges one of
    ``num_experts_per_tok``, so the same gap moves the output that many
    halves less (as ``references/latent.py``)."""
    return config["num_experts_per_tok"] / 2


ATTENTION_KEYS = ("w_qa", "q_norm", "w_qb", "w_kva", "kv_norm", "w_kb",
                  "w_vb", "w_o")


def _layer(x, f, config, dense: bool):
    """(the layer's output [S, hidden], its router's margin [S]) from its
    input x and its weights f as served."""
    c = config
    eps = float(c["rms_norm_eps"])
    post = bool(c.get("sandwich_norm"))
    w = {k: f[k].astype(_F32) for k in ATTENTION_KEYS}
    o = _attention(_rms(x, f["ln1"], eps), w, c)
    x = x + (_rms(o, f["ln1_post"], eps) if post else o)
    h = _rms(x, f["ln2"], eps)
    margin = jnp.full(x.shape[:1], jnp.inf, _F32)
    if dense:
        y = _swiglu(h, f["w_gate"], f["w_up"], f["w_down"])
    else:
        held = f["w_gate"].shape[0]
        mix, margin = _route(h, f["router"], top_k=c["num_experts_per_tok"],
                             offset=expert_offset(c, held), held=held,
                             scale=float(c["routed_scaling_factor"]))
        y = _swiglu(h, f["ws_gate"], f["ws_up"], f["ws_down"])
        for e in range(held):               # one expert upcast at a time
            y = y + mix[:, e:e + 1] * _swiglu(
                h, f["w_gate"][e], f["w_up"][e], f["w_down"][e])
    return x + (_rms(y, f["ln2_post"], eps) if post else y), margin


def forward_all(params: Dict[str, Any], tokens: Sequence[int],
                config: Dict[str, Any], module: bool = True):
    """{"logits" [S, vocab], "margins" [S], and with ``module`` (where the
    weights hold one) "mtp_logits" [S - 1, vocab], "mtp_margins" [S - 1]}
    in float32 for one unbatched sequence. ``mtp_logits[i]`` is the
    module's guess at token i + 2 from the main layers' output at i and
    token i + 1. A position's margin is the least of its routers' gaps
    (``_route``) times ``router_margin_scale``; the module's counts its
    own router and the main layers' at that position."""
    c = config
    eps = float(c["rms_norm_eps"])
    scale = router_margin_scale(c)
    toks = jnp.asarray(tokens, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][toks].astype(_F32)
        margins = jnp.full(x.shape[:1], jnp.inf, _F32)
        for i in range(c["num_hidden_layers"]):
            x, m = _layer(x, params["layers"][i], c,
                          i < c["first_k_dense_replace"])
            margins = jnp.minimum(margins, m * scale)
        head = params["unembed"].astype(_F32)
        out = {"logits": _rms(x, params["final_norm"], eps) @ head,
               "margins": margins}
        if module and "mtp" in params and len(tokens) > 1:
            f = params["mtp"][0]
            e = params["embed"][toks[1:]].astype(_F32)
            h = jnp.concatenate([_rms(e, f["enorm"], eps),
                                 _rms(x[:-1], f["hnorm"], eps)], -1)
            y, m = _layer(h @ f["w_eh"].astype(_F32), f, c, False)
            out["mtp_logits"] = _rms(y, f["final_norm"], eps) @ head
            out["mtp_margins"] = jnp.minimum(margins[:-1], m * scale)
        return out


def forward_with_margins(params: Dict[str, Any], tokens: Sequence[int],
                         config: Dict[str, Any]):
    """(logits [S, vocab], margins [S]): what ``system.check_correct``
    compares (the main model; the module's logits are ``forward_all``'s)."""
    out = forward_all(params, tokens, config, module=False)
    return out["logits"], out["margins"]


def forward(params, tokens, config):
    return forward_with_margins(params, tokens, config)[0]
