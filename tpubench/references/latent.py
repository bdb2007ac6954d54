"""The plain reference of the ``latent`` family: latent attention with a
learned key selector on the global layers, windowed latent attention
between them, a head-wise output gate, and one chip's share of a wide
expert layer. Written from the configuration's published keys
(``configs/dots3-note-prev-l5-ep8.json``) and the publications that
define what two of them name:

  h = RMSNorm(x), eps rms_norm_eps; pre-norm residual blocks; untied
  head; final RMSNorm.
  Full layer   c_q = a_q RMSNorm(h W_qa); q = c_q W_qb -> H x (nope + rope),
               rotary (rope_theta) on the rope part; [c_kv ; k_r] = h W_kva,
               c_kv = a_kv RMSNorm(c_kv), rotary on k_r, shared by all
               heads; k_nope = c_kv W_kb, v = c_kv W_vb; score
               (q_nope.k_nope + q_rope.k_r) / sqrt(nope + rope), causal.
               a_q = sqrt(hidden / q_lora_rank), a_kv = sqrt(hidden /
               kv_lora_rank) (apply_mla_qkv_lora_rescale, after LongCat-
               Flash's mla_scale_q_lora / mla_scale_kv_lora).
  Selector     (full layers; the indexer of DeepSeek-V3.2) q_I = c_q W_Iq
               -> IH x ID, k_I = LayerNorm(h W_Ik), rotary on the first
               qk_rope_head_dim of each, w = h W_Iw / sqrt(IH ID);
               I[t,s] = sum_j w[t,j] relu(q_I[t,j] . k_I[s]), s <= t;
               position t attends the index_topk positions of largest
               I[t,.], all of them while t < index_topk.
  Sliding      the same latent attention at the swa_* sizes, no selector,
               keys t - (sliding_window_size - 1) .. t.
  Gate         g = sigmoid(h W_g), a scalar a head (attention_gate_type
               headwise, arXiv:2505.06708); out = concat(g_h o_h) W_o.
  FFN          the first first_k_dense_replace layers: SwiGLU. After
               them: s = sigmoid(h W_r); chosen = top-k of s + b
               (noaux_tc, no groups); weight s_i / sum_chosen s times
               routed_scaling_factor; y = sum over chosen AND held of
               weight_i E_i(h), plus the shared expert.

The share: a sparse layer's ``w_gate`` / ``w_up`` / ``w_down`` hold the
experts this chip holds (the configuration's ``n_routed_experts``), its
router every expert (``router_width``); ``expert_share`` says which. What the other chips'
experts would add is left out, here as in the program.

float32 ``jax.numpy`` under ``default_matmul_precision("highest")``; no
cache, no kernel, no batching, nothing imported from ``tpushare.models``
or ``tpushare.ops``. Keys and values are expanded to heads (the program
runs the absorbed form); attention runs a block of heads and a block of
queries at a time, one layer or expert upcast at a time, so 4,096
positions x 128 heads fit beside the served weights. The weights come
as served (bf16, a dict a layer, W_kb and W_vb stored a head: a storage
layout and no arithmetic). Rotary rotates the pairs (i, i + d/2).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp

_F32 = jnp.float32
FULL = "full_attention"
HEAD_BLOCK = 16
QUERY_BLOCK = 256


def _rms(x, w, eps, scale=1.0):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * w.astype(_F32) * scale


def _rotate(x, pos, theta: float):
    """x [S, H, D] at positions pos [S]."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=_F32) / half)
    ang = pos.astype(_F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention_dims(config: Dict[str, Any], kind: str) -> Dict[str, Any]:
    p = "" if kind == FULL else "swa_"
    return {"H": config[p + "num_attention_heads"],
            "q_rank": config[p + "q_lora_rank"],
            "kv_rank": config[p + "kv_lora_rank"],
            "nope": config[p + "qk_nope_head_dim"],
            "rope": config[p + "qk_rope_head_dim"],
            "v": config[p + "v_head_dim"],
            "theta": float(config[p + "rope_theta"])}


@functools.partial(jax.jit, static_argnames=("scale",))
def _attend_block(q, k, v, keep, *, scale):
    """q [Q, h, d], k [S, h, d], v [S, h, dv], keep [Q, S] -> [Q, h, dv]."""
    s = jnp.einsum("qhd,khd->hqk", q, k) * scale
    s = jnp.where(keep[None], s, -jnp.inf)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)


@jax.jit
def _selector_scores(qi, wi, ki):
    """I [Q, S] for a block of queries."""
    return jnp.einsum("qj,qjs->qs", wi,
                      jax.nn.relu(jnp.einsum("qjd,sd->qjs", qi, ki)))


@functools.partial(jax.jit, static_argnames=("topk",))
def _selected(I, qpos, *, topk):
    """(keep [Q, S], margin [Q]) from selector scores I [Q, S] of queries
    at positions qpos: the topk largest among s <= t (all while there are
    no more than topk), and the gap between the last kept and the first
    dropped over the spread of the row's scores (infinite where nothing
    is dropped)."""
    S = I.shape[1]
    causal = jnp.arange(S)[None, :] <= qpos[:, None]
    if S <= topk:
        return causal, jnp.full(qpos.shape, jnp.inf, _F32)
    masked = jnp.where(causal, I, -jnp.inf)
    vals, idx = jax.lax.top_k(masked, topk + 1)
    keep = jnp.zeros(I.shape, bool).at[
        jnp.arange(I.shape[0])[:, None], idx[:, :topk]].set(True) & causal
    n = jnp.sum(causal, axis=1)
    mean = jnp.sum(jnp.where(causal, I, 0.0), axis=1) / n
    std = jnp.sqrt(jnp.sum(jnp.where(causal, jnp.square(I - mean[:, None]),
                                     0.0), axis=1) / n)
    margin = jnp.where(n > topk, (vals[:, topk - 1] - vals[:, topk]) / std,
                       jnp.inf)
    return keep, margin


@functools.partial(jax.jit, static_argnames=("topk",))
def _adopt(I, qpos, own, theirs, band, *, topk):
    """The selection a block of queries attends when the program's is
    known: the reference's ``own`` [Q, S], but for the keys whose score
    lies within ``band`` (in units of the spread of the row's scores) of
    the line between the last kept and the first dropped, which are the
    program's (``theirs`` [Q, S]) to choose: rounding decides those, and
    no arithmetic can be held to which side they fall. Every other key is
    the reference's, so a program that keeps or drops one of those is
    compared with a reference that did not. Also a query's [keys taken
    from the program against the reference's own choice, disagreements
    outside the band (not taken), the farthest disagreement from the
    line]; a query that drops nothing has no line, and every
    disagreement there is outside."""
    S = I.shape[1]
    causal = jnp.arange(S)[None, :] <= qpos[:, None]
    differ = (theirs != own) & (causal | theirs)
    if S <= topk:
        dist = jnp.full(I.shape, jnp.inf, _F32)
    else:
        masked = jnp.where(causal, I, -jnp.inf)
        vals, _ = jax.lax.top_k(masked, topk + 1)
        n = jnp.sum(causal, axis=1)
        mean = jnp.sum(jnp.where(causal, I, 0.0), axis=1) / n
        std = jnp.sqrt(jnp.sum(jnp.where(
            causal, jnp.square(I - mean[:, None]), 0.0), axis=1) / n)
        line = (vals[:, topk - 1] + vals[:, topk]) / 2
        dist = jnp.where((n > topk)[:, None] & causal,
                         jnp.abs(I - line[:, None]) / std[:, None], jnp.inf)
    near = dist <= band
    adopted = jnp.where(near, theirs, own)
    stats = jnp.stack([
        jnp.sum(differ & near, axis=1).astype(_F32),
        jnp.sum(differ & ~near, axis=1).astype(_F32),
        jnp.max(jnp.where(differ, dist, 0.0), axis=1)], axis=1)
    return adopted, stats


def _attention(h, w, config, kind: str, kept=None, band: float = 0.0):
    """(attention output [S, hidden], selector margin [S], selection
    report [S, 3] or None) of one layer from its normed input h
    [S, hidden] and its weights w (upcast by the caller). ``kept``
    [S, S]: the keys the program's selector kept on this layer
    (``_adopt``); with it the margin is infinite: no tie is left to
    excuse."""
    d = attention_dims(config, kind)
    S, hidden = h.shape
    H, nope, rope = d["H"], d["nope"], d["rope"]
    eps = float(config["rms_norm_eps"])
    rescale = bool(config.get("apply_mla_qkv_lora_rescale"))
    aq = math.sqrt(hidden / d["q_rank"]) if rescale else 1.0
    akv = math.sqrt(hidden / d["kv_rank"]) if rescale else 1.0
    pos = jnp.arange(S)
    cq = _rms(h @ w["w_qa"], w["q_norm"], eps, aq)
    q = (cq @ w["w_qb"]).reshape(S, H, nope + rope)
    q = jnp.concatenate(
        [q[..., :nope], _rotate(q[..., nope:], pos, d["theta"])], -1)
    kv = h @ w["w_kva"]
    ckv = _rms(kv[:, :d["kv_rank"]], w["kv_norm"], eps, akv)
    kr = _rotate(kv[:, None, d["kv_rank"]:], pos, d["theta"])    # [S, 1, rope]
    k = jnp.concatenate([jnp.einsum("sc,hdc->shd", ckv, w["w_kb"]),
                         jnp.broadcast_to(kr, (S, H, rope))], -1)
    v = jnp.einsum("sc,hcv->shv", ckv, w["w_vb"])
    gate = jax.nn.sigmoid(h @ w["w_g"])                           # [S, H]

    margin = jnp.full((S,), jnp.inf, _F32)
    if kind == FULL:
        IH, ID = config["index_n_heads"], config["index_head_dim"]
        qi = (cq @ w["w_iq"]).reshape(S, IH, ID)
        qi = jnp.concatenate(
            [_rotate(qi[..., :rope], pos, d["theta"]), qi[..., rope:]], -1)
        ki = h @ w["w_ik"]
        mu = jnp.mean(ki, -1, keepdims=True)
        ki = ((ki - mu) / jnp.sqrt(jnp.mean(jnp.square(ki - mu), -1,
                                            keepdims=True) + eps)
              * w["ik_norm_w"] + w["ik_norm_b"])
        ki = jnp.concatenate(
            [_rotate(ki[:, None, :rope], pos, d["theta"])[:, 0],
             ki[:, rope:]], -1)
        wi = h @ w["w_iw"] / math.sqrt(IH * ID)
    else:
        window = config["sliding_window_size"]

    out, report = [], []
    for q0 in range(0, S, QUERY_BLOCK):
        qs = slice(q0, min(S, q0 + QUERY_BLOCK))
        if kind == FULL:
            I = _selector_scores(qi[qs], wi[qs], ki)
            keep, m = _selected(I, pos[qs], topk=config["index_topk"])
            if kept is None:
                margin = margin.at[qs].set(m)
            else:
                keep, stats = _adopt(I, pos[qs], keep, jnp.asarray(kept[qs]),
                                     band, topk=config["index_topk"])
                report.append(stats)
        else:
            keep = ((pos[None, :] <= pos[qs, None])
                    & (pos[None, :] > pos[qs, None] - window))
        heads = [_attend_block(q[qs, h0:h0 + HEAD_BLOCK],
                               k[:, h0:h0 + HEAD_BLOCK],
                               v[:, h0:h0 + HEAD_BLOCK], keep,
                               scale=1.0 / math.sqrt(nope + rope))
                 for h0 in range(0, H, HEAD_BLOCK)]
        out.append(jnp.concatenate(heads, axis=1))
    o = jnp.concatenate(out, axis=0) * gate[..., None]
    return (o.reshape(S, H * d["v"]) @ w["w_o"], margin,
            jnp.concatenate(report) if report else None)


@jax.jit
def _swiglu(h, w_gate, w_up, w_down):
    g = h @ w_gate.astype(_F32)
    u = h @ w_up.astype(_F32)
    return (jax.nn.silu(g) * u) @ w_down.astype(_F32)


@functools.partial(jax.jit, static_argnames=("top_k", "offset", "held",
                                             "scale"))
def _route(h, router, bias, *, top_k, offset, held, scale):
    """(mix [S, held]: the weight of each held expert, 0 where it is not
    chosen; margin [S]: the gap between the last chosen and the first
    unchosen of s + b over the spread of the position's s + b, counted
    only where a held expert is on either side)."""
    s = jax.nn.sigmoid(h @ router.astype(_F32))
    sel = s + bias.astype(_F32)
    _, idx = jax.lax.top_k(sel, top_k + 1)
    chosen = idx[:, :top_k]
    ws = jnp.take_along_axis(s, chosen, axis=1)
    ws = ws / jnp.sum(ws, axis=1, keepdims=True) * scale
    E = router.shape[-1]
    mix = jnp.sum(jax.nn.one_hot(chosen, E, dtype=_F32) * ws[..., None], 1)
    ranked = jnp.take_along_axis(sel, idx, axis=1)
    gap = (ranked[:, top_k - 1] - ranked[:, top_k]) / jnp.std(sel, axis=1)
    edge = idx[:, top_k - 1:top_k + 1]
    ours = jnp.any((edge >= offset) & (edge < offset + held), axis=1)
    return mix[:, offset:offset + held], jnp.where(ours, gap, jnp.inf)


def expert_offset(config: Dict[str, Any], held: int) -> int:
    return int(config.get("expert_share", {}).get("index", 0)) * held


ATTENTION_KEYS = ("ln1", "w_qa", "q_norm", "w_qb", "w_kva", "kv_norm", "w_kb",
                  "w_vb", "w_g", "w_o", "w_iq", "w_ik", "ik_norm_w",
                  "ik_norm_b", "w_iw")


def router_margin_scale(config: Dict[str, Any]) -> float:
    """What a router's relative gap is multiplied by before it is held
    against ``reference.ROUTER_TIE_MARGIN``. That line was set where a
    flip exchanges one of TWO chosen experts, half of the layer's routed
    output. Here it exchanges one of ``num_experts_per_tok``, so the same
    gap moves the output that many halves less and is counted that much
    wider."""
    return config["num_experts_per_tok"] / 2


def forward_with_margins(params: Dict[str, Any], tokens: Sequence[int],
                         config: Dict[str, Any], kept=None,
                         band: float = 0.0, report=None):
    """(logits [S, vocab], margins [S]) in float32 for one unbatched
    sequence. A position's margin is the least, over the sparse layers,
    of its router's gap (``_route``) times ``router_margin_scale`` and,
    over the full layers, of its selector's gap (``_selected``), as it
    is: among thousands of keys the last kept and the first dropped lie
    closer than any rounding resolves, so alone the reference can hold no
    position that drops a key. ``kept`` (a [S, S] mask a full layer: the
    keys the program's selector kept) makes the selection the program's
    within ``band`` of the line and the reference's beyond it
    (``_adopt``), and such a position is held like any other; ``report``
    (a list) then takes ``_adopt``'s numbers [S, 3] a full layer."""
    eps = float(config["rms_norm_eps"])
    n_dense = config["first_k_dense_replace"]
    kinds = config["layer_types"][:config["num_hidden_layers"]]
    kept = iter(kept) if kept is not None else None
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens, jnp.int32)].astype(_F32)
        margins = jnp.full(x.shape[:1], jnp.inf, _F32)
        for i, (kind, f) in enumerate(zip(kinds, params["layers"])):
            w = {k: f[k].astype(_F32) for k in ATTENTION_KEYS if k in f}
            o, m, stats = _attention(
                _rms(x, w["ln1"], eps), w, config, kind,
                next(kept) if kept is not None and kind == FULL else None,
                band)
            if stats is not None and report is not None:
                report.append(stats)
            margins = jnp.minimum(margins, m)
            x = x + o
            h = _rms(x, f["ln2"], eps)
            if i < n_dense:
                x = x + _swiglu(h, f["w_gate"], f["w_up"], f["w_down"])
                continue
            held = f["w_gate"].shape[0]
            mix, m = _route(h, f["router"], f["router_bias"],
                            top_k=config["num_experts_per_tok"],
                            offset=expert_offset(config, held), held=held,
                            scale=float(config["routed_scaling_factor"]))
            margins = jnp.minimum(margins, m * router_margin_scale(config))
            y = _swiglu(h, f["ws_gate"], f["ws_up"], f["ws_down"])
            for e in range(held):           # one expert upcast at a time
                y = y + mix[:, e:e + 1] * _swiglu(
                    h, f["w_gate"][e], f["w_up"][e], f["w_down"][e])
            x = x + y
        x = _rms(x, params["final_norm"].astype(_F32), eps)
        return x @ params["unembed"].astype(_F32), margins


def forward(params, tokens, config):
    return forward_with_margins(params, tokens, config)[0]
