"""What every family's check shares, and the plain reference of two.

``relative_error``, ``verdict``, the router-tie margin and the check's
default sizes are every family's. The equations below are those of
``families/dense.py`` and ``families/moe.py``, which re-export
``forward_with_margins``; another family brings its own (in its file
under ``families/``, or in one it imports).

A float32 ``jax.numpy`` forward of the Mistral block and of the Mixtral
block, written from the published descriptions (Mistral 7B,
arXiv:2310.06825; Mixtral of Experts, arXiv:2401.04088; and the
``modeling_mistral`` / ``modeling_mixtral`` equations they ship with):

  h   = x + Attn(RMSNorm(x))        rotary GQA, causal, softmax in f32
  out = h + FFN(RMSNorm(h))         SwiGLU: W_down(silu(W_gate h) * W_up h)
  Mixtral's FFN: p = softmax(W_router h) over all experts; the top-k
  experts by p, their weights renormalised to sum to 1; the weighted
  sum of those experts' SwiGLU outputs.
  logits = W_head RMSNorm(x_L)

No cache, no kernel, no batching, no import from ``tpushare.models`` or
``tpushare.ops``: every position attends the whole prefix in one pass,
under ``default_matmul_precision("highest")`` (on a TPU a float32
matmul otherwise runs in bf16 passes). Rotary embedding rotates the
pairs (i, i + d/2), the layout of the released checkpoints.

It takes the weights as they are served (bf16, the program's stacked
layout, which is a storage layout and no arithmetic) and upcasts one
layer at a time, one expert at a time: the float32 tree does not fit
beside the served one.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp

# A family's own limits, and what they were set from, are in
# ``families/<family>.py``. The check compares relative error against the
# largest |logit| of the reference. Where a model routes, a token whose last chosen and first
# unchosen router logits are closer than bf16 can tell takes another
# expert than the float32 reference does. That is no error of the
# system, and it moves that position's logits by a quarter of their
# scale and more. So a reference returns, for every position, the
# smallest such gap over the layers, in units of the spread of that
# position's router logits (infinite where nothing routes), and a
# checked position under ROUTER_TIE_MARGIN is held only to TIE_TOLERANCE
# (finite, the right scale); every other checked position is held to
# the family's tolerance, each one, no median. The margins of 36
# positions of the 8-expert top-2 cell (six seeds, all six flips among
# them; v5e, PR 22), computed afterwards by this file on the CPU from
# the same seeds: the six that flipped 0.0029 to 0.0226, those that did
# not 0.0023 and up, the two kinds mixed below 0.04 (PERF.md, Findings).
# The margin is 3.5 times the largest that flipped; it excuses about
# half of all positions there, so the check takes prompts until the
# family's HELD_POSITIONS are held, MAX_CHECK_PROMPTS at most.
ROUTER_TIE_MARGIN = 8e-2
TIE_TOLERANCE = 1.0
MAX_CHECK_PROMPTS = 12
#: The check's prompt length where ``configs/<name>.json`` gives no
#: ``check_prompt_tokens`` of its own (``system.check_tokens``).
CHECK_PROMPT_TOKENS = 300

_F32 = jnp.float32


def _rms(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * w.astype(_F32)


def _rotate(x, theta: float):
    """x [S, H, D] at positions 0..S-1."""
    S, _, D = x.shape
    half = D // 2
    inv = theta ** (-jnp.arange(half, dtype=_F32) / half)
    ang = jnp.arange(S, dtype=_F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


@functools.partial(jax.jit, static_argnames=("n_heads", "n_kv", "theta", "eps"))
def _attention_half(x, ln, wq, wk, wv, wo, *, n_heads, n_kv, theta, eps):
    S, d = x.shape
    hd = wq.shape[-1] // n_heads
    h = _rms(x, ln, eps)
    q = (h @ wq.astype(_F32)).reshape(S, n_heads, hd)
    k = (h @ wk.astype(_F32)).reshape(S, n_kv, hd)
    v = (h @ wv.astype(_F32)).reshape(S, n_kv, hd)
    q, k = _rotate(q, theta), _rotate(k, theta)
    g = n_heads // n_kv
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(_F32(hd))
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v).reshape(S, n_heads * hd)
    return x + o @ wo.astype(_F32)


@jax.jit
def _swiglu(h, w_gate, w_up, w_down):
    g = h @ w_gate.astype(_F32)
    u = h @ w_up.astype(_F32)
    return (jax.nn.silu(g) * u) @ w_down.astype(_F32)


@functools.partial(jax.jit, static_argnames=("top_k",))
def _route(h, router, *, top_k):
    """The mixing weights [S, E], and each position's margin: the gap
    between the last router logit that is chosen and the first that is
    not, over the standard deviation of the position's router logits."""
    z = h @ router.astype(_F32)                                 # [S, E]
    p = jax.nn.softmax(z, axis=-1)
    w, idx = jax.lax.top_k(p, top_k)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    E = router.shape[-1]
    mix = jnp.sum(jax.nn.one_hot(idx, E, dtype=_F32) * w[..., None], 1)
    ranked = jnp.sort(z, axis=-1)[:, ::-1]
    margin = (ranked[:, top_k - 1] - ranked[:, top_k]) / jnp.std(z, axis=-1)
    return mix, margin


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm, w_head, *, eps):
    return _rms(x, norm, eps) @ w_head.astype(_F32)


def forward(params: Dict[str, Any], tokens: Sequence[int],
            config: Dict[str, Any]) -> jnp.ndarray:
    """Logits [S, vocab] in float32 for one unbatched sequence."""
    return forward_with_margins(params, tokens, config)[0]


def forward_with_margins(params: Dict[str, Any], tokens: Sequence[int],
                         config: Dict[str, Any]):
    """(logits [S, vocab], margins [S]) in float32 for one unbatched
    sequence; a position's margin is the smallest of its router margins
    over the layers (``_route``), infinite for a dense model.
    ``config`` holds the published keys (hidden_size, num_hidden_layers,
    num_attention_heads, num_key_value_heads, rms_norm_eps, rope_theta,
    and for Mixtral num_local_experts, num_experts_per_tok)."""
    eps = float(config["rms_norm_eps"])
    kw = dict(n_heads=config["num_attention_heads"],
              n_kv=config["num_key_value_heads"],
              theta=float(config["rope_theta"]), eps=eps)
    n_exp = config.get("num_local_experts", 0)
    lay = params["layers"]
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens, jnp.int32)].astype(_F32)
        margins = jnp.full(x.shape[:1], jnp.inf, _F32)
        for i in range(config["num_hidden_layers"]):
            x = _attention_half(x, lay["ln1"][i], lay["wq"][i], lay["wk"][i],
                                lay["wv"][i], lay["wo"][i], **kw)
            h = _rms(x, lay["ln2"][i], eps)
            if n_exp:
                mix, margin = _route(h, lay["router"][i],
                                     top_k=config["num_experts_per_tok"])
                margins = jnp.minimum(margins, margin)
                y = jnp.zeros_like(x)
                for e in range(n_exp):      # one expert upcast at a time
                    y = y + mix[:, e:e + 1] * _swiglu(
                        h, lay["w_gate"][i, e], lay["w_up"][i, e],
                        lay["w_down"][i, e])
            else:
                y = _swiglu(h, lay["w_gate"][i], lay["w_up"][i],
                            lay["w_down"][i])
            x = x + y
        w_head = (params["unembed"] if "unembed" in params
                  else params["embed"].T)
        return _head(x, params["final_norm"], w_head, eps=eps), margins


def relative_error(got, want) -> float:
    """max |got - want| over the largest |want|."""
    got = jnp.asarray(got, _F32)
    want = jnp.asarray(want, _F32)
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def verdict(errors: List[float], margins: List[float], finite: bool,
            tolerance: float, held_positions: int) -> Dict[str, Any]:
    """Every position the router does not excuse within ``tolerance``,
    every excused one within TIE_TOLERANCE, all finite, and
    ``held_positions`` or more held. Both numbers are the family's
    (``families/<family>.py``: ``tolerance(config)``, ``HELD_POSITIONS``)."""
    held = [e for e, m in zip(errors, margins) if m >= ROUTER_TIE_MARGIN]
    tied = [e for e, m in zip(errors, margins) if m < ROUTER_TIE_MARGIN]
    ok = (finite and len(held) >= held_positions
          and all(e <= tolerance for e in held)
          and all(e <= TIE_TOLERANCE for e in tied))
    return {"ok": bool(ok), "max_held_rel_err": max(held, default=None),
            "tolerance": tolerance, "held": len(held),
            "held_positions": held_positions, "router_ties": len(tied),
            "max_tied_rel_err": max(tied, default=None),
            "rel_errs": errors, "router_margins": margins, "finite": finite}
