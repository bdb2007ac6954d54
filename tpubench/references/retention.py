"""The plain reference of the ``retention`` family: the Qwen3-14B block
Brumby-14B-Base was retrained from, with power retention (degree 2) in
place of softmax attention. Written from the configuration's published
keys (``configs/brumby14b-l8.json``) and, for what the config does not
carry (its ``assumed``), from power attention (arXiv:2507.04239) and the
release's description of the gated retention call:

  x = RMSNorm(h), eps rms_norm_eps; pre-norm residual blocks; untied
  head; final RMSNorm.
  q = x W_q -> [T, H, D]   k = x W_k -> [T, Hkv, D]   v = x W_v -> [T, Hkv, D]
  q, k <- RMSNorm over each head's D (w_qn, w_kn), then rotary (rope_theta,
          all D, pairs (i, i + D/2))
  log g = logsigmoid(x W_g + b_g) -> [T, Hkv]
  for query head i of key-value head j = i // (H / Hkv), s <= t:
      a[t, s] = (c q_i[t] . k_j[s])^2 exp(sum_{r = s+1 .. t} log g_j[r])
      o_i[t]  = sum_s a[t, s] v_j[s] / (sum_s a[t, s] + eps)
  h <- h + concat_i(o_i) W_o ;   h <- h + SwiGLU(RMSNorm(h))

with c = 1 / D and eps = ``EPS``, at that scale. This is the quadratic
form: no feature map, no chunks, no state. The program computes the same
thing as a recurrence over a state (``tpushare/models/retention.py``).

float32 ``jax.numpy`` under ``default_matmul_precision("highest")``; no
cache, no kernel, no batching, nothing imported from ``tpushare``. The
[T, T] weights of one head are never whole: a block of ``Q_BLOCK``
queries and ``HEAD_BLOCK`` query heads at a time, one layer upcast at a
time, so 2,048 positions x 40 heads fit beside the served weights. The
weights come as served (bf16, a dict a layer: a storage layout and no
arithmetic).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp

from tpubench.reference import _rms, _rotate

_F32 = jnp.float32
#: the normaliser's eps, at the scale of (q.k / D)^2 (``assumed.eps``)
EPS = 1e-6
Q_BLOCK = 512
HEAD_BLOCK = 8
VOCAB_BLOCK = 16384


def retention(q, k, v, log_g, eps: float = EPS):
    """q [T, H, D], k, v [T, Hkv, D], log_g [T, Hkv] -> o [T, H, D], by
    the quadratic form, a block of queries and heads at a time."""
    T, H, D = q.shape
    Hkv = k.shape[1]
    G = H // Hkv
    run = jnp.cumsum(log_g, axis=0)                 # [T, Hkv]
    k, v, run = (jnp.repeat(a, G, axis=1) for a in (k, v, run))
    c = 1.0 / D
    s_at = jnp.arange(T)
    out = []
    for h0 in range(0, H, HEAD_BLOCK):
        hs = slice(h0, h0 + HEAD_BLOCK)
        rows = []
        for t0 in range(0, T, Q_BLOCK):
            ts = slice(t0, min(T, t0 + Q_BLOCK))
            score = jnp.einsum("thd,shd->hts", q[ts, hs], k[:, hs]) * c
            decay = run[ts, hs].T[:, :, None] - run[:, hs].T[:, None, :]
            seen = s_at[None, ts, None] >= s_at[None, None, :]
            a = score ** 2 * jnp.exp(jnp.where(seen, decay, -jnp.inf))
            num = jnp.einsum("hts,shd->thd", a, v[:, hs])
            rows.append(num / (a.sum(-1).T[:, :, None] + eps))
        out.append(jnp.concatenate(rows, axis=0))
    return jnp.concatenate(out, axis=1)


def _layer(x, w, config: Dict[str, Any]):
    T = x.shape[0]
    H, Hkv = config["num_attention_heads"], config["num_key_value_heads"]
    D, eps = config["head_dim"], float(config["rms_norm_eps"])
    theta = float(config["rope_theta"])
    f = {k: a.astype(_F32) for k, a in w.items()}
    h = _rms(x, f["ln1"], eps)
    q = _rotate(_rms((h @ f["wq"]).reshape(T, H, D), f["q_norm"], eps), theta)
    k = _rotate(_rms((h @ f["wk"]).reshape(T, Hkv, D), f["k_norm"], eps),
                theta)
    v = (h @ f["wv"]).reshape(T, Hkv, D)
    log_g = jax.nn.log_sigmoid(h @ f["wg"] + f["bg"])
    x = x + retention(q, k, v, log_g).reshape(T, H * D) @ f["wo"]
    h = _rms(x, f["ln2"], eps)
    return x + (jax.nn.silu(h @ f["w_gate"]) * (h @ f["w_up"])) @ f["w_down"]


@functools.partial(jax.jit, static_argnames=("frozen",))
def _layer_jit(x, w, frozen):
    return _layer(x, w, dict(frozen))


def forward(params, tokens: Sequence[int], config: Dict[str, Any]):
    """Logits [T, vocab] in float32 for one unbatched sequence."""
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "rope_theta")
    frozen = tuple((k, config[k]) for k in keys)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens, jnp.int32)].astype(_F32)
        for w in params["layers"]:
            x = _layer_jit(x, w, frozen)
        x = _rms(x, params["final_norm"], float(config["rms_norm_eps"]))
        # the head a block of the vocabulary at a time: the float32 copy
        # of all of it (3.1 GB at 151,936 x 5,120) has no room beside the
        # served weights and the state
        head = params["unembed"]
        return jnp.concatenate(
            [x @ head[:, v0:v0 + VOCAB_BLOCK].astype(_F32)
             for v0 in range(0, head.shape[1], VOCAB_BLOCK)], axis=1)


def forward_with_margins(params, tokens: Sequence[int],
                         config: Dict[str, Any]):
    """(logits [T, vocab], margins [T]): nothing routes, so no position
    is ever excused."""
    logits = forward(params, tokens, config)
    return logits, jnp.full((logits.shape[0],), jnp.inf, _F32)
