"""Mixture-of-Experts transformer LM with expert parallelism.

Extends the dense decoder (models/transformer.py) with top-k routed
expert MLPs, sharded over the ``ep`` mesh axis. TPU-first choices:

- Two dispatch modes, both fully static-shaped: *dense* (one-hot
  combine weights, batched expert einsums — every local expert
  computes every token; simplest, MXU-only) and *grouped capacity*
  dispatch (capacity_factor set: scatter token ids into per-expert
  [E, C] queues, gather, compute, scatter-add — expert FLOPs shrink
  from E_local·T to E_local·C with Switch/GShard overflow dropping).
- Expert parallelism: each ep rank holds n_experts/ep experts and
  computes their contribution for ALL local tokens, then one psum over
  ``ep`` combines — no all_to_all needed for the dense formulation,
  and it composes with tp (each expert's hidden dim sharded over tp,
  psum over tp inside the expert block).
- Aux load-balance loss (Switch-style fraction·probability) keeps
  routing trainable.

The reference system schedules pods but has no model code (SURVEY.md
§2); MoE is part of the workload harness those pods run.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from tpushare.ops import apply_rotary, attention, rms_norm, rotary_embedding
from tpushare.models.spec import SpecDecodeMixin
from tpushare.models.transformer import ParallelCtx, _act
from tpushare.parallel.multihost import addressable_fetch, host_scalar
from tpushare.parallel.ring_attention import ring_attention


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    vocab_size: int = 32_000
    d_model: int = 2048
    n_layers: int = 12
    n_heads: int = 8
    n_kv_heads: int = 4
    head_dim: int = 256
    d_ff: int = 8192               # per-expert hidden dim
    n_experts: int = 8
    top_k: int = 2
    # None = dense dispatch (every local expert computes every token);
    # a float enables grouped capacity dispatch (_grouped_dispatch):
    # each expert processes at most ceil(tokens·top_k/n_experts·factor)
    # routed tokens via static-shape scatter/gather, overflow
    # assignments dropped in token order (Switch/GShard semantics).
    capacity_factor: Optional[float] = None
    # Expert-parallel combine strategy:
    # - "psum": tokens replicated across ep; every rank computes its
    #   local experts' contribution for ALL tokens and one psum([T,Dm])
    #   over ep combines. No token exchange; comm is O(T·Dm) per layer
    #   regardless of ep size — right for small ep meshes.
    # - "a2a" (requires capacity_factor): tokens SHARDED over ep (ep is
    #   a data axis); each rank routes its T/ep tokens, an all_to_all
    #   ships each routed token to the rank owning its expert, and a
    #   second all_to_all returns outputs. Comm is O(T·K/ep·Dm) per
    #   rank and routing/expert FLOPs divide by ep — the GShard
    #   scaling shape for large ep meshes.
    # - "expert_choice" (Zhou et al.): EXPERTS pick their top-C tokens
    #   by router score — perfect load balance by construction, no aux
    #   loss, no capacity tuning (C = ceil(T·K/E·factor)); a token may
    #   be picked by 0..E experts. Combines over ep like "psum".
    # - "dropless" (MegaBlocks-style): assignments sorted by expert and
    #   computed with lax.ragged_dot grouped GEMMs — EXACT MoE (no
    #   capacity, no drops) at the ideal T·K expert-FLOP count (dense
    #   dispatch costs E_local·T). Composes with ep like "psum"
    #   (non-local assignments sort past the group total, which
    #   ragged_dot zero-skips) and with tp (hidden dim sharded).
    routing: str = "psum"
    rope_base: float = 10_000.0
    rope_scaling: Optional[Tuple[float, float, float, float]] = None
    norm_eps: float = 1e-6
    act: str = "silu"
    aux_loss_weight: float = 0.01
    # True: the head is embed.T (the framework's own MoE LMs). False:
    # a separate [Dm, V] "unembed" leaf (converted Mixtral checkpoints
    # — HF Mixtral never ties; convert.moe_config_from_hf sets this).
    tie_embeddings: bool = True
    dtype: Any = jnp.bfloat16
    remat: bool = True

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim


def tiny(vocab_size: int = 256, d_model: int = 64, n_layers: int = 2,
         n_heads: int = 4, n_kv_heads: int = 2, head_dim: int = 16,
         d_ff: int = 128, n_experts: int = 4, top_k: int = 2,
         **kw) -> MoEConfig:
    return MoEConfig(vocab_size=vocab_size, d_model=d_model,
                     n_layers=n_layers, n_heads=n_heads,
                     n_kv_heads=n_kv_heads, head_dim=head_dim, d_ff=d_ff,
                     n_experts=n_experts, top_k=top_k, dtype=jnp.float32,
                     **kw)


def init_params(rng: jax.Array, cfg: MoEConfig) -> Dict[str, Any]:
    ks = jax.random.split(rng, 9)
    L, Dm, F, E = cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.n_experts

    def dense(key, shape, fan_in):
        return (jax.random.truncated_normal(key, -2, 2, shape, jnp.float32)
                / math.sqrt(fan_in)).astype(cfg.dtype)

    out = {
        "embed": dense(ks[0], (cfg.vocab_size, Dm), Dm),
        "layers": {
            "ln1": jnp.ones((L, Dm), cfg.dtype),
            "ln2": jnp.ones((L, Dm), cfg.dtype),
            "wq": dense(ks[1], (L, Dm, cfg.q_dim), Dm),
            "wk": dense(ks[2], (L, Dm, cfg.kv_dim), Dm),
            "wv": dense(ks[3], (L, Dm, cfg.kv_dim), Dm),
            "wo": dense(ks[4], (L, cfg.q_dim, Dm), cfg.q_dim),
            "router": dense(ks[5], (L, Dm, E), Dm),
            "w_gate": dense(ks[6], (L, E, Dm, F), Dm),
            "w_up": dense(ks[7], (L, E, Dm, F), Dm),
            "w_down": dense(ks[8], (L, E, F, Dm), F),
        },
        "final_norm": jnp.ones((Dm,), cfg.dtype),
    }
    if not cfg.tie_embeddings:
        k_un = jax.random.fold_in(ks[0], 1)
        out["unembed"] = dense(k_un, (Dm, cfg.vocab_size), Dm)
    return out


def param_specs(cfg: MoEConfig, *, tp: str = "tp",
                ep: str = "ep") -> Dict[str, Any]:
    """Experts over ep; per-expert hidden over tp; attention like the
    dense model. The router is replicated (every rank routes every
    token — routing decisions must agree globally)."""
    specs = {
        "embed": P(None, None),
        "layers": {
            "ln1": P(None, None), "ln2": P(None, None),
            "wq": P(None, None, tp), "wk": P(None, None, tp),
            "wv": P(None, None, tp), "wo": P(None, tp, None),
            "router": P(None, None, None),
            "w_gate": P(None, ep, None, tp),
            "w_up": P(None, ep, None, tp),
            "w_down": P(None, ep, tp, None),
        },
        "final_norm": P(None),
    }
    if not cfg.tie_embeddings:
        specs["unembed"] = P(None, None)
    return specs


def _local_experts(layer: Dict[str, jnp.ndarray]) -> int:
    """Experts on this ep rank — works for full-precision layers
    (w_gate [E, Dm, F]) and fused-int8 layers (w_gate#q8)."""
    wg = layer.get("w_gate", layer.get("w_gate#q8"))
    return wg.shape[0]


_Q8_ROUTING_WARNED = set()


def _q8_routing_warn(routing: str) -> None:
    # Loud once per routing: the fused int8 kernel covers the queue-
    # shaped dispatches; anything else silently widening the expert
    # weights in-graph would re-create the r5 roofline gap unnoticed.
    if routing in _Q8_ROUTING_WARNED:
        return
    _Q8_ROUTING_WARNED.add(routing)
    import warnings
    warnings.warn(
        f"fused int8 expert path does not cover routing={routing!r}; "
        f"expert weights widen in-graph (dequant_hook semantics) for "
        f"this dispatch", RuntimeWarning, stacklevel=3)


def _q8_expert_mlps(x_e: jnp.ndarray, layer: Dict[str, jnp.ndarray],
                    cfg: MoEConfig) -> jnp.ndarray:
    """The three expert matmuls on [E_l, C, Dm] token queues (or a
    shared [C, Dm] block every expert computes) -> [E_l, C, Dm],
    straight off raw int8 expert leaves. The ONE seam where the fused
    dequant×GEMM kernel replaces the wide einsums: ops/q8_expert
    streams the weights HBM->VMEM as int8 and dequantizes tiles inside
    the matmul — no materialized wide copy (the r5 roofline-gap
    culprit). Per-shard under ep×tp placement: each rank calls this on
    its local expert/hidden slice; tp-partial outputs are psum'd by
    the caller as before (placement contract unchanged)."""
    from tpushare.ops.q8_expert import q8_expert_dispatch
    return q8_expert_dispatch(
        x_e, layer["w_gate#q8"], layer["w_gate#scale"],
        layer["w_up#q8"], layer["w_up#scale"],
        layer["w_down#q8"], layer["w_down#scale"], act=cfg.act)


def _moe_ffn(h: jnp.ndarray, layer: Dict[str, jnp.ndarray],
             cfg: MoEConfig, pctx: ParallelCtx,
             ep_axis: Optional[str],
             data_axes: Tuple[str, ...] = (),
             phase_timer=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Routed expert MLP. h [B,S,Dm] → (out [B,S,Dm], aux_loss scalar).

    ``phase_timer`` (measurement mode only — forward's docstring) marks
    router / dispatch / expert_gemm spans; None on every hot path.

    Fused int8 experts: a layer carrying raw ``w_gate#q8``-style
    leaves (quant.fused_expert_hook) routes its expert matmuls through
    ops/q8_expert — covered for the queue-shaped dispatches (psum
    dense, grouped capacity, a2a, expert_choice); dropless needs wide
    weights for ragged_dot and falls back loudly to in-graph
    dequantization."""
    B, S, Dm = h.shape
    E = cfg.n_experts
    pt = phase_timer
    q8 = "w_gate#q8" in layer
    if q8 and cfg.routing == "dropless":
        from tpushare.models.quant import dequant_expert_leaves
        _q8_routing_warn(cfg.routing)
        layer = dequant_expert_leaves(layer, cfg.dtype)
        q8 = False
    E_local = _local_experts(layer)             # experts on this ep rank

    # Routing — replicated math, identical on every rank.
    logits = (h @ layer["router"]).astype(jnp.float32)        # [B,S,E]
    probs = jax.nn.softmax(logits, axis=-1)
    if cfg.routing == "expert_choice":
        # Experts pick tokens: perfectly balanced by construction, so
        # the Switch aux loss does not exist for this strategy.
        if pt is not None:
            pt.mark("router", block_on=probs)
        out = _expert_choice_dispatch(h, layer, cfg, pctx, ep_axis, probs,
                                      q8=q8)
        if pt is not None:
            pt.mark("expert_gemm", block_on=out)
        return out.astype(h.dtype), jnp.zeros((), jnp.float32)
    top_w, top_i = jax.lax.top_k(probs, cfg.top_k)            # [B,S,K]
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    # Combine weights as a dense [B,S,E] one-hot mixture (static shapes).
    combine = jnp.sum(
        jax.nn.one_hot(top_i, E, dtype=jnp.float32) * top_w[..., None],
        axis=2)                                               # [B,S,E]

    # Switch aux loss: E * Σ_e fraction_routed(e) · mean_prob(e).
    # fraction·probability is nonlinear in the data, so under dp/sp the
    # per-expert statistics must be averaged globally BEFORE the
    # product — a per-shard aux pmean'd afterwards would differ from
    # the single-device value.
    frac = jnp.mean((combine > 0).astype(jnp.float32), axis=(0, 1))
    mean_p = jnp.mean(probs, axis=(0, 1))
    for ax in data_axes:
        frac = jax.lax.pmean(frac, ax)
        mean_p = jax.lax.pmean(mean_p, ax)
    aux = E * jnp.sum(frac * mean_p)
    if pt is not None:
        pt.mark("router", block_on=(combine, top_w, top_i, aux))

    if cfg.routing not in ("psum", "a2a", "dropless"):
        raise ValueError(
            f"unknown routing {cfg.routing!r}; expected 'psum', 'a2a', "
            "'dropless', or 'expert_choice'")
    if cfg.routing == "dropless":
        out = _dropless_dispatch(h, layer, cfg, pctx, ep_axis, top_w,
                                 top_i)
        if pt is not None:
            pt.mark("expert_gemm", block_on=out)
    elif cfg.routing == "a2a" and ep_axis is not None:
        if cfg.capacity_factor is None:
            raise ValueError("routing='a2a' requires capacity_factor")
        out = _a2a_dispatch(h, layer, cfg, pctx, ep_axis, top_w, top_i,
                            q8=q8)
        if pt is not None:
            pt.mark("expert_gemm", block_on=out)
    elif cfg.capacity_factor is not None:
        out = _grouped_dispatch(h, layer, cfg, pctx, ep_axis, top_w,
                                top_i, q8=q8, phase_timer=pt)
    else:
        # This rank's expert slice of the combine weights.
        if ep_axis is not None:
            start = jax.lax.axis_index(ep_axis) * E_local
            combine_local = jax.lax.dynamic_slice_in_dim(combine, start,
                                                         E_local, axis=2)
        else:
            combine_local = combine

        # Dense batched expert compute on local experts (MXU-shaped).
        # Fused int8: every local expert runs the whole [T, Dm] token
        # block, so ONE shared 2-D block goes to the kernel — no
        # [E_l, T, Dm] broadcast is ever materialized.
        hc = h.astype(cfg.dtype)
        if q8:
            y = _q8_expert_mlps(hc.reshape(B * S, Dm), layer, cfg)
            out_e = y.reshape(E_local, B, S, Dm).transpose(1, 0, 2, 3)
        else:
            gate = jnp.einsum("bsd,edf->besf", hc, layer["w_gate"])
            up = jnp.einsum("bsd,edf->besf", hc, layer["w_up"])
            ff = _act(cfg.act, gate) * up                 # [B,E_l,S,F]
            out_e = jnp.einsum("besf,efd->besd", ff, layer["w_down"])
        if pctx.tp is not None:
            out_e = jax.lax.psum(out_e, pctx.tp)
        if pt is not None:
            pt.mark("expert_gemm", block_on=out_e)
        out = jnp.einsum("bse,besd->bsd",
                         combine_local.astype(out_e.dtype), out_e)
        if ep_axis is not None:
            out = jax.lax.psum(out, ep_axis)
        if pt is not None:
            pt.mark("dispatch", block_on=out)
    return out.astype(h.dtype), aux


def expert_capacity(n_tokens: int, cfg: MoEConfig,
                    default_factor: Optional[float] = None) -> int:
    """Per-expert token capacity C = min(T, ceil(T·K/E · factor))
    (static). The one copy of the formula, shared by the capacity and
    expert-choice dispatches; ``default_factor`` stands in when the
    config has no capacity_factor (expert-choice's factor-optional
    contract). C can never exceed T — an expert cannot pick or be
    assigned more tokens than exist."""
    factor = (cfg.capacity_factor if cfg.capacity_factor is not None
              else default_factor)
    assert factor is not None
    return min(n_tokens,
               max(1, math.ceil(n_tokens * cfg.top_k / cfg.n_experts
                                * factor)))


def _pvary(x: jnp.ndarray, axis: str) -> jnp.ndarray:
    """Explicitly tag x as varying over ``axis`` — see
    _dropless_dispatch on why the implicit lift at a varying-index
    gather is not sufficient."""
    return jax.lax.pcast(x, (axis,), to="varying")


def _route_buffers(top_w: jnp.ndarray, top_i: jnp.ndarray, T: int, E: int,
                   C: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Static-shape routing queues shared by the grouped and a2a paths.

    Scatters assignment token ids and combine weights into [E, C]
    (position = first-come in token order, deterministic; overflow
    assignments land in a sacrificial row/col that is sliced off —
    Switch/GShard drop semantics). Returns (buf token ids with
    sentinel T for empty slots, wbuf f32 weights)."""
    K = top_i.shape[-1]
    eid = top_i.reshape(T * K)                        # expert per assignment
    w = top_w.reshape(T * K).astype(jnp.float32)
    tok = jnp.arange(T * K, dtype=jnp.int32) // K     # token per assignment
    onehot = jax.nn.one_hot(eid, E, dtype=jnp.int32)  # [T*K, E]
    pos = jnp.cumsum(onehot, axis=0) - onehot
    pos_in_e = jnp.take_along_axis(pos, eid[:, None], axis=1)[:, 0]
    keep = pos_in_e < C
    safe_e = jnp.where(keep, eid, E)
    safe_c = jnp.where(keep, pos_in_e, C)
    buf = jnp.full((E + 1, C + 1), T, jnp.int32)
    buf = buf.at[safe_e, safe_c].set(tok.astype(jnp.int32))[:E, :C]
    wbuf = jnp.zeros((E + 1, C + 1), jnp.float32)
    wbuf = wbuf.at[safe_e, safe_c].set(w)[:E, :C]
    return buf, wbuf


def _a2a_dispatch(h: jnp.ndarray, layer: Dict[str, jnp.ndarray],
                  cfg: MoEConfig, pctx: ParallelCtx, ep_axis: str,
                  top_w: jnp.ndarray, top_i: jnp.ndarray,
                  q8: bool = False) -> jnp.ndarray:
    """GShard-style token routing: ep shards the DATA; each rank routes
    its local T tokens into per-expert queues [E, C], an all_to_all
    ships each queue to the rank owning the expert, the expert MLPs run
    on [E_local, ep·C] received tokens, and a second all_to_all returns
    outputs for the local scatter-add combine. No ep psum: both top-k
    contributions of a token come back through its own queues.

    Capacity is per (source rank, expert): C = ceil(T_local·K/E·factor)
    — drop decisions are made locally in token order, so they differ
    from the single-rank grouped path only when overflow occurs.
    """
    B, S, Dm = h.shape
    E = cfg.n_experts
    E_local = _local_experts(layer)
    ep = E // E_local
    T = B * S                                # local tokens (ep is data)
    C = expert_capacity(T, cfg)

    buf, wbuf = _route_buffers(top_w, top_i, T, E, C)

    hc = h.reshape(T, Dm).astype(cfg.dtype)
    hpad = jnp.concatenate([hc, jnp.zeros((1, Dm), cfg.dtype)], axis=0)
    x_send = hpad[buf].reshape(ep, E_local, C, Dm)
    # dim 0 = destination rank; after the exchange dim 0 = source rank.
    x_recv = jax.lax.all_to_all(x_send, ep_axis, 0, 0)
    xe = x_recv.transpose(1, 0, 2, 3).reshape(E_local, ep * C, Dm)

    if q8:
        y = _q8_expert_mlps(xe, layer, cfg)
    else:
        gate = jnp.einsum("ecd,edf->ecf", xe, layer["w_gate"])
        up = jnp.einsum("ecd,edf->ecf", xe, layer["w_up"])
        ff = _act(cfg.act, gate) * up
        y = jnp.einsum("ecf,efd->ecd", ff, layer["w_down"])
    if pctx.tp is not None:
        y = jax.lax.psum(y, pctx.tp)

    # Inverse exchange: outputs return to their source rank, arriving
    # rank-major over expert owners == the [E, C] queue order.
    y = y.reshape(E_local, ep, C, Dm).transpose(1, 0, 2, 3)
    y_ret = jax.lax.all_to_all(y, ep_axis, 0, 0).reshape(E, C, Dm)

    out = jnp.zeros((T + 1, Dm), y_ret.dtype)
    out = out.at[buf].add(wbuf[..., None].astype(y_ret.dtype) * y_ret)
    return out[:T].reshape(B, S, Dm)


def _dropless_dispatch(h: jnp.ndarray, layer: Dict[str, jnp.ndarray],
                       cfg: MoEConfig, pctx: ParallelCtx,
                       ep_axis: Optional[str],
                       top_w: jnp.ndarray, top_i: jnp.ndarray) -> jnp.ndarray:
    """Exact MoE via grouped GEMMs (MegaBlocks-style, TPU-native).

    Assignments are sorted by expert (stable, so token order within an
    expert is preserved) and the three expert matmuls run as
    ``lax.ragged_dot`` grouped GEMMs over the per-expert group sizes —
    every token-expert pair computes exactly once (the ideal FLOP
    count; no capacity bound, nothing dropped, no padding waste).

    Under ep, non-local assignments map to a sentinel group that sorts
    past ``sum(group_sizes)``; ragged_dot leaves those rows zero and
    the TPU lowering's group loop never touches them, so per-rank
    expert FLOPs are the local share. Combine is the same scatter-add +
    ep psum as the capacity path (tokens replicated over ep).

    The ep-replicated h is EXPLICITLY pvary'd before the sorted
    gather/scatter: without the explicit boundary, the gather-with-
    varying-indices transpose silently drops the varying tag and the
    replicated-param cotangents miss their cross-rank psum (observed:
    exact forward, ~O(1) wrong embed/attention grads on an ep mesh;
    the explicit pvary's own transpose supplies the psum).
    """
    B, S, Dm = h.shape
    E_local = layer["w_gate"].shape[0]
    T = B * S
    K = cfg.top_k
    A = T * K

    eid = top_i.reshape(A)
    w = top_w.reshape(A).astype(jnp.float32)
    tok = jnp.arange(A, dtype=jnp.int32) // K
    if ep_axis is not None:
        # Same explicit boundary as ht below: w is differentiable (its
        # cotangent reaches the router) and about to be gathered with
        # ep-varying indices.
        w = _pvary(w, ep_axis)
        start = jax.lax.axis_index(ep_axis) * E_local
        local = jnp.logical_and(eid >= start, eid < start + E_local)
        le = jnp.where(local, eid - start, E_local)   # sentinel -> tail
    else:
        le = eid
    order = jnp.argsort(le, stable=True)
    tok_s, w_s = tok[order], w[order]
    sizes = jnp.bincount(le, length=E_local + 1)[:E_local].astype(jnp.int32)

    ht = h.reshape(T, Dm).astype(cfg.dtype)
    if ep_axis is not None:
        ht = _pvary(ht, ep_axis)
    x = ht[tok_s]                                     # [A, Dm] sorted
    gate = jax.lax.ragged_dot(x, layer["w_gate"], sizes)
    up = jax.lax.ragged_dot(x, layer["w_up"], sizes)
    ff = _act(cfg.act, gate) * up
    y = jax.lax.ragged_dot(ff, layer["w_down"], sizes)   # [A, Dm]
    if pctx.tp is not None:
        y = jax.lax.psum(y, pctx.tp)
    out = jnp.zeros((T, Dm), y.dtype)
    if ep_axis is not None:
        out = _pvary(out, ep_axis)
    out = out.at[tok_s].add(w_s[:, None].astype(y.dtype) * y)
    if ep_axis is not None:
        out = jax.lax.psum(out, ep_axis)
    return out.reshape(B, S, Dm)


def _grouped_dispatch(h: jnp.ndarray, layer: Dict[str, jnp.ndarray],
                      cfg: MoEConfig, pctx: ParallelCtx,
                      ep_axis: Optional[str],
                      top_w: jnp.ndarray, top_i: jnp.ndarray,
                      q8: bool = False, phase_timer=None) -> jnp.ndarray:
    """Capacity-bounded grouped expert compute (Switch/GShard drop
    semantics) — each expert runs its matmuls on at most C routed
    tokens instead of all T, cutting expert FLOPs from E_local·T to
    E_local·C = E_local·T·K/E·factor per rank.

    All shapes are static: assignments scatter token ids into an
    [E, C] buffer (first-come in token order wins, overflow rows/cols
    land in a sacrificial row/col that is sliced off), token vectors
    are gathered to [E_local, C, Dm], and results scatter-add back.
    XLA lowers the scatters/gathers to O(T·Dm) data movement; the
    matmuls stay MXU-shaped.
    """
    B, S, Dm = h.shape
    E = cfg.n_experts
    E_local = _local_experts(layer)
    T = B * S
    C = expert_capacity(T, cfg)
    pt = phase_timer

    # Queue positions are token-order — deterministic and identical on
    # every rank since routing is replicated under "psum" ep.
    buf, wbuf = _route_buffers(top_w, top_i, T, E, C)

    if ep_axis is not None:
        start = jax.lax.axis_index(ep_axis) * E_local
        buf = jax.lax.dynamic_slice_in_dim(buf, start, E_local, axis=0)
        wbuf = jax.lax.dynamic_slice_in_dim(wbuf, start, E_local, axis=0)

    # Gather inputs (sentinel token T reads the zero pad row), run the
    # expert MLPs on [E_local, C] tokens, scatter-add weighted results.
    hc = h.reshape(T, Dm).astype(cfg.dtype)
    hpad = jnp.concatenate([hc, jnp.zeros((1, Dm), cfg.dtype)], axis=0)
    x_e = hpad[buf]                                   # [E_l, C, Dm]
    if pt is not None:
        pt.mark("dispatch", block_on=x_e)
    if q8:
        y_e = _q8_expert_mlps(x_e, layer, cfg)
    else:
        gate = jnp.einsum("ecd,edf->ecf", x_e, layer["w_gate"])
        up = jnp.einsum("ecd,edf->ecf", x_e, layer["w_up"])
        ff = _act(cfg.act, gate) * up
        y_e = jnp.einsum("ecf,efd->ecd", ff, layer["w_down"])
    if pctx.tp is not None:
        y_e = jax.lax.psum(y_e, pctx.tp)
    if pt is not None:
        pt.mark("expert_gemm", block_on=y_e)
    contrib = wbuf[..., None].astype(y_e.dtype) * y_e
    out = jnp.zeros((T + 1, Dm), y_e.dtype)
    out = out.at[buf].add(contrib)[:T]
    if ep_axis is not None:
        out = jax.lax.psum(out, ep_axis)
    if pt is not None:
        pt.mark("dispatch", block_on=out)
    return out.reshape(B, S, Dm)


def _expert_choice_dispatch(h: jnp.ndarray, layer: Dict[str, jnp.ndarray],
                            cfg: MoEConfig, pctx: ParallelCtx,
                            ep_axis: Optional[str],
                            probs: jnp.ndarray,
                            q8: bool = False) -> jnp.ndarray:
    """Expert-choice routing (Zhou et al.): EXPERTS pick their top-C
    tokens by router score instead of tokens picking top-K experts.

    Load balance is perfect by construction — every expert processes
    exactly C = ceil(T·K/E·factor) tokens — so there is no aux loss to
    tune and no drops in the Switch sense (a token can be chosen by
    zero experts, contributing only its residual path, or by many).
    Selections are BATCH-LOCAL: under dp/sp sharding each shard's
    experts pick from that shard's tokens (the per-device semantics
    every EC trainer has), so exact single-device parity holds on
    batch-replicated meshes (ep x tp) — tested so.
    All shapes static: per-expert top_k over the [E, T] score columns,
    gather [E_local, C, Dm], the same MXU-shaped expert matmuls as the
    capacity path, weighted scatter-add back, ep psum combine (tokens
    replicated over ep, like 'psum'/'dropless').

    Same explicit vma boundary as _dropless_dispatch: the replicated
    token matrix is pvary'd before the ep-varying gather, or the
    transpose silently drops the replicated-param psum.
    """
    B, S, Dm = h.shape
    E = cfg.n_experts
    E_local = _local_experts(layer)
    T = B * S
    C = expert_capacity(T, cfg, default_factor=1.0)

    p = probs.reshape(T, E)
    w_e, idx_e = jax.lax.top_k(p.T, C)               # [E, C] each
    if ep_axis is not None:
        w_e = _pvary(w_e.astype(jnp.float32), ep_axis)
        start = jax.lax.axis_index(ep_axis) * E_local
        w_e = jax.lax.dynamic_slice_in_dim(w_e, start, E_local, axis=0)
        idx_e = jax.lax.dynamic_slice_in_dim(idx_e, start, E_local, axis=0)

    hc = h.reshape(T, Dm).astype(cfg.dtype)
    if ep_axis is not None:
        hc = _pvary(hc, ep_axis)
    x_e = hc[idx_e]                                  # [E_l, C, Dm]
    if q8:
        y_e = _q8_expert_mlps(x_e, layer, cfg)
    else:
        gate = jnp.einsum("ecd,edf->ecf", x_e, layer["w_gate"])
        up = jnp.einsum("ecd,edf->ecf", x_e, layer["w_up"])
        ff = _act(cfg.act, gate) * up
        y_e = jnp.einsum("ecf,efd->ecd", ff, layer["w_down"])
    if pctx.tp is not None:
        y_e = jax.lax.psum(y_e, pctx.tp)
    contrib = w_e[..., None].astype(y_e.dtype) * y_e
    out = jnp.zeros((T, Dm), y_e.dtype)
    if ep_axis is not None:
        out = _pvary(out, ep_axis)
    out = out.at[idx_e].add(contrib)
    if ep_axis is not None:
        out = jax.lax.psum(out, ep_axis)
    return out.reshape(B, S, Dm)


def init_cache(cfg: MoEConfig, batch: int, max_len: int
               ) -> Dict[str, jnp.ndarray]:
    """Dense KV decode cache for the MoE LM — same row layout as
    transformer.init_cache ({"k","v"} [L, B, max_len, Hkv, Dh]) so
    checkpoint/restore tooling composes. Expert weights carry no
    per-token state: KV is the ONLY cache MoE decode needs (routing
    re-decides per token from the hidden state)."""
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, cfg.dtype),
            "v": jnp.zeros(shape, cfg.dtype)}


def forward(params: Dict[str, Any], tokens: jnp.ndarray, cfg: MoEConfig, *,
            pctx: Optional[ParallelCtx] = None,
            ep_axis: Optional[str] = None,
            data_axes: Tuple[str, ...] = (),
            attn_impl: str = "auto",
            cache: Optional[Dict[str, jnp.ndarray]] = None,
            pos_offset=0,
            layers_hook=None,
            last_logit_only: bool = False,
            phase_timer=None):
    """tokens [B,S] → (logits [B,S,V] f32, aux_loss scalar) — and the
    updated cache as a third element when ``cache`` is given.

    Inference (mirrors transformer.forward's dense-cache contract):
    ``cache`` from init_cache turns the call into prefill (S > 1 or
    scalar ``pos_offset``: writes KV at pos_offset..pos_offset+S-1,
    causal over the written prefix) or ragged decode (``pos_offset``
    an int32 [B] array, S == 1: each row writes at its own length and
    attends positions <= it). Routing is recomputed per token from the
    hidden state — experts hold no decode state, so KV rows are the
    whole cache and every dispatch strategy (psum/a2a/dropless/
    expert_choice) decodes unchanged. Under a real tp axis the cache
    must shard kv heads over tp (the dense serving.cache_specs
    contract): each rank computes only its local kv heads, and a
    replicated cache would silently broadcast that local slice across
    the full head axis on the ragged .set().

    ``layers_hook`` is the same per-layer transform seam as
    transformer.forward's: it maps the xs slice of params["layers"]
    to the real layer tree INSIDE the scan body. quant.dequant_hook
    works unchanged here — _QUANT_KEYS already names w_gate/w_up/
    w_down and its per-output-channel scale logic is rank-generic, so
    expert stacks [L, E, Dm, F] quantize to int8 + [L, E, 1, F]
    scales; the router ("router") deliberately stays full precision
    (routing argmaxes are precision-sensitive and the leaf is tiny).
    MoE decode streams the experts from HBM every step, so int8
    expert storage halves the decode bandwidth floor — the serving
    reason this seam exists (benchmarks/bench_moe.py). quant.
    fused_expert_hook keeps the expert leaves int8 through to the
    fused dequant×GEMM kernel (ops/q8_expert) — same placement
    contract (quant_moe_param_specs), no materialized wide copy.

    ``phase_timer`` (utils/profiling.PhaseTimer) is MEASUREMENT MODE
    ONLY: when set, the layer scan unrolls into a host loop and every
    phase — dequant (hook) / attn / router / dispatch / expert_gemm /
    unembed — closes with a ``block_until_ready`` mark, exactly the
    host-device syncs the serving hot loop must never make. The
    default None keeps this seam invisible to the production paths
    (zero extra fetches, the scan untouched); a traced call with a
    timer raises — measurement mode cannot run under jit, where the
    marks would time tracing, not execution. bench_moe.py's
    phase_breakdown rows ride this."""
    pctx = pctx or ParallelCtx()
    if phase_timer is not None and isinstance(tokens, jax.core.Tracer):
        raise ValueError(
            "phase_timer is measurement-mode only: call forward "
            "eagerly (outside jit) — under a trace the block_until_"
            "ready marks would measure tracing, not device execution")
    B, S = tokens.shape
    Dh = cfg.head_dim
    use_cache = cache is not None
    # Paged decode (the transformer.forward contract): the cache dict
    # carries the stacked block pools ({"pool_k": [L,nb,bs,Hkv*Dh],
    # "pool_v", "table": [B,mb], "active": [B]}) instead of dense rows.
    # KV is the ONLY MoE cache (routing re-decides per token), so the
    # block pool ports unchanged: the stacks are the layer loop's
    # carry, each layer scatters its rows at [l, blk, off] in place and
    # attends through the table at layer l (pallas paged kernel on TPU,
    # one gather of the slots' blocks elsewhere) — no layer is sliced
    # out or restacked. No kv_quant/multi-LoRA branches here —
    # those are dense-LM features (paged.PagedSlotServer rejects them
    # under a forward_fn override).
    paged = use_cache and "pool_k" in cache
    # transformer.forward's convention: a 1-D pos_offset means ragged
    # decode; any scalar (python int, numpy/jnp 0-d, traced) means
    # prefill continuation.
    ragged = use_cache and jnp.asarray(pos_offset).ndim == 1
    if paged and not ragged:
        raise ValueError("paged cache requires ragged decode (pos [B])")
    if paged and phase_timer is not None:
        raise ValueError("phase_timer measures the dense-row cache "
                         "only (paged_forward never passes one)")
    pg_active = (jnp.asarray(cache["active"])
                 if paged and "active" in cache
                 else (jnp.ones((B,), bool) if paged else None))
    if ragged:
        # S == 1: continuous-batching decode. S > 1: ragged
        # multi-token scoring (speculative verify) — row b's queries
        # sit at pos_b..pos_b+S-1 and its KV rows scatter there.
        pos = jnp.asarray(pos_offset, jnp.int32).reshape(B)
        positions = pos[:, None] + jnp.arange(S)[None, :]     # [B, S]
    else:
        positions = pos_offset + jnp.arange(S)[None, :]
        if pctx.sp is not None:
            positions = positions + jax.lax.axis_index(pctx.sp) * S
        positions = jnp.broadcast_to(positions, (B, S))
    cos, sin = rotary_embedding(positions, Dh, base=cfg.rope_base,
                                scaling=cfg.rope_scaling)

    x = params["embed"][tokens].astype(cfg.dtype)
    if phase_timer is not None:
        # Charges the embedding gather + rope/mask setup above.
        phase_timer.mark("embed", block_on=(x, cos, sin))
    M = cache["k"].shape[2] if use_cache and not paged else 0
    if paged:
        kv_mask = None          # built per-layer off the block table
    elif ragged and S > 1:
        # [B, S, M]: query j of row b attends kv positions <= pos_b+j
        # (mha_reference's 3D-mask contract for ragged verify).
        kv_mask = (jnp.arange(M)[None, None, :]
                   <= positions[:, :, None])
    elif ragged:
        kv_mask = jnp.arange(M)[None, :] <= positions         # [B, M]
    else:
        kv_mask = None

    def block(x, layer, lk=None, lv=None, l=None):
        # Paged: lk/lv are the whole stacked pools and ``l`` the layer
        # to write and read; dense rows: this layer's slices.
        pt = phase_timer
        if layers_hook is not None:
            layer = layers_hook(layer)
            if pt is not None:
                # The dequant_hook path materializes wide copies here
                # — the span this mark exists to localize; the fused
                # hook only widens the (small) attention leaves.
                pt.mark("dequant", block_on=jax.tree.leaves(layer))
        h = rms_norm(x, layer["ln1"], eps=cfg.norm_eps)
        H = layer["wq"].shape[-1] // Dh
        Hkv = layer["wk"].shape[-1] // Dh
        q = apply_rotary((h @ layer["wq"]).reshape(B, S, H, Dh), cos, sin)
        k = apply_rotary((h @ layer["wk"]).reshape(B, S, Hkv, Dh), cos, sin)
        v = (h @ layer["wv"]).reshape(B, S, Hkv, Dh)
        if use_cache and paged:
            # Scatter the new KV through the block table (inactive or
            # out-of-range positions land in the sacrificial trash
            # block — the same guard as transformer.forward's paged
            # branches), then attend straight off the pool. S == 1 is
            # ragged decode, S > 1 the multi-token speculative verify.
            bs_pg = lk.shape[2]
            mb = cache["table"].shape[1]
            trash = lk.shape[1] - 1
            table = cache["table"]
            bi = jnp.minimum(positions // bs_pg, mb - 1)       # [B, S]
            entry = jnp.take_along_axis(table, bi, 1)          # [B, S]
            blk = jnp.where(pg_active[:, None] & (entry >= 0)
                            & (positions < mb * bs_pg), entry, trash)
            off = positions % bs_pg
            lk = lk.at[l, blk, off].set(
                k.reshape(B, S, Hkv * Dh).astype(lk.dtype))
            lv = lv.at[l, blk, off].set(
                v.reshape(B, S, Hkv * Dh).astype(lv.dtype))
            from tpushare.ops.flash_attention import (
                paged_decode_eligible, paged_flash_decode,
                paged_flash_verify, paged_verify_eligible)
            eligible = (paged_decode_eligible if S == 1
                        else paged_verify_eligible)
            kernel = (paged_flash_decode if S == 1
                      else paged_flash_verify)
            if (attn_impl != "reference"
                    and eligible(q, lk, max_ctx=mb * bs_pg,
                                 stacked=True)):
                # Pages stream from HBM once per slot per step; the
                # fallback below re-materializes the whole slot view
                # per layer (the eligibility policy notes).
                attn = kernel(q, lk, lv, table, pos, layer=l)
            else:
                safe = jnp.where(table >= 0, table, trash)
                kd = lk[l, safe].reshape(B, mb * bs_pg, Hkv, Dh)
                vd = lv[l, safe].reshape(B, mb * bs_pg, Hkv, Dh)
                pg_mask = (jnp.arange(mb * bs_pg)[None, None, :]
                           <= positions[:, :, None])           # [B,S,M]
                attn = attention(q, kd, vd, causal=False,
                                 kv_mask=pg_mask, impl=attn_impl)
        elif use_cache and ragged:
            # mode="drop": a multi-token row whose padded tail would
            # spill past max_len (fused admission chunks, spec blocks
            # near capacity) drops those writes instead of clamping
            # them into the last live position.
            lk = lk.at[jnp.arange(B)[:, None], positions].set(
                k.astype(lk.dtype), mode="drop")
            lv = lv.at[jnp.arange(B)[:, None], positions].set(
                v.astype(lv.dtype), mode="drop")
            attn = attention(q, lk, lv, causal=False, kv_mask=kv_mask,
                             impl=attn_impl)
        elif use_cache:
            lk = jax.lax.dynamic_update_slice_in_dim(
                lk, k.astype(lk.dtype), pos_offset, axis=1)
            lv = jax.lax.dynamic_update_slice_in_dim(
                lv, v.astype(lv.dtype), pos_offset, axis=1)
            # Zero rows past the written prefix sit above every query
            # position, so the causal q_offset mask hides them.
            attn = attention(q, lk, lv, causal=True, q_offset=pos_offset,
                             impl=attn_impl)
        elif pctx.sp is not None:
            attn = ring_attention(q, k, v, axis_name=pctx.sp, causal=True)
        else:
            attn = attention(q, k, v, causal=True, impl=attn_impl)
        o = attn.reshape(B, S, H * Dh) @ layer["wo"]
        if pctx.tp is not None:
            o = jax.lax.psum(o, pctx.tp)
        x = x + o
        if pt is not None:
            pt.mark("attn", block_on=(x, lk, lv))

        h = rms_norm(x, layer["ln2"], eps=cfg.norm_eps)
        ff, aux = _moe_ffn(h, layer, cfg, pctx, ep_axis, data_axes,
                           phase_timer=pt)
        return x + ff, aux, lk, lv

    if cfg.remat and phase_timer is None:
        block = jax.checkpoint(block)

    if phase_timer is not None:
        # Measurement mode: the scan unrolls into a host loop so the
        # per-phase marks inside block() can drain the device queue
        # between phases (a mark inside a scan body would be traced
        # away). Bit-compatible with the scan — same per-layer ops on
        # the same slices; only the loop carrier differs.
        aux_l, nk_l, nv_l = [], [], []
        for li in range(cfg.n_layers):
            layer_i = {k: v[li] for k, v in params["layers"].items()}
            if use_cache:
                x, aux, lk, lv = block(x, layer_i, cache["k"][li],
                                       cache["v"][li])
                nk_l.append(lk)
                nv_l.append(lv)
            else:
                x, aux, _, _ = block(x, layer_i)
            aux_l.append(aux)
        aux_per_layer = jnp.stack(aux_l)
        if use_cache:
            nk, nv = jnp.stack(nk_l), jnp.stack(nv_l)
            # The re-stack is a measurement-loop artifact (the scan
            # carries layers in place) — keep it out of unembed.
            phase_timer.mark("kv_stack", block_on=(nk, nv))
    elif paged:
        # The stacked pools are the CARRY and the layer index rides xs
        # with the weights (transformer.forward's paged scan): each
        # layer's scatter updates the carried buffer in place.
        def body(carry, xs):
            layer, l = xs
            x, aux, nk, nv = block(carry[0], layer, *carry[1:], l)
            return (x, nk, nv), aux
        (x, nk, nv), aux_per_layer = jax.lax.scan(
            body, (x, cache["pool_k"], cache["pool_v"]),
            (params["layers"], jnp.arange(cfg.n_layers)))
    elif use_cache:
        def body(x, xs):
            layer, lk, lv = xs
            x, aux, lk, lv = block(x, layer, lk, lv)
            return x, (aux, lk, lv)
        x, (aux_per_layer, nk, nv) = jax.lax.scan(
            body, x, (params["layers"], cache["k"], cache["v"]))
    else:
        def body(x, layer):
            x, aux, _, _ = block(x, layer)
            return x, aux
        x, aux_per_layer = jax.lax.scan(body, x, params["layers"])
    if last_logit_only:
        # Unembed only the final position: a prefill that feeds a
        # decode loop discards the other S-1 vocab rows, and at real
        # (S, V) the [B, S, V] tensor is the dominant prefill
        # cost/HBM spike (same escape hatch as transformer.forward).
        x = x[:, -1:]
    x = rms_norm(x, params["final_norm"], eps=cfg.norm_eps)
    unembed = (params["embed"].T if cfg.tie_embeddings
               else params["unembed"]).astype(cfg.dtype)
    logits = x @ unembed
    if phase_timer is not None:
        phase_timer.mark("unembed", block_on=logits)
    out = (logits.astype(jnp.float32), jnp.mean(aux_per_layer))
    if use_cache:
        return out + ((dict(cache, pool_k=nk, pool_v=nv) if paged
                       else {"k": nk, "v": nv}),)
    return out


def decode_phase_bytes(cfg: MoEConfig, params: Dict[str, Any],
                       kv_tokens: int) -> Dict[str, int]:
    """Per-phase bytes that MUST move HBM<->VMEM for one decode step —
    the phase-level roofline denominators bench_moe.py pairs with a
    PhaseTimer snapshot (profiling.phase_roofline). Splits the same
    total the aggregate rows use (params streamed once + live KV read
    + row write): weights are charged to the phase that streams them,
    AT THEIR STORED WIDTH (int8 + scales when quantized — the whole
    point: a dequant-hook path whose expert_gemm phase runs far below
    the int8 denominator is paying for a materialized wide copy the
    floor does not include). Pure-overhead phases (dequant, dispatch,
    kv_stack — zero mandatory weight traffic at decode activation
    sizes) carry 0 and read as unrooflined overhead in the table.

    ``kv_tokens`` = total live KV positions across the batch
    (sum of lengths)."""
    layers = params["layers"]

    def _stored(keys) -> int:
        total = 0
        for k in keys:
            for kk in (k, k + "#q8", k + "#scale"):
                if kk in layers:
                    total += layers[kk].nbytes
        return total

    kv_row = 2 * cfg.n_kv_heads * cfg.head_dim * jnp.dtype(
        cfg.dtype).itemsize
    unembed = (params["embed"] if cfg.tie_embeddings
               else params["unembed"])
    return {
        "embed": 0,
        "dequant": 0,
        "attn": (_stored(("ln1", "wq", "wk", "wv", "wo"))
                 + kv_tokens * cfg.n_layers * kv_row),
        "router": _stored(("ln2", "router")),
        "dispatch": 0,
        "expert_gemm": _stored(("w_gate", "w_up", "w_down")),
        "kv_stack": 0,
        "unembed": unembed.nbytes + params["final_norm"].nbytes,
    }


@functools.partial(jax.jit, static_argnames=(
    "cfg", "max_new_tokens", "temperature", "top_k", "top_p",
    "attn_impl", "layers_hook"))
def generate(params, tokens: jnp.ndarray, cfg: MoEConfig, *,
             max_new_tokens: int = 32,
             temperature: float = 0.0,
             top_k: Optional[int] = None,
             top_p: Optional[float] = None,
             rng: Optional[jax.Array] = None,
             attn_impl: str = "auto",
             layers_hook=None) -> jnp.ndarray:
    """tokens [B, S] → [B, S + max_new_tokens]: MoE inference with a
    KV cache — one prefill, then a lax.scan of single-token ragged
    decodes (zero per-token recompiles; the whole loop is one compiled
    program, mirroring models/generate.generate for the dense LM).
    temperature 0 = greedy; otherwise sample_logits' filters apply."""
    from tpushare.models.generate import sample_logits
    B, S = tokens.shape
    if temperature > 0.0 and rng is None:
        raise ValueError("temperature sampling needs rng")
    rng = jax.random.PRNGKey(0) if rng is None else rng
    cache = init_cache(cfg, B, S + max_new_tokens)
    logits, _, cache = forward(params, tokens, cfg, cache=cache,
                               pos_offset=0, attn_impl=attn_impl,
                               layers_hook=layers_hook,
                               last_logit_only=True)
    k0, rng = jax.random.split(rng)

    def pick(lg, key):
        return sample_logits(lg, key, temperature=temperature,
                             top_k=top_k, top_p=top_p).astype(tokens.dtype)

    last = pick(logits[:, -1], k0)

    def step(carry, key):
        last, cache, t = carry
        lg, _, cache = forward(params, last[:, None], cfg, cache=cache,
                               pos_offset=jnp.full((B,), t, jnp.int32),
                               attn_impl=attn_impl,
                               layers_hook=layers_hook)
        return (pick(lg[:, 0], key), cache, t + 1), last

    keys = jax.random.split(rng, max_new_tokens)
    _, outs = jax.lax.scan(step, (last, cache, jnp.int32(S)), keys)
    return jnp.concatenate([tokens, outs.T], axis=1)


def paged_forward(params, tokens: jnp.ndarray, cfg: MoEConfig, *,
                  pctx: Optional[ParallelCtx] = None,
                  cache: Optional[Dict[str, jnp.ndarray]] = None,
                  pos_offset=0,
                  attn_impl: str = "auto",
                  layers_hook=None,
                  last_logit_only: bool = False,
                  mlora_idx=None,
                  mlora_scale: float = 1.0):
    """transformer.forward-shaped adapter over the MoE LM: returns
    (logits, cache) — the aux loss is inference-irrelevant and dropped
    — so paged.decode_core/verify_core/PagedSlotServer drive the MoE
    family through their ``forward_fn`` seam unchanged. The paged KV
    pool is pure cache state and routing holds none, which is exactly
    why the block-pool machinery ports to MoE without a second
    implementation. Multi-LoRA kwargs are accepted for signature
    parity and rejected loudly (the adapter bank is a dense-LM
    feature)."""
    del mlora_scale                     # meaningful only with a bank
    if mlora_idx is not None:
        raise ValueError("MoE serving has no adapter bank "
                         "(multi-LoRA is a dense-server feature)")
    out = forward(params, tokens, cfg, pctx=pctx, cache=cache,
                  pos_offset=pos_offset, attn_impl=attn_impl,
                  layers_hook=layers_hook,
                  last_logit_only=last_logit_only)
    if cache is None:
        return out[0], None
    logits, _aux, new_cache = out
    return logits, new_cache


class MoESlotServer(SpecDecodeMixin):
    """Continuous batching for the MoE LM — the SlotServer surface
    (admit/step/evict, ragged decode over one static-shaped cache) on
    moe.forward, so MoE models serve under the same engine pattern as
    the dense LM (serving.SlotServer docstring for the design).

    Deliberately simpler than the dense servers: no paged pools or
    multi-LoRA — expert weights dominate MoE memory, so dense KV rows
    at max_len are the right first serving shape and the paged
    machinery's win is proportionally smaller. ``prefix_cache`` is
    the row-level variant (one retained row, longest-common-prefix
    reuse; whole and chunked admits both consult it). Routing needs
    no slot state (re-decided per token from the hidden state), which
    is why admit/step are pure cache plumbing. ``layers_hook=
    quant.fused_expert_hook(cfg)`` serves an int8 quantize_params
    tree through the fused dequant×GEMM kernel (ops/q8_expert) —
    expert weights (the dominant MoE memory AND decode-bandwidth
    cost) store at 1/2 the bf16 bytes and stream from HBM as int8
    with no materialized wide copy; ``quant.dequant_hook(cfg)`` is
    the legacy per-layer widening hook, kept as the A/B oracle."""

    def __init__(self, params, cfg: MoEConfig, *, n_slots: int,
                 max_len: int, temperature: float = 0.0,
                 top_k: Optional[int] = None, top_p: Optional[float] = None,
                 seed: int = 0, attn_impl: str = "auto",
                 layers_hook=None, prefix_cache: bool = False,
                 speculative_draft=None, gamma: int = 4,
                 spec_horizon: int = 1,
                 draft_layers_hook=None,
                 mesh=None, param_specs=None, draft_param_specs=None,
                 phase_timer=None):
        from tpushare.models.serving import (TokenSampler,
                                             make_placement,
                                             mesh_attn_impl)
        attn_impl = mesh_attn_impl(mesh, attn_impl)
        # mesh: span a jax.sharding Mesh — expert stacks over ep,
        # per-expert GEMMs and attention heads over tp (param_specs;
        # int8 expert trees need quant.quant_moe_param_specs), dense
        # KV rows split on the kv-head axis. The one jitted forward
        # compiles SPMD from placement alone (no pctx/shard_map), so
        # every tick/admission/speculation path runs unchanged.
        self.mesh = mesh
        self._placement = make_placement(mesh, cfg, param_specs)
        if self._placement is not None:
            params = self._placement.place_params(params)
        self.params = params
        self.cfg = cfg
        self.n_slots = n_slots
        self.max_len = max_len
        # Per-slot speculative decoding on the shared seam
        # (models/spec.py SpecDecodeMixin): a draft LM proposes
        # gamma×horizon tokens per slot, ONE multi-token ragged verify
        # (forward's S>1 ragged mode) scores every slot's block, and
        # each slot accepts ITS OWN matched prefix — no lockstep min
        # across slots (the dense generate-level loops' compromise).
        # Draft KV rides a second dense cache; stale rows from
        # rejected proposals are overwritten before they can be
        # attended (the same write-before-attend argument as bucket
        # padding). temperature>0 composes via the seam's stochastic
        # rejection rule (spec.spec_accept_core) — the old greedy-only
        # restriction was the third divergent spec copy's limitation,
        # not the MoE family's.
        self.speculative = speculative_draft is not None
        self.gamma = gamma
        self.spec_horizon = spec_horizon
        if self.speculative:
            self._spec_init(gamma=gamma, spec_horizon=spec_horizon,
                            temperature=temperature, top_k=top_k,
                            top_p=top_p, cap=max_len)
            self.draft_params, self.draft_cfg = speculative_draft
            if self.draft_cfg.vocab_size != cfg.vocab_size:
                raise ValueError("draft and target must share a "
                                 "vocabulary")
            self._dfwd = jax.jit(functools.partial(
                forward, cfg=self.draft_cfg, attn_impl=attn_impl,
                layers_hook=draft_layers_hook))
            # Prefill variant: the draft prefill needs NO logits —
            # last_logit_only skips the [1, S, V] unembed (forward's
            # own docstring calls it the dominant prefill HBM spike).
            self._dfwd_prefill = jax.jit(functools.partial(
                forward, cfg=self.draft_cfg, attn_impl=attn_impl,
                layers_hook=draft_layers_hook, last_logit_only=True))
            self.dcache = init_cache(self.draft_cfg, n_slots, max_len)
            if self._placement is not None:
                dplace = make_placement(mesh, self.draft_cfg,
                                        draft_param_specs, role="draft")
                self.draft_params = dplace.place_params(self.draft_params)
                self.dcache = dplace.place_kv(self.dcache)
        self.cache = init_cache(cfg, n_slots, max_len)
        if self._placement is not None:
            self.cache = self._placement.place_kv(self.cache)
        # Device->host transfers made by the tick paths — the /stats
        # observability counter for the one-fetch-per-host invariant.
        self.device_fetches = 0
        self.lengths = jnp.zeros((n_slots,), jnp.int32)
        # Host mirror of the per-slot lengths: admit sets S, a plain
        # tick adds 1 per active slot, a speculative round adds the
        # fetched a+1 — so the spec-round guard, max_len retirement,
        # and evict all read host state and step() performs exactly
        # ONE device->host transfer (the token fetch).
        self._lengths_np = np.zeros((n_slots,), np.int64)
        self.last_token = jnp.zeros((n_slots, 1), jnp.int32)
        self.active = np.zeros(n_slots, dtype=bool)       # host truth
        self._active_dev = jnp.zeros((n_slots,), bool)    # device mirror
        # Uploaded by copy, never aliased (see PagedSlotServer.__init__).
        self._admissions: Dict[int, Dict[str, Any]] = {}  # chunked
        # Row-level prefix cache: the dense-row idiom of the paged
        # server's block prefix cache. ONE retained (prompt, row)
        # from the most recent whole admit; a new admit copies the
        # longest common prefix's KV (jnp rows are immutable, so the
        # "copy" is a reference) and prefills only the suffix.
        # Deliberately a 1-entry registry: the win it targets is the
        # shared-system-prompt pattern, and expert weights — not KV
        # rows — dominate MoE serving memory.
        self.prefix_cache = prefix_cache
        self._prefix: Optional[Tuple[np.ndarray, Dict[str, Any]]] = None
        self.last_cached_len = 0
        self.prefix_hit_tokens = 0
        self.prefix_prompt_tokens = 0
        self._sampler = TokenSampler(temperature, top_k, top_p, seed)
        # MEASUREMENT MODE (phase_timer set): the forward runs EAGER
        # and phase-instrumented — per-phase block_until_ready marks
        # are exactly the syncs the hot loop bans, so this server
        # shape exists for benches/diagnostics only and is asserted
        # excluded from the serving CLI (tests/test_sync_free.py).
        # Default None: ONE jitted forward — prefill ([1, P], scalar
        # offset) and decode ([n_slots, 1], ragged offsets) are just
        # different shapes in its compile cache.
        self.phase_timer = phase_timer
        if phase_timer is not None:
            self._fwd = functools.partial(
                forward, cfg=cfg, attn_impl=attn_impl,
                layers_hook=layers_hook, phase_timer=phase_timer)
        else:
            self._fwd = jax.jit(functools.partial(
                forward, cfg=cfg, attn_impl=attn_impl,
                layers_hook=layers_hook))

    @property
    def admitting_count(self) -> int:
        return len(self._admissions)

    @property
    def admission_slots(self):
        """Slots with an in-flight chunked admission (the engine's
        quarantine path reaps untracked ones)."""
        return list(self._admissions)

    def _claim_slot(self, prompt: jnp.ndarray) -> int:
        """Shared admit validation + slot pick (mid-chunked-admission
        slots have active=False but are NOT free)."""
        if prompt.ndim != 1:
            raise ValueError("admit takes a single unbatched prompt")
        S = int(prompt.shape[0])
        if S >= self.max_len:
            raise ValueError(f"prompt length {S} >= max_len "
                             f"{self.max_len}")
        for slot in range(self.n_slots):
            if not self.active[slot] and slot not in self._admissions:
                return slot
        # Typed transient pressure (see paged.PoolExhausted): the
        # engine holds the request instead of quarantining it.
        from tpushare.models.paged import PoolExhausted
        raise PoolExhausted("no free slots")

    def _finish_admit(self, slot: int, row, last_logits,
                      S: int, prompt: Optional[jnp.ndarray] = None,
                      drow=None, din_cache: bool = False) -> None:
        """Install a prefilled [1, max_len] row into the shared cache
        and activate the slot with its first sampled token. ``row``
        None means the admission already lives in the shared cache
        (fused chunks wrote it in place — nothing to install). With
        speculation, the draft cache installs here too: ``drow`` is a
        chunked admission's already-prefilled draft row (admit_step
        chunks the draft alongside the target so chunked admission
        bounds ALL prefill latency) and ``din_cache`` marks a draft
        that fused chunks already wrote into dcache; a whole admit
        leaves both unset and cold-prefills the whole prompt (draft
        KV never rides the target's prefix registry — int8-self
        drafts stream half the weights, so the unshared prefill is
        cheap relative to the bookkeeping of a second registry)."""
        if row is not None:
            self.cache = {kk: self.cache[kk].at[:, slot].set(row[kk][:, 0])
                          for kk in self.cache}
        if self.speculative and not din_cache:
            if drow is None:
                from tpushare.models.serving import bucket_len
                assert prompt is not None
                padded = jnp.zeros((min(bucket_len(S), self.max_len),),
                                   jnp.int32).at[:S].set(prompt[:S])
                drow = init_cache(self.draft_cfg, 1, self.max_len)
                _, _, drow = self._dfwd_prefill(
                    self.draft_params, padded[None, :], cache=drow,
                    pos_offset=0)
            self.dcache = {kk: self.dcache[kk].at[:, slot].set(
                drow[kk][:, 0]) for kk in self.dcache}
        self.lengths = self.lengths.at[slot].set(S)
        self._lengths_np[slot] = S
        nxt = self._sampler.pick(last_logits)[0].astype(jnp.int32)
        self.last_token = self.last_token.at[slot, 0].set(nxt)
        self.active[slot] = True
        self._active_dev = jnp.array(self.active)

    def _cached_prefix_len(self, prompt_np: np.ndarray) -> int:
        """Longest usable cached-prefix length: common prefix with the
        retained prompt, capped at S-1 (the admit must still forward
        at least the final token to produce the logits it samples
        from)."""
        if self._prefix is None:
            return 0
        cp, _ = self._prefix
        m = min(len(cp), len(prompt_np) - 1)
        if m <= 0:
            return 0
        neq = np.nonzero(cp[:m] != prompt_np[:m])[0]
        return int(neq[0]) if neq.size else m

    def admit(self, prompt: jnp.ndarray) -> int:
        """Prefill ``prompt`` [S] into a free slot; returns the slot.
        Prompts zero-pad to a power-of-two bucket (one compile per
        bucket); junk rows past S are never attended (length mask).
        With ``prefix_cache`` the longest common prefix with the
        retained row is reused and only the suffix prefills —
        bit-identical to a cold admit (KV is causal: a prefix's rows
        do not depend on what follows)."""
        from tpushare.models.serving import bucket_len
        slot = self._claim_slot(prompt)
        S = int(prompt.shape[0])
        prompt = jnp.asarray(prompt, jnp.int32)
        prompt_np = np.asarray(prompt)
        p = (self._cached_prefix_len(prompt_np)
             if self.prefix_cache else 0)
        if p > 0:
            # The suffix keeps its power-of-two width (compile
            # variants stay O(log max_len)); when the padded end would
            # spill past max_len, REUSE LESS (shrink p to fit) rather
            # than compiling a fresh width per distinct prefix length.
            # S < max_len guarantees S - p' <= width after shrinking.
            width = bucket_len(S - p)
            if p + width > self.max_len:
                p = max(0, self.max_len - width)
        if p > 0:
            row = self._prefix[1]        # immutable jnp rows: no copy
            toks = jnp.zeros((1, width), jnp.int32).at[
                0, :S - p].set(prompt[p:])
            logits, _, row = self._fwd(self.params, toks, cache=row,
                                       pos_offset=p)
            last = logits[:1, S - 1 - p]
        else:
            padded = jnp.zeros((min(bucket_len(S), self.max_len),),
                               prompt.dtype).at[:S].set(prompt)
            row = init_cache(self.cfg, 1, self.max_len)
            logits, _, row = self._fwd(self.params, padded[None, :],
                                       cache=row, pos_offset=0)
            last = logits[:1, S - 1]
        self.last_cached_len = p
        if self.prefix_cache:
            self.prefix_hit_tokens += p
            self.prefix_prompt_tokens += S
            self._prefix = (prompt_np, row)
        self._finish_admit(slot, row, last, S, prompt=prompt)
        return slot

    def admit_start(self, prompt: jnp.ndarray,
                    chunk_tokens: int = 256) -> int:
        """Begin a chunked admission: reserve a slot, prefill nothing;
        drive with admit_step() (one chunk per call). Dense rows make
        the MoE version of chunked prefill trivial next to the paged
        one: each chunk is a prefill continuation into the slot's own
        [1, max_len] row (forward's scalar-pos_offset mode), so
        chunked and whole admission are bit-identical by construction
        and there is nothing to re-gather between chunks."""
        slot = self._claim_slot(prompt)
        if chunk_tokens < 1:
            raise ValueError("chunk_tokens must be >= 1")
        prompt = jnp.asarray(prompt, jnp.int32)
        prompt_np = np.asarray(prompt)
        S = int(prompt.shape[0])
        # Chunked admits consult the prefix cache like whole admits:
        # the reused prefix simply counts as already-done chunks.
        p = (self._cached_prefix_len(prompt_np)
             if self.prefix_cache else 0)
        self.last_cached_len = p
        if self.prefix_cache:
            self.prefix_hit_tokens += p
            self.prefix_prompt_tokens += S
        st = {
            "prompt": prompt, "prompt_np": prompt_np,
            "S": S, "done": p,
            "chunk": int(chunk_tokens),
            "row": (self._prefix[1] if p > 0
                    else init_cache(self.cfg, 1, self.max_len)),
            "in_cache": False,          # fused chunks write the shared
            "din_cache": False,         # cache/dcache rows in place
        }
        if self.speculative:
            # The draft prefills in chunks too — from position 0
            # (draft KV never rides the target's prefix registry), so
            # a prefix-hit target may finish before the draft; the
            # admission completes only when BOTH rows are full.
            st["drow"] = init_cache(self.draft_cfg, 1, self.max_len)
            st["ddone"] = 0
        self._admissions[slot] = st
        return slot

    def _chunk_forward(self, fwd, params, prompt, row, done: int,
                       S: int, chunk: int, want_last: bool = True):
        """One bounded prefill chunk [done, end) into ``row`` — shared
        by the target and draft sides of a chunked admission, so no
        single forward on EITHER weight stream exceeds the admission
        chunk. The final (ragged) chunk zero-pads to a power-of-two
        bucket capped at ``chunk`` (compile variants stay O(log chunk));
        when the padded end would spill past max_len — where the
        clamped dynamic_update_slice would corrupt earlier rows — it
        falls back to the exact residual shape. Returns (last-position
        logits [1, V] on the final chunk when ``want_last`` else None,
        row, end)."""
        from tpushare.models.serving import bucket_len
        end = min(S, done + chunk)
        width = end - done
        if end >= S:                      # final chunk: bucket-pad
            width = min(bucket_len(end - done), chunk)
            if done + width > self.max_len:
                width = end - done
        toks = jnp.zeros((1, width), jnp.int32).at[0, :end - done].set(
            prompt[done:end])
        logits, _, row = fwd(params, toks, cache=row, pos_offset=done)
        last = (logits[:1, S - 1 - done]
                if want_last and end >= S else None)
        return last, row, end

    def admit_step(self, slot: int,
                   max_chunk_tokens: Optional[int] = None
                   ) -> Optional[int]:
        """Prefill the next chunk of a started admission — one target
        chunk AND (with speculation) one draft chunk per call, so
        chunked admission bounds the latency of BOTH prefills: the old
        whole-prompt draft prefill in _finish_admit reintroduced
        exactly the long-prompt stall chunked prefill exists to
        remove. Returns None while chunks remain on either side; the
        final call installs the rows, samples the first token,
        activates the slot, and returns that token."""
        st = self._admissions.get(slot)
        if st is None:
            raise ValueError(
                f"slot {slot} has no in-flight admission (already "
                f"completed, evicted, or admitted whole)")
        S, chunk = st["S"], st["chunk"]
        if max_chunk_tokens is not None:
            # The engine's tick budget bounds serial chunks too (the
            # admission-only half of the budget alternation).
            chunk = max(1, min(chunk, max_chunk_tokens))
        if st["done"] < S:
            if st["in_cache"]:
                # Fused chunks moved this admission into the shared
                # cache; serial chunks then operate on the slot's own
                # cache row (view in, scatter back).
                row = {kk: self.cache[kk][:, slot:slot + 1]
                       for kk in self.cache}
                last, row, st["done"] = self._chunk_forward(
                    self._fwd, self.params, st["prompt"], row,
                    st["done"], S, chunk)
                self.cache = {kk: self.cache[kk].at[:, slot].set(
                    row[kk][:, 0]) for kk in self.cache}
                self._track_admit_frontier(slot, st)
            else:
                last, st["row"], st["done"] = self._chunk_forward(
                    self._fwd, self.params, st["prompt"], st["row"],
                    st["done"], S, chunk)
            if last is not None:
                st["last"] = last
        if self.speculative and st["ddone"] < S:
            if st["din_cache"]:
                drow = {kk: self.dcache[kk][:, slot:slot + 1]
                        for kk in self.dcache}
                _, drow, st["ddone"] = self._chunk_forward(
                    self._dfwd_prefill, self.draft_params, st["prompt"],
                    drow, st["ddone"], S, chunk, want_last=False)
                self.dcache = {kk: self.dcache[kk].at[:, slot].set(
                    drow[kk][:, 0]) for kk in self.dcache}
            else:
                _, st["drow"], st["ddone"] = self._chunk_forward(
                    self._dfwd_prefill, self.draft_params, st["prompt"],
                    st["drow"], st["ddone"], S, chunk, want_last=False)
        if st["done"] < S or (self.speculative and st["ddone"] < S):
            return None
        del self._admissions[slot]
        if self.prefix_cache:
            self._prefix = (st["prompt_np"],
                            ({kk: self.cache[kk][:, slot:slot + 1]
                              for kk in self.cache} if st["in_cache"]
                             else st["row"]))
        self._finish_admit(slot,
                           None if st["in_cache"] else st["row"],
                           st["last"], S, prompt=st["prompt"],
                           drow=st.get("drow"),
                           din_cache=st["din_cache"])
        self.device_fetches += 1
        return int(host_scalar(self.last_token[slot, 0]))

    def _track_admit_frontier(self, slot: int, st) -> None:
        """An in-cache admission keeps lengths[slot] at its target
        write frontier: plain ticks and spec rounds write a junk KV
        row for every inactive slot at lengths[slot], and ``done`` is
        the one position the next chunk overwrites before attending —
        a stale 0 there would clobber the admission's real KV."""
        self.lengths = self.lengths.at[slot].set(st["done"])
        self._lengths_np[slot] = st["done"]

    def step(self, prefill_work: Optional[int] = None,
             max_chunk_tokens: Optional[int] = None):
        """One engine tick for every active slot -> {slot: token} (or
        {slot: [tokens...]} on a speculative round). Inactive slots
        compute garbage rows that are ignored (static shapes beat
        dynamic batching on TPU); a slot reaching max_len retires.
        A speculative server runs a spec round whenever every active
        slot has room for gamma+1 rows; near capacity it falls back
        to plain single-token ticks (a clamped scatter past max_len
        would corrupt earlier rows).

        ``prefill_work``: a slot with an in-flight chunked admission —
        its next chunk rides the SAME jitted forward as the decode
        rows (forward's ragged multi-token mode), capped at
        ``max_chunk_tokens``. A tick carrying a fused chunk is always
        a plain tick (spec rounds skip it; the draft side mirrors the
        decode tokens AND advances its own chunk in one draft
        forward). When the chunk completes the admission, the
        returned dict also carries that slot's first sampled token."""
        return self.step_async(prefill_work, max_chunk_tokens).finalize()

    def step_async(self, prefill_work: Optional[int] = None,
                   max_chunk_tokens: Optional[int] = None):
        """step() with the token fetch deferred (serving.PendingStep
        contract): all device work dispatches here; finalize()
        performs the ONE device->host fetch and builds the out
        dict."""
        from tpushare.models.serving import PendingStep
        if self.phase_timer is not None:
            # Measurement mode: open the chain so the instrumented
            # forward's marks attribute this tick's phases.
            self.phase_timer.start()
        if prefill_work is not None:
            if prefill_work not in self._admissions:
                raise ValueError(f"slot {prefill_work} has no "
                                 f"in-flight admission")
            return self._fused_tick_async(prefill_work, max_chunk_tokens)
        if not self.active.any():
            return PendingStep.done({})
        if self.speculative:
            # Spec-vs-plain decided from the HOST lengths mirror — the
            # old per-tick device_get here stalled the pipeline before
            # the round even started. The room check covers the whole
            # gamma×horizon block (spec_block_len): a clamped scatter
            # past max_len would corrupt earlier rows.
            if (self._lengths_np[self.active] + self.spec_block_len + 1
                    <= self.max_len).all():
                return self._spec_step_async()
            # Plain fallback on a speculative server still mirrors
            # the token into the draft cache: a skipped draft write
            # would leave a permanent zero row every later draft
            # query attends (the draft-cache-hole review catch).
            _, _, self.dcache = self._dfwd_prefill(
                self.draft_params, self.last_token, cache=self.dcache,
                pos_offset=self.lengths)
        logits, _, self.cache = self._fwd(
            self.params, self.last_token, cache=self.cache,
            pos_offset=self.lengths)
        nxt = self._sampler.pick(logits[:, 0]).astype(jnp.int32)
        self.lengths = self.lengths + self._active_dev.astype(jnp.int32)
        self.last_token = jnp.where(self._active_dev[:, None],
                                    nxt[:, None], self.last_token)
        # Host mirror advances by the same +1 per active slot; the
        # tick's ONE transfer is the token fetch itself.
        self._lengths_np[self.active] += 1
        slots = [int(s) for s in np.nonzero(self.active)[0]]
        retired = False
        for slot in slots:
            if int(self._lengths_np[slot]) >= self.max_len:
                self.active[slot] = False   # next write would be OOB
                retired = True
        if retired:
            self._active_dev = jnp.array(self.active)

        def _finalize(invalid):
            self.device_fetches += 1
            nxt_np = addressable_fetch(nxt)
            return {s: int(nxt_np[s]) for s in slots
                    if s not in invalid}

        return PendingStep(_finalize, slots=slots)

    def _fused_tick(self, slot: int,
                    max_chunk_tokens: Optional[int]) -> Dict[int, int]:
        """One fused engine tick: every active decode slot contributes
        1 token and admission ``slot`` contributes its next chunk, in
        ONE forward per weight stream (target always; with speculation
        the draft's decode-token mirror and its own admission chunk
        share one draft forward too). Spec rounds never run on a tick
        carrying a fused chunk — the plain-tick fallback semantics.
        Sync discipline unchanged: exactly one device->host transfer
        (the token fetch; the admission's first token rides it)."""
        return self._fused_tick_async(slot, max_chunk_tokens).finalize()

    def _fused_tick_async(self, slot: int,
                          max_chunk_tokens: Optional[int]):
        from tpushare.models.serving import (PendingStep,
                                             fused_chunk_span,
                                             fused_token_batch)
        st = self._admissions[slot]
        if not self.active.any():
            # No decode batch to fuse into: serial admission is the
            # fast path (and the bit-exactness oracle); the tick
            # budget still caps its chunk. Its fetch cannot be
            # deferred (the chunk loop needs the completion signal).
            tok = self.admit_step(slot,
                                  max_chunk_tokens=max_chunk_tokens)
            return PendingStep.done({} if tok is None else {slot: tok})
        S, chunk = st["S"], st["chunk"]
        done = st["done"]
        t_end = t_width = 0
        if done < S:
            t_end, t_width = fused_chunk_span(done, S, chunk,
                                              max_chunk_tokens)
        d_end = d_width = 0
        if self.speculative and st["ddone"] < S:
            d_end, d_width = fused_chunk_span(st["ddone"], S, chunk,
                                              max_chunk_tokens)
        if t_width == 0 and d_width == 0:
            return self.step_async()    # budget left no chunk room
        if t_width:
            if not st["in_cache"]:
                # First fused chunk: the admission's [0, done) KV
                # moves from the serial row into the shared cache
                # row, where fused forwards read and extend it.
                self.cache = {kk: self.cache[kk].at[:, slot].set(
                    st["row"][kk][:, 0]) for kk in self.cache}
                st["row"] = None
                st["in_cache"] = True
            toks = fused_token_batch(self.last_token, st["prompt"],
                                     done, t_end, t_width, slot)
            pos = self.lengths.at[slot].set(done)
            logits, _, self.cache = self._fwd(
                self.params, toks, cache=self.cache, pos_offset=pos)
            st["done"] = t_end
            if t_end >= S:
                st["last"] = logits[slot:slot + 1, S - 1 - done]
        else:
            # Target side already fully prefilled (prefix hit) while
            # the draft still chunks: plain decode forward.
            logits, _, self.cache = self._fwd(
                self.params, self.last_token, cache=self.cache,
                pos_offset=self.lengths)
        if self.speculative:
            if d_width:
                if not st["din_cache"]:
                    self.dcache = {kk: self.dcache[kk].at[:, slot].set(
                        st["drow"][kk][:, 0]) for kk in self.dcache}
                    st["drow"] = None
                    st["din_cache"] = True
                dtoks = fused_token_batch(self.last_token, st["prompt"],
                                          st["ddone"], d_end, d_width,
                                          slot)
                dpos = self.lengths.at[slot].set(st["ddone"])
                _, _, self.dcache = self._dfwd_prefill(
                    self.draft_params, dtoks, cache=self.dcache,
                    pos_offset=dpos)
                st["ddone"] = d_end
            else:
                # Draft mirror of the plain tick: a skipped draft
                # write would leave a permanent zero row every later
                # draft query attends (the draft-cache-hole catch).
                _, _, self.dcache = self._dfwd_prefill(
                    self.draft_params, self.last_token,
                    cache=self.dcache, pos_offset=self.lengths)
        final = (st["done"] >= S
                 and (not self.speculative or st["ddone"] >= S))
        if final:
            # Admission pick before the decode pick: matches the
            # serial engine order on the sampler's key stream.
            first = self._sampler.pick(st["last"]).astype(jnp.int32)
        nxt = self._sampler.pick(logits[:, 0]).astype(jnp.int32)
        self.lengths = self.lengths + self._active_dev.astype(jnp.int32)
        self.last_token = jnp.where(self._active_dev[:, None],
                                    nxt[:, None], self.last_token)
        self._lengths_np[self.active] += 1
        decode_slots = [int(s) for s in np.nonzero(self.active)[0]]
        for s in decode_slots:
            if int(self._lengths_np[s]) >= self.max_len:
                self.active[s] = False
        if final:
            del self._admissions[slot]
            # A side that never ran a fused chunk still holds its KV
            # in the admission row — install it (the draft can finish
            # on a fused draft chunk while the target completed
            # serially, and vice versa).
            if not st["in_cache"] and st["row"] is not None:
                self.cache = {kk: self.cache[kk].at[:, slot].set(
                    st["row"][kk][:, 0]) for kk in self.cache}
            if (self.speculative and not st["din_cache"]
                    and st.get("drow") is not None):
                self.dcache = {kk: self.dcache[kk].at[:, slot].set(
                    st["drow"][kk][:, 0]) for kk in self.dcache}
            if self.prefix_cache:
                self._prefix = (st["prompt_np"],
                                {kk: self.cache[kk][:, slot:slot + 1]
                                 for kk in self.cache})
            # Activation is dispatch-side device work: the slot's
            # first token stays on device (first[0] indexes the
            # device array, no fetch) until finalize.
            self.lengths = self.lengths.at[slot].set(S)
            self._lengths_np[slot] = S
            self.last_token = self.last_token.at[slot, 0].set(first[0])
            self.active[slot] = True
        elif st["in_cache"]:
            self._track_admit_frontier(slot, st)
        self._active_dev = jnp.array(self.active)
        out_slots = decode_slots + ([slot] if final else [])

        def _finalize(invalid):
            self.device_fetches += 1
            if final:
                nxt_np, first_np = addressable_fetch((nxt, first))
            else:
                nxt_np = addressable_fetch(nxt)
            out: Dict[int, int] = {}
            for s in decode_slots:
                if s not in invalid:
                    out[s] = int(nxt_np[s])
            if final and slot not in invalid:
                out[slot] = int(first_np[0])
            return out

        return PendingStep(_finalize, slots=out_slots)

    # -- speculation hooks (models/spec.py SpecDecodeMixin owns the
    # round driver; these supply the dense-row MoE mechanics) ---------

    def _spec_begin(self, h: int):
        """Dense rows need no capacity prep: the step() room guard
        (host mirror) already ensured every active slot holds the
        whole h+1 block below max_len."""
        del h
        return self.lengths

    def _spec_draft_step(self, tok, base, j: int):
        """One draft decode, all slots batched (the draft cache
        mirrors the target's positions)."""
        dl, _, self.dcache = self._dfwd(
            self.draft_params, tok, cache=self.dcache,
            pos_offset=base + j)
        return dl[:, 0]

    def _spec_draft_catchup(self, block, tok, base, h: int):
        """One multi-token write of the SAME block fills position
        base+h (the proposal loop only wrote inputs last..d_{h-1}) —
        without it, a fully-accepted round leaves a permanent
        draft-cache hole there, degrading every later proposal exactly
        in the high-acceptance regime speculation exists for. Rewrites
        of [base, base+h) are idempotent (same inputs, same
        positions)."""
        del tok, h
        _, _, self.dcache = self._dfwd_prefill(
            self.draft_params, block, cache=self.dcache,
            pos_offset=base)
        return self.dcache

    def _spec_verify(self, block, base):
        """ONE multi-token ragged verify for the whole batch."""
        tl, _, self.cache = self._fwd(self.params, block,
                                      cache=self.cache,
                                      pos_offset=base)
        return tl

    def _spec_commit(self, a_b, correction, active) -> None:
        self.lengths = self.lengths + (a_b + 1) * active.astype(
            jnp.int32)
        self.last_token = jnp.where(active[:, None], correction,
                                    self.last_token)

    def _spec_host_lengths(self):
        return self._lengths_np

    def _spec_capacity(self) -> int:
        return self.max_len

    def evict(self, slot: int) -> None:
        self._admissions.pop(slot, None)   # cancel mid-chunked admit
        self.active[slot] = False
        self._active_dev = jnp.array(self.active)
        self.lengths = self.lengths.at[slot].set(0)
        self._lengths_np[slot] = 0


def lm_loss(params, tokens: jnp.ndarray, cfg: MoEConfig, *,
            pctx: Optional[ParallelCtx] = None,
            ep_axis: Optional[str] = None,
            data_axes: Tuple[str, ...] = ()) -> jnp.ndarray:
    """Global loss: the nll term is pmean'd over ``data_axes`` (the aux
    term is already global — its statistics are pmean'd before the
    product). Differentiating this global scalar under shard_map gives
    correct grads with NO post-grad reductions (see models/training.py
    module docstring for the double-count hazard)."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    logits, aux = forward(params, inputs, cfg, pctx=pctx, ep_axis=ep_axis,
                          data_axes=data_axes)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)
    loss = jnp.mean(nll)
    for ax in data_axes:
        loss = jax.lax.pmean(loss, ax)
    return loss + cfg.aux_loss_weight * aux


def sgd_train_step(params, tokens, cfg: MoEConfig, *, lr: float = 1e-3,
                   pctx: Optional[ParallelCtx] = None,
                   ep_axis: Optional[str] = None,
                   data_axes: Tuple[str, ...] = ()):
    """One SGD step on the global loss. No post-grad reductions:
    the vma-aware shard_map transpose already accumulates replicated-
    param cotangents across ranks (with the loss pmean's 1/n), and
    ep/tp-sharded params keep their local grads (verified exactly
    against single-device in tests/test_moe.py)."""
    import functools as _ft
    loss, grads = jax.value_and_grad(
        _ft.partial(lm_loss, cfg=cfg, pctx=pctx, ep_axis=ep_axis,
                    data_axes=data_axes))(params, tokens)
    new_params = jax.tree.map(
        lambda p, g: (p - lr * g.astype(jnp.float32)).astype(p.dtype),
        params, grads)
    return new_params, loss


def adamw_train_step(params, opt_state, tokens, cfg: MoEConfig, *,
                     lr: float = 1e-3, weight_decay: float = 0.0,
                     pctx: Optional[ParallelCtx] = None,
                     ep_axis: Optional[str] = None,
                     data_axes: Tuple[str, ...] = ()):
    """One AdamW step on the global MoE loss (nll + aux); moments
    mirror the param tree so they shard with param_specs. Returns
    (params, state, loss)."""
    import functools as _ft
    from tpushare.models.training import apply_adamw
    loss, grads = jax.value_and_grad(
        _ft.partial(lm_loss, cfg=cfg, pctx=pctx, ep_axis=ep_axis,
                    data_axes=data_axes))(params, tokens)
    new_p, new_state = apply_adamw(params, grads, opt_state, lr=lr,
                                   weight_decay=weight_decay)
    return new_p, new_state, loss


def make_adamw_spmd_train_step(cfg: MoEConfig, mesh, *, lr: float = 1e-3,
                               weight_decay: float = 0.0):
    """AdamW over the dp×sp×tp×ep mesh; moments shard like the params
    (ep-sharded experts get ep-sharded moments for free). Same batch
    layout rules as make_spmd_train_step (routing='a2a' makes ep a
    data axis)."""
    from jax import shard_map
    import functools as _ft
    from tpushare.models.training import adamw_init, opt_state_specs
    if cfg.n_experts % mesh.shape["ep"]:
        raise ValueError(f"ep={mesh.shape['ep']} must divide "
                         f"n_experts={cfg.n_experts}")
    if cfg.routing == "a2a":
        batch_spec = P(("dp", "ep"), "sp")
        data_axes = ("dp", "ep", "sp")
    else:
        batch_spec = P("dp", "sp")
        data_axes = ("dp", "sp")
    specs = param_specs(cfg)
    step = shard_map(
        _ft.partial(adamw_train_step, cfg=cfg, lr=lr,
                    weight_decay=weight_decay,
                    pctx=ParallelCtx(tp="tp", sp="sp"), ep_axis="ep",
                    data_axes=data_axes),
        mesh=mesh,
        in_specs=(specs, opt_state_specs(specs), batch_spec),
        out_specs=(specs, opt_state_specs(specs), P()),
    )

    def opt_init(params):
        # Moments created directly sharded (see the streaming-fsdp
        # opt_init rationale in models/training.py).
        shardings = jax.tree.map(
            lambda sp: jax.sharding.NamedSharding(mesh, sp),
            {"mu": specs, "nu": specs, "count": P()})
        return jax.jit(adamw_init, out_shardings=shardings)(params)

    return jax.jit(step), opt_init


def make_spmd_train_step(cfg: MoEConfig, mesh, *, lr: float = 1e-3):
    """Fully-sharded MoE train step over a dp×sp×tp×ep mesh.

    Under routing="psum" the batch shards over (dp, sp) and is
    replicated across ep; under routing="a2a" ep is an additional data
    axis — the batch shards over ((dp, ep), sp) and the all_to_all
    exchange inside _moe_ffn carries tokens to their expert owners."""
    from jax import shard_map
    import functools as _ft
    if cfg.n_experts % mesh.shape["ep"]:
        raise ValueError(f"ep={mesh.shape['ep']} must divide "
                         f"n_experts={cfg.n_experts}")
    if cfg.routing == "a2a":
        batch_spec = P(("dp", "ep"), "sp")
        data_axes = ("dp", "ep", "sp")
    else:
        batch_spec = P("dp", "sp")
        data_axes = ("dp", "sp")
    step = shard_map(
        _ft.partial(sgd_train_step, cfg=cfg, lr=lr,
                    pctx=ParallelCtx(tp="tp", sp="sp"), ep_axis="ep",
                    data_axes=data_axes),
        mesh=mesh,
        in_specs=(param_specs(cfg), batch_spec),
        out_specs=(param_specs(cfg), P()),
    )
    return jax.jit(step)
