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
from jax.sharding import PartitionSpec as P

from tpushare.ops import apply_rotary, attention, rms_norm, rotary_embedding
from tpushare.models.transformer import ParallelCtx, _act
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
