"""Pipeline parallelism for the MoE LM (pp × ep × tp — Mixtral-style).

The stacked MoE layers shard over ``pp`` exactly like the dense
pipeline (models/pipeline.py): contiguous layer blocks per stage, SPMD
fill/drain with one ppermute hop per round, stage identity from
axis_index. Inside each stage the MoE FFN keeps its expert parallelism
(experts over ``ep``, per-expert hidden over ``tp`` — moe._moe_ffn
unchanged), so one step composes pipeline depth with expert width.

Schedule: GPipe (autodiff through the fill/drain loop). The manual-VJP
1F1B/interleaved schedules are dense-only for now — their machinery is
model-agnostic except the block, but MoE's per-round aux-loss
accumulation through a manual VJP is real new surface; the seam is the
same ``schedule`` argument if it becomes worth it.

Routing: "psum" and "dropless" compose (tokens replicated across ep,
experts combine via psum / ragged_dot). "a2a" is REJECTED: it makes ep
a data axis (tokens sharded over ep), which contradicts the pipeline's
replicated microbatch queue.

The aux (load-balancing) loss needs care the dense pipeline doesn't:
every stage computes aux for every round, but only rounds carrying a
real microbatch may contribute — garbage fill/drain rounds would bias
the router loss. Valid rounds are masked per stage and the psum over
pp divides by P·M. Note the semantics this implies: aux is NONLINEAR
in the batch (routing fractions of a microbatch != of the full batch),
so the optimized objective is the mean of per-MICROBATCH losses — the
standard microbatched-MoE objective, exact-parity tested against a
per-microbatch single-device reference (not against the full-batch
aux, which no microbatched trainer computes).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

from tpushare.models.moe import (
    MoEConfig, _moe_ffn, param_specs as moe_param_specs,
)
from tpushare.models.transformer import ParallelCtx
from tpushare.ops import apply_rotary, attention, rms_norm, rotary_embedding


def param_specs(cfg: MoEConfig, *, pp: str = "pp", tp: str = "tp",
                ep: str = "ep") -> Dict[str, Any]:
    """MoE specs with the stacked-layer axis sharded over pp (experts
    stay over ep, expert hidden over tp)."""
    specs = moe_param_specs(cfg, tp=tp, ep=ep)
    specs["layers"] = {k: P(pp, *tuple(s)[1:])
                      for k, s in specs["layers"].items()}
    return specs


def moe_pipelined_lm_loss(params, inputs: jnp.ndarray,
                          targets: jnp.ndarray, cfg: MoEConfig, *,
                          pp_axis: str = "pp",
                          tp_axis: Optional[str] = "tp",
                          ep_axis: Optional[str] = "ep",
                          data_axes: Tuple[str, ...] = (),
                          n_microbatches: int) -> jnp.ndarray:
    """Global MoE loss (nll + aux) through the pp pipeline.

    inputs/targets [B, S] pre-shifted and aligned; B divides by
    n_microbatches. Call inside shard_map with params per
    param_specs(). Returns the GLOBAL scalar (masked psums over pp,
    pmean over data_axes) so differentiating it yields correct grads.
    """
    if cfg.routing == "a2a":
        raise NotImplementedError(
            "routing='a2a' shards tokens over ep (ep as a data axis) "
            "and cannot ride the pipeline's replicated microbatches; "
            "use routing='psum' or 'dropless' with pp")
    n_stages = jax.lax.psum(1, pp_axis)
    stage = jax.lax.axis_index(pp_axis)
    M = n_microbatches
    B, S = inputs.shape
    assert B % M == 0, f"batch {B} not divisible into {M} microbatches"
    Bm = B // M
    Dh = cfg.head_dim
    pctx = ParallelCtx(tp=tp_axis)

    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (Bm, S))
    cos, sin = rotary_embedding(positions, Dh, base=cfg.rope_base,
                                scaling=cfg.rope_scaling)

    x_mb = params["embed"][inputs.reshape(M, Bm, S)].astype(cfg.dtype)

    def block(x, layer):
        h = rms_norm(x, layer["ln1"], eps=cfg.norm_eps)
        H = layer["wq"].shape[-1] // Dh
        Hkv = layer["wk"].shape[-1] // Dh
        q = apply_rotary((h @ layer["wq"]).reshape(Bm, S, H, Dh), cos, sin)
        k = apply_rotary((h @ layer["wk"]).reshape(Bm, S, Hkv, Dh), cos, sin)
        v = (h @ layer["wv"]).reshape(Bm, S, Hkv, Dh)
        attn = attention(q, k, v, causal=True)
        o = attn.reshape(Bm, S, H * Dh) @ layer["wo"]
        if tp_axis is not None:
            o = jax.lax.psum(o, tp_axis)
        x = x + o
        h = rms_norm(x, layer["ln2"], eps=cfg.norm_eps)
        ff, aux = _moe_ffn(h, layer, cfg, pctx, ep_axis, data_axes)
        return x + ff, aux

    def local_layers(x):
        def body(x, layer):
            return block(x, layer)
        x, aux_layers = jax.lax.scan(body, x, params["layers"])
        return x, jnp.mean(aux_layers)

    perm = [(i, i + 1) for i in range(n_stages - 1)]

    def step(t, carry):
        inflight, outputs, aux_acc = carry
        mb = jax.lax.dynamic_index_in_dim(x_mb, jnp.minimum(t, M - 1), 0,
                                          keepdims=False)
        inp = jnp.where(stage == 0, mb, inflight)
        act, aux = local_layers(inp)
        # Only rounds carrying a REAL microbatch feed the router loss.
        valid = jnp.logical_and(t - stage >= 0, t - stage < M)
        aux_acc = aux_acc + jnp.where(valid, aux, 0.0)
        slot = t - (n_stages - 1)
        write = jnp.logical_and(stage == n_stages - 1, slot >= 0)
        upd = jax.lax.dynamic_update_index_in_dim(
            outputs, act.astype(outputs.dtype), jnp.maximum(slot, 0), 0)
        outputs = jnp.where(write, upd, outputs)
        inflight = jax.lax.ppermute(act, pp_axis, perm)
        return inflight, outputs, aux_acc

    vma = {pp_axis} | set(jax.typeof(x_mb).vma)

    def pvary(x):
        return jax.lax.pcast(x, tuple(vma), to="varying")

    inflight0 = pvary(jnp.zeros((Bm, S, cfg.d_model), cfg.dtype))
    outputs0 = pvary(jnp.zeros((M, Bm, S, cfg.d_model), cfg.dtype))
    aux0 = pvary(jnp.zeros((), jnp.float32))
    _, outputs, aux_acc = jax.lax.fori_loop(
        0, M + n_stages - 1, step, (inflight0, outputs0, aux0))

    x = outputs.reshape(B, S, cfg.d_model)
    x = rms_norm(x, params["final_norm"], eps=cfg.norm_eps)
    unembed = (params["embed"].T if cfg.tie_embeddings
               else params["unembed"]).astype(cfg.dtype)
    logits = (x @ unembed).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)
    local = jnp.where(stage == n_stages - 1, jnp.mean(nll), 0.0)
    loss = jax.lax.psum(local, pp_axis)
    # Every stage contributed M valid per-layer-mean aux values; the
    # psum/(P*M) is the global mean over layers and microbatches
    # (stages hold equal layer counts).
    aux = jax.lax.psum(aux_acc, pp_axis) / (n_stages * M)
    for ax in data_axes:
        loss = jax.lax.pmean(loss, ax)
        # aux statistics are already pmean'd over data_axes inside
        # _moe_ffn (moe.lm_loss's contract), so this pmean is value-
        # neutral — it exists to clear the vma tag the pvary'd loop
        # carry stamped on aux (equal values, still typed varying).
        aux = jax.lax.pmean(aux, ax)
    return loss + cfg.aux_loss_weight * aux


def _check_mesh(cfg: MoEConfig, mesh: Mesh) -> None:
    if cfg.n_experts % mesh.shape["ep"]:
        raise ValueError(f"ep={mesh.shape['ep']} must divide "
                         f"n_experts={cfg.n_experts}")


def _loss_and_grads(params, inputs, targets, cfg: MoEConfig,
                    n_microbatches: int):
    return jax.value_and_grad(functools.partial(
        moe_pipelined_lm_loss, cfg=cfg, pp_axis="pp", tp_axis="tp",
        ep_axis="ep", data_axes=("dp",),
        n_microbatches=n_microbatches))(params, inputs, targets)


def make_moe_pp_train_step(cfg: MoEConfig, mesh: Mesh, *,
                           n_microbatches: int, lr: float = 1e-3):
    """SGD train step over a pp×ep×tp (×dp) mesh for the MoE LM."""
    from tpushare.models.training import _sgd_update
    _check_mesh(cfg, mesh)

    def _step(params, inputs, targets):
        loss, grads = _loss_and_grads(params, inputs, targets, cfg,
                                      n_microbatches)
        return _sgd_update(params, grads, lr), loss

    specs = param_specs(cfg)
    inner = shard_map(_step, mesh=mesh,
                      in_specs=(specs, P("dp", None), P("dp", None)),
                      out_specs=(specs, P()))

    def step(params, tokens):
        return inner(params, tokens[:, :-1], tokens[:, 1:])

    return jax.jit(step)


def make_moe_pp_adamw_train_step(cfg: MoEConfig, mesh: Mesh, *,
                                 n_microbatches: int, lr: float = 1e-3,
                                 weight_decay: float = 0.0):
    """AdamW over the pp×ep×tp (×dp) mesh: fp32 moments mirror the
    param tree and shard with param_specs — each stage holds optimizer
    state only for its own layer shard, each ep rank only for its own
    experts. Init state with training.adamw_init."""
    from tpushare.models.training import apply_adamw, opt_state_specs
    _check_mesh(cfg, mesh)

    def _step(params, opt_state, inputs, targets):
        loss, grads = _loss_and_grads(params, inputs, targets, cfg,
                                      n_microbatches)
        new_p, new_state = apply_adamw(params, grads, opt_state,
                                       lr=lr, weight_decay=weight_decay)
        return new_p, new_state, loss

    specs = param_specs(cfg)
    ospecs = opt_state_specs(specs)
    inner = shard_map(_step, mesh=mesh,
                      in_specs=(specs, ospecs, P("dp", None),
                                P("dp", None)),
                      out_specs=(specs, ospecs, P()))

    def step(params, opt_state, tokens):
        return inner(params, opt_state, tokens[:, :-1], tokens[:, 1:])

    return jax.jit(step)
