"""Training step for the transformer LM — SPMD over the full mesh.

One functional train step (loss → grad → update) that runs three ways
with the same code: single-device (tests), pjit-auto-sharded (annotate
params with param_specs and let XLA insert collectives), or fully
manual under shard_map with a ParallelCtx (tp psum inside the model,
sp ring attention, dp/sp handled here). The driver's dryrun_multichip
exercises the shard_map path on a dp×sp×tp mesh.

Gradient correctness under shard_map: the loss is made GLOBAL (pmean
over the data axes) *before* jax.grad. The vma-aware shard_map
transpose then inserts the cross-rank psums for replicated-param
cotangents itself, with the pmean's 1/n built in — differentiating a
shard-local loss and pmean'ing grads afterwards double-counts exactly
by the data-axis size (caught by the exact-parity tests in
tests/test_transformer.py).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

from tpushare.models.transformer import (
    ParallelCtx, TransformerConfig, forward, param_specs,
)


def xent_loss(params: Dict[str, Any], inputs: jnp.ndarray,
              targets: jnp.ndarray, cfg: TransformerConfig, *,
              pctx: Optional[ParallelCtx] = None,
              data_axes: Tuple[str, ...] = (),
              layers_hook=None) -> jnp.ndarray:
    """Cross-entropy of forward(inputs) against aligned ``targets``
    (both [B, S]). With ``data_axes`` the local mean is pmean'd into
    the global mean (equal shard sizes)."""
    logits, _ = forward(params, inputs, cfg, pctx=pctx,
                        layers_hook=layers_hook)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)
    loss = jnp.mean(nll)
    for ax in data_axes:
        loss = jax.lax.pmean(loss, ax)
    return loss


def lm_loss(params: Dict[str, Any], tokens: jnp.ndarray,
            cfg: TransformerConfig, *,
            pctx: Optional[ParallelCtx] = None,
            data_axes: Tuple[str, ...] = ()) -> jnp.ndarray:
    """Next-token cross-entropy over tokens [B, S+1]."""
    return xent_loss(params, tokens[:, :-1], tokens[:, 1:], cfg,
                     pctx=pctx, data_axes=data_axes)


def _sgd_update(params, grads, lr):
    """The one SGD update rule every step variant shares (fp32 math,
    param dtype preserved) — exact-parity tests compare paths built on
    this, so there is exactly one copy."""
    return jax.tree.map(
        lambda p, g: (p - lr * g.astype(jnp.float32)).astype(p.dtype),
        params, grads)


def sgd_train_step(params: Dict[str, Any], tokens: jnp.ndarray,
                   cfg: TransformerConfig, *, lr: float = 1e-3,
                   pctx: Optional[ParallelCtx] = None,
                   data_axes: Tuple[str, ...] = ()
                   ) -> Tuple[Dict[str, Any], jnp.ndarray]:
    """One SGD step on the (global) loss; no post-grad reductions —
    see module docstring."""
    loss, grads = jax.value_and_grad(
        functools.partial(lm_loss, cfg=cfg, pctx=pctx,
                          data_axes=data_axes))(params, tokens)
    return _sgd_update(params, grads, lr), loss


def _sgd_xent_step(params, inputs, targets, cfg, *, lr, pctx, data_axes):
    loss, grads = jax.value_and_grad(
        functools.partial(xent_loss, cfg=cfg, pctx=pctx,
                          data_axes=data_axes))(params, inputs, targets)
    return _sgd_update(params, grads, lr), loss


def _reject_axes(mesh: Mesh, axes: Tuple[str, ...]) -> None:
    for ax in axes:
        if mesh.shape[ax] > 1:
            raise NotImplementedError(
                f"{ax} axis not used by the dense-LM train step "
                f"(pp: models.pipeline; ep: models.moe)")


def make_spmd_train_step(cfg: TransformerConfig, mesh: Mesh, *,
                         lr: float = 1e-3, sp_impl: str = "ring"):
    """Build the fully-sharded train step for ``mesh``.

    Layout: params tp-sharded per param_specs; batch tokens [B, S+1]
    with batch over dp and sequence over sp (cross-shard attention via
    ring attention, or DeepSpeed-Ulysses all_to_all with
    sp_impl="a2a" — parallel/ulysses.py for the trade-offs). The next-token shift happens
    OUTSIDE the shard_map: inputs tokens[:, :-1] and targets
    tokens[:, 1:] are sharded (dp, sp) as two aligned [B, S] arrays, so
    every sp shard holds matching (input, target) pairs — the sp loss
    is exact, including at shard boundaries (XLA inserts the halo
    exchange when resharding the two slices).
    """
    if mesh.shape["fsdp"] > 1:
        raise NotImplementedError(
            "use make_fsdp_train_step for the manual-fsdp schedule, or "
            "pjit auto sharding with param_specs(fsdp='fsdp')")
    _reject_axes(mesh, ("pp", "ep"))
    # Name every axis even at size 1: size-1 collectives are free
    # no-ops, and naming them keeps the varying-manual-axes types
    # uniform (params are tp-tagged by their specs regardless of tp
    # size, so the model's tp psums must always run to clear the tag).
    if sp_impl not in ("ring", "a2a"):
        raise ValueError(f"unknown sp_impl {sp_impl!r}; 'ring' or 'a2a'")
    pctx = ParallelCtx(tp="tp", sp="sp", sp_impl=sp_impl)

    specs = param_specs(cfg, tp="tp")
    batch_spec = P("dp", "sp")

    inner = shard_map(
        functools.partial(_sgd_xent_step, cfg=cfg, lr=lr, pctx=pctx,
                          data_axes=("dp", "sp")),
        mesh=mesh,
        in_specs=(specs, batch_spec, batch_spec),
        out_specs=(specs, P()),
    )

    def step(params, tokens):
        return inner(params, tokens[:, :-1], tokens[:, 1:])

    return jax.jit(step)


# --- manual FSDP (ZeRO-style sharded storage) ------------------------------

def fsdp_shard_params(params: Dict[str, Any], n_shards: int,
                      mesh: Optional[Mesh] = None) -> Dict[str, Any]:
    """Flatten each leaf to [n_shards, ceil(size/n_shards)] (zero-padded)
    — the storage layout of the manual fsdp step. With ``mesh``, place
    each leaf sharded P('fsdp') so every device holds only its slice."""
    def shard(p):
        n = p.size
        c = -(-n // n_shards)
        flat = jnp.pad(p.reshape(-1), (0, n_shards * c - n))
        out = flat.reshape(n_shards, c)
        if mesh is not None:
            out = jax.device_put(
                out, jax.sharding.NamedSharding(mesh, P("fsdp")))
        return out
    return jax.tree.map(shard, params)


def fsdp_unshard_params(flat: Dict[str, Any],
                        like: Dict[str, Any]) -> Dict[str, Any]:
    """Inverse of fsdp_shard_params; ``like`` supplies shapes/dtypes
    (e.g. jax.eval_shape of init_params)."""
    return jax.tree.map(
        lambda f, l: f.reshape(-1)[:l.size].reshape(l.shape).astype(l.dtype),
        flat, like)


def _fsdp_sgd_step(flat, inputs, targets, *, like, cfg, lr, pctx,
                   data_axes):
    """Runs per-rank inside shard_map: gather full params, compute the
    global loss, let the transpose reduce-scatter the grads.

    The manual collectives are exactly FSDP's pair: the forward
    all_gathers each (flat, padded) leaf back to a full param, and
    because the loss is made global (pmean over the data axes, fsdp
    among them) *before* jax.grad, the VJP of that all_gather IS the
    reduce_scatter — each rank receives the sum of all ranks' gradient
    contributions for just its own shard, already carrying the pmean's
    1/n. The SGD update then touches only rank-local state. Nothing
    full-size persists between steps; full params are materialized
    transiently per step (per-layer streaming gather inside the scan is
    the production refinement, see ROADMAP)."""
    def loss_fn(flat):
        gathered = jax.tree.map(
            lambda f: jax.lax.all_gather(f, "fsdp", axis=0, tiled=True),
            flat)
        params = fsdp_unshard_params(gathered, like)
        return xent_loss(params, inputs, targets, cfg, pctx=pctx,
                         data_axes=data_axes)
    loss, gflat = jax.value_and_grad(loss_fn)(flat)
    return _sgd_update(flat, gflat, lr), loss


def fsdp_stream_shard_params(params: Dict[str, Any], n_shards: int,
                             mesh: Optional[Mesh] = None) -> Dict[str, Any]:
    """Storage layout for the STREAMING fsdp step: non-layer leaves
    flatten to [F*c] (sharded P('fsdp')); layer-stacked leaves keep
    their leading L dim and flatten per layer to [L, F*c] (sharded
    P(None, 'fsdp')), so the forward can all_gather ONE layer at a
    time inside the scan instead of the whole stack up front."""
    def flat_pad(p, lead_L: bool):
        if lead_L:
            L = p.shape[0]
            n = p.size // L
            c = -(-n // n_shards)
            out = jnp.pad(p.reshape(L, n), ((0, 0), (0, n_shards * c - n)))
            spec = P(None, "fsdp")
        else:
            n = p.size
            c = -(-n // n_shards)
            out = jnp.pad(p.reshape(-1), (0, n_shards * c - n))
            spec = P("fsdp")
        if mesh is not None:
            out = jax.device_put(out, jax.sharding.NamedSharding(mesh, spec))
        return out
    return {k: (jax.tree.map(functools.partial(flat_pad, lead_L=True), v)
                if k == "layers"
                else jax.tree.map(functools.partial(flat_pad, lead_L=False),
                                  v))
            for k, v in params.items()}


def _unflatten_like(flat, like):
    """[>=size] zero-padded flat leaf -> ``like``'s shape/dtype."""
    return jax.tree.map(
        lambda f, l: f.reshape(-1)[:l.size].reshape(l.shape).astype(l.dtype),
        flat, like)


def _fsdp_stream_value_and_grad(flat, inputs, targets, *, like,
                                layer_like, cfg, pctx, data_axes):
    """Per-rank streaming-fsdp loss and grads (shared by the SGD and
    AdamW steps): gather the small non-layer leaves up front, and hand
    forward() a layers_hook that all_gathers each layer's flat slice
    inside the scan — peak gathered-param memory is ONE layer (plus
    embed), and under remat the backward re-gathers per layer so the
    hook's VJP is a per-layer reduce-scatter."""
    gather = lambda f: jax.lax.all_gather(f, "fsdp", axis=0, tiled=True)

    def hook(layer_flat):
        return _unflatten_like(jax.tree.map(gather, layer_flat),
                               layer_like)

    def loss_fn(flat):
        top = {k: v for k, v in flat.items() if k != "layers"}
        params = _unflatten_like(
            jax.tree.map(gather, top),
            {k: v for k, v in like.items() if k != "layers"})
        params["layers"] = flat["layers"]      # consumed via the hook
        return xent_loss(params, inputs, targets, cfg, pctx=pctx,
                         data_axes=data_axes, layers_hook=hook)
    return jax.value_and_grad(loss_fn)(flat)


def _fsdp_stream_sgd_step(flat, inputs, targets, *, like, layer_like, cfg,
                          lr, pctx, data_axes):
    loss, gflat = _fsdp_stream_value_and_grad(
        flat, inputs, targets, like=like, layer_like=layer_like, cfg=cfg,
        pctx=pctx, data_axes=data_axes)
    return _sgd_update(flat, gflat, lr), loss


def _fsdp_stream_adamw_step(flat, opt_state, inputs, targets, *, like,
                            layer_like, cfg, lr, weight_decay, pctx,
                            data_axes):
    """AdamW on the streaming-fsdp layout: same gather/hook forward as
    the SGD step (shared _fsdp_stream_value_and_grad); moments live in
    the SAME flat-sharded layout as the params (AdamW is elementwise,
    so the update is entirely shard-local — this IS ZeRO: optimizer
    state per device is size/F). Padding slots keep zero grads and
    zero moments."""
    loss, gflat = _fsdp_stream_value_and_grad(
        flat, inputs, targets, like=like, layer_like=layer_like, cfg=cfg,
        pctx=pctx, data_axes=data_axes)
    new_flat, new_state = apply_adamw(flat, gflat, opt_state, lr=lr,
                                      weight_decay=weight_decay)
    return new_flat, new_state, loss


def _fsdp_stream_setup(cfg: TransformerConfig, mesh: Mesh):
    """Shared validation + layout contract of the streaming-fsdp
    factories (single source of truth for specs/batch layout)."""
    if not cfg.remat:
        raise ValueError(
            "streaming fsdp requires cfg.remat=True: without "
            "checkpointing the block the backward saves all gathered "
            "layers and the one-layer peak-memory property is lost "
            "(use make_fsdp_train_step)")
    if mesh.shape["tp"] > 1:
        raise NotImplementedError(
            "manual fsdp with tp: use pjit auto sharding with "
            "param_specs(tp='tp', fsdp='fsdp')")
    _reject_axes(mesh, ("pp", "ep"))
    from tpushare.models.transformer import init_params
    like = jax.eval_shape(lambda k: init_params(k, cfg),
                          jax.random.PRNGKey(0))
    layer_like = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape[1:], l.dtype),
        like["layers"])
    flat_specs = {k: (jax.tree.map(lambda _: P(None, "fsdp"), v)
                      if k == "layers"
                      else jax.tree.map(lambda _: P("fsdp"), v))
                  for k, v in like.items()}
    return (like, layer_like, flat_specs, P(("dp", "fsdp"), "sp"),
            ParallelCtx(tp=None, sp="sp"), mesh.shape["fsdp"])


def make_fsdp_stream_train_step(cfg: TransformerConfig, mesh: Mesh, *,
                                lr: float = 1e-3):
    """Streaming-gather variant of make_fsdp_train_step (same math,
    exact-parity tested): layer params are gathered one layer at a
    time inside the model's scan, so transient full-param memory is
    embed + one layer instead of the whole tree. Returns
    (jitted step, shard_fn).

    Requires cfg.remat (see _fsdp_stream_setup)."""
    like, layer_like, flat_specs, batch_spec, pctx, F = (
        _fsdp_stream_setup(cfg, mesh))

    inner = shard_map(
        functools.partial(_fsdp_stream_sgd_step, like=like,
                          layer_like=layer_like, cfg=cfg, lr=lr, pctx=pctx,
                          data_axes=("dp", "fsdp", "sp")),
        mesh=mesh,
        in_specs=(flat_specs, batch_spec, batch_spec),
        out_specs=(flat_specs, P()),
    )

    def step(flat_params, tokens):
        return inner(flat_params, tokens[:, :-1], tokens[:, 1:])

    return jax.jit(step), functools.partial(fsdp_stream_shard_params,
                                            n_shards=F, mesh=mesh)


def make_fsdp_stream_adamw_step(cfg: TransformerConfig, mesh: Mesh, *,
                                lr: float = 1e-3,
                                weight_decay: float = 0.0):
    """AdamW on the streaming-fsdp layout — full ZeRO: params,
    gradients, AND optimizer moments all sharded 1/F per device, layer
    params gathered one at a time inside the scan. Returns
    (jitted step, shard_fn, opt_init_fn); step(flat, opt_state,
    tokens) -> (flat, opt_state, loss). Same remat requirement as
    make_fsdp_stream_train_step."""
    like, layer_like, flat_specs, batch_spec, pctx, F = (
        _fsdp_stream_setup(cfg, mesh))
    ospecs = opt_state_specs(flat_specs)

    inner = shard_map(
        functools.partial(_fsdp_stream_adamw_step, like=like,
                          layer_like=layer_like, cfg=cfg, lr=lr,
                          weight_decay=weight_decay, pctx=pctx,
                          data_axes=("dp", "fsdp", "sp")),
        mesh=mesh,
        in_specs=(flat_specs, ospecs, batch_spec, batch_spec),
        out_specs=(flat_specs, ospecs, P()),
    )

    def step(flat_params, opt_state, tokens):
        return inner(flat_params, opt_state, tokens[:, :-1],
                     tokens[:, 1:])

    def opt_init(flat_params):
        # Shared schema (adamw_init) created DIRECTLY sharded via jit
        # out_shardings — the fp32 moments are 2x the params' bytes,
        # and even a transient unsharded materialization would defeat
        # the ZeRO layout this API exists for.
        shardings = jax.tree.map(
            lambda sp: jax.sharding.NamedSharding(mesh, sp),
            {"mu": flat_specs, "nu": flat_specs, "count": P()})
        return jax.jit(adamw_init, out_shardings=shardings)(flat_params)

    return (jax.jit(step),
            functools.partial(fsdp_stream_shard_params, n_shards=F,
                              mesh=mesh),
            opt_init)


def fsdp_stream_unshard_params(flat: Dict[str, Any],
                               like: Dict[str, Any]) -> Dict[str, Any]:
    """Inverse of fsdp_stream_shard_params (checkpoint/eval export)."""
    out = {}
    for k, v in flat.items():
        if k == "layers":
            out[k] = jax.tree.map(
                lambda f, l: (f[:, :l.size // l.shape[0]]
                              .reshape(l.shape).astype(l.dtype)),
                v, like["layers"])
        else:
            out[k] = _unflatten_like(v, like[k])
    return out


def make_fsdp_train_step(cfg: TransformerConfig, mesh: Mesh, *,
                         lr: float = 1e-3):
    """Manual shard_map FSDP train step over mesh axes fsdp×dp×sp.

    Params live sharded: each leaf flattened and split along the fsdp
    axis (fsdp_shard_params), so per-device param memory is size/F.
    The fsdp axis is also a data axis (FSDP is data parallelism with
    sharded storage): tokens shard over (dp, fsdp) jointly. tp is
    mutually exclusive with this step (tp-sharded params would need a
    two-level gather); use the pjit auto path param_specs(tp, fsdp) to
    combine them.
    """
    if mesh.shape["tp"] > 1:
        raise NotImplementedError(
            "manual fsdp with tp: use pjit auto sharding with "
            "param_specs(tp='tp', fsdp='fsdp')")
    _reject_axes(mesh, ("pp", "ep"))
    F = mesh.shape["fsdp"]
    from tpushare.models.transformer import init_params
    like = jax.eval_shape(lambda k: init_params(k, cfg),
                          jax.random.PRNGKey(0))
    pctx = ParallelCtx(tp=None, sp="sp")

    flat_specs = jax.tree.map(lambda _: P("fsdp"), like)
    batch_spec = P(("dp", "fsdp"), "sp")

    inner = shard_map(
        functools.partial(_fsdp_sgd_step, like=like, cfg=cfg, lr=lr,
                          pctx=pctx, data_axes=("dp", "fsdp", "sp")),
        mesh=mesh,
        in_specs=(flat_specs, batch_spec, batch_spec),
        out_specs=(flat_specs, P()),
    )

    def step(flat_params, tokens):
        return inner(flat_params, tokens[:, :-1], tokens[:, 1:])

    return jax.jit(step), functools.partial(fsdp_shard_params,
                                            n_shards=F, mesh=mesh)


# --- AdamW -----------------------------------------------------------------
# Hand-rolled state-as-dict (mu/nu mirror the param tree) so the
# optimizer state shards with exactly the param PartitionSpecs — no
# pytree-structure plumbing between optax namedtuples and shard_map
# in_specs. Matches optax.adamw semantics (decoupled weight decay,
# bias-corrected moments).

def _adamw_update(params, grads, mu, nu, count, *, lr, b1=0.9,
                  b2=0.999, eps=1e-8, weight_decay=0.0):
    """The one elementwise AdamW rule every step variant shares
    (decoupled weight decay, bias-corrected moments, fp32 math,
    param dtype preserved). ``count`` is the ALREADY-incremented step
    number. Returns (new_params, new_mu, new_nu)."""
    c = count.astype(jnp.float32)

    def upd(p, g, m, n):
        g = g.astype(jnp.float32)
        m = b1 * m + (1 - b1) * g
        n = b2 * n + (1 - b2) * g * g
        step = (m / (1 - b1 ** c)) / (jnp.sqrt(n / (1 - b2 ** c)) + eps)
        p32 = p.astype(jnp.float32)
        return ((p32 - lr * (step + weight_decay * p32)).astype(p.dtype),
                m, n)

    flat = jax.tree.map(upd, params, grads, mu, nu)
    pick = lambda i: jax.tree.map(
        lambda t: t[i], flat, is_leaf=lambda x: isinstance(x, tuple))
    return pick(0), pick(1), pick(2)


def apply_adamw(params, grads, opt_state, *, lr, b1=0.9, b2=0.999,
                eps=1e-8, weight_decay=0.0):
    """One AdamW application on an adamw_init-layout state: increments
    count, runs _adamw_update, rebuilds the state dict. The ONE copy of
    this glue, shared by the dense/MoE/pipeline step factories."""
    count = opt_state["count"] + 1
    new_p, new_mu, new_nu = _adamw_update(
        params, grads, opt_state["mu"], opt_state["nu"], count, lr=lr,
        b1=b1, b2=b2, eps=eps, weight_decay=weight_decay)
    return new_p, {"mu": new_mu, "nu": new_nu, "count": count}


def adamw_init(params: Dict[str, Any]) -> Dict[str, Any]:
    zeros = lambda t: jax.tree.map(
        lambda p: jnp.zeros(p.shape, jnp.float32), t)
    return {"mu": zeros(params), "nu": zeros(params),
            "count": jnp.zeros((), jnp.int32)}


def opt_state_specs(specs: Dict[str, Any]) -> Dict[str, Any]:
    """PartitionSpec tree for adamw_init's state given param specs."""
    return {"mu": specs, "nu": specs, "count": P()}


def adamw_train_step(params, opt_state, tokens, cfg: TransformerConfig, *,
                     lr: float = 1e-3, b1: float = 0.9, b2: float = 0.999,
                     eps: float = 1e-8, weight_decay: float = 0.0,
                     pctx: Optional[ParallelCtx] = None,
                     data_axes: Tuple[str, ...] = ()):
    """One AdamW step on the global loss. Returns (params, state, loss)."""
    loss, grads = jax.value_and_grad(
        functools.partial(lm_loss, cfg=cfg, pctx=pctx,
                          data_axes=data_axes))(params, tokens)
    new_params, new_state = apply_adamw(
        params, grads, opt_state, lr=lr, b1=b1, b2=b2, eps=eps,
        weight_decay=weight_decay)
    return new_params, new_state, loss


def make_adamw_spmd_train_step(cfg: TransformerConfig, mesh: Mesh, *,
                               lr: float = 1e-3, weight_decay: float = 0.0):
    """AdamW over the dp×sp×tp mesh; optimizer moments shard like the
    params (the fsdp-free version of ZeRO: tp-sharded params get
    tp-sharded moments for free)."""
    specs = param_specs(cfg, tp="tp")
    ospecs = opt_state_specs(specs)
    batch_spec = P("dp", "sp")
    pctx = ParallelCtx(tp="tp", sp="sp")

    def _step(params, opt_state, inputs, targets):
        loss, grads = jax.value_and_grad(
            functools.partial(xent_loss, cfg=cfg, pctx=pctx,
                              data_axes=("dp", "sp")))(params, inputs,
                                                       targets)
        new_p, new_state = apply_adamw(params, grads, opt_state,
                                       lr=lr, weight_decay=weight_decay)
        return new_p, new_state, loss

    inner = shard_map(_step, mesh=mesh,
                      in_specs=(specs, ospecs, batch_spec, batch_spec),
                      out_specs=(specs, ospecs, P()))

    def step(params, opt_state, tokens):
        return inner(params, opt_state, tokens[:, :-1], tokens[:, 1:])

    return jax.jit(step)
