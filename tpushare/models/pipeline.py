"""Pipeline parallelism (GPipe-style) for the decoder LM.

The stacked-layer param axis ([L, ...], already scanned on one device)
shards naturally over the ``pp`` mesh axis: each stage holds L/pp
consecutive blocks. Microbatches stream through the stages with one
``ppermute`` hop per step — SPMD pipelining, no per-stage programs:
every rank runs the same jitted code, stage identity comes from
``axis_index``. The schedule is the classic M + P - 1 step GPipe fill/
drain; bubbles shrink as microbatches grow.

Embedding/unembedding stay replicated (cheap at these sizes): every
rank embeds the microbatch queue, only stage 0's activations enter the
pipe, and only the last stage's logits contribute to the loss (masked
psum makes it global). Composes with tp (Megatron psums inside blocks)
— pp×tp is the canonical large-model layout; dp/sp ride on top via the
usual data-axis pmean of gradients.

The reference system has no parallelism of any kind (SURVEY.md §2);
this is workload-harness capability the scheduled pods use.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

from tpushare.models.transformer import (
    ParallelCtx, TransformerConfig, layer_windows,
    param_specs as dense_param_specs,
)
from tpushare.ops import apply_rotary, attention, rms_norm, rotary_embedding
from tpushare.models.transformer import _act
from tpushare.parallel.ring_attention import ring_attention


def param_specs(cfg: TransformerConfig, *, pp: str = "pp",
                tp: str = "tp") -> Dict[str, Any]:
    """Dense-LM specs with the stacked-layer axis sharded over pp."""
    specs = dense_param_specs(cfg, tp=tp)
    layers = {k: P(pp, *tuple(s)[1:]) for k, s in specs["layers"].items()}
    specs["layers"] = layers
    return specs


def _block(x, layer, cfg: TransformerConfig, cos, sin, tp: Optional[str],
           sp: Optional[str] = None, w=None):
    """One transformer block on local activations (no cache). With
    ``sp``, x holds this rank's sequence slice and attention crosses
    shards via ring attention — the same composition the dense SPMD
    path uses (transformer.py block), here inside a pipeline stage.
    ``w`` is this layer's sliding window (traced scalar, None/0 =
    global) and softcap comes from cfg — Gemma-2-style configs train
    identically through the pipeline and the dense path."""
    B, S, _ = x.shape
    Dh = cfg.head_dim
    h = rms_norm(x, layer["ln1"], eps=cfg.norm_eps, offset=cfg.norm_offset)
    H = layer["wq"].shape[-1] // Dh
    Hkv = layer["wk"].shape[-1] // Dh
    q = apply_rotary((h @ layer["wq"]).reshape(B, S, H, Dh), cos, sin)
    k = apply_rotary((h @ layer["wk"]).reshape(B, S, Hkv, Dh), cos, sin)
    v = (h @ layer["wv"]).reshape(B, S, Hkv, Dh)
    if sp is not None:
        attn = ring_attention(q, k, v, axis_name=sp, causal=True,
                              scale=cfg.attn_scale, window=w,
                              attn_softcap=cfg.attn_softcap)
    else:
        attn = attention(q, k, v, causal=True, scale=cfg.attn_scale,
                         window=w, attn_softcap=cfg.attn_softcap)
    o = attn.reshape(B, S, H * Dh) @ layer["wo"]
    if tp is not None:
        o = jax.lax.psum(o, tp)
    if cfg.post_norms:
        o = rms_norm(o, layer["ln_post_attn"], eps=cfg.norm_eps,
                     offset=cfg.norm_offset)
    x = x + o
    h = rms_norm(x, layer["ln2"], eps=cfg.norm_eps, offset=cfg.norm_offset)
    ff = _act(cfg.act, h @ layer["w_gate"]) * (h @ layer["w_up"])
    ff = ff @ layer["w_down"]
    if tp is not None:
        ff = jax.lax.psum(ff, tp)
    if cfg.post_norms:
        ff = rms_norm(ff, layer["ln_post_ffw"], eps=cfg.norm_eps,
                      offset=cfg.norm_offset)
    return x + ff


def _local_layer_windows(cfg: TransformerConfig, pp_axis: str,
                         interleaved_v: Optional[int] = None):
    """This rank's per-layer sliding windows in STORAGE order ([L/P]
    int32, 0 = global layer), or None when cfg has none. The model-
    order pattern comes from transformer.layer_windows (the one copy
    of the Gemma-2 alternation rule); it is permuted for interleaved
    storage and sliced to the stage's contiguous shard."""
    wls = layer_windows(cfg)
    if wls is None:
        return None
    P_static = jax.lax.axis_size(pp_axis)
    if interleaved_v is not None:
        wls = wls[jnp.asarray(
            interleaved_layer_order(cfg.n_layers, P_static, interleaved_v))]
    n_local = cfg.n_layers // P_static
    stage = jax.lax.axis_index(pp_axis)
    return jax.lax.dynamic_slice(wls, (stage * n_local,), (n_local,))


def _sp_rotary(S: int, Bm: int, cfg: TransformerConfig,
               sp_axis: Optional[str]):
    """(cos, sin) for a [Bm, S]-shaped microbatch whose sequence may be
    an sp shard. One copy of the sp position-offset rule (this rank's
    slice starts at sp_index * S_local — the same rule as
    transformer.forward under pctx.sp) shared by every pp schedule, so
    a rope change cannot diverge them."""
    positions = jnp.arange(S)[None, :]
    if sp_axis is not None:
        positions = positions + jax.lax.axis_index(sp_axis) * S
    positions = jnp.broadcast_to(positions, (Bm, S))
    return rotary_embedding(positions, cfg.head_dim, base=cfg.rope_base,
                            scaling=cfg.rope_scaling)


def pipelined_lm_loss(params, inputs: jnp.ndarray, targets: jnp.ndarray,
                      cfg: TransformerConfig, *,
                      pp_axis: str = "pp", tp_axis: Optional[str] = "tp",
                      sp_axis: Optional[str] = None,
                      data_axes: Tuple[str, ...] = (),
                      n_microbatches: int) -> jnp.ndarray:
    """Next-token loss computed through the pp pipeline.

    inputs/targets [B, S] pre-shifted and aligned (the factories shift
    tokens[:, :-1]/[:, 1:] OUTSIDE shard_map so the sequence axis can
    shard over ``sp_axis`` — ring attention inside the blocks crosses
    shards, the same composition as the dense SPMD path); B must
    divide by n_microbatches. Call inside shard_map with params
    sharded per param_specs(); returns the GLOBAL mean loss (masked
    psum over pp, pmean over ``data_axes``) so differentiating it
    directly yields correct grads (see models/training.py on the
    post-grad-pmean double-count hazard)."""
    n_stages = jax.lax.psum(1, pp_axis)
    stage = jax.lax.axis_index(pp_axis)
    M = n_microbatches
    B, S = inputs.shape
    assert B % M == 0, f"batch {B} not divisible into {M} microbatches"
    Bm = B // M

    cos, sin = _sp_rotary(S, Bm, cfg, sp_axis)

    # Every rank embeds the whole microbatch queue (replicated, cheap).
    x_mb = params["embed"][inputs.reshape(M, Bm, S)].astype(cfg.dtype)
    if cfg.embed_scale:
        x_mb = x_mb * jnp.asarray(jnp.sqrt(cfg.d_model), cfg.dtype)

    wls = _local_layer_windows(cfg, pp_axis)

    def local_layers(x):
        # None is a valid scan-xs leaf (empty pytree): w arrives None.
        def body(x, xs):
            layer, w = xs
            return _block(x, layer, cfg, cos, sin, tp_axis,
                          sp=sp_axis, w=w), None
        x, _ = jax.lax.scan(body, x, (params["layers"], wls))
        return x

    perm = [(i, i + 1) for i in range(n_stages - 1)]   # stage i -> i+1

    def step(t, carry):
        inflight, outputs = carry
        # Stage 0 injects microbatch t (clamped; masked when t >= M).
        mb = jax.lax.dynamic_index_in_dim(x_mb, jnp.minimum(t, M - 1), 0,
                                          keepdims=False)
        inp = jnp.where(stage == 0, mb, inflight)
        act = local_layers(inp)
        # Last stage captures its result at output slot t - (P-1).
        slot = t - (n_stages - 1)
        write = jnp.logical_and(stage == n_stages - 1, slot >= 0)
        upd = jax.lax.dynamic_update_index_in_dim(
            outputs, act.astype(outputs.dtype), jnp.maximum(slot, 0), 0)
        outputs = jnp.where(write, upd, outputs)
        # Hop to the next stage (non-cyclic: last stage's send is dropped).
        inflight = jax.lax.ppermute(act, pp_axis, perm)
        return inflight, outputs

    # Accumulator vma must match the loop outputs': the pipe axis plus
    # whatever the embedded microbatches vary over (dp, sp, ...).
    vma = {pp_axis} | set(jax.typeof(x_mb).vma)

    def pvary(x):
        return jax.lax.pcast(x, tuple(vma), to="varying")

    inflight0 = pvary(jnp.zeros((Bm, S, cfg.d_model), cfg.dtype))
    outputs0 = pvary(jnp.zeros((M, Bm, S, cfg.d_model), cfg.dtype))
    _, outputs = jax.lax.fori_loop(0, M + n_stages - 1, step,
                                   (inflight0, outputs0))

    # Head on the last stage's outputs; other stages contribute zeros,
    # the masked psum over pp makes the loss global and replicated.
    x = outputs.reshape(B, S, cfg.d_model)
    x = rms_norm(x, params["final_norm"], eps=cfg.norm_eps,
                 offset=cfg.norm_offset)
    unembed = (params["embed"].T if cfg.tie_embeddings
               else params["unembed"]).astype(cfg.dtype)
    logits = (x @ unembed).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)
    local = jnp.where(stage == n_stages - 1, jnp.mean(nll), 0.0)
    loss = jax.lax.psum(local, pp_axis)
    for ax in data_axes:
        loss = jax.lax.pmean(loss, ax)
    return loss


class _ManualVJPShared:
    """Machinery shared by the manual-VJP schedules (1F1B and
    interleaved): microbatch splitting, chunk/embed/head closures, the
    vma discipline, the head/embed lax.cond wrappers, and the grad
    finalization epilogue. One copy, so a numerics fix cannot silently
    diverge the two schedules."""

    def __init__(self, params, inputs, targets, cfg: TransformerConfig,
                 pp_axis: str, tp_axis: Optional[str], M: int,
                 sp_axis: Optional[str] = None):
        self.cfg = cfg
        self.pp_axis = pp_axis
        self.sp_axis = sp_axis
        self.stage = jax.lax.axis_index(pp_axis)
        B, S = inputs.shape          # S is the sp-LOCAL length under sp
        assert B % M == 0, f"batch {B} not divisible into {M} microbatches"
        self.Bm = B // M
        self.S = S
        self.inv_m = 1.0 / M
        self.inputs_mb = inputs.reshape(M, self.Bm, S)
        self.targets_mb = targets.reshape(M, self.Bm, S)
        self.cos, self.sin = _sp_rotary(S, self.Bm, cfg, sp_axis)
        self.scale = (jnp.asarray(jnp.sqrt(cfg.d_model), cfg.dtype)
                      if cfg.embed_scale else None)
        self.tied = cfg.tie_embeddings
        self.head_key = "embed" if self.tied else "unembed"
        self.params = params
        self.P_static = jax.lax.axis_size(pp_axis)

        self.vma = {pp_axis} | set(
            jax.typeof(params["embed"][self.inputs_mb[0]]).vma)

        # CRITICAL: params that are replicated over pp/dp must be pcast
        # to varying BEFORE they enter a vjp. The vma-aware transpose
        # psums a replicated ("invarying") argument's cotangent over
        # those axes INSIDE the vjp — which here would sum other
        # stages' garbage head computations before the validity mask
        # can drop them (pp), and double-count against the explicit
        # data-axis pmean in finalize() (dp). Varying inputs come back
        # as per-rank partials; the only hidden psums left are over tp,
        # where every rank computes the same schedule so they are
        # exactly the Megatron grad reductions.
        head_param = params["embed"] if self.tied else params["unembed"]
        self.v_final = self.pvary(params["final_norm"])
        self.v_head = self.pvary(head_param)
        self.tp_axis = tp_axis

    def pvary(self, x):
        missing = tuple(self.vma - set(jax.typeof(x).vma))
        return jax.lax.pcast(x, missing, to="varying") if missing else x

    def chunk_fwd(self, x, lyrs, ws=None):
        """Scan ``lyrs`` over x; ``ws`` is the aligned per-layer
        sliding-window array (or None for all-global models)."""
        cfg = self.cfg

        # None is a valid scan-xs leaf (empty pytree): w arrives None.
        def body(x, xs):
            layer, w = xs
            return _block(x, layer, cfg, self.cos, self.sin,
                          self.tp_axis, sp=self.sp_axis, w=w), None
        y, _ = jax.lax.scan(body, x, (lyrs, ws))
        return y

    def embed_fwd(self, toks):
        x = self.params["embed"][toks].astype(self.cfg.dtype)
        return x * self.scale if self.scale is not None else x

    def head_loss(self, y, final_norm_p, head_p, tgt):
        cfg = self.cfg
        x = rms_norm(y, final_norm_p, eps=cfg.norm_eps,
                     offset=cfg.norm_offset)
        unembed = (head_p.T if self.tied else head_p).astype(cfg.dtype)
        logits = (x @ unembed).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return jnp.mean(-jnp.take_along_axis(logp, tgt[..., None], axis=-1))

    def zero_grads(self, layers):
        z = {"layers": jax.tree.map(jnp.zeros_like, layers),
             "embed": jnp.zeros_like(self.params["embed"]),
             "final_norm": jnp.zeros_like(self.params["final_norm"])}
        if not self.tied:
            z["unembed"] = jnp.zeros_like(self.params["unembed"])
        return z

    def head_cond(self, take_loss, y, tgt, fn_acc, hd_acc, l_acc):
        """Head forward+VJP under lax.cond (no collectives inside, so
        per-rank branching cannot deadlock); returns the dy cotangent
        entering the last chunk plus updated accumulators."""

        def _run(y, tgt, fn_acc, hd_acc, l_acc):
            nll, head_vjp = jax.vjp(self.head_loss, y, self.v_final,
                                    self.v_head, tgt)
            dy, dfn, dhd, _ = head_vjp(
                self.pvary(jnp.asarray(self.inv_m, jnp.float32)))
            return (dy.astype(self.cfg.dtype), fn_acc + dfn, hd_acc + dhd,
                    l_acc + nll * self.inv_m)

        def _skip(y, tgt, fn_acc, hd_acc, l_acc):
            return jnp.zeros_like(y), fn_acc, hd_acc, l_acc

        return jax.lax.cond(take_loss, _run, _skip,
                            y, tgt, fn_acc, hd_acc, l_acc)

    def embed_cond(self, do, acc_e, toks, dx):
        """Embedding-gather closure under lax.cond (only the rank that
        owns chunk 0 pays the [V, D] scatter)."""

        def _run(acc_e, toks, dxv):
            demb = dxv * self.scale if self.scale is not None else dxv
            return acc_e.at[toks].add(demb.astype(acc_e.dtype))

        return jax.lax.cond(do, _run, lambda acc_e, toks, dxv: acc_e,
                            acc_e, toks, dx)

    def finalize(self, loss_acc, acc, data_axes):
        """Layer grads are pp-local (each stage owns its shard);
        replicated leaves carry stage-masked partial sums — psum over
        pp completes them. Then average over the data axes."""
        loss = jax.lax.psum(loss_acc, self.pp_axis)
        grads = {"layers": acc["layers"],
                 "embed": jax.lax.psum(acc["embed"], self.pp_axis),
                 "final_norm": jax.lax.psum(acc["final_norm"],
                                            self.pp_axis)}
        if not self.tied:
            grads["unembed"] = jax.lax.psum(acc["unembed"], self.pp_axis)
        for ax in data_axes:
            loss = jax.lax.pmean(loss, ax)
            grads = jax.tree.map(lambda g: jax.lax.pmean(g, ax), grads)
        return loss, grads


def onef1b_loss_and_grads(params, inputs: jnp.ndarray,
                          targets: jnp.ndarray,
                          cfg: TransformerConfig, *,
                          pp_axis: str = "pp",
                          tp_axis: Optional[str] = "tp",
                          sp_axis: Optional[str] = None,
                          data_axes: Tuple[str, ...] = (),
                          n_microbatches: int):
    """1F1B pipeline schedule with manual per-microbatch VJP.

    The GPipe path above differentiates the whole fill/drain loop, so
    autodiff keeps every microbatch's residuals live until the drain —
    O(M) activation memory per stage. 1F1B runs each microbatch's
    backward as soon as its forward clears the last stage, so at most
    2·(P−1−s) microbatches are in flight at stage s — O(P), independent
    of M. The backward recomputes its chunk forward from the stored
    chunk *input* (remat: the ring buffer holds one [Bm,S,D] tensor per
    in-flight microbatch, never per-layer activations).

    Timetable (round r, stage s, P stages): forward of microbatch m at
    r = m + s; backward at r = m + 2P − 2 − s. The last stage does F
    and B of the same microbatch in one round (loss cotangent feeds
    straight back); interior stages receive activations via ppermute
    s→s+1 and cotangents via s−1←s, each exactly one round before use.

    Returns (loss, grads): loss is the global mean (psum over pp, pmean
    over data_axes); grads are ready to apply (pp-sharded layer grads
    local to each stage, replicated embed/head grads psum'd over pp,
    everything pmean'd over data_axes).
    """
    M = n_microbatches
    sh = _ManualVJPShared(params, inputs, targets, cfg, pp_axis, tp_axis,
                          M, sp_axis=sp_axis)
    stage, P_static = sh.stage, sh.P_static
    layers = params["layers"]
    wls_local = _local_layer_windows(cfg, pp_axis)
    # Ring capacity covers the in-flight window (write-then-read order
    # makes it 2P-1 at stage 0; never more than M are in flight).
    R_cap = max(1, min(2 * P_static - 1, M))

    v_layers = jax.tree.map(sh.pvary, layers)
    act_shape = (sh.Bm, sh.S, cfg.d_model)
    carry0 = (
        sh.pvary(jnp.zeros(act_shape, cfg.dtype)),            # fwd msg
        sh.pvary(jnp.zeros(act_shape, cfg.dtype)),            # bwd msg
        sh.pvary(jnp.zeros((R_cap,) + act_shape, cfg.dtype)), # residual ring
        jax.tree.map(sh.pvary, sh.zero_grads(layers)),
        sh.pvary(jnp.zeros((), jnp.float32)),                 # loss acc
    )
    perm_up = [(i, i + 1) for i in range(P_static - 1)]
    perm_dn = [(i + 1, i) for i in range(P_static - 1)]

    def round_fn(r, carry):
        fwd_msg, bwd_msg, ring, acc, loss_acc = carry

        # ---- forward: microbatch m_f = r - stage ----------------------
        m_f = r - stage
        valid_f = jnp.logical_and(m_f >= 0, m_f < M)
        m_f_c = jnp.clip(m_f, 0, M - 1)
        toks_f = jax.lax.dynamic_index_in_dim(sh.inputs_mb, m_f_c, 0, False)
        x_in = jnp.where(stage == 0, sh.embed_fwd(toks_f), fwd_msg)
        slot_f = jax.lax.rem(m_f_c, R_cap)
        ring = jnp.where(valid_f,
                         jax.lax.dynamic_update_index_in_dim(
                             ring, x_in, slot_f, 0),
                         ring)
        y = sh.chunk_fwd(x_in, v_layers, wls_local)

        # ---- head on the last stage (same round as its forward) -------
        tgt_f = jax.lax.dynamic_index_in_dim(sh.targets_mb, m_f_c, 0, False)
        at_last = stage == P_static - 1
        take_loss = jnp.logical_and(at_last, valid_f)
        dy_head, acc["final_norm"], acc[sh.head_key], loss_acc = \
            sh.head_cond(take_loss, y, tgt_f, acc["final_norm"],
                         acc[sh.head_key], loss_acc)

        # ---- backward: microbatch m_b = r - (2P - 2 - stage) ----------
        m_b = r - (2 * P_static - 2 - stage)
        valid_b = jnp.logical_and(m_b >= 0, m_b < M)
        m_b_c = jnp.clip(m_b, 0, M - 1)
        slot_b = jax.lax.rem(m_b_c, R_cap)
        x_res = jax.lax.dynamic_index_in_dim(ring, slot_b, 0, False)
        dy = jnp.where(at_last, dy_head, bwd_msg)
        _, chunk_vjp = jax.vjp(
            lambda xr, ly: sh.chunk_fwd(xr, ly, wls_local),
            x_res, v_layers)                                   # remat fwd
        dx, dlayers = chunk_vjp(sh.pvary(dy))
        acc["layers"] = jax.tree.map(
            lambda a, g: a + jnp.where(valid_b, g, jnp.zeros_like(g)),
            acc["layers"], dlayers)
        # Stage 0's dx closes the embedding gather.
        toks_b = jax.lax.dynamic_index_in_dim(sh.inputs_mb, m_b_c, 0, False)
        acc["embed"] = sh.embed_cond(
            jnp.logical_and(stage == 0, valid_b), acc["embed"], toks_b, dx)

        # ---- hops -----------------------------------------------------
        fwd_msg = jax.lax.ppermute(y, pp_axis, perm_up)
        bwd_msg = jax.lax.ppermute(dx, pp_axis, perm_dn)
        return fwd_msg, bwd_msg, ring, acc, loss_acc

    n_rounds = M + 2 * P_static - 2
    _, _, _, acc, loss_acc = jax.lax.fori_loop(0, n_rounds, round_fn, carry0)
    return sh.finalize(loss_acc, acc, data_axes)


# ---------------------------------------------------------------------------
# Interleaved 1F1B (Megatron virtual stages): v model chunks per rank.
# ---------------------------------------------------------------------------

def interleaved_layer_order(n_layers: int, n_stages: int, v: int):
    """Storage permutation for schedule="interleaved".

    Megatron interleaving assigns rank s the NON-adjacent model chunks
    {s, s+P, ..., s+(v-1)P} (model chunk q = layers [q*Lc, (q+1)*Lc),
    Lc = L/(P*v)), so consecutive chunks live on consecutive ranks and
    a microbatch crosses every rank v times per pass. jax shards the
    stacked [L, ...] axis contiguously over pp, so the stacked array
    must be stored permuted: ``stacked[perm]`` puts model layer
    ``perm[r]`` at storage row r, giving rank s's contiguous shard
    exactly its round-robin chunks (local row j*Lc+k = model chunk
    j*P+s layer k). Apply once with to_interleaved_storage()."""
    if n_layers % (n_stages * v):
        raise ValueError(f"{n_layers} layers not divisible into "
                         f"{n_stages}x{v} chunks")
    lc = n_layers // (n_stages * v)
    perm = []
    for s in range(n_stages):
        for j in range(v):
            q = j * n_stages + s
            perm.extend(range(q * lc, (q + 1) * lc))
    return perm


def to_interleaved_storage(params, n_stages: int, v: int):
    """Permute a params tree's stacked layers into interleaved storage
    order (host-side, once, before shard_tree — NOT inside the step:
    permuting sharded params per step would gather across ranks)."""
    some_leaf = next(iter(jax.tree.leaves(params["layers"])))
    perm = jnp.asarray(
        interleaved_layer_order(some_leaf.shape[0], n_stages, v))
    out = dict(params)
    out["layers"] = jax.tree.map(lambda a: a[perm], params["layers"])
    return out


def build_interleaved_schedule(n_stages: int, v: int, M: int):
    """Static interleaved-1F1B timetable + buffer capacities.

    Megatron's interleaved schedule (per-rank op order: warmup of
    (P-s-1)*2 + (v-1)*P forwards, then 1F1B pairs, then drain; chunk
    order within each phase cycles in groups of P microbatches) is
    list-scheduled here against the true dependencies — one op per rank
    per slot, a message sent at slot t is usable at t+1 — yielding
    per-slot tables the SPMD executor replays. Capacities for the
    forward/backward mailboxes and the residual ring are grown until
    the mod-M ring reuse provably never clobbers an unconsumed entry,
    so buffer safety is a build-time theorem, not a runtime hope.

    Returns a dict: tables f_j/f_m/b_j/b_m of shape [T, P] (-1 = idle),
    capacities qf/qb/rc, per-rank bubble slot counts, and T.
    """
    P, D = n_stages, n_stages * v
    if M % P:
        raise ValueError(f"interleaved schedule needs microbatches "
                         f"divisible by stages (M={M}, P={P})")
    total = v * M

    def fwd_op(k):   # Megatron get_model_chunk_id order, forward
        return ((k // P) % v, (k // (P * v)) * P + (k % P))

    def bwd_op(k):   # backward visits chunks in reverse
        return (v - 1 - ((k // P) % v), (k // (P * v)) * P + (k % P))

    ops = []
    for s in range(P):
        warm = min((P - s - 1) * 2 + (v - 1) * P, total)
        seq = [("F",) + fwd_op(i) for i in range(warm)]
        nf, nb = warm, 0
        while nf < total or nb < total:
            if nf < total:
                seq.append(("F",) + fwd_op(nf))
                nf += 1
            if nb < total:
                seq.append(("B",) + bwd_op(nb))
                nb += 1
        ops.append(seq)

    done_f: Dict[Tuple[int, int], int] = {}
    done_b: Dict[Tuple[int, int], int] = {}
    ptr = [0] * P
    bubbles = [0] * P
    f_j, f_m, b_j, b_m = [], [], [], []
    t = 0
    while any(ptr[s] < len(ops[s]) for s in range(P)):
        rows = [[-1] * P for _ in range(4)]
        fired = []
        for s in range(P):
            if ptr[s] >= len(ops[s]):
                continue
            kind, j, m = ops[s][ptr[s]]
            q = j * P + s
            if kind == "F":
                ready = q == 0 or done_f.get((q - 1, m), t) <= t - 1
            else:
                ready = done_f.get((q, m), t) <= t - 1 and (
                    q == D - 1 or done_b.get((q + 1, m), t) <= t - 1)
            if ready:
                fired.append((s, kind, j, m, q))
            else:
                bubbles[s] += 1
        if not fired:
            raise RuntimeError(
                f"interleaved schedule deadlocked at slot {t} "
                f"(P={P}, v={v}, M={M})")
        for s, kind, j, m, q in fired:
            if kind == "F":
                done_f[(q, m)] = t
                rows[0][s], rows[1][s] = j, m
            else:
                done_b[(q, m)] = t
                rows[2][s], rows[3][s] = j, m
            ptr[s] += 1
        f_j.append(rows[0])
        f_m.append(rows[1])
        b_j.append(rows[2])
        b_m.append(rows[3])
        t += 1

    # Mod-ring capacities, grown until reuse is provably clobber-free.
    # Mailboxes are written in a slot's epilogue (post-ppermute) and
    # read in the body, so an entry consumed at slot c may be rewritten
    # at any w >= c; the residual ring is written in the body's forward
    # phase, which precedes the body's backward-phase read, so its
    # rewrite needs strictly c < w.
    def grow(cap, safe):
        while cap < M and not safe(cap):
            cap += 1
        return cap

    def qf_safe(cap):
        return all(done_f.get((q, m - cap), -1) <= done_f[(q - 1, m)]
                   for q in range(1, D) for m in range(cap, M))

    def qb_safe(cap):
        return all(done_b.get((q, m - cap), -1) <= done_b[(q + 1, m)]
                   for q in range(D - 1) for m in range(cap, M))

    def rc_safe(cap):
        return all(done_b.get((q, m - cap), -1) < done_f[(q, m)]
                   for q in range(D) for m in range(cap, M))

    return {
        "f_j": f_j, "f_m": f_m, "b_j": b_j, "b_m": b_m, "T": t,
        "qf": grow(1, qf_safe), "qb": grow(1, qb_safe),
        "rc": grow(1, rc_safe), "bubbles": bubbles,
    }


def interleaved_loss_and_grads(params, inputs: jnp.ndarray,
                               targets: jnp.ndarray,
                               cfg: TransformerConfig, *,
                               pp_axis: str = "pp",
                               tp_axis: Optional[str] = "tp",
                               sp_axis: Optional[str] = None,
                               data_axes: Tuple[str, ...] = (),
                               n_microbatches: int, n_chunks: int = 2):
    """Interleaved 1F1B: v = n_chunks virtual stages per rank.

    Same manual-VJP/remat machinery as onef1b_loss_and_grads, driven by
    the static build_interleaved_schedule() timetable instead of the
    closed-form 1F1B round formulas: each slot, a rank replays its
    table row — at most one chunk-forward and one chunk-backward, with
    chunk identity/microbatch as traced table lookups. Activations hop
    rank -> rank+1 *cyclically* (a microbatch wraps P-1 -> 0 between
    chunk groups), cotangents the reverse; per-chunk mailboxes and the
    residual ring use mod-capacity slots the builder proved safe.
    Expects params["layers"] in interleaved storage order
    (to_interleaved_storage). Grad/loss contract matches 1F1B.
    """
    v = n_chunks
    M = n_microbatches
    sh = _ManualVJPShared(params, inputs, targets, cfg, pp_axis, tp_axis,
                          M, sp_axis=sp_axis)
    stage, P_static = sh.stage, sh.P_static
    D = P_static * v

    sched = build_interleaved_schedule(P_static, v, M)
    QF, QB, RC = sched["qf"], sched["qb"], sched["rc"]
    tab = {k: jnp.asarray(sched[k], jnp.int32)
           for k in ("f_j", "f_m", "b_j", "b_m")}

    # Local stacked layers [L/P, ...] -> [v, Lc, ...]: local chunk j is
    # model chunk j*P + stage (interleaved storage order).
    some = next(iter(jax.tree.leaves(params["layers"])))
    lc = some.shape[0] // v
    layers = jax.tree.map(
        lambda a: a.reshape((v, lc) + a.shape[1:]), params["layers"])
    wls_local = _local_layer_windows(cfg, pp_axis, interleaved_v=v)
    wls_chunks = (None if wls_local is None
                  else wls_local.reshape(v, lc))

    def chunk_windows(j):
        return (None if wls_chunks is None
                else jax.lax.dynamic_index_in_dim(wls_chunks, j, 0, False))

    v_layers = jax.tree.map(sh.pvary, layers)
    act = (sh.Bm, sh.S, cfg.d_model)
    carry0 = (
        sh.pvary(jnp.zeros((v, QF) + act, cfg.dtype)),   # fwd mailboxes
        sh.pvary(jnp.zeros((v, QB) + act, cfg.dtype)),   # bwd mailboxes
        sh.pvary(jnp.zeros((v, RC) + act, cfg.dtype)),   # residual rings
        jax.tree.map(sh.pvary, sh.zero_grads(layers)),
        sh.pvary(jnp.zeros((), jnp.float32)),            # loss acc
    )
    perm_up = [(i, (i + 1) % P_static) for i in range(P_static)]
    perm_dn = [(i, (i - 1) % P_static) for i in range(P_static)]
    at_last_rank = stage == P_static - 1

    def cell_read(buf, j, slot):
        return jax.lax.dynamic_slice(
            buf, (j, slot, 0, 0, 0), (1, 1) + act)[0, 0]

    def cell_write(buf, j, slot, val, do):
        upd = jax.lax.dynamic_update_slice(
            buf, val[None, None].astype(buf.dtype), (j, slot, 0, 0, 0))
        return jnp.where(do, upd, buf)

    def tree_at(tree, j):
        return jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, j, 0, False), tree)

    def round_fn(t, carry):
        fwd_mail, bwd_mail, ring, acc, loss_acc = carry
        row = lambda k: jax.lax.dynamic_index_in_dim(tab[k], t, 0, False)[stage]

        # ---- forward phase -------------------------------------------
        fj_raw, fm_raw = row("f_j"), row("f_m")
        valid_f = fj_raw >= 0
        j_f = jnp.clip(fj_raw, 0, v - 1)
        m_f = jnp.clip(fm_raw, 0, M - 1)
        q_f = j_f * P_static + stage
        toks_f = jax.lax.dynamic_index_in_dim(sh.inputs_mb, m_f, 0, False)
        x_mail = cell_read(fwd_mail, j_f, jax.lax.rem(m_f, QF))
        x_in = jnp.where(q_f == 0, sh.embed_fwd(toks_f), x_mail)
        ring = cell_write(ring, j_f, jax.lax.rem(m_f, RC), x_in, valid_f)
        y = sh.chunk_fwd(x_in, tree_at(v_layers, j_f), chunk_windows(j_f))
        send_f = jnp.logical_and(valid_f, q_f < D - 1)
        # Chunk q's output enters chunk q+1: next rank, same local j —
        # except the cyclic wrap P-1 -> 0, where the group advances (j+1).
        jd_f = jnp.where(at_last_rank, j_f + 1, j_f)
        meta_f = jnp.stack([jd_f, m_f, send_f.astype(jnp.int32)])

        # ---- backward phase ------------------------------------------
        bj_raw, bm_raw = row("b_j"), row("b_m")
        valid_b = bj_raw >= 0
        j_b = jnp.clip(bj_raw, 0, v - 1)
        m_b = jnp.clip(bm_raw, 0, M - 1)
        q_b = j_b * P_static + stage
        x_res = cell_read(ring, j_b, jax.lax.rem(m_b, RC))
        y_b, chunk_vjp = jax.vjp(
            lambda xr, ly: sh.chunk_fwd(xr, ly, chunk_windows(j_b)),
            x_res, tree_at(v_layers, j_b))

        tgt_b = jax.lax.dynamic_index_in_dim(sh.targets_mb, m_b, 0, False)
        at_head = q_b == D - 1
        take_loss = jnp.logical_and(at_head, valid_b)
        dy_head, acc["final_norm"], acc[sh.head_key], loss_acc = \
            sh.head_cond(take_loss, y_b, tgt_b, acc["final_norm"],
                         acc[sh.head_key], loss_acc)

        dy = jnp.where(at_head, dy_head,
                       cell_read(bwd_mail, j_b, jax.lax.rem(m_b, QB)))
        dx, dlayers = chunk_vjp(sh.pvary(dy))
        acc["layers"] = jax.tree.map(
            lambda a, g: jax.lax.dynamic_update_index_in_dim(
                a,
                jax.lax.dynamic_index_in_dim(a, j_b, 0, False)
                + jnp.where(valid_b, g, jnp.zeros_like(g)),
                j_b, 0),
            acc["layers"], dlayers)

        toks_b = jax.lax.dynamic_index_in_dim(sh.inputs_mb, m_b, 0, False)
        acc["embed"] = sh.embed_cond(
            jnp.logical_and(q_b == 0, valid_b), acc["embed"], toks_b, dx)

        send_b = jnp.logical_and(valid_b, q_b > 0)
        jd_b = jnp.where(stage == 0, j_b - 1, j_b)
        meta_b = jnp.stack([jd_b, m_b, send_b.astype(jnp.int32)])

        # ---- hops + mailbox delivery ---------------------------------
        y_in = jax.lax.ppermute(y, pp_axis, perm_up)
        mf_in = jax.lax.ppermute(meta_f, pp_axis, perm_up)
        dx_in = jax.lax.ppermute(dx, pp_axis, perm_dn)
        mb_in = jax.lax.ppermute(meta_b, pp_axis, perm_dn)
        fwd_mail = cell_write(
            fwd_mail, jnp.clip(mf_in[0], 0, v - 1),
            jax.lax.rem(jnp.clip(mf_in[1], 0, M - 1), QF),
            y_in, mf_in[2] > 0)
        bwd_mail = cell_write(
            bwd_mail, jnp.clip(mb_in[0], 0, v - 1),
            jax.lax.rem(jnp.clip(mb_in[1], 0, M - 1), QB),
            dx_in, mb_in[2] > 0)
        return fwd_mail, bwd_mail, ring, acc, loss_acc

    _, _, _, acc, loss_acc = jax.lax.fori_loop(0, sched["T"], round_fn,
                                               carry0)
    # Un-reshape the per-chunk layer grads back to the [L/P, ...] shard.
    acc["layers"] = jax.tree.map(
        lambda a: a.reshape((v * lc,) + a.shape[2:]), acc["layers"])
    return sh.finalize(loss_acc, acc, data_axes)


def _pp_loss_and_grads(params, inputs, targets, cfg: TransformerConfig, *,
                       schedule: str, n_microbatches: int, n_chunks: int,
                       sp_axis: Optional[str]):
    """Schedule dispatch shared by the SGD and AdamW pp train steps.

    sp is a REAL sequence axis here: inputs/targets arrive sharded
    over it, blocks attend across shards via ring attention, and the
    loss/grad pmean over sp combines the slices (pp x tp x sp x dp).
    The factories pass sp_axis=None on sp=1 meshes so the common
    pipeline configuration keeps the fused attention() fast path
    instead of a degenerate one-hop ring."""
    if schedule == "interleaved":
        return interleaved_loss_and_grads(
            params, inputs, targets, cfg, pp_axis="pp", tp_axis="tp",
            sp_axis=sp_axis, data_axes=("dp", "sp"),
            n_microbatches=n_microbatches, n_chunks=n_chunks)
    if schedule == "1f1b":
        return onef1b_loss_and_grads(
            params, inputs, targets, cfg, pp_axis="pp", tp_axis="tp",
            sp_axis=sp_axis, data_axes=("dp", "sp"),
            n_microbatches=n_microbatches)
    return jax.value_and_grad(functools.partial(
        pipelined_lm_loss, cfg=cfg, pp_axis="pp", tp_axis="tp",
        sp_axis=sp_axis, data_axes=("dp", "sp"),
        n_microbatches=n_microbatches))(params, inputs, targets)


_SCHEDULES = ("gpipe", "1f1b", "interleaved")


def make_pp_train_step(cfg: TransformerConfig, mesh: Mesh, *,
                       n_microbatches: int, lr: float = 1e-3,
                       schedule: str = "gpipe", n_chunks: int = 2):
    """SGD train step over a pp×tp×sp (×dp) mesh.

    schedule="gpipe": autodiff through the fill/drain loop (O(M)
    residual memory per stage). schedule="1f1b": one-forward-one-
    backward with remat (O(P) residual memory); same bubble fraction,
    same numerics (tested equal). schedule="interleaved": Megatron
    virtual stages (n_chunks chunks/rank, bubble shrinks ~1/v; params
    must be in to_interleaved_storage() order, M divisible by P).

    sp is a REAL sequence axis (long-context pipeline training): the
    step takes tokens [B, S+1], shifts outside the shard_map, and
    shards the sequence over sp — ring attention inside the stages
    crosses shards. S = tokens.shape[1] - 1 must divide by the mesh's
    sp size (sp=1 meshes behave exactly as before).
    """
    if schedule not in _SCHEDULES:
        raise ValueError(f"unknown pipeline schedule {schedule!r}")
    sp_axis = "sp" if mesh.shape.get("sp", 1) > 1 else None

    from tpushare.models.training import _sgd_update

    def _step(params, inputs, targets):
        loss, grads = _pp_loss_and_grads(
            params, inputs, targets, cfg, schedule=schedule,
            n_microbatches=n_microbatches, n_chunks=n_chunks,
            sp_axis=sp_axis)
        return _sgd_update(params, grads, lr), loss

    specs = param_specs(cfg)
    # The next-token shift happens OUTSIDE the shard_map (the dense
    # path's trick, training.py:106-113) so the sequence axis shards
    # over sp as two aligned [B, S] arrays.
    inner = shard_map(_step, mesh=mesh,
                      in_specs=(specs, P("dp", "sp"), P("dp", "sp")),
                      out_specs=(specs, P()))

    def step(params, tokens):
        return inner(params, tokens[:, :-1], tokens[:, 1:])

    return jax.jit(step)


def make_pp_adamw_train_step(cfg: TransformerConfig, mesh: Mesh, *,
                             n_microbatches: int, lr: float = 1e-3,
                             weight_decay: float = 0.0,
                             schedule: str = "1f1b", n_chunks: int = 2):
    """AdamW train step over a pp×tp×sp (×dp) mesh (sp is a real
    sequence axis with ring attention — see make_pp_train_step).

    Optimizer moments mirror the param tree and shard with the SAME
    PartitionSpecs (training.opt_state_specs): each stage holds fp32
    mu/nu only for its own layer shard — pipeline-ZeRO for free, no
    replicated optimizer state. Step signature matches
    make_adamw_spmd_train_step: step(params, opt_state, tokens) ->
    (params, opt_state, loss); init state with training.adamw_init.
    Schedule semantics and preconditions are make_pp_train_step's:
    schedule="interleaved" requires params (and therefore the moment
    trees) in to_interleaved_storage() order and M divisible by P.
    """
    from tpushare.models.training import apply_adamw, opt_state_specs
    if schedule not in _SCHEDULES:
        raise ValueError(f"unknown pipeline schedule {schedule!r}")
    sp_axis = "sp" if mesh.shape.get("sp", 1) > 1 else None

    def _step(params, opt_state, inputs, targets):
        loss, grads = _pp_loss_and_grads(
            params, inputs, targets, cfg, schedule=schedule,
            n_microbatches=n_microbatches, n_chunks=n_chunks,
            sp_axis=sp_axis)
        new_p, new_state = apply_adamw(params, grads, opt_state,
                                       lr=lr, weight_decay=weight_decay)
        return new_p, new_state, loss

    specs = param_specs(cfg)
    ospecs = opt_state_specs(specs)
    inner = shard_map(_step, mesh=mesh,
                      in_specs=(specs, ospecs, P("dp", "sp"),
                                P("dp", "sp")),
                      out_specs=(specs, ospecs, P()))

    def step(params, opt_state, tokens):
        return inner(params, opt_state, tokens[:, :-1], tokens[:, 1:])

    return jax.jit(step)
