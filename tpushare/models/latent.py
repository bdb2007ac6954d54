"""Latent-attention decoder with a learned key selector, windowed
layers between the global ones, a head-wise output gate and one chip's
share of a wide expert layer: the third family beside transformer.py
and moe.py, served over the same paged pool (models/paged.py).

Layer kinds follow ``cfg.layer_types``; a layer's weights are a dict of
their own (``params["layers"][i]``), because the kinds differ in shape:

  full     q = (a_q RMSNorm(h W_qa)) W_qb -> H x (nope + rope), rotary on
           the rope part; [c_kv ; k_r] = h W_kva, c_kv = a_kv
           RMSNorm(c_kv), rotary on k_r (one for all heads); keys and
           values are c_kv W_kb, c_kv W_vb. Cached a token: the
           selector's key, c_kv and k_r. The selector scores every
           cached position, I[t,s] = sum_j w[t,j] relu(q_I[t,j].k_I[s]),
           and a query attends the ``index_topk`` positions of largest
           I (all of them while there are fewer).
  sliding  the same latent attention at its own sizes, no selector,
           keys t - (window - 1) .. t.
  both     out = concat_heads(sigmoid(h W_g)_head * o_head) W_o.
  FFN      SwiGLU at ``d_ff`` in the first ``n_dense`` layers; after
           them sigmoid router scores over ALL ``n_experts``, top-k of
           score + bias, weights renormalised over the chosen, and the
           part of the sum that the ``experts_held`` experts starting at
           ``expert_offset`` give, plus the shared expert: the partial
           result of one chip of an expert-parallel layer, with no code
           standing in for the other chips.

Attention always runs in the absorbed form (queries folded through
W_kb, values read as c_kv and expanded after the softmax), so every
head reads the same cached row and nothing is expanded to heads.

Cache layout: ``pool_k`` holds the full layers' latent rows
[n_full, n_blocks, bs, kv_rank + rope (+ zeros to a whole lane tile)],
``pool_v`` the sliding layers' at their own sizes, ``pool_x`` the
full layers' selector keys [n_full, n_blocks, bs, index_dim]: three
pools under ONE block table, shared by the prefix cache like keys and
values. (The selector's keys have a pool of their own because scoring
reads them alone: as the leading columns of the latent rows they made
the compiler relay the whole pool, minor axis first, to gather them.) A
sliding layer keeps every block (freeing behind the window needs a
table per kind); its decode reads only the blocks its window touches.
No large array is sliced along its minor axis: the values are read as
whole rows and the small output is cut to ``kv_rank``.

A configuration may also describe a model of the same attention with no
selector (``selector`` off: no ``pool_x``, a full layer attends every
cached row of its slot through the block table), no gate, a norm on each
sublayer's output before the residual add (``sandwich_norm``), no
selection bias, and a multi-token-prediction module (``n_mtp``):

  MTP      h'_i = [N_e(Emb(t_{i+1})) ; N_h(h_i)] W_eh, h_i the last main
           layer's output before the final norm; one layer of the expert
           kind with latent rows of its own (one more layer of
           ``pool_k``, under the same block table); logits through the
           model's head after the module's own norm: a guess at t_{i+2}.

A server whose configuration carries the module and whose weights hold
one (``params["mtp"]``) drafts with it: a tick verifies two positions a
stream (the last emitted token and the draft) and emits one or two
tokens (``draft_tick``; acceptance is models/spec.py's).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tpushare.models.paged import (PagedSlotServer, _admission_row, _program,
                                   apply_growth, growth_width,
                                   publish_prefix)
from tpushare.models.serving import upload_mirror
from tpushare.models.spec import (draft_sample_core, greedy_accept_core,
                                  spec_accept_core)
from tpushare.ops.latent_decode import (latent_decode_eligible,
                                        latent_paged_decode)
from tpushare.ops.norms import layer_norm
from tpushare.ops.rotary import apply_rotary, rotary_embedding
from tpushare.utils.profiling import span

FULL, SLIDING = "full_attention", "sliding_attention"
_F32 = jnp.float32
_NEG = -jnp.inf


@dataclasses.dataclass(frozen=True)
class AttnDims:
    """One kind of latent attention."""
    n_heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    rope_base: float

    #: a cached row is padded with zeros to a multiple of this: the
    #: chip's default layout of a pool whose rows are not whole lane
    #: tiles puts the BLOCK axis minor-most, and every tick then copies
    #: the pool to gather rows from it and back (compiled for a v5e)
    row_align: int = 128

    @property
    def key_dim(self) -> int:           # a cached row, and a query against it
        return -(-(self.kv_rank + self.rope) // self.row_align) * self.row_align


class SelectionLog:
    """A checker's tap (``LatentConfig.select_log``). A server whose
    configuration carries one has its serial admission and its decode
    step also return WHICH keys the selector kept, and leaves the last of
    each here, on the device: a checker that compares logits alone cannot
    tell a wrong selection from the keys that change sides at the
    selector's edge under rounding. The fused tick returns none (no
    checker drives it; the tests hold it to the serial admission)."""

    def __init__(self):
        #: the last serial admission: its prompt, and a chunk each (first
        #: query position, bits [n_full, queries, ceil(keys / 8)] uint8,
        #: a query's kept key positions packed low bit first)
        self.prompt = None
        self.admission = []
        #: the last decode step: (positions [B], active [B], tokens
        #: [B, 1], kept key positions [n_full, B, index_topk] int32, -1
        #: where none)
        self.step = None


class DraftLog:
    """A checker's tap (``LatentConfig.draft_log``), as ``SelectionLog``
    is the selector's: a drafting server whose configuration carries one
    also returns the logits its module drafted from, and leaves the last
    round's here, on the device: (lengths before the round [B], active
    [B], draft logits [B, V]: the module's guess at the token AFTER
    ``last_token``, i.e. at position lengths + 1). What the sampler is
    handed are the main model's logits; the module's reach nothing
    else."""

    def __init__(self):
        self.step = None


@dataclasses.dataclass(frozen=True)
class LatentConfig:
    vocab_size: int
    d_model: int
    layer_types: Tuple[str, ...]
    full: AttnDims
    swa: AttnDims
    window: int = 513                   # keys a sliding query sees, its own included
    index_heads: int = 64
    index_dim: int = 128
    index_topk: int = 2048
    n_dense: int = 1
    d_ff: int = 13824
    d_expert: int = 1536
    n_experts: int = 256                # the router's width
    experts_held: int = 32              # this chip's share ...
    expert_offset: int = 0              # ... starting at this expert
    top_k: int = 8
    n_shared: int = 1
    routed_scale: float = 1.0
    qkv_rescale: bool = True
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # queries a block of the chunked (prefill) attention; what bounds its
    # [heads, block, keys] float32 scores
    q_block_full: int = 32
    q_block_swa: int = 256
    moe_block: int = 2048               # tokens an expert dispatch
    prefill_block: int = 1024           # tokens a piece of a serial prefill
    # PagedSlotServer reads these off every family's config
    n_kv_heads: int = 1
    #: a checker's tap (``SelectionLog``); None in a deployment
    select_log: Optional[SelectionLog] = None
    #: a full layer scores its keys and attends ``index_topk`` of them;
    #: off, it attends every cached row and caches no selector key
    selector: bool = True
    gate: bool = True                   # the head-wise output gate
    #: a norm on each sublayer's output before the residual add
    sandwich_norm: bool = False
    router_bias: bool = True            # top-k of score + bias (noaux_tc)
    #: multi-token-prediction modules (0 or 1): one more cached layer
    n_mtp: int = 0
    #: a checker's tap (``DraftLog``); None in a deployment
    draft_log: Optional[DraftLog] = None

    @property
    def n_layers(self) -> int:
        return len(self.layer_types)

    @property
    def n_full(self) -> int:
        return sum(t == FULL for t in self.layer_types)

    @property
    def n_swa(self) -> int:
        return self.n_layers - self.n_full

    @property
    def n_moe(self) -> int:
        return self.n_layers - self.n_dense

    @property
    def n_sparse(self) -> int:          # layers that route: the module's too
        return self.n_moe + self.n_mtp

    @property
    def n_cached_full(self) -> int:
        """Layers of ``pool_k``: the full layers, then the module's."""
        return self.n_full + self.n_mtp

    def pool_shapes(self, n_blocks: int, block_size: int):
        """(pool_k, pool_v, pool_x) shapes: paged.init_paged_cache's
        hook. No selector, no ``pool_x``."""
        return ((self.n_cached_full, n_blocks, block_size,
                 self.full.key_dim),
                (self.n_swa, n_blocks, block_size, self.swa.key_dim),
                (self.n_full, n_blocks, block_size, self.index_dim)
                if self.selector else None)

    def init_row_cache(self, batch: int, max_len: int):
        """The dense row cache of one admission (paged._admission_row's
        hook): the two pools' rows, contiguous, with the expert counters
        the serial prefill adds to (LatentSlotServer.admit_step)."""
        row = {"k": jnp.zeros((self.n_cached_full, batch, max_len, 1,
                               self.full.key_dim), self.dtype),
               "v": jnp.zeros((self.n_swa, batch, max_len, 1,
                               self.swa.key_dim), self.dtype),
               "moe_counts": jnp.zeros((n_counts(self),), jnp.int32)}
        if self.selector:
            row["x"] = jnp.zeros((self.n_full, batch, max_len, 1,
                                  self.index_dim), self.dtype)
        if self.n_mtp:
            # the module's carry between an admission's chunks: the main
            # layers' output at the position before the chunk, and
            # whether there is one (``LatentSlotServer.admit_step``)
            row["h_last"] = jnp.zeros((self.d_model,), self.dtype)
            row["h_ok"] = jnp.zeros((), bool)
        return row


def n_counts(cfg: LatentConfig) -> int:
    """[assignments to held experts, tokens routed, keys the selectors
    kept, keys they saw, load of each (sparse layer, held expert)...]."""
    return 4 + cfg.n_sparse * cfg.experts_held


def bump(counts, c):
    """Add ``c`` [n_counts] to the running counters [2, n_counts]: int32
    in two limbs of 30 bits (low, high), so they run for the daemon's
    life without a 64-bit type or a fetch to empty them."""
    low = counts[0] + c
    return jnp.stack([low & 0x3FFFFFFF, counts[1] + (low >> 30)])


def tiny(vocab_size: int = 256, **kw) -> LatentConfig:
    """Toy widths with one layer of each kind, a window and a selector
    shorter than a test's prompts, and 4 of 16 experts held."""
    base = dict(
        vocab_size=vocab_size, d_model=64,
        layer_types=(FULL, FULL, SLIDING, SLIDING),
        full=AttnDims(4, 32, 16, 8, 8, 8, 8e7, row_align=16),
        swa=AttnDims(2, 32, 24, 12, 4, 8, 5e4, row_align=16),
        window=9, index_heads=4, index_dim=16, index_topk=12, n_dense=1,
        d_ff=96, d_expert=32, n_experts=16, experts_held=4, expert_offset=4,
        top_k=4, dtype=jnp.float32, q_block_full=8, q_block_swa=16)
    base.update(kw)
    return LatentConfig(**base)


def init_params(rng: jax.Array, cfg: LatentConfig) -> Dict[str, Any]:
    """A dict a layer under ``layers`` (the kinds differ in shape, so
    nothing is stacked over depth: a layer's weights are whole arrays,
    and no tick slices or copies one out of a stack)."""
    Dm = cfg.d_model
    keys = iter(jax.random.split(
        rng, 32 * (cfg.n_layers + cfg.n_mtp) + 8))

    def dense(shape, fan_in):
        return (jax.random.truncated_normal(next(keys), -2, 2, shape, _F32)
                / math.sqrt(fan_in)).astype(cfg.dtype)

    def rescale(a: AttnDims):
        return (cfg.d_model / math.sqrt(a.q_rank * a.kv_rank)
                if cfg.qkv_rescale else 1.0)

    def attn(a: AttnDims, selector: bool):
        H = a.n_heads
        w = {"ln1": jnp.ones((Dm,), cfg.dtype),
             "w_qa": dense((Dm, a.q_rank), Dm),
             "q_norm": jnp.ones((a.q_rank,), cfg.dtype),
             # drawn narrower by the rescale's two factors: with c_q and
             # c_kv scaled up, unit-variance weights give attention logits
             # a spread of 6 and attention that is one-hot, which no
             # trained model's is and under which one key exchanged at
             # the selector's edge changes everything downstream
             "w_qb": dense((a.q_rank, H * (a.nope + a.rope)),
                           a.q_rank * rescale(a) ** 2),
             "w_kva": dense((Dm, a.kv_rank + a.rope), Dm),
             "kv_norm": jnp.ones((a.kv_rank,), cfg.dtype),
             # stored a head: the absorbed products are batched over heads
             "w_kb": dense((H, a.nope, a.kv_rank), a.kv_rank),
             "w_vb": dense((H, a.kv_rank, a.v_dim), a.kv_rank)}
        if cfg.gate:
            w["w_g"] = dense((Dm, H), Dm)
        w["w_o"] = dense((H * a.v_dim, Dm), H * a.v_dim)
        if selector:
            IH, ID = cfg.index_heads, cfg.index_dim
            w.update({"w_iq": dense((a.q_rank, IH * ID), a.q_rank),
                      "w_ik": dense((Dm, ID), Dm),
                      "ik_norm_w": jnp.ones((ID,), cfg.dtype),
                      "ik_norm_b": jnp.zeros((ID,), cfg.dtype),
                      "w_iw": dense((Dm, IH), Dm)})
        return w

    Eh, Fe, Fs = cfg.experts_held, cfg.d_expert, cfg.n_shared * cfg.d_expert

    def layer(kind, is_dense: bool):
        w = attn(cfg.full if kind == FULL else cfg.swa,
                 kind == FULL and cfg.selector)
        w["ln2"] = jnp.ones((Dm,), cfg.dtype)
        if cfg.sandwich_norm:
            # ones, as the other norms': the depth-scaled initialisation
            # of the published recipe is of training
            w["ln1_post"] = jnp.ones((Dm,), cfg.dtype)
            w["ln2_post"] = jnp.ones((Dm,), cfg.dtype)
        if is_dense:
            w.update({"w_gate": dense((Dm, cfg.d_ff), Dm),
                      "w_up": dense((Dm, cfg.d_ff), Dm),
                      "w_down": dense((cfg.d_ff, Dm), cfg.d_ff)})
        else:
            w.update({
                "router": dense((Dm, cfg.n_experts), Dm),
                # the selection bias of noaux_tc: a weight. A trained
                # one flattens the experts' load, so it is drawn at the
                # scale of the gap it has to bridge: among 256 sigmoid
                # scores of a position the 8th and 9th lie 0.005 apart
                # (median), and 0.01 x normal changes a quarter of the
                # choices while the load stays within 1.3 of its mean
                # (0.1 x normal decided the routing: a few experts won
                # every token, load max/mean 8 to 10)
                "router_bias": 0.01 * jax.random.truncated_normal(
                    next(keys), -2, 2, (cfg.n_experts,), _F32),
                "w_gate": dense((Eh, Dm, Fe), Dm),
                "w_up": dense((Eh, Dm, Fe), Dm),
                "w_down": dense((Eh, Fe, Dm), Fe),
                "ws_gate": dense((Dm, Fs), Dm),
                "ws_up": dense((Dm, Fs), Dm),
                "ws_down": dense((Fs, Dm), Fs)})
            if not cfg.router_bias:
                del w["router_bias"]
        return w

    layers = [layer(kind, i < cfg.n_dense)
              for i, kind in enumerate(cfg.layer_types)]
    params = {"embed": dense((cfg.vocab_size, Dm), Dm),
              "unembed": dense((Dm, cfg.vocab_size), Dm),
              "final_norm": jnp.ones((Dm,), cfg.dtype),
              "layers": layers}
    if cfg.n_mtp:
        # the module shares the embedding and the head; its own: the two
        # norms and the projection that join a token's embedding to the
        # hidden state before it, one layer of the expert kind, a norm
        params["mtp"] = [dict(
            layer(FULL, False), enorm=jnp.ones((Dm,), cfg.dtype),
            hnorm=jnp.ones((Dm,), cfg.dtype),
            w_eh=dense((2 * Dm, Dm), 2 * Dm),
            final_norm=jnp.ones((Dm,), cfg.dtype))
            for _ in range(cfg.n_mtp)]
    return params


# ---------------------------------------------------------------------------
# One layer's arithmetic on flat tokens [N, Dm] at positions [N].
# ---------------------------------------------------------------------------


def _rms(x, w, eps, scale: float = 1.0):
    xf = x.astype(_F32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * (w.astype(_F32) * scale)
            ).astype(x.dtype)


def _rope(x, pos, base: float):
    """x [N, H, D] rotated at ``pos`` [N] (pairs (i, i + D/2))."""
    cos, sin = rotary_embedding(pos, x.shape[-1], base=base)
    return apply_rotary(x, cos, sin)


def _rope_head(x, pos, n_rope: int, base: float):
    """Rotary on the first ``n_rope`` of the last axis (the selector)."""
    return jnp.concatenate(
        [_rope(x[..., :n_rope], pos, base), x[..., n_rope:]], axis=-1)


def _project(h, pos, w, a: AttnDims, cfg: LatentConfig, selector: bool):
    """Everything a layer's attention needs of ``h`` [N, Dm]: the
    absorbed query [N, H, kv_rank + rope], the row to cache, the gate,
    and the selector's query, key (``xrow``, cached too) and head
    weights."""
    N = h.shape[0]
    H = a.n_heads
    aq = math.sqrt(cfg.d_model / a.q_rank) if cfg.qkv_rescale else 1.0
    akv = math.sqrt(cfg.d_model / a.kv_rank) if cfg.qkv_rescale else 1.0
    cq = _rms(h @ w["w_qa"], w["q_norm"], cfg.norm_eps, aq)
    q = (cq @ w["w_qb"]).reshape(N, H, a.nope + a.rope)
    q_rope = _rope(q[..., a.nope:], pos, a.rope_base)
    kv = h @ w["w_kva"]
    ckv = _rms(kv[:, :a.kv_rank], w["kv_norm"], cfg.norm_eps, akv)
    kr = _rope(kv[:, None, a.kv_rank:], pos, a.rope_base)[:, 0]
    q_abs = jnp.einsum("nhd,hdc->nhc", q[..., :a.nope], w["w_kb"])
    pad = a.key_dim - a.kv_rank - a.rope
    out = {"q": jnp.concatenate(
               [q_abs, q_rope, jnp.zeros((N, H, pad), q.dtype)], axis=-1),
           "row": jnp.concatenate(
               [ckv, kr, jnp.zeros((N, pad), kv.dtype)], axis=-1)}
    if cfg.gate:
        out["gate"] = jax.nn.sigmoid((h @ w["w_g"]).astype(_F32))
    if selector:
        IH, ID = cfg.index_heads, cfg.index_dim
        qi = (cq @ w["w_iq"]).reshape(N, IH, ID)
        ki = layer_norm(h @ w["w_ik"], w["ik_norm_w"], w["ik_norm_b"],
                        eps=cfg.norm_eps)
        out["qi"] = _rope_head(qi, pos, a.rope, a.rope_base)
        out["xrow"] = _rope_head(ki[:, None, :], pos, a.rope,
                                 a.rope_base)[:, 0]
        out["wi"] = (h @ w["w_iw"]).astype(_F32) / math.sqrt(IH * ID)
    return out


def _attend(q, keys, keep, a: AttnDims):
    """Absorbed latent attention. q [..., Q, H, C+R]; keys [..., T, C+R]
    (one row for all heads); keep [..., Q, T]. Returns the latent output
    [..., Q, H, C], before W_vb. Queries and heads are ONE axis of both
    products (a [Q x H, C+R] by [C+R, T] matrix product: with them apart
    the chip's compiler made the query axis a convolution window and ran
    at a twentieth of the matrix unit's rate)."""
    *lead, Q, H, C = q.shape
    scale = 1.0 / math.sqrt(a.nope + a.rope)
    s = jnp.einsum("...mc,...tc->...mt", q.reshape(*lead, Q * H, C), keys,
                   preferred_element_type=_F32) * scale
    s = jnp.where(keep[..., :, None, :], s.reshape(*lead, Q, H, -1), _NEG)
    p = jax.nn.softmax(s, axis=-1).astype(keys.dtype)
    # over the whole row, then cut: the rope columns of the output are
    # an eighth more work, slicing the rows themselves is a relayout
    o = jnp.einsum("...mt,...tc->...mc", p.reshape(*lead, Q * H, -1), keys)
    return o.reshape(*lead, Q, H, C)[..., :a.kv_rank]


def _select_scores(qi, wi, ki):
    """I[q, t] = sum_j w[q, j] relu(q_I[q, j] . k_I[t]), float32.
    qi [..., Q, IH, ID]; wi [..., Q, IH]; ki [..., T, ID]."""
    *lead, Q, IH, ID = qi.shape
    s = jnp.einsum("...md,...td->...mt", qi.reshape(*lead, Q * IH, ID), ki,
                   preferred_element_type=_F32)
    s = jax.nn.relu(s).reshape(*lead, Q, IH, -1)
    return jnp.sum(wi[..., None] * s, axis=-2)


def _kth_largest(x, k: int):
    """The k-th largest of the last axis, exactly, by bisection on the
    float's bits (32 passes of compare-and-count; no sort). -inf where a
    row has fewer than k finite entries."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    # a key that orders like the float: flip the magnitude of negatives
    key = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    ukey = jax.lax.bitcast_convert_type(key, jnp.uint32) ^ jnp.uint32(1 << 31)

    def body(i, ans):
        cand = ans | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        n = jnp.sum(ukey >= cand[..., None], axis=-1)
        return jnp.where(n >= k, cand, ans)

    ans = jax.lax.fori_loop(0, 32, body,
                            jnp.zeros(x.shape[:-1], jnp.uint32))
    key = jax.lax.bitcast_convert_type(ans ^ jnp.uint32(1 << 31), jnp.int32)
    bits = jnp.where(key < 0, key ^ jnp.int32(0x7FFFFFFF), key)
    return jax.lax.bitcast_convert_type(bits, _F32)


def _top_mask(x, k: int):
    """True at the k largest of the last axis, the lower index first
    among equals (``lax.top_k``'s order, which the decode step uses);
    where fewer than k entries are finite, at all of those."""
    thr = _kth_largest(x, k)[..., None]
    above = x > thr
    ties = x == thr
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    return (above | (ties & (jnp.cumsum(ties, axis=-1) <= room))) & (x > _NEG)


def _blocks_of(x, n: int):
    """[P, ...] -> [ceil(P/n), n, ...], zero-padded."""
    P = x.shape[0]
    pad = -P % n
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad, *x.shape[1:]), x.dtype)])
    return x.reshape((P + pad) // n, n, *x.shape[1:])


def _query_blocks(qpos, n: int):
    """Query positions in blocks of ``n``; the padding of the last block
    repeats the last real position (it attends something, and its result
    is dropped)."""
    qp = _blocks_of(qpos + 1, n) - 1
    return jnp.where(qp < 0, qpos[-1], qp)


def _chunk_full(keys, xkeys, pr, qpos, cfg: LatentConfig):
    """A chunk of queries against one sequence's contiguous latent rows
    [T, kv_rank + rope] and selector keys [T, index_dim] (the chunk's
    own already written): the selector's scores a block of queries at a
    time, ONE threshold search over the whole chunk (its 32 passes are
    latency-bound: a search a block would be a thousand small steps),
    then attention masked to the selected set, a block at a time.
    Returns (latent output [P, H, C], the keys each query kept [P, T])."""
    a = cfg.full
    T, P = keys.shape[0], pr["q"].shape[0]
    kpos = jnp.arange(T)
    Qb = min(cfg.q_block_full, P)
    qp = _query_blocks(qpos, Qb)
    keep = kpos[None, None, :] <= qp[:, :, None]
    if cfg.selector and T > cfg.index_topk:
        with jax.named_scope("latent_select"):
            I = jax.lax.map(
                lambda x: jnp.where(x[2], _select_scores(x[0], x[1], xkeys),
                                    _NEG),
                (_blocks_of(pr["qi"], Qb), _blocks_of(pr["wi"], Qb), keep))
            keep = _top_mask(I.reshape(-1, T), cfg.index_topk).reshape(I.shape)
    o = jax.lax.map(lambda x: _attend(x[0], keys, x[1], a),
                    (_blocks_of(pr["q"], Qb), keep))
    return o.reshape(-1, *o.shape[2:])[:P], keep.reshape(-1, T)[:P]


def _selection_counts(keep, qpos, live):
    """[keys kept, keys seen] of live queries at ``qpos`` whose kept keys
    are ``keep`` [Q, T] (a mask) or [Q, K] (positions, -1 where none)."""
    kept = keep if keep.dtype == jnp.bool_ else keep >= 0
    return jnp.stack([jnp.sum(kept & live[:, None]),
                      jnp.sum(jnp.where(live, qpos + 1, 0))]).astype(jnp.int32)


def _chunk_swa(keys, pr, qpos, cfg: LatentConfig):
    """The same for a sliding layer: a block of queries reads only the
    rows its windows reach."""
    a, W = cfg.swa, cfg.window
    T, P = keys.shape[0], pr["q"].shape[0]
    Qb = min(cfg.q_block_swa, P)
    Lk = min(T, Qb + W - 1)

    def block(args):
        q, qp = args
        start = jnp.clip(qp[0] - (W - 1), 0, T - Lk)
        k = jax.lax.dynamic_slice_in_dim(keys, start, Lk, axis=0)
        kpos = start + jnp.arange(Lk)
        keep = ((kpos[None, :] <= qp[:, None])
                & (kpos[None, :] > qp[:, None] - W))
        return _attend(q, k, keep, a)

    o = jax.lax.map(block, (_blocks_of(pr["q"], Qb),
                            _query_blocks(qpos, Qb)))
    return o.reshape(-1, *o.shape[2:])[:P]


def _decode_full(pool, xpool, li: int, tb, pos, pr, cfg: LatentConfig):
    """One query a slot against the paged full-layer pools (its own rows
    already written): score every cached position of the slot through
    the block table, keep ``index_topk``, gather those rows, attend.
    tb [B, mb] holds valid block ids (no -1). Returns (latent output
    [B, H, C], the key positions each slot kept [B, K], -1 where a slot
    holds fewer)."""
    a, bs = cfg.full, pool.shape[2]
    B, mb = tb.shape
    T = mb * bs
    with jax.named_scope("latent_select"):
        ki = xpool[li, tb].reshape(B, T, -1)
        I = _select_scores(pr["qi"][:, None], pr["wi"][:, None], ki)[:, 0]
        I = jnp.where(jnp.arange(T)[None, :] <= pos[:, None], I, _NEG)
        vals, idx = jax.lax.top_k(I, min(cfg.index_topk, T))
        blk = jnp.take_along_axis(tb, idx // bs, axis=1)
        rows = pool[li, blk, idx % bs]                      # [B, K, C+R]
    kept = vals > _NEG
    o = _attend(pr["q"][:, None], rows, kept[:, None, :], a)[:, 0]
    return o, jnp.where(kept, idx, -1)


def decode_kernel_serves(cfg: LatentConfig, n_slots: int, n_q: int,
                         pool) -> bool:
    """Whether ``_decode_all`` hands ``n_q`` queries a slot to the paged
    kernel (``ops/latent_decode``): by the backend and the shapes the
    call will see, nothing else."""
    a = cfg.full
    q = jax.ShapeDtypeStruct((n_slots, n_q, a.n_heads, a.key_dim), cfg.dtype)
    return latent_decode_eligible(q, pool, a.kv_rank)


def _gather_attend(pool, li: int, tb, pos, q, a: AttnDims):
    """``_decode_all`` in plain ``jnp``: every slot's whole table width
    gathered into a dense [B, keys a table can hold, row] copy, masked
    past each query's position."""
    B, mb = tb.shape
    rows = pool[li, tb].reshape(B, mb * pool.shape[2], -1)
    keep = jnp.arange(rows.shape[1])[None, None, :] <= pos[:, :, None]
    return _attend(q, rows, keep, a)


def _decode_all(pool, li: int, tb, pos, live, q, cfg: LatentConfig):
    """``q`` [B, Q, H, C+R]: Q queries a slot at ``pos`` [B, Q] against
    every cached row of the slot, read through the block table (its own
    rows already written): the full layer of a model with no selector.
    A row past a query's position is masked, so what a rejected draft
    left there is never attended. Returns the latent output
    [B, Q, H, C]; what a query that is not ``live`` [B, Q] gets is
    nobody's to read.

    Where the backend and the shapes allow (``latent_decode_eligible``)
    a paged kernel reads each slot's live blocks once; every other call
    (the CPU, the float32 toys, odd widths) gathers, and that form is
    the reference the kernel is tested against."""
    a = cfg.full
    with jax.named_scope("latent_attend"):
        if latent_decode_eligible(q, pool, a.kv_rank):
            return latent_paged_decode(
                q, pool, tb, pos, live, layer=li, kv_rank=a.kv_rank,
                scale=1.0 / math.sqrt(a.nope + a.rope))
        return _gather_attend(pool, li, tb, pos, q, a)


def _decode_swa(pool, li: int, tb, pos, pr, cfg: LatentConfig):
    """One query a slot against the blocks its window touches."""
    a, W, bs = cfg.swa, cfg.window, pool.shape[2]
    B, mb = tb.shape
    nwb = min(mb, (W - 1 + bs - 1) // bs + 1)
    first = jnp.maximum(pos - (W - 1), 0) // bs
    bidx = first[:, None] + jnp.arange(nwb)[None, :]
    blk = jnp.take_along_axis(tb, jnp.minimum(bidx, mb - 1), axis=1)
    rows = pool[li, blk].reshape(B, nwb * bs, -1)
    kpos = (bidx[:, :, None] * bs + jnp.arange(bs)).reshape(B, nwb * bs)
    keep = (kpos <= pos[:, None]) & (kpos > pos[:, None] - W)
    return _attend(pr["q"][:, None], rows, keep[:, None, :], a)[:, 0]


def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def route(h, router, bias, cfg: LatentConfig):
    """(chosen experts [N, K], their weights [N, K] float32): sigmoid
    scores over the router's whole width, top-k of score + bias, the
    chosen scores renormalised."""
    s = jax.nn.sigmoid(jnp.dot(h, router, preferred_element_type=_F32))
    _, top_i = jax.lax.top_k(
        s if bias is None else s + bias.astype(_F32), cfg.top_k)
    top_s = jnp.take_along_axis(s, top_i, axis=1)
    return top_i, (top_s / jnp.sum(top_s, axis=-1, keepdims=True)
                   * cfg.routed_scale)


def moe_ffn(h, w, cfg: LatentConfig, live):
    """``_moe_ffn`` over at most ``cfg.moe_block`` tokens at a time: a
    long serial prefill would otherwise hold its [tokens x top_k, d_model]
    assignment rows in float32 at once (1.3 GB at 8,192 tokens)."""
    N = h.shape[0]
    if N <= cfg.moe_block:
        return _moe_ffn(h, w, cfg, live)
    y, counts = jax.lax.map(
        lambda a: _moe_ffn(a[0], w, cfg, a[1]),
        (_blocks_of(h, cfg.moe_block), _blocks_of(live, cfg.moe_block)))
    return y.reshape(-1, y.shape[-1])[:N], jnp.sum(counts, axis=0)


def _moe_ffn(h, w, cfg: LatentConfig, live):
    """This chip's part of the expert layer for tokens h [N, Dm]: the
    held experts' weighted outputs for the assignments that reach them
    (sorted by expert, grouped GEMMs over the group sizes, so the work
    follows the assignments), plus the shared expert. ``live`` [N] masks
    padding out of the routed work and the counters. Returns (y, counts
    [2 + experts_held])."""
    N, Dm = h.shape
    K, Eh = cfg.top_k, cfg.experts_held
    top_i, top_w = route(h, w["router"], w.get("router_bias"), cfg)
    le = top_i - cfg.expert_offset
    local = (le >= 0) & (le < Eh) & live[:, None]
    le = jnp.where(local, le, Eh).reshape(N * K)    # the rest sort last
    order = jnp.argsort(le, stable=True)
    tok = (jnp.arange(N * K, dtype=jnp.int32) // K)[order]
    sizes = jnp.bincount(le, length=Eh + 1)[:Eh].astype(jnp.int32)
    x = h[tok]
    ff = (jax.nn.silu(jax.lax.ragged_dot(x, w["w_gate"], sizes))
          * jax.lax.ragged_dot(x, w["w_up"], sizes))
    y = jax.lax.ragged_dot(ff, w["w_down"], sizes)
    mine = (jnp.arange(N * K) < jnp.sum(sizes))[:, None]
    y = jnp.where(mine, top_w.reshape(N * K)[order][:, None]
                  * y.astype(_F32), 0.0)
    out = jnp.zeros((N, Dm), _F32).at[tok].add(y)
    out = out + _swiglu(h, w["ws_gate"], w["ws_up"], w["ws_down"])
    counts = jnp.concatenate([
        jnp.stack([jnp.sum(local), jnp.sum(live)]).astype(jnp.int32), sizes])
    return out.astype(h.dtype), counts


def _layer(x, w, kind, li: int, dense: bool, cfg: LatentConfig, pos, live,
           attend):
    """One decoder layer on flat tokens x [N, Dm]: (x, what ``attend``
    counted, the expert counts or None where the FFN is dense)."""
    N = x.shape[0]
    a = cfg.full if kind == FULL else cfg.swa
    h = _rms(x, w["ln1"], cfg.norm_eps)
    pr = _project(h, pos, w, a, cfg,
                  selector=kind == FULL and cfg.selector)
    o, sel = attend(kind, li, pr)
    o = jnp.einsum("nhc,hcv->nhv", o, w["w_vb"])
    if cfg.gate:
        o = o * pr["gate"][..., None].astype(o.dtype)
    o = o.reshape(N, -1) @ w["w_o"]
    if cfg.sandwich_norm:
        o = _rms(o, w["ln1_post"], cfg.norm_eps)
    x = x + o
    h = _rms(x, w["ln2"], cfg.norm_eps)
    if dense:
        y, c = _swiglu(h, w["w_gate"], w["w_up"], w["w_down"]), None
    else:
        y, c = moe_ffn(h, w, cfg, live)
    if cfg.sandwich_norm:
        y = _rms(y, w["ln2_post"], cfg.norm_eps)
    return x + y, sel, c


def _run_layers(params, cfg: LatentConfig, tokens, pos, live, attend):
    """The residual stream of flat ``tokens`` [N] at ``pos`` [N] through
    every layer. ``attend(kind, index in kind, projections) -> (latent
    output [N, H, C], [keys kept, keys seen] on a full layer with a
    selector else None)`` owns the cache. Returns (final hidden [N, Dm],
    counts [4 + n_moe x experts_held])."""
    x = params["embed"][tokens].astype(cfg.dtype)
    totals = jnp.zeros((4,), jnp.int32)
    loads = []
    n_of = {FULL: 0, SLIDING: 0}
    for i, (kind, w) in enumerate(zip(cfg.layer_types, params["layers"])):
        li = n_of[kind]
        n_of[kind] += 1
        x, sel, c = _layer(x, w, kind, li, i < cfg.n_dense, cfg, pos, live,
                           attend)
        if sel is not None:
            totals = totals.at[2:].add(sel)
        if c is not None:
            totals = totals.at[:2].add(c[:2])
            loads.append(c[2:])
    return x, jnp.concatenate([totals, *loads])


def _mtp_layer(params, cfg: LatentConfig, tokens, h_prev, pos, live, attend):
    """The module on flat positions ``pos`` [N]: ``h_prev`` [N, Dm] is
    the main layers' output there (before the final norm) and ``tokens``
    [N] the tokens that FOLLOW them. Its layer is cached layer
    ``n_full`` of the full pool. Returns (the module's hidden [N, Dm],
    counts [2 + experts_held])."""
    m = params["mtp"][0]
    e = params["embed"][tokens].astype(cfg.dtype)
    x = jnp.concatenate([_rms(e, m["enorm"], cfg.norm_eps),
                         _rms(h_prev, m["hnorm"], cfg.norm_eps)],
                        axis=-1) @ m["w_eh"]
    x, _, c = _layer(x, m, FULL, cfg.n_full, False, cfg, pos, live, attend)
    return x, c


def _merge_counts(c, cm):
    """The main layers' counts with the module's appended: its tokens
    and assignments join the totals, its loads are the last layer's."""
    return jnp.concatenate([c[:2] + cm[:2], c[2:], cm[2:]])


def _mtp_logits(params, cfg: LatentConfig, x):
    x = _rms(x, params["mtp"][0]["final_norm"], cfg.norm_eps)
    return (x @ params["unembed"]).astype(_F32)


def _logits(params, cfg: LatentConfig, x):
    x = _rms(x, params["final_norm"], cfg.norm_eps)
    return (x @ params["unembed"]).astype(_F32)


# ---------------------------------------------------------------------------
# The three programs of the slot server.
# ---------------------------------------------------------------------------


def _write_rows(pool, li: int, table, slot_of, pos, ok, rows):
    """Scatter ``rows`` [N, W] into (table[slot_of, pos // bs], pos % bs)
    of layer ``li``; rows that are not ``ok`` (or have no block) go to
    the trash block, the pool's last."""
    bs, mb = pool.shape[2], table.shape[1]
    blk = table[slot_of, jnp.minimum(pos // bs, mb - 1)]
    blk = jnp.where(ok & (blk >= 0) & (pos // bs < mb), blk,
                    pool.shape[1] - 1)
    return pool.at[li, blk, pos % bs].set(rows)


#: ``_write_rows`` as a program of its own, into the pool's own buffer
#: (the eager form copies a pool of gigabytes): a serial admission's one
#: row behind its chunk (``LatentSlotServer.admit_step``)
_write_rows_donated = jax.jit(_write_rows, static_argnums=1, donate_argnums=0)


class _Paged:
    """The attention of one pass over the paged pools: Q decode rows a
    slot (B x Q tokens, slot-major; Q is 2 where a tick verifies a draft
    beside the last emitted token), and (in a fused tick) one admission
    chunk of P rows behind them. ``pools`` ({FULL, SLIDING, "x"}) is
    shared by the passes of one program and rebound as rows are
    written."""

    def __init__(self, cfg, pools, table, pos=None, live=None, chunk=None):
        self.cfg, self.table, self.pools = cfg, table, pools
        self.pos, self.live = pos, live     # [B, Q], or None: no decode rows
        self.tb = jnp.maximum(table, 0)
        self.chunk = chunk          # (slot, positions [P], live [P], n_kblk)
        self.kept = []              # a selecting layer: what its decode rows kept

    def _write(self, name, li, nd, rows):
        pool = self.pools[name]
        if nd:
            Q = self.pos.shape[1]
            pool = _write_rows(pool, li, self.table, jnp.arange(nd) // Q,
                               self.pos.reshape(nd), self.live.reshape(nd),
                               rows[:nd])
        if self.chunk is not None:
            slot, cpos, clive, _ = self.chunk
            pool = _write_rows(pool, li, self.table, slot, cpos, clive,
                               rows[nd:])
        self.pools[name] = pool
        return pool

    def _slot_rows(self, pool, li):
        """The admitting slot's first n_kblk blocks of one pool layer,
        contiguous."""
        slot, _, _, n_kblk = self.chunk
        rows = pool[li, jax.lax.dynamic_slice_in_dim(
            self.tb, slot, 1, axis=0)[0, :n_kblk]]
        return rows.reshape(-1, rows.shape[-1])

    def __call__(self, kind, li, pr):
        cfg = self.cfg
        nd = 0 if self.pos is None else self.pos.size
        selects = kind == FULL and cfg.selector
        pool = self._write(kind, li, nd, pr["row"])
        if selects:
            xpool = self._write("x", li, nd, pr["xrow"])
        sel, outs = None, []
        if nd:
            dec = {k: v[:nd] for k, v in pr.items()}
            if selects:             # one query a slot
                o, kept = _decode_full(pool, xpool, li, self.tb,
                                       self.pos[:, 0], dec, cfg)
                self.kept.append(kept)
                sel = _selection_counts(kept, self.pos[:, 0],
                                        self.live[:, 0])
            elif kind == FULL:
                q = dec["q"].reshape(*self.pos.shape, *dec["q"].shape[1:])
                o = _decode_all(pool, li, self.tb, self.pos, self.live, q,
                                cfg)
                o = o.reshape(nd, *o.shape[2:])
            else:
                o = _decode_swa(pool, li, self.tb, self.pos[:, 0], dec, cfg)
            outs.append(o)
        if self.chunk is not None:
            chunk = {k: v[nd:] for k, v in pr.items()}
            keys, (_, cpos, clive, _) = self._slot_rows(pool, li), self.chunk
            if kind == FULL:
                oc, keep = _chunk_full(
                    keys, self._slot_rows(xpool, li) if selects else None,
                    chunk, cpos, cfg)
                if selects:
                    c = _selection_counts(keep, cpos, clive)
                    sel = c if sel is None else sel + c
            else:
                oc = _chunk_swa(keys, chunk, cpos, cfg)
            outs.append(oc)
        return (outs[0] if len(outs) == 1 else jnp.concatenate(outs)), sel


def _pools(pool_k, pool_v, pool_x):
    return {FULL: pool_k, SLIDING: pool_v, "x": pool_x}


def decode_tick(params, tokens, pool_k, pool_v, pool_x, table, lengths,
                active, grow, counts, *, cfg: LatentConfig):
    """One token a slot, over the table with this tick's new blocks
    written in (``paged.apply_growth``; ``grow`` None: none). tokens
    [B, 1]. Returns (logits [B, 1, V], pool_k, pool_v, pool_x, lengths
    advanced for active slots, the table, counts), and under a
    ``select_log`` the key positions each full layer kept
    [n_full, B, K]."""
    table = apply_growth(table, lengths, grow, pool_k.shape[2])
    att = _Paged(cfg, _pools(pool_k, pool_v, pool_x), table,
                 lengths[:, None], active[:, None])
    x, c = _run_layers(params, cfg, tokens[:, 0], lengths, active, att)
    out = (_logits(params, cfg, x)[:, None], att.pools[FULL],
           att.pools[SLIDING], att.pools["x"],
           lengths + active.astype(jnp.int32), table, bump(counts, c))
    return out if cfg.select_log is None else (*out, jnp.stack(att.kept))


def fused_tick(params, last_token, chunk_tokens, pool_k, pool_v, pool_x,
               table, lengths, active, grow, slot, done, n_valid, counts, *,
               cfg: LatentConfig, n_kblk: int):
    """A decode step for the active slots and ``chunk_tokens`` [P] of
    slot ``slot``'s prompt at positions done.. in ONE pass over the
    weights: B + P tokens, not B x P, over the table with this tick's
    new blocks written in (``grow``, as decode_tick's). The chunk
    attends its slot's first ``n_kblk`` blocks. Returns (decode logits
    [B, V], the logits after the chunk's last real token [1, V], pool_k,
    pool_v, pool_x, lengths advanced for the decode rows, the table,
    counts)."""
    table = apply_growth(table, lengths, grow, pool_k.shape[2])
    B, P = last_token.shape[0], chunk_tokens.shape[0]
    cpos = done + jnp.arange(P)
    clive = jnp.arange(P) < n_valid
    att = _Paged(cfg, _pools(pool_k, pool_v, pool_x), table,
                 lengths[:, None], active[:, None],
                 chunk=(slot, cpos, clive, n_kblk))
    x, c = _run_layers(
        params, cfg, jnp.concatenate([last_token[:, 0], chunk_tokens]),
        jnp.concatenate([lengths, cpos]), jnp.concatenate([active, clive]),
        att)
    last = jax.lax.dynamic_slice_in_dim(x, B + n_valid - 1, 1, axis=0)
    lg = _logits(params, cfg, jnp.concatenate([x[:B], last]))
    return (lg[:B], lg[B:], att.pools[FULL], att.pools[SLIDING],
            att.pools["x"], lengths + active.astype(jnp.int32), table,
            bump(counts, c))


def draft_tick(params, last_token, mtp_h, mtp_tok, mtp_n, pool_k, pool_v,
               pool_x, table, lengths, active, grow, counts, key=None,
               chunk_tokens=None, slot=None, done=None, n_valid=None, *,
               cfg: LatentConfig, cap: int, sampling=None, n_kblk: int = 0):
    """A self-drafting round for the active slots, and with
    ``chunk_tokens`` an admission chunk behind it (``fused_tick``'s
    arguments), in one program over the grown table.

    What a slot carries between rounds (``mtp_h`` [B, 2, Dm], ``mtp_tok``
    [B, 2], ``mtp_n`` [B]): the main layers' output at the ``n`` (1 or 2)
    positions its last round committed, lengths - n .., and the tokens
    that follow them, the last of which is ``last_token``. The round:

      draft    the module runs those positions, writing its own rows
               there, and its guess after the last is the draft;
      verify   the main layers run [last_token, draft] at lengths,
               lengths + 1 in one pass over the weights;
      accept   models/spec.py's cores on the two positions' logits: the
               slot advances by 1 (the draft is not what the model says
               after last_token) or 2, and its next last_token is the
               model's own token at the cut. A rejected position leaves
               a stale row in the main layers' pool, and with it the
               module would leave one in its own next round: the length
               mask hides both and the next round overwrites them.

    An admission chunk's tokens run through the module a position
    behind: token j joins the hidden state before it, which for the
    chunk's first token is the admitting slot's ``mtp_h[slot, 0]`` where
    ``mtp_n[slot]`` says a chunk left one (not after a prefix hit: the
    row at the shared prefix's last position stays its publisher's).

    Returns (the first verified position's logits [B, V], the logits
    after the chunk's last real token [1, V] or None, drafts [B],
    corrections [B, 1], accepted counts [B], pool_k, pool_v, pool_x,
    lengths advanced, the table, counts, last_token, mtp_h, mtp_tok,
    mtp_n), and under a ``draft_log`` the module's draft logits
    [B, V]."""
    table = apply_growth(table, lengths, grow, pool_k.shape[2])
    B, Dm = lengths.shape[0], cfg.d_model
    two = jnp.arange(2)
    pools = _pools(pool_k, pool_v, pool_x)
    with jax.named_scope("mtp_draft"):
        mpos = (lengths - mtp_n)[:, None] + two
        mlive = active[:, None] & (two < mtp_n[:, None])
        xm, cm = _mtp_layer(
            params, cfg, mtp_tok.reshape(-1), mtp_h.reshape(2 * B, Dm),
            mpos.reshape(-1), mlive.reshape(-1),
            _Paged(cfg, pools, table, mpos, mlive))
        dl = _mtp_logits(params, cfg, jnp.take_along_axis(
            xm.reshape(B, 2, Dm),
            jnp.maximum(mtp_n - 1, 0)[:, None, None], axis=1)[:, 0])
        if sampling is None:
            draft = jnp.argmax(dl, axis=-1).astype(jnp.int32)
        else:
            k_draft, k_accept = jax.random.split(key)
            draft, qd = draft_sample_core(dl, k_draft, **sampling)
            draft = draft.astype(jnp.int32)
    with jax.named_scope("mtp_verify"):
        vpos = lengths[:, None] + two
        vlive = jnp.broadcast_to(active[:, None], (B, 2))
        toks = jnp.concatenate([last_token, draft[:, None]], 1).reshape(-1)
        pos, live, ch = vpos.reshape(-1), vlive.reshape(-1), None
        if chunk_tokens is not None:
            P = chunk_tokens.shape[0]
            cpos = done + jnp.arange(P)
            clive = jnp.arange(P) < n_valid
            ch = (slot, cpos, clive, n_kblk)
            toks = jnp.concatenate([toks, chunk_tokens])
            pos = jnp.concatenate([pos, cpos])
            live = jnp.concatenate([live, clive])
        x, c = _run_layers(params, cfg, toks, pos, live,
                           _Paged(cfg, pools, table, vpos, vlive, chunk=ch))
        tl = _logits(params, cfg, x[:2 * B]).reshape(B, 2, -1)
    if sampling is None:
        a_b, corr = greedy_accept_core(tl, draft[:, None], lengths, cap=cap)
    else:
        a_b, corr = spec_accept_core(tl, draft[:, None], qd[:, None],
                                     k_accept, lengths, cap=cap, **sampling)
    new_h = jnp.where(active[:, None, None], x[:2 * B].reshape(B, 2, Dm),
                      mtp_h)
    new_tok = jnp.where(active[:, None], jnp.stack(
        [jnp.where(a_b >= 1, draft, corr[:, 0]), corr[:, 0]], axis=1),
        mtp_tok)
    new_n = jnp.where(active, a_b + 1, mtp_n)
    first = None
    if chunk_tokens is not None:
        with jax.named_scope("mtp_draft"):
            xc = x[2 * B:]
            carry = jax.lax.dynamic_slice(mtp_h, (slot, 0, 0), (1, 1, Dm))[0]
            has = jax.lax.dynamic_slice(mtp_n, (slot,), (1,))[0] > 0
            mlive_c = clive & ((jnp.arange(P) > 0) | has)
            mpos_c = jnp.maximum(cpos - 1, 0)
            _, cm2 = _mtp_layer(
                params, cfg, chunk_tokens, jnp.concatenate([carry, xc[:-1]]),
                mpos_c, mlive_c,
                _Paged(cfg, pools, table, chunk=(slot, mpos_c, mlive_c,
                                                 n_kblk)))
            cm = cm + cm2
        last = jax.lax.dynamic_slice_in_dim(xc, n_valid - 1, 1, axis=0)
        new_h = jax.lax.dynamic_update_slice(new_h, last[:, None],
                                             (slot, 0, 0))
        new_n = jax.lax.dynamic_update_slice(
            new_n, jnp.ones((1,), new_n.dtype), (slot,))
        first = _logits(params, cfg, last)
    out = (tl[:, 0], first, draft, corr, a_b, pools[FULL], pools[SLIDING],
           pools["x"], lengths + active.astype(jnp.int32) * (a_b + 1),
           table, bump(counts, _merge_counts(c, cm)),
           jnp.where(active[:, None], corr, last_token), new_h, new_tok,
           new_n)
    return out if cfg.draft_log is None else (*out, dl)


def paged_forward(params, tokens, cfg: LatentConfig, *, cache=None,
                  pos_offset=0, attn_impl: str = "auto", layers_hook=None,
                  mlora_idx=None, mlora_scale: float = 1.0):
    """transformer.forward's shape for the cache PagedSlotServer's serial
    prefill hands a family: an admission's dense row, tokens [1, P] at
    pos_offset... Returns (logits [1, P, V], the row). Where the
    configuration has a multi-token-prediction module the row also
    carries the module's rows (layer ``n_full`` of "k", written a
    position behind: token j with the hidden state before it, from
    "h_last" for the first where "h_ok") and returns the main layers'
    output at every position of the chunk under "hidden"."""
    del attn_impl, mlora_scale
    if layers_hook is not None or mlora_idx is not None:
        raise ValueError("the latent family has no layers_hook or "
                         "adapter bank")
    if cache is None:
        raise ValueError("the latent family serves through a cache "
                         "(LatentSlotServer); it has no cacheless forward")
    if "pool_k" in cache:
        raise NotImplementedError(
            "over the paged pools the latent family runs its own programs "
            "(latent.decode_tick, latent.fused_tick), not decode_core's")
    P = tokens.shape[1]
    # A long serial prefill (a whole prompt, padded to a power of two of
    # blocks) runs as consecutive pieces through all the layers, each
    # attending the row the earlier ones wrote: its activations are a
    # piece's, not the prompt's.
    piece = max(d for d in range(1, min(P, cfg.prefill_block) + 1)
                if P % d == 0)
    if piece < min(P, cfg.prefill_block) // 4:
        piece = P                       # an awkward length: all at once

    def run(carry, xs):
        toks, start = xs
        qpos = start + jnp.arange(piece)
        rows = {FULL: carry["k"], SLIDING: carry["v"], "x": carry.get("x")}

        def write(name, li, new):
            rows[name] = jax.lax.dynamic_update_slice(
                rows[name], new[None, None, :, None, :].astype(
                    rows[name].dtype), (li, 0, start, 0, 0))
            return rows[name][li, 0, :, 0]

        kept = []

        def attend(kind, li, pr):
            keys = write(kind, li, pr["row"])
            if kind != FULL:
                return _chunk_swa(keys, pr, qpos, cfg), None
            if not cfg.selector:
                return _chunk_full(keys, None, pr, qpos, cfg)[0], None
            o, keep = _chunk_full(keys, write("x", li, pr["xrow"]), pr, qpos,
                                  cfg)
            kept.append(keep)
            return o, _selection_counts(keep, qpos, live)

        live = jnp.ones((piece,), bool)
        x, c = _run_layers(params, cfg, toks, qpos, live, attend)
        out = (_logits(params, cfg, x),)
        if cfg.select_log is not None:
            out += (jnp.packbits(jnp.stack(kept), axis=-1,
                                 bitorder="little"),)
        new = {}
        if cfg.n_mtp:
            mlive = (jnp.arange(piece) > 0) | carry["h_ok"]

            def attend_behind(kind, li, pr):
                # rows for positions qpos - 1: a scatter, the first
                # dropped where there is no hidden state before it
                T = rows[FULL].shape[2]
                rows[FULL] = rows[FULL].at[
                    li, 0, jnp.where(mlive, qpos - 1, T), 0].set(
                        pr["row"].astype(rows[FULL].dtype), mode="drop")
                return _chunk_full(rows[FULL][li, 0, :, 0], None, pr,
                                   jnp.maximum(qpos - 1, 0), cfg)[0], None

            with jax.named_scope("mtp_draft"):
                _, cm = _mtp_layer(
                    params, cfg, toks,
                    jnp.concatenate([carry["h_last"][None], x[:-1]]),
                    jnp.maximum(qpos - 1, 0), mlive, attend_behind)
            c = _merge_counts(c, cm)
            new.update(h_last=x[-1], h_ok=jnp.ones((), bool))
            out += (x,)
        new.update(k=rows[FULL], v=rows[SLIDING],
                   moe_counts=carry["moe_counts"] + c)
        if rows["x"] is not None:
            new["x"] = rows["x"]
        return new, out

    done = jnp.asarray(pos_offset, jnp.int32)
    row, (logits, *rest) = jax.lax.scan(
        run, {k: v for k, v in cache.items() if k != "hidden"},
        (tokens[0].reshape(-1, piece), done + piece * jnp.arange(P // piece)))
    if cfg.n_mtp:
        row["hidden"] = rest.pop().reshape(P, -1)
    if rest:        # [pieces, n_full, piece, T / 8] -> [n_full, P, T / 8]
        row["kept"] = jnp.moveaxis(rest[0], 0, 1).reshape(
            cfg.n_full, P, -1)
    return logits.reshape(1, P, -1), row


class LatentSlotServer(PagedSlotServer):
    """PagedSlotServer over the two latent pools: the same admission,
    block tables, prefix cache, sampler and tick interface; its own
    decode and fused programs (``jit_paged_decode``, ``jit_paged_fused``)
    and the counters of what is new (``family_stats``).

    Where the configuration carries a multi-token-prediction module
    (``cfg.n_mtp``) the server drafts with it (``drafting``): a tick is
    ``draft_tick``, a slot advances by one or two tokens, ``step``
    returns a list a slot, and ``speculative`` is set so the engine and
    ``/stats`` treat it as any drafting server (models/spec.py owns the
    acceptance arithmetic and the round's deferred half)."""

    #: what ``PagedSlotServer`` takes and this family still refuses, and
    #: why. (Drafting itself is not refused: a configuration with a
    #: multi-token-prediction module drafts with it, no flag.)
    REFUSED = {
        "kv_quant": "the cached row is the normed latent and the rotary "
                    "key, one row for all heads; no int8 row layout or "
                    "scales pool exists for it",
        "multi_lora": "the adapters' deltas are written for the dense "
                      "family's q/k/v/o projections, not the latent ones",
        "speculative_draft": "a second model as draft needs pools of its "
                             "own under the latent layout; the family "
                             "drafts only with its own multi-token-"
                             "prediction module (LatentConfig.n_mtp)",
        "mesh": "one chip's share of the experts is a configuration "
                "(experts_held, expert_offset); no code exchanges tokens "
                "between shares",
        "layers_hook": "the layers are not stacked over depth, so there "
                       "is no scan for a hook to ride",
    }

    def __init__(self, params, cfg: LatentConfig, **kw):
        for flag, why in self.REFUSED.items():
            if kw.get(flag):
                raise ValueError(
                    f"the latent family does not support {flag}: {why}")
        self.drafting = bool(cfg.n_mtp)
        if self.drafting and (cfg.n_mtp != 1 or cfg.selector
                              or "mtp" not in params):
            raise ValueError(
                "a multi-token-prediction module is served at depth 1, on "
                "a model with no key selector, from weights that hold one "
                "(params['mtp'])")
        super().__init__(params, cfg, forward_fn=paged_forward, **kw)
        # what the programs count (``bump``), on the device until
        # /stats asks
        self._counts = jnp.zeros((2, n_counts(cfg)), jnp.int32)
        self._bump = jax.jit(bump)
        self._decode_prog = jax.jit(
            _program("paged_decode", decode_tick, cfg=cfg),
            donate_argnums=(2, 3, 4))
        self._fused_prog = jax.jit(
            _program("paged_fused", fused_tick, cfg=cfg),
            static_argnames=("n_kblk",), donate_argnums=(3, 4, 5))
        self._decode = self._decode_counted
        if self.drafting:
            self._init_drafting(**{k: kw.get(k) for k in
                                   ("temperature", "top_k", "top_p")})

    def _init_drafting(self, temperature, top_k, top_p):
        cfg, B = self.cfg, self.cache.n_slots
        temperature = temperature or 0.0
        self.speculative = True
        self._spec_init(gamma=1, spec_horizon=1, temperature=temperature,
                        top_k=top_k, top_p=top_p, cap=self.slot_capacity)
        static = dict(cfg=cfg, cap=self.slot_capacity, sampling=dict(
            temperature=temperature, top_k=top_k, top_p=top_p)
            if self._spec_stochastic else None)
        self._draft_prog = jax.jit(
            _program("paged_decode", draft_tick, **static),
            donate_argnums=(5, 6, 7))
        self._draft_fused_prog = jax.jit(
            _program("paged_fused", draft_tick, **static),
            static_argnames=("n_kblk",), donate_argnums=(5, 6, 7))
        # what a slot carries from one round to the next (draft_tick);
        # during an admission, row 0 is the chunks' carry
        self._mtp_h = jnp.zeros((B, 2, cfg.d_model), cfg.dtype)
        self._mtp_tok = jnp.zeros((B, 2), jnp.int32)
        self._mtp_n = jnp.zeros((B,), jnp.int32)
        # a round writes position length + 1 too
        w = growth_width(1, self.cache.block_size)
        self._no_growth[w] = jnp.full((B, w), -1, jnp.int32)
        #: latent rows the rounds' attention had to read: host
        #: arithmetic off the lengths mirror, every cached layer's rows
        #: up to the round's last write
        self.latent_rows_read = 0
        #: paged-kernel calls the rounds' programs made: one a cached
        #: layer a round where the kernel is ``_decode_all``'s choice at
        #: this server's shapes, none where it is not
        self.latent_decode_calls = 0
        self._decode_calls_a_round = (
            cfg.n_cached_full if decode_kernel_serves(
                cfg, B, 2, self.cache.pool_k) else 0)

    # -- counters -----------------------------------------------------

    def family_stats(self) -> Dict[str, Any]:
        """What ``/stats`` adds for this family. The selector's and the
        experts' counters are counted by the programs and live on the
        device between calls (one small fetch here, none in a tick; a
        serial admission counts the padding of its last piece too); the
        rest is read off the host mirrors."""
        cfg = self.cfg
        limbs = np.asarray(self._counts).astype(np.int64)
        c = limbs[0] + (limbs[1] << 30)
        lens = self.cache.host_lengths()[
            (self.cache.host_table() >= 0).any(axis=1)].astype(np.int64)
        loads = c[4:]
        out = {}
        if self.drafting:
            out = {"mtp_rounds": self.spec_rounds,
                   "mtp_proposed": self.spec_draft_tokens,
                   "mtp_accepted": self.spec_accepted_tokens,
                   # a slot's round emits what it accepted and one more
                   "mtp_emitted": (self.spec_draft_tokens
                                   + self.spec_accepted_tokens),
                   "latent_rows_read": self.latent_rows_read,
                   "latent_decode_calls": self.latent_decode_calls}
        return {
            **out,
            "select_keys_kept": int(c[2]) if cfg.selector else None,
            "select_keys_seen": int(c[3]) if cfg.selector else None,
            # rows the slots' tables hold, a layer kind; a block shared
            # by the prefix cache counts once a slot that reads it (the
            # module's rows are full rows)
            "latent_rows_live": {
                "full": cfg.n_cached_full * int(lens.sum()),
                "sliding": cfg.n_swa * int(lens.sum())},
            # rows of sliding layers behind every window still to come
            "window_rows_dead": cfg.n_swa * int(
                np.maximum(lens - (cfg.window - 1), 0).sum()),
            "latent_row_bytes": {
                "full": ((cfg.full.key_dim
                          + (cfg.index_dim if cfg.selector else 0))
                         * self.cache.pool_k.dtype.itemsize),
                "sliding": (cfg.swa.key_dim
                            * self.cache.pool_v.dtype.itemsize)},
            "expert_assign_local": int(c[0]),
            "expert_tokens": int(c[1]),
            "expert_load": [int(v) for v in loads],
            "expert_load_max": int(loads.max()) if loads.size else 0,
        }

    # -- programs -----------------------------------------------------

    def _decode_counted(self, params, tokens, pool_k, pool_v, table,
                        lengths, active, grow=None, pool_k_scale=None,
                        pool_v_scale=None):
        logits, pk, pv, px, new_lengths, table, self._counts, *kept = (
            self._decode_prog(params, tokens, pool_k, pool_v,
                              self.cache.pool_x, table, lengths, active,
                              grow, self._counts))
        # the parent rebinds the two pools it knows; the third here
        self.cache = dataclasses.replace(self.cache, pool_x=px)
        if kept:
            self.cfg.select_log.step = (lengths, active, tokens, kept[0])
        return logits, pk, pv, None, None, new_lengths, table

    #: the width a fused chunk shorter than the server's chunk runs at:
    #: a prompt's tail is padded up to it, so a daemon builds two fused
    #: programs a step of key length and not one a power of two (a
    #: partial prefix hit leaves a tail of any length)
    FUSED_TAIL = 128
    #: weights and pools leave no room for a copy of a pool (the eager
    #: scatter asked 2.53 GiB with 2.46 free: my chip run, PR 28, c1);
    #: fifteen pending admissions' rows at 16k tokens would be 2.5 GB,
    #: and a partial prefix hit would build programs inside a window
    lean_admission = True

    def _fused_shape(self, done: int, width: int, chunk: int):
        """(the width a fused chunk runs at, the blocks of its slot it
        reads): ``FUSED_TAIL`` or the server's chunk, and a step of 256
        blocks (4,096 tokens at 16 a block): a program a step, not a
        key length."""
        width = (self.FUSED_TAIL if width <= self.FUSED_TAIL
                 else max(width, chunk))
        return width, min(self.cache.max_blocks, -(-(done + width) // (
            256 * self.cache.block_size)) * 256)

    def _fused_forward(self, slot, st, done, end, width, final, grow):
        width, n_kblk = self._fused_shape(done, width, st["chunk"])
        chunk = np.zeros((width,), np.int32)
        chunk[:end - done] = st["prompt_np"][done:end]
        # the chunk, the scalars and the growth array are host values:
        # the call uploads them, and no eager operation runs ahead of it
        nxt, first, pk, pv, px, lengths, table, self._counts = (
            self._pools_dispatch(
                self._fused_prog, self.params, self.last_token, chunk,
                self.cache.pool_k, self.cache.pool_v, self.cache.pool_x,
                self.cache.block_table, self.cache.lengths,
                self._active_dev, grow, np.int32(slot), np.int32(done),
                np.int32(end - done), self._counts, n_kblk=n_kblk))
        self.cache = dataclasses.replace(
            self.cache, pool_k=pk, pool_v=pv, pool_x=px,
            block_table=table, lengths=lengths)
        return nxt, (first if final else None)

    # -- a drafting server's tick --------------------------------------

    def _draft_dispatch(self, chunk=None, n_kblk: int = 0):
        """Launch one ``draft_tick`` (with ``chunk`` = (tokens, slot,
        done, n_valid) as host values: a fused one) and rebind what it
        hands back. Returns (the admission's first logits [1, V] or
        None, drafts [B], corrections [B, 1], accepted counts [B])."""
        with span("slot.grow"):
            grow = self._grow_active(extra=1)
            lnp = self.cache.host_lengths()[self.active]
            self.latent_rows_read += int(
                self.cfg.n_full * (lnp + 2).sum()
                + self.cfg.n_mtp * lnp.sum())
            self.latent_decode_calls += self._decode_calls_a_round
        with span("slot.launch"):
            key = (self._sampler.next_key() if self._spec_stochastic
                   else None)
            prog, kw = self._draft_prog, {}
            if chunk is not None:
                prog, kw = self._draft_fused_prog, {"n_kblk": n_kblk}
            lengths0, active0 = self.cache.lengths, self._active_dev
            (tl0, first, drafts, corr, a_b, pk, pv, px, lengths, table,
             self._counts, self.last_token, self._mtp_h, self._mtp_tok,
             self._mtp_n, *dl) = self._pools_dispatch(
                prog, self.params, self.last_token, self._mtp_h,
                self._mtp_tok, self._mtp_n, self.cache.pool_k,
                self.cache.pool_v, self.cache.pool_x,
                self.cache.block_table, self.cache.lengths,
                self._active_dev, grow, self._counts, key,
                *(chunk or ()), **kw)
            self.cache = dataclasses.replace(
                self.cache, pool_k=pk, pool_v=pv, pool_x=px,
                block_table=table, lengths=lengths)
            if dl:
                self.cfg.draft_log.step = (lengths0, active0, dl[0])
        with span("slot.sample"):
            # The logits the round's first token was taken from go
            # through the sampler as a plain tick's do: what taps the
            # sampler (a checker, a test) reads a drafting step as it
            # reads any other, and a NaN row is the sampler's to flag.
            # Acceptance itself is the program's (spec.py's cores).
            self._sampler.pick(tl0)
        return first, drafts, corr, a_b

    def _spec_step_async(self):
        """A plain tick of a drafting server: one round a slot, a list
        of one or two tokens a slot at finalize. The host lengths mirror
        advances at finalize, with the accepted counts (models/spec.py
        ``_spec_pending``)."""
        from tpushare.models.serving import PendingStep
        if not self.active.any():
            return PendingStep.done({})
        # (TS104: _grow_active's np.asarray is over host-built index
        # lists, as paged.py's own call sites note in the baseline)
        _, drafts, corr, a_b = self._draft_dispatch()  # tpushare: ignore[TS104]
        with span("slot.accept"):
            return self._spec_pending(drafts[:, None], corr, a_b)

    def _fused_tick_async(self, slot: int, max_chunk_tokens: Optional[int]):
        if not self.drafting:
            return super()._fused_tick_async(slot, max_chunk_tokens)
        from tpushare.models.serving import PendingStep, fused_chunk_span
        st = self._admissions[slot]
        if not self.active.any():       # nothing to fuse into: serial
            tok = self.admit_step(slot, max_chunk_tokens=max_chunk_tokens)
            return PendingStep.done({} if tok is None else {slot: tok})
        S, done = int(st["prompt_np"].shape[0]), st["done"]
        end, width = fused_chunk_span(done, S, st["chunk"], max_chunk_tokens,
                                      gran=self.cache.block_size)
        if width == 0:
            return self.step_async()    # budget left no chunk room
        width, n_kblk = self._fused_shape(done, width, st["chunk"])
        chunk = np.zeros((width,), np.int32)
        chunk[:end - done] = st["prompt_np"][done:end]
        first_logits, drafts, corr, a_b = self._draft_dispatch(  # tpushare: ignore[TS104]
            (chunk, np.int32(slot), np.int32(done), np.int32(end - done)),
            n_kblk)
        st["done"], st["row_stale"], st["mtp_carry"] = end, True, True
        if end < S:
            with span("slot.accept"):
                return self._spec_pending(drafts[:, None], corr, a_b)
        with span("slot.sample"):
            first = self._sampler.pick(first_logits).astype(jnp.int32)
        with span("slot.accept"):
            pend = self._spec_pending(drafts[:, None], corr, a_b,
                                      first=(slot, first))
        with span("slot.mirror"):
            # the slot joins the batch: its first token is last_token
            # and what follows the hidden state its last chunk left
            del self._admissions[slot]
            if self.prefix_cache:
                publish_prefix(self.cache, st["blocks"], st["prompt_np"],
                               keys=st["keys"])
            self.last_token = self.last_token.at[slot, 0].set(first[0])
            self._mtp_tok = self._mtp_tok.at[slot, 0].set(first[0])
            self.active[slot] = True
            self._active_dev = upload_mirror(self.active)
        return pend

    def admit_start(self, prompt, **kw):
        slot = super().admit_start(prompt, **kw)
        if self.drafting:       # no chunk has left a hidden state yet
            self._mtp_n = self._mtp_n.at[slot].set(0)
        return slot

    def admit_step(self, slot: int, max_chunk_tokens: Optional[int] = None):
        st = self._admissions[slot]
        done = st["done"]
        if self.drafting:
            if st["row_stale"]:
                # the parent would build the row; the module's carry
                # goes in with it
                with span("slot.admit.row"):
                    st["row"], st["comp_len"], _ = _admission_row(
                        self.cfg, self.cache, slot,
                        int(st["prompt_np"].shape[0]), done)
                st["row_stale"] = False
            st["row"]["h_last"] = self._mtp_h[slot, 0]
            # ``_mtp_n[slot] > 0``, from the host: a chunk left a state
            st["row"]["h_ok"] = np.bool_(st.get("mtp_carry", False))
        # the serial chunk scatters into donated pools: rebuilt if it raises
        tok = self._pools_dispatch(super().admit_step, slot,
                                   max_chunk_tokens)
        self._counts = self._bump(self._counts, st["row"]["moe_counts"])
        st["row"]["moe_counts"] = jnp.zeros_like(st["row"]["moe_counts"])
        if self.drafting:
            if st.get("mtp_carry"):
                # the chunk's first token joined the state a chunk left
                # at ``done - 1`` and wrote the module's row THERE, a
                # block behind those the chunk scatters (paged.
                # _prefill_chunk: its own blocks only): one row to the
                # pool by hand
                self.cache = dataclasses.replace(
                    self.cache, pool_k=self._pools_dispatch(
                        _write_rows_donated, self.cache.pool_k,
                        self.cfg.n_full, self.cache.block_table,
                        np.full((1,), slot, np.int32),
                        np.full((1,), done - 1, np.int32),
                        np.ones((1,), bool),
                        st["row"]["k"][self.cfg.n_full, 0, done - 1]))
            # the hidden state at the chunk's last real position: the
            # next chunk's carry, or (with the first token, which
            # follows it) what the slot's first round starts from
            h = st["row"].pop("hidden")[st["done"] - 1 - done]
            self._mtp_h = self._mtp_h.at[slot, 0].set(h)
            self._mtp_n = self._mtp_n.at[slot].set(1)
            st["mtp_carry"] = True
            if tok is not None:
                self._mtp_tok = self._mtp_tok.at[slot, 0].set(
                    self.last_token[slot, 0])
        log = self.cfg.select_log
        if log is not None:
            if not st.setdefault("logged", False):
                st["logged"], log.admission = True, []
                log.prompt = st["prompt_np"]
            log.admission.append((done, st["row"].pop("kept")))
        return tok
