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
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tpushare.models.paged import PagedSlotServer, _program, apply_growth
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

    def pool_shapes(self, n_blocks: int, block_size: int):
        """(pool_k, pool_v, pool_x) shapes: paged.init_paged_cache's
        hook."""
        return ((self.n_full, n_blocks, block_size, self.full.key_dim),
                (self.n_swa, n_blocks, block_size, self.swa.key_dim),
                (self.n_full, n_blocks, block_size, self.index_dim))

    def init_row_cache(self, batch: int, max_len: int):
        """The dense row cache of one admission (paged._admission_row's
        hook): the two pools' rows, contiguous, with the expert counters
        the serial prefill adds to (LatentSlotServer.admit_step)."""
        return {"k": jnp.zeros((self.n_full, batch, max_len, 1,
                                self.full.key_dim), self.dtype),
                "v": jnp.zeros((self.n_swa, batch, max_len, 1,
                                self.swa.key_dim), self.dtype),
                "x": jnp.zeros((self.n_full, batch, max_len, 1,
                                self.index_dim), self.dtype),
                "moe_counts": jnp.zeros((n_counts(self),), jnp.int32)}


def n_counts(cfg: LatentConfig) -> int:
    """[assignments to held experts, tokens routed, keys the selectors
    kept, keys they saw, load of each (sparse layer, held expert)...]."""
    return 4 + cfg.n_moe * cfg.experts_held


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
    keys = iter(jax.random.split(rng, 32 * cfg.n_layers + 8))

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
             "w_vb": dense((H, a.kv_rank, a.v_dim), a.kv_rank),
             "w_g": dense((Dm, H), Dm),
             "w_o": dense((H * a.v_dim, Dm), H * a.v_dim)}
        if selector:
            IH, ID = cfg.index_heads, cfg.index_dim
            w.update({"w_iq": dense((a.q_rank, IH * ID), a.q_rank),
                      "w_ik": dense((Dm, ID), Dm),
                      "ik_norm_w": jnp.ones((ID,), cfg.dtype),
                      "ik_norm_b": jnp.zeros((ID,), cfg.dtype),
                      "w_iw": dense((Dm, IH), Dm)})
        return w

    Eh, Fe, Fs = cfg.experts_held, cfg.d_expert, cfg.n_shared * cfg.d_expert
    layers = []
    for i, kind in enumerate(cfg.layer_types):
        w = attn(cfg.full if kind == FULL else cfg.swa, kind == FULL)
        w["ln2"] = jnp.ones((Dm,), cfg.dtype)
        if i < cfg.n_dense:
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
        layers.append(w)
    return {"embed": dense((cfg.vocab_size, Dm), Dm),
            "unembed": dense((Dm, cfg.vocab_size), Dm),
            "final_norm": jnp.ones((Dm,), cfg.dtype),
            "layers": layers}


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
               [ckv, kr, jnp.zeros((N, pad), kv.dtype)], axis=-1),
           "gate": jax.nn.sigmoid((h @ w["w_g"]).astype(_F32))}
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
    if T > cfg.index_topk:
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
    _, top_i = jax.lax.top_k(s + bias.astype(_F32), cfg.top_k)
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
    top_i, top_w = route(h, w["router"], w["router_bias"], cfg)
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


def _run_layers(params, cfg: LatentConfig, tokens, pos, live, attend):
    """The residual stream of flat ``tokens`` [N] at ``pos`` [N] through
    every layer. ``attend(kind, index in kind, projections) -> (latent
    output [N, H, C], [keys kept, keys seen] on a full layer else None)``
    owns the cache. Returns (final hidden [N, Dm], counts [n_counts])."""
    x = params["embed"][tokens].astype(cfg.dtype)
    N = x.shape[0]
    totals = jnp.zeros((4,), jnp.int32)
    loads = []
    n_of = {FULL: 0, SLIDING: 0}
    for i, (kind, w) in enumerate(zip(cfg.layer_types, params["layers"])):
        a = cfg.full if kind == FULL else cfg.swa
        li = n_of[kind]
        n_of[kind] += 1
        h = _rms(x, w["ln1"], cfg.norm_eps)
        pr = _project(h, pos, w, a, cfg, selector=kind == FULL)
        o, sel = attend(kind, li, pr)
        if sel is not None:
            totals = totals.at[2:].add(sel)
        o = jnp.einsum("nhc,hcv->nhv", o, w["w_vb"])
        o = (o * pr["gate"][..., None].astype(o.dtype)).reshape(N, -1)
        x = x + o @ w["w_o"]
        h = _rms(x, w["ln2"], cfg.norm_eps)
        if i < cfg.n_dense:
            x = x + _swiglu(h, w["w_gate"], w["w_up"], w["w_down"])
        else:
            y, c = moe_ffn(h, w, cfg, live)
            x = x + y
            totals = totals.at[:2].add(c[:2])
            loads.append(c[2:])
    return x, jnp.concatenate([totals, *loads])


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


class _Paged:
    """The attention of a tick over the paged pools: B decode rows, and
    (in a fused tick) one admission chunk of P rows behind them."""

    def __init__(self, cfg, pool_k, pool_v, pool_x, table, lengths, active,
                 chunk=None):
        self.cfg, self.table = cfg, table
        self.pools = {FULL: pool_k, SLIDING: pool_v, "x": pool_x}
        self.pos, self.active = lengths, active
        self.tb = jnp.maximum(table, 0)
        self.chunk = chunk          # (slot, positions [P], live [P], n_kblk)
        self.kept = []              # a full layer: what its decode rows kept

    def _write(self, name, li, B, rows):
        pool = _write_rows(self.pools[name], li, self.table, jnp.arange(B),
                           self.pos, self.active, rows[:B])
        if self.chunk is not None:
            slot, cpos, clive, _ = self.chunk
            pool = _write_rows(pool, li, self.table, slot, cpos, clive,
                               rows[B:])
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
        cfg, B = self.cfg, self.pos.shape[0]
        dec = {k: v[:B] for k, v in pr.items()}
        pool = self._write(kind, li, B, pr["row"])
        sel = None
        if kind == FULL:
            xpool = self._write("x", li, B, pr["xrow"])
            o, kept = _decode_full(pool, xpool, li, self.tb, self.pos, dec,
                                   cfg)
            self.kept.append(kept)
            sel = _selection_counts(kept, self.pos, self.active)
        else:
            o = _decode_swa(pool, li, self.tb, self.pos, dec, cfg)
        if self.chunk is None:
            return o, sel
        chunk = {k: v[B:] for k, v in pr.items()}
        keys, (_, cpos, clive, _) = self._slot_rows(pool, li), self.chunk
        if kind == FULL:
            oc, keep = _chunk_full(keys, self._slot_rows(xpool, li), chunk,
                                   cpos, cfg)
            sel = sel + _selection_counts(keep, cpos, clive)
        else:
            oc = _chunk_swa(keys, chunk, cpos, cfg)
        return jnp.concatenate([o, oc]), sel


def decode_tick(params, tokens, pool_k, pool_v, pool_x, table, lengths,
                active, grow, counts, *, cfg: LatentConfig):
    """One token a slot, over the table with this tick's new blocks
    written in (``paged.apply_growth``; ``grow`` None: none). tokens
    [B, 1]. Returns (logits [B, 1, V], pool_k, pool_v, pool_x, lengths
    advanced for active slots, the table, counts), and under a
    ``select_log`` the key positions each full layer kept
    [n_full, B, K]."""
    table = apply_growth(table, lengths, grow, pool_k.shape[2])
    att = _Paged(cfg, pool_k, pool_v, pool_x, table, lengths, active)
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
    att = _Paged(cfg, pool_k, pool_v, pool_x, table, lengths, active,
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


def paged_forward(params, tokens, cfg: LatentConfig, *, cache=None,
                  pos_offset=0, attn_impl: str = "auto", layers_hook=None,
                  mlora_idx=None, mlora_scale: float = 1.0):
    """transformer.forward's shape for the cache PagedSlotServer's serial
    prefill hands a family: an admission's dense row, tokens [1, P] at
    pos_offset... Returns (logits [1, P, V], the row)."""
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
        rows = dict(zip((FULL, SLIDING, "x"), carry[:3]))

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
        return (rows[FULL], rows[SLIDING], rows["x"], carry[3] + c), out

    done = jnp.asarray(pos_offset, jnp.int32)
    (k, v, x, counts), (logits, *kept) = jax.lax.scan(
        run, (cache["k"], cache["v"], cache["x"], cache["moe_counts"]),
        (tokens[0].reshape(-1, piece), done + piece * jnp.arange(P // piece)))
    row = {"k": k, "v": v, "x": x, "moe_counts": counts}
    if kept:        # [pieces, n_full, piece, T / 8] -> [n_full, P, T / 8]
        row["kept"] = jnp.moveaxis(kept[0], 0, 1).reshape(
            cfg.n_full, P, -1)
    return logits.reshape(1, P, -1), row


class LatentSlotServer(PagedSlotServer):
    """PagedSlotServer over the two latent pools: the same admission,
    block tables, prefix cache, sampler and tick interface; its own
    decode and fused programs (``jit_paged_decode``, ``jit_paged_fused``)
    and the counters of what is new (``family_stats``)."""

    def __init__(self, params, cfg: LatentConfig, **kw):
        for flag in ("kv_quant", "multi_lora", "speculative_draft", "mesh",
                     "layers_hook"):
            if kw.get(flag):
                raise ValueError(f"the latent family does not support {flag}")
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
        return {
            "select_keys_kept": int(c[2]),
            "select_keys_seen": int(c[3]),
            # rows the slots' tables hold, a layer kind; a block shared
            # by the prefix cache counts once a slot that reads it
            "latent_rows_live": {"full": cfg.n_full * int(lens.sum()),
                                 "sliding": cfg.n_swa * int(lens.sum())},
            # rows of sliding layers behind every window still to come
            "window_rows_dead": cfg.n_swa * int(
                np.maximum(lens - (cfg.window - 1), 0).sum()),
            "latent_row_bytes": {
                "full": ((cfg.full.key_dim + cfg.index_dim)
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

    def _fused_forward(self, slot, st, done, end, width, final, grow):
        bs = self.cache.block_size
        width = (self.FUSED_TAIL if width <= self.FUSED_TAIL
                 else max(width, st["chunk"]))
        chunk = np.zeros((width,), np.int32)
        chunk[:end - done] = st["prompt_np"][done:end]
        # the chunk reads its slot's blocks in steps of 256 (4,096
        # tokens at 16 a block): a program a step, not a key length
        n_kblk = min(self.cache.max_blocks,
                     -(-(done + width) // (256 * bs)) * 256)
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

    def admit_step(self, slot: int, max_chunk_tokens: Optional[int] = None):
        st = self._admissions[slot]
        done = st["done"]
        # the serial chunk scatters into donated pools: rebuilt if it raises
        tok = self._pools_dispatch(super().admit_step, slot,
                                   max_chunk_tokens)
        self._counts = self._bump(self._counts, st["row"]["moe_counts"])
        st["row"]["moe_counts"] = jnp.zeros_like(st["row"]["moe_counts"])
        log = self.cfg.select_log
        if log is not None:
            if not st.setdefault("logged", False):
                st["logged"], log.admission = True, []
                log.prompt = st["prompt_np"]
            log.admission.append((done, st["row"].pop("kept")))
        return tok
