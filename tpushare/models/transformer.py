"""Decoder-only transformer LM — the flagship workload family.

Covers the BASELINE.md language workloads (Gemma-2B, Llama-3-8B) with
one functional implementation: RMSNorm pre-norm blocks, rotary GQA
attention, gated MLP, optional tied embeddings. The reference system
schedules such workloads but contains no model code (SURVEY.md §2);
this is the TPU-native harness those scheduled pods run.

TPU-first design:
- Params are a pytree of stacked per-layer arrays ([L, ...]) walked
  with ``lax.scan`` — one compiled block body regardless of depth, so
  compile time is O(1) in layers and XLA pipelines the weight loads.
- All matmuls are [*, d_model] x [d_model, *] contractions in bf16 on
  the MXU with f32 accumulation handled by preferred_element_type
  inside ops; no per-head small matmuls.
- ``ParallelCtx`` makes the same forward SPMD-explicit under
  shard_map: tp shards heads/ffn columns (Megatron-style, one psum
  after each block half), sp shards the sequence and attends via ring
  attention over ICI (parallel/ring_attention.py). Without a ctx the
  code is plain single-device jax — tests run it on CPU.
- Decode keeps a static-shaped KV cache ([L, B, max_len, Hkv, Dh]) and
  a traced offset, so autoregressive steps never recompile.
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
from tpushare.parallel.ring_attention import ring_attention
from tpushare.parallel.ulysses import ulysses_attention
from tpushare.ops.attention import window_keep


def layer_windows(cfg: "TransformerConfig"):
    """Per-layer sliding-window spans [n_layers] int32 (0 = global),
    or None when the config has none. The ONE copy of the Gemma-2
    alternation rule, shared by the dense forward's scan xs and the
    pipeline's per-stage window slices."""
    if cfg.sliding_window is None:
        return None
    return jnp.asarray(
        [cfg.sliding_window if (not cfg.alternate_sliding or l % 2 == 0)
         else 0 for l in range(cfg.n_layers)], jnp.int32)


@dataclasses.dataclass(frozen=True)
class ParallelCtx:
    """Named mesh axes the forward pass is manually parallel over.

    Used when the model runs inside shard_map; None axes mean 'not
    parallel over that dimension'. ``tp`` shards attention heads and
    MLP hidden columns; ``sp`` shards the sequence — attended via ring
    attention (sp_impl="ring", default: KV rotates over ICI hops) or
    DeepSpeed-Ulysses all_to_all head re-sharding (sp_impl="a2a"; see
    parallel/ulysses.py for the trade-offs).
    """
    tp: Optional[str] = None
    sp: Optional[str] = None
    sp_impl: str = "ring"


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32_000
    d_model: int = 2048
    n_layers: int = 18
    n_heads: int = 8
    n_kv_heads: int = 1
    head_dim: int = 256
    d_ff: int = 16_384
    rope_base: float = 10_000.0
    # Llama-3 long-context rope scaling: (factor, low_freq_factor,
    # high_freq_factor, original_max_position_embeddings) or None.
    rope_scaling: Optional[Tuple[float, float, float, float]] = None
    norm_eps: float = 1e-6
    norm_offset: float = 0.0      # 1.0 = Gemma's (1+w) RMSNorm
    act: str = "silu"             # "silu" (Llama) | "gelu" (Gemma)
    tie_embeddings: bool = True
    embed_scale: bool = False     # Gemma multiplies embeddings by sqrt(d_model)
    attn_scale: Optional[float] = None  # None -> 1/sqrt(head_dim)
    sliding_window: Optional[int] = None   # local-attention span
    alternate_sliding: bool = False        # Gemma-2: every other layer local
    attn_softcap: Optional[float] = None   # cap*tanh(logits/cap) in attention
    final_softcap: Optional[float] = None  # same on the LM-head logits
    post_norms: bool = False      # Gemma-2 sandwich norms: extra RMSNorm
                                  # on each sublayer OUTPUT before the
                                  # residual add (post-attn + post-ffw)
    dtype: Any = jnp.bfloat16
    remat: bool = True            # jax.checkpoint each block when training

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    def num_params(self) -> int:
        per_layer = (2 * self.d_model
                     + self.d_model * (self.q_dim + 2 * self.kv_dim)
                     + self.q_dim * self.d_model
                     + 3 * self.d_model * self.d_ff)
        embed = self.vocab_size * self.d_model
        return (embed * (1 if self.tie_embeddings else 2)
                + self.n_layers * per_layer + self.d_model)


def gemma_2b() -> TransformerConfig:
    """Gemma-2B geometry (the BASELINE.md whole-chip workload)."""
    return TransformerConfig(
        vocab_size=256_128, d_model=2048, n_layers=18, n_heads=8,
        n_kv_heads=1, head_dim=256, d_ff=16_384, act="gelu",
        norm_offset=1.0, embed_scale=True, tie_embeddings=True)


def gemma2_2b() -> TransformerConfig:
    """Gemma-2-2B geometry: alternating local/global attention with
    logit softcaps — exercises the sliding-window + softcap paths."""
    return TransformerConfig(
        vocab_size=256_128, d_model=2304, n_layers=26, n_heads=8,
        n_kv_heads=4, head_dim=256, d_ff=9216, act="gelu",
        norm_offset=1.0, embed_scale=True, tie_embeddings=True,
        attn_scale=256 ** -0.5, sliding_window=4096,
        alternate_sliding=True, attn_softcap=50.0, final_softcap=30.0,
        post_norms=True)


def llama3_8b() -> TransformerConfig:
    """Llama-3-8B geometry (the BASELINE.md multi-chip serving workload)."""
    return TransformerConfig(
        vocab_size=128_256, d_model=4096, n_layers=32, n_heads=32,
        n_kv_heads=8, head_dim=128, d_ff=14_336, act="silu",
        rope_base=500_000.0, tie_embeddings=False)


def tiny(vocab_size: int = 512, d_model: int = 128, n_layers: int = 2,
         n_heads: int = 4, n_kv_heads: int = 2, head_dim: int = 32,
         d_ff: int = 256, **kw) -> TransformerConfig:
    """Hardware-free test geometry."""
    return TransformerConfig(
        vocab_size=vocab_size, d_model=d_model, n_layers=n_layers,
        n_heads=n_heads, n_kv_heads=n_kv_heads, head_dim=head_dim,
        d_ff=d_ff, dtype=jnp.float32, **kw)


def init_params(rng: jax.Array, cfg: TransformerConfig) -> Dict[str, Any]:
    """Truncated-normal init, stacked over layers for lax.scan."""
    k_embed, k_layers, k_unembed = jax.random.split(rng, 3)
    L, Dm, F = cfg.n_layers, cfg.d_model, cfg.d_ff

    def dense(key, shape, fan_in):
        return (jax.random.truncated_normal(key, -2, 2, shape, jnp.float32)
                / math.sqrt(fan_in)).astype(cfg.dtype)

    ks = jax.random.split(k_layers, 7)
    params = {
        "embed": dense(k_embed, (cfg.vocab_size, Dm), Dm),
        "layers": {
            "ln1": jnp.zeros((L, Dm), cfg.dtype) if cfg.norm_offset
            else jnp.ones((L, Dm), cfg.dtype),
            "ln2": jnp.zeros((L, Dm), cfg.dtype) if cfg.norm_offset
            else jnp.ones((L, Dm), cfg.dtype),
            "wq": dense(ks[0], (L, Dm, cfg.q_dim), Dm),
            "wk": dense(ks[1], (L, Dm, cfg.kv_dim), Dm),
            "wv": dense(ks[2], (L, Dm, cfg.kv_dim), Dm),
            "wo": dense(ks[3], (L, cfg.q_dim, Dm), cfg.q_dim),
            "w_gate": dense(ks[4], (L, Dm, F), Dm),
            "w_up": dense(ks[5], (L, Dm, F), Dm),
            "w_down": dense(ks[6], (L, F, Dm), F),
        },
        "final_norm": jnp.zeros((Dm,), cfg.dtype) if cfg.norm_offset
        else jnp.ones((Dm,), cfg.dtype),
    }
    if cfg.post_norms:
        norm0 = (jnp.zeros((L, Dm), cfg.dtype) if cfg.norm_offset
                 else jnp.ones((L, Dm), cfg.dtype))
        params["layers"]["ln_post_attn"] = norm0
        params["layers"]["ln_post_ffw"] = norm0
    if not cfg.tie_embeddings:
        params["unembed"] = dense(k_unembed, (Dm, cfg.vocab_size), Dm)
    return params


def param_specs(cfg: TransformerConfig, *, tp: str = "tp",
                fsdp: Optional[str] = None) -> Dict[str, Any]:
    """PartitionSpec tree matching init_params' structure.

    Megatron layout: q/kv/gate/up columns over tp, o/down rows over tp
    (so each block needs exactly one psum per half). ``fsdp``
    additionally shards the d_model (row) axis of the column-parallel
    weights and the embedding vocab axis.
    """
    specs = {
        "embed": P(fsdp, None),
        "layers": {
            "ln1": P(None, None), "ln2": P(None, None),
            "wq": P(None, fsdp, tp), "wk": P(None, fsdp, tp),
            "wv": P(None, fsdp, tp), "wo": P(None, tp, fsdp),
            "w_gate": P(None, fsdp, tp), "w_up": P(None, fsdp, tp),
            "w_down": P(None, tp, fsdp),
        },
        "final_norm": P(None),
    }
    if cfg.post_norms:
        specs["layers"]["ln_post_attn"] = P(None, None)
        specs["layers"]["ln_post_ffw"] = P(None, None)
    if not cfg.tie_embeddings:
        specs["unembed"] = P(fsdp, None)
    return specs


def init_cache(cfg: TransformerConfig, batch: int, max_len: int,
               n_kv_heads: Optional[int] = None) -> Dict[str, jnp.ndarray]:
    """Static-shaped KV cache. ``n_kv_heads`` overrides for tp-local
    caches (cfg.n_kv_heads // tp_size)."""
    hkv = cfg.n_kv_heads if n_kv_heads is None else n_kv_heads
    shape = (cfg.n_layers, batch, max_len, hkv, cfg.head_dim)
    return {"k": jnp.zeros(shape, cfg.dtype), "v": jnp.zeros(shape, cfg.dtype)}


def _act(name: str, x: jnp.ndarray) -> jnp.ndarray:
    if name == "silu":
        return jax.nn.silu(x)
    if name == "gelu":
        return jax.nn.gelu(x, approximate=True)
    raise ValueError(f"unknown activation {name!r}")


def head_proj(h: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """``h @ w`` for a projection into attention heads (q, k, v), held
    in the dot's own layout. Left free, XLA on TPU lays the product out
    head-major for its consumers (the rotary's per-head halves, the
    attention kernels' head folds) and computes it per head, which
    reads the layer's weight out of the scanned stack through a
    per-layer slice and a transposed copy of it (32 MB each for ``wq``
    at Mistral's widths), every step. The barrier is the identity on
    values; what is reordered is the activation, a few hundred KB at
    decode."""
    return jax.lax.optimization_barrier(h @ w)


def forward(params: Dict[str, Any], tokens: jnp.ndarray,
            cfg: TransformerConfig, *,
            pctx: Optional[ParallelCtx] = None,
            cache: Optional[Dict[str, jnp.ndarray]] = None,
            pos_offset=0,
            attn_impl: str = "auto",
            layers_hook=None,
            last_logit_only: bool = False,
            mlora_idx: Optional[jnp.ndarray] = None,
            mlora_scale: float = 1.0,
            ) -> Tuple[jnp.ndarray, Optional[Dict[str, jnp.ndarray]]]:
    """LM forward. tokens [B, S] -> (logits [B, S, V], updated cache).

    ``layers_hook`` (optional) maps the per-layer xs slice of
    params["layers"] to the real layer tree INSIDE the scan body,
    within the remat boundary — the seam for manual-FSDP streaming
    gather (training.py): params["layers"] holds fsdp-sharded flat
    storage and the hook all_gathers one layer at a time, so peak
    gathered-param memory is one layer, and the backward (under remat)
    re-gathers per layer, turning the hook's VJP into a per-layer
    reduce-scatter.

    Training: cache=None. Prefill/decode: pass a cache from init_cache
    and the (traced-ok) ``pos_offset`` of tokens[:, 0]; the returned
    cache has the new K/V written at [pos_offset, pos_offset+S).
    ``pos_offset`` may also be a per-sequence [B] array for ragged
    decode (continuous batching: each slot at its own length), masking
    each row by its own offset. S == 1 is the per-token decode step;
    S > 1 is the ragged multi-token form (speculative verify, the
    fused admission tick): row b's tokens land at pos_b..pos_b+S-1
    and writes past max_len are dropped, not clamped.

    Multi-LoRA serving: when params["layers"] carries the reserved
    ``_mlora`` subtree (lora.stack_adapters — leaves [L, NA, ...], so
    the layer scan slices it with everything else), ``mlora_idx`` [B]
    selects each row's adapter and the block adds the low-rank delta
    on the ACTIVATION path (x @ A_i @ B_i), never touching the shared
    weights — different rows in one batch serve different adapters.
    idx < 0 means base model (delta masked to zero).
    Under a ParallelCtx this must be called inside shard_map over the
    named axes; array args are then local shards and head counts are
    derived from the (sharded) param shapes, not cfg.
    """
    pctx = pctx or ParallelCtx()
    B, S = tokens.shape
    Dh = cfg.head_dim
    pos = jnp.asarray(pos_offset)
    ragged = pos.ndim == 1
    # Paged decode: cache carries the stacked block pools instead of
    # dense rows ({"pool_k": [L,nb,bs,Hkv*D], "pool_v", "table": [B,mb],
    # "active": [B]}). The stacks are the layer loop's CARRY: layer l
    # writes its new rows at [l, blk, off] of the stack (a scatter on a
    # loop-carried buffer, in place) and attention reads the stack at
    # layer l (pallas paged kernel on TPU; one gather of the slots'
    # blocks elsewhere) — no layer of the pool is sliced out or
    # restacked, and the pool is never materialized as one
    # [L,B,mb*bs,...] dense cache.
    paged = cache is not None and "pool_k" in cache
    # Ragged multi-token (S > 1 with per-sequence offsets) is supported
    # by BOTH cache layouts: the paged branch (speculative verify) and,
    # since the fused engine tick, the dense-row branch — row b's
    # queries sit at pos_b..pos_b+S-1, scatter with mode="drop" (a row
    # whose tail would spill past max_len drops the junk instead of
    # clamp-corrupting the last position), and a 3D kv_mask expresses
    # the per-(row, query) causality no scalar q_offset can.
    if paged and not ragged:
        raise ValueError("paged cache requires ragged decode (pos [B])")
    # Int8 KV cache (quant.init_cache_q8 / paged kv_quant pools): int8
    # rows + per-(pos, head) scales travel the scan together; rows
    # quantize on write and the bf16 view is rebuilt one layer at a
    # time before attention. Paged+kvq dispatch follows the measured
    # crossover: slots with capacity >= ~8k ctx take the int8 pallas
    # kernel, shorter ones the gathered-view fallback;
    # TPUSHARE_DECODE_KERNEL forces either way
    # (paged_decode_eligible's policy note).
    kvq = cache is not None and ("k_scale" in cache
                                 or "pool_k_scale" in cache)
    if not kvq and cache is not None and (
            cache["pool_k" if paged else "k"].dtype == jnp.int8):
        # An int8 cache without its scale leaves would silently
        # truncate real-valued KV writes to int8 garbage (the non-kvq
        # path casts into the cache dtype) — fail loud instead.
        raise ValueError(
            "int8 KV cache reached forward() without its scale leaves "
            "(k_scale/v_scale or pool_*_scale) — pass the full "
            "init_cache_q8 / kv_quant pool dict")
    pg_active = (jnp.asarray(cache["active"])
                 if paged and "active" in cache
                 else (jnp.ones((B,), bool) if paged else None))

    positions = (pos[:, None] if ragged else pos) + jnp.arange(S)[None, :]
    if pctx.sp is not None:
        positions = positions + jax.lax.axis_index(pctx.sp) * S
    positions = jnp.broadcast_to(positions, (B, S))
    cos, sin = rotary_embedding(positions, Dh, base=cfg.rope_base,
                                scaling=cfg.rope_scaling,
                                dtype=jnp.float32)

    x = params["embed"][tokens].astype(cfg.dtype)              # [B, S, Dm]
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), cfg.dtype)

    # Per-layer sliding-window spans as scan xs (0 = global) so
    # alternating local/global layers (Gemma-2) share one compiled
    # block body — the window enters the mask as a traced scalar.
    wls = layer_windows(cfg)

    def block(x, layer, lk_cache, lv_cache, lk_s, lv_s, w, l=None):
        # lk_s/lv_s: per-(pos, head) scales when kvq, else None.
        # Paged: the four cache leaves are the whole stacks and ``l``
        # the layer to write and read; dense rows: this layer's slices.
        layer = dict(layer)
        ml = layer.pop("_mlora", None)       # [NA, ...] per-layer slice
        if layers_hook is not None:
            layer = layers_hook(layer)

        def _kvq_write(wr, wr_s, k_rows, v_rows):
            """The one quantize-on-write sequence all three cache
            branches share; ``wr``/``wr_s`` carry each branch's
            scatter indexing (value leaves vs rank-reduced scale
            leaves). Returns the four updated cache slices."""
            from tpushare.models.quant import kv_quantize
            qk, sk = kv_quantize(k_rows)
            qv, sv = kv_quantize(v_rows)
            return wr(lk_cache, qk), wr(lv_cache, qv), \
                wr_s(lk_s, sk), wr_s(lv_s, sv)

        def _ml(name, inp):
            """Per-row low-rank delta inp @ A[idx] @ B[idx] (masked to
            zero for idx < 0 = base-model rows). fp32 accumulation,
            O(B*S*d*r) — negligible next to the dense matmul for
            r << d."""
            if ml is None or name not in ml or mlora_idx is None:
                return 0
            safe = jnp.maximum(mlora_idx, 0)
            A = ml[name]["a"][safe].astype(jnp.float32)   # [B, d, r]
            Bm = ml[name]["b"][safe].astype(jnp.float32)  # [B, r, o]
            t = jnp.einsum("bsd,bdr->bsr", inp.astype(jnp.float32), A)
            d = jnp.einsum("bsr,bro->bso", t, Bm) * mlora_scale
            d = jnp.where((mlora_idx >= 0)[:, None, None], d, 0.0)
            return d.astype(inp.dtype)
        h = rms_norm(x, layer["ln1"], eps=cfg.norm_eps,
                     offset=cfg.norm_offset)
        H = layer["wq"].shape[-1] // Dh                        # tp-local heads
        Hkv = layer["wk"].shape[-1] // Dh
        q = (head_proj(h, layer["wq"]) + _ml("wq", h)).reshape(
            B, S, H, Dh)
        k = (head_proj(h, layer["wk"]) + _ml("wk", h)).reshape(
            B, S, Hkv, Dh)
        v = (head_proj(h, layer["wv"]) + _ml("wv", h)).reshape(
            B, S, Hkv, Dh)
        q = apply_rotary(q, cos, sin)
        k = apply_rotary(k, cos, sin)

        def wr_pg(c, rows):
            """Write ``rows`` [..., Hkv, Dh] at [l, blk, off] of the
            stacked pool ``c`` [L, nb, bs, Hkv*Dh] — the paged
            branches' one write, in place on the loop's carry."""
            return c.at[l, blk, off].set(
                rows.reshape(*rows.shape[:-2], -1).astype(c.dtype))

        def wr_pg_scale(c, s):
            """Scales ``s`` [..., Hkv] into column [l, blk, :, off] of
            the scale stack, stored in the kernel page layout
            [L, nb, Hkv_pad, bs] (heads zero-padded). Element writes:
            a column written as a window ([l, blk, :, off]) has XLA
            transpose the whole stack there and back."""
            hp = c.shape[2]
            sp = jnp.zeros((*s.shape[:-1], hp), jnp.float32
                           ).at[..., :Hkv].set(s)
            return c.at[l, blk[..., None], jnp.arange(hp),
                        off[..., None]].set(sp)

        def slot_views(table, trash):
            """The gathered fallback's K and V [B, mb*bs, Hkv, Dh]:
            every slot's blocks of layer ``l`` in ONE gather off the
            stack (unallocated entries read the trash block, which
            the position mask hides)."""
            safe = jnp.where(table >= 0, table, trash)
            views = []
            for c, c_s in ((lk_cache, lk_s), (lv_cache, lv_s)):
                g = c[l, safe].reshape(B, -1, Hkv, Dh)
                if kvq:
                    from tpushare.models.quant import (
                        kv_dequantize, pool_scales_to_rows)
                    rows_s = pool_scales_to_rows(c_s[l, safe], Hkv)
                    g = kv_dequantize(g, rows_s.reshape(B, -1, Hkv),
                                      cfg.dtype)
                views.append(g)
            return views

        if paged and S > 1:
            # Multi-token ragged paged step (speculative verify: the
            # target scores a gamma+1 candidate block per slot in ONE
            # forward). Scatter token j of slot b at position
            # pos[b]+j (inactive slots to the trash block), attend via
            # the gathered view with a per-(row, query) causal mask —
            # no scalar q_offset can express ragged multi-token
            # causality, hence the 3D kv_mask. No pallas path: Sq>1
            # verify is compute-shaped, XLA handles it.
            bs_pg = lk_cache.shape[2]
            mb = cache["table"].shape[1]
            trash = lk_cache.shape[1] - 1
            table = cache["table"]
            pos_grid = pos[:, None] + jnp.arange(S)[None, :]   # [B, S]
            bi = jnp.minimum(pos_grid // bs_pg, mb - 1)
            entry = jnp.take_along_axis(table, bi, 1)          # [B, S]
            # pos >= capacity would CLAMP into the last real block and
            # overwrite live KV (a speculative round near capacity
            # writes up to gamma past the end) — route to trash.
            blk = jnp.where(pg_active[:, None] & (entry >= 0)
                            & (pos_grid < mb * bs_pg), entry, trash)
            off = pos_grid % bs_pg
            if kvq:
                lk_cache, lv_cache, lk_s, lv_s = _kvq_write(
                    wr_pg, wr_pg_scale, k, v)
            else:
                lk_cache = wr_pg(lk_cache, k)
                lv_cache = wr_pg(lv_cache, v)
            from tpushare.ops.flash_attention import (
                paged_flash_verify, paged_verify_eligible)
            if (attn_impl != "reference"
                    and paged_verify_eligible(q, lk_cache,
                                              quantized=kvq,
                                              max_ctx=mb * bs_pg,
                                              stacked=True)):
                # Pages stream from HBM once per slot per round; the
                # fallback below re-materializes the whole slot view
                # per layer (paged_verify_eligible policy note).
                attn = paged_flash_verify(
                    q, lk_cache, lv_cache, table, pos, layer=l,
                    scale=cfg.attn_scale, window=w,
                    attn_softcap=cfg.attn_softcap,
                    **({"k_scale": lk_s, "v_scale": lv_s} if kvq
                       else {}))
            else:
                kd, vd = slot_views(table, trash)
                k_pos = jnp.arange(mb * bs_pg)
                kv_mask3 = k_pos[None, None, :] <= pos_grid[..., None]
                if w is not None:
                    kv_mask3 &= window_keep(pos_grid[..., None],
                                            k_pos[None, None, :], w)
                attn = attention(q, kd, vd, causal=False,
                                 kv_mask=kv_mask3,
                                 scale=cfg.attn_scale,
                                 attn_softcap=cfg.attn_softcap,
                                 impl=attn_impl)
        elif paged:
            # Paged ragged decode: scatter the new KV into each active
            # slot's current block (inactive slots write to the trash
            # block — their table entries may name live blocks another
            # step must not clobber), then attend through the table.
            bs_pg = lk_cache.shape[2]
            mb = cache["table"].shape[1]
            trash = lk_cache.shape[1] - 1
            table = cache["table"]
            bi = jnp.minimum(pos // bs_pg, mb - 1)
            entry = jnp.take_along_axis(table, bi[:, None], 1)[:, 0]
            # Same out-of-range guard as the multi-token branch: a
            # speculative draft step at base+j can run past capacity.
            blk = jnp.where(pg_active & (entry >= 0)
                            & (pos < mb * bs_pg), entry, trash)
            off = pos % bs_pg
            if kvq:
                lk_cache, lv_cache, lk_s, lv_s = _kvq_write(
                    wr_pg, wr_pg_scale, k[:, 0], v[:, 0])
            else:
                lk_cache = wr_pg(lk_cache, k[:, 0])
                lv_cache = wr_pg(lv_cache, v[:, 0])
            from tpushare.ops.flash_attention import (
                paged_decode_eligible, paged_flash_decode)
            if (attn_impl != "reference"
                    and paged_decode_eligible(q, lk_cache,
                                              quantized=kvq,
                                              max_ctx=mb * bs_pg,
                                              stacked=True)):
                # Int8 pools take the same kernel with scale pages
                # (in-kernel dequant after the DMA) when the slot
                # capacity clears the measured crossover (~8k ctx);
                # shorter contexts take the gathered fallback below
                # (paged_decode_eligible policy note).
                attn = paged_flash_decode(
                    q, lk_cache, lv_cache, table, pos, layer=l,
                    scale=cfg.attn_scale, window=w,
                    attn_softcap=cfg.attn_softcap,
                    **({"k_scale": lk_s, "v_scale": lv_s} if kvq
                       else {}))
            else:
                kd, vd = slot_views(table, trash)
                kv_mask = jnp.arange(mb * bs_pg)[None, :] <= pos[:, None]
                if w is not None:
                    kv_mask &= window_keep(
                        pos[:, None], jnp.arange(mb * bs_pg)[None, :], w)
                attn = attention(q, kd, vd, causal=False,
                                 kv_mask=kv_mask, scale=cfg.attn_scale,
                                 attn_softcap=cfg.attn_softcap,
                                 impl=attn_impl)
        elif cache is not None and ragged and S > 1:
            # Ragged multi-token over dense rows (the fused engine
            # tick: decode rows contribute 1 real token each at
            # column 0, the admitting row up to `chunk` tokens — one
            # forward, one weight stream). Token j of row b scatters
            # at pos_b+j; writes past max_len (decode rows' junk
            # columns near capacity) must vanish, so the scatter
            # spells mode="drop" explicitly — jax scatter updates
            # drop out-of-bounds by default, but dynamic_update_slice
            # (the scalar-offset branch) CLAMPS, and this contract
            # must not silently depend on which one a refactor picks
            # (pinned by tests/test_transformer.py). Attention takes
            # the 3D per-(row, query) mask — same contract as the
            # paged verify branch; no pallas path (compute-shaped,
            # XLA handles it).
            if kvq:
                from tpushare.models.quant import kv_dequantize
                wr = lambda c, x: c.at[
                    jnp.arange(B)[:, None], positions].set(x, mode="drop")
                lk_cache, lv_cache, lk_s, lv_s = _kvq_write(wr, wr, k, v)
                kd = kv_dequantize(lk_cache, lk_s, cfg.dtype)
                vd = kv_dequantize(lv_cache, lv_s, cfg.dtype)
            else:
                lk_cache = lk_cache.at[
                    jnp.arange(B)[:, None], positions].set(
                    k.astype(lk_cache.dtype), mode="drop")
                lv_cache = lv_cache.at[
                    jnp.arange(B)[:, None], positions].set(
                    v.astype(lv_cache.dtype), mode="drop")
                kd, vd = lk_cache, lv_cache
            M = kd.shape[1]
            k_pos = jnp.arange(M)
            kv_mask3 = k_pos[None, None, :] <= positions[..., None]
            if w is not None:
                kv_mask3 &= window_keep(positions[..., None],
                                        k_pos[None, None, :], w)
            attn = attention(q, kd, vd, causal=False,
                             kv_mask=kv_mask3, scale=cfg.attn_scale,
                             attn_softcap=cfg.attn_softcap,
                             impl=attn_impl)
        elif cache is not None and ragged:
            # Continuous-batching decode: each sequence writes its one
            # new KV at its own length and attends positions <= it.
            if kvq:
                from tpushare.models.quant import kv_dequantize
                wr = lambda c, x: c.at[jnp.arange(B), pos].set(x)
                lk_cache, lv_cache, lk_s, lv_s = _kvq_write(
                    wr, wr, k[:, 0], v[:, 0])
                kd = kv_dequantize(lk_cache, lk_s, cfg.dtype)
                vd = kv_dequantize(lv_cache, lv_s, cfg.dtype)
            else:
                lk_cache = lk_cache.at[jnp.arange(B), pos].set(
                    k[:, 0].astype(lk_cache.dtype))
                lv_cache = lv_cache.at[jnp.arange(B), pos].set(
                    v[:, 0].astype(lv_cache.dtype))
                kd, vd = lk_cache, lv_cache
            from tpushare.ops.flash_attention import (decode_eligible,
                                                      flash_decode)
            if attn_impl != "reference" and decode_eligible(q, kd):
                # Pallas decode kernel: streams each cache tile from
                # HBM once per kv head, ragged lengths in SMEM.
                attn = flash_decode(q, kd, vd, pos,
                                    scale=cfg.attn_scale, window=w,
                                    attn_softcap=cfg.attn_softcap)
            else:
                M = kd.shape[1]
                kv_mask = jnp.arange(M)[None, :] <= pos[:, None]  # [B, M]
                if w is not None:
                    kv_mask &= window_keep(pos[:, None],
                                           jnp.arange(M)[None, :], w)
                attn = attention(q, kd, vd, causal=False,
                                 kv_mask=kv_mask, scale=cfg.attn_scale,
                                 attn_softcap=cfg.attn_softcap,
                                 impl=attn_impl)
        elif cache is not None:
            # Write the new kv at pos_offset; attend over the full
            # static cache (future slots are zeros, masked out by the
            # causal q_offset mask since their k_pos > q_pos).
            if kvq:
                from tpushare.models.quant import kv_dequantize
                lk_cache, lv_cache, lk_s, lv_s = _kvq_write(
                    lambda c, x: jax.lax.dynamic_update_slice(
                        c, x, (0, pos_offset, 0, 0)),
                    lambda c, x: jax.lax.dynamic_update_slice(
                        c, x, (0, pos_offset, 0)),
                    k, v)
                kd = kv_dequantize(lk_cache, lk_s, cfg.dtype)
                vd = kv_dequantize(lv_cache, lv_s, cfg.dtype)
            else:
                lk_cache = jax.lax.dynamic_update_slice(
                    lk_cache, k.astype(lk_cache.dtype),
                    (0, pos_offset, 0, 0))
                lv_cache = jax.lax.dynamic_update_slice(
                    lv_cache, v.astype(lv_cache.dtype),
                    (0, pos_offset, 0, 0))
                kd, vd = lk_cache, lv_cache
            attn = attention(q, kd, vd, causal=True,
                             q_offset=pos_offset, scale=cfg.attn_scale,
                             window=w, attn_softcap=cfg.attn_softcap,
                             impl=attn_impl)
        elif pctx.sp is not None:
            if pctx.sp_impl not in ("ring", "a2a"):
                raise ValueError(
                    f"unknown sp_impl {pctx.sp_impl!r}; 'ring' or 'a2a'")
            sp_attn = (ulysses_attention if pctx.sp_impl == "a2a"
                       else ring_attention)
            attn = sp_attn(q, k, v, axis_name=pctx.sp,
                           causal=True, scale=cfg.attn_scale,
                           window=w, attn_softcap=cfg.attn_softcap,
                           impl=attn_impl)
        else:
            attn = attention(q, k, v, causal=True, scale=cfg.attn_scale,
                             window=w, attn_softcap=cfg.attn_softcap,
                             impl=attn_impl)

        attn_flat = attn.reshape(B, S, H * Dh)
        o = attn_flat @ layer["wo"] + _ml("wo", attn_flat)     # [B, S, Dm]
        if pctx.tp is not None:
            o = jax.lax.psum(o, pctx.tp)
        if cfg.post_norms:
            o = rms_norm(o, layer["ln_post_attn"], eps=cfg.norm_eps,
                         offset=cfg.norm_offset)
        x = x + o

        h = rms_norm(x, layer["ln2"], eps=cfg.norm_eps,
                     offset=cfg.norm_offset)
        ff = (_act(cfg.act, h @ layer["w_gate"] + _ml("w_gate", h))
              * (h @ layer["w_up"] + _ml("w_up", h)))
        ff = ff @ layer["w_down"] + _ml("w_down", ff)
        if pctx.tp is not None:
            ff = jax.lax.psum(ff, pctx.tp)
        if cfg.post_norms:
            ff = rms_norm(ff, layer["ln_post_ffw"], eps=cfg.norm_eps,
                          offset=cfg.norm_offset)
        return x + ff, lk_cache, lv_cache, lk_s, lv_s

    if cfg.remat and cache is None:
        block = jax.checkpoint(block)

    if cache is None:
        def body(x, xs):
            layer, w = xs
            x, _, _, _, _ = block(x, layer, None, None, None, None, w)
            return x, None
        x, _ = jax.lax.scan(body, x, (params["layers"], wls))
        new_cache = None
    elif paged:
        # The stacked pools (and their scale stacks) are the CARRY, the
        # layer index rides xs with the weights: each layer's scatter
        # updates the carried buffer in place, and with the pools
        # donated into the jitted step (paged.PagedSlotServer) the
        # returned stacks are the argument buffers themselves. As scan
        # xs/ys a layer is sliced out, updated as a copy and restacked:
        # pool-sized copies every step, whatever is live.
        def body(carry, xs):
            layer, w, l = xs
            return block(carry[0], layer, *carry[1:], w, l), None
        (x, pk, pv, pks, pvs), _ = jax.lax.scan(
            body, (x, cache["pool_k"], cache["pool_v"],
                   cache.get("pool_k_scale"), cache.get("pool_v_scale")),
            (params["layers"], wls, jnp.arange(cfg.n_layers)))
        new_cache = dict(cache, pool_k=pk, pool_v=pv)
        if kvq:
            new_cache.update(pool_k_scale=pks, pool_v_scale=pvs)
    elif kvq:
        def body(x, xs):
            layer, lk, lv, lks, lvs, w = xs
            x, lk, lv, lks, lvs = block(x, layer, lk, lv, lks, lvs, w)
            return x, (lk, lv, lks, lvs)
        x, (ck, cv, cks, cvs) = jax.lax.scan(
            body, x, (params["layers"], cache["k"], cache["v"],
                      cache["k_scale"], cache["v_scale"], wls))
        new_cache = {"k": ck, "v": cv, "k_scale": cks, "v_scale": cvs}
    else:
        def body(x, xs):
            layer, lk, lv, w = xs
            x, lk, lv, _, _ = block(x, layer, lk, lv, None, None, w)
            return x, (lk, lv)
        x, (ck, cv) = jax.lax.scan(
            body, x, (params["layers"], cache["k"], cache["v"], wls))
        new_cache = {"k": ck, "v": cv}

    if last_logit_only:
        # Prefill only needs the last position's logits: slicing before
        # the vocab projection avoids materializing [B, S, V] (for
        # Gemma-2B at S=2048 that is GiBs of activation) and its share
        # of the LM-head FLOPs. The returned logits are [B, 1, V].
        x = x[:, -1:]
    x = rms_norm(x, params["final_norm"], eps=cfg.norm_eps,
                 offset=cfg.norm_offset)
    unembed = (params["embed"].T if cfg.tie_embeddings
               else params["unembed"]).astype(cfg.dtype)
    logits = (x @ unembed).astype(jnp.float32)                 # [B, S, V]
    if cfg.final_softcap is not None:
        logits = cfg.final_softcap * jnp.tanh(logits / cfg.final_softcap)
    return logits, new_cache


def prefill(params, tokens, cfg, *, max_len: int,
            attn_impl: str = "auto"):
    """Run the prompt through the model, returning (logits, cache)."""
    cache = init_cache(cfg, tokens.shape[0], max_len)
    return forward(params, tokens, cfg, cache=cache, pos_offset=0,
                   attn_impl=attn_impl)


@functools.lru_cache(maxsize=None)
def _chunk_prefill_fwd(cfg: "TransformerConfig", attn_impl: str,
                       last_logit_only: bool):
    """One jitted forward per (cfg, attn_impl, last_logit_only),
    shared by every chunked_prefill call: pos_offset is a traced
    scalar, so all equal-shape chunks hit ONE compiled executable
    (the at-most-one ragged tail compiles separately)."""
    return jax.jit(functools.partial(forward, cfg=cfg,
                                     attn_impl=attn_impl,
                                     last_logit_only=last_logit_only))


def _chunked_prefill_loop(fwd_light, fwd_full, params, tokens, cache,
                          chunk: int, last_pos: int):
    """THE chunked-prefill loop (one copy): run ``tokens`` [B, S]
    through fixed ``chunk`` slices, returning (logit row at ``last_pos`` [B, V], cache).

    Only the piece CONTAINING ``last_pos`` runs ``fwd_full`` (full
    per-position logits, [B, chunk, V] once); every other piece runs
    ``fwd_light`` (last_logit_only — one vocab row), so the LM-head
    cost stays O(chunk·V + n_chunks·V) instead of O(S·V) and no
    full-chunk logits buffer exists outside that one piece."""
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    out = None
    for i in range(0, tokens.shape[1], chunk):
        piece = tokens[:, i:i + chunk]
        if i <= last_pos < i + piece.shape[1]:
            logits, cache = fwd_full(params, piece, cache=cache,
                                     pos_offset=jnp.int32(i))
            out = logits[:, last_pos - i]
        else:
            _, cache = fwd_light(params, piece, cache=cache,
                                 pos_offset=jnp.int32(i))
    return out, cache


def chunked_prefill(params, tokens, cfg, *, max_len: int,
                    chunk: int = 2048, attn_impl: str = "auto"):
    """Prefill a long prompt in fixed-size chunks: (last logits, cache).

    The long-context serving path: peak attention-score footprint is
    O(chunk·max_len) instead of the one-shot prefill's O(S·max_len) —
    activations scale with the chunk, not the prompt. Total FLOPs stay
    comparable (each chunk's flash k-loop still cuts at its causal
    frontier, so the summed work is the same ~S²/2 the one-shot pass
    does). Each equal-size chunk reuses one jitted forward
    (_chunk_prefill_fwd: pos_offset is traced). Numerics are exactly
    the one-shot prefill's — same cache writes, same masked attention —
    tested equal in tests/test_serving.py. Returns logits [B, 1, V]
    (the last prompt position's row, the decode seed).
    """
    B, S = tokens.shape
    if S == 0:
        raise ValueError("cannot prefill an empty prompt")
    last, cache = _chunked_prefill_loop(
        _chunk_prefill_fwd(cfg, attn_impl, True),
        _chunk_prefill_fwd(cfg, attn_impl, False),
        params, tokens, init_cache(cfg, B, max_len), chunk, S - 1)
    return last[:, None], cache


def decode_step(params, token, cfg, cache, offset, *,
                attn_impl: str = "auto"):
    """One autoregressive step: token [B, 1] at position ``offset``
    (traced scalar — no recompile per step)."""
    return forward(params, token, cfg, cache=cache, pos_offset=offset,
                   attn_impl=attn_impl)
