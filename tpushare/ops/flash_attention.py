"""Pallas TPU flash attention (causal, online-softmax).

The FLOPs of every BASELINE.md language workload live in attention +
matmuls; matmuls map straight onto the MXU, and this kernel keeps
attention from ever materializing the [Sq, Sk] score matrix in HBM —
scores live in VMEM one (block_q, block_k) tile at a time with the
classic running-max/running-sum rescaling.

Design notes (tpu-first, per /opt/skills/guides/pallas_guide.md):
- grid = (B*H, q_blocks); the head axis is folded into the grid because
  Mosaic requires the trailing two *block* dims to be tile-aligned.
- K/V for one (batch, kv_head) stay resident in VMEM across the whole
  q-block pass; the GQA q-head -> kv-head mapping happens in the
  BlockSpec index_map, so grouped kv is never broadcast in HBM. VMEM
  residency bounds eligible Sk (see MAX_RESIDENT_KV_BYTES); longer
  sequences belong to ring attention across chips (ops/ring_attention).
- q_offset arrives as a traced SMEM scalar, so chunked prefill / cache
  continuation does NOT recompile per offset.
- The k-loop trip count is cut at the causal frontier, so the kernel
  does ~half the work of a masked dense pass at long Sq.
- All accumulation in f32; inputs/outputs bf16-safe.

Hardware-free testing: pass ``interpret=True`` (used by tests/ on the
CPU mesh); ``flash_eligible`` gates the auto-dispatch to real TPU
backends and tile-friendly shapes.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpushare.ops.attention import NEG_INF, mha_reference, window_keep

DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 256
# K+V resident per grid step. Pallas double-buffers both blocks, so
# twice this, plus the q and output blocks and the f32 accumulators,
# must fit the v5e's 16 MiB of scoped VMEM. Measured on the chip (PR
# 21): at 8 MiB Mosaic refuses ("Scoped allocation with size 16.40M and
# limit 16.00M exceeded scoped vmem limit") at D=256/Sk=8192 and at
# D=128/Sk=16384; at 6 MiB (D=256/Sk=6144) and 4 MiB the kernel
# compiles and agrees with the reference. Beyond it K/V stream through
# the grid (_flash_streaming), which has no such bound.
MAX_RESIDENT_KV_BYTES = 6 * 1024 * 1024


def _sds(shape, dtype, *refs):
    """ShapeDtypeStruct whose vma (varying manual axes) is the union of
    the refs' — required for pallas_call under vma-checked shard_map
    (ring attention runs this kernel inside the sp shard_map)."""
    vma = set()
    for r in refs:
        vma |= set(jax.typeof(r).vma)
    return jax.ShapeDtypeStruct(shape, dtype, vma=frozenset(vma))


def _snap_block(block: int, size: int) -> int:
    """Largest power-of-two-ish block <= ``block`` dividing ``size``."""
    block = min(block, size)
    while size % block:
        block //= 2
    return max(block, 1)


def flash_eligible(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                   kv_mask=None) -> bool:
    """Auto-dispatch predicate: real TPU backend + tile-friendly shapes.

    Sk beyond VMEM residency streams K/V blocks through the grid (no
    upper bound). Decode steps (Sq==1) go to flash_decode via the
    model's ragged branch; masked-cache reads (kv_mask) go to the XLA
    reference path.
    """
    if jax.default_backend() != "tpu":
        return False
    if kv_mask is not None:
        return False
    B, Sq, H, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    if D not in (128, 256):
        return False
    if Sq < 128 or Sq % 128 or Sk % 128:
        return False
    return H % Hkv == 0



def _kv_live_range(p, w, blk: int, n_blocks: int):
    """(lo, hi) block range a row at position ``p`` may attend, for a
    block size ``blk`` and traced sliding window ``w`` (<=0 = global).
    Shared by every DMA-skip index_map (streaming, decode, paged) so
    the boundary rounding lives in exactly one place."""
    w_eff = jnp.where(w > 0, w, jnp.int32(2 ** 30))
    hi = jnp.clip(p // blk + 1, 1, n_blocks)              # exclusive top
    lo = jnp.clip((p - w_eff + 1) // blk, 0, hi - 1)
    return lo, hi


def _live_groups(p, w, bs: int, n_pages: int, group: int):
    """(first page, end page, first group, end group) of a slot at
    position ``p``: its live pages [lo, hi) of ``bs`` rows and the
    groups of ``group`` pages that hold one. The paged decode kernel's
    loop runs groups [g_lo, g_hi) and nothing else: what a slot costs."""
    lo, hi = _kv_live_range(p, w, bs, n_pages)
    return lo, hi, lo // group, (hi + group - 1) // group


def _fa_kernel(q_off_ref, k_off_ref, win_ref, q_ref, k_ref, v_ref, o_ref,
               *ml_refs, scale: float, block_k: int, causal: bool,
               partial: bool, softcap: Optional[float] = None):
    # Refs are [1, block, D] slices of the flattened [B*H, S, D] arrays.
    # ``k_off_ref`` is the absolute position of k[0] (nonzero when this
    # call sees one ring-attention KV chunk); ``win_ref`` holds the
    # sliding-window span (0 = global) as a traced scalar so
    # alternating local/global layers share one compiled kernel. With
    # ``partial`` the raw (unnormalized) accumulator plus the softmax
    # stats m/l are written so callers can merge chunks (ring
    # attention's cross-hop merge). Loop bounds stay independent of the
    # traced window so the kernel remains reverse-differentiable.
    block_q, D = q_ref.shape[1], q_ref.shape[2]
    Sk = k_ref.shape[1]
    qi = pl.program_id(1)
    q_offset = q_off_ref[0]
    k_offset = k_off_ref[0]
    window = win_ref[0]

    q = q_ref[0].astype(jnp.float32) * scale                # [bq, D]

    def body(kb, carry):
        acc, m, l = carry
        ks = k_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        vs = v_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, ks, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [bq, bk]
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        if causal:
            q_pos = (q_offset + qi * block_q
                     + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0))
            k_pos = (k_offset + kb * block_k
                     + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1))
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
            s = jnp.where(window_keep(q_pos, k_pos, window), s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if causal:
            # Keep fully-masked rows at p=0 (exp(NEG_INF-NEG_INF)=1).
            p = jnp.where(s > NEG_INF / 2, p, 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            p, vs, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc_new, m_new, l_new

    acc0 = jnp.zeros((block_q, D), jnp.float32)
    m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)

    if causal:
        # Only k blocks at or before this q block's causal frontier.
        q_end = q_offset + (qi + 1) * block_q
        hi = jax.lax.clamp(
            0, (q_end - k_offset + block_k - 1) // block_k, Sk // block_k)
    else:
        hi = Sk // block_k
    acc, m, l = jax.lax.fori_loop(0, hi, body, (acc0, m0, l0))
    if partial:
        # Stats are [B*H, Sq, 1] with block (1, block_q, 1): Mosaic
        # requires output blocks' last two dims to tile (8, 128) OR
        # equal the array dims — a bare [1, block_q] stats block cannot
        # lower (caught on real TPU; the interpreter accepts it), but a
        # 1-lane minor dim equal to the array's is legal and adds no
        # write amplification.
        m_ref, l_ref = ml_refs
        o_ref[0] = acc
        m_ref[0] = m
        l_ref[0] = l
    else:
        o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _fa_stream_kernel(q_off_ref, win_ref, q_ref, k_ref, v_ref, o_ref,
                      acc_ref, m_ref, l_ref, *, scale: float, causal: bool,
                      softcap: Optional[float], n_kb: int):
    """Streaming variant: K/V arrive one (block_k, D) tile per grid
    step along the innermost grid axis, so Sk is bounded by HBM, not
    VMEM. Online-softmax state lives in VMEM scratch across the k
    sweep (TPU grids run sequentially, so carrying scratch over the
    trailing grid dim is the canonical pallas flash pattern)."""
    block_q, D = q_ref.shape[1], q_ref.shape[2]
    block_k = k_ref.shape[1]
    qi = pl.program_id(1)
    kb = pl.program_id(2)
    q_offset = q_off_ref[0]
    window = win_ref[0]

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    if causal:
        # Skip blocks entirely past the causal frontier or entirely
        # below the sliding window (the DMA still lands; only compute
        # is skipped — acceptable v1 cost for unbounded Sk).
        q_lo = q_offset + qi * block_q
        q_end = q_lo + block_q
        w_eff = jnp.where(window > 0, window, jnp.int32(2 ** 30))
        run = jnp.logical_and(kb * block_k < q_end,
                              (kb + 1) * block_k > q_lo - w_eff + 1)
    else:
        run = kb >= 0  # every block contributes

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale            # [bq, D]
        ks = k_ref[0].astype(jnp.float32)                   # [bk, D]
        vs = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, ks, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        if causal:
            q_pos = (q_offset + qi * block_q
                     + jax.lax.broadcasted_iota(
                         jnp.int32, (block_q, block_k), 0))
            k_pos = (kb * block_k
                     + jax.lax.broadcasted_iota(
                         jnp.int32, (block_q, block_k), 1))
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
            s = jnp.where(window_keep(q_pos, k_pos, window), s, NEG_INF)
        m = m_ref[:, :1]
        l = l_ref[:, :1]
        acc = acc_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if causal:
            p = jnp.where(s > NEG_INF / 2, p, 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc * alpha + jax.lax.dot_general(
            p, vs, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(kb == n_kb - 1)
    def _finalize():
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _flash_streaming(q3, k3, v3, q_off, win, *, B, H, Hkv, Sq, Sk, D,
                     scale, causal, softcap, block_q, block_k, interpret,
                     out_dtype, vma_refs):
    group = H // Hkv
    n_kb = Sk // block_k

    def kv_index(bh, i, kb, q_off_ref, win_ref):
        # Block-sparse DMA skip: clamp the k-block index into this q
        # block's causal/window-live range [lo, hi). Pallas elides the
        # copy when consecutive grid steps map to the same block, so
        # k blocks outside the range are never re-DMA'd — ~2x K-read
        # bandwidth at long causal Sq. q_offset/window are traced, so
        # they reach the index_map via scalar prefetch. The kernel's
        # pl.when(run) predicate still gates compute by the LOGICAL kb.
        kvh = (bh // H) * Hkv + (bh % H) // group
        if not causal:
            return (kvh, kb, 0)
        # A q BLOCK's live range spans its rows' union: the FIRST row
        # (q_lo) reaches back furthest (window lower bound), the LAST
        # row (q_lo + block_q - 1) reaches forward furthest (causal
        # top) — caught by the streaming window test when both were
        # taken from one row.
        q_lo = q_off_ref[0] + i * block_q
        lo, _ = _kv_live_range(q_lo, win_ref[0], block_k, n_kb)
        _, hi = _kv_live_range(q_lo + block_q - 1, win_ref[0],
                               block_k, n_kb)
        return (kvh, jnp.clip(kb, lo, hi - 1), 0)

    return pl.pallas_call(
        functools.partial(_fa_stream_kernel, scale=scale, causal=causal,
                          softcap=softcap, n_kb=n_kb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B * H, Sq // block_q, n_kb),
            in_specs=[
                pl.BlockSpec((1, block_q, D),
                             lambda bh, i, kb, *_: (bh, i, 0)),
                pl.BlockSpec((1, block_k, D), kv_index),
                pl.BlockSpec((1, block_k, D), kv_index),
            ],
            out_specs=pl.BlockSpec((1, block_q, D),
                                   lambda bh, i, kb, *_: (bh, i, 0)),
            scratch_shapes=[
                pltpu.VMEM((block_q, D), jnp.float32),
                pltpu.VMEM((block_q, 128), jnp.float32),
                pltpu.VMEM((block_q, 128), jnp.float32),
            ],
        ),
        out_shape=_sds((B * H, Sq, D), out_dtype, *vma_refs),
        interpret=interpret,
    )(q_off, win, q3, k3, v3)


@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "block_q", "block_k", "interpret", "attn_softcap"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, q_offset=0,
                    scale: Optional[float] = None,
                    kv_mask: Optional[jnp.ndarray] = None,
                    window=None,
                    attn_softcap: Optional[float] = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool = False) -> jnp.ndarray:
    """Flash attention; same contract as mha_reference (BSHD layout).

    Falls back to the reference for every shape the kernel cannot tile
    (kv_mask, tiny/misaligned Sq or Sk, non-128-multiple head_dim,
    VMEM-oversized kv) so callers can use it unconditionally.
    ``q_offset`` may be a traced scalar — it does not trigger
    recompilation.
    """
    B, Sq, H, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    assert H % Hkv == 0, f"q heads {H} not a multiple of kv heads {Hkv}"
    block_q = _snap_block(block_q, Sq)
    block_k = _snap_block(block_k, Sk)
    if (kv_mask is not None or Sq < 8
            or D % 128 or block_q % 8 or block_k % 128):
        return mha_reference(q, k, v, causal=causal, q_offset=q_offset,
                             scale=scale, kv_mask=kv_mask, window=window,
                             attn_softcap=attn_softcap)
    group = H // Hkv

    # Fold heads into the leading (grid) axis: BSHD -> [B*H, S, D].
    q3 = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    k3 = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk, D)
    v3 = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk, D)
    q_off = jnp.asarray(q_offset, jnp.int32).reshape(1)
    k_off = jnp.zeros((1,), jnp.int32)
    win = jnp.asarray(0 if window is None else window, jnp.int32).reshape(1)

    if 2 * Sk * D * k.dtype.itemsize > MAX_RESIDENT_KV_BYTES:
        # K/V too large to stay VMEM-resident per grid step: stream
        # (block_k, D) tiles through the grid instead — Sk unbounded.
        out = _flash_streaming(
            q3, k3, v3, q_off, win, B=B, H=H, Hkv=Hkv, Sq=Sq, Sk=Sk, D=D,
            scale=D ** -0.5 if scale is None else scale, causal=causal,
            softcap=attn_softcap, block_q=block_q, block_k=block_k,
            interpret=interpret, out_dtype=q.dtype, vma_refs=(q, k, v))
        return out.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)

    def kv_index(bh, i):
        # q row b*H + h reads kv row b*Hkv + h//group (GQA without
        # broadcasting kv in HBM).
        return ((bh // H) * Hkv + (bh % H) // group, 0, 0)

    out = pl.pallas_call(
        functools.partial(_fa_kernel,
                          scale=D ** -0.5 if scale is None else scale,
                          block_k=block_k, causal=causal, partial=False,
                          softcap=attn_softcap),
        grid=(B * H, Sq // block_q),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_q, D), lambda bh, i: (bh, i, 0)),
            pl.BlockSpec((1, Sk, D), kv_index),
            pl.BlockSpec((1, Sk, D), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda bh, i: (bh, i, 0)),
        out_shape=_sds(q3.shape, q.dtype, q, k, v),
        interpret=interpret,
    )(q_off, k_off, win, q3, k3, v3)
    return out.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)


def partial_reference(q, k, v, *, causal=True, q_offset=0, k_offset=0,
                      scale=None, window=None, attn_softcap=None):
    """jnp ground truth for flash_attention_partial's (acc, m, l)
    contract — also the in-shard_map interpret-mode stand-in (the
    pallas interpreter cannot emulate DMAs on vma-tagged operands).
    ``window`` (traced scalar OK; <=0 or None = global) limits
    attention to the last ``window`` positions; requires causal."""
    from tpushare.ops.attention import _expand_kv
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    scale = D ** -0.5 if scale is None else scale
    ke = _expand_kv(k, H).astype(jnp.float32)
    ve = _expand_kv(v, H).astype(jnp.float32)
    logits = jnp.einsum("bqhd,bkhd->bhqk",
                        q.astype(jnp.float32) * scale, ke)
    if attn_softcap is not None:
        logits = attn_softcap * jnp.tanh(logits / attn_softcap)
    if causal:
        q_pos = q_offset + jnp.arange(Sq)[:, None]
        k_pos = k_offset + jnp.arange(Sk)[None, :]
        mask = (k_pos <= q_pos)
        if window is not None:
            mask = jnp.logical_and(mask, window_keep(q_pos, k_pos, window))
        mask = mask[None, None]
        logits = jnp.where(mask, logits, NEG_INF)
    m = jnp.max(logits, axis=-1)                       # [B,H,Sq]
    p = jnp.exp(logits - m[..., None])
    if causal:
        p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=-1)                            # [B,H,Sq]
    acc = jnp.einsum("bhqk,bkhd->bqhd", p, ve)         # [B,Sq,H,D] f32
    return acc, m, l


@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "block_q", "block_k", "interpret", "attn_softcap"))
def flash_attention_partial(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                            causal: bool = True, q_offset=0, k_offset=0,
                            scale: Optional[float] = None,
                            window=None,
                            attn_softcap: Optional[float] = None,
                            block_q: int = DEFAULT_BLOCK_Q,
                            block_k: int = DEFAULT_BLOCK_K,
                            interpret: bool = False):
    """One KV-chunk flash pass returning the UNNORMALIZED accumulator
    plus softmax stats, for cross-chunk merging (ring attention).

    q [B,Sq,H,D]; k,v [B,Sk,Hkv,D]; ``q_offset``/``k_offset`` are the
    absolute positions of q[0]/k[0] (traced scalars — chunk rotation
    does not recompile). ``window`` (traced scalar OK; None/<=0 =
    global) masks to the last ``window`` positions — kernel loop bounds
    stay causal-only, so windowing is exactness, not savings, here
    (the resident/streaming kernels own the DMA-skip optimization).
    Returns (acc [B,Sq,H,D] f32, m [B,H,Sq] f32, l [B,H,Sq] f32) with
    softmax(...)@v == acc / l after merging.
    """
    B, Sq, H, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    assert H % Hkv == 0, (H, Hkv)
    block_q = _snap_block(block_q, Sq)
    block_k = _snap_block(block_k, Sk)
    group = H // Hkv

    q3 = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    k3 = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk, D)
    v3 = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk, D)
    q_off = jnp.asarray(q_offset, jnp.int32).reshape(1)
    k_off = jnp.asarray(k_offset, jnp.int32).reshape(1)
    win = jnp.asarray(0 if window is None else window,
                      jnp.int32).reshape(1)     # 0 = global
    def kv_index(bh, i):
        return ((bh // H) * Hkv + (bh % H) // group, 0, 0)

    acc, m, l = pl.pallas_call(
        functools.partial(_fa_kernel,
                          scale=D ** -0.5 if scale is None else scale,
                          block_k=block_k, causal=causal, partial=True,
                          softcap=attn_softcap),
        grid=(B * H, Sq // block_q),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_q, D), lambda bh, i: (bh, i, 0)),
            pl.BlockSpec((1, Sk, D), kv_index),
            pl.BlockSpec((1, Sk, D), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, i: (bh, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, i: (bh, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, i: (bh, i, 0)),
        ],
        out_shape=[
            _sds((B * H, Sq, D), jnp.float32, q, k, v),
            _sds((B * H, Sq, 1), jnp.float32, q, k, v),
            _sds((B * H, Sq, 1), jnp.float32, q, k, v),
        ],
        interpret=interpret,
    )(q_off, k_off, win, q3, k3, v3)
    acc = acc.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)
    return acc, m[:, :, 0].reshape(B, H, Sq), l[:, :, 0].reshape(B, H, Sq)


def _decode_kernel(pos_ref, win_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, scale: float,
                   softcap: Optional[float], hkv: int, n_kb: int):
    # One decode step: q_ref [1, gp, D] holds the gp(>=8)-padded GQA
    # head group that shares this kv head; k_ref/v_ref stream
    # (block_k, D) cache tiles along the trailing grid axis. Ragged
    # lengths arrive as SMEM scalars: row b attends k_pos <= pos[b]
    # (the just-written token included), optionally windowed.
    gp, D = q_ref.shape[1], q_ref.shape[2]
    block_k = k_ref.shape[1]
    bh = pl.program_id(0)
    kb = pl.program_id(1)
    p = pos_ref[bh // hkv]
    window = win_ref[0]
    w_eff = jnp.where(window > 0, window, jnp.int32(2 ** 30))

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    run = jnp.logical_and(kb * block_k <= p,
                          (kb + 1) * block_k > p - w_eff + 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale            # [gp, D]
        ks = k_ref[0].astype(jnp.float32)                   # [bk, D]
        vs = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, ks, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        k_pos = (kb * block_k
                 + jax.lax.broadcasted_iota(jnp.int32, (gp, block_k), 1))
        keep = jnp.logical_and(k_pos <= p, k_pos > p - w_eff)
        s = jnp.where(keep, s, NEG_INF)
        m = m_ref[:, :1]
        l = l_ref[:, :1]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        pexp = jnp.where(s > NEG_INF / 2, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(pexp, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            pexp, vs, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(kb == n_kb - 1)
    def _finalize():
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


# Decode-kernel dispatch policy. The only on-chip differential so far
# (rounds 2-5, benchmarks/KERNELS_TPU_r5.jsonl; before PRs 1-20 and on
# another JAX, not reproduced since) put flash_decode ~3x BEHIND XLA's
# fused masked-attention decode at B=8/M=8192 — and serving is
# decode-bound, so a kernel slower than the compiler default is a
# liability. Until a credible >=1.0x re-measurement lands,
# contiguous-cache decode YIELDS to XLA; set
# TPUSHARE_DECODE_KERNEL=1 to force the pallas kernel (benchmarking /
# after validating on your hardware), =0 to force XLA uncondition-
# ally. paged_flash_decode on BF16 pools is NOT gated by this default:
# its XLA fallback gathers the paged pool into a dense
# [B, max_blocks*bs, ...] view every step (transformer.py paged
# branch), which the on-chip measurements put behind the paged kernel
# (1.22x r3 window, 1.07x re-measure). On INT8 pools dispatch keys on
# slot capacity (kernel from ~8k ctx up, the measured crossover) —
# see paged_decode_eligible.
DECODE_KERNEL_ENV = "TPUSHARE_DECODE_KERNEL"


def _decode_kernel_policy() -> Optional[bool]:
    """True = force kernel, False = force XLA, None = default."""
    import os
    val = (os.environ.get(DECODE_KERNEL_ENV) or "").strip().lower()
    if not val:
        return None         # unset or empty: default policy
    return val not in ("0", "false", "no", "off")


def decode_eligible(q: jnp.ndarray, k: jnp.ndarray) -> bool:
    """Auto-dispatch predicate for flash_decode (ragged decode step).

    Default-False on shapes that fit: the measured on-chip evidence
    has the XLA fused path ahead (policy note above); the kernel is
    opt-in via TPUSHARE_DECODE_KERNEL=1 until a credible win is
    recorded."""
    if jax.default_backend() != "tpu":
        return False
    policy = _decode_kernel_policy()
    if policy is not True:
        return False
    B, Sq, H, D = q.shape
    M, Hkv = k.shape[1], k.shape[2]
    return (Sq == 1 and D % 128 == 0 and M % 128 == 0
            and H % Hkv == 0)


@functools.partial(jax.jit, static_argnames=(
    "scale", "attn_softcap", "block_k", "interpret"))
def flash_decode(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                 pos: jnp.ndarray, *, scale: Optional[float] = None,
                 window=None, attn_softcap: Optional[float] = None,
                 block_k: int = 512,
                 interpret: bool = False) -> jnp.ndarray:
    """Ragged decode attention over a contiguous KV cache.

    q [B, 1, H, D]; k, v [B, M, Hkv, D]; pos [B] — row b attends cache
    positions <= pos[b] (the slot its new token was just written to),
    further limited to the last ``window`` positions when window > 0
    (traced scalar OK). Matches the model's ragged branch
    (models/transformer.py:275-281: kv_mask = arange <= pos, windowed).

    The GQA head group sharing a kv head rides the sublane dim (padded
    to 8), so decode streams each cache tile from HBM exactly once per
    kv head — the op is KV-bandwidth-bound, which is its roofline.
    """
    B, Sq, H, D = q.shape
    assert Sq == 1, "flash_decode is the Sq==1 path"
    M, Hkv = k.shape[1], k.shape[2]
    assert H % Hkv == 0, (H, Hkv)
    g = H // Hkv
    gp = max(8, -(-g // 8) * 8)
    block_k = _snap_block(block_k, M)

    # Head h = kvh*g + j (kv_index convention): [B,H,D] -> [B,Hkv,g,D].
    q4 = q[:, 0].reshape(B, Hkv, g, D)
    qp = jnp.zeros((B * Hkv, gp, D), q.dtype)
    qp = qp.at[:, :g].set(q4.reshape(B * Hkv, g, D))
    k3 = k.transpose(0, 2, 1, 3).reshape(B * Hkv, M, D)
    v3 = v.transpose(0, 2, 1, 3).reshape(B * Hkv, M, D)
    pos_s = jnp.asarray(pos, jnp.int32).reshape(B)
    win = jnp.asarray(0 if window is None else window,
                      jnp.int32).reshape(1)
    n_kb = M // block_k

    def kv_index(bh, kb, pos_ref, win_ref):
        # Block-sparse DMA skip (same trick as the streaming kernel):
        # clamp the cache-block index into this row's live range — a
        # repeated index elides the copy, so blocks past pos[b] (and
        # before the sliding window) are never fetched. At random fill
        # levels this halves decode's KV read traffic, which IS its
        # roofline. Compute stays gated on the logical kb.
        lo, hi = _kv_live_range(pos_ref[bh // Hkv], win_ref[0],
                                block_k, n_kb)
        return (bh, jnp.clip(kb, lo, hi - 1), 0)

    out = pl.pallas_call(
        functools.partial(_decode_kernel,
                          scale=D ** -0.5 if scale is None else scale,
                          softcap=attn_softcap, hkv=Hkv, n_kb=n_kb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B * Hkv, n_kb),
            in_specs=[
                pl.BlockSpec((1, gp, D), lambda bh, kb, *_: (bh, 0, 0)),
                pl.BlockSpec((1, block_k, D), kv_index),
                pl.BlockSpec((1, block_k, D), kv_index),
            ],
            out_specs=pl.BlockSpec((1, gp, D),
                                   lambda bh, kb, *_: (bh, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((gp, D), jnp.float32),
                pltpu.VMEM((gp, 128), jnp.float32),
                pltpu.VMEM((gp, 128), jnp.float32),
            ],
        ),
        out_shape=_sds((B * Hkv, gp, D), q.dtype, q, k, v),
        interpret=interpret,
    )(pos_s, win, qp, k3, v3)
    return out[:, :g].reshape(B, Hkv * g, D)[:, None].reshape(B, 1, H, D)


def _stacked_pages(pool_k, pool_v, k_scale, v_scale, layer, D: int):
    """The paged kernels' one view of the KV pool: pages
    [L, nb, bs, Hkv*D] (the shape models/paged.py stores, so a whole
    stacked pool is passed as it lies and ``layer`` picks the layer in
    the index_map — no layer slice exists in HBM), scale pages
    [L, nb, Hkv_pad, bs], and the layer as a scalar-prefetch operand.
    ``layer=None`` takes one layer's pool [nb, bs, Hkv, D] (scales
    [nb, Hkv_pad, bs]) as a stack of one. Returns
    (pages_k, pages_v, k_scale, v_scale, layer [1] int32, nb, bs, Hkv)."""
    if layer is None:
        nb, bs, Hkv, D2 = pool_k.shape
        assert D2 == D, (pool_k.shape, D)
        pool_k = pool_k.reshape(1, nb, bs, Hkv * D)
        pool_v = pool_v.reshape(1, nb, bs, Hkv * D)
        if k_scale is not None:
            k_scale, v_scale = k_scale[None], v_scale[None]
        layer = 0
    _, nb, bs, HD = pool_k.shape
    assert HD % D == 0 and pool_v.shape == pool_k.shape, (
        pool_k.shape, pool_v.shape, D)
    assert bs % 8 == 0, f"block_size {bs} must be a multiple of 8"
    return (pool_k, pool_v, k_scale, v_scale,
            jnp.asarray(layer, jnp.int32).reshape(1), nb, bs, HD // D)


def _scale_pages(k_scale, v_scale, L: int, nb: int, bs: int, Hkv: int):
    """The two int8 scale operands of a paged kernel, checked against
    the one page layout [L, nb, Hkv_pad, bs] (quant.scales_to_pool_layout;
    stored so at init by models/paged.py)."""
    from tpushare.models.quant import kv_scale_pad
    want = (L, nb, kv_scale_pad(Hkv), bs)   # one padding rule with the pool
    assert k_scale.shape == want == v_scale.shape, (
        f"scale pools must be pre-laid-out [L, nb, Hkv_pad, bs] = {want}"
        f", got {k_scale.shape}")
    return [k_scale.astype(jnp.float32), v_scale.astype(jnp.float32)]


#: Keys one loop step of the paged decode kernel covers. Swept on a v5e
#: at the benchmark's three pools (bf16 pages of 16 rows, PERF.md §6,
#: PR 29): 128 keys a step cost 73 / 132 / 92 us a call (chat, docqa,
#: Mixtral), 256 cost 62 / 106 / 75, 512 cost 64 / 106 / 73 — level
#: from 256 up, where a step's copies (1 MiB) already hide its matmuls;
#: 256 holds the double buffer at 2 MiB of VMEM.
DECODE_GROUP_KEYS = 256


#: VMEM the kernel's double buffer (two halves, K and V) may take.
DECODE_BUFFER_BYTES = 4 * 1024 * 1024


def _decode_group_pages(bs: int, mb: int, row_bytes: int) -> int:
    """Pages one loop step of the paged decode kernel covers, from what
    the call can see: DECODE_GROUP_KEYS keys' worth of ``bs``-row pages,
    fewer where a row of all kv heads (``row_bytes``) is so wide that
    four tiles of them would pass DECODE_BUFFER_BYTES, never more than
    a slot has, never under one (``mb`` need not divide by it)."""
    keys = min(DECODE_GROUP_KEYS, DECODE_BUFFER_BYTES // (4 * row_bytes))
    return max(1, min(mb, keys // bs))


def _pv_exact(p, v):
    """p [rows, T] float32 times v [T, D] as stored, in float32.

    Pages that are bf16 in HBM meet the MXU as bf16, never widened on
    the VPU: p is split into three bf16 terms (8 + 8 + 8 bits: all of a
    float32's mantissa), the terms ride ONE matmul as four blocks of
    rows (the fourth zero, so the stack is whole bf16 tiles) against
    the same V tile, and the three partial products, each exact in
    float32, are summed: p is not rounded. Any other page dtype
    (float32 pools, dequantized int8) takes a float32 matmul."""
    dims = (((1,), (0,)), ((), ()))
    if v.dtype != jnp.bfloat16:
        return jax.lax.dot_general(p, v.astype(jnp.float32), dims,
                                   preferred_element_type=jnp.float32)
    rows = p.shape[0]
    hi = p.astype(jnp.bfloat16).astype(jnp.float32)
    mid = (p - hi).astype(jnp.bfloat16).astype(jnp.float32)
    terms = jnp.concatenate([hi, mid, p - hi - mid, jnp.zeros_like(p)])
    out = jax.lax.dot_general(terms.astype(jnp.bfloat16), v, dims,
                              preferred_element_type=jnp.float32)
    return out[:rows] + out[rows:2 * rows] + out[2 * rows:3 * rows]


def _paged_decode_kernel(table_ref, pos_ref, win_ref, layer_ref, q_ref,
                         k_hbm, v_hbm, *rest, scale: float,
                         softcap: Optional[float], hkv: int, g_pad: int,
                         group: int, n_pages: int,
                         quantized: bool = False):
    # One decode step over a block-table-paged KV pool. Grid (B,): one
    # grid step a slot, and inside it a loop over the slot's LIVE groups
    # of ``group`` pages (_kv_live_range: pages past pos[b] or behind
    # the window cost no step, no copy and no compute). The pools stay
    # in HBM (k_hbm / v_hbm [L, nb, bs, Hkv*D], never gathered into a
    # dense [B, S, ...] view, never sliced by layer): a group's live
    # pages are copied, one DMA a page through the scalar-prefetched
    # block table, into one VMEM tile [group*bs, Hkv*D] of a double
    # buffer, and while a group is computed the next one in line (the
    # slot's next group, or the next slot's first) is already on its
    # way. Each live page streams from HBM once; all kv heads are
    # processed from the one tile in a static unroll.
    #
    # quantized=True: k/v pages are int8 and their scale pages
    # ([L, nb, Hkv_pad, bs] f32 — bs on the lane dim, the layout Mosaic
    # accepts) are copied beside them; pages dequantize on the VPU
    # after the DMA, so HBM traffic — decode's roofline — is halved
    # while the softmax/matmul math is unchanged.
    if quantized:
        (ks_hbm, vs_hbm, o_ref, k_buf, v_buf, ks_buf, vs_buf, sem,
         turn_ref) = rest
    else:
        o_ref, k_buf, v_buf, sem, turn_ref = rest
        ks_buf = vs_buf = None
    bs = k_buf.shape[1] // group
    T = group * bs
    D = q_ref.shape[2]
    b = pl.program_id(0)
    n_slots = pl.num_programs(0)
    layer = layer_ref[0]
    window = win_ref[0]
    w_eff = jnp.where(window > 0, window, jnp.int32(2 ** 30))

    def live(slot):
        return _live_groups(pos_ref[slot], window, bs, n_pages, group)

    def page_copies(slot, lo, hi, g, buf, start: bool):
        """Start, or wait for, the copies of the live pages (of
        ``slot``'s [lo, hi)) of its group ``g`` into half ``buf`` of
        the double buffer. Page j lands on rows (j - g*group)*bs of the
        tile, so a key's row is its position less the group's first."""
        first = jnp.maximum(lo, g * group)

        def one(i, _):
            at = first + i - g * group
            blk = jnp.maximum(table_ref[slot, first + i], 0)
            rows = pl.ds(pl.multiple_of(at * bs, bs), bs)
            pairs = [(k_hbm, k_buf.at[buf, rows]),
                     (v_hbm, v_buf.at[buf, rows])]
            if quantized:
                pairs += [(ks_hbm, ks_buf.at[buf, at]),
                          (vs_hbm, vs_buf.at[buf, at])]
            for src, dst in pairs:
                dma = pltpu.make_async_copy(src.at[layer, blk], dst,
                                            sem.at[buf])
                dma.start() if start else dma.wait()

        jax.lax.fori_loop(0, jnp.minimum(hi, (g + 1) * group) - first,
                          one, None)

    lo, hi, g_lo, g_hi = live(b)

    @pl.when(b == 0)
    def _first():
        # Tile rows no copy ever writes are multiplied by p = 0: they
        # must hold finite values (0 x NaN would poison the product).
        v_buf[...] = jnp.zeros_like(v_buf)
        if quantized:
            vs_buf[...] = jnp.zeros_like(vs_buf)
        turn_ref[0] = 0
        page_copies(b, lo, hi, g_lo, 0, start=True)

    p = pos_ref[b]
    rows_all = hkv * g_pad
    heads = [slice(h * g_pad, (h + 1) * g_pad) for h in range(hkv)]

    def tile(buf_ref, scale_ref, buf, h):
        """Head h of the group's tile, [T, D]: as stored, or int8
        dequantized by its pages' row scales."""
        x = buf_ref[buf, :, h * D:(h + 1) * D]
        if not quantized:
            return x
        return jnp.concatenate([
            x[i * bs:(i + 1) * bs].astype(jnp.float32)
            * scale_ref[buf, i, h, :][:, None]          # [bs, 1] row scales
            for i in range(group)])

    def step(g, carry):
        acc, m, l, buf = carry
        # Next in line: this slot's next group, else the next slot's
        # first (nothing after the last slot's last).
        last = g == g_hi - 1
        nslot = jnp.where(last, jnp.minimum(b + 1, n_slots - 1), b)
        nlo, nhi, ng_lo, _ = live(nslot)

        @pl.when(jnp.logical_not(jnp.logical_and(last, b == n_slots - 1)))
        def _prefetch():
            page_copies(nslot, nlo, nhi, jnp.where(last, ng_lo, g + 1),
                        1 - buf, start=True)

        page_copies(b, lo, hi, g, buf, start=False)
        k_pos = g * T + jax.lax.broadcasted_iota(jnp.int32, (rows_all, T), 1)
        keep = jnp.logical_and(k_pos <= p, k_pos > p - w_eff)
        s = []
        for h in range(hkv):                      # static unroll
            qh, kh = q_ref[0, heads[h], :], tile(k_buf, ks_buf, buf, h)
            if not qh.dtype == kh.dtype == jnp.bfloat16:
                # bf16 x bf16 is exact in the MXU's float32; any other
                # pair is widened first
                qh, kh = qh.astype(jnp.float32), kh.astype(jnp.float32)
            s.append(jax.lax.dot_general(
                qh, kh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32))
        s = jnp.concatenate(s) * scale                      # [rows_all, T]
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        s = jnp.where(keep, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        pexp = jnp.where(keep, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(pexp, axis=-1, keepdims=True)
        pv = jnp.concatenate([
            _pv_exact(pexp[heads[h]], tile(v_buf, vs_buf, buf, h))
            for h in range(hkv)])                           # [rows_all, D]
        return acc * alpha + pv, m_new, l, 1 - buf

    acc, _, l, buf = jax.lax.fori_loop(
        g_lo, g_hi, step,
        (jnp.zeros((rows_all, D), jnp.float32),
         jnp.full((rows_all, 1), NEG_INF, jnp.float32),
         jnp.zeros((rows_all, 1), jnp.float32), turn_ref[0]))
    turn_ref[0] = buf
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "scale", "attn_softcap", "interpret"))
def paged_flash_decode(q: jnp.ndarray, pool_k: jnp.ndarray,
                       pool_v: jnp.ndarray, table: jnp.ndarray,
                       pos: jnp.ndarray, *, scale: Optional[float] = None,
                       window=None, attn_softcap: Optional[float] = None,
                       k_scale: Optional[jnp.ndarray] = None,
                       v_scale: Optional[jnp.ndarray] = None,
                       layer=None,
                       interpret: bool = False) -> jnp.ndarray:
    """Ragged decode attention straight off a paged KV pool.

    q [B, 1, H, D]; pool_k/pool_v either the whole stacked pool
    [L, n_blocks, bs, Hkv*D] as models/paged.py stores it, with
    ``layer`` (traced scalar OK) the layer to read — the forward's
    call: the stack is the layer loop's carry and no layer of it is
    ever sliced out — or, with ``layer=None``, one layer's pool
    [n_blocks, bs, Hkv, D]; table [B, max_blocks] int32 pool
    indices (-1 = unallocated); pos [B] — slot b attends pool positions
    <= pos[b] through its block table (the new token's KV must already
    be scattered at pos[b]). Unallocated table entries are clamped to
    page 0 and masked by ``pos``, so they are never attended.

    What a call costs follows the LIVE pages, not the table's width:
    the kernel takes one grid step a slot and, inside it, one loop step
    a group of _decode_group_pages pages of the slot's live range
    (pos[b] and ``window``); a page nobody may attend is neither
    copied nor computed, and a live page is DMA'd from HBM exactly once
    per slot.

    Int8 pools: pass ``k_scale``/``v_scale`` [n_blocks, Hkv_pad, bs]
    (stacked: [L, n_blocks, Hkv_pad, bs]; the models/paged.py kv_quant
    pools store scales in exactly this
    page layout from init — quant.scales_to_pool_layout; bs on the
    lane dim because Mosaic rejects a short minor axis) — pages stream
    from HBM as int8 and dequantize on the VPU after the DMA, halving
    decode's KV page traffic; the scale pages are copied beside their
    pages. r3 measured the one-page-a-step kernel BEHIND XLA's fused
    int8 gather at 4k ctx and ahead from 8k up (1.22-1.81x); the
    dispatch crossover (paged_decode_eligible) is that measurement's
    until the grouped kernel's is taken.

    bs must be whole tiles of the pages' dtype (paged_decode_eligible:
    16 rows of bf16, 32 of int8); the score tile is a group of pages
    wide whatever bs is, so small pages cost nothing but their DMAs.
    """
    B, Sq, H, D = q.shape
    assert Sq == 1, "paged_flash_decode is the Sq==1 path"
    kp, vp, k_scale, v_scale, layer_s, nb, bs, Hkv = _stacked_pages(
        pool_k, pool_v, k_scale, v_scale, layer, D)
    assert H % Hkv == 0, (pool_k.shape, q.shape)
    quantized = k_scale is not None
    mb = table.shape[1]
    group = _decode_group_pages(bs, mb, Hkv * D * kp.dtype.itemsize)
    g = H // Hkv
    g_pad = max(8, -(-g // 8) * 8)

    # Head h = kvh*g + j: [B,H,D] -> groups on the sublane dim.
    qp = jnp.pad(q[:, 0].reshape(B, Hkv, g, D),
                 ((0, 0), (0, 0), (0, g_pad - g), (0, 0)))
    qp = qp.reshape(B, Hkv * g_pad, D)
    table_s = jnp.asarray(table, jnp.int32)
    pos_s = jnp.asarray(pos, jnp.int32).reshape(B)
    win = jnp.asarray(0 if window is None else window,
                      jnp.int32).reshape(1)

    def q_index(b, table_ref, pos_ref, win_ref, layer_ref):
        return (b, 0, 0)

    in_hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    in_specs = [pl.BlockSpec((1, Hkv * g_pad, D), q_index), in_hbm, in_hbm]
    operands = [qp, kp, vp]
    scratch = [pltpu.VMEM((2, group * bs, Hkv * D), kp.dtype)] * 2
    if quantized:
        operands += _scale_pages(k_scale, v_scale, kp.shape[0], nb, bs,
                                 Hkv)
        in_specs += [in_hbm] * 2
        scratch += [pltpu.VMEM((2, group) + operands[-1].shape[2:],
                               jnp.float32)] * 2

    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel,
                          scale=D ** -0.5 if scale is None else scale,
                          softcap=attn_softcap, hkv=Hkv, g_pad=g_pad,
                          group=group, n_pages=mb, quantized=quantized),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, Hkv * g_pad, D), q_index),
            scratch_shapes=scratch + [
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        # the double buffer's turn and its copy in flight pass from one
        # slot's grid step to the next: the steps run in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        out_shape=_sds((B, Hkv * g_pad, D), q.dtype, q, pool_k, pool_v),
        interpret=interpret,
    )(table_s, pos_s, win, layer_s, *operands)
    out4 = out.reshape(B, Hkv, g_pad, D)[:, :, :g]
    return out4.reshape(B, 1, H, D)


def _paged_verify_kernel(table_ref, pos_ref, win_ref, layer_ref, q_ref,
                         k_ref, v_ref, *rest, scale: float,
                         softcap: Optional[float], hkv: int, sq: int,
                         gq_pad: int, n_pages: int,
                         quantized: bool = False):
    # Multi-token verify over a block-table-paged KV pool: the Sq
    # candidate tokens of slot b (positions pos[b]..pos[b]+Sq-1, KV
    # already scattered) are folded into the query-row dimension next
    # to the grouped heads — per kv head, g*Sq rows ordered g-major
    # (row = j*Sq + s), so one page DMA feeds every (head, candidate)
    # pair and the pool is never gathered into a dense [B, S, ...]
    # view (the per-layer tax the multi-token fallback in
    # transformer.py pays on every speculative round). Per-row ragged
    # causality: row s attends k_pos <= pos[b] + s.
    if quantized:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest
    bs = k_ref.shape[1]
    D = q_ref.shape[2]
    b = pl.program_id(0)
    kb = pl.program_id(1)
    p = pos_ref[b]
    window = win_ref[0]
    w_eff = jnp.where(window > 0, window, jnp.int32(2 ** 30))

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Live for ANY row: the newest query (p+sq-1) bounds the top, the
    # oldest (p) bounds the window bottom.
    run = jnp.logical_and(kb * bs <= p + sq - 1,
                          (kb + 1) * bs > p - w_eff + 1)

    @pl.when(run)
    def _compute():
        k_pos = (kb * bs
                 + jax.lax.broadcasted_iota(jnp.int32, (gq_pad, bs), 1))
        qpos = p + (jax.lax.broadcasted_iota(
            jnp.int32, (gq_pad, bs), 0) % sq)
        keep = jnp.logical_and(k_pos <= qpos, k_pos > qpos - w_eff)
        for h in range(hkv):                      # static unroll
            sl = slice(h * gq_pad, (h + 1) * gq_pad)
            qh = q_ref[0, sl, :].astype(jnp.float32) * scale
            ks = k_ref[0, :, h * D:(h + 1) * D].astype(jnp.float32)
            vs = v_ref[0, :, h * D:(h + 1) * D].astype(jnp.float32)
            if quantized:
                ks = ks * ks_ref[0, h, :][:, None]    # [bs, 1] row scales
                vs = vs * vs_ref[0, h, :][:, None]
            s = jax.lax.dot_general(qh, ks, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if softcap is not None:
                s = softcap * jnp.tanh(s / softcap)
            s = jnp.where(keep, s, NEG_INF)
            m = m_ref[sl, :1]
            l = l_ref[sl, :1]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            pexp = jnp.where(s > NEG_INF / 2, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(pexp, axis=-1, keepdims=True)
            acc_ref[sl, :] = acc_ref[sl, :] * alpha + jax.lax.dot_general(
                pexp, vs, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[sl, :] = jnp.broadcast_to(m_new, (gq_pad, m_ref.shape[1]))
            l_ref[sl, :] = jnp.broadcast_to(l_new, (gq_pad, l_ref.shape[1]))

    @pl.when(kb == n_pages - 1)
    def _finalize():
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "scale", "attn_softcap", "interpret"))
def paged_flash_verify(q: jnp.ndarray, pool_k: jnp.ndarray,
                       pool_v: jnp.ndarray, table: jnp.ndarray,
                       pos: jnp.ndarray, *, scale: Optional[float] = None,
                       window=None, attn_softcap: Optional[float] = None,
                       k_scale: Optional[jnp.ndarray] = None,
                       v_scale: Optional[jnp.ndarray] = None,
                       layer=None,
                       interpret: bool = False) -> jnp.ndarray:
    """Speculative-verify attention straight off a paged KV pool.

    q [B, Sq, H, D] — slot b's Sq candidate tokens at positions
    pos[b]..pos[b]+Sq-1, whose KV must already be scattered into the
    pool; per-row causality (row s attends <= pos[b]+s) rides inside
    the kernel. Everything else (the stacked pool and ``layer``, or
    one layer's pool; int8 scale pages,
    page-level DMA skip, bs constraints) matches paged_flash_decode —
    this is its Sq>1 sibling, with candidates folded into the
    query-row dimension so each page still streams from HBM exactly
    once per slot per round.

    Deliberately NOT unified with the decode kernel, despite decode
    being the sq=1 case: this opt-in body still takes one grid step a
    page of the table (what paged_flash_decode did until PR 29, at
    0.28 us a dead step), and no cell measures it. Give it the decode
    kernel's loop over live groups once the verify kernel has a cell
    of its own."""
    B, Sq, H, D = q.shape
    assert Sq > 1, "Sq == 1 is paged_flash_decode"
    kp, vp, k_scale, v_scale, layer_s, nb, bs, Hkv = _stacked_pages(
        pool_k, pool_v, k_scale, v_scale, layer, D)
    assert H % Hkv == 0, (pool_k.shape, q.shape)
    quantized = k_scale is not None
    mb = table.shape[1]
    g = H // Hkv
    gq = g * Sq
    gq_pad = max(8, -(-gq // 8) * 8)

    # Row j*Sq + s = (head kvh*g + j, candidate s), g-major so the
    # kernel's row % Sq recovers the candidate index.
    q5 = q.reshape(B, Sq, Hkv, g, D).transpose(0, 2, 3, 1, 4)
    q5 = q5.reshape(B, Hkv, gq, D)
    qp = jnp.zeros((B, Hkv * gq_pad, D), q.dtype)
    for h in range(Hkv):                          # static, Hkv is small
        qp = qp.at[:, h * gq_pad:h * gq_pad + gq].set(q5[:, h])
    table_s = jnp.asarray(table, jnp.int32)
    pos_s = jnp.asarray(pos, jnp.int32).reshape(B)
    win = jnp.asarray(0 if window is None else window,
                      jnp.int32).reshape(1)

    def q_index(b, kb, table_ref, pos_ref, win_ref, layer_ref):
        return (b, 0, 0)

    def kv_index(b, kb, table_ref, pos_ref, win_ref, layer_ref):
        # Page-level DMA skip over the union of the Sq rows' live
        # ranges: bottom from the oldest query (pos), top from the
        # newest (pos + Sq - 1).
        lo, _ = _kv_live_range(pos_ref[b], win_ref[0], bs, mb)
        _, hi = _kv_live_range(pos_ref[b] + Sq - 1, win_ref[0], bs, mb)
        return (layer_ref[0],
                jnp.maximum(table_ref[b, jnp.clip(kb, lo, hi - 1)], 0),
                0, 0)

    in_specs = [
        pl.BlockSpec((1, Hkv * gq_pad, D), q_index),
        pl.BlockSpec((None, 1, bs, Hkv * D), kv_index),
        pl.BlockSpec((None, 1, bs, Hkv * D), kv_index),
    ]
    operands = [qp, kp, vp]
    if quantized:
        operands += _scale_pages(k_scale, v_scale, kp.shape[0], nb, bs,
                                 Hkv)
        in_specs += [pl.BlockSpec((None, 1) + operands[-1].shape[2:],
                                  kv_index)] * 2

    out = pl.pallas_call(
        functools.partial(_paged_verify_kernel,
                          scale=D ** -0.5 if scale is None else scale,
                          softcap=attn_softcap, hkv=Hkv, sq=Sq,
                          gq_pad=gq_pad, n_pages=mb, quantized=quantized),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B, mb),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, Hkv * gq_pad, D), q_index),
            scratch_shapes=[
                pltpu.VMEM((Hkv * gq_pad, D), jnp.float32),
                pltpu.VMEM((Hkv * gq_pad, 128), jnp.float32),
                pltpu.VMEM((Hkv * gq_pad, 128), jnp.float32),
            ],
        ),
        out_shape=_sds((B, Hkv * gq_pad, D), q.dtype, q, pool_k, pool_v),
        interpret=interpret,
    )(table_s, pos_s, win, layer_s, *operands)
    out5 = out.reshape(B, Hkv, gq_pad, D)[:, :, :gq]
    out5 = out5.reshape(B, Hkv, g, Sq, D).transpose(0, 3, 1, 2, 4)
    return out5.reshape(B, Sq, H, D)


def _sublanes(dtype) -> int:
    """Rows of one Mosaic tile for ``dtype``: (8, 128) holds 32-bit
    values, and narrower ones pack along the sublane axis — 16 rows of
    bf16, 32 of int8. A page of fewer rows than a tile is not a block
    the paged kernels may ask for."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def _paged_kernel_policy_ok(quantized: bool,
                            max_ctx: Optional[int]) -> Optional[bool]:
    """Shared dispatch prologue for the paged kernels: returns False
    when policy forbids the kernel, True when TPUSHARE_DECODE_KERNEL=1
    forces it, None when shape checks should decide. ONE copy so a
    policy change (env semantics, the int8 crossover constant) cannot
    silently diverge decode and verify dispatch."""
    if jax.default_backend() != "tpu":
        return False
    policy = _decode_kernel_policy()
    if policy is False:
        return False
    if quantized and policy is not True and (
            max_ctx is None or max_ctx < PAGED_Q8_KERNEL_MIN_CTX):
        return False
    return policy


def _page_dims(pool, D: int, stacked: bool):
    """(bs, Hkv, D of the page) of one layer's pool [nb, bs, Hkv, D] or,
    ``stacked``, of the stored pool [L, nb, bs, Hkv*D]."""
    if stacked:
        bs, HD = pool.shape[2:]
        return (bs, HD // D, D) if HD % D == 0 else (bs, 1, 0)
    return pool.shape[1:]


def paged_verify_eligible(q: jnp.ndarray, pool: jnp.ndarray,
                          quantized: bool = False,
                          max_ctx: Optional[int] = None,
                          stacked: bool = False) -> bool:
    """Dispatch predicate for paged_flash_verify. The XLA alternative
    is the multi-token gathered fallback (transformer.py's paged Sq>1
    branch), which materializes the whole [B, mb*bs, ...] slot view
    per layer per speculative round — the same dense-copy tax the
    decode kernel beat on chip, paid Sq times less often but on the
    same bytes. Sq is capped so the folded query rows stay a small
    multiple of the head group (speculative gamma+1, not prefill).

    OPT-IN for now (TPUSHARE_DECODE_KERNEL=1): the kernel is
    interpret-validated only — this repo's dispatch rule is that a
    default never picks a kernel ahead of banked on-chip evidence
    (DECODE_ROOFLINE.md), and interpret mode has missed Mosaic tiling
    constraints before (the r2 [1, block_q] stats-block lesson). Flips
    to auto-on once bench_kernels' paged_flash_verify row banks."""
    if _paged_kernel_policy_ok(quantized, max_ctx) is not True:
        return False
    B, Sq, H, D = q.shape
    bs, Hkv, D2 = _page_dims(pool, D, stacked)
    return (1 < Sq <= 16 and D % 128 == 0
            and bs % _sublanes(pool.dtype) == 0
            and D2 == D and H % Hkv == 0)


PAGED_Q8_KERNEL_MIN_CTX = 8192


def paged_decode_eligible(q: jnp.ndarray, pool: jnp.ndarray,
                          quantized: bool = False,
                          max_ctx: Optional[int] = None,
                          stacked: bool = False) -> bool:
    """Auto-dispatch predicate for paged_flash_decode. On by default
    for bf16 pools (unlike decode_eligible): the XLA alternative is
    the gathered dense-view fallback, which the on-chip measurement
    put behind the kernel (policy note above). TPUSHARE_DECODE_KERNEL=0
    still forces XLA for A/B runs.

    ``quantized`` (int8 pools): context-dependent, from the r3 on-chip
    crossover sweep (all chain-differenced, credible; B=8, bs=128):
    vs the gathered-dequant fallback the int8 kernel measured 0.63x at
    4k ctx but 1.22x at 8k, 1.81x at 16k, 1.68x at 32k — XLA's fused
    int8 gather materializes a dense bf16 copy whose write+reread cost
    grows with context while the kernel streams pages once. Default:
    kernel iff ``max_ctx`` (the slot capacity mb*bs) >=
    PAGED_Q8_KERNEL_MIN_CTX; TPUSHARE_DECODE_KERNEL=1/0 forces
    either way. Int8 pages of fewer than 128 rows take the fallback
    whatever the policy: their scale pages are narrower than a lane
    tile (below)."""
    if _paged_kernel_policy_ok(quantized, max_ctx) is False:
        return False
    B, Sq, H, D = q.shape
    bs, Hkv, D2 = _page_dims(pool, D, stacked)
    if quantized and bs % 128:
        # the kernel copies a page's scale page [Hkv_pad, bs] out of HBM
        # on its own, and Mosaic slices an HBM array by whole lane tiles
        # only ("Slice shape along dimension 3 must be aligned to tiling
        # (128)", compiled for a described v5e, PR 29)
        return False
    return (Sq == 1 and D % 128 == 0 and bs % _sublanes(pool.dtype) == 0
            and D2 == D and H % Hkv == 0)
