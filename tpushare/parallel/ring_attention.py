"""Ring attention: exact causal attention over a sequence-sharded mesh axis.

Long-context is first-class in this framework: when a sequence is too
long for one chip's VMEM/HBM (ops/flash_attention.py bounds resident KV
at MAX_RESIDENT_KV_BYTES), the sequence is sharded over the ``sp`` mesh
axis and KV chunks rotate around the ring via ``lax.ppermute`` — each
hop rides one ICI link, overlapping with the local attention compute,
so the score matrix is never materialized globally and no chip ever
holds more than Sk/n of the KV. Online-softmax merging across ring
steps keeps the result bit-comparable (f32 accumulation) to full
attention (ops/attention.py mha_reference is the ground truth; tests
assert equivalence on the 8-device CPU mesh).

The reference system has no analog (SURVEY.md §5: long-context absent);
this is part of the JAX workload harness the plugin schedules.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

from tpushare.ops.attention import NEG_INF, _expand_kv, window_keep


def ring_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                   axis_name: str,
                   causal: bool = True,
                   scale: Optional[float] = None,
                   window=None,
                   attn_softcap: Optional[float] = None,
                   impl: str = "auto",
                   interpret: bool = False) -> jnp.ndarray:
    """Per-shard ring attention. Call inside shard_map/pjit-manual.

    q: [B, Sq_local, H, D]; k, v: [B, Sk_local, Hkv, D] — the local
    sequence shards of this device along ``axis_name``. Shards are
    assumed contiguous in ring order (device i holds positions
    [i*S_local, (i+1)*S_local)), which is what PartitionSpec sharding
    of the sequence axis produces.

    KV rotates unexpanded (GQA heads are broadcast per-chunk, after the
    ppermute, so ICI traffic is Hkv-sized, not H-sized).

    ``window`` (requires causal; traced scalar OK, None/<=0 = global)
    limits attention to the last ``window`` positions and
    ``attn_softcap`` applies the Gemma-2 tanh cap — both exact.
    Windowing here is masking only: every hop still rotates, because
    the per-layer window arrives as a traced scan operand (alternating
    local/global layers share one compiled block body, and the global
    layers need all n hops anyway). A static-window hop-skip variant
    would only pay off on all-local models.

    ``impl``: 'dense' computes each chunk's scores as one fused XLA
    einsum; 'flash' runs the pallas partial-flash kernel per chunk
    (ops/flash_attention.flash_attention_partial) and merges the
    (acc, m, l) stats across hops — the long-context fast path on TPU;
    'auto' picks flash on TPU backends for tile-friendly local shapes.
    """
    assert causal or window is None, "window requires causal attention"
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    B, Sq, H, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    scale = D ** -0.5 if scale is None else scale
    q32 = q.astype(jnp.float32) * scale

    if impl == "auto":
        tile_ok = (D % 128 == 0 and Sq >= 128 and Sq % 128 == 0
                   and Sk % 128 == 0)
        use_flash = jax.default_backend() == "tpu" and tile_ok
    else:
        use_flash = impl == "flash"

    perm = [(j, (j + 1) % n) for j in range(n)]

    def chunk_flash(src, ks, vs):
        from tpushare.ops.flash_attention import (
            flash_attention_partial, partial_reference,
        )
        # Interpret mode (CPU tests): the pallas interpreter cannot
        # emulate DMAs on vma-tagged operands inside shard_map, so the
        # jnp contract-equivalent stands in; the kernel itself is
        # validated standalone in tests/test_parallel.py.
        fn = partial_reference if interpret else flash_attention_partial
        kwargs = {} if interpret else {"interpret": interpret}
        acc_c, m_c, l_c = fn(q, ks, vs, causal=causal, q_offset=idx * Sq,
                             k_offset=src * Sk, scale=scale,
                             window=window, attn_softcap=attn_softcap,
                             **kwargs)
        # BSHD f32 -> BHSD to match the accumulator layout.
        return (acc_c.transpose(0, 2, 1, 3), m_c[..., None], l_c[..., None])

    def chunk_dense(src, ks, vs):
        ke = _expand_kv(ks, H).astype(jnp.float32)
        ve = _expand_kv(vs, H).astype(jnp.float32)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q32, ke)      # [B,H,Sq,Sk]
        if attn_softcap is not None:
            logits = attn_softcap * jnp.tanh(logits / attn_softcap)
        if causal:
            q_pos = idx * Sq + jnp.arange(Sq)[:, None]       # global positions
            k_pos = src * Sk + jnp.arange(Sk)[None, :]
            mask = (k_pos <= q_pos)                          # [Sq,Sk]
            if window is not None:
                mask = jnp.logical_and(mask,
                                       window_keep(q_pos, k_pos, window))
            mask = mask[None, None]                          # [1,1,Sq,Sk]
            logits = jnp.where(mask, logits, NEG_INF)
        m_c = jnp.max(logits, axis=-1, keepdims=True)
        p = jnp.exp(logits - m_c)
        if causal:
            # A fully-masked chunk (future positions) leaves m_c at
            # NEG_INF, making exp(NEG_INF - NEG_INF) = 1; zero it by the
            # mask rather than by comparing magnitudes.
            p = jnp.where(mask, p, 0.0)
        l_c = jnp.sum(p, axis=-1, keepdims=True)
        acc_c = jnp.einsum("bhqk,bkhd->bhqd", p, ve)
        return acc_c, m_c, l_c

    chunk = chunk_flash if use_flash else chunk_dense

    def step(s, carry):
        acc, m, l, ks, vs = carry
        src = (idx - s) % n          # original owner of the chunk in hand
        acc_c, m_c, l_c = chunk(src, ks, vs)
        m_new = jnp.maximum(m, m_c)
        alpha = jnp.exp(m - m_new)
        beta = jnp.exp(m_c - m_new)
        l_new = l * alpha + l_c * beta
        acc_new = acc * alpha + acc_c * beta
        ks = jax.lax.ppermute(ks, axis_name, perm)
        vs = jax.lax.ppermute(vs, axis_name, perm)
        return acc_new, m_new, l_new, ks, vs

    # The fori_loop carry type must match its outputs' varying-manual-
    # axes, which is the union of everything q/k/v vary over (at least
    # the ring axis; more when this runs nested in a wider shard_map,
    # e.g. the model's dp×sp×tp training step).
    vma: set = {axis_name}
    for arr in (q, k, v):
        vma |= set(jax.typeof(arr).vma)

    def pvary(x):
        return jax.lax.pcast(x, tuple(vma), to="varying")

    acc0 = pvary(jnp.zeros((B, H, Sq, D), jnp.float32))
    m0 = pvary(jnp.full((B, H, Sq, 1), NEG_INF, jnp.float32))
    l0 = pvary(jnp.zeros((B, H, Sq, 1), jnp.float32))
    acc, m, l, _, _ = jax.lax.fori_loop(0, n, step, (acc0, m0, l0, k, v))
    out = acc / jnp.maximum(l, 1e-30)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)         # back to BSHD


def ring_attention_sharded(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                           mesh: Mesh, axis_name: str = "sp",
                           causal: bool = True,
                           scale: Optional[float] = None,
                           window=None,
                           attn_softcap: Optional[float] = None,
                           impl: str = "auto",
                           interpret: bool = False) -> jnp.ndarray:
    """Convenience wrapper: shard the sequence axis over ``axis_name``
    of ``mesh`` and run ring_attention. For callers not already inside
    a shard_map (e.g. a pjit-auto-sharded model that wants manual
    control just for attention). Batch/head/dim axes stay as-is
    (replicated w.r.t. the sp axis)."""
    spec = P(None, axis_name, None, None)
    fn = shard_map(
        functools.partial(ring_attention, axis_name=axis_name,
                          causal=causal, scale=scale, window=window,
                          attn_softcap=attn_softcap, impl=impl,
                          interpret=interpret),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)
