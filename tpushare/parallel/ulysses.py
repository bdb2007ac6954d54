"""Ulysses-style all-to-all sequence parallelism (DeepSpeed-Ulysses).

The second of the two long-context strategies (the other is
parallel/ring_attention.py): instead of rotating KV chunks around the
ring for n-1 hops, one ``all_to_all`` re-shards the activations from
sequence-sharded [B, S/n, H, D] to head-sharded [B, S, H/n, D], each
rank runs ordinary FULL attention over its head slice, and a second
all_to_all restores sequence sharding. Two collectives total,
each moving the same bytes one ring hop moves — on all-to-all-capable
fabrics (TPU ICI is a torus; XLA lowers all_to_all natively) this
trades ring's n-1 latency-bound hops for one bandwidth-bound shuffle,
and wins when n is large relative to the overlap ring can hide.

Trade-offs vs ring, honestly:
- head-count bound: the sp degree must divide the (kv-)head count;
  ring has no such bound. GQA kv heads smaller than n are broadcast
  (``_expand_kv``) before the shuffle — correct, but kv bytes inflate
  toward MHA, so ring is preferred when Hkv < n.
- memory: each rank holds the FULL sequence for its head slice during
  attention (S*H/n ≈ ring's resident S/n*H), but score tiles are
  full-length — the flash kernel (resident/streaming) bounds that in
  VMEM on TPU.
- windows/softcap come for free: attention is local and complete, so
  the standard masked kernel applies (ring needed cross-chunk stat
  merging).

The reference system has no analog (SURVEY.md §5).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

from tpushare.ops.attention import _expand_kv, attention


def ulysses_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                      axis_name: str,
                      causal: bool = True,
                      scale: Optional[float] = None,
                      window=None,
                      attn_softcap: Optional[float] = None,
                      impl: str = "auto") -> jnp.ndarray:
    """Per-shard a2a attention. Call inside shard_map/pjit-manual.

    q [B, S_local, H, D]; k, v [B, S_local, Hkv, D] — contiguous
    sequence shards along ``axis_name`` (device i holds positions
    [i*S_local, (i+1)*S_local)), like ring_attention. Requires
    H % n == 0; kv heads are broadcast up when Hkv % n != 0.
    Returns [B, S_local, H, D].
    """
    n = jax.lax.psum(1, axis_name)
    B, Sl, H, D = q.shape
    Hkv = k.shape[2]
    assert H % n == 0, f"ulysses needs sp ({n}) to divide heads ({H})"
    if Hkv % n:
        k = _expand_kv(k, H)
        v = _expand_kv(v, H)

    def seq_to_heads(x):
        # [B, S/n, h, D] -> [B, S, h/n, D]: split the head axis across
        # the group, concatenate the sequence axis.
        return jax.lax.all_to_all(x, axis_name, split_axis=2,
                                  concat_axis=1, tiled=True)

    def heads_to_seq(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=1,
                                  concat_axis=2, tiled=True)

    qh = seq_to_heads(q)              # [B, S, H/n, D]
    kh = seq_to_heads(k)
    vh = seq_to_heads(v)
    # Full-sequence attention on the local head slice: the standard
    # masked kernel handles causal/window/softcap — no cross-chunk
    # softmax-stat merging needed.
    out = attention(qh, kh, vh, causal=causal, scale=scale,
                    window=window, attn_softcap=attn_softcap, impl=impl)
    return heads_to_seq(out.astype(q.dtype))


def ulysses_attention_sharded(q: jnp.ndarray, k: jnp.ndarray,
                              v: jnp.ndarray, *,
                              mesh: Mesh, axis_name: str = "sp",
                              causal: bool = True,
                              scale: Optional[float] = None,
                              window=None,
                              attn_softcap: Optional[float] = None,
                              impl: str = "auto") -> jnp.ndarray:
    """Convenience wrapper mirroring ring_attention_sharded."""
    spec = P(None, axis_name, None, None)
    fn = shard_map(
        functools.partial(ulysses_attention, axis_name=axis_name,
                          causal=causal, scale=scale, window=window,
                          attn_softcap=attn_softcap, impl=impl),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)
