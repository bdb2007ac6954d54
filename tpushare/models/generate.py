"""Autoregressive generation for the decoder LM.

The whole-chip baseline workload (BASELINE.md: Gemma-2B inference
tokens/sec) is prefill + a decode loop; this module is that loop,
TPU-first: the whole generation is ONE jitted ``lax.scan`` over decode
steps — no host round-trip per token, static cache shapes, traced
position offsets (models/transformer.py decode never recompiles), and
greedy or temperature sampling decided at trace time.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from tpushare.models.transformer import (
    TransformerConfig, forward, init_cache,
)


def sample_logits(logits: jnp.ndarray, key: jax.Array, *,
                  temperature: float = 0.0,
                  top_k: Optional[int] = None,
                  top_p: Optional[float] = None) -> jnp.ndarray:
    """One sampling step on [B, V] logits -> [B] token ids; the ONE
    greedy/sample dispatch (temperature <= 0 is argmax) shared by
    generate() and the slot server's TokenSampler.

    Filters compose in the standard order: temperature scaling, top-k
    truncation (static k — lax.top_k keeps shapes known to XLA), then
    nucleus/top-p (smallest prefix of the sorted distribution whose
    mass reaches p; the most-probable token always survives). All
    masking happens in logit space with -inf so one categorical draw
    finishes the job — no host-side rejection loops. Threshold-TIED
    logits all survive both filters (shape-static masking; the same
    keep-ties behavior as the usual warper implementations).
    """
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1)
    return jax.random.categorical(
        key, filter_logits(logits, temperature, top_k=top_k, top_p=top_p),
        axis=-1)


def filter_logits(logits: jnp.ndarray, temperature: float,
                  top_k: Optional[int] = None,
                  top_p: Optional[float] = None) -> jnp.ndarray:
    """Temperature-scaled, top-k/top-p-masked logits on [..., V]; the
    softmax of the result IS the sampling law. Factored out of
    sample_logits so speculative acceptance can evaluate the exact
    per-token law (Leviathan's rule is exact for ANY target/draft
    distribution pair — including filtered ones — as long as both
    sides use the same filters the sampler applies). Requires
    temperature > 0."""
    logits = logits / temperature
    if top_k is not None and top_k > 0:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]       # [..., 1]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p is not None and top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]   # desc
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # Keep ranks whose PRECEDING mass is < p (rank 0 always kept);
        # the cutoff is the SMALLEST kept logit.
        keep_sorted = (cum - probs) < top_p
        cutoff = jnp.min(jnp.where(keep_sorted, sorted_logits, jnp.inf),
                         axis=-1, keepdims=True)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return logits


@functools.partial(jax.jit, static_argnames=("cfg", "max_new_tokens",
                                             "temperature", "top_k",
                                             "top_p", "attn_impl",
                                             "layers_hook"))
def generate(params, tokens: jnp.ndarray, cfg: TransformerConfig, *,
             max_new_tokens: int = 32,
             temperature: float = 0.0,
             top_k: Optional[int] = None,
             top_p: Optional[float] = None,
             rng: Optional[jax.Array] = None,
             attn_impl: str = "auto",
             layers_hook=None) -> jnp.ndarray:
    """tokens [B, S_prompt] → [B, S_prompt + max_new_tokens].

    temperature 0.0 = greedy; otherwise sampling at the given
    temperature with optional static top_k truncation and top_p
    nucleus filtering (requires ``rng``). The KV cache is sized
    exactly S_prompt + max_new_tokens, so HBM footprint is static and
    known to the scheduler's tpu-mem accounting.
    """
    B, S = tokens.shape
    total = S + max_new_tokens
    if temperature > 0.0 and rng is None:
        raise ValueError("temperature sampling needs rng")
    rng = jax.random.PRNGKey(0) if rng is None else rng

    cache = init_cache(cfg, B, total)
    logits, cache = forward(params, tokens, cfg, cache=cache, pos_offset=0,
                            attn_impl=attn_impl, last_logit_only=True,
                            layers_hook=layers_hook)
    last = logits[:, -1]

    def pick(logits, key):
        return sample_logits(logits, key, temperature=temperature,
                             top_k=top_k, top_p=top_p)

    def step(carry, key):
        last, cache, offset = carry
        tok = pick(last, key).astype(tokens.dtype)[:, None]       # [B, 1]
        logits, cache = forward(params, tok, cfg, cache=cache,
                                pos_offset=offset, attn_impl=attn_impl,
                                layers_hook=layers_hook)
        return (logits[:, -1], cache, offset + 1), tok[:, 0]

    keys = jax.random.split(rng, max_new_tokens)
    (_, _, _), new_toks = jax.lax.scan(step, (last, cache, S), keys)
    return jnp.concatenate([tokens, new_toks.T], axis=1)
