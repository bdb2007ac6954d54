"""Paged KV cache: block-table decode must match the dense-cache
ragged decode; pool accounting reclaims blocks on evict. The slot
server over it is held in tests/test_slot_server.py, under both
families' forward functions."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpushare.models import paged
from tpushare.models import transformer as tf

CFG = tf.tiny(remat=False)


def _setup():
    params = tf.init_params(jax.random.PRNGKey(0), CFG)
    rng = np.random.default_rng(31)
    toks = jnp.asarray(rng.integers(0, CFG.vocab_size, (2, 12)))
    return params, toks


def test_paged_decode_matches_dense_ragged():
    params, toks = _setup()
    lens = [5, 9]
    bs = 4

    cache = paged.init_paged_cache(CFG, n_slots=2, n_blocks=12,
                                   block_size=bs, max_blocks_per_slot=4)
    for slot, n in enumerate(lens):
        cache = paged.admit(cache, slot, n)
        _, cache = paged.prefill_into(params, toks[slot, :n], CFG, cache,
                                      slot)

    # Dense reference: per-row prefill into a batch cache + ragged step.
    dense = tf.init_cache(CFG, 2, 16)
    for b, n in enumerate(lens):
        _, c1 = tf.forward(params, toks[b:b + 1, :n], CFG,
                           cache=tf.init_cache(CFG, 1, 16), pos_offset=0)
        dense = {k: dense[k].at[:, b:b + 1].set(c1[k]) for k in dense}
    nxt = jnp.stack([toks[0, 5:6], toks[1, 9:10]])
    want, _ = tf.forward(params, nxt, CFG, cache=dense,
                         pos_offset=jnp.asarray(lens))

    for slot in range(2):
        cache = paged.grow_if_needed(cache, slot)
    got, cache = paged.paged_decode_step(params, nxt, CFG, cache)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_array_equal(np.asarray(cache.lengths),
                                  np.asarray([6, 10]))


def test_multi_step_decode_matches_dense():
    params, toks = _setup()
    n = 6
    bs = 4
    cache = paged.init_paged_cache(CFG, n_slots=1, n_blocks=8,
                                   block_size=bs, max_blocks_per_slot=4)
    cache = paged.admit(cache, 0, n)
    _, cache = paged.prefill_into(params, toks[0, :n], CFG, cache, 0)

    dense_cache = tf.init_cache(CFG, 1, 16)
    _, dense_cache = tf.forward(params, toks[0:1, :n], CFG,
                                cache=dense_cache, pos_offset=0)
    for i in range(n, 10):
        tok = toks[0:1, i:i + 1]
        cache = paged.grow_if_needed(cache, 0)
        got, cache = paged.paged_decode_step(params, tok, CFG, cache)
        want, dense_cache = tf.forward(params, tok, CFG, cache=dense_cache,
                                       pos_offset=i)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)


def test_multi_slot_multi_step_growth_matches_dense():
    """Module-level loop (grow_if_needed + paged_decode_step) with TWO
    slots crossing block boundaries: paged_decode_step must advance
    the host lengths mirror in lockstep with the device lengths, or
    grow_if_needed (mirror-only reads) never allocates the next block
    and positions past the boundary silently scatter into the shared
    trash block (the single-slot test above aliases that corruption
    away)."""
    params, toks = _setup()
    lens = [5, 6]
    bs = 4
    cache = paged.init_paged_cache(CFG, n_slots=2, n_blocks=12,
                                   block_size=bs, max_blocks_per_slot=4)
    dense = tf.init_cache(CFG, 2, 16)
    for slot, n in enumerate(lens):
        cache = paged.admit(cache, slot, n)
        _, cache = paged.prefill_into(params, toks[slot, :n], CFG,
                                      cache, slot)
        _, c1 = tf.forward(params, toks[slot:slot + 1, :n], CFG,
                           cache=tf.init_cache(CFG, 1, 16), pos_offset=0)
        dense = {k: dense[k].at[:, slot:slot + 1].set(c1[k])
                 for k in dense}
    pos = np.asarray(lens)
    for i in range(4):                       # both slots cross 8 = 2*bs
        nxt = jnp.stack([toks[0, 5 + i:6 + i], toks[1, 6 + i:7 + i]])
        for slot in range(2):
            cache = paged.grow_if_needed(cache, slot)
        got, cache = paged.paged_decode_step(params, nxt, CFG, cache)
        want, dense = tf.forward(params, nxt, CFG, cache=dense,
                                 pos_offset=jnp.asarray(pos))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)
        pos += 1
        np.testing.assert_array_equal(cache.host_lengths(), pos)
        np.testing.assert_array_equal(np.asarray(cache.lengths), pos)
    # Every position written so far has a real (non-trash) block.
    for slot, p in enumerate(pos):
        for bi in range((int(p) - 1) // bs + 1):
            assert cache.host_table()[slot, bi] >= 0, (slot, bi)


def test_hand_constructed_cache_lazy_mirrors_are_writable():
    """A PagedCache built without mirrors (table_np/lengths_np None)
    must lazily build WRITABLE copies — np.asarray of a jax buffer is
    a read-only view, and every host-side mutator writes in place."""
    import dataclasses
    cache = paged.init_paged_cache(CFG, n_slots=2, n_blocks=8,
                                   block_size=4)
    bare = dataclasses.replace(cache, table_np=None, lengths_np=None)
    bare = paged.admit(bare, 0, 5)           # mutates both mirrors
    assert bare.host_lengths()[0] == 5
    bare = paged.grow_if_needed(bare, 0)
    bare = paged.release(bare, 0)
    assert bare.host_lengths()[0] == 0
    assert (bare.host_table()[0] == -1).all()


def test_pool_accounting_and_reuse():
    cache = paged.init_paged_cache(CFG, n_slots=2, n_blocks=5,
                                   block_size=4, max_blocks_per_slot=2)
    assert len(cache.free) == 4          # last block is the trash block
    cache = paged.admit(cache, 0, 7)     # needs 2 blocks
    assert len(cache.free) == 2 and cache.live_blocks() == 2
    cache = paged.evict(cache, 0)
    assert len(cache.free) == 4 and cache.live_blocks() == 0


def test_pool_exhaustion_raises():
    cache = paged.init_paged_cache(CFG, n_slots=2, n_blocks=3,
                                   block_size=4, max_blocks_per_slot=2)
    cache = paged.admit(cache, 0, 7)     # takes both free blocks
    with pytest.raises(RuntimeError, match="exhausted"):
        paged.admit(cache, 1, 4)


def test_capacity_check():
    cache = paged.init_paged_cache(CFG, n_slots=1, n_blocks=8,
                                   block_size=4, max_blocks_per_slot=2)
    with pytest.raises(ValueError, match="capacity"):
        paged.admit(cache, 0, 8)  # 8+1 tokens > 2 blocks * 4


def test_inactive_slots_keep_length_and_blocks():
    """ADVICE fix: with an active mask, inactive slots' lengths stay
    fixed and their live blocks are never clobbered."""
    params, toks = _setup()
    bs = 4
    cache = paged.init_paged_cache(CFG, n_slots=2, n_blocks=12,
                                   block_size=bs, max_blocks_per_slot=4)
    for slot, n in enumerate((5, 6)):
        cache = paged.admit(cache, slot, n)
        _, cache = paged.prefill_into(params, toks[slot, :n], CFG, cache, slot)
    pool_before = np.asarray(cache.pool_k)
    slot1_blocks = [int(b) for b in cache.block_table[1] if int(b) >= 0]

    active = jnp.asarray([True, False])
    nxt = toks[:, 0:1]
    for slot in range(2):
        cache = paged.grow_if_needed(cache, slot)
    _, cache = paged.paged_decode_step(params, nxt, CFG, cache,
                                       active=active)
    assert np.asarray(cache.lengths).tolist() == [6, 6]
    # Slot 1's blocks are bit-identical after the masked step.
    after = np.asarray(cache.pool_k)
    for b in slot1_blocks:
        np.testing.assert_array_equal(after[:, b], pool_before[:, b])
