"""Int8 KV cache (quant.init_cache_q8 + forward's kvq paths).

Pins: requant-idempotence (unwritten rows never drift), prefill+decode
parity against the full-precision cache within int8 tolerance, the
~2x/4x storage shrink, and PagedSlotServer(kv_quant=True) end-to-end.
"""

import jax
import jax.numpy as jnp
import numpy as np

from tpushare.models import quant
from tpushare.models import transformer as tf
from tpushare.models.paged import PagedSlotServer

CFG = tf.tiny(remat=False)


def test_requant_roundtrip_is_identity():
    rng = np.random.default_rng(3)
    rows = jnp.asarray(rng.normal(size=(4, 7, 2, 16)), jnp.float32)
    q, s = quant.kv_quantize(rows)
    q2, s2 = quant.kv_quantize(quant.kv_dequantize(q, s, jnp.float32))
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q2))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(s2))


def test_prefill_decode_parity_within_int8_tolerance():
    params = tf.init_params(jax.random.PRNGKey(0), CFG)
    rng = np.random.default_rng(11)
    toks = jnp.asarray(rng.integers(0, CFG.vocab_size, (2, 9)))
    M = 16

    ref_logits, ref_cache = tf.forward(
        params, toks, CFG, cache=tf.init_cache(CFG, 2, M), pos_offset=0)
    q_logits, q_cache = tf.forward(
        params, toks, CFG, cache=quant.init_cache_q8(CFG, 2, M),
        pos_offset=0)
    # Prefill logits: ~1% relative error budget for per-row int8 KV.
    scale = float(jnp.abs(ref_logits).max())
    assert float(jnp.abs(q_logits - ref_logits).max()) < 0.02 * scale

    # Ragged decode steps stay in tolerance and in agreement (greedy).
    pos = jnp.asarray([9, 9], jnp.int32)
    nxt = jnp.argmax(ref_logits[:, -1], axis=-1)[:, None]
    for _ in range(4):
        r_log, ref_cache = tf.forward(params, nxt, CFG, cache=ref_cache,
                                      pos_offset=pos)
        q_log, q_cache = tf.forward(params, nxt, CFG, cache=q_cache,
                                    pos_offset=pos)
        assert (float(jnp.abs(q_log - r_log).max())
                < 0.02 * float(jnp.abs(r_log).max()))
        r_tok = jnp.argmax(r_log[:, 0], axis=-1)
        q_tok = jnp.argmax(q_log[:, 0], axis=-1)
        np.testing.assert_array_equal(np.asarray(r_tok), np.asarray(q_tok))
        nxt = r_tok[:, None]
        pos = pos + 1


def test_unwritten_rows_never_drift():
    params = tf.init_params(jax.random.PRNGKey(1), CFG)
    rng = np.random.default_rng(5)
    toks = jnp.asarray(rng.integers(0, CFG.vocab_size, (1, 6)))
    M = 16
    _, cache = tf.forward(params, toks, CFG,
                          cache=quant.init_cache_q8(CFG, 1, M),
                          pos_offset=0)
    frozen_k = np.asarray(cache["k"][:, :, :6]).copy()
    frozen_s = np.asarray(cache["k_scale"][:, :, :6]).copy()
    pos = jnp.asarray([6], jnp.int32)
    nxt = jnp.zeros((1, 1), jnp.int32)
    for i in range(3):
        _, cache = tf.forward(params, nxt, CFG, cache=cache,
                              pos_offset=pos + i)
    np.testing.assert_array_equal(np.asarray(cache["k"][:, :, :6]),
                                  frozen_k)
    np.testing.assert_array_equal(np.asarray(cache["k_scale"][:, :, :6]),
                                  frozen_s)


def test_storage_shrinks():
    dense = tf.init_cache(CFG, 4, 64)          # tiny cfg is f32
    q8 = quant.init_cache_q8(CFG, 4, 64)
    dense_b = sum(x.nbytes for x in dense.values())
    q8_b = sum(x.nbytes for x in q8.values())
    # int8 rows + f32/Dh scales: ~(1/itemsize + 4/Dh) of dense.
    assert q8_b < 0.45 * dense_b


def test_paged_kv_quant_matches_dense_kv_quant():
    """Paged int8 pool decode == dense int8 ragged decode: identical
    quantization (same rows, same scales) means identical logits —
    exact equality, not tolerance."""
    from tpushare.models import paged
    params = tf.init_params(jax.random.PRNGKey(3), CFG)
    rng = np.random.default_rng(31)
    toks = jnp.asarray(rng.integers(0, CFG.vocab_size, (1, 6)))
    bs = 4

    cache = paged.init_paged_cache(CFG, n_slots=1, n_blocks=8,
                                   block_size=bs, max_blocks_per_slot=4,
                                   kv_quant=True)
    assert cache.pool_k.dtype == jnp.int8
    cache = paged.admit(cache, 0, 6)
    _, cache = paged.prefill_into(params, toks[0], CFG, cache, 0)

    dense = quant.init_cache_q8(CFG, 1, 16)
    dense_log, dense = tf.forward(params, toks, CFG, cache=dense,
                                  pos_offset=0)
    nxt = jnp.argmax(dense_log[0, 5])[None, None].astype(jnp.int32)
    pos = jnp.asarray([6], jnp.int32)
    for i in range(3):
        cache = paged.grow_if_needed(cache, 0)
        p_log, cache = paged.paged_decode_step(params, nxt, CFG, cache)
        d_log, dense = tf.forward(params, nxt, CFG, cache=dense,
                                  pos_offset=pos + i)
        np.testing.assert_allclose(np.asarray(p_log[:, 0]),
                                   np.asarray(d_log[:, 0]),
                                   rtol=2e-4, atol=2e-4)
        nxt = jnp.argmax(p_log[:, 0], axis=-1)[:, None].astype(jnp.int32)


def test_prefix_cache_composes_with_kv_quant():
    """Shared prefix blocks carry their scale rows: a hit under
    kv_quant reuses int8 KV bit-identically."""
    from tpushare.models import paged
    params = tf.init_params(jax.random.PRNGKey(4), CFG)
    rng = np.random.default_rng(37)
    system = rng.integers(0, CFG.vocab_size, 8)
    p1 = jnp.asarray(np.concatenate([system,
                                     rng.integers(0, CFG.vocab_size, 4)]))
    p2 = jnp.asarray(np.concatenate([system,
                                     rng.integers(0, CFG.vocab_size, 5)]))
    srv = paged.PagedSlotServer(params, CFG, n_slots=2, n_blocks=24,
                                block_size=4, max_blocks_per_slot=8,
                                prefix_cache=True, kv_quant=True)
    s1 = srv.admit(p1)
    s2 = srv.admit(p2)
    assert srv.last_cached_len == 8
    # Shared block's int8 rows and scales are the same physical pool
    # entries (table points both slots at them).
    b1 = np.asarray(srv.cache.block_table[s1, :2])
    b2 = np.asarray(srv.cache.block_table[s2, :2])
    np.testing.assert_array_equal(b1, b2)
    # Parity vs an uncached kv_quant server — same quantized storage,
    # so trajectories match exactly.
    ref = paged.PagedSlotServer(params, CFG, n_slots=2, n_blocks=24,
                                block_size=4, max_blocks_per_slot=8,
                                kv_quant=True)
    r1, r2 = ref.admit(p1), ref.admit(p2)
    for _ in range(4):
        a = srv.step()
        b = ref.step()
        assert (a[s1], a[s2]) == (b[r1], b[r2])


def test_slot_server_kv_quant_end_to_end():
    params = tf.init_params(jax.random.PRNGKey(2), CFG)
    rng = np.random.default_rng(23)
    prompts = [jnp.asarray(rng.integers(0, CFG.vocab_size, n))
               for n in (7, 12)]
    outs = {}
    for kvq in (False, True):
        srv = PagedSlotServer(params, CFG, n_slots=2, n_blocks=24,
                              block_size=4, kv_quant=kvq)
        slots = [srv.admit(p) for p in prompts]
        toks = {s: [] for s in slots}
        for _ in range(5):
            for s, t in srv.step().items():
                toks[s].append(t)
        outs[kvq] = [toks[s] for s in slots]
        if kvq:
            assert srv.cache.pool_k.dtype == jnp.int8
            assert srv.cache.pool_k_scale is not None
    # Chunked admit (the q8 admission row crosses multiple forward()
    # calls — previously-quantized rows coexist with each chunk's new
    # writes): first decode step must match the unchunked q8 admit.
    chunked = PagedSlotServer(params, CFG, n_slots=2, n_blocks=24,
                              block_size=4, kv_quant=True)
    c_slots = []
    for p in prompts:
        c_slots.append(chunked.admit_start(p, chunk_tokens=4))
        while chunked.admit_step(c_slots[-1]) is None:
            pass
    c_first = chunked.step()
    for i, cs in enumerate(c_slots):
        assert outs[True][i][0] == c_first[cs]

    # Free-running greedy trajectories under lossy KV legitimately
    # diverge once a near-tie flips and the error compounds; the
    # per-step logit tolerance is pinned by the parity test above.
    # What IS guaranteed here: the first decode step (error budget
    # straight after prefill) matches, and every token is valid.
    for a, b in zip(outs[False], outs[True]):
        assert a[0] == b[0]
        assert all(0 <= t < CFG.vocab_size for t in b)
