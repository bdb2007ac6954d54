"""Multi-LoRA serving: per-slot adapters in one batched decode
(forward's _mlora activation-path delta + PagedSlotServer integration)."""

import jax
import jax.numpy as jnp
import numpy as np

from tpushare.models import lora
from tpushare.models import transformer as tf
from tpushare.models.generate import generate
from tpushare.models.paged import PagedSlotServer

CFG = tf.tiny(remat=False)


def _teach(params, target_token, seed, steps=40):
    """Train an adapter that emits ``target_token`` after the training
    prompt's first token (and after itself). Returns (adapter, loss,
    in-distribution prompt) — generalization to arbitrary prompts is
    not what a 40-step toy run buys, so tests serve the prompt the
    adapter was actually taught on."""
    rng = np.random.default_rng(seed)
    prompts = jnp.asarray(rng.integers(0, CFG.vocab_size, (4, 10)))
    tokens = jnp.concatenate(
        [prompts[:, :1], jnp.full_like(prompts, target_token)], axis=1)
    ad = lora.init_lora(jax.random.PRNGKey(seed), CFG, rank=4)
    for _ in range(steps):
        ad, loss = lora.lora_train_step(params, ad, tokens, CFG, lr=0.3)
    return ad, float(loss), prompts[0, :1]


def test_activation_delta_matches_weight_merge():
    params = tf.init_params(jax.random.PRNGKey(0), CFG)
    ad, _, _ = _teach(params, 7, seed=1, steps=5)
    bank = lora.stack_adapters([ad])
    toks = jnp.asarray(np.random.default_rng(2).integers(
        0, CFG.vocab_size, (2, 9)))
    got = tf.forward(lora.multi_lora_params(params, bank), toks, CFG,
                     mlora_idx=jnp.zeros((2,), jnp.int32))[0]
    want = tf.forward(lora.merge_lora(params, ad), toks, CFG)[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    # idx -1 = base model, exactly.
    base = tf.forward(params, toks, CFG)[0]
    off = tf.forward(lora.multi_lora_params(params, bank), toks, CFG,
                     mlora_idx=jnp.full((2,), -1, jnp.int32))[0]
    np.testing.assert_array_equal(np.asarray(base), np.asarray(off))


def test_server_serves_three_tenants_one_batch():
    """PagedSlotServer(multi_lora=...): two adapters and the base
    model in ONE batched decode; the base slot's stream is
    generate()'s."""
    params = tf.init_params(jax.random.PRNGKey(3), CFG)
    ad7, l7, p7 = _teach(params, 7, seed=11)
    ad42, l42, p42 = _teach(params, 42, seed=13)
    assert l7 < 0.5 and l42 < 0.5
    bank = lora.stack_adapters([ad7, ad42])
    rng = np.random.default_rng(5)
    base_prompt = jnp.asarray(rng.integers(0, CFG.vocab_size, 8))
    srv = PagedSlotServer(params, CFG, n_slots=3, n_blocks=32,
                          block_size=8, max_blocks_per_slot=4,
                          multi_lora=bank)
    s0 = srv.admit(p7, adapter=0)
    s1 = srv.admit(p42, adapter=1)
    s2 = srv.admit(base_prompt)                # base model
    streams = {s0: [], s1: [], s2: [int(srv.last_token[s2, 0])]}
    for _ in range(4):
        for s, t in srv.step().items():
            streams[s].append(t)
    # Each tenant follows ITS adapter inside one batched decode.
    assert streams[s0].count(7) >= 3, streams[s0]
    assert streams[s1].count(42) >= 3, streams[s1]
    # The base slot matches the plain model exactly.
    ref = generate(params, base_prompt[None, :], CFG, max_new_tokens=5)
    assert streams[s2] == [int(t) for t in ref[0, 8:]]
    import pytest
    with pytest.raises(ValueError, match="out of range"):
        srv.admit(p7, adapter=5)


def test_prefix_cache_isolated_per_adapter():
    """Adapters change the KV a prompt produces (wv targets) — the
    SAME tokens under DIFFERENT adapters must never share blocks,
    while the same adapter still hits."""
    params = tf.init_params(jax.random.PRNGKey(5), CFG)
    ad, _, _ = _teach(params, 9, seed=19, steps=10)
    bank = lora.stack_adapters([ad, ad])
    prompt = jnp.asarray(np.random.default_rng(21).integers(
        0, CFG.vocab_size, 16))
    srv = PagedSlotServer(params, CFG, n_slots=3, n_blocks=48,
                          block_size=8, max_blocks_per_slot=4,
                          prefix_cache=True, multi_lora=bank)
    srv.admit(prompt, adapter=0)
    assert srv.last_cached_len == 0
    srv.admit(prompt, adapter=1)           # different adapter: MISS
    assert srv.last_cached_len == 0
    srv.evict(0)
    srv.admit(prompt, adapter=0)           # same adapter: HIT
    assert srv.last_cached_len == 8


def test_triple_composition_prefix_kvq_multilora():
    """The whole serving stack in ONE server: paged pool + int8 KV +
    prefix caching + per-slot adapters. Hits stay adapter-isolated,
    storage stays int8, and a taught adapter still emits its task
    token through the composed pipeline."""
    params = tf.init_params(jax.random.PRNGKey(3), CFG)
    ad7, _, p7 = _teach(params, 7, seed=11)
    bank = lora.stack_adapters([ad7, ad7])
    prompt = jnp.asarray(np.concatenate(
        [np.asarray(p7), np.random.default_rng(29).integers(
            0, CFG.vocab_size, 15)]))        # 16 tokens = 2 full blocks
    srv = PagedSlotServer(params, CFG, n_slots=2, n_blocks=48,
                          block_size=8, max_blocks_per_slot=4,
                          prefix_cache=True, kv_quant=True,
                          multi_lora=bank)
    assert srv.cache.pool_k.dtype == jnp.int8
    s0 = srv.admit(prompt, adapter=0)
    assert srv.last_cached_len == 0
    toks0 = [srv.step()[s0] for _ in range(3)]
    srv.evict(s0)
    s1 = srv.admit(prompt, adapter=0)        # same adapter: HIT
    assert srv.last_cached_len == 8
    toks1 = [srv.step()[s1] for _ in range(3)]
    # Bit-identical int8 reuse: same trajectory after the hit.
    assert toks0 == toks1
    srv.admit(prompt, adapter=1)             # other adapter: MISS
    assert srv.last_cached_len == 0
    # The taught behavior survives the composed pipeline: a 1-token
    # prompt (the training prompt) decodes to the task token.
    srv2 = PagedSlotServer(params, CFG, n_slots=1, n_blocks=16,
                           block_size=8, max_blocks_per_slot=4,
                           prefix_cache=True, kv_quant=True,
                           multi_lora=bank)
    s = srv2.admit(p7, adapter=0)
    stream = [srv2.step()[s] for _ in range(3)]
    assert stream.count(7) >= 2, stream


def test_adapter_slot_resets_on_evict():
    params = tf.init_params(jax.random.PRNGKey(4), CFG)
    ad, _, _ = _teach(params, 9, seed=17, steps=10)
    bank = lora.stack_adapters([ad])
    srv = PagedSlotServer(params, CFG, n_slots=2, n_blocks=16,
                          block_size=8, multi_lora=bank)
    p = jnp.asarray(np.random.default_rng(7).integers(
        0, CFG.vocab_size, 6))
    s = srv.admit(p, adapter=0)
    assert srv._ml.adapter_of(s) == 0
    srv.evict(s)
    assert srv._ml.adapter_of(s) == -1


def test_admit_rejects_out_of_range_adapter():
    """A clamped device gather would silently serve ANOTHER tenant's
    adapter — admit must fail loud host-side instead."""
    import pytest
    params = tf.init_params(jax.random.PRNGKey(6), CFG)
    bank = lora.stack_adapters(
        [lora.init_lora(jax.random.PRNGKey(8), CFG, 2)] * 2)
    srv = PagedSlotServer(params, CFG, n_slots=2, n_blocks=16,
                          block_size=8, multi_lora=bank)
    p = jnp.asarray(np.random.default_rng(9).integers(
        0, CFG.vocab_size, 5))
    with pytest.raises(ValueError, match="out of range"):
        srv.admit(p, adapter=2)
    with pytest.raises(ValueError, match="out of range"):
        srv.admit(p, adapter=-2)
    plain = PagedSlotServer(params, CFG, n_slots=2, n_blocks=16,
                            block_size=8)
    with pytest.raises(ValueError, match="not set"):
        plain.admit(p, adapter=0)


def test_stack_adapters_validates():
    params = tf.init_params(jax.random.PRNGKey(5), CFG)
    a1 = lora.init_lora(jax.random.PRNGKey(6), CFG, 2,
                        targets=("wq", "wv"))
    a2 = lora.init_lora(jax.random.PRNGKey(7), CFG, 2, targets=("wq",))
    import pytest
    with pytest.raises(ValueError, match="disagree"):
        lora.stack_adapters([a1, a2])
    with pytest.raises(ValueError, match="at least one"):
        lora.stack_adapters([])
    bank = lora.stack_adapters([a1, a1])
    assert bank["wq"]["a"].shape[1] == 2       # [L, NA, d, r]
