"""Int8 weight quantization: storage halves, logits stay close, greedy
decode agrees on tiny models, and the layers_hook path works through
generate()'s cached decode."""

import jax
import jax.numpy as jnp
import numpy as np

from tpushare.models import quant
from tpushare.models import transformer as tf
from tpushare.models.generate import generate

CFG = tf.tiny(remat=False)


def _setup(seed=0):
    params = tf.init_params(jax.random.PRNGKey(seed), CFG)
    rng = np.random.default_rng(1)
    toks = jnp.asarray(rng.integers(0, CFG.vocab_size, (2, 16)))
    return params, toks


def test_storage_shrinks_and_dtypes():
    params, _ = _setup()
    qp = quant.quantize_params(params, CFG)
    assert qp["layers"]["wq#q8"].dtype == jnp.int8
    assert qp["layers"]["wq#scale"].shape == (CFG.n_layers, 1,
                                              CFG.n_heads * CFG.head_dim)
    assert "wq" not in qp["layers"]
    assert qp["layers"]["ln1"].dtype == params["layers"]["ln1"].dtype
    # Layer-stack bytes shrink to ~1/4 of f32 (int8 + small scales).
    orig = quant.param_bytes({"layers": params["layers"]})
    new = quant.param_bytes({"layers": qp["layers"]})
    assert new < 0.3 * orig


def test_logits_close_to_full_precision():
    params, toks = _setup()
    ref, _ = tf.forward(params, toks, CFG)
    qp = quant.quantize_params(params, CFG)
    got, _ = quant.quantized_forward(qp, toks, CFG)
    # Per-channel int8 keeps relative logit error small; compare the
    # softmax distributions rather than raw logits.
    pr = jax.nn.softmax(ref, axis=-1)
    pq = jax.nn.softmax(got, axis=-1)
    tv = 0.5 * jnp.sum(jnp.abs(pr - pq), axis=-1)  # total variation
    assert float(jnp.max(tv)) < 0.05


def test_roundtrip_exact_for_representable_weights():
    # Weights already of the form q * s (q integer in [-127,127]) must
    # round-trip exactly through quantize/dequant.
    params, _ = _setup()
    qp = quant.quantize_params(params, CFG)
    hook = quant.dequant_hook(CFG)
    # Build an exactly-representable layer tree from the dequant view.
    layer0 = {k: v[0] for k, v in qp["layers"].items()}
    exact0 = hook(layer0)
    requant = quant.quantize_layers(
        {k: v[None] for k, v in exact0.items()})
    redeq = hook({k: v[0] for k, v in requant.items()})
    for k in exact0:
        np.testing.assert_allclose(np.asarray(exact0[k]),
                                   np.asarray(redeq[k]),
                                   rtol=1e-6, atol=1e-7)


def test_greedy_decode_through_cache_agrees():
    params, toks = _setup()
    qp = quant.quantize_params(params, CFG)
    hook = quant.dequant_hook(CFG)
    got = generate(qp, toks, CFG, max_new_tokens=8, temperature=0.0,
                   layers_hook=hook)
    want = generate(params, toks, CFG, max_new_tokens=8, temperature=0.0)
    assert got.shape == want.shape == (2, 16 + 8)
    # Int8 may flip near-tied argmaxes, but on this fixed seed the
    # greedy trajectories should agree almost everywhere — a scale/axis
    # bug in the cached path flips most of them.
    agree = float(jnp.mean((got[:, 16:] == want[:, 16:]).astype(
        jnp.float32)))
    assert agree >= 0.75, f"quantized greedy agreement {agree}"


def test_hook_is_memoized():
    # generate() jit-keys on hook identity; a fresh closure per call
    # would recompile the whole program every request.
    assert quant.dequant_hook(CFG) is quant.dequant_hook(CFG)


def test_tp_quantized_decoder_matches_single_device():
    # Int8 storage sharded over tp + per-rank dequant must reproduce
    # the single-device quantized forward exactly (fp noise only).
    from tpushare.models.serving import make_tp_decoder, sharded_cache
    from tpushare.models.transformer import init_cache
    from tpushare.parallel import make_mesh, shard_tree

    params, toks = _setup()
    qp = quant.quantize_params(params, CFG)
    ref, _ = quant.quantized_forward(
        qp, toks, CFG, cache=init_cache(CFG, 2, 24), pos_offset=0)

    mesh = make_mesh({"tp": 2}, devices=jax.devices()[:2])
    sharded = shard_tree(qp, mesh, quant.quant_param_specs(CFG))
    prefill_fn, decode_fn = make_tp_decoder(CFG, mesh, quantized=True)
    cache = sharded_cache(CFG, mesh, 2, 24)
    logits, cache = prefill_fn(sharded, toks, cache)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    # One decode step runs under the hook too.
    logits2, cache = decode_fn(sharded, toks[:, :1], cache, 16)
    assert np.isfinite(np.asarray(logits2)).all()


def test_tp_paged_decoder_quantized_runs():
    from tpushare.models.paged import admit, init_paged_cache
    from tpushare.models.serving import (make_tp_paged_decoder,
                                         paged_pool_specs)
    from tpushare.parallel import make_mesh, shard_tree

    params, _ = _setup()
    qp = quant.quantize_params(params, CFG)
    mesh = make_mesh({"tp": 2}, devices=jax.devices()[:2])
    step = make_tp_paged_decoder(CFG, mesh, block_size=8, quantized=True)
    cache = init_paged_cache(CFG, n_slots=2, n_blocks=9, block_size=8,
                             max_blocks_per_slot=3)
    for slot in range(2):
        cache = admit(cache, slot, 0)
    sharded = shard_tree(qp, mesh, quant.quant_param_specs(CFG))
    pk = shard_tree(cache.pool_k, mesh, paged_pool_specs())
    pv = shard_tree(cache.pool_v, mesh, paged_pool_specs())
    toks = jnp.array([[3], [5]], jnp.int32)
    logits, pk, pv, lengths = step(
        sharded, toks, pk, pv, cache.block_table,
        jnp.zeros((2,), jnp.int32), jnp.ones((2,), bool))
    assert np.isfinite(np.asarray(logits)).all()
    assert list(np.asarray(lengths)) == [1, 1]


def test_quantized_self_speculation_exact():
    # Draft = int8 clone of the target: output must STILL be exactly
    # the full-precision greedy trajectory (the draft only proposes),
    # via the draft_layers_hook path.
    from tpushare.models.generate import generate
    from tpushare.models.speculative import speculative_generate

    params, toks = _setup()
    qp = quant.quantize_params(params, CFG)
    want = generate(params, toks, CFG, max_new_tokens=12, temperature=0.0)
    got = speculative_generate(
        params, qp, toks, CFG, max_new_tokens=12, gamma=4,
        draft_layers_hook=quant.dequant_hook(CFG))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_quantized_draft_sampling_runs():
    from tpushare.models.speculative import speculative_sample
    params, toks = _setup()
    qp = quant.quantize_params(params, CFG)
    out = speculative_sample(
        params, qp, toks, CFG, rng=jax.random.PRNGKey(0),
        max_new_tokens=6, gamma=3, temperature=1.0,
        draft_layers_hook=quant.dequant_hook(CFG))
    assert out.shape == (2, 16 + 6)
    assert int(jnp.max(out)) < CFG.vocab_size


def test_quantized_slot_server_serves():
    from tpushare.models.paged import PagedSlotServer

    params, _ = _setup()
    qp = quant.quantize_params(params, CFG)
    rng = np.random.default_rng(5)
    prompt = jnp.asarray(rng.integers(0, CFG.vocab_size, (7,)))

    psrv = PagedSlotServer(qp, CFG, n_slots=2, n_blocks=9, block_size=8,
                           max_blocks_per_slot=4,
                           layers_hook=quant.dequant_hook(CFG))
    pid = psrv.admit(prompt)
    ptoks = psrv.step()
    assert pid in ptoks and 0 <= ptoks[pid] < CFG.vocab_size


def test_truncated_spec_on_higher_rank_leaf_rejected():
    # A JAX-legal truncated spec (trailing axes implicitly replicated)
    # would let quant_layer_specs build the scale spec from the wrong
    # positions and silently drop sharding; with the layer tree
    # supplied for rank validation it must refuse instead.
    from jax.sharding import PartitionSpec as P
    import pytest
    layers = {"w_gate": jnp.zeros((2, 4, 8, 16))}   # rank-4 MoE stack
    with pytest.raises(ValueError, match="truncated"):
        quant.quant_layer_specs({"w_gate": P(None, "ep", None)},
                                layers=layers)
    # Full-rank spec passes and keeps ep on E / drops In.
    out = quant.quant_layer_specs(
        {"w_gate": P(None, "ep", None, "tp")}, layers=layers)
    assert tuple(out["w_gate#scale"]) == (None, "ep", None, "tp")
