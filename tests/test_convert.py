"""HF → tpushare conversion parity: tiny randomly-initialized
transformers models (no network), logits compared end-to-end."""

import numpy as np
import pytest
import jax.numpy as jnp

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

from tpushare.models import transformer as tf
from tpushare.models.convert import from_hf


def _llama_tiny(tie=False, kv_heads=2):
    cfg = transformers.LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=kv_heads, max_position_embeddings=64,
        rms_norm_eps=1e-6, rope_theta=10000.0, tie_word_embeddings=tie,
        attn_implementation="eager")
    torch.manual_seed(0)
    return transformers.LlamaForCausalLM(cfg).eval()


def _compare(model, rtol=2e-4, atol=2e-4):
    params, cfg = from_hf(model, dtype=jnp.float32)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, (2, 12))
    with torch.no_grad():
        want = model(torch.tensor(toks)).logits.float().numpy()
    got, _ = tf.forward(params, jnp.asarray(toks), cfg)
    np.testing.assert_allclose(np.asarray(got), want, rtol=rtol, atol=atol)


def test_llama_untied_logits_match():
    _compare(_llama_tiny(tie=False))


def test_llama_tied_logits_match():
    _compare(_llama_tiny(tie=True))


def test_llama_mha_no_gqa():
    _compare(_llama_tiny(kv_heads=4))


def test_config_derivation():
    model = _llama_tiny()
    _, cfg = from_hf(model)
    assert cfg.n_kv_heads == 2 and cfg.head_dim == 16
    assert cfg.act == "silu" and cfg.norm_offset == 0.0
    assert not cfg.embed_scale


def test_state_dict_input():
    model = _llama_tiny()
    params, cfg = from_hf(model.state_dict(), hf_cfg=model.config,
                          dtype=jnp.float32)
    assert params["layers"]["wq"].shape == (2, 64, 64)


def _gemma2_tiny():
    cfg = transformers.Gemma2Config(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=4, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, max_position_embeddings=64,
        rms_norm_eps=1e-6, rope_theta=10000.0, tie_word_embeddings=True,
        query_pre_attn_scalar=16, sliding_window=8,
        attn_logit_softcapping=50.0, final_logit_softcapping=30.0,
        attn_implementation="eager")
    torch.manual_seed(1)
    return transformers.Gemma2ForCausalLM(cfg).eval()


def test_gemma2_logits_match():
    # Full Gemma-2 block: sandwich norms (post-attn + pre/post-FFW),
    # alternating sliding window, softcaps, query_pre_attn_scalar.
    model = _gemma2_tiny()
    _compare(model, rtol=5e-4, atol=5e-4)


def test_gemma2_config_derivation():
    from tpushare.models.convert import config_from_hf
    cfg = config_from_hf(_gemma2_tiny().config)
    assert cfg.post_norms and cfg.alternate_sliding
    assert cfg.sliding_window == 8
    assert cfg.attn_softcap == 50.0 and cfg.final_softcap == 30.0
    assert cfg.attn_scale == 16 ** -0.5
    assert cfg.norm_offset == 1.0 and cfg.embed_scale


def test_llama3_rope_scaling_logits_match():
    # Llama-3 long-context rope scaling must be applied, not silently
    # ignored: with original_max_position_embeddings SMALLER than the
    # test sequence, the scaled and unscaled frequency tables diverge
    # within the first few positions, so this parity only passes when
    # the llama3 remap is implemented faithfully.
    cfg = transformers.LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, max_position_embeddings=64,
        rms_norm_eps=1e-6, rope_theta=10000.0,
        rope_scaling={"rope_type": "llama3", "factor": 8.0,
                      "low_freq_factor": 1.0, "high_freq_factor": 4.0,
                      "original_max_position_embeddings": 8},
        attn_implementation="eager")
    torch.manual_seed(0)
    model = transformers.LlamaForCausalLM(cfg).eval()
    params, tcfg = from_hf(model, dtype=jnp.float32)
    assert tcfg.rope_scaling == (8.0, 1.0, 4.0, 8.0)
    _compare(model)


def test_unknown_rope_scaling_rejected():
    cfg = transformers.LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=1, num_attention_heads=4,
        num_key_value_heads=2,
        rope_scaling={"rope_type": "yarn", "factor": 2.0},
        attn_implementation="eager")
    torch.manual_seed(0)
    model = transformers.LlamaForCausalLM(cfg).eval()
    with pytest.raises(NotImplementedError, match="yarn"):
        from_hf(model, dtype=jnp.float32)


def _mixtral_tiny(sliding_window=None, **kw):
    cfg = transformers.MixtralConfig(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, num_local_experts=4,
        num_experts_per_tok=2, max_position_embeddings=64,
        sliding_window=sliding_window, rms_norm_eps=1e-6,
        rope_theta=10000.0, attn_implementation="eager", **kw)
    torch.manual_seed(0)
    return transformers.MixtralForCausalLM(cfg).eval()


def test_mixtral_logits_match():
    from tpushare.models import moe
    from tpushare.models.convert import moe_from_hf
    model = _mixtral_tiny()
    params, cfg = moe_from_hf(model, dtype=jnp.float32)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, (2, 12))
    with torch.no_grad():
        want = model(torch.tensor(toks)).logits.float().numpy()
    got, _ = moe.forward(params, jnp.asarray(toks), cfg)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4,
                               atol=2e-4)


def test_mixtral_config_and_routing_knobs():
    from tpushare.models.convert import moe_config_from_hf
    model = _mixtral_tiny()
    cfg = moe_config_from_hf(model.config)
    assert cfg.n_experts == 4 and cfg.top_k == 2
    assert cfg.n_kv_heads == 2 and cfg.head_dim == 16
    assert cfg.routing == "psum" and cfg.act == "silu"


def test_mixtral_generate_and_serving_compose():
    # Converted params run the whole inference stack: cached generate
    # equals full-recompute argmax, and the slot server streams it.
    from tpushare.models import moe
    from tpushare.models.convert import moe_from_hf
    model = _mixtral_tiny()
    params, cfg = moe_from_hf(model, dtype=jnp.float32)
    prompt = jnp.asarray([[5, 17, 90, 3, 41]])
    out = moe.generate(params, prompt, cfg, max_new_tokens=6)
    assert out.shape == (1, 11)
    from tpushare.models.paged import PagedSlotServer
    srv = PagedSlotServer(params, cfg, n_slots=2, n_blocks=8,
                          block_size=4, forward_fn=moe.paged_forward)
    s = srv.admit(prompt[0])
    got = [int(srv.last_token[s, 0])]
    for _ in range(5):
        got.append(srv.step()[s])
    assert got == [int(t) for t in out[0, 5:]]


def test_mixtral_sliding_window_rejected():
    from tpushare.models.convert import moe_from_hf
    model = _mixtral_tiny(sliding_window=16)
    with pytest.raises(NotImplementedError, match="sliding_window"):
        moe_from_hf(model, dtype=jnp.float32)


def test_mixtral_nonsilu_act_rejected():
    from tpushare.models.convert import moe_config_from_hf
    model = _mixtral_tiny(hidden_act="relu")
    with pytest.raises(NotImplementedError, match="hidden_act"):
        moe_config_from_hf(model.config)
