"""Transformer LM: forward shapes, cache/decode equivalence, parity of
the shard_map SPMD path with single-device execution (8-dev CPU mesh)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpushare.models import transformer as tf
from tpushare.models.training import lm_loss, make_spmd_train_step, sgd_train_step
from tpushare.parallel import make_mesh, shard_tree

CFG = tf.tiny(remat=False)


def _params(cfg=CFG, seed=0):
    return tf.init_params(jax.random.PRNGKey(seed), cfg)


def _tokens(cfg=CFG, batch=2, seq=16, seed=1):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)))


class TestForward:
    def test_logits_shape_and_dtype(self):
        params = _params()
        logits, cache = tf.forward(params, _tokens(), CFG)
        assert logits.shape == (2, 16, CFG.vocab_size)
        assert logits.dtype == jnp.float32
        assert cache is None

    def test_causality(self):
        # Changing a future token must not change earlier logits.
        params = _params()
        toks = _tokens()
        logits1, _ = tf.forward(params, toks, CFG)
        toks2 = toks.at[:, -1].set((toks[:, -1] + 1) % CFG.vocab_size)
        logits2, _ = tf.forward(params, toks2, CFG)
        np.testing.assert_allclose(np.asarray(logits1[:, :-1]),
                                   np.asarray(logits2[:, :-1]),
                                   rtol=1e-5, atol=1e-5)

    def test_remat_matches_no_remat(self):
        cfg_r = tf.tiny(remat=True)
        params = _params(cfg_r)
        logits_r, _ = tf.forward(params, _tokens(cfg_r), cfg_r)
        logits, _ = tf.forward(params, _tokens(CFG), CFG)
        np.testing.assert_allclose(np.asarray(logits_r), np.asarray(logits),
                                   rtol=1e-5, atol=1e-5)

    def test_untied_unembed(self):
        cfg = tf.tiny(tie_embeddings=False)
        params = _params(cfg)
        assert "unembed" in params
        logits, _ = tf.forward(params, _tokens(cfg), cfg)
        assert logits.shape[-1] == cfg.vocab_size

    def test_gemma_style_options(self):
        cfg = tf.tiny(norm_offset=1.0, embed_scale=True, act="gelu")
        params = _params(cfg)
        logits, _ = tf.forward(params, _tokens(cfg), cfg)
        assert np.isfinite(np.asarray(logits)).all()

    def test_preset_param_counts(self):
        # Geometry sanity: presets land near their nameplate sizes.
        assert 2.0e9 < tf.gemma_2b().num_params() < 3.2e9
        assert 7.0e9 < tf.llama3_8b().num_params() < 9.0e9


class TestDecode:
    def test_prefill_then_decode_matches_full_forward(self):
        params = _params()
        toks = _tokens(seq=12)
        full_logits, _ = tf.forward(params, toks, CFG)

        logits_p, cache = tf.prefill(params, toks[:, :8], CFG, max_len=16)
        np.testing.assert_allclose(np.asarray(logits_p),
                                   np.asarray(full_logits[:, :8]),
                                   rtol=2e-4, atol=2e-4)
        for i in range(8, 12):
            logits_d, cache = tf.decode_step(params, toks[:, i:i + 1], CFG,
                                             cache, i)
            np.testing.assert_allclose(np.asarray(logits_d[:, 0]),
                                       np.asarray(full_logits[:, i]),
                                       rtol=2e-4, atol=2e-4)

    def test_decode_no_recompile_across_offsets(self):
        params = _params()
        cache = tf.init_cache(CFG, 1, 8)
        step = jax.jit(
            lambda p, t, c, off: tf.forward(p, t, CFG, cache=c,
                                            pos_offset=off))
        tok = jnp.zeros((1, 1), jnp.int32)
        _, cache = step(params, tok, cache, 0)
        n0 = step._cache_size()
        _, cache = step(params, tok, cache, 1)
        _, cache = step(params, tok, cache, 5)
        assert step._cache_size() == n0


class TestTraining:
    def test_loss_decreases(self):
        params = _params()
        toks = _tokens(seq=17)
        loss0 = lm_loss(params, toks, CFG)
        for _ in range(3):
            params, loss = sgd_train_step(params, toks, CFG, lr=0.5)
        assert float(loss) < float(loss0)

    def test_spmd_step_matches_single_device(self):
        # dp=2, sp=2, tp=2 over the 8 virtual CPU devices; one step of
        # the fully-manual SPMD path must match the single-device step.
        cfg = tf.tiny(remat=False)
        mesh = make_mesh({"dp": 2, "sp": 2, "tp": 2})
        params = _params(cfg)
        toks = _tokens(cfg, batch=4, seq=17)  # S=16 divisible by sp=2

        spmd_step = make_spmd_train_step(cfg, mesh, lr=0.1)
        sharded = shard_tree(params, mesh, tf.param_specs(cfg))
        new_params, loss = spmd_step(sharded, toks)
        assert np.isfinite(float(loss))
        # Params actually changed and stayed finite.
        delta = jax.tree.reduce(
            lambda a, b: a + b,
            jax.tree.map(lambda a, b: float(jnp.abs(a - b).sum()),
                         new_params, params))
        assert delta > 0

    def test_spmd_sp_windowed_softcap_matches_single_device(self):
        # Gemma-2-style alternating sliding windows + tanh softcap
        # under REAL sequence parallelism: the ring path must apply
        # both (pre-r3 it silently dropped softcap and raised on
        # windows). Exact step parity vs single device.
        cfg = tf.tiny(remat=False, n_layers=4, sliding_window=8,
                      alternate_sliding=True, attn_softcap=30.0)
        mesh = make_mesh({"dp": 2, "sp": 2, "tp": 2})
        params = _params(cfg)
        toks = _tokens(cfg, batch=4, seq=33)  # S=32, 16/shard
        ref_params, ref_loss = sgd_train_step(params, toks, cfg, lr=0.1)
        spmd_step = make_spmd_train_step(cfg, mesh, lr=0.1)
        sharded = shard_tree(params, mesh, tf.param_specs(cfg))
        new_params, loss = spmd_step(sharded, toks)
        np.testing.assert_allclose(float(loss), float(ref_loss),
                                   rtol=1e-5, atol=1e-6)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5),
            new_params, ref_params)

    def test_dp_tp_step_exactly_matches_single_device(self):
        # sp=1 ⇒ no shard-boundary approximation: the dp×tp SPMD loss
        # AND the updated params must equal single-device exactly.
        # (Loss-only parity once masked a dp-fold grad double-count —
        # the vma transpose already psums replicated-param cotangents.)
        cfg = tf.tiny(remat=False)
        mesh = make_mesh({"dp": 4, "tp": 2})
        params = _params(cfg)
        toks = _tokens(cfg, batch=4, seq=16)
        ref_params, ref_loss = sgd_train_step(params, toks, cfg, lr=0.1)
        spmd_step = make_spmd_train_step(cfg, mesh, lr=0.1)
        sharded = shard_tree(params, mesh, tf.param_specs(cfg))
        new_params, loss = spmd_step(sharded, toks)
        np.testing.assert_allclose(float(loss), float(ref_loss),
                                   rtol=1e-5, atol=1e-6)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=5e-4, atol=1e-5),
            new_params, ref_params)

    def test_sp_step_exactly_matches_single_device(self):
        # sp=4 with ring attention and the outside-the-shard_map
        # next-token shift: loss AND updated params must match
        # single-device exactly (inputs/targets are aligned per shard).
        cfg = tf.tiny(remat=False)
        mesh = make_mesh({"sp": 4, "tp": -1})
        assert mesh.shape["tp"] == 2
        params = _params(cfg)
        toks = _tokens(cfg, batch=2, seq=17)  # S=16 divisible by sp
        ref_params, ref_loss = sgd_train_step(params, toks, cfg, lr=0.1)
        spmd_step = make_spmd_train_step(cfg, mesh, lr=0.1)
        sharded = shard_tree(params, mesh, tf.param_specs(cfg))
        new_params, loss = spmd_step(sharded, toks)
        np.testing.assert_allclose(float(loss), float(ref_loss),
                                   rtol=1e-5, atol=1e-6)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=5e-4, atol=1e-5),
            new_params, ref_params)


class TestRaggedDecode:
    def test_per_sequence_offsets_match_scalar_decodes(self):
        # Two sequences at DIFFERENT positions decode in one batched
        # step; each row must equal its own scalar-offset decode.
        params = _params()
        toks = _tokens(batch=2, seq=12)
        # Prefill row 0 with 6 tokens, row 1 with 9, in separate caches,
        # then merge into one batch cache.
        cache = tf.init_cache(CFG, 2, 16)
        lens = [6, 9]
        for b, n in enumerate(lens):
            _, c1 = tf.forward(
                {k: v for k, v in params.items()},
                toks[b:b + 1, :n], CFG,
                cache=tf.init_cache(CFG, 1, 16), pos_offset=0)
            cache = {kk: cache[kk].at[:, b:b + 1].set(c1[kk])
                     for kk in cache}

        offsets = jnp.asarray(lens)
        next_tok = jnp.stack([toks[0, 6:7], toks[1, 9:10]])    # [2, 1]
        logits_b, cache_b = tf.forward(params, next_tok, CFG, cache=cache,
                                       pos_offset=offsets)

        for b, n in enumerate(lens):
            _, c1 = tf.forward(params, toks[b:b + 1, :n], CFG,
                               cache=tf.init_cache(CFG, 1, 16), pos_offset=0)
            logits_s, _ = tf.forward(params, toks[b:b + 1, n:n + 1], CFG,
                                     cache=c1, pos_offset=n)
            np.testing.assert_allclose(np.asarray(logits_b[b]),
                                       np.asarray(logits_s[0]),
                                       rtol=2e-4, atol=2e-4)

    def test_ragged_multi_token_matches_scalar_prefill(self):
        """The fused-tick branch: ragged multi-token over dense rows
        (row b's tokens at pos_b..pos_b+S-1) must score and write KV
        exactly like each row's own scalar-offset prefill
        continuation."""
        params = _params()
        toks = _tokens(batch=2, seq=12)
        lens = [6, 3]
        cache = tf.init_cache(CFG, 2, 16)
        for b, n in enumerate(lens):
            _, c1 = tf.forward(params, toks[b:b + 1, :n], CFG,
                               cache=tf.init_cache(CFG, 1, 16),
                               pos_offset=0)
            cache = {kk: cache[kk].at[:, b:b + 1].set(c1[kk])
                     for kk in cache}
        block = jnp.stack([toks[0, 6:10], toks[1, 3:7]])       # [2, 4]
        logits_b, cache_b = tf.forward(params, block, CFG, cache=cache,
                                       pos_offset=jnp.asarray(lens))
        for b, n in enumerate(lens):
            _, c1 = tf.forward(params, toks[b:b + 1, :n], CFG,
                               cache=tf.init_cache(CFG, 1, 16),
                               pos_offset=0)
            logits_s, c1 = tf.forward(params, toks[b:b + 1, n:n + 4],
                                      CFG, cache=c1, pos_offset=n)
            np.testing.assert_allclose(np.asarray(logits_b[b]),
                                       np.asarray(logits_s[0]),
                                       rtol=2e-4, atol=2e-4)
            for kk in cache_b:
                np.testing.assert_allclose(
                    np.asarray(cache_b[kk][:, b, :n + 4]),
                    np.asarray(c1[kk][:, 0, :n + 4]),
                    rtol=2e-4, atol=2e-4)

    def test_ragged_multi_token_drops_out_of_range_writes(self):
        """Writes past max_len must VANISH: a row near capacity must
        not corrupt its last live position. This pins the drop
        semantics themselves (jax scatter drops out-of-bounds by
        default, but dynamic_update_slice clamps — the fused tick
        must not silently depend on which primitive a refactor
        picks): position 7 is compared against a reference that only
        writes in range, which a clamped duplicate write (position
        8/9's KV at its own rotary phase) would break."""
        params = _params()
        cache = tf.init_cache(CFG, 2, 8)
        toks = _tokens(batch=2, seq=4)
        _, cache = tf.forward(params, toks, CFG, cache=cache,
                              pos_offset=0)
        before = np.asarray(cache["k"][:, 0])
        # Row 0 writes at 6..9: 6, 7 are real writes; 8, 9 must vanish.
        _, cache2 = tf.forward(params, toks, CFG, cache=cache,
                               pos_offset=jnp.asarray([6, 0]))
        after = np.asarray(cache2["k"][:, 0])
        np.testing.assert_array_equal(after[:, :6], before[:, :6])
        # Positions 6..7 must hold exactly what an in-range-only write
        # of the same first two tokens produces (KV at position p
        # depends only on tokens <= p, so the 2-token forward is a
        # bit-exact oracle). Under clamp mode position 7 instead holds
        # a duplicate write from position 8 or 9 — this catches it.
        _, ref = tf.forward(params, toks[:, :2], CFG, cache=cache,
                            pos_offset=jnp.asarray([6, 0]))
        np.testing.assert_array_equal(after[:, 6:8],
                                      np.asarray(ref["k"][:, 0, 6:8]))


class TestGemma2Features:
    def test_sliding_window_masks_distant_tokens(self):
        # With window=4, changing token 0 must not affect logits at
        # position >= 5 (outside every window); with global attention
        # it must.
        cfg = tf.tiny(sliding_window=4, remat=False)
        params = _params(cfg)
        toks = _tokens(cfg, seq=12)
        toks2 = toks.at[:, 0].set((toks[:, 0] + 1) % cfg.vocab_size)
        l1, _ = tf.forward(params, toks, cfg)
        l2, _ = tf.forward(params, toks2, cfg)
        np.testing.assert_allclose(np.asarray(l1[:, 8:]),
                                   np.asarray(l2[:, 8:]),
                                   rtol=1e-5, atol=1e-5)
        cfg_g = tf.tiny(remat=False)
        g1, _ = tf.forward(params, toks, cfg_g)
        g2, _ = tf.forward(params, toks2, cfg_g)
        assert float(jnp.abs(g1[:, 8:] - g2[:, 8:]).max()) > 1e-6

    def test_alternating_layers_leak_through_global(self):
        # With alternating local/global, layer 1 is global: early
        # tokens DO influence late positions even with a tiny window.
        cfg = tf.tiny(sliding_window=2, alternate_sliding=True,
                      remat=False)
        params = _params(cfg)
        toks = _tokens(cfg, seq=12)
        toks2 = toks.at[:, 0].set((toks[:, 0] + 1) % cfg.vocab_size)
        l1, _ = tf.forward(params, toks, cfg)
        l2, _ = tf.forward(params, toks2, cfg)
        assert float(jnp.abs(l1[:, 8:] - l2[:, 8:]).max()) > 1e-6

    def test_windowed_decode_matches_full_forward(self):
        cfg = tf.tiny(sliding_window=4, attn_softcap=20.0,
                      final_softcap=10.0, remat=False)
        params = _params(cfg)
        toks = _tokens(cfg, seq=10)
        full, _ = tf.forward(params, toks, cfg)
        _, cache = tf.forward(params, toks[:, :7], cfg,
                              cache=tf.init_cache(cfg, 2, 12), pos_offset=0)
        for i in range(7, 10):
            ld, cache = tf.forward(params, toks[:, i:i + 1], cfg,
                                   cache=cache, pos_offset=i)
            np.testing.assert_allclose(np.asarray(ld[:, 0]),
                                       np.asarray(full[:, i]),
                                       rtol=2e-4, atol=2e-4)

    def test_softcap_bounds_logits(self):
        cfg = tf.tiny(final_softcap=5.0, remat=False)
        params = _params(cfg)
        logits, _ = tf.forward(params, _tokens(cfg), cfg)
        assert float(jnp.abs(logits).max()) <= 5.0

    def test_gemma2_preset_forward(self):
        cfg = tf.tiny(sliding_window=4, alternate_sliding=True,
                      attn_softcap=50.0, final_softcap=30.0,
                      norm_offset=1.0, embed_scale=True, act="gelu",
                      remat=False)
        params = _params(cfg)
        logits, _ = tf.forward(params, _tokens(cfg), cfg)
        assert np.isfinite(np.asarray(logits)).all()
        assert 2e9 < tf.gemma2_2b().num_params() < 3.5e9


def _plain_proj(h, w):
    return h @ w


def _random_bank(params, cfg, seed=3):
    """A multi-LoRA bank of one adapter on every target whose B is not
    zero (a fresh adapter's delta is exactly zero)."""
    from tpushare.models import lora
    ad = lora.init_lora(jax.random.PRNGKey(seed), cfg, rank=4,
                        targets=lora.LORA_TARGETS)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), len(ad)))
    ad = {n: {"a": t["a"], "b": 0.05 * jax.random.normal(
        next(keys), t["b"].shape, t["b"].dtype)} for n, t in ad.items()}
    return lora.multi_lora_params(params, lora.stack_adapters([ad]))


class TestHeadProj:
    """The attention projections go through ``tf.head_proj`` (the dot
    held in its own layout, so that XLA on TPU does not copy the
    layer's weight out of the scanned stack): in bf16, with an adapter
    on every projection in one row and none in the other, the forward
    is the one the plain ``h @ w`` gives, for one token a row at its own
    offset, for a block of tokens, and through the gradient. The barrier
    is the identity on values, so the two agree bit for bit."""

    CFG = dataclasses.replace(CFG, dtype=jnp.bfloat16)

    def _run(self, case):
        cfg = self.CFG
        params = _random_bank(_params(cfg), cfg)
        idx = jnp.asarray([0, -1], jnp.int32)
        toks = _tokens(cfg, batch=2, seq=9)
        if case == "decode":
            cache = tf.init_cache(cfg, 2, 16)
            _, cache = tf.forward(params, toks[:, :8], cfg, cache=cache,
                                  pos_offset=0, mlora_idx=idx)
            return tf.forward(params, toks[:, 8:], cfg, cache=cache,
                              pos_offset=jnp.asarray([8, 5]),
                              mlora_idx=idx)[0]
        if case == "block":
            return tf.forward(params, toks, cfg, mlora_idx=idx)[0]
        loss = lambda p: lm_loss(p, toks, cfg)
        return jax.grad(loss)(params)["layers"]

    @pytest.mark.parametrize("case", ["decode", "block", "grad"])
    def test_matches_the_plain_product(self, case, monkeypatch):
        got = self._run(case)
        monkeypatch.setattr(tf, "head_proj", _plain_proj)
        want = self._run(case)
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(g, np.float32),
                                          np.asarray(w, np.float32))
