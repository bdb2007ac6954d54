"""Unit tests for tpushare.ops: norms, rotary, attention, and the
pallas flash kernel (interpret mode — hardware-free, per SURVEY.md §4's
fixture strategy)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpushare.ops import (apply_rotary, attention, flash_attention,
                          layer_norm, mha_reference, rms_norm,
                          rotary_embedding)


class TestNorms:
    def test_rms_norm_matches_numpy(self):
        x = np.random.default_rng(0).normal(size=(2, 5, 64)).astype(np.float32)
        w = np.random.default_rng(1).normal(size=(64,)).astype(np.float32)
        got = rms_norm(jnp.asarray(x), jnp.asarray(w))
        want = x / np.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-6) * w
        np.testing.assert_allclose(got, want, rtol=1e-5)

    def test_rms_norm_gemma_offset(self):
        x = jnp.ones((1, 1, 8))
        w = jnp.zeros((8,))
        # offset=1.0: zero weight still passes the normalized signal through
        y = rms_norm(x, w, offset=1.0)
        np.testing.assert_allclose(y, x / np.sqrt(1 + 1e-6), rtol=1e-5)

    def test_rms_norm_bf16_stats_in_f32(self):
        x = (jnp.ones((1, 2048)) * 100).astype(jnp.bfloat16)
        y = rms_norm(x, jnp.ones((2048,)))
        assert y.dtype == jnp.bfloat16
        assert bool(jnp.all(jnp.isfinite(y.astype(jnp.float32))))

    def test_layer_norm_zero_mean_unit_var(self):
        x = np.random.default_rng(2).normal(3.0, 5.0, (4, 32)).astype(np.float32)
        y = layer_norm(jnp.asarray(x), jnp.ones((32,)), jnp.zeros((32,)))
        np.testing.assert_allclose(np.asarray(y).mean(-1), 0.0, atol=1e-5)
        np.testing.assert_allclose(np.asarray(y).std(-1), 1.0, atol=1e-3)


class TestRotary:
    def test_position_zero_is_identity(self):
        x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 1, 2, 16)),
                        dtype=jnp.float32)
        cos, sin = rotary_embedding(jnp.zeros((1, 1), jnp.int32), 16)
        np.testing.assert_allclose(apply_rotary(x, cos, sin), x, rtol=1e-6)

    def test_norm_preserved(self):
        x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 7, 4, 32)),
                        dtype=jnp.float32)
        pos = jnp.broadcast_to(jnp.arange(7)[None, :], (2, 7))
        cos, sin = rotary_embedding(pos, 32)
        y = apply_rotary(x, cos, sin)
        np.testing.assert_allclose(jnp.linalg.norm(y, axis=-1),
                                   jnp.linalg.norm(x, axis=-1), rtol=1e-5)

    def test_relative_position_property(self):
        # <rot(q,p) , rot(k,p)> depends only on the *relative* offset: shifting
        # both positions by a constant must not change the dot product.
        rng = np.random.default_rng(3)
        q = jnp.asarray(rng.normal(size=(1, 1, 1, 16)), dtype=jnp.float32)
        k = jnp.asarray(rng.normal(size=(1, 1, 1, 16)), dtype=jnp.float32)
        def dot_at(p_q, p_k):
            cq, sq = rotary_embedding(jnp.full((1, 1), p_q), 16)
            ck, sk = rotary_embedding(jnp.full((1, 1), p_k), 16)
            return float(jnp.sum(apply_rotary(q, cq, sq) * apply_rotary(k, ck, sk)))
        assert dot_at(5, 3) == pytest.approx(dot_at(105, 103), rel=1e-4)


class TestReferenceAttention:
    def test_causal_masking(self):
        # Changing a future token must not change current output.
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.normal(size=(1, 8, 2, 16)), dtype=jnp.float32)
        k = jnp.asarray(rng.normal(size=(1, 8, 2, 16)), dtype=jnp.float32)
        v = jnp.asarray(rng.normal(size=(1, 8, 2, 16)), dtype=jnp.float32)
        out1 = mha_reference(q, k, v, causal=True)
        k2 = k.at[0, 7].set(99.0)
        v2 = v.at[0, 7].set(99.0)
        out2 = mha_reference(q, k2, v2, causal=True)
        np.testing.assert_allclose(out1[0, :7], out2[0, :7], rtol=1e-5)
        assert not np.allclose(out1[0, 7], out2[0, 7])

    def test_gqa_equals_expanded_mha(self):
        rng = np.random.default_rng(1)
        q = jnp.asarray(rng.normal(size=(2, 6, 4, 8)), dtype=jnp.float32)
        k = jnp.asarray(rng.normal(size=(2, 6, 2, 8)), dtype=jnp.float32)
        v = jnp.asarray(rng.normal(size=(2, 6, 2, 8)), dtype=jnp.float32)
        got = mha_reference(q, k, v)
        want = mha_reference(q, jnp.repeat(k, 2, axis=2),
                             jnp.repeat(v, 2, axis=2))
        # Grouped-einsum GQA reassociates vs the expanded path; allow
        # f32 reassociation noise.
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-6)

    def test_decode_step_matches_prefill(self):
        # Sq=1 with q_offset=t must equal row t of the full prefill.
        rng = np.random.default_rng(2)
        S = 10
        q = jnp.asarray(rng.normal(size=(1, S, 2, 8)), dtype=jnp.float32)
        k = jnp.asarray(rng.normal(size=(1, S, 2, 8)), dtype=jnp.float32)
        v = jnp.asarray(rng.normal(size=(1, S, 2, 8)), dtype=jnp.float32)
        full = mha_reference(q, k, v, causal=True)
        for t in (0, 4, 9):
            step = mha_reference(q[:, t:t + 1], k, v, causal=True, q_offset=t)
            np.testing.assert_allclose(step[:, 0], full[:, t], rtol=1e-5)

    def test_kv_mask_excludes_positions(self):
        rng = np.random.default_rng(4)
        q = jnp.asarray(rng.normal(size=(1, 1, 2, 8)), dtype=jnp.float32)
        k = jnp.asarray(rng.normal(size=(1, 6, 2, 8)), dtype=jnp.float32)
        v = jnp.asarray(rng.normal(size=(1, 6, 2, 8)), dtype=jnp.float32)
        mask = jnp.asarray([[True, True, True, False, False, False]])
        got = mha_reference(q, k, v, causal=False, kv_mask=mask)
        want = mha_reference(q, k[:, :3], v[:, :3], causal=False)
        np.testing.assert_allclose(got, want, rtol=1e-5)


class TestFlashAttention:
    """Pallas kernel vs reference, interpret mode (CPU)."""

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("H,Hkv", [(4, 4), (4, 2)])
    def test_matches_reference(self, causal, H, Hkv):
        rng = np.random.default_rng(0)
        B, S, D = 2, 512, 128
        q = jnp.asarray(rng.normal(size=(B, S, H, D)), dtype=jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), dtype=jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), dtype=jnp.float32)
        got = flash_attention(q, k, v, causal=causal, block_q=128,
                              block_k=128, interpret=True)
        want = mha_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)

    def test_cross_attention_longer_kv(self):
        rng = np.random.default_rng(1)
        q = jnp.asarray(rng.normal(size=(1, 128, 2, 128)), dtype=jnp.float32)
        k = jnp.asarray(rng.normal(size=(1, 384, 2, 128)), dtype=jnp.float32)
        v = jnp.asarray(rng.normal(size=(1, 384, 2, 128)), dtype=jnp.float32)
        got = flash_attention(q, k, v, causal=True, q_offset=256,
                              block_q=128, block_k=128, interpret=True)
        want = mha_reference(q, k, v, causal=True, q_offset=256)
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)

    def test_bf16(self):
        rng = np.random.default_rng(2)
        q = jnp.asarray(rng.normal(size=(1, 256, 2, 128)), dtype=jnp.bfloat16)
        k = jnp.asarray(rng.normal(size=(1, 256, 2, 128)), dtype=jnp.bfloat16)
        v = jnp.asarray(rng.normal(size=(1, 256, 2, 128)), dtype=jnp.bfloat16)
        got = flash_attention(q, k, v, block_q=128, block_k=128,
                              interpret=True).astype(jnp.float32)
        want = mha_reference(q, k, v).astype(jnp.float32)
        np.testing.assert_allclose(got, want, rtol=5e-2, atol=5e-2)

    def test_fallback_on_tiny_sq(self):
        rng = np.random.default_rng(3)
        q = jnp.asarray(rng.normal(size=(1, 1, 2, 128)), dtype=jnp.float32)
        k = jnp.asarray(rng.normal(size=(1, 128, 2, 128)), dtype=jnp.float32)
        v = jnp.asarray(rng.normal(size=(1, 128, 2, 128)), dtype=jnp.float32)
        got = flash_attention(q, k, v, q_offset=127, interpret=True)
        want = mha_reference(q, k, v, q_offset=127)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)

    def test_odd_multiple_of_128_snaps_block(self):
        # S=384 is eligible (multiple of 128) but not divisible by the
        # default 256 block: the block must snap down, not assert.
        rng = np.random.default_rng(5)
        q = jnp.asarray(rng.normal(size=(1, 384, 2, 128)), dtype=jnp.float32)
        k = jnp.asarray(rng.normal(size=(1, 384, 2, 128)), dtype=jnp.float32)
        v = jnp.asarray(rng.normal(size=(1, 384, 2, 128)), dtype=jnp.float32)
        got = flash_attention(q, k, v, interpret=True)
        want = mha_reference(q, k, v)
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)

    def test_q_offset_traced_no_retrace(self):
        rng = np.random.default_rng(6)
        q = jnp.asarray(rng.normal(size=(1, 128, 2, 128)), dtype=jnp.float32)
        k = jnp.asarray(rng.normal(size=(1, 512, 2, 128)), dtype=jnp.float32)
        v = jnp.asarray(rng.normal(size=(1, 512, 2, 128)), dtype=jnp.float32)
        for off in (0, 128, 384):
            got = flash_attention(q, k, v, q_offset=jnp.int32(off),
                                  block_q=128, block_k=128, interpret=True)
            want = mha_reference(q, k, v, q_offset=off)
            np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)

    def test_head_dim_64_falls_back_to_reference(self):
        # BERT-base head_dim=64 cannot tile on the MXU lane dim; the
        # kernel must route to the reference, not crash in Mosaic.
        rng = np.random.default_rng(7)
        q = jnp.asarray(rng.normal(size=(1, 128, 2, 64)), dtype=jnp.float32)
        k = jnp.asarray(rng.normal(size=(1, 128, 2, 64)), dtype=jnp.float32)
        v = jnp.asarray(rng.normal(size=(1, 128, 2, 64)), dtype=jnp.float32)
        got = flash_attention(q, k, v, interpret=True)
        np.testing.assert_allclose(got, mha_reference(q, k, v), rtol=1e-5,
                                   atol=1e-6)

    def test_custom_scale_honored_by_both_impls(self):
        rng = np.random.default_rng(8)
        q = jnp.asarray(rng.normal(size=(1, 128, 2, 128)), dtype=jnp.float32)
        k = jnp.asarray(rng.normal(size=(1, 128, 2, 128)), dtype=jnp.float32)
        v = jnp.asarray(rng.normal(size=(1, 128, 2, 128)), dtype=jnp.float32)
        got = flash_attention(q, k, v, scale=0.5, block_q=128, block_k=128,
                              interpret=True)
        want = mha_reference(q, k, v, scale=0.5)
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)
        assert not np.allclose(want, mha_reference(q, k, v))

    def test_non_divisible_gqa_heads_rejected(self):
        q = jnp.zeros((1, 128, 6, 128))
        k = jnp.zeros((1, 128, 4, 128))
        with pytest.raises(AssertionError):
            flash_attention(q, k, k, interpret=True)

    def test_auto_dispatch_on_cpu_uses_reference(self):
        rng = np.random.default_rng(4)
        q = jnp.asarray(rng.normal(size=(1, 128, 2, 128)), dtype=jnp.float32)
        k = jnp.asarray(rng.normal(size=(1, 128, 2, 128)), dtype=jnp.float32)
        v = jnp.asarray(rng.normal(size=(1, 128, 2, 128)), dtype=jnp.float32)
        out = attention(q, k, v, impl="auto")  # cpu backend -> reference path
        np.testing.assert_allclose(out, mha_reference(q, k, v), rtol=1e-6)


class TestFlashWindowSoftcap:
    """Windowed + softcapped flash kernel vs reference (interpret)."""

    def _arrs(self, seq=64, heads=2, dim=16, kv_heads=2, seed=21):
        rng = np.random.default_rng(seed)
        q = jnp.asarray(rng.standard_normal((2, seq, heads, dim)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((2, seq, kv_heads, dim)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((2, seq, kv_heads, dim)), jnp.float32)
        return q, k, v

    def test_window_matches_reference(self):
        from tpushare.ops.flash_attention import flash_attention
        q, k, v = self._arrs()
        got = flash_attention(q, k, v, causal=True, window=8,
                              interpret=True)
        want = mha_reference(q, k, v, causal=True, window=8)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_softcap_matches_reference(self):
        from tpushare.ops.flash_attention import flash_attention
        q, k, v = self._arrs(seed=22)
        got = flash_attention(q, k, v, causal=True, attn_softcap=10.0,
                              interpret=True)
        want = mha_reference(q, k, v, causal=True, attn_softcap=10.0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_window_and_softcap_with_offset(self):
        from tpushare.ops.flash_attention import flash_attention
        q, k, v = self._arrs(seed=23)
        q_half = q[:, :32]
        got = flash_attention(q_half, k, v, causal=True, q_offset=16,
                              window=8, attn_softcap=20.0, interpret=True)
        want = mha_reference(q_half, k, v, causal=True, q_offset=16,
                             window=8, attn_softcap=20.0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_traced_window_no_recompile(self):
        # Alternating local/global layers pass the window as a traced
        # scalar through one compiled kernel.
        from tpushare.ops.flash_attention import flash_attention
        q, k, v = self._arrs(seed=24)
        f = jax.jit(lambda w: flash_attention(q, k, v, causal=True,
                                              window=w, interpret=True))
        out_local = f(jnp.asarray(8))
        out_global = f(jnp.asarray(0))
        want_local = mha_reference(q, k, v, causal=True, window=8)
        want_global = mha_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out_local),
                                   np.asarray(want_local), rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(out_global),
                                   np.asarray(want_global), rtol=2e-5, atol=2e-5)


class TestFlashStreaming:
    """Streaming-grid kernel (Sk beyond VMEM residency) vs reference."""

    def _force_stream(self, monkeypatch):
        # Shrink the residency cap so small test shapes take the
        # streaming path without needing 16k-token inputs. The cap is
        # read at trace time, so drop the jit cache on the way in and
        # out (the monkeypatch teardown can't invalidate traces).
        import importlib
        fa = importlib.import_module("tpushare.ops.flash_attention")
        fa.flash_attention.clear_cache()
        monkeypatch.setattr(fa, "MAX_RESIDENT_KV_BYTES", 1)

    @pytest.fixture(autouse=True)
    def _clean_cache(self):
        import importlib
        fa = importlib.import_module("tpushare.ops.flash_attention")
        yield
        fa.flash_attention.clear_cache()

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("H,Hkv", [(4, 4), (4, 2)])
    def test_matches_reference(self, causal, H, Hkv, monkeypatch):
        self._force_stream(monkeypatch)
        rng = np.random.default_rng(3)
        B, S, D = 2, 512, 128
        q = jnp.asarray(rng.normal(size=(B, S, H, D)), dtype=jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), dtype=jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), dtype=jnp.float32)
        got = flash_attention(q, k, v, causal=causal, block_q=128,
                              block_k=128, interpret=True)
        want = mha_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)

    def test_window_and_softcap(self, monkeypatch):
        self._force_stream(monkeypatch)
        rng = np.random.default_rng(4)
        B, S, H, D = 1, 512, 2, 128
        q = jnp.asarray(rng.normal(size=(B, S, H, D)), dtype=jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, S, H, D)), dtype=jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, S, H, D)), dtype=jnp.float32)
        got = flash_attention(q, k, v, causal=True, window=256,
                              attn_softcap=30.0, block_q=128, block_k=128,
                              interpret=True)
        want = mha_reference(q, k, v, causal=True, window=256,
                             attn_softcap=30.0)
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)

    def test_q_offset_chunked_prefill(self, monkeypatch):
        self._force_stream(monkeypatch)
        rng = np.random.default_rng(5)
        B, Sq, Sk, H, D = 1, 128, 640, 2, 128
        q = jnp.asarray(rng.normal(size=(B, Sq, H, D)), dtype=jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, Sk, H, D)), dtype=jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, Sk, H, D)), dtype=jnp.float32)
        got = flash_attention(q, k, v, causal=True, q_offset=512,
                              block_q=128, block_k=128, interpret=True)
        want = mha_reference(q, k, v, causal=True, q_offset=512)
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


class TestFlashDecode:
    """Ragged decode kernel vs the model's kv_mask reference path."""

    def _ref(self, q, k, v, pos, window=None, softcap=None):
        M = k.shape[1]
        kv_mask = jnp.arange(M)[None, :] <= pos[:, None]
        if window is not None:
            kv_mask &= jnp.arange(M)[None, :] > pos[:, None] - window
        return mha_reference(q, k, v, causal=False, kv_mask=kv_mask,
                             attn_softcap=softcap)

    @pytest.mark.parametrize("H,Hkv", [(4, 4), (8, 2), (4, 1)])
    def test_matches_masked_reference(self, H, Hkv):
        from tpushare.ops.flash_attention import flash_decode
        rng = np.random.default_rng(6)
        B, M, D = 3, 256, 128
        q = jnp.asarray(rng.normal(size=(B, 1, H, D)), dtype=jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, M, Hkv, D)), dtype=jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, M, Hkv, D)), dtype=jnp.float32)
        pos = jnp.asarray([0, 100, 255], jnp.int32)
        got = flash_decode(q, k, v, pos, block_k=128, interpret=True)
        want = self._ref(q, k, v, pos)
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)

    def test_window_and_softcap(self):
        from tpushare.ops.flash_attention import flash_decode
        rng = np.random.default_rng(7)
        B, M, H, D = 2, 256, 4, 128
        q = jnp.asarray(rng.normal(size=(B, 1, H, D)), dtype=jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, M, H, D)), dtype=jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, M, H, D)), dtype=jnp.float32)
        pos = jnp.asarray([40, 200], jnp.int32)
        got = flash_decode(q, k, v, pos, window=64, attn_softcap=20.0,
                           block_k=128, interpret=True)
        want = self._ref(q, k, v, pos, window=64, softcap=20.0)
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)

    def test_bf16(self):
        from tpushare.ops.flash_attention import flash_decode
        rng = np.random.default_rng(8)
        B, M, H, D = 2, 128, 4, 128
        q = jnp.asarray(rng.normal(size=(B, 1, H, D)), dtype=jnp.bfloat16)
        k = jnp.asarray(rng.normal(size=(B, M, H, D)), dtype=jnp.bfloat16)
        v = jnp.asarray(rng.normal(size=(B, M, H, D)), dtype=jnp.bfloat16)
        pos = jnp.asarray([5, 100], jnp.int32)
        got = flash_decode(q, k, v, pos, block_k=128,
                           interpret=True).astype(jnp.float32)
        want = self._ref(q, k, v, pos).astype(jnp.float32)
        np.testing.assert_allclose(got, want, rtol=3e-2, atol=3e-2)


class TestPagedFlashDecode:
    """Block-table paged decode kernel vs the gathered dense reference
    (the exact computation models/paged.decode_core materializes)."""

    def _setup(self, B=3, H=4, Hkv=2, D=128, nb=10, bs=16, mb=4, seed=9):
        rng = np.random.default_rng(seed)
        pool_k = jnp.asarray(rng.normal(size=(nb, bs, Hkv, D)), jnp.float32)
        pool_v = jnp.asarray(rng.normal(size=(nb, bs, Hkv, D)), jnp.float32)
        table = jnp.asarray([[3, 7, 1, -1], [0, 2, -1, -1],
                             [5, 8, 6, 4]][:B], jnp.int32)[:, :mb]
        pos = jnp.asarray([40, 20, 55][:B], jnp.int32)
        q = jnp.asarray(rng.normal(size=(B, 1, H, D)), jnp.float32)
        return q, pool_k, pool_v, table, pos

    def _ref(self, q, pool_k, pool_v, table, pos, window=None, softcap=None):
        nb, bs = pool_k.shape[:2]
        B, mb = table.shape
        safe = jnp.where(table >= 0, table, nb - 1)
        kd = pool_k[safe].reshape(B, mb * bs, *pool_k.shape[2:])
        vd = pool_v[safe].reshape(B, mb * bs, *pool_v.shape[2:])
        kv_mask = jnp.arange(mb * bs)[None, :] <= pos[:, None]
        if window is not None:
            kv_mask &= jnp.arange(mb * bs)[None, :] > pos[:, None] - window
        return mha_reference(q, kd, vd, causal=False, kv_mask=kv_mask,
                             attn_softcap=softcap)

    def test_matches_gathered_reference(self):
        from tpushare.ops.flash_attention import paged_flash_decode
        q, pk, pv, table, pos = self._setup()
        got = paged_flash_decode(q, pk, pv, table, pos, interpret=True)
        want = self._ref(q, pk, pv, table, pos)
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)

    def test_mha_no_group(self):
        from tpushare.ops.flash_attention import paged_flash_decode
        q, pk, pv, table, pos = self._setup(H=2, Hkv=2)
        got = paged_flash_decode(q, pk, pv, table, pos, interpret=True)
        want = self._ref(q, pk, pv, table, pos)
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)

    def test_window_and_softcap(self):
        from tpushare.ops.flash_attention import paged_flash_decode
        q, pk, pv, table, pos = self._setup()
        got = paged_flash_decode(q, pk, pv, table, pos, window=24,
                                 attn_softcap=25.0, interpret=True)
        want = self._ref(q, pk, pv, table, pos, window=24, softcap=25.0)
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)

    def test_bf16(self):
        from tpushare.ops.flash_attention import paged_flash_decode
        q, pk, pv, table, pos = self._setup()
        q, pk, pv = (x.astype(jnp.bfloat16) for x in (q, pk, pv))
        got = paged_flash_decode(q, pk, pv, table, pos,
                                 interpret=True).astype(jnp.float32)
        want = self._ref(q, pk, pv, table, pos).astype(jnp.float32)
        np.testing.assert_allclose(got, want, rtol=3e-2, atol=3e-2)

    def test_int8_pages_match_dequantized_reference(self):
        """kv_quant pools through the kernel (int8 pages + scale
        pages) == the gathered dequantized reference, exactly the
        computation the kvq fallback materializes."""
        from tpushare.models.quant import kv_dequantize, kv_quantize
        from tpushare.ops.flash_attention import paged_flash_decode
        q, pk, pv, table, pos = self._setup()
        from tpushare.models.quant import scales_to_pool_layout
        qk, sk = kv_quantize(pk)
        qv, sv = kv_quantize(pv)
        got = paged_flash_decode(q, qk, qv, table, pos,
                                 k_scale=scales_to_pool_layout(sk),
                                 v_scale=scales_to_pool_layout(sv),
                                 interpret=True)
        want = self._ref(q, kv_dequantize(qk, sk, jnp.float32),
                         kv_dequantize(qv, sv, jnp.float32), table, pos)
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)

    def test_int8_pages_window_softcap(self):
        from tpushare.models.quant import kv_dequantize, kv_quantize
        from tpushare.ops.flash_attention import paged_flash_decode
        q, pk, pv, table, pos = self._setup()
        from tpushare.models.quant import scales_to_pool_layout
        qk, sk = kv_quantize(pk)
        qv, sv = kv_quantize(pv)
        got = paged_flash_decode(q, qk, qv, table, pos, window=24,
                                 attn_softcap=25.0,
                                 k_scale=scales_to_pool_layout(sk),
                                 v_scale=scales_to_pool_layout(sv),
                                 interpret=True)
        want = self._ref(q, kv_dequantize(qk, sk, jnp.float32),
                         kv_dequantize(qv, sv, jnp.float32), table, pos,
                         window=24, softcap=25.0)
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)

    # -- the loop over live groups (PR 29): a group is
    # DECODE_GROUP_KEYS // bs pages, 16 of 16 rows, 8 of 32 ------------

    def _live_case(self, pos, mb, bs=16, window=None, H=4, Hkv=2, D=128,
                   seed=11, poison=False):
        """Slots at ``pos`` over a pool drawn at random, each slot's
        table naming a page of its own for every page up to pos[b]
        and -1 beyond. ``poison``: a second copy of the pools in which
        every page no slot may attend (unnamed, or wholly behind the
        window) holds NaN. Returns (q, pool_k, pool_v, table, pos) or,
        poisoned, (..., poisoned_k, poisoned_v)."""
        rng = np.random.default_rng(seed)
        B = len(pos)
        need = [p // bs + 1 for p in pos]
        nb = sum(need) + 3
        pk, pv = (rng.normal(size=(nb, bs, Hkv, D)).astype(np.float32)
                  for _ in range(2))
        table = np.full((B, mb), -1, np.int32)
        pages = iter(rng.permutation(nb - 1))
        live = set()
        for b in range(B):
            table[b, :need[b]] = [next(pages) for _ in range(need[b])]
            first = 0 if window is None else max(
                0, (pos[b] - window + 1) // bs)
            live.update(int(x) for x in table[b, first:need[b]])
        q = jnp.asarray(rng.normal(size=(B, 1, H, D)), jnp.float32)
        out = (q, jnp.asarray(pk), jnp.asarray(pv), jnp.asarray(table),
               jnp.asarray(pos, jnp.int32))
        if poison:
            dead = [i for i in range(nb) if i not in live]
            for a in (pk.copy(), pv.copy()):
                a[dead] = np.nan
                out += (jnp.asarray(a),)
        return out

    @pytest.mark.parametrize("kw", [
        # 17 pages a slot at a group of 8: the last group holds one
        dict(bs=32, mb=17, pos=[543, 260, 17]),
        dict(bs=16, mb=20, pos=[0, 300, 0]),
        # a page's last row and the next page's first, at a page's
        # edge and at a group's (255 | 256)
        dict(bs=16, mb=40, pos=[15, 16, 255, 256]),
        # the table all -1 but page 0
        dict(bs=16, mb=20, pos=[9, 200]),
        # a window inside one group (601..700 of 512..767; 401..500)
        dict(bs=16, mb=48, pos=[700, 500], window=100),
        # a window over three groups (241..760; 81..600)
        dict(bs=16, mb=48, pos=[760, 600], window=520),
        dict(bs=16, mb=40, pos=[611, 90, 300], attn_softcap=20.0),
    ], ids=["mb_17_at_a_group_of_8", "pos_0", "page_and_group_edges",
            "table_unallocated_but_page_0", "window_inside_one_group",
            "window_over_three_groups", "softcap_over_three_groups"])
    def test_live_groups_match_gathered_reference(self, kw):
        from tpushare.ops.flash_attention import paged_flash_decode
        window, softcap = kw.get("window"), kw.get("attn_softcap")
        q, pk, pv, table, pos = self._live_case(
            kw["pos"], kw["mb"], kw["bs"], window)
        if kw["pos"] == [9, 200]:
            assert (np.asarray(table[0, 1:]) == -1).all()
        got = paged_flash_decode(q, pk, pv, table, pos, window=window,
                                 attn_softcap=softcap, interpret=True)
        want = self._ref(q, pk, pv, table, pos, window=window,
                         softcap=softcap)
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)

    @pytest.mark.parametrize("window", [None, 300])
    def test_pages_outside_the_live_range_are_never_read(self, window):
        """Every page no slot may attend holds NaN; 0 x NaN in the
        value product would show."""
        from tpushare.ops.flash_attention import paged_flash_decode
        q, pk, pv, table, pos, bad_k, bad_v = self._live_case(
            [530, 0, 270, 40], mb=40, window=window, poison=True)
        assert np.isnan(np.asarray(bad_k)).any()
        got = paged_flash_decode(q, bad_k, bad_v, table, pos,
                                 window=window, interpret=True)
        assert np.isfinite(np.asarray(got)).all()
        want = self._ref(q, pk, pv, table, pos, window=window)
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)

    def test_int8_pages_over_three_groups(self):
        """Scale pages copied beside their int8 pages, 20 pages of 32
        rows at a group of 8."""
        from tpushare.models.quant import (
            kv_dequantize, kv_quantize, scales_to_pool_layout)
        from tpushare.ops.flash_attention import paged_flash_decode
        q, pk, pv, table, pos = self._live_case([639, 0, 300, 31],
                                                mb=20, bs=32)
        qk, sk = kv_quantize(pk)
        qv, sv = kv_quantize(pv)
        got = paged_flash_decode(q, qk, qv, table, pos, window=400,
                                 k_scale=scales_to_pool_layout(sk),
                                 v_scale=scales_to_pool_layout(sv),
                                 interpret=True)
        want = self._ref(q, kv_dequantize(qk, sk, jnp.float32),
                         kv_dequantize(qv, sv, jnp.float32), table, pos,
                         window=400)
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_stacked_pool_with_a_traced_layer_in_a_scan(self, dtype):
        """The forward's call: the stack [L, nb, bs, Hkv*D] as it lies,
        ``layer`` the scan's counter. bf16 pages take the MXU-exact
        path (q.K in bf16, p split in three bf16 terms)."""
        from tpushare.ops.flash_attention import paged_flash_decode
        q, pk, pv, table, pos = self._live_case([530, 0, 270], mb=40)
        L = 3
        rng = np.random.default_rng(2)
        sk, sv = (jnp.asarray(rng.normal(size=(L,) + pk.shape),
                              jnp.float32).astype(dtype) for _ in range(2))
        q = q.astype(dtype)
        nb, bs, Hkv, D = pk.shape

        def layer(carry, l):
            return carry, paged_flash_decode(
                q, sk.reshape(L, nb, bs, Hkv * D),
                sv.reshape(L, nb, bs, Hkv * D), table, pos, layer=l,
                interpret=True)
        _, got = jax.jit(lambda: jax.lax.scan(layer, 0, jnp.arange(L)))()
        tol = 2e-3 if dtype == jnp.float32 else 2e-2
        for l in range(L):
            want = self._ref(q, sk[l], sv[l], table, pos)
            np.testing.assert_allclose(
                np.asarray(got[l], np.float32),
                np.asarray(want, np.float32), rtol=tol, atol=tol)

    def test_the_exact_value_product_rounds_no_probability(self):
        """p in three bf16 terms against bf16 V is the float32 product
        of p and V (to float32 summation), where p cast to bf16 is a
        hundred times off."""
        from tpushare.ops.flash_attention import _pv_exact
        rng = np.random.default_rng(4)
        p = jnp.asarray(rng.uniform(size=(8, 256)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(256, 128)), jnp.bfloat16)
        want = np.asarray(p, np.float64) @ np.asarray(
            v.astype(jnp.float32), np.float64)
        got = np.asarray(_pv_exact(p, v), np.float64)
        rounded = np.asarray(p.astype(jnp.bfloat16).astype(jnp.float32),
                             np.float64) @ np.asarray(
            v.astype(jnp.float32), np.float64)
        assert np.abs(got - want).max() < 2e-5
        assert np.abs(rounded - want).max() > 1e-3


class TestDecodeDispatchPolicy:
    """VERDICT r2 item 2: the measured-on-chip evidence has XLA's fused
    decode AHEAD of flash_decode, so the default dispatch must never
    take the slower pallas path; the kernel is env-opt-in. The paged
    kernel's XLA alternative (gathered dense view) measured slower, so
    it stays auto-on."""

    def _decode_shapes(self):
        q = jnp.zeros((2, 1, 8, 128), jnp.bfloat16)
        k = jnp.zeros((2, 1024, 2, 128), jnp.bfloat16)
        return q, k

    def _paged_shapes(self):
        q = jnp.zeros((2, 1, 8, 128), jnp.bfloat16)
        pool = jnp.zeros((16, 128, 2, 128), jnp.bfloat16)
        return q, pool

    def test_contiguous_decode_yields_to_xla_by_default(self, monkeypatch):
        import importlib
        fa = importlib.import_module('tpushare.ops.flash_attention')
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.delenv(fa.DECODE_KERNEL_ENV, raising=False)
        assert fa.decode_eligible(*self._decode_shapes()) is False

    def test_contiguous_decode_kernel_is_env_opt_in(self, monkeypatch):
        import importlib
        fa = importlib.import_module('tpushare.ops.flash_attention')
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setenv(fa.DECODE_KERNEL_ENV, "1")
        assert fa.decode_eligible(*self._decode_shapes()) is True
        monkeypatch.setenv(fa.DECODE_KERNEL_ENV, "0")
        assert fa.decode_eligible(*self._decode_shapes()) is False

    def test_paged_decode_stays_auto_on(self, monkeypatch):
        import importlib
        fa = importlib.import_module('tpushare.ops.flash_attention')
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.delenv(fa.DECODE_KERNEL_ENV, raising=False)
        assert fa.paged_decode_eligible(*self._paged_shapes()) is True
        # A page must fill a Mosaic tile of its dtype: 8 rows of f32,
        # 16 of bf16 (the daemon's default block), 32 of int8.
        q, _ = self._paged_shapes()
        for dtype, bs, want in ((jnp.float32, 8, True),
                                (jnp.bfloat16, 8, False),
                                (jnp.bfloat16, 16, True),
                                (jnp.int8, 16, False),
                                (jnp.int8, 32, True)):
            pool = jnp.zeros((16, bs, 2, 128), dtype)
            assert fa.paged_decode_eligible(q, pool) is want, (dtype, bs)
        monkeypatch.setenv(fa.DECODE_KERNEL_ENV, "0")
        assert fa.paged_decode_eligible(*self._paged_shapes()) is False

    def test_paged_int8_kernel_follows_measured_crossover(self,
                                                          monkeypatch):
        """r3 on-chip crossover sweep: the int8 kernel lost to XLA's
        fused int8-gather at 4k ctx (0.63x) but won from 8k up (1.22x
        / 1.81x / 1.68x at 8k/16k/32k, credible) — dispatch keys on
        the slot capacity, with the env var forcing either way."""
        import importlib
        fa = importlib.import_module('tpushare.ops.flash_attention')
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.delenv(fa.DECODE_KERNEL_ENV, raising=False)
        short = fa.PAGED_Q8_KERNEL_MIN_CTX - 128
        long = fa.PAGED_Q8_KERNEL_MIN_CTX
        assert fa.paged_decode_eligible(*self._paged_shapes(),
                                        quantized=True,
                                        max_ctx=short) is False
        assert fa.paged_decode_eligible(*self._paged_shapes(),
                                        quantized=True,
                                        max_ctx=long) is True
        # No capacity information -> conservative fallback.
        assert fa.paged_decode_eligible(*self._paged_shapes(),
                                        quantized=True) is False
        # Scale pages narrower than a lane tile cannot be copied out of
        # HBM one a page (Mosaic, PR 29): pages of 32 or 64 rows take
        # the fallback even when forced.
        q, _ = self._paged_shapes()
        for bs, want in ((32, False), (64, False), (128, True),
                         (256, True)):
            pool = jnp.zeros((16, bs, 2, 128), jnp.int8)
            assert fa.paged_decode_eligible(
                q, pool, quantized=True, max_ctx=long) is want, bs
        # Env forces win over the heuristic in both directions.
        monkeypatch.setenv(fa.DECODE_KERNEL_ENV, "1")
        assert fa.paged_decode_eligible(*self._paged_shapes(),
                                        quantized=True,
                                        max_ctx=short) is True
        monkeypatch.setenv(fa.DECODE_KERNEL_ENV, "0")
        assert fa.paged_decode_eligible(*self._paged_shapes(),
                                        quantized=True,
                                        max_ctx=long) is False

    def test_never_eligible_off_tpu(self, monkeypatch):
        import importlib
        fa = importlib.import_module('tpushare.ops.flash_attention')
        monkeypatch.setenv(fa.DECODE_KERNEL_ENV, "1")
        assert fa.decode_eligible(*self._decode_shapes()) is False
        assert fa.paged_decode_eligible(*self._paged_shapes()) is False


class TestPagedFlashVerify:
    """Multi-token (speculative-verify) paged kernel vs the gathered
    3D-masked reference — the exact computation transformer.py's paged
    Sq>1 branch materializes."""

    def _setup(self, B=3, Sq=4, H=4, Hkv=2, D=128, nb=10, bs=16, mb=4,
               seed=11):
        rng = np.random.default_rng(seed)
        pool_k = jnp.asarray(rng.normal(size=(nb, bs, Hkv, D)), jnp.float32)
        pool_v = jnp.asarray(rng.normal(size=(nb, bs, Hkv, D)), jnp.float32)
        table = jnp.asarray([[3, 7, 1, -1], [0, 2, -1, -1],
                             [5, 8, 6, 4]][:B], jnp.int32)[:, :mb]
        pos = jnp.asarray([40, 20, 55][:B], jnp.int32)
        q = jnp.asarray(rng.normal(size=(B, Sq, H, D)), jnp.float32)
        return q, pool_k, pool_v, table, pos

    def _ref(self, q, pool_k, pool_v, table, pos, window=None,
             softcap=None):
        nb, bs = pool_k.shape[:2]
        B, mb = table.shape
        Sq = q.shape[1]
        safe = jnp.where(table >= 0, table, nb - 1)
        kd = pool_k[safe].reshape(B, mb * bs, *pool_k.shape[2:])
        vd = pool_v[safe].reshape(B, mb * bs, *pool_v.shape[2:])
        pos_grid = pos[:, None] + jnp.arange(Sq)[None, :]
        k_pos = jnp.arange(mb * bs)
        mask = k_pos[None, None, :] <= pos_grid[..., None]
        if window is not None:
            mask &= k_pos[None, None, :] > pos_grid[..., None] - window
        return mha_reference(q, kd, vd, causal=False, kv_mask=mask,
                             attn_softcap=softcap)

    def test_matches_gathered_reference(self):
        from tpushare.ops.flash_attention import paged_flash_verify
        q, pk, pv, table, pos = self._setup()
        got = paged_flash_verify(q, pk, pv, table, pos, interpret=True)
        want = self._ref(q, pk, pv, table, pos)
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)

    def test_per_row_causality_differs_across_candidates(self):
        """Row s must attend exactly <= pos+s: zeroing the KV at
        position pos+1 changes rows >= 1 but NOT row 0."""
        from tpushare.ops.flash_attention import paged_flash_verify
        q, pk, pv, table, pos = self._setup(B=1, mb=4, seed=13)
        bs = pk.shape[1]
        p = int(pos[0])
        blk = int(table[0, (p + 1) // bs])
        pk2 = pk.at[blk, (p + 1) % bs].set(0.0)
        pv2 = pv.at[blk, (p + 1) % bs].set(0.0)
        a = paged_flash_verify(q, pk, pv, table, pos, interpret=True)
        b = paged_flash_verify(q, pk2, pv2, table, pos, interpret=True)
        np.testing.assert_allclose(a[:, 0], b[:, 0], rtol=1e-6, atol=1e-6)
        assert not np.allclose(a[:, 1], b[:, 1], atol=1e-4)

    def test_mha_window_softcap_bf16(self):
        from tpushare.ops.flash_attention import paged_flash_verify
        q, pk, pv, table, pos = self._setup(H=2, Hkv=2)
        q, pk, pv = (x.astype(jnp.bfloat16) for x in (q, pk, pv))
        got = paged_flash_verify(q, pk, pv, table, pos, window=24,
                                 attn_softcap=25.0,
                                 interpret=True).astype(jnp.float32)
        want = self._ref(q, pk, pv, table, pos, window=24,
                         softcap=25.0).astype(jnp.float32)
        np.testing.assert_allclose(got, want, rtol=3e-2, atol=3e-2)

    def test_int8_pages_match_dequantized_reference(self):
        from tpushare.models.quant import (kv_dequantize, kv_quantize,
                                           scales_to_pool_layout)
        from tpushare.ops.flash_attention import paged_flash_verify
        q, pk, pv, table, pos = self._setup()
        qk, sk = kv_quantize(pk)
        qv, sv = kv_quantize(pv)
        got = paged_flash_verify(q, qk, qv, table, pos,
                                 k_scale=scales_to_pool_layout(sk),
                                 v_scale=scales_to_pool_layout(sv),
                                 interpret=True)
        want = self._ref(q, kv_dequantize(qk, sk, jnp.float32),
                         kv_dequantize(qv, sv, jnp.float32), table, pos)
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)

    def test_odd_group_padding(self):
        # g*Sq not a multiple of 8 exercises the gq_pad row padding.
        from tpushare.ops.flash_attention import paged_flash_verify
        q, pk, pv, table, pos = self._setup(Sq=3, H=2, Hkv=2)
        got = paged_flash_verify(q, pk, pv, table, pos, interpret=True)
        want = self._ref(q, pk, pv, table, pos)
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)

    def test_eligibility_policy(self, monkeypatch):
        import importlib
        fa = importlib.import_module('tpushare.ops.flash_attention')
        q = jnp.zeros((2, 4, 4, 128), jnp.bfloat16)
        pool = jnp.zeros((8, 16, 2, 128), jnp.bfloat16)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        # OPT-IN until the on-chip bench row banks (dispatch rule:
        # defaults never pick a kernel ahead of banked evidence).
        monkeypatch.delenv("TPUSHARE_DECODE_KERNEL", raising=False)
        assert fa.paged_verify_eligible(q, pool) is False
        monkeypatch.setenv("TPUSHARE_DECODE_KERNEL", "0")
        assert fa.paged_verify_eligible(q, pool) is False
        monkeypatch.setenv("TPUSHARE_DECODE_KERNEL", "1")
        assert fa.paged_verify_eligible(q, pool) is True
        # Forced policy overrides the int8 crossover, like decode.
        assert fa.paged_verify_eligible(q, pool, quantized=True,
                                        max_ctx=4096) is True
        # Sq=1 is paged_flash_decode's job; huge Sq is prefill-shaped.
        assert fa.paged_verify_eligible(
            jnp.zeros((2, 1, 4, 128), jnp.bfloat16), pool) is False
        assert fa.paged_verify_eligible(
            jnp.zeros((2, 32, 4, 128), jnp.bfloat16), pool) is False
