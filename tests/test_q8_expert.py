"""Fused dequant×GEMM int8 MoE expert path (ISSUE 12, ROADMAP 3).

Numeric-accuracy pins for ops/q8_expert + quant.fused_expert_hook:
- the pallas kernel (interpreter mode, CPU CI) against its jnp
  reference — bit-exact, both x layouts, single and multi F-tile;
- the fused path against the dequant_hook path — greedy served token
  streams BIT-EXACT; logits within a documented tolerance (the fused
  math keeps f32 through the matmul and scales after the dot, the
  hook rounds W·s into cfg.dtype before it — an ulp-level, strictly
  precision-favoring difference);
- eligibility-gate negatives: bad shapes fall back LOUDLY to the
  reference (RuntimeWarning), never silently;
- ep×tp sharded fused serving bit-exact vs the single-chip oracle
  (placement contract unchanged: quant_moe_param_specs);
- the phase-timer measurement seam: instrumented eager forward
  matches the jitted scan, refuses to run under a trace, and the
  per-phase byte floors cover the step total.
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpushare.models import moe, quant
from tpushare.models.paged import PagedSlotServer
from tpushare.ops import q8_expert as qe
from tpushare.utils import profiling

CFG = moe.tiny(remat=False)
PARAMS = moe.init_params(jax.random.PRNGKey(0), CFG)
QPARAMS = quant.quantize_params(PARAMS, CFG)

# Kernel-ELIGIBLE tiny config (d_model 128, d_ff 128 — both lane-tile
# aligned): the integration tests below route the REAL kernel (under
# the interpreter) through moe.forward/_moe_ffn/the slot servers.
# moe.tiny's d_model=64 is deliberately ineligible — it exercises the
# fallback half of the gate.
CFG128 = moe.tiny(d_model=128, remat=False)
PARAMS128 = moe.init_params(jax.random.PRNGKey(0), CFG128)
QPARAMS128 = quant.quantize_params(PARAMS128, CFG128)


def _quant(w, axis=-2):
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=axis, keepdims=True)
                    / 127.0, 1e-12)
    return (jnp.clip(jnp.round(w / s), -127, 127).astype(jnp.int8), s)


def _kernel_operands(E=2, Dm=128, F=256, C=5, seed=0, x_ndim=2,
                     dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    mk = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    wgq, wgs = _quant(mk(E, Dm, F))
    wuq, wus = _quant(mk(E, Dm, F))
    wdq, wds = _quant(mk(E, F, Dm))
    x = mk(C, Dm) if x_ndim == 2 else mk(E, C, Dm)
    return x.astype(dtype), wgq, wgs, wuq, wus, wdq, wds


def _prompt(seed, n, vocab=None):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, vocab or CFG.vocab_size, n),
                       jnp.int32)


def _server(params, cfg, **kw):
    """The slot server over the sparse family's forward."""
    return PagedSlotServer(params, cfg, n_slots=2, n_blocks=32,
                           block_size=4, forward_fn=moe.paged_forward,
                           **kw)


class TestKernelInterpreterParity:
    """The pallas kernel logic runs in CPU CI via interpret mode and
    must reproduce the jnp reference exactly — same op order (scale
    after dot, f32 accumulation), same tiles."""

    @pytest.mark.parametrize("x_ndim", [2, 3])
    def test_single_tile_bit_exact(self, x_ndim):
        ops = _kernel_operands(x_ndim=x_ndim)
        ker = qe.q8_expert_ffn(*ops, act="silu", interpret=True)
        ref = qe.q8_expert_ffn_reference(*ops, act="silu")
        assert ker.shape == ref.shape == (2, 5, 128)
        assert (ker == ref).all()

    def test_multi_tile_accumulation(self):
        # F=1024 sweeps two 512-wide tiles: the VMEM-scratch partial
        # sums across the F grid must reproduce the one-shot einsum up
        # to f32 reassociation (the tile sweep sums per-512 partials;
        # observed ~2e-4 relative on O(5e3) outputs — summation order
        # only, single-tile shapes are pinned bit-exact above).
        ops = _kernel_operands(F=1024, C=4)
        ker = qe.q8_expert_ffn(*ops, act="silu", interpret=True)
        ref = qe.q8_expert_ffn_reference(*ops, act="silu")
        np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                                   rtol=5e-4, atol=5e-4)

    def test_bf16_tokens(self):
        # x in bf16 (the on-chip serving dtype): output dtype follows
        # x, accumulation stays f32 inside.
        ops = _kernel_operands(dtype=jnp.bfloat16, C=3)
        ker = qe.q8_expert_ffn(*ops, act="silu", interpret=True)
        ref = qe.q8_expert_ffn_reference(*ops, act="silu")
        assert ker.dtype == jnp.bfloat16
        assert (ker == ref).all()

    def test_gelu_act(self):
        ops = _kernel_operands(C=3)
        ker = qe.q8_expert_ffn(*ops, act="gelu", interpret=True)
        ref = qe.q8_expert_ffn_reference(*ops, act="gelu")
        assert (ker == ref).all()

    def test_ragged_c_padding_sliced_off(self):
        # C=5 pads to the 8-row sublane tile inside; the pad rows must
        # not leak into the output.
        ops = _kernel_operands(C=5)
        ker = qe.q8_expert_ffn(*ops, act="silu", interpret=True)
        assert ker.shape[1] == 5


class TestEligibilityGate:
    def test_misaligned_d_model(self):
        ok, reason = qe.q8_expert_eligible(
            jnp.zeros((2, 64, 128), jnp.int8))
        assert not ok and "d_model" in reason

    def test_misaligned_d_ff(self):
        ok, reason = qe.q8_expert_eligible(
            jnp.zeros((2, 128, 192), jnp.int8))
        assert not ok and "d_ff" in reason

    def test_non_int8_weights(self):
        ok, reason = qe.q8_expert_eligible(
            jnp.zeros((2, 128, 128), jnp.float32))
        assert not ok and "int8" in reason

    def test_eligible_serving_shape(self):
        ok, reason = qe.q8_expert_eligible(
            jnp.zeros((8, 1024, 4096), jnp.int8))
        assert ok, reason

    def test_decode_token_block_fits_vmem(self):
        # Decode batch (C = n_slots) at on-chip serving width.
        ok, reason = qe.q8_expert_eligible(
            jnp.zeros((8, 1024, 4096), jnp.int8), n_tokens=8,
            x_dtype=jnp.bfloat16)
        assert ok, reason

    def test_prefill_sized_token_block_rejected(self):
        # A whole-prompt prefill block would blow core VMEM (the
        # kernel carries [Cp, Dm] x + an f32 accumulator across the
        # F sweep) — the gate must bound C, not crash Mosaic.
        ok, reason = qe.q8_expert_eligible(
            jnp.zeros((8, 1024, 4096), jnp.int8), n_tokens=2048,
            x_dtype=jnp.bfloat16)
        assert not ok and "VMEM" in reason

    def test_kernel_refuses_ineligible_shapes(self):
        ops = _kernel_operands(Dm=64, F=128)
        with pytest.raises(ValueError, match="ineligible"):
            qe.q8_expert_ffn(*ops, act="silu", interpret=True)

    def test_dispatch_falls_back_loudly_not_silently(self, monkeypatch):
        # A caller that asked for the kernel (policy=1) with a shape
        # the gate rejects gets the REFERENCE result plus a
        # RuntimeWarning naming the reason — never a silent fallback.
        monkeypatch.setenv(qe.Q8_EXPERT_KERNEL_ENV, "1")
        monkeypatch.setattr(qe, "_FALLBACK_WARNED", set())
        ops = _kernel_operands(Dm=64, F=128)
        with pytest.warns(RuntimeWarning, match="falling back"):
            out = qe.q8_expert_dispatch(*ops, act="silu")
        assert (out == qe.q8_expert_ffn_reference(*ops,
                                                  act="silu")).all()

    def test_fallback_warns_once_per_reason(self, monkeypatch):
        monkeypatch.setenv(qe.Q8_EXPERT_KERNEL_ENV, "1")
        monkeypatch.setattr(qe, "_FALLBACK_WARNED", set())
        ops = _kernel_operands(Dm=64, F=128)
        with pytest.warns(RuntimeWarning):
            qe.q8_expert_dispatch(*ops, act="silu")
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            qe.q8_expert_dispatch(*ops, act="silu")     # quiet now


class TestDispatchPolicy:
    def test_force_reference(self, monkeypatch):
        # Policy 0 must never touch the kernel, even when eligible.
        monkeypatch.setenv(qe.Q8_EXPERT_KERNEL_ENV, "0")

        def boom(*a, **kw):                  # pragma: no cover
            raise AssertionError("kernel dispatched under policy 0")
        monkeypatch.setattr(qe, "q8_expert_ffn", boom)
        ops = _kernel_operands()
        out = qe.q8_expert_dispatch(*ops, act="silu")
        assert out.shape == (2, 5, 128)

    def test_interpret_mode_routes_to_kernel(self, monkeypatch):
        monkeypatch.setenv(qe.Q8_EXPERT_KERNEL_ENV, "interpret")
        calls = {}
        real = qe.q8_expert_ffn

        def spy(*a, **kw):
            calls["interpret"] = kw.get("interpret")
            return real(*a, **kw)
        monkeypatch.setattr(qe, "q8_expert_ffn", spy)
        ops = _kernel_operands()
        out = qe.q8_expert_dispatch(*ops, act="silu")
        assert calls == {"interpret": True}
        assert (out == qe.q8_expert_ffn_reference(*ops,
                                                  act="silu")).all()

    def test_default_is_reference_until_banked(self, monkeypatch):
        # No policy: reference on EVERY backend, and NO warning — the
        # repo's dispatch rule (a default never picks a kernel ahead
        # of banked on-chip evidence; flash_attention's
        # paged_verify_eligible precedent). Flips once the bench row
        # banks.
        monkeypatch.delenv(qe.Q8_EXPERT_KERNEL_ENV, raising=False)

        def boom(*a, **kw):                  # pragma: no cover
            raise AssertionError("kernel dispatched by default")
        monkeypatch.setattr(qe, "q8_expert_ffn", boom)
        ops = _kernel_operands()
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            qe.q8_expert_dispatch(*ops, act="silu")

    def test_unknown_policy_value_raises(self, monkeypatch):
        # A typo must fail loudly, not silently force the kernel on
        # (or off) — the serve.py loud-config discipline.
        monkeypatch.setenv(qe.Q8_EXPERT_KERNEL_ENV, "reference")
        ops = _kernel_operands()
        with pytest.raises(ValueError, match="expected 1"):
            qe.q8_expert_dispatch(*ops, act="silu")

    def test_dispatch_mode_reports_the_real_decision(self, monkeypatch):
        wgq = jnp.zeros((2, 128, 256), jnp.int8)
        monkeypatch.delenv(qe.Q8_EXPERT_KERNEL_ENV, raising=False)
        assert qe.q8_dispatch_mode(8, wgq) == "reference"
        monkeypatch.setenv(qe.Q8_EXPERT_KERNEL_ENV, "interpret")
        assert qe.q8_dispatch_mode(8, wgq) == "pallas-interpret"
        monkeypatch.setenv(qe.Q8_EXPERT_KERNEL_ENV, "1")
        assert qe.q8_dispatch_mode(8, wgq) == "pallas"
        # Forced kernel + ineligible operands = reference (what the
        # loud fallback will actually run).
        assert qe.q8_dispatch_mode(
            8, jnp.zeros((2, 64, 128), jnp.int8)) == "reference"
        monkeypatch.setenv(qe.Q8_EXPERT_KERNEL_ENV, "0")
        assert qe.q8_dispatch_mode(8, wgq) == "reference"


class TestFusedHook:
    def test_memoized_identity(self):
        # generate()/the slot servers key their jit caches on the
        # hook's identity — a fresh closure per call would recompile
        # the serving program every request (the JC801 discipline).
        assert (quant.fused_expert_hook(CFG)
                is quant.fused_expert_hook(CFG))

    def test_expert_leaves_stay_int8(self):
        layer = {k: v[0] for k, v in QPARAMS["layers"].items()}
        out = quant.fused_expert_hook(CFG)(layer)
        assert out["w_gate#q8"].dtype == jnp.int8
        assert out["w_down#scale"].dtype == jnp.float32
        # Attention leaves widen exactly like dequant_hook's.
        assert out["wq"].dtype == CFG.dtype
        assert "wq#q8" not in out
        ref = quant.dequant_hook(CFG)(layer)
        assert (out["wq"] == ref["wq"]).all()

    def test_dequant_expert_leaves_matches_hook(self):
        layer = {k: v[0] for k, v in QPARAMS["layers"].items()}
        wide = quant.dequant_expert_leaves(layer, CFG.dtype)
        ref = quant.dequant_hook(CFG)(layer)
        for k in ("w_gate", "w_up", "w_down", "wq"):
            assert (wide[k] == ref[k]).all()


# Documented logits tolerance for fused-vs-hook: both paths compute
# the same dequantized matmul, but the fused math applies the per-
# output-channel scale AFTER the f32 dot while the hook rounds W·s
# into cfg.dtype BEFORE it — an ulp-level reordering (f32 tiny
# models: ~1e-5 absolute on O(10) logits) that strictly favors the
# fused path's precision. Greedy token streams are pinned bit-exact.
LOGITS_TOL = dict(rtol=2e-4, atol=2e-4)


class TestFusedVsDequantHook:
    """The serving pins: same int8 tree through both hooks."""

    @pytest.mark.parametrize("routing,kw", [
        ("psum", {}),                            # dense dispatch
        ("psum", {"capacity_factor": 1.5}),      # grouped dispatch
        ("expert_choice", {}),
    ])
    def test_greedy_generate_streams_bit_exact(self, routing, kw):
        cfg = moe.tiny(remat=False, routing=routing, **kw)
        qp = quant.quantize_params(PARAMS, cfg)
        toks = _prompt(3, 12)[None, :]
        out_d = moe.generate(qp, toks, cfg, max_new_tokens=16,
                             layers_hook=quant.dequant_hook(cfg))
        out_f = moe.generate(qp, toks, cfg, max_new_tokens=16,
                             layers_hook=quant.fused_expert_hook(cfg))
        assert (np.asarray(out_d) == np.asarray(out_f)).all()

    @pytest.mark.parametrize("routing,kw", [
        ("psum", {}),
        ("psum", {"capacity_factor": 1.5}),
    ])
    def test_logits_within_documented_tolerance(self, routing, kw):
        cfg = moe.tiny(remat=False, routing=routing, **kw)
        qp = quant.quantize_params(PARAMS, cfg)
        toks = _prompt(4, 10)[None, :]
        lg_d, _ = moe.forward(qp, toks, cfg,
                              layers_hook=quant.dequant_hook(cfg))
        lg_f, _ = moe.forward(qp, toks, cfg,
                              layers_hook=quant.fused_expert_hook(cfg))
        np.testing.assert_allclose(np.asarray(lg_d), np.asarray(lg_f),
                                   **LOGITS_TOL)

    def test_dropless_falls_back_loudly(self, monkeypatch):
        # ragged_dot needs wide weights: the fused hook's int8 leaves
        # widen in-graph (dequant_hook semantics) with a loud warning.
        monkeypatch.setattr(moe, "_Q8_ROUTING_WARNED", set())
        cfg = moe.tiny(remat=False, routing="dropless")
        qp = quant.quantize_params(PARAMS, cfg)
        toks = _prompt(5, 8)[None, :]
        with pytest.warns(RuntimeWarning, match="dropless"):
            lg_f, _ = moe.forward(
                qp, toks, cfg, layers_hook=quant.fused_expert_hook(cfg))
        lg_d, _ = moe.forward(qp, toks, cfg,
                              layers_hook=quant.dequant_hook(cfg))
        np.testing.assert_allclose(np.asarray(lg_d), np.asarray(lg_f),
                                   **LOGITS_TOL)

    def test_served_stream_bit_exact(self):
        # The slot server's path (admit + ragged decode ticks): the
        # engine-visible token stream must not change when the fused
        # hook replaces the dequant hook.
        streams = {}
        for name, hook in (("dequant", quant.dequant_hook(CFG)),
                           ("fused", quant.fused_expert_hook(CFG))):
            srv = _server(QPARAMS, CFG, layers_hook=hook)
            srv.admit(_prompt(11, 7))
            srv.admit(_prompt(12, 5))
            toks = []
            for _ in range(10):
                toks.append(sorted(srv.step().items()))
            streams[name] = toks
        assert streams["fused"] == streams["dequant"]


class TestKernelThroughServingPath:
    """Finding of the r12 review: moe.tiny's d_model=64 is (by
    design) kernel-INELIGIBLE, so fallback-path pins alone would
    never run the kernel through _moe_ffn / the slot servers. These
    tests use the eligible CFG128 under the interpret policy and SPY
    on q8_expert_ffn to prove the real kernel ran inside the real
    serving path — and that the stream still matches the dequant-hook
    oracle bit-exactly."""

    def _spy(self, monkeypatch):
        calls = []
        real = qe.q8_expert_ffn

        def spy(*a, **kw):
            calls.append(kw.get("interpret"))
            return real(*a, **kw)
        monkeypatch.setattr(qe, "q8_expert_ffn", spy)
        return calls

    @pytest.mark.parametrize("routing,kw", [
        ("psum", {}),
        ("psum", {"capacity_factor": 1.5}),
    ])
    def test_kernel_runs_inside_forward_stream_exact(self, routing,
                                                     kw, monkeypatch):
        monkeypatch.setenv(qe.Q8_EXPERT_KERNEL_ENV, "interpret")
        cfg = moe.tiny(d_model=128, remat=False, routing=routing, **kw)
        qp = quant.quantize_params(PARAMS128, cfg)
        toks = _prompt(51, 10, cfg.vocab_size)[None, :]
        calls = self._spy(monkeypatch)
        lg_f, _ = moe.forward(qp, toks, cfg,
                              layers_hook=quant.fused_expert_hook(cfg))
        assert calls and all(c is True for c in calls), calls
        lg_d, _ = moe.forward(qp, toks, cfg,
                              layers_hook=quant.dequant_hook(cfg))
        assert (jnp.argmax(lg_f[:, -1], -1)
                == jnp.argmax(lg_d[:, -1], -1)).all()
        np.testing.assert_allclose(np.asarray(lg_d), np.asarray(lg_f),
                                   **LOGITS_TOL)

    def test_kernel_runs_inside_slot_server_tick(self, monkeypatch):
        monkeypatch.setenv(qe.Q8_EXPERT_KERNEL_ENV, "interpret")
        calls = self._spy(monkeypatch)
        streams = {}
        for name, hook in (("fused", quant.fused_expert_hook(CFG128)),
                           ("dequant", quant.dequant_hook(CFG128))):
            srv = _server(QPARAMS128, CFG128, layers_hook=hook)
            srv.admit(_prompt(52, 7, CFG128.vocab_size))
            streams[name] = [sorted(srv.step().items())
                             for _ in range(8)]
        assert streams["fused"] == streams["dequant"]
        assert calls and all(c is True for c in calls), calls


@pytest.mark.skipif(len(jax.devices()) < 4,
                    reason="needs 4 forced host devices")
class TestShardedFusedServing:
    """ep×tp composition: the fused int8 path is per-shard and the
    placement contract (quant.quant_moe_param_specs) is unchanged, so
    the sharded stream must be bit-exact vs the single-chip oracle —
    the same oracle design as test_sharded_serving.py."""

    def _stream(self, mesh):
        from tpushare.parallel import make_mesh
        specs = quant.quant_moe_param_specs(CFG) if mesh else None
        srv = _server(QPARAMS, CFG,
                      layers_hook=quant.fused_expert_hook(CFG),
                      mesh=mesh, param_specs=specs)
        srv.admit(_prompt(21, 6))
        srv.admit(_prompt(22, 9))
        out = []
        for _ in range(8):
            out.append(sorted(srv.step().items()))
        return out

    def test_eptp_stream_matches_single_chip(self):
        from tpushare.parallel import make_mesh
        mesh = make_mesh({"tp": 2, "ep": 2},
                         devices=jax.devices()[:4])
        assert self._stream(mesh) == self._stream(None)

    def test_eptp_kernel_interpret_matches_single_chip(self,
                                                       monkeypatch):
        # The KERNEL (interpret) under the ep×tp placement path:
        # sharded stream bit-exact vs the single-chip kernel stream.
        # On real Mosaic the sharded lowering is unvalidated until the
        # bench row banks — which is why the kernel is opt-in — but
        # the placement contract and the dispatch seam must already
        # hold here.
        from tpushare.parallel import make_mesh
        monkeypatch.setenv(qe.Q8_EXPERT_KERNEL_ENV, "interpret")

        def stream(mesh):
            specs = (quant.quant_moe_param_specs(CFG128) if mesh
                     else None)
            srv = _server(QPARAMS128, CFG128,
                          layers_hook=quant.fused_expert_hook(CFG128),
                          mesh=mesh, param_specs=specs)
            srv.admit(_prompt(23, 5, CFG128.vocab_size))
            return [sorted(srv.step().items()) for _ in range(6)]

        mesh = make_mesh({"tp": 2, "ep": 2},
                         devices=jax.devices()[:4])
        assert stream(mesh) == stream(None)


class TestPhaseTimerSeam:
    """The measurement-mode half of the tentpole: instrumented eager
    forward == the jitted scan, per-phase accounting covers the step,
    and the seam can never leak into a jitted hot path."""

    def _cache_decode(self, hook, phase_timer=None):
        cache = moe.init_cache(CFG, 2, 32)
        toks = jnp.stack([_prompt(31, 8), _prompt(32, 8)])
        lg, _, cache = moe.forward(QPARAMS, toks, CFG, cache=cache,
                                   pos_offset=0, layers_hook=hook)
        pos = jnp.full((2,), 8, jnp.int32)
        if phase_timer is not None:
            phase_timer.start()
        return moe.forward(QPARAMS, jnp.argmax(lg[:, -1:], -1)
                           .astype(jnp.int32), CFG, cache=cache,
                           pos_offset=pos, layers_hook=hook,
                           phase_timer=phase_timer)

    @pytest.mark.parametrize("hookname", ["dequant", "fused"])
    def test_instrumented_matches_jitted_scan(self, hookname):
        hook = (quant.dequant_hook(CFG) if hookname == "dequant"
                else quant.fused_expert_hook(CFG))
        pt = profiling.PhaseTimer()
        lg_i, _, cache_i = self._cache_decode(hook, pt)
        lg_j, _, cache_j = self._cache_decode(hook)
        np.testing.assert_allclose(np.asarray(lg_i), np.asarray(lg_j),
                                   rtol=1e-5, atol=1e-5)
        assert (jnp.argmax(lg_i[:, 0], -1)
                == jnp.argmax(lg_j[:, 0], -1)).all()
        np.testing.assert_allclose(np.asarray(cache_i["k"]),
                                   np.asarray(cache_j["k"]),
                                   rtol=1e-6, atol=1e-6)

    def test_phases_cover_the_decode_step(self):
        pt = profiling.PhaseTimer()
        self._cache_decode(quant.dequant_hook(CFG), pt)
        snap = pt.snapshot()
        for ph in ("embed", "dequant", "attn", "router",
                   "expert_gemm", "unembed"):
            assert ph in snap, (ph, sorted(snap))
        total = sum(r["fraction"] for r in snap.values())
        assert total == pytest.approx(1.0, abs=0.01)

    def test_fused_hook_still_marks_dequant_phase(self):
        # The fused hook widens only the attention leaves — the
        # dequant phase exists (the attention widening) but the
        # expert widening is gone from it by construction.
        pt = profiling.PhaseTimer()
        self._cache_decode(quant.fused_expert_hook(CFG), pt)
        assert "dequant" in pt.snapshot()

    def test_timer_under_jit_raises(self):
        pt = profiling.PhaseTimer()
        with pytest.raises(ValueError, match="measurement-mode"):
            jax.jit(lambda p, t: moe.forward(p, t, CFG,
                                             phase_timer=pt))(
                PARAMS, jnp.zeros((1, 4), jnp.int32))

    def test_phase_bytes_cover_step_total(self):
        # The per-phase floors must partition the aggregate roofline
        # denominator bench_moe uses: params streamed once + live KV.
        kv_tokens = 16
        pb = moe.decode_phase_bytes(CFG, QPARAMS, kv_tokens)
        params_bytes = sum(x.nbytes for x in jax.tree.leaves(QPARAMS))
        kv_row = 2 * CFG.n_kv_heads * CFG.head_dim * jnp.dtype(
            CFG.dtype).itemsize
        assert sum(pb.values()) == params_bytes + kv_tokens * \
            CFG.n_layers * kv_row
        # Expert floor is the STORED (int8+scale) width — the whole
        # point of the phase table.
        lx = QPARAMS["layers"]
        assert pb["expert_gemm"] == sum(
            lx[k].nbytes for k in lx if k.startswith(("w_gate",
                                                      "w_up",
                                                      "w_down")))

    def test_phase_roofline_table_shape(self):
        pt = profiling.PhaseTimer()
        self._cache_decode(quant.dequant_hook(CFG), pt)
        pb = moe.decode_phase_bytes(CFG, QPARAMS, 16)
        table = profiling.phase_roofline(pt.snapshot(), pb, 1,
                                         on_chip=False)
        for row in table.values():
            assert set(row) == {"fraction", "ms_per_step",
                                "bytes_per_step_mib",
                                "pct_of_roofline"}
            assert row["pct_of_roofline"] is None      # off-chip
        on = profiling.phase_roofline(pt.snapshot(), pb, 1,
                                      generation="v5e", on_chip=True)
        assert on["attn"]["pct_of_roofline"] is not None
        assert on["dispatch"]["pct_of_roofline"] is None  # 0-byte


def test_analysis_q8_seam_clean():
    """JC801 pin (the kernel-dispatch-seam-memoized satellite): the
    fused path's modules carry zero unbaselined findings — the hook
    is lru_cached, the kernel wrappers are module-level jits, so no
    per-call pallas_call rebuild is reachable from tick methods —
    and no finding of any other family landed with the seam either."""
    import os
    from tpushare.analysis import baseline as baseline_mod
    from tpushare.analysis.config import load_config
    from tpushare.analysis.engine import analyze_paths
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    config = load_config(root=repo)
    findings = analyze_paths(
        [os.path.join(repo, "tpushare", "ops", "q8_expert.py"),
         os.path.join(repo, "tpushare", "models", "quant.py"),
         os.path.join(repo, "tpushare", "models", "moe.py")], config)
    entries = baseline_mod.load(config.resolve(config.baseline))
    new, _ = baseline_mod.diff(findings, entries)
    assert new == [], [f.render() for f in new]


def test_jc801_would_catch_unmemoized_fused_hook(tmp_path):
    """Red proof for the memoization pin above: strip the lru_cache
    off fused_expert_hook and JC801 fires — the clean gate is
    protection, not blindness."""
    import os
    from tpushare.analysis.config import load_config
    from tpushare.analysis.engine import all_rules, analyze_file
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    src = open(os.path.join(repo, "tpushare", "models",
                            "quant.py")).read()
    stripped = src.replace(
        "@functools.lru_cache(maxsize=None)\ndef fused_expert_hook",
        "def fused_expert_hook")
    assert stripped != src, "anchor drifted: fused_expert_hook no " \
        "longer directly under lru_cache"
    bad = tmp_path / "quant_red.py"
    bad.write_text(stripped)
    config = load_config(root=repo)
    findings = analyze_file(str(bad), config,
                            rules=[r for r in all_rules()
                                   if r.id == "JC801"],
                            respect_scope=False)
    assert any(f.rule == "JC801" and "fused_expert_hook" in f.message
               for f in findings), [f.render() for f in findings]
