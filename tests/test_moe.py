"""MoE LM: routing correctness, forward shapes, and ep×tp SPMD parity
with single-device execution (the critical check: vma-aware transpose
must produce full replicated-param grads under expert parallelism)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpushare.models import moe
from tpushare.models.transformer import ParallelCtx
from tpushare.parallel import make_mesh, shard_tree

CFG = moe.tiny(remat=False)


def _params(cfg=CFG, seed=0):
    return moe.init_params(jax.random.PRNGKey(seed), cfg)


def _tokens(cfg=CFG, batch=2, seq=16, seed=1):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)))


class TestForward:
    def test_shapes_and_finiteness(self):
        logits, aux = moe.forward(_params(), _tokens(), CFG)
        assert logits.shape == (2, 16, CFG.vocab_size)
        assert np.isfinite(np.asarray(logits)).all()
        assert float(aux) > 0

    def test_causality(self):
        params, toks = _params(), _tokens()
        l1, _ = moe.forward(params, toks, CFG)
        toks2 = toks.at[:, -1].set((toks[:, -1] + 1) % CFG.vocab_size)
        l2, _ = moe.forward(params, toks2, CFG)
        np.testing.assert_allclose(np.asarray(l1[:, :-1]),
                                   np.asarray(l2[:, :-1]),
                                   rtol=1e-5, atol=1e-5)

    def test_topk_mass_normalized(self):
        # Each token's combine weights sum to 1 across experts.
        params, toks = _params(), _tokens()
        h = params["embed"][toks]
        layer = jax.tree.map(lambda x: x[0], params["layers"])
        out, _ = moe._moe_ffn(h, layer, CFG, ParallelCtx(), None)
        assert out.shape == h.shape

    def test_aux_loss_balanced_router_is_one(self):
        # With perfectly uniform routing probs the Switch aux loss is
        # E * E*(1/E * 1/E)... = 1 when fraction==uniform and probs uniform.
        cfg = moe.tiny(n_experts=4, top_k=4)  # route to all -> frac=1? no:
        # top_k == E means every expert gets every token: frac_e = 1,
        # mean_p = 1/E, aux = E * sum(1 * 1/E) = E * 1 = ... compute:
        params = moe.init_params(jax.random.PRNGKey(0), cfg)
        # zero the router -> uniform probs
        params["layers"]["router"] = jnp.zeros_like(
            params["layers"]["router"])
        _, aux = moe.forward(params, _tokens(cfg), cfg)
        np.testing.assert_allclose(float(aux), cfg.n_experts, rtol=1e-5)


class TestSpmd:
    def test_ep_tp_step_matches_single_device(self):
        cfg = moe.tiny(remat=False)
        params = _params(cfg)
        toks = _tokens(cfg, batch=4, seq=16)

        ref_params, ref_loss = moe.sgd_train_step(params, toks, cfg, lr=0.1)

        mesh = make_mesh({"dp": 2, "ep": 2, "tp": 2})
        step = moe.make_spmd_train_step(cfg, mesh, lr=0.1)
        sharded = shard_tree(params, mesh, moe.param_specs(cfg))
        new_params, loss = step(sharded, toks)

        np.testing.assert_allclose(float(loss), float(ref_loss),
                                   rtol=1e-5, atol=1e-6)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5),
            new_params, ref_params)

    def test_ep_must_divide_experts(self):
        cfg = moe.tiny(n_experts=3)
        mesh = make_mesh({"ep": 2, "tp": -1})
        with pytest.raises(ValueError, match="divide"):
            moe.make_spmd_train_step(cfg, mesh)


class TestCapacityDispatch:
    def test_generous_capacity_matches_dense(self):
        # C >= T: nothing drops, grouped == dense up to fp order.
        cfg_d = moe.tiny(remat=False)
        cfg_c = moe.tiny(remat=False,
                         capacity_factor=cfg_d.n_experts / cfg_d.top_k)
        params, toks = _params(cfg_d), _tokens(cfg_d)
        ld, _ = moe.forward(params, toks, cfg_d)
        lc, _ = moe.forward(params, toks, cfg_c)
        np.testing.assert_allclose(np.asarray(ld), np.asarray(lc),
                                   rtol=2e-4, atol=2e-4)

    def test_overflow_drops_in_token_order(self):
        # Tight capacity: grouped output == the dense formula with the
        # dropped assignments' combine weights zeroed, computed by an
        # independent numpy replay of the first-come-in-token-order rule.
        cfg = moe.tiny(remat=False, capacity_factor=0.5)
        params = _params(cfg)
        toks = _tokens(cfg, batch=2, seq=16)
        h = params["embed"][toks].astype(cfg.dtype)
        layer = jax.tree.map(lambda x: x[0], params["layers"])

        got, _ = moe._moe_ffn(h, layer, cfg, ParallelCtx(), None)

        B, S, _ = h.shape
        T, E, K = B * S, cfg.n_experts, cfg.top_k
        C = moe.expert_capacity(T, cfg)
        logits = np.asarray((h @ layer["router"]).astype(jnp.float32))
        probs = np.asarray(jax.nn.softmax(logits, axis=-1)).reshape(T, E)
        top_i = np.argsort(-probs, axis=-1, kind="stable")[:, :K]
        top_w = np.take_along_axis(probs, top_i, axis=1)
        top_w /= np.maximum(top_w.sum(-1, keepdims=True), 1e-9)
        fill = {e: 0 for e in range(E)}
        combine = np.zeros((T, E), np.float32)
        for t in range(T):
            for k in range(K):
                e = int(top_i[t, k])
                if fill[e] < C:
                    combine[t, e] = top_w[t, k]
                fill[e] += 1
        hc = np.asarray(h).reshape(T, -1)
        want = np.zeros_like(hc)
        act = {"silu": jax.nn.silu, "gelu": jax.nn.gelu}[cfg.act]
        for e in range(E):
            gate = hc @ np.asarray(layer["w_gate"][e])
            up = hc @ np.asarray(layer["w_up"][e])
            y = (np.asarray(act(gate)) * up) @ np.asarray(layer["w_down"][e])
            want += combine[:, e:e + 1] * y
        np.testing.assert_allclose(np.asarray(got).reshape(T, -1), want,
                                   rtol=2e-4, atol=2e-4)

    def test_ep_tp_step_matches_single_device(self):
        cfg = moe.tiny(remat=False, capacity_factor=1.5)
        params = _params(cfg)
        toks = _tokens(cfg, batch=4, seq=16)
        ref_params, ref_loss = moe.sgd_train_step(params, toks, cfg, lr=0.1)
        mesh = make_mesh({"dp": 1, "ep": 4, "tp": 2})
        step = moe.make_spmd_train_step(cfg, mesh, lr=0.1)
        sharded = shard_tree(params, mesh, moe.param_specs(cfg))
        new_params, loss = step(sharded, toks)
        np.testing.assert_allclose(float(loss), float(ref_loss),
                                   rtol=1e-5, atol=1e-6)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5),
            new_params, ref_params)


class TestA2ARouting:
    """all_to_all token routing: ep shards the data; tokens travel to
    their expert owners and back. With generous capacity (no drops) the
    result must match the single-device dense step exactly — including
    gradients through both all_to_alls."""

    def test_step_matches_single_device_dense(self):
        cfg_ref = moe.tiny(remat=False)
        cfg = moe.tiny(remat=False, routing="a2a",
                       capacity_factor=cfg_ref.n_experts / cfg_ref.top_k)
        params = _params(cfg_ref)
        toks = _tokens(cfg_ref, batch=4, seq=16)
        ref_params, ref_loss = moe.sgd_train_step(params, toks, cfg_ref,
                                                  lr=0.1)
        mesh = make_mesh({"dp": 1, "ep": 4, "tp": 2})
        step = moe.make_spmd_train_step(cfg, mesh, lr=0.1)
        sharded = shard_tree(params, mesh, moe.param_specs(cfg))
        new_params, loss = step(sharded, toks)
        np.testing.assert_allclose(float(loss), float(ref_loss),
                                   rtol=1e-5, atol=1e-6)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5),
            new_params, ref_params)

    def test_tight_capacity_runs_and_is_finite(self):
        # Per-source-rank capacity drop semantics differ from the
        # single-rank order under overflow (documented); the step must
        # still run and stay finite.
        cfg = moe.tiny(remat=False, routing="a2a", capacity_factor=0.5)
        params = _params(cfg)
        toks = _tokens(cfg, batch=4, seq=16)
        mesh = make_mesh({"dp": 2, "ep": 2, "tp": 2})
        step = moe.make_spmd_train_step(cfg, mesh, lr=0.1)
        sharded = shard_tree(params, mesh, moe.param_specs(cfg))
        _, loss = step(sharded, toks)
        assert np.isfinite(float(loss))

    def test_a2a_requires_capacity(self):
        cfg = moe.tiny(remat=False, routing="a2a")
        params = _params(cfg)
        toks = _tokens(cfg, batch=4, seq=16)
        mesh = make_mesh({"dp": 1, "ep": 4, "tp": 2})
        step = moe.make_spmd_train_step(cfg, mesh, lr=0.1)
        sharded = shard_tree(params, mesh, moe.param_specs(cfg))
        with pytest.raises(ValueError, match="capacity_factor"):
            step(sharded, toks)


class TestDroplessRouting:
    """ragged_dot grouped-GEMM dispatch: exact MoE (no capacity bound),
    must equal the dense formulation bit-for-bit up to fp order, single
    device and under ep x tp."""

    def test_matches_dense_single_device(self):
        cfg_d = moe.tiny(remat=False)
        cfg = moe.tiny(remat=False, routing="dropless")
        params, toks = _params(cfg_d), _tokens(cfg_d)
        ld, auxd = moe.forward(params, toks, cfg_d)
        lr, auxr = moe.forward(params, toks, cfg)
        np.testing.assert_allclose(np.asarray(ld), np.asarray(lr),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(float(auxd), float(auxr), rtol=1e-6)

    def test_ep_tp_step_matches_single_device(self):
        cfg = moe.tiny(remat=False, routing="dropless")
        params = _params(cfg)
        toks = _tokens(cfg, batch=4, seq=16)
        ref_params, ref_loss = moe.sgd_train_step(params, toks, cfg, lr=0.1)
        mesh = make_mesh({"dp": 1, "ep": 4, "tp": 2})
        step = moe.make_spmd_train_step(cfg, mesh, lr=0.1)
        sharded = shard_tree(params, mesh, moe.param_specs(cfg))
        new_params, loss = step(sharded, toks)
        np.testing.assert_allclose(float(loss), float(ref_loss),
                                   rtol=1e-5, atol=1e-6)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5),
            new_params, ref_params)


class TestMoEAdamW:
    def test_spmd_matches_single_device(self):
        from tpushare.models.training import adamw_init
        cfg = moe.tiny(remat=False)
        params = _params(cfg)
        toks = _tokens(cfg, batch=4, seq=16)
        ref_p, ref_s = params, adamw_init(params)
        for _ in range(2):
            ref_p, ref_s, ref_loss = moe.adamw_train_step(
                ref_p, ref_s, toks, cfg, lr=0.01, weight_decay=0.1)

        mesh = make_mesh({"dp": 2, "ep": 2, "tp": 2})
        step, opt_init = moe.make_adamw_spmd_train_step(
            cfg, mesh, lr=0.01, weight_decay=0.1)
        sharded = shard_tree(params, mesh, moe.param_specs(cfg))
        p, s = sharded, opt_init(sharded)
        for _ in range(2):
            p, s, loss = step(p, s, toks)
        np.testing.assert_allclose(float(loss), float(ref_loss),
                                   rtol=1e-5, atol=1e-6)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=5e-3, atol=5e-4),
            p, ref_p)
        assert int(s["count"]) == 2


class TestExpertChoice:
    def test_every_expert_processes_exactly_capacity(self):
        # First-principles check of the headline EC invariant: each
        # expert independently processes the C = ceil(T·K/E) tokens
        # with the highest router score FOR THAT EXPERT, weighted by
        # that score, scatter-added over the token axis. Expected
        # output is recomputed here with numpy argsort per expert —
        # a wrong top_k axis, wrong C, or a gather/scatter mixup in
        # _expert_choice_dispatch all diverge from it.
        import math

        from tpushare.models.transformer import _act

        rng = np.random.default_rng(7)
        B, S, Dm, F, E = 1, 8, 4, 6, 4
        cfg = moe.tiny(d_model=Dm, d_ff=F, n_experts=E, top_k=2,
                       remat=False, routing="expert_choice")
        T = B * S
        C = moe.expert_capacity(T, cfg, default_factor=1.0)
        assert C == math.ceil(T * cfg.top_k / E)   # 4 < T: real selection

        h = jnp.asarray(rng.normal(size=(B, S, Dm)), jnp.float32)
        probs = jnp.asarray(rng.random((B, S, E)), jnp.float32)  # no ties
        layer = {
            "w_gate": jnp.asarray(rng.normal(size=(E, Dm, F)) * 0.3,
                                  jnp.float32),
            "w_up": jnp.asarray(rng.normal(size=(E, Dm, F)) * 0.3,
                                jnp.float32),
            "w_down": jnp.asarray(rng.normal(size=(E, F, Dm)) * 0.3,
                                  jnp.float32),
        }
        got = np.asarray(moe._expert_choice_dispatch(
            h, layer, cfg, ParallelCtx(), None, probs))

        p = np.asarray(probs).reshape(T, E)
        x = np.asarray(h).reshape(T, Dm)
        expected = np.zeros((T, Dm), np.float32)
        for e in range(E):
            picked = np.argsort(-p[:, e])[:C]      # expert e's top-C tokens
            for t in picked:
                xe = x[t]
                ff = (np.asarray(_act(cfg.act,
                                      jnp.asarray(xe @ layer["w_gate"][e])))
                      * (xe @ np.asarray(layer["w_up"][e])))
                expected[t] += p[t, e] * (ff @ np.asarray(layer["w_down"][e]))
        # The allclose IS the invariant check: `expected` applies each
        # expert to exactly its C highest-scoring tokens and nothing
        # else, so an implementation that picks more, fewer, or
        # different tokens (wrong top_k axis, wrong C) diverges.
        np.testing.assert_allclose(got.reshape(T, Dm), expected,
                                   rtol=2e-5, atol=2e-6)

    def test_forward_finite_no_aux_router_grad(self):
        # Output differs from dense (tokens may be picked by 0..E
        # experts) but is finite, aux is zero by construction, and the
        # router gradient flows.
        cfg = moe.tiny(remat=False, routing="expert_choice")
        params = _params(cfg)
        toks = _tokens(cfg)
        logits, aux = moe.forward(params, toks, cfg)
        assert float(aux) == 0.0                 # no aux by construction
        assert np.isfinite(np.asarray(logits)).all()
        _, g = jax.value_and_grad(
            lambda p: moe.lm_loss(p, toks, cfg))(params)
        assert float(jnp.abs(g["layers"]["router"]).sum()) > 0

    def test_loss_decreases(self):
        cfg = moe.tiny(remat=False, routing="expert_choice")
        params = _params(cfg)
        toks = _tokens(cfg)
        l0 = moe.lm_loss(params, toks, cfg)
        for _ in range(3):
            params, loss = moe.sgd_train_step(params, toks, cfg, lr=0.5)
        assert float(loss) < float(l0)

    def test_ep_tp_step_matches_single_device(self):
        # ep x tp only: expert-choice selections are BATCH-LOCAL (each
        # shard's experts pick from its own tokens), so dp/sp sharding
        # legitimately changes which tokens are picked — the same
        # per-shard semantics every EC trainer has. With the batch
        # unsharded, ep x tp must match single-device exactly.
        cfg = moe.tiny(remat=False, routing="expert_choice")
        params = _params(cfg)
        toks = _tokens(cfg, batch=4, seq=16)
        ref_params, ref_loss = moe.sgd_train_step(params, toks, cfg,
                                                  lr=0.1)
        mesh = make_mesh({"ep": 4, "tp": 2})
        step = moe.make_spmd_train_step(cfg, mesh, lr=0.1)
        sharded = shard_tree(params, mesh, moe.param_specs(cfg))
        new_params, loss = step(sharded, toks)
        np.testing.assert_allclose(float(loss), float(ref_loss),
                                   rtol=1e-5, atol=1e-6)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5),
            new_params, ref_params)

    def test_pipeline_composes(self):
        from tpushare.models.moe_pipeline import (make_moe_pp_train_step,
                                                  param_specs)
        cfg = moe.tiny(remat=False, n_layers=4, routing="expert_choice")
        params = _params(cfg)
        toks = _tokens(cfg, batch=4, seq=16)
        mesh = make_mesh({"pp": 2, "ep": 2, "tp": 2})
        step = make_moe_pp_train_step(cfg, mesh, n_microbatches=2, lr=0.1)
        _, loss = step(shard_tree(params, mesh, param_specs(cfg)), toks)
        assert np.isfinite(float(loss))


class TestMoEInference:
    """Cache-aware MoE decode (VERDICT world: Mixtral-style inference,
    not just training): prefill-with-cache must match the plain
    forward, scanned ragged decode must match full recompute token by
    token, and every routing strategy decodes unchanged (experts hold
    no decode state — KV rows are the whole cache)."""

    def test_prefill_with_cache_matches_forward(self):
        params = _params()
        toks = _tokens(seq=12)
        want, _ = moe.forward(params, toks, CFG)
        cache = moe.init_cache(CFG, toks.shape[0], 20)
        got, _, cache = moe.forward(params, toks, CFG, cache=cache,
                                    pos_offset=0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        # KV rows written exactly over [0, S): the tail stays zero.
        assert not np.allclose(np.asarray(cache["k"][:, :, :12]), 0.0)
        assert np.all(np.asarray(cache["k"][:, :, 12:]) == 0.0)

    @pytest.mark.parametrize("routing,kw", [
        ("psum", {}),
        ("psum", {"capacity_factor": 2.0}),
        ("dropless", {}),
        ("expert_choice", {"capacity_factor": 2.0}),
    ])
    def test_generate_matches_full_recompute(self, routing, kw):
        """Greedy cached generation == argmax over the full forward at
        every position — the gold-standard KV-cache parity, per
        routing strategy."""
        cfg = moe.tiny(remat=False, routing=routing, **kw)
        params = _params(cfg, seed=3)
        toks = _tokens(cfg, batch=2, seq=7, seed=4)
        out = moe.generate(params, toks, cfg, max_new_tokens=6)
        assert out.shape == (2, 13)
        cur = toks
        for _ in range(6):
            logits, _ = moe.forward(params, cur, cfg)
            nxt = jnp.argmax(logits[:, -1], axis=-1)[:, None]
            cur = jnp.concatenate([cur, nxt.astype(cur.dtype)], axis=1)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(cur))

    def test_ragged_decode_rows_advance_independently(self):
        """Two rows at different lengths: each row's decode logits must
        equal its own full-recompute logits (the [B] pos_offset ragged
        contract)."""
        params = _params()
        rng = np.random.default_rng(9)
        l0, l1 = 9, 5
        p0 = jnp.asarray(rng.integers(0, CFG.vocab_size, l0))
        p1 = jnp.asarray(rng.integers(0, CFG.vocab_size, l1))
        M = 16
        cache = moe.init_cache(CFG, 2, M)
        # Prefill each row alone at its own length (row-batched prefill
        # of ragged prompts is the servers' job; here: correctness).
        for b, p in ((0, p0), (1, p1)):
            row = moe.init_cache(CFG, 1, M)
            _, _, row = moe.forward(params, p[None, :], CFG, cache=row,
                                    pos_offset=0)
            cache = {
                "k": cache["k"].at[:, b].set(row["k"][:, 0]),
                "v": cache["v"].at[:, b].set(row["v"][:, 0]),
            }
        # The prompts' KV is in the cache; decode each row's NEXT
        # token (its greedy continuation) at its own length.
        nxt = []
        for p in (p0, p1):
            lg, _ = moe.forward(params, p[None, :], CFG)
            nxt.append(int(jnp.argmax(lg[0, -1])))
        step_tokens = jnp.asarray([[nxt[0]], [nxt[1]]])
        lengths = jnp.asarray([l0, l1], jnp.int32)
        lg, _, cache = moe.forward(params, step_tokens, CFG, cache=cache,
                                   pos_offset=lengths)
        for b, p in ((0, p0), (1, p1)):
            full = jnp.concatenate([p, step_tokens[b]])
            want, _ = moe.forward(params, full[None, :], CFG)
            np.testing.assert_allclose(np.asarray(lg[b, 0]),
                                       np.asarray(want[0, -1]),
                                       rtol=2e-4, atol=2e-4)

    def test_sampled_generation_reproducible_and_in_vocab(self):
        params = _params()
        toks = _tokens(batch=2, seq=5, seed=6)
        a = moe.generate(params, toks, CFG, max_new_tokens=8,
                         temperature=0.9, top_p=0.9,
                         rng=jax.random.PRNGKey(5))
        b = moe.generate(params, toks, CFG, max_new_tokens=8,
                         temperature=0.9, top_p=0.9,
                         rng=jax.random.PRNGKey(5))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert np.all((np.asarray(a) >= 0)
                      & (np.asarray(a) < CFG.vocab_size))

    def test_ep_decode_step_matches_single_device(self):
        """One ragged decode step under an ep shard_map == the
        single-device step: expert parallelism composes with the KV
        cache (the cache shards over nothing; experts shard over ep)."""
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        cfg = moe.tiny(remat=False)
        params = _params(cfg, seed=2)
        toks = _tokens(cfg, batch=2, seq=6, seed=7)
        cache = moe.init_cache(cfg, 2, 8)
        _, _, cache = moe.forward(params, toks, cfg, cache=cache,
                                  pos_offset=0)
        step = jnp.asarray([[3], [5]], jnp.int32)
        lengths = jnp.asarray([6, 6], jnp.int32)
        want, _, _ = moe.forward(params, step, cfg, cache=cache,
                                 pos_offset=lengths)

        mesh = make_mesh({"ep": 4, "dp": -1})
        specs = moe.param_specs(cfg)
        sharded = shard_tree(params, mesh, specs)

        @partial(shard_map, mesh=mesh,
                 in_specs=(specs, P(), P(), P()), out_specs=P())
        def ep_step(p, t, c_k, c_v):
            # tp rides along (size 1 here): params are tp-sharded by
            # the specs, and the tp psum also resets their vma so the
            # layer-scan carry stays consistent.
            lg, _, _ = moe.forward(p, t, cfg,
                                   cache={"k": c_k, "v": c_v},
                                   pos_offset=lengths, ep_axis="ep",
                                   pctx=ParallelCtx(tp="tp"))
            return lg
        got = ep_step(sharded, step, cache["k"], cache["v"])
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)


class TestMoEInt8:
    """Int8 expert weights through forward's layers_hook seam:
    quant._QUANT_KEYS already names w_gate/w_up/w_down and its
    per-output-channel scale logic is rank-generic, so the rank-4
    expert stacks [L, E, Dm, F] quantize with [L, E, 1, F] scales and
    quant.dequant_hook serves unchanged. MoE decode streams all
    experts from HBM every step — int8 halves that floor
    (benchmarks/bench_moe.py measures it)."""

    def test_expert_stacks_quantize_router_stays_fp(self):
        from tpushare.models import quant
        params = _params()
        qp = quant.quantize_params(params, CFG)
        L, E, Dm, F = (CFG.n_layers, CFG.n_experts, CFG.d_model,
                       CFG.d_ff)
        assert qp["layers"]["w_gate#q8"].dtype == jnp.int8
        assert qp["layers"]["w_gate#q8"].shape == (L, E, Dm, F)
        assert qp["layers"]["w_gate#scale"].shape == (L, E, 1, F)
        assert qp["layers"]["w_down#scale"].shape == (L, E, 1, Dm)
        # Routing argmaxes are precision-sensitive; the router leaf is
        # tiny — it must stay full precision.
        assert qp["layers"]["router"].dtype == params["layers"][
            "router"].dtype
        assert "w_gate" not in qp["layers"]

    def test_logits_close_to_full_precision(self):
        from tpushare.models import quant
        params, toks = _params(), _tokens()
        ref, _ = moe.forward(params, toks, CFG)
        qp = quant.quantize_params(params, CFG)
        got, _ = moe.forward(qp, toks, CFG,
                             layers_hook=quant.dequant_hook(CFG))
        pr = jax.nn.softmax(ref, axis=-1)
        pq = jax.nn.softmax(got, axis=-1)
        tv = 0.5 * jnp.sum(jnp.abs(pr - pq), axis=-1)
        assert float(jnp.max(tv)) < 0.05

    @pytest.mark.parametrize("routing,kw", [
        ("psum", {}),
        ("dropless", {}),
        ("psum", {"capacity_factor": 2.0}),
    ])
    def test_greedy_generate_mostly_agrees(self, routing, kw):
        from tpushare.models import quant
        cfg = moe.tiny(remat=False, routing=routing, **kw)
        params = moe.init_params(jax.random.PRNGKey(0), cfg)
        toks = _tokens(cfg)
        qp = quant.quantize_params(params, cfg)
        got = moe.generate(qp, toks, cfg, max_new_tokens=8,
                           layers_hook=quant.dequant_hook(cfg))
        want = moe.generate(params, toks, cfg, max_new_tokens=8)
        assert got.shape == want.shape
        agree = float(jnp.mean((got[:, 16:] == want[:, 16:]).astype(
            jnp.float32)))
        assert agree >= 0.75, f"int8 MoE greedy agreement {agree}"


class TestMoESpeculative:
    """speculative_generate/sample(model="moe"): the dense loops run
    unchanged on moe.forward through speculative._model_fns — exact
    greedy parity vs moe.generate for ANY draft (the draft only
    affects speed), every routing strategy, and composing with int8
    self-drafts via draft_layers_hook."""

    @pytest.mark.parametrize("routing", ["psum", "dropless"])
    def test_greedy_exact_vs_generate_imperfect_draft(self, routing):
        from tpushare.models.speculative import speculative_generate
        cfg = moe.tiny(remat=False, routing=routing)
        params = moe.init_params(jax.random.PRNGKey(0), cfg)
        draft = moe.init_params(jax.random.PRNGKey(7), cfg)
        toks = _tokens(cfg, batch=2, seq=7)
        want = moe.generate(params, toks, cfg, max_new_tokens=16)
        got = speculative_generate(params, draft, toks, cfg,
                                   max_new_tokens=16, gamma=4,
                                   model="moe")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_int8_self_draft_greedy_exact_and_high_acceptance(self):
        from tpushare.models import quant
        from tpushare.models.speculative import speculative_generate
        cfg = moe.tiny(remat=False, routing="dropless")
        params = moe.init_params(jax.random.PRNGKey(0), cfg)
        qp = quant.quantize_params(params, cfg)
        toks = _tokens(cfg, batch=2, seq=7)
        want = moe.generate(params, toks, cfg, max_new_tokens=16)
        got = speculative_generate(
            params, qp, toks, cfg, max_new_tokens=16, gamma=3,
            draft_layers_hook=quant.dequant_hook(cfg), model="moe")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_perfect_self_draft_exact(self):
        from tpushare.models.speculative import speculative_generate
        cfg = moe.tiny(remat=False)
        params = moe.init_params(jax.random.PRNGKey(0), cfg)
        toks = _tokens(cfg, batch=3, seq=5, seed=2)
        want = moe.generate(params, toks, cfg, max_new_tokens=11)
        got = speculative_generate(params, params, toks, cfg,
                                   max_new_tokens=11, gamma=4,
                                   model="moe")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_sample_reproducible_and_in_vocab(self):
        from tpushare.models.speculative import speculative_sample
        cfg = moe.tiny(remat=False)
        params = moe.init_params(jax.random.PRNGKey(0), cfg)
        draft = moe.init_params(jax.random.PRNGKey(3), cfg)
        toks = _tokens(cfg, batch=2, seq=6, seed=4)
        key = jax.random.PRNGKey(42)
        a = speculative_sample(params, draft, toks, cfg, rng=key,
                               max_new_tokens=12, gamma=3,
                               temperature=0.9, model="moe")
        b = speculative_sample(params, draft, toks, cfg, rng=key,
                               max_new_tokens=12, gamma=3,
                               temperature=0.9, model="moe")
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        new = np.asarray(a[:, 6:])
        assert new.shape == (2, 12)
        assert ((new >= 0) & (new < cfg.vocab_size)).all()

    def test_sample_first_token_matches_target_law(self):
        # Same TV-vs-multinomial-null methodology as the dense
        # TestSpeculativeSampling: the emitted law must be the MoE
        # TARGET's softmax regardless of the (mismatched) draft — this
        # pins the distribution path of the moe adapter, not just
        # reproducibility.
        from tpushare.models.speculative import speculative_sample
        cfg = moe.tiny(remat=False, vocab_size=16)
        params = moe.init_params(jax.random.PRNGKey(0), cfg)
        draft = moe.init_params(jax.random.PRNGKey(11), cfg)
        toks = jnp.asarray(
            np.random.default_rng(3).integers(0, 16, (1, 5)))
        logits, _ = moe.forward(params, toks, cfg)
        p_true = np.asarray(jax.nn.softmax(logits[0, -1]), np.float64)
        p_true /= p_true.sum()
        n = 400
        keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(100, 100 + n))
        outs = jax.vmap(lambda k: speculative_sample(
            params, draft, toks, cfg, cfg, rng=k, max_new_tokens=3,
            gamma=2, temperature=1.0, model="moe"))(keys)
        first = np.bincount(np.asarray(outs[:, 0, 5]),
                            minlength=16).astype(float)
        rng = np.random.default_rng(0)
        tvs = [0.5 * np.abs(rng.multinomial(n, p_true) / n
                            - p_true).sum() for _ in range(200)]
        mu, sd = float(np.mean(tvs)), float(np.std(tvs))
        tv = 0.5 * np.abs(first / n - p_true).sum()
        assert tv < mu + 4 * sd, f"moe first-token TV {tv} vs {mu}+-{sd}"


class TestMoEShardedDecode:
    """MoE ragged decode on a REAL ep x tp mesh (tp=2, not the
    size-1 tp the other shard_map tests ride): the KV cache must
    shard kv heads over tp (serving.cache_specs contract — a
    replicated cache silently broadcasts each rank's local kv heads
    on the ragged .set()), and the int8 tree shards through the
    rank-generic quant_layer_specs (expert stacks [L, E, In, Out] ->
    scale specs [L, E, 1, Out] keeping the ep sharding)."""

    @pytest.mark.parametrize("quantized", [False, True])
    def test_ep_tp_decode_matches_single_device(self, quantized):
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from tpushare.models import quant
        cfg = moe.tiny(remat=False)
        fp = _params(cfg, seed=2)
        hook = quant.dequant_hook(cfg) if quantized else None
        params = quant.quantize_params(fp, cfg) if quantized else fp
        toks = _tokens(cfg, batch=2, seq=6, seed=7)
        cache = moe.init_cache(cfg, 2, 8)
        _, _, cache = moe.forward(fp, toks, cfg,
                                  cache=cache, pos_offset=0)
        step = jnp.asarray([[3], [5]], jnp.int32)
        lengths = jnp.asarray([6, 6], jnp.int32)
        want, _, _ = moe.forward(params, step, cfg, cache=cache,
                                 pos_offset=lengths, layers_hook=hook)

        mesh = make_mesh({"ep": 2, "tp": 2, "dp": -1})
        specs = (quant.quant_moe_param_specs(cfg) if quantized
                 else moe.param_specs(cfg))
        if quantized:
            # Scale specs must keep ep on E and tp on Out, drop In.
            assert tuple(specs["layers"]["w_gate#scale"]) == \
                (None, "ep", None, "tp")
            assert tuple(specs["layers"]["w_down#scale"]) == \
                (None, "ep", None, None)
        sharded = shard_tree(params, mesh, specs)

        cspec = P(None, None, None, "tp", None)   # kv heads over tp

        @partial(shard_map, mesh=mesh,
                 in_specs=(specs, P(), cspec, cspec), out_specs=P())
        def ep_step(p, t, c_k, c_v):
            lg, _, _ = moe.forward(p, t, cfg,
                                   cache={"k": c_k, "v": c_v},
                                   pos_offset=lengths, ep_axis="ep",
                                   pctx=ParallelCtx(tp="tp"),
                                   layers_hook=hook)
            return lg
        got = ep_step(sharded, step, cache["k"], cache["v"])
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)


class TestMoERaggedMultiToken:
    """forward's ragged mode with S > 1 (speculative verify): scoring
    a candidate block at per-row offsets must equal teacher-forced
    single-token ragged decodes, per position, per row."""

    def test_block_scores_equal_stepwise(self):
        params = _params()
        rng = np.random.default_rng(41)
        toks = _tokens(batch=2, seq=6, seed=7)
        cache = moe.init_cache(CFG, 2, 16)
        # Ragged prefixes: row 0 at 6, row 1 at 4 (prefill then trim).
        _, _, cache = moe.forward(params, toks, CFG, cache=cache,
                                  pos_offset=0)
        lengths = jnp.asarray([6, 4], jnp.int32)
        block = jnp.asarray(rng.integers(0, CFG.vocab_size, (2, 3)),
                            jnp.int32)
        want_block, _, _ = moe.forward(params, block, CFG, cache=cache,
                                       pos_offset=lengths)
        # Stepwise: feed the same tokens one at a time.
        c = dict(cache)
        lens = lengths
        for j in range(3):
            lg, _, c = moe.forward(params, block[:, j:j + 1], CFG,
                                   cache=c, pos_offset=lens)
            np.testing.assert_allclose(np.asarray(want_block[:, j]),
                                       np.asarray(lg[:, 0]),
                                       rtol=2e-5, atol=2e-5)
            lens = lens + 1
