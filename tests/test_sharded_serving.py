"""Sharded multi-chip ServeEngine (ISSUE 7): the slot servers span a
NamedSharding mesh — tensor-parallel dense, expert x tensor-parallel
MoE, KV pools split on the kv-head axis — and every decode
stream, chunked admission, fused tick, and greedy speculation round is
BIT-EXACT vs the single-chip engine (the correctness oracle: placement
alone makes the same jitted code compile SPMD, so tokens must not
change). Runs without TPUs under forced host devices
(tests/conftest.py forces 8; the CI sharded job forces 4 — the meshes
below use prefixes of the first 4 devices so both environments work).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpushare.models import moe, quant
from tpushare.models import transformer as tf
from tpushare.models.paged import PagedSlotServer
from tpushare.parallel import make_mesh, parse_mesh_spec, serving_mesh

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=4+")

TF_CFG = tf.tiny(remat=False)
TF_PARAMS = tf.init_params(jax.random.PRNGKey(0), TF_CFG)
MOE_CFG = moe.tiny(remat=False)
MOE_PARAMS = moe.init_params(jax.random.PRNGKey(0), MOE_CFG)
MOE_QDRAFT = quant.quantize_params(MOE_PARAMS, MOE_CFG)


def _mesh_tp():
    return make_mesh({"tp": 2}, devices=jax.devices()[:2])


def _mesh_eptp():
    return make_mesh({"tp": 2, "ep": 2}, devices=jax.devices()[:4])


def _prompt(seed, n, vocab):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, vocab, n), jnp.int32)


# mesh=None is the single-chip oracle; mesh=mk_mesh() the sharded run.
FAMILIES = {
    "paged_tp": (
        lambda mesh: PagedSlotServer(TF_PARAMS, TF_CFG, n_slots=3,
                                     n_blocks=64, block_size=4,
                                     mesh=mesh),
        _mesh_tp, TF_CFG),
    "paged_spec_tp": (
        lambda mesh: PagedSlotServer(TF_PARAMS, TF_CFG, n_slots=3,
                                     n_blocks=96, block_size=4,
                                     speculative_draft=(TF_PARAMS, TF_CFG),
                                     gamma=2, mesh=mesh),
        _mesh_tp, TF_CFG),
    # Multi-token draft horizon on-mesh (ISSUE 11): the seam's longer
    # block runs the same SPMD dispatches, so horizon-k sharded
    # streams must stay bit-exact vs the single-chip oracle too.
    "paged_spec_horizon_tp": (
        lambda mesh: PagedSlotServer(TF_PARAMS, TF_CFG, n_slots=3,
                                     n_blocks=96, block_size=4,
                                     speculative_draft=(TF_PARAMS, TF_CFG),
                                     gamma=2, spec_horizon=2,
                                     mesh=mesh),
        _mesh_tp, TF_CFG),
    "paged_moe_eptp": (
        lambda mesh: PagedSlotServer(MOE_PARAMS, MOE_CFG, n_slots=3,
                                     n_blocks=64, block_size=4,
                                     forward_fn=moe.paged_forward,
                                     mesh=mesh),
        _mesh_eptp, MOE_CFG),
    "paged_moe_spec_eptp": (
        lambda mesh: PagedSlotServer(
            MOE_PARAMS, MOE_CFG, n_slots=3, n_blocks=96, block_size=4,
            forward_fn=moe.paged_forward,
            speculative_draft=(MOE_QDRAFT, MOE_CFG), gamma=2,
            draft_layers_hook=quant.dequant_hook(MOE_CFG), mesh=mesh,
            draft_param_specs=(quant.quant_moe_param_specs(MOE_CFG)
                               if mesh is not None else None)),
        _mesh_eptp, MOE_CFG),
}


def _drive(srv, long_prompt, ticks=8, chunk=8):
    """One decode stream + one chunk-admitted long prompt riding fused
    ticks (mirrors test_fused_tick._drive). Returns every emitted
    token in schedule order — the full stream the oracle must match
    bit-for-bit."""
    vocab = srv.cfg.vocab_size
    s0 = srv.admit(_prompt(1, 6, vocab))
    streams = {s0: [int(srv.last_token[s0, 0])]}
    a = srv.admit_start(long_prompt, chunk_tokens=chunk)
    admitted = []
    for _ in range(ticks):
        if a is not None:
            out = srv.step(prefill_work=a)
            if a in out:
                admitted.append(out.pop(a))
                a = None
        else:
            out = srv.step()
        for s, t in out.items():
            streams.setdefault(s, []).extend(
                t if isinstance(t, list) else [t])
    assert a is None, "admission never completed"
    return streams, admitted


class TestShardedBitExact:
    """THE acceptance oracle: sharded paged ep x tp MoE decode (and
    dense tp decode) bit-exact vs the single-chip engine — including
    chunked admission, fused ticks, and greedy speculation."""

    @pytest.mark.parametrize("family", sorted(FAMILIES))
    def test_matches_single_chip(self, family):
        mk, mk_mesh, cfg = FAMILIES[family]
        lp = _prompt(7, 21, cfg.vocab_size)
        want = _drive(mk(None), lp)
        got = _drive(mk(mk_mesh()), lp)
        assert got == want, family

    def test_sharded_fused_matches_sharded_serial(self):
        """Fused and serial admission agree ON the mesh too (the
        fused-tick invariant survives sharding, not just placement)."""
        lp = _prompt(9, 21, TF_CFG.vocab_size)

        def run(fused):
            srv = PagedSlotServer(TF_PARAMS, TF_CFG, n_slots=3,
                                  n_blocks=64, block_size=4,
                                  mesh=_mesh_tp())
            s0 = srv.admit(_prompt(1, 6, TF_CFG.vocab_size))
            streams = {s0: [int(srv.last_token[s0, 0])]}
            a = srv.admit_start(lp, chunk_tokens=8)
            admitted = []
            for _ in range(8):
                if a is not None and fused:
                    out = srv.step(prefill_work=a)
                    if a in out:
                        admitted.append(out.pop(a))
                        a = None
                else:
                    if a is not None:
                        tok = srv.admit_step(a)
                        if tok is not None:
                            admitted.append(tok)
                            a = None
                    out = srv.step()
                for s, t in out.items():
                    streams.setdefault(s, []).append(t)
            return admitted, streams

        a1, s1 = run(True)
        a2, s2 = run(False)
        assert a1 == a2
        for s in s1:
            n = min(len(s1[s]), len(s2[s]))
            assert s1[s][:n] == s2[s][:n]

    def test_prefix_sharing_is_placement_blind(self):
        """Block ids are host-global (the pool's block axis is never
        sharded), so chain-keyed prefix sharing works unchanged on the
        mesh — same hit length, same first token, same pool counters
        as the single-chip server."""
        def run(mesh):
            srv = PagedSlotServer(MOE_PARAMS, MOE_CFG, n_slots=2,
                                  n_blocks=32, block_size=4,
                                  forward_fn=moe.paged_forward,
                                  prefix_cache=True, mesh=mesh)
            prompt = _prompt(13, 13, MOE_CFG.vocab_size)
            a = srv.admit(prompt)
            first = int(srv.last_token[a, 0])
            srv.evict(a)
            b = srv.admit(prompt)
            return (srv.last_cached_len, first,
                    int(srv.last_token[b, 0]),
                    len(srv.cache.free), srv.cache.live_blocks())

        assert run(_mesh_eptp()) == run(None)


class TestShardedEngine:
    """Engine integration on the mesh, driven synchronously: same
    tokens as the unsharded engine, forwards_per_tick == 1.0 and
    fetches_per_tick <= 1.0 hold, and /stats grows the mesh fields
    with pool counters reported pool-global."""

    PROMPTS = [[5, 9, 12, 3], list(range(40, 70)), [9, 9, 2]]

    def _run(self, mesh, **kw):
        from tpushare.cli import serve as serve_mod
        eng = serve_mod.ServeEngine(
            MOE_PARAMS, MOE_CFG, model_family="moe",
            n_slots=4, n_blocks=128, block_size=4, idle_sleep_s=0.0,
            prefill_chunk=8, mesh=mesh, **kw)
        reqs = [serve_mod._Request(list(p), 5, None)
                for p in self.PROMPTS]
        for r in reqs:
            assert eng.submit(r)
        for _ in range(400):
            if all(r.done.is_set() for r in reqs):
                break
            eng._loop_once()
        assert all(r.done.is_set() for r in reqs)
        assert all(r.error is None for r in reqs), [r.error for r in reqs]
        return eng, [r.tokens for r in reqs]

    def test_sharded_engine_matches_single_chip(self):
        _, want = self._run(None)
        eng, got = self._run(_mesh_eptp())
        assert got == want
        st = eng.stats()
        assert st["forwards_per_tick"] == 1.0
        assert st["fetches_per_tick"] is not None
        assert st["fetches_per_tick"] <= 1.0
        assert st["fused_ticks"] >= 1

    def test_stats_mesh_observability(self):
        eng, _ = self._run(_mesh_eptp())
        st = eng.stats()
        assert st["mesh_shape"] == {"ep": 2, "tp": 2}
        assert st["num_devices"] == 4
        assert st["device_fetches"] > 0
        # Pool counters are pool-GLOBAL (host-side block ids), so the
        # drained sharded engine reports exactly the same pool state
        # as the single-chip one (prefix-published blocks park on the
        # LRU, whatever the mesh) — the autoscaler reads true
        # exhaustion, never a per-shard fraction.
        eng1, _ = self._run(None)
        unsharded = eng1.stats()
        assert st["free_blocks"] == unsharded["free_blocks"]
        assert st["reclaimable_blocks"] == unsharded["reclaimable_blocks"]
        # free + LRU-reclaimable covers the whole pool (127 = 128 - 1
        # trash block): nothing leaked, nothing double-counted.
        assert st["free_blocks"] + st["reclaimable_blocks"] == 127
        assert st["live_blocks"] == unsharded["live_blocks"]
        assert unsharded["mesh_shape"] is None
        assert unsharded["num_devices"] == 1


class TestElasticShrink:
    """Mesh failure domain (ISSUE 13): a chip-health event mid-serving
    triggers degrade-and-replay — every stream finishes TOKEN-EXACT vs
    the single-chip oracle on the shrunken mesh (the same
    placement-blindness that made the unsharded engine the r8 oracle
    makes it the oracle for every degraded shape), and recovery grows
    the engine back to the configured mesh at an idle tick."""

    def _drive_engine(self, eng, prompts, shrink_at=None, dev=None,
                      max_tokens=6, limit=600):
        from tpushare.cli import serve as serve_mod
        reqs = [serve_mod._Request(list(p), max_tokens, None)
                for p in prompts]
        for r in reqs:
            assert eng.submit(r)
        for i in range(limit):
            if all(r.done.is_set() for r in reqs):
                break
            if shrink_at is not None and i == shrink_at:
                eng.chip_event(dev, False)
            eng._loop_once()
        assert all(r.done.is_set() for r in reqs), "engine stalled"
        assert all(r.error is None for r in reqs), \
            [r.error for r in reqs]
        return [list(r.tokens) for r in reqs]

    def _pin_shrink(self, mk_engine, mk_mesh, vocab, dev,
                    want_current, shrink_at=4, max_tokens=6):
        prompts = [[5, 9, 12, 3], list(range(40, 60)), [9, 9, 2]]
        want = self._drive_engine(mk_engine(None), prompts,
                                  max_tokens=max_tokens)
        eng = mk_engine(mk_mesh())
        got = self._drive_engine(eng, prompts, shrink_at=shrink_at,
                                 dev=dev, max_tokens=max_tokens)
        assert got == want
        st = eng.stats()
        assert st["reshards"] >= 1
        assert st["degraded"] is True
        assert st["replayed_on_reshard"] >= 1
        assert st["mesh_shape_current"] == want_current
        assert st["mesh_shape_configured"] == st["mesh_shape"] or \
            st["mesh_shape_current"] == st["mesh_shape"]
        assert st["reshard_ms"] is not None
        assert st["fetches_per_tick"] is not None
        assert st["fetches_per_tick"] <= 1.0
        return eng

    def test_dense_paged_tp2_to_1(self):
        from tpushare.cli import serve as serve_mod

        def mk(mesh):
            return serve_mod.ServeEngine(
                TF_PARAMS, TF_CFG, n_slots=4, n_blocks=128,
                block_size=4, idle_sleep_s=0.0, prefill_chunk=8,
                mesh=mesh, max_reshards=5)

        eng = self._pin_shrink(mk, _mesh_tp, TF_CFG.vocab_size,
                               dev=1, want_current={})
        assert eng.stats()["num_devices"] == 1
        assert eng.stats()["num_devices_configured"] == 2

    def test_paged_moe_eptp_2x2_to_2x1(self):
        from tpushare.cli import serve as serve_mod

        def mk(mesh):
            return serve_mod.ServeEngine(
                MOE_PARAMS, MOE_CFG, model_family="moe",
                n_slots=4, n_blocks=128, block_size=4,
                idle_sleep_s=0.0, prefill_chunk=8, mesh=mesh,
                max_reshards=5)

        eng = self._pin_shrink(mk, _mesh_eptp, MOE_CFG.vocab_size,
                               dev=3, want_current={"ep": 2})
        # 2x1: ep survives the tie, tp collapses (the issue-named
        # degrade shape).
        assert eng.stats()["num_devices"] == 2

    def test_spec_horizon2_across_a_shrink(self):
        """A speculative engine (gamma=2, horizon=2) shrinks tp=2 -> 1
        mid-stream: draft + target re-place together and the greedy
        stream stays bit-exact vs the single-chip oracle."""
        from tpushare.cli import serve as serve_mod

        def mk(mesh):
            return serve_mod.ServeEngine(
                TF_PARAMS, TF_CFG, n_slots=3, n_blocks=128,
                block_size=4, idle_sleep_s=0.0,
                speculative_draft=(TF_PARAMS, TF_CFG), gamma=2,
                spec_horizon=2, mesh=mesh, max_reshards=5,
                draft_param_specs=None)

        self._pin_shrink(mk, _mesh_tp, TF_CFG.vocab_size,
                         dev=1, want_current={}, shrink_at=2,
                         max_tokens=16)

    def test_grow_back_after_recovery(self):
        from tpushare.cli import serve as serve_mod
        eng = serve_mod.ServeEngine(
            MOE_PARAMS, MOE_CFG, model_family="moe",
            n_slots=4, n_blocks=128, block_size=4, idle_sleep_s=0.0,
            mesh=_mesh_eptp(), max_reshards=5)
        self._drive_engine(eng, [[5, 9, 12, 3]], shrink_at=2, dev=3)
        assert eng.stats()["degraded"] is True
        # Recovery: per-chip healthy event + idle ticks -> full mesh.
        eng.chip_event(3, True)
        for _ in range(4):
            eng._loop_once()
        st = eng.stats()
        assert st["degraded"] is False
        assert st["grow_backs"] == 1
        assert st["mesh_shape_current"] == {"ep": 2, "tp": 2}
        assert st["num_devices"] == 4
        # The regrown engine still serves, token-exact vs oracle.
        oracle = serve_mod.ServeEngine(
            MOE_PARAMS, MOE_CFG, model_family="moe",
            n_slots=4, n_blocks=128, block_size=4, idle_sleep_s=0.0)
        want = self._drive_engine(oracle, [[7, 7, 3]])
        assert self._drive_engine(eng, [[7, 7, 3]]) == want

    def test_undrain_is_the_all_clear(self):
        """The plugin's all-healthy hook POSTs /undrain; for a
        shrunken engine that marks every chip healthy and the next
        idle tick grows back."""
        from tpushare.cli import serve as serve_mod
        eng = serve_mod.ServeEngine(
            TF_PARAMS, TF_CFG, n_slots=2, n_blocks=64, block_size=4,
            idle_sleep_s=0.0, mesh=_mesh_tp(), max_reshards=5)
        self._drive_engine(eng, [[5, 9, 12, 3]], shrink_at=2, dev=1)
        assert eng.stats()["degraded"] is True
        eng.begin_drain()
        assert eng.end_drain() is True
        for _ in range(4):
            eng._loop_once()
        assert eng.stats()["degraded"] is False
        assert eng.stats()["mesh_shape_current"] == {"tp": 2}

    def test_reshard_checkpoint_source(self, tmp_path):
        """--reshard-checkpoint: weights rebuild from the orbax
        checkpoint written at boot instead of the in-memory copy —
        same degraded stream, bit-exact."""
        from tpushare.cli import serve as serve_mod

        def mk(mesh, **kw):
            return serve_mod.ServeEngine(
                TF_PARAMS, TF_CFG, n_slots=3, n_blocks=64,
                block_size=4, idle_sleep_s=0.0, mesh=mesh,
                max_reshards=5, **kw)

        prompts = [[5, 9, 12, 3], [9, 9, 2]]
        want = self._drive_engine(mk(None), prompts)
        eng = mk(_mesh_tp(),
                 reshard_checkpoint=str(tmp_path / "ckpt"))
        assert (tmp_path / "ckpt").exists()
        got = self._drive_engine(eng, prompts, shrink_at=3, dev=1)
        assert got == want
        assert eng.stats()["reshards"] == 1

    def test_reshard_checkpoint_requires_mesh(self):
        from tpushare.cli import serve as serve_mod
        with pytest.raises(ValueError, match="mesh"):
            serve_mod.ServeEngine(TF_PARAMS, TF_CFG, n_slots=2,
                                  n_blocks=32, block_size=4,
                                  reshard_checkpoint="/tmp/nope")

    def test_reshard_budget_exhausted_goes_drained_sticky(self):
        """max_reshards=0: the first mesh fault drains the replica
        STICKY — /readyz goes red (the router sheds it) and undrain
        is refused."""
        from tpushare.cli import serve as serve_mod
        eng = serve_mod.ServeEngine(
            TF_PARAMS, TF_CFG, n_slots=2, n_blocks=32, block_size=4,
            idle_sleep_s=0.0, mesh=_mesh_tp(), max_reshards=0)
        eng.chip_event(1, False)
        eng._loop_once()                # the tick picks up the fault
        assert eng.stats()["reshards"] == 0
        assert eng._draining.is_set() and eng._drain_sticky
        assert "reshard budget exhausted" in eng.stats()["last_error"]
        late = serve_mod._Request([5, 9], 2, None)
        assert eng.submit(late)
        assert late.done.wait(2) and late.error is not None
        assert eng.end_drain() is False

    def test_total_chip_loss_drains_and_fails_fast(self):
        from tpushare.cli import serve as serve_mod
        eng = serve_mod.ServeEngine(
            TF_PARAMS, TF_CFG, n_slots=2, n_blocks=32, block_size=4,
            idle_sleep_s=0.0, mesh=_mesh_tp(), max_reshards=5)
        req = serve_mod._Request([5, 9, 12], 30, None)
        assert eng.submit(req)
        for _ in range(3):
            eng._loop_once()
        eng.chip_event(0, False)
        eng.chip_event(1, False)
        eng._loop_once()
        assert req.done.is_set() and req.error is not None
        assert "no serving shape" in eng.stats()["last_error"]
        assert eng._draining.is_set() and eng._drain_sticky


class TestPlacementValidation:
    def test_tp_must_divide_kv_heads(self):
        mesh = make_mesh({"tp": 4}, devices=jax.devices()[:4])
        with pytest.raises(ValueError, match="n_kv_heads"):
            PagedSlotServer(TF_PARAMS, TF_CFG, n_slots=2, n_blocks=16,
                            block_size=4, mesh=mesh)

    def test_ep_must_divide_experts(self):
        # tiny MoE has 4 experts; ep=3 cannot divide them.
        if len(jax.devices()) < 6:
            pytest.skip("needs 6 forced devices for ep=3,tp=2")
        mesh = make_mesh({"ep": 3, "tp": 2}, devices=jax.devices()[:6])
        with pytest.raises(ValueError, match="n_experts"):
            PagedSlotServer(MOE_PARAMS, MOE_CFG, n_slots=2,
                            n_blocks=16, block_size=4,
                            forward_fn=moe.paged_forward, mesh=mesh)

    def test_ep_rejected_for_dense(self):
        mesh = make_mesh({"ep": 2}, devices=jax.devices()[:2])
        with pytest.raises(ValueError, match="expert-parallel"):
            PagedSlotServer(TF_PARAMS, TF_CFG, n_slots=2, n_blocks=16,
                            block_size=4, mesh=mesh)

    def test_non_serving_axes_rejected(self):
        mesh = make_mesh({"dp": 2}, devices=jax.devices()[:2])
        with pytest.raises(ValueError, match="tp/ep"):
            PagedSlotServer(TF_PARAMS, TF_CFG, n_slots=2, n_blocks=16,
                            block_size=4, mesh=mesh)

    def test_kv_quant_and_multi_lora_rejected(self):
        mesh = _mesh_tp()
        with pytest.raises(ValueError, match="kv_quant"):
            PagedSlotServer(TF_PARAMS, TF_CFG, n_slots=2, n_blocks=16,
                            block_size=4, kv_quant=True, mesh=mesh)
        from tpushare.models.lora import init_lora, stack_adapters
        bank = stack_adapters([init_lora(
            jax.random.PRNGKey(1), TF_CFG, 2)])
        with pytest.raises(ValueError, match="multi_lora"):
            PagedSlotServer(TF_PARAMS, TF_CFG, n_slots=2, n_blocks=16,
                            block_size=4, multi_lora=bank, mesh=mesh)

    def test_draft_heads_must_divide_too(self):
        mesh = make_mesh({"tp": 4}, devices=jax.devices()[:4])
        wide = tf.tiny(remat=False, n_kv_heads=4, n_heads=4)
        wide_params = tf.init_params(jax.random.PRNGKey(2), wide)
        with pytest.raises(ValueError, match="draft"):
            PagedSlotServer(wide_params, wide, n_slots=2, n_blocks=16,
                            block_size=4, mesh=mesh,
                            speculative_draft=(TF_PARAMS, TF_CFG))


class TestMeshSpec:
    def test_parse(self):
        assert parse_mesh_spec("tp=2,ep=2") == {"tp": 2, "ep": 2}
        assert parse_mesh_spec(" tp=2 , ep=-1 ") == {"tp": 2, "ep": -1}

    @pytest.mark.parametrize("bad", [
        "", "tp", "tp=0", "tp=x", "bogus=2", "tp=2,tp=4"])
    def test_parse_rejects(self, bad):
        with pytest.raises(ValueError):
            parse_mesh_spec(bad)

    def test_serving_mesh_uses_device_prefix(self, capsys):
        mesh = serving_mesh({"tp": 2, "ep": 2})
        assert mesh.size == 4
        assert mesh.shape["tp"] == 2 and mesh.shape["ep"] == 2
        if len(jax.devices()) > 4:
            assert "idle" in capsys.readouterr().err

    def test_serving_mesh_wildcard_absorbs_grant(self):
        mesh = serving_mesh({"tp": -1})
        assert mesh.size == len(jax.devices())

    def test_serving_mesh_poisoned_grant_raises(self, monkeypatch):
        from tpushare.utils.tenant import AllocationError
        monkeypatch.setenv("TPU_VISIBLE_CHIPS", "no-tpu-has-4-units")
        with pytest.raises(AllocationError):
            serving_mesh({"tp": 2})


class TestCliMesh:
    def _engine_from_argv(self, monkeypatch, *argv):
        import sys
        from tpushare.cli import serve as serve_mod
        monkeypatch.setattr(sys, "argv", ["tpushare-serve", *argv])
        captured = {}

        def fake_serve(engine, host, port, **kw):
            captured["engine"] = engine
            raise KeyboardInterrupt     # skip the signal loop

        monkeypatch.setattr(serve_mod, "serve", fake_serve)
        try:
            serve_mod.main()
        except KeyboardInterrupt:
            pass
        return captured["engine"]

    def test_moe_paged_mesh_serves_end_to_end(self, monkeypatch):
        """The acceptance demo path: tpushare-serve --mesh tp=2,ep=2
        --model-family moe builds a sharded engine that
        serves a request end-to-end."""
        from tpushare.cli import serve as serve_mod
        eng = self._engine_from_argv(
            monkeypatch, "--mesh", "tp=2,ep=2",
            "--model-family", "moe")
        st = eng.stats()
        assert st["mesh_shape"] == {"ep": 2, "tp": 2}
        assert st["num_devices"] == 4
        assert st["kv"] == "paged" and st["model_family"] == "moe"
        req = serve_mod._Request([5, 9, 12, 3], 5, None)
        assert eng.submit(req)
        for _ in range(200):
            if req.done.is_set():
                break
            eng._loop_once()
        assert req.done.is_set() and req.error is None
        assert len(req.tokens) == 5
        assert eng.stats()["fetches_per_tick"] <= 1.0

    def test_reshard_flags_plumb_through_argv(self, monkeypatch,
                                              tmp_path):
        eng = self._engine_from_argv(
            monkeypatch, "--mesh", "tp=2", "--max-reshards", "7",
            "--reshard-checkpoint", str(tmp_path / "ckpt"))
        assert eng._max_reshards == 7
        assert eng._param_store is not None
        assert eng._param_store.path == str(tmp_path / "ckpt")
        assert (tmp_path / "ckpt").exists()

    def test_reshard_checkpoint_needs_mesh_flag(self, monkeypatch):
        with pytest.raises(SystemExit, match="--mesh"):
            self._engine_from_argv(
                monkeypatch, "--reshard-checkpoint", "/tmp/nope")

    def test_dense_mesh_rejects_ep(self, monkeypatch):
        with pytest.raises(SystemExit, match="expert parallelism"):
            self._engine_from_argv(monkeypatch, "--mesh", "tp=2,ep=2")

    def test_bad_mesh_spec_exits_with_recipe(self, monkeypatch):
        with pytest.raises(SystemExit,
                           match="xla_force_host_platform"):
            self._engine_from_argv(monkeypatch, "--mesh", "bogus=2")


class TestChipEventIdempotent:
    def test_repeated_unhealthy_events_do_not_burn_the_budget(self):
        """A re-POSTed unhealthy event for a chip the engine already
        resharded around is a no-op — the bounded reshard budget is
        for real shape changes only."""
        from tpushare.cli import serve as serve_mod
        eng = serve_mod.ServeEngine(
            TF_PARAMS, TF_CFG, n_slots=2, n_blocks=32, block_size=4,
            idle_sleep_s=0.0, mesh=_mesh_tp(), max_reshards=3)
        eng.chip_event(1, False)
        eng._loop_once()
        assert eng.stats()["reshards"] == 1
        for _ in range(3):                  # duplicate churn pushes
            eng.chip_event(1, False)
            eng._loop_once()
        st = eng.stats()
        assert st["reshards"] == 1          # no budget burned
        assert st["degraded"] is True
        assert not eng._draining.is_set()


class TestMeshFaultClassification:
    """Review-hardening pins (r13): the mesh-fault classifier covers
    the ADMISSION path, health flaps never burn the reshard budget,
    and a non-serving chip's death is recorded without a rebuild."""

    def _engine(self, mesh, **kw):
        from tpushare.cli import serve as serve_mod
        kw.setdefault("idle_sleep_s", 0.0)
        kw.setdefault("max_reshards", 5)
        return serve_mod.ServeEngine(TF_PARAMS, TF_CFG, n_slots=2,
                                     n_blocks=64, block_size=4,
                                     mesh=mesh, **kw)

    def test_admission_dispatch_death_reshards(self):
        """Chip loss at PREFILL time: an XlaRuntimeError out of a
        sharded admission must reshard — not burn the request's whole
        replay budget re-popping onto the broken placement inside one
        tick."""
        from tpushare.chaos import InjectedXlaRuntimeError
        from tpushare.cli import serve as serve_mod
        eng = self._engine(_mesh_tp(), max_replays=3)
        real = eng.srv.admit
        state = {"left": 1}

        def dying_admit(*a, **kw):
            if state["left"] > 0:
                state["left"] -= 1
                raise InjectedXlaRuntimeError(
                    "INTERNAL: chip lost mid-prefill")
            return real(*a, **kw)

        eng.srv.admit = dying_admit
        req = serve_mod._Request([5, 9, 12, 3], 4, None)
        assert eng.submit(req)
        for _ in range(300):
            if req.done.is_set():
                break
            eng._loop_once()
        assert req.done.is_set() and req.error is None, req.error
        st = eng.stats()
        assert st["reshards"] == 1
        assert st["replays"] == 1       # one replay, not a burned budget
        # Oracle: the replayed stream is the clean stream.
        oracle = self._engine(None)
        want = serve_mod._Request([5, 9, 12, 3], 4, None)
        assert oracle.submit(want)
        for _ in range(200):
            if want.done.is_set():
                break
            oracle._loop_once()
        assert req.tokens == want.tokens

    def test_flap_before_the_tick_is_a_no_op(self):
        """unhealthy-then-healthy between ticks (a flapping probe):
        the mesh is whole again, so nothing quarantines, nothing
        rebuilds, and the bounded budget is untouched."""
        eng = self._engine(_mesh_tp())
        eng.chip_event(1, False)
        eng.chip_event(1, True)
        for _ in range(3):
            eng._loop_once()
        st = eng.stats()
        assert st["reshards"] == 0 and st["quarantines"] == 0
        assert st["degraded"] is False
        assert eng._mesh_fault is None

    def test_non_serving_chip_death_records_without_rebuild(self):
        """After a degrade to devices [0, 1] of a 2x2 mesh, the death
        of healthy-but-IDLE chip 2 must not burn a reshard on a
        shape-identical rebuild — but it must still block grow-back
        until that chip recovers too."""
        from tpushare.cli import serve as serve_mod
        eng = serve_mod.ServeEngine(
            MOE_PARAMS, MOE_CFG, model_family="moe",
            n_slots=2, n_blocks=64, block_size=4, idle_sleep_s=0.0,
            mesh=_mesh_eptp(), max_reshards=5)
        eng.chip_event(3, False)
        eng._loop_once()
        assert eng.stats()["reshards"] == 1     # degraded to [0, 1]
        eng.chip_event(2, False)                # idle chip dies
        for _ in range(3):
            eng._loop_once()
        st = eng.stats()
        assert st["reshards"] == 1              # no budget burned
        assert st["degraded"] is True
        # Chip 3 alone recovering must NOT grow back (chip 2 is dead).
        eng.chip_event(3, True)
        for _ in range(3):
            eng._loop_once()
        assert eng.stats()["grow_backs"] == 0
        # Full recovery grows.
        eng.chip_event(2, True)
        for _ in range(3):
            eng._loop_once()
        assert eng.stats()["grow_backs"] == 1
        assert eng.stats()["degraded"] is False
