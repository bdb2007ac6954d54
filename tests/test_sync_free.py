"""Sync-free serving hot loop: every slot server's engine tick must
perform at most ONE device->host transfer (the token fetch), with the
spec-round guard, retirement, and block growth branching on host
mirrors; chunked admission must bound the DRAFT prefill too; and the
paged block pool must serve the MoE family (moe.paged_forward through
PagedSlotServer's forward_fn seam) bit-identically to moe.generate."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpushare.models import moe, quant
from tpushare.models import transformer as tf
from tpushare.models.paged import PagedSlotServer

MOE_CFG = moe.tiny(remat=False)
MOE_PARAMS = moe.init_params(jax.random.PRNGKey(0), MOE_CFG)
MOE_QDRAFT = quant.quantize_params(MOE_PARAMS, MOE_CFG)
TF_CFG = tf.tiny(remat=False)
TF_PARAMS = tf.init_params(jax.random.PRNGKey(0), TF_CFG)

FAMILIES = ("dense", "moe")
CFGS = {"dense": TF_CFG, "moe": MOE_CFG}


def _server(family, *, spec=False, **kw):
    """The one slot server under either family's forward function
    (``spec``: the dense family drafts with itself, the sparse one
    with its own int8 rounding)."""
    kw.setdefault("n_slots", 2)
    kw.setdefault("n_blocks", 64)
    kw.setdefault("block_size", 4)
    if family == "moe":
        if spec:
            kw.setdefault("speculative_draft", (MOE_QDRAFT, MOE_CFG))
            kw.setdefault("draft_layers_hook",
                          quant.dequant_hook(MOE_CFG))
        return PagedSlotServer(MOE_PARAMS, MOE_CFG,
                               forward_fn=moe.paged_forward, **kw)
    if spec:
        kw.setdefault("speculative_draft", (TF_PARAMS, TF_CFG))
    return PagedSlotServer(TF_PARAMS, TF_CFG, **kw)


def _prompt(seed, n, vocab):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, vocab, n), jnp.int32)


@contextlib.contextmanager
def count_transfers(counts):
    """Count explicit device->host transfers: jax.device_get calls AND
    np.asarray on jax Arrays (the two spellings the pre-fix hot loops
    used — the spec-round guard's device_get(self.lengths) and
    _grow_active's np.asarray(cache.lengths/block_table))."""
    orig_get, orig_asarray = jax.device_get, np.asarray

    def get(x):
        counts[-1] += 1
        return orig_get(x)

    def asarray(a, *args, **kw):
        if isinstance(a, jax.Array):
            counts[-1] += 1
        return orig_asarray(a, *args, **kw)

    jax.device_get = get
    np.asarray = asarray
    try:
        yield
    finally:
        jax.device_get = orig_get
        np.asarray = orig_asarray


def _assert_one_transfer_per_tick(srv, ticks=3):
    srv.step()                                  # warm (compile) tick
    counts = []
    with count_transfers(counts):
        for _ in range(ticks):
            counts.append(0)
            out = srv.step()
            assert out                          # slots actually active
    assert counts == [1] * ticks, counts


@pytest.mark.parametrize("family", FAMILIES)
class TestOneTransferPerTick:
    """The regression the host-mirror refactor is held to: pre-fix,
    the spec guard device_get lengths every tick (2 transfers/round)
    and PagedSlotServer._grow_active np.asarray'd the device lengths
    AND block table every tick (3 transfers/tick). Held under both
    forward functions the server runs."""

    def test_plain(self, family):
        srv = _server(family)
        vocab = CFGS[family].vocab_size
        srv.admit(_prompt(1, 6, vocab))
        srv.admit(_prompt(2, 4, vocab))
        _assert_one_transfer_per_tick(srv)

    def test_speculative(self, family):
        srv = _server(family, spec=True, gamma=3)
        srv.admit(_prompt(1, 6, CFGS[family].vocab_size))
        _assert_one_transfer_per_tick(srv)

    @pytest.mark.parametrize("horizon", [2, 4])
    def test_speculative_horizon(self, family, horizon):
        """Multi-token horizons change the block length, never the
        sync count: a gamma*K round is still ONE fetch."""
        srv = _server(family, spec=True, n_blocks=128, gamma=2,
                      spec_horizon=horizon)
        srv.admit(_prompt(1, 6, CFGS[family].vocab_size))
        _assert_one_transfer_per_tick(srv)

    def test_speculative_stochastic_horizon_one_transfer(self, family):
        """temperature>0 speculation: the stochastic accept cores
        sample on-device off the sampler's key stream — still exactly
        one fetch per round."""
        srv = _server(family, spec=True, n_blocks=128,
                      temperature=0.8, seed=2, gamma=2, spec_horizon=2)
        srv.admit(_prompt(1, 6, CFGS[family].vocab_size))
        _assert_one_transfer_per_tick(srv)

    def test_retirement_still_exact_from_host_mirror(self, family):
        """Capacity retirement reads the host mirror — it must fire
        on exactly the tick the device lengths reach the slot's
        capacity (2 blocks of 4 here)."""
        srv = _server(family, n_slots=1, max_blocks_per_slot=2)
        assert srv.slot_capacity == 8
        s = srv.admit(_prompt(3, 6, CFGS[family].vocab_size))
        srv.step()                                   # 7
        out = srv.step()                             # 8 -> retires
        assert s in out and not srv.active[s]
        assert int(jax.device_get(srv.cache.lengths)[s]) == 8
        assert int(srv.cache.host_lengths()[s]) == 8

    def test_device_mask_is_a_copy_of_the_host_mirror(self, family):
        """On the CPU backend ``jnp.asarray`` aliases a numpy buffer
        that happens to be 64-byte aligned (half of all allocations),
        and the server flips ``active`` in place while a dispatch that
        reads the device mask may still be in flight: the retirement
        test above and the overlapped tick's bit-exactness failed now
        and then for it. The mirror is uploaded by copy; pinned here on
        a host mirror that IS aligned."""
        srv = _server(family)
        raw = np.zeros(srv.active.size + 64, np.uint8)
        off = -raw.ctypes.data % 64
        srv.active = raw[off:off + srv.active.size].view(bool)
        slot = srv.admit(_prompt(1, 6, CFGS[family].vocab_size))
        dev = srv._active_dev
        assert bool(dev[slot])
        srv.active[slot] = False            # what retirement does
        assert bool(dev[slot]), "the device mask aliases the host mirror"
        srv.active[slot] = True
        srv.evict(slot)
        assert not bool(srv._active_dev[slot])


class TestFusedKernelPathSyncFree:
    """ISSUE 12: the fused int8 expert path (quant.fused_expert_hook
    -> ops/q8_expert) must not change the tick's sync discipline —
    phase-timer-OFF engines keep exactly one fetch per tick on the
    fused path, and phase-timer-ON is measurement mode:
    instrumented, eager, deliberately sync-heavy, and excluded from
    the serving CLI path."""

    def test_paged_moe_fused(self):
        srv = PagedSlotServer(MOE_QDRAFT, MOE_CFG, n_slots=2,
                              n_blocks=32, block_size=4,
                              forward_fn=moe.paged_forward,
                              layers_hook=quant.fused_expert_hook(
                                  MOE_CFG))
        srv.admit(_prompt(1, 6, MOE_CFG.vocab_size))
        srv.admit(_prompt(2, 4, MOE_CFG.vocab_size))
        _assert_one_transfer_per_tick(srv)

    def test_real_kernel_in_tick(self, monkeypatch):
        # The REAL kernel (pallas interpreter, kernel-eligible
        # d_model=128 config) inside the jitted tick: still exactly
        # one fetch. The tiny-config test above covers the reference
        # fallback half of the dispatch gate.
        from tpushare.ops import q8_expert
        monkeypatch.setenv(q8_expert.Q8_EXPERT_KERNEL_ENV,
                           "interpret")
        cfg128 = moe.tiny(d_model=128, remat=False)
        qp128 = quant.quantize_params(
            moe.init_params(jax.random.PRNGKey(0), cfg128), cfg128)
        srv = PagedSlotServer(
            qp128, cfg128, n_slots=2, n_blocks=32, block_size=4,
            forward_fn=moe.paged_forward,
            layers_hook=quant.fused_expert_hook(cfg128))
        srv.admit(_prompt(1, 6, cfg128.vocab_size))
        _assert_one_transfer_per_tick(srv)

    @pytest.mark.parametrize("horizon", [1, 2])
    def test_spec_horizon_fused_draft(self, horizon):
        # int8-self draft through the FUSED hook: a gamma*K round is
        # still exactly one fetch.
        srv = _server("moe", spec=True, n_blocks=128, gamma=2,
                      spec_horizon=horizon,
                      draft_layers_hook=quant.fused_expert_hook(MOE_CFG))
        srv.admit(_prompt(1, 6, MOE_CFG.vocab_size))
        _assert_one_transfer_per_tick(srv)

    def test_phase_timer_on_is_not_sync_free(self, monkeypatch):
        # The seam is real: a phase-timer forward drains the device
        # queue (block_until_ready) at EVERY phase boundary — many
        # barriers a step where a tick's whole budget is one fetch.
        # That is precisely why it must never reach the hot loop.
        from tpushare.utils.profiling import PhaseTimer
        pt = PhaseTimer()
        cache = moe.init_cache(MOE_CFG, 1, 16)
        tok = _prompt(1, 1, MOE_CFG.vocab_size)[None, :]
        barriers = [0]
        orig = jax.block_until_ready

        def spy(x):
            barriers[0] += 1
            return orig(x)
        monkeypatch.setattr(jax, "block_until_ready", spy)
        pt.start()
        moe.forward(MOE_QDRAFT, tok, MOE_CFG, cache=cache,
                    pos_offset=jnp.zeros((1,), jnp.int32),
                    layers_hook=quant.fused_expert_hook(MOE_CFG),
                    phase_timer=pt)
        # One barrier per phase mark per layer — a plain tick's sync
        # budget is 1 (the token fetch), so > 1 proves measurement
        # mode is the opposite of sync-free.
        assert barriers[0] > 1, barriers
        assert pt.snapshot()                        # phases charged

    def test_engine_fused_path_forwards_per_tick_and_stream(self):
        # The acceptance-criteria serving invariants on the new path:
        # forwards_per_tick == 1.0 AND the engine-visible token
        # streams bit-exact vs the dequant-hook engine.
        from tpushare.cli import serve as serve_mod
        from tpushare.models import quant as q
        rng = np.random.default_rng(9)
        prompts = [[int(t) for t in rng.integers(
            0, MOE_CFG.vocab_size, n)] for n in (6, 11)]

        def run(hook):
            eng = serve_mod.ServeEngine(
                MOE_QDRAFT, MOE_CFG, model_family="moe", n_slots=2,
                n_blocks=32, block_size=4, layers_hook=hook,
                idle_sleep_s=0.0)
            reqs = [serve_mod._Request(list(p), 6, None)
                    for p in prompts]
            for r in reqs:
                assert eng.submit(r)
            for _ in range(200):
                if all(r.done.is_set() for r in reqs):
                    break
                eng._tick()
            assert all(r.done.is_set() for r in reqs)
            assert all(r.error is None for r in reqs)
            return eng, [r.tokens for r in reqs]

        eng_f, toks_f = run(q.fused_expert_hook(MOE_CFG))
        _, toks_d = run(q.dequant_hook(MOE_CFG))
        assert toks_f == toks_d
        assert eng_f.stats()["forwards_per_tick"] == 1.0

    def test_phase_timer_excluded_from_serving_cli(self):
        # Measurement mode must be unreachable from tpushare-serve:
        # no flag spells it and the CLI module never names the seam.
        import inspect

        from tpushare.cli import serve as serve_mod
        parser = serve_mod.build_parser()
        flags = [s for a in parser._actions
                 for s in a.option_strings]
        assert not any("phase" in f for f in flags), flags
        assert "phase_timer" not in inspect.getsource(serve_mod)


@pytest.mark.parametrize("family", FAMILIES)
class TestFusedTickOneTransfer:
    """The PR-2 invariant extended to the fused engine tick: a tick
    that carries an admission chunk alongside the decode batch is
    still exactly ONE device->host transfer — the token fetch (the
    admission's completion token rides the same fetch). Fused chunks
    add zero syncs."""

    def _assert_fused(self, srv, prompt, chunk=8):
        srv.step()                              # warm (compile) tick
        slot = srv.admit_start(prompt, chunk_tokens=chunk)
        counts = []
        with count_transfers(counts):
            done = False
            while not done:
                counts.append(0)
                out = srv.step(prefill_work=slot)
                assert out
                done = slot in out
        assert counts == [1] * len(counts), counts

    def test_plain(self, family):
        vocab = CFGS[family].vocab_size
        srv = _server(family, n_blocks=32)
        srv.admit(_prompt(1, 6, vocab))
        self._assert_fused(srv, _prompt(4, 21, vocab))

    def test_speculative(self, family):
        vocab = CFGS[family].vocab_size
        srv = _server(family, spec=True, gamma=3)
        srv.admit(_prompt(1, 6, vocab))
        self._assert_fused(srv, _prompt(4, 21, vocab))


class TestShardedOneTransfer:
    """The sync-free invariant under sharding (ISSUE 7): a mesh-
    sharded server's tick is still exactly ONE device->host transfer.
    The token fetch reads a replicated array, so each host gathers
    from its own addressable shard — one fetch per host — and the
    servers' device_fetches counter (the /stats observability surface)
    must agree with the monkeypatched ground truth."""

    pytestmark = pytest.mark.skipif(
        len(jax.devices()) < 4,
        reason="needs 4+ forced host devices")

    @staticmethod
    def _mesh(n):
        from tpushare.parallel import make_mesh
        axes = {"tp": 2} if n == 2 else {"tp": 2, "ep": 2}
        return make_mesh(axes, devices=jax.devices()[:n])

    def test_paged_dense_tp(self):
        srv = PagedSlotServer(TF_PARAMS, TF_CFG, n_slots=2,
                              n_blocks=32, block_size=4,
                              mesh=self._mesh(2))
        srv.admit(_prompt(1, 6, TF_CFG.vocab_size))
        srv.admit(_prompt(2, 4, TF_CFG.vocab_size))
        _assert_one_transfer_per_tick(srv)

    def test_paged_moe_eptp(self):
        srv = PagedSlotServer(MOE_PARAMS, MOE_CFG, n_slots=2,
                              n_blocks=32, block_size=4,
                              forward_fn=moe.paged_forward,
                              mesh=self._mesh(4))
        srv.admit(_prompt(1, 6, MOE_CFG.vocab_size))
        _assert_one_transfer_per_tick(srv)

    def test_paged_speculative_tp(self):
        srv = PagedSlotServer(TF_PARAMS, TF_CFG, n_slots=2,
                              n_blocks=64, block_size=4,
                              speculative_draft=(TF_PARAMS, TF_CFG),
                              gamma=3, mesh=self._mesh(2))
        srv.admit(_prompt(1, 6, TF_CFG.vocab_size))
        _assert_one_transfer_per_tick(srv)

    def test_fused_tick_sharded_still_one_transfer(self):
        srv = PagedSlotServer(TF_PARAMS, TF_CFG, n_slots=2,
                              n_blocks=64, block_size=4,
                              mesh=self._mesh(2))
        srv.admit(_prompt(1, 6, TF_CFG.vocab_size))
        srv.step()                              # warm (compile) tick
        slot = srv.admit_start(_prompt(4, 21, TF_CFG.vocab_size),
                               chunk_tokens=8)
        counts = []
        with count_transfers(counts):
            done = False
            while not done:
                counts.append(0)
                out = srv.step(prefill_work=slot)
                assert out
                done = slot in out
        assert counts == [1] * len(counts), counts

    def test_device_fetches_counter_is_ground_truth(self):
        """The /stats counter must count exactly what the transfer
        monkeypatch counts — an observability surface that drifts
        from reality is worse than none."""
        srv = PagedSlotServer(MOE_PARAMS, MOE_CFG, n_slots=2,
                              n_blocks=32, block_size=4,
                              forward_fn=moe.paged_forward,
                              mesh=self._mesh(4))
        srv.admit(_prompt(1, 6, MOE_CFG.vocab_size))
        srv.step()                              # warm (compile) tick
        f0 = srv.device_fetches
        counts = [0]
        with count_transfers(counts):
            for _ in range(3):
                srv.step()
        assert srv.device_fetches - f0 == counts[0] == 3


@pytest.mark.parametrize("family", FAMILIES)
class TestChunkedDraftPrefill:
    """Chunked admission must bound the DRAFT prefill too: a draft
    prompt cold-prefilled in one forward would reintroduce the
    long-prompt stall for the draft's weight stream."""

    GAMMA = 3
    CHUNK = 4

    def test_no_draft_forward_exceeds_chunk(self, family):
        srv = _server(family, spec=True, gamma=self.GAMMA)
        widths = []
        orig = srv._draft_prefill

        def spy(p, toks, *a, **kw):
            widths.append(int(toks.shape[1]))
            return orig(p, toks, *a, **kw)

        srv._draft_prefill = spy
        slot = srv.admit_start(_prompt(5, 13, CFGS[family].vocab_size),
                               chunk_tokens=self.CHUNK)
        while srv.admit_step(slot) is None:
            pass
        assert widths, "draft never prefilled"
        assert max(widths) <= self.CHUNK, widths
        # The whole prompt was covered: ceil(13 / 4) chunks.
        assert len(widths) == 4

    def test_chunked_spec_admission_matches_whole(self, family):
        prompt = _prompt(7, 10, CFGS[family].vocab_size)

        def run(chunked):
            srv = _server(family, spec=True, gamma=self.GAMMA)
            if chunked:
                slot = srv.admit_start(prompt, chunk_tokens=self.CHUNK)
                while srv.admit_step(slot) is None:
                    pass
            else:
                slot = srv.admit(prompt)
            toks = [int(srv.last_token[slot, 0])]
            for _ in range(4):
                t = srv.step()[slot]
                toks.extend(t if isinstance(t, list) else [t])
            return toks

        assert run(True) == run(False)


class TestPagedMoE:
    """The paged block pool serving the MoE family through the
    forward_fn seam: bit-identical streams, block-granular prefix
    sharing, and a real pool-pressure signal."""

    def _mk(self, **kw):
        kw.setdefault("n_slots", 2)
        kw.setdefault("n_blocks", 32)
        kw.setdefault("block_size", 4)
        return PagedSlotServer(MOE_PARAMS, MOE_CFG,
                               forward_fn=moe.paged_forward, **kw)

    def test_matches_moe_generate(self):
        srv = self._mk()
        p1 = _prompt(11, 6, MOE_CFG.vocab_size)
        p2 = _prompt(12, 4, MOE_CFG.vocab_size)
        s1, s2 = srv.admit(p1), srv.admit(p2)
        toks = {s1: [int(srv.last_token[s1, 0])],
                s2: [int(srv.last_token[s2, 0])]}
        for _ in range(5):
            for s, t in srv.step().items():
                toks[s].append(t)
        for p, s in ((p1, s1), (p2, s2)):
            want = moe.generate(MOE_PARAMS, p[None, :], MOE_CFG,
                                max_new_tokens=6)
            assert toks[s] == [int(t) for t in want[0, p.shape[0]:]]

    def test_prefix_sharing_is_block_granular(self):
        srv = self._mk(prefix_cache=True)
        prompt = _prompt(13, 13, MOE_CFG.vocab_size)
        a = srv.admit(prompt)
        first_a = int(srv.last_token[a, 0])
        srv.evict(a)
        b = srv.admit(prompt)
        # (S-1)//bs = 12//4 = 3 full blocks reused.
        assert srv.last_cached_len == 12
        assert int(srv.last_token[b, 0]) == first_a

    def test_pool_counters_are_real(self):
        srv = self._mk(n_blocks=16)
        total = 15                           # n_blocks - 1 (trash)
        assert len(srv.cache.free) == total
        srv.admit(_prompt(14, 6, MOE_CFG.vocab_size))
        used = srv.cache.live_blocks()
        assert used > 0
        assert len(srv.cache.free) == total - used

    def test_speculative_int8_self(self):
        def run(spec):
            kw = {}
            if spec:
                kw = dict(speculative_draft=(MOE_QDRAFT, MOE_CFG),
                          gamma=3,
                          draft_layers_hook=quant.dequant_hook(MOE_CFG))
            srv = self._mk(n_blocks=64, **kw)
            s = srv.admit(_prompt(15, 6, MOE_CFG.vocab_size))
            toks = [int(srv.last_token[s, 0])]
            for _ in range(5):
                t = srv.step()[s]
                toks.extend(t if isinstance(t, list) else [t])
            return toks[:6]

        assert run(True) == run(False)

    def test_forward_fn_rejects_dense_only_features(self):
        with pytest.raises(ValueError, match="kv_quant"):
            self._mk(kv_quant=True)


class TestEngineStatsSchema:
    """/stats tags the family and the one KV layout, with real pool
    counters for every family; the removed layout is refused by
    name."""

    @pytest.mark.parametrize("kv", (None, "paged"))
    def test_moe_reports_real_pool(self, kv):
        from tpushare.cli import serve as serve_mod
        eng = serve_mod.ServeEngine(MOE_PARAMS, MOE_CFG,
                                    model_family="moe", kv=kv,
                                    n_slots=1, n_blocks=16,
                                    block_size=4)
        assert type(eng.srv) is PagedSlotServer
        st = eng.stats()
        assert st["model_family"] == "moe" and st["kv"] == "paged"
        assert st["free_blocks"] == 15
        assert st["live_blocks"] == 0

    @pytest.mark.parametrize("family", FAMILIES)
    def test_kv_rows_refused_by_name(self, family):
        from tpushare.cli import serve as serve_mod
        params = MOE_PARAMS if family == "moe" else TF_PARAMS
        with pytest.raises(ValueError, match="removed in PR 30"):
            serve_mod.ServeEngine(params, CFGS[family],
                                  model_family=family, kv="rows")

    def test_cli_moe_default_is_the_paged_server(self):
        """``tpushare-serve --model-family moe`` with no further flag:
        the path the chip measures, with a pool behind ``/stats``."""
        from tpushare.cli import serve as serve_mod
        eng = serve_mod.build_engine(serve_mod.build_parser().parse_args(
            ["--model-family", "moe", "--platform", "cpu"]))
        assert type(eng.srv) is PagedSlotServer
        assert eng.srv._forward_fn is moe.paged_forward
        st = eng.stats()
        assert st["model_family"] == "moe" and st["kv"] == "paged"
        assert st["free_blocks"] == 255 and st["live_blocks"] == 0


class TestCliFlagGuards:
    def _main_argv(self, monkeypatch, *argv):
        import sys
        from tpushare.cli import serve as serve_mod
        monkeypatch.setattr(sys, "argv", ["tpushare-serve", *argv])
        return serve_mod.main

    def test_int8_experts_plus_int8_self_draft_rejected(self,
                                                        monkeypatch):
        main = self._main_argv(monkeypatch, "--model-family", "moe",
                               "--int8-experts", "--draft-preset",
                               "int8-self")
        with pytest.raises(SystemExit,
                           match="bit-identical"):
            main()

    @pytest.mark.parametrize("argv", (
        ("--model-family", "moe", "--kv", "rows"),
        ("--kv=paged",),
        ("--model-family", "moe", "--max-len", "128")))
    def test_removed_flags_refused_by_name(self, monkeypatch, argv):
        main = self._main_argv(monkeypatch, *argv)
        with pytest.raises(SystemExit, match="removed in PR 30"):
            main()

    def test_help_lists_neither_removed_flag(self):
        from tpushare.cli import serve as serve_mod
        flags = {s for a in serve_mod.build_parser()._actions
                 for s in a.option_strings}
        assert not {"--kv", "--max-len"} & flags
        assert {"--kv-quant", "--n-blocks", "--block-size"} <= flags
        assert len(flags - {"-h", "--help"}) == 38


# ---------------------------------------------------------------------------
# Tiered tick paths stay sync-free (ISSUE 9)
# ---------------------------------------------------------------------------

class TestTieredTickSyncFree:
    """Every SLO decision — tier pop order, fused-chunk arbitration,
    preempt-low-for-high victim choice, quota verdicts — is pure host
    arithmetic: a tiered engine tick still makes at most the ONE
    device->host transfer the invariant allows."""

    def _engine(self, **kw):
        from tpushare.cli.serve import ServeEngine
        kw.setdefault("idle_sleep_s", 0.001)
        kw.setdefault("chaos_spec", "")
        return ServeEngine(TF_PARAMS, TF_CFG, n_slots=3, n_blocks=64,
                           block_size=8, prefill_chunk=8,
                           tick_token_budget=16, **kw)

    @pytest.mark.parametrize("profiled", (False, True),
                             ids=("untraced", "profiler-on"))
    def test_mixed_tier_ticks_one_transfer(self, profiled, tmp_path):
        """``profiled``: under a jax.profiler session the engine's
        spans are live (tpushare.utils.profiling.span) — they must add
        no transfer either."""
        from tpushare.utils.profiling import trace
        with (trace(str(tmp_path)) if profiled
              else contextlib.nullcontext()):
            self._mixed_tier_ticks_one_transfer()

    def _mixed_tier_ticks_one_transfer(self):
        from tpushare.cli.serve import _Request
        from tpushare.slo import TenantQuotaSpec
        eng = self._engine(
            tenant_quotas={"acme": TenantQuotaSpec(0, None)})
        rng = np.random.default_rng(5)
        mk = lambda n, tier, tenant: _Request(
            [int(t) for t in rng.integers(0, TF_CFG.vocab_size, n)],
            8, None, tier=tier, tenant=tenant)
        reqs = [mk(6, "interactive", "acme"),
                mk(24, "batch", "acme"),        # chunk-admits (> 8)
                mk(6, "standard", "default")]
        for r in reqs:
            assert eng.submit(r)
        for _ in range(4):                      # admit + warm/compile
            eng._loop_once()
        counts = []
        with count_transfers(counts):
            for _ in range(6):
                counts.append(0)
                eng._loop_once()
        assert all(c <= 1 for c in counts), counts
        assert any(c == 1 for c in counts), counts
        for _ in range(3000):
            if all(r.done.is_set() for r in reqs):
                break
            eng._loop_once()
        assert all(r.error is None for r in reqs)
        st = eng.stats()
        # the live /stats spelling of the same invariant
        assert st["fetches_per_tick"] is not None
        assert st["fetches_per_tick"] <= 1.0
        assert st["forwards_per_tick"] == 1.0
        per = st["per_tier"]
        assert sum(row["completed"] for row in per.values()) == 3


class TestJournaledTickSyncFree:
    """Crash-only serving (ISSUE 14): the write-ahead journal rides
    the tick's HOST work — with journaling on (--journal-fsync tick,
    the strongest policy) the engine still makes at most the ONE
    device->host transfer per work tick, and fetches_per_tick == 1
    holds on decode-only storms. Journaling off = zero journal I/O
    (pinned in test_durable's bit-exactness suite)."""

    def test_journaled_engine_fetches_per_tick(self, tmp_path):
        from tpushare.cli.serve import ServeEngine, _Request
        eng = ServeEngine(TF_PARAMS, TF_CFG, n_slots=2, n_blocks=64,
                          block_size=8, idle_sleep_s=0.0,
                          chaos_spec="",
                          journal_dir=str(tmp_path / "j"),
                          journal_fsync="tick")
        rng = np.random.default_rng(3)
        reqs = [_Request([int(t) for t in rng.integers(
            0, TF_CFG.vocab_size, 5 + i)], 10, None) for i in range(2)]
        for r in reqs:
            assert eng.submit(r)
        for _ in range(4):                      # admit + warm/compile
            eng._loop_once()
        counts = []
        with count_transfers(counts):
            for _ in range(5):
                counts.append(0)
                eng._loop_once()
        # Journal appends/fsyncs are file I/O, never device syncs.
        assert all(c <= 1 for c in counts), counts
        assert any(c == 1 for c in counts), counts
        for _ in range(2000):
            if all(r.done.is_set() for r in reqs):
                break
            eng._loop_once()
        assert all(r.error is None for r in reqs)
        st = eng.stats()
        # The acceptance pin: no prefill chunking here, so every work
        # tick is a decode step — EXACTLY one fetch per tick with the
        # journal on.
        assert st["fetches_per_tick"] == 1.0
        assert st["forwards_per_tick"] == 1.0
        # The journal actually ran (records + at least one fsync).
        assert st["journal"]["records"] > 0
        assert st["journal"]["fsyncs"] > 0
        eng.stop()


class TestDegradedMeshSyncFree:
    """Mesh failure domain (ISSUE 13): the one-fetch-per-host
    invariant survives a shrink — on the DEGRADED mesh (a server
    rebuilt on the reshard plan's carved sub-mesh still ticks at
    exactly one transfer) and across the shrink tick itself (the
    reshard — quarantine, re-carve, host-sourced rebuild — adds no
    device->host transfers of its own)."""

    pytestmark = pytest.mark.skipif(
        len(jax.devices()) < 4,
        reason="needs 4+ forced host devices")

    @staticmethod
    def _degraded_mesh(axes, n, dead):
        from tpushare.models.reshard import plan_reshard
        from tpushare.parallel import make_mesh
        cfg = MOE_CFG if "ep" in axes else TF_CFG
        mesh = make_mesh(axes, devices=jax.devices()[:n])
        healthy = [i != dead for i in range(n)]
        plan = plan_reshard(mesh, healthy, cfg)
        assert plan.degraded and plan.mesh is not None
        return plan.mesh

    def test_paged_dense_on_degraded_tp1(self):
        mesh = self._degraded_mesh({"tp": 2}, 2, dead=1)
        assert mesh.size == 1
        srv = PagedSlotServer(TF_PARAMS, TF_CFG, n_slots=2,
                              n_blocks=32, block_size=4, mesh=mesh)
        srv.admit(_prompt(1, 6, TF_CFG.vocab_size))
        srv.admit(_prompt(2, 4, TF_CFG.vocab_size))
        _assert_one_transfer_per_tick(srv)

    def test_paged_moe_on_degraded_2x1(self):
        mesh = self._degraded_mesh({"tp": 2, "ep": 2}, 4, dead=3)
        assert mesh.size == 2           # ep survives the tie: 2x1
        srv = PagedSlotServer(MOE_PARAMS, MOE_CFG, n_slots=2,
                              n_blocks=32, block_size=4,
                              forward_fn=moe.paged_forward, mesh=mesh)
        srv.admit(_prompt(1, 6, MOE_CFG.vocab_size))
        _assert_one_transfer_per_tick(srv)

    def test_shrink_tick_itself_stays_sync_free(self):
        """Engine-level: the tick that absorbs the chip loss —
        quarantine + replay + re-carve + rebuild — performs NO
        counted device->host transfer (the ParamStore is already
        host-resident; placement is device_put), and every tick
        around it keeps the <= 1 contract."""
        from tpushare.cli.serve import ServeEngine, _Request
        from tpushare.parallel import make_mesh
        eng = ServeEngine(TF_PARAMS, TF_CFG, n_slots=3, n_blocks=64,
                          block_size=4, idle_sleep_s=0.0,
                          chaos_spec="",
                          mesh=make_mesh({"tp": 2},
                                         devices=jax.devices()[:2]),
                          max_reshards=5)
        rng = np.random.default_rng(7)
        reqs = [_Request([int(t) for t in rng.integers(
            0, TF_CFG.vocab_size, 5 + i)], 12, None) for i in range(3)]
        for r in reqs:
            assert eng.submit(r)
        for _ in range(4):                      # admit + warm/compile
            eng._loop_once()
        counts = []
        with count_transfers(counts):
            for i in range(8):
                counts.append(0)
                if i == 2:
                    eng.chip_event(1, False)    # next tick reshards
                eng._loop_once()
        # Tick 2 IS the reshard: quarantine + re-carve + rebuild from
        # the host-resident ParamStore — zero device->host transfers.
        assert counts[2] == 0, (counts, "the reshard tick fetched")
        # Tick 3 re-admits the replayed requests (whole-prompt
        # admissions fetch, exactly as at boot — admission fetches
        # are outside the tick-work invariant, which is why the
        # engine's device_fetches delta wraps only the step
        # dispatch); every OTHER tick keeps the <= 1 contract.
        assert all(c <= 1 for j, c in enumerate(counts) if j != 3), \
            counts
        assert eng.stats()["reshards"] == 1
        for _ in range(2000):
            if all(r.done.is_set() for r in reqs):
                break
            eng._loop_once()
        assert all(r.error is None for r in reqs)
        st = eng.stats()
        assert st["degraded"] is True
        assert st["fetches_per_tick"] is not None
        assert st["fetches_per_tick"] <= 1.0
        assert st["forwards_per_tick"] == 1.0


class TestOffloadTierSyncFree:
    """Host KV tier (r18): demotion is an ADMISSION cost (its
    device_get runs under demote_for_alloc, never inside a decode
    tick), and the promotion direction is host->device only —
    prefetch_prefix performs ZERO counted device->host transfers, a
    promoted admission adds no transfer beyond admission's own token
    fetch, and decode ticks after a promotion keep the one-transfer
    contract."""

    def _tiered(self, n_blocks=10):
        from tpushare.models.kvtier import HostKvTier
        srv = PagedSlotServer(TF_PARAMS, TF_CFG, n_slots=2,
                              n_blocks=n_blocks, block_size=4,
                              max_blocks_per_slot=8, prefix_cache=True)
        tier = HostKvTier(32 << 20)
        # Pin the measured policy to "transfer": this suite polices
        # TRANSFER COUNTS; the crossover's timing-dependent verdict
        # is pinned in test_kv_offload.
        tier.estimator.observe_transfer("d2h", 1 << 40, 1.0)
        tier.estimator.observe_transfer("h2d", 1 << 40, 1.0)
        srv.cache.host_tier = tier
        return srv, tier

    @staticmethod
    def _spill_all(cache):
        """What a pool-exhausting admission does, in miniature: demote
        the parked LRU, then RECLAIM it (demotion is a pure copy — the
        device blocks survive until alloc_blocks unpublishes them).
        Admission-path work, run OUTSIDE any counted window exactly
        like a real admission."""
        from tpushare.models.paged import alloc_blocks, demote_for_alloc
        need = len(cache.free) + len(cache.lru)
        demote_for_alloc(cache, need)
        cache.free.extend(alloc_blocks(cache, need))

    def test_prefetch_zero_fetches_admit_promotes_staged(self):
        srv, tier = self._tiered()
        p = _prompt(1, 13, TF_CFG.vocab_size)
        slot = srv.admit(p)
        for _ in range(4):
            srv.step()
        srv.evict(slot)                 # 3 published blocks park
        self._spill_all(srv.cache)
        assert tier.snapshot()["demotions"] == 3
        assert not srv.cache.index      # nothing device-resident
        np_p = np.asarray(p)
        counts = [0]
        with count_transfers(counts):
            staged = srv.prefetch_prefix(np_p)
        assert staged == 3
        assert counts == [0], "prefetch fetched from device"
        counts = [0]
        with count_transfers(counts):
            slot = srv.admit(p)
        # Promotion from the staged uploads adds NOTHING on top of
        # what a plain whole-prompt admission may fetch.
        assert counts[0] <= 1, counts
        snap = tier.snapshot()
        assert snap["promotions"] == 3
        assert snap["prefetch_hit_rate"] == 1.0
        assert srv.last_cached_len == 12
        _assert_one_transfer_per_tick(srv)

    def test_unstaged_promotion_also_fetch_free(self):
        """A prefetch MISS (no overlap window ran) promotes straight
        from host numpy — still h2d-only, still <= 1 counted transfer
        on the admission."""
        srv, tier = self._tiered()
        p = _prompt(2, 13, TF_CFG.vocab_size)
        slot = srv.admit(p)
        srv.evict(slot)
        self._spill_all(srv.cache)
        counts = [0]
        with count_transfers(counts):
            srv.admit(p)
        assert counts[0] <= 1, counts
        snap = tier.snapshot()
        assert snap["promotions"] == 3
        assert snap["prefetch_hit_rate"] == 0.0
        _assert_one_transfer_per_tick(srv)

    def test_engine_tier_storm_fetches_per_tick(self):
        """Engine-level acceptance pin: a storm that demotes under
        pool pressure AND promotes on re-admission (with the overlap
        window's prefetch hook live) keeps the /stats spelling of the
        invariant — fetches_per_tick <= 1.0."""
        from tpushare.cli.serve import ServeEngine, _Request
        eng = ServeEngine(TF_PARAMS, TF_CFG, n_slots=2, n_blocks=16,
                          block_size=4, idle_sleep_s=0.0,
                          chaos_spec="", host_kv_bytes=32 << 20)
        tier = eng._host_tier
        tier.estimator.observe_transfer("d2h", 1 << 40, 1.0)
        tier.estimator.observe_transfer("h2d", 1 << 40, 1.0)
        rng = np.random.default_rng(11)
        mk = lambda seed: [int(t) for t in np.random.default_rng(
            seed).integers(0, TF_CFG.vocab_size, 13)]
        a = mk(1)

        # max_tokens 2: requests never outgrow their admission
        # allocation, so every reclaim happens at ADMISSION (the
        # demote path) — decode-time growth destroys without demoting
        # by design (a device_get there would break the step loop).
        def run(prompt):
            r = _Request(list(prompt), 2, None)
            assert eng.submit(r)
            for _ in range(3000):
                if r.done.is_set():
                    break
                eng._loop_once()
            assert r.done.is_set() and r.error is None, r.error
            return r.tokens

        want = run(a)
        for seed in (3, 4, 5, 6):       # pressure: A's chain demotes
            run(mk(seed))
        assert tier.snapshot()["demotions"] > 0
        got = run(a)                    # promote from the host tier
        assert got == want              # bit-exact through the tier
        snap = tier.snapshot()
        assert snap["promotions"] > 0
        st = eng.stats()
        assert st["fetches_per_tick"] is not None
        assert st["fetches_per_tick"] <= 1.0
        assert st["forwards_per_tick"] == 1.0
        assert st["host_tier"]["promotions"] == snap["promotions"]
        eng.stop()


class TestPerProcessFetch:
    """Multi-host invariant (ISSUE 19): the per-tick token fetch is a
    per-PROCESS addressable-shard read. On real multi-host every
    process runs this same SPMD tick, so the global cost is one fetch
    per process per tick — never a cross-process gather. The forced
    process view pins the per-process half: a num_processes=2 engine's
    decode tick performs exactly ONE counted transfer in THIS process,
    identical to the single-process engine."""

    def test_two_process_engine_one_fetch_per_tick(self):
        from tpushare.cli.serve import ServeEngine, _Request
        from tpushare.parallel import make_mesh
        eng = ServeEngine(TF_PARAMS, TF_CFG, n_slots=2, n_blocks=64,
                          block_size=4, idle_sleep_s=0.0,
                          chaos_spec="",
                          mesh=make_mesh({"tp": 2},
                                         devices=jax.devices()[:2]),
                          num_processes=2)
        reqs = [_Request([5, 9, 12, 3], 30, None),
                _Request([9, 9, 2], 30, None)]
        for r in reqs:
            assert eng.submit(r)
        for _ in range(4):                      # admit + warm/compile
            eng._loop_once()
        f0 = eng.srv.device_fetches
        counts = []
        with count_transfers(counts):
            for _ in range(5):
                counts.append(0)
                eng._loop_once()
        assert counts == [1] * 5, counts
        # The per-process /stats counter is ground truth for the same
        # five ticks (what the gang heartbeat reports upstream).
        assert eng.srv.device_fetches - f0 == sum(counts)
        st = eng.stats()
        assert st["num_processes"] == 2
        assert st["fetches_per_tick"] is not None
        assert st["fetches_per_tick"] <= 1.0
