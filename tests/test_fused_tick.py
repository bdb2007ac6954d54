"""Fused prefill+decode engine tick: while an admission is in flight
with active decode slots, each tick issues exactly ONE model forward
(the chunk rides the decode batch — no second weight stream) and the
fused path is bit-exact vs the serial admit_step oracle for the slot
server under both families' forward functions, with int8 pools, the
prefix cache and speculation, and through the engine."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpushare.models import moe, quant
from tpushare.models import transformer as tf
from tpushare.models.paged import PagedSlotServer
from tpushare.models.serving import fused_chunk_span, fused_token_batch

TF_CFG = tf.tiny(remat=False)
TF_PARAMS = tf.init_params(jax.random.PRNGKey(0), TF_CFG)
MOE_CFG = moe.tiny(remat=False)
MOE_PARAMS = moe.init_params(jax.random.PRNGKey(0), MOE_CFG)
MOE_QDRAFT = quant.quantize_params(MOE_PARAMS, MOE_CFG)
VOCAB = TF_CFG.vocab_size


def _prompt(seed, n, vocab=None):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, vocab or VOCAB, n), jnp.int32)


def _drive(srv, long_prompt, fused, ticks=8, chunk=8):
    """Admit one short prompt (a live decode stream), then chunk-admit
    ``long_prompt`` while decoding. Returns (streams, admit_tokens):
    every token each slot emitted, and the admission's first token."""
    s0 = srv.admit(_prompt(1, 6))
    streams = {s0: [int(srv.last_token[s0, 0])]}
    a = srv.admit_start(long_prompt, chunk_tokens=chunk)
    admitted = []
    for _ in range(ticks):
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
            streams.setdefault(s, []).extend(
                t if isinstance(t, list) else [t])
    assert a is None, "admission never completed"
    return streams, admitted


FAMILIES = {
    "paged": lambda: PagedSlotServer(TF_PARAMS, TF_CFG, n_slots=3,
                                     n_blocks=64, block_size=4),
    "paged_prefix": lambda: PagedSlotServer(
        TF_PARAMS, TF_CFG, n_slots=3, n_blocks=64, block_size=4,
        prefix_cache=True),
    "paged_spec": lambda: PagedSlotServer(
        TF_PARAMS, TF_CFG, n_slots=3, n_blocks=96, block_size=4,
        speculative_draft=(TF_PARAMS, TF_CFG), gamma=2),
    "paged_moe": lambda: PagedSlotServer(
        MOE_PARAMS, MOE_CFG, n_slots=3, n_blocks=64, block_size=4,
        forward_fn=moe.paged_forward),
    "paged_kvq": lambda: PagedSlotServer(
        TF_PARAMS, TF_CFG, n_slots=3, n_blocks=64, block_size=4,
        kv_quant=True),
    "paged_moe_spec": lambda: PagedSlotServer(
        MOE_PARAMS, MOE_CFG, n_slots=3, n_blocks=96, block_size=4,
        forward_fn=moe.paged_forward,
        speculative_draft=(MOE_QDRAFT, MOE_CFG), gamma=2,
        draft_layers_hook=quant.dequant_hook(MOE_CFG)),
}


class TestFusedBitExact:
    """Fused chunks must change WHEN work happens, never WHAT tokens
    come out: the admission's first token and every decode stream are
    identical to the serial admit_step oracle (compared as common
    prefixes — serial drivers land one extra decode tick)."""

    @pytest.mark.parametrize("family", sorted(FAMILIES))
    def test_matches_serial(self, family):
        vocab = (MOE_CFG if "moe" in family else TF_CFG).vocab_size
        lp = _prompt(7, 21, vocab)
        s_serial, a_serial = _drive(FAMILIES[family](), lp, fused=False)
        s_fused, a_fused = _drive(FAMILIES[family](), lp, fused=True)
        assert a_serial == a_fused
        assert set(s_serial) == set(s_fused)
        for s in s_serial:
            n = min(len(s_serial[s]), len(s_fused[s]))
            assert n > 0
            assert s_serial[s][:n] == s_fused[s][:n], (family, s)

    def test_paged_prefix_publish_survives_fused_admit(self):
        """A fused admission must publish its prefix blocks exactly
        like the serial path: a re-admit of the same prompt hits."""
        srv = FAMILIES["paged_prefix"]()
        lp = _prompt(7, 21)
        _drive(srv, lp, fused=True)
        slot = srv.admit(lp)
        assert srv.last_cached_len == 20  # (S-1)//bs * bs = 5*4
        assert srv.active[slot]

    def test_fused_mid_admission_handoff_to_serial(self):
        """Engine fallback path: fused chunks, then serial admit_step
        finishing the same admission (decode batch drained mid-admit)
        — the stale serial row must be re-gathered, keeping the
        stream identical to all-serial."""
        lp = _prompt(9, 29)

        def run(mode):
            srv = FAMILIES["paged"]()
            s0 = srv.admit(_prompt(1, 6))
            streams = {s0: [int(srv.last_token[s0, 0])]}
            a = srv.admit_start(lp, chunk_tokens=8)
            admitted = []
            i = 0
            while a is not None:
                use_fused = (mode == "fused_then_serial" and i < 2)
                if use_fused:
                    out = srv.step(prefill_work=a)
                    if a in out:
                        admitted.append(out.pop(a))
                        a = None
                else:
                    tok = srv.admit_step(a)
                    if tok is not None:
                        admitted.append(tok)
                        a = None
                    out = srv.step()
                for s, t in out.items():
                    streams.setdefault(s, []).append(t)
                i += 1
            for _ in range(3):
                for s, t in srv.step().items():
                    streams[s].append(t)
            return admitted, streams

        a1, s1 = run("serial")
        a2, s2 = run("fused_then_serial")
        assert a1 == a2
        for s in s1:
            n = min(len(s1[s]), len(s2[s]))
            assert s1[s][:n] == s2[s][:n]


class TestDispatchCount:
    """The regression the fused tick is held to: while >= 1 admission
    is in flight with active decode slots, a fused tick issues exactly
    ONE target-model forward (pre-fix: the chunk was a standalone
    forward — two full weight streams per tick)."""

    def _count_target_forwards(self, srv, names):
        counts = [0]
        for name in names:
            orig = getattr(srv, name)

            def spy(*a, __orig=orig, **kw):
                counts[0] += 1
                return __orig(*a, **kw)

            setattr(srv, name, spy)
        return counts

    @pytest.mark.parametrize("family,fwd_names", [
        (family, ("_decode", "_prefill", "_verify", "_fused"))
        for family in ("paged", "paged_kvq", "paged_spec", "paged_moe",
                       "paged_moe_spec")])
    def test_one_forward_per_fused_tick(self, family, fwd_names):
        srv = FAMILIES[family]()
        srv.admit(_prompt(1, 6, (MOE_CFG if "moe" in family
                                 else TF_CFG).vocab_size))
        a = srv.admit_start(_prompt(7, 21, (MOE_CFG if "moe" in family
                                            else TF_CFG).vocab_size),
                            chunk_tokens=8)
        counts = self._count_target_forwards(srv, fwd_names)
        ticks = 0
        while a is not None and ticks < 10:
            counts[0] = 0
            out = srv.step(prefill_work=a)
            assert out, "no work happened"
            assert counts[0] == 1, (
                f"{family}: tick carrying a fused chunk issued "
                f"{counts[0]} target forwards (want exactly 1)")
            if a in out:
                a = None
            ticks += 1
        assert a is None, "admission never completed"


class TestFusedHelpers:
    def test_fused_chunk_span_budget(self):
        # Unbounded: full chunk; final chunk bucket-pads under chunk.
        assert fused_chunk_span(0, 100, 32) == (32, 32)
        assert fused_chunk_span(96, 100, 32) == (100, 16)
        # Budget rounds down to the granule (paged block size).
        assert fused_chunk_span(0, 100, 32, max_chunk_tokens=19,
                                gran=4) == (16, 16)
        # No room for one granule -> (done, 0): caller plain-ticks.
        assert fused_chunk_span(0, 100, 32, max_chunk_tokens=3,
                                gran=4) == (0, 0)
        assert fused_chunk_span(0, 100, 32, max_chunk_tokens=0) == (0, 0)

    def test_fused_token_batch_layout(self):
        last = jnp.asarray([[7], [8], [9]], jnp.int32)
        prompt = jnp.arange(100, 121, dtype=jnp.int32)
        toks = np.asarray(fused_token_batch(last, prompt, 8, 16, 8, 1))
        assert toks.shape == (3, 8)
        assert toks[0, 0] == 7 and toks[2, 0] == 9
        assert list(toks[1]) == list(range(108, 116))

    def test_admit_step_honors_max_chunk_tokens(self):
        """The tick budget bounds SERIAL chunks too (the
        admission-only half of the engine's budget alternation must
        not smuggle a full unbounded chunk past the latency bound)."""
        # Rounds down to block alignment with a one-block floor.
        for family, vocab in (("paged", TF_CFG.vocab_size),
                              ("paged_moe", MOE_CFG.vocab_size)):
            srv = FAMILIES[family]()
            slot = srv.admit_start(_prompt(7, 21, vocab),
                                   chunk_tokens=16)
            assert srv.admit_step(slot, max_chunk_tokens=7) is None
            assert srv._admissions[slot]["done"] == 4      # one 4-block
            assert srv.admit_step(slot, max_chunk_tokens=2) is None
            assert srv._admissions[slot]["done"] == 8      # floor: 1 block

    def test_step_rejects_unknown_prefill_work(self):
        for family in ("paged", "paged_moe"):
            srv = FAMILIES[family]()
            srv.admit(_prompt(1, 6, (MOE_CFG if "moe" in family
                                     else TF_CFG).vocab_size))
            with pytest.raises((ValueError, KeyError)):
                srv.step(prefill_work=2)


class TestEngineFusedTick:
    """Engine integration, driven synchronously (no engine thread):
    chunked+fused admission serves the same tokens as whole admits,
    /stats reports forwards_per_tick == 1.0, and the token budget
    alternates instead of starving either side."""

    def _run_engine(self, prompts, max_tokens=5, **kw):
        from tpushare.cli import serve as serve_mod
        kw.setdefault("n_slots", 4)
        kw.setdefault("n_blocks", 128)
        kw.setdefault("block_size", 4)
        engine = serve_mod.ServeEngine(TF_PARAMS, TF_CFG,
                                       idle_sleep_s=0.0, **kw)
        reqs = [serve_mod._Request(list(p), max_tokens, None)
                for p in prompts]
        for r in reqs:
            assert engine.submit(r)
        for _ in range(400):
            if all(r.done.is_set() for r in reqs):
                break
            engine._tick()
        assert all(r.done.is_set() for r in reqs)
        assert all(r.error is None for r in reqs), [r.error for r in reqs]
        return engine, [r.tokens for r in reqs]

    PROMPTS = None

    @classmethod
    def _prompts(cls):
        if cls.PROMPTS is None:
            rng = np.random.default_rng(3)
            cls.PROMPTS = [
                [int(t) for t in rng.integers(0, VOCAB, 6)],
                [int(t) for t in rng.integers(0, VOCAB, 27)],
                [int(t) for t in rng.integers(0, VOCAB, 6)],
            ]
        return cls.PROMPTS

    def test_fused_admission_matches_whole_admit(self):
        _, want = self._run_engine(self._prompts())
        engine, got = self._run_engine(self._prompts(), prefill_chunk=8)
        assert got == want
        st = engine.stats()
        assert st["chunked_admits"] >= 1
        assert st["fused_ticks"] >= 1
        # THE tentpole invariant, visible in /stats: one model forward
        # per engine tick, admissions in flight or not.
        assert st["forwards_per_tick"] == 1.0

    def test_token_budget_alternates(self):
        from tpushare.cli import serve as serve_mod
        caps = []
        orig = serve_mod.ServeEngine._advance_one_admission

        def spy(self, slot, gen=None):
            caps.append(self._tick_token_budget or None)
            return orig(self, slot, gen)

        serve_mod.ServeEngine._advance_one_admission = spy
        try:
            engine, got = self._run_engine(
                self._prompts(), prefill_chunk=8, tick_token_budget=1)
        finally:
            serve_mod.ServeEngine._advance_one_admission = orig
        # Budget of 1 token/tick can never fit a chunk beside a decode
        # batch: every admission advances on its own serial tick —
        # ITSELF capped at the budget (block-aligned floor) — yet
        # everything still completes and stays exact.
        _, want = self._run_engine(self._prompts())
        assert got == want
        assert engine.stats()["fused_ticks"] == 0
        assert engine.stats()["forwards_per_tick"] == 1.0
        assert caps and all(c == 1 for c in caps)

    def test_budget_with_room_still_fuses(self):
        engine, got = self._run_engine(
            self._prompts(), prefill_chunk=8, tick_token_budget=64)
        _, want = self._run_engine(self._prompts())
        assert got == want
        assert engine.stats()["fused_ticks"] >= 1
