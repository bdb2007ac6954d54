"""The ONE speculation seam (models/spec.py): every family's
speculative path rides the same draft-propose / verify-accept cores
and the same round driver.

Pinned here:
- GREEDY BIT-EXACTNESS for all five family shapes — dense
  (generate-level loop), dense-kvq (paged dense LM with int8 KV
  pools), paged, paged-prefix, paged-moe — at horizon 1 AND
  at a multi-token horizon k>1: the draft and the horizon affect
  speed, never output.
- STOCHASTIC MoE speculation (the old third copy rejected
  temperature>0): TV-distance pins of the emitted-token law against
  the target softmax, mirroring test_spec_paged's method, plus the
  perfect-draft full-acceptance and reproducibility invariants at the
  server level.
- The NaN-laundering FIX (documented-but-unfixed residual since the
  chaos PR): a NaN verify row yields token -1 under SAMPLING exactly
  as under argmax — acceptance can never cross a poisoned position,
  and a cut on one emits the sentinel instead of resampling through a
  NaN softmax.
- The seam's live accounting (spec_rounds / accept rate / horizon)
  and the measurement-mode PhaseTimer attachment.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpushare.models import moe, quant, spec
from tpushare.models import transformer as tf
from tpushare.models.paged import PagedSlotServer

TF_CFG = tf.tiny(remat=False)
TF_PARAMS = tf.init_params(jax.random.PRNGKey(0), TF_CFG)
TF_DRAFT = (tf.init_params(jax.random.PRNGKey(9), TF_CFG), TF_CFG)
MOE_CFG = moe.tiny(remat=False)
MOE_PARAMS = moe.init_params(jax.random.PRNGKey(0), MOE_CFG)
MOE_QDRAFT = quant.quantize_params(MOE_PARAMS, MOE_CFG)


def _prompt(seed, n, vocab=None):
    rng = np.random.default_rng(seed)
    return jnp.asarray(
        rng.integers(0, vocab or TF_CFG.vocab_size, n), jnp.int32)


def _stream(srv, slot, n):
    out = [int(srv.last_token[slot, 0])]
    while len(out) < n:
        t = srv.step().get(slot, [])
        out.extend(t if isinstance(t, list) else [t])
    return out[:n]


def _greedy_oracle(mk_server, prompt, n):
    srv = mk_server()
    return _stream(srv, srv.admit(prompt), n)


# ---------------------------------------------------------------------------
# Greedy bit-exactness: five family shapes × horizons {1, 2}
# ---------------------------------------------------------------------------

def _paged(spec_draft=None, horizon=1, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("n_blocks", 64)
    kw.setdefault("block_size", 4)
    params, cfg = kw.pop("model", (TF_PARAMS, TF_CFG))
    if cfg is MOE_CFG:
        kw.setdefault("forward_fn", moe.paged_forward)
    return PagedSlotServer(params, cfg, speculative_draft=spec_draft,
                           spec_horizon=horizon, gamma=2, **kw)


SHAPES = {
    # label -> (mk_plain, mk_spec(horizon), prompt, vocab)
    "dense-kvq": (
        lambda: _paged(kv_quant=True),
        lambda h: _paged(TF_DRAFT, h, kv_quant=True),
        17),
    "paged": (
        lambda: _paged(),
        lambda h: _paged(TF_DRAFT, h),
        13),
    "paged-prefix": (
        lambda: _paged(prefix_cache=True),
        lambda h: _paged(TF_DRAFT, h, prefix_cache=True),
        11),
    "paged-moe": (
        lambda: _paged(model=(MOE_PARAMS, MOE_CFG)),
        lambda h: _paged((MOE_QDRAFT, MOE_CFG), h,
                         model=(MOE_PARAMS, MOE_CFG),
                         draft_layers_hook=quant.dequant_hook(MOE_CFG)),
        9),
}


@pytest.mark.parametrize("horizon", [1, 2])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_greedy_bit_exact_per_shape_and_horizon(shape, horizon):
    """The acceptance criterion made a pin: greedy token streams are
    bit-unchanged vs the non-speculative oracle for every family, at
    the classic horizon AND a multi-token one."""
    mk_plain, mk_spec, plen = SHAPES[shape]
    vocab = (MOE_CFG if "moe" in shape else TF_CFG).vocab_size
    prompt = _prompt(3, plen, vocab)
    want = _greedy_oracle(mk_plain, prompt, 12)
    srv = mk_spec(horizon)
    slot = srv.admit(prompt)
    assert _stream(srv, slot, 12) == want
    assert srv.spec_rounds > 0
    assert srv.spec_horizon == horizon


@pytest.mark.parametrize("horizon", [1, 2])
def test_greedy_bit_exact_dense_loop(horizon):
    """The fifth shape: the generate-level dense loop
    (speculative_generate) — exactly greedy at any horizon, for a
    draft that disagrees with the target."""
    from tpushare.models.generate import generate
    from tpushare.models.speculative import speculative_generate
    toks = jnp.stack([_prompt(5, 9), _prompt(6, 9)])
    want = generate(TF_PARAMS, toks, TF_CFG, max_new_tokens=12,
                    temperature=0.0)
    got = speculative_generate(TF_PARAMS, TF_DRAFT[0], toks, TF_CFG,
                               max_new_tokens=12, gamma=2,
                               horizon=horizon)
    assert (np.asarray(want) == np.asarray(got)).all()


def test_horizon_self_draft_accepts_full_block():
    """draft == target at horizon 2: every round must emit the whole
    gamma*horizon+1 block — pins that the catch-up write and the
    acceptance fold handle the longer block (a draft-KV hole at any
    position of the extended block would collapse acceptance from
    round 2 on, exactly like the original gamma-only regression)."""
    srv = _paged((TF_PARAMS, TF_CFG), horizon=2)
    slot = srv.admit(_prompt(4, 9))
    for round_i in range(3):
        out = srv.step()
        assert len(out[slot]) == 5, (round_i, out)     # 2*2 + 1
    assert srv.spec_accept_rate() == 1.0


def test_horizon_validation():
    with pytest.raises(ValueError, match="spec_horizon"):
        _paged(TF_DRAFT, horizon=0)
    with pytest.raises(ValueError, match="gamma"):
        PagedSlotServer(TF_PARAMS, TF_CFG, n_slots=1, n_blocks=16,
                        block_size=4, speculative_draft=TF_DRAFT,
                        gamma=0)
    from tpushare.models.speculative import speculative_generate
    with pytest.raises(ValueError, match="horizon"):
        speculative_generate(TF_PARAMS, TF_PARAMS,
                             jnp.zeros((1, 4), jnp.int32), TF_CFG,
                             gamma=2, horizon=0)


def test_seam_accounting():
    """spec_rounds / spec_draft_tokens / spec_accepted_tokens are the
    /stats + bench surface: proposed = rounds * active * gamma*K,
    accept rate = accepted/proposed in [0, 1] (1.0 for a self-draft)."""
    srv = _paged((TF_PARAMS, TF_CFG), horizon=2)
    slot = srv.admit(_prompt(8, 9))
    for _ in range(4):
        srv.step()
    assert srv.spec_rounds == 4
    assert srv.spec_draft_tokens == 4 * srv.spec_block_len
    assert srv.spec_accepted_tokens == srv.spec_draft_tokens
    assert srv.spec_accept_rate() == 1.0
    del slot


# ---------------------------------------------------------------------------
# Stochastic MoE speculation (temperature > 0 on the third family)
# ---------------------------------------------------------------------------

def _mk_moe_stoch(**kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("n_blocks", 64)
    kw.setdefault("block_size", 4)
    kw.setdefault("temperature", 1.0)
    kw.setdefault("gamma", 3)
    return PagedSlotServer(
        MOE_PARAMS, MOE_CFG, forward_fn=moe.paged_forward,
        speculative_draft=kw.pop("draft", (MOE_PARAMS, MOE_CFG)), **kw)


class TestStochasticMoESpeculation:
    """temperature > 0 MoE speculation on the unified seam: proposals
    sampled from the draft's filtered law, verified by the
    Leviathan/Chen rule PER SLOT, emitted-token marginal == the
    target sampler's law. Mirrors
    test_spec_paged.TestStochasticPagedSpeculation — the TV pin runs
    the seam cores over REAL MoE logits, and the server-level tests
    pin the integration invariants."""

    @staticmethod
    def _null_tv(p, n, reps=200, seed=0):
        rng = np.random.default_rng(seed)
        tvs = [0.5 * np.abs(rng.multinomial(n, p) / n - p).sum()
               for _ in range(reps)]
        return float(np.mean(tvs)), float(np.std(tvs))

    def test_first_token_law_matches_moe_target(self):
        """The round's first emitted token over REAL MoE verify
        logits (int8-self draft law as q) follows the MoE target
        softmax — the seam's acceptance is exact for the family the
        old copy locked out."""
        prompt = _prompt(20, 9, MOE_CFG.vocab_size)
        # Real target/draft logits at the first decode position.
        tlog, _, _ = moe.forward(MOE_PARAMS, prompt[None, :], MOE_CFG,
                                 cache=moe.init_cache(MOE_CFG, 1, 16),
                                 pos_offset=0, last_logit_only=True)
        dlog, _, _ = moe.forward(MOE_QDRAFT, prompt[None, :], MOE_CFG,
                                 cache=moe.init_cache(MOE_CFG, 1, 16),
                                 pos_offset=0, last_logit_only=True,
                                 layers_hook=quant.dequant_hook(MOE_CFG))
        tl = jnp.concatenate([tlog, tlog], axis=1)        # [1, 2, V]
        dl = dlog[:, 0]
        base = jnp.zeros((1,), jnp.int32)

        def one(key):
            kd, ka = jax.random.split(key)
            d0, q0 = spec.draft_sample_core(dl, kd, temperature=1.0)
            a_b, corr = spec.spec_accept_core(
                tl, d0[:, None].astype(jnp.int32), q0[:, None], ka,
                base, cap=1 << 20, temperature=1.0)
            return jnp.where(a_b[0] >= 1, d0[0], corr[0, 0])

        n = 600
        keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(100, 100 + n))
        toks = np.asarray(jax.jit(jax.vmap(one))(keys))
        V = MOE_CFG.vocab_size
        hist = np.bincount(toks, minlength=V).astype(float)
        p_true = np.asarray(jax.nn.softmax(tl[0, 0]), np.float64)
        p_true /= p_true.sum()
        tv = 0.5 * np.abs(hist / n - p_true).sum()
        mu, sd = self._null_tv(p_true, n)
        assert tv < mu + 4 * sd, f"TV {tv} vs null {mu}+-{sd}"

    def test_server_round_token_law_matches_target(self):
        """Server-level TV pin through the REAL MoE server loop: pin
        the pending token after admit (its KV is written by the
        round's own block, so the pin is clean), run one stochastic
        spec round per readmit, and compare the round's first emitted
        token against the EXACT conditional target law — one forward
        on [prompt, pin] gives softmax ground truth. One server, so
        the jit caches make the readmit loop cheap."""
        prompt = _prompt(21, 7, MOE_CFG.vocab_size)
        pin = 3
        ext = jnp.concatenate([prompt, jnp.asarray([pin], jnp.int32)])
        tlog, _, _ = moe.forward(MOE_PARAMS, ext[None, :], MOE_CFG,
                                 cache=moe.init_cache(MOE_CFG, 1, 16),
                                 pos_offset=0, last_logit_only=True)
        p_true = np.asarray(jax.nn.softmax(tlog[0, 0]), np.float64)
        p_true /= p_true.sum()
        srv = _mk_moe_stoch(n_slots=1, gamma=1, seed=11,
                            draft=(MOE_QDRAFT, MOE_CFG),
                            draft_layers_hook=quant.dequant_hook(
                                MOE_CFG))
        n = 220
        toks = []
        for _ in range(n):
            s = srv.admit(prompt)
            srv.last_token = srv.last_token.at[s, 0].set(pin)
            toks.append(srv.step()[s][0])
            srv.evict(s)
        hist = np.bincount(np.asarray(toks),
                           minlength=MOE_CFG.vocab_size).astype(float)
        tv = 0.5 * np.abs(hist / n - p_true).sum()
        mu, sd = self._null_tv(p_true, n)
        assert tv < mu + 4 * sd, f"TV {tv} vs null {mu}+-{sd}"

    def test_perfect_draft_always_accepts(self):
        """draft == target at temperature>0: p/q == 1 pointwise, so
        every round must emit gamma+1 tokens — pins the q bookkeeping
        through the sparse family's forward."""
        srv = _mk_moe_stoch(seed=5)
        slot = srv.admit(_prompt(22, 9, MOE_CFG.vocab_size))
        for round_i in range(4):
            out = srv.step()
            assert len(out[slot]) == 4, (round_i, out)

    def test_stream_reproducible_and_in_vocab(self):
        def run(seed):
            srv = _mk_moe_stoch(draft=(MOE_QDRAFT, MOE_CFG),
                                draft_layers_hook=quant.dequant_hook(
                                    MOE_CFG),
                                temperature=0.8, seed=seed)
            slot = srv.admit(_prompt(23, 11, MOE_CFG.vocab_size))
            out = [int(srv.last_token[slot, 0])]
            while len(out) < 12:
                out.extend(srv.step()[slot])
            return out[:12]

        a, b, c = run(7), run(7), run(8)
        assert a == b
        assert a != c
        assert all(0 <= t < MOE_CFG.vocab_size for t in a)

    def test_stochastic_horizon_runs(self):
        """Stochastic + horizon>1 compose: the round emits up to
        gamma*K+1 and a perfect draft emits exactly that."""
        srv = _mk_moe_stoch(gamma=2, spec_horizon=2, seed=3)
        slot = srv.admit(_prompt(24, 9, MOE_CFG.vocab_size))
        out = srv.step()
        assert len(out[slot]) == 5          # 2*2 + 1, p/q == 1


# ---------------------------------------------------------------------------
# The NaN-laundering fix (stochastic residual closed)
# ---------------------------------------------------------------------------

class TestStochasticNaNGuard:
    """Regression for the documented-but-unfixed residual (PR 4):
    stochastic acceptance resampled through softmax and could launder
    a NaN verify row into a plausible in-vocab id. NaN rows must now
    yield -1 under sampling exactly as under argmax."""

    V = 8

    def _accept(self, tl, drafts, seed=0):
        qd = jax.nn.softmax(jnp.zeros((1, drafts.shape[1], self.V)), -1)
        return spec.spec_accept_core(
            tl, drafts, qd, jax.random.PRNGKey(seed),
            jnp.zeros((1,), jnp.int32), cap=1 << 20, temperature=1.0)

    def test_cut_on_poisoned_row_emits_sentinel(self):
        rng = np.random.default_rng(0)
        tl = jnp.asarray(rng.normal(size=(1, 3, self.V)), jnp.float32)
        tl = tl.at[0, 0].set(jnp.nan)       # poison the cut row
        for seed in range(6):               # any key: never laundered
            a_b, corr = self._accept(
                tl, jnp.asarray([[1, 2]], jnp.int32), seed)
            assert int(a_b[0]) == 0
            assert int(corr[0, 0]) == -1

    def test_poisoned_position_never_accepts(self):
        """Even a draft the (poisoned) target would 'certainly'
        accept cuts the chain at the NaN position; clean prefix
        positions still accept."""
        tl = jnp.where(jnp.arange(self.V)[None, None, :] == 1,
                       50.0, -50.0) * jnp.ones((1, 3, 1))
        tl = jnp.asarray(tl, jnp.float32).at[0, 1].set(jnp.nan)
        a_b, corr = self._accept(tl, jnp.asarray([[1, 1]], jnp.int32))
        assert int(a_b[0]) == 1             # clean pos 0 accepted
        assert int(corr[0, 0]) == -1        # poisoned cut -> sentinel

    def test_clean_rows_unaffected(self):
        """The guard must not perturb clean acceptance: p(draft)=1
        rows accept every position and emit the in-vocab bonus."""
        tl = jnp.where(jnp.arange(self.V)[None, None, :] == 1,
                       50.0, -50.0) * jnp.ones((1, 3, 1))
        a_b, corr = self._accept(jnp.asarray(tl, jnp.float32),
                                 jnp.asarray([[1, 1]], jnp.int32))
        assert int(a_b[0]) == 2
        assert int(corr[0, 0]) == 1

    def test_server_level_poisoned_verify_emits_sentinel(self):
        """A stochastic MoE server whose verify logits come back
        poisoned emits -1 for the poisoned slot (the engine's
        quarantine trigger), never an in-vocab laundered id."""
        srv = _mk_moe_stoch(n_slots=1, gamma=2, seed=1)
        slot = srv.admit(_prompt(30, 7, MOE_CFG.vocab_size))
        real_verify = srv._spec_verify

        def poisoned(block, base):
            tl = real_verify(block, base)
            return tl.at[:].set(jnp.nan)

        srv._spec_verify = poisoned
        out = srv.step()
        assert out[slot][-1] == -1, out
        assert len(out[slot]) == 1          # nothing accepted

    def test_greedy_verify_tokens_is_the_one_guard(self):
        tl = jnp.asarray(np.ones((2, 2, self.V)), jnp.float32)
        tl = tl.at[0, 1].set(jnp.nan)
        got = np.asarray(spec.greedy_verify_tokens(tl))
        assert got[0, 1] == -1
        assert (got != -1)[1].all()


# ---------------------------------------------------------------------------
# PhaseTimer attachment (measurement mode)
# ---------------------------------------------------------------------------

def test_phase_timer_breakdown():
    """An attached PhaseTimer records the draft / verify /
    accept-fold chain per round; detached (the default) the driver
    takes the zero-overhead path (sync-free — test_sync_free pins
    the transfer count)."""
    from tpushare.utils.profiling import PhaseTimer
    srv = _paged((TF_PARAMS, TF_CFG), horizon=2)
    slot = srv.admit(_prompt(40, 9))
    assert srv._spec_timer is None
    srv.step()                              # warm, untimed
    t = PhaseTimer()
    srv._spec_timer = t
    for _ in range(3):
        srv.step()
    snap = t.snapshot()
    assert set(snap) == {"draft", "verify", "accept_fold"}
    for row in snap.values():
        assert row["count"] == 3
        assert row["seconds"] >= 0.0
    assert abs(sum(r["fraction"] for r in snap.values()) - 1.0) < 0.01
    del slot
