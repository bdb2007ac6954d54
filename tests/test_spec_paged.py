"""Speculative decoding over the paged pools (PagedSlotServer
speculative_draft): every emitted token must be EXACTLY what greedy
non-speculative decoding produces — the draft model affects speed,
never output — with per-slot ragged acceptance (no dense-loop lockstep),
composing with prefix caching and int8 KV pools. The server-level
properties are held under both forward functions the server runs
(``family``: the dense LM, the sparse one via moe.paged_forward)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpushare.models import moe, quant
from tpushare.models import transformer as tf
from tpushare.models.paged import PagedSlotServer

CFG = tf.tiny(remat=False)
PARAMS = tf.init_params(jax.random.PRNGKey(0), CFG)
DRAFT_SAME = (PARAMS, CFG)                    # self-draft: 100% accept
DRAFT_OTHER = (tf.init_params(jax.random.PRNGKey(9), CFG), CFG)
MOE_CFG = moe.tiny(remat=False)
MOE_PARAMS = moe.init_params(jax.random.PRNGKey(0), MOE_CFG)

FAMILIES = ("dense", "moe")
# family -> (params, cfg, server kwargs, self-draft, mismatched draft)
FAMILY = {
    "dense": (PARAMS, CFG, {}, DRAFT_SAME, DRAFT_OTHER),
    "moe": (MOE_PARAMS, MOE_CFG, {"forward_fn": moe.paged_forward},
            (MOE_PARAMS, MOE_CFG),
            (moe.init_params(jax.random.PRNGKey(9), MOE_CFG), MOE_CFG)),
}


def _prompt(seed, n, family="dense"):
    rng = np.random.default_rng(seed)
    return jnp.asarray(
        rng.integers(0, FAMILY[family][1].vocab_size, n), jnp.int32)


def _mk(spec=None, family="dense", **kw):
    """``spec``: a (params, cfg) draft, or "same" / "other" for the
    family's own self-draft and mismatched draft."""
    params, cfg, fkw, same, other = FAMILY[family]
    spec = {"same": same, "other": other}.get(spec, spec) \
        if isinstance(spec, str) else spec
    kw.setdefault("n_slots", 2)
    kw.setdefault("n_blocks", 32)
    kw.setdefault("block_size", 4)
    return PagedSlotServer(params, cfg, speculative_draft=spec, **fkw,
                           **kw)


def _greedy_reference(prompt, n, **kw):
    srv = _mk(None, **kw)
    slot = srv.admit(prompt)
    out = [int(srv.last_token[slot, 0])]
    while len(out) < n:
        out.append(srv.step()[slot])
    return out[:n]


def _spec_stream(srv, slot, n):
    out = [int(srv.last_token[slot, 0])]
    while len(out) < n:
        out.extend(srv.step()[slot])
    return out[:n]


@pytest.mark.parametrize("family", FAMILIES)
@pytest.mark.parametrize("draft", ["same", "other"])
def test_spec_matches_greedy(draft, family):
    prompt = _prompt(3, 13, family)
    want = _greedy_reference(prompt, 12, family=family)
    srv = _mk(draft, family, gamma=3)
    slot = srv.admit(prompt)
    assert _spec_stream(srv, slot, 12) == want


@pytest.mark.parametrize("family", FAMILIES)
def test_self_draft_accepts_full_blocks(family):
    """draft == target: EVERY round must emit gamma+1 tokens — not
    just the first. (Regression: the g-step draft loop never wrote the
    last proposal's KV, so each fully-accepted round left a draft-KV
    hole at base+gamma and acceptance collapsed from round 2 on.)"""
    srv = _mk("same", family, gamma=3)
    slot = srv.admit(_prompt(4, 9, family))
    for round_i in range(4):
        out = srv.step()
        assert len(out[slot]) == 4, (round_i, out)     # gamma + 1


@pytest.mark.parametrize("family", FAMILIES)
def test_per_slot_ragged_acceptance(family):
    """Two slots advance independently (the dense loop's lockstep min
    is gone): each slot's flattened stream equals its solo greedy run
    even when their acceptance counts differ per round."""
    p1, p2 = _prompt(5, 11, family), _prompt(6, 7, family)
    want1 = _greedy_reference(p1, 10, family=family)
    want2 = _greedy_reference(p2, 10, family=family)
    srv = _mk("other", family, gamma=3)
    s1, s2 = srv.admit(p1), srv.admit(p2)
    got1, got2 = [int(srv.last_token[s1, 0])], [int(srv.last_token[s2, 0])]
    while len(got1) < 10 or len(got2) < 10:
        out = srv.step()
        got1.extend(out.get(s1, []))
        got2.extend(out.get(s2, []))
    assert got1[:10] == want1
    assert got2[:10] == want2


@pytest.mark.parametrize("family", FAMILIES)
def test_spec_with_prefix_cache(family):
    shared = _prompt(7, 8, family)
    p1 = jnp.concatenate([shared, _prompt(8, 3, family)])
    p2 = jnp.concatenate([shared, _prompt(9, 5, family)])
    want = _greedy_reference(p2, 8, family=family, prefix_cache=True)
    srv = _mk("other", family, gamma=3, prefix_cache=True)
    srv.admit(p1)
    s2 = srv.admit(p2)
    assert srv.last_cached_len == 8           # shared blocks hit
    assert _spec_stream(srv, s2, 8) == want


def test_spec_with_int8_pools():
    prompt = _prompt(10, 13)
    want = _greedy_reference(prompt, 10, kv_quant=True)
    srv = _mk(DRAFT_OTHER, gamma=3, kv_quant=True)
    slot = srv.admit(prompt)
    assert _spec_stream(srv, slot, 10) == want


@pytest.mark.parametrize("family", FAMILIES)
def test_spec_capacity_deactivates_cleanly(family):
    """Acceptance clamps at slot capacity; the slot retires exactly
    like the non-speculative server (no KV past the last block — the
    trash-routing guard) and with the same tokens."""
    kw = dict(n_slots=1, n_blocks=8, block_size=4,
              max_blocks_per_slot=5)        # capacity 20
    prompt = _prompt(11, 9, family)
    ref = _mk(None, family, **kw)
    s0 = ref.admit(prompt)
    want = [int(ref.last_token[s0, 0])]
    while ref.active[s0]:
        out = ref.step()
        if s0 in out:
            want.append(out[s0])
    srv = _mk("same", family, gamma=3, **kw)
    slot = srv.admit(prompt)
    got = [int(srv.last_token[slot, 0])]
    while srv.active[slot]:
        out = srv.step()
        got.extend(out.get(slot, []))
    assert got == want
    assert int(srv.cache.lengths[slot]) <= srv.slot_capacity


def _mlora_bank(n=2):
    """Adapter bank with LARGE nonzero deltas so an adapter-blind
    draft would visibly disagree with the adapted target. init_lora
    zeroes B (delta starts at exactly 0), so BOTH factors are filled
    with noise here."""
    from tpushare.models import lora
    ads = []
    for i in range(n):
        ad = lora.init_lora(jax.random.PRNGKey(40 + i), CFG, rank=2)
        leaves, treedef = jax.tree.flatten(ad)
        keys = jax.random.split(jax.random.PRNGKey(100 + i), len(leaves))
        ads.append(jax.tree.unflatten(treedef, [
            0.3 * jax.random.normal(k, l.shape, l.dtype)
            for k, l in zip(keys, leaves)]))
    return lora.stack_adapters(ads)


def test_spec_mlora_matches_nonspec_per_adapter():
    """Speculative x multi-LoRA (the last documented serving seam):
    three slots on adapters 0/1/base must emit exactly their
    non-speculative adapted streams — the verify side runs the adapted
    target, and the draft carries the same bank so acceptance holds."""
    bank = _mlora_bank()
    # SAME prompt for all three slots: any stream difference is the
    # adapter's doing (and the vacuousness guard below has teeth).
    prompts = [_prompt(30, 9)] * 3
    adapters = [0, 1, -1]

    ref = _mk(None, multi_lora=bank, n_slots=3)
    want = []
    for p, a in zip(prompts, adapters):
        s = ref.admit(p, adapter=a)
        out = [int(ref.last_token[s, 0])]
        while len(out) < 8:
            out.append(ref.step()[s])
        ref.evict(s)
        want.append(out)
    # Vacuousness guard: the adapters must actually change the model
    # (identical streams would make spec-vs-nonspec parity meaningless).
    assert len({tuple(w) for w in want}) == 3, want

    srv = _mk(DRAFT_SAME, gamma=3, multi_lora=bank, n_slots=3)
    slots = [srv.admit(p, adapter=a) for p, a in zip(prompts, adapters)]
    got = [[int(srv.last_token[s, 0])] for s in slots]
    while any(len(g) < 8 for g in got):
        out = srv.step()
        for i, s in enumerate(slots):
            got[i].extend(out.get(s, []))
    assert [g[:8] for g in got] == want


def test_spec_mlora_self_draft_accepts_fully():
    """draft == target (same bank): every round emits gamma+1 for every
    adapted slot — pins that the draft actually APPLIES the adapters
    (an adapter-blind draft diverges under _mlora_bank's noise-filled
    factors)."""
    bank = _mlora_bank()
    srv = _mk(DRAFT_SAME, gamma=3, multi_lora=bank, n_slots=2)
    s0 = srv.admit(_prompt(33, 9), adapter=0)
    s1 = srv.admit(_prompt(34, 8), adapter=1)
    for round_i in range(3):
        out = srv.step()
        assert len(out[s0]) == 4 and len(out[s1]) == 4, (round_i, out)


def test_spec_mlora_rejects_geometry_mismatch():
    import dataclasses
    bank = _mlora_bank()
    other_cfg = dataclasses.replace(CFG, n_layers=CFG.n_layers + 1)
    draft = (tf.init_params(jax.random.PRNGKey(2), other_cfg), other_cfg)
    with pytest.raises(NotImplementedError, match="geometry"):
        _mk(draft, multi_lora=bank)


@pytest.mark.parametrize("family", FAMILIES)
def test_quantized_self_draft(family):
    """Quantized self-speculation: the int8 rounding of the target as
    the draft — still bit-exact greedy output, and acceptance is high
    (the draft is the target's own rounding)."""
    prompt = _prompt(12, 13, family)
    want = _greedy_reference(prompt, 12, family=family)
    params, cfg = FAMILY[family][:2]
    srv = _mk((quant.quantize_params(params, cfg), cfg), family,
              draft_layers_hook=quant.dequant_hook(cfg), gamma=3)
    slot = srv.admit(prompt)
    rounds = 0
    out = [int(srv.last_token[slot, 0])]
    while len(out) < 12:
        out.extend(srv.step()[slot])
        rounds += 1
    assert out[:12] == want
    # int8-rounded draft of random weights tracks the target closely:
    # mean emitted per round must beat the no-speculation floor of 1.
    assert (len(out) - 1) / rounds > 1.5, (len(out), rounds)


def test_gamma_validated():
    with pytest.raises(ValueError):
        _mk(DRAFT_SAME, gamma=0)


class TestStochasticPagedSpeculation:
    """temperature > 0 paged speculation (VERDICT r4 #6): proposals are
    sampled from the draft's filtered law, verified by the
    Leviathan/Chen rejection rule PER SLOT (no lockstep min), and every
    emitted token's marginal must equal the non-speculative sampler's
    law. The distribution pins run at the spec_accept_core level —
    fixed synthetic logits, one compiled vmap over hundreds of keys —
    mirroring test_speculative.TestSpeculativeSampling's TV-vs-null
    method; server-level tests cover the integration properties."""

    V = 16

    @staticmethod
    def _null_tv(p, n, reps=200, seed=0):
        rng = np.random.default_rng(seed)
        tvs = [0.5 * np.abs(rng.multinomial(n, p) / n - p).sum()
               for _ in range(reps)]
        return float(np.mean(tvs)), float(np.std(tvs))

    def _first_token_law(self, tlog, dlog, n, seed0, temperature=1.0,
                         top_k=None, top_p=None):
        """Empirical law of the round's FIRST emitted token (accepted
        draft or cut-0 residual resample) for g=1 synthetic logits."""
        from tpushare.models.paged import (draft_sample_core,
                                           spec_accept_core)
        tl = jnp.asarray(tlog, jnp.float32)[None]      # [1, 2, V]
        dl = jnp.asarray(dlog, jnp.float32)[None]      # [1, V]
        base = jnp.zeros((1,), jnp.int32)

        def one(key):
            kd, ka = jax.random.split(key)
            d0, q0 = draft_sample_core(dl, kd, temperature=temperature,
                                       top_k=top_k, top_p=top_p)
            a_b, corr = spec_accept_core(
                tl, d0[:, None].astype(jnp.int32), q0[:, None], ka,
                base, cap=1 << 20, temperature=temperature,
                top_k=top_k, top_p=top_p)
            return jnp.where(a_b[0] >= 1, d0[0], corr[0, 0])

        keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(seed0, seed0 + n))
        toks = np.asarray(jax.jit(jax.vmap(one))(keys))
        return np.bincount(toks, minlength=self.V).astype(float)

    def test_first_token_matches_target_law(self):
        rng = np.random.default_rng(0)
        tlog = rng.normal(size=(2, self.V))
        dlog = rng.normal(size=(self.V,))              # mismatched draft
        p_true = np.asarray(jax.nn.softmax(jnp.asarray(tlog[0])),
                            np.float64)
        p_true /= p_true.sum()
        n = 600
        hist = self._first_token_law(tlog, dlog, n, seed0=100)
        tv = 0.5 * np.abs(hist / n - p_true).sum()
        mu, sd = self._null_tv(p_true, n)
        assert tv < mu + 4 * sd, f"TV {tv} vs null {mu}+-{sd}"

    def test_law_independent_of_draft(self):
        rng = np.random.default_rng(1)
        tlog = rng.normal(size=(2, self.V))
        n = 600
        h_self = self._first_token_law(tlog, tlog[0], n, seed0=300)
        h_mism = self._first_token_law(tlog, rng.normal(size=(self.V,)),
                                       n, seed0=700)
        tv = 0.5 * np.abs(h_self / n - h_mism / n).sum()
        p_hat = h_self / n
        mu, sd = self._null_tv(p_hat, n)
        lim = np.sqrt(2) * mu + 4 * sd
        assert tv < lim, f"draft-dependent law: {tv} > {lim}"

    def test_top_k_filter_respected(self):
        """With target top_k=4, emitted tokens must stay inside the
        target's top-4 set and follow the renormalized law (both sides
        share the sampler's filter_logits)."""
        rng = np.random.default_rng(2)
        tlog = rng.normal(size=(2, self.V))
        dlog = rng.normal(size=(self.V,))
        n = 600
        hist = self._first_token_law(tlog, dlog, n, seed0=900, top_k=4)
        keep = np.argsort(tlog[0])[-4:]
        assert hist[[i for i in range(self.V) if i not in keep]].sum() == 0
        p_true = np.zeros(self.V)
        p_true[keep] = np.exp(tlog[0][keep])
        p_true /= p_true.sum()
        tv = 0.5 * np.abs(hist / n - p_true).sum()
        mu, sd = self._null_tv(p_true, n)
        assert tv < mu + 4 * sd

    def test_perfect_draft_always_accepts(self):
        """draft == target at temperature>0: p/q == 1 pointwise, so
        every round must emit gamma+1 tokens — pins the q bookkeeping
        (a proposal scored against a mismatched q would reject)."""
        srv = _mk(DRAFT_SAME, gamma=3, temperature=1.0, seed=5)
        slot = srv.admit(_prompt(20, 9))
        for round_i in range(4):
            out = srv.step()
            assert len(out[slot]) == 4, (round_i, out)

    def test_stream_reproducible_and_in_vocab(self):
        """Same seed -> identical stream (the sampler's (seed, draws)
        stream drives proposals and accept/resample); tokens in-vocab;
        mismatched draft still completes."""
        def run(seed):
            srv = _mk(DRAFT_OTHER, gamma=3, temperature=0.8, top_p=0.9,
                      seed=seed)
            slot = srv.admit(_prompt(21, 11))
            out = [int(srv.last_token[slot, 0])]
            while len(out) < 12:
                out.extend(srv.step()[slot])
            return out[:12]

        a, b, c = run(7), run(7), run(8)
        assert a == b
        assert a != c                   # astronomically unlikely equal
        assert all(0 <= t < CFG.vocab_size for t in a)

    @pytest.mark.parametrize("family", FAMILIES)
    def test_stochastic_capacity_clamp(self, family):
        """Capacity clamp at temperature>0: the slot retires without
        device lengths ever exceeding capacity."""
        srv = _mk("same", family, gamma=3, temperature=1.0, n_slots=1,
                  n_blocks=8, block_size=4, max_blocks_per_slot=5)
        slot = srv.admit(_prompt(22, 9, family))
        while srv.active[slot]:
            srv.step()
        assert int(srv.cache.lengths[slot]) <= srv.slot_capacity
