"""The ONE speculation seam: draft-propose / verify-accept cores and
the per-slot round driver every serving family shares.

Speculative decoding previously lived as three divergent copies —
generate-level dense loops in ``models/speculative.py``, per-slot
cores + ``_spec_step`` in ``models/paged.py``, and a greedy-only MoE
path in ``models/moe.py`` — so every improvement landed once and
rotted twice (ROADMAP item 5). This module is the single home now:

- **Pure cores** (family-blind math on logits/tokens):
  ``greedy_verify_tokens`` (the NaN→-1 laundering guard's one home),
  ``greedy_accept_core`` (longest matched prefix + capacity clamp),
  ``draft_sample_core`` (one filtered draft proposal + its law) and
  ``spec_accept_core`` (the Leviathan/Chen stochastic rejection rule,
  per-slot or lockstep). The generate-level loops and the paged slot
  server call exactly these.
- **The round driver** (``SpecDecodeMixin._spec_step``): the one
  implementation of a speculative round — h = gamma × horizon draft
  proposals, the draft-KV catch-up write, ONE multi-token target
  verify, per-slot acceptance fold, device-state commit, and the
  round's single device→host fetch (tokens + accepted counts) —
  parameterized by a small per-family hook surface
  (``_spec_draft_step`` / ``_spec_draft_catchup`` / ``_spec_verify``
  / ``_spec_commit`` + state accessors). PagedSlotServer implements
  the hooks; its ``_spec_step`` IS this method.

Draft horizons (the longer-horizon mode): ``spec_horizon=K`` scales
the drafted block to ``gamma*K`` tokens per round — one target weight
stream now verifies up to ``gamma*K+1`` tokens with acceptance-prefix
semantics (the emitted sequence is the longest accepted prefix plus
the target's own correction token, exactly as at K=1, so greedy
output stays bit-identical at ANY horizon and stochastic output keeps
the target law). High-acceptance drafts (int8-self) convert the
longer block into fewer target forwards per emitted token; mismatched
drafts see acceptance decay with K — the ``spec_horizon_sweep`` bench
row measures the tradeoff per family. K=1 is exactly the historical
behavior.

NaN discipline (the stochastic-spec laundering fix): a NaN verify row
must yield token -1 — the invalid-by-construction sentinel the engine
quarantines — under GREEDY (``greedy_verify_tokens``) and under
SAMPLING (``spec_accept_core``: poisoned positions can never accept,
and a correction cut on a poisoned row emits -1 instead of
resampling through a NaN softmax into a plausible in-vocab id).
TokenSampler.pick guards the plain decode path the same way; this
closes the documented residual (PR 4) where stochastic acceptance
could still launder a poisoned round.

Sync discipline: the driver performs exactly ONE device→host transfer
per round (the fused tokens+counts fetch), at any horizon —
tests/test_sync_free.py pins it per family and per horizon. The
optional ``PhaseTimer`` attachment (``srv._spec_timer``) adds
blocking per-phase barriers and is measurement-mode only.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from tpushare.models.serving import upload_mirror
from tpushare.parallel.multihost import addressable_fetch


# ---------------------------------------------------------------------------
# Pure cores
# ---------------------------------------------------------------------------

def greedy_verify_tokens(tl: jnp.ndarray) -> jnp.ndarray:
    """NaN-guarded greedy verify argmax, [..., V] -> [...] int32.

    A NaN logits row picks -1 (invalid by construction): -1 never
    matches a draft, so acceptance cuts BEFORE the poisoned position,
    and the emitted correction is the sentinel the engine quarantines
    — bare argmax would launder real poisoned logits into a plausible
    in-vocab id that replay then preserves. The same guard
    TokenSampler applies to plain decode picks, at the one home every
    greedy verify path shares."""
    return jnp.where(jnp.isnan(tl).any(-1), jnp.int32(-1),
                     jnp.argmax(tl, axis=-1).astype(jnp.int32))


def accept_len(accept: jnp.ndarray) -> jnp.ndarray:
    """Longest accepted prefix: [B, g] bool -> [B] int32 counts."""
    return jnp.sum(jnp.cumprod(accept.astype(jnp.int32), axis=1), axis=1)


def _room_clamp(a_b, base, cap):
    """Clamp accepted counts so a round's emit count (a+1) never takes
    a slot past ``cap`` tokens: a_b <= max(cap - base - 1, 0). A slot
    with room for the whole block passes through unchanged — the MoE
    host-side guard (lengths + h + 1 <= max_len) makes this a no-op
    there, while paged slots rely on it at capacity."""
    return jnp.minimum(a_b, jnp.maximum(cap - base - 1, 0))


def greedy_accept_core(tl, drafts, base, *, cap: int,
                       lockstep: bool = False):
    """Greedy verify-accept: longest prefix of ``drafts`` [B, g]
    matching the NaN-guarded argmax of ``tl`` [B, g+1, V], clamped to
    the per-slot room (``cap`` static capacity, ``base`` [B] current
    lengths). Returns (a_b [B], correction [B, 1]) — the correction is
    the target's own pick at the cut position (the bonus token when
    every draft accepted; -1 when the cut row is poisoned).

    ``lockstep=True`` is the generate-level dense loops' batching
    compromise: every row cuts at the batch MIN (rows stay exactly
    greedy — a_b >= a* for all b — trading speedup for static
    shapes). The slot servers keep per-row ragged acceptance."""
    g = drafts.shape[1]
    greedy = greedy_verify_tokens(tl)
    a_b = accept_len(greedy[:, :g] == drafts)
    a_b = _room_clamp(a_b, base, cap)
    if lockstep:
        a_b = jnp.broadcast_to(jnp.min(a_b), a_b.shape)
    correction = jnp.take_along_axis(greedy, a_b[:, None], 1)
    return a_b, correction


def draft_sample_core(logits, key, *, temperature: float,
                      top_k=None, top_p=None):
    """One draft proposal: sample [B] tokens from the filtered draft
    law on [B, V] logits and return that law (needed by the accept
    rule's q(x) and residual)."""
    from tpushare.models.generate import filter_logits
    f = filter_logits(logits, temperature, top_k=top_k, top_p=top_p)
    return (jax.random.categorical(key, f, axis=-1),
            jax.nn.softmax(f, axis=-1))


def spec_accept_core(tl, drafts, qdists, key, base, *,
                     cap: int, temperature: float,
                     top_k=None, top_p=None,
                     lockstep: bool = False):
    """Stochastic acceptance (Leviathan/Chen rejection rule) over the
    verify logits — per slot by default, lockstep-min for the dense
    generate-level loop.

    tl [B, g+1, V] target verify logits, drafts [B, g] proposals drawn
    from the draft's filtered law, qdists [B, g, V] that law. Both
    sides run through the SAME filter_logits the server's TokenSampler
    applies, so every emitted token's marginal is exactly the
    non-speculative sampler's law (the rejection rule is exact for any
    filtered target/draft pair). Returns (a_b [B] accepted counts
    clamped to capacity, correction [B, 1] the cut-position token:
    the accepted draft when the cut lands on an accepted position
    (capacity clamp), else a residual max(0, p-q) resample — the bonus
    position has q=0, reducing the residual to plain p).

    NaN guard (the laundering fix): a poisoned verify row can never
    accept its draft (the cut lands at or before it), and a cut ON a
    poisoned row emits -1 instead of resampling through a NaN softmax
    — without this, ``jnp.where(mass > eps)`` read a NaN mass as
    False, fell back to the NaN target law, and
    ``jax.random.categorical`` laundered it into a plausible in-vocab
    id (the documented-but-unfixed stochastic residual from PR 4)."""
    from tpushare.models.generate import filter_logits
    B, g = drafts.shape
    V = tl.shape[-1]
    bad = jnp.isnan(tl).any(-1)                               # [B, g+1]
    p = jax.nn.softmax(
        filter_logits(tl, temperature, top_k=top_k, top_p=top_p), axis=-1)
    pxs = jnp.take_along_axis(p[:, :g], drafts[..., None], 2)[..., 0]
    qxs = jnp.take_along_axis(qdists, drafts[..., None], 2)[..., 0]
    k_acc, k_res = jax.random.split(key)
    u = jax.random.uniform(k_acc, (B, g))
    # NaN pxs already compares False, but make the rejection explicit:
    # a poisoned verify position must cut the chain, never accept.
    accept = (u < jnp.minimum(1.0, pxs / jnp.maximum(qxs, 1e-30))) \
        & ~bad[:, :g]
    a_b = _room_clamp(accept_len(accept), base, cap)
    if lockstep:
        a_b = jnp.broadcast_to(jnp.min(a_b), a_b.shape)
    ga = jnp.broadcast_to(a_b[:, None, None], (B, 1, V))
    p_at = jnp.take_along_axis(p, ga, 1)[:, 0]                 # [B, V]
    qpad = jnp.concatenate([qdists, jnp.zeros_like(qdists[:, :1])], 1)
    q_at = jnp.take_along_axis(qpad, ga, 1)[:, 0]
    resid = jnp.maximum(p_at - q_at, 0.0)
    mass = jnp.sum(resid, axis=-1, keepdims=True)
    resid = jnp.where(mass > 1e-12, resid / mass, p_at)
    resampled = jax.random.categorical(
        k_res, jnp.log(jnp.maximum(resid, 1e-30)), axis=-1)
    acc_pad = jnp.concatenate([accept, jnp.zeros((B, 1), bool)], 1)
    acc_at = jnp.take_along_axis(acc_pad, a_b[:, None], 1)[:, 0]
    draft_pad = jnp.concatenate([drafts, jnp.zeros_like(drafts[:, :1])], 1)
    draft_at = jnp.take_along_axis(draft_pad, a_b[:, None], 1)[:, 0]
    correction = jnp.where(acc_at, draft_at,
                           resampled.astype(drafts.dtype))
    # A cut on a poisoned row: the residual above was computed from
    # NaN probabilities — emit the -1 sentinel the engine quarantines.
    cut_bad = jnp.take_along_axis(bad, a_b[:, None], 1)[:, 0]
    correction = jnp.where(cut_bad, jnp.asarray(-1, drafts.dtype),
                           correction)
    return a_b, correction[:, None]


def build_spec_cores(*, cap: int, temperature: float,
                     top_k=None, top_p=None, stochastic: bool):
    """The per-server jitted core dispatches every speculative slot
    server builds at construction: (greedy_accept, draft_sample,
    stochastic_accept) — the latter two None when greedy. One builder
    so the families' core wiring (capacity clamp, shared sampler
    filters) cannot drift."""
    greedy = jax.jit(functools.partial(greedy_accept_core, cap=cap))
    if not stochastic:
        return greedy, None, None
    sample = jax.jit(functools.partial(
        draft_sample_core, temperature=temperature,
        top_k=top_k, top_p=top_p))
    accept = jax.jit(functools.partial(
        spec_accept_core, cap=cap, temperature=temperature,
        top_k=top_k, top_p=top_p))
    return greedy, sample, accept


# ---------------------------------------------------------------------------
# The round driver
# ---------------------------------------------------------------------------

class SpecDecodeMixin:
    """The shared speculative-round driver for the slot-server
    families. A server opts in by calling ``_spec_init`` at
    construction and implementing the hook surface; ``_spec_step``
    (the engine-tick method) then has exactly ONE implementation.

    Hook contract (all device-side; no hook may perform a host
    transfer — TS103/TS104 police the whole chain):

    - ``_spec_begin(h)`` -> base [B] device lengths, after any
      capacity prep (paged: ``_grow_active(extra=h)``).
    - ``_spec_draft_step(tok, base, j)`` -> [B, V] draft logits for
      proposal j, advancing the draft KV at position ``base + j``.
    - ``_spec_draft_catchup(block, tok, base, h)``: ensure draft KV
      exists through position ``base + h`` (the proposal loop only
      wrote KV for its INPUTS; without this a fully-accepted round
      leaves a permanent draft-KV hole at base+h that degrades every
      later proposal exactly in the high-acceptance regime
      speculation exists for). Returns a device reference to the
      catch-up write (draft pools / cache leaves) — measurement mode
      blocks on it so the catch-up dispatch's wall-clock lands in the
      DRAFT phase, not the verify span it would otherwise drain into.
    - ``_spec_verify(block, base)`` -> [B, h+1, V] target verify
      logits; target KV written, lengths NOT advanced (rejected
      positions leave stale KV the length mask keeps unattended until
      the next round overwrites it — free rollback).
    - ``_spec_commit(a_b, correction, active)``: advance device
      lengths by (a+1) per active slot and fold the correction into
      ``last_token``.
    - ``_spec_host_lengths()`` -> the np lengths mirror;
      ``_spec_capacity()`` -> the static per-slot token capacity.

    Requires (both families already have them): ``gamma``,
    ``spec_horizon``, ``active`` (host bool), ``_active_dev``,
    ``last_token``, ``_sampler``, ``device_fetches``.
    """

    #: measurement-mode per-phase timer (utils/profiling.PhaseTimer);
    #: None (the default) costs nothing and keeps the round sync-free.
    _spec_timer = None

    def _spec_init(self, *, gamma: int, spec_horizon: int,
                   temperature: float, top_k, top_p, cap: int) -> None:
        if gamma < 1:
            raise ValueError(f"gamma must be >= 1, got {gamma}")
        if spec_horizon < 1:
            raise ValueError(
                f"spec_horizon must be >= 1, got {spec_horizon}")
        self.gamma = gamma
        self.spec_horizon = spec_horizon
        self._spec_stochastic = temperature > 0.0
        self._spec_timer = None
        # Live acceptance accounting (the /stats + bench surface):
        # rounds run, draft tokens proposed, draft tokens accepted
        # (corrections excluded — accept rate is about the DRAFTS).
        self.spec_rounds = 0
        self.spec_draft_tokens = 0
        self.spec_accepted_tokens = 0
        (self._greedy_accept, self._draft_sample,
         self._spec_accept) = build_spec_cores(
            cap=cap, temperature=temperature, top_k=top_k,
            top_p=top_p, stochastic=self._spec_stochastic)

    @property
    def spec_block_len(self) -> int:
        """Drafted tokens per round: gamma × horizon (the round's
        verify block is this + 1; the round's emit count is at most
        this + 1 — the granule the engine's tick-token budget must
        cover)."""
        return self.gamma * self.spec_horizon

    def spec_accept_rate(self) -> Optional[float]:
        """Accepted / proposed draft tokens over the server's
        lifetime (None before the first round): 1.0 = every draft
        accepted — the live signal for tuning gamma × horizon."""
        if not self.spec_draft_tokens:
            return None
        return self.spec_accepted_tokens / self.spec_draft_tokens

    def _spec_step(self) -> Dict[int, list]:
        """One speculative round: h = gamma×horizon draft proposals +
        one multi-token target verify; per-slot acceptance-prefix
        fold. Greedy emission is exactly what non-speculative greedy
        decoding produces (the draft affects speed, never output);
        stochastic emission keeps the target sampler's law per token
        (Leviathan/Chen). ONE device→host transfer per round — the
        tokens + accepted counts fetch — at any horizon."""
        return self._spec_step_async().finalize()

    def _spec_step_async(self):
        """_spec_step with the round's one fetch deferred
        (serving.PendingStep contract). Dispatch side: drafts, verify,
        device-side commit (lengths + correction fold), counters that
        need no fetch. Finalize side: the tokens + accepted-counts
        fetch, the host lengths-mirror advance it implies, acceptance
        accounting, out-dict build, and capacity retirement (the
        accepted count per slot is unknowable before the fetch)."""
        from tpushare.models.serving import PendingStep
        if not self.active.any():
            return PendingStep.done({})
        h = self.spec_block_len
        timer = self._spec_timer
        if timer is not None:
            timer.start()
        base = self._spec_begin(h)
        active = self._active_dev
        tok = self.last_token
        stochastic = self._spec_stochastic
        drafts: List[jnp.ndarray] = []
        qdists: List[jnp.ndarray] = []
        if stochastic:
            # h proposal keys + 1 accept/resample key, all off the
            # server's reproducible (seed, draws) stream.
            keys = jax.random.split(self._sampler.next_key(), h + 1)
        for j in range(h):
            dl = self._spec_draft_step(tok, base, j)
            if stochastic:
                nxt, qd = self._draft_sample(dl, keys[j])
                tok = nxt.astype(jnp.int32)[:, None]
                qdists.append(qd)
            else:
                tok = jnp.argmax(dl, axis=-1).astype(jnp.int32)[:, None]
            drafts.append(tok)
        drafts_arr = jnp.concatenate(drafts, axis=1)          # [B, h]
        block = jnp.concatenate([self.last_token, drafts_arr], axis=1)
        catchup_ref = self._spec_draft_catchup(block, tok, base, h)
        if timer is not None:
            # Block on the catch-up's own outputs too: `block` does
            # not depend on them, so marking on it alone would let
            # the catch-up dispatch drain inside the verify span.
            timer.mark("draft", (block, catchup_ref))
        tl = self._spec_verify(block, base)
        if timer is not None:
            timer.mark("verify", tl)
        if stochastic:
            a_b, correction = self._spec_accept(
                tl, drafts_arr, jnp.stack(qdists, axis=1), keys[h], base)
        else:
            a_b, correction = self._greedy_accept(tl, drafts_arr, base)
        self._spec_commit(a_b, correction, active)
        return self._spec_pending(drafts_arr, correction, a_b)

    def _spec_pending(self, drafts_arr, correction, a_b, first=None):
        """The round's deferred half, as a PendingStep: everything after
        the device-side commit. Where a family's draft is a module of
        the target itself (latent.LatentSlotServer: draft, verify,
        accept and commit are ONE program of its own, so none of the
        dispatch hooks above applies), it joins the seam here, with
        drafts_arr [B, h], correction [B, 1] and a_b [B] as its program
        left them on the device. ``first`` = (slot, token [1] on the
        device): an admission the same program completed; its first
        token rides the round's one fetch."""
        from tpushare.models.serving import PendingStep
        timer = self._spec_timer
        h = drafts_arr.shape[1]
        cap = self._spec_capacity()
        slots = [int(s) for s in np.nonzero(self.active)[0]]
        self.spec_rounds += 1
        self.spec_draft_tokens += len(slots) * h

        def _finalize(invalid):
            # ONE transfer per round: tokens + accepted counts in a
            # single fetch; the host lengths mirror then advances by
            # the same a+1 the commit's device formula applied —
            # per recorded slot, skipping slots whose request changed
            # in flight (their mirror was reset by evict/re-admit).
            self.device_fetches += 1
            drafts_np, corr_np, a_np, *first_np = addressable_fetch(
                (drafts_arr, correction, a_b)
                + (() if first is None else (first[1],)))
            if timer is not None:
                timer.mark("accept_fold")
            lnp = self._spec_host_lengths()
            out: Dict[int, list] = {}
            retired = False
            for slot in slots:
                if slot in invalid:
                    continue
                a = int(a_np[slot])
                lnp[slot] += a + 1
                self.spec_accepted_tokens += a
                out[slot] = ([int(t) for t in drafts_np[slot, :a]]
                             + [int(corr_np[slot, 0])])
                if int(lnp[slot]) >= cap:
                    self.active[slot] = False
                    retired = True
            if retired:
                self._active_dev = upload_mirror(self.active)
            if first is not None and first[0] not in invalid:
                out[first[0]] = int(first_np[0][0])
            return out

        return PendingStep(_finalize, slots=slots + (
            [] if first is None else [first[0]]))
