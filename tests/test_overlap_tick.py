"""Overlapped tick pipeline (ISSUE 17, 33).

The engine keeps up to two ticks in flight: with tick N's fetch owed,
a pass dispatches tick N+1 FIRST and only then fetches and applies N,
so the device has its next program queued before a token comes home.
These tests pin the contract:

* bit-exactness — the overlapped engine serves byte-identical token
  streams to the serial engine across every family shape (dense rows,
  KV-quota'd dense, chunked/fused paged, speculative, paged MoE,
  MoE rows);
* the deferred fetch — at most one device->host transfer per tick,
  the next dispatch goes out BEFORE the fetch of the oldest owed tick,
  never more than two are owed, and the overlap-window pick makes ZERO
  transfers;
* running ahead — a stream the owed tick ends (end-of-sequence,
  max_tokens, a NaN) has a row in the tick dispatched ahead, and that
  row reaches nobody: not the stream, not the slot's next tenant, not
  the same request replayed into the same slot; an admission a fused
  tick completed owns its first decode token; a speculative engine
  never runs ahead;
* fault domains — a forward fault at the overlapped dispatch
  quarantines the DISPATCHED tick's slots, never the next tick's
  picked set; a device fault surfacing at finalize replays token-
  exact;
* /stats — host_gap_ms / overlap_enabled / pipeline_flushes report
  null (not zero) in serial mode and real values under overlap.
"""

import jax
import numpy as np
import pytest

from tpushare.chaos import InjectedXlaRuntimeError
from tpushare.cli import serve as serve_mod
from tpushare.cli.serve import ServeEngine, _Request
from tpushare.models import moe, quant
from tpushare.models import transformer as tf
from tpushare.slo import TenantQuotaSpec
from test_sync_free import count_transfers

TF_CFG = tf.tiny(remat=False)
TF_PARAMS = tf.init_params(jax.random.PRNGKey(0), TF_CFG)
MOE_CFG = moe.tiny(remat=False)
MOE_PARAMS = moe.init_params(jax.random.PRNGKey(0), MOE_CFG)

FAMILIES = ("dense", "dense-kvq", "paged", "paged-spec", "paged-moe",
            "paged-moe-spec")


def make_engine(family, *, overlap, **kw):
    kw.setdefault("idle_sleep_s", 0.0)
    kw.setdefault("chaos_spec", "")     # never inherit the session env
    kw["overlap_tick"] = overlap
    if family == "dense":
        return ServeEngine(TF_PARAMS, TF_CFG, n_slots=2, n_blocks=48,
                           block_size=8, **kw)
    if family == "dense-kvq":
        return ServeEngine(TF_PARAMS, TF_CFG, n_slots=2, n_blocks=48,
                           block_size=8,
                           tenant_quotas={"acme":
                                          TenantQuotaSpec(4, 24)},
                           **kw)
    if family == "paged":                       # chunked => fused admits
        return ServeEngine(TF_PARAMS, TF_CFG, n_slots=2, n_blocks=48,
                           block_size=8, prefill_chunk=8, **kw)
    if family == "paged-spec":
        return ServeEngine(TF_PARAMS, TF_CFG, n_slots=2, n_blocks=48,
                           block_size=8,
                           speculative_draft=(TF_PARAMS, TF_CFG),
                           gamma=2, spec_horizon=2, **kw)
    if family == "paged-moe":
        return ServeEngine(MOE_PARAMS, MOE_CFG, model_family="moe",
                           n_slots=2, n_blocks=48,
                           block_size=8, prefill_chunk=8, **kw)
    if family == "paged-moe-spec":
        return ServeEngine(
            MOE_PARAMS, MOE_CFG, model_family="moe", n_slots=2,
            n_blocks=48, block_size=8,
            speculative_draft=(quant.quantize_params(MOE_PARAMS,
                                                     MOE_CFG), MOE_CFG),
            draft_layers_hook=quant.dequant_hook(MOE_CFG),
            gamma=2, **kw)
    raise AssertionError(family)


def vocab_of(family):
    return (MOE_CFG if "moe" in family else TF_CFG).vocab_size


def prompts_for(family, n, seed=7):
    """Mixed lengths, some past the chunked families' prefill_chunk=8
    so fused admission engages; n > n_slots so completions must
    refill slots mid-run (the pipeline's admission bubble seam)."""
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(0, vocab_of(family),
                                          5 + 4 * (i % 3))]
            for i in range(n)]


def drive(engine, prompts, max_tokens=6, limit=3000, tenant=None):
    """Run an UNSTARTED engine synchronously (no threads)."""
    reqs = [_Request(list(p), max_tokens, None,
                     **({"tenant": tenant} if tenant else {}))
            for p in prompts]
    for r in reqs:
        assert engine.submit(r)
    for _ in range(limit):
        if all(r.done.is_set() for r in reqs):
            break
        engine._loop_once()
    assert all(r.done.is_set() for r in reqs), "engine stalled"
    return reqs


# ---------------------------------------------------------------------------
# Bit-exactness: overlapped == serial, every family shape
# ---------------------------------------------------------------------------

class TestOverlapBitExact:
    @pytest.mark.parametrize("family", FAMILIES)
    def test_overlap_matches_serial(self, family):
        prompts = prompts_for(family, 4)
        tenant = "acme" if family == "dense-kvq" else None
        want = drive(make_engine(family, overlap=False), prompts,
                     tenant=tenant)
        assert all(r.error is None for r in want), \
            [r.error for r in want]
        eng = make_engine(family, overlap=True)
        got = drive(eng, prompts, tenant=tenant)
        assert all(r.error is None for r in got), [r.error for r in got]
        assert [list(r.tokens) for r in got] \
            == [list(r.tokens) for r in want]
        st = eng.stats()
        assert st["overlap_enabled"] is True
        assert st["forwards_per_tick"] == 1.0
        assert st["fetches_per_tick"] is not None
        if family == "paged-spec":
            # The overlap must not cost acceptance: speculation still
            # lands more tokens than steps.
            assert st["tokens_out"] > st["steps"]

    def test_fused_admission_matches_under_overlap(self):
        """Chunked prompts long enough that fused chunk+decode ticks
        happen while the pipeline is primed."""
        rng = np.random.default_rng(11)
        prompts = [[int(t) for t in rng.integers(0, TF_CFG.vocab_size,
                                                 n)]
                   for n in (6, 27, 19)]
        want = drive(make_engine("paged", overlap=False), prompts)
        eng = make_engine("paged", overlap=True)
        got = drive(eng, prompts)
        assert [list(r.tokens) for r in got] \
            == [list(r.tokens) for r in want]
        st = eng.stats()
        assert st["chunked_admits"] >= 1
        assert st["forwards_per_tick"] == 1.0


# ---------------------------------------------------------------------------
# The deferred fetch: <= 1/tick, one tick late, none in the pick
# ---------------------------------------------------------------------------

class TestDeferredFetch:
    def _warm(self, eng, prompts, ticks=5):
        reqs = [_Request(list(p), 24, None) for p in prompts]
        for r in reqs:
            assert eng.submit(r)
        for _ in range(ticks):                  # admit + warm/compile
            eng._loop_once()
        return reqs

    def test_one_fetch_per_tick_and_one_tick_late(self, monkeypatch):
        """With the pipeline primed a pass launches the NEXT tick
        before it fetches the oldest owed one, fetches exactly that
        one, and never owes more than two."""
        from tpushare.models import paged
        eng = make_engine("dense", overlap=True)
        self._warm(eng, prompts_for("dense", 2))
        # Pipeline primed: one dispatch is owed BETWEEN ticks.
        assert len(eng._pending_ticks) == 1
        events = []
        step_async, fetch = eng.srv.step_async, paged.addressable_fetch

        def launching(*a, **kw):
            events.append(("launch", len(eng._pending_ticks)))
            return step_async(*a, **kw)

        def fetching(x):
            # the tick being fetched has left the queue
            events.append(("fetch", len(eng._pending_ticks) + 1))
            return fetch(x)

        eng.srv.step_async = launching
        monkeypatch.setattr(paged, "addressable_fetch", fetching)
        counts = []
        ahead0 = eng.stats()["ahead_ticks"]
        with count_transfers(counts):
            for _ in range(5):
                counts.append(0)
                del events[:]
                before = eng._pending_ticks[0].tick_id
                f0 = eng.srv.device_fetches
                eng._loop_once()
                # Launched N+1 with N owed, THEN fetched N (two owed
                # at that moment, never more), and N+1 is what is left.
                assert events == [("launch", 1), ("fetch", 2)], events
                assert eng.srv.device_fetches == f0 + 1
                assert [p.tick_id for p in eng._pending_ticks] \
                    == [before + 1]
                assert eng._pending_ticks[0].ahead
        assert counts == [1] * 5, counts
        st = eng.stats()
        assert st["ahead_ticks"] == ahead0 + 5
        assert st["fetches_per_tick"] is not None
        assert st["fetches_per_tick"] <= 1.0
        assert st["forwards_per_tick"] == 1.0

    def test_pick_stage_makes_zero_transfers(self):
        eng = make_engine("dense-kvq", overlap=True)
        self._warm(eng, prompts_for("dense-kvq", 2))
        counts = [0]
        with count_transfers(counts):
            eng._plan_next_pick()
        assert counts[-1] == 0, counts

    def test_drain_leaves_no_pending_tick(self):
        """Two streams that end together by count: the engine sees on
        the host that every in-flight token is a last one and does not
        run ahead, so a draining engine runs no wasted program."""
        serial = make_engine("dense", overlap=False)
        drive(serial, prompts_for("dense", 2))
        eng = make_engine("dense", overlap=True)
        drive(eng, prompts_for("dense", 2))
        for _ in range(50):
            if not eng._pending_ticks:
                break
            eng._loop_once()
        assert not eng._pending_ticks
        st = eng.stats()
        assert st["ahead_ticks"] > 0
        assert st["ahead_dropped_tokens"] == 0
        assert st["model_forwards"] == serial.stats()["model_forwards"]


# ---------------------------------------------------------------------------
# Running ahead: what a tick dispatched before the fetch may carry
# ---------------------------------------------------------------------------

def _engine_of(server, *, overlap):
    """The three slot servers behind the engine (the sparse family
    shares the dense one's): two slots, so a third request recycles."""
    kw = dict(overlap_tick=overlap, idle_sleep_s=0.0, chaos_spec="",
              n_slots=2)
    if server == "dense":
        return ServeEngine(TF_PARAMS, TF_CFG, n_blocks=48, block_size=8,
                           **kw), TF_CFG.vocab_size
    if server == "latent":
        from tpushare.models import latent
        cfg = latent.tiny()
        return ServeEngine(latent.init_params(jax.random.PRNGKey(3), cfg),
                           cfg, model_family="latent", n_blocks=64,
                           block_size=16, max_blocks_per_slot=8,
                           **kw), cfg.vocab_size
    if server == "retention":
        from tpushare.models import retention
        cfg = retention.tiny()
        return ServeEngine(retention.init_params(jax.random.PRNGKey(1),
                                                 cfg),
                           cfg, model_family="retention", n_blocks=64,
                           block_size=4, max_blocks_per_slot=16,
                           **kw), cfg.vocab_size
    raise AssertionError(server)


def _run(eng, prompts, max_tokens, eos=None, limit=3000):
    reqs = [_Request(list(p), m, eos) for p, m in zip(prompts, max_tokens)]
    for r in reqs:
        assert eng.submit(r)
    for _ in range(limit):
        if all(r.done.is_set() for r in reqs) and not eng._pending_ticks:
            break
        eng._loop_once()
    assert all(r.done.is_set() for r in reqs), "engine stalled"
    assert all(r.error is None for r in reqs), [r.error for r in reqs]
    return reqs


class TestRunningAhead:
    @pytest.mark.parametrize("ending", ("eos", "max_tokens"))
    @pytest.mark.parametrize("server", ("dense", "latent", "retention"))
    def test_an_ended_stream_emits_nothing_from_the_tick_ahead(
            self, server, ending):
        """A stream that ends at tick N has a row in N+1, launched
        before N's token was seen. That row is emitted to nobody: the
        stream stops where the serial engine stops it, and the request
        that takes over its slot reads the serial engine's tokens."""
        eng, vocab = _engine_of(server, overlap=True)
        rng = np.random.default_rng(5)
        prompts = [[int(t) for t in rng.integers(0, vocab, n)]
                   for n in (6, 9, 7, 5)]
        caps = [9, 4, 7, 6]             # streams end at different ticks
        eos = cut = None
        if ending == "eos":
            # a token the serial engine first emits mid-stream ends
            # that stream (and any other that meets it) early
            free, _ = _engine_of(server, overlap=False)
            streams = [r.tokens for r in _run(free, prompts, [9] * 4)]
            cut = next((i, j) for i, toks in enumerate(streams[:3])
                       for j in range(2, 7) if toks[j] not in toks[:j])
            eos = streams[cut[0]][cut[1]]
            caps = [9] * 4
        serial, _ = _engine_of(server, overlap=False)
        want = _run(serial, prompts, caps, eos)
        got = _run(eng, prompts, caps, eos)
        assert [list(r.tokens) for r in got] \
            == [list(r.tokens) for r in want]
        if ending == "eos":
            assert len(got[cut[0]].tokens) == cut[1] + 1
            assert got[cut[0]].tokens[-1] == eos
        st, ref = eng.stats(), serial.stats()
        # the mechanism engaged: rows were computed ahead for streams
        # that had ended, one a stream at most, and none was counted
        assert st["ahead_ticks"] > 0
        assert 1 <= st["ahead_dropped_tokens"] <= len(prompts)
        assert st["tokens_out"] == ref["tokens_out"]
        assert st["completed"] == ref["completed"] == len(prompts)
        assert st["fetches_per_tick"] <= 1.0
        assert st["forwards_per_tick"] == 1.0
        # nothing leaked with the recycled slots
        assert not eng._active and not eng._admitting
        assert not eng.srv.active.any()

    @pytest.mark.parametrize("n_slots", (1, 2))
    def test_a_row_retired_at_capacity_ends_its_own_stream(self, n_slots):
        """A stream that reaches its slot's capacity is retired at
        dispatch with its last token still in flight, and the drain
        may hand the slot on before that token comes home: the token
        ends the retired stream (where the serial engine ends it) and
        never reaches the slot's next tenant."""
        def run(overlap):
            eng = ServeEngine(TF_PARAMS, TF_CFG, n_slots=n_slots,
                              n_blocks=48, block_size=8,
                              max_blocks_per_slot=2, overlap_tick=overlap,
                              idle_sleep_s=0.0, chaos_spec="")
            rng = np.random.default_rng(5)
            prompts = [[int(t) for t in
                        rng.integers(0, TF_CFG.vocab_size, n)]
                       for n in (5, 7, 6, 4)]
            return eng, _run(eng, prompts, [24] * 4)
        _, want = run(False)
        eng, got = run(True)
        # every stream stops at the slot's 16 positions, not at 24
        assert [len(r.prompt) + len(r.tokens) for r in want] == [17] * 4
        assert [list(r.tokens) for r in got] \
            == [list(r.tokens) for r in want]
        assert eng.stats()["ahead_ticks"] > 0

    def test_an_arrival_during_the_dispatch_is_admitted_before_the_fetch(
            self):
        """The fetch blocks for the rest of a device program (a fused
        tick's may be long): a request that arrives while N+1 is being
        dispatched is admitted ahead of that wait, as the fetch-first
        order admitted it at the top of its pass, and not a tick
        later."""
        eng = make_engine("dense", overlap=True)
        first, late = [_Request(list(p), 24, None)
                       for p in prompts_for("dense", 2)]
        assert eng.submit(first)
        for _ in range(5):
            eng._loop_once()
        assert len(eng._pending_ticks) == 1 and eng._pending_ticks[0].ahead
        step_async, events = eng.srv.step_async, []

        def launching(*a, **kw):
            assert eng.submit(late)             # arrives mid-dispatch
            eng.srv.step_async = step_async
            return step_async(*a, **kw)

        finalize = eng._finalize_pending

        def finalizing():
            events.append(late in eng._active.values())
            return finalize()

        eng.srv.step_async = launching
        eng._finalize_pending = finalizing
        eng._loop_once()
        assert events == [True]         # placed before the fetch began
        assert len(late.tokens) == 1    # its first token is out
        for _ in range(200):
            eng._loop_once()
        want = drive(make_engine("dense", overlap=False),
                     prompts_for("dense", 2), max_tokens=24)
        assert [first.tokens, late.tokens] == [r.tokens for r in want]

    def test_a_fused_admission_owns_its_first_decode_token(self):
        """The slot server activates an admission at the dispatch of
        its last fused chunk; the engine moves the request to _active
        only when that tick is applied. The tick dispatched in between
        carries the request, or its first decode token has no owner."""
        rng = np.random.default_rng(11)
        prompts = [[int(t) for t in rng.integers(0, TF_CFG.vocab_size, n)]
                   for n in (6, 27, 19)]
        want = _run(make_engine("paged", overlap=False), prompts, [8] * 3)
        eng = make_engine("paged", overlap=True)
        reqs = [_Request(list(p), 8, None) for p in prompts]
        for r in reqs:
            assert eng.submit(r)
        carried = 0
        for _ in range(3000):
            if all(r.done.is_set() for r in reqs):
                break
            owed = list(eng._pending_ticks)
            eng._loop_once()
            if owed and owed[-1].landed is not None and eng._pending_ticks:
                nxt = eng._pending_ticks[-1]
                if nxt.ahead and nxt is not owed[-1]:
                    slot = owed[-1].landed
                    assert nxt.slot_reqs[slot] is owed[-1].slot_reqs[slot]
                    assert nxt.work != slot
                    carried += 1
        assert carried >= 1, "no tick ran ahead of a completed admission"
        assert [list(r.tokens) for r in reqs] \
            == [list(r.tokens) for r in want]
        st = eng.stats()
        assert st["chunked_admits"] >= 1
        assert st["forwards_per_tick"] == 1.0

    def test_nan_at_the_owed_tick_never_reaches_the_replayed_stream(self):
        """A NaN token at tick N with N+1 in flight: the NaN failure
        domain stays ONE slot (its neighbour never replays), the
        poisoned stream's row in N+1 is dropped although the SAME
        request object replays into the SAME slot before N+1 comes
        home (the placement stamp decides, not the object), and the
        replay is token-exact."""
        prompts = prompts_for("dense", 2)
        want = drive(make_engine("dense", overlap=False), prompts,
                     max_tokens=10)
        eng = make_engine("dense", overlap=True)
        state = {"fired": None}

        def fire(value=None):
            if (state["fired"] is None and isinstance(value, dict)
                    and len(value) == 2 and eng._pending_ticks):
                slot = sorted(value)[0]
                state["fired"] = (slot, eng._active[slot])
                out = dict(value)
                out[slot] = float("nan")
                return out
            return None

        eng._fault_token_fetch = fire
        reqs = [_Request(list(p), 10, None) for p in prompts]
        for r in reqs:
            assert eng.submit(r)
        flushes0 = eng._pipeline_flushes
        readmitted = []
        finalize = eng._finalize_pending

        def finalizing():
            if state["fired"] and eng._pending_ticks:
                slot, req = state["fired"]
                oldest = eng._pending_ticks[0]
                # back in its slot when the tick that ran ahead of the
                # NaN comes home: same object, another placement
                readmitted.append(eng._active.get(slot) is req
                                  and oldest.slot_reqs.get(slot) is req
                                  and not oldest.carries(slot, req))
            return finalize()

        eng._finalize_pending = finalizing
        for _ in range(3000):
            if all(r.done.is_set() for r in reqs):
                break
            eng._loop_once()
        readmitted = any(readmitted)
        assert state["fired"] and readmitted
        assert [list(r.tokens) for r in reqs] \
            == [list(r.tokens) for r in want]
        st = eng.stats()
        assert st["quarantines"] == 1 and st["replays"] == 1
        assert st["ahead_dropped_tokens"] >= 1
        assert eng._pipeline_flushes == flushes0    # nobody else's lost

    def test_no_transfer_between_the_launch_and_the_end_of_dispatch(self):
        """Read off a CPU profiler session (tests/launch_trace.py): a
        primed pass launches one program with nothing ahead of it,
        nothing comes to the host between that launch and the end of
        the dispatch stage, and the pass's one fetch span follows it.
        The control: the fetch-first order (an engine that may not run
        ahead) shows its span BEFORE the launch, and a transfer put
        into the dispatch stage is seen."""
        from tests.launch_trace import Session
        eng = make_engine("dense", overlap=True)
        reqs = [_Request(list(p), 40, None)
                for p in prompts_for("dense", 2)]
        for r in reqs:
            assert eng.submit(r)
        for _ in range(8):                      # admit, compile, prime
            eng._loop_once()
        with Session() as ticks:
            with ticks.tick("ahead"):
                eng._loop_once()
            eng._runs_ahead = lambda: False
            with ticks.tick("fetch-first"):
                eng._loop_once()
            del eng._runs_ahead
            # a tap on the sampler reads the logits back inside the
            # dispatch stage, after the launch
            pick = eng.srv._sampler.pick
            eng.srv._sampler.pick = lambda lg: (int(lg[0, 0]), pick(lg))[1]
            with ticks.tick("stray"):
                eng._loop_once()
        assert ticks["ahead"] == {
            "programs": ["paged_decode"], "uploads": 0, "arguments": 0,
            "fetches_in_dispatch": 0, "fetch_spans": [0, 1]}
        assert ticks["fetch-first"]["fetch_spans"] == [1, 0]
        assert ticks["fetch-first"]["fetches_in_dispatch"] == 0
        assert ticks["stray"]["fetches_in_dispatch"] >= 1

    @pytest.mark.parametrize("family", ("paged-spec", "paged-moe-spec"))
    def test_a_speculative_engine_holds_one_tick(self, family):
        """The accepted counts decide the next tick's lengths, so the
        host cannot dispatch before the fetch: depth stays one."""
        eng = make_engine(family, overlap=True)
        assert eng.srv.speculative
        owed = []
        step_async = eng.srv.step_async

        def launching(*a, **kw):
            owed.append(len(eng._pending_ticks))
            return step_async(*a, **kw)

        eng.srv.step_async = launching
        drive(eng, prompts_for(family, 3))
        assert owed and set(owed) == {0}
        st = eng.stats()
        assert st["ahead_ticks"] == 0
        assert st["ahead_dropped_tokens"] == 0


# ---------------------------------------------------------------------------
# Fault domains under overlap
# ---------------------------------------------------------------------------

class TestOverlapFaultDomains:
    def test_forward_fault_quarantines_dispatched_tick_only(self):
        """A forward:raise at the overlapped dispatch quarantines the
        slots of the tick being DISPATCHED — the next tick's picked
        (but uncommitted) admission stays queued and serves clean.
        Streams stay token-exact vs the fault-free serial oracle."""
        prompts = prompts_for("dense", 3)       # 3 reqs > 2 slots:
        want = drive(make_engine("dense", overlap=False), prompts)

        eng = make_engine("dense", overlap=True)
        reqs = [_Request(list(p), 6, None) for p in prompts]
        for r in reqs:
            assert eng.submit(r)
        for _ in range(4):
            eng._loop_once()
        assert not all(r.done.is_set() for r in reqs)
        state = {"left": 1, "active_at_fault": None}

        def fire(value=None):
            if state["left"] > 0:
                state["left"] -= 1
                state["active_at_fault"] = len(eng._active)
                raise InjectedXlaRuntimeError("INTERNAL: injected")
            return None

        eng._fault_forward = fire
        for _ in range(3000):
            if all(r.done.is_set() for r in reqs):
                break
            eng._loop_once()
        assert state["left"] == 0, "fault never fired"
        assert all(r.error is None for r in reqs), \
            [r.error for r in reqs]
        assert [list(r.tokens) for r in reqs] \
            == [list(r.tokens) for r in want]
        st = eng.stats()
        # Quarantine scope == the dispatched batch, nothing more: only
        # the requests in flight at the fault replayed; the queued
        # request never entered the blast radius.
        assert st["replays"] == state["active_at_fault"]
        assert st["quarantines"] == state["active_at_fault"]

    def test_finalize_fault_replays_token_exact(self):
        """A device fault surfacing at the DEFERRED fetch (tick N's
        death observed with tick N+1 already in flight) flushes N+1
        and still replays everything token-exact."""
        prompts = prompts_for("dense", 2)
        want = drive(make_engine("dense", overlap=False), prompts)

        eng = make_engine("dense", overlap=True)
        reqs = [_Request(list(p), 6, None) for p in prompts]
        for r in reqs:
            assert eng.submit(r)
        for _ in range(4):
            eng._loop_once()
        (pend,) = eng._pending_ticks
        flushes0 = eng._pipeline_flushes

        class Boom:
            def finalize(self, invalid=frozenset()):
                raise InjectedXlaRuntimeError("INTERNAL: finalize")

        pend.step = Boom()
        eng._loop_once()
        # N+1 went out ahead of the fetch that surfaced N's death: it
        # is abandoned unfetched (and counted), never trusted.
        assert not eng._pending_ticks
        assert eng._pipeline_flushes == flushes0 + 1
        assert eng.stats()["ahead_ticks"] >= 1
        for _ in range(3000):
            if all(r.done.is_set() for r in reqs):
                break
            eng._loop_once()
        assert all(r.error is None for r in reqs), \
            [r.error for r in reqs]
        assert [list(r.tokens) for r in reqs] \
            == [list(r.tokens) for r in want]
        assert eng.stats()["quarantines"] >= 1

    def test_quarantine_flushes_primed_pipeline(self):
        """_quarantine_inflight drops the in-flight dispatch unfetched
        (and counts it): at a fault, 'in flight' means exactly the
        dispatched tick's slot set."""
        eng = make_engine("dense", overlap=True)
        reqs = [_Request(list(p), 8, None)
                for p in prompts_for("dense", 2)]
        for r in reqs:
            assert eng.submit(r)
        for _ in range(4):
            eng._loop_once()
        assert len(eng._pending_ticks) == 1
        flushes0 = eng._pipeline_flushes
        eng._quarantine_inflight("test: fault with pipeline primed")
        assert not eng._pending_ticks
        assert eng._pipeline_flushes == flushes0 + 1
        for _ in range(3000):
            if all(r.done.is_set() for r in reqs):
                break
            eng._loop_once()
        assert all(r.error is None for r in reqs)


# ---------------------------------------------------------------------------
# /stats + CLI contract
# ---------------------------------------------------------------------------

class TestOverlapStats:
    def test_serial_mode_reports_null_not_zero(self):
        eng = make_engine("dense", overlap=False)
        drive(eng, prompts_for("dense", 1))
        st = eng.stats()
        assert st["overlap_enabled"] is False
        assert st["pipeline_flushes"] is None
        assert st["host_gap_ms"] is None

    def test_overlap_mode_reports_gap_percentiles(self):
        eng = make_engine("dense", overlap=True)
        drive(eng, prompts_for("dense", 2))
        st = eng.stats()
        assert st["overlap_enabled"] is True
        assert isinstance(st["pipeline_flushes"], int)
        gap = st["host_gap_ms"]
        assert set(gap) == {"p50", "p99"}
        assert gap["p50"] is not None and gap["p50"] >= 0.0
        assert gap["p99"] >= gap["p50"]

    def test_cli_flag_defaults_on(self):
        parser = serve_mod.build_parser()
        assert parser.parse_args([]).overlap_tick == "on"
        assert parser.parse_args(
            ["--overlap-tick", "off"]).overlap_tick == "off"
