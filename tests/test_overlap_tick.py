"""Overlapped tick pipeline (ISSUE 17).

The engine's two-stage tick hides host scheduling, journal fsync, and
bookkeeping behind the in-flight dispatch: tick N's device step is
finalized (the ONE fetch) at the top of tick N+1, while tick N+1's
pick was precomputed inside tick N's device window. These tests pin
the contract:

* bit-exactness — the overlapped engine serves byte-identical token
  streams to the serial engine across every family shape (dense rows,
  KV-quota'd dense, chunked/fused paged, speculative, paged MoE,
  MoE rows);
* the deferred fetch — at most one device->host transfer per tick,
  the fetch lands one tick AFTER its dispatch, and the overlap-window
  pick makes ZERO transfers;
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

    def test_one_fetch_per_tick_and_one_tick_late(self):
        eng = make_engine("dense", overlap=True)
        self._warm(eng, prompts_for("dense", 2))
        # Pipeline primed: a dispatch is in flight BETWEEN ticks.
        assert eng._pending_tick is not None
        counts = []
        with count_transfers(counts):
            for _ in range(5):
                counts.append(0)
                before = eng._pending_tick.tick_id
                f0 = eng.srv.device_fetches
                eng._loop_once()
                # The tick fetched exactly the PREVIOUS dispatch and
                # launched the next one: fetch rides one tick late.
                assert eng.srv.device_fetches == f0 + 1
                assert eng._pending_tick.tick_id == before + 1
        assert all(c <= 1 for c in counts), counts
        assert any(c == 1 for c in counts), counts
        st = eng.stats()
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
        eng = make_engine("dense", overlap=True)
        drive(eng, prompts_for("dense", 2))
        for _ in range(50):
            if eng._pending_tick is None:
                break
            eng._loop_once()
        assert eng._pending_tick is None


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
        death observed at tick N+1) still replays everything in the
        pending tick token-exact."""
        prompts = prompts_for("dense", 2)
        want = drive(make_engine("dense", overlap=False), prompts)

        eng = make_engine("dense", overlap=True)
        reqs = [_Request(list(p), 6, None) for p in prompts]
        for r in reqs:
            assert eng.submit(r)
        for _ in range(4):
            eng._loop_once()
        pend = eng._pending_tick
        assert pend is not None

        class Boom:
            def finalize(self, invalid=frozenset()):
                raise InjectedXlaRuntimeError("INTERNAL: finalize")

        pend.step = Boom()
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
        assert eng._pending_tick is not None
        flushes0 = eng._pipeline_flushes
        eng._quarantine_inflight("test: fault with pipeline primed")
        assert eng._pending_tick is None
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
