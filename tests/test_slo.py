"""Multi-tenant SLO serving (ISSUE 9): tiers, deadline-aware tick
scheduling, per-tenant KV quotas, per-tier /stats — policy units plus
the engine/router integration and the analysis-sweep pins.
"""

import os
import sys
import threading
import time

import jax
import numpy as np
import pytest

from tpushare.cli.serve import ServeEngine, _Request
from tpushare.models import transformer as tf
from tpushare.models.paged import (PagedSlotServer, PoolExhausted,
                                   QuotaExceeded)
from tpushare.slo import (KvQuota, TenantQuotaSpec, TickScheduler,
                          TierSpec, TierStats, choose_victim,
                          parse_quota_spec, parse_tier, tier_rank)
from tpushare.slo.tiers import SHED_ORDER, TIER_ORDER, TIERS

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CFG = tf.tiny(remat=False)
PARAMS = tf.init_params(jax.random.PRNGKey(0), CFG)


def prompts(n, length=6, seed=3):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(0, CFG.vocab_size, length)]
            for _ in range(n)]


def make_engine(**kw):
    kw.setdefault("idle_sleep_s", 0.001)
    kw.setdefault("chaos_spec", "")
    kw.setdefault("n_slots", 2)
    kw.setdefault("n_blocks", 48)
    kw.setdefault("block_size", 8)
    return ServeEngine(PARAMS, CFG, **kw)


def drive(engine, reqs, limit=3000):
    for r in reqs:
        assert engine.submit(r)
    for _ in range(limit):
        if all(r.done.is_set() for r in reqs):
            break
        engine._loop_once()
    assert all(r.done.is_set() for r in reqs), "engine stalled"
    return reqs


class _Stub:
    """Scheduler duck-contract stub (tier/seq/t_submit/tokens)."""

    def __init__(self, tier, seq=0, t_submit=0.0, tokens=()):
        self.tier = tier
        self.seq = seq
        self.t_submit = t_submit
        self.tokens = list(tokens)


# ---------------------------------------------------------------------------
# Tier model
# ---------------------------------------------------------------------------

class TestTiers:
    def test_table_shape(self):
        assert TIER_ORDER == ("interactive", "standard", "batch")
        assert SHED_ORDER == tuple(reversed(TIER_ORDER))
        ranks = [TIERS[n].rank for n in TIER_ORDER]
        assert ranks == sorted(ranks)
        # batch is best-effort by construction: no deadline to breach
        assert TIERS["batch"].ttft_deadline_ms is None

    def test_parse_tier(self):
        assert parse_tier(None, "standard") == "standard"
        assert parse_tier("batch") == "batch"
        with pytest.raises(ValueError):
            parse_tier("interactve")    # typos 400, never downgrade
        with pytest.raises(ValueError):
            parse_tier(3)


# ---------------------------------------------------------------------------
# TickScheduler
# ---------------------------------------------------------------------------

class TestScheduler:
    def test_weighted_fair_pop_proportions(self):
        sched = TickScheduler(now_fn=lambda: 0.0)
        for i in range(8):
            sched.push(_Stub("interactive", seq=i))
            sched.push(_Stub("standard", seq=i))
            sched.push(_Stub("batch", seq=i))
        first7 = [sched.pop().tier for _ in range(7)]
        # one full rotation at weights 4/2/1 — batch FLOWS at its
        # share instead of starving behind the latency tiers
        assert first7.count("interactive") == 4
        assert first7.count("standard") == 2
        assert first7.count("batch") == 1

    def test_at_risk_overrides_rotation(self):
        # A tier table where the rotation would all but ignore
        # interactive — the strict-priority override must still win
        # the moment its TTFT deadline is at risk.
        specs = {
            "interactive": TierSpec("interactive", 0, 1, 500.0, None),
            "batch": TierSpec("batch", 2, 100, None, None),
        }
        clock = [0.0]
        sched = TickScheduler(specs, default_tier="batch",
                              now_fn=lambda: clock[0])
        sched.push(_Stub("interactive", t_submit=0.0))
        for i in range(5):
            sched.push(_Stub("batch", seq=i))
        clock[0] = 0.3              # 300ms >= 0.5 * 500ms TTFT budget
        assert sched.pop().tier == "interactive"

    def test_push_front_keeps_place_within_tier(self):
        sched = TickScheduler(now_fn=lambda: 0.0)
        a, b, c = (_Stub("batch", seq=i) for i in range(3))
        sched.push(a)
        sched.push(b)
        sched.push_front(c)         # a preempted victim resumes first
        assert sched.pop() is c
        assert sched.pop() is a

    def test_backlog_and_drain(self):
        sched = TickScheduler(now_fn=lambda: 0.0)
        sched.push(_Stub("batch"))
        sched.push(_Stub("interactive"))
        assert sched.backlog() == 2
        assert sched.backlog_by_tier()["batch"] == 1
        drained = sched.drain()
        assert [r.tier for r in drained] == ["interactive", "batch"]
        assert sched.backlog() == 0

    def test_pick_admission_prefers_at_risk_interactive(self):
        clock = [0.0]
        sched = TickScheduler(now_fn=lambda: clock[0])
        admitting = {0: _Stub("batch", seq=1),
                     3: _Stub("interactive", seq=2, t_submit=0.0)}
        clock[0] = 0.4
        assert sched.pick_admission(admitting) == 3
        # within one tier: oldest admission first
        sched2 = TickScheduler(now_fn=lambda: 0.0)
        assert sched2.pick_admission(
            {5: _Stub("batch", seq=9), 1: _Stub("batch", seq=2)}) == 1

    def test_alternation_tier_ladder(self):
        clock = [0.0]
        sched = TickScheduler(now_fn=lambda: clock[0])
        active = {0: _Stub("interactive", tokens=[1])}
        # batch admission never steals a budget-starved tick from
        # higher-tier decode rows
        assert sched.alternation(_Stub("batch"), active) == "decode"
        # an at-risk interactive admission claims the tick from
        # lower-tier decode rows
        clock[0] = 0.4
        assert sched.alternation(
            _Stub("interactive", t_submit=0.0),
            {0: _Stub("batch", tokens=[1])}) == "admit"
        # equal tiers keep the engine's fair alternation (None) — a
        # single-tier deployment behaves exactly as before tiering
        assert sched.alternation(
            _Stub("batch"), {0: _Stub("batch", tokens=[1])}) is None
        assert sched.alternation(_Stub("batch"), {}) == "admit"

    def test_choose_victim(self):
        active = {0: _Stub("interactive", seq=9),
                  1: _Stub("batch", seq=1),
                  2: _Stub("batch", seq=5),
                  3: _Stub("standard", seq=7)}
        # lowest tier first, newest within it
        assert choose_victim(active) == 2
        # preempt-low-for-high: strictly below the incoming rank only
        assert choose_victim(active,
                             below_rank=tier_rank("standard")) == 2
        assert choose_victim(
            {0: _Stub("interactive", seq=1)},
            below_rank=tier_rank("interactive")) is None


# ---------------------------------------------------------------------------
# KvQuota
# ---------------------------------------------------------------------------

class TestKvQuota:
    def test_parse_quota_spec(self):
        q = parse_quota_spec("acme=16:64, bg =0:32,burst=8:")
        assert q["acme"] == TenantQuotaSpec(16, 64)
        assert q["bg"] == TenantQuotaSpec(0, 32)
        assert q["burst"] == TenantQuotaSpec(8, None)
        with pytest.raises(ValueError):
            parse_quota_spec("acme=64:16")      # ceiling < reserve
        with pytest.raises(ValueError):
            parse_quota_spec("acme=banana")

    def test_ceiling_and_reserve_verdicts(self):
        q = KvQuota({"a": TenantQuotaSpec(0, 4),
                     "b": TenantQuotaSpec(6, None)})
        kind, _ = q.admit_verdict("a", 5, allocatable=100)
        assert kind == "ceiling"
        assert q.admit_verdict("a", 4, allocatable=100) is None
        q.charge("a", 4)
        assert q.admit_verdict("a", 1, allocatable=100)[0] == "ceiling"
        # b's untouched floor of 6 blocks anyone else's deep dig
        assert q.admit_verdict("a", 0, allocatable=5)[0] == "reserve"
        assert q.admit_verdict("a", 0, allocatable=6) is None
        assert q.admit_verdict("c", 5, allocatable=10)[0] == "reserve"
        q.charge("b", 6)                # floor met: headroom drops to 0
        assert q.admit_verdict("c", 4, allocatable=4) is None

    def test_attainable_and_over_floor(self):
        q = KvQuota({"b": TenantQuotaSpec(14, None)})
        # even an idle pool owes b its full 14-block floor
        assert q.attainable_blocks("a", 16) == 2
        assert q.attainable_blocks("b", 16) == 16
        # over_floor: the only victims worth preempting for a
        # reserve hold (freeing an under-floor tenant's blocks grows
        # its unmet floor by the freed amount — zero net headroom)
        q.charge("b", 6)
        assert q.over_floor("b") is False        # 6 < floor 14
        q.charge("b", 9)
        assert q.over_floor("b") is True
        q.charge("d", 1)                         # unquota'd: floor 0
        assert q.over_floor("d") is True

    def test_charge_refund_snapshot(self):
        q = KvQuota({"a": TenantQuotaSpec(2, 8)})
        q.charge("a", 3)
        q.charge("x", 1)
        assert q.over_ceiling("a") is False
        q.charge("a", 6)
        assert q.over_ceiling("a") is True
        snap = q.snapshot()
        assert snap["a"] == {"used_blocks": 9, "reserve": 2,
                             "ceiling": 8, "host_bytes": None,
                             "host_bytes_used": 0}
        assert snap["x"]["ceiling"] is None
        q.refund("a", 9)
        q.refund("x", 1)
        assert q.used == {}

    def test_snapshot_safe_against_engine_thread_churn(self):
        # /stats runs snapshot() on an HTTP handler thread while the
        # engine thread charges/refunds — charge() inserts a tenant's
        # first key, refund() pops a zeroed one, so the ledger's key
        # membership churns under the reader. Pin the contract: no
        # RuntimeError and coherent rows under sustained churn.
        q = KvQuota({"a": TenantQuotaSpec(2, 8)})
        stop = threading.Event()
        errors = []

        def churn():
            i = 0
            while not stop.is_set():
                name = f"t{i % 97}"
                q.charge(name, 1)
                q.refund(name, 1)       # pops the key: membership churn
                i += 1

        t = threading.Thread(target=churn, daemon=True)
        t.start()
        try:
            for _ in range(3000):
                try:
                    snap = q.snapshot()
                except RuntimeError as e:    # pragma: no cover
                    errors.append(e)
                    break
                assert snap["a"]["reserve"] == 2
        finally:
            stop.set()
            t.join(timeout=5)
        assert not errors


# ---------------------------------------------------------------------------
# TierStats
# ---------------------------------------------------------------------------

class TestTierStats:
    def test_counters_percentiles_breaches(self):
        ts = TierStats()
        ts.bump("batch", "admitted")
        for ms in (100.0, 200.0, 700.0):
            ts.record_first_token("interactive", ms)
        ts.record_completion("interactive", 5, 400.0)   # 100ms/token
        snap = ts.snapshot()
        inter = snap["interactive"]
        # 700ms > the 500ms TTFT deadline: one breach
        assert inter["deadline_breaches"] == 1
        assert inter["completed"] == 1
        assert inter["ttft_p50_ms"] == 200.0
        assert inter["per_token_p50_ms"] == 100.0
        assert snap["batch"]["admitted"] == 1
        # batch has no deadline: nothing it does breaches
        ts.record_first_token("batch", 10 ** 6)
        assert ts.snapshot()["batch"]["deadline_breaches"] == 0


# ---------------------------------------------------------------------------
# Quota-aware paged pool (models/paged.py)
# ---------------------------------------------------------------------------

class TestPagedQuota:
    def mk(self, quota, **kw):
        kw.setdefault("n_slots", 2)
        kw.setdefault("n_blocks", 17)
        kw.setdefault("block_size", 4)
        return PagedSlotServer(PARAMS, CFG, kv_quota=quota, **kw)

    def test_ceiling_refused_and_rolled_back(self):
        q = KvQuota({"a": TenantQuotaSpec(0, 2)})
        srv = self.mk(q)
        free0 = len(srv.cache.free)
        prompt = jax.numpy.asarray(prompts(1, 12)[0])   # 4 blocks
        with pytest.raises(QuotaExceeded) as ei:
            srv.admit(prompt, tenant="a")
        assert ei.value.kind == "ceiling"
        assert ei.value.tenant == "a"
        assert isinstance(ei.value, PoolExhausted)  # engine compat
        # rollback is exact: nothing charged, nothing leaked
        assert q.used == {}
        assert len(srv.cache.free) == free0
        assert not srv.active.any()

    def test_reserve_floor_blocks_other_tenants(self):
        # 16 usable blocks; b reserves 14, so a may only take 2
        q = KvQuota({"b": TenantQuotaSpec(14, None)})
        srv = self.mk(q)
        prompt = jax.numpy.asarray(prompts(1, 12)[0])   # needs 4
        with pytest.raises(QuotaExceeded) as ei:
            srv.admit(prompt, tenant="a")
        assert ei.value.kind == "reserve"
        # b itself admits against its own floor
        slot = srv.admit(prompt, tenant="b")
        assert q.used["b"] == 4
        srv.evict(slot)
        assert q.used == {}

    def test_growth_charges_and_evict_refunds(self):
        q = KvQuota({"a": TenantQuotaSpec(0, None)})
        srv = self.mk(q)
        prompt = jax.numpy.asarray(prompts(1, 7)[0])    # 2 blocks (7+1)
        slot = srv.admit(prompt, tenant="a")
        assert q.used["a"] == 2
        for _ in range(6):                  # decode past the boundary
            srv.step()
        assert q.used["a"] >= 3             # growth charged
        srv.evict(slot)
        assert q.used == {}                 # exact refund

    def test_unquotad_server_unchanged(self):
        srv = self.mk(None)
        slot = srv.admit(jax.numpy.asarray(prompts(1, 6)[0]))
        out = srv.step()
        assert slot in out
        srv.evict(slot)


# ---------------------------------------------------------------------------
# Engine integration
# ---------------------------------------------------------------------------

class TestEngineTiers:
    def test_interactive_admits_before_queued_batch(self):
        eng = make_engine(n_slots=1)
        ps = prompts(3)
        reqs = [_Request(list(ps[0]), 4, None, tier="batch"),
                _Request(list(ps[1]), 4, None, tier="batch"),
                _Request(list(ps[2]), 4, None, tier="interactive")]
        drive(eng, reqs)
        assert all(r.error is None for r in reqs)
        # submitted LAST, admitted FIRST: the single slot served the
        # interactive request before either queued batch request
        assert reqs[2].t_first <= min(reqs[0].t_first, reqs[1].t_first)
        per = eng.stats()["per_tier"]
        assert per["interactive"]["admitted"] == 1
        assert per["batch"]["admitted"] == 2
        assert per["interactive"]["completed"] == 1

    def test_preempt_batch_for_interactive_on_full_slots(self):
        eng = make_engine(n_slots=2)
        ps = prompts(3, length=8, seed=11)
        batch = [_Request(list(p), 12, None, tier="batch")
                 for p in ps[:2]]
        for r in batch:
            assert eng.submit(r)
        for _ in range(4):              # both admitted, decoding
            eng._loop_once()
        assert eng.active_count() == 2
        inter = _Request(list(ps[2]), 4, None, tier="interactive")
        drive(eng, [inter] + batch)
        assert all(r.error is None for r in (inter, *batch))
        st = eng.stats()
        assert st["preempted"] >= 1
        per = st["per_tier"]
        # the victim was batch — interactive traffic is never the one
        # preempted for capacity while lower tiers hold slots
        assert per["batch"]["preempted"] >= 1
        assert per["interactive"]["preempted"] == 0
        assert per["interactive"]["quarantined"] == 0

    def test_equal_tier_never_self_preempts_on_full_slots(self):
        # Slots full of batch + ANOTHER batch arriving must wait, not
        # churn (preempt-low-for-high is strict)
        eng = make_engine(n_slots=1)
        ps = prompts(2, seed=17)
        reqs = [_Request(list(p), 4, None, tier="batch") for p in ps]
        drive(eng, reqs)
        assert eng.stats()["preempted"] == 0
        assert all(r.error is None for r in reqs)

    def test_quota_ceiling_answers_429_when_nothing_refundable(self):
        eng = make_engine(
            tenant_quotas={"t1": TenantQuotaSpec(0, 1)})
        r = _Request(prompts(1, 12)[0], 4, None, tenant="t1")
        drive(eng, [r])
        assert r.status == 429
        assert "ceiling" in r.error
        # the pool itself is untouched — another tenant admits fine
        r2 = _Request(prompts(1, 12, seed=5)[0], 4, None, tenant="t2")
        drive(eng, [r2])
        assert r2.error is None

    def test_infeasible_reserve_need_answers_429_not_livelock(self):
        """A fresh need beyond (usable pool - other tenants' full
        floors) can NEVER be satisfied — pre-fix the engine held it
        forever, and once at-risk its strict-priority head re-popped
        every tick, churned other tenants' slots with futile
        preemptions, and wedged all admissions."""
        eng = make_engine(
            n_blocks=17, block_size=4,       # 16 usable
            tenant_quotas={"b": TenantQuotaSpec(14, None)})
        # tenant a needs 4 fresh blocks; 16 - b's floor 14 = 2 < 4
        r = _Request(prompts(1, 12)[0], 4, None,
                     tier="interactive", tenant="a")
        drive(eng, [r])
        assert r.status == 429
        assert "permanent" in r.error
        # the engine is not wedged: b itself admits and completes
        r2 = _Request(prompts(1, 12, seed=5)[0], 4, None,
                      tier="standard", tenant="b")
        drive(eng, [r2])
        assert r2.error is None and len(r2.tokens) == 4

    def test_reserve_hold_never_preempts_under_floor_tenant(self):
        """Preemption for a reserve hold targets only victims whose
        eviction raises net headroom: an at-or-under-floor tenant's
        freed blocks grow its own unmet floor by the same amount —
        pre-fix choose_victim still churned the lowest tier (b's
        under-floor batch slots) tick after tick without ever curing
        the hold."""
        eng = make_engine(
            n_slots=4, n_blocks=17, block_size=4,    # 16 usable
            tenant_quotas={"b": TenantQuotaSpec(10, None)})
        ps = prompts(4, length=8, seed=23)
        # b: two batch streams, 3 blocks each = 6 used, UNDER its
        # 10-block floor. d (unquota'd, over its zero floor): one
        # standard stream of 5 blocks.
        b_reqs = [_Request(list(p), 4, None, tier="batch", tenant="b")
                  for p in ps[:2]]
        d_req = _Request(prompts(1, 16, seed=29)[0], 4, None,
                         tier="standard", tenant="d")
        for r in b_reqs + [d_req]:
            assert eng.submit(r)
        for _ in range(50):
            if eng.active_count() == 3:
                break
            eng._loop_once()
        assert eng.active_count() == 3
        # free = 16-6-5 = 5; a needs 2 fresh: post-admission
        # allocatable 5 - 2 = 3 < b's unmet floor 10-6 = 4 ->
        # reserve hold (feasible: 2 <= 16-10). The only victim that
        # cures it is d's standard slot; b's batch slots are lower
        # tier but under-floor.
        a_req = _Request(prompts(1, 7, seed=31)[0], 4, None,
                         tier="interactive", tenant="a")
        drive(eng, [a_req] + b_reqs + [d_req])
        assert all(r.error is None
                   for r in (a_req, d_req, *b_reqs))
        per = eng.stats()["per_tier"]
        assert per["standard"]["preempted"] >= 1      # d paid
        assert per["batch"]["preempted"] == 0         # b never churned
        assert per["interactive"]["preempted"] == 0

    def test_admit_failure_refund_unparks_tenant(self, monkeypatch):
        """The mid-admission failure handler refunds the tenant's
        blocks through its evictions — so it must unpark like every
        other refund path (completion, preemption, quarantine,
        cancelled reap): pre-fix, a tenant whose LAST in-flight work
        died during admission left its ceiling-parked requests in
        _quota_parked until shutdown."""
        eng = make_engine(
            tenant_quotas={"acme": TenantQuotaSpec(0, 4)})
        # Ceiling-parked earlier in its life (white-box: the park
        # list is the holding pen _unpark_tenant drains).
        held = _Request(prompts(1, 7)[0], 4, None,
                        tier="standard", tenant="acme")
        eng._quota_parked.append(held)
        doomed = _Request(prompts(1, 3, seed=43)[0], 4, None,
                          tier="interactive", tenant="acme")
        assert eng.submit(doomed)
        real_admit = eng.srv.admit

        def flaky(prompt, **kw):        # kills only doomed's shape
            if int(prompt.shape[0]) == 3:
                raise RuntimeError("injected mid-admission fault")
            return real_admit(prompt, **kw)

        monkeypatch.setattr(eng.srv, "admit", flaky)
        for _ in range(200):
            if doomed.done.is_set():
                break
            eng._loop_once()
        assert doomed.error is not None and doomed.status == 503
        # THE PIN: the failure path unparked acme — held is already
        # back in the rotation (no re-submit: it is the same request
        # object) and completes on the intact pool.
        assert eng.stats()["quota_parked"] == 0
        for _ in range(500):
            if held.done.is_set():
                break
            eng._loop_once()
        assert held.done.is_set(), "unparked request never admitted"
        assert held.error is None and len(held.tokens) == 4

    def test_stats_surface(self):
        eng = make_engine()
        r = _Request(prompts(1)[0], 3, None, tier="interactive",
                     tenant="acme")
        drive(eng, [r])
        st = eng.stats()
        assert st["default_tier"] == "standard"
        assert set(st["per_tier"]) == set(TIER_ORDER)
        row = st["per_tier"]["interactive"]
        for key in ("admitted", "completed", "preempted", "quarantined",
                    "deadline_breaches", "tokens", "ttft_p50_ms",
                    "ttft_p99_ms", "per_token_p50_ms",
                    "per_token_p99_ms"):
            assert key in row, key
        assert row["admitted"] == 1 and row["completed"] == 1
        assert row["tokens"] == 3
        assert row["ttft_p50_ms"] is not None
        assert st["queue_by_tier"] == {t: 0 for t in TIER_ORDER}
        # null-not-zero: an unquota'd engine reports no tenant ledger
        assert st["tenants"] is None
        q_eng = make_engine(
            tenant_quotas={"acme": TenantQuotaSpec(2, 32)})
        assert q_eng.stats()["tenants"]["acme"]["reserve"] == 2

    def test_moe_family_meters_a_tenant_quota(self):
        """The one slot server meters the sparse family's blocks like
        the dense one's: a prompt over the tenant's ceiling is a 429,
        one under it is served and charged."""
        from tpushare.models import moe
        cfg = moe.tiny(remat=False)
        params = moe.init_params(jax.random.PRNGKey(0), cfg)
        eng = ServeEngine(params, cfg, model_family="moe", n_slots=2,
                          n_blocks=16, block_size=4, idle_sleep_s=0.0,
                          tenant_quotas={"acme": TenantQuotaSpec(0, 2)})
        small = _Request([1, 2, 3], 2, None, tenant="acme")
        big = _Request(list(range(1, 14)), 2, None, tenant="acme")
        drive(eng, [small, big])
        assert small.error is None and len(small.tokens) == 2
        assert big.status == 429 and "KV-block ceiling" in big.error
        assert eng.stats()["tenants"]["acme"]["ceiling"] == 2

    def test_tier_http_contract(self):
        from tpushare.cli import serve as serve_mod
        import http.client, json as _json
        eng = make_engine()
        httpd = serve_mod.serve(eng, host="127.0.0.1", port=0,
                                timeout_s=60.0)
        port = httpd.server_address[1]

        def post(body):
            conn = http.client.HTTPConnection("127.0.0.1", port,
                                              timeout=30)
            conn.request("POST", "/v1/completions",
                         _json.dumps(body).encode(),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            out = _json.loads(resp.read() or b"{}")
            conn.close()
            return resp.status, out

        try:
            st, out = post({"prompt": prompts(1)[0], "max_tokens": 3,
                            "tier": "interactive", "tenant": "acme"})
            assert st == 200 and len(out["tokens"]) == 3
            st, out = post({"prompt": prompts(1)[0], "max_tokens": 3,
                            "tier": "platinum"})
            assert st == 400 and "tier" in out["error"]
            st, out = post({"prompt": prompts(1)[0], "max_tokens": 3,
                            "tenant": 7})
            assert st == 400
            assert eng.stats()["per_tier"]["interactive"][
                "admitted"] == 1
        finally:
            httpd.shutdown()
            eng.stop()


# ---------------------------------------------------------------------------
# Analysis sweep: tpushare/slo rides CC/RL/lock-order, and is clean
# ---------------------------------------------------------------------------

class TestAnalysisSweep:
    def test_slo_is_in_the_sweep_paths(self):
        from tpushare.analysis.rules.concurrency import CONCURRENCY_PATHS
        from tpushare.analysis.rules.interproc import (LOCK_ORDER_PATHS,
                                                       RESOURCE_PATHS)
        assert "tpushare/slo" in CONCURRENCY_PATHS
        assert "tpushare/slo" in RESOURCE_PATHS
        assert "tpushare/slo" in LOCK_ORDER_PATHS

    def test_tier_counter_fixture_yields_cc201(self):
        from tpushare.analysis import load_config
        from tpushare.analysis.engine import all_rules, analyze_file
        cfg = load_config(root=REPO)
        found = analyze_file(
            os.path.join(REPO, "tests", "fixtures", "analysis",
                         "cc201_tier_counters.py"),
            cfg, rules=[r for r in all_rules()
                        if r.id.startswith("CC")],
            respect_scope=False)
        assert {f.rule for f in found} == {"CC201"}
        msgs = " ".join(f.message for f in found)
        assert "_tier_breaches" in msgs and "_poll_loop" in msgs

    def test_real_slo_tree_pinned_clean(self):
        from tpushare.analysis import load_config
        from tpushare.analysis.engine import all_rules, analyze_paths
        cfg = load_config(root=REPO)
        rules = [r for r in all_rules()
                 if r.id.startswith(("CC", "RL"))]
        found = analyze_paths([os.path.join(REPO, "tpushare", "slo")],
                              cfg, rules=rules)
        assert found == [], [f.render() for f in found]
