"""Fault-injection harness + failure-domain recovery (ISSUE 4).

The chaos injector (tpushare/chaos) and the engine recovery it exists
to prove land together: seeded fault storms must leave every request
either token-exact vs a fault-free oracle or cleanly 503'd; NaN
quarantine is slot-scoped; tick failures replay the whole batch;
replays are bounded; the loop supervisor restarts a crashed engine
thread; the plugin's unhealthy transition drains a co-located daemon;
and with no spec armed every fault point is the shared no-op.
"""

import time

import jax
import numpy as np
import pytest

from tpushare import chaos
from tpushare.chaos import (NOOP, InjectedUnavailable,
                            InjectedXlaRuntimeError, Injector, parse_spec)
from tpushare.cli import serve as serve_mod
from tpushare.cli.serve import ServeEngine, _Request
from tpushare.models import moe
from tpushare.models import transformer as tf

TF_CFG = tf.tiny(remat=False)
TF_PARAMS = tf.init_params(jax.random.PRNGKey(0), TF_CFG)
MOE_CFG = moe.tiny(remat=False)
MOE_PARAMS = moe.init_params(jax.random.PRNGKey(0), MOE_CFG)

FAMILIES = ("dense", "moe_chunked", "moe_paged")


def make_engine(family, **kw):
    kw.setdefault("idle_sleep_s", 0.001)
    kw.setdefault("chaos_spec", "")     # never inherit the session env
    if family == "dense":
        return ServeEngine(TF_PARAMS, TF_CFG, n_slots=2, n_blocks=48,
                           block_size=8, max_blocks_per_slot=12, **kw)
    if family == "moe_chunked":
        # Blocks of 4 and chunks of 4: every prompt over 4 tokens is a
        # chunked admission riding fused ticks, on the sparse family.
        return ServeEngine(MOE_PARAMS, MOE_CFG, model_family="moe",
                           n_slots=2, n_blocks=96, block_size=4,
                           prefill_chunk=4, **kw)
    if family == "moe_paged":
        return ServeEngine(MOE_PARAMS, MOE_CFG, model_family="moe",
                           n_slots=2, n_blocks=48, block_size=8, **kw)
    raise AssertionError(family)


def vocab_of(family):
    return (TF_CFG if family == "dense" else MOE_CFG).vocab_size


def prompts_for(family, n, seed=5):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(0, vocab_of(family),
                                          4 + 3 * (i % 4))]
            for i in range(n)]


def drive(engine, prompts, max_tokens=5, limit=2000):
    """Run an UNSTARTED engine synchronously (no threads): submit all
    prompts, call _loop_once until every request terminates."""
    reqs = [_Request(list(p), max_tokens, None) for p in prompts]
    for r in reqs:
        assert engine.submit(r)
    for _ in range(limit):
        if all(r.done.is_set() for r in reqs):
            break
        engine._loop_once()
    assert all(r.done.is_set() for r in reqs), "engine stopped progressing"
    return reqs


def run_started(engine, prompts, max_tokens=5, timeout=120):
    """Threaded run: returns requests after every terminal transition."""
    engine.start()
    reqs = [_Request(list(p), max_tokens, None) for p in prompts]
    for r in reqs:
        assert engine.submit(r)
    for r in reqs:
        assert r.done.wait(timeout), "request hung"
    return reqs


# ---------------------------------------------------------------------------
# Injector: grammar, determinism, kinds, zero overhead
# ---------------------------------------------------------------------------

class TestInjector:
    def test_spec_grammar(self):
        faults, seed = parse_spec(
            "forward:raise@p=0.02;token_fetch:nan@p=0.01;"
            "apiserver:latency@p=0.5,ms=20;seed=7")
        assert seed == 7
        by_point = {f.point: f for f in faults}
        assert by_point["engine.tick.forward"].kind == "raise"
        assert by_point["engine.tick.forward"].p == 0.02
        assert by_point["k8s.apiserver"].ms == 20
        # summary is re-parseable (the /stats surface round-trips)
        inj = Injector(faults, seed=seed)
        refaults, reseed = parse_spec(inj.spec_summary())
        assert set(refaults) == set(faults) and reseed == 7

    @pytest.mark.parametrize("bad", [
        "nosuchpoint:raise@p=0.1",          # unknown point
        "forward:explode@p=0.1",            # unknown kind
        "forward:raise",                    # missing p
        "forward:raise@p=1.5",              # p out of range
        "forward:raise@p=0.1,zs=2",         # unknown param
    ])
    def test_bad_specs_fail_loudly(self, bad):
        with pytest.raises(ValueError):
            parse_spec(bad)

    def test_chip_failure_point_parses(self):
        faults, seed = parse_spec("chip_failure:raise@p=0.5;seed=4")
        assert faults[0].point == "mesh.chip_failure"
        assert faults[0].kind == "raise" and seed == 4
        # An engine point: its raise is XlaRuntimeError-shaped, never
        # the infra OSError shape.
        fire = Injector(faults, seed=seed).point("mesh.chip_failure")
        with pytest.raises(InjectedXlaRuntimeError):
            for _ in range(50):
                fire()

    def test_unarmed_points_are_the_shared_noop(self):
        inj = Injector.from_spec("")
        assert not inj.active
        for p in chaos.POINTS:
            assert inj.point(p) is NOOP
        # armed injector: only the armed point is non-noop
        inj = Injector.from_spec("forward:raise@p=1")
        assert inj.point("engine.tick.forward") is not NOOP
        assert inj.point("engine.admit") is NOOP

    def test_raise_shapes_by_point(self):
        inj = Injector.from_spec("forward:raise@p=1;apiserver:raise@p=1")
        with pytest.raises(InjectedXlaRuntimeError) as ei:
            inj.point("engine.tick.forward")()
        assert isinstance(ei.value, RuntimeError)       # XLA-shaped
        assert str(ei.value).startswith("INTERNAL:")
        with pytest.raises(InjectedUnavailable) as ei:
            inj.point("k8s.apiserver")()
        assert isinstance(ei.value, OSError)            # conn-shaped

    def test_nan_poisons_exactly_one_slot(self):
        inj = Injector.from_spec("token_fetch:nan@p=1;seed=3")
        out = inj.point("engine.token_fetch")({0: 5, 1: [3, 4]})
        bad = [s for s, t in out.items()
               if not isinstance(t, (int, list)) and t != t]
        assert len(bad) == 1
        good = ({0, 1} - set(bad)).pop()
        assert out[good] == {0: 5, 1: [3, 4]}[good]     # untouched

    def test_hang_is_bounded_by_deadline(self):
        inj = Injector.from_spec("forward:hang@p=1",
                                 deadline_ms=30)
        t0 = time.monotonic()
        inj.point("engine.tick.forward")()
        dt = time.monotonic() - t0
        assert 0.04 <= dt < 0.5         # ~2x deadline, never unbounded

    def test_seeded_determinism(self):
        def draws(seed):
            inj = Injector.from_spec(f"forward:raise@p=0.3;seed={seed}")
            fire = inj.point("engine.tick.forward")
            out = []
            for _ in range(40):
                try:
                    fire()
                    out.append(0)
                except InjectedXlaRuntimeError:
                    out.append(1)
            return out
        assert draws(7) == draws(7)
        assert draws(7) != draws(8)
        assert sum(draws(7)) > 0


class TestZeroOverhead:
    def test_engine_without_spec_holds_noops(self, monkeypatch):
        monkeypatch.delenv(chaos.ENV_CHAOS, raising=False)
        e = ServeEngine(TF_PARAMS, TF_CFG, n_slots=2, n_blocks=32,
                        block_size=8)     # chaos_spec=None -> env -> off
        assert e._fault_forward is NOOP
        assert e._fault_token_fetch is NOOP
        assert e._fault_admit is NOOP
        assert e._fault_chip is NOOP
        st = e.stats()
        assert st["chaos_active"] is False and st["chaos_spec"] is None
        assert st["tick_in_flight_ms"] is None      # no tick running

    def test_engine_reads_env_spec(self, monkeypatch):
        monkeypatch.setenv(chaos.ENV_CHAOS, "forward:raise@p=0.5;seed=2")
        e = ServeEngine(TF_PARAMS, TF_CFG, n_slots=2, n_blocks=32,
                        block_size=8)
        assert e.stats()["chaos_active"] is True
        assert e._fault_forward is not NOOP


# ---------------------------------------------------------------------------
# Quarantine / replay unit tests (synchronous engine, all families)
# ---------------------------------------------------------------------------

def one_shot_nan(engine):
    """Poison the lowest-slot token of the first non-empty fetch."""
    state = {"fired": False}

    def fire(value=None):
        if state["fired"] or not isinstance(value, dict) or not value:
            return None
        state["fired"] = True
        out = dict(value)
        out[sorted(out)[0]] = float("nan")
        return out

    engine._fault_token_fetch = fire
    return state


def one_shot_raise(engine, n=1):
    state = {"left": n}

    def fire(value=None):
        if state["left"] > 0:
            state["left"] -= 1
            raise InjectedXlaRuntimeError("INTERNAL: injected (test)")
        return None

    engine._fault_forward = fire
    return state


class TestQuarantineReplay:
    @pytest.mark.parametrize("family", FAMILIES)
    def test_nan_quarantines_one_slot_token_exact(self, family):
        prompts = prompts_for(family, 2)
        want = [list(r.tokens) for r in drive(make_engine(family), prompts)]
        eng = make_engine(family)
        state = one_shot_nan(eng)
        reqs = drive(eng, prompts)
        assert state["fired"]
        assert [list(r.tokens) for r in reqs] == want
        assert all(r.error is None for r in reqs)
        st = eng.stats()
        # The NaN failure domain is ONE slot: exactly one quarantine,
        # one replay; the co-resident stream never replays.
        assert st["quarantines"] == 1 and st["replays"] == 1
        assert "NaN" in st["last_error"] or st["last_error"]

    @pytest.mark.parametrize("family", FAMILIES)
    def test_tick_raise_replays_whole_batch_token_exact(self, family):
        prompts = prompts_for(family, 2)
        want = [list(r.tokens) for r in drive(make_engine(family), prompts)]
        eng = make_engine(family)
        one_shot_raise(eng)
        reqs = drive(eng, prompts)
        assert [list(r.tokens) for r in reqs] == want
        st = eng.stats()
        assert st["engine_errors"] >= 1
        assert st["quarantines"] >= 1 and st["replays"] >= 1

    def test_replay_twice_has_no_duplicate_prefix(self):
        """Two quarantines of the same request must fold each token
        into the replayed prompt ONCE (the fold-watermark fix: the
        old prompt+tokens concat duplicated the prefix on the second
        preemption/replay and silently corrupted the continuation)."""
        prompts = prompts_for("dense", 1)
        want = [list(r.tokens)
                for r in drive(make_engine("dense"), prompts, max_tokens=6)]
        eng = make_engine("dense")
        state = {"left": 2}

        def fire(value=None):
            # Raise on ticks that already generated some tokens so the
            # two replays both carry a non-empty prefix.
            if state["left"] > 0 and isinstance(value, dict) and value:
                state["left"] -= 1
                out = dict(value)
                out[sorted(out)[0]] = float("nan")
                return out
            return None

        eng._fault_token_fetch = fire
        reqs = drive(eng, prompts, max_tokens=6)
        assert eng.stats()["replays"] == 2
        assert [list(r.tokens) for r in reqs] == want

    def test_bounded_replays_end_in_clean_503(self):
        eng = make_engine("dense", max_replays=2)
        one_shot_raise(eng, n=10 ** 6)      # permanent fault
        reqs = drive(eng, prompts_for("dense", 1))
        (r,) = reqs
        assert r.error is not None and r.status == 503
        assert "replays exhausted" in r.error
        assert eng.stats()["replays"] == 2
        # The engine survived: a fresh request (fault cleared) works.
        eng._fault_forward = NOOP
        (r2,) = drive(eng, prompts_for("dense", 1, seed=9))
        assert r2.error is None and len(r2.tokens) == 5

    def test_admit_fault_replays_and_reaps_orphans(self):
        prompts = prompts_for("dense", 1)
        want = [list(r.tokens) for r in drive(make_engine("dense"), prompts)]
        eng = make_engine("dense")
        state = {"left": 1}

        def fire(value=None):
            if state["left"] > 0:
                state["left"] -= 1
                raise InjectedXlaRuntimeError("INTERNAL: admit (test)")
            return None

        eng._fault_admit = fire
        reqs = drive(eng, prompts)
        assert [list(r.tokens) for r in reqs] == want
        st = eng.stats()
        assert st["replays"] == 1 and st["engine_errors"] >= 1
        # No admission state (or blocks) leaked by the failed admit.
        assert eng.srv.admission_slots == []

    def test_recovery_tick_stays_sync_free(self):
        """The quarantining tick itself performs at most the ONE
        device->host transfer every tick is allowed (the token fetch):
        NaN validation and quarantine bookkeeping are pure host work
        (the sync-free invariant holds on the recovery path)."""
        from test_sync_free import count_transfers
        eng = make_engine("dense")
        reqs = [_Request(list(p), 10, None)
                for p in prompts_for("dense", 2)]
        for r in reqs:
            assert eng.submit(r)
        for _ in range(3):                  # admit + warm ticks
            eng._loop_once()
        assert not any(r.done.is_set() for r in reqs)
        one_shot_nan(eng)
        counts = [0]
        with count_transfers(counts):
            eng._loop_once()                # the quarantining tick
        assert eng.stats()["quarantines"] == 1
        assert counts[-1] <= 1, counts
        # Let the replay finish; output stays correct.
        for _ in range(2000):
            if all(r.done.is_set() for r in reqs):
                break
            eng._loop_once()
        assert all(r.error is None for r in reqs)


class TestRecoveryEdgeCases:
    """Regressions for the review findings on the recovery paths."""

    def test_admit_failure_after_activation_reaps_the_slot(self):
        """srv.admit() succeeds (slot ACTIVE server-side), then a later
        step of the admission path fails: the recovery handler must
        evict the orphaned active slot — otherwise it consumes engine
        capacity forever — and still replay the request token-exact."""
        prompts = prompts_for("dense", 1)
        want = [list(r.tokens) for r in drive(make_engine("dense"), prompts)]
        eng = make_engine("dense")
        real_admit = eng.srv.admit
        state = {"left": 1}

        def admit_then_die(*a, **kw):
            slot = real_admit(*a, **kw)
            if state["left"] > 0:
                state["left"] -= 1
                raise InjectedXlaRuntimeError(
                    "INTERNAL: token fetch after admit (test)")
            return slot

        eng.srv.admit = admit_then_die
        reqs = drive(eng, prompts)
        assert [list(r.tokens) for r in reqs] == want
        assert all(r.error is None for r in reqs)
        # No orphaned active slot: server activity matches engine
        # tracking (everything completed, so both are empty).
        assert int(eng.srv.active.sum()) == 0
        assert eng.stats()["replays"] == 1

    def test_slot_capacity_retires_only_the_offender(self):
        """paged.SlotCapacityExceeded is a per-slot ceiling: the
        offender finishes with its tokens so far, the co-resident
        stream is neither preempted nor quarantined."""
        from tpushare.models.paged import SlotCapacityExceeded
        prompts = prompts_for("dense", 2)
        want = [list(r.tokens) for r in drive(make_engine("dense"), prompts)]
        eng = make_engine("dense")
        reqs = [_Request(list(p), 5, None) for p in prompts]
        for r in reqs:
            assert eng.submit(r)
        for _ in range(3):                  # both admitted + warm
            eng._loop_once()
        assert len(eng._active) == 2
        victim_slot = sorted(eng._active)[0]
        victim = eng._active[victim_slot]
        real_step = eng.srv.step
        state = {"left": 1}

        def cap_once(*a, **kw):
            if state["left"] > 0:
                state["left"] -= 1
                raise SlotCapacityExceeded(
                    victim_slot, f"slot {victim_slot} exceeded "
                                 f"max_blocks")
            return real_step(*a, **kw)

        eng.srv.step = cap_once
        for _ in range(2000):
            if all(r.done.is_set() for r in reqs):
                break
            eng._loop_once()
        # Offender: finished cleanly at its tokens-so-far (a prefix of
        # the unconstrained run); survivor: full-length, token-exact.
        assert victim.error is None
        v_want = want[reqs.index(victim)]
        assert v_want[:len(victim.tokens)] == list(victim.tokens)
        other = [r for r in reqs if r is not victim][0]
        assert list(other.tokens) == want[reqs.index(other)]
        st = eng.stats()
        assert st["quarantines"] == 0 and st["preempted"] == 0

    def test_real_nan_logits_pick_the_invalid_token(self):
        """The sampler must not LAUNDER NaN logits through argmax into
        a plausible in-vocab id: a NaN row picks -1, which the
        engine's token validation quarantines. (Without this, the
        per-slot NaN failure domain would be reachable only through
        the injector's dict-poison, never from real poisoned
        logits.)"""
        import jax.numpy as jnp
        from tpushare.models.serving import TokenSampler
        s = TokenSampler()
        logits = np.zeros((2, 16), np.float32)
        logits[1, 3] = 5.0
        logits[0, 5] = np.nan
        toks = np.asarray(s.pick(jnp.asarray(logits)))
        assert toks[0] == -1 and toks[1] == 3
        # ...and -1 is invalid by construction for every family.
        assert make_engine("dense")._tok_bad(-1)

    def test_tok_bad_rejects_non_integral_floats(self):
        eng = make_engine("dense")
        assert eng._tok_bad(3.7)
        assert eng._tok_bad(float("nan"))
        assert eng._tok_bad(-1)
        assert eng._tok_bad(vocab_of("dense"))
        assert not eng._tok_bad(0)
        assert not eng._tok_bad(np.int32(3))
        assert not eng._tok_bad(3.0)        # integral float is a token


# ---------------------------------------------------------------------------
# Supervisor restart + tick deadline (threaded engine)
# ---------------------------------------------------------------------------

class TestDonatedPoolRecovery:
    """The KV pools are DONATED into the jitted ticks (ISSUE 7): a
    dispatch that dies AFTER consuming its donated inputs (a mid-
    execution XlaRuntimeError on chip — past every engine fault point)
    must leave the server with LIVE pools, or quarantine-and-replay
    recovery (the PR-4 contract) degenerates into an unrecoverable
    'Array has been deleted' loop until restarts exhaust."""

    def _arm_late_fault(self, srv, n_faults=1):
        """Wrap the server's donating decode so the REAL jit runs
        (consuming the donated pools) and THEN raises — the failure
        shape no engine-level fault point can produce."""
        orig = srv._decode
        fired = [0]

        def boom(*a, **kw):
            out = orig(*a, **kw)
            if fired[0] < n_faults:
                fired[0] += 1
                # drop `out` — exactly what a raise inside the
                # dispatch does to the caller
                raise InjectedXlaRuntimeError(
                    "chaos: post-donation device failure")
            return out

        srv._decode = boom
        return fired

    def test_pools_survive_post_donation_failure(self):
        eng = make_engine("dense")
        prompts = prompts_for("dense", 2)
        want = [r.tokens for r in drive(make_engine("dense"), prompts)]
        fired = self._arm_late_fault(eng.srv)
        reqs = drive(eng, prompts)
        assert fired[0] == 1, "late fault never fired"
        assert not eng.srv.cache.pool_k.is_deleted()
        assert not eng.srv.cache.pool_v.is_deleted()
        st = eng.stats()
        assert st["quarantines"] >= 1 and st["replays"] >= 1
        # Token-exact recovery: replay re-prefills from the prompts,
        # so the zero-rebuilt pools change nothing observable.
        assert [r.tokens for r in reqs] == want
        assert all(r.error is None for r in reqs)

    def test_prefix_cache_unpublished_on_pool_rebuild(self):
        """The rebuilt pools are zeros: every published prefix block's
        KV died with the old pools, so a later identical admit must
        MISS (a hit would serve bit-garbage KV silently)."""
        from tpushare.models.paged import PagedSlotServer
        srv = PagedSlotServer(TF_PARAMS, TF_CFG, n_slots=2,
                              n_blocks=32, block_size=4,
                              prefix_cache=True)
        rng = np.random.default_rng(9)
        prompt = jax.numpy.asarray(
            rng.integers(0, TF_CFG.vocab_size, 13), "int32")
        a = srv.admit(prompt)
        srv.evict(a)
        assert srv.cache.index          # published and resident
        total_free = len(srv.cache.free) + len(srv.cache.lru)
        b = srv.admit(prompt)
        assert srv.last_cached_len == 12
        self._arm_late_fault(srv)
        with pytest.raises(InjectedXlaRuntimeError):
            srv.step()
        srv.evict(b)
        assert not srv.cache.pool_k.is_deleted()
        assert not srv.cache.index and not srv.cache.lru
        c = srv.admit(prompt)
        assert srv.last_cached_len == 0     # MISS: KV was rebuilt
        srv.evict(c)
        # Nothing leaked across the rebuild: the whole pool is
        # allocatable again.
        assert len(srv.cache.free) + len(srv.cache.lru) == total_free


class TestSupervisor:
    # The lethal injections below kill the engine thread ON PURPOSE
    # (that is what the supervisor recovers from); pytest's thread
    # excepthook warning about them is the test working as intended.
    pytestmark = pytest.mark.filterwarnings(
        "ignore::pytest.PytestUnhandledThreadExceptionWarning")

    def test_lethal_error_restarts_engine_thread(self):
        prompts = prompts_for("dense", 1)
        want = [list(r.tokens) for r in drive(make_engine("dense"), prompts)]
        eng = make_engine("dense", max_engine_restarts=3,
                          restart_backoff_s=0.01)
        real = eng.srv.step
        state = {"left": 1}

        def lethal(*a, **kw):
            if state["left"] > 0:
                state["left"] -= 1
                # BaseException: escapes the per-tick Exception
                # recovery and kills the engine thread.
                raise SystemExit("lethal (injected)")
            return real(*a, **kw)

        eng.srv.step = lethal
        try:
            reqs = run_started(eng, prompts)
            assert [list(r.tokens) for r in reqs] == want
            assert all(r.error is None for r in reqs)
            st = eng.stats()
            assert st["engine_restarts"] == 1
            assert eng.healthy() and eng.state() == "running"
        finally:
            eng.srv.step = real
            eng.stop()

    def test_restarts_exhausted_goes_red(self):
        eng = make_engine("dense", max_engine_restarts=1,
                          restart_backoff_s=0.01)

        def always_lethal(*a, **kw):
            raise SystemExit("lethal (injected)")

        eng.srv.step = always_lethal
        eng.start()
        try:
            req = _Request(prompts_for("dense", 1)[0], 4, None)
            assert eng.submit(req)
            assert req.done.wait(30)
            assert req.error is not None
            deadline = time.time() + 10
            while eng.healthy() and time.time() < deadline:
                time.sleep(0.01)
            assert not eng.healthy() and eng.state() == "dead"
            assert eng.stats()["engine_restarts"] == 1
            # With no engine left, a new submission must fail FAST
            # (draining 503), not park in a queue nothing drains.
            late = _Request(prompts_for("dense", 1)[0], 2, None)
            assert eng.submit(late)
            assert late.done.wait(2)
            assert late.error is not None
        finally:
            eng.stop()

    def test_tick_deadline_breaches_are_counted(self):
        eng = make_engine("dense", tick_deadline_ms=20,
                          chaos_spec="forward:latency@p=1,ms=60;seed=1")
        try:
            reqs = run_started(eng, prompts_for("dense", 1),
                               max_tokens=3)
            assert all(r.error is None for r in reqs)
            assert eng.stats()["deadline_breaches"] >= 1
        finally:
            eng.stop()


# ---------------------------------------------------------------------------
# Health-churn drain + plugin/k8s fault points
# ---------------------------------------------------------------------------

@pytest.fixture
def chaos_env(monkeypatch):
    def arm(spec):
        monkeypatch.setenv(chaos.ENV_CHAOS, spec)
        chaos.reset_default_injector()
    yield arm
    monkeypatch.delenv(chaos.ENV_CHAOS, raising=False)
    chaos.reset_default_injector()


class TestHealthChurnDrain:
    def test_unhealthy_chip_drains_colocated_daemon(self):
        from tpushare.k8s.events import EventRecorder
        from tpushare.plugin.allocate import Allocator
        from tpushare.plugin.backend import FakeBackend
        from tpushare.plugin.devices import expand_devices
        from tpushare.plugin.health import serve_drain_hook
        from tpushare.plugin.podmanager import PodManager
        from tpushare.plugin.server import TpuDevicePlugin
        from fakes import FakeKubeClient, make_node

        eng = make_engine("dense")
        httpd = serve_mod.serve(eng, host="127.0.0.1", port=0,
                                timeout_s=60.0)
        try:
            # A long generation accepted BEFORE the churn...
            pre = _Request(prompts_for("dense", 1)[0], 12, None)
            assert eng.submit(pre)

            kube = FakeKubeClient(nodes=[make_node()])
            topo = FakeBackend(chips=2, hbm_gib=16).probe()
            dm = expand_devices(topo)
            podmgr = PodManager(kube, "node-1", sleep=lambda s: None)
            alloc = Allocator(dm, topo, podmgr, kube,
                              recorder=EventRecorder(kube, "node-1"))
            url = (f"http://127.0.0.1:{httpd.server_address[1]}/drain")
            plugin = TpuDevicePlugin(
                dm, topo, alloc, socket_path="/tmp/unused.sock",
                on_unhealthy=serve_drain_hook(url))
            plugin.set_chip_health(topo.chips[0].uuid, False)

            # New work is refused the moment the drain lands...
            post = _Request(prompts_for("dense", 1, seed=9)[0], 3, None)
            assert eng.submit(post)
            assert post.done.wait(10)
            assert post.error and "draining" in post.error
            # ...while the accepted request still completes.
            assert pre.done.wait(60)
            assert pre.error is None and len(pre.tokens) == 12
            assert eng.state() == "draining" and eng.healthy()
        finally:
            httpd.shutdown()
            eng.stop()

    def test_recovered_chip_undrains_only_when_all_healthy(self):
        """Drain must not be one-way: full chip recovery POSTs
        /undrain and the replica rejoins service — but only once EVERY
        chip is healthy again, and never over a SIGTERM drain."""
        from tpushare.k8s.events import EventRecorder
        from tpushare.plugin.allocate import Allocator
        from tpushare.plugin.backend import FakeBackend
        from tpushare.plugin.devices import expand_devices
        from tpushare.plugin.health import (serve_drain_hook,
                                            serve_undrain_hook)
        from tpushare.plugin.podmanager import PodManager
        from tpushare.plugin.server import TpuDevicePlugin
        from fakes import FakeKubeClient, make_node

        eng = make_engine("dense")
        httpd = serve_mod.serve(eng, host="127.0.0.1", port=0,
                                timeout_s=60.0)
        try:
            kube = FakeKubeClient(nodes=[make_node()])
            topo = FakeBackend(chips=2, hbm_gib=16).probe()
            dm = expand_devices(topo)
            podmgr = PodManager(kube, "node-1", sleep=lambda s: None)
            alloc = Allocator(dm, topo, podmgr, kube,
                              recorder=EventRecorder(kube, "node-1"))
            url = f"http://127.0.0.1:{httpd.server_address[1]}/drain"
            plugin = TpuDevicePlugin(
                dm, topo, alloc, socket_path="/tmp/unused.sock",
                on_unhealthy=serve_drain_hook(url),
                on_healthy=serve_undrain_hook(url))
            u0, u1 = topo.chips[0].uuid, topo.chips[1].uuid
            plugin.set_chip_health(u0, False)
            plugin.set_chip_health(u1, False)
            assert eng._draining.is_set()
            # One of two chips back: still draining.
            plugin.set_chip_health(u0, True)
            assert eng._draining.is_set()
            # All healthy: undrained, serving again.
            plugin.set_chip_health(u1, True)
            assert not eng._draining.is_set()
            req = _Request(prompts_for("dense", 1)[0], 2, None)
            assert eng.submit(req) and req.done.wait(60)
            assert req.error is None and len(req.tokens) == 2
            # SIGTERM-style drain is sticky: undrain refused.
            eng._drain_sticky = True
            eng._draining.set()
            assert eng.end_drain() is False
            assert eng._draining.is_set()
        finally:
            httpd.shutdown()
            eng.stop()

    def test_hook_unset_and_dead_daemon(self, monkeypatch):
        from tpushare.plugin.health import serve_drain_hook
        monkeypatch.delenv("TPUSHARE_DRAIN_URL", raising=False)
        assert serve_drain_hook() is None
        hook = serve_drain_hook("http://127.0.0.1:9/drain",
                                timeout_s=0.2)
        assert hook("chip-0") is False      # never raises


class TestDaemonSeams:
    def test_health_probe_fault_reads_all_unhealthy(self, chaos_env):
        from tpushare.plugin.backend import FakeBackend
        from tpushare.plugin.health import composite_prober
        backend = FakeBackend(chips=2, hbm_gib=16)
        topo = backend.probe()
        chaos_env("health_probe:raise@p=1")
        probe = composite_prober(backend)
        assert probe(topo) == {c.uuid: False for c in topo.chips}

    def test_health_probe_unarmed_is_healthy(self, chaos_env):
        from tpushare.plugin.backend import FakeBackend
        from tpushare.plugin.health import composite_prober
        backend = FakeBackend(chips=2, hbm_gib=16)
        topo = backend.probe()
        chaos_env("")                       # explicit: nothing armed
        probe = composite_prober(backend)
        assert all(probe(topo).values())

    def test_apiserver_fault_is_connection_shaped(self, chaos_env):
        from tpushare.k8s.client import KubeClient, _Config
        chaos_env("apiserver:raise@p=1")
        kube = KubeClient(_Config(host="127.0.0.1", port=1,
                                  scheme="http"))
        with pytest.raises(InjectedUnavailable):
            kube.get_node("node-1")


# ---------------------------------------------------------------------------
# The seeded fault-storm property test (acceptance)
# ---------------------------------------------------------------------------

class TestFaultStorm:
    """Under forward:raise + token_fetch:nan (fixed seed), every
    submitted request either completes with tokens bit-identical to
    the fault-free oracle or ends in a clean 503, for every engine
    family — and the engine itself survives the storm."""

    SPEC = "forward:raise@p=0.15;token_fetch:nan@p=0.1;seed=11"

    @pytest.mark.parametrize("family", FAMILIES)
    def test_storm_token_exact_or_clean_503(self, family):
        prompts = prompts_for(family, 5)
        kw = {}
        if family == "dense":
            # Chunked admissions ride the storm too (fused-tick and
            # mid-admission quarantine paths).
            kw["prefill_chunk"] = 8
        oracle = make_engine(family, **kw)
        want = drive(oracle, prompts)
        assert all(r.error is None for r in want)

        eng = make_engine(family, chaos_spec=self.SPEC, max_replays=30,
                          tick_deadline_ms=500, **kw)
        try:
            reqs = run_started(eng, prompts)
            for w, r in zip(want, reqs):
                if r.error is None:
                    assert list(r.tokens) == list(w.tokens)
                else:
                    assert r.status == 503, (r.status, r.error)
            st = eng.stats()
            assert st["replays"] > 0, "storm exercised nothing"
            assert eng.healthy()
            # At least one request must survive token-exact (a storm
            # that 503s everything is not the property).
            assert any(r.error is None for r in reqs)
        finally:
            eng.stop()


class TestChipHealthHook:
    """Per-chip churn, tenant side (ISSUE 13): the plugin's unhealthy
    transition POSTs /mesh/chip with the chip's identity
    (health.serve_chip_health_hook) — a SHARDED engine degrades onto
    its survivors; an unsharded engine keeps the drain behavior (one
    chip IS its whole domain)."""

    def _plugin(self, url, chips=2):
        from tpushare.k8s.events import EventRecorder
        from tpushare.plugin.allocate import Allocator
        from tpushare.plugin.backend import FakeBackend
        from tpushare.plugin.devices import expand_devices
        from tpushare.plugin.health import (serve_chip_health_hook,
                                            serve_undrain_hook)
        from tpushare.plugin.podmanager import PodManager
        from tpushare.plugin.server import TpuDevicePlugin
        from fakes import FakeKubeClient, make_node

        kube = FakeKubeClient(nodes=[make_node()])
        topo = FakeBackend(chips=chips, hbm_gib=16).probe()
        dm = expand_devices(topo)
        podmgr = PodManager(kube, "node-1", sleep=lambda s: None)
        alloc = Allocator(dm, topo, podmgr, kube,
                          recorder=EventRecorder(kube, "node-1"))
        plugin = TpuDevicePlugin(
            dm, topo, alloc, socket_path="/tmp/unused.sock",
            on_unhealthy=serve_chip_health_hook(topo, url),
            on_healthy=serve_undrain_hook(url))
        return plugin, topo

    def test_sharded_engine_degrades_not_drains(self):
        from tpushare.parallel import make_mesh
        eng = make_engine("dense", max_reshards=5,
                          mesh=make_mesh({"tp": 2},
                                         devices=jax.devices()[:2]))
        httpd = serve_mod.serve(eng, host="127.0.0.1", port=0,
                                timeout_s=60.0)
        try:
            url = f"http://127.0.0.1:{httpd.server_address[1]}/drain"
            plugin, topo = self._plugin(url)
            plugin.set_chip_health(topo.chips[1].uuid, False)
            # The hook landed as a chip event, NOT a drain: the
            # replica still accepts work, and the engine thread
            # degrades at its next tick.
            assert not eng._draining.is_set()
            req = _Request(prompts_for("dense", 1)[0], 3, None)
            assert eng.submit(req) and req.done.wait(60)
            assert req.error is None and len(req.tokens) == 3
            st = eng.stats()
            assert st["reshards"] == 1 and st["degraded"] is True
            assert st["healthy_devices"] == 1
            # All-healthy recovery: the plugin POSTs /undrain — the
            # engine's all-clear; the next idle tick grows back.
            plugin.set_chip_health(topo.chips[1].uuid, True)
            deadline = time.time() + 30
            while (eng.stats()["degraded"]
                   and time.time() < deadline):
                time.sleep(0.02)
            assert eng.stats()["degraded"] is False
            assert eng.stats()["grow_backs"] == 1
        finally:
            httpd.shutdown()
            eng.stop()

    def test_unsharded_engine_keeps_drain_behavior(self):
        eng = make_engine("dense")
        httpd = serve_mod.serve(eng, host="127.0.0.1", port=0,
                                timeout_s=60.0)
        try:
            url = f"http://127.0.0.1:{httpd.server_address[1]}/drain"
            plugin, topo = self._plugin(url)
            plugin.set_chip_health(topo.chips[0].uuid, False)
            assert eng._draining.is_set()       # one chip IS the domain
            post = _Request(prompts_for("dense", 1)[0], 3, None)
            assert eng.submit(post)
            assert post.done.wait(10)
            assert post.error and "draining" in post.error
        finally:
            httpd.shutdown()
            eng.stop()

    def test_chip_to_device_maps_through_the_grant(self, monkeypatch):
        # The pod was granted chips {2, 5}: plugin chip index 5 is
        # the engine's device position 1.
        monkeypatch.setenv("TPU_VISIBLE_CHIPS", "5,2")
        assert serve_mod.chip_to_device(2) == 0
        assert serve_mod.chip_to_device(5) == 1
        with pytest.raises(ValueError, match="not in this pod"):
            serve_mod.chip_to_device(3)
        monkeypatch.setenv("TPU_VISIBLE_CHIPS", "no-tpu-has-4GiB-to-run")
        with pytest.raises(ValueError, match="poisoned"):
            serve_mod.chip_to_device(0)
        monkeypatch.delenv("TPU_VISIBLE_CHIPS")
        assert serve_mod.chip_to_device(1) == 1     # identity fallback

    def test_mesh_chip_endpoint_validates(self):
        import json as _json
        import urllib.request

        eng = make_engine("dense")
        httpd = serve_mod.serve(eng, host="127.0.0.1", port=0,
                                timeout_s=10.0)
        base = f"http://127.0.0.1:{httpd.server_address[1]}"

        def post(body):
            req = urllib.request.Request(
                base + "/mesh/chip", method="POST",
                data=_json.dumps(body).encode(),
                headers={"Content-Type": "application/json"})
            try:
                with urllib.request.urlopen(req, timeout=5) as r:
                    return r.status, _json.loads(r.read())
            except urllib.error.HTTPError as e:
                return e.code, _json.loads(e.read())

        try:
            code, out = post({"device": 0, "healthy": False})
            assert code == 200 and out["mesh"] is None
            assert eng._draining.is_set()       # unsharded fallback
            code, out = post({"device": 0, "healthy": True})
            assert code == 200
            assert not eng._draining.is_set()
            assert post({"healthy": False})[0] == 400
            assert post({"device": "x"})[0] == 400
            assert post({"device": 0, "healthy": "down"})[0] == 400
            assert post({"chip": True, "healthy": False})[0] == 400
        finally:
            httpd.shutdown()
            eng.stop()


# ---------------------------------------------------------------------------
# Mesh shrink storm (ISSUE 13 acceptance pin)
# ---------------------------------------------------------------------------

@pytest.mark.skipif(len(jax.devices()) < 4,
                    reason="needs 4+ forced host devices")
class TestMeshShrinkStorm:
    """The elastic-mesh acceptance pin: a seeded mesh.chip_failure
    storm against a SHARDED engine (tp=2 dense; ep x tp = 2x2 MoE)
    kills chips mid-serving — every answer is token-exact vs the
    single-chip oracle or a clean 503, nothing is lost, the engine
    ends the storm SERVING DEGRADED (reshards >= 1, degraded=true,
    a smaller current mesh), one-fetch-per-tick holds throughout,
    and grow-back lands after the undrain all-clear."""

    SPEC = "chip_failure:raise@p=0.2;seed=3"

    def _mesh(self, family):
        from tpushare.parallel import make_mesh
        if family == "dense":
            return make_mesh({"tp": 2}, devices=jax.devices()[:2])
        return make_mesh({"tp": 2, "ep": 2}, devices=jax.devices()[:4])

    @pytest.mark.parametrize("family", ["dense", "moe_paged"])
    def test_storm_shrinks_serves_degraded_grows_back(self, family):
        prompts = prompts_for(family, 5)
        want = drive(make_engine(family), prompts)
        assert all(r.error is None for r in want)

        eng = make_engine(family, chaos_spec=self.SPEC, max_replays=30,
                          max_reshards=10, mesh=self._mesh(family))
        reqs = drive(eng, prompts)
        for w, r in zip(want, reqs):
            if r.error is None:
                assert list(r.tokens) == list(w.tokens)
            else:
                assert r.status == 503, (r.status, r.error)
        st = eng.stats()
        assert st["reshards"] >= 1, "storm never shrank the mesh"
        assert st["degraded"] is True
        assert st["mesh_shape_current"] != st["mesh_shape_configured"]
        assert st["replayed_on_reshard"] >= 1
        # Nothing lost: every request terminated (drive asserts it),
        # and at least one survived token-exact.
        assert any(r.error is None for r in reqs)
        # Sync-free held across every shrink (the /stats spelling).
        assert st["fetches_per_tick"] is not None
        assert st["fetches_per_tick"] <= 1.0
        # The chaos seam actually fired, and is observable.
        assert st["chaos_fired"].get("mesh.chip_failure", 0) >= 1
        # Grow-back: the undrain all-clear (the plugin's all-healthy
        # hook) + an idle tick restore the configured mesh. The storm
        # is STILL armed, so a fire can beat the grow to a tick's
        # preamble (and re-shrink it later) — the pin is that a quiet
        # idle tick grows back, checked at the grow tick itself.
        assert eng.end_drain() is True
        for _ in range(25):
            eng.end_drain()     # chips keep "recovering" under fire
            eng._loop_once()
            if eng.stats()["grow_backs"] >= 1:
                break
        st = eng.stats()
        assert st["grow_backs"] >= 1, "undrain never grew the mesh back"
        assert st["degraded"] is False
        assert st["mesh_shape_current"] == st["mesh_shape_configured"]

    def test_chip_failure_never_kills_the_last_chip(self):
        """p=1: every tick fires, but the injector models PARTIAL
        chip loss — the engine shrinks to one chip and keeps serving
        there (total loss is the drain path, driven via chip_event)."""
        eng = make_engine("dense",
                          chaos_spec="chip_failure:raise@p=1;seed=1",
                          max_replays=50, max_reshards=10,
                          mesh=self._mesh("dense"))
        reqs = drive(eng, prompts_for("dense", 2))
        assert all(r.done.is_set() for r in reqs)
        st = eng.stats()
        assert st["reshards"] == 1          # one shrink, then stable
        assert st["healthy_devices"] == 1
        assert any(r.error is None for r in reqs)

    def test_unsharded_engine_ignores_the_point(self):
        """mesh.chip_failure is a MESH point: an unsharded engine
        never calls it (its chip domain is the daemon drain), so an
        armed spec must not perturb the stream."""
        prompts = prompts_for("dense", 2)
        want = drive(make_engine("dense"), prompts)
        eng = make_engine("dense",
                          chaos_spec="chip_failure:raise@p=1;seed=1")
        reqs = drive(eng, prompts)
        assert [list(r.tokens) for r in reqs] == \
            [list(w.tokens) for w in want]
        assert all(r.error is None for r in reqs)
        assert eng.stats()["chaos_fired"] in (None, {}) or \
            eng.stats()["chaos_fired"].get("mesh.chip_failure", 0) == 0


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="needs 2+ forced host devices")
class TestSupervisorMeshSeam:
    """The supervisor x mesh seam (ISSUE 13 satellite): a supervised
    restart of a SHARDED engine re-places weights on the CURRENT
    healthy mesh, never the boot-time one — pinned by killing the
    engine thread at the exact moment a chip-health event lands."""

    pytestmark = pytest.mark.filterwarnings(
        "ignore::pytest.PytestUnhandledThreadExceptionWarning")

    def test_restart_lands_on_current_healthy_mesh(self):
        from tpushare.parallel import make_mesh
        prompts = prompts_for("dense", 1)
        want = [list(r.tokens) for r in
                drive(make_engine("dense"), prompts, max_tokens=6)]

        mesh = make_mesh({"tp": 2}, devices=jax.devices()[:2])
        eng = make_engine("dense", mesh=mesh, max_reshards=5,
                          max_engine_restarts=3,
                          restart_backoff_s=0.01)
        real = eng.srv.step
        state = {"left": 1}

        def lethal(*a, **kw):
            if state["left"] > 0:
                state["left"] -= 1
                # The chip event lands exactly as the engine dies —
                # the reshard cannot run in THIS thread's lifetime;
                # only the supervisor can place the restart correctly.
                eng.chip_event(1, False)
                raise SystemExit("lethal (injected)")
            return real(*a, **kw)

        eng.srv.step = lethal
        reqs = run_started(eng, prompts, max_tokens=6)
        try:
            assert [list(r.tokens) for r in reqs] == want
            assert all(r.error is None for r in reqs)
            st = eng.stats()
            assert st["engine_restarts"] == 1
            assert st["reshards"] >= 1
            # The restarted engine serves on the CURRENT (healthy)
            # mesh — one device, not the boot-time two.
            assert st["mesh_shape_current"] == {}
            assert st["num_devices"] == 1
            assert st["degraded"] is True
            assert eng.healthy() and eng.state() == "running"
        finally:
            eng.stop()


# ---------------------------------------------------------------------------
# Router kill-a-replica storm (ISSUE 8 acceptance pin)
# ---------------------------------------------------------------------------

class TestRouterKillStorm:
    """K=3 engine replicas behind the real front door under a
    mixed-prefix request storm: killing one replica mid-storm loses
    ZERO requests (every answer is token-exact vs the single-engine
    oracle or a clean 503), the router's breaker opens for the dead
    replica and closes only after it returns via /undrain, and
    prefix-affinity routing strictly lifts prefix_hit_tokens over
    random routing on the same trace."""

    PREFIX_LEN = 16                     # 2 full blocks at block_size 8
    GROUPS = 3
    PER_GROUP = 4

    def _mixed_prompts(self, seed=5):
        rng = np.random.default_rng(seed)
        prompts = []
        for _ in range(self.GROUPS):
            prefix = [int(t) for t in rng.integers(
                0, vocab_of("dense"), self.PREFIX_LEN)]
            for _ in range(self.PER_GROUP):
                prompts.append(prefix + [int(t) for t in rng.integers(
                    0, vocab_of("dense"), 4)])
        return prompts

    def _fleet(self, k, policy="affinity", **router_kw):
        from tpushare.router import Router
        from tpushare.router.daemon import serve_router
        replicas = []
        for _ in range(k):
            eng = make_engine("dense")
            httpd = serve_mod.serve(eng, host="127.0.0.1", port=0)
            replicas.append([eng, httpd, httpd.server_address[1]])
        urls = [f"http://127.0.0.1:{p}" for _, _, p in replicas]
        router_kw.setdefault("poll_interval_s", 0.1)
        router_kw.setdefault("breaker_threshold", 2)
        router_kw.setdefault("breaker_backoff_s", 0.05)
        router_kw.setdefault("retry_budget", 2)
        router_kw.setdefault("shed_wait_s", 1.0)
        router_kw.setdefault("probe_timeout_s", 0.5)
        router = Router(urls, policy=policy, **router_kw)
        rhttpd = serve_router(router, "127.0.0.1", 0)
        router.poll_once()              # learn block sizes immediately
        return replicas, router, rhttpd, rhttpd.server_address[1]

    @staticmethod
    def _teardown(replicas, router, rhttpd):
        rhttpd.shutdown()
        router.stop()
        for eng, httpd, _ in replicas:
            if httpd is not None:
                httpd.shutdown()
            eng.stop()

    @staticmethod
    def _post(port, obj, timeout=120):
        import http.client
        import json as _json
        conn = http.client.HTTPConnection("127.0.0.1", port,
                                          timeout=timeout)
        try:
            conn.request("POST", "/v1/completions",
                         _json.dumps(obj).encode(),
                         {"Content-Type": "application/json"})
            r = conn.getresponse()
            return r.status, _json.loads(r.read() or b"{}")
        finally:
            conn.close()

    def _storm(self, port, prompts, max_tokens=3):
        import threading
        results = [None] * len(prompts)

        def go(i, p):
            try:
                results[i] = self._post(port, {"prompt": p,
                                               "max_tokens": max_tokens})
            except Exception as e:      # transport death = LOST
                results[i] = ("transport", {"error": str(e)})

        threads = [threading.Thread(target=go, args=(i, p))
                   for i, p in enumerate(prompts)]
        for t in threads:
            t.start()
        return threads, results

    def test_kill_one_mid_storm_loses_nothing(self):
        from tpushare.router import CLOSED, OPEN
        prompts = self._mixed_prompts()
        oracle = make_engine("dense")
        want = drive(oracle, prompts, max_tokens=3)
        assert all(r.error is None for r in want)
        want_tokens = [list(r.tokens) for r in want]

        replicas, router, rhttpd, rport = self._fleet(3)
        try:
            # Wave 1: the fleet takes the trace clean.
            threads, wave1 = self._storm(rport, prompts)
            for t in threads:
                t.join(120)
            # Wave 2 fires, and replica 0 is KILLED while it's in
            # flight: its HTTP server dies (connection resets for
            # everything routed there) and its engine stops.
            threads, wave2 = self._storm(rport, prompts)
            eng0, httpd0, port0 = replicas[0]
            httpd0.shutdown()
            httpd0.server_close()       # release the port for revival
            eng0.stop()
            replicas[0][1] = None       # torn down already
            for t in threads:
                t.join(120)

            exact = clean_503 = 0
            for got in wave1 + wave2:
                assert got is not None, "request hung (lost)"
                status, body = got
                assert status != "transport", body
                if status == 200:
                    assert body["tokens"] in want_tokens, \
                        "routed answer diverged from the oracle"
                    exact += 1
                else:
                    # the ONLY acceptable failure class is a clean 503
                    assert status == 503, (status, body)
                    clean_503 += 1
            assert exact + clean_503 == 2 * len(prompts)
            assert exact > 0
            # every wave-1 answer must be exact (no faults yet)
            assert all(s == 200 for s, _ in wave1)

            # Breaker: opens for the dead replica...
            deadline = time.time() + 10
            while (router.replicas[0].breaker != OPEN
                   and time.time() < deadline):
                router.poll_once()
                time.sleep(0.05)
            assert router.replicas[0].breaker == OPEN

            # ...and CLOSES only after the replica returns via
            # /undrain: the revived engine comes back draining (alive,
            # not ready), which must NOT close the breaker.
            eng0b = make_engine("dense")
            eng0b.begin_drain()
            httpd0b = serve_mod.serve(eng0b, host="127.0.0.1",
                                      port=port0)
            replicas[0][0], replicas[0][1] = eng0b, httpd0b
            time.sleep(0.2)             # past the breaker backoff
            for _ in range(3):
                router.poll_once()
            assert router.replicas[0].breaker != CLOSED
            import http.client
            conn = http.client.HTTPConnection("127.0.0.1", port0,
                                              timeout=10)
            conn.request("POST", "/undrain", b"{}")
            assert conn.getresponse().status == 200
            conn.close()
            deadline = time.time() + 10
            while (router.replicas[0].breaker != CLOSED
                   and time.time() < deadline):
                router.poll_once()
                time.sleep(0.05)
            assert router.replicas[0].breaker == CLOSED
            assert router._routable(router.replicas[0])
            # traffic rebalanced: the survivors served wave 2
            served = [r.proxied for r in router.replicas]
            assert served[1] + served[2] > 0
        finally:
            self._teardown(replicas, router, rhttpd)

    def _run_trace(self, policy, seed):
        """Sequential mixed-prefix trace through a fresh K=3 fleet;
        returns summed replica-side prefix_hit_tokens."""
        prompts = self._mixed_prompts()
        replicas, router, rhttpd, rport = self._fleet(
            3, policy=policy, seed=seed)
        try:
            for p in prompts:
                status, body = self._post(rport, {"prompt": p,
                                                  "max_tokens": 2})
                assert status == 200, body
            return sum(eng.stats()["prefix_hit_tokens"]
                       for eng, _, _ in replicas)
        finally:
            self._teardown(replicas, router, rhttpd)

    def test_affinity_strictly_lifts_prefix_hits_vs_random(self):
        """The measured routing win: on the same trace (3 prefix
        groups x 4 members), affinity routes every group to the
        replica already holding its blocks — random scatters them and
        forfeits hits. Strict inequality is the acceptance bar."""
        affinity_hits = self._run_trace("affinity", seed=0)
        random_hits = self._run_trace("random", seed=0)
        # Affinity: 3 groups x 3 follow-ups x 16 shared-prefix tokens.
        assert affinity_hits == (self.GROUPS * (self.PER_GROUP - 1)
                                 * self.PREFIX_LEN)
        assert affinity_hits > random_hits


# ---------------------------------------------------------------------------
# Priority survives failure (ISSUE 9): tier + quota through
# preemption, quarantine, and replay
# ---------------------------------------------------------------------------

class TestTierSurvivesFailure:
    def _mk(self, **kw):
        """Pool sized so two 15-token admits + decode growth MUST
        exhaust it (the test_serve preemption geometry: 8 usable
        blocks at bs=4, 4 per prompt) — preemption is forced, not
        probabilistic."""
        kw.setdefault("idle_sleep_s", 0.001)
        kw.setdefault("chaos_spec", "")
        return ServeEngine(TF_PARAMS, TF_CFG, n_slots=2, n_blocks=9,
                           block_size=4, prefix_cache=False, **kw)

    def _prompts(self):
        rng = np.random.default_rng(7)
        return [[int(t) for t in rng.integers(0, TF_CFG.vocab_size, 15)]
                for _ in range(2)]

    def test_preempted_interactive_replays_token_exact_tier_intact(self):
        """A preempted-then-replayed interactive request under a
        seeded fault storm: tokens bit-identical to the fault-free
        oracle, the tier and its deadline clock (t_submit) survive
        every re-admission, and the per-tenant quota ledger refunds
        to exactly zero."""
        from tpushare.slo import TenantQuotaSpec
        ps = self._prompts()
        want = [list(r.tokens) for r in drive(
            ServeEngine(TF_PARAMS, TF_CFG, n_slots=2, n_blocks=64,
                        block_size=4, prefix_cache=False,
                        idle_sleep_s=0.001, chaos_spec=""),
            ps, max_tokens=8)]
        eng = self._mk(
            tenant_quotas={"acme": TenantQuotaSpec(0, None)})
        reqs = [_Request(list(p), 8, None, tier="interactive",
                         tenant="acme") for p in ps]
        clocks = [r.t_submit for r in reqs]
        for r in reqs:
            assert eng.submit(r)
        # Phase 1: decode until pool growth forces the preemption.
        for _ in range(3000):
            if eng.stats()["preempted"] >= 1:
                break
            eng._loop_once()
        assert eng.stats()["preempted"] >= 1
        # Phase 2: the fault storm lands ON the preempt-pressured
        # engine — a poisoned fetch quarantines mid-recovery.
        state = one_shot_nan(eng)
        for _ in range(3000):
            if all(r.done.is_set() for r in reqs):
                break
            eng._loop_once()
        assert all(r.done.is_set() for r in reqs)
        assert state["fired"]
        assert [list(r.tokens) for r in reqs] == want
        assert all(r.error is None for r in reqs)
        st = eng.stats()
        per = st["per_tier"]["interactive"]
        # the machinery actually ran: preemption AND quarantine/replay
        assert per["preempted"] >= 1 and st["preempted"] >= 1
        assert per["quarantined"] >= 1 and st["replays"] >= 1
        # tier identity + deadline clock survived every re-admission
        assert [r.tier for r in reqs] == ["interactive"] * 2
        assert [r.t_submit for r in reqs] == clocks
        assert per["completed"] == 2 and per["ttft_p50_ms"] is not None
        # quota accounting survived preempt/quarantine/replay: every
        # charged block was refunded exactly once
        assert eng._kv_quota.used == {}

    def test_batch_preemption_never_cascades_into_interactive(self):
        """Mixed tiers under pool pressure: the preemption victim is
        ALWAYS the batch slot, and no interactive request is ever
        quarantined by a batch preemption — the failure domains stay
        tier-isolated."""
        ps = self._prompts()
        want = [list(r.tokens) for r in drive(
            ServeEngine(TF_PARAMS, TF_CFG, n_slots=2, n_blocks=64,
                        block_size=4, prefix_cache=False,
                        idle_sleep_s=0.001, chaos_spec=""),
            ps, max_tokens=8)]
        eng = self._mk()
        reqs = [_Request(list(ps[0]), 8, None, tier="interactive"),
                _Request(list(ps[1]), 8, None, tier="batch")]
        for r in reqs:
            assert eng.submit(r)
        for _ in range(3000):
            if all(r.done.is_set() for r in reqs):
                break
            eng._loop_once()
        assert all(r.done.is_set() for r in reqs)
        assert [list(r.tokens) for r in reqs] == want
        st = eng.stats()
        per = st["per_tier"]
        assert st["preempted"] >= 1
        assert per["batch"]["preempted"] == st["preempted"]
        assert per["interactive"]["preempted"] == 0
        assert per["interactive"]["quarantined"] == 0
        assert st["quarantines"] == 0


class TestOffloadStorm:
    """r18 chaos points (kv.demote / kv.promote / router.block_fetch):
    the KV economy's fault contract is DEGRADE, never corrupt — a
    failed demotion is a plain eviction (the chain recomputes), a
    failed promotion is a clean tier miss (the prefix recomputes
    token-exact), a failed block fetch is a skipped migration (local
    recompute) — and none of the three can lose a request or wedge
    the engine/router."""

    SPEC = "demote:raise@p=0.4;promote:raise@p=0.3;seed=13"

    @staticmethod
    def _mk_prompt(seed):
        return [int(t) for t in np.random.default_rng(seed).integers(
            0, TF_CFG.vocab_size, 13)]

    def test_offload_points_parse_with_aliases(self):
        from tpushare.chaos import Injector
        inj = Injector.from_spec(
            "demote:raise@p=1;promote:latency@p=1,ms=1;"
            "block_fetch:raise@p=1;seed=3")
        for point in ("kv.demote", "kv.promote", "router.block_fetch"):
            assert inj.point(point) is not NOOP

    def test_offload_storm_token_exact_nothing_lost(self):
        """Thrash a tiny tiered pool so every repeat admission crosses
        demote AND promote with both points armed: every answer must
        be bit-identical to a fault-free big-pool oracle (these faults
        degrade silently — a 503 would itself be a bug)."""
        groups = [self._mk_prompt(s) for s in (1, 2)]
        fill = {s: self._mk_prompt(s) for s in range(20, 36)}
        oracle = ServeEngine(TF_PARAMS, TF_CFG, n_slots=2, n_blocks=64,
                             block_size=4, idle_sleep_s=0.001,
                             chaos_spec="")
        want = {tuple(p): list(r.tokens) for p, r in
                zip(groups, drive(oracle, groups, max_tokens=2))}

        eng = ServeEngine(TF_PARAMS, TF_CFG, n_slots=2, n_blocks=16,
                          block_size=4, max_blocks_per_slot=8,
                          idle_sleep_s=0.001, chaos_spec=self.SPEC,
                          host_kv_bytes=32 << 20)
        tier = eng._host_tier
        # Pin the crossover to "transfer" so every reclaim ATTEMPTS
        # demotion — the armed fault, not the policy, decides.
        tier.estimator.observe_transfer("d2h", 1 << 40, 1.0)
        tier.estimator.observe_transfer("h2d", 1 << 40, 1.0)
        # Sequential single-prompt rounds: group prompts re-admit
        # repeatedly with filler pressure between, so chains demote,
        # promote, fail both ways, and recompute — all seeded.
        seq = ([groups[0], groups[1]]
               + [fill[s] for s in (20, 21, 22, 23)] + [groups[0]]
               + [fill[s] for s in (24, 25, 26, 27)]
               + [groups[1], groups[0]]
               + [fill[s] for s in (28, 29, 30, 31)]
               + [groups[1], groups[0]]
               + [fill[s] for s in (32, 33, 34, 35)]
               + [groups[0], groups[1]])
        for p in seq:
            (r,) = drive(eng, [p], max_tokens=2)
            assert r.error is None, r.error
            if tuple(p) in want:
                assert list(r.tokens) == want[tuple(p)], \
                    "offload fault corrupted a decode"
        snap = tier.snapshot()
        # The storm exercised BOTH faulted seams and both survived
        # draws (seeded: stable across runs).
        assert snap["demote_failures"] > 0
        assert snap["promote_failures"] > 0
        assert snap["demotions"] > 0
        assert snap["promotions"] > 0
        # Never-started engine (synchronous drive): completion of the
        # whole sequence IS the liveness proof; the /stats invariant
        # still has to hold under the storm.
        assert eng.stats()["fetches_per_tick"] <= 1.0
        eng.stop()

    def test_block_fetch_fault_skips_migration_never_blocks(self):
        """router.block_fetch raising (or delaying, then failing on a
        dead sink) turns the migration instruction into a counted
        no-op: the route itself proceeds."""
        from tpushare.router.core import Router
        for spec in ("block_fetch:raise@p=1;seed=1",
                     "block_fetch:latency@p=1,ms=5;seed=1"):
            r = Router(["http://a:1", "http://b:2"],
                       poll_interval_s=9999, migrate_min_blocks=2,
                       chaos_spec=spec)
            a, b = r.replicas
            a.block_size = b.block_size = 8
            b.prefix_keys = {"k0", "k1"}
            r._maybe_migrate(a, ["k0", "k1"], None)
            st = r.stats()
            assert st["migrations_instructed"] == 1
            assert st["migrations_failed"] == 1
            assert st["migrated_blocks"] == 0
