"""Multi-host serving (ISSUE 19): one ServeEngine spanning processes,
and host-loss recovery — the failure ladder's last rung.

The CPU backend cannot run cross-process computations, so the CI
correctness lane is the FORCED PROCESS VIEW: one process's forced host
devices are partitioned into logical ranks (ProcessTopology.forced_view
semantics via ServeEngine(num_processes=)), and host_event() drives a
whole rank's device range through the same chip-health / plan-reshard /
token-exact-replay machinery a real dead host would. The oracle is the
single-process unsharded engine, exactly as in test_sharded_serving —
placement (and now the process axis) must never change tokens.

The gang liaison (real TCP heartbeats, tpushare/parallel/gang.py) is
exercised against a live engine at the bottom: sever -> heartbeat
silence ages out -> poll -> host_event -> reshard across the process
boundary -> follower reconnects -> rejoin -> grow back.

Runs under XLA_FLAGS=--xla_force_host_platform_device_count=4+
(tests/conftest.py forces 8; the CI multihost-serving job forces 4).
"""

import json as _json
import time
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from tpushare.cli import serve as serve_mod
from tpushare.models import moe
from tpushare.models import transformer as tf
from tpushare.parallel import make_mesh
from tpushare.parallel.gang import GangFollower, GangLeader
from tpushare.parallel.multihost import ProcessTopology

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=4+")

TF_CFG = tf.tiny(remat=False)
TF_PARAMS = tf.init_params(jax.random.PRNGKey(0), TF_CFG)
MOE_CFG = moe.tiny(remat=False)
MOE_PARAMS = moe.init_params(jax.random.PRNGKey(0), MOE_CFG)

PROMPTS = [[5, 9, 12, 3], list(range(40, 60)), [9, 9, 2]]


def _mesh_tp():
    return make_mesh({"tp": 2}, devices=jax.devices()[:2])


def _mesh_eptp():
    return make_mesh({"tp": 2, "ep": 2}, devices=jax.devices()[:4])


def _mk_dense(mesh, n_proc=1, **kw):
    kw.setdefault("chaos_spec", "")
    return serve_mod.ServeEngine(
        TF_PARAMS, TF_CFG, n_slots=4, n_blocks=128, block_size=4,
        idle_sleep_s=0.0, prefill_chunk=8, mesh=mesh,
        num_processes=n_proc, **kw)


def _mk_moe(mesh, n_proc=1, **kw):
    kw.setdefault("chaos_spec", "")
    return serve_mod.ServeEngine(
        MOE_PARAMS, MOE_CFG, model_family="moe",
        n_slots=4, n_blocks=128, block_size=4, idle_sleep_s=0.0,
        prefill_chunk=8, mesh=mesh, num_processes=n_proc, **kw)


def _drive(eng, prompts=PROMPTS, host_kill=None, host_rejoin=False,
           max_tokens=6, limit=800):
    """Drive to completion; host_kill=(tick, rank) fires host_event
    mid-stream, host_rejoin=True revives the rank after the reshard
    lands. Returns the token streams (the oracle-comparable output)."""
    reqs = [serve_mod._Request(list(p), max_tokens, None)
            for p in prompts]
    for r in reqs:
        assert eng.submit(r)
    rejoined = False
    for i in range(limit):
        if all(r.done.is_set() for r in reqs) and (
                not host_rejoin or rejoined):
            break
        if host_kill is not None and i == host_kill[0]:
            eng.host_event(host_kill[1], False)
        if (host_rejoin and not rejoined
                and eng.stats()["reshards"] >= 1):
            eng.host_event(host_kill[1], True)
            rejoined = True
        eng._loop_once()
    assert all(r.done.is_set() for r in reqs), "engine stalled"
    assert all(r.error is None for r in reqs), [r.error for r in reqs]
    return [list(r.tokens) for r in reqs]


class TestProcessTopology:
    def test_forced_view_partitions_contiguously(self):
        topo = ProcessTopology.forced_view(2, 4)
        assert topo.num_processes == 2
        assert topo.local_device_count == 2
        assert topo.device_range(0) == range(0, 2)
        assert topo.device_range(1) == range(2, 4)
        assert topo.process_of(1) == 0 and topo.process_of(3) == 1
        assert topo.total_devices == 4

    def test_forced_view_requires_divisibility(self):
        with pytest.raises(ValueError, match="divide"):
            ProcessTopology.forced_view(3, 4)

    @pytest.mark.parametrize("kw", [
        dict(num_processes=0, process_index=0, local_device_count=1),
        dict(num_processes=2, process_index=2, local_device_count=1),
        dict(num_processes=2, process_index=-1, local_device_count=1),
        dict(num_processes=2, process_index=0, local_device_count=0),
    ])
    def test_ctor_validation(self, kw):
        with pytest.raises(ValueError):
            ProcessTopology(**kw)


class TestEngineProcessValidation:
    def test_num_processes_needs_a_mesh(self):
        with pytest.raises(ValueError, match="mesh"):
            _mk_dense(None, n_proc=2)

    def test_num_processes_must_divide_the_mesh(self):
        with pytest.raises(ValueError, match="divide"):
            serve_mod.ServeEngine(
                TF_PARAMS, TF_CFG, n_slots=2, n_blocks=32,
                block_size=4, idle_sleep_s=0.0, chaos_spec="",
                mesh=_mesh_tp(), num_processes=3)

    def test_gang_needs_two_processes(self):
        leader = GangLeader(2, heartbeat_timeout_s=1.0)
        try:
            with pytest.raises(ValueError, match="num_processes"):
                _mk_dense(_mesh_tp(), n_proc=1, gang=leader)
        finally:
            leader.close()

    def test_host_event_needs_process_awareness(self):
        eng = _mk_dense(None)
        with pytest.raises(ValueError, match="process-aware"):
            eng.host_event(0, False)

    def test_host_event_rank_bounds(self):
        eng = _mk_dense(_mesh_tp(), n_proc=2)
        with pytest.raises(ValueError, match="rank"):
            eng.host_event(2, False)


class TestMultihostBitExact:
    """The tentpole's correctness bar: a 2-process engine (dense tp
    and MoE ep x tp) emits the SAME tokens as the single-process
    unsharded oracle — the process axis is placement, and placement
    never changes tokens."""

    def test_dense_tp_two_process_matches_oracle(self):
        want = _drive(_mk_dense(None))
        eng = _mk_dense(_mesh_tp(), n_proc=2)
        assert _drive(eng) == want
        st = eng.stats()
        assert st["num_processes"] == 2
        assert st["healthy_processes"] == 2
        assert st["host_losses"] == 0

    def test_paged_moe_eptp_two_process_matches_oracle(self):
        want = _drive(_mk_moe(None))
        eng = _mk_moe(_mesh_eptp(), n_proc=2)
        assert _drive(eng) == want
        assert eng.stats()["num_processes"] == 2


class TestHostLossRecovery:
    """The ladder's last rung: a dead host shrinks the mesh ACROSS
    the process boundary through degrade-checkpoint-replay, streams
    stay token-exact, and the mesh grows back when the host returns."""

    def test_host_kill_mid_stream_token_exact(self):
        want = _drive(_mk_dense(None))
        eng = _mk_dense(_mesh_tp(), n_proc=2, max_reshards=4)
        got = _drive(eng, host_kill=(4, 1))
        assert got == want
        st = eng.stats()
        assert st["host_losses"] == 1
        assert st["reshards"] >= 1
        assert st["replayed_on_reshard"] >= 1
        assert st["degraded"] is True
        assert st["healthy_processes"] == 1

    def test_moe_eptp_host_kill_token_exact(self):
        want = _drive(_mk_moe(None))
        eng = _mk_moe(_mesh_eptp(), n_proc=2, max_reshards=4)
        got = _drive(eng, host_kill=(4, 1))
        assert got == want
        assert eng.stats()["host_losses"] == 1
        assert eng.stats()["reshards"] >= 1

    def test_grow_back_after_host_rejoin(self):
        want = _drive(_mk_dense(None))
        eng = _mk_dense(_mesh_tp(), n_proc=2, max_reshards=4)
        got = _drive(eng, host_kill=(4, 1), host_rejoin=True)
        assert got == want
        for _ in range(8):              # idle ticks to grow back
            eng._loop_once()
        st = eng.stats()
        assert st["host_rejoins"] == 1
        assert st["grow_backs"] >= 1
        assert st["mesh_shape_current"] == st["mesh_shape_configured"]
        assert st["healthy_processes"] == st["num_processes"] == 2
        assert st["degraded"] is False

    def test_repeated_loss_events_count_once(self):
        eng = _mk_dense(_mesh_tp(), n_proc=2, max_reshards=4)
        eng.host_event(1, False)
        eng.host_event(1, False)        # liaison re-verdict / retry
        assert eng.stats()["host_losses"] == 1
        eng.host_event(1, True)
        eng.host_event(1, True)
        assert eng.stats()["host_rejoins"] == 1

    def test_budget_exhausted_goes_drained_sticky(self):
        """--max-reshards exhaustion on a HOST fault is the same
        drained-sticky terminal state as a chip fault (the ladder
        shares one budget)."""
        eng = _mk_dense(_mesh_tp(), n_proc=2, max_reshards=0)
        eng.host_event(1, False)
        eng._loop_once()
        assert eng.stats()["reshards"] == 0
        assert eng._draining.is_set() and eng._drain_sticky
        assert "reshard budget exhausted" in eng.stats()["last_error"]
        assert eng.end_drain() is False

    def test_undrain_resets_host_health(self):
        eng = _mk_dense(_mesh_tp(), n_proc=2, max_reshards=4)
        _drive(eng, host_kill=(2, 1))
        eng.begin_drain()
        assert eng.end_drain() is True
        assert eng.stats()["healthy_processes"] == 2


class TestHostChaos:
    """chaos satellite: the host.loss point kills a whole (never the
    last, never its own) rank; the engine absorbs it through the same
    ladder and the storm stays token-exact."""

    def test_host_loss_chaos_storm_token_exact(self):
        want = _drive(_mk_dense(None))
        eng = _mk_dense(_mesh_tp(), n_proc=2, max_reshards=4,
                        chaos_spec="host_loss:raise@p=1;seed=1",
                        max_replays=30)
        got = _drive(eng)
        assert got == want
        st = eng.stats()
        assert st["host_losses"] >= 1
        assert st["reshards"] >= 1
        # Never the last host: rank 0 (own) survives.
        assert st["healthy_processes"] >= 1
        assert st["chaos_fired"].get("host.loss", 0) >= 1

    def test_single_process_engine_ignores_the_point(self):
        """host.loss is a PROCESS-AXIS point: without num_processes
        >= 2 there is no host domain, so an armed spec must not
        perturb the stream."""
        want = _drive(_mk_dense(None))
        eng = _mk_dense(_mesh_tp(),
                        chaos_spec="host_loss:raise@p=1;seed=1")
        assert _drive(eng) == want
        assert eng.stats()["host_losses"] == 0
        assert eng.stats()["chaos_fired"].get("host.loss", 0) == 0


class TestGangEngine:
    """The liaison x engine seam over real sockets: heartbeat silence
    becomes a host_event, the reshard crosses the process boundary,
    and the follower's reconnect grows the mesh back."""

    def test_sever_to_reshard_to_rejoin_to_grow_back(self):
        want = _drive(_mk_dense(None), max_tokens=8)
        leader = GangLeader(2, heartbeat_timeout_s=0.25)
        follower = GangFollower(f"127.0.0.1:{leader.port}", 1,
                                interval_s=0.03, fetches_fn=lambda: 7)
        eng = _mk_dense(_mesh_tp(), n_proc=2, max_reshards=4,
                        gang=leader)
        try:
            deadline = time.monotonic() + 5.0
            while (leader.seen_ranks() != [1]
                   and time.monotonic() < deadline):
                time.sleep(0.02)
            assert leader.seen_ranks() == [1]
            reqs = [serve_mod._Request(list(p), 8, None)
                    for p in PROMPTS]
            for r in reqs:
                assert eng.submit(r)
            severed = False
            for i in range(4000):
                if i == 4 and not severed:
                    leader.sever(1)
                    severed = True
                st = eng.stats()
                if (all(r.done.is_set() for r in reqs)
                        and st["host_rejoins"] >= 1):
                    break
                eng._loop_once()
                # Liaison detection is wall-clock (timeout aging), so
                # give the beats room between full-speed ticks.
                time.sleep(0.005)
            st = eng.stats()
            assert all(r.error is None for r in reqs)
            assert [list(r.tokens) for r in reqs] == want
            assert st["host_losses"] >= 1
            assert st["host_rejoins"] >= 1
            assert st["reshards"] >= 1
            for _ in range(8):
                eng._loop_once()
            st = eng.stats()
            assert st["grow_backs"] >= 1
            assert st["mesh_shape_current"] == \
                st["mesh_shape_configured"]
            # The heartbeat's fetch counter surfaced in /stats.
            assert st["gang"]["process_fetches"].get("1") == 7
            assert st["gang"]["num_processes"] == 2
        finally:
            follower.stop()
            leader.close()


class TestStatsProcessAxis:
    """Null-not-zero: process fields are null without a process-aware
    mesh; the loss counters are plain counters (0, like reshards)."""

    def test_nulls_when_unsharded(self):
        st = _mk_dense(None).stats()
        assert st["num_processes"] is None
        assert st["process_index"] is None
        assert st["healthy_processes"] is None
        assert st["gang"] is None
        assert st["host_losses"] == 0 and st["host_rejoins"] == 0

    def test_nulls_when_sharded_but_single_process(self):
        st = _mk_dense(_mesh_tp()).stats()
        assert st["num_processes"] is None
        assert st["healthy_processes"] is None

    def test_process_fields_on_a_process_mesh(self):
        st = _mk_dense(_mesh_tp(), n_proc=2).stats()
        assert st["num_processes"] == 2
        assert st["process_index"] == 0
        assert st["healthy_processes"] == 2
        assert st["gang"] is None       # forced view: no liaison


class TestMeshHostEndpoint:
    def _serve(self, eng):
        httpd = serve_mod.serve(eng, host="127.0.0.1", port=0,
                                timeout_s=10.0)
        base = f"http://127.0.0.1:{httpd.server_address[1]}"

        def post(body):
            req = urllib.request.Request(
                base + "/mesh/host", method="POST",
                data=_json.dumps(body).encode(),
                headers={"Content-Type": "application/json"})
            try:
                with urllib.request.urlopen(req, timeout=5) as r:
                    return r.status, _json.loads(r.read())
            except urllib.error.HTTPError as e:
                return e.code, _json.loads(e.read())

        return httpd, post

    def test_route_drives_the_host_ladder(self):
        eng = _mk_dense(_mesh_tp(), n_proc=2, max_reshards=4)
        httpd, post = self._serve(eng)
        try:
            code, out = post({"rank": 1, "healthy": False})
            assert code == 200
            assert out["rank"] == 1
            assert out["healthy_processes"] == 1
            assert out["num_processes"] == 2
            code, out = post({"rank": 1, "healthy": True})
            assert code == 200 and out["healthy_processes"] == 2
            assert post({"healthy": False})[0] == 400
            assert post({"rank": "x", "healthy": False})[0] == 400
            assert post({"rank": True, "healthy": False})[0] == 400
            assert post({"rank": 1, "healthy": "down"})[0] == 400
            assert post({"rank": 9, "healthy": False})[0] == 400
        finally:
            httpd.shutdown()
            eng.stop()

    def test_route_400s_without_a_process_mesh(self):
        eng = _mk_dense(None)
        httpd, post = self._serve(eng)
        try:
            code, out = post({"rank": 0, "healthy": False})
            assert code == 400
            assert "process-aware" in out["error"]
        finally:
            httpd.shutdown()
            eng.stop()


class TestCliProcessView:
    def _engine_from_argv(self, monkeypatch, *argv):
        import sys
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

    def test_process_view_builds_a_process_engine(self, monkeypatch):
        eng = self._engine_from_argv(
            monkeypatch, "--mesh", "tp=2", "--process-view", "2")
        try:
            assert eng._topo is not None
            assert eng._topo.num_processes == 2
            assert eng.stats()["num_processes"] == 2
        finally:
            eng.stop()

    def test_process_view_must_divide_the_mesh(self, monkeypatch):
        with pytest.raises(SystemExit, match="divide"):
            self._engine_from_argv(
                monkeypatch, "--mesh", "tp=2", "--process-view", "3")

    def test_process_view_conflicts_with_gang_env(self, monkeypatch):
        from tpushare.parallel import multihost
        from tpushare.plugin import const
        monkeypatch.setenv(const.ENV_COORDINATOR, "127.0.0.1:8476")
        monkeypatch.setenv(const.ENV_NUM_PROCESSES, "2")
        monkeypatch.setenv(const.ENV_PROCESS_ID, "0")
        monkeypatch.setattr(multihost, "initialize",
                            lambda *a, **kw: None)
        with pytest.raises(SystemExit, match="conflicts"):
            self._engine_from_argv(
                monkeypatch, "--mesh", "tp=2", "--process-view", "2")
