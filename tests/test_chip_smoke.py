"""chip_smoke.py's verdict logic against canned startup lines and /stats
bodies. No JAX, no daemon: what a run on the chip must refuse is decided
by pure functions, so it can be pinned here. The rehearsal that starts a
real daemon is tests/test_chip_smoke_rehearsal.py (slow tier)."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import chip_smoke as cs  # noqa: E402

TPU_BANNER = ("tpushare-serve on 127.0.0.1:43211 (dense/gemma_2b, 8 slots) "
              "platform=tpu device_kind='TPU v5 lite' devices=1 "
              "bytes_in_use=[5153960448]")
CPU_BANNER = ("tpushare-serve on 127.0.0.1:8478 (dense/tiny, 8 slots) "
              "platform=cpu device_kind='cpu' devices=1 "
              "bytes_in_use=[None]")
SEED_BANNER = "tpushare-serve on 127.0.0.1:8478 (dense/tiny, 8 slots)"


def clean_stats(**over):
    body = {"engine_errors": 0, "last_error": None, "quarantines": 0,
            "replays": 0, "engine_restarts": 0, "deadline_breaches": 0,
            "reshards": 0, "rejected": 0, "preempted": 0,
            "evict_errors": 0, "degraded": None, "fetches_per_tick": 1.0,
            "overlap_enabled": True, "chunked_admits": 1,
            "fused_ticks": 2, "prefix_hit_tokens": 576,
            "mesh_shape_current": None}
    body.update(over)
    return body


def test_banner_parse():
    b = cs.parse_banner(TPU_BANNER)
    assert b == {"port": 43211, "platform": "tpu", "kind": "TPU v5 lite",
                 "count": 1}
    assert cs.parse_banner("WARNING: something else") is None
    # The seed's startup line named no device: that is not a banner.
    assert cs.parse_banner(SEED_BANNER) is None


def test_platform_cpu_fails():
    assert cs.judge_banner(cs.parse_banner(TPU_BANNER), "tpu") == []
    fails = cs.judge_banner(cs.parse_banner(CPU_BANNER), "tpu")
    assert fails and "platform=cpu" in fails[0]
    assert cs.judge_banner(None, "tpu")          # no banner at all
    four = cs.parse_banner(TPU_BANNER.replace("devices=1", "devices=4"))
    assert cs.judge_banner(four, "tpu", want_count=1)
    assert cs.judge_banner(four, "tpu", want_count=4) == []


def test_clean_stats_pass():
    assert cs.judge_stats(clean_stats()) == []
    assert cs.judge_traffic(clean_stats()) == []
    # ``degraded`` is null on an unsharded engine and false on a healthy
    # sharded one; neither is a failure.
    assert cs.judge_stats(clean_stats(degraded=False)) == []


def test_each_recovery_counter_fails():
    for key in cs.ZERO_COUNTERS:
        fails = cs.judge_stats(clean_stats(**{key: 1}))
        assert fails == [f"/stats {key}=1"], key


def test_engine_errors_fail_with_their_message():
    fails = cs.judge_stats(clean_stats(
        engine_errors=1, last_error="Mosaic failed to compile"))
    assert any("engine_errors=1" in f for f in fails)
    assert any("Mosaic failed to compile" in f for f in fails)


def test_degraded_true_fails():
    assert cs.judge_stats(clean_stats(degraded=True)) == [
        "/stats degraded=true"]


def test_fetches_per_tick_must_hold():
    assert cs.judge_stats(clean_stats(fetches_per_tick=1.01))
    assert cs.judge_stats(clean_stats(fetches_per_tick=None))
    assert cs.judge_stats(clean_stats(fetches_per_tick=0.97)) == []


def test_mesh_must_still_be_the_configured_one():
    ok = clean_stats(degraded=False, mesh_shape_current={"tp": 4})
    assert cs.judge_stats(ok, mesh={"tp": 4}) == []
    shrunk = clean_stats(degraded=False, mesh_shape_current={"tp": 2})
    assert cs.judge_stats(shrunk, mesh={"tp": 4})


def test_traffic_must_have_reached_its_paths():
    for key in ("chunked_admits", "fused_ticks", "prefix_hit_tokens"):
        assert cs.judge_traffic(clean_stats(**{key: 0})), key
    assert cs.judge_traffic(clean_stats(overlap_enabled=False))


def test_discovery_must_agree_with_jax():
    topo = {"backend": "libtpu", "generation": "v5e",
            "chips": [{"index": 0, "hbm_bytes": 16909336064}]}
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    assert cs.judge_discovery(topo, dev, 16909336064) == []
    # A static-table 16 GiB is not what the runtime lets a tenant use.
    assert cs.judge_discovery(topo, dev, 16 << 30)
    assert cs.judge_discovery(topo, dict(dev, count=4), 16909336064)


def test_completion_verdicts():
    ok = {"id": "r", "tokens": [5, 0, 255_999], "cached_prefix": 0}
    assert cs.judge_completion("r", 200, ok, 256_128, 3) == []
    assert cs.judge_completion("r", 503, "engine error", 256_128, 3)
    assert cs.judge_completion("r", 200, {"tokens": []}, 256_128, 3)
    # -1 is what the sampler emits for a non-finite logits row.
    assert cs.judge_completion("r", 200, {"tokens": [5, -1, 7]},
                               256_128, 3)
    assert cs.judge_completion("r", 200, {"tokens": [5, 256_128, 7]},
                               256_128, 3)
    assert cs.judge_completion("r", 200, {"tokens": [5, 6]}, 256_128, 3)
    assert cs.judge_completion("r", 200, {"tokens": [5, True, 7]},
                               256_128, 3)


def test_prompts_are_seeded_and_in_vocab():
    a = cs.prompt_of(1, 300, 256_128)
    assert a == cs.prompt_of(1, 300, 256_128) != cs.prompt_of(2, 300,
                                                              256_128)
    assert len(a) == 300 and all(0 <= t < 256_128 for t in a)
    assert all(0 <= t < 512 for t in cs.prompt_of(7, 700, 512))


def test_parent_stays_off_jax_and_fails_outside_a_checkout(tmp_path):
    """Importing the script (and the helpers its parent uses) must not
    import jax; and in a directory that holds chip_smoke.py and nothing
    else of the repo it exits non-zero and prints no result."""
    code = ("import sys; sys.path.insert(0, %r); import chip_smoke; "
            "chip_smoke.tenant_env('tpu'); chip_smoke.cache_entries(); "
            "assert 'jax' not in sys.modules, 'parent touched jax'"
            % REPO)
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr[-800:]
    alone = tmp_path / "alone"
    alone.mkdir()
    with open(os.path.join(REPO, "chip_smoke.py")) as f:
        (alone / "chip_smoke.py").write_text(f.read())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=alone,
                         env=env, capture_output=True, text=True,
                         timeout=60)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "not a tpushare checkout" in out.stderr
