"""The ``openpangu-ultra-l5-ep32`` configuration and its cell: what the
files state, what the family counts from them, the readers of the
metrics the cell adds (on a recorded ``/stats`` pair and a recorded list
of device operations), the traced rehearsal of the cell, and the check
``system.check_correct`` cannot make: the multi-token-prediction
module's draft logits against the reference's.

That last one runs at toy widths here and at the published widths on the
chip, outside pytest (``tests/conftest.py`` holds JAX to the CPU):

    python -m tests.benchmark.test_openpangu_ultra_l5_ep32 --seed 7
"""

import dataclasses
import json
import os
import re
import subprocess
import sys
import types

import pytest

from tpubench import peaks, spec
from tpubench.families import mla_mtp as fam
from tpubench.readers import latent_stats, mla_stats, mla_trace, stats_delta

HERE = os.path.dirname(os.path.abspath(__file__))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NAME = "openpangu-ultra-l5-ep32"
CELL = NAME + ".longdoc"
CUTS = ["first_k_dense_replace", "n_routed_experts", "num_hidden_layers",
        "vocab_size"]

#: the published keys, as ISSUE 35 copied them from the catalog's row
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 3, "hidden_act": "silu",
    "hidden_size": 7680, "intermediate_size": 18432, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "pangu_ultra_moe",
    "moe_intermediate_size": 2048, "n_routed_experts": 256,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 128,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_theta": 25600000, "routed_scaling_factor": 2.5,
    "sandwich_norm": True, "tie_word_embeddings": False, "v_head_dim": 128,
    "vocab_size": 153600}


def load_config():
    with open(os.path.join(spec.HERE, "configs", NAME + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    return load_config()


def test_the_file_carries_every_published_key_but_the_four_cuts(config):
    differs = sorted(k for k, v in PUBLISHED.items() if config.get(k) != v)
    assert differs == sorted(config["reduced"]) == CUTS
    assert set(config["reduced_why"]) == set(config["reduced"])
    assert set(config["engine_why"]) == set(config["engine"])
    for key in CUTS:
        assert config["published"][key] == PUBLISHED[key]
    assert config["published"]["n_routed_experts"] == config["router_width"]
    for key in ("sandwich_norm", "router", "mtp_module", "rotary"):
        assert config["assumed"][key], key
    assert "32 chips" in config["deployment"]
    assert "8 slices" in config["deployment"]
    assert "check_prompt_tokens" not in config
    assert config["family"] == "mla_mtp" and fam.MODEL_FAMILY == "latent"


def test_the_published_keys_are_the_catalogs(config):
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "openPangu-Ultra-MoE-718B")
    assert config["source"] == row["source_url"]
    assert row["config"] == PUBLISHED
    entry = next(c for c in spec.benchmark()["configs"] if c["name"] == NAME)
    assert entry["source"] == row["source_url"]
    assert sorted(entry["reduced"]) == CUTS


def test_the_cut_keeps_to_the_floors(config):
    after = config["num_hidden_layers"] - config["first_k_dense_replace"]
    assert config["first_k_dense_replace"] == 1 and after == 4
    assert config["n_routed_experts"] == 8
    assert config["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    share = config["expert_share"]
    assert share["of"] * config["n_routed_experts"] == config["router_width"]
    assert config["num_nextn_predict_layers"] == 1      # the module whole
    # no width differs from the source
    widths = [k for k in PUBLISHED if k.endswith(("_dim", "_rank", "_size"))
              and k != "vocab_size"] + ["num_experts_per_tok"]
    assert all(config[k] == PUBLISHED[k] for k in widths)


def test_the_family_counts_the_issues_bytes(config):
    w = fam.weight_elements(config)
    M = lambda n: round(n / 1e6, 1)
    # ISSUE 35: attention 196.6 M a layer (11.8 + 37.7 + 4.4 + 16.8 + 125.8)
    assert [M(n) for n in (7680 * 1536, 1536 * 128 * 192, 7680 * 576,
                           512 * 128 * 256, 16384 * 7680)] == [
                               11.8, 37.7, 4.4, 16.8, 125.8]
    assert M(w["attention"]) == 196.6
    assert M(w["one_expert"]) == 47.2 and M(w["sparse_outside"]) == 49.2
    sparse = w["attention"] + w["sparse_outside"] + 8 * w["one_expert"]
    assert round(sparse / 1e6) == 623 and round(2 * sparse / 1e9, 2) == 1.25
    dense = w["attention"] + w["dense_ffn"]
    assert round(dense / 1e6) == 621 and round(2 * dense / 1e9, 2) == 1.24
    assert round((w["embed"] + w["head"]) / 1e6) == 295
    module = w["module_own"] + sparse
    assert round(module / 1e6) == 741 and round(2 * module / 1e9, 2) == 1.48
    assert fam.parameters(config) == (4 * sparse + dense + module
                                      + w["embed"] + w["head"])
    assert round(2 * fam.parameters(config) / 1e9, 1) == 8.3
    # 16 experts a chip, the count ISSUE 35 turned down: 12.1 GB
    sixteen = dict(config, n_routed_experts=16)
    assert round(2 * fam.parameters(sixteen) / 1e9, 1) == 12.1
    # the cache: 6 rows of 640 a token, 7,680 bytes; the pool 3.5 GB
    assert fam.cached_bytes_per_token(config) == 6 * 640 * 2 == 7680
    e = config["engine"]
    pool = e["n_blocks"] * e["block_size"] * fam.cached_bytes_per_token(config)
    assert e["n_blocks"] * e["block_size"] == 458752
    assert round(pool / 1e9, 1) == 3.5
    total = 2 * fam.parameters(config) + pool
    assert round(total / 1e9, 1) == 11.8 and 0.68 < total / 16.9e9 < 0.72
    # what one step must read at the least: below the whole, above a half
    floor = peaks.forward_weight_bytes(config)
    assert floor == fam.forward_weight_bytes(config)
    assert round(floor / 1e9, 2) == 4.70 < 2 * fam.parameters(config) / 1e9


def test_program_config_and_the_programs_weights_are_the_files(config):
    import jax
    import jax.numpy as jnp
    cfg = fam.program_config(config, jnp.bfloat16)
    assert (cfg.n_layers, cfg.n_full, cfg.n_swa, cfg.n_dense, cfg.n_moe,
            cfg.n_mtp) == (5, 5, 0, 1, 4, 1)
    assert (cfg.n_experts, cfg.experts_held, cfg.top_k) == (256, 8, 8)
    assert (cfg.full.n_heads, cfg.full.q_rank, cfg.full.kv_rank) == (
        128, 1536, 512)
    assert (cfg.selector, cfg.gate, cfg.qkv_rescale, cfg.router_bias) == (
        False,) * 4
    assert cfg.sandwich_norm and cfg.routed_scale == 2.5
    sk, sv, sx = cfg.pool_shapes(8, 16)
    assert sk == (6, 8, 16, 640) and sv[0] == 0 and sx is None
    shapes = jax.eval_shape(lambda k: fam.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree.leaves(shapes))
    assert n == fam.parameters(config)
    toy = spec.load_cell(CELL, rehearse=True).config
    small = fam.program_config(toy, jnp.float32)
    assert (small.n_layers, small.n_dense, small.n_mtp,
            small.experts_held, small.n_experts) == (2, 1, 1, 8, 32)


def test_the_cell_is_put_together_from_its_files():
    cell = spec.load_cell(CELL)
    assert cell.chips == 1 and cell.traffic_name == "longdoc"
    assert {"mtp.accept_pct", "mtp.tokens_per_round",
            "mla.cache_share_of_step_bytes_pct", "mla.attend_busy_pct",
            "engine.ttft_cold_ms_per_ktok_p50", "engine.ttft_warm_p50_ms",
            "engine.itl_p90_ms", "engine.output_tok_s",
            "engine.served_tok_s", "slot.fused_tick_pct",
            "cache.prefix_hit_pct", "forward.hbm_floor_pct",
            "moe.local_assign_per_token", "moe.expert_load_max_over_mean",
            "device.idle_pct", "engine.ahead_tick_pct"} <= set(cell.per_layer)
    assert not {"attn.select_busy_pct", "attn.keys_kept_pct",
                "cache.window_dead_pct"} & set(cell.per_layer)
    bench = spec.benchmark()
    assert len(bench["workloads"]) == 6
    assert all(w["chips"] == 1 for w in bench["workloads"])
    e = cell.engine
    assert e["block_size"] * e["max_blocks_per_slot"] >= 16384 + 96 + 256
    p = cell.traffic["params"]
    assert p["clients"] + p["background"]["streams"] == e["n_slots"]


def _ctx(before, after, **kw):
    cell = types.SimpleNamespace(config=load_config())
    return types.SimpleNamespace(stats_before=before, stats_after=after,
                                 cell=cell, **kw)


#: a ``/stats`` pair as a drafting engine gives it
BEFORE = {"mtp_rounds": 100, "mtp_proposed": 1400, "mtp_accepted": 2,
          "mtp_emitted": 1402, "latent_rows_read": 10_000_000,
          "model_forwards": 140}
AFTER = {"mtp_rounds": 1100, "mtp_proposed": 16400, "mtp_accepted": 5,
         "mtp_emitted": 16405, "latent_rows_read": 1_090_000_000,
         "model_forwards": 1140,
         "latent_row_bytes": {"full": 1280, "sliding": 1280}}


def test_the_counter_readers_on_a_recorded_stats_delta():
    ctx = _ctx(BEFORE, AFTER)
    assert stats_delta.read(ctx, "ratio_pct", num="mtp_accepted",
                            den="mtp_proposed") == pytest.approx(
                                100 * 3 / 15000)
    assert latent_stats.read(ctx, "ratio", num="mtp_emitted",
                             den="mtp_proposed") == pytest.approx(
                                 15003 / 15000)
    moved = 1_080_000_000 * 1280
    weights = 1000 * fam.forward_weight_bytes(load_config())
    assert mla_stats.read(ctx, "cache_share_pct") == pytest.approx(
        100 * moved / (moved + weights))
    # the layer files name these readers and arguments
    for name, want in (("mtp.accept_pct", 100 * 3 / 15000),
                       ("mtp.tokens_per_round", 15003 / 15000),
                       ("mla.cache_share_of_step_bytes_pct",
                        100 * moved / (moved + weights))):
        lm = spec.layer_metric(name)
        assert spec.reader(lm["reader"]).read(
            ctx, **lm["args"]) == pytest.approx(want)


def test_the_counter_readers_find_nothing_in_another_programs_stats():
    ctx = _ctx({"work_ticks": 1, "model_forwards": 2},
               {"work_ticks": 9, "model_forwards": 7})
    assert mla_stats.read(ctx, "cache_share_pct") is None
    assert stats_delta.read(ctx, "ratio_pct", num="mtp_accepted",
                            den="mtp_proposed") is None
    assert latent_stats.read(ctx, "ratio", num="mtp_emitted",
                             den="mtp_proposed") is None
    with pytest.raises(ValueError):
        mla_stats.read(ctx, "nonsense")


ENGINE = {"n_slots": 16, "block_size": 16, "max_blocks_per_slot": 1046,
          "n_blocks": 28672}
RECORD = os.path.join(HERE, "data", "v5e_longdoc_mla_ops.json")


@pytest.fixture(scope="module")
def recorded():
    """The device operations of a traced run of the cell's committed
    program on the chip (``data/v5e_longdoc_mla_ops.json``, which says
    which run: short name, self ns summed, count), laid end to end as
    ``trace.load`` would give them."""
    with open(RECORD) as f:
        rec = json.load(f)
    ops, t = [], 0.0
    for name, ns, _ in rec["ops"]:
        ops.append((name, t, float(ns), False))
        t += ns
    return rec, ops


def test_the_attention_reader_on_recorded_operations(config, recorded):
    rec, ops = recorded
    pats = mla_trace.patterns(config, ENGINE)
    names = {n for n, *_ in ops}
    hit = lambda n: any(p.search(n) for p in pats)
    for name in rec["attend"] + rec["not_attend"]:
        assert name in names, name
    assert all(hit(n) for n in rec["attend"])
    assert not any(hit(n) for n in rec["not_attend"])
    # no match is a result [tokens, ..]: 1,024 chunk tokens, 1,056 with
    # the 32 decode rows, 8,448 assignments of them to experts
    assert not [n for n in names if hit(n)
                and re.search(r"\[(1024|1056|8448),", n)]
    kinds = {n.split(" ")[1] for n in names if hit(n)}
    assert kinds <= {"fusion", "copy", "broadcast", "slice", "bitcast"}
    # the dots3 cell's recorded selector operations are none of these
    with open(os.path.join(HERE, "data", "v5e_longdoc_ops.json")) as f:
        dots = {n for n, *_ in json.load(f)["ops"]}
    assert not [n for n in dots if " sort " in n and hit(n)]
    share = mla_trace.attend_share(ops, pats)
    assert share == pytest.approx(rec["attend_busy_pct"], abs=0.3)
    assert 0 < share < 100


def test_the_attention_reader_finds_nothing_where_a_selector_attends(config):
    with open(os.path.join(spec.HERE, "configs",
                           "dots3-note-prev-l5-ep8.json")) as f:
        dots = json.load(f)
    assert mla_trace.patterns(dots, ENGINE) is None
    assert mla_trace.patterns({"hidden_size": 4096}, ENGINE) is None
    assert mla_trace.read(types.SimpleNamespace(trace=None)) is None
    # and the selector's reader nothing here
    from tpubench.readers import select_trace
    assert select_trace.patterns(config, ENGINE) is None


def test_the_traced_rehearsal_reports_the_cells_own_counters():
    """The whole command at toy widths on the CPU with ``--trace 1``: the
    three counter metrics read a number off the drafting engine's
    ``/stats`` (the fourth reads the device's line of a chip's trace)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-m", "tpubench.run", "--workload", CELL,
         "--seed", "2147483659", "--seconds", "5", "--trace", "1",
         "--rehearse"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    m = line["metrics"]
    assert 0 <= m["mtp.accept_pct"]["value"] < 50
    assert 1.0 <= m["mtp.tokens_per_round"]["value"] < 1.5
    assert 0 < m["mla.cache_share_of_step_bytes_pct"]["value"] < 100
    assert m["engine.ahead_tick_pct"]["value"] == 0
    assert "mla.attend_busy_pct" not in m       # no device line on a CPU


# ---------------------------------------------------------------------------
# The module's draft logits against the reference's.
# ---------------------------------------------------------------------------


def module_against_reference(config, engine, seed: int, n_prompt: int,
                             dtype_name: str, rounds: int = 2):
    """Through ``ServeEngine``'s own slot server, as ``system.check_correct``
    drives it: admit a seeded prompt, run ``rounds`` drafting steps, and
    compare, a round, the logits the module drafted from (the program's
    ``DraftLog`` tap) and those the round's first token was taken from
    with ``references/mla_mtp.forward_all``. Returns a dict a round."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from tpubench import reference, system
    from tpubench.references import mla_mtp as ref
    from tpushare.cli.serve import ServeEngine
    from tpushare.models.latent import DraftLog
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[dtype_name]
    cfg = dataclasses.replace(fam.program_config(config, dtype),
                              draft_log=DraftLog())
    params = jax.jit(lambda k: fam.init_params(k, cfg))(
        jax.random.PRNGKey(seed))
    eng = ServeEngine(
        params, cfg, model_family=fam.MODEL_FAMILY,
        n_slots=engine["n_slots"], n_blocks=engine["n_blocks"],
        block_size=engine["block_size"],
        max_blocks_per_slot=engine.get("max_blocks_per_slot"),
        prefill_chunk=engine.get("prefill_chunk"), seed=seed)
    try:
        srv = eng.srv
        seen = []
        pick = srv._sampler.pick
        srv._sampler.pick = lambda lg: (seen.append(lg), pick(lg))[1]
        prompt = system.check_prompt(seed, 0, n_prompt, config["vocab_size"])
        slot = srv.admit(jnp.asarray(prompt, jnp.int32))
        toks = [int(srv.last_token[slot, 0])]
        taken = []
        for _ in range(rounds):
            before = len(toks)
            toks += srv.step()[slot]
            taken.append((n_prompt + before - 1,
                          np.asarray(srv.cfg.draft_log.step[2][slot]),
                          np.asarray(seen[-1][slot])))
        srv.evict(slot)
    finally:
        eng.stop()
    want = ref.forward_all(params, prompt + toks, config)
    return [{"position": at,
             "module_rel_err": reference.relative_error(
                 dl, want["mtp_logits"][at - 1]),
             "module_margin": float(want["mtp_margins"][at - 1]),
             "main_rel_err": reference.relative_error(
                 lg, want["logits"][at]),
             "main_margin": float(want["margins"][at])}
            for at, dl, lg in taken]


def test_the_modules_draft_logits_agree_with_the_reference_at_toy_widths():
    cell = spec.load_cell(CELL, rehearse=True)
    out = module_against_reference(cell.config, cell.engine, seed=11,
                                   n_prompt=90, dtype_name="float32",
                                   rounds=3)
    assert [r["position"] for r in out][0] == 90
    # float32 on both sides: the order of the sums is what is left
    assert max(r["module_rel_err"] for r in out) < 2e-5
    assert max(r["main_rel_err"] for r in out) < 2e-5


def main(argv=None) -> int:
    """On the chip, at the published widths: the last line is
    ``MODULE {...}`` and the exit code says whether every position no
    router tie excuses is within the family's tolerance."""
    import argparse
    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args(argv)
    import jax
    from tpubench import reference, system
    if not a.rehearse and jax.devices()[0].platform != "tpu":
        print("no accelerator; no result", file=sys.stderr)
        return 4
    cell = spec.load_cell(CELL, rehearse=a.rehearse)
    out = module_against_reference(
        cell.config, cell.engine, a.seed, system.check_tokens(cell),
        cell.config["torch_dtype"])
    tol = fam.tolerance(cell.config)
    held = [r["module_rel_err"] for r in out
            if r["module_margin"] >= reference.ROUTER_TIE_MARGIN]
    ok = all(e <= tol for e in held) and all(
        r["module_rel_err"] <= reference.TIE_TOLERANCE for r in out)
    print("MODULE " + json.dumps({
        "ok": ok, "tolerance": tol, "seed": a.seed,
        "device": jax.devices()[0].device_kind, "rounds": out}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
