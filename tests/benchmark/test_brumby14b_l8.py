"""The ``brumby14b-l8`` configuration and its cell: what the files state,
what the family counts from them, the plain reference on a case small
enough to write by hand, and the readers of the metrics the cell adds
(on a recorded toy ``/stats`` pair and a toy list of device
operations)."""

import json
import os
import types

import jax.numpy as jnp
import numpy as np
import pytest

from tpubench import peaks, spec
from tpubench.families import retention as fam
from tpubench.readers import retention_stats, retention_trace
from tpubench.references import retention as ref

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NAME = "brumby14b-l8"
CELL = NAME + ".longgen"

#: the published keys, as ISSUE 32 copied them
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 5120, "intermediate_size": 17408,
    "max_position_embeddings": 32768, "max_window_layers": 40,
    "model_type": "brumby", "num_attention_heads": 40,
    "num_hidden_layers": 40, "num_key_value_heads": 8, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(spec.HERE, "configs", NAME + ".json")) as f:
        return json.load(f)


def test_the_file_carries_every_published_key_but_the_depth(config):
    differs = sorted(k for k, v in PUBLISHED.items() if config.get(k) != v)
    assert differs == config["reduced"] == ["num_hidden_layers"]
    assert config["num_hidden_layers"] == 8 >= 4        # the floor is four
    assert config["published"]["num_hidden_layers"] == 40
    assert config["torch_dtype"] == "bfloat16"
    assert config["family"] == fam.MODEL_FAMILY == "retention"
    assert set(config["reduced_why"]) == set(config["reduced"])
    assert set(config["engine_why"]) == set(config["engine"])
    for key in ("power", "gate", "normaliser", "eps", "qk_norm_and_rotary",
                "state_dtype", "feature_map", "weights", "left_out"):
        assert config["assumed"][key], key
    assert "switch-over" in config["assumed"]["left_out"]


def test_the_published_keys_are_the_catalogs(config):
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Brumby-14B-Base")
    assert config["source"] == row["source_url"]
    assert row["config"] == PUBLISHED


def test_the_family_counts_the_issues_bytes(config):
    w = fam.weight_elements(config)
    # ISSUE 32: a layer 330.3 M parameters, 0.661 GB; embedding and head
    # 1.556 B; 8 layers and the whole vocabulary 4.198 B, 8.40 GB
    matrices = (2 * 5120 * 5120 + 2 * 5120 * 1024 + 5120 * 8
                + 3 * 5120 * 17408)
    assert matrices == 330_342_400                      # the issue's 330.3 M
    # and what it left out: the gate's bias, two head norms, two norms
    assert w["layer"] == matrices + 8 + 2 * 128 + 2 * 5120
    assert round((w["embed"] + w["head"]) / 1e9, 3) == 1.556
    assert fam.parameters(config) // 10 ** 6 == 4198     # 4.198 B
    assert round(2 * fam.parameters(config) / 1e9, 2) == 8.40
    whole = dict(config, num_hidden_layers=40)
    assert round(2 * fam.parameters(whole) / 1e9, 1) == 29.5
    # a stream's state: 8,256 x 128 float32 a kv head and its normaliser,
    # 34.08 MB a layer, 273 MB at 8 layers, 4.36 GB for 16 streams
    per = fam.state_bytes_per_layer_stream(config)
    assert per == 4 * 8 * 8256 * 129 and round(per / 1e6, 2) == 34.08
    assert round(8 * per / 1e6) == 273
    assert round(16 * 8 * per / 1e9, 2) == 4.36
    # a decode tick of 16: 8 x 0.661 GB of layers and the 1.556 GB head
    # (ISSUE 32 wrote the head's 0.778 B parameters as GB: 6.06), 8.72 GB
    # of state: 56 % of the step's bytes, 19.0 ms at 819 GB/s
    weights = peaks.forward_weight_bytes(config)
    assert weights == fam.forward_weight_bytes(config)
    assert round(weights / 1e9, 2) == 6.84
    moved = 16 * 8 * 2 * per
    assert round(moved / 1e9, 2) == 8.72
    assert round(100 * moved / (moved + weights)) == 56
    assert round(1e3 * (moved + weights) / 819e9, 1) == 19.0


def test_program_config_reads_the_published_keys(config):
    cfg = fam.program_config(config, jnp.bfloat16)
    assert (cfg.d_model, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim, cfg.d_ff, cfg.vocab_size) == (
                5120, 8, 40, 8, 128, 17408, 151936)
    assert (cfg.rope_base, cfg.norm_eps, cfg.eps) == (1e6, 1e-6, ref.EPS)
    assert cfg.inner_chunk == 128
    assert cfg.prefill_chunk == config["engine"]["prefill_chunk"] == 1024
    # as laid out on the chip: 65 x 128 rows, 0.8 % over the 8,256
    assert cfg.features == 8320
    assert cfg.state_bytes(16) == 16 * 8 * 8 * 4 * 8320 * 129
    assert retention_trace.state_row_bytes(config) == cfg.state_bytes()
    toy = spec.load_cell(CELL, rehearse=True).config
    small = fam.program_config(toy, jnp.float32)
    assert (small.inner_chunk, small.prefill_chunk, small.head_dim) == (
        8, 32, 16)


def test_the_cell_is_put_together_from_its_files():
    cell = spec.load_cell(CELL)
    assert cell.chips == 1 and cell.traffic["generator"] == "lognormal"
    assert {"retention.step_roofline_pct",
            "retention.state_share_of_step_bytes_pct",
            "retention.state_live_gb", "forward.hbm_floor_pct",
            "engine.output_tok_s", "engine.served_tok_s",
            "slot.tick_ms", "device.idle_pct"} <= set(cell.per_layer)
    assert "cache.prefix_hit_pct" not in cell.per_layer
    e, p = cell.engine, cell.traffic["params"]
    assert e["n_blocks"] == e["n_slots"] * e["max_blocks_per_slot"]
    assert (e["block_size"] * e["max_blocks_per_slot"]
            >= p["prompt"]["max"] + p["output"]["max"])
    assert p["clients"] == "n_slots" and p["loop"] == "closed"
    sched = spec.generator("lognormal").generate(
        p, 7, cell.config["vocab_size"], window_s=40, warm_s=10,
        rate_rps=None, engine=e)
    assert sched["clients"] == 16 and len(sched["shapes"]) == 16
    lens = sorted(len(sched["pool"][r["id"]]) for r in sched["shapes"])
    assert lens == list(range(512, 8193, 512))
    # whole chunks of 1,024 and at most one of 512: two program widths
    assert {n % 1024 for n in lens} == {0, 512}
    assert len(sched["main"]) >= 8 * 40        # no client runs dry
    from tpubench import system
    assert system.check_tokens(cell) == 2048
    assert system.check_tokens(spec.load_cell(CELL, rehearse=True)) == 96


def test_the_reference_on_a_hand_written_three_token_case():
    """One head of two dimensions, three tokens, gates 1/2, 1/2, 1/4."""
    q = jnp.asarray([[[1.0, 0.0]], [[0.0, 2.0]], [[1.0, 1.0]]])
    k = jnp.asarray([[[1.0, 1.0]], [[2.0, 0.0]], [[0.0, 1.0]]])
    v = jnp.asarray([[[1.0, 0.0]], [[0.0, 1.0]], [[1.0, 1.0]]])
    log_g = jnp.log(jnp.asarray([[0.5], [0.5], [0.25]]))
    got = ref.retention(q, k, v, log_g, eps=0.0)
    # a[t, s] = (q_t.k_s / 2)^2 x the gates after s up to t
    #   t=0: a00 = 1/4                                 -> v0
    #   t=1: a10 = 1 x 1/2, a11 = 0                    -> v0
    #   t=2: a20 = 1 x 1/8, a21 = 1 x 1/4, a22 = 1/4   -> below
    want = np.asarray([[1.0, 0.0], [1.0, 0.0],
                       [(1 / 8 + 1 / 4) / (5 / 8), (1 / 4 + 1 / 4) / (5 / 8)]])
    np.testing.assert_allclose(got[:, 0], want, rtol=1e-6)
    # eps enters the denominator at that scale
    with_eps = ref.retention(q, k, v, log_g, eps=0.25)
    np.testing.assert_allclose(with_eps[0, 0], [0.5, 0.0], rtol=1e-6)


def test_the_references_margins_excuse_nothing():
    import jax
    from tpushare.models import retention
    cfg = retention.tiny()
    params = retention.init_params(jax.random.PRNGKey(0), cfg)
    toy = {"num_attention_heads": 4, "num_key_value_heads": 2,
           "head_dim": 16, "rms_norm_eps": 1e-6, "rope_theta": 1e4}
    logits, margins = fam.forward_with_margins(params, [1, 2, 3, 4, 5], toy)
    assert logits.shape == (5, cfg.vocab_size) and logits.dtype == jnp.float32
    assert bool(jnp.isinf(margins).all())
    assert fam.tolerance(toy) == fam.TOLERANCE and fam.HELD_POSITIONS == 2
    fam.warm_growth(object())                   # nothing to warm


def _ctx(before, after, **kw):
    cell = types.SimpleNamespace(config=TOY, name="toy.cell", rehearse=False)
    return types.SimpleNamespace(stats_before=before, stats_after=after,
                                 cell=cell, **kw)


#: the toy widths of the rehearsal, and a ``/stats`` pair as its engine
#: gives it (tests/test_retention.py's server: a slot's state over both
#: layers is 2 x 2 x 144 x 17 x 4 = 39,168 bytes)
TOY = {"family": "retention", "hidden_size": 64, "intermediate_size": 96,
       "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
       "num_hidden_layers": 2, "vocab_size": 512, "torch_dtype": "float32"}
ROW = 39168
BEFORE = {"retention_state_bytes_moved": 10 * 2 * ROW, "retention_ticks": 5,
          "model_forwards": 8, "retention_state_bytes_live": 2 * ROW}
AFTER = {"retention_state_bytes_moved": 10 * 2 * ROW + 100 * 2 * ROW * 3,
         "retention_ticks": 105, "model_forwards": 118,
         "retention_state_bytes_live": 3 * ROW}


def test_the_counter_readers_on_a_recorded_stats_pair():
    ctx = _ctx(BEFORE, AFTER, stats_samples=[
        {"retention_state_bytes_live": 3 * ROW},
        {"retention_state_bytes_live": 2 * ROW}, {"work_ticks": 3}])
    weights = fam.forward_weight_bytes(TOY)
    moved = 600 * ROW
    assert retention_stats.read(ctx, "state_share_pct") == pytest.approx(
        100 * moved / (moved + 110 * weights))
    assert retention_stats.read(ctx, "state_live_gb") == pytest.approx(
        (3 + 2 + 3) / 3 * ROW / 1e9)
    with pytest.raises(ValueError):
        retention_stats.read(ctx, "nonsense")


def test_the_readers_find_nothing_in_another_programs_stats():
    ctx = _ctx({"work_ticks": 1, "model_forwards": 1},
               {"work_ticks": 9, "model_forwards": 9}, stats_samples=[],
               window_s=40.0, trace={"busy_s": 1.0},
               peaks=peaks.PEAKS["TPU v5 lite"])
    assert retention_stats.read(ctx, "state_share_pct") is None
    assert retention_stats.read(ctx, "state_live_gb") is None
    assert retention_trace.read(ctx) is None
    ctx.trace = None
    assert retention_trace.read(ctx) is None


def test_the_roofline_reader_on_toy_device_operations():
    """The kernel's events by name among a device's operations; bytes
    from the shapes; mean active slots from the counters."""
    assert retention_trace.state_row_bytes(TOY) == ROW
    ops = [("fusion.3 fusion f32[4,64]", 0.0, 5000.0, False),
           ("retention_step.6 mosaic f32[4,2,2,16]", 5000.0, 2000.0, True),
           ("retention_step.9 mosaic f32[4,2,2,16]", 9000.0, 4000.0, True),
           ("ragged_dot.1 mosaic bf16[8,8]", 14000.0, 1000.0, True)]
    durations = retention_trace.kernel_events(ops)
    assert durations == [2000.0, 4000.0]
    # 3 slots active: per kv head S and z in and out, phi(q) of 2 heads
    # and phi(k), the value tile, 2 numerators and denominators
    per_head = (2 * 4 * (16 * 144 + 144) + 4 * (2 * 144 + 144)
                + 4 * 16 * 16 + 4 * 2 * 17)
    assert retention_trace.step_bytes(TOY, 3) == 3 * 2 * per_head
    want = 100 * (2 * 3 * 2 * per_head / 819e9) / 6e-6
    assert retention_trace.roofline_pct(durations, TOY, 3.0, 819e9) == \
        pytest.approx(want)
    assert retention_trace.roofline_pct([], TOY, 3.0, 819e9) is None
    # the counters' mean: 600 rows moved twice over 100 ticks = 3 active
    ctx = _ctx(BEFORE, AFTER, window_s=40.0, stats_samples=[])
    moved, ticks = retention_trace.around_slice(ctx)
    assert moved / (2.0 * ROW * ticks) == 3.0
    # with a sample a second, between those that enclose the slice
    # (seconds 18 and 22 of 40): 2 active there, whatever the window's
    ctx.stats_samples = [
        {"retention_state_bytes_moved": 2 * ROW * 2 * 10 * k,
         "retention_ticks": 10 * k} for k in range(1, 40)]
    moved, ticks = retention_trace.around_slice(ctx)
    assert (moved, ticks) == (2 * ROW * 2 * 40, 40)
    # at the published widths the state, twice, is all but 3 % of it
    full = spec.load_cell(CELL).config
    state = 16 * 8 * 4 * (128 * 8320 + 8320)
    assert 2 * state < retention_trace.step_bytes(full, 16) < 2.07 * state
