"""The ``dots3-note-prev-l5-ep8`` configuration and its cell: what the
files state, what the family counts from them, and the readers of the
metrics the cell adds (on a recorded ``/stats`` delta and a recorded
list of device operations)."""

import json
import os
import re
import types

import pytest

from tpubench import spec
from tpubench.families import latent as fam
from tpubench.readers import latent_stats, select_trace, stats_delta, trace

HERE = os.path.dirname(os.path.abspath(__file__))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NAME = "dots3-note-prev-l5-ep8"
CELL = NAME + ".longdoc"


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(spec.HERE, "configs", NAME + ".json")) as f:
        return json.load(f)


def test_the_file_carries_every_published_number_but_the_three_cuts(config):
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "dots3-note-prev")
    assert config["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items()
                     if config.get(k) != v)
    assert differs == sorted(config["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert config["published"]["n_routed_experts"] == config["router_width"]
    assert set(config["reduced_why"]) == set(config["reduced"])
    assert set(config["engine_why"]) == set(config["engine"])


def test_the_cut_keeps_to_the_floors(config):
    kinds = config["layer_types"][:config["num_hidden_layers"]]
    after = kinds[config["first_k_dense_replace"]:]
    period = ["sliding_attention"] * 3 + ["full_attention"]
    assert len(after) >= 4 and sorted(after) == sorted(period)
    assert config["n_routed_experts"] >= 8
    assert config["vocab_size"] * 8 >= config["published"]["vocab_size"]
    share = config["expert_share"]
    assert share["of"] * config["n_routed_experts"] == config["router_width"]


def test_the_family_counts_the_issues_parameters(config):
    w = fam.weight_elements(config)
    # ISSUE 28's table: 356.4 + 169.0 + 347.2 M outside the routed
    # experts, 23.6 M an expert
    assert round(w["outside_experts"] / 1e6, 1) == 872.6
    assert w["one_expert"] == 3 * 5120 * 1536
    total = (w["outside_experts"] + w["head"] + 19008 * 5120
             + w["sparse_layers"] * 32 * w["one_expert"])
    assert round(total / 1e9, 3) == 4.087
    floor = fam.forward_weight_bytes(config)
    assert 2.12e9 < floor < 2.14e9
    assert floor < 2 * total


def test_program_config_reads_the_published_keys(config):
    import jax.numpy as jnp
    cfg = fam.program_config(config, jnp.bfloat16)
    assert cfg.layer_types == tuple(config["layer_types"][:5])
    assert (cfg.n_full, cfg.n_swa, cfg.n_dense, cfg.n_moe) == (2, 3, 1, 4)
    assert (cfg.n_experts, cfg.experts_held, cfg.top_k) == (256, 32, 8)
    assert (cfg.full.n_heads, cfg.swa.n_heads) == (128, 64)
    assert (cfg.full.kv_rank, cfg.swa.kv_rank, cfg.window) == (512, 1024, 513)
    # cached a token: 2 x (128 + 512 + 64) + 3 x (1,024 + 64) values as
    # published; the rows are padded to whole lane tiles on the chip
    sk, sv, sx = cfg.pool_shapes(8, 16)
    assert (sk[-1], sv[-1], sx[-1]) == (640, 1152, 128)


def test_the_cell_is_put_together_from_its_files():
    cell = spec.load_cell(CELL)
    assert cell.chips == 1 and cell.traffic["generator"] == "docqa"
    assert {"attn.select_busy_pct", "attn.keys_kept_pct",
            "cache.window_dead_pct", "moe.local_assign_per_token",
            "moe.expert_load_max_over_mean", "cache.prefix_hit_pct",
            "slot.fused_tick_pct", "forward.hbm_floor_pct"} <= set(
                cell.per_layer)
    e = cell.engine
    longest = 16384 + 96 + 256
    assert e["block_size"] * e["max_blocks_per_slot"] >= longest
    p = cell.traffic["params"]
    assert p["clients"] + p["background"]["streams"] == e["n_slots"]
    bg = p["background"]
    assert (bg["prompt_tokens"] + bg["max_tokens"]
            <= e["block_size"] * e["max_blocks_per_slot"])
    sched = spec.generator("docqa").generate(
        p, 7, cell.config["vocab_size"], window_s=40, warm_s=30,
        rate_rps=None, engine=e)
    assert len(sched["main"]) == 160 and len(sched["shapes"]) == 12
    assert max(len(v) for v in sched["pool"].values()) == 16384


def _ctx(before, after, **kw):
    return types.SimpleNamespace(stats_before=before, stats_after=after, **kw)


#: a ``/stats`` pair as the toy engine gives it (tests/test_latent.py's
#: server after 100 prompt tokens, then 60 more steps)
BEFORE = {"select_keys_seen": 10100, "select_keys_kept": 7248,
          "expert_assign_local": 300, "expert_tokens": 336,
          "expert_load": [10, 40, 20, 30, 25, 25, 50, 0, 30, 20, 10, 40]}
AFTER = {"select_keys_seen": 26020, "select_keys_kept": 13008,
         "expert_assign_local": 480, "expert_tokens": 516,
         "expert_load": [20, 70, 30, 45, 35, 40, 80, 0, 60, 30, 20, 50],
         "latent_rows_live": {"full": 320, "sliding": 320},
         "window_rows_dead": 256,
         "latent_row_bytes": {"full": 160, "sliding": 112}}


def test_the_counter_readers_on_a_recorded_stats_delta():
    ctx = _ctx(BEFORE, AFTER)
    assert stats_delta.read(ctx, "ratio_pct", num="select_keys_kept",
                            den="select_keys_seen") == pytest.approx(
                                100 * 5760 / 15920)
    assert latent_stats.read(ctx, "ratio", num="expert_assign_local",
                             den="expert_tokens") == pytest.approx(1.0)
    # deltas 10 30 10 15 10 15 30 0 30 10 10 10: max 30 over mean 15
    assert latent_stats.read(ctx, "load_max_over_mean",
                             key="expert_load") == pytest.approx(2.0)
    assert latent_stats.read(ctx, "window_dead_pct") == pytest.approx(
        100 * 256 * 112 / (320 * 160 + 320 * 112))


def test_the_counter_readers_find_nothing_in_another_programs_stats():
    ctx = _ctx({"work_ticks": 1}, {"work_ticks": 9})
    assert latent_stats.read(ctx, "ratio", num="expert_assign_local",
                             den="expert_tokens") is None
    assert latent_stats.read(ctx, "load_max_over_mean",
                             key="expert_load") is None
    assert latent_stats.read(ctx, "window_dead_pct") is None
    with pytest.raises(ValueError):
        latent_stats.read(ctx, "nonsense")


ENGINE = {"n_slots": 16, "block_size": 16, "max_blocks_per_slot": 1046,
          "n_blocks": 24576}


@pytest.fixture(scope="module")
def recorded_ops():
    """The device operations of a traced run of the cell's committed
    program on the chip (``data/v5e_longdoc_ops.json``, which says which
    run: short name, self ns summed, count),
    laid end to end as ``trace.load`` would give them."""
    with open(os.path.join(HERE, "data", "v5e_longdoc_ops.json")) as f:
        rec = json.load(f)
    ops, t = [], 0.0
    for name, ns, _ in rec["ops"]:
        ops.append((name, t, float(ns), False))
        t += ns
    return ops


#: what the selector's operations are called in that run, by hand: the
#: score product of a chunk's block of queries [32 x 64 index heads,
#: keys] and its sum over heads [32, keys]; the threshold's passes and
#: masks; a decode step's scores [16 slots, keys], their sort, the key
#: gather [keys, 16, 128] and the row gather [16 x 2,048, 640]
SELECTOR = ("convolution_maximum_fusion.7 fusion f32[2048,16384]",
            "multiply_reduce_fusion.4 fusion f32[32,12288]",
            "fusion.138 fusion u32[1024,16384]",
            "dynamic-slice_bitcast_fusion.16 fusion pred[32,16384]",
            "compare_and_fusion.4 fusion pred[32,32,16384]",
            "fusion.134 fusion f32[16,16736]",
            "sort sort (f32[16,16736]",
            "fusion.9 fusion bf16[16736,16,128]",
            "fusion.11 fusion bf16[32768,640]")
#: and what is not: attention's softmax over a chunk's block [32 x 128
#: heads, keys], its row sums and its mask by head, the gate and the
#: projections of 16 slots by 128 heads, the router's sort, the count of
#: the keys a chunk's queries kept
NOT_SELECTOR = ("divide_convert_fusion.30 fusion bf16[4096,16384]",
                "fusion.825 fusion (f32[4096]",
                "broadcast.2610 broadcast pred[32,128,16384]",
                "fusion.649 fusion (f32[16,128]",
                "fusion.817 fusion bf16[1,32,128,512]",
                "sort.8 sort (f32[1040,256]",
                "convert_reduce_fusion.17 fusion s32[1024]")


def test_the_selector_reader_on_recorded_operations(config, recorded_ops):
    pats = select_trace.patterns(config, ENGINE)
    names = {n for n, *_ in recorded_ops}
    hit = lambda n: any(p.search(n) for p in pats)
    for name in SELECTOR + NOT_SELECTOR:
        assert name in names
    assert all(hit(n) for n in SELECTOR)
    assert not any(hit(n) for n in NOT_SELECTOR)
    # attention's own scores, should the compiler one day leave them out
    # of the softmax's fusion, have the same two axes: not the selector's
    assert not hit("fusion.1 fusion f32[4096,16384]")
    assert hit("fusion.1 fusion f32[2048,16384]")
    # every match is of one of the kinds above
    kinds = {re.sub(r"^\S+ ", "", re.sub(r"\[\d+,(\d+)\]$", r"[n,\1]", n))
             for n in names if hit(n)}
    assert {k.split(" ")[0] for k in kinds} <= {
        "fusion", "sort", "copy", "iota", "slice", "reshape",
        "bitcast-convert", "broadcast", "copy-done"}
    share = select_trace.selector_share(recorded_ops, pats)
    # that run's own line read 12.16 %; 1.1 % of busy time is of
    # operations too small for the record
    assert share == pytest.approx(12.2, abs=0.2)


def test_the_selector_reader_finds_nothing_where_there_is_no_selector(config):
    """A configuration without a selector has no pattern; a run that was
    not traced has nothing to read; and of the recorded chat slice of
    the dense family (no sort, no index heads) only the shapes that
    happen to be as long as a key axis could match, which is why the
    metric lists its cells."""
    assert select_trace.patterns({"hidden_size": 4096}, ENGINE) is None
    assert select_trace.read(types.SimpleNamespace(trace=None)) is None
    path = os.path.join(HERE, "data", "v5e_chat_slice.xplane.pb")
    devs = trace.load(path)["devices"]
    names = {n for n, *_ in devs[sorted(devs)[0]]}
    assert not [n for n in names if " sort " in n]
    pats = select_trace.patterns(config, ENGINE)
    assert not [n for n in names if pats[2].search(n) or pats[3].search(n)]
