"""The check that decides ``correct`` is itself checked: the plain
reference against the program's forwards at tiny widths on the CPU, and
one ``--rehearse`` run end to end."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpubench import reference, spec, system

DENSE = {"hidden_size": 64, "intermediate_size": 160, "num_attention_heads": 4,
         "num_key_value_heads": 2, "num_hidden_layers": 3, "vocab_size": 257,
         "rope_theta": 1e6, "rms_norm_eps": 1e-5}
MOE = dict(DENSE, num_local_experts=4, num_experts_per_tok=2)


def _tokens(n, vocab):
    return np.random.default_rng(0).integers(0, vocab, n).tolist()


def test_reference_agrees_with_transformer_forward():
    from tpushare.models import transformer as tf
    cfg = tf.TransformerConfig(
        vocab_size=257, d_model=64, n_layers=3, n_heads=4, n_kv_heads=2,
        head_dim=16, d_ff=160, rope_base=1e6, norm_eps=1e-5, act="silu",
        tie_embeddings=False, dtype=jnp.float32, remat=False)
    params = tf.init_params(jax.random.PRNGKey(1), cfg)
    toks = _tokens(40, 257)
    with jax.default_matmul_precision("highest"):
        want, _ = tf.forward(params, jnp.asarray([toks]), cfg,
                             attn_impl="reference")
    got = reference.forward(params, toks, DENSE)
    assert got.shape == (40, 257)
    assert reference.relative_error(got, want[0]) < 1e-5


def test_reference_agrees_with_moe_forward():
    from tpushare.models import moe
    cfg = moe.MoEConfig(
        vocab_size=257, d_model=64, n_layers=3, n_heads=4, n_kv_heads=2,
        head_dim=16, d_ff=160, n_experts=4, top_k=2, rope_base=1e6,
        norm_eps=1e-5, act="silu", tie_embeddings=False, dtype=jnp.float32,
        remat=False)
    params = moe.init_params(jax.random.PRNGKey(2), cfg)
    toks = _tokens(40, 257)
    with jax.default_matmul_precision("highest"):
        want = moe.forward(params, jnp.asarray([toks]), cfg,
                           attn_impl="reference")[0]
    got = reference.forward(params, toks, MOE)
    assert reference.relative_error(got, want[0]) < 1e-5


def test_reference_sees_a_wrong_rotary_base_and_a_dropped_expert():
    """Tight enough to fail what it must: the tolerance is not slack."""
    from tpushare.models import moe
    cfg = moe.MoEConfig(
        vocab_size=257, d_model=64, n_layers=3, n_heads=4, n_kv_heads=2,
        head_dim=16, d_ff=160, n_experts=4, top_k=2, rope_base=1e6,
        norm_eps=1e-5, tie_embeddings=False, dtype=jnp.float32, remat=False)
    params = moe.init_params(jax.random.PRNGKey(2), cfg)
    toks = _tokens(40, 257)
    good = reference.forward(params, toks, MOE)
    for wrong in (dict(MOE, rope_theta=1e4), dict(MOE, num_experts_per_tok=1)):
        bad = reference.forward(params, toks, wrong)
        assert reference.relative_error(bad, good) > spec.family("moe").TOLERANCE


INF = float("inf")


@pytest.mark.parametrize("errors, margins, finite, family, ok", [
    # every position that no router tie excuses is held, each one
    ([0.02, 0.03, 0.01, 0.05], [0.3, 0.2, 0.1, 0.09], True, "moe", True),
    ([0.02, 0.03, 0.01, 0.06], [0.3, 0.2, 0.1, 0.09], True, "moe", False),
    # a flipped expert is excused only where the reference saw the tie
    ([0.02, 0.25, 0.01, 0.03, 0.02], [0.3, 0.004, 0.1, 0.2, 0.2], True,
     "moe", True),
    ([0.02, 0.25, 0.01, 0.03, 0.02], [0.3, 0.09, 0.1, 0.2, 0.2], True,
     "moe", False),
    # an excused position still has to be a logit vector of the right scale
    ([0.02, 1.5, 0.01, 0.03, 0.02], [0.3, 0.004, 0.1, 0.2, 0.2], True,
     "moe", False),
    # too few positions held is no check
    ([0.02, 0.03, 0.01], [0.3, 0.2, 0.1], True, "moe", False),
    ([0.019, 0.019], [INF, INF], True, "dense", True),
    ([0.019, 0.021], [INF, INF], True, "dense", False),
    ([0.004, 0.005], [INF, INF], False, "dense", False),
])
def test_verdict_holds_every_position_no_router_tie_excuses(
        errors, margins, finite, family, ok):
    fam = spec.family(family)
    n_layers = 16 if family == "dense" else 4
    assert reference.verdict(
        errors, margins, finite, fam.tolerance({"num_hidden_layers": n_layers}),
        fam.HELD_POSITIONS)["ok"] is ok


def test_tolerances():
    dense, moe = spec.family("dense"), spec.family("moe")
    assert dense.tolerance({"num_hidden_layers": 16}) == pytest.approx(0.02)
    assert dense.tolerance({"num_hidden_layers": 32}) == \
        pytest.approx(0.005 * 32 ** 0.5)
    assert moe.tolerance({"num_hidden_layers": 4}) == moe.TOLERANCE == 0.055
    assert (dense.HELD_POSITIONS, moe.HELD_POSITIONS) == (2, 4)
    assert reference.ROUTER_TIE_MARGIN > 3 * 0.0226    # largest flipped seen


def test_margins_are_the_routers_own_and_infinite_for_a_dense_model():
    from tpushare.models import moe, transformer as tf
    cfg = moe.MoEConfig(
        vocab_size=257, d_model=64, n_layers=3, n_heads=4, n_kv_heads=2,
        head_dim=16, d_ff=160, n_experts=4, top_k=2, rope_base=1e6,
        norm_eps=1e-5, tie_embeddings=False, dtype=jnp.float32, remat=False)
    params = moe.init_params(jax.random.PRNGKey(2), cfg)
    toks = _tokens(40, 257)
    logits, margins = reference.forward_with_margins(params, toks, MOE)
    assert margins.shape == (40,) and bool((margins > 0).all())
    assert bool(jnp.isfinite(margins).all())
    # a router whose second and third columns are equal ties everywhere
    tied = jax.tree_util.tree_map(lambda x: x, params)
    r = tied["layers"]["router"]
    order = jnp.argsort(-(reference._rms(
        params["embed"][jnp.asarray(toks)].astype(jnp.float32),
        tied["layers"]["ln2"][0], 1e-5) @ r[0]).mean(0))
    tied["layers"] = dict(tied["layers"], router=r.at[:, :, order[2]].set(
        r[:, :, order[1]]))
    _, m2 = reference.forward_with_margins(tied, toks, MOE)
    assert float(m2.min()) < reference.ROUTER_TIE_MARGIN
    dcfg = tf.TransformerConfig(
        vocab_size=257, d_model=64, n_layers=3, n_heads=4, n_kv_heads=2,
        head_dim=16, d_ff=160, rope_base=1e6, norm_eps=1e-5, act="silu",
        tie_embeddings=False, dtype=jnp.float32, remat=False)
    dparams = tf.init_params(jax.random.PRNGKey(1), dcfg)
    _, dm = reference.forward_with_margins(dparams, toks, DENSE)
    assert bool(jnp.isinf(dm).all())


# What int8 does to the check, measured here because the tolerance's
# comment may claim only what a test shows. Toy widths, float32 weights:
# the int8 error alone, without the bf16 walk the chip adds to it.
@pytest.mark.parametrize("cell_name, mode, passes", [
    ("mistral7b-l16.chat", "plain", True),
    ("mistral7b-l16.chat", "int8_weights", False),
    # An int8 cache costs 0.0045 over two layers, under the dense bound
    # there; and int8 experts cost 0.015 to 0.028, under the sparse bound,
    # which upstream router flips already force to 0.055. Neither is seen:
    # PERF.md lists a tighter, short-prompt check as an open question.
    ("mistral7b-l16.chat", "int8_kv", True),
    ("mixtral8x7b-l4.chat-batch", "plain", True),
    ("mixtral8x7b-l4.chat-batch", "int8_weights", True),
])
def test_what_the_logits_check_sees_of_int8(cell_name, mode, passes):
    from tpushare.cli.serve import ServeEngine
    from tpushare.models import quant
    cell = spec.load_cell(cell_name, rehearse=True)
    cfg = system.program_config(cell)
    family = system.family_of(cell)
    sparse = "num_local_experts" in cell.config
    params = family.init_params(jax.random.PRNGKey(5), cfg)
    served, kw = params, {}
    if mode == "int8_kv":
        kw["kv_quant"] = True
    elif mode == "int8_weights":
        served = quant.quantize_params(params, cfg)
        kw["layers_hook"] = (quant.fused_expert_hook(cfg) if sparse
                             else quant.dequant_hook(cfg))
    e = cell.engine
    engine = ServeEngine(
        served, cfg, model_family=family.MODEL_FAMILY, kv=e.get("kv"),
        n_slots=e["n_slots"], n_blocks=e["n_blocks"],
        block_size=e["block_size"],
        max_blocks_per_slot=e.get("max_blocks_per_slot"), seed=5, **kw)
    try:
        out = system.check_correct(
            cell, {"engine": engine, "params": params}, 5, lambda m: None)
    finally:
        engine.stop()
    assert out["ok"] is passes, out
    if mode == "plain":
        assert out["max_held_rel_err"] < 1e-4
    else:
        assert out["max_held_rel_err"] > 3e-3      # int8 is no rounding error


def test_check_prompt_is_seeded():
    a = system.check_prompt(3, 0, 50, 1000)
    assert a == system.check_prompt(3, 0, 50, 1000)
    assert a != system.check_prompt(4, 0, 50, 1000)
    assert a != system.check_prompt(3, 1, 50, 1000)
    assert all(0 <= t < 1000 for t in a)


CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.mark.parametrize(
    "cell_name", [w["name"] for w in spec.benchmark()["workloads"]])
def test_rehearsal_end_to_end_prints_the_contracts_last_line(cell_name):
    """Engine, HTTP daemon, child generator, last-line JSON: the whole
    command at toy widths on the CPU, for every cell of BENCHMARK.json:
    a cell a later PR adds is rehearsed with no edit here."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-m", "tpubench.run", "--workload", cell_name,
         "--seed", "5", "--seconds", "4", "--trace", "0", "--rehearse"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert CONTRACT_KEYS <= set(line)          # the driver ignores the rest
    assert line["compiled_in_window"] >= 0
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"       # never a measurement
    cell = spec.load_cell(cell_name)
    assert set(line["metrics"]) == set(cell.end_to_end)
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    # every number compared beside its limit: the line's last key, and
    # the last lines of standard error
    assert list(line)[-1] == "compared"
    held = line["compared"]["max_held_rel_err"]
    assert 0 <= held["value"] <= held["limit"]
    assert line["compared"]["held"]["value"] >= line["compared"]["held"]["limit"]
    tail = out.stderr.strip().splitlines()[-len(line["compared"]):]
    assert [t.split()[2].rstrip(":") for t in tail] == list(line["compared"])


def test_without_rehearse_and_without_a_chip_there_is_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "tpubench.run", "--workload",
         "mistral7b-l16.chat", "--seed", "1", "--seconds", "2", "--trace", "0"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "no accelerator" in out.stderr
    assert not any(l.startswith("{") for l in out.stdout.splitlines())
