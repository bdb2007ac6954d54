"""A family is a file: the harness finds a configuration's program
config, reference, limits and weight bytes by the configuration's
``family`` key, and names no family itself. A third family that exists
only in the tests (``conftest.third_cell``) goes through the whole path
with no file under ``tpubench/`` edited."""

import dataclasses
import os
import re

import jax.numpy as jnp
import pytest

from tpubench import peaks, reference, spec, system


def test_a_third_family_goes_through_the_harness_with_no_edit(
        third_cell, monkeypatch):
    """``load_cell`` -> ``program_config`` -> ``build`` -> ``check_correct``
    at rehearsal widths; the check's prompt is longer than the window,
    so a reference that leaves the window out is seen."""
    cell, seen = third_cell
    assert cell.config["layer_types"] == ["sliding_attention", "full_attention"]
    window, check = (cell.config[k] for k in ("sliding_window",
                                              "check_prompt_tokens"))
    assert window < check
    cfg = system.program_config(cell)
    assert (cfg.sliding_window, cfg.alternate_sliding) == (window, True)
    assert peaks.forward_weight_bytes(cell.config) == 12345
    sut = system.build(cell, 5, lambda m: None)
    try:
        good = system.check_correct(cell, sut, 5, lambda m: None)
        assert seen == [check + 1]        # the configuration's length
        assert good["ok"] is True and good["max_held_rel_err"] < 1e-4, good
        assert good["held_positions"] == good["held"] == 2
        # the wrong reference: the dense family's, which knows no window
        monkeypatch.setattr(system.family_of(cell), "forward_with_margins",
                            spec.family("dense").forward_with_margins)
        bad = system.check_correct(cell, sut, 5, lambda m: None)
        assert bad["ok"] is False and bad["max_held_rel_err"] > 0.05, bad
        assert system.compared(bad)["max_held_rel_err"] == {
            "value": bad["max_held_rel_err"], "limit": bad["tolerance"]}
    finally:
        sut["engine"].stop()


def test_a_family_may_bring_its_own_warm_growth(third_cell, monkeypatch):
    cell, _ = third_cell
    calls = []
    monkeypatch.setattr(system, "warm_growth",
                        lambda engine: calls.append(("system", engine)))
    system.warm(cell, "engine")
    monkeypatch.setattr(system.family_of(cell), "warm_growth",
                        lambda engine: calls.append(("family", engine)),
                        raising=False)
    system.warm(cell, "engine")
    assert calls == [("system", "engine"), ("family", "engine")]


def test_a_family_that_is_no_file_is_refused():
    with pytest.raises(ImportError):
        spec.family("no_such_family")
    with pytest.raises(ValueError):
        spec.family("../reference")


FAMILY_NAME = re.compile(r"""["'](moe|dense)["']""")


def test_no_code_outside_families_names_a_family():
    """``system.py``, ``peaks.py`` and the rest reach a family through
    ``spec.family`` alone: no quoted family name to compare with, and no
    import of a family's file."""
    checked = 0
    for d, _, files in os.walk(spec.HERE):
        if os.path.basename(d) in ("families", "__pycache__"):
            continue
        for f in files:
            if not f.endswith(".py"):
                continue
            with open(os.path.join(d, f)) as fh:
                src = fh.read()
            checked += 1
            rel = os.path.relpath(os.path.join(d, f), spec.HERE)
            assert not FAMILY_NAME.search(src), rel
            assert not re.search(r"(from|import)\s+tpubench\.families", src), rel
    assert checked >= 15
    assert FAMILY_NAME.search('c["family"] == "moe"')    # the grep bites


@pytest.mark.parametrize("asked, n_tok", [
    (None, reference.CHECK_PROMPT_TOKENS),      # absent: 300
    (40, 40), (1000, 1000),                     # honoured
    (5000, 16 * 128 - 2),                       # capped by the slot
])
def test_check_prompt_tokens_is_the_configurations(asked, n_tok):
    cell = spec.load_cell("mistral7b-l16.chat")
    assert "check_prompt_tokens" not in cell.config
    assert reference.CHECK_PROMPT_TOKENS == 300
    if asked is not None:
        cell = dataclasses.replace(
            cell, config=dict(cell.config, check_prompt_tokens=asked))
    assert system.check_tokens(cell) == n_tok


def test_every_configuration_checks_at_the_length_it_states():
    """The default where a configuration states no length; where it
    states one, with its reason, that length up to what a slot holds."""
    for w in spec.benchmark()["workloads"]:
        cell = spec.load_cell(w["name"])
        e = cell.engine
        holds = e["block_size"] * (e.get("max_blocks_per_slot")
                                   or e["n_blocks"]) - 2
        asked = cell.config.get("check_prompt_tokens")
        if asked is None:
            assert system.check_tokens(cell) == reference.CHECK_PROMPT_TOKENS
        else:
            assert cell.config["check_prompt_tokens_why"].strip(), w["name"]
            assert system.check_tokens(cell) == min(asked, holds), w["name"]


def test_the_two_families_are_todays_two_branches():
    """The same config objects as before the move, from the same keys."""
    from tpushare.models.moe import MoEConfig
    from tpushare.models.transformer import TransformerConfig
    dense = system.program_config(spec.load_cell("mistral7b-l16.chat"))
    assert dense == TransformerConfig(
        vocab_size=32000, d_model=4096, n_layers=16, n_heads=32, n_kv_heads=8,
        head_dim=128, d_ff=14336, rope_base=1e6, norm_eps=1e-5, act="silu",
        tie_embeddings=False, sliding_window=None, dtype=jnp.bfloat16,
        remat=False)
    sparse = system.program_config(spec.load_cell("mixtral8x7b-l4.chat-batch"))
    assert isinstance(sparse, MoEConfig)
    assert (sparse.n_layers, sparse.n_experts, sparse.top_k, sparse.d_ff,
            sparse.d_model, sparse.dtype) == (4, 8, 2, 14336, 4096, jnp.bfloat16)
    assert spec.family("moe").forward_with_margins \
        is spec.family("dense").forward_with_margins \
        is reference.forward_with_margins
