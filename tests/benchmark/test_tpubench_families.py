"""A family is a file: the harness finds a configuration's program
config, reference, limits and weight bytes by the configuration's
``family`` key, and names no family itself. A third family that exists
only here goes through the whole path with no file under ``tpubench/``
edited."""

import dataclasses
import json
import os
import re
import sys
import types

import jax
import jax.numpy as jnp
import pytest

from tpubench import peaks, reference, spec, system

WINDOW = 8
CHECK_TOKENS = 48
_F32 = jnp.float32


def _windowed_attention(x, ln, wq, wk, wv, wo, *, n_heads, n_kv, theta, eps,
                        window):
    """``reference._attention_half`` with a key kept only where it lies
    within the last ``window`` positions of its query (None: global)."""
    S, _ = x.shape
    hd = wq.shape[-1] // n_heads
    h = reference._rms(x, ln, eps)
    q = (h @ wq.astype(_F32)).reshape(S, n_heads, hd)
    k = (h @ wk.astype(_F32)).reshape(S, n_kv, hd)
    v = (h @ wv.astype(_F32)).reshape(S, n_kv, hd)
    q, k = reference._rotate(q, theta), reference._rotate(k, theta)
    k = jnp.repeat(k, n_heads // n_kv, axis=1)
    v = jnp.repeat(v, n_heads // n_kv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(_F32(hd))
    qi, ki = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    keep = qi >= ki
    if window is not None:
        keep &= ki > qi - window
    p = jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v).reshape(S, n_heads * hd)
    return x + o @ wo.astype(_F32)


def _windowed_forward(params, tokens, config):
    """The third family's plain reference: the Mistral block with the
    attention of each layer as ``layer_types`` (a list) says."""
    eps = float(config["rms_norm_eps"])
    kw = dict(n_heads=config["num_attention_heads"],
              n_kv=config["num_key_value_heads"],
              theta=float(config["rope_theta"]), eps=eps)
    lay = params["layers"]
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens, jnp.int32)].astype(_F32)
        for i, kind in enumerate(config["layer_types"]):
            x = _windowed_attention(
                x, lay["ln1"][i], lay["wq"][i], lay["wk"][i], lay["wv"][i],
                lay["wo"][i], **kw,
                window=(config["sliding_window"]
                        if kind == "sliding_attention" else None))
            h = reference._rms(x, lay["ln2"][i], eps)
            x = x + reference._swiglu(h, lay["w_gate"][i], lay["w_up"][i],
                                      lay["w_down"][i])
        logits = reference._head(x, params["final_norm"], params["unembed"],
                                 eps=eps)
    return logits, jnp.full(x.shape[:1], jnp.inf, _F32)


def _third_family(seen):
    """``families/toywindow.py`` as a later PR would add it: alternating
    window and global layers, the program's Gemma-2 rule (even layers
    local). It needs the configuration's list, which the harness hands
    over whole."""
    dense = spec.family("dense")
    mod = types.ModuleType("tpubench.families.toywindow")

    def program_config(config, dtype):
        assert isinstance(config["layer_types"], list)
        assert all(kind == ("sliding_attention", "full_attention")[i % 2]
                   for i, kind in enumerate(config["layer_types"]))
        return dataclasses.replace(
            dense.program_config(config, dtype),
            sliding_window=config["sliding_window"], alternate_sliding=True)

    def forward_with_margins(params, tokens, config):
        seen.append(len(tokens))
        return _windowed_forward(params, tokens, config)

    mod.program_config = program_config
    mod.init_params = dense.init_params
    mod.MODEL_FAMILY = dense.MODEL_FAMILY
    mod.forward_with_margins = forward_with_margins
    mod.tolerance = dense.tolerance
    mod.HELD_POSITIONS = dense.HELD_POSITIONS
    mod.forward_weight_bytes = lambda config: 12345
    return mod


@pytest.fixture
def third_cell(tmp_path, monkeypatch):
    """A configuration, a cell and a family that no file under
    ``tpubench/`` knows: a configuration file in a temporary directory,
    two entries added to what ``spec.benchmark`` reads, a module put
    where ``spec.family`` looks."""
    body = dict(spec.load_cell("mistral7b-l16.chat").config)
    n = body["num_hidden_layers"]
    body.update(
        family="toywindow", sliding_window=4096,
        layer_types=["sliding_attention", "full_attention"] * (n // 2),
        check_prompt_tokens=1024,
        check_prompt_tokens_why="the check has to leave the window")
    body["rehearse"] = dict(body["rehearse"], widths=dict(
        body["rehearse"]["widths"], sliding_window=WINDOW,
        layer_types=["sliding_attention", "full_attention"],
        check_prompt_tokens=CHECK_TOKENS))
    path = tmp_path / "toywindow-l16.json"
    path.write_text(json.dumps(body))
    bench = spec.benchmark()
    bench["configs"].append({
        "name": "toywindow-l16", "source": body["source"], "file": str(path),
        "reduced": body["reduced"], "why": "a third family, in a test"})
    bench["workloads"].append({
        "name": "toywindow-l16.chat", "config": "toywindow-l16",
        "traffic": "chat", "chips": 1, "why": "a third family, in a test"})
    monkeypatch.setattr(spec, "benchmark", lambda: bench)
    seen = []
    monkeypatch.setitem(sys.modules, "tpubench.families.toywindow",
                        _third_family(seen))
    return spec.load_cell("toywindow-l16.chat", rehearse=True), seen


def test_a_third_family_goes_through_the_harness_with_no_edit(
        third_cell, monkeypatch):
    """``load_cell`` -> ``program_config`` -> ``build`` -> ``check_correct``
    at rehearsal widths; the check's prompt is longer than the window,
    so a reference that leaves the window out is seen."""
    cell, seen = third_cell
    assert cell.config["layer_types"] == ["sliding_attention", "full_attention"]
    cfg = system.program_config(cell)
    assert (cfg.sliding_window, cfg.alternate_sliding) == (WINDOW, True)
    assert peaks.forward_weight_bytes(cell.config) == 12345
    sut = system.build(cell, 5, lambda m: None)
    try:
        good = system.check_correct(cell, sut, 5, lambda m: None)
        assert seen == [CHECK_TOKENS + 1]        # the configuration's length
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


def test_no_present_configuration_sets_its_own_check_length():
    for w in spec.benchmark()["workloads"]:
        cell = spec.load_cell(w["name"])
        assert system.check_tokens(cell) == 300, w["name"]


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
