"""Fixtures that add to what ``spec.benchmark`` returns, in memory, so
that a test can show that what a later PR adds needs no edit to a file
that exists: ``third_cell``, a configuration of a family that no file
under ``tpubench/`` knows; ``roomy_benchmark``, two more cells on two
more configurations, one of them on four chips."""

import copy
import dataclasses
import json
import os
import sys
import types

import jax
import jax.numpy as jnp
import pytest

from tpubench import reference, spec

HERE = os.path.dirname(os.path.abspath(__file__))
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


#: The files of ``roomy_benchmark``'s configurations (``data/room/``,
#: under the benchmark's ``paths`` as a configuration's file has to be).
ROOM = os.path.relpath(os.path.join(HERE, "data", "room"), spec.ROOT)
#: The cells ``roomy_benchmark`` adds: (configuration, traffic, chips).
ROOM_CELLS = (("room-one-chip", "longgen", 1),
              ("room-four-chips", "chat-batch", 4))


@pytest.fixture
def roomy_benchmark(monkeypatch):
    """``BENCHMARK.json`` as a later PR that adds two configurations and
    a cell on each would leave it: a one-chip cell and a four-chip one,
    each where the contract's counts leave room for it (today both: 8
    cells, 1 on four chips), the one-chip cell also among the cells of
    the one per-layer metric that lists them all but one. Returned, and
    what ``spec.benchmark`` returns while the test runs."""
    bench = spec.benchmark()
    listed = next(m for m in bench["per_layer"]
                  if m["name"] == "device.decode_idle_ms")
    for config, traffic, chips in ROOM_CELLS:
        cells = bench["workloads"]
        fours = sum(w["chips"] == 4 for w in cells) + (chips == 4)
        if len(cells) == 24 or fours > max(1, (len(cells) + 1) // 4):
            continue
        path = os.path.join(ROOM, config + ".json")
        with open(os.path.join(spec.ROOT, path)) as f:
            body = json.load(f)
        assert body["chips"] == chips
        bench["configs"].append({
            "name": config, "source": body["source"], "file": path,
            "reduced": body["reduced"],
            "why": "a new configuration, in a test"})
        bench["workloads"].append({
            "name": f"{config}.{traffic}", "config": config,
            "traffic": traffic, "chips": chips,
            "why": "a new cell, in a test"})
        if chips == 1:
            listed["workloads"].append(f"{config}.{traffic}")
    monkeypatch.setattr(spec, "benchmark", lambda: copy.deepcopy(bench))
    return bench
