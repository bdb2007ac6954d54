"""BENCHMARK.json and the data files keep to the contract's limits, and
every file a cell names exists."""

import json
import os
import re

import pytest

from tpubench import spec

BENCH = spec.benchmark()
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
ONE_LINE = re.compile(r"^[^\t\n]{1,200}$")
PATH_RE = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj).*size|_dim$|_rank$|"
                   r"head_dim|expansion|experts_per_tok")


def test_top_level_keys_and_sizes():
    assert set(BENCH) == KEYS
    assert os.path.getsize(os.path.join(spec.ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= len(BENCH["paths"]) <= 16
    assert all(PATH_RE.match(p) and not p.startswith("/") and ".." not in p
               for p in BENCH["paths"])
    assert 1 <= len(BENCH["command"]) <= 32
    assert all(ONE_LINE.match(w) for w in BENCH["command"])
    rs = BENCH["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    # a full check with 24 cells has to fit into 43,200 s
    assert (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200


def test_configs():
    assert 1 <= len(BENCH["configs"]) <= 24
    names = [c["name"] for c in BENCH["configs"]]
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(names)) == len(names) and len(set(files)) == len(files)
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert spec.NAME_RE.match(c["name"]) and c["name"] in used
        assert ONE_LINE.match(c["source"]) and ONE_LINE.match(c["why"])
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        assert len(c["reduced"]) <= 16
        assert not any(WIDTH.search(k) for k in c["reduced"])
        with open(os.path.join(spec.ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["source"] == c["source"]
        assert body["reduced"] == c["reduced"]
        # a family is a file under families/ that exposes what the
        # harness asks of one, not a name on a list
        assert spec.NAME_RE.match(body["family"])
        assert os.path.exists(f"{spec.HERE}/families/{body['family']}.py")
        family = spec.family(body["family"])
        for name in spec.FAMILY_EXPOSES:
            assert hasattr(family, name), (body["family"], name)
        assert isinstance(family.HELD_POSITIONS, int)
        assert isinstance(family.MODEL_FAMILY, str)
        for k in ("n_slots", "n_blocks", "block_size", "prefill_chunk", "kv"):
            assert k in body["engine"] and k in body["engine_why"]


def test_workloads():
    cells = BENCH["workloads"]
    assert 2 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        for k in ("name", "config", "traffic"):
            assert spec.NAME_RE.match(w[k])
        assert w["chips"] in (1, 4) and ONE_LINE.match(w["why"])
        cell = spec.load_cell(w["name"])
        assert os.path.exists(f"{spec.HERE}/generators/"
                              f"{cell.traffic['generator']}.py")
        assert ONE_LINE.match(cell.traffic["who"])
        if cell.traffic["params"].get("loop") == "open":
            assert cell.rate_rps and cell.rate_rps > 0
        assert "setup_s" in cell.end_to_end and len(cell.end_to_end) >= 2
        assert cell.per_layer
        assert cell.chips == cell.config["chips"]


def test_metrics():
    e2e, per = BENCH["end_to_end"], BENCH["per_layer"]
    assert 1 <= len(e2e) <= 16 and 1 <= len(per) <= 128
    names = [m["name"] for m in e2e + per]
    assert len(set(names)) == len(names)
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in e2e + per:
        assert spec.NAME_RE.match(m["name"]) and spec.UNIT_RE.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in spec.SOURCES
        assert set(m.get("workloads", cells)) <= cells
    for m in e2e:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert "setup_s" in [m["name"] for m in e2e]
    e2e_names = {m["name"] for m in e2e}
    for m in per:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e_names and ONE_LINE.match(m["layer"])
        lm = spec.layer_metric(m["name"])
        assert (lm["layer"], lm["unit"], lm["moves"]) == \
            (m["layer"], m["unit"], m["moves"])
        assert hasattr(spec.reader(lm["reader"]), "read")
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_every_file_under_paths_is_named_from_a_names_characters():
    for p in BENCH["paths"]:
        for d, _, files in os.walk(os.path.join(spec.ROOT, p)):
            if "__pycache__" in d:
                continue
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), spec.ROOT)
                assert PATH_RE.match(rel), rel


def test_an_unknown_cell_is_refused():
    with pytest.raises(SystemExit):
        spec.load_cell("no-such.cell")


def test_unknown_device_kind_is_an_error_not_a_default():
    from tpubench import peaks
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        peaks.peaks_for("TPU v9000")


def test_forward_weight_bytes_from_shapes():
    from tpubench import peaks
    dense = spec.load_cell("mistral7b-l16.chat").config
    moe = spec.load_cell("mixtral8x7b-l4.chat-batch").config
    per_layer = 4096 * (4096 + 2 * 1024) + 4096 * 4096 + 2 * 4096
    ffn = 3 * 4096 * 14336
    head = 4096 * 32000 + 4096
    assert peaks.forward_weight_bytes(dense) == 2 * (16 * (per_layer + ffn) + head)
    assert peaks.forward_weight_bytes(moe) == 2 * (
        4 * (per_layer + 8 * ffn + 4096 * 8) + head)


def test_the_load_generator_and_the_schedule_never_import_jax():
    """The parent holds the chip; a child that touched JAX would ask for
    it too. Generators run before the parent touches JAX."""
    import subprocess
    import sys
    code = ("import sys, tpubench.loadgen, tpubench.metrics, tpubench.spec; "
            "import tpubench.generators.lognormal, tpubench.generators.docqa; "
            "assert 'jax' not in sys.modules, 'jax was imported'")
    subprocess.run([sys.executable, "-c", code], cwd=spec.ROOT, check=True,
                   timeout=120)
