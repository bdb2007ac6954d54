"""Where the benchmark's data lives, and how a cell is put together.

``BENCHMARK.json`` names cells, configurations and metrics; everything
that belongs to one of them sits in a file found by that name:

  configs/<config>.json      widths as published, the cut, engine sizes,
                             and the ``family`` that runs it
  families/<family>.py       how the program is configured and drawn for
                             such a configuration, its plain reference
                             and that reference's limits, the bytes one
                             forward must read
  traffic/<mix>.json         generator name, its parameters, the loop
  generators/<name>.py       one general generator per file
  layers/<metric>.json       one per-layer metric: layer, reader, args
  readers/<name>.py          one reader per file
  cells/<cell>.json          optional: what only this pairing fixes
                             (an open loop's rate, engine sizes that
                             the traffic forces), each with its reason

No name is listed in code: adding a cell is adding entries and files.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import os
import re
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def _load(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def benchmark() -> Dict[str, Any]:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: Dict[str, Any]      # configs/<config>.json (rehearsal widths merged in)
    traffic: Dict[str, Any]     # traffic/<mix>.json
    engine: Dict[str, Any]      # config's engine sizes, cell overrides applied
    rate_rps: Optional[float]   # open loop only
    end_to_end: List[str]
    per_layer: List[str]
    rehearse: bool = False


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, *, rehearse: bool = False) -> Cell:
    bench = benchmark()
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"no cell {name!r} in BENCHMARK.json; cells: "
                         f"{[w['name'] for w in bench['workloads']]}")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    with open(os.path.join(ROOT, conf["file"])) as f:
        config = json.load(f)
    traffic = _load("traffic", entry["traffic"] + ".json")
    cell_path = os.path.join(HERE, "cells", name + ".json")
    cell_file = {}
    if os.path.exists(cell_path):
        with open(cell_path) as f:
            cell_file = json.load(f)
    engine = dict(config["engine"])
    engine.update({k: v for k, v in cell_file.get("engine", {}).items()
                   if k != "why"})
    if rehearse:
        # The CPU rehearsal: same family, same code path, toy widths and a
        # pool to match. Never a measurement.
        config = dict(config, **config["rehearse"]["widths"])
        engine.update(config["rehearse"]["engine"])
        engine.update(cell_file.get("rehearse", {}).get("engine", {}))
        traffic = dict(traffic, params=dict(
            traffic["params"], **traffic.get("rehearse", {})))
    e2e = [m["name"] for m in bench["end_to_end"] if _applies(m, name)]
    per = []
    for m in bench["per_layer"]:
        if _applies(m, name) and m["moves"] in e2e:
            per.append(m["name"])
    return Cell(name=name, chips=int(entry["chips"]),
                config_name=entry["config"], traffic_name=entry["traffic"],
                config=config, traffic=traffic, engine=engine,
                rate_rps=cell_file.get("rate_rps"),
                end_to_end=e2e, per_layer=per, rehearse=rehearse)


#: What ``families/<family>.py`` exposes (``families/__init__.py`` says
#: what each is); ``warm_growth(engine)`` besides, where the family's
#: cache is not the one ``system.warm_growth`` knows.
FAMILY_EXPOSES = ("program_config", "init_params", "MODEL_FAMILY",
                  "forward_with_margins", "tolerance", "HELD_POSITIONS",
                  "forward_weight_bytes")


def family(name: str):
    """The family module ``families/<name>.py``, by a configuration's
    ``family`` key."""
    if not NAME_RE.match(name):
        raise ValueError(f"bad family name {name!r}")
    return importlib.import_module(f"tpubench.families.{name}")


def generator(name: str):
    """The generator module ``generators/<name>.py``."""
    if not NAME_RE.match(name):
        raise ValueError(f"bad generator name {name!r}")
    return importlib.import_module(f"tpubench.generators.{name}")


def layer_metric(name: str) -> Dict[str, Any]:
    """``layers/<metric>.json``: layer, unit, moves, reader, args."""
    return _load("layers", name + ".json")


def reader(name: str):
    if not NAME_RE.match(name):
        raise ValueError(f"bad reader name {name!r}")
    return importlib.import_module(f"tpubench.readers.{name}")
