"""Independent requests with lognormal prompt and output lengths.

Every prompt's tokens are drawn anew, so no two share a block and the
prefix cache is bypassed by construction. Lengths are stratified: the n
requests of a phase take the n equal-probability strata of the
distribution, one each, jittered inside the stratum and shuffled by the
seed. Every seed therefore sends the same amount of work in another
order, which is what lets two runs agree. Arrivals in an open loop are
a Poisson process conditioned on its count: round(rate x seconds)
arrivals, uniform over the phase.
"""

from __future__ import annotations

import math
from statistics import NormalDist
from typing import Any, Dict, List, Optional

import numpy as np

_N = NormalDist()


def stratified_lognormal(rng: np.random.Generator, n: int, spec: Dict[str, Any]
                         ) -> List[int]:
    """n lengths, one from each of the n strata of lognormal(median,
    sigma), clipped to [min, max], in a seeded order."""
    u = (np.arange(n) + rng.random(n)) / n
    rng.shuffle(u)
    mu = math.log(spec["median"])
    out = []
    for x in u:
        z = _N.inv_cdf(min(max(float(x), 1e-9), 1 - 1e-9))
        v = math.exp(mu + spec["sigma"] * z)
        out.append(snap(v, spec))
    return out


def snap(v: float, spec: Dict[str, Any]) -> int:
    """To the nearest multiple of spec["grid"] (1 where there is none)
    inside [min, max]."""
    g = spec.get("grid", 1)
    lo, hi = -(-spec["min"] // g) * g, spec["max"] // g * g
    return min(max(int(round(v / g)) * g, lo), hi)


def grid_lengths(spec: Dict[str, Any]) -> List[int]:
    g = spec["grid"]
    return list(range(-(-spec["min"] // g) * g, spec["max"] + 1, g))


def _requests(rng, tag: str, n: int, params, vocab: int, pool) -> List[Dict]:
    plen = stratified_lognormal(rng, n, params["prompt"])
    olen = stratified_lognormal(rng, n, params["output"])
    reqs = []
    for i in range(n):
        pid = f"{tag}{i}"
        pool[pid] = rng.integers(0, vocab, plen[i]).tolist()
        reqs.append({"id": pid, "parts": [pid], "max_tokens": olen[i]})
    return reqs


def _arrivals(rng, n: int, t0: float, t1: float) -> List[float]:
    return sorted((t0 + (t1 - t0) * rng.random(n)).tolist())


def generate(params: Dict[str, Any], seed: int, vocab: int, *,
             window_s: float, warm_s: float, rate_rps: Optional[float],
             engine: Dict[str, Any]) -> Dict[str, Any]:
    main_rng, warm_rng, shape_rng = (
        np.random.default_rng([seed, k]) for k in (0, 1, 2))
    pool: Dict[str, List[int]] = {}
    loop = params["loop"]
    if loop == "open":
        if not rate_rps:
            raise ValueError("an open loop needs the cell's rate_rps "
                             "(tpubench/cells/<cell>.json)")
        n_main = round(rate_rps * window_s)
        n_warm = round(rate_rps * warm_s)
        clients = None
    else:
        clients = params["clients"]
        if clients == "n_slots":
            clients = engine["n_slots"]
        n_main = params["n_main"]
        n_warm = params["n_warm"]
    main = _requests(main_rng, "m", n_main, params, vocab, pool)
    warm = _requests(warm_rng, "w", n_warm, params, vocab, pool)
    if loop == "open":
        for r, t in zip(main, _arrivals(main_rng, n_main, 0.0, window_s)):
            r["due"] = t
        for r, t in zip(warm, _arrivals(warm_rng, n_warm, -warm_s, 0.0)):
            r["due"] = t
    # Every length on the grid, whatever the seed drew: set-up is the
    # same work in every run.
    lens = grid_lengths(params["prompt"])
    shape_reqs = []
    for i, s in enumerate(lens):
        pid = f"s{i}"
        pool[pid] = shape_rng.integers(0, vocab, s).tolist()
        shape_reqs.append({"id": pid, "parts": [pid], "max_tokens": 2})
    return {"loop": loop, "clients": clients, "pool": pool,
            "shapes": shape_reqs, "background": [],
            "warm": warm, "main": main}
