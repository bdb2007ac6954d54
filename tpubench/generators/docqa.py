"""Documents asked about several times, each time with a new question.

A request is document + question; the document's blocks are what the
prefix cache can serve, the question is always fresh. The asks of one
document are spread among those of the documents that follow it, so a
cached document has to survive other admissions before it is hit.

Document lengths come from a short list (multiples of the prefill
chunk) and question lengths from a grid: the slot server builds a model
program per distinct cached length and a handful of small ones (slices,
scatters) per distinct prompt length and chunk offset, a daemon that has
run for an hour has met them all, and a short list lets the warm-up meet
them all too: ``shapes`` asks every (document length, question length)
pair once cold and once warm. Documents take the lengths in turn,
shuffled within each round; question lengths are stratified over the
grid and answer lengths over their range. Closed loop:
``clients`` workers take the next ask from one shared queue.

``background`` streams (a long generation beside the questions) are the
harness's own device, not a tenant's traffic: they keep a decode batch
alive, so every admission goes through the fused tick, the path this
mix exists to load, and none takes the slot server's serial path, whose
programs are keyed on (document length, tokens done) and would compile
inside the window. Nothing judged is taken from them
(``metrics.TRAFFIC_PHASES``). An ask after a document's first is marked
``warm``: its first token is timed apart from a new document's.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np


def _stratified_int(rng, n: int, lo: int, hi: int) -> List[int]:
    u = (np.arange(n) + rng.random(n)) / n
    rng.shuffle(u)
    return [int(lo + x * (hi - lo + 1)) for x in np.minimum(u, 1 - 1e-9)]


def _asks(rng, tag: str, n_docs: int, params, vocab: int, pool) -> List[Dict]:
    lens = list(params["doc_tokens"])
    k = params["asks_per_doc"]
    bands = params["spread_bands"]          # one [lo, hi) per later ask
    if len(bands) != k - 1:
        raise ValueError("spread_bands needs asks_per_doc - 1 bands")
    grid = params["question_tokens"]
    q = [grid[i] for i in _stratified_int(rng, n_docs * k, 0, len(grid) - 1)]
    a = _stratified_int(rng, n_docs * k, *params["answer_tokens"])
    order, keyed = [], []
    for d in range(n_docs):
        if d % len(lens) == 0:
            order = [lens[i] for i in rng.permutation(len(lens))]
        did = f"{tag}d{d}"
        pool[did] = rng.integers(0, vocab, order[d % len(lens)]).tolist()
        for j in range(k):
            at = d if j == 0 else d + rng.uniform(*bands[j - 1])
            qid = f"{tag}q{d}.{j}"
            pool[qid] = rng.integers(0, vocab, q[d * k + j]).tolist()
            keyed.append((at, {"id": qid, "parts": [did, qid],
                               "max_tokens": a[d * k + j], "doc": d,
                               "ask": j, "warm": j > 0}))
    keyed.sort(key=lambda t: t[0])
    return [r for _, r in keyed]


def generate(params: Dict[str, Any], seed: int, vocab: int, *,
             window_s: float, warm_s: float, rate_rps: Optional[float],
             engine: Dict[str, Any]) -> Dict[str, Any]:
    main_rng, warm_rng, shape_rng = (
        np.random.default_rng([seed, k]) for k in (0, 1, 2))
    pool: Dict[str, List[int]] = {}
    main = _asks(main_rng, "m", params["n_docs_main"], params, vocab, pool)
    warm = _asks(warm_rng, "w", params["n_docs_warm"], params, vocab, pool)
    # Every pair of lengths twice: a new document (cold, chunk by
    # chunk), then the same document under a new question (warm).
    shape_reqs = []
    for i, n in enumerate(params["doc_tokens"]):
        for j, qlen in enumerate(params["question_tokens"]):
            did = f"sd{i}.{j}"
            pool[did] = shape_rng.integers(0, vocab, n).tolist()
            for k in range(2):
                qid = f"sq{i}.{j}.{k}"
                pool[qid] = shape_rng.integers(0, vocab, qlen).tolist()
                shape_reqs.append({"id": qid, "parts": [did, qid],
                                   "max_tokens": 2})
    background = []
    bg = params.get("background")
    for i in range(bg["streams"] if bg else 0):
        pid = f"bg{i}"
        pool[pid] = shape_rng.integers(0, vocab, bg["prompt_tokens"]).tolist()
        background.append({"id": pid, "parts": [pid],
                           "max_tokens": bg["max_tokens"]})
    return {"loop": "closed", "clients": params["clients"], "pool": pool,
            "shapes": shape_reqs, "background": background,
            "warm": warm, "main": main}
