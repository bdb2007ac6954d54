"""Arithmetic from client stamps to the end-to-end metrics.

Kept here, under the benchmark's paths, so every PR computes the same
number the same way. Pure Python on lists: a few hundred thousand
samples at most.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Iterable, List, Optional, Sequence


def percentile(samples: Sequence[float], q: float) -> Optional[float]:
    """The q-th percentile (0..100) by linear interpolation between the
    two nearest order statistics (the 'linear' method); None when there
    is no sample."""
    xs = sorted(samples)
    if not xs:
        return None
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


#: phases whose streams are the cell's own traffic. ``background`` and
#: ``holder`` streams are the harness's device for keeping a decode batch
#: alive; no tenant sends them, so nothing judged is taken from them.
TRAFFIC_PHASES = ("warm", "main")


def gaps_in_window(rows: Iterable[Dict[str, Any]], t_end: float
                   ) -> List[float]:
    """All gaps of the traffic's own streams whose later stamp lies in
    [0, t_end): warm-up requests still streaming in the window count,
    because their users wait for those tokens too."""
    out = []
    for r in rows:
        if r["phase"] not in TRAFFIC_PHASES:
            continue
        st = r["stamps"]
        for a, b in zip(st, st[1:]):
            if 0.0 <= b < t_end:
                out.append(b - a)
    return out


def request_ok(r: Dict[str, Any], vocab: int) -> bool:
    """Status 200, a done event, exactly max_tokens ids, each in
    [0, vocab) (the sampler's NaN guard emits -1)."""
    return (r["status"] == 200 and r["done"] and not r["error"]
            and len(r["tokens"]) == r["max_tokens"]
            and all(isinstance(t, int) and 0 <= t < vocab
                    for t in r["tokens"]))


def clock_start(r: Dict[str, Any], loop: str) -> float:
    """Where a request's clock starts: when it was due in an open loop
    (a stall then counts against every request it delayed), when it
    was sent in a closed one."""
    return r["due"] if loop == "open" else r["sent"]


def end_to_end(rows: List[Dict[str, Any]], *, loop: str, window_s: float,
               vocab: int) -> Dict[str, Any]:
    """The client's-side metrics of one window, in seconds-based units
    as BENCHMARK.json names them, plus the sample counts and the
    attempted/failed counts. Times in ``rows`` are seconds relative to
    the window's start."""
    measured = [r for r in rows if r["phase"] == "main"
                and 0.0 <= clock_start(r, loop) < window_s]
    bad = [r for r in measured if not request_ok(r, vocab)]
    # A background stream that died is a failure of the window too.
    bad += [r for r in rows if r["phase"] == "background"
            and (r["error"] or r["status"] != 200
                 or any(not (0 <= t < vocab) for t in r["tokens"]))]
    # A request is ``warm`` where the generator built its prompt on one
    # an earlier request sent (a document asked again) and cold where it
    # shares nothing: the two are different populations (a prefill
    # against a cache hit), and a median over both sits in the gap
    # between them and jumps with the mix of a window.
    ttft = {w: [(r["stamps"][0] - clock_start(r, loop)) * 1e3
                for r in measured if r["stamps"] and bool(r.get("warm")) == w]
            for w in (False, True)}
    # A cold first token over its prompt's length: what is left of the
    # spread between short and long documents is the admission rate
    # itself (and the wait behind another admission, which a median
    # over the window mostly leaves out).
    per_ktok = [(r["stamps"][0] - clock_start(r, loop)) * 1e6
                / r["prompt_tokens"]
                for r in measured if r["stamps"] and not r.get("warm")]
    gaps = [g * 1e3 for g in gaps_in_window(rows, window_s)]
    # Tokens served in the window, counted when they are produced: an
    # output token at its stamp, a prompt (computed or cached) at its
    # request's first token. Counting whole requests at their end would
    # swing with the few long ones that straddle the window's edges.
    live = [r for r in rows if r["phase"] in TRAFFIC_PHASES]
    produced = sum(1 for r in live for t in r["stamps"] if 0.0 <= t < window_s)
    served = produced + sum(r["prompt_tokens"] for r in live if r["stamps"]
                            and 0.0 <= r["stamps"][0] < window_s)
    done = [r for r in rows if r["done"] and r["t_done"] is not None
            and 0.0 <= r["t_done"] < window_s]
    late = [(r["sent"] - r["due"]) * 1e3 for r in measured
            if loop == "open" and r["sent"] is not None]
    return {
        "attempted": len(measured),
        "failed": len(bad),
        "failed_examples": [
            {k: r[k] for k in ("id", "status", "error", "done")}
            | {"n_tokens": len(r["tokens"]), "max_tokens": r["max_tokens"]}
            for r in bad[:5]],
        "values": {
            "ttft_cold_p50_ms": percentile(ttft[False], 50),
            "ttft_warm_p50_ms": percentile(ttft[True], 50),
            "ttft_p95_ms": percentile(ttft[False] + ttft[True], 95),
            "ttft_cold_ms_per_ktok_p50": percentile(per_ktok, 50),
            "itl_p50_ms": percentile(gaps, 50),
            "itl_p90_ms": percentile(gaps, 90),
            "itl_p99_ms": percentile(gaps, 99),
            "output_tok_s": produced / window_s,
            "served_tok_s": served / window_s,
            "late_p99_ms": percentile(late, 99),
        },
        "samples": {"ttft_cold": len(ttft[False]),
                    "ttft_warm": len(ttft[True]), "itl_gaps": len(gaps),
                    "completed_in_window": len(done),
                    "late": len(late)},
    }
