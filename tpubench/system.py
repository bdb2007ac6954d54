"""The system under test, built as ``tpushare-serve`` builds it.

From the program this takes ``init_params``, ``ServeEngine``,
``cli.serve.serve`` and the compile-cache helper: everything
``build_engine`` adds to a preset lookup. The rest of this file is the
benchmark's own: the correctness check against the family's reference and
the host spans of the traced run. What differs from one family of model
to another is in ``families/<family>.py``, found by the configuration's
``family`` key; nothing here names one.
"""

from __future__ import annotations

import math
import time
from typing import Any, Dict, List

from tpubench import reference, spec
from tpubench.spec import Cell


def family_of(cell: Cell):
    """``families/<family>.py`` of the cell's configuration."""
    return spec.family(cell.config["family"])


def program_config(cell: Cell):
    """The program's config object, as the configuration's family builds
    it from the whole configuration file."""
    import jax.numpy as jnp
    c = cell.config
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[c["torch_dtype"]]
    return family_of(cell).program_config(c, dtype)


def build(cell: Cell, seed: int, log) -> Dict[str, Any]:
    """Weights from the seed on the device in one jitted call, then the
    engine at the cell's sizes. One chip: a cell over a mesh brings its
    placement here in the PR that measures it."""
    import jax
    e = cell.engine
    cfg = program_config(cell)
    family = family_of(cell)
    key = jax.random.PRNGKey(seed)
    t0 = time.monotonic()
    params = jax.jit(lambda k: family.init_params(k, cfg))(key)
    jax.block_until_ready(params)
    t1 = time.monotonic()
    from tpushare.cli.serve import ServeEngine
    engine = ServeEngine(
        params, cfg, model_family=family.MODEL_FAMILY, kv=e.get("kv"),
        n_slots=e["n_slots"], n_blocks=e["n_blocks"],
        block_size=e["block_size"],
        max_blocks_per_slot=e.get("max_blocks_per_slot"),
        prefill_chunk=e.get("prefill_chunk"),
        max_queue=e.get("max_queue", 64), seed=seed)
    log(f"init {t1 - t0:.1f}s (jitted, on device), engine "
        f"{time.monotonic() - t1:.1f}s")
    return {"cfg": cfg, "params": params, "engine": engine}


def check_prompt(seed: int, k: int, n: int, vocab: int) -> List[int]:
    """n token ids from the seed by a small LCG, the same on every
    machine (after chip_smoke.py's prompt_of)."""
    out, x = [], (seed * 2654435761 + k * 40503 + 1) % 2 ** 32 or 1
    for _ in range(n):
        x = (1103515245 * x + 12345) % 2 ** 31
        out.append(x % vocab)
    return out


def check_tokens(cell: Cell) -> int:
    """The check's prompt length: the configuration's own
    ``check_prompt_tokens`` (a window or a selector that 300 tokens
    would never leave or fill), else ``reference.CHECK_PROMPT_TOKENS``;
    either way no longer than a slot holds with the decoded token."""
    return min(cell.config.get("check_prompt_tokens",
                               reference.CHECK_PROMPT_TOKENS),
               cell.engine["block_size"]
               * (cell.engine.get("max_blocks_per_slot")
                  or cell.engine["n_blocks"]) - 2)


def check_correct(cell: Cell, system: Dict[str, Any], seed: int, log
                  ) -> Dict[str, Any]:
    """Admit a seeded prompt and decode one step through the engine's
    own slot server, before the engine thread starts; compare the logits
    it sampled from with the full forward of the family's reference."""
    import jax.numpy as jnp
    srv = system["engine"].srv
    family = family_of(cell)
    vocab = cell.config["vocab_size"]
    n_tok = check_tokens(cell)
    errors, margins, finite = [], [], True
    need = family.HELD_POSITIONS
    for k in range(reference.MAX_CHECK_PROMPTS):
        if sum(m >= reference.ROUTER_TIE_MARGIN for m in margins) >= need:
            break
        prompt = check_prompt(seed, k, n_tok, vocab)
        seen = []                   # the logits the server sampled from
        pick = srv._sampler.pick
        srv._sampler.pick = lambda lg: (seen.append(lg), pick(lg))[1]
        try:
            slot = srv.admit(jnp.asarray(prompt, jnp.int32))
            tok1 = int(srv.last_token[slot, 0])
            srv.step()
        finally:
            srv._sampler.pick = pick
        srv.evict(slot)
        want, margin = family.forward_with_margins(
            system["params"], prompt + [tok1], cell.config)
        for got, at in ((seen[0][0], n_tok - 1), (seen[1][slot], n_tok)):
            finite = finite and bool(jnp.isfinite(got).all())
            errors.append(reference.relative_error(got, want[at]))
            margins.append(float(margin[at]))
    out = reference.verdict(errors, margins, finite,
                            family.tolerance(cell.config), need)
    log(f"correctness vs the reference of tpubench/families/"
        f"{cell.config['family']}.py: " + repr(out))
    return out


def compared(out: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """The numbers of ``check_correct``'s verdict, each beside its limit:
    the worst held position under the family's tolerance, the worst
    excused one under TIE_TOLERANCE, and at least ``held_positions``
    held. A number that is not finite goes as text: the line stays JSON."""
    def num(v):
        return v if v is None or math.isfinite(v) else repr(v)
    return {
        "max_held_rel_err": {"value": num(out["max_held_rel_err"]),
                             "limit": out["tolerance"]},
        "max_tied_rel_err": {"value": num(out["max_tied_rel_err"]),
                             "limit": reference.TIE_TOLERANCE},
        "held": {"value": out["held"], "limit": out["held_positions"]},
    }


def warm(cell: Cell, engine) -> None:
    """The family's ``warm_growth`` where it has one, else the one
    below."""
    getattr(family_of(cell), "warm_growth", warm_growth)(engine)


def warm_growth(engine) -> None:
    """The one program of the decode path whose shape traffic decides:
    ``_grow_active`` scatters the new block ids of the k slots that cross
    a block boundary in the same tick into the block table, one small
    program per k. Which k a window meets depends on how requests
    interleave, so no request can be sent to warm them; build all of
    them here, 1..n_slots, on the server's own table (``.at[].set``
    returns a new array; the cache is not touched)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    table = getattr(engine.srv, "_inner", engine.srv).cache.block_table
    for k in range(1, table.shape[0] + 1):
        idx = np.arange(k)
        jax.block_until_ready(
            table.at[idx, idx % table.shape[1]].set(
                jnp.asarray(list(range(k)), jnp.int32)))


#: what the traced run wraps in the program, from here: the slot
#: server's dispatch entry points and the deferred token fetch. A gap
#: of the device outside all of them is "engine loop".
HOST_SPANS = ("admit", "admit_start", "admit_step", "step", "step_async")
FETCH_SPAN = "token_fetch"


def annotate(engine) -> None:
    """Host spans for the traced run only (``jax.profiler.
    TraceAnnotation`` around the slot server's entry points and
    ``PendingStep.finalize``). The program has no spans of its own yet;
    a refactor that renames these methods moves this list."""
    import jax
    from tpushare.models import serving

    def wrap(fn, name):
        def inner(*a, **kw):
            with jax.profiler.TraceAnnotation(name):
                return fn(*a, **kw)
        return inner

    # On the class, not the instance: the engine reads an instance-level
    # ``step`` as a test's override and then leaves its overlapped tick.
    cls = type(getattr(engine.srv, "_inner", engine.srv))
    for name in HOST_SPANS:
        if hasattr(cls, name):
            setattr(cls, name, wrap(getattr(cls, name), "tpubench." + name))
    serving.PendingStep.finalize = wrap(serving.PendingStep.finalize,
                                        "tpubench." + FETCH_SPAN)
