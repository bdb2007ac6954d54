"""The system under test, built as ``tpushare-serve`` builds it.

From the program this takes ``init_params``, ``ServeEngine``,
``cli.serve.serve`` and the compile-cache helper: everything
``build_engine`` adds to a preset lookup. The rest of this file is the
benchmark's own: the correctness check against ``reference.py`` and the
host spans of the traced run.
"""

from __future__ import annotations

import time
import types
from typing import Any, Dict, List

from tpubench import reference
from tpubench.spec import Cell


def program_config(cell: Cell):
    """The program's config object from the published keys."""
    import jax.numpy as jnp
    c = cell.config
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[c["torch_dtype"]]
    if c["family"] == "moe":
        from tpushare.models.convert import moe_config_from_hf
        hf = types.SimpleNamespace(**{k: v for k, v in c.items()
                                      if not isinstance(v, (dict, list))})
        return moe_config_from_hf(hf, dtype=dtype)
    from tpushare.models.transformer import TransformerConfig
    return TransformerConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        head_dim=c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"],
        d_ff=c["intermediate_size"], rope_base=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]), act=c["hidden_act"],
        tie_embeddings=bool(c["tie_word_embeddings"]),
        sliding_window=c.get("sliding_window"), dtype=dtype, remat=False)


def build(cell: Cell, seed: int, log) -> Dict[str, Any]:
    """Weights from the seed on the device in one jitted call, then the
    engine at the cell's sizes. One chip: a cell over a mesh brings its
    placement here in the PR that measures it."""
    import jax
    c, e = cell.config, cell.engine
    cfg = program_config(cell)
    if c["family"] == "moe":
        from tpushare.models import moe as family
    else:
        from tpushare.models import transformer as family
    key = jax.random.PRNGKey(seed)
    t0 = time.monotonic()
    params = jax.jit(lambda k: family.init_params(k, cfg))(key)
    jax.block_until_ready(params)
    t1 = time.monotonic()
    from tpushare.cli.serve import ServeEngine
    engine = ServeEngine(
        params, cfg, model_family=c["family"], kv=e.get("kv"),
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


def check_correct(cell: Cell, system: Dict[str, Any], seed: int, log
                  ) -> Dict[str, Any]:
    """Admit a seeded prompt and decode one step through the engine's
    own slot server, before the engine thread starts; compare the logits
    it sampled from with the reference's full forward."""
    import jax.numpy as jnp
    srv = system["engine"].srv
    family = cell.config["family"]
    vocab = cell.config["vocab_size"]
    n_tok = min(reference.CHECK_PROMPT_TOKENS,
                cell.engine["block_size"]
                * (cell.engine.get("max_blocks_per_slot")
                   or cell.engine["n_blocks"]) - 2)
    errors, margins, finite = [], [], True
    need = reference.HELD_POSITIONS[family]
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
        want, margin = reference.forward_with_margins(
            system["params"], prompt + [tok1], cell.config)
        for got, at in ((seen[0][0], n_tok - 1), (seen[1][slot], n_tok)):
            finite = finite and bool(jnp.isfinite(got).all())
            errors.append(reference.relative_error(got, want[at]))
            margins.append(float(margin[at]))
    out = reference.verdict(errors, margins, finite, family,
                            cell.config["num_hidden_layers"])
    log("correctness vs tpubench/reference.py: " + repr(out))
    return out


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
