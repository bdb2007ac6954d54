"""``python -m tpubench.control --workload <cell> --seed <n> --precision
float8_e4m3fn``: the control of a cell's correctness check. The same
check (``system.check_correct``: the engine's own slot server against the
family's reference on the weights the configuration states), but the
engine serves its weights rounded to a LOWER precision than the file
states (every leaf of two or more axes, its mantissa cut to the lower
precision's in place, ``lax.reduce_precision``). The check
has to fail by one of its limits, or it could not tell the stated
precision from a cheaper one. Two trees of weights do not fit one chip,
so the control runs the check's two halves apart: the rounded engine
answers ``--prompts`` seeded prompts (``system.check_prompt``, the
check's own) and is torn down; the stated weights are drawn again from
the seed and the family's reference judges what was answered, by
``reference.verdict`` and the family's own limits. ``--both`` does the
same with the unrounded engine first. Where the family's check reads
what the engine selected (``program_selection``), that is taken with
each answer, and every answer is also judged against the reference's own
selection (``own_selection``): the witness of what the selection's edge
costs a comparison of logits alone. No window, no traffic, no result
line: the last line is ``CONTROL {...}``."""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys


def answers(cell, sut, seed: int, n_prompts: int):
    """What ``system.check_correct`` takes from the engine, for the
    first ``n_prompts`` seeded prompts: (prompt + first token, the logits
    the prompt's last position was sampled from, those of one decode
    step, the engine's selection where the family reads one), on the
    host."""
    import jax.numpy as jnp
    import numpy as np
    from tpubench import system
    srv = sut["engine"].srv
    n_tok = system.check_tokens(cell)
    selection = getattr(system.family_of(cell), "program_selection",
                        lambda tokens: None)
    out = []
    for k in range(n_prompts):
        prompt = system.check_prompt(seed, k, n_tok, cell.config["vocab_size"])
        seen = []
        pick = srv._sampler.pick
        srv._sampler.pick = lambda lg: (seen.append(lg), pick(lg))[1]
        try:
            slot = srv.admit(jnp.asarray(prompt, jnp.int32))
            tok1 = int(srv.last_token[slot, 0])
            srv.step()
        finally:
            srv._sampler.pick = pick
        srv.evict(slot)
        out.append((prompt + [tok1], np.asarray(seen[0][0]),
                    np.asarray(seen[1][slot]), selection(prompt + [tok1])))
    return out


def judge(cell, params, taken, own_selection: bool = False):
    """``reference.verdict`` on ``answers`` against the family's
    reference on ``params``; ``own_selection``: against the reference's
    own selection, whatever the engine's was."""
    import numpy as np
    from tpubench import reference, system
    family = system.family_of(cell)
    errors, margins, finite, selections = [], [], True, []
    for tokens, first, second, kept in taken:
        kw = ({"kept": None if own_selection else kept}
              if hasattr(family, "program_selection") else {})
        want, margin = family.forward_with_margins(params, tokens,
                                                   cell.config, **kw)
        selections.append(getattr(family, "LAST_REPORT", None))
        n = len(tokens) - 1
        for got, at in ((first, n - 1), (second, n)):
            finite = finite and bool(np.isfinite(got).all())
            errors.append(reference.relative_error(got, want[at]))
            margins.append(float(margin[at]))
    v = reference.verdict(errors, margins, finite,
                          family.tolerance(cell.config),
                          family.HELD_POSITIONS)
    v["selection"] = selections
    return v


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--precision", default="float8_e4m3fn")
    ap.add_argument("--prompts", type=int, default=3)
    ap.add_argument("--both", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--band", type=float,
                    help="judge with this SELECT_BAND, not the family's own "
                         "(1e9 takes every disagreement: what a band "
                         "would do is read off the farthest)")
    a = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    from tpubench import spec, system
    from tpubench.run import log
    cell = spec.load_cell(a.workload, rehearse=a.rehearse)
    if not a.rehearse and jax.devices()[0].platform != "tpu":
        print("tpubench.control: no accelerator; no result", file=sys.stderr)
        return 4
    # the precision's mantissa at the weights' own exponent range, as a
    # scaled fp8 tensor keeps it: unscaled, weights of 1/sqrt(5120) lie
    # under e4m3's least normal number and the control would fail for
    # its range, not its precision
    bits = (8, jnp.finfo(jnp.dtype(a.precision)).nmant)
    family = system.family_of(cell)
    if a.band is not None:
        family.SELECT_BAND = a.band
    cfg = system.program_config(cell)
    out = {}
    for name in (["stated"] if a.both else []) + [a.precision]:
        sut = system.build(cell, a.seed, log)
        if name != "stated":
            sut["engine"].srv.params = jax.jit(
                # reduce_precision, not a pair of converts: on the chip
                # XLA removes a narrowing convert that is widened again
                # (excess precision is allowed), and the "rounded" engine
                # then answers digit for digit as the stated one (my chip
                # runs, PR 28, c3 and c4)
                lambda t: jax.tree.map(
                    lambda w: (jax.lax.reduce_precision(
                        w, exponent_bits=bits[0], mantissa_bits=bits[1])
                        if w.ndim >= 2 else w), t),
                donate_argnums=0)(sut.pop("params"))
        try:
            taken = answers(cell, sut, a.seed, a.prompts)
        finally:
            sut["engine"].stop()
        del sut
        gc.collect()
        params = jax.jit(lambda k: family.init_params(k, cfg))(
            jax.random.PRNGKey(a.seed))
        keys = ("ok", "max_held_rel_err", "tolerance", "held", "router_ties",
                "max_tied_rel_err", "rel_errs", "router_margins", "selection")
        v = judge(cell, params, taken)
        log(f"{name}: {v}")
        out[name] = {k: v[k] for k in keys}
        if taken[0][3] is not None:
            v = judge(cell, params, taken, own_selection=True)
            log(f"{name}, the reference's own selection: {v}")
            out[name + ".own_selection"] = {k: v[k] for k in keys}
        del params
        gc.collect()
    print("CONTROL " + json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
