"""One file per family of model: what the harness needs to run and to
check a configuration whose ``family`` key names the file.

``spec.family(name)`` imports ``families/<name>.py``; ``system.py`` and
``peaks.py`` reach a family in no other way, and no code outside this
directory names one. A file exposes (``spec.FAMILY_EXPOSES``):

  program_config(config, dtype)
      The program's config object from the whole ``configs/<name>.json``
      dict (lists and nested groups included; at a rehearsal the toy
      widths are merged in), at the jnp dtype of its ``torch_dtype``.
  init_params(key, cfg)
      The program's weights from a PRNG key; the harness calls it under
      one ``jax.jit``.
  MODEL_FAMILY
      The string ``ServeEngine(model_family=...)`` takes.
  forward_with_margins(params, tokens, config)
      The family's plain reference: (logits [S, vocab], margins [S]) in
      float32 for one unbatched sequence, from the served weights and
      the configuration's keys, importing nothing of the program. A
      position's margin is what ``reference.ROUTER_TIE_MARGIN`` is
      compared with; infinite where nothing routes.
  tolerance(config)
      The relative error a held position may show, with the readings it
      was set from beside it.
  HELD_POSITIONS
      How many checked positions must be held to that tolerance (an int;
      two a prompt are checked).
  forward_weight_bytes(config)
      Bytes of weights one forward must read from HBM, from the shapes
      alone (``forward.hbm_floor_pct`` divides by it).
  warm_growth(engine)            optional
      Builds the programs of the decode path whose shape traffic decides
      and no request can warm; absent, ``system.warm_growth`` (one block
      table of one paged pool).

The reference may live here or in a new file the family imports
(``tpubench/references/<name>.py``); ``reference.py`` holds what every
family shares. A configuration may also set ``check_prompt_tokens`` (with
its reason, under ``check_prompt_tokens_why``): the check's prompt
length, where 300 tokens would not leave a window or fill a selector.
"""
