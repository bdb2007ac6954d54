"""The latent family (models/latent.py) at toy widths on the CPU, held
to its plain reference (tpubench/references/latent.py): prefill then
decode through the slot server, chunked and fused admission, the prefix
cache, the selector below and above ``index_topk``, the expert share,
the selection bias, and the counters."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpubench import reference
from tpubench.families import latent as fam
from tpubench.references import latent as ref
from tpushare.models import latent
from tpushare.models.latent import LatentSlotServer
from tests.launch_trace import Session, tables_agree

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-5          # float32 on both sides


def toy_config(**over):
    with open(os.path.join(ROOT, "tpubench", "configs",
                           "dots3-note-prev-l5-ep8.json")) as f:
        config = json.load(f)
    return {**config, **config["rehearse"]["widths"], **over}


@pytest.fixture(scope="module")
def toy():
    config = toy_config()
    cfg = fam.program_config(config, jnp.float32)
    params = jax.jit(lambda k: fam.init_params(k, cfg))(jax.random.PRNGKey(3))
    return config, cfg, params


def server(cfg, params, **kw):
    kw = dict(dict(n_slots=4, n_blocks=160, block_size=16,
                   max_blocks_per_slot=24, prefix_cache=True), **kw)
    srv = LatentSlotServer(params, cfg, **kw)
    seen = []
    pick = srv._sampler.pick
    srv._sampler.pick = lambda lg: (seen.append(np.asarray(lg)), pick(lg))[1]
    return srv, seen


def prompt_of(n, seed=0, vocab=512):
    return np.random.default_rng(seed).integers(0, vocab, n).tolist()


@pytest.mark.parametrize("n,piece", [(40, 1024), (150, 1024), (333, 1024),
                                     (333, 64)])
def test_prefill_then_decode_match_the_reference(toy, n, piece):
    """Shorter than the toy window and index_topk (40), longer than
    both (150: window 33, index_topk 48), over several blocks of queries
    (333), and with the serial prefill cut into pieces (64 tokens of the
    512 the prompt is padded to)."""
    config, cfg, params = toy
    srv, seen = server(dataclasses.replace(cfg, prefill_block=piece), params)
    prompt = prompt_of(n, seed=n)
    slot = srv.admit(jnp.asarray(prompt, jnp.int32))
    toks = [int(srv.last_token[slot, 0])]
    for _ in range(3):
        toks.append(srv.step()[slot])
    want, _ = fam.forward_with_margins(params, prompt + toks[:3], config)
    assert reference.relative_error(seen[0][0], want[n - 1]) < TOL
    for j in range(3):
        assert reference.relative_error(seen[1 + j][slot], want[n + j]) < TOL


def test_chunked_admission_equals_whole_prompt_admission(toy):
    _, cfg, params = toy
    prompt = jnp.asarray(prompt_of(200, seed=5), jnp.int32)
    whole, seen_w = server(cfg, params)
    whole.admit(prompt)
    chunked, seen_c = server(cfg, params)
    slot = chunked.admit_start(prompt, chunk_tokens=48)
    while chunked.admit_step(slot) is None:
        pass
    np.testing.assert_allclose(seen_c[0], seen_w[0], rtol=0, atol=2e-5)
    whole.step(), chunked.step()
    np.testing.assert_allclose(seen_c[1][slot], seen_w[1][0], rtol=0,
                               atol=2e-5)


def test_fused_admission_beside_a_decoding_stream_matches_the_reference(toy):
    """The fused tick (decode rows and a chunk in one program, writing
    through the block table) gives the admitted prompt and the stream
    beside it the reference's logits."""
    config, cfg, params = toy
    srv, seen = server(cfg, params)
    first = prompt_of(70, seed=1)
    a = srv.admit(jnp.asarray(first, jnp.int32))
    toks_a = [int(srv.last_token[a, 0])]
    doc = prompt_of(230, seed=2)
    b = srv.admit_start(jnp.asarray(doc, jnp.int32), chunk_tokens=64)
    assert b != a
    n_seen = len(seen)
    while b in srv.admission_slots:
        out = srv.step(prefill_work=b)
        toks_a.append(out[a])
    fused = seen[n_seen:]
    # the last fused tick picked the admission's first token, then the
    # decode rows'
    want_doc, _ = fam.forward_with_margins(params, doc, config)
    assert reference.relative_error(fused[-2][0], want_doc[-1]) < TOL
    want_a, _ = fam.forward_with_margins(params, first + toks_a, config)
    decode_picks = [x for x in fused if x.shape[0] == 4]
    for j, lg in enumerate(decode_picks):
        assert reference.relative_error(lg[a], want_a[70 + j]) < TOL
    # and the admitted stream decodes on
    tok_b = int(srv.last_token[b, 0])
    srv.step()
    want_b, _ = fam.forward_with_margins(params, doc + [tok_b], config)
    assert reference.relative_error(seen[-1][b], want_b[-1]) < TOL


def test_a_prefix_cache_hit_equals_a_cold_admission(toy):
    config, cfg, params = toy
    doc, q1, q2 = prompt_of(192, 7), prompt_of(20, 8), prompt_of(24, 9)
    srv, seen = server(cfg, params)
    s1 = srv.admit(jnp.asarray(doc + q1, jnp.int32))
    assert srv.last_cached_len == 0
    srv.evict(s1)
    s2 = srv.admit(jnp.asarray(doc + q2, jnp.int32))
    assert srv.last_cached_len == 192       # every block of the document
    tok = int(srv.last_token[s2, 0])
    srv.step()
    cold, seen_cold = server(cfg, params, prefix_cache=False)
    c2 = cold.admit(jnp.asarray(doc + q2, jnp.int32))
    cold.step()
    np.testing.assert_allclose(seen[1], seen_cold[0], rtol=0, atol=2e-5)
    np.testing.assert_allclose(seen[2][s2], seen_cold[1][c2], rtol=0,
                               atol=2e-5)
    want, _ = fam.forward_with_margins(params, doc + q2 + [tok], config)
    assert reference.relative_error(seen[2][s2], want[-1]) < TOL


def test_below_index_topk_the_full_layer_is_dense_and_above_it_drops_keys():
    """With index_topk out of reach the selector changes nothing; within
    reach it changes the logits of a prompt longer than it and of none
    shorter."""
    outs = {}
    for topk in (48, 4096):
        config = toy_config(index_topk=topk)
        cfg = fam.program_config(config, jnp.float32)
        params = jax.jit(lambda k: fam.init_params(k, cfg))(
            jax.random.PRNGKey(3))
        for n in (40, 150):
            srv, seen = server(cfg, params)
            srv.admit(jnp.asarray(prompt_of(n, seed=n), jnp.int32))
            srv.step()
            outs[topk, n] = (seen[0][0], seen[1][0])
    for j in range(2):
        np.testing.assert_allclose(outs[48, 40][j], outs[4096, 40][j],
                                   rtol=0, atol=2e-5)
        assert reference.relative_error(outs[48, 150][j],
                                        outs[4096, 150][j]) > 1e-3


def test_kth_largest_and_top_mask_follow_top_k():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(6, 90)), jnp.float32)
    x = x.at[0, 40:].set(-jnp.inf).at[1, 10:30].set(0.25)   # few finite; ties
    for k in (1, 7, 48):
        want = jnp.sort(x, axis=-1)[:, ::-1][:, k - 1]
        np.testing.assert_array_equal(latent._kth_largest(x, k), want)
        _, idx = jax.lax.top_k(x, k)
        mask = np.zeros(x.shape, bool)
        np.put_along_axis(mask, np.asarray(idx), True, axis=1)
        mask &= np.asarray(x) > -np.inf
        np.testing.assert_array_equal(latent._top_mask(x, k), mask)


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer(toy):
    """Every share routes over all 16 experts and adds its own 4; the
    shared expert is computed on every chip alike and counted once."""
    config, cfg, params = toy
    w = dict(params["layers"][1])
    rng = jax.random.split(jax.random.PRNGKey(11), 4)
    E, Eh = cfg.n_experts, cfg.experts_held
    dense = lambda k, shape: jax.random.normal(k, shape) / np.sqrt(shape[-2])
    full = {"w_gate": dense(rng[0], (E, cfg.d_model, cfg.d_expert)),
            "w_up": dense(rng[1], (E, cfg.d_model, cfg.d_expert)),
            "w_down": dense(rng[2], (E, cfg.d_expert, cfg.d_model))}
    h = jax.random.normal(rng[3], (37, cfg.d_model))
    live = jnp.ones((37,), bool)
    shared = latent._swiglu(h, w["ws_gate"], w["ws_up"], w["ws_down"])
    total, assigned = shared, 0
    for r in range(E // Eh):
        share = dataclasses.replace(cfg, expert_offset=r * Eh)
        wr = dict(w, **{k: v[r * Eh:(r + 1) * Eh] for k, v in full.items()})
        y, counts = latent.moe_ffn(h, wr, share, live)
        total = total + (y - shared)
        assigned += int(counts[0])
        assert int(counts[1]) == 37
        assert int(counts[2:].sum()) == int(counts[0])
    assert assigned == 37 * cfg.top_k         # every assignment has one home
    # the uncut layer, by the reference's own routing
    with jax.default_matmul_precision("highest"):
        mix, _ = ref._route(h, w["router"], w["router_bias"],
                            top_k=cfg.top_k, offset=0, held=E,
                            scale=cfg.routed_scale)
        want = ref._swiglu(h, w["ws_gate"], w["ws_up"], w["ws_down"])
        for e in range(E):
            want = want + mix[:, e:e + 1] * ref._swiglu(
                h, full["w_gate"][e], full["w_up"][e], full["w_down"][e])
    np.testing.assert_allclose(total, want, rtol=0, atol=5e-5)


def test_a_changed_selection_bias_changes_a_choice(toy):
    _, cfg, params = toy
    w = params["layers"][1]
    h = jax.random.normal(jax.random.PRNGKey(5), (64, cfg.d_model))
    with_b, wb = latent.route(h, w["router"], w["router_bias"], cfg)
    without, w0 = latent.route(h, w["router"],
                               jnp.zeros_like(w["router_bias"]), cfg)
    assert (np.sort(with_b, 1) != np.sort(without, 1)).any()
    # the weights are the sigmoid scores renormalised, the bias left out
    np.testing.assert_allclose(wb.sum(1), cfg.routed_scale, atol=1e-6)


def test_the_margin_flags_a_planted_tie_only_beside_a_held_expert():
    h = jnp.eye(4, 8)
    router = jnp.zeros((8, 16)).at[0].set(jnp.arange(16.0) / 4)
    bias = jnp.zeros((16,))
    # position 0: scores rise with the expert index; top-4 are 12..15,
    # the edge lies between experts 12 and 11
    router = router.at[0, 11].set(router[0, 12] - 1e-4)
    kw = dict(top_k=4, scale=1.0)
    _, near = ref._route(h, router, bias, offset=8, held=4, **kw)   # holds 11
    _, far = ref._route(h, router, bias, offset=0, held=4, **kw)    # holds 0-3
    assert near[0] < reference.ROUTER_TIE_MARGIN
    assert np.isinf(far[0])
    # a clear gap on a held edge is no tie
    router = router.at[0, 11].set(router[0, 12] - 1.0)
    _, clear = ref._route(h, router, bias, offset=8, held=4, **kw)
    assert clear[0] > reference.ROUTER_TIE_MARGIN


def test_selector_margin_is_infinite_until_a_key_is_dropped():
    I = jnp.asarray(np.random.default_rng(1).normal(size=(3, 60)), jnp.float32)
    keep, margin = ref._selected(I, jnp.asarray([10, 48, 59]), topk=48)
    assert np.isinf(margin[0]) and np.isfinite(margin[1:]).all()
    assert keep.sum(1).tolist() == [11, 48, 48]


def test_the_engine_says_which_keys_it_kept(toy):
    """The checker's tap: after a serial admission and one decode step
    the family reads, a full layer, which keys every position kept. In
    float32 that is the reference's own selection, key for key."""
    config, cfg, params = toy
    assert cfg.select_log is not None
    srv, seen = server(cfg, params)
    n = 150
    prompt = prompt_of(n, seed=31)
    slot = srv.admit(jnp.asarray(prompt, jnp.int32))
    tokens = prompt + [int(srv.last_token[slot, 0])]
    srv.step()
    kept = fam.program_selection(tokens, cfg.select_log)
    assert kept.shape == (cfg.n_full, n + 1, n + 1)
    want = np.minimum(np.arange(n + 1) + 1, cfg.index_topk)
    for layer in kept:
        assert layer.sum(1).tolist() == want.tolist()
        assert not np.triu(layer, 1).any()          # causal
    logits, margins = fam.forward_with_margins(params, tokens, config,
                                               kept=kept)
    rep = fam.LAST_REPORT
    assert [(r["taken"], r["outside"]) for r in rep["layers"]] == [(0, 0)] * 2
    assert reference.relative_error(seen[1][slot], logits[n]) < TOL
    assert np.isinf(margins[n]) or margins[n] > 0   # no selector tie left
    # another sequence than the tap holds: the reference's own selection
    assert fam.program_selection(tokens[:-1] + [tokens[-1] ^ 1],
                                 cfg.select_log) is None
    assert fam.program_selection(tokens + [5], cfg.select_log) is None
    # a deployment's configuration has no tap, and its programs return
    # nothing more
    plain, _ = server(dataclasses.replace(cfg, select_log=None), params)
    plain.admit(jnp.asarray(prompt, jnp.int32))
    plain.step()
    assert srv.family_stats()["select_keys_kept"] == \
        plain.family_stats()["select_keys_kept"]


def test_the_reference_follows_the_engine_only_inside_the_band():
    I = jnp.asarray(np.random.default_rng(1).normal(size=(3, 60)), jnp.float32)
    qpos = jnp.asarray([10, 59, 59])
    own, _ = ref._selected(I, qpos, topk=48)
    order = np.argsort(-np.asarray(I[1]))
    edge = np.asarray(own).copy()       # query 1: the 48th and 49th change sides
    edge[1, order[47]], edge[1, order[48]] = False, True
    gap = float(I[1, order[47]] - I[1, order[48]]) / float(jnp.std(I[1]))
    adopted, stats = ref._adopt(I, qpos, own, jnp.asarray(edge), gap, topk=48)
    np.testing.assert_array_equal(adopted, edge)
    assert stats[:, :2].tolist() == [[0, 0], [2, 0], [0, 0]]
    assert float(stats[1, 2]) <= gap
    far = np.asarray(own).copy()        # query 2: the best key for the worst
    order = np.argsort(-np.asarray(I[2]))
    far[2, order[0]], far[2, order[59]] = False, True
    adopted, stats = ref._adopt(I, qpos, own, jnp.asarray(far), gap, topk=48)
    np.testing.assert_array_equal(adopted, own)
    assert stats[2, :2].tolist() == [0, 2] and float(stats[2, 2]) > 1.0
    # a query that drops nothing has no line: any disagreement is outside
    wrong = np.asarray(own).copy()
    wrong[0, 3] = False
    adopted, stats = ref._adopt(I, qpos, own, jnp.asarray(wrong), 10.0,
                                topk=48)
    assert bool(adopted[0, 3]) and stats[0, :2].tolist() == [0, 1]


def test_in_bf16_the_selection_is_what_the_logits_cannot_hold():
    """At toy widths in bfloat16, a prompt that drops more than half of
    its keys: against the reference's own selection the logits are off
    by the keys that changed sides at the edge; against the engine's
    selection, followed within the band, they hold."""
    config = toy_config(index_topk=128, hidden_size=256, q_lora_rank=64,
                        kv_lora_rank=32, index_n_heads=8, index_head_dim=32,
                        qk_rope_head_dim=8)
    cfg = fam.program_config(config, jnp.bfloat16)
    params = jax.jit(lambda k: fam.init_params(k, cfg))(jax.random.PRNGKey(2))
    srv, seen = server(cfg, params, max_blocks_per_slot=40)
    n = 300
    prompt = prompt_of(n, seed=2)
    slot = srv.admit(jnp.asarray(prompt, jnp.int32))
    tokens = prompt + [int(srv.last_token[slot, 0])]
    srv.step()
    kept = fam.program_selection(tokens)
    errs = {}
    for name, k in (("own", None), ("engine", kept)):
        want, _ = fam.forward_with_margins(params, tokens, config, kept=k)
        errs[name] = max(reference.relative_error(seen[0][0], want[n - 1]),
                         reference.relative_error(seen[1][slot], want[n]))
    assert errs["engine"] < fam.TOLERANCE < errs["own"]
    rep = fam.LAST_REPORT["layers"]
    assert all(r["taken"] > 0 for r in rep)
    assert max(r["farthest"] for r in rep) < 0.5


def test_counters_follow_what_was_served(toy):
    _, cfg, params = toy
    srv, _ = server(cfg, params)
    n = 100
    slot = srv.admit(jnp.asarray(prompt_of(n), jnp.int32))
    for _ in range(5):
        srv.step()
    st = srv.family_stats()
    # counted by the programs, so what the device did: the serial prefill
    # pads its prompt to a power of two of blocks (128 here) and selects
    # for the padding too; the five decode steps follow the prompt
    pos = np.concatenate([np.arange(128), n + np.arange(5)])
    assert st["select_keys_seen"] == cfg.n_full * int((pos + 1).sum())
    assert st["select_keys_kept"] == cfg.n_full * int(
        np.minimum(pos + 1, cfg.index_topk).sum())
    rows = n + 5
    assert st["latent_rows_live"] == {"full": cfg.n_full * rows,
                                      "sliding": cfg.n_swa * rows}
    assert st["window_rows_dead"] == cfg.n_swa * (rows - (cfg.window - 1))
    assert sum(st["expert_load"]) == st["expert_assign_local"]
    assert st["expert_load_max"] == max(st["expert_load"])
    # the serial prefill routes its padding too; the decode steps route
    # one token a sparse layer each
    before = st["expert_tokens"]
    srv.step()
    assert srv.family_stats()["expert_tokens"] - before == cfg.n_moe
    srv.evict(slot)
    assert srv.family_stats()["latent_rows_live"] == {"full": 0, "sliding": 0}


def _growth_scenario(srv, tick):
    """Lengths 31, 30, 29 on blocks of 16: a tick where no slot crosses
    a block boundary, one where one does, a fused tick beside a
    crossing, then 31, 31, 31 for a tick where every slot crosses."""
    slots = [srv.admit(jnp.asarray(prompt_of(n, seed=n), jnp.int32))
             for n in (31, 30, 29)]
    tick("none")                                # 31 30 29
    tick("one")                                 # 32 31 30
    a = srv.admit_start(jnp.asarray(prompt_of(100, seed=7), jnp.int32),
                        chunk_tokens=48)
    tick("fused", a)                            # 33 32 31
    for s in slots + [a]:
        srv.evict(s)
    for seed in (1, 2, 3):
        srv.admit(jnp.asarray(prompt_of(31, seed=seed), jnp.int32))
    tick("full")                                # 31 31 31
    tick("every")                               # 32 32 32
    for s in range(srv.cache.n_slots):
        srv.evict(s)


@pytest.fixture(scope="module")
def launch_readings(toy):
    """One traced session of the scenario on a server that has run it
    once already (every shape compiled)."""
    _, cfg, params = toy
    srv = LatentSlotServer(params, cfg, n_slots=4, n_blocks=160,
                           block_size=16, max_blocks_per_slot=24)
    _growth_scenario(srv, lambda label, work=None:
                     srv.step(prefill_work=work))
    grown = {}
    with Session() as ticks:
        def tick(label, work=None):
            before = (srv.growth_ticks, srv.blocks_grown)
            with ticks.tick(label):
                srv.step(prefill_work=work)
            grown[label] = (srv.growth_ticks - before[0],
                            srv.blocks_grown - before[1])
            tables_agree(srv)
        _growth_scenario(srv, tick)
    return ticks, grown


@pytest.mark.parametrize("label,program,blocks", [
    ("none", "paged_decode", 0), ("one", "paged_decode", 1),
    ("every", "paged_decode", 3), ("fused", "paged_fused", 1)])
def test_a_tick_runs_no_eager_operation_ahead_of_its_launch(
        launch_readings, label, program, blocks):
    """Block growth, the chunk and its three scalars ride the family's
    own decode and fused programs (ISSUE 31)."""
    ticks, grown = launch_readings
    # a tick that grows nothing uploads nothing; the others their
    # growth array, and a fused tick its chunk and three scalars too
    arguments = {"none": 0, "fused": 5}.get(label, 1)
    assert ticks[label] == {"programs": [program], "uploads": 0,
                            "arguments": arguments}
    assert grown[label] == (int(blocks > 0), blocks)


def test_the_device_table_is_the_host_mirror_after_every_tick(toy):
    """Whole, chunked and fused admissions, evictions and a re-admission
    into the freed slot between ticks; every stream's last decode step
    reads the reference's logits."""
    config, cfg, params = toy
    srv, seen = server(cfg, params, n_slots=3)
    prompts, toks = {}, {}
    pending = None

    def admit(seed, n, chunked=False):
        nonlocal pending
        p = prompt_of(n, seed=seed)
        if chunked:
            slot = pending = srv.admit_start(jnp.asarray(p, jnp.int32),
                                             chunk_tokens=48)
            toks[slot] = []
        else:
            slot = srv.admit(jnp.asarray(p, jnp.int32))
            toks[slot] = [int(srv.last_token[slot, 0])]
        prompts[slot] = p
        return slot

    def tick():
        nonlocal pending
        out = srv.step(prefill_work=pending)
        tables_agree(srv)
        for slot, tok in out.items():
            toks[slot].append(tok)
        if pending in out:
            pending = None

    def close(slot):
        """The slot's last decode pick against the reference, then
        evict."""
        tick()
        want, _ = fam.forward_with_margins(
            params, prompts[slot] + toks[slot][:-1], config)
        assert reference.relative_error(seen[-1][slot], want[-1]) < TOL
        srv.evict(slot)
        toks.pop(slot)
        tables_agree(srv)

    a, b = admit(1, 45), admit(2, 30)
    for _ in range(4):                          # b crosses at 32, a at 48
        tick()
    c = admit(3, 120, chunked=True)             # fused beside a and b
    while pending is not None:
        tick()
    close(a)
    d = admit(4, 61)                            # a's slot again
    assert d == a
    for _ in range(5):                          # d crosses at 64
        tick()
    close(b), close(c), close(d)
    assert srv.cache.live_blocks() == 0
    assert srv.growth_ticks >= 3 and srv.blocks_grown >= srv.growth_ticks


def test_what_the_family_does_not_serve_is_refused(toy):
    _, cfg, params = toy
    with pytest.raises(ValueError, match="kv_quant"):
        LatentSlotServer(params, cfg, n_slots=2, n_blocks=8, kv_quant=True)
    with pytest.raises(ValueError, match="cacheless"):
        latent.paged_forward(params, jnp.zeros((1, 4), jnp.int32), cfg)


def test_the_engine_serves_the_family_over_http(toy):
    """ServeEngine(model_family="latent"): the same engine thread,
    chunked admission, prefix cache and HTTP front door; /stats carries
    the family's counters."""
    import http.client
    from tpushare.cli import serve as serve_mod
    config, cfg, params = toy
    engine = serve_mod.ServeEngine(
        params, cfg, model_family="latent", n_slots=3, n_blocks=160,
        block_size=16, max_blocks_per_slot=24, prefill_chunk=64,
        idle_sleep_s=0.001)
    httpd = serve_mod.serve(engine, host="127.0.0.1", port=0, timeout_s=300.0)
    port = httpd.server_address[1]

    def post(prompt, n):
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
        conn.request("POST", "/v1/completions",
                     json.dumps({"prompt": prompt, "max_tokens": n}),
                     {"Content-Type": "application/json"})
        r = conn.getresponse()
        return r.status, json.loads(r.read())

    try:
        doc = prompt_of(160, seed=21)
        status, first = post(doc + prompt_of(10, seed=22), 4)
        assert status == 200 and len(first["tokens"]) == 4
        status, again = post(doc + prompt_of(12, seed=23), 3)
        assert status == 200 and again["cached_prefix"] == 160
        # greedy tokens are the reference's argmax
        want, _ = fam.forward_with_margins(
            params, doc + prompt_of(10, seed=22) + first["tokens"][:3], config)
        assert first["tokens"] == [int(t) for t in jnp.argmax(want[169:], -1)]
        st = engine.stats()
        assert st["model_family"] == "latent" and st["kv"] == "paged"
        assert st["select_keys_seen"] > st["select_keys_kept"] > 0
        assert st["expert_tokens"] > 0 and st["prefix_hit_tokens"] == 160
    finally:
        httpd.shutdown()
        engine.stop()
