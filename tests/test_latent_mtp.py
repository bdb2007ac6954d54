"""A latent model with no selector, sandwich norms and a multi-token-
prediction module that drafts for its own model (models/latent.py), at
toy widths on the CPU: held to its plain reference
(tpubench/references/mla_mtp.py) through the paged cache with drafting
on, token for token against the same server with drafting off, and the
bookkeeping a two-token tick needs (stale rows, counters, spans, the
engine's list a slot)."""

import dataclasses
import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpubench import reference
from tpubench.families import mla_mtp as fam
from tpubench.references import mla_mtp as ref
from tpushare.models import latent
from tpushare.models.latent import DraftLog, LatentSlotServer
from tpushare.utils.profiling import SPAN_PREFIX
from tests.launch_trace import Session, tables_agree

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# float32 on both sides: what is left is the order of the sums (the
# absorbed form, rows read through a table, two positions a slot). bf16
# anywhere reads 1e-2 on these logits.
TOL = 2e-5


def toy_config(**over):
    with open(os.path.join(ROOT, "tpubench", "configs",
                           "openpangu-ultra-l5-ep32.json")) as f:
        config = json.load(f)
    # two expert layers after the dense one (the rehearsal's toy has one:
    # its serial prefill must compile inside a 4 s window on a loaded CPU)
    return {**config, **config["rehearse"]["widths"],
            "num_hidden_layers": 3, **over}


def build(config, seed=3, **cfg_over):
    cfg = dataclasses.replace(fam.program_config(config, jnp.float32),
                              **cfg_over)
    params = jax.jit(lambda k: fam.init_params(k, cfg))(
        jax.random.PRNGKey(seed))
    return cfg, params


@pytest.fixture(scope="module")
def toy():
    config = toy_config()
    return (config, *build(config, draft_log=DraftLog()))


@pytest.fixture(scope="module")
def chatty():
    """A vocabulary of 8: the seeded module agrees with its model every
    few tokens, so both outcomes of a round are met."""
    config = toy_config(vocab_size=8)
    return (config, *build(config))


def without_module(cfg, params):
    """The same model served with drafting off: no module in the
    configuration, none in the weights."""
    return (dataclasses.replace(cfg, n_mtp=0, draft_log=None),
            {k: v for k, v in params.items() if k != "mtp"})


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


def as_list(t):
    return t if isinstance(t, list) else [t]


def decode(srv, want: int, toks=None, work=None):
    """Step until every slot in ``toks`` has ``want`` tokens; a fused
    chunk of admission ``work`` rides each tick while it lasts."""
    toks = toks if toks is not None else {}
    while min(len(t) for t in toks.values()) < want:
        fused = work if work in srv.admission_slots else None
        for s, t in srv.step(prefill_work=fused).items():
            toks.setdefault(s, []).extend(as_list(t))
    return {s: t[:want] for s, t in toks.items()}


@pytest.mark.parametrize("n", [40, 150, 333])
def test_prefill_then_drafting_decode_match_the_reference(toy, n):
    """Through the paged cache with drafting on: the logits every token
    was taken from (the prompt's last position, then each round's first
    verified position) against the reference's full forward, and the
    module's draft logits of each round against the reference's module.
    333 tokens run the serial prefill in pieces (64 of the 512 the prompt
    is padded to), so the module's carry crosses pieces."""
    config, cfg, params = toy
    srv, seen = server(dataclasses.replace(cfg, prefill_block=64), params)
    prompt = prompt_of(n, seed=n)
    slot = srv.admit(jnp.asarray(prompt, jnp.int32))
    toks = [int(srv.last_token[slot, 0])]
    rounds = []                     # (tokens before the round, draft logits)
    for _ in range(4):
        before = len(toks)
        toks += srv.step()[slot]
        lengths, active, dl = srv.cfg.draft_log.step
        assert int(lengths[slot]) == n + before - 1 and bool(active[slot])
        rounds.append((before, np.asarray(dl[slot])))
    want = ref.forward_all(params, prompt + toks, config)
    assert reference.relative_error(seen[0][0], want["logits"][n - 1]) < TOL
    picks = [x for x in seen[1:] if x.shape[0] == srv.cache.n_slots]
    assert len(picks) == 4
    for (before, dl), lg in zip(rounds, picks):
        at = n + before - 1         # the position of the round's last_token
        assert reference.relative_error(lg[slot], want["logits"][at]) < TOL
        # the module stands a position behind and guesses the token after
        # the one the main model is about to give
        assert reference.relative_error(dl, want["mtp_logits"][at - 1]) < TOL
    # greedy: every emitted token is the reference's argmax
    assert toks == [int(t) for t in jnp.argmax(
        want["logits"][n - 1:n - 1 + len(toks)], -1)]


def test_greedy_output_with_drafting_on_is_drafting_off_token_for_token(
        chatty):
    """64 tokens a stream on three streams, a fourth admitted through
    the fused tick beside them (chunks of 48) and decoded on: the
    drafting server emits what the same model emits with no module, and
    rounds of both outcomes were among them."""
    _, cfg, params = chatty
    outs = {}
    for name, (c, p) in (("on", (cfg, params)),
                         ("off", without_module(cfg, params))):
        srv, _ = server(c, p)
        toks = {}
        for i, n in enumerate((37, 16, 50)):
            s = srv.admit(jnp.asarray(prompt_of(n, i, 8), jnp.int32))
            toks[s] = [int(srv.last_token[s, 0])]
        decode(srv, 20, toks)
        doc = prompt_of(150, 9, 8)
        b = srv.admit_start(jnp.asarray(doc, jnp.int32), chunk_tokens=48)
        decode(srv, 64, toks, work=b)
        assert b not in srv.admission_slots and len(toks[b]) >= 1
        outs[name] = decode(srv, 64, toks)
        tables_agree(srv)
        if name == "on":
            st = srv.family_stats()
            assert 0 < st["mtp_accepted"] < st["mtp_proposed"]
            assert st["mtp_emitted"] == (st["mtp_proposed"]
                                         + st["mtp_accepted"])
            assert srv.spec_accept_rate() == pytest.approx(
                st["mtp_accepted"] / st["mtp_proposed"])
    assert outs["on"] == outs["off"]


def test_a_stale_row_after_a_rejected_draft_is_never_attended(chatty):
    """A slot that decoded (leaving, past its length, the rows of
    rejected drafts in the main layers' pool and the module's) is
    evicted and another prompt admitted over the same blocks: it answers
    as on a fresh server."""
    _, cfg, params = chatty
    used, _ = server(cfg, params, prefix_cache=False)
    s = used.admit(jnp.asarray(prompt_of(45, 1, 8), jnp.int32))
    decode(used, 40, {s: []})
    blocks = set(used.cache.host_table()[s][used.cache.host_table()[s] >= 0])
    assert used.spec_accepted_tokens < used.spec_draft_tokens
    used.evict(s)
    fresh, _ = server(cfg, params, prefix_cache=False)
    outs = []
    for srv in (used, fresh):
        s2 = srv.admit(jnp.asarray(prompt_of(30, 2, 8), jnp.int32))
        toks = {s2: [int(srv.last_token[s2, 0])]}
        outs.append(decode(srv, 48, toks)[s2])
        if srv is used:         # last in, first out: the same blocks
            again = srv.cache.host_table()[s2]
            assert set(again[again >= 0]) & blocks
    assert outs[0] == outs[1]


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer(toy):
    """Every share routes over all 32 toy experts (no bias, the routed
    sum scaled by 2.5) and adds its own 8; the shared expert is computed
    on every chip alike and counted once."""
    config, cfg, params = toy
    w = dict(params["layers"][1])
    assert "router_bias" not in w and cfg.routed_scale == 2.5
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
    assert E // Eh == 4 and assigned == 37 * cfg.top_k
    with jax.default_matmul_precision("highest"):
        mix, _ = ref._route(h, w["router"], top_k=cfg.top_k, offset=0,
                            held=E, scale=cfg.routed_scale)
        want = ref._swiglu(h, w["ws_gate"], w["ws_up"], w["ws_down"])
        for e in range(E):
            want = want + mix[:, e:e + 1] * ref._swiglu(
                h, full["w_gate"][e], full["w_up"][e], full["w_down"][e])
    np.testing.assert_allclose(total, want, rtol=0, atol=1e-4)


def test_a_configuration_without_a_selector_allocates_no_selector_pool(toy):
    """The dots3 toy builds what it did (three pools, the selector's
    weights); this one builds two, the module's rows as one more layer
    of the first, and no selector, gate or bias in its weights."""
    _, cfg, params = toy
    dots = latent.tiny()
    dsrv = LatentSlotServer(
        jax.jit(lambda k: latent.init_params(k, dots))(jax.random.PRNGKey(0)),
        dots, n_slots=2, n_blocks=8, block_size=16)
    assert dots.pool_shapes(8, 16) == (
        (2, 8, 16, 32), (2, 8, 16, 32), (2, 8, 16, 16))
    assert dsrv.cache.pool_x.shape == (2, 8, 16, 16)
    assert not dsrv.drafting and not dsrv.speculative
    assert {"w_iq", "w_g", "router_bias"} <= set(dsrv.params["layers"][1])
    assert set(dots.init_row_cache(1, 32)) == {"k", "v", "x", "moe_counts"}
    srv, _ = server(cfg, params)
    assert srv.cache.pool_x is None
    assert srv.cache.pool_k.shape[0] == cfg.n_full + 1 == 4
    assert srv.cache.pool_v.shape[0] == 0
    assert cfg.pool_shapes(8, 16)[2] is None
    assert not {"w_iq", "w_g", "router_bias"} & set(params["layers"][1])
    assert {"ln1_post", "ln2_post"} <= set(params["layers"][0])
    assert {"enorm", "hnorm", "w_eh", "final_norm", "router"} <= set(
        params["mtp"][0])
    assert srv.drafting and srv.speculative and srv.gamma == 1


def test_counters_follow_what_was_served(toy):
    _, cfg, params = toy
    srv, _ = server(cfg, params)
    n = 100
    slot = srv.admit(jnp.asarray(prompt_of(n), jnp.int32))
    before = srv.family_stats()
    assert before["mtp_rounds"] == before["latent_rows_read"] == 0
    assert before["select_keys_seen"] is None
    emitted, rows = 0, 0
    for _ in range(5):
        # a round at length L reads rows 0..L+1 of every main layer and
        # the module's rows 0..L-1
        L = int(srv.cache.host_lengths()[slot])
        rows += cfg.n_full * (L + 2) + L
        emitted += len(srv.step()[slot])
    st = srv.family_stats()
    assert (st["mtp_rounds"], st["mtp_proposed"]) == (5, 5)
    assert st["mtp_emitted"] == emitted == 5 + st["mtp_accepted"]
    assert st["latent_rows_read"] == rows
    # the CPU gathers: no paged-kernel call is counted; where the kernel
    # is the rounds' choice the count is one a cached layer a round
    assert st["latent_decode_calls"] == 0
    assert st["latent_rows_live"]["full"] == (cfg.n_full + 1) * (n + emitted)
    assert st["latent_row_bytes"]["full"] == 4 * cfg.full.key_dim
    # the module's expert layer counts as one more sparse layer
    assert len(st["expert_load"]) == (cfg.n_moe + 1) * cfg.experts_held
    assert sum(st["expert_load"]) == st["expert_assign_local"]
    t0 = st["expert_tokens"]
    srv._decode_calls_a_round = cfg.n_cached_full
    srv.step()
    assert srv.family_stats()["latent_decode_calls"] == cfg.n_full + 1
    # two positions a sparse layer, and the module's one (or two) pending
    assert srv.family_stats()["expert_tokens"] - t0 in (
        2 * cfg.n_moe + 1, 2 * cfg.n_moe + 2)


def test_a_drafting_tick_launches_one_program_and_names_its_spans(toy):
    """Growth, draft, verify, acceptance and the commit ride ONE program
    (``paged_decode``; ``paged_fused`` with a chunk), nothing runs ahead
    of it, and the host's part of acceptance is under ``slot.accept``
    beside ``slot.launch`` and ``slot.sample``."""
    _, cfg, params = toy
    srv = LatentSlotServer(params, cfg, n_slots=4, n_blocks=160,
                           block_size=16, max_blocks_per_slot=24)

    def scenario(tick):
        for n in (31, 30):
            srv.admit(jnp.asarray(prompt_of(n, seed=n), jnp.int32))
        tick("plain")
        tick("crossing")
        a = srv.admit_start(jnp.asarray(prompt_of(100, seed=7), jnp.int32),
                            chunk_tokens=48)
        tick("fused", a)
        for s in range(srv.cache.n_slots):
            srv.evict(s)

    scenario(lambda label, work=None: srv.step(prefill_work=work))
    with Session() as ticks:
        def tick(label, work=None):
            with ticks.tick(label):
                srv.step(prefill_work=work)
            tables_agree(srv)
        scenario(tick)
    for label, program in (("plain", "paged_decode"),
                           ("crossing", "paged_decode"),
                           ("fused", "paged_fused")):
        assert ticks[label]["programs"] == [program], ticks[label]
        assert ticks[label]["uploads"] == 0


def test_the_host_half_of_acceptance_is_a_span(toy, tmp_path):
    _, cfg, params = toy
    srv = LatentSlotServer(params, cfg, n_slots=2, n_blocks=32,
                           block_size=16, max_blocks_per_slot=8)
    srv.admit(jnp.asarray(prompt_of(20), jnp.int32))
    srv.step()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        srv.step()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb"))[-1]
    names = [e.name[len(SPAN_PREFIX):]
             for plane in jax.profiler.ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith(SPAN_PREFIX)]
    assert [n for n in names if n.startswith("slot.")] == [
        "slot.grow", "slot.launch", "slot.sample", "slot.accept",
        "slot.fetch"]


def test_the_scopes_reach_the_programs_hlo(toy):
    """``latent_attend``, ``mtp_draft`` and ``mtp_verify`` are in the
    drafting program's metadata (a full profile's; the harness's reduced
    trace drops them, which is why ``mla.attend_busy_pct`` reads shapes)."""
    _, cfg, params = toy
    srv = LatentSlotServer(params, cfg, n_slots=2, n_blocks=32,
                           block_size=16, max_blocks_per_slot=8)
    grow = srv._no_growth[2]
    text = srv._draft_prog.lower(
        srv.params, srv.last_token, srv._mtp_h, srv._mtp_tok, srv._mtp_n,
        srv.cache.pool_k, srv.cache.pool_v, srv.cache.pool_x,
        srv.cache.block_table, srv.cache.lengths, srv._active_dev, grow,
        srv._counts, None).as_text(debug_info=True)
    for scope in ("latent_attend", "mtp_draft", "mtp_verify"):
        assert scope in text, scope


def test_what_a_drafting_server_cannot_be_built_from_is_refused(toy):
    _, cfg, params = toy
    with pytest.raises(ValueError, match="multi-token-prediction"):
        LatentSlotServer({k: v for k, v in params.items() if k != "mtp"},
                         cfg, n_slots=2, n_blocks=8)
    with pytest.raises(ValueError, match="speculative_draft"):
        LatentSlotServer(params, cfg, n_slots=2, n_blocks=8,
                         speculative_draft=(params, cfg))


def test_sampled_drafting_runs_the_rejection_rule(chatty):
    """temperature > 0: the draft is sampled from the module's law and
    accepted by models/spec.py's rejection rule inside the program;
    tokens stay in the vocabulary and both outcomes occur."""
    _, cfg, params = chatty
    srv = LatentSlotServer(params, cfg, n_slots=2, n_blocks=64,
                           block_size=16, max_blocks_per_slot=12,
                           temperature=0.8, seed=5)
    s = srv.admit(jnp.asarray(prompt_of(25, 3, 8), jnp.int32))
    toks = decode(srv, 60, {s: []})[s]
    assert all(0 <= t < 8 for t in toks)
    assert 0 < srv.spec_accepted_tokens < srv.spec_draft_tokens


def test_the_engine_serves_a_drafting_server_over_http(toy):
    """ServeEngine(model_family="latent") with the module in the config
    and the weights: no flag. Greedy tokens are the reference's argmax,
    a prefix hit still hits, /stats has the ``speculative`` group and
    the module's counters, and the engine does not run ahead."""
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
        q = prompt_of(10, seed=22)
        status, first = post(doc + q, 9)
        assert status == 200 and len(first["tokens"]) == 9
        status, again = post(doc + prompt_of(12, seed=23), 3)
        assert status == 200 and again["cached_prefix"] == 160
        want, _ = fam.forward_with_margins(
            params, doc + q + first["tokens"][:8], config)
        assert first["tokens"] == [int(t) for t in jnp.argmax(want[169:], -1)]
        st = engine.stats()
        assert st["model_family"] == "latent"
        spec = st["speculative"]
        assert spec["gamma"] == 1 and spec["spec_rounds"] > 0
        assert 1.0 <= spec["mean_tokens_per_round"] <= 2.0
        assert st["mtp_proposed"] >= st["mtp_rounds"] > 0
        assert st["mtp_emitted"] == st["mtp_proposed"] + st["mtp_accepted"]
        assert st["latent_rows_read"] > 0 and st["expert_tokens"] > 0
        assert st["select_keys_seen"] is None
        assert st["ahead_ticks"] == 0 < st["work_ticks"]
    finally:
        httpd.shutdown()
        engine.stop()
