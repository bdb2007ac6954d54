"""The two-token path of a self-drafting latent server, held exactly.

With seeded weights a module agrees with its model by chance, so the
benchmark's cell and most of ``tests/test_latent_mtp.py`` run rounds
that emit one token. Here the module is MADE to agree (``agreeing``): a
toy model whose every sublayer's output norm is zero emits a token that
is a function of the token before it alone, and a module whose joining
projection passes the next token's embedding through computes that same
function, so every draft is the model's own next token and every round
emits two. The same weights with the module's final norm negated draft
the model's LEAST likely token: no round accepts. Between them and the
model served with no module: the tokens, the cached rows of the main
layers and of the module, the lengths, the prefix cache's keys and the
engine's ``tokens_out`` are the one-token stream's, and a request ends
at its ``max_tokens`` exactly, an accepted draft past it dropped.

The attention and the experts still run and still write their rows
(their outputs are multiplied by the zero norm afterwards), so a row
written to the wrong position, or left stale after an accepted draft,
shows in the comparison of rows even though no token depends on it; that
tokens do depend on rows is the business of the other file's tests.
"""

import http.client
import json

import jax.numpy as jnp
import numpy as np
import pytest

from tests.launch_trace import tables_agree
from tests.test_latent_mtp import (as_list, build, prompt_of, server,
                                   toy_config, without_module)


def agreeing(params, sign: float = 1.0):
    """``params`` with every output norm zeroed (main layers and the
    module's), the module's projection [I ; 0] (the next token's normed
    embedding, nothing of the hidden state), and the module's final norm
    ``sign`` x ones: +1 drafts the model's own next token, -1 its least
    likely one."""
    def quiet(w):
        return dict(w, ln1_post=jnp.zeros_like(w["ln1_post"]),
                    ln2_post=jnp.zeros_like(w["ln2_post"]))
    m = params["mtp"][0]
    Dm = m["w_eh"].shape[1]
    eye = jnp.concatenate([jnp.eye(Dm), jnp.zeros((Dm, Dm))]).astype(
        m["w_eh"].dtype)
    return dict(params, layers=[quiet(w) for w in params["layers"]],
                mtp=[dict(quiet(m), w_eh=eye,
                          final_norm=sign * jnp.ones_like(m["final_norm"]))])


@pytest.fixture(scope="module")
def made():
    """(cfg, the weights whose module always agrees, those whose module
    never does)."""
    cfg, params = build(toy_config())
    return cfg, agreeing(params), agreeing(params, -1.0)


def rows_of(srv, slot: int, layer: int, n: int):
    """The first ``n`` cached rows of ``slot`` in ``layer`` of the full
    pool, read through the host's table."""
    blocks = srv.cache.host_table()[slot]
    pool = np.asarray(srv.cache.pool_k[layer])
    return pool[blocks[blocks >= 0]].reshape(-1, pool.shape[-1])[:n]


def run_streams(srv, want: int):
    """A 37-token prompt admitted serially and decoded, a 150-token one
    admitted beside it through the fused tick in chunks of 48 (its
    blocks and the first stream's cross block boundaries meanwhile),
    both decoded until each has ``want`` tokens. Returns ({slot:
    tokens}, the sizes of every slot's every round)."""
    toks, sizes = {}, []

    def tick(work=None):
        for s, t in srv.step(prefill_work=work).items():
            toks.setdefault(s, []).extend(as_list(t))
            if s != work:
                sizes.append(len(as_list(t)))
        tables_agree(srv)

    a = srv.admit(jnp.asarray(prompt_of(37, 1), jnp.int32))
    toks[a] = [int(srv.last_token[a, 0])]
    for _ in range(3):
        tick()
    b = srv.admit_start(jnp.asarray(prompt_of(150, 2), jnp.int32),
                        chunk_tokens=48)
    while b in srv.admission_slots:
        tick(b)
    while min(len(t) for t in toks.values()) < want:
        tick()
    return (a, b), toks, sizes


def test_an_agreeing_module_emits_two_tokens_a_round_and_leaves_the_one_token_state(
        made):
    cfg, yes, no = made
    two, _ = server(cfg, yes)
    one, _ = server(cfg, no)
    off, _ = server(*without_module(cfg, yes))
    (a, b), t2, sizes2 = run_streams(two, 31)
    _, t1, sizes1 = run_streams(one, 31)
    _, t0, sizes0 = run_streams(off, 31)
    # every round of the agreeing module emitted two, of the other one
    assert set(sizes2) == {2} and set(sizes1) == set(sizes0) == {1}
    st2, st1 = two.family_stats(), one.family_stats()
    assert st2["mtp_accepted"] == st2["mtp_proposed"] == len(sizes2)
    assert st2["mtp_emitted"] == 2 * len(sizes2)
    assert st1["mtp_accepted"] == 0 < st1["mtp_proposed"] == len(sizes1)
    assert st1["mtp_emitted"] == len(sizes1)
    assert two.spec_accept_rate() == 1.0 and one.spec_accept_rate() == 0.0
    for s, n in ((a, 37), (b, 150)):
        # token for token the stream without the module
        m = min(len(t2[s]), len(t1[s]), len(t0[s]))
        assert m >= 31 and t2[s][:m] == t1[s][:m] == t0[s][:m]
        assert len(set(t0[s][:m])) > 4          # a stream, not a fixed point
        # a slot's length is its prompt and all it emitted but the last
        for srv, t in ((two, t2), (one, t1), (off, t0)):
            assert int(srv.cache.host_lengths()[s]) == n + len(t[s]) - 1
        # the main layers' rows: what the one-token streams cached
        m = n + m - 1
        for layer in range(cfg.n_full):
            want = rows_of(off, s, layer, m)
            assert np.abs(want).max() > 0.1
            for srv in (two, one):
                np.testing.assert_allclose(rows_of(srv, s, layer, m), want,
                                           rtol=0, atol=1e-5)
        # the module's rows (position p: the state at p with token p + 1;
        # the last committed positions are still to be written)
        got, want = (rows_of(srv, s, cfg.n_full, m - 2) for srv in (two, one))
        assert np.abs(want[1:]).max() > 0.1
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)
    # the same prompts published the same prefix blocks
    assert sorted(two.cache.index) == sorted(off.cache.index) != []


def engine_of(cfg, params):
    from tpushare.cli import serve as serve_mod
    engine = serve_mod.ServeEngine(
        params, cfg, model_family="latent", n_slots=3, n_blocks=160,
        block_size=16, max_blocks_per_slot=24, prefill_chunk=64,
        idle_sleep_s=0.001)
    httpd = serve_mod.serve(engine, host="127.0.0.1", port=0, timeout_s=300.0)
    return engine, httpd


def post(httpd, prompt, n):
    conn = http.client.HTTPConnection("127.0.0.1", httpd.server_address[1],
                                      timeout=300)
    conn.request("POST", "/v1/completions",
                 json.dumps({"prompt": prompt, "max_tokens": n}),
                 {"Content-Type": "application/json"})
    r = conn.getresponse()
    assert r.status == 200
    return json.loads(r.read())["tokens"]


LIMITS = (1, 2, 3, 4, 7, 8, 13)


@pytest.fixture(scope="module")
def chatty_weights():
    """A vocabulary of 8 and ordinary seeded weights: the module agrees
    every few tokens, so requests end on rounds of both outcomes."""
    return build(toy_config(vocab_size=8))


@pytest.mark.parametrize("which", ["agreeing", "chatty"])
def test_a_request_ends_at_its_limit_and_the_engine_counts_what_the_plain_stream_counts(
        made, chatty_weights, which):
    """Through ``ServeEngine`` over HTTP, with the module and without:
    requests of ``LIMITS`` tokens on prompts that share a 96-token
    document (a prefix hit from the second on). Every answer has exactly
    its limit, is the plain engine's token for token, and ``tokens_out``,
    the completions and the prefix cache's keys are the plain engine's;
    the drafting server emitted MORE than the engine passed on: the
    accepted drafts past a limit, dropped."""
    if which == "agreeing":
        cfg, params, vocab = made[0], made[1], 512
    else:
        (cfg, params), vocab = chatty_weights, 8
    doc = prompt_of(96, 0, vocab)
    asks = [doc + prompt_of(5 + i, 40 + i, vocab)
            for i in range(len(LIMITS))]
    outs, stats, keys = {}, {}, {}
    for name, (c, p) in (("on", (cfg, params)),
                         ("off", without_module(cfg, params))):
        engine, httpd = engine_of(c, p)
        try:
            outs[name] = [post(httpd, q, n) for q, n in zip(asks, LIMITS)]
            stats[name] = engine.stats()
            keys[name] = sorted(engine.srv.cache.index)
        finally:
            httpd.shutdown()
            engine.stop()
    assert [len(t) for t in outs["on"]] == list(LIMITS)
    assert outs["on"] == outs["off"]
    on, off = stats["on"], stats["off"]
    # (a request's first token is its admission's and counted there)
    assert on["tokens_out"] == off["tokens_out"] == sum(LIMITS) - len(LIMITS)
    assert on["completed"] == off["completed"] == len(LIMITS)
    # every request after the first reads the document's six blocks
    assert (on["prefix_hit_tokens"] == off["prefix_hit_tokens"]
            == 96 * (len(LIMITS) - 1))
    assert keys["on"] == keys["off"] != []
    assert off["mtp_rounds"] is None and "speculative" not in off
    # a round's tokens reach the engine as a list: what the rounds
    # emitted and the engine did not pass on are accepted drafts past a
    # request's limit (limits 2, 4 and 8 leave an odd count to rounds of
    # two: with the agreeing module three drafts are dropped)
    dropped = on["mtp_emitted"] - on["tokens_out"]
    spec = on["speculative"]
    assert spec["gamma"] == 1 and spec["spec_rounds"] == on["mtp_rounds"]
    if which == "agreeing":
        assert on["mtp_accepted"] == on["mtp_proposed"] > 0
        assert spec["spec_accept_rate"] == 1.0 and dropped == 3
        # 13 tokens: the admission's, then six rounds of two
        assert on["mtp_proposed"] == sum(-(-(n - 1) // 2) for n in LIMITS)
    else:
        assert 0 < on["mtp_accepted"] < on["mtp_proposed"] and dropped >= 0
        assert 0 < spec["spec_accept_rate"] < 1


@pytest.mark.parametrize("seed", [5, 8, 13])
def test_drafting_on_is_drafting_off_over_seeds_of_weights(seed):
    """Other weights than the other file's (seed 3), a vocabulary of 8:
    two streams whose lengths cross block boundaries at different ticks,
    a third admitted beside them in chunks of 32 (five chunks, the last
    a partial one), 40 tokens a stream: token for token the server with
    no module, with rounds of both outcomes on the way."""
    cfg, params = build(toy_config(vocab_size=8), seed=seed)
    outs = {}
    for name, (c, p) in (("on", (cfg, params)),
                         ("off", without_module(cfg, params))):
        srv, _ = server(c, p)
        toks = {}
        for i, n in enumerate((15, 33)):
            s = srv.admit(jnp.asarray(prompt_of(n, seed + i, 8), jnp.int32))
            toks[s] = [int(srv.last_token[s, 0])]
        b = srv.admit_start(jnp.asarray(prompt_of(141, seed + 2, 8),
                                        jnp.int32), chunk_tokens=32)
        while min(len(t) for t in toks.values()) < 40 or len(toks) < 3:
            work = b if b in srv.admission_slots else None
            for s, t in srv.step(prefill_work=work).items():
                toks.setdefault(s, []).extend(as_list(t))
        tables_agree(srv)
        outs[name] = {s: t[:40] for s, t in toks.items()}
        if name == "on":
            assert 0 < srv.spec_accepted_tokens < srv.spec_draft_tokens
    assert outs["on"] == outs["off"]


# ---------------------------------------------------------------------------
# The module's rows, whatever path admitted the prompt. No token depends
# on them (a wrong row costs acceptance only), so only the draft logits
# against the reference's show one.
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tapped():
    from tpushare.models.latent import DraftLog
    config = toy_config()
    return (config, *build(config, draft_log=DraftLog()))


def admit_by(srv, path: str, prompt):
    """Admit ``prompt`` by ``path``; returns (slot, its first token)."""
    p = jnp.asarray(prompt, jnp.int32)
    if path == "whole":
        slot = srv.admit(p)
        return slot, int(srv.last_token[slot, 0])
    slot = srv.admit_start(p, chunk_tokens=48)
    first = None
    while slot in srv.admission_slots:
        if path == "serial chunks":
            first = srv.admit_step(slot)
        else:                       # fused into a decoding stream's ticks
            first = srv.step(prefill_work=slot).get(slot)
    return slot, as_list(first)[0]


@pytest.mark.parametrize("path", ["serial chunks", "fused chunks",
                                  "prefix hit, whole",
                                  "prefix hit, fused chunks"])
def test_the_modules_draft_follows_the_reference_whatever_admitted_the_prompt(
        tapped, path):
    """150 tokens in chunks of 48 (a chunk's first token writes the
    module's row a block behind the chunk), alone or beside a decoding
    stream; and after a prefix hit of 96 tokens whose next token is the
    publisher's (the row at a shared prefix's last position stays its
    publisher's: ``assumed.prefix_cache`` in the configuration's file).
    The draft logits of four rounds against ``forward_all``'s, at the
    float32 tolerance of the other file."""
    from tpubench import reference
    from tpubench.references import mla_mtp as ref
    config, cfg, params = tapped
    srv, _ = server(cfg, params)
    doc = prompt_of(150, 5)
    if path != "serial chunks":
        srv.admit(jnp.asarray(prompt_of(37, 1), jnp.int32))
        srv.step()
    if path.startswith("prefix hit"):
        pub, _ = admit_by(srv, "fused chunks", doc)
        srv.evict(pub)
        prompt = doc[:97] + prompt_of(40, 8)
        slot, first = admit_by(srv, path.split(", ")[1], prompt)
        assert srv.prefix_hit_tokens == 96
    else:
        prompt = doc
        slot, first = admit_by(srv, path, prompt)
    toks, rounds = [first], []
    for _ in range(4):
        before = len(toks)
        toks += as_list(srv.step()[slot])
        rounds.append((before, np.asarray(srv.cfg.draft_log.step[2][slot])))
    want = ref.forward_all(params, prompt + toks, config)
    for before, dl in rounds:
        at = len(prompt) + before - 1
        assert reference.relative_error(
            dl, want["mtp_logits"][at - 1]) < 2e-5, (path, before)


def test_the_tick_budget_counts_two_positions_a_drafting_stream(made):
    """``--tick-token-budget``: a self-drafting round verifies two
    positions a stream and cannot be split, so a budget of one is
    refused by name, and the engine charges a stream two tokens of a
    tick's room where the same model with no module charges one."""
    from tpushare.cli import serve as serve_mod
    cfg, params, _ = made
    kw = dict(model_family="latent", n_slots=3, n_blocks=160, block_size=16,
              max_blocks_per_slot=24, prefill_chunk=64)
    with pytest.raises(ValueError, match="two positions"):
        serve_mod.ServeEngine(params, cfg, tick_token_budget=1, **kw)
    engine = serve_mod.ServeEngine(params, cfg, tick_token_budget=40, **kw)
    try:
        assert engine._stream_positions == 2
        c, p = without_module(cfg, params)
        plain = serve_mod.ServeEngine(p, c, tick_token_budget=40, **kw)
        assert plain._stream_positions == 1
        plain.stop()
    finally:
        engine.stop()
