"""The retention family (``tpushare.models.retention``, ``ops.retention``)
at toy widths on the CPU: the program's chunked prefill and decode step
through ``RetentionSlotServer`` against the plain quadratic reference
(``tpubench/references/retention.py``) on seeded weights, what the
recurrent state must and must not remember, the kernel under the Pallas
interpreter against its ``jax.numpy`` form, and the engine over HTTP."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpubench.references import retention as ref
from tpushare.models import retention
from tpushare.models.retention import RetentionSlotServer
from tpushare.ops import retention as ops

CFG = retention.tiny()
PARAMS = retention.init_params(jax.random.PRNGKey(1), CFG)
CONFIG = {"num_attention_heads": CFG.n_heads,
          "num_key_value_heads": CFG.n_kv_heads, "head_dim": CFG.head_dim,
          "rms_norm_eps": CFG.norm_eps, "rope_theta": CFG.rope_base}


def server(cfg=CFG, **kw):
    kw.setdefault("n_slots", 3)
    kw.setdefault("n_blocks", 64)
    kw.setdefault("block_size", 4)
    kw.setdefault("max_blocks_per_slot", 20)
    return RetentionSlotServer(PARAMS, cfg, **kw)


def prompt_of(n, seed=0):
    return np.random.default_rng(seed).integers(0, CFG.vocab_size, n)


class Tap:
    """The logits a server sampled from (what the benchmark's check
    reads: ``tpubench/system.check_correct``)."""

    def __init__(self, srv):
        self.seen, pick = [], srv._sampler.pick
        srv._sampler.pick = lambda lg: (self.seen.append(np.asarray(lg)),
                                        pick(lg))[1]


def serve_prompt(srv, prompt, chunk=None, steps=3):
    """Admit, decode ``steps`` tokens; (slot, tokens, sampled logits)."""
    tap = Tap(srv)
    slot = srv.admit_start(jnp.asarray(prompt, jnp.int32), chunk_tokens=chunk)
    while srv.admit_step(slot) is None:
        pass
    toks = [int(srv.last_token[slot, 0])]
    for _ in range(steps):
        toks.append(srv.step()[slot])
    logits = [tap.seen[0][0]] + [lg[slot] for lg in tap.seen[1:]]
    return slot, toks, logits


# -- the mathematics --------------------------------------------------


@pytest.mark.parametrize("dim", [16, 128])
def test_phi_of_q_dot_phi_of_k_is_q_dot_k_squared(dim):
    q, k = jax.random.normal(jax.random.PRNGKey(dim), (2, 7, dim))
    got = jnp.einsum("nf,nf->n", ops.phi(q), ops.phi(k))
    # float32 sums of D (D + 1) / 2 products of either sign: exact to a
    # rounding of the terms' scale, |q|^2 |k|^2
    np.testing.assert_allclose(got, jnp.einsum("nd,nd->n", q, k) ** 2,
                               rtol=1e-4, atol=1e-7 * dim ** 2)
    w = ops.feature_weights(dim)
    assert w.size == ops.n_features(dim) == (dim // 2 + 1) * dim
    assert int((w > 0).sum()) == dim * (dim + 1) // 2    # 8,256 at 128


def _random_layer(T, seed=3):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    H, Hkv, D = CFG.n_heads, CFG.n_kv_heads, CFG.head_dim
    q = jax.random.normal(ks[0], (T, H, D)) * D ** -0.5
    k = jax.random.normal(ks[1], (T, Hkv, D)) * D ** -0.5
    v = jax.random.normal(ks[2], (T, Hkv, D))
    log_g = -jax.random.uniform(ks[3], (T, Hkv), minval=0.001, maxval=0.3)
    return q, k, v, log_g


@pytest.mark.parametrize("inner", [4, 8, 24])
def test_the_recurrence_is_the_quadratic_form(inner):
    """Token by token through the step, and chunk by chunk through the
    scan, the state gives what the reference's [T, T] weights give. The
    reference scales q.k by 1 / D; the program's q and k arrive scaled
    by D^-1/2 each."""
    T, D = 24, CFG.head_dim
    q, k, v, log_g = _random_layer(T)
    with jax.default_matmul_precision("highest"):
        want = ref.retention(q * D ** 0.5, k * D ** 0.5, v, log_g)
    cfg = dataclasses.replace(CFG, inner_chunk=inner)
    s0 = jnp.zeros((cfg.n_kv_heads, D, cfg.features))
    z0 = jnp.zeros((cfg.n_kv_heads, cfg.features))
    o, s, z = retention.chunk_scan(s0, z0, q, k, v, log_g,
                                   jnp.ones((T,), bool), cfg)
    np.testing.assert_allclose(o, want, atol=2e-5)
    state, zz = s0[None, None], z0[None, None]
    for t in range(T):
        o_t, state, zz = ops.step_reference(
            state, zz, 0, q[t:t + 1], k[t:t + 1], v[t:t + 1],
            log_g[t:t + 1], jnp.ones((1,), bool), eps=cfg.eps)
        np.testing.assert_allclose(o_t[0], want[t], atol=2e-5)
    # and both arrive at the same state
    np.testing.assert_allclose(state[0, 0], s, atol=1e-5)
    np.testing.assert_allclose(zz[0, 0], z, atol=1e-5)


def test_padding_neither_decays_nor_writes():
    T, D = 16, CFG.head_dim
    q, k, v, log_g = _random_layer(T)
    s0 = jnp.ones((CFG.n_kv_heads, D, CFG.features))
    z0 = jnp.ones((CFG.n_kv_heads, CFG.features))
    live = jnp.arange(T) < 5
    _, s, z = retention.chunk_scan(s0, z0, q, k, v, log_g, live, CFG)
    short = dataclasses.replace(CFG, inner_chunk=5)
    _, s5, z5 = retention.chunk_scan(s0, z0, q[:5], k[:5], v[:5], log_g[:5],
                                     jnp.ones((5,), bool), short)
    np.testing.assert_allclose(s, s5, atol=1e-6)
    np.testing.assert_allclose(z, z5, atol=1e-6)


def test_the_heads_of_a_group_read_one_state():
    """GQA: one state a KEY-VALUE head; its query heads differ only in
    their queries."""
    srv = server()
    assert srv.state.shape == (CFG.n_layers, 3, CFG.n_kv_heads, CFG.head_dim,
                               CFG.features)
    assert srv.z.shape == (CFG.n_layers, 3, CFG.n_kv_heads, CFG.features)
    q, k, v, log_g = _random_layer(1)
    q = jnp.repeat(q[:, ::CFG.group], CFG.group, axis=1)   # a group alike
    state = jax.random.normal(jax.random.PRNGKey(0), srv.state.shape)[:, :1]
    z = jnp.ones_like(srv.z)[:, :1]
    o, _, _ = ops.step_reference(state, z, 1, q, k, v, log_g,
                                 jnp.ones((1,), bool), eps=CFG.eps)
    o = np.asarray(o).reshape(CFG.n_kv_heads, CFG.group, -1)
    assert (o == o[:, :1]).all()
    assert not np.allclose(o[0], o[1])


# -- the program against the reference --------------------------------


@pytest.mark.parametrize("chunk, inner", [(None, 8), (16, 8), (8, 4),
                                          (24, 24)])
def test_prefill_then_decode_match_the_reference(chunk, inner):
    """The logits the server sampled from, out of the chunked prefill and
    out of four decode steps, against the reference's full forward: the
    answer depends neither on ``prefill_chunk`` nor on the inner chunk."""
    cfg = dataclasses.replace(CFG, inner_chunk=inner)
    prompt = prompt_of(53, seed=chunk or 0)
    _, toks, logits = serve_prompt(server(cfg), prompt, chunk, steps=4)
    want = np.asarray(ref.forward(PARAMS, list(prompt) + toks[:-1], CONFIG))
    for i, got in enumerate(logits):
        at = len(prompt) - 1 + i
        assert np.abs(got - want[at]).max() < 2e-5 * np.abs(want[at]).max()
        assert toks[i] == int(want[at].argmax())


# -- what a slot remembers ---------------------------------------------


def test_an_evicted_slots_next_stream_sees_nothing_of_the_last():
    srv = server(n_slots=1)
    slot, _, _ = serve_prompt(srv, prompt_of(30, seed=1), chunk=16, steps=3)
    assert float(jnp.abs(srv.state[:, slot]).max()) > 0
    srv.evict(slot)
    _, toks, logits = serve_prompt(srv, prompt_of(21, seed=2), steps=2)
    _, toks0, logits0 = serve_prompt(server(n_slots=1), prompt_of(21, seed=2),
                                     steps=2)
    assert toks == toks0
    for a, b in zip(logits, logits0):
        assert (a == b).all()                   # bit for bit


@pytest.mark.parametrize("impl", ["reference", "interpret"])
def test_an_inactive_slots_state_is_bit_identical_after_a_tick(impl):
    cfg = dataclasses.replace(CFG, step_impl=impl)
    srv = server(cfg)
    a, _, _ = serve_prompt(srv, prompt_of(12, seed=3), steps=1)
    b, _, _ = serve_prompt(srv, prompt_of(9, seed=4), steps=1)
    srv.evict(a)            # the slot and its budget go; the state stays
    before = np.asarray(srv.state[:, a]), np.asarray(srv.z[:, a])
    moving = np.asarray(srv.state[:, b])
    assert srv.step().keys() == {b}
    assert (np.asarray(srv.state[:, a]) == before[0]).all()
    assert (np.asarray(srv.z[:, a]) == before[1]).all()
    assert not (np.asarray(srv.state[:, b]) == moving).all()


def test_no_pool_is_built_and_the_budget_is_tokens():
    srv = server()
    assert srv.cache.pool_k.size == srv.cache.pool_v.size == 0
    assert srv.slot_capacity == 4 * 20
    slot, _, _ = serve_prompt(srv, prompt_of(10), steps=3)
    assert srv.cache.live_blocks() == 4         # 14 tokens of 4 a block
    assert srv.growth_ticks >= 1
    srv.evict(slot)
    assert srv.cache.live_blocks() == 0 and len(srv.cache.free) == 63
    with pytest.raises(ValueError, match="capacity"):
        srv.admit(jnp.zeros((80,), jnp.int32))


def test_the_counters_follow_the_active_slots():
    srv = server()
    row = CFG.state_bytes()
    assert row == 4 * CFG.n_layers * CFG.n_kv_heads * CFG.features * (
        CFG.head_dim + 1)
    serve_prompt(srv, prompt_of(20), chunk=8, steps=2)      # 3 chunks
    b, _, _ = serve_prompt(srv, prompt_of(5, seed=1), steps=0)
    srv.step()
    st = srv.family_stats()
    assert st["retention_state_bytes"] == 3 * row
    assert st["retention_state_bytes_live"] == 2 * row
    assert st["retention_chunks"] == 4
    assert st["retention_ticks"] == 3
    assert st["retention_state_bytes_moved"] == 2 * row * (1 + 1 + 2)


# -- the kernel ----------------------------------------------------------


@pytest.mark.parametrize("active", [(True, False, True, False),
                                    (False, False, False, True),
                                    (True, True, True, True),
                                    (False, False, False, False)])
@pytest.mark.parametrize("budget_blocks", [1, 3, 9])
def test_retention_step_under_the_interpreter_is_its_fallback(active,
                                                              budget_blocks):
    """Every tiling of the features (one roll-block a grid step, three,
    all nine), active slots compacted to the front of the grid."""
    L, B, Hkv, G, D = 2, 4, 2, 3, 16
    F = ops.n_features(D)
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    state = jax.random.normal(ks[0], (L, B, Hkv, D, F))
    z = jax.random.uniform(ks[1], (L, B, Hkv, F)) + 1.0
    q = jax.random.normal(ks[2], (B, Hkv * G, D)) * 0.3
    k = jax.random.normal(ks[3], (B, Hkv, D)) * 0.3
    v = jax.random.normal(ks[4], (B, Hkv, D))
    log_g = -jax.random.uniform(ks[5], (B, Hkv)) * 0.1
    act = jnp.asarray(active)
    want = ops.step_reference(state, z, 1, q, k, v, log_g, act, eps=1e-6)
    got = jax.jit(lambda *a: ops.step_kernel(
        *a, eps=1e-6, interpret=True,
        vmem_budget=16 * Hkv * D * D * budget_blocks))(
            state, z, 1, q, k, v, log_g, act)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=1e-4, rtol=1e-5)
    idle = ~np.asarray(active)
    assert (np.asarray(got[1])[:, idle] == np.asarray(state)[:, idle]).all()
    assert (np.asarray(got[1])[0] == np.asarray(state)[0]).all()


def test_the_kernel_takes_whole_lane_tiles_only():
    assert ops.step_eligible(128, 128) and not ops.step_eligible(16, 16)


# -- the tick ------------------------------------------------------------


def test_a_fused_tick_is_a_serial_admission_and_a_decode_step():
    """A chunk fused into the decode batch (one pass over the weights)
    leaves the logits a serial admission beside a decoding stream
    leaves."""
    p0, p1 = prompt_of(7, seed=5), prompt_of(37, seed=6)

    def run(fused):
        srv = server()
        tap = Tap(srv)
        s0 = srv.admit(jnp.asarray(p0, jnp.int32))
        s1 = srv.admit_start(jnp.asarray(p1, jnp.int32), chunk_tokens=16)
        out0, first = [], None
        while first is None:
            if fused:
                got = srv.step(prefill_work=s1)
                first = got.get(s1)
            else:
                got = srv.step()
                first = srv.admit_step(s1)
            out0.append(got[s0])
        for _ in range(2):
            got = srv.step()
            out0.append(got[s0])
        return srv, out0, first, tap.seen

    serial, out_s, first_s, seen_s = run(False)
    fused, out_f, first_f, seen_f = run(True)
    assert (out_s, first_s) == (out_f, first_f)
    assert fused.chunks == serial.chunks == 4   # p0's one, p1's three
    want = np.asarray(ref.forward(PARAMS, list(p1), CONFIG))[-1]
    assert first_f == int(want.argmax())
    # the final fused tick sampled the admission's logits first
    np.testing.assert_allclose(seen_f[-4][0], want, atol=2e-5)
    np.testing.assert_allclose(fused.state, serial.state, atol=1e-5)


def test_a_failed_dispatch_leaves_a_state_to_replay_into():
    srv = server()
    serve_prompt(srv, prompt_of(9), steps=1)
    srv.state.delete(), srv.z.delete()          # as a donation that raised
    srv._recover_donated_pools()
    assert not srv.state.is_deleted() and float(jnp.abs(srv.state).max()) == 0


# -- refusals, and the engine ---------------------------------------------


@pytest.mark.parametrize("flag, value", [
    ("kv_quant", True), ("multi_lora", object()), ("mesh", object()),
    ("speculative_draft", (PARAMS, CFG)), ("layers_hook", lambda *a: a),
    ("prefix_cache", True)])
def test_what_the_family_does_not_serve_is_refused_by_name(flag, value):
    with pytest.raises(ValueError, match=flag):
        server(**{flag: value})


@pytest.mark.parametrize("kw, name", [
    ({"prefix_cache": True}, "prefix_cache"), ({"kv_quant": True}, "kv_quant"),
    ({"host_kv_bytes": 1 << 20}, "prefix_cache"),
    ({"speculative_draft": (PARAMS, CFG)}, "speculative_draft")])
def test_the_engine_refuses_the_same_by_name(kw, name):
    from tpushare.cli.serve import ServeEngine
    with pytest.raises(ValueError, match=name):
        ServeEngine(PARAMS, CFG, model_family="retention", n_slots=2,
                    n_blocks=32, block_size=4, **kw)


def test_the_engine_serves_the_family_over_http():
    """ServeEngine(model_family="retention"): the same engine thread,
    chunked admission fused into the decode batch, SSE front door;
    /stats carries the family's counters and the pool keys stay numbers."""
    import http.client
    import threading
    from tpushare.cli import serve as serve_mod
    engine = serve_mod.ServeEngine(
        PARAMS, CFG, model_family="retention", n_slots=3, n_blocks=96,
        block_size=4, max_blocks_per_slot=32, prefill_chunk=16,
        idle_sleep_s=0.001)
    assert engine.srv.prefix_cache is False
    httpd = serve_mod.serve(engine, host="127.0.0.1", port=0, timeout_s=300.0)
    port = httpd.server_address[1]
    done = {}

    def post(name, prompt, n):
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
        conn.request("POST", "/v1/completions",
                     json.dumps({"prompt": prompt, "max_tokens": n}),
                     {"Content-Type": "application/json"})
        r = conn.getresponse()
        done[name] = (r.status, json.loads(r.read()))

    try:
        long, short = prompt_of(70, seed=8).tolist(), prompt_of(9, 9).tolist()
        t = threading.Thread(target=post, args=("short", short, 40))
        t.start()
        post("long", long, 5)
        t.join()
        for name, prompt in (("long", long), ("short", short)):
            status, body = done[name]
            assert status == 200 and body["cached_prefix"] == 0
            toks = body["tokens"]
            want = np.asarray(ref.forward(PARAMS, prompt + toks[:-1], CONFIG))
            assert toks == [int(x) for x in
                            want[len(prompt) - 1:].argmax(-1)], name
        st = engine.stats()
        assert st["model_family"] == "retention" and st["kv"] == "paged"
        assert st["retention_state_bytes"] == 3 * CFG.state_bytes()
        assert st["retention_state_bytes_moved"] > 0
        assert st["retention_chunks"] >= 6 and st["retention_ticks"] > 0
        assert st["fused_ticks"] > 0 and st["chunked_admits"] >= 1
        assert st["prefix_hit_tokens"] == 0 and st["select_keys_seen"] is None
        for key in ("live_blocks", "free_blocks", "pool_free_frac"):
            assert isinstance(st[key], (int, float)), key
        assert st["live_blocks"] == 0 and st["free_blocks"] == 95
    finally:
        httpd.shutdown()
        engine.stop()
