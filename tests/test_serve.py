"""tpushare-serve HTTP daemon (cli/serve.py): continuous batching,
prefix-cache accounting, error paths — driven over real HTTP."""

import http.client
import json

import jax
import numpy as np
import pytest

from tpushare.cli import serve as serve_mod
from tpushare.models import transformer as tf

CFG = tf.tiny(remat=False)


@pytest.fixture(scope="module")
def server():
    params = tf.init_params(jax.random.PRNGKey(0), CFG)
    engine = serve_mod.ServeEngine(params, CFG, n_slots=2, n_blocks=32,
                                   block_size=8, max_blocks_per_slot=8,
                                   idle_sleep_s=0.001)
    httpd = serve_mod.serve(engine, host="127.0.0.1", port=0,
                            timeout_s=120.0)
    try:
        yield httpd.server_address[1], engine
    finally:
        httpd.shutdown()
        engine.stop()


def _post(port, path, obj):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    conn.request("POST", path, json.dumps(obj),
                 {"Content-Type": "application/json"})
    r = conn.getresponse()
    return r.status, json.loads(r.read())


def _get(port, path):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    conn.request("GET", path)
    r = conn.getresponse()
    return r.status, json.loads(r.read())


def _concurrent_posts(port, named_prompts, max_tokens, join_s=90):
    """POST every (name, prompt) concurrently; {name: (status, body)}."""
    import threading
    results = {}

    def go(name, prompt):
        results[name] = _post(port, "/v1/completions",
                              {"prompt": prompt,
                               "max_tokens": max_tokens})

    threads = [threading.Thread(target=go, args=(n, p))
               for n, p in named_prompts]
    for t in threads:
        t.start()
    for t in threads:
        t.join(join_s)
    return results


def test_healthz(server):
    port, _ = server
    assert _get(port, "/healthz") == (200, {"ok": True, "state": "running"})


def test_completion_matches_direct_server(server):
    port, _ = server
    rng = np.random.default_rng(3)
    prompt = [int(t) for t in rng.integers(0, CFG.vocab_size, 10)]
    status, out = _post(port, "/v1/completions",
                        {"prompt": prompt, "max_tokens": 5})
    assert status == 200
    assert len(out["tokens"]) == 5
    # Reference: a direct PagedSlotServer run (greedy) — the HTTP
    # daemon must be a transport, not a different model.
    from tpushare.models.paged import PagedSlotServer
    import jax.numpy as jnp
    ref = PagedSlotServer(tf.init_params(jax.random.PRNGKey(0), CFG),
                          CFG, n_slots=2, n_blocks=32, block_size=8,
                          max_blocks_per_slot=8, prefix_cache=True)
    slot = ref.admit(jnp.asarray(prompt))
    want = [int(ref.last_token[slot, 0])]
    while len(want) < 5:
        want.append(ref.step()[slot])
    assert out["tokens"] == want


def test_shared_prefix_hits_cache(server):
    port, engine = server
    rng = np.random.default_rng(7)
    system = [int(t) for t in rng.integers(0, CFG.vocab_size, 16)]
    p1 = system + [int(t) for t in rng.integers(0, CFG.vocab_size, 3)]
    p2 = system + [int(t) for t in rng.integers(0, CFG.vocab_size, 4)]
    s1, o1 = _post(port, "/v1/completions",
                   {"prompt": p1, "max_tokens": 2})
    s2, o2 = _post(port, "/v1/completions",
                   {"prompt": p2, "max_tokens": 2})
    assert s1 == 200 and s2 == 200
    assert o2["cached_prefix"] == 16          # the shared system prompt
    status, stats = _get(port, "/stats")
    assert status == 200
    assert stats["prefix_hit_tokens"] >= 16
    assert stats["completed"] >= 2


def test_bad_requests(server):
    port, _ = server
    assert _post(port, "/v1/completions", {})[0] == 400
    assert _post(port, "/v1/completions",
                 {"prompt": "not ids"})[0] == 400
    assert _post(port, "/v1/completions", {"prompt": []})[0] == 400
    assert _post(port, "/v1/completions", [1, 2, 3])[0] == 400
    assert _post(port, "/v1/completions",
                 {"prompt": [1], "max_tokens": 0})[0] == 400
    assert _post(port, "/v1/completions",
                 {"prompt": [1], "max_tokens": 10 ** 9})[0] == 400
    assert _post(port, "/v1/completions",
                 {"prompt": [1], "eos": "2"})[0] == 400
    assert _get(port, "/nope")[0] == 404


def test_out_of_vocab_prompt_rejected(server):
    port, _ = server
    status, out = _post(port, "/v1/completions",
                        {"prompt": [10 ** 9], "max_tokens": 2})
    assert status == 400 and "token ids" in out["error"]


def test_oversized_prompt_gets_400_not_503(server):
    """Prompt beyond slot capacity is a CLIENT error (permanent) — a
    503 would invite infinite retries."""
    port, engine = server
    cap = engine.srv.slot_capacity
    prompt = [1] * (cap + 1)
    status, out = _post(port, "/v1/completions",
                        {"prompt": prompt, "max_tokens": 2})
    assert status == 400, out
    assert "capacity" in out["error"]


def test_pool_pressure_queues_instead_of_rejecting():
    """Admit under transient pool pressure waits for in-flight decodes
    to finish instead of 503ing the backlog."""
    import jax
    params = tf.init_params(jax.random.PRNGKey(1), CFG)
    # Pool sized so two 17-token prompts cannot coexist (5 blocks each
    # at bs=4; 7 usable blocks): the second must wait for the first
    # generation to complete and free its blocks (requeue, not 503).
    engine = serve_mod.ServeEngine(params, CFG, n_slots=2, n_blocks=8,
                                   block_size=4, max_blocks_per_slot=8,
                                   prefix_cache=False,
                                   idle_sleep_s=0.001)
    httpd = serve_mod.serve(engine, host="127.0.0.1", port=0,
                            timeout_s=120.0)
    port = httpd.server_address[1]
    try:
        rng = np.random.default_rng(13)
        p1 = [int(t) for t in rng.integers(0, CFG.vocab_size, 17)]
        p2 = [int(t) for t in rng.integers(0, CFG.vocab_size, 17)]
        results = _concurrent_posts(port, (("a", p1), ("b", p2)), 3,
                                    join_s=60)
        assert results["a"][0] == 200 and results["b"][0] == 200
        assert len(results["a"][1]["tokens"]) == 3
        assert len(results["b"][1]["tokens"]) == 3
    finally:
        httpd.shutdown()
        engine.stop()


def test_multi_lora_over_http():
    """Adapter selection per request: two taught fine-tunes and the
    base model served from one daemon."""
    import jax
    from tpushare.models import lora
    params = tf.init_params(jax.random.PRNGKey(3), CFG)

    def teach(target, seed):
        rng = np.random.default_rng(seed)
        prompts = jax.numpy.asarray(
            rng.integers(0, CFG.vocab_size, (4, 10)))
        toks = jax.numpy.concatenate(
            [prompts[:, :1], jax.numpy.full_like(prompts, target)],
            axis=1)
        ad = lora.init_lora(jax.random.PRNGKey(seed), CFG, rank=4)
        for _ in range(40):
            ad, _ = lora.lora_train_step(params, ad, toks, CFG, lr=0.3)
        return ad, int(prompts[0, 0])

    ad7, p7 = teach(7, 11)
    ad42, p42 = teach(42, 13)
    bank = lora.stack_adapters([ad7, ad42])
    engine = serve_mod.ServeEngine(params, CFG, n_slots=3, n_blocks=32,
                                   block_size=8, max_blocks_per_slot=4,
                                   multi_lora=bank, idle_sleep_s=0.001)
    httpd = serve_mod.serve(engine, host="127.0.0.1", port=0,
                            timeout_s=120.0)
    port = httpd.server_address[1]
    try:
        _, o7 = _post(port, "/v1/completions",
                      {"prompt": [p7], "max_tokens": 4, "adapter": 0})
        _, o42 = _post(port, "/v1/completions",
                       {"prompt": [p42], "max_tokens": 4, "adapter": 1})
        assert o7["tokens"].count(7) >= 3, o7
        assert o42["tokens"].count(42) >= 3, o42
        status, out = _post(port, "/v1/completions",
                            {"prompt": [p7], "max_tokens": 2,
                             "adapter": 9})
        assert status == 400 and "out of range" in out["error"]
        status, _ = _post(port, "/v1/completions",
                          {"prompt": [p7], "max_tokens": 2,
                           "adapter": "a"})
        assert status == 400
        # bool subclasses int: true would silently mean adapter 1.
        status, _ = _post(port, "/v1/completions",
                          {"prompt": [p7], "max_tokens": 2,
                           "adapter": True})
        assert status == 400
    finally:
        httpd.shutdown()
        engine.stop()


def test_engine_survives_step_failure(server):
    """The engine must outlive anything unexpected step() can raise —
    and with failure-domain recovery (ISSUE 4) the in-flight request
    no longer 503s on a transient fault: its slot is quarantined and
    the request REPLAYS token-exactly (same answer as a clean run).
    /healthz stays truthful throughout. (Pool-exhaustion errors never
    land here — typed paged.PoolExhausted takes the single-victim
    preemption path, covered by
    test_pool_exhaustion_preempts_one_victim_not_all.)"""
    port, engine = server
    # Wait until no earlier test's request is still in flight: the
    # injected raise fires on the NEXT step tick and would otherwise
    # quarantine a straggler slot instead of this test's request.
    import time as _time
    deadline = _time.time() + 10
    while (engine.active_count() or engine._admitting
           or not engine._pending.empty()) and _time.time() < deadline:
        _time.sleep(0.01)
    # Clean reference answer first.
    status, clean = _post(port, "/v1/completions",
                          {"prompt": [3, 1, 4], "max_tokens": 4})
    assert status == 200
    base = engine.stats()
    real_step = engine.srv.step
    state = {"raised": False}

    def boom(*a, **kw):
        if not state["raised"]:
            state["raised"] = True
            raise RuntimeError("device wedged (injected)")
        return real_step(*a, **kw)

    engine.srv.step = boom
    try:
        status, out = _post(port, "/v1/completions",
                            {"prompt": [3, 1, 4], "max_tokens": 4})
    finally:
        engine.srv.step = real_step
    # The one-shot fault is absorbed: quarantine + replay, then the
    # same tokens a fault-free run produces (greedy replay carries the
    # already-generated prefix).
    assert status == 200 and out["tokens"] == clean["tokens"]
    st = engine.stats()
    assert st["engine_errors"] >= base["engine_errors"] + 1
    assert st["quarantines"] >= base["quarantines"] + 1
    assert st["replays"] >= base["replays"] + 1
    # Engine thread is alive and serving again.
    status, out = _post(port, "/v1/completions",
                        {"prompt": [3, 1, 4], "max_tokens": 2})
    assert status == 200 and len(out["tokens"]) == 2
    assert _get(port, "/healthz")[0] == 200


def test_eos_stops_generation(server):
    port, _ = server
    rng = np.random.default_rng(11)
    prompt = [int(t) for t in rng.integers(0, CFG.vocab_size, 6)]
    # First find what the model emits, then use it as EOS.
    _, ref = _post(port, "/v1/completions",
                   {"prompt": prompt, "max_tokens": 3})
    eos = ref["tokens"][1]
    _, out = _post(port, "/v1/completions",
                   {"prompt": prompt, "max_tokens": 50, "eos": eos})
    assert out["tokens"][-1] == eos
    assert len(out["tokens"]) <= 3


def test_stop_before_start_is_safe():
    """ADVICE r3: stop() on a never-started engine must not raise from
    Thread.join, and healthz must not report ok for a dead engine."""
    params = tf.init_params(jax.random.PRNGKey(2), CFG)
    engine = serve_mod.ServeEngine(params, CFG, n_slots=1, n_blocks=8,
                                   block_size=4)
    req = serve_mod._Request([1, 2, 3], 2, None)
    assert engine.submit(req)
    engine.stop()                       # never started: no join crash
    assert req.done.is_set() and req.error
    assert not engine.healthy()
    assert engine.state() == "shutting_down"


def test_queue_full_gives_429():
    """Bounded pending queue: overflow is an immediate reject, not an
    unbounded queue + parked handler threads (ADVICE r3)."""
    params = tf.init_params(jax.random.PRNGKey(3), CFG)
    engine = serve_mod.ServeEngine(params, CFG, n_slots=1, n_blocks=8,
                                   block_size=4, max_queue=2)
    # engine not started: queue can only fill
    assert engine.submit(serve_mod._Request([1], 1, None))
    assert engine.submit(serve_mod._Request([1], 1, None))
    assert not engine.submit(serve_mod._Request([1], 1, None))
    engine.stop()


def test_queue_bound_survives_tiered_intake():
    """Flood backpressure on a RUNNING engine: the tier scheduler's
    intake drain is bounded at max_queue, so a sustained flood still
    hits the Queue's 429 backstop instead of growing the per-tier
    deques without bound (accepted-not-admitted work stays <= 2x
    max_queue: scheduler backlog + pending queue)."""
    import time as _t
    params = tf.init_params(jax.random.PRNGKey(6), CFG)
    engine = serve_mod.ServeEngine(params, CFG, n_slots=1, n_blocks=32,
                                   block_size=8, max_blocks_per_slot=8,
                                   idle_sleep_s=0.001, max_queue=2)
    engine.start()
    try:
        # Saturate the single slot with the longest generation the
        # slot's 8-block capacity admits (prompt 3 + 56 < 64 tokens).
        busy = serve_mod._Request([1, 2, 3], 56, None)
        assert engine.submit(busy)
        deadline = _t.time() + 30
        while engine.active_count() < 1 and _t.time() < deadline:
            _t.sleep(0.005)
        # Flood: far more than 2x max_queue, submitted in microseconds
        # while busy holds the slot. The engine may drain up to
        # max_queue into the scheduler, so accepts can reach
        # scheduler(2) + queue(2) (+1 for a drain racing a put) — the
        # rest MUST bounce off the full Queue (the handler's 429).
        # Pre-fix every submit succeeded: the drain emptied the Queue
        # each tick and the per-tier deques grew without bound.
        accepted = sum(
            1 for _ in range(10)
            if engine.submit(serve_mod._Request([1, 2, 3], 4, None)))
        assert accepted <= 2 * 2 + 1, f"flood accepted {accepted}"
    finally:
        engine.stop()


def test_ceiling_hold_parks_without_blocking_other_tenants():
    """A tenant over its own KV-block ceiling with work in flight is
    PARKED (waiting on its own refunds), not held at its tier front —
    pre-fix its at-risk head won every pop() via strict priority and
    one over-quota tenant froze every other tenant's admissions for
    the lifetime of its streams."""
    import time as _t

    from tpushare.slo.quota import TenantQuotaSpec
    params = tf.init_params(jax.random.PRNGKey(7), CFG)
    engine = serve_mod.ServeEngine(
        params, CFG, n_slots=3, n_blocks=64, block_size=4,
        max_blocks_per_slot=16, idle_sleep_s=0.001,
        tenant_quotas={"acme": TenantQuotaSpec(reserve=0, ceiling=4)})
    engine.start()
    try:
        # acme's stream holds ~3 of its 4-block ceiling for ~40 ticks.
        busy = serve_mod._Request([1, 2, 3, 4, 5, 6, 7, 8], 40, None,
                                  tier="standard", tenant="acme")
        assert engine.submit(busy)
        deadline = _t.time() + 30
        while engine.active_count() < 1 and _t.time() < deadline:
            _t.sleep(0.005)
        # acme's second request needs 3 fresh blocks: 3 used + 3 > 4
        # -> ceiling hold (work in flight, so no 429). interactive on
        # purpose: the tier whose at-risk head caused the freeze.
        held = serve_mod._Request([9, 8, 7, 6, 5, 4, 3, 2], 4, None,
                                  tier="interactive", tenant="acme")
        assert engine.submit(held)
        # Another tenant must sail through while acme is parked.
        other = serve_mod._Request([1, 1, 2, 3], 4, None,
                                   tier="standard", tenant="bob")
        assert engine.submit(other)
        assert other.done.wait(30)
        assert other.error is None and len(other.tokens) == 4
        assert not held.done.is_set()       # still parked, not 429'd
        assert engine.stats()["quota_parked"] == 1
        # busy completes -> refund -> unpark -> held admits and runs.
        assert busy.done.wait(60) and busy.error is None
        assert held.done.wait(30)
        assert held.error is None and len(held.tokens) == 4
    finally:
        engine.stop()


def test_pool_exhaustion_preempts_one_victim_not_all():
    """Mid-flight pool exhaustion sheds ONE victim (recompute-preempted
    and resumed) instead of 503ing every in-flight request (ADVICE r3
    medium). Greedy decoding makes the resumed generation bit-identical
    to an unpreempted run."""
    import threading
    params = tf.init_params(jax.random.PRNGKey(4), CFG)
    rng = np.random.default_rng(7)
    p1 = [int(t) for t in rng.integers(0, CFG.vocab_size, 15)]
    p2 = [int(t) for t in rng.integers(0, CFG.vocab_size, 15)]

    # Reference run: big pool, no pressure.
    ref = serve_mod.ServeEngine(params, CFG, n_slots=2, n_blocks=64,
                                block_size=4, prefix_cache=False,
                                idle_sleep_s=0.001)
    httpd = serve_mod.serve(ref, host="127.0.0.1", port=0, timeout_s=120.0)
    try:
        want = {}
        for name, p in (("a", p1), ("b", p2)):
            st, body = _post(httpd.server_address[1], "/v1/completions",
                             {"prompt": p, "max_tokens": 8})
            assert st == 200
            want[name] = body["tokens"]
    finally:
        httpd.shutdown()
        ref.stop()

    # Pressured run: both prompts fill the pool exactly (4 blocks each
    # of the 8 usable — block 8 is the trash block); the first decode
    # growth past the reserved 16 positions must exhaust the pool and
    # trigger preemption.
    engine = serve_mod.ServeEngine(params, CFG, n_slots=2, n_blocks=9,
                                   block_size=4, prefix_cache=False,
                                   idle_sleep_s=0.001)
    httpd = serve_mod.serve(engine, host="127.0.0.1", port=0,
                            timeout_s=120.0)
    port = httpd.server_address[1]
    try:
        results = _concurrent_posts(port, (("a", p1), ("b", p2)), 8)
        for name in ("a", "b"):
            assert results[name][0] == 200, results[name]
            assert results[name][1]["tokens"] == want[name]
        # at least one preemption actually happened (the test's point)
        assert engine.stats()["preempted"] >= 1
    finally:
        httpd.shutdown()
        engine.stop()


def test_chunked_prefill_interleaves_with_decode():
    """--prefill-chunk: a long admission must not change outputs, must
    be split into chunks (stats), and a short concurrent request keeps
    decoding while the long prompt trickles in."""
    import threading
    params = tf.init_params(jax.random.PRNGKey(6), CFG)
    rng = np.random.default_rng(21)
    long_p = [int(t) for t in rng.integers(0, CFG.vocab_size, 48)]
    short_p = [int(t) for t in rng.integers(0, CFG.vocab_size, 6)]

    # Reference: whole-prompt admission.
    ref = serve_mod.ServeEngine(params, CFG, n_slots=2, n_blocks=32,
                                block_size=8, idle_sleep_s=0.001)
    httpd = serve_mod.serve(ref, host="127.0.0.1", port=0, timeout_s=120.0)
    try:
        want = {}
        for name, p in (("long", long_p), ("short", short_p)):
            st, body = _post(httpd.server_address[1], "/v1/completions",
                             {"prompt": p, "max_tokens": 6})
            assert st == 200
            want[name] = body["tokens"]
    finally:
        httpd.shutdown()
        ref.stop()

    engine = serve_mod.ServeEngine(params, CFG, n_slots=2, n_blocks=32,
                                   block_size=8, idle_sleep_s=0.001,
                                   prefill_chunk=16)
    httpd = serve_mod.serve(engine, host="127.0.0.1", port=0,
                            timeout_s=120.0)
    port = httpd.server_address[1]
    try:
        results = _concurrent_posts(
            port, (("long", long_p), ("short", short_p)), 6)
        for name in ("long", "short"):
            assert results[name][0] == 200, results[name]
            assert results[name][1]["tokens"] == want[name], name
        st = engine.stats()
        assert st["chunked_admits"] >= 1
        assert st["completed"] >= 2
    finally:
        httpd.shutdown()
        engine.stop()


def test_streaming_matches_blocking(server):
    """stream=true: SSE events carry the same greedy tokens as the
    blocking response, closing with a done event."""
    import socket as _socket
    port, _ = server
    rng = np.random.default_rng(31)
    prompt = [int(t) for t in rng.integers(0, CFG.vocab_size, 9)]
    st, blocking = _post(port, "/v1/completions",
                         {"prompt": prompt, "max_tokens": 5})
    assert st == 200

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    conn.request("POST", "/v1/completions",
                 json.dumps({"prompt": prompt, "max_tokens": 5,
                             "stream": True}))
    resp = conn.getresponse()
    assert resp.status == 200
    assert resp.getheader("Content-Type") == "text/event-stream"
    events = []
    ids = []
    for raw in resp.read().split(b"\n\n"):
        for line in raw.strip().splitlines():
            if line.startswith(b"data: "):
                events.append(json.loads(line[len(b"data: "):]))
            elif line.startswith(b"id: "):      # r15 resume cursors
                ids.append(int(line[len(b"id: "):]))
    conn.close()
    toks = [e["token"] for e in events if "token" in e]
    assert toks == blocking["tokens"]
    assert events[-1].get("done") is True
    # r15: monotonic event ids — the resume cursor — count delivered
    # tokens (the done event repeats the final cursor).
    assert ids == list(range(1, len(toks) + 1)) + [len(toks)]
    assert resp.getheader("X-Request-Id")
    # the blocking run published this prompt's full block, so the
    # streamed rerun reports a prefix hit (8 of 9 tokens at bs=8)
    assert events[-1]["cached_prefix"] == 8


def test_streaming_is_event_driven():
    """The SSE handler must block on req.cond, not poll (VERDICT r4
    #5): across a 300 ms producer idle gap the handler performs O(1)
    condition waits — the old 10 ms poll quantum needed >= 30 — and
    every token still arrives, in order, before the done event. Uses a
    fake engine so the producer's timing is test-controlled."""
    import threading
    import time as _time
    from http.server import ThreadingHTTPServer

    class _CountingCondition(threading.Condition):
        def __init__(self):
            super().__init__()
            self.wait_calls = 0

        def wait(self, timeout=None):
            self.wait_calls += 1
            return super().wait(timeout)

    def _producer(req):
        req.push(11)
        req.push(22)
        _time.sleep(0.3)        # idle gap: a poll loop racks up waits
        req.push(33)
        req.finish()

    captured = {}

    class _FakeSrv:
        cfg = CFG

    class _FakeEngine:
        srv = _FakeSrv()
        max_tokens_cap = 4096

        def note_http_turn(self, cpu_s):
            pass

        def submit(self, req):
            req.cond = _CountingCondition()
            captured["req"] = req
            threading.Thread(target=_producer, args=(req,),
                             daemon=True).start()
            return True

    httpd = ThreadingHTTPServer(
        ("127.0.0.1", 0), serve_mod.make_handler(_FakeEngine(), 30.0))
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        conn = http.client.HTTPConnection(
            "127.0.0.1", httpd.server_address[1], timeout=30)
        conn.request("POST", "/v1/completions",
                     json.dumps({"prompt": [1, 2], "max_tokens": 8,
                                 "stream": True}))
        resp = conn.getresponse()
        assert resp.status == 200
        events = [json.loads(line[len(b"data: "):])
                  for raw in resp.read().split(b"\n\n")
                  for line in raw.strip().splitlines()
                  if line.startswith(b"data: ")]
        conn.close()
    finally:
        httpd.shutdown()
    assert [e["token"] for e in events if "token" in e] == [11, 22, 33]
    assert events[-1].get("done") is True
    # O(1) wakeups: one per wait-drain round plus slack for spurious
    # wakeups — nowhere near the >=30 a 10 ms poll would need.
    assert captured["req"].cond.wait_calls <= 8, \
        captured["req"].cond.wait_calls


def test_streaming_client_disconnect_frees_slot():
    """Closing the SSE connection mid-generation cancels the request:
    the slot must come back (no decode-to-max_tokens for nobody)."""
    import socket, time as _time
    params = tf.init_params(jax.random.PRNGKey(8), CFG)
    engine = serve_mod.ServeEngine(params, CFG, n_slots=1, n_blocks=32,
                                   block_size=8, idle_sleep_s=0.001)
    httpd = serve_mod.serve(engine, host="127.0.0.1", port=0,
                            timeout_s=120.0)
    port = httpd.server_address[1]
    try:
        body = json.dumps({"prompt": [3, 1, 4, 1, 5],
                           "max_tokens": 4096, "stream": True}).encode()
        s = socket.create_connection(("127.0.0.1", port), timeout=30)
        s.sendall(b"POST /v1/completions HTTP/1.1\r\n"
                  b"Host: x\r\nContent-Length: %d\r\n\r\n" % len(body)
                  + body)
        # read until at least one token event arrived, then vanish
        buf = b""
        while b'{"token"' not in buf:
            buf += s.recv(4096)
        s.close()
        t0 = _time.time()
        while _time.time() - t0 < 60:
            if (engine.active_count() == 0
                    and engine.stats()["completed"] >= 1):
                break
            _time.sleep(0.05)
        assert engine.active_count() == 0
        assert engine.stats()["completed"] >= 1
        # Discriminate cancel-on-disconnect from decode-to-capacity:
        # the slot retires at 256 tokens (32 blocks x 8) regardless,
        # so a broken cancel path would still free it — but only after
        # generating ~250 tokens. A working cancel reaps within a few
        # engine ticks of the disconnect.
        assert engine.stats()["tokens_out"] < 128, engine.stats()
        # slot is reusable immediately
        st, out = _post(port, "/v1/completions",
                        {"prompt": [2, 7], "max_tokens": 2})
        assert st == 200 and len(out["tokens"]) == 2
    finally:
        httpd.shutdown()
        engine.stop()


def test_speculative_engine_matches_blocking():
    """--draft-preset engine: responses bit-match a non-speculative
    engine (the draft only buys speed), including eos truncation of a
    mid-block acceptance."""
    params = tf.init_params(jax.random.PRNGKey(0), CFG)
    rng = np.random.default_rng(41)
    prompt = [int(t) for t in rng.integers(0, CFG.vocab_size, 12)]

    plain = serve_mod.ServeEngine(params, CFG, n_slots=2, n_blocks=32,
                                  block_size=8, idle_sleep_s=0.001)
    httpd = serve_mod.serve(plain, host="127.0.0.1", port=0,
                            timeout_s=120.0)
    try:
        st, want = _post(httpd.server_address[1], "/v1/completions",
                         {"prompt": prompt, "max_tokens": 9})
        assert st == 200
    finally:
        httpd.shutdown()
        plain.stop()

    spec = serve_mod.ServeEngine(
        params, CFG, n_slots=2, n_blocks=32, block_size=8,
        idle_sleep_s=0.001,
        speculative_draft=(params, CFG), gamma=3)   # self-draft
    httpd = serve_mod.serve(spec, host="127.0.0.1", port=0,
                            timeout_s=120.0)
    port = httpd.server_address[1]
    try:
        st, got = _post(port, "/v1/completions",
                        {"prompt": prompt, "max_tokens": 9})
        assert st == 200
        assert got["tokens"] == want["tokens"]
        # eos truncation: use the 4th generated token as eos — the
        # speculative engine must stop there even though the round
        # that produced it accepted more.
        eos = want["tokens"][3]
        first = want["tokens"].index(eos)       # eos may appear earlier
        st, got = _post(port, "/v1/completions",
                        {"prompt": prompt, "max_tokens": 9, "eos": eos})
        assert st == 200
        assert got["tokens"] == want["tokens"][:first + 1]
        # speedup mechanics actually engaged: fewer steps than tokens
        st_stats = spec.stats()
        assert st_stats["steps"] < st_stats["tokens_out"]
    finally:
        httpd.shutdown()
        spec.stop()


def test_spec_horizon_engine_matches_and_reports():
    """--spec-horizon engine (multi-token drafts): responses bit-match
    the non-speculative engine at k>1, and /stats carries the seam's
    spec_horizon / spec_rounds / spec_accept_rate counters."""
    params = tf.init_params(jax.random.PRNGKey(0), CFG)
    rng = np.random.default_rng(43)
    prompt = [int(t) for t in rng.integers(0, CFG.vocab_size, 12)]

    plain = serve_mod.ServeEngine(params, CFG, n_slots=2, n_blocks=64,
                                  block_size=8, idle_sleep_s=0.001)
    httpd = serve_mod.serve(plain, host="127.0.0.1", port=0,
                            timeout_s=120.0)
    try:
        st, want = _post(httpd.server_address[1], "/v1/completions",
                         {"prompt": prompt, "max_tokens": 9})
        assert st == 200
    finally:
        httpd.shutdown()
        plain.stop()

    spec = serve_mod.ServeEngine(
        params, CFG, n_slots=2, n_blocks=64, block_size=8,
        idle_sleep_s=0.001,
        speculative_draft=(params, CFG), gamma=2, spec_horizon=2)
    httpd = serve_mod.serve(spec, host="127.0.0.1", port=0,
                            timeout_s=120.0)
    try:
        st, got = _post(httpd.server_address[1], "/v1/completions",
                        {"prompt": prompt, "max_tokens": 9})
        assert st == 200
        assert got["tokens"] == want["tokens"]
        sp = spec.stats()["speculative"]
        assert sp["spec_horizon"] == 2
        assert sp["spec_rounds"] > 0
        # self-draft: every proposed token accepted
        assert sp["spec_accept_rate"] == 1.0
        assert sp["gamma"] == 2
    finally:
        httpd.shutdown()
        spec.stop()


def test_spec_horizon_budget_granule_rejected():
    """A tick budget below the spec-round granule (gamma*K+1) could
    never admit one round — loud error at both the engine and the
    argv layer, never a silent never-speculates deployment."""
    params = tf.init_params(jax.random.PRNGKey(0), CFG)
    with pytest.raises(ValueError, match="granule"):
        serve_mod.ServeEngine(
            params, CFG, n_slots=2, n_blocks=32, block_size=8,
            speculative_draft=(params, CFG), gamma=4, spec_horizon=4,
            tick_token_budget=8)


def test_spec_horizon_cli_guards(monkeypatch):
    cases = [
        (["--spec-horizon", "2"], "needs --draft-preset"),
        (["--spec-horizon", "0", "--draft-preset", "tiny"], ">= 1"),
        (["--draft-preset", "tiny", "--spec-horizon", "4",
          "--tick-token-budget", "8"], "granule"),
    ]
    for argv, pat in cases:
        monkeypatch.setattr("sys.argv", ["tpushare-serve", *argv])
        with pytest.raises(SystemExit, match=pat):
            serve_mod.build_engine(
                serve_mod.build_parser().parse_args())


def test_cli_flag_plumbing(monkeypatch):
    """main() must hand every sampling/speculation flag to ServeEngine
    (the engine supported sampling before the CLI exposed it — pin the
    plumbing so a flag can't silently go nowhere)."""
    captured = {}

    class _FakeEngine:
        def __init__(self, params, cfg, **kw):
            captured.update(kw)

    def _fake_serve(engine, host, port, **kw):
        class _S:
            server_address = (host, 0)
        raise KeyboardInterrupt          # unwind main() after capture

    monkeypatch.setattr(serve_mod, "ServeEngine", _FakeEngine)
    monkeypatch.setattr(serve_mod, "serve", _fake_serve)
    monkeypatch.setattr(
        "sys.argv",
        ["tpushare-serve", "--preset", "tiny", "--temperature", "0.7",
         "--top-k", "40", "--top-p", "0.9", "--draft-preset",
         "int8-self", "--gamma", "3", "--spec-horizon", "2",
         "--prefill-chunk", "256",
         "--prefill-chunk-force", "--tick-token-budget", "640",
         "--seed", "5"])
    try:
        serve_mod.main()
    except KeyboardInterrupt:
        pass
    assert captured["temperature"] == 0.7
    assert captured["top_k"] == 40
    assert captured["top_p"] == 0.9
    assert captured["gamma"] == 3
    assert captured["spec_horizon"] == 2
    # --prefill-chunk-force keeps the below-floor value verbatim.
    assert captured["prefill_chunk"] == 256
    assert captured["tick_token_budget"] == 640
    assert captured["seed"] == 5
    assert captured["speculative_draft"] is not None
    assert captured["draft_layers_hook"] is not None
    # Without --prefill-chunk-force a below-floor chunk clamps to the
    # documented break-even floor (VERDICT r5 #7: 256 was accepted
    # silently at a measured 2x cost).
    monkeypatch.setattr(
        "sys.argv",
        ["tpushare-serve", "--preset", "tiny",
         "--prefill-chunk", "256"])
    captured.clear()
    try:
        serve_mod.main()
    except KeyboardInterrupt:
        pass
    assert captured["prefill_chunk"] == serve_mod.PREFILL_CHUNK_FLOOR
    # At or above the floor nothing clamps.
    monkeypatch.setattr(
        "sys.argv",
        ["tpushare-serve", "--preset", "tiny",
         "--prefill-chunk", "1024"])
    captured.clear()
    try:
        serve_mod.main()
    except KeyboardInterrupt:
        pass
    assert captured["prefill_chunk"] == 1024
    # top-k/top-p sentinel values mean "off", not a literal filter.
    monkeypatch.setattr(
        "sys.argv", ["tpushare-serve", "--preset", "tiny"])
    captured.clear()
    try:
        serve_mod.main()
    except KeyboardInterrupt:
        pass
    assert captured["top_k"] is None and captured["top_p"] is None
    assert captured["temperature"] == 0.0


def test_preemption_composes_with_speculation():
    """Pool exhaustion on a SPECULATIVE engine preempts one victim and
    the resumed stream stays bit-identical (greedy): the victim's
    re-admission re-prefills the draft pools too, so acceptance — and
    therefore output chunking — survives the recompute round-trip."""
    import threading
    params = tf.init_params(jax.random.PRNGKey(4), CFG)
    rng = np.random.default_rng(7)
    p1 = [int(t) for t in rng.integers(0, CFG.vocab_size, 15)]
    p2 = [int(t) for t in rng.integers(0, CFG.vocab_size, 15)]

    def run(n_blocks):
        engine = serve_mod.ServeEngine(
            params, CFG, n_slots=2, n_blocks=n_blocks, block_size=4,
            prefix_cache=False, idle_sleep_s=0.001,
            speculative_draft=(params, CFG), gamma=3)
        httpd = serve_mod.serve(engine, host="127.0.0.1", port=0,
                                timeout_s=120.0)
        port = httpd.server_address[1]
        try:
            results = _concurrent_posts(port, (("a", p1), ("b", p2)), 8)
            return results, engine.stats()
        finally:
            httpd.shutdown()
            engine.stop()

    want, _ = run(n_blocks=64)                # no pressure: reference
    got, stats = run(n_blocks=9)              # both prompts fill pool
    for name in ("a", "b"):
        assert want[name][0] == 200 and got[name][0] == 200
        assert got[name][1]["tokens"] == want[name][1]["tokens"], name
    assert stats["preempted"] >= 1            # the test's point


def test_drain_finishes_accepted_work_and_refuses_new():
    """drain(): accepted requests run to completion; new arrivals get
    an immediate 503 naming the drain; the engine reports idle and
    /healthz stays 200 with state=draining (liveness must not kill a
    pod mid-drain)."""
    import threading
    import time as _time
    params = tf.init_params(jax.random.PRNGKey(6), CFG)
    engine = serve_mod.ServeEngine(params, CFG, n_slots=2, n_blocks=32,
                                   block_size=8, idle_sleep_s=0.001)
    httpd = serve_mod.serve(engine, host="127.0.0.1", port=0,
                            timeout_s=120.0)
    port = httpd.server_address[1]
    try:
        results = {}

        def go():
            results["inflight"] = _post(
                port, "/v1/completions",
                {"prompt": [3, 1, 4, 1, 5], "max_tokens": 40})

        t = threading.Thread(target=go)
        t.start()
        # wait until the request is actually active, then drain
        deadline = _time.time() + 30
        while engine.active_count() == 0 and _time.time() < deadline:
            _time.sleep(0.01)
        drained = {}

        def do_drain():
            drained["idle"] = engine.drain(timeout_s=60.0)

        dt = threading.Thread(target=do_drain)
        dt.start()
        _time.sleep(0.05)                      # drain flag is set now
        assert _get(port, "/healthz") == (200, {"ok": True,
                                                "state": "draining"})
        st, body = _post(port, "/v1/completions",
                         {"prompt": [2, 7], "max_tokens": 2})
        assert st == 503 and "draining" in body["error"]
        t.join(90)
        dt.join(90)
        assert results["inflight"][0] == 200
        assert len(results["inflight"][1]["tokens"]) == 40
        assert drained["idle"] is True
        assert engine.stats()["completed"] >= 1
    finally:
        httpd.shutdown()
        engine.stop()


def test_cli_sigterm_drains_and_exits_zero():
    """The CLI's SIGTERM path: the daemon drains and exits 0 (the
    kubelet preemption contract — grace period, then SIGKILL)."""
    import os
    import re
    import signal
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=".")
    proc = subprocess.Popen(
        [sys.executable, "-m", "tpushare.cli.serve", "--preset", "tiny",
         "--port", "0"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, cwd=str(__import__("pathlib").Path(
            __file__).parent.parent))
    try:
        # stderr is folded into the pipe: skip any startup warnings
        # until the banner line.
        port = None
        for _ in range(50):
            line = proc.stdout.readline()
            m = re.search(r"tpushare-serve on .*:(\d+) ", line)
            if m:
                port = int(m.group(1))
                break
        assert port is not None, "banner never printed"
        st, out = _post(port, "/v1/completions",
                        {"prompt": [3, 1, 4], "max_tokens": 3})
        assert st == 200 and len(out["tokens"]) == 3
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=60)
        assert rc == 0, (rc, proc.stdout.read())
    finally:
        if proc.poll() is None:
            proc.kill()


class TestMoEServe:
    """model_family="moe": the HTTP daemon serves the MoE LM through
    the same engine and slot server as the dense one (the paged pool
    under moe.paged_forward), with dense-only options rejected loudly
    and streams matching moe.generate."""

    @pytest.fixture(scope="class")
    def moe_server(self):
        from tpushare.models import moe, quant
        cfg = moe.tiny(remat=False)
        params = quant.quantize_params(
            moe.init_params(jax.random.PRNGKey(0), cfg), cfg)
        engine = serve_mod.ServeEngine(
            params, cfg, model_family="moe", n_slots=2, n_blocks=32,
            block_size=4, prefix_cache=False, idle_sleep_s=0.001,
            layers_hook=quant.dequant_hook(cfg))
        httpd = serve_mod.serve(engine, host="127.0.0.1", port=0,
                                timeout_s=120.0)
        try:
            yield httpd.server_address[1], engine, params, cfg
        finally:
            httpd.shutdown()
            engine.stop()

    def test_completion_matches_moe_generate(self, moe_server):
        import jax.numpy as jnp
        from tpushare.models import moe, quant
        port, _, params, cfg = moe_server
        prompt = [3, 1, 4, 1, 5, 9]
        status, body = _post(port, "/v1/completions",
                             {"prompt": prompt, "max_tokens": 6})
        assert status == 200, body
        want = moe.generate(params, jnp.asarray([prompt]), cfg,
                            max_new_tokens=6,
                            layers_hook=quant.dequant_hook(cfg))
        assert body["tokens"] == [int(t) for t in want[0, 6:]]

    def test_concurrent_streams_no_crosstalk(self, moe_server):
        import jax.numpy as jnp
        from tpushare.models import moe, quant
        port, _, params, cfg = moe_server
        pa, pb = [7, 2, 9], [11, 5, 6, 8]
        res = _concurrent_posts(port, [("a", pa), ("b", pb)], 5)
        for name, prompt in (("a", pa), ("b", pb)):
            status, body = res[name]
            assert status == 200, body
            want = moe.generate(params, jnp.asarray([prompt]), cfg,
                                max_new_tokens=5,
                                layers_hook=quant.dequant_hook(cfg))
            assert body["tokens"] == [int(t) for t in
                                      want[0, len(prompt):]], name

    def test_stats_and_health(self, moe_server):
        port, engine, _, _ = moe_server
        status, body = _get(port, "/stats")
        assert status == 200
        assert body["n_slots"] == 2
        # The sparse family's default is the paged pool: real counters.
        assert body["free_blocks"] + body["live_blocks"] \
            + body["reclaimable_blocks"] == 31
        assert body["model_family"] == "moe" and body["kv"] == "paged"
        assert "speculative" not in body
        status, _ = _get(port, "/healthz")
        assert status == 200

    def test_minimal_moe_engine_constructs_with_defaults(self):
        # The unsupported-check must not reject its own defaults:
        # ServeEngine(params, cfg, model_family="moe") with nothing
        # else passed is the documented minimal construction.
        from tpushare.models import moe
        cfg = moe.tiny(remat=False)
        params = moe.init_params(jax.random.PRNGKey(0), cfg)
        eng = serve_mod.ServeEngine(params, cfg, model_family="moe")
        assert eng.stats()["n_slots"] == 8
        assert eng.stats()["free_blocks"] == 255

    def test_dense_only_options_rejected(self):
        from tpushare.models import moe
        cfg = moe.tiny(remat=False)
        params = moe.init_params(jax.random.PRNGKey(0), cfg)
        with pytest.raises(ValueError, match="does not support"):
            serve_mod.ServeEngine(params, cfg, model_family="moe",
                                  kv_quant=True)
        with pytest.raises(ValueError, match="model_family"):
            serve_mod.ServeEngine(params, cfg, model_family="nope")

    def test_chunked_prefill_moe_engine(self):
        # prefill_chunk now composes with model_family="moe": long
        # admits trickle in chunks and the stream equals the unchunked
        # engine's.
        import jax.numpy as jnp
        from tpushare.models import moe
        cfg = moe.tiny(remat=False)
        params = moe.init_params(jax.random.PRNGKey(0), cfg)
        prompt = [int(t) for t in
                  np.random.default_rng(5).integers(0, cfg.vocab_size,
                                                    12)]
        out = {}
        for chunk in (None, 4):
            engine = serve_mod.ServeEngine(
                params, cfg, model_family="moe", n_slots=2, n_blocks=32,
                block_size=4, prefill_chunk=chunk, idle_sleep_s=0.001)
            httpd = serve_mod.serve(engine, host="127.0.0.1", port=0,
                                    timeout_s=120.0)
            try:
                status, body = _post(httpd.server_address[1],
                                     "/v1/completions",
                                     {"prompt": prompt,
                                      "max_tokens": 5})
                assert status == 200, body
                out[chunk] = body["tokens"]
                if chunk:
                    assert engine.stats()["chunked_admits"] >= 1
            finally:
                httpd.shutdown()
                engine.stop()
        assert out[None] == out[4]

    def test_speculative_moe_serving(self):
        # int8-self speculation over HTTP: stream equals the plain
        # engine's, /stats reports the acceptance signal.
        import jax.numpy as jnp
        from tpushare.models import moe, quant
        cfg = moe.tiny(remat=False)
        params = moe.init_params(jax.random.PRNGKey(0), cfg)
        prompt = [3, 1, 4, 1, 5, 9, 2, 6]
        out = {}
        for spec in (False, True):
            kw = {}
            if spec:
                kw = dict(
                    speculative_draft=(quant.quantize_params(params,
                                                             cfg), cfg),
                    gamma=3,
                    draft_layers_hook=quant.dequant_hook(cfg))
            engine = serve_mod.ServeEngine(
                params, cfg, model_family="moe", n_slots=2, n_blocks=64,
                block_size=4, idle_sleep_s=0.001, **kw)
            httpd = serve_mod.serve(engine, host="127.0.0.1", port=0,
                                    timeout_s=120.0)
            try:
                status, body = _post(httpd.server_address[1],
                                     "/v1/completions",
                                     {"prompt": prompt,
                                      "max_tokens": 8})
                assert status == 200, body
                out[spec] = body["tokens"]
                if spec:
                    stats = engine.stats()
                    assert stats["speculative"]["gamma"] == 3
                    assert stats["speculative"][
                        "mean_tokens_per_round"] > 1.0
            finally:
                httpd.shutdown()
                engine.stop()
        assert out[True] == out[False]

    def test_adapter_request_rejected_400(self, moe_server):
        port, *_ = moe_server
        status, body = _post(port, "/v1/completions",
                             {"prompt": [1, 2], "max_tokens": 2,
                              "adapter": 0})
        assert status == 400
