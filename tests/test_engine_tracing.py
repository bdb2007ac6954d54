"""The engine traces itself (ISSUE 25): stage spans and stage clocks on
the engine's thread, ``slot.*`` spans inside them, ``http.*`` spans on
the handler threads sharing the request's id, queue wait and admission
time as counters, and stable names for the paged server's programs.

The traced fixtures run a toy paged engine behind its real HTTP front
door under a CPU ``jax.profiler`` session and read the spans back with
``jax.profiler.ProfileData``: the path the benchmark's traced run
takes on the chip."""

import glob
import json
import threading
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpushare.cli.serve import ENGINE_STAGES, ServeEngine, _Request, serve
from tpushare.models import transformer as tf
from tpushare.models.paged import PagedSlotServer
from tpushare.utils.profiling import SPAN_PREFIX, StageClock, span

CFG = tf.tiny(remat=False)
PARAMS = tf.init_params(jax.random.PRNGKey(0), CFG)
MODES = ("overlap", "serial")


def make_engine(mode, **kw):
    kw.setdefault("chaos_spec", "")     # never inherit the session env
    return ServeEngine(PARAMS, CFG, n_slots=2, n_blocks=48, block_size=8,
                       prefill_chunk=8, overlap_tick=(mode == "overlap"),
                       **kw)


def _prompt(rng, n):
    return [int(t) for t in rng.integers(0, CFG.vocab_size, n)]


def _post(port, prompt, max_tokens, stream):
    body = json.dumps({"prompt": prompt, "max_tokens": max_tokens,
                       "stream": stream}).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/completions", data=body)
    with urllib.request.urlopen(req, timeout=300) as r:
        return r.headers.get("X-Request-Id"), r.read()


def _spans(trace_dir):
    """{thread line: [(name, start_ns, end_ns, stats)]} of the
    program's own spans, prefix taken off."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(
        f"{trace_dir}/plugins/profile/*/*.xplane.pb"))[-1]
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            evs = [(e.name[len(SPAN_PREFIX):], e.start_ns,
                    e.start_ns + e.duration_ns, dict(e.stats))
                   for e in line.events if e.name.startswith(SPAN_PREFIX)]
            if evs:
                out[(line.name, i)] = sorted(evs, key=lambda e: e[1])
    return out


@pytest.fixture(scope="module", params=MODES)
def traced(request, tmp_path_factory):
    """One traced run per tick mode: a journaled engine (so the journal
    stage exists) serves four streamed requests on two slots, then
    idles; /stats and the wall clock are read around the session."""
    mode = request.param
    tmp = tmp_path_factory.mktemp("trace-" + mode)
    eng = make_engine(mode, idle_sleep_s=0.002,
                      journal_dir=str(tmp / "journal"))
    httpd = serve(eng, port=0)
    port = httpd.server_address[1]
    rng = np.random.default_rng(1)
    try:
        for n in (5, 13, 21):           # every program the run will use
            _post(port, _prompt(rng, n), 4, False)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        # the wall clock on both sides of each snapshot: on a loaded
        # machine /stats takes milliseconds, and the stages run on
        t0_lo = time.monotonic()
        before = eng.stats()
        t0_hi = time.monotonic()
        jax.profiler.start_trace(str(tmp / "trace"), profiler_options=opts)
        try:
            rids = [None] * 4
            def client(i, n):
                rids[i], _ = _post(port, _prompt(rng, n), 8, True)
            threads = [threading.Thread(target=client, args=(i, n))
                       for i, n in enumerate((5, 13, 21, 9))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=300)
            assert not any(t.is_alive() for t in threads)
            time.sleep(0.05)            # a few idle iterations
        finally:
            jax.profiler.stop_trace()
        t1_lo = time.monotonic()
        after = eng.stats()
        t1_hi = time.monotonic()
    finally:
        httpd.shutdown()
        httpd.server_close()
        eng.stop()
    lines = _spans(str(tmp / "trace"))
    engine_line = [evs for evs in lines.values()
                   if any(n.startswith("engine.") for n, *_ in evs)]
    assert len(engine_line) == 1, "engine spans on more than one thread"
    return {"mode": mode, "engine": engine_line[0], "lines": lines,
            "rids": rids, "before": before, "after": after,
            "wall_ms": (t1_lo - t0_hi) * 1e3,       # at least
            "wall_max_ms": (t1_hi - t0_lo) * 1e3}   # and at most


def _stages(traced):
    return [e for e in traced["engine"] if e[0].startswith("engine.")]


def test_every_stage_appears_as_a_span(traced):
    names = {n[len("engine."):] for n, *_ in _stages(traced)}
    want = set(ENGINE_STAGES)
    if traced["mode"] == "serial":      # no deferred fetch, no window
        want -= {"finalize", "plan"}
    assert names == want


def test_stage_spans_on_the_engine_thread_never_overlap(traced):
    stages = _stages(traced)
    assert len(stages) > 50
    for (_, _, end, _), (name, start, _, _) in zip(stages, stages[1:]):
        assert start >= end, name


def test_every_slot_span_lies_inside_a_stage_that_may_hold_it(traced):
    holders = [(s, e) for n, s, e, _ in _stages(traced)
               if n in ("engine.dispatch", "engine.admit",
                        "engine.finalize")]
    slot = [e for e in traced["engine"] if e[0].startswith("slot.")]
    names = {n for n, *_ in slot}
    assert {"slot.grow", "slot.launch", "slot.sample", "slot.mirror",
            "slot.fetch", "slot.admit.lookup", "slot.admit.row",
            "slot.admit.prefill", "slot.admit.scatter",
            "slot.admit.first_token"} <= names
    for name, start, end, _ in slot:
        assert any(s <= start and end <= e for s, e in holders), name
    # and nowhere else: the slot server is driven by the engine's thread
    others = [n for evs in traced["lines"].values()
              if evs is not traced["engine"] for n, *_ in evs]
    assert others and all(n.startswith("http.") for n in others)


def test_spans_of_one_request_share_its_id(traced):
    admits = [st for n, _, _, st in traced["engine"] if n == "engine.admit"]
    assert all({"rid", "prompt_tokens"} <= set(st) for st in admits)
    placed = {st["rid"]: st for st in admits if "cached_tokens" in st}
    accepts, writes = {}, {}
    for evs in traced["lines"].values():
        for n, _, _, st in evs:
            if n == "http.accept":
                accepts[st["rid"]] = st
            elif n == "http.write":
                writes[st["rid"]] = writes.get(st["rid"], 0) + 1
    for rid in traced["rids"]:          # the X-Request-Id the client saw
        assert rid in accepts and rid in placed
        assert writes[rid] == 8 + 1     # eight tokens and the done event
    by_len = sorted(int(st["prompt_tokens"]) for st in placed.values())
    assert by_len == [5, 9, 13, 21]
    assert {int(st["chunked"]) for st in placed.values()} == {0, 1}


def test_stage_clocks_sum_to_the_threads_wall_clock(traced):
    ms0 = traced["before"]["engine_thread_ms"]
    ms1 = traced["after"]["engine_thread_ms"]
    assert set(ms0) == set(ms1) == set(ENGINE_STAGES)
    assert all(ms1[k] >= ms0[k] for k in ms1)           # monotone
    n0 = traced["before"]["engine_thread_n"]
    n1 = traced["after"]["engine_thread_n"]
    assert all(n1[k] >= n0[k] for k in n1)
    staged = sum(ms1[k] - ms0[k] for k in ms1)
    assert staged <= traced["wall_max_ms"] * 1.001
    assert staged >= traced["wall_ms"] * 0.98           # remainder < 2 %
    if traced["mode"] == "serial":
        assert ms1["finalize"] == 0 and ms1["plan"] == 0
        assert traced["after"]["host_gap_ms"] is None
    else:
        assert ms1["finalize"] > ms0["finalize"]
    assert ms1["journal"] > ms0["journal"] and ms1["idle"] > ms0["idle"]
    # the spans of the session are the entries the clocks counted (the
    # stages of work: the engine also idles while the session starts)
    seen = {}
    for n, *_ in _stages(traced):
        seen[n[len("engine."):]] = seen.get(n[len("engine."):], 0) + 1
    for k in ("admit", "dispatch", "apply", "finalize", "plan"):
        assert seen.get(k, 0) == n1[k] - n0[k], k


def test_the_ahead_counters_say_how_often_the_engine_ran_ahead(traced):
    """/stats ``ahead_ticks`` (work ticks dispatched with an older tick
    still owed) and ``ahead_dropped_tokens`` (rows such a tick computed
    for a stream that had ended), beside ``work_ticks``; and on the
    trace, a tick that ran ahead is a ``dispatch`` span with the
    ``finalize`` of the older tick behind it."""
    b, a = traced["before"], traced["after"]
    ahead = a["ahead_ticks"] - b["ahead_ticks"]
    dropped = a["ahead_dropped_tokens"] - b["ahead_dropped_tokens"]
    # admissions of what arrived during the dispatch go in between
    names = [n for n, *_ in _stages(traced) if n != "engine.admit"]
    behind = sum(x == "engine.dispatch" and y == "engine.finalize"
                 for x, y in zip(names, names[1:]))
    if traced["mode"] == "serial":
        assert a["ahead_ticks"] == 0 and a["ahead_dropped_tokens"] == 0
        assert behind == 0
        return
    assert 0 < ahead <= a["work_ticks"] - b["work_ticks"]
    assert behind == ahead
    # a request that ends leaves at most one row in the tick ahead
    assert 0 <= dropped <= a["completed"] - b["completed"] == 4


def test_queue_wait_and_admission_time_count_each_admission_once(traced):
    b, a = traced["before"], traced["after"]
    assert a["queue_wait_n"] - b["queue_wait_n"] == 4
    assert a["admit_n"] - b["admit_n"] == 4
    wait = a["queue_wait_ms_sum"] - b["queue_wait_ms_sum"]
    admit = a["admit_ms_sum"] - b["admit_ms_sum"]
    assert wait > 0 and admit > 0
    # four requests on two slots: two of them waited for a slot, and
    # no first token took longer than the session
    assert wait + admit < 4 * traced["wall_ms"]


@pytest.mark.parametrize("mode", MODES)
def test_a_replayed_request_is_counted_at_its_first_life_only(mode):
    eng = make_engine(mode, idle_sleep_s=0.0)
    rng = np.random.default_rng(3)
    reqs = [_Request(_prompt(rng, n), 12, None) for n in (6, 19)]
    for r in reqs:
        assert eng.submit(r)
    for _ in range(200):
        if all(len(r.tokens) >= 2 for r in reqs):
            break
        eng._loop_once()
    first_admit = [r.t_admit for r in reqs]
    assert all(t is not None for t in first_admit)
    eng._quarantine_inflight("test: replay everything")
    for _ in range(3000):
        if all(r.done.is_set() for r in reqs):
            break
        eng._loop_once()
    assert all(r.error is None and len(r.tokens) == 12 for r in reqs)
    st = eng.stats()
    assert st["replays"] == 2
    assert st["queue_wait_n"] == 2 and st["admit_n"] == 2
    assert [r.t_admit for r in reqs] == first_admit
    assert st["queue_wait_ms_sum"] == pytest.approx(sum(
        (r.t_admit - r.t_submit) * 1e3 for r in reqs))
    assert st["admit_ms_sum"] == pytest.approx(sum(
        (r.t_first - r.t_admit) * 1e3 for r in reqs))


def test_a_held_pop_does_not_stamp_the_request():
    eng = make_engine("overlap", idle_sleep_s=0.0)
    rng = np.random.default_rng(4)
    reqs = [_Request(_prompt(rng, 6), 6, None) for _ in range(3)]
    for r in reqs:
        assert eng.submit(r)
    for _ in range(3):
        eng._loop_once()
    # two slots: the third request was popped, found no slot, went back
    assert [r.t_admit is not None for r in reqs] == [True, True, False]
    assert eng.stats()["queue_wait_n"] == 2
    assert eng.stats()["engine_thread_n"]["admit"] > 2


def test_stage_clock_and_span_cost_nothing_they_should_not():
    clock = StageClock("t", ("a", "b"))
    with clock.stage("a", k=1) as sp:
        sp.set_metadata(more=2)
        time.sleep(0.002)
    with pytest.raises(KeyError):       # stages are named up front
        with clock.stage("c"):
            pass
    with pytest.raises(RuntimeError):   # a raise inside is still timed
        with clock.stage("b"):
            raise RuntimeError("x")
    snap = clock.snapshot()
    assert snap["n"] == {"a": 1, "b": 1}
    assert snap["ms"]["a"] >= 2.0 and snap["ms"]["b"] >= 0.0
    snap["ms"]["a"] = -1                # a copy, not the clock's own
    assert clock.ms["a"] >= 2.0
    # with no profiler session a span is a flag test: well under 5 us
    t0 = time.perf_counter()
    for _ in range(2000):
        with span("x", a=1):
            pass
    assert (time.perf_counter() - t0) / 2000 < 50e-6


@pytest.mark.parametrize("family", ("dense", "moe"))
@pytest.mark.parametrize("draft", (False, True), ids=("target", "draft"))
def test_the_paged_programs_carry_stable_names(draft, family):
    """The three program names the trace readers key on, whichever
    forward function the one slot server runs."""
    if family == "moe":
        from tpushare.models import moe
        cfg = moe.tiny(remat=False)
        params = moe.init_params(jax.random.PRNGKey(0), cfg)
        fkw, init_cache = {"forward_fn": moe.paged_forward}, moe.init_cache
    else:
        cfg, params, fkw, init_cache = CFG, PARAMS, {}, tf.init_cache
    kw = ({"speculative_draft": (params, cfg), "gamma": 2} if draft else {})
    srv = PagedSlotServer(params, cfg, n_slots=2, n_blocks=16,
                          block_size=8, **fkw, **kw)
    pre = "draft_" if draft else ""
    c = srv.cache
    toks = jnp.zeros((2, 1), jnp.int32)
    active = jnp.ones((2,), bool)
    decode = srv._draft_decode if draft else srv._decode
    fused = srv._draft_fused if draft else srv._fused
    prefill = srv._draft_prefill if draft else srv._prefill
    text = decode.lower(params, toks, c.pool_k, c.pool_v, c.block_table,
                        c.lengths, active).as_text()
    assert f"module @jit_{pre}paged_decode " in text
    text = fused.lower(params, toks, c.pool_k, c.pool_v, c.block_table,
                       c.lengths, active, np.full((2, 1), -1, np.int32),
                       np.zeros((8,), np.int32), np.int32(1), np.int32(0),
                       np.int32(8)).as_text()
    assert f"module @jit_{pre}paged_fused " in text
    if not draft:       # a speculative round's verify: the same forward
        text = srv._verify.lower(params, jnp.zeros((2, 8), jnp.int32),
                                 c.pool_k, c.pool_v, c.block_table,
                                 c.lengths, active).as_text()
        assert "module @jit_paged_fused " in text
    row = init_cache(cfg, 1, 16)
    text = prefill.lower(params, jnp.zeros((1, 16), jnp.int32), cache=row,
                         pos_offset=0).as_text()
    assert f"module @jit_{pre}paged_prefill " in text
