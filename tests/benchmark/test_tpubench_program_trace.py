"""The two readers of what the program writes about itself: the stage
clocks and admission counters in ``/stats`` (``readers/stage.py``) and
its spans and program names in the device trace
(``readers/program_trace.py``); on hand-made events, and the second on
a small trace recorded on the chip with the spans in it, checked in
beside this file."""

import json
import os
import types

import pytest

from tpubench import spec
from tpubench.readers import program_trace as pt
from tpubench.readers import stage

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "v5e_chat_program_slice.xplane.pb")
MS = 1e6    # events are in nanoseconds


def _ctx(before, after, cell="mistral7b-l16.chat", rehearse=False):
    return types.SimpleNamespace(
        stats_before=before, stats_after=after, window_s=40.0,
        cell=types.SimpleNamespace(name=cell, rehearse=rehearse))


# -- readers/stage.py -------------------------------------------------------

STAGES0 = {"preamble": 1.0, "admit": 100.0, "finalize": 1000.0,
           "apply": 10.0, "schedule": 2.0, "dispatch": 200.0, "plan": 1.0,
           "journal": 0.0, "idle": 50.0}
STAGES1 = {"preamble": 3.0, "admit": 400.0, "finalize": 4000.0,
           "apply": 40.0, "schedule": 8.0, "dispatch": 700.0, "plan": 4.0,
           "journal": 0.0, "idle": 50.0}


def test_stage_ms_per_tick_is_a_delta_over_work_ticks():
    ctx = _ctx({"engine_thread_ms": STAGES0, "work_ticks": 10},
               {"engine_thread_ms": STAGES1, "work_ticks": 110})
    assert stage.read(ctx, "ms_per_tick", "dispatch") == pytest.approx(5.0)
    assert stage.read(ctx, "ms_per_tick", "finalize") == pytest.approx(30.0)
    # host: every stage but the deferred fetch and the idle sleep
    host = (2 + 300 + 30 + 6 + 500 + 3 + 0) / 100
    assert stage.read(ctx, "ms_per_tick", "host") == pytest.approx(host)


def test_the_host_sum_logs_the_whole_split_once(capsys):
    ctx = _ctx({"engine_thread_ms": STAGES0, "work_ticks": 10},
               {"engine_thread_ms": STAGES1, "work_ticks": 110})
    stage.read(ctx, "ms_per_tick", "dispatch")
    assert capsys.readouterr().out == ""
    stage.read(ctx, "ms_per_tick", "host")
    out = capsys.readouterr().out
    assert out.count("[tpubench stage]") == 1
    assert '"finalize": 30.0' in out and "stages hold 3841 of" in out


def test_stage_ms_per_divides_a_sum_by_its_count():
    ctx = _ctx({"queue_wait_ms_sum": 10.0, "queue_wait_n": 2,
                "admit_ms_sum": 50.0, "admit_n": 2},
               {"queue_wait_ms_sum": 250.0, "queue_wait_n": 10,
                "admit_ms_sum": 1050.0, "admit_n": 12})
    assert stage.read(ctx, "ms_per", "queue_wait") == pytest.approx(30.0)
    assert stage.read(ctx, "ms_per", "admit") == pytest.approx(100.0)


def test_stage_reader_returns_none_on_a_program_without_the_keys():
    parent = _ctx({"work_ticks": 10}, {"work_ticks": 110})
    assert stage.read(parent, "ms_per_tick", "host") is None
    assert stage.read(parent, "ms_per_tick", "dispatch") is None
    assert stage.read(parent, "ms_per", "queue_wait") is None
    idle = _ctx({"engine_thread_ms": STAGES0, "work_ticks": 10,
                 "admit_ms_sum": 1.0, "admit_n": 3},
                {"engine_thread_ms": STAGES1, "work_ticks": 10,
                 "admit_ms_sum": 1.0, "admit_n": 3})
    assert stage.read(idle, "ms_per_tick", "host") is None      # no tick
    assert stage.read(idle, "ms_per", "admit") is None      # no admission
    renamed = _ctx({"engine_thread_ms": {"launch": 1.0}, "work_ticks": 1},
                   {"engine_thread_ms": {"launch": 2.0}, "work_ticks": 2})
    assert stage.read(renamed, "ms_per_tick", "dispatch") is None
    with pytest.raises(ValueError):
        stage.read(parent, "p50", "dispatch")


# -- readers/program_trace.py on hand-made events ---------------------------

def test_intersect_and_innermost():
    assert pt.intersect([(0, 4), (6, 9)], [(1, 2), (3, 7), (8, 12)]) == \
        [(1, 2), (3, 4), (6, 7), (8, 9)]
    assert pt.intersect([], [(0, 1)]) == []
    spans = [("engine.dispatch", 10, 30), ("slot.grow", 12, 15),
             ("slot.launch", 15, 25), ("engine.plan", 31, 32),
             ("engine.admit", 40, 60), ("slot.admit.prefill", 45, 50),
             ("deep", 46, 47)]
    assert pt.innermost(spans) == [
        ("engine.dispatch", 10, 12), ("slot.grow", 12, 15),
        ("slot.launch", 15, 25), ("engine.dispatch", 25, 30),
        ("engine.plan", 31, 32), ("engine.admit", 40, 45),
        ("slot.admit.prefill", 45, 46), ("deep", 46, 47),
        ("slot.admit.prefill", 47, 50), ("engine.admit", 50, 60)]


def _two_ticks():
    """Two ticks of 100 ms. Device: a sampler tail, idle, the decode
    program; the second tick's decode is a fused program. Engine thread:
    finalize (the fetch), apply, dispatch (grow, launch), plan."""
    dev = "/device:TPU:0"
    ops, mods, eng = [], [], []
    for k, prog in enumerate(("jit_paged_decode(11)", "jit_paged_fused(22)")):
        t = k * 100 * MS
        # idle 0-12 ms, then the program 12-90, then two eager ones
        mods += [(prog, t + 12 * MS, t + 90 * MS),
                 ("jit__sample_guarded(5)", t + 92 * MS, t + 93 * MS),
                 ("jit__where(6)", t + 95 * MS, t + 96 * MS)]
        ops += [(t + 12 * MS, t + 50 * MS), (t + 50 * MS, t + 90 * MS),
                (t + 92 * MS, t + 93 * MS), (t + 95 * MS, t + 96 * MS)]
        eng += [("engine.finalize", t + 0 * MS, t + 3 * MS),
                ("slot.fetch", t + 0.5 * MS, t + 3 * MS),
                ("engine.apply", t + 3 * MS, t + 4 * MS),
                ("engine.dispatch", t + 4 * MS, t + 14 * MS),
                ("slot.grow", t + 4 * MS, t + 7 * MS),
                ("slot.launch", t + 7 * MS, t + 13 * MS),
                ("slot.sample", t + 13 * MS, t + 14 * MS),
                ("engine.plan", t + 14 * MS, t + 15 * MS)]
    http = [("http.write", 5 * MS, 6 * MS), ("http.write", 105 * MS, 106 * MS)]
    return {"ops": {dev: ops}, "modules": {dev: mods},
            "threads": [http, eng], "tagged": []}


def test_reduce_a_hand_made_trace():
    red = pt.reduce(_two_ticks())
    assert red["ticks"] == 2 and red["programs"] == 6
    assert red["window_ms"] == pytest.approx(196.0)
    assert red["busy_ms"] == pytest.approx(160.0)
    assert red["idle_ms"] == pytest.approx(36.0)
    # idle 0-12 of each tick: fetch 2.5, finalize's own 0.5, apply 1,
    # grow 3, launch 5 (the program starts at 12, inside launch)
    inner = red["idle_innermost_ms"]
    assert inner["slot.fetch"] == pytest.approx(5.0)
    assert inner["engine.finalize"] == pytest.approx(1.0)
    assert inner["engine.apply"] == pytest.approx(2.0)
    assert inner["slot.grow"] == pytest.approx(6.0)
    assert inner["slot.launch"] == pytest.approx(10.0)
    assert inner.get("engine.dispatch", 0) == pytest.approx(0.0)
    # 90-92, 93-95 of each tick and 96-100 of the first: under no span
    assert inner[pt.NO_SPAN] == pytest.approx(12.0)
    assert sum(inner.values()) == pytest.approx(red["idle_ms"])
    assert list(inner)[0] == pt.NO_SPAN           # sorted, largest first
    # a span's own time is its whole length, children included
    assert red["span_ms"]["engine.dispatch"] == pytest.approx(20.0)
    assert red["span_ms"]["slot.launch"] == pytest.approx(12.0)
    # the handler thread's two writes, both while the device is idle
    assert red["writes"] == 2 and red["write_ms"] == pytest.approx(2.0)
    assert red["write_p50_ms"] == pytest.approx(1.0)
    assert red["idle_under_writes_ms"] == pytest.approx(2.0)
    assert red["requests"] == []
    assert red["module_busy_ms"]["jit_paged_decode"] == pytest.approx(78.0)
    assert red["module_busy_ms"]["jit_paged_fused"] == pytest.approx(78.0)
    assert "2 ticks, 6 programs (3.0 a tick)" in pt.table(red)
    json.dumps(red)


def test_request_paths_join_accept_admit_and_first_write_by_rid():
    tagged = [
        # r1: accepted, popped and held once, then placed; two events
        (pt.ACCEPT, 10 * MS, 11 * MS, {"rid": "r1"}),
        (pt.ADMIT, 20 * MS, 21 * MS, {"rid": "r1", "prompt_tokens": 40}),
        (pt.ADMIT, 31 * MS, 81 * MS, {"rid": "r1", "prompt_tokens": 40,
                                      "chunked": 0, "cached_tokens": 16}),
        (pt.WRITE, 95 * MS, 96 * MS, {"rid": "r1"}),
        (pt.WRITE, 80 * MS, 82 * MS, {"rid": "r1"}),
        # r0: arrived first, chunked, did not stream (or not yet)
        (pt.ACCEPT, 1 * MS, 3 * MS, {"rid": "r0"}),
        (pt.ADMIT, 4 * MS, 9 * MS, {"rid": "r0", "prompt_tokens": 900,
                                    "chunked": 1, "cached_tokens": 0}),
        # accepted before the slice began: no row
        (pt.ADMIT, 100 * MS, 120 * MS, {"rid": "old", "prompt_tokens": 8}),
        (pt.WRITE, 119 * MS, 120 * MS, {"rid": "old"}),
    ]
    r0, r1 = pt.request_paths(tagged)
    assert (r0["rid"], r0["prompt_tokens"], r0["chunked"]) == ("r0", 900, 1)
    assert r0["accept_ms"] == pytest.approx(2.0)
    assert r0["queue_ms"] == pytest.approx(1.0)
    assert r0["admit_ms"] == pytest.approx(5.0)
    assert r0["admit_to_first_write_ms"] is None
    assert (r1["rid"], r1["cached_tokens"]) == ("r1", 16)
    assert r1["queue_ms"] == pytest.approx(20.0)    # to the pop that placed
    assert r1["admit_ms"] == pytest.approx(50.0)
    assert r1["admit_to_first_write_ms"] == pytest.approx(51.0)
    t = dict(_two_ticks(), tagged=tagged)
    out = pt.table(pt.reduce(t))
    assert "http.write on the handler threads: 2 events (1.0 a tick)" in out
    lines = out.splitlines()
    assert lines[-3].startswith("request") and lines[-2].startswith("r0 ")
    assert lines[-2].rstrip().endswith("-") and lines[-1].startswith("r1 ")


def test_a_trace_without_the_programs_spans_reduces_to_nothing():
    t = _two_ticks()
    assert pt.reduce(dict(t, threads=[])) is None
    assert pt.reduce(dict(t, threads=[t["threads"][0]])) is None   # http only
    assert pt.reduce(dict(t, ops={})) is None


def _write_trace(monkeypatch, tmp_path, t, cell="mistral7b-l16.chat"):
    """Put ``t`` where ``read`` looks for the traced run's file."""
    d = tmp_path / "tpubench_out" / (cell + ".trace") / "trace" / \
        "plugins" / "profile" / "2026_01_01_00_00_00"
    d.mkdir(parents=True)
    (d / "vm.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(spec, "ROOT", str(tmp_path))
    monkeypatch.setattr(pt, "load", lambda path: t)
    pt._reduced.cache_clear()


def test_read_serves_its_values_and_logs_the_table_once(
        monkeypatch, tmp_path, capsys):
    _write_trace(monkeypatch, tmp_path, _two_ticks())
    ctx = _ctx({}, {})
    assert pt.read(ctx, "idle_ms_per_tick",
                   spans=["slot.grow", "slot.launch"]) == pytest.approx(8.0)
    assert pt.read(ctx, "programs_per_tick") == pytest.approx(3.0)
    assert pt.read(ctx, "module_busy_pct",
                   prefixes=["paged_prefill", "paged_fused"]) == \
        pytest.approx(100 * 78 / 160)
    assert pt.read(ctx, "idle_ms_per_tick", spans=["slot.renamed"]) is None
    assert pt.read(ctx, "write_ms_per_tick") == pytest.approx(1.0)
    out = capsys.readouterr().out
    assert out.count("idle under the innermost span") == 1
    assert "slot.launch" in out
    with pytest.raises(ValueError):
        pt.read(ctx, "p50")
    # no traced run of this cell: nothing to read, nothing raised
    assert pt.read(_ctx({}, {}, cell="mixtral8x7b-l4.chat-batch"),
                   "programs_per_tick") is None
    pt._reduced.cache_clear()


def test_read_returns_none_on_the_parents_trace(monkeypatch, tmp_path):
    """The program before this PR: ``tpubench.*`` wrappers only, and a
    decode program named ``jit__unknown``."""
    t = _two_ticks()
    t["threads"] = []
    t["modules"] = {k: [(n.replace("paged_decode", "_unknown")
                         .replace("paged_fused", "_unknown"), s, e)
                        for n, s, e in v] for k, v in t["modules"].items()}
    _write_trace(monkeypatch, tmp_path, t)
    ctx = _ctx({}, {})
    for value, kw in (("idle_ms_per_tick", {"spans": ["slot.grow"]}),
                      ("programs_per_tick", {}), ("write_ms_per_tick", {}),
                      ("module_busy_pct", {"prefixes": ["paged_fused"]})):
        assert pt.read(ctx, value, **kw) is None
    pt._reduced.cache_clear()


def test_admission_share_is_zero_not_absent_where_programs_are_named(
        monkeypatch, tmp_path):
    t = _two_ticks()
    t["modules"] = {k: [(n.replace("paged_fused", "paged_decode"), s, e)
                        for n, s, e in v] for k, v in t["modules"].items()}
    _write_trace(monkeypatch, tmp_path, t)
    assert pt.read(_ctx({}, {}), "module_busy_pct",
                   prefixes=["paged_prefill", "paged_fused"]) == 0.0
    pt._reduced.cache_clear()


def test_the_new_metrics_name_readers_and_arguments_that_exist():
    bench = spec.benchmark()
    new = ("engine.queue_wait_ms", "engine.admit_ms",
           "engine.host_ms_per_tick", "engine.dispatch_ms_per_tick",
           "slot.prelaunch_idle_ms", "slot.programs_per_tick",
           "device.admission_busy_pct", "http.write_ms_per_tick")
    entries = {m["name"]: m for m in bench["per_layer"]}
    t = _two_ticks()
    full = _ctx({"engine_thread_ms": STAGES0, "work_ticks": 10,
                 "queue_wait_ms_sum": 0.0, "queue_wait_n": 0,
                 "admit_ms_sum": 0.0, "admit_n": 0},
                {"engine_thread_ms": STAGES1, "work_ticks": 110,
                 "queue_wait_ms_sum": 9.0, "queue_wait_n": 3,
                 "admit_ms_sum": 90.0, "admit_n": 3})
    for name in new:
        m = entries[name]
        assert "workloads" not in m and m["moves"] == "itl_p50_ms"
        assert m["better"] == "lower"
        lm = spec.layer_metric(name)
        assert lm["what"]
        if lm["reader"] == "stage":
            assert stage.read(full, **lm["args"]) > 0
        else:
            assert lm["reader"] == "program_trace"
            red = pt.reduce(t)
            assert all(s in red["span_ms"]
                       for s in lm["args"].get("spans", []))
    # every cell reports them
    for w in bench["workloads"]:
        assert set(new) <= set(spec.load_cell(w["name"]).per_layer)


# -- the recorded trace: 0.54 s (six ticks, one whole-prompt admission
# of 320 tokens) cut from the traced run of mistral7b-l16.chat on a v5e
# (PR 25, seed 2500000011): the device's op and module lines and every
# tpushare.* / tpubench.* host span, the spans' stats kept -------------------

def test_recorded_v5e_trace_holds_the_programs_spans_and_names():
    t = pt.load(RECORDED)
    assert list(t["ops"]) == list(t["modules"]) == ["/device:TPU:0"]
    assert len(t["ops"]["/device:TPU:0"]) == 10677
    mods = {n.split("(")[0] for n, _, _ in t["modules"]["/device:TPU:0"]}
    assert {"jit_paged_decode", "jit_paged_prefill"} <= mods
    assert "jit__unknown" not in mods
    engine = max(t["threads"], key=len)
    names = {n for n, _, _ in engine}
    assert {"engine." + s for s in (
        "preamble", "admit", "finalize", "apply", "schedule", "dispatch",
        "plan")} <= names
    assert {"slot.grow", "slot.launch", "slot.sample", "slot.mirror",
            "slot.fetch", "slot.admit.lookup", "slot.admit.row",
            "slot.admit.prefill", "slot.admit.scatter",
            "slot.admit.first_token"} <= names
    # the engine's stages tile its thread: none overlaps the next
    stages = sorted((s, e) for n, s, e in engine if n.startswith("engine."))
    assert all(b[0] >= a[1] for a, b in zip(stages, stages[1:]))
    # and every slot span lies inside a stage that may hold it
    holders = [(s, e) for n, s, e in engine
               if n in ("engine.dispatch", "engine.admit",
                        "engine.finalize")]
    for n, s, e in engine:
        if n.startswith("slot."):
            assert any(a <= s and e <= b for a, b in holders), n
    # the handler threads wrote their events beside it, under one id each
    others = [sp for sp in t["threads"] if sp is not engine]
    assert others and all(n in ("http.accept", "http.write")
                          for sp in others for n, _, _ in sp)


def test_recorded_v5e_trace_reduces_to_the_numbers_it_held():
    red = pt.reduce(pt.load(RECORDED))
    assert red["ticks"] == 6 and red["programs"] == 189
    assert red["window_ms"] == pytest.approx(538.03, rel=1e-4)
    assert red["busy_ms"] == pytest.approx(428.11, rel=1e-4)
    inner = red["idle_innermost_ms"]
    assert sum(inner.values()) == pytest.approx(red["idle_ms"], rel=1e-9)
    assert inner[pt.NO_SPAN] < 0.03 * red["idle_ms"]    # spans tile the thread
    # the one admission's eager pool scatter leaves the device idlest,
    # then block growth; the launch itself is not where the wait is
    assert list(inner)[:2] == ["slot.admit.scatter", "slot.grow"]
    assert inner["slot.grow"] == pytest.approx(16.79, rel=1e-3)
    assert inner["slot.launch"] == pytest.approx(12.48, rel=1e-3)
    assert red["module_busy_ms"]["jit_paged_decode"] == \
        pytest.approx(389.12, rel=1e-4)
    assert red["module_busy_ms"]["jit_paged_prefill"] == \
        pytest.approx(23.48, rel=1e-3)
    # the old reducer reads the same file as before: its wrappers are there
    from tpubench.readers import trace
    old = trace.reduce(trace.load(RECORDED))
    assert old["busy_s"] * 1e3 == pytest.approx(red["busy_ms"], rel=1e-9)
    assert {n for n, _ in old["idle_gaps"]} <= {
        "step_async", "token_fetch", "admit", "engine loop"}


def test_recorded_v5e_trace_follows_the_one_request_it_saw_arrive():
    red = pt.reduce(pt.load(RECORDED))
    (row,) = red["requests"]
    assert row["rid"].startswith("cab9edb5") and row["prompt_tokens"] == 256
    assert (row["chunked"], row["cached_tokens"]) == (0, 0)
    assert row["accept_ms"] == pytest.approx(0.218, rel=1e-2)
    assert row["queue_ms"] == pytest.approx(15.001, rel=1e-3)
    assert row["admit_ms"] == pytest.approx(81.340, rel=1e-3)
    # the first event leaves as soon as the admission is over
    assert row["admit_to_first_write_ms"] == pytest.approx(81.751, rel=1e-3)
    # eighteen streams' events a tick; one in the herd takes 0.9 ms,
    # the first of a stream, alone, a tenth of that
    assert red["writes"] == 106
    assert red["write_ms"] / red["ticks"] == pytest.approx(15.628, rel=1e-3)
    assert red["write_p50_ms"] == pytest.approx(0.905, rel=1e-2)


def test_the_trace_metrics_on_the_recorded_trace(monkeypatch, tmp_path):
    recorded = pt.load(RECORDED)
    _write_trace(monkeypatch, tmp_path, recorded)
    ctx = _ctx({}, {})
    got = {name: pt.read(ctx, **spec.layer_metric(name)["args"])
           for name in ("slot.prelaunch_idle_ms", "slot.programs_per_tick",
                        "device.admission_busy_pct",
                        "http.write_ms_per_tick")}
    assert got["slot.prelaunch_idle_ms"] == pytest.approx(
        (16.793 + 12.479) / 6, rel=1e-3)
    assert got["slot.programs_per_tick"] == pytest.approx(31.5)
    assert got["device.admission_busy_pct"] == pytest.approx(
        100 * 23.483 / 428.11, rel=1e-3)
    assert got["http.write_ms_per_tick"] == pytest.approx(15.628, rel=1e-3)
    pt._reduced.cache_clear()
