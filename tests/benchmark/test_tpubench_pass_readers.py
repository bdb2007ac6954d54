"""The two readers of the engine's pass ledger (ISSUE 37): the per-kind
split and the thread-CPU clocks in ``/stats`` (``readers/pass_clock.py``)
and the device's idle time inside ``tpushare.pass`` spans of a kind
(``readers/pass_trace.py``); on synthetic ``/stats`` and a hand-built
trace, and on a parent's ``/stats``, which has none of it."""

import types

import pytest

from tpubench.readers import pass_clock, pass_trace
from tpubench.readers import program_trace as pt

MS = 1e6    # events are in nanoseconds


def _ctx(before, after, cell="mistral7b-l16.chat"):
    return types.SimpleNamespace(
        stats_before=before, stats_after=after, window_s=40.0,
        cell=types.SimpleNamespace(name=cell, rehearse=False))


def _rec(n, wall, wait, cpu):
    return {"n": n, "wall_ms": wall, "wait_ms": wait, "cpu_ms": cpu}


STAGES = ("preamble", "admit", "finalize", "apply", "schedule", "dispatch",
          "plan", "journal", "idle")
BEFORE = {
    "work_ticks": 10, "http_turns": 50, "http_turn_cpu_ms": 5.0,
    "engine_pass": {"idle": _rec(5, 50.0, 0.0, 1.0),
                    "decode": _rec(10, 100.0, 30.0, 40.0),
                    "admit": _rec(1, 20.0, 0.0, 5.0),
                    "fused": _rec(0, 0.0, 0.0, 0.0)},
    "engine_thread_cpu_ms": {s: 1.0 for s in STAGES},
}
AFTER = {
    "work_ticks": 110, "http_turns": 1650, "http_turn_cpu_ms": 205.0,
    # 100 decode passes of 11 ms: 2 waiting, 6 working, 3 neither
    "engine_pass": {"idle": _rec(9, 90.0, 0.0, 2.0),
                    "decode": _rec(110, 1200.0, 230.0, 640.0),
                    "admit": _rec(3, 120.0, 0.0, 45.0),
                    "fused": _rec(4, 200.0, 40.0, 60.0)},
    "engine_thread_cpu_ms": dict({s: 1.0 for s in STAGES}, dispatch=301.0,
                                 apply=201.0, finalize=51.0, idle=9.0),
}


@pytest.mark.parametrize("value,want", [
    ("pass_ms", 11.0), ("wait_ms", 2.0), ("offcpu_ms", 3.0),
    # dispatch and apply: 300 + 200 ms over 100 ticks, finalize and idle out
    ("host_cpu_ms_per_tick", 5.0),
    ("handler_cpu_ms_per_tick", 2.0)])
def test_pass_clock_reads_window_deltas(value, want):
    assert pass_clock.read(_ctx(BEFORE, AFTER), value) == pytest.approx(want)


def test_pass_clock_reads_any_kind():
    ctx = _ctx(BEFORE, AFTER)
    assert pass_clock.read(ctx, "pass_ms", kind="fused") == pytest.approx(50)
    assert pass_clock.read(ctx, "wait_ms", kind="fused") == pytest.approx(10)
    assert pass_clock.read(ctx, "offcpu_ms", kind="admit") == \
        pytest.approx((100 - 40) / 2)


def test_pass_clock_logs_the_whole_split_once(capsys):
    ctx = _ctx(BEFORE, AFTER)
    pass_clock.read(ctx, "wait_ms")
    pass_clock.read(ctx, "host_cpu_ms_per_tick")
    assert capsys.readouterr().out == ""
    pass_clock.read(ctx, "pass_ms")
    out = capsys.readouterr().out
    assert out.count("[tpubench pass_clock]") == 1
    assert '"decode": {"n": 100, "wall": 11.0, "wait": 2.0, "cpu": 6.0, ' \
        '"offcpu": 3.0}' in out
    assert '"fused": {"n": 4, "wall": 50.0' in out
    assert "100 work ticks; handler turns a tick 16.00" in out


@pytest.mark.parametrize("value", ("pass_ms", "wait_ms", "offcpu_ms",
                                   "host_cpu_ms_per_tick",
                                   "handler_cpu_ms_per_tick"))
def test_pass_clock_is_none_on_a_parents_stats(value):
    parent = _ctx({"work_ticks": 10, "engine_thread_ms": {"idle": 1.0}},
                  {"work_ticks": 110, "engine_thread_ms": {"idle": 2.0}})
    assert pass_clock.read(parent, value) is None
    # and where nothing was counted
    still = _ctx(BEFORE, dict(BEFORE))
    assert pass_clock.read(still, value) is None


def test_pass_clock_refuses_an_unknown_value():
    with pytest.raises(ValueError):
        pass_clock.read(_ctx(BEFORE, AFTER), "p50")


# -- readers/pass_trace.py on a hand-built trace ----------------------------

def _passes_trace():
    """Three passes: decode 0-10 ms (device busy 2-9), fused 10-40 (busy
    12-38), decode 40-52 (busy 45-50); an idle pass 52-60."""
    ops = [(2 * MS, 9 * MS), (12 * MS, 20 * MS), (19 * MS, 38 * MS),
           (45 * MS, 50 * MS)]
    return {"ops": ops, "passes": {
        "decode": [(0, 10 * MS), (40 * MS, 52 * MS)],
        "fused": [(10 * MS, 40 * MS)], "idle": [(52 * MS, 60 * MS)]}}


def test_pass_trace_shares_idle_time_out_by_kind():
    red = pass_trace.reduce(_passes_trace())
    assert red["decode"]["n"] == 2
    assert red["decode"]["wall_ms"] == pytest.approx(22.0)
    # 0-2 and 9-10 of the first, 40-45 and 50-52 of the second
    assert red["decode"]["idle_ms"] == pytest.approx(10.0)
    assert red["fused"]["idle_ms"] == pytest.approx(2.0 + 2.0)
    assert red["idle"]["idle_ms"] == pytest.approx(8.0)
    assert "decode: 2 passes, 11.000 ms wall and 5.000 ms device idle" \
        in pass_trace.table(red)


def test_pass_trace_is_none_without_passes_or_device():
    assert pass_trace.reduce({"ops": [(0, 1)], "passes": {}}) is None
    assert pass_trace.reduce({"ops": [], "passes": {"decode": [(0, 1)]}}) \
        is None


def test_pass_trace_read_is_none_where_no_trace_was_written(tmp_path,
                                                            monkeypatch):
    from tpubench import spec
    monkeypatch.setattr(spec, "ROOT", str(tmp_path))
    assert pass_trace.read(_ctx({}, {}, cell="no-such.cell")) is None


def test_pass_trace_read_says_where_no_pass_of_the_kind_is(monkeypatch,
                                                           capsys):
    monkeypatch.setattr(pass_trace.trace, "find", lambda d: "x.xplane.pb")
    monkeypatch.setattr(pass_trace, "_reduced",
                        lambda path: pass_trace.reduce(_passes_trace()))
    ctx = _ctx({}, {})
    assert pass_trace.read(ctx) == pytest.approx(5.0)
    assert pass_trace.read(ctx, kind="admit") is None
    assert "no admit pass" in capsys.readouterr().out


def test_pass_clock_logs_the_slices_split_in_a_traced_run(monkeypatch,
                                                          capsys):
    """In the cell ``device.decode_idle_ms`` leaves out, the traced log
    still carries the slice's split by kind, through ``pass_clock``."""
    seen = []
    monkeypatch.setattr(pass_trace, "split", lambda ctx: seen.append(ctx))
    ctx = _ctx(BEFORE, AFTER, cell="openpangu-ultra-l5-ep32.longdoc")
    pass_clock.read(ctx, "pass_ms")
    assert seen == []                   # untraced: no trace to read
    ctx.trace = {"busy_s": 1.0}
    pass_clock.read(ctx, "wait_ms")
    assert seen == []
    pass_clock.read(ctx, "pass_ms")
    assert seen == [ctx]


def test_program_traces_no_span_row_is_the_pass_now():
    """The one change to an existing reader: with every moment of the
    engine's thread inside a ``tpushare.pass``, the idle table's
    "(no span)" row is the ``pass`` row."""
    ops = {"/device:TPU:0": [(5 * MS, 9 * MS), (15 * MS, 19 * MS)]}
    eng = [("engine.dispatch", 1 * MS, 3 * MS),
           ("engine.dispatch", 11 * MS, 13 * MS)]
    bare = {"ops": ops, "modules": {}, "threads": [eng], "tagged": []}
    red = pt.reduce(bare)
    passes = [("pass", 1 * MS, 10 * MS), ("pass", 10 * MS, 19 * MS)]
    red2 = pt.reduce(dict(bare, threads=[eng + passes]))
    assert red["idle_innermost_ms"][pt.NO_SPAN] == pytest.approx(6.0)
    assert red2["idle_innermost_ms"]["pass"] == pytest.approx(6.0)
    assert red2["idle_innermost_ms"][pt.NO_SPAN] == pytest.approx(0.0)
    assert red2["idle_innermost_ms"]["engine.dispatch"] == pytest.approx(
        red["idle_innermost_ms"]["engine.dispatch"])


NEW = ("engine.decode_pass_ms", "engine.decode_wait_ms",
       "engine.decode_offcpu_ms", "engine.host_cpu_ms_per_tick",
       "http.handler_cpu_ms_per_tick", "device.decode_idle_ms")
# The drafting cell's traced slice falls whole into admissions in about
# half its runs (no decode pass to read), so the one traced metric
# lists cells and leaves it out; the window's counters are every cell's.
SLICE_HOLDS_NO_DECODE = ("openpangu-ultra-l5-ep32.longdoc",)


@pytest.mark.parametrize("name", NEW)
def test_each_new_metric_is_its_cells_and_reads_its_file(name, monkeypatch):
    from tpubench import spec
    bench = spec.benchmark()
    m = {m["name"]: m for m in bench["per_layer"]}[name]
    assert m["moves"] == "itl_p50_ms"
    cells = [w["name"] for w in bench["workloads"]]
    if m["source"] == "program_span":
        assert set(m["workloads"]) <= set(cells)
        assert not set(m["workloads"]) & set(SLICE_HOLDS_NO_DECODE)
    else:
        assert "workloads" not in m
    assert m["unit"] == "ms" and m["better"] == "lower"
    lm = spec.layer_metric(name)
    assert lm["what"] and lm["layer"] == m["layer"]
    reader = spec.reader(lm["reader"])
    if reader is pass_trace:
        monkeypatch.setattr(pass_trace.trace, "find", lambda d: "x")
        monkeypatch.setattr(pass_trace, "_reduced",
                            lambda path: pass_trace.reduce(_passes_trace()))
    assert reader.read(_ctx(BEFORE, AFTER), **lm["args"]) > 0
    for c in cells:
        assert (name in spec.load_cell(c).per_layer) == \
            ("workloads" not in m or c in m["workloads"])
