"""The reduction from a device trace to numbers: interval arithmetic on
hand-made events, and the whole reducer on a small trace recorded on
the chip and checked in beside this file."""

import os

import pytest

from tpubench.readers import trace

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "v5e_chat_slice.xplane.pb")
MS = 1e6    # events are in nanoseconds


def test_union_total_and_gaps():
    busy = trace.union([(5, 7), (0, 2), (1, 3), (7, 8), (10, 11)])
    assert busy == [(0, 3), (5, 8), (10, 11)]
    assert trace.total(busy) == 7
    assert trace.gaps(busy, 0, 12) == [(3, 5), (8, 10), (11, 12)]
    assert trace.gaps(busy, -1, 11) == [(-1, 0), (3, 5), (8, 10)]
    assert trace.gaps([], 0, 4) == [(0, 4)]


def test_self_time_takes_nested_operations_out():
    ops = [("while", 0.0, 100.0, False),
           ("fusion.1", 10.0, 30.0, False),
           ("custom-call.2", 50.0, 40.0, True),
           ("fusion.3", 120.0, 10.0, False)]
    st = {n: t for n, t, _ in trace.self_times(ops)}
    assert st == {"while": 30.0, "fusion.1": 30.0, "custom-call.2": 40.0,
                  "fusion.3": 10.0}


def test_idle_gaps_are_named_after_what_the_host_was_doing():
    spans = [("step_async", 0 * MS, 4 * MS), ("token_fetch", 10 * MS, 9 * MS),
             ("step", 30 * MS, 6 * MS), ("step_async", 31 * MS, 4 * MS)]
    idle = [(1 * MS, 3 * MS),       # inside step_async
            (11 * MS, 18 * MS),     # inside the fetch
            (20 * MS, 29 * MS),     # under no span at all
            (31 * MS, 35 * MS)]     # step and step_async nest: given once
    named = trace.name_gaps(idle, spans)
    assert named["token_fetch"] == pytest.approx(7e-3)
    assert named["engine loop"] == pytest.approx(9e-3)
    assert named.get("step_async", 0) + named.get("step", 0) == \
        pytest.approx(2e-3 + 4e-3)
    assert sum(named.values()) == pytest.approx(22e-3)


def _synthetic():
    dev0 = [("while", 0.0, 60 * MS, False),
            ("fusion.7", 0.0, 20 * MS, False),
            ("custom-call.3", 20 * MS, 30 * MS, True),
            ("all-reduce.1", 50 * MS, 10 * MS, False),
            ("fusion.9", 80 * MS, 10 * MS, False)]
    dev1 = [("fusion.7", 0.0, 50 * MS, False)]
    spans = [("token_fetch", 60 * MS, 20 * MS), ("step_async", 95 * MS, 5 * MS)]
    return {"devices": {"/device:TPU:0": dev0, "/device:TPU:1": dev1},
            "spans": spans}


def test_reduce_a_hand_made_trace():
    red = trace.reduce(_synthetic())
    assert red["window_s"] == pytest.approx(0.100)
    assert red["n_devices"] == 2
    # device 0 busy 70 ms, device 1 busy 50 ms: the average
    assert red["busy_s"] == pytest.approx(0.060)
    assert red["idle_pct"] == pytest.approx(40.0)
    assert red["mosaic_busy_pct"] == pytest.approx(100 * 30 / 70)
    assert red["device_ops"][0] == ["custom-call.3", pytest.approx(0.030)]
    assert dict(map(tuple, red["device_ops"]))["while"] == pytest.approx(0.0)
    gaps = dict(map(tuple, red["idle_gaps"]))
    assert gaps["token_fetch"] == pytest.approx(0.020)
    assert gaps.get("engine loop", 0) + gaps.get("step_async", 0) == \
        pytest.approx(0.010)


def test_a_trace_in_which_nothing_ran_on_a_device_reduces_to_nothing():
    assert trace.reduce({"devices": {}, "spans": [("step", 0.0, 1.0)]}) is None
    assert trace.reduce({"devices": {"/device:TPU:0": []}, "spans": []}) is None


def test_find_returns_the_newest_xplane_file(tmp_path):
    assert trace.find(str(tmp_path)) is None
    for stamp in ("2026_01_01_00_00_00", "2026_01_02_00_00_00"):
        d = tmp_path / "plugins" / "profile" / stamp
        d.mkdir(parents=True)
        (d / "vm.xplane.pb").write_bytes(b"")
    assert "2026_01_02" in trace.find(str(tmp_path))


# -- the recorded trace: 0.4 s (five decode ticks) cut from the traced
# run of mistral7b-l16.chat on a v5e (PR 22, second look), device op lines
# and the tpubench host spans only, event stats dropped -----------------

def test_recorded_v5e_trace_loads_as_the_reducer_expects():
    t = trace.load(RECORDED)
    assert list(t["devices"]) == ["/device:TPU:0"]
    ops = t["devices"]["/device:TPU:0"]
    assert len(ops) == 8433
    assert {n for n, *_ in t["spans"]} == {"step_async", "token_fetch"}
    mosaic = {n for n, _, _, m in ops if m}
    assert mosaic == {"paged_flash_decode.11 mosaic bf16[32,64,128]"}


def test_recorded_v5e_trace_reduces_to_the_numbers_it_held():
    t = trace.load(RECORDED)
    red = trace.reduce(t)
    assert red["n_devices"] == 1
    assert red["window_s"] == pytest.approx(0.395121, rel=1e-4)
    assert red["busy_s"] == pytest.approx(0.339154, rel=1e-4)
    assert red["idle_pct"] == pytest.approx(14.1645, rel=1e-3)
    assert red["mosaic_busy_pct"] == pytest.approx(38.345, rel=1e-3)
    # busy time again, the slow way: mark every microsecond an op covers
    ops = t["devices"]["/device:TPU:0"]
    lo = min(s for _, s, _, _ in ops)
    n = int((max(s + d for _, s, d, _ in ops) - lo) / 1e3) + 2
    covered = bytearray(n)
    for _, s, d, _ in ops:
        a, b = int((s - lo) / 1e3), int((s + d - lo) / 1e3)
        covered[a:b + 1] = b"\x01" * (b + 1 - a)
    assert sum(covered) / 1e6 == pytest.approx(red["busy_s"], rel=0.02)
    # the heaviest operation is the paged decode kernel, 26 ms a tick
    top = red["device_ops"]
    assert len(top) == 10 and top[0][0].startswith("paged_flash_decode.11")
    assert top[0][1] == pytest.approx(0.13005, rel=1e-3)
    assert [s for _, s in top] == sorted((s for _, s in top), reverse=True)
    # self time: the layer loop itself keeps almost none of its 293 ms
    whiles = [s for n, s in top if n.startswith("while")]
    assert not whiles or whiles[0] < 0.01
    # idle gaps are named, and add up to the idle time of the window
    gaps = dict(map(tuple, red["idle_gaps"]))
    assert set(gaps) <= {"step_async", "token_fetch", "engine loop"}
    assert sum(gaps.values()) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-6)
