"""Percentile and gap arithmetic on hand-made samples."""

import pytest

from tpubench import metrics


def test_percentile_linear_interpolation():
    xs = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert metrics.percentile(xs, 0) == 10.0
    assert metrics.percentile(xs, 50) == 30.0
    assert metrics.percentile(xs, 100) == 50.0
    assert metrics.percentile(xs, 95) == pytest.approx(48.0)
    assert metrics.percentile([3.0, 1.0], 50) == pytest.approx(2.0)
    assert metrics.percentile([7.0], 99) == 7.0
    assert metrics.percentile([], 50) is None


def _row(**kw):
    r = dict(id="r", phase="main", due=None, sent=0.0, status=200, error=None,
             done=True, tokens=[1, 2, 3], stamps=[0.1, 0.2, 0.4], t_done=0.4,
             cancelled=False, max_tokens=3, prompt_tokens=10, warm=False)
    r.update(kw)
    return r


def test_token_gaps_and_window_filter():
    rows = [_row(stamps=[-0.5, -0.1, 0.3, 9.9, 10.2])]
    # a gap counts where its later stamp is inside [0, 10)
    assert metrics.gaps_in_window(rows, 10.0) == pytest.approx([0.4, 9.6])


def test_request_ok_every_way_it_can_fail():
    ok = _row()
    assert metrics.request_ok(ok, vocab=100)
    assert not metrics.request_ok(_row(status=429), 100)
    assert not metrics.request_ok(_row(done=False), 100)
    assert not metrics.request_ok(_row(tokens=[1, 2]), 100)        # short
    assert not metrics.request_ok(_row(tokens=[1, -1, 3]), 100)    # NaN guard
    assert not metrics.request_ok(_row(tokens=[1, 2, 100]), 100)   # off the vocab
    assert not metrics.request_ok(_row(error="boom"), 100)


def test_end_to_end_open_loop_clock_starts_when_due():
    rows = [
        _row(id="a", due=1.0, sent=1.2, stamps=[1.5, 1.6, 1.7], t_done=1.7),
        _row(id="b", due=2.0, sent=2.0, stamps=[2.1, 2.3, 2.6], t_done=2.6),
        _row(id="w", phase="warm", due=-1.0, sent=-1.0,
             stamps=[-0.5, 0.5, 0.75], t_done=0.75),
        _row(id="late", due=11.0, sent=11.0, stamps=[], tokens=[], done=False),
        _row(id="x", due=3.0, sent=3.0, status=429, stamps=[], tokens=[],
             done=False, t_done=None),
    ]
    m = metrics.end_to_end(rows, loop="open", window_s=10.0, vocab=100)
    assert m["attempted"] == 3 and m["failed"] == 1      # a, b, x; x refused
    v = m["values"]
    assert v["ttft_cold_p50_ms"] == pytest.approx((500 + 100) / 2)
    assert v["ttft_warm_p50_ms"] is None
    # lateness of a, b, x: 200, 0, 0 ms
    assert v["late_p99_ms"] == pytest.approx(0.98 * 200, rel=1e-6)
    # gaps: a 100,100; b 200,300; warm 1000 (ends at 0.5), 250
    assert m["samples"]["itl_gaps"] == 6
    assert v["itl_p50_ms"] == pytest.approx(225.0)
    # sorted 100 100 200 250 300 1000: position 4.5 of 0..5
    assert v["itl_p90_ms"] == pytest.approx(650.0)
    # a: 500 ms for 10 prompt tokens, b: 100 ms; x never answered
    assert v["ttft_cold_ms_per_ktok_p50"] == pytest.approx(30000.0)
    # produced in the window: a and b whole (10 + 3 each), of the warm
    # stream the two tokens stamped after 0 (its prompt was served before)
    assert v["served_tok_s"] == pytest.approx((13 + 13 + 2) / 10.0)
    assert v["output_tok_s"] == pytest.approx((3 + 3 + 2) / 10.0)


def test_end_to_end_closed_loop_clock_starts_when_sent():
    rows = [_row(id="a", sent=1.0, stamps=[1.25, 1.5, 1.75], t_done=1.75),
            _row(id="bg", phase="background", sent=-5.0, max_tokens=4096,
                 stamps=[-4.0, 0.5], tokens=[1, 2], done=False, t_done=None,
                 cancelled=True)]
    m = metrics.end_to_end(rows, loop="closed", window_s=10.0, vocab=100)
    assert m["attempted"] == 1 and m["failed"] == 0
    assert m["values"]["ttft_p95_ms"] == pytest.approx(250.0)
    assert m["values"]["late_p99_ms"] is None
    # a background stream that died is a failure of the window
    rows[1]["error"] = "engine error"
    assert metrics.end_to_end(rows, loop="closed", window_s=10.0,
                              vocab=100)["failed"] == 1


def test_nothing_judged_comes_from_a_background_stream():
    """The long stream beside the document questions is the harness's
    device; its gaps and tokens are in no judged number."""
    ask = _row(id="a", sent=1.0, stamps=[1.25, 1.5, 1.75], t_done=1.75)
    bg = _row(id="bg", phase="background", sent=-5.0, max_tokens=4096,
              stamps=[-4.0, 0.5, 4.5, 9.5], tokens=[1, 2, 3, 4], done=False,
              t_done=None)
    holder = _row(id="holder", phase="holder", sent=-9.0, stamps=[-8.0, 0.2],
                  tokens=[1, 2], done=False, t_done=None, cancelled=True)
    alone = metrics.end_to_end([ask], loop="closed", window_s=10.0, vocab=100)
    beside = metrics.end_to_end([ask, bg, holder], loop="closed",
                                window_s=10.0, vocab=100)
    assert beside["values"] == alone["values"]
    assert beside["samples"] == alone["samples"]
    assert alone["samples"]["itl_gaps"] == 2
    assert alone["values"]["output_tok_s"] == pytest.approx(0.3)


def test_first_tokens_of_repeated_documents_are_timed_apart():
    rows = [_row(id="c1", sent=0.0, stamps=[2.0, 2.1, 2.2], warm=False),
            _row(id="c2", sent=1.0, stamps=[3.4, 3.5, 3.6], warm=False),
            _row(id="w1", sent=4.0, stamps=[4.2, 4.3, 4.4], warm=True),
            _row(id="w2", sent=5.0, stamps=[5.1, 5.2, 5.3], warm=True),
            _row(id="w3", sent=6.0, stamps=[6.3, 6.4, 6.5], warm=True)]
    m = metrics.end_to_end(rows, loop="closed", window_s=10.0, vocab=100)
    assert m["values"]["ttft_cold_p50_ms"] == pytest.approx(2200.0)
    assert m["values"]["ttft_warm_p50_ms"] == pytest.approx(200.0)
    # cold only, over the prompt's length: 2.0 s and 2.4 s for 10 and 20
    rows[1]["prompt_tokens"] = 20
    again = metrics.end_to_end(rows, loop="closed", window_s=10.0, vocab=100)
    assert again["values"]["ttft_cold_ms_per_ktok_p50"] == pytest.approx(
        (200000.0 + 120000.0) / 2)
    assert m["samples"]["ttft_cold"] == 2 and m["samples"]["ttft_warm"] == 3
    # the tail is over all of them
    assert m["values"]["ttft_p95_ms"] == pytest.approx(2320.0)


def test_programs_built_inside_the_window_are_counted_by_name():
    from tpubench import run
    compiles = [(9.9, 2.0, "jit_decode"), (10.0, 0.1, "jit(scatter)"),
                (12.5, 0.1, "jit(scatter)"), (49.9, 3.0, "jit_prefill"),
                (50.0, 0.1, "jit(slice)")]
    assert run.built_in_window(compiles, 10.0, 40.0) == {
        "jit(scatter)": 2, "jit_prefill": 1}
    assert run.built_in_window(compiles[:1], 10.0, 40.0) == {}
