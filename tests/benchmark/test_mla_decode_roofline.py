"""``mla.decode_roofline_pct`` (``tpubench/readers/mla_decode_trace.py``):
the floor's arithmetic on hand-built device operations and counters, what
the reader finds in a program without the counter or a trace without the
kernel, and that the metric resolves through ``spec`` as the others do."""

import types

import pytest

from tpubench import peaks, spec
from tpubench.readers import mla_decode_trace as reader

CELL = "openpangu-ultra-l5-ep32.longdoc"
V5E = peaks.PEAKS["TPU v5 lite"]


def _ctx(before, after, **kw):
    kw.setdefault("window_s", 40.0)
    kw.setdefault("stats_samples", [])
    kw.setdefault("trace", {"busy_s": 1.0})
    kw.setdefault("peaks", V5E)
    cell = types.SimpleNamespace(config=spec.load_cell(CELL).config,
                                 name="no.such.cell", rehearse=False)
    return types.SimpleNamespace(stats_before=before, stats_after=after,
                                 cell=cell, **kw)


def test_the_metric_resolves_through_spec_as_the_other_readers_do():
    lm = spec.layer_metric("mla.decode_roofline_pct")
    assert lm["layer"] == "kernels" and lm["unit"] == "%"
    assert lm["moves"] == "itl_p50_ms" and lm["args"] == {}
    assert spec.reader(lm["reader"]) is reader
    entry = [m for m in spec.benchmark()["per_layer"]
             if m["name"] == "mla.decode_roofline_pct"]
    assert len(entry) == 1
    # a later cell on the same kernel may add itself to the list
    assert CELL in entry[0].pop("workloads")
    assert entry == [{"name": "mla.decode_roofline_pct", "unit": "%",
                      "better": "higher", "source": "device_trace",
                      "layer": "kernels", "moves": "itl_p50_ms"}]
    assert "mla.decode_roofline_pct" in spec.load_cell(CELL).per_layer
    for other in ("dots3-note-prev-l5-ep8.longdoc", "mistral7b-l16.chat"):
        assert "mla.decode_roofline_pct" not in spec.load_cell(other).per_layer


def test_the_kernels_name_is_the_programs():
    from tpushare.ops import latent_decode
    assert reader.KERNEL == latent_decode.KERNEL_NAME


def test_the_floor_is_the_published_widths_whatever_implements_them():
    config = spec.load_cell(CELL).config
    assert reader.row_widths(config) == {"key": 576, "value": 512,
                                         "heads": 128, "bytes": 2}
    # five main layers verify two positions, the module's call runs what
    # the last round committed
    assert reader.mean_queries(config, 1.0) == pytest.approx(11 / 6)
    assert reader.mean_queries(config, 2.0) == 2.0
    rows = 16 * 12288.0
    # the matrix unit bounds it: 2 x 2 x 128 x 1,088 operations a row
    flops = rows * 2 * 2 * 128 * (576 + 512)
    assert reader.call_floor_s(config, rows, 2.0, V5E) == pytest.approx(
        flops / 197e12)
    assert flops / 197e12 > rows * 1152 / 819e9
    # with a head the bytes would: the larger of the two, always
    one_head = dict(config, num_attention_heads=1)
    assert reader.call_floor_s(one_head, rows, 2.0, V5E) == pytest.approx(
        rows * 1152 / 819e9)


def test_a_perfect_call_at_the_padded_widths_reads_85():
    """Two events by name among a device's operations; a kernel that ran
    the 640-wide row through both products at the matrix unit's peak
    reads 85, one that cuts the output to the latent 94.4, and one that
    took the floor itself 100."""
    config = spec.load_cell(CELL).config
    rows = 16 * 12288.0
    padded_ns = 1e9 * rows * 2 * 2 * 128 * (640 + 640) / 197e12
    ops = [("fusion.3 fusion bf16[32,7680]", 0.0, 5000.0, False),
           ("latent_paged_decode.1 mosaic bf16[16,256,512]", 5e3, padded_ns,
            True),
           ("latent_paged_decode.7 mosaic bf16[16,256,512]", 9e6, padded_ns,
            True),
           ("ragged_dot.1 mosaic bf16[8,8]", 2e7, 1000.0, True)]
    durations = reader.kernel_events(ops)
    assert durations == [padded_ns, padded_ns]
    assert reader.roofline_pct(durations, config, rows, 2.0, V5E) == \
        pytest.approx(85.0)
    cut_ns = padded_ns * (640 + 512) / (640 + 640)
    assert reader.roofline_pct([cut_ns], config, rows, 2.0, V5E) == \
        pytest.approx(100 * 1088 / 1152)
    floor_ns = 1e9 * reader.call_floor_s(config, rows, 2.0, V5E)
    assert reader.roofline_pct([floor_ns] * 3, config, rows, 2.0, V5E) == \
        pytest.approx(100.0)
    # a dead second query of the module's call is no useful work
    assert reader.roofline_pct([floor_ns], config, rows, 11 / 6, V5E) == \
        pytest.approx(100 * 11 / 12)
    assert reader.roofline_pct([], config, rows, 2.0, V5E) is None
    assert reader.roofline_pct(durations, config, 0.0, 2.0, V5E) is None


BEFORE = {"latent_rows_read": 10_000_000, "latent_decode_calls": 600,
          "mtp_emitted": 1402, "mtp_proposed": 1400}
AFTER = {"latent_rows_read": 1_090_000_000, "latent_decode_calls": 6000,
         "mtp_emitted": 16405, "mtp_proposed": 16400}


def test_rows_a_call_come_from_the_counters_around_the_slice():
    ctx = _ctx(BEFORE, AFTER)
    rows, calls, emitted, proposed = reader.around_slice(ctx)
    assert (rows, calls) == (1_080_000_000, 5400)
    assert emitted / proposed == pytest.approx(15003 / 15000)
    # with a sample a second, between those that enclose the slice
    # (seconds 18 and 22 of 40), whatever the window's
    ctx.stats_samples = [
        {"latent_rows_read": 1_000_000 * k, "latent_decode_calls": 6 * k,
         "mtp_emitted": 16 * k, "mtp_proposed": 16 * k}
        for k in range(1, 40)]
    assert reader.around_slice(ctx) == (4_000_000, 24, 64, 64)
    # a sample without the counter: the window's again
    del ctx.stats_samples[17]["latent_decode_calls"]
    assert reader.around_slice(ctx)[:2] == (1_080_000_000, 5400)


def test_none_without_the_counter_the_calls_the_kernel_or_the_trace():
    # a program from before the kernel: rows counted, no call counter
    old = ({k: v for k, v in BEFORE.items() if k != "latent_decode_calls"},
           {k: v for k, v in AFTER.items() if k != "latent_decode_calls"})
    assert reader.read(_ctx(*old)) is None
    # one that gathers: the counter stands still
    assert reader.read(_ctx(BEFORE, dict(
        AFTER, latent_decode_calls=BEFORE["latent_decode_calls"]))) is None
    # another family's stats
    assert reader.read(_ctx({"work_ticks": 1}, {"work_ticks": 9})) is None
    # counters, and no trace directory of the cell's name: no events
    assert reader.read(_ctx(BEFORE, AFTER)) is None
    # an untraced run, a device that is not in the table
    assert reader.read(_ctx(BEFORE, AFTER, trace=None)) is None
    assert reader.read(_ctx(BEFORE, AFTER, peaks=None)) is None
    # a configuration with no latent rows
    ctx = _ctx(BEFORE, AFTER)
    ctx.cell.config = spec.load_cell("mistral7b-l16.chat").config
    assert reader.read(ctx) is None


def test_the_reader_reads_a_recorded_device_line(monkeypatch, tmp_path):
    """``read`` end to end over a stand-in for the trace's loader: the
    kernel's events of the first device, the counters' rows a call."""
    ctx = _ctx(BEFORE, AFTER)
    rows_a_call = 1_080_000_000 / 5400
    q = reader.mean_queries(ctx.cell.config, 15003 / 15000)
    ns = 1e9 * reader.call_floor_s(ctx.cell.config, rows_a_call, q, V5E)
    ops = [("latent_paged_decode.1 mosaic bf16[16,256,512]", 0.0, 2 * ns,
            True),
           ("fusion.3 fusion bf16[32,7680]", 3 * ns, 5000.0, False)]
    monkeypatch.setattr(reader.trace, "find", lambda d: str(tmp_path))
    monkeypatch.setattr(reader, "_ops", lambda path: ops)
    assert reader.read(ctx) == pytest.approx(50.0)
    monkeypatch.setattr(reader, "_ops", lambda path: ops[1:])
    assert reader.read(ctx) is None
