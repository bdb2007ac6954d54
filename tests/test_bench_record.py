"""Pin bench.py's driver-contract lines.

A run that cannot measure on an accelerator FAILS: one JSON line that
names the failure, no number, exit code 1 — never a CPU rerun, never an
older run's figure attached. The only CPU path left is the explicit
TPUSHARE_BENCH_FORCE_CPU harness mode, and its record is labelled and
scores nothing. No subprocesses — these exercise the record assembly
and main()'s control flow with the probe and the measurement stubbed."""

import json

import pytest

import bench


def test_forced_cpu_harness_is_labelled_and_non_scoring():
    rec = bench.final_record(42.75, "cpu", {
        "solo_variance_pct": 1.2,
        "credible": True,          # A-B-A gates passed — irrelevant on CPU
    })
    assert rec["backend"] == "cpu"
    # No CPU number under the device metric's name.
    assert rec["value"] is None
    assert rec["vs_baseline"] is None
    assert rec["credible"] is False
    assert rec["advisory_cpu_pct"] == 42.75
    assert any("FORCE_CPU" in r for r in rec["refusal_reasons"])
    assert rec["metric"] == "colocated_tokens_per_sec_pct"
    assert rec["unit"] == "%"
    json.dumps(rec)


def test_forced_cpu_harness_keeps_prior_refusal_reasons():
    rec = bench.final_record(120.0, "cpu", {
        "credible": False,
        "refusal_reasons": ["co-located/solo 120.0% > 100%"],
    })
    assert len(rec["refusal_reasons"]) == 2
    assert rec["refusal_reasons"][0].startswith("co-located/solo")
    assert rec["vs_baseline"] is None


def test_tpu_credible_scores():
    rec = bench.final_record(97.1, "tpu", {
        "solo_variance_pct": 0.8,
        "credible": True,
    })
    assert rec["value"] == 97.1
    assert rec["vs_baseline"] == round(97.1 / 95.0, 4)
    assert rec["credible"] is True
    assert "advisory_cpu_pct" not in rec
    assert "refusal_reasons" not in rec


def test_tpu_incredible_refuses_vs_baseline():
    rec = bench.final_record(126.76, "tpu", {
        "solo_variance_pct": 9.0,
        "credible": False,
        "refusal_reasons": ["solo A1/A2 variance 9.0% > 5%"],
    })
    assert rec["vs_baseline"] is None
    assert rec["credible"] is False
    assert rec["value"] == 126.76


def test_windows_never_leak_into_the_driver_line():
    rec = bench.final_record(50.0, "tpu", {
        "credible": True,
        "windows": {"solo_a1": {"serve_tokens_per_sec": 1.0}},
    })
    assert "windows" not in rec


def test_window_raws_go_to_the_chip_tools_output_dir():
    """Per-window raws used to overwrite (or sit beside) a record of an
    earlier round under benchmarks/; a record is not edited, so they go
    to chiprun_out/, which git ignores."""
    rel = bench.os.path.relpath(bench.WINDOWS_PATH, bench.REPO)
    assert rel.split(bench.os.sep)[0] == "chiprun_out"
    assert not hasattr(bench, "artifact_path")


def test_refused_record_never_cites_an_older_run(tmp_path, monkeypatch):
    """A refused (or harness) record used to carry a pointer to the
    round's banked credible artifact; a run reports itself only."""
    bdir = tmp_path / "benchmarks"
    bdir.mkdir()
    monkeypatch.setattr(bench, "REPO", str(tmp_path))
    with open(bdir / "NORTH_STAR_TPU_r4.json", "w") as f:
        json.dump({"credible": True, "value_pct": 99.51,
                   "solo_variance_pct": 4.54}, f)
    for rec in (bench.final_record(42.0, "cpu", {}),
                bench.final_record(126.0, "tpu", {"credible": False}),
                bench.failure_record("no accelerator")):
        assert "banked_credible_prior_run" not in rec
        assert "99.51" not in json.dumps(rec)


def _run_main(monkeypatch, capsys, probe, measure=None):
    """bench.main() with the probe child and the measurement stubbed;
    ``probe`` is _probe_once's answer, or a callable standing in for it.
    Returns (exit code, the one stdout line parsed, measure calls)."""
    calls = []
    monkeypatch.delenv("TPUSHARE_BENCH_FORCE_CPU", raising=False)
    monkeypatch.setattr(bench, "_probe_once",
                        probe if callable(probe) else lambda attempt_s: probe)

    def _measure(solo_env, child_env, extras=None):
        calls.append((solo_env, child_env))
        if measure is None:
            raise AssertionError("measurement must not run")
        return measure(solo_env, child_env, extras)

    monkeypatch.setattr(bench, "_measure", _measure)
    rc = bench.main()
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 1, out            # ONE stdout line, always
    return rc, json.loads(out[0]), calls


def test_probe_failure_is_named_and_nothing_is_measured(monkeypatch,
                                                        capsys):
    """A probe child that exits with an error ends the run: the line
    carries the child's own reason, and no tenant is started."""
    rc, rec, calls = _run_main(
        monkeypatch, capsys, (None, "rc=1: ImportError: libtpu"))
    assert rc == 1 and rec["ok"] is False
    assert "ImportError: libtpu" in rec["error"]
    assert calls == []
    assert "value" not in rec and "advisory_cpu_pct" not in rec


def test_probe_hang_is_one_bounded_attempt(monkeypatch, capsys):
    """A hung probe is killed at its deadline and the run fails — no
    triage ladder, no retries, no fallback."""
    deadlines = []

    def fake_probe(attempt_s):
        deadlines.append(attempt_s)
        return None, f"hung >{attempt_s:.0f}s"

    rc, rec, calls = _run_main(monkeypatch, capsys, fake_probe)
    assert rc == 1 and calls == []
    assert deadlines == [bench.INIT_TIMEOUT_S]
    assert rec["ok"] is False and "hung" in rec["error"]


def test_probe_resolving_to_cpu_is_a_failure(monkeypatch, capsys):
    """JAX settling on the CPU is 'no accelerator', not a backend to
    measure on (the old probe returned "cpu" on three paths and the
    tenants then ran BERT-tiny there)."""
    rc, rec, calls = _run_main(monkeypatch, capsys, ("cpu", "cpu"))
    assert rc == 1 and rec["ok"] is False
    assert "no accelerator" in rec["error"]
    assert calls == []
    with pytest.raises(bench.BenchFailure, match="no accelerator"):
        bench.probe_backend()


def test_unknown_device_kind_is_an_error(monkeypatch, capsys):
    """A device the peak tables do not know used to be called v5e."""
    rc, rec, calls = _run_main(monkeypatch, capsys,
                               ("tpu", "TPU v9 hyper"))
    assert rc == 1 and "TPU v9 hyper" in rec["error"]
    assert calls == []


def test_failed_measurement_is_a_failure_line_not_a_cpu_rerun(
        monkeypatch, capsys):
    """A tenant that cannot open the chip (or dies) fails the run with
    its phase and message; the measurement runs ONCE — no re-probe, no
    retry, no rerun under TPUSHARE_BENCH_FORCE_CPU."""
    def measure(solo_env, child_env, extras):
        raise bench.BenchFailure(
            "co-located phase (2 tenant processes on one chip): tenant "
            "1 of 2 never said READY; its stderr ends: TPU "
            "initialization failed: open(/dev/vfio/2): Device or "
            "resource busy")

    rc, rec, calls = _run_main(monkeypatch, capsys,
                               ("tpu", "TPU v5 lite"), measure)
    assert rc == 1 and rec["ok"] is False
    assert "co-located phase" in rec["error"]
    assert "Device or resource busy" in rec["error"]
    assert rec["device"] == {"platform": "tpu", "kind": "TPU v5 lite"}
    assert len(calls) == 1
    solo_env, child_env = calls[0]
    assert "TPUSHARE_BENCH_FORCE_CPU" not in solo_env
    assert solo_env["TPUSHARE_TPU_GENERATION"] == "v5e"
    assert "value" not in rec


def test_measured_record_names_its_device(monkeypatch, capsys, tmp_path):
    monkeypatch.setattr(bench, "WINDOWS_PATH",
                        str(tmp_path / "out" / "w.json"))

    def measure(solo_env, child_env, extras):
        extras.update({"credible": True, "solo_variance_pct": 1.0,
                       "windows": {"solo_a1": {}}})
        return 96.0

    rc, rec, _ = _run_main(monkeypatch, capsys, ("tpu", "TPU v5 lite"),
                           measure)
    assert rc == 0
    assert rec["device"] == {"platform": "tpu", "kind": "TPU v5 lite"}
    assert rec["backend"] == "tpu" and rec["value"] == 96.0
    assert "windows" not in rec
    with open(tmp_path / "out" / "w.json") as f:
        assert "windows" in json.load(f)


def test_no_fallback_text_left_in_bench():
    with open(bench.__file__) as f:
        src = f.read()
    assert "falling back to CPU" not in src
    for gone in ("triage_probe_hang", "_accel_holders", "artifact_path"):
        assert not hasattr(bench, gone)
