"""HBM isolation bench harness (benchmarks/bench_isolation.py) on CPU:
the full two-tenant protocol (plugin env -> READY/GO barrier -> hog
allocation walk + steady measured windows -> verdict JSON) runs end to
end; only the real OOM-at-fraction assertion needs the chip."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "benchmarks", "bench_isolation.py")


@pytest.mark.slow
def test_isolation_protocol_cpu():
    env = dict(os.environ, TPUSHARE_BENCH_FORCE_CPU="1")
    env.pop("JAX_PLATFORMS", None)
    out = subprocess.run([sys.executable, SCRIPT], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-1500:]
    row = json.loads(out.stdout.strip().splitlines()[-1])
    assert row["metric"] == "hbm_isolation"
    assert row["backend"] == "cpu"
    # Protocol mechanics: the hog walked its allocation loop and the
    # steady tenant produced measured windows spanning the hog window.
    assert row["hog"]["allocated_gib"] >= 0
    assert len(row["steady_windows"]) >= 8
    ts = [w["t"] for w in row["steady_windows"]]
    assert min(ts) < 4.0 < max(ts)
    # On CPU the OOM leg is vacuous; the verdict key must still exist
    # (the on-chip artifact uses the same shape).
    assert "isolated" in row
