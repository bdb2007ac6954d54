"""chip_smoke.py --rehearse-cpu: the whole script against a real daemon at
--preset tiny on the CPU. Slow tier (it starts two JAX processes). What it
pins: every phase of the smoke holds here, the parent ends off JAX, and a
rehearsal says ``platform: cpu`` and can never print the pass line."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_rehearsal_holds_and_never_passes():
    # conftest's forced 8-device host view must not reach the daemon: a
    # one-chip tenant sees one device.
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"),
         "--rehearse-cpu"], cwd=REPO, env=env, capture_output=True,
        text=True, timeout=900)
    tail = out.stdout[-3000:] + out.stderr[-1500:]
    assert out.returncode == 3, tail      # held, and is not the chip
    assert "platform: cpu" in out.stdout
    assert "FAIL:" not in out.stdout, tail
    assert '"ok": true' not in out.stdout
    last = out.stdout.strip().splitlines()[-1]
    assert last.startswith("rehearsal held"), last
