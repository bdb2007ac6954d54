"""On-chip HBM isolation proof (VERDICT r3 #4; SURVEY §7 hard part 1).

Two tenant processes under the plugin's injected env, 8 GiB grants
each on one 16 GiB chip:

- Tenant HOG applies its tenant limits, then deliberately allocates
  PAST its fraction in 256 MiB steps. The enforcing guard
  (utils/tenant.apply_tenant_limits, TPUSHARE_HBM_ENFORCE=raise
  default) must deliver SoftHbmOom near its grant — not let it walk
  the whole chip. (The first on-chip run of this bench proved the
  r4 XLA_PYTHON_CLIENT_MEM_FRACTION hint alone enforces nothing on
  TPU: the hog reached 12 GiB against an 8 GiB grant.)
- Tenant STEADY runs a continuously-measured inference loop the whole
  time. Its throughput during and after the neighbor's OOM must be
  unchanged within noise — the isolation claim is exactly that a
  misbehaving neighbor cannot degrade you.

Emits one JSON line naming its backend, and writes the full result to
chiprun_out/isolation.json when on the accelerator. With no
accelerator the run fails (bench.py's probe, exit 1). Under
TPUSHARE_BENCH_FORCE_CPU=1 (the harness mode the tests use) the OOM
leg is vacuous — the CPU has no device-memory grant to hit; the run
only validates the parent/tenant protocol and says backend="cpu".

Both tenants hold the one chip AT THE SAME TIME, from two processes;
where libtpu gives a chip to one process only, the second tenant
cannot start and the run fails with libtpu's message.

Usage: python benchmarks/bench_isolation.py
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH_DIR)
sys.path.insert(0, REPO)

from bench import (BenchFailure, INIT_TIMEOUT_S,  # noqa: E402
                   _readline_deadline, failure_record, log, plugin_env,
                   probe_backend)

WINDOW_S = 1.0
N_WINDOWS = 12          # steady runs ~12s; hog fires at window ~4
HOG_AT_S = 4.0


def steady_main() -> None:
    from tpushare.utils.tenant import apply_tenant_limits
    apply_tenant_limits()
    force_cpu = os.environ.get("TPUSHARE_BENCH_FORCE_CPU") == "1"
    import jax
    if force_cpu:
        jax.config.update("jax_platforms", "cpu")
    else:
        from tpushare.utils.compile_cache import enable_compile_cache
        enable_compile_cache()
    import jax.numpy as jnp
    import numpy as np
    from tpushare.models import bert

    if not force_cpu and jax.default_backend() == "cpu":
        raise SystemExit("steady tenant: no accelerator to measure on")
    cfg = bert.tiny() if force_cpu else bert.bert_base()
    batch, seq = (2, 32) if force_cpu else (8, 128)
    params = bert.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (batch, seq)))
    fwd = jax.jit(lambda p, t: bert.forward(p, t, cfg)["pooled"])
    fwd(params, tokens).block_until_ready()
    print("READY", flush=True)
    sys.stdin.readline()                        # GO
    fwd(params, tokens).block_until_ready()     # re-warm (the parent
    # anchors the hog's fire time on this WARM, so the baseline
    # windows stay clean)
    print("WARM", flush=True)
    t0 = time.time()
    windows = []
    for _ in range(N_WINDOWS):
        w0 = time.time()
        calls = 0
        while time.time() < w0 + WINDOW_S:
            fwd(params, tokens).block_until_ready()
            calls += 1
        windows.append({"t": round(w0 - t0, 2),
                        "tokens_per_sec": calls * batch * seq
                        / (time.time() - w0)})
    print("STEADY_RESULT " + json.dumps(windows), flush=True)


def hog_main() -> None:
    from tpushare.utils.tenant import apply_tenant_limits
    spec = apply_tenant_limits()
    force_cpu = os.environ.get("TPUSHARE_BENCH_FORCE_CPU") == "1"
    import jax
    if force_cpu:
        jax.config.update("jax_platforms", "cpu")
    else:
        from tpushare.utils.compile_cache import enable_compile_cache
        enable_compile_cache()
    import jax.numpy as jnp

    print("READY", flush=True)
    sys.stdin.readline()                        # GO
    limit = spec.hbm_limit_bytes or (8 << 30)
    chunk = 256 << 20
    # On CPU there is no device-memory fraction to hit: cap the walk at
    # 1 GiB so the harness stays testable without 12 GiB of host RAM.
    target = int(1.5 * limit) if not force_cpu else (1 << 30)
    held, allocated, oomed, err = [], 0, False, ""
    while allocated < target:
        try:
            a = jnp.ones((chunk // 4,), jnp.float32)
            # Barrier per chunk: an unbarriered walk dispatches every
            # chunk before the 50 ms guard poll ever runs — the whole
            # walk "allocates" in one interval.
            float(a[0])
            held.append(a)
            allocated += chunk
        except Exception as e:                  # noqa: BLE001 — any OOM class
            oomed = True
            err = type(e).__name__
            break
    del held
    print("HOG_RESULT " + json.dumps({
        "oomed": oomed, "error": err,
        "allocated_gib": round(allocated / 2 ** 30, 2),
        "limit_gib": round(limit / 2 ** 30, 2),
        # Two-sided: an OOM far BELOW the grant is a failed (trigger-
        # happy) limit just like one far past it — both must not feed
        # isolated:true.
        "oom_within_1gib_of_limit": bool(
            oomed and limit - (1 << 30) <= allocated <= limit + (1 << 30)),
    }), flush=True)


def main() -> int:
    # FORCE_CPU wins before any probe: the CPU protocol test must stay
    # a CPU test on a machine that has a chip.
    if os.environ.get("TPUSHARE_BENCH_FORCE_CPU") == "1":
        backend = "cpu"
    else:
        try:
            backend, _, _ = probe_backend()
        except BenchFailure as e:
            log(f"BENCH FAILED: {e}")
            print(json.dumps(dict(failure_record(str(e)),
                                  metric="hbm_isolation")))
            return 1
    on_tpu = backend != "cpu"
    env = dict(os.environ)
    env.update(plugin_env(units_req=8))         # two 8/16 tenants

    me = os.path.abspath(__file__)
    deadline = time.time() + INIT_TIMEOUT_S
    steady = subprocess.Popen([sys.executable, me, "--steady"], env=env,
                              stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                              text=True, cwd=REPO)
    hog = subprocess.Popen([sys.executable, me, "--hog"], env=env,
                           stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                           text=True, cwd=REPO)
    try:
        for p in (steady, hog):
            line = _readline_deadline(p, deadline)
            if not line.startswith("READY"):
                raise RuntimeError(f"tenant died before ready: {line!r}")
        steady.stdin.write("GO\n")
        steady.stdin.flush()
        # Anchor on the steady tenant's WARM (its window t=0), not on
        # GO: the post-GO re-warm takes time, and firing the hog on the
        # parent's clock would contaminate the 'before' baseline
        # windows.
        line = _readline_deadline(steady, deadline)
        if not line.startswith("WARM"):
            raise RuntimeError(f"steady died before warm: {line!r}")
        time.sleep(HOG_AT_S)                    # steady mid-measurement
        hog.stdin.write("GO\n")
        hog.stdin.flush()
        hog_out, _ = hog.communicate(timeout=600)
        steady_out, _ = steady.communicate(timeout=600)
    finally:
        for p in (steady, hog):
            if p.poll() is None:
                p.kill()

    def payload(out, tag):
        lines = [l for l in (out or "").splitlines() if l.startswith(tag)]
        if not lines:
            raise RuntimeError(f"no {tag!r} in tenant output: {out[-400:]!r}")
        return json.loads(lines[-1][len(tag):])

    hog_res = payload(hog_out, "HOG_RESULT ")
    windows = payload(steady_out, "STEADY_RESULT ")
    before = [w["tokens_per_sec"] for w in windows if w["t"] < HOG_AT_S - 1]
    after = [w["tokens_per_sec"] for w in windows if w["t"] >= HOG_AT_S - 1]
    mean = lambda xs: sum(xs) / len(xs) if xs else 0.0  # noqa: E731
    degradation_pct = (100.0 * (1 - mean(after) / mean(before))
                       if mean(before) else 0.0)
    result = {
        "metric": "hbm_isolation",
        "value": round(degradation_pct, 2),
        "unit": "% steady-tenant degradation during neighbor OOM",
        "vs_baseline": None,
        "backend": backend if on_tpu else "cpu",
        "hog": hog_res,
        "steady_windows": windows,
        # On chip the verdict requires the OOM to land NEAR the grant
        # (a hog that sails 4 GiB past its fraction before dying is a
        # failed limit, not isolation) AND the neighbor to be
        # unaffected; on CPU only the protocol is being validated.
        "isolated": bool(
            (not on_tpu or (hog_res["oomed"]
                            and hog_res["oom_within_1gib_of_limit"]))
            and degradation_pct < 10.0),
    }
    if on_tpu:
        path = os.path.join(REPO, "chiprun_out", "isolation.json")
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump(result, f, indent=1)
        log(f"isolation artifact: {path}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    if "--steady" in sys.argv:
        steady_main()
    elif "--hog" in sys.argv:
        hog_main()
    else:
        raise SystemExit(main())
