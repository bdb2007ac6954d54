"""North-star benchmark: two co-located tenant PROCESSES on one chip.

BASELINE.md's headline target is two JAX inference pods bin-packed on
one chip, each reaching >=95% of whole-chip tokens/sec (the reference
publishes no numbers of its own — SURVEY.md §6 — so BASELINE.json's
north star is the bar). Round 1 approximated co-location with two
threads sharing one jitted fn: that measured GIL-serialized dispatch on
one XLA queue, not the plugin's contract. This bench measures the real
scenario: the parent allocates through the plugin's single-chip
Allocate fast path (the same env a kubelet would inject into the pod),
then spawns tenant OS processes that call ``apply_tenant_limits()``
before JAX init — process isolation, per-tenant HBM fraction, separate
XLA clients.

stdout: ONE JSON line. A measurement names its device; a run that
cannot measure (no chip, a device kind the peak tables do not know, a
tenant that could not open the chip, a tenant that died) prints
``{"ok": false, "error": ...}`` naming the failure and exits 1. There
is no CPU fallback: a CPU run says nothing about sharing a chip.
stderr: diagnostics incl. MFU.

Env knobs:
  TPUSHARE_BENCH_INIT_TIMEOUT  seconds the probe, and each tenant, may
                               take to reach the chip and compile (300)
  TPUSHARE_BENCH_SECONDS       measured window per phase, s (6.0)
  TPUSHARE_BENCH_CHAIN_K       device-chained steps per dispatch (16)
  TPUSHARE_BENCH_FORCE_CPU     1 = harness mode: the tenants run a tiny
                               model on the CPU so tests can drive the
                               parent/tenant protocol. Its record is
                               labelled (backend cpu, value null,
                               advisory_cpu_pct) and scores nothing.
  JAX_COMPILATION_CACHE_DIR    where the persistent XLA cache goes
                               (tpushare.utils.compile_cache: unset, a
                               fixed directory inside the checkout)
"""

from __future__ import annotations

import json
import os
import select
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

INIT_TIMEOUT_S = float(os.environ.get("TPUSHARE_BENCH_INIT_TIMEOUT", "300"))
BENCH_SECONDS = float(os.environ.get("TPUSHARE_BENCH_SECONDS", "6.0"))
RESULT_TAG = "TENANT_RESULT "
WINDOWS_PATH = os.path.join(REPO, "chiprun_out", "bench_windows.json")


class BenchFailure(RuntimeError):
    """The bench could not measure; main() turns it into the one
    failure line and exit code 1."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _probe_once(attempt_s: float) -> tuple:
    """One killable probe: (backend, device_kind) or (None, reason).

    The probe is a subprocess because a parent that has touched JAX
    holds the chip, and the tenants could then not open it."""
    code = ("import jax\n"
            "d = jax.devices()\n"
            "print('PROBE|' + jax.default_backend() + '|' + d[0].device_kind,"
            " flush=True)\n")
    # Child output goes to a tempfile, not a pipe: verbose libtpu init
    # logging could fill a 64 KiB pipe and deadlock a healthy probe.
    with tempfile.TemporaryFile(mode="w+", prefix="tpushare-probe-") as sink:
        proc = subprocess.Popen([sys.executable, "-c", code],
                                stdout=sink, stderr=subprocess.STDOUT,
                                text=True)
        try:
            proc.wait(timeout=attempt_s)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
            return None, f"hung >{attempt_s:.0f}s"
        sink.seek(0)
        out = sink.read() or ""
    for line in out.splitlines():
        if line.startswith("PROBE|"):
            _, backend, kind = line.split("|", 2)
            return backend, kind
    return None, f"rc={proc.returncode}: {out.strip()[-400:]}"


def probe_backend() -> tuple:
    """(backend, device_kind, generation) of the accelerator a JAX child
    sees. One attempt, bounded by TPUSHARE_BENCH_INIT_TIMEOUT. Raises
    BenchFailure when the child fails or hangs, when JAX resolves to the
    CPU, or when the device kind is one the peak tables do not know."""
    backend, kind = _probe_once(INIT_TIMEOUT_S)
    if backend is None:
        raise BenchFailure(f"accelerator probe failed: {kind}")
    if backend == "cpu":
        raise BenchFailure("no accelerator: JAX resolved to the cpu "
                           "backend, and a CPU run does not measure "
                           "chip sharing")
    from tpushare.plugin.backend import generation_from_kind
    try:
        generation = generation_from_kind(kind)
    except ValueError as e:
        raise BenchFailure(str(e))
    log(f"probe: backend={backend} device={kind!r} ({generation})")
    return backend, kind, generation


def bench_backend() -> tuple:
    """(backend, generation) for the scripts under benchmarks/: the
    TPUSHARE_BENCH_FORCE_CPU harness mode answers ("cpu", None) without
    a probe; otherwise the probe's answer — or its BenchFailure, which
    ends the script: none of them measures without a chip."""
    if os.environ.get("TPUSHARE_BENCH_FORCE_CPU"):
        return "cpu", None
    backend, _kind, generation = probe_backend()
    return backend, generation


def plugin_env(units_req: int = 8, units_per_chip: int = 16) -> dict:
    """The env the plugin would inject for an ``units_req``-GiB pod:
    runs the real Allocate single-chip fast path (allocate.py:158-164,
    mirroring /root/reference/pkg/gpu/nvidia/allocate.go:154-181) on a
    1-chip fake topology."""
    from tpushare.deviceplugin import pb
    from tpushare.plugin.allocate import Allocator
    from tpushare.plugin.backend import FakeBackend
    from tpushare.plugin.devices import expand_devices
    from tpushare.plugin import const

    # Built directly, not through auto_backend(): the single-chip fast
    # path needs exactly this topology, whatever TPUSHARE_FAKE_* the
    # ambient env carries.
    topo = FakeBackend(chips=1, hbm_gib=units_per_chip, mesh=(1, 1, 1),
                       generation="v5e").probe()
    devmap = expand_devices(topo)

    class _NoPendingPods:
        def get_candidate_pods(self):
            return []

    alloc = Allocator(devmap, topo, _NoPendingPods(), kube=None)
    ids = [d.ID for d in devmap.devices[:units_req]]
    resp = alloc.allocate(pb.AllocateRequest(container_requests=[
        pb.ContainerAllocateRequest(devicesIDs=ids)]))
    envs = dict(resp.container_responses[0].envs)
    visible = envs.get(const.ENV_TPU_VISIBLE_CHIPS, "")
    if visible.startswith("no-tpu"):
        raise BenchFailure(f"allocation poisoned: {envs}")
    return envs


def _readline_deadline(p: subprocess.Popen, deadline: float) -> str:
    """One stdout line from ``p``, or raise if ``deadline`` passes
    first (a tenant hung in TPU init must not wedge the bench)."""
    while True:
        remaining = deadline - time.time()
        if remaining <= 0:
            raise RuntimeError("tenant warmup deadline exceeded")
        ready, _, _ = select.select([p.stdout], [], [], min(remaining, 5.0))
        if ready:
            return p.stdout.readline()
        if p.poll() is not None:
            return p.stdout.readline()   # EOF drains without blocking


def _run_streams(child_env: dict, n: int) -> list:
    """Spawn n tenant processes; barrier them past compile so all
    streams measure the same contended window; return parsed results.
    A tenant that dies or stalls raises with the tail of its stderr —
    when a second process cannot open a chip the first one holds, that
    tail is libtpu's own message."""
    ready_deadline = time.time() + INIT_TIMEOUT_S
    errs = [tempfile.TemporaryFile(mode="w+", prefix="tpushare-tenant-")
            for _ in range(n)]
    procs = [subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--tenant"],
        env=dict(child_env, TPUSHARE_BENCH_STREAM=str(i)),
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, stderr=errs[i],
        text=True, cwd=REPO) for i in range(n)]

    def _await(i: int, word: str, deadline: float) -> None:
        try:
            line = _readline_deadline(procs[i], deadline)
        except RuntimeError as e:
            line = f"<{e}>"
        if not line.startswith(word):
            errs[i].seek(0)
            raise RuntimeError(
                f"tenant {i} of {n} never said {word} (got {line!r}, "
                f"rc={procs[i].poll()}); its stderr ends: "
                f"{errs[i].read()[-1500:]}")

    try:
        for i in range(n):
            _await(i, "READY", ready_deadline)
        # Two-step barrier: GO triggers each tenant's re-warm (the
        # first dispatch after the idle READY gap can be slow); the
        # phase anchor t0 is broadcast only after every tenant reports
        # WARM, so the measured windows overlap regardless of how long
        # any one re-warm took.
        for p in procs:
            p.stdin.write("GO\n")
            p.stdin.flush()
        warm_deadline = time.time() + 120
        for i in range(n):
            _await(i, "WARM", warm_deadline)
        t0 = time.time() + 0.5       # shared wall-clock phase anchor
        for p in procs:
            p.stdin.write(f"T0 {t0}\n")
            p.stdin.flush()
        results = []
        for i, p in enumerate(procs):
            out, _ = p.communicate(timeout=4 * BENCH_SECONDS + 120)
            payload = [l for l in out.splitlines()
                       if l.startswith(RESULT_TAG)]
            if p.returncode != 0 or not payload:
                errs[i].seek(0)
                raise RuntimeError(
                    f"tenant {i} of {n} exited rc={p.returncode} with "
                    f"{len(payload)} result lines; its stderr ends: "
                    f"{errs[i].read()[-1500:]}")
            results.append(json.loads(payload[-1][len(RESULT_TAG):]))
        for f in errs:               # the tenants' diagnostics (MFU...)
            f.seek(0)
            sys.stderr.write(f.read())
        return results
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
        for f in errs:
            f.close()


def tenant_main() -> None:
    """One tenant pod: consume the injected env exactly as a real
    tenant would (utils/tenant.py), then run two measured phases and
    report throughput + MFU.

    Phase "serve": a request-driven inference loop — one blocked
    forward per request, the pattern of the BASELINE scenario (two
    *inference pods* bin-packed on a chip; such pods are latency-
    bound with idle device time between requests, which is exactly
    the headroom the plugin's co-location sells). The headline metric
    compares co-located vs solo serve throughput.

    Phase "sat": a device-chained scan of K forwards per dispatch
    (each step's tokens derive from the previous step's output, so
    the device must serialize them; one host sync per K steps). This
    measures device-saturated throughput with the host out of the
    loop. MFU is reported from this phase.

    Phases are aligned across tenants by wall-clock windows around
    the parent's broadcast t0 (same host, same clock).
    """
    from tpushare.utils.tenant import apply_tenant_limits, get_enforcing_guard

    # Disjoint host-core slice per tenant, like the cpuset a kubelet
    # gives each pod: the contended resource under test is the chip,
    # not host CPU. No-op when the host is too small to partition.
    stream = int(os.environ.get("TPUSHARE_BENCH_STREAM", "0"))
    ncpu = os.cpu_count() or 1
    k = int(os.environ.get("TPUSHARE_BENCH_CPUS", "0")) or min(4, ncpu // 2)
    if k >= 1 and ncpu >= 2 * k:
        try:
            os.sched_setaffinity(0, range(stream * k, (stream + 1) * k))
        except (AttributeError, OSError, ValueError):
            pass

    apply_tenant_limits()             # before jax init, per contract
    force_cpu = os.environ.get("TPUSHARE_BENCH_FORCE_CPU") == "1"
    generation = os.environ.get("TPUSHARE_TPU_GENERATION")
    import jax
    if force_cpu:
        # Harness mode. CPU compiles are fast and XLA:CPU cache entries
        # are machine-specific — no cache.
        jax.config.update("jax_platforms", "cpu")
    else:
        from tpushare.utils.compile_cache import enable_compile_cache
        enable_compile_cache()
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from tpushare.models import bert

    if not force_cpu and (jax.default_backend() == "cpu"
                          or not generation):
        # The parent probed an accelerator and named its generation;
        # a tenant that then lands on the CPU must not quietly measure
        # a smaller model there.
        raise SystemExit(
            f"tenant: backend={jax.default_backend()!r}, "
            f"TPUSHARE_TPU_GENERATION={generation!r} — no accelerator "
            f"to measure on")
    cfg = bert.tiny() if force_cpu else bert.bert_base()
    batch, seq = (2, 32) if force_cpu else (8, 128)
    chain_k = int(os.environ.get("TPUSHARE_BENCH_CHAIN_K", "16"))
    params = bert.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (batch, seq)))
    fwd = jax.jit(lambda p, t: bert.forward(p, t, cfg)["pooled"])

    def _chain_body(toks, _):
        pooled = bert.forward(params, toks, cfg)["pooled"]
        bump = jnp.sum(pooled).astype(jnp.int32) & 1   # data dependency
        return (toks + bump) % cfg.vocab_size, None

    chain = jax.jit(
        lambda t: lax.scan(_chain_body, t, None, length=chain_k)[0])
    fwd(params, tokens).block_until_ready()            # compile
    chain(tokens).block_until_ready()

    print("READY", flush=True)
    sys.stdin.readline()                               # "GO"
    # Re-warm after the idle READY->GO gap (the other tenant may have
    # spent ~30s compiling) so first-dispatch/re-attach overhead lands
    # before the measured window, not inside it. The parent broadcasts
    # the phase anchor only after every tenant is WARM.
    fwd(params, tokens).block_until_ready()
    chain(tokens).block_until_ready()
    print("WARM", flush=True)
    anchor = sys.stdin.readline().split()              # "T0 <t0>"
    t0 = float(anchor[1]) if len(anchor) > 1 else time.time() + 0.2

    def _window(fn, start, seconds):
        """Blocked calls of fn inside [start, start+seconds); returns
        (completions, measured_elapsed)."""
        while time.time() < start:
            time.sleep(min(0.01, max(0.0, start - time.time())))
        deadline = start + seconds
        calls, w0 = 0, time.perf_counter()
        while time.time() < deadline:
            fn()
            calls += 1
        return calls, time.perf_counter() - w0

    # apply_tenant_limits() armed the enforcing guard (r5): it is the
    # single watchdog — a second manual HbmGuard here would just race
    # it for the breach count, and a real overshoot now kills the
    # tenant with SoftHbmOom (the bench fails loudly) instead of
    # logging past it.
    guard = get_enforcing_guard()
    serve_calls, serve_s = _window(
        lambda: fwd(params, tokens).block_until_ready(),
        t0, BENCH_SECONDS)
    sat_calls, sat_s = _window(
        lambda: chain(tokens).block_until_ready(),
        t0 + BENCH_SECONDS + 2.0, BENCH_SECONDS)

    result = {
        "serve_tokens_per_sec": serve_calls * batch * seq / serve_s,
        "sat_tokens_per_sec": sat_calls * chain_k * batch * seq / sat_s,
        "hbm_breaches": guard.breaches if guard else 0,
    }
    if not force_cpu and sat_calls:
        from tpushare.utils import profiling
        step_s = sat_s / (sat_calls * chain_k)
        m = profiling.mfu(bert.flops_per_forward(cfg, batch, seq), step_s,
                          generation)
        if m is not None:
            result["mfu_pct"] = round(100 * m, 2)
    print(RESULT_TAG + json.dumps(result), flush=True)
    if guard:
        # Its thread polls device memory every 50 ms; left running into
        # interpreter shutdown it aborts the process (rc -6) after the
        # result is already out.
        guard.stop()


def _phase(name: str, env: dict, n: int) -> list:
    try:
        return _run_streams(env, n)
    except (RuntimeError, subprocess.SubprocessError) as e:
        raise BenchFailure(f"{name} phase ({n} tenant process"
                           f"{'es' if n > 1 else ''} on one chip): {e}")


def _measure(solo_env: dict, child_env: dict, extras: dict = None) -> float:
    """A-B-A protocol: solo window, co-located window, solo window
    again — all in one run, so a drifting baseline shows up as A1/A2
    disagreement instead of silently inflating the headline (a
    dispatch-bound solo baseline once read as 126.76%). The headline
    is refused (credible=false, with reasons) when solo variance
    exceeds 5% or co-located/solo exceeds 100%. A phase whose tenants
    cannot start raises, naming the phase."""
    solo_a = _phase("solo[A1]", solo_env, 1)[0]
    if extras is not None and "mfu_pct" in solo_a:
        extras["solo_mfu_pct"] = solo_a["mfu_pct"]
    log(f"solo[A1]: serve {solo_a['serve_tokens_per_sec']:,.0f} tok/s, "
        f"saturated {solo_a['sat_tokens_per_sec']:,.0f} tok/s"
        + (f", mfu {solo_a['mfu_pct']:.1f}%" if "mfu_pct" in solo_a else ""))
    co = _phase("co-located", child_env, 2)
    log("co-located serve: " + " / ".join(
        f"{r['serve_tokens_per_sec']:,.0f}" for r in co) + " tok/s"
        + "; saturated: " + " / ".join(
            f"{r['sat_tokens_per_sec']:,.0f}" for r in co) + " tok/s"
        + ("" if "mfu_pct" not in co[0] else "; mfu " + "/".join(
            f"{r['mfu_pct']:.1f}%" for r in co)))
    for i, r in enumerate(co):
        if r.get("hbm_breaches"):
            log(f"stream {i}: {r['hbm_breaches']} HBM-limit breaches")
    solo_b = _phase("solo[A2]", solo_env, 1)[0]
    log(f"solo[A2]: serve {solo_b['serve_tokens_per_sec']:,.0f} tok/s, "
        f"saturated {solo_b['sat_tokens_per_sec']:,.0f} tok/s")

    a1 = solo_a["serve_tokens_per_sec"]
    a2 = solo_b["serve_tokens_per_sec"]
    solo_serve = (a1 + a2) / 2.0
    variance_pct = (100.0 * abs(a1 - a2) / solo_serve) if solo_serve else 0.0
    if solo_a["sat_tokens_per_sec"] > 0:
        sat_pct = (100.0 * min(r["sat_tokens_per_sec"] for r in co)
                   / solo_a["sat_tokens_per_sec"])
        log(f"saturated co-location: {sat_pct:.1f}% per stream "
            f"(<=50% is physical when both streams saturate the chip)")
    value = (100.0 * min(r["serve_tokens_per_sec"] for r in co)
             / solo_serve) if solo_serve > 0 else 0.0
    log(f"solo A1/A2 variance: {variance_pct:.1f}%")

    reasons = []
    if variance_pct > 5.0:
        reasons.append(f"solo A1/A2 variance {variance_pct:.1f}% > 5%"
                       " (baseline unstable; run not chip-bound)")
    if value > 100.0:
        reasons.append(f"co-located/solo {value:.1f}% > 100% is"
                       " physically impossible against a saturated solo"
                       " baseline (solo was dispatch-bound)")
    if reasons:
        log("HEADLINE REFUSED: " + "; ".join(reasons))
    if extras is not None:
        extras.update({
            "windows": {
                "solo_a1": solo_a, "colocated": co, "solo_a2": solo_b,
            },
            "solo_variance_pct": round(variance_pct, 2),
            "credible": not reasons,
            **({"refusal_reasons": reasons} if reasons else {}),
        })
    return value


def final_record(value: float, measured_backend: str, extras: dict) -> dict:
    """The driver-contract JSON line for a finished measurement.

    An on-chip number that failed the A-B-A gates refuses
    ``vs_baseline``. ``backend == "cpu"`` only ever reaches here from
    the TPUSHARE_BENCH_FORCE_CPU harness mode; two saturated streams on
    shared host cores say nothing about sharing a chip, so that record
    carries NO value under the device metric's name: ``value`` and
    ``vs_baseline`` are null, ``credible`` is false with the reason,
    and the percentage the harness computed is restated as
    ``advisory_cpu_pct``."""
    on_accel = measured_backend != "cpu"
    out = {
        "metric": "colocated_tokens_per_sec_pct",
        "value": round(value, 2) if on_accel else None,
        "unit": "%",
        "backend": measured_backend,
    }
    fields = {k: v for k, v in extras.items() if k != "windows"}
    if not on_accel:
        reasons = list(fields.get("refusal_reasons", []))
        reasons.append(
            "TPUSHARE_BENCH_FORCE_CPU harness mode: a CPU run checks "
            "the parent/tenant protocol, not chip sharing")
        fields["credible"] = False
        fields["refusal_reasons"] = reasons
        fields["advisory_cpu_pct"] = round(value, 2)
    credible = bool(fields.get("credible", True))
    out["vs_baseline"] = (round(value / 95.0, 4)
                          if on_accel and credible else None)
    out.update(fields)
    return out


def failure_record(error: str, device: dict = None) -> dict:
    """The one line a run that could not measure prints: the failure by
    name, the device if one was found, and no number."""
    out = {"ok": False, "metric": "colocated_tokens_per_sec_pct",
           "error": error}
    if device:
        out["device"] = device
    return out


def main() -> int:
    device = None
    try:
        if os.environ.get("TPUSHARE_BENCH_FORCE_CPU") == "1":
            backend, generation = "cpu", None   # harness runs never probe
        else:
            backend, kind, generation = probe_backend()
            device = {"platform": backend, "kind": kind}

        # Solo baseline = a pod granted the WHOLE chip (16/16 units, no
        # HBM fraction), per BASELINE's ">=95% of whole-chip
        # tokens/sec"; the co-located streams run under the half-chip
        # (8/16) tenant env.
        def _env(units_req: int) -> dict:
            env = dict(os.environ)
            env.update(plugin_env(units_req=units_req))
            if generation:
                env["TPUSHARE_TPU_GENERATION"] = generation
            return env

        solo_env, child_env = _env(16), _env(8)
        log("tenant env: " + ", ".join(
            f"{k}={child_env[k]}" for k in sorted(child_env)
            if k.startswith(("TPU_", "TPUSHARE_", "ALIYUN_COM"))))
        extras = {}
        value = _measure(solo_env, child_env, extras)
    except BenchFailure as e:
        log(f"BENCH FAILED: {e}")
        print(json.dumps(failure_record(str(e), device)))
        return 1

    windows = extras.pop("windows", None)
    record = final_record(value, backend, extras)
    if device:
        record["device"] = device
    if backend != "cpu" and windows is not None:
        # Per-window raw numbers beside the headline, under the chip
        # tool's output directory — never into benchmarks/, whose
        # files are records of earlier rounds.
        os.makedirs(os.path.dirname(WINDOWS_PATH), exist_ok=True)
        with open(WINDOWS_PATH, "w") as f:
            json.dump({**record, "windows": windows}, f, indent=1)
        log(f"per-window raws: {WINDOWS_PATH}")
    print(json.dumps(record))
    return 0


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--tenant":
        tenant_main()
    else:
        raise SystemExit(main())
