"""Profiling and throughput accounting for tenant workloads.

The reference's only diagnostic is a SIGQUIT goroutine dump
(coredump.go; mirrored by plugin/coredump.py). Tenant JAX processes
get more: an XLA trace context (view in TensorBoard/Perfetto), a
steady-state step timer, and model FLOPs accounting so benchmarks can
report MFU (model FLOPs utilization) against the chip's peak — the
number that tells you whether co-located tenants are compute-starved
or just HBM-bound.

The serving path traces itself with two things from here. ``span``
names a stretch of host code in whatever profiler session is running
(``trace()`` above, or anyone's ``jax.profiler.start_trace``), on the
device trace's clock; ``StageClock`` cuts one thread's loop into named
stages, each a span plus an always-on cumulative wall and thread-CPU
clock that ``/stats`` shows (the two apart say how much of a stage was
work and how much waiting: for the device, the interpreter's lock or
the OS). Neither takes a flag: spans are on when someone traces, clocks
always.
"""

from __future__ import annotations

import contextlib
import time
from typing import Callable, Optional

import jax

# Peak dense bf16 FLOP/s per chip (public figures) — used for MFU.
PEAK_FLOPS = {
    "v4": 275e12,
    "v5e": 197e12,
    "v5p": 459e12,
    "v6e": 918e12,
}

# Peak HBM bandwidth per chip, bytes/s (public figures) — decode is
# bandwidth-bound, so its utilization denominator is bytes streamed
# per step / this, not FLOPs (VERDICT r3 #5: a tokens/sec claim with
# no roofline denominator says nothing about how good it is).
HBM_BANDWIDTH = {
    "v4": 1228e9,
    "v5e": 819e9,
    "v5p": 2765e9,
    "v6e": 1640e9,
}


def _peak(table: dict, generation: str) -> float:
    """A chip that is not in the table is an error, not a default: a
    utilization against another chip's peak is a wrong number that
    reads like a measured one."""
    if generation not in table:
        raise ValueError(f"no peak figure for TPU generation "
                         f"{generation!r} (known: {sorted(table)})")
    return table[generation]


def bandwidth_utilization(bytes_per_step: float, step_seconds: float,
                          generation: str,
                          n_chips: int = 1) -> Optional[float]:
    """Achieved HBM bandwidth as a fraction of ``generation``'s peak
    (None for a non-positive step time; an unknown generation raises).
    ``bytes_per_step`` = bytes that MUST move between HBM and VMEM per
    step (weights read once + live KV read + KV writes) — the
    decode-regime roofline denominator."""
    bw = _peak(HBM_BANDWIDTH, generation)
    if step_seconds <= 0:
        return None
    return bytes_per_step / step_seconds / (bw * n_chips)


@contextlib.contextmanager
def trace(log_dir: str):
    """XLA profiler trace around a block: with trace('/tmp/tb'): step()."""
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


#: every span of the program carries this prefix in a trace
SPAN_PREFIX = "tpushare."


def span(name: str, **args):
    """A host span ``tpushare.<name>`` in the running profiler session
    (``/host:CPU``, the calling thread's line, the device trace's
    clock); ``args`` become the event's stats, and more can be added
    before it closes with ``.set_metadata(**args)``. With no session on
    entering costs one flag test. Never a barrier, never a device
    call."""
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name, **args)


class StageClock:
    """One thread's loop cut into named stages that do not overlap:
    ``with clock.stage(name):`` is the span ``<prefix>.<name>`` plus
    the elapsed ``time.monotonic()`` added to ``ms[name]``, the
    elapsed ``time.thread_time()`` (the thread's own CPU time) to
    ``cpu_ms[name]`` and one to ``n[name]``. Whatever the thread does
    under no stage shows as the remainder against its wall clock. One
    writer (the owning thread) and no lock: every stage is named up
    front so the dicts never change size, and ``snapshot()`` hands
    other threads a copy."""

    def __init__(self, prefix: str, stages):
        self._prefix = prefix + "."
        self.ms = {name: 0.0 for name in stages}
        self.cpu_ms = {name: 0.0 for name in stages}
        self.n = {name: 0 for name in stages}

    @contextlib.contextmanager
    def stage(self, name: str, **args):
        t0, c0 = time.monotonic(), time.thread_time()
        try:
            with span(self._prefix + name, **args) as sp:
                yield sp
        finally:
            # read in the reverse order: the CPU interval lies inside
            # the wall one, so a stage's CPU never exceeds its wall
            self.cpu_ms[name] += (time.thread_time() - c0) * 1e3
            self.ms[name] += (time.monotonic() - t0) * 1e3
            self.n[name] += 1

    def snapshot(self) -> dict:
        """{"ms": {stage: cumulative wall ms}, "cpu_ms": {stage:
        cumulative thread-CPU ms}, "n": {stage: entries}}."""
        return {"ms": {k: round(v, 3) for k, v in self.ms.items()},
                "cpu_ms": {k: round(v, 3) for k, v in self.cpu_ms.items()},
                "n": dict(self.n)}


def time_step(fn: Callable, *args, warmup: int = 2, iters: int = 10,
              **kwargs) -> float:
    """Median wall-clock seconds of ``fn(*args)`` at steady state."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args, **kwargs))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args, **kwargs))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def time_step_chained(body: Callable, init, *consts, k_lo: int = 16,
                      k_hi: int = 256, iters: int = 5,
                      min_credible_delta_s: float = 0.020) -> tuple:
    """Per-step seconds of ``body`` (carry[, *consts] -> carry) with
    the host's per-dispatch cost cancelled out; returns
    ``(seconds, credible)``.

    ``consts`` are loop-invariant operands (params, caches) passed as
    REAL jit arguments. Closing over them instead bakes them into the
    lowered module as constants — a gemma-2b body captured 5 GB of
    weights that way and the 1-core XLA compile ran for upwards of 25
    minutes before being killed (r3); as arguments the same program
    compiles in normal time.

    ``time_step`` times one dispatch, host overhead included — for a
    sub-millisecond kernel that is mostly the host. Here each timed
    call is a ``lax.scan`` chain of K data-dependent steps ending in a
    device->host scalar readback, and the per-step time is the
    difference between a k_hi-long and a k_lo-long chain divided by
    (k_hi - k_lo), so dispatch and readback cancel (the methodology
    of benchmarks/bench_kernels.py). Each chain is timed with
    ``time_step`` (median of ``iters``). ``credible`` is False when
    the chain delta is inside the jitter floor — callers must not
    report such a reading as a measured value.
    """
    import jax.numpy as jnp

    def make(k):
        def chained(c, *cs):
            def b(carry, _):
                return body(carry, *cs), jnp.float32(0)
            cf, _ = jax.lax.scan(b, c, None, length=k)
            leaf = jax.tree.leaves(cf)[0]
            return jnp.sum(leaf.astype(jnp.float32))
        jfn = jax.jit(chained)
        return lambda c, *cs: float(jfn(c, *cs))        # scalar readback

    t_lo = time_step(make(k_lo), init, *consts, warmup=2, iters=iters)
    t_hi = time_step(make(k_hi), init, *consts, warmup=2, iters=iters)
    delta = t_hi - t_lo
    credible = delta >= min_credible_delta_s
    return max(delta, 1e-9) / (k_hi - k_lo), credible


#: newest host-gap samples kept by the engine's ring (matches the
#: tier-latency SAMPLE_CAP in slo/stats.py).
HOST_GAP_CAP = 512


def gap_percentiles(samples_ms) -> dict:
    """{p50, p99} (ms, nearest-rank) over a host-gap sample ring —
    the /stats ``host_gap_ms`` spelling. Values are None until the
    first overlapped dispatch records a gap; callers in serial mode
    report the whole block as null instead (null-not-0: a serial
    engine has no host gap to hide, not a zero-length one)."""
    out = {}
    for name, q in (("p50", 0.50), ("p99", 0.99)):
        if not samples_ms:
            out[name] = None
            continue
        ordered = sorted(samples_ms)
        idx = min(len(ordered) - 1, int(q * len(ordered)))
        out[name] = round(ordered[idx], 3)
    return out


class PhaseTimer:
    """Chained per-phase wall-clock accumulator: ``start()`` opens a
    chain, each ``mark(phase, block_on=...)`` closes the span since the
    previous mark/start and charges it to ``phase``. Passing the
    phase's output arrays as ``block_on`` drains the device queue
    first, so async-dispatched work is attributed to the phase that
    dispatched it — the same discipline ``time_step`` uses, applied
    per phase instead of per step.

    MEASUREMENT MODE ONLY: the ``block_until_ready`` barriers it
    inserts are exactly the host-device syncs the serving hot loop
    must never make (the one-fetch-per-tick invariant,
    tests/test_sync_free.py). The speculative seam
    (models/spec.py) carries a timer slot that defaults to None —
    attach one ONLY in benches and diagnostics (the
    ``spec_horizon_sweep`` bench row's draft/verify/accept-fold
    breakdown rides this)."""

    def __init__(self):
        self.seconds: dict = {}
        self.counts: dict = {}
        self._t0: Optional[float] = None

    def start(self) -> None:
        """Open a chain; the next mark() measures from here."""
        self._t0 = time.perf_counter()

    def mark(self, phase: str, block_on=None) -> None:
        """Close the open span as ``phase`` (no-op when no chain is
        open, so an un-started timer costs nothing on any path)."""
        if self._t0 is None:
            return
        if block_on is not None:
            jax.block_until_ready(block_on)
        now = time.perf_counter()
        self.seconds[phase] = self.seconds.get(phase, 0.0) \
            + (now - self._t0)
        self.counts[phase] = self.counts.get(phase, 0) + 1
        self._t0 = now

    def snapshot(self) -> dict:
        """{phase: {seconds, count, fraction}} — fractions over the
        total accumulated time (the bench-row spelling)."""
        total = sum(self.seconds.values())
        return {
            ph: {"seconds": round(s, 6),
                 "count": self.counts.get(ph, 0),
                 "fraction": round(s / total, 4) if total else None}
            for ph, s in self.seconds.items()
        }


def phase_roofline(snapshot: dict, phase_bytes: dict, n_steps: int,
                   generation: Optional[str] = None, n_chips: int = 1,
                   on_chip: bool = True) -> dict:
    """PhaseTimer snapshot + per-phase must-move bytes -> the
    phase×roofline table bench_moe.py emits per decode row:
    {phase: {fraction, ms_per_step, bytes_per_step_mib,
    pct_of_roofline}}.

    ``fraction`` is the phase's share of the measured step (where the
    time goes); ``pct_of_roofline`` is that phase's achieved HBM
    bandwidth against ITS OWN mandatory byte floor (how good the
    phase is at moving what it must) — a phase with a large fraction
    AND a low roofline % is the one paying for traffic its floor does
    not include, which is exactly the localization the aggregate
    pct_of_roofline could not give. Zero-byte phases (dequant,
    dispatch: pure overhead at decode shapes) report pct None —
    their fraction IS the indictment. Off-chip (``on_chip`` False)
    every pct is None and ``generation`` is not needed: CPU fractions
    prove the machinery, not the bandwidth story. On chip an unknown
    (or missing) generation raises."""
    bw = _peak(HBM_BANDWIDTH, generation) if on_chip else None
    rows = {}
    for ph, rec in snapshot.items():
        sec = rec["seconds"] / max(n_steps, 1)
        nb = phase_bytes.get(ph)
        pct = None
        if on_chip and bw and nb and sec > 0:
            pct = round(100.0 * nb / sec / (bw * n_chips), 1)
        rows[ph] = {
            "fraction": rec["fraction"],
            "ms_per_step": round(sec * 1e3, 3),
            "bytes_per_step_mib": (round(nb / 2 ** 20, 2) if nb
                                   else None),
            "pct_of_roofline": pct,
        }
    return rows


def transformer_flops(cfg, batch: int, seq: int, *,
                      training: bool = False) -> float:
    """Dense-transformer FLOPs for one forward (×3 for fwd+bwd).

    2·params·tokens for the matmuls plus the attention score/value
    terms (2·2·B·S²·H·Dh per layer, halved for causal masking).
    """
    tokens = batch * seq
    # The input-embedding gather does no matmul FLOPs, so the vocab
    # projection counts exactly once whether or not embeddings are
    # tied: num_params() holds one table copy when tied (it *is* the
    # unembed matmul) and two when untied (drop the gather-only one).
    embed_table = cfg.vocab_size * cfg.d_model
    active = cfg.num_params()
    if not getattr(cfg, "tie_embeddings", True):
        active -= embed_table
    matmul = 2.0 * active * tokens
    attn = cfg.n_layers * 2 * 2 * batch * seq * seq * cfg.q_dim / 2
    total = matmul + attn
    return 3.0 * total if training else total


def mfu(flops_per_step: float, step_seconds: float,
        generation: str, n_chips: int = 1) -> Optional[float]:
    """Model FLOPs utilization in [0, 1] against ``generation``'s peak
    (None for a non-positive step time; an unknown generation raises)."""
    peak = _peak(PEAK_FLOPS, generation)
    if step_seconds <= 0:
        return None
    return flops_per_step / step_seconds / (peak * n_chips)
