"""Training loop driver: steps, checkpointing, deterministic resume.

The glue between the SPMD train steps (training.py) and the tenant
lifecycle: a bin-packed training pod can be preempted or rescheduled at
any time (the plugin's world is annotations + rebind, SURVEY.md §3.4),
so the loop checkpoints params+opt-state+step and resumes bit-exact —
tests/test_trainer.py proves interrupted == uninterrupted.

Kept deliberately functional: ``fit`` drives any (params, opt_state,
tokens) -> (params, opt_state, loss) step function; data order is the
caller's responsibility (pass a deterministic iterator for exact
resume).
"""

from __future__ import annotations

import logging
import os
from typing import Any, Callable, Dict, Iterable, Optional, Tuple

import jax

from tpushare.utils import checkpoint

log = logging.getLogger("tpushare.trainer")

StepFn = Callable[..., Tuple[Any, Any, Any]]


def save_state(path: str, params: Any, opt_state: Any, step: int) -> None:
    checkpoint.save(path, {"params": params, "opt_state": opt_state,
                           "step": jax.numpy.asarray(step)})


def load_state(path: str, *, like_params: Any, like_opt: Any,
               shardings: Optional[Dict[str, Any]] = None):
    """Restore (params, opt_state, step); shardings optionally remap
    onto a new mesh (the rescheduled-tenant path)."""
    like = {"params": like_params, "opt_state": like_opt,
            "step": jax.numpy.asarray(0)}
    sh = None
    if shardings is not None:
        sh = {"params": shardings["params"],
              "opt_state": shardings["opt_state"],
              "step": None}
    state = checkpoint.restore(path, like=like, shardings=sh)
    return state["params"], state["opt_state"], int(state["step"])


def fit(step_fn: StepFn, params: Any, opt_state: Any,
        batches: Iterable[Any], *,
        steps: int,
        start_step: int = 0,
        ckpt_dir: Optional[str] = None,
        ckpt_every: int = 0,
        log_every: int = 10,
        tokens_per_step: int = 0,
        flops_per_step: float = 0.0,
        tpu_generation: Optional[str] = None,
        n_chips: int = 0) -> Tuple[Any, Any, list]:
    """Run ``steps`` optimizer steps from ``start_step``.

    ``batches`` must already be positioned at ``start_step`` (resume
    determinism is data-order determinism). Returns (params, opt_state,
    losses). Checkpoints land in ckpt_dir/step_<n>.

    Throughput telemetry: pass ``tokens_per_step`` to log tokens/sec
    over each log window (the loss read acts as the device sync), and
    ``flops_per_step`` with ``tpu_generation`` (the chip whose peak MFU
    divides by; without it no MFU is logged) to log MFU via
    utils/profiling — e.g. profiling.transformer_flops(cfg, B, S,
    training=True) for a train step with GLOBAL batch B. MFU divides
    by ``n_chips`` x one chip's peak (0 = len(jax.devices()), the
    whole visible mesh). The first window includes jit compile time,
    so its line is excluded from the throughput telemetry (warmup).
    """
    import time

    losses = []
    it = iter(batches)
    window_t0 = time.perf_counter()
    window_steps = 0
    warmed = False       # first window holds jit compile: no telemetry
    for step in range(start_step, steps):
        batch = next(it)
        params, opt_state, loss = step_fn(params, opt_state, batch)
        losses.append(loss)
        window_steps += 1
        if log_every and (step + 1) % log_every == 0:
            loss_f = float(loss)          # device sync for honest timing
            dt = time.perf_counter() - window_t0
            msg = f"step {step + 1} loss {loss_f:.4f}"
            if warmed and tokens_per_step and dt > 0 and window_steps:
                msg += (f" | {tokens_per_step * window_steps / dt:,.0f}"
                        f" tok/s")
            if (warmed and flops_per_step and tpu_generation
                    and dt > 0 and window_steps):
                from tpushare.utils import profiling
                m = profiling.mfu(flops_per_step, dt / window_steps,
                                  tpu_generation,
                                  n_chips=n_chips or len(jax.devices()))
                if m is not None:
                    msg += f" | mfu {100 * m:.1f}%"
            log.info("%s", msg)
            window_t0 = time.perf_counter()
            window_steps = 0
            warmed = True
        if ckpt_dir and ckpt_every and (step + 1) % ckpt_every == 0:
            path = os.path.join(ckpt_dir, f"step_{step + 1}")
            save_state(path, params, opt_state, step + 1)
            log.info("checkpointed %s", path)
    return params, opt_state, losses


def latest_checkpoint(ckpt_dir: str) -> Optional[str]:
    """Newest step_<n> directory, or None."""
    if not os.path.isdir(ckpt_dir):
        return None
    steps = []
    for name in os.listdir(ckpt_dir):
        if name.startswith("step_") and name[5:].isdigit():
            steps.append(int(name[5:]))
    if not steps:
        return None
    return os.path.join(ckpt_dir, f"step_{max(steps)}")
