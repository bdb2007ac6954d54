"""Profiling helpers: step timing, FLOPs accounting, MFU."""

import jax.numpy as jnp
import pytest

from tpushare.models import transformer as tf
from tpushare.utils import profiling


def test_time_step_returns_positive():
    f = lambda x: jnp.sum(x * x)
    t = profiling.time_step(f, jnp.ones((64, 64)), warmup=1, iters=3)
    assert t > 0


def test_time_step_chained_threads_consts_without_capture():
    """Loop-invariant operands ride as jit arguments: the chained body
    must receive them per step and the measurement must come out
    positive. (Closure capture of large consts bakes them into the
    lowered module — the gemma-2b MFU bench hit a >25-minute 1-core
    compile that way; this pins the argument-threading contract.)"""
    w = jnp.full((32, 32), 0.5)

    def body(c, w_):
        assert w_.shape == (32, 32)          # consts reach the body
        return c @ w_ + 1.0

    s, credible = profiling.time_step_chained(
        body, jnp.ones((4, 32)), w, k_lo=1, k_hi=8, iters=2,
        min_credible_delta_s=0.0)
    # credible is jitter-dependent for a microsecond body — only the
    # contract (consts delivered, positive reading) is asserted.
    assert s > 0 and isinstance(credible, bool)


def test_transformer_flops_scale():
    cfg = tf.gemma_2b()
    fwd = profiling.transformer_flops(cfg, batch=1, seq=128)
    # ~2 * 2.5B params * 128 tokens ≈ 6.4e11, plus attention terms.
    assert 5e11 < fwd < 1e12
    assert profiling.transformer_flops(cfg, 1, 128, training=True) == 3 * fwd


def test_mfu_bounds():
    cfg = tf.gemma_2b()
    flops = profiling.transformer_flops(cfg, 8, 128)
    u = profiling.mfu(flops, step_seconds=0.05, generation="v5e")
    assert 0 < u < 1
    # A chip that is not in the table is an error, never another
    # chip's peak (it used to default to v5e / return None).
    with pytest.raises(ValueError, match="unknown-chip"):
        profiling.mfu(flops, 0.05, generation="unknown-chip")
    with pytest.raises(ValueError, match="unknown-chip"):
        profiling.bandwidth_utilization(1e9, 0.05, "unknown-chip")
