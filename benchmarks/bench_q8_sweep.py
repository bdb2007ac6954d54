"""int8 paged-decode crossover re-sweep (VERDICT r4 weak #2).

The r3 sweep that set ``PAGED_Q8_KERNEL_MIN_CTX = 8192`` timed the
gathered-dequant fallback WITH a whole-pool scale transpose inside the
measured region; r4 moved scales into the kernel layout at pool init
(quant.scales_to_pool_layout), so the shipped crossover constant is
known-conservative — "the real crossover can only be at or below 8k"
(docs/DECODE_ROOFLINE.md). This sweep re-measures both sides
post-layout-fix, at the production code paths:

- kernel side: ops.flash_attention.paged_flash_decode with pool-layout
  scale pages (in-kernel dequant after the page DMA);
- fallback side: the transformer.py Sq==1 gathered branch verbatim —
  table-gather the int8 pools, pool_scales_to_rows, kv_dequantize to
  a dense [B, mb*bs] bf16 view, masked reference attention.

Timing is the shared chain-differenced harness (bench_kernels:
pools ride the scan carry, one row scattered per step, scalar-readback
barrier). One JSON row per context, each naming its backend, plus a
summary row recommending the new MIN_CTX: the smallest swept context
from which the kernel wins monotonically.

Usage: python benchmarks/bench_q8_sweep.py [--iters 5]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

# (ctx, B): B drops at 32k so the dense bf16 gathered view of the
# fallback side still fits next to both pools.
SWEEP = [(2048, 8), (4096, 8), (8192, 8), (16384, 8), (32768, 4)]
H, HKV, D, BS = 8, 2, 128, 128          # gemma-2b-shaped heads (r3 sweep)


def one_ctx(ctx: int, B: int, iters: int) -> dict:
    from benchmarks.bench_kernels import _timeit_paged_chained
    from tpushare.models.quant import (kv_dequantize, kv_quantize,
                                       pool_scales_to_rows,
                                       scales_to_pool_layout)
    from tpushare.ops.attention import mha_reference
    from tpushare.ops.flash_attention import paged_flash_decode

    mb = ctx // BS
    nb = B * mb + 1
    key = jax.random.PRNGKey(ctx)
    kq, kk, kv_ = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, 1, H, D), jnp.bfloat16)
    pool_k = jax.random.normal(kk, (nb, BS, HKV, D), jnp.bfloat16)
    pool_v = jax.random.normal(kv_, (nb, BS, HKV, D), jnp.bfloat16)
    table = jnp.asarray(
        (1 + np.arange(B)[:, None] * mb + np.arange(mb)[None, :]
         ).astype(np.int32))
    pos = jnp.full((B,), ctx - 2, jnp.int32)     # worst case: full slots
    qk, sk_r = kv_quantize(pool_k)
    qv, sv_r = kv_quantize(pool_v)
    sk = scales_to_pool_layout(sk_r)             # pool layout from init,
    sv = scales_to_pool_layout(sv_r)             # outside the timed region

    def kernel_fn(qc, pkc, pvc, t, pc):
        return paged_flash_decode(qc, pkc, pvc, t, pc,
                                  k_scale=sk, v_scale=sv)

    def gathered_fn(qc, pkc, pvc, t, pc):
        # transformer.py Sq==1 fallback branch, verbatim shapes.
        ks_r = pool_scales_to_rows(sk[t], HKV)
        vs_r = pool_scales_to_rows(sv[t], HKV)
        kd = kv_dequantize(pkc[t], ks_r, jnp.bfloat16
                           ).reshape(B, mb * BS, HKV, D)
        vd = kv_dequantize(pvc[t], vs_r, jnp.bfloat16
                           ).reshape(B, mb * BS, HKV, D)
        kv_mask = jnp.arange(mb * BS)[None, :] <= pc[:, None]
        return mha_reference(qc, kd, vd, causal=False, kv_mask=kv_mask)

    # Parity first (the sweep is also a full-slot correctness pin).
    out = jax.jit(kernel_fn)(q, qk, qv, table, pos)
    ref = jax.jit(gathered_fn)(q, qk, qv, table, pos)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - ref.astype(jnp.float32))))

    k_ms, k_cred = _timeit_paged_chained(kernel_fn, q, qk, qv, table,
                                         pos, iters=iters)
    g_ms, g_cred = _timeit_paged_chained(gathered_fn, q, qk, qv, table,
                                         pos, iters=iters)
    return {
        "sweep": "paged_q8_crossover_r5", "backend": jax.default_backend(),
        "ctx": ctx, "B": B, "max_err": round(err, 5),
        "gathered_ms": round(g_ms, 3), "int8_kernel_ms": round(k_ms, 3),
        "speedup": round(g_ms / k_ms, 2) if k_ms else 0.0,
        "timing_credible": bool(k_cred and g_cred),
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=5)
    args = ap.parse_args()

    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu:
        # CPU run validates the harness only; its rows say
        # backend="cpu" and are no device measurement.
        global SWEEP
        SWEEP = [(256, 2), (512, 2)]

    rows = []
    for ctx, B in SWEEP:
        row = one_ctx(ctx, B, args.iters)
        rows.append(row)
        print(json.dumps(row), flush=True)

    # Smallest context from which the (credible) kernel wins and keeps
    # winning — the dispatch constant the sweep exists to set.
    rec = None
    for row in sorted(rows, key=lambda r: r["ctx"]):
        if row["timing_credible"] and row["speedup"] >= 1.0:
            rec = row["ctx"] if rec is None else rec
        elif row["timing_credible"]:
            rec = None                   # a later loss resets the run
    print(json.dumps({
        "sweep_summary": "paged_q8_crossover_r5",
        "backend": jax.default_backend(),
        "recommended_min_ctx": rec,
        "current_constant": 8192,
    }), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
