"""MoE serving-level decode throughput: dense-dispatch vs dropless.

Times ONE full-model MoE ragged decode step (models/moe.forward) at
serving shapes, over dense KV rows and over the paged pool,
with the chained scan-differenced methodology
(profiling.time_step_chained docstring) so host dispatch cancels out
of the number. Two routing rows tell the MoE decode story:

- routing="psum" (dense dispatch): every local expert computes every
  token — E/K times the ideal expert FLOPs.
- routing="dropless" (ragged_dot grouped GEMMs): exact MoE at the
  ideal T*K expert-FLOP count.
- int8 experts (quant.quantize_params + dequant_hook through
  moe.forward's layers_hook seam): same routing, half the expert
  bytes.
- fused int8 expert path (quant.fused_expert_hook + the ops/q8_expert
  dequant×GEMM pallas kernel): the expert weights stream HBM->VMEM as
  int8 with NO materialized wide copy — the comparison row against
  the dequant-hook path is ROADMAP item 3's measurement.

Every decode row carries ``phase_breakdown``: a per-phase (router /
dispatch / expert GEMM / attention / unembed / dequant) fraction +
per-phase roofline table from the measurement-mode instrumented
forward (moe.forward's phase_timer seam + moe.decode_phase_bytes),
so the aggregate pct_of_roofline gap is LOCALIZED to the phase paying
it. ``scoreable`` is false off-chip — CPU rows prove the row shape
and the machinery (incl. the pallas kernel via interpreter-mode
parity) before a TPU run banks numbers.

At decode batch (T = n_slots tokens/step) both routings are expected
to sit at the weight-streaming roofline — all E experts' weights must
cross HBM once per step regardless of routing — which is exactly why
the int8 row should approach 2x: halving the streamed bytes halves a
bandwidth-bound step. A prefill row (T = B*S tokens) is where
dropless' FLOP advantage can actually show. None of these rows is a
number of record: the serving path is measured by `tpubench`
(PERF.md).

Prints one JSON row per configuration. Usage:
  python benchmarks/bench_moe.py [--slots 8] [--ctx 2048] [--layers 8]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--ctx", type=int, default=2048)
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--prefill-seq", type=int, default=512)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import bench_backend
    from tpushare.models import moe
    from tpushare.utils import profiling

    backend, generation = bench_backend()
    on_tpu = backend != "cpu"
    if not on_tpu:
        jax.config.update("jax_platforms", "cpu")

    if on_tpu:
        # ~1.7 GB params (1.6 GB of it expert weights): big enough
        # that decode is weight-stream-bound like a real MoE, small
        # enough to share a 16 GiB chip with its KV cache.
        base = dict(vocab_size=32_000, d_model=1024, n_layers=args.layers,
                    n_heads=8, n_kv_heads=4, head_dim=128, d_ff=4096,
                    n_experts=8, top_k=2, dtype=jnp.bfloat16, remat=False)
        B, ctx, S_pre = args.slots, args.ctx, args.prefill_seq
        min_delta = 0.020
    else:
        base = dict(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                    n_kv_heads=2, head_dim=16, d_ff=128, n_experts=4,
                    top_k=2, dtype=jnp.float32, remat=False)
        B, ctx, S_pre = 4, 64, 32
        min_delta = 0.0

    rows = []

    def emit(row):
        rows.append(row)
        print(json.dumps(row), flush=True)

    def phase_breakdown(cfg, params, hook, cache, lengths, kv_tokens,
                        steps=2):
        """Measurement-mode per-phase table for one decode config: the
        instrumented eager forward (moe.forward phase_timer seam)
        drains the device queue at every phase boundary, then
        profiling.phase_roofline pairs the fractions with
        moe.decode_phase_bytes' per-phase byte floors. One warm pass
        (eager per-op compiles) before the timed steps."""
        pt = profiling.PhaseTimer()
        tok = jnp.zeros((int(lengths.shape[0]), 1), jnp.int32)
        for i in range(steps + 1):
            if i:
                pt.start()
            _, _aux, cache = moe.forward(
                params, tok, cfg, cache=cache, pos_offset=lengths,
                layers_hook=hook, phase_timer=pt if i else None)
        return profiling.phase_roofline(
            pt.snapshot(), moe.decode_phase_bytes(cfg, params,
                                                  kv_tokens),
            steps, generation, on_chip=on_tpu)

    psum_fp = None          # (cfg, params) reused by the paged family
    psum_q8 = None          # (cfg, qparams) for the fused-kernel row

    for routing, quantized in (("psum", False), ("dropless", False),
                               ("dropless", True), ("psum", True)):
        cfg = moe.MoEConfig(routing=routing, **base)
        params = moe.init_params(jax.random.PRNGKey(0), cfg)
        if routing == "psum" and not quantized:
            psum_fp = (cfg, params)
        hook = None
        if quantized:
            from tpushare.models import quant
            params = quant.quantize_params(params, cfg)
            hook = quant.dequant_hook(cfg)
            if routing == "psum":
                psum_q8 = (cfg, params)
        params_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
        cache = moe.init_cache(cfg, B, ctx)
        rng = np.random.default_rng(3)
        lengths_np = rng.integers(ctx // 2, ctx - 1, B)
        lengths = jnp.asarray(lengths_np, jnp.int32)

        # KV writes stay live by carrying the cache (dropping the
        # returned cache would let XLA dead-code the row updates);
        # lengths are a const so per-step work is constant, and the
        # token carry makes steps data-dependent (blocks CSE).
        def body(carry, params_, lengths_, cfg=cfg, hook=hook):
            tok, ck, cv = carry
            logits, _, ncache = moe.forward(
                params_, tok, cfg, cache={"k": ck, "v": cv},
                pos_offset=lengths_, layers_hook=hook)
            nxt = jnp.argmax(logits[:, 0], axis=-1)[:, None].astype(
                jnp.int32) % cfg.vocab_size
            return (nxt, ncache["k"], ncache["v"])

        tok0 = jnp.zeros((B, 1), jnp.int32)
        t, credible = profiling.time_step_chained(
            body, (tok0, cache["k"], cache["v"]), params, lengths,
            k_lo=2, k_hi=16, iters=3, min_credible_delta_s=min_delta)
        kv_row_bytes = 2 * cfg.n_kv_heads * cfg.head_dim * jnp.dtype(
            cfg.dtype).itemsize
        step_bytes = params_bytes + int(lengths_np.sum()) * (
            cfg.n_layers * kv_row_bytes)
        roofline_t = (step_bytes / profiling.HBM_BANDWIDTH[generation]
                      if on_tpu else None)
        util = (profiling.bandwidth_utilization(step_bytes, t, generation)
                if credible and on_tpu else None)
        emit({
            "metric": "moe_decode_tokens_per_sec",
            "routing": routing,
            "int8_experts": quantized,
            "value": round(B / t, 1) if credible else None,
            "unit": "tokens/s",
            "vs_baseline": 0,
            "backend": backend, "slots": B, "ctx": ctx,
            "n_experts": cfg.n_experts, "top_k": cfg.top_k,
            "params_mib": round(params_bytes / 2 ** 20, 1),
            "ms_per_step": round(1e3 * t, 2) if credible else None,
            "hbm_bytes_per_step_mib": round(step_bytes / 2 ** 20, 1),
            "roofline_tokens_per_sec": (round(B / roofline_t, 1)
                                        if roofline_t else None),
            "pct_of_roofline": (round(100 * util, 1)
                                if util is not None else None),
            "timing_credible": bool(credible),
            "scoreable": bool(credible and on_tpu),
            "phase_breakdown": phase_breakdown(
                cfg, params, hook, moe.init_cache(cfg, B, ctx),
                lengths, int(lengths_np.sum())),
        })

        if quantized:
            continue    # decode is where int8's bandwidth win lives

        # Prefill: T = B*S tokens/call — enough FLOPs that dense
        # dispatch's E/K-fold expert overcompute separates from
        # dropless' ideal count.
        def body_pre(carry, params_, cfg=cfg):
            tokens = carry
            logits, _ = moe.forward(params_, tokens, cfg,
                                    last_logit_only=True)
            nxt = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
            return (tokens + nxt[:, None]) % cfg.vocab_size

        toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S_pre)),
                           jnp.int32)
        t_pre, cred_pre = profiling.time_step_chained(
            body_pre, toks, params, k_lo=2, k_hi=8, iters=3,
            min_credible_delta_s=min_delta)
        emit({
            "metric": "moe_prefill_tokens_per_sec",
            "routing": routing,
            "value": round(B * S_pre / t_pre, 1) if cred_pre else None,
            "unit": "tokens/s",
            "vs_baseline": 0,
            "backend": backend, "batch": B, "seq": S_pre,
            "ms_per_step": round(1e3 * t_pre, 2) if cred_pre else None,
            "timing_credible": bool(cred_pre),
        })

    # Fused dequant×GEMM expert kernel (ops/q8_expert) vs the dequant
    # hook, same int8 psum tree both sides (ROADMAP item 3): the hook
    # rebuilds a full-width copy of every expert's weights inside the
    # scan body each step — int8 decode streaming int8 AND paying wide
    # write+reread is the measured 40.6%-of-roofline gap; the fused
    # path streams the experts once, as int8, dequantizing tiles in
    # VMEM inside the matmul. On chip the kernel dispatches for real
    # (d_model/d_ff are tile-aligned); on CPU the timing compares the
    # no-wide-copy reference path and the kernel logic itself is
    # proven via interpreter-mode parity on an eligible mini shape —
    # the row shape banks before a TPU run scores it.
    from tpushare.models import quant
    from tpushare.ops import q8_expert

    cfg, qparams = psum_q8
    qbytes = sum(x.nbytes for x in jax.tree.leaves(qparams))
    rng = np.random.default_rng(3)
    lengths_np = rng.integers(ctx // 2, ctx - 1, B)
    lengths = jnp.asarray(lengths_np, jnp.int32)
    hooks = {"dequant": quant.dequant_hook(cfg),
             "fused": quant.fused_expert_hook(cfg)}
    # Serving dispatch is kernel-OPT-IN until this very row banks on
    # chip (the repo's banked-evidence rule) — the bench is where the
    # evidence comes from, so ON CHIP it forces the kernel for the
    # fused timing unless the operator already pinned a policy. The
    # row records the mode the dispatch ACTUALLY chose.
    forced = False
    if on_tpu and not os.environ.get(q8_expert.Q8_EXPERT_KERNEL_ENV):
        os.environ[q8_expert.Q8_EXPERT_KERNEL_ENV] = "1"
        forced = True
    times = {}
    for name, hook in hooks.items():
        cache = moe.init_cache(cfg, B, ctx)

        def body(carry, params_, lengths_, cfg=cfg, hook=hook):
            tok, ck, cv = carry
            logits, _, ncache = moe.forward(
                params_, tok, cfg, cache={"k": ck, "v": cv},
                pos_offset=lengths_, layers_hook=hook)
            nxt = jnp.argmax(logits[:, 0], axis=-1)[:, None].astype(
                jnp.int32) % cfg.vocab_size
            return (nxt, ncache["k"], ncache["v"])

        tok0 = jnp.zeros((B, 1), jnp.int32)
        times[name] = profiling.time_step_chained(
            body, (tok0, cache["k"], cache["v"]), qparams, lengths,
            k_lo=2, k_hi=16, iters=3, min_credible_delta_s=min_delta)
    t_f, cred_f = times["fused"]
    t_d, cred_d = times["dequant"]
    credible = cred_f and cred_d
    kv_row_bytes = 2 * cfg.n_kv_heads * cfg.head_dim * jnp.dtype(
        cfg.dtype).itemsize
    step_bytes = qbytes + int(lengths_np.sum()) * (
        cfg.n_layers * kv_row_bytes)
    util = (profiling.bandwidth_utilization(step_bytes, t_f, generation)
            if credible and on_tpu else None)
    row = {
        "metric": "moe_q8_fused_decode_tokens_per_sec",
        "routing": "psum",
        "int8_experts": True,
        "expert_path": "fused",
        # The REAL dispatch decision (policy env + eligibility at the
        # decode token block), not a shape-only guess: an A/B run
        # with TPUSHARE_Q8_EXPERT_KERNEL=0 must bank "reference".
        "kernel_mode": q8_expert.q8_dispatch_mode(
            B, qparams["layers"]["w_gate#q8"][0], x_dtype=cfg.dtype),
        "value": round(B / t_f, 1) if credible else None,
        "unit": "tokens/s",
        "vs_baseline": 0,
        "backend": backend, "slots": B, "ctx": ctx,
        "params_mib": round(qbytes / 2 ** 20, 1),
        "ms_per_step": round(1e3 * t_f, 2) if credible else None,
        "dequant_hook_ms_per_step": (round(1e3 * t_d, 2)
                                     if credible else None),
        # > 1.0 = the fused path beats the materialized-wide-copy
        # path; the acceptance bar is pct_of_roofline >= 55 on chip.
        "vs_dequant_hook": (round(t_d / t_f, 3) if credible else None),
        "hbm_bytes_per_step_mib": round(step_bytes / 2 ** 20, 1),
        "pct_of_roofline": (round(100 * util, 1)
                            if util is not None else None),
        "timing_credible": bool(credible),
        "scoreable": bool(credible and on_tpu),
        "phase_breakdown": phase_breakdown(
            cfg, qparams, hooks["fused"], moe.init_cache(cfg, B, ctx),
            lengths, int(lengths_np.sum())),
        "phase_breakdown_dequant_hook": phase_breakdown(
            cfg, qparams, hooks["dequant"],
            moe.init_cache(cfg, B, ctx), lengths,
            int(lengths_np.sum())),
    }
    if not on_tpu:
        # CPU proof that the KERNEL (not just the fallback) computes
        # the expert FFN: interpreter-mode run on an eligible shape
        # vs the reference math, max |err| recorded in the row.
        rng_k = np.random.default_rng(7)
        E_k, Dm_k, F_k, C_k = 2, 128, 256, 8

        def _q(w, axis):
            s = jnp.maximum(jnp.max(jnp.abs(w), axis=axis,
                                    keepdims=True) / 127.0, 1e-12)
            return (jnp.clip(jnp.round(w / s), -127, 127)
                    .astype(jnp.int8), s)

        mk = lambda *s: jnp.asarray(rng_k.normal(size=s), jnp.float32)
        wgq, wgs = _q(mk(E_k, Dm_k, F_k), -2)
        wuq, wus = _q(mk(E_k, Dm_k, F_k), -2)
        wdq, wds = _q(mk(E_k, F_k, Dm_k), -2)
        x_k = mk(C_k, Dm_k)
        ker = q8_expert.q8_expert_ffn(x_k, wgq, wgs, wuq, wus, wdq,
                                      wds, act="silu", interpret=True)
        ref = q8_expert.q8_expert_ffn_reference(
            x_k, wgq, wgs, wuq, wus, wdq, wds, act="silu")
        row["interpreter_parity_max_err"] = float(
            jnp.max(jnp.abs(ker - ref)))
        row["kernel_mode"] = "interpreter-proof"
    if forced:
        del os.environ[q8_expert.Q8_EXPERT_KERNEL_ENV]
    emit(row)

    # Paged-KV family (the serving path): the SAME full-model
    # ragged decode step at equal batch/context, but KV lives in the
    # block pool and attention goes through the block table
    # (moe.forward's paged branch — pallas paged kernel on TPU, gathered
    # view elsewhere). The row records its ratio against the dense-row
    # psum row above: at decode batch both are weight-stream-bound, so
    # paged should ride the same roofline while buying block-granular
    # admission and prefix sharing.
    routing = "psum"                    # the measured best decode config
    cfg, params = psum_fp               # the dense loop's fp psum objects
    params_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    bs_pg = 128 if on_tpu else 16       # kernel-eligible on TPU
    mb = -(-ctx // bs_pg)
    n_blocks = B * mb + 1               # + trash block
    pool_shape = (cfg.n_layers, n_blocks, bs_pg, cfg.n_kv_heads,
                  cfg.head_dim)
    pool_k = jnp.zeros(pool_shape, cfg.dtype)
    pool_v = jnp.zeros(pool_shape, cfg.dtype)
    table = jnp.arange(B * mb, dtype=jnp.int32).reshape(B, mb)
    active = jnp.ones((B,), bool)
    rng = np.random.default_rng(3)
    lengths_np = rng.integers(ctx // 2, ctx - 1, B)
    lengths = jnp.asarray(lengths_np, jnp.int32)

    def body_paged(carry, params_, lengths_, cfg=cfg, table=table,
                   active=active):
        tok, pk, pv = carry
        cache = {"pool_k": pk, "pool_v": pv, "table": table,
                 "active": active}
        logits, _, ncache = moe.forward(params_, tok, cfg, cache=cache,
                                        pos_offset=lengths_)
        nxt = jnp.argmax(logits[:, 0], axis=-1)[:, None].astype(
            jnp.int32) % cfg.vocab_size
        return (nxt, ncache["pool_k"], ncache["pool_v"])

    tok0 = jnp.zeros((B, 1), jnp.int32)
    t, credible = profiling.time_step_chained(
        body_paged, (tok0, pool_k, pool_v), params, lengths,
        k_lo=2, k_hi=16, iters=3, min_credible_delta_s=min_delta)
    kv_row_bytes = 2 * cfg.n_kv_heads * cfg.head_dim * jnp.dtype(
        cfg.dtype).itemsize
    step_bytes = params_bytes + int(lengths_np.sum()) * (
        cfg.n_layers * kv_row_bytes)
    dense_row = next(
        (r for r in rows
         if r["metric"] == "moe_decode_tokens_per_sec"
         and r["routing"] == routing and not r["int8_experts"]),
        None)
    value = round(B / t, 1) if credible else None
    emit({
        "metric": "moe_paged_decode_tokens_per_sec",
        "routing": routing,
        "kv": "paged",
        "block_size": bs_pg,
        "value": value,
        "unit": "tokens/s",
        "vs_baseline": 0,
        "backend": backend, "slots": B, "ctx": ctx,
        "params_mib": round(params_bytes / 2 ** 20, 1),
        "ms_per_step": round(1e3 * t, 2) if credible else None,
        "hbm_bytes_per_step_mib": round(step_bytes / 2 ** 20, 1),
        # >= 1.0 means paged decode is no worse than the dense-row
        # MoE path at equal batch/context (the acceptance bar).
        "vs_dense_rows": (
            round(value / dense_row["value"], 3)
            if value and dense_row and dense_row["value"] else None),
        "timing_credible": bool(credible),
        "scoreable": bool(credible and on_tpu),
        "phase_breakdown": phase_breakdown(
            cfg, params, None,
            {"pool_k": pool_k, "pool_v": pool_v, "table": table,
             "active": active},
            lengths, int(lengths_np.sum())),
    })

    # Rows go to stdout only, each naming its backend.
    return 0


if __name__ == "__main__":
    sys.exit(main())
