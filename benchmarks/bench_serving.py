"""Serving-level paged decode throughput: bf16 vs int8 KV pools.

Times ONE full-model paged decode step (models/paged.decode_core — the
exact jitted function PagedSlotServer.step dispatches) at serving
shapes, with the chained scan-differenced methodology
(profiling.time_step_chained docstring) so host dispatch cancels out
of the number. Prints one JSON row per pool mode with
model-level decode tokens/sec and the per-slot KV bytes — the
capacity-vs-speed tradeoff kv_quant serves.

Usage: python benchmarks/bench_serving.py [--preset gemma_2b]
       [--slots 8] [--ctx 8192] [--block-size 128]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time as _time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    # The sharded_decode row needs >= 2 host devices on CPU; forcing
    # them must happen BEFORE jax initializes (a TPU backend is
    # unaffected — the flag applies to the host platform only).
    if ("jax" not in sys.modules
            and "xla_force_host_platform_device_count"
            not in os.environ.get("XLA_FLAGS", "")):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4").strip()
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="auto",
                    choices=["auto", "tiny", "gemma_2b"])
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--ctx", type=int, default=8192)
    ap.add_argument("--block-size", type=int, default=128)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import bench_backend
    from tpushare.models import paged
    from tpushare.models import transformer as tf
    from tpushare.models.quant import kv_quantize
    from tpushare.utils import profiling

    backend, generation = bench_backend()
    on_tpu = backend != "cpu"
    if not on_tpu:
        jax.config.update("jax_platforms", "cpu")
    preset = args.preset
    if preset == "auto":
        preset = "gemma_2b" if on_tpu else "tiny"
    cfg = {"tiny": tf.tiny, "gemma_2b": tf.gemma_2b}[preset]()
    B = args.slots
    bs = args.block_size if on_tpu else 8
    ctx = args.ctx if on_tpu else 64
    mb = ctx // bs
    nb = B * mb + 1
    L, Hkv, Dh = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim

    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    params_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    kv_row_bytes_bf16 = 2 * Hkv * Dh * jnp.dtype(cfg.dtype).itemsize
    kv_row_bytes_int8 = 2 * Hkv * (Dh * 1 + 4)      # int8 row + f32 scale

    def run_mode(kvq: bool, n_slots: int, label: str):
        """One timed decode configuration -> (agg tokens/s or None, row)."""
        mb_ = mb
        nb_ = n_slots * mb_ + 1
        table = jnp.asarray(
            (1 + np.arange(n_slots)[:, None] * mb_ + np.arange(mb_)[None, :]
             ).astype(np.int32))
        # Slots at ~3/4 fill: decode reads a realistic mix of pages.
        lengths_np = np.random.default_rng(2).integers(
            ctx // 2, ctx - 1, n_slots)
        lengths = jnp.asarray(lengths_np, jnp.int32)
        active = jnp.ones((n_slots,), bool)
        pool_f = jax.random.normal(jax.random.PRNGKey(1),
                                   (L, nb_, bs, Hkv, Dh),
                                   jnp.float32) * 0.05
        if kvq:
            from tpushare.models.quant import scales_to_pool_layout
            pk, pks = kv_quantize(pool_f)
            pks = scales_to_pool_layout(pks)   # kernel page layout
            pv, pvs = pk, pks          # same stats; bytes are the story
        else:
            pk = pool_f.astype(cfg.dtype)
            pv, pks, pvs = pk, None, None
        del pool_f
        # the stored page holds its kv heads merged (paged.PagedCache)
        pk = pv = pk.reshape(L, nb_, bs, Hkv * Dh)

        # params ride as a const ARGUMENT: closure capture bakes the
        # 5 GB tree into the lowered module as constants and the
        # compile never finishes (profiling.time_step_chained).
        def body(tok, params_, pk_, pv_, pks_=None, pvs_=None):
            out = paged.decode_core(
                params_, tok, pk_, pv_, table, lengths, active,
                cfg=cfg, block_size=bs,
                **({"pool_k_scale": pks_, "pool_v_scale": pvs_}
                   if kvq else {}))
            logits = out[0]
            # Data-dependent carry: next token from this step's logits.
            return jnp.argmax(logits[:, 0], axis=-1)[:, None].astype(
                jnp.int32) % cfg.vocab_size

        tok0 = jnp.zeros((n_slots, 1), jnp.int32)
        consts = (params, pk, pv) + ((pks, pvs) if kvq else ())
        t, credible = profiling.time_step_chained(
            body, tok0, *consts, k_lo=2, k_hi=16, iters=3,
            min_credible_delta_s=0.020 if on_tpu else 0.0)
        kv_bytes = sum(x.nbytes for x in (pk, pv)
                       ) + (pks.nbytes + pvs.nbytes if kvq else 0)
        # Bandwidth roofline (VERDICT r3 #5): bytes that MUST stream
        # from HBM per step — the full weight tree once (decode is
        # weight-stream-bound at small batch) + every live KV row.
        kv_row = kv_row_bytes_int8 if kvq else kv_row_bytes_bf16
        step_bytes = params_bytes + int(lengths_np.sum()) * L * kv_row
        roofline_t = (step_bytes / profiling.HBM_BANDWIDTH[generation]
                      if on_tpu else None)
        util = (profiling.bandwidth_utilization(
            step_bytes, t, generation) if credible and on_tpu else None)
        row = {
            "metric": f"{preset}_paged_decode_tokens_per_sec",
            "mode": label,
            "kv_quant": kvq,
            "value": round(n_slots / t, 1) if credible else None,
            "unit": "tokens/s",
            "vs_baseline": 0,
            "backend": backend, "slots": n_slots, "ctx": ctx,
            "block_size": bs,
            "ms_per_step": round(1e3 * t, 2) if credible else None,
            "kv_pool_mib": round(kv_bytes / 2 ** 20, 1),
            "hbm_bytes_per_step_mib": round(step_bytes / 2 ** 20, 1),
            "roofline_tokens_per_sec": (round(n_slots / roofline_t, 1)
                                        if roofline_t else None),
            "pct_of_roofline": (round(100 * util, 1)
                                if util is not None else None),
            "timing_credible": bool(credible),
        }
        return (n_slots / t if credible else None), row

    bf16_tps, row = run_mode(False, B, "bf16")
    print(json.dumps(row), flush=True)
    _, row = run_mode(True, B, "int8_parity")
    print(json.dumps(row), flush=True)
    # The capacity conversion int8 exists for (VERDICT r3 #5): the
    # halved KV bytes become 2x the concurrent slots in the SAME HBM
    # grant — the aggregate-throughput win, not just byte parity.
    cap_tps, row = run_mode(True, 2 * B, "int8_capacity_2x_slots")
    if bf16_tps and cap_tps:
        row["capacity_win_vs_bf16"] = round(cap_tps / bf16_tps, 3)
    print(json.dumps(row), flush=True)

    # Quantized self-speculation: the draft is the TARGET's own int8
    # rounding (acceptance near 100%) at half the draft weight stream.
    # Both rows run the same host-driven PagedSlotServer loop, so the
    # ratio is apples-to-apples; accept_rate reports emitted tokens
    # per round over the gamma+1 ceiling.
    from tpushare.models import quant
    from tpushare.models.paged import PagedSlotServer

    from specloop import PHASE_ROUNDS, run_serving_loop, spec_row_fields

    gamma = 3
    rounds = 16

    def make_prompts(n, plen):
        return [jnp.asarray(r, jnp.int32) for r in
                np.random.default_rng(5).integers(
                    0, cfg.vocab_size, (n, plen))]

    qdraft = quant.quantize_params(params, cfg)   # once for all rows

    def run_loop(spec: bool, prompts, g=None, horizon=1, timer=None):
        g = gamma if g is None else g
        # Worst-case emission at full acceptance is gamma*K+1 tokens
        # per round INCLUDING the untimed warm-up step (+1) and the
        # untimed phase-breakdown pass (PHASE_ROUNDS).
        need = len(prompts[0]) \
            + (g * horizon + 1) * (rounds + 1 + PHASE_ROUNDS)
        blocks_per_slot = -(-need // bs) + 1
        kw = dict(n_slots=len(prompts),
                  n_blocks=len(prompts) * max(16, blocks_per_slot) + 1,
                  block_size=bs)
        if spec:
            kw.update(speculative_draft=(qdraft, cfg),
                      gamma=g, spec_horizon=horizon,
                      draft_layers_hook=quant.dequant_hook(cfg))
        return run_serving_loop(
            lambda: PagedSlotServer(params, cfg, **kw), prompts,
            rounds, phase_timer=timer)

    # plen -> (prompts, plain tok/s): the plain baseline is identical
    # for every speculative row at the same prompts, so spec_row and
    # the horizon sweep share one measurement per prompt length
    # (on chip each redundant baseline is a server build + compile +
    # `rounds` timed steps).
    plain_baselines = {}

    def plain_baseline(plen: int):
        if plen not in plain_baselines:
            prompts = make_prompts(min(B, 4), plen)
            tps, _, _ = run_loop(False, prompts)
            plain_baselines[plen] = (prompts, tps)
        return plain_baselines[plen]

    def spec_row(mode: str, plen: int):
        prompts, plain_tps = plain_baseline(plen)
        spec_tps, per_round, extras = run_loop(True, prompts)
        print(json.dumps(dict({
            "metric": f"{preset}_spec_decode_tokens_per_sec",
            "mode": mode,
            "backend": backend, "slots": len(prompts),
            "prompt_tokens": plen, "block_size": bs,
        }, **spec_row_fields(spec_tps, plain_tps, per_round, gamma,
                             extras=extras))),
            flush=True)

    spec_row("int8_self_draft", 48)
    if on_tpu:
        # Production-shaped: the draft pays real paged attention over a
        # 1k prefix each proposal, so this row is the honest speculation
        # value at serving context (the 48-token row is a smoke).
        spec_row("int8_self_draft_1k_prompt", 1024)

    # Multi-token draft horizon sweep (ISSUE 11): the unified seam's
    # longer-horizon mode at k in {1, 2, 4}, per family (paged dense
    # LM + MoE dense rows), int8-self draft. The acceptance-weighted
    # win the sweep measures: one target verify weight-stream per
    # round, so target_forwards_per_token = 1/mean-emitted — at high
    # accept rates a longer block buys a near-proportional reduction,
    # while a collapsing accept_rate says the draft can't carry that
    # horizon. The per-phase draft/verify/accept-fold breakdown
    # (profiling.PhaseTimer on the seam's timer slot) localizes where
    # the round's wall-clock goes; off-chip rows are methodology
    # smoke, not scoreable numbers.
    from tpushare.models import moe as _moe
    from tpushare.utils.profiling import PhaseTimer

    SWEEP_KS = (1, 2, 4)

    def emit_sweep_row(family, plen, k, tps, plain_tps, per_round,
                       extras):
        print(json.dumps(dict({
            "metric": "spec_horizon_sweep",
            "family": family, "mode": "int8_self_draft",
            "backend": backend,
            # The fused-tick precedent: CPU wall-clock of a
            # bandwidth-bound tradeoff proves mechanics, not value.
            "scoreable": on_tpu,
            "slots": min(B, 4), "prompt_tokens": plen,
        }, **spec_row_fields(tps, plain_tps, per_round, gamma,
                             horizon=k, extras=extras))),
            flush=True)

    def horizon_sweep_paged(plen: int):
        # The k loop varies only the SPECULATIVE side: ONE plain
        # baseline per (family, plen), shared with spec_row's —
        # re-timing an identical baseline per k (or per row) would
        # pay extra server builds + compiles + timed runs for
        # numbers that can't differ.
        prompts, plain_tps = plain_baseline(plen)
        for k in SWEEP_KS:
            timer = PhaseTimer()
            tps, per_round, extras = run_loop(
                True, prompts, g=gamma, horizon=k, timer=timer)
            emit_sweep_row("paged_dense", plen, k, tps, plain_tps,
                           per_round, extras)

    def horizon_sweep_moe(plen: int):
        mcfg = _moe.tiny(remat=False)
        mparams = _moe.init_params(jax.random.PRNGKey(0), mcfg)
        mq = quant.quantize_params(mparams, mcfg)
        mprompts = [jnp.asarray(r, jnp.int32) for r in
                    np.random.default_rng(6).integers(
                        0, mcfg.vocab_size, (min(B, 4), plen))]
        # One max_len sized for the LARGEST horizon keeps every row
        # (and the shared plain baseline) on the same cache shape.
        need = plen + (gamma * max(SWEEP_KS) + 1) \
            * (rounds + 2 + PHASE_ROUNDS)
        mlen = 1 << (need - 1).bit_length()

        def mk(k):
            kw = dict(n_slots=len(mprompts), max_len=mlen)
            if k:
                kw.update(
                    speculative_draft=(mq, mcfg), gamma=gamma,
                    spec_horizon=k,
                    draft_layers_hook=quant.dequant_hook(mcfg))
            return lambda: _moe.MoESlotServer(mparams, mcfg, **kw)

        plain_tps, _, _ = run_serving_loop(mk(0), mprompts, rounds)
        for k in SWEEP_KS:
            timer = PhaseTimer()
            tps, per_round, extras = run_serving_loop(
                mk(k), mprompts, rounds, phase_timer=timer)
            emit_sweep_row("moe_rows", plen, k, tps, plain_tps,
                           per_round, extras)

    sweep_plen = 48 if on_tpu else 16
    horizon_sweep_paged(sweep_plen)
    horizon_sweep_moe(sweep_plen)

    # Chunked prefill (VERDICT r4 #4): the persistent admission row
    # removed the per-chunk prefix re-gather, so total admit time
    # should stay ~flat as the chunk shrinks (the old path paid
    # ~S^2/(2*chunk) extra gathered KV-row HBM traffic — at S=2048 and
    # chunk=S/8 that was ~7 extra full-prompt KV copies). Each config
    # warms once (compiles per chunk index) then times one fresh
    # admission.
    S_admit = 2048 if on_tpu else 96
    admit_prompt = jnp.asarray(np.random.default_rng(7).integers(
        0, cfg.vocab_size, S_admit), jnp.int32)

    def time_admit(chunk):
        srv = PagedSlotServer(params, cfg, n_slots=1,
                              n_blocks=S_admit // bs + 4, block_size=bs)

        def run():
            slot = srv.admit_start(admit_prompt, chunk_tokens=chunk)
            while srv.admit_step(slot) is None:
                pass
            jax.block_until_ready(srv.cache.pool_k)
            srv.evict(slot)

        run()                                  # compile + warm
        t0 = _time.perf_counter()
        run()
        return _time.perf_counter() - t0

    whole = time_admit(None)
    for chunk in (S_admit // 8, S_admit // 4):
        dt = time_admit(chunk)
        print(json.dumps({
            "metric": f"{preset}_chunked_admit_tokens_per_sec",
            "chunk_tokens": chunk, "prompt_tokens": S_admit,
            "value": round(S_admit / dt, 1), "unit": "tokens/s",
            "vs_baseline": 0,
            "whole_admit_tokens_per_sec": round(S_admit / whole, 1),
            "chunked_vs_whole": round(whole / dt, 3),
            "backend": backend, "block_size": bs,
        }), flush=True)

    # Fused admission under load (r6 tentpole): decode tokens/sec for
    # N active slots WHILE a long prompt chunk-admits. Serial pays two
    # weight streams per tick (one standalone chunk forward + one
    # decode forward — VERDICT r5 #7's measured 0.49x at chunk=256 was
    # exactly this); the fused tick folds the chunk into the decode
    # batch's forward (srv.step(prefill_work=...)), one stream.
    n_load = min(B, 4)
    chunk_f = max(bs, (S_admit // 8 // bs) * bs)

    def admission_under_load(fused: bool):
        need = S_admit // bs + 4 + n_load * 16
        srv = PagedSlotServer(params, cfg, n_slots=n_load + 1,
                              n_blocks=need + 1, block_size=bs)
        for p in make_prompts(n_load, 24):
            srv.admit(p)

        def run():
            slot = srv.admit_start(admit_prompt, chunk_tokens=chunk_f)
            decode_toks = ticks = 0
            while True:
                ticks += 1
                if fused:
                    out = srv.step(prefill_work=slot)
                    done = slot in out
                    decode_toks += len(out) - (1 if done else 0)
                else:
                    done = srv.admit_step(slot) is not None
                    decode_toks += len(srv.step())
                if done:
                    break
            jax.block_until_ready(srv.cache.pool_k)
            srv.evict(slot)
            return decode_toks, ticks

        run()                              # compile + warm
        t0 = _time.perf_counter()
        decode_toks, ticks = run()
        dt = _time.perf_counter() - t0
        return decode_toks / dt, ticks

    serial_tps, serial_ticks = admission_under_load(False)
    fused_tps, fused_ticks = admission_under_load(True)
    print(json.dumps({
        "metric": f"{preset}_admission_under_load_decode_tokens_per_sec",
        "mode": "fused_vs_serial",
        "value": round(fused_tps, 1), "unit": "tokens/s",
        "vs_baseline": 0,
        "serial_decode_tokens_per_sec": round(serial_tps, 1),
        "fused_vs_serial": round(fused_tps / serial_tps, 3)
        if serial_tps else None,
        "active_slots": n_load, "prompt_tokens": S_admit,
        "chunk_tokens": chunk_f,
        # Target-weight-stream forwards per tick while admitting: the
        # serial loop pays 2, the fused tick exactly 1 (the /stats
        # forwards_per_tick counter reports the same invariant live).
        "forwards_per_tick": {"serial": 2.0, "fused": 1.0},
        "ticks": {"serial": serial_ticks, "fused": fused_ticks},
        "backend": backend, "block_size": bs,
        # The fused win is the REMOVED second weight stream — a
        # bandwidth-bound (on-chip) effect. A compute-bound CPU run
        # instead pays for the decode rows' padded junk columns, so
        # only the on-TPU number scores the >= serial acceptance bar.
        "scoreable": bool(on_tpu),
    }), flush=True)

    # Sharded decode (ISSUE 7): the SAME slot-server decode loop on a
    # NamedSharding mesh (weights per param_specs, KV pools split on
    # the kv-head axis) vs the single-chip server — dense tp=2 and
    # paged ep x tp MoE. The sharded win is ICI/HBM-bandwidth-bound
    # (each chip streams 1/tp of the weights and pools per tick), so
    # CPU forced-host-device runs prove plumbing, not speed:
    # scoreable only on chip. forwards_per_tick is counted from the
    # actual jitted dispatches — sharding must not add forwards.
    from tpushare.models import moe
    from tpushare.models.serving import mesh_axes
    from tpushare.parallel import make_mesh

    # NOTE: the axes param must not be named mesh_axes — it would
    # shadow the imported serving.mesh_axes the row formatter calls
    # (that exact shadowing shipped once and made every sharded row
    # die with "'dict' object is not callable").
    def sharded_row(label, mk, axes, n_mesh, vocab):
        if len(jax.devices()) < n_mesh:
            return
        mesh = make_mesh(axes, devices=jax.devices()[:n_mesh])

        def decode_tps(srv, rounds=16):
            calls = [0]
            orig = srv._decode

            def spy(*a, **kw):
                calls[0] += 1
                return orig(*a, **kw)

            srv._decode = spy
            prompts = [jnp.asarray(r, jnp.int32) for r in
                       np.random.default_rng(6).integers(
                           0, vocab, (min(B, 4), 24))]
            for p in prompts:
                srv.admit(p)
            srv.step()                         # compile + warm
            calls[0] = 0
            t0 = _time.perf_counter()
            toks = 0
            for _ in range(rounds):
                toks += len(srv.step())
            jax.block_until_ready(srv.cache.pool_k)
            dt = _time.perf_counter() - t0
            return toks / dt, calls[0] / rounds

        single_tps, single_fpt = decode_tps(mk(None))
        shard_tps, shard_fpt = decode_tps(mk(mesh))
        print(json.dumps({
            "metric": f"{preset}_sharded_decode_tokens_per_sec",
            "mode": label,
            "value": round(shard_tps, 1), "unit": "tokens/s",
            "vs_baseline": 0,
            "single_chip_tokens_per_sec": round(single_tps, 1),
            "sharded_vs_single_chip": (round(shard_tps / single_tps, 3)
                                       if single_tps else None),
            "mesh": mesh_axes(mesh),
            "num_devices": mesh.size,
            "forwards_per_tick": {"single_chip": single_fpt,
                                  "sharded": shard_fpt},
            "slots": min(B, 4), "block_size": bs,
            "backend": backend,
            # The win is interconnect/bandwidth-bound; a forced-host-
            # device CPU run pays SPMD partition overhead with zero
            # bandwidth gain, so only the on-chip ratio scores.
            "scoreable": bool(on_tpu),
        }), flush=True)

    sharded_row(
        "tp2_dense_paged",
        lambda mesh: PagedSlotServer(
            params, cfg, n_slots=min(B, 4) + 1,
            n_blocks=min(B, 4) * 24 + 1, block_size=bs, mesh=mesh),
        {"tp": 2}, 2, cfg.vocab_size)
    moe_cfg = moe.tiny(remat=False)
    moe_params = moe.init_params(jax.random.PRNGKey(3), moe_cfg)
    sharded_row(
        "eptp2x2_paged_moe_tiny",
        lambda mesh: PagedSlotServer(
            moe_params, moe_cfg, n_slots=min(B, 4) + 1,
            n_blocks=min(B, 4) * 24 + 1, block_size=bs,
            forward_fn=moe.paged_forward, mesh=mesh),
        {"tp": 2, "ep": 2}, 4, moe_cfg.vocab_size)

    # Decode under faults (ISSUE 4): the steady-state cost of the
    # failure-domain recovery machinery. Same engine, same requests;
    # the faulted row injects forward:raise@p=0.01 (a seeded
    # XlaRuntimeError-shaped fault roughly once per hundred ticks) and
    # pays for it in quarantine evictions + token-exact replay
    # re-prefills. The ratio IS the price of reliability at that fault
    # rate; replay/quarantine counts ride in the record so a regression
    # in recovery cost is attributable.
    from tpushare.cli.serve import ServeEngine, _Request

    n_f = min(B, 4)

    def decode_under_faults(spec):
        eng = ServeEngine(params, cfg, n_slots=n_f,
                          n_blocks=n_f * 24 + 1, block_size=bs,
                          idle_sleep_s=0.0005, chaos_spec=spec,
                          max_replays=64)
        prompts = make_prompts(n_f, 24)

        def run():
            reqs = [_Request([int(t) for t in p], 24, None)
                    for p in prompts]
            for r in reqs:
                if not eng.submit(r):       # plain call: -O strips
                    raise RuntimeError("queue refused a bench request")
            while not all(r.done.is_set() for r in reqs):
                eng._loop_once()
            if any(r.error is not None for r in reqs):
                raise RuntimeError(
                    "fault-storm request failed inside the bench")
            return sum(len(r.tokens) for r in reqs)

        run()                                  # compile + warm
        t0 = _time.perf_counter()
        toks = run()
        dt = _time.perf_counter() - t0
        return toks / dt, eng.stats()

    clean_tps, _ = decode_under_faults("")
    # The scoreable (TPU) row runs the issue's p=0.01; the CPU smoke
    # runs too few ticks for p=0.01 to ever fire (an injected-nothing
    # row proves nothing), so it densifies the storm instead —
    # scoreable stays false there regardless.
    fault_p = 0.01 if on_tpu else 0.1
    fault_spec = f"forward:raise@p={fault_p};seed=11"
    fault_tps, fstats = decode_under_faults(fault_spec)
    print(json.dumps({
        "metric": f"{preset}_decode_under_faults_tokens_per_sec",
        "mode": f"forward_raise_p{fault_p:g}",
        "value": round(fault_tps, 1), "unit": "tokens/s",
        "vs_baseline": 0,
        "clean_decode_tokens_per_sec": round(clean_tps, 1),
        "faulted_vs_clean": (round(fault_tps / clean_tps, 3)
                             if clean_tps else None),
        "chaos_spec": fault_spec,
        "replays": fstats["replays"],
        "quarantines": fstats["quarantines"],
        "engine_errors": fstats["engine_errors"],
        "slots": n_f, "max_tokens": 24,
        "backend": backend, "block_size": bs,
        # CPU runs are compute-bound and re-prefill cost dominates
        # differently than on-chip; only the TPU ratio scores.
        "scoreable": bool(on_tpu),
    }), flush=True)

    # SLO tiers (ISSUE 9): the latency/batch-size tradeoff the tier
    # scheduler navigates (the curve of PAPERS.md 1812.11731). The
    # SAME mixed storm — batch saturating the slots, interactive
    # landing on the full pool — runs tiered (priority admission,
    # preempt-low-for-high, deadline-aware ticks) and as a no-tiers
    # FIFO baseline (every request one tier), and the row records the
    # interactive tier's p99 TTFT + per-token latency under each:
    # the protection ratio IS the tiering win, legitimate only while
    # batch throughput stays > 0 (protection must not starve the
    # throughput tier). A second tiered run at half the batch load
    # emits the tradeoff curve points (batch rows vs latency).
    from tpushare.slo.stats import _pct

    n_slo = min(B, 4)

    slo_eng = ServeEngine(params, cfg, n_slots=n_slo,
                          n_blocks=n_slo * 24 + 1, block_size=bs,
                          idle_sleep_s=0.0005)
    slo_eng.start()

    def slo_storm(tiered: bool, n_batch: int, n_inter: int = 3):
        """One storm on the shared engine; returns per-class latency
        off the request objects themselves (wall clock, this pass
        only — engine counter rings span every pass)."""
        rng_s = np.random.default_rng(13)

        def mk(tier, plen, mt):
            r = _Request([int(t) for t in rng_s.integers(
                0, cfg.vocab_size, plen)], mt, None,
                tier=tier if tiered else "standard")
            if not slo_eng.submit(r):   # plain call: -O strips asserts
                raise RuntimeError("queue refused a bench request")
            return r
        t0 = _time.perf_counter()
        batch_rs = [mk("batch", 12, 32) for _ in range(n_batch)]
        want_active = min(n_batch, n_slo)
        while (slo_eng.active_count() < want_active
               and _time.perf_counter() - t0 < 60):
            _time.sleep(0.001)
        inter_rs = [mk("interactive", 8, 6) for _ in range(n_inter)]
        hung = sum(1 for r in inter_rs + batch_rs
                   if not r.done.wait(180))
        dt = _time.perf_counter() - t0
        if hung:
            raise RuntimeError(f"slo-storm: {hung} request(s) hung "
                               f"past 180s (engine wedged?)")
        if any(r.error is not None for r in inter_rs + batch_rs):
            raise RuntimeError("slo-storm request failed in the bench")

        def lat(rs):
            ttft = [(r.t_first - r.t_submit) * 1e3 for r in rs]
            per_tok = [(r.t_last - r.t_first) * 1e3 / (len(r.tokens) - 1)
                       for r in rs if len(r.tokens) > 1]
            return {"ttft_p99_ms": _pct(ttft, 0.99),
                    "per_token_p50_ms": _pct(per_tok, 0.50),
                    "per_token_p99_ms": _pct(per_tok, 0.99)}
        return {
            "interactive": lat(inter_rs), "batch": lat(batch_rs),
            "batch_tokens_per_sec": round(
                sum(len(r.tokens) for r in batch_rs) / dt, 1),
        }

    n_batch_full = n_slo + 2
    slo_storm(True, n_batch_full)          # compile + warm (ungraded)
    tiered = slo_storm(True, n_batch_full)
    half = slo_storm(True, max(1, n_batch_full // 2))
    fifo = slo_storm(False, n_batch_full)
    pre = slo_eng.stats()["preempted"]
    slo_eng.stop()
    t_ttft = tiered["interactive"]["ttft_p99_ms"]
    f_ttft = fifo["interactive"]["ttft_p99_ms"]
    print(json.dumps({
        "metric": f"{preset}_slo_tiers_interactive_p99_ttft_ms",
        "mode": "tiered_vs_fifo",
        "value": t_ttft, "unit": "ms",
        "vs_baseline": 0,
        "fifo_interactive_p99_ttft_ms": f_ttft,
        "ttft_protection_x": (round(f_ttft / t_ttft, 3)
                              if t_ttft else None),
        "interactive_per_token_p99_ms":
            tiered["interactive"]["per_token_p99_ms"],
        "fifo_interactive_per_token_p99_ms":
            fifo["interactive"]["per_token_p99_ms"],
        "batch_tokens_per_sec": tiered["batch_tokens_per_sec"],
        "fifo_batch_tokens_per_sec": fifo["batch_tokens_per_sec"],
        "preemptions": pre,
        # (batch rows, latency) tradeoff points per tier: the knob
        # the tier weights walk — more batch rows buy throughput at
        # the latency tiers' expense.
        "curve": [
            {"batch_rows": max(1, n_batch_full // 2),
             "interactive": half["interactive"], "batch": half["batch"]},
            {"batch_rows": n_batch_full,
             "interactive": tiered["interactive"],
             "batch": tiered["batch"]},
        ],
        "slots": n_slo, "backend": backend, "block_size": bs,
        # Wall-clock latency under host-driven CPU ticks measures the
        # policy's ORDERING, not chip latency; only on-TPU numbers
        # score the protection bar.
        "scoreable": bool(on_tpu),
    }), flush=True)

    # Overlapped tick pipeline (ISSUE 17): the same saturated decode
    # storm — every slot occupied, journal at its strongest policy
    # (--journal-fsync tick) — runs with the pipeline on and off, and
    # the row records the stream-visible win: inter-token gap p50/p99
    # stamped at each request's own push(), plus the engine's
    # host_gap_ms (the host scheduling time the overlap hides behind
    # the in-flight dispatch). On CPU the "device window" is host
    # compute too, so the gap delta measures machinery, not the chip
    # overlap — scoreable only on TPU.
    import tempfile

    def overlapped_storm(overlap: bool):
        eng = ServeEngine(
            params, cfg, n_slots=n_slo, n_blocks=n_slo * 24 + 1,
            block_size=bs, idle_sleep_s=0.0,
            journal_dir=tempfile.mkdtemp(prefix="tpushare-bench-j"),
            journal_fsync="tick", overlap_tick=overlap)
        eng.start()
        rng_o = np.random.default_rng(17)

        def timed_request(plen, mt):
            r = _Request([int(t) for t in rng_o.integers(
                0, cfg.vocab_size, plen)], mt, None)
            ts = []
            orig = r.push

            def push(tok, _orig=orig, _ts=ts):
                _ts.append(_time.perf_counter())
                _orig(tok)
            r.push = push
            if not eng.submit(r):
                raise RuntimeError("queue refused a bench request")
            return r, ts
        warm, _ = timed_request(8, 4)           # compile (ungraded)
        if not warm.done.wait(180):
            raise RuntimeError("overlap bench warm request hung")
        pairs = [timed_request(8, 48) for _ in range(n_slo)]
        hung = sum(1 for r, _ in pairs if not r.done.wait(180))
        if hung or any(r.error is not None for r, _ in pairs):
            raise RuntimeError("overlap bench request failed/hung")
        gaps = [g for _, ts in pairs
                for g in (np.diff(ts) * 1e3).tolist()]
        st = eng.stats()
        eng.stop()
        return {"gap_p50_ms": _pct(gaps, 0.50),
                "gap_p99_ms": _pct(gaps, 0.99),
                "fetches_per_tick": st["fetches_per_tick"],
                "host_gap_ms": st["host_gap_ms"],
                "pipeline_flushes": st["pipeline_flushes"]}

    ov_on = overlapped_storm(True)
    ov_off = overlapped_storm(False)
    print(json.dumps({
        "metric": f"{preset}_overlapped_tick_inter_token_gap_ms",
        "mode": "overlap_on_vs_off",
        "value": ov_on["gap_p50_ms"], "unit": "ms",
        "vs_baseline": 0,
        "p99_ms": ov_on["gap_p99_ms"],
        "serial_p50_ms": ov_off["gap_p50_ms"],
        "serial_p99_ms": ov_off["gap_p99_ms"],
        "host_gap_ms": ov_on["host_gap_ms"],
        "pipeline_flushes": ov_on["pipeline_flushes"],
        "fetches_per_tick": ov_on["fetches_per_tick"],
        "serial_fetches_per_tick": ov_off["fetches_per_tick"],
        "journal_fsync": "tick",
        "slots": n_slo, "backend": backend, "block_size": bs,
        "scoreable": bool(on_tpu),
    }), flush=True)

    # Routed storm (ISSUE 8): the front door's prefix-affinity lift.
    # The SAME mixed-prefix trace (groups sharing a block-aligned
    # prompt prefix) runs through a 2-replica fleet twice — once under
    # affinity routing (chain-key match -> the block holder), once
    # under seeded random routing — and the row records the summed
    # replica-side prefix_hit_tokens of each. The lift is the routing
    # win: hits the random policy forfeits by scattering a prefix
    # group across replicas that then each re-prefill it.
    import http.client as _http_client

    from tpushare.cli.serve import serve as serve_engine
    from tpushare.router import Router
    from tpushare.router.daemon import serve_router

    groups, per_group, prefix_blocks = 3, 4, 2
    rng_rt = np.random.default_rng(9)
    trace = []
    for _ in range(groups):
        prefix = [int(t) for t in rng_rt.integers(
            0, cfg.vocab_size, prefix_blocks * bs)]
        for _ in range(per_group):
            trace.append(prefix + [int(t) for t in rng_rt.integers(
                0, cfg.vocab_size, 4)])

    def routed_trace(policy):
        fleet = []
        for _ in range(2):
            eng = ServeEngine(params, cfg, n_slots=4,
                              n_blocks=len(trace) * 8 + 1,
                              block_size=bs, idle_sleep_s=0.0005)
            httpd = serve_engine(eng, host="127.0.0.1", port=0)
            fleet.append((eng, httpd))
        urls = [f"http://127.0.0.1:{h.server_address[1]}"
                for _, h in fleet]
        router = Router(urls, policy=policy, poll_interval_s=0.1,
                        seed=3)
        rhttpd = serve_router(router, "127.0.0.1", 0)
        rport = rhttpd.server_address[1]
        router.poll_once()              # learn block sizes pre-trace
        t0 = _time.perf_counter()
        try:
            for p in trace:
                conn = _http_client.HTTPConnection("127.0.0.1", rport,
                                                   timeout=120)
                conn.request("POST", "/v1/completions",
                             json.dumps({"prompt": p,
                                         "max_tokens": 4}).encode(),
                             {"Content-Type": "application/json"})
                resp = conn.getresponse()
                ok = resp.status == 200
                resp.read()
                conn.close()
                if not ok:              # plain raise: -O strips asserts
                    raise RuntimeError("routed bench request failed")
            dt = _time.perf_counter() - t0
            hits = sum(eng.stats()["prefix_hit_tokens"]
                       for eng, _ in fleet)
            return hits, dt
        finally:
            rhttpd.shutdown()
            router.stop()
            for eng, httpd in fleet:
                httpd.shutdown()
                eng.stop()

    affinity_hits, affinity_dt = routed_trace("affinity")
    random_hits, random_dt = routed_trace("random")
    print(json.dumps({
        "metric": f"{preset}_routed_storm_prefix_hit_lift",
        "mode": "affinity_vs_random",
        "value": (round(affinity_hits / random_hits, 3)
                  if random_hits else None),
        "unit": "x_prefix_hit_tokens",
        "vs_baseline": 0,
        "affinity_prefix_hit_tokens": affinity_hits,
        "random_prefix_hit_tokens": random_hits,
        "affinity_trace_s": round(affinity_dt, 3),
        "random_trace_s": round(random_dt, 3),
        "requests": len(trace), "replicas": 2,
        "prefix_tokens": prefix_blocks * bs,
        "backend": backend, "block_size": bs,
        # The lift in tokens saved is platform-independent, but its
        # latency value (skipped prefill forwards) is a
        # bandwidth-bound on-chip effect; CPU rows prove routing
        # plumbing, not speed.
        "scoreable": bool(on_tpu),
    }), flush=True)

    # Global KV economy (r18): the SAME shared-prefix trace warms
    # replica 0, replica 0 drains, and the storm must land on replica
    # 1 — once with the host tier + cross-replica migration live (the
    # router pulls the drained holder's chains into the sink's host
    # tier, admissions promote them) and once recompute-only (no
    # tier, migration off: the sink re-prefills every prefix from
    # scratch). The sink's interactive-path TTFT p50/p99 IS the row:
    # migration's value is prefill work the sink never does. The
    # crossover estimator's measured inputs ride along so a policy
    # regression (bad rates -> refused transfers) is attributable.
    def kv_offload_trace(migrate: bool):
        fleet = []
        for _ in range(2):
            kw = {"host_kv_bytes": 64 << 20} if migrate else {}
            eng = ServeEngine(params, cfg, n_slots=4,
                              n_blocks=len(trace) * 8 + 1,
                              block_size=bs, idle_sleep_s=0.0005, **kw)
            httpd = serve_engine(eng, host="127.0.0.1", port=0)
            fleet.append((eng, httpd))
        urls = [f"http://127.0.0.1:{h.server_address[1]}"
                for _, h in fleet]
        router = Router(urls, poll_interval_s=0.1,
                        migrate_min_blocks=2 if migrate else 0)
        rhttpd = serve_router(router, "127.0.0.1", 0)
        rport = rhttpd.server_address[1]

        def post(port, p):
            conn = _http_client.HTTPConnection("127.0.0.1", port,
                                               timeout=120)
            conn.request("POST", "/v1/completions",
                         json.dumps({"prompt": p,
                                     "max_tokens": 4}).encode(),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            ok = resp.status == 200
            resp.read()
            conn.close()
            if not ok:                  # plain raise: -O strips asserts
                raise RuntimeError("kv-offload bench request failed")
        try:
            src_port = fleet[0][1].server_address[1]
            for p in trace:             # warm the future drain source
                post(src_port, p)
            router.poll_once()          # learn replica 0's gossip
            fleet[0][0].begin_drain()
            router.poll_once()          # observe not-ready
            t0 = _time.perf_counter()
            for p in trace:
                post(rport, p)
            dt = _time.perf_counter() - t0
            sink = fleet[1][0].stats()
            rstats = router.stats()
        finally:
            rhttpd.shutdown()
            router.stop()
            for eng, httpd in fleet:
                httpd.shutdown()
                eng.stop()
        tiers = sink["per_tier"]["standard"]
        return {"ttft_p50_ms": tiers["ttft_p50_ms"],
                "ttft_p99_ms": tiers["ttft_p99_ms"],
                "prefix_hit_tokens": sink["prefix_hit_tokens"],
                "host_tier": sink["host_tier"],
                "migrated_blocks": rstats.get("migrated_blocks", 0),
                "trace_s": round(dt, 3)}

    mig = kv_offload_trace(True)
    recompute = kv_offload_trace(False)
    ht = mig["host_tier"] or {}
    print(json.dumps({
        "metric": f"{preset}_kv_offload_migration_ttft_ms",
        "mode": "migrate_vs_recompute",
        "value": mig["ttft_p99_ms"], "unit": "ms",
        "vs_baseline": 0,
        "ttft_p50_ms": mig["ttft_p50_ms"],
        "recompute_ttft_p50_ms": recompute["ttft_p50_ms"],
        "recompute_ttft_p99_ms": recompute["ttft_p99_ms"],
        "ttft_p99_win_x": (round(
            recompute["ttft_p99_ms"] / mig["ttft_p99_ms"], 3)
            if mig["ttft_p99_ms"] else None),
        "migrated_blocks": mig["migrated_blocks"],
        "sink_promotions": ht.get("promotions"),
        "sink_prefix_hit_tokens": mig["prefix_hit_tokens"],
        "recompute_prefix_hit_tokens": recompute["prefix_hit_tokens"],
        "crossover": ht.get("crossover"),
        "trace_s": {"migrate": mig["trace_s"],
                    "recompute": recompute["trace_s"]},
        "requests": len(trace), "replicas": 2,
        "prefix_tokens": prefix_blocks * bs,
        "backend": backend, "block_size": bs,
        # The win is skipped prefill forwards (bandwidth-bound on
        # chip) vs a host-RAM pull; CPU rows prove the economy's
        # plumbing end to end, never its speed.
        "scoreable": False,
    }), flush=True)

    # Multi-host host loss (r19): the failure ladder's last rung as
    # numbers — a 2-process engine's steady decode rate, its rate
    # degraded onto the surviving host, and the wall-clock from host
    # rejoin to the grown-back full mesh (re-placement compile
    # included: that IS what an operator waits for). The CPU row runs
    # the forced process view (one process carries both ranks), so it
    # proves the ladder's plumbing, never multi-host speed.
    mh = ServeEngine(params, cfg, n_slots=n_f, n_blocks=n_f * 24 + 1,
                     block_size=bs, idle_sleep_s=0.0005,
                     chaos_spec="",
                     mesh=make_mesh({"tp": 2},
                                    devices=jax.devices()[:2]),
                     num_processes=2, max_reshards=4)

    def mh_run():
        reqs = [_Request([int(t) for t in p], 24, None)
                for p in make_prompts(n_f, 24)]
        for r in reqs:
            if not mh.submit(r):        # plain call: -O strips asserts
                raise RuntimeError("queue refused a bench request")
        while not all(r.done.is_set() for r in reqs):
            mh._loop_once()
        if any(r.error is not None for r in reqs):
            raise RuntimeError("multihost bench request failed")
        return sum(len(r.tokens) for r in reqs)

    mh_run()                                   # compile + warm
    t0 = _time.perf_counter()
    steady_tps = mh_run() / (_time.perf_counter() - t0)
    mh.host_event(1, False)                    # rank 1's host dies
    mh_run()                                   # shrunken-mesh compile
    t0 = _time.perf_counter()
    degraded_tps = mh_run() / (_time.perf_counter() - t0)
    mh.host_event(1, True)                     # the host comes back
    t0 = _time.perf_counter()
    while mh.stats()["grow_backs"] < 1:        # idle ticks grow back
        mh._loop_once()
    recovery_s = _time.perf_counter() - t0
    mh_stats = mh.stats()
    mh.stop()
    print(json.dumps({
        "metric": f"{preset}_multihost_host_loss",
        "mode": "forced_process_view_tp2_x2",
        "value": round(degraded_tps, 1), "unit": "tokens/s",
        "vs_baseline": 0,
        "steady_decode_tokens_per_sec": round(steady_tps, 1),
        "degraded_vs_steady": (round(degraded_tps / steady_tps, 3)
                               if steady_tps else None),
        "recovery_to_full_mesh_s": round(recovery_s, 3),
        "host_losses": mh_stats["host_losses"],
        "host_rejoins": mh_stats["host_rejoins"],
        "reshards": mh_stats["reshards"],
        "grow_backs": mh_stats["grow_backs"],
        "num_processes": mh_stats["num_processes"],
        "slots": n_f, "max_tokens": 24,
        "backend": backend, "block_size": bs,
        # The degraded ratio and recovery clock only mean anything
        # against real per-host compute and interconnect; the CPU
        # forced view shares one host's cores across both ranks.
        "scoreable": False,
    }), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
