"""On-chip pallas kernel validation + timing.

Runs every pallas kernel (resident flash, streaming flash, partial
flash, ragged decode, paged decode bf16/int8, paged verify, the paged
latent decode, the flash kernel under shard_map, the fused int8 expert
FFN) on the real TPU,
compiled by Mosaic, checks numerical parity against the XLA reference,
and times kernel vs reference. Prints one JSON line per kernel:

  {"kernel": ..., "ok": bool, "max_err": float, "kernel_ms": float,
   "ref_ms": float, "speedup": float, "timing_credible": bool}

A kernel Mosaic rejects prints ``"ok": false`` with the compiler's
message and the run goes on to the next one. ``--parity`` skips the
timing halves (compile + compare only).

Timing methodology:
- Every timed call ends in a device->host scalar readback, and
  per-call time is the DIFFERENCE between a k_hi-long and a k_lo-long
  device-chained scan divided by (k_hi - k_lo), so dispatch and
  readback cancel.
- Loop-invariant operands get hoisted/VMEM-parked by XLA (an invariant
  KV cache times decode at 3.7 TB/s — above the HBM roofline), so
  decode-shaped benches carry the cache through the scan and scatter
  one row per step, the serving access pattern.
- When the chain delta is within jitter the number is garbage;
  ``timing_credible`` is false unless the delta clears an absolute
  floor, rather than silently reporting a sub-noise reading.
"""

from __future__ import annotations

import functools
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

K_LO, K_HI = 16, 256
MIN_CREDIBLE_DELTA_S = 0.020     # chain delta must clear 20 ms of jitter
PARITY_ONLY = "--parity" in sys.argv[1:]

def _timeit_scan(body, init, *consts, iters: int = 5):
    """Per-iteration (ms, credible) of ``body`` (carry[, *consts] ->
    carry); thin ms-unit wrapper over the shared
    ``profiling.time_step_chained`` (scan-differencing with
    scalar-readback barrier — one implementation so the methodology
    cannot silently fork). Loop-invariant tensors go in ``consts`` as
    real jit arguments, never closures (closure capture bakes them
    into the module as constants — see time_step_chained)."""
    from tpushare.utils.profiling import time_step_chained

    s, credible = time_step_chained(
        body, init, *consts, k_lo=K_LO, k_hi=K_HI, iters=iters,
        min_credible_delta_s=MIN_CREDIBLE_DELTA_S)
    return s * 1e3, credible


def _timeit_chained(fn, q, *rest, iters: int = 5):
    """(ms, credible) for ``fn(q, *rest)``; the carry perturbs the
    ORIGINAL q by the output (data dependency blocks CSE; re-anchoring
    to q each step keeps the operand's statistics over the chain)."""
    def body(c, *cs):
        o = fn(c, *cs[:-1])
        o0 = o[0] if isinstance(o, tuple) else o
        return cs[-1] + (o0 * 1e-3).astype(c.dtype)
    return _timeit_scan(body, q, *rest, q, iters=iters)


def _timeit_decode_chained(fn, q, k, v, pos, *, iters: int = 5):
    """(ms, credible), decode-shaped: KV cache in the carry, one row
    per slot scattered each step (see module docstring on hoisting)."""
    B, _, H, D = q.shape
    M, Hkv = k.shape[1], k.shape[2]

    def body(carry, q0):
        qc, kc, vc, pc = carry
        o = fn(qc, kc, vc, pc)
        p2 = jnp.minimum(pc + 1, M - 1)
        row = o[:, 0, :Hkv, :].astype(kc.dtype)
        return (q0 + (o * 1e-3).astype(q0.dtype),
                kc.at[jnp.arange(B), p2].set(row),
                vc.at[jnp.arange(B), p2].set(row),
                p2)
    return _timeit_scan(body, (q, k, v, pos), q, iters=iters)


def _timeit_paged_chained(fn, q, pk, pv, table, pos, *,
                          iters: int = 5):
    """(ms, credible), paged: pools in the carry, one row per slot
    scattered through the block table each step."""
    B = q.shape[0]
    nb, bs, Hkv, D = pk.shape
    mb = table.shape[1]

    def body(carry, table0, q0):
        qc, pkc, pvc, pc = carry
        o = fn(qc, pkc, pvc, table0, pc)
        p2 = jnp.minimum(pc + 1, bs * mb - 1)
        blk = jnp.take_along_axis(table0, (p2 // bs)[:, None], 1)[:, 0]
        row = o[:, 0, :Hkv, :].astype(pkc.dtype)
        return (q0 + (o * 1e-3).astype(q0.dtype),
                pkc.at[blk, p2 % bs].set(row),
                pvc.at[blk, p2 % bs].set(row),
                p2)
    return _timeit_scan(body, (q, pk, pv, pos), table, q, iters=iters)


def _report(name, out, ref, kernel_ms, kernel_cred, ref_ms, ref_cred, *,
            floor_ms=None):
    """Print a kernel's row; ``floor_ms``, where given, is the least time
    the call can take, and the row gains the kernel's share of it."""
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - ref.astype(jnp.float32))))
    ok = err < 3e-2  # bf16 inputs, f32 softmax in both paths
    row = {"kernel": name, "ok": bool(ok), "max_err": round(err, 5),
           "backend": jax.default_backend()}
    if kernel_ms is not None:
        row.update({
            "kernel_ms": round(kernel_ms, 3), "ref_ms": round(ref_ms, 3),
            "speedup": round(ref_ms / kernel_ms, 2) if kernel_ms else None,
            "timing_credible": bool(kernel_cred and ref_cred)})
        if floor_ms is not None and kernel_ms:
            row["floor_pct"] = round(100.0 * floor_ms / kernel_ms, 1)
    print(json.dumps(row), flush=True)
    return ok


def _timed_pair(timer, fl, rf, *args):
    """Run the timer on kernel and reference; returns _report's tail
    arguments (kernel_ms, kernel_cred, ref_ms, ref_cred) — all None
    under --parity."""
    if PARITY_ONLY:
        return None, None, None, None
    k_ms, k_cred = timer(fl, *args)
    r_ms, r_cred = timer(rf, *args)
    return k_ms, k_cred, r_ms, r_cred


def _mk(seed, *shapes, dtype=jnp.bfloat16):
    """Random bf16 tensors, one per shape, from one seeded key split."""
    ks = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    return [jax.random.normal(k, sh, dtype) for k, sh in zip(ks, shapes)]


def bench_resident():
    from tpushare.ops.attention import mha_reference
    from tpushare.ops.flash_attention import flash_attention
    B, Sq, H, Hkv, D = 4, 2048, 8, 2, 128
    q, k, v = _mk(0, (B, Sq, H, D), (B, Sq, Hkv, D), (B, Sq, Hkv, D))
    fl = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))
    rf = jax.jit(lambda q, k, v: mha_reference(q, k, v, causal=True))
    return _report("flash_resident", fl(q, k, v), rf(q, k, v),
                   *_timed_pair(_timeit_chained, fl, rf, q, k, v))


def bench_resident_window_softcap():
    from tpushare.ops.attention import mha_reference
    from tpushare.ops.flash_attention import flash_attention
    B, Sq, H, Hkv, D = 2, 2048, 8, 4, 128
    q, k, v = _mk(1, (B, Sq, H, D), (B, Sq, Hkv, D), (B, Sq, Hkv, D))
    fl = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=512, attn_softcap=50.0))
    rf = jax.jit(lambda q, k, v: mha_reference(
        q, k, v, causal=True, window=512, attn_softcap=50.0))
    return _report("flash_window_softcap", fl(q, k, v), rf(q, k, v),
                   *_timed_pair(_timeit_chained, fl, rf, q, k, v))


def bench_streaming():
    from tpushare.ops.attention import mha_reference
    from tpushare.ops.flash_attention import flash_attention
    # Sk=32768 > MAX_RESIDENT_KV_BYTES bound -> streaming path. The
    # reference materializes [B,Hkv,G,Sq,Sk] f32 scores, so Sq stays
    # modest (the last rows, via q_offset) — this checks parity and
    # times only that tail slice, not a full-Sq run.
    B, Sq, Sk, H, Hkv, D = 1, 512, 32768, 8, 2, 128
    q, k, v = _mk(2, (B, Sq, H, D), (B, Sk, Hkv, D), (B, Sk, Hkv, D))
    off = Sk - Sq
    fl = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True,
                                                 q_offset=off))
    rf = jax.jit(lambda q, k, v: mha_reference(q, k, v, causal=True,
                                               q_offset=off))
    return _report("flash_streaming_32k", fl(q, k, v), rf(q, k, v),
                   *_timed_pair(_timeit_chained, fl, rf, q, k, v))


def bench_partial():
    from tpushare.ops.flash_attention import (flash_attention_partial,
                                              partial_reference)
    B, Sq, Sk, H, Hkv, D = 2, 1024, 1024, 8, 2, 128
    q, k, v = _mk(3, (B, Sq, H, D), (B, Sk, Hkv, D), (B, Sk, Hkv, D))
    koff = 1024

    def _norm(fn):
        # Compare acc/l, not raw acc: the unnormalized accumulator's
        # magnitude scales with l (sum of exp weights), so absolute
        # error on it is meaningless; acc/l is the softmax output the
        # ring-attention merge ultimately produces.
        def run(q, k, v):
            acc, m, l = fn(q, k, v, q_offset=koff, k_offset=0)
            return acc / jnp.maximum(l.transpose(0, 2, 1)[..., None], 1e-30)
        return jax.jit(run)

    fl = _norm(flash_attention_partial)
    rf = _norm(partial_reference)
    return _report("flash_partial", fl(q, k, v), rf(q, k, v),
                   *_timed_pair(_timeit_chained, fl, rf, q, k, v))


def bench_decode():
    from tpushare.ops.attention import mha_reference
    from tpushare.ops.flash_attention import flash_decode
    B, M, H, Hkv, D = 8, 8192, 8, 2, 128
    q, k, v = _mk(4, (B, 1, H, D), (B, M, Hkv, D), (B, M, Hkv, D))
    pos = jax.random.randint(jax.random.PRNGKey(40), (B,), 128, M - 1)
    fl = jax.jit(lambda q, k, v, pos: flash_decode(q, k, v, pos))
    def _ref(q, k, v, pos):
        kv_mask = jnp.arange(M)[None, :] <= pos[:, None]
        return mha_reference(q, k, v, causal=False, kv_mask=kv_mask)
    rf = jax.jit(_ref)
    return _report("flash_decode", fl(q, k, v, pos), rf(q, k, v, pos),
                   *_timed_pair(_timeit_decode_chained, fl, rf, q, k, v,
                                pos))


def bench_paged():
    from tpushare.ops.attention import mha_reference
    from tpushare.ops.flash_attention import paged_flash_decode
    B, H, Hkv, D, bs, mb = 8, 8, 2, 128, 128, 32   # 4096 ctx max
    nb = B * mb + 1
    q, pool_k, pool_v = _mk(5, (B, 1, H, D), (nb, bs, Hkv, D),
                            (nb, bs, Hkv, D))
    # Identity-ish block table: slot b owns pages [1 + b*mb, 1 + (b+1)*mb)
    table = (1 + np.arange(B)[:, None] * mb + np.arange(mb)[None, :]
             ).astype(np.int32)
    pos = jax.random.randint(jax.random.PRNGKey(50), (B,), 128, bs * mb - 1)
    table = jnp.asarray(table)
    fl = jax.jit(lambda q, pk, pv, t, pos: paged_flash_decode(
        q, pk, pv, t, pos))
    def _ref(q, pk, pv, t, pos):
        # Materialize the contiguous view through the table, then mask.
        kc = pk[t].reshape(B, mb * bs, Hkv, D)
        vc = pv[t].reshape(B, mb * bs, Hkv, D)
        kv_mask = jnp.arange(mb * bs)[None, :] <= pos[:, None]
        return mha_reference(q, kc, vc, causal=False, kv_mask=kv_mask)
    rf = jax.jit(_ref)

    return _report("paged_flash_decode",
                   fl(q, pool_k, pool_v, table, pos),
                   rf(q, pool_k, pool_v, table, pos),
                   *_timed_pair(_timeit_paged_chained, fl, rf, q, pool_k,
                                pool_v, table, pos))


def bench_paged_q8():
    """Int8 paged decode: same block-table kernel streaming half the
    page bytes (decode's roofline) + in-kernel dequant. Reference =
    the bf16-pool kernel on the dequantized pools — so the row
    isolates the int8-streaming effect, parity AND speed."""
    from tpushare.models.quant import kv_dequantize, kv_quantize
    from tpushare.ops.flash_attention import paged_flash_decode
    B, H, Hkv, D, bs, mb = 8, 8, 2, 128, 128, 32   # 4096 ctx max
    nb = B * mb + 1
    q, pool_k, pool_v = _mk(6, (B, 1, H, D), (nb, bs, Hkv, D),
                            (nb, bs, Hkv, D))
    table = jnp.asarray(
        (1 + np.arange(B)[:, None] * mb + np.arange(mb)[None, :]
         ).astype(np.int32))
    pos = jax.random.randint(jax.random.PRNGKey(60), (B,), 128, bs * mb - 1)
    from tpushare.models.quant import scales_to_pool_layout
    qk, sk_r = kv_quantize(pool_k)
    qv, sv_r = kv_quantize(pool_v)
    dk = kv_dequantize(qk, sk_r, pool_k.dtype)
    dv = kv_dequantize(qv, sv_r, pool_v.dtype)
    # Scale pages live in the kernel layout from init (ADVICE r3): the
    # timed region no longer pays a whole-pool transpose per step.
    sk = scales_to_pool_layout(sk_r)
    sv = scales_to_pool_layout(sv_r)
    fl = jax.jit(lambda q, pk, pv, t, pos: paged_flash_decode(
        q, pk, pv, t, pos, k_scale=sk, v_scale=sv))
    rf = jax.jit(lambda q, pk, pv, t, pos: paged_flash_decode(
        q, pk, pv, t, pos))
    out = fl(q, qk, qv, table, pos)
    ref = rf(q, dk, dv, table, pos)
    # Pools ride the carry (data-dependent chain); the scale pages are
    # small (~0.5 MB) loop-invariant closures — they would be hoisted
    # as constants either way and stay far under the capture warning.
    k_ms = k_cred = r_ms = r_cred = None
    if not PARITY_ONLY:
        k_ms, k_cred = _timeit_paged_chained(
            lambda qc, pkc, pvc, t, pc: paged_flash_decode(
                qc, pkc, pvc, t, pc, k_scale=sk, v_scale=sv),
            q, qk, qv, table, pos)
        r_ms, r_cred = _timeit_paged_chained(
            lambda qc, pkc, pvc, t, pc: paged_flash_decode(
                qc, pkc, pvc, t, pc),
            q, dk, dv, table, pos)
    return _report("paged_flash_decode_int8", out, ref, k_ms, k_cred,
                   r_ms, r_cred)


def bench_paged_verify():
    """Multi-token speculative-verify kernel vs the gathered 3D-masked
    fallback (transformer.py's paged Sq>1 branch) — the per-round
    whole-slot-view gather is the cost under test."""
    from tpushare.ops.attention import mha_reference
    from tpushare.ops.flash_attention import paged_flash_verify
    B, Sq, H, Hkv, D, bs, mb = 8, 4, 8, 2, 128, 128, 32   # 4096 ctx
    nb = B * mb + 1
    q, pool_k, pool_v = _mk(8, (B, Sq, H, D), (nb, bs, Hkv, D),
                            (nb, bs, Hkv, D))
    table = jnp.asarray(
        (1 + np.arange(B)[:, None] * mb + np.arange(mb)[None, :]
         ).astype(np.int32))
    pos = jax.random.randint(jax.random.PRNGKey(70), (B,), 128,
                             bs * mb - Sq)
    fl = jax.jit(lambda q, pk, pv, t, pos: paged_flash_verify(
        q, pk, pv, t, pos))

    def _ref(q, pk, pv, t, pos):
        kc = pk[t].reshape(B, mb * bs, Hkv, D)
        vc = pv[t].reshape(B, mb * bs, Hkv, D)
        pos_grid = pos[:, None] + jnp.arange(Sq)[None, :]
        mask = jnp.arange(mb * bs)[None, None, :] <= pos_grid[..., None]
        return mha_reference(q, kc, vc, causal=False, kv_mask=mask)
    rf = jax.jit(_ref)
    return _report("paged_flash_verify",
                   fl(q, pool_k, pool_v, table, pos),
                   rf(q, pool_k, pool_v, table, pos),
                   *_timed_pair(_timeit_paged_chained, fl, rf, q, pool_k,
                                pool_v, table, pos))


def _timeit_latent_chained(fn, q, pool, table, pos, *, layer: int,
                           iters: int = 5):
    """(ms, credible), paged latent rows: the stacked pool in the carry,
    one row per slot scattered through the block table each step."""
    B, mb = table.shape
    bs, C = pool.shape[2:]

    def body(carry, table0, q0):
        qc, pc, at = carry
        o = fn(qc, pc, table0, at)                  # [B, Q, H, rank]
        nxt = jnp.minimum(at + 1, bs * mb - 1)
        blk = jnp.take_along_axis(table0, nxt[:, :1] // bs, 1)[:, 0]
        row = jnp.concatenate([o[:, 0, 0], o[:, 0, 1]], -1)[:, :C]
        return (q0 + (o[..., :1] * 1e-3).astype(q0.dtype),
                pc.at[layer, blk, nxt[:, 0] % bs].set(row.astype(pc.dtype)),
                nxt)
    return _timeit_scan(body, (q, pool, pos), table, q, iters=iters)


def _latent_floor_ms(n_rows: int, n_q: int) -> float:
    """The least time a call over ``n_rows`` cached rows under ``n_q``
    live queries a slot can take at the pangu cell's published widths:
    the floor ``mla.decode_roofline_pct`` holds the kernel to in a trace
    (``tpubench/readers/mla_decode_trace.call_floor_s``), so that the
    kernel alone and the cell read on one yardstick."""
    from tpubench import spec
    from tpubench.peaks import peaks_for
    from tpubench.readers.mla_decode_trace import call_floor_s
    with open(os.path.join(spec.ROOT, "tpubench", "configs",
                           "openpangu-ultra-l5-ep32.json")) as f:
        config = json.load(f)
    peaks = peaks_for(jax.devices()[0].device_kind)
    return 1e3 * call_floor_s(config, n_rows, n_q, peaks)


def _bench_latent_paged(live_rows: int):
    """The paged latent decode kernel at the pangu cell's shapes (16
    slots, two queries of 128 heads, rows of 640 of which 512 are the
    latent, a table of 1,046 pages of 16) with every slot at
    ``live_rows``, against the gathered ``jnp`` form; its row gives the
    kernel's share of the published widths' floor (``floor_pct``)."""
    from tpushare.models import latent
    from tpushare.ops.latent_decode import latent_paged_decode
    B, Q, H, bs, mb, L, li = 16, 2, 128, 16, 1046, 2, 1
    dims = latent.AttnDims(H, 1536, 512, 128, 64, 128, 25.6e6)
    C, nb = dims.key_dim, B * mb + 1
    q, pool = _mk(11, (B, Q, H, C), (L, nb, bs, C))
    table = jnp.asarray(1 + np.arange(B)[:, None] * mb
                        + np.arange(mb)[None, :], jnp.int32)
    pos = jnp.asarray(live_rows - 2 + np.zeros((B, 1), np.int32)
                      + np.arange(Q)[None, :], jnp.int32)
    live = jnp.ones((B, Q), bool)
    fl = jax.jit(lambda q, pool, t, pos: latent_paged_decode(
        q, pool, t, pos, live, layer=li, kv_rank=dims.kv_rank,
        scale=(dims.nope + dims.rope) ** -0.5))
    rf = jax.jit(lambda q, pool, t, pos: latent._gather_attend(
        pool, li, t, pos, q, dims))
    timer = functools.partial(_timeit_latent_chained, layer=li)
    return _report(f"latent_paged_decode_{live_rows // 1024}k",
                   fl(q, pool, table, pos), rf(q, pool, table, pos),
                   *_timed_pair(timer, fl, rf, q, pool, table, pos),
                   floor_ms=_latent_floor_ms(B * live_rows, Q))


def bench_latent_paged():
    return all([_bench_latent_paged(n) for n in (8192, 12288, 16384)])


def bench_ring_shardmap():
    """Ring attention's REAL flash inner loop lowered inside a
    vma-tagged shard_map on the actual Mosaic toolchain — the half of
    'ring attention on hardware' one visible chip can validate (the
    multi-hop DMA interplay needs >=2 chips; this catches the
    kernel-under-manual-axes lowering class of failure the CPU
    interpreter cannot, since it swaps in the jnp contract-equivalent
    under shard_map). sp=1: collectives are degenerate no-ops, the
    pallas_call and its vma-tagged operands are not."""
    from tpushare.ops.attention import mha_reference
    from tpushare.parallel import make_mesh, ring_attention_sharded
    B, S, H, Hkv, D = 2, 1024, 8, 2, 128
    q, k, v = _mk(7, (B, S, H, D), (B, S, Hkv, D), (B, S, Hkv, D))
    mesh = make_mesh({"sp": 1, "tp": -1},
                     devices=jax.devices()[:1])
    out = ring_attention_sharded(q, k, v, mesh=mesh, causal=True,
                                 impl="flash")
    ref = mha_reference(q, k, v, causal=True)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - ref.astype(jnp.float32))))
    ok = err < 2e-2
    print(json.dumps({"kernel": "ring_flash_shardmap_sp1", "ok": ok,
                      "max_err": round(err, 5)}), flush=True)
    return ok


def bench_q8_expert():
    """Fused dequant x GEMM expert FFN at a decode-shaped token block
    against its scale-after-dot f32 reference (parity only: its speed
    belongs to bench_moe's rows)."""
    from tpushare.ops.q8_expert import (q8_expert_ffn,
                                        q8_expert_ffn_reference)
    E, C, Dm, F = 8, 8, 1024, 4096
    ks = jax.random.split(jax.random.PRNGKey(9), 7)
    x = jax.random.normal(ks[0], (C, Dm), jnp.bfloat16)

    def w(k, shape):
        return jax.random.randint(k, shape, -127, 128, jnp.int8)

    def sc(k, n):
        return jax.random.uniform(k, (E, 1, n), jnp.float32, 1e-3, 3e-3)

    args = (x, w(ks[1], (E, Dm, F)), sc(ks[2], F), w(ks[3], (E, Dm, F)),
            sc(ks[4], F), w(ks[5], (E, F, Dm)), sc(ks[6], Dm))
    out = q8_expert_ffn(*args)
    ref = q8_expert_ffn_reference(*args)
    # Relative: the outputs' scale depends on the random scales.
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - ref.astype(jnp.float32)))
                / jnp.max(jnp.abs(ref.astype(jnp.float32))))
    ok = err < 3e-2
    print(json.dumps({"kernel": "q8_expert_ffn", "ok": ok,
                      "max_rel_err": round(err, 5)}), flush=True)
    return ok


def main():
    print(json.dumps({"backend": jax.default_backend(),
                      "devices": [str(d) for d in jax.devices()],
                      "parity_only": PARITY_ONLY}),
          flush=True)
    if jax.default_backend() == "cpu":
        print(json.dumps({"all_ok": False, "error":
                          "no accelerator: these kernels are compiled "
                          "by Mosaic, which needs the chip"}), flush=True)
        return 1
    results = []
    for bench in (bench_resident, bench_resident_window_softcap,
                  bench_streaming, bench_partial, bench_decode,
                  bench_paged, bench_paged_q8, bench_paged_verify,
                  bench_latent_paged, bench_ring_shardmap, bench_q8_expert):
        try:
            results.append(bench())
        except Exception as e:      # noqa: BLE001 — a kernel Mosaic
            # rejects must not hide the verdict on the ones after it.
            results.append(False)
            print(json.dumps({"kernel": bench.__name__, "ok": False,
                              "error": f"{type(e).__name__}: "
                                       f"{str(e)[-1500:]}"}),
                  flush=True)
    print(json.dumps({"all_ok": all(results)}), flush=True)
    return 0 if all(results) else 1


if __name__ == "__main__":
    raise SystemExit(main())
