"""Power retention (degree 2): the feature map, and one decode step of
the recurrent state.

A retention layer keeps no keys and no values. A key-value head's whole
past is a matrix ``S`` and a vector ``z``,

    S_t = g_t S_{t-1} + phi(k_t) v_t^T        z_t = g_t z_{t-1} + phi(k_t)
    o_i[t] = phi(q_i[t])^T S_t / (phi(q_i[t]) . z_t + eps)

with ``phi(x) . phi(y) = (x . y)^2``. ``phi`` here is the symmetric map
(``x_a x_b`` over unordered pairs, sqrt 2 on the off-diagonal ones:
D (D + 1) / 2 values, 8,256 at D = 128) laid out by DIAGONALS, so that it
is built from lane rotations and every block is a whole lane tile:

    phi(x)[d, a] = w[d, a] * x[a] * x[(a + d) % D]      d = 0 .. D/2

Row 0 is the diagonal (weight 1); rows 1 .. D/2 - 1 hold each unordered
pair once (sqrt 2); row D/2 would hold each pair twice, so its second
half is zero. That is (D/2 + 1) x D entries, 8,320 at D = 128, of which
D/2 are padding that stays exactly zero in ``phi``, ``S`` and ``z``.

State layout: ``state [layers, slots, kv heads, Dv, F]`` float32 (F the
padded feature count, minor-most: a tile of it is lane-dense, and the
read-out contracts lanes of both operands as q.K^T does), ``z [layers,
slots, kv heads, F]``.

``retention_step`` is the decode tick's pass over the state: decay,
rank-one update and the group's read-out while a (slot, head) tile is in
VMEM, the state aliased in place, so the state crosses HBM twice a layer
(once in, once out) where two XLA operations cross it three times. Slots
that are not active are compacted out of the grid through the scalar
prefetch: their blocks are neither fetched nor written. The ``jax.numpy``
form of the same signature serves the CPU and is what the interpreter
parity tests hold the kernel to.
"""

from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST


def n_features(head_dim: int) -> int:
    """Entries of ``phi`` as laid out: (D/2 + 1) x D, D/2 of them zero."""
    return (head_dim // 2 + 1) * head_dim


def feature_weights(head_dim: int) -> np.ndarray:
    """w[d, a] of the diagonal layout, float32 [D/2 + 1, D]."""
    D = head_dim
    if D % 2:
        raise ValueError("power retention needs an even head size")
    w = np.full((D // 2 + 1, D), math.sqrt(2.0), np.float32)
    w[0] = 1.0
    w[D // 2, D // 2:] = 0.0
    return w


def phi(x: jnp.ndarray) -> jnp.ndarray:
    """[..., D] -> [..., (D/2 + 1) D] float32, ``phi(x).phi(y) = (x.y)^2``."""
    D = x.shape[-1]
    x = x.astype(_F32)
    rolled = jnp.stack([jnp.roll(x, -d, axis=-1) for d in range(D // 2 + 1)],
                       axis=-2)
    out = x[..., None, :] * rolled * feature_weights(D)
    return out.reshape(*x.shape[:-1], n_features(D))


def step_reference(state, z, li, q, k, v, log_g, active, *, eps: float):
    """``retention_step`` in plain ``jax.numpy``: every slot computed,
    the inactive ones left as they were."""
    B, Hkv = k.shape[:2]
    G = q.shape[1] // Hkv
    g = jnp.exp(log_g.astype(_F32))
    pk = phi(k)                                         # [B, Hkv, F]
    pq = phi(q).reshape(B, Hkv, G, -1)
    live = active[:, None, None]
    z_new = jnp.where(live, g[..., None] * z[li] + pk, z[li])
    s_new = jnp.where(
        live[..., None],
        g[..., None, None] * state[li]
        + v.astype(_F32)[..., :, None] * pk[..., None, :], state[li])
    num = jnp.einsum("bhgf,bhvf->bhgv", pq, s_new, precision=_HI)
    den = jnp.einsum("bhgf,bhf->bhg", pq, z_new, precision=_HI)
    o = jnp.where(live[..., None], num / (den[..., None] + eps), 0.0)
    return (o.reshape(B, Hkv * G, -1), state.at[li].set(s_new),
            z.at[li].set(z_new))


def _step_kernel(order_ref, n_ref, li_ref, g_ref, pq_ref, pk_ref, vb_ref,
                 s_ref, z_ref, num_ref, den_ref, s_out, z_out, *,
                 n_chunks: int, width: int):
    """One (slot, tile of features) step over every kv head. Refs: g
    [B, Hkv] in SMEM; pq [Hkv, G, T]; pk [Hkv, T]; vb [Hkv, Dv, width]
    (v broadcast along lanes); s [Hkv, Dv, T]; z [Hkv, T]; num [Hkv, Dv,
    width] (lane i: query head i of the group) and den [Hkv, G, 1]
    resident over the slot's tiles; T = n_chunks x width.

    All float32 on the VPU: the read-out o_i[v] = sum_f S[v, f] phi(q_i)[f]
    multiplies the updated tile by a query's features along the lanes,
    adds the roll-blocks up and reduces the lanes once a tile. (The
    same read-out as a float32 product on the MXU, five query rows
    against a weight tile a roll-block, takes the same time on a v5e,
    1.74 ms against 1.76 at 16 slots: the tile's copies bound both,
    PERF.md section 6, PR 32. This form is the one the cell was
    measured with.)"""
    del li_ref
    i, t = pl.program_id(0), pl.program_id(1)
    n_heads, group = pq_ref.shape[:2]
    dv = s_ref.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (dv, width), 1)

    @pl.when(i < n_ref[0])
    def _():
        # heads and roll-blocks of features unrolled: every slice of a
        # ref is static and a whole tile (Mosaic takes no dynamic
        # sublane index, and a slice of a loaded row keeps a lane offset
        # it will not broadcast)
        for h in range(n_heads):
            g = g_ref[order_ref[i], h]
            vb = vb_ref[h]
            den = jnp.zeros((group, 1), _F32)
            for c in range(n_chunks):
                at = slice(c * width, (c + 1) * width)
                pk = pk_ref[h:h + 1, at]
                zn = g * z_ref[h:h + 1, at] + pk
                z_out[h:h + 1, at] = zn
                s_out[h, :, at] = g * s_ref[h, :, at] + vb * pk
                den += jnp.sum(pq_ref[h, :, at] * zn, axis=1, keepdims=True)
            num = jnp.zeros((dv, width), _F32)
            for q in range(group):
                acc = jnp.zeros((dv, width), _F32)
                for c in range(n_chunks):
                    at = slice(c * width, (c + 1) * width)
                    acc += s_out[h, :, at] * pq_ref[h, q:q + 1, at]
                num = jnp.where(lane == q,
                                jnp.sum(acc, axis=1, keepdims=True), num)

            @pl.when(t == 0)
            def _(h=h, num=num, den=den):
                num_ref[h] = num
                den_ref[h] = den

            @pl.when(t > 0)
            def _(h=h, num=num, den=den):
                num_ref[h] += num
                den_ref[h] += den

    @pl.when(n_ref[0] == 0)
    def _():
        # no slot is active: the one block the grid names goes back as
        # it came
        s_out[...] = s_ref[...]
        z_out[...] = z_ref[...]


def _tile_chunks(head_dim: int, n_kv: int, dv: int, budget: int) -> int:
    """Roll-blocks of features a grid step: the most that divide the
    D/2 + 1 and keep the state's four buffers (in and out, each double)
    within ``budget`` bytes of VMEM."""
    n = head_dim // 2 + 1
    fit = [c for c in range(1, n + 1)
           if n % c == 0 and 4 * 4 * n_kv * dv * c * head_dim <= budget]
    return max(fit) if fit else 1


def step_kernel(state, z, li, q, k, v, log_g, active, *, eps: float,
                interpret: bool = False,
                vmem_budget: int = 32 * 2 ** 20):
    """``retention_step`` as a Pallas kernel (see the module's text)."""
    _, B, Hkv, Dv, F = state.shape
    D = k.shape[-1]
    G = q.shape[1] // Hkv
    n_c = _tile_chunks(D, Hkv, Dv, vmem_budget)
    T = n_c * D
    n_t = F // T
    order = jnp.argsort(~active, stable=True).astype(jnp.int32)
    n_act = jnp.sum(active).astype(jnp.int32)

    # steps past the last active slot name its last block again: the
    # pipeline neither fetches nor writes a block whose index stands
    def slot(i, order, n):
        return order[jnp.minimum(i, jnp.maximum(n[0] - 1, 0))]

    def tile(i, t, n):
        return jnp.where(i < n[0], t, n_t - 1)

    def rows(i, t, o, n, l):                # [B, Hkv, (G,) F]
        return (slot(i, o, n), 0, tile(i, t, n))

    def whole(i, t, o, n, l):               # [B, Hkv, x, y], a slot's
        return (slot(i, o, n), 0, 0, 0)

    pq = phi(q).reshape(B, Hkv, G, F)
    pk = phi(k)
    vb = jnp.broadcast_to(v.astype(_F32)[..., None], (B, Hkv, Dv, D))
    s_spec = pl.BlockSpec(
        (None, None, Hkv, Dv, T),
        lambda i, t, o, n, l: (l[0], slot(i, o, n), 0, 0, tile(i, t, n)))
    z_spec = pl.BlockSpec(
        (None, None, Hkv, T),
        lambda i, t, o, n, l: (l[0], slot(i, o, n), 0, tile(i, t, n)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, n_t),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((None, Hkv, G, T), lambda i, t, o, n, l: (
                slot(i, o, n), 0, 0, tile(i, t, n))),
            pl.BlockSpec((None, Hkv, T), rows),
            pl.BlockSpec((None, Hkv, Dv, D), whole),
            s_spec, z_spec],
        out_specs=[pl.BlockSpec((None, Hkv, Dv, D), whole),
                   pl.BlockSpec((None, Hkv, G, 1), whole),
                   s_spec, z_spec])
    num, den, state, z = pl.pallas_call(
        functools.partial(_step_kernel, n_chunks=n_c, width=D),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, Hkv, Dv, D), _F32),
                   jax.ShapeDtypeStruct((B, Hkv, G, 1), _F32),
                   jax.ShapeDtypeStruct(state.shape, _F32),
                   jax.ShapeDtypeStruct(z.shape, _F32)],
        # operands counted with the three prefetched scalars in front
        input_output_aliases={7: 2, 8: 3},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_budget + 16 * 2 ** 20),
        interpret=interpret, name="retention_step",
    )(order, n_act[None], jnp.asarray(li, jnp.int32)[None],
      jnp.exp(log_g.astype(_F32)), pq, pk, vb, state, z)
    num = jnp.swapaxes(num[..., :G], 2, 3)              # [B, Hkv, G, Dv]
    o = jnp.where(active[:, None, None, None], num / (den + eps), 0.0)
    return o.reshape(B, Hkv * G, Dv), state, z


def step_eligible(head_dim: int, dv: int) -> bool:
    """Mosaic takes whole lane tiles: a roll-block of features and a
    value row are each a multiple of 128 lanes."""
    return head_dim % 128 == 0 and dv % 8 == 0


def retention_step(state, z, li, q, k, v, log_g, active, *, eps: float,
                   impl: str = "auto"
                   ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One token a slot through layer ``li`` of the state.

    state [L, B, Hkv, Dv, F] and z [L, B, Hkv, F] float32 (donate them:
    the kernel updates them in place); q [B, H, D], k [B, Hkv, D] (both
    already normed, rotated and scaled), v [B, Hkv, Dv], log_g [B, Hkv]
    (log of the gate, <= 0), active [B] bool. Returns (o [B, H, Dv]
    float32, zero for a slot that is not active; state; z), the state of
    a slot that is not active untouched.

    ``impl``: "kernel" (Mosaic), "interpret" (the kernel under the Pallas
    interpreter), "reference" (``jax.numpy``), or "auto": the kernel on a
    TPU where the shapes are whole tiles, else the reference."""
    if impl == "auto":
        impl = ("kernel" if jax.default_backend() == "tpu"
                and step_eligible(k.shape[-1], v.shape[-1]) else "reference")
    if impl == "reference":
        return step_reference(state, z, li, q, k, v, log_g, active, eps=eps)
    return step_kernel(state, z, li, q, k, v, log_g, active, eps=eps,
                       interpret=impl == "interpret")
