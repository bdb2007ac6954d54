"""Paged decode attention over a latent pool, in the absorbed form.

A latent (MLA) layer caches ONE row a position for all its heads: the
normed latent and the rotary key, ``key_dim`` values (zero-padded to
whole lane tiles). In the absorbed form a head's query is already
multiplied into that row's space, so the row is the key of every head
AND, cut to its first ``kv_rank`` columns, the value of every head:

    s[q, h, t] = q[q, h] . row[t] * scale        o[q, h] = softmax(s) row[:, :kv_rank]

``latent_paged_decode`` is ``paged_flash_decode``'s scheme
(``ops/flash_attention.py``) for that shape: grid ``(B,)``, one grid step
a slot, inside it a loop over groups of the slot's LIVE pages, one DMA a
page through the scalar-prefetched page list into one VMEM tile of a
double buffer, the next group (or the next live slot's first) in flight
while this one is computed, the softmax online. What differs:

- one tile serves as keys and as values, so a page crosses HBM once;
- queries and heads are ONE axis of both products (``Q x H`` rows, as
  ``models/latent._attend`` lays them out), a row's mask position is its
  query's;
- the output product is one bf16 product accumulated in float32
  (``_attend``'s precision, not ``_pv_exact``'s split);
- slots with no live query are compacted out of the loop (the
  ``retention_step`` scheme): they cost no copy and no product, and
  their output is zero;
- the loop step is one straight-line block: every group is whole (a
  slot's page list is cut and clamped before the call, so a group's
  copies need no branch and no arithmetic on the scalar core), the next
  group's copies start at its top and one wait covers the whole tile.

The stacked pool ``[L, n_blocks, bs, key_dim]`` stays in HBM whole and
the layer is a scalar: no layer slice, no gathered view exists.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpushare.ops.attention import NEG_INF
from tpushare.ops.flash_attention import DECODE_BUFFER_BYTES, _sds, _sublanes

#: the kernel's name in a trace (``tpubench/readers/mla_decode_trace.py``
#: finds its events by it)
KERNEL_NAME = "latent_paged_decode"

#: Keys one loop step covers: 512 rows of 1,280 B are 0.66 MB a buffer
#: half and a [256, 512] float32 score tile 0.5 MB. Swept on a v5e at
#: the pangu cell's shapes, 12k live rows a slot (PERF.md section 6,
#: PR 36): 256 keys a step cost 1.01 ms a call, 512 cost 0.93; at 1,024
#: the program halted on the chip (64 copies in flight on one
#: semaphore, 1.3 MB: 40,960 granules of 32 bytes, past the 32,767 the
#: interpreter's semaphores hold; not looked into on the chip).
LATENT_GROUP_KEYS = 512


def _latent_group_pages(bs: int, mb: int, row_bytes: int) -> int:
    """Pages one loop step covers, from what the call can see
    (``_decode_group_pages``'s rule): LATENT_GROUP_KEYS keys' worth of
    ``bs``-row pages, fewer where a row is so wide that a tile of them
    would pass a quarter of DECODE_BUFFER_BYTES (1 MiB: the most one
    semaphore of the dense kernel waits for), never more than a slot
    has, never under one."""
    keys = min(LATENT_GROUP_KEYS, DECODE_BUFFER_BYTES // (4 * row_bytes))
    return max(1, min(mb, keys // bs))


def latent_decode_eligible(q: jnp.ndarray, pool: jnp.ndarray,
                           kv_rank: int) -> bool:
    """Whether ``latent_paged_decode`` serves q [B, Q, H, key_dim] over
    pool [L, n_blocks, bs, key_dim]: by backend and shape alone. A TPU,
    pages of whole tiles of the pool's dtype, rows and the output's cut
    of whole lane tiles, ``Q x H`` whole sublane tiles. Every other
    call keeps the ``jnp`` body of ``latent._decode_all``."""
    if jax.default_backend() != "tpu":
        return False
    _, Q, H, C = q.shape
    bs, C2 = pool.shape[2:]
    return (q.dtype == pool.dtype and C2 == C and C % 128 == 0
            and 0 < kv_rank <= C and kv_rank % 128 == 0
            and bs % _sublanes(pool.dtype) == 0
            and (Q * H) % _sublanes(q.dtype) == 0)


def _latent_decode_kernel(pages_ref, qpos_ref, order_ref, n_ref, layer_ref,
                          q_ref, pool_hbm, o_ref, buf, acc_ref, sem,
                          turn_ref, *, scale: float, n_q: int, n_heads: int,
                          kv_rank: int, group: int, n_pages: int, width: int):
    # Grid step i serves slot order[i]; the first n_ref[0] steps are the
    # slots with a live query, in slot order. pages [B * width] is each
    # slot's page list, whole groups wide (``_page_lists``); qpos
    # [B * n_q] holds a query's position, -1 where it is not live. buf
    # [2, group * bs, key_dim] is the double buffer, acc [n_q * n_heads,
    # kv_rank] the output's float32 accumulator; the buffer's turn and
    # its copy in flight pass from one grid step to the next.
    bs = buf.shape[1] // group
    T = group * bs
    rows = n_q * n_heads
    i = pl.program_id(0)
    n_live = n_ref[0]
    layer = layer_ref[0]

    def span(step):
        """(slot, its groups) of a live step: the pages up to the slot's
        largest live position."""
        slot = order_ref[step]
        top = qpos_ref[slot * n_q]
        for j in range(1, n_q):
            top = jnp.maximum(top, qpos_ref[slot * n_q + j])
        hi = jnp.clip(top // bs + 1, 1, n_pages)
        return slot, (hi + group - 1) // group

    def start_group(slot, g, half):
        """Start the copies of ``slot``'s group ``g`` into ``half`` of the
        buffer, page j of the group on rows j * bs of the tile."""
        at = slot * width + g * group
        for j in range(group):
            pltpu.make_async_copy(
                pool_hbm.at[layer, pages_ref[at + j]],
                buf.at[half, pl.ds(j * bs, bs)], sem.at[half]).start()

    def wait_group(half):
        """Wait for a whole group's copies into ``half``: one wait for
        the tile's bytes (the descriptor is only its size)."""
        pltpu.make_async_copy(buf.at[half], buf.at[half],
                              sem.at[half]).wait()

    @pl.when(i == 0)
    def _first():
        turn_ref[0] = 0

        @pl.when(n_live > 0)
        def _():
            start_group(order_ref[0], 0, 0)

    @pl.when(i >= n_live)
    def _dead():
        o_ref[0] = jnp.zeros_like(o_ref[0])

    @pl.when(i < n_live)
    def _live():
        slot, n_g = span(i)
        # in line after this slot's last group: the next live slot's
        # first, and after the call's last, this slot's first again
        nslot = order_ref[jnp.minimum(i + 1, n_live - 1)]
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
        row_pos = jnp.full((rows, 1), qpos_ref[slot * n_q], jnp.int32)
        for j in range(1, n_q):
            row_pos = jnp.where(row >= j * n_heads,
                                qpos_ref[slot * n_q + j], row_pos)
        lane = jax.lax.broadcasted_iota(jnp.int32, (rows, T), 1)
        acc_ref[...] = jnp.zeros_like(acc_ref)

        # One straight-line block a step. The next group's copies start
        # first: placed after the score product, the scheduler sinks them
        # to the step's end and the next wait stalls (on a v5e at 12k
        # rows a slot, 1.18 ms a call against 0.97; PERF.md section 6).
        def step(g, carry):
            m, l, half = carry
            last = g == n_g - 1
            start_group(jnp.where(last, nslot, slot),
                        jnp.where(last, 0, g + 1), 1 - half)
            wait_group(half)
            tile = buf[half]                                # [T, key_dim]
            s = jax.lax.dot_general(
                q_ref[0], tile, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [rows, T]
            s = jnp.where(lane <= row_pos - g * T, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
                p.astype(tile.dtype), tile[:, :kv_rank],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return m_new, l, 1 - half

        _, l, half = jax.lax.fori_loop(
            0, n_g, step,
            (jnp.full((rows, 1), NEG_INF, jnp.float32),
             jnp.zeros((rows, 1), jnp.float32), turn_ref[0]))
        turn_ref[0] = half

        @pl.when(i == n_live - 1)
        def _drain():
            # the call's last copies, which no step reads
            wait_group(half)

        # a query that is not live (beside a live one) attended nothing
        o_ref[0] = jnp.where(row_pos >= 0,
                             acc_ref[...] / jnp.maximum(l, 1e-30),
                             0.0).astype(o_ref.dtype)


def _page_lists(table, qpos, *, bs: int, group: int, n_blocks: int):
    """Each slot's page list as the kernel's copies read it: [B * width]
    int32, ``width`` the table's width rounded up to whole groups. Past
    a slot's live pages (past its table, or a -1 entry) it repeats the
    slot's last live page, whose rows there lie past every query's
    position: a group is always whole and a slot reads no block but its
    own. Every entry is a block of the pool, so no copy can leave it."""
    B, mb = table.shape
    width = -(-mb // group) * group
    hi = jnp.clip(jnp.max(qpos, axis=1) // bs + 1, 1, mb)         # [B]
    col = jnp.arange(width)[None, :]
    padded = jnp.pad(table, ((0, 0), (0, width - mb)))
    last = jnp.sum(jnp.where(col == hi[:, None] - 1, padded, 0), axis=1)
    pages = jnp.where(col < hi[:, None], padded, last[:, None])
    return jnp.clip(pages, 0, n_blocks - 1).reshape(B * width), width


@functools.partial(jax.jit, static_argnames=("kv_rank", "scale", "interpret"))
def latent_paged_decode(q: jnp.ndarray, pool: jnp.ndarray,
                        table: jnp.ndarray, pos: jnp.ndarray,
                        live: jnp.ndarray, *, layer, kv_rank: int,
                        scale: float,
                        interpret: bool = False) -> jnp.ndarray:
    """Absorbed latent attention of Q queries a slot straight off the
    paged pool.

    q [B, Q, H, key_dim]; pool the WHOLE stacked pool [L, n_blocks, bs,
    key_dim] with ``layer`` (a traced scalar is fine) the layer to read;
    table [B, max_blocks] int32 pool indices (-1 = unallocated: never
    read past a slot's live pages); pos, live [B, Q]: query (b, j)
    attends slot b's positions <= pos[b, j] through its table (its own
    row already written) and nothing where it is not live. Returns the
    latent output [B, Q, H, kv_rank], zero for a query that is not live.

    A call costs the LIVE pages, in whole groups: a slot's loop runs up
    to its largest live position, a page past a query's position is
    masked (what a rejected draft left there is never attended), a slot
    with no live query is skipped."""
    B, Q, H, C = q.shape
    L, n_blocks, bs, C2 = pool.shape
    assert C2 == C and 0 < kv_rank <= C, (q.shape, pool.shape, kv_rank)
    mb = table.shape[1]
    group = _latent_group_pages(bs, mb, C * pool.dtype.itemsize)
    qpos = jnp.where(live, pos, -1).astype(jnp.int32)            # [B, Q]
    alive = jnp.max(qpos, axis=1) >= 0
    order = jnp.argsort(jnp.logical_not(alive), stable=True)
    pages, width = _page_lists(jnp.asarray(table, jnp.int32), qpos, bs=bs,
                               group=group, n_blocks=n_blocks)

    def at_slot(i, pages_ref, qpos_ref, order_ref, n_ref, layer_ref):
        return (order_ref[i], 0, 0)

    def at_live_slot(i, pages_ref, qpos_ref, order_ref, n_ref, layer_ref):
        # a skipped step asks for the block it already holds: no copy
        return (order_ref[jnp.minimum(i, jnp.maximum(n_ref[0] - 1, 0))],
                0, 0)

    out = pl.pallas_call(
        functools.partial(_latent_decode_kernel, scale=scale, n_q=Q,
                          n_heads=H, kv_rank=kv_rank, group=group,
                          n_pages=mb, width=width),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(B,),
            in_specs=[pl.BlockSpec((1, Q * H, C), at_live_slot),
                      pl.BlockSpec(memory_space=pltpu.HBM)],
            out_specs=pl.BlockSpec((1, Q * H, kv_rank), at_slot),
            scratch_shapes=[
                pltpu.VMEM((2, group * bs, C), pool.dtype),
                pltpu.VMEM((Q * H, kv_rank), jnp.float32),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        # the double buffer's turn and its copy in flight pass from one
        # slot's grid step to the next: the steps run in order. Every
        # index the kernel reads by is in range by construction (the
        # page lists and the layer are clipped into the pool here, the
        # slot order and positions are the call's own), so Mosaic's
        # run-time checks are off: on a v5e they cost the scalar core
        # 0.38 us a group of copies (PERF.md section 6).
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), disable_bounds_checks=True),
        out_shape=_sds((B, Q * H, kv_rank), q.dtype, q, pool),
        interpret=interpret, name=KERNEL_NAME,
    )(pages, qpos.reshape(B * Q), order.astype(jnp.int32),
      jnp.sum(alive).astype(jnp.int32).reshape(1),
      jnp.clip(jnp.asarray(layer, jnp.int32), 0, L - 1).reshape(1),
      q.reshape(B, Q * H, C), pool)
    return out.reshape(B, Q, H, kv_rank)
