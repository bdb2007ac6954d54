"""The paged latent decode kernel (``ops/latent_decode``) under the
Pallas interpreter against the ``jnp`` body of ``latent._decode_all``
(what every call takes on the CPU): Q = 1 and 2, ragged lengths, a dead
slot, a dead query beside a live one, stale rows past a position, a
shared prefix block, whole groups past a slot's live pages, and the
eligibility predicate's truth table.

Under the interpreter a DMA semaphore counts elements in int16
(``SEMAPHORE_MAX_VALUE``, 32,767) and the kernel waits once for a whole
tile, so every tile here stays under that count: six pages of 16 rows of
256 are 24,576 elements, a group of two such pages 8,192."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpushare.models import latent
from tpushare.ops import latent_decode
from tpushare.ops.latent_decode import (latent_decode_eligible,
                                        latent_paged_decode)

BS, H, L, LAYER = 16, 8, 3, 1


def _cfg(key_dim: int):
    """A toy whose full layers have ``key_dim``-wide rows (the rank is
    the row less one lane tile's worth of rope and padding)."""
    rank = key_dim - 128 if key_dim > 128 else 128
    return latent.AttnDims(H, 32, rank, 24, 8 if key_dim > 128 else 0, 8,
                           1e4, row_align=128)


def _config(dims):
    return dataclasses.replace(latent.tiny(), full=dims, dtype=jnp.bfloat16,
                               selector=False)


def _case(key_dim: int, n_q: int, lengths, *, mb: int = 6, seed: int = 0,
          shared: bool = False):
    """Queries, a bf16 pool of random rows, a block table of distinct
    (or, ``shared``, a first block common to slots 0 and 1) blocks, and
    positions: query j of slot b at lengths[b] + j."""
    B = len(lengths)
    nb = B * mb + 2
    kq, kp = jax.random.split(jax.random.PRNGKey(seed))
    q = jax.random.normal(kq, (B, n_q, H, key_dim), jnp.bfloat16)
    pool = jax.random.normal(kp, (L, nb, BS, key_dim), jnp.bfloat16)
    table = (1 + np.arange(B)[:, None] * mb + np.arange(mb)[None, :]
             ).astype(np.int32)
    if shared:
        table[1, 0] = table[0, 0]
    pos = np.asarray(lengths, np.int32)[:, None] + np.arange(n_q)[None, :]
    return q, pool, table, pos


def _both(dims, q, pool, table, pos, live):
    cfg = _config(dims)
    tb = jnp.maximum(jnp.asarray(table), 0)
    want = latent._decode_all(pool, LAYER, tb, jnp.asarray(pos),
                              jnp.asarray(live), q, cfg)
    got = latent_paged_decode(
        q, pool, jnp.asarray(table), jnp.asarray(pos), jnp.asarray(live),
        layer=LAYER, kv_rank=dims.kv_rank,
        scale=1.0 / math.sqrt(dims.nope + dims.rope), interpret=True)
    return np.asarray(got, np.float32), np.asarray(want, np.float32)


def _close(got, want, live):
    assert got.shape == want.shape
    live = np.asarray(live)
    # bf16 outputs of unit-variance rows: the two differ in where the
    # weights are rounded (normalised or not), a few bf16 steps
    np.testing.assert_allclose(got[live], want[live], atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize("key_dim", [128, 256])
@pytest.mark.parametrize("n_q", [1, 2])
def test_ragged_slots_agree_with_the_jnp_body(key_dim, n_q):
    """One slot holds a single row, one ends in its table's last block,
    the others between; more than one group of pages a slot."""
    dims = _cfg(key_dim)
    mb = 6
    lengths = [0, mb * BS - n_q, 37, 16]
    q, pool, table, pos = _case(key_dim, n_q, lengths, mb=mb)
    live = np.ones(pos.shape, bool)
    got, want = _both(dims, q, pool, table, pos, live)
    _close(got, want, live)


def _unallocated_past_live(table, pos):
    """-1 in every entry of a slot's table past its live pages."""
    table = table.copy()
    for b in range(table.shape[0]):
        table[b, pos[b].max() // BS + 1:] = -1
    return table


GROUPS = [
    # mb, lengths, -1 past the live pages
    ("five live pages of six", 6, [70, 3, 95, 33], False),
    ("a table of five pages, the last group past it", 5, [78, 3, 60, 33],
     False),
    ("live pages ending one page into a group", 6, [32, 33, 38, 46], False),
    ("-1 past the live pages", 5, [78, 3, 40, 16], True),
]


@pytest.mark.parametrize("case", GROUPS, ids=[c[0] for c in GROUPS])
def test_the_loop_runs_in_groups_and_the_last_may_be_short(case,
                                                           monkeypatch):
    """A group of two pages: a slot's last group may reach past its live
    pages, past its table's width, or onto -1 entries. It is copied
    whole all the same, its pages there the slot's last live page again,
    whose rows lie past every query's position."""
    _, mb, lengths, unallocated = case
    monkeypatch.setattr(latent_decode, "LATENT_GROUP_KEYS", 2 * BS)
    dims = _cfg(256)
    q, pool, table, pos = _case(256, 2, lengths, mb=mb, seed=3)
    if unallocated:
        table = _unallocated_past_live(table, pos)
    live = np.ones(pos.shape, bool)
    got, want = _both(dims, q, pool, table, pos, live)
    _close(got, want, live)


@pytest.mark.parametrize("group_keys", [2 * BS, 512])
@pytest.mark.parametrize("unallocated", [False, True])
def test_a_page_past_the_live_ones_never_reads_a_foreign_block(
        group_keys, unallocated, monkeypatch):
    """NaN in block 0 and in every block no slot holds live (a slot's
    own blocks past its live pages among them, or -1 entries there):
    the clamp copies none of them, so every output is finite and what
    the ``jnp`` body gives over the clean pool."""
    monkeypatch.setattr(latent_decode, "LATENT_GROUP_KEYS", group_keys)
    dims = _cfg(256)
    q, pool, table, pos = _case(256, 2, [78, 3, 40, 16], mb=5, seed=7)
    if unallocated:
        table = _unallocated_past_live(table, pos)
    live = np.ones(pos.shape, bool)
    held = {int(table[b, p]) for b in range(table.shape[0])
            for p in range(pos[b].max() // BS + 1)}
    poisoned = np.asarray(pool, np.float32)
    for blk in range(pool.shape[1]):
        if blk not in held:
            poisoned[:, blk] = np.nan
    assert 0 not in held
    clean, want = _both(dims, q, pool, table, pos, live)
    got, _ = _both(dims, q, jnp.asarray(poisoned, jnp.bfloat16), table, pos,
                   live)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, clean)
    _close(got, want, live)


@pytest.mark.parametrize("dead", [[1], [0, 3], [0, 1, 2, 3]])
def test_a_dead_slot_costs_nothing_and_reads_zero(dead):
    """A slot with no live query, its table all -1: skipped (its pages
    are never copied: the table would send them to block 0), zeros out;
    the live slots agree whatever their place among the dead."""
    dims = _cfg(256)
    q, pool, table, pos = _case(256, 2, [20, 40, 5, 60], seed=1)
    live = np.ones(pos.shape, bool)
    for b in dead:
        live[b] = False
        table[b] = -1
    got, want = _both(dims, q, pool, table, pos, live)
    _close(got, want, live)
    assert not got[~live].any()


def test_a_dead_query_beside_a_live_one():
    """The module's pass of a slot that committed one position: its
    second query is not live; the first is served, the second zero."""
    dims = _cfg(256)
    q, pool, table, pos = _case(256, 2, [31, 47, 0], seed=2)
    live = np.asarray([[True, False], [True, True], [True, False]])
    got, want = _both(dims, q, pool, table, pos, live)
    _close(got, want, live)
    assert not got[~live].any()


def test_a_stale_row_past_a_position_is_never_attended():
    """What a rejected draft left past a query's position: garbage
    there (as large as bf16 holds: what a row can be, never NaN) leaves
    every live output as it was."""
    dims = _cfg(256)
    q, pool, table, pos = _case(256, 2, [20, 45], seed=4)
    live = np.ones(pos.shape, bool)
    clean, _ = _both(dims, q, pool, table, pos, live)
    dirty = np.asarray(pool, np.float32)
    for b in range(2):
        for p in range(pos[b].max() + 1, table.shape[1] * BS):
            dirty[LAYER, table[b, p // BS], p % BS] = (
                -3e38 if p % 2 else 3e38)
    got, want = _both(dims, q, jnp.asarray(dirty, jnp.bfloat16), table, pos,
                      live)
    np.testing.assert_array_equal(got, clean)
    # the first query's own mask hides the second's row too
    assert np.isfinite(got).all()


def test_a_shared_prefix_block_is_read_by_both_slots():
    dims = _cfg(256)
    q, pool, table, pos = _case(256, 2, [40, 52, 9], seed=5, shared=True)
    live = np.ones(pos.shape, bool)
    got, want = _both(dims, q, pool, table, pos, live)
    _close(got, want, live)
    # and it matters: the same call with slot 1 on a block of its own
    own = table.copy()
    own[1, 0] = table.max() + 1
    other, _ = _both(dims, q, pool, own, pos, live)
    assert np.abs(other[1] - got[1]).max() > 1e-2
    np.testing.assert_array_equal(other[0], got[0])


def test_another_layer_of_the_stack_is_another_answer():
    dims = _cfg(128)
    q, pool, table, pos = _case(128, 1, [25, 30], seed=6)
    live = np.ones(pos.shape, bool)
    got, want = _both(dims, q, pool, table, pos, live)
    _close(got, want, live)
    at0 = latent_paged_decode(
        q, pool, jnp.asarray(table), jnp.asarray(pos), jnp.asarray(live),
        layer=0, kv_rank=dims.kv_rank, scale=1.0, interpret=True)
    assert np.abs(np.asarray(at0, np.float32) - got).max() > 1e-2


def _sds(shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


ELIGIBLE = [
    # q, pool, kv_rank, on a TPU
    ("the pangu cell", (16, 2, 128, 640), (6, 64, 16, 640), 512,
     jnp.bfloat16, jnp.bfloat16, True),
    ("one query a slot", (16, 1, 128, 640), (6, 64, 16, 640), 512,
     jnp.bfloat16, jnp.bfloat16, True),
    ("pages under a bf16 tile", (16, 2, 128, 640), (6, 64, 8, 640), 512,
     jnp.bfloat16, jnp.bfloat16, False),
    ("float32 pages of 8 rows", (4, 2, 8, 128), (2, 9, 8, 128), 128,
     jnp.float32, jnp.float32, True),
    ("a row that is no lane tile", (6, 2, 4, 32), (3, 9, 16, 32), 16,
     jnp.float32, jnp.float32, False),
    ("a rank that is no lane tile", (16, 2, 128, 640), (6, 64, 16, 640),
     576, jnp.bfloat16, jnp.bfloat16, False),
    ("query rows under a sublane tile", (4, 1, 4, 128), (2, 9, 16, 128),
     128, jnp.bfloat16, jnp.bfloat16, False),
    ("queries and pool of two dtypes", (16, 2, 128, 640), (6, 64, 16, 640),
     512, jnp.float32, jnp.bfloat16, False),
    ("rows of two widths", (16, 2, 128, 640), (6, 64, 16, 768), 512,
     jnp.bfloat16, jnp.bfloat16, False),
]


@pytest.mark.parametrize("case", ELIGIBLE, ids=[c[0] for c in ELIGIBLE])
def test_the_eligibility_predicates_truth_table(case, monkeypatch):
    _, qs, ps, rank, qd, pd, on_tpu = case
    q, pool = _sds(qs, qd), _sds(ps, pd)
    # here: the CPU, whatever the shapes
    assert jax.default_backend() == "cpu"
    assert not latent_decode_eligible(q, pool, rank)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert latent_decode_eligible(q, pool, rank) is on_tpu


def test_the_group_follows_what_the_call_sees():
    pages = latent_decode._latent_group_pages
    assert pages(16, 1046, 1280) == 32          # 512 keys of the cell's rows
    assert pages(16, 5, 1280) == 5              # never more than a slot has
    assert pages(128, 64, 1280) == 4
    assert pages(16, 64, 256 * 1024) == 1       # never under one
    # a row so wide that a tile of 512 keys would pass a MiB
    assert pages(16, 64, 8192) == 8


def test_the_server_counts_no_kernel_call_on_the_cpu():
    cfg = _config(_cfg(256))
    pool = _sds((3, 9, 16, 256))
    assert not latent.decode_kernel_serves(cfg, 4, 2, pool)
