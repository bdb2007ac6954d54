"""The paged decode kernel at the benchmark's three pools, compiled by
Mosaic for a described v5e (no chip attached), and what a call costs
read off its structure: one grid step a slot and, inside it, a loop
whose bounds are the slot's live groups, not the table's width; and the
kernel's cases against the gathered reference, for the fast tier.

The topology is described inside a module-scoped fixture, after
collection (on-chip-measurement guide, 2; the fixtures are those of
tests/benchmark/test_tpubench_compile_v5e.py)."""

import importlib
import os

import jax
import jax.numpy as jnp
import pytest

# The kernel against the gathered reference (interpret mode): the cases
# are tests/test_ops.py's, which conftest.py keeps in the slow tier with
# every other ops test. The decode kernel is every dense cell's hot path,
# so the fast tier collects that one class here as well.
from tests.test_ops import TestPagedFlashDecode  # noqa: E402,F401

# the module: ``tpushare.ops`` re-exports a function of the same name
fa = importlib.import_module("tpushare.ops.flash_attention")

os.environ.setdefault("TPU_LOG_DIR", "disabled")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


#: cell -> (slots, stacked pool [L, nb, bs, Hkv*D], blocks a slot, dtype)
POOLS = {
    "mistral7b-l16.chat": (32, (16, 3072, 16, 1024), 128, jnp.bfloat16),
    "mistral7b-l16.docqa": (6, (16, 2048, 16, 1024), 272, jnp.bfloat16),
    "mixtral8x7b-l4.chat-batch": (16, (4, 4096, 16, 1024), 128,
                                  jnp.bfloat16),
    # no cell: a kv_quant pool at the int8 kernel's crossover (8k a slot)
    "int8-stack": (8, (4, 1024, 128, 1024), 64, jnp.int8),
}


def _call(pool_name, sds):
    """(function of the kernel's operands with ``layer`` traced, its
    operands as ``sds(shape, dtype)``) at one of POOLS."""
    slots, stack, mb, dtype = POOLS[pool_name]
    args = [sds((slots, 1, 32, 128), jnp.bfloat16), sds(stack, dtype),
            sds(stack, dtype), sds((slots, mb), jnp.int32),
            sds((slots,), jnp.int32), sds((), jnp.int32)]
    if dtype == jnp.int8:
        L, nb, bs, _ = stack
        args += [sds((L, nb, 8, bs), jnp.float32)] * 2

    def call(q, pk, pv, table, pos, layer, *scales):
        kw = dict(zip(("k_scale", "v_scale"), scales))
        return fa.paged_flash_decode(q, pk, pv, table, pos, layer=layer,
                                     window=jnp.int32(0), **kw)
    return call, args


@pytest.mark.parametrize("pool", POOLS)
def test_the_kernel_compiles_for_a_v5e_at_the_cells_pools(
        pool, one_chip, no_compile_cache):
    call, args = _call(pool, lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip))
    compiled = jax.jit(call).lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    # the stack is read where it lies: no layer of it, and no gathered
    # view of a slot, is a temporary of the call
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def _eqns(jaxpr, name):
    return [e for e in jaxpr.eqns if e.primitive.name == name]


@pytest.mark.parametrize("pool", ["mistral7b-l16.chat",
                                  "mistral7b-l16.docqa"])
def test_a_call_costs_its_live_groups(pool):
    """The grid is (slots,) whatever the table's width; the kernel's
    one top-level loop has traced bounds (a ``while``: no loop of the
    table's length), and those bounds, ``_live_groups``, are the
    groups that hold a live page."""
    slots, stack, mb, _ = POOLS[pool]
    call, args = _call(pool, jax.ShapeDtypeStruct)
    (pc,) = _eqns(jax.make_jaxpr(call)(*args).jaxpr.eqns[0]
                  .params["jaxpr"].jaxpr, "pallas_call")
    assert tuple(pc.params["grid_mapping"].grid) == (slots,)
    kernel = pc.params["jaxpr"]
    assert len(_eqns(kernel, "while")) == 1 and not _eqns(kernel, "scan")
    bs = stack[2]
    group = fa._decode_group_pages(bs, mb, stack[3] * 2)
    assert group * bs == fa.DECODE_GROUP_KEYS == 256

    def steps(pos, window=0):
        _, _, g_lo, g_hi = fa._live_groups(pos, window, bs, mb, group)
        return int(g_hi - g_lo)
    cap = mb * bs
    for pos in (0, 15, 16, 255, 256, 400, 2047, cap - 1, cap + 40):
        live_pages = min(pos, cap - 1) // bs + 1
        assert steps(pos) == -(-live_pages // group), pos
    # chat at its peak (ledger, PR 28: 18.5 % of 3,072 blocks live):
    # 16 slots at 25-30 pages and 16 idle cost 48 steps where the
    # one-page-a-step grid took slots x mb = 4,096
    assert sum(steps(p) for p in [0] * 16 + [430] * 16) == 48
    # behind a window a slot costs its window's groups, at most one
    # more than the window's length in groups
    assert steps(cap - 1, window=256) <= 2
    assert steps(cap - 1, window=1024) <= 5
