"""The paged forward writes KV rows into the stacked pools where they
lie: the pools are the layer loop's carry, donated into the jitted step,
so a decode or fused program holds one pool generation and copies none.

Three checks, none of which needs a chip: (1) the server's own jitted
programs, compiled on the CPU, have temporaries far under one pool and
alias both pools to their arguments; (2) the decode program at the chat
cell's shapes, compiled for a described v5e, holds no pool-sized copy,
reshape, dynamic-slice or dynamic-update-slice; (3) decode ticks touch
only the blocks the live tables name, and the kernel reading the stack at
layer ``l`` gives bit for bit what the per-layer call gives.

The v5e topology is described inside a module-scoped fixture, after
collection (on-chip-measurement guide, 2; the fixtures mirror
tests/benchmark/test_tpubench_compile_v5e.py)."""

import functools
import importlib
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpushare.models import moe, paged
from tpushare.models import transformer as tf

# the module: ``tpushare.ops`` re-exports a function of the same name
fa = importlib.import_module("tpushare.ops.flash_attention")

os.environ.setdefault("TPU_LOG_DIR", "disabled")

N_SLOTS, N_BLOCKS, BS, MB = 4, 2048, 16, 8


def _family(sparse: bool):
    """(params as shapes, cfg, forward_fn) of a tiny model whose pool
    dwarfs its activations, as a deployment's does. Full-precision
    pools are float32 here and bf16 on the chip: XLA's CPU backend
    widens a bf16 scatter's whole operand to float32 and back, which
    the chip's does not (the v5e test below compiles bf16)."""
    if sparse:
        cfg = moe.tiny(n_kv_heads=2, head_dim=32, remat=False)
        init, fwd = moe.init_params, moe.paged_forward
    else:
        cfg = tf.tiny(n_kv_heads=2, head_dim=32)
        init, fwd = tf.init_params, None
    params = jax.eval_shape(lambda k: init(k, cfg), jax.random.PRNGKey(0))
    return params, cfg, fwd


def _lowered(srv, program: str):
    """The server's own jitted program, lowered at its own shapes: a
    plain tick's (block growth riding it), a fused tick's, or a
    speculative round's verify."""
    c = srv.cache
    grow = np.full((c.n_slots, 1), -1, np.int32)
    fn, width, tail = {
        "decode": (srv._decode, 1, (grow,)),
        "fused": (srv._fused, 1, (grow, np.zeros((4,), np.int32),
                                  np.int32(0), np.int32(0), np.int32(4))),
        "verify": (srv._verify, 4, ()),
    }[program]
    return fn.lower(srv.params, jnp.zeros((c.n_slots, width), jnp.int32),
                    c.pool_k, c.pool_v, c.block_table, c.lengths,
                    jnp.ones((c.n_slots,), bool), *tail,
                    pool_k_scale=c.pool_k_scale,
                    pool_v_scale=c.pool_v_scale)


@pytest.mark.parametrize("program", ("decode", "fused", "verify"))
@pytest.mark.parametrize("family,kv_quant", (
    ("dense", False), ("dense", True), ("sparse", False)),
    ids=("dense-fp", "dense-kvq", "sparse-fp"))
def test_a_paged_program_holds_one_pool_generation(family, kv_quant,
                                                   program):
    """temp bytes under half of ONE pool (two generations would be
    four pools' worth), and where the backend reports aliasing, every
    pool leaf aliased to its argument. kv_quant with a forward_fn is
    refused by the server, so sparse has no int8 case."""
    params, cfg, fwd = _family(family == "sparse")
    srv = paged.PagedSlotServer(
        jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), params),
        cfg, n_slots=N_SLOTS, n_blocks=N_BLOCKS, block_size=BS,
        max_blocks_per_slot=MB, kv_quant=kv_quant, forward_fn=fwd)
    c = srv.cache
    assert c.pool_k.shape == (cfg.n_layers, N_BLOCKS, BS,
                              cfg.n_kv_heads * cfg.head_dim)
    ma = _lowered(srv, program).compile().memory_analysis()
    pool = c.pool_k.nbytes
    assert ma.temp_size_in_bytes < pool // 2, (ma, pool)
    leaves = 2 * pool + (2 * c.pool_k_scale.nbytes if kv_quant else 0)
    if ma.alias_size_in_bytes:
        assert ma.alias_size_in_bytes >= leaves, (ma, leaves)


# -- (2) the chat cell's decode program for a described v5e ----------------

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


#: ``%name = bf16[16,3072,16,1024]{layout} opcode(`` of optimized HLO
_HLO_OP = re.compile(r"= \w+\[([\d,]+)\]\S* ([\w\-]+)\(")


def pool_sized_moves(hlo: str, layer_elems: int, n_layers: int):
    """(opcode, shape) of every copy, reshape, dynamic-slice and
    dynamic-update-slice whose result has the element count of one
    layer of a pool or of a whole pool: what the layer scan's xs/ys
    form left in the program."""
    found = []
    for m in _HLO_OP.finditer(hlo):
        n = int(np.prod([int(d) for d in m[1].split(",")]))
        if (m[2] in ("copy", "reshape", "dynamic-slice",
                     "dynamic-update-slice")
                and n in (layer_elems, n_layers * layer_elems)):
            found.append((m[2], m[1]))
    return found


@pytest.mark.parametrize("cell", ("mistral7b-l16.chat",
                                  "mixtral8x7b-l4.chat-batch"))
def test_the_chat_decode_program_moves_no_pool_on_a_v5e(
        cell, one_chip, no_compile_cache, monkeypatch):
    """The decode step of both chat cells (block growth riding it) at
    their real widths and pools, compiled by the chip's own compiler
    with the paged kernel on its path (the dispatch asks the backend, which is the CPU here: the
    test answers for it)."""
    sparse = cell.startswith("mixtral")
    kw = dict(vocab_size=32000, d_model=4096, n_heads=32, n_kv_heads=8,
              head_dim=128, d_ff=14336, rope_base=1e6, norm_eps=1e-5,
              tie_embeddings=False, dtype=jnp.bfloat16)
    if sparse:
        cfg = moe.MoEConfig(n_layers=4, n_experts=8, top_k=2, **kw)
        mod, fwd, slots, n_blocks = moe, moe.paged_forward, 16, 4096
    else:
        cfg = tf.TransformerConfig(n_layers=16, **kw)
        mod, fwd, slots, n_blocks = tf, None, 32, 3072
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv(fa.DECODE_KERNEL_ENV, raising=False)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    params = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda k: mod.init_params(k, cfg),
                       jax.random.PRNGKey(0)))
    pool = sds((cfg.n_layers, n_blocks, 16, 8 * 128), jnp.bfloat16)
    step = jax.jit(paged._program(
        "paged_decode", paged.tick_decode, cfg=cfg, block_size=16,
        forward_fn=fwd), donate_argnums=(2, 3))
    compiled = step.lower(
        params, sds((slots, 1), jnp.int32), pool, pool,
        sds((slots, 128), jnp.int32), sds((slots,), jnp.int32),
        sds((slots,), jnp.bool_), sds((slots, 1), jnp.int32)).compile()
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") >= 1
    one_layer = n_blocks * 16 * 8 * 128
    assert pool_sized_moves(hlo, one_layer, cfg.n_layers) == []
    ma = compiled.memory_analysis()
    pool_bytes = 2 * cfg.n_layers * one_layer
    assert ma.alias_size_in_bytes >= 2 * pool_bytes
    assert ma.temp_size_in_bytes < pool_bytes // 2


def test_the_reader_of_pool_sized_moves_finds_the_scan_form():
    """The HLO reader on lines of the form the xs/ys scan compiled to
    (PERF_LEDGER, PR 25, breakdown.device_ops) and on ones it must
    pass: an in-place scatter fusion, a parameter, a small slice."""
    hlo = """
  %copy.62 = bf16[16,3072,16,8,128]{4,3,2,1,0:T(8,128)(2,1)} copy(%p)
  %reshape.718 = bf16[3072,16,1024]{2,1,0:T(8,128)(2,1)} reshape(%x)
  %dynamic-slice.5 = bf16[1,3072,16,8,128]{4,3,2,1,0} dynamic-slice(%a, %i)
  %dus.4 = bf16[16,3072,16,1024]{3,2,1,0} dynamic-update-slice(%a, %b, %i)
  %fusion.167 = bf16[16,3072,16,1024]{3,2,1,0:T(8,128)(2,1)} fusion(%a)
  %pool_k.1 = bf16[16,3072,16,1024]{3,2,1,0} parameter(2)
  %dynamic-slice.9 = bf16[1,4096,14336]{2,1,0} dynamic-slice(%w, %i)
  %copy.3 = bf16[32,1,4096]{2,1,0} copy(%h)
"""
    got = pool_sized_moves(hlo, 3072 * 16 * 8 * 128, 16)
    assert [op for op, _ in got] == [
        "copy", "reshape", "dynamic-slice", "dynamic-update-slice"]


# -- (3) same rows to the same places, bit for bit -------------------------

CFG = tf.tiny(n_kv_heads=2, head_dim=32)
PARAMS = tf.init_params(jax.random.PRNGKey(3), CFG)


def _prompt(seed: int, n: int):
    return jnp.asarray(np.random.default_rng(seed).integers(
        1, CFG.vocab_size, n), jnp.int32)


_PAGED_FLASH_DECODE = fa.paged_flash_decode      # before any test patches it


def _kernel_route(monkeypatch, per_layer: bool):
    """Put the paged decode kernel (interpreter) on the forward's path:
    reading the stack at ``layer``, as the forward calls it, or through
    the per-layer call the benchmark's compile test keeps."""
    real = _PAGED_FLASH_DECODE

    def route(q, pk, pv, table, pos, *, layer, **kw):
        if per_layer:
            D = q.shape[-1]
            pk, pv = (p[layer].reshape(*p.shape[1:3], -1, D)
                      for p in (pk, pv))
            layer = None
        return real(q, pk, pv, table, pos, layer=layer, interpret=True,
                    **kw)
    monkeypatch.setattr(fa, "paged_flash_decode", route)
    monkeypatch.setattr(fa, "paged_decode_eligible",
                        lambda *a, **kw: True)


def _run_ticks(n_ticks: int):
    """A slot that grows over a block boundary, one that reaches its
    capacity and retires mid-run, two never admitted. Returns (pool_k
    and pool_v before and after, tokens a tick, the server)."""
    srv = paged.PagedSlotServer(PARAMS, CFG, n_slots=4, n_blocks=24,
                                block_size=8, max_blocks_per_slot=3)
    grow = srv.admit(_prompt(0, 6))        # crosses 8 at the 2nd tick
    full = srv.admit(_prompt(1, 21))       # capacity 24: 3 ticks left
    before = [np.asarray(srv.cache.pool_k), np.asarray(srv.cache.pool_v)]
    tokens = [srv.step() for _ in range(n_ticks)]
    assert not srv.active[full] and srv.active[grow]
    assert int(srv.cache.host_lengths()[grow]) == 6 + n_ticks
    after = [np.asarray(srv.cache.pool_k), np.asarray(srv.cache.pool_v)]
    return before, after, tokens, srv


@pytest.mark.parametrize("route", ("gathered", "kernel"))
def test_decode_ticks_touch_only_the_blocks_live_tables_name(
        route, monkeypatch):
    if route == "kernel":
        _kernel_route(monkeypatch, per_layer=False)
    before, after, tokens, srv = _run_ticks(5)
    assert [sorted(t) for t in tokens] == [[0, 1]] * 3 + [[0]] * 2
    table = srv.cache.host_table()
    named = set(int(b) for b in table[table >= 0])
    trash = srv.cache.pool_k.shape[1] - 1
    assert len(named) == 2 + 3 and trash not in named
    others = [b for b in range(trash) if b not in named]
    for b4, aft in zip(before, after):
        np.testing.assert_array_equal(aft[:, others], b4[:, others])
        assert (aft[:, sorted(named)] != b4[:, sorted(named)]).any()


def test_the_stack_read_at_a_layer_is_the_per_layer_call(monkeypatch):
    """Tokens and pools of several ticks through the kernel reading the
    stacked pool at ``layer`` equal, bit for bit, those through the
    per-layer call ``paged_flash_decode(q, pool_k[nb,bs,Hkv,D], ...)``."""
    _kernel_route(monkeypatch, per_layer=False)
    _, stacked_pools, stacked_tokens, _ = _run_ticks(5)
    _kernel_route(monkeypatch, per_layer=True)
    _, layer_pools, layer_tokens, _ = _run_ticks(5)
    assert stacked_tokens == layer_tokens
    for a, b in zip(stacked_pools, layer_pools):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("kernel", ("decode", "verify"))
def test_a_paged_kernel_reads_any_layer_of_a_stack(kernel):
    """Direct: the kernel over [L, nb, bs, Hkv*D] at layer l against the
    same kernel over that layer alone, heads apart."""
    L, nb, bs, Hkv, D, B, mb = 3, 12, 8, 2, 32, 2, 4
    rng = np.random.default_rng(5)
    pk, pv = (jnp.asarray(rng.normal(size=(L, nb, bs, Hkv * D)),
                          jnp.float32) for _ in range(2))
    table = jnp.asarray(rng.permutation(nb - 1)[:B * mb].reshape(B, mb),
                        jnp.int32)
    pos = jnp.asarray([13, 22], jnp.int32)
    sq = 1 if kernel == "decode" else 3
    q = jnp.asarray(rng.normal(size=(B, sq, 4, D)), jnp.float32)
    fn = functools.partial(
        fa.paged_flash_decode if kernel == "decode"
        else fa.paged_flash_verify, interpret=True)
    for l in range(L):
        got = fn(q, pk, pv, table, pos, layer=jnp.int32(l))
        want = fn(q, pk[l].reshape(nb, bs, Hkv, D),
                  pv[l].reshape(nb, bs, Hkv, D), table, pos)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
