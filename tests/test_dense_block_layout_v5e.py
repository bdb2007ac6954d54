"""The dense (Mistral) and sparse (Mixtral) blocks' decode tick and
whole-prompt prefill, compiled at the published widths (two layers)
for a described v5e with no chip attached: every projection reads its
layer's weight where it lies in the scanned stack, so the optimised HLO
holds no slice and no copy of a layer weight's size. Left free, XLA
lays q out head-major for the rotary and the attention kernels and
copies ``wq`` (and ``wk``) transposed out of the stack every layer of
every step (``transformer.head_proj``).

The topology is described inside a module-scoped fixture, after
collection (the fixtures are those of tests/test_paged_decode_v5e.py).
The projections' CPU parity cases live with their models' tests, which
the fast tier skips; it collects them here as well."""

import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest

from tests.test_moe import TestMoEHeadProj  # noqa: F401
from tests.test_transformer import TestHeadProj  # noqa: F401

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


@pytest.fixture()
def chip_dispatch(monkeypatch):
    """The program picks its attention (paged decode kernel, flash
    prefill) by ``jax.default_backend()``, which names the CPU here:
    compile what the chip runs."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _mistral():
    from tpushare.models import transformer
    cfg = transformer.TransformerConfig(
        vocab_size=32000, d_model=4096, n_layers=2, n_heads=32,
        n_kv_heads=8, head_dim=128, d_ff=14336, rope_base=1e6,
        norm_eps=1e-5, tie_embeddings=False, dtype=jnp.bfloat16,
        remat=False)
    return cfg, transformer.init_params, None


def _mixtral():
    from tpushare.models import moe
    cfg = moe.MoEConfig(
        vocab_size=32000, d_model=4096, n_layers=2, n_heads=32,
        n_kv_heads=8, head_dim=128, d_ff=14336, n_experts=8, top_k=2,
        rope_base=1e6, norm_eps=1e-5, tie_embeddings=False,
        dtype=jnp.bfloat16, remat=False)
    return cfg, moe.init_params, moe.paged_forward


#: model -> (its config, params and forward; the slots of its chat cell)
MODELS = {"mistral7b": (_mistral, 32), "mixtral8x7b": (_mixtral, 16)}


def _compiled(model, program, sds):
    from tpushare.models import paged, transformer
    build, slots = MODELS[model]
    cfg, init, forward_fn = build()
    params = jax.eval_shape(lambda: init(jax.random.PRNGKey(0), cfg))
    p = jax.tree.map(lambda a: sds(a.shape, a.dtype), params)
    L, i32 = cfg.n_layers, jnp.int32
    if program == "decode":
        f = jax.jit(functools.partial(
            paged.tick_decode, cfg=cfg, block_size=16,
            forward_fn=forward_fn), donate_argnums=(2, 3))
        pool = sds((L, 256, 16, 1024), jnp.bfloat16)
        args = (p, sds((slots, 1), i32), pool, pool, sds((slots, 128), i32),
                sds((slots,), i32), sds((slots,), jnp.bool_),
                sds((slots, 1), i32))
        return params, f.lower(*args).compile()
    row = sds((L, 1, 1024, 8, 128), jnp.bfloat16)
    f = jax.jit(functools.partial(forward_fn or transformer.forward,
                                  cfg=cfg))
    return params, f.lower(p, sds((1, 512), i32), cache={"k": row, "v": row},
                           pos_offset=sds((), i32)).compile()


_RESULT = re.compile(r"^\s*%(\S+) = \w+\[([\d,]*)\]")


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("model", MODELS)
def test_no_layer_weight_is_copied_out_of_the_stack(
        model, program, one_chip, no_compile_cache, chip_dispatch):
    """A slice (``dynamic-slice_fusion``) or a copy whose result is one
    layer's weight, ones dropped: ``bf16[1,4096,4096]`` for ``wq`` and
    ``bf16[1,4096,1024]`` for ``wk``, each a slice and a copy a layer,
    when the projections are a plain ``h @ w``."""
    params, compiled = _compiled(model, program, lambda shape, dtype:
                                 jax.ShapeDtypeStruct(shape, dtype,
                                                      sharding=one_chip))
    weights = {tuple(a.shape[1:]) for a in jax.tree.leaves(params["layers"])
               if a.ndim == 3}
    assert {(4096, 4096), (4096, 1024)} <= weights
    moved = []
    for line in compiled.as_text().splitlines():
        m = _RESULT.match(line)
        if m and (m.group(1).startswith("copy")
                  or "dynamic-slice_fusion" in m.group(1)):
            dims = tuple(int(d) for d in m.group(2).split(",")
                         if d and d != "1")
            if dims in weights:
                moved.append(line.strip()[:120])
    assert not moved, moved
