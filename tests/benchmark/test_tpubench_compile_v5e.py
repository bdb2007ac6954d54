"""The kernels of the benchmark's main path, compiled at the benchmark's
widths for a described v5e (no chip attached): what Mosaic would refuse
on the chip it refuses here, on every later PR, at no chip time. The
topology is described inside a module-scoped fixture, after collection,
so every xdist worker collects the same tests and only the one that
runs this file loads the TPU's library (on-chip-measurement guide, 2)."""

import os

import pytest

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
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


def _sds(shape, dtype, sharding):
    import jax
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_flash_attention_at_the_prefill_bucket(one_chip, no_compile_cache):
    """[1, 512, 32, 128] against 8 KV heads: the 512-token whole-prompt
    admission of the Mistral block."""
    import jax
    import jax.numpy as jnp
    from tpushare.ops.flash_attention import flash_attention
    q = _sds((1, 512, 32, 128), jnp.bfloat16, one_chip)
    kv = _sds((1, 512, 8, 128), jnp.bfloat16, one_chip)
    compiled = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True)).lower(q, kv, kv).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 1


def test_paged_flash_decode_at_the_chat_pool(one_chip, no_compile_cache):
    """32 slots over a pool of [4096, 16, 8, 128] with 128 blocks a
    slot: the decode step's attention in the chat cells."""
    import jax
    import jax.numpy as jnp
    from tpushare.ops.flash_attention import paged_flash_decode
    q = _sds((32, 1, 32, 128), jnp.bfloat16, one_chip)
    pool = _sds((4096, 16, 8, 128), jnp.bfloat16, one_chip)
    table = _sds((32, 128), jnp.int32, one_chip)
    pos = _sds((32,), jnp.int32, one_chip)
    compiled = jax.jit(lambda q, pk, pv, t, p: paged_flash_decode(
        q, pk, pv, t, p)).lower(q, pool, pool, table, pos).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 1
