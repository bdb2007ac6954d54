"""Test env: force JAX onto a virtual 8-device CPU mesh before any jax
import, so sharding tests run hardware-free (SURVEY.md §4's fixture
strategy; the reference has no hardware-free path at all)."""

import os
import sys

# Hard-set (not setdefault): tests are deterministic and hardware-free
# whatever the session env says.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Persistent XLA compile cache for THIS process only (the helper's
# rule: JAX_COMPILATION_CACHE_DIR wins when set; else the fixed
# directory inside the checkout, under the suite's own lane — XLA:CPU
# entries are AOT code for this machine and must not mix with the
# chip's). Why: the full suite compiles ~500 XLA:CPU programs in one
# process, and past ~90% of them the CPU compiler was observed
# segfaulting (reproduced three times at the same test; no single
# module triggers it — both alphabetical halves pass alone). With the
# cache, warm runs compile almost nothing, and even a crashed cold run
# banks every entry up to the crash, so reruns self-heal past it.
# Deliberately jax.config-only, NOT os.environ: the env var would leak
# into every subprocess tests spawn (serve CLI, dryruns), where the
# cache's serialize-on-write stalled the serve engine's first compile
# past its test's 120s timeout.
from tpushare.utils.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache(lane="cpu-tests")
# Cache EVERY entry: the accumulation risk is compile count, and the
# suite's compiles are mostly small ones the default 1s/min-size
# thresholds would keep recompiling forever.
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


import pytest  # noqa: E402

# Two-tier gate (VERDICT r2 item 7). The fast tier — ``pytest -m "not
# slow"`` — is the full reference-parity plugin core (allocate, backend,
# devices, topology, podutils, podmanager, kubelet client, server,
# manager, daemon e2e, extender, leader, health, metrics, events,
# inspect, tenant, native discovery, fuzz, race) and finishes in a
# couple of minutes on one core. The slow tier is everything that
# compiles JAX programs (models/ops/parallel, collective-heavy CPU-mesh
# tests, subprocess dryruns), which dominates the suite's wall-clock.
# Policy: a test module lands here iff it imports jax or spawns a
# JAX-running subprocess.
SLOW_MODULES = {
    "test_adamw", "test_checkpoint", "test_chip_smoke_rehearsal",
    "test_convert",
    "test_distributed_2proc", "test_e2e_dryrun",
    "test_finetune_serve", "test_fsdp",
    "test_generate", "test_kv_quant", "test_lora", "test_models",
    "test_moe", "test_multi_lora",
    "test_multihost",
    "test_moe_pipeline", "test_ops", "test_paged", "test_parallel",
    "test_pipeline",
    "test_prefix_cache", "test_serve",
    "test_profiling", "test_quant", "test_serving",
    "test_speculative", "test_trainer", "test_transformer",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.fspath.purebasename in SLOW_MODULES:
            item.add_marker(pytest.mark.slow)
    # Run the heaviest-compile module FIRST (stable sort keeps all other
    # order). The XLA:CPU compiler was observed segfaulting on
    # test_transformer's dp2/sp2/tp2 shard_map train-step compile — but
    # only ~45 modules deep into a full run (three times at the same
    # test; standalone and both 12-module halves pass with it LAST).
    # The crash needs this compile on top of hundreds of accumulated
    # in-process compiles; doing it first removes the accumulation.
    items.sort(key=lambda item:
               0 if item.fspath.purebasename == "test_transformer" else 1)
