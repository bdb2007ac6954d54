"""Where the persistent XLA compile cache lives — the one home.

Callers: ``tpushare.cli.serve`` (the daemon), the ``bench.py`` tenant,
the children of ``chip_smoke.py`` and ``tests/conftest.py``.

The rule: where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and this module sets NO other directory in code — whoever runs
the program (an operator, the chip tool) places the cache from outside.
Where it is not set, the cache goes to a FIXED directory inside the
checkout (git-ignored). The path is part of JAX's cache key, so a
directory built from a temporary name, a pid, the user or the time
would never hit; this one is the same from every process of a tree.

``lane`` keeps entries apart that must not mix: XLA:CPU entries are
AOT code for THIS machine (loading them on another host risks
SIGILL), so the CPU test suite caches under its own lane and the
chip's programs under theirs. Callers that run on the CPU outside the
test suite do not enable the cache at all — CPU compiles are fast.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
#: git-ignored and chip-tool-ignored (.gitignore, .chiprunignore)
FIXED_ROOT = os.path.join(_REPO, ".jax_cache")


def compile_cache_dir(lane: str = "chip") -> str:
    """The directory the compile cache is (or would be) in: the
    environment's when set, else the fixed one for ``lane``."""
    return os.environ.get(ENV_VAR) or os.path.join(FIXED_ROOT, lane)


def enable_compile_cache(lane: str = "chip") -> str:
    """Turn the persistent compile cache on before the first compile;
    returns the directory in use. JAX's default thresholds (entry
    size, compile seconds) are left alone — callers that want every
    entry cached (the test suite) lower them themselves."""
    path = compile_cache_dir(lane)
    if not os.environ.get(ENV_VAR):
        import jax
        jax.config.update("jax_compilation_cache_dir", path)
    return path
