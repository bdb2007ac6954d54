"""tpushare.utils — tenant-side contract, checkpointing, profiling.

- ``tenant``        — consume the plugin's injected env (validation, HBM
  guard); the in-pod half of the memory-isolation contract.
- ``checkpoint``    — orbax save/restore with cross-mesh resume.
- ``profiling``     — XLA traces, step timing, FLOPs/MFU accounting.
- ``compile_cache`` — where the persistent XLA compile cache lives.

Submodules are imported by name (``from tpushare.utils import tenant``),
never eagerly here: ``profiling`` and ``checkpoint`` import jax, and the
jax-free callers of this package (``tenant`` before backend init, the
parents of ``chip_smoke.py`` and ``bench.py``) must stay off it.
"""
