"""tpushare.utils.compile_cache: where the persistent XLA cache lives.

The environment variable wins and no other directory is set in code;
unset, the path is the fixed one inside the checkout — the same from
every process, because the path is part of the cache key."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = (
    "import sys; sys.path.insert(0, %r)\n"
    "from tpushare.utils import compile_cache as cc\n"
    "assert 'jax' not in sys.modules      # the helper alone is jax-free\n"
    "import jax\n"
    "before = jax.config.jax_compilation_cache_dir\n"
    "got = cc.enable_compile_cache(lane=sys.argv[1])\n"
    "print('|'.join(map(str, (got, before, "
    "jax.config.jax_compilation_cache_dir, cc.compile_cache_dir(sys.argv[1])"
    "))))\n" % REPO)


def _run(lane, env_dir=None):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    # A cwd and a TMPDIR that differ per call: neither may reach the path.
    out = subprocess.run([sys.executable, "-c", _CHILD, lane], env=env,
                         cwd="/" if env_dir is None else REPO,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-800:]
    return out.stdout.strip().split("|")


def test_env_var_wins_and_no_other_directory_is_set(tmp_path):
    want = str(tmp_path / "outside")
    got, before, after, reported = _run("chip", env_dir=want)
    assert got == want == reported
    # JAX read the variable itself; the helper changed nothing.
    assert before == after == want


def test_unset_is_the_fixed_directory_in_the_checkout():
    a = _run("chip")
    b = _run("chip")
    fixed = os.path.join(REPO, ".jax_cache", "chip")
    assert a[0] == b[0] == fixed          # same from two processes
    assert a[1] == "None" and a[2] == fixed
    # Lanes keep the CPU suite's machine-specific entries apart.
    assert _run("cpu-tests")[0] == os.path.join(REPO, ".jax_cache",
                                                "cpu-tests")


def test_the_fixed_directory_is_git_ignored_and_nothing_names_tmp():
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
    for path in ("bench.py", "chip_smoke.py", "tests/conftest.py",
                 "benchmarks/bench_isolation.py",
                 "tpushare/utils/compile_cache.py"):
        with open(os.path.join(REPO, path)) as f:
            assert "/tmp/tpushare" not in f.read(), path
