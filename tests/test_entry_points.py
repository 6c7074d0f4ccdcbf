"""Process-level contracts of the entry points: where the compilation
cache lands, and that the benchmark harness's parent never imports JAX
(a process holding the chip must not start children that need it)."""

import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_JIT_ONE = (
    "import sys, jax, jax.numpy as jnp\n"
    "from repro.launch import compile_cache as cc\n"
    "if len(sys.argv) > 1: cc.CACHE_DIR = cc.Path(sys.argv[1])\n"
    "print(cc.enable_compile_cache())\n"
    "jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()\n"
)


def _run(code, *args, env_extra=None):
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "-c", code, *args], env=env,
                          cwd=ROOT, capture_output=True, text=True,
                          check=True).stdout


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_location(tmp_path, from_env):
    """With ``JAX_COMPILATION_CACHE_DIR`` set, entries land there and only
    there; without it, in the fixed in-checkout directory."""
    env_dir, default_dir = tmp_path / "env", tmp_path / "default"
    if from_env:
        out = _run(_JIT_ONE, str(default_dir),
                   env_extra={"JAX_COMPILATION_CACHE_DIR": str(env_dir)})
        used, unused = env_dir, default_dir
    else:
        out = _run(_JIT_ONE, str(default_dir))
        used, unused = default_dir, env_dir
    assert out.strip().splitlines()[-1] == str(used)
    assert any(used.iterdir())
    assert not unused.exists()


def test_default_cache_dir_is_fixed_in_checkout():
    from repro.launch.compile_cache import CACHE_DIR
    assert CACHE_DIR == __import__("pathlib").Path(ROOT) / ".jax_cache"


def test_benchmark_parent_stays_off_jax():
    out = _run("import sys, benchmarks.run\n"
               "print(sorted(m for m in sys.modules\n"
               "             if m.split('.')[0] in ('jax', 'repro')))\n")
    assert out.strip() == "[]"
