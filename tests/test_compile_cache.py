"""The compile-cache helper (`repro.launch.cache.enable_compile_cache`).

It honours JAX_COMPILATION_CACHE_DIR when set and otherwise places the
cache at one fixed path inside the checkout, the same in every process
(the path is part of every cache key).  Each case runs in a child process
so the test process's own JAX config is untouched.
"""
import os
import pathlib
import subprocess
import sys

_PROBE = ("import jax\n"
          "from repro.launch.cache import enable_compile_cache\n"
          "print(enable_compile_cache())\n"
          "print(jax.config.jax_compilation_cache_dir)\n")
_REPO = pathlib.Path(__file__).resolve().parents[1]


def _probe(**env_over):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(env_over)
    env["PYTHONPATH"] = str(_REPO / "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", _PROBE], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.split()


def test_default_cache_dir_is_fixed_inside_checkout():
    first, second = _probe(), _probe()
    assert first == second
    path, configured = first
    assert path == configured == str(_REPO / ".jax_cache")


def test_env_cache_dir_is_honoured(tmp_path):
    want = str(tmp_path / "cache")
    path, configured = _probe(JAX_COMPILATION_CACHE_DIR=want)
    assert path == configured == want
