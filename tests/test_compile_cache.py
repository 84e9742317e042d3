"""The entry points' persistent compilation cache: the directory the
environment names, else a fixed directory in the checkout."""

import os
import pathlib
import subprocess
import sys

CHECKOUT = pathlib.Path(__file__).resolve().parents[1]
PROBE = ("import jax\n"
         "from repro.launch.jax_cache import enable_compile_cache\n"
         "print(enable_compile_cache())\n"
         "print(jax.config.jax_compilation_cache_dir)\n"
         "jax.jit(lambda x: x * 3 + 1)(jax.numpy.ones(5)).block_until_ready()\n")


def _probe(**env_over):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(env_over)
    env["PYTHONPATH"] = str(CHECKOUT / "src")
    r = subprocess.run([sys.executable, "-c", PROBE], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout.split()


def test_environment_directory_is_used(tmp_path):
    d = str(tmp_path / "jc")
    returned, configured = _probe(JAX_COMPILATION_CACHE_DIR=d,
                                  JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    assert returned == configured == d
    assert any(p.name.endswith("-cache") for p in pathlib.Path(d).iterdir())


def test_default_directory_is_fixed_in_the_checkout():
    returned, configured = _probe(
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="10000")
    assert returned == configured == str(CHECKOUT / ".jax_cache")
