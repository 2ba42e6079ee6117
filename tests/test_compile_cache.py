"""Where the entry points keep JAX's persistent compilation cache
(``repro.launch.compile_cache``).  Each case runs in a fresh interpreter:
the cache directory is process-wide state that JAX fixes at the first
compile."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT_CACHE = os.path.join(REPO, ".jax_cache")

SCRIPT = """
import jax, jax.numpy as jnp
import repro.engine, repro.launch.serve_snn, repro.launch.socket_serve
print("after_import", jax.config.jax_compilation_cache_dir)
if {enable}:
    from repro.launch.compile_cache import enable_compile_cache
    print("enabled", enable_compile_cache())
    print("config", jax.config.jax_compilation_cache_dir)
if {compile}:
    jax.jit(lambda x: x * 2 + 1)(jnp.arange(4.0)).block_until_ready()
"""


def _run(env_dir, *, enable: bool, compile: bool) -> dict:
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH="src", JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    p = subprocess.run(
        [sys.executable, "-c", SCRIPT.format(enable=enable, compile=compile)],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    return dict(line.split(" ", 1) for line in p.stdout.splitlines())


def test_importing_repro_leaves_the_cache_off():
    assert _run(None, enable=False, compile=False)["after_import"] == "None"


def test_env_dir_is_used_and_nothing_else(tmp_path):
    before = set(os.listdir(CHECKOUT_CACHE)) \
        if os.path.isdir(CHECKOUT_CACHE) else set()
    out = _run(tmp_path, enable=True, compile=True)
    assert out["enabled"] == out["config"] == str(tmp_path)
    assert os.listdir(tmp_path), "no cache entry written to the env dir"
    after = set(os.listdir(CHECKOUT_CACHE)) \
        if os.path.isdir(CHECKOUT_CACHE) else set()
    assert after == before


def test_checkout_dir_without_env_and_git_ignores_it():
    out = _run(None, enable=True, compile=False)
    assert out["enabled"] == out["config"] == CHECKOUT_CACHE
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
