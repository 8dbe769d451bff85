"""Where ``enable_compile_cache`` puts JAX's persistent compilation cache.

Each case runs in a child process: JAX reads ``JAX_COMPILATION_CACHE_DIR``
when it is imported, and the cache settings are process-wide.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PROG = textwrap.dedent("""
    import json, os, sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp
    from repro.launch.compile_cache import CACHE_DIR, enable_compile_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    used = enable_compile_cache()
    if os.environ.get("COMPILE_SOMETHING"):
        jax.block_until_ready(jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)))
    print(json.dumps({"used": used,
                      "config": jax.config.jax_compilation_cache_dir,
                      "default": str(CACHE_DIR)}))
""")


def _run(env_dir=None, compile_something=False):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    if compile_something:
        env["COMPILE_SOMETHING"] = "1"
    res = subprocess.run([sys.executable, "-c", PROG], capture_output=True,
                         text=True, cwd=REPO, env=env, timeout=300)
    assert res.returncode == 0, res.stderr[-2000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


def test_env_dir_is_left_to_jax_and_receives_the_cache(tmp_path):
    cache = tmp_path / "cache"
    out = _run(str(cache), compile_something=True)
    assert out["used"] == out["config"] == str(cache)
    assert out["default"] != str(cache)
    assert any(cache.iterdir())


@pytest.mark.parametrize("env_value", [None, ""])
def test_default_is_the_checkout_cache_dir(env_value):
    out = _run(env_value)
    assert out["used"] == out["config"] == out["default"] \
        == os.path.join(REPO, ".jax_cache")
