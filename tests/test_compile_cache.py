"""The entry points' persistent compilation cache (launch/compile_cache)."""
import os
import subprocess
import sys

import jax
import pytest

from repro.launch import compile_cache

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SRC = os.path.join(REPO, "src")


@pytest.fixture
def config_updates(monkeypatch):
    """Record jax.config.update calls instead of changing this process's
    cache (later tests in the worker must not start writing one)."""
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_env_var_wins_and_nothing_else_is_set(monkeypatch, config_updates,
                                              tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable() == str(tmp_path)
    assert config_updates == []


def test_default_is_the_fixed_repo_directory(monkeypatch, config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache.REPO_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    assert compile_cache.enable() == compile_cache.REPO_CACHE_DIR
    assert config_updates == [("jax_compilation_cache_dir",
                               compile_cache.REPO_CACHE_DIR)]


@pytest.mark.parametrize("source", ["env", "default"])
def test_compiled_executables_land_in_the_cache(tmp_path, source):
    """End to end in a fresh process: a jit compile after enable() writes
    its executable to the chosen directory."""
    cache = tmp_path / "cache"
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=SRC,
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if source == "env":
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache)
        point = ""
    else:                   # the default directory, moved to tmp_path
        point = f"compile_cache.REPO_CACHE_DIR = {str(cache)!r}\n"
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.launch import compile_cache\n" + point +
            "print(compile_cache.enable())\n"
            "jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()\n")
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr[-2000:]
    assert res.stdout.strip().splitlines()[-1] == str(cache)
    assert any(n.endswith("-cache") for n in os.listdir(cache))
