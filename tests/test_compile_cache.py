"""The persistent compilation cache is placed from outside or at a fixed
path of the checkout, and importing the library never turns it on."""

import os
import subprocess
import sys

import jax
import pytest

from repro.runtime import compile_cache


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_environment_variable_wins_and_nothing_is_set(monkeypatch,
                                                      restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    jax.config.update("jax_compilation_cache_dir", None)
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir is None


def test_default_is_fixed_under_the_checkout(monkeypatch,
                                             restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(compile_cache.DEFAULT_DIR)
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.DEFAULT_DIR.parent.joinpath("src", "repro").is_dir()
    assert compile_cache.DEFAULT_DIR.name == ".jax_cache"


def test_library_import_leaves_the_cache_off():
    code = ("import jax, repro.core.service, repro.runtime.workers, "
            "repro.core.stream; print(jax.config.jax_compilation_cache_dir)")
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == "None"
