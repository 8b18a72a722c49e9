"""Where ``repro.compile_cache.enable`` puts JAX's persistent cache."""
import os

import jax

from repro import compile_cache


def _with_cache_dir(fn):
    before = jax.config.jax_compilation_cache_dir
    try:
        return fn()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_env_var_wins_and_is_left_alone(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))

    def run():
        before = jax.config.jax_compilation_cache_dir
        assert compile_cache.enable() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before

    _with_cache_dir(run)


def test_default_is_the_checkout_root(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)

    def run():
        path = compile_cache.enable()
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert path == os.path.join(root, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path

    _with_cache_dir(run)
