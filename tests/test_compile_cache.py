"""The persistent compile cache helper: placed from outside, or fixed."""
import jax

from repro.launch.compile_cache import (CHECKOUT_ROOT, compile_cache_dir,
                                        enable_compile_cache)


def test_cache_dir_from_environment(tmp_path):
    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cc")}
    assert compile_cache_dir(env) == tmp_path / "cc"


def test_cache_dir_fixed_checkout_path():
    path = compile_cache_dir({})
    assert path == CHECKOUT_ROOT / ".jax_cache"
    assert (CHECKOUT_ROOT / "pyproject.toml").is_file()
    assert compile_cache_dir({"JAX_COMPILATION_CACHE_DIR": ""}) == path


def test_enable_keeps_environment_dir(tmp_path, monkeypatch):
    """With the variable set, JAX reads it itself; the helper sets no
    other directory in code."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == tmp_path
    assert jax.config.jax_compilation_cache_dir == before
