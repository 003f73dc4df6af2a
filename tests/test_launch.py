"""Entry-point plumbing: the compile cache placement and the served model."""
import jax
import jax.numpy as jnp
import pytest

from repro.launch import compile_cache
from repro.launch.serve import build_model


@pytest.fixture
def cache_dir_restored():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_defaults_to_the_checkout(monkeypatch,
                                                cache_dir_restored):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    where = compile_cache.use_compile_cache()
    assert where == str(compile_cache.DEFAULT_DIR)
    assert compile_cache.DEFAULT_DIR.name == ".jax_cache"
    assert (compile_cache.DEFAULT_DIR.parent / "chip_smoke.py").exists()
    assert jax.config.jax_compilation_cache_dir == where


def test_compile_cache_placed_from_outside_is_left_alone(
        monkeypatch, tmp_path, cache_dir_restored):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_build_model_published_unless_smoke():
    """The launcher serves published widths in the published dtype; only
    ``smoke=True`` takes the reduced float32 model (shapes only here: the
    full weights are 6.4 GB)."""
    full = jax.eval_shape(lambda: build_model("llama3_2_3b")[1])
    assert full["embed"].shape == (128256, 3072)
    assert full["embed"].dtype == jnp.bfloat16
    assert full["layers"]["mlp"]["w_up"].shape == (28, 3072, 8192)
    small, params = build_model("llama3_2_3b", smoke=True)
    assert small.dtype == "float32" and small.vocab_size == 2048
    assert params["embed"].shape == (2048, small.d_model)
