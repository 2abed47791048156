"""The persistent compilation cache's placement: a deployment's
``JAX_COMPILATION_CACHE_DIR`` is left to JAX, else a fixed directory in
the checkout; compiles of any length are kept."""
import os

import jax
import pytest

from repro.launch import cache


@pytest.mark.parametrize("env_set", [False, True])
def test_enable_compile_cache_placement(monkeypatch, tmp_path, env_set):
    before = (jax.config.jax_compilation_cache_dir,
              jax.config.jax_persistent_cache_min_compile_time_secs)
    if env_set:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        path = cache.enable_compile_cache()
        if env_set:
            # JAX reads the variable itself; no other directory is set
            assert path == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before[0]
        else:
            assert path == str(cache.CHECKOUT_CACHE)
            assert jax.config.jax_compilation_cache_dir == path
            # <checkout>/.jax_cache: beside the repository's own files
            assert os.path.exists(cache.CHECKOUT_CACHE.parent / "chip_smoke.py")
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    finally:
        # nothing compiled in between, so the cache never initialized here
        jax.config.update("jax_compilation_cache_dir", before[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          before[1])
