"""Fixtures of the benchmark's own tests."""
import pytest


@pytest.fixture
def own_compile_cache():
    """A run turns JAX's persistent compilation cache on for its process;
    give the worker's later tests the settings they had."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_enable_compilation_cache")
    before = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in before.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()
