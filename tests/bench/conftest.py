"""A benchmark run points JAX's persistent compilation cache at the
checkout and caches every compile; these tests drive whole runs in a pytest
worker that other test files share, so each test gets the settings back."""
import jax
import pytest

_SETTINGS = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")


@pytest.fixture(autouse=True)
def _restore_jax_cache_settings():
    saved = {name: getattr(jax.config, name) for name in _SETTINGS}
    yield
    for name, value in saved.items():
        jax.config.update(name, value)
    from jax.experimental.compilation_cache import compilation_cache
    compilation_cache.reset_cache()
