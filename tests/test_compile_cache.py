"""``repro.compile_cache.use_compile_cache``: where the cache lives, and a
key that holds the program's metadata (its ``named_scope`` names), with
``JAX_COMPILATION_CACHE_DIR`` set and unset."""
import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro import compile_cache

_KEYS = ("jax_compilation_cache_dir",
         "jax_compilation_cache_include_metadata_in_key")


@pytest.fixture
def restored():
    """Put the process's cache settings back as they were."""
    saved = {k: getattr(jax.config, k) for k in _KEYS}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("env", ["set", "unset"])
def test_cache_key_includes_metadata(env, restored, monkeypatch, tmp_path):
    jax.config.update("jax_compilation_cache_include_metadata_in_key", False)
    if env == "set":
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        want = str(tmp_path)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(compile_cache.CHECKOUT / ".jax_cache")
    assert compile_cache.use_compile_cache() == want
    assert jax.config.jax_compilation_cache_include_metadata_in_key
    if env == "unset":
        assert jax.config.jax_compilation_cache_dir == want
