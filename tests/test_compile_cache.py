"""``repro.compile_cache``: where the entry points keep JAX's persistent cache.

With ``JAX_COMPILATION_CACHE_DIR`` set, that directory is JAX's own and the
helper sets no other; without it, the cache goes to the checkout's fixed
``.jax_cache``.  Either way every executable is cached, however small.
"""

from pathlib import Path

import jax
import pytest

from repro import compile_cache

_KEYS = (
    "jax_compilation_cache_dir",
    "jax_persistent_cache_min_compile_time_secs",
    "jax_persistent_cache_min_entry_size_bytes",
)


@pytest.fixture
def restore_config():
    prev = {k: getattr(jax.config, k) for k in _KEYS}
    yield
    for k, v in prev.items():
        jax.config.update(k, v)


def test_env_dir_is_left_to_jax(monkeypatch, restore_config, tmp_path):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == -1


def test_default_dir_is_in_the_checkout(monkeypatch, restore_config):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    path = compile_cache.enable_compile_cache()
    checkout = Path(__file__).resolve().parents[1]
    assert path == str(checkout / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert ".jax_cache/" in (checkout / ".gitignore").read_text().splitlines()
