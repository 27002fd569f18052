"""Where the persistent compile cache goes (repro.compile_cache)."""
import pathlib

import pytest

import jax

from repro.compile_cache import CACHE_DIR_NAME, enable_compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]
_KEYS = ("jax_compilation_cache_dir",
         "jax_persistent_cache_min_compile_time_secs",
         "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture()
def jax_cache_config():
    prev = {k: getattr(jax.config, k) for k in _KEYS}
    yield
    for k, v in prev.items():
        jax.config.update(k, v)


def test_env_dir_stands_and_code_sets_no_other(jax_cache_config, monkeypatch,
                                               tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache(tmp_path / "checkout") == str(tmp_path / "env")
    assert jax.config.jax_compilation_cache_dir == before
    assert not (tmp_path / "checkout").exists()
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_fixed_in_checkout_dir_without_env(jax_cache_config, monkeypatch,
                                           tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str((tmp_path / CACHE_DIR_NAME).resolve())
    assert enable_compile_cache(tmp_path) == want
    assert jax.config.jax_compilation_cache_dir == want
    # the same checkout always names the same directory, and git ignores it
    assert enable_compile_cache(tmp_path) == want
    ignored = (ROOT / ".gitignore").read_text().split()
    assert f"{CACHE_DIR_NAME}/" in ignored
