"""The entry points' compile-cache placement (pipelinedp_tpu/compile_cache)."""

import jax

from pipelinedp_tpu import compile_cache


def _restoring_cache_dir(fn):
    before = jax.config.jax_compilation_cache_dir
    try:
        return fn(), jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_env_dir_wins_and_nothing_is_set(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.CACHE_DIR_ENV, str(tmp_path / "env"))
    before = jax.config.jax_compilation_cache_dir
    path, after = _restoring_cache_dir(
        lambda: compile_cache.configure(str(tmp_path)))
    assert path == str(tmp_path / "env")
    assert after == before


def test_default_is_fixed_under_the_checkout(monkeypatch, tmp_path):
    monkeypatch.delenv(compile_cache.CACHE_DIR_ENV, raising=False)
    path, after = _restoring_cache_dir(
        lambda: compile_cache.configure(str(tmp_path)))
    assert path == str(tmp_path / ".jax_cache")
    assert after == path
    # The same checkout always maps to the same directory.
    again, _ = _restoring_cache_dir(
        lambda: compile_cache.configure(str(tmp_path)))
    assert again == path
