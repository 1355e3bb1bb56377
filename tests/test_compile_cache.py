"""repro.launch.compile_cache: where the persistent compile cache goes."""
import pathlib

import jax

from repro.launch import compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]


def test_env_var_wins_and_code_sets_nothing(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_the_fixed_repo_dir(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
