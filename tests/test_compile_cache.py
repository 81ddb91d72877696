"""The persistent compile cache helper: env var wins, else <repo>/.jax_cache.

``enable`` is exercised with ``jax.config.update`` replaced, so no test
turns the real cache on.
"""
import jax
import pytest

from repro import compile_cache


@pytest.fixture
def config_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_env_var_set_means_nothing_set_in_code(monkeypatch, config_updates):
    monkeypatch.setenv(compile_cache.ENV_VAR, "/elsewhere/cache")
    assert compile_cache.cache_dir_to_set() is None
    assert compile_cache.enable() == "/elsewhere/cache"
    assert config_updates == []


def test_env_var_unset_uses_fixed_repo_path(monkeypatch, config_updates):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    want = compile_cache.REPO_ROOT / ".jax_cache"
    assert compile_cache.cache_dir_to_set() == want
    assert (want.parent / "chip_smoke.py").is_file()  # really the repo root
    assert compile_cache.enable() == str(want)
    assert config_updates == [("jax_compilation_cache_dir", str(want))]
    # fixed: the same path on every call, never per process or time
    assert compile_cache.enable() == str(want)


def test_empty_env_var_counts_as_unset(monkeypatch, config_updates):
    monkeypatch.setenv(compile_cache.ENV_VAR, "")
    assert compile_cache.cache_dir_to_set() == (
        compile_cache.REPO_ROOT / ".jax_cache")
