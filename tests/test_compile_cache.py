"""Where enable_compile_cache puts JAX's persistent compilation cache."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.utils import compile_cache

REPO = os.path.normpath(os.path.join(os.path.dirname(__file__), ".."))


@pytest.fixture
def jax_cache_config():
    """Restore the process-wide cache settings a test changes."""
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    try:
        yield
    finally:
        for n, v in saved.items():
            jax.config.update(n, v)
        compilation_cache.reset_cache()


def test_repo_cache_dir_is_git_ignored():
    assert os.path.normpath(compile_cache.REPO_CACHE_DIR) == \
        os.path.join(REPO, ".jax_cache")
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


@pytest.mark.parametrize("env_set", [True, False])
def test_cache_written_only_where_chosen(env_set, tmp_path, monkeypatch,
                                         jax_cache_config):
    env_dir, repo_dir = tmp_path / "env", tmp_path / "repo"
    monkeypatch.setattr(compile_cache, "REPO_CACHE_DIR", str(repo_dir))
    if env_set:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(env_dir))
        # JAX reads the variable when it starts; stand in for that here
        jax.config.update("jax_compilation_cache_dir", str(env_dir))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    used = compile_cache.enable_compile_cache()
    want, other = (env_dir, repo_dir) if env_set else (repo_dir, env_dir)
    assert used == str(want)
    if env_set:        # nothing is set in code where the variable is set
        assert jax.config.jax_compilation_cache_dir == before
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compilation_cache.reset_cache()
    jax.jit(lambda x: jnp.cos(x) * 5 - 2)(jnp.ones(11)).block_until_ready()
    assert os.listdir(want)
    assert not other.exists()
