"""Persistent XLA compilation cache (tpudas.utils.compile_cache): the
placement rule and that the cache really fills."""

import glob
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp


@pytest.fixture
def fresh_cache_config(monkeypatch):
    """Restore JAX's cache config after the test, and start it with the
    module's enable latch cleared."""
    import tpudas.utils.compile_cache as cc

    keys = (
        "jax_compilation_cache_dir",
        "jax_enable_compilation_cache",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes",
    )
    prev = {k: getattr(jax.config, k) for k in keys}
    monkeypatch.setattr(cc, "_ENABLED", False)
    yield cc
    for k, v in prev.items():
        jax.config.update(k, v)
    from jax._src import compilation_cache as _cc

    _cc.reset_cache()


def test_cache_fills_at_the_selected_dir(tmp_path, monkeypatch,
                                         fresh_cache_config):
    cc = fresh_cache_config
    d = str(tmp_path / "cache")
    monkeypatch.setattr(cc, "CHECKOUT_CACHE", d)
    monkeypatch.delenv(cc.ENV_DIR, raising=False)
    assert cc.enable_compile_cache() == d
    assert jax.config.jax_compilation_cache_dir == d
    # drop the entry thresholds so this tiny jit is cached
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    f = jax.jit(lambda x: jnp.sin(x) @ x.T)
    f(np.ones((32, 32), np.float32)).block_until_ready()
    assert len(glob.glob(os.path.join(d, "*"))) >= 1
    # idempotent second call reports the same dir
    assert cc.enable_compile_cache() == d


@pytest.mark.parametrize("env_set", [False, True])
def test_placement_rule(tmp_path, monkeypatch, fresh_cache_config,
                        env_set):
    """``JAX_COMPILATION_CACHE_DIR`` set: that directory, and no other
    is configured in code.  Unset: the fixed ``<checkout>/.jax_cache``."""
    cc = fresh_cache_config
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert cc.CHECKOUT_CACHE == os.path.join(repo, ".jax_cache")
    before = jax.config.jax_compilation_cache_dir
    if env_set:
        d = str(tmp_path / "from_env")
        monkeypatch.setenv(cc.ENV_DIR, d)
        assert cc.enable_compile_cache() == d
        assert jax.config.jax_compilation_cache_dir == before
    else:
        monkeypatch.delenv(cc.ENV_DIR, raising=False)
        assert cc.cache_dir() == cc.CHECKOUT_CACHE
        assert cc.enable_compile_cache() == cc.CHECKOUT_CACHE
        assert jax.config.jax_compilation_cache_dir == cc.CHECKOUT_CACHE


def test_lfproc_turns_the_cache_on(monkeypatch, fresh_cache_config):
    cc = fresh_cache_config
    monkeypatch.delenv(cc.ENV_DIR, raising=False)
    from tpudas.proc.lfproc import LFProc

    LFProc()
    assert cc._ENABLED
    assert jax.config.jax_compilation_cache_dir == cc.CHECKOUT_CACHE
