"""Run-time set-up for the chip: compile-cache placement and peak tables."""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.launch import roofline
from repro.runtime import compile_cache

_CACHE_FLAGS = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
                "jax_persistent_cache_min_compile_time_secs",
                "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture
def restore_cache_config():
    from jax.experimental.compilation_cache import compilation_cache

    saved = {k: getattr(jax.config, k) for k in _CACHE_FLAGS}
    yield compilation_cache
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("env_set", [True, False], ids=["env", "default"])
def test_compile_cache_placement(env_set, tmp_path, monkeypatch,
                                 restore_cache_config):
    if env_set:
        want = str(tmp_path / "cache")
        monkeypatch.setenv(compile_cache.ENV_VAR, want)
    else:
        want = str(compile_cache.DEFAULT_DIR)
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    assert compile_cache.enable() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert jax.config.jax_enable_compilation_cache
    if env_set:
        # a compile lands in the named directory (and so nowhere else)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        restore_cache_config.reset_cache()
        jax.jit(lambda x: x * 3.0 + 1.0).lower(jnp.ones((7,))).compile()
        assert os.listdir(want)
    else:
        # fixed, at the checkout root: never a temp name, pid or time
        assert compile_cache.DEFAULT_DIR.name == ".jax_cache"
        assert (compile_cache.DEFAULT_DIR.parent / "pyproject.toml").exists()


def test_peaks_keyed_by_device_kind():
    v5e = roofline.peaks(roofline.V5E)
    assert v5e["flops_bf16"] == 197e12 and v5e["hbm_bw"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        roofline.peaks("cpu")
