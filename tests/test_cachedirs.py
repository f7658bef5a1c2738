"""One place sets the compile-cache directory; the plan cache has a fixed
root in the checkout."""
from __future__ import annotations

import os

import jax
import pytest

from repro import cachedirs
from repro.deploy import PlanCache


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_cache_dir_is_left_alone(monkeypatch, restore_cache_dir,
                                     tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    assert cachedirs.setup_compile_cache() == str(tmp_path)


def test_default_cache_dirs_live_in_the_checkout(monkeypatch,
                                                 restore_cache_dir,
                                                 tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert cachedirs.setup_compile_cache() == os.path.join(root,
                                                           ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    assert PlanCache().root == os.path.join(root, ".mdm_plan_cache")
