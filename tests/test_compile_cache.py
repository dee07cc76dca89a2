"""Where the persistent XLA compile cache lives (jaxconf): the
deployment's ``JAX_COMPILATION_CACHE_DIR`` when set, else one fixed path
inside the checkout; ``GEOMESA_TPU_COMPILE_CACHE=off`` (the suite's own
setting) turns it off. Each case restores the process's cache state, so
no later test writes a cache entry."""

import os

import pytest

from geomesa_tpu import jaxconf


@pytest.fixture
def fresh_cache(monkeypatch):
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.setattr(jaxconf, "_cache_dir", None)
    monkeypatch.delenv("GEOMESA_TPU_COMPILE_CACHE", raising=False)
    yield
    jax.config.update("jax_compilation_cache_dir", prev)
    compilation_cache.reset_cache()


def test_env_dir_wins(fresh_cache, monkeypatch, tmp_path):
    import jax

    d = str(tmp_path / "xla")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", d)
    assert jaxconf.enable_compilation_cache() == d
    assert jax.config.jax_compilation_cache_dir == d
    assert os.path.isdir(d)


def test_unset_uses_the_fixed_checkout_path(fresh_cache, monkeypatch,
                                            tmp_path):
    import jax

    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert jaxconf.DEFAULT_CACHE_DIR == os.path.join(checkout, ".jax_cache")
    # resolve against a stand-in so the test creates nothing in the tree
    monkeypatch.setattr(jaxconf, "DEFAULT_CACHE_DIR", str(tmp_path / "c"))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = jaxconf.enable_compilation_cache()
    assert got == str(tmp_path / "c")
    assert jax.config.jax_compilation_cache_dir == got


def test_off_switch(fresh_cache, monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "x"))
    monkeypatch.setenv("GEOMESA_TPU_COMPILE_CACHE", "off")
    assert jaxconf.enable_compilation_cache() is None
    assert not (tmp_path / "x").exists()
