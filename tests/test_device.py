"""The device policy (``repro.device``): one function picks the device
path, from what JAX reports, and the compile cache sits at a fixed path."""

import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro import device
from repro.core.store import IndexStore
from repro.core.verify import VerifyBatcher, compare_ids_batch

SRC = Path(__file__).resolve().parents[1] / "src"


def test_on_tpu_picks_host_paths_on_cpu():
    assert jax.default_backend() == "cpu"
    assert device.on_tpu() is False
    assert IndexStore._similar_probe(None) == "host"
    assert IndexStore._similar_probe("auto") == "host"
    VerifyBatcher("process")  # allowed off the chip


def test_on_tpu_follows_jax_backend(monkeypatch):
    """Every auto choice follows the platform JAX reports."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert device.on_tpu() is True
    assert IndexStore._similar_probe(None) == "device"
    with pytest.raises(ValueError, match="process pool"):
        VerifyBatcher("process")


def test_on_tpu_asks_jax_even_when_not_yet_imported():
    """The answer never depends on whether the caller imported JAX first:
    the policy imports JAX and asks it, it does not look in sys.modules."""
    code = (
        "import sys\n"
        "from repro.device import on_tpu\n"
        "assert 'jax' not in sys.modules\n"
        "assert on_tpu() is False\n"
        "assert 'jax' in sys.modules\n"
        "from repro.core.verify import compare_ids_batch\n"
        "assert compare_ids_batch(['a', 'b'], ['a', 'c']) == [True, False]\n"
    )
    env = dict(os.environ, PYTHONPATH=str(SRC), JAX_PLATFORMS="cpu")
    subprocess.run(
        [sys.executable, "-c", code], env=env, check=True, timeout=120
    )


def test_compare_ids_auto_matches_explicit_backends():
    exp = ["InChI=1S/CH4/c1H4", "InChI=1S/H2O/h1H2", "x"]
    got = ["InChI=1S/CH4/c1H4", "InChI=1S/H2O/h1H3", "x"]
    want = [True, False, True]
    assert compare_ids_batch(exp, got) == want
    assert compare_ids_batch(exp, got, backend="string") == want
    assert compare_ids_batch(exp, got, backend="digest") == want


def test_compile_cache_honours_env(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/where")
    before = jax.config.jax_compilation_cache_dir
    assert device.use_compile_cache() == "/some/where"
    assert jax.config.jax_compilation_cache_dir == before  # nothing set


def test_compile_cache_defaults_to_fixed_checkout_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = device.use_compile_cache()
        assert got == str(SRC.parent / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
        assert device.use_compile_cache() == got  # same path every call
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
