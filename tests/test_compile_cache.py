"""Compile-cache placement (utils/compile_cache.py): one function, one rule.
``JAX_COMPILATION_CACHE_DIR`` set -> our code sets no directory; unset ->
``<checkout>/.jax_cache``, with a host-keyed sub-directory on the CPU (the
key that stops migrated containers loading foreign-machine XLA:CPU code)."""
import logging
import os
import re

import jax
import pytest

from distar_tpu.utils import compile_cache as cc

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_env_var_set_means_our_code_sets_no_directory(monkeypatch, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/from/outside")
    jax.config.update("jax_compilation_cache_dir", "sentinel-untouched")
    cc.configure()
    assert jax.config.jax_compilation_cache_dir == "sentinel-untouched"
    assert cc.active_dir() == "sentinel-untouched"


def test_unset_means_checkout_jax_cache(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    cc.configure()
    base = os.path.join(REPO, ".jax_cache")
    # the suite runs on the CPU backend: host-keyed sub-directory
    assert jax.config.jax_compilation_cache_dir == cc.cache_dir("cpu")
    assert os.path.dirname(cc.cache_dir("cpu")) == base
    assert cc.cache_dir("tpu") == base  # accelerators share the top level
    assert "/tmp" not in cc.cache_dir("cpu") and str(os.getpid()) not in cc.cache_dir("cpu")


def test_cpu_sub_key_is_stable_and_never_empty():
    key = cc._host_cpu_key()
    assert key == cc._host_cpu_key(), "key must be deterministic within one host"
    assert isinstance(key, str) and len(key) == 8
    import hashlib

    # the empty-string hash would give distinct hosts the same key
    assert key != hashlib.sha1(b"").hexdigest()[:8]
    assert os.path.basename(cc.cache_dir("cpu")) == f"cpu-{key}"


def test_configure_degrades_loudly_not_silently(monkeypatch, caplog):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)

    def broken_update(*a, **k):
        raise RuntimeError("no such flag")

    monkeypatch.setattr(jax.config, "update", broken_update)
    with caplog.at_level(logging.WARNING):
        cc.configure()  # must not raise
    assert any("compile cache NOT configured" in r.getMessage() for r in caplog.records)


def test_no_python_file_outside_compile_cache_names_a_cache_path():
    """Every caller goes through ``configure()``: a cache path (or the jax
    option / environment variable that sets one) spelled anywhere else is a
    second rule."""
    pattern = re.compile(
        r"jax_cache|jax_compilation_cache_dir|JAX_COMPILATION_CACHE_DIR")
    allowed = {
        os.path.join("distar_tpu", "utils", "compile_cache.py"),
        os.path.join("tests", "test_compile_cache.py"),
    }
    offenders = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".") and d not in (
            "__pycache__", "experiments", "chiprun_out")]
        for name in files:
            path = os.path.join(root, name)
            rel = os.path.relpath(path, REPO)
            if not name.endswith(".py") or rel in allowed:
                continue
            with open(path, errors="replace") as f:
                if pattern.search(f.read()):
                    offenders.append(rel)
    assert offenders == []
