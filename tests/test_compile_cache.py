"""The persistent compile cache's location: ``JAX_COMPILATION_CACHE_DIR``
when the environment sets it (and then nothing is set in code), else the
fixed ``.jax_cache`` directory of the checkout."""
import os
import secrets
import subprocess
import sys

import jax
import pytest

from repro import compile_cache
from repro.compile_cache import DEFAULT_DIR, ENV_VAR, enable_compile_cache

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "src")

# compile one program in a fresh process; the threshold is lowered in
# this probe only, so that a sub-second CPU compile is written at all
PROBE = """
import jax, jax.numpy as jnp
from repro.compile_cache import enable_compile_cache
print(enable_compile_cache())
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
print(float(jax.jit(lambda x: jnp.sin(x) * {n})(1.0)))
"""


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_env_var_is_honoured_and_nothing_is_set(monkeypatch, tmp_path,
                                                restore_cache_dir):
    monkeypatch.setenv(ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_the_fixed_checkout_dir(monkeypatch, restore_cache_dir):
    monkeypatch.delenv(ENV_VAR, raising=False)
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(compile_cache.__file__))))
    assert DEFAULT_DIR == os.path.join(root, ".jax_cache")
    assert enable_compile_cache() == DEFAULT_DIR
    assert jax.config.jax_compilation_cache_dir == DEFAULT_DIR
    assert enable_compile_cache() == DEFAULT_DIR      # stable


def _entries(path):
    return set(os.listdir(path)) if os.path.isdir(path) else set()


def _probe(env, n):
    out = subprocess.run([sys.executable, "-c", PROBE.format(n=n)],
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.split()[0]


@pytest.mark.parametrize("with_env", [True, False])
def test_entries_land_only_in_the_chosen_dir(tmp_path, with_env):
    env = dict(os.environ, PYTHONPATH=os.path.abspath(SRC),
               JAX_PLATFORMS="cpu")
    env.pop(ENV_VAR, None)
    if with_env:
        env[ENV_VAR] = str(tmp_path / "cache")
    before_default = _entries(DEFAULT_DIR)
    # a program no earlier run compiled, so its entry is new wherever
    # it lands
    n = secrets.randbelow(1 << 30) + 2
    try:
        where = _probe(env, n)
    finally:
        new_default = _entries(DEFAULT_DIR) - before_default
        for name in new_default:              # leave the cache as found
            os.remove(os.path.join(DEFAULT_DIR, name))
    if with_env:
        assert where == str(tmp_path / "cache")
        assert _entries(where), "no entry written to the env dir"
        assert not new_default
    else:
        assert where == DEFAULT_DIR
        assert new_default, "no entry written to the checkout dir"
