"""Where the persistent compilation cache goes: placed from outside by
``JAX_COMPILATION_CACHE_DIR``, else one fixed directory in the checkout."""

import os
import subprocess
import sys

import jax
import pytest

from conftest import REPO_ROOT
from gpt_2_distributed_tpu.compile_cache import (
    DEFAULT_CACHE_DIR,
    ENV_VAR,
    ensure_compile_cache,
)


@pytest.fixture()
def restore_jax_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_set_sets_nothing_in_code(monkeypatch, restore_jax_cache_dir):
    monkeypatch.setenv(ENV_VAR, "/somewhere/else")
    before = jax.config.jax_compilation_cache_dir
    assert ensure_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == before
    assert os.environ[ENV_VAR] == "/somewhere/else"


def test_unset_uses_the_fixed_checkout_directory(monkeypatch, restore_jax_cache_dir):
    monkeypatch.delenv(ENV_VAR, raising=False)
    first = ensure_compile_cache()
    assert first == DEFAULT_CACHE_DIR == os.path.join(REPO_ROOT, ".jax_cache")
    # jax was imported long before: the config is told, and children inherit.
    assert jax.config.jax_compilation_cache_dir == first
    assert os.environ[ENV_VAR] == first
    assert ensure_compile_cache() == first


def test_same_directory_in_another_process():
    env = {k: v for k, v in os.environ.items() if k != ENV_VAR}
    code = ("from gpt_2_distributed_tpu.compile_cache import "
            "ensure_compile_cache as f; print(f()); print(f())")
    paths = [
        subprocess.run([sys.executable, "-c", code], cwd=REPO_ROOT, env=env,
                       capture_output=True, text=True, check=True,
                       timeout=60).stdout.split()
        for _ in range(2)
    ]
    assert paths == [[DEFAULT_CACHE_DIR] * 2] * 2


def test_helper_imports_without_jax():
    """The front-door parents that stay off jax call it too."""
    code = ("import sys; from gpt_2_distributed_tpu.compile_cache import "
            "ensure_compile_cache as f; f(); print('jax' in sys.modules)")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO_ROOT,
                         capture_output=True, text=True, check=True, timeout=60)
    assert out.stdout.strip() == "False"


def test_default_directory_is_git_ignored():
    with open(os.path.join(REPO_ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_suite_never_writes_the_checkout_cache():
    """conftest turns JAX's own switch off for this process and (through the
    environment) every child, so CLI tests that call the helper place the
    directory and then write nothing into it."""
    assert jax.config.jax_enable_compilation_cache is False
    assert os.environ["JAX_ENABLE_COMPILATION_CACHE"] == "false"
