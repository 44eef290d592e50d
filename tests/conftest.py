"""Test environment: force an 8-device virtual CPU platform BEFORE jax import.

This is the multi-device-without-a-cluster strategy from SURVEY.md §4: DP and
FSDP sharding tests run against 8 virtual CPU devices, so the full parallelism
surface is exercised in CI with no TPU attached.
"""

import os
import re

os.environ["JAX_PLATFORMS"] = "cpu"
# The CLIs place JAX's persistent compilation cache in the checkout
# (gpt_2_distributed_tpu/compile_cache.py). Tests must not write there, and
# the AOT compiles for a described TPU could not be read back anyway, so
# JAX's own switch is off for this process and every child it starts.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
# Force exactly 8 virtual devices, replacing any pre-existing count in the
# environment (a mismatched count would trip the device assert below and
# error the whole session).
_flags = re.sub(
    r"--xla_force_host_platform_device_count=\d+", "",
    os.environ.get("XLA_FLAGS", ""),
).strip()
os.environ["XLA_FLAGS"] = (
    _flags + " --xla_force_host_platform_device_count=8"
).strip()

import jax
import numpy as np
import pytest

# The env var above covers children; this covers a jax that something
# imported before conftest ran.
jax.config.update("jax_platforms", "cpu")
# Matmuls default to a reduced-precision fastmath mode (bf16-class, ~1e-1 abs
# error on unit-scale fp32 matmuls); golden-parity tests need real fp32.
jax.config.update("jax_default_matmul_precision", "highest")

# Fail fast if the virtual 8-device platform did not take effect — otherwise
# every sharding test silently degenerates to a replicated single-device mesh
# and the parallelism layer ships unverified.
assert jax.device_count() == 8, (
    f"expected 8 virtual CPU devices, got {jax.device_count()}: {jax.devices()}"
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def forced_host_device_env(n_devices: int,
                           extra: dict | None = None) -> dict:
    """Subprocess env pinned to exactly ``n_devices`` virtual CPU devices.

    The same force-before-jax-import dance this conftest does for the test
    process itself, packaged for child processes. The implementation lives
    in ``gpt_2_distributed_tpu.resilience.forced_host_device_env`` — the
    worker spawner uses it to pin process-isolated serving replicas on CPU
    hosts — and this delegation keeps test subprocesses on the exact same
    env recipe. ``extra`` overlays additional vars last.
    """
    from gpt_2_distributed_tpu.resilience import forced_host_device_env as f

    return f(n_devices, extra)


# The serving CLIs, each with the least it needs to get past argparse.
SERVING_CLIS = {
    "serve": ["-m", "gpt_2_distributed_tpu.serving.serve",
              "--init_random", "--requests", "-"],
    "frontend": ["-m", "gpt_2_distributed_tpu.serving.frontend.server",
                 "--init_random"],
    "worker": ["-m", "gpt_2_distributed_tpu.serving.frontend.worker",
               "--init_random"],
}


@pytest.fixture()
def run_jax_free(tmp_path):
    """``run(*argv)`` starts ``python *argv`` with a poisoned ``jax`` first
    on PYTHONPATH: whatever it printed and exited with, it did before jax
    loaded."""
    import subprocess
    import sys

    poison = tmp_path / "poison"
    (poison / "jax").mkdir(parents=True)
    (poison / "jax" / "__init__.py").write_text(
        "raise ImportError('touched jax at parse time')\n"
    )
    env = dict(os.environ, PYTHONPATH=f"{poison}{os.pathsep}{REPO_ROOT}")

    def run(*argv):
        return subprocess.run(
            [sys.executable, *argv], cwd=REPO_ROOT, env=env,
            stdin=subprocess.DEVNULL, capture_output=True, text=True,
            timeout=120,
        )

    return run


@pytest.fixture()
def run_cli_jax_free(run_jax_free):
    """``run(cli, *flags)``: one of :data:`SERVING_CLIS` under
    :func:`run_jax_free`."""
    return lambda cli, *flags: run_jax_free(*SERVING_CLIS[cli], *flags)


@pytest.fixture(scope="session")
def shard_dir(tmp_path_factory):
    """Synthetic uint16 .bin shards shared across tests."""
    from gpt_2_distributed_tpu.data.synthetic import write_synthetic_shards

    d = tmp_path_factory.mktemp("shards")
    write_synthetic_shards(
        str(d), num_shards=5, tokens_per_shard=4096, vocab_size=257, seed=1234
    )
    return str(d)


@pytest.fixture(scope="session")
def tiny_config():
    from gpt_2_distributed_tpu.config import GPT2Config

    return GPT2Config(
        vocab_size=257,
        n_positions=64,
        n_embd=32,
        n_layer=2,
        n_head=2,
        embd_dropout=0.0,
        attn_dropout=0.0,
        resid_dropout=0.0,
    )


@pytest.fixture()
def rng_np():
    """Function-scoped: every test draws from a fresh seeded stream, so test
    data never depends on collection order (a session-scoped mutable rng made
    the whole suite order-dependent — round-1 VERDICT weak-point #2)."""
    return np.random.default_rng(0)
