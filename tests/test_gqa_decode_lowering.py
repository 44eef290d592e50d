"""The state families' decode programs lowered for a DESCRIBED TPU v5e, at
their cells' sizes: the grouped-query decode kernel is lowered once per
program, however many attention layers call it, and what it is handed does
not grow with the table. Lowering only, nothing compiled or run; no timing
(a CPU host's lowering time says little). Skipped where no v5e can be
described."""

import functools
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# cell -> attention layers of its decode program
CELLS = {"serve-nemotron-reasoning": 2, "serve-jamba-docs": 2, "serve-sala-longdoc": 4}
KERNEL = "paged_gqa_decode"


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu / unknown topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def _lowered(workload, width, topo, monkeypatch) -> str:
    """The cell's decode program, as its family builds it, at a block-table
    width of ``width``, lowered for one chip of ``topo``."""
    from benchmark import harness
    from gpt_2_distributed_tpu.serving.families import family_of
    from gpt_2_distributed_tpu.serving.paged_cache import pool_shape

    # the attention asks the first device whether it runs on a TPU
    monkeypatch.setattr(jax, "devices", lambda *a, **k: list(topo.devices))
    monkeypatch.setenv("BENCH_KEEP_TRACE", "")      # a program module sets it on import
    chip = SingleDeviceSharding(topo.devices[0])
    cell = harness.load_cell(workload)
    config = cell["program"].model_config(cell["config_file"])
    serve = cell["program"].serve_config(cell["config_file"], cell["mix"])
    family = family_of(config)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def on_chip(tree):
        return jax.tree_util.tree_map(lambda a: arr(a.shape, a.dtype), tree)

    params = on_chip(jax.eval_shape(
        lambda: family.init_params(config, jax.random.PRNGKey(0))))
    pool = arr(pool_shape(config.kv_pool_view, serve), jnp.bfloat16)
    state = on_chip(jax.eval_shape(
        lambda: family.init_state(config, serve, jnp.bfloat16)))
    b, i32 = serve.max_batch, jnp.int32
    decode = functools.partial(
        family.decode_impl, config=config, temperature=0.0, top_k=None)
    # lower at the precision the program runs at, not conftest's "highest"
    precision_was = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", None)
    try:
        return jax.jit(decode, donate_argnames=("k_pool", "v_pool", "state")).lower(
            params, pool, pool, state, arr((b, width), i32), arr((b,), i32),
            arr((b,), i32), arr((b,), jnp.bool_), arr((b, 2), jnp.uint32)).as_text()
    finally:
        jax.config.update("jax_default_matmul_precision", precision_was)


def _kernel_calls(text: str) -> list[int]:
    """The operand count of each custom call of the kernel in ``text``."""
    found = []
    for line in text.splitlines():
        call = re.search(r"stablehlo\.custom_call @tpu_custom_call\(([^)]*)\)", line)
        if call and KERNEL in line:
            found.append(len(call.group(1).split(",")))
    return found


@pytest.mark.parametrize("workload", CELLS)
def test_decode_program_lowers_the_kernel_once(workload, topo, monkeypatch):
    """One custom call of the kernel in the program's text, in one function
    that each attention layer calls; the same operands at a table of 96
    blocks as at one of 512 (the list is prefetched as scalars, the pools
    stay in HBM: nothing in the lowering grows with the width)."""
    operands = []
    for width in (96, 512):
        text = _lowered(workload, width, topo, monkeypatch)
        calls = _kernel_calls(text)
        assert len(calls) == 1, calls
        assert text.count(f"call @{KERNEL}(") == CELLS[workload]
        operands += calls
    assert operands[0] == operands[1]
