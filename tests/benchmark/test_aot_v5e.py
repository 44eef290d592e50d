"""The three cells' step programs, compiled for a DESCRIBED v5e: nothing
runs, and a pass here is not a chip run. What it guards: the shapes the
benchmark times lower for the chip at all, and the 1.5B serving cell's
``max_batch`` (``benchmark/configs/gpt2-xl.json``) still fits - weights,
pools and the decode program's temporaries under 90% of the chip's memory.

The topology is described inside a fixture, never at import: see the
``on-chip-measurement`` guide, section 2.
"""

import functools
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

HBM_BYTES = 16e9
I32, F32, BF16 = jnp.int32, jnp.float32, jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu / unknown topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_was = jax.config.jax_enable_compilation_cache
    precision_was = jax.config.jax_default_matmul_precision
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_default_matmul_precision", None)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", cache_was)
    jax.config.update("jax_default_matmul_precision", precision_was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _cell(workload):
    from benchmark import harness

    return harness.load_cell(workload)


def _on_chip(tree, chip):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), tree)


def _need(compiled):
    mem = compiled.memory_analysis()
    return (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)


def test_train_step_124m_b8(chip, topo, monkeypatch):
    """The guarded step as ``train-124m-1k`` builds it, at 12 x 8 x 1024."""
    from benchmark import harness
    from gpt_2_distributed_tpu import train as trainer
    from gpt_2_distributed_tpu.parallel.train_step import (
        make_optimizer, make_train_step)
    from gpt_2_distributed_tpu.resilience import init_guard_state

    monkeypatch.setattr(jax, "devices", lambda *a, **k: list(topo.devices))
    cell = _cell("train-124m-1k")
    args = trainer.build_parser().parse_args(cell["program"].trainer_flags(
        cell["config_file"], cell["mix"], "unused", 0))
    config = cell["program"].train_model_config(args)
    optimizer = make_optimizer(args.lr, weight_decay=args.weight_decay)
    step = make_train_step(config, optimizer, guard=True)
    params = jax.eval_shape(
        lambda: cell["reference"].make_weights(cell["sizes"], 0))
    opt_state = jax.eval_shape(optimizer.init, params)
    shape = (args.grad_accum_steps, args.batch, args.seq_len)
    assert shape == (12, 8, 1024)
    tokens = jax.ShapeDtypeStruct(shape, I32, sharding=chip)
    compiled = step.lower(
        _on_chip(params, chip), _on_chip(opt_state, chip),
        _on_chip(jax.eval_shape(init_guard_state), chip), tokens, tokens,
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=chip), 0,
        jax.ShapeDtypeStruct((shape[0],), F32, sharding=chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()   # the flash kernel
    assert _need(compiled) < HBM_BYTES


def _serving_programs(workload, chip, topo, monkeypatch, extra_slots=0):
    from benchmark import harness
    from gpt_2_distributed_tpu.serving import engine as eng

    monkeypatch.setattr(jax, "devices", lambda *a, **k: list(topo.devices))
    cell = _cell(workload)
    cfg = cell["config_file"]
    config = cell["program"].model_config(cfg)
    cell["config_file"]["serve"]["max_batch"] += extra_slots
    serve = cell["program"].serve_config(cfg, cell["mix"])
    params = _on_chip(jax.eval_shape(
        lambda: cell["reference"].make_weights(cell["sizes"], 0)), chip)
    pool = jax.ShapeDtypeStruct(
        (config.n_layer, serve.num_blocks, config.n_head, serve.block_size,
         config.head_dim), BF16, sharding=chip)
    m = serve.max_blocks_per_seq(config.n_positions)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    b, c = serve.max_batch, serve.prefill_chunk
    decode = jax.jit(
        functools.partial(eng._decode_step_impl, config=config, temperature=0.0,
                          top_k=None, attn_impl=serve.attn_impl),
        donate_argnames=("k_pool", "v_pool"),
    ).lower(params, pool, pool, arr((b, m), I32), arr((b,), I32), arr((b,), I32),
            arr((b,), jnp.bool_), arr((b, 2), jnp.uint32)).compile()
    chunk = jax.jit(
        functools.partial(eng._chunk_prefill_impl, config=config,
                          temperature=0.0, top_k=None),
        donate_argnames=("k_pool", "v_pool"),
    ).lower(params, pool, pool, arr((1, m), I32), arr((1, c), I32),
            arr((1,), I32), arr((1,), I32), arr((1, 2), jnp.uint32)).compile()
    return decode, chunk


@pytest.mark.parametrize("workload", ["serve-xl-backlog"])
def test_serving_programs_fit(workload, chip, topo, monkeypatch):
    """The decode step and the 256-wide chunk prefill at the cell's
    ``max_batch``: both lower (the decode step with the paged kernel), and
    resident weights + pools + temporaries stay under 90% of the chip."""
    decode, chunk = _serving_programs(workload, chip, topo, monkeypatch)
    assert "tpu_custom_call" in decode.as_text()     # the paged-decode kernel
    for name, compiled in (("decode", decode), ("chunk prefill", chunk)):
        need = _need(compiled)
        print(f"{workload} {name}: {need / 1e9:.2f} GB "
              f"(temp {compiled.memory_analysis().temp_size_in_bytes / 1e9:.2f} GB)")
        assert need < 0.9 * HBM_BYTES, f"{name} needs {need / 1e9:.2f} GB"


def test_xl_max_batch_is_the_largest_multiple_of_4_that_fits(chip, topo, monkeypatch):
    """``max_batch`` in configs/gpt2-xl.json is fixed by this analysis: with 4
    more slots the decode program passes 90% of the chip or does not compile
    at all. (The scan over layers copies both pools and XLA hoists a bf16
    copy of every weight, so the temporaries are as large as the state.)"""
    try:
        decode, _ = _serving_programs(
            "serve-xl-backlog", chip, topo, monkeypatch, extra_slots=4)
    except jax.errors.JaxRuntimeError as e:
        assert "RESOURCE_EXHAUSTED" in str(e)
        return
    assert _need(decode) > 0.9 * HBM_BYTES
