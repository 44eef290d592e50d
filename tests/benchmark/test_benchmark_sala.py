"""The family ``minicpm_sala`` in the benchmark: its configuration file
against the package's preset, its arithmetic, its cell's own functions at the
CPU tests' size, its scope reader against a recorded trace, and its two step
programs compiled for a described v5e at the cell's real sizes."""

import functools
import os
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest

from benchmark import harness, scopes, serve_cell
from benchmark.reduce_trace import NoKernelEvent, Trace
from tests.benchmark.bench_tiny import CPU_DEVICE
from tests.test_sala_model import CONFIG, config_file

CELL = "serve-sala-longdoc"
SMALL_TRACE = os.path.join(os.path.dirname(__file__), "small_serve.xplane.pb")
ref = harness.load_module("reference", "minicpm_sala")


@pytest.fixture(scope="module")
def cell():
    return harness.load_cell(CELL)


def test_configuration_is_the_published_one_cut_to_sixteen_layers(cell):
    from gpt_2_distributed_tpu.config import SALA_PRESETS

    cfg = cell["config_file"]
    assert cell["program"].model_config(cfg) == SALA_PRESETS["minicpm-sala-9b"].cut(16, 9)
    assert cfg["reduced"] == ["num_hidden_layers", "mixer_types"]
    assert cfg["num_hidden_layers"] == len(cfg["mixer_types"]) == 16
    assert cfg["published_num_hidden_layers"] == 32 == cell["sizes"]["published_num_hidden_layers"]
    assert sorted(cfg["assumed"]) == [
        "initializer_range", "initializer_why", "lightning_decay", "norm_scope",
        "rotary", "sparse", "sparse_why"]
    serve = cell["program"].serve_config(cfg, cell["mix"])
    assert (serve.max_batch, serve.block_size, serve.num_blocks) == (8, 64, 8 * 512 + 1)
    assert serve.max_seq_len == cell["mix"]["max_total"] == 32768
    assert serve.max_blocks_per_seq(cfg["max_position_embeddings"]) == 512


def test_traffic_is_the_issues_round(cell):
    from benchmark import traffic

    pool = traffic.length_pool(cell["mix"])
    prompts = sorted(p for p, _ in pool)
    assert prompts[0] == 8870 and prompts[-1] == 28672 and sum(prompts) == 138624
    assert min(prompts) > cell["config_file"]["assumed"]["sparse"]["dense_below"]
    assert sorted(o for _, o in pool)[::7] == [119, 551] and sum(o for _, o in pool) == 2274


def test_arithmetic_counts_what_the_equations_ask(cell):
    sizes = cell["sizes"]
    c, f, v = 4096, 16384, 73448
    linear, sparse = 5 * c * c + 3 * c * f, 3 * c * c + 2 * c * 256 + 3 * c * f
    assert ref.matmul_params(sizes) == 12 * linear + 4 * sparse + c * v
    assert ref.attention_shapes(sizes) == {
        "kv_layers": 4, "heads": 32, "kv_heads": 2, "head_dim": 128}
    # a long context costs what its 97 selected blocks cost, not what it sees
    far, near = (ref.forward_flops_per_token(sizes, n) for n in (30000, 6208))
    assert far - near == pytest.approx(4 * 2.0 * 32 * 128 * 30000 / 16)
    assert near == pytest.approx(
        2.0 * ref.matmul_params(sizes) + 12 * 4 * 32 * 128 * 128 + 4 * 4 * 32 * 128 * 6208)
    assert ref.selected_blocks(sizes, 8192) == 128 and ref.selected_blocks(sizes, 8193) == 97
    assert ref.forward_flops_per_token(sizes, 9000) - ref.prefill_flops_per_token(
        sizes, 9000) == 2.0 * c * v


def tiny_cell():
    mix = {"kind": "backlog", "base_seed": 7, "pool": 8,
           "prompt": {"dist": "lognormal", "median": 60, "sigma": 0.4, "min": 34, "max": 100},
           "output": {"dist": "lognormal", "median": 6, "sigma": 0.5, "min": 2, "max": 12},
           "max_total": 128, "min_queue_slots": 1.0, "check_tokens": 20}
    cfg = dict(config_file(), name="sala-tiny", family="minicpm_sala",
               serve={"max_batch": 3, "block_size": 8, "prefill_chunk": 32,
                      "prefix_cache": False, "admission": "reserve", "temperature": 0})
    return harness.attach_family({
        "name": f"sala-tiny-{os.getpid()}", "config": "sala-tiny", "traffic": "backlog",
        "chips": 1, "config_file": cfg, "mix": mix,
        "limits": {"token_logit_gap": {"limit": 0.01}},
        "end_to_end": [{"name": n, "unit": "x"} for n in ("serve_tok_s", "setup_s")],
        "per_layer": []})


def test_the_cells_own_functions_run_at_the_tiny_size():
    cell = tiny_cell()
    assert cell["program"].model_config(cell["config_file"]) == CONFIG
    result = serve_cell.run(cell, 2**31 + 28, 1.5, False, dict(CPU_DEVICE),
                            time.monotonic(), harness.CompileCounter())
    assert result["correct"] is True and result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"serve_tok_s", "setup_s"}
    (row,) = result["compared"]
    # bfloat16 weights served in bfloat16 against the float32 reference
    assert row["name"] == "token_logit_gap" and 0 <= row["value"] <= 0.01


def test_counter_readers():
    cell = {"reference": ref}
    sizes = ref.sizes_of(harness.load_json(harness.BENCH_DIR, "configs", "minicpm-sala-l16.json"))
    ctx = {"cell": cell, "sizes": sizes, "peaks": {"flops_per_s_bf16": 197e12},
           "stats": {"prefill_tokens": 20480, "prefill_attended": 20480 * 6000,
                     "prefill_ms": 1500.0, "sparse_selected": 30, "sparse_visible": 120}}
    mfu = harness.load_reader("mfu_pct.prefill")(ctx)
    assert mfu == pytest.approx(
        100 * 20480 * ref.prefill_flops_per_token(sizes, 6000) / 1.5 / 197e12)
    assert 50 < mfu < 100
    assert harness.load_reader("sparse_keep_pct")(ctx) == 25.0
    gpt2_stats = {"prefill_ms": 3.0, "prefill_dispatches": 2}     # no such counters
    for name in ("mfu_pct.prefill", "sparse_keep_pct"):
        assert harness.load_reader(name)({**ctx, "stats": gpt2_stats}) is None


def test_scopes_reads_op_names_out_of_a_recorded_trace(monkeypatch, tmp_path):
    trace = Trace.from_file(SMALL_TRACE)
    (plane, events), = scopes.device_ops(SMALL_TRACE).items()
    assert [e[0] for e in events] == [e[0] for e in trace.device_ops[plane]]
    assert abs(events[0][2] - trace.device_ops[plane][0][1]) < 2        # ns
    assert any(op.startswith("jit(decode_step)/while/body/closed_call/") for _, op, *_ in events)
    lo, hi = trace.window_ns()
    busy = trace.busy_seconds(lo, hi)
    loop = scopes.scope_seconds(SMALL_TRACE, lo, hi, ("while/body/closed_call",))
    decode = scopes.scope_seconds(
        SMALL_TRACE, lo, hi, ("while/body/closed_call",), "jit(decode_step)")
    assert 0 < decode < loop <= busy * 1.0001
    assert scopes.scope_seconds(SMALL_TRACE, lo, hi, ("sala/select",)) == 0.0
    # the two trace readers: nothing without a kept trace, a failed run where
    # the kept trace holds no operation of their scopes
    ctx = {"traced_stats": {"decode_rows": 8, "decode_attended": 8 * 6208},
           "trace_window_ns": (lo, hi), "device": {"busy_s": busy}}
    monkeypatch.setenv("BENCH_KEEP_TRACE", str(tmp_path / "none.xplane.pb"))
    for name in ("sparse_attn_roofline", "mixer_share_pct"):
        assert harness.load_reader(name)(ctx) is None
    monkeypatch.setenv("BENCH_KEEP_TRACE", SMALL_TRACE)
    for name in ("sparse_attn_roofline", "mixer_share_pct"):
        with pytest.raises(NoKernelEvent):
            harness.load_reader(name)(ctx)


def test_step_programs_compile_for_a_v5e_at_the_cells_sizes(cell):
    """Both programs as the engine builds them, 8 slots of 32,768 tokens:
    weights, pools, state and each program's temporaries under the chip's
    16 GB. Nothing runs: a pass here is not a chip run."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    from gpt_2_distributed_tpu.serving import sala_programs
    from gpt_2_distributed_tpu.serving.paged_cache import pool_shape

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu / unknown topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    chip = SingleDeviceSharding(topo.devices[0])
    cfg = cell["config_file"]
    config = cell["program"].model_config(cfg)
    serve = cell["program"].serve_config(cfg, cell["mix"])

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def on_chip(tree):
        return jax.tree_util.tree_map(lambda a: arr(a.shape, a.dtype), tree)

    params = on_chip(jax.eval_shape(lambda: ref.make_weights(cell["sizes"], 0)))
    assert sum(a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(params)) \
        == pytest.approx(10.08e9, rel=1e-3)
    pool = arr(pool_shape(config.kv_pool_view, serve), jnp.bfloat16)
    state = on_chip(jax.eval_shape(
        lambda: sala_programs.init_state(config, serve, jnp.bfloat16)))
    b, c, m = serve.max_batch, serve.prefill_chunk, 512
    i32 = jnp.int32
    static = dict(config=config, temperature=0.0, top_k=None)
    donate = ("k_pool", "v_pool", "state")
    cache_was = jax.config.jax_enable_compilation_cache
    precision_was = jax.config.jax_default_matmul_precision
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_default_matmul_precision", None)
    compilation_cache.reset_cache()
    try:
        decode = jax.jit(functools.partial(sala_programs.decode_step_impl, **static),
                         donate_argnames=donate).lower(
            params, pool, pool, state, arr((b, m), i32), arr((b,), i32), arr((b,), i32),
            arr((b,), jnp.bool_), arr((b, 2), jnp.uint32)).compile()
        chunk = jax.jit(functools.partial(sala_programs.chunk_prefill_impl, **static),
                        donate_argnames=donate).lower(
            params, pool, pool, state, arr((1, m), i32), arr((1, c), i32), arr((1,), i32),
            arr((1,), i32), arr((1, 2), jnp.uint32), arr((1,), i32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        jax.config.update("jax_default_matmul_precision", precision_was)
        compilation_cache.reset_cache()
    for compiled in (decode, chunk):
        mem = compiled.memory_analysis()
        need = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                + mem.output_size_in_bytes - mem.alias_size_in_bytes)
        assert 11.3e9 < need < 0.9 * 16e9
