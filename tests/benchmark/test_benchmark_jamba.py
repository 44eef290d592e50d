"""The family ``jamba`` in the benchmark: its entries in ``BENCHMARK.json``,
its configuration file against the published config and the package's preset,
its mix, its limit, its arithmetic, the serving cell's own functions at the CPU
tests' size, its readers on made-up counters and on a recorded trace, and its
two step programs compiled for a described v5e at the real sizes."""

import functools
import os
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest

from benchmark import harness, scopes, serve_cell, traffic
from benchmark.reduce_trace import NoKernelEvent, Trace
from tests.benchmark.bench_tiny import CPU_DEVICE
from tests.test_jamba_model import CONFIG, config_file

CELL = "serve-jamba-docs"
SMALL_TRACE = os.path.join(os.path.dirname(__file__), "small_serve.xplane.pb")
ref = harness.load_module("reference", "jamba")

# ai21labs/AI21-Jamba2-3B config.json, as the catalog has it
PUBLISHED = {
    "attn_layer_offset": 7, "attn_layer_period": 14, "expert_layer_offset": 1,
    "expert_layer_period": 2, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 8192, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_state": 16, "mamba_dt_rank": 160, "mamba_expand": 2,
    "mamba_proj_bias": False, "max_position_embeddings": 262144, "model_type": "jamba",
    "num_attention_heads": 20, "num_experts": 1, "num_experts_per_tok": 1,
    "num_hidden_layers": 28, "num_key_value_heads": 1, "num_logits_to_keep": 1,
    "rms_norm_eps": 1e-06, "sliding_window": None, "tie_word_embeddings": True,
    "use_mamba_kernels": True, "vocab_size": 65536,
}


@pytest.fixture(scope="module")
def cell():
    return harness.load_cell(CELL)


JOINED = ("serve_tok_s", "batch_occupancy", "prefill_ms", "decode_step_ms", "mfu_pct.decode",
          "mfu_pct.prefill", "device_idle_pct.serve", "engine_host_ms", "decode_dispatch_ms",
          "setup_compile_s", "setup_programs")
SHARE_READERS = ("sscan_share_pct", "mqa_attend_share_pct")
NEW_READERS = ("sscan_share_pct", "sscan_prefill_roofline", "mqa_attend_share_pct")


def test_the_manifest_names_the_configuration_the_cell_and_its_readers(cell):
    """Found by name, and after what was there: a later family appends after
    these, so nothing here asks to be last."""
    manifest = harness.load_json(harness.ROOT, "BENCHMARK.json")
    names = lambda entries: [e["name"] for e in entries]
    configs, cells = names(manifest["configs"]), names(manifest["workloads"])
    assert configs.index("jamba2-3b") > configs.index("nemotron3-nano-l14")
    assert manifest["configs"][configs.index("jamba2-3b")] == {
        "name": "jamba2-3b", "source": cell["config_file"]["source"],
        "file": "benchmark/configs/jamba2-3b.json", "reduced": [],
        "why": manifest["configs"][configs.index("jamba2-3b")]["why"]}
    assert cells.count(CELL) == 1 and cells.index(CELL) > cells.index("serve-nemotron-reasoning")
    entry = manifest["workloads"][cells.index(CELL)]
    assert (entry["config"], entry["traffic"], entry["chips"]) == ("jamba2-3b", "docs-backlog", 1)
    assert len(entry["why"]) <= 200 and "selective scan" in entry["why"]
    metrics = {m["name"]: m for m in manifest["end_to_end"] + manifest["per_layer"]}
    for name in JOINED:                                  # appended, nothing taken away
        listed = metrics[name]["workloads"]
        assert listed.count(CELL) == 1
        assert listed.index(CELL) > listed.index("serve-nemotron-reasoning")
    better = {"sscan_share_pct": "lower", "sscan_prefill_roofline": "higher",
              "mqa_attend_share_pct": "lower"}
    for name in NEW_READERS:
        assert metrics[name]["workloads"] == [CELL]
        assert (metrics[name]["moves"], metrics[name]["unit"], metrics[name]["layer"],
                metrics[name]["source"], metrics[name]["better"]) == (
            "serve_tok_s", "%", "Kernels", "device_trace", better[name])
        assert os.path.exists(os.path.join(harness.BENCH_DIR, "metrics", name + ".py"))
    layers = names(manifest["per_layer"])
    assert [layers.index(n) for n in NEW_READERS] == sorted(layers.index(n) for n in NEW_READERS)
    assert layers.index("sscan_share_pct") > layers.index("attend_share_pct")
    assert [m["name"] for m in cell["end_to_end"]] == ["serve_tok_s", "setup_s"]
    assert {m["name"] for m in cell["per_layer"]} == set(JOINED[1:]) | set(NEW_READERS)


def test_the_limit_lies_between_its_two_readings(cell):
    """No router in the model: the program's largest reading and the fp8
    control's least lie well apart, and the limit is near their geometric
    mean with room on both sides."""
    (name,) = cell["limits"]
    limit = cell["limits"][name]
    assert name == "token_logit_gap"
    assert limit["lower"] * 1.5 < limit["limit"] < limit["upper"] / 1.5
    assert abs(limit["limit"] / (limit["lower"] * limit["upper"]) ** 0.5 - 1) < 0.25


def test_configuration_is_the_published_one_with_nothing_reduced(cell):
    from gpt_2_distributed_tpu.config import JAMBA_PRESETS

    cfg = cell["config_file"]
    assert cfg["reduced"] == [] and cfg["family"] == "jamba"
    for key, value in PUBLISHED.items():
        assert cfg[key] == value, key
    assert cfg["deployment"] == (
        "one chip holds the whole model; nothing shared, nothing left out")
    assert sorted(cfg["assumed"]) == [
        "conv_init", "feed_forward", "head_dim", "head_dim_why", "initializer_range",
        "initializer_why", "inner_norms", "layer_order", "positions", "ssm_init",
        "ssm_state_dtype", "time_step_range"]
    assert cell["program"].model_config(cfg) == JAMBA_PRESETS["jamba2-3b"]
    serve = cell["program"].serve_config(cfg, cell["mix"])
    assert (serve.max_batch, serve.block_size, serve.num_blocks, serve.prefill_chunk) \
        == (16, 64, 16 * 512 + 1, 1024)
    assert serve.max_seq_len == cell["mix"]["max_total"] == 32768
    assert (serve.prefix_cache, serve.admission, cfg["serve"]["temperature"]) \
        == (False, "reserve", 0)
    assert cfg["precision"]["ssm_state"] == "float32"


def test_traffic_is_the_issues_round(cell):
    mix = cell["mix"]
    assert (mix["kind"], mix["pool"], mix["min_queue_slots"], mix["check_tokens"]) == (
        "backlog", 16, 1.0, 400)
    assert mix["prompt"] == {"dist": "lognormal", "median": 6144, "sigma": 0.8,
                             "min": 1024, "max": 28672}
    assert mix["output"] == {"dist": "lognormal", "median": 512, "sigma": 0.6,
                             "min": 64, "max": 4096}
    pool = traffic.length_pool(mix)
    prompts, outputs = sorted(p for p, _ in pool), sorted(o for _, o in pool)
    assert len(pool) == 16 and max(p + o for p, o in pool) <= 32768
    assert (prompts[0], prompts[-1], sum(prompts)) == (1384, 27267, 131066)
    assert (outputs[0], outputs[-1], sum(outputs)) == (167, 1566, 9649)
    assert sum(-(-p // 1024) for p in prompts) == 137            # chunks a round
    # every seed gets the round in one order, with its own ids
    a, b = (traffic.requests(mix, cell["sizes"]["vocab_size"], s) for s in (1, 2**31 + 5))
    first_a, first_b = next(a), next(b)
    assert len(first_a.prompt) == len(first_b.prompt) == pool[0][0]
    assert first_a.prompt != first_b.prompt and max(first_b.prompt) < 65536


def test_arithmetic_counts_what_the_equations_ask(cell):
    sizes = cell["sizes"]
    c, d, f = 2560, 5120, 8192
    mamba = c * 2 * d + d * (160 + 32) + 160 * d + d * c
    attention = 2 * c * 2560 + 2 * c * 128
    assert sizes["pattern"] == "MMMMMMM*MMMMMMMMMMMMM*MMMMMM" and sizes["head_dim"] == 128
    assert ref.mixer_matmul_params(sizes, "M") == mamba
    assert ref.mixer_matmul_params(sizes, "*") == attention
    assert ref.mlp_params(sizes) == 3 * c * f
    assert [round(ref.layer_params(sizes, k) / 1e6, 2) for k in "M*"] == [104.16, 76.68]
    assert round(ref.num_params(sizes) / 1e6) == 3029
    assert round(ref.num_params(sizes) * 2 / 1e9, 2) == 6.06     # bfloat16 bytes
    ssm = 2 * 4 * d + 7 * d * 16
    assert ref.forward_flops_per_token(sizes, 1000) == pytest.approx(
        2.0 * (26 * mamba + 2 * attention + 28 * 3 * c * f + c * 65536) + 26 * ssm
        + 2 * 4.0 * 20 * 128 * 1000)
    assert ref.forward_flops_per_token(sizes, 900) - ref.prefill_flops_per_token(
        sizes, 900) == 2.0 * c * 65536
    assert ref.attention_shapes(sizes) == {
        "kv_layers": 2, "heads": 20, "kv_heads": 1, "head_dim": 128}
    # one chunk of 1,024 tokens through 26 layers: 83.9 M state updates a layer
    ops, nbytes = ref.selective_scan_work(sizes, 1024 * 26, chunk=1024)
    assert ops == 7.0 * 1024 * 26 * d * 16 and 1024 * d * 16 == 83_886_080
    assert nbytes == 26 * (1024 * (3 * d * 2 + d * 4 + 2 * 16 * 4) + 2 * d * 16 * 4)
    assert ref.selective_scan_work(sizes, 512 * 26, chunk=512)[1] \
        == 26 * (512 * (3 * d * 2 + d * 4 + 2 * 16 * 4) + 2 * d * 16 * 4)


def tiny_cell():
    mix = {"kind": "backlog", "base_seed": 7, "pool": 8,
           "prompt": {"dist": "lognormal", "median": 40, "sigma": 0.4, "min": 20, "max": 80},
           "output": {"dist": "lognormal", "median": 6, "sigma": 0.5, "min": 2, "max": 12},
           "max_total": 128, "min_queue_slots": 1.0, "check_tokens": 20}
    cfg = dict(config_file(), name="jamba-tiny", family="jamba",
               serve={"max_batch": 3, "block_size": 8, "prefill_chunk": 16,
                      "prefix_cache": False, "admission": "reserve", "temperature": 0})
    return harness.attach_family({
        "name": f"jamba-tiny-{os.getpid()}", "config": "jamba-tiny",
        "traffic": "backlog", "chips": 1, "config_file": cfg, "mix": mix,
        "limits": {"token_logit_gap": {"limit": 0.01}},
        "end_to_end": [{"name": n, "unit": "x"} for n in ("serve_tok_s", "setup_s")],
        "per_layer": []})


def test_the_cells_own_functions_run_at_the_tiny_size():
    cell = tiny_cell()
    assert cell["program"].model_config(cell["config_file"]) == CONFIG
    result = serve_cell.run(cell, 2**31 + 35, 1.5, False, dict(CPU_DEVICE),
                            time.monotonic(), harness.CompileCounter())
    assert result["correct"] is True and result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"serve_tok_s", "setup_s"}
    (row,) = result["compared"]
    # bfloat16 weights served in bfloat16 against the float32 reference
    assert row["name"] == "token_logit_gap" and 0 <= row["value"] <= 0.01


def test_the_joined_prefill_reader_counts_this_familys_arithmetic(cell):
    ctx = {"cell": cell, "sizes": cell["sizes"], "peaks": {"flops_per_s_bf16": 197e12},
           "stats": {"prefill_tokens": 51200, "prefill_attended": 51200 * 4000,
                     "prefill_ms": 8000.0}}
    mfu = harness.load_reader("mfu_pct.prefill")(ctx)
    assert mfu == pytest.approx(
        100 * 51200 * ref.prefill_flops_per_token(cell["sizes"], 4000) / 8.0 / 197e12)
    assert 15 < mfu < 25                                  # 5.8 GFLOP a token, 156 ms a chunk


def test_trace_readers_on_a_recorded_trace(cell, monkeypatch, tmp_path):
    """The recorded trace is GPT-2's: it holds none of this family's scopes,
    which fails a run; pointed at a scope it does hold (the decode step's
    layer loop) each reader gives that scope's time against its measure."""
    trace = Trace.from_file(SMALL_TRACE)
    lo, hi = trace.window_ns()
    busy = trace.busy_seconds(lo, hi)
    peaks = {"flops_per_s_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    traced = {"sscan_tokens": 20 * 1024 * 26, "sscan_rows": 300}
    ctx = {"cell": cell, "sizes": cell["sizes"], "peaks": peaks, "traced_stats": traced,
           "trace_window_ns": (lo, hi), "device": {"busy_s": busy}}
    # nothing without a kept trace, and nothing from a program without the counter
    monkeypatch.setenv("BENCH_KEEP_TRACE", str(tmp_path / "none.xplane.pb"))
    for name in NEW_READERS:
        assert harness.load_reader(name)(ctx) is None
    monkeypatch.setenv("BENCH_KEEP_TRACE", SMALL_TRACE)
    assert harness.load_reader("sscan_prefill_roofline")(
        {**ctx, "traced_stats": {"decode_rows": 8}}) is None
    for name in NEW_READERS:
        with pytest.raises(NoKernelEvent):
            harness.load_reader(name)(ctx)
    # a scope the trace holds
    scope = "while/body/closed_call"
    loop = scopes.scope_seconds(SMALL_TRACE, lo, hi, (scope,))
    decode = scopes.scope_seconds(SMALL_TRACE, lo, hi, (scope,), "jit(decode_step)")
    assert 0 < decode < loop <= busy
    for name in SHARE_READERS:
        monkeypatch.setattr(harness.load_module("metrics", name), "SCOPES", (scope,))
        assert harness.load_reader(name)(ctx) == pytest.approx(100 * loop / busy)
    roofline = harness.load_module("metrics", "sscan_prefill_roofline")
    assert (roofline.SCOPE, roofline.PROGRAM) == ("jamba/sscan", "jit(chunk_prefill)")
    monkeypatch.setattr(roofline, "SCOPE", scope)
    monkeypatch.setattr(roofline, "PROGRAM", "jit(decode_step)")
    ops, nbytes = ref.selective_scan_work(cell["sizes"], traced["sscan_tokens"], 1024)
    least = max(ops / 197e12, nbytes / 819e9)
    assert least == nbytes / 819e9                        # bound by memory
    assert roofline.read(ctx) == pytest.approx(100 * least / decode)


def test_step_programs_compile_for_a_v5e_at_the_cells_sizes(cell, monkeypatch):
    """Both programs as the engine builds them, 16 slots of 32,768 tokens:
    weights, pools, both states and each program's temporaries well under the
    15.75 GB a program can have, and over the quarter of the chip a cell has
    to fill; the chunk program holds the scan's kernel, compiled by Mosaic.
    Nothing runs: a pass here is not a chip run."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    from gpt_2_distributed_tpu.serving import jamba_programs
    from gpt_2_distributed_tpu.serving.paged_cache import pool_shape

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu / unknown topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # the scan asks the first device whether to take its kernel
    monkeypatch.setattr(jax, "devices", lambda *a, **k: list(topo.devices))
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
        == pytest.approx(6.06e9, rel=2e-3)
    pool = arr(pool_shape(config.kv_pool_view, serve), jnp.bfloat16)
    state = on_chip(jax.eval_shape(
        lambda: jamba_programs.init_state(config, serve, jnp.bfloat16)))
    assert state["ssm"].shape == (26, 16, 16, 5120) and state["conv"].shape == (26, 16, 3 * 5120)
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
        decode = jax.jit(functools.partial(jamba_programs.decode_step_impl, **static),
                         donate_argnames=donate).lower(
            params, pool, pool, state, arr((b, m), i32), arr((b,), i32), arr((b,), i32),
            arr((b,), jnp.bool_), arr((b, 2), jnp.uint32)).compile()
        chunk = jax.jit(functools.partial(jamba_programs.chunk_prefill_impl, **static),
                        donate_argnames=donate).lower(
            params, pool, pool, state, arr((1, m), i32), arr((1, c), i32), arr((1,), i32),
            arr((1,), i32), arr((1, 2), jnp.uint32), arr((1,), i32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        jax.config.update("jax_default_matmul_precision", precision_was)
        compilation_cache.reset_cache()
    text = chunk.as_text()
    assert text.count("tpu_custom_call") >= 26 and "selective_scan_chunk" in text
    assert "tpu_custom_call" not in decode.as_text()      # the one-token update is XLA
    for compiled in (decode, chunk):
        mem = compiled.memory_analysis()
        need = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                + mem.output_size_in_bytes - mem.alias_size_in_bytes)
        assert 6.7e9 < need < 8.0e9
        assert mem.temp_size_in_bytes < 0.5e9             # a chunk's temporaries, not [T, N, D]
