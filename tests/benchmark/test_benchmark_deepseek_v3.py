"""The family ``deepseek_v3`` in the benchmark: its entries in
``BENCHMARK.json``, its configuration file against the published config and
the package's preset, its mix, its limit, its arithmetic, the serving cell's
own functions at the CPU tests' size, its readers on made-up counters and on a
recorded trace, and its two step programs compiled for a described v5e at the
real sizes."""

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
from tests.test_deepseek_v3_model import CONFIG, config_file

CELL = "serve-kanana-longqa"
SMALL_TRACE = os.path.join(os.path.dirname(__file__), "small_serve.xplane.pb")
ref = harness.load_module("reference", "deepseek_v3")

# kakaocorp/kanana-2-30b-a3b-instruct-2601 config.json, as the catalog has it
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "kv_lora_rank": 512, "max_position_embeddings": 32768, "model_type": "deepseek_v3",
    "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 128, "n_shared_experts": 2, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 6, "num_hidden_layers": 48,
    "num_key_value_heads": 32, "q_lora_rank": None, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 1000000,
    "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 128256,
}
REDUCED = {"num_hidden_layers": 7, "n_routed_experts": 16}


@pytest.fixture(scope="module")
def cell():
    return harness.load_cell(CELL)


# The family-agnostic lists this cell joins. `intake_ms`, `intake_idle_pct`
# and `prefill_dispatch_ms` are not among them: their own test holds each
# list to exactly three cells.
JOINED = ("serve_tok_s", "batch_occupancy", "prefill_ms", "decode_step_ms", "mfu_pct.decode",
          "device_idle_pct.serve", "engine_host_ms", "decode_dispatch_ms",
          "setup_compile_s", "setup_programs", "mfu_pct.prefill")
NEW_READERS = ("mla_share_pct", "mla_decode_roofline")


def test_the_manifest_names_the_configuration_the_cell_and_its_readers(cell):
    """Found by name, and appended after what was there: nothing here asks to
    be last, and a shared list is only held to hold this cell once, after the
    cells it held before."""
    manifest = harness.load_json(harness.ROOT, "BENCHMARK.json")
    names = lambda entries: [e["name"] for e in entries]
    configs, cells = names(manifest["configs"]), names(manifest["workloads"])
    assert configs.index("kanana2-30b-a3b-l7") > configs.index("jamba2-3b")
    assert manifest["configs"][configs.index("kanana2-30b-a3b-l7")] == {
        "name": "kanana2-30b-a3b-l7", "source": cell["config_file"]["source"],
        "file": "benchmark/configs/kanana2-30b-a3b-l7.json",
        "reduced": ["num_hidden_layers", "n_routed_experts"],
        "why": manifest["configs"][configs.index("kanana2-30b-a3b-l7")]["why"]}
    assert cells.count(CELL) == 1 and cells.index(CELL) > cells.index("serve-jamba-docs")
    entry = manifest["workloads"][cells.index(CELL)]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "kanana2-30b-a3b-l7", "longqa-backlog", 1)
    assert len(entry["why"]) <= 200 and "latent attention" in entry["why"]
    metrics = {m["name"]: m for m in manifest["end_to_end"] + manifest["per_layer"]}
    for name in JOINED:                                  # appended, nothing taken away
        listed = metrics[name]["workloads"]
        assert listed.count(CELL) == 1
        assert listed.index(CELL) > listed.index("serve-nemotron-reasoning")
    better = {"mla_share_pct": "lower", "mla_decode_roofline": "higher"}
    for name in NEW_READERS:
        assert metrics[name]["workloads"] == [CELL]
        assert (metrics[name]["moves"], metrics[name]["unit"], metrics[name]["layer"],
                metrics[name]["source"], metrics[name]["better"]) == (
            "serve_tok_s", "%", "Kernels", "device_trace", better[name])
        assert os.path.exists(os.path.join(harness.BENCH_DIR, "metrics", name + ".py"))
    layers = names(manifest["per_layer"])
    assert layers.index("mla_share_pct") > layers.index("prefill_dispatch_ms")
    assert [m["name"] for m in cell["end_to_end"]] == ["serve_tok_s", "setup_s"]
    assert set(JOINED[1:]) | set(NEW_READERS) <= {m["name"] for m in cell["per_layer"]}


def test_the_limit_lies_between_its_two_readings(cell):
    """A flipped choice of experts is the widest gap at either precision, so
    the program's largest reading and the fp8 control's least lie under 2x
    apart; the limit keeps a tenth of room from each."""
    (name,) = cell["limits"]
    limit = cell["limits"][name]
    assert name == "token_logit_gap"
    assert limit["lower"] * 1.1 < limit["limit"] < limit["upper"] / 1.1
    assert "control" in limit["from"] and "seeds" in limit["from"]


def test_configuration_is_the_published_one_cut_in_depth_and_experts(cell):
    from gpt_2_distributed_tpu.config import DEEPSEEK_V3_PRESETS

    cfg = cell["config_file"]
    assert cfg["family"] == "deepseek_v3" and cfg["reduced"] == list(REDUCED)
    for key, value in PUBLISHED.items():
        assert cfg[key] == REDUCED.get(key, value), key
    assert (cfg["published_num_hidden_layers"], cfg["published_n_routed_experts"],
            cfg["experts_held"]) == (48, 128, [0, 16])
    assert "8-way expert parallel" in cfg["deployment"]
    assert cfg["assumed"]["initializer_range"] == 0.02
    assert cfg["assumed"]["e_score_correction_bias"] == 0
    assert cell["program"].model_config(cfg) == DEEPSEEK_V3_PRESETS["kanana2-30b-a3b-l7"]
    serve = cell["program"].serve_config(cfg, cell["mix"])
    assert (serve.max_batch, serve.block_size, serve.num_blocks, serve.prefill_chunk) \
        == (32, 64, 32 * 512 + 1, 2048)
    assert serve.max_seq_len == cell["mix"]["max_total"] == 32768
    assert (serve.prefix_cache, serve.admission, cfg["serve"]["temperature"]) \
        == (False, "reserve", 0)
    assert cfg["precision"]["latent_cache"] == "bfloat16"


def test_traffic_is_the_cells_round(cell):
    mix = cell["mix"]
    assert (mix["kind"], mix["pool"], mix["min_queue_slots"], mix["check_tokens"]) == (
        "backlog", 32, 1.0, 400)
    assert mix["prompt"] == {"dist": "lognormal", "median": 12288, "sigma": 0.6,
                             "min": 2048, "max": 28672}
    assert mix["output"] == {"dist": "lognormal", "median": 768, "sigma": 0.6,
                             "min": 128, "max": 4096}
    pool = traffic.length_pool(mix)
    prompts, outputs = sorted(p for p, _ in pool), sorted(o for _, o in pool)
    assert len(pool) == 32 and max(p + o for p, o in pool) <= 32768
    assert (prompts[0], prompts[-1], sum(prompts)) == (3375, 28672, 445554)
    assert (outputs[0], outputs[-1], sum(outputs)) == (211, 2796, 29161)
    assert sum(-(-p // 2048) for p in prompts) == 232            # chunks a round
    # every seed gets the round in one order, with its own ids
    a, b = (traffic.requests(mix, cell["sizes"]["vocab_size"], s) for s in (1, 2**31 + 5))
    first_a, first_b = next(a), next(b)
    assert len(first_a.prompt) == len(first_b.prompt) == pool[0][0]
    assert first_a.prompt != first_b.prompt and max(first_b.prompt) < 128256


def test_arithmetic_counts_what_the_equations_ask(cell):
    sizes = cell["sizes"]
    c, h = 2048, 32
    attention = c * h * 192 + c * 576 + 512 * h * 256 + h * 128 * c
    assert sizes["pattern"] == "DEEEEEE" and ref.attention_matmul_params(sizes) == attention
    assert round(attention / 1e6, 2) == 26.35
    assert [round(ref.layer_params(sizes, k) / 1e6, 1) for k in "DE"] == [64.1, 111.5]
    assert round(ref.num_params(sizes) / 1e6) == 1259
    asked = 6 * 16 / 128
    moe = c * 128 + 3 * c * 1536 + asked * 3 * c * 768
    assert ref.forward_flops_per_token(sizes, 1000) == pytest.approx(
        2.0 * (7 * attention + 3 * c * 6144 + 6 * moe + c * 128256)
        + 7 * 2.0 * h * 1000 * (192 + 128))
    assert ref.forward_flops_per_token(sizes, 900) - ref.prefill_flops_per_token(
        sizes, 900) == 2.0 * c * 128256
    assert ref.attention_shapes(sizes) == {
        "kv_layers": 7, "heads": 32, "kv_heads": 1, "head_dim": 576}
    # the sizing: 3.7 GB of latent read by a step of 32 rows at 14.5k
    flops, nbytes = ref.mla_decode_work(sizes, keys=32 * 14500, rows=32)
    assert nbytes == 32 * 14500 * 7 * 576 * 2 + 32 * 7 * 32 * (576 + 512) * 2
    assert flops == 32 * 14500 * 7 * 32 * 2 * (576 + 512)
    assert round(nbytes / 1e9, 2) == 3.76


def tiny_cell():
    mix = {"kind": "backlog", "base_seed": 7, "pool": 8,
           "prompt": {"dist": "lognormal", "median": 40, "sigma": 0.4, "min": 20, "max": 80},
           "output": {"dist": "lognormal", "median": 6, "sigma": 0.5, "min": 2, "max": 12},
           "max_total": 128, "min_queue_slots": 1.0, "check_tokens": 20}
    cfg = dict(config_file(), name="deepseek-v3-tiny", family="deepseek_v3",
               serve={"max_batch": 3, "block_size": 8, "prefill_chunk": 16,
                      "prefix_cache": False, "admission": "reserve", "temperature": 0})
    return harness.attach_family({
        "name": f"deepseek-v3-tiny-{os.getpid()}", "config": "deepseek-v3-tiny",
        "traffic": "backlog", "chips": 1, "config_file": cfg, "mix": mix,
        "limits": {"token_logit_gap": {"limit": 0.05}},
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
    # bfloat16 weights served in bfloat16 against the float32 reference; a
    # router's flip would show as a gap of tenths
    assert row["name"] == "token_logit_gap" and 0 <= row["value"] <= 0.05


def test_trace_readers_on_a_recorded_trace(cell, monkeypatch, tmp_path):
    """The recorded trace is GPT-2's: it holds none of this family's scopes,
    which fails a run; pointed at a scope it does hold (the decode step's
    layer loop) each reader gives that scope's time against its measure."""
    trace = Trace.from_file(SMALL_TRACE)
    lo, hi = trace.window_ns()
    busy = trace.busy_seconds(lo, hi)
    peaks = {"flops_per_s_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    traced = {"decode_rows": 300, "decode_attended": 300 * 9000}
    ctx = {"cell": cell, "sizes": cell["sizes"], "peaks": peaks, "traced_stats": traced,
           "trace_window_ns": (lo, hi), "device": {"busy_s": busy}}
    # nothing without a kept trace, and nothing from a program without the counters
    monkeypatch.setenv("BENCH_KEEP_TRACE", str(tmp_path / "none.xplane.pb"))
    for name in NEW_READERS:
        assert harness.load_reader(name)(ctx) is None
    monkeypatch.setenv("BENCH_KEEP_TRACE", SMALL_TRACE)
    assert harness.load_reader("mla_decode_roofline")(
        {**ctx, "traced_stats": {"prefill_tokens": 8}}) is None
    for name in NEW_READERS:
        with pytest.raises(NoKernelEvent):
            harness.load_reader(name)(ctx)
    # a scope the trace holds
    scope = "while/body/closed_call"
    loop = scopes.scope_seconds(SMALL_TRACE, lo, hi, (scope,))
    decode = scopes.scope_seconds(SMALL_TRACE, lo, hi, (scope,), "jit(decode_step)")
    assert 0 < decode <= loop <= busy
    share = harness.load_module("metrics", "mla_share_pct")
    assert share.SCOPES == tuple(f"deepseek_v3/mla_{part}" for part in (
        "project", "prefill", "decode", "out"))
    monkeypatch.setattr(share, "SCOPES", (scope,))
    assert share.read(ctx) == pytest.approx(100 * loop / busy)
    roofline = harness.load_module("metrics", "mla_decode_roofline")
    assert (roofline.SCOPE, roofline.PROGRAM) == ("deepseek_v3/mla_decode", "jit(decode_step)")
    monkeypatch.setattr(roofline, "SCOPE", scope)
    flops, nbytes = ref.mla_decode_work(cell["sizes"], 300 * 9000, 300)
    least = max(flops / 197e12, nbytes / 819e9)
    assert least == nbytes / 819e9                        # bound by memory
    assert roofline.read(ctx) == pytest.approx(100 * least / decode)


def test_step_programs_compile_for_a_v5e_at_the_cells_sizes(cell, monkeypatch):
    """Both programs as the engine builds them, 32 slots of 32,768 tokens:
    weights, the one latent pool and each program's temporaries under the
    15.75 GB a program can have, and over the quarter of the chip a cell has
    to fill; the decode program holds the absorbed attention's kernel,
    compiled by Mosaic. Nothing runs: a pass here is not a chip run."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    from gpt_2_distributed_tpu.serving import deepseek_v3_programs
    from gpt_2_distributed_tpu.serving.paged_cache import init_pools, pool_shape

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu / unknown topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # the attention asks the first device whether to take its kernel
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
        == pytest.approx(2.52e9, rel=2e-3)
    shape = pool_shape(config.kv_pool_view, serve)
    assert shape[0] == 7 and shape[-3:] == (1, 64, 640)
    empty = jax.eval_shape(lambda: init_pools(config.kv_pool_view, serve)[1])
    assert empty.shape == (0,)                            # no V pool
    pool, v_pool = arr(shape, jnp.bfloat16), arr((0,), jnp.bfloat16)
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
        decode = jax.jit(functools.partial(deepseek_v3_programs.decode_step_impl, **static),
                         donate_argnames=donate).lower(
            params, pool, v_pool, {}, arr((b, m), i32), arr((b,), i32), arr((b,), i32),
            arr((b,), jnp.bool_), arr((b, 2), jnp.uint32)).compile()
        chunk = jax.jit(functools.partial(deepseek_v3_programs.chunk_prefill_impl, **static),
                        donate_argnames=donate).lower(
            params, pool, v_pool, {}, arr((1, m), i32), arr((1, c), i32), arr((1,), i32),
            arr((1,), i32), arr((1, 2), jnp.uint32), arr((1,), i32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        jax.config.update("jax_default_matmul_precision", precision_was)
        compilation_cache.reset_cache()
    assert "paged_mla_decode" in decode.as_text()
    assert "paged_mla_decode" not in chunk.as_text()     # a chunk attends expanded, in XLA
    for compiled in (decode, chunk):
        mem = compiled.memory_analysis()
        need = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                + mem.output_size_in_bytes - mem.alias_size_in_bytes)
        # 2.52 GB of weights and 9.40 GB of pool (576 lanes stored as 640)
        assert 11.9e9 < need < 12.5e9 < 15.75e9
        assert mem.temp_size_in_bytes < 0.5e9            # a chunk's tiles, not its context
