"""The yardstick's own arithmetic: operations and bytes, peaks,
the traffic generator, the weights, the manifest's files and the two
modules of every family it names - and, at the end, that a new family is
files only."""

import hashlib
import json
import os
import re
import shutil
import time

import jax
import numpy as np
import pytest

from bench_tiny import CPU_DEVICE, LIMITS, TINY_CONFIG, TINY_MIXES
from benchmark import check, harness, serve_cell, traffic, train_cell, work

ref = harness.load_module("reference", "gpt2")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


# What BENCHMARK.json drives is held by ``check_...`` functions that read the
# manifest under ``harness.ROOT`` when they are called: the tests below run
# them on the repo's manifest, one case an entry, and
# ``test_a_new_family_is_files_only`` runs them again on a copy that holds
# another family's entries. What is GPT-2's alone takes GPT-2's entries alone.

def _manifest():
    return harness.load_json(harness.ROOT, "BENCHMARK.json")


def _family(entry):
    return harness.load_json(harness.ROOT, entry["file"]).get("family")


def _configs(family=None):
    return [c for c in _manifest()["configs"] if family in (None, _family(c))]


def check_gpt2_arithmetic(entry):
    from gpt_2_distributed_tpu.config import MODEL_PRESETS
    from gpt_2_distributed_tpu.utils.flops import flops_per_token

    cfg = harness.load_json(harness.ROOT, entry["file"])
    preset = MODEL_PRESETS[cfg["program"]["preset"]]
    assert harness.load_module("program", "gpt2").model_config(cfg) == preset
    sizes = ref.sizes_of(cfg)
    for key in ("n_layer", "n_embd", "n_head", "vocab_size", "n_positions"):
        assert getattr(preset, key) == cfg[key] == sizes[key]
    assert preset.layer_norm_eps == cfg["layer_norm_epsilon"]
    assert preset.initializer_range == cfg["initializer_range"]
    for seq in (1024, 4096):
        assert ref.train_flops_per_token(sizes, seq) == flops_per_token(preset, seq)
    assert ref.forward_flops_per_token(sizes, 0) * 3 == pytest.approx(
        ref.train_flops_per_token(sizes, 0))
    assert ref.attention_shapes(sizes) == {
        "kv_layers": preset.n_layer, "heads": preset.n_head,
        "kv_heads": preset.n_head, "head_dim": preset.head_dim}


def check_family_contract(entry):
    """Whatever the architecture: ``reference/<family>.py`` and
    ``program/<family>.py`` exist and export the names that the runners of
    this configuration's cells call, and the reference imports nothing of
    the program."""
    family = _family(entry)
    assert NAME.match(family)
    runners = {harness.runner_name(harness.load_cell(w["name"]))
               for w in _manifest()["workloads"] if w["config"] == entry["name"]}
    assert runners
    for module_kind, names in harness.FAMILY_CONTRACT.items():
        module = harness.load_module(module_kind, family)
        for name in names["always"] + sum((names[r] for r in runners), ()):
            assert hasattr(module, name), f"{module_kind}/{family}.py lacks {name}"
    with open(os.path.join(harness.BENCH_DIR, "reference", family + ".py")) as f:
        assert "gpt_2_distributed_tpu" not in f.read()


def check_manifest():
    manifest = _manifest()
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    end_to_end = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in end_to_end and "workloads" not in end_to_end["setup_s"]
    cells = {w["name"] for w in manifest["workloads"]}
    for w in manifest["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4) and len(w["why"]) <= 200
        cell = harness.load_cell(w["name"])
        assert cell["limits"], f"no limits file for {w['name']}"
        assert len(cell["end_to_end"]) >= 2 and cell["per_layer"]
    for m in manifest["end_to_end"]:
        assert NAME.match(m["name"]) and 0 < m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in manifest["per_layer"]:
        assert NAME.match(m["name"]) and "bound" not in m
        assert os.path.exists(os.path.join(harness.BENCH_DIR, "metrics", m["name"] + ".py"))
        moved = end_to_end[m["moves"]]
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
    for c in manifest["configs"]:
        assert any(c["file"].startswith(p + "/") for p in manifest["paths"])
        assert harness.load_json(harness.ROOT, c["file"])["reduced"] == c["reduced"]
    assert os.path.getsize(os.path.join(harness.ROOT, "BENCHMARK.json")) < 64 * 1024


@pytest.mark.parametrize("entry", _configs("gpt2"), ids=lambda c: c["name"])
def test_flops_equal_the_programs_and_sizes_equal_the_presets(entry):
    check_gpt2_arithmetic(entry)


@pytest.mark.parametrize("entry", _configs(), ids=lambda c: c["name"])
def test_configuration_names_a_family_whose_two_modules_keep_the_contract(entry):
    check_family_contract(entry)


def test_manifest_names_files_that_exist_and_metrics_that_cells_report():
    check_manifest()


def test_kernel_work_and_roofline_bounds():
    peaks = harness.load_peaks("TPU v5 lite")
    assert peaks["flops_per_s_bf16"] == 197e12 and peaks["hbm_bytes_per_s"] == 819e9
    fwd = work.flash_attention_work(8, 12, 1024, 64, backward=False)
    bwd = work.flash_attention_work(8, 12, 1024, 64, backward=True)
    assert fwd == (2 * 8 * 12 * 1024 * 1024 * 64, 4 * 8 * 12 * 1024 * 64 * 2)
    assert bwd[0] == 2 * fwd[0] and bwd[1] == 2 * fwd[1]
    assert work.roofline_seconds(*fwd, peaks)[1] == "compute"
    flops, nbytes = work.paged_attention_work(1000, 4, 25, 64, kv_heads=25)
    assert nbytes == (2 * 1000 * 25 * 64 + 2 * 4 * 25 * 64) * 2
    assert work.roofline_seconds(flops, nbytes, peaks) == (nbytes / 819e9, "memory")
    # grouped queries: every query head does its products, K and V are read
    # in the KV heads alone
    gq_flops, gq_bytes = work.paged_attention_work(1000, 4, 32, 128, kv_heads=2)
    assert gq_flops == 4 * 1000 * 32 * 128
    assert gq_bytes == (2 * 1000 * 2 * 128 + 2 * 4 * 32 * 128) * 2


def test_requests_repeat_for_a_seed_and_differ_across_seeds():
    mix = TINY_MIXES["backlog"]

    def take(seed, n=40):
        source = traffic.requests(mix, 257, seed)
        return [next(source) for _ in range(n)]

    a, b, c = take(3), take(3), take(2**31 + 9)
    assert a == b and a != c
    # every seed gets the same sizes in the same order, round after round:
    # the seed changes the token ids, never the work
    pool = traffic.length_pool(mix)
    assert [(len(r.prompt), r.max_new_tokens) for r in a] == (pool * 3)[:40]
    assert [(len(r.prompt), r.max_new_tokens) for r in c] == (pool * 3)[:40]
    assert sorted(p for p, _ in pool) != [p for p, _ in pool]     # not sorted by length
    assert all(p + o <= mix["max_total"] for p, o in pool)


def test_shards_repeat_for_a_seed_and_rows_differ(tmp_path):
    mix = TINY_MIXES["train"]
    one = traffic.write_shards(str(tmp_path / "a"), mix, 257, 5)
    two = traffic.write_shards(str(tmp_path / "b"), mix, 257, 5)
    other = traffic.write_shards(str(tmp_path / "c"), mix, 257, 6)
    data = [np.fromfile(p, "<u2") for p in one]
    assert all(np.array_equal(d, np.fromfile(p, "<u2")) for d, p in zip(data, two))
    assert not np.array_equal(data[0], np.fromfile(other[0], "<u2"))
    assert len(data) == mix["shards"] and data[0].size == mix["tokens_per_shard"]
    rows = data[0][: (data[0].size // 129) * 129].reshape(-1, 129)
    assert len({r.tobytes() for r in rows}) == len(rows)


def test_rows_in_shards_holds_a_feed_to_the_files(tmp_path):
    mix = TINY_MIXES["train"]
    paths = traffic.write_shards(str(tmp_path), mix, 257, 5)
    t = mix["seq_len"]
    data = [np.fromfile(p, "<u2").astype(np.int32) for p in paths]
    windows = [(d[k * t:(k + 1) * t], d[k * t + 1:(k + 1) * t + 1])
               for d in data for k in (3, 0, 7)]
    x, y = (np.stack(a) for a in zip(*windows))
    from_files, wrong = traffic.rows_in_shards(paths, t, 6, [(x, y)])
    assert wrong == 0
    assert np.array_equal(from_files[0][0], x) and np.array_equal(from_files[0][1], y)
    assert traffic.rows_in_shards(paths, t, 8, [(x, y)])[1] == 2        # a step two rows short
    assert traffic.rows_in_shards(paths, t, 6, [(x, x)])[1] == 6        # labels not shifted
    assert traffic.rows_in_shards(paths, t, 6, [(x, y), (x[:1], y[:1])])[1] == 6  # fed before
    off = x.copy()
    off[2, 9] += 1                                                       # not a window
    assert traffic.rows_in_shards(paths, t, 6, [(off, y)])[1] == 1
    unaligned = (data[0][5:5 + t][None], data[0][6:6 + t][None])
    assert traffic.rows_in_shards(paths, t, 1, [unaligned])[1] == 1


def test_weights_are_the_programs_init_bit_for_bit(tiny_config):
    from gpt_2_distributed_tpu.models import gpt2

    sizes = {k: getattr(tiny_config, k) for k in ref.SIZE_KEYS}
    for seed in (0, 42):
        ours = ref.make_weights(ref.sizes_of(sizes), seed)
        theirs = gpt2.init_params(tiny_config, seed=seed)
        assert jax.tree_util.tree_structure(ours) == jax.tree_util.tree_structure(theirs)
        for a, b in zip(jax.tree_util.tree_leaves(ours), jax.tree_util.tree_leaves(theirs)):
            assert a.dtype == b.dtype and np.array_equal(a, b)
    big = ref.make_weights(ref.sizes_of(sizes), 2**31 + 5)     # the driver's seeds are large
    assert np.isfinite(np.asarray(big["wte"])).all()


def test_reference_logits_match_the_programs_fp32_forward(tiny_config):
    from gpt_2_distributed_tpu.models import gpt2

    sizes = ref.sizes_of({k: getattr(tiny_config, k) for k in ref.SIZE_KEYS})
    w = ref.make_weights(sizes, 1)
    idx = np.random.default_rng(0).integers(0, sizes["vocab_size"], (2, 48))
    ours = ref.logits(w, sizes, idx)
    theirs, _ = gpt2.forward(w, tiny_config, idx, compute_dtype=np.float32)
    assert np.abs(np.asarray(ours) - np.asarray(theirs)).max() < 2e-5


def test_leaf_gaps_and_the_nought_gradient_rule():
    ref_norms = {"a": 1.0, "b": 2.0, "c": 4.0, "key_bias": 1e-9}
    prog = dict(ref_norms, a=1.5, key_bias=1e-3)
    # key_bias reads against the median leaf, not against its own nought
    assert check.worst_leaf_gap(prog, ref_norms) == (pytest.approx(0.5 / 1.5), "a")
    assert check.nought_gradient_leaves(ref_norms) == {"key_bias"}
    gap, leaf = check.worst_leaf_gap(
        dict(ref_norms, key_bias=3.0), ref_norms, skip={"key_bias"})
    assert gap == 0.0
    ok, rows = check.judge({"x": 0.1, "y": 9.0}, {"x": {"limit": 0.2}})
    assert ok and [r["ok"] for r in rows] == [True, None]
    assert not check.judge({"x": 0.3}, {"x": {"limit": 0.2}})[0]
    assert not check.judge({"x": float("nan")}, {"x": {"limit": 0.2}})[0]
    assert not check.judge({"y": 0.0}, {"x": {"limit": 0.2}})[0]   # nothing compared


def test_token_logit_gaps():
    logits = np.array([[0.0, 2.0, 1.0], [5.0, 1.0, 4.5], [0.0, 0.0, 9.0]])
    gaps = check.token_logit_gaps(logits, prompt_len=2, tokens=[2, 2])
    assert gaps.tolist() == [0.5, 0.0]


# --- a new family is files only -----------------------------------------------
#
# A new architecture's cells are new files only: in a copy of the benchmark,
# a made-up family whose configuration has none of GPT-2's keys and a position
# table sixteen times its mix gets its two modules, a configuration, two
# mixes, two limits files, a metric reader and its manifest entries; both tiny
# cells then run to ``correct`` through ``serve_cell`` / ``train_cell`` on the
# CPU, with no file that was there before touched. (The made-up family's
# modules translate its keys and delegate to GPT-2's: what a real one writes
# itself is its own mathematics, not more of the harness.)

FAMILY = "madeup"

CONFIG = {
    "name": "madeup-2l", "family": FAMILY, "source": "tests",
    "hidden_size": 32, "num_hidden_layers": 2, "num_attention_heads": 2,
    "vocab_size": 257, "max_position_embeddings": 2048, "rms_norm_eps": 1e-05,
    "reduced": [], "assumed": {},
    "program": {"preset": "124M"},
    "train": TINY_CONFIG["train"],
    "serve": dict(TINY_CONFIG["serve"], attn_impl="xla"),
    "reference": {"rows_per_block": 2},
}

REFERENCE = '''
"""The made-up family's plain reference: its own keys, GPT-2's mathematics."""
from benchmark import harness

_gpt2 = harness.load_module("reference", "gpt2")

make_weights = _gpt2.make_weights
serving_reference = _gpt2.serving_reference
control_matmul = _gpt2.control_matmul
train_steps, leaf_norms = _gpt2.train_steps, _gpt2.leaf_norms
ADAM_B1, ADAM_B2, ADAM_EPS = _gpt2.ADAM_B1, _gpt2.ADAM_B2, _gpt2.ADAM_EPS
forward_flops_per_token = _gpt2.forward_flops_per_token
train_flops_per_token = _gpt2.train_flops_per_token
attention_shapes = _gpt2.attention_shapes


def sizes_of(config):
    return _gpt2.sizes_of({
        "vocab_size": config["vocab_size"],
        "n_positions": config["max_position_embeddings"],
        "n_embd": config["hidden_size"], "n_layer": config["num_hidden_layers"],
        "n_head": config["num_attention_heads"],
        "layer_norm_epsilon": config["rms_norm_eps"]})
'''

PROGRAM = '''
"""The made-up family on the program's side: its keys onto the package's."""
from benchmark import harness

_gpt2 = harness.load_module("program", "gpt2")
train_model_config = _gpt2.train_model_config


def _as_gpt2(config):
    return dict(config, n_embd=config["hidden_size"],
                n_layer=config["num_hidden_layers"],
                n_head=config["num_attention_heads"],
                n_positions=config["max_position_embeddings"])


def model_config(config):
    return _gpt2.model_config(_as_gpt2(config))


def serve_config(config, mix):
    return _gpt2.serve_config(_as_gpt2(config), mix)


def trainer_flags(config, mix, data_dir, seed):
    return _gpt2.trainer_flags(_as_gpt2(config), mix, data_dir, seed)
'''

READER = '''
"""Serving scheduler: requests admitted per engine step (engine counters)."""


def read(ctx):
    stats = ctx["stats"]
    return stats["admitted"] / stats["steps"] if stats.get("steps") else None
'''

CELLS = {"madeup-backlog": "backlog", "madeup-train": "train"}


def _digests(root):
    out = {}
    for folder, _, files in os.walk(root):
        for name in files:
            path = os.path.join(folder, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = hashlib.sha256(f.read()).hexdigest()
    return out


@pytest.fixture()
def copied_benchmark(tmp_path, monkeypatch):
    """The benchmark's files in a directory of the test's own, with the
    harness pointed at it."""
    bench = tmp_path / "benchmark"
    shutil.copytree(harness.BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    monkeypatch.setattr(harness, "BENCH_DIR", str(bench))
    monkeypatch.setattr(harness, "WORK_DIR", str(tmp_path / ".bench_work"))
    return tmp_path


def _add_the_family(root):
    """What a ``model_config`` PR adds: files, and entries in the manifest."""
    bench = root / "benchmark"
    (bench / "reference" / f"{FAMILY}.py").write_text(REFERENCE)
    (bench / "program" / f"{FAMILY}.py").write_text(PROGRAM)
    (bench / "configs" / "madeup-2l.json").write_text(json.dumps(CONFIG))
    (bench / "metrics" / "admitted_per_step.py").write_text(READER)
    for cell, kind in CELLS.items():
        (bench / "traffic" / f"madeup-{kind}.json").write_text(
            json.dumps(TINY_MIXES[kind]))
        (bench / "limits" / f"{cell}.json").write_text(json.dumps(LIMITS[kind]))
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["configs"].append({
        "name": "madeup-2l", "source": "tests", "reduced": [],
        "file": "benchmark/configs/madeup-2l.json", "why": "none of GPT-2's keys"})
    for cell, kind in CELLS.items():
        manifest["workloads"].append({
            "name": cell, "config": "madeup-2l", "traffic": f"madeup-{kind}",
            "chips": 1, "why": "a family that is files only"})
        rate = "serve_tok_s" if kind == "backlog" else "train_tok_s_per_chip"
        next(m for m in manifest["end_to_end"]
             if m["name"] == rate)["workloads"].append(cell)
    # a committed reader serves the new family's training cell as it is
    next(m for m in manifest["per_layer"]
         if m["name"] == "mfu_pct.train")["workloads"].append("madeup-train")
    manifest["per_layer"].append({
        "name": "admitted_per_step", "unit": "requests", "better": "higher",
        "source": "program_counter", "layer": "Serving scheduler",
        "moves": "serve_tok_s", "workloads": ["madeup-backlog"]})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))


def test_a_new_family_is_files_only(copied_benchmark, monkeypatch):
    before = _digests(copied_benchmark / "benchmark")
    _add_the_family(copied_benchmark)

    engines, build = [], serve_cell.build_engine

    def build_and_keep(cell, seed):
        engine, driver = build(cell, seed)
        engines.append(engine)
        return engine, driver

    monkeypatch.setattr(serve_cell, "build_engine", build_and_keep)
    for name, kind in CELLS.items():
        cell = harness.load_cell(name)
        assert cell["reference"].__file__.startswith(str(copied_benchmark))
        assert not set(cell["config_file"]) & {"n_embd", "n_layer", "n_head",
                                               "n_positions"}
        runner = serve_cell if kind == "backlog" else train_cell
        result = runner.run(cell, 2**31 + 21, 1.0, False, dict(CPU_DEVICE),
                            time.monotonic(), harness.CompileCounter())
        assert result["correct"] is True and result["attempted"] > 0
        assert result["failed"] == 0
        assert {m["name"] for m in cell["end_to_end"]} == set(result["metrics"])

    # the pool is sized by the mix (4 slots x 128 / 8 blocks, and the null
    # block), not by the 2048 positions of the table
    (engine,) = engines
    serve, mix = CONFIG["serve"], TINY_MIXES["backlog"]
    assert engine.config.n_positions == 2048 > 8 * mix["max_total"]
    assert engine.serve.num_blocks == (
        serve["max_batch"] * mix["max_total"] // serve["block_size"] + 1) == 65

    # its reader is found by name and reads the engine's counters
    cell = harness.load_cell("madeup-backlog")
    assert [m["name"] for m in cell["per_layer"]] == ["admitted_per_step"]
    assert [m["name"] for m in harness.load_cell("madeup-train")["per_layer"]] == [
        "mfu_pct.train"]
    assert harness.read_layer_metrics(cell, {"stats": {"admitted": 6, "steps": 4}}) == {
        "admitted_per_step": {"value": 1.5, "unit": "requests"}}

    # what BENCHMARK.json drives in this file holds for the new entries as
    # it stands: the next family adds no failing case and edits no test
    check_manifest()
    assert [c["name"] for c in _configs()] == [
        c["name"] for c in _configs("gpt2")] + ["madeup-2l"]
    for entry in _configs():
        check_family_contract(entry)
    for entry in _configs("gpt2"):
        check_gpt2_arithmetic(entry)

    after = _digests(copied_benchmark / "benchmark")
    after = {k: v for k, v in after.items() if "__pycache__" not in k}
    assert {k: after[k] for k in before} == before        # nothing there was touched
    assert len(after) == len(before) + 8                  # and eight files came


def test_a_configuration_without_a_family_or_with_half_of_one_is_refused(
        copied_benchmark):
    _add_the_family(copied_benchmark)
    os.remove(copied_benchmark / "benchmark" / "program" / f"{FAMILY}.py")
    with pytest.raises(harness.RunFailed, match=f"program/{FAMILY}.py"):
        harness.load_cell("madeup-backlog")
    (copied_benchmark / "benchmark" / "program" / f"{FAMILY}.py").write_text(
        "def model_config(config):\n    return None\n")
    with pytest.raises(harness.RunFailed, match="lacks .'serve_config'"):
        harness.load_cell("madeup-backlog")
    # a mix of a kind that no runner drives is refused as the cell is loaded,
    # not by an AttributeError inside it
    (copied_benchmark / "benchmark" / "program" / f"{FAMILY}.py").write_text(PROGRAM)
    (copied_benchmark / "benchmark" / "traffic" / "madeup-backlog.json").write_text(
        json.dumps(dict(TINY_MIXES["backlog"], kind="open_loop")))
    with pytest.raises(harness.RunFailed, match="kind 'open_loop' is none of"):
        harness.load_cell("madeup-backlog")
    config = dict(CONFIG)
    del config["family"]
    (copied_benchmark / "benchmark" / "configs" / "madeup-2l.json").write_text(
        json.dumps(config))
    with pytest.raises(harness.RunFailed, match="names no \"family\""):
        harness.load_cell("madeup-backlog")
