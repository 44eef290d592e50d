"""The yardstick's own arithmetic: operations and bytes, peaks,
the traffic generator, the weights, the manifest's files."""

import os
import re

import jax
import numpy as np
import pytest

from bench_tiny import TINY_MIXES
from benchmark import check, harness, traffic, work
from benchmark.reference import gpt2 as ref

ROOT = harness.ROOT
MANIFEST = harness.load_json(ROOT, "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.mark.parametrize("entry", MANIFEST["configs"], ids=lambda c: c["name"])
def test_flops_equal_the_programs_and_sizes_equal_the_presets(entry):
    from gpt_2_distributed_tpu.config import MODEL_PRESETS
    from gpt_2_distributed_tpu.utils.flops import flops_per_token

    cfg = harness.load_json(ROOT, entry["file"])
    preset = MODEL_PRESETS[cfg["program"]["preset"]]
    for key in ("n_layer", "n_embd", "n_head", "vocab_size", "n_positions"):
        assert getattr(preset, key) == cfg[key]
    assert preset.layer_norm_eps == cfg["layer_norm_epsilon"]
    assert preset.initializer_range == cfg["initializer_range"]
    for seq in (1024, 4096):
        assert work.train_flops_per_token(cfg, seq) == flops_per_token(preset, seq)
    assert work.forward_flops_per_token(cfg, 0) * 3 == pytest.approx(
        work.train_flops_per_token(cfg, 0))


def test_kernel_work_and_roofline_bounds():
    peaks = harness.load_peaks("TPU v5 lite")
    assert peaks["flops_per_s_bf16"] == 197e12 and peaks["hbm_bytes_per_s"] == 819e9
    fwd = work.flash_attention_work(8, 12, 1024, 64, backward=False)
    bwd = work.flash_attention_work(8, 12, 1024, 64, backward=True)
    assert fwd == (2 * 8 * 12 * 1024 * 1024 * 64, 4 * 8 * 12 * 1024 * 64 * 2)
    assert bwd[0] == 2 * fwd[0] and bwd[1] == 2 * fwd[1]
    assert work.roofline_seconds(*fwd, peaks)[1] == "compute"
    flops, nbytes = work.paged_attention_work(1000, 4, 25, 64)
    assert nbytes == (2 * 1000 * 25 * 64 + 2 * 4 * 25 * 64) * 2
    assert work.roofline_seconds(flops, nbytes, peaks) == (nbytes / 819e9, "memory")


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


def test_manifest_names_files_that_exist_and_metrics_that_cells_report():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    end_to_end = {m["name"]: m for m in MANIFEST["end_to_end"]}
    assert "setup_s" in end_to_end and "workloads" not in end_to_end["setup_s"]
    cells = {w["name"] for w in MANIFEST["workloads"]}
    for w in MANIFEST["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4) and len(w["why"]) <= 200
        cell = harness.load_cell(w["name"])
        assert cell["limits"], f"no limits file for {w['name']}"
        assert len(cell["end_to_end"]) >= 2 and cell["per_layer"]
    for m in MANIFEST["end_to_end"]:
        assert NAME.match(m["name"]) and 0 < m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in MANIFEST["per_layer"]:
        assert NAME.match(m["name"]) and "bound" not in m
        assert os.path.exists(os.path.join(harness.BENCH_DIR, "metrics", m["name"] + ".py"))
        moved = end_to_end[m["moves"]]
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
    for c in MANIFEST["configs"]:
        assert any(c["file"].startswith(p + "/") for p in MANIFEST["paths"])
        assert harness.load_json(ROOT, c["file"])["reduced"] == c["reduced"]
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
