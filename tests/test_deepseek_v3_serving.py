"""DeepSeek-V3's block through ``ServingEngine`` at the CPU tests' size:
chunked prefill (expanded attention over the latent pool) and decoding
(absorbed attention over the same pool) against the benchmark's plain
reference over the whole sequence; one latent pool and no V pool; slots
reused and requests preempted; the counters on an example reckoned by hand;
what the engine and the CLI refuse."""

import json
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gpt_2_distributed_tpu.config import ServeConfig
from gpt_2_distributed_tpu.serving.engine import ServingEngine
from gpt_2_distributed_tpu.serving.families import family_of
from tests import pipelined_cases
from tests.conftest import REPO_ROOT
from tests.test_deepseek_v3_model import CONFIG, SIZES, raised, ref

PROMPTS, NEW = (70, 41, 90, 9), (20, 30, 12, 40)


@pytest.fixture(scope="module")
def weights():
    """The reference's weights; the engine is handed them raised to float32."""
    return ref.make_weights(SIZES, 11)


@pytest.fixture(scope="module")
def params(weights):
    return raised(weights)


def serve_config(**changes):
    base = dict(max_batch=3, block_size=8, num_blocks=3 * 16 + 1, prefill_chunk=16,
                max_seq_len=128)
    return ServeConfig(**{**base, **changes})


def engine(params, **changes):
    return ServingEngine(params, CONFIG, serve_config(**changes), temperature=0.0,
                         compute_dtype=jnp.float32)


def requests(seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CONFIG.vocab_size, n).tolist() for n in PROMPTS]


def served(eng, prompts, new=NEW):
    handles = [eng.submit(p, n, rng=i) for i, (p, n) in enumerate(zip(prompts, new))]
    eng.run_until_idle(max_steps=2000)
    assert all(h.done and h.finish_reason == "length" for h in handles)
    return [h.generated for h in handles]


def assert_tokens_are_the_references(weights, prompts, generated):
    """Every served token is the reference's best at its position, over the
    reference's full forward of prompt + tokens: the program's float32 logits
    are the reference's to round-off, and no two logits of these lie that
    close."""
    for prompt, tokens in zip(prompts, generated):
        logits = ref.logits_with(weights, SIZES, np.asarray([prompt + tokens]))[0]
        rows = logits[len(prompt) - 1:len(prompt) - 1 + len(tokens)]
        gaps = rows.max(-1) - rows[np.arange(len(tokens)), tokens]
        assert gaps.max() == 0.0, (len(prompt), gaps.max())


@pytest.fixture(scope="module")
def fresh_tokens(params):
    """What a fresh engine serves for `requests()`, four requests in three
    slots, rows at different lengths side by side in every decode step."""
    eng = engine(params)
    tokens = served(eng, requests())
    return eng, tokens


def test_prefill_in_chunks_then_decode_equals_the_references_full_forward(
        weights, fresh_tokens):
    eng, tokens = fresh_tokens
    assert [len(t) for t in tokens] == list(NEW)
    assert all(p % 16 for p in PROMPTS)          # no prompt is a multiple of the chunk
    assert_tokens_are_the_references(weights, requests(), tokens)
    # one chunk program, one decode program, whatever came and went
    assert eng._chunk_fn._cache_size() == 1 and eng._decode_fn._cache_size() == 1
    # one latent pool - 3 layers, 1 row of 40 values padded to 128 lanes a
    # position - and no V pool; nothing kept beside it
    assert eng.k_pool.shape[0] == 3 and eng.k_pool.shape[-3:] == (1, 8, 128)
    assert eng.v_pool.shape == (0,) and eng.state == {}
    assert eng.kv_pool_bytes_per_device == 3 * 49 * 8 * 128 * 4
    assert family_of(CONFIG).name == "a DeepseekV3Config"


def test_the_logits_path_is_the_expanded_forwards(weights, params, fresh_tokens):
    """The served logits of a prompt's last position and of the decode steps
    after it, taken from the programs themselves, against the reference's
    full forward: float32 round-off, orders below the gap between two
    logits."""
    from gpt_2_distributed_tpu.models import deepseek_v3 as ds

    prompt, tokens = requests()[1], fresh_tokens[1][1]
    seq = np.asarray([prompt + tokens])
    want = ref.logits_with(weights, SIZES, seq)[0]
    got = np.asarray(jax.jit(lambda p, i: ds.forward(p, CONFIG, i))(params, seq))[0]
    np.testing.assert_allclose(got, want, atol=2e-6)


def test_another_chunk_size_serves_the_same_tokens(params, fresh_tokens):
    assert served(engine(params, prefill_chunk=32), requests()) == fresh_tokens[1]


def test_counters_on_an_example_reckoned_by_hand(params):
    """One slot, one request of 21 prompt tokens and 6 new in chunks of 8:
    three chunk dispatches (8, 8, 5 tokens) and five decode steps, each over
    2 expert layers of 4 held experts; every layer attends every key up to
    its own."""
    prompt = np.random.default_rng(6).integers(0, CONFIG.vocab_size, 21).tolist()
    eng = engine(params, max_batch=1, num_blocks=17, prefill_chunk=8)
    served(eng, [prompt], new=(6,))
    s = eng.stats
    assert (s["prefill_dispatches"], s["decode_steps"], s["decode_rows"]) == (3, 5, 5)
    assert s["moe_expert_slots"] == (3 + 5) * 2 * 4
    assert 0 < s["moe_experts_touched"] <= s["moe_expert_slots"]
    assert 0 < s["moe_rows"] <= 26 * 2 * 2                       # 2 of 8 chosen, 4 held
    assert s["prefill_attended"] + s["decode_attended"] == sum(range(1, 27))
    assert s["ssm_rows"] == s["sparse_rows"] == 0


def test_the_counters_reach_the_host_behind_the_tokens(params):
    from gpt_2_distributed_tpu.serving import deepseek_v3_programs

    family = family_of(CONFIG)
    assert family.counters == deepseek_v3_programs.COUNTERS == (
        "moe_rows", "moe_experts_touched")
    eng = engine(params)
    served(eng, [requests()[3]], new=(6,))
    tokens, keys, eng.k_pool, eng.v_pool, eng.state = eng._decode_fn(
        eng.params, eng.k_pool, eng.v_pool, eng.state, eng.block_table, eng.tokens,
        eng.pos, np.zeros(3, bool), eng.keys)
    assert tokens.shape == (3 + len(family.counters),) and keys.shape == (3, 2)
    assert np.asarray(tokens)[3:].tolist() == [0, 0]       # no live row: no pair, no expert


@pytest.mark.parametrize("case", pipelined_cases.CASES)
def test_pipelined_loop_serves_what_a_collecting_loop_does(case, weights, params):
    def make_engine(temperature=0.0, **serve):
        return ServingEngine(params, CONFIG, serve_config(**serve),
                             temperature=temperature, compute_dtype=jnp.float32)

    ids = pipelined_cases.run(
        case, make_engine, requests(),
        squeeze=dict(max_batch=2, admission="watermark", num_blocks=21,
                     watermark_blocks=0))
    if case == "greedy":
        assert_tokens_are_the_references(weights, requests(), ids)


def test_a_reused_slot_serves_what_a_fresh_engine_does(params, fresh_tokens):
    eng = engine(params, max_batch=1, num_blocks=17)
    assert served(eng, requests()) == fresh_tokens[1]      # one slot, four times over


def test_a_preempted_request_resumes_to_the_same_tokens(weights, params):
    """Watermark admission on a pool too small for both slots' growth: the
    newer request is swapped out, and prefills again into fresh blocks."""
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, CONFIG.vocab_size, n).tolist() for n in (9, 12)]
    eng = engine(params, max_batch=2, admission="watermark", num_blocks=13,
                 watermark_blocks=0)
    tokens = served(eng, prompts, new=(60, 50))
    assert eng.stats["preemptions"] > 0 and eng.stats["resumes"] > 0
    assert_tokens_are_the_references(weights, prompts, tokens)


@pytest.mark.parametrize("changes, names", [
    (dict(prefix_cache=True), "prefix_cache: latent blocks are not shared"),
    (dict(prefill_chunk=0), "whole-prompt prefill.*latent pool"),
    (dict(mesh="data:2", max_batch=4, num_blocks=50), "serving mesh.*latent pool"),
    (dict(prefill_batch=2), "prefill_batch"),
    (dict(prefill_chunk=12), "prefill_chunk=12: a chunk covers whole blocks"),
    (dict(spec="draft:124M,k:2"), "speculative"),
])
def test_what_the_engine_cannot_do_for_this_family_is_refused_by_name(
        params, changes, names):
    with pytest.raises(ValueError, match="cannot serve a DeepseekV3Config with.*" + names):
        engine(params, **changes)


def test_a_draft_model_and_an_overlong_request_are_refused(params):
    with pytest.raises(ValueError, match="speculative"):
        ServingEngine(params, CONFIG, serve_config(), draft_params=params,
                      draft_config=CONFIG)
    eng = engine(params)
    with pytest.raises(ValueError, match=r"exceeds max_seq_len \(128\)"):
        eng.submit(list(range(100)), 29)


@pytest.mark.parametrize("flags, names", [
    (["--prefill_chunk", "16", "--block_size", "8", "--prefix_cache"], "prefix_cache"),
    (["--block_size", "8"], "whole-prompt prefill"),
    (["--prefill_chunk", "16", "--block_size", "8", "--serve_mesh", "data:2"], "serving mesh"),
])
def test_the_cli_refuses_the_same_before_jax_loads(run_cli_jax_free, flags, names):
    done = run_cli_jax_free("serve", "--model", "deepseek-v3-tiny", *flags)
    assert done.returncode == 2 and names in done.stderr, done.stderr[-400:]
    assert "touched jax" not in done.stderr


def test_a_checkpoint_is_refused_by_name(capsys):
    import argparse

    from gpt_2_distributed_tpu.config import validate_model_flags

    flags = argparse.Namespace(model="kanana2-30b-a3b-l7", ckpt="runs/x", first_layer=0)
    with pytest.raises(SystemExit):
        validate_model_flags(argparse.ArgumentParser(), flags)
    assert "--model kanana2-30b-a3b-l7 has no checkpoint format" in capsys.readouterr().err


def test_the_serving_cli_serves_the_preset(tmp_path):
    """``gpt2-tpu-serve --model deepseek-v3-tiny`` through ``ServingEngine``
    and ``EngineDriver``: the tokens are the ones an engine built by hand on
    ``init_params`` of the same seed serves."""
    from gpt_2_distributed_tpu.config import DEEPSEEK_V3_PRESETS
    from gpt_2_distributed_tpu.models import deepseek_v3

    prompts = [list(range(3, 40)), list(range(50, 59))]
    reqs = tmp_path / "reqs.jsonl"
    reqs.write_text("".join(json.dumps({"prompt_ids": p, "new": 5}) + "\n" for p in prompts))
    done = subprocess.run(
        [sys.executable, "-m", "gpt_2_distributed_tpu.serving.serve", "--model",
         "deepseek-v3-tiny", "--init_random", "--seed", "0", "--temperature", "0",
         "--requests", str(reqs), "--max_batch", "2", "--block_size", "8",
         "--prefill_chunk", "16", "--max_seq_len", "64"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    records = [json.loads(line) for line in done.stdout.splitlines() if line.startswith("{")]
    got = {tuple(r["prompt_ids"]) if "prompt_ids" in r else r["id"]: r["generated"]
           for r in records if "generated" in r}
    whole = DEEPSEEK_V3_PRESETS["deepseek-v3-tiny"]
    eng = ServingEngine(
        deepseek_v3.init_params(whole, jax.random.PRNGKey(0)), whole,
        ServeConfig(max_batch=2, block_size=8, num_blocks=17, prefill_chunk=16,
                    max_seq_len=64), temperature=0.0)
    want = served(eng, prompts, new=(5, 5))
    assert sorted(got.values()) == sorted(want)
