"""Jamba through ``ServingEngine`` at the CPU tests' size: chunked prefill and
decoding through the paged pools (one KV head), the per-slot selective-scan
state and the convolution tail against the benchmark's plain reference over
the whole sequence; slots reused and requests preempted; the counters on an
example reckoned by hand; what the engine and the CLI refuse."""

import json
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gpt_2_distributed_tpu.config import ServeConfig
from gpt_2_distributed_tpu.serving.engine import RequestHandle, ServingEngine
from gpt_2_distributed_tpu.serving.families import family_of
from tests import pipelined_cases
from tests.conftest import REPO_ROOT
from tests.test_jamba_model import CONFIG, SIZES, raised, ref

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
    are the reference's to round-off (4e-6, ``test_jamba_model.py``), and no
    two logits of these lie that close."""
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
    # layers, slots, N, D: the channels last (a last dimension of 16 pads to 128 lanes)
    assert eng.state["ssm"].shape == (3, 3, 16, 128) and eng.state["ssm"].dtype == jnp.float32
    assert eng.state["conv"].shape == (3, 3, (4 - 1) * 128)      # layers, slots, taps x D
    assert eng.k_pool.shape[0] == 1 and eng.k_pool.shape[-3:] == (1, 8, 16)   # 1 KV layer, 1 KV head
    assert family_of(CONFIG).name == "a JambaConfig"


def test_the_logits_path_is_the_dense_forwards(weights, params, fresh_tokens):
    """The chunk program's last-position logits and the decode program's, read
    off the programs themselves, against ``forward`` over prompt + tokens:
    float32 round-off through pools and states (tolerance 5e-6 of logits that
    spread by 0.17)."""
    from gpt_2_distributed_tpu.models import jamba
    from gpt_2_distributed_tpu.serving import jamba_programs

    prompt, tokens = requests()[3], fresh_tokens[1][3]           # 9 prompt tokens, 40 new
    seq = np.asarray(prompt + tokens)
    want = np.asarray(jamba.forward(params, CONFIG, seq[None]))[0]
    serve = serve_config(max_batch=1, num_blocks=17)
    eng = ServingEngine(params, CONFIG, serve, temperature=0.0, compute_dtype=jnp.float32)
    seen = {}

    def keep(logits, keys, temperature, top_k):
        seen["logits"] = logits
        return jnp.argmax(logits, -1).astype(jnp.int32), keys

    table = jnp.arange(1, 17, dtype=jnp.int32)[None]
    static = dict(config=CONFIG, temperature=0.0, top_k=None)
    keys = jnp.zeros((1, 2), jnp.uint32)
    real = jamba_programs.sample_rows
    jamba_programs.sample_rows = keep
    try:
        chunk = jnp.zeros((1, 16), jnp.int32).at[0, :9].set(jnp.asarray(prompt))
        tok, _, k, v, state = jamba_programs.chunk_prefill_impl(
            params, eng.k_pool, eng.v_pool, eng.state, table, chunk, jnp.array([0]),
            jnp.array([9]), keys, jnp.array([0]), **static)
        np.testing.assert_allclose(seen["logits"][0], want[8], atol=5e-6)
        assert int(tok[0]) == tokens[0]
        for i in range(5):
            tok, _, k, v, state = jamba_programs.decode_step_impl(
                params, k, v, state, table, tok, jnp.array([9 + i]), jnp.array([True]),
                keys, **static)
            np.testing.assert_allclose(seen["logits"][0], want[9 + i], atol=5e-6)
            assert int(tok[0]) == tokens[1 + i]
    finally:
        jamba_programs.sample_rows = real


def test_another_chunk_size_serves_the_same_tokens(params, fresh_tokens):
    assert served(engine(params, prefill_chunk=32), requests()) == fresh_tokens[1]


def test_counters_on_an_example_reckoned_by_hand(params):
    """One slot, one request of 21 prompt tokens and 6 new in chunks of 8:
    three chunk dispatches (8, 8, 5 tokens) and five decode steps, each over
    3 Mamba layers."""
    prompt = np.random.default_rng(6).integers(0, CONFIG.vocab_size, 21).tolist()
    eng = engine(params, max_batch=1, num_blocks=17, prefill_chunk=8)
    served(eng, [prompt], new=(6,))
    s = eng.stats
    assert (s["prefill_dispatches"], s["decode_steps"], s["decode_rows"]) == (3, 5, 5)
    assert s["sscan_tokens"] == 21 * 3 and s["sscan_rows"] == 5 * 3
    assert s["state_resets"] == 1 and s["prefill_tokens"] == 21
    assert s["prefill_attended"] + s["decode_attended"] == sum(range(1, 27))
    snap = eng.metrics_snapshot()
    for key in ("sscan_tokens", "sscan_rows", "state_resets", "kv_pool_bytes_per_device"):
        assert snap[key] > 0
    assert snap["sscan_tokens"] == 21 and snap["sscan_rows"] == 3    # per dispatch, per step
    assert snap["ssm_rows"] == 0 and snap["moe_rows"] == 0 and snap["sparse_rows"] == 0


def test_the_programs_end_in_the_tokens_alone(params):
    """The family counts on the host: nothing follows the sampled tokens."""
    from gpt_2_distributed_tpu.models.generate import sample_rows
    from gpt_2_distributed_tpu.serving import jamba_programs, nemotron_programs

    assert jamba_programs.sample_rows is nemotron_programs.sample_rows is sample_rows
    assert family_of(CONFIG).counters == ()
    eng = engine(params)
    tokens, keys, eng.k_pool, eng.v_pool, eng.state = eng._decode_fn(
        eng.params, eng.k_pool, eng.v_pool, eng.state, eng.block_table, eng.tokens,
        eng.pos, np.zeros(3, bool), eng.keys)
    assert tokens.shape == (3,) and keys.shape == (3, 2)
    assert float(jnp.abs(eng.state["ssm"]).max()) == 0.0           # idle rows keep their state


def test_the_engine_holds_the_tree_it_was_given(params, fresh_tokens):
    eng, _ = fresh_tokens
    assert eng.params is params
    at_bf16 = ServingEngine(params, CONFIG, serve_config(), temperature=0.0)
    assert at_bf16.compute_dtype == jnp.bfloat16 and at_bf16.params is params
    assert at_bf16.state["conv"].dtype == jnp.bfloat16
    assert at_bf16.state["ssm"].dtype == jnp.float32
    assert eng.metrics_snapshot()["weight_bytes"] == sum(
        a.nbytes for a in jax.tree_util.tree_leaves(params))


@pytest.mark.parametrize("case", pipelined_cases.CASES)
def test_pipelined_loop_serves_what_a_collecting_loop_does(case, weights, params):
    """The engine dispatches decode step N+1 before it reads step N's tokens
    back; the ids it serves are those of the same engine made to collect
    after every dispatch (``tests/pipelined_cases.py``) - and, greedy, the
    reference's best at every position."""
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
    assert eng.stats["state_resets"] == 4


def test_a_preempted_request_resumes_to_the_same_tokens(weights, params):
    """Watermark admission on a pool too small for both slots' growth: the
    newer request is swapped out, and prefills again from zero states."""
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, CONFIG.vocab_size, n).tolist() for n in (9, 12)]
    eng = engine(params, max_batch=2, admission="watermark", num_blocks=13,
                 watermark_blocks=0)
    tokens = served(eng, prompts, new=(60, 50))
    assert eng.stats["preemptions"] > 0 and eng.stats["resumes"] > 0
    assert eng.stats["state_resets"] > len(prompts)
    assert_tokens_are_the_references(weights, prompts, tokens)


def test_migration_rebuilds_the_state_by_prefilling_again(params, fresh_tokens):
    src, dst = engine(params), engine(params)
    prompts = requests()
    handles = [src.submit(p, n, rng=i) for i, (p, n) in enumerate(zip(prompts, NEW))]
    for _ in range(12):
        src.step()
    moved = [RequestHandle.from_wire(h.to_wire()) for h in src.extract_inflight()]
    assert any(h.generated for h in moved)
    for h in moved:
        dst.adopt(h)
    dst.run_until_idle(max_steps=2000)
    by_id = {h.id: h.generated for h in moved}
    assert [by_id[h.id] for h in handles] == fresh_tokens[1]


@pytest.mark.parametrize("changes, names", [
    (dict(prefix_cache=True), "prefix_cache: a hit would need a snapshot of the state-space"),
    (dict(prefill_chunk=0), "whole-prompt prefill"),
    (dict(mesh="data:2", max_batch=4, num_blocks=50), "serving mesh.*grouped-query pools"),
    (dict(prefill_batch=2), "prefill_batch"),
    (dict(prefill_chunk=12), "prefill_chunk=12: a chunk covers whole blocks"),
    (dict(spec="draft:124M,k:2"), "speculative"),
])
def test_what_the_engine_cannot_do_for_this_family_is_refused_by_name(
        params, changes, names):
    with pytest.raises(ValueError, match="cannot serve a JambaConfig with.*" + names):
        engine(params, **changes)


def test_the_state_families_share_one_refusal():
    from gpt_2_distributed_tpu.config import FAMILY_FLAGS, refuse_for_state_family

    assert len(FAMILY_FLAGS) == 3
    assert all(row.refuse is refuse_for_state_family for row in FAMILY_FLAGS)


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
    (["--prefill_chunk", "16", "--block_size", "8", "--n_embd", "32"], "is a GPT-2 size"),
    (["--prefill_chunk", "16", "--block_size", "8", "--draft_preset", "124M",
      "--spec_k", "2"], "speculative"),
])
def test_the_cli_refuses_the_same_before_jax_loads(run_cli_jax_free, flags, names):
    done = run_cli_jax_free("serve", "--model", "jamba-tiny", *flags)
    assert done.returncode == 2 and names in done.stderr, done.stderr[-400:]
    assert "touched jax" not in done.stderr


def test_a_checkpoint_is_refused_by_name(capsys):
    import argparse

    from gpt_2_distributed_tpu.config import validate_model_flags

    flags = argparse.Namespace(model="jamba2-3b", ckpt="runs/x", first_layer=0)
    with pytest.raises(SystemExit):
        validate_model_flags(argparse.ArgumentParser(), flags)
    assert "--model jamba2-3b has no checkpoint format" in capsys.readouterr().err


def test_sample_refuses_the_family_by_name():
    """``sample.py`` samples GPT-2 and MiniCPM-SALA; this family is served."""
    from gpt_2_distributed_tpu import sample

    with pytest.raises(SystemExit):
        sample.build_argparser().parse_args(["--model", "jamba2-3b", "--init_random"])


def test_the_serving_cli_serves_the_preset(tmp_path):
    """``gpt2-tpu-serve --model jamba-tiny`` through ``ServingEngine`` and
    ``EngineDriver``: the tokens are the ones an engine built by hand on
    ``init_params`` of the same seed serves."""
    from gpt_2_distributed_tpu.models import jamba

    prompts = [list(range(3, 40)), list(range(50, 59))]
    reqs = tmp_path / "reqs.jsonl"
    reqs.write_text("".join(json.dumps({"prompt_ids": p, "new": 5}) + "\n" for p in prompts))
    done = subprocess.run(
        [sys.executable, "-m", "gpt_2_distributed_tpu.serving.serve", "--model",
         "jamba-tiny", "--init_random", "--seed", "0", "--temperature", "0",
         "--requests", str(reqs), "--max_batch", "2", "--block_size", "8",
         "--prefill_chunk", "16", "--max_seq_len", "64"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    records = [json.loads(line) for line in done.stdout.splitlines() if line.startswith("{")]
    got = {tuple(r["prompt_ids"]) if "prompt_ids" in r else r["id"]: r["generated"]
           for r in records if "generated" in r}
    eng = ServingEngine(
        jamba.init_params(CONFIG, jax.random.PRNGKey(0)), CONFIG,
        ServeConfig(max_batch=2, block_size=8, num_blocks=17, prefill_chunk=16,
                    max_seq_len=64), temperature=0.0)
    want = served(eng, prompts, new=(5, 5))
    assert sorted(got.values()) == sorted(want)
