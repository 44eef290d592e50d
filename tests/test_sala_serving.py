"""MiniCPM-SALA through ``ServingEngine`` at the CPU tests' size: chunked
prefill and decoding through the paged pools, the compressed-key pool and the
per-slot linear state against the benchmark's plain reference over the whole
sequence; slots reused and requests preempted; what the engine refuses."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gpt_2_distributed_tpu.config import ServeConfig
from gpt_2_distributed_tpu.serving.engine import RequestHandle, ServingEngine
from tests import pipelined_cases
from tests.test_sala_model import CONFIG, SIZES, ref

PROMPTS, NEW = (70, 41, 90, 9), (20, 30, 12, 40)


@pytest.fixture(scope="module")
def weights():
    """The reference's weights; the engine is handed them raised to float32."""
    return ref.make_weights(SIZES, 11)


@pytest.fixture(scope="module")
def params(weights):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), weights)


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
    reference's full forward of prompt + tokens."""
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
    assert_tokens_are_the_references(weights, requests(), tokens)
    # one chunk program, one decode program, whatever came and went
    assert eng._chunk_fn._cache_size() == 1 and eng._decode_fn._cache_size() == 1
    assert eng.state["lin"].shape == (6, 3, 4, 16, 16) and eng.state["lin"].dtype == jnp.float32
    assert eng.state["kc"].shape == (2, 49, 1, 4, 16)
    assert eng.k_pool.shape[0] == 2 and eng.k_pool.shape[-3:] == (1, 8, 16)   # 2 KV layers, 1 KV head


def test_another_chunk_size_serves_the_same_tokens(params, fresh_tokens):
    assert served(engine(params, prefill_chunk=32), requests()) == fresh_tokens[1]


def test_counters_follow_the_selection(fresh_tokens):
    eng, _ = fresh_tokens
    s, sp = eng.stats, CONFIG.sparse
    assert s["prefill_tokens"] == sum(PROMPTS) and s["state_resets"] == len(PROMPTS)
    assert s["decode_rows"] == sum(NEW) - len(NEW)
    assert s["sparse_rows"] == s["prefill_tokens"] + s["decode_rows"]
    positions = np.concatenate([np.arange(p + n - 1) for p, n in zip(PROMPTS, NEW)])
    selected, visible = sp.selected_blocks(positions)
    assert s["sparse_selected"] == selected.sum() < s["sparse_visible"] == visible.sum()
    attended = (selected * 8 - (7 - positions % 8)).sum()
    assert s["prefill_attended"] + s["decode_attended"] == attended
    # past the dense range a row attends fewer keys than it sees
    assert s["decode_attended"] < sum(
        np.arange(p, p + n - 1).sum() + n - 1 for p, n in zip(PROMPTS, NEW))
    snap = eng.metrics_snapshot()
    for key in ("prefill_tokens", "prefill_attended", "sparse_rows", "sparse_selected",
                "sparse_visible", "state_resets"):
        assert snap[key] > 0
    assert snap["kv_pool_bytes_per_device"] > 0


def test_the_engine_holds_the_tree_it_was_given(params, fresh_tokens):
    """The cast at construction is the GPT-2 family's. A SalaConfig's tree
    (bfloat16 among float32 norm weights as ``init_params`` makes it; all
    float32 here) is held as it came, whatever the engine computes in."""
    eng, _ = fresh_tokens
    assert eng.params is params
    at_bf16 = ServingEngine(params, CONFIG, serve_config(), temperature=0.0)
    assert at_bf16.compute_dtype == jnp.bfloat16 and at_bf16.params is params
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
    prompts = requests()
    assert served(eng, prompts) == fresh_tokens[1]      # one slot, four times over
    assert eng.stats["state_resets"] == 4


def test_a_preempted_request_resumes_to_the_same_tokens(weights, params):
    """Watermark admission on a pool too small for both slots' growth: the
    newer request is swapped out, and prefills again from a zero state."""
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, CONFIG.vocab_size, n).tolist() for n in (9, 12)]
    eng = engine(params, max_batch=2, admission="watermark", num_blocks=13,
                 watermark_blocks=0)
    tokens = served(eng, prompts, new=(60, 50))
    assert eng.stats["preemptions"] > 0 and eng.stats["resumes"] > 0
    assert eng.stats["state_resets"] > len(prompts)
    assert_tokens_are_the_references(weights, prompts, tokens)


def test_migration_rebuilds_the_state_by_prefilling_again(params, fresh_tokens):
    """``extract_inflight`` / ``to_wire`` carry tokens, not state: the adopting
    engine prefills prompt + generated from position 0, so from a zero state."""
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
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(prefill_chunk=0), "whole-prompt prefill"),
    (dict(mesh="data:2", max_batch=4, num_blocks=50), "serving mesh"),
    (dict(prefill_batch=2), "prefill_batch"),
    (dict(block_size=16), "block_size=16"),
    (dict(prefill_chunk=12), "prefill_chunk=12"),
    (dict(spec="draft:124M,k:2"), "speculative"),
])
def test_what_the_engine_cannot_do_for_this_family_is_refused_by_name(
        params, changes, names):
    with pytest.raises(ValueError, match="cannot serve a SalaConfig with.*" + names):
        engine(params, **changes)


def test_a_draft_model_and_an_overlong_request_are_refused(params):
    with pytest.raises(ValueError, match="speculative"):
        ServingEngine(params, CONFIG, serve_config(), draft_params=params,
                      draft_config=CONFIG)
    eng = engine(params)
    with pytest.raises(ValueError, match=r"exceeds max_seq_len \(128\)"):
        eng.submit(list(range(100)), 29)
    assert eng._m == 16          # the table is as wide as the traffic, not 4096 / 8
