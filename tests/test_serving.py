"""Serving subsystem: block allocator units, engine bit-parity against
``generate_cached``, compile-once across admission/eviction churn, EOS
eviction, padding edges, streaming, and the serving CLIs' flag contract.

The exactness bar is deliberately BIT-equality, not allclose: the decode
step mirrors ``decode.decode_step`` op-for-op with batch a parallel dim
throughout, and each slot carries its own PRNG chain in generate_cached's
split order — so a request's tokens cannot depend on who shares the batch.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gpt_2_distributed_tpu.config import ServeConfig
from gpt_2_distributed_tpu.models import gpt2
from gpt_2_distributed_tpu.models.decode import generate_cached
from gpt_2_distributed_tpu.serving import (
    BlockAllocator,
    PrefixCache,
    ServingEngine,
)
from gpt_2_distributed_tpu.serving.engine import RequestHandle

import pipelined_cases

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def tiny_params(tiny_config):
    return gpt2.init_params(tiny_config, seed=0)


@pytest.fixture(autouse=True)
def _tier1_runtime_budget(request):
    """Default-tier budget guard: every non-slow test in this module must
    finish well inside tier-1's suite timeout. The scheduler property tests
    are deliberately sized down (tiny config, few prompt/new shapes so the
    one-shot references share jit cache entries); a test blowing this budget
    means someone scaled a config up — push it to @slow instead."""
    t0 = time.perf_counter()
    yield
    if request.node.get_closest_marker("slow") is None:
        elapsed = time.perf_counter() - t0
        assert elapsed < 90, (
            f"{request.node.name} took {elapsed:.1f}s — default-tier tests "
            "must stay under 90s; size the config down or mark it slow"
        )


def _serve(**kw):
    base = dict(max_batch=4, block_size=8, num_blocks=32, attn_impl="xla")
    base.update(kw)
    return ServeConfig(**base)


def _oneshot(params, config, prompt, key, new, **kw):
    """generate_cached batch-1 reference; returns just the NEW tokens."""
    out = generate_cached(
        params, config, jnp.asarray([prompt], jnp.int32), key,
        max_new_tokens=new, **kw,
    )
    return np.asarray(out)[0, len(prompt):].tolist()


# --------------------------------------------------------------- allocator


class TestBlockAllocator:
    def test_all_or_nothing_and_null_block_reserved(self):
        a = BlockAllocator(8)           # blocks 1..7 allocatable
        assert a.available == 7
        ids = a.alloc(7)
        assert sorted(ids) == list(range(1, 8))  # block 0 never handed out
        assert a.alloc(1) is None       # empty pool -> None, not partial
        a.release(ids[:3])
        assert a.available == 3
        assert a.alloc(4) is None       # 4 > 3: free list left untouched
        assert a.available == 3
        assert len(a.alloc(3)) == 3

    def test_double_free_and_foreign_ids_are_loud(self):
        a = BlockAllocator(8)
        ids = a.alloc(2)
        a.release(ids)
        with pytest.raises(ValueError, match="double free"):
            a.release(ids)
        with pytest.raises(ValueError, match="not an allocated block"):
            a.release([0])              # the null block
        with pytest.raises(ValueError, match="need at least one"):
            a.alloc(0)

    def test_too_small_pool_rejected(self):
        with pytest.raises(ValueError, match="num_blocks=1"):
            BlockAllocator(1)
        with pytest.raises(ValueError):
            ServeConfig(max_batch=0)
        with pytest.raises(ValueError):
            ServeConfig(block_size=0)

    def test_refcount_retain_release(self):
        # Prefix sharing rests on this: a block freed by its writer stays
        # alive while anyone (the cache, another request) still holds it.
        a = BlockAllocator(8)
        [b] = a.alloc(1)
        assert a.refcount(b) == 1
        a.retain(b)
        assert a.refcount(b) == 2
        a.release([b])                  # writer done; cache still holds it
        assert a.refcount(b) == 1 and a.available == 6
        a.release([b])
        assert a.refcount(b) == 0 and a.available == 7
        with pytest.raises(ValueError, match="double free"):
            a.release([b])
        with pytest.raises(ValueError, match="not an allocated block"):
            a.retain(b)                 # free blocks can't be re-pinned


class TestPrefixCache:
    def test_lookup_returns_longest_leading_run(self):
        a = BlockAllocator(16)
        c = PrefixCache(4)
        toks = list(range(12))          # exactly 3 full blocks
        ids = a.alloc(3)
        for j, b in enumerate(ids):
            assert c.insert(toks, j, b, a)
        assert all(a.refcount(b) == 2 for b in ids)  # writer + cache
        assert c.lookup(toks) == ids
        # Diverging at block 1 ends the run at block 0 — block 1's K/V
        # attends into the span that differs.
        assert c.lookup(toks[:4] + [99] * 8) == ids[:1]
        # No full block, no hits; and a hit can't start past a miss.
        assert c.lookup(toks[:3]) == []
        assert c.lookup([99] + toks[1:]) == []
        # First writer wins: re-inserting is a no-op, no double pin.
        assert not c.insert(toks, 0, ids[0], a)
        assert a.refcount(ids[0]) == 2

    def test_evict_one_skips_pinned_entries(self):
        a = BlockAllocator(16)
        c = PrefixCache(4)
        toks = list(range(8))
        ids = a.alloc(2)
        for j, b in enumerate(ids):
            c.insert(toks, j, b, a)
        a.release([ids[0]])             # request dropped block 0 only
        assert c.evict_one(a)           # cache-only entry goes first
        assert a.refcount(ids[0]) == 0
        assert not c.evict_one(a)       # the survivor is pinned: refuse
        assert len(c) == 1
        a.release([ids[1]])
        c.clear(a)
        assert len(c) == 0 and a.available == 15

    def test_lookup_refreshes_lru_order(self):
        a = BlockAllocator(16)
        c = PrefixCache(2)
        [b1] = a.alloc(1)
        c.insert([1, 2], 0, b1, a)
        a.release([b1])
        [b2] = a.alloc(1)
        c.insert([3, 4], 0, b2, a)
        a.release([b2])
        assert c.lookup([1, 2]) == [b1]  # touch: b2 becomes the LRU entry
        assert c.evict_one(a)
        assert a.refcount(b2) == 0 and a.refcount(b1) == 1


# ----------------------------------------------------- engine bit-parity


def _mixed_trace():
    prompts = [[1, 2, 3], [7, 8, 9, 10, 11], [42], [5, 6], [200, 201, 202]]
    news = [10, 7, 12, 1, 9]
    keys = [jax.random.PRNGKey(100 + i) for i in range(5)]
    return prompts, news, keys


# The engine holds `gpt2.serving_weights(params, compute_dtype)`; the one-shot
# reference is handed the float32 tree and casts each weight at its use. At
# bfloat16 the two multiply by the same bf16(w); at float32 the engine holds
# the caller's own arrays.
DTYPES = pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                                 ids=["bfloat16", "float32"])


@DTYPES
def test_engine_greedy_bit_matches_generate_cached(
        dtype, tiny_params, tiny_config):
    prompts, news, keys = _mixed_trace()
    eng = ServingEngine(tiny_params, tiny_config, _serve(), temperature=0.0,
                        compute_dtype=dtype)
    handles = [eng.submit(p, n, rng=k)
               for p, n, k in zip(prompts, news, keys)]
    eng.run_until_idle(max_steps=200)
    for h, p, n, k in zip(handles, prompts, news, keys):
        ref = _oneshot(tiny_params, tiny_config, p, k, n, temperature=0.0,
                       compute_dtype=dtype)
        assert h.generated == ref, h.id
        assert h.done and h.finish_reason == "length"
    # All blocks back after drain; no leak across the whole trace.
    assert eng.allocator.available == eng.serve.num_blocks - 1


@DTYPES
def test_engine_sampled_bit_matches_generate_cached(
        dtype, tiny_params, tiny_config):
    # temperature>0 + top_k: the per-slot PRNG chains must replay the exact
    # threefry split order of the one-shot path regardless of batch mates.
    prompts, news, keys = _mixed_trace()
    eng = ServingEngine(tiny_params, tiny_config, _serve(),
                        temperature=0.9, top_k=40, compute_dtype=dtype)
    handles = [eng.submit(p, n, rng=k)
               for p, n, k in zip(prompts, news, keys)]
    eng.run_until_idle(max_steps=200)
    for h, p, n, k in zip(handles, prompts, news, keys):
        ref = _oneshot(tiny_params, tiny_config, p, k, n,
                       temperature=0.9, top_k=40, compute_dtype=dtype)
        assert h.generated == ref, h.id


# ------------------------------------------- the one-deep decode pipeline


@pytest.mark.parametrize("chunk", [0, 5], ids=["whole-prompt", "chunked"])
@pytest.mark.parametrize("case", pipelined_cases.CASES)
def test_pipelined_loop_serves_what_a_collecting_loop_does(
        case, chunk, tiny_params, tiny_config):
    """The engine dispatches decode step N+1 before it reads step N's tokens
    back; the ids it serves are those of the same engine made to collect
    after every dispatch (``tests/pipelined_cases.py``), and - greedy and
    sampled - those of ``generate_cached(batch=1)``."""
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 256, n).tolist() for n in (5, 11, 17, 3)]

    def make_engine(temperature=0.0, **serve):
        return ServingEngine(
            tiny_params, tiny_config, _serve(prefill_chunk=chunk, **serve),
            temperature=temperature)

    ids = pipelined_cases.run(
        case, make_engine, prompts,
        squeeze=dict(num_blocks=10, admission="watermark", watermark_blocks=1))
    if case in ("greedy", "sampled"):
        temperature = 0.8 if case == "sampled" else 0.0
        for i, (p, got) in enumerate(zip(prompts, ids)):
            assert got == _oneshot(
                tiny_params, tiny_config, p, jax.random.PRNGKey(100 + i),
                len(got), temperature=temperature), i


def test_a_lost_engine_hands_back_the_request_whose_last_token_was_unread(
        tiny_params, tiny_config):
    """``extract_inflight`` on an engine whose device no longer answers: the
    unread step is lost with it, and the request that had left its slot with
    its last token in that step crosses with the slotted ones - its chain
    head beside the tokens it has - and samples that token again elsewhere."""
    prompts, new = [[1, 2, 3], [7, 8, 9, 10, 11]], (5, 12)
    want = []
    for i, (p, n) in enumerate(zip(prompts, new)):
        want.append(_oneshot(tiny_params, tiny_config, p, jax.random.PRNGKey(i),
                             n, temperature=0.9))
    src, dst = (ServingEngine(tiny_params, tiny_config, _serve(), temperature=0.9)
                for _ in range(2))
    handles = [src.submit(p, n, rng=i) for i, (p, n) in enumerate(zip(prompts, new))]
    while sum(not h.done for h in handles) == src.occupancy + src.queue_depth:
        src.step()
    assert not handles[0].done and len(handles[0].generated) == new[0] - 1

    def lost(*args, **kw):
        raise RuntimeError("device lost")

    src._decode_turn = lost
    moved = src.extract_inflight()
    assert [h.id for h in moved] == [0, 1] and not src.has_work()
    for h in moved:
        dst.adopt(RequestHandle.from_wire(h.to_wire()))
    moved = list(dst._queue)
    dst.run_until_idle(max_steps=200)
    assert [h.generated for h in moved] == want


# ------------------------------------------------------ the weights it holds

LAYER_NORMS = {"ln_f_scale", "ln_f_bias", "ln1_scale", "ln1_bias",
               "ln2_scale", "ln2_bias"}


def _leaves(tree):
    """{leaf name: array} of a GPT-2 tree (no two leaves share a name)."""
    return {path[-1].key: a
            for path, a in jax.tree_util.tree_leaves_with_path(tree)}


def test_serving_weights_casts_what_the_forwards_cast(tiny_config):
    """``gpt2.serving_weights``: every leaf the forwards take
    ``.astype(compute_dtype)`` of is in that dtype, bit for bit what the
    cast at the use gives; the LayerNorm leaves, read as float32, are the
    caller's; the tree given is neither donated nor changed; and a tree
    that needs no cast comes back as it is."""
    given = gpt2.init_params(tiny_config, seed=3)
    # biases and LayerNorm leaves start at 0 and 1: give every leaf values
    # that bfloat16 rounds
    given = jax.tree_util.tree_map(
        lambda a: a + jnp.linspace(0.1, 0.7, a.size).reshape(a.shape), given)
    before = jax.tree_util.tree_map(np.asarray, given)
    held = gpt2.serving_weights(given, jnp.bfloat16)
    assert (jax.tree_util.tree_structure(held)
            == jax.tree_util.tree_structure(given))
    for name, leaf in _leaves(held).items():
        mine = _leaves(given)[name]
        assert leaf.shape == mine.shape, name
        if name in LAYER_NORMS:
            assert leaf is mine and leaf.dtype == jnp.float32, name
        else:
            assert leaf.dtype == jnp.bfloat16, name
            np.testing.assert_array_equal(
                np.asarray(leaf), np.asarray(mine.astype(jnp.bfloat16)), name)
    assert sorted(n for n, a in _leaves(held).items()
                  if a.dtype == jnp.float32) == sorted(LAYER_NORMS)
    for name, mine in _leaves(given).items():
        assert not mine.is_deleted(), name
        np.testing.assert_array_equal(np.asarray(mine), _leaves(before)[name])
    assert gpt2.serving_weights(given, jnp.float32) is given
    assert gpt2.serving_weights(held, jnp.bfloat16) is held
    # a tree cast in part: only the leaves that need it are made anew
    mixed = dict(given, wte=held["wte"])
    again = gpt2.serving_weights(mixed, jnp.bfloat16)
    assert again["wte"] is held["wte"] and again["wpe"].dtype == jnp.bfloat16
    # abstract, as the AOT tests hand it over
    shapes = jax.eval_shape(lambda t: gpt2.serving_weights(t, jnp.bfloat16), given)
    assert ({n: a.dtype for n, a in _leaves(shapes).items()}
            == {n: a.dtype for n, a in _leaves(held).items()})


def test_engine_holds_its_weights_in_the_compute_dtype(tiny_params, tiny_config):
    """A bfloat16 engine keeps no float32 copy of a matmul or embedding
    leaf, and leaves its caller's tree alone; a float32 engine holds the
    caller's own arrays; the step programs are handed what is held."""
    before = jax.tree_util.tree_map(np.asarray, tiny_params)
    eng = ServingEngine(tiny_params, tiny_config, _serve(prefill_chunk=4),
                        temperature=0.0)
    held = _leaves(eng.params)
    assert sorted(n for n, a in held.items()
                  if a.dtype == jnp.float32) == sorted(LAYER_NORMS)
    assert all(a.dtype == jnp.bfloat16 for n, a in held.items()
               if n not in LAYER_NORMS)
    seen = []
    inner = eng._decode_fn
    eng._decode_fn = lambda params, *a: (seen.append(params), inner(params, *a))[1]
    h = eng.submit([5, 6, 7, 8, 9], 4, rng=0)
    eng.run_until_idle(max_steps=50)
    assert h.done and seen and all(p is eng.params for p in seen)
    for name, mine in _leaves(tiny_params).items():
        assert not mine.is_deleted() and mine.dtype == jnp.float32, name
        np.testing.assert_array_equal(np.asarray(mine), _leaves(before)[name])
    same = ServingEngine(tiny_params, tiny_config, _serve(), temperature=0.0,
                         compute_dtype=jnp.float32)
    assert same.params is tiny_params
    assert same.weight_bytes > eng.weight_bytes > same.weight_bytes // 2


def test_load_model_returns_what_an_engine_holds():
    """``serve.load_model`` / ``load_draft_model`` (the CLIs' and the
    workers' loaders) hand back the tree already cast, so that no float32
    copy lives on in their caller's frame beside the engine's tree; the
    engine then takes it as it is."""
    from gpt_2_distributed_tpu.serving import serve as serve_cli

    args = serve_cli.build_argparser().parse_args([
        "--init_random", "--requests", "-", "--n_layer", "2", "--n_embd", "32",
        "--n_head", "2", "--vocab_size", "257", "--seq_len", "64",
        "--draft_preset", "124M"])
    config, params = serve_cli.load_model(args)
    from gpt_2_distributed_tpu.config import MODEL_PRESETS
    small = MODEL_PRESETS["124M"].replace(n_layer=1, n_embd=32, n_head=2)
    with pytest.MonkeyPatch.context() as mp:
        mp.setitem(MODEL_PRESETS, "124M", small)
        draft_config, draft_params = serve_cli.load_draft_model(args, config)
    assert draft_config.vocab_size == 257 and draft_config.n_layer == 1
    for tree in (params, draft_params):
        assert sorted(n for n, a in _leaves(tree).items()
                      if a.dtype == jnp.float32) == sorted(LAYER_NORMS)
        assert _leaves(tree)["mlp_fc_w"].dtype == jnp.bfloat16
    eng = ServingEngine(params, config, _serve(), temperature=0.0)
    assert eng.params is params


def test_compile_once_across_admission_eviction_churn(
    tiny_params, tiny_config,
):
    # 9 requests through 2 slots: continuous admission backfills as rows
    # evict, and the decode step must stay ONE compiled program throughout —
    # churn changes array contents, never shapes.
    serve = _serve(max_batch=2, num_blocks=16)
    eng = ServingEngine(tiny_params, tiny_config, serve, temperature=0.0)
    rng = np.random.default_rng(3)
    specs = [
        (rng.integers(0, tiny_config.vocab_size,
                      int(rng.integers(1, 12))).tolist(),
         int(rng.integers(2, 9)))
        for _ in range(9)
    ]
    handles = [eng.submit(p, n, rng=jax.random.PRNGKey(i))
               for i, (p, n) in enumerate(specs)]
    eng.run_until_idle(max_steps=500)
    assert eng._decode_fn._cache_size() == 1
    # Prefill compiles per bucket, not per prompt length.
    buckets = {-(-len(p) // serve.block_size) for p, _ in specs}
    assert eng._prefill_fn._cache_size() == len(buckets)
    assert eng.stats["admitted"] == 9 and eng.stats["finished"] == 9
    assert eng.allocator.available == serve.num_blocks - 1
    # Every interleaving still bit-matches its solo reference.
    for h, (p, n), i in zip(handles, specs, range(9)):
        ref = _oneshot(tiny_params, tiny_config, p,
                       jax.random.PRNGKey(i), n, temperature=0.0)
        assert h.generated == ref, h.id


def test_fifo_admission_head_of_line(tiny_params, tiny_config):
    # One slot: requests must complete in submission order even though
    # later ones are shorter (no queue jumping past a waiting head).
    serve = _serve(max_batch=1, num_blocks=16)
    eng = ServingEngine(tiny_params, tiny_config, serve, temperature=0.0)
    hs = [
        eng.submit([1, 2, 3], 8, rng=0),
        eng.submit([4, 5], 2, rng=1),
        eng.submit([6], 3, rng=2),
    ]
    eng.run_until_idle(max_steps=200)
    assert all(h.done for h in hs)
    assert [h.finish_time for h in hs] == sorted(h.finish_time for h in hs)
    # With one slot there is never more than one request in flight, so
    # first-token times are FIFO too.
    assert [h.first_token_time for h in hs] == sorted(
        h.first_token_time for h in hs
    )


def test_eos_evicts_early_and_releases_blocks(tiny_params, tiny_config):
    # Sample a varied stream first, then replay it with eos_id set to a
    # token that first appears mid-stream: generation must cut exactly
    # there, report "eos", and hand every block back.
    p, n, key = [1, 2, 3], 10, jax.random.PRNGKey(100)
    full = _oneshot(tiny_params, tiny_config, p, key, n,
                    temperature=0.9, top_k=40)
    k = next(i for i in range(1, len(full)) if full[i] not in full[:i])
    serve = _serve(eos_id=full[k])
    eng = ServingEngine(tiny_params, tiny_config, serve,
                        temperature=0.9, top_k=40)
    h = eng.submit(p, n, rng=key)
    eng.run_until_idle(max_steps=100)
    assert h.finish_reason == "eos"
    assert h.generated == full[:k + 1]   # the EOS token itself is emitted
    assert eng.allocator.available == serve.num_blocks - 1


def test_finish_at_prefill_max_new_one(tiny_params, tiny_config):
    # max_new_tokens=1 finishes inside admission: first token only, no
    # decode steps, blocks returned without ever scattering.
    eng = ServingEngine(tiny_params, tiny_config, _serve(), temperature=0.0)
    h = eng.submit([5, 6, 7], 1, rng=0)
    eng.run_until_idle(max_steps=10)
    ref = _oneshot(tiny_params, tiny_config, [5, 6, 7],
                   jax.random.PRNGKey(0), 1, temperature=0.0)
    assert h.generated == ref and h.finish_reason == "length"
    assert eng.stats["decode_steps"] == 0
    assert eng.allocator.available == eng.serve.num_blocks - 1


def test_padding_edges_block_multiple_and_exact_context_fit(
    tiny_params, tiny_config,
):
    # Prompt exactly a block multiple (no pad), and prompt+new == the full
    # context window (the last writable position is used, never exceeded).
    npos = tiny_config.n_positions
    cases = [
        ([3] * 8, 5),               # len == block_size -> zero right-pad
        ([7] * (npos - 6), 6),      # exact fit: P + new == n_positions
    ]
    serve = _serve(num_blocks=2 * (npos // 8) + 1)
    eng = ServingEngine(tiny_params, tiny_config, serve, temperature=0.0)
    hs = [eng.submit(p, n, rng=jax.random.PRNGKey(9)) for p, n in cases]
    eng.run_until_idle(max_steps=200)
    for h, (p, n) in zip(hs, cases):
        ref = _oneshot(tiny_params, tiny_config, p,
                       jax.random.PRNGKey(9), n, temperature=0.0)
        assert h.generated == ref, (len(p), n)


def test_prefill_bucket_straddles_n_positions(tiny_params, tiny_config):
    # block_size=12 on n_positions=64: a 61-token prompt buckets to 72,
    # past the position table — the forward runs at 64, K/V zero-pad to the
    # scatter width, and the result still bit-matches the one-shot path.
    npos = tiny_config.n_positions
    assert npos % 12 != 0
    p = [11] * (npos - 3)
    serve = _serve(block_size=12, num_blocks=16)
    eng = ServingEngine(tiny_params, tiny_config, serve, temperature=0.0)
    h = eng.submit(p, 3, rng=jax.random.PRNGKey(4))
    eng.run_until_idle(max_steps=50)
    ref = _oneshot(tiny_params, tiny_config, p,
                   jax.random.PRNGKey(4), 3, temperature=0.0)
    assert h.generated == ref


def test_pallas_engine_matches_xla_engine(tiny_params, tiny_config):
    prompts, news, keys = _mixed_trace()
    outs = {}
    for impl in ("xla", "pallas"):
        eng = ServingEngine(tiny_params, tiny_config,
                            _serve(attn_impl=impl), temperature=0.0)
        hs = [eng.submit(p, n, rng=k)
              for p, n, k in zip(prompts[:3], news[:3], keys[:3])]
        eng.run_until_idle(max_steps=200)
        outs[impl] = [h.generated for h in hs]
    assert outs["pallas"] == outs["xla"]


def test_streaming_callbacks_order_and_ttft(tiny_params, tiny_config):
    got = []
    eng = ServingEngine(tiny_params, tiny_config, _serve(), temperature=0.0)
    h = eng.submit([1, 2, 3], 6, rng=0,
                   on_token=lambda req, t: got.append((req.id, t)))
    eng.run_until_idle(max_steps=50)
    # Every token streamed, in generation order, tagged with the request.
    assert got == [(h.id, t) for t in h.generated]
    assert len(h.generated) == 6
    # The timestamps the bench derives TTFT/latency from are all stamped
    # and ordered: submit <= first token <= finish.
    assert h.submit_time <= h.first_token_time <= h.finish_time


def test_submit_validation_shared_with_decode_paths(
    tiny_params, tiny_config,
):
    eng = ServingEngine(tiny_params, tiny_config, _serve(), temperature=0.0)
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit([1, 2], 0, rng=0)
    with pytest.raises(ValueError, match="exceeds"):
        eng.submit([1] * tiny_config.n_positions, 4, rng=0)
    # A request too big for the WHOLE pool can never be admitted: rejected
    # at submit, not deadlocked in the queue.
    small = ServingEngine(
        tiny_params, tiny_config, _serve(num_blocks=3), temperature=0.0,
    )
    with pytest.raises(ValueError, match="could never be admitted"):
        small.submit([1] * 20, 10, rng=0)
    # Engine-level sampling config fails the same shared check.
    with pytest.raises(ValueError, match="top_k"):
        ServingEngine(tiny_params, tiny_config, _serve(),
                      temperature=1.0, top_k=0)


# ----------------------------------------- chunked prefill / prefix cache


def test_chunked_prefill_bit_parity_any_chunk_width(tiny_params, tiny_config):
    # The chunk split is a scheduling choice, not a numerics choice: any
    # width reproduces whole-prompt prefill bit-for-bit, and the fixed
    # width keeps the chunk program at ONE compile per engine.
    prompts, news, keys = _mixed_trace()
    for chunk in (1, 3, 19):
        eng = ServingEngine(tiny_params, tiny_config,
                            _serve(prefill_chunk=chunk), temperature=0.0)
        hs = [eng.submit(p, n, rng=k)
              for p, n, k in zip(prompts, news, keys)]
        eng.run_until_idle(max_steps=500)
        assert eng._chunk_fn._cache_size() == 1, chunk
        assert eng._decode_fn._cache_size() == 1, chunk
        for h, p, n, k in zip(hs, prompts, news, keys):
            ref = _oneshot(tiny_params, tiny_config, p, k, n, temperature=0.0)
            assert h.generated == ref, (chunk, h.id)
        assert eng.allocator.available == eng.serve.num_blocks - 1


def test_chunked_prefill_sampled_prng_chain_intact(tiny_params, tiny_config):
    # Every chunk samples (one compiled program), the host discards all but
    # the final draw — the request's threefry chain must land exactly where
    # the one-shot path leaves it.
    prompts, news, keys = _mixed_trace()
    eng = ServingEngine(tiny_params, tiny_config, _serve(prefill_chunk=5),
                        temperature=0.9, top_k=40)
    hs = [eng.submit(p, n, rng=k) for p, n, k in zip(prompts, news, keys)]
    eng.run_until_idle(max_steps=500)
    for h, p, n, k in zip(hs, prompts, news, keys):
        ref = _oneshot(tiny_params, tiny_config, p, k, n,
                       temperature=0.9, top_k=40)
        assert h.generated == ref, h.id


def test_prefix_cache_reuse_bit_parity_and_accounting(
    tiny_params, tiny_config,
):
    # Two prompts sharing a 16-token (2-block) prefix: the second must skip
    # prefill for the cached span, report it, and still stream the exact
    # bits of a cold run — cached K/V is a pure function of the prefix.
    pfx = list(range(50, 66))
    p1, p2 = pfx + [7, 8, 9], pfx + [10, 11]
    eng = ServingEngine(tiny_params, tiny_config, _serve(prefix_cache=True),
                        temperature=0.0)
    h1 = eng.submit(p1, 6, rng=jax.random.PRNGKey(1))
    eng.run_until_idle(max_steps=100)
    assert eng.stats["prefix_hit_tokens"] == 0
    h2 = eng.submit(p2, 6, rng=jax.random.PRNGKey(2))
    eng.run_until_idle(max_steps=100)
    assert eng.stats["prefix_hit_tokens"] == 16
    assert h1.prefix_cached_tokens == 0 and h2.prefix_cached_tokens == 16
    for h, p, s in ((h1, p1, 1), (h2, p2, 2)):
        ref = _oneshot(tiny_params, tiny_config, p, jax.random.PRNGKey(s), 6,
                       temperature=0.0)
        assert h.generated == ref, h.id
    # Cache entries are the only blocks still out; clearing balances books.
    assert eng.allocator.available == (
        eng.serve.num_blocks - 1 - len(eng._cache)
    )
    eng.clear_prefix_cache()
    assert eng.allocator.available == eng.serve.num_blocks - 1


def test_prefix_cache_with_chunked_prefill_sampled(tiny_params, tiny_config):
    # The two features compose: a cache hit moves the chunk walk's start,
    # chunks resume mid-prompt, and the sampled stream is still bit-exact.
    pfx = list(range(30, 46))
    specs = [(pfx + [9, 8, 7, 6], 7), (pfx + [5, 4], 5), (pfx[:8] + [3], 4)]
    eng = ServingEngine(
        tiny_params, tiny_config,
        _serve(prefix_cache=True, prefill_chunk=3),
        temperature=0.9, top_k=40,
    )
    hs = []
    for i, (p, n) in enumerate(specs):
        hs.append(eng.submit(p, n, rng=jax.random.PRNGKey(60 + i)))
        eng.run_until_idle(max_steps=200)   # serialize to make hits certain
    assert eng.stats["prefix_hit_tokens"] == 16 + 8
    assert eng._chunk_fn._cache_size() == 1
    for h, (p, n), i in zip(hs, specs, range(3)):
        ref = _oneshot(tiny_params, tiny_config, p,
                       jax.random.PRNGKey(60 + i), n,
                       temperature=0.9, top_k=40)
        assert h.generated == ref, h.id


def test_cow_on_block_aligned_cached_prompt(tiny_params, tiny_config):
    # A fully-cached, block-aligned prompt must copy-on-write its tail
    # block: the last position is recomputed for its logits and scattered
    # into the PRIVATE copy. The shared entry must survive unscathed for a
    # third request that extends the prefix.
    p = list(range(100, 116))               # exactly 2 blocks of 8
    eng = ServingEngine(tiny_params, tiny_config, _serve(prefix_cache=True),
                        temperature=0.0)
    key = jax.random.PRNGKey(5)
    h1 = eng.submit(p, 5, rng=key)
    eng.run_until_idle(max_steps=100)
    h2 = eng.submit(p, 5, rng=key)          # identical prompt: full hit
    eng.run_until_idle(max_steps=100)
    assert eng.stats["cow_copies"] == 1
    assert h2.prefix_cached_tokens == 15    # all but the recomputed last
    ref = _oneshot(tiny_params, tiny_config, p, key, 5, temperature=0.0)
    assert h1.generated == ref and h2.generated == ref
    # h2 decoded over its private tail copy; the cached block must still
    # hold the ORIGINAL prefix K/V for an extending prompt.
    p3 = p + [11, 12, 13]
    h3 = eng.submit(p3, 4, rng=key)
    eng.run_until_idle(max_steps=100)
    assert h3.prefix_cached_tokens == 16
    ref3 = _oneshot(tiny_params, tiny_config, p3, key, 4, temperature=0.0)
    assert h3.generated == ref3


@pytest.mark.parametrize("chunk", [0, 5], ids=["whole-prompt", "chunked"])
def test_split_block_axis_pool_bit_parity(tiny_params, tiny_config, chunk):
    """Past 64 blocks a pool is stored with its block axis split (70 ->
    [L, 2, 35, H, bs, D]; on the TPU that keeps it row-major, PR 26) and
    every program works on the merged view: block ids on both sides of the
    seam - whole-prompt scatter or chunk writes, a copy-on-write, decode
    writes - and every stream still equals ``generate_cached``."""
    from gpt_2_distributed_tpu.serving.paged_cache import pool_shape, split_blocks

    assert split_blocks(64) == (64,) and split_blocks(257) == (6, 43)
    three = split_blocks(8193)          # 128 slots x 64 blocks + the null one
    assert len(three) == 3 and max(three) <= 64
    assert 8193 <= three[0] * three[1] * three[2] <= 8200
    serve = _serve(num_blocks=70, prefill_chunk=chunk, prefix_cache=True)
    eng = ServingEngine(tiny_params, tiny_config, serve, temperature=0.0)
    assert eng.k_pool.shape == pool_shape(tiny_config, serve)
    assert eng.k_pool.shape[1:3] == (2, 35)
    shared = list(range(100, 116))                  # two full blocks of 8
    prompts = [shared] * 2 + [[30 + i] * (14 + i) for i in range(10)]
    keys = [jax.random.PRNGKey(70 + i) for i in range(len(prompts))]
    seen = set()
    hs = [eng.submit(prompts[0], 12, rng=keys[0])]
    eng.run_until_idle(max_steps=100)   # cached before its twin arrives
    hs += [eng.submit(p, 12, rng=k) for p, k in zip(prompts[1:], keys[1:])]
    while not all(h.done for h in hs):
        eng.step()
        seen.update(int(b) for b in eng.block_table.ravel())
    assert eng.stats["cow_copies"] >= 1
    assert min(seen - {0}) < 35 < max(seen), sorted(seen)
    for h, p, k in zip(hs, prompts, keys):
        assert h.generated == _oneshot(
            tiny_params, tiny_config, p, k, 12, temperature=0.0), h.id
    assert eng.k_pool.shape == pool_shape(tiny_config, serve)


# ------------------------------------------- watermark admission / preempt


def test_watermark_preemption_bit_parity_and_accounting(
    tiny_params, tiny_config,
):
    # 6 requests, 7 allocatable blocks, lazy grants: growth must exhaust
    # the pool and preempt (newest victim), and every stream must still
    # bit-match its solo run — recompute-prefill restores the PRNG chain
    # head and never re-emits.
    serve = _serve(max_batch=4, num_blocks=8,
                   admission="watermark", watermark_blocks=1)
    eng = ServingEngine(tiny_params, tiny_config, serve, temperature=0.0)
    specs = [([3 * i + 1, 3 * i + 2, 3 * i + 3], 14) for i in range(6)]
    hs = [eng.submit(p, n, rng=jax.random.PRNGKey(40 + i))
          for i, (p, n) in enumerate(specs)]
    eng.run_until_idle(max_steps=1000)
    assert eng.stats["preemptions"] > 0
    assert eng.stats["preemptions"] == sum(h.preemptions for h in hs)
    # Whole-prompt resumes share ONE full-width chunk program; decode
    # stays one program through all the churn.
    assert eng._chunk_fn._cache_size() == 1
    assert eng._decode_fn._cache_size() == 1
    for h, (p, n), i in zip(hs, specs, range(6)):
        ref = _oneshot(tiny_params, tiny_config, p,
                       jax.random.PRNGKey(40 + i), n, temperature=0.0)
        assert h.generated == ref, h.id
        assert h.resumes == h.preemptions       # every swap-out came back
        assert h.queue_wait_ms >= 0 and h.done
        assert h.submit_time <= h.first_token_time <= h.finish_time
    assert eng.allocator.available == serve.num_blocks - 1


@pytest.mark.parametrize(
    "chunk,temp", [(0, 0.0), (0, 0.9), (5, 0.0), (5, 0.9)],
)
def test_scheduler_churn_property(tiny_params, tiny_config, chunk, temp):
    # The whole scheduler surface at once: shared-prefix traffic, chunked
    # or whole prefill, watermark grants sized to force preemption — and
    # the exactness contract must hold for EVERY request, greedy and
    # sampled, with the compiled-program census unchanged.
    rng = np.random.default_rng(7)
    pfx = list(range(200, 208))             # one full shared block
    plens, news = (5, 9, 13, 17), (6, 12)   # few shapes: refs stay cheap
    specs = []
    for i in range(8):
        pl, nw = plens[i % 4], news[i % 2]
        p = (pfx + rng.integers(1, 257, pl - 8).tolist()
             if i % 3 != 2 and pl > 8
             else rng.integers(1, 257, pl).tolist())
        specs.append((p, nw))
    top_k = 40 if temp else None
    serve = _serve(max_batch=4, num_blocks=8, prefix_cache=True,
                   admission="watermark", watermark_blocks=1,
                   prefill_chunk=chunk)
    eng = ServingEngine(tiny_params, tiny_config, serve,
                        temperature=temp, top_k=top_k)
    hs = [eng.submit(p, n, rng=jax.random.PRNGKey(1000 + i))
          for i, (p, n) in enumerate(specs)]
    eng.run_until_idle(max_steps=2000)
    assert eng._decode_fn._cache_size() == 1
    if chunk:
        assert eng._chunk_fn._cache_size() == 1
    assert eng.stats["preemptions"] > 0     # the pool is sized to force it
    assert eng.stats["prefix_hit_tokens"] > 0
    for h, (p, n), i in zip(hs, specs, range(8)):
        ref = _oneshot(tiny_params, tiny_config, p,
                       jax.random.PRNGKey(1000 + i), n,
                       temperature=temp, top_k=top_k)
        assert h.generated == ref, h.id
    assert eng.allocator.available == (
        serve.num_blocks - 1 - len(eng._cache)
    )
    eng.clear_prefix_cache()
    assert eng.allocator.available == serve.num_blocks - 1


def test_pool_garbage_is_invisible_under_chunked_prefill(
    tiny_params, tiny_config,
):
    # Chunked prefill scatters K/V at position granularity, so unwritten
    # pool positions keep whatever they held. Pre-poisoning the entire pool
    # must not flip a single output bit: every read is either overwritten
    # first or causally masked to an exact zero.
    prompts, news, keys = _mixed_trace()
    outs = []
    for poison in (False, True):
        eng = ServingEngine(
            tiny_params, tiny_config,
            _serve(prefill_chunk=3, prefix_cache=True,
                   admission="watermark"),
            temperature=0.0,
        )
        if poison:
            eng.k_pool = jnp.full_like(eng.k_pool, 999.0)
            eng.v_pool = jnp.full_like(eng.v_pool, -999.0)
        hs = [eng.submit(p, n, rng=k)
              for p, n, k in zip(prompts, news, keys)]
        eng.run_until_idle(max_steps=500)
        outs.append([h.generated for h in hs])
    assert outs[0] == outs[1]
    for got, p, n, k in zip(outs[1], prompts, news, keys):
        ref = _oneshot(tiny_params, tiny_config, p, k, n, temperature=0.0)
        assert got == ref


# -------------------------------------------------------- serving CLIs


@pytest.mark.parametrize("cli", ["serve", "frontend"])
def test_cli_help_is_jax_free(run_cli_jax_free, cli):
    r = run_cli_jax_free(cli, "--help")
    assert r.returncode == 0, r.stderr[-500:]
    for flag in ("--prefill_chunk", "--admission", "--serve_mesh",
                 "--placement", "--inject_replica_fail_at", "--spec_k"):
        assert flag in r.stdout, flag


@pytest.mark.parametrize("cli", ["serve", "frontend"])
@pytest.mark.parametrize("flags, named", [
    (("--prefill_chunk", "-1"), "prefill_chunk=-1"),
    (("--watermark_blocks", "-1"), "watermark_blocks=-1"),
    (("--prefill_batch", "0"), "prefill_batch=0"),
    # mesh specs go through config.parse_serve_mesh
    (("--serve_mesh", "fsdp:2"), "unknown axis 'fsdp'"),
    (("--serve_mesh", "data:0"), "degree must be >= 1"),
], ids=" ".join)
def test_cli_fleet_parent_refuses_engine_flags_jax_free(
    run_cli_jax_free, tmp_path, cli, flags, named
):
    """The parent of a worker fleet builds its ServeConfig, whose ranges
    and mesh grammar refuse these, before it spawns a worker — and never
    loads jax (the poisoned one would stop it first)."""
    requests = tmp_path / "requests.jsonl"
    requests.write_text('{"prompt_ids": [1, 2, 3]}\n')
    r = run_cli_jax_free(cli, "--placement", "subprocess", *flags,
                         *(("--requests", str(requests))
                           if cli == "serve" else ()))
    assert r.returncode != 0
    assert named in r.stderr, r.stderr[-300:]


@pytest.mark.slow
def test_serve_cli_end_to_end_stream(tmp_path):
    # gpt2-tpu-serve over a JSONL request file with --stream: one token
    # line per generated token plus a final record per request.
    reqs = tmp_path / "reqs.jsonl"
    reqs.write_text(
        '{"prompt_ids": [1, 2, 3], "new": 4, "seed": 0}\n'
        '{"prompt_ids": [9, 8], "new": 3, "seed": 1}\n'
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-m", "gpt_2_distributed_tpu.serving.serve",
         "--init_random",
         "--n_layer", "2", "--n_embd", "32", "--n_head", "2",
         "--vocab_size", "257", "--seq_len", "64",
         "--requests", str(reqs), "--temperature", "0",
         "--max_batch", "2", "--block_size", "8", "--stream",
         "--prefill_chunk", "2", "--prefix_cache",
         "--admission", "watermark", "--watermark_blocks", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=420,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [json.loads(x) for x in r.stdout.strip().splitlines()]
    finals = [x for x in lines if "generated" in x]
    streams = [x for x in lines if "token" in x]
    assert len(finals) == 2
    assert {f["finish_reason"] for f in finals} == {"length"}
    for f in finals:
        toks = [s["token"] for s in streams if s["id"] == f["id"]]
        assert toks == f["generated"]
        assert f["ttft_ms"] >= 0
        # Scheduler accounting rides along on every final record.
        assert f["queue_wait_ms"] >= 0
        assert f["preempted"] == 0          # pool is ample here
        assert f["prefix_cached_tokens"] >= 0
