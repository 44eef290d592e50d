"""Speculative decoding with a self-hosted draft model (PR 20).

The bar: speculation is an OPTIMIZATION, invisible in tokens. Greedy
streams must stay bit-identical to ``generate_cached(batch=1)`` for any
draft run length k — through chunked prefill, prefix-cache hits,
watermark preemption and cross-engine migration — and sampled streams
must be distributed exactly as the target model (the accept/resample
rule), which the fp64 Monte-Carlo test pins against the closed form and
an engine-level histogram cross-checks end to end. Speculation is
default-off and opt-in per engine via ``ServeConfig.spec``; the flag
family is refused jax-free at parse time on all three CLIs.
"""

from __future__ import annotations

import numpy as np
import pytest

from gpt_2_distributed_tpu.config import (
    GPT2Config,
    ServeConfig,
    parse_serve_spec,
)
from gpt_2_distributed_tpu.models import gpt2
from gpt_2_distributed_tpu.serving import ServingEngine
from gpt_2_distributed_tpu.serving.engine import (
    _spec_accept,
    _spec_cdf_sample,
    _spec_probs,
)
from gpt_2_distributed_tpu.serving.paged_cache import draft_serve_view

from test_serving import _oneshot, _serve


@pytest.fixture(scope="module")
def tiny_params(tiny_config):
    return gpt2.init_params(tiny_config, seed=0)


@pytest.fixture(scope="module")
def draft(tiny_config):
    """A genuinely different (smaller) model drafting for the target —
    the shrunken-config arrangement the CLIs use for 124M on CPU."""
    draft_config = tiny_config.replace(n_layer=1)
    return gpt2.init_params(draft_config, seed=1), draft_config


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(3)
    return [
        list(map(int, rng.integers(1, 256, size=n)))
        for n in (5, 11, 17, 3)
    ]


@pytest.fixture(scope="module")
def greedy_refs(tiny_params, tiny_config, prompts):
    import jax

    return [
        _oneshot(tiny_params, tiny_config, p, jax.random.PRNGKey(i), 8,
                 temperature=0.0)
        for i, p in enumerate(prompts)
    ]


def _spec_engine(params, config, serve, draft, **kw):
    draft_params, draft_config = draft
    return ServingEngine(params, config, serve, draft_params=draft_params,
                         draft_config=draft_config, **kw)


# ----------------------------------------------------------- config/spec


class TestParseServeSpec:
    def test_parse_forms(self):
        assert parse_serve_spec("") == (None, 0)
        assert parse_serve_spec("draft:124M,k:4") == ("124M", 4)
        assert parse_serve_spec("draft=124M,k=2") == ("124M", 2)
        assert ServeConfig(spec="draft:124M,k:3").spec_k == 3
        assert ServeConfig().spec_k == 0          # default off

    @pytest.mark.parametrize("bad", [
        "draft:124M",                  # missing k
        "k:4",                         # missing draft
        "draft:124M,k:0",              # k < 1
        "draft:124M,k:x",              # non-integer k
        "draft:bogus,k:4",             # unknown preset
        "draft:124M,k:4,extra:1",      # unknown key
        "draft:124M,draft:124M,k:4",   # duplicate key
    ])
    def test_rejects(self, bad):
        with pytest.raises(ValueError):
            parse_serve_spec(bad)

    def test_serve_config_validates_at_construction(self):
        with pytest.raises(ValueError):
            ServeConfig(spec="draft:bogus,k:4")


class TestEngineValidation:
    def test_spec_without_draft_model(self, tiny_params, tiny_config):
        with pytest.raises(ValueError, match="draft"):
            ServingEngine(tiny_params, tiny_config,
                          _serve(spec="draft:124M,k:2"))

    def test_draft_without_spec(self, tiny_params, tiny_config, draft):
        with pytest.raises(ValueError, match="spec"):
            _spec_engine(tiny_params, tiny_config, _serve(), draft)

    def test_draft_not_smaller(self, tiny_params, tiny_config):
        with pytest.raises(ValueError, match="smaller"):
            ServingEngine(tiny_params, tiny_config,
                          _serve(spec="draft:124M,k:2"),
                          draft_params=tiny_params,
                          draft_config=tiny_config)

    def test_draft_vocab_mismatch(self, tiny_config, tiny_params):
        dc = tiny_config.replace(n_layer=1, vocab_size=259)
        with pytest.raises(ValueError, match="vocab"):
            ServingEngine(tiny_params, tiny_config,
                          _serve(spec="draft:124M,k:2"),
                          draft_params=gpt2.init_params(dc, seed=1),
                          draft_config=dc)

    def test_draft_positions_too_small(self, tiny_config, tiny_params):
        dc = tiny_config.replace(n_layer=1, n_positions=32)
        with pytest.raises(ValueError, match="n_positions"):
            ServingEngine(tiny_params, tiny_config,
                          _serve(spec="draft:124M,k:2"),
                          draft_params=gpt2.init_params(dc, seed=1),
                          draft_config=dc)


def test_draft_serve_view_full_per_slot_capacity():
    """The draft pool reuses the allocator machinery at full per-slot
    capacity: a draft block-run allocation can never fail, so a spec
    round never deadlocks on draft blocks (only target blocks preempt)."""
    serve = _serve(max_batch=4, block_size=8, num_blocks=19)
    dv = draft_serve_view(serve, n_positions=64)
    assert dv.spec == "" and dv.prefix_cache is False
    m = dv.max_blocks_per_seq(64)
    assert dv.num_blocks == 4 * m + 1     # all slots full-length + null
    assert dv.block_size == serve.block_size


# ------------------------------------------------- greedy bit-equality


@pytest.mark.parametrize("k", [1, 2, 4])
def test_greedy_bit_equality(tiny_params, tiny_config, draft, prompts,
                             greedy_refs, k):
    eng = _spec_engine(tiny_params, tiny_config,
                       _serve(spec=f"draft:124M,k:{k}"), draft,
                       temperature=0.0)
    hs = [eng.submit(p, 8, rng=i) for i, p in enumerate(prompts)]
    eng.run_until_idle(max_steps=500)
    assert [h.generated for h in hs] == greedy_refs
    assert eng.stats["spec_draft_tokens"] > 0
    assert eng.stats["spec_accepted_tokens"] >= 0


def test_greedy_bit_equality_chunked_prefill_prefix_hits(
    tiny_params, tiny_config, draft, prompts
):
    """Chunked prefill + prefix-cache hits under speculation: requests
    share an 8-token (full-block) prefix, so later admissions resume
    from cached blocks — the draft catch-up pass must rebuild draft KV
    over tokens the TARGET never re-prefilled."""
    import jax

    shared = prompts[1][:8]
    reqs = [shared + p for p in prompts]
    refs = [
        _oneshot(tiny_params, tiny_config, p, jax.random.PRNGKey(i), 8,
                 temperature=0.0)
        for i, p in enumerate(reqs)
    ]
    eng = _spec_engine(
        tiny_params, tiny_config,
        _serve(spec="draft:124M,k:2", prefill_chunk=8, prefix_cache=True),
        draft, temperature=0.0,
    )
    # first request alone registers the prefix blocks; the rest hit them
    hs = [eng.submit(reqs[0], 8, rng=0)]
    eng.run_until_idle(max_steps=500)
    hs += [eng.submit(p, 8, rng=i) for i, p in enumerate(reqs[1:], 1)]
    eng.run_until_idle(max_steps=500)
    assert [h.generated for h in hs] == refs
    assert eng.stats["prefix_hit_tokens"] > 0


def test_greedy_bit_equality_watermark_preemption(
    tiny_params, tiny_config, draft, prompts
):
    """A tight pool under watermark admission: preemption discards draft
    KV with the slot; the resumed request must re-draft and stay
    bit-identical (the draft pool itself never preempts — it is sized
    for every slot at full length)."""
    import jax

    shared = prompts[2]                  # 17 tokens
    reqs = [shared + p for p in prompts]
    refs = [
        _oneshot(tiny_params, tiny_config, p, jax.random.PRNGKey(i), 12,
                 temperature=0.0)
        for i, p in enumerate(reqs)
    ]
    eng = _spec_engine(
        tiny_params, tiny_config,
        _serve(max_batch=4, num_blocks=16, spec="draft:124M,k:2",
               prefill_chunk=8, prefix_cache=True, admission="watermark",
               watermark_blocks=1),
        draft, temperature=0.0,
    )
    hs = [eng.submit(p, 12, rng=i) for i, p in enumerate(reqs)]
    eng.run_until_idle(max_steps=1000)
    assert [h.generated for h in hs] == refs


@pytest.mark.parametrize("mesh", ["data:2", "data:2,tp:2"])
def test_greedy_bit_equality_sharded(tiny_params, tiny_config, draft,
                                     prompts, greedy_refs, mesh):
    """The mesh must stay invisible under speculation too: draft pool
    blocks shard over 'data' like the target pool, draft heads over
    'tp'."""
    eng = _spec_engine(tiny_params, tiny_config,
                       _serve(spec="draft:124M,k:2", mesh=mesh,
                              num_blocks=64, prefill_chunk=8,
                              prefix_cache=True),
                       draft, temperature=0.0)
    hs = [eng.submit(p, 8, rng=i) for i, p in enumerate(prompts)]
    eng.run_until_idle(max_steps=500)
    assert [h.generated for h in hs] == greedy_refs


@pytest.mark.parametrize("mesh", ["", "data:2,tp:2"])
def test_draft_tree_is_held_in_the_compute_dtype(tiny_params, tiny_config,
                                                 draft, mesh):
    """The draft model's weights are held as the target's are: cast once
    at construction (its LayerNorm leaves stay float32), placed under the
    mesh as cast, and the caller's float32 tree left alive."""
    import jax
    import jax.numpy as jnp

    from test_serving import LAYER_NORMS, _leaves

    eng = _spec_engine(tiny_params, tiny_config,
                       _serve(spec="draft:124M,k:2", mesh=mesh, num_blocks=64),
                       draft, temperature=0.0)
    for held, given in ((eng.params, tiny_params), (eng.draft_params, draft[0])):
        assert sorted(n for n, a in _leaves(held).items()
                      if a.dtype == jnp.float32) == sorted(LAYER_NORMS)
        assert all(a.dtype == jnp.float32 and not a.is_deleted()
                   for a in jax.tree_util.tree_leaves(given))
    if mesh:
        assert "tp" in str(eng.draft_params["block"]["attn_qkv_w"].sharding.spec)


# ------------------------------------- migration during speculation


def test_migration_during_speculation_across_mesh_shapes(
    tiny_params, tiny_config, draft, prompts
):
    """extract_inflight mid-speculation on a data:2 engine, adopt into a
    data:2,tp:2 engine: draft KV is disposable — the adopting engine
    re-drafts from the committed stream — so every stream completes
    bit-identically with zero re-emitted tokens and no wire-format
    change."""
    import jax

    serve_a = _serve(max_batch=4, num_blocks=64, mesh="data:2",
                     spec="draft:124M,k:3")
    serve_b = _serve(max_batch=4, num_blocks=64, mesh="data:2,tp:2",
                     spec="draft:124M,k:3")
    eng_a = _spec_engine(tiny_params, tiny_config, serve_a, draft,
                         temperature=0.0)
    streams: dict[int, list[int]] = {}

    def on_token(req, tok):
        streams.setdefault(req.id, []).append(tok)

    # k=3 emits up to 4 tokens a round: 24 new tokens outlast the three
    # steps before the extraction, where the shared refs' 8 do not.
    new = 24
    hs = [eng_a.submit(p, new, rng=i, on_token=on_token)
          for i, p in enumerate(prompts)]
    for _ in range(3):                   # prefills + at least one round
        eng_a.step()
    moved = eng_a.extract_inflight()
    assert moved, "nothing in flight to migrate"
    assert len(moved) == sum(1 for h in hs if not h.done)
    assert any(0 < len(h.generated) < new for h in hs)
    eng_b = _spec_engine(tiny_params, tiny_config, serve_b, draft,
                         temperature=0.0)
    for req in moved:
        eng_b.adopt(req)
    eng_b.run_until_idle(max_steps=500)
    for i, (h, p) in enumerate(zip(hs, prompts)):
        ref = _oneshot(tiny_params, tiny_config, p, jax.random.PRNGKey(i),
                       new, temperature=0.0)
        assert h.generated == ref
        assert streams[h.id] == h.generated   # no re-emits, no gaps


def test_migration_between_spec_and_plain_engines(
    tiny_params, tiny_config, draft, prompts, greedy_refs
):
    """The wire format carries no draft state, so requests migrate
    freely across the speculation boundary in BOTH directions: a plain
    engine adopts a spec engine's requests (and vice versa) with
    bit-identical streams."""
    spec_serve = _serve(spec="draft:124M,k:2")
    eng_spec = _spec_engine(tiny_params, tiny_config, spec_serve, draft,
                            temperature=0.0)
    hs = [eng_spec.submit(p, 8, rng=i) for i, p in enumerate(prompts)]
    for _ in range(3):
        eng_spec.step()
    eng_plain = ServingEngine(tiny_params, tiny_config, _serve(),
                              temperature=0.0)
    for req in eng_spec.extract_inflight():
        eng_plain.adopt(req)
    eng_plain.run_until_idle(max_steps=500)
    assert [h.generated for h in hs] == greedy_refs

    # and back: plain -> speculative
    eng_plain2 = ServingEngine(tiny_params, tiny_config, _serve(),
                               temperature=0.0)
    hs2 = [eng_plain2.submit(p, 8, rng=i) for i, p in enumerate(prompts)]
    for _ in range(3):
        eng_plain2.step()
    eng_spec2 = _spec_engine(tiny_params, tiny_config, spec_serve, draft,
                             temperature=0.0)
    for req in eng_plain2.extract_inflight():
        eng_spec2.adopt(req)
    eng_spec2.run_until_idle(max_steps=500)
    assert [h.generated for h in hs2] == greedy_refs


# -------------------------------------- sampled: target distribution


def test_accept_resample_marginal_is_target_distribution():
    """The fp64 Monte-Carlo pin of the acceptance rule: over seeded
    trials, the FIRST emitted token of a k=1 round — draft sampled from
    q, accept coin, residual resample — must be distributed exactly as
    the target p. Closed form: q(d)min(1, p(d)/q(d)) + P(reject) *
    residual(d) = min(p,q) + max(p-q, 0) = p. The empirical TV distance
    has no model noise (everything fp64, seeded), only MC noise."""
    rng = np.random.default_rng(0)
    vocab = 7
    vlogits = rng.normal(size=(2, vocab)).astype(np.float32) * 2.0
    qlogits = rng.normal(size=vocab) * 1.5
    q = _spec_probs(qlogits, 1.0, None)
    p = _spec_probs(vlogits[0], 1.0, None)

    trials = 20_000
    unis = rng.random((trials, 4))       # 3k+1 = 4 uniforms per round
    counts = np.zeros(vocab)
    accepted_total = 0
    for t in range(trials):
        d = _spec_cdf_sample(q, unis[t, 0])
        emit, accepted = _spec_accept(
            vlogits, np.array([d], np.int32), [q], unis[t], 1.0, None
        )
        counts[emit[0]] += 1
        accepted_total += accepted
    tv = 0.5 * np.abs(counts / trials - p).sum()
    assert tv < 0.02, (tv, counts / trials, p)
    # acceptance rate must match sum(min(p, q)) — the closed form
    alpha = float(np.minimum(p, q).sum())
    assert accepted_total / trials == pytest.approx(alpha, abs=0.02)


def test_accept_resample_with_top_k_masks_like_sample_token():
    """top_k masking flows through both distributions: emitted tokens
    must stay inside the target's top-k support."""
    rng = np.random.default_rng(1)
    vocab = 9
    vlogits = rng.normal(size=(2, vocab)).astype(np.float32)
    q = _spec_probs(rng.normal(size=vocab), 1.0, 3)
    p = _spec_probs(vlogits[0], 1.0, 3)
    support = set(np.flatnonzero(p > 0).tolist())
    for t in range(2_000):
        unis = rng.random(4)
        d = _spec_cdf_sample(q, unis[0])
        emit, _ = _spec_accept(
            vlogits, np.array([d], np.int32), [q], unis, 1.0, 3
        )
        assert emit[0] in support


def test_greedy_accept_rule_emits_only_argmaxes():
    vlogits = np.array([[0.0, 3.0, 1.0],
                        [2.0, 0.0, 1.0],
                        [0.0, 1.0, 5.0]], np.float32)
    # clean sweep: both drafts match, bonus appended
    emit, acc = _spec_accept(vlogits, np.array([1, 0], np.int32),
                             None, None, 0.0, None)
    assert (emit, acc) == ([1, 0, 2], 2)
    # first mismatch: correction replaces the draft, round truncates
    emit, acc = _spec_accept(vlogits, np.array([2, 0], np.int32),
                             None, None, 0.0, None)
    assert (emit, acc) == ([1], 0)


def test_sampled_engine_distribution_matches_plain(tiny_config):
    """Engine-level distribution check on a small vocab: the pooled
    token histogram from a speculative engine must match a plain
    engine's over the same request set (both sample the target process;
    only the PRNG realization differs). Deterministic seeds — the
    tolerance covers sampling noise only."""
    config = GPT2Config(
        vocab_size=13, n_positions=32, n_embd=16, n_layer=2, n_head=2,
        embd_dropout=0.0, attn_dropout=0.0, resid_dropout=0.0,
    )
    params = gpt2.init_params(config, seed=0)
    draft_config = config.replace(n_layer=1)
    draft_params = gpt2.init_params(draft_config, seed=1)
    serve_on = _serve(max_batch=8, spec="draft:124M,k:2")
    serve_off = _serve(max_batch=8)

    n_req, n_new = 200, 4
    prompt = [1, 2, 3]

    def harvest(eng):
        hs = [eng.submit(prompt, n_new, rng=i) for i in range(n_req)]
        eng.run_until_idle(max_steps=3000)
        toks = [t for h in hs for t in h.generated]
        assert len(toks) == n_req * n_new
        return np.bincount(toks, minlength=config.vocab_size)

    hist_on = harvest(ServingEngine(
        params, config, serve_on, draft_params=draft_params,
        draft_config=draft_config, temperature=1.0,
    ))
    hist_off = harvest(ServingEngine(
        params, config, serve_off, temperature=1.0,
    ))
    n = n_req * n_new
    tv = 0.5 * np.abs(hist_on / n - hist_off / n).sum()
    assert tv < 0.15, (tv, hist_on, hist_off)


# -------------------------------------------- telemetry + trace spans


def test_spec_round_spans_events_and_report(tiny_params, tiny_config,
                                            draft, prompts, tmp_path):
    """Satellite 3 end to end: a traced speculative run emits draft and
    verify spans plus one spec_accept event per slot-round, and
    obs_report's speculation_summary recovers acceptance rate and mean
    accepted run from them."""
    from gpt_2_distributed_tpu.obs.trace import get_tracer
    from scripts.obs_report import (
        build_report,
        load_trace_dir,
        speculation_summary,
    )

    get_tracer().configure(str(tmp_path))
    try:
        eng = _spec_engine(tiny_params, tiny_config,
                           _serve(spec="draft:124M,k:2"), draft,
                           temperature=0.0)
        hs = [eng.submit(p, 8, rng=i) for i, p in enumerate(prompts)]
        eng.run_until_idle(max_steps=500)
    finally:
        get_tracer().configure(None, enabled=False)
    assert all(h.done for h in hs)

    records = load_trace_dir(str(tmp_path))
    spans = {r["name"] for r in records if r.get("ph") == "span"}
    assert "draft" in spans and "verify" in spans
    evs = [r for r in records
           if r.get("ph") == "event" and r["name"] == "spec_accept"]
    assert evs, "no spec_accept events in the trace"
    for ev in evs:
        assert ev["attrs"]["drafted"] == 2
        assert 0 <= ev["attrs"]["accepted"] <= 2

    sp = speculation_summary(records)
    assert sp is not None
    assert sp["n_rounds"] == len(evs)
    assert sp["draft_tokens"] == 2 * len(evs)
    assert 0.0 <= sp["acceptance_rate"] <= 1.0
    assert sp["tokens_per_verify"] == pytest.approx(
        1.0 + sp["acceptance_rate"] * 2, abs=1.0
    )
    assert build_report(str(tmp_path))["speculation"] == sp

    # the engine's own counters agree with the trace-derived summary
    assert eng.stats["spec_draft_tokens"] == sp["draft_tokens"]
    assert eng.stats["spec_accepted_tokens"] == sp["accepted_tokens"]


def test_metrics_snapshot_carries_spec_keys(tiny_params, tiny_config,
                                            draft, prompts):
    eng = _spec_engine(tiny_params, tiny_config,
                       _serve(spec="draft:124M,k:2"), draft,
                       temperature=0.0)
    for i, p in enumerate(prompts[:2]):
        eng.submit(p, 4, rng=i)
    eng.run_until_idle(max_steps=200)
    snap = eng.metrics_snapshot()
    for key in ("spec_draft_tokens", "spec_accepted_tokens",
                "spec_rollbacks", "draft_ms", "verify_ms"):
        assert key in snap, key
    assert snap["spec_draft_tokens"] > 0
    assert snap["draft_ms"] > 0 and snap["verify_ms"] > 0


# ------------------------------------------- jax-free CLI refusals


@pytest.mark.parametrize("cli", ["serve", "frontend"])
@pytest.mark.parametrize("flags, named", [
    (("--draft_preset", "124M", "--spec_k", "0"), "--spec_k"),
    (("--spec_k", "2"), "--draft_preset"),    # speculation is opt-in
    (("--draft_preset", "bogus"), "--draft_preset"),
    # draft must be strictly smaller than the (default 124M) target
    (("--draft_preset", "124M"), "--draft_preset"),
    (("--draft_ckpt", "ckpt"), "--draft_preset"),   # rides on the preset
], ids=" ".join)
def test_spec_flags_rejected_jax_free(run_cli_jax_free, cli, flags, named):
    """serve.py and frontend/server.py refuse bad speculation flags at
    parse time with jax poisoned on PYTHONPATH: the draft-flag family is
    validated by config.validate_worker_flags, which imports no jax."""
    r = run_cli_jax_free(cli, *flags)
    assert r.returncode != 0
    assert named in r.stderr, r.stderr[-300:]
