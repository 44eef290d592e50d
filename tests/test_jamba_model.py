"""Jamba at the CPU tests' size: the package's model and ops against the
benchmark's plain reference (``benchmark/reference/jamba.py``, which imports
nothing of the package). Float32, seeded random weights; layers ``M M * M``,
four query heads over one KV head, a state of 16 x 128 a Mamba layer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from gpt_2_distributed_tpu.config import JAMBA_PRESETS, JambaConfig
from gpt_2_distributed_tpu.models import jamba
from gpt_2_distributed_tpu.ops import selective_scan

ref = harness.load_module("reference", "jamba")
CONFIG = JAMBA_PRESETS["jamba-tiny"]


def config_file(config: JambaConfig = CONFIG) -> dict:
    """The configuration as a benchmark file states it."""
    out = {k: getattr(config, k) for k in ref.INT_KEYS}
    out.update(rms_norm_eps=config.rms_norm_eps, num_experts=config.num_experts,
               tie_word_embeddings=config.tie_word_embeddings)
    out["assumed"] = {"head_dim": config.head_dim,
                      "initializer_range": config.initializer_range,
                      "time_step_range": [config.time_step_min, config.time_step_max]}
    return out


SIZES = ref.sizes_of(config_file())


def raised(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


@pytest.fixture(scope="module")
def weights():
    return ref.make_weights(SIZES, 5)


def test_init_params_is_the_references_make_weights_bit_for_bit(weights):
    params = jamba.init_params(CONFIG, jax.random.PRNGKey(5))
    assert jax.tree_util.tree_structure(params) == jax.tree_util.tree_structure(weights)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(weights),
                            jax.tree_util.tree_leaves(params)):
        assert a.dtype == b.dtype and bool((a == b).all()), jax.tree_util.keystr(path)
    assert params["embed"].dtype == jnp.bfloat16 and params["norm_f"].dtype == jnp.float32
    assert "lm_head" not in params                               # the head is the embedding
    mamba, attention = params["layers"][0], params["layers"][2]
    assert mamba["in_proj"].shape == (64, 2 * 128) and mamba["x_proj"].shape == (128, 8 + 32)
    assert mamba["A_log"].shape == (128, 16) and mamba["A_log"].dtype == jnp.float32
    assert attention["wk"].shape == (64, 16) and attention["wq"].shape == (64, 64)
    n = sum(a.size for a in jax.tree_util.tree_leaves(params))
    assert n == CONFIG.num_params() == ref.num_params(SIZES)


def test_published_preset_counts_what_the_issue_reckoned():
    full = JAMBA_PRESETS["jamba2-3b"]
    assert full.layer_kinds == "MMMMMMM*MMMMMMMMMMMMM*MMMMMM"
    assert full.layers_of("*") == (7, 21) and len(full.layers_of("M")) == 26
    assert round(full.num_params() / 1e6) == 3029
    assert round(full.layer_params("M") / 1e6, 2) == 104.16
    assert round(full.layer_params("*") / 1e6, 2) == 76.68
    assert (full.d_inner, full.mamba_d_state, full.mamba_dt_rank) == (5120, 16, 160)
    view = full.kv_pool_view
    assert (view.n_layer, view.n_head, view.head_dim) == (2, 1, 128)
    # a cut keeps each layer the kind its published index gives it
    assert full.cut(14, 7).layer_kinds == "*MMMMMMMMMMMMM"
    assert full.cut(16, 7).cut(2, 13).layer_kinds == "M*"
    with pytest.raises(ValueError, match="not within"):
        full.cut(14, 20)


def test_dense_forward_equals_the_references_logits(weights):
    """Float32 round-off over four layers (5e-7 of logits that spread by
    0.17): the tolerance is 4e-6. The control's precision, a bfloat16 state
    and a mixer without its inner norms each miss it by orders."""
    ids = np.random.default_rng(0).integers(0, CONFIG.vocab_size, (2, 40))
    params = raised(weights)
    got = np.asarray(jax.jit(lambda p, i: jamba.forward(p, CONFIG, i))(params, ids))
    want = ref.logits_with(weights, SIZES, ids)
    assert want.shape == (2, 40, CONFIG.vocab_size) and want.std() > 0.01
    np.testing.assert_allclose(got, want, atol=4e-6)
    rough = ref.logits_with(weights, SIZES, ids[:1], ref.control_matmul)
    assert np.abs(rough - want[:1]).max() > 100 * np.abs(got - want).max()
    for changed in (dict(state_dtype=jnp.bfloat16), dict(inner_norms=False)):
        off = np.asarray(jax.jit(lambda p, i: jamba.forward(p, CONFIG, i, **changed))(
            params, ids))
        assert np.abs(off - want).max() > 1e-4, changed
        with pytest.raises(AssertionError):
            np.testing.assert_allclose(off, want, atol=4e-6)


def _scan_inputs(t, d, n, seed=1):
    rng = np.random.default_rng(seed)
    u = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    b, c = (jnp.asarray(rng.normal(size=(t, n)), jnp.float32) for _ in range(2))
    dt = jnp.asarray(rng.uniform(0.01, 0.5, size=(t, d)), jnp.float32)
    a = -jnp.asarray(rng.uniform(1.0, 16.0, size=(d, n)), jnp.float32)     # [D, N] as published
    return u, dt, a, b, c


@pytest.mark.parametrize("sub", [None, True], ids=["xla_scan", "kernel_interpreted"])
def test_chunked_scan_equals_step_equals_the_token_recurrence(sub):
    """Off the chip ``chunked`` is a ``lax.scan`` over the tokens; the Pallas
    kernel, interpreted, gives the same. Both compute the recurrence's own
    products in its own order, in float32: they differ from the reference by
    the last bit of a sum over 16 (2e-6 of values up to ~3)."""
    t, d, n = 48, 24, 16
    u, dt, a, b, c = _scan_inputs(t, d, n)
    want = ref.selective_scan(u, dt, a, b, c)
    zero = jnp.zeros((n, d), jnp.float32)
    got, state = selective_scan.chunked(u, dt, a.T, b, c, zero, sub)
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)
    # chunks of unequal length with the state between them, the second padded
    cut, real = 32, 11
    first, mid = selective_scan.chunked(u[:cut], dt[:cut], a.T, b[:cut], c[:cut], zero, sub)
    tail = lambda v: v[cut:cut + 16]
    live = (jnp.arange(16) < real)[:, None]
    second, end = selective_scan.chunked(
        tail(u), jnp.where(live, tail(dt), 0.0), a.T, tail(b), tail(c), mid, sub)
    np.testing.assert_allclose(first, want[:cut], rtol=2e-6, atol=2e-6)
    np.testing.assert_allclose(second[:real], want[cut:cut + real], rtol=2e-6, atol=2e-6)
    # the padded tail left the state where the last real token did: whatever
    # the padding holds changes no bit of it, and it is the real tokens' state
    junk = lambda v: jnp.where(live if v.shape[1] == d else live[:, :1], tail(v), 7.0)
    _, again = selective_scan.chunked(
        junk(u), jnp.where(live, tail(dt), 0.0), a.T, junk(b), junk(c), mid, sub)
    np.testing.assert_array_equal(end, again)
    _, exact = selective_scan.chunked(
        u[cut:cut + real], dt[cut:cut + real], a.T, b[cut:cut + real], c[cut:cut + real],
        mid)                                   # 11 tokens: no whole group, the XLA form
    np.testing.assert_allclose(end, exact, rtol=2e-6, atol=1e-7)
    # ... and decoding on from there, one token a step, an idle row untouched
    states = jnp.stack([end, end])
    two = lambda v, i: v[i][None].repeat(2, 0)
    for i in range(cut + real, t):
        step_dt = two(dt, i) * jnp.array([1.0, 0.0])[:, None]
        y, states = selective_scan.step(two(u, i), step_dt, a.T, two(b, i), two(c, i), states)
        np.testing.assert_allclose(y[0], want[i], rtol=2e-6, atol=2e-6)
    np.testing.assert_array_equal(states[1], end)               # bit for bit
    assert float(jnp.abs(state).max()) > 0


def test_a_bfloat16_state_fails_the_scans_comparison():
    u, dt, a, b, c = _scan_inputs(48, 24, 16)
    want = ref.selective_scan(u, dt, a, b, c)
    rough, _ = selective_scan.chunked(u, dt, a.T, b, c, jnp.zeros((16, 24), jnp.bfloat16))
    assert np.abs(np.asarray(rough, np.float32) - want).max() > 1e-3


def test_the_state_fills_and_decays_under_the_assumed_initialisation(weights):
    """With N(0, 0.02) alone the convolution's output, so ``u``, would sit at a
    fiftieth and the state stay empty; with ``A_log = log(1..16)`` and the
    steps at 0.001-0.1 the slowest index keeps a token for hundreds of steps
    and the fastest forgets it within a few."""
    lp = raised(weights["layers"][0])
    t = 64
    x = jnp.asarray(np.random.default_rng(3).normal(size=(t, 64)), jnp.float32)
    from gpt_2_distributed_tpu.ops import ssd

    u_raw, _ = jamba.mamba_in(CONFIG, lp, x)
    zeros = jnp.zeros((3, CONFIG.d_inner), jnp.float32)
    conv, _ = ssd.conv_chunk(u_raw, zeros, lp["conv_w"], lp["conv_b"], t)
    live = jnp.arange(t) < 32                                    # then 32 tokens of padding
    u, b, c, dt, a = jamba.scan_inputs(CONFIG, lp, conv, live)
    assert a.shape == (16, 128) and float(a.max()) == -1.0 and float(a.min()) == -16.0
    assert 0.0005 < float(dt[:32].min()) and float(dt[:32].max()) < 0.3
    assert float(jnp.abs(dt[32:]).max()) == 0.0
    np.testing.assert_allclose(jnp.sqrt(jnp.mean(b * b, -1)), 1.0, rtol=1e-3)   # the inner norm
    _, filled = selective_scan.chunked(u, dt, a, b, c, jnp.zeros_like(a))
    assert float(jnp.abs(filled).mean()) > 1e-3                  # it fills
    # run on with no input: index 16 decays faster than index 1
    quiet = jnp.zeros_like(u[:32])
    _, later = selective_scan.chunked(
        quiet, jnp.full_like(quiet, 0.05), a, b[:32], c[:32], filled)
    kept = jnp.abs(later).sum(-1) / jnp.abs(filled).sum(-1)
    assert 0.15 < float(kept[0]) < 0.25 and float(kept[-1]) < 1e-9   # exp(-1.6), exp(-25.6)


def test_the_serving_reference_is_the_plain_forward_at_every_position(weights):
    """``serving_reference`` makes each layer's weights from the seed as the
    walk reaches it and gives the logits ``logits_with`` gives over the whole
    tree - no row of them changed; the control's forward is another."""
    ids = np.random.default_rng(7).integers(0, CONFIG.vocab_size, (1, 64))
    plain = ref.logits_with(weights, SIZES, ids)
    logits = ref.serving_reference(SIZES, 5)
    np.testing.assert_array_equal(logits(ids), plain)
    assert (plain[0].std(axis=-1) > 0.05).all()
    rough = logits(ids, ref.control_matmul)
    assert np.abs(rough - plain).max() > 3e-3


def test_schema_refuses_what_it_cannot_mean():
    with pytest.raises(ValueError, match="num_experts=2"):
        CONFIG.replace(num_experts=2)
    with pytest.raises(ValueError, match="attn_layer_offset"):
        CONFIG.replace(attn_layer_offset=4)
    with pytest.raises(ValueError, match="num_key_value_heads"):
        CONFIG.replace(num_key_value_heads=3)
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        CONFIG.replace(tie_word_embeddings=False)
