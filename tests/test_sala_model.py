"""MiniCPM-SALA at the CPU tests' size: the package's model and ops against
the benchmark's plain reference (``benchmark/reference/minicpm_sala.py``,
which imports nothing of the package). Float32, seeded random weights; the
sparse sizes are small enough that selection is live within 100 tokens."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from gpt_2_distributed_tpu.config import (
    SALA_PRESETS,
    SalaConfig,
    SparseAttentionConfig,
)
from gpt_2_distributed_tpu.models import minicpm_sala as sala
from gpt_2_distributed_tpu.ops import linear_attention, sparse_select

ref = harness.load_module("reference", "minicpm_sala")
CONFIG = SALA_PRESETS["minicpm-sala-tiny"]


def config_file(config: SalaConfig = CONFIG) -> dict:
    """The configuration as a benchmark file states it."""
    keys = ("vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "lightning_nh", "lightning_head_dim", "max_position_embeddings",
            "rms_norm_eps", "rope_theta", "scale_emb", "scale_depth", "dim_model_base")
    out = {k: getattr(config, k) for k in keys}
    out["published_num_hidden_layers"] = config.num_hidden_layers
    out["num_hidden_layers"] = len(config.mixer_types)
    out["lightning_nkv"] = config.lightning_nh
    out["mixer_types"] = list(config.mixer_types)
    out["assumed"] = {"sparse": dataclasses.asdict(config.sparse),
                      "initializer_range": config.initializer_range}
    return out


SIZES = ref.sizes_of(config_file())


@pytest.fixture(scope="module")
def weights():
    return ref.make_weights(SIZES, 5)


def test_init_params_is_the_references_make_weights_bit_for_bit(weights):
    params = sala.init_params(CONFIG, jax.random.PRNGKey(5))
    assert jax.tree_util.tree_structure(params) == jax.tree_util.tree_structure(weights)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(weights),
                            jax.tree_util.tree_leaves(params)):
        assert a.dtype == b.dtype and bool((a == b).all()), jax.tree_util.keystr(path)
    assert params["embed"].dtype == jnp.bfloat16 and params["norm_f"].dtype == jnp.float32
    n = sum(a.size for a in jax.tree_util.tree_leaves(params))
    assert n == CONFIG.num_params()


def test_published_preset_counts_what_the_issue_reckoned():
    full = SALA_PRESETS["minicpm-sala-9b"]
    assert len(full.mixer_types) == 32 and len(full.sparse_layers) == 8
    cut = full.cut(16, 9)
    kinds = "".join("m" if m == "minicpm4" else "l" for m in cut.mixer_types)
    assert kinds == "mllllllmmllllmll" and cut.num_hidden_layers == 32
    assert round(cut.num_params() * 2 / 1e9, 2) == 10.08       # bfloat16 bytes
    assert cut.sparse.list_width == 128 and cut.sparse.local_blocks == 32
    with pytest.raises(ValueError, match="not within"):
        full.cut(16, 20)


def test_dense_forward_equals_the_references_logits(weights):
    ids = np.random.default_rng(0).integers(0, CONFIG.vocab_size, (2, 100))
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), weights)
    got = np.asarray(jax.jit(lambda p, i: sala.forward(p, CONFIG, i))(params, ids))
    want = ref.logits_with(weights, SIZES, ids)
    assert want.shape == (2, 100, CONFIG.vocab_size) and want.std() > 0.01
    np.testing.assert_allclose(got, want, atol=2e-6)
    # the control's precision is one the logits can tell from the stated one
    rough = ref.logits_with(weights, SIZES, ids[:1], ref.control_matmul)
    assert np.abs(rough - want[:1]).max() > 100 * np.abs(got - want).max()


@pytest.mark.parametrize("sub", [4, 16])
def test_chunked_linear_attention_equals_the_token_recurrence(sub):
    t, h, d = 48, 4, 16
    rng = np.random.default_rng(1)
    q, k, v = (jnp.asarray(rng.normal(size=(t, h, d)), jnp.float32) for _ in range(3))
    slopes = linear_attention.decay_slopes(h)
    np.testing.assert_allclose(jnp.exp(-slopes), ref.lightning_decay(h), rtol=1e-6)
    want = ref.lightning_recurrence(q, k, v, ref.lightning_decay(h))
    zero = jnp.zeros((h, d, d), jnp.float32)
    got, state = linear_attention.chunked(q, k, v, jnp.ones((t,), bool), zero, slopes, sub)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    # two chunks with the state between them, the second padded past its end
    cut, real = 32, 11
    first, mid = linear_attention.chunked(
        q[:cut], k[:cut], v[:cut], jnp.ones((cut,), bool), zero, slopes, sub)
    pad = lambda x: jnp.pad(x[cut:cut + 16], ((0, 0), (0, 0), (0, 0)))
    second, end = linear_attention.chunked(
        pad(q), pad(k), pad(v), jnp.arange(16) < real, mid, slopes, sub)
    np.testing.assert_allclose(first, want[:cut], rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(second[:real], want[cut:cut + real], rtol=2e-5, atol=2e-5)
    # ... and decoding on from there, one token a step, an idle row untouched
    states = jnp.stack([end, end])
    for i in range(cut + real, t):
        o, states = linear_attention.step(
            q[i][None].repeat(2, 0), k[i][None].repeat(2, 0), v[i][None].repeat(2, 0),
            jnp.array([True, False]), states, slopes)
        np.testing.assert_allclose(o[0], want[i], rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(states[1], end)
    assert float(jnp.abs(state).max()) > 0


def test_fast_heads_neither_overflow_nor_lose_the_state():
    """At 32 heads the first forgets within a few tokens: exp(-0.84 * 256)
    underflows, and nothing may turn that into inf or nan."""
    t, h, d = 512, 32, 8
    rng = np.random.default_rng(2)
    q, k, v = (jnp.asarray(rng.normal(size=(t, h, d)), jnp.float32) for _ in range(3))
    got, state = linear_attention.chunked(
        q, k, v, jnp.ones((t,), bool), jnp.ones((h, d, d), jnp.float32),
        linear_attention.decay_slopes(h))
    assert bool(jnp.isfinite(got).all()) and bool(jnp.isfinite(state).all())
    want = ref.lightning_recurrence(q, k, v, ref.lightning_decay(h))   # from zero
    np.testing.assert_allclose(got[64:, 0], want[64:, 0], atol=1e-4)   # head 0 forgot the ones


@pytest.mark.parametrize("t", [100, 61])
def test_selected_sets_equal_the_references(t):
    sp = CONFIG.sparse
    as_dict = dataclasses.asdict(sp)
    kv, g, d = 2, 3, 16
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(t, kv * g, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(t, kv, d)), jnp.float32)
    n_blocks = -(-t // sp.block)
    pos = jnp.arange(t)
    want = ref.selected_set(q, ref.compressed_keys(k, as_dict), pos, n_blocks, as_dict)
    kc = sparse_select.window_means(
        jnp.pad(k, ((0, n_blocks * sp.block - t), (0, 0), (0, 0))), sp)
    score = sparse_select.block_scores(q.reshape(t, kv, g, d), kc, pos, sp)
    got = sparse_select.select_blocks(score, pos, sp)
    np.testing.assert_array_equal(got, want)
    # dense below the limit, then init + local + topk blocks, never a later one
    counts = np.asarray(got.sum(-1))
    selected, visible = sp.selected_blocks(np.arange(t))
    np.testing.assert_array_equal(counts, np.broadcast_to(selected, counts.shape))
    assert (visible[sp.dense_below:] >= selected[sp.dense_below:]).all()
    assert counts[:, -1].max() == sp.init_blocks + sp.local_blocks + sp.topk < n_blocks
    # the list form: ascending blocks, as many as the mask holds
    blocks, count = sparse_select.mask_to_list(got, sp.list_width)
    for kvh in range(kv):
        for row in (0, sp.dense_below, t - 1):
            n = int(count[kvh, row])
            assert blocks[kvh, row, :n].tolist() == np.flatnonzero(got[kvh, row]).tolist()


def test_sparse_sizes_must_nest():
    with pytest.raises(ValueError, match="must nest"):
        SparseAttentionConfig(window=32, stride=12)
    with pytest.raises(ValueError, match="mixer_types"):
        SalaConfig(mixer_types=("minicpm4", "mamba"))
