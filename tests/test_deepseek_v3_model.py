"""DeepSeek-V3's block at the CPU tests' size: the package's model and ops
against the benchmark's plain reference (``benchmark/reference/
deepseek_v3.py``, which imports nothing of the package). Float32, seeded
random weights; C 64, 4 heads (dn 16, dr 8, dv 16), latent rank 32, 8 experts
2 a token with 2 shared, in two shares of 4; layers ``DEE``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from gpt_2_distributed_tpu.config import (
    DEEPSEEK_V3_PRESETS,
    DENSE_LAYER,
    EXPERT_LAYER,
    DeepseekV3Config,
)
from gpt_2_distributed_tpu.models import deepseek_v3 as ds
from gpt_2_distributed_tpu.ops import moe
from gpt_2_distributed_tpu.ops.paged_attention import paged_mla_attention, paged_mla_prefill

ref = harness.load_module("reference", "deepseek_v3")
WHOLE = DEEPSEEK_V3_PRESETS["deepseek-v3-tiny"]      # every expert held
CONFIG = WHOLE.share((0, 4))                         # the share the tests serve
KEYS = ("vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size",
        "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
        "n_shared_experts", "routed_scaling_factor", "kv_lora_rank", "q_lora_rank",
        "qk_rope_head_dim", "qk_nope_head_dim", "v_head_dim", "n_group", "topk_group",
        "num_experts_per_tok", "first_k_dense_replace", "norm_topk_prob", "rms_norm_eps",
        "rope_theta", "rope_interleave", "max_position_embeddings",
        "tie_word_embeddings", "first_layer")


def config_file(config: DeepseekV3Config = CONFIG) -> dict:
    """The configuration as a benchmark file states it."""
    out = {k: getattr(config, k) for k in KEYS}
    out["n_routed_experts"] = config.n_held
    out["published_n_routed_experts"] = config.n_routed_experts
    out["experts_held"] = list(config.experts_held)
    out["assumed"] = {"initializer_range": config.initializer_range}
    return out


SIZES = ref.sizes_of(config_file())


def raised(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


@pytest.fixture(scope="module")
def weights():
    return ref.make_weights(SIZES, 5)


def test_init_params_is_the_references_make_weights_bit_for_bit(weights):
    params = ds.init_params(CONFIG, jax.random.PRNGKey(5))
    assert jax.tree_util.tree_structure(params) == jax.tree_util.tree_structure(weights)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(weights),
                            jax.tree_util.tree_leaves(params)):
        assert a.dtype == b.dtype and bool((a == b).all()), jax.tree_util.keystr(path)
    assert CONFIG.layer_kinds == "DEE" and len(params["dense"]) == 1
    experts = params["moe"]
    assert experts["w_gate_up"].shape == (2, 4, 64, 64)         # [layers, held, 2F, C]
    assert experts["w_down"].shape == (2, 4, 32, 64)            # [layers, held, F, C]
    assert experts["router"].shape == (2, 64, 8)                 # the router keeps its width
    assert experts["shared_gate_up"].shape == (2, 64, 2 * 2 * 32)   # two shared as one
    n = sum(a.size for a in jax.tree_util.tree_leaves(params))
    assert n == CONFIG.num_params() == ref.num_params(SIZES)
    # an expert's matrix comes of its own index, whoever holds it
    other = ds.init_params(WHOLE.share((2, 6)), jax.random.PRNGKey(5))["moe"]
    np.testing.assert_array_equal(other["w_gate_up"][:, :2], experts["w_gate_up"][:, 2:])


def test_published_preset_counts_what_the_sizing_reckoned():
    full = DEEPSEEK_V3_PRESETS["kanana2-30b-a3b"]
    assert full.layer_kinds == "D" + "E" * 47
    assert (full.qk_head_dim, full.latent_dim, full.latent_lanes) == (192, 576, 640)
    assert round(full.num_params() / 1e9, 1) == 30.7
    cut = DEEPSEEK_V3_PRESETS["kanana2-30b-a3b-l7"]
    assert cut == full.cut(7).share((0, 16)) and cut.layer_kinds == "DEEEEEE"
    assert round(cut.layer_params(DENSE_LAYER) / 1e6, 1) == 64.1
    assert round(cut.layer_params(EXPERT_LAYER) / 1e6, 1) == 111.5
    assert round((cut.num_params() - cut.num_params(False)) / 1e6, 1) == 525.3
    assert round(cut.num_params() / 1e6) == 1259
    assert round(cut.num_params() * 2 / 1e9, 2) == 2.52          # bfloat16 bytes
    view = cut.kv_pool_view
    assert (view.n_layer, view.n_head, view.head_dim, view.latent) == (7, 1, 640, True)
    with pytest.raises(ValueError, match="not within"):
        full.cut(7, 45)


@pytest.mark.parametrize("t", [40, 160])     # under and over a row tile of the grouped matmul
def test_expanded_forward_equals_the_references_logits(weights, t):
    ids = np.random.default_rng(0).integers(0, CONFIG.vocab_size, (2, t))
    got = np.asarray(jax.jit(lambda p, i: ds.forward(p, CONFIG, i))(raised(weights), ids))
    want = ref.logits_with(weights, SIZES, ids)
    assert want.shape == (2, t, CONFIG.vocab_size) and want.std() > 0.01
    # float32 round-off over three layers: 1e-7 of logits that spread by 0.16
    np.testing.assert_allclose(got, want, atol=2e-6)
    # the control's precision is one the logits can tell from the stated one
    rough = ref.logits_with(weights, SIZES, ids[:1], ref.control_matmul)
    assert np.abs(rough - want[:1]).max() > 100 * np.abs(got - want).max()


def test_the_interleaved_rope_rotates_the_pairs_by_hand():
    rng = np.random.default_rng(1)
    x = rng.normal(size=(5, 3, 8)).astype(np.float32)
    pos = np.asarray([0, 1, 7, 300, 4095])
    got = np.asarray(ds.rope(jnp.asarray(x), jnp.asarray(pos)[:, None], 1e6))
    want = np.empty_like(x)
    for i in range(4):
        angle = pos[:, None] * 1e6 ** (-2 * i / 8)
        a, b = x[..., 2 * i], x[..., 2 * i + 1]
        want[..., 2 * i] = a * np.cos(angle) - b * np.sin(angle)
        want[..., 2 * i + 1] = b * np.cos(angle) + a * np.sin(angle)
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_array_equal(got[0], x[0])                 # position 0 turns nothing
    # the published form moves the evens first: the same scores, pair for pair.
    # Its frequencies are 1 / theta^(2i/d), the program's theta^(-2i/d): in
    # float32 an ulp apart, which 4095 radians make 3e-5
    published = np.asarray(ref.rope(jnp.asarray(x), jnp.asarray(pos)[:, None], 1e6))
    order = np.r_[0:8:2, 1:8:2]
    np.testing.assert_allclose(published, got[..., order], atol=1e-4)


def _latent_case(seed=2):
    """One row's queries at positions 0-22 of a 24-position latent cache
    held in scrambled blocks of 8 (the rows zero-padded to 128 lanes, as the
    pool stores them), and the layer's weights."""
    rng = np.random.default_rng(seed)
    w = ds.init_params(WHOLE, jax.random.PRNGKey(seed), jnp.float32)["dense"][0]
    t, bs, lanes = 23, 8, WHOLE.latent_lanes
    h = jnp.asarray(rng.normal(size=(t, WHOLE.hidden_size)), jnp.float32)
    params = {"embed": jnp.zeros((1, 1), jnp.float32)}
    q_nope, q_pe, row = ds.mla_project(WHOLE, params, w, h, jnp.arange(t))
    table = jnp.asarray([5, 2, 7], jnp.int32)
    pool = jnp.zeros((1, 9, 1, bs, lanes), jnp.float32)
    padded = jnp.pad(row, ((0, 24 - t), (0, lanes - row.shape[-1])))
    pool = pool.at[0, table, 0].set(padded.reshape(3, bs, lanes))
    return w, q_nope, q_pe, row, pool, table


def test_absorbed_attention_equals_expanded_attention_on_one_latent_cache():
    w, q_nope, q_pe, row, pool, table = _latent_case()
    t = q_nope.shape[0]
    w_uk, w_uv = ds.up_projections(WHOLE, w)
    dense = ds.expanded_attention(WHOLE, w, q_nope, q_pe, row)     # [T, H, dv]
    walked = paged_mla_prefill(q_nope, q_pe, pool, table, jnp.arange(t), w_uk, w_uv,
                               tile_blocks=2)
    pad = WHOLE.latent_lanes - WHOLE.latent_dim
    q = jnp.pad(ds.absorbed_query(WHOLE, w, q_nope, q_pe), ((0, 0), (0, 0), (0, pad)))
    blocks = jnp.broadcast_to(table, (t, 1, 3))
    logical = jnp.broadcast_to(jnp.arange(3), (t, 1, 3))
    count = (jnp.arange(t) // 8 + 1)[:, None]
    u = paged_mla_attention(q[:, None], pool, blocks, logical, count, jnp.arange(t),
                            value_lanes=WHOLE.kv_lora_rank, scale_dim=WHOLE.qk_head_dim)
    absorbed = jnp.einsum("thr,rhv->thv", u[:, 0], w_uv)
    # float32 sums in another order: round-off of values that spread by 0.02
    np.testing.assert_allclose(np.asarray(walked), np.asarray(dense), atol=1e-6)
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(dense), atol=1e-6)
    np.testing.assert_allclose(np.asarray(ds.mla_out(WHOLE, w, u[:, 0], absorbed=True)),
                               np.asarray(ds.mla_out(WHOLE, w, dense)), atol=1e-6)


def _layer_weights(seed=3):
    w = ds.init_params(WHOLE, jax.random.PRNGKey(seed), jnp.float32)
    return w, jax.tree_util.tree_map(lambda a: a[0], w["moe"])


@pytest.mark.parametrize("interpret", [None, True], ids=["xla", "kernel"])
@pytest.mark.parametrize("live", [40, 3], ids=["full", "few"])
def test_the_gated_expert_layer_equals_a_loop_over_experts(interpret, live):
    """The SwiGLU form of ``moe.expert_layer`` (one grouped matmul for gate and
    up, then down) against a per-expert loop; ``few`` live rows take no
    spare rows, ``full`` gives every held expert one."""
    _, lp = _layer_weights()
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(40, 64)), jnp.float32)
    chosen, weights = moe.route(x, lp["router"], lp["router_bias"], 2, 2.448)
    valid = jnp.arange(40) < live
    y, sizes = moe.expert_layer(x, chosen, weights, valid, lp["w_gate_up"], lp["w_down"],
                                gated=True, interpret=interpret)
    want = np.zeros((40, 64), np.float32)
    for e in range(8):
        gate_up = np.asarray(x) @ np.asarray(lp["w_gate_up"][e]).T
        act = gate_up[:, :32] / (1 + np.exp(-gate_up[:, :32])) * gate_up[:, 32:]
        w_e = np.where(np.asarray(chosen) == e, np.asarray(weights), 0).sum(-1)
        want += (w_e * np.asarray(valid))[:, None] * (act @ np.asarray(lp["w_down"][e]))
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-5)
    counted = np.bincount(np.asarray(chosen)[:live].ravel(), minlength=8)
    np.testing.assert_array_equal(np.asarray(sizes), counted)


def test_the_two_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """The guide's tie of the share to the model: the feed-forward of an
    expert layer as the shares [0, 4) and [4, 8) compute it, the shared
    experts (which every holder computes) counted once, is the uncut
    reference's."""
    whole_sizes = ref.sizes_of(config_file(WHOLE))
    lp = ref.stacked_layer(ref.make_weights(whole_sizes, 6), whole_sizes, 1)
    h = jnp.asarray(np.random.default_rng(7).normal(size=(30, 64)), jnp.float32)
    y = ref.rms_norm(h, lp["mlp_norm"], WHOLE.rms_norm_eps)
    shared = ref.swiglu(y, lp["shared_gate_up"], lp["shared_down"])
    uncut = ref.routed_part(lp, whole_sizes, y) + shared
    total = -shared
    for lo in (0, 4):
        config = WHOLE.share((lo, lo + 4))
        mine = raised(ref.make_weights(ref.sizes_of(config_file(config)), 6))
        layer = jax.tree_util.tree_map(lambda a: a[0], mine["moe"])
        out, _ = ds.feed_forward(config, mine, layer, EXPERT_LAYER, h,
                                 jnp.ones((30,), bool))
        total = total + out
    # float32 round-off of two sums in another order
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut), atol=2e-6)
    assert np.abs(np.asarray(uncut - shared)).max() > 1e-3      # the experts add something


def test_the_serving_reference_is_the_plain_forward_at_every_position(weights):
    ids = np.random.default_rng(8).integers(0, CONFIG.vocab_size, (1, 24))
    logits = ref.serving_reference(SIZES, 5)
    np.testing.assert_array_equal(logits(ids), ref.logits_with(weights, SIZES, ids))


def test_schema_refuses_what_it_cannot_mean():
    with pytest.raises(ValueError, match="q_lora_rank"):
        DeepseekV3Config(q_lora_rank=1536)
    with pytest.raises(ValueError, match="ungrouped routing"):
        DeepseekV3Config(n_group=8, topk_group=4)
    with pytest.raises(ValueError, match="rope_interleave"):
        DeepseekV3Config(rope_interleave=False)
    with pytest.raises(ValueError, match="no range"):
        DeepseekV3Config(experts_held=(120, 136))
    with pytest.raises(ValueError, match="whole number of rotated pairs"):
        DeepseekV3Config(qk_rope_head_dim=63)
