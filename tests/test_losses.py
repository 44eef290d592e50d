"""Blocked cross-entropy parity vs. the dense path: values and gradients must
match the reference CE semantics exactly (fp32 log-softmax, token-mean,
ignore_index=-100 — ``/root/reference/model.py:353-359``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gpt_2_distributed_tpu.models.gpt2 import cross_entropy
from gpt_2_distributed_tpu.ops.losses import IGNORE_INDEX, blocked_cross_entropy


def dense_ce(x, wte, labels):
    logits = jnp.einsum("nc,vc->nv", x, wte, preferred_element_type=jnp.float32)
    return cross_entropy(logits[None], labels[None])


def make_data(n=100, c=32, v=257, seed=0, masked=0):
    r = np.random.default_rng(seed)
    x = jnp.asarray(r.normal(size=(n, c)), jnp.float32)
    wte = jnp.asarray(r.normal(size=(v, c)) * 0.02, jnp.float32)
    labels = r.integers(0, v, n)
    if masked:
        labels[:masked] = IGNORE_INDEX
    return x, wte, jnp.asarray(labels, jnp.int32)


@pytest.mark.parametrize("masked", [0, 17])
@pytest.mark.parametrize("block_rows", [32, 64, 128])
def test_value_matches_dense(masked, block_rows):
    # n=100 is deliberately NOT a multiple of block_rows: exercises padding.
    x, wte, labels = make_data(masked=masked)
    a = blocked_cross_entropy(x, wte, labels, block_rows)
    b = dense_ce(x, wte, labels)
    np.testing.assert_allclose(float(a), float(b), rtol=1e-6)


def test_grads_match_dense():
    x, wte, labels = make_data(masked=9)
    ga = jax.grad(
        lambda x, w: blocked_cross_entropy(x, w, labels, 32), argnums=(0, 1)
    )(x, wte)
    gb = jax.grad(lambda x, w: dense_ce(x, w, labels), argnums=(0, 1))(x, wte)
    for a, b in zip(ga, gb):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-6,
        )


def test_all_masked_rows_safe():
    x, wte, labels = make_data(n=64)
    labels = jnp.full_like(labels, IGNORE_INDEX)
    loss = blocked_cross_entropy(x, wte, labels, 32)
    assert float(loss) == 0.0
    g = jax.grad(lambda x: blocked_cross_entropy(x, wte, labels, 32))(x)
    assert bool(jnp.isfinite(g).all())


def test_bf16_inputs_fp32_loss():
    x, wte, labels = make_data()
    a = blocked_cross_entropy(x.astype(jnp.bfloat16), wte.astype(jnp.bfloat16),
                              labels, 64)
    b = dense_ce(x.astype(jnp.bfloat16), wte.astype(jnp.bfloat16), labels)
    assert a.dtype == jnp.float32
    np.testing.assert_allclose(float(a), float(b), rtol=2e-3)


def test_bf16_logit_rounding_matches_autocast_semantics():
    """For bf16 inputs the blocked CE rounds chunk logits to bf16 exactly
    once before the fp32 log-softmax — torch autocast's dtype sequence
    (bf16 lm_head output, F.cross_entropy upcasts internally). Against a
    dense reference with the same single rounding, agreement must be far
    tighter than vs the unrounded dense path (test above): only the blocked
    LSE accumulation order differs."""
    x, wte, labels = make_data(masked=7)
    xb, wb = x.astype(jnp.bfloat16), wte.astype(jnp.bfloat16)

    logits = jnp.einsum("nc,vc->nv", xb, wb).astype(jnp.float32)  # one bf16 rounding
    a = blocked_cross_entropy(xb, wb, labels, 64)
    b = cross_entropy(logits[None], labels[None])
    # Chunked vs dense contraction shapes may order the fp32 accumulation
    # differently -> occasional 1-ulp bf16 output differences feeding the
    # LSE; 2e-5 absorbs that while staying ~100x tighter than the
    # vs-unrounded-dense bound above (rtol 2e-3).
    np.testing.assert_allclose(float(a), float(b), rtol=2e-5)

    # Gradients flow through the same rounded logits and the input-dtype
    # backward matmuls; check dx against the dense autograd at bf16-level
    # tolerance (the dense path's dx accumulates in bf16 epsilon too).
    ga = jax.grad(lambda x: blocked_cross_entropy(x, wb, labels, 64))(xb)
    gb = jax.grad(
        lambda x: cross_entropy(
            jnp.einsum("nc,vc->nv", x, wb).astype(jnp.float32)[None],
            labels[None],
        )
    )(xb)
    np.testing.assert_allclose(
        np.asarray(ga, np.float32), np.asarray(gb, np.float32),
        atol=1e-7, rtol=2e-2,
    )


def test_forward_training_path_matches_logits_path(tiny_config, rng_np):
    """gpt2.forward's blocked-CE training path == its dense logits path."""
    from gpt_2_distributed_tpu.models import gpt2

    params = gpt2.init_params(tiny_config)
    x = jnp.asarray(
        rng_np.integers(0, tiny_config.vocab_size, (2, 32)), jnp.int32
    )
    y = jnp.asarray(
        rng_np.integers(0, tiny_config.vocab_size, (2, 32)), jnp.int32
    )
    none_logits, loss_blocked = gpt2.forward(
        params, tiny_config, x, labels=y, compute_dtype=jnp.float32
    )
    logits, loss_dense = gpt2.forward(
        params, tiny_config, x, labels=y, compute_dtype=jnp.float32,
        return_logits=True,
    )
    assert none_logits is None and logits is not None
    np.testing.assert_allclose(float(loss_blocked), float(loss_dense), rtol=1e-6)


def test_loss_impl_dense_config_path(tiny_config, rng_np):
    """config.loss_impl='dense' trains on full logits with DCE'd outputs:
    same loss as the blocked path, logits still not returned."""
    from gpt_2_distributed_tpu.models import gpt2

    params = gpt2.init_params(tiny_config)
    x = jnp.asarray(
        rng_np.integers(0, tiny_config.vocab_size, (2, 32)), jnp.int32
    )
    y = jnp.asarray(
        rng_np.integers(0, tiny_config.vocab_size, (2, 32)), jnp.int32
    )
    logits_d, loss_dense = gpt2.forward(
        params, tiny_config.replace(loss_impl="dense"), x, labels=y,
        compute_dtype=jnp.float32,
    )
    _, loss_blocked = gpt2.forward(
        params, tiny_config, x, labels=y, compute_dtype=jnp.float32
    )
    assert logits_d is None  # training path must not emit [B,T,V] outputs
    np.testing.assert_allclose(float(loss_dense), float(loss_blocked), rtol=1e-6)


def test_config_loss_block_rows_threads_through(tiny_config, rng_np, monkeypatch):
    """config.loss_block_rows REACHES the blocked CE op (loss values are
    chunking-invariant by design, so equality can't prove threading — capture
    the argument instead), losses stay correct, and the value is validated."""
    from gpt_2_distributed_tpu.config import GPT2Config
    from gpt_2_distributed_tpu.models import gpt2

    params = gpt2.init_params(tiny_config)
    x = jnp.asarray(rng_np.integers(0, tiny_config.vocab_size, (2, 33)), jnp.int32)
    y = jnp.asarray(rng_np.integers(0, tiny_config.vocab_size, (2, 33)), jnp.int32)

    seen = []
    real = gpt2.blocked_cross_entropy

    def spy(xf, wte, labels, block_rows=None):
        seen.append(block_rows)
        return real(xf, wte, labels, block_rows)

    monkeypatch.setattr(gpt2, "blocked_cross_entropy", spy)
    losses = [
        float(gpt2.forward(
            params, tiny_config.replace(loss_block_rows=br), x, labels=y,
            compute_dtype=jnp.float32,
        )[1])
        for br in (7, 32, 1024)
    ]
    assert seen == [7, 32, 1024]  # the config value reached the op
    np.testing.assert_allclose(losses[0], losses[1], rtol=1e-6)
    np.testing.assert_allclose(losses[0], losses[2], rtol=1e-6)

    with pytest.raises(ValueError, match="loss_block_rows"):
        GPT2Config(loss_block_rows=0)


def test_config_validates_impl_choices():
    import pytest

    from gpt_2_distributed_tpu.config import GPT2Config

    with pytest.raises(ValueError, match="loss_impl"):
        GPT2Config(loss_impl="Blocked")
    with pytest.raises(ValueError, match="attention_impl"):
        GPT2Config(attention_impl="flashy")
    with pytest.raises(ValueError, match="remat"):
        GPT2Config(remat="attention")
