"""Flash-attention kernel tests (CPU interpret mode).

Parity targets: the dense causal attention of ``ops/attention.py`` (itself
behavior-matched to ``/root/reference/model.py:80-159``) for values and
gradients, including the dropout path — the dense oracle reproduces the
kernel's counter-based dropout mask bit-for-bit at the JAX level, so dropout
fwd/bwd are checked exactly, not just statistically.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gpt_2_distributed_tpu.ops.attention import causal_attention
from gpt_2_distributed_tpu.ops.flash_attention import (
    _dropout_bits,
    flash_attention,
)


def make_qkv(B=2, H=3, T=256, D=64, seed=0, dtype=jnp.float32):
    r = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(r.normal(size=(B, H, T, D)), dtype)
    return mk(), mk(), mk()


def dense_oracle_with_kernel_mask(q, k, v, seed_scalar, rate):
    """Dense attention applying the kernel's exact dropout mask.

    The kernel's bits are a pure hash of absolute (batch, head, row, col), so
    one full-[T, T] call reproduces every tile the kernel generates regardless
    of its blocking."""
    B, H, T, D = q.shape
    scale = 1.0 / np.sqrt(D)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    mask = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    if rate > 0.0:
        threshold = jnp.uint32(int(rate * (2**32)))
        keep = (
            jnp.stack(
                [
                    jnp.stack(
                        [
                            _dropout_bits(seed_scalar, b, h, 0, 0, (T, T))
                            for h in range(H)
                        ]
                    )
                    for b in range(B)
                ]
            )
            >= threshold
        )
        p = jnp.where(keep, p / (1.0 - rate), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)


def test_fwd_matches_dense():
    q, k, v = make_qkv()
    o_d = causal_attention(q, k, v)
    o_f = flash_attention(q, k, v, interpret=True)
    np.testing.assert_allclose(np.asarray(o_f), np.asarray(o_d), atol=2e-5)


def test_bwd_matches_dense():
    q, k, v = make_qkv()

    def loss_d(q, k, v):
        return (causal_attention(q, k, v) ** 2).sum()

    def loss_f(q, k, v):
        return (flash_attention(q, k, v, interpret=True) ** 2).sum()

    gd = jax.grad(loss_d, argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gd, gf):
        scale = float(jnp.abs(a).max())
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), atol=2e-5 * max(scale, 1.0)
        )


def test_causality():
    """Output at position i must not depend on tokens > i."""
    q, k, v = make_qkv(B=1, H=1, T=128)
    o1 = flash_attention(q, k, v, interpret=True)
    k2 = k.at[:, :, 64:].set(99.0)
    v2 = v.at[:, :, 64:].set(99.0)
    o2 = flash_attention(q, k2, v2, interpret=True)
    np.testing.assert_allclose(
        np.asarray(o1[:, :, :64]), np.asarray(o2[:, :, :64]), atol=1e-6
    )
    assert not np.allclose(np.asarray(o1[:, :, 64:]), np.asarray(o2[:, :, 64:]))


def test_dropout_fwd_matches_dense_oracle():
    q, k, v = make_qkv(B=1, H=2, T=256)
    key = jax.random.PRNGKey(3)
    o_f = flash_attention(
        q, k, v, dropout_rate=0.1, rng=key, deterministic=False, interpret=True
    )
    # Recover the int32 seed exactly as flash_attention derives it.
    seed = jax.random.randint(key, (1,), 0, jnp.iinfo(jnp.int32).max, jnp.int32)
    o_d = dense_oracle_with_kernel_mask(q, k, v, seed[0], 0.1)
    np.testing.assert_allclose(np.asarray(o_f), np.asarray(o_d), atol=2e-5)


def test_dropout_bwd_matches_dense_oracle():
    q, k, v = make_qkv(B=1, H=2, T=256)
    key = jax.random.PRNGKey(5)
    seed = jax.random.randint(key, (1,), 0, jnp.iinfo(jnp.int32).max, jnp.int32)

    def loss_f(q, k, v):
        return (
            flash_attention(
                q, k, v, dropout_rate=0.1, rng=key, deterministic=False,
                interpret=True,
            ) ** 2
        ).sum()

    def loss_d(q, k, v):
        return (dense_oracle_with_kernel_mask(q, k, v, seed[0], 0.1) ** 2).sum()

    gf = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_d, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gd, gf):
        scale = float(jnp.abs(a).max())
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), atol=3e-5 * max(scale, 1.0)
        )


def test_multiblock_fwd_bwd_matches_dense():
    """nq=4 (T=512, block_q=128): exercises the online-softmax rescaling, the
    pl.when(j < qi) unmasked branch, dq accumulation across k-blocks, and the
    pl.ds dk/dv slice accumulation — none of which run at nq=1."""
    q, k, v = make_qkv(B=1, H=2, T=512)

    def loss_d(q, k, v):
        return (causal_attention(q, k, v) ** 2).sum()

    def loss_f(q, k, v):
        return (
            flash_attention(q, k, v, block_q=128, interpret=True) ** 2
        ).sum()

    o_d = causal_attention(q, k, v)
    o_f = flash_attention(q, k, v, block_q=128, interpret=True)
    np.testing.assert_allclose(np.asarray(o_f), np.asarray(o_d), atol=2e-5)
    gd = jax.grad(loss_d, argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gd, gf):
        scale = float(jnp.abs(a).max())
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), atol=3e-5 * max(scale, 1.0)
        )


def test_multiblock_dropout_bwd_matches_dense_oracle():
    """Dropout column offsets (j*bq != 0) must line up between the kernel's
    per-block hash tiles and the oracle's full-[T, T] mask."""
    q, k, v = make_qkv(B=1, H=1, T=256)
    key = jax.random.PRNGKey(7)
    seed = jax.random.randint(key, (1,), 0, jnp.iinfo(jnp.int32).max, jnp.int32)

    def loss_f(q, k, v):
        return (
            flash_attention(
                q, k, v, dropout_rate=0.1, rng=key, deterministic=False,
                block_q=128, interpret=True,
            ) ** 2
        ).sum()

    def loss_d(q, k, v):
        return (dense_oracle_with_kernel_mask(q, k, v, seed[0], 0.1) ** 2).sum()

    gf = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_d, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gd, gf):
        scale = float(jnp.abs(a).max())
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), atol=3e-5 * max(scale, 1.0)
        )


def test_pick_block_q():
    from gpt_2_distributed_tpu.ops.flash_attention import pick_block_q

    assert pick_block_q(1024) == 512
    assert pick_block_q(512) == 512
    assert pick_block_q(256) == 256
    assert pick_block_q(128) == 128
    assert pick_block_q(640) == 128   # not divisible by 512/256; 128 works
    assert pick_block_q(200) is None  # no 128-multiple divides it
    assert pick_block_q(64) is None   # below the minimum stripe


def test_dropout_rate_statistics():
    q, k, v = make_qkv(B=1, H=1, T=256)
    seed = jnp.int32(1234)
    bits = _dropout_bits(seed, 0, 0, 0, 0, (128, 256))
    frac = float((bits < jnp.uint32(int(0.1 * 2**32))).mean())
    assert 0.05 < frac < 0.15  # ~10% dropped


def test_bf16_inputs():
    q, k, v = make_qkv(dtype=jnp.bfloat16)
    o_f = flash_attention(q, k, v, interpret=True)
    o_d = causal_attention(q, k, v)
    assert o_f.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(o_f, np.float32), np.asarray(o_d, np.float32), atol=0.03
    )


def test_seq_not_divisible_raises():
    q, k, v = make_qkv(T=200)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, block_q=128, interpret=True)


def test_sharded_over_mesh_matches_dense():
    """Under an active multi-device mesh the entry point must wrap the Mosaic
    kernel in shard_map (GSPMD cannot auto-partition it — on a real multi-chip
    TPU the unwrapped call fails to compile) and still match dense attention.
    Runs batch sharded over the suite's 8 virtual CPU devices."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from gpt_2_distributed_tpu.parallel.mesh import MeshSpec, activate_mesh, create_mesh

    q, k, v = make_qkv(B=8, H=2, T=256, D=64, seed=3)
    mesh = create_mesh(MeshSpec(data=2, fsdp=4))
    o_dense = causal_attention(q, k, v)
    with activate_mesh(mesh):
        sharding = NamedSharding(mesh, P(("data", "fsdp"), None, None, None))
        qs, ks, vs = (jax.device_put(a, sharding) for a in (q, k, v))
        o_f = jax.jit(
            lambda a, b, c: flash_attention(a, b, c, interpret=True)
        )(qs, ks, vs)
    np.testing.assert_allclose(np.asarray(o_f), np.asarray(o_dense), atol=2e-5)


def test_sharded_dropout_streams_differ_per_shard():
    """The shard_map wrapper mixes the linear shard index into the kernel
    seed; without it every batch shard reuses identical masks (the kernel
    hashes LOCAL coordinates). Mask equality across shards is the regression
    signal."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from gpt_2_distributed_tpu.parallel.mesh import MeshSpec, activate_mesh, create_mesh

    B, H, T, D = 8, 2, 256, 64
    q = jnp.ones((B, H, T, D), jnp.float32)
    k, v = q, jnp.asarray(
        np.random.default_rng(0).normal(size=(B, H, T, D)), jnp.float32)
    mesh = create_mesh(MeshSpec(data=8, fsdp=1))
    with activate_mesh(mesh):
        sharding = NamedSharding(mesh, P("data", None, None, None))
        qs, ks, vs = (jax.device_put(a, sharding) for a in (q, k, v))
        out = jax.jit(lambda a, b, c: flash_attention(
            a, b, c, dropout_rate=0.5, rng=jax.random.PRNGKey(5),
            deterministic=False, interpret=True,
        ))(qs, ks, vs)
    out = np.asarray(out)
    # Identical q/k and shared v mean any two batch rows agree iff their
    # dropout masks agree. Rows live on different devices; they must differ.
    same = sum(
        np.allclose(out[0], out[b]) for b in range(1, B)
    )
    assert same == 0, f"{same}/7 shards reused the shard-0 dropout mask"


@pytest.mark.parametrize("impl,seq_len,line", [
    ("auto", 128, "[kernels] attention: dense (xla)"),     # flash only on a TPU
    ("dense", 128, "[kernels] attention: dense (xla)"),
    ("flash", 128, "[kernels] attention: flash (interpret)"),
])
def test_resolved_attention_impl_is_said_once(monkeypatch, capsys, impl, seq_len, line):
    """The trace-time choice (platform, shape, mesh) is printed once per
    process, so a run can be held to the implementation it was meant to take."""
    from gpt_2_distributed_tpu.ops import spmd
    from gpt_2_distributed_tpu.ops.attention import select_attention_impl

    monkeypatch.setattr(spmd, "_RESOLVED_IMPLS", set())
    q = jnp.zeros((1, seq_len, 2, 64), jnp.float32)
    for _ in range(2):
        select_attention_impl(impl, seq_len)(q, q, q)
    assert capsys.readouterr().err.splitlines() == [line]
