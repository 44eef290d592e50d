"""The chip's compiler, without the chip: every Pallas kernel of the main path
at real widths, and the whole 124M train step, compiled ahead of time for a
DESCRIBED TPU v5e (``topologies.get_topology_desc``) with ``interpret=False``.

Interpret mode accepts kernels Mosaic refuses (a slice off the tiling, too
much VMEM), so the CPU suite alone cannot say a kernel will lower. These
compiles can, for shapes only: nothing runs, and a pass here is never
reported as a chip run. Each case asserts its ``tpu_custom_call`` is really
in the compiled text — a compile that quietly took an unfused or dense path
proves nothing. Forward and gradient are compiled separately on purpose:
XLA drops a forward kernel whose output the gradient does not need, so a
gradient-only compile can hold no forward kernel at all.

Skipped where the topology cannot be described (no libtpu). The file sorts
first so it always lands inside the tier-1 clock.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from gpt_2_distributed_tpu.config import MODEL_PRESETS
from gpt_2_distributed_tpu.ops.flash_attention import flash_attention
from gpt_2_distributed_tpu.ops.flash_block import flash_block
from gpt_2_distributed_tpu.ops.fused_layer import (
    fused_bias_gelu_dropout,
    fused_ln_residual_dropout,
)
from gpt_2_distributed_tpu.ops.fused_matmul import (
    matmul_bias_gelu_dropout,
    matmul_bias_residual_dropout,
)
from gpt_2_distributed_tpu.ops.paged_attention import paged_attention_pallas

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32
HBM_BYTES = 16 * 1024**3   # one v5e chip
RATE = 0.1                 # the presets' dropout rate
C = MODEL_PRESETS["124M"].n_embd          # 768
ROWS = (4, 1024)                          # batch 4 x seq 1024 activations


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no libtpu / unknown topology
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    # An AOT compile for an unattached chip can be written to the persistent
    # cache but never read back; keep the cache off around these compiles.
    # And compile at the precision the program runs at: conftest pins
    # "highest" for CPU parity tests, which reaches the dots INSIDE the
    # kernels, and Mosaic refuses an fp32-precision matmul on bf16 operands.
    cache_was = jax.config.jax_enable_compilation_cache
    precision_was = jax.config.jax_default_matmul_precision
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_default_matmul_precision", None)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", cache_was)
    jax.config.update("jax_default_matmul_precision", precision_was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _kernels(chip, fn, *shapes) -> int:
    """Mosaic kernels in ``fn`` compiled for the chip, from ``(dims, dtype)``
    argument shapes."""
    args = [
        jax.ShapeDtypeStruct(s[0], s[1], sharding=chip) for s in shapes
    ]
    text = jax.jit(fn).lower(*args).compile().as_text()
    return text.count("tpu_custom_call")


KEY = ((2,), jnp.uint32)   # a raw threefry key, as the model passes it


def _summed(fn):
    """Scalar-valued ``fn`` for jax.grad: sum every output in fp32."""
    def f(*a):
        out = fn(*a)
        return sum(
            jnp.sum(o.astype(F32)) for o in jax.tree_util.tree_leaves(out)
        )
    return f


# --- flash attention ---------------------------------------------------------

FLASH_SHAPES = {
    "124M@1024": (4, 12, 1024, 64),
    "124M@4096": (2, 12, 4096, 64),
}


@pytest.mark.parametrize("shape", FLASH_SHAPES.values(), ids=FLASH_SHAPES)
def test_flash_forward(chip, shape):
    qkv = (shape, BF16)
    n = _kernels(
        chip, lambda q, k, v: flash_attention(q, k, v, interpret=False),
        qkv, qkv, qkv,
    )
    assert n >= 1


@pytest.mark.parametrize("shape", FLASH_SHAPES.values(), ids=FLASH_SHAPES)
def test_flash_forward_backward_dropout(chip, shape):
    qkv = (shape, BF16)

    def loss(q, k, v, key):
        return jnp.sum(flash_attention(
            q, k, v, dropout_rate=RATE, rng=key, deterministic=False,
            interpret=False,
        ).astype(F32))

    n = _kernels(chip, jax.value_and_grad(loss, (0, 1, 2)), qkv, qkv, qkv, KEY)
    assert n == 2   # the forward kernel and the one backward kernel


def test_flash_block_forward_backward(chip):
    qkv = ((4, 12, 512, 64), BF16)
    scalar = ((), I32)

    def fwd(q, k, v, row, col):
        return flash_block(q, k, v, row, col, interpret=False)

    assert _kernels(chip, fwd, qkv, qkv, qkv, scalar, scalar) >= 1
    grad = jax.grad(_summed(fwd), (0, 1, 2))
    assert _kernels(chip, grad, qkv, qkv, qkv, scalar, scalar) >= 2


# --- paged decode attention --------------------------------------------------


@pytest.mark.parametrize("heads", [12, 25], ids=["124M-H12", "1.5B-H25"])
def test_paged_decode(chip, heads):
    batch, bs, d = 8, 16, 64
    m = 1024 // bs                       # full-context block table
    pool = ((1 + batch * m, heads, bs, d), BF16)
    n = _kernels(
        chip,
        lambda q, kp, vp, bt, ln: paged_attention_pallas(
            q, kp, vp, bt, ln, interpret=False
        ),
        ((batch, heads, d), BF16), pool, pool,
        ((batch, m), I32), ((batch,), I32),
    )
    assert n == 1


# --- fused epilogues and fused matmuls, C=768 --------------------------------

X = ((*ROWS, C), BF16)          # residual-stream activations
H4 = ((*ROWS, 4 * C), BF16)     # MLP hidden activations
VEC = ((C,), BF16)
VEC4 = ((4 * C,), BF16)
DROP = dict(rate=RATE, deterministic=False, interpret=False)

FUSED = {
    "fused_ln_residual_dropout": (
        lambda x, o, s, b, key: fused_ln_residual_dropout(
            x, o, s, b, rng=key, **DROP),
        (X, X, VEC, VEC, KEY), (0, 1, 2, 3),
    ),
    "fused_bias_gelu_dropout": (
        lambda h, b, key: fused_bias_gelu_dropout(h, b, rng=key, **DROP),
        (H4, VEC4, KEY), (0, 1),
    ),
    "matmul_bias_gelu_dropout": (
        lambda x, w, b, key: matmul_bias_gelu_dropout(
            x, w, b, rng=key, **DROP),
        (X, ((C, 4 * C), BF16), VEC4, KEY), (0, 1, 2),
    ),
    "matmul_bias_residual_dropout": (
        lambda x, w, b, r, key: matmul_bias_residual_dropout(
            x, w, b, r, rng=key, **DROP),
        (H4, ((4 * C, C), BF16), VEC, X, KEY), (0, 1, 2, 3),
    ),
}


@pytest.mark.parametrize("name", FUSED)
def test_fused_forward(chip, name):
    fn, shapes, _ = FUSED[name]
    assert _kernels(chip, fn, *shapes) >= 1


@pytest.mark.parametrize("name", FUSED)
def test_fused_backward(chip, name):
    fn, shapes, wrt = FUSED[name]
    assert _kernels(chip, jax.grad(_summed(fn), wrt), *shapes) >= 1


# --- the whole train step ----------------------------------------------------


def test_train_step_124m(chip, topo, monkeypatch):
    """``make_train_step`` as the trainer builds it for ``--model 124M``
    (batch 4, seq 1024, dropout on, guarded, attention left to choose),
    compiled for one chip. The attention policy asks ``jax.devices()`` for
    the platform at trace time and would see this sandbox's CPU, so the
    probe is steered here, in the test: it must then pick the flash kernel
    by itself, and the step must fit the chip's memory."""
    from gpt_2_distributed_tpu.models import gpt2
    from gpt_2_distributed_tpu.parallel.train_step import (
        make_optimizer,
        make_train_step,
    )
    from gpt_2_distributed_tpu.resilience import init_guard_state

    monkeypatch.setattr(jax, "devices", lambda *a, **k: list(topo.devices))
    accum, batch, seq = 2, 4, 1024
    config = MODEL_PRESETS["124M"].replace(n_positions=seq, scan_layers=True)
    optimizer = make_optimizer(3e-3)
    step = make_train_step(config, optimizer, guard=True)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
            tree,
        )

    params = jax.eval_shape(lambda: gpt2.init_params(config))
    opt_state = jax.eval_shape(optimizer.init, params)
    tokens = jax.ShapeDtypeStruct((accum, batch, seq), I32, sharding=chip)
    compiled = step.lower(
        on_chip(params), on_chip(opt_state),
        on_chip(jax.eval_shape(init_guard_state)),
        tokens, tokens, jax.ShapeDtypeStruct(*KEY, sharding=chip), 0,
        jax.ShapeDtypeStruct((accum,), F32, sharding=chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    # Donated state aliases its outputs; what is not aliased is extra.
    need = (
        mem.argument_size_in_bytes + mem.temp_size_in_bytes
        + mem.output_size_in_bytes - mem.alias_size_in_bytes
    )
    assert need < HBM_BYTES, f"124M step needs {need / 2**30:.2f} GiB"
