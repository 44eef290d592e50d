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
from gpt_2_distributed_tpu.ops.paged_attention import (
    paged_attention_pallas,
    paged_decode_grid,
)

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


PAGED_SHAPES = {
    # heads, batch, block size; a full-context block table
    "124M-H12": (12, 8, 16),
    "1.5B-H25": (25, 8, 16),
    "124M-H12-b128": (12, 128, 16),
    "1.5B-H25-bs32": (25, 8, 32),
}


@pytest.mark.parametrize("shape", PAGED_SHAPES.values(), ids=PAGED_SHAPES)
def test_paged_decode(chip, shape):
    heads, batch, bs = shape
    d = 64
    m = 1024 // bs                       # full-context block table
    pool = ((1 + batch * m, heads, bs, d), BF16)
    shapes = (((batch, heads, d), BF16), pool, pool,
              ((batch, m), I32), ((batch,), I32))

    def fn(q, kp, vp, bt, ln):
        return paged_attention_pallas(q, kp, vp, bt, ln, interpret=False)

    assert _kernels(chip, fn, *shapes) == 1
    # The grid it was built with is the one the shapes give: a row's every
    # head in one step, P >= 1 blocks a step, never (B, H, M) again.
    grid, per_step = paged_decode_grid(batch, heads, m, bs, d, 2)
    traced = jax.make_jaxpr(fn)(
        *(jax.ShapeDtypeStruct(*s) for s in shapes))
    built = [e.params["grid_mapping"].grid for e in traced.jaxpr.eqns
             if e.primitive.name == "pallas_call"]
    assert built == [grid] and 1 <= per_step <= m
    assert grid[0] * grid[1] <= batch * -(-m // per_step) < batch * heads * m


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


def _compiled_train_step(chip, topo, monkeypatch, config, accum, batch):
    """``make_train_step`` as the trainer builds it (guarded, attention left
    to choose), compiled for one chip. The attention policy asks
    ``jax.devices()`` for the platform at trace time and would see this
    sandbox's CPU, so the probe is steered here, in the test: it must then
    pick the flash kernel by itself."""
    from gpt_2_distributed_tpu.models import gpt2
    from gpt_2_distributed_tpu.parallel.train_step import (
        make_optimizer,
        make_train_step,
    )
    from gpt_2_distributed_tpu.resilience import init_guard_state

    monkeypatch.setattr(jax, "devices", lambda *a, **k: list(topo.devices))
    optimizer = make_optimizer(3e-3)
    step = make_train_step(config, optimizer, guard=True)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
            tree,
        )

    params = jax.eval_shape(lambda: gpt2.init_params(config))
    opt_state = jax.eval_shape(optimizer.init, params)
    tokens = jax.ShapeDtypeStruct(
        (accum, batch, config.n_positions), I32, sharding=chip)
    return step.lower(
        on_chip(params), on_chip(opt_state),
        on_chip(jax.eval_shape(init_guard_state)),
        tokens, tokens, jax.ShapeDtypeStruct(*KEY, sharding=chip), 0,
        jax.ShapeDtypeStruct((accum,), F32, sharding=chip),
    ).compile()


def test_train_step_124m(chip, topo, monkeypatch):
    """The step for ``--model 124M`` (batch 4, seq 1024, dropout on) must
    pick the flash kernel and fit the chip's memory."""
    config = MODEL_PRESETS["124M"].replace(n_positions=1024, scan_layers=True)
    compiled = _compiled_train_step(chip, topo, monkeypatch, config, 2, 4)
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    # Donated state aliases its outputs; what is not aliased is extra.
    need = (
        mem.argument_size_in_bytes + mem.temp_size_in_bytes
        + mem.output_size_in_bytes - mem.alias_size_in_bytes
    )
    assert need < HBM_BYTES, f"124M step needs {need / 2**30:.2f} GiB"


# --- the kernel names the benchmark's readers match --------------------------
#
# benchmark/metrics/flash_attn_roofline.py and paged_attn_roofline.py find
# their kernels in a trace by HLO instruction name (the pallas_calls carry no
# name=), and raise where they find none: a traced benchmark run then fails,
# on the chip. JAX derives those names from the name stack, so a
# jax.named_scope on the path, a name= on a pallas_call or a renamed wrapper
# function changes them. The profiler names a device event by its
# instruction's whole text, which is a line of the compiled module's text, so
# the needles can be held to the compiled programs here. When this fails, the
# readers have to learn the new name (a `benchmark` PR) before the program
# may take it.


def _reader_constants(metric: str) -> dict:
    from benchmark import harness

    return harness.load_reader(metric).__globals__


def _instructions_matching(hlo_text: str, needles) -> list[str]:
    """Instructions a reader would match: ``Trace.kernel_seconds``'s rule
    (starts with the first needle, contains the rest)."""
    head, rest = needles[0], needles[1:]
    lines = (line.strip().removeprefix("ROOT ") for line in hlo_text.splitlines())
    return [l for l in lines if l.startswith(head) and all(n in l for n in rest)]


def test_train_step_flash_kernel_names(chip, topo, monkeypatch):
    """The train step with its layers unrolled, as ``train-124m-1k`` runs
    124M (2 layers here: the names come from the name stack, not the depth):
    one forward and one backward flash kernel a layer, under the names
    ``flash_attn_roofline`` matches. (Under ``scan_layers`` both are named
    ``closed_call``; the benchmark has no such training cell yet.)"""
    config = MODEL_PRESETS["124M"].replace(
        n_positions=1024, n_layer=2, scan_layers=False)
    text = _compiled_train_step(chip, topo, monkeypatch, config, 2, 4).as_text()
    names = _reader_constants("flash_attn_roofline")
    forward = _instructions_matching(text, names["FORWARD"])
    backward = _instructions_matching(text, names["BACKWARD"])
    assert len(forward) == len(backward) == config.n_layer
    assert not set(forward) & set(backward)
    assert text.count("tpu_custom_call") == 2 * config.n_layer


def _engine_programs(chip, topo, monkeypatch, num_blocks=4 * 64 + 1,
                     held=None):
    """The decode step and the 256-wide chunk prefill, bound, named, donated
    and handed their pools as ``ServingEngine`` does - stored in
    ``paged_cache.pool_shape`` - at the 1.5B widths, 2 layers, 4 slots.
    The weights are the float32 tree ``init_params`` gives, through ``held``
    if given (``gpt2.serving_weights`` at a dtype: what an engine holds)."""
    from gpt_2_distributed_tpu.config import ServeConfig
    from gpt_2_distributed_tpu.models import gpt2
    from gpt_2_distributed_tpu.serving import engine as eng
    from gpt_2_distributed_tpu.serving.paged_cache import pool_shape

    monkeypatch.setattr(jax, "devices", lambda *a, **k: list(topo.devices))
    config = MODEL_PRESETS["1.5B"].replace(n_layer=2)
    serve = ServeConfig(max_batch=4, block_size=16, num_blocks=num_blocks,
                        prefill_chunk=256, attn_impl="pallas")

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    params = jax.eval_shape(lambda: gpt2.init_params(config))
    if held is not None:
        params = jax.eval_shape(held, params)
    params = jax.tree_util.tree_map(lambda a: arr(a.shape, a.dtype), params)
    pool = arr(pool_shape(config, serve), BF16)
    b, m = serve.max_batch, serve.max_blocks_per_seq(config.n_positions)
    decode = jax.jit(
        eng._program("decode_step", eng._decode_step_impl, config=config,
                     temperature=0.0, top_k=None, attn_impl=serve.attn_impl),
        donate_argnames=("k_pool", "v_pool"),
    ).lower(
        params, pool, pool, arr((b, m), I32), arr((b,), I32), arr((b,), I32),
        arr((b,), jnp.bool_), arr((b, 2), jnp.uint32)).compile()
    chunk = jax.jit(
        eng._program("chunk_prefill", eng._chunk_prefill_impl, config=config,
                     temperature=0.0, top_k=None),
        donate_argnames=("k_pool", "v_pool"),
    ).lower(
        params, pool, pool, arr((1, m), I32),
        arr((1, serve.prefill_chunk), I32), arr((1,), I32), arr((1,), I32),
        arr((1, 2), jnp.uint32)).compile()
    return decode, chunk, pool.shape, params


def test_engine_decode_step_kernel_name(chip, topo, monkeypatch):
    """The engine's decode step - ``_decode_step_impl`` bound and named as
    ``ServingEngine`` binds and names it - at the 1.5B widths, 2 layers:
    its one Mosaic kernel sits in the layer scan under the name
    ``paged_attn_roofline`` matches. The jit's own name (``decode_step``)
    names the module, not the instruction. (The kernel compiled alone, as
    ``test_paged_decode`` compiles it, does not get this name.)"""
    text = _engine_programs(chip, topo, monkeypatch)[0].as_text()
    assert "jit_decode_step" in text.splitlines()[0]
    kernel = _instructions_matching(
        text, _reader_constants("paged_attn_roofline")["KERNEL"])
    assert len(kernel) == 1 and text.count("tpu_custom_call") == 1


def test_engine_programs_never_copy_their_pools(chip, topo, monkeypatch):
    """One layout for a pool, end to end (PR 26). As the engine builds
    them, the decode step and the chunk prefill take the pools row-major at
    entry - the compiler's own default for the stored shape, so a program
    read back from the compile cache has it too - alias them to their
    outputs, and hold no ``copy`` of anything pool-shaped: neither a whole
    pool nor a layer's slice, whichever way the block axis is spelt. And
    their temporaries do not grow with the pool: twice the blocks, the same
    bytes. (They cannot be "below the pools' bytes": the hoisted bf16 copy
    of the weights is larger than 4 slots' pools at any depth.)"""
    import re

    small = _engine_programs(chip, topo, monkeypatch)
    large = _engine_programs(chip, topo, monkeypatch, num_blocks=8 * 64 + 1)
    shape = small[2]
    assert shape == (2, 6, 43, 25, 16, 64)
    stored = "bf16[" + ",".join(map(str, shape)) + "]{5,4,3,2,1,0:"
    blocks = {"6,43", "257", "258"}     # split, asked for, merged
    for name, compiled, bigger in zip(("decode", "chunk"), small[:2], large[:2]):
        text = compiled.as_text()
        head = text.splitlines()[0]
        assert head.count(stored) == 4, (name, head)    # 2 pools in, 2 out
        assert head.count("may-alias") == 2, (name, head)
        for line in text.splitlines():
            made = re.match(r"\s*(?:ROOT )?%\S+ = (\S+) copy(?:-start)?\(", line)
            if made:
                dims = re.search(r"\[([\d,]*)\]", made.group(1)).group(1)
                assert not any(
                    re.search(rf"(^|,){b}(,|$)", dims) for b in blocks
                ), (name, line.strip()[:160])
        temp = compiled.memory_analysis().temp_size_in_bytes
        grown = bigger.memory_analysis().temp_size_in_bytes
        assert abs(grown - temp) < 2**20, (name, temp, grown)


def _weight_casts(hlo_text: str, params) -> list[str]:
    """``convert`` instructions whose result is a bfloat16 array of a weight
    leaf's shape, whole or one layer's slice of a stacked leaf: a cast of
    that weight (no activation of these programs has such a shape)."""
    import re

    shapes = set()
    for a in jax.tree_util.tree_leaves(params):
        shapes |= {a.shape, a.shape[1:]}
    found = []
    for line in hlo_text.splitlines():
        made = re.match(r"\s*(?:ROOT )?%\S+ = bf16\[([\d,]+)\]\S* convert\(", line)
        if made and tuple(map(int, made.group(1).split(","))) in shapes:
            found.append(line.strip()[:160])
    return found


def test_engine_programs_never_cast_their_weights(chip, topo, monkeypatch):
    """What an engine holds is what its programs multiply by (PR 31). On the
    tree ``gpt2.serving_weights`` gives at bfloat16 - the engine's own - the
    decode step and the chunk prefill convert no weight, take the bf16 tree
    and the two pools as their arguments, and keep under a tenth of the
    weights' bytes in temporaries beside one relayout copy of ``wte`` (the
    row gather and the head want it two ways: ``PERF.md`` section 7; at 48
    layers copy and all are under that tenth). On the float32 tree, which
    the step impls still accept and ``tests/benchmark/test_aot_v5e.py``
    still lowers, XLA hoists the ``.astype`` of every stacked matmul weight
    out of the layer scan and runs it whole on every call, into temporaries.
    That is what an engine must never be handed again."""
    from gpt_2_distributed_tpu.models import gpt2

    given = _engine_programs(chip, topo, monkeypatch)
    held = _engine_programs(
        chip, topo, monkeypatch, held=lambda t: gpt2.serving_weights(t, BF16))

    def nbytes(tree):
        return sum(a.size * a.dtype.itemsize
                   for a in jax.tree_util.tree_leaves(tree))

    f32_bytes, held_bytes = nbytes(given[3]), nbytes(held[3])
    norms = nbytes([a for a in jax.tree_util.tree_leaves(held[3])
                    if a.dtype == F32])
    assert 0 < norms < 2**16 and held_bytes - norms == (f32_bytes - norms) // 2
    wte = nbytes(held[3]["wte"])
    for name, before, after in zip(("decode", "chunk"), given[:2], held[:2]):
        assert len(_weight_casts(before.as_text(), given[3])) >= 4, name
        assert not _weight_casts(after.as_text(), given[3]), name
        mem = after.memory_analysis()
        assert mem.temp_size_in_bytes - wte < held_bytes // 10, (
            name, mem.temp_size_in_bytes, held_bytes)
        assert before.memory_analysis().temp_size_in_bytes > mem.temp_size_in_bytes
        # The pools as the device holds them (D 64 in 128 lanes) are the
        # bytes aliased to the outputs; the rest is the bf16 tree, the
        # padding of its tiles and the step's rows.
        rest = mem.argument_size_in_bytes - mem.alias_size_in_bytes - held_bytes
        assert 0 <= rest < held_bytes // 100, (name, mem.argument_size_in_bytes)
