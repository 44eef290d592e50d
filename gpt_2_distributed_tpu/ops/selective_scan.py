"""The Mamba-1 selective scan: a state-space recurrence whose decay differs
for every channel ``d`` and state index ``n``.

Per channel ``d`` of the ``D`` inner channels, with the keys and queries
(``B_t`` and ``C_t``, of width ``N``) shared by all channels::

    S_t[n, d] = exp(dt_t[d] A[n, d]) S_{t-1}[n, d] + dt_t[d] u_t[d] B_t[n]     # float32
    y_t[d]    = sum_n S_t[n, d] C_t[n]

``ops/ssd.py`` is Mamba-2's recurrence: there the decay is one scalar a head
and token, so a sub-chunk becomes a decayed causal product on the MXU. Here
the decay ``exp(dt_t[d] A[n, d])`` has no such factor - a product over a run
of tokens would need one ``[tokens, tokens]`` matrix for every ``(n, d)`` -
so the recurrence is walked token by token on the vector units.

The state is kept ``[N, D]``, the channels last: a last dimension of ``N =
16`` would be padded to 128 lanes, eight times the memory and the vector
work. ``A`` is given the same way round.

Serving needs the recurrence in two forms that must both equal it:
``chunked`` for a prefill chunk (state in, state out) and ``step`` for a
decode token. Every decay is ``exp`` of ``dt A <= 0``, so nothing overflows.
Padding and idle rows are handled by ``dt`` itself, as in ``ops/ssd.py``: a
token with ``dt = 0`` neither decays the state nor adds to it, bit for bit
(``1 * S + 0``). The causal convolution before the scan is ``ssd.conv_chunk``
/ ``ssd.conv_step``.

``chunked`` is a Pallas kernel on a TPU (``selective_scan_chunk``: the whole
``[N, D]`` state stays in VMEM while the kernel walks a chunk's tokens in
blocks of ``TOKEN_BLOCK``) and a ``lax.scan`` over the tokens elsewhere. The
XLA form is fast on the chip too (0.41 ms a layer for a chunk of 1,024 at ``D``
5120, four tokens to an iteration; PERF.md, PR 35), but each of its 256
iterations is several device operations: a profiler records millions of them
in seconds of prefill, and a traced run drowns. ``step`` is plain XLA.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from gpt_2_distributed_tpu.ops.spmd import pallas_mode, record_resolved_impl

TOKEN_BLOCK = 32     # tokens of u, dt and y a grid step of the kernel holds (128 overflows VMEM)
GROUP = 8            # tokens walked between two loads / stores: one sublane tile


def _scan_kernel(u_ref, dt_ref, a_ref, b_ref, c_ref, s_in_ref, y_ref, s_ref):
    """One block of tokens: ``s_ref`` [N, D], the output state, is the same
    block at every grid step - it stays in VMEM, is set from ``s_in_ref`` at
    the first and written back after the last. ``b_ref`` / ``c_ref`` are
    ``[block, N, 1]``: a token's keys as a column, broadcast over the lanes."""
    @pl.when(pl.program_id(0) == 0)
    def _start():
        s_ref[...] = s_in_ref[...]

    a = a_ref[...]

    def group(g, carry):
        at = pl.multiple_of(g * GROUP, GROUP)
        u, dt = u_ref[pl.ds(at, GROUP), :], dt_ref[pl.ds(at, GROUP), :]    # [GROUP, D]
        s = s_ref[...]
        ys = []
        for j in range(GROUP):
            dt_t = dt[j:j + 1, :]                                          # [1, D]
            s = jnp.exp(dt_t * a) * s + b_ref[at + j] * (dt_t * u[j:j + 1, :])
            ys.append(jnp.sum(s * c_ref[at + j], axis=0, keepdims=True))
        s_ref[...] = s
        y_ref[pl.ds(at, GROUP), :] = jnp.concatenate(ys, axis=0)
        return carry

    jax.lax.fori_loop(0, u_ref.shape[0] // GROUP, group, 0)


def selective_scan_chunk(u, dt, a, b, c, state, *, interpret: bool = False):
    """The kernel behind ``chunked``: ``T`` a multiple of ``GROUP``, float32
    throughout. Grid: the chunk's token blocks, in order."""
    t, d = u.shape
    n = a.shape[0]
    block = TOKEN_BLOCK if t % TOKEN_BLOCK == 0 else t
    tokens = lambda width: pl.BlockSpec((block, width), lambda i: (i, 0))
    column = pl.BlockSpec((block, n, 1), lambda i: (i, 0, 0))
    whole = pl.BlockSpec((n, d), lambda i: (0, 0))
    y, state = pl.pallas_call(
        _scan_kernel,
        grid=(t // block,),
        in_specs=[tokens(d), tokens(d), whole, column, column, whole],
        out_specs=[tokens(d), whole],
        out_shape=[jax.ShapeDtypeStruct((t, d), jnp.float32),
                   jax.ShapeDtypeStruct((n, d), jnp.float32)],
        interpret=interpret,
        name="selective_scan_chunk",
    )(u, dt, a, b[:, :, None], c[:, :, None], state)
    return y, state


def chunked(
    u: jnp.ndarray,        # [T, D] float32 the convolved, activated input
    dt: jnp.ndarray,       # [T, D] float32 step sizes, 0 at padding
    a: jnp.ndarray,        # [N, D] float32, negative
    b: jnp.ndarray,        # [T, N] float32
    c: jnp.ndarray,        # [T, N] float32
    state: jnp.ndarray,    # [N, D] float32, the state before u[0]
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``(y [T, D] float32, state after the last token with dt > 0)``; the
    state is kept in the dtype it came in - float32 in every program; a lower
    one is the tests', and takes the XLA form. ``interpret``: None takes the
    kernel on a TPU and a ``lax.scan`` over the tokens elsewhere; True or
    False is the kernel, interpreted or compiled (the tests', and an
    ahead-of-time build's). Either way the temporaries are a few tokens'
    ``[N, D]`` decays and inputs, never the chunk's ``[T, N, D]``."""
    kernel = interpret is not None or jax.devices()[0].platform == "tpu"
    if kernel and u.shape[0] % GROUP == 0 and state.dtype == jnp.float32:
        record_resolved_impl(
            "selective_scan", f"pallas ({pallas_mode(bool(interpret))})")
        return selective_scan_chunk(u, dt, a, b, c, state, interpret=bool(interpret))
    record_resolved_impl("selective_scan", "xla (scan)")

    def body(s, xs):
        u_t, dt_t, b_t, c_t = xs                             # [D], [D], [N], [N]
        s = (jnp.exp(dt_t[None, :] * a) * s
             + b_t[:, None] * (dt_t * u_t)[None, :]).astype(state.dtype)
        # on the vector units: a dot would round its float32 operands
        return s, jnp.sum(s * c_t[:, None], axis=0, dtype=jnp.float32)

    state, y = jax.lax.scan(body, state, (u, dt, b, c), unroll=4)
    return y, state


def step(
    u: jnp.ndarray,        # [B, D] float32
    dt: jnp.ndarray,       # [B, D] float32, 0 for an idle row: it keeps its state
    a: jnp.ndarray,        # [N, D] float32
    b: jnp.ndarray,        # [B, N] float32
    c: jnp.ndarray,        # [B, N] float32
    state: jnp.ndarray,    # [B, N, D] float32
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One token a row: ``(y [B, D] float32, new state)``."""
    state = jnp.exp(dt[:, None, :] * a[None]) * state \
        + b[:, :, None] * (dt * u)[:, None, :]
    return jnp.sum(state * c[:, :, None], axis=1), state
