"""Linear attention with a per-head decay (Lightning Attention).

The recurrence, per head with decay ``lambda = exp(-slope)``::

    S_t = lambda * S_{t-1} + k_t^T v_t          # [d, d], float32
    o_t = q_t S_t

Serving needs it in two forms that must both equal it: ``chunked`` for a
prefill chunk (state in, state out: within a sub-chunk a decayed causal
product on the MXU, between sub-chunks the state) and ``step`` for a decode
token. Every decay that is formed is ``exp`` of a sum of ``-slope`` over a
run of tokens, so nothing can overflow however fast a head forgets (the
usual ``q lambda^i``, ``k lambda^-i`` split overflows float32 at slopes near
1 within a few hundred tokens). Padding is handled by the decay itself: a
token that is not ``valid`` neither decays the state nor adds to it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def decay_slopes(heads: int) -> jnp.ndarray:
    """``slope_h = 2^(-8 (h + 1) / H)``, so ``lambda_h = exp(-slope_h)``: the
    convention Lightning Attention publishes; fast heads first."""
    h = jnp.arange(1, heads + 1, dtype=jnp.float32)
    return 2.0 ** (-8.0 * h / heads)


def chunked(
    q: jnp.ndarray,        # [T, H, d] compute dtype
    k: jnp.ndarray,
    v: jnp.ndarray,
    valid: jnp.ndarray,    # [T] bool — False = padding
    state: jnp.ndarray,    # [H, d, d] float32, the state before q[0]
    slopes: jnp.ndarray,   # [H] float32
    sub: int = 256,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``(o [T, H, d] float32, state after the last valid token)``."""
    t, h, d = q.shape
    sub = min(sub, t)
    if t % sub:
        raise ValueError(f"chunk of {t} tokens is no multiple of sub-chunk {sub}")
    n = t // sub
    causal = jnp.tril(jnp.ones((sub, sub), bool))

    def body(s_in, xs):
        q_c, k_c, v_c, ok = xs                                  # [sub, H, d], [sub]
        step = -slopes[None] * ok[:, None].astype(jnp.float32)  # [sub, H]
        a = jnp.cumsum(step, axis=0)                            # log decay to i, inclusive
        # within the sub-chunk: (q_i . k_j) exp(a_i - a_j) for j <= i
        gap = a.T[:, :, None] - a.T[:, None, :]                 # [H, i, j]
        decay = jnp.where(causal[None] & ok[None, None], jnp.exp(jnp.minimum(gap, 0.0)), 0.0)
        scores = jnp.einsum("ihd,jhd->hij", q_c, k_c,
                            preferred_element_type=jnp.float32) * decay
        o = jnp.einsum("hij,jhd->ihd", scores.astype(v_c.dtype), v_c,
                       preferred_element_type=jnp.float32)
        # from before the sub-chunk: exp(a_i) q_i S_in
        o = o + jnp.exp(a)[:, :, None] * jnp.einsum(
            "ihd,hde->ihe", q_c.astype(jnp.float32), s_in, precision=HIGHEST)
        # the state after it: exp(a_last) S_in + sum_j exp(a_last - a_j) k_j^T v_j
        carry = jnp.where(ok[:, None], jnp.exp(a[-1][None] - a), 0.0)      # [sub, H]
        s_out = jnp.exp(a[-1])[:, None, None] * s_in + jnp.einsum(
            "jhd,jhe->hde", k_c.astype(jnp.float32) * carry[:, :, None],
            v_c.astype(jnp.float32), precision=HIGHEST)
        return s_out, o

    split = lambda x: x.reshape(n, sub, *x.shape[1:])
    state, o = jax.lax.scan(body, state, (split(q), split(k), split(v), split(valid)))
    return o.reshape(t, h, d), state


def step(
    q: jnp.ndarray,        # [B, H, d]
    k: jnp.ndarray,
    v: jnp.ndarray,
    active: jnp.ndarray,   # [B] bool — an idle row keeps its state
    state: jnp.ndarray,    # [B, H, d, d] float32
    slopes: jnp.ndarray,   # [H] float32
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One token a row: ``(o [B, H, d] float32, new state)``."""
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    new = jnp.exp(-slopes)[None, :, None, None] * state \
        + kf[:, :, :, None] * vf[:, :, None, :]
    state = jnp.where(active[:, None, None, None], new, state)
    o = jnp.einsum("bhd,bhde->bhe", q.astype(jnp.float32), state, precision=HIGHEST)
    return o, state
