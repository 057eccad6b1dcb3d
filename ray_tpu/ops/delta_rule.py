"""The gated delta rule (linear attention with a recurrent state), with a
decay a channel or one scalar a head, in the two forms a serving engine
needs.

State of one head ``S`` in ``R^{dk x dv}``, zero before the first token.
With ``a_t = exp(g_t)`` in ``(0, 1]^dk`` and ``beta_t`` in ``[0, 2)``::

    S_t = (I - beta_t k_t k_t^T) diag(a_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

- `delta_rule_step`: one token a sequence, the state read and written
  once (decode). Multiplies and sums in float32 on the vector unit: no
  matrix product, so nothing is rounded to bf16 on a TPU.
- `delta_rule_chunked`: a whole prompt, a chunk of positions at a time
  by the WY form (prefill). Inside a chunk the recurrence unrolls into a
  unit lower-triangular system: with ``G_t`` the running sum of ``g``
  inside the chunk and ``D_ti = exp(G_t - G_i)`` the decay from position
  ``i`` to ``t`` (a vector over ``dk``), ``A_ti = sum_d k_td k_id D_tid``
  for ``i < t`` and ``B_ti = sum_d q_td k_id D_tid`` for ``i <= t``::

      (I + diag(beta) A) U = diag(beta) (V - (k * exp(G)) S_0)
      O   = (q * exp(G)) S_0 + B U
      S_C = diag(exp(G_C)) S_0 + (k * exp(G_C - G))^T U

  The decays between two positions are taken as they stand, a pair at a
  time under the causal mask, never as ``exp(G_t) * exp(-G_i)``: every
  exponent is at most 0, so the form holds for any decay (a factor
  ``exp(-G)`` overflows float32 once a chunk's decay sums past 88). The
  pairs' sums run in float32 on the vector unit and every matrix product
  is taken at `Precision.HIGHEST` (float32 on the chip): the state a
  prefill ends on is what decode starts from.

A decay that is **one scalar a head** (``g`` of rank one less than
``q``: ``[.., H]`` beside ``[.., H, dk]``; the rank tells the two apart)
is ``a_t`` times the identity, and the chunk's decays between two
positions are one ``[C, C]`` matrix a head instead of ``[C, C, dk]``:
``A = (k k^T) * D`` and ``B = (q k^T) * D``, every product of the chunk on
the matrix unit, the same equations and the same rule for the exponents
(a pair at a time under the mask, each at most 0). The per-channel form
on a decay broadcast over ``dk`` gives the same numbers.

Positions past a prompt's length (a padded shape bucket) are handed
``g = 0`` and ``beta = 0``: they leave the state as it is.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST


def delta_rule_step(state, q, k, v, g, beta):
    """state ``[..., dk, dv]`` float32; q, k ``[..., dk]``; g ``[...,
    dk]`` or, one scalar a head, ``[...]``; v ``[..., dv]``; beta
    ``[...]``. Returns ``(o [..., dv], new_state)``. ``g = 0`` and ``beta
    = 0`` return the state bit for bit."""
    f32 = jnp.float32
    q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, g, beta))
    a = jnp.exp(g)[..., None] if g.ndim == q.ndim else \
        jnp.exp(g)[..., None, None]
    decayed = a * state                                      # diag(a) S
    k_read = jnp.sum(decayed * k[..., None], axis=-2)        # (aS)^T k
    q_read = jnp.sum(decayed * q[..., None], axis=-2)        # (aS)^T q
    delta = beta[..., None] * (v - k_read)                   # [..., dv]
    new_state = decayed + k[..., None] * delta[..., None, :]
    o = q_read + jnp.sum(k * q, axis=-1, keepdims=True) * delta
    return o, new_state


def delta_rule_chunked(q, k, v, g, beta, state, chunk: int = 64):
    """q, k ``[S, H, dk]``; g ``[S, H, dk]`` or, one scalar a head, ``[S,
    H]``; v ``[S, H, dv]``; beta ``[S, H]``; state ``[H, dk, dv]``
    float32 (what came before position 0). ``S`` is a multiple of
    `chunk`. Returns ``(o [S, H, dv], final_state)``."""
    f32 = jnp.float32
    s, h, dk = q.shape
    dv = v.shape[-1]
    n = s // chunk
    if n * chunk != s:
        raise ValueError(f"{s} positions are no multiple of chunk {chunk}")

    def chunks(x):                      # [S, H, ...] -> [n, H, C, ...]
        x = x.astype(f32).reshape((n, chunk, h) + x.shape[2:])
        return jnp.moveaxis(x, 2, 1)

    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    strictly_lower = jnp.tril(lower, -1)
    eye = jnp.eye(chunk, dtype=f32)

    def one_chunk_scalar(state, xs):
        q, k, v, g, beta = xs               # [H, C, dk] ..., g, beta [H, C]
        decay = jnp.cumsum(g, axis=1)                        # G_t  [H, C]
        # D_ti, zero above the diagonal: [H, C, C].
        between = jnp.exp(jnp.where(
            lower, decay[:, :, None] - decay[:, None, :], -jnp.inf))
        a = jnp.where(strictly_lower, jnp.einsum(
            "htd,hid->hti", k, k, precision=_HIGHEST) * between, 0.0)
        b = jnp.einsum("htd,hid->hti", q, k, precision=_HIGHEST) * between
        from_start = jnp.exp(decay)[..., None]               # exp(G_t)
        read = jnp.einsum("htd,hdv->htv", k * from_start, state,
                          precision=_HIGHEST)
        u = jax.lax.linalg.triangular_solve(
            eye + beta[..., None] * a, beta[..., None] * (v - read),
            left_side=True, lower=True, unit_diagonal=True)
        o = (jnp.einsum("htd,hdv->htv", q * from_start, state,
                        precision=_HIGHEST)
             + jnp.einsum("hti,hiv->htv", b, u, precision=_HIGHEST))
        to_end = jnp.exp(decay[:, -1:] - decay)[..., None]   # exp(G_C - G_t)
        state = (from_start[:, -1, :, None] * state
                 + jnp.einsum("htd,htv->hdv", k * to_end, u,
                              precision=_HIGHEST))
        return state, o

    def one_chunk(state, xs):
        q, k, v, g, beta = xs               # [H, C, dk] ..., beta [H, C]
        decay = jnp.cumsum(g, axis=1)                        # G_t
        # D_ti, zero above the diagonal: [H, C, C, dk].
        between = jnp.exp(jnp.where(
            lower[None, :, :, None],
            decay[:, :, None, :] - decay[:, None, :, :], -jnp.inf))
        k_pairs = k[:, None, :, :] * between                 # k_i D_ti
        a = jnp.where(strictly_lower,
                      jnp.sum(k[:, :, None, :] * k_pairs, axis=-1), 0.0)
        b = jnp.sum(q[:, :, None, :] * k_pairs, axis=-1)
        from_start = jnp.exp(decay)                          # exp(G_t)
        read = jnp.einsum("htd,hdv->htv", k * from_start, state,
                          precision=_HIGHEST)
        u = jax.lax.linalg.triangular_solve(
            eye + beta[..., None] * a, beta[..., None] * (v - read),
            left_side=True, lower=True, unit_diagonal=True)
        o = (jnp.einsum("htd,hdv->htv", q * from_start, state,
                        precision=_HIGHEST)
             + jnp.einsum("hti,hiv->htv", b, u, precision=_HIGHEST))
        to_end = jnp.exp(decay[:, -1:, :] - decay)           # exp(G_C - G_t)
        state = (from_start[:, -1, :, None] * state
                 + jnp.einsum("htd,htv->hdv", k * to_end, u,
                              precision=_HIGHEST))
        return state, o

    state, o = jax.lax.scan(one_chunk if g.ndim == q.ndim
                            else one_chunk_scalar, state.astype(f32),
                            tuple(chunks(x) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 1, 2).reshape(s, h, dv), state
