"""Lightning attention: linear attention with a recurrent state and ONE
constant decay a head, in the two forms a serving engine needs.

State of one head ``S`` in ``R^{dk x dv}``, zero before the first token.
With ``lambda = exp(g)`` in ``(0, 1]``::

    S_t = lambda S_{t-1} + k_t v_t^T
    o_t = S_t^T q_t

No delta rule, no convolution, no gate that depends on the data: the
decay is a constant of the head and the layer, and the caller scales
``q`` (``1 / sqrt(dk)``).

- `lightning_step`: one token a sequence, the state read and written
  once (decode). Multiplies and sums in float32 on the vector unit.
- `lightning_step_in_pool`: that step for one layer of a pool of slots'
  states ``[slots, layers, H, dk, dv]``, every slot a sequence's. On
  the chip a Pallas kernel (in a trace ``lightning_decode_step``) that
  brings a slot's state of eight heads into VMEM, updates it, reads it
  and writes it back IN PLACE (the pool is the kernel's operand and its
  result: no layer of it is copied out or put back), a state's bytes
  moved twice and no more; elsewhere the step over the layer sliced out.
  It moves EVERY slot's state, a row's or not (a slot without a row
  comes with ``g = 0`` and ``k = 0``, which leave it bit for bit).
- `lightning_chunked`: a whole prompt or a chunk of one, `chunk`
  positions at a time. With ``G_t`` the running sum of ``g`` inside the
  chunk and ``D_ti = exp(G_t - G_i)`` for ``i <= t`` (one ``[C, C]``
  matrix a head, as the scalar-decay form of `ops/delta_rule.py` builds
  it: a pair at a time under the causal mask, every exponent at most
  0)::

      O   = (q * exp(G)) S_0 + ((q k^T) * D) V
      S_C = exp(G_C) S_0 + (k * exp(G_C - G))^T V

  Every product of the chunk is on the matrix unit, at
  `Precision.HIGHEST` (float32 on the chip): the state a prefill ends on
  is what decode starts from, and the layer's own products are a
  thousandth of a chunk's.

``g`` comes a position (``[S, H]``): the head's constant at a live
position, 0 at one past a prompt's length (a padded shape bucket), whose
key the caller hands over as zeros: it leaves the state as it is.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST


def lightning_step(state, q, k, v, g):
    """state ``[..., dk, dv]`` float32; q, k ``[..., dk]``; v ``[...,
    dv]``; g ``[...]`` (log of the decay). Returns ``(o [..., dv],
    new_state)``. ``g = 0`` with ``k = 0`` returns the state bit for
    bit."""
    f32 = jnp.float32
    q, k, v, g = (x.astype(f32) for x in (q, k, v, g))
    new_state = (jnp.exp(g)[..., None, None] * state
                 + k[..., None] * v[..., None, :])
    return jnp.sum(new_state * q[..., None], axis=-2), new_state


def lightning_chunked(q, k, v, g, state, chunk: int = 128):
    """q, k ``[S, H, dk]``; v ``[S, H, dv]``; g ``[S, H]``; state ``[H,
    dk, dv]`` float32 (what came before position 0). ``S`` is a multiple
    of `chunk`. Returns ``(o [S, H, dv], final_state)``."""
    f32 = jnp.float32
    s, h, _ = q.shape
    dv = v.shape[-1]
    n = s // chunk
    if n * chunk != s:
        raise ValueError(f"{s} positions are no multiple of chunk {chunk}")

    def chunks(x):                      # [S, H, ...] -> [n, H, C, ...]
        x = x.astype(f32).reshape((n, chunk, h) + x.shape[2:])
        return jnp.moveaxis(x, 2, 1)

    lower = jnp.tril(jnp.ones((chunk, chunk), bool))

    def one_chunk(state, xs):
        q, k, v, g = xs                     # [H, C, dk] ..., g [H, C]
        decay = jnp.cumsum(g, axis=1)                        # G_t  [H, C]
        # D_ti, zero above the diagonal: [H, C, C].
        between = jnp.exp(jnp.where(
            lower, decay[:, :, None] - decay[:, None, :], -jnp.inf))
        pairs = jnp.einsum("htd,hid->hti", q, k,
                           precision=_HIGHEST) * between
        from_start = jnp.exp(decay)[..., None]               # exp(G_t)
        o = (jnp.einsum("htd,hdv->htv", q * from_start, state,
                        precision=_HIGHEST)
             + jnp.einsum("hti,hiv->htv", pairs, v, precision=_HIGHEST))
        to_end = jnp.exp(decay[:, -1:] - decay)[..., None]   # exp(G_C - G_t)
        state = (from_start[:, -1, :, None] * state
                 + jnp.einsum("htd,htv->hdv", k * to_end, v,
                              precision=_HIGHEST))
        return state, o

    state, o = jax.lax.scan(one_chunk, state.astype(f32),
                            tuple(chunks(x) for x in (q, k, v, g)))
    return jnp.moveaxis(o, 1, 2).reshape(s, h, dv), state


# -- one step over a layer of the state pool, in place -----------------------
STEP_KERNEL_NAME = "lightning_decode_step"
_STEP_HEADS = 8         # heads a grid step: 512 KB of state at 128 x 128


def step_kernel_eligible(heads: int, dk: int, dv: int) -> bool:
    """The kernel needs the TPU backend, a state whose rows fill the
    lanes and whole groups of `_STEP_HEADS` heads (the unit tests' tiny
    models take the sliced step)."""
    return (jax.default_backend() == "tpu" and dk % 8 == 0
            and dv % 128 == 0 and heads % _STEP_HEADS == 0)


def _step_body(qk_ref, v_ref, decay_ref, s_ref, s_out_ref, o_ref, *,
               heads: int):
    """One slot's `_STEP_HEADS` heads. qk_ref ``[1, dk, 2 H]``: q's and
    then k's heads on the lanes, so that a head's q or k is a COLUMN
    (``dk`` on the sublanes, as the state's rows lie) picked out by a
    masked sum over the lanes; v_ref and decay_ref ``[1, heads here,
    dv]`` rows; the state ``[1, 1, heads here, dk, dv]``."""
    from jax.experimental import pallas as pl

    first = pl.program_id(1) * _STEP_HEADS
    qk = qk_ref[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, qk.shape, 1)

    def column(at):
        return jnp.sum(jnp.where(lane == at, qk, 0.0), axis=1, keepdims=True)

    for h in range(_STEP_HEADS):
        state = (s_ref[0, 0, h] * decay_ref[0, h:h + 1, :]
                 + column(heads + first + h) * v_ref[0, h:h + 1, :])
        s_out_ref[0, 0, h] = state
        o_ref[0, h:h + 1, :] = jnp.sum(state * column(first + h), axis=0,
                                       keepdims=True)


def lightning_step_kernel(pool, layer: int, q, k, v, g, *,
                          interpret: bool = False):
    """`lightning_step_in_pool`'s kernel: grid ``(slots, H / 8)``, the
    pool aliased to the first result."""
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    n, _, h, dk, dv = pool.shape
    qk = jnp.concatenate([q, k], axis=1).astype(f32).transpose(0, 2, 1)
    decay = jnp.broadcast_to(jnp.exp(g.astype(f32))[..., None], (n, h, dv))
    rows = pl.BlockSpec((1, _STEP_HEADS, dv), lambda i, j: (i, j, 0))
    states = pl.BlockSpec((1, 1, _STEP_HEADS, dk, dv),
                          lambda i, j: (i, layer, j, 0, 0))
    new_pool, o = pl.pallas_call(
        functools.partial(_step_body, heads=h),
        grid=(n, h // _STEP_HEADS),
        in_specs=[pl.BlockSpec((1, dk, 2 * h), lambda i, j: (i, 0, 0)),
                  rows, rows, states],
        out_specs=[states, rows],
        out_shape=[jax.ShapeDtypeStruct(pool.shape, f32),
                   jax.ShapeDtypeStruct((n, h, dv), f32)],
        input_output_aliases={3: 0},
        name=STEP_KERNEL_NAME,
        interpret=interpret,
    )(qk, v.astype(f32), decay, pool)
    return o, new_pool


def lightning_step_in_pool(pool, layer: int, q, k, v, g):
    """`lightning_step` for layer `layer` (an int) of pool ``[slots,
    layers, H, dk, dv]`` float32: q, k ``[slots, H, dk]``, v ``[slots,
    H, dv]``, g ``[slots, H]``, a slot's row each. Returns ``(o [slots,
    H, dv], the pool with the layer's states moved on)``."""
    _, _, h, dk, dv = pool.shape
    if step_kernel_eligible(h, dk, dv):
        return lightning_step_kernel(pool, layer, q, k, v, g)
    o, state = lightning_step(pool[:, layer], q, k, v, g)
    return o, pool.at[:, layer].set(state)
