"""Rotary position embeddings (RoPE)."""

from __future__ import annotations

import jax.numpy as jnp


def rotary_freqs(head_dim: int, max_len: int, theta: float = 10000.0):
    """Precompute cos/sin tables: [max_len, head_dim//2] each."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                           / head_dim))
    t = jnp.arange(max_len, dtype=jnp.float32)
    ang = jnp.outer(t, inv)  # [L, D/2]
    return jnp.cos(ang), jnp.sin(ang)


def apply_rotary(x, cos, sin, positions=None):
    """x: [..., S, H, D]; cos/sin: [L, D/2]; positions: [S] global indices
    (defaults to arange — pass explicit global positions under sequence
    sharding)."""
    seq = x.shape[-3]
    if positions is None:
        positions = jnp.arange(seq)
    c = cos[positions][:, None, :]  # [S, 1, D/2]
    s = sin[positions][:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    out = jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)
    return out.astype(x.dtype)


def rotary_inv_freq(rot_dim: int, theta: float, yarn: dict = None):
    """Inverse frequencies ``[rot_dim // 2]`` float32 of a rotation over
    `rot_dim` values of a head: ``theta ** (-2 i / rot_dim)``, or, with
    `yarn` (``factor``, ``original_max_position_embeddings``,
    ``beta_fast``, ``beta_slow``), YaRN's blend of them: a frequency that
    turns more than ``beta_fast`` times over the original context stays,
    one that turns less than ``beta_slow`` times is divided by ``factor``,
    and a linear ramp over the pair index lies between the two (bounds
    floored and ceiled, as `rope_type` "yarn" computes them)."""
    import math

    exponent = jnp.arange(0, rot_dim, 2, dtype=jnp.float32) / rot_dim
    inv = 1.0 / (theta ** exponent)
    if not yarn:
        return inv
    original = yarn["original_max_position_embeddings"]

    def pair_of(turns):
        return (rot_dim * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(pair_of(yarn["beta_fast"])), 0)
    high = min(math.ceil(pair_of(yarn["beta_slow"])), rot_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(rot_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return inv / yarn["factor"] * ramp + inv * (1.0 - ramp)


def rotary_cos_sin(positions, inv_freq, attention_factor: float = 1.0):
    """cos and sin ``[*positions.shape, rot_dim // 2]`` float32 of the
    angles ``position * inv_freq``, each times `attention_factor` (YaRN
    scales the rotated values, and so the scores, through its tables)."""
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    return jnp.cos(ang) * attention_factor, jnp.sin(ang) * attention_factor


def apply_rotary_partial(x, cos, sin):
    """Rotate the first ``2 * cos.shape[-1]`` values of every head and
    pass the rest through. x ``[T, H, D]``; cos, sin ``[T, rot_dim //
    2]`` at the tokens' positions (`rotary_cos_sin`). The rotated part
    pairs value ``i`` with value ``i + rot_dim // 2``, as `apply_rotary`
    does over the whole head. Computed and returned in float32."""
    half = cos.shape[-1]
    x = x.astype(jnp.float32)
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c, rest],
                           axis=-1)


def rotary_cos_sin_sections(positions, inv_freq, sections):
    """`rotary_cos_sin` over several position streams: positions ``[n,
    T]``, a row a stream (temporal, height, width), and `sections`, how
    many of the ``rot_dim // 2`` pairs each stream turns, in order
    (``mrope_section`` [16, 24, 24]: pairs 0-15 by the first stream,
    16-39 by the second, 40-63 by the third). cos, sin ``[T, rot_dim //
    2]`` float32; with equal streams they are `rotary_cos_sin`'s."""
    import numpy as np

    half = inv_freq.shape[0]
    if len(sections) != positions.shape[0] or sum(sections) != half:
        raise ValueError(f"sections {list(sections)} do not share out "
                         f"{half} pairs over {positions.shape[0]} streams")
    stream = np.repeat(np.arange(len(sections)), sections)       # [half]
    ang = positions.astype(jnp.float32)[..., None] * inv_freq   # [n, T, half]
    ang = ang[stream, :, np.arange(half)].T                      # [T, half]
    return jnp.cos(ang), jnp.sin(ang)


def apply_rotary_interleaved(x, cos, sin):
    """Rotate every head of x ``[T, H, D]`` whole, pairing value ``2 i``
    with value ``2 i + 1`` (`rope_interleave`: the pairs lie side by
    side, where `apply_rotary_partial` pairs ``i`` with ``i + D / 2``).
    cos, sin ``[T, D // 2]`` at the tokens' positions (`rotary_cos_sin`).
    Computed and returned in float32."""
    x = x.astype(jnp.float32)
    pairs = x.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.stack([a * c - b * s, a * s + b * c], axis=-1).reshape(x.shape)
