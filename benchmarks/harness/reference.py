"""The plain reference of the one block the tree runs: float32,
`default_matmul_precision("highest")`, no kernels, no cache, no batching
tricks. Written from the published description of a pre-norm decoder
(RMSNorm with a scale, multi-head attention with rotary embeddings on the
whole head, silu-gated FFN, tied embeddings, no biases), not from
`models/transformer.py` or `serve/engine/model.py`; it shares only the
layout of the parameter tree with them, because it is handed the same
seeded weights:

    embed [V, d]; ln_f [d]; layers.{ln1, ln2} [L, d];
    layers.wqkv [L, d, 3, d]; layers.wo [L, d, d];
    layers.w13 [L, d, 2, f] (gate, up); layers.w2 [L, f, d]

Rotary: the half-split form (x1, x2 = the two halves of a head), as
GPT-NeoX and the HF implementations of both configurations use it.
Imported only in processes that own a device.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NORM_EPS = 1e-6   # the tree's fixed value; see each configuration's `assumed`


def _rms_norm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + NORM_EPS) * scale


def _rotary(x, theta: float):
    """x [S, H, hd] at positions 0..S-1."""
    s, _, hd = x.shape
    inv_freq = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def logits_one_sequence(params, tokens, *, n_heads: int, rope_theta: float):
    """tokens [S] int32 -> logits [S, V], float32, one sequence."""
    f32 = jnp.float32
    x = params["embed"].astype(f32)[tokens]                   # [S, d]
    s, d = x.shape
    hd = d // n_heads
    causal = jnp.tril(jnp.ones((s, s), bool))

    def block(x, lp):
        lp = jax.tree.map(lambda a: a.astype(f32), lp)
        y = _rms_norm(x, lp["ln1"])
        q, k, v = (jnp.dot(y, lp["wqkv"][:, i, :]).reshape(s, n_heads, hd)
                   for i in range(3))
        q, k = _rotary(q, rope_theta), _rotary(k, rope_theta)
        scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(f32(hd))
        scores = jnp.where(causal[None], scores, -jnp.inf)
        attn = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
        x = x + jnp.dot(attn.reshape(s, d), lp["wo"])
        y = _rms_norm(x, lp["ln2"])
        gate, up = jnp.dot(y, lp["w13"][:, 0, :]), jnp.dot(y, lp["w13"][:, 1, :])
        return x + jnp.dot(jax.nn.silu(gate) * up, lp["w2"]), None

    x, _ = jax.lax.scan(block, x, params["layers"])
    x = _rms_norm(x, params["ln_f"].astype(f32))
    return jnp.dot(x, params["embed"].astype(f32).T)


def _highest(fn):
    def run(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)
    return run


def make_logits_fn(n_heads: int, rope_theta: float):
    """jitted (params, tokens [S]) -> logits [S, V]."""
    return jax.jit(_highest(lambda params, tokens: logits_one_sequence(
        params, tokens, n_heads=n_heads, rope_theta=rope_theta)))


def make_row_nll_fn(n_heads: int, rope_theta: float):
    """jitted (params, row [S+1]) -> summed next-token NLL of the row."""
    def row_nll(params, row):
        logits = logits_one_sequence(params, row[:-1], n_heads=n_heads,
                                     rope_theta=rope_theta)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, row[1:, None], axis=-1))
    return jax.jit(_highest(row_nll))


def lm_loss(params, tokens, *, n_heads: int, rope_theta: float) -> float:
    """Mean next-token NLL of a batch [B, S+1], one row at a time so that
    the float32 logits of one row are all that is ever held."""
    row_nll = make_row_nll_fn(n_heads, rope_theta)
    total = 0.0
    for i in range(tokens.shape[0]):
        total += float(row_nll(params, tokens[i]))
    return total / (tokens.shape[0] * (tokens.shape[1] - 1))
