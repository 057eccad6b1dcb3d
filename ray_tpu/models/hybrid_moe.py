"""A hybrid sparse decoder: softmax attention with grouped heads in one
layer of a period, gated delta-rule linear attention in the others, and
a sparse-expert layer (routed experts beside a shared one) in every
layer. Pre-norm RMSNorm with a scale, residual adds, an untied head.

A period of the layer pattern is ``[GQA, KDA, KDA, KDA]``:

- *GQA*: `n_heads` query heads over `n_kv_heads` key/value heads, no
  positional rotation, causal softmax at ``1/sqrt(head_dim)``, an output
  gate ``W_o(attn * sigmoid(W_gate y))``.
- *KDA*: ``q, k, v = W y`` through a causal depthwise convolution of
  `conv_kernel` taps and a silu; q and k l2-normalised a head (q also
  over ``sqrt(dk)``); a decay a head and channel ``g = -exp(A_log) *
  softplus(W_f2 W_f1 y + dt_bias)``; ``beta = 2 sigmoid(W_b y)``; the
  delta rule (`ops/delta_rule.py`); then ``W_o(rmsnorm_head(o) *
  sigmoid(W_g2 W_g1 y))``.
- *Experts* (`ops/experts.py`): sigmoid scores over all `n_experts`, the
  top `top_k` of score + selection bias, weights normalised over the
  chosen; the routed experts `experts_held` live here (a chip's share
  under expert parallelism) and the shared expert is whole.

This file holds the shapes and the seeded weights. The serving math is
`serve/engine/hybrid_model.py`; there is no training path.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp

KDA_PER_PERIOD = 3          # a period is one GQA layer and three KDA
LAYERS_PER_PERIOD = 1 + KDA_PER_PERIOD


@dataclass(frozen=True)
class HybridConfig:
    vocab_size: int
    d_model: int
    n_periods: int
    n_heads: int                 # GQA query heads
    n_kv_heads: int
    head_dim: int
    kda_heads: int
    kda_head_dim: int            # dk = dv
    gate_rank: int               # the two low-rank gate paths of a KDA
    n_experts: int               # the router's width
    experts_held: Tuple[int, int]    # routed experts [lo, hi) held here
    top_k: int
    expert_width: int
    shared_width: int
    conv_kernel: int = 4
    routed_scaling: float = 1.0
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"      # weights and the operands of products

    @property
    def n_kda_layers(self) -> int:
        return self.n_periods * KDA_PER_PERIOD

    @property
    def kda_width(self) -> int:
        return self.kda_heads * self.kda_head_dim

    @property
    def n_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]


def init_params(key, cfg: HybridConfig) -> dict:
    """Seeded weights: every leaf stacked by period ``[P, ...]``, a
    period's three KDA layers and four expert layers as lists of such
    trees (a layer's matrices are arrays of their own: a step slices no
    stack of them). Matrices in `cfg.dtype` at ``N(0, 1/fan_in)``; norm
    scales, the router, its selection bias, the decay's `a_log` and
    `dt_bias` in float32. The decay is seeded as this kind of layer
    seeds it: ``exp(a_log)`` uniform in 1-16 a head, ``softplus(dt_bias)``
    log-uniform in 0.001-0.1 a channel, the gate path ``W_f2 W_f1 y`` at
    a std of 0.3 (what an init of 0.02 gives it at these widths): from
    0.001 to several a step, a chunk's sum from under 1 to hundreds. The
    selection bias is non-zero so that its path is exercised, and small
    (std 0.002): random router weights already level the experts' load,
    and a bias of 0.01 moves an expert's share of the tokens by a
    quarter, so that the share of a step's choices that falls on the
    held experts changed with the seed (12.3-14.7%)."""
    f32, dt = jnp.float32, jnp.dtype(cfg.dtype)
    d, p = cfg.d_model, cfg.n_periods
    kw, r = cfg.kda_width, cfg.gate_rank
    q_w, kv_w = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    keys = iter(jax.random.split(key, 128))

    def mat(*shape, fan_in=None, scale=1.0):
        fan_in = fan_in or shape[-2]
        w = jax.random.normal(next(keys), shape, f32)
        return (w * (scale / fan_in ** 0.5)).astype(dt)

    def kda_layer():
        step = jnp.exp(jax.random.uniform(
            next(keys), (p, kw), f32, jnp.log(0.001), jnp.log(0.1)))
        return {
            "wq": mat(p, d, kw), "wk": mat(p, d, kw), "wv": mat(p, d, kw),
            # Depthwise taps for q, k and v side by side: [taps, 3 kw].
            "conv": jax.random.normal(
                next(keys), (p, cfg.conv_kernel, 3 * kw), f32)
            * cfg.conv_kernel ** -0.5,
            "wf1": mat(p, d, r), "wf2": mat(p, r, kw, scale=0.3),
            "a_log": jnp.log(jax.random.uniform(
                next(keys), (p, cfg.kda_heads), f32, 1.0, 16.0)),
            "dt_bias": jnp.log(jnp.expm1(step)),     # softplus^-1
            "wb": mat(p, d, cfg.kda_heads),
            "wg1": mat(p, d, r), "wg2": mat(p, r, kw),
            "onorm": jnp.ones((p, cfg.kda_head_dim), f32),
            "wo": mat(p, kw, d),
        }

    def expert_layer():
        return {
            "router": jax.random.normal(
                next(keys), (p, d, cfg.n_experts), f32) * d ** -0.5,
            "select_bias": 0.002 * jax.random.normal(
                next(keys), (p, cfg.n_experts), f32),
            "w_gate": mat(p, cfg.n_held, d, cfg.expert_width),
            "w_up": mat(p, cfg.n_held, d, cfg.expert_width),
            "w_down": mat(p, cfg.n_held, cfg.expert_width, d),
            "shared_gate": mat(p, d, cfg.shared_width),
            "shared_up": mat(p, d, cfg.shared_width),
            "shared_down": mat(p, cfg.shared_width, d),
        }

    return {
        "embed": jax.random.normal(next(keys), (cfg.vocab_size, d),
                                   f32).astype(dt),
        "head": mat(d, cfg.vocab_size),
        "ln_f": jnp.ones((d,), f32),
        "ln1": jnp.ones((p, LAYERS_PER_PERIOD, d), f32),
        "ln2": jnp.ones((p, LAYERS_PER_PERIOD, d), f32),
        "gqa": {
            "wq": mat(p, d, q_w), "wk": mat(p, d, kv_w),
            "wv": mat(p, d, kv_w), "wgate": mat(p, d, q_w),
            "wo": mat(p, q_w, d),
        },
        "kda": [kda_layer() for _ in range(KDA_PER_PERIOD)],
        "moe": [expert_layer() for _ in range(LAYERS_PER_PERIOD)],
    }
