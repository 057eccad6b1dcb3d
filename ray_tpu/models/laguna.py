"""A sparse decoder of window and global attention layers: a period of
one *full* (global, causal) layer and three *sliding* layers that see a
window of positions, with different numbers of query heads over the same
key/value heads, a sigmoid gate a head on the attention's output, two
rotary schemes, a dense MLP in the first layer and a sparse-expert layer
(softmax router over all experts, routed experts beside a shared one) in
every other. Pre-norm RMSNorm with a scale, residual adds, an untied
head.

- *Mixer of a layer* with ``H`` query heads (`heads_full` or
  `heads_sliding`): ``q = y W_q`` (``H`` x `head_dim`), ``k = y W_k``,
  ``v = y W_v`` (`n_kv_heads` x `head_dim`); q and k rotated
  (`ops/rotary.py`): a full layer over the first `rope_full["rot_dim"]`
  values of a head with YaRN frequencies and the factor on cos and sin, a
  sliding layer over `rope_sliding["rot_dim"]` with plain ones; causal
  softmax at ``1/sqrt(head_dim)``, query head ``i`` on key head ``i //
  (H / n_kv_heads)``, on a sliding layer only the keys ``j`` with ``i - j
  < window``; head ``h``'s output times ``sigmoid(y W_g)[h]``; ``W_o``.
- *Layer 0's MLP*: ``W_down(silu(W_gate y) * W_up y)`` at `dense_width`.
- *Experts* (`ops/experts.py`): softmax scores over all `n_experts`, the
  `top_k` largest, weights normalised over the chosen and scaled; the
  routed experts `experts_held` live here (a chip's share under expert
  parallelism) and the shared expert is whole.

This file holds the shapes and the seeded weights. The serving math is
`serve/engine/laguna_model.py`; there is no training path.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Tuple

import jax
import jax.numpy as jnp

SLIDING_PER_PERIOD = 3      # a period is one full layer and three sliding
LAYERS_PER_PERIOD = 1 + SLIDING_PER_PERIOD


@dataclass(frozen=True)
class LagunaConfig:
    vocab_size: int
    d_model: int
    n_periods: int
    heads_full: int              # query heads of a full layer
    heads_sliding: int           # and of a sliding layer
    n_kv_heads: int
    head_dim: int
    window: int                  # positions a sliding layer's query sees
    dense_width: int             # layer 0's MLP
    n_experts: int               # the router's width
    experts_held: Tuple[int, int]    # routed experts [lo, hi) held here
    top_k: int
    expert_width: int
    shared_width: int
    # rot_dim, theta, attention_factor and, for YaRN, yarn: {factor,
    # original_max_position_embeddings, beta_fast, beta_slow}.
    rope_full: dict = field(default_factory=dict)
    rope_sliding: dict = field(default_factory=dict)
    routed_scaling: float = 1.0
    router_scoring: str = "softmax"
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"      # weights and the operands of products

    @property
    def n_full_layers(self) -> int:
        return self.n_periods

    @property
    def n_sliding_layers(self) -> int:
        return self.n_periods * SLIDING_PER_PERIOD

    @property
    def n_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]


def init_params(key, cfg: LagunaConfig) -> dict:
    """Seeded weights: `periods`, a list of a tree a period (a layer's
    matrices are arrays of their own: a step slices no stack of them).
    A period: ``ln1``, ``ln2`` ``[4, d]``; ``full`` and ``sliding`` (a
    list of three), each ``{wq, wk, wv, wgate, wo}``; ``mlp``, a list of
    the four layers' feed-forward halves: ``{gate, up, down}`` for layer
    0 of the model, else ``{router, w_gate, w_up, w_down, shared_gate,
    shared_up, shared_down}``. Matrices in `cfg.dtype` at ``N(0,
    1/fan_in)``; norm scales and the router in float32."""
    f32, dt = jnp.float32, jnp.dtype(cfg.dtype)
    d = cfg.d_model
    kv_w = cfg.n_kv_heads * cfg.head_dim
    keys = iter(jax.random.split(key, 64 * cfg.n_periods + 8))

    def mat(*shape):
        w = jax.random.normal(next(keys), shape, f32)
        return (w * shape[-2] ** -0.5).astype(dt)

    def mixer(heads):
        return {"wq": mat(d, heads * cfg.head_dim), "wk": mat(d, kv_w),
                "wv": mat(d, kv_w), "wgate": mat(d, heads),
                "wo": mat(heads * cfg.head_dim, d)}

    def expert_layer():
        return {
            "router": jax.random.normal(
                next(keys), (d, cfg.n_experts), f32) * d ** -0.5,
            "w_gate": mat(cfg.n_held, d, cfg.expert_width),
            "w_up": mat(cfg.n_held, d, cfg.expert_width),
            "w_down": mat(cfg.n_held, cfg.expert_width, d),
            "shared_gate": mat(d, cfg.shared_width),
            "shared_up": mat(d, cfg.shared_width),
            "shared_down": mat(cfg.shared_width, d)}

    def dense_mlp():
        return {"gate": mat(d, cfg.dense_width),
                "up": mat(d, cfg.dense_width),
                "down": mat(cfg.dense_width, d)}

    def period(p):
        return {
            "ln1": jnp.ones((LAYERS_PER_PERIOD, d), f32),
            "ln2": jnp.ones((LAYERS_PER_PERIOD, d), f32),
            "full": mixer(cfg.heads_full),
            "sliding": [mixer(cfg.heads_sliding)
                        for _ in range(SLIDING_PER_PERIOD)],
            "mlp": [dense_mlp() if p == 0 and j == 0 else expert_layer()
                    for j in range(LAYERS_PER_PERIOD)]}

    return {
        "embed": jax.random.normal(next(keys), (cfg.vocab_size, d),
                                   f32).astype(dt),
        "head": mat(d, cfg.vocab_size),
        "ln_f": jnp.ones((d,), f32),
        "periods": [period(p) for p in range(cfg.n_periods)],
    }
