"""A sparse decoder whose attention selects its keys: every layer has
`n_heads` query heads over `n_kv_heads` key/value heads of `head_dim`
values with an RMSNorm a head on q and on k, a rotary in sections over
three position streams, and a learned *indexer* that picks the
`index_topk` earlier positions a query attends to; a sparse-expert layer
(softmax router, the `top_k` largest, weights normalised over the chosen,
no shared expert, no dense layer) in every layer. Pre-norm RMSNorm with a
scale, residual adds, an untied head, no bias.

- *Mixer*: ``q = rope(RMSNorm(y W_q))``, ``k = rope(RMSNorm(y W_k))``
  (the norm over a head's `head_dim` values, with a scale), ``v = y
  W_v``; `rope` turns a head's ``head_dim / 2`` pairs (value ``i`` with
  value ``i + head_dim / 2``) at base `rope_theta`, pair ``i`` by the
  position stream `mrope_section` gives it (`ops/rotary.py`,
  `rotary_cos_sin_sections`); softmax at ``1/sqrt(head_dim)`` over the
  selected positions, query head ``i`` on key head ``i // (n_heads /
  n_kv_heads)``; ``W_o``.
- *Indexer* (`ops/sparse_attention.py`): ``qi = rope(y W_qi)``
  (`index_heads` heads of `index_dim` values), ``ki = rope(LayerNorm(y
  W_ki))`` (one key of `index_dim` values a position; the norm has a
  scale and a bias), both turned whole on the temporal stream, ``w = (y
  W_w) * index_heads^-1/2 * index_dim^-1/2``; the score of key ``s`` for
  the query at ``t`` is ``sum_j w[t, j] relu(qi[t, j] . ki[s])``, and the
  query attends to the `index_topk` positions ``s <= t`` with the largest
  scores (all while ``t < index_topk``), its own position competing like
  any other.
- *Experts* (`ops/experts.py`): softmax scores over all `n_experts`, the
  `top_k` largest, weights normalised over the chosen; the routed
  experts `experts_held` live here (a chip's share under expert
  parallelism).

This file holds the shapes and the seeded weights. The serving math is
`serve/engine/keye_model.py`; there is no training path.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp


# The mean of the seeded head norms' scales (`init_params` says why 1).
QK_NORM_MEAN = 1.0


@dataclass(frozen=True)
class KeyeVL2Config:
    vocab_size: int
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rope_theta: float
    mrope_section: Tuple[int, ...]   # pairs of a head a position stream
    index_heads: int
    index_dim: int
    index_topk: int              # positions a query attends to
    n_experts: int               # the router's width
    experts_held: Tuple[int, int]    # routed experts [lo, hi) held here
    top_k: int
    expert_width: int
    routed_scaling: float = 1.0
    router_scoring: str = "softmax"
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"      # weights and the operands of products

    @property
    def n_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]


def init_params(key, cfg: KeyeVL2Config) -> dict:
    """Seeded weights: `layers`, a list of a tree a layer: ``ln1``,
    ``ln2`` ``[d]``; ``mixer``: ``{wq, wk, wv, wo, q_norm, k_norm}``;
    ``indexer``: ``{wq [d, J di], wk [d, di], ww [d, J], k_scale, k_bias
    [di]}``; ``mlp``: ``{router, w_gate, w_up, w_down}``. Matrices in
    `cfg.dtype` at ``N(0, 1/fan_in)``; the router and every norm's scale
    in float32. The head norms' scales are drawn ``N(m, 0.1 m)`` with
    ``m = QK_NORM_MEAN`` = 1: scores then spread about 1 wide. (At
    1.5, a peaked softmax, a key that falls on the other side of a
    query's threshold by rounding moves a layer's output enough to move
    the next layer's selection, and twelve layers on the engine's bf16
    operands stood 0.12-0.15 off the float32 reference at 8,448
    positions where they stand 0.008 at 1.0; attending to every causal
    key, or to half as many, moves the logits by ten times that at 1.0
    too: my chip runs, PR 57.) The index key's norm is drawn ``N(1,
    0.1)`` (scale) and ``N(0, 0.1)`` (bias), so that leaving it out
    shows."""
    f32, dt = jnp.float32, jnp.dtype(cfg.dtype)
    d = cfg.d_model
    keys = iter(jax.random.split(key, 20 * cfg.n_layers + 8))

    def mat(*shape):
        w = jax.random.normal(next(keys), shape, f32)
        return (w * shape[-2] ** -0.5).astype(dt)

    def around(mean, spread, n):
        return mean + spread * jax.random.normal(next(keys), (n,), f32)

    def layer():
        return {
            "ln1": jnp.ones((d,), f32), "ln2": jnp.ones((d,), f32),
            "mixer": {"wq": mat(d, cfg.n_heads * cfg.head_dim),
                      "wk": mat(d, cfg.n_kv_heads * cfg.head_dim),
                      "wv": mat(d, cfg.n_kv_heads * cfg.head_dim),
                      "wo": mat(cfg.n_heads * cfg.head_dim, d),
                      "q_norm": around(QK_NORM_MEAN, 0.1 * QK_NORM_MEAN,
                                       cfg.head_dim),
                      "k_norm": around(QK_NORM_MEAN, 0.1 * QK_NORM_MEAN,
                                       cfg.head_dim)},
            "indexer": {"wq": mat(d, cfg.index_heads * cfg.index_dim),
                        "wk": mat(d, cfg.index_dim),
                        "ww": mat(d, cfg.index_heads),
                        "k_scale": around(1.0, 0.1, cfg.index_dim),
                        "k_bias": around(0.0, 0.1, cfg.index_dim)},
            "mlp": {"router": jax.random.normal(
                        next(keys), (d, cfg.n_experts), f32) * d ** -0.5,
                    "w_gate": mat(cfg.n_held, d, cfg.expert_width),
                    "w_up": mat(cfg.n_held, d, cfg.expert_width),
                    "w_down": mat(cfg.n_held, cfg.expert_width, d)}}

    return {
        "embed": jax.random.normal(next(keys), (cfg.vocab_size, d),
                                   f32).astype(dt),
        "head": mat(d, cfg.vocab_size),
        "ln_f": jnp.ones((d,), f32),
        "layers": [layer() for _ in range(cfg.n_layers)],
    }
