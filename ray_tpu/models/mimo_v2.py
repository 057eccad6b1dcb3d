"""A sparse decoder of window and global attention layers whose two layer
kinds differ in their key/value heads, not in their query heads: a
*global* (causal) layer on `kv_heads_global` key/value heads and a
*window* layer, which sees `window` positions, on `kv_heads_window`, all
under `n_heads` query heads; keys of `head_dim` values over values of
`v_head_dim`; a learned sink logit a query head in the window layers'
softmax; a dense MLP in the layers `dense_layers` names and a
sparse-expert layer (sigmoid router with a selection bias, no shared
expert) in every other. Pre-norm RMSNorm with a scale, residual adds, an
untied head, no bias, no gate, no norm on q or k.

- *Mixer of a layer* with ``Hkv`` key/value heads: ``q = y W_q`` (`n_heads`
  x `head_dim`), ``k = y W_k`` (``Hkv`` x `head_dim`), ``v = value_scale
  * (y W_v)`` (``Hkv`` x `v_head_dim`); the first `rot_dim` values of q
  and k rotated (`ops/rotary.py`, value ``i`` with value ``i + rot_dim /
  2``) at `theta_global` or `theta_window`; causal softmax at
  ``1/sqrt(head_dim)``, query head ``i`` on key head ``i // (n_heads /
  Hkv)``, in a window layer over the keys ``j`` with ``i - j < window``
  and one more column of the head's `sink` logit that carries no value;
  ``W_o`` from ``n_heads * v_head_dim``.
- *A dense layer's MLP*: ``W_down(silu(W_gate y) * W_up y)`` at
  `dense_width`.
- *Experts* (`ops/experts.py`): sigmoid scores over all `n_experts`, the
  `top_k` largest of score + `select_bias`, weights normalised over the
  chosen and scaled; the routed experts `experts_held` live here (a
  chip's share under expert parallelism).

This file holds the shapes and the seeded weights. The serving math is
`serve/engine/mimo_model.py`; there is no training path.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class MimoV2Config:
    vocab_size: int
    d_model: int
    n_heads: int                 # query heads of every layer
    head_dim: int                # a key's and a query's values
    v_head_dim: int              # a value's
    kv_heads_global: int
    kv_heads_window: int
    window: int                  # positions a window layer's query sees
    layer_is_window: Tuple[bool, ...]   # a layer: window (True) or global
    layer_is_dense: Tuple[bool, ...]    # a layer: dense MLP or experts
    dense_width: int
    n_experts: int               # the router's width
    experts_held: Tuple[int, int]    # routed experts [lo, hi) held here
    top_k: int
    expert_width: int
    rot_dim: int                 # values of a head that are rotated
    theta_global: float
    theta_window: float
    value_scale: float = 1.0
    routed_scaling: float = 1.0
    router_scoring: str = "sigmoid"
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"      # weights and the operands of products

    @property
    def n_layers(self) -> int:
        return len(self.layer_is_window)

    @property
    def n_window_layers(self) -> int:
        return sum(self.layer_is_window)

    @property
    def n_global_layers(self) -> int:
        return self.n_layers - self.n_window_layers

    @property
    def n_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    def kv_heads(self, window: bool) -> int:
        return self.kv_heads_window if window else self.kv_heads_global


def init_params(key, cfg: MimoV2Config) -> dict:
    """Seeded weights: `layers`, a list of a tree a layer (a layer's
    matrices are arrays of their own: a step slices no stack of them):
    ``ln1``, ``ln2`` ``[d]``; ``mixer``: ``{wq, wk, wv, wo}`` and, in a
    window layer, ``sink`` ``[n_heads]``; ``mlp``: ``{gate, up, down}``
    in a dense layer, else ``{router, select_bias, w_gate, w_up,
    w_down}``. Matrices in `cfg.dtype` at ``N(0, 1/fan_in)``; norm
    scales, the router, its selection bias (``N(0, 0.02)``: of the size
    of the gaps between a token's ranked scores, so that leaving it out
    changes what is chosen) and the sinks (``N(0, 1)``) in float32."""
    f32, dt = jnp.float32, jnp.dtype(cfg.dtype)
    d = cfg.d_model
    keys = iter(jax.random.split(key, 16 * cfg.n_layers + 8))

    def mat(*shape):
        w = jax.random.normal(next(keys), shape, f32)
        return (w * shape[-2] ** -0.5).astype(dt)

    def mixer(window: bool):
        hkv = cfg.kv_heads(window)
        tree = {"wq": mat(d, cfg.n_heads * cfg.head_dim),
                "wk": mat(d, hkv * cfg.head_dim),
                "wv": mat(d, hkv * cfg.v_head_dim),
                "wo": mat(cfg.n_heads * cfg.v_head_dim, d)}
        if window:
            tree["sink"] = jax.random.normal(next(keys), (cfg.n_heads,), f32)
        return tree

    def expert_layer():
        return {
            "router": jax.random.normal(
                next(keys), (d, cfg.n_experts), f32) * d ** -0.5,
            "select_bias": 0.02 * jax.random.normal(
                next(keys), (cfg.n_experts,), f32),
            "w_gate": mat(cfg.n_held, d, cfg.expert_width),
            "w_up": mat(cfg.n_held, d, cfg.expert_width),
            "w_down": mat(cfg.n_held, cfg.expert_width, d)}

    def dense_mlp():
        return {"gate": mat(d, cfg.dense_width),
                "up": mat(d, cfg.dense_width),
                "down": mat(cfg.dense_width, d)}

    def layer(window: bool, dense: bool):
        return {"ln1": jnp.ones((d,), f32), "ln2": jnp.ones((d,), f32),
                "mixer": mixer(window),
                "mlp": dense_mlp() if dense else expert_layer()}

    return {
        "embed": jax.random.normal(next(keys), (cfg.vocab_size, d),
                                   f32).astype(dt),
        "head": mat(d, cfg.vocab_size),
        "ln_f": jnp.ones((d,), f32),
        "layers": [layer(w, dn) for w, dn in zip(cfg.layer_is_window,
                                                 cfg.layer_is_dense)],
    }
