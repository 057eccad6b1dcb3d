"""A sparse decoder of latent attention and gated delta-rule layers: one
layer in four attends by multi-head *latent* attention (MLA: a position
keeps one compressed latent and one rotary key for all heads), the other
three are Gated DeltaNet layers (a recurrent state a value head, one
scalar decay a head), a dense MLP in the leading layers and a
sparse-expert layer (sigmoid router over all experts, routed experts
beside an ungated shared one) in every other. Norms before and after
each sublayer (`pre_post`), zero-centred gated scales, an untied head.

- *Norm*: ``N_w(x) = x / sqrt(mean(x^2) + eps) * 2 sigmoid(w)``, ``w`` a
  learned vector that is 0 at a scale of 1.
- *Block*: ``x = x + N_post(Mixer(N_pre(x)))``, then ``x = x +
  N_post'(FFN(N_pre'(x)))``; a final norm before the head.
- *MLA layer*: ``c_q = N(y W_dq)``; ``[q_nope | q_r]_h = c_q W_uq``;
  ``[c_kv | k_r] = y W_dkv``, ``c_kv = N(c_kv)``; rotary (interleaved
  pairs, YaRN frequencies) on ``q_r`` a head and on ``k_r``, one key for
  all heads. **A position's cache row is ``[c_kv, k_r]``**, `kv_lora_rank`
  + `rope_dim` values, once. Keys and values a head come out of the
  latent (``W_uk``, ``W_uv``: `ops/latent_attention.py`, which holds
  both forms of the attention); causal softmax at ``(nope_dim +
  rope_dim)^-0.5 * mscale^2``; a sigmoid gate a value from the
  sublayer's input on the heads' outputs; ``W_o``.
- *Gated DeltaNet layer*: ``[q | k | v] = silu(conv(y W_qkv))`` (causal,
  depthwise, `conv_kernel` taps, no bias; `gdn_key_heads` heads of q and
  k under `gdn_heads` of v); q and k l2-normalised a head (q also over
  ``sqrt(dk)``), a key head serving ``gdn_heads / gdn_key_heads`` value
  heads; ``beta = sigmoid(y W_b)`` and ``g = -exp(A_log) softplus(y W_a +
  dt_bias)``, one scalar a value head; the delta rule
  (`ops/delta_rule.py`); ``W_o(N_head(o) * 2 sigmoid(y W_z))``.
- *FFN*: ``W_2(silu(min(a, limit)) * clip(b, -limit, limit))``, ``[a |
  b] = y W_13``. Experts (`ops/experts.py`): sigmoid scores over all
  `n_experts`, the top `top_k` of score + selection bias, weights
  normalised over the chosen and scaled; the routed experts
  `experts_held` live here (a chip's share under expert parallelism)
  and the shared expert is whole.

This file holds the shapes and the seeded weights. The serving math is
`serve/engine/gigachat_model.py`; there is no training path.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Tuple

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class GigaChat35Config:
    vocab_size: int
    d_model: int
    n_layers: int
    mla_layers: Tuple[int, ...]      # the layers that attend; the rest GDN
    n_dense_layers: int              # leading layers with a dense MLP
    n_heads: int                     # MLA query heads
    q_rank: int                      # q_lora_rank
    kv_rank: int                     # kv_lora_rank: the latent
    nope_dim: int                    # qk_nope_head_dim
    rope_dim: int                    # qk_rope_head_dim
    v_dim: int                       # v_head_dim
    gdn_heads: int                   # value heads of a GDN layer
    gdn_key_heads: int
    gdn_head_dim: int                # dk = dv
    dense_width: int
    n_experts: int                   # the router's width
    experts_held: Tuple[int, int]    # routed experts [lo, hi) held here
    top_k: int
    expert_width: int
    shared_width: int
    conv_kernel: int = 4
    routed_scaling: float = 1.0
    swiglu_limit: float = 10.0
    rope_theta: float = 100000.0
    # {factor, original_max_position_embeddings, beta_fast, beta_slow,
    # mscale_all_dim}; empty: plain frequencies, no scaling of the scores.
    yarn: dict = field(default_factory=dict)
    norm_eps: float = 1e-6
    o_norm_eps: float = 1e-6
    dtype: str = "bfloat16"          # weights and the operands of products

    @property
    def n_mla_layers(self) -> int:
        return len(self.mla_layers)

    @property
    def n_gdn_layers(self) -> int:
        return self.n_layers - len(self.mla_layers)

    @property
    def gdn_width(self) -> int:      # the value heads' width
        return self.gdn_heads * self.gdn_head_dim

    @property
    def gdn_key_width(self) -> int:
        return self.gdn_key_heads * self.gdn_head_dim

    @property
    def gdn_conv_width(self) -> int:     # q, k and v side by side
        return 2 * self.gdn_key_width + self.gdn_width

    @property
    def n_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    @property
    def latent_width(self) -> int:       # a position's cache row
        return self.kv_rank + self.rope_dim

    @property
    def softmax_scale(self) -> float:
        """``(nope + rope)^-0.5 * mscale^2``, ``mscale = 0.1 mscale_all_dim
        ln(factor) + 1`` under YaRN (the `deepseek_v3` convention)."""
        scale = (self.nope_dim + self.rope_dim) ** -0.5
        if not self.yarn or self.yarn["factor"] <= 1:
            return scale
        m = (0.1 * self.yarn.get("mscale_all_dim", 1.0)
             * math.log(self.yarn["factor"]) + 1.0)
        return scale * m * m


def init_params(key, cfg: GigaChat35Config) -> dict:
    """Seeded weights: `layers`, a list of a tree a layer (a layer's
    matrices are arrays of their own). A layer: the four norm vectors
    ``ln1``, ``ln1_post``, ``ln2``, ``ln2_post`` ``[d]``; ``mixer``, an
    MLA layer's ``{wdq, q_norm, wuq, wdkv, kv_norm, wuk, wuv, wgate,
    wo}`` or a GDN layer's ``{wqkv, conv, wa, a_log, dt_bias, wb, wz,
    onorm, wo}``; ``mlp``, ``{gate, up, down}`` in a leading layer, else
    ``{router, select_bias, w_gate, w_up, w_down, shared_gate, shared_up,
    shared_down}``. Matrices in `cfg.dtype` at ``N(0, 1/fan_in)``; norm
    vectors at ``N(0, 0.1)`` (zero is a scale of 1: the gate's path is
    exercised), the router, its selection bias and the decay's `a_log`
    and `dt_bias` in float32. The decay is seeded as
    `models/hybrid_moe.py` seeds it, a head where that has a channel:
    ``exp(a_log)`` uniform in 1-16, ``softplus(dt_bias)`` log-uniform in
    0.001-0.1, the gate path ``W_a y`` at a std of 0.3."""
    f32, dt = jnp.float32, jnp.dtype(cfg.dtype)
    d, h = cfg.d_model, cfg.n_heads
    keys = iter(jax.random.split(key, 32 * cfg.n_layers + 8))

    def mat(*shape, scale=1.0):
        w = jax.random.normal(next(keys), shape, f32)
        return (w * (scale * shape[-2] ** -0.5)).astype(dt)

    def norm(n):
        return 0.1 * jax.random.normal(next(keys), (n,), f32)

    def mla():
        return {"wdq": mat(d, cfg.q_rank), "q_norm": norm(cfg.q_rank),
                "wuq": mat(cfg.q_rank, h * (cfg.nope_dim + cfg.rope_dim)),
                "wdkv": mat(d, cfg.latent_width),
                "kv_norm": norm(cfg.kv_rank),
                "wuk": mat(cfg.kv_rank, h * cfg.nope_dim),
                "wuv": mat(cfg.kv_rank, h * cfg.v_dim),
                "wgate": mat(d, h * cfg.v_dim),
                "wo": mat(h * cfg.v_dim, d)}

    def gdn():
        step = jnp.exp(jax.random.uniform(
            next(keys), (cfg.gdn_heads,), f32, jnp.log(0.001), jnp.log(0.1)))
        return {"wqkv": mat(d, cfg.gdn_conv_width),
                # Depthwise taps for q, k and v side by side.
                "conv": jax.random.normal(
                    next(keys), (cfg.conv_kernel, cfg.gdn_conv_width), f32)
                * cfg.conv_kernel ** -0.5,
                "wa": mat(d, cfg.gdn_heads, scale=0.3),
                "a_log": jnp.log(jax.random.uniform(
                    next(keys), (cfg.gdn_heads,), f32, 1.0, 16.0)),
                "dt_bias": jnp.log(jnp.expm1(step)),     # softplus^-1
                "wb": mat(d, cfg.gdn_heads),
                "wz": mat(d, cfg.gdn_width),
                "onorm": norm(cfg.gdn_head_dim),
                "wo": mat(cfg.gdn_width, d)}

    def dense_mlp():
        return {"gate": mat(d, cfg.dense_width),
                "up": mat(d, cfg.dense_width),
                "down": mat(cfg.dense_width, d)}

    def expert_layer():
        return {
            "router": jax.random.normal(
                next(keys), (d, cfg.n_experts), f32) * d ** -0.5,
            "select_bias": 0.002 * jax.random.normal(
                next(keys), (cfg.n_experts,), f32),
            "w_gate": mat(cfg.n_held, d, cfg.expert_width),
            "w_up": mat(cfg.n_held, d, cfg.expert_width),
            "w_down": mat(cfg.n_held, cfg.expert_width, d),
            "shared_gate": mat(d, cfg.shared_width),
            "shared_up": mat(d, cfg.shared_width),
            "shared_down": mat(cfg.shared_width, d)}

    def layer(i):
        return {"ln1": norm(d), "ln1_post": norm(d), "ln2": norm(d),
                "ln2_post": norm(d),
                "mixer": mla() if i in cfg.mla_layers else gdn(),
                "mlp": (dense_mlp() if i < cfg.n_dense_layers
                        else expert_layer())}

    return {
        "embed": jax.random.normal(next(keys), (cfg.vocab_size, d),
                                   f32).astype(dt),
        "head": mat(d, cfg.vocab_size),
        "ln_f": norm(d),
        "layers": [layer(i) for i in range(cfg.n_layers)],
    }
