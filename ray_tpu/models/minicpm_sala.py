"""A dense decoder of two kinds of layer (`mixer_types`): attention that
selects its key BLOCKS from compressed keys of the cache itself, beside
lightning linear-attention layers of one constant decay a head; a plain
SwiGLU in every layer, muP scalings on the embedding, the residual adds
and the head, an untied head.

- *Norm*: ``N_w(x) = x / sqrt(mean(x^2) + eps) * w``.
- *Stream*: ``x_0 = scale_emb * E[token]``; a layer is ``x = x + a
  Mixer(N(x))``, ``x = x + a MLP(N(x))`` with ``a = scale_depth /
  sqrt(published_layers)`` (the PUBLISHED depth, whatever the depth held
  here); ``logits = W_head(N(x) / (d / dim_model_base))``.
- *Block-sparse attention layer* (``minicpm4``): `n_heads` query heads
  over `n_kv_heads` key/value heads, an RMSNorm a head on q and k, NO
  rotary: the layer has no position signal but the causal mask and its
  selection. Key/value head ``g`` keeps, beside its keys, their
  *compressed keys* ``c_{g,j} = mean(k_{g, stride j .. stride j + kernel
  - 1})`` (whole kernels only). A query scores them (``softmax_j(q_h .
  c_{g,j} / sqrt(hd))``, summed over the group's heads), a block of
  `sparse_block` positions takes the largest score of the kernels that
  overlap it, and the query attends to the first `init_blocks` blocks,
  the blocks of its last `window_size` positions and the `topk` blocks of
  the rest with the largest scores; while it stands before `dense_len`,
  to every block (`ops/block_sparse_attention.py`). A sigmoid gate a
  value from the sublayer's input on the heads' outputs, then ``W_o``.
- *Lightning layer* (``lightning-attn``): `lightning_heads` heads of q, k
  and v, an RMSNorm a head on q and k, rotary over the whole head
  (halves rotated); ``S_t = lambda S_{t-1} + k_t v_t^T``, ``o_t = S_t^T
  q_t / sqrt(hd)`` (`ops/lightning_attention.py`), ``lambda = exp(-s_h
  f_l)`` a head and layer with ``s_h = 2^(-8 (h + 1) / H)`` and ``f_l = 1
  - l / (published_layers - 1) + 1e-5``, ``l`` the PUBLISHED index of the
  layer (`layer_offset` + its index here); ``W_o(N_head(o) * sigmoid(y
  W_g))``.
- *MLP*: ``W_2(silu(y W_1) * (y W_3))``.

This file holds the shapes and the seeded weights. The serving math is
`serve/engine/minicpm_sala_model.py`; there is no training path.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"
_LANES = 128


@dataclass(frozen=True)
class MiniCPMSALAConfig:
    vocab_size: int
    d_model: int
    mixer_types: Tuple[str, ...]     # a layer's kind, in order
    n_heads: int                     # query heads of a sparse layer
    n_kv_heads: int
    head_dim: int
    lightning_heads: int
    lightning_head_dim: int
    dense_width: int
    layer_offset: int = 0            # the published index of layer 0
    published_layers: int = 32
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    # The selection (`sparse_config` of the family).
    kernel_size: int = 32
    kernel_stride: int = 16
    sparse_block: int = 64
    init_blocks: int = 1
    window_size: int = 2048
    topk: int = 64
    dense_len: int = 8192
    dtype: str = "bfloat16"          # weights and the operands of products

    @property
    def n_layers(self) -> int:
        return len(self.mixer_types)

    @property
    def sparse_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, kind in enumerate(self.mixer_types)
                     if kind == SPARSE)

    @property
    def n_sparse_layers(self) -> int:
        return len(self.sparse_layers)

    @property
    def n_lightning_layers(self) -> int:
        return self.n_layers - self.n_sparse_layers

    @property
    def vocab_padded(self) -> int:
        """The head's columns and the embedding's rows: whole lanes."""
        return -(-self.vocab_size // _LANES) * _LANES

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / math.sqrt(self.published_layers)

    @property
    def logit_divisor(self) -> float:
        return self.d_model / self.dim_model_base

    def log_decays(self):
        """``log(lambda)`` of the lightning layers held here, ``[lightning
        layers, H]`` float32 (a tuple of tuples: a constant of the
        programs)."""
        h = self.lightning_heads
        slopes = [2.0 ** (-8.0 * (i + 1) / h) for i in range(h)]
        rows = []
        for i, kind in enumerate(self.mixer_types):
            if kind == LIGHTNING:
                f = (1.0 - (self.layer_offset + i)
                     / (self.published_layers - 1) + 1e-5)
                rows.append(tuple(-s * f for s in slopes))
        return tuple(rows)


def init_params(key, cfg: MiniCPMSALAConfig) -> dict:
    """Seeded weights: `layers`, a list of a tree a layer. A layer:
    ``ln1``, ``ln2`` ``[d]``; ``mixer``, a sparse layer's ``{wq, wk, wv,
    q_norm, k_norm, wgate, wo}`` or a lightning layer's ``{wq, wk, wv,
    q_norm, k_norm, onorm, wgate, wo}``; ``mlp`` ``{gate, up, down}``.
    Matrices in `cfg.dtype` at ``N(0, 1/fan_in)``, the embedding at
    ``N(0, 1/scale_emb^2)`` (``x_0`` then has unit size, as a trained
    muP embedding's); norm scales float32 at ``N(1, 0.1)`` (the scale's
    path is exercised). The embedding's rows
    and the head's columns past `vocab_size` (up to whole lanes) are
    zeros: no id names them and the model masks them out of its logits."""
    f32, dt = jnp.float32, jnp.dtype(cfg.dtype)
    d, hd = cfg.d_model, cfg.head_dim
    keys = iter(jax.random.split(key, 16 * cfg.n_layers + 8))
    fill = cfg.vocab_padded - cfg.vocab_size

    def mat(*shape):
        w = jax.random.normal(next(keys), shape, f32)
        return (w * shape[-2] ** -0.5).astype(dt)

    def norm(n):
        return 1.0 + 0.1 * jax.random.normal(next(keys), (n,), f32)

    def sparse():
        return {"wq": mat(d, cfg.n_heads * hd),
                "wk": mat(d, cfg.n_kv_heads * hd),
                "wv": mat(d, cfg.n_kv_heads * hd),
                "q_norm": norm(hd), "k_norm": norm(hd),
                "wgate": mat(d, cfg.n_heads * hd),
                "wo": mat(cfg.n_heads * hd, d)}

    def lightning():
        w = cfg.lightning_heads * cfg.lightning_head_dim
        return {"wq": mat(d, w), "wk": mat(d, w), "wv": mat(d, w),
                "q_norm": norm(cfg.lightning_head_dim),
                "k_norm": norm(cfg.lightning_head_dim),
                "onorm": norm(cfg.lightning_head_dim),
                "wgate": mat(d, w), "wo": mat(w, d)}

    def layer(kind):
        return {"ln1": norm(d), "ln2": norm(d),
                "mixer": sparse() if kind == SPARSE else lightning(),
                "mlp": {"gate": mat(d, cfg.dense_width),
                        "up": mat(d, cfg.dense_width),
                        "down": mat(cfg.dense_width, d)}}

    # A muP embedding is of size 1 / scale_emb: x_0 has unit size.
    embed = jax.random.normal(next(keys), (cfg.vocab_size, d),
                              f32) / cfg.scale_emb
    return {
        "embed": jnp.pad(embed.astype(dt), ((0, fill), (0, 0))),
        "head": jnp.pad(mat(d, cfg.vocab_size), ((0, 0), (0, fill))),
        "ln_f": norm(d),
        "layers": [layer(kind) for kind in cfg.mixer_types],
    }
