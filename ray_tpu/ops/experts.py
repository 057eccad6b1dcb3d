"""A dropless sparse-expert layer that is told which experts it holds.

Expert parallelism gives each chip a range ``held = (lo, hi)`` of a
layer's routed experts. The router is whole on every chip: it scores all
the experts and picks a token's top k among them (`route`). The chip
then computes, for the tokens routed to its own experts, those experts'
part of the layer's result (`held_experts_ffn`); what the absent experts
would add is another chip's to compute, and nothing here stands in for
it or for the exchange. The shares of all chips add up to the whole
layer.

No capacity and no dropped token: the (token, expert) pairs that fall on
held experts are sorted by expert and laid out in row tiles, each tile
of one expert (an expert with no token gets no tile, one with many gets
several). A `lax.scan` over the tiles multiplies a tile's rows with its
expert's three matrices and adds the weighted result to the tokens' rows;
a tile that holds no pair skips its branch, so the weights of an expert
nobody chose are not read. The number of tiles is fixed by the shapes
(``ceil(T k / tile) + held``), whatever the imbalance.

`ops/moe.py` is the other expert layer of the tree: one-hot dispatch
with a capacity that drops tokens, for the training model, where the
expert axis is sharded and XLA makes the exchange.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def route(y, router_w, select_bias, top_k: int, scaling: float = 1.0,
          scoring: str = "sigmoid"):
    """y ``[T, d]``; router_w ``[d, E]``; select_bias ``[E]`` or None.
    Scores are ``sigmoid(y W)``, or with `scoring` "softmax" the softmax
    of ``y W`` over all ``E`` experts, in float32; the `top_k` largest
    of ``score + select_bias`` are chosen; a chosen expert's weight is
    its score over the chosen scores' sum, times `scaling`.
    Returns ``(experts [T, k] int32, weights [T, k] float32)``."""
    f32 = jnp.float32
    if scoring not in ("sigmoid", "softmax"):
        raise ValueError(f"a router scores by sigmoid or softmax, not "
                         f"{scoring!r}")
    logits = jnp.dot(y.astype(f32), router_w.astype(f32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = (jax.nn.sigmoid(logits) if scoring == "sigmoid"
              else jax.nn.softmax(logits, axis=-1))
    ranked = (scores if select_bias is None
              else scores + select_bias.astype(f32))
    _, experts = jax.lax.top_k(ranked, top_k)
    chosen = jnp.take_along_axis(scores, experts, axis=-1)
    weights = chosen / jnp.sum(chosen, axis=-1, keepdims=True) * scaling
    return experts.astype(jnp.int32), weights


def _tile_rows(tokens: int) -> int:
    """Rows of a tile: the whole (padded) batch of a decode step, so
    that an expert's weights are read once a step; 128 for a prompt."""
    tile = 8
    while tile < min(tokens, 128):
        tile *= 2
    return tile


def held_experts_ffn(y, experts, weights, w_gate, w_up, w_down,
                     held: Tuple[int, int], valid=None):
    """The held experts' part of a sparse-expert layer.

    y ``[T, d]``; experts, weights ``[T, k]`` from `route`; w_gate, w_up
    ``[hi - lo, d, f]`` and w_down ``[hi - lo, f, d]``, the matrices of
    experts ``lo .. hi - 1``; valid ``[T]`` bool (rows of a padded batch
    that are no sequence route nowhere). An expert is
    ``W_down(silu(W_gate y) * W_up y)``; products take their operands in
    the weights' dtype and accumulate in float32.

    Returns ``(out [T, d] float32, load [hi - lo] int32)``: the weighted
    sum over a token's chosen experts that are held here, and the pairs
    that fell on each held expert."""
    f32 = jnp.float32
    t, d = y.shape
    k = experts.shape[1]
    lo, hi = held
    n_held = hi - lo
    here = (experts >= lo) & (experts < hi)
    if valid is not None:
        here &= valid[:, None]
    # Pairs, sorted by held expert; pairs for absent experts sort last.
    pair_expert = jnp.where(here, experts - lo, n_held).reshape(-1)
    order = jnp.argsort(pair_expert, stable=True)
    pair_expert = pair_expert[order]
    pair_token = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)[order]
    pair_weight = weights.reshape(-1)[order]
    load = jnp.zeros((n_held + 1,), jnp.int32).at[pair_expert].add(
        1)[:n_held]
    # Tiles: expert e takes ceil(load[e] / tile) of them, in order.
    tile = _tile_rows(t)
    n_tiles = -(-t * k // tile) + n_held
    rows = n_tiles * tile
    tiles_of = (load + tile - 1) // tile
    tiles_end = jnp.cumsum(tiles_of)
    first_row = (tiles_end - tiles_of) * tile
    first_pair = jnp.cumsum(load) - load
    at = jnp.minimum(pair_expert, n_held - 1)
    row = jnp.where(pair_expert < n_held,
                    first_row[at] + jnp.arange(t * k) - first_pair[at],
                    rows)                       # absent: dropped below
    row_token = jnp.full((rows,), t, jnp.int32).at[row].set(
        pair_token, mode="drop").reshape(n_tiles, tile)
    row_weight = jnp.zeros((rows,), f32).at[row].set(
        pair_weight, mode="drop").reshape(n_tiles, tile)
    tile_expert = jnp.searchsorted(tiles_end, jnp.arange(n_tiles),
                                   side="right").astype(jnp.int32)
    # Row t of the input and of the sum is padding: empty tile rows
    # read zeros from it and add zeros to it.
    y_ext = jnp.concatenate([y, jnp.zeros((1, d), y.dtype)])
    act = w_gate.dtype

    def one_tile(acc, xs):
        expert, tokens, gates = xs

        def compute(acc):
            x = y_ext[tokens].astype(act)
            gate = jnp.dot(x, w_gate[expert], preferred_element_type=f32)
            up = jnp.dot(x, w_up[expert], preferred_element_type=f32)
            out = jnp.dot((jax.nn.silu(gate) * up).astype(act),
                          w_down[expert], preferred_element_type=f32)
            return acc.at[tokens].add(out * gates[:, None])

        return jax.lax.cond(expert < n_held, compute, lambda acc: acc,
                            acc), None

    acc, _ = jax.lax.scan(
        one_tile, jnp.zeros((t + 1, d), f32),
        (tile_expert, row_token, row_weight))
    return acc[:t], load
