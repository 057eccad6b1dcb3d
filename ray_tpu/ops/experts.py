"""A dropless sparse-expert layer that is told which experts it holds.

Expert parallelism gives each chip a range ``held = (lo, hi)`` of a
layer's routed experts. The router is whole on every chip: it scores all
the experts and picks a token's top k among them (`route`). The chip
then computes, for the tokens routed to its own experts, those experts'
part of the layer's result (`held_experts_ffn`); what the absent experts
would add is another chip's to compute, and nothing here stands in for
it or for the exchange. The shares of all chips add up to the whole
layer.

No capacity and no dropped token. Two bodies, one result:

- The scan, wherever the kernel is not `kernel_eligible` (off the chip,
  a prompt of more rows than a tile, the unit tests' widths), and the
  kernel's twin in the tests: the (token, expert) pairs that fall on held
  experts are sorted by expert and laid out in row tiles, each tile of
  one expert (an expert with no token gets no tile, one with many gets
  several). A `lax.scan` over the tiles multiplies a tile's rows with its
  expert's three matrices and adds the weighted result to the tokens'
  rows; a tile that holds no pair skips its branch, so the weights of an
  expert nobody chose are not read. The number of tiles is fixed by the
  shapes (``ceil(T k / tile) + held``), whatever the imbalance.
- `grouped_ffn_kernel`, on the chip for a batch that is one tile (a
  decode step's rows): one Pallas call over the grid ``(tiles, f /
  block)``, a tile an expert that has a pair. A token chooses an expert
  once, so every such expert has one tile, and its tile is the whole
  batch (16 rows are the matrix unit's fewest either way) under a row's
  weight in it, 0 where the row did not choose it: nothing is sorted,
  gathered or scattered. The tile -> expert table is scalar-prefetched
  and the three weight stacks' index maps pick ``(tile_expert[i], block
  j)``, so the pipeline's double buffering brings the next block of an
  expert's matrices (or the next expert's first) while this one is
  multiplied: the overlap the scan's branches forbid. The ``[tile, d]``
  float32 sum stays in VMEM over the whole grid. A tile without a pair
  names the block before it (no fetch) and skips its products. In a
  device trace it is `held_experts_ffn_decode`.

`ops/moe.py` is the other expert layer of the tree: one-hot dispatch
with a capacity that drops tokens, for the training model, where the
expert axis is sharded and XLA makes the exchange.
"""

from __future__ import annotations

import functools
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


# Rows of a tile at the most: a decode step's (padded) batch is one tile,
# a longer prompt several.
_ROWS_MOST = 128
# The kernel's weight blocks, three of them and two deep, may take this
# much of the chip's VMEM (`f_block`).
_VMEM_FOR_WEIGHTS = 48 << 20


def _tile_rows(tokens: int, least: int = 8) -> int:
    """Rows of a tile: the whole (padded) batch of a decode step, so
    that an expert's weights are read once a step; 128 for a prompt; no
    fewer than `least`."""
    tile = least
    while tile < min(tokens, _ROWS_MOST):
        tile *= 2
    return tile


def kernel_eligible(tokens: int, d: int, f: int, dtype) -> bool:
    """Whether `held_experts_ffn` runs `grouped_ffn_kernel`, from what it
    can see: the TPU backend, a batch that is one tile (a decode step's
    rows, a short prompt's), bfloat16 or float32 weights, and weight
    blocks of whole lanes (``d`` and ``f`` multiples of 128). Elsewhere
    (off the chip, a longer prompt, the unit tests' widths) the scan
    runs. A prompt of several tiles an expert keeps the scan because a
    kernel over its sorted rows lost to it on the chip (PERF.md, PR 41
    and PR 42: XLA's gather before and scatter-add after cover every row
    the shapes must allow, eight times the rows that are live)."""
    return (jax.default_backend() == "tpu" and tokens <= _ROWS_MOST
            and d % 128 == 0 and f % 128 == 0
            and jnp.dtype(dtype) in (jnp.bfloat16, jnp.float32))


def f_block(d: int, f: int, itemsize: int) -> int:
    """Columns of ``w_gate`` and ``w_up`` (rows of ``w_down``) a grid
    step of the kernel brings: the largest multiple of 128 that divides
    `f` whose three blocks ``[d, block]``, two deep, fit
    `_VMEM_FOR_WEIGHTS`; the whole of `f` where there is none (no shape
    `kernel_eligible` lets through). The whole of 1,024 at
    ``d`` 3,072 in bfloat16, 640 of 1,280 at 4,096: fewer, larger blocks
    were no slower than blocks of 256 or 512 at either (PERF.md, PR
    41)."""
    most = _VMEM_FOR_WEIGHTS // (2 * 3 * d * itemsize)
    return max((b for b in range(128, min(f, most) + 1, 128) if f % b == 0),
               default=f)


def _ffn_body(expert_ref, live_ref, x_ref, share_ref, gate_ref, up_ref,
              down_ref, o_ref):
    """One grid step: the batch's rows against block ``j`` of tile
    ``i``'s expert, weighted by each row's share in that expert and
    added to the ``[tile, d]`` float32 output block, which stays in VMEM
    over the whole grid."""
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _first_step():
        o_ref[...] = jnp.zeros(o_ref.shape, f32)

    @pl.when(i < live_ref[0])
    def _a_touched_expert():
        x = x_ref[...]
        gate = jnp.dot(x, gate_ref[...], preferred_element_type=f32)
        up = jnp.dot(x, up_ref[...], preferred_element_type=f32)
        out = jnp.dot((jax.nn.silu(gate) * up).astype(x.dtype),
                      down_ref[...], preferred_element_type=f32)
        o_ref[...] += out * share_ref[...]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def grouped_ffn_kernel(x, tile_expert, share, w_gate, w_up, w_down, *,
                       block: int = None, interpret: bool = False):
    """x ``[tile, d]`` in the weights' dtype, a batch that is one tile;
    tile_expert ``[n_tiles]`` int32, the experts with a pair in ascending
    order, then ``n_held``; share ``[n_tiles, tile, 1]`` float32, each
    row's weight in its tile's expert (0 where the row did not choose
    it); w_gate, w_up ``[n_held, d, f]``, w_down ``[n_held, f, d]``.
    Returns ``[tile, d]`` float32: the sum over the tiles of ``share x
    W_down(silu(W_gate x) * W_up x)``.

    A Pallas TPU kernel over the grid ``(tiles, f / block)``: the tile ->
    expert table and the count of live tiles are scalar-prefetched and
    the weight operands' index maps pick ``(tile_expert[i], block j)``,
    so the pipeline fetches the next block of the three matrices, or the
    next expert's first, while this one is multiplied. A tile without a
    pair names the block the last live tile ended on (no fetch) and
    skips its products: an expert nobody chose is not read. `block` is
    `f_block`'s where None. Jitted, so that a step's layers of one shape
    are traced and lowered once."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tile, d = x.shape
    n_held, _, f = w_gate.shape
    n_tiles = tile_expert.shape[0]
    itemsize = jnp.dtype(w_gate.dtype).itemsize
    if block is None:
        block = f_block(d, f, itemsize)
    n_blocks = f // block
    live = jnp.sum(tile_expert < n_held).astype(jnp.int32)
    # A tile without a pair stands at the last live tile's last block.
    last = jnp.maximum(live - 1, 0)
    at = jnp.minimum(jnp.arange(n_tiles, dtype=jnp.int32), last)
    prefetched = [jnp.minimum(tile_expert, n_held - 1)[at].astype(jnp.int32),
                  jnp.reshape(live, (1,))]

    def block_of(i, j, live_ref):
        return jnp.where(i < live_ref[0], j, n_blocks - 1)

    def whole(i, j, expert_ref, live_ref):
        return (0, 0)

    def share_map(i, j, expert_ref, live_ref):
        return (jnp.minimum(i, jnp.maximum(live_ref[0] - 1, 0)), 0, 0)

    def in_map(i, j, expert_ref, live_ref):
        return (expert_ref[i], 0, block_of(i, j, live_ref))

    def down_map(i, j, expert_ref, live_ref):
        return (expert_ref[i], block_of(i, j, live_ref), 0)

    return pl.pallas_call(
        _ffn_body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetched),
            grid=(n_tiles, n_blocks),
            in_specs=[pl.BlockSpec((tile, d), whole),
                      pl.BlockSpec((None, tile, 1), share_map),
                      pl.BlockSpec((None, d, block), in_map),
                      pl.BlockSpec((None, d, block), in_map),
                      pl.BlockSpec((None, block, d), down_map)],
            out_specs=pl.BlockSpec((tile, d), whole)),
        out_shape=jax.ShapeDtypeStruct((tile, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # Two buffers of the three blocks, and room for the rest.
            vmem_limit_bytes=2 * 3 * d * block * itemsize + (16 << 20)),
        name="held_experts_ffn_decode",
        interpret=interpret,
    )(*prefetched, x, share, w_gate, w_up, w_down)


def _one_tile(y, local, weights, w_gate, w_up, w_down):
    """A batch of one tile through `grouped_ffn_kernel`: every expert
    with a pair meets the whole batch, and a row's weight in it, 0 where
    the row did not choose it, takes the place of the sort, the gather
    and the scatter-add. `local` ``[T, k]``: a pair's held expert,
    ``n_held`` where it fell on none. A token chooses an expert once, so
    there are at most ``min(n_held, T k)`` tiles."""
    t, k = local.shape
    n_held = w_gate.shape[0]
    chose = local[None] == jnp.arange(n_held, dtype=local.dtype)[:, None,
                                                                 None]
    share = jnp.sum(jnp.where(chose, weights[None], 0.0), axis=-1)  # [E, T]
    load = jnp.sum(chose, axis=(1, 2)).astype(jnp.int32)
    # A whole tile of the chip in the operands' dtype: 8 rows of 4
    # bytes, 16 of 2.
    tile = _tile_rows(t, 32 // jnp.dtype(w_gate.dtype).itemsize)
    tile_expert = jnp.nonzero(load > 0, size=min(n_held, t * k),
                              fill_value=n_held)[0].astype(jnp.int32)
    share = jnp.pad(share, ((0, 1), (0, tile - t)))[tile_expert]
    out = grouped_ffn_kernel(
        jnp.pad(y, ((0, tile - t), (0, 0))).astype(w_gate.dtype),
        tile_expert, share[..., None], w_gate, w_up, w_down)
    return out[:t], load


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
    local = jnp.where(here, experts - lo, n_held)
    if kernel_eligible(t, d, w_gate.shape[2], w_gate.dtype):
        return _one_tile(y, local, weights, w_gate, w_up, w_down)
    # Pairs, sorted by held expert; pairs for absent experts sort last.
    pair_expert = local.reshape(-1)
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
