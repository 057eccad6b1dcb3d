"""A dropless sparse-expert layer that is told which experts it holds.

Expert parallelism gives each chip a range ``held = (lo, hi)`` of a
layer's routed experts. The router is whole on every chip: it scores all
the experts and picks a token's top k among them (`route`). The chip
then computes, for the tokens routed to its own experts, those experts'
part of the layer's result (`held_experts_ffn`); what the absent experts
would add is another chip's to compute, and nothing here stands in for
it or for the exchange. The shares of all chips add up to the whole
layer.

No capacity and no dropped token. Three bodies, one result;
`held_experts_ffn` chooses by what it sees (`kernel_eligible`):

- The scan, off the chip and at widths that are not whole lanes (the
  unit tests'), and the kernels' twin in the tests: the (token, expert)
  pairs that fall on held experts are sorted by expert and laid out in
  row tiles, each tile of one expert (an expert with no token gets no
  tile, one with many gets several). A `lax.scan` over the tiles
  multiplies a tile's rows with its expert's three matrices and adds the
  weighted result to the tokens' rows; a tile that holds no pair skips
  its branch, so the weights of an expert nobody chose are not read. The
  number of tiles is fixed by the shapes (``ceil(T k / tile) + held``),
  whatever the imbalance.
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
- `held_experts_ffn_prefill`, on the chip for a prompt of more rows:
  one Pallas call over the scan's sorted row tiles, the same grid and
  index maps. XLA makes what is no wider than the ``T k`` pairs (their
  sort, `load`, a tile's expert, first pair and live rows, all
  scalar-prefetched); the kernel copies a tile's live rows out of `y` in
  HBM itself, a copy a row into one of two VMEM slabs while the tile
  before is multiplied, and adds a tile's weighted live rows to their
  tokens' rows of a ``[T, d]`` float32 sum that stays in VMEM over the
  whole grid. A kernel fed by XLA's gather and emptied by its
  scatter-add lost to the scan, because those run over every row the
  shapes must allow, 45,056 for 5,120 live pairs at ``[4096, 3072]``
  (10.81 ms against 6.31: PERF.md, PR 41); this one takes 1.8 ms there
  (PERF.md, PR 46). In a device trace it is `held_experts_ffn_prefill`.

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


def gated(gate, up, limit: float = None):
    """``silu(gate) * up``, an expert's middle; with `limit` (a model
    with a `swiglu_limit`) ``silu(min(gate, limit)) * clip(up, -limit,
    limit)``. A call without one traces what it always has."""
    if limit is not None:
        gate, up = jnp.minimum(gate, limit), jnp.clip(up, -limit, limit)
    return jax.nn.silu(gate) * up


# Rows of a tile at the most: a decode step's (padded) batch is one tile
# and `grouped_ffn_kernel`'s, a longer prompt several.
_ROWS_MOST = 128
# The kernel's weight blocks, three of them and two deep, may take this
# much of the chip's VMEM (`f_block`).
_VMEM_FOR_WEIGHTS = 48 << 20
# What the prompt kernel may take of a v5e core's 128 MiB of VMEM in all.
_VMEM_MOST = 112 << 20


def _tile_rows(tokens: int, least: int = 8) -> int:
    """Rows of a tile of the scan and of the one-tile kernel: the whole
    (padded) batch of a decode step, so that an expert's weights are
    read once a step; 128 for a prompt's scan (the prompt kernel's tiles
    are `prefill_tiles`'); no fewer than `least`."""
    tile = least
    while tile < min(tokens, _ROWS_MOST):
        tile *= 2
    return tile


def kernel_eligible(tokens: int, d: int, f: int, dtype) -> bool:
    """Whether `held_experts_ffn` runs a Pallas body, from what it can
    see: the TPU backend, bfloat16 or float32 weights, weight blocks of
    whole lanes (``d`` and ``f`` multiples of 128) and, for a prompt of
    more than `_ROWS_MOST` rows, a row that is whole tiles of the chip's
    memory (``d`` a multiple of 1,024: the kernel copies single rows) and
    a ``[T, d]`` float32 sum that fits the chip's VMEM beside the least
    blocks (`_prefill_vmem`: up to 4,096 rows at 3,072 or 4,096; 8,192
    rows keep the scan, not measured, no cell has them). A batch of one
    tile then goes through `grouped_ffn_kernel`, a longer prompt through
    `held_experts_ffn_prefill`; elsewhere (off the chip, the unit tests'
    widths) the scan runs."""
    itemsize = jnp.dtype(dtype).itemsize
    return (jax.default_backend() == "tpu"
            and d % 128 == 0 and f % 128 == 0
            and jnp.dtype(dtype) in (jnp.bfloat16, jnp.float32)
            and (tokens <= _ROWS_MOST
                 or d % 1024 == 0
                 and _prefill_vmem(tokens, d, _ROWS_MOST, 128, itemsize)
                 <= _VMEM_MOST))


def f_block(d: int, f: int, itemsize: int,
            budget: int = _VMEM_FOR_WEIGHTS) -> int:
    """Columns of ``w_gate`` and ``w_up`` (rows of ``w_down``) a grid
    step of the kernel brings: the largest multiple of 128 that divides
    `f` whose three blocks ``[d, block]``, two deep, fit
    `budget`; the whole of `f` where there is none (no shape
    `kernel_eligible` lets through). The whole of 1,024 at
    ``d`` 3,072 in bfloat16, 640 of 1,280 at 4,096: fewer, larger blocks
    were no slower than blocks of 256 or 512 at either (PERF.md, PR
    41)."""
    most = budget // (2 * 3 * d * itemsize)
    return max((b for b in range(128, min(f, most) + 1, 128) if f % b == 0),
               default=f)


def _prefill_vmem(rows: int, d: int, tile: int, block: int,
                  itemsize: int) -> int:
    """Bytes of VMEM `held_experts_ffn_prefill` holds at these sizes: the
    ``[rows, d]`` float32 sum, two float32 slabs of a tile's rows, the
    rows in the weights' dtype, the tile's float32 result and one more
    of it as a value, the three weight blocks two deep, the three
    ``[tile, block]`` float32 products, and room for the rest."""
    rows = -(-rows // _ROWS_MOST) * _ROWS_MOST
    return (rows * d * 4 + tile * d * (4 * 4 + itemsize)
            + 2 * 3 * d * block * itemsize + 3 * tile * block * 4
            + (8 << 20))


# (rows, k, held, d, f, bytes a weight): (rows a tile, columns a block),
# where a sweep on the chip (TPU v5 lite) found better than
# `prefill_tiles`' rule: the two sparse cells' prefill buckets, pairs
# over all the experts by a seeded uniform draw; the whole call's
# milliseconds (sort, kernel, the two relayouts; 10 dependent calls in
# one program, best of 4) beside each setting, the scan's first. PR 46.
# What won, a tile that holds an expert's expected pairs in one (1.2
# times them) and blocks of half of f, is no rule here: the expected
# pairs go by the count of all the experts, which the layer is not told.
_SWEPT_PREFILL_TILES = {
    # 160 pairs an expert. Scan 5.92; (192, 512) 1.81, (192, 256) 1.81,
    # (176, 256) 1.82, (208, 256) 1.84, (224, 256) 1.87, (256, 256) 1.92,
    # (256, 512) 2.03, (192, 1024) 2.27, the rule's (128, 1024) 2.52,
    # (128, 256) 2.73, (512, 512) 3.02: the next expert's first block is
    # there sooner than its whole matrices.
    (4096, 10, 32, 3072, 1024, 2): (192, 512),
    # 80 pairs an expert. Scan 3.04; (96, 512) 1.07, (112, 512) 1.09,
    # (160, 512) 1.09, (144, 512) 1.09, (128, 512) 1.11, (96, 256) 1.17,
    # (256, 256) 1.30, the rule's (128, 1024) 1.35, (256, 512) 1.40, (64,
    # 512) 1.86.
    (2048, 10, 32, 3072, 1024, 2): (96, 512),
    # Swept too, and left to the rule, which is within 2% of the best:
    # (1024, 10, 32, 3072, 1024, 2), 40 pairs an expert, the 0.60 GB of
    # 32 experts' matrices 0.74 ms at the HBM roof: scan 3.27; (80, 1024)
    # 0.96, the rule's (128, 1024) 0.97, (64, 1024) 0.97, (56, 1024) 0.98,
    # (64, 512) 0.98, (128, 512) 1.00, (48, 1024) 1.00, (256, 1024) 1.06,
    # (32, 1024) 1.20.
    # (512, 8, 40, 4096, 1280, 2), 13 pairs an expert, 40 experts' 1.26
    # GB 1.54 ms at the roof: scan 4.21; (64, 1280) 1.83, (128, 1280)
    # 1.83, the rule's (128, 640) 1.84, (32, 1280) 1.85, (64, 640) 1.85,
    # (128, 256) 1.85, (32, 640) 1.86, (32, 256) 1.90, (16, 640) 2.14.
    # (256, 8, 40, 4096, 1280, 2), 6 pairs an expert: scan 3.55; (32,
    # 1280) 1.80, (128, 1280) 1.82, (64, 640) 1.82, the rule's (128, 640)
    # 1.83, (32, 640) 1.83, (16, 640) 1.85.
}


def prefill_tiles(rows: int, k: int, held: int, d: int, f: int,
                  itemsize: int) -> Tuple[int, int]:
    """Rows of a tile and columns of a weight block for a prompt of
    `rows` rows with `k` pairs a token over `held` experts of ``[d,
    f]``. The rule: tiles of `_ROWS_MOST` rows and `f_block`'s columns
    under what the sum and the tile's buffers leave of `_VMEM_MOST`. A
    shape at which a sweep on the chip found better has its entry in
    `_SWEPT_PREFILL_TILES`; at the two that have one the rule reads 1.35
    and 2.52 ms against 1.07 and 1.81, the scan 3.04 and 5.92."""
    swept = _SWEPT_PREFILL_TILES.get((rows, k, held, d, f, itemsize))
    if swept:
        return swept
    left = _VMEM_MOST - _prefill_vmem(rows, d, _ROWS_MOST, 0, itemsize)
    return _ROWS_MOST, f_block(d, f, itemsize,
                               min(_VMEM_FOR_WEIGHTS, max(left, 0)))


def _ffn_body(expert_ref, live_ref, x_ref, share_ref, gate_ref, up_ref,
              down_ref, o_ref, *, limit=None):
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
        out = jnp.dot(gated(gate, up, limit).astype(x.dtype),
                      down_ref[...], preferred_element_type=f32)
        o_ref[...] += out * share_ref[...]


@functools.partial(jax.jit, static_argnames=("block", "interpret", "limit"))
def grouped_ffn_kernel(x, tile_expert, share, w_gate, w_up, w_down, *,
                       block: int = None, interpret: bool = False,
                       limit: float = None):
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
        _ffn_body if limit is None else functools.partial(_ffn_body,
                                                          limit=limit),
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


def _one_tile(y, local, weights, w_gate, w_up, w_down, limit=None):
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
        tile_expert, share[..., None], w_gate, w_up, w_down, limit=limit)
    return out[:t], load


def _pairs_by_expert(local, weights, n_held: int):
    """The (token, expert) pairs sorted by held expert, pairs for absent
    experts last: ``(pair_expert, pair_token, pair_weight)``, each ``[T
    k]``. `local` ``[T, k]``: a pair's held expert, ``n_held`` where it
    fell on none. One sort of ``expert x T k + pair`` carries the weights
    along and is its own order: an `argsort` and three gathers by it
    cost a prompt of 4,096 rows 0.29 ms a gather on the chip (40,960
    pairs, 7 ns each) where the sort costs 0.04 (my chip run, PR 46),
    and the chip's compiler takes 10 s over this sort where the stable
    `argsort` took it 15. The key is 32 bits: a call of so many pairs
    that it passes them (millions of rows) is refused."""
    t, k = local.shape
    pairs = t * k
    if (n_held + 1) * pairs > jnp.iinfo(jnp.int32).max:
        raise ValueError(
            f"{pairs} pairs over {n_held} held experts pass the sort's "
            f"32-bit key")
    at = jnp.arange(pairs, dtype=jnp.int32)
    key, pair_weight = jax.lax.sort(
        (local.reshape(-1) * pairs + at, weights.reshape(-1)), num_keys=1,
        is_stable=False)
    pair_expert = key // pairs
    return pair_expert, (key - pair_expert * pairs) // k, pair_weight


def _of_expert(table, expert):
    """``table[expert]`` for a short `table` ``[n_held]`` and `expert`
    ``[n]`` in its range or past it (0 then), as compares and a sum:
    XLA's gather takes 90 ns an element on the chip."""
    chosen = expert[:, None] == jnp.arange(table.shape[0])[None]
    return jnp.sum(jnp.where(chosen, table[None], 0), axis=1)


def _tiles_by_expert(pair_expert, n_held: int, tile: int, n_tiles: int):
    """Row tiles over the sorted pairs, each of one expert: expert e
    takes ``ceil(load[e] / tile)`` of them, in order. Returns ``(load
    [n_held], tiles_of [n_held], tiles_end [n_held], tile_expert
    [n_tiles])``; a tile past the last live one names ``n_held``."""
    load = jnp.sum(pair_expert[:, None] == jnp.arange(n_held)[None],
                   axis=0, dtype=jnp.int32)
    tiles_of = (load + tile - 1) // tile
    tiles_end = jnp.cumsum(tiles_of)
    tile_expert = jnp.sum(jnp.arange(n_tiles)[:, None] >= tiles_end[None],
                          axis=1, dtype=jnp.int32)
    return load, tiles_of, tiles_end, tile_expert


def _scan_of_tiles(y, local, weights, w_gate, w_up, w_down, limit=None):
    """The scan: a `lax.scan` over row tiles of one expert each, a tile
    without a pair skipping its branch."""
    f32 = jnp.float32
    t, d = y.shape
    k = local.shape[1]
    n_held = w_gate.shape[0]
    pair_expert, pair_token, pair_weight = _pairs_by_expert(
        local, weights, n_held)
    tile = _tile_rows(t)
    n_tiles = -(-t * k // tile) + n_held
    rows = n_tiles * tile
    load, tiles_of, tiles_end, tile_expert = _tiles_by_expert(
        pair_expert, n_held, tile, n_tiles)
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
            out = jnp.dot(gated(gate, up, limit).astype(act),
                          w_down[expert], preferred_element_type=f32)
            return acc.at[tokens].add(out * gates[:, None])

        return jax.lax.cond(expert < n_held, compute, lambda acc: acc,
                            acc), None

    acc, _ = jax.lax.scan(
        one_tile, jnp.zeros((t + 1, d), f32),
        (tile_expert, row_token, row_weight))
    return acc[:t], load


def _prefill_body(expert_ref, live_ref, first_ref, rows_ref, token_ref,
                  weight_ref, y_ref, gate_ref, up_ref, down_ref, o_ref,
                  slabs, arrived, x_ref, z_ref, acc_ref, left, *sum_ref,
                  limit=None):
    """One grid step: tile ``i``'s rows against block ``j`` of its
    expert. A row of ``d`` values lies as ``d / 128`` sublanes of 128
    lanes in `y_ref`, the slabs, `z_ref` and the sum (a row is then whole
    tiles of the chip's memory, which a copy may address and a single
    row of a ``[rows, d]`` array is not; row tiles meet the matrix unit
    through strided loads and stores). At a tile's first block its rows
    have arrived in one of two VMEM slabs, a copy a row from `y_ref` in
    HBM, started while the tile before was multiplied, and the tile
    after's are started; at its last the tile's live rows, each times
    its weight, are added to their tokens' rows of the float32 sum,
    which stays in VMEM over the whole grid and leaves by one copy at
    the end. Only live rows are copied and folded. Scalars by `lax`'s
    own operations, as `ops/paged_attention.py:_kernel_body` says why."""
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32, i32 = jnp.float32, jnp.int32
    i, j = pl.program_id(0), pl.program_id(1)
    last_block = lax.sub(pl.num_programs(1), i32(1))
    tile = x_ref.shape[0]
    chunks = x_ref.shape[1] // 128          # sublanes a row
    live = live_ref[0]

    def row(r):
        at = lax.mul(r, i32(chunks))
        return pl.ds(pl.multiple_of(at, 8) if chunks % 8 == 0 else at,
                     chunks)

    def fetch(t, slab):
        first = first_ref[t]

        def one(r, _):
            pltpu.make_async_copy(
                y_ref.at[row(token_ref[lax.add(first, r)])],
                slabs.at[slab, row(r)], arrived.at[slab]).start()
        lax.fori_loop(i32(0), rows_ref[t], one, None)

    @pl.when(lax.bitwise_and(lax.eq(i, i32(0)), lax.eq(j, i32(0))))
    def _first_step():
        some = _ROWS_MOST * chunks              # the sum's rows at a time
        zeros = jnp.zeros((some, 128), f32)

        def clear(c, _):
            acc_ref[pl.ds(pl.multiple_of(lax.mul(c, i32(some)), 8),
                          some)] = zeros
        lax.fori_loop(i32(0), i32(acc_ref.shape[0] // some), clear, None)
        # A tile's products run over the slab's rows no copy of this
        # call has filled too (never folded, but they must be finite).
        slabs[...] = jnp.zeros(slabs.shape, f32)

        @pl.when(lax.gt(live, i32(0)))
        def _fetch_the_first_tile():
            fetch(i32(0), i32(0))

    @pl.when(lax.lt(i, live))
    def _a_live_tile():
        slab = lax.rem(i, i32(2))
        first, rows = first_ref[i], rows_ref[i]

        @pl.when(lax.eq(j, i32(0)))
        def _rows_in():
            @pl.when(lax.lt(lax.add(i, i32(1)), live))
            def _fetch_the_next_tile():
                fetch(lax.add(i, i32(1)), lax.sub(i32(1), slab))

            def arrive(r, _):
                pltpu.make_async_copy(y_ref.at[row(i32(0))],
                                      slabs.at[slab, row(i32(0))],
                                      arrived.at[slab]).wait()
            lax.fori_loop(i32(0), rows, arrive, None)
            for c in range(chunks):
                x_ref[:, c * 128:(c + 1) * 128] = slabs[
                    slab, pl.ds(c, tile, stride=chunks)].astype(x_ref.dtype)

        x = x_ref[...]
        gate = jnp.dot(x, gate_ref[...], preferred_element_type=f32)
        up = jnp.dot(x, up_ref[...], preferred_element_type=f32)
        out = jnp.dot(gated(gate, up, limit).astype(x.dtype),
                      down_ref[...], preferred_element_type=f32)
        if sum_ref:             # several blocks: their sum, block by block
            @pl.when(lax.eq(j, i32(0)))
            def _first_block():
                sum_ref[0][...] = out

            @pl.when(lax.gt(j, i32(0)))
            def _a_further_block():
                sum_ref[0][...] += out

        @pl.when(lax.eq(j, last_block))
        def _fold_the_live_rows():
            whole = sum_ref[0][...] if sum_ref else out
            for c in range(chunks):
                z_ref[pl.ds(c, tile, stride=chunks)] = whole[
                    :, c * 128:(c + 1) * 128]

            # A token chooses an expert once: no two rows of a tile meet.
            def one(r, _):
                at = lax.add(first, r)
                token = row(token_ref[at])
                acc_ref[token] = (acc_ref[token]
                                  + z_ref[row(r)] * weight_ref[at])
            lax.fori_loop(i32(0), rows, one, None)

    @pl.when(lax.bitwise_and(
        lax.eq(i, lax.sub(pl.num_programs(0), i32(1))),
        lax.eq(j, last_block)))
    def _the_sum_out():
        whole = pltpu.make_async_copy(acc_ref, o_ref, left)
        whole.start()
        whole.wait()


@functools.partial(jax.jit, static_argnames=("tile", "block", "interpret",
                                             "limit"))
def held_experts_ffn_prefill(y, tile_expert, tile_first, tile_rows,
                             pair_token, pair_weight, w_gate, w_up, w_down,
                             *, tile: int, block: int,
                             interpret: bool = False, limit: float = None):
    """y ``[T, d]`` float32, T a multiple of 128; the sorted pairs'
    tokens and weights `pair_token`, `pair_weight` ``[T k]``; a tile's
    expert, the sorted pair its first row is and how many rows it has in
    `tile_expert`, `tile_first`, `tile_rows` ``[n_tiles]`` (live tiles
    first, ``n_held`` and 0 rows after them); w_gate, w_up ``[n_held, d,
    f]``, w_down ``[n_held, f, d]``. Returns ``[T, d]`` float32: over
    the tiles, each live row's ``weight x W_down(silu(W_gate y) * W_up
    y)`` of the tile's expert added at its token's row.

    A Pallas TPU kernel over the grid ``(tiles, f / block)``, in a trace
    `held_experts_ffn_prefill`. The five tables are scalar-prefetched;
    the weight operands' index maps pick ``(tile_expert[i], block j)`` as
    `grouped_ffn_kernel`'s do, so consecutive tiles of one expert fetch
    nothing where `block` is the whole of ``f``, and a tile without a
    pair names the last live block, skips its body and costs a grid step
    and no byte. `y` and the result stay in HBM, a row as ``[d / 128,
    128]`` (`_prefill_body`): nothing of ``[rows, d]`` is built, gathered
    or scattered outside. Jitted, so that a program's layers of one
    shape are traced and lowered once."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    t, d = y.shape
    n_held, _, f = w_gate.shape
    n_tiles = tile_expert.shape[0]
    act = w_gate.dtype
    if t % _ROWS_MOST or tile % 8 or f % block or d % 128:
        raise ValueError(f"{t} rows are no multiple of {_ROWS_MOST}, tiles "
                         f"of {tile} rows are no whole sublanes, blocks of "
                         f"{block} columns do not divide experts of {f}, or "
                         f"a row of {d} is no whole lanes")
    n_blocks = f // block
    chunks = d // 128
    is_live = tile_expert < n_held
    live = jnp.sum(is_live).astype(jnp.int32)
    # A tile without a pair stands at the last live tile's last block:
    # it names that tile's expert, the largest one with a pair.
    last = jnp.max(jnp.where(is_live, tile_expert, 0))
    prefetched = [jnp.where(is_live, tile_expert, last).astype(jnp.int32),
                  jnp.reshape(live, (1,)), tile_first.astype(jnp.int32),
                  tile_rows.astype(jnp.int32), pair_token.astype(jnp.int32),
                  pair_weight.astype(f32)]

    def block_of(i, j, live_ref):
        return jnp.where(i < live_ref[0], j, n_blocks - 1)

    def in_map(i, j, expert_ref, live_ref, *tables):
        return (expert_ref[i], 0, block_of(i, j, live_ref))

    def down_map(i, j, expert_ref, live_ref, *tables):
        return (expert_ref[i], block_of(i, j, live_ref), 0)

    scratch = [pltpu.VMEM((2, tile * chunks, 128), f32),
               pltpu.SemaphoreType.DMA((2,)),
               pltpu.VMEM((tile, d), act),
               pltpu.VMEM((tile * chunks, 128), f32),
               pltpu.VMEM((t * chunks, 128), f32),
               pltpu.SemaphoreType.DMA(())]
    if n_blocks > 1:
        scratch.append(pltpu.VMEM((tile, d), f32))
    out = pl.pallas_call(
        _prefill_body if limit is None else functools.partial(
            _prefill_body, limit=limit),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetched),
            grid=(n_tiles, n_blocks),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec((None, d, block), in_map),
                      pl.BlockSpec((None, d, block), in_map),
                      pl.BlockSpec((None, block, d), down_map)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((t * chunks, 128), f32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_prefill_vmem(
                t, d, tile, block, jnp.dtype(act).itemsize)),
        name="held_experts_ffn_prefill",
        interpret=interpret,
    )(*prefetched, y.reshape(t * chunks, 128), w_gate, w_up, w_down)
    return out.reshape(t, d)


def _sorted_tiles(y, local, weights, w_gate, w_up, w_down, limit=None):
    """A prompt through `held_experts_ffn_prefill`. What XLA makes is no
    wider than the pairs: their sort by held expert, `load`, and a
    tile's expert, first pair and live rows; the kernel gathers its own
    rows and folds its own result."""
    t, d = y.shape
    k = local.shape[1]
    n_held, _, f = w_gate.shape
    tile, block = prefill_tiles(t, k, n_held, d, f,
                                jnp.dtype(w_gate.dtype).itemsize)
    n_tiles = -(-t * k // tile) + n_held
    pair_expert, pair_token, pair_weight = _pairs_by_expert(
        local, weights, n_held)
    load, tiles_of, tiles_end, tile_expert = _tiles_by_expert(
        pair_expert, n_held, tile, n_tiles)
    # Tile i is its expert's `nth`; the pairs of expert e start at
    # `first_pair[e]` in the sorted order. A tile past the last live one
    # has no rows.
    nth = (jnp.arange(n_tiles, dtype=jnp.int32)
           - _of_expert(tiles_end - tiles_of, tile_expert))
    tile_first = _of_expert(jnp.cumsum(load) - load, tile_expert) + nth * tile
    tile_rows = jnp.clip(_of_expert(load, tile_expert) - nth * tile, 0, tile)
    t_pad = -(-t // _ROWS_MOST) * _ROWS_MOST
    out = held_experts_ffn_prefill(
        jnp.pad(y.astype(jnp.float32), ((0, t_pad - t), (0, 0))),
        tile_expert, tile_first, tile_rows, pair_token, pair_weight,
        w_gate, w_up, w_down, tile=tile, block=block, limit=limit)
    return out[:t], load


def held_experts_ffn(y, experts, weights, w_gate, w_up, w_down,
                     held: Tuple[int, int], valid=None, limit: float = None):
    """The held experts' part of a sparse-expert layer.

    y ``[T, d]``; experts, weights ``[T, k]`` from `route`; w_gate, w_up
    ``[hi - lo, d, f]`` and w_down ``[hi - lo, f, d]``, the matrices of
    experts ``lo .. hi - 1``; valid ``[T]`` bool (rows of a padded batch
    that are no sequence route nowhere). An expert is
    ``W_down(silu(W_gate y) * W_up y)`` (`gated`, which clamps both
    factors at `limit` where the model has one); products take their
    operands in the weights' dtype and accumulate in float32.

    Returns ``(out [T, d] float32, load [hi - lo] int32)``: the weighted
    sum over a token's chosen experts that are held here, and the pairs
    that fell on each held expert."""
    t, d = y.shape
    lo, hi = held
    n_held = hi - lo
    here = (experts >= lo) & (experts < hi)
    if valid is not None:
        here &= valid[:, None]
    local = jnp.where(here, experts - lo, n_held)
    if not kernel_eligible(t, d, w_gate.shape[2], w_gate.dtype):
        return _scan_of_tiles(y, local, weights, w_gate, w_up, w_down, limit)
    if t <= _ROWS_MOST:
        return _one_tile(y, local, weights, w_gate, w_up, w_down, limit)
    return _sorted_tiles(y, local, weights, w_gate, w_up, w_down, limit)
