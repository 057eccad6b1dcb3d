"""A layer's matrix product against a stack of layers' weights, read
where the stack lies.

A decoder keeps a matrix of every layer in one array, ``[L, K, N]``, and
a step's layer ``l`` multiplies its rows ``x [rows, K]`` with ``w[l]``.
Written as ``x @ w[l]`` over float32 weights, XLA's default precision on
a TPU rounds both operands to bfloat16, and the compiler makes the
rounded weights an array of their own: hoisted out of the layer loop, a
bfloat16 copy of the WHOLE stack, written and read back every call (62%
of `olmo-1b`'s decode step, PERF.md, PR 50); with the loop unrolled, a
copy a layer. A decode step is bound by the bytes of its weights, so the
copy is the step.

Two bodies, one result (float32, both operands rounded to bfloat16,
products summed in float32):

- `stacked_weight_matmul_kernel`: a Pallas TPU kernel. The layer index
  is scalar-prefetched and the weight operand's index map picks ``(layer,
  k block, n block)``, so the pipeline's double buffering streams that
  layer's ``[tk, tn]`` tiles from HBM through VMEM once, each rounded to
  bfloat16 as it arrives and multiplied with the rows' ``k`` block (the
  rows are rounded once, outside, and stay in VMEM whole). The float32
  output block stays in VMEM over a column block's ``k`` steps and is
  the accumulator. No copy of the stack, and none of a layer out of it,
  reaches HBM. A bfloat16 stack is read as it is.
- ``x @ w_stack[layer]`` as XLA makes it: off the chip, at widths that
  are not whole lanes (the unit tests'), and for more rows than
  `_ROWS_MOST`, the largest the sweep on the chip covered.

`stacked_weight_matmul` picks by what it can see (`kernel_eligible`: the
backend, the widths, the rows), as `paged_attention.kernel_eligible` and
`experts.kernel_eligible` do. The stack has to lie as the kernel's tiles
want it, ``(8, 128)`` tiles on its last two axes: a 3-D float32 array
does, a 4-D one (``[L, d, 3, d]``) the chip lays out otherwise and would
re-lay in front of every call, so the caller reshapes such a stack once,
at load (`serve/engine/model.py`).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

# The rows the kernel takes in one block, and the most it is chosen for.
# Whole `olmo-1b` prefill programs on the chip (TPU v5 lite; 8 calls,
# best of 3, ms a call; XLA's products behind its bf16 copy of the stacks
# -> this kernel): 32 rows 15.05 -> 6.63, 64 15.24 -> 6.73, 128 18.71 ->
# 6.90, 256 20.00 -> 7.36, 512 22.35 -> 8.68, 1,024 30.66 -> 17.15 (PR
# 50). 1,024 is the largest bucket any cell reaches and the largest
# swept; more rows would go in several row blocks, each reading the
# matrix again, and keep XLA's product.
_ROWS_MOST = 1024
# Two weight tiles in flight may take this much VMEM.
_VMEM_FOR_WEIGHTS = 8 << 20
# A bfloat16 tile is 16 rows.
_ROW_TILE = 16


def kernel_eligible(rows: int, k: int, n: int, dtype) -> bool:
    """Whether `stacked_weight_matmul` runs the Pallas body, from what
    the call sees: the TPU backend, a float32 or bfloat16 stack whose
    ``[K, N]`` matrices are whole lanes, and no more rows than the sweep
    on the chip says the kernel wins at (`_ROWS_MOST`)."""
    return (jax.default_backend() == "tpu"
            and k % 128 == 0 and n % 128 == 0
            and jnp.dtype(dtype) in (jnp.bfloat16, jnp.float32)
            and rows <= _ROWS_MOST)


def _largest_divisor(n: int, most: int, step: int = 128) -> int:
    """The largest multiple of `step` that divides `n` and is at most
    `most`; `n` itself where there is none."""
    return max((b for b in range(step, min(n, most) + 1, step)
                if n % b == 0), default=n)


def tiles(k: int, n: int, itemsize: int) -> Tuple[int, int]:
    """Rows and columns ``(tk, tn)`` of a weight tile for ``[K, N]``
    matrices of `itemsize` bytes a value: 512 columns (a tile's row is 2
    KB of one matrix row in float32) and as many rows of `K` as keep two
    tiles in flight inside `_VMEM_FOR_WEIGHTS`, but at least 16 tiles a
    call, so that the first tile's fetch, which nothing hides, is a small
    part of it.

    The sweep that set it (PR 50, TPU v5 lite, float32 stacks; 32
    dependent calls in one program, best of 5, ms a call, which holds
    about 10 us of the loop's own glue; 8 rows / 256 rows; the roof is
    the matrix's bytes over 819 GB/s):
    2048 x 6144 (roof 0.061): the rule's 1024 x 512 0.090 / 0.091; 2048
    x 256 0.086 / 0.090, 2048 x 512 0.089 / 0.091, 512 x 512 0.089 /
    0.092, 2048 x 2048 0.090 / none, 512 x 256 0.092 / 0.106.
    2048 x 2048 (0.020): the rule's 512 x 512 0.043 / 0.046; 2048 x 512
    0.042 / 0.049, 1024 x 512 0.044 / 0.046, 1024 x 256 0.046 / 0.044,
    2048 x 2048 0.046 / none.
    2048 x 16384 (0.164): the rule's 2048 x 512 0.199 / 0.203; 1024 x
    1024 0.198 / 0.202, 512 x 512 0.200 / 0.212, 2048 x 2048 0.201 /
    none, 2048 x 256 0.211 / 0.211, 512 x 256 0.216 / 0.246.
    8192 x 2048 (0.082): the rule's 2048 x 512 0.113 / 0.130; 512 x 512
    0.110 / 0.130, 1024 x 512 0.113 / 0.126, 4096 x 512 0.116 / 0.133,
    8192 x 512 0.114 / none, 8192 x 256 0.118 / 0.134.
    Tiles from 1 to 16 MB read within 3% of each other at every shape
    (the pipeline hides a grid step's cost behind the next tile's
    fetch); what loses is a tile of 256 columns or fewer than 16 tiles a
    call. Inside `olmo-1b`'s traced decode step the four read 91%, 86%,
    91% and 90% of their roofs. No shape has a swept entry of its own:
    none beat the rule by more than the runs differ."""
    tn = _largest_divisor(n, 512)
    most = min(_VMEM_FOR_WEIGHTS // (2 * tn * itemsize),
               max(k * n // (16 * tn), 128))
    return _largest_divisor(k, most), tn


def vmem_bytes(rows: int, k: int, tk: int, tn: int, itemsize: int) -> int:
    """Bytes of VMEM the kernel holds at these sizes: two weight tiles
    and one rounded, the rows in bfloat16 (two buffers), the float32
    output block (two buffers) and one more of it as a value, and room
    for the rest."""
    return (2 * tk * tn * itemsize + tk * tn * 2 + 2 * rows * k * 2
            + 3 * rows * tn * 4 + (4 << 20))


def _body(layer_ref, x_ref, w_ref, o_ref, *, tk: int, k_steps: int):
    """One grid step: the rows' ``k`` block against one weight tile,
    added to the column block's float32 output."""
    from jax.experimental import pallas as pl

    f32, bf16 = jnp.float32, jnp.bfloat16
    if k_steps == 1:
        o_ref[...] = jnp.dot(x_ref[...], w_ref[...].astype(bf16),
                             preferred_element_type=f32)
        return
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _first_block():
        o_ref[...] = jnp.zeros(o_ref.shape, f32)

    x = x_ref[:, pl.ds(pl.multiple_of(kk * tk, tk), tk)]
    o_ref[...] += jnp.dot(x, w_ref[...].astype(bf16),
                          preferred_element_type=f32)


@functools.partial(jax.jit, static_argnames=("tk", "tn", "tm", "name",
                                             "interpret"))
def stacked_weight_matmul_kernel(x, w_stack, layer, *, tk: int = None,
                                 tn: int = None, tm: int = None,
                                 name: str = "stacked_weight_matmul",
                                 interpret: bool = False):
    """x ``[rows, K]``; w_stack ``[L, K, N]`` float32 or bfloat16; layer
    an int32 scalar. Returns ``[rows, N]`` float32: ``x @ w_stack[layer]``
    with both operands rounded to bfloat16 and float32 sums.

    A Pallas TPU kernel over the grid ``(row blocks, N / tn, K / tk)``:
    `layer` is scalar-prefetched and the stack's index map picks
    ``(layer, k, j)``, so the pipeline fetches the next tile of that
    layer's matrix while this one is rounded and multiplied. The rows
    are rounded once, here, padded to whole bfloat16 tiles, and a row
    block stays in VMEM over the whole of `K`; more than `tm` rows
    (`_ROWS_MOST` where None) go in several blocks, each of which reads
    the matrix again. `tk`, `tn` are `tiles`' where None. Jitted, so
    that a step's layers of one shape are traced and lowered once."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, k = x.shape
    _, _, n = w_stack.shape
    itemsize = jnp.dtype(w_stack.dtype).itemsize
    if tk is None or tn is None:
        tk, tn = tiles(k, n, itemsize)
    tm = min(-(-rows // _ROW_TILE) * _ROW_TILE, tm or _ROWS_MOST)
    rows_pad = -(-rows // tm) * tm
    xb = jnp.pad(x.astype(jnp.bfloat16), ((0, rows_pad - rows), (0, 0)))
    k_steps = k // tk

    out = pl.pallas_call(
        functools.partial(_body, tk=tk, k_steps=k_steps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows_pad // tm, n // tn, k_steps),
            in_specs=[
                pl.BlockSpec((tm, k), lambda i, j, kk, layer_ref: (i, 0)),
                pl.BlockSpec((None, tk, tn),
                             lambda i, j, kk, layer_ref:
                             (layer_ref[0], kk, j))],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda i, j, kk, layer_ref: (i, j))),
        out_shape=jax.ShapeDtypeStruct((rows_pad, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_bytes(tm, k, tk, tn, itemsize)),
        name=name,
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), xb, w_stack)
    return out[:rows]


def stacked_weight_matmul(x, w_stack, layer,
                          name: str = "stacked_weight_matmul"):
    """``x @ w_stack[layer]`` for x ``[rows, K]`` float32, w_stack ``[L,
    K, N]`` (float32, or bfloat16 read as it is) and `layer` an int32
    scalar; ``[rows, N]`` float32. On the chip, at whole lanes and no
    more than `_ROWS_MOST` rows, the kernel (in a device trace under
    `name`, so that a trace tells a decode step's calls from a
    prompt's); elsewhere XLA's product of the layer's matrix."""
    rows, k = x.shape
    if kernel_eligible(rows, k, w_stack.shape[2], w_stack.dtype):
        return stacked_weight_matmul_kernel(x, w_stack, layer, name=name)
    return jnp.dot(x, w_stack[layer].astype(x.dtype),
                   preferred_element_type=jnp.float32)
