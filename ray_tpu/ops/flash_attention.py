"""Causal flash attention on the TPU: three Pallas kernels and their VJP.

The bodies of `jax.experimental.pallas.ops.tpu.flash_attention` (online
softmax forward, a dK/dV kernel, a dQ kernel; bf16 operands, float32
scores, statistics and accumulators) cut to what `attention()` sends here
(causal, equal query and key lengths, no bias, no segments), with what
the shipped bodies leave on the table at the causal diagonal:

- A grid step holds a *major* tile and walks it in *minor* tiles. A minor
  tile that lies wholly above the diagonal is not computed (the shipped
  kernels skip whole major tiles only, and compute then mask every minor
  inside one that runs), and the mask (two iotas, a compare, a select) is
  built only on minor tiles the diagonal crosses.
- The forward hands the backward one row of log-sum-exp a query,
  ``[B, H, 1, S]`` float32, instead of ``l`` and ``m`` broadcast over 128
  lanes (``[B, H, S, 128]`` float32 each, written by the forward and read
  by both backward kernels: 268 MB apiece at ``[8, 32, 2048]``).
- The dK/dV kernel works on transposed scores ``[k, q]``, so the row
  statistics broadcast along sublanes and ``p^T @ dO`` and ``dS^T @ Q``
  need no transpose of a score tile.

Tiles are chosen by `ops.attention.flash_tiles` from ``(seq_len,
head_dim)``; `ops.attention.plain_attention` is the plain form the tests
hold the kernels to.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

# Pallas is imported where it is used: `ops.attention` imports this module
# in every process, and the import costs a worker that never reaches the
# chip's path about a second.
_LANES = 128
_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
# Every grid is (batch, head, the kernel's row tile, the major it walks
# and accumulates over).
_GRID_SEMANTICS = ("parallel", "parallel", "parallel", "arbitrary")
# `jax.checkpoint` names of what the backward needs of the forward beside
# q, k and v: the output and its log-sum-exp. `ops.attention.attention`
# puts the first on its other paths' output; the second exists on the
# kernels' path alone.
ATTN_OUT = "attn_out"
ATTN_LSE = "attn_lse"


@dataclasses.dataclass(frozen=True)
class FlashTiles:
    """Rows and columns of the score tiles. ``*_major`` is what a grid
    step brings into VMEM beside the kernel's own row tile; the minor
    (the name without ``_major``) is what one pass of the inner loop
    computes, skips or masks. Every size is a multiple of 128 that
    divides the sequence, a minor divides its major."""
    block_q: int              # forward: query rows a grid step
    block_k_major: int
    block_k: int
    block_k_dkv: int          # dK/dV: key rows a grid step
    block_q_major_dkv: int
    block_q_dkv: int
    block_q_dq: int           # dQ: query rows a grid step
    block_k_major_dq: int
    block_k_dq: int

    def check(self, seq_len: int) -> None:
        for name, size in dataclasses.asdict(self).items():
            if size % _LANES or seq_len % size:
                raise ValueError(f"{name}={size} is no multiple of "
                                 f"{_LANES} that divides {seq_len}")
        for major, minor in (("block_k_major", "block_k"),
                             ("block_q_major_dkv", "block_q_dkv"),
                             ("block_k_major_dq", "block_k_dq")):
            if getattr(self, major) % getattr(self, minor):
                raise ValueError(f"{minor} does not divide {major}")


def _lanes(x, n: int):
    """``x`` is ``[rows, 128]`` with equal lanes: the same at ``n``."""
    if n <= _LANES:
        return x[:, :n]
    return jnp.tile(x, (1, n // _LANES))


def _runs(row_tile, row_block, col_tile, col_block):
    """Whether a tile has an entry on or below the diagonal (its bottom
    left corner is): rows are queries, columns keys."""
    return (row_tile + 1) * row_block - 1 >= col_tile * col_block


def _query_tile_specs(bq: int, bkm: int, d: int):
    """Block specs of the two kernels whose grid is (batch, head, query
    tile, key major): a ``[bq, d]`` query-side tile, a ``[bkm, d]`` key
    major, and a ``[1, bq]`` slice of a ``[B, H, 1, S]`` row."""
    from jax.experimental import pallas as pl

    def kv_map(bi, hi, qi, kj):
        # A key major above the diagonal is not computed; staying on
        # major 0 (the next query tile's first) keeps it from being
        # fetched.
        return (bi, hi, jnp.where(_runs(qi, bq, kj, bkm), kj, 0), 0)

    return (pl.BlockSpec((None, None, bq, d),
                         lambda bi, hi, qi, kj: (bi, hi, qi, 0)),
            pl.BlockSpec((None, None, bkm, d), kv_map),
            pl.BlockSpec((None, None, 1, bq),
                         lambda bi, hi, qi, kj: (bi, hi, 0, qi)))


def _causal_step(step, offset, q_first, n_q, k_first, n_k):
    """Call ``step(offset, masked)`` for the minor tile of queries
    ``q_first..`` and keys ``k_first..`` if the causal mask leaves
    anything of it; ``masked`` if it takes anything away. The two tests
    are on program ids, so each is a branch on the chip."""
    from jax.experimental import pallas as pl

    runs = k_first <= q_first + n_q - 1
    crossed = k_first + n_k - 1 > q_first
    pl.when(runs & jnp.logical_not(crossed))(
        functools.partial(step, offset, False))
    pl.when(runs & crossed)(functools.partial(step, offset, True))


def _causal(s, first_row, first_col, rows_are_queries: bool = True):
    at0 = first_row + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    at1 = first_col + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    keep = at1 <= at0 if rows_are_queries else at0 <= at1
    return jnp.where(keep, s, _MASK_VALUE)


# -- forward ---------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
                *, scale: float, block_k: int):
    from jax.experimental import pallas as pl

    block_q, d = q_ref.shape
    block_k_major = k_ref.shape[0]
    qi, kj = pl.program_id(2), pl.program_id(3)
    q0, k0 = qi * block_q, kj * block_k_major

    @pl.when(kj == 0)
    def _start():
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def step(start, masked):
        k = k_ref[pl.ds(start, block_k), :]
        v = v_ref[pl.ds(start, block_k), :]
        s = jax.lax.dot_general(q_ref[...], k, _NT,
                                preferred_element_type=jnp.float32) * scale
        if masked:
            s = _causal(s, q0, k0 + start)
        m_prev = m_ref[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - _lanes(m_next, block_k))
        alpha = jnp.exp(m_prev - m_next)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_next
        acc_ref[...] = acc_ref[...] * _lanes(alpha, d) + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    for start in range(0, block_k_major, block_k):
        _causal_step(step, start, q0, block_q, k0 + start, block_k)

    @pl.when(kj == pl.num_programs(3) - 1)
    def _finish():
        l = l_ref[...]
        o_ref[...] = (acc_ref[...] * _lanes(1.0 / l, d)).astype(o_ref.dtype)
        if lse_ref is not None:
            lse_ref[...] = (m_ref[...] + jnp.log(l)).T[:1]


def _forward(q, k, v, tiles: FlashTiles, save_lse: bool, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, s, d = q.shape
    if not q.shape == k.shape == v.shape:
        raise ValueError(f"q, k and v differ in shape: {q.shape}, "
                         f"{k.shape}, {v.shape}")
    tiles.check(s)
    bq, bkm, bk = tiles.block_q, tiles.block_k_major, tiles.block_k
    q_spec, kv_spec, row_spec = _query_tile_specs(bq, bkm, d)
    out_shape = [jax.ShapeDtypeStruct(q.shape, q.dtype)]
    out_specs = [q_spec]
    if save_lse:
        out_shape.append(jax.ShapeDtypeStruct((b, h, 1, s), jnp.float32))
        out_specs.append(row_spec)
        body = _fwd_kernel
    else:
        def body(q_ref, k_ref, v_ref, o_ref, *scratch, **kw):
            return _fwd_kernel(q_ref, k_ref, v_ref, o_ref, None, *scratch,
                               **kw)
    out = pl.pallas_call(
        functools.partial(body, scale=d ** -0.5, block_k=bk),
        grid=(b, h, s // bq, s // bkm),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bq, _LANES), jnp.float32),
                        pltpu.VMEM((bq, _LANES), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=_GRID_SEMANTICS),
        name=f"flash_mha_fwd_block_q_{bq}_block_k_major_{bkm}_block_k_{bk}",
        interpret=interpret,
    )(q, k, v)
    return out if save_lse else (out[0], None)


# -- backward: dK and dV ---------------------------------------------------
def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref,
                dv_ref, dk_acc, dv_acc, *, scale: float, block_q: int):
    from jax.experimental import pallas as pl

    block_k, _ = k_ref.shape
    block_q_major = q_ref.shape[0]
    kj, qi = pl.program_id(2), pl.program_id(3)
    k0, q0 = kj * block_k, qi * block_q_major

    @pl.when(qi == 0)
    def _start():
        dk_acc[...] = jnp.zeros(dk_acc.shape, jnp.float32)
        dv_acc[...] = jnp.zeros(dv_acc.shape, jnp.float32)

    def step(start, masked):
        rows = pl.ds(start, block_q)
        q, do = q_ref[rows, :], do_ref[rows, :]
        # Scores with keys on the rows: [block_k, block_q].
        s = jax.lax.dot_general(k_ref[...], q, _NT,
                                preferred_element_type=jnp.float32) * scale
        if masked:
            s = _causal(s, k0, q0 + start, rows_are_queries=False)
        p = jnp.exp(s - lse_ref[:, rows])
        dv_acc[...] += jnp.dot(p.astype(do.dtype), do,
                               preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v_ref[...], do, _NT,
                                 preferred_element_type=jnp.float32)
        ds = (dp - di_ref[:, rows]) * p * scale
        dk_acc[...] += jnp.dot(ds.astype(q.dtype), q,
                               preferred_element_type=jnp.float32)

    for start in range(0, block_q_major, block_q):
        _causal_step(step, start, q0 + start, block_q, k0, block_k)

    @pl.when(qi == pl.num_programs(3) - 1)
    def _finish():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _backward_dkv(q, k, v, do, lse, di, tiles: FlashTiles, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, s, d = q.shape
    bk, bqm, bq = (tiles.block_k_dkv, tiles.block_q_major_dkv,
                   tiles.block_q_dkv)

    def q_tile(kj, qi):
        # Query tiles before the diagonal are not computed: wait on the
        # first that is.
        return jnp.maximum(qi, (kj * bk) // bqm)

    q_spec = pl.BlockSpec((None, None, bqm, d), lambda bi, hi, kj, qi:
                          (bi, hi, q_tile(kj, qi), 0))
    row_spec = pl.BlockSpec((None, None, 1, bqm), lambda bi, hi, kj, qi:
                            (bi, hi, 0, q_tile(kj, qi)))
    k_spec = pl.BlockSpec((None, None, bk, d), lambda bi, hi, kj, qi:
                          (bi, hi, kj, 0))
    return pl.pallas_call(
        functools.partial(_dkv_kernel, scale=d ** -0.5, block_q=bq),
        grid=(b, h, s // bk, s // bqm),
        in_specs=[q_spec, k_spec, k_spec, q_spec, row_spec, row_spec],
        out_specs=[k_spec, k_spec],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=_GRID_SEMANTICS),
        name=(f"flash_mha_bwd_dkv_block_k_{bk}_block_q_major_{bqm}"
              f"_block_q_{bq}"),
        interpret=interpret,
    )(q, k, v, do, lse, di)


# -- backward: dQ ----------------------------------------------------------
def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref,
               lse_col, di_col, dq_acc, *, scale: float, block_k: int):
    from jax.experimental import pallas as pl

    block_q, _ = q_ref.shape
    block_k_major = k_ref.shape[0]
    qi, kj = pl.program_id(2), pl.program_id(3)
    q0, k0 = qi * block_q, kj * block_k_major

    @pl.when(kj == 0)
    def _start():
        # The statistics arrive a row a query tile; this kernel's scores
        # have queries on the rows.
        lse_col[...] = jnp.broadcast_to(lse_ref[...], (_LANES, block_q)).T
        di_col[...] = jnp.broadcast_to(di_ref[...], (_LANES, block_q)).T
        dq_acc[...] = jnp.zeros(dq_acc.shape, jnp.float32)

    def step(start, masked):
        k = k_ref[pl.ds(start, block_k), :]
        v = v_ref[pl.ds(start, block_k), :]
        s = jax.lax.dot_general(q_ref[...], k, _NT,
                                preferred_element_type=jnp.float32) * scale
        if masked:
            s = _causal(s, q0, k0 + start)
        p = jnp.exp(s - _lanes(lse_col[...], block_k))
        dp = jax.lax.dot_general(do_ref[...], v, _NT,
                                 preferred_element_type=jnp.float32)
        ds = (dp - _lanes(di_col[...], block_k)) * p * scale
        dq_acc[...] += jnp.dot(ds.astype(k.dtype), k,
                               preferred_element_type=jnp.float32)

    for start in range(0, block_k_major, block_k):
        _causal_step(step, start, q0, block_q, k0 + start, block_k)

    @pl.when(kj == pl.num_programs(3) - 1)
    def _finish():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)


def _backward_dq(q, k, v, do, lse, di, tiles: FlashTiles, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, s, d = q.shape
    bq, bkm, bk = tiles.block_q_dq, tiles.block_k_major_dq, tiles.block_k_dq
    q_spec, kv_spec, row_spec = _query_tile_specs(bq, bkm, d)
    return pl.pallas_call(
        functools.partial(_dq_kernel, scale=d ** -0.5, block_k=bk),
        grid=(b, h, s // bq, s // bkm),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, _LANES), jnp.float32),
                        pltpu.VMEM((bq, _LANES), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=_GRID_SEMANTICS),
        name=f"flash_mha_bwd_dq_block_q_{bq}_block_k_major_{bkm}_block_k_{bk}",
        interpret=interpret,
    )(q, k, v, do, lse, di)


# -- the differentiable call -----------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_mha(q, k, v, tiles: FlashTiles, interpret: bool = False):
    """Causal softmax attention. q/k/v: ``[B, H, S, D]``, ``D`` 64 or a
    multiple of 128, ``S`` a multiple of every tile."""
    return _forward(q, k, v, tiles, False, interpret)[0]


def _flash_mha_fwd(q, k, v, tiles, interpret):
    o, lse = _forward(q, k, v, tiles, True, interpret)
    # What the backward needs of the forward beside q, k and v gets its
    # `jax.checkpoint` name here, and the named values ARE the primal
    # output and the residuals: a policy that saves the two names leaves
    # the backward no forward kernel to run again (unnamed, or named on
    # the caller's transpose alone, `jax.checkpoint` re-runs this rule
    # for them). The output is kept as ``[B, S, H * D]``: whole lanes at
    # any head size (as the kernel wrote it, ``[.., S, 64]`` lies in
    # tiles of 128 lanes in HBM: twice the bytes a layer), and the shape
    # the caller's output projection reads, so the way back to the
    # kernels' shape folds into the caller's transpose.
    b, h, s, d = o.shape
    rows = checkpoint_name(o.transpose(0, 2, 1, 3).reshape(b, s, h * d),
                           ATTN_OUT)
    lse = checkpoint_name(lse, ATTN_LSE)
    return (rows.reshape(b, s, h, d).transpose(0, 2, 1, 3),
            (q, k, v, rows, lse))


def _flash_mha_bwd(tiles, interpret, residuals, do):
    q, k, v, rows, lse = residuals
    b, h, s, d = q.shape
    # Of the output the kernels take only `di`, a head's sum of o * do a
    # query: summed in the shape the output was kept in (`do` is the
    # transpose of a cotangent that arrives in that shape), so no
    # `[B, H, S, D]` copy of the output is made for it.
    do_rows = do.transpose(0, 2, 1, 3).reshape(b, s, h * d)
    di = jnp.sum((rows.astype(jnp.float32) * do_rows.astype(jnp.float32)
                  ).reshape(b, s, h, d), axis=-1)           # [B, S, H]
    di = di.transpose(0, 2, 1)[:, :, None, :]               # [B, H, 1, S]
    dk, dv = _backward_dkv(q, k, v, do, lse, di, tiles, interpret)
    dq = _backward_dq(q, k, v, do, lse, di, tiles, interpret)
    return dq, dk, dv


flash_mha.defvjp(_flash_mha_fwd, _flash_mha_bwd)


# -- the serving prefill's forward: grouped heads, an optional window ------
def _prefill_fwd_kernel(at_ref, q_ref, k_ref, v_ref, *rest, scale: float,
                        window, n_visit: int, with_sink: bool,
                        with_keep: bool = False):
    """One query tile of one query head against the `kj`-th key tile it
    visits: all tiles up to the diagonal without a window, the last
    `n_visit` up to the diagonal with one. `at_ref` holds the key index
    on the first query's diagonal and the count of live keys
    (`prefill_attention_fwd`); positions below are key indices. A tile
    wholly above the diagonal, wholly left of the window or wholly
    before the first live key is skipped; the masks are built only on a
    tile the diagonal, the window's edge or the first live key crosses.
    With a sink (``[1, 128]``, the head's logit on every lane) the
    running softmax starts from that column, which carries no value.
    With a keep tile (int8, a query's selection of its keys) every tile
    that runs is masked, by it too."""
    from jax.experimental import pallas as pl

    sink_ref = rest[0] if with_sink else None
    keep_ref = rest[int(with_sink)] if with_keep else None
    o_ref, m_ref, l_ref, acc_ref = rest[int(with_sink) + int(with_keep):]
    block, d = o_ref.shape
    qi, kj = pl.program_id(1), pl.program_id(2)
    offset = at_ref[0]
    first_live = offset + pl.num_programs(1) * block - at_ref[1]
    diag = qi + offset // block           # the tile the diagonal crosses
    kt = kj if window is None else diag - (n_visit - 1) + kj
    q0, k0 = diag * block, kt * block

    @pl.when(kj == 0)
    def _start():
        if with_sink:
            m_ref[...] = jnp.broadcast_to(sink_ref[...], m_ref.shape)
            l_ref[...] = jnp.ones(l_ref.shape, jnp.float32)
        else:
            m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
            l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def step(masked):
        s = jax.lax.dot_general(q_ref[...], k_ref[...], _NT,
                                preferred_element_type=jnp.float32) * scale
        if masked:
            at_q = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            at_k = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            keep = (at_k <= at_q) & (at_k >= first_live)
            if window is not None:
                keep &= at_q - at_k < window
            if with_keep:
                keep &= keep_ref[...].astype(jnp.int32) != 0
            s = jnp.where(keep, s, _MASK_VALUE)
        m_prev = m_ref[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - _lanes(m_next, block))
        alpha = jnp.exp(m_prev - m_next)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_next
        acc_ref[...] = acc_ref[...] * _lanes(alpha, d) + jnp.dot(
            p.astype(v_ref.dtype), v_ref[...],
            preferred_element_type=jnp.float32)

    runs = (kt >= 0) & (kt <= diag) & (k0 + block > first_live)
    crossed = (kt == diag) | (k0 < first_live)
    if window is not None:
        # The nearest and the farthest (query, key) pair of the tile.
        runs &= q0 - (k0 + block - 1) < window
        crossed |= q0 + block - 1 - k0 >= window
    if with_keep:
        pl.when(runs)(functools.partial(step, True))
    else:
        pl.when(runs & jnp.logical_not(crossed))(
            functools.partial(step, False))
        pl.when(runs & crossed)(functools.partial(step, True))

    @pl.when(kj == n_visit - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] * _lanes(1.0 / l_ref[...], d)
                      ).astype(o_ref.dtype)


def prefill_block(seq_len: int, window: int = None) -> int:
    """The prefill forward's tile: 512 rows and keys (or the sequence,
    below that); under a window of at most half of it, the power of two
    that holds the window, no smaller than the lanes: a window of 128
    then computes 256 keys a query, not 1,024."""
    block = min(512, seq_len)
    if window is not None:
        while block >= 2 * max(window, _LANES) and block % 2 == 0:
            block //= 2
    return block


def prefill_attention_fwd(q, k, v, window: int = None, sink=None, *,
                          offset=0, live=None, keep=None,
                          block: int = None, interpret: bool = False):
    """Causal softmax attention of one sequence for the serving prefill,
    forward only: q ``[H, Sq, D]`` over k ``[Hkv, Sk, D]`` and v ``[Hkv,
    Sk, Dv]``, query head ``i`` on key head ``i // (H // Hkv)``; with
    `window` a query sees the keys ``j`` with ``i - j < window`` alone;
    with `sink` ``[H]`` a head's softmax has one more column of that
    logit and no value. Float32 out, ``[H, Sq, Dv]``. ``Sq`` and ``Sk``
    are multiples of `block` (`prefill_block`), ``Dv`` of 128; keys of
    another width (192) go in filled up with zeros to whole lanes.

    A whole prompt has as many queries as keys and query ``i`` lies on
    key ``i``. A chunk of a prompt (`prefill_chunk`) brings its queries
    and every key they may see: query ``i`` lies on key ``offset + i``
    and of the keys up to the last query's the last `live` alone exist
    (the chunk's own and those of the positions before it; a key before
    them, the head of a window's tail that no position filled, is seen
    by no query). Both may be traced scalars, which the kernel and its
    index maps read from SMEM: one program whatever the chunk's place.
    `offset` is a multiple of `block`, ``offset + Sq <= Sk`` and ``live
    >= Sq``. A key tile past the diagonal or before the first live key
    is neither computed nor fetched. A query tile walks the same key
    tiles in the same order wherever its chunk begins: with `offset` 0
    and every key live this is the whole prompt's result, bit for bit.

    `keep` ``[Sq, Sk]`` bool (a layer that selects its keys,
    `ops/sparse_attention.py`): a query attends to the keys it sees AND
    keeps; it goes in as int8 tiles, a head's tile the same as every
    other's, and every tile up to the diagonal is then masked. Under
    ``flash_prefill_fwd_selected`` in a device trace.

    The training kernels above are left as they are (their tiles, their
    names): this one runs under ``flash_prefill_fwd_causal`` or
    ``flash_prefill_fwd_window_<w>`` in a device trace."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    h, sq, d = q.shape
    hkv, sk, dv = v.shape
    if k.shape != (hkv, sk, d) or sk < sq or h % hkv:
        raise ValueError(f"q {q.shape} does not go over k {k.shape}, "
                         f"v {v.shape}")
    block = block or prefill_block(sq, window)
    if sq % block or sk % block or block % _LANES:
        raise ValueError(f"block={block} is no multiple of {_LANES} that "
                         f"divides {sq} and {sk}")
    scale = d ** -0.5
    if d % _LANES:
        fill = ((0, 0), (0, 0), (0, -d % _LANES))
        q, k = jnp.pad(q, fill), jnp.pad(k, fill)
        d = q.shape[2]
    group = h // hkv
    n_tiles = sk // block
    n_visit = (n_tiles if window is None
               else min(n_tiles, -(-(window - 1) // block) + 1))
    at = jnp.stack([jnp.asarray(offset, jnp.int32),
                    jnp.asarray(offset + sq if live is None else live,
                                jnp.int32)])

    def kv_map(hi, qi, kj, at_ref):
        diag = qi + at_ref[0] // block
        kt = kj if window is None else diag - (n_visit - 1) + kj
        first_live = jnp.maximum(at_ref[0] + sq - at_ref[1], 0) // block
        # A tile that is skipped is not fetched: stay on one that runs.
        return (hi // group, jnp.clip(kt, first_live, diag), 0)

    def q_map(hi, qi, kj, at_ref):
        return (hi, qi, 0)

    in_specs = [pl.BlockSpec((None, block, d), q_map),
                pl.BlockSpec((None, block, d), kv_map),
                pl.BlockSpec((None, block, dv), kv_map)]
    operands = [q, k, v]
    if sink is not None:
        in_specs.append(pl.BlockSpec((None, 1, _LANES),
                                     lambda hi, qi, kj, at_ref: (hi, 0, 0)))
        operands.append(jnp.broadcast_to(
            sink.astype(jnp.float32)[:, None, None], (h, 1, _LANES)))
    if keep is not None:
        in_specs.append(pl.BlockSpec(
            (block, block),
            lambda hi, qi, kj, at_ref: (qi, kv_map(hi, qi, kj, at_ref)[1])))
        operands.append(keep.astype(jnp.int8))
    return pl.pallas_call(
        functools.partial(_prefill_fwd_kernel, scale=scale,
                          window=window, n_visit=n_visit,
                          with_sink=sink is not None,
                          with_keep=keep is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(h, sq // block, n_visit),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((None, block, dv), q_map),
            scratch_shapes=[pltpu.VMEM((block, _LANES), jnp.float32),
                            pltpu.VMEM((block, _LANES), jnp.float32),
                            pltpu.VMEM((block, dv), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((h, sq, dv), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name=("flash_prefill_fwd_selected" if keep is not None
              else "flash_prefill_fwd_causal" if window is None
              else f"flash_prefill_fwd_window_{window}"),
        interpret=interpret,
    )(at, *operands)
