"""Attention: plain (XLA-fused), flash (one chip: the Pallas kernels of
`ops/flash_attention.py`, their tiles chosen here from the call's
``(seq_len, head_dim)``) and ring (sequence-parallel over ICI).

Ring attention (SURVEY.md §2.5 / §5 — absent from the reference, built new):
each ``sp`` rank holds one sequence block of Q/K/V; K/V blocks rotate around
the ring via ``ppermute`` while a flash-style online softmax accumulates
output — so attention over sequence length S costs O(S/P) memory per chip and
overlaps compute with neighbor-to-neighbor ICI transfers. Differentiable
(autodiff through the scan; the ppermute transpose is the reverse rotation).

Position bookkeeping travels *with* the ring: each K/V block's global
positions are ppermuted alongside it, so the same body works standalone
(`ring_attention`) or inside an enclosing manual shard_map that also handles
pipeline stages (`ring_attention_manual`).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ray_tpu.ops.flash_attention import (ATTN_OUT, FlashTiles, flash_mha,
                                          prefill_attention_fwd,
                                          prefill_block)

_NEG_INF = -1e30


# Tiles of the three flash kernels by (seq_len, head_dim): what a sweep on
# the chip found fastest for that shape, each kernel timed alone over 24
# (15 at S = 1024) settings of its row tile, major and minor; bf16 on a
# TPU v5e, milliseconds a call (PERF.md, Findings, PR 34).
_SWEPT_TILES = {
    # [8, 32, 2048, 64]: forward 3.91 (4.00 with the log-sum-exp saved),
    # dK/dV 6.38, dQ 5.10; whole forward + backward 13.20 against 21.60 for
    # the kernels shipped with JAX at tiles of 1024. PR 34.
    (2048, 64): FlashTiles(
        block_q=512, block_k_major=2048, block_k=512,
        block_k_dkv=512, block_q_major_dkv=1024, block_q_dkv=512,
        block_q_dq=512, block_k_major_dq=2048, block_k_dq=512),
    # [8, 16, 1024, 128]: forward 0.46 (0.46), dK/dV 0.75, dQ 0.61; whole
    # 1.78 against 3.33. One forward tile beats skipping a quarter of it:
    # the online softmax's upkeep a minor costs more there. PR 34.
    (1024, 128): FlashTiles(
        block_q=1024, block_k_major=1024, block_k=1024,
        block_k_dkv=512, block_q_major_dkv=1024, block_q_dkv=512,
        block_q_dq=1024, block_k_major_dq=1024, block_k_dq=512),
}


def flash_tiles(seq_len: int, head_dim: int) -> FlashTiles:
    """The tiles `flash_attention_tpu` runs the kernels with: the swept
    entry for a shape somebody measured, else `_tiles_by_rule`."""
    return (_SWEPT_TILES.get((seq_len, head_dim))
            or _tiles_by_rule(seq_len))


def _tiles_by_rule(seq_len: int) -> FlashTiles:
    """For a shape nobody swept, what the sweeps agree on at either head
    size. Row tiles and minors of 512: the diagonal then leaves 10 of 16
    tile pairs at S = 2048 (tiles of 1024 leave 3 of 4), while minors of
    256 lose more to the statistics' upkeep and the matrix unit's fill
    than the 36 of 64 pairs save. Majors of up to 2048: a grid step costs
    about 0.35 us and re-reads its row tile's neighbours, a minor inside
    one costs nothing, and 2048 rows of two operands fit VMEM beside the
    score tiles. Every size is a multiple of 128 that divides `seq_len`
    (the largest such under the target), a minor divides its major."""
    def largest(at_most, unit):
        return max(b for b in range(unit, min(at_most, seq_len) + 1, unit)
                   if seq_len % b == 0)

    block = largest(512, 128)
    major = largest(2048, block)
    return FlashTiles(
        block_q=block, block_k_major=major, block_k=block,
        block_k_dkv=block, block_q_major_dkv=major, block_q_dkv=block,
        block_q_dq=block, block_k_major_dq=major, block_k_dq=block)


def flash_attention_tpu(q, k, v):
    """Causal flash attention on the TPU through the tree's Pallas kernels
    (`ops/flash_attention.py`): O(S) memory, no materialized [B,H,S,S]
    score matrix, differentiable (custom VJP).

    q/k/v: [B, S, H, D] (we transpose to the kernels' [B, H, S, D]).
    Under `jax.checkpoint` the kernels' VJP names what it keeps itself
    (the output, once, and the log-sum-exp); the transpose handed back
    here carries no name, so a names policy keeps one copy.
    """
    tiles = flash_tiles(q.shape[1], q.shape[3])
    qt, kt, vt = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
    return flash_mha(qt, kt, vt, tiles).transpose(0, 2, 1, 3)


def _flash_eligible(q) -> bool:
    """Flash kernel needs the TPU backend, a lane-aligned head_dim, and a
    sequence long enough to tile (standard arange positions only)."""
    s, d = q.shape[1], q.shape[3]
    return (jax.default_backend() == "tpu"
            and (d % 128 == 0 or d == 64)   # kernel handles 64 natively
            and s % 128 == 0)


def plain_attention(q, k, v, *, causal: bool = True, positions=None):
    """Softmax attention. q/k/v: [B, S, H, D]; positions: [S] global indices
    for the causal mask (defaults to arange)."""
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        idx = jnp.arange(q.shape[1]) if positions is None else positions
        mask = idx[:, None] >= idx[None, :]
        scores = jnp.where(mask[None, None], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def banded_attention(q, k, v, window: int = None, sink=None, *, offset=0,
                     live=None, keep=None):
    """The plain form of `prefill_attention`: q ``[H, Sq, D]`` over k
    ``[Hkv, Sk, D]`` and v ``[Hkv, Sk, Dv]`` (query head ``i`` on key
    head ``i // group``), causal, with `window` only the keys ``j`` with
    ``i - j < window``, with `sink` ``[H]`` one more softmax column a
    head, of that logit and no value; query ``i`` lies on key ``offset +
    i`` and of the keys up to the last query's only the last `live`
    exist (`flash_attention.prefill_attention_fwd`; a whole prompt's are
    0 and every key); with `keep` ``[Sq, Sk]`` bool a query attends to
    the keys it sees and keeps. The whole ``[H, Sq, Sk]`` float32 score
    matrix is built: for short prompts, the CPU and the tests. Float32
    out, ``[H, Sq, Dv]``."""
    h, sq, d = q.shape
    hkv, sk = k.shape[:2]
    qg = q.reshape(hkv, h // hkv, sq, d)
    scores = jnp.einsum("kgqd,ksd->kgqs", qg, k,
                        preferred_element_type=jnp.float32) * d ** -0.5
    at_q = offset + jnp.arange(sq)[:, None]
    at_k = jnp.arange(sk)[None, :]
    seen = at_q >= at_k
    if window is not None:
        seen &= at_q - at_k < window
    if live is not None:
        seen &= at_k >= offset + sq - live
    if keep is not None:
        seen &= keep
    scores = jnp.where(seen, scores, _NEG_INF)
    if sink is not None:
        scores = jnp.concatenate([scores, jnp.broadcast_to(
            sink.astype(jnp.float32).reshape(hkv, h // hkv, 1, 1),
            scores.shape[:3] + (1,))], axis=-1)
    probs = jax.nn.softmax(scores, axis=-1)[..., :sk]
    out = jnp.einsum("kgqs,ksd->kgqd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(h, sq, v.shape[2])


def prefill_attention(q, k, v, window: int = None, sink=None, *, offset=0,
                      live=None, keep=None):
    """One prompt's attention in the serving prefill, or one chunk's
    over the keys it sees (`offset`, `live`: traced scalars or ints, as
    `flash_attention.prefill_attention_fwd` takes them); grouped heads,
    an optional window, an optional sink, an optional selection (`keep`
    ``[Sq, Sk]`` bool: a call without one compiles as it always has),
    forward only: the Pallas forward of `ops/flash_attention.py` on a
    TPU for values of a
    multiple of 128 (keys may be wider: 192 over 128) and lengths that
    tile, `banded_attention` elsewhere."""
    sq, sk, dv = q.shape[1], k.shape[1], v.shape[2]
    if (jax.default_backend() == "tpu" and dv % 128 == 0
            and sq % 128 == 0 and sk % prefill_block(sq, window) == 0):
        return prefill_attention_fwd(q, k, v, window, sink, offset=offset,
                                     live=live, keep=keep)
    return banded_attention(q, k, v, window, sink, offset=offset, live=live,
                            keep=keep)


def ring_attention_manual(q, k, v, q_pos, *, axis_name: str = "sp",
                          causal: bool = True):
    """Manual-collective ring attention body. Must run inside a shard_map
    where `axis_name` is a manual axis. q/k/v: local blocks [B, S_loc, H, D];
    q_pos: [S_loc] global positions of the local block."""
    axis_size = jax.lax.axis_size(axis_name)
    b, s_loc, h, d = q.shape
    scale = d ** -0.5
    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]

    qf = q.astype(jnp.float32)

    def step(carry, _):
        o, l, m, k_blk, v_blk, kv_pos = carry
        scores = jnp.einsum("bqhd,bkhd->bhqk", qf,
                            k_blk.astype(jnp.float32)) * scale
        if causal:
            mask = q_pos[:, None] >= kv_pos[None, :]
            scores = jnp.where(mask[None, None], scores, _NEG_INF)
        m_blk = jnp.max(scores, axis=-1)                     # [B,H,Q]
        m_new = jnp.maximum(m, m_blk)
        p = jnp.exp(scores - m_new[..., None])               # [B,H,Q,K]
        corr = jnp.exp(m - m_new)                            # [B,H,Q]
        l_new = l * corr + jnp.sum(p, axis=-1)
        o_new = (o * corr[..., None]
                 + jnp.einsum("bhqk,bkhd->bhqd", p,
                              v_blk.astype(jnp.float32)))
        k_next = jax.lax.ppermute(k_blk, axis_name, perm)
        v_next = jax.lax.ppermute(v_blk, axis_name, perm)
        pos_next = jax.lax.ppermute(kv_pos, axis_name, perm)
        return (o_new, l_new, m_new, k_next, v_next, pos_next), None

    o0 = jnp.zeros((b, h, s_loc, d), jnp.float32)
    l0 = jnp.zeros((b, h, s_loc), jnp.float32)
    m0 = jnp.full((b, h, s_loc), _NEG_INF, jnp.float32)
    (o, l, m, _, _, _), _ = jax.lax.scan(
        step, (o0, l0, m0, k, v, q_pos), None, length=axis_size)
    l = jnp.maximum(l, 1e-20)
    out = (o / l[..., None]).transpose(0, 2, 1, 3)  # [B,S_loc,H,D]
    return out.astype(q.dtype)


def ring_attention(q, k, v, *, mesh, axis_name: str = "sp",
                   causal: bool = True, positions=None):
    """Sequence-parallel attention: shard_map manual over `axis_name` only;
    batch/head axes stay under the automatic (GSPMD) partitioner."""
    if positions is None:
        positions = jnp.arange(q.shape[1])
    spec = P(None, axis_name, None, None)
    body = functools.partial(ring_attention_manual, axis_name=axis_name,
                             causal=causal)
    return jax.shard_map(
        body, mesh=mesh, in_specs=(spec, spec, spec, P(axis_name)),
        out_specs=spec, axis_names={axis_name}, check_vma=False,
    )(q, k, v, positions)


def attention(q, k, v, *, causal: bool = True, mesh=None,
              sp_axis: str = "sp", positions=None, manual_sp: bool = False):
    """Dispatch:
    - `manual_sp=True`: already inside a shard_map manual over `sp_axis`
      (e.g. a pipeline stage) — run the ring body directly.
    - mesh shards the sequence axis — wrap in shard_map ring.
    - one chip, causal, default positions, a shape the kernels take: the
      flash kernels.
    - otherwise plain attention.

    Whichever path ran, what a checkpointed layer may keep of it for the
    backward carries the `jax.checkpoint` name ``attn_out``: the output
    itself on the XLA and ring paths; on the flash path the kernels' VJP
    names it (as ``[B, S, H * D]``) and its log-sum-exp, ``attn_lse``
    (`ops/flash_attention.py:_flash_mha_fwd`).
    """
    if manual_sp:
        if positions is None:
            # A local arange would give every sp rank positions 0..S_loc-1
            # and a silently wrong causal mask; derive the global block
            # positions from the rank instead.
            rank = jax.lax.axis_index(sp_axis)
            positions = rank * q.shape[1] + jnp.arange(q.shape[1])
        o = ring_attention_manual(q, k, v, positions, axis_name=sp_axis,
                                  causal=causal)
    elif mesh is not None and sp_axis in mesh.axis_names \
            and mesh.shape[sp_axis] > 1:
        o = ring_attention(q, k, v, mesh=mesh, axis_name=sp_axis,
                           causal=causal, positions=positions)
    else:
        # positions=None means standard arange — exactly what the fused
        # TPU kernel's causal mask implements. Single-chip only: a
        # pallas_call has no SPMD partitioning rule, so under a >1-device
        # mesh (dp/tp sharded q/k/v) we stay on the XLA path instead of
        # forcing an all-gather.
        unsharded = mesh is None or all(
            mesh.shape[a] == 1 for a in mesh.axis_names)
        if positions is None and causal and unsharded and _flash_eligible(q):
            return flash_attention_tpu(q, k, v)
        o = plain_attention(q, k, v, causal=causal, positions=positions)
    return checkpoint_name(o, ATTN_OUT)
