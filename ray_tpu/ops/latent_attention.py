"""Multi-head latent attention (MLA) over a cache of one row a position.

A position keeps ``[c_kv, k_r]``: the compressed latent (``rank`` values,
normed) and one rotary key (``rope`` values, rotated) that every head
shares. A head's keys and values are linear in the latent, ``k_nope,h =
c_kv W_uk,h`` and ``v_h = c_kv W_uv,h``, which gives the attention two
forms with the same numbers:

- *expanded* (a prompt, a chunk of one): the latents are multiplied out
  into keys ``[k_nope,h | k_r]`` and values a head (`expand_latent`) and
  go through the prefill's forward like any other keys of ``nope +
  rope`` over values of ``dv`` (`ops.attention.prefill_attention`).
- *absorbed* (a decode step): the query takes ``W_uk`` instead,
  ``q_abs,h = W_uk,h q_nope,h`` (`absorb_query`), and meets the latent
  rows as they lie: ``score = q_abs,h . c_kv + q_r,h . k_r``, ``o_lat,h =
  sum p c_kv``, then ``o_h = W_uv,h^T o_lat,h`` (`unabsorb_output`). The
  keys and values a head are never formed, and a row is read once for
  all heads: `paged_latent_decode_attention`.

**The pool.** A row of ``rank + rope`` values (576) is held in whole
planes of 128 lanes: ``[num_blocks, layers, P, block_size, 128]`` with
``P = ceil((rank + rope) / 128)`` (5: a row of 640, the last 64 lanes
zeros), the cache manager's *planes* layout (`kv_cache.py`; the model's
``kv_token_shape`` is ``(layers, P, 128)``). A layer's page ``pool[block,
layer]`` is one contiguous piece of whole ``[16, 128]`` bf16 tiles, so
the decode walk of `ops/paged_attention.py` (tables, walks, two slabs,
copies eight a turn) fetches it as it fetches any page held by planes,
and only the body differs (`paged_attention._attend_latent`): one fetch a
page, the values being the row's own first ``rank`` lanes. `latent_row`
and `rows_of_pages` go between a position's ``[rank + rope]`` values and
its planes.

Arithmetic of the two decode products: operands in the pool's dtype
(bf16 on the chip), float32 accumulation, float32 softmax; the query and
the probabilities are rounded to the pool's dtype for them, as the
model's other products round theirs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import _NEG_INF
from ray_tpu.ops.paged_attention import paged_decode_attention_kernel

LANES = 128
KERNEL_NAME = "paged_latent_decode_attention"


def latent_planes(width: int) -> int:
    """Planes of `LANES` lanes a row of `width` values takes."""
    return -(-width // LANES)


def latent_row(c_kv, k_r):
    """The cache rows ``[T, P, 128]`` of latents ``[T, rank]`` and rotary
    keys ``[T, rope]``: side by side, filled up with zeros to whole
    planes."""
    row = jnp.concatenate([c_kv, k_r.astype(c_kv.dtype)], axis=-1)
    planes = latent_planes(row.shape[-1])
    row = jnp.pad(row, ((0, 0), (0, planes * LANES - row.shape[-1])))
    return row.reshape(row.shape[0], planes, LANES)


def rows_of_pages(pages):
    """Pages ``[..., nb, P, bs, 128]`` of one layer of a latent pool ->
    the positions' rows ``[..., nb * bs, 128 P]``."""
    *lead, nb, planes, bs, lanes = pages.shape
    at = len(lead)
    rows = pages.transpose(*range(at), at, at + 2, at + 1, at + 3)
    return rows.reshape(*lead, nb * bs, planes * lanes)


def expand_latent(c_kv, k_r, wuk, wuv, n_heads: int):
    """The *expanded* form's keys and values of latents ``[S, rank]`` and
    rotary keys ``[S, rope]``: ``([H, S, nope + rope], [H, S, dv])`` in
    the latents' dtype, a head's keys its ``c_kv W_uk,h`` beside the one
    rotary key. wuk ``[rank, H * nope]``, wuv ``[rank, H * dv]``; both
    operands of a product in the weights' dtype, float32 accumulation."""
    s = c_kv.shape[0]
    act = c_kv.dtype

    def heads(w):
        out = jnp.dot(c_kv.astype(w.dtype), w,
                      preferred_element_type=jnp.float32)
        return out.astype(act).reshape(s, n_heads, -1).transpose(1, 0, 2)

    k_nope, v = heads(wuk), heads(wuv)
    k_r = jnp.broadcast_to(k_r.astype(act)[None], (n_heads,) + k_r.shape)
    return jnp.concatenate([k_nope, k_r], axis=-1), v


def absorb_query(q_nope, q_r, wuk, width: int):
    """The *absorbed* query ``[B, H, width]`` float32 of q_nope ``[B, H,
    nope]`` and q_r ``[B, H, rope]``: ``[W_uk,h q_nope,h | q_r,h]``, as
    wide as the pool's row (zeros past ``rank + rope``). wuk ``[rank, H *
    nope]``."""
    _, h, nope = q_nope.shape
    w = wuk.reshape(wuk.shape[0], h, nope)
    q_abs = jnp.einsum("bhn,chn->bhc", q_nope.astype(w.dtype), w,
                       preferred_element_type=jnp.float32)
    q = jnp.concatenate([q_abs, q_r.astype(jnp.float32)], axis=-1)
    return jnp.pad(q, ((0, 0), (0, 0), (0, width - q.shape[-1])))


def unabsorb_output(o_lat, wuv):
    """``o_h = W_uv,h^T o_lat,h``: o_lat ``[B, H, rank]`` float32, wuv
    ``[rank, H * dv]`` -> ``[B, H, dv]`` float32."""
    _, h, rank = o_lat.shape
    w = wuv.reshape(rank, h, -1)
    return jnp.einsum("bhc,chd->bhd", o_lat.astype(w.dtype), w,
                      preferred_element_type=jnp.float32)


def kernel_eligible(n_heads: int, rank: int) -> bool:
    """The walk's kernel needs the TPU backend, values of whole planes
    and query heads of whole sublanes."""
    return (jax.default_backend() == "tpu" and rank % LANES == 0
            and n_heads % 8 == 0)


def paged_latent_decode_attention_xla(q, row_new, pool, tables, positions,
                                      layer, rank: int, scale: float):
    """The body off the chip: the same products over the gathered pages.
    q ``[B, H, 128 P]`` float32 (`absorb_query`); row_new ``[B, 128 P]``,
    the step's own rows (not yet in the pool); pool ``[N, L, P, bs,
    128]``; tables ``[B, nb]`` int32; positions ``[B]``. Returns ``[B, H,
    rank]`` float32: the probabilities against the rows' first `rank`
    lanes. Pool positions at or past a row's `position` may hold
    anything: they are masked."""
    f32 = jnp.float32
    rows = rows_of_pages(pool[tables, layer])              # [B, S, 128 P]
    act = rows.dtype
    scores = jnp.einsum("bhw,bsw->bhs", q.astype(act), rows,
                        preferred_element_type=f32) * scale
    at = jnp.arange(rows.shape[1])[None, :]
    scores = jnp.where((at < positions[:, None])[:, None], scores, _NEG_INF)
    row_new = row_new.astype(act)
    own = jnp.einsum("bhw,bw->bh", q, row_new.astype(f32))[..., None] * scale
    probs = jax.nn.softmax(jnp.concatenate([scores, own], axis=-1), axis=-1)
    return (jnp.einsum("bhs,bsc->bhc", probs[..., :-1].astype(act),
                       rows[..., :rank], preferred_element_type=f32)
            + probs[..., -1:] * row_new[:, None, :rank].astype(f32))


def paged_latent_decode_attention(q, row_new, pool, tables, positions,
                                  layer, rank: int, scale: float, *,
                                  interpret: bool = None):
    """One layer's absorbed decode attention through the block tables:
    `ops.paged_attention`'s walk with the latent body on the chip (under
    the name ``paged_latent_decode_attention``), the XLA body elsewhere.
    Arguments and result as `paged_latent_decode_attention_xla`."""
    if interpret is None and not kernel_eligible(q.shape[1], rank):
        return paged_latent_decode_attention_xla(
            q, row_new, pool, tables, positions, layer, rank, scale)
    if pool.shape[2] * LANES != q.shape[2]:
        raise ValueError(f"a query of {q.shape[2]} values does not meet "
                         f"the rows of pool {pool.shape}")
    own = row_new[:, None, :]
    return paged_decode_attention_kernel(
        q, own, own[..., :rank], pool, tables, positions, layer,
        interpret=bool(interpret), name=KERNEL_NAME,
        latent_scale=float(scale))
