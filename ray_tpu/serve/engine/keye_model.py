"""The engine model of the sparse decoder of `models/keye_vl2.py`: every
layer's attention selects its keys. A learned indexer scores every
earlier position for a query (16 index heads against ONE index key a
position), the query attends to the `index_topk` positions with the
largest scores (its own competing like any other) and to no other; 32
query heads over 4 key/value heads of 128 with an RMSNorm a head on q and
k, a rotary in sections over three position streams (the engine has text
alone and passes one stream thrice), held experts behind a softmax router
without a shared expert in every layer.

What a position keeps: its keys and values, ``[layers, 2, Hkv, hd]`` in
the cache's global group (`kv_token_shape`; at 4 key/value heads the
pool is held by planes, ``[blocks, layers, 2 * Hkv, block_size, hd]``:
`kv_planes`, `ops.paged_attention.held_by_planes`), and its index key,
``[layers, di]`` in rows of whole lanes (`index_row_width`: 64 values in
128), in a pool that *rides* the global group's blocks
(`kv_groups["index"]`, `kv_cache.py`: ``[blocks, layers, block_size,
128]``, read through the same table). A step reads the index pool whole
(every live position of every row, every layer) and the KV of the
selected positions.

A decode step is one compiled program: in, one int32 array ``[b_pad, 4 +
nb_pad]`` (token, position, write offset, write block, the table); out,
one int32 array ``[b_pad + 3]``. A layer of it: the index scores over the
row's pages (`ops.sparse_attention.paged_index_scores`, in a trace
``paged_index_scores``), the exact selection (`select_topk`, the scope
``index_select``), the attention over the selected positions (the scope
``attn_selected``) by the walk of `ops/paged_attention.py` under a keep
mask (``paged_decode_attention``, the kernel the other models run): every
live page of the row is fetched as for a layer that sees them all, and
the positions not selected are masked. (A fetch of the selected rows
alone, a copy of 2 KB each, is slower on the chip at every length a cell
reaches: PERF.md, Findings, PR 57; ROADMAP R13 a.) A bucket whose table
cannot hold more than `index_topk` positions selects nothing and passes
no mask.

Prefill runs a prompt of at most `prefill_chunk_tokens` whole and a
longer one in chunks (`prefill_chunk`, the protocol of
`layer_groups_model.py`): a chunk's queries score the index keys the pool
already holds and its own, a query past position `index_topk - 1` keeps a
key iff its score is at least its `index_topk`-th largest, and the
forward (`ops.attention.prefill_attention` with `keep`) masks the rest.

Arithmetic: weights and both pools in `cfg.dtype` (bf16 on the chip);
the residual stream, norms, softmax, rotary tables, the router's product
and the index scores' ReLU, weights and sum in float32; a matrix product
takes both operands in `cfg.dtype` and accumulates in float32; the index
key is rounded to `cfg.dtype` when stored, and the step's own score is
taken against the rounded key.
"""

from __future__ import annotations

import math
from typing import List, Sequence

import numpy as np

from ray_tpu.core import flight
from ray_tpu.serve.engine.layer_groups_model import GLOBAL, PromptGroups
from ray_tpu.serve.engine.model import (PromptKV, _next_pow2,
                                        place_sources, step_tokens)
from ray_tpu.serve.engine.sparse_model import SparseEngineModel

INDEX = "index"


class KeyeEngineModel(SparseEngineModel):
    """Incremental decoding over `models/keye_vl2.py` weights.

    KV entry a token: ``[n_layers, 2, n_kv_heads, head_dim]`` in the
    global group (held by planes where `kv_planes` says so) and
    ``[n_layers, 128]`` in the index pool."""

    # What the engine's `stats()` adds of this model's own (summed over
    # decode steps, rows x layers): positions the indexer scored, those
    # attended to, those whose KV the attention's body fetched, and the
    # index keys' bytes; queries of prefills past `index_topk`.
    own_counters = ("decode_index_tokens_scored", "decode_kv_tokens_selected",
                    "decode_kv_tokens_read", "decode_index_bytes_read",
                    "prefill_selected_queries")

    # As `layer_groups_model.py`'s: a prompt of at most as many positions
    # is prefilled whole, a longer one in chunks of as many.
    prefill_chunk_tokens = 1024

    def __init__(self, params, cfg, max_batch_size: int = 8,
                 jit_cache_cap: int = 32):
        from ray_tpu.ops.paged_attention import (attention_widths,
                                                 by_planes, held_by_planes,
                                                 kernel_eligible, page_groups)
        from ray_tpu.ops.sparse_attention import index_row_width

        super().__init__(params, cfg, jit_cache_cap, max_batch_size)
        self._page_groups, self._by_planes = page_groups, by_planes
        itemsize = self.kv_dtype.itemsize
        self.kv_token_shape = (cfg.n_layers, 2, cfg.n_kv_heads,
                               cfg.head_dim)
        # How the cache holds the global group's pool, from the head
        # count (`ops.paged_attention.held_by_planes`).
        self.kv_planes = {GLOBAL: held_by_planes(cfg.n_kv_heads)}
        # An index key lies in a row of whole lanes (64 values in 128).
        self._index_row = index_row_width(cfg.index_dim)
        self.kv_groups = {INDEX: {"kv_shape": (cfg.n_layers, self._index_row),
                                  "rides": True}}
        self.kv_token_bytes = math.prod(self.kv_token_shape) * itemsize
        # A position's index keys as the pool holds them and as the
        # model counts them.
        self.index_token_bytes_held = (cfg.n_layers * self._index_row
                                       * itemsize)
        self.index_token_bytes_model = (cfg.n_layers * cfg.index_dim
                                        * itemsize)
        self._attn_inplace = kernel_eligible(*attention_widths(
            cfg.n_heads, cfg.head_dim, cfg.n_kv_heads, cfg.head_dim))
        for name in self.own_counters:
            setattr(self, name, 0)
        self.decode_kv_bytes_read_held = 0
        self.decode_kv_bytes_read_model = 0

    # -- the layer's pieces --------------------------------------------
    def _ropes(self, positions):
        """(cos, sin) of the heads' sectioned rotary and of the
        indexer's plain one at `positions` (text: three equal streams)."""
        import jax.numpy as jnp

        from ray_tpu.ops.rotary import (rotary_cos_sin,
                                        rotary_cos_sin_sections,
                                        rotary_inv_freq)

        cfg = self._cfg
        streams = jnp.broadcast_to(positions[None],
                                   (len(cfg.mrope_section),)
                                   + positions.shape)
        return (rotary_cos_sin_sections(
                    streams, rotary_inv_freq(cfg.head_dim, cfg.rope_theta),
                    cfg.mrope_section),
                rotary_cos_sin(positions, rotary_inv_freq(cfg.index_dim,
                                                          cfg.rope_theta)))

    def _qkv(self, y, lp, rope):
        """The normed, rotated q ``[T, H, hd]`` and k ``[T, Hkv, hd]``,
        and v ``[T, Hkv, hd]``, float32."""
        from ray_tpu.ops.rotary import apply_rotary_partial

        cfg = self._cfg
        t = y.shape[0]
        q = self._mm(y, lp["wq"]).reshape(t, cfg.n_heads, cfg.head_dim)
        k = self._mm(y, lp["wk"]).reshape(t, cfg.n_kv_heads, cfg.head_dim)
        v = self._mm(y, lp["wv"]).reshape(t, cfg.n_kv_heads, cfg.head_dim)
        return (apply_rotary_partial(self._norm(q, lp["q_norm"]), *rope),
                apply_rotary_partial(self._norm(k, lp["k_norm"]), *rope), v)

    def _index(self, y, ip, rope):
        """The indexer's rotated queries ``[T, J, di]``, its rotated key
        ``[T, di]`` and the heads' weights ``[T, J]``, float32."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.ops.rotary import apply_rotary_partial

        cfg = self._cfg
        t = y.shape[0]
        qi = self._mm(y, ip["wq"]).reshape(t, cfg.index_heads,
                                           cfg.index_dim)
        ki = self._mm(y, ip["wk"])
        ki = ki - jnp.mean(ki, axis=-1, keepdims=True)
        ki = (ki * jax.lax.rsqrt(jnp.mean(jnp.square(ki), axis=-1,
                                          keepdims=True) + cfg.norm_eps)
              * ip["k_scale"] + ip["k_bias"])
        w = self._mm(y, ip["ww"]) * (cfg.index_heads * cfg.index_dim) ** -0.5
        return (apply_rotary_partial(qi, *rope),
                apply_rotary_partial(ki[:, None], *rope)[:, 0], w)

    def _index_row_of(self, ki):
        """Index keys ``[T, di]`` as the pool's rows ``[T, row]``."""
        import jax.numpy as jnp

        return jnp.pad(ki, ((0, 0), (0, self._index_row - ki.shape[1])))

    # -- prefill -------------------------------------------------------
    def _prompt_layers(self, params, tokens, pos, length, attend):
        """The layers over positions `pos` of one prompt, `tokens` there
        (a whole prompt in its bucket, or a chunk), of which the first
        `length` are live. ``attend(q, k, v, qi, w, ki, layer)`` is a
        layer's attention: q ``[S, H, hd]``, its own k, v ``[S, Hkv,
        hd]`` and index key ``[S, di]`` in the pools' dtype, the index
        queries ``[S, J, di]`` and weights ``[S, J]``; ``[H, S, hd]``
        out. Returns the logits after the last live position, the KV
        rows ``[S, layers, 2, Hkv, hd]`` and the index rows ``[S,
        layers, di]``."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.ops.paged_attention import kv_row

        act = params["embed"].dtype
        with jax.named_scope("embed"):
            x = params["embed"][tokens].astype(jnp.float32)    # [S, d]
        live = jnp.arange(tokens.shape[0]) < length
        rope, rope_index = self._ropes(pos)
        rows, index_rows = [], []
        for layer, lp in enumerate(params["layers"]):
            with jax.named_scope("attn_selected"):
                y = self._norm(x, lp["ln1"])
                q, k, v = self._qkv(y, lp["mixer"], rope)
                qi, ki, w = self._index(y, lp["indexer"], rope_index)
                k, v, ki = k.astype(act), v.astype(act), ki.astype(act)
                o = attend(q.astype(act), k, v, qi, w, ki, layer)
                x = x + self._mm(o.transpose(1, 0, 2).reshape(
                    tokens.shape[0], -1), lp["mixer"]["wo"])
            rows.append(kv_row(k, v))
            index_rows.append(self._index_row_of(ki))
            x, _ = self._experts(x, lp["ln2"], lp["mlp"], live)
        with jax.named_scope("lm_head"):
            last = self._norm(x[length - 1], params["ln_f"])
            logits = self._mm(last[None], params["head"])[0]
        return (logits, jnp.stack(rows, axis=1),
                jnp.stack(index_rows, axis=1))

    def _selected_attention(self, q, keys, vals, qi, w, index_keys, offset,
                            live):
        """A prompt's or a chunk's attention ``[H, Sq, hd]``, q ``[Sq, H,
        hd]`` over keys and vals ``[Hkv, Sk, hd]``: query ``i`` lies on
        key ``offset + i`` and of the keys the first `live` exist; where
        they can be more than `index_topk`, under the indexer's
        selection."""
        from ray_tpu.ops.attention import prefill_attention
        from ray_tpu.ops.sparse_attention import (prefill_index_scores,
                                                  prefill_keep)

        keep = None
        if keys.shape[1] > self._cfg.index_topk:
            scores = prefill_index_scores(qi.transpose(1, 0, 2), w,
                                          index_keys, offset)
            keep = prefill_keep(scores, offset, live, self._cfg.index_topk)
        return prefill_attention(q.transpose(1, 0, 2), keys, vals,
                                 offset=offset, live=live, keep=keep)

    def _build_prefill(self, s_pad: int):
        import jax
        import jax.numpy as jnp

        self.jit_compiles += 1

        def attend(q, k, v, qi, w, ki, layer):
            # A padded position lies after every live one: the causal
            # mask alone keeps it from a live query.
            return self._selected_attention(
                q, k.transpose(1, 0, 2), v.transpose(1, 0, 2), qi, w, ki, 0,
                s_pad)

        def prefill(params, tokens, length):
            return self._prompt_layers(params, tokens, jnp.arange(s_pad),
                                       length, attend)

        return jax.jit(prefill)

    def _build_prefill_chunk(self, s_keys: int, block_size: int):
        """The program of one chunk of a prompt whose keys lie in
        `s_keys` positions: the chunk's place comes in as a scalar."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.ops.paged_attention import heads_of_pages

        self.jit_compiles += 1
        cfg, c = self._cfg, self.prefill_chunk_tokens

        def prefill_chunk(pools, params, packed):
            tokens, start, length = packed[:c], packed[c], packed[c + 1]
            table = packed[c + 2:]
            # What the pools hold of the positions before the chunk
            # (whatever the pages hold from `start` on, no query sees
            # it).
            with jax.named_scope("kv_gather"):
                index_before = pools[INDEX][table]     # [nb, L, bs, row]
            zero = jnp.int32(0)

            def attend(q, k, v, qi, w, ki, layer):
                with jax.named_scope("kv_gather"):
                    old_k, old_v = heads_of_pages(
                        pools[GLOBAL], table, layer, cfg.n_kv_heads,
                        cfg.head_dim)
                at = (zero, start, zero)
                return self._selected_attention(
                    q, jax.lax.dynamic_update_slice(
                        old_k, k.transpose(1, 0, 2), at),
                    jax.lax.dynamic_update_slice(
                        old_v, v.transpose(1, 0, 2), at), qi, w,
                    jax.lax.dynamic_update_slice(
                        index_before[:, layer].reshape(
                            s_keys, -1)[:, :cfg.index_dim],
                        ki, (start, zero)),
                    start, start + c)

            return self._prompt_layers(params, tokens,
                                       start + jnp.arange(c), length, attend)

        return jax.jit(prefill_chunk)

    # -- decode --------------------------------------------------------
    def _build_decode_paged(self, b_pad: int, nb_pad: int,
                            block_size: int, probe: bool = False):
        """The step's program; with `probe`, a program over the same
        arguments that writes nothing and returns what each layer kept,
        ``[layers, b_pad, nb_pad * block_size + 1]`` bool (the last
        column the step's own position)."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.ops.paged_attention import (kv_row,
                                                 paged_decode_attention,
                                                 write_rows)
        from ray_tpu.ops.sparse_attention import (own_index_scores,
                                                  paged_index_scores,
                                                  select_topk)
        from ray_tpu.serve.engine.kv_cache import rider_slots

        self.jit_compiles += 1
        cfg, f32 = self._cfg, jnp.float32
        s_pad = nb_pad * block_size
        selects = s_pad + 1 > cfg.index_topk

        def attend(q, k, v, qi, w, ki, pools, tables, positions, layer):
            if not selects:
                return paged_decode_attention(q, k, v, pools[GLOBAL], tables,
                                              positions, layer)
            cached = jnp.arange(s_pad)[None, :] < positions[:, None]
            scores = jnp.concatenate(
                [paged_index_scores(qi, w, pools[INDEX], tables, positions,
                                    layer),
                 own_index_scores(qi, w, ki)[:, None]], axis=1)
            keep = select_topk(
                scores, jnp.pad(cached, ((0, 0), (0, 1)),
                                constant_values=True), cfg.index_topk)
            kept.append(keep)
            return paged_decode_attention(
                q, k, v, pools[GLOBAL], tables, positions, layer,
                keep=keep[:, :-1], own_keep=keep[:, -1])

        kept = []       # a trace's: what each layer kept

        def decode_paged(pools, params, packed, before):
            del kept[:]
            positions, woffs, wblocks = (packed[:, i] for i in range(1, 4))
            tokens = step_tokens(packed, before)
            tables = packed[:, 4:-1]
            # A padding row writes past both pools: it routes nowhere.
            valid = wblocks < pools[GLOBAL].shape[0]
            act = pools[GLOBAL].dtype
            with jax.named_scope("embed"):
                x = params["embed"][tokens].astype(f32)        # [B, d]
            rope, rope_index = self._ropes(positions)
            rows, index_rows = [], []
            counts = jnp.zeros((3,), jnp.int32)
            for layer, lp in enumerate(params["layers"]):
                with jax.named_scope("attn_selected"):
                    y = self._norm(x, lp["ln1"])
                    q, k, v = self._qkv(y, lp["mixer"], rope)
                    qi, ki, w = self._index(y, lp["indexer"], rope_index)
                    k, v, ki = k.astype(act), v.astype(act), ki.astype(act)
                    with jax.named_scope("kv_gather"):
                        o = attend(q, k, v, qi, w, ki, pools, tables,
                                   positions, jnp.int32(layer))
                    x = x + self._mm(o.reshape(o.shape[0], -1),
                                     lp["mixer"]["wo"])
                rows.append(kv_row(k, v))
                index_rows.append(self._index_row_of(ki))
                x, c = self._experts(x, lp["ln2"], lp["mlp"], valid)
                counts += c
            with jax.named_scope("lm_head"):
                logits = self._mm(self._norm(x, params["ln_f"]),
                                  params["head"])
            with jax.named_scope("kv_write"):
                new_pools = {
                    GLOBAL: write_rows(pools[GLOBAL], wblocks, woffs,
                                       jnp.stack(rows, axis=1)),
                    INDEX: pools[INDEX].at[
                        rider_slots(pools[INDEX], wblocks, woffs)].set(
                        jnp.stack(index_rows, axis=1), mode="drop")}
            with jax.named_scope("sample"):
                ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return self._step_out(ids, counts, b_pad), logits, new_pools

        if probe:
            def kept_by_layer(pools, params, packed):
                # Every token from the host: no step before.
                decode_paged(pools, params, packed, jnp.zeros(
                    (self._ids_width(b_pad) + self._ids_trail,), jnp.int32))
                if not kept:      # nothing to select: every live position
                    cached = (jnp.arange(s_pad + 1)[None, :]
                              < packed[:, 1:2])
                    own = jnp.arange(s_pad + 1)[None, :] == s_pad
                    return jnp.broadcast_to(
                        cached | own, (cfg.n_layers,) + cached.shape)
                return jnp.stack(kept)

            return jax.jit(kept_by_layer)
        return jax.jit(decode_paged, donate_argnums=(0,))

    # -- engine interface ----------------------------------------------
    def _prompt_rows(self, kv, index_rows, n: int) -> PromptGroups:
        return PromptGroups(kv, n, {INDEX: PromptKV(index_rows, n)})

    def _count_selected_queries(self, start: int, end: int) -> None:
        self.prefill_selected_queries += max(
            0, end - max(start, self._cfg.index_topk))

    def prefill(self, tokens: Sequence[int]):
        """Run the prompt. Returns the host logits that predict the next
        token and a `PromptGroups`: the prompt's KV rows and, under
        ``groups["index"]``, its index keys, still on the device."""
        with flight.span("model", "prefill", len(tokens)):
            logits, (kv, index_rows), n = self._run_prefill(tokens)
            self._count_selected_queries(0, n)
            return logits, self._prompt_rows(kv, index_rows, n)

    def prefill_chunk(self, tokens: Sequence[int], pools, tables: dict,
                      start: int, block_size: int, *, meanwhile=None):
        """Run positions ``[start, start + prefill_chunk_tokens)`` of the
        prompt `tokens`, whose positions before `start` are in `pools`
        (the KV's and the index keys'), read through `tables` (the
        sequence's `step_tables`). The protocol of
        `layer_groups_model.LayerGroupsEngineModel.prefill_chunk`: the
        host logits for the chunk that holds the prompt's last token,
        else None, and the chunk's rows for `write_range(seq, start,
        ...)`. One program a power of two of the prompt's length."""
        with flight.span("model", "prefill", len(tokens)):
            return self._prefill_chunk(tokens, pools, tables, start,
                                       block_size, meanwhile)

    def _prefill_chunk(self, tokens, pools, tables, start: int,
                       block_size: int, meanwhile):
        phase, c = self.phase, self.prefill_chunk_tokens
        n = len(tokens)
        length = min(c, n - start)
        self.prefill_calls += 1
        self.prefill_tokens += length
        self._count_selected_queries(start, start + length)
        with flight.span("model", "prefill.prep", None, phase,
                         "prefill_prep_s"):
            if start % c or c % block_size:
                raise ValueError(
                    f"a chunk of {c} positions at {start} does not lie on "
                    f"blocks of {block_size}")
            s_keys = max(_next_pow2(-(-n // c) * c), 4 * c)
            key = ("chunk", c, s_keys, block_size)
            fn = self._prefill_jit.get(key)
            if fn is None:
                fn = self._prefill_jit[key] = \
                    self._build_prefill_chunk(*key[2:])
            nb = s_keys // block_size
            packed = np.zeros((c + 2 + nb,), np.int32)
            packed[:length] = np.asarray(tokens[start:start + length],
                                         np.int32)
            packed[c], packed[c + 1] = start, length
            _, table = tables[GLOBAL]
            packed[c + 2:c + 2 + min(nb, len(table))] = table[:nb]
        with flight.span("model", "prefill.dispatch", None, phase,
                         "prefill_dispatch_s"):
            logits, kv, index_rows = fn(pools, self._params, packed)
        self._count_experts_step(c)
        if meanwhile is not None:
            meanwhile()
        logits = self._prompt_logits(logits, start + length == n)
        return logits, self._prompt_rows(kv, index_rows, length)

    def decode_paged(self, pools, block_tables: List[dict],
                     last_tokens: Sequence[int],
                     positions: Sequence[int], write_blocks: dict,
                     write_offs: dict, block_size: int, *,
                     meanwhile=None, ahead=None):
        """One fused step. `pools` is ``{"global": KV pool, "index":
        index pool}``, `write_blocks` and `write_offs` name the global
        group's slots (the index pool's are the same), ``block_tables[i]``
        is row i's ``{"global": (0, table)}`` (`step_tables`). A write
        list shorter than the batch leaves the other rows unwritten, as
        in a warm-up. Returns ``(step, new_pools)``; both pools were
        donated."""
        with flight.span("model", "decode", len(last_tokens)):
            return self._decode_paged(pools, block_tables, last_tokens,
                                      positions, write_blocks, write_offs,
                                      block_size, meanwhile, ahead)

    def _decode_paged(self, pools, block_tables, last_tokens, positions,
                      write_blocks, write_offs, block_size: int,
                      meanwhile, ahead):
        b = len(last_tokens)
        self.decode_calls += 1
        with flight.span("model", "decode.prep", None, self.phase,
                         "decode_prep_s"):
            b_pad, nb_pad = self._step_bucket(positions, block_size)
            self._count_step(pools, positions, nb_pad, block_size)
            key = (b_pad, nb_pad, block_size)
            fn = self._decode_paged_jit.get(key)
            if fn is None:
                fn = self._decode_paged_jit[key] = \
                    self._build_decode_paged(*key)
            packed = self._pack_step(pools, block_tables, last_tokens,
                                     positions, b_pad, nb_pad)
            k = min(len(write_blocks.get(GLOBAL, ())), b)
            packed[:k, 2] = write_offs[GLOBAL][:k]
            packed[:k, 3] = write_blocks[GLOBAL][:k]
            place_sources(packed, ahead)
            args = (pools, self._params, packed)
        step, (new_pools,) = self._run_decode(fn, args, b, b_pad, meanwhile,
                                              ahead)
        return step, new_pools

    def probe_selection(self, pools, block_tables: List[dict],
                        last_tokens: Sequence[int],
                        positions: Sequence[int], block_size: int):
        """What each layer of the decode step over these rows would keep
        (`_build_decode_paged` with `probe`), on the host: ``[layers,
        rows, positions + 1]`` bool, a row's own position last. Writes
        nothing and counts nothing: the benchmark's check and the tests
        compare it with the reference's selection."""
        b = len(last_tokens)
        b_pad, nb_pad = self._step_bucket(positions, block_size)
        key = ("probe", b_pad, nb_pad, block_size)
        fn = self._decode_paged_jit.get(key)
        if fn is None:
            fn = self._decode_paged_jit[key] = self._build_decode_paged(
                *key[1:], probe=True)
        packed = self._pack_step(pools, block_tables, last_tokens,
                                 positions, b_pad, nb_pad)
        # Any block inside the pool: the row is a live one to the expert
        # layers, and the probe's program writes nothing.
        packed[:b, 3] = 0
        return np.asarray(fn(pools, self._params, packed))[:, :b]

    @staticmethod
    def _step_bucket(positions, block_size: int):
        """(rows, table columns) of the program a step over rows at
        `positions` runs: powers of two."""
        return (_next_pow2(max(len(positions), 1)),
                _next_pow2(max(max(int(p) // block_size + 1
                                   for p in positions), 1)))

    @staticmethod
    def _pack_step(pools, block_tables, last_tokens, positions, b_pad: int,
                   nb_pad: int):
        """The step's one host buffer, a row a sequence: token, position,
        write offset, write block (past the pools: dropped, until the
        caller names a slot), the table, the row's place in the step
        before's ids (none, until the caller names one)."""
        packed = np.zeros((b_pad, 5 + nb_pad), np.int32)
        packed[:, 3] = int(pools[GLOBAL].shape[0])
        packed[:, -1] = -1
        for i, (token, position) in enumerate(zip(last_tokens, positions)):
            table = block_tables[i][GLOBAL][1][:nb_pad]
            packed[i, 0], packed[i, 1] = token, position
            packed[i, 4:4 + len(table)] = table
        return packed

    def _count_step(self, pools, positions, nb_pad: int,
                    block_size: int) -> None:
        """A step's counters, by the arithmetic of its program: a row at
        position ``p`` has ``p + 1`` live positions (its own among
        them); the walk fetches the KV of every one of them, whatever the
        layer selects. Where the kernels run, as `layer_groups_model.py`
        counts them: the live pages the tables named, the groups they
        came in, and those pages' bytes of both pools as they hold a
        position and as the model counts one (an index key lies in a row
        of `index_row_width` values)."""
        cfg = self._cfg
        layers = cfg.n_layers
        live = sum(int(p) + 1 for p in positions)
        selects = nb_pad * block_size + 1 > cfg.index_topk
        self.decode_kv_tokens_selected += layers * sum(
            min(int(p) + 1, cfg.index_topk) for p in positions)
        self.decode_kv_tokens_read += live * layers
        if selects:
            self.decode_index_tokens_scored += live * layers
            self.decode_index_bytes_read += (
                live * self.index_token_bytes_held)
        if self._attn_inplace:
            cached = sum(-(-int(p) // block_size) for p in positions)
            self.decode_attn_inplace_steps += 1
            self.decode_kv_pages_read += cached
            if self._by_planes(pools[GLOBAL]):
                self.decode_kv_pages_read_planes += cached
            self.decode_kv_page_groups_read += self._page_groups(
                pools[GLOBAL], nb_pad, positions)
            index = ((self.index_token_bytes_held,
                      self.index_token_bytes_model) if selects else (0, 0))
            self.decode_kv_bytes_read_held += cached * block_size * (
                self.kv_token_bytes + index[0])
            self.decode_kv_bytes_read_model += cached * block_size * (
                self.kv_token_bytes + index[1])
