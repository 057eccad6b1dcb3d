"""The engine model of the dense decoder of `models/minicpm_sala.py`:
attention that selects its key BLOCKS from compressed keys of the cache
itself in some layers (32 query heads over 2 key/value heads, no rotary),
lightning linear-attention layers of one constant decay a head in the
others, a plain SwiGLU and muP scalings in every layer.

It is driven through the engine's calls (`model.py`) as a model with
state (`state_model.StateSteps`: `state_shapes`, the slot-order step, the
host side of a decode step; `state_model.StateChunks`: a chunk of a
prompt that begins from its sequence's slot) over the dense base
(`sparse_model.DecoderEngineModel`: no router, no expert counts behind a
step's ids).

What a sequence keeps:

- **KV rows** of the selecting layers, ``[sparse layers, Hkv, 2, hd]`` a
  position in the cache's one group, held by planes *head-major*
  (`ops/block_sparse_attention.py`: a key/value head's ``[K, V]`` page in
  one piece, because a head selects its own blocks).
- **A state slot** (`state_shapes`): ``s``, the lightning layers' ``[H,
  dk, dv]`` float32 states; ``ck``, the selecting layers' *compressed
  keys*, ``[sparse layers, Hkv, max_seq_len / stride, hd]`` in the pool's
  dtype; ``ksum``, per selecting layer the sum of the last whole stride
  of keys and of the stride being filled, float32 (a compressed key
  straddles two strides, so a step that completes one adds the two sums).
  The compressed keys lie in the slot and not in a pool that rides the
  KV's blocks: a step scores EVERY compressed key of a row, so a row's
  keys in one piece are read where they lie (2 MB a row-layer at 64k
  positions), where a rider would be gathered through the table (4,096
  pieces of 512 B), hold a row of them a block whether or not its stride
  is whole, and cost 1 KB a block of the pool (131,072 blocks: 134 MB)
  against 4 MB a slot (6 slots: 25 MB). What it costs: a slot's keys are
  sized for `max_seq_len` whatever the sequence's length.

A decode step is one compiled program a (batch, table) bucket. A
selecting layer of it: the step's key joins the stride sums and, where it
completes a stride, a compressed key is written (scope
``compress_keys``); the row's compressed keys are scored, pooled to
blocks and selected (``block_select``); the chosen blocks' pages are
walked a key/value head at a time (``attn_block_sparse``: the paged
walk's kernel under ``block_sparse_paged_decode_attention``). A bucket
whose table cannot reach `dense_len` selects nothing and walks the
table. A lightning layer of it: one step in slot order over the layer's
states where they lie in the pool (``lightning_attn``; on the chip the
kernel ``lightning_decode_step``).

Prefill runs a prompt of at most `prefill_chunk_tokens` whole and a
longer one in chunks that begin from the sequence's slot (the lightning
states, the compressed keys so far, the last stride's sum) and put what
they end on back; a chunk's selecting layers gather the earlier keys and
values by the table, score the compressed keys the slot holds and their
own, and run the flash forward a group at a time under the group's block
mask.

Arithmetic: weights, the KV pool and the compressed keys in `cfg.dtype`
(bf16 on the chip); the lightning state, the stride sums, the residual
stream, norms, softmax, decays and logits float32; a matrix product takes
both operands in `cfg.dtype` and accumulates in float32, the compressed
keys' scores among them; the lightning layers' own products are float32
at the highest precision (`ops/lightning_attention.py`). A compressed key
is the mean of the keys AS THE POOL HOLDS THEM (rounded to `cfg.dtype`),
summed in float32 and rounded once when stored.
"""

from __future__ import annotations

from typing import List

import numpy as np

from ray_tpu.serve.engine.kv_cache import KVCacheManager
from ray_tpu.serve.engine.model import _next_pow2, step_tokens
from ray_tpu.serve.engine.sparse_model import DecoderEngineModel
from ray_tpu.serve.engine.state_model import StateChunks, StateSteps


class MiniCPMSALAEngineModel(StateChunks, StateSteps, DecoderEngineModel):
    """Incremental decoding over `models/minicpm_sala.py` weights. A
    prompt of at most `prefill_chunk_tokens` is prefilled whole in pow2
    length buckets; a longer one the scheduler runs through
    `prefill_chunk`; a decode step is jitted a (batch, table) bucket."""

    prefill_chunk_tokens = 1024
    # Summed over decode steps, a (row, selecting layer, key/value head)
    # each: blocks that had a score, blocks attended to, cached positions
    # of the pages the attention's body fetched, live positions the
    # selection ranked (through their blocks' compressed keys: what
    # `keye_model.py` counts of its indexer), compressed keys written;
    # the lightning states' bytes a step's rows read and write; chunks
    # that began past position 0, and those of them that began from
    # their sequence's slot.
    own_counters = ("decode_blocks_scored", "decode_blocks_selected",
                    "decode_kv_tokens_read", "decode_index_tokens_scored",
                    "compressed_keys_written", "lightning_state_bytes_moved",
                    "prefill_later_chunks", "prefill_state_chunks")

    def __init__(self, params, cfg, max_batch_size: int = 8,
                 jit_cache_cap: int = 32, max_seq_len: int = 65536,
                 lightning_chunk: int = 128):
        import jax.numpy as jnp

        from ray_tpu.ops.paged_attention import (held_by_planes,
                                                 kernel_eligible,
                                                 pages_per_step)

        super().__init__(params, cfg, jit_cache_cap, max_batch_size)
        if (cfg.kernel_size != 2 * cfg.kernel_stride
                or cfg.sparse_block % cfg.kernel_stride):
            raise ValueError(
                "compressed keys of two strides a kernel and blocks of "
                "whole strides are what this model's programs compute")
        self._pages_per_step = pages_per_step
        self._chunk = lightning_chunk
        self._log_decays = np.asarray(cfg.log_decays(), np.float32)
        hd, hkv = cfg.head_dim, cfg.n_kv_heads
        # Head-major: a key/value head's [K, V] side by side.
        self.kv_token_shape = (cfg.n_sparse_layers, hkv, 2, hd)
        self.kv_planes = {KVCacheManager.GLOBAL: held_by_planes(hkv)}
        self._strides = max_seq_len // cfg.kernel_stride
        dk = cfg.lightning_head_dim
        self.state_shapes = {
            "s": ((cfg.n_lightning_layers, cfg.lightning_heads, dk, dk),
                  jnp.float32),
            "ck": ((cfg.n_sparse_layers, hkv, self._strides, hd),
                   self.kv_dtype),
            "ksum": ((cfg.n_sparse_layers, 2, hkv, hd), jnp.float32)}
        self._attn_inplace = kernel_eligible(cfg.n_heads, hd, hkv)
        self._state_bytes = (cfg.n_lightning_layers * cfg.lightning_heads
                             * dk * dk * 4)
        for name in self.own_counters:
            setattr(self, name, 0)
        self.decode_kv_bytes_read_held = 0
        self.decode_kv_bytes_read_model = 0

    # -- the layers' pieces --------------------------------------------
    def _embed(self, params, tokens):
        import jax
        import jax.numpy as jnp

        with jax.named_scope("embed"):
            return (params["embed"][tokens].astype(jnp.float32)
                    * self._cfg.scale_emb)

    def _rope(self, positions):
        from ray_tpu.ops.rotary import rotary_cos_sin, rotary_inv_freq

        cfg = self._cfg
        return rotary_cos_sin(positions, rotary_inv_freq(
            cfg.lightning_head_dim, cfg.rope_theta))

    def _sparse_qkv(self, y, mp):
        """A selecting layer's normed q ``[T, H, hd]`` and k ``[T, Hkv,
        hd]`` and its v ``[T, Hkv, hd]``, float32; no rotary."""
        cfg = self._cfg
        t = y.shape[0]
        q = self._mm(y, mp["wq"]).reshape(t, cfg.n_heads, cfg.head_dim)
        k = self._mm(y, mp["wk"]).reshape(t, cfg.n_kv_heads, cfg.head_dim)
        v = self._mm(y, mp["wv"]).reshape(t, cfg.n_kv_heads, cfg.head_dim)
        return (self._norm(q, mp["q_norm"]), self._norm(k, mp["k_norm"]), v)

    def _lightning_qkv(self, y, mp, rope):
        """A lightning layer's q (normed, rotated, over ``sqrt(dk)``), k
        (normed, rotated) and v, ``[T, H, dk]`` float32."""
        from ray_tpu.ops.rotary import apply_rotary_partial

        cfg = self._cfg
        shape = (y.shape[0], cfg.lightning_heads, cfg.lightning_head_dim)
        q = self._norm(self._mm(y, mp["wq"]).reshape(shape), mp["q_norm"])
        k = self._norm(self._mm(y, mp["wk"]).reshape(shape), mp["k_norm"])
        v = self._mm(y, mp["wv"]).reshape(shape)
        return (apply_rotary_partial(q, *rope)
                * cfg.lightning_head_dim ** -0.5,
                apply_rotary_partial(k, *rope), v)

    def _gated_out(self, y, o, mp):
        """``W_o(o * sigmoid(y W_g))``: o ``[T, H, hd]``."""
        import jax

        gate = jax.nn.sigmoid(self._mm(y, mp["wgate"]))
        return self._mm(o.reshape(o.shape[0], -1) * gate, mp["wo"])

    def _mlp(self, x, lp):
        """The layer's second half, ``x + a MLP(N(x))``."""
        import jax

        with jax.named_scope("dense_mlp"):
            mp = lp["mlp"]
            return x + self._cfg.residual_scale * self._gated_ffn(
                self._norm(x, lp["ln2"]), mp["gate"], mp["up"], mp["down"])

    def _logits(self, x, params):
        """``W_head(N(x) / (d / dim_model_base))`` over the vocabulary:
        the head's columns past it (whole lanes) are left out."""
        import jax

        cfg = self._cfg
        with jax.named_scope("lm_head"):
            last = self._norm(x, params["ln_f"]) / cfg.logit_divisor
            return self._mm(last, params["head"])[..., :cfg.vocab_size]

    def _kinds(self, params):
        """Every layer in order: its tree, whether it selects, and its
        index among the layers of its kind."""
        seen = {True: 0, False: 0}
        sparse = set(self._cfg.sparse_layers)
        for i, lp in enumerate(params["layers"]):
            yield lp, i in sparse, seen[i in sparse]
            seen[i in sparse] += 1

    def _select(self, q, ck, positions):
        """The blocks the queries `q` ``[T, H, hd]`` at `positions`
        attend to, ``[Hkv, T, blocks]`` bool, from compressed keys `ck`
        ``[Hkv, J, hd]``."""
        from ray_tpu.ops.block_sparse_attention import (compressed_scores,
                                                        select_blocks,
                                                        valid_kernels)

        cfg = self._cfg
        r = compressed_scores(q, ck, valid_kernels(
            positions, cfg.kernel_size, cfg.kernel_stride))
        return select_blocks(
            r, positions, block=cfg.sparse_block, stride=cfg.kernel_stride,
            init_blocks=cfg.init_blocks, window=cfg.window_size,
            topk=cfg.topk, dense_len=cfg.dense_len)

    # -- prefill -------------------------------------------------------
    def _prompt_layers(self, params, tokens, pos, length, carried, attend,
                       s_keys: int):
        """The layers over positions `pos` of one prompt, `tokens` there
        (a whole prompt in its bucket, or a chunk), of which the first
        `length` are live; the keys a query may see lie in `s_keys`
        positions. `carried`: what the positions before left (the
        sequence's slot; zeros at position 0). ``attend(q, k, v, keep,
        index)`` is a selecting layer's attention: q ``[S, H, hd]`` and
        the positions' own k, v ``[S, Hkv, hd]`` in the pool's dtype,
        `keep` ``[Hkv, S, blocks]`` or None; ``[H, S, hd]`` out. Returns
        the logits after the last live position, the KV rows ``[S,
        sparse layers, Hkv, 2, hd]`` and the state the positions end
        on."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.ops.block_sparse_attention import (compress, head_rows,
                                                        stride_sums)
        from ray_tpu.ops.lightning_attention import lightning_chunked

        cfg, f32 = self._cfg, jnp.float32
        act = params["embed"].dtype
        s_pad, stride = tokens.shape[0], cfg.kernel_stride
        chunk = min(self._chunk, s_pad)
        a = cfg.residual_scale
        x = self._embed(params, tokens)                          # [S, d]
        live = jnp.arange(s_pad) < length
        rope = self._rope(pos)
        first = pos[0] // stride          # the stride the positions begin
        selects = s_keys > cfg.dense_len
        used = min(s_keys // stride, self._strides)
        decays = jnp.asarray(self._log_decays)
        rows, states, cks, ksums = [], [], [], []
        for lp, sparse, index in self._kinds(params):
            mp = lp["mixer"]
            y = self._norm(x, lp["ln1"])
            if sparse:
                q, k, v = self._sparse_qkv(y, mp)
                k, v = k.astype(act), v.astype(act)
                with jax.named_scope("compress_keys"):
                    sums = stride_sums(
                        jnp.where(live[:, None, None], k, 0), stride)
                    before = jnp.concatenate(
                        [carried["ksum"][index, :1], sums])
                    new = compress(before, cfg.kernel_size).astype(act)
                    new = new.transpose(1, 0, 2)           # [Hkv, n, hd]
                    ck, zero = carried["ck"][index], jnp.int32(0)
                    # Kernel `first - 1` straddles the stride before
                    # these positions; none does before position 0.
                    at = jnp.maximum(first - 1, 0)
                    ck = jax.lax.dynamic_update_slice(
                        ck, jnp.where(first > 0, new[:, :1], jax.lax.
                                      dynamic_slice_in_dim(ck, at, 1, 1)),
                        (zero, at, zero))
                    ck = jax.lax.dynamic_update_slice(
                        ck, new[:, 1:], (zero, first, zero))
                    # What the next position's stride adds to: the last
                    # whole stride's sum, and the sum of a stride the
                    # live positions end inside.
                    whole = length // stride
                    inside = jnp.where(
                        length % stride > 0,
                        sums[jnp.minimum(whole, sums.shape[0] - 1)], 0.0)
                    ksums.append(jnp.stack([before[whole], inside]))
                    cks.append(ck)
                keep = None
                if selects:
                    with jax.named_scope("block_select"):
                        keep = self._select(q.astype(act), ck[:, :used],
                                            pos)
                with jax.named_scope("attn_block_sparse"):
                    o = attend(q.astype(act), k, v, keep, index)
                    out = self._gated_out(y, o.transpose(1, 0, 2), mp)
                rows.append(head_rows(k, v))
            else:
                with jax.named_scope("lightning_attn"):
                    q, k, v = self._lightning_qkv(y, mp, rope)
                    g = jnp.where(live[:, None], decays[index][None], 0.0)
                    o, s_end = lightning_chunked(
                        q, jnp.where(live[:, None, None], k, 0.0), v, g,
                        carried["s"][index], chunk)
                    out = self._gated_out(
                        y, self._norm(o, mp["onorm"]), mp)
                states.append(s_end)
            x = self._mlp(x + a * out, lp)
        logits = self._logits(x[length - 1][None], params)[0]
        return (logits, jnp.stack(rows, axis=1),
                {"s": jnp.stack(states), "ck": jnp.stack(cks),
                 "ksum": jnp.stack(ksums).astype(f32)})

    def _build_prefill(self, s_pad: int):
        import jax
        import jax.numpy as jnp

        from ray_tpu.ops.attention import prefill_attention

        if s_pad > self._cfg.dense_len:
            raise ValueError(
                f"a prompt of {s_pad} positions prefilled whole would "
                f"select past dense_len {self._cfg.dense_len}: it goes in "
                f"chunks")
        self.jit_compiles += 1
        shapes = self.state_shapes

        def attend(q, k, v, keep, index):
            # A padded position lies after every live one: the causal
            # mask alone keeps it from a live query. A whole prompt is
            # shorter than `dense_len`: every block.
            return prefill_attention(
                q.transpose(1, 0, 2), k.transpose(1, 0, 2),
                v.transpose(1, 0, 2))

        def prefill(params, tokens, length):
            zeros = {name: jnp.zeros(shape, dt)
                     for name, (shape, dt) in shapes.items()}
            return self._prompt_layers(
                params, tokens, jnp.arange(s_pad), length, zeros, attend,
                s_pad)

        return jax.jit(prefill)

    def _build_prefill_chunk(self, s_keys: int, block_size: int):
        """The program of one chunk of a prompt whose keys lie in
        `s_keys` positions: the chunk's place and the sequence's slot
        come in as scalars."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.ops.attention import prefill_attention
        from ray_tpu.ops.block_sparse_attention import (
            block_sparse_prefill_attention, heads_of_head_major_pages)

        self.jit_compiles += 1
        cfg, c = self._cfg, self.prefill_chunk_tokens

        def prefill_chunk(pool, state, params, packed):
            tokens, start, length, slot = (packed[:c], packed[c],
                                           packed[c + 1], packed[c + 2])
            table = packed[c + 3:]          # s_keys / block_size blocks
            # What the positions before the chunk left in the sequence's
            # slot; nothing came before position 0.
            carried = {name: jnp.where(start > 0, pool_[slot], 0)
                       for name, pool_ in state.items()}
            zero = jnp.int32(0)

            def attend(q, k, v, keep, index):
                # The keys and values at their positions, the chunk's
                # own among them (whatever a page holds from `start` on,
                # no query sees it); every key up to the chunk's last
                # is live.
                with jax.named_scope("kv_gather"):
                    keys, vals = heads_of_head_major_pages(
                        pool[table, index])
                at = (zero, start, zero)
                keys = jax.lax.dynamic_update_slice(
                    keys, k.transpose(1, 0, 2), at)
                vals = jax.lax.dynamic_update_slice(
                    vals, v.transpose(1, 0, 2), at)
                q = q.transpose(1, 0, 2)
                if keep is None:
                    return prefill_attention(q, keys, vals, offset=start,
                                             live=start + c)
                return block_sparse_prefill_attention(
                    q, keys, vals, keep, block=cfg.sparse_block,
                    offset=start)

            return self._prompt_layers(
                params, tokens, start + jnp.arange(c), length, carried,
                attend, s_keys)

        return jax.jit(prefill_chunk)

    # -- decode --------------------------------------------------------
    def _pages_most(self, nb_pad: int, block_size: int) -> int:
        """The most pages of a table of `nb_pad` a row's group can
        attend to: every page below `dense_len`, else the first blocks',
        the window's (one more block where it straddles) and the top
        blocks'."""
        cfg = self._cfg
        a_block = cfg.sparse_block // block_size
        chosen = a_block * (cfg.init_blocks + cfg.topk + 1
                            + -(-cfg.window_size // cfg.sparse_block))
        return min(nb_pad, max(cfg.dense_len // block_size, chosen))

    def _build_decode_paged(self, b_pad: int, nb_pad: int,
                            block_size: int, probe: bool = False):
        """The step's program; with `probe`, a program over the same
        arguments that writes nothing and returns what each selecting
        layer kept, ``[sparse layers, b_pad, Hkv, blocks]`` bool."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.ops.block_sparse_attention import (chosen_pages,
                                                        head_rows,
                                                        head_walk_attention)
        from ray_tpu.ops.lightning_attention import lightning_step_in_pool
        from ray_tpu.ops.paged_attention import write_rows

        self.jit_compiles += 1
        cfg, f32 = self._cfg, jnp.float32
        stride, hkv = cfg.kernel_stride, cfg.n_kv_heads
        selects = nb_pad * block_size > cfg.dense_len
        used = nb_pad * block_size // stride
        if used > self._strides:
            raise ValueError(
                f"a table of {nb_pad} blocks of {block_size} reaches past "
                f"the {self._strides} compressed keys a slot holds")
        most = self._pages_most(nb_pad, block_size)
        a = cfg.residual_scale
        kept = []       # a trace's: what each selecting layer kept

        def decode_paged(pool, state, params, packed, before):
            del kept[:]
            tokens, positions = step_tokens(packed, before), packed[:, 1]
            wblocks, woffs, slots = packed[:, 2], packed[:, 3], packed[:, 4]
            tables = packed[:, 5:-1]
            act = pool.dtype
            n_slots = state["s"].shape[0]
            # A padding row names slot `n_slots`: its scatters drop, and
            # what it gathers (the last slot's) is thrown away.
            at_slot = jnp.minimum(slots, n_slots - 1)
            used_slots = jnp.zeros((n_slots,), bool).at[slots].set(
                True, mode="drop")
            at_of_slot = jnp.zeros((n_slots,), jnp.int32).at[slots].set(
                positions, mode="drop")
            rope = self._rope(at_of_slot)
            decays = jnp.asarray(self._log_decays)
            x = self._embed(params, tokens)                      # [B, d]
            rows = []
            for lp, sparse, index in self._kinds(params):
                mp = lp["mixer"]
                y = self._norm(x, lp["ln1"])
                if sparse:
                    q, k, v = self._sparse_qkv(y, mp)
                    k, v = k.astype(act), v.astype(act)
                    with jax.named_scope("compress_keys"):
                        ksum = state["ksum"][at_slot, index]  # [B, 2, ..]
                        inside = ksum[:, 1] + k.astype(f32)
                        done = (positions % stride == stride - 1)
                        ends = positions // stride - 1    # the kernel
                        new = ((ksum[:, 0] + inside)
                               * (1.0 / cfg.kernel_size)).astype(act)
                        ck = state["ck"].at[
                            slots[:, None], index, jnp.arange(hkv)[None, :],
                            jnp.where(done & (ends >= 0), ends,
                                      self._strides)[:, None]].set(
                            new, mode="drop")
                        done = done[:, None, None]
                        state = dict(state, ck=ck, ksum=state["ksum"].at[
                            slots, index].set(jnp.stack(
                                [jnp.where(done, inside, ksum[:, 0]),
                                 jnp.where(done, 0.0, inside)], axis=1),
                            mode="drop"))
                    pages, counts = tables, positions
                    if selects:
                        with jax.named_scope("block_select"):
                            keep = jax.vmap(self._select)(
                                q[:, None].astype(act),
                                ck[at_slot, index, :, :used],
                                positions[:, None])[:, :, 0]
                            kept.append(keep)
                            pages, counts = chosen_pages(
                                keep, tables, positions, block_size, most)
                    with jax.named_scope("attn_block_sparse"):
                        with jax.named_scope("kv_gather"):
                            o = head_walk_attention(
                                q, k, v, pool, pages, counts,
                                jnp.int32(index))
                        out = self._gated_out(y, o, mp)
                    rows.append(head_rows(k, v))
                else:
                    with jax.named_scope("lightning_attn"):
                        # Slot order: row i's input at slot slots[i].
                        y = self._to_slots(y, slots, n_slots)
                        q, k, v = self._lightning_qkv(y, mp, rope)
                        g = jnp.where(used_slots[:, None],
                                      decays[index][None], 0.0)
                        o, s = lightning_step_in_pool(state["s"], index,
                                                      q, k, v, g)
                        state = dict(state, s=s)
                        out = self._of_slots(self._gated_out(
                            y, self._norm(o, mp["onorm"]), mp), slots)
                x = self._mlp(x + a * out, lp)
            logits = self._logits(x, params)
            with jax.named_scope("kv_write"):
                new_pool = write_rows(pool, wblocks, woffs,
                                      jnp.stack(rows, axis=1))
            with jax.named_scope("sample"):
                ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return self._step_out(ids, None, b_pad), logits, new_pool, state

        if probe:
            def kept_by_layer(pool, state, params, packed):
                # Every token from the host: no step before.
                decode_paged(pool, state, params, packed, jnp.zeros(
                    (self._ids_width(b_pad),), jnp.int32))
                if kept:
                    return jnp.stack(kept)
                # Nothing to select: every block that holds a position.
                blocks = nb_pad * block_size // cfg.sparse_block
                every = (jnp.arange(blocks)[None, :]
                         <= packed[:, 1:2] // cfg.sparse_block)
                return jnp.broadcast_to(
                    every[None, :, None],
                    (cfg.n_sparse_layers, b_pad, hkv, blocks))

            return jax.jit(kept_by_layer)
        return jax.jit(decode_paged, donate_argnums=(0, 1))

    # -- engine interface (`prefill_chunk`: `StateChunks`) --------------
    def probe_selection(self, pool, state, block_tables, last_tokens,
                        positions, slots, block_size: int):
        """What each selecting layer of the decode step over these rows
        would keep (`_build_decode_paged` with `probe`), on the host:
        ``[sparse layers, rows, Hkv, blocks]`` bool. Writes nothing and
        counts nothing: the benchmark's check and the tests compare it
        with the reference's selection."""
        b = len(last_tokens)
        b_pad = _next_pow2(max(b, 1))
        nb_pad = _next_pow2(max(int(p) // block_size + 1
                                for p in positions))
        key = ("probe", b_pad, nb_pad, block_size)
        fn = self._decode_paged_jit.get(key)
        if fn is None:
            fn = self._decode_paged_jit[key] = self._build_decode_paged(
                *key[1:], probe=True)
        packed = self._pack_rows(pool, state, block_tables, last_tokens,
                                 positions, b_pad, nb_pad)
        packed[:b, 4] = slots[:b]
        return np.asarray(fn(pool, state, self._params, packed))[:, :b]

    def selected_blocks(self, position: int) -> int:
        """How many blocks a group of the query at `position` attends
        to, by the selection's rule."""
        cfg = self._cfg
        exist = position // cfg.sparse_block + 1
        if position < cfg.dense_len:
            return exist
        window_from = max(position - cfg.window_size + 1, 0) \
            // cfg.sparse_block
        rest = max(window_from - cfg.init_blocks, 0)
        return (min(cfg.init_blocks, exist) + exist - window_from
                + min(cfg.topk, rest))

    def _count_step(self, pool, pages: List[int], nb_pad: int, positions,
                    block_size: int) -> None:
        """A step's counters, by the arithmetic of its program: a
        (row, selecting layer, key/value head) each, a page a key/value
        head's ``[K, V]`` of a layer's block. The chosen blocks are
        whole but the last, which holds what is cached of it."""
        cfg = self._cfg
        each = cfg.n_sparse_layers * cfg.n_kv_heads
        stride, block = cfg.kernel_stride, cfg.sparse_block
        selects = nb_pad * block_size > cfg.dense_len
        # A key/value head's page of a layer, [K, V], and how many the
        # walk brings together over the table it is handed.
        page_bytes = 2 * block_size * cfg.head_dim * self.kv_dtype.itemsize
        together = self._pages_per_step(
            page_bytes, self._pages_most(nb_pad, block_size) if selects
            else nb_pad)
        fetched = groups = 0
        for p in (int(p) for p in positions):
            blocks = self.selected_blocks(p) if selects \
                else p // block + 1
            self.decode_blocks_selected += each * blocks
            pages = ((blocks - 1) * (block // block_size)
                     + -(-(p - p // block * block) // block_size))
            fetched += pages
            groups += -(-pages // together)
            if selects:
                self.decode_blocks_scored += each * (p // block + 1)
                self.decode_index_tokens_scored += each * (p + 1)
            if p % stride == stride - 1 and p >= cfg.kernel_size - 1:
                self.compressed_keys_written += each
        self.decode_kv_tokens_read += each * fetched * block_size
        self.lightning_state_bytes_moved += (
            2 * len(positions) * self._state_bytes)
        if self._attn_inplace:
            self.decode_attn_inplace_steps += 1
            self.decode_kv_pages_read += each * fetched
            self.decode_kv_pages_read_planes += each * fetched
            self.decode_kv_page_groups_read += each * groups
            self.decode_kv_bytes_read_held += each * fetched * page_bytes
            self.decode_kv_bytes_read_model += each * fetched * page_bytes
