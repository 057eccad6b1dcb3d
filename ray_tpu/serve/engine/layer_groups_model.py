"""What the engine models over two layer groups of the KV cache share
(`laguna_model.py`, `mimo_model.py`): sparse decoders of *global*
(causal) attention layers and *window* layers that see a window of
positions.

Such a model declares `kv_groups`, the layer groups of its KV. The
global layers' rows go to the `global` group (the model's
`kv_token_shape`), the window layers' to the `window` group, whose blocks
the cache manager gives back as they leave the window (`kv_cache.py`). A
prefill's result carries a row a group (`PromptGroups`), and
`decode_paged` takes and hands back a dict of pools, reads each group
through its own table (the window group's compact, with the logical
block it begins at), and writes the step's rows into both.

A row of a group is ``[layers of the group, S, Hkv, dv]``
(`ops.paged_attention.kv_row`): the keys and the values of a layer kind's
``Hkv`` key/value heads in slots of the values' width, ``[K, V]`` where
keys and values are of one width. The two groups' rows may differ in
their key/value heads, and with them in how the cache holds the group's
pool (`kv_planes`: a group whose heads do not fill a float32 tile is
held by planes, `ops.paged_attention.held_by_planes`; MiMo's global
group on 4 heads is, its window group and both of Laguna's on 8 are
not). The programs here read and write either layout
(`paged_decode_attention`, `write_rows`, `heads_of_pages`); a group of
rows runs what it always has.

What is here: the prefill's, a prefill chunk's and the decode step's
programs, the packed step buffer, the window table, the page and byte
counters by group. What a model keeps: its layers in order (`_layers`),
a layer kind's widths (`_attention_widths`) and rotary tables (`_rope`),
`_qkv`, and the mixer's way out (`_mixer_out`).

A decode step is one compiled program: in, one int32 array ``[b_pad, 7 +
nb_pad + window_blocks]`` (token, position, write offset, the global and
the window group's write blocks, the window table's first logical block,
the global table, the window table, the row's place in the step before's
ids or -1: `model.step_tokens`) and the step before's result where it
lies on the device; out, one int32 array ``[width + 3]``: the greedy ids
at the model's largest batch bucket's width and the step's three expert
counters.
"""

from __future__ import annotations

import math
from typing import Dict, List, Sequence

import numpy as np

from ray_tpu.core import flight
from ray_tpu.serve.engine.model import (PromptKV, _next_pow2,
                                        place_sources, step_tokens)
from ray_tpu.serve.engine.sparse_model import SparseEngineModel

GLOBAL, WINDOW = "global", "window"


class PromptGroups(PromptKV):
    """A prefill's KV rows a layer group: itself the global group's
    (every position), `groups["window"]` the window layers' (the same
    positions; `KVCacheManager.write_range` stores only those the window
    still reaches)."""

    __slots__ = ("groups",)

    def __init__(self, padded, n: int, groups: Dict[str, PromptKV]):
        super().__init__(padded, n)
        self.groups = groups


class LayerGroupsEngineModel(SparseEngineModel):
    """Incremental decoding over a global and a window layer group.

    Prefill runs the prompt once in pow2 length buckets through
    `ops.attention.prefill_attention` (the windowed, grouped flash
    forward on the chip); a decode step is jitted a (batch, global table)
    bucket, the window table's width fixed by the window. A prompt is
    never prefilled from an adopted prefix: the engine adopts none
    beside a window group. A prompt longer than `prefill_chunk_tokens`
    the scheduler runs through `prefill_chunk`, a chunk at a time.

    A subclass defines `_attention_widths(full)` (query heads, a key's
    values, key/value heads, a value's values), `_group_layers(full)`,
    `_layers(params)` (every layer in order: its mixer's tree, the two
    norm scales, its feed-forward tree, whether it is global), `_rope`,
    `_qkv` and `_mixer_out`."""

    def __init__(self, params, cfg, max_batch_size: int = 8,
                 jit_cache_cap: int = 32):
        from ray_tpu.ops.paged_attention import (attention_widths,
                                                 by_planes, held_by_planes,
                                                 kernel_eligible, kv_slots,
                                                 page_groups)

        super().__init__(params, cfg, jit_cache_cap, max_batch_size)
        self._page_groups, self._by_planes = page_groups, by_planes
        rows, planes, itemsize = {}, {}, self.kv_dtype.itemsize
        # A position's bytes in a group: as the pool holds it (whole
        # slots) and as the model counts it (its keys and values).
        self.kv_token_bytes_held: Dict[str, int] = {}
        self.kv_token_bytes_model: Dict[str, int] = {}
        for group, full in ((GLOBAL, True), (WINDOW, False)):
            _, dk, hkv, dv = self._attention_widths(full)
            layers = self._group_layers(full)
            rows[group] = (layers, kv_slots(dk, dv), hkv, dv)
            # How the cache holds the group's pool, from its head count.
            planes[group] = held_by_planes(hkv)
            self.kv_token_bytes_held[group] = math.prod(rows[group]) * itemsize
            self.kv_token_bytes_model[group] = (layers * hkv * (dk + dv)
                                                * itemsize)
        self.kv_token_shape = rows[GLOBAL]
        self.kv_groups = {WINDOW: {"kv_shape": rows[WINDOW],
                                   "window": cfg.window}}
        self.kv_planes = planes
        self._attn_inplace = all(
            kernel_eligible(*attention_widths(
                *self._attention_widths(full)))
            for full in (True, False))
        # Live pages the steps' tables named, a group: pages that hold a
        # cached position the row's query sees (their sum is
        # `decode_kv_pages_read`), and the groups of pages the kernel
        # fetched them in (`ops.paged_attention.page_groups`; their sum
        # is `decode_kv_page_groups_read`).
        self.decode_kv_pages_read_global = 0
        self.decode_kv_pages_read_window = 0
        self.decode_kv_page_groups_read_global = 0
        self.decode_kv_page_groups_read_window = 0
        # Those pages' bytes, both groups': as the pools hold a position
        # and as the model counts it. Keys wider than values are held in
        # whole slots of the values' width: the first is then the larger.
        self.decode_kv_bytes_read_held = 0
        self.decode_kv_bytes_read_model = 0

    def window_table_blocks(self, block_size: int) -> int:
        """Blocks of the window group a sequence holds at the most."""
        return math.ceil(self._cfg.window / block_size) + 1

    # Positions of a prompt a call of `prefill_chunk` runs; a prompt of
    # at most as many is prefilled whole. A chunk is what a running row
    # waits behind, and every chunk reads the weights once more. Swept
    # on the chip at both configurations' published widths (PR 56; wall
    # time of a call, the first chunk to the last, and a whole prompt's
    # chunks together; one seed, the second run of each):
    #   MiMo, 7,680 tokens in the 8,192 bucket (whole: 237.6 ms)
    #     512: 16.3-19.2 ms a chunk, 292.9 ms   1,024: 26.6-31.2, 245.6
    #     2,048: 49.5-57.1, 222.5
    #   Laguna, 4,096 tokens (whole: 115.5 ms)
    #     512: 20.3-22.0 ms a chunk, 185.4 ms   1,024: 30.1-32.6, 134.1
    #     2,048: 56.2-59.0, 119.8
    # 512 costs a prompt a fifth to a third more than 1,024 for 10 ms
    # less of a stall; 2,048 saves it a tenth and doubles the stall.
    # One value for both: nothing the code can see separates them.
    prefill_chunk_tokens = 1024

    def _chunk_tail_tokens(self) -> int:
        """Positions before a chunk that a window layer's keys begin
        with: the window, in whole tiles of the window layers' forward
        (`ops.flash_attention.prefill_block`), so that the chunk's first
        query lies on a tile's edge."""
        from ray_tpu.ops.flash_attention import prefill_block

        tile = prefill_block(self.prefill_chunk_tokens, self._cfg.window)
        return -(-self._cfg.window // tile) * tile

    # -- shared math ---------------------------------------------------
    def _feed_forward(self, x, ln2, mp, valid):
        """A layer's second half: a dense MLP (no counts) or the expert
        layer."""
        import jax
        import jax.numpy as jnp

        if "router" in mp:
            return self._experts(x, ln2, mp, valid)
        with jax.named_scope("dense_mlp"):
            y = self._norm(x, ln2)
            out = self._gated_ffn(y, mp["gate"], mp["up"], mp["down"])
        return x + out, jnp.zeros((3,), jnp.int32)

    # -- prefill -------------------------------------------------------
    def _prompt_layers(self, params, tokens, pos, length, attend):
        """The layers over positions `pos` of one prompt, `tokens` there
        (a whole prompt in its bucket, or a chunk), of which the first
        `length` are live. ``attend(q, k, v, full, index, lp)`` is a
        layer's attention: q ``[S, H, dk]``, its own k and v ``[S, Hkv,
        ..]`` in the pools' dtype, whether it is global, its index in
        its group; ``[H, S, dv]`` out. Returns the logits after the last
        live position and both groups' rows, ``[S, layers of the group,
        slots, Hkv, dv]``."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.ops.paged_attention import kv_row

        act = params["embed"].dtype
        with jax.named_scope("embed"):
            x = params["embed"][tokens].astype(jnp.float32)    # [S, d]
        live = jnp.arange(tokens.shape[0]) < length
        ropes = {True: self._rope(pos, True), False: self._rope(pos, False)}
        rows = {True: [], False: []}
        for lp, ln1, ln2, mp, full in self._layers(params):
            with jax.named_scope("attn_global" if full else "attn_window"):
                y = self._norm(x, ln1)
                q, k, v = self._qkv(y, lp, full, ropes[full])
                k, v = k.astype(act), v.astype(act)
                o = attend(q.astype(act), k, v, full, len(rows[full]), lp)
                x = x + self._mixer_out(y, o.transpose(1, 0, 2), lp)
            rows[full].append(kv_row(k, v))
            x, _ = self._feed_forward(x, ln2, mp, live)
        with jax.named_scope("lm_head"):
            last = self._norm(x[length - 1], params["ln_f"])
            logits = self._mm(last[None], params["head"])[0]
        return (logits, jnp.stack(rows[True], axis=1),
                jnp.stack(rows[False], axis=1))

    def _build_prefill(self, s_pad: int):
        import jax
        import jax.numpy as jnp

        from ray_tpu.ops.attention import prefill_attention

        self.jit_compiles += 1
        window = self._cfg.window

        def attend(q, k, v, full, index, lp):
            # A padded position lies after every live one: the causal
            # mask alone keeps it from a live query.
            return prefill_attention(
                q.transpose(1, 0, 2), k.transpose(1, 0, 2),
                v.transpose(1, 0, 2), None if full else window,
                lp.get("sink"))                                # [H, S, dv]

        def prefill(params, tokens, length):
            return self._prompt_layers(params, tokens, jnp.arange(s_pad),
                                       length, attend)

        return jax.jit(prefill)

    def _build_prefill_chunk(self, s_keys: int, block_size: int):
        """The program of one chunk of a prompt whose global keys lie in
        `s_keys` positions: the chunk's place comes in as a scalar."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.ops.attention import prefill_attention
        from ray_tpu.ops.paged_attention import (by_planes, heads_of_pages,
                                                 kv_of_rows)

        self.jit_compiles += 1
        window = self._cfg.window
        c, tail = self.prefill_chunk_tokens, self._chunk_tail_tokens()
        nb = s_keys // block_size

        def prefill_chunk(pools, params, packed):
            tokens, start, length = packed[:c], packed[c], packed[c + 1]
            tables = {GLOBAL: packed[c + 2:c + 2 + nb],
                      WINDOW: packed[c + 2 + nb:]}
            # What the pools hold of the positions before the chunk, a
            # position a row: the global group's at their positions
            # (whatever a row from `start` on reads, no query sees it),
            # the window group's last `tail` (a row of a position the
            # group gave back lies outside every window; one before the
            # prompt's first is not live).
            # (A pool held by planes is read a layer at a time, below.)
            with jax.named_scope("kv_gather"):
                before = {group: pool[tables[group]].reshape(
                    (-1,) + pool.shape[2:]) for group, pool in pools.items()
                    if not by_planes(pool)}
            zero = jnp.int32(0)

            def attend(q, k, v, full, index, lp):
                group = GLOBAL if full else WINDOW
                if by_planes(pools[group]):
                    return attend_by_heads(q, k, v, full, group, index, lp)
                old_k, old_v = kv_of_rows(before[group][:, index],
                                          k.shape[-1])
                if full:
                    # The keys at their positions, the chunk's own among
                    # them; every key up to the chunk's last is live.
                    at = (start, zero, zero)
                    keys = jax.lax.dynamic_update_slice(old_k, k, at)
                    vals = jax.lax.dynamic_update_slice(old_v, v, at)
                    offset, live = start, start + c
                else:
                    keys = jnp.concatenate([old_k, k])
                    vals = jnp.concatenate([old_v, v])
                    offset, live = tail, jnp.minimum(start, tail) + c
                return prefill_attention(
                    q.transpose(1, 0, 2), keys.transpose(1, 0, 2),
                    vals.transpose(1, 0, 2), None if full else window,
                    lp.get("sink"), offset=offset, live=live)

            def attend_by_heads(q, k, v, full, group, index, lp):
                """`attend` over a pool held by planes: the keys and
                values come out a head at a time, ``[Hkv, nb * bs, ..]``,
                as the forward takes them."""
                with jax.named_scope("kv_gather"):
                    old_k, old_v = heads_of_pages(
                        pools[group], tables[group], index, k.shape[1],
                        k.shape[-1])
                k, v = k.transpose(1, 0, 2), v.transpose(1, 0, 2)
                if full:
                    at = (zero, start, zero)
                    keys = jax.lax.dynamic_update_slice(old_k, k, at)
                    vals = jax.lax.dynamic_update_slice(old_v, v, at)
                    offset, live = start, start + c
                else:
                    keys = jnp.concatenate([old_k, k], axis=1)
                    vals = jnp.concatenate([old_v, v], axis=1)
                    offset, live = tail, jnp.minimum(start, tail) + c
                return prefill_attention(
                    q.transpose(1, 0, 2), keys, vals,
                    None if full else window, lp.get("sink"),
                    offset=offset, live=live)

            return self._prompt_layers(params, tokens,
                                       start + jnp.arange(c), length, attend)

        return jax.jit(prefill_chunk)

    # -- decode --------------------------------------------------------
    def _build_decode_paged(self, b_pad: int, nb_pad: int,
                            block_size: int):
        import jax
        import jax.numpy as jnp

        from ray_tpu.ops.paged_attention import (kv_row,
                                                 paged_decode_attention,
                                                 write_rows)

        self.jit_compiles += 1
        cfg, f32 = self._cfg, jnp.float32

        def decode_paged(pools, params, packed, before):
            tokens, positions, woffs = (step_tokens(packed, before),
                                        packed[:, 1], packed[:, 2])
            wblocks = {GLOBAL: packed[:, 3], WINDOW: packed[:, 4]}
            starts = packed[:, 5]
            tables = {GLOBAL: packed[:, 6:6 + nb_pad],
                      WINDOW: packed[:, 6 + nb_pad:-1]}
            # A padding row writes past both pools: it routes nowhere.
            valid = wblocks[GLOBAL] < pools[GLOBAL].shape[0]
            act = pools[GLOBAL].dtype
            with jax.named_scope("embed"):
                x = params["embed"][tokens].astype(f32)        # [B, d]
            ropes = {True: self._rope(positions, True),
                     False: self._rope(positions, False)}
            rows = {True: [], False: []}
            counts = jnp.zeros((3,), jnp.int32)
            for lp, ln1, ln2, mp, full in self._layers(params):
                group = GLOBAL if full else WINDOW
                with jax.named_scope("attn_global" if full
                                     else "attn_window"):
                    y = self._norm(x, ln1)
                    q, k, v = self._qkv(y, lp, full, ropes[full])
                    k, v = k.astype(act), v.astype(act)
                    with jax.named_scope("kv_gather"):
                        o = paged_decode_attention(
                            q, k, v, pools[group], tables[group], positions,
                            jnp.int32(len(rows[full])),
                            None if full else cfg.window,
                            None if full else starts, lp.get("sink"))
                    x = x + self._mixer_out(y, o, lp)
                rows[full].append(kv_row(k, v))
                x, c = self._feed_forward(x, ln2, mp, valid)
                counts += c
            with jax.named_scope("lm_head"):
                logits = self._mm(self._norm(x, params["ln_f"]),
                                  params["head"])
            with jax.named_scope("kv_write"):
                new_pools = {
                    group: write_rows(pools[group], wblocks[group], woffs,
                                      jnp.stack(rows[full], axis=1))
                    for group, full in ((GLOBAL, True), (WINDOW, False))}
            with jax.named_scope("sample"):
                ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return self._step_out(ids, counts, b_pad), logits, new_pools

        return jax.jit(decode_paged, donate_argnums=(0,))

    # -- engine interface ----------------------------------------------
    def prefill(self, tokens: Sequence[int]):
        """Run the prompt. Returns the host logits that predict the next
        token and a `PromptGroups`: the prompt's KV rows of both layer
        groups, still on the device."""
        with flight.span("model", "prefill", len(tokens)):
            logits, (kv_global, kv_window), n = self._run_prefill(tokens)
            return logits, PromptGroups(
                kv_global, n, {WINDOW: PromptKV(kv_window, n)})

    def prefill_chunk(self, tokens: Sequence[int], pools, tables: dict,
                      start: int, block_size: int, *, meanwhile=None):
        """Run positions ``[start, start + prefill_chunk_tokens)`` of the
        prompt `tokens` (those of them it has), whose positions before
        `start` are in `pools`, read through `tables` (the sequence's
        `step_tables`, as they stand before this chunk's blocks are
        allocated and the window group's older ones given back). `start`
        is a multiple of the chunk. Returns the host logits that predict
        the next token for the chunk that holds the prompt's last token,
        else None, and a `PromptGroups` of the chunk's rows, on the
        device, for `write_range(seq, start, ...)`.

        A program of its own between two decode steps. `meanwhile` (the
        protocol's: `model.py`) runs behind the dispatch. The chunk that
        holds the prompt's last token is read (its logits); any other
        returns as soon as it is dispatched, its rows unfinished device
        values (`sparse_model._prompt_logits`). The device runs programs
        in dispatch order, and that is what orders things: a block that
        `allocate` gives back after this call (``writable_from=start``,
        a window group's expired blocks) and hands to another sequence
        can only be written by a program behind this chunk, which has
        read it by then.
        One program a power of two of the prompt's length, in which the
        global keys lie, and one for every prompt of up to four chunks:
        the chunk's place is a scalar to it, a key tile past the chunk's
        end costs a global layer's forward a third of a microsecond,
        and a program more costs a replica 5-6 s of set-up (PR 56)."""
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
        with flight.span("model", "prefill.prep", None, phase,
                         "prefill_prep_s"):
            tail = self._chunk_tail_tokens()
            if start % c or c % block_size or tail % block_size:
                raise ValueError(
                    f"a chunk of {c} positions at {start} over a tail of "
                    f"{tail} does not lie on blocks of {block_size}")
            s_keys = max(_next_pow2(-(-n // c) * c), 4 * c)
            key = ("chunk", c, s_keys, block_size)
            fn = self._prefill_jit.get(key)
            if fn is None:
                fn = self._prefill_jit[key] = \
                    self._build_prefill_chunk(*key[2:])
            nb, tb = s_keys // block_size, tail // block_size
            packed = np.zeros((c + 2 + nb + tb,), np.int32)
            packed[:length] = np.asarray(tokens[start:start + length],
                                         np.int32)
            packed[c], packed[c + 1] = start, length
            _, table = tables[GLOBAL]
            packed[c + 2:c + 2 + min(nb, len(table))] = table[:nb]
            # The window group's blocks of the `tail` positions before
            # `start`, by their place in its compact table; block 0 for
            # one it no longer holds (or never did).
            base, near = tables[WINDOW]
            first = start // block_size - tb - base
            lo, hi = max(0, -first), min(tb, len(near) - first)
            if lo < hi:
                at = c + 2 + nb
                packed[at + lo:at + hi] = near[first + lo:first + hi]
        with flight.span("model", "prefill.dispatch", None, phase,
                         "prefill_dispatch_s"):
            logits, kv_global, kv_window = fn(pools, self._params, packed)
        self._count_experts_step(c)
        if meanwhile is not None:
            meanwhile()
        logits = self._prompt_logits(logits, start + length == n)
        return logits, PromptGroups(
            kv_global, length, {WINDOW: PromptKV(kv_window, length)})

    def decode_paged(self, pools, block_tables: List[dict],
                     last_tokens: Sequence[int],
                     positions: Sequence[int], write_blocks: dict,
                     write_offs: dict, block_size: int, *,
                     meanwhile=None, ahead=None):
        """One fused step over both layer groups. `pools`, `write_blocks`
        and `write_offs` are dicts a group (`KVCacheManager.paged_step`
        over groups); ``block_tables[i]`` is row i's ``{group: (base,
        table)}`` (`step_tables`). A write list shorter than the batch
        leaves the other rows unwritten, as in a warm-up. Returns
        ``(step, new_pools)``; both pools were donated."""
        with flight.span("model", "decode", len(last_tokens)):
            return self._decode_paged(pools, block_tables, last_tokens,
                                      positions, write_blocks, write_offs,
                                      block_size, meanwhile, ahead)

    def _decode_paged(self, pools, block_tables, last_tokens, positions,
                      write_blocks, write_offs, block_size: int,
                      meanwhile, ahead):
        b = len(last_tokens)
        self.decode_calls += 1
        window = self._cfg.window
        with flight.span("model", "decode.prep", None, self.phase,
                         "decode_prep_s"):
            b_pad = _next_pow2(max(b, 1))
            tw = self.window_table_blocks(block_size)
            # Pages that hold a cached position: [0, p) in the global
            # group, [max(0, p - window + 1), p) in the window group.
            cached = [-(-int(p) // block_size) for p in positions]
            nb_pad = _next_pow2(max(max(int(p) // block_size + 1
                                        for p in positions), 1))
            if self._attn_inplace:
                self.decode_attn_inplace_steps += 1
                in_window = sum(
                    c - max(0, int(p) - window + 1) // block_size
                    for c, p in zip(cached, positions))
                self.decode_kv_pages_read_global += sum(cached)
                self.decode_kv_pages_read_window += in_window
                self.decode_kv_pages_read += sum(cached) + in_window
                for pages, group in ((sum(cached), GLOBAL),
                                     (in_window, WINDOW)):
                    if self._by_planes(pools[group]):
                        self.decode_kv_pages_read_planes += pages
                    self.decode_kv_bytes_read_held += (
                        pages * block_size * self.kv_token_bytes_held[group])
                    self.decode_kv_bytes_read_model += (
                        pages * block_size
                        * self.kv_token_bytes_model[group])
                groups = (
                    self._page_groups(pools[GLOBAL], nb_pad, positions),
                    self._page_groups(pools[WINDOW], tw, positions, window))
                self.decode_kv_page_groups_read_global += groups[0]
                self.decode_kv_page_groups_read_window += groups[1]
                self.decode_kv_page_groups_read += sum(groups)
            key = (b_pad, nb_pad, block_size)
            fn = self._decode_paged_jit.get(key)
            if fn is None:
                fn = self._decode_paged_jit[key] = \
                    self._build_decode_paged(*key)
            # One host buffer, a row a sequence; a write block past a
            # pool is dropped.
            packed = np.zeros((b_pad, 7 + nb_pad + tw), np.int32)
            packed[:, 3] = int(pools[GLOBAL].shape[0])
            packed[:, 4] = int(pools[WINDOW].shape[0])
            for i in range(b):
                _, table = block_tables[i][GLOBAL]
                start, near = block_tables[i][WINDOW]
                table, near = table[:nb_pad], near[:tw]
                packed[i, 0] = last_tokens[i]
                packed[i, 1] = positions[i]
                packed[i, 5] = start
                packed[i, 6:6 + len(table)] = table
                packed[i, 6 + nb_pad:6 + nb_pad + len(near)] = near
            k = min(len(write_blocks.get(GLOBAL, ())), b)
            packed[:k, 2] = write_offs[GLOBAL][:k]
            packed[:k, 3] = write_blocks[GLOBAL][:k]
            packed[:k, 4] = write_blocks[WINDOW][:k]
            place_sources(packed, ahead)
            args = (pools, self._params, packed)
        step, (new_pools,) = self._run_decode(fn, args, b, b_pad, meanwhile,
                                              ahead)
        return step, new_pools
