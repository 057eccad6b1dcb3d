"""What the engine models with a per-sequence state share
(`hybrid_model.py`, `gigachat_model.py`: sparse decoders in which
delta-rule layers keep a recurrent state ``s`` and the last inputs of a
short convolution ``conv`` beside the KV rows of their attention layers;
`minicpm_sala_model.py`: a dense decoder whose lightning layers keep a
state ``s`` alone, beside what its selecting layers keep a sequence).

Such a model declares `state_shapes`; the cache manager then holds a
slot a sequence (`kv_cache.py`), a prefill's result carries the state it
ended on (`PromptState`), and `decode_paged` takes the state pool and the
rows' slots beside the KV pool and hands both pools back.

What is here, in `StateSteps` (a mixin beside whichever base holds the
model's products: `sparse_model.SparseEngineModel` for
`StateEngineModel`, `sparse_model.DecoderEngineModel` for a dense
model): the payload, the host side of a decode step (one int32
array ``[b_pad, 6 + nb_pad]``: token, position, write block, write
offset, state slot, block table, the row's place in the step before's
ids or -1), the short convolution, and the two ends of a recurrent
layer's step in *slot order* (`_to_slots` and `_of_slots`; with a
convolution `_slot_inputs`, `_slot_store`): a row's
input scattered to its slot and the layer's output gathered back, so
that the state pool is read and written where it lies, a slot no row of
the step uses keeps its state bit for bit, and no copy of the batch's
state is built. What a model keeps: its layers, its programs, and how a
step's pages are counted (`_count_pages`).

**A chunk of a prompt carries the state** (`StateChunks`, a second mixin
for the models that offer `prefill_chunk`: `gigachat_model.py`,
`minicpm_sala_model.py`; the scheduler runs a long prompt in chunks
where the model has the call, so `hybrid_model.py` does not take it):
the chunk's program reads the sequence's slot for what the positions
before it left (zeros at position 0), and its payload is a `PromptState`
of the state the chunk ended on, which `write_range` puts back into the
slot. The mixin holds the call's host side; a model builds the program
(`_build_prefill_chunk(s_keys, block_size)`).
"""

from __future__ import annotations

from typing import List, Sequence

import numpy as np

from ray_tpu.core import flight
from ray_tpu.serve.engine.kv_cache import KVCacheManager
from ray_tpu.serve.engine.model import (PromptKV, _next_pow2,
                                        place_sources)
from ray_tpu.serve.engine.sparse_model import SparseEngineModel


class PromptState(PromptKV):
    """A prefill's KV rows with the state the prompt (or the chunk of
    one) ended on: `state`, a dict of device arrays a sequence
    (`state_shapes`), which `KVCacheManager.write_range` stores in the
    sequence's slot."""

    __slots__ = ("state",)

    def __init__(self, padded, n: int, state: dict):
        super().__init__(padded, n)
        self.state = state


class StateSteps:
    """The calls and the program pieces of an engine model with
    `state_shapes` (``"s"`` among them, ``[layers with state, ...]`` a
    sequence). A subclass sets `_attn_inplace` (whether its decode
    attention reads the pool in place) and may count a step's pages its
    own way (`_count_pages`)."""

    _attn_inplace = False

    # -- inside a program ----------------------------------------------
    @staticmethod
    def _short_conv(window, taps):
        """``silu`` of the causal depthwise convolution: `window`
        ``[taps, T, w]`` (a token's own projection last) against `taps`
        ``[taps, w]``, float32."""
        import jax
        import jax.numpy as jnp

        return jax.nn.silu(jnp.sum(
            window.astype(jnp.float32) * taps[:, None, :], axis=0))

    @staticmethod
    def _l2norm(x):
        import jax
        import jax.numpy as jnp

        return x * jax.lax.rsqrt(
            jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)

    @staticmethod
    def _prompt_window(pre, before, taps: int):
        """A prompt's (or a chunk's) convolution inputs: `pre` ``[S, w]``
        behind the ``taps - 1`` inputs `before` it (zeros at position 0).
        Returns them side by side ``[taps - 1 + S, w]`` and the window
        ``[taps, S, w]``."""
        import jax.numpy as jnp

        padded = jnp.concatenate([before.astype(pre.dtype), pre])
        return padded, jnp.stack([padded[j:j + pre.shape[0]]
                                  for j in range(taps)])

    @staticmethod
    def _to_slots(y, slots, n_slots: int):
        """Rows `y` ``[B, d]`` laid in slot order ``[n_slots, d]`` (row i
        at slot ``slots[i]``; a slot past the pool is dropped, a slot no
        row names reads zeros)."""
        import jax.numpy as jnp

        return jnp.zeros((n_slots, y.shape[1]), jnp.float32).at[slots].set(
            y, mode="drop")

    @staticmethod
    def _of_slots(out, slots):
        """And back: row i takes slot ``slots[i]``'s (a padding row, whose
        slot lies past the pool, the last one's: thrown away)."""
        import jax.numpy as jnp

        return out[jnp.minimum(slots, out.shape[0] - 1)]

    def _slot_inputs(self, x, ln, state, layer, slots, project):
        """A delta-rule layer's step, its first half: the rows' inputs
        `x` ``[B, d]`` normed by `ln` and laid in slot order
        (`_to_slots`), the layer's
        tails and states as the pool holds them, and the convolution's
        window ``[n_slots, taps, w]`` behind ``project(y)``."""
        import jax
        import jax.numpy as jnp

        y = self._to_slots(self._norm(x, ln), slots, state["s"].shape[0])
        tail = jax.lax.dynamic_index_in_dim(
            state["conv"], layer, axis=1, keepdims=False)
        s = jax.lax.dynamic_index_in_dim(
            state["s"], layer, axis=1, keepdims=False)
        pre = project(y).astype(tail.dtype)
        window = jnp.concatenate([tail, pre[:, None]], axis=1)
        return y, tail, s, window

    @staticmethod
    def _slot_store(state, layer, s, window, tail, used):
        """Its second half: the pool with the layer's new states `s` and
        the window's last inputs, a slot no row uses left as it was."""
        import jax
        import jax.numpy as jnp

        new_tail = jnp.where(used[:, None, None], window[:, 1:], tail)
        return {
            "s": jax.lax.dynamic_update_index_in_dim(
                state["s"], s, layer, axis=1),
            "conv": jax.lax.dynamic_update_index_in_dim(
                state["conv"], new_tail, layer, axis=1)}

    # -- engine interface ----------------------------------------------
    def prefill(self, tokens: Sequence[int]):
        """Run the prompt. Returns the host logits that predict the next
        token and a `PromptState`: the prompt's KV rows and the state it
        ended on, both still on the device."""
        with flight.span("model", "prefill", len(tokens)):
            return self._prefill(tokens)

    def _prefill(self, tokens: Sequence[int]):
        logits, (kv, state), n = self._run_prefill(tokens)
        return logits, PromptState(kv, n, state)

    def decode_paged(self, pool, block_tables: List[Sequence[int]],
                     last_tokens: Sequence[int],
                     positions: Sequence[int],
                     write_blocks: Sequence[int],
                     write_offs: Sequence[int], block_size: int,
                     state=None, slots: Sequence[int] = (), *,
                     meanwhile=None, ahead=None):
        """One fused step, as `TransformerEngineModel.decode_paged`,
        over both pools: `state` is the cache's state pool and
        `slots[i]` row i's slot (a list shorter than the batch leaves
        the other rows without a slot: they read and write no state,
        as in a warm-up). Returns ``(step, new_pool, new_state)``; both
        pools were donated."""
        with flight.span("model", "decode", len(last_tokens)):
            return self._decode_paged(pool, block_tables, last_tokens,
                                      positions, write_blocks, write_offs,
                                      block_size, state, slots, meanwhile,
                                      ahead)

    def _count_step(self, pool, pages: List[int], nb_pad: int, positions,
                    block_size: int) -> None:
        """A step is about to be packed: count what it reads (a model
        whose attention reads the pool in place: `_count_pages`)."""
        if self._attn_inplace:
            self._count_pages(pool, pages, nb_pad, positions, block_size)

    def _count_pages(self, pool, pages: List[int], nb_pad: int,
                     positions, block_size: int) -> None:
        """A step that reads the pool in place has been packed: its live
        pages (`pages[i]` row i's) and the groups they go in."""
        self.decode_attn_inplace_steps += 1
        self.decode_kv_pages_read += sum(pages)
        self.decode_kv_page_groups_read += self._page_groups(
            pool, nb_pad, positions)

    @staticmethod
    def _pack_rows(pool, state, block_tables, last_tokens, positions,
                   b_pad: int, nb_pad: int):
        """A step's one host buffer, a row a sequence: token, position,
        write block (past the pool: dropped, until the caller names a
        slot), write offset, state slot (past the state pool likewise),
        the table, the row's place in the step before's ids (none)."""
        packed = np.zeros((b_pad, 6 + nb_pad), np.int32)
        packed[:, 2] = int(pool.shape[0])
        packed[:, 4] = int(state["s"].shape[0])
        packed[:, -1] = -1
        for i, (token, position) in enumerate(zip(last_tokens, positions)):
            table = block_tables[i][:nb_pad]
            packed[i, 0], packed[i, 1] = token, position
            packed[i, 5:5 + len(table)] = table
        return packed

    def _decode_paged(self, pool, block_tables, last_tokens, positions,
                      write_blocks, write_offs, block_size: int, state,
                      slots, meanwhile, ahead):
        phase = self.phase
        b = len(last_tokens)
        self.decode_calls += 1
        with flight.span("model", "decode.prep", None, phase,
                         "decode_prep_s"):
            b_pad = _next_pow2(max(b, 1))
            pages = [int(p) // block_size + 1 for p in positions]
            nb_pad = _next_pow2(max(max(pages), 1))
            self._count_step(pool, pages, nb_pad, positions, block_size)
            key = (b_pad, nb_pad, block_size)
            fn = self._decode_paged_jit.get(key)
            if fn is None:
                fn = self._decode_paged_jit[key] = \
                    self._build_decode_paged(*key)
            packed = self._pack_rows(pool, state, block_tables, last_tokens,
                                     positions, b_pad, nb_pad)
            k = min(len(write_blocks), b)
            packed[:k, 2] = write_blocks[:k]
            packed[:k, 3] = write_offs[:k]
            packed[:min(len(slots), b), 4] = slots[:b]
            place_sources(packed, ahead)
            args = (pool, state, self._params, packed)
        step, (new_pool, new_state) = self._run_decode(
            fn, args, b, b_pad, meanwhile, ahead)
        return step, new_pool, new_state


class StateEngineModel(StateSteps, SparseEngineModel):
    """A sparse engine model with `state_shapes` ``{"s": .., "conv":
    ..}``, each ``[layers with state, ...]`` a sequence."""


class StateChunks:
    """`prefill_chunk` of a model with state (beside `StateSteps`): the
    host side of the call. The model sets `prefill_chunk_tokens`, counts
    `prefill_later_chunks` and `prefill_state_chunks` and builds the
    chunk's program, ``fn(pool, state, params, packed) -> (logits, rows,
    state)``."""

    def prefill_chunk(self, tokens: Sequence[int], pools: dict,
                      table: List[int], start: int, block_size: int, *,
                      meanwhile=None, slot: int = None):
        """Run positions ``[start, start + prefill_chunk_tokens)`` of the
        prompt `tokens` (those of them it has), whose positions before
        `start` are in the KV pool ``pools["global"]``, read through
        `table` (the sequence's block table as it stands before this
        chunk's blocks are allocated), and whose state at `start` is in
        the state pool ``pools["state"]`` at `slot` (the sequence's: None
        before its first chunk has been stored, when `start` is 0 and
        nothing is read). `start` is a multiple of the chunk. Returns the
        host logits that predict the next token for the chunk that holds
        the prompt's last token, else None, and a `PromptState` of the
        chunk's KV rows and the state it ended on, on the device, for
        `write_range(seq, start, ...)`.

        A program of its own between two decode steps; `meanwhile` (the
        protocol's: `model.py`) runs behind the dispatch, and only the
        prompt's last chunk is waited for
        (`sparse_model._prompt_logits`): the state an earlier chunk ended
        on goes into its slot as the unfinished device value it is.
        One program a power of two of the prompt's length, and one for
        every prompt of up to four chunks, as the layer-groups models'."""
        with flight.span("model", "prefill", len(tokens)):
            return self._prefill_chunk(tokens, pools, table, start,
                                       block_size, meanwhile, slot)

    def _prefill_chunk(self, tokens, pools, table, start: int,
                       block_size: int, meanwhile, slot):
        phase, c = self.phase, self.prefill_chunk_tokens
        n = len(tokens)
        length = min(c, n - start)
        self.prefill_calls += 1
        self.prefill_tokens += length
        if start:
            self.prefill_later_chunks += 1
            if slot is None:
                raise ValueError(
                    f"a chunk at {start} without its sequence's state slot")
            self.prefill_state_chunks += 1
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
            packed = np.zeros((c + 3 + nb,), np.int32)
            packed[:length] = np.asarray(tokens[start:start + length],
                                         np.int32)
            packed[c], packed[c + 1] = start, length
            packed[c + 2] = slot or 0
            packed[c + 3:c + 3 + min(nb, len(table))] = table[:nb]
        with flight.span("model", "prefill.dispatch", None, phase,
                         "prefill_dispatch_s"):
            logits, rows, state = fn(
                pools[KVCacheManager.GLOBAL], pools[KVCacheManager.STATE],
                self._params, packed)
        self._program_dispatched(c)
        if meanwhile is not None:
            meanwhile()
        logits = self._prompt_logits(logits, start + length == n)
        return logits, PromptState(rows, length, state)
