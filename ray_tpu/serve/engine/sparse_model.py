"""What the engine models that keep their own programs share. Every one
of them (`DecoderEngineModel`: `minicpm_sala_model.py`, a dense decoder,
and through `SparseEngineModel` the sparse ones): the norm, the product
helper, the gated feed-forward, the prefill's bucket and dispatch, the
decode step's one upload and one fetch, and the counters the engine's
`stats()` reads. The sparse decoders beside it (`SparseEngineModel`:
`hybrid_model.py`, `gigachat_model.py`, `keye_model.py`, and through
`layer_groups_model.py` `laguna_model.py` and `mimo_model.py`): the
expert layer with its three counts, which a decode program sends along
behind its ids. A model keeps its own layers, its packed row and its
pools."""

from __future__ import annotations

from typing import Dict, Sequence

import numpy as np

from ray_tpu.core import flight
from ray_tpu.serve.engine.model import (DecodeStep, StepIds, _JitLRU,
                                        _next_pow2, read_after_dispatch)


class DecoderEngineModel(StepIds):
    """Base of an engine model over seeded weights `params` and a config
    `cfg` with `vocab_size`, `norm_eps`, `dtype` (and `swiglu_limit`,
    where a gated feed-forward clamps its two factors). A subclass builds
    its jitted programs (`_build_prefill(s_pad)`) and packs its decode
    row."""

    def __init__(self, params, cfg, jit_cache_cap: int = 32,
                 max_batch_size: int = 8):
        import jax
        import jax.numpy as jnp

        self._params = params
        self._cfg = cfg
        self._max_batch = max_batch_size
        self.vocab_size = cfg.vocab_size
        self.eos_token = 1
        self.kv_dtype = jnp.dtype(cfg.dtype)
        self._prefill_jit = _JitLRU(jit_cache_cap)
        self._decode_paged_jit = _JitLRU(jit_cache_cap)
        self.prefill_calls = 0
        self.prefill_tokens = 0
        # Chunks of prompts that handed the host nothing and were not
        # waited for (`_prompt_logits`).
        self.prefill_chunks_unwaited = 0
        self.decode_calls = 0
        self.jit_compiles = 0
        # As `TransformerEngineModel`'s: what a decode step moves across
        # the host boundary, and how it reads the KV pool.
        self.decode_h2d_arrays = 0
        self.decode_d2h_bytes = 0
        self.decode_attn_inplace_steps = 0
        self.decode_kv_pages_read = 0
        self.decode_kv_page_groups_read = 0
        # Of `decode_kv_pages_read`, the pages read from a pool held by
        # planes (`ops.paged_attention.by_planes`).
        self.decode_kv_pages_read_planes = 0
        self.phase: Dict[str, float] = dict.fromkeys(
            ("prefill_prep_s", "prefill_dispatch_s", "prefill_wait_s",
             "prefill_kv_d2h_s", "decode_prep_s", "decode_dispatch_s",
             "decode_wait_s"), 0.0)
        self._jnp = jnp
        self._tree_leaves = jax.tree_util.tree_leaves

    @property
    def kv_pool_ns(self):
        return self._jnp

    @property
    def jit_cache_evictions(self) -> int:
        return (self._prefill_jit.evictions
                + self._decode_paged_jit.evictions)

    # -- shared math ---------------------------------------------------
    def _norm(self, x, scale):
        import jax
        import jax.numpy as jnp

        var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + self._cfg.norm_eps) * scale

    @staticmethod
    def _mm(y, w):
        """Both operands in the weights' dtype, float32 out."""
        import jax.numpy as jnp

        return jnp.dot(y.astype(w.dtype), w,
                       preferred_element_type=jnp.float32)

    def _gated_ffn(self, y, gate, up, down):
        """``W_down(silu(W_gate y) * W_up y)``, both factors clamped at
        the model's `swiglu_limit` where it has one (as
        `ops.experts.gated`)."""
        import jax
        import jax.numpy as jnp

        # (Written out, not through `gated`: the products and the silu
        # keep the order they have always had in a program.)
        limit = getattr(self._cfg, "swiglu_limit", None)
        a = self._mm(y, gate)
        if limit is not None:
            a = jnp.minimum(a, limit)
        a, b = jax.nn.silu(a), self._mm(y, up)
        if limit is not None:
            b = jnp.clip(b, -limit, limit)
        return self._mm(a * b, down)

    # -- the host side of the two calls --------------------------------
    def _run_prefill(self, tokens: Sequence[int]):
        """The prompt through its pow2 length bucket's program. Returns
        the host logits, what else the program returned, and the
        prompt's length."""
        jnp, phase = self._jnp, self.phase
        self.prefill_calls += 1
        n = len(tokens)
        self.prefill_tokens += n
        with flight.span("model", "prefill.prep", None, phase,
                         "prefill_prep_s"):
            s_pad = _next_pow2(max(n, 8))
            fn = self._prefill_jit.get(s_pad)
            if fn is None:
                fn = self._prefill_jit[s_pad] = self._build_prefill(s_pad)
            padded = np.zeros((s_pad,), np.int32)
            padded[:n] = np.asarray(tokens, np.int32)
            args = (jnp.asarray(padded), jnp.int32(n))
        with flight.span("model", "prefill.dispatch", None, phase,
                         "prefill_dispatch_s"):
            logits, *rest = fn(self._params, *args)
        self._program_dispatched(s_pad)
        return self._prompt_logits(logits), rest, n

    def _prompt_logits(self, logits, last: bool = True):
        """What a prefill program hands the host, the one rule of every
        model's `prefill` and `prefill_chunk`. From the program that
        holds the prompt's last token (`last`: a whole prompt's, or the
        chunk with ``start + length == n``) the logits of its first
        token, read here: the call's one wait. From any other chunk
        None, and NOTHING is waited for: the chunk's rows and state are
        unfinished device values, which `write_range`'s donated scatter
        and the batch's decode step take as they are, dispatched behind
        the chunk, so the host's turn between them passes beside a busy
        device. What keeps that sound is the device's order, not the
        host's wait: programs run in dispatch order, so whatever the
        host does after this call returns (a block `allocate` gives
        back and hands to another sequence) can reach the device only
        behind the chunk that still reads it. An error only the device
        can raise surfaces where its values are next read: the decode
        step's ids, or the prompt's last chunk here."""
        if not last:
            self.prefill_chunks_unwaited += 1
            return None
        with flight.span("model", "prefill.logits_wait", None, self.phase,
                         "prefill_wait_s"):
            return np.asarray(logits)

    def _program_dispatched(self, rows: int) -> None:
        """A program of `rows` rows (a prefill's bucket, a decode
        step's) has been dispatched, so traced: a subclass counts what
        it got when it was."""

    def _step_out(self, ids, counts, b_pad: int):
        """Inside a decode program: its one int32 result, the greedy ids
        at the fixed width, then `_ids_trail` counters of the step
        (`counts`; None where the model sends none along)."""
        import jax.numpy as jnp

        ids = jnp.pad(ids, (0, self._ids_width(b_pad) - b_pad))
        return ids if counts is None else jnp.concatenate([ids, counts])

    def _run_decode(self, fn, args, b: int, b_pad: int, meanwhile=None,
                    ahead=None):
        """One dispatch of a decode bucket's program `fn` over `args`
        (the packed host array among them: the step's one upload) and
        the step before's result on the device (`ahead`'s, else zeros).
        Returns the `DecodeStep` and what else the program returned (the
        pools). `meanwhile` and `ahead` are the protocol's (`model.py`):
        the one runs between the dispatch and the wait, the other says
        whose ids the wait is for."""
        phase = self.phase
        self.decode_h2d_arrays += sum(
            isinstance(leaf, np.ndarray)
            for leaf in self._tree_leaves(args))
        before = self._before(ahead, b_pad)
        with flight.span("model", "decode.dispatch", None, phase,
                         "decode_dispatch_s"):
            out, logits, *rest = fn(*args, before)
        self._program_dispatched(b_pad)
        step = DecodeStep(out, b, logits, self)
        if meanwhile is not None:
            meanwhile()
        read_after_dispatch(step, ahead)
        return step, rest

    def _fetch_ids(self, on_device) -> np.ndarray:
        """A decode step is read (`DecodeStep.ids`): the wait for the
        device, the trip of its int32 result to the host (the greedy
        ids, then what the program sent along: `_ids_trail`), both
        counted."""
        with flight.span("model", "decode.logits_wait", None, self.phase,
                         "decode_wait_s"):
            out = np.asarray(on_device)
            self.decode_d2h_bytes += out.nbytes
        return out

    def prefill_paged(self, tokens: Sequence[int], pool,
                      block_table: Sequence[int], prefix_len: int,
                      block_size: int):
        """The engine adopts no prefix over these models (a state, a
        window group), so the offset is always 0 and this is
        `prefill`."""
        if prefix_len:
            raise ValueError(
                "a prefix's KV blocks do not restore what this model "
                "keeps a sequence: it prefills a prompt whole")
        return self.prefill(tokens)


class SparseEngineModel(DecoderEngineModel):
    """A decoder with sparse-expert layers: `cfg` also has `top_k`,
    `routed_scaling`, `experts_held` (and `router_scoring`, where the
    router does not score by sigmoid)."""

    def __init__(self, params, cfg, jit_cache_cap: int = 32,
                 max_batch_size: int = 8):
        super().__init__(params, cfg, jit_cache_cap, max_batch_size)
        # The expert layers' counts over decode steps, summed over
        # layers, computed inside the step and fetched with its ids:
        # (token, expert) pairs on held experts; (layer, expert) pairs
        # with at least one token; the largest load of a held expert.
        self.moe_local_assignments = 0
        self.moe_expert_touches = 0
        self.moe_max_expert_load = 0
        # Programs run (a prefill, a decode step: each runs every expert
        # layer once) by the body their expert layers got when they were
        # traced, `ops.experts`' kernel or its scan, found by their rows.
        self.moe_steps_kernel = 0
        self.moe_steps_scan = 0
        self._experts_kernel_at: Dict[int, bool] = {}

    def _experts(self, x, ln2, mp, valid):
        """The expert layer's residual add (with the shared expert's,
        where the layer's tree has one); returns the new `x` and the
        layer's three counts."""
        routed, x, counts = self._experts_of(self._norm(x, ln2), mp, valid,
                                             x)
        return x + routed, counts

    def _experts_of(self, y, mp, valid, onto=None):
        """The expert layer over its normed input `y`: the held experts'
        part of the routed sum, the shared expert's output (None where
        the layer's tree has none; added onto `onto`, the residual
        stream, where one is given) and the layer's three counts."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.ops.experts import (held_experts_ffn, kernel_eligible,
                                         route)

        cfg = self._cfg
        self._experts_kernel_at[y.shape[0]] = kernel_eligible(
            *y.shape, mp["w_gate"].shape[2], mp["w_gate"].dtype)
        with jax.named_scope("moe_route"):
            experts, weights = route(
                y, mp["router"], mp.get("select_bias"), cfg.top_k,
                cfg.routed_scaling,
                getattr(cfg, "router_scoring", "sigmoid"))
        with jax.named_scope("moe_experts"):
            routed, load = held_experts_ffn(
                y, experts, weights, mp["w_gate"], mp["w_up"],
                mp["w_down"], cfg.experts_held, valid,
                getattr(cfg, "swiglu_limit", None))
            shared = onto
            if "shared_gate" in mp:
                shared = self._gated_ffn(y, mp["shared_gate"],
                                         mp["shared_up"], mp["shared_down"])
                if onto is not None:
                    shared = onto + shared
        counts = jnp.stack([jnp.sum(load), jnp.sum(load > 0),
                            jnp.max(load)]).astype(jnp.int32)
        return routed, shared, counts

    def _count_experts_step(self, rows: int) -> None:
        """A program of `rows` rows has been dispatched (so traced)."""
        if self._experts_kernel_at.get(rows):
            self.moe_steps_kernel += 1
        else:
            self.moe_steps_scan += 1

    _program_dispatched = _count_experts_step

    # A decode program's int32 result: the ids at `_ids_width`, then
    # the step's three expert counters.
    _ids_trail = 3

    def _fetch_ids(self, on_device) -> np.ndarray:
        """As the base's, the step's three expert counters taken off
        the end and counted."""
        out = super()._fetch_ids(on_device)
        self.moe_local_assignments += int(out[-3])
        self.moe_expert_touches += int(out[-2])
        self.moe_max_expert_load += int(out[-1])
        return out[:-3]
