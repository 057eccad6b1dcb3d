"""The engine model of the hybrid sparse decoder (`models/hybrid_moe.py`):
grouped-query attention over the paged KV pool in one layer of four,
delta-rule linear attention over a per-sequence state in the other
three, a sparse-expert layer with its held experts in every layer.

It is driven through the engine's three calls (`model.py`), and declares
what `TransformerEngineModel` does not: `state_shapes`, the state a
sequence keeps beside its KV rows. The cache manager then holds a slot a
sequence (`kv_cache.py`), a prefill's result carries the state it ended
on, and `decode_paged` takes the state pool and the rows' slots beside
the KV pool and hands both pools back.

Arithmetic: weights and the KV pool in `cfg.dtype` (bf16 on the chip);
the residual stream, norms, softmax, router scores, decay, beta, gates
and the delta-rule state in float32; a matrix product takes both
operands in `cfg.dtype` and accumulates in float32, the router's and the
delta rule's own products excepted (float32 at the highest precision);
logits float32.

A decode step is one compiled program, as the dense model's: in, one
int32 array ``[b_pad, 6 + nb_pad]`` (token, position, write block, write
offset, state slot, block table, the row's place in the step before's
ids or -1: `model.step_tokens`) and the step before's result where it
lies on the device; out, one int32 array ``[width + 3]``: the greedy ids
at the model's largest batch bucket's width and the step's three expert
counters. The delta-rule
layers of a step run in slot order over the whole state pool (a row's
input scattered to its slot, the layer's output gathered back): the
pool is read and written where it lies, a slot no row of the step uses
keeps its state bit for bit, and no copy of the batch's state is built.
"""

from __future__ import annotations

from ray_tpu.serve.engine.model import step_tokens
from ray_tpu.serve.engine.state_model import (PromptState,  # noqa: F401
                                              StateEngineModel)


class HybridEngineModel(StateEngineModel):
    """Incremental decoding over `models/hybrid_moe.py` weights.

    KV entry a token: ``[n_periods, 2, n_kv_heads, head_dim]`` (the GQA
    layers alone keep KV). State a sequence: ``s`` ``[n_kda_layers, H,
    dk, dv]`` float32, the delta rule's, and ``conv`` ``[n_kda_layers,
    taps - 1, 3 H dk]``, the last inputs of the short convolutions.
    Prefill runs the prompt once (chunked delta rule, `kda_chunk`
    positions a chunk) in pow2 length buckets; a decode step is jitted a
    (batch, table) bucket. A prompt is prefilled whole, never from an
    adopted prefix (the engine adopts none over a model with state) and
    never in chunks: this model offers no `prefill_chunk`, and its prompt
    attention builds a prompt's whole score matrix. (The chunk that
    carries a state from its sequence's slot is
    `gigachat_model.GigaChatEngineModel.prefill_chunk`, over
    `state_model.py`, which also holds what the two models share: the
    payload, the host side of a decode step, the short convolution and
    the slot-order ends of a delta-rule layer's step.) The norm, the
    product helper, the expert layer and the counters are
    `sparse_model.SparseEngineModel`'s."""

    def __init__(self, params, cfg, max_batch_size: int = 8,
                 jit_cache_cap: int = 32, kda_chunk: int = 64):
        import jax.numpy as jnp

        from ray_tpu.models.hybrid_moe import KDA_PER_PERIOD
        from ray_tpu.ops.paged_attention import (kernel_eligible,
                                                 page_groups)

        super().__init__(params, cfg, jit_cache_cap, max_batch_size)
        self._page_groups = page_groups
        self._chunk = kda_chunk
        self.kv_token_shape = (cfg.n_periods, 2, cfg.n_kv_heads,
                               cfg.head_dim)
        dk = cfg.kda_head_dim
        self.state_shapes = {
            "s": ((cfg.n_kda_layers, cfg.kda_heads, dk, dk), jnp.float32),
            "conv": ((cfg.n_kda_layers, cfg.conv_kernel - 1,
                      3 * cfg.kda_width), self.kv_dtype)}
        self._kda_per_period = KDA_PER_PERIOD
        self._attn_inplace = kernel_eligible(cfg.n_heads, cfg.head_dim,
                                             cfg.n_kv_heads)

    def _over_periods(self, body, carry, xs):
        """`lax.scan` of `body` over the periods; one period runs
        inline, its weights sliced by a constant."""
        import jax

        if self._cfg.n_periods > 1:
            return jax.lax.scan(body, carry, xs)
        carry, ys = body(carry, jax.tree.map(lambda a: a[0], xs))
        return carry, jax.tree.map(lambda a: a[None], ys)

    def _kda_inputs(self, y, lp, window, live):
        """The delta rule's q, k, v, g, beta for tokens `y` ``[T, d]``
        from the convolution's `window` ``[taps, T, 3 H dk]`` (a token's
        own projection last). `live` ``[T]``: a token that is padding
        gets ``g = 0``, ``beta = 0`` and leaves the state alone."""
        import jax
        import jax.numpy as jnp

        cfg, f32 = self._cfg, jnp.float32
        t = y.shape[0]
        h, dk = cfg.kda_heads, cfg.kda_head_dim
        mixed = self._short_conv(window, lp["conv"])
        q, k, v = (mixed[:, i * h * dk:(i + 1) * h * dk].reshape(t, h, dk)
                   for i in range(3))

        q, k = self._l2norm(q) * dk ** -0.5, self._l2norm(k)
        g = -jnp.exp(lp["a_log"])[None, :, None] * jax.nn.softplus(
            self._mm(self._mm(y, lp["wf1"]), lp["wf2"])
            + lp["dt_bias"]).reshape(t, h, dk)
        beta = 2.0 * jax.nn.sigmoid(self._mm(y, lp["wb"]))
        g = jnp.where(live[:, None, None], g, 0.0)
        beta = jnp.where(live[:, None], beta, 0.0)
        return q, k, v, g, beta

    def _kda_output(self, y, o, lp):
        """``W_o(rmsnorm_head(o) * sigmoid(W_g2 W_g1 y))``."""
        import jax

        t = y.shape[0]
        o = self._norm(o, lp["onorm"]).reshape(t, -1)
        gate = jax.nn.sigmoid(self._mm(self._mm(y, lp["wg1"]), lp["wg2"]))
        return self._mm(o * gate, lp["wo"])

    # -- prefill -------------------------------------------------------
    def _build_prefill(self, s_pad: int):
        import jax
        import jax.numpy as jnp

        from ray_tpu.ops.delta_rule import delta_rule_chunked

        self.jit_compiles += 1
        cfg, f32 = self._cfg, jnp.float32
        hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        h, dk = cfg.kda_heads, cfg.kda_head_dim
        taps = cfg.conv_kernel
        chunk = min(self._chunk, s_pad)

        def prefill(params, tokens, length):
            act = params["embed"].dtype
            with jax.named_scope("embed"):
                x = params["embed"][tokens].astype(f32)        # [S, d]
            pos = jnp.arange(s_pad)
            live = pos < length
            causal = (pos[:, None] >= pos[None, :]) & live[None, :]

            def gqa(x, ln, lp):
                y = self._norm(x, ln)
                q = self._mm(y, lp["wq"]).reshape(s_pad, hkv, hq // hkv, hd)
                k = self._mm(y, lp["wk"]).astype(act).reshape(
                    s_pad, hkv, hd)
                v = self._mm(y, lp["wv"]).astype(act).reshape(
                    s_pad, hkv, hd)
                scores = jnp.einsum("qkgd,skd->kgqs", q.astype(act), k,
                                    preferred_element_type=f32) * hd ** -0.5
                scores = jnp.where(causal[None, None], scores, -1e30)
                probs = jax.nn.softmax(scores, axis=-1)
                o = jnp.einsum("kgqs,skd->qkgd", probs.astype(act), v,
                               preferred_element_type=f32)
                gate = jax.nn.sigmoid(self._mm(y, lp["wgate"]))
                out = self._mm(o.reshape(s_pad, hq * hd) * gate, lp["wo"])
                return x + out, jnp.stack([k, v], axis=1)

            def kda(x, ln, lp):
                y = self._norm(x, ln)
                pre = jnp.concatenate(
                    [self._mm(y, lp[w]) for w in ("wq", "wk", "wv")],
                    axis=-1).astype(act)                   # [S, 3 H dk]
                padded, window = self._prompt_window(
                    pre, jnp.zeros((taps - 1, pre.shape[1]), act), taps)
                q, k, v, g, beta = self._kda_inputs(y, lp, window, live)
                o, s_end = delta_rule_chunked(
                    q, k, v, g, beta, jnp.zeros((h, dk, dk), f32), chunk)
                # The inputs of positions length-3 .. length-1: what the
                # next token's convolution reads.
                tail = jax.lax.dynamic_slice_in_dim(padded, length,
                                                    taps - 1, axis=0)
                return x + self._kda_output(y, o, lp), s_end, tail

            def period(x, pp):
                with jax.named_scope("gqa_attn"):
                    x, kv = gqa(x, pp["ln1"][0], pp["gqa"])
                x, _ = self._experts(x, pp["ln2"][0],
                                     pp["moe"][0], live)
                states, tails = [], []
                for j in range(self._kda_per_period):
                    with jax.named_scope("kda"):
                        x, s_end, tail = kda(x, pp["ln1"][1 + j],
                                             pp["kda"][j])
                    x, _ = self._experts(x, pp["ln2"][1 + j],
                                         pp["moe"][1 + j], live)
                    states.append(s_end)
                    tails.append(tail)
                return x, (kv, jnp.stack(states), jnp.stack(tails))

            stacked = {k: params[k] for k in
                       ("ln1", "ln2", "gqa", "kda", "moe")}
            x, (kv, states, tails) = self._over_periods(period, x, stacked)
            with jax.named_scope("lm_head"):
                last = self._norm(x[length - 1], params["ln_f"])
                logits = self._mm(last[None], params["head"])[0]
            state = {"s": states.reshape((-1,) + states.shape[2:]),
                     "conv": tails.reshape((-1,) + tails.shape[2:])}
            # kv [P, S, 2, Hkv, hd] -> [S, P, 2, Hkv, hd]
            return logits, kv.transpose(1, 0, 2, 3, 4), state

        return jax.jit(prefill)

    # -- decode --------------------------------------------------------
    def _build_decode_paged(self, b_pad: int, nb_pad: int,
                            block_size: int):
        import jax
        import jax.numpy as jnp

        from ray_tpu.ops.delta_rule import delta_rule_step
        from ray_tpu.ops.paged_attention import paged_decode_attention

        self.jit_compiles += 1
        cfg, f32 = self._cfg, jnp.float32
        hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        per = self._kda_per_period

        def decode_paged(pool, state, params, packed, before):
            tokens, positions = step_tokens(packed, before), packed[:, 1]
            wblocks, woffs, slots = packed[:, 2], packed[:, 3], packed[:, 4]
            tables = packed[:, 5:-1]
            n_slots = state["s"].shape[0]
            # A padding row names slot `n_slots`: its scatter drops, it
            # routes to no expert, and what it gathers is thrown away.
            valid = slots < n_slots
            used = jnp.zeros((n_slots,), bool).at[slots].set(
                True, mode="drop")
            with jax.named_scope("embed"):
                x = params["embed"][tokens].astype(f32)        # [B, d]

            def gqa(x, ln, lp, layer):
                y = self._norm(x, ln)
                q = self._mm(y, lp["wq"]).reshape(b_pad, hq, hd)
                k = self._mm(y, lp["wk"]).astype(pool.dtype).reshape(
                    b_pad, hkv, hd)
                v = self._mm(y, lp["wv"]).astype(pool.dtype).reshape(
                    b_pad, hkv, hd)
                with jax.named_scope("kv_gather"):
                    o = paged_decode_attention(q, k, v, pool, tables,
                                               positions, layer)
                gate = jax.nn.sigmoid(self._mm(y, lp["wgate"]))
                out = self._mm(o.reshape(b_pad, hq * hd) * gate, lp["wo"])
                return x + out, jnp.stack([k, v], axis=1)

            def kda(x, ln, lp, state, layer):
                # Slot order: row i's input at slot slots[i].
                y, tail, s, window = self._slot_inputs(
                    x, ln, state, layer, slots,
                    lambda y: jnp.concatenate(
                        [self._mm(y, lp[w]) for w in ("wq", "wk", "wv")],
                        axis=-1))
                q, k, v, g, beta = self._kda_inputs(
                    y, lp, window.transpose(1, 0, 2), used)
                o, s = delta_rule_step(s, q, k, v, g, beta)
                state = self._slot_store(state, layer, s, window, tail,
                                         used)
                out = self._kda_output(y, o, lp)
                return x + out[jnp.minimum(slots, n_slots - 1)], state

            def period(carry, xs):
                x, state, counts = carry
                pp, p = xs
                with jax.named_scope("gqa_attn"):
                    x, kv = gqa(x, pp["ln1"][0], pp["gqa"], p)
                x, c = self._experts(x, pp["ln2"][0],
                                     pp["moe"][0], valid)
                counts += c
                for j in range(per):
                    with jax.named_scope("kda"):
                        x, state = kda(x, pp["ln1"][1 + j],
                                       pp["kda"][j], state,
                                       p * per + j)
                    x, c = self._experts(x, pp["ln2"][1 + j],
                                         pp["moe"][1 + j], valid)
                    counts += c
                return (x, state, counts), kv

            stacked = {k: params[k] for k in
                       ("ln1", "ln2", "gqa", "kda", "moe")}
            (x, state, counts), new_kv = self._over_periods(
                period, (x, state, jnp.zeros((3,), jnp.int32)),
                (stacked, jnp.arange(cfg.n_periods, dtype=jnp.int32)))
            with jax.named_scope("lm_head"):
                logits = self._mm(self._norm(x, params["ln_f"]),
                                  params["head"])
            with jax.named_scope("kv_write"):
                # new_kv [P, B, 2, Hkv, hd] -> [B, P, 2, Hkv, hd]
                new_pool = pool.at[wblocks, woffs].set(
                    new_kv.transpose(1, 0, 2, 3, 4), mode="drop")
            with jax.named_scope("sample"):
                ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return self._step_out(ids, counts, b_pad), logits, new_pool, state

        return jax.jit(decode_paged, donate_argnums=(0, 1))
