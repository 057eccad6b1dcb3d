"""The engine model of the latent-attention / Gated DeltaNet sparse
decoder (`models/gigachat35.py`): multi-head latent attention over a
paged pool of ONE row a position in one layer of four, delta-rule layers
over a per-sequence state in the others, a dense MLP in the leading
layers and a sparse-expert layer with its held experts in the rest,
norms before and after every sublayer.

It is driven through the engine's calls (`model.py`) as a model with
state (`state_model.py`: `state_shapes`, the slot-order step, the host
side of a decode step), and adds two things no model before it had:

- **A latent cache row.** A position keeps ``[c_kv, k_r]`` (576 values)
  once for all 64 heads, in whole planes of 128 lanes (a row of 640:
  `ops/latent_attention.py`). The model's one KV group is that pool,
  held by planes: ``kv_token_shape = (MLA layers, P, 128)``. A prompt's
  attention is the *expanded* form through the prefill's forward, a
  decode step's the *absorbed* form over the pool's pages where they
  lie (`paged_latent_decode_attention`: the paged walk with a latent
  body, one fetch a page).
- **A chunk of a prompt that carries state** (`prefill_chunk`): the
  chunk's delta-rule layers start from what the sequence's state slot
  holds (zeros at position 0) and its payload carries the state it ended
  on; the latent layer gathers the earlier rows by the table, expands
  them, puts the chunk's own in and runs the forward from an offset.
  One program a power of two of the keys, the chunk's place a scalar.

Arithmetic: weights and the latent pool in `cfg.dtype` (bf16 on the
chip); the residual stream, norms, softmax, router scores, decay, beta,
gates and the delta-rule state in float32; a matrix product takes both
operands in `cfg.dtype` and accumulates in float32 (the absorbed form's
two products over the pool among them), the router's and the delta
rule's own products excepted (float32 at the highest precision); logits
float32.
"""

from __future__ import annotations

from ray_tpu.serve.engine.kv_cache import KVCacheManager
from ray_tpu.serve.engine.model import step_tokens
from ray_tpu.serve.engine.state_model import StateChunks, StateEngineModel


class GigaChatEngineModel(StateChunks, StateEngineModel):
    """Incremental decoding over `models/gigachat35.py` weights.

    KV entry a token: ``[n_mla_layers, P, 128]``, the latent rows. State
    a sequence: ``s`` ``[n_gdn_layers, H, dk, dk]`` float32 and ``conv``
    ``[n_gdn_layers, taps - 1, 2 Hk dk + H dk]``. A prompt of at most
    `prefill_chunk_tokens` is prefilled whole in pow2 length buckets; a
    longer one the scheduler runs through `prefill_chunk`; a decode step
    is jitted a (batch, table) bucket."""

    # As `layer_groups_model.py` says of its chunk: what a running row
    # waits behind, against one more read of the weights a chunk.
    prefill_chunk_tokens = 1024
    own_counters = ("decode_latent_pages_read", "prefill_later_chunks",
                    "prefill_state_chunks")

    def __init__(self, params, cfg, max_batch_size: int = 8,
                 jit_cache_cap: int = 32, gdn_chunk: int = 64):
        import jax.numpy as jnp

        from ray_tpu.ops.latent_attention import (LANES, kernel_eligible,
                                                  latent_planes)
        from ray_tpu.ops.paged_attention import live_pages, page_groups

        super().__init__(params, cfg, jit_cache_cap, max_batch_size)
        self._page_groups, self._live_pages = page_groups, live_pages
        self._chunk = gdn_chunk
        planes = latent_planes(cfg.latent_width)
        self._row_width = planes * LANES
        self.kv_token_shape = (cfg.n_mla_layers, planes, LANES)
        # A row has no heads to fill a tile with: held by planes.
        self.kv_planes = {KVCacheManager.GLOBAL: True}
        self.state_shapes = {
            "s": ((cfg.n_gdn_layers, cfg.gdn_heads, cfg.gdn_head_dim,
                   cfg.gdn_head_dim), jnp.float32),
            "conv": ((cfg.n_gdn_layers, cfg.conv_kernel - 1,
                      cfg.gdn_conv_width), self.kv_dtype)}
        self._attn_inplace = kernel_eligible(cfg.n_heads, cfg.kv_rank)
        # A position's bytes: as the pool holds it (whole planes) and as
        # the model counts it (the latent and the rotary key).
        itemsize = self.kv_dtype.itemsize
        self.kv_token_bytes_held = (cfg.n_mla_layers * self._row_width
                                    * itemsize)
        self.kv_token_bytes_model = (cfg.n_mla_layers * cfg.latent_width
                                     * itemsize)
        # Live latent pages the steps' tables named (all of them from a
        # pool held by planes), and their bytes both ways.
        self.decode_latent_pages_read = 0
        self.decode_kv_bytes_read_held = 0
        self.decode_kv_bytes_read_model = 0
        # Chunks that began past position 0, and those of them that began
        # from their sequence's state slot.
        self.prefill_later_chunks = 0
        self.prefill_state_chunks = 0

    # -- shared math ---------------------------------------------------
    def _norm(self, x, w, eps: float = None):
        """``x / sqrt(mean(x^2) + eps) * 2 sigmoid(w)``: the zero-centred
        gated scale (0 is a scale of 1)."""
        import jax
        import jax.numpy as jnp

        var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        return (x * jax.lax.rsqrt(var + (eps or self._cfg.norm_eps))
                * (2.0 * jax.nn.sigmoid(w)))

    def _rope(self, positions):
        from ray_tpu.ops.rotary import rotary_cos_sin, rotary_inv_freq

        cfg = self._cfg
        return rotary_cos_sin(positions, rotary_inv_freq(
            cfg.rope_dim, cfg.rope_theta, cfg.yarn or None))

    def _latent_inputs(self, y, lp, rope):
        """An MLA layer's queries and cache row for tokens `y` ``[T,
        d]``: q_nope ``[T, H, nope]``, q_r ``[T, H, rope]`` rotated, the
        normed latent ``[T, rank]`` and the one rotary key ``[T, rope]``,
        float32."""
        from ray_tpu.ops.rotary import apply_rotary_interleaved

        cfg = self._cfg
        t = y.shape[0]
        c_q = self._norm(self._mm(y, lp["wdq"]), lp["q_norm"])
        q = self._mm(c_q, lp["wuq"]).reshape(
            t, cfg.n_heads, cfg.nope_dim + cfg.rope_dim)
        down = self._mm(y, lp["wdkv"])
        c_kv = self._norm(down[:, :cfg.kv_rank], lp["kv_norm"])
        q_r = apply_rotary_interleaved(q[..., cfg.nope_dim:], *rope)
        k_r = apply_rotary_interleaved(down[:, None, cfg.kv_rank:], *rope)
        return q[..., :cfg.nope_dim], q_r, c_kv, k_r[:, 0]

    def _latent_output(self, y, o, lp):
        """``W_o(o * sigmoid(W_g y))``: o ``[T, H, dv]``."""
        import jax

        gate = jax.nn.sigmoid(self._mm(y, lp["wgate"]))
        return self._mm(o.reshape(o.shape[0], -1) * gate, lp["wo"])

    def _gdn_inputs(self, y, lp, window, live):
        """The delta rule's q, k, v ``[T, H, dk]``, g and beta ``[T, H]``
        for tokens `y` ``[T, d]`` from the convolution's `window``
        ``[taps, T, w]``; a key head serves ``H / Hk`` value heads.
        `live` ``[T]``: a token that is padding gets ``g = 0``, ``beta =
        0`` and leaves the state alone."""
        import jax
        import jax.numpy as jnp

        cfg = self._cfg
        t, dk = y.shape[0], cfg.gdn_head_dim
        kw = cfg.gdn_key_width
        mixed = self._short_conv(window, lp["conv"])
        q = mixed[:, :kw].reshape(t, cfg.gdn_key_heads, dk)
        k = mixed[:, kw:2 * kw].reshape(t, cfg.gdn_key_heads, dk)
        v = mixed[:, 2 * kw:].reshape(t, cfg.gdn_heads, dk)
        serves = cfg.gdn_heads // cfg.gdn_key_heads
        q = jnp.repeat(self._l2norm(q) * dk ** -0.5, serves, axis=1)
        k = jnp.repeat(self._l2norm(k), serves, axis=1)
        g = -jnp.exp(lp["a_log"])[None, :] * jax.nn.softplus(
            self._mm(y, lp["wa"]) + lp["dt_bias"])
        beta = jax.nn.sigmoid(self._mm(y, lp["wb"]))
        return (q, k, v, jnp.where(live[:, None], g, 0.0),
                jnp.where(live[:, None], beta, 0.0))

    def _gdn_output(self, y, o, lp):
        """``W_o(N_head(o) * 2 sigmoid(W_z y))``: o ``[T, H, dk]``."""
        import jax

        o = self._norm(o, lp["onorm"], self._cfg.o_norm_eps)
        gate = 2.0 * jax.nn.sigmoid(self._mm(y, lp["wz"]))
        return self._mm(o.reshape(o.shape[0], -1) * gate, lp["wo"])

    def _feed_forward(self, x, lp, valid):
        """A layer's second half, ``x + N_post(FFN(N_pre(x)))``: a dense
        MLP (no counts) or the expert layer."""
        import jax
        import jax.numpy as jnp

        mp = lp["mlp"]
        y = self._norm(x, lp["ln2"])
        if "router" in mp:
            routed, shared, counts = self._experts_of(y, mp, valid)
            out = routed if shared is None else shared + routed
        else:
            with jax.named_scope("dense_mlp"):
                out = self._gated_ffn(y, mp["gate"], mp["up"], mp["down"])
            counts = jnp.zeros((3,), jnp.int32)
        return x + self._norm(out, lp["ln2_post"]), counts

    def _kinds(self, params):
        """Every layer in order: its tree, whether it attends, and its
        index among the layers of its kind."""
        seen = {True: 0, False: 0}
        for i, lp in enumerate(params["layers"]):
            attends = i in self._cfg.mla_layers
            yield lp, attends, seen[attends]
            seen[attends] += 1

    # -- prefill -------------------------------------------------------
    def _prompt_layers(self, params, tokens, pos, length, carried, attend):
        """The layers over positions `pos` of one prompt, `tokens` there
        (a whole prompt in its bucket, or a chunk), of which the first
        `length` are live. `carried`: what the positions before left,
        ``{"s": [GDN layers, H, dk, dk], "conv": [GDN layers, taps - 1,
        w]}``. ``attend(q, c_kv, k_r, lp, index)`` is a latent layer's
        attention: q ``[S, H, nope + rope]`` and the positions' own
        latents and rotary keys in the pool's dtype; ``[H, S, dv]`` out.
        Returns the logits after the last live position, the latent rows
        ``[S, MLA layers, P, 128]`` and the state the positions end on."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.ops.delta_rule import delta_rule_chunked
        from ray_tpu.ops.latent_attention import latent_row

        cfg, f32 = self._cfg, jnp.float32
        act = params["embed"].dtype
        s_pad, taps = tokens.shape[0], cfg.conv_kernel
        chunk = min(self._chunk, s_pad)
        with jax.named_scope("embed"):
            x = params["embed"][tokens].astype(f32)            # [S, d]
        live = jnp.arange(s_pad) < length
        rope = self._rope(pos)
        # The prefill's forward scales by the keys' width alone.
        more = cfg.softmax_scale * (cfg.nope_dim + cfg.rope_dim) ** 0.5
        rows, states, tails = [], [], []
        for lp, attends, index in self._kinds(params):
            mp = lp["mixer"]
            y = self._norm(x, lp["ln1"])
            if attends:
                with jax.named_scope("attn_latent"):
                    q_nope, q_r, c_kv, k_r = self._latent_inputs(y, mp, rope)
                    c_kv, k_r = c_kv.astype(act), k_r.astype(act)
                    q = jnp.concatenate([q_nope, q_r], axis=-1) * more
                    o = attend(q.astype(act), c_kv, k_r, mp, index)
                    out = self._latent_output(y, o.transpose(1, 0, 2), mp)
                rows.append(latent_row(c_kv, k_r))
            else:
                with jax.named_scope("gdn"):
                    pre = self._mm(y, mp["wqkv"]).astype(act)
                    padded, window = self._prompt_window(
                        pre, carried["conv"][index], taps)
                    q, k, v, g, beta = self._gdn_inputs(y, mp, window, live)
                    o, s_end = delta_rule_chunked(
                        q, k, v, g, beta, carried["s"][index], chunk)
                    out = self._gdn_output(y, o, mp)
                states.append(s_end)
                # The inputs of the last taps - 1 live positions: what
                # the next token's convolution reads.
                tails.append(jax.lax.dynamic_slice_in_dim(
                    padded, length, taps - 1, axis=0))
            x = x + self._norm(out, lp["ln1_post"])
            x, _ = self._feed_forward(x, lp, live)
        with jax.named_scope("lm_head"):
            last = self._norm(x[length - 1], params["ln_f"])
            logits = self._mm(last[None], params["head"])[0]
        return (logits, jnp.stack(rows, axis=1),
                {"s": jnp.stack(states), "conv": jnp.stack(tails)})

    def _attend_expanded(self, q, rows_c_kv, rows_k_r, lp, **where):
        """The expanded form: the latents multiplied out into keys and
        values a head, then the prefill's forward."""
        import jax

        from ray_tpu.ops.attention import prefill_attention
        from ray_tpu.ops.latent_attention import expand_latent

        with jax.named_scope("latent_expand"):
            keys, vals = expand_latent(rows_c_kv, rows_k_r, lp["wuk"],
                                       lp["wuv"], self._cfg.n_heads)
        return prefill_attention(q.transpose(1, 0, 2), keys, vals, **where)

    def _build_prefill(self, s_pad: int):
        import jax
        import jax.numpy as jnp

        self.jit_compiles += 1
        shapes = self.state_shapes

        def attend(q, c_kv, k_r, lp, index):
            # A padded position lies after every live one: the causal
            # mask alone keeps it from a live query.
            return self._attend_expanded(q, c_kv, k_r, lp)

        def prefill(params, tokens, length):
            zeros = {name: jnp.zeros(shape, dt)
                     for name, (shape, dt) in shapes.items()}
            return self._prompt_layers(params, tokens, jnp.arange(s_pad),
                                       length, zeros, attend)

        return jax.jit(prefill)

    def _build_prefill_chunk(self, s_keys: int, block_size: int):
        """The program of one chunk of a prompt whose latent rows lie in
        `s_keys` positions: the chunk's place and the sequence's slot
        come in as scalars."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.ops.latent_attention import rows_of_pages

        self.jit_compiles += 1
        cfg = self._cfg
        c = self.prefill_chunk_tokens
        rank, width = cfg.kv_rank, cfg.latent_width

        def prefill_chunk(pool, state, params, packed):
            tokens, start, length, slot = (packed[:c], packed[c],
                                           packed[c + 1], packed[c + 2])
            table = packed[c + 3:]          # s_keys / block_size blocks
            # What the positions before the chunk left in the sequence's
            # slot; nothing came before position 0.
            carried = {name: jnp.where(start > 0, pool_[slot], 0)
                       for name, pool_ in state.items()}

            def attend(q, c_kv, k_r, lp, index):
                # The rows at their positions, the chunk's own among
                # them (whatever a row from `start` on reads in the
                # pool, no query sees it); every key up to the chunk's
                # last is live.
                with jax.named_scope("kv_gather"):
                    rows = rows_of_pages(pool[table, index])
                own = jnp.concatenate([c_kv, k_r], axis=-1)
                rows = jax.lax.dynamic_update_slice(
                    rows[:, :width], own, (start, jnp.int32(0)))
                return self._attend_expanded(
                    q, rows[:, :rank], rows[:, rank:], lp, offset=start,
                    live=start + c)

            return self._prompt_layers(params, tokens,
                                       start + jnp.arange(c), length,
                                       carried, attend)

        return jax.jit(prefill_chunk)

    # -- decode --------------------------------------------------------
    def _build_decode_paged(self, b_pad: int, nb_pad: int,
                            block_size: int):
        import jax
        import jax.numpy as jnp

        from ray_tpu.ops.delta_rule import delta_rule_step
        from ray_tpu.ops.latent_attention import (
            absorb_query, latent_row, paged_latent_decode_attention,
            unabsorb_output)
        from ray_tpu.ops.paged_attention import write_rows

        self.jit_compiles += 1
        cfg, f32 = self._cfg, jnp.float32

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
            rope = self._rope(positions)
            rows, counts = [], jnp.zeros((3,), jnp.int32)
            for lp, attends, index in self._kinds(params):
                mp = lp["mixer"]
                if attends:
                    with jax.named_scope("attn_latent"):
                        y = self._norm(x, lp["ln1"])
                        q_nope, q_r, c_kv, k_r = self._latent_inputs(
                            y, mp, rope)
                        row = latent_row(c_kv.astype(pool.dtype), k_r)
                        q = absorb_query(q_nope, q_r, mp["wuk"],
                                         self._row_width)
                        with jax.named_scope("kv_gather"):
                            o_lat = paged_latent_decode_attention(
                                q, row.reshape(b_pad, -1), pool, tables,
                                positions, jnp.int32(index), cfg.kv_rank,
                                cfg.softmax_scale)
                        out = self._latent_output(
                            y, unabsorb_output(o_lat, mp["wuv"]), mp)
                    rows.append(row)
                else:
                    with jax.named_scope("gdn"):
                        # Slot order: row i's input at slot slots[i].
                        y, tail, s, window = self._slot_inputs(
                            x, lp["ln1"], state, jnp.int32(index), slots,
                            lambda y: self._mm(y, mp["wqkv"]))
                        q, k, v, g, beta = self._gdn_inputs(
                            y, mp, window.transpose(1, 0, 2), used)
                        o, s = delta_rule_step(s, q, k, v, g, beta)
                        state = self._slot_store(state, jnp.int32(index), s,
                                                 window, tail, used)
                        out = self._gdn_output(y, o, mp)[
                            jnp.minimum(slots, n_slots - 1)]
                x = x + self._norm(out, lp["ln1_post"])
                x, c = self._feed_forward(x, lp, valid)
                counts += c
            with jax.named_scope("lm_head"):
                logits = self._mm(self._norm(x, params["ln_f"]),
                                  params["head"])
            with jax.named_scope("kv_write"):
                new_pool = write_rows(pool, wblocks, woffs,
                                      jnp.stack(rows, axis=1))
            with jax.named_scope("sample"):
                ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return self._step_out(ids, counts, b_pad), logits, new_pool, state

        return jax.jit(decode_paged, donate_argnums=(0, 1))

    # -- engine interface (`prefill_chunk`: `StateChunks`) --------------
    def _count_pages(self, pool, pages, nb_pad: int, positions,
                     block_size: int) -> None:
        """As the base's, by the pages that hold a cached position (what
        the walk fetches: `ops.paged_attention.live_pages`), with the
        latent rows' bytes as held and as the model counts them."""
        cached = sum(self._live_pages(int(p), block_size)
                     for p in positions)
        self.decode_attn_inplace_steps += 1
        self.decode_kv_pages_read += cached
        self.decode_kv_pages_read_planes += cached
        self.decode_latent_pages_read += cached
        self.decode_kv_page_groups_read += self._page_groups(
            pool, nb_pad, positions)
        self.decode_kv_bytes_read_held += (cached * block_size
                                           * self.kv_token_bytes_held)
        self.decode_kv_bytes_read_model += (cached * block_size
                                            * self.kv_token_bytes_model)
