"""The engine model of the window-and-global sparse decoder
(`models/laguna.py`): one full attention layer and three sliding-window
layers a period, different query-head counts over the same key/value
heads, a gate a head, two rotary schemes, a dense MLP in layer 0 and
held experts behind a softmax router in every other layer.

Its KV lives in two layer groups of the cache, and what a model over two
layer groups does with them (the prefill's and the decode step's
programs, the packed step buffer, the window table, the counters by
group) is `layer_groups_model.py`'s; here are this decoder's layers.

Arithmetic: weights and both KV pools in `cfg.dtype` (bf16 on the chip);
the residual stream, norms, softmax, rotary tables, gates and the
router's product (at the highest precision) in float32; a matrix product
takes both operands in `cfg.dtype` and accumulates in float32; logits
float32. (The decode step's scores take the rotated query in float32
against the pool's keys, as the hybrid model's do.)
"""

from __future__ import annotations

from ray_tpu.serve.engine.layer_groups_model import (  # noqa: F401
    GLOBAL, WINDOW, LayerGroupsEngineModel, PromptGroups)


class LagunaEngineModel(LayerGroupsEngineModel):
    """Incremental decoding over `models/laguna.py` weights.

    KV entry a token: ``[n_full_layers, 2, n_kv_heads, head_dim]`` in the
    global group and ``[n_sliding_layers, 2, n_kv_heads, head_dim]`` in
    the window group (`kv_groups`)."""

    def _attention_widths(self, full: bool):
        cfg = self._cfg
        return (cfg.heads_full if full else cfg.heads_sliding,
                cfg.head_dim, cfg.n_kv_heads, cfg.head_dim)

    def _group_layers(self, full: bool) -> int:
        return (self._cfg.n_full_layers if full
                else self._cfg.n_sliding_layers)

    def _rope(self, positions, full: bool):
        """cos, sin ``[T, rot_dim // 2]`` of a layer kind at
        `positions`."""
        from ray_tpu.ops.rotary import rotary_cos_sin, rotary_inv_freq

        rope = self._cfg.rope_full if full else self._cfg.rope_sliding
        inv = rotary_inv_freq(rope["rot_dim"], rope["theta"],
                              rope.get("yarn"))
        return rotary_cos_sin(positions, inv,
                              rope.get("attention_factor", 1.0))

    def _qkv(self, y, lp, full: bool, rope):
        """The rotated q ``[T, H, hd]`` and k, and v ``[T, Hkv, hd]``,
        float32."""
        from ray_tpu.ops.rotary import apply_rotary_partial

        cfg = self._cfg
        t = y.shape[0]
        heads = cfg.heads_full if full else cfg.heads_sliding
        q = self._mm(y, lp["wq"]).reshape(t, heads, cfg.head_dim)
        k = self._mm(y, lp["wk"]).reshape(t, cfg.n_kv_heads, cfg.head_dim)
        v = self._mm(y, lp["wv"]).reshape(t, cfg.n_kv_heads, cfg.head_dim)
        return (apply_rotary_partial(q, *rope),
                apply_rotary_partial(k, *rope), v)

    def _mixer_out(self, y, o, lp):
        """``W_o`` of the heads' outputs ``[T, H, hd]``, each times its
        head's gate ``sigmoid(y W_g)``."""
        import jax

        gate = jax.nn.sigmoid(self._mm(y, lp["wgate"]))         # [T, H]
        return self._mm((o * gate[:, :, None]).reshape(o.shape[0], -1),
                        lp["wo"])

    def _layers(self, params):
        """(mixer's tree, ln1, ln2, feed-forward tree, is it full) of
        every layer, in order."""
        for pp in params["periods"]:
            mixers = [(pp["full"], True)] + [(lp, False)
                                             for lp in pp["sliding"]]
            for j, (lp, full) in enumerate(mixers):
                yield lp, pp["ln1"][j], pp["ln2"][j], pp["mlp"][j], full
