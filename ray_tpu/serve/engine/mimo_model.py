"""The engine model of the sparse decoder of `models/mimo_v2.py`: global
layers on few key/value heads (4 under 64 query heads) beside window
layers on twice as many, keys of 192 values over values of 128, a value
scale, a learned sink logit a head in the window layers' softmax, one
partial rotary with a base a layer kind, a dense MLP in the first layer
and held experts behind a sigmoid router with a selection bias, without
a shared expert, in every other.

Its KV lives in two layer groups of the cache, and what a model over two
layer groups does with them is `layer_groups_model.py`'s; here are this
decoder's layers. A row of a group holds a key in one and a half slots
of the values' 128 (`ops.paged_attention.kv_row`: three slots a
key/value head, the third half zeros), so a pool holds 384 values a
key/value head a layer where the model counts 320: `kv_token_bytes_held`
over `kv_token_bytes_model`.

Arithmetic: weights and both KV pools in `cfg.dtype` (bf16 on the chip);
the residual stream, norms, softmax with the sink, rotary tables and the
router's product (at the highest precision) in float32; a matrix product
takes both operands in `cfg.dtype` and accumulates in float32; logits
float32. The value scale multiplies the float32 product ``y W_v`` before
it is rounded to the pool's dtype.
"""

from __future__ import annotations

from ray_tpu.serve.engine.layer_groups_model import LayerGroupsEngineModel


class MimoEngineModel(LayerGroupsEngineModel):
    """Incremental decoding over `models/mimo_v2.py` weights.

    KV entry a token: ``[n_global_layers, 3, kv_heads_global, 128]`` in
    the global group and ``[n_window_layers, 3, kv_heads_window, 128]``
    in the window group at the published widths (`kv_groups`)."""

    def _attention_widths(self, full: bool):
        cfg = self._cfg
        return (cfg.n_heads, cfg.head_dim, cfg.kv_heads(not full),
                cfg.v_head_dim)

    def _group_layers(self, full: bool) -> int:
        return (self._cfg.n_global_layers if full
                else self._cfg.n_window_layers)

    def _rope(self, positions, full: bool):
        """cos, sin ``[T, rot_dim // 2]`` of a layer kind at
        `positions`."""
        from ray_tpu.ops.rotary import rotary_cos_sin, rotary_inv_freq

        cfg = self._cfg
        return rotary_cos_sin(positions, rotary_inv_freq(
            cfg.rot_dim, cfg.theta_global if full else cfg.theta_window))

    def _qkv(self, y, lp, full: bool, rope):
        """The rotated q ``[T, H, dk]`` and k ``[T, Hkv, dk]``, and the
        scaled v ``[T, Hkv, dv]``, float32."""
        from ray_tpu.ops.rotary import apply_rotary_partial

        cfg = self._cfg
        t, hkv = y.shape[0], cfg.kv_heads(not full)
        q = self._mm(y, lp["wq"]).reshape(t, cfg.n_heads, cfg.head_dim)
        k = self._mm(y, lp["wk"]).reshape(t, hkv, cfg.head_dim)
        v = self._mm(y, lp["wv"]).reshape(t, hkv, cfg.v_head_dim)
        return (apply_rotary_partial(q, *rope),
                apply_rotary_partial(k, *rope), cfg.value_scale * v)

    def _mixer_out(self, y, o, lp):
        """``W_o`` of the heads' outputs ``[T, H, dv]``."""
        return self._mm(o.reshape(o.shape[0], -1), lp["wo"])

    def _layers(self, params):
        """(mixer's tree, ln1, ln2, feed-forward tree, is it global) of
        every layer, in order."""
        for layer, window in zip(params["layers"],
                                 self._cfg.layer_is_window):
            yield (layer["mixer"], layer["ln1"], layer["ln2"],
                   layer["mlp"], not window)
