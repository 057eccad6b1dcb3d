"""TPU-native ops: attention (plain/ring), MoE dispatch, rotary embeddings.

The reference has no sequence-parallel or long-context kernels anywhere
(SURVEY.md §2.5 — ring attention/Ulysses absent, delegated to DeepSpeed user
code); these are designed new for the ICI mesh. `flash_attention` holds
the one-chip train step's causal flash kernels (Pallas; `attention()`
picks them and their tiles). `paged_attention` (decode
attention over the serving engine's paged KV pool, a Pallas kernel),
`latent_attention` (multi-head latent attention over a pool of one row a
position: the expanded and the absorbed form, the walk's latent body),
`delta_rule` (the gated delta rule: chunked for prefill, one step for
decode), `lightning_attention` (linear attention of one constant decay a
head, in the same two forms), `block_sparse_attention` (attention that
selects its key blocks from compressed keys of the cache itself: the
scores, the selection, a step over the chosen pages a key/value head at
a time) and `experts` (a dropless expert layer that holds a range of the
routed experts) are imported by the engine's models alone; `moe` is the
training model's capacity-drop dispatch.
"""

from ray_tpu.ops.attention import attention, plain_attention, ring_attention
from ray_tpu.ops.rotary import apply_rotary, rotary_freqs

__all__ = ["attention", "plain_attention", "ring_attention",
           "apply_rotary", "rotary_freqs"]
