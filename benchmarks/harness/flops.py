"""Operations and bytes of kernels every architecture shares, and the
roofline they are held against. What a model needs from its shapes alone
(parameters, training FLOPs, a decode step's bytes and FLOPs) is counted
by its family (`benchmarks/families/<name>.py`) and reaches a reader as
`ctx["counts"]`."""

from __future__ import annotations

from benchmarks.harness import manifest


# The dense block's counts live with their family; the names stay here
# for the readers and tests that import them.
_dense = manifest.load_family(manifest.DEFAULT_FAMILY)
param_counts = _dense.param_counts
train_flops_per_token = _dense.train_flops_per_token
kv_bytes_per_token = _dense.kv_bytes_per_token
decode_step_bytes = _dense.decode_step_bytes
decode_step_flops = _dense.decode_step_flops


def flash_attention_cost(batch: int, heads: int, seq_len: int,
                         head_dim: int, bytes_per_value: int = 2) -> dict:
    """Causal flash attention, forward + backward, over the whole batch:
    the FLOPs the algorithm needs (the masked half is not needed; the
    backward recomputes the scores by design, which is counted: 2 matmuls
    forward, 5 backward, each 2*S*S/2*hd per head) and the bytes it must
    move (q, k, v, o read or written once forward; q, k, v, o, do read and
    dq, dk, dv written backward)."""
    per_matmul = 2.0 * batch * heads * head_dim * seq_len * (seq_len + 1) / 2
    tensor = batch * heads * seq_len * head_dim * bytes_per_value
    return {"flops": 7 * per_matmul, "bytes": (4 + 8) * tensor}


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of operations over
    peak FLOP/s and bytes over peak bytes/s."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
