"""Operations and bytes the model needs, from its shapes alone.

Recomputed operations (remat) are not counted: MFU is the model's FLOPs
over the chip's peak, not the hardware's."""

from __future__ import annotations


def param_counts(w: dict) -> dict:
    """`w`: TransformerConfig fields (vocab_size, d_model, n_layers,
    n_heads, d_ff). One block: fused QKV [d, 3d], out [d, d], gate+up
    [d, 2f], down [f, d], two norm scales; tied embedding [V, d]."""
    d, f, layers, v = w["d_model"], w["d_ff"], w["n_layers"], w["vocab_size"]
    per_layer_matmul = 4 * d * d + 3 * d * f
    return {
        "embedding": v * d,
        "layer_matmul": per_layer_matmul,
        "matmul": layers * per_layer_matmul + v * d,   # logits reuse embed
        "total": layers * (per_layer_matmul + 2 * d) + v * d + d,
    }


def train_flops_per_token(w: dict, seq_len: int) -> float:
    """Forward + backward: 6 per matmul parameter, plus causal attention.
    Per layer and token the forward does QK^T and PV over (S+1)/2 keys on
    average: 2 * 2 * d * (S+1)/2 = 2*d*(S+1); three times that with the
    backward."""
    n = param_counts(w)["matmul"]
    attn = 3 * 2 * w["d_model"] * (seq_len + 1) * w["n_layers"]
    return 6.0 * n + attn


def kv_bytes_per_token(w: dict, bytes_per_value: int = 4) -> int:
    """K and V of every layer for one position: [L, 2, H, hd]."""
    return w["n_layers"] * 2 * w["d_model"] * bytes_per_value


def decode_step_bytes(w: dict, live_kv_tokens: float,
                      bytes_per_value: int = 4) -> float:
    """What one decode step must read from HBM at the least: every
    weight once (the tied embedding is the logits matmul) and the live
    KV of the batch."""
    return (param_counts(w)["total"] * bytes_per_value
            + live_kv_tokens * kv_bytes_per_token(w, bytes_per_value))


def decode_step_flops(w: dict, batch: float, live_kv_tokens: float) -> float:
    return (2.0 * param_counts(w)["matmul"] * batch
            + 2 * 2 * w["d_model"] * w["n_layers"] * live_kv_tokens)


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
