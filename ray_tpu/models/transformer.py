"""Flagship decoder-only transformer LM, designed TPU-first.

Covers the reference's GPT-J-6B fine-tune role (BASELINE.md: DeepSpeed ZeRO-3
on GPUs, `release/release_tests.yaml:850-869`) the TPU way:

- GSPMD shardings on every weight (``param_specs``): FSDP/ZeRO over ``dp``,
  Megatron row/col over ``tp`` — zero-redundancy comes from the SPMD
  partitioner, not an optimizer-state wrapper.
- sequence parallelism: ring attention over ``sp`` (ops/attention.py).
- optional MoE layers with experts sharded over ``dp`` (ops/moe.py).
- layers stacked and scanned (`lax.scan`) for O(1) compile time in depth;
  `jax.checkpoint` rematerialization per layer when ``remat=True``.
- bfloat16 activations, float32 params/accumulators (MXU-friendly).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.ops.attention import attention
from ray_tpu.ops.flash_attention import ATTN_LSE, ATTN_OUT
from ray_tpu.ops.moe import moe_ffn
from ray_tpu.ops.rotary import apply_rotary, rotary_freqs


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    d_ff: int = 1376
    max_seq_len: int = 2048
    rope_theta: float = 10000.0
    num_experts: int = 0          # 0 => dense FFN in every layer
    moe_top_k: int = 2
    capacity_factor: float = 1.25
    dtype: Any = jnp.bfloat16     # activation/compute dtype
    param_dtype: Any = jnp.float32
    remat: bool = False
    # None = save nothing (recompute the whole layer); "dots" saves
    # matmul outputs and recomputes only elementwise work — often the
    # better FLOPs/HBM trade on TPU.
    remat_policy: Optional[str] = None
    aux_loss_weight: float = 0.01
    # >0 => the LM loss fuses the logits GEMM + softmax-NLL per sequence
    # chunk of this size, so the [B,S,V] logits tensor (1 GiB bf16 at
    # 16x1024x32k) is never materialized in HBM: each [B,chunk,V] block
    # lives only inside one rematerialized scan step.
    loss_chunk: int = 0

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 1


Params = Dict[str, Any]


def init_params(key, cfg: TransformerConfig) -> Params:
    d, f, h, v, l = (cfg.d_model, cfg.d_ff, cfg.n_heads * cfg.head_dim,
                     cfg.vocab_size, cfg.n_layers)

    def norm(key, shape, fan_in):
        return (jax.random.normal(key, shape, cfg.param_dtype)
                * (1.0 / fan_in) ** 0.5)

    keys = iter(jax.random.split(key, 16))
    layers: Dict[str, jax.Array] = {
        # Q/K/V fused into ONE [d, 3, h] projection (a single MXU GEMM of
        # [B*S, d] x [d, 3h] instead of three half-width ones); the packing
        # dim stays unsharded so q/k/v unpack without resharding under tp.
        "ln1": jnp.ones((l, d), cfg.param_dtype),
        "wqkv": norm(next(keys), (l, d, 3, h), d),
        "wo": norm(next(keys), (l, h, d), h),
        "ln2": jnp.ones((l, d), cfg.param_dtype),
    }
    if cfg.is_moe:
        e = cfg.num_experts
        layers["router"] = norm(next(keys), (l, d, e), d)
        layers["moe_w1"] = norm(next(keys), (l, e, d, f), d)
        layers["moe_w2"] = norm(next(keys), (l, e, f, d), f)
    else:
        # gate (w1) and up (w3) fused the same way: [d, 2, f].
        layers["w13"] = norm(next(keys), (l, d, 2, f), d)
        layers["w2"] = norm(next(keys), (l, f, d), f)
    return {
        "embed": norm(next(keys), (v, d), d),
        "layers": layers,
        "ln_f": jnp.ones((d,), cfg.param_dtype),
    }


def param_specs(cfg: TransformerConfig) -> Params:
    """PartitionSpec pytree mirroring `init_params` (dp=FSDP, tp=Megatron;
    layer-stack dim unsharded; experts over dp)."""
    layers: Dict[str, P] = {
        "ln1": P(None, None),
        "wqkv": P(None, "dp", None, "tp"),
        "wo": P(None, "tp", "dp"),
        "ln2": P(None, None),
    }
    if cfg.is_moe:
        layers["router"] = P(None, None, None)
        layers["moe_w1"] = P(None, "dp", None, "tp")
        layers["moe_w2"] = P(None, "dp", "tp", None)
    else:
        layers["w13"] = P(None, "dp", None, "tp")
        layers["w2"] = P(None, "tp", "dp")
    return {
        "embed": P("tp", "dp"),
        "layers": layers,
        "ln_f": P(None),
    }




_REMAT_POLICIES = {
    None: None,
    "dots": "dots_with_no_batch_dims_saveable",
    "dots_batch": "dots_saveable",
    # Save what the attention's backward needs of its forward, under the
    # names the path that `ops.attention.attention` took gives it: ONE
    # copy of the output a layer (~B*S*d bf16, 50 MB at 16x1024x1536),
    # [B,S,H,D] from the XLA and ring paths, [B,S,H*D] from the flash
    # kernels' VJP with its [B,H,1,S] float32 log-sum-exp (B*H*S*4 bytes,
    # 2.1 MB at 8x32x2048). The backward then re-runs the layer up to q,
    # k and v (norm, the qkv product, rotary, the kernels' transposes) and
    # from the output on (wo, the FFN whole), and no attention: the only
    # O(S^2) op of the layer, on the flash path a forward kernel a layer.
    "save_attn": ("names", (ATTN_OUT, ATTN_LSE)),
    # Additionally save the fused QKV projection (3x bigger than the
    # output): the backward skips the norm and the qkv product too, and
    # recomputes rotary, the transposes, wo and the FFN. Worth it when
    # HBM has headroom.
    "save_attn_qkv": ("names", (ATTN_OUT, ATTN_LSE, "qkv")),
}


def _checkpoint_layer(fn, policy_name):
    policy = None
    mapped = _REMAT_POLICIES.get(policy_name, policy_name)
    if isinstance(mapped, tuple) and mapped[0] == "names":
        policy = jax.checkpoint_policies.save_only_these_names(*mapped[1])
    elif mapped:
        policy = getattr(jax.checkpoint_policies, mapped)
    return jax.checkpoint(fn, static_argnums=(2, 3, 4), policy=policy)


def _rmsnorm(x, scale, eps=1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale.astype(x.dtype)


def _layer(x, lp, cfg: TransformerConfig, mesh, manual_sp, cos, sin,
           positions):
    b, s, d = x.shape
    h, hd = cfg.n_heads, cfg.head_dim
    act = cfg.dtype

    # -- attention block (the scopes name the ops in a profile) ---------
    with jax.named_scope("attn"):
        y = _rmsnorm(x, lp["ln1"])
        qkv = jnp.einsum("bsd,dkh->kbsh", y, lp["wqkv"].astype(act))
        qkv = checkpoint_name(qkv, "qkv")
        q = qkv[0].reshape(b, s, h, hd)
        k = qkv[1].reshape(b, s, h, hd)
        v = qkv[2].reshape(b, s, h, hd)
        # positions=None means "standard arange" — kept None through to
        # attention() so the fused TPU flash kernel stays eligible.
        pos = jnp.arange(s) if positions is None else positions
        q = apply_rotary(q, cos, sin, pos)
        k = apply_rotary(k, cos, sin, pos)
        if mesh is not None and not manual_sp:
            qkv_sharding = NamedSharding(mesh, P("dp", "sp", "tp", None))
            q, k, v = (jax.lax.with_sharding_constraint(t, qkv_sharding)
                       for t in (q, k, v))
        # attention() names what a checkpoint policy may save of it.
        o = attention(q, k, v, causal=True, mesh=mesh, positions=positions,
                      manual_sp=manual_sp)
        x = x + (o.reshape(b, s, h * hd) @ lp["wo"].astype(act))

    # -- FFN block ------------------------------------------------------
    with jax.named_scope("mlp"):
        y = _rmsnorm(x, lp["ln2"])
        if cfg.is_moe:
            ff, aux = moe_ffn(y, lp["router"], lp["moe_w1"], lp["moe_w2"],
                              top_k=cfg.moe_top_k,
                              capacity_factor=cfg.capacity_factor)
        else:
            gu = jnp.einsum("bsd,dkf->kbsf", y, lp["w13"].astype(act))
            ff = (jax.nn.silu(gu[0]) * gu[1]) @ lp["w2"].astype(act)
            aux = jnp.zeros((), jnp.float32)
        x = x + ff
    if mesh is not None and not manual_sp:
        x = jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P("dp", "sp", None)))
    return x, aux


def backbone(params: Params, tokens: jax.Array, cfg: TransformerConfig,
             mesh=None, positions: Optional[jax.Array] = None
             ) -> Tuple[jax.Array, jax.Array]:
    """tokens [B,S] int32 -> (final hidden states [B,S,D], aux scalar)."""
    act = cfg.dtype
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0).astype(act)
    if mesh is not None:
        x = jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P("dp", "sp", None)))
    cos, sin = rotary_freqs(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)

    def scan_body(carry, lp):
        fn = _layer
        if cfg.remat:
            fn = _checkpoint_layer(_layer, cfg.remat_policy)
        x_new, aux = fn(carry, lp, cfg, mesh, False, cos, sin, positions)
        return x_new, aux

    x, auxes = jax.lax.scan(scan_body, x, params["layers"])
    return _rmsnorm(x, params["ln_f"]), jnp.sum(auxes)


def forward(params: Params, tokens: jax.Array, cfg: TransformerConfig,
            mesh=None, positions: Optional[jax.Array] = None
            ) -> Tuple[jax.Array, jax.Array]:
    """tokens [B,S] int32 -> (logits [B,S,V], aux_loss scalar)."""
    x, aux = backbone(params, tokens, cfg, mesh, positions)
    # Tied embeddings. Logits stay in the compute dtype (bf16 on TPU): the
    # loss upcasts inside its reductions, so the [B,S,V] float32 array the
    # old code materialized (2 GB at B=16,S=1024,V=32k) never exists.
    # einsum instead of `x @ embed.T`: no materialized transpose, XLA
    # picks the contraction layout.
    with jax.named_scope("lm_head"):
        logits = jnp.einsum("bsd,vd->bsv", x,
                            params["embed"].astype(cfg.dtype))
    return logits, aux


def to_pipelined(params: Params, n_stages: int) -> Params:
    """Reshape stacked layer leaves [L, ...] -> [n_stages, L/n_stages, ...]
    for pipeline-parallel execution (leading dim sharded over ``pp``)."""
    out = dict(params)
    out["layers"] = jax.tree.map(
        lambda a: a.reshape(n_stages, a.shape[0] // n_stages, *a.shape[1:]),
        params["layers"])
    return out


def pipelined_param_specs(cfg: TransformerConfig) -> Params:
    """Specs matching `to_pipelined` output: layer leaves gain a leading
    ``pp`` dim; the original per-layer spec shifts right (its leading
    layer-stack dim was already None)."""
    base = param_specs(cfg)
    base["layers"] = {k: P("pp", *s) for k, s in base["layers"].items()}
    return base


def forward_pipelined(params: Params, tokens: jax.Array,
                      cfg: TransformerConfig, mesh,
                      num_microbatches: int = 2,
                      ) -> Tuple[jax.Array, jax.Array]:
    """Pipeline-parallel forward: embed/head replicated over ``pp``, layer
    stages flow through the GPipe schedule (parallel/pipeline.py), with
    ring-attention sequence parallelism fused into the same manual shard_map
    when the mesh has sp > 1."""
    from ray_tpu.parallel.pipeline import gpipe

    act = cfg.dtype
    x = jnp.take(params["embed"], tokens, axis=0).astype(act)
    positions = jnp.arange(tokens.shape[1])
    manual_sp = "sp" in mesh.axis_names and mesh.shape["sp"] > 1

    rope = rotary_freqs(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)

    def stage_fn(stage_layers, x_mb, pos, consts):
        cos, sin = consts

        def body(carry, lp):
            fn = _layer
            if cfg.remat:
                fn = _checkpoint_layer(_layer, cfg.remat_policy)
            x_new, aux = fn(carry, lp, cfg, mesh, manual_sp, cos, sin, pos)
            return x_new, aux

        x_out, auxes = jax.lax.scan(body, x_mb, stage_layers)
        return x_out, jnp.sum(auxes)

    x, aux = gpipe(stage_fn, params["layers"], x, positions, rope, mesh=mesh,
                   num_microbatches=num_microbatches)
    x = _rmsnorm(x, params["ln_f"])
    logits = jnp.einsum("bsd,vd->bsv", x, params["embed"].astype(act))
    return logits, aux


def _token_nll(logits, targets, mask=None) -> jax.Array:
    """Fused next-token NLL: logsumexp + target-logit gather, accumulated in
    float32. Unlike log_softmax→gather this never materializes a [B,S,V]
    float32 intermediate — XLA fuses the upcast into the reductions, so the
    logits are read from HBM in their compute dtype."""
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)   # [B,S]
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = lse - tgt.astype(jnp.float32)
    if mask is None:
        return jnp.mean(nll)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def _chunked_nll(x, embed, targets, mask, chunk: int) -> jax.Array:
    """Chunked fused cross-entropy over tied embeddings.

    x [B,S,D] final hiddens, embed [V,D]. The logits for each sequence chunk
    ([B,chunk,V]) exist only inside one `jax.checkpoint`-ed scan step: the
    forward reduces them to (sum_nll, count) immediately, and the backward
    recomputes the chunk's logits GEMM instead of reading a saved [B,S,V]
    from HBM. At 16x1024x32k bf16 that replaces 1 GiB of HBM write+read(x2)
    with a ~3% FLOPs recompute of the logits GEMM.
    """
    b, s, d = x.shape
    n = s // chunk
    # [n, B, C, D] so scan's leading axis is the chunk index. (Any sp
    # sharding on S is resharded here — far cheaper than full logits.)
    xs = x.reshape(b, n, chunk, d).transpose(1, 0, 2, 3)
    ts = targets.reshape(b, n, chunk).transpose(1, 0, 2)
    ms = (jnp.ones((b, s), jnp.float32) if mask is None
          else mask.astype(jnp.float32)).reshape(b, n, chunk).transpose(1, 0, 2)

    @jax.checkpoint
    def chunk_fn(x_c, t_c, m_c):
        logits = jnp.einsum("bcd,vd->bcv", x_c, embed)
        lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
        tgt = jnp.take_along_axis(logits, t_c[..., None], axis=-1)[..., 0]
        nll = lse - tgt.astype(jnp.float32)
        return jnp.sum(nll * m_c), jnp.sum(m_c)

    def body(carry, xc_tc_mc):
        tot, cnt = carry
        t, c = chunk_fn(*xc_tc_mc)
        return (tot + t, cnt + c), None

    (tot, cnt), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (xs, ts, ms))
    return tot / jnp.maximum(cnt, 1.0)


def lm_loss(params: Params, batch: Dict[str, jax.Array],
            cfg: TransformerConfig, mesh=None) -> jax.Array:
    """Next-token cross-entropy; batch = {"tokens": [B,S+1] int32,
    optional "mask": [B,S]}."""
    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    if cfg.loss_chunk and inputs.shape[1] % cfg.loss_chunk == 0:
        x, aux = backbone(params, inputs, cfg, mesh)
        with jax.named_scope("lm_head"):
            loss = _chunked_nll(x, params["embed"].astype(cfg.dtype),
                                targets, batch.get("mask"), cfg.loss_chunk)
    else:
        logits, aux = forward(params, inputs, cfg, mesh)
        loss = _token_nll(logits, targets, batch.get("mask"))
    return loss + cfg.aux_loss_weight * aux


def lm_loss_pipelined(params: Params, batch: Dict[str, jax.Array],
                      cfg: TransformerConfig, mesh,
                      num_microbatches: int = 2) -> jax.Array:
    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    logits, aux = forward_pipelined(params, inputs, cfg, mesh,
                                    num_microbatches=num_microbatches)
    loss = _token_nll(logits, targets, batch.get("mask"))
    return loss + cfg.aux_loss_weight * aux
