"""The family of the one block the tree has run since the benchmark began:
a pre-norm decoder with plain multi-head attention, rotary embeddings on
the whole head, a silu-gated FFN, RMSNorm with a scale, tied embeddings
and no biases (`models/transformer.py`, served by
`TransformerEngineModel`). A configuration without a `family` key is of
this family.

A family's file is found by the name in a configuration's `family` key
(`manifest.load_family`) and is the only place under `benchmarks/` that
imports the program's model classes. It owns, for its architecture:
shapes, the serving build and its drive through the engine's cache, the
training build, the plain reference, counts, tolerances and what a traced
run wraps. `benchmarks/README.md` ("Adding an architecture") lists the
names the harness calls. Nothing at the top of this file imports JAX or
the program: the harness's process loads it for the shapes and counts.
"""

from __future__ import annotations

# ---------------------------------------------------------------------------
# shapes: published config.json keys -> this family's widths
# ---------------------------------------------------------------------------
# Published config.json keys -> `TransformerConfig` fields.
WIDTH_KEYS = {"vocab_size": "vocab_size", "hidden_size": "d_model",
              "num_hidden_layers": "n_layers",
              "num_attention_heads": "n_heads",
              "intermediate_size": "d_ff", "rope_theta": "rope_theta"}


def widths(config: dict) -> dict:
    """The `TransformerConfig` fields of a published config. The block the
    tree runs is plain multi-head attention with tied embeddings, a
    silu-gated FFN and no biases: a config that says otherwise is refused
    here, not run as something else."""
    problems = []
    if config.get("num_key_value_heads",
                  config["num_attention_heads"]) != \
            config["num_attention_heads"]:
        problems.append("grouped-query heads")
    if not config.get("tie_word_embeddings", False):
        problems.append("untied embeddings")
    if config.get("attention_bias", False):
        problems.append("attention biases")
    if config.get("hidden_act", "silu") != "silu":
        problems.append(f"activation {config.get('hidden_act')}")
    if problems:
        raise ValueError("the tree's one block cannot run this config: "
                         + ", ".join(problems))
    return {ours: config[theirs] for theirs, ours in WIDTH_KEYS.items()}


def toy_widths(w: dict) -> dict:
    """The same block at a size the CPU tests can hold: 4 heads of 16."""
    return dict(w, vocab_size=512, d_model=64, n_layers=2, n_heads=4,
                d_ff=128, rope_theta=10000.0)


# ---------------------------------------------------------------------------
# tolerances
# ---------------------------------------------------------------------------
# Tolerance of engine logits against the float32 reference at one
# position: the root-mean-square of the difference over the
# root-mean-square of the reference's logits. The engine keeps float32
# weights and activations but multiplies with XLA's default precision,
# which on a TPU rounds both operands to bf16 (the trace shows the
# weights converted each step) and accumulates in float32: measured
# 0.009-0.012 on the chip at the published widths, 1e-6 on the CPU
# (PERF.md, Findings). A wrong position, a stale or missing KV row or a
# dropped layer gives about 1: forty times the limit. The largest single
# logit's difference (over the same rms) is held to five times the limit;
# over 50 k logits it sits at four to five times the rms difference.
LOGIT_TOLERANCE = 0.025

# Tolerance of the first step's loss (bf16 activations, float32
# accumulation in the reductions, the flash kernel on one chip) against
# the float32 reference's loss on the same batch and weights. The loss is
# a mean over 16k-33k tokens, so bf16 rounding of single logits (2^-8
# relative, on logits of order one) averages out: measured differences
# are under 0.003 (PERF.md, Findings). At seeded random weights the loss
# sits about 0.5 above ln(V); attention or the FFN gone wrong moves it by
# more than 0.02.
LOSS_TOLERANCE = 0.01


# ---------------------------------------------------------------------------
# counts: operations and bytes the model needs, from its shapes alone.
# Recomputed operations (remat) are not counted: MFU is the model's FLOPs
# over the chip's peak, not the hardware's.
# ---------------------------------------------------------------------------
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
                      bytes_per_value: int = 4,
                      kv_bytes_per_value: int = None) -> float:
    """What one decode step must read from HBM at the least: every
    weight once (the tied embedding is the logits matmul) and the live
    KV of the batch. `bytes_per_value` is what a weight is held in,
    `kv_bytes_per_value` what a pool row is (the weights' where it is not
    given)."""
    if kv_bytes_per_value is None:
        kv_bytes_per_value = bytes_per_value
    return (param_counts(w)["total"] * bytes_per_value
            + live_kv_tokens * kv_bytes_per_token(w, kv_bytes_per_value))


def decode_step_flops(w: dict, batch: float, live_kv_tokens: float) -> float:
    return (2.0 * param_counts(w)["matmul"] * batch
            + 2 * 2 * w["d_model"] * w["n_layers"] * live_kv_tokens)


# What the tree holds today where no replica has said otherwise: float32
# weights and a float32 KV pool (the configurations' `departures`).
HELD_TODAY = {"weights": {"dtype": "float32", "bytes_per_value": 4},
              "kv_pool": {"dtype": "float32", "bytes_per_value": 4}}


def counts(w: dict, held: dict = None) -> dict:
    """What readers get as `ctx["counts"]`. `held` is what the replica
    reported of the weights and the KV pool it holds (`dtype`,
    `bytes_per_value` each); bytes are counted by it, never by a
    constant. Every parameter is active for every token and all of the
    published parameters of the cut are held, so `active` and `held`
    equal `total`; a sequence has no state beside its KV rows."""
    held = held or HELD_TODAY
    weight_bytes = held["weights"]["bytes_per_value"]
    kv_bytes = held["kv_pool"]["bytes_per_value"]
    params = param_counts(w)
    return {
        "params": dict(params, active=params["total"],
                       held=params["total"]),
        "held": held,
        "train_flops_per_token":
            lambda seq_len: train_flops_per_token(w, seq_len),
        "decode_step_flops":
            lambda batch, live_tokens: decode_step_flops(w, batch,
                                                         live_tokens),
        "decode_step_bytes":
            lambda batch, live_tokens: decode_step_bytes(
                w, live_tokens, weight_bytes, kv_bytes),
        "kv_bytes_per_token": kv_bytes_per_token(w, kv_bytes),
        "state_bytes_per_sequence": 0,
    }


# ---------------------------------------------------------------------------
# serving, in the replica that holds the chip
# ---------------------------------------------------------------------------
def build_serving(w: dict, settings: dict, seed: int) -> dict:
    """Widths + the cell's settings + seed -> seeded weights on the
    device, the engine model and the `EngineConfig`."""
    import jax

    from ray_tpu.models.transformer import TransformerConfig, init_params
    from ray_tpu.serve.engine import EngineConfig, TransformerEngineModel

    cfg = TransformerConfig(**w, max_seq_len=settings["max_seq_len"])
    # Weights on the device in one jitted call from the seed.
    params = jax.jit(lambda: init_params(
        jax.random.PRNGKey(seed % (2 ** 31 - 1)), cfg))()
    engine = dict(settings["engine"])
    model = TransformerEngineModel(
        params, cfg, max_batch_size=engine["max_batch_size"])
    # Random weights give no token the meaning "end of sequence".
    model.eos_token = None
    return {"params": params, "model": model,
            "engine_config": EngineConfig(**engine)}


def warm_bucket(engine, served: dict, batch: int, table_blocks: int) -> None:
    """A read-only fused step (empty write list) over block 0: compiles
    and runs the `(batch, table_blocks)` bucket."""
    block = engine.config.block_size
    model = served["model"]
    engine.cache.mutate_pool(
        lambda pool: model.decode_paged(
            pool, [[0] * table_blocks] * batch, [2] * batch,
            [table_blocks * block - 1] * batch, [], [], block))


def drive(engine, served: dict, tokens: list, steps: int, sid: str):
    """Prefill of `tokens`, then `steps` greedy decode steps through the
    engine's cache as the scheduler makes them, on a sequence of its own
    while the engine is idle. Returns the logits rows (one for the
    prefill, one for each step) and the tokens with the greedy ones
    appended."""
    import numpy as np

    cache, model = engine.cache, served["model"]
    block = engine.config.block_size
    tokens, n, got = list(tokens), len(tokens), []
    cache.allocate(sid, n, writable_from=0)
    logits, kv = model.prefill(tokens)
    cache.write_range(sid, 0, kv)
    got.append(np.asarray(logits))
    for _ in range(steps):
        tok = int(np.argmax(got[-1]))
        tokens.append(tok)
        pos = len(tokens) - 1
        cache.allocate(sid, len(tokens), writable_from=pos)
        table = cache.block_table(sid)
        logits = cache.paged_step(
            [(sid, pos)],
            lambda pool, blocks, offs: model.decode_paged(
                pool, [table], [tok], [pos], blocks, offs, block))
        got.append(np.asarray(logits)[0])
    cache.free(sid)
    return got, tokens


# What a `--trace 1` run wraps: the model's method behind each span.
TRACED_CALLS = {"prefill": "prefill", "decode_step": "decode_paged"}


def decode_step_rows_and_live(args: tuple, kwargs: dict):
    """Rows of one `decode_paged` call and the live tokens they attend
    over, from its arguments: `(pool, tables, lasts, positions, ...)`."""
    positions = args[3] if len(args) > 3 else kwargs["positions"]
    return len(positions), sum(int(p) + 1 for p in positions)


# ---------------------------------------------------------------------------
# training, in the worker that holds the chips
# ---------------------------------------------------------------------------
def build_training(w: dict, trainer: dict, seq_len: int, seed: int,
                   mesh) -> dict:
    """Widths + the cell's trainer settings + seed + mesh -> seeded
    sharded weights and the loss function for `make_train_step`."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.transformer import (TransformerConfig, init_params,
                                            lm_loss, param_specs)
    from ray_tpu.parallel.spmd import init_sharded

    cfg = TransformerConfig(
        **w, max_seq_len=seq_len, dtype=jnp.bfloat16,
        remat=True, remat_policy=trainer["remat_policy"])
    # Weights on the devices, sharded, in one jitted call from the seed.
    params = init_sharded(
        lambda: init_params(
            jax.random.PRNGKey(seed % (2 ** 31 - 1)), cfg),
        param_specs(cfg), mesh)
    return {"params": params,
            "loss_fn": lambda p, b: lm_loss(p, b, cfg, mesh=mesh)}


# ---------------------------------------------------------------------------
# the plain reference: float32, `default_matmul_precision("highest")`, no
# kernels, no cache, no batching tricks. Written from the published
# description of a pre-norm decoder (RMSNorm with a scale, multi-head
# attention with rotary embeddings on the whole head, silu-gated FFN, tied
# embeddings, no biases), not from `models/transformer.py` or
# `serve/engine/model.py`; it shares only the layout of the parameter tree
# with them, because it is handed the same seeded weights:
#
#     embed [V, d]; ln_f [d]; layers.{ln1, ln2} [L, d];
#     layers.wqkv [L, d, 3, d]; layers.wo [L, d, d];
#     layers.w13 [L, d, 2, f] (gate, up); layers.w2 [L, f, d]
#
# Rotary: the half-split form (x1, x2 = the two halves of a head), as
# GPT-NeoX and the HF implementations of both configurations use it.
# Called only in processes that own a device.
# ---------------------------------------------------------------------------
NORM_EPS = 1e-6   # the tree's fixed value; see each configuration's `assumed`


def _rms_norm(x, scale):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + NORM_EPS) * scale


def _rotary(x, theta: float):
    """x [S, H, hd] at positions 0..S-1."""
    import jax.numpy as jnp

    s, _, hd = x.shape
    inv_freq = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def logits_one_sequence(params, tokens, *, n_heads: int, rope_theta: float):
    """tokens [S] int32 -> logits [S, V], float32, one sequence."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    x = params["embed"].astype(f32)[tokens]                   # [S, d]
    s, d = x.shape
    hd = d // n_heads
    causal = jnp.tril(jnp.ones((s, s), bool))

    def block(x, lp):
        lp = jax.tree.map(lambda a: a.astype(f32), lp)
        y = _rms_norm(x, lp["ln1"])
        q, k, v = (jnp.dot(y, lp["wqkv"][:, i, :]).reshape(s, n_heads, hd)
                   for i in range(3))
        q, k = _rotary(q, rope_theta), _rotary(k, rope_theta)
        scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(f32(hd))
        scores = jnp.where(causal[None], scores, -jnp.inf)
        attn = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
        x = x + jnp.dot(attn.reshape(s, d), lp["wo"])
        y = _rms_norm(x, lp["ln2"])
        gate, up = jnp.dot(y, lp["w13"][:, 0, :]), jnp.dot(y, lp["w13"][:, 1, :])
        return x + jnp.dot(jax.nn.silu(gate) * up, lp["w2"]), None

    x, _ = jax.lax.scan(block, x, params["layers"])
    x = _rms_norm(x, params["ln_f"].astype(f32))
    return jnp.dot(x, params["embed"].astype(f32).T)


def _highest(fn):
    def run(*args, **kwargs):
        import jax

        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)
    return run


def make_logits_fn(n_heads: int, rope_theta: float):
    """jitted (params, tokens [S]) -> logits [S, V]."""
    import jax

    return jax.jit(_highest(lambda params, tokens: logits_one_sequence(
        params, tokens, n_heads=n_heads, rope_theta=rope_theta)))


def make_row_nll_fn(n_heads: int, rope_theta: float):
    """jitted (params, row [S+1]) -> summed next-token NLL of the row."""
    import jax
    import jax.numpy as jnp

    def row_nll(params, row):
        logits = logits_one_sequence(params, row[:-1], n_heads=n_heads,
                                     rope_theta=rope_theta)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, row[1:, None], axis=-1))
    return jax.jit(_highest(row_nll))


def lm_loss(params, tokens, *, n_heads: int, rope_theta: float) -> float:
    """Mean next-token NLL of a batch [B, S+1], one row at a time so that
    the float32 logits of one row are all that is ever held."""
    row_nll = make_row_nll_fn(n_heads, rope_theta)
    total = 0.0
    for i in range(tokens.shape[0]):
        total += float(row_nll(params, tokens[i]))
    return total / (tokens.shape[0] * (tokens.shape[1] - 1))


def reference_logits(w: dict):
    """(params, tokens [S] int32) -> logits [S, V] of one sequence."""
    return make_logits_fn(w["n_heads"], w["rope_theta"])


def reference_loss(w: dict):
    """(params, tokens [B, S+1]) -> mean next-token loss, a float."""
    return lambda params, tokens: lm_loss(
        params, tokens, n_heads=w["n_heads"], rope_theta=w["rope_theta"])
