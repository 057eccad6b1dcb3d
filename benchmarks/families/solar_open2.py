"""The family of hybrid sparse decoders with a recurrent state: a period
of one grouped-query softmax layer (no rotation, an output gate) and
three gated delta-rule linear-attention layers (a decay a channel, short
convolutions, beta in (0, 2)), a sparse-expert layer in every layer
(sigmoid router over all experts, a selection bias, top k, one shared
expert), pre-norm RMSNorm, an untied head. Served by
`HybridEngineModel`; there is no training half.

A configuration of this family is one chip's share of a deployment in
which `share_chips` chips share each layer: attention, the delta-rule
layers, the shared expert and the router whole on every chip (data
parallel), ``n_routed_experts`` of the published experts held here
(expert parallel; the router keeps its published width), the vocabulary
sliced. The reference is handed the same share.

What a reader of `benchmarks/README.md` ("Adding an architecture") needs
to know of a family with state:

- `counts` fills `state_bytes_per_sequence` (the delta rule's float32
  state and the convolutions' tails of every linear-attention layer) and
  `kv_bytes_per_token` (the softmax layers alone keep KV). Its
  `decode_step_bytes(rows, live)` is the non-expert weights, the head,
  the held experts a step of `rows` rows touches by expectation under
  uniform routing, twice the rows' state (read and written) and the live
  KV. `params` tells `total` (the published model) from `held` (on this
  chip) from `active` (a token, published model); `moe` gives the
  readers of the expert counters their denominators.
- `held` (what the replica reports, the harness's `held_bytes`) covers
  the weights and the KV pool only. The state pool's types are not in
  it: `counts` takes the state's bytes from `STATE_BYTES_PER_VALUE`
  below, and the state's float32 is held three ways: the declared dtype
  against the configuration's `arithmetic` and every value of the
  state's shape inside the jitted steps (`tests/test_hybrid_engine.py`),
  and on every run by `own_limits`.
- A family may hold limits the harness does not know. This one's
  `drive` compares its rows and the state slot it ended on with the
  reference itself (`own_limits`: the least of a drive's positions, the
  state, the state's bits) and, where they fail, hands back rows that are no
  numbers: `correct` comes out false. The harness's `LOGIT_TOLERANCE`
  holds every position, and over a discrete top-k router it cannot be
  tight (the tolerances below say why).
- The engine adopts no prefix for a model that declares state, so
  `drive` and the served path always prefill a prompt whole.

Nothing at the top of this file imports JAX or the program.
"""

from __future__ import annotations

# ---------------------------------------------------------------------------
# shapes
# ---------------------------------------------------------------------------
KDA_PER_PERIOD = 3
LAYERS_PER_PERIOD = 4
# The delta rule's state is float32 and the convolutions' tails are in
# the weights' dtype (the configuration's `arithmetic`).
STATE_BYTES_PER_VALUE = 4


# The program's files this family drives, under the `ray_tpu` package the
# process would import. A checkout that lacks them (the parent of the PR
# that brought the family) cannot run its cells, and says so when the
# cell is resolved, before any cluster or chip is touched: a replica that
# cannot import its model would be restarted until the deployment times
# out.
PROGRAM_FILES = ("models/hybrid_moe.py", "serve/engine/hybrid_model.py")


def widths(config: dict) -> dict:
    """Published keys -> `HybridConfig` fields (plus `kda_chunk`). A
    config this family's block does not compute is refused, as is a
    program that has no such model."""
    import importlib.util
    import os

    package = importlib.util.find_spec("ray_tpu")   # found, not imported
    where = list(package.submodule_search_locations) if package else [""]
    missing = [f for f in PROGRAM_FILES
               if not os.path.isfile(os.path.join(where[0], f))]
    if missing:
        raise ValueError(f"this tree's ray_tpu lacks {', '.join(missing)}: "
                         f"it cannot serve a hybrid model with state")
    lin = config.get("linear_attn_config", {})
    layers = config["num_hidden_layers"]
    problems = []
    for key, want in (("use_rope", False), ("use_gqa_gate", True),
                      ("kda_use_full_proj", False),
                      ("kda_allow_neg_eigval", True),
                      ("first_k_dense_replace", 0),
                      ("tie_word_embeddings", False),
                      ("n_shared_experts", 1), ("norm_topk_prob", True),
                      ("gqa_interval", KDA_PER_PERIOD)):
        if config.get(key) != want:
            problems.append(f"{key}={config.get(key)!r} (runs {want!r})")
    if layers % LAYERS_PER_PERIOD or config.get("gqa_layers") != list(
            range(0, layers, LAYERS_PER_PERIOD)):
        problems.append("a depth that is no whole number of [GQA, KDA, "
                        "KDA, KDA] periods")
    if lin.get("num_kv_heads") is not None:
        problems.append("grouped heads in the linear-attention layers")
    if config["num_attention_heads"] % config["num_key_value_heads"]:
        problems.append("query heads no multiple of key/value heads")
    held = config.get("experts_held")
    if not held or held[1] - held[0] != config["n_routed_experts"]:
        problems.append("experts_held does not name n_routed_experts "
                        "experts")
    if problems:
        raise ValueError("the hybrid block cannot run this config: "
                         + ", ".join(problems))
    published = config.get("published", {})
    return {
        "vocab_size": config["vocab_size"],
        "d_model": config["hidden_size"],
        "n_periods": layers // LAYERS_PER_PERIOD,
        "n_heads": config["num_attention_heads"],
        "n_kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "kda_heads": lin["num_heads"],
        "kda_head_dim": lin["head_dim"],
        "conv_kernel": lin["short_conv_kernel_size"],
        "gate_rank": config["assumed"]["kda_gate_rank"]["value"],
        "n_experts": published.get("n_routed_experts",
                                   config["n_routed_experts"]),
        "experts_held": list(held),
        "top_k": config["num_experts_per_tok"],
        "expert_width": config["moe_intermediate_size"],
        "shared_width": (config["n_shared_experts"]
                         * config["moe_intermediate_size"]),
        "routed_scaling": float(config["routed_scaling_factor"]),
        "norm_eps": config["rms_norm_eps"],
        "dtype": config["arithmetic"]["weights"],
        "kda_chunk": 64,
        # The published model, for `counts`: depth, experts, vocabulary.
        "published": {
            "n_periods": published.get("num_hidden_layers", layers)
            // LAYERS_PER_PERIOD,
            "vocab_size": published.get("vocab_size",
                                        config["vocab_size"])},
    }


def toy_widths(w: dict) -> dict:
    """The same block at a size the CPU tests hold: 2 periods, 4 heads
    of 16 (2 key/value heads), 16 experts of which 2 are held, top 4,
    float32 throughout (the CPU tests compare exactly; the chip's
    arithmetic is checked on the chip)."""
    return dict(w, vocab_size=512, d_model=64, n_periods=2, n_heads=4,
                n_kv_heads=2, head_dim=16, kda_heads=4, kda_head_dim=16,
                gate_rank=16, n_experts=16, experts_held=[0, 2], top_k=4,
                expert_width=32, shared_width=32, dtype="float32",
                kda_chunk=16,
                published={"n_periods": 2, "vocab_size": 512})


def model_config(w: dict):
    """`HybridConfig` of the widths (in a process that may import the
    program). No position is encoded: the model has no longest context
    of its own, the cell's `max_seq_len` bounds the traffic alone."""
    from ray_tpu.models.hybrid_moe import HybridConfig

    fields = {k: v for k, v in w.items()
              if k not in ("kda_chunk", "published")}
    fields["experts_held"] = tuple(fields["experts_held"])
    return HybridConfig(**fields)


# ---------------------------------------------------------------------------
# tolerances
# ---------------------------------------------------------------------------
# Engine logits against the float32 reference at one position: rms of the
# difference over rms of the reference's logits. The engine rounds the
# operands of a matrix product to bf16 (the weights are stored so) and
# accumulates in float32. Three limits, and a precision below the stated
# one has to fail by one of them (the readings: PERF.md, Findings, PR 32).
#
# `LOGIT_TOLERANCE`, the harness's, holds every position (the largest
# single logit to five times it). What sets it is the router, not the
# rounding: 766 of 792 checked positions read 0.004-0.025, and at the
# other 26 the operands' noise swapped a token's eighth and ninth expert
# where their scores are close: when one of the two is held here that
# position's logits move by the expert's weighted output (0.03-0.080;
# largest single logit 0.32), and so do the next few through the state.
# Two swaps at one position would read about 0.1; a row that decodes from
# another row's state reads 1.3 at toy widths.
LOGIT_TOLERANCE = 0.12

# The family's own, which `drive` holds and the harness does not know
# (`own_limits`): what a swap cannot reach, a lower precision does.
#
# `POSITIONS_TOLERANCE`: the least of a drive's positions (the last of the
# prompt and the decode steps) reads within it. A swap, and what it leaves
# in the state for the next few tokens, moves one position of a drive or
# some (two of four in 1 of 120 sound drives on the chip, three never); a
# lower precision moves them all. Sound drives' least reading is at most
# 0.0148; with the held experts, routed and shared, at fp8's 3 mantissa
# bits it is at least 0.0520 (40 and 6 seeds, my chip runs, PR 32).
POSITIONS_TOLERANCE = 0.028
# `STATE_TOLERANCE`: the delta rule's state after the drive's last token
# against the reference's, rms of the difference over rms, the worst
# layer: 0.007-0.028 sound, 0.033 where a swap fell on the last tokens,
# 0.078-0.091 with the experts at fp8. And the state holds float32's
# bits: `STATE_BF16_SHARE` is the largest share of its values that bf16
# may hold exactly (chance gives 2**-16, the chip reads 0.00003-0.00005;
# a state rounded to bf16 after every update gives 1.0, and its logits
# and its state read as a sound run's: this is the limit that sees it).
STATE_TOLERANCE = 0.055
STATE_BF16_SHARE = 0.01

# No training half: nothing reads this. `test_bench_manifest` asks every
# family for the name.
LOSS_TOLERANCE = 0.01


# ---------------------------------------------------------------------------
# counts
# ---------------------------------------------------------------------------
def param_counts(w: dict) -> dict:
    d, r = w["d_model"], w["gate_rank"]
    kw = w["kda_heads"] * w["kda_head_dim"]
    q_w, kv_w = (w["n_heads"] * w["head_dim"],
                 w["n_kv_heads"] * w["head_dim"])
    kda = (4 * d * kw + 2 * (d * r + r * kw) + d * w["kda_heads"]
           + 3 * w["conv_kernel"] * kw + w["kda_heads"] + kw
           + w["kda_head_dim"])
    gqa = 2 * d * q_w + 2 * d * kv_w + q_w * d
    expert = 3 * d * w["expert_width"]
    shared = 3 * d * w["shared_width"]
    router = d * w["n_experts"] + w["n_experts"]
    # A layer beside its routed experts: mixer, shared expert, router,
    # two norms; averaged over a period where the kinds differ.
    kda_rest = kda + shared + router + 2 * d
    gqa_rest = gqa + shared + router + 2 * d
    period_rest = KDA_PER_PERIOD * kda_rest + gqa_rest
    n_held = w["experts_held"][1] - w["experts_held"][0]
    layers = w["n_periods"] * LAYERS_PER_PERIOD
    pub = w["published"]
    pub_layers = pub["n_periods"] * LAYERS_PER_PERIOD
    head = w["vocab_size"] * d
    return {
        "kda_layer": kda, "gqa_layer": gqa, "expert": expert,
        "shared_expert": shared, "router": router,
        "rest_held": w["n_periods"] * period_rest,      # non-expert
        "experts_held": layers * n_held * expert,
        "head": head,
        "held": (w["n_periods"] * period_rest + layers * n_held * expert
                 + 2 * head + d),
        "total": (pub["n_periods"] * period_rest
                  + pub_layers * w["n_experts"] * expert
                  + 2 * pub["vocab_size"] * d + d),
        "active": (pub["n_periods"] * period_rest
                   + pub_layers * w["top_k"] * expert
                   + 2 * pub["vocab_size"] * d + d),
    }


def state_bytes_per_sequence(w: dict, weight_bytes: int) -> int:
    """The delta rule's ``[H, dk, dv]`` float32 state and the
    convolutions' ``[taps - 1, 3 H dk]`` tails, every linear layer."""
    h, dk = w["kda_heads"], w["kda_head_dim"]
    layers = w["n_periods"] * KDA_PER_PERIOD
    return layers * (h * dk * dk * STATE_BYTES_PER_VALUE
                     + (w["conv_kernel"] - 1) * 3 * h * dk * weight_bytes)


def kv_bytes_per_token(w: dict, kv_bytes: int) -> int:
    """K and V of the softmax layers for one position."""
    return w["n_periods"] * 2 * w["n_kv_heads"] * w["head_dim"] * kv_bytes


def experts_touched(w: dict, rows: float) -> float:
    """Held experts of one layer with at least one of `rows` tokens, by
    expectation, when every token picks `top_k` of the router's experts
    uniformly."""
    n_held = w["experts_held"][1] - w["experts_held"][0]
    return n_held * (1.0 - (1.0 - w["top_k"] / w["n_experts"]) ** rows)


def decode_step_bytes(w: dict, rows: float, live_kv_tokens: float,
                      weight_bytes: int, kv_bytes: int) -> float:
    """What one decode step of `rows` rows must move at the least: the
    non-expert weights and the head once, the expected held experts it
    touches, its rows' state read and written, and the live KV."""
    p = param_counts(w)
    layers = w["n_periods"] * LAYERS_PER_PERIOD
    return ((p["rest_held"] + p["head"]) * weight_bytes
            + layers * experts_touched(w, rows) * p["expert"] * weight_bytes
            + 2 * rows * state_bytes_per_sequence(w, weight_bytes)
            + live_kv_tokens * kv_bytes_per_token(w, kv_bytes))


def decode_step_flops(w: dict, rows: float, live_kv_tokens: float) -> float:
    """2 a matmul parameter a row (a row's expert pairs that fall on held
    experts by expectation), the softmax layers' scores and values over
    the live KV, and about 8 operations a state value a row."""
    p = param_counts(w)
    layers = w["n_periods"] * LAYERS_PER_PERIOD
    n_held = w["experts_held"][1] - w["experts_held"][0]
    pairs_here = w["top_k"] * n_held / w["n_experts"]
    h, dk = w["kda_heads"], w["kda_head_dim"]
    return (2.0 * rows * (p["rest_held"] + p["head"]
                          + layers * pairs_here * p["expert"])
            + 2 * 2 * w["n_heads"] * w["head_dim"] * w["n_periods"]
            * live_kv_tokens
            + 8.0 * rows * w["n_periods"] * KDA_PER_PERIOD * h * dk * dk)


# What the tree holds where no replica has said otherwise (the
# configuration's `arithmetic`).
HELD_TODAY = {"weights": {"dtype": "bfloat16", "bytes_per_value": 2},
              "kv_pool": {"dtype": "bfloat16", "bytes_per_value": 2}}


def counts(w: dict, held: dict = None) -> dict:
    """What readers get as `ctx["counts"]` (module docstring)."""
    held = held or HELD_TODAY
    weight_bytes = held["weights"]["bytes_per_value"]
    kv_bytes = held["kv_pool"]["bytes_per_value"]
    return {
        "params": param_counts(w),
        "held": held,
        "moe": {"layers": w["n_periods"] * LAYERS_PER_PERIOD,
                "experts_held": (w["experts_held"][1]
                                 - w["experts_held"][0])},
        "experts_touched": lambda rows: experts_touched(w, rows),
        "decode_step_flops":
            lambda batch, live_tokens: decode_step_flops(w, batch,
                                                         live_tokens),
        "decode_step_bytes":
            lambda batch, live_tokens: decode_step_bytes(
                w, batch, live_tokens, weight_bytes, kv_bytes),
        "kv_bytes_per_token": kv_bytes_per_token(w, kv_bytes),
        "state_bytes_per_sequence":
            state_bytes_per_sequence(w, weight_bytes),
    }


# ---------------------------------------------------------------------------
# serving, in the replica that holds the chip
# ---------------------------------------------------------------------------
def build_serving(w: dict, settings: dict, seed: int) -> dict:
    import jax

    from ray_tpu.models.hybrid_moe import init_params
    from ray_tpu.serve.engine import EngineConfig, HybridEngineModel

    cfg = model_config(w)
    params = jax.jit(lambda: init_params(
        jax.random.PRNGKey(seed % (2 ** 31 - 1)), cfg))()
    engine = dict(settings["engine"])
    model = HybridEngineModel(
        params, cfg, max_batch_size=engine["max_batch_size"],
        kda_chunk=w["kda_chunk"])
    model.eos_token = None     # random weights: no token means "end"
    return {"params": params, "model": model, "widths": w,
            "engine_config": EngineConfig(**engine)}


def warm_bucket(engine, served: dict, batch: int, table_blocks: int) -> None:
    """A step of `batch` rows that belong to no sequence (no write slot,
    no state slot) over block 0: compiles and runs the bucket, and leaves
    both pools as they were."""
    block = engine.config.block_size
    model = served["model"]
    engine.cache.paged_step(
        [], lambda pool, blocks, offs, state, slots: model.decode_paged(
            pool, [[0] * table_blocks] * batch, [2] * batch,
            [table_blocks * block - 1] * batch, blocks, offs, block,
            state, slots))


def drive(engine, served: dict, tokens: list, steps: int, sid: str):
    """Prefill of `tokens`, then `steps` greedy decode steps through the
    engine's cache (KV blocks and the sequence's state slot) as the
    scheduler makes them, on a sequence of its own while the engine is
    idle. Returns the logits rows and the tokens with the greedy ones
    appended. A drive that breaks one of the family's own limits
    (`own_limits`) while every row is inside the harness's
    `LOGIT_TOLERANCE` hands its rows back as NaN: the harness counts a
    row that is no number as not correct, the one way a family has to
    fail a run by a limit the harness does not know. (Rows outside the
    harness's limit fail by it, and keep their numbers.)"""
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
            lambda pool, blocks, offs, state, slots: model.decode_paged(
                pool, [table], [tok], [pos], blocks, offs, block, state,
                slots))
        got.append(np.asarray(logits)[0])
    state = cache.read_state(sid)["s"]
    cache.free(sid)
    readings = own_limits(served, got, tokens, n, state)
    served.setdefault("own_limits", []).append(readings)
    if not readings["ok"] and readings["positions"][-1] <= LOGIT_TOLERANCE:
        got = [np.full_like(row, np.nan) for row in got]
    return got, tokens


def own_limits(served: dict, got: list, tokens: list, n: int,
               state) -> dict:
    """The family's own limits over one drive (the tolerances above):
    the reference's logits and state on the drive's tokens against the
    rows and the state slot the engine ended on."""
    import numpy as np

    def gap(x, expect):
        return float(np.sqrt(np.mean((x - expect) ** 2)
                             / np.mean(expect * expect)))

    want, want_state = reference(served["widths"])(
        served["params"], np.asarray(tokens, np.int32))
    want, want_state = np.asarray(want), np.asarray(want_state)
    positions = sorted(gap(row, want[n - 1 + j])
                       for j, row in enumerate(got))
    state = np.ascontiguousarray(state, np.float32)
    readings = {
        "positions": positions,
        "state": max(gap(s, expect) for s, expect in
                     zip(state, want_state)),
        # float32 values whose low 16 bits are clear: bf16 holds them.
        "state_bf16_share": float(np.mean(
            state.view(np.uint32) & 0xFFFF == 0))}
    readings["ok"] = bool(
        positions[0] <= POSITIONS_TOLERANCE
        and readings["state"] <= STATE_TOLERANCE
        and readings["state_bf16_share"] <= STATE_BF16_SHARE)
    return readings


TRACED_CALLS = {"prefill": "prefill", "decode_step": "decode_paged"}


def decode_step_rows_and_live(args: tuple, kwargs: dict):
    """Rows of one `decode_paged` call and the live tokens its softmax
    layers attend over: `(pool, tables, lasts, positions, ...)`."""
    positions = args[3] if len(args) > 3 else kwargs["positions"]
    return len(positions), sum(int(p) + 1 for p in positions)


# ---------------------------------------------------------------------------
# the plain reference: float32, `default_matmul_precision("highest")`,
# the recurrence a token at a time, a dense loop over the held experts,
# no cache, no kernels, no batching. Written from the layers' equations
# (ISSUE 32; the configuration's `assumed` and `departures`), not from
# `serve/engine/hybrid_model.py` or `ray_tpu/ops/`; it shares only the
# layout of the parameter tree, because it is handed the same seeded
# weights (`models/hybrid_moe.init_params`: every leaf stacked by period
# P; `kda` a list of a period's three layers, `moe` of its four):
#
#     embed [V, d]; head [d, V]; ln_f [d]; ln1, ln2 [P, 4, d]
#     gqa.{wq, wgate} [P, d, H hd]; gqa.{wk, wv} [P, d, Hkv hd];
#     gqa.wo [P, H hd, d]
#     kda[j].{wq, wk, wv} [P, d, H dk]; kda[j].conv [P, taps, 3 H dk];
#     kda[j].{wf1, wg1} [P, d, r]; kda[j].{wf2, wg2} [P, r, H dk];
#     kda[j].a_log [P, H]; kda[j].dt_bias [P, H dk]; kda[j].wb [P, d, H];
#     kda[j].onorm [P, dk]; kda[j].wo [P, H dk, d]
#     moe[j].router [P, d, E]; moe[j].select_bias [P, E];
#     moe[j].{w_gate, w_up} [P, held, d, f]; moe[j].w_down [P, held, f, d];
#     moe[j].shared_{gate, up} [P, d, fs]; moe[j].shared_down [P, fs, d]
#
# It is given the same share as the chip: the router's full width, the
# held experts' part of the routed sum, the sliced vocabulary.
# ---------------------------------------------------------------------------
def _rms_norm(x, scale, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _gated_ffn(y, w_gate, w_up, w_down):
    import jax

    return (jax.nn.silu(y @ w_gate) * (y @ w_up)) @ w_down


def _ref_gqa(y, lp, w):
    """Causal softmax attention, query head i over key head i // group,
    no rotation, gated output. y [S, d]."""
    import jax
    import jax.numpy as jnp

    s = y.shape[0]
    h, hkv, hd = w["n_heads"], w["n_kv_heads"], w["head_dim"]
    q = (y @ lp["wq"]).reshape(s, h, hd)
    k = jnp.repeat((y @ lp["wk"]).reshape(s, hkv, hd), h // hkv, axis=1)
    v = jnp.repeat((y @ lp["wv"]).reshape(s, hkv, hd), h // hkv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(hd))
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None], scores,
                       -jnp.inf)
    attn = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
    gate = jax.nn.sigmoid(y @ lp["wgate"])
    return (attn.reshape(s, h * hd) * gate) @ lp["wo"]


def _ref_kda(y, lp, w):
    """The gated delta rule, one token at a time. y [S, d]. Returns the
    layer's output and the state after the last token."""
    import jax
    import jax.numpy as jnp

    s = y.shape[0]
    h, dk, taps = w["kda_heads"], w["kda_head_dim"], w["conv_kernel"]

    def conv_silu(x, kernel):
        """Causal depthwise convolution: out_t = sum_j kernel[j] *
        x[t - (taps - 1) + j], zeros before position 0."""
        padded = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1])), x])
        return jax.nn.silu(sum(kernel[j] * padded[j:j + s]
                               for j in range(taps)))

    def l2norm(x):
        return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)

    width = h * dk
    q, k, v = (conv_silu(y @ lp[name],
                         lp["conv"][:, i * width:(i + 1) * width]
                         ).reshape(s, h, dk)
               for i, name in enumerate(("wq", "wk", "wv")))
    q, k = l2norm(q) / jnp.sqrt(jnp.float32(dk)), l2norm(k)
    g = -jnp.exp(lp["a_log"])[None, :, None] * jax.nn.softplus(
        (y @ lp["wf1"]) @ lp["wf2"] + lp["dt_bias"]).reshape(s, h, dk)
    beta = 2.0 * jax.nn.sigmoid(y @ lp["wb"])                  # [S, H]

    def token(state, xs):
        q, k, v, g, beta = xs                  # [H, dk] ..., beta [H]
        decayed = jnp.exp(g)[:, :, None] * state          # diag(a) S
        # (I - beta k k^T) diag(a) S + beta k v^T
        state = (decayed
                 - beta[:, None, None] * k[:, :, None]
                 * jnp.einsum("hk,hkv->hv", k, decayed)[:, None, :]
                 + beta[:, None, None] * k[:, :, None] * v[:, None, :])
        return state, jnp.einsum("hkv,hk->hv", state, q)

    state, o = jax.lax.scan(token, jnp.zeros((h, dk, dk)),
                            (q, k, v, g, beta))
    o = _rms_norm(o, lp["onorm"], w["norm_eps"]).reshape(s, width)
    gate = jax.nn.sigmoid((y @ lp["wg1"]) @ lp["wg2"])
    return (o * gate) @ lp["wo"], state


def _ref_experts(y, mp, w):
    """Shared expert plus the held experts' part of the routed sum."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    lo, hi = w["experts_held"]
    scores = jax.nn.sigmoid(y @ mp["router"].astype(f32))       # [S, E]
    ranked = jnp.argsort(-(scores + mp["select_bias"]), axis=-1)
    chosen = jnp.zeros(scores.shape, bool).at[
        jnp.arange(y.shape[0])[:, None], ranked[:, :w["top_k"]]].set(True)
    weights = jnp.where(chosen, scores, 0.0)
    weights = (weights / jnp.sum(weights, axis=-1, keepdims=True)
               * w["routed_scaling"])

    def one_expert(total, xs):
        w_gate, w_up, w_down, weight = xs
        out = _gated_ffn(y, w_gate.astype(f32), w_up.astype(f32),
                         w_down.astype(f32))
        return total + weight[:, None] * out, None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(y),
        (mp["w_gate"], mp["w_up"], mp["w_down"], weights[:, lo:hi].T))
    shared = _gated_ffn(y, mp["shared_gate"].astype(f32),
                        mp["shared_up"].astype(f32),
                        mp["shared_down"].astype(f32))
    return shared + routed


def logits_one_sequence(params, tokens, w: dict):
    """tokens [S] int32 -> logits [S, V] and the delta rule's state
    after the last token, [linear layers, H, dk, dv]; float32, one
    sequence."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    eps = w["norm_eps"]

    def layer_params(tree, *index):
        return jax.tree.map(lambda a: a[index].astype(f32), tree)

    x, states = params["embed"].astype(f32)[tokens], []
    for p in range(w["n_periods"]):
        for j in range(LAYERS_PER_PERIOD):
            y = _rms_norm(x, params["ln1"][p, j].astype(f32), eps)
            if j == 0:
                x = x + _ref_gqa(y, layer_params(params["gqa"], p), w)
            else:
                out, state = _ref_kda(
                    y, layer_params(params["kda"][j - 1], p), w)
                x = x + out
                states.append(state)
            y = _rms_norm(x, params["ln2"][p, j].astype(f32), eps)
            # The experts' stacks stay in their dtype until an expert
            # is used: 40 of them in float32 are 2.5 GB a layer.
            x = x + _ref_experts(
                y, jax.tree.map(lambda a: a[p], params["moe"][j]), w)
    x = _rms_norm(x, params["ln_f"].astype(f32), eps)
    return x @ params["head"].astype(f32), jnp.stack(states)


_REFERENCES: dict = {}


def reference(w: dict):
    """jitted (params, tokens [S] int32) -> (logits [S, V], state after
    the last token [linear layers, H, dk, dv]); one program a widths,
    whoever asks (`drive`'s own limits and the harness's comparison)."""
    import json

    import jax

    def run(params, tokens):
        with jax.default_matmul_precision("highest"):
            return logits_one_sequence(params, tokens, w)

    key = json.dumps(w, sort_keys=True)
    if key not in _REFERENCES:
        _REFERENCES[key] = jax.jit(run)
    return _REFERENCES[key]


def reference_logits(w: dict):
    """(params, tokens [S] int32) -> logits [S, V]."""
    both = reference(w)
    return lambda params, tokens: both(params, tokens)[0]
