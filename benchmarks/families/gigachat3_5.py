"""The family of latent-attention / Gated DeltaNet sparse decoders
(`model_type` `gigachat3_5`): one layer in four attends by multi-head
latent attention (MLA: a position keeps ONE compressed latent of
`kv_lora_rank` values and one rotary key of `qk_rope_head_dim` for all
heads; rotary pairs interleaved, YaRN), the others are Gated DeltaNet
layers (a recurrent state a value head, one scalar decay a head, a short
convolution, fewer key heads than value heads); a dense MLP in the
leading layers and a sparse-expert layer (sigmoid router over all
experts, a selection bias, top k, one ungated shared expert) in the
rest; norms before and after every sublayer with zero-centred gated
scales, a clamped SwiGLU, an untied head. Served by
`GigaChatEngineModel`; there is no training half.

A configuration of this family is one chip's share of a deployment in
which `share_chips` chips share each layer: attention, the delta-rule
layers, the shared expert and the router whole on every chip (data
parallel), ``n_routed_experts`` of the published experts held here
(expert parallel; the router keeps its published width), the vocabulary
sliced. The reference is handed the same share.

What a reader of `benchmarks/README.md` ("Adding an architecture") needs
to know of this family beside what `solar_open2.py` says of a family
with state:

- `counts` fills `kv_bytes_per_token` as the MODEL counts a position (the
  latent and the rotary key of every MLA layer: 1,152 B at the published
  widths in bf16) and `latent` with what the pool HOLDS for it (whole
  planes of 128 lanes: 1,280 B); `decode_step_bytes` and
  `decode_attention_cost("latent", tokens)` take the held bytes, which
  are what a step's walk moves.
- `drive` prefills as the scheduler does: a prompt of at most a chunk
  whole, a longer one a chunk at a time, each chunk handed the
  sequence's state slot and its payload's state written back; so the
  check's longer prompts go through `prefill_chunk` and its carried
  state, and through the absorbed decode over rows a chunk wrote.
- The reference uses the *expanded* attention and the *token-by-token*
  delta rule: neither the absorbed form nor the chunked form is checked
  against itself.
- The family's own limits (`own_limits`, held by `drive`): the least of
  a drive's positions, the delta rule's state after the last token, that
  the state holds float32's bits, and the latent rows the cache holds
  for the drive's positions against the reference's latents (what tells
  a latent pool at a lower precision from a sound one: one MLA layer in
  five moves the logits too little).

Nothing at the top of this file imports JAX or the program.
"""

from __future__ import annotations

import math

# ---------------------------------------------------------------------------
# shapes
# ---------------------------------------------------------------------------
# The delta rule's state is float32 and the convolutions' tails are in
# the weights' dtype (the configuration's `arithmetic`).
STATE_BYTES_PER_VALUE = 4
# A latent row is held in whole planes of this many lanes.
LANES = 128

# The program's files this family drives, under the `ray_tpu` package the
# process would import. A checkout that lacks them (the parent of the PR
# that brought the family) cannot run its cells, and says so when the
# cell is resolved, before any cluster or chip is touched.
PROGRAM_FILES = ("models/gigachat35.py", "serve/engine/gigachat_model.py",
                 "ops/latent_attention.py")

_MODEL_FIELDS = (
    "vocab_size", "d_model", "n_layers", "mla_layers", "n_dense_layers",
    "n_heads", "q_rank", "kv_rank", "nope_dim", "rope_dim", "v_dim",
    "gdn_heads", "gdn_key_heads", "gdn_head_dim", "dense_width",
    "n_experts", "experts_held", "top_k", "expert_width", "shared_width",
    "conv_kernel", "routed_scaling", "swiglu_limit", "rope_theta", "yarn",
    "norm_eps", "o_norm_eps", "dtype")


def widths(config: dict) -> dict:
    """Published keys -> `GigaChat35Config` fields (plus `gdn_chunk`). A
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
                         f"it cannot serve a latent-attention model")
    problems = []
    for key, want in (
            ("norm_type", "ZeroCenteredGatedNorm"),
            ("layernorm_type", "pre_post"), ("layernorm_gating_weight", 2),
            ("gated_attention", True), ("use_shared_expert_sigmoid", False),
            ("use_mla_scaling_factor", True), ("rope_interleave", True),
            ("linear_attention_type", "GigaChat35GatedDeltaNet"),
            ("linear_gating_type", "gated_rmsnorm_sigmoid_zero_centered"),
            ("linear_sigmoid_gate_scale", 2), ("hidden_act", "silu"),
            ("n_group", 1), ("topk_group", 1), ("norm_topk_prob", True),
            ("n_shared_experts", 1), ("attention_bias", False),
            ("tie_word_embeddings", False)):
        if config.get(key) != want:
            problems.append(f"{key}={config.get(key)!r} (runs {want!r})")
    layers = config["num_hidden_layers"]
    attends = list(config.get("full_attention_layers", ()))
    if not attends or any(not 0 <= i < layers for i in attends):
        problems.append("full_attention_layers names no layer of the depth")
    if config["linear_key_head_dim"] != config["linear_value_head_dim"]:
        problems.append("linear key and value heads of different sizes")
    if config["linear_num_value_heads"] % config["linear_num_key_heads"]:
        problems.append("linear value heads no multiple of key heads")
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        problems.append("latent attention with grouped heads")
    if config.get("rope_scaling", {}).get("type") != "yarn":
        problems.append("a rotary scaling other than yarn")
    held = config.get("experts_held")
    if not held or held[1] - held[0] != config["n_routed_experts"]:
        problems.append("experts_held does not name n_routed_experts "
                        "experts")
    if problems:
        raise ValueError("the gigachat3_5 block cannot run this config: "
                         + ", ".join(problems))
    published = config.get("published", {})
    rope = config["rope_scaling"]
    return {
        "vocab_size": config["vocab_size"],
        "d_model": config["hidden_size"],
        "n_layers": layers,
        "mla_layers": attends,
        "n_dense_layers": config["first_k_dense_replace"],
        "n_heads": config["num_attention_heads"],
        "q_rank": config["q_lora_rank"],
        "kv_rank": config["kv_lora_rank"],
        "nope_dim": config["qk_nope_head_dim"],
        "rope_dim": config["qk_rope_head_dim"],
        "v_dim": config["v_head_dim"],
        "gdn_heads": config["linear_num_value_heads"],
        "gdn_key_heads": config["linear_num_key_heads"],
        "gdn_head_dim": config["linear_key_head_dim"],
        "dense_width": config["intermediate_size"],
        "n_experts": published.get("n_routed_experts",
                                   config["n_routed_experts"]),
        "experts_held": list(held),
        "top_k": config["num_experts_per_tok"],
        "expert_width": config["moe_intermediate_size"],
        "shared_width": (config["n_shared_experts"]
                         * config["moe_intermediate_size"]),
        "conv_kernel": config["linear_conv_kernel_dim"],
        "routed_scaling": float(config["routed_scaling_factor"]),
        "swiglu_limit": float(config["swiglu_limit"]),
        "rope_theta": float(config["rope_theta"]),
        "yarn": {"factor": rope["factor"],
                 "original_max_position_embeddings":
                     rope["original_max_position_embeddings"],
                 "beta_fast": rope["beta_fast"],
                 "beta_slow": rope["beta_slow"],
                 "mscale_all_dim": rope["mscale_all_dim"]},
        "norm_eps": config["rms_norm_eps"],
        "o_norm_eps": config["linear_attn_o_norm_eps"],
        "dtype": config["arithmetic"]["weights"],
        "gdn_chunk": 64,
        # The published model, for `counts`: depth, kinds, vocabulary.
        "published": {
            "n_layers": published.get("num_hidden_layers", layers),
            "n_mla_layers": len(published.get("full_attention_layers",
                                              attends)),
            "n_dense_layers": published.get("first_k_dense_replace",
                                            config["first_k_dense_replace"]),
            "vocab_size": published.get("vocab_size",
                                        config["vocab_size"])},
    }


def toy_widths(w: dict) -> dict:
    """The same block at a size the CPU tests hold, every mechanism kept
    (the same five layers: GDN + dense MLP, MLA, three GDN, the last four
    over experts): 4 heads over a latent of 32 and a rotary key of 8, 4
    value heads over 2 key heads of 16, 16 experts of which 2 are held,
    top 4, float32 throughout (the CPU tests compare exactly; the chip's
    arithmetic is checked on the chip); a prompt past 16 positions goes
    in chunks of 16 (`prefill_chunk_tokens`, which `build_serving` sets
    on the model instance)."""
    return dict(w, vocab_size=512, d_model=64, n_heads=4, q_rank=32,
                kv_rank=32, nope_dim=16, rope_dim=8, v_dim=16, gdn_heads=4,
                gdn_key_heads=2, gdn_head_dim=16, dense_width=96,
                n_experts=16, experts_held=[0, 2], top_k=4, expert_width=32,
                shared_width=32, dtype="float32", gdn_chunk=8,
                prefill_chunk_tokens=16,
                published=dict(w["published"], vocab_size=512))


def model_config(w: dict):
    """`GigaChat35Config` of the widths (in a process that may import
    the program)."""
    from ray_tpu.models.gigachat35 import GigaChat35Config

    fields = {k: w[k] for k in _MODEL_FIELDS}
    fields["experts_held"] = tuple(fields["experts_held"])
    fields["mla_layers"] = tuple(fields["mla_layers"])
    return GigaChat35Config(**fields)


# ---------------------------------------------------------------------------
# tolerances
# ---------------------------------------------------------------------------
# Engine logits against the float32 reference at one position: rms of the
# difference over rms of the reference's logits. The engine rounds the
# operands of a matrix product to bf16 (the weights and the latent rows
# are stored so) and accumulates in float32. Three limits, and a
# precision below the stated one has to fail by one of them (the
# readings: PERF.md, Findings, PR 61).
#
# `LOGIT_TOLERANCE`, the harness's, holds every position (the largest
# single logit to five times it). As `solar_open2.py` and `laguna.py` say
# of their own: what sets it is the router, not the rounding. Most
# positions read 0.006-0.02; where the operands' noise swaps a token's
# eighth and ninth expert and one of the two is held here, that position
# moves by the expert's weighted output (the weights carry the published
# scaling of 2.5, and the norm behind the layer brings what is left back
# to unit size) and the next few move through the state: sound runs'
# worst position read 0.010-0.166 over 11 seeds of 84 positions (my chip
# runs, PR 61), the experts at fp8's mantissa 0.10-0.19 there, so no
# limit on the WORST position tells a precision apart; the family's own
# limits below do. This one stands at three times the largest sound
# reading, under what a wrong mechanism reads at every position (no
# decay 0.86-1.02, a row that decodes from another row's state 1 and
# more; a reference without the output gate reads 0.29-0.37 and fails by
# the least position below).
LOGIT_TOLERANCE = 0.5

# The family's own, which `drive` holds and the harness does not know
# (`own_limits`): what a swap cannot reach, a lower precision does. Each
# between its two readings (my chip runs, PR 61: 11 seeds sound; the
# controls of `gigachat3_5_controls.py` on 2 seeds at 48, 200 and 2,304
# tokens), with the more room on the sound side, since fresh seeds read
# higher.
# `POSITIONS_TOLERANCE`: the least of a drive's positions (the last of
# the prompt and the decode steps). A swap moves one position of a drive
# or some; a lower precision moves them all. Sound 0.0062-0.0123; the
# expert layers' inputs at fp8's mantissa 0.038-0.041 (the nearest
# precision below the stated one: not `correct`, by this limit).
POSITIONS_TOLERANCE = 0.028
# `LATENT_TOLERANCE`: the rows the latent pool holds for the drive's
# positions (the latent and the rotary key, as stored) against the
# reference's float32 latents on the same tokens, rms of the difference
# over rms, the worst MLA layer. Sound 0.0040-0.0042 (bf16's rounding
# and the layer before's); a pool at fp8's mantissa 0.0268-0.0269, whose
# drives' least position reads 0.008-0.024 and would pass the limit
# above: one MLA layer in five moves the logits too little, so this is
# the limit that sees a latent pool in 8 bits.
LATENT_TOLERANCE = 0.011
# `STATE_TOLERANCE`: the delta rule's state after the drive's last token
# against the reference's, rms of the difference over rms, the worst
# layer. Sound 0.014-0.059 (a swap among the last tokens shows here);
# the experts at fp8 0.078-0.104, which the least position already
# fails; a reference without the output gate 0.48, without the decay
# 0.99, a stale slot 1: it stands between the sound runs and a wrong
# state, not between two precisions. `STATE_BF16_SHARE`: the largest
# share of the state's values that bf16 may hold exactly (chance gives
# 2**-16, the chip reads 0.00003-0.00005; a state rounded to bf16 after
# every update gives 1.0, and its logits and its state read as a sound
# run's: this is the limit that sees it).
STATE_TOLERANCE = 0.15
STATE_BF16_SHARE = 0.01

# No training half: nothing reads this. `test_bench_manifest` asks every
# family for the name.
LOSS_TOLERANCE = 0.01


# ---------------------------------------------------------------------------
# counts
# ---------------------------------------------------------------------------
def row_planes(w: dict) -> int:
    """Planes of `LANES` lanes the pool holds a latent row in."""
    return -(-(w["kv_rank"] + w["rope_dim"]) // LANES)


def param_counts(w: dict) -> dict:
    d, h = w["d_model"], w["n_heads"]
    gw = w["gdn_heads"] * w["gdn_head_dim"]
    gkw = w["gdn_key_heads"] * w["gdn_head_dim"]
    conv_w = 2 * gkw + gw
    gdn = (d * conv_w + 2 * d * gw + 2 * d * w["gdn_heads"]
           + w["conv_kernel"] * conv_w + 2 * w["gdn_heads"]
           + w["gdn_head_dim"])
    mla = (d * w["q_rank"] + w["q_rank"]
           + w["q_rank"] * h * (w["nope_dim"] + w["rope_dim"])
           + d * (w["kv_rank"] + w["rope_dim"]) + w["kv_rank"]
           + w["kv_rank"] * h * (w["nope_dim"] + w["v_dim"])
           + 2 * d * h * w["v_dim"])
    dense = 3 * d * w["dense_width"]
    expert = 3 * d * w["expert_width"]
    shared = 3 * d * w["shared_width"]
    router = d * w["n_experts"] + w["n_experts"]
    norms = 4 * d

    def rest(n_layers, n_mla, n_dense):
        """Everything but the routed experts, embedding and head."""
        return ((n_layers - n_mla) * gdn + n_mla * mla + n_dense * dense
                + (n_layers - n_dense) * (shared + router)
                + n_layers * norms)

    n_held = w["experts_held"][1] - w["experts_held"][0]
    n_layers, n_mla = w["n_layers"], len(w["mla_layers"])
    n_dense = w["n_dense_layers"]
    pub = w["published"]
    head = w["vocab_size"] * d
    rest_held = rest(n_layers, n_mla, n_dense)
    rest_pub = rest(pub["n_layers"], pub["n_mla_layers"],
                    pub["n_dense_layers"])
    pub_expert_layers = pub["n_layers"] - pub["n_dense_layers"]
    return {
        "gdn_layer": gdn, "mla_layer": mla, "dense_mlp": dense,
        "expert": expert, "shared_expert": shared, "router": router,
        "rest_held": rest_held,
        "experts_held": (n_layers - n_dense) * n_held * expert,
        "head": head,
        "held": (rest_held + (n_layers - n_dense) * n_held * expert
                 + 2 * head + d),
        "total": (rest_pub + pub_expert_layers * w["n_experts"] * expert
                  + 2 * pub["vocab_size"] * d + d),
        "active": (rest_pub + pub_expert_layers * w["top_k"] * expert
                   + 2 * pub["vocab_size"] * d + d),
    }


def state_bytes_per_sequence(w: dict, weight_bytes: int) -> int:
    """The delta rule's ``[H, dk, dv]`` float32 state and the
    convolution's ``[taps - 1, 2 Hk dk + H dk]`` tail, every GDN layer."""
    h, hk, dk = w["gdn_heads"], w["gdn_key_heads"], w["gdn_head_dim"]
    layers = w["n_layers"] - len(w["mla_layers"])
    return layers * (h * dk * dk * STATE_BYTES_PER_VALUE
                     + (w["conv_kernel"] - 1) * (2 * hk + h) * dk
                     * weight_bytes)


def kv_bytes_per_token(w: dict, kv_bytes: int) -> int:
    """A position as the model counts it: the latent and the one rotary
    key of every MLA layer."""
    return len(w["mla_layers"]) * (w["kv_rank"] + w["rope_dim"]) * kv_bytes


def held_bytes_per_token(w: dict, kv_bytes: int) -> int:
    """And as the pool holds it: whole planes."""
    return len(w["mla_layers"]) * row_planes(w) * LANES * kv_bytes


def experts_touched(w: dict, rows: float) -> float:
    """Held experts of one layer with at least one of `rows` tokens, by
    expectation, when every token picks `top_k` of the router's experts
    uniformly."""
    n_held = w["experts_held"][1] - w["experts_held"][0]
    return n_held * (1.0 - (1.0 - w["top_k"] / w["n_experts"]) ** rows)


def decode_attention_cost(w: dict, group: str, tokens: float,
                          kv_bytes: int) -> dict:
    """The absorbed attention of decode steps over `tokens` cached
    positions in all: every query head against the row as held, the
    probabilities against its latent, once a position for all heads."""
    if group != "latent":
        raise ValueError(f"this family has the latent group alone, not "
                         f"{group!r}")
    n_mla = len(w["mla_layers"])
    held = row_planes(w) * LANES
    return {"flops": 2.0 * w["n_heads"] * (held + w["kv_rank"]) * n_mla
            * tokens,
            "bytes": float(tokens * held_bytes_per_token(w, kv_bytes))}


def decode_step_bytes(w: dict, rows: float, live_kv_tokens: float,
                      weight_bytes: int, kv_bytes: int) -> float:
    """What one decode step of `rows` rows must move at the least: the
    non-expert weights and the head once, the expected held experts it
    touches, its rows' state read and written, and the live pages'
    latent rows as the pool holds them."""
    p = param_counts(w)
    expert_layers = w["n_layers"] - w["n_dense_layers"]
    return ((p["rest_held"] + p["head"]) * weight_bytes
            + expert_layers * experts_touched(w, rows) * p["expert"]
            * weight_bytes
            + 2 * rows * state_bytes_per_sequence(w, weight_bytes)
            + live_kv_tokens * held_bytes_per_token(w, kv_bytes))


def decode_step_flops(w: dict, rows: float, live_kv_tokens: float) -> float:
    """2 a matmul parameter a row (a row's expert pairs that fall on held
    experts by expectation), the absorbed attention over the live rows,
    and about 8 operations a state value a row."""
    p = param_counts(w)
    expert_layers = w["n_layers"] - w["n_dense_layers"]
    n_held = w["experts_held"][1] - w["experts_held"][0]
    pairs_here = w["top_k"] * n_held / w["n_experts"]
    gdn_layers = w["n_layers"] - len(w["mla_layers"])
    return (2.0 * rows * (p["rest_held"] + p["head"]
                          + expert_layers * pairs_here * p["expert"])
            + decode_attention_cost(w, "latent", live_kv_tokens, 2)["flops"]
            + 8.0 * rows * gdn_layers * w["gdn_heads"]
            * w["gdn_head_dim"] ** 2)


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
        "moe": {"layers": w["n_layers"] - w["n_dense_layers"],
                "experts_held": (w["experts_held"][1]
                                 - w["experts_held"][0])},
        "latent": {"layers": len(w["mla_layers"]),
                   "row_values": w["kv_rank"] + w["rope_dim"],
                   "row_values_held": row_planes(w) * LANES,
                   "bytes_per_token_held":
                       held_bytes_per_token(w, kv_bytes)},
        "experts_touched": lambda rows: experts_touched(w, rows),
        "decode_attention_cost":
            lambda group, tokens: decode_attention_cost(w, group, tokens,
                                                        kv_bytes),
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

    from ray_tpu.models.gigachat35 import init_params
    from ray_tpu.serve.engine import EngineConfig, GigaChatEngineModel

    cfg = model_config(w)
    params = jax.jit(lambda: init_params(
        jax.random.PRNGKey(seed % (2 ** 31 - 1)), cfg))()
    engine = dict(settings["engine"])
    model = GigaChatEngineModel(
        params, cfg, max_batch_size=engine["max_batch_size"],
        gdn_chunk=w["gdn_chunk"])
    model.eos_token = None     # random weights: no token means "end"
    if "prefill_chunk_tokens" in w:
        model.prefill_chunk_tokens = w["prefill_chunk_tokens"]
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


def prefill_as_the_scheduler(engine, model, tokens: list, sid: str):
    """The prompt into the cache under `sid` as the scheduler puts it
    there: whole where it is at most a chunk long, else a chunk at a
    time (table and slot read, the model's chunk over both pools, the
    cache grown by the chunk, its rows and the state it ended on
    written). Returns the logits that predict the next token."""
    cache, block = engine.cache, engine.config.block_size
    n, chunk = len(tokens), model.prefill_chunk_tokens
    if n <= chunk:
        cache.allocate(sid, n, writable_from=0)
        logits, kv = model.prefill(tokens)
        cache.write_range(sid, 0, kv)
        return logits
    for start in range(0, n, chunk):
        table, slot = cache.step_tables(sid), cache.slot_of(sid)
        logits, kv = cache.with_pools(
            lambda pools: model.prefill_chunk(tokens, pools, table, start,
                                              block, slot=slot))
        cache.allocate(sid, min(n, start + chunk), writable_from=start)
        cache.write_range(sid, start, kv)
    return logits


def drive(engine, served: dict, tokens: list, steps: int, sid: str):
    """Prefill of `tokens` (a prompt longer than a chunk through the
    chunks and their carried state, as the scheduler does), then `steps`
    greedy decode steps through the engine's cache (latent blocks and
    the sequence's state slot) as the scheduler makes them, on a sequence
    of its own while the engine is idle. Returns the logits rows and the
    tokens with the greedy ones appended. A drive that breaks one of the
    family's own limits (`own_limits`) while every row is inside the
    harness's `LOGIT_TOLERANCE` hands its rows back as NaN: the harness
    counts a row that is no number as not correct, the one way a family
    has to fail a run by a limit the harness does not know."""
    import numpy as np

    cache, model = engine.cache, served["model"]
    block = engine.config.block_size
    tokens, n = list(tokens), len(tokens)
    got = [np.asarray(prefill_as_the_scheduler(engine, model, tokens, sid))]
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
    state, rows = cache.read_state(sid)["s"], _rows_in_cache(
        cache, sid, len(tokens), served["widths"])
    cache.free(sid)
    readings = own_limits(served, got, tokens, n, state, rows)
    served.setdefault("own_limits", []).append(readings)
    for name, value, limit in (
            ("drive_least", readings["positions"][0], POSITIONS_TOLERANCE),
            ("state", readings["state"], STATE_TOLERANCE),
            ("latent_rows", readings["latent"], LATENT_TOLERANCE),
            ("state_bf16_share", readings["state_bf16_share"],
             STATE_BF16_SHARE)):
        print(f"compared: {name}_at_{n}={value} limit={limit}", flush=True)
    if not readings["ok"] and readings["positions"][-1] <= LOGIT_TOLERANCE:
        got = [np.full_like(row, np.nan) for row in got]
    return got, tokens


def _rows_in_cache(cache, sid: str, n: int, w: dict):
    """The first `n` positions' rows of `sid` as the latent pool holds
    them, ``[MLA layers, n, rank + rope]`` float32: the pages its table
    names (``[nb, L, P, bs, 128]``), a position a row, the padding
    lanes dropped. Read through `with_pools`, on the device, then one
    copy to the host."""
    import numpy as np

    table = np.asarray(cache.block_table(sid), np.int32)
    pages = np.asarray(cache.with_pools(
        lambda pools: pools[cache.GLOBAL][table])).astype(np.float32)
    nb, layers, planes, bs, lanes = pages.shape
    rows = pages.transpose(1, 0, 3, 2, 4).reshape(layers, nb * bs,
                                                  planes * lanes)
    return rows[:, :n, :w["kv_rank"] + w["rope_dim"]]


def own_limits(served: dict, got: list, tokens: list, n: int,
               state, rows) -> dict:
    """The family's own limits over one drive (the tolerances above):
    the reference's logits, state and latents on the drive's tokens
    against the logits rows, the state slot the engine ended on and the
    latent rows its cache holds."""
    import numpy as np

    def gap(x, expect):
        return float(np.sqrt(np.mean((x - expect) ** 2)
                             / np.mean(expect * expect)))

    # (`reference_widths`: a control hands the reference other widths.)
    want, want_state, want_rows = (np.asarray(x) for x in reference(
        served.get("reference_widths", served["widths"]))(
        served["params"], np.asarray(tokens, np.int32)))
    positions = sorted(gap(row, want[n - 1 + j])
                       for j, row in enumerate(got))
    state = np.ascontiguousarray(state, np.float32)
    readings = {
        "positions": positions,
        "state": max(gap(s, expect) for s, expect in
                     zip(state, want_state)),
        "latent": max(gap(r, expect) for r, expect in
                      zip(rows, want_rows)),
        # float32 values whose low 16 bits are clear: bf16 holds them.
        "state_bf16_share": float(np.mean(
            state.view(np.uint32) & 0xFFFF == 0))}
    readings["ok"] = bool(
        positions[0] <= POSITIONS_TOLERANCE
        and readings["state"] <= STATE_TOLERANCE
        and readings["latent"] <= LATENT_TOLERANCE
        and readings["state_bf16_share"] <= STATE_BF16_SHARE)
    return readings


TRACED_CALLS = {"prefill": "prefill_chunk", "decode_step": "decode_paged"}


def decode_step_rows_and_live(args: tuple, kwargs: dict):
    """Rows of one `decode_paged` call and the live tokens its latent
    layer attends over: `(pool, tables, lasts, positions, ...)`."""
    positions = args[3] if len(args) > 3 else kwargs["positions"]
    return len(positions), sum(int(p) + 1 for p in positions)


# ---------------------------------------------------------------------------
# the plain reference: float32, `default_matmul_precision("highest")`,
# the EXPANDED attention (keys and values a head multiplied out of the
# latents, a head at a time, the whole causal score matrix), the delta
# rule a TOKEN at a time, a dense loop over the held experts, no cache,
# no kernels, no batching. Written from the layers' equations (ISSUE 61;
# the configuration's `assumed` and `departures`), not from
# `serve/engine/gigachat_model.py` or `ray_tpu/ops/`; it shares only the
# layout of the parameter tree, because it is handed the same seeded
# weights (`models/gigachat35.init_params`):
#
#     embed [V, d]; head [d, V]; ln_f [d]; layers: a list, each
#       ln1, ln1_post, ln2, ln2_post [d]
#       mixer (MLA): wdq [d, rq]; q_norm [rq]; wuq [rq, H (nope + rope)];
#         wdkv [d, rank + rope]; kv_norm [rank]; wuk [rank, H nope];
#         wuv [rank, H dv]; wgate [d, H dv]; wo [H dv, d]
#       mixer (GDN): wqkv [d, 2 Hk dk + H dk]; conv [taps, the same];
#         wa, wb [d, H]; a_log, dt_bias [H]; wz [d, H dk]; onorm [dk];
#         wo [H dk, d]
#       mlp (dense): gate, up [d, f]; down [f, d]
#       mlp (experts): router [d, E]; select_bias [E]; w_gate, w_up
#         [held, d, fe]; w_down [held, fe, d]; shared_gate, shared_up
#         [d, fs]; shared_down [fs, d]
#
# It is given the same share as the chip: the router's full width, the
# held experts' part of the routed sum, the sliced vocabulary. Widths with
# `without` (a control's: "output_gate", "decay") leave a mechanism out.
# ---------------------------------------------------------------------------
def _zc_norm(x, w, eps):
    """``x / sqrt(mean(x^2) + eps) * 2 sigmoid(w)``."""
    import jax
    import jax.numpy as jnp

    return (x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * 2.0 * jax.nn.sigmoid(w))


def _clamped_ffn(y, w_gate, w_up, w_down, limit):
    """``W_2(silu(min(a, limit)) * clip(b, -limit, limit))``."""
    import jax
    import jax.numpy as jnp

    a = jnp.minimum(y @ w_gate, limit)
    b = jnp.clip(y @ w_up, -limit, limit)
    return (jax.nn.silu(a) * b) @ w_down


def _yarn_inv_freq(w: dict):
    """YaRN's inverse frequencies over the rotary key's pairs: a pair
    that turns more than `beta_fast` times over the original context
    keeps its frequency, one that turns less than `beta_slow` times has
    it divided by `factor`, a linear ramp between (bounds floored and
    ceiled)."""
    import jax.numpy as jnp

    dim, theta, yarn = w["rope_dim"], w["rope_theta"], w["yarn"]
    inv = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    original = yarn["original_max_position_embeddings"]

    def pair_of(turns):
        return (dim * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(pair_of(yarn["beta_fast"])), 0)
    high = min(math.ceil(pair_of(yarn["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return inv / yarn["factor"] * ramp + inv * (1.0 - ramp)


def _ref_rotate(x, inv_freq):
    """Rotary over x ``[S, ..., D]`` at positions 0..S-1, the pairs side
    by side: values ``2 i`` and ``2 i + 1`` turn by ``position *
    inv_freq[i]``."""
    import jax.numpy as jnp

    s = x.shape[0]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq
    ang = ang.reshape((s,) + (1,) * (x.ndim - 2) + (ang.shape[-1],))
    a, b = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                     a * jnp.sin(ang) + b * jnp.cos(ang)], axis=-1)
    return out.reshape(x.shape)


def _ref_mla(y, lp, w):
    """Latent attention, expanded: a head's keys and values multiplied
    out of the latents, causal softmax a head, gated output. y [S, d].
    Returns the layer's output and the positions' latents and rotary
    keys side by side, ``[S, rank + rope]`` (what a cache would keep)."""
    import jax
    import jax.numpy as jnp

    s = y.shape[0]
    h, nope, rope, dv = w["n_heads"], w["nope_dim"], w["rope_dim"], w["v_dim"]
    rank, eps = w["kv_rank"], w["norm_eps"]
    inv_freq = _yarn_inv_freq(w)
    c_q = _zc_norm(y @ lp["wdq"], lp["q_norm"], eps)
    q = (c_q @ lp["wuq"]).reshape(s, h, nope + rope)
    q_nope, q_r = q[..., :nope], _ref_rotate(q[..., nope:], inv_freq)
    down = y @ lp["wdkv"]
    c_kv = _zc_norm(down[:, :rank], lp["kv_norm"], eps)
    k_r = _ref_rotate(down[:, rank:], inv_freq)                 # [S, rope]
    m = 0.1 * w["yarn"]["mscale_all_dim"] * math.log(w["yarn"]["factor"]) + 1
    scale = (nope + rope) ** -0.5 * m * m
    causal = jnp.tril(jnp.ones((s, s), bool))

    def one_head(xs):
        q_nope, q_r, w_uk, w_uv = xs      # [S, nope], [S, rope], [rank, ..]
        k_nope, v = c_kv @ w_uk, c_kv @ w_uv
        scores = (q_nope @ k_nope.T + q_r @ k_r.T) * scale
        scores = jnp.where(causal, scores, -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ v              # [S, dv]

    o = jax.lax.map(one_head, (
        q_nope.transpose(1, 0, 2), q_r.transpose(1, 0, 2),
        lp["wuk"].reshape(rank, h, nope).transpose(1, 0, 2),
        lp["wuv"].reshape(rank, h, dv).transpose(1, 0, 2)))
    o = o.transpose(1, 0, 2).reshape(s, h * dv)
    if "output_gate" not in w.get("without", ()):
        o = o * jax.nn.sigmoid(y @ lp["wgate"])
    return o @ lp["wo"], jnp.concatenate([c_kv, k_r], axis=-1)


def _ref_gdn(y, lp, w):
    """Gated DeltaNet, one token at a time. y [S, d]. Returns the layer's
    output and the state after the last token."""
    import jax
    import jax.numpy as jnp

    s = y.shape[0]
    h, hk, dk = w["gdn_heads"], w["gdn_key_heads"], w["gdn_head_dim"]
    taps = w["conv_kernel"]
    pre = y @ lp["wqkv"]
    padded = jnp.concatenate([jnp.zeros((taps - 1, pre.shape[1])), pre])
    mixed = jax.nn.silu(sum(lp["conv"][j] * padded[j:j + s]
                            for j in range(taps)))

    def l2norm(x):
        return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)

    q = l2norm(mixed[:, :hk * dk].reshape(s, hk, dk)) / math.sqrt(dk)
    k = l2norm(mixed[:, hk * dk:2 * hk * dk].reshape(s, hk, dk))
    v = mixed[:, 2 * hk * dk:].reshape(s, h, dk)
    # Value head i reads key head i // (H / Hk).
    q, k = (jnp.repeat(x, h // hk, axis=1) for x in (q, k))
    g = -jnp.exp(lp["a_log"]) * jax.nn.softplus(y @ lp["wa"]
                                                + lp["dt_bias"])   # [S, H]
    if "decay" in w.get("without", ()):
        g = jnp.zeros_like(g)
    beta = jax.nn.sigmoid(y @ lp["wb"])                             # [S, H]

    def token(state, xs):
        q, k, v, g, beta = xs          # [H, dk] x 3, [H], [H]
        decayed = jnp.exp(g)[:, None, None] * state        # a S
        read = jnp.einsum("hk,hkv->hv", k, decayed)        # (a S)^T k
        state = decayed + (beta[:, None, None] * k[:, :, None]
                           * (v - read)[:, None, :])
        return state, jnp.einsum("hkv,hk->hv", state, q)

    state, o = jax.lax.scan(token, jnp.zeros((h, dk, dk)),
                            (q, k, v, g, beta))
    o = _zc_norm(o, lp["onorm"], w["o_norm_eps"]).reshape(s, h * dk)
    gate = 2.0 * jax.nn.sigmoid(y @ lp["wz"])
    return (o * gate) @ lp["wo"], state


def _ref_dense(y, mp, w):
    """The dense MLP, a block of its columns at a time (the same sum: a
    whole ``[S, f]`` in float32 beside the weights' float32 copies is
    what a long check's device cannot spare)."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    d, f = mp["gate"].shape
    block = next(b for b in (2048, 1024, f) if f % b == 0)

    def one_block(total, xs):
        gate, up, down = xs
        return total + _clamped_ffn(y, gate.astype(f32), up.astype(f32),
                                    down.astype(f32), w["swiglu_limit"]), None

    total, _ = jax.lax.scan(
        one_block, jnp.zeros_like(y),
        (mp["gate"].reshape(d, f // block, block).transpose(1, 0, 2),
         mp["up"].reshape(d, f // block, block).transpose(1, 0, 2),
         mp["down"].reshape(f // block, block, d)))
    return total


def _ref_experts(y, mp, w):
    """Shared expert (ungated) plus the held experts' part of the routed
    sum: sigmoid scores over all experts, the top k of score + bias, the
    chosen scores over their sum times the scaling."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    lo, hi = w["experts_held"]
    limit = w["swiglu_limit"]
    scores = jax.nn.sigmoid(y @ mp["router"].astype(f32))       # [S, E]
    ranked = jnp.argsort(-(scores + mp["select_bias"]), axis=-1)
    chosen = jnp.zeros(scores.shape, bool).at[
        jnp.arange(y.shape[0])[:, None], ranked[:, :w["top_k"]]].set(True)
    weights = jnp.where(chosen, scores, 0.0)
    weights = (weights / jnp.sum(weights, axis=-1, keepdims=True)
               * w["routed_scaling"])

    def one_expert(total, xs):
        w_gate, w_up, w_down, weight = xs
        out = _clamped_ffn(y, w_gate.astype(f32), w_up.astype(f32),
                           w_down.astype(f32), limit)
        return total + weight[:, None] * out, None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(y),
        (mp["w_gate"], mp["w_up"], mp["w_down"], weights[:, lo:hi].T))
    shared = _clamped_ffn(y, mp["shared_gate"].astype(f32),
                          mp["shared_up"].astype(f32),
                          mp["shared_down"].astype(f32), limit)
    return shared + routed


def logits_one_sequence(params, tokens, w: dict):
    """tokens [S] int32 -> logits [S, V], the delta rule's state after
    the last token, [GDN layers, H, dk, dv], and the positions' latents
    and rotary keys, [MLA layers, S, rank + rope]; float32, one
    sequence."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    eps = w["norm_eps"]
    x, states, latents = params["embed"].astype(f32)[tokens], [], []
    for i, lp in enumerate(params["layers"]):
        mixer = jax.tree.map(lambda a: a.astype(f32), lp["mixer"])
        y = _zc_norm(x, lp["ln1"], eps)
        if i in w["mla_layers"]:
            out, rows = _ref_mla(y, mixer, w)
            latents.append(rows)
        else:
            out, state = _ref_gdn(y, mixer, w)
            states.append(state)
        x = x + _zc_norm(out, lp["ln1_post"], eps)
        y = _zc_norm(x, lp["ln2"], eps)
        # The matrices stay in their dtype until a block of them is
        # used: 16 experts in float32 are 2.8 GB a layer.
        out = (_ref_experts(y, lp["mlp"], w) if "router" in lp["mlp"]
               else _ref_dense(y, lp["mlp"], w))
        x = x + _zc_norm(out, lp["ln2_post"], eps)
    x = _zc_norm(x, params["ln_f"], eps)
    return (x @ params["head"].astype(f32), jnp.stack(states),
            jnp.stack(latents))


_REFERENCES: dict = {}


def reference(w: dict):
    """jitted (params, tokens [S] int32) -> (logits [S, V], state after
    the last token [GDN layers, H, dk, dv], latents [MLA layers, S, rank
    + rope]); one program a widths,
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
