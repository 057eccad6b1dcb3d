"""The family of sparse decoders whose window and global attention layers
differ in their key/value heads (`model_type` ``mimo_v2``): every layer
has `n_heads` query heads with keys of `head_dim` values over values of
`v_head_dim`; a *global* (causal) layer has `kv_heads_global` key/value
heads, a *window* layer, which sees `window` positions, has
`kv_heads_window` and a learned sink logit a query head, one more column
of its softmax that carries no value; values are scaled; the first
`rot_dim` values of a head are rotated, at a base a layer kind; a dense
MLP in the layers the config names and, in every other, a sparse-expert
layer (sigmoid scores over all experts, the top k of score + selection
bias, weights normalised over the chosen, no shared expert); pre-norm
RMSNorm, an untied head, no bias, no gate. Served by `MimoEngineModel`;
there is no training half.

A configuration of this family is one chip's share of a deployment in
which `share_chips` chips share each layer: attention, router and the
dense MLP whole on every chip (data parallel), ``n_routed_experts`` of
the published experts held here (expert parallel; the router keeps its
published width), the vocabulary sliced. The reference is handed the
same share.

What a reader of `benchmarks/README.md` ("Adding an architecture") needs
to know of this family, beside what `families/laguna.py` says of a
family with layer groups (all of which holds here: the cache's `global`
and `window` groups, `engine.group_blocks`, `kv_group_bytes_per_token`,
`decode_attention_cost(group, tokens)`, `own_limits`, no prefix
adopted):

- Every count is of the model's values: a position's row in a group is
  ``layers x Hkv x (head_dim + v_head_dim)`` values (2,560 bytes a global
  layer and 5,120 a window layer in bfloat16). The program's pools hold a
  key in whole slots of the values' width (384 values a key/value head
  where the model has 320), so a roofline share over these counts reads
  the padding as time lost, never as work done; the reader
  `kv_pool_padding_pct` takes the pools' side from the model's counters.
- `params`: `total` and `active` are the published model's (every layer,
  all experts or `top_k` of them, the whole vocabulary), `held` what this
  chip holds.

Nothing at the top of this file imports JAX or the program.
"""

from __future__ import annotations

# The program's files this family drives, under the `ray_tpu` package the
# process would import. A checkout that lacks them (the parent of the PR
# that brought the family) cannot run its cells, and says so when the
# cell is resolved, before any cluster or chip is touched.
PROGRAM_FILES = ("models/mimo_v2.py", "serve/engine/mimo_model.py")


# ---------------------------------------------------------------------------
# shapes
# ---------------------------------------------------------------------------
def widths(config: dict) -> dict:
    """Published keys -> `MimoV2Config` fields. A config this family's
    block does not compute is refused, as is a program that has no such
    model."""
    import importlib.util
    import os

    package = importlib.util.find_spec("ray_tpu")   # found, not imported
    where = list(package.submodule_search_locations) if package else [""]
    missing = [f for f in PROGRAM_FILES
               if not os.path.isfile(os.path.join(where[0], f))]
    if missing:
        raise ValueError(f"this tree's ray_tpu lacks {', '.join(missing)}: "
                         f"it cannot serve a model of window and global "
                         f"layers on different key/value heads")
    layers = config["num_hidden_layers"]
    problems = []
    for key, want in (("attention_bias", False),
                      ("tie_word_embeddings", False),
                      ("norm_topk_prob", True), ("hidden_act", "silu"),
                      ("scoring_func", "sigmoid"),
                      ("topk_method", "noaux_tc"), ("n_group", 1),
                      ("topk_group", 1), ("n_shared_experts", None),
                      ("routed_scaling_factor", None),
                      ("add_full_attention_sink_bias", False),
                      ("add_swa_attention_sink_bias", True)):
        if config.get(key) != want:
            problems.append(f"{key}={config.get(key)!r} (runs {want!r})")
    pattern, sparse = config["hybrid_layer_pattern"], config["moe_layer_freq"]
    if len(pattern) != layers or len(sparse) != layers \
            or set(pattern) - {0, 1} or set(sparse) - {0, 1}:
        problems.append("hybrid_layer_pattern / moe_layer_freq that do not "
                        "name every layer 0 or 1")
    if 0 not in pattern or 1 not in pattern:
        problems.append("a depth without both layer kinds")
    heads = config["num_attention_heads"]
    for key, other in (("swa_num_attention_heads", "num_attention_heads"),
                       ("swa_head_dim", "head_dim"),
                       ("swa_v_head_dim", "v_head_dim"),
                       ("sliding_window_size", "sliding_window"),
                       ("attention_chunk_size", "sliding_window")):
        if config[key] != config[other]:
            problems.append(f"{key} other than {other}")
    for key in ("num_key_value_heads", "swa_num_key_value_heads"):
        if heads % config[key]:
            problems.append(f"query heads no multiple of {key}")
    if (config.get("rope_scaling") or {}).get("rope_type",
                                              "default") != "default":
        problems.append("a rotary scaling")
    held = config.get("experts_held")
    if not held or held[1] - held[0] != config["n_routed_experts"]:
        problems.append("experts_held does not name n_routed_experts "
                        "experts")
    if problems:
        raise ValueError("the mimo_v2 block cannot run this config: "
                         + ", ".join(problems))
    published = config.get("published", {})
    rot = int(config["head_dim"] * config["partial_rotary_factor"])
    return {
        "vocab_size": config["vocab_size"],
        "d_model": config["hidden_size"],
        "n_heads": heads,
        "head_dim": config["head_dim"],
        "v_head_dim": config["v_head_dim"],
        "kv_heads_global": config["num_key_value_heads"],
        "kv_heads_window": config["swa_num_key_value_heads"],
        "window": config["sliding_window"],
        "layer_is_window": [bool(kind) for kind in pattern],
        "layer_is_dense": [not s for s in sparse],
        "dense_width": config["intermediate_size"],
        "n_experts": published.get("n_routed_experts",
                                   config["n_routed_experts"]),
        "experts_held": list(held),
        "top_k": config["num_experts_per_tok"],
        "expert_width": config["moe_intermediate_size"],
        "rot_dim": rot - rot % 2,
        "theta_global": float(config["rope_theta"]),
        "theta_window": float(config["swa_rope_theta"]),
        "value_scale": float(config["attention_value_scale"]),
        "norm_eps": config["layernorm_epsilon"],
        "dtype": config["arithmetic"]["weights"],
        # The published model, for `counts`: its layers and vocabulary.
        "published": {
            "layer_is_window": [bool(kind) for kind in published.get(
                "hybrid_layer_pattern", pattern)],
            "layer_is_dense": [not s for s in published.get(
                "moe_layer_freq", sparse)],
            "vocab_size": published.get("vocab_size",
                                        config["vocab_size"])},
    }


def toy_widths(w: dict) -> dict:
    """The same block at a size the CPU tests hold, every mechanism kept:
    8 query heads, keys of 24 over values of 16 (3 : 2, a key one and a
    half slots of a pool row), 2 global key/value heads to 4 window
    ones, a window of 16 positions (one block: two blocks a sequence,
    which prompts of 16-48 cross), the sink, 8 of a head's 24 values
    rotated, the same seven layers (the dense first one, two global to
    five window), 8 experts of which 2 are held, top 2, float32
    throughout (the CPU tests compare exactly; the chip's arithmetic is
    checked on the chip)."""
    return dict(
        w, vocab_size=512, d_model=64, n_heads=8, head_dim=24,
        v_head_dim=16, kv_heads_global=2, kv_heads_window=4, window=16,
        dense_width=96, n_experts=8, experts_held=[0, 2], top_k=2,
        expert_width=32, rot_dim=8, dtype="float32",
        published={"layer_is_window": w["layer_is_window"],
                   "layer_is_dense": w["layer_is_dense"],
                   "vocab_size": 512})


def model_config(w: dict):
    """`MimoV2Config` of the widths (in a process that may import the
    program). The model has no longest context of its own: the cell's
    `max_seq_len` bounds the traffic alone."""
    from ray_tpu.models.mimo_v2 import MimoV2Config

    fields = {k: v for k, v in w.items() if k != "published"}
    for key in ("experts_held", "layer_is_window", "layer_is_dense"):
        fields[key] = tuple(fields[key])
    return MimoV2Config(**fields)


# ---------------------------------------------------------------------------
# tolerances
# ---------------------------------------------------------------------------
# Engine logits against the float32 reference at one position: rms of the
# difference over rms of the reference's logits. The engine rounds the
# operands of a matrix product to bf16 (the weights and both KV pools are
# stored so) and accumulates in float32. Three limits, as
# `families/laguna.py` has them and for its reasons, and a precision below
# the stated one, a softmax without its sink, values without their scale
# or a window a block short has to fail by one of them (the readings:
# PERF.md, Findings, PR 53; my chip runs, PR 53).
#
# `LOGIT_TOLERANCE`, the harness's, holds every position (the largest
# single logit to five times it). What sets it is the router, not the
# rounding: at most positions the gap is that of the rounding (0.003-0.02),
# and at a few the operands' noise swaps a token's eighth and ninth expert
# where their ranked scores are close: when one of the two is held here
# that position's logits move by the expert's weighted output (a weight
# of about 1 / 8 of a layer's routed sum), and are back at the next.
# One swap read 0.057-0.065 on the chip (the worst position of a drive,
# three seeds); the limit leaves room over three at one position. A row
# that attends through another row's table reads about 1, and values
# without their scale 0.14-0.36 at every position; what reads below it (a
# softmax without its sink, fp8 experts, a window a block short) fails
# the limits below.
LOGIT_TOLERANCE = 0.3

# The family's own, which `drive` holds and the harness does not know
# (`own_limits`): what a swap cannot reach, a lower precision or a missing
# mechanism does. A swap moves one position of a drive or a few; those
# move them all. A drive has two limits: on the least of its 21 positions
# (the last of the prompt and 20 decode steps) and on their median. A row
# is (prompt length from which it holds, least, median). The readings a
# drive length on the chip (PERF.md, Findings, PR 53; three seeds, least /
# median): sound at the most 0.0035 / 0.0041 at 48 tokens, 0.0017 / 0.0019
# at 200, 0.0015 / 0.0018 at 1,040, 0.0015 / 0.0017 at 4,352. The held
# experts' matrices at fp8's 3 mantissa bits read a median of at least
# 0.0046 / 0.0053 / 0.0044 / 0.0048 there (their least position is one
# whose token chose no held expert, and reads as a sound one: the median
# is the limit that sees them); a softmax without its sink 0.071 / 0.080
# at 48, 0.023 / 0.025 at 200, 0.0054 / 0.0057 at 1,040 and 0.0033 /
# 0.0034 at 4,352 (the window layers' share of the result falls as the
# global layers' keys grow); values without their scale 0.14-0.33
# everywhere; a window one block short 0.07 from 200 tokens on (at 48 the
# drive never leaves the window). So: at 48 tokens the limits stand
# between the sound drives and the sink's (fp8 passes there); from 128 on
# the median stands between the sound drives (1.8 times the largest) and
# fp8's (0.66-0.73 of the least), and the sink's lies above both. A
# control fails by three of its four drives; a drive that fails either
# limit fails the run.
DRIVE_LIMITS = ((0, 0.012, 0.012),
                (128, 0.0060, 0.0035),
                (1024, 0.0030, 0.0032))


def drive_limits(n: int) -> tuple:
    """(least, median) a drive of an `n`-token prompt is held to."""
    return [row[1:] for row in DRIVE_LIMITS if row[0] <= n][-1]


# No training half: nothing reads this. `test_bench_manifest` asks every
# family for the name.
LOSS_TOLERANCE = 0.01


# ---------------------------------------------------------------------------
# counts
# ---------------------------------------------------------------------------
def _n_held(w: dict) -> int:
    return w["experts_held"][1] - w["experts_held"][0]


def param_counts(w: dict) -> dict:
    d, dk, dv, heads = (w["d_model"], w["head_dim"], w["v_head_dim"],
                        w["n_heads"])

    def mixer(hkv):
        return d * heads * dk + d * hkv * (dk + dv) + heads * dv * d

    glob, wind = mixer(w["kv_heads_global"]), mixer(w["kv_heads_window"])
    expert = 3 * d * w["expert_width"]
    router = d * w["n_experts"] + w["n_experts"]      # and its bias
    dense = 3 * d * w["dense_width"]

    def rest(is_window, is_dense):
        """Everything of these layers but the routed experts (the window
        layers' sinks, `n_heads` values each, among it)."""
        n_window, n_dense = sum(is_window), sum(is_dense)
        return ((len(is_window) - n_window) * glob
                + n_window * (wind + heads) + n_dense * dense
                + (len(is_dense) - n_dense) * router
                + len(is_window) * 2 * d)

    pub = w["published"]
    sparse = len(w["layer_is_dense"]) - sum(w["layer_is_dense"])
    pub_sparse = len(pub["layer_is_dense"]) - sum(pub["layer_is_dense"])
    rest_held = rest(w["layer_is_window"], w["layer_is_dense"])
    rest_pub = rest(pub["layer_is_window"], pub["layer_is_dense"])
    head = w["vocab_size"] * d
    return {
        "global_layer": glob, "window_layer": wind, "expert": expert,
        "router": router, "dense_mlp": dense,
        "rest_held": rest_held,
        "experts_held": sparse * _n_held(w) * expert,
        "head": head,
        "held": rest_held + sparse * _n_held(w) * expert + 2 * head + d,
        "total": (rest_pub + pub_sparse * w["n_experts"] * expert
                  + 2 * pub["vocab_size"] * d + d),
        "active": (rest_pub + pub_sparse * w["top_k"] * expert
                   + 2 * pub["vocab_size"] * d + d),
    }


def group_layers(w: dict) -> dict:
    n_window = sum(w["layer_is_window"])
    return {"global": len(w["layer_is_window"]) - n_window,
            "window": n_window}


def group_kv_heads(w: dict) -> dict:
    return {"global": w["kv_heads_global"], "window": w["kv_heads_window"]}


def kv_group_bytes_per_token(w: dict, kv_bytes: int) -> dict:
    """K and V of one position in each layer group, as the model counts
    them: a key's `head_dim` values and a value's `v_head_dim` a
    key/value head."""
    row = (w["head_dim"] + w["v_head_dim"]) * kv_bytes
    heads = group_kv_heads(w)
    return {group: layers * heads[group] * row
            for group, layers in group_layers(w).items()}


def experts_touched(w: dict, rows: float) -> float:
    """Held experts of one layer with at least one of `rows` tokens, by
    expectation, when every token picks `top_k` of the router's experts
    uniformly."""
    return _n_held(w) * (1.0 - (1.0 - w["top_k"] / w["n_experts"]) ** rows)


def window_tokens(w: dict, rows: float, live_kv_tokens: float) -> float:
    """Cached positions the window layers of a step read: a row's
    length or the window, whichever is less (by the rows' mean length:
    exact where every row is past the window)."""
    if not rows:
        return 0.0
    return rows * min(live_kv_tokens / rows, w["window"])


def _sparse_layers(w: dict) -> int:
    return len(w["layer_is_dense"]) - sum(w["layer_is_dense"])


def decode_step_bytes(w: dict, rows: float, live_kv_tokens: float,
                      weight_bytes: int, kv_bytes: int) -> float:
    """What one decode step of `rows` rows must move at the least: the
    non-expert weights and the head once, the expected held experts it
    touches, `live_kv_tokens` in the global group's layers and the
    window's share of them in the window group's."""
    p = param_counts(w)
    by_group = kv_group_bytes_per_token(w, kv_bytes)
    return ((p["rest_held"] + p["head"]) * weight_bytes
            + _sparse_layers(w) * experts_touched(w, rows) * p["expert"]
            * weight_bytes
            + live_kv_tokens * by_group["global"]
            + window_tokens(w, rows, live_kv_tokens) * by_group["window"])


def decode_attention_cost(w: dict, group: str, tokens: float,
                          kv_bytes: int) -> dict:
    """Scores (over `head_dim`) and values (over `v_head_dim`) of one
    group's layers over `tokens` cached positions (summed over rows),
    and the bytes of those positions."""
    return {"flops": (2.0 * w["n_heads"] * (w["head_dim"] + w["v_head_dim"])
                      * group_layers(w)[group] * tokens),
            "bytes": tokens * kv_group_bytes_per_token(w, kv_bytes)[group]}


def decode_step_flops(w: dict, rows: float, live_kv_tokens: float) -> float:
    """2 a matmul parameter a row (a row's expert pairs that fall on held
    experts by expectation) and both groups' scores and values over the
    KV they read."""
    p = param_counts(w)
    pairs_here = w["top_k"] * _n_held(w) / w["n_experts"]
    return (2.0 * rows * (p["rest_held"] + p["head"]
                          + _sparse_layers(w) * pairs_here * p["expert"])
            + decode_attention_cost(w, "global", live_kv_tokens, 0)["flops"]
            + decode_attention_cost(
                w, "window", window_tokens(w, rows, live_kv_tokens),
                0)["flops"])


# What the tree holds where no replica has said otherwise (the
# configuration's `arithmetic`).
HELD_TODAY = {"weights": {"dtype": "bfloat16", "bytes_per_value": 2},
              "kv_pool": {"dtype": "bfloat16", "bytes_per_value": 2}}


def counts(w: dict, held: dict = None) -> dict:
    """What readers get as `ctx["counts"]` (module docstring)."""
    held = held or HELD_TODAY
    weight_bytes = held["weights"]["bytes_per_value"]
    kv_bytes = held["kv_pool"]["bytes_per_value"]
    by_group = kv_group_bytes_per_token(w, kv_bytes)
    return {
        "params": param_counts(w),
        "held": held,
        "moe": {"layers": _sparse_layers(w), "experts_held": _n_held(w)},
        "experts_touched": lambda rows: experts_touched(w, rows),
        "decode_step_flops":
            lambda batch, live_tokens: decode_step_flops(w, batch,
                                                         live_tokens),
        "decode_step_bytes":
            lambda batch, live_tokens: decode_step_bytes(
                w, batch, live_tokens, weight_bytes, kv_bytes),
        "kv_bytes_per_token": sum(by_group.values()),
        "kv_group_bytes_per_token": by_group,
        "window": w["window"],
        "decode_attention_cost":
            lambda group, tokens: decode_attention_cost(w, group, tokens,
                                                        kv_bytes),
        "state_bytes_per_sequence": 0,
    }


# ---------------------------------------------------------------------------
# serving, in the replica that holds the chip
# ---------------------------------------------------------------------------
def build_serving(w: dict, settings: dict, seed: int) -> dict:
    import jax

    from ray_tpu.models.mimo_v2 import init_params
    from ray_tpu.serve.engine import EngineConfig, MimoEngineModel

    cfg = model_config(w)
    params = jax.jit(lambda: init_params(
        jax.random.PRNGKey(seed % (2 ** 31 - 1)), cfg))()
    engine = dict(settings["engine"])
    model = MimoEngineModel(params, cfg,
                            max_batch_size=engine["max_batch_size"])
    model.eos_token = None     # random weights: no token means "end"
    return {"params": params, "model": model, "widths": w,
            "engine_config": EngineConfig(**engine)}


def warm_bucket(engine, served: dict, batch: int, table_blocks: int) -> None:
    """A step of `batch` rows that belong to no sequence (no write slot)
    over block 0 of both groups: compiles and runs the bucket, and leaves
    both pools as they were."""
    block = engine.config.block_size
    model = served["model"]
    position = table_blocks * block - 1
    near = model.window_table_blocks(block)
    start = max(0, position - served["widths"]["window"] + 1) // block
    tables = {"global": (0, [0] * table_blocks),
              "window": (start, [0] * min(near, table_blocks - start))}
    engine.cache.paged_step(
        [], lambda pools, blocks, offs: model.decode_paged(
            pools, [tables] * batch, [2] * batch, [position] * batch,
            blocks, offs, block))


def drive(engine, served: dict, tokens: list, steps: int, sid: str):
    """Prefill of `tokens`, then `steps` greedy decode steps through the
    engine's cache (both layer groups: tables grown, the window group's
    expired blocks released) as the scheduler makes them, on a sequence
    of its own while the engine is idle. Returns the logits rows and the
    tokens with the greedy ones appended. A drive that breaks one of the
    family's own limits (`own_limits`) while every row is inside the
    harness's `LOGIT_TOLERANCE` hands its rows back as NaN: the harness
    counts a row that is no number as not correct, the one way a family
    has to fail a run by a limit the harness does not know. (Rows outside
    the harness's limit fail by it, and keep their numbers.)"""
    import numpy as np

    cache, model = engine.cache, served["model"]
    block = engine.config.block_size
    tokens, n, got = list(tokens), len(tokens), []
    cache.allocate(sid, n, writable_from=0)
    logits, kv = model.prefill(tokens)
    cache.write_range(sid, 0, kv)
    got.append(np.asarray(logits))
    held = []
    for _ in range(steps):
        tok = int(np.argmax(got[-1]))
        tokens.append(tok)
        pos = len(tokens) - 1
        cache.release_expired(sid, len(tokens))
        cache.allocate(sid, len(tokens), writable_from=pos)
        tables = cache.step_tables(sid)
        held.append(len(tables["window"][1]))
        logits = cache.paged_step(
            [(sid, pos)],
            lambda pools, blocks, offs: model.decode_paged(
                pools, [tables], [tok], [pos], blocks, offs, block))
        got.append(np.asarray(logits)[0])
    cache.free(sid)
    readings = own_limits(served, got, tokens, n)
    readings["window_blocks_held_max"] = max(held, default=0)
    served.setdefault("own_limits", []).append(readings)
    if not readings["ok"] and readings["positions"][-1] <= LOGIT_TOLERANCE:
        got = [np.full_like(row, np.nan) for row in got]
    return got, tokens


def own_limits(served: dict, got: list, tokens: list, n: int) -> dict:
    """The family's own limits over one drive (the tolerances above):
    the reference's logits on the drive's tokens against the rows the
    engine gave, sorted. `served["reference_widths"]`, where a test or a
    control on the chip sets it, hands the reference other widths than
    the engine runs."""
    import numpy as np

    def gap(x, expect):
        return float(np.sqrt(np.mean((x - expect) ** 2)
                             / np.mean(expect * expect)))

    want = np.asarray(reference_logits(
        served.get("reference_widths") or served["widths"])(
        served["params"], np.asarray(tokens, np.int32)))
    positions = sorted(gap(row, want[n - 1 + j])
                       for j, row in enumerate(got))
    median = positions[len(positions) // 2]
    least_limit, median_limit = drive_limits(n)
    return {"positions": positions, "median": median,
            "limits": [least_limit, median_limit],
            "ok": bool(positions[0] <= least_limit
                       and median <= median_limit)}


TRACED_CALLS = {"prefill": "prefill", "decode_step": "decode_paged"}


def decode_step_rows_and_live(args: tuple, kwargs: dict):
    """Rows of one `decode_paged` call and the cached positions its
    global layers attend over: `(pools, tables, lasts, positions, ...)`."""
    positions = args[3] if len(args) > 3 else kwargs["positions"]
    return len(positions), sum(int(p) + 1 for p in positions)


# ---------------------------------------------------------------------------
# the plain reference: float32, `default_matmul_precision("highest")`, a
# sequence at a time, a head at a time over that head's ``[S, S]`` score
# matrix (76 MB at 4,372 positions: it fits beside the served weights), a
# dense loop over the held experts one expert at a time, no cache, no
# kernels, no batching. Written from the layers' equations (ISSUE 53; the
# configuration's `assumed` and `departures`), not from
# `serve/engine/mimo_model.py` or `ray_tpu/ops/`; it shares only the
# layout of the parameter tree, because it is handed the same seeded
# weights (`models/mimo_v2.init_params`):
#
#     embed [V, d]; head [d, V]; ln_f [d]; layers: a list of
#       ln1, ln2 [d]
#       mixer.{wq [d, H dk], wk [d, Hkv dk], wv [d, Hkv dv],
#              wo [H dv, d]} and, in a window layer, sink [H]
#       mlp of a dense layer: {gate, up [d, F], down [F, d]}; of every
#       other: {router [d, E], select_bias [E], w_gate, w_up
#               [held, d, f], w_down [held, f, d]}
#
# It is given the same share as the chip: the router's full width, the
# held experts' part of the routed sum, the sliced vocabulary. Widths may
# switch a mechanism off for a control (`without`: "sink", "value_scale",
# "select_bias"; `window` may be shortened): the tests and the chip's
# controls hand it such widths and the comparison has to fail.
# ---------------------------------------------------------------------------
def _rms_norm(x, scale, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _gated_ffn(y, w_gate, w_up, w_down):
    import jax

    return (jax.nn.silu(y @ w_gate) * (y @ w_up)) @ w_down


def _ref_rotate(x, rot: int, theta: float):
    """x [S, H, dk]: the first `rot` values of a head rotated by the
    angles ``p * theta^(-2i/rot)`` (value i with value i + rot / 2), the
    rest kept."""
    import jax.numpy as jnp

    half = rot // 2
    i = jnp.arange(half, dtype=jnp.float32)
    angle = (jnp.arange(x.shape[0], dtype=jnp.float32)[:, None]
             * theta ** (-2.0 * i / rot)[None, :])
    c, s = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate([a * c - b * s, b * c + a * s, rest], axis=-1)


def _ref_mixer(y, lp, w, is_window: bool):
    """Causal softmax attention at 1/sqrt(dk), query head i over key
    head i // group; a window layer over the keys j with i - j < window
    alone, its head's sink one more column of the softmax that carries
    no value. y [S, d]."""
    import jax.numpy as jnp

    without = w.get("without", ())
    s = y.shape[0]
    heads, dk, dv = w["n_heads"], w["head_dim"], w["v_head_dim"]
    hkv = w["kv_heads_window"] if is_window else w["kv_heads_global"]
    theta = w["theta_window"] if is_window else w["theta_global"]
    q = _ref_rotate((y @ lp["wq"]).reshape(s, heads, dk), w["rot_dim"],
                    theta)
    k = _ref_rotate((y @ lp["wk"]).reshape(s, hkv, dk), w["rot_dim"],
                    theta)
    v = (y @ lp["wv"]).reshape(s, hkv, dv)
    if "value_scale" not in without:
        v = w["value_scale"] * v
    at = jnp.arange(s)
    seen = at[:, None] >= at[None, :]
    if is_window:
        seen &= at[:, None] - at[None, :] < w["window"]
    sink = (lp["sink"] if is_window and "sink" not in without
            else jnp.full((heads,), -jnp.inf))

    def one_head(xs):
        qh, key_head, b = xs                     # [S, dk], scalar, scalar
        scores = qh @ k[:, key_head].T / jnp.sqrt(jnp.float32(dk))
        scores = jnp.where(seen, scores, -jnp.inf)
        m = jnp.maximum(jnp.max(scores, axis=-1, keepdims=True), b)
        p = jnp.exp(scores - m)
        total = jnp.sum(p, axis=-1, keepdims=True) + jnp.exp(b - m)
        return (p / total) @ v[:, key_head]

    import jax

    o = jax.lax.map(one_head, (q.transpose(1, 0, 2),
                               jnp.arange(heads) // (heads // hkv), sink))
    return o.transpose(1, 0, 2).reshape(s, heads * dv) @ lp["wo"]


def _ref_routing(y, mp, w):
    """Weights [S, E] of the routed sum: sigmoid scores over all experts,
    the top k of score + selection bias kept, the kept scores over their
    sum."""
    import jax
    import jax.numpy as jnp

    scores = jax.nn.sigmoid(y @ mp["router"].astype(jnp.float32))
    ranked = scores
    if "select_bias" not in w.get("without", ()):
        ranked = scores + mp["select_bias"].astype(jnp.float32)
    order = jnp.argsort(-ranked, axis=-1)
    chosen = jnp.zeros(scores.shape, bool).at[
        jnp.arange(y.shape[0])[:, None], order[:, :w["top_k"]]].set(True)
    weights = jnp.where(chosen, scores, 0.0)
    return weights / jnp.sum(weights, axis=-1, keepdims=True)


def routed_share(y, mp, weights, held):
    """The part of the routed sum that the experts `held` = [lo, hi)
    add, given their matrices `mp["w_*"]` ``[hi - lo, ...]``: one expert
    at a time, each over all tokens. (Public: the share test adds the
    sixteen shares up.)"""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    lo, hi = held

    def one_expert(total, xs):
        w_gate, w_up, w_down, weight = xs
        out = _gated_ffn(y, w_gate.astype(f32), w_up.astype(f32),
                         w_down.astype(f32))
        return total + weight[:, None] * out, None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(y),
        (mp["w_gate"], mp["w_up"], mp["w_down"], weights[:, lo:hi].T))
    return routed


def sparse_layer_share(y, mp, w):
    """The held experts' part of a sparse layer's routed sum over the
    normed rows `y` [S, d]. There is no shared expert."""
    return routed_share(y, mp, _ref_routing(y, mp, w), w["experts_held"])


def logits_one_sequence(params, tokens, w: dict):
    """tokens [S] int32 -> logits [S, V]; float32, one sequence."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    eps = w["norm_eps"]

    def as_f32(tree):
        return jax.tree.map(lambda a: a.astype(f32), tree)

    x = params["embed"].astype(f32)[tokens]
    for layer, is_window in zip(params["layers"], w["layer_is_window"]):
        y = _rms_norm(x, layer["ln1"].astype(f32), eps)
        x = x + _ref_mixer(y, as_f32(layer["mixer"]), w, is_window)
        y = _rms_norm(x, layer["ln2"].astype(f32), eps)
        mp = layer["mlp"]
        if "router" in mp:
            # The experts' stacks stay in their dtype until an expert is
            # used: 16 of them in float32 are 1.6 GB a layer.
            x = x + sparse_layer_share(y, mp, w)
        else:
            x = x + _gated_ffn(y, *(mp[k].astype(f32)
                                    for k in ("gate", "up", "down")))
    x = _rms_norm(x, params["ln_f"].astype(f32), eps)
    return x @ params["head"].astype(f32)


_REFERENCES: dict = {}


def reference_logits(w: dict):
    """jitted (params, tokens [S] int32) -> logits [S, V]; one program a
    widths, whoever asks (`drive`'s own limits and the harness's
    comparison)."""
    import json

    import jax

    def run(params, tokens):
        with jax.default_matmul_precision("highest"):
            return logits_one_sequence(params, tokens, w)

    key = json.dumps(w, sort_keys=True)
    if key not in _REFERENCES:
        _REFERENCES[key] = jax.jit(run)
    return _REFERENCES[key]
