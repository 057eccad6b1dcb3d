"""The family of sparse decoders whose attention selects its keys
(`model_type` ``KeyeVL2``): every layer has `n_heads` query heads over
`n_kv_heads` key/value heads of `head_dim` values, an RMSNorm a head on q
and k, a rotary in sections over three position streams, and a learned
*indexer* (`index_heads` heads of `index_dim` values over ONE index key a
position) that picks the `index_topk` earlier positions a query attends
to; a sparse-expert layer in every layer (softmax scores over all
experts, the top k, weights normalised over the chosen, no shared expert,
no dense layer); pre-norm RMSNorm, an untied head, no bias. Served by
`KeyeEngineModel`; there is no training half.

A configuration of this family is one chip's share of a deployment in
which `share_chips` chips share each layer: attention, indexer, router
and norms whole on every chip (data parallel), ``num_experts`` of the
published experts held here (expert parallel; the router keeps its
published width), the vocabulary sliced. The reference is handed the
same share.

What a reader of `benchmarks/README.md` ("Adding an architecture") needs
to know of this family:

- A position keeps two kinds of row: its keys and values (``layers x 2 x
  n_kv_heads x head_dim`` values, `kv_bytes_per_token`) in the cache's
  one layer group, and its index key (``layers x index_dim`` values,
  `index_bytes_per_token`) in a pool that rides that group's blocks
  (`serve/engine/kv_cache.py`). `counts["held"]` names the dtype of both
  under `kv_pool` (one dtype, the harness reads the global group's);
  `pool_bytes_per_token` is their sum, what a block of 16 positions costs
  the chip.
- `decode_step_bytes(rows, live)` counts what a step MUST move: the
  weights (non-expert ones and the head once, the expected touched
  experts), every live position's index key, and the KV of the
  positions a row attends to, ``min(live, rows x index_topk)``: not of
  every live one, which a body that walks all pages fetches today. A
  roofline share over it reads such a body's extra bytes as time lost.
- `decode_attention_cost("selected", tokens)`: scores and values over
  `tokens` attended positions (summed over rows and layers' worth is the
  caller's: the cost is of ALL layers a position, as the other families'
  group costs are) and their KV's bytes; `index_scores_cost(tokens)`:
  the indexer's products over `tokens` scored positions and their index
  keys' bytes.
- `own_limits`: beside the logits' limits a drive is held to the share
  of the reference's selected positions that the engine selected too,
  at the drive's last step: in the first layer
  (`FIRST_LAYER_OVERLAP_LIMIT`: rounding alone) and the least over the
  layers (`SELECTION_OVERLAP_LIMIT`); `drive` prints each as a
  ``compared:`` line on standard error (the harness's `checks` take no
  family's own).
- `params`: `total` and `active` are the published model's, `held` what
  this chip holds.

Nothing at the top of this file imports JAX or the program.
"""

from __future__ import annotations

import sys

# The program's files this family drives, under the `ray_tpu` package the
# process would import. A checkout that lacks them (the parent of the PR
# that brought the family) cannot run its cells, and says so when the
# cell is resolved, before any cluster or chip is touched.
PROGRAM_FILES = ("models/keye_vl2.py", "serve/engine/keye_model.py")


# ---------------------------------------------------------------------------
# shapes
# ---------------------------------------------------------------------------
def widths(config: dict) -> dict:
    """Published keys -> `KeyeVL2Config` fields. A config this family's
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
                         f"it cannot serve a model whose attention selects "
                         f"its keys")
    problems = []
    for key, want in (("attention_bias", False),
                      ("tie_word_embeddings", False),
                      ("norm_topk_prob", True), ("hidden_act", "silu"),
                      ("decoder_sparse_step", 1), ("mlp_only_layers", []),
                      ("use_sliding_window", False),
                      ("sliding_window", None)):
        if config.get(key) != want:
            problems.append(f"{key}={config.get(key)!r} (runs {want!r})")
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    if heads % kv_heads:
        problems.append("query heads no multiple of num_key_value_heads")
    rope = config.get("rope_scaling") or {}
    sections = rope.get("mrope_section")
    if rope.get("rope_type", "default") != "default" or not sections \
            or 2 * sum(sections) != config["head_dim"]:
        problems.append("a rotary other than sections that share out a "
                        "head's pairs")
    sa = config.get("sa_config") or {}
    if sa.get("indexer_num_kv_heads") != 1:
        problems.append("an indexer with other than one key head")
    held = config.get("experts_held")
    if not held or held[1] - held[0] != config["num_experts"]:
        problems.append("experts_held does not name num_experts experts")
    if problems:
        raise ValueError("the keye_vl2 block cannot run this config: "
                         + ", ".join(problems))
    published = config.get("published", {})
    return {
        "vocab_size": config["vocab_size"],
        "d_model": config["hidden_size"],
        "n_layers": config["num_hidden_layers"],
        "n_heads": heads,
        "n_kv_heads": kv_heads,
        "head_dim": config["head_dim"],
        "rope_theta": float(config["rope_theta"]),
        "mrope_section": list(sections),
        "index_heads": sa["indexer_num_heads"],
        "index_dim": sa["indexer_head_dim"],
        "index_topk": sa["topk"],
        "n_experts": published.get("num_experts", config["num_experts"]),
        "experts_held": list(held),
        "top_k": config["num_experts_per_tok"],
        "expert_width": config["moe_intermediate_size"],
        "norm_eps": config["rms_norm_eps"],
        "dtype": config["arithmetic"]["weights"],
        # The published model, for `counts`: its depth and vocabulary.
        "published": {
            "n_layers": published.get("num_hidden_layers",
                                      config["num_hidden_layers"]),
            "vocab_size": published.get("vocab_size",
                                        config["vocab_size"])},
    }


def toy_widths(w: dict) -> dict:
    """The same block at a size the CPU tests hold, every mechanism kept:
    8 query heads over 2 key/value heads of 16 (four query heads a key
    head), 8 pairs in sections of 2, 3 and 3, an indexer of 4 heads of 8
    that keeps 8 positions (prompts of 16-48 stand two to six times as
    deep), three layers, 8 experts of which 2 are held, top 2, float32
    throughout (the CPU tests compare exactly; the chip's arithmetic is
    checked on the chip); a prompt past 16 positions goes in chunks of 16
    (`prefill_chunk_tokens`, which `build_serving` sets on the model
    where the widths name it)."""
    return dict(
        w, vocab_size=512, d_model=64, n_layers=3, n_heads=8, n_kv_heads=2,
        head_dim=16, mrope_section=[2, 3, 3], index_heads=4, index_dim=8,
        index_topk=8, n_experts=8, experts_held=[0, 2], top_k=2,
        expert_width=32, dtype="float32", prefill_chunk_tokens=16,
        published={"n_layers": 3, "vocab_size": 512})


def model_config(w: dict):
    """`KeyeVL2Config` of the widths (in a process that may import the
    program)."""
    from ray_tpu.models.keye_vl2 import KeyeVL2Config

    fields = {k: v for k, v in w.items()
              if k not in ("published", "without", "prefill_chunk_tokens")}
    for key in ("experts_held", "mrope_section"):
        fields[key] = tuple(fields[key])
    return KeyeVL2Config(**fields)


# ---------------------------------------------------------------------------
# tolerances
# ---------------------------------------------------------------------------
# Engine logits against the float32 reference at one position: rms of the
# difference over rms of the reference's logits. The engine rounds the
# operands of a matrix product to bf16 (the weights and both pools are
# stored so) and accumulates in float32. As `families/mimo_v2.py` has
# them and for its reasons, the harness's limit holds every position and
# is set by what a swap does (the router's eighth and ninth expert close
# together: 0.02-0.07 at one position of a drive, on the chip), and the
# family's own limits (`DRIVE_LIMITS`, on the least and the median of a
# drive's 21 positions, and the two limits on the selection below) stand
# between the sound drives and the controls. The readings (PERF.md,
# Findings, PR 57; my chip runs, read with `keye_vl2_controls.py` beside
# this file), least / median:
#   under `index_topk` positions (48, 200), where the selection keeps
#   everything and a drive reads the rounding alone: 0.0026-0.0037 /
#   0.0029-0.0039;
#   past it (2,304; 8,448), sound, 19 seeds: 0.0026-0.0066 /
#   0.0027-0.0081; 0.0064-0.0085 / 0.0074-0.0093;
#   the engine with its keys and values at fp8's mantissa, 3 seeds:
#   0.0234-0.0248 / 0.0241-0.0274; 0.0245-0.0270 / 0.0252-0.0287;
#   the reference that attends to every causal key (8,448): 0.071-0.081
#   / 0.088-0.091; the one that keeps 1,024: 0.066-0.067 / 0.076-0.079;
#   the engine with its index keys at fp8's mantissa, 4 seeds (8,448):
#   0.0119-0.0135 / 0.0131-0.0142: inside these limits, it fails by the
#   first layer's selection, below.
LOGIT_TOLERANCE = 0.3

# (prompt length from which it holds, least, median). Past `index_topk`
# a drive's positions also carry the keys that fell on the other side of
# a threshold, and what they moved in the layers behind. Between the
# largest sound reading and the least of the KV pool at fp8: 1.6 times
# the one, 0.6 of the other.
DRIVE_LIMITS = ((0, 0.012, 0.012),
                (2048, 0.014, 0.015))

# Of the positions the reference selected for the drive's last query,
# the share the engine selected too, a layer. The FIRST layer's input is
# the embedding on both sides, so what differs there is the rounding of
# the indexer's operands alone. At 8,448 the sound engine keeps
# 0.9976-1.0 (19 seeds: at most 5 of 2,048 on the other side), index
# keys at fp8's mantissa 0.9824-0.9849 (4 seeds: 31-36), a reference
# that attends to every causal key 0.24: the limit stands 14 positions
# off, between 5 and 31. (At 2,304, where 257 positions are dropped, the
# fp8 keys read 0.9956-0.9985 and pass: the check's longest prompt tells
# them.) A later layer's input already differs by what the layers before
# it moved, and a selection feeds the next one: the sound engine reads
# 0.81-0.99 there (the least in one layer of twelve, which one by the
# seed) and both fp8 controls 0.96-0.97, so no precision is told by it;
# it is held from far below against a layer that keeps other keys
# altogether (every causal key: 0.24): a layer that lost two fifths is
# another model's.
FIRST_LAYER_OVERLAP_LIMIT = 0.993
SELECTION_OVERLAP_LIMIT = 0.6


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
    d, hd = w["d_model"], w["head_dim"]
    heads, kv_heads = w["n_heads"], w["n_kv_heads"]
    attention = 2 * d * heads * hd + 2 * d * kv_heads * hd
    norms = 2 * d + 2 * hd
    indexer = (d * w["index_heads"] * w["index_dim"] + d * w["index_dim"]
               + d * w["index_heads"] + 2 * w["index_dim"])
    router = d * w["n_experts"]
    expert = 3 * d * w["expert_width"]
    rest = attention + norms + indexer + router
    pub = w["published"]
    return {
        "attention": attention, "norms": norms, "indexer": indexer,
        "router": router, "expert": expert, "rest_a_layer": rest,
        "published_layer": rest + w["n_experts"] * expert,
        "held_layer": rest + _n_held(w) * expert,
        "rest_held": w["n_layers"] * rest,
        "experts_held": w["n_layers"] * _n_held(w) * expert,
        "head": w["vocab_size"] * d,
        "held": (w["n_layers"] * (rest + _n_held(w) * expert)
                 + 2 * w["vocab_size"] * d + d),
        "total": (pub["n_layers"] * (rest + w["n_experts"] * expert)
                  + 2 * pub["vocab_size"] * d + d),
        "active": (pub["n_layers"] * (rest + w["top_k"] * expert)
                   + 2 * pub["vocab_size"] * d + d),
    }


def kv_bytes_per_token(w: dict, kv_bytes: int) -> int:
    return w["n_layers"] * 2 * w["n_kv_heads"] * w["head_dim"] * kv_bytes


def index_bytes_per_token(w: dict, kv_bytes: int) -> int:
    return w["n_layers"] * w["index_dim"] * kv_bytes


def experts_touched(w: dict, rows: float) -> float:
    """Held experts of one layer with at least one of `rows` tokens, by
    expectation, when every token picks `top_k` of the router's experts
    uniformly."""
    return _n_held(w) * (1.0 - (1.0 - w["top_k"] / w["n_experts"]) ** rows)


def selected_tokens(w: dict, rows: float, live_kv_tokens: float) -> float:
    """Positions a step's rows attend to: a row's live ones or
    `index_topk`, whichever is less (by the rows' mean length: exact
    where every row is past `index_topk`)."""
    return min(live_kv_tokens, rows * w["index_topk"])


def decode_attention_cost(w: dict, group: str, tokens: float,
                          kv_bytes: int) -> dict:
    """Scores and values of every layer over `tokens` attended positions
    (summed over rows), and those positions' KV bytes. One group,
    ``"selected"``: the positions a query attends to."""
    if group != "selected":
        raise ValueError(f"this family's attention has the group "
                         f"'selected', not {group!r}")
    return {"flops": 4.0 * w["n_heads"] * w["head_dim"] * w["n_layers"]
            * tokens,
            "bytes": tokens * kv_bytes_per_token(w, kv_bytes)}


def index_scores_cost(w: dict, tokens: float, kv_bytes: int) -> dict:
    """The indexer's products of every layer over `tokens` scored
    positions (summed over rows), and those positions' index keys."""
    return {"flops": 2.0 * w["index_heads"] * w["index_dim"] * w["n_layers"]
            * tokens,
            "bytes": tokens * index_bytes_per_token(w, kv_bytes)}


def decode_step_bytes(w: dict, rows: float, live_kv_tokens: float,
                      weight_bytes: int, kv_bytes: int) -> float:
    """What one decode step of `rows` rows must move at the least
    (module docstring)."""
    p = param_counts(w)
    return ((p["rest_held"] + p["head"]) * weight_bytes
            + w["n_layers"] * experts_touched(w, rows) * p["expert"]
            * weight_bytes
            + live_kv_tokens * index_bytes_per_token(w, kv_bytes)
            + selected_tokens(w, rows, live_kv_tokens)
            * kv_bytes_per_token(w, kv_bytes))


def decode_step_flops(w: dict, rows: float, live_kv_tokens: float) -> float:
    """2 a matmul parameter a row (a row's expert pairs that fall on held
    experts by expectation), the indexer over every live position and
    the attention over the selected ones."""
    p = param_counts(w)
    pairs_here = w["top_k"] * _n_held(w) / w["n_experts"]
    return (2.0 * rows * (p["rest_held"] + p["head"]
                          + w["n_layers"] * pairs_here * p["expert"])
            + index_scores_cost(w, live_kv_tokens, 0)["flops"]
            + decode_attention_cost(
                w, "selected", selected_tokens(w, rows, live_kv_tokens),
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
    return {
        "params": param_counts(w),
        "held": held,
        "moe": {"layers": w["n_layers"], "experts_held": _n_held(w)},
        "experts_touched": lambda rows: experts_touched(w, rows),
        "decode_step_flops":
            lambda batch, live_tokens: decode_step_flops(w, batch,
                                                         live_tokens),
        "decode_step_bytes":
            lambda batch, live_tokens: decode_step_bytes(
                w, batch, live_tokens, weight_bytes, kv_bytes),
        "kv_bytes_per_token": kv_bytes_per_token(w, kv_bytes),
        "index_bytes_per_token": index_bytes_per_token(w, kv_bytes),
        "pool_bytes_per_token": (kv_bytes_per_token(w, kv_bytes)
                                 + index_bytes_per_token(w, kv_bytes)),
        "index_topk": w["index_topk"],
        "decode_attention_cost":
            lambda group, tokens: decode_attention_cost(w, group, tokens,
                                                        kv_bytes),
        "index_scores_cost":
            lambda tokens: index_scores_cost(w, tokens, kv_bytes),
        "state_bytes_per_sequence": 0,
    }


# ---------------------------------------------------------------------------
# serving, in the replica that holds the chip
# ---------------------------------------------------------------------------
def build_serving(w: dict, settings: dict, seed: int) -> dict:
    import jax

    from ray_tpu.models.keye_vl2 import init_params
    from ray_tpu.serve.engine import EngineConfig, KeyeEngineModel

    cfg = model_config(w)
    params = jax.jit(lambda: init_params(
        jax.random.PRNGKey(seed % (2 ** 31 - 1)), cfg))()
    engine = dict(settings["engine"])
    model = KeyeEngineModel(params, cfg,
                            max_batch_size=engine["max_batch_size"])
    model.eos_token = None     # random weights: no token means "end"
    if "prefill_chunk_tokens" in w:
        model.prefill_chunk_tokens = w["prefill_chunk_tokens"]
    return {"params": params, "model": model, "widths": w,
            "engine_config": EngineConfig(**engine)}


def warm_bucket(engine, served: dict, batch: int, table_blocks: int) -> None:
    """A step of `batch` rows that belong to no sequence (no write slot)
    over block 0: compiles and runs the bucket, and leaves both pools as
    they were."""
    block = engine.config.block_size
    model = served["model"]
    position = table_blocks * block - 1
    tables = {"global": (0, [0] * table_blocks)}
    engine.cache.paged_step(
        [], lambda pools, blocks, offs: model.decode_paged(
            pools, [tables] * batch, [2] * batch, [position] * batch,
            blocks, offs, block))


def prefill_as_the_scheduler(engine, model, tokens: list, sid: str):
    """The prompt into the cache under `sid` as the scheduler puts it
    there: whole where it is at most a chunk long, else a chunk at a
    time (tables read, the model's chunk over the pools, the cache grown
    by the chunk, its rows written). Returns the logits that predict the
    next token."""
    cache, block = engine.cache, engine.config.block_size
    n, chunk = len(tokens), model.prefill_chunk_tokens
    if n <= chunk:
        cache.allocate(sid, n, writable_from=0)
        logits, kv = model.prefill(tokens)
        cache.write_range(sid, 0, kv)
        return logits
    for start in range(0, n, chunk):
        tables = cache.step_tables(sid)
        logits, kv = cache.with_pools(
            lambda pools: model.prefill_chunk(tokens, pools, tables, start,
                                              block))
        cache.allocate(sid, min(n, start + chunk), writable_from=start)
        cache.write_range(sid, start, kv)
    return logits


def drive(engine, served: dict, tokens: list, steps: int, sid: str):
    """Prefill of `tokens` (a prompt longer than a chunk through the
    chunks, as the scheduler does), then `steps` greedy decode steps
    through the engine's cache (both pools) as the scheduler makes them,
    on a sequence of its own while the engine is idle. Before the last
    step the model is asked what that step's layers keep
    (`probe_selection`), for `own_limits`. Returns the logits rows and
    the tokens with the greedy ones appended. A drive that breaks one of
    the family's own limits while every row is inside the harness's
    `LOGIT_TOLERANCE` hands its rows back as NaN: the harness counts a
    row that is no number as not correct, the one way a family has to
    fail a run by a limit the harness does not know."""
    import numpy as np

    cache, model = engine.cache, served["model"]
    block = engine.config.block_size
    tokens, n = list(tokens), len(tokens)
    got = [np.asarray(prefill_as_the_scheduler(engine, model, tokens, sid))]
    kept = None
    for step in range(steps):
        tok = int(np.argmax(got[-1]))
        tokens.append(tok)
        pos = len(tokens) - 1
        cache.allocate(sid, len(tokens), writable_from=pos)
        tables = cache.step_tables(sid)
        if step == steps - 1:
            kept = cache.with_pools(lambda pools: model.probe_selection(
                pools, [tables], [tok], [pos], block))[:, 0]
        logits = cache.paged_step(
            [(sid, pos)],
            lambda pools, blocks, offs: model.decode_paged(
                pools, [tables], [tok], [pos], blocks, offs, block))
        got.append(np.asarray(logits)[0])
    cache.free(sid)
    readings = own_limits(served, got, tokens, n, kept)
    served.setdefault("own_limits", []).append(readings)
    for name, value, limit in (
            ("drive_least", readings["positions"][0], readings["limits"][0]),
            ("drive_median", readings["median"], readings["limits"][1]),
            ("first_layer_overlap", readings["first_layer_overlap"],
             FIRST_LAYER_OVERLAP_LIMIT),
            ("selection_overlap", readings["selection_overlap"],
             SELECTION_OVERLAP_LIMIT)):
        print(f"compared: {name}_at_{n}={value} limit={limit}",
              file=sys.stderr, flush=True)
    if not readings["ok"] and readings["positions"][-1] <= LOGIT_TOLERANCE:
        got = [np.full_like(row, np.nan) for row in got]
    return got, tokens


def own_limits(served: dict, got: list, tokens: list, n: int,
               kept=None) -> dict:
    """The family's own limits over one drive (the tolerances above):
    the reference's logits on the drive's tokens against the rows the
    engine gave, sorted, and the share of the reference's selection for
    the last query that the engine's (`kept`, ``[layers, >= positions +
    1]`` bool with the query's own position last; None: not held) shares.
    `served["reference_widths"]`, where a test or a control on the chip
    sets it, hands the reference other widths than the engine runs."""
    import numpy as np

    def gap(x, expect):
        return float(np.sqrt(np.mean((x - expect) ** 2)
                             / np.mean(expect * expect)))

    w = served.get("reference_widths") or served["widths"]
    want, selected = reference_with_selection(w)(
        served["params"], np.asarray(tokens, np.int32))
    want, selected = np.asarray(want), np.asarray(selected)
    positions = sorted(gap(row, want[n - 1 + j])
                       for j, row in enumerate(got))
    median = positions[len(positions) // 2]
    least_limit, median_limit = drive_limits(n)
    overlap, by_layer = 1.0, []
    if kept is not None:
        last = len(tokens) - 1
        mine = np.concatenate([kept[:, :last], kept[:, -1:]], axis=1)
        by_layer = (np.sum(mine & selected, axis=1)
                    / np.sum(selected, axis=1)).tolist()
        overlap = float(min(by_layer))
    return {"positions": positions, "median": median,
            "selection_overlap_by_layer": by_layer,
            "limits": [least_limit, median_limit],
            "selection_overlap": overlap,
            "first_layer_overlap": by_layer[0] if by_layer else 1.0,
            "ok": bool(positions[0] <= least_limit
                       and median <= median_limit
                       and overlap >= SELECTION_OVERLAP_LIMIT
                       and (not by_layer
                            or by_layer[0] >= FIRST_LAYER_OVERLAP_LIMIT))}


TRACED_CALLS = {"prefill": "prefill", "decode_step": "decode_paged"}


def decode_step_rows_and_live(args: tuple, kwargs: dict):
    """Rows of one `decode_paged` call and the positions live in it (a
    row at position ``p`` has ``p + 1``, its own among them): `(pools,
    tables, lasts, positions, ...)`."""
    positions = args[3] if len(args) > 3 else kwargs["positions"]
    return len(positions), sum(int(p) + 1 for p in positions)


# ---------------------------------------------------------------------------
# the plain reference: float32, `default_matmul_precision("highest")`, a
# sequence at a time, a head at a time over that head's ``[S, S]`` score
# matrix, the indexer a head at a time over ``[S, S]`` too, the selection
# by a full sort of every query's scores, a dense loop over the held
# experts one expert at a time, no cache, no kernels, no batching. Written
# from the layer's equations (ISSUE 57, section 1; the configuration's
# `assumed` and `departures`), not from `serve/engine/keye_model.py` or
# `ray_tpu/ops/`; it shares only the layout of the parameter tree, because
# it is handed the same seeded weights (`models/keye_vl2.init_params`):
#
#     embed [V, d]; head [d, V]; ln_f [d]; layers: a list of
#       ln1, ln2 [d]
#       mixer.{wq [d, H hd], wk, wv [d, Hkv hd], wo [H hd, d],
#              q_norm, k_norm [hd]}
#       indexer.{wq [d, J di], wk [d, di], ww [d, J], k_scale, k_bias [di]}
#       mlp.{router [d, E], w_gate, w_up [held, d, f], w_down [held, f, d]}
#
# It is given the same share as the chip: the router's full width, the
# held experts' part of the routed sum, the sliced vocabulary. Widths may
# switch a mechanism off for a control (`without`: "selection": every
# causal key; "qk_norm"; `index_topk` may be halved): the tests and the
# chip's controls hand it such widths and the comparison has to fail.
# ---------------------------------------------------------------------------
def _rms_norm(x, scale, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _gated_ffn(y, w_gate, w_up, w_down):
    import jax

    return (jax.nn.silu(y @ w_gate) * (y @ w_up)) @ w_down


def _ref_rotate(x, streams, theta: float, sections):
    """x [S, H, D]: pair i (value i with value i + D / 2) turned by the
    angle ``p_s(i) * theta^(-2i/D)``, ``s(i)`` the stream `sections`
    gives pair i; streams [n, S]."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    columns = []
    stream = [s for s, n in enumerate(sections) for _ in range(n)]
    for i in range(half):
        angle = streams[stream[i]].astype(jnp.float32) * theta ** (
            -2.0 * i / x.shape[-1])
        columns.append(angle)
    angle = jnp.stack(columns, axis=-1)                       # [S, half]
    c, s = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], axis=-1)


def _ref_selection(y, ip, w, streams):
    """[S, S] bool: the positions each query attends to. Scores by the
    indexer a head at a time; the `index_topk` largest of a query's
    scores over s <= t by a full sort (all while t < index_topk)."""
    import jax
    import jax.numpy as jnp

    s = y.shape[0]
    at = jnp.arange(s)
    causal = at[:, None] >= at[None, :]
    if "selection" in w.get("without", ()) or s <= w["index_topk"]:
        return causal
    heads, di = w["index_heads"], w["index_dim"]
    qi = _ref_rotate((y @ ip["wq"]).reshape(s, heads, di), streams[:1],
                     w["rope_theta"], [di // 2])
    ki = y @ ip["wk"]
    ki = ki - jnp.mean(ki, axis=-1, keepdims=True)
    ki = ki * jax.lax.rsqrt(jnp.mean(ki * ki, axis=-1, keepdims=True)
                            + w["norm_eps"]) * ip["k_scale"] + ip["k_bias"]
    ki = _ref_rotate(ki[:, None], streams[:1], w["rope_theta"],
                     [di // 2])[:, 0]
    weight = (y @ ip["ww"]) * heads ** -0.5 * di ** -0.5     # [S, J]

    def one_head(total, xs):
        q, wj = xs
        return total + wj[:, None] * jax.nn.relu(q @ ki.T), None

    scores, _ = jax.lax.scan(one_head, jnp.zeros((s, s), jnp.float32),
                             (qi.transpose(1, 0, 2), weight.T))
    scores = jnp.where(causal, scores, -jnp.inf)
    kth = -jnp.sort(-scores, axis=-1)[:, w["index_topk"] - 1]
    # A query with fewer causal keys than `index_topk` has -inf there.
    return causal & (scores >= kth[:, None])


def _ref_mixer(y, layer, w, streams):
    """Softmax attention at 1/sqrt(hd) over the selected positions,
    query head i over key head i // group; y [S, d]. Returns the mixer's
    output and the selection [S, S]."""
    import jax
    import jax.numpy as jnp

    lp = layer["mixer"]
    s = y.shape[0]
    heads, kv_heads, hd = w["n_heads"], w["n_kv_heads"], w["head_dim"]
    q = (y @ lp["wq"]).reshape(s, heads, hd)
    k = (y @ lp["wk"]).reshape(s, kv_heads, hd)
    v = (y @ lp["wv"]).reshape(s, kv_heads, hd)
    if "qk_norm" not in w.get("without", ()):
        q = _rms_norm(q, lp["q_norm"], w["norm_eps"])
        k = _rms_norm(k, lp["k_norm"], w["norm_eps"])
    q = _ref_rotate(q, streams, w["rope_theta"], w["mrope_section"])
    k = _ref_rotate(k, streams, w["rope_theta"], w["mrope_section"])
    seen = _ref_selection(y, layer["indexer"], w, streams)

    def one_head(xs):
        qh, key_head = xs
        scores = qh @ k[:, key_head].T / jnp.sqrt(jnp.float32(hd))
        scores = jnp.where(seen, scores, -jnp.inf)
        p = jnp.exp(scores - jnp.max(scores, axis=-1, keepdims=True))
        return (p / jnp.sum(p, axis=-1, keepdims=True)) @ v[:, key_head]

    o = jax.lax.map(one_head, (q.transpose(1, 0, 2),
                               jnp.arange(heads) // (heads // kv_heads)))
    return o.transpose(1, 0, 2).reshape(s, heads * hd) @ lp["wo"], seen


def _ref_routing(y, mp, w):
    """Weights [S, E] of the routed sum: softmax scores over all experts,
    the top k kept, the kept scores over their sum."""
    import jax
    import jax.numpy as jnp

    scores = jax.nn.softmax(y @ mp["router"].astype(jnp.float32), axis=-1)
    order = jnp.argsort(-scores, axis=-1)
    chosen = jnp.zeros(scores.shape, bool).at[
        jnp.arange(y.shape[0])[:, None], order[:, :w["top_k"]]].set(True)
    weights = jnp.where(chosen, scores, 0.0)
    return weights / jnp.sum(weights, axis=-1, keepdims=True)


def routed_share(y, mp, weights, held):
    """The part of the routed sum that the experts `held` = [lo, hi)
    add, given their matrices `mp["w_*"]` ``[hi - lo, ...]``: one expert
    at a time, each over all tokens. (Public: the share test adds the
    eight shares up.)"""
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
    """The held experts' part of a layer's routed sum over the normed
    rows `y` [S, d]. There is no shared expert."""
    return routed_share(y, mp, _ref_routing(y, mp, w), w["experts_held"])


def logits_one_sequence(params, tokens, w: dict, streams=None):
    """tokens [S] int32 -> logits [S, V] and, a layer, the positions the
    LAST query attends to, [layers, S] bool; float32, one sequence.
    `streams` [3, S]: the position streams (None: text, all three the
    token's index)."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    eps = w["norm_eps"]
    if streams is None:
        streams = jnp.broadcast_to(jnp.arange(tokens.shape[0])[None],
                                   (3, tokens.shape[0]))

    def as_f32(tree):
        return jax.tree.map(lambda a: a.astype(f32), tree)

    x = params["embed"].astype(f32)[tokens]
    selected = []
    for layer in params["layers"]:
        y = _rms_norm(x, layer["ln1"].astype(f32), eps)
        out, seen = _ref_mixer(
            y, {"mixer": as_f32(layer["mixer"]),
                "indexer": as_f32(layer["indexer"])}, w, streams)
        x = x + out
        selected.append(seen[-1])
        y = _rms_norm(x, layer["ln2"].astype(f32), eps)
        # The experts' stacks stay in their dtype until an expert is used.
        x = x + sparse_layer_share(y, layer["mlp"], w)
    x = _rms_norm(x, params["ln_f"].astype(f32), eps)
    return x @ params["head"].astype(f32), jnp.stack(selected)


_REFERENCES: dict = {}


def reference_with_selection(w: dict):
    """jitted (params, tokens [S] int32) -> (logits [S, V], the last
    query's selection a layer [layers, S]); one program a widths."""
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
    """(params, tokens [S] int32) -> logits [S, V]: the harness's name."""
    both = reference_with_selection(w)
    return lambda params, tokens: both(params, tokens)[0]
