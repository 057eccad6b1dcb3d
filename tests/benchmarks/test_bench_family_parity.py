"""The default family computes what the harness computed before there
were families: golden numbers taken from the tree of PR 30 (by
`benchmarks.harness.flops`, `manifest.model_widths`, `reference` and the
two tolerance constants there), at the published widths of both
configurations and, for the reference, at toy widths to the bit."""

import hashlib
import json
import os

import numpy as np
import pytest

from benchmarks.harness import flops, manifest

with open(os.path.join(os.path.dirname(__file__),
                       "parent_pr30_golden.json")) as f:
    GOLDEN = json.load(f)
FAMILY = manifest.load_family()
CELL_OF = {"olmo-1b": "olmo-1b.serve.decode-heavy",
           "smollm2-1.7b": "smollm2-1.7b.train.seq2k"}
# Batch 8 at 300 live tokens a row, 2,400 together; float32 held.
QUANTITIES = {
    "widths": lambda w, c: w,
    "param_counts": lambda w, c: {k: c["params"][k] for k in (
        "embedding", "layer_matmul", "matmul", "total")},
    "train_flops_per_token_2048":
        lambda w, c: c["train_flops_per_token"](2048),
    "decode_step_bytes_8x300": lambda w, c: c["decode_step_bytes"](8, 2400),
    "decode_step_flops_8x300": lambda w, c: c["decode_step_flops"](8, 2400),
    "kv_bytes_per_token": lambda w, c: c["kv_bytes_per_token"],
}


@pytest.mark.parametrize("quantity", sorted(QUANTITIES))
@pytest.mark.parametrize("config", sorted(CELL_OF))
def test_counts_of_the_default_family_equal_the_parents(config, quantity):
    cell = manifest.load_cell(CELL_OF[config])
    assert cell["family"] == manifest.DEFAULT_FAMILY == "dense"
    widths = cell["widths"]
    got = QUANTITIES[quantity](widths, FAMILY.counts(widths))
    assert got == GOLDEN[config][quantity]
    assert type(got) is type(GOLDEN[config][quantity])
    # The names the readers and tests imported before are the family's.
    assert flops.param_counts is FAMILY.param_counts
    assert flops.decode_step_bytes(widths, 2400) == \
        GOLDEN[config]["decode_step_bytes_8x300"]


def test_both_tolerances_are_the_parents():
    assert {"logit": FAMILY.LOGIT_TOLERANCE,
            "loss": FAMILY.LOSS_TOLERANCE} == GOLDEN["tolerances"]


def test_bytes_follow_what_the_replica_says_it_holds():
    """float32 today (the golden numbers); a replica that reported a
    bf16 pool, or bf16 weights, would be counted at 2 bytes a value."""
    w = manifest.load_cell(CELL_OF["olmo-1b"])["widths"]
    f32 = {"dtype": "float32", "bytes_per_value": 4}
    bf16 = {"dtype": "bfloat16", "bytes_per_value": 2}
    today = FAMILY.counts(w, {"weights": f32, "kv_pool": f32})
    assert today["decode_step_bytes"](8, 2400) == \
        FAMILY.counts(w)["decode_step_bytes"](8, 2400) == \
        GOLDEN["olmo-1b"]["decode_step_bytes_8x300"]
    assert today["params"]["active"] == today["params"]["held"] == \
        today["params"]["total"]
    weights = today["params"]["total"] * 4
    pool = FAMILY.counts(w, {"weights": f32, "kv_pool": bf16})
    assert pool["kv_bytes_per_token"] == 131_072
    assert pool["decode_step_bytes"](8, 2400) == weights + 2400 * 131_072
    both = FAMILY.counts(w, {"weights": bf16, "kv_pool": bf16})
    assert both["decode_step_bytes"](8, 2400) * 2 == \
        today["decode_step_bytes"](8, 2400)
    assert both["held"]["weights"]["dtype"] == "bfloat16"


def test_the_reference_at_toy_widths_equals_the_parents_to_the_bit():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig, init_params

    widths = FAMILY.toy_widths(manifest.load_cell(
        CELL_OF["olmo-1b"])["widths"])
    cfg = TransformerConfig(**widths, max_seq_len=128, dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(3), cfg)
    params["layers"]["ln1"] = params["layers"]["ln1"] * 1.3
    params["ln_f"] = params["ln_f"] * 0.7
    tokens = jax.random.randint(jax.random.PRNGKey(1), (48,), 2, 512)
    logits = np.asarray(FAMILY.reference_logits(widths)(params, tokens))
    rows = jax.random.randint(jax.random.PRNGKey(2), (3, 33), 0, 512)
    loss = FAMILY.reference_loss(widths)(params, rows)
    assert {"logits_sha256": hashlib.sha256(logits.tobytes()).hexdigest(),
            "logits_sum": float(logits.astype(np.float64).sum()),
            "logit_3_5": float(logits[3, 5]).hex(),
            "loss": float(loss).hex()} == GOLDEN["toy"]
