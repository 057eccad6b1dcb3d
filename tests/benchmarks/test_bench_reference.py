"""The default family's float32 reference against the program, at toy
widths on the CPU: `forward` and `lm_loss` of models/transformer.py, and
the engine's prefill followed by paged decode steps. On the chip the same
comparison runs at the published widths in every run's set-up. The toy
widths are the family's own; the reference's numbers are pinned to the
bit against the tree before the families (PR 30)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import manifest, reference, serve_cell
from ray_tpu.models.transformer import (TransformerConfig, forward,
                                        init_params, lm_loss)

FAMILY = manifest.load_family()
WIDTHS = FAMILY.toy_widths(
    manifest.load_cell("olmo-1b.serve.chat-steady")["widths"])
HEADS_OF_16 = dict(WIDTHS)                       # 4 heads of 16
HEADS_OF_8 = dict(WIDTHS, n_heads=8, rope_theta=130000.0)


@pytest.fixture(scope="module", params=[HEADS_OF_16, HEADS_OF_8],
                ids=["heads16", "heads8-theta130k"])
def model(request):
    cfg = TransformerConfig(**request.param, max_seq_len=128,
                            dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(3), cfg)
    # The norm scales are 1 at init: move them, or a reference that forgot
    # them would pass.
    params["layers"]["ln1"] = params["layers"]["ln1"] * 1.3
    params["ln_f"] = params["ln_f"] * 0.7
    return cfg, params, request.param


def test_reference_logits_match_forward(model):
    cfg, params, widths = model
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 48), 2,
                                cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        want, _ = forward(params, tokens, cfg)
    ref = FAMILY.reference_logits(widths)
    for row in range(2):
        got = ref(params, tokens[row])
        np.testing.assert_allclose(np.asarray(got), np.asarray(want[row]),
                                   atol=2e-4)


def test_reference_loss_matches_lm_loss(model):
    cfg, params, widths = model
    tokens = jax.random.randint(jax.random.PRNGKey(2), (3, 33), 0,
                                cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        want = float(lm_loss(params, {"tokens": tokens}, cfg))
    got = FAMILY.reference_loss(widths)(params, tokens)
    assert got == reference.lm_loss(params, tokens, n_heads=cfg.n_heads,
                                    rope_theta=cfg.rope_theta)
    assert got == pytest.approx(want, abs=1e-4)
    # Not the loss of a model that ignores its input: ln(V) is far off.
    assert abs(got - np.log(cfg.vocab_size)) > 1e-3


def _engine(cfg, params):
    """The engine as `build_serving` makes it, around weights whose norm
    scales the fixture has moved."""
    from ray_tpu.serve.engine import (EngineConfig, InferenceEngine,
                                      TransformerEngineModel)

    model = TransformerEngineModel(params, cfg, max_batch_size=2)
    model.eos_token = None
    engine = InferenceEngine(model, EngineConfig(
        paged_decode=True, max_batch_size=2, block_size=16, num_blocks=16))
    return engine, {"params": params, "model": model}


def test_engine_prefill_and_paged_decode_match_the_reference(model):
    cfg, params, widths = model
    engine, served = _engine(cfg, params)
    check = serve_cell.check_against_reference(
        FAMILY, engine, served, widths, prompt_lengths=[16, 21, 40],
        steps=3, seed=5)
    assert check["ok"] and len(check["errors"]) == 3 * 4
    assert check["max_error"] < 1e-4           # float32 against float32
    assert engine.cache.stats()["used_blocks"] == 0   # check freed its rows


def test_the_tolerance_catches_a_wrong_model(model):
    """A reference handed other rotary frequencies stands for any fault of
    that size (a wrong position, a stale KV row): far over the limit."""
    cfg, params, widths = model
    engine, served = _engine(cfg, params)
    check = serve_cell.check_against_reference(
        FAMILY, engine, served, widths, prompt_lengths=[40], steps=2, seed=5,
        reference_widths=dict(widths, rope_theta=cfg.rope_theta * 30))
    assert not check["ok"]
    assert check["max_error"] > 4 * FAMILY.LOGIT_TOLERANCE
