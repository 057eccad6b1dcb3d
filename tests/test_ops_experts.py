"""The dropless expert layer that is told which experts it holds
(`ops/experts.py`), against a dense loop over (token, choice) pairs."""

import functools
import re

import numpy as np
import pytest

pytestmark = pytest.mark.unit

T, D, F, E, K = 37, 32, 24, 16, 4


def _weights(seed=0):
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)

    def mat(*shape):
        return jnp.asarray(rng.normal(size=shape) / np.sqrt(shape[-2]),
                           jnp.float32)

    return {"y": jnp.asarray(rng.normal(size=(T, D)), jnp.float32),
            "router": mat(D, E),
            "bias": jnp.asarray(0.1 * rng.normal(size=(E,)), jnp.float32),
            "w_gate": mat(E, D, F), "w_up": mat(E, D, F),
            "w_down": mat(E, F, D)}


def _dense(w, experts, weights, lo, hi, valid=None):
    y = np.asarray(w["y"], np.float64)
    out = np.zeros(y.shape)
    for t in range(y.shape[0]):
        if valid is not None and not valid[t]:
            continue
        for j in range(experts.shape[1]):
            e = int(experts[t, j])
            if lo <= e < hi:
                gate = y[t] @ np.asarray(w["w_gate"][e], np.float64)
                up = y[t] @ np.asarray(w["w_up"][e], np.float64)
                out[t] += float(weights[t, j]) * (
                    (gate / (1 + np.exp(-gate)) * up)
                    @ np.asarray(w["w_down"][e], np.float64))
    return out


def _held(w, experts, weights, lo, hi, valid=None):
    from ray_tpu.ops.experts import held_experts_ffn

    return held_experts_ffn(w["y"], experts, weights, w["w_gate"][lo:hi],
                            w["w_up"][lo:hi], w["w_down"][lo:hi], (lo, hi),
                            valid)


def test_router_scores_all_experts_and_normalises_over_the_chosen():
    from ray_tpu.ops.experts import route

    w = _weights()
    experts, weights = route(w["y"], w["router"], w["bias"], K, 2.0)
    scores = 1 / (1 + np.exp(-np.asarray(w["y"]) @ np.asarray(w["router"])))
    want = np.argsort(-(scores + np.asarray(w["bias"])), axis=-1)[:, :K]
    np.testing.assert_array_equal(np.sort(experts, -1), np.sort(want, -1))
    np.testing.assert_allclose(np.sum(weights, -1), 2.0, rtol=1e-6)
    chosen = np.take_along_axis(scores, np.asarray(experts), -1)
    np.testing.assert_allclose(
        weights, 2.0 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-5)


@pytest.mark.parametrize("lo,hi", [(0, 2), (6, 8), (3, 11), (0, 16)])
def test_held_part_equals_the_dense_loop_over_its_pairs(lo, hi):
    from ray_tpu.ops.experts import route

    w = _weights(1)
    experts, weights = route(w["y"], w["router"], w["bias"], K)
    out, load = _held(w, experts, weights, lo, hi)
    np.testing.assert_allclose(out, _dense(w, experts, weights, lo, hi),
                               atol=1e-5)
    want_load = [(np.asarray(experts) == e).sum() for e in range(lo, hi)]
    np.testing.assert_array_equal(load, want_load)


def test_the_shares_of_all_chips_add_up_to_the_whole_layer():
    from ray_tpu.ops.experts import route

    w = _weights(2)
    experts, weights = route(w["y"], w["router"], w["bias"], K)
    parts = [_held(w, experts, weights, lo, lo + 2) for lo in range(0, E, 2)]
    whole, load = _held(w, experts, weights, 0, E)
    np.testing.assert_allclose(sum(p[0] for p in parts), whole, atol=1e-5)
    np.testing.assert_array_equal(
        np.concatenate([p[1] for p in parts]), load)
    assert int(load.sum()) == T * K          # no pair dropped anywhere


def test_one_expert_that_takes_every_token_drops_none():
    """A selection bias that sends every token to expert 3: its load is
    every token, whatever the imbalance, and the result is the dense
    loop's."""
    from ray_tpu.ops.experts import route

    w = _weights(3)
    experts, weights = route(w["y"], w["router"],
                             w["bias"].at[3].set(100.0), K)
    assert (np.asarray(experts) == 3).any(axis=-1).all()
    out, load = _held(w, experts, weights, 2, 4)
    assert int(load[1]) == T
    np.testing.assert_allclose(out, _dense(w, experts, weights, 2, 4),
                               atol=1e-5)


def test_rows_that_are_no_sequence_route_nowhere():
    import jax.numpy as jnp

    from ray_tpu.ops.experts import route

    w = _weights(4)
    experts, weights = route(w["y"], w["router"], w["bias"], K)
    valid = np.arange(T) % 3 != 0
    out, load = _held(w, experts, weights, 0, 8, jnp.asarray(valid))
    np.testing.assert_allclose(
        out, _dense(w, experts, weights, 0, 8, valid), atol=1e-5)
    assert not np.asarray(out)[~valid].any()
    assert int(load.sum()) == sum(
        int(((np.asarray(experts)[t] >= 0) & (np.asarray(experts)[t] < 8))
            .sum()) for t in range(T) if valid[t])


def test_it_runs_under_jit_with_a_fixed_number_of_tiles():
    import jax

    from ray_tpu.ops.experts import _tile_rows, route

    assert [_tile_rows(t) for t in (1, 8, 32, 100, 128, 1024)] == \
        [8, 8, 32, 128, 128, 128]
    # The kernel's rows: a whole tile of the chip at two bytes a value.
    assert [_tile_rows(t, 16) for t in (1, 8, 16, 32, 100, 128)] == \
        [16, 16, 16, 32, 128, 128]
    w = _weights(5)

    @jax.jit
    def run(y):
        experts, weights = route(y, w["router"], w["bias"], K)
        return _held(dict(w, y=y), experts, weights, 4, 8)

    experts, weights = route(w["y"], w["router"], w["bias"], K)
    out, _ = run(w["y"])
    np.testing.assert_allclose(out, _dense(w, experts, weights, 4, 8),
                               atol=1e-5)


# ---------------------------------------------------------------------------
# the grouped gated-FFN kernel (what the chip runs at a decode step's
# rows), interpreted, against the scan (what runs here) and the dense loop
# ---------------------------------------------------------------------------
def _case(t, d, f, e, k, seed, dtype="float32", send_all_to=None,
          keep_off=None):
    """Inputs of `t` tokens over `e` experts of ``[d, f]``: `send_all_to`
    an expert every token chooses, `keep_off` a range no token does."""
    import jax.numpy as jnp

    from ray_tpu.ops.experts import route

    rng = np.random.default_rng(seed)

    def mat(*shape):
        return jnp.asarray(rng.normal(size=shape) / np.sqrt(shape[-2]),
                           jnp.float32).astype(dtype)

    bias = 0.1 * rng.normal(size=(e,))
    if send_all_to is not None:
        bias[send_all_to] = 100.0
    if keep_off is not None:
        bias[keep_off[0]:keep_off[1]] = -100.0
    y = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(d, e)) / np.sqrt(d), jnp.float32)
    experts, weights = route(y, router, jnp.asarray(bias, jnp.float32), k)
    return {"y": y, "experts": experts, "weights": weights,
            "w_gate": mat(e, d, f), "w_up": mat(e, d, f),
            "w_down": mat(e, f, d)}


def _through(c, lo, hi, valid, monkeypatch, kernel: bool, block=None,
             tiles=None):
    """`held_experts_ffn` over a case through the scan, or through the
    kernels steered on and interpreted (the backend here is the CPU): a
    batch of one tile through `grouped_ffn_kernel` at `block`, a longer
    prompt through `held_experts_ffn_prefill` at `tiles` (rows a tile,
    columns a block), the rule's where None."""
    from functools import partial

    from ray_tpu.ops import experts as ex

    monkeypatch.setattr(ex, "kernel_eligible", lambda t, *widths: kernel)
    if kernel:
        monkeypatch.setattr(ex, "grouped_ffn_kernel", partial(
            ex.grouped_ffn_kernel, interpret=True, block=block))
        monkeypatch.setattr(ex, "held_experts_ffn_prefill", partial(
            ex.held_experts_ffn_prefill, interpret=True))
        if tiles is not None:
            monkeypatch.setattr(ex, "prefill_tiles", lambda *shape: tiles)
    return _held(c, c["experts"], c["weights"], lo, hi, valid)


KERNEL_CASES = {
    # name: (case arguments, held, rows off, f block)
    # Both cells' shapes cut small: 16 rows, top 10 of 64 with 8 held at
    # f = 256 (repo-context), top 8 of 80 with 10 held at f = 384, three
    # blocks of 128 by the rule's divisors (decode-wide).
    "repo_context_cut_small": (
        dict(t=16, d=256, f=256, e=64, k=10, seed=10), (8, 16), None, None),
    "decode_wide_cut_small": (
        dict(t=16, d=256, f=384, e=80, k=8, seed=11), (10, 20), None, 128),
    "repo_context_cut_small_bf16": (
        dict(t=16, d=256, f=256, e=64, k=10, seed=12, dtype="bfloat16"),
        (8, 16), None, 128),
    "decode_wide_cut_small_bf16": (
        dict(t=16, d=256, f=384, e=80, k=8, seed=13, dtype="bfloat16"),
        (10, 20), None, None),
    "one_row": (dict(t=1, d=128, f=256, e=16, k=4, seed=14), (0, 8), None,
                128),
    "t_100_in_a_tile_of_128": (
        dict(t=100, d=128, f=256, e=16, k=4, seed=15), (2, 10), None, 256),
    "a_whole_tile_of_128_rows": (
        dict(t=128, d=128, f=256, e=8, k=4, seed=16), (0, 4), None, None),
    "a_whole_tile_of_128_rows_bf16": (
        dict(t=128, d=128, f=256, e=8, k=4, seed=17, dtype="bfloat16"),
        (0, 4), None, 128),
    "every_pair_on_one_held_expert": (
        dict(t=16, d=128, f=256, e=8, k=1, seed=18, send_all_to=3),
        (2, 6), None, 128),
    "an_expert_nobody_chose": (
        dict(t=16, d=128, f=256, e=16, k=4, seed=19, keep_off=(5, 7)),
        (4, 8), None, 128),
    "no_pair_on_any_held_expert": (
        dict(t=16, d=128, f=256, e=16, k=4, seed=20, keep_off=(4, 8)),
        (4, 8), None, 128),
    "padded_batch_rows_off": (
        dict(t=16, d=128, f=256, e=16, k=4, seed=21), (0, 8),
        [True] * 5 + [False] * 11, 128),
    "padded_batch_rows_off_bf16": (
        dict(t=16, d=128, f=256, e=16, k=4, seed=22, dtype="bfloat16"),
        (0, 8), [False] * 3 + [True] * 13, 128),
    "more_held_experts_than_pairs": (       # tiles: T k = 6, not 16 held
        dict(t=3, d=128, f=256, e=16, k=2, seed=23), (0, 16), None, 128),
    "f_of_five_blocks": (                   # 1,280's shape in small
        dict(t=16, d=128, f=640, e=16, k=4, seed=24), (0, 8), None, 128),
}


@pytest.mark.parametrize("name", list(KERNEL_CASES))
def test_kernel_matches_the_scan_and_the_dense_loop(name, monkeypatch):
    import jax.numpy as jnp

    args, (lo, hi), rows_on, block = KERNEL_CASES[name]
    c = _case(**args)
    valid = None if rows_on is None else jnp.asarray(rows_on)
    want, want_load = _through(c, lo, hi, valid, monkeypatch, kernel=False)
    got, load = _through(c, lo, hi, valid, monkeypatch, kernel=True,
                         block=block)
    bf16 = args.get("dtype") == "bfloat16"
    # The same operands and float32 sums: the order of the sums differs.
    np.testing.assert_allclose(got, want, atol=2e-5 if bf16 else 5e-6)
    np.testing.assert_allclose(
        got, _dense(c, c["experts"], c["weights"], lo, hi, rows_on),
        atol=5e-2 if bf16 else 2e-5)
    # The pairs on each held expert, to the integer.
    np.testing.assert_array_equal(load, want_load)
    experts = np.asarray(c["experts"])
    on = np.ones(len(experts), bool) if rows_on is None else np.asarray(
        rows_on)
    np.testing.assert_array_equal(
        load, [(experts[on] == e).sum() for e in range(lo, hi)])
    if name == "every_pair_on_one_held_expert":
        assert load.tolist() == [0, 16, 0, 0]
    if name == "an_expert_nobody_chose":
        assert load.tolist()[1:3] == [0, 0] and np.asarray(load).any()
    if name == "no_pair_on_any_held_expert":
        assert not np.asarray(load).any() and not np.asarray(got).any()
    if rows_on is not None:
        assert not np.asarray(got)[~on].any()


PROMPT_CASES = {
    # name: (case arguments, held, rows off, (rows a tile, columns a
    # block) or None for the rule's). A row is d / 128 sublanes and whole
    # tiles of the chip's memory at d a multiple of 1,024.
    "rows_256_k_2": (
        dict(t=256, d=1024, f=256, e=16, k=2, seed=30), (0, 8), None, None),
    "rows_256_k_8": (
        dict(t=256, d=1024, f=256, e=32, k=8, seed=31), (8, 16), None,
        None),
    "rows_512_k_2": (
        dict(t=512, d=1024, f=256, e=16, k=2, seed=32), (4, 12), None,
        None),
    "rows_512_k_8_bf16": (
        dict(t=512, d=1024, f=256, e=32, k=8, seed=33, dtype="bfloat16"),
        (0, 8), None, None),
    "rows_1024_k_2_bf16": (
        dict(t=1024, d=1024, f=128, e=16, k=2, seed=34, dtype="bfloat16"),
        (0, 4), None, None),
    "rows_1024_k_8": (
        dict(t=1024, d=1024, f=128, e=64, k=8, seed=35), (16, 24), None,
        (256, 128)),
    # 256 pairs on one expert: two tiles of 128 in a row, one weight fetch.
    "every_pair_on_one_held_expert": (
        dict(t=256, d=1024, f=256, e=8, k=1, seed=36, send_all_to=3),
        (2, 6), None, None),
    "no_pair_on_any_held_expert": (
        dict(t=256, d=1024, f=256, e=16, k=4, seed=37, keep_off=(4, 8)),
        (4, 8), None, None),
    "an_expert_nobody_chose": (
        dict(t=256, d=1024, f=256, e=16, k=4, seed=38, keep_off=(5, 7)),
        (4, 8), None, None),
    "padded_rows_off": (
        dict(t=256, d=1024, f=256, e=16, k=4, seed=39), (0, 8),
        [True] * 150 + [False] * 106, None),
    "padded_rows_off_bf16": (
        dict(t=512, d=1024, f=256, e=16, k=4, seed=40, dtype="bfloat16"),
        (0, 8), [False] * 40 + [True] * 300 + [False] * 172, (64, 256)),
    "rows_300_no_multiple_of_the_tile": (
        dict(t=300, d=1024, f=256, e=8, k=4, seed=41), (0, 4), None, None),
    "rows_300_in_tiles_of_32_bf16": (
        dict(t=300, d=1024, f=256, e=8, k=4, seed=42, dtype="bfloat16"),
        (4, 8), None, (32, 256)),
    "f_of_three_blocks": (
        dict(t=256, d=1024, f=384, e=16, k=4, seed=43), (0, 8), None,
        (128, 128)),
    "f_of_three_blocks_bf16": (
        dict(t=256, d=2048, f=384, e=16, k=4, seed=44, dtype="bfloat16"),
        (8, 16), None, (128, 128)),
}


@pytest.mark.parametrize("name", list(PROMPT_CASES))
def test_prompt_kernel_matches_the_scan_and_the_dense_loop(name,
                                                           monkeypatch):
    """More rows than a tile: on the chip `held_experts_ffn_prefill`
    over the expert-sorted row tiles, which gathers its own rows and
    folds its own result; here interpreted beside the scan."""
    import jax.numpy as jnp

    from ray_tpu.ops import experts as ex

    args, (lo, hi), rows_on, tiles = PROMPT_CASES[name]
    c = _case(**args)
    assert args["t"] > ex._ROWS_MOST
    valid = None if rows_on is None else jnp.asarray(rows_on)
    want, want_load = _through(c, lo, hi, valid, monkeypatch, kernel=False)
    got, load = _through(c, lo, hi, valid, monkeypatch, kernel=True,
                         tiles=tiles)
    bf16 = args.get("dtype") == "bfloat16"
    # The same operands, float32 sums and order of tiles; several blocks
    # of f add up in another order.
    np.testing.assert_allclose(got, want, atol=2e-5 if bf16 else 5e-6)
    on = (np.ones(args["t"], bool) if rows_on is None
          else np.asarray(rows_on))
    some = np.flatnonzero(on)[::17]             # the dense loop is slow
    dense = _dense(dict(c, y=c["y"][some]), np.asarray(c["experts"])[some],
                   np.asarray(c["weights"])[some], lo, hi)
    np.testing.assert_allclose(np.asarray(got)[some], dense,
                               atol=5e-2 if bf16 else 5e-5)
    np.testing.assert_array_equal(load, want_load)
    experts = np.asarray(c["experts"])
    np.testing.assert_array_equal(
        load, [(experts[on] == e).sum() for e in range(lo, hi)])
    if name == "every_pair_on_one_held_expert":
        assert load.tolist() == [0, 256, 0, 0]
    if name == "an_expert_nobody_chose":
        assert load.tolist()[1:3] == [0, 0] and np.asarray(load).any()
    if name == "no_pair_on_any_held_expert":
        assert not np.asarray(load).any() and not np.asarray(got).any()
    if "rows_1024" in name or "rows_300" in name:
        assert int(load.max()) > ex._ROWS_MOST  # several tiles an expert
    if rows_on is not None:
        assert not np.asarray(got)[~on].any()


def test_the_prompt_kernels_name_is_not_the_decode_kernels():
    """`held_experts_ffn_decode_roofline` sums every operation whose
    name begins with the decode kernel's and divides the decode steps'
    bytes by it: a prompt's kernel under such a name would add time and
    no bytes."""
    import jax
    import jax.numpy as jnp

    from benchmarks.layer_metrics import held_experts_ffn_decode_roofline
    from ray_tpu.ops import experts as ex

    def spec(*shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype)

    def names(fn, *args, **static):
        text = str(jax.make_jaxpr(functools.partial(fn, **static))(*args))
        return set(re.findall(r"held_experts_ffn\w*", text))

    bf16, f32 = jnp.bfloat16, jnp.float32
    stacks = [spec(4, 1024, 256, dtype=bf16), spec(4, 1024, 256, dtype=bf16),
              spec(4, 256, 1024, dtype=bf16)]
    prompt = names(ex.held_experts_ffn_prefill, spec(256, 1024, dtype=f32),
                   spec(12), spec(12), spec(12), spec(1024),
                   spec(1024, dtype=f32), *stacks, tile=128, block=256)
    decode = names(ex.grouped_ffn_kernel, spec(16, 1024, dtype=bf16),
                   spec(4), spec(4, 16, 1, dtype=f32), *stacks)
    assert prompt == {"held_experts_ffn_prefill"}
    assert decode == {"held_experts_ffn_decode"}
    reads = held_experts_ffn_decode_roofline.KERNEL
    assert all(reads.match(name) for name in decode)
    assert not any(reads.match(name) for name in prompt)


@pytest.mark.parametrize("rows, d, f, dtype, want", [
    # Both sparse cells' prefill buckets.
    (1024, 3072, 1024, "bfloat16", True), (4096, 3072, 1024, "bfloat16", True),
    (256, 4096, 1280, "bfloat16", True), (512, 4096, 1280, "float32", True),
    # The [T, d] float32 sum alone is 100 MB: the scan.
    (8192, 3072, 1024, "bfloat16", False),
    # A row that is no whole tiles of the chip's memory: the scan.
    (1024, 3072 + 128, 1024, "bfloat16", False),
    (1024, 3072, 1000, "bfloat16", False),
    (1024, 3072, 1024, "float16", False),
], ids=["repo_context_1024", "repo_context_4096", "decode_wide_256",
        "float32_512", "sum_past_vmem", "row_of_25_sublanes",
        "f_no_whole_lanes", "float16"])
def test_a_prompt_takes_the_kernel_where_its_sum_fits_vmem(
        rows, d, f, dtype, want, monkeypatch):
    import jax

    from ray_tpu.ops import experts as ex

    assert not ex.kernel_eligible(rows, d, f, dtype)        # off the chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ex.kernel_eligible(rows, d, f, dtype) == want
    if want:
        itemsize = 4 if dtype == "float32" else 2
        tile, block = ex.prefill_tiles(rows, 8, 32, d, f, itemsize)
        assert rows % tile == 0 and f % block == 0 and block % 128 == 0
        assert ex._prefill_vmem(rows, d, tile, block,
                                itemsize) <= ex._VMEM_MOST


@pytest.mark.parametrize("rows, k, held, d, f, want", [
    (1024, 10, 32, 3072, 1024, (128, 1024)),        # the rule's
    (2048, 10, 32, 3072, 1024, (96, 512)),          # a swept entry
    (4096, 10, 32, 3072, 1024, (192, 512)),         # a swept entry
    (256, 8, 40, 4096, 1280, (128, 640)),           # the rule's
    (512, 8, 40, 4096, 1280, (128, 640)),           # the rule's
    (4096, 8, 32, 3072, 1024, (128, 1024)),         # another k: the rule's
], ids=["repo_context_1024", "repo_context_2048", "repo_context_4096",
        "decode_wide_256", "decode_wide_512", "unswept_k"])
def test_prefill_tiles_at_the_cells_buckets_are_the_ones_timed(
        rows, k, held, d, f, want):
    """The tiles PR 46's chip runs were made with, bfloat16 weights: two
    buckets by their swept entry, the others by the rule; every one fits
    the chip's VMEM and tiles of no whole sublanes are not chosen."""
    from ray_tpu.ops import experts as ex

    tile, block = ex.prefill_tiles(rows, k, held, d, f, 2)
    assert (tile, block) == want
    assert tile % 8 == 0 and f % block == 0
    assert ex._prefill_vmem(rows, d, tile, block, 2) <= ex._VMEM_MOST


def test_pairs_past_the_sorts_32_bit_key_are_refused():
    """The one sort's key is ``expert x T k + pair`` in 32 bits; a call
    whose pairs pass it raises at trace, where a shape in reach sorts."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import experts as ex

    def pairs(rows, k):
        return (jax.ShapeDtypeStruct((rows, k), jnp.int32),
                jax.ShapeDtypeStruct((rows, k), jnp.float32))

    sort = functools.partial(ex._pairs_by_expert, n_held=32)
    assert [a.shape for a in jax.eval_shape(sort, *pairs(4096, 10))] \
        == [(40960,)] * 3
    with pytest.raises(ValueError, match="32-bit key"):
        jax.eval_shape(sort, *pairs(1 << 22, 16))


def test_eligibility_follows_backend_rows_widths_and_dtype(monkeypatch):
    """Off the chip nothing is eligible; on it, bfloat16 or float32
    weights and weight blocks of whole lanes, a batch of one tile or a
    longer prompt (the next test): what the code can see, no option."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import experts as ex

    assert not ex.kernel_eligible(16, 3072, 1024, jnp.bfloat16)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ex.kernel_eligible(16, 3072, 1024, jnp.bfloat16)
    assert ex.kernel_eligible(128, 4096, 1280, jnp.bfloat16)
    assert ex.kernel_eligible(16, 3072, 1024, jnp.float32)
    assert ex.kernel_eligible(16, 3072, 1024, "bfloat16")
    assert ex.kernel_eligible(256, 3072, 1024, jnp.bfloat16)  # a prompt
    assert ex.kernel_eligible(4096, 4096, 1280, jnp.bfloat16)
    assert not ex.kernel_eligible(16, 3072, 1024, jnp.float16)
    assert not ex.kernel_eligible(16, 3072, 1024, jnp.float8_e4m3fn)
    assert not ex.kernel_eligible(16, 32, 24, jnp.bfloat16)  # the unit tests'
    assert not ex.kernel_eligible(16, 3072, 1000, jnp.bfloat16)


@pytest.mark.parametrize("d, f, want", [
    # laguna-s-2.1's experts, [3072, 1024] in bf16: the whole of f.
    (3072, 1024, 1024),
    # solar-open2-250b's, [4096, 1280]: 1,280 = 2 x 640; the whole of it,
    # two deep, would be 63 MB.
    (4096, 1280, 640),
    # Wider still: the budget decides. Narrow: never more than f itself.
    (8192, 2048, 512), (1024, 256, 256), (1024, 128, 128),
], ids=["laguna", "solar", "wide", "narrow", "one_lane_block"])
def test_f_block_follows_the_widths_and_the_vmem_budget(d, f, want):
    from ray_tpu.ops import experts as ex

    block = ex.f_block(d, f, 2)
    assert block == want
    assert f % block == 0 and block % 128 == 0
    # Three blocks, two deep, inside the budget.
    assert 2 * 3 * d * block * 2 <= ex._VMEM_FOR_WEIGHTS
    # Four-byte operands halve what fits.
    assert ex.f_block(d, f, 4) <= block
