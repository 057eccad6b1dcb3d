"""Decode attention over the paged KV pool (`ops/paged_attention.py`).

The Pallas kernel runs here in interpret mode at small shapes with heads
of 128, against the XLA body it replaces off the chip and against a
dense float64 reference that gathers each row's cache by hand. At the
end of the file the kernel and the whole paged decode step are compiled
for a described v5e at `olmo-1b`'s widths: nothing runs, but the chip's
compiler says what it would refuse, and whether it would copy the pool
or, since PR 50 (`ops/weight_matmul.py`), the weights.
"""

import functools

import numpy as np
import pytest

pytestmark = pytest.mark.unit

N_BLOCKS, BS, LAYERS, HEADS, HD = 12, 4, 3, 8, 128
STALE = 1.0e4     # what a reused block may still hold past `position`


def _dense_reference(q, k_new, v_new, pool, tables, positions, layer):
    """Row by row in float64: gather positions [0, position) through
    the table, append the step's own key and value, softmax."""
    out = np.zeros(q.shape, np.float64)
    bs = pool.shape[1]
    for i, pos in enumerate(positions):
        rows = [pool[tables[i][t // bs], t % bs, layer]
                for t in range(pos)]
        keys = np.stack([r[0] for r in rows] + [k_new[i]]).astype(np.float64)
        vals = np.stack([r[1] for r in rows] + [v_new[i]]).astype(np.float64)
        scores = np.einsum("hd,shd->hs", q[i].astype(np.float64),
                           keys) * q.shape[-1] ** -0.5
        probs = np.exp(scores - scores.max(axis=-1, keepdims=True))
        probs /= probs.sum(axis=-1, keepdims=True)
        out[i] = np.einsum("hs,shd->hd", probs, vals)
    return out


def _case(name):
    """(tables [B, nb], positions [B], pool) for one named layout."""
    rng = np.random.default_rng(sum(map(ord, name)))
    pool = rng.normal(size=(N_BLOCKS, BS, LAYERS, 2, HEADS, HD)).astype(
        np.float32)
    nb = 4
    if name == "one_token_row":
        # Nothing cached: the row attends over its own token alone.
        tables, positions = [[3, 0, 0, 0], [5, 6, 0, 0]], [0, 6]
    elif name == "row_ends_on_block_edge":
        # Positions 8 and 16: the cached part fills whole pages, and
        # the second row fills the whole table.
        tables, positions = [[1, 2, 9, 0], [4, 5, 6, 7]], [8, 16]
    elif name == "rows_shorter_than_table":
        tables, positions = [[7, 0, 0, 0], [2, 3, 0, 0], [8, 9, 10, 11]], \
            [3, 5, 15]
    elif name == "padded_batch_rows":
        # Rows past the batch: table of zeros, position 0, as
        # `decode_paged` pads them; block 0 holds another row's data.
        tables, positions = [[0, 1, 0, 0], [0, 0, 0, 0], [0, 0, 0, 0],
                             [0, 0, 0, 0]], [7, 0, 0, 0]
    elif name == "shuffled_tables":
        perm = rng.permutation(N_BLOCKS)
        tables = [perm[:4].tolist(), perm[4:8].tolist(),
                  perm[8:12].tolist()]
        positions = [13, 9, 15]
    elif name == "shared_tables":
        # Two rows read the same prefix blocks (prefix sharing), then
        # part ways.
        tables, positions = [[2, 5, 8, 0], [2, 5, 9, 0], [2, 5, 0, 0]], \
            [10, 11, 8]
    elif name == "stale_data_past_position":
        # A reused block: rows at and past `position`, and the blocks
        # behind padded table entries, hold large stale values.
        tables, positions = [[1, 2, 0, 0], [3, 0, 0, 0]], [6, 1]
        pool[2, 2:] = STALE
        pool[3, 1:] = STALE
        pool[0] = -STALE
    elif name == "one_row_one_page":
        nb = 1
        tables, positions = [[6]], [3]
    else:
        raise KeyError(name)
    assert all(len(t) == nb for t in tables)
    return (np.asarray(tables, np.int32), np.asarray(positions, np.int32),
            pool)


CASES = ["one_token_row", "row_ends_on_block_edge",
         "rows_shorter_than_table", "padded_batch_rows", "shuffled_tables",
         "shared_tables", "stale_data_past_position", "one_row_one_page"]


def _inputs(name):
    tables, positions, pool = _case(name)
    rng = np.random.default_rng(len(name))
    q, k_new, v_new = (rng.normal(size=(len(positions), HEADS, HD)).astype(
        np.float32) for _ in range(3))
    return q, k_new, v_new, pool, tables, positions


# Pages a fetch: one (a page a step, as the kernel was), what
# `pages_per_step` takes for the table (None), one that leaves these
# tables of 4 a ragged last group, and more than a table names.
PAGES = [1, None, 3, 8]
PAGES_IDS = ["pages1", "pages_by_rule", "pages3_ragged", "pages8_wider"]


@pytest.mark.parametrize("pages", PAGES, ids=PAGES_IDS)
@pytest.mark.parametrize("name", CASES)
def test_kernel_matches_xla_body_and_dense_reference(name, pages):
    import jax.numpy as jnp

    from ray_tpu.ops import paged_attention as pa

    args = _inputs(name)
    for layer in (0, LAYERS - 1):
        want = _dense_reference(*args, layer)
        dev = [jnp.asarray(a) for a in args]
        xla = np.asarray(pa.paged_decode_attention_xla(*dev, layer))
        kernel = np.asarray(pa.paged_decode_attention_kernel(
            *dev, jnp.int32(layer), pages=pages, interpret=True))
        assert np.isfinite(kernel).all()
        np.testing.assert_allclose(kernel, xla, atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(kernel, want, atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(xla, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("pages", PAGES, ids=PAGES_IDS)
@pytest.mark.parametrize("name", ["padded_batch_rows",
                                  "stale_data_past_position"])
def test_result_ignores_what_lies_past_position(name, pages):
    """Rewrite everything a row must not read (pool rows at or past its
    position, every block its live pages do not name): neither body's
    result moves by a bit, whatever a fetch brings beside the live
    pages."""
    import jax.numpy as jnp

    from ray_tpu.ops import paged_attention as pa

    q, k_new, v_new, pool, tables, positions = _inputs(name)
    live = np.zeros(pool.shape[:2], bool)
    for table, pos in zip(tables, positions):
        for t in range(pos):
            live[table[t // BS], t % BS] = True
    other = np.where(live[:, :, None, None, None, None], pool,
                     np.float32(-3 * STALE))
    for body in (pa.paged_decode_attention_xla,
                 lambda *a: pa.paged_decode_attention_kernel(
                     *a, pages=pages, interpret=True)):
        got = [np.asarray(body(*(jnp.asarray(a) for a in (
            q, k_new, v_new, p, tables, positions)), jnp.int32(1)))
            for p in (pool, other)]
        np.testing.assert_array_equal(got[0], got[1])


def test_a_slab_no_copy_filled_never_reaches_the_result():
    """A fetch of 8 pages for rows that hold 1 or 2: the slab's other
    pages are what the call's first step cleared, whatever VMEM held,
    and a masked key's value of NaN would otherwise poison the sum
    (0 x NaN). Here: a pool of NaN everywhere but the live rows."""
    import jax.numpy as jnp

    from ray_tpu.ops import paged_attention as pa

    q, k_new, v_new, pool, tables, positions = _inputs(
        "stale_data_past_position")
    live = np.zeros(pool.shape[:2], bool)
    for table, pos in zip(tables, positions):
        for t in range(pos):
            live[table[t // BS], t % BS] = True
    # Whole live pages are copied, stale rows and all: those stay finite
    # (the pool never holds anything else); every other block is NaN.
    named = np.zeros(pool.shape[0], bool)
    named[[table[t // BS] for table, pos in zip(tables, positions)
           for t in range(pos)]] = True
    poisoned = np.where(named[:, None, None, None, None, None], pool,
                        np.float32("nan"))
    got = [np.asarray(pa.paged_decode_attention_kernel(
        *(jnp.asarray(a) for a in (q, k_new, v_new, p, tables, positions)),
        jnp.int32(1), pages=8, interpret=True)) for p in (pool, poisoned)]
    assert np.isfinite(got[1]).all()
    np.testing.assert_array_equal(got[0], got[1])


# The three models' pools as the cells hold them: a page's bytes, the
# widths their tables take, and what a fetch then brings.
@pytest.mark.parametrize("page_bytes, width, want", [
    (16 * 2 * 8 * 128 * 2, 512, 32),    # laguna global, bf16: 64 KB
    (16 * 2 * 8 * 128 * 2, 128, 32),
    (16 * 2 * 8 * 128 * 2, 33, 32),     # its window group's 33 columns
    (16 * 2 * 8 * 128 * 2, 64, 32),     # solar-open2's one KV layer
    (16 * 2 * 8 * 128 * 2, 16, 16),
    (16 * 2 * 16 * 128 * 4, 64, 8),     # olmo-1b, float32: 256 KB
    (16 * 2 * 16 * 128 * 4, 32, 8),
    (16 * 2 * 16 * 128 * 4, 4, 4),      # short contexts: the table's width
    (16 * 2 * 16 * 128 * 4, 2, 2),
    (16 * 2 * 16 * 128 * 4, 1, 1),
    (4 * 2 * 8 * 128 * 4, 4, 4),        # this file's pool
    (16 << 20, 64, 1),                  # a page past the budget: one
], ids=lambda v: str(v))
def test_pages_per_step_follows_page_bytes_and_table_width(page_bytes,
                                                           width, want):
    from ray_tpu.ops import paged_attention as pa

    pages = pa.pages_per_step(page_bytes, width)
    assert pages == want
    # Two slabs fit the budget, a pass divides the group, and no knob.
    assert pages == 1 or 2 * pages * page_bytes <= pa._VMEM_FOR_PAGES
    assert pages % pa._pages_a_pass(pages, page_bytes) == 0
    assert pages & (pages - 1) == 0


@pytest.mark.parametrize("window", [None, 40], ids=["global", "window40"])
@pytest.mark.parametrize("pages_wanted", [1, 2, 8])
def test_page_groups_is_a_direct_count_of_the_kernels_walk(window,
                                                           pages_wanted):
    """`page_groups` against a walk written out: a row's live columns
    from the first that holds a key it sees, `pages` at a time."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import paged_attention as pa

    bs, width = 16, {1: 1, 2: 2, 8: 64}[pages_wanted]
    # A float32 pool of 256 KB pages: 8 a fetch, or the table's width.
    pool = jax.ShapeDtypeStruct((64, bs, 3, 2, 16, 128), jnp.float32)
    pages = pa.pool_pages_per_step(pool, width)
    assert pages == pages_wanted
    positions = [p for p in (0, 1, 15, 16, 17, 40, 129, 500, 1000)
                 if p < width * bs]
    direct = 0
    for p in positions:
        columns = [c for c in range(width)
                   if c * bs < p and (window is None
                                      or c * bs + bs > p - window + 1)]
        assert len(columns) == pa.live_pages(p, bs, window)
        direct += len(range(0, len(columns), pages))
    assert pa.page_groups(pool, width, positions, window) == direct
    assert pa.page_groups(pool, width, [0], window) == 0


def test_kernel_eligibility_follows_backend_and_widths(monkeypatch):
    """Off the chip nothing is eligible; on it, a multiple of 8 heads of
    a multiple of 128: what the code can see, no option."""
    import jax

    from ray_tpu.ops import paged_attention as pa

    assert not pa.kernel_eligible(16, 128)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pa.kernel_eligible(16, 128) and pa.kernel_eligible(8, 256)
    assert not pa.kernel_eligible(32, 64)      # smollm2's heads
    assert not pa.kernel_eligible(12, 128)     # the pool's layout differs
    assert not pa.kernel_eligible(4, 16)       # the unit tests' models


# ---------------------------------------------------------------------------
# the engine's step over the kernel, interpreted
# ---------------------------------------------------------------------------
def _tiny_model(**kw):
    import jax

    from ray_tpu.models.transformer import TransformerConfig, init_params
    from ray_tpu.serve.engine import TransformerEngineModel

    cfg = TransformerConfig(vocab_size=64, d_model=HEADS * HD, n_layers=2,
                            n_heads=HEADS, d_ff=64, max_seq_len=64)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return TransformerEngineModel(params, cfg, **kw)


def _generate(model, prompts, new_tokens):
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine

    model.eos_token = None
    eng = InferenceEngine(model, EngineConfig(
        max_batch_size=4, block_size=4, num_blocks=32))
    streams = [eng.submit(p, new_tokens) for p in prompts]
    while eng.step():
        pass
    return [list(s) for s in streams], eng.stats()


def test_engine_step_through_the_kernel_matches_the_xla_step(monkeypatch):
    """The same requests through the engine twice: the XLA body (what
    the CPU picks) and the kernel, steered here and interpreted. Same
    tokens, and the counters say which steps read pages in place."""
    from functools import partial

    from ray_tpu.ops import paged_attention as pa

    rng = np.random.default_rng(3)
    prompts = [rng.integers(2, 64, n).tolist() for n in (1, 4, 9)]
    want, plain = _generate(_tiny_model(), prompts, 6)
    assert plain["paged_steps"] > 0
    assert (plain["decode_attn_inplace_steps"],
            plain["decode_kv_pages_read"]) == (0, 0)

    monkeypatch.setattr(pa, "kernel_eligible",
                        lambda h, hd, hkv=None: hd % 128 == 0)
    monkeypatch.setattr(pa, "paged_decode_attention_kernel", partial(
        pa.paged_decode_attention_kernel, interpret=True))
    got, stats = _generate(_tiny_model(), prompts, 6)
    assert got == want
    assert stats["decode_attn_inplace_steps"] == stats["paged_steps"] > 0
    # Every row of every step names `position // 4 + 1` pages; the rows
    # decode positions n .. n + 4 (the prefill gave the first token).
    assert stats["decode_kv_pages_read"] == sum(
        pos // 4 + 1 for n in (1, 4, 9) for pos in range(n, n + 5))
    # Tables of 1, 2 and 4 columns here, and pages of 32 KB: a fetch
    # brings a row's every live page, so a row that has one is one group.
    assert stats["decode_kv_page_groups_read"] == sum(
        1 for n in (1, 4, 9) for pos in range(n, n + 5) if pos > 0)
    assert plain["decode_kv_page_groups_read"] == 0


def test_engine_programs_through_the_weight_kernel_match_xlas(monkeypatch):
    """A prompt and three decode steps through the engine's cache twice
    (`benchmarks/families/dense.py`'s drive): XLA's product of the
    bf16-rounded operands (what the chip's default precision makes of
    the float32 product), and `ops.weight_matmul`'s kernel, steered on
    here and interpreted. The same greedy tokens, and the counters say
    which body every program was traced with. The logits differ by what
    a bf16 rounding makes of another order of a float32 sum: where a
    sum's last bit moves a value across a bf16 boundary, the next
    product sees 2^-8 of it (1e-3 of the logits' size here; a wrong
    layer index or a missed block of K gives about 1)."""
    from functools import partial

    import jax.numpy as jnp

    from benchmarks.families import dense
    from ray_tpu.ops import weight_matmul as wm
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine

    def rounded(x, w_stack, layer, name=None):
        return jnp.dot(x.astype(jnp.bfloat16),
                       w_stack[layer].astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)

    def drive():
        model = _tiny_model()
        engine = InferenceEngine(model, EngineConfig(
            max_batch_size=4, block_size=4, num_blocks=32))
        prompt = np.random.default_rng(5).integers(2, 64, 9).tolist()
        rows, tokens = dense.drive(engine, {"model": model}, prompt, 3, "s")
        return np.stack(rows), tokens, engine.stats()

    monkeypatch.setattr(wm, "stacked_weight_matmul", rounded)
    want, want_tokens, plain = drive()
    assert (plain["dense_steps_kernel"], plain["dense_steps_xla"]) == (0, 4)
    monkeypatch.undo()

    monkeypatch.setattr(wm, "kernel_eligible", lambda rows, k, n, dt: True)
    monkeypatch.setattr(wm, "stacked_weight_matmul_kernel", partial(
        wm.stacked_weight_matmul_kernel, interpret=True))
    got, got_tokens, stats = drive()
    assert got_tokens == want_tokens
    assert np.sqrt(np.mean((got - want) ** 2)) < 3e-3 * np.sqrt(
        np.mean(want ** 2))
    # One prefill (a bucket of 16 rows) and three steps (a row each).
    assert (stats["dense_steps_kernel"], stats["dense_steps_xla"]) == (4, 0)


# ---------------------------------------------------------------------------
# compiled for the chip, not run (no chip here)
# ---------------------------------------------------------------------------
OLMO = dict(vocab_size=50304, d_model=2048, n_layers=16, n_heads=16,
            d_ff=8192, rope_theta=10000.0)
POOL = (1024, 16, 16, 2, 16, 128)       # the serve cells' pool: 4.3 GB


@pytest.fixture(scope="module")
def one_chip():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no compiler, no test
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without the chip."""
    import jax

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", before)


@pytest.mark.parametrize("b_pad,nb_pad", [(1, 1), (8, 32), (8, 64)])
def test_kernel_compiles_for_v5e_at_olmo_widths(one_chip, no_compile_cache,
                                                b_pad, nb_pad):
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import paged_attention as pa

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    row = spec((b_pad,) + POOL[-2:])
    compiled = jax.jit(pa.paged_decode_attention_kernel).lower(
        row, row, row, spec(POOL), spec((b_pad, nb_pad), jnp.int32),
        spec((b_pad,), jnp.int32), spec((), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # The pool is an operand as it stands: no converted copy of it.
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


# `olmo-1b`'s four stacks of layer matrices as the engine model holds
# them, and one layer of each: what no instruction of a compiled program
# may produce (a rounded or re-laid copy of a stack, a layer copied out).
STACKS = {"wqkv": (16, 2048, 6144), "wo": (16, 2048, 2048),
          "w13": (16, 2048, 16384), "w2": (16, 8192, 2048)}


def _weight_sized_results(text: str):
    """The instructions of a compiled program whose result is a stack of
    layer matrices or one layer's matrix (bf16 or float32, by any name of
    instruction but the program's parameters and the loop's plumbing)."""
    import re

    shapes = set(STACKS.values()) | {s[1:] for s in STACKS.values()}
    found = []
    for dtype, dims, op in re.findall(
            r"= (f32|bf16)\[([\d,]+)\]\S* ([\w-]+)\(", text):
        shape = tuple(int(d) for d in dims.split(",") if d != "1")
        if shape in shapes and op not in (
                "parameter", "get-tuple-element", "bitcast"):
            found.append(f"{dtype}[{dims}] {op}")
    return found


def _abstract_olmo_model(one_chip):
    """`TransformerEngineModel` at `olmo-1b`'s widths over shapes, not
    arrays: built under `eval_shape`, which also gives the shapes of the
    tree it lays out for its programs."""
    import jax

    from ray_tpu.models.transformer import TransformerConfig, init_params
    from ray_tpu.serve.engine import TransformerEngineModel

    cfg = TransformerConfig(**OLMO, max_seq_len=1024)
    held = {}

    def build(params):
        held["model"] = TransformerEngineModel(params, cfg)
        return held["model"]._params

    laid = jax.eval_shape(build, jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    assert {k: laid["layers"][k].shape for k in STACKS} == STACKS
    return held["model"], jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        laid)


def _steer_both_kernels_on(monkeypatch):
    """The backend here is the CPU: what the chip would choose."""
    from ray_tpu.ops import paged_attention as pa
    from ray_tpu.ops import weight_matmul as wm

    rows_most = wm._ROWS_MOST
    monkeypatch.setattr(pa, "kernel_eligible", lambda h, hd, hkv=None: True)
    monkeypatch.setattr(wm, "kernel_eligible",
                        lambda rows, k, n, dtype: rows <= rows_most)


def test_decode_step_compiles_for_v5e_without_a_copy_of_the_pool(
        one_chip, no_compile_cache, monkeypatch):
    """The whole `jit_decode_paged` of the (8, 64) bucket at `olmo-1b`'s
    widths, both kernels steered on: the donated pool is aliased to the
    output, and the program holds next to nothing besides its arguments:
    no dense copy of the batch's cache, no second pool and, since PR 50,
    no bf16 copy of the weight stacks (2.15 GB until then) nor a layer
    copied out of one; the float32 stacks are the kernels' operands as
    the model holds them."""
    import jax
    import jax.numpy as jnp

    _steer_both_kernels_on(monkeypatch)
    model, laid = _abstract_olmo_model(one_chip)
    assert (1024, 16) + model.kv_token_shape == POOL
    compiled = model._build_decode_paged(8, 64, 16).lower(
        jax.ShapeDtypeStruct(POOL, jnp.float32, sharding=one_chip), laid,
        jax.ShapeDtypeStruct((8, 5 + 64), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((model._ids_width(8),), jnp.int32,
                             sharding=one_chip)).compile()
    text, memory = compiled.as_text(), compiled.memory_analysis()
    # One paged attention call and four weight products a layer.
    assert text.count("tpu_custom_call") == 5
    pool_bytes = int(np.prod(POOL)) * 4
    assert memory.alias_size_in_bytes == pool_bytes
    assert memory.temp_size_in_bytes < 64 << 20
    for dense in ("f32[512,16,16,2,16,128]", "f32[16,8,1024,2,16,128]",
                  "f32[8,1024,16,2,16,128]", "bf16[16,2048,", "bf16[16,8192,"):
        assert dense not in text
    assert _weight_sized_results(text) == []


def test_prefill_bucket_compiles_for_v5e_without_a_copy_of_the_weights(
        one_chip, no_compile_cache, monkeypatch):
    """`jit_prefill` of the 256 bucket, whose products take the kernel:
    as the decode step, no rounded copy of a stack and no layer copied
    out of one."""
    import jax
    import jax.numpy as jnp

    _steer_both_kernels_on(monkeypatch)
    model, laid = _abstract_olmo_model(one_chip)
    compiled = model._build_prefill(256).lower(
        laid, jax.ShapeDtypeStruct((256,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)).compile()
    text, memory = compiled.as_text(), compiled.memory_analysis()
    assert text.count("tpu_custom_call") == 4
    assert memory.temp_size_in_bytes < 64 << 20
    assert "bf16[16,2048," not in text and "bf16[16,8192," not in text
    assert _weight_sized_results(text) == []


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("rows", [8, 256])
@pytest.mark.parametrize("name", list(STACKS))
def test_weight_kernel_compiles_for_v5e_at_olmo_widths(
        one_chip, no_compile_cache, name, rows, dtype):
    """`stacked_weight_matmul_kernel` alone at each of the model's four
    ``(K, N)``, a decode step's rows and a prompt's, the tiles the chip
    runs with: the stack is an operand as it stands."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import weight_matmul as wm

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    _, k, n = STACKS[name]
    compiled = jax.jit(wm.stacked_weight_matmul_kernel).lower(
        spec((rows, k)), spec(STACKS[name], dtype),
        spec((), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
    assert _weight_sized_results(compiled.as_text()) == []


# ---------------------------------------------------------------------------
# grouped heads over a bf16 pool: 64 query heads on 8 key/value heads
# ---------------------------------------------------------------------------
Q_HEADS = 64


def _grouped_inputs(name, pool_dtype):
    """The layouts above with `Q_HEADS` query heads over the pool's
    `HEADS` key/value heads, the pool rounded to `pool_dtype`."""
    import jax.numpy as jnp

    tables, positions, pool = _case(name)
    rng = np.random.default_rng(len(name) + 100)
    b = len(positions)
    q = rng.normal(size=(b, Q_HEADS, HD)).astype(np.float32)
    k_new, v_new = (np.asarray(jnp.asarray(
        rng.normal(size=(b, HEADS, HD)), pool_dtype).astype(jnp.float32))
        for _ in range(2))
    pool = jnp.asarray(np.clip(pool, -STALE, STALE), pool_dtype)
    return q, k_new, v_new, pool, tables, positions


def _grouped_reference(q, k_new, v_new, pool, tables, positions, layer):
    """Query head i over key head i // group: the ungrouped reference on
    the pool with each key/value head repeated `group` times."""
    group = q.shape[1] // k_new.shape[1]
    return _dense_reference(
        q, np.repeat(k_new, group, axis=1), np.repeat(v_new, group, axis=1),
        np.repeat(np.asarray(pool.astype("float32")), group, axis=4),
        tables, positions, layer)


@pytest.mark.parametrize("pages", PAGES, ids=PAGES_IDS)
@pytest.mark.parametrize("pool_dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("name", ["one_token_row", "rows_shorter_than_table",
                                  "padded_batch_rows", "shuffled_tables",
                                  "stale_data_past_position"])
def test_grouped_heads_kernel_matches_its_xla_twin(name, pool_dtype, pages):
    import jax.numpy as jnp

    from ray_tpu.ops import paged_attention as pa

    args = _grouped_inputs(name, pool_dtype)
    for layer in (0, LAYERS - 1):
        want = _grouped_reference(*args, layer)
        dev = [jnp.asarray(a) for a in args]
        xla = np.asarray(pa.paged_decode_attention_xla(*dev, layer))
        kernel = np.asarray(pa.paged_decode_attention_kernel(
            *dev, jnp.int32(layer), pages=pages, interpret=True))
        assert kernel.shape == (len(args[-1]), Q_HEADS, HD)
        assert np.isfinite(kernel).all()
        np.testing.assert_allclose(kernel, xla, atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(xla, want, atol=2e-5, rtol=2e-5)


def test_grouped_eligibility_follows_the_pool_rows_heads(monkeypatch):
    """With grouped heads the pool's rows are `n_kv_heads` wide: those
    decide, from backend and widths alone."""
    import jax

    from ray_tpu.ops import paged_attention as pa

    assert not pa.kernel_eligible(64, 128, 8)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pa.kernel_eligible(64, 128, 8)
    assert pa.kernel_eligible(16, 128) and pa.kernel_eligible(16, 128, 16)
    assert pa.kernel_eligible(64, 128, 4)      # by planes, the per-head body
    assert pa.kernel_eligible(32, 128, 2)      # likewise (and a head at a time)
    assert not pa.kernel_eligible(64, 128, 1)  # not taken yet: ROADMAP R0b
    assert not pa.kernel_eligible(64, 64, 8)
    assert not pa.kernel_eligible(12, 128, 8)      # no whole groups


HYBRID_POOL = (4096, 16, 1, 2, 8, 128)      # the hybrid cell's pool, bf16


@pytest.mark.parametrize("b_pad,nb_pad", [(1, 4), (32, 64)])
def test_grouped_kernel_compiles_for_v5e_over_the_bf16_pool(
        one_chip, no_compile_cache, b_pad, nb_pad):
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import paged_attention as pa

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(pa.paged_decode_attention_kernel).lower(
        spec((b_pad, Q_HEADS, 128)), spec((b_pad, 8, 128), jnp.bfloat16),
        spec((b_pad, 8, 128), jnp.bfloat16),
        spec(HYBRID_POOL, jnp.bfloat16), spec((b_pad, nb_pad), jnp.int32),
        spec((b_pad,), jnp.int32), spec((), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def test_hybrid_decode_step_compiles_for_v5e_with_both_pools_in_place(
        one_chip, no_compile_cache, monkeypatch):
    """The whole (32, 64) decode step of the hybrid model at its
    published widths, the kernel steered on: the KV pool and the state
    pool are both aliased to the outputs, and beside its arguments the
    program holds tens of megabytes: no copy of a pool, of the batch's
    state or of a layer's expert matrices."""
    import json
    import os

    import jax
    import jax.numpy as jnp

    from benchmarks.harness import manifest
    from ray_tpu.models.hybrid_moe import init_params
    from ray_tpu.ops import paged_attention as pa
    from ray_tpu.serve.engine import HybridEngineModel

    monkeypatch.setattr(pa, "kernel_eligible", lambda *heads: True)
    family = manifest.load_family("solar_open2")
    with open(os.path.join(manifest.ROOT, "benchmarks", "configs",
                           "solar-open2-250b.json")) as f:
        cfg = family.model_config(family.widths(json.load(f)))

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(on_chip, jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    weight_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                       for a in jax.tree_util.tree_leaves(params))
    assert 6.6e9 < weight_bytes < 6.65e9
    model = HybridEngineModel(params, cfg, max_batch_size=32)
    assert (4096, 16) + model.kv_token_shape == HYBRID_POOL
    state = {name: jax.ShapeDtypeStruct((33,) + tuple(shape), dtype,
                                        sharding=one_chip)
             for name, (shape, dtype) in model.state_shapes.items()}
    compiled = model._build_decode_paged(32, 64, 16).lower(
        jax.ShapeDtypeStruct(HYBRID_POOL, jnp.bfloat16, sharding=one_chip),
        state, params, jax.ShapeDtypeStruct((32, 6 + 64), jnp.int32,
                                            sharding=one_chip),
        jax.ShapeDtypeStruct((model._ids_width(32) + 3,), jnp.int32,
                             sharding=one_chip)).compile()
    memory = compiled.memory_analysis()
    assert "tpu_custom_call" in compiled.as_text()
    pools = int(np.prod(HYBRID_POOL)) * 2 + 33 * (
        3 * 64 * 128 * 128 * 4 + 3 * 3 * 3 * 8192 * 2)
    # Both pools, at the chip's layout (the tails' rows pad a little).
    assert pools <= memory.alias_size_in_bytes < 1.01 * pools
    assert memory.temp_size_in_bytes < 200e6


# ---------------------------------------------------------------------------
# the train step's flash kernels (`ops/flash_attention.py`), compiled for
# the chip at the shapes the train cells and `flash_tiles`' entries name.
# They live here because a described topology belongs in one test file:
# the worker that runs it loads the TPU's library, and only one may.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("shape", [
    (8, 32, 2048, 64),      # smollm2-1.7b.train.seq2k
    (8, 16, 1024, 128),     # the other swept shape
    (4, 16, 2048, 128),     # olmo-1b's, by the rule
    (1, 8, 8192, 128),      # a long sequence, by the rule
])
def test_flash_kernels_compile_for_v5e_at_the_chosen_tiles(
        one_chip, no_compile_cache, shape):
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.attention import flash_tiles
    from ray_tpu.ops.flash_attention import flash_mha

    tiles = flash_tiles(shape[2], shape[3])
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v, do):
        return jnp.sum(flash_mha(q, k, v, tiles).astype(jnp.float32)
                       * do.astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x, x).compile().as_text()
    # The device trace names an operation after its kernel, and the
    # benchmark's `flash_attn_roofline` finds the three by "flash".
    for kernel in (f"flash_mha_fwd_block_q_{tiles.block_q}_",
                   f"flash_mha_bwd_dkv_block_k_{tiles.block_k_dkv}_",
                   f"flash_mha_bwd_dq_block_q_{tiles.block_q_dq}_"):
        assert kernel in text, kernel


@pytest.mark.parametrize("policy", ["save_attn", "save_attn_qkv"])
def test_checkpointed_train_step_compiles_for_v5e_with_one_flash_forward(
        one_chip, no_compile_cache, monkeypatch, policy):
    """The gradient of `smollm2-1.7b.train.seq2k`'s layers (two of them,
    a small vocabulary) through the kernels: the chip's compiler is handed
    the forward kernel once a layer, not a second time for the backward,
    and what the layers keep of the attention is the output in whole
    lanes (the kernels' own ``[.., 2048, 64]`` lies in tiles of 128 lanes:
    twice the bytes) and a row of statistics a query."""
    import importlib
    import re

    import jax
    import jax.numpy as jnp

    from ray_tpu.models.transformer import (TransformerConfig, init_params,
                                            lm_loss)

    # `attention()` asks the backend, and the backend here is the CPU.
    monkeypatch.setattr(importlib.import_module("ray_tpu.ops.attention"),
                        "_flash_eligible", lambda q: True)
    cfg = TransformerConfig(
        vocab_size=1024, d_model=2048, n_layers=2, n_heads=32, d_ff=8192,
        max_seq_len=2048, dtype=jnp.bfloat16, remat=True,
        remat_policy=policy)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg)))
    batch = {"tokens": jax.ShapeDtypeStruct((8, 2049), jnp.int32,
                                            sharding=one_chip)}
    text = jax.jit(jax.grad(lambda p, b: lm_loss(p, b, cfg))).lower(
        params, batch).compile().as_text()
    calls = re.findall(r"custom-call\(.*?flash_mha_(fwd|bwd_dkv|bwd_dq)_",
                       text)
    assert sorted(calls) == ["bwd_dkv", "bwd_dq", "fwd"], calls
    # The two layers' kept values, stacked by the scan.
    assert "f32[2,8,32,1,2048]" in text
    assert "bf16[2,8,32,2048,64]" not in text


# ---------------------------------------------------------------------------
# the window-and-global model's kernels (`laguna-s-2.1`), compiled for the
# chip at its published widths: the windowed paged decode kernel at groups
# of 9 query heads a key head and the global one at groups of 6, the
# prefill's windowed grouped flash forward, and the whole decode step.
# ---------------------------------------------------------------------------
GLOBAL_POOL = (8192, 16, 3, 2, 8, 128)      # 1.6 GB in bf16
WINDOW_POOL = (640, 16, 9, 2, 8, 128)       # 0.38 GB


@pytest.mark.parametrize("heads, pool, window, nb_pad", [
    (48, GLOBAL_POOL, None, 512), (72, WINDOW_POOL, 512, 33)],
    ids=["global_group6", "window_group9"])
def test_windowed_grouped_kernel_compiles_for_v5e_over_its_group_pool(
        one_chip, no_compile_cache, heads, pool, window, nb_pad):
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import paged_attention as pa

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def call(q, k, v, pool, tables, positions, layer, starts):
        return pa.paged_decode_attention_kernel(
            q, k, v, pool, tables, positions, layer, window, starts)

    compiled = jax.jit(call).lower(
        spec((16, heads, 128)), spec((16, 8, 128), jnp.bfloat16),
        spec((16, 8, 128), jnp.bfloat16), spec(pool, jnp.bfloat16),
        spec((16, nb_pad), jnp.int32), spec((16,), jnp.int32),
        spec((), jnp.int32), spec((16,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # The device trace tells a window layer's calls from a global one's.
    assert ("paged_window_decode_attention" in text) == (window is not None)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("heads, window, seq", [
    (48, None, 4096), (72, 512, 4096), (72, 512, 1024)],
    ids=["causal_4096", "window_4096", "window_1024"])
def test_prefill_flash_forward_compiles_for_v5e(one_chip, no_compile_cache,
                                                heads, window, seq):
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.flash_attention import prefill_attention_fwd

    def spec(h):
        return jax.ShapeDtypeStruct((h, seq, 128), jnp.bfloat16,
                                    sharding=one_chip)

    text = jax.jit(lambda q, k, v: prefill_attention_fwd(
        q, k, v, window)).lower(spec(heads), spec(8),
                                spec(8)).compile().as_text()
    assert ("flash_prefill_fwd_causal" if window is None
            else f"flash_prefill_fwd_window_{window}") in text
    assert "flash_mha_fwd" not in text          # the train step's name


def test_laguna_decode_step_compiles_for_v5e_with_both_pools_in_place(
        one_chip, no_compile_cache, monkeypatch):
    """The whole (16, 512) decode step at the published widths, the
    kernels steered on: both groups' pools are aliased to the outputs,
    twelve attention kernel calls (3 global, 9 window), eleven of the
    experts' kernel, and beside its arguments
    the program holds tens of megabytes: no copy of a pool or of a
    layer's expert matrices."""
    import json
    import os

    import jax
    import jax.numpy as jnp

    from benchmarks.harness import manifest
    from ray_tpu.models.laguna import init_params
    from ray_tpu.ops import experts as ex
    from ray_tpu.ops import paged_attention as pa
    from ray_tpu.serve.engine import LagunaEngineModel

    monkeypatch.setattr(pa, "kernel_eligible", lambda *heads: True)
    monkeypatch.setattr(ex, "kernel_eligible", lambda t, *widths: t <= 128)
    family = manifest.load_family("laguna")
    with open(os.path.join(manifest.ROOT, "benchmarks", "configs",
                           "laguna-s-2.1.json")) as f:
        cfg = family.model_config(family.widths(json.load(f)))

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(on_chip, jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    weight_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                       for a in jax.tree_util.tree_leaves(params))
    assert 8.6e9 < weight_bytes < 8.7e9
    model = LagunaEngineModel(params, cfg, max_batch_size=16)
    assert (8192, 16) + model.kv_token_shape == GLOBAL_POOL
    assert (640, 16) + model.kv_groups["window"]["kv_shape"] == WINDOW_POOL
    assert model.window_table_blocks(16) == 33
    pools = {"global": jax.ShapeDtypeStruct(GLOBAL_POOL, jnp.bfloat16,
                                            sharding=one_chip),
             "window": jax.ShapeDtypeStruct(WINDOW_POOL, jnp.bfloat16,
                                            sharding=one_chip)}
    compiled = model._build_decode_paged(16, 512, 16).lower(
        pools, params, jax.ShapeDtypeStruct((16, 7 + 512 + 33), jnp.int32,
                                            sharding=one_chip),
        jax.ShapeDtypeStruct((model._ids_width(16) + 3,), jnp.int32,
                             sharding=one_chip)).compile()
    memory, text = compiled.memory_analysis(), compiled.as_text()
    assert text.count("paged_window_decode_attention") >= 9
    # Eleven expert layers, each one call of the grouped FFN kernel.
    assert text.count("held_experts_ffn_decode") >= 11
    both = (int(np.prod(GLOBAL_POOL)) + int(np.prod(WINDOW_POOL))) * 2
    assert both <= memory.alias_size_in_bytes < 1.01 * both
    assert memory.temp_size_in_bytes < 100e6


# ---------------------------------------------------------------------------
# the held experts' grouped gated-FFN kernel (`ops/experts.py`), compiled
# for the chip at both sparse cells' decode shapes, at a short prompt's
# whole tile of 128 rows and in float32, with the block `f_block` chooses.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("tokens, top_k, n_held, d, f, dtype", [
    (16, 10, 32, 3072, 1024, "bfloat16"),   # repo-context's decode step
    (16, 8, 40, 4096, 1280, "bfloat16"),    # decode-wide's
    (1, 10, 32, 3072, 1024, "bfloat16"),    # a lone row: ten tiles, not 32
    (128, 8, 40, 4096, 1280, "bfloat16"),   # a short prompt: one whole tile
    (8, 10, 32, 3072, 1024, "float32"),     # float32 weights: tiles of 8
], ids=["repo_context_decode", "decode_wide_decode", "one_row",
        "a_prompt_of_128", "float32_weights"])
def test_experts_kernel_compiles_for_v5e_at_the_cells_shapes(
        one_chip, no_compile_cache, monkeypatch, tokens, top_k, n_held, d,
        f, dtype):
    """`held_experts_ffn` whole, the kernel steered on (the backend here
    is the CPU): beside its arguments the program holds no copy, slice or
    conversion of an expert stack (0.2 GB a matrix at either cell)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import experts as ex

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ex.kernel_eligible(tokens, d, f, dtype)

    def spec(shape, dtype=dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(functools.partial(
        ex.held_experts_ffn, held=(n_held, 2 * n_held))).lower(
        spec((tokens, d), jnp.float32), spec((tokens, top_k), jnp.int32),
        spec((tokens, top_k), jnp.float32), spec((n_held, d, f)),
        spec((n_held, d, f)), spec((n_held, f, d))).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "held_experts_ffn_decode" in text
    assert "while" not in text                  # no scan beside it
    stack = n_held * d * f * jnp.dtype(dtype).itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < stack / 64


# The prompt's kernel (`held_experts_ffn_prefill`) at both sparse cells'
# prefill buckets, with the tiles `prefill_tiles` chooses.
@pytest.mark.parametrize("tokens, top_k, n_held, d, f", [
    (1024, 10, 32, 3072, 1024), (2048, 10, 32, 3072, 1024),
    (4096, 10, 32, 3072, 1024),             # repo-context's three buckets
    (256, 8, 40, 4096, 1280), (512, 8, 40, 4096, 1280),     # decode-wide's
], ids=["repo_context_1024", "repo_context_2048", "repo_context_4096",
        "decode_wide_256", "decode_wide_512"])
def test_experts_prompt_kernel_compiles_for_v5e_at_the_cells_buckets(
        one_chip, no_compile_cache, monkeypatch, tokens, top_k, n_held, d,
        f):
    """`held_experts_ffn` whole for a prompt, the kernel steered on: the
    chip's compiler takes the rows' copies (a row is whole sublanes), the
    tables in SMEM (40,960 tokens and as many weights at 4,096 rows) and
    the ``[T, d]`` float32 sum in VMEM beside the weight blocks; beside
    its arguments the program holds nothing of ``[rows, d]`` (the rows
    the shapes must allow are 11 times T at 4,096): no scan, no copy of
    an expert stack, and temporaries of a few ``[T, d]``."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import experts as ex

    dtype = "bfloat16"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ex.kernel_eligible(tokens, d, f, dtype)

    def spec(shape, dtype=dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(functools.partial(
        ex.held_experts_ffn, held=(n_held, 2 * n_held))).lower(
        spec((tokens, d), jnp.float32), spec((tokens, top_k), jnp.int32),
        spec((tokens, top_k), jnp.float32), spec((n_held, d, f)),
        spec((n_held, d, f)), spec((n_held, f, d))).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "held_experts_ffn_prefill" in text
    assert "held_experts_ffn_decode" not in text
    assert "while" not in text                  # no scan beside it
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 3 * tokens * d * 4 + (1 << 20)


# ---------------------------------------------------------------------------
# keys wider than values, 4 key/value heads, a sink: the two layer kinds
# of `mimo-v2.5.serve.doc-context` (`ops/paged_attention.py`, `kv_row`'s
# pools; `ops/flash_attention.py`, the prefill's forward), compiled for
# the chip: the paged kernel over each group's pool, the prefill's
# forward at keys of 192 over values of 128, and the whole decode step.
# ---------------------------------------------------------------------------
MIMO_GLOBAL_POOL = (10240, 16, 2, 3, 4, 128)    # 1.0 GB in bf16
MIMO_WINDOW_POOL = (160, 16, 5, 3, 8, 128)      # 0.08 GB
# The global group as the engine holds it since PR 58: 4 key/value heads
# do not fill a float32 tile, so the pool lies by planes (layer, slot x
# head), a layer's page of 48 KB in one piece.
MIMO_GLOBAL_PLANES = (10240, 2, 3 * 4, 16, 128)


@pytest.mark.parametrize("hkv, pool, window, nb_pad", [
    (4, MIMO_GLOBAL_POOL, None, 512), (8, MIMO_WINDOW_POOL, 128, 9)],
    ids=["global_4_key_heads", "window_8_key_heads_sink"])
def test_wide_key_kernel_compiles_for_v5e_over_its_group_pool(
        one_chip, no_compile_cache, hkv, pool, window, nb_pad):
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import paged_attention as pa

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def call(q, k, v, pool, tables, positions, layer, starts, sink):
        return pa.paged_decode_attention_kernel(
            q, k, v, pool, tables, positions, layer, window,
            None if window is None else starts,
            None if window is None else sink)

    compiled = jax.jit(call).lower(
        spec((16, 64, 192)), spec((16, hkv, 192), jnp.bfloat16),
        spec((16, hkv, 128), jnp.bfloat16), spec(pool, jnp.bfloat16),
        spec((16, nb_pad), jnp.int32), spec((16,), jnp.int32),
        spec((), jnp.int32), spec((16,), jnp.int32),
        spec((64,))).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert ("paged_window_decode_attention" in text) == (window is not None)
    # The pool is an operand as it stands at 4 key/value heads too: no
    # converted copy of it.
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("hkv, window, name", [
    (4, None, "flash_prefill_fwd_causal"),
    (8, 128, "flash_prefill_fwd_window_128")],
    ids=["causal_4_key_heads", "window_128_sink"])
def test_prefill_forward_compiles_for_v5e_at_keys_of_192(
        one_chip, no_compile_cache, hkv, window, name):
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.flash_attention import prefill_attention_fwd

    def spec(h, d):
        return jax.ShapeDtypeStruct((h, 8192, d), jnp.bfloat16,
                                    sharding=one_chip)

    sink = jax.ShapeDtypeStruct((64,), jnp.float32, sharding=one_chip)
    compiled = jax.jit(lambda q, k, v, b: prefill_attention_fwd(
        q, k, v, window, None if window is None else b)).lower(
        spec(64, 192), spec(hkv, 192), spec(hkv, 128), sink).compile()
    assert name in compiled.as_text()
    # Beside the float32 output: q and k filled up to whole lanes.
    assert compiled.memory_analysis().temp_size_in_bytes < 400e6


def test_mimo_decode_step_compiles_for_v5e_with_both_pools_in_place(
        one_chip, no_compile_cache, monkeypatch):
    """The whole (16, 512) decode step at the published widths as the
    chip dispatches it: both groups' pools are aliased to the outputs,
    seven attention kernel calls (2 global on 4 key/value heads, 5 window
    on 8 with the sink), six of the experts' kernel, and beside its
    arguments the program holds a few megabytes."""
    import json
    import os

    import jax
    import jax.numpy as jnp

    from benchmarks.harness import manifest
    from ray_tpu.models.mimo_v2 import init_params
    from ray_tpu.serve.engine import MimoEngineModel

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    family = manifest.load_family("mimo_v2")
    with open(os.path.join(manifest.ROOT, "benchmarks", "configs",
                           "mimo-v2.5.json")) as f:
        cfg = family.model_config(family.widths(json.load(f)))

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(on_chip, jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    weight_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                       for a in jax.tree_util.tree_leaves(params))
    assert 6.8e9 < weight_bytes < 6.9e9
    model = MimoEngineModel(params, cfg, max_batch_size=16)
    assert model._attn_inplace
    assert (10240, 16) + model.kv_token_shape == MIMO_GLOBAL_POOL
    assert (160, 16) + model.kv_groups["window"]["kv_shape"] == \
        MIMO_WINDOW_POOL
    assert model.window_table_blocks(16) == 9
    assert model.kv_planes == {"global": True, "window": False}
    pools = {"global": jax.ShapeDtypeStruct(MIMO_GLOBAL_PLANES, jnp.bfloat16,
                                            sharding=one_chip),
             "window": jax.ShapeDtypeStruct(MIMO_WINDOW_POOL, jnp.bfloat16,
                                            sharding=one_chip)}
    compiled = model._build_decode_paged(16, 512, 16).lower(
        pools, params, jax.ShapeDtypeStruct((16, 7 + 512 + 9), jnp.int32,
                                            sharding=one_chip),
        jax.ShapeDtypeStruct((model._ids_width(16) + 3,), jnp.int32,
                             sharding=one_chip)).compile()
    memory, text = compiled.memory_analysis(), compiled.as_text()
    assert text.count("paged_window_decode_attention") >= 5
    assert text.count("held_experts_ffn_decode") >= 6
    both = (int(np.prod(MIMO_GLOBAL_POOL))
            + int(np.prod(MIMO_WINDOW_POOL))) * 2
    assert both <= memory.alias_size_in_bytes < 1.01 * both
    assert memory.temp_size_in_bytes < 100e6


# ---------------------------------------------------------------------------
# a chunk of a long prompt (PR 56): the prefill's forward with the first
# query's key and the live keys' count as scalars, and the whole chunk
# program at `mimo-v2.5`'s published widths
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("heads, hkv, dk, keys, window, name", [
    (64, 4, 192, 8192, None, "flash_prefill_fwd_causal"),
    (64, 8, 192, 128 + 1024, 128, "flash_prefill_fwd_window_128"),
    (48, 8, 128, 4096, None, "flash_prefill_fwd_causal"),
    (72, 8, 128, 512 + 1024, 512, "flash_prefill_fwd_window_512")],
    ids=["mimo_global", "mimo_window_sink", "laguna_global",
         "laguna_window"])
def test_a_chunks_forward_compiles_for_v5e_with_its_place_a_scalar(
        one_chip, no_compile_cache, heads, hkv, dk, keys, window, name):
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.flash_attention import prefill_attention_fwd

    def spec(h, s, d):
        return jax.ShapeDtypeStruct((h, s, d), jnp.bfloat16,
                                    sharding=one_chip)

    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    sink = jax.ShapeDtypeStruct((heads,), jnp.float32, sharding=one_chip)
    compiled = jax.jit(lambda q, k, v, b, offset, live: prefill_attention_fwd(
        q, k, v, window, b if dk == 192 and window else None, offset=offset,
        live=live)).lower(
        spec(heads, 1024, dk), spec(hkv, keys, dk), spec(hkv, keys, 128),
        sink, scalar, scalar).compile()
    assert name in compiled.as_text()
    # Beside the float32 output: q and k filled up to whole lanes.
    assert compiled.memory_analysis().temp_size_in_bytes < 100e6


def test_mimo_chunk_program_compiles_for_v5e_beside_both_pools(
        one_chip, no_compile_cache, monkeypatch):
    """A chunk of 1,024 positions of a prompt in the 8,192 bucket at the
    published widths as the chip dispatches it: the pools go in and are
    not copied (what the program holds beside its arguments is the
    positions before the chunk, 50 MB of rows of two layers, and a
    chunk's activations), seven calls of the prefill's forward and six
    of the prompt's experts' kernel."""
    import json
    import os

    import jax
    import jax.numpy as jnp

    from benchmarks.harness import manifest
    from ray_tpu.models.mimo_v2 import init_params
    from ray_tpu.serve.engine import MimoEngineModel

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    family = manifest.load_family("mimo_v2")
    with open(os.path.join(manifest.ROOT, "benchmarks", "configs",
                           "mimo-v2.5.json")) as f:
        cfg = family.model_config(family.widths(json.load(f)))

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(on_chip, jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    model = MimoEngineModel(params, cfg, max_batch_size=16)
    assert (model.prefill_chunk_tokens, model._chunk_tail_tokens()) == \
        (1024, 128)
    pools = {"global": jax.ShapeDtypeStruct(MIMO_GLOBAL_PLANES, jnp.bfloat16,
                                            sharding=one_chip),
             "window": jax.ShapeDtypeStruct(MIMO_WINDOW_POOL, jnp.bfloat16,
                                            sharding=one_chip)}
    compiled = model._build_prefill_chunk(8192, 16).lower(
        pools, params, jax.ShapeDtypeStruct((1024 + 2 + 512 + 8,), jnp.int32,
                                            sharding=one_chip)).compile()
    memory, text = compiled.memory_analysis(), compiled.as_text()
    assert "jit_prefill_chunk" in text
    assert text.count("flash_prefill_fwd_causal") >= 2
    assert text.count("flash_prefill_fwd_window_128") >= 5
    assert text.count("held_experts_ffn_prefill") >= 6
    assert memory.temp_size_in_bytes < 200e6
    # The chunk's rows of both groups and the logits.
    assert memory.output_size_in_bytes < 40e6


# ---------------------------------------------------------------------------
# a pool of 4 key/value heads held by planes (PR 58): the per-head body,
# the cache's writes into such a pool, and `keye-vl-2.0-30b-a3b`'s two
# programs over it at the published widths
# ---------------------------------------------------------------------------
KEYE_PLANES = (17408, 12, 2 * 4, 16, 128)       # 6.8 GB in bf16
KEYE_INDEX_POOL = (17408, 12, 16, 128)


@pytest.mark.parametrize("h, dk, pool, nb_pad, keep, sink", [
    (32, 128, KEYE_PLANES, 1024, False, False),
    (32, 128, KEYE_PLANES, 1024, True, False),
    (64, 192, MIMO_GLOBAL_PLANES, 512, False, False),
    (64, 192, MIMO_GLOBAL_PLANES, 512, True, True)],
    ids=["keye", "keye_keep", "mimo_global_keys_of_192",
         "keys_of_192_keep_sink"])
def test_per_head_kernel_compiles_for_v5e_over_a_pool_held_by_planes(
        one_chip, no_compile_cache, h, dk, pool, nb_pad, keep, sink):
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import paged_attention as pa

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def call(q, k, v, pool, tables, positions, layer, kept, own, logit):
        return pa.paged_decode_attention_kernel(
            q, k, v, pool, tables, positions, layer,
            sink=logit if sink else None, keep=kept if keep else None,
            own_keep=own if keep else None)

    compiled = jax.jit(call).lower(
        spec((16, h, dk)), spec((16, 4, dk), jnp.bfloat16),
        spec((16, 4, 128), jnp.bfloat16), spec(pool, jnp.bfloat16),
        spec((16, nb_pad), jnp.int32), spec((16,), jnp.int32),
        spec((), jnp.int32), spec((16, nb_pad * 16), jnp.bool_),
        spec((16,), jnp.bool_), spec((h,))).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "paged_decode_attention" in text
    # The pool is an operand as it stands; beside it the keep mask's
    # float32 rows, where there is one.
    assert compiled.memory_analysis().temp_size_in_bytes < (
        (8 << 20) if keep else (1 << 20))


@pytest.mark.parametrize("rows", [1024, 256])
def test_the_caches_writes_into_a_planes_pool_compile_for_v5e_in_place(
        one_chip, no_compile_cache, rows):
    """A prefill's range a block at a time with its ragged tail by slots
    (`set_blocks_planes`), a range by slots alone and a copied block:
    the pool is donated and aliased, and no program holds a second."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.serve.engine.kv_cache import _DevicePoolOps

    def spec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    ops = _DevicePoolOps(16, (12, 2, 4, 128))
    pool = spec(KEYE_PLANES, jnp.bfloat16)
    payload = spec((rows, 12, 2, 4, 128), jnp.bfloat16)
    pool_bytes = int(np.prod(KEYE_PLANES)) * 2
    for compiled in (
            ops.set_blocks_planes.lower(
                pool, spec((rows // 16,)), payload, spec((16,)), spec((16,)),
                spec(())).compile(),
            ops.scatter_planes.lower(pool, spec((rows,)), spec((rows,)),
                                     payload).compile(),
            ops.copy_block.lower(pool, spec(()), spec(())).compile()):
        memory = compiled.memory_analysis()
        assert pool_bytes <= memory.alias_size_in_bytes < 1.01 * pool_bytes
        assert memory.temp_size_in_bytes < 64e6


def _abstract_keye_model(one_chip, monkeypatch):
    import json
    import os

    import jax

    from benchmarks.harness import manifest
    from ray_tpu.models.keye_vl2 import init_params
    from ray_tpu.serve.engine import KeyeEngineModel

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    family = manifest.load_family("keye_vl2")
    with open(os.path.join(manifest.ROOT, "benchmarks", "configs",
                           "keye-vl-2.0-30b-a3b.json")) as f:
        cfg = family.model_config(family.widths(json.load(f)))

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(on_chip, jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    model = KeyeEngineModel(params, cfg, max_batch_size=16)
    assert model._attn_inplace and model.kv_planes == {"global": True}
    assert model.kv_token_shape == (12, 2, 4, 128)
    return model, params


def test_keye_programs_compile_for_v5e_with_the_planes_pool_in_place(
        one_chip, no_compile_cache, monkeypatch):
    """The (16, 1024) decode step and the 16,384-key chunk program at the
    published widths as the chip dispatches them, the KV pool held by
    planes: the step aliases both pools to its outputs and holds a few
    megabytes beside its arguments; the chunk reads the pools where they
    lie, a layer's pages at a time, and holds no copy of a pool."""
    import jax
    import jax.numpy as jnp

    model, params = _abstract_keye_model(one_chip, monkeypatch)

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pools = {"global": spec(KEYE_PLANES), "index": spec(KEYE_INDEX_POOL)}
    both = (int(np.prod(KEYE_PLANES)) + int(np.prod(KEYE_INDEX_POOL))) * 2
    compiled = model._build_decode_paged(16, 1024, 16).lower(
        pools, params, spec((16, 5 + 1024), jnp.int32),
        spec((model._ids_width(16) + 3,), jnp.int32)).compile()
    memory, text = compiled.memory_analysis(), compiled.as_text()
    assert text.count("paged_decode_attention") >= 12
    assert text.count("paged_index_scores") >= 12
    assert both <= memory.alias_size_in_bytes < 1.01 * both
    assert memory.temp_size_in_bytes < 100e6
    compiled = model._build_prefill_chunk(16384, 16).lower(
        pools, params, spec((1024 + 2 + 1024,), jnp.int32)).compile()
    memory, text = compiled.memory_analysis(), compiled.as_text()
    assert "jit_prefill_chunk" in text
    assert text.count("flash_prefill_fwd_selected") >= 12
    # The index keys of the positions before the chunk (50 MB), a
    # layer's keys and values a head at a time, a chunk's activations.
    assert memory.temp_size_in_bytes < 300e6
    assert memory.output_size_in_bytes < 40e6


# ---------------------------------------------------------------------------
# a latent pool (PR 61): the walk's latent body and
# `gigachat3.5-432b-a28b`'s programs over it at the published widths
# ---------------------------------------------------------------------------
GIGACHAT_POOL = (34816, 1, 5, 16, 128)          # 0.71 GB in bf16


def _gigachat_on(one_chip, monkeypatch):
    """The family's model over described parameters, steered onto the
    chip's branches."""
    import json
    import os

    import jax
    import jax.numpy as jnp

    from benchmarks.harness import manifest
    from ray_tpu.models.gigachat35 import init_params
    from ray_tpu.serve.engine import GigaChatEngineModel

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    family = manifest.load_family("gigachat3_5")
    with open(os.path.join(manifest.ROOT, "benchmarks", "configs",
                           "gigachat3.5-432b-a28b.json")) as f:
        cfg = family.model_config(family.widths(json.load(f)))

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(on_chip, jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)))
    model = GigaChatEngineModel(params, cfg, max_batch_size=32)
    assert model._attn_inplace and model.kv_token_shape == GIGACHAT_POOL[1:3] \
        + GIGACHAT_POOL[4:]
    pool = jax.ShapeDtypeStruct(GIGACHAT_POOL, jnp.bfloat16,
                                sharding=one_chip)
    state = {name: jax.ShapeDtypeStruct((32,) + tuple(shape), dt,
                                        sharding=one_chip)
             for name, (shape, dt) in model.state_shapes.items()}
    return model, params, pool, state


def test_latent_kernel_compiles_for_v5e_at_the_published_widths(
        one_chip, no_compile_cache):
    """32 rows of 64 query heads of 640 over a table of 1,024 blocks of a
    pool of 576-value rows held in 5 planes: the walk's kernel under the
    latent body's name, the pool not copied."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import latent_attention as la

    def spec(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(lambda q, row, pool, tables, positions:
                       la.paged_latent_decode_attention(
                           q, row, pool, tables, positions, jnp.int32(0),
                           512, 0.1, interpret=False)).lower(
        spec(32, 64, 640), spec(32, 640, dtype=jnp.bfloat16),
        spec(*GIGACHAT_POOL, dtype=jnp.bfloat16),
        spec(32, 1024, dtype=jnp.int32),
        spec(32, dtype=jnp.int32)).compile()
    assert "paged_latent_decode_attention" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 20e6


def test_gigachat_decode_program_compiles_for_v5e_in_place(
        one_chip, no_compile_cache, monkeypatch):
    """A full step of 32 rows over the 1,024-block table bucket at the
    published widths: one call of the latent walk, four of the decode
    experts' kernel (d 7,168 x f 2,048), both pools donated and neither
    copied."""
    import jax
    import jax.numpy as jnp

    model, params, pool, state = _gigachat_on(one_chip, monkeypatch)
    compiled = model._build_decode_paged(32, 1024, 16).lower(
        pool, state, params,
        jax.ShapeDtypeStruct((32, 6 + 1024), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((model._ids_width(32) + 3,), jnp.int32,
                             sharding=one_chip)).compile()
    memory, text = compiled.memory_analysis(), compiled.as_text()
    assert text.count("paged_latent_decode_attention") >= 1
    assert text.count("held_experts_ffn_decode") >= 4
    assert memory.temp_size_in_bytes < 100e6


def test_gigachat_chunk_program_compiles_for_v5e_beside_both_pools(
        one_chip, no_compile_cache, monkeypatch):
    """A chunk of 1,024 positions of a prompt in the 16,384 bucket: the
    expanded keys and values of 16,384 rows a head are what the program
    holds beside its arguments (1.2 GB), one call of the causal forward
    from an offset, four expert layers through the prompt's kernel."""
    import jax
    import jax.numpy as jnp

    model, params, pool, state = _gigachat_on(one_chip, monkeypatch)
    compiled = model._build_prefill_chunk(16384, 16).lower(
        pool, state, params,
        jax.ShapeDtypeStruct((1024 + 3 + 1024,), jnp.int32,
                             sharding=one_chip)).compile()
    memory, text = compiled.memory_analysis(), compiled.as_text()
    assert "jit_prefill_chunk" in text
    assert text.count("flash_prefill_fwd_causal") >= 1
    assert text.count("held_experts_ffn_prefill") >= 4
    assert memory.temp_size_in_bytes < 1.5e9
    # The chunk's latent rows, the state it ended on and the logits.
    assert memory.output_size_in_bytes < 40e6


@pytest.mark.parametrize("pages,with_counts_a_head", [(512, True),
                                                      (256, False)])
def test_head_walk_compiles_for_v5e_over_a_head_major_pool(
        one_chip, no_compile_cache, monkeypatch, pages, with_counts_a_head):
    """The block-selecting model's decode attention at its cell's widths:
    32 query heads over 2 key/value heads of 128, a head-major planes
    pool of 24,576 blocks, a row's chosen pages a head (512 at the most)
    or one table for both: two calls of the paged walk's kernel under
    its own name, the pool an operand as it stands."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import block_sparse_attention as bsa

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    tables = (8, 2, pages) if with_counts_a_head else (8, pages)
    counts = (8, 2) if with_counts_a_head else (8,)
    compiled = jax.jit(bsa.head_walk_attention).lower(
        spec((8, 32, 128)), spec((8, 2, 128), jnp.bfloat16),
        spec((8, 2, 128), jnp.bfloat16),
        spec((24576, 2, 4, 16, 128), jnp.bfloat16),
        spec(tables, jnp.int32), spec(counts, jnp.int32),
        spec((), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2
    assert bsa.DECODE_KERNEL_NAME in text
    assert compiled.memory_analysis().temp_size_in_bytes < (1 << 20)


def test_lightning_step_compiles_for_v5e_in_place_over_the_state_pool(
        one_chip, no_compile_cache, monkeypatch):
    """The block-selecting model's six lightning layers' decode steps at
    its cell's widths (6 slots, 32 heads of 128 x 128 float32: 75 MB of
    states), one after another over a donated pool: six calls of the
    step's kernel under its own name, the pool their operand and their
    result as it stands (no layer copied out or put back)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import lightning_attention as la

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def spec(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    def six_layers(pool, q, k, v, g):
        outs = []
        for layer in range(6):
            o, pool = la.lightning_step_in_pool(pool, layer, q, k, v, g)
            outs.append(o)
        return jnp.stack(outs), pool

    compiled = jax.jit(six_layers, donate_argnums=(0,)).lower(
        spec((6, 6, 32, 128, 128)), spec((6, 32, 128)), spec((6, 32, 128)),
        spec((6, 32, 128)), spec((6, 32))).compile()
    text, memory = compiled.as_text(), compiled.memory_analysis()
    assert text.count("tpu_custom_call") == 6
    assert la.STEP_KERNEL_NAME in text
    assert memory.alias_size_in_bytes >= 6 * 6 * 32 * 128 * 128 * 4
    assert memory.temp_size_in_bytes < (4 << 20)
