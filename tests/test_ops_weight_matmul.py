"""A layer's product against a stack of layers' weights
(`ops/weight_matmul.py`).

The Pallas kernel runs here in interpret mode at the dense model's four
``(K, N)`` cut to a few tiles, against `dot` of the bf16-rounded operands
with float32 sums: what XLA's default precision makes of the float32
product on the chip. The kernel compiled for a described v5e at the real
widths, alone and inside the decode and prefill programs, is in
`tests/test_ops_paged_attention.py`, which owns the described topology.
"""

import numpy as np
import pytest

pytestmark = pytest.mark.unit

LAYERS = 5
# `olmo-1b`'s four products (2048 x 6144, 2048 x 2048, 2048 x 16384,
# 8192 x 2048) at an eighth of their widths, in tiles that give each
# several column blocks and, but for one, several blocks of K.
SHAPES = {"wqkv": (256, 768, 128, 256), "wo": (256, 256, 128, 128),
          "w13": (256, 2048, 256, 512), "w2": (1024, 256, 256, 128)}


def _operands(rows, k, n, dtype, seed=0):
    import jax.numpy as jnp

    rng = np.random.default_rng([seed, rows, k, n])
    x = jnp.asarray(rng.normal(size=(rows, k)), jnp.float32)
    stack = jnp.asarray(rng.normal(size=(LAYERS, k, n)) / np.sqrt(k), dtype)
    return x, stack


def _rounded_dot(x, w):
    import jax.numpy as jnp

    return jnp.dot(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("rows", [1, 8, 64, 300])
@pytest.mark.parametrize("name", list(SHAPES))
def test_kernel_matches_the_dot_of_the_rounded_operands(name, rows, dtype):
    """Every layer of the stack, its ends and its middle: the kernel's
    result is the rounded operands' product summed in float32, up to
    the order of the sum over the blocks of K."""
    import jax.numpy as jnp

    from ray_tpu.ops import weight_matmul as wm

    k, n, tk, tn = SHAPES[name]
    x, stack = _operands(rows, k, n, dtype)
    for layer in (0, LAYERS // 2, LAYERS - 1):
        got = wm.stacked_weight_matmul_kernel(
            x, stack, jnp.int32(layer), tk=tk, tn=tn, interpret=True)
        assert got.shape == (rows, n) and got.dtype == jnp.float32
        np.testing.assert_allclose(got, _rounded_dot(x, stack[layer]),
                                   rtol=0, atol=2e-5)


@pytest.mark.parametrize("tm", [16, 128])
def test_rows_past_one_block_go_in_several(tm):
    """300 rows in blocks of 16 and of 128 (the last one padded): every
    row block meets every tile of the layer's matrix."""
    import jax.numpy as jnp

    from ray_tpu.ops import weight_matmul as wm

    k, n, tk, tn = SHAPES["w2"]
    x, stack = _operands(300, k, n, "float32", seed=1)
    got = wm.stacked_weight_matmul_kernel(x, stack, jnp.int32(3), tk=tk,
                                          tn=tn, tm=tm, interpret=True)
    np.testing.assert_allclose(got, _rounded_dot(x, stack[3]), rtol=0,
                               atol=2e-5)


def test_one_block_of_k_writes_without_an_accumulator():
    import jax.numpy as jnp

    from ray_tpu.ops import weight_matmul as wm

    x, stack = _operands(8, 256, 512, "float32", seed=2)
    got = wm.stacked_weight_matmul_kernel(x, stack, jnp.int32(4), tk=256,
                                          tn=128, interpret=True)
    np.testing.assert_array_equal(got, _rounded_dot(x, stack[4]))


def test_off_the_chip_the_product_is_xlas(monkeypatch):
    """On the CPU `stacked_weight_matmul` is ``x @ w_stack[layer]`` in
    float32, a bf16 stack read up; steered on, the same call goes
    through the kernel under the name it was given."""
    import functools

    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import weight_matmul as wm

    x, stack = _operands(8, 256, 384, "float32", seed=3)
    got = wm.stacked_weight_matmul(x, stack, jnp.int32(2))
    np.testing.assert_allclose(got, x @ stack[2], rtol=1e-6, atol=1e-6)
    half = stack.astype(jnp.bfloat16)
    np.testing.assert_allclose(
        wm.stacked_weight_matmul(x, half, jnp.int32(2)),
        x @ half[2].astype(jnp.float32), rtol=1e-6, atol=1e-6)

    monkeypatch.setattr(wm, "kernel_eligible", lambda *a: True)
    monkeypatch.setattr(wm, "stacked_weight_matmul_kernel", functools.partial(
        wm.stacked_weight_matmul_kernel, interpret=True))

    def steered(x, w, i):
        return wm.stacked_weight_matmul(x, w, i,
                                        "stacked_weight_matmul_decode")

    np.testing.assert_allclose(steered(x, stack, jnp.int32(2)),
                               _rounded_dot(x, stack[2]), rtol=0, atol=2e-5)
    assert "stacked_weight_matmul_decode" in str(
        jax.make_jaxpr(steered)(x, stack, jnp.int32(2)))


def test_kernel_eligibility_follows_backend_widths_and_rows(monkeypatch):
    """Off the chip nothing is eligible; on it, whole lanes of a float32
    or bfloat16 stack at no more rows than the sweep's bound: what the
    call can see, no option."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import weight_matmul as wm

    assert not wm.kernel_eligible(8, 2048, 6144, jnp.float32)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for k, n in ((2048, 6144), (2048, 2048), (2048, 16384), (8192, 2048)):
        assert wm.kernel_eligible(8, k, n, jnp.float32)
        assert wm.kernel_eligible(1, k, n, jnp.bfloat16)
        assert wm.kernel_eligible(wm._ROWS_MOST, k, n, jnp.float32)
        assert not wm.kernel_eligible(wm._ROWS_MOST + 1, k, n, jnp.float32)
    assert not wm.kernel_eligible(8, 64, 128, jnp.float32)   # the unit tests'
    assert not wm.kernel_eligible(8, 128, 192, jnp.float32)
    assert not wm.kernel_eligible(8, 2048, 2048, jnp.float16)
    assert not wm.kernel_eligible(8, 2048, 2048, jnp.int8)


@pytest.mark.parametrize("itemsize", [4, 2])
@pytest.mark.parametrize("rows", [1, 8, 64, 256, 1024])
@pytest.mark.parametrize("k, n", [(2048, 6144), (2048, 2048),
                                  (2048, 16384), (8192, 2048)])
def test_tiles_divide_the_matrix_and_fit_the_vmem_budget(k, n, rows,
                                                         itemsize):
    """The tiles the chip runs with: whole lanes that divide the matrix,
    at least 16 of them a call, two in flight inside the weights' budget,
    and the whole kernel inside a v5e core's VMEM at any row count it is
    chosen for."""
    from ray_tpu.ops import weight_matmul as wm

    tk, tn = wm.tiles(k, n, itemsize)
    assert k % tk == 0 and n % tn == 0 and tk % 128 == 0 and tn % 128 == 0
    assert (k // tk) * (n // tn) >= 16
    assert 2 * tk * tn * itemsize <= wm._VMEM_FOR_WEIGHTS
    padded = -(-rows // 16) * 16
    assert wm.vmem_bytes(padded, k, tk, tn, itemsize) <= 100 << 20


def test_the_tiles_of_the_models_four_products():
    """What the sweep on the chip was read against (PR 50)."""
    from ray_tpu.ops import weight_matmul as wm

    assert [wm.tiles(k, n, 4) for k, n in (
        (2048, 6144), (2048, 2048), (2048, 16384), (8192, 2048))] == [
        (1024, 512), (512, 512), (2048, 512), (2048, 512)]
