"""Ring attention == plain attention, forward and backward; the flash
kernels' tiles and the kernels themselves (interpreted: no chip here)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P


@pytest.fixture(scope="module")
def mesh():
    from ray_tpu.parallel import make_mesh
    return make_mesh((2, 1, 2, 2), devices=jax.devices("cpu")[:8])


def _rand_qkv(key, b=2, s=32, h=4, d=8):
    ks = jax.random.split(key, 3)
    shape = (b, s, h, d)
    return tuple(jax.random.normal(k, shape, jnp.float32) for k in ks)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_matches_plain_forward(mesh, causal):
    from ray_tpu.ops import plain_attention, ring_attention

    q, k, v = _rand_qkv(jax.random.PRNGKey(0))
    sharding = NamedSharding(mesh, P("dp", "sp", "tp", None))
    qs, ks, vs = (jax.device_put(x, sharding) for x in (q, k, v))

    ref = plain_attention(q, k, v, causal=causal)
    with jax.set_mesh(mesh) if hasattr(jax, "set_mesh") else _null():
        out = jax.jit(
            lambda a, b_, c: ring_attention(a, b_, c, mesh=mesh,
                                            causal=causal))(qs, ks, vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ring_gradients_match(mesh):
    from ray_tpu.ops import plain_attention, ring_attention

    q, k, v = _rand_qkv(jax.random.PRNGKey(1))
    sharding = NamedSharding(mesh, P("dp", "sp", "tp", None))
    qs, ks, vs = (jax.device_put(x, sharding) for x in (q, k, v))

    def loss_plain(q, k, v):
        return plain_attention(q, k, v, causal=True).sum()

    def loss_ring(q, k, v):
        return ring_attention(q, k, v, mesh=mesh, causal=True).sum()

    g_ref = jax.grad(loss_plain, argnums=(0, 1, 2))(q, k, v)
    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(qs, ks, vs)
    for a, b in zip(g_ref, g_ring):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# the flash kernels: which tiles, handed on as chosen, and what they compute
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("seq_len,head_dim", [
    (2048, 64), (1024, 128), (2048, 128), (4096, 64), (384, 128),
    (8192, 128), (128, 64), (640, 128)])
def test_flash_tiles_fit_the_sequence(seq_len, head_dim):
    import dataclasses
    import importlib

    A = importlib.import_module("ray_tpu.ops.attention")
    tiles = A.flash_tiles(seq_len, head_dim)
    tiles.check(seq_len)        # what the kernels' wrappers would refuse
    sizes = dataclasses.asdict(tiles)
    assert all(b % 128 == 0 and seq_len % b == 0 for b in sizes.values())
    for major, minor in (("block_k_major", "block_k"),
                         ("block_q_major_dkv", "block_q_dkv"),
                         ("block_k_major_dq", "block_k_dq")):
        assert sizes[major] % sizes[minor] == 0, (major, minor)
    if (seq_len, head_dim) in ((2048, 64), (1024, 128)):
        # the two shapes a sweep on the chip measured: its entry, as is
        assert tiles is A._SWEPT_TILES[(seq_len, head_dim)]
    else:
        assert (seq_len, head_dim) not in A._SWEPT_TILES
        assert tiles == A._tiles_by_rule(seq_len)


def test_flash_tiles_check_refuses_what_does_not_tile():
    from ray_tpu.ops.flash_attention import FlashTiles

    good = dict(block_q=256, block_k_major=512, block_k=256,
                block_k_dkv=256, block_q_major_dkv=512, block_q_dkv=256,
                block_q_dq=256, block_k_major_dq=512, block_k_dq=256)
    FlashTiles(**good).check(1024)
    for bad in (dict(block_q=192), dict(block_k=384), dict(block_q_dq=768),
                dict(block_k_major_dq=256, block_k_dq=512)):
        with pytest.raises(ValueError):
            FlashTiles(**{**good, **bad}).check(1024)


def test_flash_attention_tpu_hands_the_chosen_tiles_to_the_kernel(
        monkeypatch):
    import importlib

    A = importlib.import_module("ray_tpu.ops.attention")
    seen = []

    def kernel(q, k, v, tiles):
        seen.append((q.shape, tiles))
        return q

    monkeypatch.setattr(A, "flash_mha", kernel)
    for s, d in ((2048, 64), (1024, 128), (384, 128)):
        x = jnp.zeros((1, s, 2, d), jnp.bfloat16)
        assert A.flash_attention_tpu(x, x, x).shape == x.shape
        shape, tiles = seen[-1]
        assert shape == (1, 2, s, d)            # the kernels' [B, H, S, D]
        assert tiles == A.flash_tiles(s, d)
    assert seen[0][1] is A._SWEPT_TILES[(2048, 64)]


# Tiles that put every case of the walk over minor tiles on the grid at
# S = 512: minors skipped above the diagonal, crossed by it, clear of it;
# majors of the whole sequence and of a part; unequal rows and columns.
_WALKS = {
    "minors_in_one_major": (256, 512, 128, 256, 512, 128, 256, 512, 128),
    "majors_and_minors": (128, 256, 128, 128, 256, 128, 128, 256, 128),
    "wide_rows": (512, 256, 256, 512, 256, 128, 512, 128, 128),
    "one_tile": (512, 512, 512, 512, 512, 512, 512, 512, 512),
}


@pytest.mark.parametrize("head_dim", [64, 128])
@pytest.mark.parametrize("walk", sorted(_WALKS))
def test_flash_kernels_match_plain_attention(walk, head_dim):
    """Output and the three gradients of the interpreted kernels against
    `plain_attention`, in float32 so that only the algorithm differs."""
    from ray_tpu.ops import plain_attention
    from ray_tpu.ops.flash_attention import FlashTiles, flash_mha

    tiles = FlashTiles(*_WALKS[walk])
    tiles.check(512)
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    q, k, v, do = (jax.random.normal(kk, (1, 2, 512, head_dim),
                                     jnp.float32) for kk in ks)

    def flash(q, k, v):
        return flash_mha(q, k, v, tiles, True)

    def plain(q, k, v):
        return plain_attention(*(t.transpose(0, 2, 1, 3)
                                 for t in (q, k, v))).transpose(0, 2, 1, 3)

    got = (flash(q, k, v),) + jax.grad(
        lambda *a: jnp.sum(flash(*a) * do), argnums=(0, 1, 2))(q, k, v)
    want = (plain(q, k, v),) + jax.grad(
        lambda *a: jnp.sum(plain(*a) * do), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip(("o", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4, err_msg=name)


# ---------------------------------------------------------------------------
# what a checkpointed layer keeps of its attention, by the path it took
# ---------------------------------------------------------------------------
_B, _S, _H = 1, 256, 2


def _checkpointed_layer(policy, head_dim, flash, monkeypatch,
                        checkpoint=True):
    """``layer(x, lp)`` through `models.transformer._layer`, checkpointed
    with the tree's own policy as `backbone` does it, and its arguments;
    with `flash` the dispatch is steered onto the kernels, interpreted."""
    import importlib

    from ray_tpu.models import transformer as T
    from ray_tpu.ops.flash_attention import flash_mha
    from ray_tpu.ops.rotary import rotary_freqs

    if flash:
        A = importlib.import_module("ray_tpu.ops.attention")
        monkeypatch.setattr(A, "_flash_eligible", lambda q: True)
        monkeypatch.setattr(
            A, "flash_mha", lambda q, k, v, tiles: flash_mha(q, k, v, tiles,
                                                             True))
    cfg = T.TransformerConfig(
        vocab_size=64, d_model=_H * head_dim, n_layers=1, n_heads=_H,
        d_ff=128, max_seq_len=_S, dtype=jnp.float32, remat=checkpoint,
        remat_policy=policy)
    lp = jax.tree.map(lambda a: a[0],
                      T.init_params(jax.random.PRNGKey(3), cfg)["layers"])
    x = jax.random.normal(jax.random.PRNGKey(4), (_B, _S, cfg.d_model),
                          jnp.float32)
    cos, sin = rotary_freqs(head_dim, _S, cfg.rope_theta)

    # A function of its own a case: `jax.checkpoint` keeps the trace of a
    # function it has seen, whichever path `attention()` took in it.
    def fn(*args):
        return T._layer(*args)

    if checkpoint:
        fn = T._checkpoint_layer(fn, policy)
    return (lambda x, lp: fn(x, lp, cfg, None, False, cos, sin, None)[0],
            x, lp)


def _grads(layer):
    return jax.grad(lambda x, lp: jnp.sum(jnp.square(layer(x, lp))),
                    argnums=(0, 1))


def _pallas_call_names(jaxpr):
    """The `name` of every `pallas_call` in `jaxpr` and its sub-jaxprs."""
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names.extend(_pallas_call_names(sub))
    return names


_POLICIES = ["save_attn", "save_attn_qkv"]


@pytest.mark.parametrize("head_dim", [64, 128])
@pytest.mark.parametrize("policy", _POLICIES)
def test_checkpointed_layer_runs_each_flash_kernel_once(
        policy, head_dim, monkeypatch):
    """The backward of a checkpointed layer holds no second forward
    kernel: the VJP's residuals are the saved names."""
    layer, x, lp = _checkpointed_layer(policy, head_dim, True, monkeypatch)
    names = _pallas_call_names(
        jax.make_jaxpr(_grads(layer))(x, lp).jaxpr)
    kinds = sorted(n.split("_block")[0] for n in names)
    assert kinds == ["flash_mha_bwd_dkv", "flash_mha_bwd_dq",
                     "flash_mha_fwd"], names


@pytest.mark.parametrize("head_dim", [64, 128])
@pytest.mark.parametrize("policy", _POLICIES)
def test_checkpointed_flash_layer_gradients_are_the_whole_layers(
        policy, head_dim, monkeypatch):
    """Keeping the kernels' output and statistics changes nothing the
    layer computes: the gradients of the layer without `jax.checkpoint`."""
    layer, x, lp = _checkpointed_layer(policy, head_dim, True, monkeypatch)
    whole, _, _ = _checkpointed_layer(policy, head_dim, True, monkeypatch,
                                      checkpoint=False)
    got = _grads(layer)(x, lp)
    want = _grads(whole)(x, lp)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("head_dim", [64, 128])
@pytest.mark.parametrize("policy", _POLICIES)
@pytest.mark.parametrize("path", ["flash", "plain"])
def test_checkpointed_layer_saves_one_copy_of_the_attention_output(
        path, policy, head_dim, monkeypatch):
    """Beside its arguments the layer keeps the names of its policy: on
    the kernels' path their output once, as ``[B, S, H * D]`` (not the
    kernels' ``[B, H, S, D]`` and the caller's ``[B, S, H, D]`` both), with
    the ``[B, H, 1, S]`` statistics; on the plain path the ``[B, S, H, D]``
    output alone, as ever."""
    from jax._src.ad_checkpoint import saved_residuals

    layer, x, lp = _checkpointed_layer(policy, head_dim, path == "flash",
                                       monkeypatch)
    kept = sorted((aval.shape, aval.dtype.name)
                  for aval, why in saved_residuals(layer, x, lp)
                  if not why.startswith(("from the argument",
                                         "from a constant")))
    want = [((_B, _S, _H * head_dim), "float32"),
            ((_B, _H, 1, _S), "float32")]
    if path == "plain":
        want = [((_B, _S, _H, head_dim), "float32")]
    if policy == "save_attn_qkv":
        want.append(((3, _B, _S, _H * head_dim), "float32"))
    assert kept == sorted(want)


class _null:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False
