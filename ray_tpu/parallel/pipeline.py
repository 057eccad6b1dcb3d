"""Pipeline parallelism: GPipe schedule over the ``pp`` mesh axis.

The reference has no pipeline parallelism (SURVEY.md §2.5 — TP/PP delegated
to DeepSpeed integrations); this is the TPU-native design: stages live on
``pp`` mesh slices, microbatch activations flow between neighbors via
``ppermute`` inside a ``shard_map`` that is *manual over pp (and sp)* but
leaves dp/tp to the automatic SPMD partitioner. The whole schedule is a
`lax.scan`, so it is differentiable (backward runs the reverse schedule) and
compiles to a single XLA program.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


def _gpipe_body(stage_params, x, positions, consts, *, stage_fn,
                axis: str, n_micro: int):
    """Runs per pp-rank. stage_params: [1, ...] leaves (this rank's stage);
    x: [B, S(loc), D] activations (batch global/auto over dp); positions:
    [S(loc)] global positions; consts: replicated loop-invariant arrays
    (e.g. rotary tables) passed through to stage_fn."""
    n_stages = lax.axis_size(axis)
    rank = lax.axis_index(axis)
    stage_p = jax.tree.map(lambda a: jnp.squeeze(a, 0), stage_params)

    b = x.shape[0]
    assert b % n_micro == 0, f"batch {b} not divisible by {n_micro} microbatches"
    mb = b // n_micro
    x_mb = x.reshape(n_micro, mb, *x.shape[1:])
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    t_total = n_micro + n_stages - 1

    def step(carry, t):
        recv, outs, aux_sum = carry
        in_idx = jnp.clip(t, 0, n_micro - 1)
        first_stage_in = lax.dynamic_index_in_dim(x_mb, in_idx, 0,
                                                  keepdims=False)
        my_in = jnp.where(rank == 0, first_stage_in, recv)
        y, aux = stage_fn(stage_p, my_in, positions, consts)
        # Collect outputs on the last stage for valid schedule slots.
        out_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
        valid = jnp.logical_and(t >= n_stages - 1, rank == n_stages - 1)
        prev = lax.dynamic_index_in_dim(outs, out_idx, 0, keepdims=False)
        outs = lax.dynamic_update_index_in_dim(
            outs, jnp.where(valid, y, prev), out_idx, 0)
        # Each rank's real compute window is rank <= t < rank + n_micro;
        # outside it the stage chews bubble garbage whose aux must not count.
        in_window = jnp.logical_and(t >= rank, t < rank + n_micro)
        aux_sum = aux_sum + jnp.where(in_window, aux, 0.0)
        recv_next = lax.ppermute(y, axis, perm)
        return (recv_next, outs, aux_sum), None

    recv0 = jnp.zeros((mb, *x.shape[1:]), x.dtype)
    outs0 = jnp.zeros_like(x_mb)
    (_, outs, aux_sum), _ = lax.scan(
        step, (recv0, outs0, jnp.zeros((), jnp.float32)),
        jnp.arange(t_total))
    # Only the last rank holds real outputs; psum replicates them to all pp
    # ranks (the head/loss then runs redundantly — cheap for logits' seq
    # shard, and keeps out_specs uniform).
    outs = lax.psum(outs, axis)
    # One window per (stage, microbatch); the per-call aux formula is
    # token-count invariant, so divide by n_micro to match the
    # non-pipelined objective exactly.
    aux_sum = lax.psum(aux_sum, axis) / n_micro
    return outs.reshape(x.shape), aux_sum


def gpipe(stage_fn: Callable, stage_params, x, positions, consts=(), *,
          mesh, num_microbatches: int, pp_axis: str = "pp",
          sp_axis: str = "sp", param_specs=None):
    """Run `stage_fn(stage_p, x_micro, positions, consts) -> (y, aux)` as a
    pipeline.

    stage_params: pytree with leading [n_stages, ...] on every leaf, sharded
    over `pp_axis`. x: [B, S, D] activations. The shard_map is manual over
    {pp, sp} — inside, the sequence dim is the local sp block and attention
    must use `ring_attention_manual`.
    """
    manual = {pp_axis}
    sp_in_mesh = sp_axis in mesh.axis_names and mesh.shape[sp_axis] > 1
    if sp_in_mesh:
        manual.add(sp_axis)
    seq_axis = sp_axis if sp_in_mesh else None

    if param_specs is None:
        p_specs = jax.tree.map(
            lambda a: P(pp_axis, *(None,) * (a.ndim - 1)), stage_params)
    else:
        p_specs = param_specs
    x_spec = P(None, seq_axis, None)
    pos_spec = P(seq_axis)
    const_specs = jax.tree.map(lambda a: P(*(None,) * a.ndim), consts)

    body = functools.partial(
        _gpipe_body, stage_fn=stage_fn, axis=pp_axis,
        n_micro=num_microbatches)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(p_specs, x_spec, pos_spec, const_specs),
        out_specs=(x_spec, P()),
        axis_names=manual, check_vma=False,
    )(stage_params, x, positions, consts)
