"""SPMD training-step construction: shard params, build jitted train steps.

This is the seam the reference fills with torch DDP/FSDP wrappers
(`python/ray/train/torch/train_loop_utils.py:74,100 prepare_model`); here a
model is "prepared" by placing its params with NamedShardings and letting the
XLA SPMD partitioner insert all collectives (psum/reduce-scatter/all-gather
over ICI).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P


def shard_pytree(tree, specs, mesh):
    """Place every leaf according to its PartitionSpec."""
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), tree, specs)


def init_sharded(init_fn: Callable, specs, mesh, *args):
    """Run an init function with its outputs materialized directly in sharded
    form (no full replica on any one device)."""
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs)
    return jax.jit(init_fn, out_shardings=shardings)(*args)


def batch_sharding(mesh, *, batch_axis="dp", seq_axis=None):
    """Sharding for a [B, S(+1)] token batch. By default the sequence dim is
    left replicated (the +1 of next-token targets rarely divides the sp axis);
    the in-graph sharding constraints reshard activations over sp."""
    seq = seq_axis if (seq_axis and seq_axis in mesh.axis_names) else None
    return NamedSharding(mesh, P(batch_axis, seq))


def make_train_step(loss_fn: Callable, optimizer,
                    donate: bool = True) -> Callable:
    """loss_fn(params, batch) -> scalar. Returns jitted
    step(params, opt_state, batch) -> (params, opt_state, loss).

    Shardings are inferred from the committed input arrays (params placed via
    `init_sharded`, batch via `batch_sharding`); XLA propagates them through
    the grads and optimizer update, so FSDP/TP/SP need no further wiring.
    """

    import optax

    # The function's name is the device program's: `jit_train_step` in a
    # profile's module line.
    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return jax.jit(train_step, donate_argnums=(0, 1) if donate else ())


def make_eval_step(loss_fn: Callable) -> Callable:
    return jax.jit(loss_fn)
