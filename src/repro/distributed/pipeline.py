"""GPipe-style pipeline parallelism over a 'stage' mesh axis.

The production meshes in this repo are (data, model) / (pod, data,
model); PP is the optional third parallelism dimension for meshes that
add a 'stage' axis (DESIGN.md §5).  Implementation is jax-native:
``shard_map`` over the stage axis + ``lax.ppermute`` to hand
activations to the next stage, with the classic GPipe schedule —
n_micro + n_stages - 1 ticks, bubble fraction (S-1)/(M+S-1).

``gpipe_forward`` is deliberately minimal (single-activation stage
functions) — it is the substrate demonstrator exercised by
``examples/pipeline_demo.py`` and its test; wiring it under the
transformer's period scan is mechanical (each period = one stage).
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

Array = jax.Array


def gpipe_forward(stage_fn: Callable[[Any, Array], Array],
                  stage_params: Any, xs: Array, *, mesh: Mesh,
                  axis_name: str = "stage") -> Array:
    """Run microbatches through pipeline stages.

    stage_fn:     (params_of_one_stage, activation) -> activation
    stage_params: pytree with leading axis n_stages (sharded on `axis_name`)
    xs:           (n_micro, ...) microbatch activations fed to stage 0
    returns:      (n_micro, ...) outputs of the LAST stage.
    """
    n_stages = mesh.shape[axis_name]
    n_micro = xs.shape[0]
    assert n_micro >= 1

    def per_stage(params_local, xs_local):
        params_local = jax.tree_util.tree_map(lambda a: a[0], params_local)
        stage = jax.lax.axis_index(axis_name)
        ticks = n_micro + n_stages - 1

        def tick(t, state):
            carry, ys = state
            # stage 0 consumes a fresh microbatch; others take the carry
            feed = xs_local[jnp.minimum(t, n_micro - 1)]
            inp = jnp.where(stage == 0, feed, carry)
            out = stage_fn(params_local, inp)
            # the last stage finishes microbatch t-(S-1) at tick t
            idx = t - (n_stages - 1)
            write = (stage == n_stages - 1) & (idx >= 0)
            ys = jnp.where(
                write,
                jax.lax.dynamic_update_index_in_dim(
                    ys, out, jnp.maximum(idx, 0), 0),
                ys)
            # hand the activation to the next stage (non-cyclic shift)
            carry = jax.lax.ppermute(
                out, axis_name,
                [(i, i + 1) for i in range(n_stages - 1)])
            return carry, ys

        carry0 = jnp.zeros_like(xs_local[0])
        ys0 = jnp.zeros_like(xs_local)
        _, ys = jax.lax.fori_loop(0, ticks, tick, (carry0, ys0))
        return ys[None]          # (1, n_micro, ...) per stage

    stacked = jax.shard_map(
        per_stage, mesh=mesh,
        in_specs=(P(axis_name), P()),
        out_specs=P(axis_name),
        check_vma=False,
    )(stage_params, xs)
    return stacked[-1]           # last stage's outputs


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    """GPipe bubble overhead: (S-1) / (M+S-1)."""
    return (n_stages - 1) / (n_micro + n_stages - 1)
