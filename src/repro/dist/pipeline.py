"""Microbatch pipeline parallelism over a ``stage`` mesh axis (GPipe
schedule, shard_map + ppermute).

Each device owns one stage's weights (the leading axis of ``stage_params``
shards over the axis).  Microbatch ``m`` enters stage 0 at step ``m`` and
exits stage ``S-1`` at step ``m + S - 1``; the schedule runs
``n_micro + n_stages - 1`` steps with activations shifting one stage per
step through ``ppermute``.  Bubble fraction: ``(S-1) / (n_micro + S - 1)``.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

__all__ = ["pipeline_apply"]


def pipeline_apply(
    mesh,
    stage_fn: Callable,
    stage_params,
    x: jax.Array,
    *,
    axis: str = "stage",
) -> jax.Array:
    """Run ``x`` (n_micro, mb, ...) through ``n_stages`` pipelined stages.

    ``stage_fn(w, xb) -> yb`` applies one stage to one microbatch;
    ``stage_params`` is a pytree whose leaves carry a leading stage axis of
    size ``mesh.shape[axis]``.  Output shapes must equal input shapes
    (residual-block pipelines).  Returns the (n_micro, mb, ...) outputs,
    replicated — numerically identical to applying the stages sequentially.
    """
    n_stages = mesh.shape[axis]
    n_micro = x.shape[0]
    leading = {leaf.shape[0] for leaf in jax.tree.leaves(stage_params)}
    if leading != {n_stages}:
        raise ValueError(
            f"stage_params leading dims {sorted(leading)} must all equal the "
            f"'{axis}' axis size {n_stages} (one stage per device)"
        )
    last = n_stages - 1
    ring = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def local(w, xs):
        w = jax.tree.map(lambda a: a[0], w)  # this device's stage weights
        sidx = jax.lax.axis_index(axis)
        carry = jnp.zeros_like(xs[0])
        out = jnp.zeros_like(xs)
        for t in range(n_micro + n_stages - 1):
            feed = xs[t] if t < n_micro else jnp.zeros_like(xs[0])
            inp = jnp.where(sidx == 0, feed, carry)
            y = stage_fn(w, inp)
            m = t - last
            if 0 <= m < n_micro:  # the last stage emits microbatch m now
                out = out.at[m].set(jnp.where(sidx == last, y, out[m]))
            carry = jax.lax.ppermute(y, axis, ring)
        # Only the last stage holds real outputs; psum replicates them.
        return jax.lax.psum(
            jnp.where(sidx == last, out, jnp.zeros_like(out)), axis
        )

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )
    return fn(stage_params, x)
