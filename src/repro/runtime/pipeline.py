"""Pipeline-parallel executor: GPipe-style fill/drain over a `stage` mesh
axis with `shard_map` + `ppermute` microbatch rotation.

The layer→stage map and the microbatch order come from the HDATS planner
(`repro.plan.plan_pipeline`); this executor realizes the schedule on a mesh.
Stages hold equal layer counts (the planner's contiguous map is padded to
equal size by construction when `layers % stages == 0`; unequal maps run the
planner's schedule host-side — see plan_pipeline's microbatch_order).

Differentiable: ppermute has a transpose rule, so jax.grad through
``pipeline_apply`` yields pipeline-parallel backward (fill/drain reversed).
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


__all__ = ["pipeline_apply"]


def pipeline_apply(
    mesh,
    stage_params: Any,          # pytree, leaves stacked (n_stages, ...)
    x_mb: jax.Array,            # (n_micro, mb, ...) microbatched inputs
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    *,
    stage_axis: str = "stage",
) -> jax.Array:
    """Run all microbatches through the stage pipeline; returns outputs
    (n_micro, mb, ...) as produced by the LAST stage."""
    n_stages = mesh.shape[stage_axis]
    n_micro = x_mb.shape[0]
    n_ticks = n_micro + n_stages - 1

    def shard_fn(params_local, x_local):
        # params_local: leaves (1, ...); x_local: (n_micro, mb, ...) on stage 0
        # (other stages receive zeros — the spec broadcasts the real batch
        # from stage 0's shard; we index microbatches locally)
        sid = jax.lax.axis_index(stage_axis)
        p_local = jax.tree.map(lambda a: a[0], params_local)
        mb_shape = x_local.shape[1:]
        buf = jnp.zeros(mb_shape, x_local.dtype)          # in-flight activation
        outs = jnp.zeros((n_micro, *mb_shape), x_local.dtype)

        def tick(carry, t):
            buf, outs = carry
            # stage 0 injects microbatch t (when in range)
            inject = jnp.where(t < n_micro, t, n_micro - 1)
            x_in = x_local[inject]
            buf = jnp.where((sid == 0) & (t < n_micro), x_in, buf)
            y = stage_fn(p_local, buf)
            # last stage emits microbatch t-(n_stages-1)
            emit = t - (n_stages - 1)
            emit_idx = jnp.clip(emit, 0, n_micro - 1)
            do_emit = (sid == n_stages - 1) & (emit >= 0)
            outs = jnp.where(
                do_emit,
                jax.lax.dynamic_update_index_in_dim(outs, y, emit_idx, 0),
                outs,
            )
            # rotate activations forward one stage
            buf = jax.lax.ppermute(
                y, stage_axis, [(i, (i + 1) % n_stages) for i in range(n_stages)]
            )
            return (buf, outs), None

        (buf, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(n_ticks))
        return outs[None]  # (1, n_micro, mb, ...) per stage

    n_extra = x_mb.ndim - 1
    out = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P(stage_axis), P(*([None] * (1 + n_extra)))),
        out_specs=P(stage_axis),
        check_vma=False,
    )(stage_params, x_mb)
    # (n_stages, n_micro, ...) — only the LAST stage's slot holds real outputs
    return out[-1]
