"""Pipeline parallelism over the "pod" mesh axis (GPipe-style).

For multi-pod jobs the cheapest inter-pod link is the pod-to-pod DCI, so
the natural decomposition is one PIPELINE STAGE per pod: layer stack
split into `n_stages` groups, stage s owned by pod s, activations
handed off with `jax.lax.ppermute` once per microbatch tick. Data
parallelism ("data") and tensor parallelism ("model") continue INSIDE
each pod, nested in the same shard_map.

Schedule: GPipe with M microbatches — M + S - 1 ticks; bubble fraction
(S-1)/(M+S-1). Backward is jax.grad through the forward loop (ppermute
transposes to the reverse shift automatically).

This module is deliberately generic: `make_pipeline_forward` takes any
per-stage apply function. The dense transformer adapter
(`transformer_stage_fn`) groups its layers into contiguous stages. Used
by tests/test_pipeline.py and launch/dryrun.py --pipeline.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["make_pipeline_forward", "stack_stage_params", "transformer_stage_fn"]


def stack_stage_params(per_stage_params: list):
    """Stack a list of per-stage param pytrees along a new leading axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_stage_params)


def make_pipeline_forward(
    stage_fn: Callable,  # (stage_params, x, stage_idx) -> y
    mesh: Mesh,
    *,
    n_stages: int,
    n_microbatches: int,
    pod_axis: str = "pod",
    data_axes: tuple = ("data",),
    model_axis: str = "model",
):
    """Returns f(stacked_stage_params, x) -> y running the GPipe schedule.

    x: (B, ...) global batch; B must divide by n_microbatches. The
    returned function must be called under `jax.jit` with the mesh's
    shardings; stacked_stage_params' leading axis is sharded over
    `pod_axis`, so each pod materializes only its own stage weights.
    """
    if mesh.shape[pod_axis] != n_stages:
        raise ValueError(f"n_stages={n_stages} != pod axis size {mesh.shape[pod_axis]}")

    def pipelined(stage_params_local, x_local):
        # Inside shard_map: stage_params_local has leading dim 1 (this
        # pod's stage); x_local is this data-shard's slice of the batch.
        stage_idx = jax.lax.axis_index(pod_axis)
        sp = jax.tree.map(lambda a: a[0], stage_params_local)

        b = x_local.shape[0]
        mb = b // n_microbatches
        micro = x_local.reshape(n_microbatches, mb, *x_local.shape[1:])

        n_ticks = n_microbatches + n_stages - 1
        buf = jnp.zeros_like(micro[0])
        outs = jnp.zeros_like(micro)
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(carry, t):
            buf, outs = carry
            # stage 0 injects microbatch t (when in range)
            inject = jnp.where(t < n_microbatches, t, 0)
            x_in = jnp.where(stage_idx == 0, micro[inject], buf)
            y = stage_fn(sp, x_in, stage_idx)
            # last stage collects its finished microbatch (t - (S-1))
            out_slot = t - (n_stages - 1)
            collect = (stage_idx == n_stages - 1) & (out_slot >= 0)
            outs = jax.lax.cond(
                collect,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.maximum(out_slot, 0), 0
                ),
                lambda o: o,
                outs,
            )
            # hand off to the next stage
            buf = jax.lax.ppermute(y, pod_axis, perm)
            return (buf, outs), None

        (buf, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(n_ticks))
        # broadcast final outputs from the last stage to all pods so the
        # loss is computable everywhere (one extra psum of activations).
        is_last = (stage_idx == n_stages - 1).astype(outs.dtype)
        outs = jax.lax.psum(outs * is_last, pod_axis)
        return outs.reshape(b, *outs.shape[2:])

    in_specs = (
        P(pod_axis),  # stage-stacked params: stage dim over pods
        P(data_axes),  # batch over data axes (pods all see their slice? no:
        # batch replicated across pods, sharded over data inside the pod)
    )
    out_specs = P(data_axes)
    return jax.shard_map(
        pipelined, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


def transformer_stage_fn(layer_fn: Callable, layers_per_stage: int):
    """Adapter: run `layers_per_stage` stacked layers as one stage.

    stage_params: pytree with leading dim = layers_per_stage.
    """

    def fn(stage_params, x, stage_idx):
        def body(h, lp):
            return layer_fn(lp, h), None

        y, _ = jax.lax.scan(body, x, stage_params)
        return y

    return fn
