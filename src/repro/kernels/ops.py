"""Public jit'd entry points for the kernels package.

Dispatch is three-axis — every hot-path call is the product of three
independent, jit-static choices:

1. **Engine** (the ``impl`` argument): which code family runs.
   ``"auto"`` picks Pallas on TPU and the pure-jnp `metrics`/`ref`
   oracles on CPU (interpret-mode Pallas is far slower than XLA:CPU for
   the same math). Tests force ``impl="pallas"`` with
   ``interpret=True`` to validate the kernels themselves against the
   oracles.

2. **Plan** (the ``plan`` argument on the two hot-path entry points):
   WHICH measured-fastest variant of that engine runs, resolved from
   `repro.kernels.autotune`'s committed per-backend plan file. Shapes
   are concrete at trace time, so a ``plan="auto"`` registry lookup is
   a plain dict get baked into the compiled program — zero dispatch
   cost per call. A lookup miss, a stale plan file, or a plan the
   engine/shape can't run falls back to `autotune.DEFAULT_TAU` /
   `DEFAULT_INGEST`, which reproduce the pre-autotune dispatch bit for
   bit. Pass an explicit `autotune.TauPlan` / `IngestPlan` to pin a
   variant (the round-builders thread a resolved `PlanPair` through
   statically), or ``plan="default"`` to ignore the registry.

3. **Metric** (the ``metric`` argument on `distance_multi`): WHAT the
   per-round computation scores against the shared counts matrix — an
   elementwise-lane distance from the `repro.kernels.metrics` registry
   ("l1" | "chi2" | "hellinger"). The metric is a plain string, so it
   is hashable and jit-static exactly like ``plan``; it is threaded the
   same way (MultiQuerySpec -> fused_round / make_distributed_round /
   make_pump_round -> this module). Metric and plan compose freely —
   every tuned variant runs every metric — and autotune plan keys are
   per-metric, because the score changes the VPU cost that decides
   which variant wins. The ``metric="l1"`` default reproduces the
   pre-metric-layer l1 ops bit for bit.

Plan-driven entry points (variants per engine; every variant of one
metric is bit-identical on integer-valued counts — see
tests/test_autotune.py and tests/test_metrics.py):

  ======================  ==============================================
  op                      plan knobs
  ======================  ==============================================
  distance_multi          variant: "batched" (one counts pass scores all
                          Q targets — `metrics.distance_multi_pallas` /
                          `metrics.distance_multi_ref`), "unrolled" (Q
                          single-query passes stacked), "xla" (fused 3D
                          broadcast, `metrics.distance_multi_xla`);
                          z_tile / x_tile / sweeps (Pallas tiling and
                          single- vs two-sweep V_X phase); lowprec
                          (uint16 counts traffic behind a runtime
                          overflow gate, exact by construction and
                          metric-agnostic — kernels upcast per tile).
  histogram_with_rowsums  fused: one pass
                          (`histogram_with_rowsums_pallas` /
                          `histogram_with_rowsums_ref`) vs the two-step
                          histogram + separate row reduction (on the
                          Pallas engine the same kernel, rows reduced
                          from its reshaped counts either way);
                          s_tile / z_tile (Pallas tiling: samples, and
                          packed rows of the flat z * V_X + x key).
                          ``impl="matmul"`` (chunked one-hot
                          contraction) bypasses the plan — it is an
                          explicit engine request, not a tuned variant.
                          No metric axis: the counts matrix is shared
                          by every metric and query type.
  ======================  ==============================================

Fixed-dispatch entry points (no plan — one variant per engine):
`histogram` (histogram_pallas / histogram_ref / "matmul"),
`l1_distance` (Q=1 l1 — `l1_distance_pallas`, V_X <= 4096 /
`ref.l1_distance_ref`), `anyactive` (anyactive_pallas / anyactive_ref).
`l1_distance_multi` is the l1 pin of `distance_multi`, kept for its
import surface.

`l1_distance` is the Q=1 legacy entry point; every round in the engine
(histsim / multiquery / distributed / pump) routes through
`distance_multi` and `histogram_with_rowsums`, so the plan file is
what the serving loop actually runs. After editing the plan file on
disk, call `autotune.reload()` — it clears the jit caches that hold the
previously-baked plans.
"""

from __future__ import annotations

import functools
import re
from typing import Literal, Union

import jax
import jax.numpy as jnp

from repro.kernels import autotune, ref
from repro.kernels.anyactive import anyactive_pallas
from repro.kernels.histogram import histogram_pallas
from repro.kernels.l1_distance import l1_distance_pallas

__all__ = [
    "histogram",
    "histogram_with_rowsums",
    "distance_multi",
    "l1_distance",
    "l1_distance_multi",
    "anyactive",
    "default_impl",
    "tpu_kernels_in",
]

Impl = Literal["auto", "pallas", "ref"]
# "auto": trace-time registry lookup; "default": pin the pre-autotune
# dispatch; or an explicit plan instance (hashable -> jit-static).
TauPlanArg = Union[str, None, autotune.TauPlan]
IngestPlanArg = Union[str, None, autotune.IngestPlan]


def default_impl() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _resolve(impl: Impl) -> str:
    return default_impl() if impl == "auto" else impl


def tpu_kernels_in(hlo_text: str) -> set:
    """Names of the Pallas TPU kernels (``tpu_custom_call``) in the text
    of a compiled program (``jit(f).lower(...).compile().as_text()``)."""
    return {
        re.search(r"%([A-Za-z_]\w*?)(?:\.\d+)? = ", line).group(1)
        for line in hlo_text.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line
    }


@functools.partial(jax.jit, static_argnames=("v_z", "v_x", "impl", "interpret", "onehot_dtype"))
def histogram(
    z_idx: jax.Array,
    x_idx: jax.Array,
    *,
    v_z: int,
    v_x: int,
    impl: Impl = "auto",
    interpret: bool = False,
    onehot_dtype=jnp.float32,
) -> jax.Array:
    """(V_Z, V_X) f32 histogram of (z, x) pairs; negative ids dropped.

    impl: "pallas" (TPU kernel) | "ref" (scatter-add) | "matmul"
    (chunked one-hot contraction — the MXU formulation in plain jnp).
    """
    if _resolve(impl) == "pallas":
        return histogram_pallas(z_idx, x_idx, v_z=v_z, v_x=v_x, interpret=interpret)
    if impl == "matmul":
        return ref.histogram_matmul(
            z_idx, x_idx, v_z=v_z, v_x=v_x, onehot_dtype=onehot_dtype
        )
    return ref.histogram_ref(z_idx, x_idx, v_z=v_z, v_x=v_x)


@functools.partial(
    jax.jit,
    static_argnames=("v_z", "v_x", "impl", "interpret", "onehot_dtype", "plan"),
)
def histogram_with_rowsums(
    z_idx: jax.Array,
    x_idx: jax.Array,
    *,
    v_z: int,
    v_x: int,
    impl: Impl = "auto",
    interpret: bool = False,
    onehot_dtype=jnp.float32,
    plan: IngestPlanArg = "auto",
) -> tuple:
    """((V_Z, V_X), (V_Z,)) histogram + row-sum delta.

    rows == counts.sum(axis=1) exactly (integer-valued f32 counts), so
    `ingest` can advance ``n_i`` without re-reading the delta matrix.
    Same impl choices as `histogram`; ``plan`` picks the tuned variant
    (fused one-pass vs two-step, Pallas tiles — see the module
    docstring). ``impl="matmul"`` bypasses the plan.
    """
    if impl == "matmul":
        counts = ref.histogram_matmul(
            z_idx, x_idx, v_z=v_z, v_x=v_x, onehot_dtype=onehot_dtype
        )
        return counts, jnp.sum(counts, axis=1)
    return autotune.run_ingest(
        z_idx,
        x_idx,
        v_z=v_z,
        v_x=v_x,
        plan=autotune.coerce_ingest_plan(plan, v_z, v_x),
        engine=_resolve(impl),
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("impl", "interpret"))
def l1_distance(
    counts: jax.Array,
    q_hat: jax.Array,
    *,
    impl: Impl = "auto",
    interpret: bool = False,
) -> jax.Array:
    """(V_Z,) f32 distances tau_i = ||normalize(counts_i) - q_hat||_1."""
    if _resolve(impl) == "pallas":
        return l1_distance_pallas(counts, q_hat, interpret=interpret)
    return ref.l1_distance_ref(counts, q_hat)


@functools.partial(jax.jit, static_argnames=("metric", "impl", "interpret", "plan"))
def distance_multi(
    counts: jax.Array,
    q_hat: jax.Array,
    *,
    metric: str = "l1",
    impl: Impl = "auto",
    interpret: bool = False,
    plan: TauPlanArg = "auto",
) -> jax.Array:
    """(Q, V_Z) f32 batched distances for a (Q, V_X) target matrix.

    ``metric`` picks WHAT is computed (registry score: "l1" | "chi2" |
    "hellinger" — squared Hellinger); ``plan`` picks the tuned variant
    of HOW (batched one-pass / Q-unrolled / fused-3D "xla", plus Pallas
    tiles, sweep phase, and the uint16 low-precision counts path — see
    the module docstring). The default plan is the batched form: HBM
    traffic Q * V_Z * V_X -> V_Z * V_X + Q * V_X, independent of Q and
    of the metric. Within one metric all variants are bit-identical on
    integer-valued counts, so the plan is a pure wall-clock choice.
    Unlike the Q=1 `l1_distance`, V_X is unbounded (lane-tiled on TPU).
    """
    tau_plan = autotune.coerce_tau_plan(
        plan, counts.shape[0], counts.shape[1], q_hat.shape[0], metric
    )
    return autotune.run_tau(
        counts, q_hat, plan=tau_plan, engine=_resolve(impl),
        interpret=interpret, metric=metric,
    )


def l1_distance_multi(
    counts: jax.Array,
    q_hat: jax.Array,
    *,
    impl: Impl = "auto",
    interpret: bool = False,
    plan: TauPlanArg = "auto",
) -> jax.Array:
    """`distance_multi` pinned to metric="l1" (the pre-metric-layer
    entry point, bit-identical to it; kept for its import surface)."""
    return distance_multi(
        counts, q_hat, metric="l1", impl=impl, interpret=interpret, plan=plan
    )


@functools.partial(jax.jit, static_argnames=("impl", "interpret"))
def anyactive(
    bitmap: jax.Array,
    active_words: jax.Array,
    *,
    impl: Impl = "auto",
    interpret: bool = False,
) -> jax.Array:
    """(num_blocks,) bool AnyActive marks from a packed bitmap."""
    if _resolve(impl) == "pallas":
        return anyactive_pallas(bitmap, active_words, interpret=interpret)
    return ref.anyactive_ref(bitmap, active_words)
