"""Public jit'd entry points for the kernels package.

Dispatch is two-axis, and both axes are jit-static:

1. **Engine** (the ``impl`` argument): which code family runs.
   ``"auto"`` picks Pallas on TPU and the pure-jnp `metrics`/`ref`
   oracles on CPU (interpret-mode Pallas is far slower than XLA:CPU for
   the same math). Tests force ``impl="pallas"`` with
   ``interpret=True`` to validate the kernels themselves against the
   oracles.

2. **Metric** (the ``metric`` argument on `distance_multi`): WHAT the
   per-round computation scores against the shared counts matrix — an
   elementwise-lane distance from the `repro.kernels.metrics` registry
   ("l1" | "chi2" | "hellinger"). It is a property of the spec, threaded
   as a plain string (MultiQuerySpec -> fused_round /
   make_distributed_round / make_pump_round -> this module).

Each job has one kernel per engine:

  ======================  ==============================================
  op                      Pallas engine / ref engine
  ======================  ==============================================
  histogram_with_rowsums  `histogram_with_rowsums_pallas` (one pass over
                          the packed z * V_X + x key, row sums reduced
                          from the reshaped counts) /
                          `ref.histogram_with_rowsums_ref`.
                          ``impl="matmul"`` (chunked one-hot
                          contraction) is an explicit engine request.
                          No metric axis: the counts matrix is shared
                          by every metric and query type.
  distance_multi          `metrics.distance_multi_pallas` (one counts
                          pass scores all Q targets; single- or
                          two-sweep layout by padded V_X) /
                          `metrics.distance_multi_ref`.
  histogram               histogram_pallas / histogram_ref / "matmul".
  anyactive               anyactive_pallas / anyactive_ref.
  ======================  ==============================================

The kernels' tile sizes are their own constants. `l1_distance` (Q=1
l1, V_X <= 4096) and `l1_distance_multi` (the l1 pin of
`distance_multi`) are legacy entry points kept for their import
surface; every round in the engine (histsim / multiquery / distributed
/ pump) routes through `distance_multi` and `histogram_with_rowsums`.
"""

from __future__ import annotations

import collections
import functools
import re
from typing import Literal

import jax
import jax.numpy as jnp

from repro.kernels import metrics, ref
from repro.kernels.anyactive import anyactive_pallas
from repro.kernels.histogram import histogram_pallas, histogram_with_rowsums_pallas
from repro.kernels.l1_distance import l1_distance_pallas

__all__ = [
    "histogram",
    "histogram_with_rowsums",
    "distance_multi",
    "l1_distance",
    "l1_distance_multi",
    "anyactive",
    "default_impl",
    "tpu_kernel_calls",
    "tpu_kernels_in",
]

Impl = Literal["auto", "pallas", "ref"]


def default_impl() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _resolve(impl: Impl) -> str:
    return default_impl() if impl == "auto" else impl


def tpu_kernel_calls(hlo_text: str) -> collections.Counter:
    """Calls per Pallas TPU kernel name (``tpu_custom_call``) in the text
    of a compiled program (``jit(f).lower(...).compile().as_text()``)."""
    return collections.Counter(
        re.search(r"%([A-Za-z_]\w*?)(?:\.\d+)? = ", line).group(1)
        for line in hlo_text.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line
    )


def tpu_kernels_in(hlo_text: str) -> set:
    """Names of the Pallas TPU kernels in the text of a compiled program."""
    return set(tpu_kernel_calls(hlo_text))


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


@functools.partial(jax.jit, static_argnames=("v_z", "v_x", "impl", "interpret", "onehot_dtype"))
def histogram_with_rowsums(
    z_idx: jax.Array,
    x_idx: jax.Array,
    *,
    v_z: int,
    v_x: int,
    impl: Impl = "auto",
    interpret: bool = False,
    onehot_dtype=jnp.float32,
) -> tuple:
    """((V_Z, V_X), (V_Z,)) histogram + row-sum delta.

    rows == counts.sum(axis=1) exactly (integer-valued f32 counts), so
    `ingest` can advance ``n_i`` without re-reading the delta matrix.
    Same impl choices as `histogram`.
    """
    if impl == "matmul":
        counts = ref.histogram_matmul(
            z_idx, x_idx, v_z=v_z, v_x=v_x, onehot_dtype=onehot_dtype
        )
        return counts, jnp.sum(counts, axis=1)
    if _resolve(impl) == "pallas":
        return histogram_with_rowsums_pallas(
            z_idx, x_idx, v_z=v_z, v_x=v_x, interpret=interpret
        )
    return ref.histogram_with_rowsums_ref(z_idx, x_idx, v_z=v_z, v_x=v_x)


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


@functools.partial(jax.jit, static_argnames=("metric", "impl", "interpret"))
def distance_multi(
    counts: jax.Array,
    q_hat: jax.Array,
    *,
    metric: str = "l1",
    impl: Impl = "auto",
    interpret: bool = False,
) -> jax.Array:
    """(Q, V_Z) f32 batched distances for a (Q, V_X) target matrix.

    ``metric`` picks WHAT is computed (registry score: "l1" | "chi2" |
    "hellinger" — squared Hellinger). One counts pass scores every
    target: HBM traffic is V_Z * V_X + Q * V_X, independent of Q and of
    the metric. Unlike the Q=1 `l1_distance`, V_X is unbounded
    (lane-tiled on TPU).
    """
    if _resolve(impl) == "pallas":
        return metrics.distance_multi_pallas(counts, q_hat, metric=metric, interpret=interpret)
    return metrics.distance_multi_ref(counts, q_hat, metric=metric)


def l1_distance_multi(
    counts: jax.Array,
    q_hat: jax.Array,
    *,
    impl: Impl = "auto",
    interpret: bool = False,
) -> jax.Array:
    """`distance_multi` pinned to metric="l1" (the pre-metric-layer
    entry point, bit-identical to it; kept for its import surface)."""
    return distance_multi(counts, q_hat, metric="l1", impl=impl, interpret=interpret)


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
