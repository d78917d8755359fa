"""Pluggable distance metrics over the shared row-normalized counts matrix.

Every query type this engine serves reduces to the same per-round
computation: normalize each candidate row of the shared (V_Z, V_X)
counts matrix once, then reduce an ELEMENTWISE score against each of Q
target distributions,

    tau[q, i] = sum_x score(r_hat[i, x], q_hat[q, x]).

The score is the only thing that differs between distances, so the
XLA reference forms and the Pallas single-/two-sweep Q-batched tile
kernels are written ONCE here, parameterized by a `MetricDef`, and ℓ1 becomes one
registry instance (`ops.l1_distance_multi` is now a thin alias; its
output is bit-identical to the pre-metric-layer kernels because the l1
instance emits the exact same op sequence).

Registry entries are `(score, l1_budget, native_l1_budget)`:

  score      — the elementwise lane term (runs inside the kernels);
  l1_budget  — the deviation half of the metric: an inverse modulus of
               continuity mapping a tolerated metric-space deviation to
               the ℓ1 deviation that implies it, which is what lets
               `core.bounds.metric_log_delta` reuse Theorem 1's ℓ1
               concentration bound for every metric (see bounds.py for
               the derivations — conservative for chi2/hellinger);
  native_l1_budget — the metric-native refinement: the same inverse
               modulus made OBSERVATION-AWARE (it may read the measured
               tau), always >= l1_budget by construction (each form is
               a max over independently valid budgets), so the implied
               sample complexity never exceeds the conservative one.
               Derivations in `core/bounds.py`.

Metrics ship three instances:

  l1         sum |r - q|            in [0, 2]; empty row -> 1
  chi2       sum (r-q)^2 / (r+q)    in [0, 2]; 0/0 lanes -> 0; empty
             row -> 1 (= sum q). The classic chi-square distance;
             dominated pointwise by |r - q| so also <= l1.
  hellinger  0.5 * sum (sqrt(r) - sqrt(q))^2   — SQUARED Hellinger,
             in [0, 1]; empty row -> 0.5. Additive over lanes (which is
             what the accumulating two-sweep kernel needs) and monotone
             in the Hellinger distance proper, so top-k rankings agree.

All scores are 0 on padded lanes (r = q = 0), so the kernels' lane
padding needs no masking — the same property the l1 kernels relied on.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "METRICS",
    "METRIC_NAMES",
    "MetricDef",
    "coerce_metric",
    "distance_ref",
    "distance_multi_ref",
    "distance_pallas",
    "distance_multi_pallas",
    "MAX_SINGLE_BLOCK_VX",
]

_Z_TILE = 256
# Lane-tile width: one (Z_TILE x X_TILE) f32 block, double-buffered,
# plus the per-query score temporaries of the two-sweep kernel must fit
# the scoped VMEM (256 x 1024 x 4B = 1 MiB a block; at X_TILE = 4096 the
# Q=8 two-sweep kernel runs out of VMEM on a v5e). V_X beyond this is
# lane-tiled.
_X_TILE = 1024
# Single-block V_X bound for the Q=1 kernel form.
MAX_SINGLE_BLOCK_VX = 4096


# ---------------------------------------------------------------------------
# Elementwise scores (run inside the Pallas kernels AND the XLA references)
# ---------------------------------------------------------------------------


def _score_l1(r: jax.Array, q: jax.Array) -> jax.Array:
    return jnp.abs(r - q)


def _score_chi2(r: jax.Array, q: jax.Array) -> jax.Array:
    # 0/0 -> 0 by convention; since r, q >= 0, the denominator is zero
    # only when both are (|r - q| <= r + q), so the guarded divide is
    # exact — no mass is ever dropped.
    s = r + q
    d = r - q
    return jnp.where(s > 0.0, (d * d) / jnp.where(s > 0.0, s, 1.0), 0.0)


def _score_hellinger(r: jax.Array, q: jax.Array) -> jax.Array:
    d = jnp.sqrt(r) - jnp.sqrt(q)
    return 0.5 * (d * d)


@dataclasses.dataclass(frozen=True)
class MetricDef:
    """One pluggable distance: score + deviation budget."""

    name: str
    score: Callable[[jax.Array, jax.Array], jax.Array]
    # Inverse modulus of continuity w.r.t. ℓ1: the ℓ1 deviation that
    # guarantees a metric-space deviation <= eps. Pure scalar math
    # (works on floats and traced jnp scalars alike); the l1 instance
    # is the IDENTITY — it must add zero ops so the refactored l1
    # bound path stays bit-identical to Theorem 1 as previously coded.
    l1_budget: Callable
    # tau of a candidate with zero sampled mass (r_hat = 0 vs a
    # normalized target): documentation + oracle value for tests.
    empty_row_tau: float = 1.0
    # Observation-aware inverse modulus (eps, tau) -> ℓ1 budget; None
    # falls back to the uniform `l1_budget`. Must dominate `l1_budget`
    # pointwise (it is a max over valid budgets including the uniform
    # one), so switching the engine to native bounds can only retire
    # queries EARLIER, never claim less than the conservative family.
    native_l1_budget: Optional[Callable] = None


def _budget_l1(eps):
    return eps


def _budget_chi2(eps):
    # chi2(p, q) is 3-Lipschitz in p under ℓ1 (|d/dp (p-q)^2/(p+q)| =
    # |(p - q)(p + 3q)| / (p + q)^2 <= 3), so an ℓ1 deviation of eps/3
    # moves the chi2 distance by at most eps. See bounds.py.
    return eps / 3.0


def _budget_hellinger(eps):
    # |H^2(p, t) - H^2(q, t)| <= sqrt(l1) + l1/2 (Cauchy-Schwarz on the
    # sqrt difference), so l1 <= eps^2/4 keeps the squared-Hellinger
    # deviation within eps/2 + eps^2/8 <= eps for eps <= 1. See bounds.py.
    return 0.25 * eps * eps


def _native_budget_chi2(eps, tau):
    # max of two independently valid ℓ1 budgets for a chi2 deviation of
    # eps (derivations in core/bounds.py):
    #   eps/3                        — the uniform 3-Lipschitz modulus
    #                                  (tight at tau = 2, cannot be
    #                                  uniformly improved);
    #   (sqrt(tau+eps) - sqrt(tau))^2 — via the Le Cam metric sqrt(Δ/2)
    #                                  and the observed tau (-> eps at
    #                                  tau = 0: 3x the uniform budget,
    #                                  9x fewer samples for close
    #                                  candidates).
    t = jnp.maximum(tau, 0.0)
    tri = jnp.square(jnp.sqrt(t + eps) - jnp.sqrt(t))
    return jnp.maximum(eps / 3.0, tri)


def _native_budget_hellinger(eps, tau):
    # max of three independently valid ℓ1 budgets for a squared-
    # Hellinger deviation of eps (derivations in core/bounds.py):
    #   eps^2/4                       — the conservative PR-9 floor;
    #   (sqrt(1+2 eps) - 1)^2         — EXACT inverse of the Cauchy-
    #                                   Schwarz modulus sqrt(l1)+l1/2
    #                                   (~eps^2 for small eps, 4x the
    #                                   floor);
    #   2 (sqrt(tau+eps)-sqrt(tau))^2 — via the Hellinger metric,
    #                                   H <= sqrt(l1/2), and the
    #                                   observed tau (-> 2 eps at
    #                                   tau = 0).
    t = jnp.maximum(tau, 0.0)
    cs = jnp.square(jnp.sqrt(1.0 + 2.0 * eps) - 1.0)
    tri = 2.0 * jnp.square(jnp.sqrt(t + eps) - jnp.sqrt(t))
    return jnp.maximum(jnp.maximum(0.25 * eps * eps, cs), tri)


METRICS = {
    "l1": MetricDef("l1", _score_l1, _budget_l1, empty_row_tau=1.0),
    "chi2": MetricDef(
        "chi2", _score_chi2, _budget_chi2, empty_row_tau=1.0,
        native_l1_budget=_native_budget_chi2,
    ),
    "hellinger": MetricDef(
        "hellinger", _score_hellinger, _budget_hellinger, empty_row_tau=0.5,
        native_l1_budget=_native_budget_hellinger,
    ),
}
METRIC_NAMES = tuple(METRICS)


def coerce_metric(metric) -> MetricDef:
    """Registry lookup with a helpful error; accepts a MetricDef as-is."""
    if isinstance(metric, MetricDef):
        return metric
    try:
        return METRICS[metric]
    except (KeyError, TypeError):
        raise ValueError(
            f"unknown metric {metric!r}; have {METRIC_NAMES}"
        ) from None


# ---------------------------------------------------------------------------
# XLA reference forms (semantics of record — see kernels/ref.py)
# ---------------------------------------------------------------------------


def distance_ref(counts: jax.Array, q_hat: jax.Array, *, metric="l1") -> jax.Array:
    """(V_Z,) float32 tau_i = sum_x score(normalize(counts_i), q_hat).

    Rows with zero mass score the empty histogram against q_hat (tau =
    the metric's ``empty_row_tau``); their delta_i is 1 anyway (n_i = 0)
    so the engine never terminates on their account.
    """
    m = coerce_metric(metric)
    counts = counts.astype(jnp.float32)
    row = jnp.sum(counts, axis=1, keepdims=True)
    r_hat = counts / jnp.maximum(row, 1.0)
    return jnp.sum(m.score(r_hat, q_hat[None, :].astype(jnp.float32)), axis=1)


def distance_multi_ref(counts: jax.Array, q_hat: jax.Array, *, metric="l1") -> jax.Array:
    """(Q, V_Z) batched tau: normalization hoisted ONCE for all queries,
    per-query lane reductions unrolled over the static leading axis
    (each 2D reduce runs on XLA:CPU's full thread pool — measured ~2x
    faster than the fused-3D broadcast at Q=8). Elementwise ops and the
    lane reduction match `distance_ref` exactly, so each tau row is
    bit-identical to the corresponding single-query call.
    """
    m = coerce_metric(metric)
    counts = counts.astype(jnp.float32)
    row = jnp.sum(counts, axis=1, keepdims=True)
    r_hat = counts / jnp.maximum(row, 1.0)
    q = q_hat.astype(jnp.float32)
    return jnp.stack(
        [jnp.sum(m.score(r_hat, q[i][None, :]), axis=1) for i in range(q.shape[0])]
    )


# ---------------------------------------------------------------------------
# Pallas TPU kernels: the l1_distance_multi tile structure, score-generic
# ---------------------------------------------------------------------------


def _distance_multi_kernel(counts_ref, q_ref, out_ref, *, num_q: int, score):
    """Single-sweep: whole (padded) V_X in one block."""
    counts = counts_ref[...].astype(jnp.float32)  # (Z_TILE, V_X)
    row = jnp.sum(counts, axis=1, keepdims=True)
    r_hat = counts / jnp.maximum(row, 1.0)
    q = q_ref[...].astype(jnp.float32)  # (Q, V_X)
    for i in range(num_q):  # unrolled: counts tile stays VMEM-resident
        out_ref[i, :] = jnp.sum(score(r_hat, q[i][None, :]), axis=1)


def _distance_multi_tiled_kernel(counts_ref, q_ref, out_ref, row_ref, *, num_q: int, score):
    """Lane-tiled: phase 0 row sums, phase 1 per-query tau partials.

    Requires the score to be additive over lanes — true of every
    registry metric (l1 / chi2 / squared Hellinger are all plain lane
    sums of an elementwise term).
    """
    phase = pl.program_id(1)
    xb = pl.program_id(2)
    counts = counts_ref[...].astype(jnp.float32)  # (Z_TILE, X_TILE)

    @pl.when((phase == 0) & (xb == 0))
    def _init_row():
        row_ref[...] = jnp.zeros_like(row_ref)

    @pl.when(phase == 0)
    def _accum_row():
        row_ref[...] += jnp.sum(counts, axis=1, keepdims=True)

    @pl.when((phase == 1) & (xb == 0))
    def _init_out():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(phase == 1)
    def _accum_tau():
        r_hat = counts / jnp.maximum(row_ref[:, 0:1], 1.0)
        q = q_ref[...].astype(jnp.float32)  # (Q, X_TILE)
        for i in range(num_q):
            out_ref[i, :] += jnp.sum(score(r_hat, q[i][None, :]), axis=1)


def distance_multi_pallas(
    counts: jax.Array,
    q_hat: jax.Array,
    *,
    metric="l1",
    z_tile: int = _Z_TILE,
    x_tile: int = _X_TILE,
    sweeps: int = 0,
    interpret: bool = False,
) -> jax.Array:
    """(Q, V_Z) float32 distances tau[q, i] for a (Q, V_X) target batch.

    Each (Z_TILE, V_X) counts tile is loaded into VMEM ONCE,
    row-normalized once, and scored against the whole (Q, V_X) target
    matrix before the next tile is fetched: HBM traffic is
    V_Z * V_X + Q * V_X per round, independent of Q, for EVERY metric —
    the score only changes the VPU lane term. V_X and V_Z are padded
    internally; q_hat padding is 0 and every registry score is 0 at
    (0, 0), so padded lanes contribute nothing.

    ``sweeps`` selects the layout (both layouts are bit-identical; the
    engine always passes 0, tests force the others): 0 picks by padded
    V_X (single-sweep when V_X fits one ``x_tile`` VMEM block, else the
    two-sweep lane-tiled form whose phase 0 accumulates row sums into a
    VMEM scratch and phase 1 accumulates the per-query score partials),
    1 forces single-sweep (raises if V_X does not fit), 2 forces
    two-sweep.
    """
    score = coerce_metric(metric).score
    v_z, v_x = counts.shape
    num_q, v_xq = q_hat.shape
    if v_xq != v_x:
        raise ValueError(f"q_hat V_X={v_xq} does not match counts V_X={v_x}")
    if x_tile % 128 != 0:
        raise ValueError(f"x_tile must be a lane multiple of 128, got {x_tile}")
    if sweeps not in (0, 1, 2):
        raise ValueError(f"sweeps must be 0 (auto), 1 or 2, got {sweeps}")

    z_tile = min(z_tile, v_z)
    vz_pad = -(-v_z // z_tile) * z_tile
    vx_pad = max(128, -(-v_x // 128) * 128)
    if sweeps == 1 and vx_pad > x_tile:
        raise ValueError(
            f"sweeps=1 needs padded V_X ({vx_pad}) <= x_tile ({x_tile})"
        )
    if vx_pad <= x_tile and sweeps != 2:
        x_tile, tiled = vx_pad, False
    else:
        x_tile = min(x_tile, vx_pad)  # forced two-sweep on a small V_X
        vx_pad, tiled = -(-v_x // x_tile) * x_tile, True
    if (vz_pad, vx_pad) != (v_z, v_x):
        counts = jnp.pad(counts, ((0, vz_pad - v_z), (0, vx_pad - v_x)))
        q_hat = jnp.pad(q_hat, ((0, 0), (0, vx_pad - v_x)))

    out_shape = jax.ShapeDtypeStruct((num_q, vz_pad), jnp.float32)
    if not tiled:
        out = pl.pallas_call(
            functools.partial(_distance_multi_kernel, num_q=num_q, score=score),
            grid=(vz_pad // z_tile,),
            in_specs=[
                pl.BlockSpec((z_tile, vx_pad), lambda zb: (zb, 0)),
                pl.BlockSpec((num_q, vx_pad), lambda zb: (0, 0)),
            ],
            out_specs=pl.BlockSpec((num_q, z_tile), lambda zb: (0, zb)),
            out_shape=out_shape,
            interpret=interpret,
        )(counts, q_hat)
    else:
        out = pl.pallas_call(
            functools.partial(_distance_multi_tiled_kernel, num_q=num_q, score=score),
            grid=(vz_pad // z_tile, 2, vx_pad // x_tile),
            in_specs=[
                pl.BlockSpec((z_tile, x_tile), lambda zb, ph, xb: (zb, xb)),
                pl.BlockSpec((num_q, x_tile), lambda zb, ph, xb: (0, xb)),
            ],
            out_specs=pl.BlockSpec((num_q, z_tile), lambda zb, ph, xb: (0, zb)),
            out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM((z_tile, 128), jnp.float32)],
            interpret=interpret,
        )(counts, q_hat)
    return out[:, :v_z]


def distance_pallas(
    counts: jax.Array,
    q_hat: jax.Array,
    *,
    metric="l1",
    z_tile: int = _Z_TILE,
    interpret: bool = False,
) -> jax.Array:
    """(V_Z,) float32 single-query tau — the Q=1 instance of the batched
    kernel, reached only through the legacy `l1_distance` entry points.
    V_X must fit one VMEM block (<= `MAX_SINGLE_BLOCK_VX`).
    """
    if counts.shape[1] > MAX_SINGLE_BLOCK_VX:
        raise ValueError(
            f"V_X={counts.shape[1]} exceeds single-block bound {MAX_SINGLE_BLOCK_VX}"
        )
    return distance_multi_pallas(
        counts, q_hat[None, :], metric=metric, z_tile=z_tile, interpret=interpret
    )[0]
