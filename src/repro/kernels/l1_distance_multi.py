"""Q-batched l1 distances: thin alias over the metric registry.

The Q-batched one-HBM-pass tile structure this module introduced (each
(Z_TILE, V_X) counts tile loaded into VMEM once, row-normalized once,
scored against the whole (Q, V_X) target matrix; single-sweep vs
two-sweep lane-tiled layouts) now lives score-generic in
`repro.kernels.metrics.distance_multi_pallas` — the l1 instance emits
the exact same ops as the kernel that used to live here, so this alias
is bit-identical to it. Kept for its import surface
(`l1_distance_multi_pallas`), used by kernel tests.
"""

from __future__ import annotations

import jax

from repro.kernels import metrics

__all__ = ["l1_distance_multi_pallas"]

# Re-exported tile constants (benchmarks import the lane bound).
_Z_TILE = metrics._Z_TILE
_X_TILE = metrics._X_TILE


def l1_distance_multi_pallas(
    counts: jax.Array,
    q_hat: jax.Array,
    *,
    z_tile: int = 256,
    x_tile: int = 4096,
    sweeps: int = 0,
    interpret: bool = False,
) -> jax.Array:
    """(Q, V_Z) float32 l1 distances tau[q, i] for a (Q, V_X) target
    batch; see `metrics.distance_multi_pallas` for layout and knobs."""
    return metrics.distance_multi_pallas(
        counts,
        q_hat,
        metric="l1",
        z_tile=z_tile,
        x_tile=x_tile,
        sweeps=sweeps,
        interpret=interpret,
    )
