"""Single-query l1 distance: thin alias over the metric registry at Q=1.

Historically this module held its own Pallas kernel (the statistics
engine's original hot loop, paper Sec 3). The metric layer
(`repro.kernels.metrics`) now owns ONE score-generic kernel family and
the l1 instance of its Q=1 form emits the exact op sequence of the old
kernel (load tile -> row sum -> max(row, 1) divide -> |diff| -> lane
reduce), so this alias is bit-identical to the kernel it replaced.
Kept for its import surface (`l1_distance_pallas`, `_MAX_VX`): the
legacy `ops.l1_distance` and older tests import it directly.

Rows with zero mass return ||q_hat||_1 (= 1), matching ref.py.
"""

from __future__ import annotations

import jax

from repro.kernels import metrics

__all__ = ["l1_distance_pallas"]

_Z_TILE = 256
# Single-block V_X bound: (Z_TILE x V_X) f32 must fit VMEM with headroom.
_MAX_VX = metrics.MAX_SINGLE_BLOCK_VX


def l1_distance_pallas(
    counts: jax.Array,
    q_hat: jax.Array,
    *,
    z_tile: int = _Z_TILE,
    interpret: bool = False,
) -> jax.Array:
    """(V_Z,) float32 distances tau_i. V_X must be <= 4096 (one VMEM block).

    V_X and V_Z are padded internally; q_hat padding is 0 so padded lanes
    contribute |0 - 0| = 0.
    """
    return metrics.distance_pallas(
        counts, q_hat, metric="l1", z_tile=z_tile, interpret=interpret
    )
