"""Pallas TPU kernel: per-candidate histogram accumulation.

TPU adaptation (see DESIGN.md Sec 2): the paper's CPU implementation
scatters each tuple into its bin — random writes that are hostile to the
TPU memory system (no fast scatter). We instead express the histogram as
a ONE-HOT CONTRACTION that runs on the MXU:

    counts[z, x] = sum_s onehot_z[s, z] * onehot_x[s, x]
                 = (onehot_z)^T @ (onehot_x)

Layout. Samples arrive lane-dense: the (N,) id vectors are viewed as
(N/128, 128) rows, and one grid step takes S_TILE/128 such rows (a
multiple of 8, so every block is a whole number of (8, 128) int32
tiles). Per row, the kernel builds the two transposed one-hot tiles in
VMEM — (Z_TILE, 128) and (VX_PAD, 128), candidate / bin ids along
sublanes compared against the row's 128 lanes, no gather and no
relayout — and issues a (Z_TILE x 128) @ (128 x VX_PAD) matmul that
contracts the 128 samples, accumulating into the output block, which
stays resident in VMEM across the inner (sample) grid dimension. The
output is lane-padded to VX_PAD = V_X rounded up to 128 (the MXU fills
128 output lanes whatever V_X is) and sliced back outside.

Padding convention: z or x entries < 0 never match any iota row, so
padded samples contribute zero — no separate mask operand.

`histogram_with_rowsums_pallas` additionally emits the per-candidate
row-sum delta (the ingest-side ``n_i`` increment) from the SAME pass:
the counts block is still VMEM-resident after the last sample tile, so
the lane reduction is free — `ingest` no longer re-streams the full
delta matrix from HBM for a separate ``jnp.sum(delta, axis=1)``. The
row sums leave as a (VZ_PAD, 1) column, a 2-D layout Mosaic and XLA
agree on for any number of candidate tiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["SAMPLE_TILE_MULTIPLE", "histogram_pallas", "histogram_with_rowsums_pallas"]

_LANES = 128
# A sample tile is whole (8, 128) int32 tiles of the (N/128, 128) view.
SAMPLE_TILE_MULTIPLE = 8 * _LANES
# Default tile sizes: S_TILE samples per grid step, Z_TILE candidate rows.
# VMEM footprint: two (S_TILE/128, 128) i32 input blocks, the (Z, 128)
# and (VX_PAD, 128) f32 one-hots of one row, the (Z, VX_PAD) f32 output
# block — at S=2048, Z=256, VX_PAD<=2048 well inside the scoped VMEM.
_S_TILE = 2048
_Z_TILE = 256


def _histogram_kernel(z_ref, x_ref, out_ref, *rows_ref, z_tile: int, vx_pad: int, num_sb: int):
    zb = pl.program_id(0)
    sb = pl.program_id(1)

    @pl.when(sb == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # Candidate / bin ids along sublanes; the row's samples along lanes.
    z_ids = jax.lax.broadcasted_iota(jnp.int32, (z_tile, _LANES), 0) + zb * z_tile
    x_ids = jax.lax.broadcasted_iota(jnp.int32, (vx_pad, _LANES), 0)

    def row(r, carry):
        z = z_ref[pl.ds(r, 1), :]  # (1, 128) int32
        x = x_ref[pl.ds(r, 1), :]
        onehot_z = (z == z_ids).astype(jnp.float32)  # (Z_TILE, 128)
        onehot_x = (x == x_ids).astype(jnp.float32)  # (VX_PAD, 128)
        # (Z_TILE, 128) @ (128, VX_PAD) on the MXU, contracting samples.
        out_ref[...] += jax.lax.dot_general(
            onehot_z,
            onehot_x,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return carry

    jax.lax.fori_loop(0, z_ref.shape[0], row, 0)

    if rows_ref:  # fused row-sum output: reduce the still-resident block
        @pl.when(sb == num_sb - 1)
        def _rows():
            rows_ref[0][...] = jnp.sum(out_ref[...], axis=1, keepdims=True)


def _histogram_call(
    z_idx: jax.Array,
    x_idx: jax.Array,
    *,
    v_z: int,
    v_x: int,
    s_tile: int,
    z_tile: int,
    with_rowsums: bool,
    interpret: bool,
):
    if s_tile <= 0 or s_tile % SAMPLE_TILE_MULTIPLE:
        raise ValueError(
            f"s_tile must be a positive multiple of {SAMPLE_TILE_MULTIPLE}, got {s_tile}"
        )
    if z_tile < v_z and z_tile % 8:
        raise ValueError(f"z_tile must be a multiple of 8 below V_Z, got {z_tile}")
    z_idx = z_idx.reshape(-1)
    x_idx = x_idx.reshape(-1)
    n = z_idx.shape[0]
    # Clamp out-of-range ids to the "never matches" value -1.
    z_idx = jnp.where((z_idx >= 0) & (z_idx < v_z), z_idx, -1).astype(jnp.int32)
    x_idx = jnp.where((x_idx >= 0) & (x_idx < v_x), x_idx, -1).astype(jnp.int32)

    # (N,) -> (rows, 128); a window shorter than one tile is one block.
    rows = max(1, -(-n // _LANES))
    s_rows = min(s_tile // _LANES, rows)
    rows_pad = -(-rows // s_rows) * s_rows
    n_pad = rows_pad * _LANES
    if n_pad != n:
        z_idx = jnp.pad(z_idx, (0, n_pad - n), constant_values=-1)
        x_idx = jnp.pad(x_idx, (0, n_pad - n), constant_values=-1)
    z_idx = z_idx.reshape(rows_pad, _LANES)
    x_idx = x_idx.reshape(rows_pad, _LANES)

    z_tile = min(z_tile, v_z)
    vz_pad = -(-v_z // z_tile) * z_tile
    vx_pad = -(-v_x // _LANES) * _LANES

    grid = (vz_pad // z_tile, rows_pad // s_rows)
    out_shape = [jax.ShapeDtypeStruct((vz_pad, vx_pad), jnp.float32)]
    out_specs = [pl.BlockSpec((z_tile, vx_pad), lambda zb, sb: (zb, 0))]
    if with_rowsums:
        out_shape.append(jax.ShapeDtypeStruct((vz_pad, 1), jnp.float32))
        out_specs.append(pl.BlockSpec((z_tile, 1), lambda zb, sb: (zb, 0)))
    outs = pl.pallas_call(
        functools.partial(
            _histogram_kernel, z_tile=z_tile, vx_pad=vx_pad, num_sb=grid[1]
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((s_rows, _LANES), lambda zb, sb: (sb, 0)),
            pl.BlockSpec((s_rows, _LANES), lambda zb, sb: (sb, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(z_idx, x_idx)
    if with_rowsums:
        return outs[0][:v_z, :v_x], outs[1][:v_z, 0]
    return outs[0][:v_z, :v_x]


def histogram_pallas(
    z_idx: jax.Array,
    x_idx: jax.Array,
    *,
    v_z: int,
    v_x: int,
    s_tile: int = _S_TILE,
    z_tile: int = _Z_TILE,
    interpret: bool = False,
) -> jax.Array:
    """(V_Z, V_X) float32 histogram of (z, x) sample pairs.

    Entries with z_idx < 0 or x_idx < 0 (or >= bounds) are dropped.
    Inputs are padded to tile multiples internally; ``s_tile`` is a
    multiple of `SAMPLE_TILE_MULTIPLE`.
    """
    return _histogram_call(
        z_idx, x_idx, v_z=v_z, v_x=v_x, s_tile=s_tile, z_tile=z_tile,
        with_rowsums=False, interpret=interpret,
    )


def histogram_with_rowsums_pallas(
    z_idx: jax.Array,
    x_idx: jax.Array,
    *,
    v_z: int,
    v_x: int,
    s_tile: int = _S_TILE,
    z_tile: int = _Z_TILE,
    interpret: bool = False,
) -> tuple:
    """((V_Z, V_X), (V_Z,)) histogram + its row sums, one fused pass.

    The row sums are reduced from the VMEM-resident counts block after
    the last sample tile, so rows[i] == counts[i].sum() exactly (counts
    are integer-valued f32 — every reduction order is exact below 2^24).
    """
    return _histogram_call(
        z_idx, x_idx, v_z=v_z, v_x=v_x, s_tile=s_tile, z_tile=z_tile,
        with_rowsums=True, interpret=interpret,
    )
