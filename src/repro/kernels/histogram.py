"""Pallas TPU kernel: per-candidate histogram accumulation.

TPU adaptation (see DESIGN.md Sec 2): the paper's CPU implementation
scatters each tuple into its bin — random writes that are hostile to the
TPU memory system (no fast scatter). We instead express the histogram as
a ONE-HOT CONTRACTION that runs on the MXU, over the flat joint key
j = z * V_X + x, packed 128 keys to a row:

    out[j // 128, j % 128] = sum_s onehot_row[s, j // 128] * onehot_lane[s, j % 128]
                           = (onehot_row)^T @ (onehot_lane)

and counts = out.reshape(-1)[:V_Z * V_X].reshape(V_Z, V_X). Contracting
z against x instead would pad V_X to 128 output lanes: at V_X = 24 only
18.75% of the cells the MXU computes would be real. The packed output
has ceil(V_Z * V_X / 128) rows, nearly all of them real at every shape
(`ingest_lane_fill`).

Layout. Samples arrive lane-dense: the (N,) key vector is viewed as
(N/128, 128) rows, and one grid step takes S_TILE/128 such rows (a
multiple of 8, so every block is a whole number of (8, 128) int32
tiles). Per row, the kernel builds the two transposed one-hot tiles in
VMEM — (ROW_TILE, 128) for the packed row ``j >> 7`` and (128, 128) for
the lane ``j & 127``, ids along sublanes compared against the row's 128
lanes, no gather and no relayout — and issues a (ROW_TILE x 128) @
(128 x 128) matmul that contracts the 128 samples, accumulating into
the output block, which stays resident in VMEM across the inner
(sample) grid dimension.

Padding convention: a sample whose z or x is out of range gets key -1,
whose packed row -1 never matches any iota row, so padded samples
contribute zero — no separate mask operand.

`histogram_with_rowsums_pallas` adds the per-candidate row sums (the
ingest-side ``n_i`` increment), reduced from the reshaped counts.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = [
    "SAMPLE_TILE_MULTIPLE",
    "histogram_pallas",
    "histogram_with_rowsums_pallas",
    "ingest_lane_fill",
]

_LANES = 128
_LANE_BITS = 7  # log2(_LANES): key >> 7 is its packed row, key & 127 its lane
# A sample tile is whole (8, 128) int32 tiles of the (N/128, 128) view.
SAMPLE_TILE_MULTIPLE = 8 * _LANES
# Default tile sizes: S_TILE samples per grid step, Z_TILE packed rows.
# VMEM footprint: one (S_TILE/128, 128) i32 input block, the (Z, 128)
# and (128, 128) f32 one-hots of one row, the (Z, 128) f32 output block
# — at S=2048, Z=256 well inside the scoped VMEM.
_S_TILE = 2048
_Z_TILE = 256


def _packed_rows(v_z: int, v_x: int, z_tile: int) -> tuple:
    """(row tile, padded row count) of the packed (rows, 128) output:
    the rows of the V_Z * V_X keys, in whole (8, 128) f32 tiles, padded
    to a whole number of row tiles of at most ``z_tile`` rows."""
    rows = -(-(v_z * v_x) // _LANES)
    rows = -(-rows // 8) * 8
    row_tile = min(z_tile, rows)
    return row_tile, -(-rows // row_tile) * row_tile


def ingest_lane_fill(v_z: int, v_x: int, z_tile: int = _Z_TILE) -> float:
    """Share of the contraction's computed output cells that are real
    counts: V_Z * V_X over the padded (rows, 128) output, for a row tile
    of ``z_tile``. Depends on the shape alone."""
    _, rows_pad = _packed_rows(v_z, v_x, z_tile)
    return v_z * v_x / (rows_pad * _LANES)


def _histogram_kernel(j_ref, out_ref, *, row_tile: int):
    rb = pl.program_id(0)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # Packed-row / lane ids along sublanes; the row's samples along lanes.
    row_ids = jax.lax.broadcasted_iota(jnp.int32, (row_tile, _LANES), 0) + rb * row_tile
    lane_ids = jax.lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 0)

    def row(r, carry):
        j = j_ref[pl.ds(r, 1), :]  # (1, 128) int32 keys
        onehot_row = ((j >> _LANE_BITS) == row_ids).astype(jnp.float32)  # (ROW_TILE, 128)
        onehot_lane = ((j & (_LANES - 1)) == lane_ids).astype(jnp.float32)  # (128, 128)
        # (ROW_TILE, 128) @ (128, 128) on the MXU, contracting samples.
        out_ref[...] += jax.lax.dot_general(
            onehot_row,
            onehot_lane,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return carry

    jax.lax.fori_loop(0, j_ref.shape[0], row, 0)


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
    multiple of `SAMPLE_TILE_MULTIPLE`, ``z_tile`` tiles the packed rows.
    """
    if s_tile <= 0 or s_tile % SAMPLE_TILE_MULTIPLE:
        raise ValueError(
            f"s_tile must be a positive multiple of {SAMPLE_TILE_MULTIPLE}, got {s_tile}"
        )
    if v_z * v_x >= 2**31:
        raise ValueError(f"V_Z * V_X must fit an int32 key, got {v_z} * {v_x}")
    row_tile, rows_pad = _packed_rows(v_z, v_x, z_tile)
    if row_tile % 8:
        raise ValueError(f"z_tile must be a multiple of 8 below the packed rows, got {z_tile}")
    z_idx = z_idx.reshape(-1).astype(jnp.int32)
    x_idx = x_idx.reshape(-1).astype(jnp.int32)
    n = z_idx.shape[0]
    # Out-of-range ids get the "never matches" key -1.
    valid = (z_idx >= 0) & (z_idx < v_z) & (x_idx >= 0) & (x_idx < v_x)
    key = jnp.where(valid, z_idx * v_x + x_idx, -1)

    # (N,) -> (rows, 128); a window shorter than one tile is one block.
    rows = max(1, -(-n // _LANES))
    s_rows = min(s_tile // _LANES, rows)
    n_pad = -(-rows // s_rows) * s_rows * _LANES
    if n_pad != n:
        key = jnp.pad(key, (0, n_pad - n), constant_values=-1)
    key = key.reshape(-1, _LANES)

    out = pl.pallas_call(
        functools.partial(_histogram_kernel, row_tile=row_tile),
        grid=(rows_pad // row_tile, key.shape[0] // s_rows),
        in_specs=[pl.BlockSpec((s_rows, _LANES), lambda rb, sb: (sb, 0))],
        out_specs=pl.BlockSpec((row_tile, _LANES), lambda rb, sb: (rb, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_pad, _LANES), jnp.float32),
        interpret=interpret,
    )(key)
    return out.reshape(-1)[: v_z * v_x].reshape(v_z, v_x)


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
    """((V_Z, V_X), (V_Z,)) histogram + its row sums.

    rows[i] == counts[i].sum() exactly: counts are integer-valued f32,
    and every reduction order is exact below 2^24.
    """
    counts = histogram_pallas(
        z_idx, x_idx, v_z=v_z, v_x=v_x, s_tile=s_tile, z_tile=z_tile,
        interpret=interpret,
    )
    return counts, jnp.sum(counts, axis=1)
