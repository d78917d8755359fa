"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantics of record: each kernel's test sweeps shapes and
dtypes and asserts allclose against these functions. They are also the
production path on CPU (interpret-mode Pallas is far slower than XLA:CPU
for the same math), selected automatically by ``ops.py``.

The distance oracles are the l1 instances of the score-generic forms in
`repro.kernels.metrics` (same module-of-record relationship the Pallas
kernels have): the delegation adds no ops, so they remain bit-identical
to the standalone l1 bodies they replaced.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import metrics

__all__ = [
    "histogram_ref",
    "histogram_with_rowsums_ref",
    "l1_distance_ref",
    "l1_distance_multi_ref",
    "anyactive_ref",
]


def histogram_ref(
    z_idx: jax.Array,
    x_idx: jax.Array,
    *,
    v_z: int,
    v_x: int,
    dtype=jnp.float32,
) -> jax.Array:
    """Per-candidate histogram of a sample batch.

    Args:
      z_idx: (N,) int32 candidate ids; entries < 0 are padding and dropped.
      x_idx: (N,) int32 group (bin) ids; entries < 0 dropped.
      v_z, v_x: histogram dimensions.

    Returns:
      (V_Z, V_X) counts with counts[z, x] = #{samples with ids (z, x)}.
    """
    valid = (z_idx >= 0) & (x_idx >= 0) & (z_idx < v_z) & (x_idx < v_x)
    w = valid.astype(dtype)
    # mode="drop" discards out-of-bounds (negative) indices.
    return (
        jnp.zeros((v_z, v_x), dtype)
        .at[z_idx, x_idx]
        .add(w, mode="drop")
    )


def histogram_matmul(
    z_idx: jax.Array,
    x_idx: jax.Array,
    *,
    v_z: int,
    v_x: int,
    chunk: int = 32_768,
    onehot_dtype=jnp.float32,
) -> jax.Array:
    """One-hot-contraction histogram in plain jnp (the MXU formulation).

    Algebraically identical to histogram_ref and to the Pallas kernel:
    counts = onehot(z)^T @ onehot(x), evaluated in unrolled sample chunks
    so the one-hot buffers stay bounded. This is the production path the
    distributed engine lowers for the dry-run (XLA cost-analysis sees the
    real matmul FLOPs; the Pallas kernel is its VMEM-tiled twin on TPU).

    onehot_dtype=bfloat16 halves the one-hot bytes and doubles MXU rate;
    accumulation stays f32 so counts are exact (0/1 entries, exact f32
    sums up to 2^24 per bin).
    """
    n = z_idx.shape[0]
    z_idx = jnp.where((z_idx >= 0) & (z_idx < v_z), z_idx, v_z).astype(jnp.int32)
    x_idx = jnp.where((x_idx >= 0) & (x_idx < v_x), x_idx, v_x).astype(jnp.int32)
    chunk = min(chunk, n)
    n_pad = -(-n // chunk) * chunk
    if n_pad != n:
        z_idx = jnp.pad(z_idx, (0, n_pad - n), constant_values=v_z)
        x_idx = jnp.pad(x_idx, (0, n_pad - n), constant_values=v_x)
    acc = jnp.zeros((v_z, v_x), jnp.float32)
    for c in range(n_pad // chunk):
        zc = jax.lax.dynamic_slice_in_dim(z_idx, c * chunk, chunk)
        xc = jax.lax.dynamic_slice_in_dim(x_idx, c * chunk, chunk)
        oz = jax.nn.one_hot(zc, v_z, dtype=onehot_dtype, axis=-1)  # pads -> all-zero
        ox = jax.nn.one_hot(xc, v_x, dtype=onehot_dtype, axis=-1)
        acc = acc + jax.lax.dot_general(
            oz, ox, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
    return acc


def histogram_with_rowsums_ref(
    z_idx: jax.Array,
    x_idx: jax.Array,
    *,
    v_z: int,
    v_x: int,
    dtype=jnp.float32,
) -> tuple:
    """((V_Z, V_X), (V_Z,)) histogram + per-candidate row sums.

    rows == counts.sum(axis=1) by construction — the semantics the fused
    Pallas pass must reproduce (exact: counts are integer-valued).
    """
    counts = histogram_ref(z_idx, x_idx, v_z=v_z, v_x=v_x, dtype=dtype)
    return counts, jnp.sum(counts, axis=1)


def l1_distance_ref(counts: jax.Array, q_hat: jax.Array) -> jax.Array:
    """tau_i = || counts_i / sum(counts_i) - q_hat ||_1 per candidate row.

    Rows with zero mass get tau = ||q_hat||_1 (= 1 for a distribution):
    an unsampled candidate estimates the empty histogram. Its delta_i is
    1 anyway (n_i = 0) so HistSim never terminates on its account.

    Args:
      counts: (V_Z, V_X) nonnegative counts.
      q_hat: (V_X,) normalized target.

    Returns:
      (V_Z,) float32 distances.
    """
    return metrics.distance_ref(counts, q_hat, metric="l1")


def l1_distance_multi_ref(counts: jax.Array, q_hat: jax.Array) -> jax.Array:
    """Q-batched tau: tau[q, i] = || normalize(counts_i) - q_hat_q ||_1.

    The normalization r_hat is computed ONCE for all queries (the CPU
    counterpart of the Q-batched Pallas kernel; the PR-2 path paid the
    row sum + division Q times) and the per-query |diff| reductions are
    unrolled over the STATIC leading axis rather than broadcast to a
    (Q, V_Z, V_X) intermediate — XLA:CPU runs each 2D reduce on its
    full thread pool, which measures ~2x faster than the fused-3D
    broadcast form at Q=8. Elementwise ops and the lane reduction match
    `l1_distance_ref` exactly, so each tau row is bit-identical to the
    corresponding single-query call.

    Args:
      counts: (V_Z, V_X) nonnegative counts.
      q_hat: (Q, V_X) normalized targets.

    Returns:
      (Q, V_Z) float32 distances.
    """
    return metrics.distance_multi_ref(counts, q_hat, metric="l1")


def anyactive_ref(bitmap: jax.Array, active_words: jax.Array) -> jax.Array:
    """AnyActive block marking over a packed bitmap (paper Alg. 3).

    Args:
      bitmap: (num_blocks, W) uint32 — bit (b, 32w + j) set iff data block
        b contains at least one tuple of candidate 32w + j.
      active_words: (W,) uint32 — packed active-candidate mask.

    Returns:
      (num_blocks,) bool — True = :read, False = :skip.
    """
    hits = jnp.bitwise_and(bitmap, active_words[None, :])
    return jnp.any(hits != 0, axis=1)
