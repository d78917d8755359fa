"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # property tests need it; skip on minimal installs
from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.anyactive import anyactive_pallas
from repro.kernels.histogram import (
    histogram_pallas,
    histogram_with_rowsums_pallas,
    ingest_lane_fill,
)
from repro.kernels.l1_distance import l1_distance_pallas
from repro.kernels import ops


HIST_SHAPES = [
    (161, 24, 5_000),
    (7548, 24, 2_000),
    (64, 161, 1_000),
    (10, 2, 100),
    (300, 7, 777),
    (1, 1, 16),
    (2110, 5, 3_000),
]


class TestHistogramKernel:
    @pytest.mark.parametrize("v_z,v_x,n", HIST_SHAPES)
    def test_matches_oracle(self, v_z, v_x, n, rng):
        z = rng.integers(-1, v_z, size=n).astype(np.int32)
        x = rng.integers(-1, v_x, size=n).astype(np.int32)
        got = histogram_pallas(jnp.asarray(z), jnp.asarray(x), v_z=v_z, v_x=v_x, interpret=True)
        want = ref.histogram_ref(jnp.asarray(z), jnp.asarray(x), v_z=v_z, v_x=v_x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("s_tile,z_tile", [(1024, 32), (2048, 256), (1024, 1024)])
    def test_tile_sweep(self, s_tile, z_tile, rng):
        v_z, v_x, n = 200, 30, 5000  # several sample tiles at every s_tile
        z = rng.integers(0, v_z, size=n).astype(np.int32)
        x = rng.integers(0, v_x, size=n).astype(np.int32)
        got = histogram_pallas(
            jnp.asarray(z), jnp.asarray(x), v_z=v_z, v_x=v_x,
            s_tile=s_tile, z_tile=z_tile, interpret=True,
        )
        want = ref.histogram_ref(jnp.asarray(z), jnp.asarray(x), v_z=v_z, v_x=v_x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want))

    def test_total_mass_conserved(self, rng):
        v_z, v_x, n = 50, 11, 999
        z = rng.integers(0, v_z, size=n).astype(np.int32)
        x = rng.integers(0, v_x, size=n).astype(np.int32)
        got = histogram_pallas(jnp.asarray(z), jnp.asarray(x), v_z=v_z, v_x=v_x, interpret=True)
        assert float(got.sum()) == n

    def test_out_of_range_dropped(self):
        z = jnp.asarray([0, 5, 99, -1], jnp.int32)
        x = jnp.asarray([0, 1, 0, 0], jnp.int32)
        got = histogram_pallas(z, x, v_z=4, v_x=2, interpret=True)
        assert float(got.sum()) == 1.0  # only (0, 0) is in range


def _aliasing_ids(v_z, v_x):
    """Out-of-range pairs whose flat key z * V_X + x would land on a real
    cell: x == V_X (the next candidate's bin 0), x == -1 (the previous
    candidate's last bin), z == V_Z or z < 0 with a valid x."""
    zs = np.arange(v_z, dtype=np.int32)
    xs = np.arange(v_x, dtype=np.int32) % v_x
    z = np.concatenate([zs, zs, np.full(v_x, v_z), np.full(v_x, -1)])
    x = np.concatenate([np.full(v_z, v_x), np.full(v_z, -1), xs, xs])
    return z.astype(np.int32), x.astype(np.int32)


# (v_z, v_x, n, z_tile, out_of_range): the served shapes, V_X at and past
# the lane width, a candidate straddling two packed rows (37 * 43 = 1591
# keys, 12.4 rows), several packed-row tiles, a window shorter than one
# 128-sample row, and out-of-range ids that would alias onto real cells.
PACKED_CASES = [
    (7548, 24, 3_000, 256, False),
    (161, 24, 5_000, 256, False),
    (191, 2, 4_000, 256, False),
    (161, 161, 5_000, 256, False),
    (40, 128, 3_000, 256, False),
    (37, 43, 4_000, 256, False),
    (200, 30, 5_000, 16, False),
    (161, 24, 50, 256, False),
    (37, 43, 3_000, 8, True),
]


class TestPackedHistogram:
    @pytest.mark.parametrize("v_z,v_x,n,z_tile,out_of_range", PACKED_CASES)
    def test_counts_and_rows_bit_exact(self, v_z, v_x, n, z_tile, out_of_range, rng):
        z = rng.integers(0, v_z, size=n).astype(np.int32)
        x = rng.integers(0, v_x, size=n).astype(np.int32)
        if out_of_range:
            bad_z, bad_x = _aliasing_ids(v_z, v_x)
            z, x = np.concatenate([z, bad_z]), np.concatenate([x, bad_x])
        got_c, got_r = histogram_with_rowsums_pallas(
            jnp.asarray(z), jnp.asarray(x), v_z=v_z, v_x=v_x,
            s_tile=1024, z_tile=z_tile, interpret=True,
        )
        want_c, want_r = ref.histogram_with_rowsums_ref(
            jnp.asarray(z), jnp.asarray(x), v_z=v_z, v_x=v_x
        )
        np.testing.assert_array_equal(np.asarray(got_c), np.asarray(want_c))
        np.testing.assert_array_equal(np.asarray(got_r), np.asarray(want_r))
        assert float(got_c.sum()) == n  # every in-range pair, and no other

    @pytest.mark.parametrize(
        "v_z,v_x,rows_pad,fill", [(7548, 24, 1536, 0.921), (161, 24, 32, 0.943)]
    )
    def test_lane_fill(self, v_z, v_x, rows_pad, fill):
        """Taxi: 1,416 packed rows in 6 tiles of 256, against 0.184 for a
        z-by-x contraction (7,680 x 128 cells); flights: 31 rows in 32."""
        got = ingest_lane_fill(v_z, v_x)
        assert got == v_z * v_x / (rows_pad * 128)
        assert got == pytest.approx(fill, abs=1e-3)


class TestL1DistanceKernel:
    @pytest.mark.parametrize("v_z,v_x", [(161, 24), (7548, 12), (33, 161), (5, 2), (256, 2048)])
    def test_matches_oracle(self, v_z, v_x, rng):
        counts = (rng.random((v_z, v_x)) * 100).astype(np.float32)
        counts[rng.random(v_z) < 0.2] = 0.0  # some empty rows
        q = rng.dirichlet(np.ones(v_x)).astype(np.float32)
        got = l1_distance_pallas(jnp.asarray(counts), jnp.asarray(q), interpret=True)
        want = ref.l1_distance_ref(jnp.asarray(counts), jnp.asarray(q))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)

    def test_distance_range(self, rng):
        counts = (rng.random((64, 16)) * 50).astype(np.float32)
        q = rng.dirichlet(np.ones(16)).astype(np.float32)
        tau = np.asarray(l1_distance_pallas(jnp.asarray(counts), jnp.asarray(q), interpret=True))
        assert (tau >= -1e-6).all() and (tau <= 2.0 + 1e-5).all()

    def test_identical_distribution_zero(self):
        q = jnp.asarray([0.25, 0.25, 0.5], jnp.float32)
        counts = q[None, :] * 400
        tau = l1_distance_pallas(counts, q, interpret=True)
        assert float(tau[0]) == pytest.approx(0.0, abs=1e-6)

    def test_rejects_oversize_vx(self):
        with pytest.raises(ValueError):
            l1_distance_pallas(jnp.zeros((8, 5000)), jnp.zeros((5000,)), interpret=True)


class TestAnyActiveKernel:
    @pytest.mark.parametrize("nb,v_z", [(1000, 161), (333, 7548), (17, 33), (4096, 64)])
    def test_matches_oracle(self, nb, v_z, rng):
        w = -(-v_z // 32)
        bm = rng.integers(0, 2**32, size=(nb, w), dtype=np.uint32)
        mask = rng.integers(0, 2**32, size=(w,), dtype=np.uint32)
        got = anyactive_pallas(jnp.asarray(bm), jnp.asarray(mask), interpret=True)
        want = ref.anyactive_ref(jnp.asarray(bm), jnp.asarray(mask))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_empty_mask_skips_all(self, rng):
        bm = rng.integers(0, 2**32, size=(100, 3), dtype=np.uint32)
        got = anyactive_pallas(jnp.asarray(bm), jnp.zeros((3,), jnp.uint32), interpret=True)
        assert not np.asarray(got).any()

    def test_full_mask_reads_nonempty(self, rng):
        bm = rng.integers(0, 2**32, size=(100, 3), dtype=np.uint32)
        bm[0] = 0
        mask = np.full((3,), 0xFFFFFFFF, dtype=np.uint32)
        got = np.asarray(anyactive_pallas(jnp.asarray(bm), jnp.asarray(mask), interpret=True))
        assert not got[0]
        assert got[1:].sum() == (np.asarray(bm[1:]).any(axis=1)).sum()


class TestOpsDispatch:
    def test_ref_on_cpu_by_default(self):
        assert ops.default_impl() == ("pallas" if jax.default_backend() == "tpu" else "ref")

    def test_histogram_jit_shapes(self, rng):
        z = jnp.asarray(rng.integers(0, 10, 100), jnp.int32)
        x = jnp.asarray(rng.integers(0, 5, 100), jnp.int32)
        out = ops.histogram(z, x, v_z=10, v_x=5)
        assert out.shape == (10, 5) and out.dtype == jnp.float32

    @given(seed=st.integers(0, 100))
    @settings(deadline=None, max_examples=20)
    def test_pallas_ref_agree_property(self, seed):
        rng = np.random.default_rng(seed)
        v_z = int(rng.integers(2, 400))
        v_x = int(rng.integers(2, 200))
        n = int(rng.integers(1, 2000))
        z = jnp.asarray(rng.integers(-1, v_z, n), jnp.int32)
        x = jnp.asarray(rng.integers(-1, v_x, n), jnp.int32)
        a = ops.histogram(z, x, v_z=v_z, v_x=v_x, impl="pallas", interpret=True)
        b = ops.histogram(z, x, v_z=v_z, v_x=v_x, impl="ref")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))
