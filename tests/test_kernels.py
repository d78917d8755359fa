"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # property tests need it; skip on minimal installs
from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.anyactive import anyactive_pallas
from repro.kernels.histogram import histogram_pallas
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
