"""Golden suite for the pluggable metric & query-type layer (PR 9).

Four contracts, each pinned here:

  1. ORACLE — every registry metric's distance ops (ref / Pallas
     interpret, single- and two-sweep, Q in {1, 3, 8}) agree with a
     float64 numpy brute force, including the zero-mass-row and
     lane-padding conventions; the one Pallas path the engine runs
     matches the ref at the deployments' shapes.
  2. BIT-IDENTITY — the l1 arm of the refactor reproduces the
     PRE-REFACTOR implementation bit for bit. The old ref bodies are
     FROZEN below verbatim (from the pre-metric-layer ref.py); if a
     metrics.py change makes l1 drift by even one ULP, this fails.
  3. BOUNDS — the per-metric bound family is registered for exactly the
     kernel registry's metrics, l1 composes to Theorem 1 unchanged, and
     `assign_closeness` labels/retires with the promised semantics
     (early-reject: clearly-far candidates leave the active set first).
  4. SERVE — a closeness query admitted MID-STREAM next to live top-k
     queries shares their counts and returns correct labels, for every
     metric.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import bounds
from repro.core import deviations as dev
from repro.kernels import metrics, ops

jax.config.update("jax_platform_name", "cpu")

METRIC_NAMES = list(metrics.METRIC_NAMES)
QS = [1, 3, 8]
# (V_Z, V_X) of the served deployments: FLIGHTS-q1/q2, TAXI-q1, POLICE-q2.
SERVED_SHAPES = [(161, 24), (7548, 24), (2110, 5)]


# ---------------------------------------------------------------------------
# float64 numpy brute-force oracles (independent re-derivation, not jnp)
# ---------------------------------------------------------------------------


def _normalize_rows(counts):
    counts = np.asarray(counts, np.float64)
    row = counts.sum(axis=1, keepdims=True)
    return counts / np.maximum(row, 1.0)


def _oracle(counts, q_hat, metric):
    """(Q, V_Z) float64 distances, straight from the definitions."""
    r = _normalize_rows(counts)  # (V_Z, V_X)
    q = np.asarray(q_hat, np.float64)  # (Q, V_X)
    out = np.zeros((q.shape[0], r.shape[0]))
    for qi in range(q.shape[0]):
        for zi in range(r.shape[0]):
            p, t = r[zi], q[qi]
            if metric == "l1":
                out[qi, zi] = np.abs(p - t).sum()
            elif metric == "chi2":
                s = p + t
                d = p - t
                out[qi, zi] = np.where(s > 0, d * d / np.where(s > 0, s, 1), 0).sum()
            elif metric == "hellinger":
                out[qi, zi] = 0.5 * ((np.sqrt(p) - np.sqrt(t)) ** 2).sum()
            else:
                raise AssertionError(metric)
    return out


def _case(v_z, v_x, q, seed, zero_rows=True):
    rng = np.random.default_rng(seed)
    counts = rng.integers(0, 200, size=(v_z, v_x)).astype(np.float32)
    if zero_rows:
        counts[:: max(v_z // 7, 2)] = 0.0  # unsampled candidates
    q_hat = rng.dirichlet(np.full(v_x, 0.7), size=q).astype(np.float32)
    return jnp.asarray(counts), jnp.asarray(q_hat)


class TestOracle:
    @pytest.mark.parametrize("metric", METRIC_NAMES)
    @pytest.mark.parametrize("q", QS)
    def test_ref_and_xla_match_bruteforce(self, metric, q):
        counts, q_hat = _case(37, 24, q, seed=17 * METRIC_NAMES.index(metric) + q)
        want = _oracle(counts, q_hat, metric)
        got_ref = np.asarray(metrics.distance_multi_ref(counts, q_hat, metric=metric))
        np.testing.assert_allclose(got_ref, want, rtol=2e-5, atol=2e-6)

    @pytest.mark.parametrize("metric", METRIC_NAMES)
    @pytest.mark.parametrize("q", QS)
    @pytest.mark.parametrize("sweeps", [1, 2])
    def test_pallas_interpret_matches_ref(self, metric, q, sweeps):
        # Odd shapes exercise the padding paths; sweeps=2 the lane tiling.
        counts, q_hat = _case(37, 300, q, seed=7)  # 300 -> 3 lane tiles
        got = np.asarray(
            metrics.distance_multi_pallas(
                counts, q_hat, metric=metric, z_tile=8,
                x_tile=128 if sweeps == 2 else 4096,
                sweeps=sweeps, interpret=True,
            )
        )
        want = np.asarray(metrics.distance_multi_ref(counts, q_hat, metric=metric))
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)

    @pytest.mark.parametrize("metric", METRIC_NAMES)
    @pytest.mark.parametrize("q", [1, 8])
    @pytest.mark.parametrize("v_z,v_x", SERVED_SHAPES)
    def test_served_shapes_match_ref(self, v_z, v_x, q, metric):
        # The kernel and layout the TPU engine runs (its own tiles, the
        # sweep layout chosen from the padded V_X), at the served shapes.
        counts, q_hat = _case(v_z, v_x, q, seed=v_z + q)
        got = np.asarray(
            ops.distance_multi(counts, q_hat, metric=metric, impl="pallas", interpret=True)
        )
        want = np.asarray(metrics.distance_multi_ref(counts, q_hat, metric=metric))
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)

    @pytest.mark.parametrize("metric", METRIC_NAMES)
    def test_single_query_is_row_zero(self, metric):
        counts, q_hat = _case(19, 12, 1, seed=11)
        one = np.asarray(metrics.distance_ref(counts, q_hat[0], metric=metric))
        multi = np.asarray(metrics.distance_multi_ref(counts, q_hat, metric=metric))
        np.testing.assert_array_equal(one, multi[0])

    @pytest.mark.parametrize("metric", METRIC_NAMES)
    def test_empty_row_convention(self, metric):
        # Zero-mass rows estimate the empty histogram: tau = ||q||_1 = 1
        # for l1/chi2, 0.5 * sum (sqrt 0 - sqrt q)^2 = 0.5 for hellinger.
        counts = jnp.zeros((3, 8), jnp.float32)
        q_hat = jnp.full((1, 8), 0.125, jnp.float32)
        tau = np.asarray(metrics.distance_multi_ref(counts, q_hat, metric=metric))
        want = metrics.coerce_metric(metric).empty_row_tau
        np.testing.assert_allclose(tau, want, rtol=1e-6)

    def test_ops_entrypoint_dispatches_metric(self):
        counts, q_hat = _case(29, 16, 3, seed=5)
        for metric in METRIC_NAMES:
            got = np.asarray(ops.distance_multi(counts, q_hat, metric=metric))
            want = _oracle(counts, q_hat, metric)
            np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
        # distinct metrics give distinct answers (the axis is live)
        a = np.asarray(ops.distance_multi(counts, q_hat, metric="l1"))
        b = np.asarray(ops.distance_multi(counts, q_hat, metric="chi2"))
        assert not np.array_equal(a, b)

    def test_unknown_metric_rejected(self):
        counts, q_hat = _case(8, 8, 1, seed=0)
        with pytest.raises(ValueError, match="metric"):
            metrics.distance_multi_ref(counts, q_hat, metric="tv")


# ---------------------------------------------------------------------------
# l1 bit-identity against the FROZEN pre-refactor implementations
# ---------------------------------------------------------------------------

# Verbatim copies of the pre-metric-layer ref.py bodies (PR 8 tree).
# Do not "modernize" these — their whole value is staying frozen.


def _frozen_l1_distance_ref(counts, q_hat):
    counts = counts.astype(jnp.float32)
    row = jnp.sum(counts, axis=1, keepdims=True)
    r_hat = counts / jnp.maximum(row, 1.0)
    return jnp.sum(jnp.abs(r_hat - q_hat[None, :].astype(jnp.float32)), axis=1)


def _frozen_l1_distance_multi_ref(counts, q_hat):
    counts = counts.astype(jnp.float32)
    row = jnp.sum(counts, axis=1, keepdims=True)
    r_hat = counts / jnp.maximum(row, 1.0)
    q = q_hat.astype(jnp.float32)
    return jnp.stack(
        [jnp.sum(jnp.abs(r_hat - q[i][None, :]), axis=1) for i in range(q.shape[0])]
    )


class TestL1BitIdentity:
    @pytest.mark.parametrize("q", QS)
    def test_refs_bit_identical(self, q):
        counts, q_hat = _case(53, 24, q, seed=23)
        np.testing.assert_array_equal(
            np.asarray(metrics.distance_multi_ref(counts, q_hat, metric="l1")),
            np.asarray(_frozen_l1_distance_multi_ref(counts, q_hat)),
        )
        np.testing.assert_array_equal(
            np.asarray(metrics.distance_ref(counts, q_hat[0], metric="l1")),
            np.asarray(_frozen_l1_distance_ref(counts, q_hat[0])),
        )

    @pytest.mark.parametrize("q", QS)
    def test_jaxpr_identical(self, q):
        # Stronger than value equality: the l1 instance EMITS the same
        # program as the frozen body — zero added ops, so the compiled
        # artifact cannot differ either.
        counts, q_hat = _case(53, 24, q, seed=23)
        new = jax.make_jaxpr(
            lambda c, t: metrics.distance_multi_ref(c, t, metric="l1")
        )(counts, q_hat)
        old = jax.make_jaxpr(_frozen_l1_distance_multi_ref)(counts, q_hat)
        assert str(new) == str(old)

    def test_ops_l1_alias_bit_identical(self):
        counts, q_hat = _case(53, 24, 4, seed=29)
        np.testing.assert_array_equal(
            np.asarray(ops.l1_distance_multi(counts, q_hat)),
            np.asarray(ops.distance_multi(counts, q_hat, metric="l1")),
        )
        np.testing.assert_array_equal(
            np.asarray(ops.distance_multi(counts, q_hat, metric="l1")),
            np.asarray(_frozen_l1_distance_multi_ref(counts, q_hat)),
        )

    def test_metric_log_delta_l1_is_theorem1(self):
        eps = jnp.asarray([0.01, 0.06, 0.3], jnp.float32)
        n = jnp.asarray([10.0, 1e4, 1e6], jnp.float32)
        np.testing.assert_array_equal(
            np.asarray(bounds.metric_log_delta(eps, n, 24, metric="l1")),
            np.asarray(bounds.theorem1_log_delta(eps, n, 24)),
        )


# ---------------------------------------------------------------------------
# Bound family + closeness retirement rule
# ---------------------------------------------------------------------------


class TestBounds:
    def test_every_registry_metric_has_a_bound(self):
        # A metric cannot ship a kernel score without a bound family.
        assert tuple(bounds.BOUNDED_METRICS) == tuple(metrics.METRIC_NAMES)
        for m in metrics.METRIC_NAMES:
            v = float(bounds.metric_l1_budget(0.1, m))
            assert 0.0 < v <= 0.1  # budgets shrink (l1 is the identity)

    @pytest.mark.parametrize("metric", ["chi2", "hellinger"])
    def test_non_l1_bounds_are_conservative(self, metric):
        # Same eps, same n: a non-l1 metric may never claim MORE
        # confidence than the l1 bound it routes through.
        eps, n = 0.1, 5e4
        ld = float(bounds.metric_log_delta(eps, n, 24, metric=metric))
        ld_l1 = float(bounds.metric_log_delta(eps, n, 24, metric="l1"))
        assert ld >= ld_l1

    @pytest.mark.parametrize("metric", METRIC_NAMES)
    def test_metric_epsilon_inverts_budget(self, metric):
        # metric_epsilon(n, delta) is the metric-space radius whose
        # budget reproduces theorem1_epsilon(n, delta).
        n, delta, v_x = 3e4, 0.01, 24
        eps_m = float(bounds.metric_epsilon(n, delta, v_x, metric=metric))
        back = float(bounds.metric_l1_budget(eps_m, metric))
        want = float(bounds.theorem1_epsilon(n, delta, v_x))
        np.testing.assert_allclose(back, want, rtol=1e-5)

    def test_closeness_labels_and_termination(self):
        tau = jnp.asarray([0.02, 0.10, 0.19, 0.60], jnp.float32)
        st = dev.assign_closeness(
            tau, jnp.full((4,), 1e5, jnp.float32),
            eps=0.1, gap=0.1, delta=0.05, v_x=24,
        )
        # threshold = eps + gap/2 = 0.15
        np.testing.assert_array_equal(
            np.asarray(st.in_top_k), [True, True, False, False]
        )
        # margins: max(tau - eps, (eps + gap) - tau) — always >= gap/2
        np.testing.assert_allclose(
            np.asarray(st.eps_i), [0.18, 0.10, 0.09, 0.50], rtol=1e-5
        )
        # enough samples -> every slot certified, bound fired
        assert float(st.delta_upper) < 0.05
        assert not bool(np.asarray(st.active).any())

    def test_closeness_early_reject(self):
        # A clearly-far candidate (huge margin) must leave the active
        # set BEFORE a borderline one (margin == gap/2) — the engine
        # analogue of the closeness testers' cheap far-rejection.
        tau = jnp.asarray([0.21, 0.90], jnp.float32)  # borderline, far
        for n in (2e3, 1e4, 1e5):
            st = dev.assign_closeness(
                tau, jnp.full((2,), n, jnp.float32),
                eps=0.1, gap=0.2, delta=0.01, v_x=24,
            )
            a = np.asarray(st.active)
            if a[1]:
                assert a[0]  # far never outlasts borderline
        # and at moderate n, far already retired while borderline active
        st = dev.assign_closeness(
            tau, jnp.full((2,), 2e3, jnp.float32),
            eps=0.1, gap=0.2, delta=0.01, v_x=24,
        )
        assert bool(np.asarray(st.active)[0]) and not bool(np.asarray(st.active)[1])

    @pytest.mark.parametrize("metric", ["chi2", "hellinger"])
    def test_closeness_other_metrics(self, metric):
        tau = jnp.asarray([0.05, 0.5], jnp.float32)
        st = dev.assign_closeness(
            tau, jnp.full((2,), 1e6, jnp.float32),
            eps=0.2, gap=0.2, delta=0.05, v_x=24, metric=metric,
        )
        np.testing.assert_array_equal(np.asarray(st.in_top_k), [True, False])


# ---------------------------------------------------------------------------
# Mixed-type serving over one shared counts matrix
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def served():
    from repro.data.layout import block_layout
    from repro.data.synth import SynthSpec, make_dataset

    spec = SynthSpec(
        v_z=48, v_x=16, num_tuples=120_000, k=5, n_close=6,
        close_distance=0.03, far_distance=0.4, zipf_a=1.0, seed=3,
    )
    ds = make_dataset(spec)
    blocked = block_layout(ds.z, ds.x, v_z=48, v_x=16, block_size=512, seed=3)
    return ds, blocked


class TestMixedServe:
    def test_topk_and_closeness_share_stream(self, served):
        from repro.serve.fastmatch_server import MatchServer

        ds, blocked = served
        srv = MatchServer(blocked, max_queries=4, lookahead=64, seed=3)
        rid_top = srv.submit(ds.target, k=5, eps=0.08, delta=0.05)
        rid_close = srv.submit_closeness(ds.target, eps=0.10, gap=0.25, delta=0.05)
        res = srv.run_until_idle()
        rt, rc = res[rid_top], res[rid_close]
        assert rt.qtype == "topk" and rc.qtype == "closeness"
        tau = ds.true_dists
        assert sorted(rt.ids.tolist()) == sorted(
            np.argsort(tau, kind="stable")[:5].tolist()
        )
        close_set = set(rc.ids.tolist())
        # promise: everything within eps labeled close, nothing beyond
        # eps + gap labeled close (gap region unconstrained)
        assert set(np.flatnonzero(tau <= 0.10).tolist()) <= close_set
        assert close_set.isdisjoint(np.flatnonzero(tau >= 0.35).tolist())
        # nearest-first by the scheduler's tau estimates at retirement
        est = np.asarray(rc.state.tau)
        assert list(rc.ids) == sorted(rc.ids.tolist(), key=lambda i: est[i])

    def test_mid_stream_admission_no_recompile(self, served):
        from repro.serve.fastmatch_server import MatchServer

        ds, blocked = served
        srv = MatchServer(blocked, max_queries=2, lookahead=32, seed=3)
        rid_top = srv.submit(ds.target, k=5, eps=0.08, delta=0.05)
        # drive a few windows so counts accumulate, then admit the
        # closeness query mid-stream into the live scheduler
        for _ in range(3):
            srv.step()
        tuples_before = srv.scheduler.tuples_read
        assert tuples_before > 0
        rid_close = srv.submit_closeness(ds.target, eps=0.10, gap=0.25, delta=0.05)
        res = srv.run_until_idle()
        rc = res[rid_close]
        # the late query rode the shared counts: its live-read counter
        # excludes what was sampled before admission
        assert rc.tuples_read <= srv.scheduler.tuples_read - tuples_before
        tau = ds.true_dists
        close_set = set(rc.ids.tolist())
        assert set(np.flatnonzero(tau <= 0.10).tolist()) <= close_set
        assert close_set.isdisjoint(np.flatnonzero(tau >= 0.35).tolist())

    @pytest.mark.parametrize("metric", ["chi2", "hellinger"])
    def test_non_l1_server_topk(self, served, metric):
        from repro.serve.fastmatch_server import MatchServer

        ds, blocked = served
        srv = MatchServer(blocked, max_queries=2, lookahead=64, seed=3, metric=metric)
        rid = srv.submit(ds.target, k=5, eps=0.3, delta=0.05)
        out = srv.run_until_idle()[rid]
        want = _oracle(
            ds.true_hists * 1.0, np.asarray([ds.target / ds.target.sum()]), metric
        )[0]
        # true_hists are already normalized rows — renormalize guard
        assert sorted(out.ids.tolist()) == sorted(
            np.argsort(want, kind="stable")[:5].tolist()
        )

    def test_closeness_rejects_bad_args(self, served):
        from repro.serve.fastmatch_server import MatchServer

        ds, blocked = served
        srv = MatchServer(blocked, max_queries=2, lookahead=64)
        with pytest.raises(ValueError, match="gap"):
            srv.submit_closeness(ds.target, eps=0.1, gap=0.0)
        with pytest.raises(ValueError, match="eps"):
            srv.submit_closeness(ds.target, eps=-0.1, gap=0.1)
