"""Warm-start persistence: checkpoint/restore of the shared sample cache.

The load-bearing property (golden equivalence): a `MatchServer` restored
from a snapshot must answer a freshly submitted query with BIT-IDENTICAL
counts, tau, and result to the uninterrupted server it was saved from —
the warm cache is the whole serving speedup, so a restart must not
degrade it, and a stale cache (different layout/spec) must be rejected
rather than silently corrupting bounds.
"""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import multiquery as mq
from repro.data.layout import block_layout
from repro.data.synth import SynthSpec, make_dataset, perturb_distribution
from repro.serve.fastmatch_server import MatchServer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
K, EPS, DELTA = 5, 0.08, 0.05


@pytest.fixture(scope="module")
def dataset():
    spec = SynthSpec(
        v_z=64, v_x=16, num_tuples=600_000, k=K, n_close=5,
        close_distance=0.02, far_distance=0.3, zipf_a=0.9, seed=5,
    )
    ds = make_dataset(spec)
    blocked = block_layout(ds.z, ds.x, v_z=64, v_x=16, block_size=512, seed=5)
    return ds, blocked


@pytest.fixture(scope="module")
def targets(dataset):
    ds, _ = dataset
    rng = np.random.default_rng(9)
    return [ds.target] + [perturb_distribution(ds.target, d, rng) for d in (0.01, 0.03)]


def _server(blocked, ckpt_dir=None, **kw):
    kw.setdefault("max_queries", 4)
    kw.setdefault("lookahead", 64)
    kw.setdefault("seed", 3)
    return MatchServer(blocked, checkpoint_dir=ckpt_dir, **kw)


def _serve_and_save(blocked, targets, ckpt_dir, **kw):
    server = _server(blocked, str(ckpt_dir), **kw)
    for t in targets:
        server.submit(t, k=K, eps=EPS, delta=DELTA)
    server.run_until_idle()
    server.save_cache()
    return server


class TestSchedulerHooks:
    """export_cache / import_cache on the scheduler itself."""

    def test_export_import_roundtrip(self, dataset, targets):
        _, blocked = dataset
        spec = mq.MultiQuerySpec(v_z=blocked.v_z, v_x=blocked.v_x, max_queries=2)
        a = mq.SharedCountsScheduler(blocked, spec, window=64, seed=1)
        a.admit(targets[0], k=K, eps=EPS, delta=DELTA)
        a.pump()
        snap = a.export_cache()

        b = mq.SharedCountsScheduler(blocked, spec, window=64, seed=777)
        b.import_cache(snap)
        np.testing.assert_array_equal(np.asarray(a.state.counts), np.asarray(b.state.counts))
        np.testing.assert_array_equal(np.asarray(a.state.n), np.asarray(b.state.n))
        np.testing.assert_array_equal(a.read_mask, b.read_mask)
        np.testing.assert_array_equal(a.order, b.order)  # visit order restored, not seed 777's
        assert (a.rounds, a.passes, a.blocks_read, a.tuples_read) == (
            b.rounds, b.passes, b.blocks_read, b.tuples_read)

    def test_place_cache_reshard_in_memory(self, dataset, targets):
        """place_cache re-places a snapshot per cache_pspecs without the
        disk round-trip (single-device mesh here; placement API +
        value preservation exercised)."""
        import jax
        from jax.sharding import Mesh

        from repro.core.distributed import place_cache

        _, blocked = dataset
        spec = mq.MultiQuerySpec(v_z=blocked.v_z, v_x=blocked.v_x, max_queries=2)
        a = mq.SharedCountsScheduler(blocked, spec, window=64, seed=1)
        a.admit(targets[0], k=K, eps=EPS, delta=DELTA)
        a.pump()
        snap = a.export_cache()
        mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
        placed = place_cache(snap, mesh)
        assert "model" in str(placed.counts.sharding)
        np.testing.assert_array_equal(np.asarray(snap.counts), np.asarray(placed.counts))
        np.testing.assert_array_equal(np.asarray(snap.read_mask), np.asarray(placed.read_mask))

    def test_import_with_live_queries_refused(self, dataset, targets):
        _, blocked = dataset
        spec = mq.MultiQuerySpec(v_z=blocked.v_z, v_x=blocked.v_x, max_queries=2)
        a = mq.SharedCountsScheduler(blocked, spec, window=64, seed=1)
        snap = a.export_cache()
        a.admit(targets[0], k=K, eps=EPS, delta=DELTA)
        with pytest.raises(RuntimeError, match="live queries"):
            a.import_cache(snap)

    def test_import_wrong_layout_shape_refused(self, dataset, targets):
        _, blocked = dataset
        spec = mq.MultiQuerySpec(v_z=blocked.v_z, v_x=blocked.v_x, max_queries=2)
        snap = mq.SharedCountsScheduler(blocked, spec, window=64, seed=1).export_cache()
        other = block_layout(
            np.zeros(1024, np.int64), np.zeros(1024, np.int64),
            v_z=blocked.v_z, v_x=blocked.v_x, block_size=512, seed=0,
        )
        b = mq.SharedCountsScheduler(other, spec, window=2, seed=1)
        with pytest.raises(ValueError, match="read_mask"):
            b.import_cache(snap)


class TestGoldenEquivalence:
    """restart == no restart, bit for bit, for the next query."""

    def test_restored_server_bit_identical(self, dataset, targets, tmp_path):
        ds, blocked = dataset
        a = _serve_and_save(blocked, targets, tmp_path)
        b = MatchServer.restore(
            blocked, checkpoint_dir=str(tmp_path), max_queries=4, lookahead=64, seed=999,
        )
        np.testing.assert_array_equal(
            np.asarray(a.scheduler.state.counts), np.asarray(b.scheduler.state.counts))
        np.testing.assert_array_equal(a.scheduler.read_mask, b.scheduler.read_mask)

        # a demanding fresh query: must keep sampling on BOTH servers,
        # exercising identical continued marking/ingest trajectories
        rng = np.random.default_rng(4)
        fresh = perturb_distribution(ds.target, 0.05, rng)
        ra_id = a.submit(fresh, k=K, eps=0.04, delta=0.01)
        ra = a.run_until_idle()[ra_id]
        rb_id = b.submit(fresh, k=K, eps=0.04, delta=0.01)
        rb = b.run_until_idle()[rb_id]

        np.testing.assert_array_equal(ra.ids, rb.ids)
        np.testing.assert_array_equal(  # tau of the served slot, bit for bit
            np.asarray(ra.state.tau), np.asarray(rb.state.tau))
        np.testing.assert_array_equal(
            np.asarray(a.scheduler.state.counts), np.asarray(b.scheduler.state.counts))
        assert ra.exact == rb.exact
        assert ra.tuples_read == rb.tuples_read
        assert ra.rounds == rb.rounds
        assert ra.delta_upper == rb.delta_upper

    def test_warm_restart_answers_covered_query_with_zero_io(
        self, dataset, targets, tmp_path
    ):
        ds, blocked = dataset
        _serve_and_save(blocked, targets, tmp_path)
        b = MatchServer.restore(
            blocked, checkpoint_dir=str(tmp_path), max_queries=4, lookahead=64,
        )
        before = b.metrics["total_tuples_read"]
        rng = np.random.default_rng(11)
        rid = b.submit(perturb_distribution(ds.target, 0.02, rng), k=K, eps=EPS, delta=DELTA)
        res = b.run_until_idle()[rid]
        assert res.tuples_read == 0
        assert b.metrics["total_tuples_read"] == before  # zero new I/O after restart


class TestCrashAtomicityAndStaleness:
    def test_kill_mid_save_falls_back_to_newest_complete_step(
        self, dataset, targets, tmp_path
    ):
        ds, blocked = dataset
        a = _serve_and_save(blocked, targets, tmp_path)
        want_counts = np.asarray(a.scheduler.state.counts)
        # simulate a process dying mid-save: a populated .tmp.<pid> dir
        # (dead pid) and a truncated LATEST pointer
        orphan = tmp_path / "step_9999.tmp.4190001"
        orphan.mkdir()
        (orphan / "arr_0.npy").write_bytes(b"half-written junk")
        (tmp_path / "LATEST").write_text("")
        b = MatchServer.restore(
            blocked, checkpoint_dir=str(tmp_path), max_queries=4, lookahead=64,
        )
        np.testing.assert_array_equal(want_counts, np.asarray(b.scheduler.state.counts))
        # the next successful save sweeps the orphan
        b.save_cache()
        assert not orphan.exists()
        assert (tmp_path / "LATEST").read_text().startswith("step_")

    def test_stale_layout_rejected(self, dataset, targets, tmp_path):
        ds, blocked = dataset
        _serve_and_save(blocked, targets, tmp_path)
        reshuffled = block_layout(
            ds.z, ds.x, v_z=blocked.v_z, v_x=blocked.v_x, block_size=512, seed=6,
        )
        with pytest.raises(ValueError, match="config hash"):
            MatchServer.restore(
                reshuffled, checkpoint_dir=str(tmp_path), max_queries=4, lookahead=64,
            )

    def test_stale_v_x_rejected(self, dataset, targets, tmp_path):
        ds, blocked = dataset
        _serve_and_save(blocked, targets, tmp_path)
        coarser = block_layout(
            ds.z, np.minimum(ds.x, 7), v_z=blocked.v_z, v_x=8, block_size=512, seed=5,
        )
        # max_queries matches the saved spec, so the ONLY hash difference
        # is the layout/content side (v_x) — isolates what this test pins
        with pytest.raises(ValueError, match="config hash"):
            MatchServer.restore(
                coarser, checkpoint_dir=str(tmp_path), max_queries=4, lookahead=64,
            )

    def test_stale_spec_rejected(self, dataset, targets, tmp_path):
        _, blocked = dataset
        _serve_and_save(blocked, targets, tmp_path)
        with pytest.raises(ValueError, match="config hash"):
            MatchServer.restore(
                blocked, checkpoint_dir=str(tmp_path), max_queries=8, lookahead=64,
            )

    def test_missing_checkpoint_raises(self, dataset, tmp_path):
        _, blocked = dataset
        with pytest.raises(FileNotFoundError):
            MatchServer.restore(blocked, checkpoint_dir=str(tmp_path / "empty"))


class TestAutosave:
    def test_retirement_cadence(self, dataset, targets, tmp_path):
        _, blocked = dataset
        server = _server(blocked, str(tmp_path), autosave_every=1)
        for t in targets:
            server.submit(t, k=K, eps=EPS, delta=DELTA)
        server.run_until_idle()
        # retirements alone must have produced a restorable snapshot
        assert server._manager.latest_step() is not None
        b = MatchServer.restore(
            blocked, checkpoint_dir=str(tmp_path), max_queries=4, lookahead=64,
        )
        np.testing.assert_array_equal(
            np.asarray(server.scheduler.state.counts), np.asarray(b.scheduler.state.counts))

    def test_round_cadence(self, dataset, targets, tmp_path):
        _, blocked = dataset
        server = _server(
            blocked, str(tmp_path), autosave_every=0, autosave_rounds=1,
        )
        server.submit(targets[0], k=K, eps=EPS, delta=DELTA)
        server.run_until_idle()
        assert server._manager.latest_step() is not None

    def test_save_without_new_rounds_bumps_step(self, dataset, targets, tmp_path):
        """restore -> save_cache with zero new rounds must write a NEW
        step, never re-write the one LATEST points at (re-writing it
        would reopen the mid-save crash window on the only snapshot)."""
        _, blocked = dataset
        _serve_and_save(blocked, targets, tmp_path)
        b = MatchServer.restore(
            blocked, checkpoint_dir=str(tmp_path), max_queries=4, lookahead=64,
        )
        before = b._manager.latest_step()
        b.save_cache()
        assert b._manager.latest_step() == before + 1

    def test_no_checkpoint_dir_save_refused(self, dataset):
        _, blocked = dataset
        server = _server(blocked, None)
        with pytest.raises(RuntimeError, match="checkpoint_dir"):
            server.save_cache()


@pytest.mark.slow
class TestReshardedRestore:
    """Elastic restart: a snapshot written under one mesh shape restores
    candidate-sharded onto another (1 -> 8 and 8 -> 4 device splits)."""

    def test_reshard_1_to_8_to_4(self, tmp_path):
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.join(REPO, "src")
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        code = textwrap.dedent(f"""
            import json, numpy as np, jax
            from jax.sharding import Mesh
            from repro.data.layout import block_layout
            from repro.data.synth import SynthSpec, make_dataset, perturb_distribution
            from repro.serve.fastmatch_server import MatchServer

            ckpt = {str(tmp_path)!r}
            spec = SynthSpec(v_z=64, v_x=16, num_tuples=400_000, k=5, n_close=5,
                             close_distance=0.02, far_distance=0.3, zipf_a=0.9, seed=5)
            ds = make_dataset(spec)
            blocked = block_layout(ds.z, ds.x, v_z=64, v_x=16, block_size=512, seed=5)
            rng = np.random.default_rng(9)
            fresh = perturb_distribution(ds.target, 0.05, rng)
            kw = dict(max_queries=4, lookahead=64)

            a = MatchServer(blocked, seed=3, checkpoint_dir=ckpt, **kw)
            a.submit(ds.target, k=5, eps=0.08, delta=0.05)
            a.run_until_idle()
            a.save_cache()

            mesh8 = Mesh(np.array(jax.devices()).reshape(1, 8), ("data", "model"))
            b = MatchServer.restore(blocked, checkpoint_dir=ckpt, mesh=mesh8, **kw)
            eq_18 = bool(np.array_equal(np.asarray(a.scheduler.state.counts),
                                        np.asarray(b.scheduler.state.counts)))
            sharded = "model" in str(b.scheduler.state.counts.sharding)
            # re-save the SAME cache from the 8-way sharded server (the
            # snapshot host-gathers the sharded counts) before any new
            # sampling, then restore it onto a 4-device mesh
            b.save_cache()
            mesh4 = Mesh(np.array(jax.devices()[:4]).reshape(1, 4), ("data", "model"))
            c = MatchServer.restore(blocked, checkpoint_dir=ckpt, mesh=mesh4, **kw)
            eq_84 = bool(np.array_equal(np.asarray(b.scheduler.state.counts),
                                        np.asarray(c.scheduler.state.counts)))

            # the same demanding fresh query must now follow an identical
            # continued-sampling trajectory on all three mesh shapes
            results = []
            for srv in (a, b, c):
                rid = srv.submit(fresh, k=5, eps=0.04, delta=0.01)
                results.append(srv.run_until_idle()[rid])
            ra, rb, rc = results

            print(json.dumps(dict(
                eq_18=eq_18, eq_84=eq_84, sharded=sharded,
                ids_18=bool(np.array_equal(ra.ids, rb.ids)),
                ids_84=bool(np.array_equal(rb.ids, rc.ids)),
                tuples=[int(ra.tuples_read), int(rb.tuples_read), int(rc.tuples_read)],
            )))
        """)
        out = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True, env=env,
            timeout=900,
        )
        assert out.returncode == 0, out.stderr[-4000:]
        res = json.loads(out.stdout.strip().splitlines()[-1])
        assert res["eq_18"] and res["eq_84"] and res["sharded"], res
        assert res["ids_18"] and res["ids_84"], res
        assert res["tuples"][0] == res["tuples"][1] == res["tuples"][2], res

    def test_pump_reshard_8_to_4_workers(self, tmp_path):
        """Pump-mode elastic restart: a cache checkpointed under an
        8-worker pump restores into a 4-worker pump (and into the
        single-stream mesh server — snapshots are global, not
        per-worker) with bit-identical counts/read_mask/counters, and a
        fresh query covered by the warm cache answers with bit-identical
        counts/tau/result on every restored width. (A query that must
        KEEP sampling sees each width's own per-worker visit
        interleaving — answers agree as matching sets, compared below —
        but the warm prefix itself must be width-invariant bit for bit.)
        """
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.join(REPO, "src")
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        code = textwrap.dedent(f"""
            import json, numpy as np, jax
            from jax.sharding import Mesh
            from repro.data.layout import block_layout
            from repro.data.synth import SynthSpec, make_dataset, perturb_distribution
            from repro.serve.fastmatch_server import MatchServer

            ckpt = {str(tmp_path)!r}
            spec = SynthSpec(v_z=64, v_x=16, num_tuples=400_000, k=5, n_close=5,
                             close_distance=0.02, far_distance=0.3, zipf_a=0.9, seed=5)
            ds = make_dataset(spec)
            blocked = block_layout(ds.z, ds.x, v_z=64, v_x=16, block_size=512, seed=5)
            rng = np.random.default_rng(9)
            kw = dict(max_queries=4, lookahead=64)

            mesh8 = Mesh(np.array(jax.devices()).reshape(8, 1), ("data", "model"))
            a = MatchServer(blocked, seed=3, checkpoint_dir=ckpt, mesh=mesh8,
                            pump=True, **kw)
            for d in (0.0, 0.01, 0.03):
                a.submit(perturb_distribution(ds.target, d, rng) if d else ds.target,
                         k=5, eps=0.08, delta=0.05)
            a.run_until_idle()
            a.save_cache()

            mesh4 = Mesh(np.array(jax.devices()[:4]).reshape(4, 1), ("data", "model"))
            b = MatchServer.restore(blocked, checkpoint_dir=ckpt, mesh=mesh4,
                                    pump=True, **kw)
            plain = MatchServer.restore(blocked, checkpoint_dir=ckpt, **kw)
            eq = lambda x, y: bool(np.array_equal(np.asarray(x), np.asarray(y)))
            restore_ok = (
                eq(a.scheduler.state.counts, b.scheduler.state.counts)
                and eq(a.scheduler.read_mask, b.scheduler.read_mask)
                and eq(a.scheduler.state.counts, plain.scheduler.state.counts)
                and a.scheduler.rounds == b.scheduler.rounds == plain.scheduler.rounds
                and a.scheduler.tuples_read == b.scheduler.tuples_read
                    == plain.scheduler.tuples_read)

            # covered fresh query: zero new I/O on every width -> the
            # whole answer (ids, tau, counts) must be bit-identical
            covered = perturb_distribution(ds.target, 0.02, np.random.default_rng(4))
            outs = []
            for srv in (a, b, plain):
                rid = srv.submit(covered, k=5, eps=0.08, delta=0.05)
                outs.append(srv.run_until_idle()[rid])
            ra, rb, rp = outs
            covered_ok = (
                eq(ra.ids, rb.ids) and eq(ra.ids, rp.ids)
                and eq(ra.state.tau, rb.state.tau) and eq(ra.state.tau, rp.state.tau)
                and ra.tuples_read == rb.tuples_read == rp.tuples_read == 0)

            # demanding fresh query: must keep sampling; widths may
            # interleave blocks differently but the matching SET agrees
            hard = perturb_distribution(ds.target, 0.05, np.random.default_rng(11))
            sets = []
            for srv in (a, b, plain):
                rid = srv.submit(hard, k=5, eps=0.04, delta=0.01)
                r = srv.run_until_idle()[rid]
                sets.append((sorted(r.ids.tolist()), r.exact))
            print(json.dumps(dict(
                restore_ok=restore_ok, covered_ok=covered_ok,
                hard_ok=sets[0] == sets[1] == sets[2])))
        """)
        out = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True, env=env,
            timeout=900,
        )
        assert out.returncode == 0, out.stderr[-4000:]
        res = json.loads(out.stdout.strip().splitlines()[-1])
        assert res["restore_ok"], res
        assert res["covered_ok"], res
        assert res["hard_ok"], res
