"""Golden equivalence: the mesh rounds vs the single-device round.

`repro.core.pump.DistributedPump` replaces the scheduler's one gathered
window stream with one `ShardedSource` stream per mesh worker feeding
the explicit shard_map pump round. This suite pins the refactor to the
single-stream semantics, in the spirit of `test_device_loop.py`:

  * LOCKSTEP — driven with the same global windows, a pump round and
    a round of the candidate-sharded single-stream mesh scheduler must
    both be bit-identical to the single-device `fused_round` (no mesh):
    counts / n / tau / read_mask / cursor counters for mesh shapes
    sweeping data x model in {1, 2, 8} x {1, 2}, with mid-stream
    admission AND retirement inside the drive (delta_upper is bit-exact
    with the model axis unsharded and allclose under model sharding);
  * the full pump() loop (per-worker visit interleaving) must resolve
    the same queries to the same matching sets as the unsharded server,
    and `prefetch=True` must not change a single bit;
  * the exact-completion fallback of both mesh paths must land on the
    single-device scheduler's true counts.

Multi-device cases run in subprocesses with their own XLA_FLAGS (the
main test process must keep 1 device); the single-worker TestPumpOnOneDevice
cases run in-process on a (1, 1) mesh and cover tier-1.
"""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_subprocess(code: str) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


# Shared prologue for the subprocess cases (pre-dedented; the per-test
# bodies are dedented before concatenation, so the joined script is flat).
_DATASET = textwrap.dedent("""
    import jax, jax.numpy as jnp, numpy as np, json
    from jax.sharding import Mesh
    from repro.core import multiquery as mq
    from repro.core.pump import DistributedPump
    from repro.data.layout import block_layout
    from repro.data.synth import SynthSpec, make_dataset, perturb_distribution

    spec_s = SynthSpec(v_z=64, v_x=16, num_tuples=300_000, k=5, n_close=5, seed=3)
    ds = make_dataset(spec_s)
    blocked = block_layout(ds.z, ds.x, v_z=64, v_x=16, block_size=512, seed=3)
    spec = mq.MultiQuerySpec(v_z=64, v_x=16, max_queries=4)
    rng = np.random.default_rng(9)
    targets = [ds.target] + [
        perturb_distribution(ds.target, d, rng) for d in (0.01, 0.03, 0.05)
    ]
""")


@pytest.mark.slow
class TestPumpGolden:
    def test_lockstep_bit_identical_across_mesh_shapes(self):
        """Same global windows through the single-device scheduler (the
        reference, no mesh), the candidate-sharded mesh scheduler and
        the pump: every per-round quantity of both mesh paths must match
        the reference bit for bit — including a query admitted mid-drive
        and queries retired mid-drive — for worker counts 1/2/8 and model
        shardings 1/2."""
        out = _run_subprocess(_DATASET + textwrap.dedent("""
            K, EPS, DELTA = 5, 0.08, 0.02

            def same(a, b):
                return bool(np.array_equal(np.asarray(a), np.asarray(b)))

            def compare(ref, s, msz):
                return dict(
                    counts=same(ref.state.counts, s.state.counts),
                    n=same(ref.state.n, s.state.n),
                    tau=same(ref.state.tau, s.state.tau),
                    # delta_upper sums delta_i over V_Z: bit-exact with the
                    # model axis unsharded, allclose when it is sharded.
                    du=same(ref.state.delta_upper, s.state.delta_upper)
                       if msz == 1 else
                       bool(np.allclose(np.asarray(ref.state.delta_upper),
                                        np.asarray(s.state.delta_upper),
                                        rtol=1e-5, atol=1e-7)),
                    mask=same(ref.read_mask, s.read_mask),
                    counters=(ref.blocks_read, ref.blocks_considered,
                              ref.tuples_read, ref.rounds)
                             == (s.blocks_read, s.blocks_considered,
                                 s.tuples_read, s.rounds),
                    live=sorted(ref.tickets) == sorted(s.tickets),
                )

            def drive(shape):
                dsz, msz = shape
                mesh = Mesh(np.array(jax.devices()[: dsz * msz]).reshape(dsz, msz),
                            ("data", "model"))
                kw = dict(window=32, seed=0, start_block=7)
                ref = mq.SharedCountsScheduler(blocked, spec, **kw)
                runs = dict(
                    mesh=mq.SharedCountsScheduler(blocked, spec, mesh=mesh, **kw),
                    pump=DistributedPump(blocked, spec, mesh=mesh, **kw),
                )
                scheds = [ref, *runs.values()]
                for t in targets[:3]:
                    for s in scheds:
                        s.admit(t, k=K, eps=EPS, delta=DELTA)
                # shuffled windows so every round straddles worker ranges
                order = np.random.default_rng(1).permutation(blocked.num_blocks)
                checks = {name: [] for name in runs}
                for r in range(12):
                    if r == 3:  # mid-stream admission into the free slot
                        for s in scheds:
                            s.admit(targets[3], k=3, eps=0.1, delta=DELTA)
                    win = order[r * 32 : (r + 1) * 32]
                    for s in scheds:
                        s.run_window(win)
                        s._poll_terminated()  # mid-stream retirement
                    for name, s in runs.items():
                        checks[name].append(compare(ref, s, msz))
                out = {}
                for name, s in runs.items():
                    flat = {k: all(c[k] for c in checks[name]) for k in checks[name][0]}
                    flat.update(
                        retired=len(ref.outcomes),
                        same_retired=set(ref.outcomes) == set(s.outcomes),
                        ids=all(np.array_equal(ref.outcomes[q].ids, s.outcomes[q].ids)
                                for q in ref.outcomes),
                    )
                    out[name] = flat
                return out

            results = {f"{s}/{name}": r
                       for s in [(1, 1), (2, 1), (8, 1), (2, 2), (4, 2)]
                       for name, r in drive(s).items()}
            ok = all(all(v for k, v in r.items() if k != "retired")
                     for r in results.values())
            # the drive must actually exercise retirement somewhere
            ok = ok and any(r["retired"] > 0 for r in results.values())
            print(json.dumps(dict(ok=ok, results=results)))
        """))
        res = json.loads(out.strip().splitlines()[-1])
        assert res["ok"], res["results"]

    def test_pump_loop_matches_single_stream_answers(self):
        """The full pump() loop — per-worker visit interleaving, its own
        pass structure — must resolve the same queries to the same
        matching sets as the unsharded server, and the prefetch-wrapped
        pump must reproduce the plain pump bit for bit."""
        out = _run_subprocess(_DATASET + textwrap.dedent("""
            from repro.serve.fastmatch_server import MatchServer

            ref = MatchServer(blocked, max_queries=4, lookahead=64, seed=11)
            rids_ref = [ref.submit(t, k=5, eps=0.08, delta=0.05) for t in targets]
            res_ref = ref.run_until_idle()

            mesh = Mesh(np.array(jax.devices()).reshape(8, 1), ("data", "model"))
            srv = MatchServer(blocked, max_queries=4, lookahead=64, seed=11,
                              mesh=mesh, pump=True)
            rids = [srv.submit(t, k=5, eps=0.08, delta=0.05) for t in targets]
            res = srv.run_until_idle()

            pre = MatchServer(blocked, max_queries=4, lookahead=64, seed=11,
                              mesh=mesh, pump=True, prefetch=True)
            rids_pre = [pre.submit(t, k=5, eps=0.08, delta=0.05) for t in targets]
            res_pre = pre.run_until_idle()

            ids_ok = all(
                sorted(res[r].ids.tolist()) == sorted(res_ref[rr].ids.tolist())
                and res[r].exact == res_ref[rr].exact
                for r, rr in zip(rids, rids_ref))
            pre_ok = all(
                np.array_equal(res_pre[a].ids, res[b].ids)
                for a, b in zip(rids_pre, rids))
            pre_bits = bool(np.array_equal(
                np.asarray(pre.scheduler.state.counts),
                np.asarray(srv.scheduler.state.counts)))
            # 8 parallel worker streams amortize the poll cadence: far
            # fewer dispatched rounds (hence host polls) per pass
            fewer_rounds = srv.scheduler.rounds < ref.scheduler.rounds
            print(json.dumps(dict(
                ok=bool(ids_ok and pre_ok and pre_bits and fewer_rounds),
                ids_ok=ids_ok, pre_ok=pre_ok, pre_bits=pre_bits,
                rounds=[int(srv.scheduler.rounds), int(ref.scheduler.rounds)],
                syncs=[int(srv.scheduler.host_syncs), int(ref.scheduler.host_syncs)])))
        """))
        res = json.loads(out.strip().splitlines()[-1])
        assert res["ok"], res

    def test_exact_completion_lockstep(self):
        """An unreachable bound forces the exact fallback: the pump's
        per-worker completion chunks and the mesh scheduler's completion
        must land on the same true counts and the same exact answers as
        the single-device completion."""
        out = _run_subprocess(_DATASET + textwrap.dedent("""
            mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))
            kw = dict(window=32, seed=0, start_block=3)
            ref = mq.SharedCountsScheduler(blocked, spec, **kw)
            runs = dict(
                mesh=mq.SharedCountsScheduler(blocked, spec, mesh=mesh, **kw),
                pump=DistributedPump(blocked, spec, mesh=mesh, **kw),
            )
            scheds = [ref, *runs.values()]
            for s in scheds:
                s.admit(targets[0], k=3, eps=0.02, delta=1e-9)
            order = np.random.default_rng(2).permutation(blocked.num_blocks)
            for r in range(4):
                win = order[r * 32 : (r + 1) * 32]
                for s in scheds:
                    s.run_window(win)
            for s in scheds:
                s.complete_remaining()
            eq = {}
            for name, s in runs.items():
                for leaf in ("counts", "n", "tau"):
                    eq[f"{name}_{leaf}"] = bool(np.array_equal(
                        np.asarray(getattr(ref.state, leaf)),
                        np.asarray(getattr(s.state, leaf))))
            eq["all_read"] = all(bool(s.read_mask.all()) for s in scheds)
            eq["ok"] = all(eq.values())
            print(json.dumps(eq))
        """))
        res = json.loads(out.strip().splitlines()[-1])
        assert res["ok"], res


class TestPumpOnOneDevice:
    """Tier-1 (single device) coverage: a (1, 1) mesh pump is the
    degenerate one-worker case and must reproduce the plain scheduler
    bit for bit; construction guards must fire early."""

    @pytest.fixture(scope="class")
    def small(self):
        from repro.data.layout import block_layout
        from repro.data.synth import SynthSpec, make_dataset

        spec = SynthSpec(v_z=24, v_x=8, num_tuples=40_000, k=3, n_close=3, seed=4)
        ds = make_dataset(spec)
        blocked = block_layout(ds.z, ds.x, v_z=24, v_x=8, block_size=256, seed=4)
        return ds, blocked

    def _mesh(self):
        import jax
        from jax.sharding import Mesh

        return Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("data", "model"))

    def test_one_worker_pump_bit_identical_to_scheduler(self, small):
        from repro.core import multiquery as mq
        from repro.core.pump import DistributedPump

        ds, blocked = small
        spec = mq.MultiQuerySpec(v_z=24, v_x=8, max_queries=2)
        ref = mq.SharedCountsScheduler(blocked, spec, window=16, seed=0, start_block=5)
        pmp = DistributedPump(
            blocked, spec, mesh=self._mesh(), window=16, seed=0, start_block=5)
        for s in (ref, pmp):
            s.admit(ds.target, k=3, eps=0.08, delta=0.05)
        for s in (ref, pmp):
            s.pump(max_passes=2)
        np.testing.assert_array_equal(
            np.asarray(ref.state.counts), np.asarray(pmp.state.counts))
        np.testing.assert_array_equal(np.asarray(ref.state.n), np.asarray(pmp.state.n))
        np.testing.assert_array_equal(
            np.asarray(ref.state.tau), np.asarray(pmp.state.tau))
        np.testing.assert_array_equal(ref.read_mask, pmp.read_mask)
        assert ref.rounds == pmp.rounds and ref.tuples_read == pmp.tuples_read
        assert set(ref.outcomes) == set(pmp.outcomes)
        for q in ref.outcomes:
            np.testing.assert_array_equal(ref.outcomes[q].ids, pmp.outcomes[q].ids)

    def test_one_worker_cache_roundtrip_interchangeable(self, small):
        """A pump snapshot must import into a plain scheduler and vice
        versa — the CacheSnapshot layout is global, not per-worker."""
        from repro.core import multiquery as mq
        from repro.core.pump import DistributedPump

        ds, blocked = small
        spec = mq.MultiQuerySpec(v_z=24, v_x=8, max_queries=2)
        pmp = DistributedPump(
            blocked, spec, mesh=self._mesh(), window=16, seed=0, start_block=5)
        pmp.admit(ds.target, k=3, eps=0.08, delta=0.05)
        pmp.pump(max_passes=1)
        snap = pmp.export_cache()
        assert np.asarray(snap.read_mask).shape == (blocked.num_blocks,)

        plain = mq.SharedCountsScheduler(blocked, spec, window=16, seed=9)
        plain.import_cache(snap)
        np.testing.assert_array_equal(
            np.asarray(plain.state.counts), np.asarray(pmp.state.counts))
        np.testing.assert_array_equal(plain.read_mask, pmp.read_mask)

        back = DistributedPump(
            blocked, spec, mesh=self._mesh(), window=16, seed=7)
        back.import_cache(plain.export_cache())
        np.testing.assert_array_equal(back.read_mask, pmp.read_mask)
        assert back.rounds == pmp.rounds and back.tuples_read == pmp.tuples_read

    def test_construction_guards(self, small):
        from repro.core import multiquery as mq
        from repro.core.pump import DistributedPump
        from repro.io import InMemorySource
        from repro.serve.fastmatch_server import MatchServer

        ds, blocked = small
        spec = mq.MultiQuerySpec(v_z=24, v_x=8, max_queries=2)
        with pytest.raises(TypeError, match="BlockedDataset"):
            DistributedPump(InMemorySource(blocked), spec, mesh=self._mesh())
        with pytest.raises(ValueError, match="mesh"):
            MatchServer(blocked, pump=True)
        with pytest.raises(ValueError, match="no axis"):
            DistributedPump(blocked, spec, mesh=self._mesh(), data_axes=("pod",))
