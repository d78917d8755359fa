"""Distributed components. Multi-device cases run in subprocesses with
their own XLA_FLAGS (the main test process must keep 1 device)."""

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
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


@pytest.mark.slow
class TestDistributedHistSim:
    def test_unified_round_matches_single_device_scheduler(self):
        """The unified make_distributed_round over MultiQueryState (counts
        sharded over "model", one psum per round, vmapped per-query stats)
        must reproduce the single-device SharedCountsScheduler for 4
        concurrent queries: ingesting exactly the blocks the scheduler
        read yields identical counts and per-slot tau/bounds/top-k."""
        out = _run_subprocess("""
            import jax, jax.numpy as jnp, numpy as np, json
            from jax.sharding import Mesh, PartitionSpec as P, NamedSharding
            from repro.core import histsim
            from repro.core import multiquery as mq
            from repro.core.distributed import make_distributed_round, multi_state_pspecs
            from repro.data.layout import block_layout
            from repro.data.synth import SynthSpec, make_dataset, perturb_distribution

            mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))
            spec_s = SynthSpec(v_z=64, v_x=16, num_tuples=300_000, k=5, n_close=5, seed=3)
            ds = make_dataset(spec_s)
            blocked = block_layout(ds.z, ds.x, v_z=64, v_x=16, block_size=512, seed=3)
            spec = mq.MultiQuerySpec(v_z=64, v_x=16, max_queries=4)
            rng = np.random.default_rng(9)
            targets = [ds.target] + [
                perturb_distribution(ds.target, d, rng) for d in (0.01, 0.03, 0.05)
            ]

            # single-device scheduler: 4 live queries, a few fused windows
            sched = mq.SharedCountsScheduler(blocked, spec, window=64, seed=0, start_block=0)
            for t in targets:
                sched.admit(t, k=5, eps=0.08, delta=0.05)
            for p in range(0, 6 * 64, 64):
                sched.run_window(sched.order[p : p + 64])

            # distributed: fresh state, same queries, the same tuples the
            # scheduler read, ingested in ONE sharded round
            state = mq.init_multi_state(spec)
            for slot, t in enumerate(targets):
                q = np.asarray(t, np.float64).ravel()
                q = (q / q.sum()).astype(np.float32)
                state = mq.admit_slot(
                    state, jnp.asarray(slot, jnp.int32), jnp.asarray(q),
                    jnp.asarray(5, jnp.int32), jnp.asarray(0.08, jnp.float32),
                    jnp.asarray(0.05, jnp.float32), spec=spec)
            read = np.where(sched.read_mask)[0]
            z = blocked.z_blocks[read].reshape(-1)
            x = blocked.x_blocks[read].reshape(-1)
            pad = (-len(z)) % 4  # data-axis divisibility
            z = np.concatenate([z, np.full(pad, -1, np.int32)])
            x = np.concatenate([x, np.full(pad, -1, np.int32)])
            specs = multi_state_pspecs()
            state = jax.device_put(
                state, jax.tree.map(lambda s: NamedSharding(mesh, s), specs))
            zs = jax.device_put(jnp.asarray(z), NamedSharding(mesh, P("data")))
            xs = jax.device_put(jnp.asarray(x), NamedSharding(mesh, P("data")))
            rnd = make_distributed_round(mesh, spec)
            with mesh:
                out = rnd(state, zs, xs)

            ids_ok = all(
                np.array_equal(
                    np.asarray(histsim.top_k_ids(mq.slot_state(out, s), 5)),
                    np.asarray(histsim.top_k_ids(mq.slot_state(sched.state, s), 5)))
                for s in range(4))
            result = {
                "counts": bool(np.array_equal(
                    np.asarray(out.counts), np.asarray(sched.state.counts))),
                "n": bool(np.array_equal(np.asarray(out.n), np.asarray(sched.state.n))),
                "tau": bool(np.allclose(
                    np.asarray(out.tau), np.asarray(sched.state.tau), atol=1e-5)),
                "du": bool(np.allclose(
                    np.asarray(out.delta_upper), np.asarray(sched.state.delta_upper),
                    rtol=1e-4, atol=1e-6)),
                "ids": bool(ids_ok),
            }
            result["ok"] = all(result.values())
            print(json.dumps(result))
        """)
        res = json.loads(out.strip().splitlines()[-1])
        assert res["ok"], res

    def test_mesh_server_matches_single_device(self):
        """MatchServer(mesh=...) — counts candidate-sharded, explicit
        shard_map rounds — must resolve the same queries to the same
        matching sets as the unsharded server."""
        out = _run_subprocess("""
            import jax, jax.numpy as jnp, numpy as np, json
            from jax.sharding import Mesh
            from repro.data.layout import block_layout
            from repro.data.synth import SynthSpec, make_dataset, perturb_distribution
            from repro.serve.fastmatch_server import MatchServer

            mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))
            spec_s = SynthSpec(v_z=64, v_x=16, num_tuples=300_000, k=5, n_close=5, seed=3)
            ds = make_dataset(spec_s)
            blocked = block_layout(ds.z, ds.x, v_z=64, v_x=16, block_size=512, seed=3)
            rng = np.random.default_rng(9)
            targets = [ds.target] + [
                perturb_distribution(ds.target, d, rng) for d in (0.01, 0.03, 0.05)
            ]

            ref = MatchServer(blocked, max_queries=4, lookahead=128, seed=11)
            rids_ref = [ref.submit(t, k=5, eps=0.08, delta=0.05) for t in targets]
            res_ref = ref.run_until_idle()

            srv = MatchServer(blocked, max_queries=4, lookahead=128, seed=11, mesh=mesh)
            rids = [srv.submit(t, k=5, eps=0.08, delta=0.05) for t in targets]
            res = srv.run_until_idle()

            ok = all(
                sorted(res[r].ids.tolist()) == sorted(res_ref[rr].ids.tolist())
                and res[r].exact == res_ref[rr].exact
                for r, rr in zip(rids, rids_ref))
            print(json.dumps({"ok": bool(ok),
                              "tuples": srv.metrics["total_tuples_read"],
                              "tuples_ref": ref.metrics["total_tuples_read"]}))
        """)
        res = json.loads(out.strip().splitlines()[-1])
        assert res["ok"], res

    def test_mesh_refuses_candidates_the_model_axis_does_not_divide(self):
        """V_Z = 64 on a model axis of 3: the mesh server, the mesh
        scheduler and the pump all refuse at construction, naming V_Z."""
        out = _run_subprocess("""
            import jax, numpy as np, json
            from jax.sharding import Mesh
            from repro.core import multiquery as mq
            from repro.core.pump import DistributedPump
            from repro.data.layout import block_layout
            from repro.data.synth import SynthSpec, make_dataset
            from repro.serve.fastmatch_server import MatchServer

            ds = make_dataset(SynthSpec(v_z=64, v_x=16, num_tuples=20_000, k=3, seed=3))
            blocked = block_layout(ds.z, ds.x, v_z=64, v_x=16, block_size=256, seed=3)
            spec = mq.MultiQuerySpec(v_z=64, v_x=16, max_queries=2)
            mesh = Mesh(np.array(jax.devices()[:6]).reshape(2, 3), ("data", "model"))
            builds = dict(
                server=lambda: MatchServer(blocked, max_queries=2, mesh=mesh),
                scheduler=lambda: mq.SharedCountsScheduler(blocked, spec, mesh=mesh),
                pump=lambda: DistributedPump(blocked, spec, mesh=mesh),
            )
            refused = {}
            for name, build in builds.items():
                try:
                    build()
                    refused[name] = False
                except ValueError as e:
                    refused[name] = "V_Z=64 must divide by model axis size 3" in str(e)
            print(json.dumps(dict(ok=all(refused.values()), refused=refused)))
        """)
        res = json.loads(out.strip().splitlines()[-1])
        assert res["ok"], res


@pytest.mark.slow
class TestPipelineParallel:
    def test_gpipe_matches_sequential(self):
        out = _run_subprocess("""
            import jax, jax.numpy as jnp, numpy as np, json
            from jax.sharding import Mesh, PartitionSpec as P, NamedSharding
            from repro.distributed.pipeline import make_pipeline_forward, stack_stage_params, transformer_stage_fn

            mesh = Mesh(np.array(jax.devices()).reshape(4, 2, 1), ("pod", "data", "model"))
            D = 16
            def layer_fn(lp, x):
                return jnp.tanh(x @ lp["w"] + lp["b"])
            rng = np.random.default_rng(0)
            n_stages, layers_per_stage = 4, 2
            stages = []
            for s in range(n_stages):
                lw = jnp.asarray(rng.normal(size=(layers_per_stage, D, D)).astype(np.float32) * 0.3)
                lb = jnp.asarray(np.zeros((layers_per_stage, D), np.float32))
                stages.append({"w": lw, "b": lb})
            stacked = stack_stage_params(stages)

            fwd = make_pipeline_forward(
                transformer_stage_fn(layer_fn, layers_per_stage), mesh,
                n_stages=n_stages, n_microbatches=4,
            )
            x = jnp.asarray(rng.normal(size=(8, D)).astype(np.float32))
            with mesh:
                y = jax.jit(fwd)(stacked, x)

            # sequential reference
            ref = x
            for s in range(n_stages):
                for l in range(layers_per_stage):
                    ref = jnp.tanh(ref @ stages[s]["w"][l] + stages[s]["b"][l])
            ok = np.allclose(np.asarray(y), np.asarray(ref), atol=1e-5)
            print(json.dumps({"ok": bool(ok)}))
        """)
        assert json.loads(out.strip().splitlines()[-1])["ok"]


class TestShardingRules:
    def test_param_specs_resolution(self):
        import jax
        from jax.sharding import Mesh, PartitionSpec as P

        from repro.configs import get_smoke_config
        from repro.distributed.sharding import param_pspecs
        from repro.models.model_zoo import get_model

        mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
        cfg = get_smoke_config("granite_8b")
        model = get_model(cfg)
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        specs = param_pspecs(shapes, mesh)
        flat = {
            "/".join(str(getattr(k, "key", getattr(k, "idx", ""))) for k in path): s
            for path, s in jax.tree_util.tree_flatten_with_path(specs)[0]
        }
        assert flat["embed/table"] == P("model", "data")
        assert flat["layers/0/attn/wq"] == P("data", "model")
        assert flat["layers/0/attn/wo"] == P("model", "data")
        assert flat["layers/0/mlp/w_down"] == P("model", "data")
        assert flat["layers/0/attn_norm/scale"] == P(None)
        assert flat["lm_head/w"] == P("data", "model")

    def test_stacked_scan_params_get_layer_dim_none(self):
        import dataclasses

        import jax
        from jax.sharding import Mesh, PartitionSpec as P

        from repro.configs import get_smoke_config
        from repro.distributed.sharding import param_pspecs
        from repro.models.model_zoo import get_model

        mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
        cfg = dataclasses.replace(get_smoke_config("granite_8b"), scan_layers=True)
        model = get_model(cfg)
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        specs = param_pspecs(shapes, mesh)
        assert specs["layers"]["attn"]["wq"] == P(None, "data", "model")

    def test_divisibility_guard(self):
        import jax
        from jax.sharding import Mesh, PartitionSpec as P

        from repro.distributed.sharding import guard_pspec

        mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
        # mesh axes have size 1 -> everything divisible
        assert guard_pspec((7, 3), P("data", "model"), mesh) == P("data", "model")

    def test_batch_pspec_fallbacks(self):
        import jax
        from jax.sharding import Mesh

        from repro.distributed.sharding import batch_pspec

        mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
        spec = batch_pspec(mesh, batch_size=4)
        assert spec[0] in ("data", ("data",), None)  # divisible by 1
