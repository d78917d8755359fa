"""Data-parallel pump scaling: ingest throughput vs worker count.

The pump's claim (`repro.core.pump`): with W data-parallel workers each
feeding its own `ShardedSource` window into the explicit-collective
round, a pass over the dataset takes ~W times fewer dispatched rounds —
and with them ~W times fewer host polls at a fixed ``poll_every`` —
while the answers stay at single-stream recall. This benchmark serves
the same query batch through `MatchServer(mesh=..., pump=True)` at
worker counts 1 / 2 / 8 plus the plain single-stream server, and measures:

  * tuples ingested/sec — wall-clock ingest bandwidth of the batch
    (on real accelerator pods this scales with aggregate worker I/O;
    on the CPU test substrate the *structural* metrics below are the
    machine-checkable scaling claim)
  * rounds + host syncs — dispatched device rounds and device↔host
    polls for the batch; the W-worker pump covers a pass in ~1/W the
    rounds, so both drop ~Wx
  * recall — against planted ground truth, must match the single
    stream at every width

The workers are 8 virtual CPU devices (``JAX_PLATFORMS=cpu`` and
``--xla_force_host_platform_device_count=8``, set by the child process
this suite spawns, on any machine, accelerator or not), so its wall
times are XLA:CPU times, and the report's ``config`` says so.

Embedded golden check: the 1-worker pump IS the single stream (same
visit order, same windows), so its trajectory must reproduce the plain
server's tuple count exactly.

Reported rows (benchmarks/run.py CSV schema):

  pump_w{W}_serve       — us per served batch, derived = tuples read
  pump_tuples_per_sec_w8 — derived = tuples ingested/sec at 8 workers
  pump_sync_reduction_w8 — derived = host syncs w1 / w8 (>= 2 = pass)
  pump_rounds_reduction_w8 — derived = rounds w1 / w8 (>= 2 = pass)

Machine-readable results land in benchmarks/results/BENCH_pump.json
and are regression-gated against benchmarks/baselines/BENCH_pump.json
by benchmarks/check_regression.py on the multi-device CI job.

Set PUMP_BENCH_SMOKE=1 for the tiny CI configuration (same code path;
exits non-zero if recall degrades vs the single stream, the 1-worker
pump diverges from it, or the w8 sync/round reduction drops below 2x).
"""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import time

import numpy as np

from benchmarks.common import env_stamp

SMOKE = bool(int(os.environ.get("PUMP_BENCH_SMOKE", "0")))
WORKERS = (1, 2, 8)
N_QUERIES = 8
K, DELTA, EPS = 10, 0.01, 0.07
LOOKAHEAD = 8 if SMOKE else 64  # per-worker window: small enough for many rounds

RESULTS = pathlib.Path(__file__).parent / "results"


def _build():
    from repro.data.layout import block_layout
    from repro.data.synth import SynthSpec, make_dataset

    spec = SynthSpec(
        v_z=64, v_x=16, num_tuples=300_000 if SMOKE else 4_000_000, k=K, n_close=10,
        close_distance=0.02, far_distance=0.3, zipf_a=1.0, close_rank="head", seed=42,
    )
    ds = make_dataset(spec)
    blocked = block_layout(ds.z, ds.x, v_z=64, v_x=16, block_size=512, seed=42)
    return spec, ds, blocked


def _targets(ds):
    from repro.data.synth import perturb_distribution

    rng = np.random.default_rng(7)
    return [ds.target] + [
        perturb_distribution(ds.target, d, rng)
        for d in np.linspace(0.004, 0.04, N_QUERIES - 1)
    ]


def _recall(ds, targets, results) -> float:
    def truth(t):
        dists = np.abs(ds.true_hists - np.asarray(t)[None, :]).sum(axis=1)
        return set(np.argsort(dists, kind="stable")[:K].tolist())

    return float(np.mean([
        len(set(r.ids.tolist()) & truth(t)) / K for t, r in zip(targets, results)
    ]))


def measure_phase() -> None:
    """Entry point executed on 8 virtual CPU devices: serve the batch
    through the plain server and through the pump at each worker count,
    print one JSON line (with the child's device stamp) consumed by
    `run` in the parent."""
    import jax
    from jax.sharding import Mesh

    from repro.serve.fastmatch_server import MatchServer

    _, ds, blocked = _build()
    targets = _targets(ds)

    def serve(**kw):
        server = MatchServer(
            blocked, max_queries=N_QUERIES, lookahead=LOOKAHEAD, seed=200,
            poll_every=1, k_cap=K, **kw,
        )
        rids = [server.submit(t, k=K, eps=EPS, delta=DELTA) for t in targets]
        t0 = time.perf_counter()
        results = server.run_until_idle()
        wall = time.perf_counter() - t0
        sched = server.scheduler
        return dict(
            wall_s=round(wall, 4),
            tuples=int(server.metrics["total_tuples_read"]),
            tuples_per_sec=round(server.metrics["total_tuples_read"] / wall, 1),
            rounds=int(sched.rounds),
            host_syncs=int(sched.host_syncs),
            loop_syncs=int(sched.loop_syncs),
            recall=_recall(ds, targets, [results[r] for r in rids]),
        )

    out = {"single": serve()}
    for w in WORKERS:
        mesh = Mesh(np.array(jax.devices()[:w]).reshape(w, 1), ("data", "model"))
        out[f"w{w}"] = serve(mesh=mesh, pump=True, prefetch=not SMOKE)
    out["env"] = env_stamp()
    print(json.dumps(out))


def run(rows: list) -> None:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = (
        str(pathlib.Path(__file__).parent.parent / "src")
        + os.pathsep + env.get("PYTHONPATH", "")
    )
    out = subprocess.run(
        [sys.executable, "-c",
         "from benchmarks.pump_throughput import measure_phase; measure_phase()"],
        env=env, capture_output=True, text=True, timeout=3600,
        cwd=str(pathlib.Path(__file__).parent.parent),
    )
    if out.returncode != 0:
        raise SystemExit(f"pump measure phase failed:\n{out.stderr[-4000:]}")
    m = json.loads(out.stdout.strip().splitlines()[-1])
    child_env = m.pop("env")

    single, w1, w8 = m["single"], m["w1"], m["w8"]
    sync_reduction = w1["loop_syncs"] / max(w8["loop_syncs"], 1)
    rounds_reduction = w1["rounds"] / max(w8["rounds"], 1)
    recall_min = min(m[k]["recall"] for k in m)
    # golden embed: the 1-worker pump IS the single stream
    w1_equivalent = w1["tuples"] == single["tuples"] and w1["rounds"] == single["rounds"]

    for w in WORKERS:
        r = m[f"w{w}"]
        rows.append(dict(name=f"pump_w{w}_serve",
                         us_per_call=1e6 * r["wall_s"], derived=r["tuples"]))
    rows.append(dict(name="pump_tuples_per_sec_w8", us_per_call=0.0,
                     derived=w8["tuples_per_sec"]))
    rows.append(dict(name="pump_sync_reduction_w8", us_per_call=0.0,
                     derived=round(sync_reduction, 2)))
    rows.append(dict(name="pump_rounds_reduction_w8", us_per_call=0.0,
                     derived=round(rounds_reduction, 2)))

    ok = (
        w1_equivalent
        and recall_min >= single["recall"]
        and sync_reduction >= 2.0
        and rounds_reduction >= 2.0
    )
    report = dict(
        config=dict(
            workers=list(WORKERS), n_queries=N_QUERIES, lookahead=LOOKAHEAD,
            k=K, eps=EPS, delta=DELTA, smoke=SMOKE, **child_env,
            devices="8 virtual CPU devices (JAX_PLATFORMS=cpu)",
        ),
        serve=m,
        sync_reduction_w8=round(sync_reduction, 3),
        rounds_reduction_w8=round(rounds_reduction, 3),
        recall_min=recall_min,
        w1_equivalent=w1_equivalent,
        ok=ok,
    )
    RESULTS.mkdir(exist_ok=True)
    (RESULTS / "BENCH_pump.json").write_text(json.dumps(report, indent=2) + "\n")

    print(f"# pump_throughput: rounds w1={w1['rounds']} -> w8={w8['rounds']} "
          f"({rounds_reduction:.1f}x), syncs {w1['loop_syncs']} -> {w8['loop_syncs']} "
          f"({sync_reduction:.1f}x), w8 {w8['tuples_per_sec']:,.0f} tuples/s, "
          f"recall min {recall_min:.3f} vs single {single['recall']:.3f}, "
          f"w1==single={w1_equivalent} -> {'PASS' if ok else 'FAIL'}")
    if SMOKE and not ok:
        raise SystemExit("pump_throughput smoke FAILED")


if __name__ == "__main__":
    rows: list = []
    run(rows)
    for r in rows:
        print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")
