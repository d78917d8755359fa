"""Smoke run of MatchServer's served path on a TPU: runs, and is right.

This is a smoke run, not a measurement: the seconds it prints include
compilation and one-off data placement, and one run is one sample.

    python chip_smoke.py              # one chip: taxi_q1, then flights_q1
    python chip_smoke.py --chips 4    # four chips: taxi_q1 only, through
                                      # the pump and the candidate-sharded
                                      # mesh, against one chip

One chip, per deployment (the benchmark's own `taxi_q1` / `flights_q1`
specs, `block_layout(block_size=512)`, device-resident
`InMemorySource`):

  * serve 8 top-k queries — the planted target and 7 seeded
    perturbations of it — through
    `MatchServer(max_queries=8, lookahead=512, k_cap=10)`;
  * check every answer against a float64 full scan in numpy of the same
    blocks: Guarantees 1 and 2 (`benchmarks.common.guarantees_hold`) for
    all 8, and recall 1.0 of the reference top-k for the planted target;
  * check that the compiled round holds the ingest, distance and
    AnyActive Pallas kernels (`tpu_custom_call`);
  * run the paper's Scan baseline (exact completion on a fresh
    scheduler) and require its counts to equal the numpy histogram.

Four chips: the same 8 queries on `taxi_q1` through
`MatchServer(mesh=Mesh(4x1), pump=True)` and `MatchServer(mesh=Mesh(1x4))`
(counts candidate-sharded), each checked against the reference as above,
with the answers (sorted ids, exactness) of one chip, and with the
counts spread over four distinct devices.

The script exits non-zero, and prints no result line, when JAX
finds no TPU or any check fails. Its last line on success is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

Compilation cache: ``JAX_COMPILATION_CACHE_DIR`` when it is set, else
``.jax_cache`` next to this file; every compiled program is kept.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time
import types

ROOT = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from benchmarks.common import QUERY_EPS, get_query, guarantees_hold  # noqa: E402
from repro.core import multiquery as mq  # noqa: E402
from repro.core.engine import EngineConfig, run_engine  # noqa: E402
from repro.core.histsim import HistSimParams  # noqa: E402
from repro.data.synth import perturb_distribution  # noqa: E402
from repro.io import InMemorySource  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.serve.fastmatch_server import MatchServer  # noqa: E402

DEPLOYMENTS = ("taxi_q1", "flights_q1")
N_QUERIES = 8
DELTA = 0.01
LOOKAHEAD = 512
K_CAP = 10
KERNELS = ("histogram_with_rowsums", "distance_multi", "anyactive")


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def use_compile_cache() -> None:
    source = "JAX_COMPILATION_CACHE_DIR"
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        source = "default"
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cache = pathlib.Path(jax.config.jax_compilation_cache_dir)
    held = sum(f.stat().st_size for f in cache.rglob("*") if f.is_file()) if cache.is_dir() else 0
    print(f"compile cache ({source}): {cache}, {held} bytes before this run")


class Reference:
    """Float64 full scan in numpy of the blocks the server reads."""

    def __init__(self, blocked):
        z = blocked.z_blocks.ravel().astype(np.int64)
        x = blocked.x_blocks.ravel().astype(np.int64)
        keep = z >= 0
        v_z, v_x = blocked.v_z, blocked.v_x
        self.counts = np.bincount(
            z[keep] * v_x + x[keep], minlength=v_z * v_x
        ).reshape(v_z, v_x)
        self.hists = self.counts / np.maximum(self.counts.sum(axis=1, keepdims=True), 1)

    def truth(self, target) -> types.SimpleNamespace:
        """The `guarantees_hold` view of the data for one target."""
        t = np.asarray(target, np.float64)
        return types.SimpleNamespace(
            true_dists=np.abs(self.hists - t / t.sum()).sum(axis=1),
            true_hists=self.hists,
        )


def targets_for(ds) -> list:
    rng = np.random.default_rng(7)
    return [ds.target] + [
        perturb_distribution(ds.target, d, rng)
        for d in np.linspace(0.01, 0.1, N_QUERIES - 1)
    ]


def new_server(source, **kw) -> MatchServer:
    return MatchServer(
        source, max_queries=N_QUERIES, lookahead=LOOKAHEAD, k_cap=K_CAP, **kw
    )


def serve(server, targets, *, k, eps) -> tuple:
    """Serve the batch; returns (results in target order, wall seconds)."""
    rids = [server.submit(t, k=k, eps=eps, delta=DELTA) for t in targets]
    t0 = time.perf_counter()
    results = server.run_until_idle()
    wall = time.perf_counter() - t0
    return [results[r] for r in rids], wall


def check_answers(name, results, targets, ref, *, k, eps) -> dict:
    """Guarantees 1 and 2 for every query, recall 1.0 for the planted one."""
    for i, (res, t) in enumerate(zip(results, targets)):
        if res.stopped or res.degraded:
            fail(f"{name} query {i}: stopped={res.stopped} degraded={res.degraded}")
        if not guarantees_hold(res, ref.truth(t), eps):
            fail(f"{name} query {i}: Guarantees 1/2 violated against the float64 scan")
    truth = ref.truth(targets[0])
    top = set(np.argsort(truth.true_dists, kind="stable")[:k].tolist())
    recall = len(top & set(results[0].ids.tolist())) / k
    if recall != 1.0:
        fail(f"{name}: planted-target recall {recall} != 1.0")
    return dict(guarantees_1_2=f"{len(results)}/{len(results)}", planted_recall=recall)


def compile_rounds(server) -> tuple:
    """AOT-compile the server's round programs; (seconds, kernel names)."""
    sched = server.scheduler
    wd = sched.source.fetch(sched.order[: sched.window], pad_to=sched.window)
    t0 = time.perf_counter()
    fused = mq.fused_round.lower(
        sched.state, sched.cursor, wd, spec=sched.spec, policy=sched.policy
    ).compile()
    mq.ingest_round.lower(sched.state, sched.cursor, wd, spec=sched.spec).compile()
    mq.stats_step.lower(sched.state, spec=sched.spec).compile()
    return time.perf_counter() - t0, ops.tpu_kernels_in(fused.as_text())


def one_chip(name: str) -> None:
    spec, ds, blocked = get_query(name)
    eps = QUERY_EPS[name]
    ref = Reference(blocked)
    targets = targets_for(ds)

    source = InMemorySource(blocked)
    data_bytes = sum(a.nbytes for a in (blocked.z_blocks, blocked.x_blocks, blocked.bitmap))
    in_use = (jax.devices()[0].memory_stats() or {}).get("bytes_in_use")

    # Compile the server's round programs before serving, so the serve
    # wall below is mostly steady state (the persistent cache hands the
    # same programs back to the server's own dispatch).
    server = new_server(source)
    compile_s, kernels = compile_rounds(server)
    missing = set(KERNELS) - kernels
    if missing:
        fail(f"{name}: compiled round lacks Pallas kernels {sorted(missing)}")

    results, wall = serve(server, targets, k=spec.k, eps=eps)
    answers = check_answers(name, results, targets, ref, k=spec.k, eps=eps)
    m = server.metrics

    t0 = time.perf_counter()
    scan = run_engine(
        source, ds.target,
        HistSimParams(v_z=spec.v_z, v_x=spec.v_x, k=spec.k, eps=eps, delta=DELTA),
        EngineConfig(variant="scan"),
    )
    scan_s = time.perf_counter() - t0
    if not np.array_equal(np.asarray(scan.state.counts), ref.counts.astype(np.float32)):
        fail(f"{name}: Scan counts differ from the numpy histogram")

    print(f"smoke run (not a measurement) {name}: " + json.dumps(dict(
        v_z=spec.v_z, v_x=spec.v_x, tuples=int(blocked.num_tuples),
        device_bytes_dataset=int(data_bytes), device_bytes_in_use=in_use,
        compile_s=round(compile_s, 3), kernels=sorted(kernels),
        serve_wall_s=round(wall, 3), rounds=int(m["total_rounds"]),
        tuples_read=int(m["total_tuples_read"]),
        fraction_read=round(float(m["total_tuples_read"]) / blocked.num_tuples, 4),
        exact=[bool(r.exact) for r in results], **answers,
        scan_wall_s=round(scan_s, 3), scan_counts_equal=True,
    )))


def four_chips() -> None:
    if len(jax.devices()) != 4:
        fail(f"--chips 4 needs 4 devices, found {len(jax.devices())}")
    name = "taxi_q1"
    spec, ds, blocked = get_query(name)
    eps = QUERY_EPS[name]
    ref = Reference(blocked)
    targets = targets_for(ds)
    devices = np.array(jax.devices())

    one = new_server(InMemorySource(blocked))
    single, single_s = serve(one, targets, k=spec.k, eps=eps)
    check_answers(f"{name} one chip", single, targets, ref, k=spec.k, eps=eps)
    print(f"smoke run (not a measurement) {name} one chip: " + json.dumps(dict(
        serve_wall_s=round(single_s, 3), rounds=int(one.metrics["total_rounds"]),
        tuples_read=int(one.metrics["total_tuples_read"]),
    )))
    runs = {
        "pump_4x1": dict(mesh=Mesh(devices.reshape(4, 1), ("data", "model")), pump=True),
        "mesh_1x4": dict(mesh=Mesh(devices.reshape(1, 4), ("data", "model"))),
    }
    for label, kw in runs.items():
        server = new_server(blocked if kw.get("pump") else InMemorySource(blocked), **kw)
        results, wall = serve(server, targets, k=spec.k, eps=eps)
        answers = check_answers(f"{name} {label}", results, targets, ref, k=spec.k, eps=eps)
        for i, (a, b) in enumerate(zip(results, single)):
            if sorted(a.ids.tolist()) != sorted(b.ids.tolist()) or a.exact != b.exact:
                fail(f"{name} {label} query {i}: answer differs from one chip")
        counts = server.scheduler.state.counts
        shard_devices = {s.device for s in counts.addressable_shards}
        if len(shard_devices) != 4:
            fail(f"{name} {label}: counts live on {len(shard_devices)} devices, not 4")
        rows = sorted({s.index[0].start or 0 for s in counts.addressable_shards})
        if not kw.get("pump") and len(rows) != 4:
            fail(f"{name} {label}: counts are not candidate-sharded 4 ways ({rows})")
        m = server.metrics
        print(f"smoke run (not a measurement) {name} {label}: " + json.dumps(dict(
            serve_wall_s=round(wall, 3), rounds=int(m["total_rounds"]),
            tuples_read=int(m["total_tuples_read"]),
            counts_devices=len(shard_devices), counts_row_offsets=rows,
            same_answers_as_one_chip=True, **answers,
        )))


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = parser.parse_args()

    platform = jax.devices()[0].platform
    if platform != "tpu":
        fail(f"needs a TPU; JAX found platform {platform!r}")
    if os.environ.get("FASTMATCH_CHAOS"):
        fail("FASTMATCH_CHAOS is set; the smoke run serves without injected faults")
    use_compile_cache()

    if args.chips == 4:
        four_chips()
    else:
        for name in DEPLOYMENTS:
            one_chip(name)
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices()),
    }}))


if __name__ == "__main__":
    main()
