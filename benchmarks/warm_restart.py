"""Warm-restart amortization: a restored `MatchServer` vs a cold one.

The serving subsystem's speedup lives in the persistent cross-query
sample cache; until PR 4 that cache died with the process, so every
restart paid the full sampling cost again — the brute-force regime the
paper's speedups are measured against. This benchmark measures the
restart analogue of the serve benchmark's I/O amortization:

  1. A "day 1" server serves a warmup batch and checkpoints its cache.
  2. The cache is restored in a NEW PROCESS (genuine cross-process
     persistence, not a same-process object copy) and a batch of fresh
     queries is served from the warm cache.
  3. A cold server (fresh cache, same configuration) serves the same
     fresh batch.

Steps 1 and 3 run in one child process and step 2 in a second, one
after the other; the parent only orchestrates and never initialises a
JAX backend, so on an accelerator each child in turn holds the device.

Acceptance: the warm-restored server must read STRICTLY fewer tuples
per query than the cold server at no recall loss against planted
ground truth.

Reported rows (benchmarks/run.py CSV schema):

  restart_cold_total   — us for the cold fresh batch, derived = tuples read
  restart_warm_total   — us for the warm fresh batch, derived = tuples read
  restart_amortization — derived = cold_tuples / warm_tuples (>1 = win)
  restart_save         — us per cache checkpoint save
  restart_restore      — us per cross-process cache restore

Machine-readable results land in benchmarks/results/BENCH_restart.json
(tuples read per query, cold vs warm-restored, plus save/restore wall
times) alongside the aggregate CSV.

Set RESTART_BENCH_SMOKE=1 for the tiny CI configuration (same code
path; exits non-zero if the warm server does not strictly win or loses
recall).
"""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import tempfile
import time

import numpy as np

from benchmarks.common import env_stamp
from repro.data.layout import block_layout
from repro.data.synth import SynthSpec, make_dataset, perturb_distribution
from repro.serve.fastmatch_server import MatchServer

SMOKE = bool(int(os.environ.get("RESTART_BENCH_SMOKE", "0")))
K, DELTA, EPS = 10, 0.01, 0.07
N_WARMUP, N_FRESH = 6, 4
MAX_QUERIES = 8
SPEC = SynthSpec(
    v_z=161, v_x=24, num_tuples=300_000 if SMOKE else 4_000_000, k=K, n_close=10,
    close_distance=0.02, far_distance=0.3, zipf_a=1.0, close_rank="head", seed=42,
)
LOOKAHEAD = 16 if SMOKE else 512

RESULTS = pathlib.Path(__file__).parent / "results"


def _build():
    ds = make_dataset(SPEC)
    blocked = block_layout(
        ds.z, ds.x, v_z=SPEC.v_z, v_x=SPEC.v_x, block_size=512, seed=42
    )
    return ds, blocked


def _warmup_targets(ds):
    rng = np.random.default_rng(7)
    return [ds.target] + [
        perturb_distribution(ds.target, d, rng)
        for d in np.linspace(0.004, 0.03, N_WARMUP - 1)
    ]


def _fresh_targets(ds):
    """The post-restart workload — deterministic, so the warm (restored,
    other process) and cold servers serve the exact same queries."""
    rng = np.random.default_rng(21)
    return [
        perturb_distribution(ds.target, d, rng)
        for d in np.linspace(0.008, 0.05, N_FRESH)
    ]


def _serve(server: MatchServer, targets):
    rids = [server.submit(t, k=K, eps=EPS, delta=DELTA) for t in targets]
    results = server.run_until_idle()
    return [results[r] for r in rids]


def _true_top_k(ds, target, k: int) -> set:
    dists = np.abs(ds.true_hists - np.asarray(target)[None, :]).sum(axis=1)
    return set(np.argsort(dists, kind="stable")[:k].tolist())


def _recall(ds, targets, results) -> float:
    return float(np.mean([
        len(set(r.ids.tolist()) & _true_top_k(ds, t, K)) / K
        for t, r in zip(targets, results)
    ]))


def day1_phase() -> None:
    """Child process 1: serve the warmup batch, checkpoint the cache to
    $RESTART_BENCH_CKPT, then serve the fresh batch on a cold server.
    Prints one JSON line consumed by `run` in the parent."""
    ds, blocked = _build()
    day1 = MatchServer(
        blocked, max_queries=MAX_QUERIES, lookahead=LOOKAHEAD, seed=200, k_cap=K,
        checkpoint_dir=os.environ["RESTART_BENCH_CKPT"],
    )
    _serve(day1, _warmup_targets(ds))
    t0 = time.perf_counter()
    day1.save_cache()
    save_s = time.perf_counter() - t0

    cold_server = MatchServer(
        blocked, max_queries=MAX_QUERIES, lookahead=LOOKAHEAD, seed=200, k_cap=K
    )
    fresh = _fresh_targets(ds)
    t0 = time.perf_counter()
    cold_results = _serve(cold_server, fresh)
    print(json.dumps(dict(
        save_s=save_s,
        serve_s=time.perf_counter() - t0,
        tuples=[int(r.tuples_read) for r in cold_results],
        total_tuples=int(cold_server.metrics["total_tuples_read"]),
        recall=_recall(ds, fresh, cold_results),
        env=env_stamp(),
    )))


def restore_phase() -> None:
    """Child process 2: warm-restore the server from $RESTART_BENCH_CKPT
    and serve the fresh batch. Prints one JSON line consumed by `run`
    in the parent."""
    ckpt = os.environ["RESTART_BENCH_CKPT"]
    ds, blocked = _build()
    t0 = time.perf_counter()
    server = MatchServer.restore(
        blocked, checkpoint_dir=ckpt,
        max_queries=MAX_QUERIES, lookahead=LOOKAHEAD, k_cap=K,
    )
    restore_s = time.perf_counter() - t0
    targets = _fresh_targets(ds)
    # the restored cursor CONTINUES the day-1 counters, so actual new
    # I/O is the delta — per-query counters are while-live deltas already
    before = server.metrics["total_tuples_read"]
    t0 = time.perf_counter()
    results = _serve(server, targets)
    print(json.dumps(dict(
        tuples=[int(r.tuples_read) for r in results],
        total_tuples=int(server.metrics["total_tuples_read"] - before),
        recall=_recall(ds, targets, results),
        restore_s=restore_s,
        serve_s=time.perf_counter() - t0,
    )))


def _child(phase: str, ckpt: str) -> dict:
    """Run one phase in a fresh interpreter; its last stdout line."""
    root = pathlib.Path(__file__).parent.parent
    env = dict(os.environ)
    env["RESTART_BENCH_CKPT"] = ckpt
    env["PYTHONPATH"] = str(root / "src") + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-c",
         f"from benchmarks.warm_restart import {phase}; {phase}()"],
        env=env, capture_output=True, text=True, timeout=1800, cwd=str(root),
    )
    if out.returncode != 0:
        raise SystemExit(f"{phase} failed:\n{out.stderr[-4000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def run(rows: list) -> None:
    ckpt = tempfile.mkdtemp(prefix="fastmatch_restart_bench_")
    # day 1 + cold restart, then the warm restore in a NEW process
    cold = _child("day1_phase", ckpt)
    warm = _child("restore_phase", ckpt)
    save_s, cold_s = cold["save_s"], cold["serve_s"]
    cold_tuples, cold_recall = cold["tuples"], cold["recall"]

    # totals are ACTUAL I/O (shared reads counted once), per-query
    # numbers in the report are the usual while-live amortized counters
    warm_total = warm["total_tuples"]
    cold_total = cold["total_tuples"]
    amortization = cold_total / max(warm_total, 1)
    ok = warm_total < cold_total and warm["recall"] >= cold_recall

    rows.append(dict(name="restart_cold_total",
                     us_per_call=1e6 * cold_s, derived=cold_total))
    rows.append(dict(name="restart_warm_total",
                     us_per_call=1e6 * warm["serve_s"], derived=warm_total))
    rows.append(dict(name="restart_amortization", us_per_call=0.0,
                     derived=round(amortization, 2)))
    rows.append(dict(name="restart_save", us_per_call=1e6 * save_s, derived=0))
    rows.append(dict(name="restart_restore",
                     us_per_call=1e6 * warm["restore_s"], derived=0))

    report = dict(
        config=dict(
            v_z=SPEC.v_z, v_x=SPEC.v_x, num_tuples=SPEC.num_tuples,
            n_warmup=N_WARMUP, n_fresh=N_FRESH, lookahead=LOOKAHEAD,
            k=K, eps=EPS, delta=DELTA, smoke=SMOKE, **cold["env"],
        ),
        cold=dict(tuples_per_query=cold_tuples, total_tuples=cold_total,
                  recall=cold_recall, serve_s=round(cold_s, 4)),
        warm=dict(tuples_per_query=warm["tuples"], total_tuples=warm_total,
                  recall=warm["recall"], serve_s=round(warm["serve_s"], 4),
                  restore_s=round(warm["restore_s"], 4)),
        save_s=round(save_s, 4),
        amortization=round(amortization, 2),
        ok=ok,
    )
    RESULTS.mkdir(exist_ok=True)
    (RESULTS / "BENCH_restart.json").write_text(json.dumps(report, indent=2) + "\n")

    print(f"# warm_restart: cold={cold_total:,} tuples vs warm-restored="
          f"{warm_total:,} ({amortization:.1f}x), recall "
          f"{warm['recall']:.3f} vs {cold_recall:.3f}, save {save_s * 1e3:.0f}ms / "
          f"restore {warm['restore_s'] * 1e3:.0f}ms -> {'PASS' if ok else 'FAIL'}")
    if SMOKE and not ok:
        raise SystemExit("warm_restart smoke FAILED")


if __name__ == "__main__":
    rows: list = []
    run(rows)
    for r in rows:
        print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")
