"""Telemetry overhead + fidelity: observing the server must not change it.

The `repro.obs` subsystem records only at host-sync/poll boundaries, so
the jitted `fused_round` path is byte-for-byte the same program with
telemetry on and off. This benchmark holds the subsystem to its two
contracts:

  1. overhead  — telemetry must add < 2% to the cost of serving the
     seeded batch. Gated on the ACCOUNTED cost: a dedicated run with
     every host-side telemetry entry point wrapped in a reentrancy-
     guarded timer (poll staging, batched flushes, round-batch emits,
     tracer/registry writes), plus the measured marginal cost of the
     two extra `device_get` leaves per poll and a rounded-up charge for
     the per-window `perf_counter` pairs the wrappers cannot see. The
     sum over everything telemetry executes must stay under 2% of the
     telemetry-off wall floor. An interleaved off/on A/B wall
     comparison is also run and REPORTED (floors = mean of each arm's
     3 fastest of ``REPEATS`` alternating runs) as corroborating
     evidence, but not gated: per-process code/data-layout bias on
     shared hosts measured at +-3..8% of a ~250ms serve — an order of
     magnitude above the thing being measured — makes a one-process
     2% wall gate a coin flip, while the accounted sum is stable and
     measures exactly the work telemetry adds.
  2. fidelity  — every engine output is bit-identical across the pair
     (top-k ids, per-query counters, host-sync count, the full
     exported cache: counts/n/read_mask/cursors), and the recorded
     tuples-to-confidence curve reproduces the stats tail: eps_n equals
     `core.bounds.theorem1_epsilon` at the polled n_min and
     per-candidate budget delta/|V_Z|, and a terminated query's final
     recorded delta_upper is below its delta.

The workload oversubscribes the server (18 queries over 6 slots at
tight eps/delta) so admission waves, retire-boundary flushes, and the
multi-pass tail are all inside the measured region.

Reported rows (benchmarks/run.py CSV schema):

  telemetry_off_serve — us per batch, telemetry off (floor estimate)
  telemetry_on_serve  — us per batch, telemetry on  (floor estimate)
  telemetry_overhead  — derived = wall (on - off) / off  [informational]
  telemetry_accounted — derived = accounted_s / off      [the gate]
  telemetry_events    — derived = trace events recorded by one run

Machine-readable results land in benchmarks/results/BENCH_telemetry.json
(gated by benchmarks/check_regression.py on the DETERMINISTIC keys —
bit_identical / curve_matches / ok — never on the wall-clock ratio),
next to the run's trace (telemetry_trace.jsonl) and confidence curves
(telemetry_curves.csv).

Set TELEMETRY_BENCH_SMOKE=1 for the smaller CI configuration (same code
path; exits non-zero if any contract fails).
"""

from __future__ import annotations

import json
import os
import pathlib
import time

import jax
import numpy as np

from benchmarks.common import env_stamp
from repro.core import multiquery
from repro.core.bounds import theorem1_epsilon
from repro.data.layout import block_layout
from repro.data.synth import SynthSpec, make_dataset, perturb_distribution
from repro.obs import registry as obs_registry
from repro.obs import tracer as obs_tracer
from repro.obs import telemetry as obs_telemetry
from repro.serve.fastmatch_server import MatchServer

N_QUERIES = 18   # submitted; oversubscribes MAX_ACTIVE slots -> admission waves
MAX_ACTIVE = 6
K, DELTA, EPS = 10, 0.001, 0.04
SMOKE = bool(int(os.environ.get("TELEMETRY_BENCH_SMOKE", "0")))
REPEATS = 9 if SMOKE else 5
OVERHEAD_LIMIT = 0.02

SPEC = SynthSpec(
    v_z=161, v_x=24, num_tuples=2_000_000 if SMOKE else 4_000_000, k=K, n_close=10,
    close_distance=0.02, far_distance=0.3, zipf_a=1.0, close_rank="head", seed=42,
)
# Smoke keeps a real window size: tiny lookahead makes per-dispatch host
# overhead dominate rather than the sampling engine being measured.
LOOKAHEAD = 128 if SMOKE else 512

RESULTS = pathlib.Path(__file__).parent / "results"


def _targets(ds):
    rng = np.random.default_rng(7)
    return [
        perturb_distribution(ds.target, d, rng)
        for d in np.linspace(0.002, 0.05, N_QUERIES)
    ]


def _serve(blocked, targets, *, telemetry):
    server = MatchServer(
        blocked, max_queries=MAX_ACTIVE, lookahead=LOOKAHEAD, seed=200,
        poll_every=4, prefetch=True, k_cap=K, telemetry=telemetry,
    )
    t0 = time.perf_counter()
    rids = [server.submit(t, k=K, eps=EPS, delta=DELTA) for t in targets]
    results = server.run_until_idle()
    wall = time.perf_counter() - t0
    return server, [results[r] for r in rids], wall


def _fingerprint(server, results):
    """Everything the engine computed, as an exactly-comparable tuple."""
    snap = server.scheduler.export_cache()
    leaves = tuple(np.asarray(leaf) for leaf in snap)
    per_query = tuple(
        (tuple(r.ids.tolist()), r.rounds, r.blocks_read, r.tuples_read,
         r.exact, r.passes)
        for r in results
    )
    return server.scheduler.host_syncs, per_query, leaves


def _identical(fp_a, fp_b) -> bool:
    if fp_a[0] != fp_b[0] or fp_a[1] != fp_b[1]:
        return False
    return all(np.array_equal(a, b) for a, b in zip(fp_a[2], fp_b[2]))


def _curves_match_tail(server) -> bool:
    """Recorded eps_n must BE Theorem 1 at the polled n_min; a
    terminated query's final delta_upper must have crossed its delta."""
    tel = server.telemetry
    retired = {e["qid"]: e for e in tel.tracer.skeleton("query_retire")}
    if set(tel.query_ids()) != set(retired):
        return False
    for qid in tel.query_ids():
        traj = tel.trajectory(qid)
        if not traj:
            return False
        for p in traj:
            ref = float(theorem1_epsilon(
                max(p["n_min"], 1.0), DELTA / SPEC.v_z, SPEC.v_x
            ))
            if not np.isclose(p["eps_n"], ref, rtol=1e-4):
                return False
            if not np.isclose(p["confidence"], max(0.0, 1.0 - p["delta_upper"])):
                return False
        if retired[qid]["terminated"] and traj[-1]["delta_upper"] >= DELTA:
            return False
    return True


# -- accounted-cost machinery ----------------------------------------------

class _CostAccount:
    """Times every wrapped call, reentrancy-guarded so nested wrapped
    calls (e.g. `flush_telemetry` -> `Counter.inc`) count once. The
    depth guard is a plain int: every wrapped entry point runs on the
    serve loop's thread (the prefetch worker only appends to plain
    lists; its measurements are flushed at stream close, on this
    thread). Wrapper cost itself lands INSIDE the measured span, so the
    account can only overstate telemetry's cost — the safe direction
    for a < limit gate.
    """

    def __init__(self):
        self.total_s = 0.0
        self.by_site: dict = {}
        self._depth = 0
        self._saved: list = []

    def _wrap(self, fn, site: str):
        def timed(*args, **kwargs):
            if self._depth:
                return fn(*args, **kwargs)
            self._depth += 1
            t0 = time.perf_counter()
            try:
                return fn(*args, **kwargs)
            finally:
                dt = time.perf_counter() - t0
                self.total_s += dt
                self.by_site[site] = self.by_site.get(site, 0.0) + dt
                self._depth -= 1
        return timed

    def patch(self, *targets):
        for cls, name in targets:
            fn = getattr(cls, name)
            self._saved.append((cls, name, fn))
            setattr(cls, name, self._wrap(fn, f"{cls.__name__}.{name}"))

    def unpatch(self):
        for cls, name, fn in self._saved:
            setattr(cls, name, fn)
        self._saved.clear()


def _timer_call_residual() -> float:
    """Per-window cost of what the wrappers cannot see: the bare
    `perf_counter` pairs in the prefetch worker/consumer, plus their
    list appends. Charged as 8 timer calls
    + 3 appends per window — a rounded-UP census (the off arm pays some
    of these branches too), measured here rather than assumed."""
    sink: list = []
    reps = 20_000
    t0 = time.perf_counter()
    for _ in range(reps):
        time.perf_counter(); time.perf_counter()  # noqa: E702
        time.perf_counter(); time.perf_counter()  # noqa: E702
        time.perf_counter(); time.perf_counter()  # noqa: E702
        time.perf_counter(); time.perf_counter()  # noqa: E702
        sink.append(0.0)
        sink.append(0.0)
        sink.append(0.0)
        if len(sink) >= 30_000:
            sink.clear()
    return (time.perf_counter() - t0) / reps


def _transfer_delta(sch) -> float:
    """Marginal cost of the two extra leaves telemetry adds to the
    single batched `device_get` in `_sync` (tau: (Q, V_Z) f32, n:
    (V_Z,) f32), measured on the final device state."""
    base = (sch.cursor, sch.state.delta_upper)
    full = (sch.cursor, sch.state.delta_upper, sch.state.tau, sch.state.n)
    for _ in range(3):
        jax.device_get(base)
        jax.device_get(full)

    def floor(tree):
        ts = []
        for _ in range(60):
            t0 = time.perf_counter()
            jax.device_get(tree)
            ts.append(time.perf_counter() - t0)
        # mean of the 3 fastest — the marginal floor, insensitive to
        # scheduler blips that a median still feels
        return float(np.mean(sorted(ts)[:3]))

    return max(floor(full) - floor(base), 0.0)


def _accounted_cost(blocked, targets) -> dict:
    """One serve with every telemetry entry point timed; returns the
    breakdown in seconds plus the run's round/sync counts."""
    acc = _CostAccount()
    acc.patch(
        (multiquery.SharedCountsScheduler, "_record_poll"),
        (multiquery.SharedCountsScheduler, "flush_telemetry"),
        (multiquery.SharedCountsScheduler, "_emit_round_batch"),
        (obs_tracer.Tracer, "emit"),
        (obs_registry.Counter, "inc"),
        (obs_registry.Gauge, "set"),
        (obs_registry.Histogram, "observe"),
        (obs_registry.Histogram, "observe_many"),
        (obs_telemetry.Telemetry, "record_curve_point"),
    )
    try:
        server, _results, wall = _serve(blocked, targets, telemetry=True)
    finally:
        acc.unpatch()
    sch = server.scheduler
    per_window = _timer_call_residual()
    leaf_delta = _transfer_delta(sch)
    hooks_s = acc.total_s
    timers_s = sch.rounds * per_window
    transfer_s = sch.host_syncs * leaf_delta
    return dict(
        hooks_s=hooks_s,
        by_site={k: round(v, 6) for k, v in sorted(
            acc.by_site.items(), key=lambda kv: -kv[1])},
        timers_s=timers_s,
        transfer_s=transfer_s,
        total_s=hooks_s + timers_s + transfer_s,
        rounds=sch.rounds,
        host_syncs=sch.host_syncs,
        wall_s=wall,
    )


def run(rows: list) -> None:
    ds = make_dataset(SPEC)
    blocked = block_layout(
        ds.z, ds.x, v_z=SPEC.v_z, v_x=SPEC.v_x, block_size=512, seed=42
    )
    targets = _targets(ds)

    # warmup: compiles the fused round and pays each arm's one-time
    # lazy-init costs outside the timed region
    _serve(blocked, targets, telemetry=None)
    _serve(blocked, targets, telemetry=True)

    # -- interleaved floor timing (reported, not gated) -----------------
    # Floor estimate per arm: mean of the 3 fastest runs — converges to
    # the same floor as a raw min but with less order-statistic jitter.
    # Arm order alternates so slow drift (thermal, co-tenant load)
    # charges both arms equally.
    off_walls, on_walls = [], []
    fp_off = fp_on = None
    last_on = None
    for i in range(REPEATS):
        arms = ((None, off_walls), (True, on_walls))
        for telemetry, walls in arms if i % 2 == 0 else arms[::-1]:
            srv, res, wall = _serve(blocked, targets, telemetry=telemetry)
            walls.append(wall)
            if telemetry is None:
                fp_off = _fingerprint(srv, res)
            else:
                fp_on = _fingerprint(srv, res)
                last_on = srv
    off_s = float(np.mean(sorted(off_walls)[:3]))
    on_s = float(np.mean(sorted(on_walls)[:3]))
    wall_overhead = (on_s - off_s) / off_s

    # -- accounted cost (the gate) --------------------------------------
    account = _accounted_cost(blocked, targets)
    accounted_frac = account["total_s"] / off_s

    bit_identical = _identical(fp_off, fp_on)
    curve_matches = _curves_match_tail(last_on)
    trace_events = last_on.telemetry.tracer.events_total

    RESULTS.mkdir(exist_ok=True)
    last_on.export_trace(RESULTS / "telemetry_trace.jsonl")
    curve_rows = last_on.telemetry.export_confidence_csv(
        RESULTS / "telemetry_curves.csv"
    )
    (RESULTS / "telemetry_metrics.prom").write_text(last_on.prometheus_metrics())

    ok = bit_identical and curve_matches and accounted_frac < OVERHEAD_LIMIT

    rows.append(dict(name="telemetry_off_serve",
                     us_per_call=1e6 * off_s, derived=0))
    rows.append(dict(name="telemetry_on_serve",
                     us_per_call=1e6 * on_s, derived=0))
    rows.append(dict(name="telemetry_overhead", us_per_call=0.0,
                     derived=round(wall_overhead, 4)))
    rows.append(dict(name="telemetry_accounted", us_per_call=0.0,
                     derived=round(accounted_frac, 4)))
    rows.append(dict(name="telemetry_events", us_per_call=0.0,
                     derived=int(trace_events)))

    report = dict(
        config=dict(
            v_z=SPEC.v_z, v_x=SPEC.v_x, num_tuples=SPEC.num_tuples,
            n_queries=N_QUERIES, max_active=MAX_ACTIVE, lookahead=LOOKAHEAD,
            poll_every=4, k=K, eps=EPS, delta=DELTA, repeats=REPEATS,
            smoke=SMOKE, **env_stamp(),
        ),
        off_s=round(off_s, 4),
        on_s=round(on_s, 4),
        wall_overhead_frac=round(wall_overhead, 4),
        accounted=dict(
            hooks_s=round(account["hooks_s"], 6),
            by_site=account["by_site"],
            timers_s=round(account["timers_s"], 6),
            transfer_s=round(account["transfer_s"], 6),
            total_s=round(account["total_s"], 6),
            rounds=account["rounds"],
            host_syncs=account["host_syncs"],
        ),
        accounted_frac=round(accounted_frac, 4),
        overhead_limit=OVERHEAD_LIMIT,
        bit_identical=bit_identical,
        curve_matches=curve_matches,
        trace_events=int(trace_events),
        curve_rows=int(curve_rows),
        ok=ok,
    )
    (RESULTS / "BENCH_telemetry.json").write_text(json.dumps(report, indent=2) + "\n")

    print(f"# telemetry_overhead: off={off_s * 1e3:.0f}ms on={on_s * 1e3:.0f}ms "
          f"(wall {wall_overhead:+.2%} informational; accounted "
          f"{accounted_frac:.2%} of limit {OVERHEAD_LIMIT:.0%}), "
          f"bit_identical={bit_identical}, curve_matches={curve_matches}, "
          f"{trace_events} events -> {'PASS' if ok else 'FAIL'}")
    if SMOKE and not ok:
        raise SystemExit("telemetry_overhead smoke FAILED")


if __name__ == "__main__":
    rows: list = []
    run(rows)
    for r in rows:
        print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")
