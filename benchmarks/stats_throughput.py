"""Statistics-engine throughput: Q-batched tau vs unrolled.

The multi-query statistics iteration is tau for every live slot. PR-2
unrolled one `ops.l1_distance` call per slot — Q HBM passes over the
shared (V_Z, V_X) counts matrix per round. The Q-batched
`ops.l1_distance_multi` streams the counts once for all slots, so the
tau bytes moved per round are independent of Q — but bytes are not wall
time (the committed history shows batched LOSING wall-clock on XLA:CPU
at Q>=4). This benchmark measures both arms for Q in {1, 2, 4, 8}:

  * tau HBM bytes/round — the roofline bytes-moved model of each path
    (f32; unrolled: Q * (V_Z*V_X + V_X + V_Z); batched:
    sweeps * V_Z*V_X + Q * (V_X + V_Z)). The statistics engine is
    memory-bound, so bytes moved IS the roofline-projected round time
    on TPU.
  * rounds/sec — measured wall-clock of the jitted stats step on this
    host for the unrolled and batched arms.

Plus the ingest row-sum delta: `ops.histogram_with_rowsums` (one pass)
vs the two-step form (histogram + separate reduction).

Reported rows (benchmarks/run.py CSV schema):

  stats_tau_q{Q}_unrolled  — us per stats round, derived = MB moved
  stats_tau_q{Q}_batched   — us per stats round, derived = MB moved
  stats_tau_bytes_q8       — derived = unrolled/batched bytes ratio (>=4 = pass)
  stats_tau_speedup_q8     — derived = measured unrolled/batched wall ratio
  stats_ingest_fused       — us per fused ingest, derived = MB saved/round

Machine-readable results land in benchmarks/results/BENCH_stats.json
(config stamped with backend/device/jax via `common.env_stamp` so
`check_regression.py` can refuse cross-hardware comparisons). Wall-clock
is reported, never gated: shared runners cannot reproduce it.

Set STATS_BENCH_SMOKE=1 for the tiny CI configuration (same code path;
exits non-zero if the batched arm is not bit-identical to the unrolled
reference on the production engine or the q=8 bytes reduction drops
below 4x).
"""

from __future__ import annotations

import json
import os
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import env_stamp
from repro.kernels import ops
from repro.kernels.l1_distance_multi import _X_TILE as _X_BLOCK  # single-sweep lane bound

SMOKE = bool(int(os.environ.get("STATS_BENCH_SMOKE", "0")))
QS = (1, 2, 4, 8)
V_Z, V_X = (256, 256) if SMOKE else (4096, 1024)
N_SAMPLES = 4_096 if SMOKE else 65_536
# smoke kernels are microseconds — reps are nearly free
REPS = 25 if SMOKE else 10

RESULTS = pathlib.Path(__file__).parent / "results"


@jax.jit
def _tau_unrolled(counts, q_hat):
    """The PR-2 statistics tau: one kernel call-site per slot."""
    return jnp.stack(
        [ops.l1_distance(counts, q_hat[i]) for i in range(q_hat.shape[0])]
    )


@jax.jit
def _tau_batched(counts, q_hat):
    return ops.l1_distance_multi(counts, q_hat)


def _time(fn, *args) -> float:
    """Median seconds per call, jit-warmed."""
    jax.block_until_ready(fn(*args))
    t = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        t.append(time.perf_counter() - t0)
    return float(np.median(t))


def _tau_bytes(q: int) -> tuple:
    """(unrolled, batched) analytic HBM bytes per stats round, f32."""
    vx_pad = max(128, -(-V_X // 128) * 128)
    sweeps = 1 if vx_pad <= _X_BLOCK else 2
    unrolled = q * (V_Z * V_X + V_X + V_Z) * 4
    batched = (sweeps * V_Z * V_X + q * (V_X + V_Z)) * 4
    return unrolled, batched


def run(rows: list) -> None:
    rng = np.random.default_rng(12)
    counts = jnp.asarray(rng.integers(0, 50, size=(V_Z, V_X)).astype(np.float32))
    z = jnp.asarray(rng.integers(-1, V_Z, size=N_SAMPLES).astype(np.int32))
    x = jnp.asarray(rng.integers(-1, V_X, size=N_SAMPLES).astype(np.int32))

    tau_rows, identical = [], True
    for q in QS:
        q_hat = jnp.asarray(
            np.stack([rng.dirichlet(np.ones(V_X)).astype(np.float32) for _ in range(q)])
        )
        t_unrolled = _time(_tau_unrolled, counts, q_hat)
        t_batched = _time(_tau_batched, counts, q_hat)

        want = np.asarray(_tau_unrolled(counts, q_hat))
        identical &= bool(np.array_equal(want, np.asarray(_tau_batched(counts, q_hat))))

        b_unrolled, b_batched = _tau_bytes(q)
        tau_rows.append(
            dict(
                q=q,
                bytes_unrolled=b_unrolled,
                bytes_batched=b_batched,
                bytes_reduction=round(b_unrolled / b_batched, 3),
                us_unrolled=round(1e6 * t_unrolled, 1),
                us_batched=round(1e6 * t_batched, 1),
                speedup=round(t_unrolled / max(t_batched, 1e-12), 3),
                rounds_per_sec_unrolled=round(1.0 / max(t_unrolled, 1e-12), 1),
                rounds_per_sec_batched=round(1.0 / max(t_batched, 1e-12), 1),
            )
        )
        rows.append(dict(name=f"stats_tau_q{q}_unrolled",
                         us_per_call=1e6 * t_unrolled,
                         derived=round(b_unrolled / 2**20, 3)))
        rows.append(dict(name=f"stats_tau_q{q}_batched",
                         us_per_call=1e6 * t_batched,
                         derived=round(b_batched / 2**20, 3)))

    # ingest: two-step vs fused
    def two_step(z, x):
        c = ops.histogram(z, x, v_z=V_Z, v_x=V_X)
        return c, jnp.sum(c, axis=1)

    t_two = _time(jax.jit(two_step), z, x)
    t_fused = _time(
        jax.jit(lambda z, x: ops.histogram_with_rowsums(z, x, v_z=V_Z, v_x=V_X)), z, x
    )
    ingest_saved = V_Z * V_X * 4  # the avoided delta-matrix re-read (fused form)

    by_q = {r["q"]: r for r in tau_rows}
    reduction_q8 = by_q[8]["bytes_reduction"]
    speedup_q8 = by_q[8]["speedup"]
    # "independent of Q": the counts-stream term doesn't scale with Q —
    # going 1 -> 8 queries grows batched bytes only by the tiny targets
    # term, so the q8/q1 ratio stays near 1 (vs 8 for unrolled).
    batched_growth = by_q[8]["bytes_batched"] / by_q[1]["bytes_batched"]

    rows.append(dict(name="stats_tau_bytes_q8", us_per_call=0.0, derived=reduction_q8))
    rows.append(dict(name="stats_tau_speedup_q8", us_per_call=0.0, derived=speedup_q8))
    rows.append(dict(name="stats_ingest_fused", us_per_call=1e6 * t_fused,
                     derived=round(ingest_saved / 2**20, 3)))

    ok = identical and reduction_q8 >= 4.0 and batched_growth < 2.0
    report = dict(
        config=dict(v_z=V_Z, v_x=V_X, n_samples=N_SAMPLES, reps=REPS,
                    smoke=SMOKE, **env_stamp()),
        tau=tau_rows,
        ingest=dict(us_two_step=round(1e6 * t_two, 1),
                    us_fused=round(1e6 * t_fused, 1),
                    speedup=round(t_two / max(t_fused, 1e-12), 3),
                    bytes_saved_per_round=ingest_saved),
        batched_bit_identical=identical,
        batched_bytes_growth_q1_to_q8=round(batched_growth, 3),
        tau_bytes_reduction_q8=reduction_q8,
        ok=ok,
    )
    RESULTS.mkdir(exist_ok=True)
    (RESULTS / "BENCH_stats.json").write_text(json.dumps(report, indent=2) + "\n")

    print(f"# stats_throughput: q8 tau bytes {by_q[8]['bytes_unrolled'] / 2**20:.1f}MB "
          f"-> {by_q[8]['bytes_batched'] / 2**20:.1f}MB ({reduction_q8:.1f}x, "
          f"growth q1->q8 {batched_growth:.2f}x), wall speedup {speedup_q8:.2f}x, "
          f"bit-identical={identical} -> {'PASS' if ok else 'FAIL'}")
    if SMOKE and not ok:
        raise SystemExit("stats_throughput smoke FAILED")


if __name__ == "__main__":
    rows: list = []
    run(rows)
    for r in rows:
        print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")
