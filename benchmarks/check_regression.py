"""Benchmark-regression gate: compare smoke-run metrics to committed baselines.

The CI smoke steps have always written machine-readable reports
(BENCH_stats.json / BENCH_restart.json / BENCH_pump.json) and uploaded
them as artifacts — but nothing ever compared two runs, so a metric
could halve silently as long as the suite's own hard floor still held.
This module closes the loop: `benchmarks/baselines/` holds a committed
snapshot of each smoke report, and the CI step

    python -m benchmarks.check_regression stats restart   # tier-1 lane
    python -m benchmarks.check_regression pump            # multi-device lane

fails the workflow when a gated metric of the fresh run regresses past
its tolerance.

Gate design: only metrics that are deterministic-per-config (seeded
sampling counts, analytic byte models, recalls, pass/fail booleans) are
gated — never wall-clock, which varies by runner. Tolerances are
generous (floats may drift in low bits across jax/jaxlib versions, and
the tier-1 matrix runs both a pinned floor and latest); a real
regression — a lost amortization, a broken equivalence — lands far
outside them. The smoke flag of both runs must agree, so a full-config
report is never judged against a smoke baseline — and when both reports
carry a hardware stamp (``config.backend`` via `common.env_stamp`), a
backend mismatch refuses the comparison outright: an XLA:CPU baseline
cannot gate a GPU run. Device-kind and jax-version drift are printed as
notes, not failures.

Refreshing a baseline after an intentional change: run the smoke
benchmark locally and copy the report over the baseline file, e.g.

    PUMP_BENCH_SMOKE=1 python -m benchmarks.run pump
    cp benchmarks/results/BENCH_pump.json benchmarks/baselines/
"""

from __future__ import annotations

import dataclasses
import json
import pathlib
import sys
from typing import Iterable, List

RESULTS = pathlib.Path(__file__).parent / "results"
BASELINES = pathlib.Path(__file__).parent / "baselines"


@dataclasses.dataclass(frozen=True)
class Gate:
    """One gated metric: a top-level key of the benchmark report.

    kind:
      "min"   — result must stay >= baseline * (1 - tol)  (bigger = better)
      "max"   — result must stay <= baseline * (1 + tol)  (smaller = better)
      "exact" — result must equal the baseline (booleans / equivalences)
    """

    key: str
    kind: str = "min"
    tol: float = 0.25

    def check(self, base, res) -> str:
        """Empty string if the gate holds, else a failure description."""
        if self.kind == "exact":
            if res != base:
                return f"{self.key}: {res!r} != baseline {base!r}"
            return ""
        base_f, res_f = float(base), float(res)
        if self.kind == "min":
            floor = base_f * (1 - self.tol)
            if res_f < floor:
                return (f"{self.key}: {res_f:g} fell below "
                        f"{floor:g} (baseline {base_f:g} - {self.tol:.0%})")
        elif self.kind == "max":
            ceil = base_f * (1 + self.tol)
            if res_f > ceil:
                return (f"{self.key}: {res_f:g} rose above "
                        f"{ceil:g} (baseline {base_f:g} + {self.tol:.0%})")
        else:
            return f"{self.key}: unknown gate kind {self.kind!r}"
        return ""


# suite name (as passed to `benchmarks.run`) -> (report file, gates)
GATES = {
    "stats": ("BENCH_stats.json", [
        Gate("tau_bytes_reduction_q8", "min", 0.10),       # analytic byte model
        Gate("batched_bytes_growth_q1_to_q8", "max", 0.10),
        Gate("batched_bit_identical", "exact"),
        Gate("ok", "exact"),
    ]),
    "restart": ("BENCH_restart.json", [
        Gate("amortization", "min", 0.30),  # cold/warm tuple ratio, seeded
        Gate("ok", "exact"),
    ]),
    "pump": ("BENCH_pump.json", [
        Gate("sync_reduction_w8", "min", 0.30),
        Gate("rounds_reduction_w8", "min", 0.30),
        Gate("recall_min", "min", 0.05),
        Gate("w1_equivalent", "exact"),
        Gate("ok", "exact"),
    ]),
    # Deterministic contracts only: the overhead ratio is wall-clock
    # (runner-dependent) and is enforced by the suite itself ("ok"
    # folds it in), so gating it here twice would just double the noise.
    "telemetry": ("BENCH_telemetry.json", [
        Gate("bit_identical", "exact"),
        Gate("curve_matches", "exact"),
        Gate("trace_events", "min", 0.25),  # seeded event count
        Gate("ok", "exact"),
    ]),
    # Anytime serving: the bit-identity of SLA stops vs polls, native
    # no-slower-than-conservative, and pruning soundness are exact per
    # config; native-arm recall is a seeded float floor. Curve shapes
    # and rounds are reported, never gated.
    "anytime": ("BENCH_anytime.json", [
        Gate("stop_poll_identical", "exact"),
        Gate("stopped_not_exact", "exact"),
        Gate("native_no_slower_chi2", "exact"),
        Gate("native_no_slower_hellinger", "exact"),
        Gate("prune_sound_chi2", "exact"),
        Gate("prune_sound_hellinger", "exact"),
        Gate("recall_chi2_native", "min", 0.15),
        Gate("recall_hellinger_native", "min", 0.15),
        Gate("ok", "exact"),
    ]),
    # Fault tolerance: the equivalence/recovery booleans and the seeded
    # quarantine count are deterministic per config; recall is a seeded
    # float floor. Wall-clock (recovery_wall_s, wall_overhead_frac) is
    # never gated — the suite itself enforces the accounted < 2%
    # wrapper-overhead limit and folds it into "ok".
    "faults": ("BENCH_faults.json", [
        Gate("transient_bit_identical", "exact"),
        Gate("recovered", "exact"),
        Gate("recovery_answers_match", "exact"),
        Gate("degraded_ran", "exact"),
        Gate("blocks_quarantined", "exact"),
        Gate("recall_degraded", "min", 0.15),
        Gate("ok", "exact"),
    ]),
    # Pluggable-metric matrix: the closeness promise booleans and the
    # l1 exact-recall bit are deterministic per config; per-metric
    # top-k recall is a seeded float floor. Rounds-to-retire is
    # reported, never gated (the conservatism ordering is documented,
    # not promised numerically).
    "metrics": ("BENCH_metrics.json", [
        Gate("l1_matches_brute", "exact"),
        Gate("closeness_ok_l1", "exact"),
        Gate("closeness_ok_chi2", "exact"),
        Gate("closeness_ok_hellinger", "exact"),
        Gate("recall_l1", "min", 0.05),
        Gate("recall_chi2", "min", 0.15),
        Gate("recall_hellinger", "min", 0.15),
        Gate("ok", "exact"),
    ]),
}


def check_suite(
    name: str,
    *,
    results_dir: pathlib.Path = RESULTS,
    baselines_dir: pathlib.Path = BASELINES,
) -> List[str]:
    """All gate failures for one suite (empty = pass)."""
    if name not in GATES:
        return [f"{name}: no regression gates defined; have {sorted(GATES)}"]
    fname, gates = GATES[name]
    base_path = baselines_dir / fname
    res_path = results_dir / fname
    if not base_path.exists():
        return [f"{name}: missing baseline {base_path}"]
    if not res_path.exists():
        return [f"{name}: missing result {res_path} — did the smoke step run?"]
    base = json.loads(base_path.read_text())
    res = json.loads(res_path.read_text())
    smoke_b = base.get("config", {}).get("smoke")
    smoke_r = res.get("config", {}).get("smoke")
    if smoke_b != smoke_r:
        return [
            f"{name}: config.smoke mismatch (baseline {smoke_b!r} vs run {smoke_r!r})"
            " — smoke baselines only gate smoke runs"
        ]
    failures = []
    # Hardware provenance: an XLA:CPU baseline says nothing about a GPU
    # run, so a backend mismatch is a hard failure when both reports are
    # stamped. Device-kind / jax-version drift is informational only —
    # the tier-1 matrix deliberately runs both a pinned floor and
    # latest, and tolerances already absorb low-bit float drift.
    backend_b = base.get("config", {}).get("backend")
    backend_r = res.get("config", {}).get("backend")
    if backend_b is not None and backend_r is not None and backend_b != backend_r:
        return [
            f"{name}: config.backend mismatch (baseline {backend_b!r} vs run"
            f" {backend_r!r}) — refusing to compare across hardware"
        ]
    if backend_b is None or backend_r is None:
        print(f"# note: {name} {'baseline' if backend_b is None else 'result'} "
              "has no backend stamp; cross-hardware comparison not checked")
    for key in ("device_kind", "jax_version"):
        kb = base.get("config", {}).get(key)
        kr = res.get("config", {}).get(key)
        if kb is not None and kr is not None and kb != kr:
            print(f"# note: {name} config.{key} differs (baseline {kb!r} vs run {kr!r})")
    for gate in gates:
        if gate.key not in base:
            failures.append(f"{name}: baseline lacks gated key {gate.key!r}")
            continue
        if gate.key not in res:
            failures.append(f"{name}: result lacks gated key {gate.key!r}")
            continue
        msg = gate.check(base[gate.key], res[gate.key])
        if msg:
            failures.append(f"{name}: {msg}")
    return failures


def main(argv: Iterable[str]) -> int:
    wanted = list(argv) or sorted(GATES)
    unknown = [n for n in wanted if n not in GATES]
    if unknown:
        print(f"unknown suite(s) {unknown}; have {sorted(GATES)}", file=sys.stderr)
        return 2
    all_failures = []
    for name in wanted:
        failures = check_suite(name)
        status = "PASS" if not failures else "FAIL"
        print(f"# regression gate {name}: {status}")
        for f in failures:
            print(f"  REGRESSION {f}")
        all_failures.extend(failures)
    return 1 if all_failures else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
