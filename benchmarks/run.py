"""Benchmark aggregator: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (one per measurement) and
writes the same to benchmarks/results/bench_results.csv.

  PYTHONPATH=src python -m benchmarks.run              # everything
  PYTHONPATH=src python -m benchmarks.run fig4 table4  # subset
"""

from __future__ import annotations

import pathlib
import sys
import time

from benchmarks import (
    anytime_curve,
    fault_recovery,
    fig4_bound_ratio,
    fig7_8_epsilon,
    fig9_lookahead,
    fig10_11_delta,
    guarantees,
    metrics_matrix,
    pump_throughput,
    roofline_report,
    serve_throughput,
    stats_throughput,
    table4_speedups,
    telemetry_overhead,
    warm_restart,
)

SUITES = {
    "fig4": fig4_bound_ratio.run,
    "table4": table4_speedups.run,
    "fig7_8": fig7_8_epsilon.run,
    "fig9": fig9_lookahead.run,
    "fig10_11": fig10_11_delta.run,
    "guarantees": guarantees.run,
    "roofline": roofline_report.run,
    "serve": serve_throughput.run,
    "stats": stats_throughput.run,
    "restart": warm_restart.run,
    "pump": pump_throughput.run,
    "telemetry": telemetry_overhead.run,
    "anytime": anytime_curve.run,
    "faults": fault_recovery.run,
    "metrics": metrics_matrix.run,
}


def main() -> None:
    wanted = sys.argv[1:] or list(SUITES)
    # Validate the whole request up front: a typo'd name must exit
    # non-zero BEFORE any suite runs, not after minutes of earlier
    # suites (a CI step asking for a renamed benchmark must fail the
    # workflow, never silently measure the wrong thing).
    unknown = [name for name in wanted if name not in SUITES]
    if unknown:
        raise SystemExit(
            f"unknown suite(s) {unknown}; have {sorted(SUITES)}"
        )
    rows: list = []
    for name in wanted:
        t0 = time.time()
        SUITES[name](rows)
        print(f"# suite {name} done in {time.time() - t0:.1f}s", file=sys.stderr)

    print("name,us_per_call,derived")
    lines = []
    for r in rows:
        line = f"{r['name']},{r['us_per_call']:.1f},{r['derived']}"
        print(line)
        lines.append(line)
    out = pathlib.Path(__file__).parent / "results"
    out.mkdir(exist_ok=True)
    (out / "bench_results.csv").write_text("name,us_per_call,derived\n" + "\n".join(lines) + "\n")


if __name__ == "__main__":
    main()
