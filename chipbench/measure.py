"""Repeated runs of one cell and their spread, for setting bounds.

    python chipbench/measure.py --workload <cell> --seconds <s> --sets 2 \
        --seeds <n> [<n> ...] [--trace 1] [--out <file.jsonl>]

Runs ``chipbench/run.py`` once per seed and set, one process after
another (this parent never touches JAX, so each child has the chip), and
prints each run's result line, then per set and metric the median and
the spread: the distance between the first and third quartiles of
``statistics.quantiles(values, n=4)`` over the median.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import statistics
import subprocess
import sys

HERE = pathlib.Path(__file__).resolve().parent


def spread(values) -> float:
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", required=True)
    p.add_argument("--seeds", nargs="+", required=True)
    p.add_argument("--sets", type=int, default=1)
    p.add_argument("--trace", default="0")
    p.add_argument("--out", default=None)
    args = p.parse_args(argv)

    sets = []
    for s in range(args.sets):
        runs = []
        for seed in args.seeds:
            cmd = [sys.executable, str(HERE / "run.py"), "--workload", args.workload,
                   "--seed", seed, "--seconds", args.seconds, "--trace", args.trace]
            proc = subprocess.run(cmd, capture_output=True, text=True, timeout=1500)
            lines = proc.stdout.strip().splitlines()
            res = json.loads(lines[-1]) if proc.returncode == 0 and lines else None
            row = {"set": s, "seed": seed, "rc": proc.returncode, "result": res,
                   "stderr_tail": proc.stderr[-1500:]}
            print(json.dumps(row), flush=True)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(row) + "\n")
            runs.append(res)
        sets.append(runs)
    for s, runs in enumerate(sets):
        ok = [r for r in runs if r is not None]
        names = sorted({k for r in ok for k in r["metrics"]})
        summary = {}
        for name in names:
            vals = [r["metrics"][name]["value"] for r in ok if name in r["metrics"]]
            if len(vals) >= 2:
                summary[name] = {"median": statistics.median(vals), "spread": spread(vals),
                                 "n": len(vals)}
        print(json.dumps({"set": s, "runs": len(runs), "correct": sum(bool(r and r["correct"])
                          for r in runs), "summary": summary}), flush=True)


if __name__ == "__main__":
    main()
