"""Offered-rate sweep of an open-loop cell, to find the rate it sustains.

    python chipbench/sweep.py --workload <cell> --seconds <s> --seed <n> --rates <r> [<r> ...]

Runs the cell at each offered rate in this one process (the mix file's
other parameters as they are) and prints per rate the latency median
and 95th percentile, the answers per second, and how many queries were
still in flight when the window closed: a backlog that grows with the
rate marks the rate as above what the server sustains. The sweep stops at the first
rate the server does not sustain: it answers fewer than 97% of the
offered rate, fails a query, or holds more than a tenth of a second of
arrivals in flight at the close.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import re
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
os.environ.setdefault("TPU_LOG_DIR", "disabled")  # libtpu logs to /tmp otherwise


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--rates", type=float, nargs="+", required=True)
    args = p.parse_args(argv)

    from chipbench import harness, spec
    from chipbench.load import clock

    import jax

    if jax.devices()[0].platform != "tpu":
        sys.exit("chipbench sweep: needs a TPU")
    harness.use_compile_cache(str(ROOT))
    cell = spec.find_cell(args.workload)
    for i, rate in enumerate(args.rates):
        cell.mix = dict(cell.mix, rate_per_s=rate)
        logs = []
        res = harness.run_cell(cell, seed=args.seed + i, seconds=args.seconds, trace=False,
                               t_process=clock(), log=logs.append)
        m = {k: v["value"] for k, v in res["metrics"].items()}
        window_line = next(line for line in logs if " in flight at the close" in line)
        in_flight = int(re.search(r"(\d+) in flight at the close", window_line).group(1))
        sustained = (res["failed"] == 0 and m.get("answers_per_s", 0) >= 0.97 * rate
                     and in_flight <= 0.1 * rate)
        print(json.dumps({"rate_per_s": rate, "sustained": sustained, "correct": res["correct"],
                          "attempted": res["attempted"], "failed": res["failed"],
                          "in_flight_at_close": in_flight,
                          **{k: m.get(k) for k in ("answer_p50_ms", "answer_p95_ms",
                                                   "answers_per_s")}}), flush=True)
        if not sustained:
            break


if __name__ == "__main__":
    main()
