"""Readings for the limits of the correctness check, on the chip.

    python chipbench/control.py --workload <cell> --seconds <s> --seeds <n> [<n> ...]

Runs the cell once per seed, in this one process, with the bfloat16
control (`reference.bf16_control`) computed beside the program's own
answers, and prints per seed the program's numbers and the control's.
The lower reading of a number is the largest the program gives over the
seeds; the upper is the smallest the control gives. The benchmark's own
runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
os.environ.setdefault("TPU_LOG_DIR", "disabled")  # libtpu logs to /tmp otherwise


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    args = p.parse_args(argv)

    from chipbench import harness, spec
    from chipbench.load import clock

    import jax

    if jax.devices()[0].platform != "tpu":
        sys.exit("chipbench control: needs a TPU")
    harness.use_compile_cache(str(ROOT))
    cell = spec.find_cell(args.workload)
    lower, upper = {}, {}
    for seed in args.seeds:
        res = harness.run_cell(cell, seed=seed, seconds=args.seconds, trace=False,
                               t_process=clock(), log=lambda m: print(m, file=sys.stderr),
                               control=True)
        prog = {k: v["value"] for k, v in res["checks"].items()}
        ctl = {k: v["value"] for k, v in res["control_checks"].items()}
        for k in prog:
            lower[k] = max(lower.get(k, prog[k]), prog[k])
            upper[k] = min(upper.get(k, ctl[k]), ctl[k])
        print(json.dumps({"seed": seed, "attempted": res["attempted"], "correct": res["correct"],
                          "program": prog, "control_correct": res["control_correct"],
                          "control": ctl}), flush=True)
    print(json.dumps({"workload": cell.name, "seeds": len(args.seeds),
                      "lower_reading": lower, "upper_reading": upper}))


if __name__ == "__main__":
    main()
