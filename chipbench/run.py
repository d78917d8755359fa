"""The chip benchmark's one command.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chip this process finds and
prints, as the last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number the correctness check
compared, with its limit (also the last lines of standard error).

It exits non-zero and prints no result when JAX finds no TPU, or fewer
chips than the cell asks for. It never falls back to the CPU. The
persistent compilation cache is ``.jax_cache`` at the checkout's root.
"""

from __future__ import annotations

import os
import pathlib
import sys
import time


def _process_start() -> float:
    """This process's start on the `time.perf_counter` clock."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.perf_counter() - (uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return time.perf_counter()


T_PROCESS = _process_start()
ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
# libtpu logs to a fixed /tmp path unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def fail(msg: str) -> None:
    print(f"chipbench: {msg}", file=sys.stderr)
    sys.exit(1)


def main(argv=None) -> None:
    import argparse
    import json

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--trace-out", default=None,
                   help="with --trace 1, also write the extracted trace events (JSON) here")
    args = p.parse_args(argv)

    from chipbench import harness, spec

    cell = spec.find_cell(args.workload)
    import jax

    import repro.serve.fastmatch_server  # noqa: F401  (the system under test, or no run)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"needs a TPU; JAX found platform {devices[0].platform!r}")
    if len(devices) < cell.chips:
        fail(f"{cell.name} needs {cell.chips} chips; JAX found {len(devices)}")
    harness.use_compile_cache(str(ROOT))
    result = harness.run_cell(
        cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        t_process=T_PROCESS, log=lambda m: print(m, file=sys.stderr), trace_out=args.trace_out,
    )
    print(json.dumps(result))
    sys.stdout.flush()
    harness.print_checks(result)


if __name__ == "__main__":
    main()
