"""Reduction from a profiler trace to the numbers the readers use.

`extract` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps
three kinds of event, all on one clock in nanoseconds:

  ops      per device, the "XLA Ops" line: every operation that ran on
           the chip. A TPU names each by its HLO instruction's text
           (``%histogram_with_rowsums.1 = f32[...] custom-call(...)``);
           kept as the instruction's base name (``histogram_with_rowsums``).
           Control flow nests: a ``cond`` op spans the ops of its branch;
  modules  per device, the "XLA Modules" line: one event per execution
           of a compiled program (``jit_fused_round(...)``);
  host     the harness's own ``TraceAnnotation`` spans (``chipbench.*``),
           which say what the host was doing while the chip sat idle.

`Trace` reduces that record: busy time as the union of op intervals,
per-kernel and per-program time and calls, the operations that took
most time (each less the ops nested in it), and the device's idle gaps
labelled by the host span around them. Its tests check it on a cut of a
trace recorded on a v5e and on a small hand-made one.
"""

from __future__ import annotations

import glob
import re
from collections import defaultdict

HOST_PREFIX = "chipbench."
_SUFFIX = re.compile(r"\.\d+$")


def base_name(name: str) -> str:
    """``%histogram_with_rowsums.1 = f32[8] custom-call(...)`` and
    ``histogram_with_rowsums.3`` -> ``histogram_with_rowsums``;
    ``jit_fused_round(123)`` -> ``jit_fused_round``."""
    head = name.split(" = ", 1)[0] if " = " in name else name.split("(")[0]
    return _SUFFIX.sub("", head.strip().lstrip("%"))


def extract(trace_dir: str) -> dict:
    """The events of the one ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane file under {trace_dir}, found {len(paths)}")
    data = ProfileData.from_file(paths[0])
    out = {"ops": {}, "modules": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = plane.name.split(":")[-1]
            for line in plane.lines:
                kind = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
                if kind:
                    out[kind][dev] = [
                        [base_name(ev.name), int(ev.start_ns), int(ev.duration_ns)]
                        for ev in line.events
                    ]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        out["host"].append([ev.name, int(ev.start_ns), int(ev.duration_ns)])
    return out


def _self_times(evs) -> list:
    """(name, ns) of each op less the ops nested inside it."""
    evs = sorted(evs, key=lambda e: (e[1], -e[2]))
    own = [d for _, _, d in evs]
    open_ = []  # indices of the ops that may still hold the next one
    for i, (_, s, d) in enumerate(evs):
        while open_ and evs[open_[-1]][1] + evs[open_[-1]][2] <= s:
            open_.pop()
        if open_ and s + d <= evs[open_[-1]][1] + evs[open_[-1]][2]:
            own[open_[-1]] -= d
        open_.append(i)
    return [(e[0], t) for e, t in zip(evs, own)]


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


class Trace:
    """Reduced view of one traced window ``[t0_ns, t1_ns]`` (host clock)."""

    def __init__(self, events: dict, t0_ns: int, t1_ns: int):
        self.t0, self.t1 = t0_ns, t1_ns
        self.ops = {d: [e for e in evs if e[1] < t1_ns and e[1] + e[2] > t0_ns]
                    for d, evs in events["ops"].items()}
        self.modules = {d: [e for e in evs if e[1] < t1_ns and e[1] + e[2] > t0_ns]
                        for d, evs in events["modules"].items()}
        self.host = [e for e in events["host"] if e[1] < t1_ns and e[1] + e[2] > t0_ns]

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    def _busy(self, dev) -> list:
        return _union(
            [max(s, self.t0), min(s + d, self.t1)] for _, s, d in self.ops[dev]
        )

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.ops:
            return 0.0
        return sum(sum(e - s for s, e in self._busy(d)) for d in self.ops) / len(self.ops) / 1e9

    def op_time(self, name: str) -> tuple:
        """(seconds, calls) of the device operations whose base name is ``name``."""
        evs = [e for evs in self.ops.values() for e in evs if base_name(e[0]) == name]
        return sum(e[2] for e in evs) / 1e9, len(evs)

    def module_time(self, name: str) -> tuple:
        """(seconds, executions) of the compiled program ``name``."""
        evs = [e for evs in self.modules.values() for e in evs if base_name(e[0]) == name]
        return sum(e[2] for e in evs) / 1e9, len(evs)

    def top_ops(self, n: int = 10) -> list:
        """The ``n`` op names that took most device time, each op counted
        less the ops nested in it, in seconds summed over the devices."""
        tot = defaultdict(int)
        for evs in self.ops.values():
            for name, dur in _self_times(evs):
                tot[base_name(name)] += dur
        return [[k, v / 1e9] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> list:
        """Idle device time by what the host was doing: each gap between
        busy intervals is charged to the innermost harness span that holds
        its midpoint ("none" outside every span)."""
        tot = defaultdict(int)
        spans = sorted(self.host, key=lambda e: -e[1])  # later start = inner
        for dev in self.ops:
            edges = [self.t0] + [x for iv in self._busy(dev) for x in iv] + [self.t1]
            for s, e in zip(edges[0::2], edges[1::2]):
                if e <= s:
                    continue
                mid = (s + e) // 2
                label = next(
                    (name for name, hs, hd in spans if hs <= mid < hs + hd), "none"
                )
                tot[label] += e - s
        k = max(len(self.ops), 1)
        return [[name, v / k / 1e9] for name, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]
