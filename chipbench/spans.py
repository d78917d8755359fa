"""Program spans (``fastmatch.*``) in a profiler trace, beside the harness's.

`trace.extract` keeps only the harness's own host spans (``chipbench.*``).
The program writes spans of its own into the same trace (`repro.obs.span`:
``fastmatch.admit``, ``.retire``, ``.sync``, ``.dispatch``, ``.fetch``,
``.pass``, ``.complete``, ``.collect``). `extract` here keeps both, each
as ``[name, start_ns, duration_ns, thread, ids]``: the host thread is the
index of the trace line it ran on, and ``ids`` holds a per-query span's
``qid``. `SpanTrace` is `trace.Trace` with two readers more:

  span_time(name)  (seconds, count): a span's self time, its duration
                   less what its child spans on the same thread cover
  idle_gaps(n)     idle device time split exactly over the innermost span,
                   of either prefix, open on the thread that runs
                   ``chipbench.window``; time outside every span is "none"

Every other reader of `trace.Trace` reads a `SpanTrace` to the same
number, with or without program spans. A host event of the older form,
``[name, start_ns, duration_ns]``, counts as thread 0.
"""

from __future__ import annotations

import glob
from collections import defaultdict

from chipbench import trace as tr

PREFIXES = (tr.HOST_PREFIX, "fastmatch.")


def extract(trace_dir: str) -> dict:
    """`trace.extract`, with the program's spans and each span's thread."""
    from jax.profiler import ProfileData

    out = tr.extract(trace_dir)
    (path,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    out["host"] = [
        [ev.name, int(ev.start_ns), int(ev.duration_ns), thread, dict(ev.stats)]
        for plane in ProfileData.from_file(path).planes if plane.name == "/host:CPU"
        for thread, line in enumerate(plane.lines)
        for ev in line.events if ev.name.startswith(PREFIXES)
    ]
    return out


def _thread(ev) -> int:
    return ev[3] if len(ev) > 3 else 0


def _innermost(spans) -> list:
    """Nested ``[name, start, duration]`` spans of one thread as disjoint
    ``(start, end, name)`` pieces, each named by the innermost span open
    over it; time outside every span has no piece."""
    out, stack, t = [], [], 0
    for name, s, d in sorted(spans, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= s:
            end, top = stack.pop()
            out.append((t, end, top))
            t = max(t, end)
        if stack:
            out.append((t, s, stack[-1][1]))
        stack.append((s + d, name))
        t = s
    while stack:
        end, top = stack.pop()
        out.append((t, end, top))
        t = max(t, end)
    return [p for p in out if p[1] > p[0]]


class SpanTrace(tr.Trace):
    """`trace.Trace` that also reads the program's spans."""

    def _by_thread(self) -> dict:
        threads = defaultdict(list)
        for ev in self.host:
            threads[_thread(ev)].append(ev[:3])
        return threads

    def span_time(self, name: str) -> tuple:
        """(seconds, count) of the host span ``name``, each span counted
        less the spans nested in it on its own thread."""
        ns, count = 0, 0
        for evs in self._by_thread().values():
            for span, own in tr._self_times(evs):
                if span == name:
                    ns, count = ns + own, count + 1
        return ns / 1e9, count

    def idle_gaps(self, n: int = 10) -> list:
        """Idle device time by what the window's host thread was doing:
        each gap between busy intervals is split over the innermost spans
        open during it ("none" outside every span), so the total is the
        window's idle time."""
        window = next((ev for ev in self.host if ev[0] == "chipbench.window"), None)
        pieces = _innermost(self._by_thread()[_thread(window) if window else 0])
        tot = defaultdict(int)
        for dev in self.ops:
            edges = [self.t0] + [x for iv in self._busy(dev) for x in iv] + [self.t1]
            j = 0
            for s, e in zip(edges[0::2], edges[1::2]):
                if e <= s:
                    continue
                while j < len(pieces) and pieces[j][1] <= s:
                    j += 1
                covered, i = 0, j
                while i < len(pieces) and pieces[i][0] < e:
                    a, b, name = pieces[i]
                    part = min(b, e) - max(a, s)
                    tot[name] += part
                    covered += part
                    i += 1
                if covered < e - s:
                    tot["none"] += e - s - covered
        k = max(len(self.ops), 1)
        return [[name, v / k / 1e9] for name, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]
