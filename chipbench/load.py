"""The one traffic generator: reads a mix file and drives `MatchServer`.

A mix file (``traffic/<name>.json``) holds only parameters:

  loop           "closed": the next batch is sent when the last one is
                 answered; "open": batches arrive on a schedule whatever
                 has finished
  batch          queries submitted together (a session, or one arrival)
  fresh_server   closed loop only: each batch opens a new server, whose
                 shared counts start empty
  rate_per_s     open loop only: arrivals per second; the run sends
                 round(rate * seconds) arrivals at uniform random times
                 (a Poisson process given its count), so every seed
                 offers the same work in another order
  warmup         {"batches": b, "batch": n}: untimed batches served in
                 set-up, on the window's shared server (open loop) or on
                 a server of their own (closed loop)
  server         keyword arguments of `MatchServer`
  target_l1      [lo, hi]: each target is the configuration's planted
                 target moved to an l1 distance drawn uniformly from it

Every query is a top-k query with the configuration's k, eps and delta.
Everything runs from one thread: `submit`, then `step()` until the
answer is in ``server.results``. An answer is timed on this module's
clock from the query's due time to the return of the `step()` after
which it is there. The host spans (``chipbench.open``, ``.submit``,
``.step``, ``.wait``) label the trace's idle gaps.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time

import numpy as np

from chipbench.gen import perturb_distribution

clock = time.perf_counter
LOOPS = ("closed", "open")


def check_mix(mix: dict) -> None:
    if mix["loop"] not in LOOPS:
        raise ValueError(f"loop must be one of {LOOPS}, got {mix['loop']!r}")
    if mix["loop"] == "open" and (mix.get("fresh_server") or "rate_per_s" not in mix):
        raise ValueError("an open loop needs rate_per_s and one shared server")
    if int(mix["batch"]) < 1:
        raise ValueError("batch must be at least 1")


@dataclasses.dataclass
class Record:
    server: int
    rid: int
    due: float
    submitted: float
    target: np.ndarray
    done: float = float("nan")
    result: object = None
    snapshot: tuple = ()


class Client:
    """The harness's side of the served path, with its bookkeeping.

    ``snapshots`` keeps, for every answer, the blocks the server had read
    when it returned it (packed bits), keyed by (server, blocks read);
    ``order`` lists those keys as they were taken."""

    def __init__(self, mix: dict, cfg: dict, target: np.ndarray, source, rng, *, tracing: bool):
        from repro.serve.fastmatch_server import MatchServer

        check_mix(mix)
        self._server_cls = MatchServer
        self.mix, self.cfg, self.planted, self.source, self.rng = mix, cfg, target, source, rng
        self.servers: list = []
        self.outstanding: dict = {}  # server index -> {rid: Record}
        self.records: list = []
        self.snapshots: dict = {}
        self.order: list = []
        self.counters0: list = []  # per server: (tuples_read, host_syncs) at window start
        if tracing:
            from jax.profiler import TraceAnnotation

            self.span = lambda name: TraceAnnotation(f"chipbench.{name}")
        else:
            self.span = lambda name: contextlib.nullcontext()

    # -- servers and queries ----------------------------------------------

    def new_server(self) -> int:
        with self.span("open"):
            s = self._server_cls(self.source, seed=int(self.rng.integers(2**31)), **self.mix["server"])
        self.servers.append(s)
        self.outstanding[len(self.servers) - 1] = {}
        self.counters0.append((0, 0))
        return len(self.servers) - 1

    def mark_window_start(self) -> None:
        """Count the window's work from here on every existing server."""
        self.counters0 = [
            (s.scheduler.tuples_read, s.scheduler.host_syncs) for s in self.servers
        ]
        self.records = []

    def submit(self, sidx: int, due: float) -> None:
        lo, hi = self.mix["target_l1"]
        target = perturb_distribution(self.planted, float(self.rng.uniform(lo, hi)), self.rng)
        with self.span("submit"):
            rid = self.servers[sidx].submit(
                target, k=self.cfg["k"], eps=self.cfg["eps"], delta=self.cfg["delta"]
            )
        rec = Record(sidx, rid, due, clock(), target)
        self.outstanding[sidx][rid] = rec
        self.records.append(rec)

    def step(self, sidx: int) -> None:
        server = self.servers[sidx]
        with self.span("step"):
            server.step()
        now = clock()
        waiting = self.outstanding[sidx]
        done = [rid for rid in waiting if rid in server.results]
        if not done:
            return
        sched = server.scheduler
        key = (sidx, sched.blocks_read)
        if key not in self.snapshots:
            self.snapshots[key] = np.packbits(sched.read_mask)
            self.order.append(key)
        for rid in done:
            rec = waiting.pop(rid)
            rec.done, rec.result, rec.snapshot = now, server.results.pop(rid), key

    def busy(self, sidx: int) -> bool:
        s = self.servers[sidx]
        return bool(s.pending or s.scheduler.tickets)

    def counters(self) -> dict:
        """The window's scheduler counters, summed over its servers."""
        tuples = sum(s.scheduler.tuples_read - c[0] for s, c in zip(self.servers, self.counters0))
        syncs = sum(s.scheduler.host_syncs - c[1] for s, c in zip(self.servers, self.counters0))
        return {"tuples_read": int(tuples), "host_syncs": int(syncs)}

    # -- the two loops -----------------------------------------------------

    def serve_batch(self, sidx: int, due: float, deadline: float) -> None:
        for _ in range(int(self.mix["batch"])):
            self.submit(sidx, due)
        while self.outstanding[sidx] and clock() < deadline:
            self.step(sidx)

    def warm_up(self) -> None:
        """Untimed batches that compile (or load) every program the window runs."""
        w = self.mix.get("warmup", {"batches": 0})
        shared = None if self.mix.get("fresh_server") else self.new_server()
        for _ in range(int(w["batches"])):
            sidx = self.new_server() if shared is None else shared
            for _ in range(int(w.get("batch", self.mix["batch"]))):
                self.submit(sidx, clock())
            while self.outstanding[sidx]:
                self.step(sidx)

    def run(self, seconds: float, grace: float) -> tuple:
        """The measured window; returns (its start, queries due in it).
        Those are served until answered or until ``grace`` seconds after
        its close."""
        if self.mix["loop"] == "closed":
            t0 = clock()
            self.mark_window_start()
            end, deadline = t0 + seconds, t0 + seconds + grace
            while clock() < end:
                due = clock()
                sidx = self.new_server() if self.mix.get("fresh_server") else 0
                self.serve_batch(sidx, due, deadline)
            return t0, len(self.records)
        n = int(round(float(self.mix["rate_per_s"]) * seconds))
        offsets = np.sort(self.rng.uniform(0.0, seconds, n))
        sidx = len(self.servers) - 1
        t0 = clock()
        self.mark_window_start()
        deadline = t0 + seconds + grace
        i = 0
        while clock() < deadline:
            now = clock()
            while i < n and t0 + offsets[i] <= now:
                for _ in range(int(self.mix["batch"])):
                    self.submit(sidx, t0 + offsets[i])
                i += 1
            if self.busy(sidx):
                self.step(sidx)
            elif i < n:
                with self.span("wait"):
                    time.sleep(max(0.0, t0 + offsets[i] - clock()))
            else:
                break
        return t0, n * int(self.mix["batch"])
