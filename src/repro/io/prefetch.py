"""Double-buffered background-thread block prefetch (paper Sec 4.2).

"The sampling engine must never stall the statistics engine": while the
device runs round t's ingest+stats, a worker thread gathers window t+1
from the wrapped source into a bounded queue. With a queue depth of 2
this is classic double buffering — the consumer always finds the next
window staged unless the underlying source is genuinely slower than the
compute, in which case the queue provides back-pressure instead of
unbounded memory growth.

Abandonment-safe: closing the stream generator mid-pass (a query
retires, the budget cuts) signals the worker and drains the queue so
a blocked `put` can never leak the thread. A worker exception is
re-raised at the consumer's next pull while the stream is being
driven; if the stream was already closed when the worker failed (the
error has nowhere to surface) it is logged instead of vanishing, as is
a worker that outlives the closing join (blocked inside a slow
``inner.fetch``).

With ``telemetry=`` attached the stream measures the stall-vs-hide
balance the double buffer exists for: per window, the producer-side
fetch cost (``prefetch_fetch_seconds`` — what is being hidden) and the
consumer-side residual wait (``prefetch_wait_seconds`` — what leaked
through), plus queue depth at each hand-off; one ``prefetch_stream``
trace event per stream summarizes windows, total wait/fetch, the
hidden fraction, and the stall fraction. The failure warnings above
are mirrored as structured events (``prefetch_worker_error`` /
``prefetch_join_timeout``) with matching counters, so a dashboard sees
them even when nobody greps logs.
"""

from __future__ import annotations

import logging
import queue
import threading
import time
from typing import Iterable, Iterator, Optional

import numpy as np

from repro.io.block_source import BlockSource, WindowData
from repro.io.faults import FetchCancelled, find_resilient
from repro.obs.spans import span

__all__ = ["PrefetchSource"]

logger = logging.getLogger(__name__)


class PrefetchSource:
    """Wrap any `BlockSource`; `stream` overlaps fetch with consumption.

    ``join_timeout`` bounds how long closing a stream waits for the
    worker thread (it is a daemon, so an over-timeout worker cannot
    hang interpreter exit — but it IS still running, which is why the
    timeout warns instead of passing silently).
    """

    def __init__(self, inner: BlockSource, *, depth: int = 2,
                 join_timeout: float = 10.0, telemetry=None):
        if depth < 1:
            raise ValueError(f"need depth >= 1, got {depth}")
        self.inner = inner
        self.depth = depth
        self.join_timeout = join_timeout
        self.telemetry = telemetry
        self.num_blocks = inner.num_blocks
        self.block_size = inner.block_size
        self.v_z = inner.v_z
        self.v_x = inner.v_x
        self.tuples_per_block = inner.tuples_per_block
        if telemetry is not None:
            reg = telemetry.registry
            self._h_wait = reg.histogram(
                "prefetch_wait_seconds",
                help="consumer stall per window (0 = fully hidden)")
            self._h_fetch = reg.histogram(
                "prefetch_fetch_seconds",
                help="producer-side gather cost per window")
            self._g_depth = reg.gauge(
                "prefetch_queue_depth", "staged windows at last hand-off")
            self._c_errors = reg.counter(
                "prefetch_worker_errors_total", "prefetch worker exceptions")
            self._c_timeouts = reg.counter(
                "prefetch_join_timeouts_total",
                "stream closes that abandoned a still-running worker")
            self._c_dropped = reg.counter(
                "prefetch_dropped_errors_total",
                "worker errors that surfaced only after stream close")

    def fetch(self, win: np.ndarray, pad_to: Optional[int] = None) -> WindowData:
        return self.inner.fetch(win, pad_to)

    def stream(
        self, windows: Iterable[np.ndarray], pad_to: Optional[int] = None
    ) -> Iterator[WindowData]:
        windows = list(windows)
        tel = self.telemetry
        q: queue.Queue = queue.Queue(maxsize=self.depth)
        stop = threading.Event()
        # Cooperative cancellation: hand the stop flag to a wrapped
        # ResilientSource so a worker "blocked" in inner.fetch is really
        # blocked in a cancellable backoff wait — stream close then stops
        # the retry loop at its next boundary instead of riding out the
        # remaining backoff schedule past join_timeout.
        resilient = find_resilient(self.inner)
        if resilient is not None:
            resilient.set_cancel_event(stop)
        failure: list = []  # the worker's exception, whether or not it queued
        # Stall-vs-hide accounting. Lock-free by construction in the
        # hot path: each list/counter has exactly one writer thread
        # (fetch_times/produced — worker; wait_times — consumer), so no
        # registry or stats lock is touched per window. Shared locks
        # here ping-pong the GIL against the dispatch loop — measured
        # at several % of round throughput. Flushed into the registry
        # once, at stream close.
        fetch_times: list = []  # worker-owned
        wait_times: list = []  # consumer-owned
        produced = [0]  # worker-owned; consumer reads it to estimate depth
        depth_last = 0

        def _put(item) -> bool:
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.05)
                    return True
                except queue.Full:
                    continue
            return False

        def worker():
            try:
                for win in windows:
                    if stop.is_set():
                        return
                    t0 = time.perf_counter()
                    with span("fetch"):
                        wd = self.inner.fetch(win, pad_to)
                    if tel is not None:
                        fetch_times.append(time.perf_counter() - t0)
                    if not _put(("data", wd)):
                        return
                    produced[0] += 1
                _put(("done", None))
            except FetchCancelled:
                # The consumer closed the stream and the resilient layer
                # abandoned the in-flight fetch — a clean shutdown, not
                # an error.
                return
            except BaseException as exc:
                # Recorded unconditionally: the queued ("error", ...) item
                # is lost when the consumer is already closing (stop set,
                # queue being drained), and an error must never vanish.
                failure.append(exc)
                if tel is not None:
                    self._c_errors.inc(1)
                    tel.tracer.emit(
                        "prefetch_worker_error",
                        source=type(self.inner).__name__, error=repr(exc),
                    )
                _put(("error", exc))

        t = threading.Thread(target=worker, name="block-prefetch", daemon=True)
        t.start()
        raised = False
        try:
            while True:
                if tel is None:
                    kind, payload = q.get()
                else:
                    t0 = time.perf_counter()
                    kind, payload = q.get()
                    wait_times.append(time.perf_counter() - t0)
                    # produced - consumed, sans the queue's mutex: the
                    # worker's counter may lag a put by an instant, so
                    # this is an estimate — fine for a gauge.
                    depth_last = max(produced[0] - len(wait_times), 0)
                if kind == "done":
                    break
                if kind == "error":
                    raised = True
                    raise payload
                yield payload
        finally:
            stop.set()
            try:
                while True:
                    q.get_nowait()
            except queue.Empty:
                pass
            t.join(timeout=self.join_timeout)
            if t.is_alive():
                logger.warning(
                    "prefetch worker still running %.1fs after stream close "
                    "(blocked in %s.fetch?); abandoning daemon thread",
                    self.join_timeout, type(self.inner).__name__,
                )
                if tel is not None:
                    self._c_timeouts.inc(1)
                    tel.tracer.emit(
                        "prefetch_join_timeout",
                        source=type(self.inner).__name__,
                        timeout_s=self.join_timeout,
                    )
            elif failure and not raised:
                logger.warning(
                    "prefetch worker failed after the stream was closed; "
                    "dropping: %r", failure[0],
                )
                if tel is not None:
                    self._c_dropped.inc(1)
                    tel.tracer.emit(
                        "prefetch_dropped_error",
                        source=type(self.inner).__name__,
                        error=repr(failure[0]),
                    )
            if resilient is not None and resilient.cancel_event is stop:
                resilient.set_cancel_event(None)
            if tel is not None:
                # Registry flush, off the hot path. The worker has
                # exited (or been abandoned past join_timeout — its
                # list stays safely readable, appends are atomic).
                self._h_fetch.observe_many(fetch_times)
                self._h_wait.observe_many(wait_times)
                self._g_depth.set(depth_last)
                snap = {
                    "windows": len(wait_times),
                    "wait_s": float(sum(wait_times)),
                    "fetch_s": float(sum(fetch_times)),
                }
                # The double buffer's report card: hidden_s is gather
                # wall the consumer never waited for; stall_frac is the
                # share that leaked through as stalls.
                snap["hidden_s"] = max(snap["fetch_s"] - snap["wait_s"], 0.0)
                # min(…, 1.0): hand-off/scheduling overhead can make the
                # measured wait exceed the fetch wall it is charged to
                snap["stall_frac"] = min(
                    snap["wait_s"] / snap["fetch_s"] if snap["fetch_s"] > 0 else 0.0,
                    1.0,
                )
                tel.tracer.emit(
                    "prefetch_stream", source=type(self.inner).__name__, **snap
                )
