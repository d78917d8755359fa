"""Metrics registry: counters, gauges, fixed-bin histograms + exporters.

A deliberately small Prometheus-shaped metrics core for the serving
stack. Three metric kinds, all host-side and lock-protected (the
prefetch worker thread records from off the main thread):

  Counter   — monotone float; ``inc`` only
  Gauge     — last-write-wins float
  Histogram — fixed upper-bound bins (Prometheus ``le`` semantics) with
              running sum/count; observations are O(1) appends on the
              hot path and are BINNED LAZILY at snapshot time on the
              host (numpy), so a scrape never dispatches device work

`MetricsRegistry` is the factory/namespace: ``registry.counter(name)``
returns the existing metric or creates it (re-registering under a
different kind raises). Export formats:

  to_prometheus() — text exposition format (scrape-able / pushable)
  snapshot()      — plain-JSON dict, one entry per metric, used by the
                    BENCH_telemetry report and test assertions

Nothing here touches jitted code: the engine records at host-sync/poll
boundaries only (see `repro.obs` package docstring).
"""

from __future__ import annotations

import json
import math
import threading
from typing import Dict, List, Optional, Sequence

import numpy as np

__all__ = ["Counter", "Gauge", "Histogram", "MetricsRegistry", "DEFAULT_LATENCY_BINS"]

# Upper bin edges (seconds) for latency histograms: 100us .. ~100s,
# roughly x3 steps — wide enough for both a fused-round dispatch and an
# exact-completion pass.
DEFAULT_LATENCY_BINS = (
    1e-4, 3e-4, 1e-3, 3e-3, 1e-2, 3e-2, 0.1, 0.3, 1.0, 3.0, 10.0, 30.0, 100.0
)


def _check_name(name: str) -> str:
    if not name or not all(c.isalnum() or c in "_:" for c in name):
        raise ValueError(f"invalid metric name {name!r}")
    return name


class Counter:
    """Monotone counter (use a ``_total`` suffix by convention)."""

    kind = "counter"

    def __init__(self, name: str, help: str = ""):
        self.name = _check_name(name)
        self.help = help
        self._value = 0.0
        self._lock = threading.Lock()

    def inc(self, amount: float = 1.0) -> None:
        if amount < 0:
            raise ValueError(f"counter {self.name} cannot decrease (inc {amount})")
        with self._lock:
            self._value += amount

    @property
    def value(self) -> float:
        return self._value

    def snapshot(self) -> dict:
        return {"kind": self.kind, "value": self._value}


class Gauge:
    """Last-write-wins instantaneous value."""

    kind = "gauge"

    def __init__(self, name: str, help: str = ""):
        self.name = _check_name(name)
        self.help = help
        self._value = 0.0
        self._lock = threading.Lock()

    def set(self, value: float) -> None:
        with self._lock:
            self._value = float(value)

    def inc(self, amount: float = 1.0) -> None:
        with self._lock:
            self._value += amount

    @property
    def value(self) -> float:
        return self._value

    def snapshot(self) -> dict:
        return {"kind": self.kind, "value": self._value}


class Histogram:
    """Fixed-bin histogram with Prometheus ``le`` bucket semantics.

    ``observe`` is an O(1) list append; samples are binned lazily by
    `_flush` — ``np.searchsorted`` assigns each sample its bin index and
    ``np.bincount`` counts them, on the host. Bin counts are stored
    NON-cumulative per bin plus an overflow bin; the exporter emits the
    cumulative ``le`` form.
    """

    kind = "histogram"

    def __init__(self, name: str, edges: Sequence[float] = DEFAULT_LATENCY_BINS, help: str = ""):
        if not edges or list(edges) != sorted(edges):
            raise ValueError(f"histogram {name}: edges must be sorted and non-empty")
        self.name = _check_name(name)
        self.help = help
        self.edges = tuple(float(e) for e in edges)
        self._counts = np.zeros(len(self.edges) + 1, np.int64)  # [+Inf] last
        self._sum = 0.0
        self._count = 0
        self._pending: List[float] = []
        self._lock = threading.Lock()

    def observe(self, value: float) -> None:
        with self._lock:
            self._pending.append(float(value))
            self._sum += float(value)
            self._count += 1

    def observe_many(self, values: Sequence[float]) -> None:
        """Batch observe under one lock acquisition — for call sites that
        accumulate samples lock-free in a hot path (e.g. the prefetch
        stream's per-window timings) and flush once at a boundary."""
        vals = [float(v) for v in values]
        if not vals:
            return
        with self._lock:
            self._pending.extend(vals)
            self._sum += sum(vals)
            self._count += len(vals)

    def _flush(self) -> None:
        """Bin pending samples on the host."""
        with self._lock:
            pending, self._pending = self._pending, []
        if not pending:
            return
        # side="left": v == edge lands in that edge's bucket (v <= le).
        bins = np.searchsorted(self.edges, np.asarray(pending, np.float64), side="left")
        counts = np.bincount(bins, minlength=len(self.edges) + 1)
        with self._lock:
            self._counts += counts

    @property
    def count(self) -> int:
        return self._count

    @property
    def sum(self) -> float:
        return self._sum

    def bucket_counts(self) -> np.ndarray:
        """Per-bin (non-cumulative) counts, overflow last."""
        self._flush()
        return self._counts.copy()

    def snapshot(self) -> dict:
        self._flush()
        return {
            "kind": self.kind,
            "edges": list(self.edges),
            "buckets": self._counts.tolist(),
            "sum": self._sum,
            "count": self._count,
        }


class MetricsRegistry:
    """Get-or-create namespace of metrics + the two exporters."""

    def __init__(self):
        self._metrics: Dict[str, object] = {}
        self._lock = threading.Lock()

    def _get_or_create(self, cls, name: str, *args, **kwargs):
        with self._lock:
            m = self._metrics.get(name)
            if m is not None:
                if not isinstance(m, cls):
                    raise ValueError(
                        f"metric {name!r} already registered as {m.kind}, not {cls.kind}"
                    )
                return m
            m = cls(name, *args, **kwargs)
            self._metrics[name] = m
            return m

    def counter(self, name: str, help: str = "") -> Counter:
        return self._get_or_create(Counter, name, help)

    def gauge(self, name: str, help: str = "") -> Gauge:
        return self._get_or_create(Gauge, name, help)

    def histogram(
        self, name: str, edges: Sequence[float] = DEFAULT_LATENCY_BINS, help: str = ""
    ) -> Histogram:
        return self._get_or_create(Histogram, name, edges, help)

    def get(self, name: str) -> Optional[object]:
        return self._metrics.get(name)

    def names(self) -> List[str]:
        return sorted(self._metrics)

    # -- exporters ---------------------------------------------------------

    def snapshot(self) -> dict:
        """JSON-able {name: metric snapshot} of every registered metric."""
        return {name: self._metrics[name].snapshot() for name in self.names()}

    def to_json(self) -> str:
        return json.dumps(self.snapshot(), indent=2, sort_keys=True)

    def to_prometheus(self) -> str:
        """Prometheus text exposition format (one scrape body)."""
        lines: List[str] = []
        for name in self.names():
            m = self._metrics[name]
            if m.help:
                lines.append(f"# HELP {name} {m.help}")
            lines.append(f"# TYPE {name} {m.kind}")
            if isinstance(m, Histogram):
                cum = 0
                for edge, c in zip(m.edges, m.bucket_counts()):
                    cum += int(c)
                    lines.append(f'{name}_bucket{{le="{_fmt(edge)}"}} {cum}')
                lines.append(f'{name}_bucket{{le="+Inf"}} {m.count}')
                lines.append(f"{name}_sum {_fmt(m.sum)}")
                lines.append(f"{name}_count {m.count}")
            else:
                lines.append(f"{name} {_fmt(m.value)}")
        return "\n".join(lines) + "\n"


def _fmt(v: float) -> str:
    """Prometheus float rendering: integers without trailing .0 noise."""
    if math.isinf(v):
        return "+Inf" if v > 0 else "-Inf"
    f = float(v)
    return str(int(f)) if f == int(f) else repr(f)
