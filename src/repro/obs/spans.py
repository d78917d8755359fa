"""Program spans in the profiler's trace.

`span` names one piece of the serving path's host work (``fastmatch.``
plus the piece's name) as a `jax.profiler.TraceAnnotation`. The spans
are always on: with no profiler running, entering and leaving one costs
about a microsecond and records nothing; under `jax.profiler.start_trace`
each lands on the host thread's line of the trace, on the same clock as
the device's operations, so the device's idle time can be charged to
the host work around it.

Per-round spans take no keyword; per-query spans carry ``qid=`` so all
spans of one request share that id.
"""

from __future__ import annotations

from jax.profiler import TraceAnnotation

__all__ = ["SPAN_PREFIX", "span", "spanned"]

SPAN_PREFIX = "fastmatch."


def span(name: str, **ids) -> TraceAnnotation:
    """``with span("sync"):`` records ``fastmatch.sync`` around the block."""
    return TraceAnnotation(SPAN_PREFIX + name, **ids)


def spanned(stream, name: str):
    """Yield from ``stream``, each pull inside ``span(name)`` (the time
    spent producing an item, not the time its consumer holds it)."""
    it = iter(stream)
    done = object()
    while True:
        with span(name):
            item = next(it, done)
        if item is done:
            return
        yield item
