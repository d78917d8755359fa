"""Telemetry for the FastMatch serving stack — what each signal measures.

FastMatch's claims are rate claims: tuples drawn per query, rounds to
retirement, speedup at equal recall (paper Sec 5-6). This package is the
measurement layer that makes those rates first-class at serve time
instead of post-hoc benchmark artifacts. It has two halves:

* Program spans (`span`), always on: the host work of the serving path,
  written into the profiler's own trace (`jax.profiler`) on the
  device's clock, so the device's idle time can be charged to the host
  work around it. They record only while a profiler runs.
* Telemetry (``MatchServer(telemetry=True)``): a `MetricsRegistry` of
  counters/gauges/latency histograms with Prometheus-text and JSON
  exporters, a `Tracer` recording per-query lifecycle and per-poll
  round-batch counts into a bounded ring with a JSONL sink, and
  per-query tuples-to-confidence trajectories (`Telemetry`). It records
  at existing host-sync/poll boundaries — the jitted `fused_round` /
  pump-round path is untouched, and a telemetry-on run is bit-identical
  to a telemetry-off run (tests/test_obs.py; gated <2% round-throughput
  overhead in benchmarks/telemetry_overhead.py). A scrape bins on the
  host and dispatches no device work.

Program spans
=============

Each is ``fastmatch.<name>``; the per-query ones carry ``qid=``:

  fastmatch.admit (qid)  — `SharedCountsScheduler.admit`: the slot
                           write, the admission stats step and its poll
  fastmatch.retire (qid) — `SharedCountsScheduler.retire`: the answer's
                           assembly, the top-k ids to the host, the
                           slot clear
  fastmatch.sync         — `SharedCountsScheduler._sync`: the one
                           batched device_get (waiting for the round
                           plus every mirror's copy);
                           ``scheduler.host_sync_bytes`` counts its bytes
  fastmatch.dispatch     — enqueueing a jitted round or ingest round
  fastmatch.fetch        — building a window: the ad-hoc fetch, each
                           window pulled from a pass stream, the
                           prefetch worker's fetch, each pump worker's
  fastmatch.pass         — a new pass's order (the read-mask gather over
                           the visit order)
  fastmatch.complete     — exact completion
  fastmatch.collect      — `MatchServer._collect`: outcomes to
                           MatchResults, the autosave check

Metric ↔ paper-quantity map
===========================

Registry metrics (``MatchServer(telemetry=True)``):

  fastmatch_tuples_read_total      — m, the number of samples drawn: the
                                     sample complexity Theorem 1 bounds
                                     and Fig. 6/Table 4 speedups count
  fastmatch_blocks_read_total      — block-granular reads of the Sec 4.2
                                     bitmap-driven I/O manager (the unit
                                     AnyActive decides on)
  fastmatch_blocks_considered_total — valid blocks of the windows
                                     dispatched, read or skipped:
                                     AnyActive's denominator, so
                                     1 - read / considered is its skip
                                     share (Sec 4.2); read alone cannot
                                     tell a skip from an unread window
  fastmatch_rounds_total           — statistics-engine iterations /
                                     windows dispatched: the x-axis of
                                     Fig. 5's per-round view of HistSim
  fastmatch_host_syncs_total       — device↔host polls: the asynchrony
                                     cost the Sec 4.2 relaxation (and
                                     poll_every) amortizes
  fastmatch_passes_total           — cyclic passes over the block layout
  fastmatch_ingest_lane_fill       — gauge, set once from the shape:
                                     V_Z·V_X over the output cells the
                                     ingest kernel's one-hot contraction
                                     computes (`kernels.histogram.
                                     ingest_lane_fill`), the share of
                                     its MXU work that is real counts
  fastmatch_queries_submitted_total/_admitted_total/_retired_total
                                   — the query population the serving
                                     layer multiplexes onto one stream
  fastmatch_query_tuples           — histogram of per-query tuples drawn
                                     while live: the per-query m whose
                                     1/N amortization is the serving win
  fastmatch_query_rounds           — histogram of rounds-to-retirement
                                     (paper Fig. 5: how many rounds
                                     HistSim needs before delta_upper
                                     crosses delta)
  fastmatch_query_wall_seconds     — submit→retire latency (the
                                     interactivity budget of Sec 1)
  prefetch_wait_seconds            — consumer stalls waiting on the
                                     sampling engine: Sec 4.2's "must
                                     never stall the statistics engine",
                                     measured (0 wait = fully hidden)
  prefetch_fetch_seconds           — producer-side gather cost the
                                     double buffer is hiding
  prefetch_queue_depth             — staged windows at the last hand-off
  prefetch_worker_errors_total / prefetch_join_timeouts_total
                                   — structured forms of the prefetch
                                     failure warnings
  checkpoint_save_seconds / checkpoint_save_bytes_total /
  checkpoint_saves_total / checkpoint_save_failures_total /
  checkpoint_gc_swept_total        — warm-start persistence cost and
                                     hygiene (PR 4's cache layer)

Confidence-trajectory columns (`Telemetry.confidence_curve`):

  tuples        — m so far (shared; ``tuples_live`` = charged to the query)
  n_min         — min_i n_i: the worst-sampled candidate, the binding
                  term in every per-candidate Theorem 1 bound
  eps_n         — Theorem 1 eps(n_min) at per-candidate budget
                  delta/|V_Z| (the AnyActive threshold of Sec 4.2):
                  the l1 deviation currently guaranteed for the
                  worst-sampled candidate
  tau_min       — the running distance estimate of the current best
                  candidate (Alg. 1's tau_i for the head of M)
  delta_upper   — sum_i delta_i, the stats tail's failure bound
                  (Alg. 1 line 6 terminates on delta_upper < delta)
  confidence    — 1 - delta_upper: the anytime guarantee level a client
                  could be handed mid-query

Trace events (`Tracer`, JSONL): ``query_enqueue`` → ``query_admit`` →
``round_batch``* (windows since the last poll, rounds, blocks read
and considered, tuples read) → ``query_retire`` → ``query_done``
(the rid↔qid join, emitted by `MatchServer`) (+ ``pass_start``,
``exact_completion``, ``budget_exhausted``, ``checkpoint_save``,
``checkpoint_gc``, ``prefetch_stream``, ``prefetch_worker_error``,
``prefetch_join_timeout``). The skeleton (timing fields stripped) is
deterministic for a seeded workload — the golden span-tree contract.
"""

from repro.obs.registry import (
    DEFAULT_LATENCY_BINS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)
from repro.obs.spans import SPAN_PREFIX, span, spanned
from repro.obs.telemetry import CURVE_COLUMNS, Telemetry
from repro.obs.tracer import TIMING_FIELDS, Tracer

__all__ = [
    "CURVE_COLUMNS",
    "Counter",
    "DEFAULT_LATENCY_BINS",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "SPAN_PREFIX",
    "TIMING_FIELDS",
    "Telemetry",
    "Tracer",
    "span",
    "spanned",
]
