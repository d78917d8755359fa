"""FastMatch query server: N concurrent matching queries, one I/O stream.

`MatchServer` is the interactive frontend the paper positions FastMatch
as ("identify the top-k closest histograms" for a user-specified
target), generalized to a query population: a request queue feeding a
fixed pool of ``max_queries`` slots (padded for stable jit shapes) over
one `SharedCountsScheduler`. The server is metric-pluggable
(``metric="l1" | "chi2" | "hellinger"`` selects the registry distance
the shared tau pass computes) and serves TWO query types through the
same queue and counts matrix: top-k matching (`submit`) and tolerant
closeness testing (`submit_closeness`) — a closeness query admitted
mid-stream next to live top-k queries shares their samples and triggers
no recompilation. Mechanics:

  admission  — pending requests enter free slots at every round
               boundary, mid-stream; a newly admitted query starts from
               the already-accumulated shared counts (with the full
               shared ``n_i`` — sampling was target-independent), which
               is where the serving speedup over one-engine-per-query
               comes from
  serving    — one AnyActive marking per window against the UNION of
               per-query active sets, one shared ingest, one vmapped
               stats step for all live queries
  retirement — a query leaves its slot the moment its own
               ``delta_upper < delta`` bound fires and is returned as a
               per-query `MatchResult`; the freed slot is refilled from
               the queue
  cache      — the shared counts matrix and the global read_mask
               persist across the server's lifetime: once the sampled
               prefix covers a later query's needs it terminates
               without any new I/O, and after an exact completion every
               subsequent query is answered instantly and exactly

The loop underneath is the device-resident `multiquery.fused_round`:
block data arrives through a pluggable `repro.io.BlockSource` (pass a
`PrefetchSource` to overlap next-window gathering with the current
round), and with ``poll_every > 1`` the scheduler dispatches that many
windows between device polls — admission and retirement then lag the
device by at most ``poll_every - 1`` windows (bounded staleness; the
generalized paper-Sec 4.2 relaxation) in exchange for ~``poll_every``x
fewer device↔host round-trips (`scheduler.host_syncs`). With ``mesh``
given, the shared counts matrix is candidate-sharded over the mesh's
model axis (V_Z must divide by that axis's size, else construction
raises ValueError), so one server spans a data-parallel mesh; add ``pump=True``
to replace the single gathered window stream with one `ShardedSource`
stream per data-parallel worker feeding the explicit-collective pump
round — ingest bandwidth then scales with worker count (see the
stream-vs-pump dispatch table and per-round collective inventory in
`repro.core.pump`).

Per-query `MatchResult` counters (blocks/tuples/rounds) measure what
was read WHILE that query was live — the amortized per-query I/O the
`benchmarks/serve_throughput.py` benchmark compares against running
`run_engine` once per query.

Warm-start persistence (the restart analogue of the serving speedup):
with ``checkpoint_dir=`` the server snapshots the warm cache — the
shared counts matrix, per-candidate row sums, the without-replacement
``read_mask`` + read counters, and the pass/visit-order bookkeeping —
crash-atomically through `repro.checkpoint.CheckpointManager`, bound to
the dataset layout + `MultiQuerySpec` by a config hash so a stale cache
is rejected at restore rather than silently corrupting bounds. The
contract:

  persisted   — everything target-independent (`multiquery.CacheSnapshot`):
                counts, n, read_mask, blocks/tuples/rounds counters,
                passes, the cyclic visit-order offset
  re-queued   — live query slots and the pending queue are NOT
                persisted: in-flight queries must be resubmitted after a
                restart. Because sampling is target-independent this is
                lossless — a resubmitted query admits against the full
                restored counts with its full shared ``n_i``, exactly as
                a late query on an uninterrupted server would.
  consistency — autosave runs at poll boundaries (after retirements),
                never per window. Even with ``poll_every > 1`` a
                snapshot is internally consistent: counts and cursor are
                outputs of the SAME fused dispatch, so the saved
                read_mask always matches the saved counts — staleness
                with respect to still-live queries only shortens the
                warm prefix, it never invalidates it.

`MatchServer.restore(dataset, checkpoint_dir=...)` is warm
construction: build, load the newest complete snapshot (elastic across
mesh shapes via `core.distributed.cache_pspecs` when ``mesh=`` is
given), and serve — a restarted server answers a fresh query with
bit-identical counts/tau/result to an uninterrupted one
(tests/test_warm_restart.py; benchmarks/warm_restart.py measures the
tuples-per-query gap vs a cold restart).

Anytime serving (progressive results + SLA stopping)
----------------------------------------------------

Every live query has a valid Theorem-1-style statement at every poll
boundary, not just at retirement. `poll_result(rid)` returns the
current `AnytimeAnswer` — best set so far (closest first), per-
candidate margin, ``eps_n`` (the metric-space deviation guaranteed at
the per-candidate budget delta/|V_Z|), ``delta_upper`` and
``confidence`` — assembled host-side from the last poll's mirrors, so
polling never dispatches device work or perturbs the loop.
`iter_results(rid)` drives `step()` and yields each answer as it
tightens, ending with the ``status="done"`` final answer; the fully
converged stream ends bit-identically to the blocking result.

SLA-driven stopping: pass ``stop=StopPolicy(wall_ms=...,
confidence=..., tuples=...)`` to `submit`/`submit_closeness` (or
``default_stop=`` at construction for a server-wide default). A
stopped query retires with the honest anytime answer of its stopping
poll — ``exact=False``, ``stopped=True`` with the reason, the achieved
``delta_upper`` attached — bit-identical to what `poll_result` would
have said at that round. The statistical rule always wins a tie, and
supervisor deadline shedding (`ServeSupervisor`) composes as
``stop_reason="deadline"``.

Guarantees and failure modes
----------------------------

The complete guarantee contract — Theorem-1 (eps, delta), the
closeness promise band [eps, eps+gap], metric-native vs conservative
bounds (``bounds_mode``), early-reject pruning (``prune``), SLA
early-stop semantics, quarantine degradation (``eps_effective = eps +
2q``) and the four-tier fault taxonomy (transient I/O retries that
stay bit-identical, permanent-I/O quarantine, crash recovery via
`ServeSupervisor`, overload shedding) — lives in ``docs/guarantees.md``
with exactly which server knobs weaken which guarantee. The short
version: serving degrades honestly, it never blocks and never lies;
every weakened answer says so on the result (``exact`` / ``degraded``
/ ``stopped`` / ``eps_effective``).

`metrics` exposes the health surface: ``last_error`` (most recent
crash/shed cause, "" when healthy), ``queries_shed``,
``blocks_quarantined``, ``degraded`` and ``eps_inflation`` (the 2q
widening every in-flight guarantee currently carries).
"""

from __future__ import annotations

import dataclasses
import pathlib
import time
from collections import deque
from typing import Deque, Dict, Optional

import jax
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.core.engine import MatchResult
from repro.core.multiquery import (
    AnytimeAnswer,
    MultiQuerySpec,
    QueryOutcome,
    SharedCountsScheduler,
    StopPolicy,
    cache_config_hash,
)
from repro.io import as_block_source, maybe_chaos
from repro.obs import Telemetry, span

__all__ = [
    "AnytimeAnswer",
    "MatchQuery",
    "MatchServer",
    "StopPolicy",
    "answer_from_result",
]


def answer_from_result(res: MatchResult, *, metric: str) -> AnytimeAnswer:
    """Degrade a blocking `MatchResult` to a ``status="done"`` anytime
    answer.

    Used when only the retired result survives — e.g. polling a query
    resolved before a supervisor crash rebuild. The per-round fields the
    retirement poll would have carried (split, eps_n, the query's
    eps/delta) are not recoverable from the result alone and come back
    NaN; the set, tau, margin and delta_upper are exact.
    """
    ids = np.asarray(res.ids)
    tau_full = np.asarray(res.state.tau)
    du = float(res.delta_upper)
    return AnytimeAnswer(
        qid=-1, qtype=res.qtype, status="done", ids=ids,
        tau=tau_full[ids], margin=np.asarray(res.state.eps_i)[ids],
        split=float("nan"), n_min=float(np.asarray(res.state.n).min()),
        tau_min=float(tau_full.min()), eps_n=float("nan"),
        delta_upper=du, confidence=max(0.0, 1.0 - du),
        round=res.rounds, tuples=res.tuples_read,
        tuples_live=res.tuples_read, eps=float("nan"),
        delta=float("nan"), metric=metric,
        exact=res.exact, stopped=res.stopped,
        stop_reason=res.stop_reason, result=res,
    )


@dataclasses.dataclass
class MatchQuery:
    """One queued request: a top-k match (Problem 1 instance) or a
    tolerant closeness test (qtype="closeness", k unused, gap > 0)."""

    rid: int
    target: np.ndarray  # (V_X,) unnormalized or normalized target histogram
    k: int
    eps: float
    delta: float
    submit_time: float
    qtype: str = "topk"  # "topk" | "closeness"
    gap: float = 0.0  # closeness promise gap
    stop: Optional[StopPolicy] = None  # SLA policy; None = server default


class MatchServer:
    """Serve top-k histogram-matching queries over one shared sample stream."""

    def __init__(
        self,
        dataset,
        *,
        max_queries: int = 8,
        criterion: str = "histsim",
        policy: str = "anyactive",
        lookahead: int = 512,
        seed: int = 0,
        start_block: Optional[int] = None,
        max_passes: int = 64,
        poll_every: int = 1,
        mesh=None,
        model_axis: str = "model",
        pump: bool = False,
        data_axes=("data",),
        prefetch: bool = False,
        k_cap: Optional[int] = None,
        checkpoint_dir: Optional[str] = None,
        autosave_every: int = 8,
        autosave_rounds: Optional[int] = None,
        checkpoint_keep_last: int = 3,
        telemetry=None,
        metric: str = "l1",
        bounds_mode: str = "native",
        prune: bool = False,
        default_stop: Optional[StopPolicy] = None,
    ):
        # k_cap: static bound on any query's k — lets the per-slot
        # deviation assignment use a (k_cap+1)-element top_k instead of
        # V_Z order stats; submissions with k > k_cap are rejected.
        #
        # pump: with mesh given, serve through the data-parallel
        # `repro.core.pump.DistributedPump` — one ShardedSource window
        # stream per worker along ``data_axes`` feeding the explicit
        # shard_map round — instead of the single-stream round over one
        # global stream (see the dispatch table in core/pump.py).
        # Requires the raw BlockedDataset. ``prefetch`` overlaps the
        # next-window gather with the current round — per worker in
        # pump mode, on the single stream otherwise.
        #
        # checkpoint_dir: enable warm-start persistence (see module
        # docstring). autosave_every: snapshot after this many query
        # retirements (0 disables retirement-cadence autosave);
        # autosave_rounds: additionally snapshot whenever this many new
        # device rounds have run since the last save. Both fire at poll
        # boundaries, off the per-window hot path; `save_cache()` forces
        # a snapshot at any time.
        #
        # telemetry: True builds a fresh `repro.obs.Telemetry`; an
        # existing instance is adopted as-is (one instance per server —
        # query ids key its curve store). The handle is threaded into
        # the scheduler/pump, every PrefetchSource, and the
        # CheckpointManager; None (default) leaves every layer on its
        # untouched zero-overhead path.
        #
        # metric: the registry distance every query on this server is
        # stated in ("l1" | "chi2" | "hellinger") — static per server;
        # see docs/guarantees.md for what to expect from non-l1 bounds.
        #
        # bounds_mode: "native" (default) routes failure bounds through
        # the observation-aware per-metric budgets (never looser than
        # the uniform ones; l1 is bit-identical either way);
        # "conservative" keeps the PR-9 uniform budgets. prune: enable
        # early-reject pruning of clearly-far candidates from the I/O
        # marking (static flag — flipping it recompiles). default_stop:
        # server-wide SLA StopPolicy for queries submitted without one.
        if telemetry is True:
            telemetry = Telemetry()
        elif telemetry is False:
            telemetry = None
        self.telemetry = telemetry
        if telemetry is not None:
            self._c_submitted = telemetry.registry.counter(
                "fastmatch_queries_submitted_total",
                "requests accepted into the queue",
            )
        if pump:
            if mesh is None:
                raise ValueError("pump=True is the data-parallel mesh path; pass mesh=")
            from repro.core.pump import DistributedPump

            self.spec = MultiQuerySpec(
                v_z=dataset.v_z,
                v_x=dataset.v_x,
                max_queries=max_queries,
                criterion=criterion,
                k_cap=k_cap,
                metric=metric,
                bounds_mode=bounds_mode,
                prune=prune,
                default_stop=default_stop,
            )
            self.scheduler = DistributedPump(
                dataset,
                self.spec,
                mesh=mesh,
                data_axes=data_axes,
                model_axis=model_axis,
                policy=policy,
                window=lookahead,
                seed=seed,
                start_block=start_block,
                poll_every=poll_every,
                prefetch=prefetch,
                telemetry=telemetry,
            )
        else:
            if tuple(data_axes) != ("data",):
                raise ValueError(
                    "data_axes only shapes the data-parallel pump; pass pump=True"
                )
            source = maybe_chaos(as_block_source(dataset))
            if prefetch:
                # Same semantics as pump mode: overlap the next window's
                # gather with the current round (worthwhile when the
                # source is host-resident or remote).
                from repro.io import PrefetchSource

                source = PrefetchSource(source, telemetry=telemetry)
            self.spec = MultiQuerySpec(
                v_z=source.v_z,
                v_x=source.v_x,
                max_queries=max_queries,
                criterion=criterion,
                k_cap=k_cap,
                metric=metric,
                bounds_mode=bounds_mode,
                prune=prune,
                default_stop=default_stop,
            )
            self.scheduler = SharedCountsScheduler(
                source,
                self.spec,
                policy=policy,
                window=lookahead,
                seed=seed,
                start_block=start_block,
                poll_every=poll_every,
                mesh=mesh,
                model_axis=model_axis,
                telemetry=telemetry,
            )
        self.max_passes = max_passes
        self._mesh = mesh
        self._model_axis = model_axis
        self._manager: Optional[CheckpointManager] = None
        if checkpoint_dir is not None:
            self._manager = CheckpointManager(
                checkpoint_dir,
                keep_last=checkpoint_keep_last,
                config_hash=cache_config_hash(self.scheduler.source, self.spec),
                telemetry=telemetry,
            )
        self.autosave_every = autosave_every
        self.autosave_rounds = autosave_rounds
        self._retired_since_save = 0
        self._rounds_at_save = 0
        self.pending: Deque[MatchQuery] = deque()
        self.results: Dict[int, MatchResult] = {}
        # Health surface (scraped via `metrics`; the supervisor writes
        # these on crash recovery / load shedding).
        self.last_error = ""
        self.queries_shed = 0
        self._rid_of_qid: Dict[int, int] = {}
        self._qid_of_rid: Dict[int, int] = {}  # live queries only
        # Retirement-time anytime statements, kept so poll_result on a
        # done query replays the exact final answer.
        self._anytime: Dict[int, AnytimeAnswer] = {}
        self._submit_time: Dict[int, float] = {}
        self._next_rid = 0
        # step()'s pass cursor (None = start a fresh pass next step)
        self._pass_order: Optional[np.ndarray] = None
        self._pass_pos = 0
        self._pass_read = 0
        self._pass_start_rounds = 0

    # -- request queue -----------------------------------------------------

    def submit(
        self,
        target: np.ndarray,
        *,
        k: int,
        eps: float = 0.06,
        delta: float = 0.01,
        stop: Optional[StopPolicy] = None,
    ) -> int:
        """Queue a top-k query; returns a request id resolved in `results`.

        Validates here, at the caller's call site — a malformed request
        must not sit in the queue and blow up mid-drain. ``stop``
        attaches an SLA `StopPolicy` (None inherits the server's
        ``default_stop``).
        """
        target = np.asarray(target, np.float64).ravel()
        if target.shape != (self.spec.v_x,):
            raise ValueError(f"target must have shape ({self.spec.v_x},), got {target.shape}")
        if not (0 < k <= self.spec.v_z):
            raise ValueError(f"need 0 < k <= V_Z={self.spec.v_z}, got k={k}")
        if self.spec.k_cap is not None and k > self.spec.k_cap:
            raise ValueError(f"k={k} exceeds the server's k_cap={self.spec.k_cap}")
        return self._enqueue(target, k=k, eps=eps, delta=delta, stop=stop)

    def submit_closeness(
        self,
        target: np.ndarray,
        *,
        eps: float,
        gap: float,
        delta: float = 0.01,
        stop: Optional[StopPolicy] = None,
    ) -> int:
        """Queue a tolerant closeness test; returns a request id.

        The result's ``ids`` are ALL candidates labeled close (within
        ``eps`` of the target in the server's metric), nearest first —
        w.p. >= 1 - delta no candidate beyond ``eps + gap`` is among
        them and none within ``eps`` is missing; labels inside the gap
        are unconstrained (the promise region). Shares slots, samples,
        and the counts matrix with top-k queries.
        """
        target = np.asarray(target, np.float64).ravel()
        if target.shape != (self.spec.v_x,):
            raise ValueError(f"target must have shape ({self.spec.v_x},), got {target.shape}")
        if not gap > 0.0:
            raise ValueError(f"closeness needs gap > 0, got gap={gap}")
        if not eps >= 0.0:
            raise ValueError(f"closeness needs eps >= 0, got eps={eps}")
        return self._enqueue(
            target, k=1, eps=eps, delta=delta, qtype="closeness", gap=gap,
            stop=stop,
        )

    def _enqueue(
        self, target, *, k, eps, delta, qtype: str = "topk", gap: float = 0.0,
        stop: Optional[StopPolicy] = None,
    ) -> int:
        rid = self._next_rid
        self._next_rid += 1
        self.pending.append(
            MatchQuery(
                rid=rid,
                target=target,
                k=k,
                eps=eps,
                delta=delta,
                submit_time=time.perf_counter(),
                qtype=qtype,
                gap=gap,
                stop=stop,
            )
        )
        if self.telemetry is not None:
            self._c_submitted.inc(1)
            self.telemetry.tracer.emit(
                "query_enqueue", rid=rid, k=k, eps=eps, delta=delta,
                qtype=qtype, gap=gap, queued=len(self.pending),
            )
        return rid

    def _admit_free(self, _sched: Optional[SharedCountsScheduler] = None) -> None:
        """Fill free slots from the queue (the scheduler's on_round hook)."""
        while self.pending and self.scheduler.free_slots:
            q = self.pending.popleft()
            qid = self.scheduler.admit(
                q.target, k=q.k, eps=q.eps, delta=q.delta,
                qtype=q.qtype, gap=q.gap, stop=q.stop,
            )
            self._rid_of_qid[qid] = q.rid
            self._qid_of_rid[q.rid] = qid
            self._submit_time[q.rid] = q.submit_time
        self._collect()

    def _collect(self) -> None:
        """Convert freshly retired scheduler outcomes into MatchResults."""
        with span("collect"):
            for qid, out in list(self.scheduler.outcomes.items()):
                rid = self._rid_of_qid.pop(qid, None)
                if rid is None:
                    continue  # already collected
                self._qid_of_rid.pop(rid, None)
                del self.scheduler.outcomes[qid]
                res = self.results[rid] = self._to_result(rid, out)
                if out.anytime is not None:
                    out.anytime.result = res
                    self._anytime[rid] = out.anytime
                self._retired_since_save += 1
                if self.telemetry is not None:
                    # The rid↔qid join point: query_enqueue events carry the
                    # request id, the scheduler's admit/retire events the
                    # slot-assigned qid — this event links the two.
                    self.telemetry.tracer.emit(
                        "query_done", rid=rid, qid=qid, exact=res.exact,
                        tuples=res.tuples_read, wall_s=res.wall_time_s,
                    )
            self._maybe_autosave()

    def _to_result(self, rid: int, out: QueryOutcome) -> MatchResult:
        wall = time.perf_counter() - self._submit_time.pop(rid)
        return MatchResult(
            ids=out.ids,
            state=out.state,
            rounds=out.rounds,
            blocks_read=out.blocks_read,
            blocks_considered=out.blocks_considered,
            tuples_read=out.tuples_read,
            wall_time_s=wall,
            exact=out.exact,
            passes=out.passes,
            degraded=out.degraded,
            eps_effective=out.eps_effective,
            qtype=out.qtype,
            stopped=out.stopped,
            stop_reason=out.stop_reason,
        )

    # -- warm-start persistence --------------------------------------------

    def _maybe_autosave(self) -> None:
        """Autosave cadence check — runs at poll/retirement boundaries
        (from `_collect`), never inside the window loop."""
        if self._manager is None:
            return
        if self.autosave_every and self._retired_since_save >= self.autosave_every:
            self.save_cache()
            return
        if self.autosave_rounds:
            # Host mirror of the device round counter: fresh as of the
            # last poll, which is exactly the cadence autosave rides.
            if self.scheduler.rounds - self._rounds_at_save >= self.autosave_rounds:
                self.save_cache()

    def save_cache(self) -> pathlib.Path:
        """Crash-atomically persist the warm cache; returns the step dir.

        The checkpoint step is the device round counter, so snapshot
        steps are monotone across restarts (the restored cursor resumes
        the count) and a newer snapshot always supersedes an older one.
        A save with no new rounds since the last snapshot bumps past the
        newest existing step instead of re-writing it: overwriting the
        step that LATEST points at would reopen the crash window the
        atomic-rename protocol exists to close.
        """
        if self._manager is None:
            raise RuntimeError("MatchServer was constructed without checkpoint_dir")
        snap = self.scheduler.export_cache()
        step = int(jax.device_get(snap.rounds))
        newest = self._manager.latest_step()
        if newest is not None and step <= newest:
            step = newest + 1
        path = self._manager.save(snap, step)
        self._retired_since_save = 0
        self._rounds_at_save = step
        return path

    def restore_cache(self, step: Optional[int] = None) -> None:
        """Adopt the newest complete snapshot (or ``step``) from
        ``checkpoint_dir``. Stale snapshots — different dataset layout
        or `MultiQuerySpec` — are rejected with ValueError via the
        config hash; a missing checkpoint raises FileNotFoundError.
        With ``mesh=`` the candidate-sharded leaves are re-placed onto
        THIS server's mesh shape, whatever shape wrote the snapshot
        (elastic restart)."""
        if self._manager is None:
            raise RuntimeError("MatchServer was constructed without checkpoint_dir")
        like = self.scheduler.export_cache()  # fresh-state shapes/dtypes
        if self._mesh is not None:
            from repro.core.distributed import cache_pspecs

            snap = self._manager.restore_resharded(
                like, self._mesh, cache_pspecs(model_axis=self._model_axis), step=step
            )
        else:
            snap = self._manager.restore(like, step=step)
        self.scheduler.import_cache(snap)
        self._retired_since_save = 0
        self._rounds_at_save = self.scheduler.rounds
        self._pass_order = None  # step()'s cursor must rebuild from the restored mask

    @classmethod
    def restore(
        cls, dataset, *, checkpoint_dir: str, step: Optional[int] = None, **kwargs
    ) -> "MatchServer":
        """Warm construction: build a server over ``dataset`` and adopt
        the newest complete snapshot in ``checkpoint_dir``. Serving
        parameters (lookahead, poll_every, ...) come from ``kwargs``
        exactly as in `__init__`; the snapshot only has to match the
        dataset layout and the spec-shaping arguments
        (max_queries/criterion/k_cap), which the config hash enforces."""
        server = cls(dataset, checkpoint_dir=checkpoint_dir, **kwargs)
        server.restore_cache(step=step)
        return server

    # -- serving loop ------------------------------------------------------

    def step(self) -> None:
        """Admit + one window + retire: the unit of incremental serving.

        Keeps the same cyclic pass structure as `pump`: a pass visits
        every currently-unread block window by window; when a whole
        pass reads nothing for the remaining live queries (or no
        unread block is left), they are completed exactly instead of
        re-marking the same window forever.
        """
        self._admit_free()
        sched = self.scheduler
        if not sched.tickets:
            return
        if self._pass_order is None or self._pass_pos >= len(self._pass_order):
            with span("pass"):
                eligible = ~sched.read_mask[sched.order] & ~sched.quarantined[sched.order]
                unread = sched.order[eligible]
            # A zero-read pass only proves sampling is exhausted for the
            # queries that were live during it — a query admitted in its
            # final windows gets a fresh pass before the exact fallback.
            fresh = any(
                t.admit_rounds >= self._pass_start_rounds
                for t in sched.tickets.values()
            )
            stalled = self._pass_order is not None and self._pass_read == 0 and not fresh
            if unread.size == 0 or stalled:
                # Counts complete (or sampling can no longer help) —
                # finish exactly; every live answer becomes exact.
                sched.complete_remaining()
                du = sched._delta_upper  # fresh: complete_remaining polls
                for slot in list(sched.tickets):
                    fired = bool(du[slot] < sched.tickets[slot].delta)
                    sched.retire(slot, exact=True, terminated=fired)
                self._pass_order = None
                self._collect()
                return
            self._pass_order = unread
            self._pass_pos = 0
            self._pass_read = 0
            self._pass_start_rounds = sched.rounds
            sched.passes += 1
        win = self._pass_order[self._pass_pos : self._pass_pos + sched.window]
        self._pass_pos += len(win)
        # Guard against blocks read (or quarantined) since this pass was
        # snapshotted (e.g. a run_until_idle interleaved between steps).
        win = win[~sched.read_mask[win] & ~sched.quarantined[win]]
        if win.size:
            self._pass_read += sched.run_window(win)
            sched._poll_terminated()
        self._collect()

    def run_until_idle(self, *, max_rounds: int = 1_000_000) -> Dict[int, MatchResult]:
        """Drain the queue: serve until every submitted query has a result."""
        self._pass_order = None  # invalidate step()'s cursor
        while self.pending or self.scheduler.tickets:
            self._admit_free()
            if not self.scheduler.tickets:
                break  # nothing admissible (no pending either, per loop cond)
            self.scheduler.pump(
                max_rounds=max_rounds,
                max_passes=self.max_passes,
                on_round=self._admit_free,
            )
            if self.scheduler.budget_exhausted:
                # A query admitted in the budget's final round may already
                # satisfy its bound from the warm cache — poll before
                # stamping anything best-effort.
                self.scheduler._poll_terminated()
                for slot in list(self.scheduler.tickets):
                    self.scheduler.retire(slot, exact=False, terminated=False)
            self._collect()
        return dict(self.results)

    # -- anytime API -------------------------------------------------------

    def poll_result(self, rid: int) -> AnytimeAnswer:
        """The current progressive answer for ``rid`` — valid at any
        poll boundary, host-only (never dispatches device work).

        status="live": the best set so far with its Theorem-1-style
        statement, assembled by `SharedCountsScheduler.peek` from the
        last poll's mirrors. status="queued": a vacuous statement
        (delta_upper=1, empty set) — the query is waiting for a slot.
        status="done": the exact final statement of the retirement
        poll, with ``.result`` holding the blocking `MatchResult`.
        Unknown (or shed) request ids raise KeyError.
        """
        self._collect()  # fold already-retired outcomes; host-only
        if rid in self._anytime:
            return self._anytime[rid]
        if rid in self.results:
            # Retired through a path that predates anytime bookkeeping
            # (e.g. results dict populated by a restore) — degrade to a
            # minimal done statement rather than failing the poll.
            return answer_from_result(self.results[rid], metric=self.spec.metric)
        qid = self._qid_of_rid.get(rid)
        if qid is not None:
            sched = self.scheduler
            for slot, t in sched.tickets.items():
                if t.qid == qid:
                    return sched.peek(slot)
        for q in self.pending:
            if q.rid == rid:
                return AnytimeAnswer(
                    qid=-1, qtype=q.qtype, status="queued",
                    ids=np.zeros(0, np.int64), tau=np.zeros(0, np.float32),
                    margin=np.zeros(0, np.float32), split=float("nan"),
                    n_min=0.0, tau_min=float("nan"), eps_n=float("inf"),
                    delta_upper=1.0, confidence=0.0,
                    round=self.scheduler.rounds,
                    tuples=self.scheduler.tuples_read, tuples_live=0,
                    eps=q.eps, delta=q.delta, metric=self.spec.metric,
                )
        raise KeyError(f"unknown request id {rid}")

    def iter_results(self, rid: int, *, max_steps: int = 100_000):
        """Stream progressively refining answers for ``rid``.

        Drives the incremental serving unit `step()` between polls (so
        OTHER queued/live queries advance too) and yields an
        `AnytimeAnswer` each time the statement changes — tighter
        delta_upper, a new round, or a different best set — ending with
        the ``status="done"`` final answer, which for a fault-free
        converged query is bit-identical to the blocking result.
        ``max_steps`` bounds the drive (the generator just stops
        yielding if it is exhausted; the query keeps its slot).
        """
        last = None
        for _ in range(max_steps):
            ans = self.poll_result(rid)
            key = (ans.status, ans.round, ans.delta_upper, ans.ids.tobytes())
            if key != last:
                last = key
                yield ans
            if ans.status == "done":
                return
            self.step()
        ans = self.poll_result(rid)
        if ans.status == "done":
            yield ans

    # -- observability -----------------------------------------------------

    @property
    def metrics(self) -> Dict[str, object]:
        sched = self.scheduler
        done = len(self.results)
        return {
            "queries_done": done,
            # queued (waiting for a slot) vs live (admitted, burning I/O)
            # are different saturation signals: a deep queue with full
            # slots means add capacity; empty queue with live queries is
            # just work in flight. queries_pending stays as their sum
            # for dashboard compatibility.
            "queries_queued": len(self.pending),
            "queries_live": sched.num_live,
            "queries_pending": len(self.pending) + sched.num_live,
            "total_blocks_read": sched.blocks_read,
            "total_tuples_read": sched.tuples_read,
            "total_rounds": sched.rounds,
            "fraction_read": float(sched.read_mask.mean()) if sched.read_mask.size else 0.0,
            # 0.0, not nan, before the first completion: nan poisons any
            # dashboard aggregation and JSON round-trips it as a string.
            "tuples_per_query": float(sched.tuples_read / done) if done else 0.0,
            # Health surface (failure-modes contract, module docstring):
            # "" / 0 / False across the board on a healthy server.
            "last_error": self.last_error,
            "queries_shed": self.queries_shed,
            "blocks_quarantined": sched.blocks_quarantined,
            "degraded": sched.blocks_quarantined > 0,
            "eps_inflation": float(sched.eps_inflation),
        }

    def export_trace(self, path) -> int:
        """Dump the lifecycle/round trace as JSONL; returns event count."""
        if self.telemetry is None:
            raise RuntimeError("MatchServer was constructed without telemetry")
        self.scheduler.flush_telemetry()
        return self.telemetry.tracer.export_jsonl(path)

    def prometheus_metrics(self) -> str:
        """The registry in Prometheus text exposition format."""
        if self.telemetry is None:
            raise RuntimeError("MatchServer was constructed without telemetry")
        self.scheduler.flush_telemetry()
        return self.telemetry.registry.to_prometheus()
