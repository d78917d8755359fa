"""Data-parallel pump: per-worker `ShardedSource` streams driving the
explicit-collective distributed round.

The paper's 35x speedup rests on many asynchronous block-based samplers
feeding one statistics engine (FastMatch Sec 5). The single-stream
mesh path, `SharedCountsScheduler(mesh=...)`, shards the counts matrix,
but every window is still gathered by ONE host stream and handed to the
round as replicated data — ingest bandwidth does not grow with the
mesh. `DistributedPump` closes that
gap: it is a `SharedCountsScheduler` whose sampling side is
data-parallel end to end.

How the two mesh paths dispatch (see also
`repro.serve.fastmatch_server.MatchServer`):

  STREAM (``MatchServer(mesh=...)``)
      One global block stream; the explicit shard_map round
      `distributed.make_stream_rounds` over state placed per
      `distributed.multi_state_pspecs`, the window replicated and its
      samples split over the data axes; window bytes are gathered
      centrally.

  PUMP (``MatchServer(mesh=..., pump=True)``)
      One `ShardedSource` per data-parallel worker (optionally
      `PrefetchSource`-wrapped, so each worker's next window gather
      overlaps the current round). Each round, every worker takes the
      next lookahead window of ITS contiguous global-id block range
      from the shared cyclic visit order and the explicit shard_map
      round (`distributed.make_pump_round`) runs mark + masked ingest +
      Q-batched stats + cursor bookkeeping.

Collectives per pump round — auditable, independent of window bytes:

  * ONE psum over the data axes of the ((V_Z/m, V_X) counts delta,
    (V_Z/m,) row-sum delta, 3 counter increments) pytree — the only
    cross-WORKER traffic; sample bytes never leave the worker that
    read them.
  * ONE tiled all-gather over the model axis of the (Q, V_Z) tau +
    (V_Z,) row sums — the statistics "control plane", after which the
    per-query deviation assignment (`multiquery.apply_stats`) runs
    replicated, exactly as in the single-stream round.

Block marking uses the union-of-active-sets words carried replicated
in the per-query statistics, so AnyActive stays mesh-wide consistent;
each worker's slice of the `SampleCursor` read_mask covers exactly its
own id range (`distributed.cursor_pspecs`), which is what makes the
without-replacement guarantee per-worker local — no read_mask traffic.

Golden contract (tests/test_pump.py): driven with the same global
windows, a pump round is bit-identical to the single-stream
`fused_round` — counts, n, tau, bounds, read_mask and counters — for
any mesh shape, mid-stream admission and retirement included. The
host-side loop (pass structure, poll_every staleness, exact-completion
fallback, warm-start snapshots) is inherited from
`SharedCountsScheduler` unchanged; `export_cache`/`import_cache`
convert between the data-sharded padded read_mask and the global
`CacheSnapshot` layout, so snapshots are interchangeable across pump
widths and with the single-stream scheduler (elastic restart, e.g.
checkpoint under 8 workers, restore under 4 — `cache_pspecs` re-places
the candidate-sharded counts exactly as in the single-stream path).

`benchmarks/pump_throughput.py` measures the scaling claim: rounds
(and with them host polls) per pass drop ~Wx with W workers at equal
recall, and tuples ingested/sec scales with the workers' aggregate
I/O bandwidth.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro.core.distributed import (
    cursor_pspecs,
    make_pump_ingest_round,
    make_pump_round,
    make_stats_step,
    window_pspecs,
)
from repro.core.multiquery import (
    MultiQuerySpec,
    RoundFns,
    SampleCursor,
    SharedCountsScheduler,
)
from repro.data.layout import BlockedDataset
from repro.io import InMemorySource, PrefetchSource, ShardedSource, WindowData
from repro.obs.spans import spanned

__all__ = ["DistributedPump"]


class DistributedPump(SharedCountsScheduler):
    """Data-parallel `SharedCountsScheduler`: one shard-local window
    stream per mesh worker feeding the explicit-collective pump round.

    Owns the raw `BlockedDataset` (it must shard it — an opaque
    `BlockSource` cannot be split by block ownership) and builds one
    `ShardedSource` per worker over the contiguous global-id ranges of
    `BlockedDataset.shard`. All scheduler semantics — admission,
    retirement, poll_every staleness, pass structure, exact completion,
    warm-start snapshots — are inherited; only where window data comes
    from and how a round is dispatched differ. ``host_syncs`` /
    ``loop_syncs`` keep counting mesh-wide device↔host polls (one poll
    gathers every worker's counters in a single fused device_get), so
    the poll_every amortization stays observable per worker count.

    ``prefetch=True`` wraps each worker's stream in a `PrefetchSource`
    so all W next-window gathers overlap the current round.
    """

    def __init__(
        self,
        dataset: BlockedDataset,
        spec: MultiQuerySpec,
        *,
        mesh,
        data_axes=("data",),
        model_axis: str = "model",
        policy: str = "anyactive",
        window: int = 512,
        seed: int = 0,
        start_block: Optional[int] = None,
        poll_every: int = 1,
        prefetch: bool = False,
        histogram_impl: str = "auto",
        onehot_dtype=jnp.float32,
        telemetry=None,
    ):
        if not isinstance(dataset, BlockedDataset):
            raise TypeError(
                "DistributedPump shards the raw BlockedDataset per worker; "
                f"got {type(dataset)!r} (wrap sources only in single-stream mode)"
            )
        self.mesh = mesh
        self.data_axes = tuple(data_axes)
        self.model_axis = model_axis
        self._histogram_impl = histogram_impl
        self._onehot_dtype = onehot_dtype
        for ax in self.data_axes + (model_axis,):
            if ax not in mesh.shape:
                raise ValueError(f"mesh has no axis {ax!r}; axes are {dict(mesh.shape)}")
        self.num_workers = int(np.prod([mesh.shape[a] for a in self.data_axes]))
        nb = dataset.num_blocks
        # ShardedSource's ceil-division ranges; the sharded read_mask is
        # padded to the full worker grid (the tail ids are never in any
        # window, so they can never be marked).
        self._blocks_per_worker = -(-nb // self.num_workers)
        self._padded_num_blocks = self._blocks_per_worker * self.num_workers
        self.shards = [
            ShardedSource(dataset, self.num_workers, w, device_resident=False)
            for w in range(self.num_workers)
        ]
        if any(s.num_blocks == 0 for s in self.shards):
            raise ValueError(
                f"{self.num_workers} workers over {nb} blocks leaves a worker "
                "with no blocks; use fewer workers (or more blocks)"
            )
        self._stream_sources = [
            PrefetchSource(s, telemetry=telemetry) if prefetch else s
            for s in self.shards
        ]
        self._cursor_shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s), cursor_pspecs(data_axes=self.data_axes)
        )
        self._wd_shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s), window_pspecs(data_axes=self.data_axes)
        )
        # The scheduler's own source stays host-resident: it serves only
        # random access (config-hash probes, ad-hoc fetches) — the hot
        # path reads through the per-worker shards.
        super().__init__(
            InMemorySource(dataset, device_resident=False),
            spec,
            policy=policy,
            window=window,
            seed=seed,
            start_block=start_block,
            poll_every=poll_every,
            mesh=mesh,
            model_axis=model_axis,
            telemetry=telemetry,
        )

    def _make_rounds(self, mesh, model_axis: str) -> RoundFns:
        """The per-worker pump rounds and the mesh statistics step."""
        kw = dict(
            data_axes=self.data_axes, model_axis=model_axis,
            histogram_impl=self._histogram_impl, onehot_dtype=self._onehot_dtype,
        )
        return RoundFns(
            round=make_pump_round(
                mesh, self.spec, blocks_per_worker=self._blocks_per_worker,
                policy=self.policy, **kw,
            ),
            ingest=make_pump_ingest_round(
                mesh, self.spec, blocks_per_worker=self._blocks_per_worker, **kw
            ),
            stats=make_stats_step(mesh, self.spec, model_axis=model_axis),
        )

    # -- cursor placement / snapshot layout --------------------------------

    def _place_cursor(self, cursor: SampleCursor) -> SampleCursor:
        """Pad the global read_mask to the worker grid and shard it over
        the data axes; counters replicate (they hold mesh-wide totals)."""
        host = jax.device_get(cursor)
        mask = np.zeros(self._padded_num_blocks, bool)
        mask[: host.read_mask.shape[0]] = np.asarray(host.read_mask, bool)
        return jax.tree.map(
            jax.device_put, host._replace(read_mask=mask), self._cursor_shardings
        )

    def _global_read_mask(self) -> jax.Array:
        nb = self.source.num_blocks
        return jnp.asarray(np.asarray(jax.device_get(self.cursor.read_mask), bool)[:nb])

    def _sync(self) -> None:
        super()._sync()
        self.read_mask = self.read_mask[: self.source.num_blocks]

    def _quarantine_sources(self) -> tuple:
        """Drain quarantine from every per-worker stream source too —
        a `ResilientSource` under one worker's prefetch wrapper
        quarantines GLOBAL block ids (`ShardedSource` speaks global),
        so the base bookkeeping applies unchanged and the degraded
        bound covers faults on any worker's I/O path."""
        return (self.source, *self._stream_sources)

    # -- data-parallel window plumbing -------------------------------------

    def _plan_pass(self, pass_order: np.ndarray) -> tuple:
        """Split a global visit order into per-worker window lists.

        Worker w's list is the order restricted to its contiguous id
        range, chunked into lookahead windows; lists are aligned to one
        length with empty windows so round r zips worker windows
        one-to-one (a worker whose share ran out contributes an
        all-padding shard that marks nothing).
        """
        per = [
            pass_order[(pass_order >= s.lo) & (pass_order < s.hi)] for s in self.shards
        ]
        n_rounds = max(-(-p.size // self.window) for p in per)
        return (
            [
                [p[r * self.window : (r + 1) * self.window] for r in range(n_rounds)]
                for p in per
            ],
            n_rounds,
        )

    def _assemble(self, wds) -> WindowData:
        """Stack per-worker windows into the round's sharded WindowData:
        dim 0 concatenates the W windows, placed so each worker's shard
        is exactly the window its own source gathered (window_pspecs).

        The shard sources are host-resident, so their leaves are numpy
        and the device_put below is the window's ONLY host→device
        transfer (device_get is a passthrough on numpy; it only pays a
        gather if a custom source hands back device arrays)."""
        def cat(field):
            return np.concatenate(
                [np.asarray(jax.device_get(getattr(w, field))) for w in wds], axis=0
            )

        host = WindowData(
            indices=cat("indices"), z=cat("z"), x=cat("x"),
            bitmap=cat("bitmap"), valid=cat("valid"),
        )
        return jax.tree.map(jax.device_put, host, self._wd_shardings)

    def _open_pass_stream(self, pass_order: np.ndarray) -> tuple:
        win_lists, n_rounds = self._plan_pass(pass_order)

        def rounds():
            streams = [
                src.stream(wins, pad_to=self.window)
                for src, wins in zip(self._stream_sources, win_lists)
            ]
            try:
                # one fetch span per worker window: the per-worker I/O
                # skew the psum round then has to wait out
                for wds in zip(*(spanned(st, "fetch") for st in streams)):
                    yield self._assemble(wds)
            finally:
                for st in streams:
                    st.close()

        return rounds(), n_rounds

    def _fetch_window(self, win: np.ndarray) -> WindowData:
        """Ad-hoc global window (MatchServer.step / run_window): split
        by block ownership, fetch shard-locally, assemble. One pump
        round regardless of how the window straddles workers."""
        pieces = [s.owned(win) for s in self.shards]
        pad = max(self.window, max(p.size for p in pieces))
        return self._assemble(
            [s.fetch(p, pad_to=pad) for s, p in zip(self.shards, pieces)]
        )
