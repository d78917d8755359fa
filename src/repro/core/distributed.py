"""Multi-pod distributed HistSim — the unified MULTI-QUERY round.

One round over `repro.core.multiquery.MultiQueryState` on a
("pod", "data", "model") mesh; the single-query case is just
``max_queries=1`` (the parallel single-query `ShardedHistSimState` this
module used to carry is gone — one loop, one state, every width):

  * corpus blocks   — range-sharded over ("pod", "data"): each worker
                      owns a contiguous range of the shuffled layout
                      (`repro.io.ShardedSource`, locality, Challenge 1)
                      and ingests only its own blocks.
  * counts matrix   — candidate-sharded over "model": each model shard
                      owns V_Z / |model| rows of the SHARED counts —
                      P("model", None) — and of n — P("model").
  * per round       — each (pod, data) shard histograms its local
                      samples *restricted to the candidate rows of its
                      model shard* (one-hot matmul, so restriction is an
                      index shift, not a gather; the kernel emits the
                      row-sum delta from the same pass), then a single
                      psum over ("pod", "data") merges the partial
                      (counts, rows) pair: the paper's r_partial
                      spinlock handoff becomes one fused all-reduce of
                      a (V_Z/m, V_X) f32 tile.
  * statistics      — per-query tau rows computed locally per model
                      shard with ONE Q-batched `ops.distance_multi`
                      call (the spec's static metric) (the shard's counts rows are streamed once
                      for all query slots; unoccupied slots masked),
                      then one tiled all-gather of (Q, V_Z) + (V_Z,)
                      floats and the same vmapped per-query deviation
                      assignment the single-device scheduler uses
                      (`multiquery.apply_stats` — the two paths share
                      the code, so they cannot drift). The per-query
                      active words and their union (V_Z bits packed)
                      return to every shard — the only "control plane"
                      traffic.

Communication per round: one psum of the (counts, row-sum) delta pair
+ one all-gather of (Q+1) x V_Z f32 — independent of the number of
samples ingested AND of the number of query slots (the batched tau
reads each shard's counts rows once, not Q times).
Sample bytes never cross the network; this is what makes the engine
scale to 1000+ nodes. `SharedCountsScheduler(mesh=...)` serves one
global window stream through the same ingest and statistics pieces
(`make_stream_rounds`); this round is the data-parallel ingest path.
Every round here is an explicit shard_map program: a Pallas TPU kernel
cannot be partitioned automatically, so each kernel call sits in a
region that is manual over the whole mesh.

The PUMP round (`make_pump_round`) is the self-feeding variant of the
same collective structure, built for `repro.core.pump.DistributedPump`:
each data-parallel worker brings its OWN window of block data (gathered
shard-locally from its `ShardedSource`), and the round additionally
runs the AnyActive marking against the replicated union active words
and advances a `SampleCursor` whose ``read_mask`` is sharded over the
data axes (`cursor_pspecs`) so each worker owns exactly its contiguous
global-id range. Per-round cross-worker traffic stays the single psum
of the (counts, rows, counter-increment) pytree + the tiny stats
all-gather — window bytes never leave the worker that read them.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.multiquery import (
    CacheSnapshot,
    MultiQuerySpec,
    MultiQueryState,
    RoundFns,
    SampleCursor,
    _advance_cursor,
    apply_stats,
)
from repro.core.policies import mark_window
from repro.io import WindowData
from repro.kernels import ops

__all__ = [
    "cache_pspecs",
    "cursor_pspecs",
    "make_distributed_round",
    "make_pump_ingest_round",
    "make_pump_round",
    "make_stats_step",
    "make_stream_rounds",
    "multi_state_pspecs",
    "place_cache",
    "window_pspecs",
]


def multi_state_pspecs(model_axis: str = "model") -> MultiQueryState:
    """PartitionSpecs for MultiQueryState: shared counts candidate-sharded
    over the model axis, all per-query statistics replicated."""
    return MultiQueryState(
        counts=P(model_axis, None),
        n=P(model_axis),
        q_hat=P(),
        k=P(),
        eps=P(),
        delta=P(),
        gap=P(),
        qtype=P(),
        tau=P(),
        eps_i=P(),
        log_delta_i=P(),
        delta_upper=P(),
        active=P(),
        active_words=P(),
        union_words=P(),
        in_top_k=P(),
        pruned=P(),
        occupied=P(),
        round_idx=P(),
    )


def cache_pspecs(model_axis: str = "model") -> CacheSnapshot:
    """PartitionSpecs for the warm-start `CacheSnapshot`: the shared
    counts/n leaves carry the SAME candidate sharding as the live
    `MultiQueryState` (derived from `multi_state_pspecs`, so the two
    cannot drift); the sampling cursor and host bookkeeping replicate.

    This is the elastic-restart contract: a snapshot host-gathered from
    one mesh shape is re-placed onto another by
    ``CheckpointManager.restore_resharded(like, mesh, cache_pspecs())``
    — e.g. a cache accumulated on 1 device restored candidate-sharded
    onto 8, or an 8-way cache restored onto a 4-device mesh."""
    ms = multi_state_pspecs(model_axis=model_axis)
    return CacheSnapshot(
        counts=ms.counts,
        n=ms.n,
        read_mask=P(),
        blocks_read=P(),
        blocks_considered=P(),
        tuples_read=P(),
        rounds=P(),
        passes=P(),
        start=P(),
    )


def cursor_pspecs(data_axes=("data",)) -> SampleCursor:
    """PartitionSpecs for the pump's device `SampleCursor`: the
    without-replacement ``read_mask`` is sharded over the data axes —
    worker w owns exactly the mask slice for its contiguous global-id
    block range [w*per, (w+1)*per) (`ShardedSource` ordering, padded to
    per * num_workers) — while the monotone counters stay replicated
    (every worker holds the mesh-wide totals; the round psums the
    per-worker increments)."""
    return SampleCursor(
        read_mask=P(tuple(data_axes)),
        blocks_read=P(),
        blocks_considered=P(),
        tuples_read=P(),
        rounds=P(),
    )


def window_pspecs(data_axes=("data",)) -> WindowData:
    """PartitionSpecs for a pump round's `WindowData`: dim 0 (the
    lookahead-window axis) carries one window per data-parallel worker,
    so each worker's shard IS the window its own `ShardedSource`
    gathered; block contents replicate over the model axis."""
    d = tuple(data_axes)
    return WindowData(
        indices=P(d),
        z=P(d, None),
        x=P(d, None),
        bitmap=P(d, None),
        valid=P(d),
    )


def place_cache(snap: CacheSnapshot, mesh, model_axis: str = "model") -> CacheSnapshot:
    """Host-gather a (possibly sharded) snapshot and re-place it on
    ``mesh`` per `cache_pspecs` — the in-memory reshard twin of the
    checkpoint round-trip, for handing a live scheduler's cache to a
    differently-shaped mesh without touching disk."""
    from jax.sharding import NamedSharding

    host = jax.device_get(snap)  # gather: full leaves on host
    shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), cache_pspecs(model_axis=model_axis)
    )
    return jax.tree.map(jax.device_put, host, shardings)


def _manual(mesh, fn, in_specs, out_specs):
    """Jit ``fn`` as one shard_map program, manual over the whole mesh
    (replication checking off: the rounds' replicated outputs come out
    of collectives)."""
    return jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    ))


def make_distributed_round(
    mesh,
    spec: MultiQuerySpec,
    *,
    data_axes=("data",),
    model_axis: str = "model",
    histogram_impl: str = "auto",
    onehot_dtype=jnp.float32,
):
    """Build the jitted shard_map multi-query round for a given mesh.

    The returned function has signature (state, z_idx, x_idx) -> state,
    where state is a `MultiQueryState` placed per `multi_state_pspecs`
    and z_idx/x_idx are (N,) int32 sharded over ``data_axes`` — the
    samples each worker read from its own block range this round
    (padding = -1). All-reduce structure is as documented above; the
    statistics tail is `multiquery.apply_stats`, identical to the
    single-device scheduler's.
    """
    vz_shard = _check_vz(spec, mesh, model_axis)
    sample_axes = tuple(data_axes)

    def round_fn(state: MultiQueryState, z_idx: jax.Array, x_idx: jax.Array):
        state = _shard_ingest(
            state, z_idx, x_idx, spec=spec, vz_shard=vz_shard,
            sample_axes=sample_axes, model_axis=model_axis,
            histogram_impl=histogram_impl, onehot_dtype=onehot_dtype,
        )
        return _shard_stats(state, spec=spec, model_axis=model_axis)

    specs = multi_state_pspecs(model_axis=model_axis)
    sample_spec = P(sample_axes)
    return _manual(mesh, round_fn, (specs, sample_spec, sample_spec), specs)


def _shard_ingest(
    state: MultiQueryState,
    z_idx: jax.Array,
    x_idx: jax.Array,
    *,
    spec: MultiQuerySpec,
    vz_shard: int,
    sample_axes,
    model_axis: str,
    histogram_impl: str,
    onehot_dtype,
) -> MultiQueryState:
    """Ingest (inside shard_map): local histogram restricted to this
    model shard's candidate rows — an index shift, not a gather — with
    the row-sum delta emitted from the same kernel pass, then ONE fused
    all-reduce of the (counts, row-sum) delta pair over the data axes
    (a single psum call, XLA fuses the pytree)."""
    shard_id = jax.lax.axis_index(model_axis)
    z_local = z_idx - shard_id * vz_shard
    z_local = jnp.where((z_local >= 0) & (z_local < vz_shard), z_local, -1)
    h, rows = ops.histogram_with_rowsums(
        z_local, x_idx, v_z=vz_shard, v_x=spec.v_x,
        impl=histogram_impl, onehot_dtype=onehot_dtype,
    )
    h, rows = jax.lax.psum((h, rows), sample_axes)
    return state._replace(counts=state.counts + h, n=state.n + rows)


def _shard_stats(
    state: MultiQueryState, *, spec: MultiQuerySpec, model_axis: str
) -> MultiQueryState:
    """Statistics tail (inside shard_map): row-local Q-batched tau (ONE
    kernel pass over this shard's counts rows scores every slot;
    unoccupied slots masked to the init value), tiny all-gather, then
    the shared vmapped per-query assignment."""
    tau_shard = ops.distance_multi(state.counts, state.q_hat, metric=spec.metric)  # (Q, vz_shard)
    tau_shard = jnp.where(state.occupied[:, None], tau_shard, 1.0)
    tau = jax.lax.all_gather(tau_shard, model_axis, axis=1, tiled=True)
    n_full = jax.lax.all_gather(state.n, model_axis, axis=0, tiled=True)
    return apply_stats(state, tau, n_full, spec=spec)


def _worker_lo(mesh, data_axes, blocks_per_worker: int) -> jax.Array:
    """This worker's first owned global block id (inside shard_map).

    The linear worker index folds the data axes in mesh-row-major order
    — the same order `P(tuple(data_axes))` lays shards out in — so the
    read_mask shard at linear position w is exactly the id range of
    `ShardedSource(dataset, num_workers, w)`."""
    wid = jax.lax.axis_index(data_axes[0])
    for ax in data_axes[1:]:
        wid = wid * mesh.shape[ax] + jax.lax.axis_index(ax)
    return wid * blocks_per_worker


def _advance_shard_cursor(
    cursor: SampleCursor,
    wd: WindowData,
    marks: jax.Array,
    local_idx: jax.Array,
    sample_axes,
) -> SampleCursor:
    """Per-worker twin of `multiquery._advance_cursor`: the scatter hits
    only this worker's read_mask shard (local ids; window padding
    repeats an owned id with a zero contribution), while the counter
    increments are psum'd so every worker carries the mesh-wide totals
    — one fused collective for the whole increment pytree."""
    read_mask = (
        cursor.read_mask.astype(jnp.int32).at[local_idx].add(marks.astype(jnp.int32)) > 0
    )
    inc_read, inc_considered, inc_tuples = jax.lax.psum(
        (
            jnp.sum(marks.astype(jnp.int32)),
            jnp.sum(wd.valid.astype(jnp.int32)),
            jnp.sum(jnp.where(marks, jnp.sum((wd.z >= 0).astype(jnp.int32), axis=1), 0)),
        ),
        sample_axes,
    )
    return SampleCursor(
        read_mask=read_mask,
        blocks_read=cursor.blocks_read + inc_read,
        blocks_considered=cursor.blocks_considered + inc_considered,
        tuples_read=cursor.tuples_read + inc_tuples,
        rounds=cursor.rounds + 1,
    )


def _check_vz(spec: MultiQuerySpec, mesh, model_axis: str) -> int:
    model_size = mesh.shape[model_axis]
    if spec.v_z % model_size != 0:
        raise ValueError(
            f"V_Z={spec.v_z} must divide by model axis size {model_size} "
            "(pad candidates to a multiple; padded rows are never sampled)"
        )
    return spec.v_z // model_size


def make_pump_round(
    mesh,
    spec: MultiQuerySpec,
    *,
    blocks_per_worker: int,
    data_axes=("data",),
    model_axis: str = "model",
    policy: str = "anyactive",
    histogram_impl: str = "auto",
    onehot_dtype=jnp.float32,
):
    """Build the jitted shard_map PUMP round: the fused sampling round
    (`multiquery.fused_round` semantics — mark + gather-mask + ingest +
    stats + read bookkeeping) where each data-parallel worker feeds
    itself from its own window.

    Signature of the returned function: (state, cursor, wd) ->
    (state, cursor), with state placed per `multi_state_pspecs`, cursor
    per `cursor_pspecs` (read_mask length blocks_per_worker *
    num_workers) and wd a `WindowData` whose dim 0 stacks one
    per-worker window, placed per `window_pspecs`.

    Semantics are pinned to `fused_round` on the union of the worker
    windows: marking uses the replicated union active words and each
    worker's own read_mask shard, and an all-empty round (no block
    marked mesh-wide) leaves the statistics — including ``round_idx`` —
    untouched. The empty-round guard is a branchless select rather than
    fused_round's lax.cond (collectives inside a cond branch do not
    lower reliably under shard_map); selected leaves are bit-identical
    either way.
    """
    vz_shard = _check_vz(spec, mesh, model_axis)
    sample_axes = tuple(data_axes)

    def round_fn(state: MultiQueryState, cursor: SampleCursor, wd: WindowData):
        local_idx = wd.indices - _worker_lo(mesh, sample_axes, blocks_per_worker)
        marks = mark_window(wd.bitmap, state.union_words, policy=policy)
        marks = marks & wd.valid & ~cursor.read_mask[local_idx]
        zw = jnp.where(marks[:, None], wd.z, jnp.int32(-1)).reshape(-1)
        xw = jnp.where(marks[:, None], wd.x, jnp.int32(-1)).reshape(-1)
        new_state = _shard_ingest(
            state, zw, xw, spec=spec, vz_shard=vz_shard,
            sample_axes=sample_axes, model_axis=model_axis,
            histogram_impl=histogram_impl, onehot_dtype=onehot_dtype,
        )
        new_state = _shard_stats(new_state, spec=spec, model_axis=model_axis)
        n_marked = jax.lax.psum(jnp.sum(marks.astype(jnp.int32)), sample_axes)
        state = jax.tree.map(
            lambda new, old: jnp.where(n_marked > 0, new, old), new_state, state
        )
        return state, _advance_shard_cursor(cursor, wd, marks, local_idx, sample_axes)

    specs = multi_state_pspecs(model_axis=model_axis)
    cspecs = cursor_pspecs(data_axes=sample_axes)
    wspecs = window_pspecs(data_axes=sample_axes)
    return _manual(mesh, round_fn, (specs, cspecs, wspecs), (specs, cspecs))


def make_pump_ingest_round(
    mesh,
    spec: MultiQuerySpec,
    *,
    blocks_per_worker: int,
    data_axes=("data",),
    model_axis: str = "model",
    histogram_impl: str = "auto",
    onehot_dtype=jnp.float32,
):
    """Build the jitted shard_map exact-completion round — the pump twin
    of `multiquery.ingest_round`: every unread block of each worker's
    window goes into the shared counts, no marking, no stats (the
    caller runs one stats step after the last chunk). Same signature
    and placement contract as `make_pump_round`."""
    vz_shard = _check_vz(spec, mesh, model_axis)
    sample_axes = tuple(data_axes)

    def round_fn(state: MultiQueryState, cursor: SampleCursor, wd: WindowData):
        local_idx = wd.indices - _worker_lo(mesh, sample_axes, blocks_per_worker)
        marks = wd.valid & ~cursor.read_mask[local_idx]
        zw = jnp.where(marks[:, None], wd.z, jnp.int32(-1)).reshape(-1)
        xw = jnp.where(marks[:, None], wd.x, jnp.int32(-1)).reshape(-1)
        state = _shard_ingest(
            state, zw, xw, spec=spec, vz_shard=vz_shard,
            sample_axes=sample_axes, model_axis=model_axis,
            histogram_impl=histogram_impl, onehot_dtype=onehot_dtype,
        )
        return state, _advance_shard_cursor(cursor, wd, marks, local_idx, sample_axes)

    specs = multi_state_pspecs(model_axis=model_axis)
    cspecs = cursor_pspecs(data_axes=sample_axes)
    wspecs = window_pspecs(data_axes=sample_axes)
    return _manual(mesh, round_fn, (specs, cspecs, wspecs), (specs, cspecs))


def make_stats_step(mesh, spec: MultiQuerySpec, *, model_axis: str = "model"):
    """Build the jitted shard_map statistics step over a candidate-sharded
    state — the mesh twin of `multiquery.stats_step` (state -> state)."""
    _check_vz(spec, mesh, model_axis)
    specs = multi_state_pspecs(model_axis=model_axis)
    return _manual(
        mesh, partial(_shard_stats, spec=spec, model_axis=model_axis), (specs,), specs
    )


def make_stream_rounds(
    mesh,
    spec: MultiQuerySpec,
    *,
    model_axis: str = "model",
    policy: str = "anyactive",
) -> RoundFns:
    """Build the rounds `SharedCountsScheduler(mesh=...)` dispatches: ONE
    global window stream over a candidate-sharded counts matrix.

    The window, the sampling cursor and the per-query statistics are
    replicated; every device marks the whole window (the same marks
    everywhere), takes its 1/D share of the window's samples along the
    mesh's other (data) axes, and histograms that share restricted to
    its model shard's candidate rows; one psum over the data axes merges
    the shares. The statistics tail is the pump's (`_shard_stats`), and
    an all-empty round leaves the statistics untouched through the same
    branchless select as the pump round. Semantics are `fused_round` /
    `ingest_round` / `stats_step` on the sharded state, as explicit
    shard_map programs (see the module docstring for why). V_Z must
    divide by the model axis, as for every candidate-sharded round.
    """
    vz_shard = _check_vz(spec, mesh, model_axis)
    data_axes = tuple(a for a in mesh.axis_names if a != model_axis)
    num_data = math.prod(mesh.shape[a] for a in data_axes)

    def ingest(state: MultiQueryState, wd: WindowData, marks: jax.Array):
        zw = jnp.where(marks[:, None], wd.z, jnp.int32(-1)).reshape(-1)
        xw = jnp.where(marks[:, None], wd.x, jnp.int32(-1)).reshape(-1)
        if num_data > 1:  # this device's share of the window's samples
            per = -(-zw.size // num_data)
            lo = _worker_lo(mesh, data_axes, per)
            zw, xw = (
                jax.lax.dynamic_slice_in_dim(
                    jnp.pad(a, (0, per * num_data - a.size), constant_values=-1), lo, per
                )
                for a in (zw, xw)
            )
        return _shard_ingest(
            state, zw, xw, spec=spec, vz_shard=vz_shard, sample_axes=data_axes,
            model_axis=model_axis, histogram_impl="auto", onehot_dtype=jnp.float32,
        )

    def round_fn(state: MultiQueryState, cursor: SampleCursor, wd: WindowData):
        marks = mark_window(wd.bitmap, state.union_words, policy=policy)
        marks = marks & wd.valid & ~cursor.read_mask[wd.indices]
        new_state = _shard_stats(ingest(state, wd, marks), spec=spec, model_axis=model_axis)
        n_marked = jnp.sum(marks.astype(jnp.int32))
        state = jax.tree.map(
            lambda new, old: jnp.where(n_marked > 0, new, old), new_state, state
        )
        return state, _advance_cursor(cursor, wd, marks)

    def ingest_fn(state: MultiQueryState, cursor: SampleCursor, wd: WindowData):
        marks = wd.valid & ~cursor.read_mask[wd.indices]
        return ingest(state, wd, marks), _advance_cursor(cursor, wd, marks)

    specs = multi_state_pspecs(model_axis=model_axis)
    return RoundFns(
        round=_manual(mesh, round_fn, (specs, P(), P()), (specs, P())),
        ingest=_manual(mesh, ingest_fn, (specs, P(), P()), (specs, P())),
        stats=make_stats_step(mesh, spec, model_axis=model_axis),
    )
