"""Shared-counts multi-query HistSim — the FastMatch serving core.

The key structural fact enabling a serving layer on top of HistSim: the
counts matrix ``r_i`` accumulated by `ingest` is *target-independent* —
only ``q_hat``, ``tau``, ``eps_i`` and ``delta_i`` depend on the query.
N concurrent queries over the same dataset can therefore share ONE
counts matrix and ONE I/O stream:

  shared   — counts (V_Z, V_X), n (V_Z,), the block read_mask / cursor
  per-query — q_hat, (k, eps, delta), query type (top-k | closeness)
              and its gap, tau, eps_i, log_delta_i, delta_upper,
              active set, matching set M (close set for closeness)

`ingest` runs once per window for everybody (reusing the one-hot-
contraction histogram kernel); `stats_step` is vmapped over the query
axis, so each query keeps its own Problem 1 parameters and its own
termination bound. The union active set — the bitwise OR of the
per-query packed ``active_words`` — feeds the AnyActive kernel, so the
I/O manager reads a block iff *any* live query still needs it.

Sample-complexity intuition (Diakonikolas et al., Canonne et al.: the
cost of testing closeness is driven by the number of samples, not the
number of hypotheses tested against them): every tuple read is charged
once but advances all N queries, so the per-query I/O cost shrinks
roughly as 1/N, and queries admitted late start from the accumulated
shared counts instead of from zero. Soundness of a late query using
the full accumulated ``n_i`` for its Theorem 1 bounds: WHICH blocks
were read does depend on the earlier queries' targets (AnyActive marks
via their active sets), but the layout pre-shuffle assigns tuples to
blocks independently of their x-values, so for each candidate any
block-granular read policy yields a uniform without-replacement sample
of that candidate's tuples — the same paper-Sec 4.2 property the
single-query engine already relies on when AnyActive is driven by its
OWN target. Hence a late query's ``n_i`` IS the shared ``n_i``, with
no discounting. (This rests on the shuffle; on a non-shuffled layout
neither the single- nor the multi-query bounds are valid.)

Query slots are padded to a fixed ``max_queries`` so every jitted
function sees stable shapes; empty slots are masked out of the active
union and report delta_upper = 0.

Device residency (paper Sec 4.2's asynchronous relaxation, taken to
its hardware conclusion): one jitted `fused_round` runs mark + masked
gather + ingest + vmapped stats AND the read bookkeeping — a
`SampleCursor` holding the without-replacement ``read_mask`` and the
blocks/tuples counters — entirely on device. The host loop in
`SharedCountsScheduler.pump` dispatches rounds back-to-back and only
polls ``delta_upper`` and the counters every ``poll_every`` windows
(`host_syncs` counts those polls). ``poll_every=1`` reproduces the
per-window host-stepped loop bit-for-bit; larger values trade bounded
retirement staleness (a query may read up to ``poll_every - 1`` extra
windows after its bound fires) for ~``poll_every``x fewer device↔host
round-trips. Block data arrives through the pluggable `repro.io`
`BlockSource` layer, so gathering the next window can overlap the
current round (`PrefetchSource`).

`SharedCountsScheduler` below is the window-marking/ingest loop that
used to live inline in `engine.run_engine`; the single-query engine is
now the ``max_queries=1`` specialization of this loop, and
`repro.serve.fastmatch_server.MatchServer` is the many-query frontend
with admission/retirement.

Pluggable metrics and query types: the spec's static ``metric`` ("l1" |
"chi2" | "hellinger") selects WHICH registry distance the shared tau
pass computes — threaded statically through `stats_step`, so one
scheduler serves one metric. Query TYPE is per-slot and dynamic: every slot carries a
``qtype`` (0 = top-k, 1 = closeness) and a ``gap``, and `apply_stats`
evaluates both retirement rules and selects per slot — admitting a
closeness query next to live top-k queries therefore triggers NO
recompilation and both share the same counts matrix mid-stream. The
l1 top-k default compiles to the exact pre-metric-layer program (the
closeness branch is selected away; selects are value-exact).
"""

from __future__ import annotations

import dataclasses
import hashlib
import math
import time
from functools import partial
from typing import Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import config_hash as _checkpoint_config_hash
from repro.core import deviations as dev
from repro.core import histsim
from repro.core.bitmap import pack_active_mask, words_for
from repro.core.histsim import HistSimState
from repro.core.policies import mark_window
from repro.io import BlockSource, WindowData, as_block_source
from repro.io.faults import WindowQuarantined, find_resilient
from repro.kernels import ops
from repro.kernels.histogram import ingest_lane_fill
from repro.obs.spans import span, spanned
from repro.obs.telemetry import Telemetry

__all__ = [
    "AnytimeAnswer",
    "CacheSnapshot",
    "MultiQuerySpec",
    "MultiQueryState",
    "QTYPE_TOPK",
    "QTYPE_CLOSENESS",
    "QueryOutcome",
    "RoundFns",
    "SampleCursor",
    "SharedCountsScheduler",
    "StopPolicy",
    "apply_stats",
    "cache_config_hash",
    "fused_round",
    "ingest_round",
    "init_cursor",
    "init_multi_state",
    "admit_slot",
    "clear_slot",
    "ingest",
    "stats_step",
    "run_round",
    "slot_state",
]


# Per-slot query types (MultiQueryState.qtype values). Dynamic — a
# traced i32 per slot, NOT a static spec field — so mixed top-k +
# closeness workloads share one compiled program.
QTYPE_TOPK = 0
QTYPE_CLOSENESS = 1


@dataclasses.dataclass(frozen=True)
class StopPolicy:
    """SLA-driven early stopping for one query (or a whole scheduler via
    ``MultiQuerySpec.default_stop``). A stopped query retires with its
    honest anytime answer — ``exact=False``, ``terminated=False``, the
    achieved ``delta_upper`` attached — bit-identical to what
    `SharedCountsScheduler.peek` would have reported at that poll.

    Fields left None never fire; the statistical retirement rule
    (delta_upper < delta) always takes precedence, so a query that
    converges before its SLA returns the normal terminated answer.

      wall_ms    — stop once the query has been live this many ms
                   (evaluated at poll boundaries, so the overshoot is
                   bounded by one poll interval, like PR-8 deadlines).
      confidence — stop once 1 - delta_upper reaches this level (a
                   weaker-than-delta "good enough" bound).
      tuples     — stop once this many tuples were read while live
                   (a hard sampling-cost SLA).
    """

    wall_ms: Optional[float] = None
    confidence: Optional[float] = None
    tuples: Optional[int] = None

    def __post_init__(self):
        if self.wall_ms is None and self.confidence is None and self.tuples is None:
            raise ValueError(
                "StopPolicy needs at least one of wall_ms/confidence/tuples"
            )
        if self.wall_ms is not None and not self.wall_ms >= 0.0:
            raise ValueError(f"need wall_ms >= 0, got {self.wall_ms}")
        if self.confidence is not None and not (0.0 < self.confidence <= 1.0):
            raise ValueError(f"need 0 < confidence <= 1, got {self.confidence}")
        if self.tuples is not None and not self.tuples >= 0:
            raise ValueError(f"need tuples >= 0, got {self.tuples}")

    def fired(self, *, wall_s: float, confidence: float, tuples: int) -> str:
        """The reason this policy fires on the given live-query gauges,
        or "" if it does not. Checked cheapest-guarantee-loss first:
        a confidence stop yields the strongest answer, so when several
        criteria fire at the same poll that is the reason reported."""
        if self.confidence is not None and confidence >= self.confidence:
            return "confidence"
        if self.tuples is not None and tuples >= self.tuples:
            return "tuples"
        if self.wall_ms is not None and wall_s * 1000.0 >= self.wall_ms:
            return "wall_ms"
        return ""


@dataclasses.dataclass(frozen=True)
class MultiQuerySpec:
    """Static shape/criterion/metric configuration shared by all query
    slots."""

    v_z: int
    v_x: int
    max_queries: int = 8
    criterion: str = "histsim"  # "histsim" | "slowmatch", applies to all slots
    # Static upper bound on any slot's k. When set, the per-slot
    # deviation assignment selects M via a (k_cap+1)-element lax.top_k
    # instead of a V_Z-sized sort; admission validates k <= k_cap.
    # None = no bound known (selection falls back to V_Z order stats).
    k_cap: Optional[int] = None
    # Registry distance the shared tau pass computes (and the bound
    # family deviations go through) — static per scheduler. "l1"
    # reproduces the pre-metric-layer program bit for bit.
    metric: str = "l1"
    # Failure-bound routing: "native" evaluates Theorem 1 at the
    # observation-aware ℓ1 budget (tighter for chi2/hellinger, never
    # looser; the l1 arm is bit-identical under both modes),
    # "conservative" keeps the PR-9 uniform budgets.
    bounds_mode: str = "native"
    # Early-reject pruning: retire clearly-far candidates from the
    # union-active set (I/O marking only — the failure bounds keep
    # summing over everyone). False compiles the exact pre-pruning
    # active-set expression; the flag is static, so flipping it is a
    # (deliberate) recompile, never a mid-stream shape change.
    prune: bool = False
    # Scheduler-wide default StopPolicy for queries admitted without
    # their own. compare=False keeps it out of __eq__/__hash__: stop
    # policies are host-loop decisions, so two specs differing only
    # here share every jit cache entry.
    default_stop: Optional[StopPolicy] = dataclasses.field(
        default=None, compare=False
    )

    def __post_init__(self):
        if self.max_queries < 1:
            raise ValueError(f"need max_queries >= 1, got {self.max_queries}")
        if self.criterion not in ("histsim", "slowmatch"):
            raise ValueError(self.criterion)
        if self.k_cap is not None and not (0 < self.k_cap <= self.v_z):
            raise ValueError(f"need 0 < k_cap <= V_Z, got k_cap={self.k_cap}")
        if self.bounds_mode not in ("native", "conservative"):
            raise ValueError(
                f"bounds_mode must be 'native' or 'conservative', "
                f"got {self.bounds_mode!r}"
            )
        if self.default_stop is not None and not isinstance(
            self.default_stop, StopPolicy
        ):
            raise TypeError(
                f"default_stop must be a StopPolicy, got {self.default_stop!r}"
            )
        from repro.kernels import metrics as _metrics

        _metrics.coerce_metric(self.metric)  # fail construction, not trace


class MultiQueryState(NamedTuple):
    """One shared counts matrix + per-slot query statistics (Q = max_queries)."""

    counts: jax.Array  # (V_Z, V_X) f32 — SHARED empirical counts r_i
    n: jax.Array  # (V_Z,) f32 — SHARED samples per candidate n_i
    q_hat: jax.Array  # (Q, V_X) f32 normalized targets
    k: jax.Array  # (Q,) i32 per-query k
    eps: jax.Array  # (Q,) f32 per-query eps
    delta: jax.Array  # (Q,) f32 per-query delta
    gap: jax.Array  # (Q,) f32 — closeness promise gap (0 for top-k slots)
    qtype: jax.Array  # (Q,) i32 — QTYPE_TOPK | QTYPE_CLOSENESS per slot
    tau: jax.Array  # (Q, V_Z) f32 per-query distance estimates
    eps_i: jax.Array  # (Q, V_Z) f32 assigned deviations
    log_delta_i: jax.Array  # (Q, V_Z) f32
    delta_upper: jax.Array  # (Q,) f32 — 0 for empty slots
    active: jax.Array  # (Q, V_Z) bool — per-query AnyActive candidates
    active_words: jax.Array  # (Q, W) uint32 packed per-query active masks
    union_words: jax.Array  # (W,) uint32 — OR over slots; drives block marking
    in_top_k: jax.Array  # (Q, V_Z) bool — per-query matching set M
    # Sticky early-reject mask (all-False unless spec.prune): candidates
    # certified clearly-far, dropped from the I/O marking only — the
    # failure bounds keep summing over every candidate.
    pruned: jax.Array  # (Q, V_Z) bool
    occupied: jax.Array  # (Q,) bool — slot holds a live query
    round_idx: jax.Array  # () i32 — statistics iterations so far


class SampleCursor(NamedTuple):
    """Device-resident sampling-side state: the without-replacement
    read_mask plus the monotone read counters, updated inside the fused
    round so the host never has to sync to account for a window."""

    read_mask: jax.Array  # (num_blocks,) bool
    blocks_read: jax.Array  # () i32
    blocks_considered: jax.Array  # () i32
    tuples_read: jax.Array  # () i32
    rounds: jax.Array  # () i32 — windows dispatched


class CacheSnapshot(NamedTuple):
    """The serving loop's durable warm-start state — everything a
    restarted server needs to answer future queries from the
    accumulated sample instead of from zero.

    Only TARGET-INDEPENDENT state is here: the shared counts matrix and
    per-candidate row sums (sufficient statistics for every future
    query — the closeness-testing view), the without-replacement
    ``read_mask`` plus its monotone counters, and the host-side pass /
    visit-order bookkeeping. Live query slots are deliberately NOT part
    of a snapshot: in-flight queries re-enter the serving queue after a
    restart, and because sampling is target-independent they lose
    nothing by re-admitting against the restored counts.

    A snapshot is a flat pytree of arrays so `CheckpointManager` can
    save it crash-atomically and `restore_resharded` can re-place the
    candidate-sharded leaves onto a different mesh shape
    (`repro.core.distributed.cache_pspecs`).
    """

    counts: jax.Array  # (V_Z, V_X) f32 shared empirical counts r_i
    n: jax.Array  # (V_Z,) f32 shared samples per candidate n_i
    read_mask: jax.Array  # (num_blocks,) bool without-replacement state
    blocks_read: jax.Array  # () i32
    blocks_considered: jax.Array  # () i32
    tuples_read: jax.Array  # () i32
    rounds: jax.Array  # () i32 — windows dispatched
    passes: jax.Array  # () i32 — host-side pass counter
    start: jax.Array  # () i32 — cyclic visit-order offset


def cache_config_hash(source, spec: MultiQuerySpec) -> str:
    """Fingerprint binding a `CacheSnapshot` to (dataset layout, spec).

    Accumulated counts are sufficient statistics for any future query
    ONLY over the exact blocked layout they were sampled from: under a
    different shuffle, block size, or attribute arity the restored
    ``read_mask``/counts pair silently invalidates every Theorem-1
    bound. The hash covers the layout dimensions, the per-block tuple
    counts, the content of up to 64 probe blocks spread evenly across
    the whole layout, and the `MultiQuerySpec`, so a stale snapshot is
    REJECTED at restore (ValueError from `CheckpointManager`) instead
    of corrupting bounds.

    The probe reads O(64) blocks, never the dataset — hashing all
    content at every warm construction would cost the cold scan the
    warm start exists to avoid. The even spread catches reshuffles,
    re-blockings and bulk rewrites anywhere in the layout; an edit
    confined to unprobed blocks that also preserves every per-block
    tuple count is the accepted residual risk of this trade.
    """
    src = as_block_source(source)
    probe = np.unique(
        np.linspace(0, src.num_blocks - 1, min(src.num_blocks, 64)).astype(np.int64)
    )
    wd = src.fetch(probe, pad_to=len(probe))
    fp = hashlib.sha256()
    fp.update(np.ascontiguousarray(np.asarray(src.tuples_per_block, np.int64)).tobytes())
    for leaf in (wd.z, wd.x, wd.bitmap):
        fp.update(np.ascontiguousarray(jax.device_get(leaf)).tobytes())
    payload = (
        "fastmatch-cache-v1",
        (spec.v_z, spec.v_x, spec.max_queries, spec.criterion, spec.k_cap),
        (src.num_blocks, src.block_size),
        fp.hexdigest(),
    )
    return _checkpoint_config_hash(payload)


def init_cursor(num_blocks: int) -> SampleCursor:
    zero = jnp.asarray(0, jnp.int32)
    return SampleCursor(
        read_mask=jnp.zeros((num_blocks,), bool),
        blocks_read=zero,
        blocks_considered=zero,
        tuples_read=zero,
        rounds=zero,
    )


def init_multi_state(spec: MultiQuerySpec) -> MultiQueryState:
    """All slots empty, counts at zero."""
    q, v_z, v_x = spec.max_queries, spec.v_z, spec.v_x
    w = words_for(v_z)
    return MultiQueryState(
        counts=jnp.zeros((v_z, v_x), jnp.float32),
        n=jnp.zeros((v_z,), jnp.float32),
        q_hat=jnp.full((q, v_x), 1.0 / v_x, jnp.float32),
        k=jnp.ones((q,), jnp.int32),
        eps=jnp.ones((q,), jnp.float32),
        delta=jnp.ones((q,), jnp.float32),
        gap=jnp.zeros((q,), jnp.float32),
        qtype=jnp.zeros((q,), jnp.int32),
        tau=jnp.ones((q, v_z), jnp.float32),
        eps_i=jnp.zeros((q, v_z), jnp.float32),
        log_delta_i=jnp.zeros((q, v_z), jnp.float32),
        delta_upper=jnp.zeros((q,), jnp.float32),
        active=jnp.zeros((q, v_z), bool),
        active_words=jnp.zeros((q, w), jnp.uint32),
        union_words=jnp.zeros((w,), jnp.uint32),
        in_top_k=jnp.zeros((q, v_z), bool),
        pruned=jnp.zeros((q, v_z), bool),
        occupied=jnp.zeros((q,), bool),
        round_idx=jnp.asarray(0, jnp.int32),
    )


@partial(jax.jit, static_argnames=("spec",))
def admit_slot(
    state: MultiQueryState,
    slot: jax.Array,
    q_hat: jax.Array,
    k: jax.Array,
    eps: jax.Array,
    delta: jax.Array,
    *,
    spec: MultiQuerySpec,
    qtype: jax.Array = QTYPE_TOPK,
    gap: jax.Array = 0.0,
) -> MultiQueryState:
    """Install a query into `slot`. Run `stats_step` before the next marking
    so the new query's active set reflects the accumulated shared counts.

    ``qtype``/``gap`` default to a top-k query (the pre-closeness
    signature — existing positional callers are unchanged); pass
    ``qtype=QTYPE_CLOSENESS`` with a positive ``gap`` for a tolerant
    closeness test (eps = the "close" radius, eps + gap = the "far"
    radius; k is ignored for such slots)."""
    del spec  # shapes carried by state
    slot = jnp.asarray(slot, jnp.int32)
    return state._replace(
        q_hat=state.q_hat.at[slot].set(jnp.asarray(q_hat, jnp.float32)),
        k=state.k.at[slot].set(jnp.asarray(k, jnp.int32)),
        eps=state.eps.at[slot].set(jnp.asarray(eps, jnp.float32)),
        delta=state.delta.at[slot].set(jnp.asarray(delta, jnp.float32)),
        gap=state.gap.at[slot].set(jnp.asarray(gap, jnp.float32)),
        qtype=state.qtype.at[slot].set(jnp.asarray(qtype, jnp.int32)),
        pruned=state.pruned.at[slot].set(False),
        occupied=state.occupied.at[slot].set(True),
    )


@partial(jax.jit, static_argnames=("spec",))
def clear_slot(state: MultiQueryState, slot: jax.Array, *, spec: MultiQuerySpec) -> MultiQueryState:
    """Free a slot (query retired): drop it from the active union.

    tau is pinned back to the init value 1.0 — the batched `stats_step`
    masks unoccupied slots out of the tau update, so whatever a freed
    slot holds would otherwise linger as a stale snapshot."""
    del spec
    slot = jnp.asarray(slot, jnp.int32)
    active_words = state.active_words.at[slot].set(jnp.uint32(0))
    return state._replace(
        occupied=state.occupied.at[slot].set(False),
        active=state.active.at[slot].set(False),
        active_words=active_words,
        tau=state.tau.at[slot].set(1.0),
        delta_upper=state.delta_upper.at[slot].set(0.0),
        gap=state.gap.at[slot].set(0.0),
        qtype=state.qtype.at[slot].set(QTYPE_TOPK),
        pruned=state.pruned.at[slot].set(False),
        union_words=_or_reduce(active_words),
    )


def _or_reduce(words: jax.Array) -> jax.Array:
    """(Q, W) uint32 -> (W,) bitwise OR over the query axis."""
    return jax.lax.reduce(words, jnp.uint32(0), jax.lax.bitwise_or, dimensions=[0])


@partial(jax.jit, static_argnames=("spec",))
def ingest(
    state: MultiQueryState,
    z_idx: jax.Array,
    x_idx: jax.Array,
    *,
    spec: MultiQuerySpec,
) -> MultiQueryState:
    """Accumulate a padded sample batch into the SHARED counts — one
    histogram-kernel launch serves every live query. The kernel emits
    the per-candidate row-sum delta from the same pass, so advancing
    ``n_i`` costs no second sweep over the delta matrix."""
    delta_counts, delta_n = ops.histogram_with_rowsums(
        z_idx, x_idx, v_z=spec.v_z, v_x=spec.v_x
    )
    return state._replace(
        counts=state.counts + delta_counts,
        n=state.n + delta_n,
    )


def apply_stats(
    state: MultiQueryState, tau: jax.Array, n: jax.Array, *, spec: MultiQuerySpec
) -> MultiQueryState:
    """Per-slot deviation assignment from precomputed distances.

    The shared tail of the statistics engine: given (Q, V_Z) distances
    and the full (V_Z,) sample counts, run the vmapped per-query
    assignment with each slot's (k, eps, delta) and rebuild the active
    union. Both `stats_step` (single device) and the unified
    `repro.core.distributed.make_distributed_round` (tau/n arriving via
    all-gather from candidate shards) end in this function, so the two
    paths cannot drift.

    Each slot's RETIREMENT RULE follows its dynamic ``qtype``: both the
    top-k deviation assignment and the closeness margins are evaluated
    (each is O(V_Z) per slot — negligible next to the (V_Z, V_X) tau
    pass) and per-slot selected, so mixing query types never
    recompiles. The select is value-exact: an all-top-k workload
    produces bit-identical results to the pre-closeness engine.

    With ``spec.prune`` the sticky per-slot ``pruned`` mask is OR-grown
    with `dev.prune_far` — candidates whose lower confidence bound
    clears the far edge (eps + gap for closeness, split + eps/2 for
    top-k) — and subtracted from the I/O marking. A Python-level
    branch: prune=False compiles the exact pre-pruning active-set
    expression, and the mask is fixed-shape so flipping candidates
    never recompiles.
    """

    def one(tau_q, k, eps, delta, gap, qtype, occupied, pruned_q):
        d_top = dev.assign_deviations_dynamic(
            tau_q, n, k=k, eps=eps, delta=delta, v_x=spec.v_x,
            criterion=spec.criterion, k_cap=spec.k_cap, metric=spec.metric,
            bounds_mode=spec.bounds_mode,
        )
        d_close = dev.assign_closeness(
            tau_q, n, eps=eps, gap=gap, delta=delta, v_x=spec.v_x,
            metric=spec.metric, bounds_mode=spec.bounds_mode,
        )
        is_close = qtype == QTYPE_CLOSENESS
        d = jax.tree.map(
            lambda a, b: jnp.where(is_close, a, b), d_close, d_top
        )
        if spec.prune:
            far_edge = jnp.where(is_close, eps + gap, d.split + 0.5 * eps)
            pruned_q = pruned_q | (
                dev.prune_far(
                    tau_q, n, far_edge=far_edge, delta=delta, v_x=spec.v_x,
                    metric=spec.metric,
                )
                & occupied
            )
            active = d.active & occupied & ~pruned_q
        else:
            active = d.active & occupied
        return (
            d.eps_i,
            d.log_delta_i,
            jnp.where(occupied, d.delta_upper, 0.0),
            active,
            pack_active_mask(active),
            d.in_top_k & occupied,
            pruned_q,
        )

    eps_i, log_delta_i, delta_upper, active, words, in_top_k, pruned = (
        jax.vmap(one)(
            tau, state.k, state.eps, state.delta, state.gap, state.qtype,
            state.occupied, state.pruned,
        )
    )
    return state._replace(
        tau=tau,
        eps_i=eps_i,
        log_delta_i=log_delta_i,
        delta_upper=delta_upper,
        active=active,
        active_words=words,
        union_words=_or_reduce(words),
        in_top_k=in_top_k,
        pruned=pruned,
        round_idx=state.round_idx + 1,
    )


@partial(jax.jit, static_argnames=("spec",))
def stats_step(state: MultiQueryState, *, spec: MultiQuerySpec) -> MultiQueryState:
    """One statistics-engine iteration for every slot — no Python loop.

    tau for ALL slots comes from ONE `ops.distance_multi` call (the
    spec's static metric — "l1" by default): the
    shared counts matrix is streamed once and scored against the whole
    (Q, V_X) target batch, so the statistics cost per round is
    independent of the number of query slots (the PR-2 path unrolled Q
    single-query kernel calls, re-reading counts per slot — and empty
    slots burned a full pass against a stale q_hat). Unoccupied slots
    are masked out of the tau update (pinned at the init value 1.0);
    the deviation assignment with each slot's (k, eps, delta) is
    vmapped over the query axis via `apply_stats`.
    """
    tau = ops.distance_multi(state.counts, state.q_hat, metric=spec.metric)
    tau = jnp.where(state.occupied[:, None], tau, 1.0)
    return apply_stats(state, tau, state.n, spec=spec)


def run_round(
    state: MultiQueryState,
    z_idx: jax.Array,
    x_idx: jax.Array,
    *,
    spec: MultiQuerySpec,
) -> MultiQueryState:
    """Shared ingest + vmapped stats — one full multi-query round."""
    return stats_step(ingest(state, z_idx, x_idx, spec=spec), spec=spec)


def _advance_cursor(cursor: SampleCursor, wd: WindowData, marks: jax.Array) -> SampleCursor:
    """Read bookkeeping shared by the sampling and exact-completion
    rounds — any change to the accounting applies to both paths."""
    # scatter-add (duplicate-safe: padding repeats a real id with a zero
    # contribution) then re-binarize — bool scatter-or is not available
    read_mask = (
        cursor.read_mask.astype(jnp.int32).at[wd.indices].add(marks.astype(jnp.int32)) > 0
    )
    return SampleCursor(
        read_mask=read_mask,
        blocks_read=cursor.blocks_read + jnp.sum(marks.astype(jnp.int32)),
        blocks_considered=cursor.blocks_considered + jnp.sum(wd.valid.astype(jnp.int32)),
        tuples_read=cursor.tuples_read
        + jnp.sum(jnp.where(marks, jnp.sum((wd.z >= 0).astype(jnp.int32), axis=1), 0)),
        rounds=cursor.rounds + 1,
    )


@partial(jax.jit, static_argnames=("spec", "policy"))
def fused_round(
    state: MultiQueryState,
    cursor: SampleCursor,
    wd: WindowData,
    *,
    spec: MultiQuerySpec,
    policy: str,
) -> tuple:
    """One device-resident sampling round: mark + gather-mask + ingest +
    vmapped stats + read bookkeeping, one dispatch, zero host syncs.

    Marking uses the union active words (stale by up to ``poll_every``
    windows of retirements — the generalized Sec 4.2 relaxation) and is
    masked by the window's padding validity and the device read_mask, so
    a block can never be double-counted even if the host hands out an
    overlapping window. Ingest+stats are skipped branchlessly (lax.cond)
    when nothing was marked, matching the host-stepped loop's cadence
    (stats run only after windows that read something).
    """
    marks = mark_window(wd.bitmap, state.union_words, policy=policy)
    marks = marks & wd.valid & ~cursor.read_mask[wd.indices]
    n_marked = jnp.sum(marks.astype(jnp.int32))

    def with_round(st: MultiQueryState) -> MultiQueryState:
        zw = jnp.where(marks[:, None], wd.z, jnp.int32(-1)).reshape(-1)
        xw = jnp.where(marks[:, None], wd.x, jnp.int32(-1)).reshape(-1)
        return stats_step(ingest(st, zw, xw, spec=spec), spec=spec)

    state = jax.lax.cond(n_marked > 0, with_round, lambda st: st, state)
    return state, _advance_cursor(cursor, wd, marks)


@partial(jax.jit, static_argnames=("spec",))
def ingest_round(
    state: MultiQueryState,
    cursor: SampleCursor,
    wd: WindowData,
    *,
    spec: MultiQuerySpec,
) -> tuple:
    """Exact-completion round: ingest every unread block of the window
    into the shared counts, no marking, no stats (the caller runs one
    `stats_step` after the last chunk — statistics are a pure function
    of the counts, so per-chunk stats would be wasted work)."""
    marks = wd.valid & ~cursor.read_mask[wd.indices]
    zw = jnp.where(marks[:, None], wd.z, jnp.int32(-1)).reshape(-1)
    xw = jnp.where(marks[:, None], wd.x, jnp.int32(-1)).reshape(-1)
    state = ingest(state, zw, xw, spec=spec)
    return state, _advance_cursor(cursor, wd, marks)


class RoundFns(NamedTuple):
    """The device programs one scheduler dispatches (jitted, static
    arguments bound): the single-device rounds above, or a mesh's
    shard_map rounds (`repro.core.distributed`)."""

    round: Callable  # (state, cursor, wd) -> (state, cursor): mark + ingest + stats
    ingest: Callable  # (state, cursor, wd) -> (state, cursor): exact completion
    stats: Callable  # state -> state: one statistics step


def slot_state(state: MultiQueryState, slot: int) -> HistSimState:
    """Single-query `HistSimState` view of one slot (counts/n are shared)."""
    return HistSimState(
        counts=state.counts,
        n=state.n,
        q_hat=state.q_hat[slot],
        tau=state.tau[slot],
        eps_i=state.eps_i[slot],
        log_delta_i=state.log_delta_i[slot],
        delta_upper=state.delta_upper[slot],
        active=state.active[slot],
        active_words=state.active_words[slot],
        in_top_k=state.in_top_k[slot],
        round_idx=state.round_idx,
    )


# ---------------------------------------------------------------------------
# The shared window-marking / ingest loop
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _Ticket:
    """Host-side bookkeeping for one live query slot."""

    qid: int
    slot: int
    k: int
    eps: float
    delta: float
    qtype: str  # "topk" | "closeness"
    gap: float  # closeness promise gap; 0.0 for top-k
    admit_time: float
    admit_rounds: int
    admit_passes: int
    admit_blocks_read: int
    admit_blocks_considered: int
    admit_tuples_read: int
    stop: Optional[StopPolicy] = None  # SLA policy; None = run to the bound


@dataclasses.dataclass
class QueryOutcome:
    """Per-query result produced at retirement."""

    qid: int
    ids: np.ndarray  # (k,) matching ids, closest first; for a closeness
    # query, ALL candidates labeled close (variable length, tau order)
    state: HistSimState  # single-query view snapshot at retirement
    delta_upper: float
    exact: bool  # the answer rests on a complete read of the data
    terminated: bool  # the statistical rule delta_upper < delta fired
    rounds: int  # windows processed while this query was live
    passes: int
    blocks_read: int
    blocks_considered: int
    tuples_read: int  # tuples ingested while this query was live
    wall_time_s: float
    # Degradation contract (I/O quarantine). ``degraded`` is True when
    # any block was quarantined while the scheduler served this query:
    # the (eps, delta) guarantee then holds over the SURVIVING block
    # population (``exact`` likewise means a complete read of the
    # survivors), and ``eps_effective`` is the honestly widened L1
    # radius vs the FULL dataset — eps + 2 * (quarantined tuple
    # fraction), since dropping a content-independent fraction q of any
    # candidate's tuples moves its empirical histogram by at most 2q in
    # L1. Fault-free: degraded=False and eps_effective == query eps.
    degraded: bool = False
    eps_effective: float = float("nan")
    blocks_quarantined: int = 0
    qtype: str = "topk"  # "topk" | "closeness"
    # SLA early stop: ``stopped`` is True when a StopPolicy (or a
    # supervisor deadline) retired the query before its statistical
    # bound fired; the answer is then exactly the anytime statement at
    # that poll (exact=False, terminated=False, achieved delta_upper).
    stopped: bool = False
    stop_reason: str = ""  # "confidence" | "tuples" | "wall_ms" | "deadline"
    # The poll-boundary anytime statement assembled at retirement by
    # `SharedCountsScheduler.peek` — the SAME host code path serving
    # live polls, so a stopped answer is bit-identical to what
    # poll_result would have said at that round.
    anytime: Optional["AnytimeAnswer"] = None


@dataclasses.dataclass
class AnytimeAnswer:
    """A progressive (poll-boundary) answer with its Theorem-1-style
    confidence statement — what `MatchServer.poll_result` returns.

    The statement reads: "the current best set is ``ids`` (closest
    first); every candidate's empirical distance is within ``eps_n`` of
    its true one w.p. > 1 - delta/|V_Z| each, the probability that the
    set is not (eps, k)-correct is at most ``delta_upper``, and each
    listed candidate would have to move by its ``margin`` (in metric
    space) for its membership promise to break."

    All quantities are the CURVE_COLUMNS trajectory quantities promoted
    from telemetry to API (`curve_point` is the inverse promotion), so
    a recorded confidence curve and a sequence of polls agree exactly.
    """

    qid: int
    qtype: str  # "topk" | "closeness"
    status: str  # "queued" | "live" | "done"
    ids: np.ndarray  # current best set, closest first
    tau: np.ndarray  # (len(ids),) empirical distances of the best set
    margin: np.ndarray  # (len(ids),) per-candidate decision margin
    split: float  # current split point / closeness threshold
    n_min: float  # weakest per-candidate sample count
    tau_min: float
    eps_n: float  # metric-space eps(n_min) at per-candidate budget delta/V_Z
    delta_upper: float  # union failure bound of the CURRENT labeling
    confidence: float  # max(0, 1 - delta_upper)
    round: int
    tuples: int
    tuples_live: int  # tuples read while this query was live
    eps: float
    delta: float
    metric: str
    exact: bool = False
    stopped: bool = False
    stop_reason: str = ""
    result: Optional[object] = None  # final MatchResult once status == "done"

    def curve_point(self) -> dict:
        """This answer as a CURVE_COLUMNS trajectory point — the exact
        dict `Telemetry.record_curve_point` stores, so polls can be
        appended to the same confidence curves telemetry records."""
        return dict(
            round=self.round,
            tuples=self.tuples,
            tuples_live=self.tuples_live,
            n_min=self.n_min,
            tau_min=self.tau_min,
            eps_n=self.eps_n,
            delta_upper=self.delta_upper,
            confidence=self.confidence,
        )


def _theorem1_eps_np(n: float, delta_i: float, v_x: int) -> float:
    """Host-side Theorem 1 eps(n) — scalar mirror of
    `repro.core.bounds.theorem1_epsilon` so recording a trajectory point
    never dispatches device work (tests pin the two against each other).
    `math` rather than numpy: this runs per live query per poll, and
    numpy scalar ops are ~10x slower than libm calls.
    """
    n = max(float(n), 1.0)
    return math.sqrt((2.0 / n) * (v_x * math.log(2.0) - math.log(delta_i)))


def _metric_eps_np(n: float, delta_i: float, v_x: int, metric: str) -> float:
    """`_theorem1_eps_np` pushed through the metric's budget inverse —
    the host-side scalar mirror of `bounds.metric_epsilon` (same
    derivations). The l1 branch is the identity, keeping the default
    telemetry path byte-identical."""
    eps1 = _theorem1_eps_np(n, delta_i, v_x)
    if metric == "l1":
        return eps1
    if metric == "chi2":
        return 3.0 * eps1
    if metric == "hellinger":
        return 2.0 * math.sqrt(eps1)
    raise ValueError(f"unknown metric {metric!r}")


class SharedCountsScheduler:
    """The FastMatch execution loop over a shared counts matrix.

    Owns the dataset-side sampling state — the cyclic visit order, the
    device-resident `SampleCursor` (global without-replacement
    ``read_mask`` + read counters), and pass structure — plus the
    `MultiQueryState`. Queries enter via `admit` (any time, into a free
    slot), leave via `retire` (collected in `outcomes`), and `pump`
    drives windows until every live query resolves:

      mark   — AnyActive over the UNION active words
      ingest — marked blocks into the shared counts
      stats  — vmapped per-query deviation assignment + bounds

    all three fused into one jitted `fused_round` dispatch per window;
    block data arrives through the `repro.io.BlockSource` given at
    construction (a `BlockedDataset` is wrapped in `InMemorySource`;
    pass a `PrefetchSource` to overlap next-window gathering with the
    current round). The host polls ``delta_upper`` + counters only
    every ``poll_every`` windows — `host_syncs` counts these polls, and
    host-side mirrors (``read_mask``, ``rounds``, ``blocks_read``, …)
    are refreshed at each one. ``poll_every=1`` reproduces the
    host-stepped loop exactly; larger values defer retirement/admission
    by at most ``poll_every - 1`` windows (bounded staleness) and let
    the budget overshoot by the same amount.

    A pass visits every not-yet-read block in cyclic order; blocks
    skipped by AnyActive stay eligible for later passes (a newly
    admitted query can re-activate them). If a whole pass reads nothing
    while queries remain live, the scheduler completes exactly — reads
    the remainder so empirical counts equal the true ones — and retires
    the stragglers with ``exact=True``. A `max_rounds` budget instead
    stops the loop with live queries left best-effort (the caller
    retires them with ``exact=False``).

    With ``mesh`` given, the shared counts matrix is placed sharded
    ``P(model_axis, None)`` (samples-per-candidate ``P(model_axis)``)
    and every round runs as one shard_map program across the mesh
    (`repro.core.distributed.make_stream_rounds`): the single window
    stream is replicated, its samples split over the other mesh axes,
    and each device histograms its candidate rows. V_Z must divide by
    the model axis size; otherwise construction raises ValueError.
    """

    def __init__(
        self,
        dataset,
        spec: MultiQuerySpec,
        *,
        policy: str = "anyactive",
        window: int = 512,
        seed: int = 0,
        start_block: Optional[int] = None,
        poll_every: int = 1,
        mesh=None,
        model_axis: str = "model",
        telemetry: Optional[Telemetry] = None,
    ):
        source: BlockSource = as_block_source(dataset)
        if spec.v_z != source.v_z or spec.v_x != source.v_x:
            raise ValueError("spec/dataset dimension mismatch")
        if getattr(source, "lo", 0) != 0:
            # A ShardedSource speaks GLOBAL block ids while the scheduler
            # owns a 0-based visit order/read_mask — shard sources feed
            # the manually driven distributed round, not this loop.
            raise ValueError(
                "SharedCountsScheduler needs a 0-based source (whole dataset); "
                "use ShardedSource with make_distributed_round instead"
            )
        if policy not in ("anyactive", "scan"):
            raise ValueError(f"unknown policy {policy!r}")
        if poll_every < 1:
            raise ValueError(f"need poll_every >= 1, got {poll_every}")
        self.source = source
        self.spec = spec
        self.policy = policy
        self.poll_every = poll_every
        nb = source.num_blocks
        self.window = max(1, min(window, nb))

        rng = np.random.default_rng(seed)
        start = start_block if start_block is not None else int(rng.integers(nb))
        self._start = start  # persisted by export_cache: the visit order
        self.order = np.roll(np.arange(nb), -start)  # cyclic visit order

        self.state = init_multi_state(spec)
        self.cursor = self._place_cursor(init_cursor(nb))
        self._rounds = self._make_rounds(mesh, model_axis)
        if mesh is not None:
            from jax.sharding import NamedSharding
            from repro.core.distributed import multi_state_pspecs

            specs = multi_state_pspecs(model_axis=model_axis)
            self.state = jax.device_put(
                self.state, jax.tree.map(lambda s: NamedSharding(mesh, s), specs)
            )
        self.tickets: Dict[int, _Ticket] = {}  # slot -> ticket
        self.outcomes: Dict[int, QueryOutcome] = {}  # qid -> outcome
        self._next_qid = 0

        # host mirrors of the device cursor + per-slot bounds, refreshed
        # by `_sync()` (per-query numbers are deltas vs admit)
        self.read_mask = np.zeros(nb, dtype=bool)
        self.rounds = 0
        self.passes = 0  # host-side pass structure, not device state
        self.blocks_read = 0
        self.blocks_considered = 0
        self.tuples_read = 0
        self._delta_upper = np.zeros(spec.max_queries, np.float32)
        # Anytime-answer mirrors (always refreshed — `peek` assembles
        # progressive answers from these between dispatches).
        self._tel_tau = np.ones((spec.max_queries, spec.v_z), np.float32)
        self._tel_n = np.zeros(spec.v_z, np.float32)
        self._in_top_k_host = np.zeros((spec.max_queries, spec.v_z), bool)
        self._pruned_host = np.zeros((spec.max_queries, spec.v_z), bool)
        # Quarantine state (host-side — quarantined blocks never reach a
        # device dispatch, they are simply excluded from every future
        # pass order). All-False in the fault-free path, in which case
        # every eligibility mask below reduces to the pre-quarantine
        # expression bit for bit.
        self.quarantined = np.zeros(nb, dtype=bool)
        self.blocks_quarantined = 0
        self.tuples_quarantined = 0
        self.total_tuples = int(np.sum(np.asarray(source.tuples_per_block, np.int64)))
        self.budget_exhausted = False
        self.host_syncs = 0  # number of device->host polls performed
        self.host_sync_bytes = 0  # bytes those polls copied to the host
        # polls made by the window loop itself (pump/run_window), i.e.
        # the steady-state cadence poll_every controls — excludes the
        # per-query fixed polls at admission
        self.loop_syncs = 0

        # Telemetry is poll-boundary only: every record below rides an
        # existing host sync, so the jitted round path and the dispatch
        # sequence are identical with telemetry on and off (the
        # bit-equivalence guard in tests/test_obs.py).
        self.telemetry = telemetry
        if telemetry is not None:
            reg = telemetry.registry
            self._tel_last = {"rounds": 0, "blocks": 0, "considered": 0,
                              "tuples": 0, "passes": 0}
            # Poll-time recording is two appends (see `_record_poll`);
            # everything dict/registry-shaped happens in
            # `flush_telemetry`, batched, at lifecycle boundaries or on
            # first read — per-poll python shaping runs cache-cold right
            # after a device phase and costs ~10x its warm price.
            self._poll_buf: list = []
            self._tel_pending = {"syncs": 0, "rounds": 0, "blocks": 0,
                                 "considered": 0, "tuples": 0, "passes": 0}
            telemetry.add_flush_hook(self.flush_telemetry)
            self._c_syncs = reg.counter(
                "fastmatch_host_syncs_total", "device-host polls performed")
            self._c_rounds = reg.counter(
                "fastmatch_rounds_total", "windows dispatched (stats iterations)")
            self._c_blocks = reg.counter(
                "fastmatch_blocks_read_total", "blocks ingested into shared counts")
            self._c_considered = reg.counter(
                "fastmatch_blocks_considered_total",
                "valid blocks in dispatched windows, read or skipped")
            self._c_tuples = reg.counter(
                "fastmatch_tuples_read_total", "tuples drawn (m of Theorem 1)")
            self._c_passes = reg.counter(
                "fastmatch_passes_total", "cyclic passes over the block layout")
            self._c_admitted = reg.counter(
                "fastmatch_queries_admitted_total", "queries admitted into slots")
            self._c_retired = reg.counter(
                "fastmatch_queries_retired_total", "queries retired with an answer")
            self._c_quarantined = reg.counter(
                "fastmatch_blocks_quarantined_total",
                "blocks dropped from the probe set after I/O quarantine")
            self._h_q_tuples = reg.histogram(
                "fastmatch_query_tuples", edges=tuple(float(10 ** e) for e in range(2, 11)),
                help="tuples read while a query was live (per-query m)")
            self._h_q_rounds = reg.histogram(
                "fastmatch_query_rounds", edges=tuple(float(2 ** e) for e in range(0, 14)),
                help="rounds to retirement (paper Fig. 5)")
            self._h_q_wall = reg.histogram(
                "fastmatch_query_wall_seconds", help="admit-to-retire wall time")
            reg.gauge(
                "fastmatch_ingest_lane_fill",
                "share of the ingest contraction's output cells that are real counts",
            ).set(ingest_lane_fill(spec.v_z, spec.v_x))

    # -- device placement hooks (overridden by the data-parallel pump) -----

    def _place_cursor(self, cursor: SampleCursor) -> SampleCursor:
        """Place a freshly built (host-side) sampling cursor on device.

        The base scheduler keeps the cursor on the default device;
        `repro.core.pump.DistributedPump` overrides this to pad the
        ``read_mask`` to the worker grid and shard it over the data
        axes (`distributed.cursor_pspecs`). Called from __init__ and
        `import_cache`, so a restored snapshot always lands with the
        same placement as a fresh cursor."""
        return cursor

    def _make_rounds(self, mesh, model_axis: str) -> RoundFns:
        """The device programs this scheduler dispatches, chosen once.

        Without a mesh: `fused_round` / `ingest_round` / `stats_step`.
        With one: the explicit shard_map rounds of
        `repro.core.distributed.make_stream_rounds`.
        `repro.core.pump.DistributedPump` overrides this with its
        per-worker rounds."""
        if mesh is None:
            return RoundFns(
                round=partial(fused_round, spec=self.spec, policy=self.policy),
                ingest=partial(ingest_round, spec=self.spec),
                stats=partial(stats_step, spec=self.spec),
            )
        from repro.core.distributed import make_stream_rounds

        return make_stream_rounds(
            mesh, self.spec, model_axis=model_axis, policy=self.policy
        )

    def _global_read_mask(self) -> jax.Array:
        """The (num_blocks,) global read_mask view of the device cursor
        — what `export_cache` persists. The pump overrides this to
        gather its data-sharded mask and strip the worker-grid padding,
        so snapshots stay interchangeable across pump widths and with
        the single-stream scheduler."""
        return self.cursor.read_mask

    # -- quarantine (degraded guarantees) ----------------------------------

    def _quarantine_sources(self) -> tuple:
        """The sources whose `ResilientSource` layers (if any) this
        scheduler drains for quarantined block ids. The data-parallel
        pump overrides this to add its per-worker stream sources."""
        return (self.source,)

    def quarantine_blocks(self, ids, *, reason: str = "io") -> int:
        """Drop blocks from the probe set (an I/O quarantine verdict —
        see `repro.io.faults.ResilientSource`). Returns how many blocks
        newly left the population.

        Already-read blocks are NOT quarantined: their tuples were
        validated at fetch time and already sit in the shared counts —
        the quarantine protects coverage accounting, not history.
        Every (eps, delta) derived after this call is over the
        surviving population; `eps_inflation` is the widened-L1 margin
        vs the full dataset that retirement folds into
        ``QueryOutcome.eps_effective``.
        """
        ids = np.asarray(ids, np.int64).ravel()
        if ids.size:
            ids = ids[~self.quarantined[ids] & ~self.read_mask[ids]]
        if ids.size == 0:
            return 0
        self.quarantined[ids] = True
        tuples = int(np.sum(np.asarray(self.source.tuples_per_block, np.int64)[ids]))
        self.blocks_quarantined += int(ids.size)
        self.tuples_quarantined += tuples
        if self.telemetry is not None:
            self._c_quarantined.inc(int(ids.size))
            self.telemetry.tracer.emit(
                "blocks_quarantine", blocks=int(ids.size), tuples=tuples,
                reason=reason, total_blocks=self.blocks_quarantined,
                population_frac=self.quarantine_fraction,
            )
        return int(ids.size)

    def _drain_quarantine(self) -> None:
        """Pull quarantined block ids out of every `ResilientSource` in
        the source chains (rides the poll boundary: fault-free this is
        a handful of attribute probes, no device work)."""
        for src in self._quarantine_sources():
            resilient = find_resilient(src)
            if resilient is not None:
                ids = resilient.take_quarantined()
                if ids.size:
                    self.quarantine_blocks(ids, reason="source")

    @property
    def quarantine_fraction(self) -> float:
        """Fraction of the dataset's TUPLES lost to quarantine (the q
        in the eps + 2q widened bound)."""
        return self.tuples_quarantined / max(self.total_tuples, 1)

    @property
    def eps_inflation(self) -> float:
        """Additive L1 widening vs the full dataset: dropping a
        content-independent tuple fraction q (the layout pre-shuffle
        assigns tuples to blocks independently of content) changes any
        candidate's normalized histogram by at most 2q in L1, so a
        query guaranteed eps over the survivors is guaranteed
        eps + 2q over the full data."""
        return 2.0 * self.quarantine_fraction

    # -- host/device synchronisation --------------------------------------

    def _sync(self) -> None:
        """One batched device->host poll: cursor + per-slot bounds.

        Everything the host loop decides on (termination, budget, pass
        structure, counters) is refreshed here and ONLY here, so
        `host_syncs` is an exact count of device↔host round-trips the
        loop performs, and `host_sync_bytes` of what they copied to the
        host (the ``nbytes`` of every polled array). Retirement snapshots (`retire`) transfer result
        data per retired query and are not part of the loop cadence.
        """
        # ONE batched poll. Beyond the cursor + bounds the host loop
        # decides on, the per-slot tau/n/in_top_k/pruned leaves feed the
        # anytime `peek` assembly and the confidence-trajectory points —
        # pure reads riding the same transfer, so device state and the
        # dispatch sequence are untouched whether or not anyone polls.
        with span("sync"):
            polled = jax.device_get(
                (self.cursor, self.state.delta_upper, self.state.tau,
                 self.state.n, self.state.in_top_k, self.state.pruned)
            )
        cursor, delta_upper, tau, n, in_top_k, pruned = polled
        self.host_sync_bytes += sum(leaf.nbytes for leaf in jax.tree.leaves(polled))
        self._tel_tau = np.asarray(tau)
        self._tel_n = np.asarray(n)
        self._in_top_k_host = np.asarray(in_top_k)
        self._pruned_host = np.asarray(pruned)
        self.read_mask = np.asarray(cursor.read_mask)
        self.rounds = int(cursor.rounds)
        self.blocks_read = int(cursor.blocks_read)
        self.blocks_considered = int(cursor.blocks_considered)
        self.tuples_read = int(cursor.tuples_read)
        self._delta_upper = np.asarray(delta_upper)
        self.host_syncs += 1
        self._drain_quarantine()
        if self.telemetry is not None:
            self._record_poll()

    def _record_poll(self) -> None:
        """Stage this poll's mirrors for telemetry (called from `_sync`
        only). Deliberately minimal — counter deltas into plain ints and
        one tuple of array refs into the poll buffer (`_sync` rebinds
        fresh arrays each poll, so refs are stable snapshots); all
        shaping happens batched in `flush_telemetry`."""
        last = self._tel_last
        p = self._tel_pending
        p["syncs"] += 1
        p["rounds"] += self.rounds - last["rounds"]
        p["blocks"] += self.blocks_read - last["blocks"]
        p["considered"] += self.blocks_considered - last["considered"]
        p["tuples"] += self.tuples_read - last["tuples"]
        p["passes"] += self.passes - last["passes"]
        last.update(rounds=self.rounds, blocks=self.blocks_read,
                    considered=self.blocks_considered,
                    tuples=self.tuples_read, passes=self.passes)
        if self.tickets:
            # The entry carries its own snapshot of the live ticket set
            # (shallow copy — tickets are immutable after admit), so
            # admit/retire never need to drain the buffer: each staged
            # poll is shaped under the set that was live when it was
            # sampled, no matter when the flush runs.
            self._poll_buf.append(
                (self.rounds, self.tuples_read, self._tel_n,
                 self._tel_tau, self._delta_upper, list(self.tickets.items()))
            )
            if len(self._poll_buf) >= 256:
                self.flush_telemetry()  # bound buffer memory on long pumps

    def flush_telemetry(self) -> None:
        """Drain staged polls into the registry and the per-query
        trajectories.

        Each buffered poll carries its own snapshot of the then-live
        ticket set, so the flush needs no relationship to admit/retire
        boundaries: it runs at pump() exit, when the buffer hits its
        memory bound, and lazily from `Telemetry`'s read accessors —
        large warm batches instead of per-poll (or per-boundary)
        shaping on the serve loop's cache-cold path.
        """
        tel = self.telemetry
        if tel is None:
            return
        p = self._tel_pending
        if p["syncs"]:
            self._c_syncs.inc(p["syncs"])
            self._c_rounds.inc(p["rounds"])
            self._c_blocks.inc(p["blocks"])
            self._c_considered.inc(p["considered"])
            self._c_tuples.inc(p["tuples"])
            self._c_passes.inc(p["passes"])
            for key in p:
                p[key] = 0
        buf = self._poll_buf
        if not buf:
            return
        self._poll_buf = []
        # vectorize the per-poll reductions across the whole batch
        n_mins = np.stack([b[2] for b in buf]).min(axis=1)  # (P,)
        tau_mins = np.stack([b[3] for b in buf]).min(axis=2)  # (P, Q)
        v_z, v_x = self.spec.v_z, self.spec.v_x
        for i, (rounds, tuples, _n, _tau, du, live) in enumerate(buf):
            n_min = float(n_mins[i])
            for slot, t in live:
                d_up = float(du[slot])
                tel.record_curve_point(t.qid, dict(
                    round=rounds,
                    tuples=tuples,
                    tuples_live=tuples - t.admit_tuples_read,
                    n_min=n_min,
                    tau_min=float(tau_mins[i, slot]),
                    # eps(n) at the per-candidate failure budget
                    # delta/|V_Z| — the AnyActive threshold the stats
                    # tail compares against.
                    eps_n=_metric_eps_np(
                        n_min, t.delta / v_z, v_x, self.spec.metric),
                    delta_upper=d_up,
                    confidence=max(0.0, 1.0 - d_up),
                ))

    def _emit_round_batch(self, windows: int) -> None:
        """One ``round_batch`` trace event per loop poll: the windows
        dispatched since the previous one and the polled totals (their
        host time is in the profiler trace's spans)."""
        if self.telemetry is not None:
            self.telemetry.tracer.emit(
                "round_batch", windows=windows, rounds=self.rounds,
                blocks_read=self.blocks_read, blocks_considered=self.blocks_considered,
                tuples_read=self.tuples_read,
            )

    # -- warm-start persistence --------------------------------------------

    def export_cache(self) -> CacheSnapshot:
        """Snapshot the durable (target-independent) serving state.

        Consistent by construction at any time: counts and cursor are
        both outputs of the same fused dispatch, and the host handles
        here always point at the LATEST dispatched round — so even with
        ``poll_every > 1`` a snapshot never interleaves a round's counts
        with a different round's read_mask. Live query slots are not
        exported (see `CacheSnapshot`).
        """
        return CacheSnapshot(
            counts=self.state.counts,
            n=self.state.n,
            read_mask=self._global_read_mask(),
            blocks_read=self.cursor.blocks_read,
            blocks_considered=self.cursor.blocks_considered,
            tuples_read=self.cursor.tuples_read,
            rounds=self.cursor.rounds,
            passes=jnp.asarray(self.passes, jnp.int32),
            start=jnp.asarray(self._start, jnp.int32),
        )

    def import_cache(self, snap: CacheSnapshot) -> None:
        """Adopt a restored warm cache: shared counts + sampling cursor +
        pass/visit-order bookkeeping.

        Must run before any admission — importing under live tickets
        would invalidate their admission-time counter snapshots, so that
        is refused. Counts/n are placed with the scheduler's existing
        sharding (the candidate-sharded placement when constructed with
        ``mesh=``); cursor leaves are re-materialized host-side so their
        placement matches a freshly constructed scheduler's.
        """
        if self.tickets:
            raise RuntimeError("import_cache requires a scheduler with no live queries")
        nb = self.source.num_blocks
        counts = jnp.asarray(snap.counts)
        if counts.shape != (self.spec.v_z, self.spec.v_x):
            raise ValueError(
                f"snapshot counts shape {counts.shape} != "
                f"{(self.spec.v_z, self.spec.v_x)} — wrong dataset/spec for this cache"
            )
        read_mask, blocks_read, blocks_considered, tuples_read, rounds, passes, start = (
            jax.device_get(
                (snap.read_mask, snap.blocks_read, snap.blocks_considered,
                 snap.tuples_read, snap.rounds, snap.passes, snap.start)
            )
        )
        read_mask = np.asarray(read_mask, bool)
        if read_mask.shape != (nb,):
            raise ValueError(
                f"snapshot read_mask covers {read_mask.shape[0]} blocks, "
                f"dataset has {nb} — wrong layout for this cache"
            )
        self.state = self.state._replace(
            counts=jax.device_put(counts.astype(jnp.float32), self.state.counts.sharding),
            n=jax.device_put(jnp.asarray(snap.n, jnp.float32), self.state.n.sharding),
        )
        self.cursor = self._place_cursor(SampleCursor(
            read_mask=jnp.asarray(read_mask),
            blocks_read=jnp.asarray(blocks_read, jnp.int32),
            blocks_considered=jnp.asarray(blocks_considered, jnp.int32),
            tuples_read=jnp.asarray(tuples_read, jnp.int32),
            rounds=jnp.asarray(rounds, jnp.int32),
        ))
        self._start = int(start)
        self.order = np.roll(np.arange(nb), -self._start)
        self.passes = int(passes)
        self._sync()  # refresh every host mirror from the restored cursor

    # -- admission / retirement -------------------------------------------

    @property
    def free_slots(self) -> list:
        return [s for s in range(self.spec.max_queries) if s not in self.tickets]

    @property
    def num_live(self) -> int:
        return len(self.tickets)

    def admit(
        self,
        target: np.ndarray,
        *,
        k: int,
        eps: float,
        delta: float,
        qtype: str = "topk",
        gap: float = 0.0,
        stop: Optional[StopPolicy] = None,
    ) -> int:
        """Place a query into a free slot; returns its qid.

        ``stop`` attaches an SLA `StopPolicy` (None inherits
        ``spec.default_stop``; pass a policy explicitly to override
        per query). Stop criteria are evaluated at poll boundaries,
        after the statistical rule.

        The immediate `stats_step` makes the query see the accumulated
        shared counts — with its full shared ``n_i`` — before the next
        window is marked, so a late query never starts from zero.
        Admission is a poll boundary (the ticket snapshots counters).

        ``qtype="closeness"`` admits a tolerant closeness test sharing
        the same counts matrix: every candidate within ``eps`` of the
        target (in the spec's metric) is labeled close, every one beyond
        ``eps + gap`` far, w.p. >= 1 - delta; inside the gap either
        label is allowed. ``k`` is ignored for closeness slots (pass 1).
        Mixing types triggers no recompilation — the type is a traced
        per-slot field.
        """
        free = self.free_slots
        if not free:
            raise RuntimeError("no free query slot; retire a query first")
        if qtype not in ("topk", "closeness"):
            raise ValueError(f"qtype must be 'topk' or 'closeness', got {qtype!r}")
        if qtype == "closeness":
            if not gap > 0.0:
                raise ValueError(f"closeness needs gap > 0, got gap={gap}")
            if not eps >= 0.0:
                raise ValueError(f"closeness needs eps >= 0, got eps={eps}")
        else:
            if gap != 0.0:
                raise ValueError("gap is only meaningful for qtype='closeness'")
            if not (0 < k <= self.spec.v_z):
                raise ValueError(f"need 0 < k <= V_Z, got k={k}")
            if self.spec.k_cap is not None and k > self.spec.k_cap:
                raise ValueError(f"k={k} exceeds spec.k_cap={self.spec.k_cap}")
        slot = free[0]
        target = np.asarray(target, np.float64).ravel()
        if target.shape != (self.spec.v_x,):
            raise ValueError(f"target must have shape ({self.spec.v_x},)")
        q_hat = (target / max(target.sum(), 1e-30)).astype(np.float32)
        qid = self._next_qid
        with span("admit", qid=qid):
            self.state = admit_slot(
                self.state,
                jnp.asarray(slot, jnp.int32),
                jnp.asarray(q_hat),
                jnp.asarray(k, jnp.int32),
                jnp.asarray(eps, jnp.float32),
                jnp.asarray(delta, jnp.float32),
                spec=self.spec,
                qtype=jnp.asarray(
                    QTYPE_CLOSENESS if qtype == "closeness" else QTYPE_TOPK,
                    jnp.int32,
                ),
                gap=jnp.asarray(gap, jnp.float32),
            )
            self.state = self._rounds.stats(self.state)
            self._sync()  # fresh counters for the ticket + fresh delta_upper
        self._next_qid += 1
        self.tickets[slot] = _Ticket(
            qid=qid,
            slot=slot,
            k=int(k),
            eps=float(eps),
            delta=float(delta),
            qtype=qtype,
            gap=float(gap),
            admit_time=time.perf_counter(),
            admit_rounds=self.rounds,
            admit_passes=self.passes,
            admit_blocks_read=self.blocks_read,
            admit_blocks_considered=self.blocks_considered,
            admit_tuples_read=self.tuples_read,
            stop=stop if stop is not None else self.spec.default_stop,
        )
        if self.telemetry is not None:
            self._c_admitted.inc(1)
            self.telemetry.tracer.emit(
                "query_admit", qid=qid, slot=slot, k=int(k), eps=float(eps),
                delta=float(delta), qtype=qtype, gap=float(gap),
                round=self.rounds, tuples=self.tuples_read,
            )
            # The ticket didn't exist yet when admission's _sync polled
            # (its buffer entry's snapshot predates the insert) — stage
            # a first point (possibly already terminal on the warm
            # cache) from those same fresh mirrors, shaped later with
            # the rest of the buffer.
            self._poll_buf.append(
                (self.rounds, self.tuples_read, self._tel_n,
                 self._tel_tau, self._delta_upper,
                 [(slot, self.tickets[slot])])
            )
        return qid

    def peek(self, slot: int) -> AnytimeAnswer:
        """The current anytime answer for a LIVE slot, assembled purely
        from the last-polled host mirrors — no device work, no
        dispatch, so polling between rounds never perturbs the loop.

        Selection and margins mirror the device statistics in f32 with
        the device's exact tie rule (np stable argsort ascending ==
        `lax.top_k(-tau)`: equal values lower-index first) and the
        device's exact operation association, so at a poll boundary the
        assembled set is bit-identical to what retirement would report.
        `retire` itself calls this with the same fresh mirrors — a
        stopped query's final answer IS the poll at its stopping round.
        """
        t = self.tickets[slot]
        tau = self._tel_tau[slot]
        du = float(self._delta_upper[slot])
        eps32 = np.float32(t.eps)
        if t.qtype == "closeness":
            close = np.flatnonzero(self._in_top_k_host[slot])
            ids = close[np.argsort(tau[close], kind="stable")]
            gap32 = np.float32(t.gap)
            split32 = eps32 + np.float32(0.5) * gap32
            sel = tau[ids]
            margin = np.maximum(
                np.maximum(sel - eps32, (eps32 + gap32) - sel), np.float32(0.0)
            )
        else:
            order = np.argsort(tau, kind="stable")
            ids = order[: t.k].copy()
            if t.k >= self.spec.v_z:
                split32 = np.float32(tau.max())
            else:
                split32 = np.float32(0.5) * (tau[order[t.k - 1]] + tau[order[t.k]])
            sel = tau[ids]
            margin = np.maximum(
                np.minimum(eps32, (split32 + np.float32(0.5) * eps32) - sel),
                np.float32(0.0),
            )
        n_min = float(self._tel_n.min())
        return AnytimeAnswer(
            qid=t.qid,
            qtype=t.qtype,
            status="live",
            ids=ids,
            tau=sel.copy(),
            margin=margin,
            split=float(split32),
            n_min=n_min,
            tau_min=float(tau.min()),
            eps_n=_metric_eps_np(
                n_min, t.delta / self.spec.v_z, self.spec.v_x, self.spec.metric
            ),
            delta_upper=du,
            confidence=max(0.0, 1.0 - du),
            round=self.rounds,
            tuples=self.tuples_read,
            tuples_live=self.tuples_read - t.admit_tuples_read,
            eps=t.eps,
            delta=t.delta,
            metric=self.spec.metric,
        )

    def retire(
        self,
        slot: int,
        *,
        exact: bool,
        terminated: bool,
        stopped: bool = False,
        stop_reason: str = "",
    ) -> QueryOutcome:
        """Snapshot a slot's answer, free the slot, record the outcome.

        ``exact`` is forced True whenever the whole surviving population
        has been read — the answer then rests on a complete read no
        matter why the query is retiring (MatchResult.exact's contract;
        with quarantined blocks "complete" means complete over the
        survivors and the outcome says so via ``degraded``). Callers
        must be at a poll boundary (mirrors fresh, i.e. after `_sync`).

        ``stopped``/``stop_reason`` record an SLA early stop (StopPolicy
        or supervisor deadline); the outcome then carries the honest
        anytime statement of that poll.
        """
        with span("retire", qid=self.tickets[slot].qid):
            anytime = self.peek(slot)
            t = self.tickets.pop(slot)
            degraded = self.blocks_quarantined > 0
            if degraded:
                exact = exact or bool(self.read_mask[~self.quarantined].all())
            else:
                exact = exact or bool(self.read_mask.all())
            view = slot_state(self.state, slot)
            if t.qtype == "closeness":
                # The close set, nearest first — in_top_k holds the close
                # labels for closeness slots (`dev.assign_closeness`); its
                # size is data-dependent, not k.
                close = np.flatnonzero(np.asarray(view.in_top_k))
                order = np.argsort(
                    np.asarray(view.tau)[close], kind="stable"
                )
                ids = close[order]
            else:
                ids = np.asarray(histsim.top_k_ids(view, t.k))
            # A query admitted and retired inside one running pass still
            # saw sampling activity — count that partial pass; a query that
            # retired before any window ran while it was live saw none.
            passes = self.passes - t.admit_passes
            if passes == 0 and self.rounds > t.admit_rounds:
                passes = 1
            outcome = QueryOutcome(
                qid=t.qid,
                ids=ids,
                state=view,
                delta_upper=float(view.delta_upper),
                exact=exact,
                terminated=terminated,
                rounds=self.rounds - t.admit_rounds,
                passes=passes,
                blocks_read=self.blocks_read - t.admit_blocks_read,
                blocks_considered=self.blocks_considered - t.admit_blocks_considered,
                tuples_read=self.tuples_read - t.admit_tuples_read,
                wall_time_s=time.perf_counter() - t.admit_time,
                degraded=degraded,
                eps_effective=t.eps + (self.eps_inflation if degraded else 0.0),
                blocks_quarantined=self.blocks_quarantined,
                qtype=t.qtype,
                stopped=stopped,
                stop_reason=stop_reason,
                anytime=anytime,
            )
            anytime.status = "done"
            anytime.exact = outcome.exact
            anytime.stopped = stopped
            anytime.stop_reason = stop_reason
            self.state = clear_slot(self.state, jnp.asarray(slot, jnp.int32), spec=self.spec)
            self.outcomes[t.qid] = outcome
        if self.telemetry is not None:
            self._c_retired.inc(1)
            self._h_q_tuples.observe(outcome.tuples_read)
            self._h_q_rounds.observe(outcome.rounds)
            self._h_q_wall.observe(outcome.wall_time_s)
            self.telemetry.tracer.emit(
                "query_retire", qid=t.qid, slot=slot, exact=outcome.exact,
                terminated=outcome.terminated, rounds=outcome.rounds,
                passes=outcome.passes, blocks=outcome.blocks_read,
                tuples=outcome.tuples_read,
                delta_upper=outcome.delta_upper, wall_s=outcome.wall_time_s,
                stopped=outcome.stopped, stop_reason=outcome.stop_reason,
            )
        return outcome

    def _poll_terminated(self) -> None:
        """Retire every live query whose termination bound has fired
        (judged on the last-polled bounds — call after `_sync`), then
        every one whose SLA StopPolicy fires. The statistical rule is
        checked FIRST, so a query that converges at the same poll its
        SLA would trip returns the normal terminated answer."""
        if not self.tickets:
            return
        du = self._delta_upper
        now = time.perf_counter()
        for slot in list(self.tickets):
            t = self.tickets[slot]
            if du[slot] < t.delta:
                self.retire(slot, exact=False, terminated=True)
                continue
            if t.stop is None:
                continue
            reason = t.stop.fired(
                wall_s=now - t.admit_time,
                confidence=max(0.0, 1.0 - float(du[slot])),
                tuples=self.tuples_read - t.admit_tuples_read,
            )
            if reason:
                self.retire(
                    slot, exact=False, terminated=False,
                    stopped=True, stop_reason=reason,
                )

    # -- the loop ----------------------------------------------------------

    def _open_pass_stream(self, pass_order: np.ndarray) -> tuple:
        """(round stream, number of rounds) for one pass over
        ``pass_order``. The base scheduler chunks the global visit
        order into lookahead windows served by its single source; the
        data-parallel pump overrides this to zip one shard-local window
        stream per worker. The returned stream must support .close()."""
        windows = [
            pass_order[p : p + self.window]
            for p in range(0, pass_order.size, self.window)
        ]
        return self.source.stream(windows, pad_to=self.window), len(windows)

    def _dispatch_round(self, wd: WindowData) -> None:
        """One fused sampling round over prepared window data (no host
        sync — polling is the loop's cadence decision)."""
        with span("dispatch"):
            self.state, self.cursor = self._rounds.round(self.state, self.cursor, wd)

    def _fetch_window(self, win: np.ndarray) -> WindowData:
        """Window data for one ad-hoc (global-id) window — the pump
        overrides this to split the window by block ownership and
        assemble the per-worker shards."""
        return self.source.fetch(win, pad_to=max(self.window, win.size))

    def _dispatch_ingest(self, wd: WindowData) -> None:
        """One exact-completion ingest round over prepared window data."""
        with span("dispatch"):
            self.state, self.cursor = self._rounds.ingest(self.state, self.cursor, wd)

    def run_window(self, win: np.ndarray) -> int:
        """Mark one lookahead window against the union active set and
        ingest the marked blocks; polls immediately (poll_every=1
        semantics — the incremental-serving unit `MatchServer.step`
        builds on). Returns the number of blocks read."""
        win = np.asarray(win)
        if win.size == 0:
            return 0
        before = self.blocks_read
        wd = self._fetch_window_or_quarantine(win)
        if wd is not None:
            self._dispatch_round(wd)
        self._sync()
        self._emit_round_batch(int(wd is not None))
        self.loop_syncs += 1
        return self.blocks_read - before

    def _fetch_window_or_quarantine(self, win: np.ndarray) -> Optional[WindowData]:
        """Fetch an ad-hoc window, converting a `WindowQuarantined`
        verdict into probe-set removal (None = the window is gone; the
        caller's next poll sees the degraded population)."""
        try:
            with span("fetch"):
                return self._fetch_window(win)
        except WindowQuarantined as exc:
            self.quarantine_blocks(exc.block_ids, reason="fetch")
            return None

    def complete_remaining(self) -> None:
        """Exact completion: read every unread block into the shared counts.

        Afterwards the empirical counts equal the true ones, so every
        answer drawn from them is exact and the guarantees hold
        deterministically. Counts as one pass (over the remainder) and
        one round per chunk — the Scan baseline in `engine.run_engine`
        is exactly this path on a fresh scheduler.
        """
        with span("complete"):
            self._sync()
            remaining = np.where(~self.read_mask & ~self.quarantined)[0]
            if remaining.size == 0:
                return
            self.passes += 1
            t0 = time.perf_counter()
            windows = 0
            stream, _ = self._open_pass_stream(remaining)
            try:
                for wd in spanned(stream, "fetch"):
                    self._dispatch_ingest(wd)
                    windows += 1
            finally:
                stream.close()
            self.state = self._rounds.stats(self.state)
            self._sync()
            if self.telemetry is not None:
                self.telemetry.tracer.emit(
                    "exact_completion", windows=windows, blocks=int(remaining.size),
                    rounds=self.rounds, tuples_read=self.tuples_read,
                    dur_s=time.perf_counter() - t0,
                )

    def pump(
        self,
        *,
        max_rounds: int = 1_000_000,
        max_passes: int = 4,
        on_round: Optional[Callable[["SharedCountsScheduler"], None]] = None,
    ) -> None:
        """Drive windows until every live query resolves.

        Dispatches `fused_round`s back-to-back through the source's
        `stream` (overlapped gathering with `PrefetchSource`) and only
        polls the device every ``poll_every`` windows; retirement,
        admission (via on_round) and the budget check happen at poll
        boundaries, so with ``poll_every > 1`` each may lag the device
        by up to ``poll_every - 1`` windows.

        on_round: called at each poll (post-retirement) — the serving
        frontend uses it to admit pending queries into slots freed
        mid-stream.

        max_rounds/max_passes budget THIS call, not the scheduler's
        lifetime: a long-lived server calling pump per batch gets the
        full budget every time.
        """
        self.budget_exhausted = False
        try:
            self._pump(max_rounds=max_rounds, max_passes=max_passes,
                       on_round=on_round)
        finally:
            # one batched drain per pump call — counters and curves are
            # current whenever the loop hands control back
            self.flush_telemetry()

    def _pump(
        self,
        *,
        max_rounds: int,
        max_passes: int,
        on_round: Optional[Callable[["SharedCountsScheduler"], None]],
    ) -> None:
        tel = self.telemetry
        self._sync()
        rounds0, passes0 = self.rounds, self.passes
        # A late-admitted query may already terminate on the accumulated
        # shared counts, before any new window is read.
        self._poll_terminated()
        while self.tickets and self.passes - passes0 < max_passes:
            with span("pass"):
                pass_order = self.order[
                    ~self.read_mask[self.order] & ~self.quarantined[self.order]
                ]
            if pass_order.size == 0:
                break
            self.passes += 1
            pass_start_rounds = self.rounds
            pass_start_blocks = self.blocks_read
            stream, n_rounds = self._open_pass_stream(pass_order)
            if tel is not None:
                tel.tracer.emit("pass_start", passes=self.passes,
                                windows=n_rounds, unread=int(pass_order.size))
            dispatched = polled = 0
            try:
                for dispatched, wd in enumerate(spanned(stream, "fetch"), start=1):
                    self._dispatch_round(wd)
                    if dispatched % self.poll_every == 0 or dispatched == n_rounds:
                        self._sync()
                        self._emit_round_batch(dispatched - polled)
                        polled = dispatched
                        self.loop_syncs += 1
                        self._poll_terminated()
                        if on_round is not None:
                            on_round(self)
                        if self.rounds - rounds0 >= max_rounds:
                            # Budget cut: live queries stay best-effort
                            # (the caller decides; no silent exact
                            # completion).
                            self.budget_exhausted = True
                            if tel is not None:
                                tel.tracer.emit(
                                    "budget_exhausted", rounds=self.rounds,
                                    live=len(self.tickets),
                                )
                            return
                        if not self.tickets:
                            break
            finally:
                stream.close()
            if dispatched == 0 or (
                dispatched % self.poll_every != 0 and dispatched != n_rounds
            ):
                # The stream ended short of the final scheduled poll —
                # only possible when a resilient source quarantined (and
                # skipped) trailing windows of the pass (fault-free, the
                # ``dispatched == n_rounds`` poll always fires). Without
                # this catch-up poll the zero-progress check below would
                # judge stale mirrors and the drained quarantine mask
                # would lag a pass behind.
                self._sync()
                self._emit_round_batch(dispatched - polled)
                self.loop_syncs += 1
                self._poll_terminated()
                if on_round is not None:
                    on_round(self)
            if self.blocks_read - pass_start_blocks == 0 and self.tickets:
                # "No unread block can help" was judged against the
                # active sets live DURING the pass — a query admitted in
                # its final windows deserves one fresh pass of its own
                # before we give up on sampling.
                fresh = any(
                    t.admit_rounds >= pass_start_rounds for t in self.tickets.values()
                )
                if not fresh:
                    break
        if self.tickets:
            # Exact fallback for the stragglers.
            self.complete_remaining()
            du = self._delta_upper
            for slot in list(self.tickets):
                fired = bool(du[slot] < self.tickets[slot].delta)
                self.retire(slot, exact=True, terminated=fired)
            if on_round is not None:
                on_round(self)
