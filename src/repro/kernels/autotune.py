"""Measurement-driven kernel plans: pick the wall-clock winner, not the
byte winner.

`BENCH_stats.json` exposed the gap this module closes: the Q-batched tau
kernel cuts HBM bytes 7.6x at Q=8 yet *loses* wall-clock to the
Q-unrolled path on XLA:CPU at Q>=4, and the fused ingest+rowsum pass
loses to the two-step form — the serving loop was hard-coded to the
theoretically-leanest variant instead of the measured-fastest one. Here
every dispatch decision the kernels package makes becomes a *plan*
looked up per shape key, and plans come from measurement:

  tau   — per ``(backend, V_Z, V_X, Q, dtype)``:
            * variant: "batched" (one counts pass scores all Q targets),
              "unrolled" (Q single-query passes — the PR-2 path), or
              "xla" (the fused 3D broadcast form, XLA's choice of
              schedule);
            * z_tile / x_tile Pallas tile sizes and the single- vs
              forced two-sweep V_X phase (TPU knobs; the CPU ref path
              has no tiling, so CPU plans keep the defaults);
            * lowprec: stream the counts matrix as uint16 (halving tau
              HBM traffic) behind a runtime overflow gate — the counts
              are integer-valued f32, and any entry above the uint16
              range falls back to the full-precision path via lax.cond,
              so results stay exact (an in-range uint16 round-trip of an
              integer-valued f32 is the identity).
  ingest — per ``(backend, V_Z, V_X, dtype)``: fused histogram+rowsums
           vs the two-step form (histogram, then a separate row
           reduction) — on the Pallas engine both reduce the rows from
           the reshaped counts, so they run the same kernel — plus the
           histogram kernel's s_tile / z_tile (z_tile tiles the packed
           rows of the flat z * V_X + x key).

Every candidate is bit-identical to the pre-autotune kernels on
integer-valued counts (enforced by tests/test_autotune.py, which sweeps
the full candidate space); the tuner is therefore free to pick purely
by measured wall time. Selection is noise-robust: the fastest candidate
wins only if it beats the "unrolled" (tau) / "fused" (ingest) reference
comparator by ``margin`` — otherwise the comparator is kept, so a
within-noise measurement can never flip the serving loop onto a variant
that merely tied.

Plan persistence (CI determinism):

  * `PlanRegistry` serializes to ``benchmarks/results/tuned/<backend>.json``
    — COMMITTED to the repo, so every CI run and every process dispatches
    from the same bytes instead of re-measuring on a noisy shared runner.
  * Lookups that miss the file fall back to `DEFAULT_TAU` /
    `DEFAULT_INGEST` (exactly the pre-autotune dispatch) silently; a
    stale schema, corrupt file, or malformed entry falls back with a
    ``warnings.warn`` — never a crash.
  * ``FASTMATCH_AUTOTUNE=1`` makes `resolve_plans` (the eager,
    scheduler-construction entry point) tune-on-miss and persist the
    result; ``FASTMATCH_PLANS_DIR`` points the registry somewhere else
    (tests use a tmpdir).
  * After changing the plan file on disk call `reload()`: it swaps the
    process registry AND clears the jax jit caches, because "auto" plan
    arguments are resolved at trace time and baked into compiled
    programs.

`repro.kernels.ops` routes `l1_distance_multi` / `histogram_with_rowsums`
through `run_tau` / `run_ingest`, and the three round-builders
(`multiquery.fused_round`, `distributed.make_distributed_round`,
`distributed.make_pump_round`) thread a `PlanPair` through, so the
serving loop, the explicit-collective mesh round, and the data-parallel
pump all run the measured-fastest configuration — and a real GPU/TPU
gets a correct tuned plan on first contact by committing its own
``<backend>.json`` instead of inheriting XLA:CPU's compromises.
"""

from __future__ import annotations

import dataclasses
import json
import os
import pathlib
import time
import warnings
from functools import partial
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import metrics, ref
from repro.kernels.histogram import (
    SAMPLE_TILE_MULTIPLE,
    histogram_pallas,
    histogram_with_rowsums_pallas,
)

# Single-block V_X bound of the Q=1 kernel the "unrolled" variant stacks.
_UNROLLED_MAX_VX = metrics.MAX_SINGLE_BLOCK_VX

__all__ = [
    "DEFAULT_INGEST",
    "DEFAULT_TAU",
    "IngestPlan",
    "PlanPair",
    "PlanRegistry",
    "TauPlan",
    "get_ingest_plan",
    "get_tau_plan",
    "ingest_key",
    "plan_path",
    "registry",
    "reload",
    "resolve_plans",
    "run_ingest",
    "run_tau",
    "tau_bytes",
    "tau_key",
    "tune_ingest",
    "tune_tau",
    "tau_candidates",
    "ingest_candidates",
]

# Schema 2: tau keys carry a ``metric`` field (the pluggable-metric
# layer tunes each distance separately — variant tradeoffs shift with
# the score's VPU cost). Schema-1 files warn-and-default on load.
PLAN_SCHEMA = 2
TAU_VARIANTS = ("batched", "unrolled", "xla")
# uint16 overflow gate for the low-precision counts path. 2**16 - 1;
# every integer-valued f32 at or below this round-trips exactly.
_U16_MAX = 65535.0
# A non-comparator candidate must beat the comparator by this fraction
# of wall time to be selected — measured deltas inside the margin are
# indistinguishable from run-to-run noise on a shared host.
DEFAULT_MARGIN = 0.07


# ---------------------------------------------------------------------------
# Plans
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TauPlan:
    """One tau (distance) dispatch decision. Hashable: jit-static."""

    variant: str = "batched"  # "batched" | "unrolled" | "xla"
    z_tile: int = 256  # Pallas candidate-row tile
    x_tile: int = metrics._X_TILE  # Pallas lane tile (single-sweep bound)
    sweeps: int = 0  # 0 = auto (by padded V_X), 1 = single, 2 = forced two-sweep
    lowprec: bool = False  # uint16 counts traffic behind the overflow gate

    def validate(self) -> None:
        if self.variant not in TAU_VARIANTS:
            raise ValueError(f"unknown tau variant {self.variant!r}; have {TAU_VARIANTS}")
        if self.z_tile < 8:
            raise ValueError(f"need z_tile >= 8, got {self.z_tile}")
        if self.x_tile % 128 != 0 or self.x_tile <= 0:
            raise ValueError(f"x_tile must be a positive lane multiple of 128, got {self.x_tile}")
        if self.sweeps not in (0, 1, 2):
            raise ValueError(f"sweeps must be 0 (auto), 1 or 2, got {self.sweeps}")


@dataclasses.dataclass(frozen=True)
class IngestPlan:
    """One ingest (histogram + row-sums) dispatch decision."""

    fused: bool = True  # one fused pass vs histogram + separate reduction
    s_tile: int = 2048  # Pallas sample tile, whole (8, 128) int32 tiles
    z_tile: int = 256  # Pallas tile of packed key rows (128 z * V_X + x keys a row)

    def validate(self) -> None:
        if self.s_tile <= 0 or self.s_tile % SAMPLE_TILE_MULTIPLE:
            raise ValueError(
                f"s_tile must be a positive multiple of {SAMPLE_TILE_MULTIPLE}, "
                f"got {self.s_tile}"
            )
        if self.z_tile < 8:
            raise ValueError(f"need z_tile >= 8, got {self.z_tile}")


@dataclasses.dataclass(frozen=True)
class PlanPair:
    """The (tau, ingest) pair one serving round consumes."""

    tau: TauPlan = dataclasses.field(default_factory=lambda: DEFAULT_TAU)
    ingest: IngestPlan = dataclasses.field(default_factory=lambda: DEFAULT_INGEST)


# The defaults reproduce the pre-autotune dispatch bit for bit: batched
# tau with the kernel's own tile constants, fused ingest.
DEFAULT_TAU = TauPlan()
DEFAULT_INGEST = IngestPlan()


def tau_key(v_z: int, v_x: int, q: int, dtype: str = "float32", metric: str = "l1") -> str:
    return f"vz={v_z},vx={v_x},q={q},dtype={dtype},metric={metric}"


def ingest_key(v_z: int, v_x: int, dtype: str = "float32") -> str:
    return f"vz={v_z},vx={v_x},dtype={dtype}"


def tau_bytes(v_z: int, v_x: int, q: int, plan: TauPlan, metric: str = "l1") -> int:
    """Analytic HBM bytes per tau round under ``plan`` (the roofline
    model `benchmarks/stats_throughput.py` reports), via the metric's
    registry ``bytes_model`` — every shipped metric streams identically
    (they differ in VPU flops only), so the model is shared.

    counts traffic: 1 pass (batched single-sweep / xla), 2 passes
    (batched forced- or auto- two-sweep), Q passes (unrolled); targets +
    output are Q * (V_X + V_Z) either way. lowprec halves the counts
    term (uint16 vs f32 is 2 bytes vs 4).
    """
    vx_pad = max(128, -(-v_x // 128) * 128)
    if plan.variant == "unrolled":
        passes = q
    elif plan.variant == "xla":
        passes = 1
    else:
        passes = 2 if plan.sweeps == 2 or (plan.sweeps == 0 and vx_pad > plan.x_tile) else 1
    return metrics.coerce_metric(metric).bytes_model(
        v_z, v_x, q, passes=passes, counts_itemsize=(2 if plan.lowprec else 4)
    )


# ---------------------------------------------------------------------------
# Executors — the ONLY code paths plans dispatch to; the tuner measures
# through these same functions, so "measured fastest" is "what runs".
# ---------------------------------------------------------------------------


def _tau_inner(plan: TauPlan, *, engine: str, interpret: bool,
               metric: str = "l1") -> Callable:
    """(counts, q_hat) -> (Q, V_Z) tau for one variant, full precision.

    Every branch normalizes in f32 with the exact elementwise sequence
    of `metrics.distance_ref` (row sum -> max(row, 1) divide -> score ->
    lane reduce), so on integer-valued counts all variants of one metric
    are bit-identical (tests/test_autotune.py and tests/test_metrics.py
    sweep the space).
    """
    if plan.variant == "xla":
        return partial(metrics.distance_multi_xla, metric=metric)
    if engine == "pallas":
        if plan.variant == "unrolled":
            def unrolled_pallas(counts, q_hat):
                return jnp.stack([
                    metrics.distance_pallas(
                        counts, q_hat[i], metric=metric,
                        z_tile=plan.z_tile, interpret=interpret,
                    )
                    for i in range(q_hat.shape[0])
                ])
            return unrolled_pallas
        return partial(
            metrics.distance_multi_pallas,
            metric=metric,
            z_tile=plan.z_tile,
            x_tile=plan.x_tile,
            sweeps=plan.sweeps,
            interpret=interpret,
        )
    if plan.variant == "unrolled":
        def unrolled_ref(counts, q_hat):
            return jnp.stack([
                metrics.distance_ref(counts, q_hat[i], metric=metric)
                for i in range(q_hat.shape[0])
            ])
        return unrolled_ref
    return partial(metrics.distance_multi_ref, metric=metric)


def _tau_usable(plan: TauPlan, *, engine: str, v_x: int) -> bool:
    """Whether ``plan`` can run at all for this engine/shape (the
    single-query Pallas kernel rejects V_X past one VMEM block, and
    Mosaic has no uint16 -> f32 cast, so the Pallas kernels take no
    lowprec counts)."""
    if engine == "pallas" and plan.variant == "unrolled" and v_x > _UNROLLED_MAX_VX:
        return False
    if engine == "pallas" and plan.variant != "xla" and plan.lowprec:
        return False
    if plan.sweeps == 1 and max(128, -(-v_x // 128) * 128) > plan.x_tile:
        return False  # forced single-sweep cannot cover a lane-tiled V_X
    return True


def run_tau(
    counts: jax.Array,
    q_hat: jax.Array,
    *,
    plan: TauPlan,
    engine: str,
    interpret: bool = False,
    metric: str = "l1",
) -> jax.Array:
    """Dispatch one (Q, V_Z) tau computation per ``plan`` and ``metric``.

    An unusable plan (e.g. a TPU-tuned unrolled plan hitting a
    lane-tiled V_X) falls back to `DEFAULT_TAU` with a warning — plans
    steer performance, never correctness or availability. The metric is
    orthogonal to the plan: every variant runs every registry metric
    (the lowprec uint16 counts gate below is metric-agnostic too — all
    kernels upcast to f32 before normalizing).
    """
    plan.validate()
    if not _tau_usable(plan, engine=engine, v_x=counts.shape[1]):
        _warn_once(
            f"tau plan {plan} unusable for engine={engine} "
            f"V_X={counts.shape[1]}; falling back to defaults"
        )
        plan = DEFAULT_TAU
    inner = _tau_inner(plan, engine=engine, interpret=interpret, metric=metric)
    if not plan.lowprec:
        return inner(counts, q_hat)
    # uint16 overflow gate: in-range integer-valued f32 counts stream as
    # uint16 (the kernels upcast per tile, so the halved traffic is
    # real); any entry past the uint16 range takes the full-precision
    # branch — exactness is never data-dependent.
    fits = jnp.max(counts) <= _U16_MAX
    return jax.lax.cond(
        fits,
        lambda c: inner(c.astype(jnp.uint16), q_hat),
        lambda c: inner(c, q_hat),
        counts,
    )


def run_ingest(
    z_idx: jax.Array,
    x_idx: jax.Array,
    *,
    v_z: int,
    v_x: int,
    plan: IngestPlan,
    engine: str,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Dispatch one ((V_Z, V_X), (V_Z,)) histogram + row-sums pass.

    fused=True is the one-pass form; fused=False is the two-step
    (histogram, then a separate row reduction). On the Pallas engine both
    run one kernel and reduce the rows from its reshaped counts. Both are
    exact on integer counts, so the plan is free to pick the
    measured-fastest form.
    """
    plan.validate()
    if engine == "pallas":
        if plan.fused:
            return histogram_with_rowsums_pallas(
                z_idx, x_idx, v_z=v_z, v_x=v_x,
                s_tile=plan.s_tile, z_tile=plan.z_tile, interpret=interpret,
            )
        counts = histogram_pallas(
            z_idx, x_idx, v_z=v_z, v_x=v_x,
            s_tile=plan.s_tile, z_tile=plan.z_tile, interpret=interpret,
        )
        return counts, jnp.sum(counts, axis=1)
    if plan.fused:
        return ref.histogram_with_rowsums_ref(z_idx, x_idx, v_z=v_z, v_x=v_x)
    counts = ref.histogram_ref(z_idx, x_idx, v_z=v_z, v_x=v_x)
    return counts, jnp.sum(counts, axis=1)


_warned: set = set()


def _warn_once(msg: str) -> None:
    if msg not in _warned:
        _warned.add(msg)
        warnings.warn(msg, stacklevel=3)


# ---------------------------------------------------------------------------
# Registry: the committed JSON artifact
# ---------------------------------------------------------------------------


def plans_dir() -> pathlib.Path:
    """``FASTMATCH_PLANS_DIR`` or the committed repo location."""
    env = os.environ.get("FASTMATCH_PLANS_DIR")
    if env:
        return pathlib.Path(env)
    # src/repro/kernels/autotune.py -> repo root / benchmarks/results/tuned
    return pathlib.Path(__file__).resolve().parents[3] / "benchmarks" / "results" / "tuned"


def plan_path(backend: Optional[str] = None) -> pathlib.Path:
    backend = backend or jax.default_backend()
    return plans_dir() / f"{backend}.json"


def _plan_from_entry(entry: dict, cls):
    fields = {f.name for f in dataclasses.fields(cls)}
    plan = cls(**{k: v for k, v in entry.items() if k in fields})
    plan.validate()
    return plan


class PlanRegistry:
    """All tuned plans for one backend, plus their provenance.

    Lookup misses return the defaults silently (an untuned shape is
    normal); structural problems — stale schema, corrupt JSON, a
    malformed entry — fall back with a warning, never an exception, so
    a bad plan file can degrade dispatch but not availability.
    """

    def __init__(self, backend: Optional[str] = None):
        self.backend = backend or jax.default_backend()
        self.tau: Dict[str, TauPlan] = {}
        self.ingest: Dict[str, IngestPlan] = {}
        self.meta: dict = {}
        self.path: Optional[pathlib.Path] = None

    # -- persistence -------------------------------------------------------

    @classmethod
    def load(cls, path: Optional[pathlib.Path] = None, backend: Optional[str] = None
             ) -> "PlanRegistry":
        reg = cls(backend=backend)
        reg.path = pathlib.Path(path) if path is not None else plan_path(reg.backend)
        if not reg.path.exists():
            return reg
        try:
            doc = json.loads(reg.path.read_text())
        except (OSError, json.JSONDecodeError) as e:
            _warn_once(f"unreadable kernel-plan file {reg.path}: {e}; using default plans")
            return reg
        if not isinstance(doc, dict) or doc.get("schema") != PLAN_SCHEMA:
            _warn_once(
                f"kernel-plan file {reg.path} has schema "
                f"{doc.get('schema') if isinstance(doc, dict) else '<not a dict>'!r}, "
                f"expected {PLAN_SCHEMA}; using default plans"
            )
            return reg
        if doc.get("backend") not in (None, reg.backend):
            _warn_once(
                f"kernel-plan file {reg.path} was tuned for backend "
                f"{doc.get('backend')!r}, running on {reg.backend!r}; using default plans"
            )
            return reg
        reg.meta = {k: v for k, v in doc.items() if k not in ("tau", "ingest")}
        for key, entry in (doc.get("tau") or {}).items():
            try:
                reg.tau[key] = _plan_from_entry(entry, TauPlan)
            except (TypeError, ValueError) as e:
                _warn_once(f"dropping malformed tau plan {key!r} in {reg.path}: {e}")
        for key, entry in (doc.get("ingest") or {}).items():
            try:
                reg.ingest[key] = _plan_from_entry(entry, IngestPlan)
            except (TypeError, ValueError) as e:
                _warn_once(f"dropping malformed ingest plan {key!r} in {reg.path}: {e}")
        return reg

    def save(self, path: Optional[pathlib.Path] = None) -> pathlib.Path:
        path = pathlib.Path(path) if path is not None else (self.path or plan_path(self.backend))
        path.parent.mkdir(parents=True, exist_ok=True)
        doc = dict(schema=PLAN_SCHEMA, backend=self.backend, **{
            k: v for k, v in self.meta.items() if k not in ("schema", "backend")
        })
        doc["tau"] = {k: dataclasses.asdict(v) for k, v in sorted(self.tau.items())}
        doc["ingest"] = {k: dataclasses.asdict(v) for k, v in sorted(self.ingest.items())}
        path.write_text(json.dumps(doc, indent=2, sort_keys=False) + "\n")
        self.path = path
        return path

    # -- lookup ------------------------------------------------------------

    def tau_plan(self, v_z: int, v_x: int, q: int, dtype: str = "float32",
                 metric: str = "l1") -> TauPlan:
        return self.tau.get(tau_key(v_z, v_x, q, dtype, metric), DEFAULT_TAU)

    def ingest_plan(self, v_z: int, v_x: int, dtype: str = "float32") -> IngestPlan:
        return self.ingest.get(ingest_key(v_z, v_x, dtype), DEFAULT_INGEST)

    def decisions(self) -> str:
        """Canonical serialization of every dispatch decision this
        registry would make — the byte-stable artifact the determinism
        tests compare across loads and processes (timing metadata is
        deliberately NOT part of it)."""
        return json.dumps(
            dict(
                backend=self.backend,
                tau={k: dataclasses.asdict(v) for k, v in sorted(self.tau.items())},
                ingest={k: dataclasses.asdict(v) for k, v in sorted(self.ingest.items())},
            ),
            sort_keys=True,
        )


_registry: Optional[PlanRegistry] = None


def registry() -> PlanRegistry:
    """The process-wide plan registry, loaded lazily from `plan_path()`."""
    global _registry
    if _registry is None:
        _registry = PlanRegistry.load()
    return _registry


def reload(path: Optional[pathlib.Path] = None, backend: Optional[str] = None) -> PlanRegistry:
    """Swap the process registry for a fresh load AND clear the jax jit
    caches: "auto" plan lookups happen at trace time, so compiled
    programs hold the plans that were loaded when they were traced."""
    global _registry
    _registry = PlanRegistry.load(path=path, backend=backend)
    jax.clear_caches()
    return _registry


def get_tau_plan(v_z: int, v_x: int, q: int, dtype: str = "float32",
                 metric: str = "l1") -> TauPlan:
    return registry().tau_plan(v_z, v_x, q, dtype, metric)


def get_ingest_plan(v_z: int, v_x: int, dtype: str = "float32") -> IngestPlan:
    return registry().ingest_plan(v_z, v_x, dtype)


def coerce_tau_plan(plan, v_z: int, v_x: int, q: int, metric: str = "l1") -> TauPlan:
    """Resolve an ops-level ``plan`` argument: "auto" consults the
    registry (at trace time — shapes are concrete there), None/"default"
    pins the pre-autotune dispatch, a `TauPlan` passes through."""
    if plan == "auto":
        return get_tau_plan(v_z, v_x, q, metric=metric)
    if plan is None or plan == "default":
        return DEFAULT_TAU
    if isinstance(plan, TauPlan):
        return plan
    raise TypeError(f"plan must be 'auto', 'default', None or TauPlan, got {plan!r}")


def coerce_ingest_plan(plan, v_z: int, v_x: int) -> IngestPlan:
    if plan == "auto":
        return get_ingest_plan(v_z, v_x)
    if plan is None or plan == "default":
        return DEFAULT_INGEST
    if isinstance(plan, IngestPlan):
        return plan
    raise TypeError(f"plan must be 'auto', 'default', None or IngestPlan, got {plan!r}")


def resolve_plans(
    v_z: int,
    v_x: int,
    q: int,
    *,
    n_samples: Optional[int] = None,
    dtype: str = "float32",
    metric: str = "l1",
) -> PlanPair:
    """The eager (host-side) plan resolution the round-builders use at
    construction: registry lookup, with ``FASTMATCH_AUTOTUNE=1``
    additionally tuning any missing key on the spot and persisting the
    result. Never called at trace time, so tune-on-miss may freely run
    device code. Tau keys are per-metric (the score shifts the
    variant tradeoff); the ingest plan is metric-independent (counts
    are shared by every metric and query type)."""
    reg = registry()
    tkey, ikey = tau_key(v_z, v_x, q, dtype, metric), ingest_key(v_z, v_x, dtype)
    if os.environ.get("FASTMATCH_AUTOTUNE") == "1":
        dirty = False
        if tkey not in reg.tau:
            reg.tau[tkey], _ = tune_tau(v_z, v_x, q, metric=metric)
            dirty = True
        if ikey not in reg.ingest:
            reg.ingest[ikey], _ = tune_ingest(
                v_z, v_x, n_samples=n_samples or _default_ingest_samples(v_z, v_x)
            )
            dirty = True
        if dirty:
            reg.save()
    return PlanPair(tau=reg.tau.get(tkey, DEFAULT_TAU), ingest=reg.ingest.get(ikey, DEFAULT_INGEST))


# ---------------------------------------------------------------------------
# The tuner
# ---------------------------------------------------------------------------


def _default_ingest_samples(v_z: int, v_x: int) -> int:
    # lookahead-window-sized batches dominate production ingest; scale
    # with the matrix so tiny test shapes stay fast to tune.
    return int(min(65_536, max(4_096, v_z * v_x // 16)))


def _measure(fn: Callable, args: tuple, *, reps: int) -> float:
    """Median seconds per call, jit-warmed (same harness the stats
    benchmark uses, so tuner-measured == benchmark-measured)."""
    jax.block_until_ready(fn(*args))
    t = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        t.append(time.perf_counter() - t0)
    return float(np.median(t))


def tau_candidates(engine: str, v_z: int, v_x: int, q: int) -> list:
    """The candidate space for one tau key. CPU: variants x lowprec
    (the ref path has no tiling). TPU: additionally tile sizes and the
    forced two-sweep phase for the batched kernel — tiles whose blocks
    fit a v5e's scoped VMEM at Q = 8 (a (256, 4096) two-sweep block
    does not)."""
    cands = []
    for variant in TAU_VARIANTS:
        base = TauPlan(variant=variant)
        if not _tau_usable(base, engine=engine, v_x=v_x):
            continue
        cands.append(base)
        low = dataclasses.replace(base, lowprec=True)
        if _tau_usable(low, engine=engine, v_x=v_x):
            cands.append(low)
        if engine == "pallas" and variant == "batched":
            for z_tile in (128, 256, 512):
                for x_tile in (256, 512, 1024):
                    for sweeps in (0, 2):
                        c = TauPlan(variant="batched", z_tile=z_tile,
                                    x_tile=x_tile, sweeps=sweeps)
                        if c not in cands:
                            cands.append(c)
    return cands


def ingest_candidates(engine: str, v_z: int, v_x: int) -> list:
    cands = [IngestPlan(fused=True), IngestPlan(fused=False)]
    if engine == "pallas":
        for s_tile in (1024, 2048, 4096):
            for z_tile in (128, 256, 512):
                for fused in (True, False):
                    c = IngestPlan(fused=fused, s_tile=s_tile, z_tile=z_tile)
                    if c not in cands:
                        cands.append(c)
    return cands


def _pick(timed: Dict, comparator, *, margin: float):
    """Fastest candidate, unless the comparator is within ``margin`` of
    it — measured deltas inside the margin are noise, and keeping the
    comparator makes the tuned-vs-reference benchmark comparison exact
    (same program) instead of a coin flip."""
    best = min(timed, key=timed.get)
    if comparator in timed and timed[comparator] <= timed[best] * (1.0 + margin):
        return comparator
    return best


def tune_tau(
    v_z: int,
    v_x: int,
    q: int,
    *,
    engine: Optional[str] = None,
    reps: int = 15,
    seed: int = 0,
    margin: float = DEFAULT_MARGIN,
    metric: str = "l1",
) -> Tuple[TauPlan, Dict[TauPlan, float]]:
    """Measure every tau candidate for one (key, metric); return
    (winner, timings).

    The comparator biased toward under ``margin`` is the "unrolled"
    full-precision plan — the PR-2 reference path every speedup in
    `BENCH_stats.json` is quoted against. The candidate space is
    metric-independent; the measurement runs the requested metric's
    score, so e.g. hellinger (two sqrts per lane) may tune differently
    from l1 on the same shape.
    """
    engine = engine or ("pallas" if jax.default_backend() == "tpu" else "ref")
    rng = np.random.default_rng(seed)
    counts = jnp.asarray(rng.integers(0, 50, size=(v_z, v_x)).astype(np.float32))
    q_hat = jnp.asarray(
        np.stack([rng.dirichlet(np.ones(v_x)).astype(np.float32) for _ in range(q)])
    )
    timed: Dict[TauPlan, float] = {}
    for cand in tau_candidates(engine, v_z, v_x, q):
        fn = jax.jit(partial(run_tau, plan=cand, engine=engine, metric=metric))
        timed[cand] = _measure(fn, (counts, q_hat), reps=reps)
    comparator = TauPlan(variant="unrolled")
    return _pick(timed, comparator, margin=margin), timed


def tune_ingest(
    v_z: int,
    v_x: int,
    *,
    n_samples: Optional[int] = None,
    engine: Optional[str] = None,
    reps: int = 15,
    seed: int = 0,
    margin: float = DEFAULT_MARGIN,
) -> Tuple[IngestPlan, Dict[IngestPlan, float]]:
    """Measure every ingest candidate for one key; comparator biased
    toward under ``margin`` is the fused (pre-autotune default) plan."""
    engine = engine or ("pallas" if jax.default_backend() == "tpu" else "ref")
    n = n_samples or _default_ingest_samples(v_z, v_x)
    rng = np.random.default_rng(seed)
    z = jnp.asarray(rng.integers(-1, v_z, size=n).astype(np.int32))
    x = jnp.asarray(rng.integers(-1, v_x, size=n).astype(np.int32))
    timed: Dict[IngestPlan, float] = {}
    for cand in ingest_candidates(engine, v_z, v_x):
        fn = jax.jit(partial(run_ingest, v_z=v_z, v_x=v_x, plan=cand, engine=engine))
        timed[cand] = _measure(fn, (z, x), reps=reps)
    return _pick(timed, IngestPlan(fused=True), margin=margin), timed
