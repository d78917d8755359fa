"""Telemetry subsystem: registry/tracer units, instrumented-layer
integration, and the two contracts the tentpole hangs on —

  * bit-equivalence: every engine output (counts, n, tau, read_mask,
    results, host-sync count) is identical with telemetry on and off;
  * curve fidelity: the recorded tuples-to-confidence trajectory
    reproduces the stats tail (eps(n) from `core.bounds.theorem1_epsilon`
    at the per-candidate budget, delta_upper from the device poll).
"""

import json
import threading
import time

import numpy as np
import pytest

from repro.core.bounds import theorem1_epsilon
from repro.data.layout import block_layout
from repro.data.synth import SynthSpec, make_dataset, perturb_distribution
from repro.kernels.histogram import ingest_lane_fill
from repro.obs import (
    CURVE_COLUMNS,
    TIMING_FIELDS,
    MetricsRegistry,
    Telemetry,
    Tracer,
)
from repro.serve.fastmatch_server import MatchServer

K, EPS, DELTA = 5, 0.08, 0.05


@pytest.fixture(scope="module")
def dataset():
    spec = SynthSpec(
        v_z=32, v_x=16, num_tuples=200_000, k=K, n_close=5,
        close_distance=0.02, far_distance=0.3, zipf_a=0.9, seed=3,
    )
    ds = make_dataset(spec)
    blocked = block_layout(ds.z, ds.x, v_z=spec.v_z, v_x=spec.v_x, block_size=512, seed=5)
    return spec, ds, blocked


@pytest.fixture(scope="module")
def targets(dataset):
    _, ds, _ = dataset
    rng = np.random.default_rng(9)
    return [perturb_distribution(ds.target, d, rng) for d in (0.01, 0.04)]


# ---------------------------------------------------------------- registry


class TestRegistry:
    def test_counter_monotone(self):
        reg = MetricsRegistry()
        c = reg.counter("x_total", "help text")
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5
        with pytest.raises(ValueError, match="cannot decrease"):
            c.inc(-1)
        assert reg.counter("x_total") is c  # get-or-create

    def test_gauge_last_write_wins(self):
        reg = MetricsRegistry()
        g = reg.gauge("depth")
        g.set(4)
        g.inc(-1)
        assert g.value == 3.0

    def test_kind_conflict_raises(self):
        reg = MetricsRegistry()
        reg.counter("m")
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("m")

    def test_bad_name_rejected(self):
        reg = MetricsRegistry()
        with pytest.raises(ValueError, match="invalid metric name"):
            reg.counter("has space")

    def test_histogram_binning_matches_numpy_reference(self):
        """Bucket counts must equal a per-sample numpy reference,
        including the v == edge boundary (le semantics: the sample
        belongs to that edge's bucket)."""
        reg = MetricsRegistry()
        h = reg.histogram("lat_seconds", edges=(0.01, 0.1, 1.0))
        samples = [0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 7.0, 0.2]
        for s in samples:
            h.observe(s)
        counts = h.bucket_counts()
        # reference: non-cumulative per-bin counts with overflow last
        ref = np.zeros(4, np.int64)
        for s in samples:
            ref[int(np.searchsorted((0.01, 0.1, 1.0), s, side="left"))] += 1
        np.testing.assert_array_equal(counts, ref)
        assert h.count == len(samples)
        assert h.sum == pytest.approx(sum(samples))

    def test_histogram_scrape_moves_nothing_to_or_from_a_device(self):
        """Binning happens on the host: a scrape dispatches no device
        program, so it cannot land between a serve loop's rounds."""
        import jax

        h = MetricsRegistry().histogram("lat_seconds", edges=(0.1, 1.0))
        for s in (0.05, 0.5, 5.0):
            h.observe(s)
        with jax.transfer_guard("disallow"):
            np.testing.assert_array_equal(h.bucket_counts(), [1, 1, 1])

    def test_histogram_thread_safe_observe(self):
        reg = MetricsRegistry()
        h = reg.histogram("t_seconds", edges=(0.5,))
        def burst():
            for _ in range(500):
                h.observe(0.1)
        threads = [threading.Thread(target=burst) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert h.count == 2000
        assert h.bucket_counts().sum() == 2000

    def test_prometheus_exposition(self):
        reg = MetricsRegistry()
        reg.counter("reads_total", "total reads").inc(7)
        reg.gauge("queue_depth").set(2)
        h = reg.histogram("lat_seconds", edges=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)
        h.observe(5.0)
        text = reg.to_prometheus()
        lines = text.splitlines()
        assert "# HELP reads_total total reads" in lines
        assert "# TYPE reads_total counter" in lines
        assert "reads_total 7" in lines
        assert "queue_depth 2" in lines
        # cumulative le buckets; +Inf bucket equals the total count
        assert 'lat_seconds_bucket{le="0.1"} 1' in lines
        assert 'lat_seconds_bucket{le="1"} 2' in lines
        assert 'lat_seconds_bucket{le="+Inf"} 3' in lines
        assert "lat_seconds_count 3" in lines

    def test_snapshot_is_json_able(self):
        reg = MetricsRegistry()
        reg.counter("a_total").inc()
        reg.histogram("b_seconds", edges=(1.0,)).observe(0.5)
        round_trip = json.loads(reg.to_json())
        assert round_trip["a_total"]["value"] == 1.0
        assert round_trip["b_seconds"]["buckets"] == [1, 0]


# ------------------------------------------------------------------ tracer


class TestTracer:
    def test_emit_sequencing_and_ring_bound(self):
        tr = Tracer(capacity=3, clock=lambda: 0.0)
        for i in range(5):
            tr.emit("e", i=i)
        evs = tr.events()
        assert [e["i"] for e in evs] == [2, 3, 4]  # oldest dropped
        assert [e["seq"] for e in evs] == [2, 3, 4]
        assert tr.events_total == 5  # truncation stays visible

    def test_skeleton_strips_timing_only(self):
        tr = Tracer(clock=lambda: 0.0)
        tr.emit("round_batch", rounds=4, gather_s=0.1, sync_s=0.2, stall_frac=0.3)
        (sk,) = tr.skeleton()
        assert sk == {"seq": 0, "kind": "round_batch", "rounds": 4}
        assert TIMING_FIELDS.issuperset({"ts", "gather_s", "sync_s", "stall_frac"})

    def test_span_records_duration(self):
        ticks = iter([0.0, 0.0, 1.5, 1.5])  # epoch, enter, exit, emit-ts
        tr = Tracer(clock=lambda: next(ticks))
        with tr.span("work", tag="x") as ev:
            ev["extra"] = 1
        (e,) = tr.events("work")
        assert e["dur_s"] == 1.5 and e["tag"] == "x" and e["extra"] == 1

    def test_export_jsonl_round_trip(self, tmp_path):
        tr = Tracer(clock=lambda: 0.0)
        tr.emit("a", v=1)
        tr.emit("b", v=[1, 2])
        p = tmp_path / "trace.jsonl"
        assert tr.export_jsonl(p) == 2
        back = [json.loads(line) for line in p.read_text().splitlines()]
        assert [e["kind"] for e in back] == ["a", "b"]
        assert back[1]["v"] == [1, 2]


# ----------------------------------------------------------- telemetry facade


class TestTelemetryCurves:
    def test_dedupe_and_cap(self):
        tel = Telemetry(max_curve_points=3)
        pt = dict.fromkeys(CURVE_COLUMNS, 0.0)
        tel.record_curve_point(1, dict(pt))
        tel.record_curve_point(1, dict(pt))  # same (round, tuples, delta_upper)
        assert len(tel.trajectory(1)) == 1
        for r in (1, 2, 3, 4):
            tel.record_curve_point(1, dict(pt, round=r))
        assert len(tel.trajectory(1)) == 3  # earliest kept
        assert tel.curve_drops == 2

    def test_confidence_curve_array_and_csv(self, tmp_path):
        tel = Telemetry()
        for r in (0, 1):
            tel.record_curve_point(7, dict.fromkeys(CURVE_COLUMNS, float(r)))
        arr = tel.confidence_curve(7)
        assert arr.shape == (2, len(CURVE_COLUMNS))
        assert tel.confidence_curve(99).shape == (0, len(CURVE_COLUMNS))
        p = tmp_path / "curve.csv"
        assert tel.export_confidence_csv(p) == 2
        header, *rows = p.read_text().splitlines()
        assert header == "qid," + ",".join(CURVE_COLUMNS)
        assert len(rows) == 2 and rows[0].startswith("7,")


# ---------------------------------------------------- server integration


def _drain(blocked, targets, *, telemetry, seed=11):
    srv = MatchServer(
        blocked, max_queries=2, lookahead=64, poll_every=2, seed=seed,
        telemetry=telemetry,
    )
    rids = [srv.submit(t, k=K, eps=EPS, delta=DELTA) for t in targets]
    return srv, rids, srv.run_until_idle()


class TestServerTelemetry:
    # Satellite: the full metrics-dict schema is a public contract.
    SCHEMA = {
        "queries_done": int,
        "queries_queued": int,
        "queries_live": int,
        "queries_pending": int,
        "total_blocks_read": int,
        "total_tuples_read": int,
        "total_rounds": int,
        "fraction_read": float,
        "tuples_per_query": float,
        # PR 8 health surface: fault/degradation observability.
        "last_error": str,
        "queries_shed": int,
        "blocks_quarantined": int,
        "degraded": bool,
        "eps_inflation": float,
    }

    def test_metrics_schema_pinned(self, dataset, targets):
        _, _, blocked = dataset
        srv = MatchServer(blocked, max_queries=2, lookahead=64)
        m = srv.metrics
        assert set(m) == set(self.SCHEMA)
        for key, typ in self.SCHEMA.items():
            assert isinstance(m[key], typ), (key, type(m[key]))
        # nan regression: before any completion the ratio is 0.0, and the
        # dict must survive a strict-JSON round trip (nan would not)
        assert m["tuples_per_query"] == 0.0
        json.loads(json.dumps(m, allow_nan=False))
        srv.submit(targets[0], k=K, eps=EPS, delta=DELTA)
        srv.run_until_idle()
        m = srv.metrics
        assert m["queries_done"] == 1 and m["tuples_per_query"] > 0.0
        for key, typ in self.SCHEMA.items():
            assert isinstance(m[key], typ), (key, type(m[key]))

    def test_bit_equivalence_on_off(self, dataset, targets):
        """Tentpole acceptance: telemetry must observe, never perturb.
        Same seeds -> identical results, identical device-poll count,
        bit-identical cache state (counts/n/read_mask/cursors)."""
        _, _, blocked = dataset
        srv_on, rids_on, res_on = _drain(blocked, targets, telemetry=True)
        srv_off, rids_off, res_off = _drain(blocked, targets, telemetry=None)
        assert rids_on == rids_off
        for rid in rids_on:
            a, b = res_on[rid], res_off[rid]
            np.testing.assert_array_equal(a.ids, b.ids)
            assert (a.rounds, a.blocks_read, a.tuples_read, a.exact, a.passes) == (
                b.rounds, b.blocks_read, b.tuples_read, b.exact, b.passes
            )
        assert srv_on.scheduler.host_syncs == srv_off.scheduler.host_syncs
        snap_on = srv_on.scheduler.export_cache()
        snap_off = srv_off.scheduler.export_cache()
        for leaf_on, leaf_off in zip(snap_on, snap_off):
            np.testing.assert_array_equal(np.asarray(leaf_on), np.asarray(leaf_off))

    def test_golden_span_tree(self, dataset, targets):
        """The event skeleton of a scripted 2-query run is deterministic:
        two identically-seeded servers produce byte-identical skeletons,
        and the per-query lifecycle reads enqueue -> admit -> retire ->
        done in submission order."""
        _, _, blocked = dataset
        srv_a, rids, _ = _drain(blocked, targets, telemetry=True)
        srv_b, _, _ = _drain(blocked, targets, telemetry=True)
        sk_a = srv_a.telemetry.tracer.skeleton()
        sk_b = srv_b.telemetry.tracer.skeleton()
        assert sk_a == sk_b
        for ev in sk_a:  # no wall-clock leaks into the deterministic view
            assert not TIMING_FIELDS.intersection(ev)

        kinds = [e["kind"] for e in sk_a]
        assert kinds.count("query_enqueue") == len(rids)
        assert kinds.count("query_admit") == len(rids)
        assert kinds.count("query_retire") == len(rids)
        assert kinds.count("query_done") == len(rids)
        assert kinds.count("pass_start") >= 1 and kinds.count("round_batch") >= 1
        # submission order is admission order (both queries fit the pool)
        admits = [e["qid"] for e in sk_a if e["kind"] == "query_admit"]
        assert admits == sorted(admits)
        # every lifecycle is ordered within the trace
        for qid in admits:
            seqs = {
                e["kind"]: e["seq"] for e in sk_a
                if e.get("qid") == qid and e["kind"] in
                ("query_admit", "query_retire", "query_done")
            }
            assert seqs["query_admit"] < seqs["query_retire"] < seqs["query_done"]
        # retire events agree with round_batch totals
        last_rb = [e for e in sk_a if e["kind"] == "round_batch"][-1]
        assert last_rb["rounds"] == srv_a.scheduler.rounds

    def test_confidence_curve_matches_stats_tail(self, dataset, targets):
        """Curve fidelity: eps_n is Theorem 1's bound at the polled
        n_min and per-candidate budget delta/V_Z; delta_upper decreases
        to below delta for a terminated query; counters agree with the
        scheduler mirrors."""
        spec, _, blocked = dataset
        srv, rids, res = _drain(blocked, targets, telemetry=True)
        tel = srv.telemetry
        sched = srv.scheduler
        assert sorted(tel.query_ids()) == sorted(
            e["qid"] for e in tel.tracer.skeleton("query_admit")
        )
        for qid in tel.query_ids():
            traj = tel.trajectory(qid)
            assert traj, qid
            for p in traj:
                ref = float(theorem1_epsilon(
                    max(p["n_min"], 1.0), DELTA / spec.v_z, spec.v_x
                ))
                np.testing.assert_allclose(p["eps_n"], ref, rtol=1e-4)
                assert p["confidence"] == pytest.approx(
                    max(0.0, 1.0 - p["delta_upper"])
                )
            # the curve rises: final confidence is the best recorded
            finals = traj[-1]
            assert finals["delta_upper"] <= traj[0]["delta_upper"]
            assert finals["tuples"] >= traj[0]["tuples"]
        # a terminated (non-exact) query crossed its bound on record
        terminated = [
            e for e in tel.tracer.skeleton("query_retire") if e["terminated"]
        ]
        for ev in terminated:
            assert tel.trajectory(ev["qid"])[-1]["delta_upper"] < DELTA
        reg = tel.registry
        assert reg.get("fastmatch_rounds_total").value == sched.rounds
        assert reg.get("fastmatch_tuples_read_total").value == sched.tuples_read
        assert reg.get("fastmatch_host_syncs_total").value == sched.host_syncs
        assert reg.get("fastmatch_queries_retired_total").value == len(res)

    @pytest.mark.parametrize("policy", ["anyactive", "scan"])
    def test_blocks_considered_counter(self, dataset, targets, policy):
        """AnyActive's denominator is exported beside its reads: every
        valid block of a dispatched window is considered, read or not, so
        considered >= read, with equality when Scan reads every block it
        is handed (each block once, within the first pass)."""
        _, _, blocked = dataset
        srv = MatchServer(blocked, max_queries=2, lookahead=64, poll_every=2, seed=11,
                          telemetry=True, policy=policy)
        for t in targets:
            srv.submit(t, k=K, eps=EPS, delta=DELTA)
        srv.run_until_idle()
        sched, reg = srv.scheduler, srv.telemetry.registry
        considered = reg.get("fastmatch_blocks_considered_total").value
        read = reg.get("fastmatch_blocks_read_total").value
        assert considered == sched.blocks_considered > 0
        assert read == sched.blocks_read > 0
        if policy == "scan":
            assert considered == read
        else:
            assert considered >= read
        batches = srv.telemetry.tracer.skeleton("round_batch")
        assert batches and all("blocks_considered" in e for e in batches)
        assert batches[-1]["blocks_considered"] == sched.blocks_considered

    def test_ingest_lane_fill_gauge(self, dataset, targets):
        """Set once from the spec and the ingest plan's row tile: 32 x 16
        keys fill 4 packed rows, padded to one (8, 128) tile."""
        _, _, blocked = dataset
        srv = MatchServer(blocked, max_queries=2, lookahead=64, telemetry=True)
        gauge = srv.telemetry.registry.get("fastmatch_ingest_lane_fill")
        assert gauge.value == ingest_lane_fill(32, 16)
        assert gauge.value == 32 * 16 / (8 * 128)

    def test_trace_and_prometheus_exports(self, dataset, targets, tmp_path):
        _, _, blocked = dataset
        srv, _, _ = _drain(blocked, targets, telemetry=True)
        p = tmp_path / "trace.jsonl"
        n = srv.export_trace(p)
        assert n == len(p.read_text().splitlines()) > 0
        text = srv.prometheus_metrics()
        assert "# TYPE fastmatch_rounds_total counter" in text
        assert "# TYPE fastmatch_query_wall_seconds histogram" in text
        plain = MatchServer(blocked, max_queries=2, lookahead=64)
        with pytest.raises(RuntimeError, match="without telemetry"):
            plain.export_trace(p)


# ------------------------------------------------------------- prefetch


class _SlowSource:
    """Minimal BlockSource: fetch sleeps, so waits are guaranteed."""

    def __init__(self, *, fetch_delay=0.02, fail_at=None, windows=6):
        self.num_blocks = windows
        self.block_size = 4
        self.v_z = 2
        self.v_x = 2
        self.tuples_per_block = np.full(windows, 4, np.int64)
        self.fetch_delay = fetch_delay
        self.fail_at = fail_at
        self.calls = 0

    def fetch(self, win, pad_to=None):
        self.calls += 1
        if self.fail_at is not None and self.calls >= self.fail_at:
            raise RuntimeError("disk on fire")
        time.sleep(self.fetch_delay)
        return ("window", int(np.asarray(win)[0]))

    def stream(self, windows, pad_to=None):
        for w in windows:
            yield self.fetch(w, pad_to)


class TestPrefetchTelemetry:
    def test_slow_source_records_nonzero_wait(self):
        """Satellite: a source slower than the consumer must show up as
        nonzero prefetch_wait samples and a stall fraction, not vanish."""
        from repro.io import PrefetchSource

        tel = Telemetry()
        src = PrefetchSource(_SlowSource(fetch_delay=0.02), telemetry=tel)
        wins = [np.array([i]) for i in range(6)]
        out = list(src.stream(wins))
        assert [o[1] for o in out] == list(range(6))
        h_wait = tel.registry.get("prefetch_wait_seconds")
        h_fetch = tel.registry.get("prefetch_fetch_seconds")
        assert h_wait.count >= len(wins) and h_wait.sum > 0.0
        assert h_fetch.count == len(wins) and h_fetch.sum >= 6 * 0.02
        (ev,) = tel.tracer.events("prefetch_stream")
        assert ev["windows"] == len(wins) + 1  # + the "done" hand-off
        assert ev["wait_s"] > 0.0 and ev["fetch_s"] > 0.0
        assert 0.0 <= ev["stall_frac"] <= 1.0
        assert ev["hidden_s"] == pytest.approx(
            max(ev["fetch_s"] - ev["wait_s"], 0.0)
        )

    def test_worker_error_is_structured_event(self):
        from repro.io import PrefetchSource

        tel = Telemetry()
        src = PrefetchSource(
            _SlowSource(fetch_delay=0.0, fail_at=3), telemetry=tel
        )
        with pytest.raises(RuntimeError, match="disk on fire"):
            list(src.stream([np.array([i]) for i in range(6)]))
        assert tel.registry.get("prefetch_worker_errors_total").value == 1
        (ev,) = tel.tracer.events("prefetch_worker_error")
        assert ev["source"] == "_SlowSource" and "disk on fire" in ev["error"]

    def test_join_timeout_is_structured_event(self):
        from repro.io import PrefetchSource

        tel = Telemetry()
        src = PrefetchSource(
            _SlowSource(fetch_delay=0.5, windows=4),
            telemetry=tel, join_timeout=0.0,
        )
        it = src.stream([np.array([i]) for i in range(4)])
        next(it)  # worker is now blocked inside the next slow fetch
        it.close()  # join(0.0) cannot outwait a 0.5s fetch
        assert tel.registry.get("prefetch_join_timeouts_total").value == 1
        (ev,) = tel.tracer.events("prefetch_join_timeout")
        assert ev["source"] == "_SlowSource" and ev["timeout_s"] == 0.0


# ------------------------------------------------------------ checkpoint


class TestCheckpointTelemetry:
    def test_save_metrics_and_event(self, tmp_path):
        from repro.checkpoint import CheckpointManager

        tel = Telemetry()
        mgr = CheckpointManager(tmp_path, telemetry=tel)
        state = {"a": np.arange(10, dtype=np.int64), "b": np.ones(3, np.float32)}
        mgr.save(state, step=4)
        reg = tel.registry
        assert reg.get("checkpoint_saves_total").value == 1
        assert reg.get("checkpoint_save_bytes_total").value == 10 * 8 + 3 * 4
        assert reg.get("checkpoint_save_seconds").count == 1
        (ev,) = tel.tracer.events("checkpoint_save")
        assert ev["step"] == 4 and ev["bytes"] == 92 and ev["save_s"] > 0.0
        assert mgr.save_failures == 0

    def test_save_failure_counted_and_reraised(self, tmp_path):
        import os

        from repro.checkpoint import CheckpointManager

        tel = Telemetry()
        mgr = CheckpointManager(tmp_path, telemetry=tel)
        # a FILE squatting on the tmp dir name makes the save's own
        # staging mkdir fail -> the failure path, deterministically
        (tmp_path / f"step_9.tmp.{os.getpid()}").write_text("squatter")
        with pytest.raises(OSError):
            mgr.save({"a": np.zeros(2)}, step=9)
        assert mgr.save_failures == 1
        assert tel.registry.get("checkpoint_save_failures_total").value == 1
        assert tel.registry.get("checkpoint_saves_total").value == 0

    def test_orphan_gc_counted(self, tmp_path):
        from repro.checkpoint import CheckpointManager

        tel = Telemetry()
        mgr = CheckpointManager(tmp_path, telemetry=tel)
        (tmp_path / "step_1.tmp.999999999").mkdir()  # dead-pid orphan
        (tmp_path / "LATEST.tmp.999999998").write_text("step_1")
        mgr.save({"a": np.zeros(2)}, step=2)  # save's GC sweeps them
        assert mgr.gc_swept == 2
        assert tel.registry.get("checkpoint_gc_swept_total").value == 2
        (ev,) = tel.tracer.events("checkpoint_gc")
        assert ev["swept"] == 2
        assert not list(tmp_path.glob("*.tmp.*"))

    def test_counters_exist_without_telemetry(self, tmp_path):
        from repro.checkpoint import CheckpointManager

        mgr = CheckpointManager(tmp_path)
        mgr.save({"a": np.zeros(2)}, step=1)
        assert mgr.gc_swept == 0 and mgr.save_failures == 0
