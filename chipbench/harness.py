"""One run of one cell: set-up, the measured window, the check, the result.

    set-up   JAX on the chip, the table made on the device from the seed
             (`gen`), the program's `InMemorySource` over it, and the
             mix's warm-up batches, which compile or load every program
             the window runs
    window   `load.Client.run` for ``seconds``; with ``trace`` the
             profiler records it (at most `TRACE_SECONDS`)
    check    after the window: the device's peak memory is read, the
             servers are dropped, and the float64 reference (`reference`)
             judges every answer the window produced
    result   the end-to-end metrics, or with ``trace`` the per-layer
             metrics from their readers, and the numbers compared

`run_cell` is the whole of a run short of the look for a chip, so tests
drive it on the CPU at small sizes.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import os
import shutil
import sys
import tempfile
from typing import Optional

import numpy as np

from chipbench import gen, load, reference
from chipbench.load import clock

GRACE_S = 60.0  # how long past the close a due query may still be answered
TRACE_SECONDS = 10.0


@dataclasses.dataclass
class RunView:
    """What a per-layer reader sees of one run."""

    config: dict
    mix: dict
    device_kind: str
    answers: int
    lags_s: list
    counters: dict
    trace: Optional[object] = None
    latencies_s: list = dataclasses.field(default_factory=list)  # due to answer


def seed_words(seed: int) -> np.ndarray:
    """Sub-seeds for every random choice of a run, from any whole number."""
    return np.random.SeedSequence(abs(int(seed))).generate_state(4)


class CompileCounter:
    """Counts programs compiled or loaded from the cache while it is on."""

    def __init__(self):
        import jax

        self.on, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, _secs, **_kw):
        if self.on and ("backend_compile" in name or "cache_retrieval" in name):
            self.count += 1


def _quantile_ms(values, q) -> float:
    return float(np.percentile(np.asarray(values) * 1e3, q))


def run_cell(cell, *, seed: int, seconds: float, trace: bool, t_process: float,
             log=print, control: bool = False, trace_out: Optional[str] = None) -> dict:
    """Everything of a run after the look for a chip; returns the result.

    With ``control`` the result also holds ``control_checks``: the same
    numbers with the bfloat16 control (`reference.bf16_control`) put in
    the program's place."""
    import jax

    from repro.data.layout import BlockedDataset
    from repro.io import InMemorySource

    cfg, mix = cell.config, cell.mix
    words = seed_words(seed)
    rng = np.random.default_rng(words[2:])
    parts = {"process_and_jax": clock() - t_process}

    t = clock()
    dist = gen.distributions(cfg, np.random.default_rng(words[:2]))
    z, x, bitmap = gen.make_table(dist, cfg["num_tuples"], words[:2])
    jax.block_until_ready((z, x, bitmap))
    parts["generate"] = clock() - t

    t = clock()
    source = InMemorySource(
        BlockedDataset(z_blocks=z, x_blocks=x, bitmap=bitmap, v_z=cfg["v_z"], v_x=cfg["v_x"])
    )
    client = load.Client(mix, cfg, dist["target"], source, rng, tracing=trace)
    parts["source"] = clock() - t
    t = clock()
    client.warm_up()
    parts["warm_up"] = clock() - t

    if trace:
        seconds = min(seconds, TRACE_SECONDS)
        trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        jax.profiler.start_trace(trace_dir)
    compiles = CompileCounter()
    compiles.on = True
    t_setup = clock()
    if trace:
        with jax.profiler.TraceAnnotation("chipbench.window"):
            t0, attempted = client.run(seconds, GRACE_S)
    else:
        t0, attempted = client.run(seconds, GRACE_S)
    compiles.on = False
    setup_s = t_setup - t_process
    parts["total"] = setup_s

    dev = jax.devices()[0]
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    events = None
    if trace:
        jax.profiler.stop_trace()
        from chipbench import trace as tr

        events = tr.extract(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        if trace_out:
            with open(trace_out, "w") as f:
                json.dump(events, f)

    client_records = client.records
    answered = [r for r in client_records if r.result is not None]
    counters = client.counters()
    counters["servers"] = len(client.servers)
    answers, order = _answers(answered), list(client.order)
    for r in answered:
        r.result = None  # the device arrays it holds are no longer needed
    snapshots = client.snapshots
    num_blocks = z.shape[0]
    del client, source, bitmap
    gc.collect()

    t = clock()
    true_counts = gen.table_counts(z, x, v_z=cfg["v_z"], v_x=cfg["v_x"])
    sample_counts = _sample_counts(z, x, snapshots, order, num_blocks, cfg)
    each = []
    numbers, wrong = reference.compare(
        answers, sample_counts, true_counts, eps=cfg["eps"],
        unanswered=attempted - len(answered), each=each,
    )
    ref_s = clock() - t
    if control:
        ctl, _ = reference.compare(
            reference.bf16_control(answers, sample_counts, order), sample_counts, true_counts,
            eps=cfg["eps"], unanswered=attempted - len(answered),
        )

    lat = [r.done - r.due for r in answered]
    lags = [r.submitted - r.due for r in answered]
    last = max((r.done for r in answered), default=t0)
    log(f"chipbench: setup parts (s) {_fmt(parts)}")
    close = t0 + seconds
    backlog = sum(1 for r in client_records if r.due <= close and not r.done <= close)
    log(f"chipbench: window {seconds} s, {attempted} due, {len(answered)} answered, "
        f"{wrong} wrong, {backlog} in flight at the close, {compiles.count} programs "
        f"compiled or loaded inside it, reference {ref_s:.3f} s, counters {counters}")

    result = {
        "correct": reference.judge(numbers),
        "attempted": int(attempted),
        "failed": int(attempted - len(answered) + wrong),
    }
    if trace:
        from chipbench import trace as tr

        host = [e for e in events["host"] if e[0] == "chipbench.window"]
        if len(host) != 1:
            raise RuntimeError(f"expected one chipbench.window span, found {len(host)}")
        tv = tr.Trace(events, host[0][1], host[0][1] + host[0][2])
        view = RunView(cfg, mix, dev.device_kind, len(answered), lags, counters, tv, lat)
        metrics = {}
        for m in cell.per_layer:
            v = cell.readers[m["name"]](view)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        if dev.platform == "tpu":
            # every metric listed for this cell has something to read on the chip;
            # a name the trace lacks would otherwise drop it without a word
            silent = [m["name"] for m in cell.per_layer if m["name"] not in metrics]
            if silent or tv.busy_s <= 0:
                raise RuntimeError(f"traced run read nothing for {silent} "
                                   f"(device busy {tv.busy_s} s); trace names changed?")
        result["metrics"] = metrics
        result["device"] = _device(jax, peak) | {"busy_s": tv.busy_s, "window_s": tv.window_s}
        result["breakdown"] = {"device_ops": tv.top_ops(10), "idle_gaps": tv.idle_gaps(10)}
    else:
        p50 = _quantile_ms(lat, 50) if lat else None
        e2e = {
            "answer_p50_ms": p50,
            "answer_p50_ms.open": p50,
            "answer_p95_ms": _quantile_ms(lat, 95) if lat else None,
            "answers_per_s": len(answered) / (last - t0) if answered and last > t0 else None,
            "setup_s": setup_s,
        }
        result["metrics"] = {
            m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end if e2e.get(m["name"]) is not None
        }
        result["device"] = _device(jax, peak)
    if control:
        result["control_checks"] = {
            k: {"value": ctl[k], "limit": lim} for k, lim in reference.LIMITS.items()
        }
        result["control_correct"] = reference.judge(ctl)
    result["checks"] = {
        k: {"value": numbers[k], "limit": lim} for k, lim in reference.LIMITS.items()
    }
    if each:
        g2 = np.sort([a["g2_ratio"] for a in each])[::-1]
        log(f"chipbench: g2_ratio per answer: largest {np.round(g2[:5], 4).tolist()}, "
            f"median {float(np.median(g2)):.4f}, n {g2.size}")
    if lat:
        log(f"chipbench: latency ms p50 {_quantile_ms(lat, 50):.3f} p95 "
            f"{_quantile_ms(lat, 95):.3f} max {max(lat) * 1e3:.3f}; "
            f"lag ms p95 {_quantile_ms(lags, 95):.3f}; n {len(lat)}")
    return result


def _fmt(d: dict) -> str:
    return ", ".join(f"{k} {v:.3f}" for k, v in d.items())


def _device(jax, peak) -> dict:
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices()),
            "memory_peak_bytes": peak}


def _answers(records) -> list:
    """The answers as host arrays; one transfer per distinct counts array."""
    host: dict = {}
    out = []
    for r in records:
        res = r.result
        key = id(res.state.counts)
        if key not in host:
            host[key] = (np.asarray(res.state.counts), np.asarray(res.state.n))
        counts, n = host[key]
        ids = np.asarray(res.ids)
        out.append(reference.Answer(
            target=r.target, ids=ids, counts=counts[ids], n=n[ids], snapshot=r.snapshot,
            flagged=bool(res.stopped or res.degraded),
        ))
    return out


def _sample_counts(z, x, snapshots, order, num_blocks, cfg) -> dict:
    """Exact counts over each snapshot's read set, grown snapshot by
    snapshot within a server (read sets only grow)."""
    out, prev = {}, None
    for key in order:
        mask = np.unpackbits(snapshots[key], count=num_blocks).astype(bool)
        if prev is not None and prev[0][0] == key[0]:
            base, prev_mask = out[prev[0]], prev[1]
            new = np.flatnonzero(mask & ~prev_mask)
        else:
            base, new = 0, np.flatnonzero(mask)
        out[key] = base + gen.block_counts(z, x, new, v_z=cfg["v_z"], v_x=cfg["v_x"])
        prev = (key, mask)
    return out


def print_checks(result: dict, stream=sys.stderr) -> None:
    for k, v in result["checks"].items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}", file=stream)


def use_compile_cache(root) -> str:
    """The persistent compilation cache: a fixed ``.jax_cache`` directory in
    the checkout, also where ``JAX_COMPILATION_CACHE_DIR`` names another, so
    that two checkouts measured on one machine share no compiled program."""
    import jax

    path = os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
