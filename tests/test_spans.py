"""Program spans in the profiler's trace (`repro.obs.span`) and the
poll-bytes counter, read back through the benchmark's trace reduction
(`chipbench.spans`) on the CPU."""

import jax
import numpy as np
import pytest

from chipbench import spans as reduction
from repro.data.layout import block_layout
from repro.data.synth import SynthSpec, make_dataset, perturb_distribution
from repro.obs import SPAN_PREFIX, spanned
from repro.serve.fastmatch_server import MatchServer

K, EPS, DELTA = 5, 0.08, 0.05
SPANS = ("admit", "retire", "sync", "dispatch", "fetch", "pass", "complete", "collect")


@pytest.fixture(scope="module")
def dataset():
    spec = SynthSpec(
        v_z=32, v_x=16, num_tuples=100_000, k=K, n_close=5,
        close_distance=0.02, far_distance=0.3, zipf_a=0.9, seed=3,
    )
    ds = make_dataset(spec)
    blocked = block_layout(ds.z, ds.x, v_z=spec.v_z, v_x=spec.v_x, block_size=512, seed=5)
    rng = np.random.default_rng(9)
    targets = [perturb_distribution(ds.target, d, rng) for d in (0.01, 0.03, 0.05)]
    return blocked, targets


def _serve(server, targets):
    """Three queries through two slots: stepped, then drained by `pump`,
    then the rest of the table read exactly."""
    for t in targets:
        server.submit(t, k=K, eps=EPS, delta=DELTA)
    for _ in range(3):
        server.step()
    server.run_until_idle()
    server.scheduler.complete_remaining()


@pytest.fixture(scope="module")
def traced(dataset, tmp_path_factory):
    blocked, targets = dataset
    _serve(MatchServer(blocked, max_queries=2, lookahead=16, prefetch=True), targets)
    server = MatchServer(blocked, max_queries=2, lookahead=16, prefetch=True)
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(trace_dir)
    try:
        _serve(server, targets)
    finally:
        jax.profiler.stop_trace()
    return server, reduction.extract(trace_dir)["host"]


def test_every_span_of_the_served_path_is_in_the_trace(traced):
    _, host = traced
    assert {e[0] for e in host} == {SPAN_PREFIX + name for name in SPANS}
    # the prefetch worker fetches on a thread of its own
    assert len({e[3] for e in host if e[0] == "fastmatch.fetch"}) == 2


def test_admission_holds_its_poll_and_shares_its_qid_with_retirement(traced):
    server, host = traced

    def of(name):
        return [e for e in host if e[0] == name]

    syncs = of("fastmatch.sync")
    for _, start, dur, thread, _ in of("fastmatch.admit"):
        inside = [s for s in syncs
                  if s[3] == thread and start <= s[1] and s[1] + s[2] <= start + dur]
        assert len(inside) == 1
    admitted = sorted(e[4]["qid"] for e in of("fastmatch.admit"))
    assert admitted == sorted(e[4]["qid"] for e in of("fastmatch.retire")) == [0, 1, 2]
    # per-round spans carry no ids
    assert all(e[4] == {} for e in host if e[0] not in ("fastmatch.admit", "fastmatch.retire"))
    assert len(syncs) == server.scheduler.host_syncs


@pytest.mark.parametrize("max_queries", [2, 8])
def test_host_sync_bytes_are_the_polled_shapes(dataset, max_queries):
    blocked, targets = dataset
    server = MatchServer(blocked, max_queries=max_queries, lookahead=16)
    _serve(server, targets)
    sched = server.scheduler
    q, v_z = max_queries, sched.spec.v_z
    per_poll = (
        sched.source.num_blocks  # read mask, one byte a block
        + 4 * 4  # rounds, blocks read and considered, tuples read: int32
        + 4 * q  # delta_upper
        + 4 * q * v_z  # tau
        + 4 * v_z  # n
        + 2 * q * v_z  # in_top_k, pruned: bool
    )
    assert sched.host_syncs > 0
    assert sched.host_sync_bytes == sched.host_syncs * per_poll


def test_spanned_times_each_pull_and_passes_items_through():
    pulls = []

    def source():
        for i in range(3):
            pulls.append(i)
            yield i

    items = spanned(source(), "fetch")
    assert pulls == []  # nothing is pulled before the consumer asks
    assert list(items) == [0, 1, 2] == pulls
    assert list(spanned([], "fetch")) == []
