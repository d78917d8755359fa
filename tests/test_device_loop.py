"""Golden equivalence: fused device-resident loop vs the host-stepped loop.

The PR-1 scheduler round-tripped to host after every window (marks to
numpy, host-side read_mask, separate jit dispatches for mark / ingest /
stats). The fused loop runs one jitted `fused_round` per window with a
device-resident `SampleCursor` and polls only every `poll_every`
windows. This suite pins the refactor to the old semantics:

  * at poll_every=1 the fused loop must produce IDENTICAL counts / n /
    read_mask / per-query top-k ids to a host-stepped reference loop
    (reimplemented here from the primitives, exactly as PR-1 ran it) —
    including mid-stream admission and the exact-completion fallback;
  * at poll_every>1 retirement staleness may change WHICH blocks are
    read, but the answers (top-k ids) must not change on these seeds;
  * everything holds with `PrefetchSource` (background-thread gathers
    from host-resident block arrays) swapped in.

Plus contract tests for the new `repro.io` layer itself.
"""

import os
import sys
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import histsim
from repro.core import multiquery as mq
from repro.core.policies import mark_window
from repro.data.layout import block_layout
from repro.data.synth import SynthSpec, make_dataset, perturb_distribution
from repro.io import InMemorySource, PrefetchSource, ShardedSource

K, EPS, DELTA = 5, 0.08, 0.05


@pytest.fixture(scope="module")
def dataset():
    spec = SynthSpec(
        v_z=48, v_x=16, num_tuples=800_000, k=K, n_close=5,
        close_distance=0.02, far_distance=0.3, zipf_a=0.9, seed=13,
    )
    ds = make_dataset(spec)
    blocked = block_layout(ds.z, ds.x, v_z=spec.v_z, v_x=spec.v_x, block_size=512, seed=13)
    return spec, ds, blocked


@pytest.fixture(scope="module")
def targets(dataset):
    _, ds, _ = dataset
    rng = np.random.default_rng(21)
    return [ds.target] + [perturb_distribution(ds.target, d, rng) for d in (0.01, 0.04)]


def run_reference(
    blocked,
    initial,
    *,
    window,
    start_block,
    max_passes=4,
    admit_plan=(),
):
    """The PR-1 host-stepped shared-counts loop, from the primitives.

    initial: [(slot, target, k, eps, delta)] admitted before round 0.
    admit_plan: [(at_round, slot, target, k, eps, delta)] admitted at
    the first retirement-poll at or after `at_round` (PR-1's on_round
    admission point). Returns (state, read_mask, outcomes) with
    outcomes[slot] = top-k ids snapshotted at retirement.
    """
    spec = mq.MultiQuerySpec(v_z=blocked.v_z, v_x=blocked.v_x, max_queries=4)
    state = mq.init_multi_state(spec)
    z_blocks = jnp.asarray(blocked.z_blocks)
    x_blocks = jnp.asarray(blocked.x_blocks)
    bitmap = jnp.asarray(blocked.bitmap)
    nb = blocked.num_blocks
    order = np.roll(np.arange(nb), -start_block)
    read_mask = np.zeros(nb, bool)
    live, admit_rounds, outcomes = {}, {}, {}
    rounds = 0
    pending = sorted(admit_plan)

    def admit(slot, target, k, eps, delta):
        nonlocal state
        q = np.asarray(target, np.float64).ravel()
        q = (q / q.sum()).astype(np.float32)
        state = mq.admit_slot(
            state, jnp.asarray(slot, jnp.int32), jnp.asarray(q),
            jnp.asarray(k, jnp.int32), jnp.asarray(eps, jnp.float32),
            jnp.asarray(delta, jnp.float32), spec=spec,
        )
        state = mq.stats_step(state, spec=spec)
        live[slot] = (k, eps, delta)
        admit_rounds[slot] = rounds

    def snapshot(slot):
        view = mq.slot_state(state, slot)
        outcomes[slot] = np.asarray(histsim.top_k_ids(view, live[slot][0]))

    def poll():
        nonlocal state, pending
        du = np.asarray(state.delta_upper)
        for slot in list(live):
            if du[slot] < live[slot][2]:
                snapshot(slot)
                state = mq.clear_slot(state, jnp.asarray(slot, jnp.int32), spec=spec)
                del live[slot]
        while pending and pending[0][0] <= rounds:
            _, slot, t, k, e, d = pending.pop(0)
            admit(slot, t, k, e, d)

    for slot, t, k, e, d in initial:
        admit(slot, t, k, e, d)
    poll()
    passes = 0
    while live and passes < max_passes:
        pass_order = order[~read_mask[order]]
        if pass_order.size == 0:
            break
        passes += 1
        pass_start_rounds = rounds
        read_this = 0
        pos = 0
        while pos < pass_order.size and live:
            win = pass_order[pos : pos + window]
            pos += len(win)
            wj = jnp.asarray(win, jnp.int32)
            marks = np.asarray(
                mark_window(bitmap[wj], state.union_words, policy="anyactive")
            )
            nm = int(marks.sum())
            if nm:
                mj = jnp.asarray(marks)
                zw = jnp.where(mj[:, None], z_blocks[wj], jnp.int32(-1))
                xw = jnp.where(mj[:, None], x_blocks[wj], jnp.int32(-1))
                state = mq.run_round(state, zw.reshape(-1), xw.reshape(-1), spec=spec)
                read_mask[win[marks]] = True
                read_this += nm
            rounds += 1
            poll()
        if read_this == 0 and live:
            if not any(admit_rounds[s] >= pass_start_rounds for s in live):
                break
    if live:
        remaining = np.where(~read_mask)[0]
        for s in range(0, remaining.size, window):
            cj = jnp.asarray(remaining[s : s + window], jnp.int32)
            state = mq.ingest(
                state, z_blocks[cj].reshape(-1), x_blocks[cj].reshape(-1), spec=spec
            )
        read_mask[remaining] = True
        state = mq.stats_step(state, spec=spec)
        for slot in list(live):
            snapshot(slot)
            state = mq.clear_slot(state, jnp.asarray(slot, jnp.int32), spec=spec)
            del live[slot]
    assert not pending, "admit_plan rounds were never reached; tune the plan"
    return state, read_mask, outcomes


def run_fused(
    blocked_or_source,
    initial,
    *,
    window,
    start_block,
    poll_every=1,
    max_passes=4,
    admit_plan=(),
):
    """Same workload through the fused SharedCountsScheduler."""
    src = blocked_or_source
    spec = mq.MultiQuerySpec(
        v_z=src.v_z, v_x=src.v_x, max_queries=4
    )
    sched = mq.SharedCountsScheduler(
        src, spec, window=window, seed=0, start_block=start_block, poll_every=poll_every
    )
    pending = sorted(admit_plan)
    slot_of_qid = {}

    def on_round(s):
        while pending and pending[0][0] <= s.rounds and s.free_slots:
            _, slot, t, k, e, d = pending.pop(0)
            # `admit` fills the lowest free slot; the plan must agree or
            # the comparison with the reference is apples-to-oranges.
            assert s.free_slots[0] == slot
            qid = s.admit(t, k=k, eps=e, delta=d)
            slot_of_qid[qid] = slot

    for slot, t, k, e, d in initial:
        qid = sched.admit(t, k=k, eps=e, delta=d)
        slot_of_qid[qid] = slot
    sched.pump(max_passes=max_passes, on_round=on_round)
    assert not pending, "admit_plan rounds were never reached; tune the plan"
    outcomes = {
        slot_of_qid[qid]: out.ids for qid, out in sched.outcomes.items()
    }
    return sched, outcomes


class TestGoldenEquivalence:
    def test_identical_to_host_stepped_loop(self, dataset, targets):
        """poll_every=1: counts, n, read_mask and every query's top-k ids
        must match the PR-1 host-stepped loop bit for bit."""
        _, _, blocked = dataset
        initial = [
            (s, t, K, EPS, DELTA) for s, t in enumerate(targets)
        ]
        ref_state, ref_mask, ref_out = run_reference(
            blocked, initial, window=64, start_block=17
        )
        sched, out = run_fused(blocked, initial, window=64, start_block=17)
        np.testing.assert_array_equal(
            np.asarray(sched.state.counts), np.asarray(ref_state.counts)
        )
        np.testing.assert_array_equal(np.asarray(sched.state.n), np.asarray(ref_state.n))
        np.testing.assert_array_equal(sched.read_mask, ref_mask)
        assert set(out) == set(ref_out)
        for slot in ref_out:
            np.testing.assert_array_equal(out[slot], ref_out[slot])

    def test_identical_with_mid_stream_admission(self, dataset, targets):
        _, _, blocked = dataset
        initial = [(0, targets[0], K, EPS, DELTA)]
        plan = [(2, 1, targets[1], K, EPS, DELTA), (4, 2, targets[2], 3, 0.1, DELTA)]
        ref_state, ref_mask, ref_out = run_reference(
            blocked, initial, window=48, start_block=5, admit_plan=plan
        )
        sched, out = run_fused(
            blocked, initial, window=48, start_block=5, admit_plan=plan
        )
        np.testing.assert_array_equal(
            np.asarray(sched.state.counts), np.asarray(ref_state.counts)
        )
        np.testing.assert_array_equal(sched.read_mask, ref_mask)
        assert set(out) == set(ref_out)
        for slot in ref_out:
            np.testing.assert_array_equal(out[slot], ref_out[slot])

    def test_identical_on_exact_completion_fallback(self):
        """Unreachable bound: both loops must fall back to the complete
        read and answer from true counts."""
        spec = SynthSpec(v_z=24, v_x=8, num_tuples=30_000, k=3, n_close=3, seed=4)
        ds = make_dataset(spec)
        blocked = block_layout(ds.z, ds.x, v_z=spec.v_z, v_x=spec.v_x, block_size=256, seed=4)
        initial = [(0, ds.target, 3, 0.02, 1e-9)]
        ref_state, ref_mask, ref_out = run_reference(
            blocked, initial, window=32, start_block=3
        )
        sched, out = run_fused(blocked, initial, window=32, start_block=3)
        assert ref_mask.all() and sched.read_mask.all()
        np.testing.assert_array_equal(
            np.asarray(sched.state.counts), np.asarray(ref_state.counts)
        )
        np.testing.assert_array_equal(out[0], ref_out[0])
        assert sched.outcomes[0].exact

    def test_identical_with_prefetch_source(self, dataset, targets):
        """The background-thread double buffer must not change a single
        bit — host-resident arrays force real per-window transfers."""
        _, _, blocked = dataset
        initial = [(s, t, K, EPS, DELTA) for s, t in enumerate(targets)]
        ref_state, ref_mask, ref_out = run_reference(
            blocked, initial, window=64, start_block=17
        )
        src = PrefetchSource(InMemorySource(blocked, device_resident=False))
        sched, out = run_fused(src, initial, window=64, start_block=17)
        np.testing.assert_array_equal(
            np.asarray(sched.state.counts), np.asarray(ref_state.counts)
        )
        np.testing.assert_array_equal(sched.read_mask, ref_mask)
        for slot in ref_out:
            np.testing.assert_array_equal(out[slot], ref_out[slot])

    def test_poll_every_staleness_preserves_answers(self, dataset, targets):
        """poll_every=8 defers retirement (may read MORE blocks) but the
        returned top-k ids must match poll_every=1 on these seeds, and
        host polls must drop ~8x."""
        _, _, blocked = dataset
        initial = [(s, t, K, EPS, DELTA) for s, t in enumerate(targets)]
        # window=16 so the workload spans enough windows for the poll
        # cadence to be visible
        s1, out1 = run_fused(blocked, initial, window=16, start_block=17, poll_every=1)
        s8, out8 = run_fused(blocked, initial, window=16, start_block=17, poll_every=8)
        for slot in out1:
            # extra samples can reorder within the matching set; the SET
            # (hence recall against any ground truth) must be unchanged
            assert sorted(out1[slot].tolist()) == sorted(out8[slot].tolist()), slot
        assert s8.blocks_read >= s1.blocks_read  # staleness never reads less
        # per-window poll cadence: ~1 sync per round vs ~1 per 8 rounds
        assert s1.host_syncs >= s1.rounds
        assert s8.host_syncs < s1.host_syncs / 2


class TestBlockSourceContract:
    def test_fetch_pads_and_masks(self, dataset):
        _, _, blocked = dataset
        src = InMemorySource(blocked)
        wd = src.fetch(np.array([3, 7, 11]), pad_to=8)
        assert wd.z.shape == (8, blocked.block_size)
        np.testing.assert_array_equal(np.asarray(wd.valid), [True] * 3 + [False] * 5)
        np.testing.assert_array_equal(np.asarray(wd.indices[:3]), [3, 7, 11])
        np.testing.assert_array_equal(np.asarray(wd.z[1]), blocked.z_blocks[7])
        np.testing.assert_array_equal(np.asarray(wd.bitmap[2]), blocked.bitmap[11])

    def test_host_and_device_resident_agree(self, dataset):
        _, _, blocked = dataset
        dev = InMemorySource(blocked).fetch(np.arange(5), pad_to=6)
        host = InMemorySource(blocked, device_resident=False).fetch(np.arange(5), pad_to=6)
        for a, b in zip(dev, host):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("v_z,words", [(161, 6), (7548, 236)])
    def test_lane_padded_fetch_matches_host_resident(self, v_z, words):
        """The device table's bitmap is lane-padded (6 -> 128, 236 -> 256
        words); the window it serves is not."""
        rng = np.random.default_rng(v_z)
        n = 37 * 64 - 5  # a ragged last block
        blocked = block_layout(
            rng.integers(0, v_z, n), rng.integers(0, 24, n), v_z=v_z, v_x=24,
            block_size=64, seed=v_z,
        )
        assert blocked.bitmap.shape[1] == words
        win = np.array([36, 0, 17, 5, 2])
        dev = InMemorySource(blocked).fetch(win, pad_to=8)
        host = InMemorySource(blocked, device_resident=False).fetch(win, pad_to=8)
        assert dev.bitmap.shape == (8, words)
        for a, b in zip(dev, host):
            assert a.shape == b.shape and a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a), b)

    def test_gathers_counts_device_fetches(self, dataset):
        _, _, blocked = dataset
        src = InMemorySource(blocked)
        assert src.gathers == 0
        for i, win in enumerate([np.arange(3), np.arange(4, 9), np.array([2])], 1):
            src.fetch(win, pad_to=8)
            assert src.gathers == i
        list(src.stream([np.arange(2), np.arange(2, 4)], pad_to=8))
        assert src.gathers == 5
        host = InMemorySource(blocked, device_resident=False)
        host.fetch(np.arange(3), pad_to=8)
        assert host.gathers == 0

    def test_gathers_exact_under_concurrent_fetches(self, dataset):
        """Prefetch workers fetch beside the caller: no lost update."""
        _, _, blocked = dataset
        src = InMemorySource(blocked)
        src.fetch(np.arange(4), pad_to=8)  # compile outside the race
        per, workers = 50, 2 * (os.cpu_count() or 1) + 2
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            threads = [
                threading.Thread(
                    target=lambda: [src.fetch(np.arange(4), pad_to=8) for _ in range(per)]
                )
                for _ in range(workers)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
        finally:
            sys.setswitchinterval(interval)
        assert not any(t.is_alive() for t in threads)
        assert src.gathers == 1 + per * workers

    def test_cache_config_hash_ignores_residency(self, dataset):
        """Warm-restart snapshots hash the probe's W words, not the
        device table's lane padding."""
        spec, _, blocked = dataset
        mspec = mq.MultiQuerySpec(v_z=spec.v_z, v_x=spec.v_x, max_queries=2, k_cap=K)
        dev = mq.cache_config_hash(InMemorySource(blocked), mspec)
        host = mq.cache_config_hash(InMemorySource(blocked, device_resident=False), mspec)
        assert dev == host

    def test_sharded_source_speaks_global_ids(self, dataset):
        _, _, blocked = dataset
        num_shards = 4
        shards = [ShardedSource(blocked, num_shards, i) for i in range(num_shards)]
        assert sum(s.num_blocks for s in shards) == blocked.num_blocks
        # contiguous, disjoint, covering ranges
        assert shards[0].lo == 0 and shards[-1].hi == blocked.num_blocks
        for a, b in zip(shards, shards[1:]):
            assert a.hi == b.lo
        s1 = shards[1]
        gids = np.arange(s1.lo, min(s1.lo + 3, s1.hi))
        wd = s1.fetch(gids, pad_to=4)
        np.testing.assert_array_equal(np.asarray(wd.indices[:3]), gids)
        np.testing.assert_array_equal(np.asarray(wd.z[0]), blocked.z_blocks[gids[0]])
        with pytest.raises(ValueError):
            s1.fetch(np.array([s1.hi]))  # out of range
        win = np.array([0, s1.lo, s1.hi - 1, blocked.num_blocks - 1])
        np.testing.assert_array_equal(s1.owned(win), [s1.lo, s1.hi - 1])

    def test_scheduler_rejects_sharded_source(self, dataset):
        """Global-id shard feeds belong to the distributed round; the
        0-based scheduler must refuse them instead of crashing mid-pass."""
        _, _, blocked = dataset
        src = ShardedSource(blocked, 2, 1)
        spec = mq.MultiQuerySpec(v_z=blocked.v_z, v_x=blocked.v_x, max_queries=1)
        with pytest.raises(ValueError, match="0-based"):
            mq.SharedCountsScheduler(src, spec)

    def test_prefetch_stream_matches_plain_stream(self, dataset):
        _, _, blocked = dataset
        inner = InMemorySource(blocked, device_resident=False)
        windows = [np.arange(i, i + 4) for i in range(0, 32, 4)]
        plain = list(inner.stream(windows, pad_to=4))
        pre = list(PrefetchSource(inner).stream(windows, pad_to=4))
        assert len(plain) == len(pre)
        for a, b in zip(plain, pre):
            for fa, fb in zip(a, b):
                np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb))

    def test_prefetch_abandoned_stream_cleans_up(self, dataset):
        """Closing the generator mid-stream (a retirement ends the pass
        early) must not hang or leak the worker thread."""
        import threading

        _, _, blocked = dataset
        src = PrefetchSource(InMemorySource(blocked), depth=1)
        windows = [np.arange(i, i + 2) for i in range(0, 40, 2)]
        before = threading.active_count()
        g = src.stream(windows, pad_to=2)
        next(g)
        g.close()
        assert threading.active_count() <= before + 1  # worker gone (or dying)

    def test_prefetch_propagates_fetch_errors(self, dataset):
        # host-resident arrays: an out-of-bounds window raises in the
        # worker thread and must surface in the consumer
        _, _, blocked = dataset
        src = PrefetchSource(InMemorySource(blocked, device_resident=False))
        windows = [np.arange(2), np.array([blocked.num_blocks + 5])]  # 2nd is OOB
        with pytest.raises(IndexError):
            list(src.stream(windows, pad_to=2))

    def test_prefetch_error_after_close_is_logged_not_lost(self, dataset, caplog):
        """A worker exception racing the generator's close has nowhere to
        re-raise — it must be logged, never silently dropped."""
        import logging
        import threading

        _, _, blocked = dataset
        release = threading.Event()
        inner = InMemorySource(blocked, device_resident=False)

        class FailsAfterClose:
            num_blocks = inner.num_blocks
            block_size = inner.block_size
            v_z = inner.v_z
            v_x = inner.v_x
            tuples_per_block = inner.tuples_per_block

            def fetch(self, win, pad_to=None):
                if len(win) == 1:  # the second (sentinel) window
                    release.wait(5)  # don't fail until the consumer closed
                    raise RuntimeError("backend fell over")
                return inner.fetch(win, pad_to)

            def stream(self, windows, pad_to=None):
                for w in windows:
                    yield self.fetch(w, pad_to)

        src = PrefetchSource(FailsAfterClose(), depth=1)
        g = src.stream([np.arange(2), np.array([0])], pad_to=2)
        next(g)
        with caplog.at_level(logging.WARNING, logger="repro.io.prefetch"):
            release.set()
            g.close()
        assert any("prefetch worker failed" in r.message for r in caplog.records)

    def test_prefetch_join_timeout_warns(self, dataset, caplog):
        """A worker stuck in a slow inner.fetch outlives the closing
        join; that must produce a warning, not a silent abandon."""
        import logging
        import threading

        _, _, blocked = dataset
        hang = threading.Event()
        inner = InMemorySource(blocked, device_resident=False)

        class SlowSource:
            num_blocks = inner.num_blocks
            block_size = inner.block_size
            v_z = inner.v_z
            v_x = inner.v_x
            tuples_per_block = inner.tuples_per_block

            def fetch(self, win, pad_to=None):
                if len(win) == 1:
                    hang.wait(5)  # longer than join_timeout below
                return inner.fetch(win, pad_to)

            def stream(self, windows, pad_to=None):
                for w in windows:
                    yield self.fetch(w, pad_to)

        src = PrefetchSource(SlowSource(), depth=1, join_timeout=0.2)
        g = src.stream([np.arange(2), np.array([0])], pad_to=2)
        next(g)
        with caplog.at_level(logging.WARNING, logger="repro.io.prefetch"):
            g.close()
        hang.set()  # let the worker finish and exit
        assert any("still running" in r.message for r in caplog.records)
