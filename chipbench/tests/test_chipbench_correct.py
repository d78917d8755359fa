"""The whole of a run short of the chip: sound runs are correct; the control
and each fault planted in the timed path come out not correct."""

import dataclasses
import functools

import numpy as np
import pytest

from chipbench import harness, spec
from chipbench.load import clock
from repro.core import multiquery as mq

# name -> (the cell whose mix and metrics it takes, its configuration, changes
# to that configuration). The last two are query shapes that no cell has yet,
# the paper's FLIGHTS-q2 (rare top-k: the close set at the Zipf tail) and
# FLIGHTS-q4 (a uniform target and a continuum of close distances). At these
# sizes their sessions read the whole table and answer exactly; the close rows
# still hold more tuples than bfloat16 counts exactly, so the control fails.
SMALL = {
    "taxi_q1.cold8": ("taxi_q1.cold8", "taxi_q1", dict(v_z=300, num_tuples=512 * 256)),
    "flights_q1.warm_open": ("flights_q1.warm_open", "flights_q1", dict(num_tuples=512 * 512)),
    "flights_q2_shape.cold8": ("taxi_q1.cold8", "flights_q1",
                               dict(close_rank="tail", zipf_a=1.2, num_tuples=512 * 4096)),
    "flights_q4_shape.cold8": ("taxi_q1.cold8", "flights_q1",
                               dict(target_kind="uniform", k=5, n_close=40, close_distance=0.16,
                                    num_tuples=512 * 512)),
}


def small_cell(name):
    """The cell at a small size, from its configuration and mix files."""
    cell, config, changes = SMALL[name]
    found = spec.find_cell(cell)
    return spec.Cell(
        name=name, chips=1,
        config=dict(spec.load_json(spec.HERE / "configs" / f"{config}.json"), **changes),
        mix=found.mix, end_to_end=found.end_to_end, per_layer=[], readers={},
    )


def run(name, seed=2**31 + 99, **kw):
    return harness.run_cell(small_cell(name), seed=seed, seconds=1.0, trace=False,
                            t_process=clock(), log=lambda m: None, **kw)


@pytest.mark.parametrize("name", sorted(SMALL))
def test_sound_run_is_correct_and_the_control_is_not(name):
    res = run(name, control=True)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {m["name"] for m in small_cell(name).end_to_end}
    assert "setup_s" in res["metrics"] and len(res["metrics"]) >= 2
    assert res["checks"]["count_err"]["value"] == 0.0
    assert not res["control_correct"]
    assert res["control_checks"]["count_err"]["value"] > 0


def _unchanged_state(orig, state, cursor, wd, **kw):
    """A round that advances the read bookkeeping but returns its state unchanged."""
    _, cursor = orig(state, cursor, wd, **kw)
    return state, cursor


def _half_batch(orig, state, cursor, wd, **kw):
    """A round that ingests half of the window's blocks and books all of them read."""
    half = wd.z.shape[0] // 2
    new, _ = orig(state, cursor, wd._replace(z=wd.z.at[half:].set(-1)), **kw)
    _, cursor = orig(state, cursor, wd, **kw)
    return new, cursor


def _altered_answer(orig, self, slot, **kw):
    """Retirement that swaps the last returned id for the farthest candidate."""
    out = orig(self, slot, **kw)
    far = int(np.argmax(self._tel_tau[slot]))
    ids = np.asarray(out.ids).copy()
    if far not in ids:
        ids[-1] = far
    self.outcomes[out.qid] = dataclasses.replace(out, ids=ids)
    return self.outcomes[out.qid]


def _plant(monkeypatch, fault):
    if fault == "altered_answer":
        orig = mq.SharedCountsScheduler.retire
        monkeypatch.setattr(mq.SharedCountsScheduler, "retire",
                            lambda self, slot, **kw: _altered_answer(orig, self, slot, **kw))
    else:
        planted = {"unchanged_state": _unchanged_state, "half_batch": _half_batch}[fault]
        monkeypatch.setattr(mq, "fused_round", functools.partial(planted, mq.fused_round))


@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch", "altered_answer"])
def test_faults_in_the_timed_path_are_not_correct(monkeypatch, fault):
    _plant(monkeypatch, fault)
    res = run("taxi_q1.cold8")
    assert not res["correct"], res["checks"]
    assert res["failed"] > 0


@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch", "altered_answer"])
def test_faults_under_the_open_loop_are_not_correct(monkeypatch, fault):
    _plant(monkeypatch, fault)
    res = run("flights_q1.warm_open")
    assert not res["correct"], res["checks"]
