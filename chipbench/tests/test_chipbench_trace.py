"""The reduction from trace events to the readers' numbers."""

import json
import pathlib

import pytest

from chipbench import trace as tr

# Two sampling rounds of a `taxi_q1.cold8` trace recorded on a TPU v5e, as
# `extract` keeps them (op names cut to their base names), with the
# window span narrowed to the cut.
RECORDED = pathlib.Path(__file__).parent / "fixtures" / "v5e_taxi_trace_cut.json"

# A hand-made trace, in ns: two devices' ops, one program, three host spans.
HAND = {
    "ops": {
        "0": [["histogram_with_rowsums.3", 100, 300], ["fusion.1", 350, 100],
              ["distance_multi.7", 800, 100], ["histogram_with_rowsums.3", 1500, 200]],
        "1": [["copy.2", 0, 2000]],
    },
    "modules": {"0": [["jit_fused_round(41)", 100, 400], ["jit_fused_round(41)", 1500, 200]],
                "1": []},
    "host": [["chipbench.window", 0, 2000], ["chipbench.step", 450, 400],
             ["chipbench.wait", 1000, 500]],
}


def test_hand_trace_reduces_to_hand_numbers():
    t = tr.Trace(HAND, 0, 2000)
    assert t.window_s == pytest.approx(2e-6)
    # device 0 busy [100,450) [800,900) [1500,1700) = 650 ns; device 1 all 2000 ns
    assert t.busy_s == pytest.approx((650 + 2000) / 2 / 1e9)
    assert t.op_time("histogram_with_rowsums") == (pytest.approx(500e-9), 2)
    assert t.op_time("distance_multi") == (pytest.approx(100e-9), 1)
    assert t.op_time("anyactive") == (0.0, 0)
    assert t.module_time("jit_fused_round") == (pytest.approx(600e-9), 2)
    assert t.top_ops(2) == [["copy", pytest.approx(2e-6)],
                            ["histogram_with_rowsums", pytest.approx(5e-7)]]
    # device 0 idle: [0,100) window, [450,800) step, [900,1500) wait, [1700,2000)
    # window; averaged over the two devices
    gaps = dict(tr.Trace(HAND, 0, 2000).idle_gaps())
    assert gaps == {"chipbench.wait": pytest.approx(600e-9 / 2),
                    "chipbench.window": pytest.approx(400e-9 / 2),
                    "chipbench.step": pytest.approx(350e-9 / 2)}


def test_window_clips_events():
    t = tr.Trace(HAND, 400, 1600)
    # device 0 busy [400,450) [800,900) [1500,1600) = 250; device 1 1200
    assert t.busy_s == pytest.approx((250 + 1200) / 2 / 1e9)


def test_recorded_trace_reduces_to_its_own_sums():
    events = json.loads(RECORDED.read_text())
    _, t0, dur = events["host"][0]
    t = tr.Trace(events, t0, t0 + dur)
    ops = events["ops"]["0"]

    def total(name):
        return sum(d for n, _, d in ops if n == name) / 1e9

    # one ingest and one distance kernel in each of the two rounds
    assert t.op_time("histogram_with_rowsums") == (pytest.approx(total("histogram_with_rowsums")), 2)
    assert t.op_time("distance_multi") == (pytest.approx(total("distance_multi")), 2)
    assert t.op_time("anyactive")[1] == 2
    assert t.module_time("jit_fused_round") == (pytest.approx(0.021917551), 2)
    assert t.window_s == pytest.approx(0.052367634)
    assert t.busy_s == pytest.approx(0.03104187)
    # the `cond` that holds each round's ingest is charged only its own
    # time, so the kernel, and not its wrapper, leads
    top = dict(t.top_ops(10))
    assert list(top)[:2] == ["histogram_with_rowsums", "copy"]
    assert top.get("cond", 0.0) < total("cond") - total("histogram_with_rowsums") + 1e-9
    # every idle nanosecond is charged to some host span
    assert sum(v for _, v in t.idle_gaps(10)) == pytest.approx(t.window_s - t.busy_s)
    assert dict(t.idle_gaps(10))["chipbench.step"] == pytest.approx(0.017844223)


def test_base_name():
    assert tr.base_name("histogram_with_rowsums.12") == "histogram_with_rowsums"
    assert tr.base_name('%histogram_with_rowsums.1 = f32[7680,24]{1,0:T(8,128)} custom-call('
                        's32[1024,128]{1,0} %a), custom_call_target="tpu_custom_call"'
                        ) == "histogram_with_rowsums"
    assert tr.base_name("%cond.24 = (f32[7680,24]) conditional(pred[] %p)") == "cond"
    assert tr.base_name("jit_fused_round(8812)") == "jit_fused_round"
    assert tr.base_name("fusion") == "fusion"
