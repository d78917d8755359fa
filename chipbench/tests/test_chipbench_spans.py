"""Program spans in the trace reduction (`chipbench.spans`)."""

import copy
import json
import pathlib

import pytest

from chipbench import harness, spans, spec
from chipbench import trace as tr

ROOT = pathlib.Path(__file__).resolve().parents[2]
RECORDED = pathlib.Path(__file__).parent / "fixtures" / "v5e_taxi_trace_cut.json"
PER_LAYER = [m["name"] for m in json.loads((ROOT / "BENCHMARK.json").read_text())["per_layer"]]
MS = 1_000_000  # ns


def _recorded():
    events = json.loads(RECORDED.read_text())
    _, t0, dur = events["host"][0]
    return events, t0, t0 + dur


def _with_program_spans(events):
    """The recorded events in the form `spans.extract` gives, plus
    hand-made program spans in the second `chipbench.step`: on the
    window's thread an admission holding its poll, a retirement and a
    collect; on a second thread a prefetch worker's fetch across the
    whole step, which must take no idle time."""
    out = copy.deepcopy(events)
    out["host"] = [e + [0, {}] for e in out["host"]]
    s = next(e[1] for e in out["host"] if e[0] == "chipbench.step" and e[1] > out["host"][0][1])
    out["host"] += [
        ["fastmatch.admit", s + MS // 10, 3 * MS, 0, {"qid": 3}],
        ["fastmatch.sync", s + MS, MS, 0, {}],
        ["fastmatch.retire", s + 4 * MS, 2 * MS, 0, {"qid": 3}],
        ["fastmatch.collect", s + 21 * MS + MS // 2, 3 * MS, 0, {}],
        ["fastmatch.fetch", s, 25 * MS, 1, {}],
    ]
    return out


def _idle_ns(t, a, b):
    """Device 0's idle time in [a, b), from its busy intervals."""
    return (b - a) - sum(max(0, min(e, b) - max(s, a)) for s, e in t._busy("0"))


@pytest.mark.parametrize("name", PER_LAYER)
def test_every_reader_reads_the_same_with_program_spans(name):
    events, t0, t1 = _recorded()
    cell = spec.find_cell("taxi_q1.cold8")
    read = spec.load_reader(name)

    def view(trace):
        return harness.RunView(
            cell.config, cell.mix, "TPU v5 lite", answers=8, lags_s=[0.001, 0.004],
            counters={"tuples_read": 17_769_600, "host_syncs": 77}, trace=trace,
            latencies_s=[1.79, 1.81, 1.93],
        )

    before = read(view(tr.Trace(events, t0, t1)))
    assert before is not None
    assert read(view(spans.SpanTrace(_with_program_spans(events), t0, t1))) == before
    assert read(view(spans.SpanTrace(events, t0, t1))) == before


def test_span_time_subtracts_children_on_the_same_thread_only():
    host = [
        ["chipbench.window", 0, 1000, 0, {}],
        ["fastmatch.admit", 100, 500, 0, {"qid": 1}],
        ["fastmatch.sync", 200, 200, 0, {}],
        ["fastmatch.admit", 700, 100, 0, {"qid": 2}],
        ["fastmatch.fetch", 250, 300, 1, {}],  # another thread, inside admit's time
    ]
    t = spans.SpanTrace({"ops": {}, "modules": {}, "host": host}, 0, 1000)
    assert t.span_time("fastmatch.admit") == (pytest.approx((300 + 100) / 1e9), 2)
    assert t.span_time("fastmatch.sync") == (pytest.approx(200 / 1e9), 1)
    assert t.span_time("fastmatch.fetch") == (pytest.approx(300 / 1e9), 1)
    assert t.span_time("chipbench.window") == (pytest.approx(400 / 1e9), 1)
    assert t.span_time("fastmatch.retire") == (0.0, 0)


def test_idle_under_program_spans_is_relabelled_and_the_total_kept():
    events, t0, t1 = _recorded()
    plain = spans.SpanTrace(events, t0, t1)
    t = spans.SpanTrace(_with_program_spans(events), t0, t1)
    old, new = dict(plain.idle_gaps(10)), dict(t.idle_gaps(10))
    assert sum(new.values()) == pytest.approx(t.window_s - t.busy_s)
    assert sum(new.values()) == pytest.approx(sum(old.values()))
    # the worker thread's fetch spans the whole step and takes nothing
    assert "fastmatch.fetch" not in new
    program = {k: v for k, v in new.items() if k.startswith("fastmatch.")}
    assert set(program) == {"fastmatch.admit", "fastmatch.sync", "fastmatch.retire",
                            "fastmatch.collect"}
    assert old["chipbench.step"] - new["chipbench.step"] == pytest.approx(sum(program.values()))
    assert new["chipbench.window"] == pytest.approx(old["chipbench.window"])
    # each span is charged the idle time it covers, less its children's
    by_name = {e[0]: e for e in _with_program_spans(events)["host"][-5:]}

    def idle(name):
        _, s, d, _, _ = by_name[name]
        return _idle_ns(t, s, s + d) / 1e9

    assert new["fastmatch.sync"] == pytest.approx(idle("fastmatch.sync"))
    assert new["fastmatch.admit"] == pytest.approx(idle("fastmatch.admit") - idle("fastmatch.sync"))
    assert new["fastmatch.retire"] == pytest.approx(idle("fastmatch.retire"))
    assert new["fastmatch.collect"] == pytest.approx(idle("fastmatch.collect"))
    assert min(program.values()) > 0


def test_a_gap_is_split_over_the_spans_it_crosses():
    events = {
        "ops": {"0": [["fusion", 0, 100], ["fusion", 900, 100]],
                "1": [["copy", 0, 1000]]},
        "modules": {},
        "host": [["chipbench.window", 0, 1000, 0, {}], ["chipbench.step", 50, 900, 0, {}],
                 ["fastmatch.sync", 80, 220, 0, {}], ["fastmatch.retire", 300, 200, 0, {}],
                 ["fastmatch.fetch", 100, 800, 1, {}]],
    }
    # device 0 is idle over [100, 900): sync [100, 300), retire [300, 500),
    # the step's own time [500, 900); averaged over the two devices
    gaps = dict(spans.SpanTrace(events, 0, 1000).idle_gaps())
    assert gaps == {"chipbench.step": pytest.approx(400e-9 / 2),
                    "fastmatch.sync": pytest.approx(200e-9 / 2),
                    "fastmatch.retire": pytest.approx(200e-9 / 2)}
    # without the program's spans the gap is the step's, as `trace.Trace` has it
    plain = [e[:3] for e in events["host"] if e[0].startswith("chipbench.")]
    for t in (tr.Trace, spans.SpanTrace):
        assert dict(t(dict(events, host=plain), 0, 1000).idle_gaps()) == {
            "chipbench.step": pytest.approx(800e-9 / 2)}


def test_events_without_a_thread_field_still_load():
    events, t0, t1 = _recorded()
    assert all(len(e) == 3 for e in events["host"])
    t = spans.SpanTrace(events, t0, t1)
    assert sum(v for _, v in t.idle_gaps(10)) == pytest.approx(t.window_s - t.busy_s)
    assert dict(t.idle_gaps(10)).keys() == dict(tr.Trace(events, t0, t1).idle_gaps(10)).keys()
    # the two steps that lie inside the window, and the one that starts before it
    assert t.span_time("chipbench.step")[1] == 3
