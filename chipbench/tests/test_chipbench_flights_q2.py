"""The FLIGHTS-q2 cell short of the chip: its own mix, metrics and configuration
file, cut only in the number of tuples. A sound run is correct against the
float64 reference; the bfloat16 control is not, since the close rows still
hold more tuples than bfloat16 counts exactly."""

from chipbench import harness, spec
from chipbench.load import clock

CELL = "flights_q2.cold8"


def small_cell():
    found = spec.find_cell(CELL)
    config = dict(spec.load_json(spec.HERE / "configs" / "flights_q2.json"),
                  num_tuples=512 * 4096)
    return spec.Cell(name=CELL, chips=1, config=config, mix=found.mix,
                     end_to_end=found.end_to_end, per_layer=[], readers={})


def test_the_configuration_is_the_rare_top_k_shape():
    config = spec.find_cell(CELL).config
    assert config["close_rank"] == "tail" and config["zipf_a"] == 1.2
    assert config["reduced"] == [] and config["num_tuples"] == 600_000_000


def test_sound_run_is_correct_and_the_control_is_not():
    cell = small_cell()
    res = harness.run_cell(cell, seed=2**31 + 99, seconds=1.0, trace=False,
                           t_process=clock(), log=lambda m: None, control=True)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert {"setup_s", "answer_p50_ms", "answers_per_s"} <= set(res["metrics"])
    assert res["checks"]["count_err"]["value"] == 0.0
    assert not res["control_correct"]
    assert res["control_checks"]["count_err"]["value"] > 0
