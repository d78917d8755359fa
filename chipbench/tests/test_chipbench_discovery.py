"""The harness finds a cell's parts by name, from files alone; peaks and rooflines."""

import json
import os
import pathlib
import subprocess
import sys

import pytest

from chipbench import harness, roofline, spec

ROOT = pathlib.Path(__file__).resolve().parents[2]


def _write(path: pathlib.Path, text: str) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(text)


def test_new_config_mix_and_metric_are_found_from_files(tmp_path):
    base = tmp_path / "bench"
    _write(base / "configs" / "toy.json", json.dumps({"v_z": 3, "v_x": 2}))
    _write(base / "traffic" / "burst.json", json.dumps({"loop": "open", "batch": 4}))
    _write(base / "metrics" / "answers_seen.py", "def read(run):\n    return run.answers\n")
    _write(base / "metrics" / "other_cell.py", "def read(run):\n    return 1.0\n")
    bench = {
        "workloads": [{"name": "toy.burst", "config": "toy", "traffic": "burst", "chips": 1}],
        "end_to_end": [{"name": "setup_s", "unit": "s"},
                       {"name": "only_elsewhere", "unit": "s", "workloads": ["x.y"]}],
        "per_layer": [{"name": "answers_seen", "unit": "answers"},
                      {"name": "other_cell", "unit": "x", "workloads": ["x.y"]}],
    }
    _write(tmp_path / "BENCHMARK.json", json.dumps(bench))
    cell = spec.find_cell("toy.burst", bench_path=tmp_path / "BENCHMARK.json", base=base)
    assert cell.config == {"v_z": 3, "v_x": 2}
    assert cell.mix == {"loop": "open", "batch": 4}
    assert [m["name"] for m in cell.end_to_end] == ["setup_s"]
    assert list(cell.readers) == ["answers_seen"]
    assert cell.readers["answers_seen"](type("Run", (), {"answers": 7})()) == 7
    with pytest.raises(KeyError, match="no workload"):
        spec.find_cell("toy.none", bench_path=tmp_path / "BENCHMARK.json", base=base)


def test_every_cell_of_the_benchmark_resolves():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = spec.find_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert set(cell.readers) == {m["name"] for m in cell.per_layer}
    for c in bench["configs"]:
        assert json.loads((ROOT / c["file"]).read_text())["name"] == c["name"]


def test_roofline_share_and_unknown_device():
    bw = roofline.peak("TPU v5 lite", "hbm_bytes_per_s")
    assert bw == 819e9
    # 819 MB in 2 ms is half of the 1 ms the chip needs at its peak bandwidth
    assert roofline.share_pct(device_kind="TPU v5 lite", seconds=2e-3,
                              bytes_moved=819e6) == pytest.approx(50.0)
    # compute-bound work is read against the FLOP peak
    assert roofline.share_pct(device_kind="TPU v5 lite", seconds=1.0, bytes_moved=0.0,
                              flops=197e12) == pytest.approx(100.0)
    assert roofline.share_pct(device_kind="TPU v5 lite", seconds=0.0, bytes_moved=1.0) is None
    with pytest.raises(KeyError, match="no peaks"):
        roofline.share_pct(device_kind="TPU v99", seconds=1.0, bytes_moved=1.0)


@pytest.mark.parametrize("name", ["ingest_roofline", "distance_roofline"])
def test_roofline_work_is_counted_from_the_algorithm(name):
    mod = spec.load_reader(name).__globals__
    if name == "ingest_roofline":
        # 8 bytes a tuple, plus the counts delta and row sums per call
        assert mod["work_bytes"](1000, 2, 10, 3) == 8000 + 2 * 4 * 10 * 4
    else:
        # counts, targets and distances in float32, per call
        assert mod["work_bytes"](3, 10, 4, 2) == 3 * 4 * (40 + 8 + 20)


@pytest.mark.parametrize("name,want", [("tuples_per_answer", 250.0), ("syncs_per_answer", 1.5),
                                       ("arrival_lag_p95_ms", 9.5)])
def test_counter_and_clock_readers(name, want):
    read = spec.load_reader(name)
    lags = [0.001 * i for i in range(11)]  # 0..10 ms: the 95th percentile is 9.5 ms
    run = harness.RunView({}, {}, "TPU v5 lite", 4, lags, {"tuples_read": 1000, "host_syncs": 6})
    assert read(run) == pytest.approx(want)
    assert read(harness.RunView({}, {}, "TPU v5 lite", 0, [], {"tuples_read": 0, "host_syncs": 0})) is None


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "taxi_q1.cold8",
         "--seed", str(2**31 + 7), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_run_exits_nonzero_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_run_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    import shutil

    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = _run(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "No module named 'repro'" in p.stderr
